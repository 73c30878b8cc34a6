"""mine.collective_ms: device time of the collective operations per mine
in the traced window, in ms (mean over the cell's chips): the
AND-allreduce between object parts (all-to-all and all-gather under
``rsag``) and the psum of the supports (all-reduce).

A run on a mesh in which no collective ran is broken, and reads None.
"""

COLLECTIVES = (
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)"
    r"(-start|-done)? "
)


def read(run):
    if run.device is None or not run.units:
        return None
    s = run.device.op_seconds(COLLECTIVES)
    return None if s is None else s / len(run.units) * 1e3

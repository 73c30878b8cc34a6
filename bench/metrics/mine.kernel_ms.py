"""mine.kernel_ms: device time of the fused Pallas frontier kernels per
mine in the traced window, in ms (mean over the cell's chips).

A mining run launches no Pallas kernel but the frontier steps of
``repro.kernels.frontier``, and the trace names a Pallas call only by its
target (``tpu_custom_call``): the kernels carry no name of their own yet.
"""

KERNELS = r"^tpu_custom_call "


def read(run):
    if run.device is None or not run.units:
        return None
    s = run.device.op_seconds(KERNELS)
    return None if s is None else s / len(run.units) * 1e3

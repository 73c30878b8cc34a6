"""idle_share.serve: the share of the traced window in which no operation
ran on the device (mean over the cell's chips), in %."""


def read(run):
    if run.device is None or not run.window_s:
        return None
    return 100.0 * (1.0 - run.device.busy_s / run.window_s)

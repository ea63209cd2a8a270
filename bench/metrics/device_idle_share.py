"""device_idle_share (device): the share of the profiled window in which
no operation ran on the chip, in percent (device trace)."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * (1.0 - run.device.busy_s() / run.device.window_s)

"""device.step_us_per_kevent: time in which an op ran on a TPU in the
traced sub-window (profiler trace), summed over the cell's chips, per
thousand events."""


def read(run):
    tr = run.traced
    if tr is None or not tr.events or not tr.device.devices():
        return None
    return tr.device.busy_total_ns() / 1e3 / (tr.events / 1e3)

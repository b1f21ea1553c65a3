"""device.idle_pct: share of the traced sub-window in which no op ran
on the device (profiler trace), averaged over the cell's chips."""


def read(run):
    tr = run.traced
    if tr is None or not tr.device.devices() or not tr.device.window_ns:
        return None
    return 100.0 * (1.0 - tr.device.mean_busy_ns() / tr.device.window_ns)

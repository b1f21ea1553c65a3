"""device.idle_in_output_pct: share of the traced sub-window in which no
op ran on the device while the host was inside one of the program's
``d2h/`` or ``decode/`` spans (profiler trace and spans on one clock),
averaged over the cell's chips. A program that opens no such span gives
nothing to read."""
from harness import xtrace

OUTPUT = ("d2h/", "decode/")


def overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    tr = run.traced
    if tr is None:
        return None
    dev = tr.device
    devs = dev.devices()
    host = xtrace.merge((s, e) for name, s, e, _ in tr.spans
                        if name.startswith(OUTPUT))
    if not devs or not dev.window_ns or not host:
        return None
    idle = sum(overlap_ns(xtrace.gaps(xtrace.merge(
        (s, e) for _, s, e in dev.ops[d]), dev.lo, dev.hi), host)
        for d in devs)
    return 100.0 * idle / len(devs) / dev.window_ns

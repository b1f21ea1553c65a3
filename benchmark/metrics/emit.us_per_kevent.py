"""emit.us_per_kevent: self time of the program's ``step/*`` and
``partition/*`` spans (dispatch, the host's wait for the device, D2H and
row decode; the benchmark's callback span taken out) in the traced
sub-window, per thousand events. Programs whose path opens no such span
(the pattern path) give nothing to read."""


def read(run):
    tr = run.traced
    if tr is None or not tr.events:
        return None
    if not tr.span_ns_of("step/", "partition/"):
        return None
    return tr.self_ns_of("step/", "partition/") / 1e3 / (tr.events / 1e3)

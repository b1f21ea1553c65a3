"""emit.wait_us_per_kevent: total duration of the program's ``wait/*``
spans (the host blocked until a step's output is ready on the device)
in the traced sub-window, per thousand events. A program that opens no
such span gives nothing to read."""


def read(run):
    tr = run.traced
    if tr is None or not tr.events or \
            not any(sp[0].startswith("wait/") for sp in tr.spans):
        return None
    return tr.span_ns_of("wait/") / 1e3 / (tr.events / 1e3)

"""emit.d2h_us_per_kevent: total duration of the program's ``d2h/*``
spans (the device-to-host copy of a step's output, the host's relayout
included) in the traced sub-window, per thousand events. A program that
opens no such span gives nothing to read."""


def read(run):
    tr = run.traced
    if tr is None or not tr.events or \
            not any(sp[0].startswith("d2h/") for sp in tr.spans):
        return None
    return tr.span_ns_of("d2h/") / 1e3 / (tr.events / 1e3)

"""emit.decode_us_per_kevent: self time of the program's ``decode/*``
spans (a step's host output turned into rows) in the traced sub-window,
per thousand events. A program that opens no such span gives nothing to
read."""


def read(run):
    tr = run.traced
    if tr is None or not tr.events or \
            not any(sp[0].startswith("decode/") for sp in tr.spans):
        return None
    return tr.self_ns_of("decode/") / 1e3 / (tr.events / 1e3)

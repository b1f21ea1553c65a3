"""ingest.us_per_kevent: self time of the program's ``ingest/*`` spans
(less the nested junction/step/partition/callback spans) in the traced
sub-window, per thousand events sent there."""


def read(run):
    tr = run.traced
    if tr is None or not tr.events or not tr.span_ns_of("ingest/"):
        return None
    return tr.self_ns_of("ingest/") / 1e3 / (tr.events / 1e3)

"""nfa.walk_rows_per_event: rows a keyed round of the parallel NFA
engine placed in a walk block (the rows with an entry left in their
band), per event consumed (program counters ``nfa_walk_rows`` /
``nfa_events`` of each query's ``stats()``, read after the window over
the whole run, warm chunks included). A program without the
``nfa_walk_rows`` counter reads nothing."""


def read(run):
    stats = [s for s in run.stats.values() if "nfa_walk_rows" in s]
    events = sum(s.get("nfa_events", 0) for s in stats)
    if not events:
        return None
    return sum(s["nfa_walk_rows"] for s in stats) / events

"""nfa.candidates_per_event: row x event pairs whose condition the
parallel NFA engine's rounds evaluated, per event consumed (program
counters ``nfa_candidates`` / ``nfa_events`` of each query's
``stats()``, read after the window over the whole run, warm chunks
included). A keyed round counts the band entries it walks, a grid round
rows x sub-batch events."""


def read(run):
    events = sum(s.get("nfa_events", 0) for s in run.stats.values())
    if not events:
        return None
    return sum(s.get("nfa_candidates", 0)
               for s in run.stats.values()) / events

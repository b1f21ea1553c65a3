"""nfa.band_iters_per_step: iterations of the parallel NFA engine's band
lookups (the binary search of each row's key inside its bucket of the
sub-batch's sorted event keys), per step (program counters
``nfa_band_iters`` / ``nfa_steps`` of each query's ``stats()``, read
after the window over the whole run, warm chunks included). A program
without the ``nfa_band_iters`` counter reads nothing."""


def read(run):
    stats = [s for s in run.stats.values() if "nfa_band_iters" in s]
    steps = sum(s.get("nfa_steps", 0) for s in stats)
    if not steps:
        return None
    return sum(s["nfa_band_iters"] for s in stats) / steps

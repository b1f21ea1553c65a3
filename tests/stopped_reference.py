"""Plain numpy reference of a keyed pattern chain, independent of the
program: ``every e1=S -> e2=S[key == e1.key] -> ... within W``, where
each later state's condition is the key's equality with e1 and nothing
else, matches each event with the next ``states - 1`` events of its key.

``consecutive_matches`` is a byte-identical copy of the function of the
same name in ``benchmark/configs/linear_road_stopped.py``, the reference
of the Linear Road benchmark cell (tests/test_nfa_parallel.py holds the
two equal).
"""
from __future__ import annotations

import numpy as np


def consecutive_matches(ts, cols, key, states, within_ms, inclusive=True):
    """Every report with the next ``states - 1`` reports of its key (the
    columns named in ``key``, all equal), in arrival order, kept when the
    last lies within ``within_ms`` of the first (at the bound too, where
    ``inclusive``). Returns the (first, last) report indices of each
    match, in order of the last."""
    n = len(ts)
    order = np.lexsort((np.arange(n),) +
                       tuple(np.asarray(cols[k]) for k in reversed(key)))
    s = states - 1
    if n <= s:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    same = np.ones(n - s, bool)
    for k in key:
        c = np.asarray(cols[k])[order]
        same &= c[s:] == c[:-s]
    first, last = order[:-s][same], order[s:][same]
    gap = np.asarray(ts)[last] - np.asarray(ts)[first]
    keep = gap <= within_ms if inclusive else gap < within_ms
    first, last = first[keep], last[keep]
    by_last = np.argsort(last, kind="stable")
    return first[by_last], last[by_last]

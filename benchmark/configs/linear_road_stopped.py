"""Generator, tiny form, plain reference and control of
`linear_road_stopped`: Linear Road's stopped-vehicle detection (Arasu et
al., VLDB 2004) on four expressways at the late-run rate.

Every vehicle sends a position report ``(type, time, vid, spd, xway,
lane, dir, seg, pos)`` every 30 s, exactly, from a whole-second phase. A
moving vehicle's position advances by its speed; 1 vehicle in 256 stops
once a cycle for 4 to 8 reports, repeating one position. The query
matches every four consecutive reports of one vehicle from one position
whose fourth lies within 90 s of the first; with reports exactly 30 s
apart every match lies on that bound. Imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

REPORTS = 20                  # reports per vehicle per cycle
PERIOD_S = 30
STOP_SHARE = 256              # 1 vehicle in this many stops per cycle
MIN_STOPPING = 16             # at least this many, so a cut block stops
STOP_REPORTS = (4, 8)         # reports a stop lasts, fewest and most
SPEED_MPH = (40, 100)
FEET_PER_MPH = 44             # feet covered in one period per mph
ROAD_FEET = 528_000
SEG_FEET = 5_280
XWAYS = 4
KEY = ("vid", "xway", "dir", "lane", "pos")
STATES = 4
WITHIN_MS = 90_000


def generate(rng, n: int):
    """The block of ``n`` reports from ``n / 20`` vehicles a cycle (the
    first ``n`` in time where 20 does not divide it): (time offsets in
    ms, columns), in time order, ties by vid."""
    v = -(-n // REPORTS)
    xway = rng.integers(0, XWAYS, v)
    direction = rng.integers(0, 2, v)
    lane = rng.integers(1, 4, v)
    phase = rng.integers(0, PERIOD_S, v)
    move = rng.integers(SPEED_MPH[0], SPEED_MPH[1] + 1, (v, REPORTS))
    spd = move.copy()
    lo, hi = STOP_REPORTS
    for car in rng.choice(v, max(v // STOP_SHARE, min(MIN_STOPPING, v)),
                          replace=False):
        k = int(rng.integers(lo, hi + 1))
        s = int(rng.integers(0, REPORTS - k + 1))
        spd[car, s:s + k] = 0
        move[car, s + 1:s + k] = 0        # k reports from one position
    move[:, 0] = 0
    travel = FEET_PER_MPH * np.cumsum(move, axis=1)
    span = travel[:, -1]
    # the whole cycle's path stays on the road: forward from below
    # ROAD_FEET - span, backward from at least span
    start = np.where(direction == 0,
                     rng.integers(0, ROAD_FEET - span),
                     rng.integers(span, ROAD_FEET))
    pos = np.where(direction[:, None] == 0, start[:, None] + travel,
                   start[:, None] - travel)
    sec = phase[:, None] + PERIOD_S * np.arange(REPORTS)
    vid = np.broadcast_to(np.arange(v)[:, None], (v, REPORTS))
    order = np.lexsort((vid.ravel(), sec.ravel()))[:n]
    offsets = (sec.ravel()[order] * 1000).astype(np.int64)

    def col(x):
        return np.broadcast_to(x, (v, REPORTS)).ravel()[order] \
            .astype(np.int32)

    return offsets, {
        "type": np.zeros(n, np.int32),
        "time": col(sec),
        "vid": col(vid),
        "spd": col(spd),
        "xway": col(xway[:, None]),
        "lane": col(lane[:, None]),
        "dir": col(direction[:, None]),
        "seg": col(pos // SEG_FEET),
        "pos": col(pos)}


def tiny(config: dict, traffic: dict):
    """256 vehicles (16 of them stop), 1,024-report chunks of 120 s of
    event time, longer than `within`, and a pending table of 2,048."""
    v = 256
    app = config["app"].replace("'1048576'", "'2048'") \
        .replace("'16384'", "'512'")
    return ({**config, "app": app,
             "time": {**config["time"],
                      "cycle_offset": {"vid": v, "time": 600}}},
            {**traffic, "block_events": v * REPORTS, "chunk_rows": 1024,
             "warm_chunks": 2})


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


def _rows(ts, cols, inclusive):
    first, last = consecutive_matches(ts, cols, KEY, STATES, WITHIN_MS,
                                      inclusive)
    pick = [cols[k] for k in ("vid", "xway", "dir", "pos", "time")]
    return [(int(ts[b]), *(int(c[a]) for c in pick), int(cols["time"][b]))
            for a, b in zip(first.tolist(), last.tolist())]


def reference(ts, cols, labels):
    return _rows(ts, cols, inclusive=True)


def control(ts, cols, labels):
    """Breaks `within`'s inclusive bound: the fourth report must come
    less than 90 s after the first. Every match lies on the bound, so
    the control finds none."""
    return _rows(ts, cols, inclusive=False)

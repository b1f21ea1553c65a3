"""Plain reference of `ysb_campaign_window`, the Yahoo Streaming
Benchmark's query: keep events whose event_type is "view", map ad_id to
its campaign (ad i belongs to campaign i // 10, as the configuration's
preload states), and count views per campaign in 10 s windows of
event_time aligned to multiples of 10,000 ms.

A window's rows go out when the first view of a later window arrives:
one per campaign seen in it, in order of the campaign's first view, as
(timestamp of its last view, campaign label, count). The window of the
last view sent is still open and emits nothing. Imports nothing of the
program.
"""
from __future__ import annotations

import numpy as np

WINDOW_MS = 10_000
ADS_PER_CAMPAIGN = 10
CAMPAIGN_LABEL = "campaign-{:03d}"


def window_counts(ts, cols, labels, upper_closed=False):
    view = labels["event_type"].index("view")
    keep = cols["event_type"] == view
    t = cols["event_time"][keep]
    stamp = ts[keep]
    camp = cols["ad_id"][keep] // ADS_PER_CAMPAIGN
    w = (t - 1) // WINDOW_MS if upper_closed else t // WINDOW_MS
    out = []
    if len(w) == 0:
        return out
    bounds = np.flatnonzero(np.diff(w)) + 1
    for lo, hi in zip(np.concatenate([[0], bounds]), bounds):
        c = camp[lo:hi]
        first = np.unique(c, return_index=True)[1]
        counts = np.bincount(c, minlength=c.max() + 1)
        # the last view of a campaign is its latest: time never goes back
        last = np.zeros(c.max() + 1, np.int64)
        np.maximum.at(last, c, stamp[lo:hi])
        for k in c[np.sort(first)]:
            out.append((int(last[k]), CAMPAIGN_LABEL.format(int(k)),
                        int(counts[k])))
    return out


def reference(ts, cols, labels):
    return window_counts(ts, cols, labels)


def control(ts, cols, labels):
    """Breaks the window bound: a view stamped exactly on a boundary is
    counted in the window before it, the off-by-one a faster window
    might make."""
    return window_counts(ts, cols, labels, upper_closed=True)

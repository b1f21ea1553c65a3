"""The trace reducers: on hand-made intervals and spans, and on a small
trace recorded on a v5e (`record_trace.py`: a traced run at 1,024-row
chunks) with the spans of the same run."""
import json
import os

import pytest

from tiny import HERE
from harness import xtrace

DATA = os.path.join(HERE, "data")


def test_merge_and_gaps():
    m = xtrace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert m == [(0, 3), (5, 9), (12, 13)]
    assert xtrace.covered(m) == 8
    assert xtrace.gaps(m, 0, 15) == [(3, 5), (9, 12), (13, 15)]
    assert xtrace.gaps(m, 1, 6) == [(3, 5)]


def test_self_time_subtracts_nested_spans_of_the_same_thread():
    spans = [("ingest/S", 0, 100, 1), ("step/q", 10, 60, 1),
             ("callback", 40, 50, 1), ("junction/Out", 55, 58, 1),
             ("step/q", 70, 90, 1), ("ingest/S", 20, 30, 2)]
    self_ns = xtrace.self_ns(spans)
    assert self_ns["ingest/S"] == (100 - 50 - 20) + 10
    assert self_ns["step/q"] == (50 - 10 - 3) + 20
    assert self_ns["callback"] == 10
    assert self_ns["junction/Out"] == 3


def test_open_span_picks_the_innermost():
    spans = [("ingest/S", 0, 100, 1), ("step/q", 10, 60, 1)]
    assert xtrace.open_span(20, spans) == "step/q"
    assert xtrace.open_span(80, spans) == "ingest/S"
    assert xtrace.open_span(200, spans) == "none"


def test_device_trace_reductions():
    dev = xtrace.DeviceTrace(0, 100, {
        0: [("p/while.1", 10, 40), ("p/sort.2", 20, 35), ("p/fusion.1", 80, 90)],
        1: [("p/fusion.1", 0, 50)]})
    assert dev.busy_ns(0) == 40 and dev.busy_ns(1) == 50
    assert dev.busy_total_ns() == 90
    assert dev.mean_busy_ns() == 45
    # own time: the while less the sort inside it
    assert dict(dev.top_ops(3)) == {"p/fusion.1": 60 / 1e9,
                                    "p/sort.2": 15 / 1e9,
                                    "p/while.1": 15 / 1e9}
    gaps = dev.idle_gaps([("ingest/S", 0, 100, 1), ("step/q", 40, 70, 1)])
    assert gaps[0] == ["ingest/S", 50 / 1e9]
    assert ["step/q", 40 / 1e9] in gaps


FIXTURE = os.path.join(DATA, "trace.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded v5e trace in tests/data")
def test_recorded_v5e_trace():
    dev = xtrace.DeviceTrace.from_file(FIXTURE)
    with open(os.path.join(DATA, "spans.json")) as f:
        rec = json.load(f)
    assert (dev.lo, dev.hi) == (rec["lo"], rec["hi"])
    assert dev.devices() == [0]
    busy = dev.busy_ns(0)
    assert 0 < busy <= dev.window_ns
    top = dev.top_ops(n=10 ** 6)
    assert sum(s for _, s in top) == pytest.approx(busy / 1e9, rel=1e-6)
    assert all(n.startswith("jit_") and "/%" in n for n, _ in top)
    spans = [tuple(s) for s in rec["spans"]]
    gaps = dev.idle_gaps(spans, n=10 ** 6)
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx((dev.window_ns - busy) / 1e9, rel=1e-6)
    # the spans lie on the trace's clock: the chunks' ingest spans fall
    # inside the traced interval
    ingest = [s for s in spans if s[0].startswith("ingest/")]
    assert len(ingest) == rec["chunks"]
    assert all(dev.lo - 1000 <= s <= e <= dev.hi + 1000
               for _, s, e, _ in ingest)
    self_ns = xtrace.self_ns(spans)
    assert sum(self_ns.values()) <= sum(e - s for _, s, e, _ in ingest) \
        + sum(e - s for n, s, e, _ in spans if n == "callback")

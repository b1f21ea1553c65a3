"""The readers of the output path's split (wait, copy, decode, and the
device's idle time inside the copy and the decode): on hand-made spans
and a hand-made device trace, where each value is worked out by hand,
and on a traced tiny run through the harness."""
import os

import pytest

from tiny import run_tiny, spec, tiny_cell
from harness import drive, xtrace

SPLIT = ("emit.wait_us_per_kevent", "emit.d2h_us_per_kevent",
         "emit.decode_us_per_kevent", "device.idle_in_output_pct")

# two threads: query q's step holds its wait, copy and decode; join j's
# wait and decode sit on another thread, the decode holding a callback
SPANS = [
    ("step/q", 0, 1_000_000, 1),
    ("wait/q", 100_000, 400_000, 1),
    ("d2h/q", 400_000, 600_000, 1),
    ("decode/q", 600_000, 900_000, 1),
    ("wait/j", 1_000_000, 1_100_000, 2),
    ("decode/j", 1_200_000, 1_300_000, 2),
    ("callback", 1_250_000, 1_280_000, 2),
]
# device 0 idles from 0.5 to 0.7 ms, device 1 never
DEVICE = xtrace.DeviceTrace(0, 2_000_000, {
    0: [("p/f", 0, 500_000), ("p/g", 700_000, 2_000_000)],
    1: [("p/f", 0, 2_000_000)]})
EVENTS = 2000


def _run(spans, device=DEVICE, events=EVENTS):
    run = drive.Run(None, 1.0, True)
    run.traced = drive.Traced(device, spans, 1, events)
    return run


def _read(name, run):
    return spec.reader(name)(run)


def test_values_by_hand():
    run = _run(SPANS)
    # wait: 300 + 100 us over 2 kevents
    assert _read("emit.wait_us_per_kevent", run) == pytest.approx(200.0)
    # d2h: 200 us over 2 kevents
    assert _read("emit.d2h_us_per_kevent", run) == pytest.approx(100.0)
    # decode self time: 300 + (100 - 30 of callback) us over 2 kevents
    assert _read("emit.decode_us_per_kevent", run) == pytest.approx(185.0)
    # device 0's gap (0.5-0.7 ms) lies inside d2h/q and decode/q: 0.2 ms
    # of 2 ms, device 1 none; the mean over both chips is 5%
    assert _read("device.idle_in_output_pct", run) == pytest.approx(5.0)


def test_idle_outside_output_spans_does_not_count():
    # the output spans moved clear of device 0's gap
    spans = [(n, s + 1_000_000, e + 1_000_000, t) if
             n.startswith(("d2h/", "decode/")) else (n, s, e, t)
             for n, s, e, t in SPANS]
    assert _read("device.idle_in_output_pct", _run(spans)) == 0.0


def test_overlap_of_interval_lists():
    ov = spec._load_module(os.path.join(
        spec.BENCH_DIR, "metrics", "device.idle_in_output_pct.py"))
    assert ov.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert ov.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert ov.overlap_ns([], [(0, 5)]) == 0


@pytest.mark.parametrize("name", SPLIT)
def test_none_where_the_spans_are_absent(name):
    older = [sp for sp in SPANS if sp[0].startswith(("step/", "callback"))]
    assert _read(name, _run(older)) is None
    assert _read(name, drive.Run(None, 1.0, False)) is None


@pytest.mark.parametrize("name", SPLIT)
def test_listed_for_the_cell(name):
    b = spec.benchmark()
    entry, = [m for m in b["per_layer"] if m["name"] == name]
    assert entry["moves"] == "events_per_s"
    assert entry["workloads"] == ["ysb.replay"]


def test_tiny_traced_run_reads_the_split():
    cell = tiny_cell(spec.benchmark()["workloads"][0]["name"])
    run, _ = run_tiny(cell, seconds=1.5, trace=True)
    for name in SPLIT[:3]:
        v = _read(name, run)
        assert v is not None and v >= 0, name
    names = {sp[0] for sp in run.traced.spans}
    # the window query's output becomes rows under its own step span,
    # and the join's dispatch opens a step span of its own
    assert {"wait/counts", "d2h/counts", "decode/counts",
            "step/views"} <= names

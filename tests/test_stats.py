"""Overflow accounting and runtime counters — the "counted, never silent"
contract. Drives windows, the NFA pending table, and the join cap past
their static capacities and asserts the counters move.

The reference's queues are unbounded (e.g. TimeWindowProcessor's
SnapshotableStreamEventQueue); here capacities are static, so overflow
MUST surface in QueryRuntime.stats()/overflow.
"""
import numpy as np

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.types import GLOBAL_STRINGS


def _playback_app(ql):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("@app:playback\n" + ql)
    rt.start()
    return rt


def test_window_overflow_counted():
    rt = _playback_app("""
        define stream S (a int);
        @info(name = 'q')
        from S#window.time(100 sec) select a insert into Out;
    """)
    q = rt.queries["q"]
    # TimeWindowOp cap is 4096; push 6000 live events inside the window
    h = rt.get_input_handler("S")
    ts = 1_000_000 + np.arange(6000, dtype=np.int64)  # all within 100 s
    h.send_arrays(ts, [np.arange(6000, dtype=np.int32)])
    assert q.overflow_total() == 6000 - 4096
    stats = q.stats()
    assert stats["overflow"] == 6000 - 4096
    assert stats["emitted"] == 6000
    rt.shutdown()


def test_nfa_overflow_counted():
    rt = _playback_app("""
        define stream A (v int);
        define stream B (v int);
        @info(name = 'q')
        from every e1=A -> e2=B[v > e1.v]
        select e1.v as first, e2.v as second
        insert into Out;
    """)
    q = rt.queries["q"]
    h = rt.get_input_handler("A")
    # every A event spawns a pending row; parallel-engine table M=4096
    n = 8192
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    h.send_arrays(ts, [np.arange(n, dtype=np.int32)])
    assert q.overflow_total() > 0
    rt.shutdown()


def test_join_overflow_counted():
    rt = _playback_app("""
        define stream L (k int);
        define stream R (k int);
        @info(name = 'q')
        from L#window.length(2000) join R#window.length(2000)
        select L.k as lk, R.k as rk
        insert into Out;
    """)
    q = rt.queries["q"]
    hl = rt.get_input_handler("L")
    hr = rt.get_input_handler("R")
    n = 2000
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    hl.send_arrays(ts, [np.zeros(n, np.int32)])
    # each R event joins 2000 buffered L rows -> n*2000 pairs >> join cap
    hr.send_arrays(ts[:64], [np.zeros(64, np.int32)])
    assert q.overflow > 0
    rt.shutdown()


def test_emitted_counter_row_path():
    """The EventBatch (row) path must count emitted rows too — a
    StreamCallback subscriber forces the non-packed path."""
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
        define stream S (a int);
        @info(name = 'q')
        from S[a > 0] select a insert into Out;
    """)
    got = []
    rt.add_callback("Out", StreamCallback(fn=lambda evs: got.extend(evs)))
    rt.start()
    rt.get_input_handler("S").send([(1,), (-2,), (3,)])
    q = rt.queries["q"]
    assert q.stats()["emitted"] == 2
    assert len(got) == 2
    rt.shutdown()


def test_group_by_key_overflow_counted():
    rt = _playback_app("""
        define stream S (sym string, v long);
        @info(name = 'q')
        from S select sym, sum(v) as total group by sym insert into Out;
    """)
    q = rt.queries["q"]
    h = rt.get_input_handler("S")
    n = 3000  # AggregateOp key capacity is 1024
    codes = np.array([GLOBAL_STRINGS.encode(f"K{i}") for i in range(n)],
                     np.int32)
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    h.send_arrays(ts, [codes, np.ones(n, np.int64)])
    assert q.overflow_total() > 0
    rt.shutdown()


YSB_COUNTS = """
    define stream Views (campaign_id int, event_time long);
    @info(name = 'counts')
    from Views#window.externalTimeBatch(event_time, 10 sec, 0)
    select campaign_id, count() as views
    group by campaign_id
    insert into Out;
"""
# dictionary codes of 100 campaigns; in a 1,024-slot table they probe to
# distances 0-2, as the benchmark's campaign codes do
CAMPAIGN_CODES = np.arange(1200, 1300, dtype=np.int32)


def _send_window_chunks(rt, first, n, rows=1024):
    """n chunks of `rows` views, one 10 s window each: every chunk closes
    the window of the chunk before it."""
    h = rt.get_input_handler("Views")
    rng = np.random.default_rng(first)
    for c in range(first, first + n):
        t = 1_000_000_000 + c * 10_000 + np.arange(rows, dtype=np.int64) * 9
        h.send_arrays(t, [rng.choice(CAMPAIGN_CODES, rows), t])


def test_group_key_probe_rounds_counted():
    rt = _playback_app(YSB_COUNTS)
    q = rt.queries["counts"]
    _send_window_chunks(rt, 0, 2)   # the first window closes: keys insert
    first = q.stats()
    assert first["probe_steps"] == 1
    _send_window_chunks(rt, 2, 4)
    stats = q.stats()
    assert stats["overflow"] == 0
    assert stats["probe_steps"] == 5
    # after the first probing step every key is in the table
    per_step = ((stats["probe_rounds"] - first["probe_rounds"]) /
                (stats["probe_steps"] - first["probe_steps"]))
    assert 1 <= per_step <= 3
    flat = rt.metrics.collect()
    base = f"siddhi.{rt.name}.query.counts"
    assert flat[f"{base}.probe_rounds"] == stats["probe_rounds"]
    assert flat[f"{base}.probe_steps"] == stats["probe_steps"]
    assert flat[f"{base}.overflow"] == 0
    rt.shutdown()


def test_snapshot_without_probe_counters_restores():
    """A snapshot taken before the probe counters existed restores with
    them at 0, and the query keeps its keys and counts on."""
    rt = _playback_app(YSB_COUNTS)
    got = []
    rt.add_callback("Out", StreamCallback(fn=lambda evs: got.extend(evs)))
    q = rt.queries["counts"]
    _send_window_chunks(rt, 0, 2)
    snap = q.snapshot_state()
    snap["states"] = tuple(
        {k: v for k, v in st.items()
         if k not in ("probe_rounds", "probe_steps")}
        if isinstance(st, dict) else st for st in snap["states"])
    q.restore_state(snap)
    assert q.stats()["probe_steps"] == 0
    _send_window_chunks(rt, 2, 1)
    stats = q.stats()
    assert stats["probe_steps"] == 1 and stats["overflow"] == 0
    assert len(got) == 2 * len(set(CAMPAIGN_CODES.tolist()))
    rt.shutdown()

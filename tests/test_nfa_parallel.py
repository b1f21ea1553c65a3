"""Differential tests: the batch-parallel NFA engine (ops/nfa_parallel.py)
must produce EXACTLY the scan engine's outputs (ops/nfa.py) — same rows,
same order — on randomized multi-stream replays, across chunk-size splits.
"""
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.runtime import PatternQueryRuntime
from siddhi_tpu.ops.nfa import NfaEngine
from siddhi_tpu.ops.nfa_parallel import ParallelNfaEngine, \
    parallel_supported


APP = "@app:playback\ndefine stream A (v int, w int);\n" \
      "define stream B (v int, w int);\n@info(name='q')\n"


def run(ql, sends, force_scan=False, expect_parallel=True):
    """sends: list of (stream_id, ts_array, [cols]). Returns output rows."""
    import siddhi_tpu.core.runtime as R
    orig = R.parallel_supported
    if force_scan:
        R.parallel_supported = lambda *a: False
    try:
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(APP + ql)
        q = rt.queries["q"]
        want = NfaEngine if (force_scan or not expect_parallel) \
            else ParallelNfaEngine
        assert type(q.engine) is want, type(q.engine)
        got = []
        from siddhi_tpu import StreamCallback
        rt.add_callback("O", StreamCallback(
            fn=lambda evs: got.extend((e.timestamp, e.data)
                                      for e in evs)))
        rt.start()
        for sid, ts, cols in sends:
            rt.get_input_handler(sid).send_arrays(ts, cols)
        rt.shutdown()
        return got
    finally:
        R.parallel_supported = orig


def gen_sends(seed, n=300, chunk=37):
    """Interleaved A/B chunks with random small ints (collision-heavy)."""
    rng = np.random.default_rng(seed)
    sends = []
    t = 1_000_000
    for i in range(n // chunk):
        sid = "A" if i % 2 == 0 else "B"
        m = chunk
        ts = t + np.arange(m, dtype=np.int64) * 7
        t = int(ts[-1]) + 3
        v = rng.integers(0, 12, m).astype(np.int32)
        w = rng.integers(0, 5, m).astype(np.int32)
        sends.append((sid, ts, [v, w]))
    return sends


QLS = [
    "from e1=A[v > 3] -> e2=B[v > e1.v] within 1 sec "
    "select e1.v as a, e2.v as b insert into O;",
    "from every e1=A[v > 3] -> e2=B[v == e1.v] "
    "select e1.v as a, e2.v as b, e1.w as w insert into O;",
    "from every e1=A[v > 5] -> e2=A[v > e1.v] -> e3=A[w == e1.w] "
    "select e1.v as a, e3.w as w insert into O;",
    # non-every plain sequence: armed-once one-shot starts route to the
    # scan engine (per-round pending lifecycle), so this entry compares
    # scan-vs-scan — kept for replay coverage of the shape
    "from e1=A, e2=A[v > e1.v], e3=A[v > e2.v] "
    "select e1.v as a, e3.v as c insert into O;",
    "from every e1=A[v > 6]<1:3> -> e2=B[v > 8] "
    "select e1[0].v as a, e2.v as b insert into O;",
    "from every e1=A[v > 6]+, e2=B[v > 3] "
    "select e1[0].v as a, e2.v as b insert into O;",
    "from e1=A<2:4> -> e2=B[v > 9] "
    "select e1[0].v as a, e1[1].v as a2, e2.v as b insert into O;",
    # e2 and e4 key alike on a slot e3's round writes between them: e4
    # looks its band up again, with the e3.w just captured
    "from every e1=A[v > 3] -> e2=A[w == coalesce(e3.w, e1.w)] "
    "-> e3=A[v > e1.v] -> e4=A[w == coalesce(e3.w, e1.w)] "
    "select e1.v as a, e3.w as w3, e4.w as w insert into O;",
]


SCAN_ONLY = {3}   # armed-once sequence starts (see QLS comment)


@pytest.mark.parametrize("qi", range(len(QLS)))
@pytest.mark.parametrize("seed", [0, 1])
def test_parallel_matches_scan(qi, seed):
    ql = QLS[qi]
    sends = gen_sends(seed)
    got_par = run(ql, sends, expect_parallel=qi not in SCAN_ONLY)
    got_scan = run(ql, sends, force_scan=True)
    assert got_par == got_scan, (
        f"q{qi} seed{seed}: parallel {len(got_par)} rows "
        f"vs scan {len(got_scan)}\n{got_par[:5]}\n{got_scan[:5]}")


def test_chunk_split_invariance():
    """Same replay, different chunk sizes -> same matches."""
    ql = QLS[1]
    base = gen_sends(7, n=300, chunk=30)
    small = []
    for sid, ts, cols in base:
        for s in range(0, len(ts), 11):
            small.append((sid, ts[s:s + 11],
                          [c[s:s + 11] for c in cols]))
    assert run(ql, base) == run(ql, small)


class TestSubBatchedCounting:
    def test_kleene_across_sub_batches(self):
        # regression: jnp.sum int32->int64 promotion widened the counting
        # slot's carry and broke the fori_loop carry contract whenever a
        # batch exceeded the PB sub-batch size
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
            @app:playback
            define stream A (v int);
            define stream B (v int);
            @info(name = 'q')
            from every e1=A[v > 10]+, e2=B[v > e1.v] within 10 sec
            select count(e1.v) as n, e2.v as bv
            insert into Out;
        """)
        got = []
        rt.add_callback("Out", StreamCallback(fn=lambda e: got.extend(e)))
        assert isinstance(rt.queries["q"].engine, ParallelNfaEngine)
        rt.start()
        B = ParallelNfaEngine.PB * 2  # force the sub-batched fori_loop
        ts = 1_700_000_000_000 + np.arange(B, dtype=np.int64)
        rng = np.random.default_rng(3)
        rt.get_input_handler("A").send_arrays(
            ts, [rng.integers(0, 100, B).astype(np.int32)])
        rt.get_input_handler("B").send_arrays(
            ts + B, [np.full(B, 99, dtype=np.int32)])
        rt.shutdown()
        assert len(got) > 0  # matches produced, no dtype crash


# -- keyed rounds: a state whose condition pairs the event with a captured
# slot by equality finds its first matching event by key ------------------

KEYED_APP = "@app:playback\ndefine stream S (k int, v int);\n"


def keyed_ql(states: int, within_ms: int, cap: str = "") -> str:
    chain = " -> ".join(f"e{i}=S[k == e1.k]" for i in range(2, states + 1))
    return (f"@info(name='q') {cap}\nfrom every e1=S -> {chain} "
            f"within {within_ms} millisecond "
            f"select e1.k as k, e1.v as v1, e{states}.v as vl "
            "insert into O;")


def run_keyed(ql, sends, force_scan=False):
    import siddhi_tpu.core.runtime as R
    orig = R.parallel_supported
    if force_scan:
        R.parallel_supported = lambda *a: False
    try:
        rt = SiddhiManager().create_siddhi_app_runtime(KEYED_APP + ql)
        got = []
        rt.add_callback("O", StreamCallback(fn=lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs)))
        rt.start()
        for ts, cols in sends:
            rt.get_input_handler("S").send_arrays(ts, cols)
        stats = rt.queries["q"].stats()
        rt.shutdown()
        return got, stats
    finally:
        R.parallel_supported = orig


def keyed_stream(seed, n, keys, step_ms, tie_every):
    """n events over ``keys`` keys; time advances by step_ms every
    ``tie_every`` events, so runs of events share a timestamp."""
    rng = np.random.default_rng(seed)
    ts = 1_000_000 + (np.arange(n, dtype=np.int64) // tie_every) * step_ms
    k = rng.integers(0, keys, n).astype(np.int32)
    v = np.arange(n, dtype=np.int32)
    return ts, k, v


def chunked(ts, k, v, size):
    """The stream in chunks of ``size`` events, or of the sizes listed."""
    sizes = [size] * -(-len(ts) // size) if isinstance(size, int) else size
    bounds = np.cumsum([0] + sizes)
    return [(ts[a:b], [k[a:b], v[a:b]]) for a, b in zip(bounds, bounds[1:])]


def reference_rows(ts, k, v, states, within_ms):
    from stopped_reference import consecutive_matches
    first, last = consecutive_matches(ts, {"k": k}, ("k",), states,
                                      within_ms)
    return [(int(ts[b]), int(k[a]), int(v[a]), int(v[b]))
            for a, b in zip(first.tolist(), last.tolist())]


# (states, keys, step_ms, events sharing a timestamp, within_ms): few keys
# make long bands (~100 same-key events per 512-event chunk); within is a
# whole number of steps, so matches fall on its bound
KEYED_CASES = {
    "2_states_long_bands": (2, 3, 10, 1, 50),
    "3_states_ties": (3, 5, 100, 7, 300),
    "4_states_boundary": (4, 4, 30, 1, 90),
    "5_states_many_keys": (5, 40, 5, 2, 400),
}


# each case at the default walk block, and again in blocks of 16 rows, so
# that a round walks its rows in several blocks
KEYED_RUNS = [pytest.param(c, None, id=c) for c in sorted(KEYED_CASES)] + [
    pytest.param(c, 16, id=f"{c}-walk_block_16") for c in sorted(KEYED_CASES)]


@pytest.mark.parametrize("case,block", KEYED_RUNS)
def test_keyed_rounds_match_scan_and_reference(case, block, monkeypatch):
    states, keys, step, tie, within = KEYED_CASES[case]
    ts, k, v = keyed_stream(len(case), 1500, keys, step, tie)
    ql = keyed_ql(states, within)
    sends = chunked(ts, k, v, 512)
    if block is not None:
        default = run_keyed(ql, sends)[1]
        monkeypatch.setattr(ParallelNfaEngine, "WALK_BLOCK", block)
    got, stats = run_keyed(ql, sends)
    want = reference_rows(ts, k, v, states, within)
    assert len(want) > 0
    assert got == want
    assert stats["overflow"] == 0
    # a keyed round walks a few band entries per row, not rows x events
    assert stats["nfa_candidates"] <= 4 * (states - 1) * stats["nfa_events"]
    # every round keys on e1's slot, which none writes: one band lookup a
    # step, a search inside the buckets of at most bit_length(B)
    # iterations for the B = 1,024 events of a 512-event chunk's bucket
    assert stats["nfa_steps"] <= stats["nfa_band_iters"] <= \
        stats["nfa_steps"] * (1024).bit_length()
    if block is not None:
        # some round of some step ran three blocks or more, and the
        # blocks walked the very entries one block walks
        rounds = (states - 1) * stats["nfa_steps"]
        assert stats["nfa_walk_rows"] > 2 * block * rounds
        assert stats["nfa_candidates"] == default["nfa_candidates"]
        assert stats["nfa_walk_rows"] == default["nfa_walk_rows"]
        assert stats["nfa_band_iters"] == default["nfa_band_iters"]
    if states == 4:
        # the scan engine's fixed table (128 rows, 256 matches a step)
        # holds this case only
        assert run_keyed(ql, sends, force_scan=True)[0] == want
        # another split (into the same 512-event bucket) changes nothing
        assert run_keyed(ql, chunked(ts, k, v, [300, 500, 412, 288]))[0] \
            == want


def _colliding_hashes(monkeypatch):
    """The 3-state case with every key hashing alike: (rows, stats, the
    reference's rows)."""
    import siddhi_tpu.ops.nfa_parallel as NP
    monkeypatch.setattr(NP, "hash_columns",
                        lambda cols, nulls: jnp_zeros_like(cols[0]))
    states, keys, step, tie, within = KEYED_CASES["3_states_ties"]
    ts, k, v = keyed_stream(5, 1500, keys, step, tie)
    sends = chunked(ts, k, v, 512)
    got, stats = run_keyed(keyed_ql(states, within), sends)
    # one bucket holds a chunk's every event: each step's one band lookup
    # halves the whole chunk, bit_length(events) iterations, at most 17
    assert stats["nfa_band_iters"] == sum(
        len(ts_c).bit_length() for ts_c, _ in sends)
    assert stats["nfa_band_iters"] <= 17 * stats["nfa_steps"]
    return got, stats, reference_rows(ts, k, v, states, within)


def test_keyed_rounds_survive_colliding_hashes(monkeypatch):
    """Every key hashing alike puts every event in every row's band: the
    whole condition, checked on each entry walked, still decides."""
    got, stats, want = _colliding_hashes(monkeypatch)
    assert got == want
    # and the walk did cross other keys' events
    states = 3
    assert stats["nfa_candidates"] > stats["nfa_completed"] * (states - 1)
    # nearly every row has an entry left in its band, so nearly every
    # row walks: more walking rows than events
    assert stats["nfa_walk_rows"] > stats["nfa_events"]


def test_keyed_rounds_survive_colliding_hashes_in_small_walk_blocks(
        monkeypatch):
    """Every row walking, in blocks of 8 rows: the reference's rows, and
    the same entries walked as in one block."""
    got1, stats1, want = _colliding_hashes(monkeypatch)
    monkeypatch.setattr(ParallelNfaEngine, "WALK_BLOCK", 8)
    got, stats, _ = _colliding_hashes(monkeypatch)
    assert got == want == got1
    assert stats["overflow"] == 0
    assert stats["nfa_candidates"] == stats1["nfa_candidates"]
    assert stats["nfa_walk_rows"] == stats1["nfa_walk_rows"]
    assert stats["nfa_band_iters"] == stats1["nfa_band_iters"]


def jnp_zeros_like(x):
    import jax.numpy as jnp
    return jnp.zeros(x.shape, jnp.int64)


# -- the band lookup: each key's band of the sorted view, found through a
# bucket directory of the keys' top bits, is searchsorted's band ---------

I32 = np.iinfo(np.int32)


def _band_case(case, rng):
    """(a sub-batch's keys, which are live, the keys looked up)."""
    from siddhi_tpu.ops.nfa_parallel import _dir_bits
    if case == "random":
        keys = rng.integers(I32.min, I32.max, 4096, endpoint=True)
        live = rng.random(4096) < 0.9
        probe = np.concatenate([keys, rng.integers(I32.min, I32.max, 2048)])
    elif case == "shared_top_bits":
        # 16 buckets, each holding ~250 events of ~300 distinct low bits
        shift = 32 - _dir_bits(4096)
        top = rng.integers(-2 ** (31 - shift), 2 ** (31 - shift), 16)
        low = rng.integers(0, 2 ** shift, 300)
        keys = (rng.choice(top, 4096) << shift) + rng.choice(low, 4096)
        live = rng.random(4096) < 0.9
        probe = np.concatenate([keys, (rng.choice(top, 2048) << shift) +
                                rng.integers(0, 2 ** shift, 2048)])
    elif case == "one_bucket":
        # all 65,536 events in one bucket: the search halves them all,
        # for the bucket's least key to the very first position
        shift = 32 - _dir_bits(65536)
        keys = (5 << shift) + rng.integers(0, 2 ** shift, 65536)
        live = np.ones(65536, bool)
        probe = np.concatenate([keys[:4096], (5 << shift) +
                                rng.integers(0, 2 ** shift, 1024),
                                [5 << shift]])
    elif case == "int32_extremes":
        # live keys at both ends of int32, among them the padding
        # sentinel (int32 max) that the dead events sort under
        keys = rng.choice([I32.min, I32.min + 1, -1, 0, I32.max - 1,
                           I32.max], 1024)
        live = rng.random(1024) < 0.75
        probe = np.array([I32.min, I32.min + 1, I32.min + 2, -1, 0, 1,
                          I32.max - 2, I32.max - 1, I32.max])
    elif case == "no_live":
        keys = rng.integers(I32.min, I32.max, 1024)
        live = np.zeros(1024, bool)
        probe = keys
    else:   # "absent": even keys, odd ones looked up
        keys = rng.integers(I32.min // 2, I32.max // 2, 4096) * 2
        live = np.ones(4096, bool)
        probe = keys[:2048] + 1
    return keys.astype(np.int32), live, probe.astype(np.int32)


@pytest.mark.parametrize("case", ["random", "shared_top_bits", "one_bucket",
                                  "int32_extremes", "no_live", "absent"])
def test_band_lookup_matches_searchsorted(case):
    import jax.numpy as jnp
    from siddhi_tpu.ops.nfa_parallel import _band_lookup, _sorted_view
    keys, live, probe = _band_case(case, np.random.default_rng(len(case)))
    view = _sorted_view(jnp.asarray(keys), jnp.asarray(live))
    lo, hi, iters = _band_lookup(view, jnp.asarray(probe))
    lo, hi, iters = np.asarray(lo), np.asarray(hi), int(iters)
    n_live = int(view["live"])
    want_lo, want_hi = (np.minimum(np.asarray(jnp.searchsorted(
        view["keys"], probe, side=side)), n_live)
        for side in ("left", "right"))
    present = np.isin(probe, keys[live])
    assert np.array_equal(want_lo < want_hi, present)
    # a present key's band is searchsorted's; an absent key's is empty
    assert np.array_equal(lo[present], want_lo[present])
    assert np.array_equal(hi[present], want_hi[present])
    assert np.array_equal(lo[~present], hi[~present])
    # the search runs while some key's interval inside its bucket is
    # non-empty: bit_length of the widest bucket's events (~3,700 random
    # keys in 16,384 buckets put at most 7 in one)
    assert iters <= len(keys).bit_length()
    widest = {"one_bucket": 65536, "no_live": 0, "random": 7}.get(case)
    if case in ("one_bucket", "no_live"):
        assert iters == widest.bit_length()
    elif widest is not None:
        assert iters <= widest.bit_length()


def test_round_kind_is_chosen_from_the_query():
    rt = SiddhiManager().create_siddhi_app_runtime(
        KEYED_APP + keyed_ql(4, 90_000) +
        "\n@info(name='g') from every e1=S -> e2=S[v > e1.v] "
        "select e1.v as a insert into G;"
        "\n@info(name='m') from every e1=S -> e2=S[v > e1.v and k == e1.k] "
        "select e1.v as a insert into M;")
    eng = rt.queries["q"].engine
    assert isinstance(eng, ParallelNfaEngine)
    # the always-armed start holds no row and runs no round
    assert [eng.round_kind(s) for s in eng.states[1:]] == ["keyed"] * 3
    assert eng.states[0].idx not in eng._waiting
    g = rt.queries["g"].engine
    assert g.round_kind(g.states[1]) == "grid"
    # a residual conjunct rides along on the key
    m = rt.queries["m"].engine
    assert m.round_kind(m.states[1]) == "keyed"


def test_pattern_cap_dial():
    from siddhi_tpu.ops.expr import CompileError
    cap = "@cap(pattern.pending='64', pattern.matches='8')"
    rt = SiddhiManager().create_siddhi_app_runtime(
        KEYED_APP + keyed_ql(2, 1_000_000, cap))
    eng = rt.queries["q"].engine
    assert (eng.M, eng.OUT) == (64, 8)
    default = SiddhiManager().create_siddhi_app_runtime(
        KEYED_APP + keyed_ql(2, 1_000))
    assert (default.queries["q"].engine.M,
            default.queries["q"].engine.OUT) == (4096, 16384)
    # past MAX_PENDING the fold's running counts would round (f32)
    for bad in ("0", "-3", "many", str(ParallelNfaEngine.MAX_PENDING + 1)):
        with pytest.raises(CompileError, match="pattern.pending"):
            SiddhiManager().create_siddhi_app_runtime(
                KEYED_APP + keyed_ql(2, 1_000,
                                     f"@cap(pattern.pending='{bad}')"))
    # 256 events on 200 keys: more partials than 64 rows, and more
    # matches in a step than 8: both dropped rows and lost matches count
    ts, k, v = keyed_stream(1, 256, 200, 1, 1)
    got, stats = run_keyed(keyed_ql(2, 1_000_000, cap),
                           chunked(ts, k, v, 128))
    assert stats["overflow"] > 0
    assert len(got) < len(reference_rows(ts, k, v, 2, 1_000_000))


KEYED_ROUNDS = 2   # keyed_ql(3, ...): rounds at e2 and e3


@pytest.mark.parametrize("pending", [4096, 16])
def test_counters_add_up(pending):
    """Per step: live_in + spawned - completed - pruned - dropped =
    live_out, where rows are dropped only by a full table (overflow, with
    matches well inside their cap); each completion is one match; and
    live_out is the table's valid rows."""
    cap = f"@cap(pattern.pending='{pending}')"
    ts, k, v = keyed_stream(3, 1024, 16, 20, 1)
    rt = SiddhiManager().create_siddhi_app_runtime(
        KEYED_APP + keyed_ql(3, 600, cap))
    rt.add_callback("O", StreamCallback(fn=lambda evs: None))
    rt.start()
    q = rt.queries["q"]
    h = rt.get_input_handler("S")
    prev = q.stats()
    for ts_c, cols in chunked(ts, k, v, 256):
        h.send_arrays(ts_c, cols)
        s = q.stats()
        d = {x: s[x] - prev[x] for x in s if x != "nfa_live_peak"}
        assert d["nfa_steps"] == 1 and d["nfa_events"] == len(ts_c)
        assert d["nfa_live_in"] + d["nfa_spawned"] - d["nfa_completed"] \
            - d["nfa_pruned"] - d["overflow"] == d["nfa_live_out"]
        assert d["nfa_completed"] == d["emitted"]
        # a row walks at most once a keyed round, and a completed row
        # walked in its last round
        assert d["nfa_walk_rows"] <= \
            (d["nfa_live_in"] + d["nfa_spawned"]) * KEYED_ROUNDS
        assert d["nfa_walk_rows"] >= d["nfa_completed"]
        assert d["nfa_live_out"] == int(np.sum(np.asarray(
            q.nfa_state["valid"])))
        assert s["nfa_live_peak"] >= d["nfa_live_out"]
        prev = s
    assert (prev["overflow"] > 0) == (pending < 100)
    # the counters live in the table: a snapshot carries them
    snap = q.snapshot_state()
    rt.shutdown()
    rt2 = SiddhiManager().create_siddhi_app_runtime(
        KEYED_APP + keyed_ql(3, 600, cap))
    rt2.queries["q"].restore_state(snap)
    assert rt2.queries["q"].stats() == {**prev, "emitted": prev["emitted"]}
    # a snapshot from before the counters restores them at 0
    old = {**snap, "nfa": {x: y for x, y in snap["nfa"].items()
                           if not x.startswith("nfa_")}}
    rt2.queries["q"].restore_state(old)
    assert rt2.queries["q"].stats()["nfa_steps"] == 0
    assert rt2.queries["q"].stats()["nfa_walk_rows"] == 0


def test_batch_longer_than_within_keeps_late_partials():
    """The 5-state chain, within 60 s, 16 symbols, 1,024-event chunks at
    200 ms an event (204.8 s a chunk): a partial opened late in a chunk
    outlives the chunk's prune."""
    import collections
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import chip_smoke as cs
    ts, sym_idx, stage, v = cs.seq5_data(4096, 16, 200, seed=1)
    names = cs.sym_names(16)
    sym = cs.sym_codes(names)[sym_idx]
    rt = SiddhiManager().create_siddhi_app_runtime(cs.SEQ5_APP)
    got = []
    rt.add_callback("Out", StreamCallback(
        fn=lambda evs: got.extend(tuple(e.data) for e in evs)))
    rt.start()
    h = rt.get_input_handler("T")
    for i in range(0, len(ts), 1024):
        h.send_arrays(ts[i:i + 1024], [sym[i:i + 1024],
                                       stage[i:i + 1024], v[i:i + 1024]])
    stats = rt.queries["q"].stats()
    rt.shutdown()
    want = cs.seq5_reference(ts, sym_idx, stage, v, names)
    g, w = collections.Counter(got), collections.Counter(want)
    assert len(want) > 0 and stats["overflow"] == 0
    assert sum((g - w).values()) + sum((w - g).values()) == 0


def _linear_road():
    """The benchmark's Linear Road configuration and its module."""
    import importlib.util
    import json
    from pathlib import Path
    base = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
    with open(base / "linear_road_stopped.json") as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "lr_stopped", base / "linear_road_stopped.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return config, mod


def test_reference_is_the_benchmarks_own():
    import inspect
    import stopped_reference
    _, mod = _linear_road()
    assert inspect.getsource(mod.consecutive_matches) == \
        inspect.getsource(stopped_reference.consecutive_matches)


def test_linear_road_tiny_form_walks_few_rows():
    """The benchmark's Linear Road query at its tiny form, one cycle of
    256 vehicles (16 of them stop) in 1,024-report chunks: a moving car's
    band is empty and a spawned row's holds only its own report, so only
    the stopped cars' rows walk, fewer than one per twenty events."""
    config, mod = _linear_road()
    config, _ = mod.tiny(config, {})
    offsets, cols = mod.generate(np.random.default_rng(2 ** 31 + 9),
                                 256 * mod.REPORTS)
    ts = 1_700_000_000_000 + offsets
    names = [c["name"] for c in config["columns"]]
    rt = SiddhiManager().create_siddhi_app_runtime(config["app"])
    got = []
    rt.add_callback(config["output"], StreamCallback(fn=lambda evs: got.extend(
        (e.timestamp, *e.data) for e in evs)))
    rt.start()
    h = rt.get_input_handler(config["stream"])
    for a in range(0, len(ts), 1024):
        h.send_arrays(ts[a:a + 1024], [cols[n][a:a + 1024] for n in names])
    stats = rt.queries["stopped"].stats()
    rt.shutdown()
    want = mod.reference(ts, cols, {})
    assert len(want) > 0 and got == want
    assert stats["overflow"] == 0
    assert stats["nfa_walk_rows"] < 0.05 * stats["nfa_events"]

"""Cost-aware DAG plan optimizer (plan/optimizer.py,
docs/performance.md "Plan optimizer"):

- OPT=1 vs OPT=0 equivalence sweep: fan-out fusion, CSE prefix sharing
  (incl. nested trie classes), chain-under-group composition, filter
  pushdown across a time window — bit-equal outputs over a fan-out
  corpus AND the golden 5-app explain corpus, on both ingest paths
- snapshot/restore crossing optimizer modes
- counting-jit steady-state zero-recompile guard on fan-out shapes,
  and AOT warmup covering the fused group program
- cost-driven selection: a crafted costs.json FLIPS the fusion
  decision (asserted via explain_diff, not hardcoded) and picks the
  measured chunk cap; cause slugs recorded either way
- costs.json hygiene: save-time pruning of stale centers, the
  load_costs_for staleness guard, stale count in statistics()['cost']
- kill switches: SIDDHI_TPU_OPT / _FANOUT / _CSE / _PUSHDOWN
- the shared `_rewrite_current` dispatch (one jitted rewrite per
  emitted batch regardless of handler fan-out)
- ref-corpus sweep: plan derivation + explain succeed for every app
  that compiles
- tools/explain.py --expect golden files for the fan-out + CSE corpora
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from siddhi_tpu import Event, SiddhiManager, StreamCallback
from siddhi_tpu.core.types import GLOBAL_STRINGS
from siddhi_tpu.obs.explain import explain_diff

TS0 = 1_700_000_000_000
PLAYBACK = "@app:playback\n"
TOOLS = pathlib.Path(__file__).parent.parent / "tools"
GOLDEN_DIR = pathlib.Path(__file__).parent / "golden_explain"

# ---------------------------------------------------------------------------
# the fan-out corpus: (name, app, n_outputs)
# ---------------------------------------------------------------------------

FANOUT4 = ("fanout4", """
    define stream S (sym string, v int, p float);
    @info(name = 'q1') from S[v > 3 and p > 0.5] select sym, v, p
        insert into Out;
    @info(name = 'q2') from S[v > 3 and p > 0.5] select sym, v + 1 as v2
        insert into Out2;
    @info(name = 'q3') from S[v > 3 and p > 0.5] select sym, p * 2.0 as pd
        insert into Out3;
    @info(name = 'q4') from S[v < 900] select sym insert into Out4;
""")

CSE_NESTED = ("cse_nested", """
    define stream S (sym string, v int, p float);
    @info(name = 'q1') from S[v > 2] select sym, v insert into Out;
    @info(name = 'q2') from S[v > 2] select sym, v insert into Out2;
    @info(name = 'q3') from S[v > 2] select sym, p insert into Out3;
""")

FANOUT_WINDOW = ("fanout_window", """
    define stream S (sym string, v int, p float);
    @info(name = 'q1') from S#window.time(2 sec)
        select sym, sum(v) as total group by sym insert into Out;
    @info(name = 'q2') from S[v > 4] select sym, v insert into Out2;
""")

FANOUT_CHAIN = ("fanout_chain", """
    define stream S (sym string, v int, p float);
    @info(name = 'q1') from S[v > 3] select sym, v insert into M1;
    @info(name = 'q2') from M1 select sym, v + 1 as v insert into Out;
    @info(name = 'q4') from S[v < 500] select sym, v insert into Out2;
""")

FANOUT_MID = ("fanout_mid", """
    define stream S (sym string, v int, p float);
    @info(name = 'q0') from S[v > 1] select sym, v insert into M;
    @info(name = 'm1') from M[v > 3] select sym, v insert into Out;
    @info(name = 'm2') from M[v > 3] select sym insert into Out2;
""")

PUSHDOWN = ("pushdown", """
    define stream S (sym string, v int, p float);
    @info(name = 'q1') from S#window.time(2 sec) select sym, v
        insert into M;
    @info(name = 'q2') from M[v > 4] select sym, v insert into Out;
""")

CORPUS = [FANOUT4, CSE_NESTED, FANOUT_WINDOW, FANOUT_CHAIN, FANOUT_MID,
          PUSHDOWN]

OUT_STREAMS = ("Out", "Out2", "Out3", "Out4")


def _events(n=48, seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        out.append((1000 + 97 * i,
                    ("A" if rng.integers(0, 2) else "B",
                     int(rng.integers(0, 10)),
                     float(np.float32(rng.uniform(0.0, 2.0))))))
    return out


def _arrays(events):
    ts = np.array([e[0] for e in events], np.int64)
    sym = np.array([GLOBAL_STRINGS.encode(e[1][0]) for e in events],
                   np.int32)
    v = np.array([e[1][1] for e in events], np.int32)
    p = np.array([e[1][2] for e in events], np.float32)
    return ts, [sym, v, p]


def _build(app, opt, persistence_store=None, **env):
    prev = {}
    env = {"SIDDHI_TPU_OPT": "1" if opt else "0", **env}
    for k, val in env.items():
        prev[k] = os.environ.get(k)
        os.environ[k] = val
    try:
        mgr = SiddhiManager()
        if persistence_store is not None:
            mgr.set_persistence_store(persistence_store)
        rt = mgr.create_siddhi_app_runtime(PLAYBACK + app)
        got = {}
        for sid in OUT_STREAMS:
            if sid in rt.junctions:
                lst = got.setdefault(sid, [])
                rt.add_callback(sid, StreamCallback(
                    fn=lambda evs, lst=lst: lst.extend(
                        (e.timestamp, e.data, e.is_expired)
                        for e in evs)))
        rt.start()
        return rt, got
    finally:
        for k, val in prev.items():
            if val is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = val


def _deterministic_stats(rt, skip_emitted=()):
    stats = rt.statistics()
    out = {}
    for name, entry in stats.items():
        if not isinstance(entry, dict):
            out[name] = entry
            continue
        drop = {"throughput_eps", "latency"}
        if name in skip_emitted:
            # pushdown-optimized segments count the PRUNED stream at
            # intermediate member boundaries (docs/performance.md) —
            # the emitted counter legitimately differs across modes
            drop.add("emitted")
        out[name] = {k: v for k, v in entry.items() if k not in drop}
    return out


def _run(app, opt, columnar, events=None, skip_emitted=()):
    rt, got = _build(app, opt)
    if events is None:
        events = _events()
    if columnar:
        ts, cols = _arrays(events)
        rt.get_input_handler("S").send_arrays(ts, cols)
    else:
        h = rt.get_input_handler("S")
        for ts, data in events:
            h.send(Event(ts, data))
    stats = _deterministic_stats(rt, skip_emitted=skip_emitted)
    rt.shutdown()
    return got, stats


# ---------------------------------------------------------------------------
# equivalence sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("columnar", [True, False],
                         ids=["columnar", "rows"])
@pytest.mark.parametrize("name,app", CORPUS, ids=[c[0] for c in CORPUS])
def test_optimized_equals_unoptimized(name, app, columnar):
    skip = ("q1",) if name == "pushdown" else ()
    opt = _run(app, opt=True, columnar=columnar, skip_emitted=skip)
    base = _run(app, opt=False, columnar=columnar, skip_emitted=skip)
    assert opt == base


def test_golden_explain_corpus_equivalent():
    """The 5-app golden corpus (test_explain.py) replays bit-equal
    across optimizer modes — apps the optimizer does NOT transform must
    be untouched by it."""
    from tests.test_explain import GOLDEN
    for name, ql in sorted(GOLDEN.items()):
        if name == "partition":
            continue  # needs a mesh fixture; covered in test_explain
        for opt in (True, False):
            rt = SiddhiManager().create_siddhi_app_runtime(ql)
            prev = os.environ.get("SIDDHI_TPU_OPT")
            os.environ["SIDDHI_TPU_OPT"] = "1" if opt else "0"
            try:
                rt.start()
            finally:
                if prev is None:
                    os.environ.pop("SIDDHI_TPU_OPT", None)
                else:
                    os.environ["SIDDHI_TPU_OPT"] = prev
            assert rt.plan_hash()
            rt.shutdown()


def test_mixed_receivers_keep_row_consumers():
    """A row-level StreamCallback on the fan-out junction rides the
    EventBatch publish path next to the fused group — both see every
    event."""
    app = FANOUT4[1]
    rows = []
    rt, got = _build(app, opt=True)
    rt.add_callback("S", StreamCallback(fn=lambda evs: rows.extend(evs)))
    assert rt.junctions["S"].fanout is not None
    ts, cols = _arrays(_events(24))
    rt.get_input_handler("S").send_arrays(ts, cols)
    rt.shutdown()
    assert len(rows) == 24
    assert got["Out4"], "grouped member produced no output"


# ---------------------------------------------------------------------------
# decisions / explain surface
# ---------------------------------------------------------------------------


def test_fanout_group_and_nested_cse_decisions():
    rt, _ = _build(CSE_NESTED[1], opt=True)
    dec = rt._opt_decisions
    fan = dec["fanout"]["S"]
    assert fan["fused"] and fan["cause"] == "fused-default"
    assert fan["members"] == ["q1", "q2", "q3"]
    # nested trie classes: all three share the filter; q1/q2 also share
    # the projection (fed from the shared filter output)
    cse = fan["cse"]
    assert {tuple(c["queries"]): c["ops"] for c in cse} == {
        ("q1", "q2", "q3"): 1, ("q1", "q2"): 2}
    # explain marks members with the group, not a break slug
    fusion = rt.explain(live=False)["decisions"]["fusion"]
    for q in ("q1", "q2", "q3"):
        assert fusion["queries"][q]["fanout_group"] == "S"
        assert "break" not in fusion["queries"][q]
    rt.shutdown()


def test_pushdown_decision_and_schedule():
    rt, _ = _build(PUSHDOWN[1], opt=True)
    dec = rt._opt_decisions
    moves = dec["pushdown"]["q1+q2"]
    assert moves[0]["filter_of"] == "q2"
    assert "q1.TimeWindowOp" in moves[0]["hoisted_past"]
    ch = rt.queries["q1"]._fused_chain
    # the hoisted filter is the first scheduled op
    assert ch.schedule[0] == ("op", 1, 0)
    rt.shutdown()


def test_kill_switches():
    # master off: no groups, no pushdown — but legacy linear fusion stays
    rt, _ = _build(PUSHDOWN[1], opt=False)
    assert rt.junctions["S"].fanout is None
    ch = rt.queries["q1"]._fused_chain
    assert ch is not None and ch.schedule[0] == ("op", 0, 0)
    assert rt._opt_decisions["enabled"] is False
    rt.shutdown()
    # per-transform switches
    rt, _ = _build(FANOUT4[1], opt=True, SIDDHI_TPU_OPT_FANOUT="0")
    assert rt.junctions["S"].fanout is None
    rt.shutdown()
    rt, _ = _build(FANOUT4[1], opt=True, SIDDHI_TPU_OPT_CSE="0")
    fo = rt.junctions["S"].fanout
    assert fo is not None and fo._classes == []
    rt.shutdown()
    rt, _ = _build(PUSHDOWN[1], opt=True, SIDDHI_TPU_OPT_PUSHDOWN="0")
    assert rt.queries["q1"]._fused_chain.schedule[0] == ("op", 0, 0)
    assert "pushdown" not in rt._opt_decisions
    rt.shutdown()


# ---------------------------------------------------------------------------
# cost-driven selection (the crafted-table flip — asserted, not hardcoded)
# ---------------------------------------------------------------------------


COST_APP = """
@app:name('xopt_cost') @app:playback
define stream S (sym string, v int, p float);
@info(name = 'q1') from S[v > 3] select sym, v insert into O1;
@info(name = 'q2') from S[v < 500] select sym, v insert into O2;
"""


def _deploy_cost(tmp_path, table=None):
    tmp_path.mkdir(parents=True, exist_ok=True)
    prev = os.environ.get("SIDDHI_TPU_STATE_DIR")
    os.environ["SIDDHI_TPU_STATE_DIR"] = str(tmp_path)
    try:
        if table is not None:
            (tmp_path / "costs.json").write_text(json.dumps(table))
        rt = SiddhiManager().create_siddhi_app_runtime(COST_APP)
        rt.start()
        rep = rt.explain(live=False)
        stats = rt.statistics()
        rt.shutdown()
        return rep, stats
    finally:
        if prev is None:
            os.environ.pop("SIDDHI_TPU_STATE_DIR", None)
        else:
            os.environ["SIDDHI_TPU_STATE_DIR"] = prev


def _cost_entry(mpe):
    return {"ms_total": 10.0, "events": 1000, "samples": 4,
            "ms_per_event": mpe}


def test_crafted_cost_table_flips_fusion_decision(tmp_path):
    """The acceptance assertion: a measured (crafted) cost table showing
    the fused center slower per event than its members DECLINES the
    fusion, the flip moves plan_hash, and explain_diff names the exact
    decision path — nothing hardcoded."""
    baseline, _ = _deploy_cost(tmp_path / "a")
    flipped, _ = _deploy_cost(tmp_path / "b", {"xopt_cost": {
        "fanout/S": _cost_entry(0.1),
        "query/q1": _cost_entry(0.01),
        "query/q2": _cost_entry(0.01),
    }})
    assert baseline["decisions"]["optimizer"]["fanout"]["S"] == {
        "members": ["q1", "q2"], "fused": True,
        "cause": "fused-default"}
    fan = flipped["decisions"]["optimizer"]["fanout"]["S"]
    assert fan["fused"] is False
    assert fan["cause"] == "cost-evidence-unfused"
    diff = explain_diff(baseline, flipped)
    assert not diff["equal"]
    assert baseline["plan_hash"] != flipped["plan_hash"]
    paths = {c["path"] for c in diff["changes"]}
    assert "decisions.optimizer.fanout.S.fused" in paths
    assert "decisions.optimizer.fanout.S.cause" in paths


def test_cost_evidence_picks_chunk_cap_and_confirms_fusion(tmp_path):
    rep, _ = _deploy_cost(tmp_path, {"xopt_cost": {
        "fanout/S": _cost_entry(0.001),
        "query/q1": _cost_entry(0.01),
        "query/q2": _cost_entry(0.01),
        "fanout/S@1024": _cost_entry(0.002),
        "fanout/S@8192": _cost_entry(0.005),
    }})
    fan = rep["decisions"]["optimizer"]["fanout"]["S"]
    assert fan["fused"] and fan["cause"] == "cost-evidence-fused"
    assert fan["chunk_cap"] == {"cap": 1024, "cause": "cost-evidence"}


def test_stale_centers_guard_and_statistics(tmp_path):
    _, stats = _deploy_cost(tmp_path, {"xopt_cost": {
        "query/q1": _cost_entry(0.01),
        "query/renamed_away": _cost_entry(0.5),
        "chain/gone+dead": _cost_entry(0.5),
    }})
    # two centers name plan units that no longer exist: ignored at
    # load, counted in statistics()['cost'] (never silent)
    assert stats["cost"]["stale_centers"] == 2


def test_cost_save_prunes_stale_centers(tmp_path):
    from siddhi_tpu.obs.costmodel import load_costs
    path = str(tmp_path / "costs.json")
    (tmp_path / "costs.json").write_text(json.dumps({"app_x": {
        "query/renamed_away": _cost_entry(0.5)}}))
    rt, _ = _build(FANOUT4[1], opt=True)
    rt.name_for_test = rt.name
    # seed the stale entry under THIS app's key, then measure + save
    tbl = load_costs(path)
    tbl[rt.name] = {"query/renamed_away": _cost_entry(0.5),
                    "fanout/ghost_junction": _cost_entry(0.5)}
    (tmp_path / "costs.json").write_text(json.dumps(tbl))
    rt.cost_start(every=1)
    ts, cols = _arrays(_events(32))
    rt.get_input_handler("S").send_arrays(ts, cols)
    rt.cost_save(path)
    rt.shutdown()
    saved = load_costs(path)
    mine = saved[list(k for k in saved if k != "app_x")[0]]
    assert "query/renamed_away" not in mine
    assert "fanout/ghost_junction" not in mine
    assert "fanout/S" in mine           # the live group center persisted
    assert any(k.startswith("fanout/S@") for k in mine), \
        "per-capacity chunk evidence missing"
    # other apps' tables untouched
    assert "query/renamed_away" in saved["app_x"]


# ---------------------------------------------------------------------------
# compile hygiene
# ---------------------------------------------------------------------------


def test_steady_state_zero_recompiles_on_fanout(monkeypatch):
    import functools

    import jax

    real_jit = jax.jit
    traces = [0]

    def counting_jit(f, *a, **kw):
        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            traces[0] += 1
            return f(*args, **kwargs)
        return real_jit(wrapped, *a, **kw)

    monkeypatch.setattr(jax, "jit", counting_jit)
    rt, _ = _build(FANOUT4[1], opt=True)
    assert rt.junctions["S"].fanout is not None
    h = rt.get_input_handler("S")

    def chunk(i):
        n = 64
        ts = 1_000_000 + i * n + np.arange(n, dtype=np.int64)
        sym = np.full((n,), GLOBAL_STRINGS.encode("A"), np.int32)
        v = (np.arange(n, dtype=np.int32) * 7) % 1000
        p = np.linspace(0.0, 2.0, n, dtype=np.float32)
        return ts, [sym, v, p]

    for i in range(3):
        h.send_arrays(*chunk(i))
    before = traces[0]
    for i in range(3, 10):
        h.send_arrays(*chunk(i))
    rt.shutdown()
    assert traces[0] == before, \
        f"steady-state chunks triggered {traces[0] - before} new traces"


def test_warmup_compiles_fanout_group_program():
    rt, _ = _build(FANOUT4[1], opt=True)
    wu = rt.warmup(buckets=[128])
    keys = {s.key for s in rt.compile_service.specs([128])}
    assert any(k.startswith("fanout:S/") for k in keys), keys
    assert wu["programs"] >= 1
    rt.shutdown()


def test_snapshot_restore_crosses_optimizer_modes():
    app = FANOUT_WINDOW[1]
    events = _events(n=40, seed=9)
    cut = 20
    full_ref = _run(app, opt=False, columnar=False, events=events)[0]

    rt, got1 = _build(app, opt=True)
    h = rt.get_input_handler("S")
    for ts, data in events[:cut]:
        h.send(Event(ts, data))
    snap = rt.snapshot()
    rt.shutdown()

    rt2, got2 = _build(app, opt=False)
    rt2.restore(snap)
    h2 = rt2.get_input_handler("S")
    for ts, data in events[cut:]:
        h2.send(Event(ts, data))
    rt2.shutdown()
    combined = {sid: got1.get(sid, []) + got2.get(sid, [])
                for sid in full_ref}
    assert combined == full_ref


# ---------------------------------------------------------------------------
# shared CURRENT-kind rewrite (one jitted dispatch per emitted batch)
# ---------------------------------------------------------------------------


def test_rewrite_current_once_per_emitted_batch(monkeypatch):
    """A query fanning out to N insert-into junctions pays ONE jitted
    kind rewrite per emitted batch, not one per handler."""
    from siddhi_tpu.core import runtime as rtmod
    app = """
        define stream S (v int);
        define stream B (v int);
        @info(name = 'q0') from S[v > 0] select v insert into A;
        @info(name = 'qa1') from A select v insert into OutA;
        @info(name = 'qa2') from A[v > 2] select v insert into OutA2;
        @info(name = 'qb') from B select v insert into OutB;
    """
    rt, _ = _build(app, opt=False)
    q0 = rt.queries["q0"]
    # fan q0 out to a second junction (B), like a multi-output query
    q0.output_handlers.append(
        rtmod.InsertIntoStreamHandler(rt.junctions["B"], "current"))
    calls = [0]
    real = rtmod._rewrite_current

    def counting(out):
        calls[0] += 1
        return real(out)

    monkeypatch.setattr(rtmod, "_rewrite_current", counting)
    ts = np.arange(16, dtype=np.int64) + TS0
    rt.get_input_handler("S").send_arrays(
        ts, [np.arange(1, 17, dtype=np.int32)])
    rt.shutdown()
    assert calls[0] == 1, \
        f"{calls[0]} rewrites for one emitted batch with 2 handlers"


# ---------------------------------------------------------------------------
# ref-corpus sweep: derivation succeeds for every app that compiles
# ---------------------------------------------------------------------------


def test_plan_derivation_over_ref_corpus():
    from siddhi_tpu.lang.parser import SiddhiParserException
    from siddhi_tpu.ops.expr import CompileError
    corpus = pathlib.Path(__file__).parent / "ref_corpus"
    mgr = SiddhiManager()
    n_ok = 0
    for f in sorted(corpus.glob("*.json")):
        for case in json.loads(f.read_text())["cases"]:
            if case.get("expect_error"):
                continue
            try:
                rt = mgr.create_siddhi_app_runtime(
                    "@app:playback " + case["app"])
            except (CompileError, SiddhiParserException):
                continue
            # the optimizer pass itself (start() entry point)
            rt._build_fused_chains()
            assert rt._opt_decisions is not None
            rep = rt.explain(live=False)
            json.dumps(rep, sort_keys=True, default=str)
            n_ok += 1
    assert n_ok > 300, f"sweep covered only {n_ok} apps"


# ---------------------------------------------------------------------------
# golden --expect files (tools/explain.py regression gate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["fanout", "cse"])
def test_explain_expect_golden(name, tmp_path):
    """The checked-in golden reports gate the optimizer's decisions:
    tools/explain.py --expect exits 0 against the committed plan and 1
    the moment any decision moves."""
    app = GOLDEN_DIR / f"{name}.siddhi"
    golden = GOLDEN_DIR / f"{name}.expect.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SIDDHI_TPU_STATE_DIR=str(tmp_path))  # no local costs.json
    proc = subprocess.run(
        [sys.executable, str(TOOLS / "explain.py"), str(app),
         "--expect", str(golden)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # doctored golden: flip the fusion decision -> exit 1
    doc = json.loads(golden.read_text())
    doc["decisions"]["optimizer"]["fanout"]["S"]["fused"] = False
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    proc = subprocess.run(
        [sys.executable, str(TOOLS / "explain.py"), str(app),
         "--expect", str(bad)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "optimizer" in proc.stdout

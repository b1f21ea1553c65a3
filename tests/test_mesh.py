"""Tier-1 mesh coverage on the virtual 8-device CPU platform (the
conftest forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
one subprocess test re-forces it from a clean environment to guard the
bench/dryrun child path independently of the conftest).

Covers the PR-12 mesh scale-out layer end to end:

- ``parallel/sharding.py``: regex rule table -> PartitionSpec mapping,
  scalar auto-replication, no-match errors, and the placement DEDUPE
  (placing twice transfers nothing);
- ``parallel/mesh.py DataParallelRunner``: sharded filter / window /
  pattern / join execution bit-equal to single-device runs (pure
  data-parallel shards equal per-shard replays; key-routed shards equal
  the single-chip union replay);
- partition-block restore re-places shards in ONE device_put per leaf
  (the counting-device_put regression for the double-placement FIX);
- ``serving/pool.py mesh=``: sharded pools bit-equal to unsharded
  pools, zero recompiles across tenant churn, balanced per-device slot
  placement, mesh-aware admission, per-device labeled gauges.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import siddhi_tpu  # noqa: F401 — x64 + cache config
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import batch_from_columns
from siddhi_tpu.parallel import sharding
from siddhi_tpu.parallel.mesh import DataParallelRunner, owner_of_host

TS0 = 1_700_000_000_000


# ---- rule table -------------------------------------------------------


def test_match_partition_rules_paths_and_actions():
    tree = {
        "slot_tbl": {"keys": np.zeros((8,), np.int64),
                     "used": np.zeros((8,), np.bool_),
                     "overflow": np.int64(0)},
        "qstates": {"q1": ({"buf": np.zeros((8, 4))},)},
    }
    specs = sharding.match_partition_rules(
        sharding.PARTITION_STATE_RULES, tree, "shards")
    # slot table replicates (the batch->slot map runs on every device)
    assert specs["slot_tbl"]["keys"] == P()
    assert specs["slot_tbl"]["overflow"] == P()
    # [K]-leading operator state shards the leading axis only
    assert specs["qstates"]["q1"][0]["buf"] == P("shards", None)


def test_match_partition_rules_scalars_always_replicate():
    tree = {"states": {"q": (np.int64(3), np.zeros((4, 2)))}}
    specs = sharding.match_partition_rules(
        sharding.POOL_STATE_RULES, tree, "s")
    assert specs["states"]["q"][0] == P()
    assert specs["states"]["q"][1] == P("s", None)


def test_match_partition_rules_no_match_is_an_error():
    with pytest.raises(ValueError, match="no partition rule"):
        sharding.match_partition_rules(
            ((r"^only/this$", sharding.SHARD),),
            {"other": np.zeros((4,))}, "s")


def test_shard_pytree_dedupe_skips_placed_leaves():
    mesh = sharding.build_mesh(8)
    tree = {"a": np.arange(16, dtype=np.int64),
            "b": np.zeros((8, 3), np.float32)}
    stats = sharding.PlacementStats()
    placed = sharding.shard_pytree(tree, mesh,
                                   sharding.DATA_PARALLEL_RULES,
                                   stats=stats)
    assert stats.snapshot() == {"device_puts": 2, "skipped": 0}
    again = sharding.shard_pytree(placed, mesh,
                                  sharding.DATA_PARALLEL_RULES,
                                  stats=stats)
    # second pass: everything already placed, ZERO transfers
    assert stats.snapshot() == {"device_puts": 2, "skipped": 2}
    assert again["a"] is placed["a"]
    np.testing.assert_array_equal(np.asarray(again["a"]),
                                  np.arange(16))


def test_check_divisible():
    mesh = sharding.build_mesh(8)
    sharding.check_divisible(64, mesh, "slots")
    with pytest.raises(ValueError, match="divide evenly"):
        sharding.check_divisible(12, mesh, "slots")


# ---- data-parallel runner: bit-equivalence sweep ----------------------

FILTER_QL = """
@app:playback
define stream S (sym int, price float, volume long);
@info(name = 'q')
from S[price > 100.0] select sym, price insert into Out;
"""

WINDOW_QL = """
@app:playback
define stream S (sym int, price float, volume long);
@info(name = 'q')
from S#window.lengthBatch(64)
select sym, sum(volume) as total group by sym insert into Out;
"""

PATTERN_QL = """
@app:playback
define stream T (sym int, stage int, v int);
@info(name = 'p')
from every e1=T[stage == 1] -> e2=T[stage == 2 and sym == e1.sym]
within 60 sec
select e1.sym as sym, e1.v as v1, e2.v as v2
insert into POut;
"""

JOIN_QL = """
@app:playback
define stream L (sym int, lv int);
define stream R (sym int, rv int);
@info(name='j')
from L#window.time(1 sec) join R#window.time(1 sec)
on L.sym == R.sym
select L.sym as sym, L.lv as lv, R.rv as rv
insert into JOut;
"""


def _mk_shard(b, seed, n_syms=12, stages=None):
    rng = np.random.default_rng(seed)
    ts = TS0 + np.arange(b, dtype=np.int64)
    cols = [rng.integers(0, n_syms, b).astype(np.int32)]
    if stages:
        cols.append(rng.integers(1, stages + 1, b).astype(np.int32))
        cols.append(rng.integers(0, 1000, b).astype(np.int32))
    else:
        cols.append(rng.uniform(0, 200, b).astype(np.float32))
        cols.append(rng.integers(1, 100, b, dtype=np.int64))
    return ts, cols


def _rows(host_batch, ncols):
    out = []
    for r in range(host_batch.valid.shape[0]):
        if host_batch.valid[r]:
            out.append(tuple(
                np.asarray(host_batch.cols[i])[r] for i in range(ncols)))
    return out


def _union(shards):
    ts = np.concatenate([s[0] for s in shards])
    ncols = len(shards[0][1])
    cols = [np.concatenate([s[1][i] for s in shards])
            for i in range(ncols)]
    order = np.argsort(ts, kind="stable")
    return ts[order], [c[order] for c in cols]


@pytest.mark.parametrize("ql", [FILTER_QL, WINDOW_QL],
                         ids=["filter", "window"])
def test_data_parallel_bit_equal_per_shard(ql):
    """Pure data-parallel (no routing): shard d's outputs are BIT-EQUAL
    to an independent single-device runtime fed shard d's sub-stream."""
    runner = DataParallelRunner(ql, "q", n_devices=8)
    shards = [_mk_shard(128, d) for d in range(8)]
    now = TS0 + 128
    out, agg = runner.step("S", runner.stack_shards("S", shards), now)
    out_h = jax.device_get(out)
    total = 0
    for d in range(8):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(ql)
        q = rt.queries["q"]
        step = q._make_step()
        b = jax.device_put(batch_from_columns(
            rt.schemas["S"], *shards[d], capacity=128))
        _s, _t, _e, ref, _d = step(
            q.states, {}, jnp.int64(0), b,
            jnp.asarray(now, jnp.int64))
        ref_h = jax.device_get(ref)
        np.testing.assert_array_equal(out_h.valid[d], ref_h.valid)
        for i in range(len(ref_h.cols)):
            np.testing.assert_array_equal(out_h.cols[i][d],
                                          np.asarray(ref_h.cols[i]))
        total += int(np.sum(ref_h.valid))
    # the psum'd aggregate equals the per-shard reference sum: the ONLY
    # cross-shard collective is this output count
    assert int(agg) == total


def test_data_parallel_pattern_routed_equals_single_chip():
    """Key-routed NFA: per-shard pending tables, events all-gathered and
    owner-masked; matches land on the owning shard and equal the
    single-chip replay of the ts-sorted union."""
    runner = DataParallelRunner(PATTERN_QL, "p", n_devices=8,
                                route_cols={"T": 0})
    shards = [_mk_shard(64, 100 + d, stages=2) for d in range(8)]
    now = TS0 + 64
    out, _agg = runner.step("T", runner.stack_shards("T", shards), now)
    out_h = jax.device_get(out)
    got = []
    for d in range(8):
        for r in range(out_h.valid.shape[1]):
            if out_h.valid[d, r]:
                sym = int(out_h.cols[0][d, r])
                assert owner_of_host(sym, 8) == d, (sym, d)
                got.append(tuple(int(out_h.cols[i][d, r])
                                 for i in range(3)))
    rt = SiddhiManager().create_siddhi_app_runtime(PATTERN_QL)
    q = rt.queries["p"]
    step = q._step_for_stream("T")
    uts, ucols = _union(shards)
    b = jax.device_put(batch_from_columns(rt.schemas["T"], uts, ucols))
    _n, _s, _t, _e, ref = step(q.nfa_state, q.states, {}, jnp.int64(0),
                               b, jnp.asarray(now, jnp.int64))
    ref_rows = [tuple(int(v) for v in row)
                for row in _rows(jax.device_get(ref), 3)]
    assert got and sorted(got) == sorted(ref_rows)


def test_data_parallel_join_routed_equals_single_chip():
    """Key-routed two-stream join: both sides all-gather + owner-mask,
    each shard's banded pools hold only its keys; the joined rows equal
    the single-chip union replay (sizes stay below JOIN_CAP so neither
    run truncates)."""
    def mk(b, seed):
        rng = np.random.default_rng(seed)
        ts = TS0 + np.arange(b, dtype=np.int64)
        return ts, [rng.integers(0, 12, b).astype(np.int32),
                    rng.integers(0, 1000, b).astype(np.int32)]

    # route_cols="auto": the banded equi conjunct's bare columns
    # (ops/join.py equi_route_columns) become the routing key
    runner = DataParallelRunner(JOIN_QL, "j", n_devices=8,
                                route_cols="auto")
    assert runner.route_cols == {"L": 0, "R": 0}
    lsh = [mk(8, d) for d in range(8)]
    rsh = [mk(8, 50 + d) for d in range(8)]
    now = TS0 + 8
    runner.step("L", runner.stack_shards("L", lsh), now)
    out, _ = runner.step("R", runner.stack_shards("R", rsh), now)
    out_h = jax.device_get(out)
    got = []
    for d in range(8):
        for r in range(out_h.valid.shape[1]):
            if out_h.valid[d, r]:
                sym = int(out_h.cols[0][d, r])
                assert owner_of_host(sym, 8) == d, (sym, d)
                got.append(tuple(int(out_h.cols[i][d, r])
                                 for i in range(3)))

    rt = SiddhiManager().create_siddhi_app_runtime(JOIN_QL)
    q = rt.queries["j"]
    step_l = q._step_for_side("L")
    step_r = q._step_for_side("R")
    now_dev = jnp.asarray(now, jnp.int64)
    uts, ucols = _union(lsh)
    bl = jax.device_put(batch_from_columns(rt.schemas["L"], uts, ucols))
    my_l, sel, _t, em, _o, lost_l, _d = step_l(
        q.side_states["L"], q.side_states["R"], q.states, {},
        jnp.int64(0), bl, now_dev)
    uts2, ucols2 = _union(rsh)
    br = jax.device_put(batch_from_columns(rt.schemas["R"], uts2,
                                           ucols2))
    _my_r, _sel, _t, _em, ref, lost_r, _d = step_r(
        q.side_states["R"], my_l, sel, {}, em, br, now_dev)
    assert int(jax.device_get(lost_l)) == 0
    assert int(jax.device_get(lost_r)) == 0
    ref_rows = [tuple(int(v) for v in row)
                for row in _rows(jax.device_get(ref), 3)]
    assert got and sorted(got) == sorted(ref_rows)


def test_data_parallel_rejects_table_readers():
    QL = """
    @app:playback
    define stream S (a int);
    define table T (a int);
    @info(name='q') from S join T on S.a == T.a
    select S.a as a insert into Out;
    """
    with pytest.raises(ValueError, match="table"):
        DataParallelRunner(QL, "q", n_devices=8)


# ---- partition blocks: restore re-placement (the dedupe FIX) ----------

PART_QL = """
@app:playback
define stream S (sym string, v int);
partition with (sym of S) begin
  @info(name='pq') from S#window.lengthBatch(4)
  select sym, sum(v) as total group by sym insert into POut;
end;
"""


def _drive_partition(rt, n=24):
    from siddhi_tpu import Event, StreamCallback
    got = []
    rt.add_callback("POut", StreamCallback(
        fn=lambda evs: got.extend(tuple(e.data) for e in evs)))
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(n):
        h.send(Event(TS0 + i, ("k%d" % (i % 5), i)))
    return got


def test_partition_restore_places_each_leaf_once(monkeypatch):
    """The FIX: a mesh restore places shards DIRECTLY from the host
    snapshot — one device_put per leaf, never a fresh single-device
    copy that a second pass then re-places."""
    mesh = sharding.build_mesh(8, axis="keys")
    rt = SiddhiManager().create_siddhi_app_runtime(
        PART_QL, partition_mesh=mesh)
    got = _drive_partition(rt)
    assert got
    blk = next(iter(rt.partitions.values()))
    snap = blk.snapshot_state()
    n_leaves = len(jax.tree_util.tree_leaves(
        {k: snap[k] for k in ("qstates", "slot_tbl", "emitted", "lost")}))

    real_put = jax.device_put
    puts = [0]

    def counting_put(x, *a, **kw):
        puts[0] += 1
        return real_put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counting_put)
    blk.restore_state(snap)
    assert puts[0] == n_leaves, (puts[0], n_leaves)
    rt.shutdown()


def test_partition_mesh_redundant_placement_is_skipped():
    """Steady-state re-placement transfers nothing: the state is
    already laid out, so _apply_mesh_sharding dedupes to zero puts."""
    mesh = sharding.build_mesh(8, axis="keys")
    rt = SiddhiManager().create_siddhi_app_runtime(
        PART_QL, partition_mesh=mesh)
    _drive_partition(rt)
    blk = next(iter(rt.partitions.values()))
    stats = sharding.placement_stats
    before = stats.snapshot()
    blk._apply_mesh_sharding()
    after = stats.snapshot()
    assert after["device_puts"] == before["device_puts"]
    assert after["skipped"] > before["skipped"]
    rt.shutdown()


def test_partition_mesh_statistics_reports_devices():
    mesh = sharding.build_mesh(8, axis="keys")
    rt = SiddhiManager().create_siddhi_app_runtime(
        PART_QL, partition_mesh=mesh)
    _drive_partition(rt)
    st = rt.statistics()
    assert st["mesh"]["n_devices"] == 8
    blk = next(iter(rt.partitions.values()))
    part = st["mesh"]["partitions"][blk.name]
    assert part["slots_per_device"] * 8 == part["slots"]
    text = rt.metrics.prometheus_text()
    assert 'device="0"' in text and 'device="7"' in text
    rt.shutdown()


# ---- tenant pools on a mesh -------------------------------------------

TENANT_QL = """
define stream In (v double, k long);
@info(name='q')
from In[v > ${lo:double} and v < ${hi:double}]#window.lengthBatch(16)
select v, k
insert into Out;
"""


def _mk_pool(mesh=None, slots=8, max_tenants=64, name="mt"):
    from siddhi_tpu.serving import TemplateRegistry
    reg = TemplateRegistry(SiddhiManager())
    return reg.pool(TENANT_QL, warm=False, slots=slots,
                    max_tenants=max_tenants, batch_max=64,
                    mesh=mesh, name=name)


def _chunk(n, seed=3):
    rng = np.random.default_rng(seed)
    ts = TS0 + np.arange(n, dtype=np.int64)
    return ts, [rng.uniform(0, 200, n),
                rng.integers(0, 1000, n, dtype=np.int64)]


def _bindings(i):
    return {"lo": 1.0 + (i % 7), "hi": 199.0 - (i % 7)}


def test_pool_mesh_bit_equal_to_unsharded():
    """The slot-axis-sharded pool delivers the SAME per-tenant rows and
    counters as an unsharded pool fed identical traffic."""
    mesh = sharding.build_mesh(8)
    got_m, got_u = {}, {}
    pools = []
    for mesh_arg, got in ((mesh, got_m), (None, got_u)):
        pool = _mk_pool(mesh=mesh_arg, slots=16, max_tenants=16,
                        name=f"eq{'m' if mesh_arg is not None else 'u'}")
        for i in range(16):
            pool.add_tenant(f"t{i}", _bindings(i))
            got.setdefault(f"t{i}", [])
            pool.add_callback(
                f"t{i}",
                functools.partial(
                    lambda evs, acc: acc.extend(
                        tuple(e.data) for e in evs), acc=got[f"t{i}"]))
        ts, cols = _chunk(96)
        for i in range(16):
            pool.send(f"t{i}", ts, cols)
        pool.flush()
        pools.append(pool)
    assert got_m == got_u
    assert any(got_m.values())
    sm = pools[0].statistics()
    su = pools[1].statistics()
    for tid in sm["tenants"]:
        assert sm["tenants"][tid]["emitted"] == \
            su["tenants"][tid]["emitted"]
    for p in pools:
        p.shutdown()


def test_pool_mesh_churn_zero_recompiles(monkeypatch):
    """Steady-state tenant churn on a SHARDED pool compiles nothing:
    slot assignment is an .at[].set on the placed arrays (the
    counting-jit guard of test_serving.py, mesh flavor)."""
    real_jit = jax.jit
    traces = [0]

    def counting_jit(f, *a, **kw):
        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            traces[0] += 1
            return f(*args, **kwargs)
        return real_jit(wrapped, *a, **kw)

    monkeypatch.setattr(jax, "jit", counting_jit)
    pool = _mk_pool(mesh=sharding.build_mesh(8), slots=8, max_tenants=8,
                    name="churn")
    for i in range(4):
        pool.add_tenant(f"t{i}", _bindings(i))
    ts, cols = _chunk(32)
    pool.send("t0", ts, cols)
    pool.flush()
    warm = traces[0]
    assert warm > 0
    for i in range(3):
        pool.remove_tenant("t1")
        pool.add_tenant("t1", _bindings(i))
        pool.add_tenant("x", _bindings(i + 1))
        pool.remove_tenant("x")
        pool.send("t0", ts, cols)
        pool.send("t1", ts, cols)
        pool.flush()
    assert traces[0] == warm, "churn on a sharded pool must not retrace"
    pool.shutdown()


def test_pool_mesh_balanced_placement_and_admission():
    """Tenants spread evenly over devices (the least-loaded device gets
    the next slot) and admission accounts per-device budgets."""
    mesh = sharding.build_mesh(8)
    pool = _mk_pool(mesh=mesh, slots=16, max_tenants=16, name="bal")
    for i in range(16):
        pool.add_tenant(f"t{i}", _bindings(i))
    st = pool.statistics()
    loads = [e["slots_placed"] for e in
             st["mesh"]["per_device"].values()]
    assert loads == [2] * 8
    ok, reason = pool.admit()
    assert not ok and "slot" in reason
    from siddhi_tpu.serving import AdmissionError
    with pytest.raises(AdmissionError) as ei:
        pool.add_tenant("overflow", _bindings(0))
    assert ei.value.saturation["cause"] == "slots-exhausted"
    pool.shutdown()


def test_pool_mesh_per_device_observability():
    """statistics()['mesh'] + the `device=` labeled gauge families:
    slots placed, rows ingested and per-device collection read time."""
    mesh = sharding.build_mesh(8)
    pool = _mk_pool(mesh=mesh, slots=8, max_tenants=8, name="obs")
    for i in range(8):
        pool.add_tenant(f"t{i}", _bindings(i))
    ts, cols = _chunk(64)
    for i in range(8):
        pool.send(f"t{i}", ts, cols)
    pool.flush()
    st = pool.statistics()
    m = st["mesh"]
    assert m["n_devices"] == 8 and m["slots_per_device"] == 1
    assert all(e["rows_ingested"] == 64
               for e in m["per_device"].values())
    assert all(e["collect_ms"] >= 0.0
               for e in m["per_device"].values())
    text = pool.metrics.prometheus_text()
    for fam in ("siddhi_obs_mesh_slots_placed",
                "siddhi_obs_mesh_rows_ingested",
                "siddhi_obs_mesh_collect_ms"):
        assert f'{fam}{{device="3"}}' in text, (fam, text[:2000])
    pool.shutdown()


def test_pool_mesh_warmup_compiles_sharded_programs():
    """AOT warmup through the CompileService carries the slot-axis
    sharding: the telemetry proves the SHARDED program compiled (not a
    single-device twin that never dispatches)."""
    pool = _mk_pool(mesh=sharding.build_mesh(8), slots=8, max_tenants=8,
                    name="warmsh")
    pool.warmup([64])
    comp = pool.statistics()["compile"]
    assert comp["warmups"] == 1
    assert comp["sharded_programs"] >= 1
    # and the warmed program really is the dispatch program: a round
    # after warmup must not add a trace
    pool.add_tenant("a", _bindings(0))
    ts, cols = _chunk(64)
    pool.send("a", ts, cols)
    pool.flush()
    assert pool.statistics()["tenants"]["a"]["pending"] == 0
    pool.shutdown()


def test_pool_mesh_snapshot_restore_isolated():
    """restore_tenant on a sharded pool writes one slot; every other
    tenant's state stays bit-identical (the .at[].set lands in the
    owning shard)."""
    pool = _mk_pool(mesh=sharding.build_mesh(8), slots=8, max_tenants=8,
                    name="snap")
    for i in range(4):
        pool.add_tenant(f"t{i}", _bindings(i))
    ts, cols = _chunk(32)
    for i in range(4):
        pool.send(f"t{i}", ts, cols)
    pool.flush()
    snap = pool.snapshot_tenant("t2")
    before = jax.device_get(pool._states)
    pool.restore_tenant("t2", snap)
    after = jax.device_get(pool._states)
    for qn in before:
        for lb, la in zip(jax.tree_util.tree_leaves(before[qn]),
                          jax.tree_util.tree_leaves(after[qn])):
            np.testing.assert_array_equal(np.asarray(lb),
                                          np.asarray(la))
    pool.shutdown()


# ---- forced CPU devices through the environment alone -------------------


def test_forced_device_shim_subprocess():
    """A clean subprocess given forced host devices through its
    environment only (JAX_PLATFORMS + XLA_FLAGS, no config re-pin) sees
    8 devices and places a sharded pytree — the env a CPU run of the
    multichip bench config gets."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import jax\n"
        "import numpy as np\n"
        "import siddhi_tpu\n"
        "from siddhi_tpu.parallel import sharding\n"
        "assert len(jax.devices()) == 8, jax.devices()\n"
        "mesh = sharding.build_mesh(8)\n"
        "t = sharding.shard_pytree({'x': np.arange(16)}, mesh,\n"
        "                          sharding.DATA_PARALLEL_RULES)\n"
        "assert len(t['x'].addressable_shards) == 8\n"
        "print('SHIM_OK')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHIM_OK" in proc.stdout


# ---- metrics_dump --device filter -------------------------------------


def test_metrics_dump_device_filter_unit():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import metrics_dump
    text = "\n".join([
        "# TYPE siddhi_p_mesh_slots_placed gauge",
        'siddhi_p_mesh_slots_placed{device="0"} 2 1',
        'siddhi_p_mesh_slots_placed{device="1"} 3 1',
        "siddhi_p_pool_rounds 4 1",
        "siddhi_p_mesh_device_1_rows 9 1",
    ])
    kept = metrics_dump.filter_device(text, "1")
    assert 'device="1"' in kept
    assert 'device="0"' not in kept
    assert "siddhi_p_mesh_device_1_rows" in kept
    assert "pool_rounds" not in kept

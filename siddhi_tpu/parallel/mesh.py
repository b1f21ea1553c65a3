"""Data-parallel mesh execution: the planner's own compiled query steps
run under ``shard_map`` over a 1-D device mesh, with per-shard
device-local state and collectives ONLY at the aggregate boundary.

Execution model (ROADMAP item 1, SURVEY §2.6):

- the ingest chunk batch axis splits over the mesh: shard d receives its
  own sub-stream slice (a ``(n_devices, B)``-stacked ``EventBatch``
  placed with ``NamedSharding(P(axis))`` — ONE transfer, each device
  gets only its rows);
- window pools, NFA pending tables, group-by tables and banded-join
  sorted pools stay DEVICE-LOCAL: shard d's state never crosses the
  interconnect (rule table: ``sharding.DATA_PARALLEL_RULES``);
- optional key routing (``route_cols``): every shard's ingest is
  all-gathered, each shard keeps the events whose key hash it owns
  (owner = hash(key) % n), restoring event-time order before
  order-sensitive steps — a key's keyed state then lives on exactly one
  shard while being reachable from every shard's input;
- ``psum`` crosses shards ONLY for aggregate outputs: the per-step
  emitted-row count is all-reduced so callers read ONE replicated
  number instead of gathering per-shard outputs.

This module is the measured multi-chip layer behind
``bench.py multichip`` and ``__graft_entry__.dryrun_multichip``; the
bit-equivalence sweep against single-chip replays lives in
tests/test_mesh.py.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import sharding
from ..core.event import batch_from_columns

# Knuth multiplicative hash — the one host/device-mirrored owner
# function (also the routing hash of __graft_entry__.dryrun_multichip)
_OWNER_MULT = 2654435761


def owner_of(codes, n_devices: int):
    """Device-side shard owner of each key code ([B] int -> [B] int32)."""
    h = (codes.astype(jnp.uint32) * jnp.uint32(_OWNER_MULT)) \
        >> jnp.uint32(8)
    return (h % jnp.uint32(n_devices)).astype(jnp.int32)


def owner_of_host(code: int, n_devices: int) -> int:
    """Host mirror of owner_of() for assertions/tests."""
    return (((code * _OWNER_MULT) & 0xFFFFFFFF) >> 8) % n_devices


def _peel(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _expand(tree):
    return jax.tree_util.tree_map(lambda x: jnp.expand_dims(x, 0), tree)


class DataParallelRunner:
    """ONE query of ONE app executed data-parallel over a mesh.

    Supports the three step families the planner compiles:

    - plain row queries (filter / window / group-by chains):
      ``QueryRuntime._make_step``;
    - pattern/sequence queries (NFA): ``_step_for_stream``;
    - two-stream joins: ``_step_for_side`` per trigger side.

    ``route_cols`` maps a trigger stream id to the index of its key
    column; routed streams all-gather + owner-mask (keyed state — group
    -by tables, NFA pending per key, join pools banded along the key
    axis — lands on the owning shard). Streams not in the map run pure
    data-parallel: each shard processes its own sub-stream.
    """

    def __init__(self, ql: str, query: str, mesh=None, n_devices=None,
                 route_cols: Optional[dict] = None):
        from ..core.manager import SiddhiManager
        from ..core.runtime import (JoinQueryRuntime, PatternQueryRuntime,
                                    QueryRuntime)
        self.mesh = mesh if mesh is not None \
            else sharding.build_mesh(n_devices)
        self.axis = self.mesh.axis_names[0]
        self.n = int(self.mesh.shape[self.axis])
        self.mgr = SiddhiManager()
        self.rt = self.mgr.create_siddhi_app_runtime(ql)
        q = self.rt.queries[query]
        self.q = q
        if route_cols == "auto":
            # joins carry their own routing key: the banded equi
            # conjunct's bare columns (ops/join.py equi_route_columns)
            rc = None
            for cross in getattr(q, "crosses", {}).values():
                rc = getattr(cross, "route_cols", None) or rc
            if rc is None:
                raise ValueError(
                    f"query '{query}' has no bare-column equi key to "
                    "route by (route_cols='auto' needs one)")
            route_cols = {q.in_schemas[s].stream_id: idx
                          for s, idx in rc.items()}
        self.route_cols = dict(route_cols or {})
        if getattr(q, "table_deps", ()):
            raise ValueError(
                f"query '{query}' reads tables — table state is not "
                "data-parallel (route it through a keyed partition)")
        if isinstance(q, JoinQueryRuntime):
            self.kind = "join"
            self._state = {
                "sides": self._stack({s: q.side_states[s]
                                      for s in ("L", "R")}),
                "sel": self._stack(q.states),
            }
        elif isinstance(q, PatternQueryRuntime):
            self.kind = "pattern"
            self._state = {"nfa": self._stack(q.nfa_state),
                           "sel": self._stack(q.states)}
        elif type(q) is QueryRuntime:
            self.kind = "row"
            self._state = {"states": self._stack(q.states)}
        else:
            raise ValueError(
                f"unsupported runtime {type(q).__name__} for "
                "data-parallel execution")
        self._emitted = self._place(
            np.zeros((self.n,), np.int64))
        self._fns: dict = {}
        self.rows_in = 0

    # -- state / batch placement ------------------------------------------

    def _place(self, tree):
        return sharding.shard_pytree(
            tree, self.mesh, sharding.DATA_PARALLEL_RULES, axis=self.axis)

    def _stack(self, tree):
        """Replicate an init-state pytree onto the leading shard axis and
        place it sharded: each device holds exactly its own copy (one
        batched pytree transfer to host, one placement per leaf)."""
        n = self.n
        host = jax.device_get(tree)
        stacked = jax.tree_util.tree_map(
            lambda x: np.broadcast_to(
                np.asarray(x)[None], (n,) + tuple(np.shape(x))).copy(),
            host)
        return self._place(stacked)

    def stack_shards(self, stream_id: str, shards):
        """Per-shard ``(ts, cols)`` host chunks -> ONE sharded
        ``(n, B)``-stacked EventBatch (device d gets row d only)."""
        schema = self.rt.schemas[stream_id]
        if len(shards) != self.n:
            raise ValueError(f"expected {self.n} shard chunks, got "
                             f"{len(shards)}")
        cap = max(len(np.asarray(ts)) for ts, _ in shards)
        batches = [batch_from_columns(schema, ts, cols, capacity=cap)
                   for ts, cols in shards]
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *batches)
        self.rows_in += sum(len(np.asarray(ts)) for ts, _ in shards)
        return jax.device_put(
            stacked, NamedSharding(self.mesh, P(self.axis)))

    # -- routing ----------------------------------------------------------

    def _router(self, stream_id: str, order_sensitive: bool):
        col = self.route_cols.get(stream_id)
        if col is None:
            return None
        axis, n = self.axis, self.n

        def route(b):
            me = jax.lax.axis_index(axis)
            g = jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, axis).reshape(
                    (-1,) + x.shape[1:]), b)
            routed = g.mask(owner_of(g.cols[col], n) == me)
            if order_sensitive:
                # the all-gather concatenates shard-major; restore
                # event-time order (stable: ties keep shard-major order,
                # the single-chip union replay's exact tie-break)
                key = jnp.where(routed.valid, routed.ts,
                                jnp.int64(2 ** 62))
                perm = jnp.argsort(key, stable=True)
                routed = jax.tree_util.tree_map(lambda x: x[perm], routed)
            return routed

        return route

    # -- compiled steps (cached per trigger+capacity: zero steady-state
    # retraces, the _step_for contract) -----------------------------------

    def _fn_for(self, trigger, cap: int):
        key = (trigger, cap)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        axis = self.axis
        if self.kind == "row":
            step = self.q._make_step()
            route = self._router(trigger, order_sensitive=False)

            def local(state, emitted, batch, now):
                s, b, e = _peel(state["states"]), _peel(batch), emitted[0]
                if route is not None:
                    b = route(b)
                s2, _t, e2, out, _due = step(s, {}, e, b, now)
                agg = jax.lax.psum(out.count().astype(jnp.int64), axis)
                return ({"states": _expand(s2)},
                        jnp.expand_dims(e2, 0), _expand(out), agg)

        elif self.kind == "pattern":
            step = self.q._step_for_stream(trigger)
            route = self._router(trigger, order_sensitive=True)

            def local(state, emitted, batch, now):
                nfa, sel = _peel(state["nfa"]), _peel(state["sel"])
                b, e = _peel(batch), emitted[0]
                if route is not None:
                    b = route(b)
                nfa2, sel2, _t, e2, out = step(nfa, sel, {}, e, b, now)
                agg = jax.lax.psum(out.count().astype(jnp.int64), axis)
                return ({"nfa": _expand(nfa2), "sel": _expand(sel2)},
                        jnp.expand_dims(e2, 0), _expand(out), agg)

        else:  # join: trigger is the side tag "L" | "R"
            side = trigger
            opp = "R" if side == "L" else "L"
            step = self.q._step_for_side(side)
            sid = self.q.in_schemas[side].stream_id
            route = self._router(sid, order_sensitive=False)

            def local(state, emitted, batch, now):
                sides = {s: _peel(state["sides"][s]) for s in ("L", "R")}
                sel = _peel(state["sel"])
                b, e = _peel(batch), emitted[0]
                if route is not None:
                    b = route(b)
                my, sel2, _t, e2, out, _lost, _due = step(
                    sides[side], sides[opp], sel, {}, e, b, now)
                new_sides = dict(state["sides"])
                new_sides[side] = _expand(my)
                agg = jax.lax.psum(out.count().astype(jnp.int64), axis)
                return ({"sides": new_sides, "sel": _expand(sel2)},
                        jnp.expand_dims(e2, 0), _expand(out), agg)

        fn = jax.jit(sharding.shard_map(
            local, self.mesh,
            (P(axis), P(axis), P(axis), P()),
            (P(axis), P(axis), P(axis), P())))
        self._fns[key] = fn
        return fn

    # -- dispatch ---------------------------------------------------------

    def step(self, trigger, stacked_batch, now: int):
        """Advance every shard one step; returns the per-shard stacked
        output batch (device-resident, sharded) and the psum'd aggregate
        emitted-row count (replicated scalar)."""
        fn = self._fn_for(trigger, int(stacked_batch.ts.shape[-1]))
        now_dev = jnp.asarray(int(now), dtype=jnp.int64)
        self._state, self._emitted, out, agg = fn(
            self._state, self._emitted, stacked_batch, now_dev)
        return out, agg

    def send_shards(self, stream_id: str, shards, now: int):
        """stack + step for the common single-trigger case."""
        trigger = stream_id if self.kind != "join" else next(
            s for s in ("L", "R")
            if self.q.in_schemas[s].stream_id == stream_id)
        return self.step(trigger, self.stack_shards(stream_id, shards),
                         now)

    @property
    def emitted_total(self) -> int:
        """Aggregate emitted rows across shards (one reduction, one
        scalar read — never a per-shard gather)."""
        return int(jax.device_get(jnp.sum(self._emitted)))

    def explain(self) -> dict:
        """Data-parallel placement decisions for the explain surface
        (obs/explain.py): step family, mesh geometry, which streams
        route by key (and on which column), and the rule-table
        placement per state leaf. Host-side metadata only — no device
        reads, no new programs."""
        return {
            "kind": self.kind,
            "query": self.q.name,
            "axis": self.axis,
            "n_devices": self.n,
            "route_cols": {sid: int(col) for sid, col
                           in sorted(self.route_cols.items())},
            "psum_boundary": "aggregate-emitted-count",
            "placement": sharding.describe_placement(
                self._state, sharding.DATA_PARALLEL_RULES, self.axis),
        }


# -- measured scaling arms (bench.py `multichip`, __graft_entry__) ----------

FILTER_QL = """
    @app:playback
    define stream S (sym int, price float, volume long);
    @info(name = 'q')
    from S[price > 100.0] select sym, price insert into Out;
"""

SEQ5_QL = """
    @app:playback
    define stream T (sym int, stage int, v int);
    @info(name = 'p')
    from every e1=T[stage == 1] -> e2=T[stage == 2] -> e3=T[stage == 3]
      -> e4=T[stage == 4] -> e5=T[stage == 5]
    within 60 sec
    select e1.sym as sym, e5.v as v insert into POut;
"""

TENANT_QL = """
define stream In (v double, k long);
@info(name='q')
from In[v > ${lo:double} and v < ${hi:double}]#window.lengthBatch(256)
select v, k
insert into Out;
"""

TS0 = 1_700_000_000_000


def _filter_shard(b: int, seed: int):
    rng = np.random.default_rng(seed)
    ts = TS0 + np.arange(b, dtype=np.int64)
    return ts, [rng.integers(0, 64, b).astype(np.int32),
                rng.uniform(0, 200, b).astype(np.float32),
                rng.integers(1, 100, b, dtype=np.int64)]


def _seq5_shard(b: int, seed: int):
    rng = np.random.default_rng(1000 + seed)
    ts = TS0 + np.arange(b, dtype=np.int64)
    return ts, [rng.integers(0, 64, b).astype(np.int32),
                rng.integers(1, 6, b).astype(np.int32),
                rng.integers(0, 1000, b).astype(np.int32)]


def _arm_entry(events: int, seconds: float, n: int,
               eps_1dev: Optional[float]) -> dict:
    eps = events / seconds
    entry = {"n_devices": n,
             "eps_aggregate": round(eps, 1),
             "eps_per_device": round(eps / n, 1),
             "seconds": round(seconds, 3)}
    if eps_1dev:
        entry["eps_1dev"] = round(eps_1dev, 1)
        entry["scaling"] = round(eps / eps_1dev, 2)
        entry["scaling_efficiency"] = round(eps / (n * eps_1dev), 3)
    return entry


def _measure_runner(ql, query, n: int, chunk: int, iters: int,
                    reps: int, mk_shard) -> float:
    """Best-of-reps wall seconds for `iters` stacked rounds of `chunk`
    rows per shard (weak scaling: per-device load is constant)."""
    runner = DataParallelRunner(ql, query, n_devices=n)
    sid = next(iter(runner.rt.schemas))
    batches = [runner.stack_shards(
        sid, [mk_shard(chunk, d + i * n) for d in range(n)])
        for i in range(2)]
    now = TS0 + chunk
    out, _ = runner.step(sid, batches[0], now)   # compile off the clock
    jax.block_until_ready(out.valid)
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(iters):
            out, _ = runner.step(sid, batches[i % 2], now + i)
        # ONE sync per timed rep closes the async-dispatch pipeline —
        # the measurement IS the sync (bench.py _drain pattern)
        jax.block_until_ready(out.valid)  # lint: disable=host-sync-in-loop
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def _measure_pool(n_mesh: int, tenants: int, rows: int, batch_max: int,
                  reps: int) -> float:
    """Best-of-reps wall seconds for one full pooled pass: every tenant
    sends `rows` rows, fair rounds drain them. n_mesh > 1 shards the
    slot axis (1/n of the tenants per device)."""
    from ..serving import TemplateRegistry
    from ..core.manager import SiddhiManager
    mesh = sharding.build_mesh(n_mesh) if n_mesh > 1 else None
    reg = TemplateRegistry(SiddhiManager())
    pool = reg.pool(TENANT_QL, warm=False, slots=tenants,
                    max_tenants=tenants, batch_max=batch_max,
                    mesh=mesh, name=f"mc{n_mesh}")
    pool.warmup([batch_max])
    for i in range(tenants):
        pool.add_tenant(f"t{i}", {"lo": 20.0 + (i % 16),
                                  "hi": 180.0 - (i % 16)})
    rng = np.random.default_rng(11)
    ts = TS0 + np.arange(rows, dtype=np.int64)
    cols = [rng.uniform(0, 200, rows), rng.integers(
        0, 1 << 20, rows, dtype=np.int64)]
    last = {}
    # terminal maps sid -> LIST of device batches; keep the newest
    pool.batch_callbacks.append(
        lambda terminal: last.update(out=next(
            iter(terminal.values()))[-1] if terminal else None))

    def one_pass():
        for i in range(tenants):
            pool.send(f"t{i}", ts, cols)
        pool.flush()
        if last.get("out") is not None:
            jax.block_until_ready(last["out"].valid)

    one_pass()   # dispatch caches settle off the clock
    best = min(_timed(one_pass) for _ in range(reps))
    pool.shutdown()
    return best


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def measure_scaling(n_devices: int = 4, chunk: int = 16384,
                    seq_chunk: Optional[int] = None,
                    iters: int = 4, reps: int = 2,
                    tenants: Optional[int] = None,
                    tenant_rows: int = 1024,
                    arms=("filter", "seq5", "tenants")) -> dict:
    """The MULTICHIP acceptance measurement: aggregate events/s for each
    arm at n_devices vs 1 device (weak scaling — per-device load held
    constant), with per-arm scaling efficiency. Returns the JSON-ready
    dict bench.py `multichip` and __graft_entry__ both emit.

    `platform`, `device_kind` and `device_count` say WHERE it ran: on
    forced CPU host devices every "device" shares the host's cores, so
    the numbers guard plumbing, not parallelism, and are never a device
    metric."""
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"measure_scaling wants {n_devices} devices, "
            f"{len(jax.devices())} visible")
    if tenants is None:
        tenants = 64 * n_devices
    if seq_chunk is None:
        seq_chunk = max(256, chunk // 4)
    out: dict = {
        "n_devices": n_devices,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "arms": {},
    }
    if "filter" in arms:
        dt1 = _measure_runner(FILTER_QL, "q", 1, chunk, iters, reps,
                              _filter_shard)
        dtn = _measure_runner(FILTER_QL, "q", n_devices, chunk, iters,
                              reps, _filter_shard)
        out["arms"]["filter"] = _arm_entry(
            n_devices * chunk * iters, dtn, n_devices,
            chunk * iters / dt1)
    if "seq5" in arms:
        dt1 = _measure_runner(SEQ5_QL, "p", 1, seq_chunk, iters, reps,
                              _seq5_shard)
        dtn = _measure_runner(SEQ5_QL, "p", n_devices, seq_chunk, iters,
                              reps, _seq5_shard)
        out["arms"]["seq5"] = _arm_entry(
            n_devices * seq_chunk * iters, dtn, n_devices,
            seq_chunk * iters / dt1)
    if "tenants" in arms:
        batch_max = min(1024, tenant_rows)
        t_small = max(n_devices, tenants // n_devices)
        dt1 = _measure_pool(1, t_small, tenant_rows, batch_max, reps)
        dtn = _measure_pool(n_devices, tenants, tenant_rows, batch_max,
                            reps)
        entry = _arm_entry(tenants * tenant_rows, dtn, n_devices,
                           t_small * tenant_rows / dt1)
        entry["tenants"] = tenants
        entry["tenants_1dev"] = t_small
        out["arms"]["tenants"] = entry
    return out

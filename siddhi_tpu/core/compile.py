"""Ahead-of-time compile service: parallel step lowering + persistent
cache telemetry (docs/compile_cache.md).

Every query in an app compiles to one (or a few) jitted step programs.
Left to the default lazy path, those programs compile serially, one at a
time, on the first chunk that reaches each query — for a realistic app
that is minutes of wall clock before the first result, paid AFTER
traffic has started arriving. Siddhi deploys in milliseconds because its
executor tree is interpreted; the TPU build gets the same deploy-time
behavior by compiling everything up front, in parallel, and persisting the
results:

1. `CompileService.specs(buckets)` enumerates every jitted step the app
   can dispatch for the configured ingest buckets — per-query row +
   packed steps, fused-chain steps, per-stream pattern steps, join side
   steps, partition trigger steps, and the cap-16 timer-batch shapes —
   together with zero-filled arguments of the exact shapes/dtypes the
   runtime will pass.
2. `warmup()` executes each spec once on a thread pool. XLA compilation
   releases the GIL, so N steps compile concurrently and wall time is
   max(compile) instead of sum(compile). Warming by *calling the
   runtime's own cached jit object* (not a parallel AOT handle)
   guarantees the dispatch-path caches are the ones that get hot: the
   first real chunk performs zero traces and zero compiles.
3. Compiles are persisted via JAX's compilation cache
   (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`, wired in
   `siddhi_tpu/__init__.py` with min-compile-time/min-entry-size 0 so
   every program is written). A spec that fails raises `WarmupError`
   once every spec was tried — nothing is left to compile lazily.
   A warm process start loads executables from disk instead of
   recompiling; the hit/miss counters below make that observable.

Packed-ingest steps are keyed by the sticky per-stream encoding tuple
(core/ingest.py). Traffic has not arrived at warmup time, so the service
compiles the encoder's INITIAL encoding by default (affine timestamps +
constant columns — what the first chunk of zeros-and-ramps traffic
produces) and accepts per-stream `samples` to derive the encoding real
traffic will settle on.

Telemetry (per app, cumulative over warmups) surfaces through
`SiddhiAppRuntime.statistics()["compile"]` and the warmup() return
value: program count, compile wall ms, persistent-cache hits/misses,
and at DETAIL stats level the per-step timing list.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .event import EventBatch, StreamSchema
from .ingest import (initial_encoding, encoding_for_sample, layout,
                     pipeline_enabled, pipeline_split_cap,
                     zero_packed_buffer)

# -- persistent-cache hit/miss counters --------------------------------------
# jax.monitoring events are process-global; one listener feeds every
# CompileService (snapshots delta around each warmup).

_CACHE_COUNTS = {"hits": 0, "misses": 0}
_CACHE_LOCK = threading.Lock()


def _cache_event(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _CACHE_LOCK:
            _CACHE_COUNTS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _CACHE_LOCK:
            _CACHE_COUNTS["misses"] += 1


try:  # monitoring is a stable public module, but stay import-safe
    jax.monitoring.register_event_listener(_cache_event)
except Exception:  # noqa: BLE001 — telemetry is best-effort
    pass


def cache_counts() -> dict:
    with _CACHE_LOCK:
        return dict(_CACHE_COUNTS)


def warm_buckets_from_env() -> tuple:
    """`SIDDHI_TPU_WARM_BUCKETS='1024,65536'` -> (1024, 65536). Unset or
    empty/'0' means no automatic warmup at start()."""
    raw = os.environ.get("SIDDHI_TPU_WARM_BUCKETS", "")
    if not raw or raw.strip() in ("0", "off"):
        return ()
    from .runtime import bucket_capacity
    out = []
    for part in raw.split(","):
        part = part.strip()
        if part:
            out.append(bucket_capacity(int(part)))
    return tuple(sorted(set(out)))


def _workers_from_env() -> int:
    raw = os.environ.get("SIDDHI_TPU_COMPILE_WORKERS", "")
    if raw:
        return max(1, int(raw))
    return max(1, min(8, os.cpu_count() or 1))


# -- zero-argument builders ---------------------------------------------------
#
# Two modes, selected by `abstract_spec_args()`:
#
# - concrete (default): real zero device buffers — what warmup() calls
#   the jitted steps with (the call donates its arguments, so the
#   builders allocate fresh buffers, never the runtime's own state).
# - abstract: `jax.ShapeDtypeStruct` leaves — what the compiled-program
#   auditor (analysis/programs.py) traces/lowers the same specs with.
#   Even a trivial `jnp.zeros` dispatches a fill program through the
#   persistent compile cache, so the audit's zero-device-work /
#   zero-new-compiles contract requires that NO concrete array is ever
#   built on the audit path.

_ABSTRACT_SPECS = threading.local()


def _abstract() -> bool:
    return getattr(_ABSTRACT_SPECS, "on", False)


@contextlib.contextmanager
def abstract_spec_args():
    """Within this context every spec builder emits
    `jax.ShapeDtypeStruct` argument leaves instead of zero device
    buffers. Thread-local: a concurrent warmup on another thread still
    materializes real buffers."""
    _ABSTRACT_SPECS.on = True
    try:
        yield
    finally:
        _ABSTRACT_SPECS.on = False


def spec_args_abstract() -> bool:
    """True inside `abstract_spec_args()` — spec builders that cannot
    route every allocation through the helpers below (mesh placement in
    serving/pool.py needs concrete buffers) branch on this."""
    return _abstract()


def _mesh_sharding(x):
    """The leaf's sharding when it spans several devices (mesh-placed
    state), else None. Warm args must carry it: the program traffic
    dispatches on a mesh is the sharded one, not a device-0 twin."""
    sh = getattr(x, "sharding", None)
    return sh if sh is not None and len(sh.device_set) > 1 else None


def _sds(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(jnp.shape(x)), jnp.result_type(x),
                                sharding=_mesh_sharding(x))


def zeros_array(shape, dtype):
    """`jnp.zeros` twin that respects abstract-spec mode (the serving
    pool's vmapped spec builders construct their stacked-slot arguments
    through this so pool programs audit without device work)."""
    if _abstract():
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
    return jnp.zeros(shape, dtype)


def _zeros_like_tree(tree):
    if _abstract():
        return jax.tree_util.tree_map(_sds, tree)
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(jnp.shape(x), jnp.result_type(x),
                            device=_mesh_sharding(x)), tree)


def _zero_batch(schema: StreamSchema, capacity: int) -> EventBatch:
    if _abstract():
        from .types import SET_LANES, AttrType, np_dtype

        def col(t):
            if t is AttrType.OBJECT:
                return jax.ShapeDtypeStruct((capacity, 1 + SET_LANES),
                                            jnp.int64)
            return jax.ShapeDtypeStruct((capacity,),
                                        jnp.dtype(np_dtype(t)))
        return EventBatch(
            ts=jax.ShapeDtypeStruct((capacity,), jnp.int64),
            cols=tuple(col(t) for t in schema.types),
            nulls=tuple(jax.ShapeDtypeStruct((capacity,), jnp.bool_)
                        for _ in schema.types),
            kind=jax.ShapeDtypeStruct((capacity,), jnp.int32),
            valid=jax.ShapeDtypeStruct((capacity,), jnp.bool_))
    return EventBatch.empty(schema, capacity)


def _zero_packed(schema: StreamSchema, enc: tuple, capacity: int):
    if _abstract():
        _, _, total = layout(len(schema.types), enc, capacity)
        return jax.ShapeDtypeStruct((total,), jnp.uint8)
    return zero_packed_buffer(schema, enc, capacity)


def _zero_now():
    if _abstract():
        return jax.ShapeDtypeStruct((), jnp.int64)
    return jnp.asarray(0, dtype=jnp.int64)


class WarmupError(RuntimeError):
    """One or more warmup specs failed to compile or run."""

    def __init__(self, app_name: str, errors: list):
        self.errors = errors
        super().__init__(
            f"app '{app_name}': {len(errors)} warmup spec(s) failed: "
            f"{errors[:3]}")


class CompileSpec:
    """One warmable program: a display key + a builder that returns
    (jitted_fn, args). The builder runs on the main thread (it touches
    the runtime's jit caches); the call runs on the pool."""

    __slots__ = ("key", "build")

    def __init__(self, key: str, build: Callable):
        self.key = key
        self.build = build


class CompileService:
    """Per-app AOT compiler: enumerate + compile every step program."""

    def __init__(self, app):
        self.app = app
        self.records: list[dict] = []   # [{"step", "ms"}...]
        self.total_ms = 0.0
        self.programs = 0
        # programs whose example args carry a multi-device sharding
        # (mesh pools / sharded partitions warm through here — the
        # telemetry proves the AOT pass compiled the SHARDED program,
        # not a single-device twin that never dispatches)
        self.sharded_programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.errors = 0
        self.warmups = 0
        # keys already compiled by THIS service: repeat warmups (pool
        # re-warm after restore, overlapping cap lists) skip them —
        # identical (step, shape-bucket) specs lower exactly once
        self._warmed_keys: set[str] = set()
        # last compiled-program audit summary (analysis/programs.py):
        # a live view — rides statistics()['compile'] and
        # ExplainReport.programs, never the plan hash
        self.audit: Optional[dict] = None
        self._lock = threading.Lock()
        # in-flight warmups: while > 0 the app is compiling and must not
        # be marked ready (service GET /ready load-balancer semantics)
        self._inflight = 0
        # cooperative cancellation: undeploy of a still-warming app sets
        # this so the background warmup bails between specs instead of
        # compiling for a dead app (core/service.py undeploy)
        self._cancel = threading.Event()
        self._threads: list[threading.Thread] = []

    # -- readiness (service /ready) --------------------------------------
    @property
    def ready(self) -> bool:
        """True when no warmup is in flight. An app that never warms up
        (no buckets configured) is trivially ready."""
        with self._lock:
            return self._inflight == 0

    def _begin(self) -> None:
        with self._lock:
            self._inflight += 1

    def _end(self) -> None:
        with self._lock:
            self._inflight -= 1

    def cancel(self) -> None:
        """Ask in-flight warmups to stop compiling (checked between
        specs; the spec being compiled finishes — XLA compiles are not
        interruptible). Sticky until the next warmup begins."""
        self._cancel.set()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for background warmup threads (undeploy: cancel() then
        join() so the inflight count provably returns to zero instead of
        leaking behind a daemon thread)."""
        for t in list(self._threads):
            t.join(timeout=timeout)
        self._threads = [t for t in self._threads if t.is_alive()]

    def warmup_async(self, buckets=None, samples: Optional[dict] = None,
                     workers: Optional[int] = None) -> threading.Thread:
        """Run warmup() on a daemon thread. Readiness flips to False
        SYNCHRONOUSLY (before this returns), so a deploy that kicks off
        an async warm is observed not-ready until the compiles land."""
        self._begin()

        def run():
            try:
                self.warmup(buckets=buckets, samples=samples,
                            workers=workers)
            finally:
                self._end()

        t = threading.Thread(target=run, daemon=True,
                             name=f"siddhi-warmup-{self.app.name}")
        self._threads.append(t)
        t.start()
        return t

    # -- enumeration -----------------------------------------------------

    def _encodings(self, schema: StreamSchema, samples: Optional[dict]):
        """Packed encodings to warm for one stream: the encoder's initial
        (cold) encoding, plus the sticky encoding a traffic sample would
        settle on."""
        encs = [initial_encoding(schema)]
        if samples and schema.stream_id in samples:
            ts, cols = samples[schema.stream_id]
            enc = encoding_for_sample(schema, ts, cols)
            if enc not in encs:
                encs.append(enc)
        return encs

    def specs(self, buckets, samples: Optional[dict] = None) -> list:
        """Every step program the app can dispatch for the given ingest
        buckets, deduplicated by key. Mirrors the dispatch paths:
        send_arrays' per-junction capacity negotiation, process_batch's
        sort-heavy splitting, and the cap-16 timer-batch shapes."""
        from .runtime import (BATCH_BUCKETS, JoinStreamReceiver,
                              PatternStreamReceiver, QueryRuntime,
                              bucket_capacity)
        from ..parallel.partition import BlockStreamReceiver
        from ..resilience.ordering import ring_enabled
        app = self.app
        buckets = tuple(sorted({bucket_capacity(int(b)) for b in buckets}))
        timer_cap = BATCH_BUCKETS[0]
        specs: dict[str, CompileSpec] = {}

        def add(key: str, build: Callable) -> None:
            if key not in specs:
                specs[key] = CompileSpec(key, build)

        fused_members = set()
        for q in app.queries.values():
            ch = getattr(q, "_fused_chain", None)
            if ch is not None:
                for m in ch.queries[1:]:
                    fused_members.add(id(m))

        # -- ingest-path steps, per junction (send_arrays negotiation) ---
        for sid, j in app.junctions.items():
            receivers = list(j.receivers)
            if not receivers or not buckets:
                continue
            packed_ok = all(getattr(r, "supports_packed", False)
                            for r in receivers)
            jcap = BATCH_BUCKETS[-1]
            for r in receivers:
                if packed_ok:
                    rc = getattr(r, "max_packed_capacity",
                                 getattr(r, "max_step_capacity", None))
                else:
                    rc = getattr(r, "max_step_capacity", None)
                if rc is not None:
                    jcap = min(jcap, rc)
            if j.async_conf is not None:
                jcap = min(jcap, j.async_conf[1])
            # cost-evidence chunk caps (plan/optimizer.py) pin the
            # dispatch shape — mirror the send_arrays negotiation so
            # the warmed programs are the ones traffic will hit
            fanout = getattr(j, "fanout", None)
            if fanout is not None and fanout.preferred_cap:
                jcap = min(jcap, fanout.preferred_cap)
            for r in receivers:
                pc = getattr(r, "preferred_ingest_cap", None)
                if pc:
                    jcap = min(jcap, pc)
            caps = sorted({bucket_capacity(min(B, jcap)) for B in buckets})
            if packed_ok and pipeline_enabled():
                # pipelined dispatch splits oversized sends into
                # pipeline_split_cap()-row sub-chunks (core/ingest.py) —
                # warm those shapes so the overlap path hits no compiles
                sub = pipeline_split_cap()
                extra = {bucket_capacity(min(B, jcap, sub))
                         for B in buckets if B > sub}
                if extra - set(caps):
                    caps = sorted(set(caps) | extra)
            if fanout is not None:
                # ONE fused fan-out program covers every grouped
                # subscriber; members keep their timer-batch specs below
                fcaps = sorted({min(c, fanout.max_step_capacity or c)
                                for c in caps})
                self._fanout_specs(add, fanout, j.schema, fcaps,
                                   packed_ok, samples)
            for r in receivers:
                if fanout is not None and fanout.covers(r):
                    continue  # grouped — dispatches via the fanout step
                if isinstance(r, QueryRuntime):
                    if id(r) in fused_members:
                        continue  # fused segments dispatch via the head
                    target = r._fused_chain or r
                    self._query_specs(add, target, j.schema, caps,
                                      packed_ok, samples)
                elif isinstance(r, PatternStreamReceiver):
                    self._pattern_specs(add, r.runtime, r.stream_id,
                                        j.schema, caps, packed_ok, samples)
                elif isinstance(r, JoinStreamReceiver):
                    self._join_specs(add, r.runtime, r.side, j.schema,
                                     caps, packed_ok, samples)
                elif isinstance(r, BlockStreamReceiver):
                    self._partition_specs(add, r.block, sid, j.schema,
                                          caps)
            buf = getattr(app, "_reorder", {}).get(sid)
            if (buf is not None and ring_enabled()
                    and buf.ring_eligible()):
                self._ring_specs(add, sid, j, receivers, fanout,
                                 fused_members, samples)

        # -- named windows: fed by InsertIntoWindowHandler at the feeding
        # query's batch capacity (approximated by the ingest buckets)
        if buckets:
            for wq in app.named_windows.values():
                caps = sorted({bucket_capacity(
                    min(B, wq.max_step_capacity or B)) for B in buckets})
                self._query_specs(add, wq, wq.in_schema, caps,
                                  packed_ok=False, samples=samples)

        # -- timer-batch steps (cap-16 row shapes, scheduler-driven) ------
        for q in list(app.queries.values()) + list(
                app.named_windows.values()):
            self._timer_specs(add, q, timer_cap)
        for block in app.partitions.values():
            self._partition_timer_specs(add, block, timer_cap)
        return list(specs.values())

    # -- per-runtime spec builders ---------------------------------------

    def _query_specs(self, add, q, schema, caps, packed_ok, samples):
        """Row + packed steps for a plain QueryRuntime or a FusedChain."""
        from .runtime import FusedChain
        fused = isinstance(q, FusedChain)
        name = q.name
        app = self.app

        def tstates_zero():
            return {t: _zeros_like_tree(app.tables[t].state)
                    for t in q.table_deps}

        def states_zero():
            if fused:
                return (tuple(_zeros_like_tree(m.states)
                              for m in q.queries),
                        tuple(_zero_now()
                              for _ in q.queries))
            return (_zeros_like_tree(q.states), _zero_now())

        head = q.head if fused else q
        row_caps = sorted({min(c, head.max_step_capacity or c)
                           for c in caps})
        for cap in row_caps:
            def build(cap=cap):
                states, emitted = states_zero()
                fn = q._step_for() if fused else q._step_for(cap)
                return fn, (states, tstates_zero(), emitted,
                            _zero_batch(schema, cap), _zero_now())
            add(f"{name}/row/{cap}", build)
        if packed_ok:
            pk_caps = sorted({min(c, head.max_packed_capacity or c)
                              for c in caps})
            for enc in self._encodings(schema, samples):
                for cap in pk_caps:
                    def build(enc=enc, cap=cap):
                        states, emitted = states_zero()
                        fn = q._packed_step_for(enc, cap)
                        return fn, (states, tstates_zero(), emitted,
                                    _zero_packed(schema, enc, cap))
                    add(f"{name}/packed/{cap}/{','.join(enc)}", build)

    def _fanout_specs(self, add, group, schema, caps, packed_ok,
                      samples):
        """Row + packed steps for a fused fan-out group
        (plan/optimizer.py FanoutGroup): one program per chunk shape
        covering every grouped subscriber of the junction."""
        app = self.app
        name = f"fanout:{group.name}"

        def tstates_zero():
            return {t: _zeros_like_tree(app.tables[t].state)
                    for t in group.table_deps}

        def states_zero():
            st, em = group._read_states()
            return _zeros_like_tree(st), _zeros_like_tree(em)

        for cap in caps:
            def build(cap=cap):
                states, emitted = states_zero()
                fn = group._step_for()
                return fn, (states, tstates_zero(), emitted,
                            _zero_batch(schema, cap), _zero_now())
            add(f"{name}/row/{cap}", build)
        if packed_ok:
            pk_caps = sorted({min(c, group.max_packed_capacity or c)
                              for c in caps})
            for enc in self._encodings(schema, samples):
                for cap in pk_caps:
                    def build(enc=enc, cap=cap):
                        states, emitted = states_zero()
                        fn = group._packed_step_for(enc, cap)
                        return fn, (states, tstates_zero(), emitted,
                                    _zero_packed(schema, enc, cap))
                    add(f"{name}/packed/{cap}/{','.join(enc)}", build)

    def _ring_specs(self, add, sid, j, receivers, fanout, fused_members,
                    samples):
        """Device reorder-ring step (resilience/ordering.py) plus the
        consumer programs its releases dispatch. The ring emits
        EventBatches of capacity 2*C which each receiver's
        process_batch slices at max_step_capacity — warm the ring sort
        AND those row shapes so the opt-in ring costs zero steady-state
        compiles and its programs join the compiled-program audit."""
        from .runtime import (JoinStreamReceiver, PatternStreamReceiver,
                              QueryRuntime)
        from ..parallel.partition import BlockStreamReceiver
        from ..resilience.ordering import ring_step_for
        from .types import np_dtype
        schema = j.schema
        buf = self.app._reorder[sid]
        C = buf.ring_capacity()
        R = 2 * C

        def build():
            fn = ring_step_for(schema.types, C)
            sts = zeros_array((C,), jnp.int64)
            scols = tuple(zeros_array((C,), np_dtype(t))
                          for t in schema.types)
            in_ts = zeros_array((C,), jnp.int64)
            in_cols = tuple(zeros_array((C,), np_dtype(t))
                            for t in schema.types)

            def sc(dt):
                if _abstract():
                    return jax.ShapeDtypeStruct((), jnp.dtype(dt))
                return jnp.asarray(0, dtype=dt)

            return fn, (sts, scols, in_ts, in_cols, sc(jnp.int32),
                        sc(jnp.int32), sc(jnp.int64), sc(jnp.int32),
                        sc(jnp.bool_))
        add(f"ring:{sid}/{C}", build)

        def split_caps(ms):
            # split_batch slices the 2C release into ms-row chunks plus
            # one R%ms-row tail — exactly the shapes dispatch will hit
            if not ms or R <= ms:
                return [R]
            out = {ms}
            if R % ms:
                out.add(R % ms)
            return sorted(out)

        if fanout is not None:
            self._fanout_specs(add, fanout, schema,
                               split_caps(fanout.max_step_capacity),
                               packed_ok=False, samples=samples)
        for r in receivers:
            if fanout is not None and fanout.covers(r):
                continue
            ms = getattr(r, "max_step_capacity", None)
            caps = split_caps(ms)
            if isinstance(r, QueryRuntime):
                if id(r) in fused_members:
                    continue
                target = r._fused_chain or r
                self._query_specs(add, target, schema, caps,
                                  packed_ok=False, samples=samples)
            elif isinstance(r, PatternStreamReceiver):
                self._pattern_specs(add, r.runtime, r.stream_id,
                                    schema, caps, packed_ok=False,
                                    samples=samples)
            elif isinstance(r, JoinStreamReceiver):
                self._join_specs(add, r.runtime, r.side, schema, caps,
                                 packed_ok=False, samples=samples)
            elif isinstance(r, BlockStreamReceiver):
                self._partition_specs(add, r.block, sid, schema, caps)

    def _pattern_specs(self, add, q, stream_id, schema, caps, packed_ok,
                       samples):
        app = self.app

        def tstates_zero():
            return {t: _zeros_like_tree(app.tables[t].state)
                    for t in q.table_deps}

        row_caps = sorted({min(c, q.max_step_capacity or c) for c in caps})
        for cap in row_caps:
            def build(cap=cap):
                fn = q._step_for_stream(stream_id)
                return fn, (_zeros_like_tree(q.nfa_state),
                            _zeros_like_tree(q.states), tstates_zero(),
                            _zero_now(),
                            _zero_batch(schema, cap), _zero_now())
            add(f"{q.name}/pattern/{stream_id}/row/{cap}", build)
        if packed_ok:
            for enc in self._encodings(schema, samples):
                for cap in row_caps:
                    def build(enc=enc, cap=cap):
                        fn = q._step_for_stream(stream_id, (enc, cap))
                        return fn, (_zeros_like_tree(q.nfa_state),
                                    _zeros_like_tree(q.states),
                                    tstates_zero(),
                                    _zero_now(),
                                    _zero_packed(schema, enc, cap))
                    add(f"{q.name}/pattern/{stream_id}/packed/{cap}/"
                        f"{','.join(enc)}", build)

    def _join_specs(self, add, q, side, schema, caps, packed_ok, samples):
        app = self.app
        opp = "R" if side == "L" else "L"

        def tstates_zero():
            return {t: _zeros_like_tree(app.tables[t].state)
                    for t in q.table_deps}

        def side_zero(s):
            return _zeros_like_tree(q.side_states[s])

        row_caps = sorted({min(c, q.max_step_capacity or c) for c in caps})
        for cap in row_caps:
            def build(cap=cap):
                fn = q._step_for_side(side)
                return fn, (side_zero(side), side_zero(opp),
                            _zeros_like_tree(q.states), tstates_zero(),
                            _zero_now(),
                            _zero_batch(schema, cap), _zero_now())
            add(f"{q.name}/join/{side}/row/{cap}", build)
        if packed_ok:
            for enc in self._encodings(schema, samples):
                for cap in row_caps:
                    def build(enc=enc, cap=cap):
                        fn = q._step_for_side(side, (enc, cap))
                        return fn, (side_zero(side), side_zero(opp),
                                    _zeros_like_tree(q.states),
                                    tstates_zero(),
                                    _zero_now(),
                                    _zero_packed(schema, enc, cap))
                    add(f"{q.name}/join/{side}/packed/{cap}/"
                        f"{','.join(enc)}", build)

    def _partition_specs(self, add, block, stream_id, schema, caps):
        row_caps = sorted({min(c, block.max_step_capacity or c)
                           for c in caps})
        for cap in row_caps:
            def build(cap=cap):
                fn = block._step_for(("stream", stream_id), cap)
                return fn, (_zeros_like_tree(block.slot_tbl),
                            _zeros_like_tree(block.qstates),
                            _zeros_like_tree(block._emitted),
                            _zeros_like_tree(block._lost),
                            _zero_batch(schema, cap), _zero_now())
            add(f"{block.name}/stream/{stream_id}/{cap}", build)

    def _partition_timer_specs(self, add, block, timer_cap):
        for plan in block.plans:
            if not block._has_timers.get(plan.name):
                continue

            def build(plan=plan):
                fn = block._step_for(("timer", plan.name), timer_cap)
                return fn, (_zeros_like_tree(block.slot_tbl),
                            _zeros_like_tree(block.qstates),
                            _zeros_like_tree(block._emitted),
                            _zeros_like_tree(block._lost),
                            _zero_batch(plan.in_schema, timer_cap),
                            _zero_now())
            add(f"{block.name}/timer/{plan.name}/{timer_cap}", build)

    def _timer_specs(self, add, q, timer_cap):
        """Scheduler-driven shapes: cap-16 TIMER batches run through the
        row steps; absent-pattern engines add a dedicated timer step and
        a due readback program."""
        from .runtime import (FusedChain, JoinQueryRuntime,
                              PatternQueryRuntime, QueryRuntime)
        app = self.app
        if isinstance(q, PatternQueryRuntime):
            if not getattr(q.engine, "has_absent", False):
                return

            def build_timer():
                fn = q._timer_step_for()
                return fn, (_zeros_like_tree(q.nfa_state),
                            _zeros_like_tree(q.states),
                            _zero_now(), _zero_now())
            add(f"{q.name}/pattern/timer", build_timer)

            def build_due():
                fn = q._due_fn_for()
                return fn, (_zeros_like_tree(q.nfa_state),)
            add(f"{q.name}/pattern/due", build_due)
            return
        if isinstance(q, JoinQueryRuntime):
            if not q._has_timers:
                return
            for side in ("L", "R"):
                schema = q.in_schemas[side]

                def build(side=side, schema=schema):
                    fn = q._step_for_side(side)
                    opp = "R" if side == "L" else "L"
                    return fn, (
                        _zeros_like_tree(q.side_states[side]),
                        _zeros_like_tree(q.side_states[opp]),
                        _zeros_like_tree(q.states),
                        {t: _zeros_like_tree(app.tables[t].state)
                         for t in q.table_deps},
                        _zero_now(),
                        _zero_batch(schema, timer_cap), _zero_now())
                add(f"{q.name}/join/{side}/row/{timer_cap}", build)
            return
        if isinstance(q, QueryRuntime):
            if not q._has_timers:
                return
            target = q._fused_chain or q

            def build():
                fused = isinstance(target, FusedChain)
                if fused:
                    states = (tuple(_zeros_like_tree(m.states)
                                    for m in target.queries),
                              tuple(_zero_now()
                                    for _ in target.queries))
                else:
                    states = (_zeros_like_tree(q.states),
                              _zero_now())
                st, emitted = states
                fn = target._step_for() if fused \
                    else target._step_for(timer_cap)
                tst = {t: _zeros_like_tree(app.tables[t].state)
                       for t in target.table_deps}
                return fn, (st, tst, emitted,
                            _zero_batch(q.in_schema, timer_cap),
                            _zero_now())
            add(f"{target.name}/row/{timer_cap}", build)

    # -- execution -------------------------------------------------------

    def warmup(self, buckets=None, samples: Optional[dict] = None,
               workers: Optional[int] = None) -> dict:
        """Compile every enumerated step, concurrently. Returns (and
        accumulates) telemetry: programs, compile_ms, cache hits/misses,
        per-step records."""
        if buckets is None:
            buckets = warm_buckets_from_env()
        self._begin()  # readiness: not ready while compiling
        try:
            return self._warmup(buckets, samples, workers)
        finally:
            self._end()

    def warm_specs(self, specs: list, workers: Optional[int] = None) -> dict:
        """Compile an externally-built spec list through this service:
        same thread pool, cache counters, cancellation and cumulative
        telemetry as warmup(). The serving TenantPool feeds its vmapped
        tenant-axis programs through here so a pool's whole compile
        story lands in ONE statistics()['compile'] entry."""
        self._begin()
        try:
            return self._run_specs(specs, workers)
        finally:
            self._end()

    def _warmup(self, buckets, samples: Optional[dict],
                workers: Optional[int]) -> dict:
        return self._run_specs(self.specs(buckets, samples=samples),
                               workers)

    def _run_specs(self, specs: list,
                   workers: Optional[int]) -> dict:
        self._cancel.clear()
        before = cache_counts()
        t0 = time.perf_counter()
        records: list[dict] = []
        errors: list[dict] = []
        cancelled: list[str] = []

        # dedupe: drop duplicate keys within this batch AND keys this
        # service already compiled (externally-built lists — the pool's
        # template-keyed specs — carry no specs()-style key dict, and a
        # re-warm with overlapping caps must not lower the same program
        # twice). Failed/cancelled specs are NOT remembered: they retry
        # on the next warmup.
        with self._lock:
            seen = set(self._warmed_keys)
        deduped = 0
        todo = []
        for s in specs:
            if s.key in seen:
                deduped += 1
                continue
            seen.add(s.key)
            todo.append(s)
        specs = todo

        def run(spec: CompileSpec) -> None:
            if self._cancel.is_set():
                # undeploy raced the warmup: stop compiling for an app
                # that is already gone (specs still run lazily if the
                # app ever dispatches again)
                cancelled.append(spec.key)
                return
            s0 = time.perf_counter()
            try:
                fn, args = spec.build()
                sharded = any(
                    len(getattr(getattr(leaf, "sharding", None),
                                "device_set", ())) > 1
                    for leaf in jax.tree_util.tree_leaves(args))
                out = fn(*args)
                jax.block_until_ready(out)
            except Exception as e:  # noqa: BLE001 — collected, raised below
                errors.append({"step": spec.key,
                               "error": f"{type(e).__name__}: {e}"})
                return
            rec = {"step": spec.key,
                   "ms": round((time.perf_counter() - s0) * 1e3, 1)}
            if sharded:
                rec["sharded"] = True
            records.append(rec)

        nworkers = workers or _workers_from_env()
        if specs:
            with ThreadPoolExecutor(max_workers=nworkers) as pool:
                list(pool.map(run, specs))
        wall = time.perf_counter() - t0
        after = cache_counts()
        n_sharded = sum(1 for r in records if r.get("sharded"))
        result = {
            "programs": len(records),
            "sharded_programs": n_sharded,
            "seconds": round(wall, 3),
            "compile_ms": round(wall * 1e3, 1),
            "cache_hits": after["hits"] - before["hits"],
            "cache_misses": after["misses"] - before["misses"],
            "steps": sorted(records, key=lambda r: -r["ms"]),
        }
        if errors:
            result["errors"] = errors
        if cancelled:
            result["cancelled"] = len(cancelled)
        if deduped:
            result["deduped"] = deduped
        with self._lock:
            self._warmed_keys.update(r["step"] for r in records)
            self.warmups += 1
            self.programs += result["programs"]
            self.sharded_programs += n_sharded
            self.total_ms += result["compile_ms"]
            self.cache_hits += result["cache_hits"]
            self.cache_misses += result["cache_misses"]
            self.errors += len(errors)
            self.records.extend(records)
        if errors:
            # a step that fails to compile at warmup fails on its first
            # chunk too: report it here, never defer it to traffic
            raise WarmupError(self.app.name, errors)
        return result

    def summary(self, detail: bool = False) -> dict:
        with self._lock:
            out = {
                "warmups": self.warmups,
                "programs": self.programs,
                "sharded_programs": self.sharded_programs,
                "compile_ms": round(self.total_ms, 1),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "errors": self.errors,
            }
            if self.audit is not None:
                out["audit"] = dict(self.audit)
            if detail:
                out["steps"] = sorted(self.records,
                                      key=lambda r: -r["ms"])
        return out

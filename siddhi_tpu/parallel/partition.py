"""Partitioned query execution: `partition with (attr of S) begin ... end`.

Reference mapping:
- PartitionRuntimeImpl (partition/PartitionRuntimeImpl.java:75) — one
  runtime per partition block                      -> PartitionBlockRuntime
- PartitionStreamReceiver (partition/PartitionStreamReceiver.java:82-146)
  — computes the key per event and routes it to a lazily-created per-key
  clone of every inner query                        -> the key->slot device
  hash table + a vmap over the slot axis
- ValuePartitionExecutor / RangePartitionExecutor
  (partition/executor/*.java)                       -> compiled key/range
  expressions evaluated over the whole batch at once
- PartitionStateHolder (util/snapshot/state/PartitionStateHolder.java:33)
  — per-key State maps                              -> operator states with
  a leading [K] slot axis

TPU-first design. The reference lazily clones the entire query runtime per
distinct key and routes each event through its key's clone — pointer-chasing
over an unbounded HashMap. Here the block compiles to ONE jitted step:

  1. the partition key of every event in the batch is computed in one
     vectorized expression pass;
  2. keys claim stable slots in a bounded open-addressing device hash table
     (ops/keyed.py) — first-seen assignment, overflow counted, never silent;
  3. the whole inner query chain runs under `jax.vmap` over the [K] slot
     axis: slot k sees the batch masked to its own events (plus TIMER
     rows, which every slot observes — each clone has its own scheduler in
     the reference too), so every existing operator works unchanged;
  4. per-query outputs [K, B] are flattened, ts-sorted, and compacted to
     one output batch; inner-stream (`#stream`) outputs never leave the
     vmap — they chain to consuming queries inside the same XLA program,
     keeping the key axis intact (the reference's per-key `#inner`
     junctions collapse into dataflow inside one step).

Multi-chip: the [K] slot axis is the sharding axis. When the app is built
with a `partition_mesh`, the stacked states are placed with a
NamedSharding over the mesh's first axis and XLA partitions the vmap
across devices — each device owns K/n key slots and masks the (replicated)
ingest batch down to the keys it owns. This is the all-gather + key-hash
ownership routing of `__graft_entry__.dryrun_multichip`, expressed through
GSPMD instead of hand-written collectives.

Bounded-state contract: at most K distinct keys are live; rows whose key
cannot claim a slot are dropped AND counted (`overflow`), mirroring the
framework-wide "counted, never silent" rule. Output compaction beyond the
per-trigger capacity is likewise counted (`lost`).

Ordering note: outputs are sorted by timestamp; rows with EQUAL timestamps
order by (slot, emission) rather than strict arrival interleaving across
keys (the reference interleaves per arrival). Within one key the order is
exact.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.event import (CURRENT, EventBatch, OutputFetch, StreamSchema,
                          TIMER)
from ..core.stream import Event, Receiver
from ..obs.tracing import maybe_span, tracer_of
from ..ops.expr import CompiledExpr, env_from_batch
from ..ops.keyed import hash_columns, lookup_or_insert
from ..ops.windows import POS_INF, WindowOp

from ..ops.sentinels import NO_SLOT

# combined-output compaction bound: several key slots can emit in the same
# step (e.g. a timer flushing every slot's timeBatch window), so the cap
# scales with K instead of a single slot's capacity; beyond it rows are
# dropped AND counted in `lost`
OUT_COMPACT_CAP = 65536


class BlockQueryPlan:
    """One query inside a partition block, compiled to an operator chain."""

    def __init__(self, name: str, input_id: str, in_schema: StreamSchema,
                 operators: list, target: str, inner_target: bool,
                 out_type: str):
        self.name = name
        self.input_id = input_id          # '#I' for inner streams
        self.in_schema = in_schema
        self.operators = operators
        self.target = target              # '#I' when inner_target
        self.inner_target = inner_target
        self.out_type = out_type

    @property
    def out_schema(self) -> StreamSchema:
        return self.operators[-1].out_schema

    def init_state(self):
        return tuple(op.init_state() for op in self.operators)

    def has_timers(self) -> bool:
        return any(isinstance(op, WindowOp) and
                   op.next_due(op.init_state()) is not None
                   for op in self.operators)


class BlockPatternPlan:
    """A pattern/sequence query inside a partition block: the NFA pending
    table gains a leading [K] slot axis under the block's vmap — each key
    instance owns an independent pending table (the reference clones
    whole query runtimes per key: PartitionRuntimeImpl.java:75,
    PartitionStreamReceiver.java:82-146)."""

    is_pattern = True

    def __init__(self, name: str, engine, sel_ops: list,
                 input_ids: set, in_schema: StreamSchema, target: str,
                 inner_target: bool, out_type: str):
        self.name = name
        self.engine = engine
        self.sel_ops = sel_ops
        self.input_ids = input_ids        # outer stream ids consumed
        self.input_id = next(iter(sorted(input_ids)))
        self.in_schema = in_schema
        self.operators = sel_ops          # for sort-heavy/overflow scans
        self.target = target
        self.inner_target = inner_target
        self.out_type = out_type

    @property
    def out_schema(self) -> StreamSchema:
        return self.sel_ops[-1].out_schema if self.sel_ops \
            else self.engine.match_schema

    def init_state(self):
        return (self.engine.init_state(),
                tuple(op.init_state() for op in self.sel_ops))

    def has_timers(self) -> bool:
        return self.engine.has_absent


class PartitionQueryPort:
    """Output surface of one partitioned query: handlers + callbacks
    (what `app.queries[name]` exposes for queries inside a partition)."""

    def __init__(self, block: "PartitionBlockRuntime", name: str,
                 out_schema: StreamSchema):
        from ..core.runtime import QueryCallbackHandler
        self.block = block
        self.name = name
        self.out_schema = out_schema
        self.output_handlers: list = []
        self.callback_handler = QueryCallbackHandler()
        self.batch_callbacks: list[Callable] = []
        self.rate_limiter = None

    def set_rate_limiter(self, rl) -> None:
        rl.emit = self._emit_limited
        rl.start(self.block.app)
        self.rate_limiter = rl

    def _emit_limited(self, timestamp: int, rows) -> None:
        for h in self.output_handlers:
            h.handle(timestamp, rows)
        self.callback_handler.handle(timestamp, rows)

    def stats(self) -> dict:
        return {"emitted": int(jax.device_get(
                    self.block._emitted[self.name])),
                "overflow": self.block.overflow_total()}

    def overflow_total(self) -> int:
        return self.block.overflow_total()


class BlockStreamReceiver(Receiver):
    """Junction subscriber feeding one outer stream into the block
    (= PartitionStreamReceiver)."""

    supports_packed = False

    def __init__(self, block: "PartitionBlockRuntime", stream_id: str):
        self.block = block
        self.stream_id = stream_id

    @property
    def max_step_capacity(self):
        return self.block.max_step_capacity

    def receive(self, events):
        self.block.process_stream_events(self.stream_id, events)

    def process_batch(self, batch, last_ts):
        self.block.process_stream_batch(self.stream_id, batch, last_ts)


def _tree_overflow_sum(tree) -> int:
    """Sum every 'overflow' entry in a host pytree of dicts/tuples."""
    total = 0
    if isinstance(tree, dict):
        for k, v in tree.items():
            if k == "overflow":
                total += int(np.sum(np.asarray(v)))
            else:
                total += _tree_overflow_sum(v)
    elif isinstance(tree, (tuple, list)):
        for v in tree:
            total += _tree_overflow_sum(v)
    return total


def _flatten_compact(out: EventBatch, out_cap: int):
    """[K, B] per-slot outputs -> one ts-sorted [out_cap] batch + lost
    count. Stable sort: equal timestamps keep (slot, row) order."""
    K, B = out.valid.shape

    def flat(x):
        return x.reshape((K * B,) + x.shape[2:])

    valid = flat(out.valid)
    ts = flat(out.ts)
    key = jnp.where(valid, ts, jnp.int64(2 ** 62))
    order = jnp.argsort(key, stable=True)[:out_cap]
    picked = EventBatch(
        ts=ts[order],
        cols=tuple(flat(c)[order] for c in out.cols),
        nulls=tuple(flat(nl)[order] for nl in out.nulls),
        kind=flat(out.kind)[order],
        valid=valid[order],
    )
    lost = (jnp.sum(valid.astype(jnp.int64)) -
            jnp.sum(picked.valid.astype(jnp.int64)))
    return picked, lost


def _concat_batches(a: EventBatch, b: EventBatch) -> EventBatch:
    return EventBatch(
        ts=jnp.concatenate([a.ts, b.ts]),
        cols=tuple(jnp.concatenate([x, y])
                   for x, y in zip(a.cols, b.cols)),
        nulls=tuple(jnp.concatenate([x, y])
                    for x, y in zip(a.nulls, b.nulls)),
        kind=jnp.concatenate([a.kind, b.kind]),
        valid=jnp.concatenate([a.valid, b.valid]),
    )


def _as_current(b: EventBatch) -> EventBatch:
    """EXPIRED rows become CURRENT when inserted into a stream
    (InsertIntoStreamCallback.java:52-55)."""
    return EventBatch(b.ts, b.cols, b.nulls,
                      jnp.where(b.valid, jnp.int32(CURRENT), b.kind),
                      b.valid)


class PartitionBlockRuntime:
    """All queries of one `partition ... begin ... end` block, executed as
    one jitted, slot-vmapped step per triggering input."""

    def __init__(self, app, name: str, n_slots: int,
                 key_specs: dict, plans: list[BlockQueryPlan],
                 mesh=None):
        self.app = app
        self.name = name
        self.K = int(n_slots)
        # key_specs: stream_id -> ("value", CompiledExpr)
        #                        | ("range", [CompiledExpr, ...]) (slot=index)
        self.key_specs = key_specs
        self.plans = plans
        self.mesh = mesh
        self.slot_tbl = {
            "keys": jnp.zeros((self.K,), jnp.int64),
            "used": jnp.zeros((self.K,), jnp.bool_),
            "overflow": jnp.int64(0),
        }
        self.qstates = {p.name: self._stack_state(p.init_state())
                        for p in plans}
        self._emitted = {p.name: jnp.int64(0) for p in plans}
        self._lost = {p.name: jnp.int64(0) for p in plans}
        self.ports = {p.name: PartitionQueryPort(self, p.name, p.out_schema)
                      for p in plans}
        self._steps: dict = {}
        self._lock = threading.Lock()
        self._sched_due: dict[str, Optional[int]] = {p.name: None
                                                     for p in plans}
        self._has_timers = {p.name: p.has_timers() for p in plans}
        # the slot-vmap multiplies every per-step sort by K — cap harder
        # (see runtime.py SORT_HEAVY_CAP)
        from ..core.runtime import PARTITION_SORT_HEAVY_CAP \
            as SORT_HEAVY_CAP
        self.max_step_capacity = SORT_HEAVY_CAP if any(
            getattr(op, "sort_heavy", False)
            for p in plans for op in p.operators) else None
        if mesh is not None:
            from . import sharding as _sharding
            _sharding.check_divisible(self.K, mesh,
                                      f"partition '{name}' slots")
            self._apply_mesh_sharding()

    # -- state layout -----------------------------------------------------
    def _stack_state(self, state):
        K = self.K
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                jnp.asarray(x)[None], (K,) + jnp.asarray(x).shape
            ) + jnp.zeros((K,) + (1,) * jnp.asarray(x).ndim,
                          dtype=jnp.asarray(x).dtype),
            state)

    def _apply_mesh_sharding(self, qstates=None, slot_tbl=None,
                             emitted=None, lost=None):
        """Place the block state per the regex rule table
        (parallel/sharding.py PARTITION_STATE_RULES): [K]-leading
        qstates shard over the mesh's first axis — XLA then partitions
        the slot-vmap across devices (each device owns K/n key slots —
        GSPMD routing, see module docstring) — and the key-slot table
        and the emitted/lost counters replicate, so the first dispatch
        already runs the steady-state program (a step returns them
        replicated; a device-0 counter would compile a second variant
        on the next chunk). Accepts HOST pytrees (restore): a sharded
        `device_put` of a numpy leaf is ONE placement that never
        aliases the payload, so restore re-places shards directly
        instead of a fresh-copy-then-re-place double transfer
        (`shard_pytree` also skips already-placed leaves — redundant
        calls transfer nothing; tests/test_mesh.py counts both)."""
        from . import sharding
        placed = sharding.shard_pytree(
            {"qstates": qstates if qstates is not None else self.qstates,
             "slot_tbl": slot_tbl if slot_tbl is not None
             else self.slot_tbl,
             "emitted": emitted if emitted is not None else self._emitted,
             "lost": lost if lost is not None else self._lost},
            self.mesh, sharding.PARTITION_STATE_RULES)
        self.qstates = placed["qstates"]
        self.slot_tbl = placed["slot_tbl"]
        self._emitted = placed["emitted"]
        self._lost = placed["lost"]

    # -- key computation --------------------------------------------------
    def _slots_for(self, spec, batch: EventBatch, now, slot_tbl):
        kind = spec[0]
        if kind == "value":
            cexpr: CompiledExpr = spec[1]
            env = env_from_batch(batch)
            env["__now__"] = now
            c = cexpr.fn(env)
            codes = hash_columns([c.values], [c.nulls])
            active = batch.valid & (batch.kind != TIMER)
            slots, keys, used, ovf = lookup_or_insert(
                slot_tbl["keys"], slot_tbl["used"], codes, active)
            slot_tbl = {"keys": keys, "used": used,
                        "overflow": slot_tbl["overflow"] + ovf}
            return slots, slot_tbl
        # range partition: slot = label's slot for the first matching range
        # condition (labels shared across streams identify the instance);
        # events matching no range are dropped (RangePartitionExecutor
        # returns null -> no instance)
        conds = spec[1]  # [(CompiledExpr, slot_index), ...]
        env = env_from_batch(batch)
        env["__now__"] = now
        B = batch.valid.shape[0]
        slots = jnp.full((B,), NO_SLOT, dtype=jnp.int32)
        for cexpr, si in conds:
            c = cexpr.fn(env)
            hit = c.values & ~c.nulls & (slots == NO_SLOT)
            slots = jnp.where(hit, jnp.int32(si), slots)
        return slots, slot_tbl

    # -- step compilation -------------------------------------------------
    def _step_for(self, trigger: tuple, capacity: int):
        fn = self._steps.get((trigger, capacity))
        if fn is None:
            fn = jax.jit(self._make_step(trigger))
            self._steps[(trigger, capacity)] = fn
        return fn

    def _make_step(self, trigger: tuple):
        kind, tid = trigger
        plans = self.plans
        K = self.K
        key_specs = self.key_specs

        # pattern plans: the engine's per-stream/timer step fns are
        # trigger-specific — built once per compiled step
        nfa_steps = {}
        for p in plans:
            if getattr(p, "is_pattern", False):
                if kind == "stream" and tid in p.input_ids:
                    nfa_steps[p.name] = p.engine.make_stream_step(tid)
                elif kind == "timer" and p.name == tid:
                    nfa_steps[p.name] = p.engine.make_timer_step()

        def step(slot_tbl, qstates, emitted, lost, batch, now):
            if kind == "stream":
                slots, slot_tbl = self._slots_for(
                    key_specs[tid], batch, now, slot_tbl)
            else:
                slots = None  # TIMER trigger: every slot observes it
            is_timer_row = batch.kind == TIMER

            def run_block(per_slot, k):
                inner_k: dict = {}
                outs_k: dict = {}
                dues_k: dict = {}
                new_k: dict = {}
                for p in plans:
                    if getattr(p, "is_pattern", False):
                        nstep = nfa_steps.get(p.name)
                        if nstep is None:
                            new_k[p.name] = per_slot[p.name]
                            continue
                        nfa_state, sel_states = per_slot[p.name]
                        if kind == "timer":
                            nfa_state, b = nstep(nfa_state, now)
                        else:
                            bk = batch.mask((slots == k) | is_timer_row)
                            nfa_state, b = nstep(nfa_state, bk, now)
                        sts = []
                        for op, st in zip(p.sel_ops, sel_states):
                            st, b = op.step(st, b, now)
                            sts.append(st)
                        new_k[p.name] = (nfa_state, tuple(sts))
                        if p.engine.has_absent:
                            dues_k[p.name] = p.engine.next_due(nfa_state)
                        outs_k[p.name] = b
                        continue
                    if kind == "timer" and p.name == tid:
                        b = batch
                    elif kind == "stream" and p.input_id == tid:
                        b = batch.mask((slots == k) | is_timer_row)
                    elif p.input_id in inner_k:
                        b = inner_k[p.input_id]
                    else:
                        new_k[p.name] = per_slot[p.name]
                        continue
                    sts = []
                    for op, st in zip(p.operators, per_slot[p.name]):
                        st, b = op.step(st, b, now)
                        sts.append(st)
                    new_k[p.name] = tuple(sts)
                    ds = [op.next_due(s) for op, s in
                          zip(p.operators, sts) if isinstance(op, WindowOp)]
                    ds = [d for d in ds if d is not None]
                    if ds:
                        due = ds[0]
                        for d in ds[1:]:
                            due = jnp.minimum(due, d)
                        dues_k[p.name] = due
                    if p.inner_target:
                        cur = _as_current(b)
                        if p.target in inner_k:
                            inner_k[p.target] = _concat_batches(
                                inner_k[p.target], cur)
                        else:
                            inner_k[p.target] = cur
                    else:
                        outs_k[p.name] = b
                return new_k, outs_k, dues_k

            ks = jnp.arange(K, dtype=jnp.int32)
            new_states, outs, dues = jax.vmap(run_block)(qstates, ks)
            flat_outs = {}
            for qn, ob in outs.items():
                out_cap = min(K * ob.valid.shape[1], OUT_COMPACT_CAP)
                flat, l = _flatten_compact(ob, out_cap)
                flat_outs[qn] = flat
                emitted = dict(emitted)
                emitted[qn] = emitted[qn] + flat.count().astype(jnp.int64)
                lost = dict(lost)
                lost[qn] = lost[qn] + l
            dues = {qn: jnp.min(d) for qn, d in dues.items()}
            return slot_tbl, new_states, emitted, lost, flat_outs, dues

        return step

    # -- runtime ----------------------------------------------------------
    def process_stream_events(self, stream_id: str, events: list[Event]):
        from ..core.runtime import QueryRuntime
        schema = self.app.schemas[stream_id]
        for batch, last_ts in QueryRuntime.encode_chunks(
                schema, events, self.max_step_capacity):
            self.process_stream_batch(stream_id, batch, last_ts)

    def process_stream_batch(self, stream_id: str, batch: EventBatch,
                             timestamp: int, now: Optional[int] = None):
        cap = self.max_step_capacity
        if cap is not None and batch.capacity > cap:
            from ..core.runtime import QueryRuntime
            for sub in QueryRuntime.split_batch(batch, cap):
                self._run(("stream", stream_id), sub, timestamp, now)
            return
        self._run(("stream", stream_id), batch, timestamp, now)

    def _run(self, trigger, batch, timestamp, now=None):
        cost = getattr(self.app, "cost", None)
        probe = cost.probe("partition", self.name) \
            if cost is not None and cost.enabled else None
        with maybe_span(self.app, "partition", self.name,
                        trigger=str(trigger)):
            if now is None:
                now = self.app.current_time()
            now_dev = jnp.asarray(now, dtype=jnp.int64)
            with self._lock:
                step = self._step_for(trigger, batch.capacity)
                (self.slot_tbl, self.qstates, self._emitted, self._lost,
                 flat_outs, dues) = step(self.slot_tbl, self.qstates,
                                         self._emitted, self._lost, batch,
                                         now_dev)
            if probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(flat_outs)
                probe.done(rows=int(batch.capacity))
            for qn, out in flat_outs.items():
                self._dispatch(qn, out, timestamp)
            if dues:
                # one pytree transfer for every query's due, not one sync
                # per query (docs/tpu_hygiene.md host-sync-in-loop)
                for qn, due in jax.device_get(dues).items():
                    self._schedule(qn, int(due))

    def _dispatch(self, qname: str, out: EventBatch, timestamp: int):
        port = self.ports[qname]
        for cb in port.batch_callbacks:
            cb(out)
        # the output fetch's spans are named by the partition, like the
        # enclosing partition/<name> span
        fetch = OutputFetch(tracer_of(self.app), self.name,
                            port.out_schema.types, out)
        if port.rate_limiter is not None:
            rows = fetch.rows()
            if rows:
                port.rate_limiter.process(timestamp, rows)
            return
        row_handlers = [h for h in port.output_handlers
                        if not h.handle_device_batch(out, timestamp)]
        if not (row_handlers or port.callback_handler.callbacks):
            return
        rows = fetch.rows()
        if not rows:
            return
        for h in row_handlers:
            h.handle(timestamp, rows)
        port.callback_handler.handle(timestamp, rows)

    # -- timers -----------------------------------------------------------
    def _schedule(self, qname: str, due: int):
        if due >= int(POS_INF):
            return
        cur = self._sched_due.get(qname)
        if cur is not None and cur <= due:
            return
        self._sched_due[qname] = due
        self.app.scheduler.notify_at(due, lambda d, q=qname:
                                     self._on_timer(q, d))

    def _on_timer(self, qname: str, due: int):
        self._sched_due[qname] = None
        if not self.app.running:
            return
        plan = next(p for p in self.plans if p.name == qname)
        from ..core.runtime import _timer_batch
        now = max(due, self.app.current_time())
        # TIMER rows carry the advanced clock (see QueryRuntime._on_timer)
        batch = _timer_batch(plan.in_schema, now)
        self._run(("timer", qname), batch, due, now=now)

    # -- snapshot ---------------------------------------------------------
    def snapshot_state(self) -> dict:
        with self._lock:
            snap = jax.device_get({"slot_tbl": self.slot_tbl,
                                   "qstates": self.qstates,
                                   "emitted": self._emitted,
                                   "lost": self._lost})
            snap["rate"] = {qn: p.rate_limiter.snapshot_state()
                            for qn, p in self.ports.items()
                            if p.rate_limiter is not None}
            return snap

    def restore_state(self, snap: dict) -> None:
        from ..core.runtime import _fresh_device
        with self._lock:
            if self.mesh is not None:
                # restore RE-PLACES shards straight from the host
                # snapshot: ONE sharded device_put per leaf (fresh
                # buffers by construction — a sharded put never aliases
                # the numpy payload, so the _fresh_device donation
                # guard is subsumed), never a fresh single-device copy
                # that a second pass then re-places
                self._apply_mesh_sharding(qstates=snap["qstates"],
                                          slot_tbl=snap["slot_tbl"],
                                          emitted=snap["emitted"],
                                          lost=snap["lost"])
            else:
                # snapshot payloads are host numpy; device_put may
                # alias them zero-copy, so single-device restores route
                # through _fresh_device before the state re-enters a
                # donated step (core/runtime.py)
                self.slot_tbl = _fresh_device(snap["slot_tbl"])
                self.qstates = _fresh_device(snap["qstates"])
                self._emitted = {k: jnp.array(v, copy=True)
                                 for k, v in snap["emitted"].items()}
                self._lost = {k: jnp.array(v, copy=True)
                              for k, v in snap["lost"].items()}
            for qn in self._sched_due:
                self._sched_due[qn] = None
            for qn, rsnap in snap.get("rate", {}).items():
                port = self.ports.get(qn)
                if port is not None and port.rate_limiter is not None:
                    port.rate_limiter.restore_state(rsnap)

    def reschedule(self) -> None:
        """Re-arm per-query timers from restored [K]-stacked states."""
        per_plan: dict[str, list] = {}
        for p in self.plans:
            if not self._has_timers[p.name]:
                continue
            with self._lock:  # restore rebinds the stacked states
                qstates = self.qstates[p.name]
            for op, st in zip(p.operators, qstates):
                if isinstance(op, WindowOp):
                    d = jax.vmap(op.next_due)(st)
                    if d is not None:
                        per_plan.setdefault(p.name, []).append(jnp.min(d))
        if per_plan:
            # reductions stay on device; ONE pytree transfer re-arms every
            # query instead of a per-window sync
            for qn, ds in jax.device_get(per_plan).items():
                self._schedule(qn, min(int(d) for d in ds))

    # -- introspection ----------------------------------------------------
    def overflow_total(self) -> int:
        with self._lock:  # vs restore/process rebinding mid-read
            host = jax.device_get((self.slot_tbl, self.qstates,
                                   self._lost))
        tbl, qstates, losts = host
        total = int(tbl["overflow"])
        total += _tree_overflow_sum(qstates)
        total += sum(int(v) for v in losts.values())
        return total

    def stats(self) -> dict:
        with self._lock:  # vs the step path rebinding counters
            emitted = jax.device_get(self._emitted)
        return {"emitted": {qn: int(v) for qn, v in emitted.items()},
                "overflow": self.overflow_total()}

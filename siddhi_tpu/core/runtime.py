"""Runtime assembly: query runtimes, the app runtime, and the planner that
builds them from the parsed query object model.

Reference mapping:
- SiddhiAppRuntimeImpl (core/SiddhiAppRuntimeImpl.java:99) -> SiddhiAppRuntime
- QueryRuntimeImpl (query/QueryRuntimeImpl.java:43)        -> QueryRuntime
- SiddhiAppParser/QueryParser/SingleInputStreamParser
  (util/parser/*.java)                                     -> Planner
- Scheduler timer events (util/Scheduler.java:113)         -> TIMER batches
  injected by core/scheduler.py when a window's next_due passes.

Execution model: each query compiles to ONE jitted step function
(state, batch, now) -> (state', out_batch, next_due). The host junction layer
feeds micro-batches in; batch capacity is bucketed so jit caches stay warm.

Chain fusion (docs/performance.md): at app start the planner's junction
graph is walked and fusible `insert into` segments Q1 -> S -> Q2 -> ... are
compiled into ONE jitted chain step, so a micro-batch traverses the whole
segment in a single XLA program instead of paying a host dispatch (plus an
eager kind-rewrite) per hop. `SIDDHI_TPU_FUSE=0` falls back to per-query
dispatch. State/window buffers are donated to their steps
(`SIDDHI_TPU_DONATE=0` opts out) so they update in place instead of
copy-on-writing every chunk.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import logging
import os
import re
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..lang import ast as A
from ..obs.profiler import op_scope
from ..ops.aggregators import AggregateOp
from ..ops.expr import (CompileError, SingleStreamScope,
                        collect_template_params, compile_expression)
from ..ops.join import (JoinCombinedScope, JoinCross, JoinSideScope,
                        combined_schema)
from ..ops.nfa import MatchScope, NfaCompiler, NfaEngine
from ..ops.nfa_parallel import ParallelNfaEngine, parallel_supported
from ..ops.operators import FilterOp, Operator
from ..ops.selector import (ProjectOp, output_attribute_name,
                            selector_needs_aggregation)
from ..ops.table import (TableFilterOp, TableOutputOp, TableRuntime,
                         expr_mentions_table)
from ..ops.windows2 import (BatchWindowOp, CronWindowOp, DelayWindowOp,
                            EmptyWindowOp, ExternalTimeBatchWindowOp,
                            ExternalTimeWindowOp, FrequentWindowOp,
                            HoppingWindowOp, LossyFrequentWindowOp,
                            SessionWindowOp, SortWindowOp,
                            TimeLengthWindowOp)
from ..ops.windows import (NEG_INF, POS_INF, LengthBatchWindowOp, LengthWindowOp,
                           TimeBatchWindowOp, TimeWindowOp, WindowOp)
from .event import (CURRENT, EXPIRED, TIMER, Attribute, EventBatch,
                    OutputFetch, StreamSchema, batch_from_rows,
                    rows_from_batch)
from .ingest import PackedChunk, unpack_buffer
from .scheduler import Scheduler
from .stream import (Event, InputHandler, QueryCallback, Receiver,
                     StreamCallback, StreamJunction)
from .types import AttrType

BATCH_BUCKETS = (16, 128, 1024, 8192, 65536, 262144, 1048576)

# step capacity cap for queries containing sort-heavy operators (windows,
# aggregations, order-by). With the int32 sort keys everywhere (see
# ops/windows.py _rel32 / emission_sort) a 65536-row sort step compiles
# in ~18 s (vs ~6 s at 8192) and runs at the same events/s — so plain
# queries take the full bucket (1M events = 16 steps); the K-vmapped
# partition blocks keep the smaller cap (compile is multiplied by the
# slot axis there)
SORT_HEAVY_CAP = 65536
PARTITION_SORT_HEAVY_CAP = 8192

WINDOW_CLASSES = {
    "time": TimeWindowOp,
    "length": LengthWindowOp,
    "lengthbatch": LengthBatchWindowOp,
    "timebatch": TimeBatchWindowOp,
    "externaltime": ExternalTimeWindowOp,
    "timelength": TimeLengthWindowOp,
    "delay": DelayWindowOp,
    "batch": BatchWindowOp,
    "sort": SortWindowOp,
    "frequent": FrequentWindowOp,
    "lossyfrequent": LossyFrequentWindowOp,
    "externaltimebatch": ExternalTimeBatchWindowOp,
    "session": SessionWindowOp,
    "cron": CronWindowOp,
    "hopping": HoppingWindowOp,
    "hoping": HoppingWindowOp,   # the reference's spelling
}


def bucket_capacity(n: int) -> int:
    i = bisect.bisect_left(BATCH_BUCKETS, n)
    if i == len(BATCH_BUCKETS):
        return BATCH_BUCKETS[-1]
    return BATCH_BUCKETS[i]


JOIN_KERNEL_ENV = "SIDDHI_TPU_JOIN_KERNEL"


def _pick_join_kernel(app_name: str, qname: str,
                      cross) -> tuple[str, str, str]:
    """Join kernel for one JoinCross: ``(kernel, reason, cause)``.

    Policy (docs/performance.md "join kernels"): the banded searchsorted
    probe whenever the ON condition carries an ``L == R`` equi conjunct,
    the [B, W] broadcast grid otherwise. ``SIDDHI_TPU_JOIN_KERNEL=
    grid|probe`` overrides (probe silently falls back to grid when no
    equi conjunct exists). The persisted PR 7 cost table
    (``<state dir>/costs.json``, obs/costmodel.load_costs) is consulted:
    when a prior profile shows this join's grid centers dominating the
    app's measured step time, the probe pick is recorded as
    evidence-backed rather than heuristic.

    ``cause`` is a machine-readable slug — ``env-override`` /
    ``no-equi-conjunct`` / ``cost-evidence`` / ``no-cost-table`` /
    ``equi-default`` — so explain (obs/explain.py) never shows a
    decision without a cause, even when the cost table is absent."""
    env = os.environ.get(JOIN_KERNEL_ENV, "").strip().lower()
    eligible = cross.equi is not None
    if env == "grid":
        return "grid", "SIDDHI_TPU_JOIN_KERNEL=grid override", \
            "env-override"
    if env == "probe":
        if eligible:
            return "probe", "SIDDHI_TPU_JOIN_KERNEL=probe override", \
                "env-override"
        return "grid", ("SIDDHI_TPU_JOIN_KERNEL=probe requested but the "
                        "ON condition has no equi conjunct — grid "
                        "fallback"), "no-equi-conjunct"
    if not eligible:
        return "grid", ("no equi conjunct in ON condition (the banded "
                        "probe needs one)"), "no-equi-conjunct"
    try:
        from ..obs.costmodel import load_costs
        tbl = load_costs().get(app_name) or {}
    except Exception:  # noqa: BLE001 — costs are advisory
        tbl = {}
    if not tbl:
        return "probe", ("equi ON condition (banded searchsorted probe); "
                         "no cost table measured yet"), "no-cost-table"
    key, costs = max(tbl.items(),
                     key=lambda kv: kv[1].get("ms_total", 0.0))
    if key.startswith(f"join/{qname}.") and "[probe]" not in key:
        return "probe", (
            f"cost table: grid-dominated center {key} "
            f"({costs.get('ms_total', 0)} ms_total) — probe selected"), \
            "cost-evidence"
    return "probe", "equi ON condition (banded searchsorted probe)", \
        "equi-default"


def _donate(*argnums):
    """donate_argnums kwargs for the state-carrying arguments of a step:
    XLA aliases the output state buffers onto the input ones, so large
    window/NFA states update in place instead of copy-on-writing every
    chunk. Donated inputs are INVALID after the call — safe here because
    every step replaces the runtime's state references before releasing
    its lock, and snapshot/statistics reads take the same lock/barrier.
    SIDDHI_TPU_DONATE=0 opts out (debugging aid)."""
    if os.environ.get("SIDDHI_TPU_DONATE", "1") == "0":
        return {}
    return {"donate_argnums": argnums}


def _fresh_device(tree):
    """Fresh device buffers for restored state. Snapshot payloads hold
    numpy arrays (device_get), and jax may alias a numpy buffer
    ZERO-COPY on device_put — donating such an aliased buffer to a step
    (see _donate) would free memory numpy still owns. Every restore path
    copies through here before the state re-enters a donated step
    argument."""
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), tree)


def _as_current(batch: EventBatch) -> EventBatch:
    """Insert-into kind rewrite (InsertIntoStreamCallback.java:52-55):
    EXPIRED events become CURRENT on insert. Pure trace transform —
    usable both inside a fused chain step and under `_rewrite_current`."""
    return EventBatch(
        ts=batch.ts, cols=batch.cols, nulls=batch.nulls,
        kind=jnp.where(batch.valid, jnp.int32(CURRENT), batch.kind),
        valid=batch.valid)


# jitted wrapper for the UNFUSED hop path: one cached dispatch per hop
# instead of three eager ops (where + broadcast + convert)
_rewrite_current = jax.jit(_as_current)


def _chain_body(ops, has_timers: bool, name: str):
    """The traced body of query ``name``'s operator chain:
    (states, tstates, emitted, batch, now) ->
    (states', tstates', emitted', out, due). Shared by the per-query
    step compilers and the fused chain step. Each operator runs under
    the named scope ``<name>/<OpClass>`` (obs/profiler.py)."""

    def chain(states, tstates, emitted, batch, now):
        new_states = []
        for op, st in zip(ops, states):
            with op_scope(f"{name}/{type(op).__name__}"):
                if op.needs_tables:
                    st, batch, tstates = op.step_tables(st, batch, now,
                                                        tstates)
                else:
                    st, batch = op.step(st, batch, now)
            new_states.append(st)
        if has_timers:
            dues = [op.next_due(st) for op, st in zip(ops, new_states)
                    if isinstance(op, WindowOp)]
            dues = [d for d in dues if d is not None]
            due = dues[0]
            for d in dues[1:]:
                due = jnp.minimum(due, d)
        else:
            due = jnp.asarray(POS_INF)
        emitted = emitted + batch.count().astype(jnp.int64)
        return tuple(new_states), tstates, emitted, batch, due

    return chain


def _build_packed_step(chain, schema: StreamSchema, enc: tuple,
                       capacity: int, sub_cap: Optional[int],
                       playback: bool) -> Callable:
    """Fused unpack + chain over a PackedChunk's single buffer. `chain`
    has the _chain_body signature; its states/emitted/due slots may be
    arbitrary pytrees (the fused chain threads tuples-per-query through
    the same builder). See QueryRuntime._packed_step_for for the
    sort-heavy scan rationale."""
    if sub_cap is not None and capacity > sub_cap:
        k = capacity // sub_cap

        def pstep(states, tstates, emitted, buf):
            batch, now = unpack_buffer(schema, enc, capacity, buf)
            subs = jax.tree_util.tree_map(
                lambda x: x.reshape((k, sub_cap) + x.shape[1:]),
                batch)

            def body(carry, sub):
                states, tstates, emitted, run_ts = carry
                if playback:
                    sub_now = jnp.maximum(run_ts, jnp.max(
                        jnp.where(sub.valid, sub.ts,
                                  jnp.asarray(NEG_INF))))
                else:
                    sub_now = now
                states, tstates, emitted, out, due = chain(
                    states, tstates, emitted, sub, sub_now)
                return ((states, tstates, emitted, sub_now),
                        (out, due))

            carry0 = (states, tstates, emitted,
                      jnp.asarray(NEG_INF))
            (states, tstates, emitted, _), (outs, dues) = \
                jax.lax.scan(body, carry0, subs)
            out = jax.tree_util.tree_map(
                lambda x: x.reshape((x.shape[0] * x.shape[1],)
                                    + x.shape[2:]), outs)
            due = jax.tree_util.tree_map(lambda d: d[-1], dues)
            return states, tstates, emitted, out, due
    else:
        def pstep(states, tstates, emitted, buf):
            batch, now = unpack_buffer(schema, enc, capacity, buf)
            return chain(states, tstates, emitted, batch, now)

    return jax.jit(pstep, **_donate(0, 1, 2))


class OutputHandler:
    def handle(self, timestamp: int, rows: list) -> None:
        raise NotImplementedError

    def handle_device_batch(self, out, timestamp: int,
                            current=None) -> bool:
        """Try to consume the DEVICE output batch without host row decode
        (device-to-device query chaining). Returns True when consumed —
        the row path is then skipped for this handler. ``current`` is a
        zero-arg memoized supplier of the CURRENT-kind-rewritten batch:
        the dispatching query builds it ONCE per emitted batch, so a
        fan-out of N insert-into handlers pays one jitted rewrite
        instead of N (docs/performance.md)."""
        return False


class InsertIntoStreamHandler(OutputHandler):
    """Publish query output into a stream junction; EXPIRED events become
    CURRENT on insert (InsertIntoStreamCallback.java:52-55).

    When every downstream receiver takes device batches, the output
    EventBatch is handed over directly — no host decode per hop
    (the reference's InsertIntoStreamCallback also stays in-memory;
    here 'in-memory' means on-device)."""

    def __init__(self, junction: StreamJunction, output_event_type: str):
        self.junction = junction
        self.output_event_type = output_event_type

    def handle_device_batch(self, out, timestamp: int,
                            current=None) -> bool:
        receivers = self.junction.receivers
        if not receivers:
            return True  # nobody listening — drop without decode
        if all(hasattr(r, "process_batch") for r in receivers):
            # kind rewrite runs as ONE jitted dispatch per emitted batch
            # — shared across every handler of the emitting query via
            # the memoized `current` supplier (fused segments do it
            # inside the chain trace instead)
            cur = current() if current is not None \
                else _rewrite_current(out)
            self.junction.publish_batch(cur, timestamp)
            return True
        return False

    def handle(self, timestamp, rows):
        events = [Event(timestamp=ts, data=vals) for ts, kind, vals in rows]
        self.junction.publish(events)


class InsertIntoWindowHandler(OutputHandler):
    """`insert into <named window>`: feed the shared window instance
    (query/output/callback/InsertIntoWindowCallback.java) — inserted
    events enter the window as fresh CURRENT arrivals."""

    def __init__(self, wq: "QueryRuntime"):
        self.wq = wq

    def handle_device_batch(self, out, timestamp, current=None):
        cur = current() if current is not None else _rewrite_current(out)
        self.wq.process_batch(cur, timestamp)
        return True

    def handle(self, timestamp, rows):
        self.wq.receive([Event(ts, vals) for ts, kind, vals in rows])


class WindowPublishHandler(OutputHandler):
    """Publish a named window's processed output — kinds preserved, so
    consuming queries see CURRENT/EXPIRED exactly as after an inline
    window (window/Window.java:65); the definition's output event type
    filters what subscribers observe."""

    def __init__(self, junction: StreamJunction, out_type: str):
        self.junction = junction
        self.out_type = out_type

    def _filtered(self, out):
        if self.out_type == "current":
            return out.mask(out.kind == CURRENT)
        if self.out_type == "expired":
            return out.mask(out.kind == EXPIRED)
        return out

    def handle_device_batch(self, out, timestamp, current=None):
        self.junction.publish_batch(self._filtered(out), timestamp)
        return True

    def handle(self, timestamp, rows):
        events = [Event(ts, vals, is_expired=(kind == EXPIRED))
                  for ts, kind, vals in rows
                  if self.out_type == "all" or
                  (self.out_type == "current" and kind == CURRENT) or
                  (self.out_type == "expired" and kind == EXPIRED)]
        self.junction.publish(events)


class TriggerRuntime:
    """`define trigger T at every 5 sec | at 'cron' | at 'start'`:
    publishes (triggered_time) events into stream T on schedule
    (trigger/{Periodic,Cron,Start}Trigger.java; PeriodicTrigger.java:73)."""

    def __init__(self, app, td, junction: StreamJunction):
        self.app = app
        self.td = td
        self.junction = junction
        self.cron = None
        if td.at_cron not in (None, "start"):
            from ..utils.cron import CronSchedule
            self.cron = CronSchedule(td.at_cron)

    def arm(self, base_ms: int) -> None:
        if self.td.at_cron == "start":
            self._fire(base_ms)
            return
        if self.cron is not None:
            due = self.cron.next_fire(base_ms)
        else:
            due = base_ms + self.td.at_every_ms
        self.app.scheduler.notify_at(due, self._on_timer)

    def _on_timer(self, due: int) -> None:
        if not self.app.running:
            return
        self._fire(due)
        self.arm(due)

    def _fire(self, ts: int) -> None:
        self.junction.publish([Event(ts, (ts,))])


class QueryCallbackHandler(OutputHandler):
    def __init__(self):
        self.callbacks: list[QueryCallback] = []

    def handle(self, timestamp, rows):
        if not self.callbacks:
            return
        in_events = [Event(ts, vals) for ts, kind, vals in rows
                     if kind == CURRENT]
        rm_events = [Event(ts, vals, is_expired=True)
                     for ts, kind, vals in rows if kind == EXPIRED]
        if not in_events and not rm_events:
            return
        for cb in self.callbacks:
            cb.receive(timestamp, in_events or None, rm_events or None)


def _timer_windows(operators) -> list:
    """Window ops that schedule timers (one init_state probe each)."""
    return [op for op in operators
            if isinstance(op, WindowOp) and
            op.next_due(op.init_state()) is not None]


def _all_host_due(timer_ops) -> bool:
    return bool(timer_ops) and all(
        getattr(op, "host_due_bound", None) is not None
        for op in timer_ops)


class QueryRuntime(Receiver):
    """One query: an operator chain jitted into a single device step."""

    # explicit packed-ingest capability (send_arrays gates on this, NOT on
    # hasattr(process_packed): subclasses that need dedicated per-stream
    # receivers override it back to False)
    supports_packed = True

    def __init__(self, name: str, operators: list[Operator],
                 in_schema: StreamSchema, app: "SiddhiAppRuntime"):
        self.name = name
        self.operators = operators
        self.in_schema = in_schema
        self.out_schema = operators[-1].out_schema
        self.app = app
        self.output_handlers: list[OutputHandler] = []
        self.callback_handler = QueryCallbackHandler()
        # raw device-batch observers (no host row decode) — the zero-copy
        # path used by bench.py and device-to-device chaining
        self.batch_callbacks: list[Callable] = []
        self.states = tuple(op.init_state() for op in operators)
        self.table_deps = sorted({t for op in operators
                                  for t in op.table_ids()})
        self.max_step_capacity = SORT_HEAVY_CAP if any(
            getattr(op, "sort_heavy", False) for op in operators) else None
        self._step: Optional[Callable] = None
        self._packed_steps: dict = {}  # (enc, capacity) -> jitted step
        # device-resident emitted-row counter: accumulated inside the
        # packed step (zero host syncs); read once via stats()
        self._emitted_dev = jnp.int64(0)
        self._lock = threading.Lock()
        # when EVERY timer window offers a host due bound, stream steps
        # schedule timers host-side with zero device readbacks
        self._timer_ops = _timer_windows(operators)
        self._has_timers = bool(self._timer_ops)
        self._host_due_all = _all_host_due(self._timer_ops)
        # host-computed schedules (cron windows: the next fire time cannot
        # come from device state)
        self._host_sched = [op.host_schedule for op in operators
                            if getattr(op, "host_schedule", None)]
        self._sched_due: Optional[int] = None
        # clock of the latest EVENT step (timers due at or before it are
        # subsumed by in-step expiry — see _schedule)
        self._last_now = -(2 ** 62)
        self._skip_past_dues = not any(
            getattr(op, "needs_catchup", False) for op in operators)
        self.rate_limiter = None
        self._qstats = None  # lazily created when statistics enabled
        # set on the HEAD query of a fusible insert-into segment
        # (SiddhiAppRuntime._build_fused_chains): batches entering this
        # query traverse the whole segment in one XLA program
        self._fused_chain: Optional["FusedChain"] = None
        # set when this query is a member of a fan-out fusion group
        # (plan/optimizer.py FanoutGroup) — the junction dispatches the
        # group once per chunk; this reference is explain evidence and
        # keeps direct sends/timers on the standalone step
        self._fanout_group = None
        # cost-evidence ingest chunk cap (plan/optimizer.py): consulted
        # by the send_arrays capacity negotiation when this query heads
        # a fused chain with measured per-capacity centers
        self.preferred_ingest_cap: Optional[int] = None
        # DETAIL latency probe sampling counter (see _lat_sample)
        self._lat_counter = 0

    # -- compile ---------------------------------------------------------
    def _make_step(self):
        return jax.jit(_chain_body(self.operators, self._has_timers,
                                   self.name), **_donate(0, 1, 2))

    def _step_for(self, capacity: int) -> Callable:
        # one jit wrapper; XLA specializes per batch-capacity shape
        if self._step is None:
            self._step = self._make_step()
        return self._step

    def _packed_step_for(self, enc: tuple, capacity: int) -> Callable:
        """Fused unpack + operator chain over a PackedChunk's single buffer
        (the high-throughput ingest path, see core/ingest.py). One compile
        per (encoding tuple, capacity); encodings are sticky so this stays
        small.

        Sort-heavy queries (max_step_capacity set) do NOT shrink the
        transfer: the whole chunk still travels and dispatches once, and
        the step body runs a lax.scan over max_step_capacity-row
        sub-batches. XLA sort compile time grows superlinearly with row
        count (~169 s at 65k rows for a window+aggregate chain, measured),
        so the scan keeps the compiled sort width small while one dispatch
        covers the full chunk. Playback per-sub-batch time advances as the
        running max event time — the same clock the pre-scan split path
        derived per sub-chunk on the host."""
        fn = self._packed_steps.get((enc, capacity))
        if fn is None:
            fn = _build_packed_step(
                _chain_body(self.operators, self._has_timers, self.name),
                self.in_schema, enc, capacity, self.max_step_capacity,
                self.app._playback)
            self._packed_steps[(enc, capacity)] = fn
        return fn

    # sort-heavy queries cap the COMPILED sort width via the in-step scan
    # (see _packed_step_for), so the packed transfer chunk can be larger
    # than max_step_capacity — but not unbounded: XLA compile time of the
    # scanned step grows with total capacity (k=8 sub-steps: ~53 s;
    # k=128: ~452 s, measured), so packed chunks cap at 64k rows
    # (8 dispatches/1M events instead of 123, ~2x the throughput of
    # dispatch-per-8k with a first-compile cost that stays bounded)
    SCAN_CHUNK_CAP = 65536

    @property
    def max_packed_capacity(self):
        return None if self.max_step_capacity is None \
            else max(self.SCAN_CHUNK_CAP, self.max_step_capacity)

    def process_packed(self, chunk: PackedChunk) -> None:
        if self._fused_chain is not None:
            return self._fused_chain.process_packed(chunk)
        cost = self.app.cost
        probe = cost.probe("query", self.name) if cost.enabled else None
        with self.app.tracer.span("step", self.name, rows=chunk.n):
            lat = self._stats_mark(chunk.n)
            self._last_now = max(self._last_now, chunk.last_ts)
            with self._lock:
                step = self._packed_step_for(chunk.enc, chunk.capacity)
                with self._table_locks():
                    tstates = {t: self.app.tables[t].state
                               for t in self.table_deps}
                    (self.states, tstates, self._emitted_dev, out,
                     due) = step(self.states, tstates, self._emitted_dev,
                                 chunk.buf)
                    for t in self.table_deps:
                        self.app.tables[t].state = tstates[t]
            if lat is not None or probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(out.valid)
                if lat is not None:
                    lat.mark_out()
                if probe is not None:
                    probe.done(rows=chunk.n)
            if self._host_due_all and chunk.ts_min is not None:
                self._dispatch_output(out, chunk.last_ts)
                self._schedule(min(op.host_due_bound(chunk.ts_min)
                                   for op in self._timer_ops))
                return
            self._dispatch_output(out, chunk.last_ts,
                                  due=due if self._has_timers else None)

    def stats(self) -> dict:
        """Runtime counters (device-synced on read). ``probe_rounds`` /
        ``probe_steps`` is the mean number of group-key probe rounds
        (ops/keyed.py lookup_or_insert) per step that probed."""
        with self._lock:  # vs restore_state rebinding the counter
            emitted = int(jax.device_get(self._emitted_dev))
        return {"emitted": emitted, "overflow": self.overflow_total(),
                **self._state_sums(("probe_rounds", "probe_steps"))}

    # -- snapshot (SnapshotService state walk -> one device_get) ----------
    def snapshot_state(self) -> dict:
        with self._lock:
            snap = jax.device_get({"states": self.states,
                                   "emitted": self._emitted_dev})
            if self.rate_limiter is not None:
                snap["rate"] = self.rate_limiter.snapshot_state()
            return snap

    def restore_state(self, snap: dict) -> None:
        with self._lock:
            self.states = self._restored_states(snap["states"])
            self._emitted_dev = jnp.array(snap["emitted"], copy=True)
            self._sched_due = None
            if self.rate_limiter is not None and "rate" in snap:
                self.rate_limiter.restore_state(snap["rate"])

    def reschedule(self) -> None:
        """After restore: re-arm timers from the restored window states
        (the reference re-registers Schedulers on restore)."""
        if not self._has_timers:
            return
        with self._lock:  # restore_state rebinds the whole tuple
            states = self.states
        dues = [op.next_due(st) for op, st in zip(self.operators, states)
                if isinstance(op, WindowOp)]
        dues = [d for d in dues if d is not None]
        if dues:
            # one transfer for all window dues, not one sync per window
            self._schedule(min(int(d) for d in jax.device_get(dues)))

    def _restored_states(self, snapped) -> tuple:
        """Fresh device operator states from a snapshot. A key of an
        operator's state that the snapshot predates (a counter added
        since it was taken) restores at the operator's initial value."""
        states = []
        for op, st in zip(self.operators, snapped):
            if isinstance(st, dict):
                init = op.init_state()
                if init.keys() - st.keys():
                    st = {**init, **st}
            states.append(st)
        return _fresh_device(tuple(states))

    def _state_sums(self, names) -> dict:
        """Sum of each named scalar counter across the operator states.
        Walks nested state dicts — aggregator tables carry their own
        counters — and reads the counters found in one transfer."""
        found = {n: [] for n in names}

        def walk(st):
            if isinstance(st, dict):
                for k, v in st.items():
                    if k in found:
                        found[k].append(v)
                    else:
                        walk(v)
            elif isinstance(st, (tuple, list)):
                for v in st:
                    walk(v)

        with self._lock:  # vs restore_state rebinding mid-walk
            walk(self.states)
            host = jax.device_get(found)
        return {n: sum(int(v) for v in vs) for n, vs in host.items()}

    def overflow_total(self) -> int:
        """Sum of overflow counters across operator states (windows etc.;
        the 'counted, never silent' contract)."""
        return self._state_sums(("overflow",))["overflow"]

    # -- runtime ---------------------------------------------------------
    @staticmethod
    def encode_chunks(schema: StreamSchema, events: list[Event],
                      max_cap: Optional[int] = None):
        """Yield (EventBatch, last_timestamp) bucketed device batches."""
        max_cap = max_cap or BATCH_BUCKETS[-1]
        for start in range(0, len(events), max_cap):
            chunk = events[start:start + max_cap]
            rows = [e.data for e in chunk]
            tss = [e.timestamp for e in chunk]
            kinds = [EXPIRED if e.is_expired else CURRENT for e in chunk]
            cap = bucket_capacity(len(chunk))
            yield (batch_from_rows(schema, rows, tss, cap, kinds),
                   chunk[-1].timestamp)

    def _qs(self):
        from .stats import QueryStats
        if self._qstats is None:
            self._qstats = QueryStats()
        return self._qstats

    def _lat_sample(self) -> bool:
        """DETAIL latency probes block_until_ready the step output, which
        serializes the async dispatch pipeline — so only every Nth chunk
        is measured (SIDDHI_TPU_LAT_EVERY, default 16; the first chunk
        always samples so short runs still report)."""
        n = self._lat_counter
        self._lat_counter = n + 1
        return n % self.app.lat_sample_every == 0

    def _stats_mark(self, n: int):
        """Ingest-boundary throughput (real event count) + DETAIL
        latency handle."""
        if self.app.stats_level <= 0:
            return None
        qs = self._qs()
        qs.throughput.mark(n)
        if self.app.stats_level >= 2 and self._lat_sample():
            qs.latency.mark_in()
            return qs.latency
        return None

    def _stats_lat(self):
        """DETAIL latency only (timer/internal batches: not traffic)."""
        if self.app.stats_level < 2 or not self._lat_sample():
            return None
        lat = self._qs().latency
        lat.mark_in()
        return lat

    def receive(self, events: list[Event]) -> None:
        dbg = self.app.debugger
        if dbg is not None:
            from .debugger import QueryTerminal
            dbg.check_break_point(self.name, QueryTerminal.IN, events)
        if self.app.stats_level > 0:
            self._qs().throughput.mark(len(events))
        for batch, last_ts in self.encode_chunks(self.in_schema, events,
                                                 self.max_step_capacity):
            self.process_batch(batch, last_ts)

    @staticmethod
    def split_batch(batch: EventBatch, cap: int):
        """Slice an oversized device batch into <=cap sub-batches (eager
        device slicing — used when device-to-device chaining hands a large
        batch to a capacity-capped query)."""
        B = batch.capacity
        for off in range(0, B, cap):
            yield jax.tree_util.tree_map(lambda x: x[off:off + cap], batch)

    def process_batch(self, batch: EventBatch, timestamp: int,
                      now: Optional[int] = None,
                      skip_due: bool = False) -> None:
        cap = self.max_step_capacity
        if cap is not None and batch.capacity > cap:
            for sub in self.split_batch(batch, cap):
                self.process_batch(sub, timestamp, now=now,
                                   skip_due=skip_due)
            return
        if self._fused_chain is not None:
            return self._fused_chain.process_batch(batch, timestamp,
                                                   now=now,
                                                   skip_due=skip_due)
        cost = self.app.cost
        probe = cost.probe("query", self.name) if cost.enabled else None
        with self.app.tracer.span("step", self.name,
                                  capacity=int(batch.capacity)):
            if now is None:
                now = self.app.current_time()
            lat = self._stats_lat()
            self._last_now = max(self._last_now, int(now))
            now_dev = jnp.asarray(now, dtype=jnp.int64)
            with self._lock:
                step = self._step_for(batch.capacity)
                with self._table_locks():
                    tstates = {t: self.app.tables[t].state
                               for t in self.table_deps}
                    (self.states, tstates, self._emitted_dev, out,
                     due) = step(self.states, tstates, self._emitted_dev,
                                 batch, now_dev)
                    for t in self.table_deps:
                        self.app.tables[t].state = tstates[t]
            if lat is not None or probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(out.valid)
                if lat is not None:
                    lat.mark_out()
                if probe is not None:
                    probe.done(rows=int(batch.capacity))
            self._dispatch_output(
                out, timestamp,
                due=due if (self._has_timers and not skip_due) else None)

    def _table_locks(self):
        stack = contextlib.ExitStack()
        for t in self.table_deps:  # sorted — consistent lock order
            stack.enter_context(self.app.tables[t].lock)
        return stack

    def set_rate_limiter(self, rl) -> None:
        """Install an output rate limiter: all row consumers (insert-into
        handlers + query/stream callbacks) see only what it emits.
        batch_callbacks stay a pre-limit device tap."""
        rl.emit = self._emit_limited
        rl.start(self.app)
        self.rate_limiter = rl
        # a limiter makes this query's hop unfusible — re-derive segments
        self.app._rebuild_fused_chains()

    def _emit_limited(self, timestamp: int, rows) -> None:
        for h in self.output_handlers:
            h.handle(timestamp, rows)
        self.callback_handler.handle(timestamp, rows)

    def _dispatch_output(self, out, timestamp: int, due=None) -> None:
        """Raw-batch observers, device-to-device chaining, timer
        scheduling, and (only when someone still needs rows) host decode +
        handler/callback delivery."""
        for cb in self.batch_callbacks:
            cb(out)
        # transfer + decode at most ONCE per emission, shared by every
        # delivery path (core/event.py OutputFetch); the decode is this
        # module's rows_from_batch, looked up per emission
        fetch = OutputFetch(self.app.tracer, self.name,
                            self.out_schema.types, out,
                            decode=rows_from_batch)
        _current: list = []

        def current_once():
            # CURRENT-kind rewrite shared across ALL handlers of this
            # emission: one jitted dispatch per emitted batch, no matter
            # how many insert-into junctions the output fans out to
            if not _current:
                _current.append(_rewrite_current(out))
            return _current[0]

        dbg = self.app.debugger
        if dbg is not None:
            from .debugger import QueryTerminal
            if (self.name, QueryTerminal.OUT) in dbg._breakpoints:
                dbg.check_break_point(
                    self.name, QueryTerminal.OUT,
                    [Event(ts, vals, is_expired=(k == EXPIRED))
                     for ts, k, vals in fetch.rows()])
        if self.rate_limiter is not None:
            if due is not None:
                _, due_host = fetch.host(due)
                self._schedule(int(due_host))
            rows = fetch.rows()
            if rows:
                self.rate_limiter.process(timestamp, rows)
            return
        row_handlers = [h for h in self.output_handlers
                        if not h.handle_device_batch(
                            out, timestamp, current=current_once)]
        if not (row_handlers or self.callback_handler.callbacks):
            if due is not None:
                # NO sync here: a blocking device->host read would stall
                # the pipeline; start an async copy and resolve the int
                # right before the next clock advance
                # (app._resolve_dues) — by then the copy has landed
                self.app.defer_due(self, due)
            return
        if due is not None:
            _, due_host = fetch.host(due)
            self._schedule(int(due_host))
        out_rows = fetch.rows(self._host_shape_rows)
        if not fetch.rows():    # nothing decoded (before the order-by)
            return
        # ingest->emit SLO mark (obs/slo.py): host rows for this query's
        # sinks/callbacks just materialized — the device_get above
        # already forced the sync, so the sample is honest. Fused
        # segments land here via the tail member (FusedChain delegates
        # its terminal delivery to tail._dispatch_output).
        slo = self.app.slo
        if slo is not None:
            slo.on_emit(self.name, rows=len(out_rows))
        for h in row_handlers:
            h.handle(timestamp, out_rows)
        self.callback_handler.handle(timestamp, out_rows)

    def _host_shape_rows(self, rows):
        """STRING order-by (+ its offset/limit) applied on decoded rows —
        the host edge of shape_output (batch_callbacks stay unordered,
        documented in ops/selector.compile_order_by)."""
        shape = getattr(self.operators[-1], "host_shape", None)
        if not shape:
            return rows
        order, offset, limit = shape
        for idx, direction in reversed(order):
            rows = sorted(rows,
                          key=lambda r: (r[2][idx] is None, r[2][idx]),
                          reverse=(direction == "desc"))
        if offset or limit:
            off = offset or 0
            rows = rows[off:off + limit] if limit is not None \
                else rows[off:]
        return rows

    # -- timers ----------------------------------------------------------
    def _schedule(self, due: int) -> None:
        if due >= int(POS_INF):
            return
        if due <= self._last_now and self._skip_past_dues \
                and self.app._columnar:
            # the event step that produced this due already processed
            # expiry/flush work up to its own clock — firing a timer for
            # an instant the step covered is a pure no-op dispatch
            # (windows expire at exact per-row points in-step). Ops that
            # genuinely need per-boundary catch-up (hopping) opt out via
            # needs_catchup.
            return
        if self._sched_due is not None and self._sched_due <= due:  # lint: disable=racy-attribute-read (arm-dedup heuristic only; a stale due costs one redundant no-op timer arm)
            return
        self._sched_due = due
        self.app.scheduler.notify_at(due, self._on_timer)

    def arm_host_timers(self, base_ms: int) -> None:
        """Schedule host-computed fires (cron windows) after base_ms."""
        for fn in self._host_sched:
            self._schedule(int(fn(base_ms)))

    def _on_timer(self, due: int) -> None:
        self._sched_due = None
        if not self.app.running:
            return
        now = max(due, self.app.current_time())
        # the TIMER row carries the ADVANCED clock, not the scheduled due:
        # window expiry compares buffered rows against the timer row's ts,
        # and the reference's playback clock has already advanced when a
        # timer fires — one fire drains every pending expiry (per-due rows
        # would re-arm a timer per expiry instant and cascade)
        if self._host_due_all and self.app._playback:
            # host-bounded timers: skip the device due readback entirely
            # and re-arm at now+1 — at most one (cheap, 16-row) timer
            # step per clock advance, zero device->host reads
            self.process_batch(_timer_batch(self.in_schema, now), due,
                               now=now, skip_due=True)
            self._schedule(now + 1)
        else:
            self.process_batch(_timer_batch(self.in_schema, now), due,
                               now=now)
        if self._host_sched:
            self.arm_host_timers(due)


class FusedChain:
    """A fusible linear `insert into` segment [Q1 -> Q2 -> ... -> Qk]
    compiled into ONE jitted chain step
    (statesQ1..Qk, tstates, emittedQ1..Qk, batch, now) ->
    (states', tstates', emitted', out, dues) — a micro-batch traverses
    the whole segment in a single XLA program with the insert-into
    CURRENT-kind rewrite done inside the trace, instead of one jit
    dispatch plus three eager ops per hop.

    Eligibility is decided by SiddhiAppRuntime._fusible_next (see
    docs/performance.md); the HEAD query's process_batch/process_packed
    delegate here. Member queries keep their own per-query steps for
    everything else (their timers, direct sends to the intermediate
    streams), so fused and unfused execution interleave safely: every
    path updates `q.states` under `q._lock`, and the fused step takes
    the member locks in segment order before running."""

    def __init__(self, app: "SiddhiAppRuntime", queries: list,
                 schedule: Optional[list] = None):
        self.app = app
        self.queries = list(queries)
        self.head = self.queries[0]
        self.tail = self.queries[-1]
        self.name = "+".join(q.name for q in self.queries)
        self.members = tuple(q.name for q in self.queries)  # span args
        self.table_deps = sorted({t for q in self.queries
                                  for t in q.table_deps})
        # execution schedule (plan/optimizer.py): member ops + per-member
        # emitted-count boundaries + hop rewrites. The optimizer's filter
        # pushdown hands a reordered schedule; None keeps declaration
        # order (bit-identical to the pre-schedule nested composition).
        from ..plan.optimizer import natural_schedule
        self.schedule = schedule or natural_schedule(self.queries)
        self._chain = self._make_chain()
        self._step: Optional[Callable] = None
        self._packed_steps: dict = {}

    def _make_chain(self):
        queries = self.queries
        schedule = self.schedule

        def chain(states, tstates, emitteds, batch, now):
            cur = batch
            new_states = [list(st) for st in states]
            new_emitted = list(emitteds)
            for entry in schedule:
                kind = entry[0]
                if kind == "op":
                    _, mi, oi = entry
                    op = queries[mi].operators[oi]
                    st = new_states[mi][oi]
                    with op_scope(
                            f"{queries[mi].name}/{type(op).__name__}"):
                        if op.needs_tables:
                            st, cur, tstates = op.step_tables(
                                st, cur, now, tstates)
                        else:
                            st, cur = op.step(st, cur, now)
                    new_states[mi][oi] = st
                elif kind == "count":
                    mi = entry[1]
                    new_emitted[mi] = emitteds[mi] + \
                        cur.count().astype(jnp.int64)
                else:  # insert-into hop, in-trace
                    cur = _as_current(cur)
            dues = []
            for mi, q in enumerate(queries):
                if q._has_timers:
                    ds = [op.next_due(st) for op, st in
                          zip(q.operators, new_states[mi])
                          if isinstance(op, WindowOp)]
                    ds = [d for d in ds if d is not None]
                    due = ds[0]
                    for d in ds[1:]:
                        due = jnp.minimum(due, d)
                else:
                    due = jnp.asarray(POS_INF)
                dues.append(due)
            return (tuple(tuple(s) for s in new_states), tstates,
                    tuple(new_emitted), cur, tuple(dues))

        return chain

    # -- locks -----------------------------------------------------------
    def _locks(self):
        stack = contextlib.ExitStack()
        for q in self.queries:  # segment order; no path takes them in
            stack.enter_context(q._lock)  # reverse, so no deadlock
        return stack

    def _table_locks(self):
        stack = contextlib.ExitStack()
        for t in self.table_deps:  # sorted — consistent lock order
            stack.enter_context(self.app.tables[t].lock)
        return stack

    # -- compile ---------------------------------------------------------
    def _step_for(self) -> Callable:
        if self._step is None:
            self._step = jax.jit(self._chain, **_donate(0, 1, 2))
        return self._step

    def _packed_step_for(self, enc: tuple, capacity: int) -> Callable:
        fn = self._packed_steps.get((enc, capacity))
        if fn is None:
            fn = _build_packed_step(self._chain, self.head.in_schema,
                                    enc, capacity,
                                    self.head.max_step_capacity,
                                    self.app._playback)
            self._packed_steps[(enc, capacity)] = fn
        return fn

    # -- runtime ---------------------------------------------------------
    def _run(self, step, *args):
        """Execute the fused step under segment + table locks and write
        every member query's state back (donated inputs are replaced
        before the locks release, so snapshot/restore and statistics —
        which take the same locks/barrier — always see live buffers)."""
        with self._locks():
            with self._table_locks():
                tstates = {t: self.app.tables[t].state
                           for t in self.table_deps}
                states = tuple(q.states for q in self.queries)
                emitted = tuple(q._emitted_dev for q in self.queries)
                states, tstates, emitted, out, dues = step(
                    states, tstates, emitted, *args)
                for t in self.table_deps:
                    self.app.tables[t].state = tstates[t]
            for q, st, em in zip(self.queries, states, emitted):
                q.states = st
                q._emitted_dev = em
        return out, dues

    def process_packed(self, chunk: PackedChunk) -> None:
        # ONE span per fused segment (the segment IS one XLA program);
        # member queries are named in args instead of per-hop spans —
        # and ONE cost center, for the same reason (obs/costmodel.py)
        cost = self.app.cost
        # cap rides the probe: per-capacity centers (chain/<n>@<cap>)
        # are the optimizer's chunk-size evidence (plan/optimizer.py)
        probe = cost.probe("chain", self.name, cap=chunk.capacity) \
            if cost.enabled else None
        with self.app.tracer.span("chain", self.name, rows=chunk.n,
                                  members=self.members):
            lat = self.head._stats_mark(chunk.n)
            for q in self.queries:
                q._last_now = max(q._last_now, chunk.last_ts)
            out, dues = self._run(
                self._packed_step_for(chunk.enc, chunk.capacity),
                chunk.buf)
            if lat is not None or probe is not None:
                jax.block_until_ready(out.valid)
                if lat is not None:
                    lat.mark_out()
                if probe is not None:
                    probe.done(rows=chunk.n)
            self._schedule_dues(dues, chunk.ts_min)
            self.tail._dispatch_output(out, chunk.last_ts)

    def process_batch(self, batch: EventBatch, timestamp: int,
                      now: Optional[int] = None,
                      skip_due: bool = False) -> None:
        cost = self.app.cost
        probe = cost.probe("chain", self.name) if cost.enabled else None
        with self.app.tracer.span("chain", self.name,
                                  members=self.members):
            if now is None:
                now = self.app.current_time()
            lat = self.head._stats_lat()
            for q in self.queries:
                q._last_now = max(q._last_now, int(now))
            now_dev = jnp.asarray(now, dtype=jnp.int64)
            out, dues = self._run(self._step_for(), batch, now_dev)
            if lat is not None or probe is not None:
                jax.block_until_ready(out.valid)
                if lat is not None:
                    lat.mark_out()
                if probe is not None:
                    probe.done(rows=int(batch.capacity))
            self._schedule_dues(dues, None, skip_head_due=skip_due)
            self.tail._dispatch_output(out, timestamp)

    def _schedule_dues(self, dues, ts_min,
                       skip_head_due: bool = False) -> None:
        """Per-member timer scheduling: host-bounded windows schedule
        with zero readbacks; device dues resolve asynchronously
        (app.defer_due) like the no-row-consumer single-query path."""
        for i, (q, due) in enumerate(zip(self.queries, dues)):
            if not q._has_timers or (skip_head_due and i == 0):
                continue
            if q._host_due_all and ts_min is not None:
                q._schedule(min(op.host_due_bound(ts_min)
                                for op in q._timer_ops))
            else:
                self.app.defer_due(q, due)


class StreamCallbackReceiver(Receiver):
    def __init__(self, callback: StreamCallback):
        self.callback = callback

    def receive(self, events):
        self.callback.receive(events)


class PatternStreamReceiver(Receiver):
    """Junction subscriber feeding one stream of a pattern query
    (= PatternMultiProcessStreamReceiver, .../state/receiver/*.java:29)."""

    supports_packed = True

    def __init__(self, runtime: "PatternQueryRuntime", stream_id: str):
        self.runtime = runtime
        self.stream_id = stream_id

    @property
    def max_step_capacity(self):
        return self.runtime.max_step_capacity

    def receive(self, events):
        self.runtime.process_stream_events(self.stream_id, events)

    def process_batch(self, batch, last_ts):
        self.runtime.process_pattern_batch(self.stream_id, batch, last_ts)

    def process_packed(self, chunk):
        self.runtime.process_pattern_packed(self.stream_id, chunk)


class PatternQueryRuntime(QueryRuntime):
    """Pattern/sequence query: the NFA engine feeds the selector chain.
    One receiver per distinct input stream; all share the pending-match
    table (reference: StateStreamRuntime + per-state processors).

    The base-class `states` tuple holds the selector operator states; the
    NFA pending table lives in `nfa_state`."""

    supports_packed = False  # consumes via PatternStreamReceivers only

    def __init__(self, name: str, engine: NfaEngine,
                 sel_ops: list[Operator], app: "SiddhiAppRuntime"):
        super().__init__(name, sel_ops, engine.match_schema, app)
        self.engine = engine
        self.nfa_state = engine.init_state()
        self._stream_steps: dict = {}
        self._timer_step: Optional[Callable] = None
        self._due_fn: Optional[Callable] = None
        self._arm_start_fn: Optional[Callable] = None

    def receive(self, events: list[Event]) -> None:
        raise RuntimeError(
            "pattern runtimes consume via per-stream PatternStreamReceivers")

    def overflow_total(self) -> int:
        """Include the NFA pending-table overflow counter."""
        total = super().overflow_total()
        return total + int(jax.device_get(self.nfa_state["overflow"]))

    def snapshot_state(self) -> dict:
        with self._lock:
            return jax.device_get({"states": self.states,
                                   "emitted": self._emitted_dev,
                                   "nfa": self.nfa_state})

    def restore_state(self, snap: dict) -> None:
        with self._lock:
            self.states = self._restored_states(snap["states"])
            self._emitted_dev = jnp.array(snap["emitted"], copy=True)
            self.nfa_state = _fresh_device(snap["nfa"])
            self._sched_due = None

    def reschedule(self) -> None:
        self._schedule_absent()

    def arm_start_deadlines(self, ts: int) -> None:
        """Base start-state absent deadlines at app start time
        (AbsentStreamPreStateProcessor.partitionCreated:291-308)."""
        with self._lock:
            if self._arm_start_fn is None:
                self._arm_start_fn = jax.jit(self.engine.arm_start)
            self.nfa_state = self._arm_start_fn(self.nfa_state,
                                                np.int64(ts))
        self._schedule_absent()

    # -- absent-pattern timers -------------------------------------------
    def _due_fn_for(self) -> Callable:
        if self._due_fn is None:
            self._due_fn = jax.jit(self.engine.next_due)
        return self._due_fn

    def _schedule_absent(self) -> None:
        """After a step: schedule a wakeup at the earliest live absent
        deadline (AbsentStreamPreStateProcessor's scheduler role)."""
        if not getattr(self.engine, "has_absent", False):
            return
        due = int(jax.device_get(self._due_fn_for()(self.nfa_state)))
        self._schedule(due)

    def _timer_step_for(self) -> Callable:
        """The absent-deadline timer step, built once and cached on the
        instance (the compile service AOT-warms it at start)."""
        if self._timer_step is None:
            tstep = self.engine.make_timer_step()
            sel_ops = self.operators

            def full(nfa_state, sel_states, emitted, now):
                nfa_state, match = tstep(nfa_state, now)
                new_sel = []
                for op, st in zip(sel_ops, sel_states):
                    st, match = op.step(st, match, now)
                    new_sel.append(st)
                emitted = emitted + match.count().astype(jnp.int64)
                return nfa_state, tuple(new_sel), emitted, match

            self._timer_step = jax.jit(full, **_donate(0, 1, 2))
        return self._timer_step

    def _on_timer(self, due: int) -> None:
        self._sched_due = None
        if not self.app.running:
            return
        cost = self.app.cost
        probe = cost.probe("pattern", f"{self.name}.timer") \
            if cost.enabled else None
        self._timer_step_for()
        with self._lock:
            (self.nfa_state, self.states, self._emitted_dev,
             out) = self._timer_step(self.nfa_state, self.states,
                                     self._emitted_dev, np.int64(due))
        if probe is not None:
            # sampled branch only: the sync serializes the pipeline
            jax.block_until_ready(out.valid)
            probe.done()
        self._dispatch_output(out, due)
        self._schedule_absent()

    def _step_for_stream(self, stream_id: str,
                         packed_key=None) -> Callable:
        key = (stream_id, packed_key)
        fn = self._stream_steps.get(key)
        if fn is None:
            nfa_step = self.engine.make_stream_step(stream_id)
            sel_ops = self.operators
            schema = self.app.schemas[stream_id]

            def run(nfa_state, sel_states, tstates, batch, now):
                nfa_state, match = nfa_step(nfa_state, batch, now)
                new_sel = []
                for op, st in zip(sel_ops, sel_states):
                    if op.needs_tables:
                        st, match, tstates = op.step_tables(st, match, now,
                                                            tstates)
                    else:
                        st, match = op.step(st, match, now)
                    new_sel.append(st)
                return nfa_state, tuple(new_sel), tstates, match

            if packed_key is not None:
                enc, capacity = packed_key

                def step(nfa_state, sel_states, tstates, emitted, buf):
                    batch, now = unpack_buffer(schema, enc, capacity, buf)
                    nfa_state, sel, tstates, match = run(
                        nfa_state, sel_states, tstates, batch, now)
                    emitted = emitted + match.count().astype(jnp.int64)
                    return nfa_state, sel, tstates, emitted, match
            else:
                def step(nfa_state, sel_states, tstates, emitted, batch,
                         now):
                    nfa_state, sel, tstates, match = run(
                        nfa_state, sel_states, tstates, batch, now)
                    emitted = emitted + match.count().astype(jnp.int64)
                    return nfa_state, sel, tstates, emitted, match
            fn = jax.jit(step, **_donate(0, 1, 2, 3))
            self._stream_steps[key] = fn
        return fn

    def process_pattern_packed(self, stream_id: str,
                               chunk: PackedChunk) -> None:
        cost = self.app.cost
        probe = cost.probe("pattern", f"{self.name}.{stream_id}") \
            if cost.enabled else None
        with self.app.tracer.span("step", self.name, rows=chunk.n,
                                  stream=stream_id):
            self._last_now = max(self._last_now, chunk.last_ts)
            with self._lock:
                step = self._step_for_stream(stream_id,
                                             (chunk.enc, chunk.capacity))
                with self._table_locks():
                    tstates = {t: self.app.tables[t].state
                               for t in self.table_deps}
                    (self.nfa_state, self.states, tstates,
                     self._emitted_dev, out) = step(
                        self.nfa_state, self.states, tstates,
                        self._emitted_dev, chunk.buf)
                    for t in self.table_deps:
                        self.app.tables[t].state = tstates[t]
            if probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(out.valid)
                probe.done(rows=chunk.n)
            self._dispatch_output(out, chunk.last_ts)
            self._schedule_absent()

    def process_stream_events(self, stream_id: str, events) -> None:
        schema = self.app.schemas[stream_id]
        for batch, last_ts in self.encode_chunks(schema, events,
                                                 self.max_step_capacity):
            self.process_pattern_batch(stream_id, batch, last_ts)

    def process_pattern_batch(self, stream_id: str, batch: EventBatch,
                              timestamp: int) -> None:
        cap = self.max_step_capacity
        if cap is not None and batch.capacity > cap:
            for sub in self.split_batch(batch, cap):
                self.process_pattern_batch(stream_id, sub, timestamp)
            return
        now_host = self.app.current_time()
        cost = self.app.cost
        probe = cost.probe("pattern", f"{self.name}.{stream_id}") \
            if cost.enabled else None
        self._last_now = max(self._last_now, int(now_host))
        now = jnp.asarray(now_host, dtype=jnp.int64)
        with self.app.tracer.span("step", self.name,
                                  capacity=int(batch.capacity),
                                  stream=stream_id):
            with self._lock:
                step = self._step_for_stream(stream_id)
                with self._table_locks():
                    tstates = {t: self.app.tables[t].state
                               for t in self.table_deps}
                    (self.nfa_state, self.states, tstates,
                     self._emitted_dev, out) = step(
                        self.nfa_state, self.states, tstates,
                        self._emitted_dev, batch, now)
                    for t in self.table_deps:
                        self.app.tables[t].state = tstates[t]
            if probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(out.valid)
                probe.done(rows=int(batch.capacity))
            self._dispatch_output(out, timestamp)
            # arm the scheduler at the earliest live absent deadline so
            # the pattern fires on clock advance even when no further
            # events come (AbsentStreamPreStateProcessor's scheduler
            # role); costs one device readback per step, only for
            # has_absent engines
            self._schedule_absent()


class JoinStreamReceiver(Receiver):
    supports_packed = True

    def __init__(self, runtime: "JoinQueryRuntime", side: str):
        self.runtime = runtime
        self.side = side

    @property
    def max_step_capacity(self):
        return self.runtime.max_step_capacity

    def receive(self, events):
        self.runtime.process_side_events(self.side, events)

    def process_batch(self, batch, last_ts):
        self.runtime.process_side_batch(self.side, batch, last_ts)

    def process_packed(self, chunk):
        self.runtime.process_side_packed(self.side, chunk)


class JoinQueryRuntime(QueryRuntime):
    """Two-stream windowed join (JoinStreamRuntime + cross-wired
    JoinProcessors in the reference). Each side runs [filters..., window];
    the window output crosses the opposite window's findable buffer."""

    supports_packed = False  # consumes via JoinStreamReceivers only

    def __init__(self, name: str, left_ops, right_ops, crosses,
                 sel_ops, in_schemas, out_schema_override, app,
                 side_tables=None):
        super().__init__(name, sel_ops, out_schema_override, app)
        self.out_schema = sel_ops[-1].out_schema if sel_ops \
            else out_schema_override
        self.side_ops = {"L": left_ops, "R": right_ops}
        self.crosses = crosses  # {"L": JoinCross|None, "R": ...}
        self.in_schemas = in_schemas  # {"L": schema, "R": schema}
        self.side_tables = side_tables or {}  # {"L"/"R": TableRuntime}
        self.side_states = {
            s: tuple(op.init_state() for op in ops)
            for s, ops in self.side_ops.items()}
        self.table_deps = sorted(set(self.table_deps) | {
            t.table_id for t in self.side_tables.values()})
        self._side_steps: dict = {}
        self._join_timer_ops = _timer_windows(
            [op for ops in self.side_ops.values() for op in ops])
        self._has_timers = bool(self._join_timer_ops)
        self._join_host_due = _all_host_due(self._join_timer_ops)
        self._overflow_dev = jnp.int64(0)
        if any(getattr(op, "sort_heavy", False)
               for ops in self.side_ops.values() for op in ops):
            self.max_step_capacity = SORT_HEAVY_CAP

    def receive(self, events):
        raise RuntimeError("join runtimes consume via JoinStreamReceivers")

    @property
    def overflow(self) -> int:
        """Total join pairs dropped at the join_cap limit so far."""
        return int(jax.device_get(self._overflow_dev))

    def overflow_total(self) -> int:
        """Selector + both side-chains' window overflow + join-cap drops."""
        total = super().overflow_total()
        for states in jax.device_get(self.side_states).values():
            for st in states:
                if isinstance(st, dict) and "overflow" in st:
                    total += int(st["overflow"])
        return total + self.overflow

    def snapshot_state(self) -> dict:
        with self._lock:
            return jax.device_get({"states": self.states,
                                   "emitted": self._emitted_dev,
                                   "sides": self.side_states,
                                   "join_overflow": self._overflow_dev})

    def restore_state(self, snap: dict) -> None:
        with self._lock:
            self.states = self._restored_states(snap["states"])
            self._emitted_dev = jnp.array(snap["emitted"], copy=True)
            self.side_states = _fresh_device(snap["sides"])
            self._overflow_dev = jnp.array(snap["join_overflow"],
                                           copy=True)
            self._sched_due = None

    def reschedule(self) -> None:
        if not self._has_timers:
            return
        dues = []
        for side, ops in self.side_ops.items():
            for op, st in zip(ops, self.side_states[side]):
                if isinstance(op, WindowOp):
                    d = op.next_due(st)
                    if d is not None:
                        dues.append(d)
        if dues:
            # both sides' dues come back in one pytree transfer
            self._schedule(min(int(d) for d in jax.device_get(dues)))

    def _step_for_side(self, side: str, packed_key=None) -> Callable:
        fn = self._side_steps.get((side, packed_key))
        if fn is None:
            my_ops = self.side_ops[side]
            opp = "R" if side == "L" else "L"
            opp_window = self.side_ops[opp][-1] \
                if self.side_ops[opp] else None  # table side: no window
            cross = self.crosses[side]
            sel_ops = self.operators
            has_timers = self._has_timers

            opp_table = self.side_tables.get(opp)
            # captured at compile time: columnar apps coalesce timer
            # fires, so crosses gate pairs by opposite-row liveness
            gate_alive = self.app._columnar

            def step(my_states, opp_states, sel_states, tstates, batch,
                     now):
                new_my = []
                for op, st in zip(my_ops, my_states):
                    st, batch = op.step(st, batch, now)
                    new_my.append(st)
                if cross is not None:
                    if opp_table is not None:
                        opp_buf = opp_table.buffer(
                            tstates[opp_table.table_id])
                    else:
                        opp_buf = opp_window.findable_buffer(opp_states[-1])
                    joined, lost = cross.cross(batch, opp_buf,
                                               gate_alive=gate_alive)
                else:
                    cap = 16
                    sch = combined_schema("#j", self.in_schemas["L"],
                                          self.in_schemas["R"])
                    joined = EventBatch.empty(sch, cap)
                    lost = jnp.int64(0)
                new_sel = []
                for op, st in zip(sel_ops, sel_states):
                    if op.needs_tables:
                        st, joined, tstates = op.step_tables(
                            st, joined, now, tstates)
                    else:
                        st, joined = op.step(st, joined, now)
                    new_sel.append(st)
                if has_timers:
                    dues = [op.next_due(st) for op, st in
                            zip(my_ops, new_my) if isinstance(op, WindowOp)]
                    dues = [d for d in dues if d is not None]
                    due = dues[0] if dues else jnp.asarray(POS_INF)
                    for d in dues[1:]:
                        due = jnp.minimum(due, d)
                else:
                    due = jnp.asarray(POS_INF)
                return (tuple(new_my), tuple(new_sel), tstates, joined,
                        lost, due)

            if packed_key is not None:
                my_schema = self.in_schemas[side]
                enc, capacity = packed_key

                def pstep(my_states, opp_states, sel_states, tstates,
                          emitted, buf):
                    batch, now = unpack_buffer(my_schema, enc, capacity,
                                               buf)
                    my, sel, tstates, joined, lost, due = step(
                        my_states, opp_states, sel_states, tstates, batch,
                        now)
                    emitted = emitted + joined.count().astype(jnp.int64)
                    return my, sel, tstates, emitted, joined, lost, due

                # opp_states (arg 1) is read-only and NOT returned — the
                # opposite side keeps referencing it, so never donate it
                fn = jax.jit(pstep, **_donate(0, 2, 3, 4))
            else:
                def ustep(my_states, opp_states, sel_states, tstates,
                          emitted, batch, now):
                    my, sel, tstates, joined, lost, due = step(
                        my_states, opp_states, sel_states, tstates, batch,
                        now)
                    emitted = emitted + joined.count().astype(jnp.int64)
                    return my, sel, tstates, emitted, joined, lost, due

                fn = jax.jit(ustep, **_donate(0, 2, 3, 4))
            self._side_steps[(side, packed_key)] = fn
        return fn

    _SIDE_NAMES = {"L": "left", "R": "right"}

    def _side_center(self, side: str) -> str:
        """Cost-center name for one side step: ``<q>.left[probe]`` —
        the kernel suffix makes the persisted cost table name WHICH
        join kernel was measured (the planner's cost-table consultation
        reads it back; tools/profile_report.py asserts it)."""
        nm = f"{self.name}.{self._SIDE_NAMES[side]}"
        cross = self.crosses.get(side)
        if cross is not None:
            nm += f"[{cross.kernel}]"
        return nm

    def process_side_packed(self, side: str, chunk: PackedChunk) -> None:
        opp = "R" if side == "L" else "L"
        cost = self.app.cost
        probe = cost.probe("join", self._side_center(side)) \
            if cost.enabled else None
        with self.app.tracer.span("step", self.name, rows=chunk.n,
                                  side=side):
            self._last_now = max(self._last_now, chunk.last_ts)
            with self._lock:
                step = self._step_for_side(side,
                                           (chunk.enc, chunk.capacity))
                with self._table_locks():
                    tstates = {t: self.app.tables[t].state
                               for t in self.table_deps}
                    (my, sel, tstates, self._emitted_dev, out, lost,
                     due) = step(self.side_states[side],
                                 self.side_states[opp], self.states,
                                 tstates, self._emitted_dev, chunk.buf)
                    for t in self.table_deps:
                        self.app.tables[t].state = tstates[t]
                self.side_states[side] = my
                self.states = sel
                self._overflow_dev = self._overflow_dev + lost
            if probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(out.valid)
                probe.done(rows=chunk.n)
            if self._join_host_due and chunk.ts_min is not None:
                self._dispatch_output(out, chunk.last_ts)
                self._schedule(min(op.host_due_bound(chunk.ts_min)
                                   for op in self._join_timer_ops))
                return
            self._dispatch_output(out, chunk.last_ts,
                                  due=due if self._has_timers else None)

    def process_side_events(self, side: str, events) -> None:
        for batch, last_ts in self.encode_chunks(self.in_schemas[side],
                                                 events,
                                                 self.max_step_capacity):
            self.process_side_batch(side, batch, last_ts)

    def process_side_batch(self, side: str, batch: EventBatch,
                           timestamp: int, now: Optional[int] = None,
                           skip_due: bool = False) -> None:
        cap = self.max_step_capacity
        if cap is not None and batch.capacity > cap:
            for sub in self.split_batch(batch, cap):
                self.process_side_batch(side, sub, timestamp, now=now,
                                        skip_due=skip_due)
            return
        cost = self.app.cost
        probe = cost.probe("join", self._side_center(side)) \
            if cost.enabled else None
        with self.app.tracer.span("step", self.name,
                                  capacity=int(batch.capacity), side=side):
            if batch.kind is not None and not bool(np.any(
                    np.asarray(batch.kind) == TIMER)):
                # only EVENT steps advance the due-subsumption clock —
                # timer fires must not suppress their own follow-up dues
                self._last_now = max(self._last_now, int(timestamp))
            if now is None:
                now = self.app.current_time()
            now_dev = jnp.asarray(now, dtype=jnp.int64)
            opp = "R" if side == "L" else "L"
            with self._lock:
                step = self._step_for_side(side)
                with self._table_locks():
                    tstates = {t: self.app.tables[t].state
                               for t in self.table_deps}
                    (my, sel, tstates, self._emitted_dev, out, lost,
                     due) = step(self.side_states[side],
                                 self.side_states[opp], self.states,
                                 tstates, self._emitted_dev, batch,
                                 now_dev)
                    for t in self.table_deps:
                        self.app.tables[t].state = tstates[t]
                self.side_states[side] = my
                self.states = sel
                # join pairs beyond join_cap are dropped by
                # JoinCross.cross — counted here, never silent (join.py
                # design contract)
                self._overflow_dev = self._overflow_dev + lost
            if probe is not None:
                # sampled branch only: the sync serializes the pipeline
                jax.block_until_ready(out.valid)
                probe.done(rows=int(batch.capacity))
            self._dispatch_output(
                out, timestamp,
                due=due if (self._has_timers and not skip_due) else None)

    def _on_timer(self, due: int) -> None:
        self._sched_due = None
        if not self.app.running:
            return
        now = max(due, self.app.current_time())
        skip = self._join_host_due and self.app._playback
        for side in ("L", "R"):
            # TIMER rows carry the advanced clock (see QueryRuntime
            # ._on_timer): one fire drains all pending window expiries
            batch = _timer_batch(self.in_schemas[side], now)
            self.process_side_batch(side, batch, due, now=now,
                                    skip_due=skip)
        if skip:
            self._schedule(now + 1)


def _timer_batch(schema: StreamSchema, due: int) -> EventBatch:
    from .event import TIMER
    cap = BATCH_BUCKETS[0]
    batch = batch_from_rows(schema, [], [], cap)
    ts = np.zeros((cap,), dtype=np.int64)
    ts[0] = due
    kind = np.zeros((cap,), dtype=np.int32)
    kind[0] = TIMER
    valid = np.zeros((cap,), dtype=np.bool_)
    valid[0] = True
    return EventBatch(ts=ts, cols=batch.cols, nulls=batch.nulls,
                      kind=kind, valid=valid)


class SiddhiAppRuntime:
    """Per-app container: junctions, query runtimes, handlers, lifecycle
    (reference SiddhiAppRuntimeImpl: start/shutdown :440-655,
    persist/restore :677-755)."""

    def __init__(self, app_ast: A.SiddhiApp, manager=None,
                 partition_mesh=None):
        self.ast = app_ast
        self.manager = manager
        self.name = app_ast.name or f"app_{id(self):x}"
        self.junctions: dict[str, StreamJunction] = {}
        self.schemas: dict[str, StreamSchema] = {}
        self.input_handlers: dict[str, InputHandler] = {}
        self.queries: dict[str, QueryRuntime] = {}
        # planner's per-join-side kernel picks: {"<q>.left": {"kernel":
        # "grid"|"probe", "reason": ...}} — statistics()['compile']
        self._join_kernels: dict[str, dict] = {}
        # plan-optimizer decision record (plan/optimizer.py build_plan,
        # set at start()): rides ExplainReport.decisions['optimizer']
        # so every transformation flip moves plan_hash
        self._opt_decisions: Optional[dict] = None
        # per-stream bounded-lateness reorder buffers keyed by stream id
        # (resilience/ordering.py, wired by the planner from @watermark
        # annotations); non-empty => watermark mode: the virtual clock
        # advances on watermark progress and never regresses
        self._reorder: dict = {}
        self.tables: dict[str, TableRuntime] = {}
        self.record_tables: dict = {}  # tid -> RecordTableRuntime (@Store)
        self.named_windows: dict[str, QueryRuntime] = {}
        self.triggers: dict[str, TriggerRuntime] = {}
        self.sources: list = []
        self.sinks: list = []
        self.aggregations: dict = {}  # id -> AggregationRuntime
        self.partitions: dict = {}  # name -> PartitionBlockRuntime
        # jax.sharding.Mesh: when set, partition blocks shard their key-slot
        # axis over the mesh's first axis via the PARTITION_STATE_RULES
        # regex table (parallel/partition.py + parallel/sharding.py);
        # `mesh` is the forward-facing name, partition_mesh the original
        self.partition_mesh = partition_mesh
        self.mesh = partition_mesh
        self.running = False
        self._playback = False
        self._playback_time: Optional[int] = None
        # set once columnar ingest (send_arrays) is used: timer dues
        # subsumed by event steps are then skipped (_schedule) — the
        # row path keeps per-boundary timer fidelity
        self._columnar = False
        # @app:playback(idle.time, increment): auto-advance parameters
        self._playback_idle_ms: Optional[int] = None
        self._playback_increment_ms: Optional[int] = None
        self._last_ingest_wall = 0.0
        self._idle_thread: Optional[threading.Thread] = None
        self._local_store = None  # fallback store when manager is None
        self._local_error_store = None  # ditto for the error store
        # per-stream junction/sink error counters (core/stats.py) — always
        # on; junctions get a reference through junction_for
        from .stats import StreamErrorStats
        self.error_stats = StreamErrorStats()
        self._cron_armed = False
        self._due_pending: list = []
        self._due_lock = threading.Lock()
        self.stats_level = 0      # OFF; see core/stats.py
        # DETAIL latency probe sampling stride (QueryRuntime._lat_sample)
        self.lat_sample_every = max(
            1, int(os.environ.get("SIDDHI_TPU_LAT_EVERY", "16") or 16))
        self.debugger = None
        # app-wide quiesce barrier (= ThreadBarrier): ingest and wall-clock
        # timer dispatch hold it; snapshot/restore take it exclusively
        self.barrier = threading.RLock()
        self.scheduler = Scheduler(playback=False, barrier=self.barrier)
        self.scheduler.resolve_hook = self._resolve_dues
        # observability (siddhi_tpu/obs/): metrics registry + chunk-span
        # tracer. The registry fills at COLLECTION time (scrape /
        # reporter tick / statistics() call) via _collect_observability;
        # the per-chunk path records only into the existing host-side
        # trackers, so BASIC-level metrics stay sync-free.
        from ..obs.costmodel import CostProfiler
        from ..obs.metrics import MetricsRegistry
        from ..obs.tracing import ChunkTracer
        self.metrics = MetricsRegistry()
        self.tracer = ChunkTracer()
        # sampled per-step cost attribution (obs/costmodel.py): default
        # OFF — every dispatch site pays one attribute check; enabled
        # via cost_start() / SIDDHI_TPU_COST_PROFILE=1 it syncs every
        # SIDDHI_TPU_COST_EVERY'th chunk per step to measure wall ms
        self.cost = CostProfiler(self)
        # SLO engine (obs/slo.py): None unless @app:slo is configured —
        # the disabled path costs one attribute check per dispatch site
        # (the CostProfiler contract); the Planner wires it below
        self.slo = None
        self.metrics.register_collector(
            lambda: self._collect_observability()[0])
        self._checkpoint_supervisor = None  # wired by CheckpointSupervisor
        self._stats_reporter_conf = None    # (reporter, interval_ms, path)
        self._reporter = None
        self._skip_start_warmup = False     # set for async-warm deploys
        Planner(self).plan()
        # AOT compile service (core/compile.py): warmup() lowers and
        # compiles every step program in parallel; start() triggers it
        # for the buckets configured via SIDDHI_TPU_WARM_BUCKETS
        from .compile import CompileService
        self.compile_service = CompileService(self)
        # flight-recorder identity: every artifact this app's recorder
        # dumps carries {app, pool, plan_hash} so a PAGE dump is
        # attributable to a plan change (obs/slo.py; the hash is
        # computed lazily at dump time — dumps are rare, plans can
        # change on live graph edits)
        if self.slo is not None and self.slo.recorder is not None:
            self.slo.recorder.identity_fn = lambda: {
                "app": self.name, "plan_hash": self.plan_hash()}
        self.scheduler.playback = self._playback
        # start-state absent deadlines are based at app start, not the
        # first event (AbsentStreamPreStateProcessor.partitionCreated);
        # under playback the base is the first observed virtual tick
        self._unarmed_patterns = [
            q for q in self.queries.values()
            if getattr(getattr(q, "engine", None),
                       "needs_start_arm", False)]
        # record which queries compiled device reads against a @Cache
        # table — losing cache completeness must be surfaced to them
        # (the device join cannot fall back to the store mid-jit)
        for q in self.queries.values():
            for t in getattr(q, "table_deps", ()):
                rt = self.record_tables.get(t)
                if rt is not None and hasattr(rt, "compiled_readers"):
                    rt.compiled_readers.add(q.name)

    # -- time ------------------------------------------------------------
    def current_time(self) -> int:
        # the playback clock is ingest-thread-owned; background writers
        # (idle advance, restore) serialize against each other via the
        # barrier, and a clock read one write stale is by-design here
        if self._playback and self._playback_time is not None:  # lint: disable=racy-attribute-read (ingest-thread-owned clock)
            return self._playback_time  # lint: disable=racy-attribute-read (ingest-thread-owned clock)
        return int(time.time() * 1000)

    def on_ingest(self, stream_id: str, events: list[Event]) -> None:
        if events:
            self.on_ingest_ts(events[-1].timestamp, events[0].timestamp)

    def defer_due(self, q, due_arr) -> None:
        """Queue a device-resident timer due for async host resolution
        (no blocking device->host read per step)."""
        due_arr.copy_to_host_async()
        with self._due_lock:
            self._due_pending.append((q, due_arr))

    def _resolve_dues(self) -> None:
        if not self._due_pending:
            return
        with self._due_lock:
            pending, self._due_pending = self._due_pending, []
        # the copy_to_host_async above staged these; collect them in one
        # transfer instead of a sync per queued due
        dues = jax.device_get([arr for _, arr in pending])
        for (q, _), due in zip(pending, dues):
            q._schedule(int(due))

    def on_ingest_ts(self, last_ts: int,
                     first_ts: Optional[int] = None) -> None:
        """Advance the playback clock (and due timers) to an ingested
        timestamp — shared by the row and columnar ingest paths."""
        self._resolve_dues()
        if self._playback:
            if self._unarmed_patterns:
                base = first_ts if first_ts is not None else last_ts
                pats, self._unarmed_patterns = self._unarmed_patterns, []
                for q in pats:
                    q.arm_start_deadlines(base)
            if not self._cron_armed:
                # playback cron schedules anchor at the first event time
                self._cron_armed = True
                base = (first_ts if first_ts is not None else last_ts) - 1
                self._arm_cron(base)
            if self._reorder and self._playback_time is not None:  # lint: disable=racy-attribute-read (ingest-thread-owned clock)
                # watermark mode: PROCESS-policy late events and replay
                # re-injection carry old timestamps — the watermark
                # clock never regresses
                last_ts = max(last_ts, self._playback_time)  # lint: disable=racy-attribute-read (ingest-thread-owned clock)
            self._playback_time = last_ts
            self._last_ingest_wall = time.monotonic()
            self.scheduler.advance_to(last_ts)

    def on_ingest_span(self, first_ts: int, last_ts: int) -> None:
        """Columnar-chunk variant: fire only timers due STRICTLY BEFORE
        the chunk's span, then advance the clock to its end. In-span
        window expiry happens inside the chunk's own jitted step (exact
        per-row expiry points), so pre-firing intermediate timers would
        only add dispatches; the caller runs a catch-up
        advance_to(last_ts) after publishing."""
        self._resolve_dues()
        if self._playback:
            if self._unarmed_patterns:
                pats, self._unarmed_patterns = self._unarmed_patterns, []
                for q in pats:
                    q.arm_start_deadlines(first_ts)
            if not self._cron_armed:
                self._cron_armed = True
                self._arm_cron(first_ts - 1)
            self.scheduler.advance_to(first_ts - 1)
            if self._reorder and self._playback_time is not None:  # lint: disable=racy-attribute-read (ingest-thread-owned clock)
                last_ts = max(last_ts, self._playback_time)  # lint: disable=racy-attribute-read (ingest-thread-owned clock)
            self._playback_time = last_ts
            self._last_ingest_wall = time.monotonic()

    def on_event_time(self, target_ms: int) -> None:
        """Watermark-driven clock (resilience/ordering.py): advance the
        virtual clock and due timers monotonically to the global
        watermark, so windows/joins/patterns fire on watermark progress
        instead of raw arrival — and never backwards. Idempotent for
        targets at or behind the current clock."""
        self._resolve_dues()
        if not self._playback:
            return
        cur = self._playback_time
        if cur is not None and target_ms <= cur:
            return
        if self._unarmed_patterns:
            pats, self._unarmed_patterns = self._unarmed_patterns, []
            for q in pats:
                q.arm_start_deadlines(target_ms)
        if not self._cron_armed:
            self._cron_armed = True
            self._arm_cron(target_ms - 1)
        self._playback_time = target_ms
        self._last_ingest_wall = time.monotonic()
        self.scheduler.advance_to(target_ms)

    def global_watermark(self) -> Optional[int]:
        """Min watermark across watermarked streams (streams that have
        not observed any event yet do not hold the watermark back — the
        idle-source caveat, docs/resilience.md). None before any
        watermarked stream has seen traffic."""
        wms = [b.watermark for b in self._reorder.values()
               if b.watermark is not None]
        return min(wms) if wms else None

    def flush_watermarks(self, final: bool = False) -> None:
        """Release reorder-buffered events (resilience/ordering.py): up
        to each stream's current watermark, or EVERYTHING when
        ``final`` (the shutdown path). A final flush also advances the
        clock to the observed event-time frontier so trailing window
        boundaries and pattern deadlines fire exactly where an
        unbuffered run's would."""
        if not self._reorder:
            return
        with self.barrier:
            for buf in self._reorder.values():
                buf.flush(final=final)
            if final:
                fronts = [b.max_ts for b in self._reorder.values()
                          if b.max_ts is not None]
                if fronts:
                    self.on_event_time(max(fronts))
            else:
                wm = self.global_watermark()
                if wm is not None:
                    self.on_event_time(wm)

    def _arm_cron(self, base_ms: int) -> None:
        for q in self.queries.values():
            if getattr(q, "_host_sched", None):
                q.arm_host_timers(base_ms)
        for t in self.triggers.values():
            t.arm(base_ms)

    # -- on-demand (store) queries (OnDemandQueryParser.java:87) ----------
    def query(self, q):
        """Execute an on-demand query string/AST against tables / named
        windows; returns result rows (SELECT) or the affected-row count
        (writes)."""
        from .ondemand import OnDemandExecutor
        with self.barrier:
            return OnDemandExecutor(self).execute(q)

    # -- chain fusion (docs/performance.md) -------------------------------
    def _fusible_next(self, q) -> Optional["QueryRuntime"]:
        return self._fusible_next_info(q)[0]

    def _fusible_next_info(self, q) -> tuple:
        """``(next, reason)``: the single downstream QueryRuntime the
        hop q -> next can fuse into (reason None), or (None, slug)
        naming WHY the hop broke the chain — the machine-readable
        fusion evidence explain surfaces (obs/explain.py). Fusible
        means: q is a plain single-stream query whose ONLY output is
        `insert into` a synchronous junction with exactly one
        subscriber that is itself a plain QueryRuntime taking device
        batches — no row-level consumers (query callbacks, rate
        limiters, device taps) on q, no @Async/@OnError machinery on
        the intermediate stream, and no sort-heavy capacity cap
        downstream (capped queries re-split batches on the host, which
        a fused trace cannot do)."""
        if type(q) is not QueryRuntime:
            return None, "not-plain-query"
        if q.rate_limiter is not None:
            return None, "rate-limiter"
        if q.callback_handler.callbacks:
            return None, "row-callbacks"
        if q.batch_callbacks:
            return None, "device-taps"
        if len(q.output_handlers) != 1:
            return None, "fan-out" if len(q.output_handlers) > 1 \
                else "no-insert-into-output"
        h = q.output_handlers[0]
        if type(h) is not InsertIntoStreamHandler:
            return None, "non-stream-output"
        j = h.junction
        if j.async_conf is not None:
            return None, "async-junction"
        if j.fault_junction is not None or j.on_error_action != "LOG":
            return None, "on-error-machinery"
        if len(j.receivers) != 1:
            return None, "multi-subscriber" if len(j.receivers) > 1 \
                else "no-subscriber"
        r = j.receivers[0]
        if type(r) is not QueryRuntime:
            return None, "downstream-not-plain-query"
        if r is q:
            return None, "self-loop"
        if r.max_step_capacity is not None:
            return None, "downstream-capacity-capped"
        return r, None

    def _fusion_enabled(self) -> bool:
        """Whether segment derivation runs at all (explain evidence):
        off under SIDDHI_TPU_FUSE=0 or an attached debugger."""
        return os.environ.get("SIDDHI_TPU_FUSE", "1") != "0" \
            and self.debugger is None

    def _build_fused_chains(self) -> None:
        """Derive the executable plan over the junction graph
        (plan/optimizer.py build_plan): maximal fusible linear segments
        compile into FusedChains on their head queries, fan-out
        junctions into FanoutGroups, with CSE prefix sharing, filter
        pushdown and cost-driven selection per the SIDDHI_TPU_OPT*
        switches. Cleared and re-derived whenever the graph changes
        (new subscriber, callback, rate limiter, debugger).
        SIDDHI_TPU_FUSE=0 keeps per-query dispatch; attaching a
        debugger does too (row breakpoints need per-query delivery)."""
        for q in self.queries.values():
            if type(q) is QueryRuntime:
                q._fused_chain = None
                q._fanout_group = None
                q.preferred_ingest_cap = None
        for j in self.junctions.values():
            j.fanout = None
        self._opt_decisions = None
        if not self._fusion_enabled():
            self._opt_decisions = {"enabled": False,
                                   "cause": "fusion-disabled"}
            return
        from ..plan.optimizer import build_plan
        build_plan(self)

    def _rebuild_fused_chains(self) -> None:
        if self.running:
            with self.barrier:  # quiesce in-flight fused dispatch
                self._build_fused_chains()

    # -- wiring ----------------------------------------------------------
    def junction_for(self, stream_id: str,
                     schema: Optional[StreamSchema] = None) -> StreamJunction:
        j = self.junctions.get(stream_id)
        if j is None:
            if schema is None:
                raise CompileError(f"undefined stream '{stream_id}'")
            j = StreamJunction(stream_id, schema)
            j.app = self
            j.error_stats = self.error_stats
            self.junctions[stream_id] = j
            self.schemas[stream_id] = schema
        elif schema is not None and schema.types != j.schema.types:
            raise CompileError(
                f"output schema {list(schema.types)} does not match existing "
                f"definition of stream '{stream_id}' {list(j.schema.types)} "
                "(reference rejects mismatched insert-into at deploy time)")
        return j

    # -- public API (= SiddhiAppRuntime) ---------------------------------
    def get_input_handler(self, stream_id: str) -> InputHandler:
        h = self.input_handlers.get(stream_id)
        if h is None:
            raise KeyError(f"no input handler for stream '{stream_id}' "
                           f"(defined streams: {list(self.input_handlers)})")
        return h

    def add_callback(self, target, callback) -> None:
        """StreamCallback on a stream id, or QueryCallback on a query name."""
        if isinstance(callback, QueryCallback):
            q = self.queries.get(target)
            if q is None:
                raise KeyError(f"no query named '{target}'")
            q.callback_handler.callbacks.append(callback)
            self._rebuild_fused_chains()
        else:
            j = self.junctions.get(target)
            if j is None:
                raise KeyError(f"no stream '{target}' to subscribe to")
            j.subscribe(StreamCallbackReceiver(callback))
            self._rebuild_fused_chains()

    def set_statistics_level(self, level) -> None:
        """OFF/BASIC/DETAIL at runtime
        (SiddhiAppRuntimeImpl.setStatisticsLevel:859)."""
        from .stats import parse_level
        self.stats_level = parse_level(level) \
            if isinstance(level, str) else int(level)

    def statistics(self) -> dict:
        """Per-query throughput/latency/memory/overflow report
        (util/statistics trackers) — a VIEW over the metrics registry's
        collection walk (obs/metrics.py): ``GET /metrics``, periodic
        reporters and bench dumps read the same numbers as dotted
        gauges (docs/observability.md)."""
        return self._collect_observability()[1]

    def _collect_observability(self) -> tuple[dict, dict]:
        """ONE walk over the runtime, shared by every observability
        surface. Returns ``(flat, report)``: ``flat`` is the registry
        snapshot of dotted metrics (``siddhi.<app>.query.<q>.emitted``,
        ``siddhi.<app>.stream.<sid>.throughput``, ...) and ``report``
        is the nested ``statistics()`` view. Device reads are batched
        into single pytree transfers under the app barrier; this never
        runs on the per-chunk path."""
        from .stats import pytree_nbytes
        p = f"siddhi.{self.name}"
        flat: dict = {}
        report: dict = {}
        # barrier: with donated state buffers a concurrent step would
        # invalidate the arrays mid-read; the barrier quiesces ingest and
        # timer dispatch for the walk (same guard snapshot() uses)
        with self.barrier:
            states_host = jax.device_get(
                {n: q.states for n, q in self.queries.items()
                 if hasattr(q, "states")})
            stats_host = {n: dict(q.stats())
                          for n, q in self.queries.items()
                          if hasattr(q, "stats")}
        for n, q in self.queries.items():
            entry = stats_host.get(n, {})
            qs = getattr(q, "_qstats", None)
            if qs is not None:
                eps = qs.throughput.events_per_sec()
                if eps is not None:
                    entry["throughput_eps"] = round(eps, 1)
                lat = qs.latency.summary()
                if lat is not None:
                    entry["latency"] = lat
            if n in states_host:
                entry["state_bytes"] = pytree_nbytes(states_host[n])
            report[n] = entry
            base = f"{p}.query.{n}"
            for key, metric in (("emitted", "emitted"),
                                ("overflow", "overflow"),
                                ("probe_rounds", "probe_rounds"),
                                ("probe_steps", "probe_steps"),
                                ("throughput_eps", "throughput"),
                                ("state_bytes", "state.bytes")):
                v = entry.get(key)
                if isinstance(v, (int, float)):
                    flat[f"{base}.{metric}"] = v
            for k, v in (entry.get("latency") or {}).items():
                flat[f"{base}.latency.{k}"] = v
        # per-stream gauges: ingest throughput (host boundary, free),
        # @Async queue depth/backpressure, junction error counters
        for sid, j in self.junctions.items():
            sbase = f"{p}.stream.{sid}"
            tput = getattr(j, "throughput", None)
            if tput is not None:
                flat[f"{sbase}.events"] = tput.count
                eps = tput.events_per_sec()
                if eps is not None:
                    flat[f"{sbase}.throughput"] = round(eps, 1)
            if j.async_conf is not None and j._queue is not None:
                flat[f"{sbase}.async.depth"] = j._queue.qsize()
                flat[f"{sbase}.async.pending"] = j._pending
                flat[f"{sbase}.async.capacity"] = j.async_conf[0]
            # event-time robustness gauges (resilience/ordering.py):
            # watermark position/lag, reorder-buffer depth and the
            # late/dropped/duplicate/forced counters
            buf = self._reorder.get(sid)
            if buf is not None:
                wm = buf.watermark
                flat[f"{sbase}.watermark"] = -1 if wm is None else int(wm)
                flat[f"{sbase}.watermark.lag_ms"] = buf.lag_ms
                flat[f"{sbase}.reorder.depth"] = buf.depth
                for k, v in buf.counters.items():
                    flat[f"{sbase}.reorder.{k}"] = v
            # ingest-path zero-copy + pipeline-overlap counters
            # (core/stream.py InputHandler.ingest_stats): coercion
            # copies and encode/device overlap are regressions/wins the
            # bench gates on (tools/bench_diff.py)
            h = self.input_handlers.get(sid)
            ing = h.ingest_stats() if h is not None else None
            if ing:
                report.setdefault("ingest", {})[sid] = ing
                for k, v in ing.items():
                    if isinstance(v, (int, float)):
                        flat[f"{sbase}.ingest.{k}"] = v
        if self._reorder:
            report["reorder"] = {
                sid: {"watermark": b.watermark, "lag_ms": b.lag_ms,
                      "depth": b.depth, **b.counters}
                for sid, b in self._reorder.items()}
        errors = self.error_stats.snapshot()
        if errors:
            report["stream_errors"] = errors
            for sid, c in errors.items():
                flat[f"{p}.stream.{sid}.errors"] = c
        for tid, rt in self.record_tables.items():
            if hasattr(rt, "cache_complete"):
                report[f"store:{tid}"] = {
                    "cache_complete": bool(rt.cache_complete),
                    "completeness_losses": rt.completeness_losses,
                    "compiled_readers": sorted(rt.compiled_readers),
                }
                flat[f"{p}.store.{tid}.cache_complete"] = \
                    int(bool(rt.cache_complete))
                flat[f"{p}.store.{tid}.completeness_losses"] = \
                    rt.completeness_losses
        # error-store backlog (resilience): events awaiting replay
        try:
            flat[f"{p}.errorstore.backlog"] = \
                self._error_store().size(self.name)
        except Exception:  # noqa: BLE001 — store backends may be remote
            pass
        # checkpoint freshness (resilience/supervisor.py), when supervised
        sup = self._checkpoint_supervisor
        if sup is not None:
            flat[f"{p}.checkpoint.count"] = sup.checkpoints
            flat[f"{p}.checkpoint.failures"] = sup.failures
            if sup.last_checkpoint_wall is not None:
                flat[f"{p}.checkpoint.age_ms"] = round(
                    (time.time() - sup.last_checkpoint_wall) * 1000.0, 1)
        # scheduler timer backlog / lag
        flat[f"{p}.scheduler.pending"] = self.scheduler.pending()
        flat[f"{p}.scheduler.lag_ms"] = \
            self.scheduler.lag_ms(self.current_time())
        # mesh placement (multi-chip partition execution): which devices
        # carry how many key slots, as a `device=` labeled gauge family
        if self.mesh is not None and self.partitions:
            axis = self.mesh.axis_names[0]
            n = int(self.mesh.shape[axis])
            slots_per_dev = [0] * n
            mesh_rep = {"axis": axis, "n_devices": n, "partitions": {}}
            for name, blk in self.partitions.items():
                mesh_rep["partitions"][name] = {
                    "slots": blk.K, "slots_per_device": blk.K // n}
                for d in range(n):
                    slots_per_dev[d] += blk.K // n
            for d in range(n):
                self.metrics.labeled_gauge(
                    f"{p}.mesh.slots_placed", {"device": str(d)},
                    dotted=f"{p}.mesh.device.{d}.slots_placed",
                    help="partition key slots placed on one mesh "
                    "device").set(slots_per_dev[d])
            report["mesh"] = mesh_rep
            flat[f"{p}.mesh.n_devices"] = n
        # AOT compile telemetry (once a warmup ran OR the static program
        # auditor stored its summary): program count, compile wall ms,
        # persistent-cache hits/misses; DETAIL level adds the per-step
        # timing list (view only)
        comp: dict = {}
        if self.compile_service.warmups or self.compile_service.audit:
            comp = self.compile_service.summary(
                detail=self.stats_level >= 2)
            for k in ("warmups", "programs", "compile_ms", "cache_hits",
                      "cache_misses"):
                flat[f"{p}.compile.{k}"] = comp[k]
        if self._join_kernels:
            # the planner's grid-vs-probe picks per join side, with the
            # reason (env override / equi heuristic / cost-table
            # evidence) — docs/performance.md "join kernels"
            comp = {**comp, "join_kernels": {
                k: dict(v) for k, v in sorted(self._join_kernels.items())}}
        if comp:
            report["compile"] = comp
        # sampled per-step cost attribution (obs/costmodel.py): the
        # step_ms histograms live natively in the registry; the ranked
        # rollup rides the statistics() view like 'compile'. Also shown
        # when the optimizer's staleness guard dropped centers at load
        # (stale evidence in costs.json — counted, never silent)
        if self.cost.samples or (self.cost.stale_centers or 0) > 0:
            report["cost"] = self.cost.report()
        # SLO view (obs/slo.py): ingest->emit latency scopes, burn-rate
        # states and saturation signals; labeled p99/burn/state gauge
        # families land in the registry for /metrics
        if self.slo is not None:
            sat = self._slo_saturation()
            report["slo"] = self.slo.evaluate(saturation=sat)
            self.slo.publish(self.metrics, f"{p}.slo")
            for k, v in sat.items():
                if isinstance(v, (int, float)):
                    flat[f"{p}.saturation.{k}"] = v
        flat[f"{p}.app.running"] = int(self.running)
        flat[f"{p}.app.ready"] = int(self.ready)
        return flat, report

    def explain(self, live: bool = True) -> dict:
        """The full plan-explain document (obs/explain.py,
        docs/observability.md "Explain"): junction dataflow graph,
        every planner decision with its machine-readable reason
        (fusion segments + break causes, join kernel picks + evidence,
        window compaction variant, watermark/late-policy config, SLO
        objectives, mesh placement per state leaf), the AOT program
        inventory, and live edge annotations. ``plan_hash`` is stable
        across deploys of the same plan; assembly compiles nothing and
        reads nothing off-device (tested in tests/test_explain.py)."""
        from ..obs.explain import ExplainReport
        return ExplainReport.from_runtime(self, live=live).as_dict()

    def plan_hash(self) -> str:
        """Stable content hash of the compiled plan (decisions + graph
        only, never live stats). Stamped into flight-recorder artifacts
        so a PAGE dump is attributable to a plan change."""
        from ..obs.explain import (compute_plan_hash, runtime_decisions,
                                   runtime_graph)
        return compute_plan_hash(runtime_graph(self),
                                 runtime_decisions(self))

    def slo_report(self) -> Optional[dict]:
        """The SLO/burn-rate view on its own (``GET /siddhi/slo``);
        None when no ``@app:slo`` objective is configured."""
        if self.slo is None:
            return None
        return self.slo.evaluate(saturation=self._slo_saturation())

    def _slo_saturation(self) -> dict:
        """Host-side pressure signals for the SLO report and flight
        recorder: timer/scheduler lag, @Async queue depth, watermark
        lag (event-time apps), error-store backlog. No device reads."""
        sat: dict = {
            "scheduler_pending": self.scheduler.pending(),
            "scheduler_lag_ms": self.scheduler.lag_ms(
                self.current_time()),
        }
        depths = [j._queue.qsize() for j in self.junctions.values()
                  if j.async_conf is not None and j._queue is not None]
        if depths:
            sat["async_depth_max"] = max(depths)
        if self._reorder:
            sat["watermark_lag_ms_max"] = max(
                b.lag_ms for b in self._reorder.values())
            sat["reorder_depth_total"] = sum(
                b.depth for b in self._reorder.values())
        try:
            sat["errorstore_backlog"] = self._error_store().size(self.name)
        except Exception:  # noqa: BLE001 — store backends may be remote
            pass
        return sat

    def debug(self):
        """Attach a step debugger (SiddhiAppRuntimeImpl.debug():657)."""
        from .debugger import SiddhiDebugger
        self.debugger = SiddhiDebugger(self)
        # row breakpoints need per-query delivery — drop fused segments
        self._build_fused_chains()
        return self.debugger

    # -- AOT compile (core/compile.py, docs/compile_cache.md) -------------
    def warmup(self, buckets=None, samples=None, workers=None) -> dict:
        """Ahead-of-time compile every step program for the given ingest
        buckets (default: SIDDHI_TPU_WARM_BUCKETS; with no buckets
        configured only the cap-16 timer-batch shapes compile).
        Lowering/compiling runs concurrently on a thread pool — XLA
        releases the GIL — so wall time is the slowest single compile,
        not the sum. `samples` maps stream ids to (ts, cols) arrays so
        packed steps compile for the encoding real traffic settles on.
        Returns telemetry: programs, compile_ms, cache_hits/misses,
        per-step timings (also surfaced via statistics()['compile'])."""
        if not self.running:
            # segments must exist before enumeration so the warmed steps
            # are the ones traffic will dispatch
            self._build_fused_chains()
        return self.compile_service.warmup(buckets=buckets,
                                           samples=samples,
                                           workers=workers)

    def audit_programs(self, buckets=None, samples=None, **kw) -> dict:
        """Static audit of every step program warmup() would compile
        (analysis/programs.py): abstract-trace each spec and verify
        donation aliasing, host-callback freedom, dtype stability and
        the @app:cap(program.mb=) budget — ZERO executions, zero device
        work, zero new compiles. The summary is stored on the compile
        service and rides statistics()['compile']['audit'] and the
        explain report's programs section (never hashed)."""
        from ..analysis.programs import audit_runtime
        return audit_runtime(self, buckets=buckets,
                             samples=samples, **kw).summary()

    def warmup_async(self, buckets=None, samples=None, workers=None):
        """warmup() on a daemon thread; readiness (`self.ready`,
        service ``GET /ready``) flips False before this returns and
        True when the compiles land — deploys return immediately while
        the load balancer holds traffic (docs/observability.md)."""
        if not self.running:
            self._build_fused_chains()
        return self.compile_service.warmup_async(
            buckets=buckets, samples=samples, workers=workers)

    @property
    def ready(self) -> bool:
        """Load-balancer readiness: running AND no AOT warmup in
        flight (core/compile.py)."""
        return self.running and self.compile_service.ready

    def _maybe_aot_warmup(self) -> None:
        if self._skip_start_warmup:
            # an async warmup was (or will be) launched by the deployer
            # (core/service.py): don't also compile inline
            return
        from .compile import warm_buckets_from_env
        buckets = warm_buckets_from_env()
        if buckets:
            self.compile_service.warmup(buckets=buckets)

    # -- tracing / profiling (siddhi_tpu/obs/, docs/observability.md) -----
    def trace_start(self, capacity: Optional[int] = None) -> None:
        """Start recording chunk spans (ingest -> junction -> step ->
        wait/d2h/decode -> sink) into the tracer ring buffer, which
        keeps the newest ``capacity`` spans (default 8,192)."""
        self.tracer.start(capacity=capacity)

    def trace_stop(self) -> int:
        """Stop recording; returns the spans the ring evicted."""
        return self.tracer.stop()

    def trace_export(self, path: str) -> str:
        """Write buffered chunk spans as Chrome ``trace_event`` JSON
        (chrome://tracing / Perfetto loadable), timestamp-ordered and —
        when the cost profiler has samples — annotated with measured
        per-step device time (``cost_ms_per_event`` etc. in span args);
        the ring's capacity and evicted-span count go in ``otherData``.
        Returns ``path``."""
        return self.tracer.export(
            path, annotations=self.cost.trace_annotations())

    # -- cost profiling (obs/costmodel.py, docs/observability.md) ---------
    def cost_start(self, every: Optional[int] = None) -> None:
        """Enable sampled per-step cost attribution: every Nth chunk per
        step is timed synchronously (``block_until_ready`` on the
        sampled branch only — the same serialization caveat as DETAIL
        latency probes). Zero jit-option changes: compile-cache keys are
        identical with profiling on or off."""
        self.cost.start(every=every)

    def cost_stop(self) -> None:
        self.cost.stop()

    def cost_report(self) -> dict:
        """Ranked per-step cost table (ms/event, share of total,
        queue-depth trend -> bottleneck verdict) from the sampled
        timings accumulated since ``cost_start()``."""
        return self.cost.report()

    def cost_save(self, path: Optional[str] = None) -> str:
        """Persist the measured cost table into
        ``<state dir>/costs.json`` (merge-on-write; the DAG
        optimizer's planned input). Centers from renamed/deleted plan
        units are pruned on save (``_cost_center_valid``) so the
        optimizer never feeds on stale evidence. Returns the path
        written."""
        return self.cost.save(path)

    def _cost_center_valid(self, key: str) -> bool:
        """Whether a persisted cost-center key names a unit of THIS
        app's current plan — the save-time pruning predicate and the
        ``load_costs_for`` staleness guard (obs/costmodel.py). Keys may
        carry a per-capacity ``@<cap>`` suffix; unknown kinds are kept
        (forward compatibility — costs are advisory)."""
        base = key.split("@", 1)[0]
        kind, _, name = base.partition("/")
        if kind == "query":
            return name in self.queries or any(
                wq.name == name for wq in self.named_windows.values())
        if kind == "chain":
            parts = name.split("+")
            return len(parts) > 1 and all(p in self.queries
                                          for p in parts)
        if kind == "fanout":
            return name in self.junctions
        if kind in ("join", "pattern"):
            return name.split(".", 1)[0] in self.queries
        if kind == "partition":
            return name in self.partitions
        return True

    def profile(self, path: str):
        """Context manager capturing a device profile of the enclosed
        block via ``jax.profiler.start_trace/stop_trace``::

            with rt.profile('/tmp/prof'):
                handler.send_arrays(ts, cols)

        Spans recorded meanwhile (``trace_start()``) land in the
        trace's host plane, on the device ops' clock."""
        from ..obs.profiler import profile
        return profile(path)

    def _start_reporter(self) -> None:
        """Launch the @app:statistics periodic reporter, if configured."""
        if self._stats_reporter_conf is None or self.stats_level <= 0 \
                or self._reporter is not None:
            return
        from ..obs.reporters import build_reporter
        name, interval_ms, path = self._stats_reporter_conf
        self._reporter = build_reporter(self, name, interval_ms,
                                        path=path).start()

    def _stop_reporter(self) -> None:
        if self._reporter is not None:
            self._reporter.stop()
            self._reporter = None

    def start(self) -> None:
        self.running = True
        self._build_fused_chains()
        # compile every step program for the configured ingest buckets
        # BEFORE sources connect: traffic arriving the moment the app
        # deploys hits ready executables instead of a serial lazy
        # compile queue (north star: start in seconds, not minutes)
        self._maybe_aot_warmup()
        self._start_reporter()
        self.scheduler.start()
        self._start_record_tables()
        for s in self.sources:
            s.connect_with_retry()
        for s in self.sinks:
            s.connect()
        if not self._playback:
            self._arm_cron(self.current_time())
            if self._unarmed_patterns:
                now = self.current_time()
                pats, self._unarmed_patterns = self._unarmed_patterns, []
                for q in pats:
                    q.arm_start_deadlines(now)
        elif self._playback_idle_ms is not None:
            # @app:playback(idle.time, increment): a wall-clock watcher
            # advances the virtual clock by `increment` whenever no
            # events arrive for `idle.time`
            # (EventTimeBasedMillisTimestampGenerator's scheduled task)
            def idle_advance():
                idle_s = self._playback_idle_ms / 1000.0
                while self.running:
                    time.sleep(idle_s)
                    if not self.running:
                        return
                    with self.barrier:
                        if self._playback_time is None:
                            continue
                        idle_for = time.monotonic() - self._last_ingest_wall
                        if idle_for < idle_s:
                            continue
                        nxt = self._playback_time + \
                            self._playback_increment_ms
                        self._playback_time = nxt
                        self.scheduler.advance_to(nxt)

            self._idle_thread = threading.Thread(
                target=idle_advance, name="playback-idle", daemon=True)
            self._idle_thread.start()

    def _start_record_tables(self) -> None:
        from .store import CacheTableRuntime
        for rt in self.record_tables.values():
            rt.store.connect()
            if isinstance(rt, CacheTableRuntime):
                rt.now_fn = self.current_time  # event-time in playback
                now = self.current_time()
                rt.preload(now)
                interval = getattr(rt, "purge_interval_ms", None)
                if interval:
                    def fire(due, rt=rt, interval=interval):
                        if not self.running:
                            return
                        rt.purge_expired(due)
                        self.scheduler.notify_at(due + interval, fire)
                    self.scheduler.notify_at(now + interval, fire)

    def start_without_sources(self) -> None:
        """Lifecycle split (SiddhiAppRuntimeImpl.startWithoutSources
        :495): run queries but keep sources disconnected."""
        self.running = True
        self._build_fused_chains()
        self._maybe_aot_warmup()
        self._start_reporter()
        self.scheduler.start()
        self._start_record_tables()
        if not self._playback:
            self._arm_cron(self.current_time())

    def start_sources(self) -> None:
        for s in self.sources:
            s.connect_with_retry()
        for s in self.sinks:
            s.connect()

    # -- checkpoint / restore (SiddhiAppRuntimeImpl.java:677-755) ---------
    def _persistence_store(self):
        from .persistence import InMemoryPersistenceStore
        if self.manager is not None:
            if self.manager.persistence_store is None:
                self.manager.persistence_store = InMemoryPersistenceStore()
            return self.manager.persistence_store
        if self._local_store is None:
            self._local_store = InMemoryPersistenceStore()
        return self._local_store

    def _error_store(self):
        """The app's error store (resilience/errorstore.py): the
        manager's shared store when one is registered (survives app
        restarts, like the persistence store), else a runtime-local
        in-memory fallback."""
        from ..resilience.errorstore import InMemoryErrorStore
        if self.manager is not None:
            if getattr(self.manager, "error_store", None) is None:
                self.manager.error_store = InMemoryErrorStore()
            return self.manager.error_store
        if self._local_error_store is None:
            self._local_error_store = InMemoryErrorStore()
        return self._local_error_store

    def replay_error_store(self) -> int:
        """Re-inject the error-store backlog through the normal
        junctions (at-least-once); returns events replayed."""
        from ..resilience.errorstore import replay
        return replay(self, self._error_store())

    def snapshot(self) -> bytes:
        """Full state snapshot as bytes (SnapshotService.fullSnapshot).
        Every query/table/partition state is a pytree of device arrays —
        one device_get each, then pickle (see core/persistence.py).
        The app barrier quiesces ingest + timers for the whole walk so
        chained queries are captured consistently (the reference's
        ThreadBarrier in SnapshotService.java:99-100)."""
        from .persistence import dump_strings, serialize
        with self.barrier:
            return self._snapshot_locked(dump_strings, serialize)

    def _snapshot_locked(self, dump_strings, serialize) -> bytes:
        payload = {
            "app": self.name,
            "playback_time": self._playback_time,
            "queries": {n: q.snapshot_state()
                        for n, q in self.queries.items()
                        if hasattr(q, "snapshot_state")},
            "windows": {n: w.snapshot_state()
                        for n, w in self.named_windows.items()},
            "tables": jax.device_get(
                {tid: t.state for tid, t in self.tables.items()}),
            "partitions": {n: b.snapshot_state()
                           for n, b in self.partitions.items()},
            "aggregations": {n: a.snapshot_state()
                             for n, a in self.aggregations.items()},
            # reorder-buffered events are accepted-but-unreleased state:
            # a crash between checkpoint and flush must not lose them
            "reorder": {sid: b.snapshot_state()
                        for sid, b in self._reorder.items()},
            "strings": dump_strings(),
        }
        return serialize(payload)

    def restore(self, data: bytes) -> None:
        """Restore a snapshot() payload bit-exact and re-arm timers."""
        from .persistence import deserialize, load_strings
        with self.barrier:
            self._restore_locked(deserialize(data), load_strings)

    def _restore_locked(self, payload, load_strings) -> None:
        load_strings(payload["strings"])
        self._playback_time = payload["playback_time"]
        for n, snap in payload["queries"].items():
            q = self.queries.get(n)
            if q is None or not hasattr(q, "restore_state"):
                continue
            q.restore_state(snap)
        for n, snap in payload.get("windows", {}).items():
            w = self.named_windows.get(n)
            if w is not None:
                w.restore_state(snap)
        for tid, tstate in payload["tables"].items():
            if tid in self.tables:
                # fresh buffers: table states feed donated step args
                self.tables[tid].state = _fresh_device(tstate)
        for n, snap in payload["partitions"].items():
            if n in self.partitions:
                self.partitions[n].restore_state(snap)
        for n, snap in payload.get("aggregations", {}).items():
            if n in self.aggregations:
                self.aggregations[n].restore_state(snap)
        for sid, snap in payload.get("reorder", {}).items():
            buf = self._reorder.get(sid)
            if buf is not None:
                buf.restore_state(snap)
        for q in self.queries.values():
            if hasattr(q, "reschedule"):
                q.reschedule()
        for w in self.named_windows.values():
            w.reschedule()
        for b in self.partitions.values():
            b.reschedule()

    def persist(self) -> str:
        """Snapshot to the manager's persistence store; returns the
        revision id. Sources pause around the capture
        (SiddhiAppRuntimeImpl.persist:677-693)."""
        from .persistence import new_revision
        store = self._persistence_store()
        rev = new_revision(self.name)
        for s in self.sources:
            s.pause()
        try:
            # drain @Async buffers so queued events land in the snapshot
            for j in self.junctions.values():
                if j.async_conf is not None:
                    j.flush_async()
            store.save(self.name, rev, self.snapshot())
        finally:
            for s in self.sources:
                s.resume()
        return rev

    def restore_revision(self, revision: str) -> None:
        store = self._persistence_store()
        data = store.load(self.name, revision)
        if data is None:
            raise KeyError(f"no revision '{revision}' for app "
                           f"'{self.name}'")
        self.restore(data)

    def restore_last_revision(self) -> Optional[str]:
        store = self._persistence_store()
        rev = store.get_last_revision(self.name)
        if rev is None:
            return None
        self.restore_revision(rev)
        return rev

    def clear_all_revisions(self) -> None:
        self._persistence_store().clear_all_revisions(self.name)

    # camelCase aliases mirroring the reference API surface
    restoreRevision = restore_revision
    restoreLastRevision = restore_last_revision
    clearAllRevisions = clear_all_revisions

    def shutdown(self) -> None:
        self.running = False  # reject new sends before draining
        self._stop_reporter()
        if self._reorder:
            # release everything still held in reorder buffers so an
            # accepted event is never silently lost at shutdown
            try:
                self.flush_watermarks(final=True)
            except Exception:  # noqa: BLE001 — shutdown must finish
                logging.getLogger("siddhi_tpu.runtime").exception(
                    "app '%s': reorder-buffer final flush failed",
                    self.name)
        flush_errors = []
        for j in self.junctions.values():
            if j.async_conf is not None:
                try:
                    j.flush_async()
                except Exception as e:  # noqa: BLE001 — shutdown must finish
                    flush_errors.append((j.stream_id, e))
                finally:
                    j.stop_async()
        if flush_errors:
            logging.getLogger("siddhi_tpu.runtime").error(
                "app '%s': async streams did not drain cleanly on "
                "shutdown: %s", self.name, flush_errors)
        for h in self.input_handlers.values():
            h.close()  # join ingest pipeline workers
        self._resolve_dues()
        for s in self.sources:
            s.disconnect()
        for s in self.sinks:
            s.disconnect()
        self.scheduler.shutdown()
        for rt in self.record_tables.values():
            rt.store.disconnect()
        for q in self.queries.values():
            if hasattr(q, "_sched_due") and isinstance(
                    getattr(q, "_sched_due"), (int, type(None))):
                q._sched_due = None
        for blk in self.partitions.values():
            for qn in blk._sched_due:
                blk._sched_due[qn] = None


class Planner:
    """AST -> runtime graph (= SiddhiAppParser + QueryParser +
    SingleInputStreamParser + SelectorParser + OutputParser)."""

    DEFAULT_TIME_CAP = 4096

    def __init__(self, app: SiddhiAppRuntime):
        self.app = app
        self.ast = app.ast
        from .extension import build_function_table
        self.functions = build_function_table(app)
        mgr = app.manager
        self.extensions = {k.lower(): v for k, v in
                           (getattr(mgr, "extensions", {}) or {}).items()} \
            if mgr is not None else {}

    DEFAULT_TABLE_CAP = 8192

    def plan(self) -> None:
        app, ast = self.app, self.ast
        # 1. defined streams -> junctions + input handlers
        for sid, sd in ast.stream_definitions.items():
            schema = StreamSchema(sid, tuple(
                Attribute(a.name, a.type) for a in sd.attributes))
            j = app.junction_for(sid, schema)
            app.input_handlers[sid] = InputHandler(sid, j, app)
            asy = A.find_annotation(sd.annotations, "Async")
            if asy is not None:
                # @Async(buffer.size, workers, batch.size.max)
                # (StreamJunction.java:101-131; batch.size.max is the
                # reference's latency/throughput dial, ours too)
                def async_int(key, default):
                    v = asy.element(key)
                    if v is None:
                        return default
                    try:
                        n = int(v)
                    except ValueError:
                        n = 0
                    if n <= 0:
                        raise CompileError(
                            f"stream '{sid}': @Async {key}='{v}' must be "
                            "a positive integer")
                    return n

                buf = async_int("buffer.size", 1024)
                batch_max = async_int("batch.size.max", buf)
                j.enable_async(app, buf, batch_max)
            oe = A.find_annotation(sd.annotations, "OnError")
            if oe is not None:
                action = (oe.element("action") or "LOG").upper()
                if action not in ("LOG", "STREAM", "STORE"):
                    # the static validator rejects this at parse time;
                    # planner backstop for validate=False / built ASTs
                    raise CompileError(
                        f"stream '{sid}': unknown @OnError action "
                        f"'{action}' (expected LOG, STREAM or STORE)")
                j.on_error_action = action
                if action == "STREAM":
                    # shadow fault stream !sid: original attrs + _error
                    fschema = StreamSchema("!" + sid, schema.attributes + (
                        Attribute("_error", AttrType.STRING),))
                    j.fault_junction = app.junction_for("!" + sid, fschema)
        # 1a2. event-time watermarks + bounded-lateness reorder buffers
        # (resilience/ordering.py; docs/resilience.md). Validated at
        # parse time by the `watermark-config` plan rule — this is the
        # planner backstop for validate=False / hand-built ASTs.
        self.plan_watermarks()
        # 1b. defined tables (@PrimaryKey -> upsert semantics);
        # @Store tables become host-side record tables, with an optional
        # device-resident @Cache front registered under the table id so
        # joins/filters run on-device against the cache (core/store.py)
        from .store import CacheTableRuntime, build_record_table
        for tid, td in ast.table_definitions.items():
            schema = StreamSchema(tid, tuple(
                Attribute(a.name, a.type) for a in td.attributes))
            sa = A.find_annotation(td.annotations, "Store")
            if sa is not None:
                rt = build_record_table(tid, schema, sa, self.extensions)
                app.record_tables[tid] = rt
                if isinstance(rt, CacheTableRuntime):
                    app.tables[tid] = rt.cache
                continue
            pk = []
            pka = A.find_annotation(td.annotations, "PrimaryKey")
            if pka is not None:
                for nm in pka.positional or list(pka.elements.values()):
                    pk.append(schema.index_of(nm.strip("'\"")))
            idxs = []
            ia = A.find_annotation(td.annotations, "Index")
            if ia is not None:
                for nm in ia.positional or list(ia.elements.values()):
                    idxs.append(schema.index_of(nm.strip("'\"")))
            cap_a = A.find_annotation(td.annotations, "cap")
            tcap = int(cap_a.element()) if cap_a is not None \
                else self.DEFAULT_TABLE_CAP
            app.tables[tid] = TableRuntime(tid, schema,
                                           capacity=tcap,
                                           pk_indices=pk,
                                           index_indices=idxs)
        # 1c. named windows: one shared window instance per definition
        # (window/Window.java:65); queries consume from its junction,
        # insert-into feeds the instance
        for wid, wd in ast.window_definitions.items():
            schema = StreamSchema(wid, tuple(
                Attribute(a.name, a.type) for a in wd.attributes))
            fo = wd.window
            if fo is None:
                raise CompileError(f"window '{wid}' needs a window type")
            h = A.WindowHandler(namespace=fo.namespace, name=fo.name,
                                parameters=fo.parameters)
            op = self.make_window(h, schema, expired_enabled=True)
            wq = QueryRuntime(f"__window__{wid}", [op], schema, app)
            out_j = app.junction_for(wid, schema)
            wq.output_handlers.append(
                WindowPublishHandler(out_j, wd.output_event_type))
            app.named_windows[wid] = wq
        # 1c2. incremental aggregations (AggregationParser.java:93)
        from .aggregation import AggregationRuntime
        for aid, ad in ast.aggregation_definitions.items():
            sid = ad.input.stream_id
            schema = app.schemas.get(sid)
            if schema is None:
                raise CompileError(
                    f"aggregation '{aid}': undefined stream '{sid}'")
            ar = AggregationRuntime(app, ad, schema)
            app.junctions[sid].subscribe(ar)
            app.aggregations[aid] = ar
        # 1d. triggers: scheduled event publishers into stream <tid>
        for tid, td in ast.trigger_definitions.items():
            schema = StreamSchema(tid, (
                Attribute("triggered_time", AttrType.LONG),))
            tj = app.junction_for(tid, schema)
            app.triggers[tid] = TriggerRuntime(app, td, tj)
        # @app:statistics(level, reporter, interval, file)
        # (SiddhiAppParser.java:116-141: level + Dropwizard reporter
        # config; statics validated at parse time by plan_rules
        # `statistics-reporter`/`statistics-interval`, planner backstop
        # here for validate=False / hand-built ASTs)
        sa = A.find_annotation(ast.annotations, "statistics")
        if sa is not None:
            from .stats import parse_level
            from ..obs.reporters import DEFAULT_INTERVAL_MS, REPORTER_NAMES
            config_keys = ("reporter", "interval", "file")
            lvl = sa.element("level")
            if lvl is None and sa.positional:
                lvl = sa.positional[0]
            if lvl is None and len(sa.elements) == 1 and not any(
                    k.lower() in config_keys for k in sa.elements):
                lvl = next(iter(sa.elements.values()))
            app.stats_level = parse_level(lvl or "BASIC")
            rep = sa.element("reporter")
            interval = sa.element("interval")
            if rep is not None:
                rname = rep.strip("'\"").lower()
                if rname not in REPORTER_NAMES:
                    raise CompileError(
                        f"unknown @app:statistics reporter '{rep}' "
                        f"(expected one of {', '.join(REPORTER_NAMES)})")
            elif interval is not None:
                rname = "console"  # interval alone: reference default
            else:
                rname = None
            if rname is not None:
                ms = _time_str_ms(interval, "@app:statistics interval") \
                    if interval is not None else DEFAULT_INTERVAL_MS
                app._stats_reporter_conf = (rname, ms, sa.element("file"))
        # @app:slo(p99=..., target=..., window=..., fast=..., every=...)
        # -> ingest->emit latency objective + burn-rate states
        # (obs/slo.py; validated at parse time by the `slo-config` plan
        # rule — planner backstop for validate=False / hand-built ASTs)
        slo_ann = A.find_annotation(ast.annotations, "slo")
        if slo_ann is not None:
            from ..obs.slo import (FlightRecorder, SLOEngine,
                                   config_from_annotation)
            try:
                objective = config_from_annotation(slo_ann)
            except ValueError as e:
                raise CompileError(str(e))
            app.slo = SLOEngine(
                app.name, objective=objective,
                recorder=FlightRecorder(app.name),
                context_fn=app._slo_saturation)
        # playback mode (+ optional idle-advance: SiddhiAppParser.java
        # :171-210 wires EventTimeBasedMillisTimestampGenerator so the
        # virtual clock advances by `increment` whenever sources stay
        # idle for `idle.time` of wall time)
        pb = A.find_annotation(ast.annotations, "playback")
        if pb is not None:
            app._playback = True
            idle = pb.element("idle.time")
            inc = pb.element("increment")
            if (idle is None) != (inc is None):
                raise CompileError(
                    "@app:playback needs BOTH idle.time and increment "
                    "(or neither)")
            if idle is not None:
                app._playback_idle_ms = _time_str_ms(
                    idle, "@app:playback idle.time")
                app._playback_increment_ms = _time_str_ms(
                    inc, "@app:playback increment")
        # 2. queries in order; inferred output streams defined as we go
        qcount = 0
        pcount = 0
        for el in ast.execution_elements:
            if isinstance(el, A.Query):
                qcount += 1
                self.plan_query(el, default_name=f"query_{qcount}")
            elif isinstance(el, A.Partition):
                pcount += 1
                qcount = self.plan_partition(el, qcount, pcount)
        # 3. sources/sinks from @source/@sink annotations
        from .io import build_io
        build_io(app, self.extensions)

    def plan_watermarks(self) -> None:
        """``@app:watermark(...)`` / per-stream ``@watermark(...)`` ->
        ReorderBuffer per configured stream, wired onto the ingest path
        (InputHandler). App-level without ``stream=`` applies to every
        defined stream; ``stream='S'`` targets one; a definition-level
        annotation overrides both. Any watermark config switches the
        app to event-time processing (playback semantics): the virtual
        clock advances on watermark progress."""
        from ..resilience.ordering import (ReorderBuffer,
                                           config_from_annotation)
        app, ast = self.app, self.ast
        wm_default = None
        wm_streams: dict = {}
        for ann in ast.annotations:
            if ann.name.lower() != "watermark":
                continue
            try:
                conf = config_from_annotation(ann)
            except ValueError as e:
                raise CompileError(f"@app:watermark: {e}")
            tgt = ann.element("stream")
            if tgt is None:
                wm_default = conf
            else:
                tgt = str(tgt).strip().strip("'\"")
                if tgt not in ast.stream_definitions:
                    raise CompileError(
                        f"@app:watermark targets undefined stream "
                        f"'{tgt}'")
                wm_streams[tgt] = conf
        for sid, sd in ast.stream_definitions.items():
            wa = A.find_annotation(sd.annotations, "watermark")
            if wa is not None:
                try:
                    conf = config_from_annotation(wa)
                except ValueError as e:
                    raise CompileError(f"stream '{sid}': @watermark: {e}")
            else:
                conf = wm_streams.get(sid) or wm_default
            if conf is None:
                continue
            buf = ReorderBuffer(sid, app.schemas[sid], conf)
            buf.handler = app.input_handlers[sid]
            if conf.policy == "STREAM":
                lt = conf.late_stream
                lsd = ast.stream_definitions.get(lt)
                if lsd is None:
                    raise CompileError(
                        f"stream '{sid}': @watermark late.stream '{lt}' "
                        "is not a defined stream")
                if [a.type for a in lsd.attributes] != \
                        [a.type for a in sd.attributes]:
                    raise CompileError(
                        f"stream '{sid}': @watermark late.stream '{lt}' "
                        "schema does not match the source stream "
                        "(late events re-publish with the original "
                        "attributes)")
                lschema = StreamSchema(lt, tuple(
                    Attribute(a.name, a.type) for a in lsd.attributes))
                buf.late_junction = app.junction_for(lt, lschema)
            app._reorder[sid] = buf
        if app._reorder:
            # watermarks define event time: windows/joins/patterns fire
            # on watermark progress (implies @app:playback semantics)
            app._playback = True

    # -- partitions ------------------------------------------------------
    DEFAULT_PARTITION_SLOTS = 32

    def plan_partition(self, part: A.Partition, qcount: int,
                       pcount: int) -> int:
        """`partition with (...) begin ... end` -> PartitionBlockRuntime
        (reference: PartitionParser.java:46 + PartitionRuntimeImpl.java:75).
        See siddhi_tpu/parallel/partition.py for the slot-vmap design."""
        from ..parallel.partition import (BlockQueryPlan, BlockStreamReceiver,
                                          PartitionBlockRuntime)
        app = self.app
        # 1. key specs per partitioned stream (shared instance space)
        key_specs: dict = {}
        label_slots: dict[str, int] = {}
        has_value = False
        for pt in part.partition_types:
            schema = app.schemas.get(pt.stream_id)
            if schema is None:
                raise CompileError(
                    f"partition: undefined stream '{pt.stream_id}'")
            scope = SingleStreamScope(schema)
            if isinstance(pt, A.ValuePartitionType):
                has_value = True
                key_specs[pt.stream_id] = (
                    "value", compile_expression(pt.expression, scope))
            elif isinstance(pt, A.RangePartitionType):
                conds = []
                for expr, label in pt.ranges:
                    ce = compile_expression(expr, scope)
                    if ce.type is not AttrType.BOOL:
                        raise CompileError(
                            "partition range condition must be BOOL")
                    if label not in label_slots:
                        label_slots[label] = len(label_slots)
                    conds.append((ce, label_slots[label]))
                key_specs[pt.stream_id] = ("range", conds)
            else:
                raise CompileError(
                    f"unknown partition type {type(pt).__name__}")
        # slot capacity: ranges are exactly the label count; value keys get
        # a bounded first-seen table (@partition slots='N' overrides)
        n_slots = len(label_slots) if (label_slots and not has_value) \
            else max(self.DEFAULT_PARTITION_SLOTS, len(label_slots))
        sa = A.find_annotation(part.annotations, "slots")
        if sa is not None:
            n_slots = int(sa.element())
        if len(label_slots) > n_slots:
            raise CompileError(
                f"partition has {len(label_slots)} range labels but only "
                f"{n_slots} slots; @slots must be >= the label count")
        mesh = getattr(app, "partition_mesh", None)
        if mesh is not None:
            n = mesh.shape[mesh.axis_names[0]]
            n_slots = ((n_slots + n - 1) // n) * n

        # 2. queries, in order; inner-stream (#S) schemas register as their
        # producers are planned
        inner_schemas: dict[str, StreamSchema] = {}
        plans: list[BlockQueryPlan] = []
        block_names: set[str] = set()
        for q in part.queries:
            qcount += 1
            name = q.name or f"query_{qcount}"
            if name in app.queries or name in block_names:
                raise CompileError(f"duplicate query name '{name}'")
            block_names.add(name)
            if isinstance(q.input, A.StateInputStream):
                plan = self._plan_partition_pattern(q, name, key_specs)
                if plan.inner_target:
                    prev = inner_schemas.get(plan.target)
                    if prev is not None and \
                            prev.types != plan.out_schema.types:
                        raise CompileError(
                            f"inner stream '{plan.target}' schema "
                            "mismatch between producers")
                    inner_schemas[plan.target] = plan.out_schema
                plans.append(plan)
                continue
            if not isinstance(q.input, A.SingleInputStream):
                raise CompileError(
                    f"query '{name}': only single-stream and pattern/"
                    "sequence queries are supported inside partitions "
                    "(joins in partitions are a later stage)")
            sin = q.input
            if sin.is_inner:
                input_id = "#" + sin.stream_id
                schema = inner_schemas.get(input_id)
                if schema is None:
                    raise CompileError(
                        f"query '{name}': inner stream '{input_id}' has no "
                        "producer earlier in this partition")
            else:
                input_id = sin.stream_id
                schema = app.schemas.get(sin.stream_id)
                if schema is None:
                    raise CompileError(f"query '{name}': undefined stream "
                                       f"'{sin.stream_id}'")
                if sin.stream_id not in key_specs:
                    raise CompileError(
                        f"query '{name}': stream '{sin.stream_id}' is not "
                        "partitioned (no 'partition with' clause names it)")
            out = q.output
            if not isinstance(out, (A.InsertIntoStream, A.ReturnStream)):
                raise CompileError(
                    f"query '{name}': table output inside partitions not "
                    "yet supported")
            out_type = out.output_event_type
            inner_target = bool(getattr(out, "is_inner", False))
            raw_target = getattr(out, "target", None) or name
            target = ("#" + raw_target) if inner_target else raw_target
            scope = SingleStreamScope(schema, aliases=(sin.alias,))
            operators = self.build_single_chain(
                q, name, schema, sin, scope, target,
                current_on=out_type in ("current", "all"),
                expired_on=out_type in ("expired", "all"),
                allow_tables=False)
            if any(getattr(op, "host_schedule", None) for op in operators):
                raise CompileError(
                    f"query '{name}': cron windows inside partitions are "
                    "not supported")
            plan = BlockQueryPlan(name, input_id, schema, operators,
                                  target, inner_target, out_type)
            if inner_target:
                prev = inner_schemas.get(target)
                if prev is not None and prev.types != plan.out_schema.types:
                    raise CompileError(
                        f"inner stream '{target}' schema mismatch between "
                        "producers")
                inner_schemas[target] = plan.out_schema
            plans.append(plan)

        block = PartitionBlockRuntime(
            app, f"partition_{pcount}", n_slots, key_specs, plans,
            mesh=mesh)
        app.partitions[block.name] = block

        # 3. wiring: subscribe consumed outer streams; wire outer outputs
        consumed = sorted(
            {sid for p in plans
             for sid in getattr(p, "input_ids", {p.input_id})
             if not sid.startswith("#")})
        for sid in consumed:
            app.junctions[sid].subscribe(BlockStreamReceiver(block, sid))
        for q, plan in zip(part.queries, plans):
            port = block.ports[plan.name]
            app.queries[plan.name] = port
            if not plan.inner_target and isinstance(
                    q.output, A.InsertIntoStream):
                tj = app.junction_for(plan.target, plan.out_schema)
                if plan.target not in app.input_handlers:
                    app.input_handlers[plan.target] = InputHandler(
                        plan.target, tj, app)
                port.output_handlers.append(
                    InsertIntoStreamHandler(tj, plan.out_type))
            self.attach_rate_limiter(port, q, plan.name)
        return qcount

    # -- windows ---------------------------------------------------------
    def window_class(self, h: A.WindowHandler):
        name = h.name if h.namespace is None else f"{h.namespace}:{h.name}"
        cls = WINDOW_CLASSES.get(name.lower())
        if cls is None:
            ext = self.extensions.get(name.lower())
            if isinstance(ext, type) and issubclass(ext, WindowOp):
                return ext
            raise CompileError(f"window '{name}' not yet supported")
        return cls

    def make_window(self, h: A.WindowHandler, schema: StreamSchema,
                    expired_enabled: bool,
                    cap_override: Optional[int] = None) -> WindowOp:
        name = h.name if h.namespace is None else f"{h.namespace}:{h.name}"
        params = []
        for p in h.parameters:
            if isinstance(p, A.Constant):
                params.append(p.value)
            elif isinstance(p, A.Variable):
                params.append(p)  # attribute params (externalTime, sort...)
            else:
                raise CompileError(
                    f"window '{name}' parameters must be constants or "
                    "attributes")
        key = name.lower()
        time_cap = cap_override or self.DEFAULT_TIME_CAP

        def attr_idx(p, role):
            if not isinstance(p, A.Variable):
                raise CompileError(
                    f"window '{name}' {role} must be a stream attribute")
            try:
                return schema.index_of(p.attribute)
            except (KeyError, ValueError):
                raise CompileError(
                    f"window '{name}': '{p.attribute}' is not an "
                    "attribute of the input stream")

        def const_of(p, role):
            if isinstance(p, A.Variable):
                raise CompileError(
                    f"window '{name}' {role} must be a constant")
            return p
        if key == "time":
            _expect(params, 1, name)
            return TimeWindowOp(schema, _ms(params[0], name),
                                cap=time_cap,
                                expired_enabled=expired_enabled)
        if key == "length":
            _expect(params, 1, name)
            return LengthWindowOp(schema, int(const_of(params[0], 'length')),
                                  expired_enabled=expired_enabled)
        if key == "lengthbatch":
            if len(params) not in (1, 2):
                raise CompileError(f"{name} takes 1-2 parameters")
            stream_cur = bool(const_of(params[1], 'mode')) \
                if len(params) == 2 else False
            return LengthBatchWindowOp(schema,
                                       int(const_of(params[0], 'length')),
                                       expired_enabled=expired_enabled,
                                       stream_current=stream_cur)
        if key in ("hopping", "hoping"):
            _expect(params, 2, name)
            return HoppingWindowOp(schema, _ms(params[0], name),
                                   _ms(params[1], name),
                                   cap=time_cap,
                                   expired_enabled=expired_enabled)
        if key == "timebatch":
            if len(params) not in (1, 2, 3):
                raise CompileError(f"{name} takes 1-3 parameters")
            start = None
            stream_cur = False
            if len(params) >= 2:
                p1 = const_of(params[1], 'start time / mode')
                if isinstance(p1, bool):
                    stream_cur = p1
                    if len(params) == 3:
                        raise CompileError(
                            f"{name}: bool mode must be the last "
                            "parameter")
                elif isinstance(p1, int):
                    start = int(p1)
                else:
                    raise CompileError(
                        f"window '{name}' start time must be int/long")
            if len(params) == 3:
                mode = const_of(params[2], 'mode')
                if not isinstance(mode, bool):
                    raise CompileError(
                        f"window '{name}' stream.current.event mode must "
                        "be a bool constant")
                stream_cur = mode
            return TimeBatchWindowOp(schema, _ms(params[0], name),
                                     start_time=start,
                                     cap=time_cap,
                                     expired_enabled=expired_enabled,
                                     stream_current=stream_cur)
        if key == "externaltimebatch":
            if len(params) not in (2, 3, 4, 5):
                raise CompileError(f"{name} takes 2-5 parameters")
            ti = attr_idx(params[0], "timestamp parameter")
            if schema.attributes[ti].type is not AttrType.LONG:
                raise CompileError(
                    f"window '{name}' timestamp attribute must be LONG")
            start = None
            start_attr = None
            if len(params) >= 3:
                if isinstance(params[2], A.Variable):
                    start_attr = attr_idx(params[2], "start time")
                else:
                    start = int(const_of(params[2], 'start time'))
            timeout = _ms(params[3], name) if len(params) >= 4 else None
            replace = bool(const_of(params[4], 'replace flag')) \
                if len(params) == 5 else False
            return ExternalTimeBatchWindowOp(
                schema, ti, _ms(params[1], name), start_time=start,
                cap=time_cap, expired_enabled=expired_enabled,
                start_attr=start_attr, timeout_ms=timeout,
                replace_ts=replace)
        if key == "externaltime":
            _expect(params, 2, name)
            ti = attr_idx(params[0], "timestamp parameter")
            if schema.attributes[ti].type is not AttrType.LONG:
                raise CompileError(
                    f"window '{name}' timestamp attribute must be LONG")
            return ExternalTimeWindowOp(schema, ti, _ms(params[1], name),
                                        cap=time_cap,
                                        expired_enabled=expired_enabled)
        if key == "timelength":
            _expect(params, 2, name)
            return TimeLengthWindowOp(schema, _ms(params[0], name),
                                      int(const_of(params[1], 'length')),
                                      expired_enabled=expired_enabled)
        if key == "delay":
            _expect(params, 1, name)
            return DelayWindowOp(schema, _ms(params[0], name),
                                 cap=time_cap,
                                 expired_enabled=expired_enabled)
        if key == "batch":
            if len(params) > 1:
                raise CompileError(f"{name} takes 0-1 parameters")
            length = int(const_of(params[0], 'length')) if params else 0
            return BatchWindowOp(schema, length, cap=time_cap,
                                 expired_enabled=expired_enabled)
        if key == "cron":
            _expect(params, 1, name)
            if not isinstance(params[0], str):
                raise CompileError(
                    f"window '{name}' takes a cron expression string")
            from ..utils.cron import CronError
            try:
                return CronWindowOp(schema, params[0],
                                    cap=time_cap,
                                    expired_enabled=expired_enabled)
            except CronError as e:
                raise CompileError(f"window '{name}': {e}")
        if key == "session":
            if len(params) not in (1, 2):
                raise CompileError(
                    f"{name} takes 1-2 parameters (allowedLatency is not "
                    "supported)")
            ki = None
            if len(params) == 2:
                ki = attr_idx(params[1], "session key")
                if schema.attributes[ki].type is not AttrType.STRING:
                    raise CompileError(
                        f"window '{name}' session key must be STRING")
            return SessionWindowOp(schema, _ms(params[0], name), ki,
                                   expired_enabled=expired_enabled)
        if key == "sort":
            if not params:
                raise CompileError(f"{name} needs a length parameter")
            keys = []
            i = 1
            while i < len(params):
                ki = attr_idx(params[i], "sort attribute")
                order = 1
                if i + 1 < len(params) and isinstance(params[i + 1], str):
                    d = params[i + 1].lower()
                    if d not in ("asc", "desc"):
                        raise CompileError(
                            f"{name}: order must be 'asc' or 'desc'")
                    order = 1 if d == "asc" else -1
                    i += 1
                keys.append((ki, order))
                i += 1
            if not keys:
                raise CompileError(f"{name} needs at least one sort "
                                   "attribute")
            return SortWindowOp(schema,
                                int(const_of(params[0], 'length')), keys,
                                expired_enabled=expired_enabled)
        if key == "frequent":
            if not params:
                raise CompileError(f"{name} needs a count parameter")
            idxs = [attr_idx(p, "key attribute") for p in params[1:]]
            return FrequentWindowOp(schema,
                                    int(const_of(params[0], 'count')),
                                    idxs,
                                    expired_enabled=expired_enabled)
        if key == "lossyfrequent":
            if not params:
                raise CompileError(f"{name} needs a support parameter")
            error = None
            rest = params[1:]
            if rest and not isinstance(rest[0], A.Variable):
                error = float(const_of(rest[0], 'error'))
                rest = rest[1:]
            idxs = [attr_idx(p, "key attribute") for p in rest]
            return LossyFrequentWindowOp(schema,
                                         float(const_of(params[0],
                                                        'support')), error,
                                         idxs,
                                         expired_enabled=expired_enabled)
        ext = self.extensions.get(key)
        if isinstance(ext, type) and issubclass(ext, WindowOp):
            return ext(schema, params, expired_enabled=expired_enabled)
        raise CompileError(f"window '{name}' not yet supported")

    def plan_query(self, q: A.Query, default_name: str) -> None:
        app = self.app
        name = q.name or default_name
        if isinstance(q.input, A.StateInputStream):
            return self.plan_pattern_query(q, name)
        if isinstance(q.input, A.JoinInputStream):
            return self.plan_join_query(q, name)
        if not isinstance(q.input, A.SingleInputStream):
            raise CompileError(
                f"query '{name}': only single-stream, join, and pattern "
                "queries supported in this stage")
        sin = q.input
        if getattr(sin, "is_fault", False):
            sin = dataclasses.replace(sin, stream_id="!" + sin.stream_id,
                                      is_fault=False)
        schema = app.schemas.get(sin.stream_id)
        if schema is None:
            raise CompileError(f"query '{name}': undefined stream "
                               f"'{sin.stream_id}'")
        scope = SingleStreamScope(schema, aliases=(sin.alias,))

        out = q.output
        if isinstance(out, (A.InsertIntoStream, A.ReturnStream,
                            A.DeleteStream, A.UpdateStream,
                            A.UpdateOrInsertStream)):
            out_type = out.output_event_type
        else:
            raise CompileError(f"query '{name}': unsupported output "
                               f"{type(out).__name__}")
        target = getattr(out, "target", None) or name
        current_on = out_type in ("current", "all")
        expired_on = out_type in ("expired", "all")
        operators = self.build_single_chain(
            q, name, schema, sin, scope, target, current_on, expired_on,
            allow_tables=True)
        self.append_table_output(operators, out, name)

        if name in app.queries:
            raise CompileError(f"duplicate query name '{name}'")
        qr = QueryRuntime(name, operators, schema, app)
        app.junctions[sin.stream_id].subscribe(qr)
        app.queries[name] = qr
        self.wire_stream_output(qr, out, out_type)
        self.attach_rate_limiter(qr, q, name)

    def attach_rate_limiter(self, qr, q: A.Query, name: str) -> None:
        """`output <all|first|last> every N events / T` and
        `output snapshot every T` -> a host-side limiter gating the row
        path (reference: OutputParser rate selection +
        query/output/ratelimit/)."""
        rate = q.output_rate
        if rate is None:
            return
        from .ratelimit import build_rate_limiter
        key_fn = None
        needs_key = (isinstance(rate, (A.EventOutputRate,
                                       A.TimeOutputRate))
                     and rate.type in ("first", "last")) or \
            isinstance(rate, A.SnapshotOutputRate)
        gb = q.selector.group_by or []
        if needs_key and gb:
            idxs = []
            for g in gb:
                col = None
                for i, oa in enumerate(q.selector.attributes):
                    e = oa.expression
                    if isinstance(e, A.Variable) and \
                            e.attribute == g.attribute:
                        col = i
                        break
                if col is None:
                    try:
                        col = qr.out_schema.index_of(g.attribute)
                    except KeyError:
                        raise CompileError(
                            f"query '{name}': group-by rate limiting "
                            f"needs '{g.attribute}' in the projection")
                idxs.append(col)

            def key_fn(row, _idxs=tuple(idxs)):
                return tuple(row[2][i] for i in _idxs)

        qr.set_rate_limiter(build_rate_limiter(rate, key_fn))

    def build_single_chain(self, q: A.Query, name: str,
                           schema: StreamSchema, sin: A.SingleInputStream,
                           scope, target: str, current_on: bool,
                           expired_on: bool,
                           allow_tables: bool = True) -> list:
        """Handler chain + selector for a single-stream query — shared by
        plan_query and partitioned block planning
        (= SingleInputStreamParser.parseInputStream + SelectorParser)."""
        app = self.app
        needs_agg = selector_needs_aggregation(q.selector)
        cap_window, _, _, cap_keys = self._cap_annotation(q)
        operators: list[Operator] = []
        window_op: Optional[WindowOp] = None
        for h in sin.handlers:
            if isinstance(h, A.Filter):
                # filters may appear before AND after the window
                # (SingleInputStreamParser.java:202-243 chains handlers in
                # declaration order; FilterProcessor evaluates its condition
                # on every non-TIMER event kind)
                if expr_mentions_table(h.expression):
                    if not allow_tables:
                        raise CompileError(
                            f"query '{name}': table references inside "
                            "partitions not yet supported")
                    operators.append(TableFilterOp(
                        h.expression, schema, app.tables, scope))
                    continue
                cond = compile_expression(h.expression, scope,
                                          self.functions)
                if cond.type is not AttrType.BOOL:
                    raise CompileError(f"query '{name}': filter must be BOOL")
                fop = FilterOp(cond, schema,
                               tparams=collect_template_params(
                                   h.expression))
                # plan-optimizer evidence (plan/canon.py): canonical
                # signature for CSE prefix sharing, referenced-column
                # set for pushdown legality
                from ..plan.canon import canonical_expr, filter_ref_names
                fop.plan_sig = "filter:" + canonical_expr(h.expression)
                fop.ref_names = filter_ref_names(h.expression)
                operators.append(fop)
            elif isinstance(h, A.WindowHandler):
                if window_op is not None:
                    raise CompileError(
                        f"query '{name}': multiple windows on one stream")
                cls = self.window_class(h)
                # sliding windows must feed EXPIRED events to aggregating
                # selectors (subtract-on-expire); batch windows only emit
                # expired when the output asks for them
                # (outputExpectsExpiredEvents in the reference)
                expired_enabled = expired_on if cls.is_batch \
                    else (expired_on or needs_agg)
                window_op = self.make_window(h, schema, expired_enabled,
                                             cap_override=cap_window)
                operators.append(window_op)
            else:
                from ..ops.streamfn import make_stream_function
                op = make_stream_function(h, schema, scope,
                                          self.functions,
                                          self.extensions, name)
                operators.append(op)
                if op.out_schema.types != schema.types:
                    schema = op.out_schema
                    scope = SingleStreamScope(schema,
                                              aliases=(sin.alias,))

        batch_mode = window_op is not None and window_op.is_batch
        src_window = None if sin.is_inner else \
            app.named_windows.get(sin.stream_id)
        expired_possible = (window_op is not None
                            and window_op.expired_enabled) or \
            src_window is not None

        if needs_agg:
            if collect_template_params(
                    *[oa.expression for oa in q.selector.attributes],
                    q.selector.having):
                # planner backstop; the template-binding plan rule
                # reports this with anchors at parse time
                raise CompileError(
                    f"query '{name}': template params are not supported "
                    "in aggregating selectors")
            operators.append(AggregateOp(
                q.selector, schema, target, scope,
                functions=self.functions,
                batch_mode=batch_mode, expired_possible=expired_possible,
                current_on=current_on, expired_on=expired_on,
                key_capacity=cap_keys or 1024,
                fifo_expiry=(window_op.fifo_expiry
                             if window_op is not None else
                             (src_window.operators[0].fifo_expiry
                              if src_window is not None else True))))
        else:
            pop = ProjectOp(
                q.selector, schema, target, scope,
                functions=self.functions,
                current_on=current_on, expired_on=expired_on)
            # plan-optimizer evidence: projection signature (CSE) and
            # the output names that pass through as identity variables
            # (pushdown legality — a downstream filter may hoist across
            # this projection only for columns it leaves untouched)
            from ..plan.canon import selector_sig
            pop.plan_sig = (f"project:{current_on}:{expired_on}:"
                            + selector_sig(q.selector))
            if q.selector.select_all:
                idn = frozenset(schema.names)
            else:
                idn = frozenset(
                    out_name
                    for i, oa in enumerate(q.selector.attributes)
                    if isinstance(oa.expression, A.Variable)
                    and oa.expression.index is None
                    and oa.expression.function_ref is None
                    and (out_name := output_attribute_name(oa, i))
                    == oa.expression.attribute)
            pop.identity_names = idn
            operators.append(pop)
        return operators

    def _plan_partition_pattern(self, q, name: str, key_specs: dict):
        """A pattern/sequence query inside a partition: the scan-engine
        NFA runs per key slot under the block vmap
        (PartitionRuntimeImpl.java:75 clones state runtimes per key)."""
        import dataclasses
        from ..ops.nfa import (MatchScope, NfaCompiler, NfaEngine,
                               rewrite_last_refs, rewrite_oob_refs)
        from ..parallel.partition import BlockPatternPlan
        app = self.app
        sin = q.input
        out = q.output
        if not isinstance(out, (A.InsertIntoStream, A.ReturnStream)):
            raise CompileError(
                f"query '{name}': table output inside partitions not "
                "yet supported")
        out_type = out.output_event_type
        inner_target = bool(getattr(out, "is_inner", False))
        raw_target = getattr(out, "target", None) or name
        target = ("#" + raw_target) if inner_target else raw_target

        compiler = NfaCompiler(app.schemas, sin.state_type)
        slots, states = compiler.compile(sin.state)
        sel = q.selector
        if sel.attributes:
            sel.attributes = [
                dataclasses.replace(
                    oa, expression=rewrite_oob_refs(
                        rewrite_last_refs(oa.expression, slots), slots))
                for oa in sel.attributes]
        if sel.having is not None:
            sel.having = rewrite_oob_refs(
                rewrite_last_refs(sel.having, slots), slots)
        # per-slot pending tables stay modest: K instances multiply
        engine = NfaEngine(slots, states, sin.state_type, sin.within_ms,
                           capacity=32, out_capacity=64)
        scope = MatchScope(slots, engine.col_index)
        input_ids = {s.stream_id for s in slots}
        for sid in sorted(input_ids):
            if sid not in key_specs:
                raise CompileError(
                    f"query '{name}': pattern stream '{sid}' is not "
                    "partitioned (no 'partition with' clause names it)")
        current_on = out_type in ("current", "all")
        expired_on = out_type in ("expired", "all")
        if selector_needs_aggregation(q.selector):
            sel_ops: list[Operator] = [AggregateOp(
                q.selector, engine.match_schema, target, scope,
                batch_mode=False, expired_possible=False,
                current_on=current_on, expired_on=expired_on)]
        else:
            sel_ops = [ProjectOp(
                q.selector, engine.match_schema, target, scope,
                current_on=current_on, expired_on=expired_on,
                having_in_scope=scope)]
        in_schema = app.schemas[sorted(input_ids)[0]]
        return BlockPatternPlan(name, engine, sel_ops, input_ids,
                                in_schema, target, inner_target, out_type)

    def append_table_output(self, operators: list, out, name: str) -> None:
        """Insert/delete/update/update-or-insert into a table becomes a
        terminal TableOutputOp (reference: OutputParser table callbacks)."""
        from ..ops.selector import OutputScope
        app = self.app
        sel_schema = operators[-1].out_schema
        escope = OutputScope(sel_schema)
        target = getattr(out, "target", None)
        if target in app.record_tables:
            # wired as a StoreOutputHandler: a host IO boundary, so
            # host-shaped (STRING-ordered) rows reach it correctly
            return
        if target in app.tables and \
                getattr(operators[-1], "host_shape", None):
            raise CompileError(
                "order by on a STRING attribute shapes rows at the host "
                "boundary and cannot feed a device table output (tables "
                "insert inside the jitted step)")
        if isinstance(out, A.InsertIntoStream) and out.target in app.tables:
            operators.append(TableOutputOp(
                "insert", app.tables[out.target], None, None, escope,
                sel_schema))
        elif isinstance(out, (A.DeleteStream, A.UpdateStream,
                              A.UpdateOrInsertStream)):
            tr = app.tables.get(out.target)
            if tr is None:
                raise CompileError(
                    f"query '{name}': '{out.target}' is not a defined "
                    "table")
            kind = {"DeleteStream": "delete", "UpdateStream": "update",
                    "UpdateOrInsertStream": "update_or_insert"}[
                type(out).__name__]
            set_clause = getattr(out, "set_clause", None)
            if kind != "delete" and not set_clause:
                # no SET: every table attribute updated from the same-named
                # output attribute (UpdateTableCallback default)
                set_clause = [
                    (A.Variable(attribute=att.name),
                     A.Variable(attribute=att.name))
                    for att in tr.schema.attributes
                    if att.name in sel_schema.names]
            operators.append(TableOutputOp(
                kind, tr, out.on, set_clause, escope, sel_schema))

    def wire_stream_output(self, qr, out, out_type: str) -> None:
        app = self.app
        target = getattr(out, "target", None)
        if target in app.record_tables:
            from .store import StoreOutputHandler
            kind = {"InsertIntoStream": "insert", "DeleteStream": "delete",
                    "UpdateStream": "update",
                    "UpdateOrInsertStream": "update_or_insert"}[
                type(out).__name__]
            set_clause = getattr(out, "set_clause", None)
            if kind in ("update", "update_or_insert") and not set_clause:
                rt = app.record_tables[target]
                set_clause = [
                    (A.Variable(attribute=att.name),
                     A.Variable(attribute=att.name))
                    for att in rt.schema.attributes
                    if att.name in qr.out_schema.names]
            qr.output_handlers.append(StoreOutputHandler(
                app.record_tables[target], kind, getattr(out, "on", None),
                set_clause, qr.out_schema))
            return
        if isinstance(out, A.InsertIntoStream) and \
                out.target in app.named_windows:
            qr.output_handlers.append(
                InsertIntoWindowHandler(app.named_windows[out.target]))
            return
        if isinstance(out, A.InsertIntoStream) and \
                out.target not in app.tables:
            tj = app.junction_for(out.target, qr.out_schema)
            if out.target not in app.input_handlers:
                app.input_handlers[out.target] = InputHandler(out.target, tj,
                                                              app)
            qr.output_handlers.append(
                InsertIntoStreamHandler(tj, out_type))

    # -- join queries ----------------------------------------------------
    @staticmethod
    def _cap_annotation(q: A.Query):
        """`@cap(window.size='N', join.pairs='M')` — bounded-state tuning
        knob (the reference's queues are unbounded; ours are static-shape
        device buffers, so capacity is an explicit per-query dial).
        window.size: rows a time-based window retains; join.pairs: max
        joined pairs emitted per step (overflow is counted, never
        silent); join.candidates: probe-kernel band-candidate expansion
        capacity before residual filtering (default 4x join.pairs);
        group.keys: slots of a single-stream group-by key table
        (default 1024)."""
        ca = A.find_annotation(q.annotations, "cap")
        if ca is None:
            return None, None, None, None

        def to_int(v, key):
            if v is None:
                return None
            try:
                n = int(v)
            except ValueError:
                raise CompileError(
                    f"@cap({key}='{v}'): expected a positive integer")
            if n <= 0:
                raise CompileError(
                    f"@cap({key}='{v}'): expected a positive integer")
            return n

        return (to_int(ca.element("window.size"), "window.size"),
                to_int(ca.element("join.pairs"), "join.pairs"),
                to_int(ca.element("join.candidates"), "join.candidates"),
                to_int(ca.element("group.keys"), "group.keys"))

    def plan_join_query(self, q: A.Query, name: str) -> None:
        app = self.app
        jin: A.JoinInputStream = q.input
        out = q.output
        cap_window, cap_pairs, cap_cands, _ = self._cap_annotation(q)
        if isinstance(out, (A.InsertIntoStream, A.ReturnStream)):
            out_type = out.output_event_type
        else:
            raise CompileError(f"query '{name}': table output not yet "
                               "supported")
        target = out.target if isinstance(out, A.InsertIntoStream) else name
        current_on = out_type in ("current", "all")
        expired_on = out_type in ("expired", "all")
        needs_agg = selector_needs_aggregation(q.selector)

        def side_chain(sin: A.SingleInputStream, side_name: str):
            schema = app.schemas.get(sin.stream_id)
            if schema is None:
                raise CompileError(
                    f"query '{name}': undefined stream '{sin.stream_id}'")
            scope = SingleStreamScope(schema, aliases=(sin.alias,))
            ops: list[Operator] = []
            window = None
            for h in sin.handlers:
                if isinstance(h, A.Filter):
                    cond = compile_expression(h.expression, scope)
                    ops.append(FilterOp(cond, schema))
                elif isinstance(h, A.WindowHandler):
                    if window is not None:
                        raise CompileError(
                            f"query '{name}': multiple windows on one "
                            "join side")
                    cls = self.window_class(h)
                    expired_enabled = expired_on if cls.is_batch \
                        else True  # joins need expired pairs for aggregates
                    window = self.make_window(h, schema, expired_enabled,
                                              cap_override=cap_window)
                    ops.append(window)
                else:
                    raise CompileError(
                        f"query '{name}': stream function in join not "
                        "supported")
            if window is None:
                # default-window insertion (JoinInputStreamParser.java:416)
                window = EmptyWindowOp(schema, expired_enabled=True)
                ops.append(window)
            return schema, ops

        # stream-table joins: a side naming a table contributes its
        # columnar buffer as the findable content and never triggers
        # (JoinInputStreamParser's table branch; the runtime's
        # side_tables machinery reads the live table state per step)
        side_tables = {}

        def table_side(sin: A.SingleInputStream, key: str):
            t = app.tables[sin.stream_id]
            if sin.handlers:
                raise CompileError(
                    f"query '{name}': windows/filters on the table side "
                    "of a join are not supported")
            side_tables[key] = t
            return t.schema, []

        for side_id in (jin.left.stream_id, jin.right.stream_id):
            if side_id in app.record_tables and side_id not in app.tables:
                raise CompileError(
                    f"query '{name}': joining @Store table '{side_id}' "
                    "requires @Cache(...) — the device join step reads "
                    "the cache buffer; an uncached store cannot be "
                    "called from inside the jitted step")
        l_is_table = jin.left.stream_id in app.tables
        r_is_table = jin.right.stream_id in app.tables
        if l_is_table and r_is_table:
            raise CompileError(
                f"query '{name}': joining two tables needs an on-demand "
                "query, not a stream join")
        if (l_is_table or r_is_table) and jin.unidirectional:
            raise CompileError(
                f"query '{name}': 'unidirectional' with a table side is "
                "redundant (tables never trigger) and would silence the "
                "stream side")
        l_schema, l_ops = table_side(jin.left, "L") if l_is_table \
            else side_chain(jin.left, "L")
        r_schema, r_ops = table_side(jin.right, "R") if r_is_table \
            else side_chain(jin.right, "R")
        side_scope = JoinSideScope(l_schema, jin.left.alias,
                                   r_schema, jin.right.alias)
        if q.selector.select_all:
            dup = set(l_schema.names) & set(r_schema.names)
            if dup:
                raise CompileError(
                    f"query '{name}': select * over a join with "
                    f"duplicate attribute(s) {sorted(dup)} — alias the "
                    "outputs (the reference rejects duplicate output "
                    "attributes)")
        jschema = combined_schema(target, l_schema, r_schema)
        crosses = {"L": None, "R": None}
        join_cap = cap_pairs or 1024
        def _win_ms(ops):
            if ops and isinstance(ops[-1], TimeWindowOp):
                return ops[-1].T
            return None

        if jin.unidirectional != "right" and not l_is_table:
            crosses["L"] = JoinCross(True, l_schema, r_schema, jin.on,
                                     side_scope, jin.join_type,
                                     join_cap=join_cap,
                                     opp_window_ms=_win_ms(r_ops),
                                     cand_cap=cap_cands)
        if jin.unidirectional != "left" and not r_is_table:
            crosses["R"] = JoinCross(False, l_schema, r_schema, jin.on,
                                     side_scope, jin.join_type,
                                     join_cap=join_cap,
                                     opp_window_ms=_win_ms(l_ops),
                                     cand_cap=cap_cands)
        # kernel selection (docs/performance.md "join kernels"): banded
        # searchsorted probe for equi joins, [B,W] grid otherwise;
        # SIDDHI_TPU_JOIN_KERNEL overrides, the PR 7 cost table backs
        # the pick with measured evidence when present
        for key, side_name in (("L", "left"), ("R", "right")):
            cross = crosses[key]
            if cross is None:
                continue
            kernel, reason, cause = _pick_join_kernel(app.name, name,
                                                      cross)
            cross.kernel = kernel
            # the cause slug guarantees explain never shows a kernel
            # pick without a machine-readable reason (obs/explain.py)
            app._join_kernels[f"{name}.{side_name}"] = {
                "kernel": kernel, "reason": reason, "cause": cause}

        sel_scope = JoinCombinedScope(side_scope, len(l_schema.types))
        if needs_agg:
            sel_ops: list[Operator] = [AggregateOp(
                q.selector, jschema, target, sel_scope,
                batch_mode=False, expired_possible=True,
                current_on=current_on, expired_on=expired_on,
                fifo_expiry=False)]
        else:
            sel_ops = [ProjectOp(q.selector, jschema, target, sel_scope,
                                 current_on=current_on,
                                 expired_on=expired_on)]

        if name in app.queries:
            raise CompileError(f"duplicate query name '{name}'")
        qr = JoinQueryRuntime(name, l_ops, r_ops, crosses, sel_ops,
                              {"L": l_schema, "R": r_schema}, jschema, app,
                              side_tables=side_tables)
        # cron windows on join sides are host-scheduled like single-stream
        # ones; their fires reach both sides as TIMER batches
        qr._host_sched.extend(
            op.host_schedule for op in l_ops + r_ops
            if getattr(op, "host_schedule", None))
        if not l_is_table:
            app.junctions[jin.left.stream_id].subscribe(
                JoinStreamReceiver(qr, "L"))
        if not r_is_table:
            app.junctions[jin.right.stream_id].subscribe(
                JoinStreamReceiver(qr, "R"))
        app.queries[name] = qr
        if isinstance(out, A.InsertIntoStream):
            tj = app.junction_for(out.target, qr.out_schema)
            if out.target not in app.input_handlers:
                app.input_handlers[out.target] = InputHandler(
                    out.target, tj, app)
            qr.output_handlers.append(InsertIntoStreamHandler(tj, out_type))
        self.attach_rate_limiter(qr, q, name)

    # -- pattern / sequence queries --------------------------------------
    def plan_pattern_query(self, q: A.Query, name: str) -> None:
        app = self.app
        sin: A.StateInputStream = q.input
        out = q.output
        if isinstance(out, (A.InsertIntoStream, A.ReturnStream)):
            out_type = out.output_event_type
        else:
            raise CompileError(f"query '{name}': table output not yet "
                               "supported")
        target = out.target if isinstance(out, A.InsertIntoStream) else name
        current_on = out_type in ("current", "all")
        expired_on = out_type in ("expired", "all")

        compiler = NfaCompiler(app.schemas, sin.state_type)
        slots, states = compiler.compile(sin.state)
        # e[last] / e[last - k] select refs -> ifThenElse chains over the
        # slot's copy columns (nfa.rewrite_last_refs)
        from ..ops.nfa import rewrite_last_refs, rewrite_oob_refs
        sel = q.selector
        if sel.attributes:
            sel.attributes = [
                dataclasses.replace(
                    oa, expression=rewrite_oob_refs(
                        rewrite_last_refs(oa.expression, slots), slots))
                for oa in sel.attributes]
        if sel.having is not None:
            sel.having = rewrite_oob_refs(
                rewrite_last_refs(sel.having, slots), slots)
        if parallel_supported(slots, states, sin.state_type):
            # the TPU-shaped round-parallel engine (larger pending table —
            # its grids are cheap; the scan engine stays small)
            engine = ParallelNfaEngine(slots, states, sin.state_type,
                                       sin.within_ms, capacity=4096,
                                       out_capacity=16384)
        else:
            engine = NfaEngine(slots, states, sin.state_type,
                               sin.within_ms)
        scope = MatchScope(slots, engine.col_index)

        sel_ops: list[Operator] = []
        if selector_needs_aggregation(q.selector):
            sel_ops.append(AggregateOp(
                q.selector, engine.match_schema, target, scope,
                batch_mode=False, expired_possible=False,
                current_on=current_on, expired_on=expired_on))
        else:
            sel_ops.append(ProjectOp(
                q.selector, engine.match_schema, target, scope,
                current_on=current_on, expired_on=expired_on,
                having_in_scope=scope))

        if name in app.queries:
            raise CompileError(f"duplicate query name '{name}'")
        qr = PatternQueryRuntime(name, engine, sel_ops, app)
        for sid in sorted({s.stream_id for s in slots}):
            app.junctions[sid].subscribe(PatternStreamReceiver(qr, sid))
        app.queries[name] = qr
        if isinstance(out, A.InsertIntoStream):
            tj = app.junction_for(out.target, qr.out_schema)
            if out.target not in app.input_handlers:
                app.input_handlers[out.target] = InputHandler(
                    out.target, tj, app)
            qr.output_handlers.append(InsertIntoStreamHandler(tj, out_type))
        self.attach_rate_limiter(qr, q, name)


def _expect(params, n, name):
    if len(params) != n:
        raise CompileError(f"window '{name}' takes {n} parameter(s), got "
                           f"{len(params)}")


def _time_str_ms(s, role: str) -> int:
    """'100 millisecond' / '2 sec' / bare ms int -> milliseconds."""
    s = str(s).strip()
    m = re.fullmatch(
        r"(\d+)\s*(millisecond|milliseconds|ms|sec|second|seconds|s|"
        r"min|minute|minutes|hour|hours|h)?", s)
    if not m:
        raise CompileError(f"{role}: cannot parse time '{s}'")
    n = int(m.group(1))
    unit = m.group(2) or "ms"
    mult = {"millisecond": 1, "milliseconds": 1, "ms": 1,
            "sec": 1000, "second": 1000, "seconds": 1000, "s": 1000,
            "min": 60_000, "minute": 60_000, "minutes": 60_000,
            "hour": 3_600_000, "hours": 3_600_000, "h": 3_600_000}[unit]
    return n * mult


def _ms(v, name) -> int:
    if not isinstance(v, int):
        raise CompileError(f"window '{name}' duration must be int/time, got "
                           f"{v!r}")
    return int(v)

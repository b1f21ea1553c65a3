"""Window operators: fixed-capacity ring-buffer retention with CURRENT /
EXPIRED / RESET emission, fully vectorized (no per-event host loop).

Reference mapping (modules/siddhi-core/.../query/processor/stream/window/):
- TimeWindowProcessor.java:133-169   -> TimeWindowOp
- LengthWindowProcessor.java:106-141 -> LengthWindowOp
- LengthBatchWindowProcessor.java    -> LengthBatchWindowOp
- TimeBatchWindowProcessor.java      -> TimeBatchWindowOp

Design: the reference walks a linked list per event, cloning events into an
expired queue and splicing EXPIRED events back into the chunk in emission
order. Here a window holds a struct-of-arrays buffer of capacity W with
monotonically increasing arrival sequence numbers. One jitted step consumes a
whole input batch:

  1. build a "pool" = buffered rows ++ new arrivals,
  2. compute, per pool row, the input row index at which it is emitted
     (expiry / eviction / flush), vectorized — e.g. searchsorted over the
     batch's running event-time (timestamps are non-decreasing in arrival
     order, as produced by InputHandler stamping and playback replay),
  3. emit EXPIRED rows interleaved *before* their triggering CURRENT row
     (exact reference ordering: TimeWindowProcessor.java:141-152 inserts
     expired events before current), reconstructed with one lexsort,
  4. keep the newest non-emitted pool rows as the next buffer.

Output capacity is static per (input capacity, window capacity). TIMER rows
advance time and are consumed (the reference removes non-CURRENT events from
the chunk: TimeWindowProcessor.java:162-163).

Overflow: the reference's queues are unbounded; here capacity is static.
When live contents exceed W the oldest rows are dropped and
state['overflow'] counts them — no silent loss.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.event import CURRENT, EXPIRED, RESET, EventBatch, StreamSchema
from ..core.types import np_dtype
from .expr import CompileError
from .keyed import cummax_fast, cumsum_fast
from .operators import Operator

from .sentinels import I32_LO, I32_MAX, NEG_INF, POS_INF  # noqa: F401


# ---------------------------------------------------------------------------
# buffer helpers
# ---------------------------------------------------------------------------


def empty_buffer(schema: StreamSchema, cap: int) -> dict:
    from ..core.types import col_zeros
    return {
        "ts": jnp.zeros((cap,), dtype=jnp.int64),
        "seq": jnp.zeros((cap,), dtype=jnp.int64),
        "cols": tuple(col_zeros(t, cap) for t in schema.types),
        "nulls": tuple(jnp.zeros((cap,), dtype=jnp.bool_)
                       for _ in schema.types),
        "valid": jnp.zeros((cap,), dtype=jnp.bool_),
    }


def _gather_buffer(pool: dict, idx, valid):
    return {
        "ts": pool["ts"][idx],
        "seq": pool["seq"][idx],
        "cols": tuple(c[idx] for c in pool["cols"]),
        "nulls": tuple(n[idx] for n in pool["nulls"]),
        "valid": valid,
    }


def make_pool(buf: dict, batch: EventBatch, arrival_seq, arrival_valid) -> dict:
    """Concatenate buffered rows with the batch's arriving rows."""
    return {
        "ts": jnp.concatenate([buf["ts"], batch.ts]),
        "seq": jnp.concatenate([buf["seq"], arrival_seq]),
        "cols": tuple(jnp.concatenate([b, c])
                      for b, c in zip(buf["cols"], batch.cols)),
        "nulls": tuple(jnp.concatenate([b, c])
                       for b, c in zip(buf["nulls"], batch.nulls)),
        "valid": jnp.concatenate([buf["valid"], arrival_valid]),
    }


def _rel32(seq):
    """Compress monotone int64 seq values to int32 sort keys.

    XLA TPU sorts int32 natively but emulates int64 (compile AND run cost
    ~2x); within one step all live seqs span far less than 2^31, so
    ordering by (seq - max_seq) clipped to int32 is exact. NEG_INF
    sentinels clamp to the int32 floor (still sorting first)."""
    smax = jnp.max(seq)
    return jnp.clip(seq - smax, I32_LO, 0).astype(jnp.int32)


# sort-free region compaction ("Streaming Computations with Region-Based
# State on SIMD Architectures" — docs/performance.md): the buffer is two
# regions, the seq-sorted base ++ the chunk's ragged arrivals, so
# compaction is rank arithmetic (one prefix sum + one searchsorted
# GATHER), never a sort. SIDDHI_TPU_WINDOW_COMPACTION=sort restores the
# argsort path everywhere (read once at import — per-call flapping would
# flap compiled-program identities, docs/compile_cache.md).
_REGION_COMPACTION = os.environ.get(
    "SIDDHI_TPU_WINDOW_COMPACTION", "region").strip().lower() != "sort"


def keep_newest(pool: dict, keep_mask, cap: int, presorted: bool = False):
    """Retain the newest (by seq) `cap` rows where keep_mask; returns
    (buffer dict of size cap in seq order, overflow_count).

    presorted=True: the caller guarantees the pool's KEPT rows already
    appear in ascending-seq order (every make_pool-style pool — base
    buffer segment then arrivals — qualifies). Compaction then needs NO
    sort: one prefix sum ranks the kept rows and one searchsorted
    gather places the newest `cap` of them, keeping the layout contract
    (valid tail in seq order) bit-compatible with the argsort path.
    Note the earlier sort-free attempt that measured SLOWER on TPU
    v5-lite (271k vs 316k ev/s on window_agg) was SCATTER-based —
    dynamic-index scatters lower worse than the native int32 sort; this
    path is pure gathers.

    The argsort path remains for pools without an ordering guarantee
    (comparator/frequency-evicting windows) and as the
    SIDDHI_TPU_WINDOW_COMPACTION=sort fallback."""
    n = pool["seq"].shape[0]
    keep = keep_mask & pool["valid"]
    if presorted and _REGION_COMPACTION:
        c = jnp.cumsum(keep.astype(jnp.int32))       # kept-rank prefix
        total = c[n - 1]
        j = jnp.arange(cap, dtype=jnp.int32)
        r = total - cap + j          # kept-rank landing in output slot j
        take = jnp.clip(jnp.searchsorted(c, r + 1, side="left"), 0, n - 1)
        new_valid = r >= 0
        overflow = jnp.maximum(total - cap, 0).astype(jnp.int64)
        return _gather_buffer(pool, take, new_valid), overflow
    key = _rel32(jnp.where(keep, pool["seq"], NEG_INF))
    idx = jnp.argsort(key)          # dropped/invalid first, then kept by seq
    kept_count = jnp.sum(keep.astype(jnp.int64))
    take = idx[n - cap:]
    new_valid = jnp.arange(n - cap, n) >= (n - jnp.minimum(kept_count, cap))
    overflow = jnp.maximum(kept_count - cap, 0)
    return _gather_buffer(pool, take, new_valid), overflow


def emission_sort(out: dict, emit_row, phase, seq, valid,
                  out_cap: int) -> EventBatch:
    """Order output rows by (emit_row, phase, seq); invalid rows last.

    emit_row: input row index at which the row is emitted.
    phase: 0 expired, 1 reset, 2 current, 3 post-current (length(0) case).

    ONE stable int32 argsort (native TPU sort width). Contract: rows with
    EQUAL (emit_row, phase) must already appear in seq order in the input
    arrays — window steps build `out` by concatenating seq-sorted buffer
    segments with row-ordered arrivals, so stability replaces the seq
    tiebreak (`seq` is kept in the signature as documentation of that
    ordering contract).
    """
    primary = jnp.where(valid, (emit_row * 4 + phase).astype(jnp.int32),
                        I32_MAX)
    order = jnp.argsort(primary)
    idx = order[:out_cap]
    return EventBatch(
        ts=out["ts"][idx],
        cols=tuple(c[idx] for c in out["cols"]),
        nulls=tuple(nu[idx] for nu in out["nulls"]),
        kind=out["kind"][idx],
        valid=valid[idx],
    )


def running_time(batch: EventBatch):
    """Per-row event time: cumulative max of valid rows' timestamps
    (timestamps are non-decreasing in arrival order; cummax guards padding)."""
    ts = jnp.where(batch.valid, batch.ts, NEG_INF)
    return cummax_fast(ts)


def arrival_seqs(batch: EventBatch, next_seq):
    """Assign consecutive seq numbers to CURRENT rows."""
    cur = batch.valid & (batch.kind == CURRENT)
    offs = cumsum_fast(cur.astype(jnp.int64)) - 1
    seq = jnp.where(cur, next_seq + offs, NEG_INF)
    n_cur = jnp.sum(cur.astype(jnp.int64))
    return cur, seq, next_seq + n_cur


def current_row_positions(cur, B: int):
    """Row index of the k-th CURRENT row (invalid ks map to garbage rows —
    callers must mask)."""
    return jnp.argsort(jnp.where(cur, jnp.arange(B, dtype=jnp.int32),
                                 I32_MAX))


class WindowOp(Operator):
    """Base: windows preserve the input schema.

    is_batch mirrors the reference's ProcessingMode.BATCH
    (BatchingWindowProcessor subclasses): the selector then emits one result
    per flush chunk and expired emission is gated on outputExpectsExpired.
    """

    is_batch = False
    sort_heavy = True  # emission_sort / keep_newest lexsorts
    # default: timers must catch up boundary-by-boundary (batch windows
    # flush ONE boundary per step). Sliding windows whose expiry is
    # computed per-row inside the event step opt out — their past dues
    # are pure no-op dispatches (runtime._schedule skip).
    needs_catchup = True
    # expiry order == arrival order (time/length/... windows expire the
    # oldest content first); sliding min/max relies on this. Windows that
    # expel by comparator or frequency set it False.
    fifo_expiry = True

    @property
    def filter_pushdown_safe(self) -> bool:
        """Whether a row-local filter commutes with this window
        BIT-EXACTLY (plan/optimizer.py pushdown legality). False by
        default: count-based membership (length/lengthBatch/sort/
        frequent) depends on WHICH rows arrive, so filtering before vs
        after selects different retained sets. Pure time-sliding
        windows override: membership is timestamp-only — but only while
        expired emission is off, because an expired row's rewritten
        observation timestamp reads the running event-time at the
        triggering row, and pre-filter masking moves that row."""
        return False

    def __init__(self, schema: StreamSchema, expired_enabled: bool = True):
        self.schema = schema
        self.expired_enabled = expired_enabled

    @property
    def out_schema(self):
        return self.schema

    def next_due(self, state) -> Optional[jnp.ndarray]:
        """Earliest pending timer (int64 scalar, POS_INF if none), or None
        if this window never needs timer wakeups."""
        return None

    # host_due_bound(ts_min) -> int: a LOWER bound on this window's next
    # due after ingesting a chunk whose earliest timestamp is ts_min.
    # Lets the runtime schedule timers without reading the device due
    # back through the host link (one blocking read per step);
    # a too-early (spurious) timer step is cheap and its own deferred
    # device due re-arms the true one. None = no host bound available.
    host_due_bound = None

    def findable_buffer(self, state) -> dict:
        """The window content a join/table find() searches (= the
        reference's expiredEventQueue handed to OperatorParser in
        compileCondition, e.g. TimeWindowProcessor.java:172-184)."""
        raise CompileError(
            f"window '{type(self).__name__}' is not findable (cannot be "
            "used in joins)")


# ---------------------------------------------------------------------------
# sliding windows
# ---------------------------------------------------------------------------


class TimeWindowOp(WindowOp):
    """#window.time(T): retain each event T ms; on expiry re-emit as EXPIRED
    with its timestamp rewritten to the expiry-observation time, interleaved
    before the triggering current event (TimeWindowProcessor.java:141-161)."""

    needs_catchup = False  # per-row in-step expiry covers past dues

    kind_name = "time"

    def __init__(self, schema, duration_ms: int, cap: int = 4096,
                 expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        self.T = int(duration_ms)
        self.cap = int(cap)

    def init_state(self):
        return {"buf": empty_buffer(self.schema, self.cap),
                "next_seq": jnp.int64(0),
                "overflow": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        W = self.cap
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        rt = running_time(batch)
        pool = make_pool(state["buf"], batch, seq, cur)
        P = W + B

        due_ts = pool["ts"] + self.T
        expire_row = jnp.searchsorted(rt, due_ts, side="left")
        # an arrival can only expire at rows strictly after its own
        # (matters for time(0): the clone is queued after expiry checks)
        own_row = jnp.concatenate([jnp.full((W,), -1, jnp.int64),
                                   jnp.arange(B, dtype=jnp.int64)])
        expire_row = jnp.maximum(expire_row, own_row + 1)
        expires_here = pool["valid"] & (expire_row < B)

        exp_row_safe = jnp.clip(expire_row, 0, B - 1)
        out = {
            "ts": jnp.concatenate([rt[exp_row_safe], batch.ts]),
            "cols": tuple(jnp.concatenate([pc, bc])
                          for pc, bc in zip(pool["cols"], batch.cols)),
            "nulls": tuple(jnp.concatenate([pn, bn])
                           for pn, bn in zip(pool["nulls"], batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((P,), EXPIRED, dtype=jnp.int32),
                jnp.full((B,), CURRENT, dtype=jnp.int32)]),
        }
        emit_row = jnp.concatenate([exp_row_safe,
                                    jnp.arange(B, dtype=jnp.int64)])
        phase = jnp.concatenate([jnp.zeros((P,), jnp.int64),
                                 jnp.full((B,), 2, jnp.int64)])
        oseq = jnp.concatenate([pool["seq"], seq])
        exp_valid = expires_here if self.expired_enabled else jnp.zeros_like(
            expires_here)
        valid = jnp.concatenate([exp_valid, cur])
        result = emission_sort(out, emit_row, phase, oseq, valid, P + B)

        buf, overflow = keep_newest(pool, ~expires_here, W, presorted=True)
        return ({"buf": buf, "next_seq": next_seq,
                 "overflow": state["overflow"] + overflow}, result)

    def next_due(self, state):
        buf = state["buf"]
        due = jnp.where(buf["valid"], buf["ts"] + self.T, POS_INF)
        return jnp.min(due)

    def host_due_bound(self, ts_min: int) -> int:
        return ts_min + self.T

    @property
    def filter_pushdown_safe(self) -> bool:
        # time-only membership: filter-then-window == window-then-filter
        # bit-exactly when no EXPIRED rows are emitted (see base class)
        return not self.expired_enabled

    def findable_buffer(self, state):
        return state["buf"]


class LengthWindowOp(WindowOp):
    """#window.length(L): keep the last L events; arrival L+k evicts arrival
    k as EXPIRED (timestamp rewritten to processing time), emitted before the
    current event (LengthWindowProcessor.java:106-141)."""

    kind_name = "length"

    def __init__(self, schema, length: int, expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        if length < 0:
            raise CompileError("length window requires length >= 0")
        self.L = int(length)

    def init_state(self):
        cap = max(self.L, 1)
        return {"buf": empty_buffer(self.schema, cap),
                "next_seq": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        L = self.L
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])

        if L == 0:
            # every event -> CURRENT, then EXPIRED clone, then RESET
            # (LengthWindowProcessor.java:125-139)
            out = {
                "ts": jnp.concatenate([batch.ts] * 3),
                "cols": tuple(jnp.concatenate([c] * 3) for c in batch.cols),
                "nulls": tuple(jnp.concatenate([n] * 3) for n in batch.nulls),
                "kind": jnp.concatenate([
                    jnp.full((B,), CURRENT, jnp.int32),
                    jnp.full((B,), EXPIRED, jnp.int32),
                    jnp.full((B,), RESET, jnp.int32)]),
            }
            rows = jnp.arange(B, dtype=jnp.int64)
            emit_row = jnp.concatenate([rows] * 3)
            phase = jnp.concatenate([jnp.full((B,), 2, jnp.int64),
                                     jnp.full((B,), 3, jnp.int64),
                                     jnp.full((B,), 3, jnp.int64)])
            oseq = jnp.concatenate([seq, seq, seq + 1])  # expired before reset
            exp_on = cur if self.expired_enabled else jnp.zeros_like(cur)
            valid = jnp.concatenate([cur, exp_on, cur])
            return ({"buf": state["buf"], "next_seq": next_seq},
                    emission_sort(out, emit_row, phase, oseq, valid, 3 * B))

        pool = make_pool(state["buf"], batch, seq, cur)
        P = pool["seq"].shape[0]
        last_seq = next_seq - 1
        evicted = pool["valid"] & (pool["seq"] <= last_seq - L)
        cur_rows = current_row_positions(cur, B)
        k = jnp.clip(pool["seq"] + L - state["next_seq"], 0, B - 1)
        emit_row_evicted = cur_rows[k]

        now_col = jnp.broadcast_to(now, (P,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([now_col, batch.ts]),
            "cols": tuple(jnp.concatenate([pc, bc])
                          for pc, bc in zip(pool["cols"], batch.cols)),
            "nulls": tuple(jnp.concatenate([pn, bn])
                           for pn, bn in zip(pool["nulls"], batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((P,), EXPIRED, jnp.int32),
                jnp.full((B,), CURRENT, jnp.int32)]),
        }
        emit_row = jnp.concatenate([emit_row_evicted,
                                    jnp.arange(B, dtype=jnp.int64)])
        phase = jnp.concatenate([jnp.zeros((P,), jnp.int64),
                                 jnp.full((B,), 2, jnp.int64)])
        oseq = jnp.concatenate([pool["seq"], seq])
        exp_valid = evicted if self.expired_enabled else jnp.zeros_like(evicted)
        valid = jnp.concatenate([exp_valid, cur])
        result = emission_sort(out, emit_row, phase, oseq, valid, P + B)
        buf, _ = keep_newest(pool, ~evicted, max(L, 1), presorted=True)
        return ({"buf": buf, "next_seq": next_seq}, result)

    def findable_buffer(self, state):
        return state["buf"]


# ---------------------------------------------------------------------------
# batch (tumbling) windows
# ---------------------------------------------------------------------------


class LengthBatchWindowOp(WindowOp):
    """#window.lengthBatch(L): tumbling count window. When the L-th event of
    a batch arrives, emit [previous batch as EXPIRED (ts=processing time),
    RESET, this batch as CURRENT] (LengthBatchWindowProcessor
    .processFullBatchEvents flush order)."""

    kind_name = "lengthBatch"
    is_batch = True

    def __init__(self, schema, length: int, expired_enabled: bool = True,
                 stream_current: bool = False):
        super().__init__(schema, expired_enabled)
        if length <= 0:
            raise CompileError("lengthBatch window requires length > 0")
        self.L = int(length)
        # 2nd bool param (stream.current.event): currents stream out on
        # arrival; only the batch EXPIRY happens at the flush
        # (LengthBatchWindowProcessor streamCurrentEvents mode)
        self.stream_current = bool(stream_current)

    def init_state(self):
        return {"cur": empty_buffer(self.schema, self.L),
                "exp": empty_buffer(self.schema, self.L),
                "next_seq": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        L = self.L
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        pool = make_pool(state["cur"], batch, seq, cur)
        P = pool["seq"].shape[0]
        EB = state["exp"]["seq"].shape[0]
        cur_rows = current_row_positions(cur, B)

        batch_of = jnp.where(pool["valid"], pool["seq"] // L, jnp.int64(-1))
        first_batch = state["next_seq"] // L      # id of pending batch
        last_complete = next_seq // L             # batches < this are complete
        flushed = pool["valid"] & (batch_of < last_complete)
        any_flush = last_complete > first_batch

        # flush row of batch k = row of arrival seq (k+1)*L - 1
        flush_seq = (batch_of + 1) * L - 1
        flush_row = cur_rows[jnp.clip(flush_seq - state["next_seq"], 0, B - 1)]
        # carried previous batch (state.exp) expires at the FIRST flush
        first_flush_row = cur_rows[jnp.clip(
            (first_batch + 1) * L - 1 - state["next_seq"], 0, B - 1)]
        # batches completed in this input batch expire at the NEXT flush
        # (if it also happens in this input batch)
        exp_next_row = cur_rows[jnp.clip(
            (batch_of + 2) * L - 1 - state["next_seq"], 0, B - 1)]
        pool_expires = flushed & (batch_of + 1 < last_complete)
        # one RESET per flush, carried by the batch's last event
        is_batch_tail = flushed & (pool["seq"] == flush_seq)

        now_exp = jnp.broadcast_to(now, (EB,)).astype(jnp.int64)
        now_pool = jnp.broadcast_to(now, (P,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([now_exp, now_pool, pool["ts"], now_pool]),
            "cols": tuple(jnp.concatenate([ec, pc, pc, pc]) for ec, pc in
                          zip(state["exp"]["cols"], pool["cols"])),
            "nulls": tuple(jnp.concatenate([en, pn, pn, pn]) for en, pn in
                           zip(state["exp"]["nulls"], pool["nulls"])),
            "kind": jnp.concatenate([
                jnp.full((EB,), EXPIRED, jnp.int32),
                jnp.full((P,), EXPIRED, jnp.int32),
                jnp.full((P,), CURRENT, jnp.int32),
                jnp.full((P,), RESET, jnp.int32)]),
        }
        arr_row = jnp.clip(pool["seq"] - state["next_seq"], 0, B - 1)
        arr_row = cur_rows[arr_row].astype(jnp.int64)
        cur_row_src = arr_row if self.stream_current \
            else jnp.where(flushed, flush_row, 0)
        exp_row_src = jnp.where(flushed, flush_row, 0) \
            if self.stream_current \
            else jnp.where(pool_expires, exp_next_row, 0)
        emit_row = jnp.concatenate([
            jnp.broadcast_to(first_flush_row, (EB,)),
            exp_row_src,
            cur_row_src,
            jnp.where(is_batch_tail, flush_row, 0)])
        phase = jnp.concatenate([
            jnp.zeros((EB,), jnp.int64),
            jnp.zeros((P,), jnp.int64),
            jnp.full((P,), 2, jnp.int64),
            jnp.ones((P,), jnp.int64)])
        oseq = jnp.concatenate([state["exp"]["seq"], pool["seq"],
                                pool["seq"], pool["seq"]])
        if self.expired_enabled:
            exp_carry_valid = state["exp"]["valid"] & any_flush
            exp_pool_valid = pool_expires
        else:
            exp_carry_valid = jnp.zeros((EB,), jnp.bool_)
            exp_pool_valid = jnp.zeros((P,), jnp.bool_)
        arrivals = pool["valid"] & (pool["seq"] >= state["next_seq"])
        cur_valid = arrivals if self.stream_current else flushed
        if self.stream_current:
            # streamed currents already went out; the completed batch
            # expires AT its own flush (not one flush later)
            exp_carry_valid = jnp.zeros((EB,), jnp.bool_)
            exp_pool_valid = flushed if self.expired_enabled \
                else jnp.zeros((P,), jnp.bool_)
        valid = jnp.concatenate([exp_carry_valid, exp_pool_valid, cur_valid,
                                 is_batch_tail])
        result = emission_sort(out, emit_row, phase, oseq, valid,
                               EB + 3 * P)

        pending = pool["valid"] & (batch_of >= last_complete)
        new_cur, _ = keep_newest(pool, pending, L, presorted=True)
        last_batch = pool["valid"] & (batch_of == last_complete - 1)
        new_exp_pool, _ = keep_newest(pool, last_batch, L, presorted=True)
        new_exp = jax.tree_util.tree_map(
            lambda a, b: jnp.where(any_flush, a, b), new_exp_pool,
            state["exp"])
        return ({"cur": new_cur, "exp": new_exp, "next_seq": next_seq},
                result)

    def findable_buffer(self, state):
        return state["cur"] if self.stream_current else state["exp"]


class TimeBatchWindowOp(WindowOp):
    """#window.timeBatch(T [, startTime]): tumbling time window. Flush
    decision is made once per input chunk (TimeBatchWindowProcessor.process:
    currentTime >= nextEmitTime), emitting [expired previous batch (ts=now),
    RESET, buffered batch including this chunk's arrivals]."""

    kind_name = "timeBatch"
    is_batch = True

    def __init__(self, schema, duration_ms: int, start_time: Optional[int] = None,
                 cap: int = 4096, expired_enabled: bool = True,
                 stream_current: bool = False):
        super().__init__(schema, expired_enabled)
        self.T = int(duration_ms)
        self.start_time = start_time
        self.cap = int(cap)
        # 2nd/3rd bool param: stream currents out on arrival, expire in
        # batches (TimeBatchWindowProcessor isStreamCurrentEvents)
        self.stream_current = bool(stream_current)

    def init_state(self):
        return {"cur": empty_buffer(self.schema, self.cap),
                "exp": empty_buffer(self.schema, self.cap),
                "next_seq": jnp.int64(0),
                "next_emit": jnp.int64(-1),
                "overflow": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        W = self.cap
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])

        if self.start_time is not None:
            init_emit = now - ((now - self.start_time) % self.T) + self.T
        else:
            init_emit = now + self.T
        next_emit = jnp.where(state["next_emit"] == -1, init_emit,
                              state["next_emit"])
        send = now >= next_emit
        next_emit = jnp.where(send, next_emit + self.T, next_emit)

        pool = make_pool(state["cur"], batch, seq, cur)
        P = W + B
        EB = W

        now_exp = jnp.broadcast_to(now, (EB,)).astype(jnp.int64)
        now_pool2 = jnp.broadcast_to(now, (P,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([now_exp, pool["ts"],
                                   jnp.broadcast_to(now, (1,)).astype(jnp.int64)]),
            "cols": tuple(jnp.concatenate([ec, pc, pc[:1]]) for ec, pc in
                          zip(state["exp"]["cols"], pool["cols"])),
            "nulls": tuple(jnp.concatenate([en, pn, pn[:1]]) for en, pn in
                           zip(state["exp"]["nulls"], pool["nulls"])),
            "kind": jnp.concatenate([
                jnp.full((EB,), EXPIRED, jnp.int32),
                jnp.full((P,), CURRENT, jnp.int32),
                jnp.full((1,), RESET, jnp.int32)]),
        }
        Z = jnp.zeros((), jnp.int64)
        emit_row = jnp.concatenate([
            jnp.zeros((EB,), jnp.int64), jnp.zeros((P,), jnp.int64),
            jnp.zeros((1,), jnp.int64)])
        phase = jnp.concatenate([
            jnp.zeros((EB,), jnp.int64), jnp.full((P,), 2, jnp.int64),
            jnp.ones((1,), jnp.int64)])
        oseq = jnp.concatenate([state["exp"]["seq"], pool["seq"], Z[None]])
        had_pending = jnp.any(pool["valid"])
        exp_valid = (state["exp"]["valid"] & send) if self.expired_enabled \
            else jnp.zeros((EB,), jnp.bool_)
        arrivals = pool["valid"] & (pool["seq"] >= state["next_seq"])
        cur_valid = arrivals if self.stream_current \
            else (pool["valid"] & send)
        valid = jnp.concatenate([
            exp_valid,
            cur_valid,
            (send & had_pending)[None]])
        if self.stream_current:
            # streamed currents already went out on arrival; the batch
            # expires AT its own boundary (not one flush later)
            exp_now = pool["valid"] & send
            if not self.expired_enabled:
                exp_now = jnp.zeros_like(exp_now)
            out = {
                "ts": jnp.concatenate([out["ts"], now_pool2]),
                "cols": tuple(jnp.concatenate([oc, pc])
                              for oc, pc in zip(out["cols"],
                                                pool["cols"])),
                "nulls": tuple(jnp.concatenate([on, pn])
                               for on, pn in zip(out["nulls"],
                                                 pool["nulls"])),
                "kind": jnp.concatenate([
                    out["kind"], jnp.full((P,), EXPIRED, jnp.int32)]),
            }
            emit_row = jnp.concatenate([emit_row,
                                        jnp.zeros((P,), jnp.int64)])
            phase = jnp.concatenate([phase, jnp.zeros((P,), jnp.int64)])
            oseq = jnp.concatenate([oseq, pool["seq"]])
            valid = jnp.concatenate([
                jnp.zeros((EB,), jnp.bool_),     # no carried expiry
                valid[EB:],
                exp_now])
        cap_out = EB + P + 1 + (P if self.stream_current else 0)
        result = emission_sort(out, emit_row, phase, oseq, valid, cap_out)

        # buffers: on send, cur batch -> exp, cur empties; else cur keeps all
        new_cur_flush, _ = keep_newest(pool, jnp.zeros_like(pool["valid"]),
                                       W, presorted=True)
        new_cur_keep, overflow = keep_newest(pool, pool["valid"], W,
                                             presorted=True)
        new_exp_flush, _ = keep_newest(pool, pool["valid"], W, presorted=True)
        new_cur = jax.tree_util.tree_map(
            lambda a, b: jnp.where(send, a, b), new_cur_flush, new_cur_keep)
        new_exp = jax.tree_util.tree_map(
            lambda a, b: jnp.where(send, a, b), new_exp_flush, state["exp"])
        return ({"cur": new_cur, "exp": new_exp, "next_seq": next_seq,
                 "next_emit": next_emit,
                 "overflow": state["overflow"] + overflow}, result)

    def next_due(self, state):
        ne = state["next_emit"]
        return jnp.where(ne == -1, POS_INF, ne)

    def findable_buffer(self, state):
        return state["cur"] if self.stream_current else state["exp"]

"""Window operators, wave 2: externalTime, timeLength, delay, batch,
sort, frequent, lossyFrequent, externalTimeBatch, session, cron.

Reference mapping (modules/siddhi-core/.../query/processor/stream/window/):
- ExternalTimeWindowProcessor.java:125-161      -> ExternalTimeWindowOp
- TimeLengthWindowProcessor.java:139-189        -> TimeLengthWindowOp
- DelayWindowProcessor.java:125-165             -> DelayWindowOp
- BatchWindowProcessor.java:122-195             -> BatchWindowOp
- SortWindowProcessor.java:152-183              -> SortWindowOp
- FrequentWindowProcessor.java:115-172          -> FrequentWindowOp
- LossyFrequentWindowProcessor.java:149-210     -> LossyFrequentWindowOp
- ExternalTimeBatchWindowProcessor.java:253-311 -> ExternalTimeBatchWindowOp
- SessionWindowProcessor.java:227-310,437-500   -> SessionWindowOp
- CronWindowProcessor.java:125-135,188-236      -> CronWindowOp

All follow windows.py's design: fixed-capacity struct-of-arrays buffers,
one vectorized step per input batch, emission order reconstructed with one
int32 argsort (emission_sort), overflow dropped-and-counted. The genuinely
sequential ones (sort/frequent/lossyFrequent) run a `lax.scan` over the
batch rows with a bounded carry — exact semantics at reduced throughput
(these are rare / deprecated in the reference).

Documented deviations from the reference (all edge cases):
- delay(0) emits an event at the next step instead of interleaved after the
  next in-chunk event (the queue drains once per step).
- frequent() decrements every tracked key when full (proper Misra-Gries);
  the reference iterates its HashMap's first mostFrequentCount keys in JVM
  hash order, which is not a stable contract to reproduce.
- lossyFrequent() tracks at most `cap` distinct keys (overflow counted);
  the reference's map is unbounded.
- session() assumes non-decreasing event time (guaranteed by playback
  replay and InputHandler stamping), so the late-event path
  (SessionWindowProcessor.addLateEvent) cannot trigger; simultaneous
  session closes order by key slot rather than end-timestamp.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.event import CURRENT, EXPIRED, RESET, TIMER, EventBatch, \
    StreamSchema
from ..core.types import AttrType, np_dtype
from .expr import CompileError
from .keyed import (cummax_fast, cumsum_fast, hash_columns,
                    lookup_or_insert, segmented_cumsum)
from .windows import (I32_MAX, NEG_INF, POS_INF, WindowOp, arrival_seqs,
                      current_row_positions, empty_buffer, emission_sort,
                      keep_newest, make_pool, running_time)


def _ext_running_time(batch: EventBatch, ts_idx: int):
    """Running external clock: cumulative max of the ts attribute over
    valid CURRENT rows."""
    e = batch.cols[ts_idx].astype(jnp.int64)
    e = jnp.where(batch.valid & (batch.kind == CURRENT), e, NEG_INF)
    return cummax_fast(e)


class ExternalTimeWindowOp(WindowOp):
    """#window.externalTime(tsAttr, T): sliding window over an event-carried
    clock. An event expires when a later event's tsAttr reaches its own
    tsAttr + T; the expired clone's timestamp is rewritten to that clock
    value and it is emitted before the triggering event
    (ExternalTimeWindowProcessor.java:129-158). No wall-clock timers."""

    needs_catchup = False  # per-row in-step expiry covers past dues

    kind_name = "externalTime"

    def __init__(self, schema, ts_idx: int, duration_ms: int,
                 cap: int = 4096, expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        self.ts_idx = int(ts_idx)
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
        rt = _ext_running_time(batch, self.ts_idx)
        pool = make_pool(state["buf"], batch, seq, cur)
        P = W + B

        pool_ext = pool["cols"][self.ts_idx].astype(jnp.int64)
        due_ext = pool_ext + self.T
        expire_row = jnp.searchsorted(rt, due_ext, side="left")
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
        exp_valid = expires_here if self.expired_enabled \
            else jnp.zeros_like(expires_here)
        valid = jnp.concatenate([exp_valid, cur])
        result = emission_sort(out, emit_row, phase, oseq, valid, P + B)

        buf, overflow = keep_newest(pool, ~expires_here, W, presorted=True)
        return ({"buf": buf, "next_seq": next_seq,
                 "overflow": state["overflow"] + overflow}, result)

    def findable_buffer(self, state):
        return state["buf"]


class TimeLengthWindowOp(WindowOp):
    """#window.timeLength(T, L): sliding window bounded by both time and
    count. Buffered rows past T expire at the head of the step (ts=now);
    an arrival finding L live rows evicts the oldest (ts=now), emitted
    before it (TimeLengthWindowProcessor.java:143-189)."""

    needs_catchup = False  # per-row in-step expiry covers past dues

    kind_name = "timeLength"

    def __init__(self, schema, duration_ms: int, length: int,
                 expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        if length <= 0:
            raise CompileError("timeLength window requires length > 0")
        self.T = int(duration_ms)
        self.L = int(length)

    def init_state(self):
        return {"buf": empty_buffer(self.schema, self.L),
                "next_seq": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        L = self.L
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        pool = make_pool(state["buf"], batch, seq, cur)
        P = L + B
        is_buf = jnp.arange(P) < L

        # 1. time expiry: buffered rows past T all drain before row 0
        #    (the reference's per-chunk fixed currentTime makes the first
        #    event's drain loop take every due row)
        time_expired = pool["valid"] & is_buf & (pool["ts"] + self.T <= now)
        live = pool["valid"] & ~time_expired
        surv_buf = live & is_buf
        count0 = jnp.sum(surv_buf.astype(jnp.int64))
        n_cur = jnp.sum(cur.astype(jnp.int64))

        # 2. length eviction: queue position q (survivors first, then
        #    arrivals in seq order); pos q is evicted at arrival
        #    k = q + max(0, L - count0) when that arrival exists
        q = jnp.where(is_buf, cumsum_fast(surv_buf.astype(jnp.int64)) - 1,
                      count0 + (pool["seq"] - state["next_seq"]))
        k_evict = q + jnp.maximum(0, L - count0)
        evicted = live & (k_evict < n_cur)
        cur_rows = current_row_positions(cur, B)
        evict_row = cur_rows[jnp.clip(k_evict, 0, B - 1)].astype(jnp.int64)

        emit_row_exp = jnp.where(time_expired, 0, evict_row)
        now_col = jnp.broadcast_to(now, (P,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([now_col, batch.ts]),
            "cols": tuple(jnp.concatenate([pc, bc])
                          for pc, bc in zip(pool["cols"], batch.cols)),
            "nulls": tuple(jnp.concatenate([pn, bn])
                           for pn, bn in zip(pool["nulls"], batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((P,), EXPIRED, dtype=jnp.int32),
                jnp.full((B,), CURRENT, dtype=jnp.int32)]),
        }
        emit_row = jnp.concatenate([emit_row_exp,
                                    jnp.arange(B, dtype=jnp.int64)])
        phase = jnp.concatenate([jnp.zeros((P,), jnp.int64),
                                 jnp.full((B,), 2, jnp.int64)])
        oseq = jnp.concatenate([pool["seq"], seq])
        exp_emit = time_expired | evicted
        exp_valid = exp_emit if self.expired_enabled \
            else jnp.zeros_like(exp_emit)
        valid = jnp.concatenate([exp_valid, cur])
        result = emission_sort(out, emit_row, phase, oseq, valid, P + B)

        buf, _ = keep_newest(pool, live & ~evicted, L, presorted=True)
        return ({"buf": buf, "next_seq": next_seq}, result)

    def next_due(self, state):
        buf = state["buf"]
        due = jnp.where(buf["valid"], buf["ts"] + self.T, POS_INF)
        return jnp.min(due)

    def host_due_bound(self, ts_min: int) -> int:
        return ts_min + self.T

    def findable_buffer(self, state):
        return state["buf"]


class DelayWindowOp(WindowOp):
    """#window.delay(T): hold every event T ms, then release it as CURRENT
    with its timestamp rewritten to the release time; arrivals are
    consumed (DelayWindowProcessor.java:125-165).

    Deviation: delay(0) releases at the next step rather than interleaved
    after the next in-chunk event (the queue drains once per step)."""

    kind_name = "delay"

    def __init__(self, schema, delay_ms: int, cap: int = 4096,
                 expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        self.T = int(delay_ms)
        self.cap = int(cap)

    def init_state(self):
        return {"buf": empty_buffer(self.schema, self.cap),
                "next_seq": jnp.int64(0),
                "overflow": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        W = self.cap
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        pool = make_pool(state["buf"], batch, seq, cur)
        P = W + B
        is_buf = jnp.arange(P) < W

        released = pool["valid"] & is_buf & (pool["ts"] + self.T <= now)
        now_col = jnp.broadcast_to(now, (P,)).astype(jnp.int64)
        out = {
            "ts": now_col,
            "cols": pool["cols"],
            "nulls": pool["nulls"],
            "kind": jnp.full((P,), CURRENT, dtype=jnp.int32),
        }
        emit_row = jnp.zeros((P,), jnp.int64)
        phase = jnp.zeros((P,), jnp.int64)
        result = emission_sort(out, emit_row, phase, pool["seq"], released,
                               P)

        buf, overflow = keep_newest(pool, pool["valid"] & ~released, W,
                                    presorted=True)
        return ({"buf": buf, "next_seq": next_seq,
                 "overflow": state["overflow"] + overflow}, result)

    def next_due(self, state):
        buf = state["buf"]
        due = jnp.where(buf["valid"], buf["ts"] + self.T, POS_INF)
        return jnp.min(due)

    def host_due_bound(self, ts_min: int) -> int:
        return ts_min + self.T

    def findable_buffer(self, state):
        return state["buf"]


class BatchWindowOp(WindowOp):
    """#window.batch([L]): chunk-tumbling window. Each step's arrivals
    (grouped per L when given, else the whole chunk) flush as
    [previous batch EXPIRED (ts=now), previous RESET, group CURRENT];
    the step's arrivals become the next EXPIRED batch
    (BatchWindowProcessor.java:122-195)."""

    kind_name = "batch"
    is_batch = True

    def __init__(self, schema, length: int = 0, cap: int = 4096,
                 expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        if length < 0:
            raise CompileError("batch window length must be >= 0")
        self.L = int(length)
        self.cap = int(cap)

    def init_state(self):
        return {"exp": empty_buffer(self.schema, self.cap),
                "reset": empty_buffer(self.schema, 1),
                "next_seq": jnp.int64(0),
                "overflow": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        EB = state["exp"]["seq"].shape[0]
        n_cur = jnp.sum(cur.astype(jnp.int64))
        any_arrivals = n_cur > 0
        cur_rows = current_row_positions(cur, B)

        # arrival index within this step; group g = a // L (L=0: one group)
        a = cumsum_fast(cur.astype(jnp.int64)) - 1
        if self.L > 0:
            grp = jnp.where(cur, a // self.L, 0)
        else:
            grp = jnp.zeros((B,), jnp.int64)
        # reset rows between in-step groups: group g>0's flush emits a RESET
        # copy of group g-1's first event just before its own currents
        grp_first = cur & (a % self.L == 0) if self.L > 0 \
            else cur & (a == 0)
        # row where group g's currents begin
        if self.L > 0:
            g_start_row = cur_rows[jnp.clip(grp * self.L, 0, B - 1)] \
                .astype(jnp.int64)
            next_g_start = cur_rows[jnp.clip((grp + 1) * self.L, 0, B - 1)] \
                .astype(jnp.int64)
            has_next_g = (grp + 1) * self.L < n_cur
        else:
            g_start_row = jnp.zeros((B,), jnp.int64)
            next_g_start = jnp.zeros((B,), jnp.int64)
            has_next_g = jnp.zeros((B,), jnp.bool_)

        exp_ts = jnp.broadcast_to(now, (EB,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([exp_ts, state["reset"]["ts"], batch.ts,
                                   batch.ts]),
            "cols": tuple(jnp.concatenate([ec, rc, bc, bc])
                          for ec, rc, bc in zip(state["exp"]["cols"],
                                                state["reset"]["cols"],
                                                batch.cols)),
            "nulls": tuple(jnp.concatenate([en, rn, bn, bn])
                           for en, rn, bn in zip(state["exp"]["nulls"],
                                                 state["reset"]["nulls"],
                                                 batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((EB,), EXPIRED, jnp.int32),
                jnp.full((1,), RESET, jnp.int32),
                jnp.full((B,), CURRENT, jnp.int32),
                jnp.full((B,), RESET, jnp.int32)]),
        }
        # carried expired + carried reset emit before group 0; each in-step
        # group-first event doubles as the NEXT group's reset marker
        emit_row = jnp.concatenate([
            jnp.zeros((EB,), jnp.int64),
            jnp.zeros((1,), jnp.int64),
            jnp.arange(B, dtype=jnp.int64),
            jnp.where(grp_first & has_next_g, next_g_start, 0)])
        phase = jnp.concatenate([
            jnp.zeros((EB,), jnp.int64),
            jnp.ones((1,), jnp.int64),
            jnp.full((B,), 2, jnp.int64),
            jnp.ones((B,), jnp.int64)])
        oseq = jnp.concatenate([state["exp"]["seq"],
                                state["reset"]["seq"], seq, seq])
        exp_valid = (state["exp"]["valid"] & any_arrivals) \
            if self.expired_enabled \
            else jnp.zeros((EB,), jnp.bool_)
        valid = jnp.concatenate([
            exp_valid,
            state["reset"]["valid"] & any_arrivals,
            cur,
            grp_first & has_next_g])
        result = emission_sort(out, emit_row, phase, oseq, valid,
                               EB + 1 + 2 * B)

        # next state: this step's arrivals (clones) become the expired
        # batch; the LAST group's first event becomes the carried reset.
        # (pool is padded to >= cap rows so keep_newest can emit cap slots)
        pool = make_pool(empty_buffer(self.schema, self.cap), batch, seq,
                         cur)
        pad = jnp.zeros((self.cap,), jnp.bool_)
        new_exp_pool, overflow = keep_newest(pool, pool["valid"], self.cap,
                                             presorted=True)
        new_exp = jax.tree_util.tree_map(
            lambda a_, b_: jnp.where(any_arrivals, a_, b_), new_exp_pool,
            state["exp"])
        if self.L > 0:
            last_grp = jnp.maximum((n_cur - 1) // self.L, 0)
            last_first = grp_first & (grp == last_grp)
        else:
            last_first = grp_first
        new_reset_pool, _ = keep_newest(
            pool, jnp.concatenate([pad, last_first]), 1, presorted=True)
        new_reset = jax.tree_util.tree_map(
            lambda a_, b_: jnp.where(any_arrivals, a_, b_), new_reset_pool,
            state["reset"])
        return ({"exp": new_exp, "reset": new_reset, "next_seq": next_seq,
                 "overflow": state["overflow"] + overflow}, result)

    def findable_buffer(self, state):
        return state["exp"]


# ---------------------------------------------------------------------------
# sequential windows (lax.scan over batch rows, bounded carry)
# ---------------------------------------------------------------------------


def _row_slices(batch: EventBatch, cur):
    """Per-row scan inputs: (cur, ts, cols, nulls)."""
    return (cur, batch.ts, batch.cols, batch.nulls)


class SortWindowOp(WindowOp):
    """#window.sort(L, attr [asc|desc], ...): keep the L smallest events
    per the comparator; when a new arrival makes L+1, the comparator-max
    (latest-inserted among ties, matching the stable Collections.sort +
    remove-last) is emitted EXPIRED (ts=now) AFTER the current event
    (SortWindowProcessor.java:152-183)."""

    kind_name = "sort"
    fifo_expiry = False

    def __init__(self, schema, length: int, keys: list,
                 expired_enabled: bool = True):
        # keys: [(col_idx, +1 asc | -1 desc), ...]
        super().__init__(schema, expired_enabled)
        if length <= 0:
            raise CompileError("sort window requires length > 0")
        for idx, _ in keys:
            if schema.attributes[idx].type is AttrType.STRING:
                raise CompileError(
                    "sort window ordering on STRING attributes is not "
                    "supported (dictionary codes do not preserve "
                    "lexicographic order)")
        self.L = int(length)
        self.keys = list(keys)

    def init_state(self):
        buf = empty_buffer(self.schema, self.L + 1)
        return {"buf": buf, "next_seq": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        L = self.L
        keys = self.keys
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])

        def body(carry, xs):
            buf, nseq = carry
            is_cur, ts, cols, nulls = xs

            def insert(buf, nseq):
                free = jnp.argmin(buf["valid"])
                buf = {
                    "ts": buf["ts"].at[free].set(ts),
                    "seq": buf["seq"].at[free].set(nseq),
                    "cols": tuple(c.at[free].set(v)
                                  for c, v in zip(buf["cols"], cols)),
                    "nulls": tuple(n.at[free].set(v)
                                   for n, v in zip(buf["nulls"], nulls)),
                    "valid": buf["valid"].at[free].set(True),
                }
                count = jnp.sum(buf["valid"].astype(jnp.int32))

                def evict(buf):
                    mask = buf["valid"]
                    for idx, order in keys:
                        v = buf["cols"][idx]
                        v_eff = v if order > 0 else -v
                        m = jnp.max(jnp.where(mask, v_eff,
                                              v_eff.dtype.type(-jnp.inf)
                                              if jnp.issubdtype(v_eff.dtype,
                                                                jnp.floating)
                                              else jnp.iinfo(
                                                  v_eff.dtype).min))
                        mask = mask & (v_eff == m)
                    ei = jnp.argmax(jnp.where(mask, buf["seq"],
                                              jnp.int64(-1)))
                    ev = {"ts": buf["ts"][ei],
                          "cols": tuple(c[ei] for c in buf["cols"]),
                          "nulls": tuple(n[ei] for n in buf["nulls"]),
                          "valid": jnp.bool_(True)}
                    buf2 = dict(buf)
                    buf2["valid"] = buf["valid"].at[ei].set(False)
                    return buf2, ev

                def no_evict(buf):
                    ev = {"ts": jnp.int64(0),
                          "cols": tuple(jnp.zeros((), c.dtype)
                                        for c in buf["cols"]),
                          "nulls": tuple(jnp.zeros((), jnp.bool_)
                                         for _ in buf["nulls"]),
                          "valid": jnp.bool_(False)}
                    return buf, ev

                buf, ev = jax.lax.cond(count > L, evict, no_evict, buf)
                return (buf, nseq + 1), ev

            def skip(buf, nseq):
                ev = {"ts": jnp.int64(0),
                      "cols": tuple(jnp.zeros((), c.dtype)
                                    for c in buf["cols"]),
                      "nulls": tuple(jnp.zeros((), jnp.bool_)
                                     for _ in buf["nulls"]),
                      "valid": jnp.bool_(False)}
                return (buf, nseq), ev

            return jax.lax.cond(is_cur, insert, skip, buf, nseq)

        (buf, _), evs = jax.lax.scan(body, (state["buf"], state["next_seq"]),
                                     _row_slices(batch, cur))

        rows = jnp.arange(B, dtype=jnp.int64)
        now_col = jnp.broadcast_to(now, (B,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([batch.ts, now_col]),
            "cols": tuple(jnp.concatenate([bc, ec])
                          for bc, ec in zip(batch.cols, evs["cols"])),
            "nulls": tuple(jnp.concatenate([bn, en])
                           for bn, en in zip(batch.nulls, evs["nulls"])),
            "kind": jnp.concatenate([
                jnp.full((B,), CURRENT, jnp.int32),
                jnp.full((B,), EXPIRED, jnp.int32)]),
        }
        emit_row = jnp.concatenate([rows, rows])
        phase = jnp.concatenate([jnp.full((B,), 2, jnp.int64),
                                 jnp.full((B,), 3, jnp.int64)])
        oseq = jnp.concatenate([seq, seq])
        ev_valid = evs["valid"] if self.expired_enabled \
            else jnp.zeros_like(evs["valid"])
        valid = jnp.concatenate([cur, ev_valid])
        result = emission_sort(out, emit_row, phase, oseq, valid, 2 * B)
        return ({"buf": buf, "next_seq": next_seq}, result)

    def findable_buffer(self, state):
        return state["buf"]


class FrequentWindowOp(WindowOp):
    """#window.frequent(N [, attrs...]): retain events of the N most
    frequent keys (Misra-Gries). A new key finding the table full
    decrements every tracked count; zeroed keys are emitted EXPIRED
    (ts=now) and freed — if that made room the new event is admitted, else
    it is silently ignored (FrequentWindowProcessor.java:115-172;
    deviation: the reference decrements its HashMap's first N keys in JVM
    hash order, we decrement all tracked keys — proper Misra-Gries)."""

    kind_name = "frequent"
    fifo_expiry = False

    def __init__(self, schema, n: int, key_idxs: list,
                 expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        if not 0 < n <= 64:
            raise CompileError("frequent window count must be in 1..64")
        self.N = int(n)
        self.key_idxs = list(key_idxs) or list(range(len(schema.types)))

    def init_state(self):
        N = self.N
        buf = empty_buffer(self.schema, N)
        return {"buf": buf,
                "keys": jnp.zeros((N,), jnp.int64),
                "counts": jnp.zeros((N,), jnp.int64),
                "next_seq": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        N = self.N
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        khash = hash_columns([batch.cols[i] for i in self.key_idxs],
                             [batch.nulls[i] for i in self.key_idxs])

        def body(carry, xs):
            buf, keys, counts = carry
            is_cur, kh, ts, cols, nulls = xs

            def dead_evs():
                return {"ts": jnp.zeros((N,), jnp.int64),
                        "cols": tuple(jnp.zeros((N,), c.dtype)
                                      for c in buf["cols"]),
                        "nulls": tuple(jnp.zeros((N,), jnp.bool_)
                                       for _ in buf["nulls"]),
                        "valid": jnp.zeros((N,), jnp.bool_)}

            def store_at(buf, slot, count_val, keys, counts):
                buf = {
                    "ts": buf["ts"].at[slot].set(ts),
                    "seq": buf["seq"],
                    "cols": tuple(c.at[slot].set(v)
                                  for c, v in zip(buf["cols"], cols)),
                    "nulls": tuple(n.at[slot].set(v)
                                   for n, v in zip(buf["nulls"], nulls)),
                    "valid": buf["valid"].at[slot].set(True),
                }
                return buf, keys.at[slot].set(kh), \
                    counts.at[slot].set(count_val)

            def process(buf, keys, counts):
                found = buf["valid"] & (keys == kh)
                hit = jnp.any(found)
                slot_hit = jnp.argmax(found)
                n_used = jnp.sum(buf["valid"].astype(jnp.int32))

                def on_hit(buf, keys, counts):
                    buf, keys, counts = store_at(
                        buf, slot_hit, counts[slot_hit] + 1, keys, counts)
                    return buf, keys, counts, jnp.bool_(True), dead_evs()

                def on_new(buf, keys, counts):
                    def has_room(buf, keys, counts):
                        free = jnp.argmin(buf["valid"])
                        buf, keys, counts = store_at(
                            buf, free, jnp.int64(1), keys, counts)
                        return (buf, keys, counts, jnp.bool_(True),
                                dead_evs())

                    def full(buf, keys, counts):
                        dec = counts - buf["valid"].astype(jnp.int64)
                        dies = buf["valid"] & (dec <= 0)
                        evs = {"ts": buf["ts"],
                               "cols": buf["cols"],
                               "nulls": buf["nulls"],
                               "valid": dies}
                        new_valid = buf["valid"] & ~dies
                        buf2 = dict(buf)
                        buf2["valid"] = new_valid
                        counts2 = jnp.where(dies, 0, dec)
                        freed = jnp.any(dies)

                        def admit(buf, keys, counts):
                            free = jnp.argmin(buf["valid"])
                            buf, keys, counts = store_at(
                                buf, free, jnp.int64(1), keys, counts)
                            return (buf, keys, counts, jnp.bool_(True),
                                    evs)

                        def drop(buf, keys, counts):
                            return (buf, keys, counts, jnp.bool_(False),
                                    evs)

                        return jax.lax.cond(freed, admit, drop, buf2, keys,
                                            counts2)

                    return jax.lax.cond(n_used < N, has_room, full, buf,
                                        keys, counts)

                return jax.lax.cond(hit, on_hit, on_new, buf, keys, counts)

            def skip(buf, keys, counts):
                return buf, keys, counts, jnp.bool_(False), dead_evs()

            buf, keys, counts, passed, evs = jax.lax.cond(
                is_cur, process, skip, buf, keys, counts)
            return (buf, keys, counts), (passed, evs)

        (buf, keys, counts), (passed, evs) = jax.lax.scan(
            body, (state["buf"], state["keys"], state["counts"]),
            (cur, khash) + _row_slices(batch, cur)[1:])

        rows = jnp.arange(B, dtype=jnp.int64)
        now_bn = jnp.broadcast_to(now, (B, N)).astype(jnp.int64)

        def flat(x):
            return x.reshape((B * N,) + x.shape[2:])

        out = {
            "ts": jnp.concatenate([flat(now_bn), batch.ts]),
            "cols": tuple(jnp.concatenate([flat(ec), bc])
                          for ec, bc in zip(evs["cols"], batch.cols)),
            "nulls": tuple(jnp.concatenate([flat(en), bn])
                           for en, bn in zip(evs["nulls"], batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((B * N,), EXPIRED, jnp.int32),
                jnp.full((B,), CURRENT, jnp.int32)]),
        }
        emit_row = jnp.concatenate([
            flat(jnp.broadcast_to(rows[:, None], (B, N))), rows])
        phase = jnp.concatenate([jnp.zeros((B * N,), jnp.int64),
                                 jnp.full((B,), 2, jnp.int64)])
        oseq = jnp.concatenate([jnp.zeros((B * N,), jnp.int64), seq])
        ev_valid = flat(evs["valid"]) if self.expired_enabled \
            else jnp.zeros((B * N,), jnp.bool_)
        valid = jnp.concatenate([ev_valid, passed & cur])
        result = emission_sort(out, emit_row, phase, oseq, valid,
                               B * N + B)
        return ({"buf": buf, "keys": keys, "counts": counts,
                 "next_seq": next_seq}, result)

    def findable_buffer(self, state):
        return state["buf"]


class LossyFrequentWindowOp(WindowOp):
    """#window.lossyFrequent(support [, error [, attrs...]]): lossy
    counting. Keys whose observed frequency is at least (support - error)
    of the total pass through; every 1/error events the table is pruned and
    pruned keys' stored events are emitted EXPIRED (ts=now)
    (LossyFrequentWindowProcessor.java:149-210; deviation: at most `cap`
    distinct keys are tracked — insert overflow is counted, never
    silent)."""

    kind_name = "lossyFrequent"
    fifo_expiry = False
    CAP = 32

    def __init__(self, schema, support: float, error: Optional[float],
                 key_idxs: list, expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        self.support = float(support)
        self.error = float(error) if error is not None else \
            self.support / 10.0
        if not 0 < self.error < 1:
            raise CompileError("lossyFrequent error must be in (0,1)")
        self.width = int(-(-1.0 // self.error)) or 1  # ceil(1/error)
        self.key_idxs = list(key_idxs) or list(range(len(schema.types)))

    def init_state(self):
        C = self.CAP
        buf = empty_buffer(self.schema, C)
        return {"buf": buf,
                "keys": jnp.zeros((C,), jnp.int64),
                "counts": jnp.zeros((C,), jnp.int64),
                "buckets": jnp.zeros((C,), jnp.int64),
                "total": jnp.int64(0),
                "overflow": jnp.int64(0),
                "next_seq": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        C = self.CAP
        width = self.width
        thresh = self.support - self.error
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        khash = hash_columns([batch.cols[i] for i in self.key_idxs],
                             [batch.nulls[i] for i in self.key_idxs])

        def body(carry, xs):
            buf, keys, counts, buckets, total, ovf = carry
            is_cur, kh, ts, cols, nulls = xs

            def dead_evs():
                return {"ts": jnp.zeros((C,), jnp.int64),
                        "cols": tuple(jnp.zeros((C,), c.dtype)
                                      for c in buf["cols"]),
                        "nulls": tuple(jnp.zeros((C,), jnp.bool_)
                                       for _ in buf["nulls"]),
                        "valid": jnp.zeros((C,), jnp.bool_)}

            def process(buf, keys, counts, buckets, total, ovf):
                total = total + 1
                bucket = (total + width - 1) // width  # ceil
                found = buf["valid"] & (keys == kh)
                hit = jnp.any(found)
                slot_hit = jnp.argmax(found)
                free_ok = jnp.any(~buf["valid"])
                free = jnp.argmin(buf["valid"])
                slot = jnp.where(hit, slot_hit, free)
                admitted = hit | free_ok
                ovf = ovf + jnp.where(admitted, 0, 1)
                buf = {
                    "ts": buf["ts"].at[slot].set(
                        jnp.where(admitted, ts, buf["ts"][slot])),
                    "seq": buf["seq"],
                    "cols": tuple(c.at[slot].set(
                        jnp.where(admitted, v, c[slot]))
                        for c, v in zip(buf["cols"], cols)),
                    "nulls": tuple(n.at[slot].set(
                        jnp.where(admitted, v, n[slot]))
                        for n, v in zip(buf["nulls"], nulls)),
                    "valid": buf["valid"].at[slot].set(
                        admitted | buf["valid"][slot]),
                }
                keys = keys.at[slot].set(jnp.where(admitted, kh,
                                                   keys[slot]))
                counts = counts.at[slot].set(
                    jnp.where(hit, counts[slot] + 1,
                              jnp.where(admitted, 1, counts[slot])))
                buckets = buckets.at[slot].set(
                    jnp.where(hit, buckets[slot],
                              jnp.where(admitted, bucket - 1,
                                        buckets[slot])))
                passed = admitted & (
                    counts[slot].astype(jnp.float64) >=
                    thresh * total.astype(jnp.float64))

                prune_now = total % width == 0
                dies = buf["valid"] & (counts + buckets <= bucket) & \
                    prune_now
                evs = {"ts": buf["ts"], "cols": buf["cols"],
                       "nulls": buf["nulls"], "valid": dies}
                buf2 = dict(buf)
                buf2["valid"] = buf["valid"] & ~dies
                return (buf2, keys, counts, buckets, total, ovf), \
                    (passed, evs)

            def skip(buf, keys, counts, buckets, total, ovf):
                return (buf, keys, counts, buckets, total, ovf), \
                    (jnp.bool_(False), dead_evs())

            return jax.lax.cond(is_cur, process, skip, buf, keys, counts,
                                buckets, total, ovf)

        (buf, keys, counts, buckets, total, ovf), (passed, evs) = \
            jax.lax.scan(
                body,
                (state["buf"], state["keys"], state["counts"],
                 state["buckets"], state["total"], state["overflow"]),
                (cur, khash) + _row_slices(batch, cur)[1:])

        rows = jnp.arange(B, dtype=jnp.int64)
        now_bc = jnp.broadcast_to(now, (B, C)).astype(jnp.int64)

        def flat(x):
            return x.reshape((B * C,) + x.shape[2:])

        out = {
            "ts": jnp.concatenate([batch.ts, flat(now_bc)]),
            "cols": tuple(jnp.concatenate([bc, flat(ec)])
                          for ec, bc in zip(evs["cols"], batch.cols)),
            "nulls": tuple(jnp.concatenate([bn, flat(en)])
                           for en, bn in zip(evs["nulls"], batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((B,), CURRENT, jnp.int32),
                jnp.full((B * C,), EXPIRED, jnp.int32)]),
        }
        # reference appends the passing current first, prunes after
        emit_row = jnp.concatenate([
            rows, flat(jnp.broadcast_to(rows[:, None], (B, C)))])
        phase = jnp.concatenate([jnp.full((B,), 2, jnp.int64),
                                 jnp.full((B * C,), 3, jnp.int64)])
        oseq = jnp.concatenate([seq, jnp.zeros((B * C,), jnp.int64)])
        ev_valid = flat(evs["valid"]) if self.expired_enabled \
            else jnp.zeros((B * C,), jnp.bool_)
        valid = jnp.concatenate([passed & cur, ev_valid])
        result = emission_sort(out, emit_row, phase, oseq, valid,
                               B * C + B)
        return ({"buf": buf, "keys": keys, "counts": counts,
                 "buckets": buckets, "total": total, "overflow": ovf,
                 "next_seq": next_seq}, result)

    def findable_buffer(self, state):
        return state["buf"]


class ExternalTimeBatchWindowOp(WindowOp):
    """#window.externalTimeBatch(tsAttr, T [, startTime]): tumbling batch
    over the event-carried clock. Arrivals buffer; the first event whose
    tsAttr reaches the batch end flushes [previous batch EXPIRED
    (ts=trigger clock), RESET, buffered batch CURRENT] and starts a new
    batch (ExternalTimeBatchWindowProcessor.java:253-311; timeout and
    replace.with.batchtime parameters are not supported).

    Because the external clock is monotone, batch membership reduces to
    the window index w = (tsAttr - start) // T: a flush fires at every
    in-step change of w, which is how the step vectorizes (the
    LengthBatchWindowOp pattern with w as the batch id)."""

    kind_name = "externalTimeBatch"
    is_batch = True

    def __init__(self, schema, ts_idx: int, duration_ms: int,
                 start_time: Optional[int] = None, cap: int = 4096,
                 expired_enabled: bool = True,
                 start_attr: Optional[int] = None,
                 timeout_ms: Optional[int] = None,
                 replace_ts: bool = False):
        super().__init__(schema, expired_enabled)
        self.ts_idx = int(ts_idx)
        self.T = int(duration_ms)
        self.start_time = start_time
        self.start_attr = start_attr      # 3rd param as a variable
        self.timeout_ms = timeout_ms      # 4th param: early-flush timer
        self.replace_ts = bool(replace_ts)  # 5th param
        self.cap = int(cap)

    def init_state(self):
        return {"cur": empty_buffer(self.schema, self.cap),
                "exp": empty_buffer(self.schema, self.cap),
                "start": jnp.int64(self.start_time
                                   if self.start_time is not None else -1),
                "next_seq": jnp.int64(0),
                "flushed": jnp.bool_(False),
                "sched": jnp.int64(POS_INF),
                "last_ext": jnp.int64(0),
                "overflow": jnp.int64(0)}

    def next_due(self, state):
        if self.timeout_ms is None:
            return None
        return state["sched"]

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        W = self.cap
        T = self.T
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        ext = batch.cols[self.ts_idx].astype(jnp.int64)
        n_cur = jnp.sum(cur.astype(jnp.int64))
        cur_rows = current_row_positions(cur, B)
        first_ext = ext[cur_rows[0]]
        if self.start_attr is not None:
            # 3rd param as a variable: the FIRST event's value of that
            # attribute anchors the batch boundaries
            # (ExternalTimeBatchWindowProcessor.initTiming startTime
            # AsVariable)
            first_start = batch.cols[self.start_attr].astype(
                jnp.int64)[cur_rows[0]]
        else:
            first_start = first_ext
        start = jnp.where(state["start"] >= 0, state["start"],
                          jnp.where(n_cur > 0, first_start, jnp.int64(-1)))
        last_ext = jnp.maximum(
            state["last_ext"],
            jnp.max(jnp.where(cur, ext, jnp.int64(0))))
        is_timer = jnp.any(batch.valid & (batch.kind == TIMER))
        timer_ts = jnp.max(jnp.where(batch.valid &
                                     (batch.kind == TIMER),
                                     batch.ts, jnp.int64(0)))

        pool = make_pool(state["cur"], batch, seq, cur)
        P = W + B
        EB = W
        pool_ext = pool["cols"][self.ts_idx].astype(jnp.int64)
        w_of = jnp.where(pool["valid"],
                         (pool_ext - start) // T, jnp.int64(-1))
        emit_cols = pool["cols"]
        if self.replace_ts:
            # 5th param: EMITTED events carry the batch END boundary in
            # the timestamp attribute (cloneAppend
            # replaceTimestampWithBatchEndTime). Emission-only: the
            # pending buffer keeps the original values — the window id
            # must keep deriving from the real event clock
            end_of = start + (w_of + 1) * T
            emit_cols = tuple(
                jnp.where(pool["valid"], end_of, c).astype(c.dtype)
                if a == self.ts_idx else c
                for a, c in enumerate(pool["cols"]))
        # arrival window ids in arrival order (non-decreasing)
        warr = jnp.where(cur, (ext - start) // T, jnp.int64(2 ** 62))
        warr_sorted = warr[cur_rows]  # arrival order; padding sorts last

        # the step's first flush: first arrival whose w exceeds the carried
        # batch's window (or the first in-step group's window)
        carried_w = jnp.max(jnp.where(pool["valid"] &
                                      (jnp.arange(P) < W),
                                      w_of, jnp.int64(-2 ** 62)))
        has_carried = jnp.any(pool["valid"][:W])
        base_w = jnp.where(has_carried, carried_w, warr_sorted[0])

        def flush_a(w):
            """Arrival index of the flush that closes window w."""
            return jnp.searchsorted(warr_sorted, w, side="right")

        a1 = flush_a(w_of)                       # current-emission flush
        row1 = cur_rows[jnp.clip(a1, 0, B - 1)].astype(jnp.int64)
        w1 = warr_sorted[jnp.clip(a1, 0, B - 1)]
        a2 = flush_a(w1)                         # the flush after that
        row2 = cur_rows[jnp.clip(a2, 0, B - 1)].astype(jnp.int64)
        cur_emits = pool["valid"] & (a1 < n_cur)
        exp_emits = pool["valid"] & (a2 < n_cur)
        # clock value at a flush = the trigger's external ts
        flush_ext1 = ext[jnp.clip(row1, 0, B - 1)]
        flush_ext2 = ext[jnp.clip(row2, 0, B - 1)]

        # carried previous batch (exp buffer) expires at the step's first
        # flush
        first_flush_a = flush_a(base_w)
        any_flush = first_flush_a < n_cur
        first_flush_row = cur_rows[jnp.clip(first_flush_a, 0, B - 1)] \
            .astype(jnp.int64)
        first_flush_ext = ext[jnp.clip(first_flush_row, 0, B - 1)]

        # RESET per flush: the flushing batch's FIRST event. Pool rows are
        # in seq order (buffer then arrivals) and w is monotone in seq, so
        # group-first = w differs from the previous valid row's w
        pidx = jnp.where(pool["valid"], jnp.arange(P), -1)
        prev_idx = jnp.concatenate([jnp.full((1,), -1),
                                    cummax_fast(pidx)[:-1]])
        prev_w = jnp.where(prev_idx >= 0, w_of[jnp.clip(prev_idx, 0)],
                           jnp.int64(-2 ** 62))
        grp_first = pool["valid"] & (w_of != prev_w)

        # timeout early-flush (4th param): a timer at/after the scheduled
        # deadline flushes the pending batch without closing its window
        # (ExternalTimeBatchWindowProcessor.process TIMER branch :258-276)
        has_timeout = self.timeout_ms is not None
        early = jnp.bool_(False)
        if has_timeout:
            early = is_timer & (state["sched"] < POS_INF) & \
                (timer_ts >= state["sched"])
        flushed0 = state["flushed"]
        any_pool = jnp.any(pool["valid"])

        exp_exp_valid = state["exp"]["valid"] & (
            any_flush | (early & (~flushed0 | any_pool)))
        if not self.expired_enabled:
            exp_exp_valid = jnp.zeros((EB,), jnp.bool_)
        # after an early flush, the batch close RE-EMITS the flushed
        # events as CURRENT ahead of the new ones (appendToOutputChunk
        # sentEventChunk)
        re_cur_valid = state["exp"]["valid"] & flushed0 & (
            any_flush | (early & any_pool))
        pool_cur_valid = cur_emits | (pool["valid"] & early)
        reset_valid = (cur_emits & grp_first) | (early & grp_first)
        flush_ts = jnp.where(early, last_ext, first_flush_ext)

        now_exp = jnp.broadcast_to(flush_ts, (EB,))
        out = {
            "ts": jnp.concatenate([
                now_exp, now_exp, pool["ts"],
                jnp.where(early, last_ext, flush_ext1)]),
            "cols": tuple(jnp.concatenate([ec, ec, pc, pc])
                          for ec, pc in zip(state["exp"]["cols"],
                                            emit_cols)),
            "nulls": tuple(jnp.concatenate([en, en, pn, pn])
                           for en, pn in zip(state["exp"]["nulls"],
                                             pool["nulls"])),
            "kind": jnp.concatenate([
                jnp.full((EB,), EXPIRED, jnp.int32),
                jnp.full((EB,), CURRENT, jnp.int32),
                jnp.full((P,), CURRENT, jnp.int32),
                jnp.full((P,), RESET, jnp.int32)]),
        }
        # in-step expired re-emission of flushed groups
        out = {
            "ts": jnp.concatenate([out["ts"], flush_ext2]),
            "cols": tuple(jnp.concatenate([oc, pc])
                          for oc, pc in zip(out["cols"], emit_cols)),
            "nulls": tuple(jnp.concatenate([on, pn])
                           for on, pn in zip(out["nulls"], pool["nulls"])),
            "kind": jnp.concatenate([out["kind"],
                                     jnp.full((P,), EXPIRED, jnp.int32)]),
        }
        emit_row = jnp.concatenate([
            jnp.broadcast_to(first_flush_row, (EB,)),
            jnp.broadcast_to(first_flush_row, (EB,)),
            jnp.where(cur_emits, row1, 0),
            jnp.where(cur_emits & grp_first, row1, 0),
            jnp.where(exp_emits, row2, 0)])
        phase = jnp.concatenate([
            jnp.zeros((EB,), jnp.int64),
            jnp.full((EB,), 2, jnp.int64),
            jnp.full((P,), 2, jnp.int64),
            jnp.ones((P,), jnp.int64),
            jnp.zeros((P,), jnp.int64)])
        oseq = jnp.concatenate([state["exp"]["seq"], state["exp"]["seq"],
                                pool["seq"], pool["seq"], pool["seq"]])
        if self.expired_enabled:
            exp_pool_valid = exp_emits
        else:
            exp_pool_valid = jnp.zeros((P,), jnp.bool_)
        valid = jnp.concatenate([exp_exp_valid, re_cur_valid,
                                 pool_cur_valid, reset_valid,
                                 exp_pool_valid])
        result = emission_sort(out, emit_row, phase, oseq, valid,
                               2 * EB + 3 * P)

        # next buffers: pending = newest un-flushed window; exp = the last
        # flushed window's rows (merged with the earlier early-flushed set
        # while the same batch window stays open)
        pending = pool["valid"] & ~cur_emits & ~early
        new_cur, overflow = keep_newest(pool, pending, W, presorted=True)
        last_flushed = pool["valid"] & cur_emits & (
            w_of == jnp.max(jnp.where(cur_emits, w_of,
                                      jnp.int64(-2 ** 62))))
        flush_set = jnp.where(early, pool["valid"], last_flushed)
        big = {
            "cols": tuple(jnp.concatenate([ec, pc])
                          for ec, pc in zip(state["exp"]["cols"],
                                            emit_cols)),
            "nulls": tuple(jnp.concatenate([en, pn])
                           for en, pn in zip(state["exp"]["nulls"],
                                             pool["nulls"])),
            "ts": jnp.concatenate([state["exp"]["ts"], pool["ts"]]),
            "seq": jnp.concatenate([state["exp"]["seq"], pool["seq"]]),
            "valid": jnp.concatenate([state["exp"]["valid"],
                                      pool["valid"]]),
        }
        # early-flushed rows stay in exp until a real boundary flush of a
        # LATER batch replaces them (append semantics keep accumulating)
        keep_exp_old = jnp.broadcast_to(flushed0, (EB,)) & \
            state["exp"]["valid"]
        big_mask = jnp.concatenate([keep_exp_old, flush_set])
        new_exp_m, _ = keep_newest(big, big_mask, W, presorted=True)
        did_flush = any_flush | (early & (~flushed0 | any_pool))
        new_exp = jax.tree_util.tree_map(
            lambda a_, b_: jnp.where(did_flush, a_, b_), new_exp_m,
            state["exp"])

        flushed1 = jnp.where(early, True,
                             jnp.where(any_flush, False, flushed0))
        sched = state["sched"]
        if has_timeout:
            trigger = early | any_flush | (
                (state["sched"] >= POS_INF) & (n_cur > 0))
            sched = jnp.where(
                trigger,
                jnp.asarray(now, jnp.int64) + self.timeout_ms, sched)
        return ({"cur": new_cur, "exp": new_exp, "start": start,
                 "next_seq": next_seq, "flushed": flushed1,
                 "sched": sched, "last_ext": last_ext,
                 "overflow": state["overflow"] + overflow}, result)

    def findable_buffer(self, state):
        return state["exp"]


def _sorted_by_slot(slots, valid, B):
    """Stable order grouping rows by slot (invalid rows last). Returns
    (order, inv) with inv[order[i]] = i."""
    key = jnp.where(valid, slots.astype(jnp.int32), I32_MAX)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    return order, inv


class SessionWindowOp(WindowOp):
    """#window.session(gap [, keyAttr]): per-key sessions. Arrivals pass
    through as CURRENT and accumulate in their key's open session; a
    session whose gap elapses (by event/timer clock) emits its members as
    EXPIRED, in order, at the close point
    (SessionWindowProcessor.java:227-310 + currentSessionTimeout :437-470;
    allowedLatency is not supported).

    Vectorized design: rows group by (key slot, in-step session id) where a
    new session starts whenever an arrival's ts reaches the previous
    member's ts + gap. A session's close row is searchsorted(running
    clock, last_member_ts + gap) — non-final sessions always close within
    the step, the final one carries with a timer at its close ts. Keys are
    a bounded slot table; members beyond the per-key capacity and keys
    beyond the table are dropped AND counted."""

    kind_name = "session"
    K = 64   # key slots
    S = 128  # members per open session

    def __init__(self, schema, gap_ms: int, key_idx: Optional[int] = None,
                 expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        self.gap = int(gap_ms)
        self.key_idx = key_idx

    def init_state(self):
        K, S = self.K, self.S
        return {
            "keys": jnp.zeros((K,), jnp.int64),
            "used": jnp.zeros((K,), jnp.bool_),
            "buf": {
                "ts": jnp.zeros((K, S), jnp.int64),
                "cols": tuple(jnp.zeros((K, S), np_dtype(t))
                              for t in self.schema.types),
                "nulls": tuple(jnp.zeros((K, S), jnp.bool_)
                               for _ in self.schema.types),
                "valid": jnp.zeros((K, S), jnp.bool_),
            },
            "count": jnp.zeros((K,), jnp.int64),
            "end": jnp.full((K,), POS_INF, jnp.int64),  # open session end
            "open": jnp.zeros((K,), jnp.bool_),
            "next_seq": jnp.int64(0),
            "overflow": jnp.int64(0),
        }

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        K, S = self.K, self.S
        gap = self.gap
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        rt = running_time(batch)
        rt_max = rt[B - 1]

        if self.key_idx is not None:
            khash = hash_columns([batch.cols[self.key_idx]],
                                 [batch.nulls[self.key_idx]])
        else:
            khash = jnp.zeros((B,), jnp.int64)
        slots, keys, used, kovf, _ = lookup_or_insert(
            state["keys"], state["used"], khash, cur)
        routed = cur & (slots >= 0)

        # --- group rows by slot, stable (slot runs keep arrival order) ---
        order, inv = _sorted_by_slot(slots, routed, B)
        s_slot = jnp.where(routed, slots, jnp.int32(-1))[order]
        s_ts = batch.ts[order]
        s_valid = routed[order]
        same_prev = jnp.concatenate([
            jnp.zeros((1,), jnp.bool_),
            (s_slot[1:] == s_slot[:-1]) & s_valid[1:] & s_valid[:-1]])
        prev_ts = jnp.concatenate([jnp.zeros((1,), jnp.int64), s_ts[:-1]])
        carried_end = state["end"][jnp.clip(s_slot, 0, K - 1)]
        carried_open = state["open"][jnp.clip(s_slot, 0, K - 1)]
        # boundary: first-in-slot rows continue the carried session only if
        # one is open and not yet elapsed; later rows compare to the
        # previous member's ts + gap
        boundary = s_valid & jnp.where(
            same_prev, s_ts >= prev_ts + gap,
            ~carried_open | (s_ts >= carried_end))
        slot_first = s_valid & ~same_prev
        # in-slot session index (0 = the slot's first in-step session)
        grp_break = slot_first | boundary
        sid = segmented_cumsum(grp_break.astype(jnp.int64), s_slot) - 1
        # does the slot's first in-step session extend the carried one?
        fidx = cummax_fast(jnp.where(slot_first, jnp.arange(B), -1))
        first_cont = slot_first & ~boundary
        cont = first_cont[jnp.clip(fidx, 0)] & (fidx >= 0)
        joins_carried = s_valid & (sid == 0) & cont
        # a session's close_ts = its LAST member's ts + gap: propagate the
        # segment-last ts backward (reverse cummax over segment ends)
        seg_key = s_slot.astype(jnp.int64) * (B + 1) + sid
        is_last = jnp.concatenate([
            seg_key[:-1] != seg_key[1:],
            jnp.ones((1,), jnp.bool_)]) & s_valid
        last_ts_rev = cummax_fast(
            jnp.where(is_last, s_ts, NEG_INF)[::-1])[::-1]
        close_ts_sorted = jnp.where(s_valid, last_ts_rev + gap, POS_INF)
        closes_sorted = close_ts_sorted <= rt_max
        close_row_sorted = jnp.searchsorted(rt, close_ts_sorted,
                                            side="left")

        # scatter back to row order
        close_ts = close_ts_sorted[inv]
        closes = closes_sorted[inv] & routed
        close_row = jnp.clip(close_row_sorted[inv], 0, B - 1)
        row_sid = jnp.where(routed, sid[inv], jnp.int64(-1))
        row_joins_carried = joins_carried[inv] & routed

        # --- carried sessions: extended close or standalone timeout ------
        # per-slot: does any batch row extend the carried session?
        ext_close_ts = jax.ops.segment_max(
            jnp.where(row_joins_carried, close_ts, NEG_INF),
            jnp.clip(slots, 0, K - 1).astype(jnp.int32), num_segments=K)
        has_ext = jax.ops.segment_max(
            row_joins_carried.astype(jnp.int32),
            jnp.clip(slots, 0, K - 1).astype(jnp.int32),
            num_segments=K) > 0
        slot_close_ts = jnp.where(has_ext, ext_close_ts, state["end"])
        slot_closes = state["open"] & (slot_close_ts <= rt_max)
        slot_close_row = jnp.clip(
            jnp.searchsorted(rt, slot_close_ts, side="left"), 0, B - 1)

        # --- emissions ----------------------------------------------------
        # carried members [K, S] close with their slot
        buf = state["buf"]
        c_emit_row = jnp.broadcast_to(slot_close_row[:, None], (K, S))
        c_valid = buf["valid"] & jnp.broadcast_to(slot_closes[:, None],
                                                  (K, S))
        # batch members whose session closes this step
        b_exp_valid = closes & jnp.where(row_joins_carried,
                                         slot_closes[
                                             jnp.clip(slots, 0, K - 1)],
                                         True)

        def flat(x):
            return x.reshape((K * S,) + x.shape[2:])

        out = {
            "ts": jnp.concatenate([flat(buf["ts"]), batch.ts, batch.ts]),
            "cols": tuple(jnp.concatenate([flat(c), bc, bc])
                          for c, bc in zip(buf["cols"], batch.cols)),
            "nulls": tuple(jnp.concatenate([flat(n), bn, bn])
                           for n, bn in zip(buf["nulls"], batch.nulls)),
            "kind": jnp.concatenate([
                jnp.full((K * S,), EXPIRED, jnp.int32),
                jnp.full((B,), EXPIRED, jnp.int32),
                jnp.full((B,), CURRENT, jnp.int32)]),
        }
        rows = jnp.arange(B, dtype=jnp.int64)
        emit_row = jnp.concatenate([
            flat(c_emit_row).astype(jnp.int64),
            jnp.where(b_exp_valid, close_row, 0).astype(jnp.int64),
            rows])
        phase = jnp.concatenate([
            jnp.zeros((K * S,), jnp.int64),
            jnp.zeros((B,), jnp.int64),
            jnp.full((B,), 2, jnp.int64)])
        oseq = jnp.concatenate([jnp.zeros((K * S,), jnp.int64), seq, seq])
        if self.expired_enabled:
            exp_c, exp_b = flat(c_valid), b_exp_valid
        else:
            exp_c = jnp.zeros((K * S,), jnp.bool_)
            exp_b = jnp.zeros((B,), jnp.bool_)
        valid = jnp.concatenate([exp_c, exp_b, routed])
        result = emission_sort(out, emit_row, phase, oseq, valid,
                               K * S + 2 * B)

        # --- new state ----------------------------------------------------
        # per slot: the final in-step session (or the surviving carried
        # one) stays open if it did not close
        final_sid = jax.ops.segment_max(
            jnp.where(routed, row_sid, jnp.int64(-1)),
            jnp.clip(slots, 0, K - 1).astype(jnp.int32), num_segments=K)
        keep_carried = state["open"] & ~slot_closes
        # rows that remain buffered: members of their slot's final session
        # when that session did not close
        row_close = closes
        stays = routed & ~row_close & (row_sid == final_sid[
            jnp.clip(slots, 0, K - 1)])
        base = jnp.where(keep_carried, state["count"], 0)
        # rank among staying rows of the same slot, in arrival order
        s_stays = stays[order]
        s_rank = segmented_cumsum(s_stays.astype(jnp.int64), s_slot)
        row_rank = s_rank[inv] - 1
        pos = base[jnp.clip(slots, 0, K - 1)] + row_rank
        in_cap = stays & (pos < S)
        member_ovf = jnp.sum((stays & ~in_cap).astype(jnp.int64))
        sk = jnp.where(in_cap, slots.astype(jnp.int32), 0)
        sp = jnp.where(in_cap, pos.astype(jnp.int32), 0)

        def scatter2(tgt, vals):
            return tgt.at[sk, sp].set(
                jnp.where(in_cap, vals, tgt[sk, sp]))

        cleared = {
            "ts": jnp.where(keep_carried[:, None], buf["ts"], 0),
            "cols": tuple(jnp.where(keep_carried[:, None], c, 0)
                          for c in buf["cols"]),
            "nulls": tuple(jnp.where(keep_carried[:, None], n, False)
                           for n in buf["nulls"]),
            "valid": jnp.where(keep_carried[:, None], buf["valid"], False),
        }
        new_buf = {
            "ts": scatter2(cleared["ts"], batch.ts),
            "cols": tuple(scatter2(c, bc)
                          for c, bc in zip(cleared["cols"], batch.cols)),
            "nulls": tuple(scatter2(n, bn)
                           for n, bn in zip(cleared["nulls"],
                                            batch.nulls)),
            "valid": scatter2(cleared["valid"],
                              jnp.ones((B,), jnp.bool_)),
        }
        new_count = jnp.minimum(
            base + jax.ops.segment_sum(
                stays.astype(jnp.int64),
                jnp.clip(slots, 0, K - 1).astype(jnp.int32),
                num_segments=K), S)
        stay_end = jax.ops.segment_max(
            jnp.where(stays, close_ts, NEG_INF),
            jnp.clip(slots, 0, K - 1).astype(jnp.int32), num_segments=K)
        new_open = keep_carried | (jax.ops.segment_max(
            stays.astype(jnp.int32),
            jnp.clip(slots, 0, K - 1).astype(jnp.int32),
            num_segments=K) > 0)
        new_end = jnp.where(stay_end > NEG_INF, stay_end,
                            jnp.where(keep_carried, state["end"],
                                      POS_INF))
        new_open = new_open & (new_end < POS_INF)

        overflow = state["overflow"] + kovf + member_ovf
        return ({"keys": keys, "used": used, "buf": new_buf,
                 "count": new_count, "end": new_end, "open": new_open,
                 "next_seq": next_seq, "overflow": overflow}, result)

    def next_due(self, state):
        return jnp.min(jnp.where(state["open"], state["end"], POS_INF))

    def host_due_bound(self, ts_min: int) -> int:
        return ts_min + self.gap


class CronWindowOp(WindowOp):
    """#window.cron('expr'): buffer arrivals; each cron firing (delivered
    as a TIMER batch by the host cron schedule) emits
    [previous batch EXPIRED (ts=now), buffered batch CURRENT] and rotates
    the buffers — nothing is emitted when the buffer is empty
    (CronWindowProcessor.java:125-135 buffers, :188-236 dispatches; the
    Quartz scheduler is replaced by utils/cron.py + the app Scheduler)."""

    kind_name = "cron"

    def __init__(self, schema, cron_expr: str, cap: int = 4096,
                 expired_enabled: bool = True):
        from ..utils.cron import CronSchedule
        super().__init__(schema, expired_enabled)
        self.schedule = CronSchedule(cron_expr)
        self.cap = int(cap)

    @property
    def host_schedule(self):
        """Host-side next-fire computer (the runtime arms app timers from
        this instead of a device next_due)."""
        return self.schedule.next_fire

    def init_state(self):
        return {"cur": empty_buffer(self.schema, self.cap),
                "exp": empty_buffer(self.schema, self.cap),
                "next_seq": jnp.int64(0),
                "overflow": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        W = self.cap
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        fire = jnp.any(batch.valid & (batch.kind == TIMER))
        has_pending = jnp.any(state["cur"]["valid"])
        flush = fire & has_pending

        EB = W
        now_exp = jnp.broadcast_to(now, (EB,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([now_exp, state["cur"]["ts"]]),
            "cols": tuple(jnp.concatenate([ec, cc])
                          for ec, cc in zip(state["exp"]["cols"],
                                            state["cur"]["cols"])),
            "nulls": tuple(jnp.concatenate([en, cn])
                           for en, cn in zip(state["exp"]["nulls"],
                                             state["cur"]["nulls"])),
            "kind": jnp.concatenate([
                jnp.full((EB,), EXPIRED, jnp.int32),
                jnp.full((W,), CURRENT, jnp.int32)]),
        }
        emit_row = jnp.zeros((EB + W,), jnp.int64)
        phase = jnp.concatenate([jnp.zeros((EB,), jnp.int64),
                                 jnp.ones((W,), jnp.int64)])
        oseq = jnp.concatenate([state["exp"]["seq"], state["cur"]["seq"]])
        exp_valid = (state["exp"]["valid"] & flush) if self.expired_enabled \
            else jnp.zeros((EB,), jnp.bool_)
        valid = jnp.concatenate([exp_valid, state["cur"]["valid"] & flush])
        result = emission_sort(out, emit_row, phase, oseq, valid, EB + W)

        # rotate on flush, then append this step's arrivals to cur
        mid_cur = jax.tree_util.tree_map(
            lambda a, b: jnp.where(flush, a, b),
            empty_buffer(self.schema, W), state["cur"])
        new_exp = jax.tree_util.tree_map(
            lambda a, b: jnp.where(flush, a, b), state["cur"],
            state["exp"])
        pool = make_pool(mid_cur, batch, seq, cur)
        new_cur, overflow = keep_newest(pool, pool["valid"], W,
                                        presorted=True)
        return ({"cur": new_cur, "exp": new_exp, "next_seq": next_seq,
                 "overflow": state["overflow"] + overflow}, result)

    def findable_buffer(self, state):
        return state["exp"]


class EmptyWindowOp(WindowOp):
    """The default window inserted on a join side declared without one
    (JoinInputStreamParser.java:416, EmptyWindowProcessor): currents pass
    through (followed by an immediate EXPIRED clone, ts=now, when expired
    output is expected) and nothing is retained — the side triggers the
    cross but contributes no findable content."""

    kind_name = "empty"

    def __init__(self, schema, expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)

    def init_state(self):
        return ()

    def step(self, state, batch: EventBatch, now):
        cur = batch.valid & (batch.kind == CURRENT)
        if not self.expired_enabled:
            return state, batch.mask(cur)
        B = batch.capacity
        now_col = jnp.broadcast_to(
            jnp.asarray(now, jnp.int64), (B,))
        out = {
            "ts": jnp.concatenate([batch.ts, now_col]),
            "cols": tuple(jnp.concatenate([c, c]) for c in batch.cols),
            "nulls": tuple(jnp.concatenate([n, n]) for n in batch.nulls),
            "kind": jnp.concatenate([
                jnp.full((B,), CURRENT, jnp.int32),
                jnp.full((B,), EXPIRED, jnp.int32)]),
        }
        rows = jnp.arange(B, dtype=jnp.int64)
        emit_row = jnp.concatenate([rows, rows])
        phase = jnp.concatenate([jnp.full((B,), 2, jnp.int64),
                                 jnp.full((B,), 3, jnp.int64)])
        seq = jnp.concatenate([rows, rows])
        valid = jnp.concatenate([cur, cur])
        return state, emission_sort(out, emit_row, phase, seq, valid,
                                    2 * B)

    def findable_buffer(self, state):
        return empty_buffer(self.schema, 1)


class HoppingWindowOp(WindowOp):
    """#window.hopping(windowTime, hopTime): overlapping tumbling windows.
    Every hopTime the retained last-windowTime of events flushes as one
    CURRENT batch (events re-emit in every hop whose span covers them).

    Reference note: HopingWindowProcessor.java:48 is an ABSTRACT extension
    base (no concrete in-core subclass, no tests) that stamps a
    `_hopingTimestamp` group key per hop; this op is the concrete
    columnar equivalent — the hop boundary plays the group-key role, and
    one flush per step carries all events of the closing hop span.
    """

    kind_name = "hopping"
    is_batch = True
    # hop boundaries coalesce if past dues are skipped — this op flushes
    # one hop per step and relies on timer catch-up (see runtime._schedule)
    needs_catchup = True

    def __init__(self, schema, window_ms: int, hop_ms: int,
                 cap: int = 4096, expired_enabled: bool = True):
        super().__init__(schema, expired_enabled)
        if hop_ms <= 0 or window_ms <= 0:
            raise CompileError("hopping window needs positive durations")
        self.W_ms = int(window_ms)
        self.H_ms = int(hop_ms)
        self.cap = int(cap)

    def init_state(self):
        return {"buf": empty_buffer(self.schema, self.cap),
                "exp": empty_buffer(self.schema, self.cap),
                "next_seq": jnp.int64(0),
                "next_hop": jnp.int64(-1),
                "overflow": jnp.int64(0)}

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        W = self.cap
        now = jnp.asarray(now, dtype=jnp.int64)
        cur, seq, next_seq = arrival_seqs(batch, state["next_seq"])
        pool = make_pool(state["buf"], batch, seq, cur)
        P = W + B
        EB = W

        next_hop = jnp.where(state["next_hop"] == -1, now + self.H_ms,
                             state["next_hop"])
        send = now >= next_hop
        hop_at = next_hop
        next_hop = jnp.where(send, next_hop + self.H_ms, next_hop)

        # the closing hop covers (hop_at - windowTime, hop_at]
        in_span = pool["valid"] & (pool["ts"] > hop_at - self.W_ms) & \
            (pool["ts"] <= hop_at)
        flushed = in_span & send

        now_exp = jnp.broadcast_to(now, (EB,)).astype(jnp.int64)
        out = {
            "ts": jnp.concatenate([now_exp, pool["ts"]]),
            "cols": tuple(jnp.concatenate([ec, pc]) for ec, pc in
                          zip(state["exp"]["cols"], pool["cols"])),
            "nulls": tuple(jnp.concatenate([en, pn]) for en, pn in
                           zip(state["exp"]["nulls"], pool["nulls"])),
            "kind": jnp.concatenate([
                jnp.full((EB,), EXPIRED, jnp.int32),
                jnp.full((P,), CURRENT, jnp.int32)]),
        }
        emit_row = jnp.zeros((EB + P,), jnp.int64)
        phase = jnp.concatenate([jnp.zeros((EB,), jnp.int64),
                                 jnp.full((P,), 2, jnp.int64)])
        oseq = jnp.concatenate([state["exp"]["seq"], pool["seq"]])
        exp_valid = (state["exp"]["valid"] & send) if self.expired_enabled \
            else jnp.zeros((EB,), jnp.bool_)
        valid = jnp.concatenate([exp_valid, flushed])
        result = emission_sort(out, emit_row, phase, oseq, valid, EB + P)

        # retain rows still inside ANY future hop (ts > next closing
        # span's low edge); on send the flushed batch becomes the next
        # expired set
        keep = pool["valid"] & (pool["ts"] > next_hop - self.W_ms)
        new_buf, overflow = keep_newest(
            pool, jnp.where(send, keep, pool["valid"]), W, presorted=True)
        new_exp_f, _ = keep_newest(pool, flushed, W, presorted=True)
        new_exp = jax.tree_util.tree_map(
            lambda a, b: jnp.where(send, a, b), new_exp_f, state["exp"])
        return ({"buf": new_buf, "exp": new_exp, "next_seq": next_seq,
                 "next_hop": next_hop,
                 "overflow": state["overflow"] + overflow}, result)

    def next_due(self, state):
        nh = state["next_hop"]
        return jnp.where(nh == -1, POS_INF, nh)

    def findable_buffer(self, state):
        return state["exp"]

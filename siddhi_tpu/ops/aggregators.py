"""Attribute aggregators + the aggregating selector operator.

Reference mapping:
- query/selector/attribute/aggregator/*.java (sum, avg, count, min, max,
  minForever, maxForever, stdDev, and, or, distinctCount) — per-event state
  machines with processAdd / processRemove / reset driven by event type
  (AttributeAggregatorExecutor.java:95-150).
- query/selector/QuerySelector.java:44 — processNoGroupBy / processGroupBy
  (per-event emission) and processInBatchNoGroupBy / processInBatchGroupBy
  (batch windows: only the last event / last event per group is emitted).
- RESET clears ALL group states (AttributeAggregatorExecutor.processReset ->
  StateHolder.cleanGroupByStates, PartitionStateHolder.java:95).

TPU design: an aggregator is a set of LANES, each an accumulator with an
associative combine (sum / min / max). A batch is processed as:

  1. per-row signed lane contributions (CURRENT adds, EXPIRED removes for
     sum lanes; null contributes identity),
  2. rows sorted by (group slot, reset segment), where the reset segment id
     is the count of RESET rows at-or-before the row (RESET is global),
  3. segmented prefix scan per lane + carry-in from persistent [K] state,
  4. unsort -> per-row running aggregate values (exactly the per-event
     values the reference's tree-walk produces), project, gate, emit.

Aggregators whose state cannot be a pure accumulator run as STATEFUL
specs with bounded device tables: distinctCount keeps a (group, value)
multiplicity table whose 0<->1 transitions feed an ordinary sum lane, and
sliding min()/max() keeps per-key value rings answered by vectorized
segment-tree range queries (FIFO window expiry makes a key's live
multiset a contiguous ring range).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..analysis.schema import aggregator_result_type
from ..core.event import (CURRENT, EXPIRED, RESET, Attribute, EventBatch,
                          StreamSchema)
from ..core.types import AttrType, NUMERIC_TYPES, np_dtype, promote
from ..lang import ast as A
from .expr import (Col, CompileError, CompiledExpr, Scope, compile_expression,
                   env_from_batch)
from .keyed import (cummax_fast, cumsum_fast, hash_columns, lookup_or_insert,
                    segmented_cummax, segmented_cummin, segmented_cumsum)
from .operators import Operator
from .selector import (AGGREGATOR_NAMES, compile_order_by, const_int,
                       output_attribute_name, shape_output)

from .sentinels import POS_INF as I64_MAX  # noqa: N811


# ---------------------------------------------------------------------------
# lane + aggregator specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Lane:
    op: str            # 'sum' | 'min' | 'max'
    dtype: object      # numpy dtype for the accumulator

    def identity(self):
        if self.op == "sum":
            return jnp.zeros((), dtype=self.dtype)
        if jnp.issubdtype(jnp.dtype(self.dtype), jnp.floating):
            return jnp.asarray(jnp.inf if self.op == "min" else -jnp.inf,
                               dtype=self.dtype)
        info = jnp.iinfo(jnp.dtype(self.dtype))
        return jnp.asarray(info.max if self.op == "min" else info.min,
                           dtype=self.dtype)

    def combine(self, a, b):
        if self.op == "sum":
            return a + b
        return jnp.minimum(a, b) if self.op == "min" else jnp.maximum(a, b)

    def segmented_scan(self, vals, seg_ids):
        if self.op == "sum":
            return segmented_cumsum(vals, seg_ids)
        if self.op == "min":
            return segmented_cummin(vals, seg_ids)
        return segmented_cummax(vals, seg_ids)


class AggSpec:
    """One aggregator call instance inside a select clause."""

    name: str
    out_type: AttrType
    lanes: tuple

    def contribs(self, arg: Optional[Col], is_add, is_remove):
        """Per-lane [B] contribution arrays (identity where no effect)."""
        raise NotImplementedError

    def value(self, lane_vals) -> Col:
        """Aggregate value from running lane values."""
        raise NotImplementedError


def _signed(x, is_add, is_remove, dtype):
    x = x.astype(dtype)
    return jnp.where(is_add, x, jnp.where(is_remove, -x, jnp.zeros_like(x)))


class SumAgg(AggSpec):
    """sum(): (sum, count) per key; null when count==0
    (SumAttributeAggregatorExecutor.AggregatorStateDouble:183-227)."""

    def __init__(self, arg_type: AttrType):
        if arg_type not in NUMERIC_TYPES:
            raise CompileError(f"sum() requires numeric input, got {arg_type}")
        self.name = "sum"
        # shared result-typing rule (analysis/schema.py): LONG for
        # integral inputs, DOUBLE for floating — mirrored statically
        self.out_type = aggregator_result_type("sum", arg_type)
        self.acc_dtype = np_dtype(self.out_type)
        self.lanes = (Lane("sum", self.acc_dtype), Lane("sum", jnp.int64))

    def contribs(self, arg, is_add, is_remove):
        eff = (is_add | is_remove) & ~arg.nulls
        x = jnp.where(eff, arg.values.astype(self.acc_dtype), 0)
        one = jnp.where(eff, jnp.int64(1), jnp.int64(0))
        return (_signed(x, is_add, is_remove, self.acc_dtype) * eff,
                _signed(one, is_add, is_remove, jnp.int64))

    def value(self, lane_vals):
        s, cnt = lane_vals
        return Col(jnp.where(cnt == 0, jnp.zeros_like(s), s), cnt == 0)


class AvgAgg(AggSpec):
    """avg(): sum/count as DOUBLE; null when count==0."""

    def __init__(self, arg_type: AttrType):
        if arg_type not in NUMERIC_TYPES:
            raise CompileError(f"avg() requires numeric input, got {arg_type}")
        self.name = "avg"
        self.out_type = aggregator_result_type("avg", arg_type)
        self.lanes = (Lane("sum", jnp.float64), Lane("sum", jnp.int64))

    def contribs(self, arg, is_add, is_remove):
        eff = (is_add | is_remove) & ~arg.nulls
        x = jnp.where(eff, arg.values.astype(jnp.float64), 0.0)
        one = jnp.where(eff, jnp.int64(1), jnp.int64(0))
        return (_signed(x, is_add, is_remove, jnp.float64),
                _signed(one, is_add, is_remove, jnp.int64))

    def value(self, lane_vals):
        s, cnt = lane_vals
        safe = jnp.maximum(cnt, 1)
        return Col(jnp.where(cnt == 0, 0.0, s / safe), cnt == 0)


class CountAgg(AggSpec):
    """count(): event count, LONG, never null
    (CountAttributeAggregatorExecutor)."""

    def __init__(self):
        self.name = "count"
        self.out_type = aggregator_result_type("count", None)
        self.lanes = (Lane("sum", jnp.int64),)

    def contribs(self, arg, is_add, is_remove):
        one = jnp.where(is_add | is_remove, jnp.int64(1), jnp.int64(0))
        return (_signed(one, is_add, is_remove, jnp.int64),)

    def value(self, lane_vals):
        (cnt,) = lane_vals
        return Col(cnt, jnp.zeros_like(cnt, dtype=jnp.bool_))


class StdDevAgg(AggSpec):
    """stdDev(): population standard deviation from (sum, sumsq, count)
    (StdDevAttributeAggregatorExecutor: std = sqrt(E[x^2] - mean^2));
    null when count==0."""

    def __init__(self, arg_type: AttrType):
        if arg_type not in NUMERIC_TYPES:
            raise CompileError(
                f"stdDev() requires numeric input, got {arg_type}")
        self.name = "stdDev"
        self.out_type = aggregator_result_type("stddev", arg_type)
        self.lanes = (Lane("sum", jnp.float64), Lane("sum", jnp.float64),
                      Lane("sum", jnp.int64))

    def contribs(self, arg, is_add, is_remove):
        eff = (is_add | is_remove) & ~arg.nulls
        x = jnp.where(eff, arg.values.astype(jnp.float64), 0.0)
        one = jnp.where(eff, jnp.int64(1), jnp.int64(0))
        return (_signed(x, is_add, is_remove, jnp.float64),
                _signed(x * x, is_add, is_remove, jnp.float64),
                _signed(one, is_add, is_remove, jnp.int64))

    def value(self, lane_vals):
        s, ss, cnt = lane_vals
        n = jnp.maximum(cnt, 1).astype(jnp.float64)
        mean = s / n
        var = jnp.maximum(ss / n - mean * mean, 0.0)
        return Col(jnp.where(cnt == 0, 0.0, jnp.sqrt(var)), cnt == 0)


class MinMaxAgg(AggSpec):
    """min()/max() without expiring content (monotonic running extreme +
    RESET segmentation). The sliding-window variant (processRemove over a
    Deque, MinAttributeAggregatorExecutor) needs the multiset path — planner
    rejects it for now."""

    def __init__(self, arg_type: AttrType, is_max: bool):
        if arg_type not in NUMERIC_TYPES:
            raise CompileError("min()/max() requires numeric input")
        self.name = "max" if is_max else "min"
        self.out_type = aggregator_result_type(self.name, arg_type)
        self.dtype = np_dtype(arg_type)
        self.lanes = (Lane("max" if is_max else "min", self.dtype),
                      Lane("sum", jnp.int64))

    def contribs(self, arg, is_add, is_remove):
        lane = self.lanes[0]
        eff = is_add & ~arg.nulls
        x = jnp.where(eff, arg.values.astype(self.dtype), lane.identity())
        one = jnp.where(eff, jnp.int64(1), jnp.int64(0))
        return (x, one)

    def value(self, lane_vals):
        m, cnt = lane_vals
        return Col(jnp.where(cnt == 0, jnp.zeros_like(m), m), cnt == 0)


class ForeverMinMaxAgg(MinMaxAgg):
    """minForever()/maxForever(): extreme over every event ever seen —
    EXPIRED events also tighten the extreme
    (MinForeverAttributeAggregatorExecutor.processRemove also does min)."""

    def __init__(self, arg_type: AttrType, is_max: bool):
        super().__init__(arg_type, is_max)
        self.name = "maxForever" if is_max else "minForever"

    def contribs(self, arg, is_add, is_remove):
        lane = self.lanes[0]
        eff = (is_add | is_remove) & ~arg.nulls
        x = jnp.where(eff, arg.values.astype(self.dtype), lane.identity())
        one = jnp.where(eff, jnp.int64(1), jnp.int64(0))
        return (x, one)


class BoolAgg(AggSpec):
    """and()/or() over BOOL: counts of true/false values
    (AndAttributeAggregatorExecutor keeps counts so removes work)."""

    def __init__(self, arg_type: AttrType, is_and: bool):
        if arg_type is not AttrType.BOOL:
            raise CompileError("and()/or() requires BOOL input")
        self.name = "and" if is_and else "or"
        self.is_and = is_and
        self.out_type = aggregator_result_type(self.name, arg_type)
        self.lanes = (Lane("sum", jnp.int64), Lane("sum", jnp.int64))

    def contribs(self, arg, is_add, is_remove):
        eff = (is_add | is_remove) & ~arg.nulls
        t = jnp.where(eff & arg.values, jnp.int64(1), jnp.int64(0))
        f = jnp.where(eff & ~arg.values, jnp.int64(1), jnp.int64(0))
        return (_signed(t, is_add, is_remove, jnp.int64),
                _signed(f, is_add, is_remove, jnp.int64))

    def value(self, lane_vals):
        t, f = lane_vals
        v = (f == 0) if self.is_and else (t > 0)
        return Col(v, jnp.zeros_like(v, dtype=jnp.bool_))


class DistinctCountAgg(AggSpec):
    """distinctCount(): exact distinct-value count per group, with
    removal support (DistinctCountAttributeAggregatorExecutor keeps a
    value->count HashMap).

    Device design: one bounded open-addressing table over (group slot,
    value) pairs holds each pair's multiplicity. Per batch: running
    per-pair counts via a segmented scan over pair segments give each
    row's 0<->1 transition (+1 first add, -1 last remove); those deltas
    then scan over (group, reset) segments with a [K] carry — the same
    shape as every other lane, just with a stateful pre-pass. Pairs
    beyond the table capacity are dropped AND counted."""

    stateful = True
    D = 4096  # (group, value) pair slots

    def __init__(self, arg_type: AttrType):
        if arg_type is None:
            raise CompileError("distinctCount() needs an argument")
        self.name = "distinctCount"
        self.out_type = aggregator_result_type("distinctcount", arg_type)
        self.lanes = (Lane("sum", jnp.int64),)

    def init_table(self, K: int):
        return {"keys": jnp.zeros((self.D,), jnp.int64),
                "used": jnp.zeros((self.D,), jnp.bool_),
                "counts": jnp.zeros((self.D,), jnp.int64),
                "carry": jnp.zeros((K,), jnp.int64),
                "overflow": jnp.int64(0)}

    def run(self, arg, ctx, tab):
        B = ctx["B"]
        K = ctx["K"]
        D = self.D
        slots, agg_row = ctx["slots"], ctx["agg_row"]
        is_add, is_remove = ctx["is_add"], ctx["is_remove"]
        reset_seg, n_resets = ctx["reset_seg"], ctx["n_resets"]

        ph = hash_columns(
            [slots.astype(jnp.int64), arg.values],
            [jnp.zeros((B,), jnp.bool_), arg.nulls])
        pslots, pkeys, pused, ovf = lookup_or_insert(
            tab["keys"], tab["used"], ph, agg_row)
        tracked = agg_row & (pslots >= 0)
        sgn = jnp.where(tracked & is_add, jnp.int64(1),
                        jnp.where(tracked & is_remove, jnp.int64(-1),
                                  jnp.int64(0)))
        ps_safe = jnp.clip(pslots, 0, D - 1)
        pair_seg = jnp.where(tracked, ps_safe.astype(jnp.int64),
                             jnp.int64(D)) * (B + 1) + reset_seg
        perm2 = jnp.argsort(jnp.clip(pair_seg, 0, 2 ** 31 - 1)
                            .astype(jnp.int32), stable=True)
        inv2 = jnp.argsort(perm2.astype(jnp.int32))
        run_s = segmented_cumsum(sgn[perm2], pair_seg[perm2])
        carry_pair = jnp.where((reset_seg == 0) & tracked,
                               tab["counts"][ps_safe], 0)
        run = run_s[inv2] + carry_pair
        delta = jnp.where(tracked & is_add & (run == 1), jnp.int64(1),
                          jnp.where(tracked & is_remove & (run == 0),
                                    jnp.int64(-1), jnp.int64(0)))

        # new pair counts: each pair's final running count in the LAST
        # reset segment (pairs untouched after a reset drop to 0)
        base_counts = jnp.where(n_resets == 0, tab["counts"],
                                jnp.zeros_like(tab["counts"]))
        seg_s = pair_seg[perm2]
        is_pair_last_s = jnp.concatenate([
            seg_s[:-1] != seg_s[1:], jnp.ones((1,), jnp.bool_)])
        pair_last = is_pair_last_s[inv2] & tracked & \
            (reset_seg == n_resets)
        tgt = jnp.where(pair_last, ps_safe, jnp.int32(D))
        new_counts = base_counts.at[tgt].set(
            jnp.where(pair_last, run, 0), mode="drop")

        # distinct running value per row: scan deltas over (group, reset)
        lane = self.lanes[0]
        d_sorted = delta[ctx["perm"]]
        pref = lane.segmented_scan(d_sorted, ctx["seg_sorted"])
        slot_safe = jnp.clip(ctx["slot_sorted"], 0, K - 1)
        carry_vec = tab["carry"]
        cin = jnp.where(ctx["segzero_sorted"], carry_vec[slot_safe],
                        jnp.int64(0))
        running = (cin + pref)[ctx["inv_perm"]]

        # new [K] carry: deltas in the last reset segment
        last_mask = (reset_seg == n_resets) & tracked
        base = jnp.where(n_resets == 0, carry_vec,
                         jnp.zeros_like(carry_vec))
        ktgt = jnp.where(last_mask, slots, jnp.int32(K))
        new_carry = base.at[ktgt].add(jnp.where(last_mask, delta, 0),
                                      mode="drop")
        new_tab = {"keys": pkeys, "used": pused, "counts": new_counts,
                   "carry": new_carry,
                   "overflow": tab["overflow"] + ovf}
        return (running,), new_tab

    def value(self, lane_vals):
        (d,) = lane_vals
        return Col(d, jnp.zeros_like(d, dtype=jnp.bool_))


class UnionSetAgg(AggSpec):
    """unionSet(): union of aggregated sets with removal support
    (UnionSetAttributeAggregatorExecutor.java:43 keeps a Set plus a
    value->count map for expired-decrement).

    Device design: a bounded [S] value/multiplicity table (SET_LANES
    slots). Per chunk: existing entries and all incoming rows' set lanes
    merge through one sort + segmented count; entries whose multiplicity
    stays positive re-pack into the table, overflow counted. Rows of one
    chunk observe the END-OF-CHUNK union (exact for batch windows, where
    one flush chunk produces one emission; documented chunk-granular for
    sliding windows). Ungrouped only — group by + unionSet rejects."""

    stateful = True

    def __init__(self, arg_type: AttrType, grouped: bool):
        from ..core.types import SET_LANES
        if arg_type is not AttrType.OBJECT:
            raise CompileError(
                "Parameter passed to unionSet aggregator should be a set "
                "object (createSet() result)")
        if grouped:
            raise CompileError(
                "unionSet() with group by is not supported yet")
        self.name = "unionSet"
        self.out_type = aggregator_result_type("unionset", arg_type)
        self.S = SET_LANES
        self.lanes = (Lane("sum", jnp.int64),)

    def init_table(self, K: int):
        from ..core.types import SET_EMPTY
        return {"vals": jnp.full((self.S,), SET_EMPTY, jnp.int64),
                "counts": jnp.zeros((self.S,), jnp.int64),
                "tag": jnp.int64(0),
                "overflow": jnp.int64(0)}

    def run(self, arg, ctx, tab):
        from ..core.types import SET_EMPTY
        B, S = ctx["B"], self.S
        is_add, is_remove = ctx["is_add"], ctx["is_remove"]
        agg_row = ctx["agg_row"]
        n_resets = ctx["n_resets"]
        reset_seg = ctx["reset_seg"]

        elems = arg.values[:, 1:]                       # [B, S]
        tag_col = arg.values[:, 0]
        eff = agg_row & ~arg.nulls & (reset_seg == n_resets)
        sgn_row = jnp.where(eff & is_add, jnp.int64(1),
                            jnp.where(eff & is_remove, jnp.int64(-1),
                                      jnp.int64(0)))
        flat_vals = elems.reshape(-1)
        flat_sgn = jnp.repeat(sgn_row, S)
        flat_sgn = jnp.where(flat_vals == SET_EMPTY, 0, flat_sgn)

        # existing table participates only when no reset wiped it
        keep_tab = n_resets == 0
        tab_vals = jnp.where(keep_tab, tab["vals"], SET_EMPTY)
        tab_cnt = jnp.where(keep_tab, tab["counts"], 0)

        all_vals = jnp.concatenate([tab_vals, flat_vals])
        all_sgn = jnp.concatenate([tab_cnt, flat_sgn])
        # distinct totals: sort by value, segment-sum the multiplicities
        order = jnp.argsort(all_vals)
        v_s = all_vals[order]
        c_s = all_sgn[order]
        seg_start = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                     v_s[1:] != v_s[:-1]])
        seg_id = cumsum_fast(seg_start.astype(jnp.int64)) - 1
        totals = segmented_cumsum(c_s, seg_id)
        is_last = jnp.concatenate([seg_id[:-1] != seg_id[1:],
                                   jnp.ones((1,), jnp.bool_)])
        live = is_last & (totals > 0) & (v_s != SET_EMPTY)
        rank = cumsum_fast(live.astype(jnp.int64)) - 1
        n_live = jnp.sum(live.astype(jnp.int64))
        dest = jnp.where(live & (rank < S), rank, jnp.int64(S))
        new_vals = jnp.full((S,), jnp.int64(SET_EMPTY)).at[dest].set(
            jnp.where(live, v_s, SET_EMPTY), mode="drop")
        new_cnt = jnp.zeros((S,), jnp.int64).at[dest].set(
            jnp.where(live, totals, 0), mode="drop")
        tag = jnp.maximum(tab["tag"], jnp.max(jnp.where(
            eff, tag_col, jnp.int64(0))))
        new_tab = {"vals": new_vals, "counts": new_cnt, "tag": tag,
                   "overflow": tab["overflow"] +
                   jnp.maximum(n_live - S, 0)}
        # every row observes the end-of-chunk union
        set_vec = jnp.concatenate([tag[None], new_vals])
        running = jnp.broadcast_to(set_vec[None, :], (B, S + 1))
        return (running,), new_tab

    def value(self, lane_vals):
        (v,) = lane_vals
        return Col(v, jnp.zeros(v.shape[:1], jnp.bool_))


def _tree_levels(w: int) -> int:
    return int(w).bit_length() - 1


class SlidingMinMaxAgg(AggSpec):
    """min()/max() over sliding-window content (removal support).

    The reference walks a Deque per key
    (MinAttributeAggregatorExecutor.processRemove). Device design:
    window expiry is FIFO (clones expire in arrival order), so a key's
    live multiset is a contiguous per-key sequence range [head, tail).
    Values land in a per-key ring buffer; each row's extreme is a
    range-min/max query answered by an implicit segment tree built once
    per step over the rings ([K, 2W] min-reduction, then a vmapped
    O(log W) query per row). Live content beyond W is dropped from the
    extreme AND counted."""

    stateful = True

    def __init__(self, arg_type: AttrType, is_max: bool, grouped: bool):
        if arg_type not in NUMERIC_TYPES:
            raise CompileError("min()/max() requires numeric input")
        self.name = "max" if is_max else "min"
        self.is_max = is_max
        self.out_type = aggregator_result_type(self.name, arg_type)
        self.dtype = np_dtype(arg_type)
        self.W = 256 if grouped else 4096  # ring capacity per key
        self.lanes = (Lane("max" if is_max else "min", self.dtype),
                      Lane("sum", jnp.int64))

    def _ident(self):
        return self.lanes[0].identity()

    def init_table(self, K: int):
        return {"ring": jnp.full((K, self.W), self._ident(),
                                 dtype=self.dtype),
                "heads": jnp.zeros((K,), jnp.int64),
                "tails": jnp.zeros((K,), jnp.int64),
                "overflow": jnp.int64(0)}

    def run(self, arg, ctx, tab):
        B, K, W = ctx["B"], ctx["K"], self.W
        slots = jnp.clip(ctx["slots"], 0, K - 1)
        agg_row = ctx["agg_row"]
        is_add = ctx["is_add"] & agg_row & ~arg.nulls
        is_remove = ctx["is_remove"] & agg_row & ~arg.nulls
        reset_seg, n_resets = ctx["reset_seg"], ctx["n_resets"]
        # RESET clears all state: model as heads := tails at the reset
        # point. With in-batch resets we conservatively clear BEFORE the
        # batch too (resets mid-batch with live sliding content is a
        # degenerate mix the reference only reaches via batch windows,
        # where min/max uses the non-sliding path).
        had_reset = n_resets > 0
        heads0 = jnp.where(had_reset, tab["tails"], tab["heads"])

        # per-row per-key add/remove ranks (sorted by group slot)
        perm, inv_perm = ctx["perm"], ctx["inv_perm"]
        gseg = ctx["slot_sorted"].astype(jnp.int64)
        adds_s = segmented_cumsum(is_add[perm].astype(jnp.int64), gseg)
        rems_s = segmented_cumsum(is_remove[perm].astype(jnp.int64), gseg)
        add_rank = adds_s[inv_perm]   # inclusive count up to this row
        rem_rank = rems_s[inv_perm]
        tail_row = tab["tails"][slots] + add_rank   # after this row
        head_row = heads0[slots] + rem_rank
        # clamp ring span: live beyond W drops off the extreme; a key
        # whose batch adds run more than W past a row's head also
        # overwrites ring slots that row still queries — both are
        # dropped-accuracy cases, counted as overflow
        over = jnp.maximum(tail_row - head_row - W, 0)
        head_eff = head_row + over

        # scatter this batch's added values into the rings
        pos = jnp.where(is_add, (tail_row - 1) % W, 0).astype(jnp.int32)
        sslot = jnp.where(is_add, slots, jnp.int32(K))
        ring = tab["ring"].at[sslot, pos].set(
            jnp.where(is_add, arg.values.astype(self.dtype),
                      self._ident()), mode="drop")

        # implicit segment tree over each ring: tree[:, 1:2W), leaves at
        # [W, 2W) = ring positions
        lane = self.lanes[0]
        levels = [ring]
        cur = ring
        for _ in range(_tree_levels(W)):
            cur = lane.combine(cur[:, 0::2], cur[:, 1::2])
            levels.append(cur)
        tree = jnp.concatenate([lv for lv in reversed(levels)], axis=1)
        # tree layout: index 1 = root ... leaves at [W, 2W)
        pad = jnp.full((K, 1), self._ident(), dtype=self.dtype)
        tree = jnp.concatenate([pad, tree], axis=1)

        # vmapped iterative RMQ over [head_eff, tail_row): the ring range
        # may wrap, so split into two non-wrapping leaf ranges and run
        # the standard bottom-up query on each
        span = jnp.maximum(tail_row - head_eff, 0)
        h = (head_eff % W).astype(jnp.int32)
        end = h + jnp.minimum(span, W).astype(jnp.int32)
        a1, b1 = h, jnp.minimum(end, W)           # [h, min(end, W))
        a2 = jnp.zeros_like(h)
        b2 = jnp.maximum(end - W, 0).astype(jnp.int32)  # wrapped part
        ltree = tree[slots]  # [B, 2W] per-row gather of the key's tree

        def rmq(a, b):
            # rolled as a fori_loop, NOT a Python loop: unrolling the
            # log2(W)+1 levels of data-dependent gathers makes XLA:CPU's
            # LLVM codegen blow up super-linearly (a single jit_chain
            # with a few of these aggregators never finishes compiling);
            # the rolled While compiles in seconds and runs the same
            # per-level ops bit-identically.
            def level(_, carry):
                res, li, ri = carry
                open_ = li < ri
                take_l = open_ & ((li & 1) == 1)
                vl = jnp.take_along_axis(
                    ltree, jnp.where(take_l, li, 1)[:, None],
                    axis=1)[:, 0]
                res = jnp.where(take_l, lane.combine(res, vl), res)
                li = jnp.where(take_l, li + 1, li)
                open_ = li < ri
                take_r = open_ & ((ri & 1) == 1)
                vr = jnp.take_along_axis(
                    ltree, jnp.where(take_r, ri - 1, 1)[:, None],
                    axis=1)[:, 0]
                res = jnp.where(take_r, lane.combine(res, vr), res)
                ri = jnp.where(take_r, ri - 1, ri)
                return res, li >> 1, ri >> 1

            res, _, _ = jax.lax.fori_loop(
                0, _tree_levels(W) + 1, level,
                (jnp.full((B,), self._ident(), dtype=self.dtype),
                 (a + W).astype(jnp.int32), (b + W).astype(jnp.int32)))
            return res

        res = lane.combine(rmq(a1, b1), rmq(a2, b2))
        count_row = span
        # new per-key pointers: totals after the batch
        n_adds = jax.ops.segment_sum(
            is_add.astype(jnp.int64), slots.astype(jnp.int32),
            num_segments=K)
        end_tail = (tab["tails"] + n_adds)[slots]
        overflow_rows = jnp.sum(
            (agg_row & (end_tail - head_eff > W)).astype(jnp.int64))
        n_rems = jax.ops.segment_sum(
            is_remove.astype(jnp.int64), slots.astype(jnp.int32),
            num_segments=K)
        new_tails = tab["tails"] + n_adds
        new_heads = jnp.maximum(heads0 + n_rems, new_tails - W)
        new_tab = {"ring": ring, "heads": new_heads, "tails": new_tails,
                   "overflow": tab["overflow"] + overflow_rows}
        return (res, count_row), new_tab

    def value(self, lane_vals):
        m, cnt = lane_vals
        return Col(jnp.where(cnt == 0, jnp.zeros_like(m), m), cnt == 0)


def make_agg_spec(name: str, arg_type: Optional[AttrType],
                  expired_possible: bool, grouped: bool = False,
                  fifo_expiry: bool = True) -> AggSpec:
    key = name.lower()
    if key == "sum":
        return SumAgg(arg_type)
    if key == "avg":
        return AvgAgg(arg_type)
    if key == "count":
        return CountAgg()
    if key == "stddev":
        return StdDevAgg(arg_type)
    if key in ("min", "max"):
        if expired_possible and not fifo_expiry:
            raise CompileError(
                f"{key}() over a window with non-FIFO expiry (sort/"
                "frequent/lossyFrequent) is not supported — the sliding "
                "extreme relies on arrival-order expiry")
        if expired_possible:
            return SlidingMinMaxAgg(arg_type, key == "max", grouped)
        return MinMaxAgg(arg_type, key == "max")
    if key in ("minforever", "maxforever"):
        return ForeverMinMaxAgg(arg_type, key == "maxforever")
    if key in ("and", "or"):
        return BoolAgg(arg_type, key == "and")
    if key == "distinctcount":
        return DistinctCountAgg(arg_type)
    if key == "unionset":
        return UnionSetAgg(arg_type, grouped)
    raise CompileError(f"unknown aggregator '{name}'")


# ---------------------------------------------------------------------------
# AST rewrite: aggregator calls -> placeholder variables
# ---------------------------------------------------------------------------


def extract_aggregators(expr: A.Expression, found: list) -> A.Expression:
    """Replace aggregator calls with __agg_<i>__ variables, collecting the
    (name, arg asts) list."""
    if isinstance(expr, A.AttributeFunction):
        if expr.namespace is None and expr.name.lower() in AGGREGATOR_NAMES:
            idx = len(found)
            found.append((expr.name, list(expr.parameters), expr.star))
            return A.Variable(attribute=f"__agg_{idx}__")
        return A.AttributeFunction(
            expr.namespace, expr.name,
            [extract_aggregators(p, found) for p in expr.parameters],
            expr.star)
    if isinstance(expr, A.MathOp):
        return A.MathOp(expr.op, extract_aggregators(expr.left, found),
                        extract_aggregators(expr.right, found))
    if isinstance(expr, A.Compare):
        return A.Compare(expr.op, extract_aggregators(expr.left, found),
                         extract_aggregators(expr.right, found))
    if isinstance(expr, A.And):
        return A.And(extract_aggregators(expr.left, found),
                     extract_aggregators(expr.right, found))
    if isinstance(expr, A.Or):
        return A.Or(extract_aggregators(expr.left, found),
                    extract_aggregators(expr.right, found))
    if isinstance(expr, A.Not):
        return A.Not(extract_aggregators(expr.expr, found))
    if isinstance(expr, A.IsNull) and expr.expr is not None:
        return A.IsNull(expr=extract_aggregators(expr.expr, found))
    return expr


class AggScope(Scope):
    """Delegates to a base scope but resolves __agg_<i>__ placeholders."""

    def __init__(self, base: Scope, agg_types: list):
        self.base = base
        self.agg_types = agg_types

    def resolve(self, var: A.Variable):
        if var.attribute and var.attribute.startswith("__agg_") \
                and var.attribute.endswith("__") and var.stream_ref is None:
            i = int(var.attribute[6:-2])
            return ("agg", i), self.agg_types[i]
        return self.base.resolve(var)

    def resolve_stream_isnull(self, is_null):
        return self.base.resolve_stream_isnull(is_null)


# ---------------------------------------------------------------------------
# the aggregating selector
# ---------------------------------------------------------------------------


class AggregateOp(Operator):
    """Select clause with aggregators and/or group-by.

    batch_mode mirrors the reference's batchingEnabled (batch windows): only
    the last qualifying row (or the last per group, in first-seen group
    order) is emitted per input chunk.
    """

    sort_heavy = True  # group-slot lexsort + unsort per step

    def __init__(self, selector: A.Selector, in_schema: StreamSchema,
                 out_stream_id: str, scope: Scope, functions=None,
                 batch_mode: bool = False, expired_possible: bool = True,
                 current_on: bool = True, expired_on: bool = False,
                 key_capacity: int = 1024, fifo_expiry: bool = True):
        self.in_schema = in_schema
        self.batch_mode = batch_mode
        self.current_on = current_on
        self.expired_on = expired_on
        self.group_by = selector.group_by
        self.K = key_capacity if selector.group_by else 1
        functions = functions or {}

        if selector.select_all:
            raise CompileError("select * cannot be combined with aggregation")

        # group-by key expressions
        self.key_exprs = [compile_expression(v, scope, functions)
                          for v in selector.group_by]

        # split output expressions into aggregator instances + wrappers
        found: list = []
        rewritten = [extract_aggregators(oa.expression, found)
                     for oa in selector.attributes]
        rewritten_having = (extract_aggregators(selector.having, found)
                            if selector.having is not None else None)

        self.agg_specs: list[AggSpec] = []
        self.agg_args: list[Optional[CompiledExpr]] = []
        for name, params, star in found:
            if len(params) > 1:
                raise CompileError(
                    f"{name}() takes at most one argument here")
            grouped = bool(selector.group_by)
            if params:
                ce = compile_expression(params[0], scope, functions)
                self.agg_specs.append(
                    make_agg_spec(name, ce.type, expired_possible,
                                  grouped, fifo_expiry))
                self.agg_args.append(ce)
            else:
                self.agg_specs.append(
                    make_agg_spec(name, None, expired_possible,
                                  grouped, fifo_expiry))
                self.agg_args.append(None)

        agg_types = [s.out_type for s in self.agg_specs]
        agg_scope = AggScope(scope, agg_types)
        self.compiled = [compile_expression(e, agg_scope, functions)
                         for e in rewritten]
        attrs = tuple(
            Attribute(output_attribute_name(oa, i), ce.type)
            for i, (oa, ce) in enumerate(zip(selector.attributes,
                                             self.compiled)))
        self._schema = StreamSchema(out_stream_id, attrs)

        # having may reference output names OR input attributes OR aggregates
        self.having = None
        if rewritten_having is not None:
            hscope = HavingScope(self._schema, agg_scope)
            self.having = compile_expression(rewritten_having, hscope,
                                             functions)
            if self.having.type is not AttrType.BOOL:
                raise CompileError("HAVING must be BOOL")

        # order by / limit / offset (STRING keys shape at the host)
        self.order_by, host_order = compile_order_by(selector,
                                                     self._schema)
        self.limit = const_int(selector.limit, "limit")
        self.offset = const_int(selector.offset, "offset")
        if host_order:
            self.host_shape = (host_order, self.offset, self.limit)
            self.limit = self.offset = None
        else:
            self.host_shape = None

    @property
    def out_schema(self):
        return self._schema

    def init_state(self):
        carries = []
        for spec in self.agg_specs:
            carries.append(tuple(
                jnp.full((self.K,), lane.identity(), dtype=lane.dtype)
                for lane in spec.lanes))
        return {
            "keys": jnp.zeros((self.K,), jnp.int64),
            "used": jnp.zeros((self.K,), jnp.bool_),
            "carry": tuple(carries),
            "tables": tuple(
                spec.init_table(self.K)
                if getattr(spec, "stateful", False) else ()
                for spec in self.agg_specs),
            "overflow": jnp.int64(0),
        }

    def step(self, state, batch: EventBatch, now):
        B = batch.capacity
        env = env_from_batch(batch)
        env["__now__"] = now
        valid = batch.valid
        is_add = valid & (batch.kind == CURRENT)
        is_remove = valid & (batch.kind == EXPIRED)
        is_reset = valid & (batch.kind == RESET)
        agg_row = is_add | is_remove

        # --- group slots -------------------------------------------------
        overflow = state["overflow"]
        if self.group_by:
            key_cols = [ce.fn(env) for ce in self.key_exprs]
            hkeys = hash_columns([c.values for c in key_cols],
                                 [c.nulls for c in key_cols])
            slots, new_keys, new_used, ov = lookup_or_insert(
                state["keys"], state["used"], hkeys, agg_row)
            # overflowed keys (slot table full) are parked on the trash slot
            # K: excluded from state, carry, and output — counted, not
            # silently mis-aggregated
            overflowed = agg_row & (slots < 0)
            agg_row = agg_row & ~overflowed
            slots = jnp.where(agg_row, slots, jnp.int32(self.K))
            overflow = overflow + ov
        else:
            new_keys, new_used = state["keys"], state["used"]
            slots = jnp.where(agg_row, jnp.int32(0), jnp.int32(self.K))

        # --- reset segmentation ------------------------------------------
        reset_seg = cumsum_fast(is_reset.astype(jnp.int64))  # inclusive
        # a reset row itself belongs to the next segment — contributions on
        # the reset row don't exist anyway (reset rows are not agg rows)
        n_resets = reset_seg[B - 1] if B > 0 else jnp.int64(0)

        # --- sort by (slot, row) -----------------------------------------
        # jnp.argsort is stable, so one int32 argsort on the slot id
        # replaces the (rows, slots) lexsort — int32 is the native TPU
        # sort width (int64 sorts emulate at ~2x compile/run cost)
        rows = jnp.arange(B, dtype=jnp.int64)
        perm = jnp.argsort(slots)
        inv_perm = jnp.argsort(perm.astype(jnp.int32))
        seg_sorted = (slots.astype(jnp.int64) * (B + 1) + reset_seg)[perm]
        slot_sorted = slots[perm]
        segzero_sorted = (reset_seg == 0)[perm]

        # --- per-aggregator running values -------------------------------
        ctx = {"B": B, "K": self.K, "slots": slots, "agg_row": agg_row,
               "is_add": is_add, "is_remove": is_remove,
               "reset_seg": reset_seg, "n_resets": n_resets,
               "perm": perm, "inv_perm": inv_perm,
               "seg_sorted": seg_sorted, "slot_sorted": slot_sorted,
               "segzero_sorted": segzero_sorted}
        agg_cols: list[Col] = []
        new_carries = []
        new_tables = []
        for spec, arg, carry, tab in zip(self.agg_specs, self.agg_args,
                                         state["carry"],
                                         state["tables"]):
            arg_col = arg.fn(env) if arg is not None else None
            if getattr(spec, "stateful", False):
                runnings, ntab = spec.run(arg_col, ctx, tab)
                agg_cols.append(spec.value(tuple(runnings)))
                new_carries.append(carry)
                new_tables.append(ntab)
                continue
            contribs = spec.contribs(arg_col, is_add, is_remove)
            lane_runnings = []
            lane_carries = []
            for lane, contrib, cvec in zip(spec.lanes, contribs, carry):
                c_sorted = contrib[perm]
                pref = lane.segmented_scan(c_sorted, seg_sorted)
                # carry-in applies to rows before any reset
                slot_safe = jnp.clip(slot_sorted, 0, self.K - 1)
                cin = jnp.where(segzero_sorted, cvec[slot_safe],
                                lane.identity())
                run_sorted = lane.combine(cin, pref)
                lane_runnings.append(run_sorted[inv_perm])
                # new carry: contributions in the LAST reset segment
                last_mask = (reset_seg == n_resets) & agg_row
                base = jnp.where(n_resets == 0, cvec,
                                 jnp.full_like(cvec, lane.identity()))
                upd = jnp.where(last_mask, contrib,
                                jnp.full_like(contrib, lane.identity()))
                tgt = jnp.where(last_mask, slots, jnp.int32(self.K))
                if lane.op == "sum":
                    newc = base.at[tgt].add(upd, mode="drop")
                elif lane.op == "min":
                    newc = base.at[tgt].min(upd, mode="drop")
                else:
                    newc = base.at[tgt].max(upd, mode="drop")
                lane_carries.append(newc)
            agg_cols.append(spec.value(tuple(lane_runnings)))
            new_carries.append(tuple(lane_carries))
            new_tables.append(tab)

        for i, c in enumerate(agg_cols):
            env[("agg", i)] = c

        # --- project ------------------------------------------------------
        out_cols, out_nulls = [], []
        for ce in self.compiled:
            c = ce.fn(env)
            if c.values.ndim == 2:   # SET columns: [rows, lanes]
                out_cols.append(jnp.broadcast_to(
                    c.values, (B,) + c.values.shape[-1:]))
            else:
                out_cols.append(jnp.broadcast_to(c.values, (B,)))
            out_nulls.append(jnp.broadcast_to(c.nulls, (B,)))

        qualifying = ((is_add & self.current_on) |
                      (is_remove & self.expired_on)) & \
            (slots < jnp.int32(self.K))
        if self.having is not None:
            henv = dict(env)
            for i, (cv, cn) in enumerate(zip(out_cols, out_nulls)):
                henv[("out", i)] = Col(cv, cn)
            hc = self.having.fn(henv)
            qualifying = qualifying & hc.values & ~hc.nulls

        out_valid = qualifying
        if self.batch_mode:
            # The reference emits one output chunk PER FLUSH
            # (LengthBatchWindowProcessor.process collects streamEventChunks
            # and the selector runs per chunk, keeping the last qualifying
            # event — or the last per group in first-seen order). A flush
            # chunk in the window's output is [EXPIRED*, RESET?, CURRENT*]:
            # a new chunk starts at the first valid row or where an
            # EXPIRED/RESET row follows a CURRENT row.
            vidx = jnp.where(valid, rows, jnp.int64(-1))
            last_valid_upto = cummax_fast(vidx)
            prev_valid = jnp.concatenate([jnp.full((1,), -1, jnp.int64),
                                          last_valid_upto[:-1]])
            prev_kind = jnp.where(
                prev_valid >= 0, batch.kind[jnp.maximum(prev_valid, 0)],
                jnp.int32(-1))
            boundary = valid & (
                (prev_valid < 0) |
                (((batch.kind == EXPIRED) | (batch.kind == RESET)) &
                 (prev_kind == CURRENT)))
            chunk_id = cumsum_fast(boundary.astype(jnp.int64))
            # last qualifying row per (slot, flush chunk); emitted in order
            # of the group's first qualifying row (chunks are contiguous row
            # ranges, so this also orders chunks)
            qkey = jnp.where(qualifying,
                             slots.astype(jnp.int64) * (B + 1) + chunk_id,
                             I64_MAX)
            # (K+1)*(B+1) < 2^31 at capped step capacities -> int32 key;
            # stable argsort keeps row order within (slot, chunk)
            assert (self.K + 1) * (B + 2) < 2 ** 31, (self.K, B)
            qkey32 = jnp.where(qualifying, qkey,
                               jnp.int64(2 ** 31 - 1)).astype(jnp.int32)
            perm2 = jnp.argsort(qkey32)
            qk_s = qkey32[perm2]
            rows_s = rows[perm2]
            is_last_s = jnp.concatenate([qk_s[:-1] != qk_s[1:],
                                         jnp.ones((1,), jnp.bool_)])
            first_s = segmented_cummin(rows_s.astype(jnp.int32), qk_s)
            out_valid = jnp.zeros((B,), jnp.bool_).at[perm2].set(
                is_last_s & (qk_s < jnp.int32(2 ** 31 - 1)))
            emit_order = jnp.zeros((B,), jnp.int64).at[perm2].set(
                first_s.astype(jnp.int64))
        else:
            emit_order = rows

        out = EventBatch(ts=batch.ts, cols=tuple(out_cols),
                         nulls=tuple(out_nulls), kind=batch.kind,
                         valid=out_valid)

        # --- order by / offset / limit (chunk level) ----------------------
        out = shape_output(out, self.order_by, self.offset, self.limit,
                           emit_order)

        new_state = {"keys": new_keys, "used": new_used,
                     "carry": tuple(new_carries),
                     "tables": tuple(new_tables), "overflow": overflow}
        return new_state, out


class HavingScope(Scope):
    """HAVING resolves output attribute names first, then falls back to the
    input scope (reference: having runs on the projected output event but
    may also reference input attrs that were projected through)."""

    def __init__(self, out_schema: StreamSchema, base: Scope):
        self.out_schema = out_schema
        self.base = base

    def resolve(self, var: A.Variable):
        if var.attribute and var.attribute.startswith("__agg_"):
            return self.base.resolve(var)
        if var.stream_ref is None:
            try:
                idx = self.out_schema.index_of(var.attribute)
                return ("out", idx), self.out_schema.types[idx]
            except KeyError:
                pass
        return self.base.resolve(var)

    def resolve_stream_isnull(self, is_null):
        return self.base.resolve_stream_isnull(is_null)



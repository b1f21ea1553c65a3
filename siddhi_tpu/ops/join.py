"""Window×window joins: banded equi-join probes with a masked
cross-product grid fallback.

Reference mapping:
- query/input/stream/join/JoinProcessor.java:78-190 — the post-window
  JoinProcessor triggers on each window-output event (CURRENT and EXPIRED,
  preserving the type on the joined row), find()s the opposite window with
  the compiled on-condition, builds two-slot StateEvents; outer joins emit
  one-sided rows when nothing matches; RESET rows pass through one-sided;
  TIMER is consumed.
- JoinInputStreamParser.java:75 — two SingleStreamRuntimes cross-wired.

TPU design, two kernels per trigger direction (docs/performance.md
"join kernels"):

- ``grid`` (the fallback, and the only option for ON conditions with no
  equi conjunct): the trigger side's window-output batch [B] is crossed
  with the opposite window's buffer [W] in one shot — the on-condition
  compiles to a broadcast [B, W] boolean grid (columns enter as
  [B,1] / [1,W]); surviving pairs are compacted to a static JOIN_CAP
  with interval prefix sums ordered (trigger row, buffer position),
  which reproduces the reference's iteration order exactly. O(B·W)
  work and memory per step.

- ``probe`` (the default for equi joins — the ops/table.py IndexProbe
  machinery promoted into the join hot path): the first
  ``L-expr == R-expr`` conjunct of the ON condition becomes the band
  key. The opposite buffer's key column is put in a stable key-sorted
  view (``sorted_key_view``: live rows ascending by key, buffer order
  within equal keys — so bands enumerate matches in exactly the grid's
  (trigger row, buffer position) order), each trigger row finds its
  candidate band with two searchsorteds (``band_bounds``), and matches
  expand into the static JOIN_CAP via interval prefix sums — no [B, W]
  anything is ever materialized. Residual non-key conjuncts (and the
  sliding-time-window liveness gate) are evaluated ONLY on the banded
  candidate pairs. O((B + W)·log W + JOIN_CAP) per step.

Both kernels emit identical rows in identical order and count overflow
identically (tests/test_join_probe.py sweeps the ref-corpus join cases
over both); the planner picks per join side (core/runtime.py,
``SIDDHI_TPU_JOIN_KERNEL`` overrides). Overflow is counted, never
silent.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.event import (CURRENT, EXPIRED, RESET, Attribute, EventBatch,
                          StreamSchema)
from ..core.types import AttrType, NUMERIC_TYPES, np_dtype, promote
from ..lang import ast as A
from .expr import Col, CompileError, CompiledExpr, Scope, compile_expression
from .keyed import cumsum_fast
from .table import band_bounds, sorted_key_view

from .sentinels import POS_INF


class JoinSideScope(Scope):
    """Resolves variables to ('L'/'R', attr_idx) over the two sides."""

    def __init__(self, left_schema: StreamSchema, left_alias,
                 right_schema: StreamSchema, right_alias):
        # an alias REPLACES the stream name (the reference rejects
        # references to the original id once `as x` is used —
        # JoinTestCase joinTest7)
        self.sides = {
            "L": (left_schema,
                  {left_alias} if left_alias else {left_schema.stream_id}),
            "R": (right_schema,
                  {right_alias} if right_alias
                  else {right_schema.stream_id}),
        }

    def resolve(self, var: A.Variable):
        ref = var.stream_ref
        if ref is not None:
            for tag, (schema, names) in self.sides.items():
                if ref in names:
                    try:
                        idx = schema.index_of(var.attribute)
                    except KeyError:
                        raise CompileError(
                            f"'{ref}' has no attribute "
                            f"'{var.attribute}'")
                    return (tag, idx), schema.types[idx]
            raise CompileError(f"unknown stream reference '{ref}' in join")
        hits = []
        for tag, (schema, _) in self.sides.items():
            if var.attribute in schema.names:
                hits.append((tag, schema))
        if len(hits) == 1:
            tag, schema = hits[0]
            idx = schema.index_of(var.attribute)
            return (tag, idx), schema.types[idx]
        raise CompileError(
            f"attribute '{var.attribute}' is "
            + ("ambiguous" if hits else "unknown") + " across join sides")


class JoinCombinedScope(Scope):
    """Selector scope over the combined (left ++ right) joined batch."""

    def __init__(self, side_scope: JoinSideScope, left_n: int):
        self.side_scope = side_scope
        self.left_n = left_n

    def resolve(self, var: A.Variable):
        (tag, idx), t = self.side_scope.resolve(var)
        return ("attr", idx if tag == "L" else self.left_n + idx), t


def combined_schema(out_id: str, left: StreamSchema,
                    right: StreamSchema) -> StreamSchema:
    attrs = []
    for att in left.attributes:
        attrs.append(Attribute(att.name, att.type))
    for att in right.attributes:
        attrs.append(Attribute(att.name, att.type))
    return StreamSchema(out_id, tuple(attrs))


# ---------------------------------------------------------------------------
# equi-conjunct analysis (probe-kernel eligibility)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EquiKey:
    """One ``L-expr == R-expr`` conjunct usable as a banded probe key.
    ``key_dtype`` is the dtype BOTH sides cast into before comparing —
    the same promotion the grid's compiled compare applies, so probe
    equality is bit-identical to grid equality (including lossy
    long->double promotion: both kernels compare post-cast)."""

    left: CompiledExpr       # key values over the L side's columns
    right: CompiledExpr      # key values over the R side's columns
    key_dtype: Any


class _TagRecorder(Scope):
    """Wraps the join side scope and records which sides ('L'/'R') an
    expression's variables resolve to."""

    def __init__(self, base: Scope):
        self.base = base
        self.tags: set = set()

    def resolve(self, var: A.Variable):
        key, t = self.base.resolve(var)
        self.tags.add(key[0])
        return key, t


def _flatten_and(e: A.Expression) -> list:
    if isinstance(e, A.And):
        return _flatten_and(e.left) + _flatten_and(e.right)
    return [e]


def _rebuild_and(conjs: list) -> A.Expression:
    out = conjs[0]
    for c in conjs[1:]:
        out = A.And(out, c)
    return out


def analyze_equi_join(on: A.Expression, side_scope: Scope):
    """First top-level ``==`` conjunct with one pure-L and one pure-R
    side -> ``(EquiKey, residual AST or None)``; ``(None, None)`` when
    the ON condition has no banded key (grid fallback)."""
    conjs = _flatten_and(on)
    for i, c in enumerate(conjs):
        if not isinstance(c, A.Compare) or c.op != "==":
            continue
        try:
            lrec = _TagRecorder(side_scope)
            lce = compile_expression(c.left, lrec)
            rrec = _TagRecorder(side_scope)
            rce = compile_expression(c.right, rrec)
        except CompileError:
            continue
        if lrec.tags == {"L"} and rrec.tags == {"R"}:
            lk, rk = lce, rce
        elif lrec.tags == {"R"} and rrec.tags == {"L"}:
            lk, rk = rce, lce
        else:
            continue      # constant / single-side / mixed-side conjunct
        if lk.type in NUMERIC_TYPES and rk.type in NUMERIC_TYPES:
            kdt = np.dtype(np_dtype(promote(lk.type, rk.type)))
        elif lk.type is rk.type and lk.type is AttrType.STRING:
            kdt = np.dtype(np_dtype(AttrType.STRING))  # dictionary codes
        elif lk.type is rk.type and lk.type is AttrType.BOOL:
            kdt = np.dtype(np.uint8)  # sortable bool encoding
        else:
            continue
        residual = conjs[:i] + conjs[i + 1:]
        return EquiKey(lk, rk, kdt), \
            (_rebuild_and(residual) if residual else None)
    return None, None


def equi_route_columns(on: A.Expression, side_scope: Scope):
    """``{'L': col_idx, 'R': col_idx}`` when the first top-level
    ``==`` conjunct compares BARE attribute references on both sides —
    the mesh router's key columns (parallel/mesh.py): hash-routing both
    streams by this column puts every band (and therefore every joined
    pair — key equality is the band) wholly on its owning shard, so the
    sorted pools stay device-local and shard outputs union to the
    single-chip replay. ``None`` when the band key is an expression
    (routable only by materializing it host-side first)."""
    for c in _flatten_and(on):
        if not isinstance(c, A.Compare) or c.op != "==":
            continue
        if not (isinstance(c.left, A.Variable)
                and isinstance(c.right, A.Variable)):
            continue
        try:
            (ltag, lidx), _lt = side_scope.resolve(c.left)
            (rtag, ridx), _rt = side_scope.resolve(c.right)
        except CompileError:
            continue
        if {ltag, rtag} == {"L", "R"}:
            return {ltag: lidx, rtag: ridx}
    return None


class JoinCross:
    """One trigger direction of a join: cross the trigger side's
    window-output batch with the opposite window buffer."""

    def __init__(self, trigger_is_left: bool, left_schema: StreamSchema,
                 right_schema: StreamSchema, on: Optional[A.Expression],
                 side_scope: JoinSideScope, join_type: str,
                 join_cap: int = 1024,
                 opp_window_ms: Optional[int] = None,
                 cand_cap: Optional[int] = None):
        self.trigger_is_left = trigger_is_left
        # opposite side is a sliding TIME window: a pair is valid only if
        # the opposite row was still alive AT THE TRIGGER ROW'S TIME
        # (coalesced timer steps may leave already-expired rows in the
        # not-yet-stepped opposite buffer; per-row gating keeps the
        # rm-pair emission bit-equal with per-boundary timer fires)
        self.opp_window_ms = opp_window_ms
        self.left_schema = left_schema
        self.right_schema = right_schema
        self.join_type = join_type
        self.cap = join_cap
        # candidate expansion capacity for the probe kernel's residual
        # stage (band pairs evaluated before compaction to JOIN_CAP);
        # @cap(join.candidates=...) overrides, default 4x headroom
        self.cand_cap = int(cand_cap) if cand_cap else 4 * join_cap
        self.cond = None
        # probe-kernel eligibility: first L==R conjunct becomes the band
        # key, everything else stays as a residual condition evaluated
        # on the banded candidates only
        self.equi: Optional[EquiKey] = None
        self.residual: Optional[CompiledExpr] = None
        self.kernel = "grid"   # planner sets "probe" (core/runtime.py)
        # mesh routing key: the band key's bare column indices per side
        # (None when the band key is an expression) — parallel/mesh.py
        # derives route_cols="auto" from this
        self.route_cols = None
        if on is not None:
            self.cond = compile_expression(on, side_scope)
            if self.cond.type is not AttrType.BOOL:
                raise CompileError("join ON condition must be BOOL")
            self.route_cols = equi_route_columns(on, side_scope)
            equi, residual_ast = analyze_equi_join(on, side_scope)
            if equi is not None:
                self.equi = equi
                if residual_ast is not None:
                    self.residual = compile_expression(residual_ast,
                                                       side_scope)
        # does the trigger side emit unmatched one-sided rows?
        self.outer = (
            join_type == "full_outer"
            or (join_type == "left_outer" and trigger_is_left)
            or (join_type == "right_outer" and not trigger_is_left))

    def cross(self, trig: EventBatch, opp_buf: dict,
              gate_alive: bool = False) -> EventBatch:
        """trig: trigger window output [B]; opp_buf: opposite window
        buffer dict (ts/seq/cols/nulls/valid, rows in seq order).
        Dispatches to the planner-selected kernel; both kernels emit
        identical rows/order/overflow counts."""
        if self.kernel == "probe" and self.equi is not None:
            return self._cross_probe(trig, opp_buf, gate_alive)
        return self._cross_grid(trig, opp_buf, gate_alive)

    # -- kernel 1: broadcast [B, W] grid (fallback) ----------------------

    def _cross_grid(self, trig: EventBatch, opp_buf: dict,
                    gate_alive: bool = False) -> EventBatch:
        B = trig.capacity
        W = opp_buf["seq"].shape[0]
        env = {}
        lsch = self.left_schema
        rsch = self.right_schema
        if self.trigger_is_left:
            for i in range(len(lsch.types)):
                env[("L", i)] = Col(trig.cols[i][:, None],
                                    trig.nulls[i][:, None])
            for i in range(len(rsch.types)):
                env[("R", i)] = Col(opp_buf["cols"][i][None, :],
                                    opp_buf["nulls"][i][None, :])
        else:
            for i in range(len(lsch.types)):
                env[("L", i)] = Col(opp_buf["cols"][i][None, :],
                                    opp_buf["nulls"][i][None, :])
            for i in range(len(rsch.types)):
                env[("R", i)] = Col(trig.cols[i][:, None],
                                    trig.nulls[i][:, None])
        env["__ts__"] = Col(trig.ts[:, None], jnp.zeros((B, 1), jnp.bool_))

        if self.cond is not None:
            c = self.cond.fn(env)
            grid = jnp.broadcast_to(c.values & ~c.nulls, (B, W))
        else:
            grid = jnp.ones((B, W), jnp.bool_)

        joinable = trig.valid & ((trig.kind == CURRENT) |
                                 (trig.kind == EXPIRED))
        pair = grid & joinable[:, None] & opp_buf["valid"][None, :]  # lint: disable=quadratic-grid-hazard (blessed grid fallback: arbitrary ON-conditions can't use the banded probe)
        if gate_alive and self.opp_window_ms is not None:
            # columnar mode only: timer fires coalesce, so the opposite
            # buffer may hold rows its own (skipped) expiry would have
            # removed — gate pairs on the opposite row being alive at
            # the trigger's timestamp. The row path fires per boundary
            # and needs no gate (the reference pairs expiring rows with
            # the opposite content AT the fire).
            alive = (opp_buf["ts"][None, :] + self.opp_window_ms  # lint: disable=quadratic-grid-hazard (liveness gate rides the already-materialized fallback grid)
                     >= trig.ts[:, None])
            pair = pair & alive
        matched_any = jnp.any(pair, axis=1)
        lone = joinable & ~matched_any if self.outer else \
            jnp.zeros((B,), jnp.bool_)
        reset = trig.valid & (trig.kind == RESET)

        # compact surviving pairs + one-sided rows to JOIN_CAP, ordered
        # (trigger row, buffer pos) with one-sided rows before any pair of
        # the same trigger row. SORT-FREE two-level ranking: indicators in
        # that order ([B, 1+W]: col 0 = lone/reset, cols 1..W = pairs),
        # a per-row prefix sum + a row-offset prefix sum, then each output
        # slot finds its (row, col) with two searchsorteds. A [B*W] sort
        # or flat scan here is 33-84M elements — pathological TPU compile.
        ind = jnp.concatenate([(lone | reset)[:, None], pair], axis=1)
        inner = jnp.cumsum(ind.astype(jnp.int32), axis=1)    # [B, W+1]
        counts = inner[:, -1]
        offs = jnp.cumsum(counts)                            # [B] inclusive
        total = offs[B - 1].astype(jnp.int64)
        j = jnp.arange(self.cap, dtype=jnp.int32)
        r = jnp.clip(jnp.searchsorted(offs, j, side="right"), 0, B - 1)
        start = offs[r] - counts[r]
        k = j - start
        c = jax.vmap(
            lambda row, kk: jnp.searchsorted(row, kk, side="right"))(
                inner[r], k)
        valid_out = j < total
        ti = r.astype(jnp.int64)                             # trigger row
        is_pair = c > 0
        oi = jnp.clip(c - 1, 0, W - 1).astype(jnp.int64)     # opposite row

        n_l = len(lsch.types)
        n_r = len(rsch.types)
        cols, nulls = [], []
        opp_invalid = ~is_pair  # one-sided: opposite side nulled
        for i in range(n_l + n_r):
            if self.trigger_is_left:
                from_trigger = i < n_l
                a = i if from_trigger else i - n_l
            else:
                from_trigger = i >= n_l
                a = i - n_l if from_trigger else i
            if from_trigger:
                cols.append(trig.cols[a][ti])
                nulls.append(trig.nulls[a][ti])
            else:
                cols.append(opp_buf["cols"][a][oi])
                nulls.append(opp_buf["nulls"][a][oi] | opp_invalid)
        return EventBatch(
            ts=trig.ts[ti],
            cols=tuple(cols),
            nulls=tuple(nulls),
            kind=trig.kind[ti],
            valid=valid_out,
        ), jnp.maximum(total - self.cap, 0)

    # -- kernel 2: banded searchsorted probe (equi joins) ----------------

    def _trig_tag(self):
        return "L" if self.trigger_is_left else "R"

    def _gathered_env(self, trig: EventBatch, opp_buf: dict, ti, oi):
        """Residual-condition env over candidate pairs: every side
        column gathered at the pair's (trigger row, opposite row) —
        1-D [CAND] lanes; XLA dead-code-eliminates unreferenced
        columns' gathers."""
        env = {}
        n_l = len(self.left_schema.types)
        n_r = len(self.right_schema.types)
        if self.trigger_is_left:
            for i in range(n_l):
                env[("L", i)] = Col(trig.cols[i][ti], trig.nulls[i][ti])
            for i in range(n_r):
                env[("R", i)] = Col(opp_buf["cols"][i][oi],
                                    opp_buf["nulls"][i][oi])
        else:
            for i in range(n_l):
                env[("L", i)] = Col(opp_buf["cols"][i][oi],
                                    opp_buf["nulls"][i][oi])
            for i in range(n_r):
                env[("R", i)] = Col(trig.cols[i][ti], trig.nulls[i][ti])
        env["__ts__"] = Col(trig.ts[ti], jnp.zeros(ti.shape, jnp.bool_))
        return env

    def _cross_probe(self, trig: EventBatch, opp_buf: dict,
                     gate_alive: bool = False) -> EventBatch:
        """Banded equi-join: key-sort the opposite buffer once
        (O(W log W) — int32/float sorts are native TPU ops), answer
        every trigger row with two searchsorteds, expand the bands into
        JOIN_CAP via interval prefix sums. The sorted view preserves
        buffer order within equal keys, so emission order — (trigger
        row, buffer position), one-sided rows first — is bit-equal with
        the grid's compaction. No [B, W] intermediate exists at any
        point."""
        B = trig.capacity
        W = opp_buf["seq"].shape[0]
        eq = self.equi
        tag = self._trig_tag()
        opp_tag = "R" if tag == "L" else "L"
        n_side = {"L": len(self.left_schema.types),
                  "R": len(self.right_schema.types)}
        tenv = {(tag, i): Col(trig.cols[i], trig.nulls[i])
                for i in range(n_side[tag])}
        tenv["__ts__"] = Col(trig.ts, jnp.zeros((B,), jnp.bool_))
        oenv = {(opp_tag, i): Col(opp_buf["cols"][i], opp_buf["nulls"][i])
                for i in range(n_side[opp_tag])}
        trig_ce = eq.left if self.trigger_is_left else eq.right
        opp_ce = eq.right if self.trigger_is_left else eq.left
        tk = trig_ce.fn(tenv)
        okc = opp_ce.fn(oenv)
        kdt = eq.key_dtype
        tkv = jnp.broadcast_to(tk.values, (B,)).astype(kdt)
        tknull = jnp.broadcast_to(tk.nulls, (B,))
        okv = jnp.broadcast_to(okc.values, (W,)).astype(kdt)
        oknull = jnp.broadcast_to(okc.nulls, (W,))

        # key-sorted view of the opposite buffer: live rows ascending by
        # key, buffer order within equal keys (= the grid's column order)
        live = opp_buf["valid"] & ~oknull
        order, sk, n_live = sorted_key_view(okv, live)

        joinable = trig.valid & ((trig.kind == CURRENT) |
                                 (trig.kind == EXPIRED))
        act = joinable & ~tknull     # null keys match nothing (grid: ==
        lo, hi = band_bounds(sk, n_live, tkv, "==", act)  # on null->F)
        cnt = (hi - lo).astype(jnp.int64)                 # band sizes [B]

        reset = trig.valid & (trig.kind == RESET)
        need_residual = self.residual is not None or (
            gate_alive and self.opp_window_ms is not None)

        if need_residual:
            # candidate stage: expand bands to [CAND] pairs, evaluate
            # the residual conjuncts (and the liveness gate) per pair
            CAND = self.cand_cap
            coffs = jnp.cumsum(cnt)                       # [B] inclusive
            ctotal = coffs[B - 1]
            cj = jnp.arange(CAND, dtype=jnp.int32)
            cr = jnp.clip(jnp.searchsorted(coffs, cj, side="right"),
                          0, B - 1)
            ck = cj - (coffs[cr] - cnt[cr])
            cvalid = cj < ctotal
            cp = jnp.clip(lo[cr] + ck, 0, W - 1).astype(jnp.int32)
            coi = order[cp]
            s = cvalid
            if self.residual is not None:
                env = self._gathered_env(trig, opp_buf, cr, coi)
                rc = self.residual.fn(env)
                s = s & jnp.broadcast_to(rc.values & ~rc.nulls, (CAND,))
            if gate_alive and self.opp_window_ms is not None:
                s = s & (opp_buf["ts"][coi] + self.opp_window_ms
                         >= trig.ts[cr])
            surv = jnp.zeros((B,), jnp.int64).at[cr].add(
                s.astype(jnp.int64), mode="drop")
            # candidates beyond CAND were never evaluated: counted as
            # dropped (never silent; size @cap(join.candidates) up)
            cand_lost = jnp.maximum(ctotal - CAND, 0)
            S = cumsum_fast(s.astype(jnp.int64))          # surv ranks
            soffs = cumsum_fast(surv)                     # [B] inclusive
        else:
            surv = cnt
            cand_lost = jnp.int64(0)

        matched = surv > 0
        lone = joinable & ~matched if self.outer else \
            jnp.zeros((B,), jnp.bool_)
        lead = (lone | reset).astype(jnp.int64)
        tot = lead + surv
        offs = jnp.cumsum(tot)                            # [B] inclusive
        total = offs[B - 1]
        j = jnp.arange(self.cap, dtype=jnp.int32)
        r = jnp.clip(jnp.searchsorted(offs, j, side="right"), 0, B - 1)
        start = offs[r] - tot[r]
        k = j - start                                     # slot-in-row
        valid_out = j < total
        is_pair = valid_out & (k >= lead[r])
        if need_residual:
            # the (k - lead)-th surviving candidate of row r, located by
            # its global survivor rank (sort-free: one searchsorted over
            # the candidate survivor prefix sums)
            m = (soffs[r] - surv[r]) + (k - lead[r])
            c = jnp.clip(jnp.searchsorted(S, m + 1, side="left"),
                         0, self.cand_cap - 1)
            oi = coi[c]
        else:
            p = jnp.clip(lo[r] + (k - lead[r]), 0, W - 1).astype(jnp.int32)
            oi = order[p]
        ti = r.astype(jnp.int64)
        oi = oi.astype(jnp.int64)

        n_l = len(self.left_schema.types)
        n_r = len(self.right_schema.types)
        cols, nulls = [], []
        opp_invalid = ~is_pair     # one-sided: opposite side nulled
        for i in range(n_l + n_r):
            if self.trigger_is_left:
                from_trigger = i < n_l
                a = i if from_trigger else i - n_l
            else:
                from_trigger = i >= n_l
                a = i - n_l if from_trigger else i
            if from_trigger:
                cols.append(trig.cols[a][ti])
                nulls.append(trig.nulls[a][ti])
            else:
                cols.append(opp_buf["cols"][a][oi])
                nulls.append(opp_buf["nulls"][a][oi] | opp_invalid)
        return EventBatch(
            ts=trig.ts[ti],
            cols=tuple(cols),
            nulls=tuple(nulls),
            kind=trig.kind[ti],
            valid=valid_out,
        ), jnp.maximum(total - self.cap, 0) + cand_lost

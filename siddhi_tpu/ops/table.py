"""In-memory event tables as device-resident column stores.

Reference mapping:
- table/InMemoryTable.java:58-200 (add/delete/update/updateOrAdd/find/
  contains over an EventHolder)
- table/holder/ListEventHolder.java / IndexEventHolder.java:60-110 (list
  scan vs primary-key map; here: one columnar buffer, with primary-key
  upsert semantics when @PrimaryKey is declared)
- util/parser/OperatorParser.java:62 (compiled conditions; here conditions
  compile to broadcast [B, T] grids like joins)
- query/output/callback/{InsertIntoTable,DeleteTable,UpdateTable,
  UpdateOrInsertTable}Callback.java (query outputs into tables — modeled
  as terminal TableOutputOps on the query's operator chain)

Shared mutable state: the table's arrays live on the TableRuntime; every
query step that touches tables receives the current state dict and returns
an updated one (the host runtime serializes access with per-table locks in
a fixed order). Capacity is static with an overflow counter.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.event import (CURRENT, EXPIRED, Attribute, EventBatch,
                          StreamSchema)
from ..core.types import AttrType, np_dtype
from ..lang import ast as A
from .expr import Col, CompileError, Scope, compile_expression
from .keyed import cumsum_fast, hash_columns
from .operators import Operator

from .sentinels import POS_INF


class TableRuntime:
    """One `define table` instance (shared across queries)."""

    def __init__(self, table_id: str, schema: StreamSchema,
                 capacity: int = 8192, pk_indices: Optional[list] = None,
                 index_indices: Optional[list] = None):
        self.table_id = table_id
        self.schema = schema
        self.cap = capacity
        self.pk = tuple(pk_indices or ())
        # @Index attributes (IndexEventHolder.java:60-110): conditions of
        # the form `T.attr OP <stream expr>` on these rewrite to sorted
        # probes instead of [B, T] grids (see IndexProbe below)
        self.indexes = tuple(index_indices or ())
        self.lock = threading.Lock()
        self.state = self.init_state()

    def init_state(self) -> dict:
        T = self.cap
        return {
            "cols": tuple(jnp.zeros((T,), dtype=np_dtype(t))
                          for t in self.schema.types),
            "nulls": tuple(jnp.zeros((T,), dtype=jnp.bool_)
                           for _ in self.schema.types),
            "ts": jnp.zeros((T,), dtype=jnp.int64),
            "seq": jnp.zeros((T,), dtype=jnp.int64),
            "valid": jnp.zeros((T,), dtype=jnp.bool_),
            "next_seq": jnp.int64(0),
            "overflow": jnp.int64(0),
        }

    # -- pure ops over (state, batch) ------------------------------------
    def insert(self, state: dict, batch: EventBatch, row_mask) -> dict:
        """Append masked batch rows. With a primary key, an existing row
        with the same key is replaced in place (IndexEventHolder.add)."""
        T = self.cap
        B = batch.capacity
        adding = row_mask & batch.valid
        if self.pk:
            bkeys = hash_columns([batch.cols[i] for i in self.pk],
                                 [batch.nulls[i] for i in self.pk])
            tkeys = hash_columns([state["cols"][i] for i in self.pk],
                                 [state["nulls"][i] for i in self.pk])
            # match each adding row to an existing row with the same key
            # primary-key upsert match: an intentional [B, T] grid —
            # in-place replacement needs per-(event,row) hits, which the
            # banded probe's interval trick cannot provide (same reason
            # updates keep the grid below)
            eq = (
                (bkeys[:, None] == tkeys[None, :])  # lint: disable=quadratic-grid-hazard
                & adding[:, None]
                & state["valid"][None, :])
            hit_row = jnp.where(jnp.any(eq, axis=1),
                                jnp.argmax(eq, axis=1), T)
            replaces = hit_row < T
            state = self._scatter_rows(state, batch,
                                       adding & replaces, hit_row,
                                       keep_seq=True)
            adding = adding & ~replaces
            # duplicate keys WITHIN the batch: later row wins (sequential
            # add semantics) — handled by scatter order below (row order)
        free = ~state["valid"]
        free_pos = jnp.argsort(~free)
        n_free = jnp.sum(free.astype(jnp.int64))
        rank = cumsum_fast(adding.astype(jnp.int64)) - 1
        ok = adding & (rank < n_free)
        dest = jnp.where(ok, free_pos[jnp.clip(rank, 0, T - 1)], T)
        state = self._scatter_rows(state, batch, ok, dest, keep_seq=False)
        lost = jnp.sum((adding & ~ok).astype(jnp.int64))
        return {**state, "overflow": state["overflow"] + lost}

    def _scatter_rows(self, state, batch, ok, dest, keep_seq):
        d = jnp.where(ok, dest, self.cap)
        cols = tuple(tc.at[d].set(bc, mode="drop")
                     for tc, bc in zip(state["cols"], batch.cols))
        nulls = tuple(tn.at[d].set(bn, mode="drop")
                      for tn, bn in zip(state["nulls"], batch.nulls))
        ts = state["ts"].at[d].set(batch.ts, mode="drop")
        if keep_seq:
            seq = state["seq"]
            next_seq = state["next_seq"]
        else:
            n_ok = cumsum_fast(ok.astype(jnp.int64)) - 1
            seq = state["seq"].at[d].set(state["next_seq"] + n_ok,
                                         mode="drop")
            next_seq = state["next_seq"] + jnp.sum(ok.astype(jnp.int64))
        valid = state["valid"].at[d].set(True, mode="drop")
        return {**state, "cols": cols, "nulls": nulls, "ts": ts,
                "seq": seq, "valid": valid, "next_seq": next_seq}

    def buffer(self, state: dict) -> dict:
        """Findable view (same layout as a window buffer), in seq order."""
        order = jnp.argsort(jnp.where(state["valid"], state["seq"],
                                      POS_INF))
        return {
            "cols": tuple(c[order] for c in state["cols"]),
            "nulls": tuple(n[order] for n in state["nulls"]),
            "ts": state["ts"][order],
            "seq": state["seq"][order],
            "valid": state["valid"][order],
        }


class TableOnScope(Scope):
    """Scope for table `on` conditions and IN-table expressions: table
    attributes resolve to ('T', idx) ([1, T] lanes), everything else
    delegates to the event scope wrapped as ('S', key) ([B, 1] lanes)."""

    def __init__(self, table_id: str, table_schema: StreamSchema,
                 event_scope: Scope, table_alias: Optional[str] = None):
        self.table_id = table_id
        self.table_schema = table_schema
        self.event_scope = event_scope
        self.table_alias = table_alias

    def resolve(self, var: A.Variable):
        ref = var.stream_ref
        if ref is not None and ref in (self.table_id, self.table_alias):
            idx = self.table_schema.index_of(var.attribute)
            return ("T", idx), self.table_schema.types[idx]
        if ref is None and var.attribute in self.table_schema.names:
            # bare names bind to the event side when it has the attribute
            # (`delete T on symbol == T.symbol`: bare `symbol` is the
            # incoming event's, matching the reference's meta resolution
            # order, ExpressionParser.java:1330-1339); the table column is
            # the fallback only when the event scope lacks the name
            try:
                key, t = self.event_scope.resolve(var)
                return ("S", key), t
            except (CompileError, KeyError):
                idx = self.table_schema.index_of(var.attribute)
                return ("T", idx), self.table_schema.types[idx]
        key, t = self.event_scope.resolve(var)
        return ("S", key), t


def grid_env(table_buf: dict, batch_env: dict) -> dict:
    """Build the [B, T] broadcast env for a table condition."""
    env = {}
    for k, colv in batch_env.items():
        if isinstance(colv, Col):
            v = colv.values
            n = colv.nulls
            if getattr(v, "ndim", 0) >= 1:
                v = v[:, None]
            if getattr(n, "ndim", 0) >= 1:
                n = n[:, None]
            env[("S", k)] = Col(v, n)
            if k == "__ts__":
                env[k] = Col(v, n)
        else:
            env[k] = colv  # __now__ scalar
    for i in range(len(table_buf["cols"])):
        env[("T", i)] = Col(table_buf["cols"][i][None, :],
                            table_buf["nulls"][i][None, :])
    return env


class TableOutputOp(Operator):
    """Terminal operator writing query output into a table:
    insert / delete / update / update-or-insert. The batch flows through
    unchanged (callbacks still observe the events)."""

    needs_tables = True

    def table_ids(self):
        return (self.table.table_id,)

    def __init__(self, kind: str, table: TableRuntime,
                 on: Optional[A.Expression], set_clause,
                 event_scope: Scope, in_schema: StreamSchema):
        self.kind = kind
        self.table = table
        self.in_schema = in_schema
        self.cond = None
        self.set_compiled = []
        if on is not None:
            scope = TableOnScope(table.table_id, table.schema, event_scope)
            self.cond = compile_expression(on, scope)
            if self.cond.type is not AttrType.BOOL:
                raise CompileError("table ON condition must be BOOL")
        for var, expr in (set_clause or []):
            tidx = table.schema.index_of(var.attribute)
            scope = TableOnScope(table.table_id, table.schema, event_scope)
            ce = compile_expression(expr, scope)
            self.set_compiled.append((tidx, ce))
        # index rewrite (delete only: updates need per-row source-event
        # selection, which the interval trick cannot provide)
        self.index_probe = analyze_index_probe(on, table, event_scope) \
            if (kind == "delete" and on is not None) else None

    @property
    def out_schema(self):
        return self.in_schema

    def step_tables(self, state, batch: EventBatch, now, tstates: dict):
        from .expr import env_from_batch
        tid = self.table.table_id
        tstate = tstates[tid]
        acting = batch.valid & (batch.kind == CURRENT)
        if self.kind == "insert":
            tstate = self.table.insert(tstate, batch, acting)
        elif self.kind == "delete" and self.index_probe is not None:
            benv = env_from_batch(batch)
            benv["__now__"] = now
            touched, _ = probe_touched(self.table, tstate,
                                       self.index_probe, benv, acting)
            tstate = {**tstate, "valid": tstate["valid"] & ~touched}
        else:
            benv = env_from_batch(batch)
            benv["__now__"] = now
            wrapped = {k: v for k, v in benv.items()}
            genv = grid_env(tstate, wrapped)
            if self.cond is not None:
                c = self.cond.fn(genv)
                grid = jnp.broadcast_to(
                    c.values & ~c.nulls,
                    (batch.capacity, self.table.cap))
            else:
                grid = jnp.ones((batch.capacity, self.table.cap),
                                jnp.bool_)
            # blessed full-scan fallback: conditions that defeated
            # analyze_index_probe (non-indexed attrs, multi-attr forms)
            grid = (
                grid & acting[:, None] & tstate["valid"][None, :])  # lint: disable=quadratic-grid-hazard
            touched = jnp.any(grid, axis=0)  # table rows hit by any event
            if self.kind == "delete":
                tstate = {**tstate, "valid": tstate["valid"] & ~touched}
            elif self.kind in ("update", "update_or_insert"):
                # per table row: the LAST matching event provides values
                # (sequential update semantics)
                src = jnp.where(
                    jnp.any(grid, axis=0),
                    (batch.capacity - 1) -
                    jnp.argmax(grid[::-1, :], axis=0),
                    0)
                cols = list(tstate["cols"])
                nulls = list(tstate["nulls"])
                for tidx, ce in self.set_compiled:
                    # evaluate per (event,row) then gather source event
                    vc = ce.fn(genv)
                    vals = jnp.broadcast_to(
                        vc.values, (batch.capacity, self.table.cap))
                    nls = jnp.broadcast_to(
                        vc.nulls, (batch.capacity, self.table.cap))
                    rowv = jnp.take_along_axis(vals, src[None, :],
                                               axis=0)[0]
                    rown = jnp.take_along_axis(nls, src[None, :],
                                               axis=0)[0]
                    cols[tidx] = jnp.where(touched, rowv, cols[tidx])
                    nulls[tidx] = jnp.where(touched, rown, nulls[tidx])
                tstate = {**tstate, "cols": tuple(cols),
                          "nulls": tuple(nulls)}
                if self.kind == "update_or_insert":
                    unmatched = acting & ~jnp.any(grid, axis=1)
                    tstate = self.table.insert(tstate, batch, unmatched)
            else:
                raise AssertionError(self.kind)
        tstates = {**tstates, tid: tstate}
        return state, batch, tstates


@dataclasses.dataclass
class IndexProbe:
    """An index-rewritable condition: `T.attr OP <stream expr>` where
    attr carries @Index or @PrimaryKey. Instead of a [B, T] condition
    grid, the step sorts the T key column once (int32/float sorts are
    native TPU ops; O(T log T) beats the O(B*T) grid for large tables —
    the reference's IndexEventHolder/CollectionExpressionParser rewrite,
    done the columnar way) and answers every event with two
    searchsorteds, marking matched rows via interval prefix sums."""

    attr: int
    op: str                      # attr OP value: '==','<','<=','>','>='
    value: "CompiledExpr"        # stream-side [B] values


def analyze_index_probe(on_ast, table: "TableRuntime",
                        event_scope: Scope) -> Optional[IndexProbe]:
    """Single comparison on an indexed attribute -> IndexProbe, else
    None (full-scan fallback)."""
    from .expr import CompiledExpr  # noqa: F401 — typing aid
    if not isinstance(on_ast, A.Compare) or on_ast.op == "!=":
        return None
    indexed = set(table.indexes) | set(table.pk)
    if not indexed:
        return None

    def table_attr(e) -> Optional[int]:
        if not isinstance(e, A.Variable) or e.index is not None:
            return None
        if e.stream_ref == table.table_id:
            return table.schema.index_of(e.attribute) \
                if e.attribute in table.schema.names else None
        if e.stream_ref is None and e.attribute in table.schema.names:
            try:
                event_scope.resolve(e)
                return None     # bare name binds to the event side
            except CompileError:
                return table.schema.index_of(e.attribute)
        return None

    def stream_side(e) -> Optional["CompiledExpr"]:
        try:
            ce = compile_expression(e, event_scope)
        except CompileError:
            return None
        return ce

    la, ra = table_attr(on_ast.left), table_attr(on_ast.right)
    if (la is None) == (ra is None):
        return None              # need exactly one table side
    if la is not None:
        attr, op, other = la, on_ast.op, on_ast.right
    else:
        flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "=="}
        attr, op, other = ra, flip[on_ast.op], on_ast.left
    if attr not in indexed:
        return None
    ce = stream_side(other)
    if ce is None or ce.type is AttrType.BOOL:
        return None
    # the probe compares in the KEY dtype; only eligible when casting the
    # stream value into it is exact (the grid path promotes both sides —
    # a DOUBLE 2.5 against an int key must NOT truncate to 2)
    import numpy as _np
    key_dt = _np.dtype(np_dtype(table.schema.types[attr]))
    val_dt = _np.dtype(np_dtype(ce.type))
    if _np.promote_types(key_dt, val_dt) != key_dt:
        return None
    return IndexProbe(attr, op, ce)


def sorted_key_view(keys, live, xp=None):
    """Stable key-sorted view of a buffer's key column: live rows first
    (ascending key, ORIGINAL POSITION order within equal keys — an
    explicit position tiebreak, not a stability assumption), dead/padded
    rows last. Returns ``(order, sorted_keys, n_live)`` where ``order``
    maps sorted position -> original buffer position.

    Shared by the table IndexProbe and the banded equi-join probe in
    ops/join.py (the promoted hot-path use): both answer per-event
    probes with two searchsorteds over this view instead of a [B, T]
    condition grid. ``xp`` selects the array namespace: jnp (default,
    in-trace device use) or numpy — the ingest-side reorder buffer
    (resilience/ordering.py) runs the SAME pad-last sort contract on
    host arrays for its in-buffer timestamp ordering."""
    if xp is None:
        xp = jnp
    T = keys.shape[0]
    import numpy as _np
    if _np.issubdtype(_np.dtype(keys.dtype.name), _np.floating):
        big = xp.asarray(_np.inf, keys.dtype)
    else:
        big = _np.asarray(_np.iinfo(_np.dtype(keys.dtype.name)).max,
                          keys.dtype.name)
    # pad-last order in one two-key sort: the position carries the pad
    # flag (padding at T + position), so it breaks key ties live-first
    # and then by position. A live row whose key equals the padding
    # sentinel (dtype max / +inf) thus sorts BEFORE the padding and the
    # n_live clamp cannot cut it off. A third sort operand costs the TPU
    # compiler minutes at 65,536 rows.
    ks = xp.where(live, keys, big)
    pos = xp.arange(T, dtype=xp.int32)
    tag = xp.where(live, pos, pos + T)
    if xp is jnp:
        sk, tag = jax.lax.sort((ks, tag), num_keys=2)
    else:
        by = xp.lexsort((tag, ks))
        sk, tag = ks[by], tag[by]
    return (xp.where(tag >= T, tag - T, tag), sk,
            xp.sum(live.astype(xp.int32)))


def band_bounds(sorted_keys, n_live, values, op, act):
    """Per-probe-value ``[lo, hi)`` positional bands over a
    ``sorted_key_view``: the contiguous run of live rows satisfying
    ``row_key OP value``. Inactive probes get empty bands."""
    sk = sorted_keys
    v = values
    if op == "==":
        lo = jnp.searchsorted(sk, v, side="left")
        hi = jnp.searchsorted(sk, v, side="right")
    elif op == "<":
        lo = jnp.zeros_like(act, jnp.int32)
        hi = jnp.searchsorted(sk, v, side="left")
    elif op == "<=":
        lo = jnp.zeros_like(act, jnp.int32)
        hi = jnp.searchsorted(sk, v, side="right")
    elif op == ">":
        lo = jnp.searchsorted(sk, v, side="right")
        hi = jnp.broadcast_to(n_live, act.shape)
    else:  # '>='
        lo = jnp.searchsorted(sk, v, side="left")
        hi = jnp.broadcast_to(n_live, act.shape)
    lo = jnp.minimum(lo.astype(jnp.int32), n_live)
    hi = jnp.minimum(hi.astype(jnp.int32), n_live)
    hi = jnp.where(act, hi, lo)
    return lo, hi


def probe_touched(table: "TableRuntime", tstate: dict, probe: IndexProbe,
                  env: dict, acting):
    """-> (touched [T] bool: rows matched by ANY acting event,
           any_hit [B] bool: events with at least one matching row)."""
    keys = tstate["cols"][probe.attr]
    knull = tstate["nulls"][probe.attr]
    live = tstate["valid"] & ~knull
    T = table.cap
    order, sk, n_live = sorted_key_view(keys, live)

    vc = probe.value.fn(env)
    v = jnp.broadcast_to(vc.values, acting.shape).astype(keys.dtype)
    vnull = jnp.broadcast_to(vc.nulls, acting.shape)
    act = acting & ~vnull
    lo, hi = band_bounds(sk, n_live, v, probe.op, act)
    any_hit = act & (hi > lo)
    # interval coverage via +1/-1 prefix sums over sorted positions
    lo_m = jnp.where(any_hit, lo, T)
    hi_m = jnp.where(any_hit, hi, T)
    delta = jnp.zeros((T + 1,), jnp.int32)
    delta = delta.at[lo_m].add(1, mode="drop")
    delta = delta.at[hi_m].add(-1, mode="drop")
    covered_sorted = jnp.cumsum(delta)[:T] > 0
    touched = jnp.zeros((T,), jnp.bool_).at[order].set(covered_sorted)
    return touched & tstate["valid"], any_hit


class InTableRewriter:
    """Extracts `expr IN table` subexpressions from a filter, replacing
    them with __in_<k>__ placeholder variables whose [B] values are
    containment results (InConditionExpressionExecutor)."""

    def __init__(self, tables: dict, event_scope: Scope):
        self.tables = tables
        self.event_scope = event_scope
        self.found: list = []  # (TableRuntime, compiled grid condition)

    def rewrite(self, expr: A.Expression) -> A.Expression:
        if isinstance(expr, A.InTable):
            tr = self.tables.get(expr.table_id)
            if tr is None:
                raise CompileError(f"undefined table '{expr.table_id}'")
            scope = TableOnScope(tr.table_id, tr.schema, self.event_scope)
            ce = compile_expression(expr.expr, scope)
            if ce.type is not AttrType.BOOL:
                raise CompileError("IN <table> expression must be BOOL")
            probe = analyze_index_probe(expr.expr, tr, self.event_scope)
            k = len(self.found)
            self.found.append((tr, ce, probe))
            return A.Variable(attribute=f"__in_{k}__")
        if isinstance(expr, A.MathOp):
            return A.MathOp(expr.op, self.rewrite(expr.left),
                            self.rewrite(expr.right))
        if isinstance(expr, A.Compare):
            return A.Compare(expr.op, self.rewrite(expr.left),
                             self.rewrite(expr.right))
        if isinstance(expr, A.And):
            return A.And(self.rewrite(expr.left), self.rewrite(expr.right))
        if isinstance(expr, A.Or):
            return A.Or(self.rewrite(expr.left), self.rewrite(expr.right))
        if isinstance(expr, A.Not):
            return A.Not(self.rewrite(expr.expr))
        if isinstance(expr, A.IsNull) and expr.expr is not None:
            return A.IsNull(expr=self.rewrite(expr.expr))
        return expr


class InTableScope(Scope):
    def __init__(self, base: Scope, n: int):
        self.base = base
        self.n = n

    def resolve(self, var: A.Variable):
        if var.stream_ref is None and var.attribute and \
                var.attribute.startswith("__in_") and \
                var.attribute.endswith("__"):
            return ("in", int(var.attribute[5:-2])), AttrType.BOOL
        return self.base.resolve(var)


class TableFilterOp(Operator):
    """FilterOp variant whose condition contains IN-table containment."""

    needs_tables = True

    def table_ids(self):
        return tuple(tr.table_id for tr, _, _ in self.contains)

    def __init__(self, cond_ast: A.Expression, schema: StreamSchema,
                 tables: dict, event_scope: Scope):
        rewriter = InTableRewriter(tables, event_scope)
        rewritten = rewriter.rewrite(cond_ast)
        self.contains = rewriter.found
        self.cond = compile_expression(
            rewritten, InTableScope(event_scope, len(self.contains)))
        if self.cond.type is not AttrType.BOOL:
            raise CompileError("filter must be BOOL")
        self.schema = schema

    @property
    def out_schema(self):
        return self.schema

    def step_tables(self, state, batch: EventBatch, now, tstates: dict):
        from ..core.event import TIMER
        from .expr import env_from_batch
        env = env_from_batch(batch)
        env["__now__"] = now
        for k, (tr, ce, probe) in enumerate(self.contains):
            tstate = tstates[tr.table_id]
            if probe is not None:
                _, any_hit = probe_touched(tr, tstate, probe, env,
                                           batch.valid)
                env[("in", k)] = Col(
                    any_hit, jnp.zeros((batch.capacity,), jnp.bool_))
                continue
            genv = grid_env(tstate, env)
            c = ce.fn(genv)
            grid = jnp.broadcast_to(c.values & ~c.nulls,
                                    (batch.capacity, tr.cap))
            grid = grid & tstate["valid"][None, :]
            env[("in", k)] = Col(jnp.any(grid, axis=1),
                                 jnp.zeros((batch.capacity,), jnp.bool_))
        c = self.cond.fn(env)
        keep = (c.values & ~c.nulls) | (batch.kind == TIMER)
        return state, batch.mask(keep), tstates


def expr_mentions_table(expr: A.Expression) -> bool:
    if isinstance(expr, A.InTable):
        return True
    if isinstance(expr, (A.MathOp, A.Compare, A.And, A.Or)):
        return expr_mentions_table(expr.left) or \
            expr_mentions_table(expr.right)
    if isinstance(expr, A.Not):
        return expr_mentions_table(expr.expr)
    if isinstance(expr, A.IsNull) and expr.expr is not None:
        return expr_mentions_table(expr.expr)
    return False

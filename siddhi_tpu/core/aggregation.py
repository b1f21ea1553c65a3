"""Incremental aggregation: `define aggregation A from S select ...
group by ... aggregate by ts every sec ... year`.

Reference mapping:
- AggregationRuntime (aggregation/AggregationRuntime.java:81)
- IncrementalExecutor chain (aggregation/IncrementalExecutor.java:103-159)
  — per-duration bucket cascade sec->min->...->year
- incremental decomposition Avg -> sum&count
  (query/selector/attribute/aggregator/incremental/*.java)
- parser util/parser/AggregationParser.java:93
- query side IncrementalAggregateCompileCondition (within ... per ...)

TPU-first design: the reference cascades one duration into the next on
bucket roll (timer-driven, pointer-chasing). Here every duration
aggregates the event batch DIRECTLY into a bounded keyed device table
whose key is hash(group values, bucket start): scatter-add lanes (sum /
count / min / max — all add-only, buckets never remove). Because buckets
are keyed rather than 'current', out-of-order events land in their
correct bucket with no special handling (the reference needs
OutOfOrderEventsDataAggregator). Month/year buckets use exact civil
calendar math on device (days-from-civil integer algorithm).

Query side (`from A within <start>, <end> per 'duration' select ...`)
materializes the duration's table as rows of
(group attrs..., defined aggregate outputs..., AGG_TIMESTAMP) and the
on-demand executor projects/filters over them.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp

from ..lang import ast as A
from ..ops.expr import (CompileError, SingleStreamScope, compile_expression,
                        env_from_batch)
from ..ops.keyed import hash_columns, lookup_or_insert
from ..ops.selector import output_attribute_name
from .event import CURRENT, Attribute, EventBatch, StreamSchema
from .stream import Receiver
from .types import AttrType, np_dtype

DURATIONS = ("seconds", "minutes", "hours", "days", "months", "years")

_FIXED_MS = {"seconds": 1000, "minutes": 60_000, "hours": 3_600_000,
             "days": 86_400_000}

_AGG_LANES = {
    # name -> lane kinds; 'ncount' counts NON-NULL argument values so
    # all-null buckets materialize as null (Siddhi aggregator semantics)
    "sum": ("sum", "ncount"),
    "count": ("count",),
    "avg": ("sum", "ncount"),
    "min": ("min", "ncount"),
    "max": ("max", "ncount"),
}


def _civil_from_days(z):
    """Days since 1970-01-01 -> (year, month) — Hinnant's civil algorithm
    in int64 (exact for the whole representable range)."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m


def _days_from_civil(y, m, d):
    y = jnp.where(m <= 2, y - 1, y)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def bucket_start(ts_ms, duration: str):
    """Bucket start timestamp (ms) for a duration, on device."""
    if duration in _FIXED_MS:
        w = _FIXED_MS[duration]
        return (ts_ms // w) * w
    days = ts_ms // 86_400_000
    y, m = _civil_from_days(days)
    if duration == "months":
        d0 = _days_from_civil(y, m, jnp.ones_like(m))
    elif duration == "years":
        d0 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    else:
        raise CompileError(f"unknown duration '{duration}'")
    return d0 * 86_400_000


class AggregationRuntime(Receiver):
    """One `define aggregation`: per-duration bounded bucket tables fed
    by a jitted scatter-add step, queried via within/per."""

    supports_packed = False
    K = 4096  # (group, bucket) slots per duration

    def __init__(self, app, ad: A.AggregationDefinition,
                 in_schema: StreamSchema):
        self.app = app
        self.ad = ad
        self.aggregation_id = ad.aggregation_id
        self.in_schema = in_schema
        self.durations = [d for d in DURATIONS if d in ad.durations]
        if not self.durations:
            raise CompileError(
                f"aggregation '{ad.aggregation_id}' has no durations")
        scope = SingleStreamScope(in_schema,
                                  aliases=(getattr(ad.input, "alias",
                                                   None),))
        self.scope = scope
        # aggregate-by timestamp attribute (LONG) or arrival time
        self.ts_idx: Optional[int] = None
        if ad.aggregate_by is not None:
            self.ts_idx = in_schema.index_of(ad.aggregate_by.attribute)
            if in_schema.attributes[self.ts_idx].type is not AttrType.LONG:
                raise CompileError(
                    "aggregate by attribute must be LONG (epoch ms)")

        # group-by: plain variables (AggregationParser restriction)
        self.group_exprs = []
        self.group_attrs = []
        for g in (ad.selector.group_by or []):
            if not isinstance(g, A.Variable):
                raise CompileError(
                    "aggregation group by must be plain attributes")
            self.group_exprs.append(compile_expression(g, scope))
            self.group_attrs.append(Attribute(
                g.attribute, in_schema.type_of(g.attribute)))

        # select attrs: plain group attrs pass through; aggregator calls
        # decompose into add-only lanes
        self.outputs = []   # (name, kind, payload)
        self.lanes = []     # (agg_name, lane_kind, CompiledExpr|None, dtype)
        for i, oa in enumerate(ad.selector.attributes):
            name = output_attribute_name(oa, i)
            e = oa.expression
            if isinstance(e, A.Variable):
                if not any(isinstance(g, A.Variable) and
                           g.attribute == e.attribute
                           for g in (ad.selector.group_by or [])):
                    raise CompileError(
                        f"aggregation select attribute '{name}' must be "
                        "a group-by attribute or an aggregate")
                self.outputs.append((name, "group",
                                     in_schema.index_of(e.attribute)))
                continue
            if isinstance(e, A.AttributeFunction) and e.namespace is None \
                    and e.name.lower() in _AGG_LANES:
                fname = e.name.lower()
                arg = None
                if e.parameters:
                    arg = compile_expression(e.parameters[0], scope)
                elif fname != "count":
                    raise CompileError(f"{fname}() needs an argument")
                lane_ids = []
                for kind in _AGG_LANES[fname]:
                    if kind in ("count", "ncount"):
                        dt = jnp.int64
                    elif arg.type in (AttrType.INT, AttrType.LONG):
                        dt = jnp.int64
                    else:
                        dt = jnp.float64
                    lane_ids.append(len(self.lanes))
                    self.lanes.append((fname, kind, arg, dt))
                out_t = (AttrType.DOUBLE if fname == "avg" or
                         (fname in ("sum", "min", "max") and arg.type
                          not in (AttrType.INT, AttrType.LONG))
                         else AttrType.LONG)
                if fname in ("min", "max") and arg.type in (
                        AttrType.INT, AttrType.LONG):
                    out_t = AttrType.LONG
                self.outputs.append((name, fname, (lane_ids, out_t)))
                continue
            raise CompileError(
                "aggregation select supports group attributes and "
                "sum/avg/count/min/max aggregates")

        out_attrs = []
        for n, kind, payload in self.outputs:
            t = in_schema.attributes[payload].type if kind == "group" \
                else payload[1]
            out_attrs.append(Attribute(n, t))
        out_attrs.append(Attribute("AGG_TIMESTAMP", AttrType.LONG))
        self.out_schema = StreamSchema(ad.aggregation_id,
                                       tuple(out_attrs))

        self.states = {d: self._init_state() for d in self.durations}
        self._lock = threading.Lock()
        self._steps: dict = {}

    def _init_state(self):
        K = self.K
        lanes = []
        for fname, kind, arg, dt in self.lanes:
            if kind == "min":
                init = jnp.iinfo(jnp.int64).max if dt == jnp.int64 \
                    else jnp.inf
            elif kind == "max":
                init = jnp.iinfo(jnp.int64).min if dt == jnp.int64 \
                    else -jnp.inf
            else:
                init = 0
            lanes.append(jnp.full((K,), init, dtype=dt))
        return {
            "keys": jnp.zeros((K,), jnp.int64),
            "used": jnp.zeros((K,), jnp.bool_),
            "bstart": jnp.zeros((K,), jnp.int64),
            "groups": tuple(jnp.zeros((K,), np_dtype(a.type))
                            for a in self.group_attrs),
            "gnulls": tuple(jnp.zeros((K,), jnp.bool_)
                            for _ in self.group_attrs),
            "lanes": tuple(lanes),
            "overflow": jnp.int64(0),
        }

    # -- ingest -----------------------------------------------------------
    def receive(self, events):
        from .runtime import QueryRuntime
        for batch, last_ts in QueryRuntime.encode_chunks(
                self.in_schema, events, None):
            self.process_batch(batch, last_ts)

    def process_batch(self, batch: EventBatch, timestamp: int,
                      now=None) -> None:
        with self._lock:
            step = self._step_for(batch.capacity)
            self.states = step(self.states, batch)

    def _step_for(self, capacity: int):
        fn = self._steps.get(capacity)
        if fn is None:
            fn = jax.jit(self._make_step())
            self._steps[capacity] = fn
        return fn

    def _make_step(self):
        K = self.K

        def step(states, batch: EventBatch):
            env = env_from_batch(batch)
            active = batch.valid & (batch.kind == CURRENT)
            if self.ts_idx is not None:
                ets = batch.cols[self.ts_idx].astype(jnp.int64)
            else:
                ets = batch.ts
            gcols = [ce.fn(env) for ce in self.group_exprs]
            new_states = {}
            for d in self.durations:
                st = states[d]
                bs = bucket_start(ets, d)
                hk = hash_columns(
                    [bs] + [c.values for c in gcols],
                    [jnp.zeros_like(active)] + [c.nulls for c in gcols])
                slots, keys, used, ovf, _ = lookup_or_insert(
                    st["keys"], st["used"], hk, active)
                ok = active & (slots >= 0)
                tgt = jnp.where(ok, slots, jnp.int32(K))
                bstart = st["bstart"].at[tgt].set(
                    jnp.where(ok, bs, 0), mode="drop")
                groups = tuple(
                    g.at[tgt].set(jnp.where(ok, c.values.astype(g.dtype),
                                            0), mode="drop")
                    for g, c in zip(st["groups"], gcols))
                gnulls = tuple(
                    gn.at[tgt].set(jnp.where(ok, c.nulls, False),
                                   mode="drop")
                    for gn, c in zip(st["gnulls"], gcols))
                lanes = []
                for (fname, kind, arg, dt), lv in zip(self.lanes,
                                                      st["lanes"]):
                    if kind == "count":
                        contrib = jnp.where(ok, jnp.int64(1), 0)
                        lanes.append(lv.at[tgt].add(contrib, mode="drop"))
                        continue
                    c = arg.fn(env)
                    eff = ok & ~c.nulls
                    if kind == "ncount":
                        lanes.append(lv.at[tgt].add(
                            jnp.where(eff, jnp.int64(1), 0), mode="drop"))
                        continue
                    v = c.values.astype(dt)
                    if kind == "sum":
                        lanes.append(lv.at[tgt].add(
                            jnp.where(eff, v, 0), mode="drop"))
                    elif kind == "min":
                        lanes.append(lv.at[tgt].min(
                            jnp.where(eff, v, lv.dtype.type(
                                jnp.iinfo(jnp.int64).max)
                                if dt == jnp.int64 else jnp.inf),
                            mode="drop"))
                    else:
                        lanes.append(lv.at[tgt].max(
                            jnp.where(eff, v, lv.dtype.type(
                                jnp.iinfo(jnp.int64).min)
                                if dt == jnp.int64 else -jnp.inf),
                            mode="drop"))
                new_states[d] = {
                    "keys": keys, "used": used, "bstart": bstart,
                    "groups": groups, "gnulls": gnulls,
                    "lanes": tuple(lanes),
                    "overflow": st["overflow"] + ovf,
                }
            return new_states

        return step

    # -- query side -------------------------------------------------------
    def duration_key(self, duration: str) -> str:
        """Normalize a `per '...'` duration spelling to the canonical
        DURATIONS key, validating it against this aggregation."""
        d = duration.lower().rstrip("'\" ")
        alias = {"sec": "seconds", "min": "minutes", "hour": "hours",
                 "day": "days", "month": "months", "year": "years"}
        d = alias.get(d, d)
        if d not in self.durations:
            raise CompileError(
                f"aggregation '{self.aggregation_id}' has no duration "
                f"'{duration}' (available: {self.durations})")
        return d

    def materialize(self, duration: str, start: Optional[int],
                    end: Optional[int]):
        """-> (schema, buffer dict) of finished+running buckets in the
        duration's table, filtered to [start, end] (AGG_TIMESTAMP)."""
        d = self.duration_key(duration)
        with self._lock:
            st = jax.device_get(self.states[d])
        return self.materialize_from(st, d, start, end)

    def materialize_from(self, st: dict, duration: str,
                         start: Optional[int], end: Optional[int]):
        """Materialize from ONE duration's HOST-side state dict (a
        device_get of `states[d]`, or one tenant's slot slice of a
        pool's stacked aggregation state — serving/pool.py
        materialize_tenant)."""
        self.duration_key(duration)
        import numpy as np
        valid = np.asarray(st["used"]).copy()
        bs = np.asarray(st["bstart"])
        if start is not None:
            valid &= bs >= start
        if end is not None:
            valid &= bs < end
        cols = []
        nulls = []
        for name, kind, payload in self.outputs:
            if kind == "group":
                # stored group columns follow group_attrs order
                gi = [a.name for a in self.group_attrs].index(
                    self.in_schema.attributes[payload].name)
                cols.append(np.asarray(st["groups"][gi]))
                nulls.append(np.asarray(st["gnulls"][gi]))
                continue
            lane_ids, out_t = payload
            lvs = [np.asarray(st["lanes"][i]) for i in lane_ids]
            if kind == "avg":
                s, nc = lvs
                cols.append(s / np.maximum(nc, 1))
                nulls.append(nc == 0)
            elif kind == "count":
                cols.append(lvs[0])
                nulls.append(np.zeros_like(valid))
            else:  # sum/min/max: null when no non-null values seen
                v, nc = lvs
                cols.append(np.where(nc == 0, np.zeros_like(v), v))
                nulls.append(nc == 0)
        cols.append(bs)
        nulls.append(np.zeros_like(valid))
        buf = {"cols": tuple(jnp.asarray(c) for c in cols),
               "nulls": tuple(jnp.asarray(n) for n in nulls),
               "ts": jnp.asarray(bs),
               "valid": jnp.asarray(valid)}
        return self.out_schema, buf

    # -- persistence ------------------------------------------------------
    def snapshot_state(self) -> dict:
        with self._lock:
            return jax.device_get(self.states)

    def restore_state(self, snap: dict) -> None:
        from .runtime import _fresh_device
        with self._lock:
            # fresh device buffers: snapshots hold host numpy that
            # device_put may alias zero-copy (see runtime._fresh_device)
            self.states = _fresh_device(snap)

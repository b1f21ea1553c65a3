"""Columnar event model — the TPU-native replacement for the reference's
pointer-linked event model.

Reference mapping:
- Event (ts + Object[] data)                  -> one row of an EventBatch
- StreamEvent type CURRENT/EXPIRED/TIMER/RESET (event/stream/StreamEvent.java:37)
                                              -> the `kind` column
- ComplexEventChunk (mutable linked list)     -> an EventBatch (fixed capacity,
                                                 validity mask)
- MetaStreamEvent (compile-time schema)       -> StreamSchema

An EventBatch is a pytree of device arrays: struct-of-arrays columns plus
timestamp / kind / validity lanes, all of one static capacity B. Invalid rows
are padding; operators must treat them as absent. Per-column null masks carry
Java null semantics through arithmetic (see ops/expr.py).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .types import AttrType, GLOBAL_STRINGS, np_dtype, null_value

# Event kinds (match reference ComplexEvent.Type ordinal semantics)
CURRENT = 0
EXPIRED = 1
TIMER = 2
RESET = 3


@dataclasses.dataclass(frozen=True)
class Attribute:
    name: str
    type: AttrType


@dataclasses.dataclass(frozen=True)
class StreamSchema:
    """Compile-time stream shape (= MetaStreamEvent)."""

    stream_id: str
    attributes: tuple[Attribute, ...]

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(a.name for a in self.attributes)

    @property
    def types(self) -> tuple[AttrType, ...]:
        return tuple(a.type for a in self.attributes)

    def index_of(self, name: str) -> int:
        for i, a in enumerate(self.attributes):
            if a.name == name:
                return i
        raise KeyError(f"stream '{self.stream_id}' has no attribute '{name}'")

    def type_of(self, name: str) -> AttrType:
        return self.attributes[self.index_of(name)].type


@jax.tree_util.register_pytree_node_class
class EventBatch:
    """A fixed-capacity micro-batch of events for one stream.

    cols[i] is the data column for attribute i; nulls[i] its null mask.
    Rows where ``valid`` is False are padding and carry no meaning.
    """

    __slots__ = ("ts", "cols", "nulls", "kind", "valid")

    def __init__(self, ts, cols, nulls, kind, valid):
        self.ts = ts
        self.cols = tuple(cols)
        self.nulls = tuple(nulls)
        self.kind = kind
        self.valid = valid

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.ts, self.cols, self.nulls, self.kind, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        ts, cols, nulls, kind, valid = children
        return cls(ts, cols, nulls, kind, valid)

    # -- shape helpers -----------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.ts.shape[0]

    def count(self):
        return jnp.sum(self.valid.astype(jnp.int32))

    @classmethod
    def empty(cls, schema: StreamSchema, capacity: int) -> "EventBatch":
        from .types import col_zeros
        cols = tuple(col_zeros(t, capacity) for t in schema.types)
        nulls = tuple(jnp.zeros((capacity,), dtype=jnp.bool_) for _ in schema.types)
        return cls(
            ts=jnp.zeros((capacity,), dtype=jnp.int64),
            cols=cols,
            nulls=nulls,
            kind=jnp.zeros((capacity,), dtype=jnp.int32),
            valid=jnp.zeros((capacity,), dtype=jnp.bool_),
        )

    def with_kind(self, kind_value: int) -> "EventBatch":
        return EventBatch(
            self.ts,
            self.cols,
            self.nulls,
            jnp.full_like(self.kind, kind_value),
            self.valid,
        )

    def mask(self, keep) -> "EventBatch":
        """Invalidate rows where ``keep`` is False (no compaction)."""
        return EventBatch(self.ts, self.cols, self.nulls, self.kind,
                          jnp.logical_and(self.valid, keep))


def batch_from_rows(
    schema: StreamSchema,
    rows: Sequence[Sequence[Any]],
    timestamps: Sequence[int],
    capacity: int,
    kinds: Sequence[int] | None = None,
) -> EventBatch:
    """Host-side: build a padded EventBatch from Python rows.

    Strings are interned into GLOBAL_STRINGS; None becomes (null mask, in-band
    placeholder).
    """
    n = len(rows)
    assert n <= capacity, (n, capacity)
    ts = np.zeros((capacity,), dtype=np.int64)
    ts[:n] = np.asarray(timestamps, dtype=np.int64)
    kind = np.zeros((capacity,), dtype=np.int32)
    if kinds is not None:
        kind[:n] = np.asarray(kinds, dtype=np.int32)
    valid = np.zeros((capacity,), dtype=np.bool_)
    valid[:n] = True

    cols = []
    nulls = []
    for i, t in enumerate(schema.types):
        dt = np_dtype(t)
        col = np.full((capacity,), null_value(t), dtype=dt)
        nul = np.zeros((capacity,), dtype=np.bool_)
        for r, row in enumerate(rows):
            v = row[i]
            if v is None:
                nul[r] = True
            elif t is AttrType.STRING:
                col[r] = GLOBAL_STRINGS.encode(v)
            elif t is AttrType.BOOL:
                col[r] = bool(v)
            else:
                col[r] = dt(v)
        cols.append(col)
        nulls.append(nul)
    return EventBatch(ts=ts, cols=tuple(cols), nulls=tuple(nulls), kind=kind,
                      valid=valid)


def batch_from_columns(
    schema: StreamSchema,
    ts,
    cols: Sequence,
    capacity: int | None = None,
) -> EventBatch:
    """Columnar fast-path ingest: build an EventBatch straight from numpy
    arrays (no per-row Python). STRING columns must already be dictionary
    codes (GLOBAL_STRINGS.encode). The TPU-native equivalent of the
    reference's Event[] send overload (InputHandler.java:63)."""
    ts = np.asarray(ts, dtype=np.int64)
    n = ts.shape[0]
    capacity = capacity or n
    assert n <= capacity, (n, capacity)
    if len(cols) != len(schema.types):
        raise ValueError(
            f"stream '{schema.stream_id}' expects {len(schema.types)} data "
            f"columns, got {len(cols)}")
    out_ts = np.zeros((capacity,), dtype=np.int64)
    out_ts[:n] = ts
    valid = np.zeros((capacity,), dtype=np.bool_)
    valid[:n] = True
    out_cols, out_nulls = [], []
    for t, c in zip(schema.types, cols):
        dt = np_dtype(t)
        col = np.zeros((capacity,), dtype=dt)
        col[:n] = np.asarray(c, dtype=dt)
        out_cols.append(col)
        out_nulls.append(np.zeros((capacity,), dtype=np.bool_))
    return EventBatch(ts=out_ts, cols=tuple(out_cols),
                      nulls=tuple(out_nulls),
                      kind=np.zeros((capacity,), dtype=np.int32),
                      valid=valid)


_UUID_BATCH_NONCE = itertools.count()


def rows_from_batch(schema_types: Sequence[AttrType], batch) -> list:
    """Host-side: decode a device EventBatch into
    (timestamp, kind, tuple(values)) rows, in row order, skipping padding.

    uuid() sentinel cells materialize here with a per-decode nonce in the
    key: unique across batches, stable within one decode. Callers that
    deliver one emission to several consumers decode once and share the
    rows (OutputFetch.rows)."""
    nonce = next(_UUID_BATCH_NONCE)
    ts = np.asarray(batch.ts)
    kind = np.asarray(batch.kind)
    valid = np.asarray(batch.valid)
    cols = [np.asarray(c) for c in batch.cols]
    nulls = [np.asarray(nl) for nl in batch.nulls]
    out = []
    for r in range(ts.shape[0]):
        if not valid[r]:
            continue
        vals = []
        for i, t in enumerate(schema_types):
            if nulls[i][r]:
                vals.append(None)
            elif t is AttrType.OBJECT:
                from .types import decode_set
                vals.append(decode_set(cols[i][r]))
            elif t is AttrType.STRING:
                vals.append(GLOBAL_STRINGS.decode(
                    cols[i][r], uuid_key=(nonce, int(ts[r]), r, i)))
            elif t is AttrType.BOOL:
                vals.append(bool(cols[i][r]))
            elif t in (AttrType.FLOAT, AttrType.DOUBLE):
                vals.append(float(cols[i][r]))
            else:
                vals.append(int(cols[i][r]))
        out.append((int(ts[r]), int(kind[r]), tuple(vals)))
    return out


class OutputFetch:
    """One step output's way to host rows, each part at most once per
    output: the host's wait for the device, the device-to-host copy and
    the row decode. Every delivery path of an emission (debugger, rate
    limiter, handlers, callbacks) shares one copy and one decoded row
    list, so uuid() cells agree across paths.

    While ``tracer`` records, the parts are the spans ``wait/<name>``,
    ``d2h/<name>`` (args ``capacity``, ``bytes``) and ``decode/<name>``
    (args ``rows``, the valid rows out). Otherwise this is one
    ``jax.device_get`` and one decode, with no sync of its own.
    ``decode`` turns the host batch into rows (``rows_from_batch``)."""

    __slots__ = ("tracer", "name", "types", "out", "decode", "_host",
                 "_rows")

    def __init__(self, tracer, name: str, types: Sequence[AttrType], out,
                 decode=rows_from_batch):
        self.tracer = tracer if tracer is not None and tracer.enabled \
            else None
        self.name = name
        self.types = types
        self.out = out
        self.decode = decode
        self._host = None
        self._rows = None

    def host(self, due=None):
        """The output on the host; with ``due``, ``(output, due)``, in
        the same copy where the output has not crossed yet."""
        if self._host is not None:
            return self._host if due is None \
                else (self._host, jax.device_get(due))
        tree = self.out if due is None else (self.out, due)
        tr = self.tracer
        if tr is None:
            got = jax.device_get(tree)
        else:
            with tr.span("wait", self.name):
                jax.block_until_ready(tree)
            nbytes = sum(int(x.nbytes)
                         for x in jax.tree_util.tree_leaves(tree))
            with tr.span("d2h", self.name, capacity=int(self.out.capacity),
                         bytes=nbytes):
                got = jax.device_get(tree)
        self._host = got if due is None else got[0]
        return got

    def rows(self, shape=None) -> list:
        """The output's valid rows, decoded once; ``shape`` (the query's
        host-side order-by) is applied to them inside the decode span."""
        if self._rows is not None and shape is None:
            return self._rows
        host = self.host()
        if self.tracer is None:
            return self._decode(host, shape)
        with self.tracer.span("decode", self.name,
                              rows=int(np.count_nonzero(host.valid))):
            return self._decode(host, shape)

    def _decode(self, host, shape):
        if self._rows is None:
            self._rows = self.decode(self.types, host)
        return self._rows if shape is None else shape(self._rows)

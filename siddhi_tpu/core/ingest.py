"""Packed columnar ingest: the high-throughput host->device path.

The reference's ingest hot path is InputHandler.send -> Disruptor ring
buffer (stream/StreamJunction.java:255-313). The TPU equivalent crosses
the host->device link once per chunk, so the wire format decides what each
chunk costs on the ingest side:

- EVERYTHING for a chunk travels in ONE 1-D uint8 buffer = one transfer =
  one RTT (a tuple of per-column arrays pays the round-trip per array);
- every dynamic scalar (row count, base timestamp, processing time, per-
  column bases) is embedded in the buffer header, so the jitted step takes
  no separate scalar arguments at all;
- each column is adaptively narrowed per chunk: constant columns ship zero
  bytes (base in the header), integer/string/long columns ship min-offset
  deltas in the narrowest of u8/u16/u32, timestamps detect arithmetic
  progressions ('aff': zero bytes + stride in the header), bools bit-pack
  to 1 bit/row, floats ship raw bits;
- encodings are STICKY per stream (they only ever widen), because the
  encoding tuple is part of the jit cache key — flapping between widths
  would trigger recompiles;
- chunks are zero-padded to the bucket capacity (zero tails compress to
  nothing on compressing transports and cost little raw);
- the validity mask / kind lane / null masks are NOT transferred at all —
  they are reconstructed on device from the header row count.

The jitted query step fuses unpacking with the operator chain, so ingest
costs one device_put per chunk and zero per-batch host round-trips.
"""
from __future__ import annotations

import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .event import EventBatch, StreamSchema
from .types import AttrType

_INT_FAMILY = (AttrType.INT, AttrType.STRING, AttrType.LONG)

# lane byte-width per row for each encoding code
_CODE_BYTES = {"c": 0, "aff": 0, "d8": 1, "d16": 2, "d32": 4,
               "f32": 4, "f64": 8, "raw64": 8}
# widening order within each family (sticky codes only move right)
_ORDER = ("c", "aff", "b1", "f32", "f64", "d8", "d16", "d32", "raw64")
_RANK = {c: i for i, c in enumerate(_ORDER)}


def _pad8(x: int) -> int:
    return (x + 7) & ~7


def _lane_bytes(code: str, capacity: int) -> int:
    if code == "b1":
        return capacity // 8
    return _CODE_BYTES[code] * capacity


def layout(n_cols: int, enc: tuple, capacity: int):
    """(header bytes, per-lane byte offsets, total buffer bytes).

    enc = (ts_code, col_code...). Header int64 slots:
    [0]=n, [1]=base_ts, [2]=now, [3]=ts_stride, [4+i]=col i base."""
    H = (4 + n_cols) * 8
    offs = []
    o = H
    for code in enc:
        offs.append(o)
        o += _pad8(_lane_bytes(code, capacity))
    return H, offs, o


def initial_encoding(schema: StreamSchema) -> tuple:
    """The sticky encoding a fresh PackedEncoder starts from (affine
    timestamps, every column constant). This is the encoding tuple the
    FIRST chunk of a stream compiles against unless the data forces a
    widening — the AOT compile service (core/compile.py) precompiles
    packed steps for it so cold starts hit a ready program."""
    return ("aff",) + ("c",) * len(schema.types)


def encoding_for_sample(schema: StreamSchema, ts, cols,
                        now: int = 0) -> tuple:
    """The sticky encoding a traffic sample settles on: run a throwaway
    encoder over the sample and return its (widened) tuple. Lets
    warmup() precompile the packed step real traffic will dispatch."""
    enc = PackedEncoder(schema)
    ts = np.ascontiguousarray(ts, dtype=np.int64)
    _, tup, _ = enc.encode(ts, cols, _sample_capacity(len(ts)), now)
    return tup


def _sample_capacity(n: int) -> int:
    cap = 8
    while cap < n:
        cap *= 2
    return cap


def zero_packed_buffer(schema: StreamSchema, enc: tuple, capacity: int):
    """A device-resident all-zero packed buffer for (enc, capacity) —
    the abstract argument the compile service warms packed steps with
    (header decodes as n=0: every row is padding)."""
    _, _, total = layout(len(schema.types), enc, capacity)
    return jax.device_put(np.zeros((total,), np.uint8))


def _int_code(span: int) -> str:
    if span < 2 ** 8:
        return "d8"
    if span < 2 ** 16:
        return "d16"
    if span < 2 ** 32:
        return "d32"
    return "raw64"


class PackedEncoder:
    """Per-stream sticky encoding chooser: codes only widen across chunks
    (each distinct encoding tuple is a separate XLA compile).

    The encode path is zero-copy where the wire format allows it: a
    caller column that already matches the lane dtype and C layout is
    bitcast-viewed straight into the packed buffer (no ``np.asarray``
    round trip, no defensive copy); coercions and per-lane copies are
    counted in ``stats`` and surface in ``statistics()['ingest']`` so
    regressions are visible. Every chunk gets a fresh zeroed host
    buffer: ``jax.device_put`` may alias it (CPU) or still be copying
    from it (TPU) after it returns, so a buffer is never rewritten."""

    def __init__(self, schema: StreamSchema):
        self.schema = schema
        self._ts_code = "aff"
        self._col_codes = ["c"] * len(schema.types)
        self.stats = {"chunks": 0, "rows": 0, "coerced_arrays": 0,
                      "view_lanes": 0, "copied_lanes": 0}

    def _widen(self, cur: str, cand: str) -> str:
        return cand if _RANK[cand] > _RANK[cur] else cur

    def _conform(self, arr, want) -> np.ndarray:
        """Zero-copy fast path: an already-conformant numpy column
        (dtype + C-contiguity match) passes through untouched; anything
        else pays one counted coercion copy."""
        if isinstance(arr, np.ndarray) and arr.dtype == want and \
                arr.flags.c_contiguous:
            return arr
        self.stats["coerced_arrays"] += 1
        return np.ascontiguousarray(arr, dtype=want)

    def _choose_codes(self, ts: np.ndarray, cols: Sequence):
        """Sticky code-choosing pass over one chunk: widens ``_ts_code``
        / ``_col_codes`` and returns the conformed columns (so callers
        never conform twice). Returns (n, conformed cols, ts span code).
        The span code is returned rather than folded immediately so a
        ROUND-wide widen (``widen_round``) can fold every chunk's span
        only once the round's final ts code is known."""
        n = int(ts.shape[0])
        types = self.schema.types
        if n >= 2:
            stride = int(ts[1]) - int(ts[0])
            is_aff = bool(np.all(np.diff(ts) == stride))
        else:
            is_aff = True
        tmin = int(ts.min()) if n else 0
        span_code = _int_code(int(ts.max()) - tmin) if n else "d8"
        ts_cand = "aff" if is_aff else span_code
        self._ts_code = self._widen(self._ts_code, ts_cand)
        conf = []
        for i, t in enumerate(types):
            if t in _INT_FAMILY:
                want = np.int64 if t is AttrType.LONG else np.int32
                c = self._conform(cols[i], want)
                lo = int(c.min()) if n else 0
                hi = int(c.max()) if n else 0
                cand = "c" if lo == hi else _int_code(hi - lo)
            elif t is AttrType.FLOAT:
                c = self._conform(cols[i], np.float32)
                u = c.view(np.uint32)
                cand = "c" if (n and (u == u[0]).all()) or n == 0 else "f32"
            elif t is AttrType.DOUBLE:
                c = self._conform(cols[i], np.float64)
                u = c.view(np.uint64)
                cand = "c" if (n and (u == u[0]).all()) or n == 0 else "f64"
            elif t is AttrType.BOOL:
                c = self._conform(cols[i], np.bool_)
                cand = "c" if (n == 0 or (c == c[0]).all()) else "b1"
            else:
                raise TypeError(f"cannot pack column type {t}")
            self._col_codes[i] = self._widen(self._col_codes[i], cand)
            conf.append(c)
        return n, conf, span_code

    @property
    def encoding(self) -> tuple:
        """The current sticky encoding tuple (the jit cache key the next
        assembled chunk will dispatch under)."""
        return (self._ts_code,) + tuple(self._col_codes)

    def widen_round(self, chunks: Sequence) -> tuple:
        """Pool-round pre-pass: sticky-widen the shared codes over EVERY
        slot's (ts, cols) chunk BEFORE any buffer is assembled, so all
        rows of one packed (slots, total) round buffer share ONE
        encoding tuple (= one jit cache key, zero recompiles on tenant
        churn). Folds every chunk's ts span once the round's final ts
        code is known — chunk A (affine) widened before chunk B flips
        the code off 'aff' must still ship deltas wide enough for A's
        span. Returns the settled encoding tuple."""
        spans = []
        for ts, cols in chunks:
            ts = self._conform(np.asarray(ts, np.int64), np.int64)
            _n, _c, span = self._choose_codes(ts, cols)
            spans.append(span)
        if self._ts_code != "aff":
            for span in spans:
                self._ts_code = self._widen(self._ts_code, span)
        return self.encoding

    def encode(self, ts: np.ndarray, cols: Sequence, capacity: int,
               now: int):
        """-> (buf np.uint8[total], enc tuple, n)."""
        assert capacity % 8 == 0, capacity
        ts = self._conform(ts, np.int64)
        n, conf, span_code = self._choose_codes(ts, cols)
        if self._ts_code != "aff":
            # once on a delta code, the width must cover THIS chunk's span
            # even when the chunk itself is affine (offsets would wrap)
            self._ts_code = self._widen(self._ts_code, span_code)
        enc = self.encoding
        _H, _offs, total = layout(len(self.schema.types), enc, capacity)
        buf = np.zeros((total,), np.uint8)
        self._assemble(ts, conf, capacity, now, buf)
        return buf, enc, n

    def encode_into(self, ts: np.ndarray, cols: Sequence, capacity: int,
                    now: int, out: np.ndarray):
        """Assemble one chunk into a CALLER-OWNED pre-zeroed buffer (one
        row of a pool round's (slots, total) stacked buffer) under the
        CURRENT sticky codes — the caller must have run ``widen_round``
        over the whole round first, so this never widens. Returns n."""
        assert capacity % 8 == 0, capacity
        ts = self._conform(ts, np.int64)
        return self._assemble(ts, cols, capacity, now, out)

    def _assemble(self, ts: np.ndarray, cols: Sequence, capacity: int,
                  now: int, buf: np.ndarray) -> int:
        """Write header + lanes for one chunk under the CURRENT sticky
        codes (already wide enough for this chunk's spans) into a zeroed
        buffer. ``cols`` may be raw caller arrays; they are conformed
        here if needed."""
        n = int(ts.shape[0])
        types = self.schema.types
        self.stats["chunks"] += 1
        self.stats["rows"] += n

        ts_code = self._ts_code
        if n >= 2:
            stride = int(ts[1]) - int(ts[0])
        else:
            stride = 0
        tmin = int(ts.min()) if n else 0
        base_ts = (int(ts[0]) if n else 0) if ts_code == "aff" else tmin

        ncols = []
        bases = []
        for i, t in enumerate(types):
            code = self._col_codes[i]
            if t in _INT_FAMILY:
                want = np.int64 if t is AttrType.LONG else np.int32
                c = self._conform(cols[i], want)
                lo = int(c.min()) if n else 0
                base = lo   # constant value when code == "c", else delta
            elif t is AttrType.FLOAT:
                c = self._conform(cols[i], np.float32)
                base = int(np.int64(np.float64(c[0]).view(np.int64))) \
                    if (code == "c" and n) else 0
            elif t is AttrType.DOUBLE:
                c = self._conform(cols[i], np.float64)
                base = int(c[:1].view(np.int64)[0]) if (code == "c" and n) \
                    else 0
            else:  # BOOL
                c = self._conform(cols[i], np.bool_)
                base = int(c[0]) if (code == "c" and n) else 0
            ncols.append((code, c))
            bases.append(base)

        enc = (ts_code,) + tuple(code for code, _ in ncols)
        H, offs, total = layout(len(types), enc, capacity)
        assert buf.nbytes == total, (buf.nbytes, total)
        hdr = buf[:H].view(np.int64)
        hdr[0] = n
        hdr[1] = base_ts
        hdr[2] = now
        hdr[3] = stride
        for i, b in enumerate(bases):
            hdr[4 + i] = b

        stats = self.stats

        def put(o: int, arr: np.ndarray, view: bool):
            """Write one lane; ``view`` marks a direct bitcast view of
            the (conformed) caller array — no intermediate temp."""
            raw = arr.view(np.uint8)
            buf[o:o + raw.nbytes] = raw
            stats["view_lanes" if view else "copied_lanes"] += 1

        # ts lane
        if ts_code == "raw64":
            put(offs[0], ts, view=True)
        elif ts_code != "aff":
            dt = {"d8": np.uint8, "d16": np.uint16,
                  "d32": np.uint32}[ts_code]
            put(offs[0], (ts - base_ts).astype(dt), view=False)

        for i, ((code, c), base) in enumerate(zip(ncols, bases)):
            o = offs[1 + i]
            if code == "c":
                continue
            if code == "b1":
                bits = np.zeros((capacity,), np.bool_)
                bits[:n] = c
                put(o, np.packbits(bits, bitorder="little"), view=False)
            elif code in ("f32", "f64"):
                put(o, c, view=True)
            elif code == "raw64":
                if c.dtype == np.int64:
                    put(o, c, view=True)
                else:
                    put(o, c.astype(np.int64), view=False)
            else:  # d8/d16/d32 deltas
                dt = {"d8": np.uint8, "d16": np.uint16,
                      "d32": np.uint32}[code]
                if _CODE_BYTES[code] < c.dtype.itemsize:
                    # the span fits the column's native dtype (e.g. d16
                    # from int32): subtract without the int64 temp
                    put(o, (c - c.dtype.type(base)).astype(dt), view=False)
                else:
                    put(o, (c.astype(np.int64) - base).astype(dt),
                        view=False)
        return n


def _bitcast_lane(buf, offset: int, capacity: int, width: int, dtype):
    raw = jax.lax.dynamic_slice(buf, (offset,), (capacity * width,))
    if width == 1:
        return raw.astype(dtype) if dtype != jnp.uint8 else raw
    return jax.lax.bitcast_convert_type(raw.reshape(capacity, width), dtype)


def unpack_buffer(schema: StreamSchema, enc: tuple, capacity: int, buf):
    """Device side (inside jit): single packed buffer -> (EventBatch, now).

    Rows >= n are padding; nulls are all-false (the packed path carries no
    nulls — null-bearing sends use the row path)."""
    types = schema.types
    C = len(types)
    H, offs, total = layout(C, enc, capacity)
    hdr = jax.lax.bitcast_convert_type(buf[:H].reshape(4 + C, 8), jnp.int64)
    n, base_ts, now, stride = hdr[0], hdr[1], hdr[2], hdr[3]
    rows = jnp.arange(capacity, dtype=jnp.int64)
    valid = rows < n

    ts_code = enc[0]
    if ts_code == "aff":
        ts = base_ts + stride * rows
    elif ts_code == "raw64":
        ts = _bitcast_lane(buf, offs[0], capacity, 8, jnp.int64)
    else:
        w = {"d8": 1, "d16": 2, "d32": 4}[ts_code]
        dt = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[w]
        ts = base_ts + _bitcast_lane(buf, offs[0], capacity, w,
                                     dt).astype(jnp.int64)
    ts = jnp.where(valid, ts, base_ts)

    cols = []
    for i, t in enumerate(types):
        code = enc[1 + i]
        o = offs[1 + i]
        base = hdr[4 + i]
        if t in _INT_FAMILY:
            out_dt = jnp.int64 if t is AttrType.LONG else jnp.int32
            if code == "c":
                col = jnp.full((capacity,), base).astype(out_dt)
            elif code == "raw64":
                col = _bitcast_lane(buf, o, capacity, 8, jnp.int64)
                col = col.astype(out_dt)
            else:
                w = {"d8": 1, "d16": 2, "d32": 4}[code]
                dt = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[w]
                col = (base + _bitcast_lane(buf, o, capacity, w,
                                            dt).astype(jnp.int64))
                col = col.astype(out_dt)
        elif t is AttrType.FLOAT:
            if code == "c":
                f = jax.lax.bitcast_convert_type(base, jnp.float64)
                col = jnp.full((capacity,), f.astype(jnp.float32))
            else:
                col = _bitcast_lane(buf, o, capacity, 4, jnp.float32)
        elif t is AttrType.DOUBLE:
            if code == "c":
                col = jnp.full(
                    (capacity,),
                    jax.lax.bitcast_convert_type(base, jnp.float64))
            else:
                col = _bitcast_lane(buf, o, capacity, 8, jnp.float64)
        else:  # BOOL
            if code == "c":
                col = jnp.full((capacity,), base != 0)
            else:
                bytes_ = buf[o:o + capacity // 8]
                idx = jnp.arange(capacity)
                col = ((bytes_[idx >> 3] >> (idx & 7).astype(jnp.uint8))
                       & 1).astype(jnp.bool_)
        cols.append(col)

    batch = EventBatch(
        ts=ts,
        cols=tuple(cols),
        nulls=tuple(jnp.zeros((capacity,), jnp.bool_) for _ in cols),
        kind=jnp.zeros((capacity,), jnp.int32),
        valid=valid,
    )
    return batch, now


class PackedChunk:
    """One device-resident packed chunk, shared by every subscriber of a
    junction (transferred once)."""

    __slots__ = ("buf", "enc", "capacity", "n", "last_ts", "ts_min")

    def __init__(self, buf, enc: tuple, capacity: int, n: int,
                 last_ts: int, ts_min=None):
        self.buf = buf              # ONE device uint8 array
        self.enc = enc              # static encoding tuple (jit cache key)
        self.capacity = capacity
        self.n = n
        self.last_ts = last_ts
        self.ts_min = ts_min        # host-known earliest ts (timer bounds)

    @classmethod
    def build(cls, encoder: PackedEncoder, ts, cols, capacity: int,
              now: int):
        buf, enc, n = encoder.encode(ts, cols, capacity, now)
        return cls(jax.device_put(buf), enc, capacity, n, int(ts[-1]),
                   ts_min=int(ts.min()) if len(ts) else None)


# -- double-buffered ingest pipeline -----------------------------------------

PIPELINE_SPLIT_DEFAULT = 262144


def pipeline_enabled() -> bool:
    """``SIDDHI_TPU_INGEST_PIPELINE=0`` kill switch (default on) for
    the double-buffered encode/dispatch overlap."""
    return os.environ.get("SIDDHI_TPU_INGEST_PIPELINE", "1").lower() \
        not in ("0", "off", "false")


def pipeline_split_cap() -> int:
    """Sub-chunk size the pipeline cuts oversized sends into
    (``SIDDHI_TPU_INGEST_PIPELINE_CHUNK`` overrides; must be a bucket
    from BATCH_BUCKETS to keep jit caches warm)."""
    raw = os.environ.get("SIDDHI_TPU_INGEST_PIPELINE_CHUNK", "")
    try:
        v = int(raw)
    except ValueError:
        v = 0
    return v if v > 0 else PIPELINE_SPLIT_DEFAULT


def pipeline_chunk_cap(n: int, max_cap: int) -> int:
    """Effective per-chunk cap under the pipeline: a send larger than
    the split cap is cut into sub-chunks so encode of chunk N+1 can
    overlap device work of chunk N even for one huge send_arrays call.
    The compile service mirrors this (core/compile.py specs) so warmed
    programs match what dispatch produces."""
    sub = pipeline_split_cap()
    return min(max_cap, sub) if n > sub else max_cap


class IngestPipeline:
    """Double-buffered ingest for one input handler: a single worker
    thread encodes chunk N+1 (pure numpy — the heavy ufuncs drop the
    GIL) while the caller thread dispatches chunk N, whose H2D copy and
    compute ride JAX async dispatch. The bounded futures window is the
    backpressure: the producer blocks in ``result()`` until the oldest
    encode lands, so at most DEPTH chunks are in flight and nothing
    queues beyond DEPTH encoded chunks —
    admission/429 decisions stay upstream (serving/qos.py).

    Donation-safe by construction: packed steps donate their state
    buffers (argnums 0-2) but never the packed chunk argument, so a
    chunk whose transfer is still in flight cannot be invalidated by
    the step consuming its predecessor."""

    DEPTH = 2

    def __init__(self, stream_id: str):
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"ingest-{stream_id}")
        self.stats = {"sends": 0, "chunks": 0, "encode_s": 0.0,
                      "dispatch_s": 0.0, "wall_s": 0.0, "overlap_s": 0.0}

    def run(self, n_chunks: int, encode, dispatch) -> None:
        """``encode(i) -> chunk`` on the worker thread; ``dispatch(i,
        chunk)`` on the caller thread, overlapped one chunk ahead."""
        from collections import deque
        from time import perf_counter
        t0 = perf_counter()
        enc_s = disp_s = 0.0

        def timed_encode(i):
            e0 = perf_counter()
            return encode(i), perf_counter() - e0

        futs = deque([self._pool.submit(timed_encode, 0)])
        try:
            for i in range(n_chunks):
                if i + 1 < n_chunks:
                    futs.append(self._pool.submit(timed_encode, i + 1))
                chunk, dt = futs.popleft().result()
                enc_s += dt
                d0 = perf_counter()
                dispatch(i, chunk)
                disp_s += perf_counter() - d0
        finally:
            while futs:  # dispatch failed: drain the lookahead encode
                f = futs.popleft()
                if not f.cancel():
                    try:
                        f.result(timeout=60)
                    except Exception:  # noqa: BLE001 — the dispatch
                        pass           # error already propagates
            wall = perf_counter() - t0
            st = self.stats
            st["sends"] += 1
            st["chunks"] += n_chunks
            st["encode_s"] += enc_s
            st["dispatch_s"] += disp_s
            st["wall_s"] += wall
            st["overlap_s"] += max(0.0, enc_s + disp_s - wall)

    def close(self) -> None:
        self._pool.shutdown(wait=True)

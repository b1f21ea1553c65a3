"""Chunk-span tracing: host-side spans around the chunk pipeline
(ingest -> junction -> step dispatch -> output fetch -> sink), recorded
into a bounded ring buffer and exportable as Chrome ``trace_event`` JSON
(``chrome://tracing`` / Perfetto loadable).

Span semantics on an async device pipeline: a span measures HOST wall
time. ``step/<q>`` covers a query's dispatch and its output handling;
where the output becomes host rows, three spans nest inside it
(core/event.py ``OutputFetch``): ``wait/<q>`` (the host blocked until
the step's output is ready), ``d2h/<q>`` (the copy) and ``decode/<q>``
(the row decode). Fused chains emit ONE span per segment
(``chain/<q1+q2+...>``) with the member query names in ``args``,
mirroring that the whole segment is a single XLA program.

Every span carries ``args["chunk"]``: the number the ingest boundary
gave the chunk that caused it (``ChunkTracer.chunk``), carried across
an ``@Async`` junction's thread hop, so one chunk's spans group in
Perfetto. While recording, each span is also a
``jax.profiler.TraceAnnotation`` of the same name: inside a profiler
session (``runtime.profile(path)``) the spans land in the trace's host
plane on the device ops' clock.

Recording is gated on ``tracer.enabled`` (default off; opt in via
``runtime.trace_start()`` or ``SIDDHI_TPU_TRACE=1``): a disabled span
is one attribute check, so the hot path stays free. The ring keeps the
newest ``capacity`` spans and counts the ones it evicts (``dropped``).
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "t0", "mirror")

    def __init__(self, tracer: "ChunkTracer", name: str, cat: str, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        chunk = getattr(tracer._local, "chunk", None)
        if chunk is not None:
            args["chunk"] = chunk
        self.args = args

    def __enter__(self):
        chunk = self.args.get("chunk")
        self.mirror = TraceAnnotation(self.name) if chunk is None \
            else TraceAnnotation(self.name, chunk=chunk)
        self.mirror.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_us = (time.perf_counter_ns() - self.t0) // 1000
        self.mirror.__exit__(None, None, None)
        self.tracer.record(self.name, self.cat, self.t0 // 1000, dur_us,
                           self.args)
        return False


class _ChunkScope:
    """Sets the calling thread's chunk number for the spans it opens."""

    __slots__ = ("local", "chunk", "prev")

    def __init__(self, local, chunk: int):
        self.local = local
        self.chunk = chunk

    def __enter__(self):
        self.prev = getattr(self.local, "chunk", None)
        self.local.chunk = self.chunk
        return self

    def __exit__(self, *exc):
        self.local.chunk = self.prev
        return False


class ChunkTracer:
    """Ring buffer of completed spans (newest ``capacity`` kept; the
    evicted ones counted in ``dropped``)."""

    CAP = 8192

    def __init__(self, capacity: int = CAP):
        self.enabled = os.environ.get("SIDDHI_TPU_TRACE", "") == "1"
        self._events = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_chunk = 0
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._events.maxlen

    # -- lifecycle -------------------------------------------------------
    def start(self, capacity: int = None) -> None:
        """Enable recording: clears buffered spans, the dropped count and
        the chunk numbering; ``capacity`` resizes the ring."""
        with self._lock:
            if capacity is not None:
                if capacity < 1:
                    raise ValueError(f"tracer capacity {capacity} < 1")
                self._events = collections.deque(maxlen=int(capacity))
            self._events.clear()
            self.dropped = 0
            self._next_chunk = 0
        self.enabled = True

    def stop(self) -> int:
        """Disable recording; returns the spans the ring evicted."""
        self.enabled = False
        return self.dropped

    # -- recording -------------------------------------------------------
    def span(self, kind: str, name: str, **args):
        """Context manager timing one pipeline stage; event name is
        ``<kind>/<name>`` (e.g. ``step/q1``, ``chain/q1+q2``)."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, f"{kind}/{name}", kind, args)

    def chunk(self, chunk: int = None):
        """Scope in which this thread's spans carry ``args["chunk"]``:
        ``chunk`` where given (a number carried across a thread hop),
        else the thread's current chunk, else the next number."""
        if not self.enabled:
            return NOOP_SPAN
        if chunk is None:
            chunk = self.current_chunk()
        if chunk is None:
            with self._lock:
                chunk = self._next_chunk
                self._next_chunk += 1
        return _ChunkScope(self._local, chunk)

    def current_chunk(self):
        """The calling thread's chunk number, or None."""
        return getattr(self._local, "chunk", None)

    def record(self, name: str, cat: str, ts_us: int, dur_us: int,
               args) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(
                (name, cat, ts_us, dur_us, threading.get_ident(), args))

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    # -- export ----------------------------------------------------------
    def export(self, path: str, annotations=None) -> str:
        """Write buffered spans as Chrome ``trace_event`` JSON ('X'
        complete events, microsecond timestamps); returns ``path``.

        Events are sorted by ``ts`` before writing — the ring buffer
        holds completion order, and Chrome/Perfetto only nest 'X' spans
        correctly from start-time-ordered input (an enclosing span
        completes AFTER its children, so buffer order is exactly
        wrong). ``annotations`` maps span names to extra ``args``
        entries — ``runtime.trace_export`` merges the cost profiler's
        measured device-time attribution here (obs/costmodel.py). The
        file's ``otherData`` holds the ring's capacity and the spans it
        evicted."""
        ann = annotations or {}
        events = sorted(self.events(), key=lambda e: e[2])
        trace = {
            "displayTimeUnit": "ms",
            "otherData": {"capacity": self.capacity,
                          "dropped": self.dropped},
            "traceEvents": [
                {"name": name, "cat": cat, "ph": "X", "ts": ts_us,
                 "dur": dur_us, "pid": os.getpid(), "tid": tid,
                 "args": {**dict(args), **ann.get(name, {})}}
                for name, cat, ts_us, dur_us, tid, args in events
            ],
        }
        with open(path, "w") as f:
            json.dump(trace, f)
        return path


def tracer_of(app):
    """The app's tracer, or None for an owner not wired to an app
    runtime (junctions/sinks can exist standalone)."""
    return getattr(app, "tracer", None) if app is not None else None


def maybe_span(app, kind: str, name: str, **args):
    """Span against ``app.tracer`` when the owner is wired to an app
    runtime, else a no-op."""
    tracer = tracer_of(app)
    if tracer is None:
        return NOOP_SPAN
    return tracer.span(kind, name, **args)


def maybe_chunk(app, chunk: int = None):
    """``app.tracer.chunk(chunk)`` when wired to an app runtime, else a
    no-op."""
    tracer = tracer_of(app)
    if tracer is None:
        return NOOP_SPAN
    return tracer.chunk(chunk)

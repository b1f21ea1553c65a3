"""Host-side stream layer: events, junctions, input handlers, callbacks.

Reference mapping:
- Event (io.siddhi.core.event.Event)            -> Event dataclass
- StreamJunction (stream/StreamJunction.java:61) -> StreamJunction (sync pub/sub;
  `@Async(buffer.size, workers, batch.size.max)` switches it to a bounded
  host-side micro-batch queue drained by a worker thread — the TPU-shaped
  stand-in for the reference's LMAX Disruptor ring buffer,
  StreamJunction.java:276-313. batch.size.max is the latency/throughput
  dial: small batches -> low latency, large -> high throughput; on the
  columnar send_arrays path it caps the device chunk size instead, since
  that path already pipelines device-side without a thread hop.)
- InputHandler (stream/input/InputHandler.java:28) -> InputHandler
- StreamCallback (stream/output/StreamCallback.java:38) -> StreamCallback
- QueryCallback (query/output/callback/QueryCallback.java:37) -> QueryCallback

The junction is the host edge of the device dataflow: queries subscribe as
receivers; events are handed over as row lists and each receiver decides how
to batch them onto the device.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Any, Callable, Optional, Sequence

from ..obs.tracing import maybe_chunk, maybe_span, tracer_of

log = logging.getLogger("siddhi_tpu.stream")


@dataclasses.dataclass
class Event:
    timestamp: int
    data: tuple
    is_expired: bool = False

    def __repr__(self):
        kind = "EXPIRED" if self.is_expired else "CURRENT"
        return f"Event{{ts={self.timestamp}, data={list(self.data)}, {kind}}}"


class Receiver:
    """A junction subscriber (query input or stream callback)."""

    def receive(self, events: list[Event]) -> None:
        raise NotImplementedError


# sentinel that stops an @Async junction's drain worker (a dedicated
# object, not None: the sentinel can be dequeued mid-coalesce and must
# survive the carry slot)
_STOP = object()

# set while a drain worker holds the app barrier dispatching a batch —
# lets chained @Async publishes detect they must not block on a full
# downstream buffer (see StreamJunction.publish)
_IN_DISPATCH = threading.local()


class StreamJunction:
    """Per-stream pub/sub hub. Synchronous: publish calls every receiver
    inline, preserving the reference's sync-mode semantics
    (StreamJunction.java:166-177)."""

    def __init__(self, stream_id: str, schema):
        self.stream_id = stream_id
        self.schema = schema
        self.receivers: list[Receiver] = []
        self.fault_junction: Optional["StreamJunction"] = None
        self.on_error_action: str = "LOG"
        # wired by the app runtime (junction_for): the owning app (error
        # store resolution) and the app-wide per-stream error counters
        self.app = None
        self.error_stats = None
        # per-stream ingest throughput (obs registry
        # siddhi.<app>.stream.<id>.throughput); lazily created when
        # statistics are enabled — marked at the host boundary, so the
        # numbers are free (no device syncs)
        self.throughput = None
        # fan-out fusion group (plan/optimizer.py FanoutGroup): when the
        # optimizer fused this junction's plain-query subscribers into
        # one program, the batch publish paths call it ONCE per chunk
        # instead of once per receiver; re-derived with the fused chains
        self.fanout = None
        self._lock = threading.Lock()
        # @Async state (None = synchronous junction)
        self.async_conf: Optional[tuple[int, int]] = None  # (buffer, batch)
        self._queue = None
        self._worker: Optional[threading.Thread] = None
        self._drained = threading.Condition()
        self._pending = 0
        self._app = None

    def subscribe(self, receiver: Receiver) -> None:
        self.receivers.append(receiver)
        # a new subscriber can break a fused insert-into segment's
        # single-consumer invariant — re-derive segments on a live app
        # (no-op before start; core/runtime._build_fused_chains)
        app = self.app
        if app is not None and getattr(app, "running", False):
            app._rebuild_fused_chains()

    # -- @Async micro-batch pipeline -------------------------------------
    def enable_async(self, app, buffer_size: int, batch_max: int) -> None:
        """Switch to async mode: publishes enqueue into a bounded buffer
        (backpressure blocks the producer, like the Disruptor's
        BlockingWaitStrategy) and one worker drains it, coalescing up to
        batch.size.max events per dispatch (StreamHandler batching).
        `workers` collapses to one: device steps serialize on the chip, so
        extra host threads only add contention."""
        import queue as _q
        self.async_conf = (int(buffer_size), int(batch_max))
        self._queue = _q.Queue(maxsize=int(buffer_size))
        self._app = app
        self._worker = threading.Thread(
            target=self._drain_loop, name=f"async-{self.stream_id}",
            daemon=True)
        self._worker.start()

    def _drain_loop(self) -> None:
        # publishes are pre-split to <= batch.size.max at enqueue, so this
        # only ever coalesces whole items (order preserved via `carry`;
        # the _STOP sentinel also rides the carry slot so it is never
        # lost when dequeued mid-coalesce). An item is (chunk, events):
        # the tracer's chunk number of the publish that queued it, which
        # the dispatch's spans carry (a coalesced batch: its first item's)
        import queue as _q
        _, batch_max = self.async_conf
        carry = None
        while True:
            item = carry if carry is not None else self._queue.get()
            carry = None
            if item is _STOP:
                with self._drained:
                    self._pending -= 1
                    self._drained.notify_all()
                return
            chunk, events = item
            batch = list(events)
            n_items = 1
            while len(batch) < batch_max:
                try:
                    nxt = self._queue.get_nowait()
                except _q.Empty:
                    break
                if nxt is _STOP or len(batch) + len(nxt[1]) > batch_max:
                    carry = nxt
                    break
                batch.extend(nxt[1])
                n_items += 1
            _IN_DISPATCH.active = True
            # @Async streams open the sampled SLO span at DISPATCH time
            # (queue wait is a saturation signal — async.depth — not
            # part of the ingest->emit latency; docs/observability.md)
            slo = getattr(self._app, "slo", None)
            tok = slo.ingest_begin(self.stream_id) if slo is not None \
                else None
            try:
                with self._app.barrier, maybe_chunk(self._app, chunk):
                    self._app.on_ingest(self.stream_id, batch)
                    self._publish_sync(batch)
            finally:
                if tok is not None:
                    slo.ingest_end(tok)
                _IN_DISPATCH.active = False
            with self._drained:
                self._pending -= n_items
                self._drained.notify_all()

    def flush_async(self, timeout: float = 30.0) -> None:
        """Block until every queued publish has been dispatched."""
        if self._queue is None:
            return
        import time as _t
        deadline = _t.monotonic() + timeout
        with self._drained:
            while self._pending > 0:
                remaining = deadline - _t.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"@Async stream '{self.stream_id}' did not drain "
                        f"within {timeout}s ({self._pending} pending)")
                self._drained.wait(remaining)

    def stop_async(self) -> None:
        if self._worker is None:
            return
        with self._drained:
            self._pending += 1
        self._queue.put(_STOP)
        self._worker.join(timeout=10)
        self._worker = None
        # later publishes fall back to the sync path instead of feeding a
        # dead queue (sends are already rejected by the running check)
        self._queue = None

    def mark_ingest(self, n: int) -> None:
        """Host-boundary stream throughput mark (obs); no-op with
        statistics OFF."""
        app = self.app
        if app is None or app.stats_level <= 0:
            return
        if self.throughput is None:
            from .stats import ThroughputTracker
            self.throughput = ThroughputTracker()
        self.throughput.mark(n)

    def count_error(self, n: int = 1) -> None:
        if self.error_stats is not None:
            self.error_stats.increment(self.stream_id, n)

    def publish_fault(self, events: list[Event], exc: Exception) -> bool:
        """Convert failing events + exception into fault events on the
        `!stream` junction; False when no fault junction is wired."""
        if self.fault_junction is None or not events:
            return False
        msg = f"{type(exc).__name__}: {exc}"
        self.fault_junction.publish([
            Event(e.timestamp, tuple(e.data) + (msg,),
                  is_expired=e.is_expired) for e in events])
        return True

    def store_error(self, events: list[Event], exc: Exception,
                    attempts: int = 1) -> bool:
        """Capture failing events into the app's error store for later
        replay; False when no app is wired (standalone junction)."""
        if self.app is None or not events:
            return False
        from ..resilience.errorstore import ErroredEvent
        self.app._error_store().store(
            self.app.name,
            ErroredEvent.from_events(
                self.stream_id, events, f"{type(exc).__name__}: {exc}",
                attempts=attempts, now=self.app.current_time()))
        return True

    def _handle_error(self, events: Optional[list[Event]],
                      exc: Exception) -> None:
        """@OnError routing (StreamJunction.handleError:368-430): STREAM
        converts the failing events + exception into fault events on the
        `!stream` junction; STORE captures them in the error store for
        replay; LOG (default) logs and continues."""
        self.count_error()
        if self.on_error_action == "STREAM" and events and \
                self.publish_fault(events, exc):
            return
        if self.on_error_action == "STORE" and events and \
                self.store_error(events, exc):
            log.warning(
                "stream '%s': %d event(s) routed to the error store "
                "after %s", self.stream_id, len(events), exc)
            return
        log.error("error processing events on stream '%s' (action=%s)",
                  self.stream_id, self.on_error_action, exc_info=exc)

    def publish(self, events: list[Event]) -> None:
        if not events:
            return
        queue = self._queue  # snapshot: stop_async may null it concurrently
        if queue is not None:
            # async mode: enqueue in <= batch.size.max slices; a full
            # buffer blocks the producer (Disruptor BlockingWaitStrategy).
            # EXCEPT when the producer is itself a drain worker holding
            # the app barrier (chained @Async streams): blocking there
            # deadlocks — no other worker can take the barrier to drain
            # this queue — so the slice is dispatched inline instead
            # (possible reordering against queued items, only in the
            # already-pathological full-buffer case; the reference's
            # Disruptor deadlocks outright in the same cycle).
            import queue as _q
            _, batch_max = self.async_conf
            tracer = tracer_of(self._app)
            chunk = tracer.current_chunk() if tracer is not None else None
            slices = [events[i:i + batch_max]
                      for i in range(0, len(events), batch_max)]
            for s in slices:
                if getattr(_IN_DISPATCH, "active", False):
                    try:
                        with self._drained:
                            self._pending += 1
                        queue.put_nowait((chunk, s))
                    except _q.Full:
                        with self._drained:
                            self._pending -= 1
                        # inline dispatch still advances the clock (the
                        # drain path does this before _publish_sync too);
                        # the worker already holds the app barrier
                        with self._app.barrier:
                            self._app.on_ingest(self.stream_id, s)
                            self._publish_sync(s)
                else:
                    with self._drained:
                        self._pending += 1
                    queue.put((chunk, s))
            return
        self._publish_sync(events)

    def _publish_sync(self, events: list[Event]) -> None:
        with maybe_span(self.app, "junction", self.stream_id,
                        events=len(events)):
            for r in list(self.receivers):
                try:
                    r.receive(events)
                except Exception as exc:  # noqa: BLE001 — fault-stream
                    self._handle_error(events, exc)  # contract

    def publish_batch(self, batch, last_ts: int) -> None:
        """Columnar fast path: receivers that implement process_batch get
        the device batch directly; row-oriented receivers get decoded
        events (decoded at most once)."""
        decoded = None

        def decode():
            from .event import EXPIRED, OutputFetch
            rows = OutputFetch(tracer_of(self.app), self.stream_id,
                               self.schema.types, batch).rows()
            return [Event(ts, vals, is_expired=(kind == EXPIRED))
                    for ts, kind, vals in rows]

        fanout = self.fanout
        with maybe_span(self.app, "junction", self.stream_id,
                        capacity=int(batch.capacity)):
            fanout_done = False
            for r in list(self.receivers):
                try:
                    if fanout is not None and fanout.covers(r):
                        # fused fan-out: ONE dispatch for every grouped
                        # subscriber (plan/optimizer.py), fired when the
                        # loop reaches the first member
                        if not fanout_done:
                            fanout_done = True
                            fanout.process_batch(batch, last_ts)
                    elif hasattr(r, "process_batch"):
                        r.process_batch(batch, last_ts)
                    else:
                        if decoded is None:
                            decoded = decode()
                        r.receive(decoded)
                except Exception as exc:  # noqa: BLE001 — fault-stream
                    if decoded is None:  # contract
                        try:
                            decoded = decode()
                        except Exception:  # noqa: BLE001
                            decoded = []
                    self._handle_error(decoded, exc)


class InputHandler:
    """User entry point for one stream (InputHandler.send overloads:
    Object[] / Event / Event[] — stream/input/InputHandler.java:40-75)."""

    def __init__(self, stream_id: str, junction: StreamJunction, app_runtime):
        self.stream_id = stream_id
        self.junction = junction
        self.app = app_runtime
        self._encoder = None  # lazy sticky PackedEncoder (core/ingest.py)
        self._pipeline = None  # lazy IngestPipeline (double-buffering)
        # serializes columnar sends per stream: the sticky encoder and
        # the pipeline worker are single-writer; ordering is always
        # _ingest_lock -> app.barrier (never the reverse)
        self._ingest_lock = threading.RLock()

    def send(self, data) -> None:
        if not self.app.running:
            raise RuntimeError(
                f"app '{self.app.name}' is not running; call start() first")
        now = self.app.current_time
        if isinstance(data, (list, tuple)) and len(data) == 0:
            return
        if isinstance(data, Event):
            events = [data]
        elif isinstance(data, (list, tuple)) and data and isinstance(
                data[0], Event):
            events = list(data)
        elif (isinstance(data, (list, tuple)) and data
              and isinstance(data[0], (list, tuple))):
            events = [Event(timestamp=now(), data=tuple(d)) for d in data]
        else:
            events = [Event(timestamp=now(), data=tuple(data))]
        self.junction.mark_ingest(len(events))
        buf = self.app._reorder.get(self.stream_id)
        if buf is not None:
            # bounded-lateness reorder buffer (resilience/ordering.py):
            # events are buffered, watermark-sorted and released through
            # _dispatch_rows; late events resolve per the stream policy
            with maybe_chunk(self.app), \
                    maybe_span(self.app, "ingest", self.stream_id,
                               events=len(events), buffered=1), \
                    self.app.barrier:
                buf.ingest_rows(events)
            return
        with maybe_chunk(self.app), \
                maybe_span(self.app, "ingest", self.stream_id,
                           events=len(events)):
            if self.junction._queue is not None:
                # @Async: hand off to the junction's worker, which
                # advances the clock when the batch is actually
                # dispatched
                self.junction.publish(events)
                return
            with self.app.barrier:
                self._dispatch_rows(events)

    def _dispatch_rows(self, events) -> None:
        """Row publish body (caller holds the app barrier unless the
        junction is @Async): advance the clock, publish, fire timers
        armed during processing. The reorder-buffer flush releases
        through here too, so buffered and direct ingest share one
        dispatch contract. The tracer's chunk number is assigned here
        unless the caller's thread already carries one."""
        with maybe_chunk(self.app):
            if self.junction._queue is not None:
                self.junction.publish(events)
                return
            # sampled ingest->emit span (obs/slo.py): queries that decode
            # host rows during this dispatch attribute against its start
            slo = self.app.slo
            tok = slo.ingest_begin(self.stream_id) if slo is not None \
                else None
            try:
                self.app.on_ingest(self.stream_id, events)
                self.junction.publish(events)
                # timers armed DURING processing (e.g. hop boundaries
                # the chunk's own event-time jump crossed) fire now, not
                # at the next external tick
                if self.app._playback and \
                        self.app._playback_time is not None:
                    self.app.scheduler.advance_to(
                        self.app._playback_time)
            finally:
                if tok is not None:
                    slo.ingest_end(tok)

    def send_arrays(self, ts, cols) -> None:
        """Columnar ingest: numpy timestamp + data column arrays
        (STRING columns as dictionary codes). Device batches with no
        per-row Python — the framework's intended high-throughput operating
        mode. Capacities are bucketed so jit caches stay warm.

        When every subscriber supports the packed path, a chunk travels as
        ONE adaptively-encoded uint8 buffer with one device transfer and
        zero per-batch host syncs (core/ingest.py); otherwise the
        EventBatch path is used."""
        if not self.app.running:
            raise RuntimeError(
                f"app '{self.app.name}' is not running; call start() first")
        n = len(ts)
        if n == 0:
            return
        self.app._columnar = True
        with self._ingest_lock:
            buf = self.app._reorder.get(self.stream_id)
            if buf is not None:
                # columnar reorder buffer: the chunk lands in numpy
                # segments; the watermark-driven flush re-emits sorted
                # chunks through _dispatch_arrays (same bucketed
                # capacities, zero new jits)
                self.junction.mark_ingest(n)
                with maybe_chunk(self.app), \
                        maybe_span(self.app, "ingest", self.stream_id,
                                   rows=n, buffered=1), self.app.barrier:
                    buf.ingest_columns(ts, cols)
                return
            self._dispatch_arrays(ts, cols)

    def _dispatch_arrays(self, ts, cols, mark: bool = True) -> None:
        """Columnar publish body: chunk to bucketed capacities and
        dispatch. Direct ingest and reorder-buffer releases share this
        path; releases pass mark=False (ingest throughput was already
        marked at arrival). When every receiver is packed-capable and
        the pipeline kill switch is on, multi-chunk sends run double-
        buffered: the pipeline worker encodes chunk N+1 while this
        thread dispatches chunk N (core/ingest.py IngestPipeline)."""
        from .ingest import (PackedEncoder, pipeline_chunk_cap,
                             pipeline_enabled)
        from .runtime import BATCH_BUCKETS
        n = len(ts)
        packed_ok = all(getattr(r, "supports_packed", False)
                        for r in self.junction.receivers)
        max_cap = BATCH_BUCKETS[-1]
        # sort-heavy receivers cap their step capacity (see runtime.py
        # SORT_HEAVY_CAP): chunk accordingly so every receiver can consume
        # the chunk without re-splitting. Packed consumers that scan
        # sub-batches inside the step (max_packed_capacity=None) take the
        # whole chunk in one dispatch instead.
        for r in self.junction.receivers:
            if packed_ok:
                rc = getattr(r, "max_packed_capacity",
                             getattr(r, "max_step_capacity", None))
            else:
                rc = getattr(r, "max_step_capacity", None)
            if rc is not None:
                max_cap = min(max_cap, rc)
        if self.junction.async_conf is not None:
            # @Async batch.size.max caps the device chunk on the columnar
            # path — the latency/throughput dial (small chunks = low
            # latency, big = throughput); no thread hop is added since
            # packed dispatch already pipelines device-side
            max_cap = min(max_cap, self.junction.async_conf[1])
        # cost-evidence chunk caps (plan/optimizer.py): a fused group or
        # chain head with measured per-capacity centers pins the chunk
        # size the evidence says is fastest per event
        fanout = self.junction.fanout
        if fanout is not None and fanout.preferred_cap:
            max_cap = min(max_cap, fanout.preferred_cap)
        for r in self.junction.receivers:
            pc = getattr(r, "preferred_ingest_cap", None)
            if pc:
                max_cap = min(max_cap, pc)
        if packed_ok and self._encoder is None:
            self._encoder = PackedEncoder(self.junction.schema)
        pipelined = packed_ok and pipeline_enabled()
        if pipelined:
            max_cap = pipeline_chunk_cap(n, max_cap)
        if pipelined and n > max_cap:
            self._dispatch_packed_pipelined(ts, cols, max_cap, mark)
            return
        for start in range(0, n, max_cap):
            t = ts[start:start + max_cap]
            c = [col[start:start + max_cap] for col in cols]
            self._dispatch_chunk(t, c, packed_ok, mark)

    def _dispatch_chunk(self, t, c, packed_ok: bool, mark: bool,
                        chunk=None) -> None:
        """Dispatch ONE bucketed chunk (serial and pipelined branches
        share this body; the pipelined branch passes a pre-encoded
        ``chunk``). The tracer's chunk number is assigned here unless
        the caller's thread already carries one (a reorder-buffer
        release inside the send that buffered it)."""
        from .event import batch_from_columns
        from .ingest import PackedChunk
        from .runtime import bucket_capacity
        last_ts = int(t[-1])
        if mark:
            self.junction.mark_ingest(len(t))
        # sampled ingest->emit span per device chunk (obs/slo.py)
        slo = self.app.slo
        tok = slo.ingest_begin(self.stream_id) if slo is not None \
            else None
        try:
            with maybe_chunk(self.app), \
                    maybe_span(self.app, "ingest", self.stream_id,
                               rows=len(t)), self.app.barrier:
                # columnar fast path: fire only dues STRICTLY BEFORE
                # the chunk's span now — in-span window expiry happens
                # inside the chunk's own step at exact per-row points,
                # so firing intermediate timers first only adds
                # dispatches (the post-publish advance_to below
                # catches up the rest)
                self.app.on_ingest_span(int(t[0]), last_ts)
                if packed_ok:
                    if chunk is None:
                        chunk = PackedChunk.build(
                            self._encoder, t, c, bucket_capacity(len(t)),
                            now=self.app.current_time())
                    self._publish_packed(chunk)
                else:
                    batch = batch_from_columns(
                        self.junction.schema, t, c,
                        capacity=bucket_capacity(len(t)))
                    self.junction.publish_batch(batch, last_ts)
                if self.app._playback:
                    # catch up timers the chunk's own steps did not
                    # subsume (multi-boundary batch flushes, absent
                    # deadlines past the span)
                    self.app.scheduler.advance_to(last_ts)
        finally:
            if tok is not None:
                slo.ingest_end(tok)

    def _publish_packed(self, chunk) -> None:
        fanout = self.junction.fanout
        fanout_done = False
        for r in list(self.junction.receivers):
            if fanout is not None and fanout.covers(r):
                # fused fan-out: one program for every grouped
                # subscriber (plan/optimizer.py)
                if not fanout_done:
                    fanout_done = True
                    fanout.process_packed(chunk)
                continue
            r.process_packed(chunk)

    def _dispatch_packed_pipelined(self, ts, cols, max_cap: int,
                                   mark: bool) -> None:
        """Double-buffered columnar dispatch: the pipeline worker
        encodes chunk N+1 (pure numpy — the heavy ufuncs release the
        GIL) while this thread dispatches chunk N (H2D + compute via
        JAX async dispatch). Playback ``now`` per chunk is precomputed
        host-side to the exact value the serial path's on_ingest_span
        would install, so both pipeline settings stay bit-identical
        (tests/test_ingest_pipeline.py)."""
        from .ingest import IngestPipeline, PackedChunk
        from .runtime import bucket_capacity
        app = self.app
        n = len(ts)
        slices = [(ts[s:s + max_cap], [col[s:s + max_cap]
                                       for col in cols])
                  for s in range(0, n, max_cap)]
        if app._playback:
            nows = []
            cur = app._playback_time
            reorder = bool(app._reorder)
            for t, _ in slices:
                last = int(t[-1])
                cur = max(last, cur) if (reorder and cur is not None) \
                    else last
                nows.append(cur)
        else:
            nows = [None] * len(slices)
        if self._pipeline is None:
            self._pipeline = IngestPipeline(self.stream_id)
        enc = self._encoder

        def encode(i):
            t, c = slices[i]
            now = nows[i]
            return PackedChunk.build(
                enc, t, c, bucket_capacity(len(t)),
                now=app.current_time() if now is None else now)

        def dispatch(i, chunk):
            t, c = slices[i]
            self._dispatch_chunk(t, c, True, mark, chunk=chunk)

        self._pipeline.run(len(slices), encode, dispatch)

    def _dispatch_device_batch(self, batch, first_ts: int,
                               last_ts: int) -> None:
        """Publish a device-resident EventBatch (reorder-ring release,
        resilience/ordering.py) under the same clock/timer contract as
        _dispatch_chunk — no host column transfer, no re-encode. The
        caller holds the app barrier (ring flushes run inside the
        ingest barrier section; the barrier is reentrant)."""
        slo = self.app.slo
        tok = slo.ingest_begin(self.stream_id) if slo is not None \
            else None
        try:
            with maybe_chunk(self.app), \
                    maybe_span(self.app, "ingest", self.stream_id,
                               rows=int(batch.capacity)), \
                    self.app.barrier:
                self.app.on_ingest_span(int(first_ts), int(last_ts))
                self.junction.publish_batch(batch, int(last_ts))
                if self.app._playback:
                    self.app.scheduler.advance_to(int(last_ts))
        finally:
            if tok is not None:
                slo.ingest_end(tok)

    def ingest_stats(self) -> Optional[dict]:
        """Zero-copy + pipeline counters for ``statistics()['ingest']``
        (core/runtime.py _collect_observability)."""
        out: dict = {}
        enc = self._encoder
        if enc is not None and enc.stats["chunks"]:
            out.update(enc.stats)
        p = self._pipeline
        if p is not None and p.stats["sends"]:
            st = p.stats
            busy = st["encode_s"] + st["dispatch_s"]
            out["pipeline_sends"] = st["sends"]
            out["pipeline_chunks"] = st["chunks"]
            out["encode_s"] = round(st["encode_s"], 6)
            out["dispatch_s"] = round(st["dispatch_s"], 6)
            out["wall_s"] = round(st["wall_s"], 6)
            out["overlap_s"] = round(st["overlap_s"], 6)
            out["overlap_frac"] = round(st["overlap_s"] / busy, 4) \
                if busy > 0 else 0.0
        return out or None

    def close(self) -> None:
        """Join the ingest pipeline worker (runtime shutdown)."""
        with self._ingest_lock:
            if self._pipeline is not None:
                self._pipeline.close()
                self._pipeline = None


class StreamCallback(Receiver):
    """Subscribe to a stream and receive raw events. Subclass and override
    receive(), or pass fn= to the constructor."""

    def __init__(self, fn: Optional[Callable[[list[Event]], None]] = None):
        self._fn = fn

    def receive(self, events: list[Event]) -> None:
        if self._fn is not None:
            self._fn(events)


class QueryCallback:
    """Per-query callback: receive(timestamp, in_events, removed_events),
    matching QueryCallback.receive(ts, inEvents, removeEvents)."""

    def __init__(self, fn: Optional[Callable] = None):
        self._fn = fn

    def receive(self, timestamp: int, in_events, removed_events) -> None:
        if self._fn is not None:
            self._fn(timestamp, in_events, removed_events)

"""One run of one cell: set-up, the timed window, the comparison with the
plain reference, and the numbers the metric readers read.

The window drives the program's user path: ``InputHandler.send_arrays``
-> the jitted step programs -> D2H and row decode -> a ``StreamCallback``
on the configuration's output stream. The callback keeps the delivered
``Event`` lists and the time they arrived; nothing else happens in it.
"""
from __future__ import annotations

import collections
import gc
import glob
import os
import shutil
import tempfile
import threading
import time

import numpy as np

from . import gen, xtrace

TRACE_S = 3.0      # length of the traced sub-window, whole chunks


class RunFailure(RuntimeError):
    """The run could not produce a result (warm-up error, bad file)."""


def compile_lookups() -> int:
    """Persistent-cache lookups in this process: one per program
    compiled, hit or miss."""
    from siddhi_tpu.core.compile import cache_counts
    c = cache_counts()
    return c["hits"] + c["misses"]


def _overflow(rt) -> int:
    parts = list(rt.queries.values()) + list(rt.partitions.values())
    return sum(int(p.overflow_total()) for p in parts
               if hasattr(p, "overflow_total"))


def rows_differing(got: list, want: list, how: str) -> int:
    """Rows missing, extra or wrong: position by position for a
    ``sequence``, as the two multisets' symmetric difference for a
    ``multiset``."""
    if how == "sequence":
        return sum(a != b for a, b in zip(got, want)) + \
            abs(len(got) - len(want))
    g, w = collections.Counter(got), collections.Counter(want)
    return sum((g - w).values()) + sum((w - g).values())


class Traced:
    """The traced sub-window: device trace, aligned spans, chunk counts."""

    def __init__(self, device, spans, chunks, events):
        self.device = device
        self.spans = spans
        self.self_ns = xtrace.self_ns(spans)
        self.chunks = chunks
        self.events = events

    def self_ns_of(self, *prefixes) -> int:
        return sum(v for k, v in self.self_ns.items()
                   if k.startswith(prefixes))

    def span_ns_of(self, *prefixes) -> int:
        return sum(e - s for name, s, e, _ in self.spans
                   if name.startswith(prefixes))


class Run:
    """What a metric reader reads (``metrics/<name>.py``: ``read(run)``)."""

    def __init__(self, cell, seconds: float, trace: bool):
        self.cell = cell
        self.seconds = seconds
        self.trace = trace
        self.setup_s = None
        self.warm = None
        self.window_s = None
        self.events_window = 0
        self.traced = None
        self.checks = []
        self.failed = None


class CellRun:
    def __init__(self, cell, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.run = Run(cell, seconds, trace)
        cfg, tr = cell.config, cell.traffic
        self.traffic = gen.Traffic(cfg, tr, seed)
        self.C = int(tr["chunk_rows"])
        if self.traffic.block % self.C:
            raise RunFailure(f"chunk_rows {self.C} does not divide "
                             f"block_events {self.traffic.block}")
        self.got = []            # (perf_counter_ns at delivery, [Event])
        self.cb_spans = []       # the callback's own spans while traced
        self.spans_on = False
        self.g = 0               # global index of the next event to send

    # -- set-up ------------------------------------------------------------
    @staticmethod
    def _codes(spec: dict, idx: np.ndarray) -> np.ndarray:
        """Label indices of a string column as the program's dictionary
        codes, the form send_arrays takes."""
        from siddhi_tpu.core.types import GLOBAL_STRINGS
        codes = np.array([GLOBAL_STRINGS.encode(x)
                          for x in gen.labels_for(spec)], np.int32)
        return codes[idx]

    def _send_columns(self):
        """The block's columns as send_arrays takes them; None for a
        column that carries the event's timestamp."""
        out = []
        for s in self.traffic.specs:
            if s["dist"] == "event_time":
                out.append(None)
                continue
            v = self.traffic.cols[s["name"]]
            out.append(self._codes(s, v) if s["type"] == "string" else v)
        return out

    def _chunk(self, g0: int):
        b0 = g0 % self.traffic.block
        ts = self.traffic.timestamps(g0, g0 + self.C)
        return ts, [ts if c is None else c[b0:b0 + self.C]
                    for c in self.block_cols]

    def _preload(self) -> None:
        """Rows sent once before the stream, stamped before its first
        event: a table's contents."""
        for pre in self.cell.config.get("preload", []):
            idx = gen.preload_rows(pre)
            cols = [self._codes(c, idx[c["name"]]) for c in pre["columns"]]
            ts = np.full(int(pre["rows"]), gen.TS0 - 1, np.int64)
            self.rt.get_input_handler(pre["stream"]).send_arrays(ts, cols)

    def _on_out(self, evs) -> None:
        t = time.perf_counter_ns()
        self.got.append((t, evs))
        # the rows kept for the comparison are the benchmark's, not the
        # program's: out of the collector's reach, no full collection
        # pays for scanning them (one v5e, ~0.95 kept rows per event: 4
        # collections took 0.45 s of a 10 s window)
        gc.freeze()
        if self.spans_on:
            self.cb_spans.append(("callback", t, time.perf_counter_ns(),
                                  threading.get_ident()))

    def setup(self) -> None:
        from siddhi_tpu import SiddhiManager, StreamCallback
        cfg = self.cell.config
        self.block_cols = self._send_columns()
        self.rt = SiddhiManager().create_siddhi_app_runtime(cfg["app"])
        self.rt.add_callback(cfg["output"], StreamCallback(fn=self._on_out))
        self.rt.start()
        self._preload()
        # one compile worker: with several, some processes on a v5e lower
        # the window app to a different module and miss the persistent
        # cache (PERF.md, Open questions); one keeps set-up the same work
        wu = self.rt.warmup(buckets=[self.C],
                            samples={cfg["stream"]: self._chunk(0)},
                            workers=1)
        if wu.get("errors"):
            raise RunFailure(f"warm-up errors: {wu['errors']}")
        self.run.warm = wu
        self.handler = self.rt.get_input_handler(cfg["stream"])
        for _ in range(int(self.cell.traffic.get("warm_chunks", 1))):
            self._send()
        self.plan_hash = self.rt.plan_hash()

    def _send(self) -> None:
        ts, cols = self._chunk(self.g)
        self.handler.send_arrays(ts, cols)
        self.g += self.C

    # -- the timed window --------------------------------------------------
    def window(self) -> None:
        import jax
        run, C = self.run, self.C
        seconds = run.seconds
        trace_at = seconds / 3 if run.trace else None
        trace_len = min(TRACE_S, seconds / 3)
        marker = None
        traced = None            # [first chunk, t0 perf ns, end chunk, t1]
        c0 = compile_lookups()
        t0 = time.perf_counter()
        k = 0
        while True:
            t_send = time.perf_counter()
            if t_send - t0 >= seconds:
                break
            if trace_at is not None:
                if traced is None and t_send - t0 >= trace_at:
                    marker = jax.profiler.TraceAnnotation(xtrace.MARKER)
                    marker.__enter__()
                    traced = [k, time.perf_counter_ns(), None, None]
                    self.rt.trace_start()
                    self.spans_on = True
                elif traced is not None and traced[2] is None and \
                        (time.perf_counter_ns() - traced[1]) / 1e9 \
                        >= trace_len:
                    traced[2:] = self._stop_trace(marker, k)
            self._send()
            k += 1
        run.window_s = time.perf_counter() - t0
        gc.unfreeze()
        if traced is not None and traced[2] is None:
            traced[2:] = self._stop_trace(marker, k)
        run.compiles_in_window = compile_lookups() - c0
        run.events_window = k * C
        self.traced_bounds = traced

    def _stop_trace(self, marker, k: int):
        t1 = time.perf_counter_ns()
        self.spans_on = False
        self.rt.trace_stop()
        marker.__exit__(None, None, None)
        return [k, t1]

    # -- after the window --------------------------------------------------
    def memory_peak(self) -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:self.cell.chips]]
        peaks = [p for p in peaks if p is not None]
        return int(max(peaks)) if peaks else None

    def reduce_trace(self, prof_dir: str) -> None:
        k0, a0, k1, _ = self.traced_bounds
        files = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RunFailure(f"{len(files)} profiler traces in {prof_dir}")
        dev = xtrace.DeviceTrace.from_file(files[0])
        off = dev.lo - a0
        spans = [(name, ts * 1000 + off, (ts + dur) * 1000 + off, tid)
                 for name, _, ts, dur, tid, _ in self.rt.tracer.events()]
        spans += [(n, s + off, e + off, tid)
                  for n, s, e, tid in self.cb_spans]
        self.run.traced = Traced(dev, spans, k1 - k0, (k1 - k0) * self.C)

    def compare(self) -> None:
        ts, cols = self.traffic.stream(self.g)
        want = self.cell.reference.reference(ts, cols, self.traffic.labels)
        got = [(e.timestamp, *e.data) for _, evs in self.got for e in evs]
        self.rows_checked = len(want)
        run = self.run
        run.rows_expected = len(want)
        run.failed = rows_differing(got, want, self.cell.config["compare"])
        # (name, value, "max" or "min", limit): each number compared
        run.checks = [
            ("rows_differing", run.failed, "max", 0),
            ("overflow", self.overflow, "max", 0),
            ("compiles_in_window", run.compiles_in_window, "max", 0),
            ("rows_expected", len(want), "min", 1),
        ]


def execute(cell, seed: int, seconds: float, trace: bool,
            t_start: float, keep_trace: str = None) -> tuple:
    """Run one cell once; returns (Run, CellRun). ``keep_trace`` copies
    the profiler trace there before it is removed."""
    import jax
    cr = CellRun(cell, seed, seconds, trace)
    prof_dir = tempfile.mkdtemp(prefix="siddhi_bench_prof_") \
        if trace else None
    try:
        cr.setup()
        print(f'{{"plan_hash": "{cr.plan_hash}", "compile_ms": '
              f'{cr.run.warm["compile_ms"]}, "cache_hits": '
              f'{cr.run.warm["cache_hits"]}, "cache_misses": '
              f'{cr.run.warm["cache_misses"]}}}', flush=True)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        cr.run.setup_s = time.perf_counter() - t_start
        try:
            cr.window()
        finally:
            if trace:
                jax.profiler.stop_trace()
        cr.run.memory_peak_bytes = cr.memory_peak()
        cr.overflow = _overflow(cr.rt)
        cr.rt.shutdown()
        if trace:
            if cr.traced_bounds is None:
                raise RunFailure("the window ended before the traced "
                                 "sub-window began")
            cr.reduce_trace(prof_dir)
            if keep_trace:
                shutil.copytree(prof_dir, keep_trace, dirs_exist_ok=True)
        cr.compare()
    finally:
        if prof_dir:
            shutil.rmtree(prof_dir, ignore_errors=True)
    return cr.run, cr

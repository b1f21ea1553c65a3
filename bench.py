"""Benchmark driver: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "configs": {...}}.

Covers all five BASELINE.md configs:
  1. filter        — StockStream stateless filter (SimpleFilterSingleQueryPerformance.java:51)
  2. window_agg    — lengthBatch(1000) + avg/sum (SimpleWindowSingleQueryPerformance.java)
  3. join          — 1s time-window join on symbol
  4. seq2          — 2-state sequence with cross-state predicate, within 5s
  5. kleene        — every (A+ -> B) with count() and within (variable-length NFA)
plus the north-star workload:
  seq5             — 5-state pattern chain over a single-event replay,
                     with p50/p99 per-chunk match latency.
and the chain-fusion workload:
  chain3           — 3-query insert-into chain, measured fused
                     (default: whole segment = one XLA program per
                     chunk) AND with SIDDHI_TPU_FUSE=0 per-query
                     dispatch.
and the plan-optimizer workload:
  fanout           — 1 stream -> 4 subscriber queries sharing a filter
                     prefix, measured optimized (one FanoutGroup
                     program per chunk, CSE-shared prefix) AND with
                     SIDDHI_TPU_OPT=0 per-query dispatch.

The headline metric/value is the north-star seq5 events/s. Each config
additionally flushes its own {"config": ...} JSON line the moment it
finishes, so a timed-out run leaves parseable partial results; the
summary line is always printed last.

vs_baseline: the reference repo publishes no numbers (BASELINE.md) and this
image has no JVM, so single-thread Java figures CANNOT be measured here.
Every entry therefore carries "baseline": "assumed" — the denominators below
are order-of-magnitude guesses for single-thread Java Siddhi on commodity
CPUs (the reference harness's typical range), NOT measurements:
  filter 1.0M ev/s, window_agg 700k, join 400k, seq2 400k, kleene 200k,
  seq5 300k.
"""
from __future__ import annotations

import json
import time

import numpy as np

import os

import jax
# importing bench.py (like importing siddhi_tpu) initializes no backend:
# the parent that spawns per-config children must never hold the chip
import siddhi_tpu
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.runtime import bucket_capacity
from siddhi_tpu.core.types import GLOBAL_STRINGS

ASSUMED = {
    "filter": 1_000_000.0,
    "window_agg": 700_000.0,
    "join": 400_000.0,
    "seq2": 400_000.0,
    "kleene": 200_000.0,
    "seq5": 300_000.0,
    # 3-query insert-into chain: per-hop dispatch costs put the Java
    # figure below the single-filter guess
    "chain3": 500_000.0,
    # same workload class as `join` (single-thread Java hash-join guess
    # is cardinality-insensitive at these sizes)
    "join_eq": 400_000.0,
    # 1 stream -> 4 subscriber queries: Java dispatches each query's
    # processor chain per event, so the guess is the filter figure
    # divided by the fan-out degree
    "fanout": 250_000.0,
    # same filter app, pipelined-ingest arm: the Java comparison point
    # is the single-threaded filter figure
    "ingest": 1_000_000.0,
}

# ---------------------------------------------------------------------------
# time-budget knobs: the r5 harness run hit its timeout (rc=124, empty
# tail), so the DEFAULT invocation must finish and print its JSON line
# inside the round budget. Three dials, all env-overridable:
#   SIDDHI_BENCH_SCALE       event-count multiplier (keeps chunk sizes
#                            and compiled-program shapes IDENTICAL so
#                            the .jax_cache still hits; only iteration
#                            counts shrink)
#   SIDDHI_BENCH_REPS        best-of-N repetitions per config
#   SIDDHI_BENCH_BUDGET_S    per-config subprocess timeout
#   SIDDHI_BENCH_DEADLINE_S  overall wall budget; configs that would
#                            start after it report {"skipped": ...}
# `python bench.py --quick` tightens all four for smoke runs;
# SIDDHI_BENCH_SCALE=1 SIDDHI_BENCH_DEADLINE_S=3600 restores the full
# r4-style measurement.
#   SIDDHI_BENCH_DISORDER=1  additionally measures the event-time
#                            reorder-buffer overhead (resilience/
#                            ordering.py) on the filter and seq5
#                            configs: events/s with a watermark buffer
#                            on ORDERED input vs the buffer-off main
#                            number ("disorder" key in the JSON line;
#                            docs/performance.md).
# ---------------------------------------------------------------------------
_env = os.environ.get
SCALE = float(_env("SIDDHI_BENCH_SCALE", "0.5") or 0.5)
REPS = int(_env("SIDDHI_BENCH_REPS", "3") or 3)
BUDGET_S = float(_env("SIDDHI_BENCH_BUDGET_S", "240") or 240)
DEADLINE_S = float(_env("SIDDHI_BENCH_DEADLINE_S", "420") or 420)
DISORDER = _env("SIDDHI_BENCH_DISORDER", "") not in ("", "0")


def _scaled(n: int, chunk: int = 1) -> int:
    """Scale an event count, rounded down to whole chunks (compiled step
    shapes stay fixed — only the number of steps changes)."""
    m = int(n * SCALE)
    return max(chunk, (m // chunk) * chunk)

SYMS = ("IBM", "WSO2", "GOOG", "MSFT")
TS0 = 1_700_000_000_000


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _warm(rt, n, chunk=None, extra_caps=(), samples=None):
    """AOT-compile the config's step programs (core/compile.py) and
    report the compile phase: compile_ms (parallel wall), persistent
    cache hits/misses, and program count. Runs BEFORE the timed first
    send, so `ttfr_ms` below measures dispatch-ready time-to-first-
    result, not a lazy compile stall.

    Also enables BASIC statistics: host-boundary counters only (no
    device syncs — docs/observability.md), so throughput/queue-depth
    gauges land in the per-config `metrics` snapshot for free."""
    rt.set_statistics_level("BASIC")
    caps = sorted({bucket_capacity(min(n, chunk or n)),
                   *map(bucket_capacity, extra_caps)})
    wu = rt.warmup(buckets=caps, samples=samples)
    return {"compile_ms": wu["compile_ms"],
            "warm_programs": wu["programs"],
            "cache_hits": wu["cache_hits"],
            "cache_misses": wu["cache_misses"]}


def _metrics_snapshot(rt):
    """Registry dump for the per-config JSON line (BENCH_r06+ records
    queue depths and latency histograms alongside events/s)."""
    try:
        return rt.metrics.collect()
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a run
        return {"error": f"{type(e).__name__}: {e}"}


def _plan_block(rt_or_pool):
    """Plan-explain block for the per-config JSON line (BENCH_r06+):
    {plan_hash, decisions} so the artifact records WHAT was measured —
    which queries fused, which join kernel ran and why, which window
    compaction variant was active — not just how fast it went
    (obs/explain.py; tools/bench_diff.py gates on the hash)."""
    try:
        rep = rt_or_pool.explain(live=False)
        return {"plan_hash": rep["plan_hash"],
                "decisions": rep["decisions"]}
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a run
        return {"error": f"{type(e).__name__}: {e}"}


def _audit_block(rt_or_pool):
    """Compiled-program audit block for the per-config JSON line
    (analysis/programs.py): {programs, bytes_est_total, findings} — the
    artifact records that every program measured was statically clean
    (donation aliased, no host callbacks, strong dtypes) at the jaxpr
    level, with zero extra executions or compiles. `store=False`: the
    bench line is the artifact; don't mutate the service telemetry
    after the measured stats were snapshotted. An audit that cannot run
    fails the config: the artifact must not claim a clean program set."""
    rep = rt_or_pool.audit_programs(store=False)
    return {k: rep[k] for k in ("programs", "bytes_est_total",
                                "findings")}


def _stage_breakdown(rt, send):
    """Per-step cost attribution (obs/costmodel.py), run AFTER the timed
    reps — every sampled chunk serializes the pipeline, so it must never
    overlap a measurement. Stride 1: the single `send()` pass times every
    step once; the ranked report lands in the config's `stage_breakdown`
    field and merges into <state dir>/costs.json for the cost-aware DAG
    optimizer (ROADMAP item 5)."""
    try:
        rt.cost_start(every=1)
        send()
        report = rt.cost_report()
        rt.cost_stop()
        rt.cost_save()
        return report
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a run
        return {"error": f"{type(e).__name__}: {e}"}


FRONTIER_CHUNKS = (64, 256, 1024)
FRONTIER_ITERS = int(_env("SIDDHI_BENCH_FRONTIER_ITERS", "32") or 32)


def _frontier(send_chunk, events_per_iter, chunks=FRONTIER_CHUNKS,
              iters=FRONTIER_ITERS):
    """Latency/throughput frontier (ROADMAP item 3's acceptance
    artifact; the TiLT-style time-centric batching trade-off): per-chunk
    synchronous send->drain latency at small/medium/large chunk sizes.
    Each row is {chunk, events_per_s, p50_ms, p95_ms, p99_ms} — small
    chunks buy match latency, large chunks buy events/s; the recorded
    curve makes the dial's cost explicit per config."""
    rows = []
    for c in chunks:
        try:
            send_chunk(c)   # warm this bucket's programs off the clock
            ms = []
            t0 = time.perf_counter()
            for _ in range(iters):
                c0 = time.perf_counter()
                send_chunk(c)
                ms.append((time.perf_counter() - c0) * 1000.0)
            total = time.perf_counter() - t0
            arr = np.array(ms)
            rows.append({
                "chunk": c,
                "events_per_s": round(events_per_iter(c) * iters / total,
                                      1),
                "p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p95_ms": round(float(np.percentile(arr, 95)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3)})
        except Exception as e:  # noqa: BLE001 — telemetry must not fail
            rows.append({"chunk": c,
                         "error": f"{type(e).__name__}: {e}"})
    return rows


def _entry(name, events, seconds, extra=None):
    eps = events / seconds
    d = {"value": round(eps, 1), "unit": "events/s",
         "events": events, "seconds": round(seconds, 3),
         "vs_baseline": round(eps / ASSUMED[name], 3),
         "baseline": "assumed"}
    if extra:
        d.update(extra)
    return d


def _drain(outs):
    jax.block_until_ready([o.valid for o in outs])
    outs.clear()


class _Last:
    """One-slot output holder: keeps only the newest device batch alive so
    a long pipelined run does not accumulate output buffers in HBM (device
    execution is in-order — syncing the last batch syncs them all)."""

    def __init__(self):
        self.out = None

    def __call__(self, out):
        self.out = out

    def drain(self):
        if self.out is not None:
            jax.block_until_ready(self.out.valid)
            self.out = None


FILTER_APP = """
    @app:playback
    define stream StockStream (symbol string, price float, volume long);
    @info(name = 'q')
    from StockStream[price > 100.0]
    select symbol, price
    insert into OutputStream;
"""


def _reorder_overhead(app_ql, stream, n, dt_off, mk_chunks, samples,
                      lateness_ms=1000):
    """SIDDHI_BENCH_DISORDER: measure the reorder-buffer tax on ORDERED
    input — the same app with an `@app:watermark` ingest buffer, same
    traffic volume, best-of-REPS (docs/performance.md). mk_chunks(i)
    yields the chunks for rep i with a monotone clock (buffered tails
    from rep i flush with rep i+1's watermark progress)."""
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(
        f"@app:watermark(lateness='{lateness_ms}')" + app_ql)
    outs = []
    next(iter(rt.queries.values())).batch_callbacks.append(outs.append)
    rt.start()
    h = rt.get_input_handler(stream)
    _warm(rt, n, samples=samples)

    def one_rep(i):
        for ts, cols in mk_chunks(i):
            h.send_arrays(ts, cols)
        _drain(outs)

    one_rep(0)   # warmup rep: encodings + release-cut buckets settle
    dt_on = min(_timed(lambda i=i: one_rep(i)) for i in range(1, REPS + 1))
    rt.flush_watermarks(final=True)
    _drain(outs)
    rt.shutdown()
    return {
        "eps_buffer_on": round(n / dt_on, 1),
        "eps_buffer_off": round(n / dt_off, 1),
        "reorder_overhead_pct": round((dt_on / dt_off - 1.0) * 100.0, 1),
        "lateness_ms": lateness_ms,
    }


def bench_filter(n=1_000_000):
    n = _scaled(n)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(FILTER_APP)
    q = rt.queries["q"]
    outs = []
    q.batch_callbacks.append(outs.append)
    rt.start()
    h = rt.get_input_handler("StockStream")
    rng = np.random.default_rng(7)
    syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)
    ts = TS0 + np.arange(n, dtype=np.int64)
    sym = syms[rng.integers(0, len(syms), n)]
    price = rng.uniform(0, 200, n).astype(np.float32)
    vol = rng.integers(1, 1000, n, dtype=np.int64)
    cinfo = _warm(rt, n, samples={"StockStream": (ts, [sym, price, vol])})
    ttfr = _timed(lambda: (h.send_arrays(ts, [sym, price, vol]),
                           _drain(outs)))          # first result, post-AOT
    # best-of-3: one timed run is hostage to transient host contention
    # (the r4 driver capture measured 2-6x below the builder's runs)
    dt = min(_timed(lambda: (h.send_arrays(ts, [sym, price, vol]),
                             _drain(outs))) for _ in range(REPS))
    dis = None
    if DISORDER:
        # reorder-buffer overhead on ordered input (monotone per-rep
        # clock: each rep's tail flushes with the next rep's watermark)
        def mk(i):
            t = ts + np.int64(i) * n
            return [(t, [sym, price, vol])]
        dis = _reorder_overhead(FILTER_APP, "StockStream", n, dt, mk,
                                {"StockStream": (ts, [sym, price, vol])})
    # AFTER the timed reps: one DETAIL-probed chunk so the registry dump
    # carries a real per-step latency summary (DETAIL serializes the
    # pipeline — docs/observability.md — so it must never overlap the
    # measurement)
    rt.lat_sample_every = 1
    rt.set_statistics_level("DETAIL")
    h.send_arrays(ts[:1024], [sym[:1024], price[:1024], vol[:1024]])
    sb = _stage_breakdown(rt, lambda: (
        h.send_arrays(ts[:8192], [sym[:8192], price[:8192], vol[:8192]]),
        _drain(outs)))
    met = _metrics_snapshot(rt)
    plan = _plan_block(rt)
    audit = _audit_block(rt)
    rt.shutdown()
    extra = {"ttfr_ms": round(ttfr * 1000.0, 1), "metrics": met,
             "plan": plan, "audit": audit, "stage_breakdown": sb, **cinfo}
    if dis is not None:
        extra["disorder"] = dis
    return _entry("filter", n, dt, extra=extra)


def bench_ingest(n=1_048_576):
    """Pipelined ingest (core/ingest.py IngestPipeline): encode chunk
    N+1 on the worker thread while chunk N's H2D+compute rides JAX
    async dispatch. Both modes send IDENTICAL sub-chunk shapes — the
    serial arm (SIDDHI_TPU_INGEST_PIPELINE=0) chunks by hand — so the
    delta is pure overlap, not a chunking confound. The
    `ingest_overlap` block records encode vs dispatch wall time and
    the overlap fraction from InputHandler.ingest_stats()."""
    n = _scaled(n, chunk=1024)
    sub = bucket_capacity(max(1024, n // 8))
    rng = np.random.default_rng(7)
    syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)
    ts = TS0 + np.arange(n, dtype=np.int64)
    sym = syms[rng.integers(0, len(syms), n)]
    price = rng.uniform(0, 200, n).astype(np.float32)
    vol = rng.integers(1, 1000, n, dtype=np.int64)
    saved = {k: os.environ.get(k) for k in
             ("SIDDHI_TPU_INGEST_PIPELINE",
              "SIDDHI_TPU_INGEST_PIPELINE_CHUNK")}

    def one(pipelined):
        os.environ["SIDDHI_TPU_INGEST_PIPELINE"] = \
            "1" if pipelined else "0"
        os.environ["SIDDHI_TPU_INGEST_PIPELINE_CHUNK"] = str(sub)
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(FILTER_APP)
        outs = []
        rt.queries["q"].batch_callbacks.append(outs.append)
        rt.start()
        h = rt.get_input_handler("StockStream")
        cinfo = _warm(rt, n, chunk=sub,
                      samples={"StockStream": (ts, [sym, price, vol])})

        def send():
            if pipelined:
                h.send_arrays(ts, [sym, price, vol])
            else:
                for s in range(0, n, sub):
                    e = s + sub
                    h.send_arrays(ts[s:e],
                                  [sym[s:e], price[s:e], vol[s:e]])
            _drain(outs)

        send()  # warmup rep: sticky encodings settle
        dt = min(_timed(send) for _ in range(REPS))
        st = h.ingest_stats() or {}
        rt.shutdown()
        return dt, st, cinfo

    try:
        dt_off, st_off, _ = one(pipelined=False)
        dt_on, st_on, cinfo = one(pipelined=True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    overlap = {
        "chunk_rows": sub,
        "chunks_per_send": -(-n // sub),
        "encode_s": st_on.get("encode_s"),
        "dispatch_s": st_on.get("dispatch_s"),
        "wall_s": st_on.get("wall_s"),
        "overlap_s": st_on.get("overlap_s"),
        "overlap_frac": st_on.get("overlap_frac"),
        "eps_pipeline": round(n / dt_on, 1),
        "eps_serial": round(n / dt_off, 1),
        "pipeline_speedup": round(dt_off / dt_on, 3),
        "zero_copy": {k: st_on.get(k) for k in
                      ("view_lanes", "copied_lanes", "coerced_arrays")},
        "serial_zero_copy": {k: st_off.get(k) for k in
                             ("view_lanes", "copied_lanes",
                              "coerced_arrays")},
    }
    return _entry("ingest", n, dt_on,
                  extra={"ingest_overlap": overlap, **cinfo})


CHAIN3_APP = """
    @app:playback
    define stream S (sym string, v int, price float);
    @info(name = 'q1')
    from S[v > 3] select sym, v, price insert into S1;
    @info(name = 'q2')
    from S1[price > 10.0] select sym, v, price insert into S2;
    @info(name = 'q3')
    from S2[v < 900] select sym, v, price insert into OutS;
"""


def _run_chain3(n: int, fused: bool):
    """One chain3 measurement; SIDDHI_TPU_FUSE toggles whole-segment
    fusion (read at app start — see docs/performance.md)."""
    prev = os.environ.get("SIDDHI_TPU_FUSE")
    os.environ["SIDDHI_TPU_FUSE"] = "1" if fused else "0"
    try:
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(CHAIN3_APP)
        q3 = rt.queries["q3"]
        outs = _Last()
        q3.batch_callbacks.append(outs)
        rt.start()
        assert (rt.queries["q1"]._fused_chain is not None) == fused
        h = rt.get_input_handler("S")
        rng = np.random.default_rng(13)
        syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)
        ts = TS0 + np.arange(n, dtype=np.int64)
        sym = syms[rng.integers(0, len(syms), n)]
        v = rng.integers(0, 1000, n).astype(np.int32)
        price = rng.uniform(0, 200, n).astype(np.float32)
        cinfo = _warm(rt, n, samples={"S": (ts, [sym, v, price])})
        ttfr = _timed(lambda: (h.send_arrays(ts, [sym, v, price]),
                               outs.drain()))
        dt = min(_timed(lambda: (h.send_arrays(ts, [sym, v, price]),
                                 outs.drain())) for _ in range(REPS))
        if fused:
            # fused run only: the breakdown names the chain/q1+q2+q3
            # center (one XLA program — docs/observability.md)
            cinfo["stage_breakdown"] = _stage_breakdown(rt, lambda: (
                h.send_arrays(ts[:8192], [sym[:8192], v[:8192],
                                          price[:8192]]),
                outs.drain()))
        cinfo["metrics"] = _metrics_snapshot(rt)
        cinfo["plan"] = _plan_block(rt)
        cinfo["audit"] = _audit_block(rt)
        rt.shutdown()
        return dt, ttfr, cinfo
    finally:
        if prev is None:
            os.environ.pop("SIDDHI_TPU_FUSE", None)
        else:
            os.environ["SIDDHI_TPU_FUSE"] = prev


def bench_chain3(n=1_048_576):
    """3-query insert-into chain (Q1 -> S1 -> Q2 -> S2 -> Q3): the chain
    fusion workload. Measures both the fused default (whole segment =
    one XLA program per chunk) and SIDDHI_TPU_FUSE=0 per-query dispatch;
    the headline value is the fused number."""
    n = _scaled(n)
    dt_fused, ttfr, cinfo = _run_chain3(n, fused=True)
    dt_unfused, _, _ = _run_chain3(n, fused=False)
    return _entry("chain3", n, dt_fused, extra={
        "fused_eps": round(n / dt_fused, 1),
        "unfused_eps": round(n / dt_unfused, 1),
        "fused_speedup": round(dt_unfused / dt_fused, 3),
        "ttfr_ms": round(ttfr * 1000.0, 1), **cinfo,
    })


# wide record, narrow projections: the shared work (packed-buffer
# unpack + the common two-filter prefix) is the bulk of each
# subscriber's program, which is exactly what fan-out fusion + CSE
# deduplicate. q1/q2 share the FULL prefix including the projection
# (nested CSE trie class), q3/q4 diverge at the projection.
FANOUT_APP = """
    @app:playback
    define stream S (sym string, price float, qty long, bid float,
                     ask float, vol long);
    @info(name = 'q1')
    from S[price * qty > 500.0 and ask - bid < 5.0][vol > 10]
        select sym, price insert into O1;
    @info(name = 'q2')
    from S[price * qty > 500.0 and ask - bid < 5.0][vol > 10]
        select sym, price insert into O2;
    @info(name = 'q3')
    from S[price * qty > 500.0 and ask - bid < 5.0][vol > 10]
        select sym, ask - bid as spread insert into O3;
    @info(name = 'q4')
    from S[price * qty > 500.0 and ask - bid < 5.0][vol > 10]
        select sym, vol insert into O4;
"""


def _run_fanout(n: int, chunk: int, optimized: bool):
    """One fanout measurement; SIDDHI_TPU_OPT toggles the plan
    optimizer (read at app start — docs/performance.md "Plan
    optimizer"). Optimized: ONE FanoutGroup program per chunk with the
    shared filter prefix evaluated once (CSE); unoptimized: four
    per-query dispatches, each unpacking the chunk and re-evaluating
    the same filter."""
    prev = os.environ.get("SIDDHI_TPU_OPT")
    os.environ["SIDDHI_TPU_OPT"] = "1" if optimized else "0"
    try:
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(FANOUT_APP)
        lasts = []
        for qn in ("q1", "q2", "q3", "q4"):
            last = _Last()
            rt.queries[qn].batch_callbacks.append(last)
            lasts.append(last)
        rt.start()
        fo = rt.junctions["S"].fanout
        assert (fo is not None) == optimized, "optimizer toggle failed"
        h = rt.get_input_handler("S")
        rng = np.random.default_rng(23)
        syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)
        ts = TS0 + np.arange(n, dtype=np.int64)
        cols = [syms[rng.integers(0, len(syms), n)],
                rng.uniform(0, 200, n).astype(np.float32),
                rng.integers(1, 100, n, dtype=np.int64),
                rng.uniform(0, 100, n).astype(np.float32),
                rng.uniform(0, 100, n).astype(np.float32),
                rng.integers(1, 1000, n, dtype=np.int64)]

        def send():
            for s in range(0, n, chunk):
                h.send_arrays(ts[s:s + chunk],
                              [c[s:s + chunk] for c in cols])
            for last in lasts:
                last.drain()

        cinfo = _warm(rt, n, chunk=chunk,
                      samples={"S": (ts[:chunk],
                                     [c[:chunk] for c in cols])})
        ttfr = _timed(send)
        dt = min(_timed(send) for _ in range(REPS))
        if optimized:
            # optimized run only: the breakdown names the fanout/S
            # center (one XLA program for all four subscribers) and its
            # per-capacity sub-centers feed the optimizer's chunk-cap
            # evidence (plan/optimizer.py)
            cinfo["stage_breakdown"] = _stage_breakdown(rt, send)
        cinfo["metrics"] = _metrics_snapshot(rt)
        cinfo["plan"] = _plan_block(rt)
        cinfo["audit"] = _audit_block(rt)
        rt.shutdown()
        return dt, ttfr, cinfo
    finally:
        if prev is None:
            os.environ.pop("SIDDHI_TPU_OPT", None)
        else:
            os.environ["SIDDHI_TPU_OPT"] = prev


def bench_fanout(n=1_048_576, chunk=None):
    """1 stream -> 4 subscriber queries sharing one filter prefix: the
    fan-out fusion + CSE workload (ROADMAP item 5 acceptance). Measures
    the optimized default (one fused program per chunk, shared prefix)
    against SIDDHI_TPU_OPT=0 per-query dispatch; the headline value is
    the optimized number and the plan block records the group decision
    with its cause slug.

    On a CPU run the HOST-side packed-buffer encode — identical in both
    arms — bounds the gap; a speedup is read off a chip run only.
    SIDDHI_BENCH_FANOUT_CHUNK overrides the chunk size."""
    chunk = chunk or int(_env("SIDDHI_BENCH_FANOUT_CHUNK", "32768")
                         or 32768)
    n = _scaled(n, chunk)
    dt_opt, ttfr, cinfo = _run_fanout(n, chunk, optimized=True)
    dt_unopt, _, _ = _run_fanout(n, chunk, optimized=False)
    return _entry("fanout", n, dt_opt, extra={
        "optimized_eps": round(n / dt_opt, 1),
        "unoptimized_eps": round(n / dt_unopt, 1),
        "opt_speedup": round(dt_unopt / dt_opt, 3),
        "subscribers": 4,
        "ttfr_ms": round(ttfr * 1000.0, 1), **cinfo,
    })


TENANT_TEMPLATE = """
define stream In (v double, k long);
@info(name='q')
from In[v > ${lo:double} and v < ${hi:double}]#window.lengthBatch(256)
select v, k
insert into Out;
"""


def _tenant_data(rows: int, seed: int = 11):
    rng = np.random.default_rng(seed)
    ts = TS0 + np.arange(rows, dtype=np.int64)
    v = rng.uniform(0, 200, rows)
    k = rng.integers(0, 1 << 20, rows, dtype=np.int64)
    return ts, [v, k]


def _tenant_bindings(i: int) -> dict:
    return {"lo": 20.0 + (i % 16), "hi": 180.0 - (i % 16)}


def _run_tenant_pool(n_tenants: int, rows: int, batch_max: int):
    """Pooled arm: ONE template, ONE compiled program set, N tenants as
    a vmapped slot axis; aggregate events/s over fair dispatch rounds."""
    from siddhi_tpu.serving import TemplateRegistry
    reg = TemplateRegistry(SiddhiManager())
    pool = reg.pool(TENANT_TEMPLATE, warm=False, slots=n_tenants,
                    max_tenants=n_tenants, batch_max=batch_max)
    wu = pool.warmup([batch_max])
    for i in range(n_tenants):
        pool.add_tenant(f"t{i}", _tenant_bindings(i))
    ts, cols = _tenant_data(rows)
    last = _Last()
    # terminal maps sid -> LIST of device batches (multi-input queries
    # can emit several per round); keep only the newest alive
    pool.batch_callbacks.append(
        lambda terminal: last(next(iter(terminal.values()))[-1]
                              if terminal else None))

    def one_pass():
        for i in range(n_tenants):
            pool.send(f"t{i}", ts, cols)
        pool.flush()
        last.drain()

    one_pass()   # warm pass: dispatch-path caches settle off the clock
    dt = min(_timed(one_pass) for _ in range(REPS))
    stats = pool.statistics()
    comp = stats["compile"]
    plan = _plan_block(pool)
    audit = _audit_block(pool)
    pool.shutdown()
    return {
        "plan": plan,
        "audit": audit,
        "eps": round(n_tenants * rows / dt, 1),
        "seconds": round(dt, 3),
        "compile_ms": wu["compile_ms"],
        "warm_programs": wu["programs"],
        "program_sets": comp["program_sets"],
        "pool_warmups": comp["warmups"],
        "slots": stats["pool"]["slots"],
        "rounds": stats["pool"]["rounds"],
        "packed_ingest": {k: stats["packed_ingest"][k] for k in
                          ("transfers_per_round", "rows_packed",
                           "pad_frac")},
    }


def _run_tenant_separate(n_tenants: int, rows: int):
    """Baseline arm: one full SiddhiAppRuntime per tenant — N parses, N
    compiles, N separate step dispatches per pass (what ROADMAP item 2
    replaces). Measured at a bounded N and extrapolated flat, which is
    GENEROUS to the baseline: aggregate events/s of serial per-runtime
    dispatch does not improve with more runtimes while its compile cost
    grows linearly."""
    from siddhi_tpu.serving import Template
    tpl = Template(TENANT_TEMPLATE)
    mgr = SiddhiManager()
    ts, cols = _tenant_data(rows)
    runtimes = []
    t0 = time.perf_counter()
    for i in range(n_tenants):
        rt = mgr.create_siddhi_app_runtime(tpl.instantiate_static(
            _tenant_bindings(i), app_name=f"sep_{i}"))
        outs = _Last()
        rt.queries["q"].batch_callbacks.append(outs)
        rt.start()
        runtimes.append((rt, rt.get_input_handler("In"), outs))
    deploy_s = time.perf_counter() - t0

    def one_pass():
        for _rt, h, outs in runtimes:
            h.send_arrays(ts, cols)
        for _rt, _h, outs in runtimes:
            outs.drain()

    t0 = time.perf_counter()
    one_pass()   # first pass pays the N per-runtime lazy compiles
    compile_s = time.perf_counter() - t0
    dt = min(_timed(one_pass) for _ in range(REPS))
    for rt, _h, _outs in runtimes:
        rt.shutdown()
    return {
        "eps": round(n_tenants * rows / dt, 1),
        "seconds": round(dt, 3),
        "deploy_ms": round(deploy_s * 1000.0, 1),
        "first_pass_compile_ms": round(compile_s * 1000.0, 1),
    }


def _run_tenant_slo(n_tenants: int, rows: int, batch_max: int,
                    skew: int = 8):
    """Skewed-traffic SLO arm (docs/observability.md "SLO engine"): one
    HOT tenant sends ``skew``x the traffic of every cold tenant while
    the pool tracks a p99 ingest->emit objective at stride 1. Reports
    measured p50/p99 vs the configured bound, attainment, the burn-rate
    state, and the hot-vs-cold p99 split — fairness must keep the cold
    tenants' latency bounded while the hot tenant's backlog spans more
    rounds."""
    from siddhi_tpu.serving import TemplateRegistry
    objective_p99_ms = float(
        _env("SIDDHI_BENCH_SLO_P99_MS", "250") or 250)
    reg = TemplateRegistry(SiddhiManager())
    pool = reg.pool(TENANT_TEMPLATE, warm=False, slots=n_tenants,
                    max_tenants=n_tenants, batch_max=batch_max,
                    slo={"p99_ms": objective_p99_ms, "target": 0.99,
                         "every": 1})
    pool.warmup([batch_max])
    for i in range(n_tenants):
        pool.add_tenant(f"t{i}", _tenant_bindings(i))
    ts, cols = _tenant_data(rows)
    hot_ts, hot_cols = _tenant_data(rows * skew, seed=13)
    for _ in range(3):
        pool.send("t0", hot_ts, hot_cols)
        for i in range(1, n_tenants):
            pool.send(f"t{i}", ts, cols)
        pool.flush()
    rep = pool.slo_report()
    scopes = rep["scopes"]
    total = scopes.get("total", {})
    hot = scopes.get("tenant=t0", {})
    cold = [e.get("p99_ms")
            for k, e in scopes.items()
            if k.startswith("tenant=") and "," not in k
            and k != "tenant=t0" and e.get("p99_ms") is not None]
    pool.shutdown()
    return {
        "objective_p99_ms": objective_p99_ms,
        "tenants": n_tenants,
        "skew": skew,
        "p50_ms": total.get("p50_ms"),
        "p99_ms": total.get("p99_ms"),
        "attainment": total.get("attainment"),
        "state": rep.get("state"),
        "hot_p99_ms": hot.get("p99_ms"),
        "cold_p99_ms_max": max(cold) if cold else None,
        "samples": total.get("count", 0),
        "saturation": {k: rep.get("saturation", {}).get(k)
                       for k in ("pending_rows", "queue_age_ms_max",
                                 "drain_lag_ms")},
    }


def _run_tenant_fairness(rows: int, batch_max: int, skew: int = 8):
    """Skewed-traffic FAIRNESS arm (docs/serving.md "QoS dials"): one
    hot tenant at ``skew``x, measured three ways — fair traffic (no
    hot), skew with QoS OFF (the pre-QoS fixed round), and skew with
    QoS ON (hot rate-limited, tenants split into high/normal/low
    priority classes). Reports the starved (cold normal-class)
    tenant's p99 under each arm, the 2x-of-fair bound, the per-class
    drain order, and the hot tenant's throttled_429s + Retry-After —
    the ROADMAP item 2 fairness acceptance, recorded per round."""
    from siddhi_tpu.serving import AdmissionError, TemplateRegistry
    rows = min(rows, 512)

    def run(hot: bool, qos: bool):
        reg = TemplateRegistry(SiddhiManager())
        tenant_qos = {
            "hi": {"priority": "high"}, "cold": {},
            "lo": {"priority": "low"},
        } if qos else {"hi": None, "cold": None, "lo": None}
        pool = reg.pool(TENANT_TEMPLATE, warm=False, slots=4,
                        max_tenants=4, batch_max=batch_max,
                        name=f"fair_{int(hot)}{int(qos)}",
                        slo={"p99_ms": 1000.0, "target": 0.99,
                             "every": 1})
        for tid, q in tenant_qos.items():
            pool.add_tenant(tid, _tenant_bindings(1), qos=q)
        if hot:
            hot_q = {"rate_eps": float(rows),
                     "burst": float(rows * skew)} if qos else None
            pool.add_tenant("hot", _tenant_bindings(0), qos=hot_q)
        ts, cols = _tenant_data(rows)
        throttled, retry_after = 0, None
        if hot:
            hot_ts, hot_cols = _tenant_data(rows * skew, seed=13)
            pool.send("hot", hot_ts, hot_cols)
            if qos:
                try:    # the re-flood: over the bucket rate -> 429
                    pool.send("hot", hot_ts, hot_cols)
                except AdmissionError as exc:
                    throttled += 1
                    retry_after = exc.saturation.get("retry_after_ms")
        for tid in ("hi", "cold", "lo"):
            pool.send(tid, ts, cols)
        drained_at = {}
        rounds = 0
        while pool.pump():
            rounds += 1
            pending = pool.statistics()["tenants"]
            for tid in ("hi", "cold", "lo"):
                if tid not in drained_at and \
                        pending[tid]["pending"] == 0:
                    drained_at[tid] = rounds
        rep = pool.slo_report()
        starved = rep["scopes"].get("tenant=cold", {}).get("p99_ms")
        pool.shutdown()
        return starved, drained_at, throttled, retry_after

    p99_fair, _d0, _t0, _r0 = run(hot=False, qos=False)
    p99_noqos, _d1, _t1, _r1 = run(hot=True, qos=False)
    p99_qos, drained, throttled, retry_after = run(hot=True, qos=True)
    # same-round ties (enough batch budget for both classes) break by
    # class rank — the report answers "who drained first"
    rank = {"hi": 0, "cold": 1, "lo": 2}
    order = sorted(drained, key=lambda t: (drained[t], rank[t]))
    bounded = None
    if p99_fair is not None and p99_qos is not None:
        # the acceptance bound, with a CPU-noise floor: a sub-ms p99
        # pair must not flap the bench on scheduler jitter
        bounded = p99_qos <= max(2.0 * p99_fair, p99_fair + 50.0)
    return {
        "skew": skew,
        "rows_per_cold_tenant": rows,
        "starved_p99_ms_fair": p99_fair,
        "starved_p99_ms_noqos": p99_noqos,
        "starved_p99_ms_qos": p99_qos,
        "p99_bounded": bounded,
        "throttled_429s": throttled,
        "retry_after_ms": retry_after,
        "class_drain_order": [
            {"hi": "high", "cold": "normal", "lo": "low"}[t]
            for t in order],
        "drain_rounds": {t: drained.get(t) for t in
                         ("hi", "cold", "lo")},
    }


def _run_tenant_rebalance(skew: int = 8, starved_rows: int = 64):
    """Live-migration REBALANCE arm (docs/serving.md "Live migration &
    rebalance"): one hot tenant floods ``skew``x the starved tenant's
    traffic into the device they share (sharded pool, per-device round
    caps), one live migration moves the hot tenant off, and the arm
    reports the starved p99 before/after vs a no-hot fair twin, the
    migration pause, and the rows moved. Runs the SAME seeded scenario
    the chaos suite asserts on (tools/chaos.py --mesh), so the bench
    number and the chaos acceptance can never drift apart. Needs >= 2
    devices (TPU mesh, or forced CPU devices in a smoke run); skipped
    otherwise."""
    if len(jax.devices()) < 2:
        return {"skipped": "needs >= 2 devices for a sharded pool"}
    from siddhi_tpu.resilience.scenarios import run_mesh_hot_tenant_skew
    # flood_rounds x 16-row chunks / starved_rows == the skew factor
    res = run_mesh_hot_tenant_skew(
        seed=11, flood_rounds=skew * starved_rows // 16,
        starved_rows=starved_rows)
    return {
        "skew": skew,
        "rows_per_starved_tenant": starved_rows,
        "starved_p99_ms_before": res["starved_p99_ms_before"],
        "starved_p99_ms_after": res["starved_p99_ms_after"],
        "starved_p99_ms_fair": res["starved_p99_ms_fair"],
        "p99_restored": res["p99_restored"],
        "bit_identical": res["bit_identical"],
        "migration_pause_ms": res["migration_pause_ms"],
        "rows_moved": res["rows_moved"],
        "lost": res["lost"],
        "duplicates": res["duplicates"],
    }


# operator-class pool arms (docs/serving.md "Poolable operator
# classes"): the SAME pooled-vs-separate comparison for a pattern
# (NFA) template and a two-stream equi-join template. These carry NO
# ${} placeholders — the template-binding rule makes every expression
# position in a join/pattern query structural (only plain
# single-stream queries can hold per-tenant parameters), so tenants
# of these classes differ by per-slot STATE, not by parameters.
POOL_PATTERN_TEMPLATE = """
define stream S (k long, v double);
@info(name='p')
from every e1=S[v > 800.0] -> e2=S[k == e1.k and v < 100.0]
within 10 sec
select e1.k as k, e1.v as v1, e2.v as v2
insert into Out;
"""

POOL_JOIN_TEMPLATE = """
define stream L (k long, v double);
define stream R (k long, w double);
@info(name='j')
from L#window.length(64) as a join R#window.length(64) as b
  on a.k == b.k
select a.k as k, a.v as v, b.w as w
insert into Out;
"""

CLASS_TEMPLATES = {
    "pattern_template": (POOL_PATTERN_TEMPLATE, ("S",)),
    "join_template": (POOL_JOIN_TEMPLATE, ("L", "R")),
}


def _class_feeds(streams, rows: int, seed: int = 17):
    """Per-stream (ts, cols) feeds for the class templates' (k long,
    v double) schemas; later streams interleave at +j ms so join sides
    merge deterministically."""
    rng = np.random.default_rng(seed)
    feeds = {}
    for j, sid in enumerate(streams):
        ts = TS0 + np.arange(rows, dtype=np.int64) * 4 + j
        k = rng.integers(0, 32, rows, dtype=np.int64)
        v = rng.uniform(0, 1000.0, rows)
        feeds[sid] = (ts, [k, v])
    return feeds


def _run_class_pool(arm: str, n_tenants: int, rows: int,
                    batch_max: int):
    """Pooled arm for one operator class: ONE template, N tenants on
    the vmapped slot axis, every backlogged ingest stream shipped as
    ONE packed device_put per fair round (docs/performance.md "Packed
    pool ingest")."""
    from siddhi_tpu.serving import TemplateRegistry
    tpl, streams = CLASS_TEMPLATES[arm]
    reg = TemplateRegistry(SiddhiManager())
    pool = reg.pool(tpl, warm=False, slots=n_tenants,
                    max_tenants=n_tenants, batch_max=batch_max,
                    name=arm)
    wu = pool.warmup([batch_max])
    for i in range(n_tenants):
        pool.add_tenant(f"t{i}", {})
    feeds = _class_feeds(streams, rows)
    last = _Last()
    pool.batch_callbacks.append(
        lambda terminal: last(next(iter(terminal.values()))[-1]
                              if terminal else None))

    def one_pass():
        for i in range(n_tenants):
            for sid in streams:
                ts, cols = feeds[sid]
                pool.send(f"t{i}", ts, cols, stream=sid)
        pool.flush()
        last.drain()

    one_pass()   # warm pass: dispatch caches + sticky encoders settle
    dt = min(_timed(one_pass) for _ in range(REPS))
    stats = pool.statistics()
    packed = stats["packed_ingest"]
    comp = stats["compile"]
    pool.shutdown()
    events = n_tenants * rows * len(streams)
    return {
        "eps": round(events / dt, 1),
        "seconds": round(dt, 3),
        "compile_ms": wu["compile_ms"],
        "program_sets": comp["program_sets"],
        "rounds": stats["pool"]["rounds"],
        "ingest_streams": list(streams),
        "packed_ingest": {k: packed[k] for k in
                          ("transfers_per_round", "rows_packed",
                           "pad_frac")},
    }


def _run_class_separate(arm: str, n_tenants: int, rows: int):
    """Baseline arm: one full runtime per tenant, serial dispatch
    (same GENEROUS flat extrapolation as _run_tenant_separate)."""
    from siddhi_tpu.serving import Template
    tpl_text, streams = CLASS_TEMPLATES[arm]
    tpl = Template(tpl_text)
    mgr = SiddhiManager()
    feeds = _class_feeds(streams, rows)
    runtimes = []
    for i in range(n_tenants):
        rt = mgr.create_siddhi_app_runtime(tpl.instantiate_static(
            {}, app_name=f"{arm}_sep_{i}"))
        outs = _Last()
        next(iter(rt.queries.values())).batch_callbacks.append(outs)
        rt.start()
        handlers = [rt.get_input_handler(sid) for sid in streams]
        runtimes.append((rt, handlers, outs))

    def one_pass():
        for _rt, handlers, outs in runtimes:
            for h, sid in zip(handlers, streams):
                ts, cols = feeds[sid]
                h.send_arrays(ts, cols)
        for _rt, _h, outs in runtimes:
            outs.drain()

    one_pass()   # first pass pays the per-runtime lazy compiles
    dt = min(_timed(one_pass) for _ in range(REPS))
    for rt, _h, _outs in runtimes:
        rt.shutdown()
    return {"eps": round(n_tenants * rows * len(streams) / dt, 1),
            "seconds": round(dt, 3)}


def _class_arm(arm: str, n_tenants: int, rows: int, batch_max: int,
               sep_n: int):
    """One operator-class pooled-vs-separate block for the tenants
    config: eps_pooled/eps_separate/speedup + the packed_ingest
    acceptance numbers."""
    pooled = _run_class_pool(arm, n_tenants, rows, batch_max)
    assert pooled["program_sets"] == 1, (arm, pooled)
    sep_at = min(sep_n, n_tenants)
    sep = _run_class_separate(arm, sep_at, rows)
    return {
        "tenants": n_tenants,
        "rows_per_tenant": rows,
        "eps_pooled": pooled["eps"],
        "eps_separate": sep["eps"],
        "separate_measured_at": sep_at,
        "extrapolated": sep_at != n_tenants,
        "speedup": round(pooled["eps"] / max(sep["eps"], 1e-9), 2),
        "compile_ms": pooled["compile_ms"],
        "program_sets": pooled["program_sets"],
        "rounds": pooled["rounds"],
        "ingest_streams": pooled["ingest_streams"],
        "packed_ingest": pooled["packed_ingest"],
    }


def bench_tenants():
    """Multi-tenant serving acceptance (ROADMAP item 2): N tenants of
    ONE filter+window template as a vmapped TenantPool vs N separate
    runtimes. Reports eps_pooled/eps_separate/speedup per N and the
    pool's one-program-set compile story; the headline value is the
    pooled aggregate events/s at the largest N. The ``slo`` block is
    the skewed-traffic SLO arm: p50/p99 attainment vs the configured
    objective with one hot tenant (docs/observability.md). The
    ``fairness`` block is the QoS acceptance: hot tenant at 8x with
    and without QoS — starved-tenant p99 vs the 2x-of-fair bound,
    per-class drain order, throttled_429s (docs/serving.md). The
    ``rebalance`` block is the live-migration acceptance: 8x skew on
    a sharded pool healed by one migration, starved p99 before/after
    vs the fair twin + pause ms + rows moved (ISSUE 17)."""
    n_list = [int(x) for x in
              _env("SIDDHI_BENCH_TENANTS", "64,256,1024").split(",")
              if x.strip()]
    sep_n = int(_env("SIDDHI_BENCH_TENANTS_SEP", "64") or 64)
    batch_max = 1024
    rows = _scaled(2048, batch_max)
    sep = _run_tenant_separate(min(sep_n, min(n_list)), rows)
    per_n = {}
    plan = None
    audit = None
    for n in n_list:
        pooled = _run_tenant_pool(n, rows, batch_max)
        assert pooled["program_sets"] == 1 and \
            pooled["pool_warmups"] == 1, pooled
        # ONE template plan regardless of N (pools of one template
        # share the plan_hash — slot counts are live facts, not plan)
        plan = pooled.get("plan") or plan
        audit = pooled.get("audit") or audit
        per_n[n] = {
            "eps_pooled": pooled["eps"],
            # flat extrapolation of the measured separate-runtimes
            # aggregate (serial dispatch: more runtimes do not add
            # events/s, they add compiles)
            "eps_separate": sep["eps"],
            "separate_measured_at": min(sep_n, min(n_list)),
            "extrapolated": n != min(sep_n, min(n_list)),
            "speedup": round(pooled["eps"] / max(sep["eps"], 1e-9), 2),
            "compile_ms": pooled["compile_ms"],
            "program_sets": pooled["program_sets"],
            "rounds": pooled["rounds"],
            "packed_ingest": pooled["packed_ingest"],
        }
    slo_arm = _run_tenant_slo(min(n_list), rows, batch_max)
    fairness = _run_tenant_fairness(rows, batch_max)
    rebalance = _run_tenant_rebalance()
    # operator-class arms (pattern NFA / two-stream equi-join pools):
    # smaller rows — the per-row work is heavier than the filter chain
    class_n = min(n_list)
    class_rows = _scaled(1024, 256)
    class_arms = {
        arm: _class_arm(arm, class_n, class_rows, batch_max=256,
                        sep_n=min(sep_n, 8))
        for arm in CLASS_TEMPLATES
    }
    n_max = max(n_list)
    head = per_n[n_max]
    return {
        "value": head["eps_pooled"], "unit": "events/s",
        "baseline": "n/a",
        "events": n_max * rows,
        "rows_per_tenant": rows,
        "eps_pooled": head["eps_pooled"],
        "eps_separate": head["eps_separate"],
        "speedup": head["speedup"],
        "compile_ms": head["compile_ms"],
        "separate": sep,
        "tenants": {str(n): per_n[n] for n in n_list},
        # packed pool ingest acceptance (docs/performance.md "Packed
        # pool ingest"): ONE transfer per ingest stream per round —
        # bench_diff.py gates on transfers_per_round creeping up
        "packed_ingest": head["packed_ingest"],
        "pattern_template": class_arms["pattern_template"],
        "join_template": class_arms["join_template"],
        "plan": plan,
        "audit": audit,
        "slo": slo_arm,
        "fairness": fairness,
        "rebalance": rebalance,
    }


def bench_window_agg(n=1_000_000):
    n = _scaled(n)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
        @app:playback
        define stream StockStream (symbol string, price float, volume long);
        @info(name = 'q')
        from StockStream#window.lengthBatch(1000)
        select avg(price) as ap, sum(volume) as sv
        insert into OutputStream;
    """)
    q = rt.queries["q"]
    outs = []
    q.batch_callbacks.append(outs.append)
    rt.start()
    h = rt.get_input_handler("StockStream")
    rng = np.random.default_rng(8)
    syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)
    ts = TS0 + np.arange(n, dtype=np.int64)
    sym = syms[rng.integers(0, len(syms), n)]
    price = rng.uniform(0, 200, n).astype(np.float32)
    vol = rng.integers(1, 1000, n, dtype=np.int64)
    cinfo = _warm(rt, n, samples={"StockStream": (ts, [sym, price, vol])})
    ttfr = _timed(lambda: (h.send_arrays(ts, [sym, price, vol]),
                           _drain(outs)))
    dt = min(_timed(lambda: (h.send_arrays(ts, [sym, price, vol]),
                             _drain(outs))) for _ in range(REPS))
    sb = _stage_breakdown(rt, lambda: (
        h.send_arrays(ts[:8192], [sym[:8192], price[:8192], vol[:8192]]),
        _drain(outs)))
    met = _metrics_snapshot(rt)
    plan = _plan_block(rt)
    audit = _audit_block(rt)
    rt.shutdown()
    return _entry("window_agg", n, dt, extra={
        "ttfr_ms": round(ttfr * 1000.0, 1), "metrics": met,
        "plan": plan, "audit": audit, "stage_breakdown": sb, **cinfo})


def _run_join(n_symbols: int, chunk: int, join_pairs: int, n_side: int,
              frontier: bool = False, kernel: str = None):
    """Shared join driver. Honest emission: every surviving pair is
    built and emitted (the r3 bench capped output at 1024 pairs/step,
    silently dropping >99% on the 4-symbol workload and measuring only
    the condition grid); pairs_dropped in the result must be 0.

    kernel pins SIDDHI_TPU_JOIN_KERNEL for the app build (None = the
    planner's auto pick — the banded probe for this equi ON condition);
    the kernel that actually ran is recorded in the result."""
    prev = os.environ.get("SIDDHI_TPU_JOIN_KERNEL")
    if kernel:
        os.environ["SIDDHI_TPU_JOIN_KERNEL"] = kernel
    try:
        return _run_join_inner(n_symbols, chunk, join_pairs, n_side,
                               frontier)
    finally:
        if kernel:
            if prev is None:
                os.environ.pop("SIDDHI_TPU_JOIN_KERNEL", None)
            else:
                os.environ["SIDDHI_TPU_JOIN_KERNEL"] = prev


def _run_join_inner(n_symbols, chunk, join_pairs, n_side, frontier):
    n_side = _scaled(n_side, chunk)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(f"""
        @app:playback
        define stream StockStream (symbol string, price float);
        define stream TwitterStream (symbol string, tweets int);
        @info(name = 'q') @cap(window.size='1024', join.pairs='{join_pairs}')
        from StockStream#window.time(1 sec) join TwitterStream#window.time(1 sec)
        on StockStream.symbol == TwitterStream.symbol
        select StockStream.symbol, price, tweets
        insert into OutputStream;
    """)
    q = rt.queries["q"]
    outs = _Last()
    q.batch_callbacks.append(outs)
    rt.start()
    hs = rt.get_input_handler("StockStream")
    ht = rt.get_input_handler("TwitterStream")
    rng = np.random.default_rng(9)
    syms = np.array([GLOBAL_STRINGS.encode(f"SYM{i:05d}")
                     for i in range(n_symbols)], np.int32)

    def mk(i, n):
        # 1000 events/s/side -> the 1s window holds ~1000 rows/side
        ts = TS0 + (np.arange(n, dtype=np.int64) + i * n)
        sym = syms[rng.integers(0, len(syms), n)]
        return ts, sym

    ts, sym = mk(0, chunk)
    price0 = rng.uniform(0, 200, chunk).astype(np.float32)
    tweets0 = rng.integers(0, 50, chunk).astype(np.int32)
    cinfo = _warm(rt, chunk, samples={"StockStream": (ts, [sym, price0]),
                                      "TwitterStream": (ts, [sym, tweets0])})
    ttfr = _timed(lambda: (hs.send_arrays(ts, [sym, price0]),
                           ht.send_arrays(ts, [sym, tweets0]),
                           outs.drain()))

    n_chunks = n_side // chunk
    dts = []
    for rep in range(REPS):   # best-of-N (timestamps keep advancing)
        base = 1 + rep * n_chunks
        t0 = time.perf_counter()
        for i in range(base, base + n_chunks):
            ts, sym = mk(i, chunk)
            hs.send_arrays(ts, [sym, rng.uniform(0, 200, chunk)
                                .astype(np.float32)])
            ht.send_arrays(ts, [sym, rng.integers(0, 50, chunk)
                                .astype(np.int32)])
            if i % 8 == 0:
                # bound in-flight output buffers: at 2M-pair caps each
                # step holds ~130MB of output in HBM until the host
                # drops its ref
                outs.drain()
        outs.drain()
        dts.append(time.perf_counter() - t0)
    dt = min(dts)
    emitted = q.stats()["emitted"]
    dropped = q.overflow
    if frontier:
        # frontier + breakdown run AFTER the timed reps on a clock past
        # every measurement pass (the playback clock must stay monotone)
        fclock = [TS0 + (3 + REPS * n_chunks) * chunk]

        def send_pair(c):
            fts = fclock[0] + np.arange(c, dtype=np.int64)
            fclock[0] += c
            fsym = syms[rng.integers(0, len(syms), c)]
            hs.send_arrays(fts, [fsym, rng.uniform(0, 200, c)
                                 .astype(np.float32)])
            ht.send_arrays(fts, [fsym, rng.integers(0, 50, c)
                                 .astype(np.int32)])
            outs.drain()

        cinfo["frontier"] = _frontier(send_pair, lambda c: 2 * c)
        cinfo["stage_breakdown"] = _stage_breakdown(
            rt, lambda: send_pair(2048))
    cinfo["metrics"] = _metrics_snapshot(rt)
    cinfo["plan"] = _plan_block(rt)
    cinfo["audit"] = _audit_block(rt)
    # which kernel actually ran (grid vs banded probe) + the planner's
    # reason — the acceptance artifact must name it
    kernels = rt.statistics().get("compile", {}).get("join_kernels", {})
    if kernels:
        cinfo["join_kernel"] = kernels.get("q.left", {}).get("kernel")
        cinfo["join_kernels"] = kernels
    rt.shutdown()
    cinfo["ttfr_ms"] = round(ttfr * 1000.0, 1)
    return dt, 2 * n_chunks * chunk, emitted, dropped, cinfo


def _join_entry(name, n_symbols):
    """One join bench config measured on BOTH kernels: the full replay
    on the planner's auto pick (the banded probe for this equi ON) and
    a quarter-length comparison pass pinned to the [B,W] grid, each
    with its own latency/throughput frontier — the ROADMAP item 3
    acceptance artifact records p99 vs events/s per kernel."""
    dt, events, emitted, dropped, cinfo = _run_join(
        n_symbols=n_symbols, chunk=8192, join_pairs=131_072,
        n_side=131_072, frontier=True)
    gdt, gevents, _, _, gcinfo = _run_join(
        n_symbols=n_symbols, chunk=8192, join_pairs=131_072,
        n_side=32_768, frontier=True, kernel="grid")
    eps, geps = events / dt, gevents / gdt
    return _entry(name, events, dt, extra={
        "symbols": n_symbols, "pairs_emitted": emitted,
        "pairs_dropped": dropped,
        "grid_eps": round(geps, 1),
        "probe_speedup_vs_grid": round(eps / geps, 3),
        "frontier_grid": gcinfo.get("frontier"), **cinfo})


def bench_join():
    """BASELINE config 3 at realistic key cardinality (1024 symbols,
    ~1 matching pair per event — what a 'join throughput' baseline guess
    plausibly describes)."""
    return _join_entry("join", 1024)


def bench_join_eq():
    """High-cardinality equi key (symbols=8192, ~0.125 expected matches
    per event): the banded probe kernel's acceptance config — band
    sizes stay tiny while the grid would still pay the full [B, W]
    product, so this is the cleanest probe-vs-grid separation."""
    return _join_entry("join_eq", 8192)


def bench_join_fanout():
    """The r3 4-symbol workload: ~250 matching window rows per event, so
    the real product is joined-pair construction — reported in pairs/s
    (input events/s is bounded by the ~133x output amplification, not by
    join speed; no vs_baseline since the assumed Java events/s number
    does not describe full-emission fanout)."""
    dt, events, emitted, dropped, cinfo = _run_join(
        n_symbols=4, chunk=2048, join_pairs=2_097_152, n_side=32_768)
    return {"value": round(emitted / dt, 1), "unit": "pairs/s",
            "events": events, "seconds": round(dt, 3),
            "events_per_sec": round(events / dt, 1),
            "pairs_emitted": emitted, "pairs_dropped": dropped,
            "baseline": "n/a", **cinfo}


def bench_seq2(n=262_144, chunk=65_536):
    """2-state sequence: Order -> Payment[oid == e1.oid] within 5 sec."""
    n = _scaled(n, chunk)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
        @app:playback
        define stream OrderS (oid int, amt float);
        define stream PayS (pid int, oid int);
        @info(name = 'q')
        from e1=OrderS[amt > 10.0] -> e2=PayS[oid == e1.oid] within 5 sec
        select e1.oid as o, e2.pid as p
        insert into Out;
    """)
    q = rt.queries["q"]
    outs = []
    q.batch_callbacks.append(outs.append)
    rt.start()
    ho = rt.get_input_handler("OrderS")
    hp = rt.get_input_handler("PayS")
    rng = np.random.default_rng(10)

    def send(i, m):
        ts = TS0 + np.arange(m, dtype=np.int64) + i * m
        oid = rng.integers(0, 1000, m).astype(np.int32)
        ho.send_arrays(ts, [oid, rng.uniform(0, 100, m).astype(np.float32)])
        hp.send_arrays(ts + m, [np.arange(m, dtype=np.int32), oid])

    # AOT warm against a twin of the first chunk (same seed -> same
    # value spans -> same sticky packed encodings)
    rngs = np.random.default_rng(10)
    s_ts = TS0 + np.arange(chunk, dtype=np.int64)
    s_oid = rngs.integers(0, 1000, chunk).astype(np.int32)
    cinfo = _warm(rt, chunk, samples={
        "OrderS": (s_ts, [s_oid,
                          rngs.uniform(0, 100, chunk).astype(np.float32)]),
        "PayS": (s_ts, [np.arange(chunk, dtype=np.int32), s_oid])})
    ttfr = _timed(lambda: (send(0, chunk), _drain(outs)))
    n_chunks = n // chunk
    dts = []
    for rep in range(REPS):   # best-of-N (timestamps keep advancing)
        base = 1 + rep * n_chunks
        t0 = time.perf_counter()
        for i in range(base, base + n_chunks):
            send(i, chunk)
        _drain(outs)
        dts.append(time.perf_counter() - t0)
    dt = min(dts)
    sb = _stage_breakdown(rt, lambda: (send(2 + REPS * n_chunks, chunk),
                                       _drain(outs)))
    met = _metrics_snapshot(rt)
    plan = _plan_block(rt)
    audit = _audit_block(rt)
    rt.shutdown()
    return _entry("seq2", 2 * n_chunks * chunk, dt, extra={
        "ttfr_ms": round(ttfr * 1000.0, 1), "metrics": met,
        "plan": plan, "audit": audit, "stage_breakdown": sb, **cinfo})


def bench_kleene(n=262_144, chunk=65_536):
    """every (A+ -> B) with count() and within — variable-length NFA."""
    n = _scaled(n, chunk)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
        @app:playback
        define stream A (v int);
        define stream B (v int);
        @info(name = 'q')
        from every e1=A[v > 10]+, e2=B[v > e1.v] within 10 sec
        select count(e1.v) as n, e2.v as bv
        insert into Out;
    """)
    q = rt.queries["q"]
    outs = []
    q.batch_callbacks.append(outs.append)
    rt.start()
    ha = rt.get_input_handler("A")
    hb = rt.get_input_handler("B")
    rng = np.random.default_rng(11)

    def send(i, m):
        ts = TS0 + np.arange(m, dtype=np.int64) + i * m
        ha.send_arrays(ts, [rng.integers(0, 100, m).astype(np.int32)])
        hb.send_arrays(ts + m, [rng.integers(0, 100, m).astype(np.int32)])

    rngs = np.random.default_rng(11)
    s_ts = TS0 + np.arange(chunk, dtype=np.int64)
    cinfo = _warm(rt, chunk, samples={
        "A": (s_ts, [rngs.integers(0, 100, chunk).astype(np.int32)]),
        "B": (s_ts, [rngs.integers(0, 100, chunk).astype(np.int32)])})
    ttfr = _timed(lambda: (send(0, chunk), _drain(outs)))
    n_chunks = n // chunk
    dts = []
    for rep in range(REPS):   # best-of-N (timestamps keep advancing)
        base = 1 + rep * n_chunks
        t0 = time.perf_counter()
        for i in range(base, base + n_chunks):
            send(i, chunk)
        _drain(outs)
        dts.append(time.perf_counter() - t0)
    dt = min(dts)
    sb = _stage_breakdown(rt, lambda: (send(2 + REPS * n_chunks, chunk),
                                       _drain(outs)))
    met = _metrics_snapshot(rt)
    plan = _plan_block(rt)
    audit = _audit_block(rt)
    rt.shutdown()
    return _entry("kleene", 2 * n_chunks * chunk, dt, extra={
        "ttfr_ms": round(ttfr * 1000.0, 1), "metrics": met,
        "plan": plan, "audit": audit, "stage_breakdown": sb, **cinfo})


SEQ5_APP = """
    @app:playback
    define stream T (sym string, stage int, v int);
    @info(name = 'q')
    from every e1=T[stage == 1] -> e2=T[stage == 2 and sym == e1.sym]
      -> e3=T[stage == 3 and sym == e1.sym]
      -> e4=T[stage == 4 and sym == e1.sym]
      -> e5=T[stage == 5 and sym == e1.sym]
    within 60 sec
    select e1.sym as sym, e1.v as v1, e5.v as v5
    insert into Out;
"""


def bench_seq5(n=1_048_576, chunk=65_536):
    """North star: 5-state pattern chain over a 1M-event replay, with
    per-chunk p50/p99 match latency (arrival -> match visible)."""
    n = _scaled(n, chunk)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(SEQ5_APP)
    q = rt.queries["q"]
    outs = []
    q.batch_callbacks.append(outs.append)
    rt.start()
    h = rt.get_input_handler("T")
    rng = np.random.default_rng(12)
    syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)

    # one monotone clock across ALL passes — a rewound playback clock
    # would let stale within-60s partials from earlier passes pollute
    # the small-chunk latency measurement
    clock = [TS0]

    def mk(m):
        ts = clock[0] + np.arange(m, dtype=np.int64)
        clock[0] += m
        sym = syms[rng.integers(0, len(syms), m)]
        stage = rng.integers(1, 6, m).astype(np.int32)
        v = rng.integers(0, 1000, m).astype(np.int32)
        return ts, [sym, stage, v]

    # AOT warm against a twin of the first chunk (same seed -> same
    # sticky encodings); the 1024 bucket serves the latency pass below
    rngs = np.random.default_rng(12)
    s_ts = TS0 + np.arange(chunk, dtype=np.int64)
    s_cols = [syms[rngs.integers(0, len(syms), chunk)],
              rngs.integers(1, 6, chunk).astype(np.int32),
              rngs.integers(0, 1000, chunk).astype(np.int32)]
    cinfo = _warm(rt, chunk, extra_caps=(1024,),
                  samples={"T": (s_ts, s_cols)})
    ttfr = _timed(lambda: (h.send_arrays(*mk(chunk)), _drain(outs)))
    n_chunks = n // chunk
    # throughput pass: pipelined sends, one drain at the end (the
    # reference harness also measures throughput streaming); best-of-3
    # so a transiently-contended host doesn't define the number
    dts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            h.send_arrays(*mk(chunk))
        _drain(outs)
        dts.append(time.perf_counter() - t0)
    dt = min(dts)
    dis = None
    if DISORDER:
        # reorder-buffer overhead on ordered input, seq5 shape (own
        # runtime + own monotone clock/rng twin of the main pass)
        rngd = np.random.default_rng(12)
        clockd = [TS0]

        def mkd(m):
            t = clockd[0] + np.arange(m, dtype=np.int64)
            clockd[0] += m
            return t, [syms[rngd.integers(0, len(syms), m)],
                       rngd.integers(1, 6, m).astype(np.int32),
                       rngd.integers(0, 1000, m).astype(np.int32)]

        dis = _reorder_overhead(
            SEQ5_APP, "T", n_chunks * chunk, dt,
            lambda i: [mkd(chunk) for _ in range(n_chunks)],
            {"T": (s_ts, s_cols)})
    # latency pass: per-chunk sync measures send -> matches visible
    lat = []
    for _ in range(8):
        c0 = time.perf_counter()
        h.send_arrays(*mk(chunk))
        _drain(outs)
        lat.append(time.perf_counter() - c0)
    # small-chunk latency mode: batch.size.max-style dial at 1024 rows —
    # honest match latency, not throughput wearing a latency label
    small = 1024
    h.send_arrays(*mk(small))   # warm the 1024 bucket
    _drain(outs)
    lat1k = []
    for _ in range(64):
        c0 = time.perf_counter()
        h.send_arrays(*mk(small))
        _drain(outs)
        lat1k.append(time.perf_counter() - c0)
    # latency/throughput frontier + per-step breakdown, AFTER every
    # timed pass (both serialize the pipeline); mk() keeps the playback
    # clock monotone across all of it
    fr = _frontier(lambda c: (h.send_arrays(*mk(c)), _drain(outs)),
                   lambda c: c)
    sb = _stage_breakdown(rt, lambda: (h.send_arrays(*mk(chunk)),
                                       _drain(outs)))
    met = _metrics_snapshot(rt)
    plan = _plan_block(rt)
    audit = _audit_block(rt)
    rt.shutdown()
    lat_ms = np.array(lat) * 1000.0
    lat1k_ms = np.array(lat1k) * 1000.0
    return _entry("seq5", n_chunks * chunk, dt, extra={
        **({"disorder": dis} if dis is not None else {}),
        "metrics": met, "plan": plan, "audit": audit,
        "frontier": fr, "stage_breakdown": sb,
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 1),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 1),
        "chunk": chunk,
        "p50_ms_1k": round(float(np.percentile(lat1k_ms, 50)), 2),
        "p99_ms_1k": round(float(np.percentile(lat1k_ms, 99)), 2),
        "latency_chunk": small,
        "ttfr_ms": round(ttfr * 1000.0, 1), **cinfo,
    })


def _ttfr_child(name: str) -> dict:
    """`bench.py --ttfr <seq5|chain3>`: one time-to-first-result probe.
    Builds the app, AOT-warms via the compile service, sends ONE small
    (1024-row) chunk, and reports wall time from runtime construction to
    the first visible result. Run twice against a shared
    JAX_COMPILATION_CACHE_DIR, the pair measures cold vs warm deploy."""
    small = 1024
    t0 = time.perf_counter()
    mgr = SiddhiManager()
    rng = np.random.default_rng(21)
    syms = np.array([GLOBAL_STRINGS.encode(s) for s in SYMS], np.int32)
    ts = TS0 + np.arange(small, dtype=np.int64)
    if name == "seq5":
        rt = mgr.create_siddhi_app_runtime(SEQ5_APP)
        tail = rt.queries["q"]
        stream, cols = "T", [syms[rng.integers(0, len(syms), small)],
                             rng.integers(1, 6, small).astype(np.int32),
                             rng.integers(0, 1000, small).astype(np.int32)]
    elif name == "chain3":
        rt = mgr.create_siddhi_app_runtime(CHAIN3_APP)
        tail = rt.queries["q3"]
        stream, cols = "S", [syms[rng.integers(0, len(syms), small)],
                             rng.integers(0, 1000, small).astype(np.int32),
                             rng.uniform(0, 200, small).astype(np.float32)]
    else:
        raise SystemExit(f"--ttfr: unknown app '{name}'")
    outs = _Last()
    tail.batch_callbacks.append(outs)
    rt.start()
    wu = rt.warmup(buckets=[small], samples={stream: (ts, cols)})
    rt.get_input_handler(stream).send_arrays(ts, cols)
    outs.drain()
    ttfr_ms = (time.perf_counter() - t0) * 1000.0
    rt.shutdown()
    return {"app": name, "ttfr_ms": round(ttfr_ms, 1),
            "compile_ms": wu["compile_ms"], "programs": wu["programs"],
            "cache_hits": wu["cache_hits"],
            "cache_misses": wu["cache_misses"]}


def bench_warmstart():
    """Cold-vs-warm deploy: run the seq5 and chain3 apps twice in fresh
    subprocesses sharing a throwaway JAX_COMPILATION_CACHE_DIR. The
    first run compiles from scratch (cold); the second loads every
    program from the persistent cache (warm) — the acceptance signal
    that apps start in seconds once the cache is populated. The children
    need the chip, so this process must not have touched a device."""
    import shutil
    import subprocess
    import sys
    import tempfile
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("bench_warmstart: this process already holds "
                           "a device; its children could not get it")
    apps = {}
    for name in ("seq5", "chain3"):
        cache = tempfile.mkdtemp(prefix=f"siddhi_warmstart_{name}_")
        try:
            runs = []
            for _ in range(2):
                env = dict(os.environ)
                env["JAX_COMPILATION_CACHE_DIR"] = cache
                proc = subprocess.run(
                    [sys.executable, __file__, "--ttfr", name],
                    capture_output=True, text=True, env=env,
                    timeout=max(60.0, BUDGET_S / 2))
                line = [ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1]
                runs.append(json.loads(line))
            cold, warm = runs
            apps[name] = {
                "cold_ttfr_ms": cold["ttfr_ms"],
                "warm_ttfr_ms": warm["ttfr_ms"],
                "cold_compile_ms": cold["compile_ms"],
                "warm_compile_ms": warm["compile_ms"],
                "warm_cache_hits": warm["cache_hits"],
                "ttfr_speedup": round(
                    cold["ttfr_ms"] / max(warm["ttfr_ms"], 1e-3), 2),
            }
        except Exception as e:  # noqa: BLE001 — record, keep benching
            apps[name] = {"error": f"{type(e).__name__}: {e}"}
        finally:
            shutil.rmtree(cache, ignore_errors=True)
    ok = [a for a in apps.values() if "warm_ttfr_ms" in a]
    value = min((a["warm_ttfr_ms"] for a in ok), default=-1)
    return {"value": value, "unit": "ms_warm_ttfr", "baseline": "n/a",
            "apps": apps}


def bench_multichip():
    """Mesh scale-out (ROADMAP item 1): aggregate events/s at n devices
    (SIDDHI_BENCH_MC_DEVICES, default 4 — one v5e host) vs 1 device for
    the filter (data-parallel ingest), seq5 (per-shard NFA state) and
    tenants (slot-axis-sharded TenantPool) arms — {n_devices,
    eps_aggregate, eps_per_device, scaling_efficiency} per arm via
    parallel/mesh.py measure_scaling, in this process. Fewer visible
    devices than asked for is an error; a CPU run needs forced host
    devices from its environment (docs/performance.md "Multi-chip
    execution")."""
    n = int(_env("SIDDHI_BENCH_MC_DEVICES", "4") or 4)
    if len(jax.devices()) < n:
        raise RuntimeError(f"multichip: {n} devices asked for, "
                           f"{len(jax.devices())} visible")
    from siddhi_tpu.parallel.mesh import measure_scaling
    out = measure_scaling(
        n_devices=n,
        chunk=int(_env("SIDDHI_BENCH_MC_CHUNK", "16384") or 16384),
        seq_chunk=int(_env("SIDDHI_BENCH_MC_SEQ_CHUNK", "4096")
                      or 4096),
        iters=int(_env("SIDDHI_BENCH_MC_ITERS", "4") or 4),
        reps=REPS,
        tenants=int(_env("SIDDHI_BENCH_MC_TENANTS", "512") or 512),
        tenant_rows=int(_env("SIDDHI_BENCH_MC_ROWS", "1024") or 1024))
    head = out["arms"].get("filter", {})
    return {"value": head.get("eps_aggregate", 0), "unit": "events/s",
            "baseline": "n/a", **out}


# expensive configs (join_fanout's 2M-pair executable) run LAST and get
# skipped when the wall deadline approaches; seq5 (the headline metric)
# runs FIRST so the JSON line always has a value. warmstart (cold-vs-warm
# deploy probes at 1024 rows) runs early: cheap, and the cold/warm split
# is the PR-5 acceptance metric.
BENCHES = ("seq5", "chain3", "fanout", "warmstart", "tenants", "filter",
           "ingest", "window_agg", "seq2", "kleene", "join", "join_eq",
           "join_fanout", "multichip")


def main():
    # Each config runs in its OWN subprocess, so one config's state
    # (compiled programs, device buffers, readbacks) cannot bend the
    # next one's numbers; this parent never touches a device, so each
    # child can take the chip. The persistent compile cache keeps child
    # startup cheap after the first ever run.
    import subprocess
    import sys
    argv = sys.argv[1:]
    env = dict(os.environ)
    if "--quick" in argv:
        argv.remove("--quick")
        env.setdefault("SIDDHI_BENCH_SCALE", "0.125")
        env.setdefault("SIDDHI_BENCH_REPS", "1")
        env.setdefault("SIDDHI_BENCH_BUDGET_S", "90")
        env.setdefault("SIDDHI_BENCH_DEADLINE_S", "240")
        # tenants smoke: small pools, small separate arm (os.environ
        # too: single-config invocations run in-process and read the
        # knob at call time, not from the subprocess env dict)
        env.setdefault("SIDDHI_BENCH_TENANTS", "16,64")
        env.setdefault("SIDDHI_BENCH_TENANTS_SEP", "8")
        os.environ.setdefault("SIDDHI_BENCH_TENANTS", "16,64")
        os.environ.setdefault("SIDDHI_BENCH_TENANTS_SEP", "8")
        # multichip smoke: tiny arms so a forced-CPU-device run
        # (test_bench_smoke) stays inside its subprocess timeout
        for k, v in (("SIDDHI_BENCH_MC_CHUNK", "2048"),
                     ("SIDDHI_BENCH_MC_SEQ_CHUNK", "512"),
                     ("SIDDHI_BENCH_MC_ITERS", "2"),
                     ("SIDDHI_BENCH_MC_TENANTS", "32"),
                     ("SIDDHI_BENCH_MC_ROWS", "256")):
            env.setdefault(k, v)
            os.environ.setdefault(k, v)
        globals().update(
            SCALE=float(env["SIDDHI_BENCH_SCALE"]),
            REPS=int(env["SIDDHI_BENCH_REPS"]),
            BUDGET_S=float(env["SIDDHI_BENCH_BUDGET_S"]),
            DEADLINE_S=float(env["SIDDHI_BENCH_DEADLINE_S"]))
    if argv and argv[0] == "--ttfr":
        print(json.dumps(_ttfr_child(argv[1])))
        return
    if argv:
        name = argv[0]
        print(json.dumps(globals()[f"bench_{name}"]()))
        return
    configs = {}
    t0 = time.monotonic()
    # flush a parseable preamble IMMEDIATELY: even a run killed by the
    # harness inside the first config's compile phase leaves one JSON
    # line instead of an empty tail (BENCH_r05: rc=124, "parsed": null)
    print(json.dumps({"config": "_meta", "benches": list(BENCHES),
                      "scale": SCALE, "reps": REPS,
                      "budget_s": BUDGET_S, "deadline_s": DEADLINE_S}),
          flush=True)
    for name in BENCHES:
        remaining = DEADLINE_S - (time.monotonic() - t0)
        if remaining < 20:
            # out of wall budget: report the skip instead of hanging the
            # whole invocation past the harness timeout (r5: rc=124)
            configs[name] = {"skipped": "deadline",
                             "deadline_s": DEADLINE_S}
        else:
            proc = None
            try:
                proc = subprocess.run(
                    [sys.executable, __file__, name],
                    capture_output=True, text=True, env=env,
                    timeout=min(BUDGET_S, remaining))
                line = [ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1]
                configs[name] = json.loads(line)
            except Exception as e:  # noqa: BLE001 — record, keep benching
                err = f"{type(e).__name__}: {e}"
                if proc is not None and proc.stderr:
                    err += " | stderr: " + proc.stderr.strip()[-500:]
                configs[name] = {"error": err}
        # flush one JSON line per finished config: a run killed at the
        # harness timeout leaves parseable partial results instead of an
        # empty tail (BENCH_r05: rc=124, tail ""); the summary line is
        # still printed LAST, so tail-line parsers keep working
        print(json.dumps({"config": name, **configs[name]}), flush=True)
    head = configs["seq5"]
    if "value" not in head:  # seq5 child failed: still report the rest
        head = {"value": 0, "vs_baseline": 0,
                "p99_ms": -1, "p99_ms_1k": -1}
    print(json.dumps({
        "metric": "seq5_events_per_sec",
        "value": head["value"],
        "unit": "events/s",
        "vs_baseline": head["vs_baseline"],
        "baseline": "assumed",
        "p99_match_latency_ms": head.get("p99_ms", -1),
        "p99_match_latency_ms_1k": head.get("p99_ms_1k", -1),
        "scale": SCALE,
        "configs": configs,
    }))


if __name__ == "__main__":
    main()

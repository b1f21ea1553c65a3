#!/usr/bin/env python
"""Run a bench config (or any .siddhi app) under the pipeline cost
profiler and print the ranked bottleneck report (docs/observability.md).

    python tools/profile_report.py --config join        # bench workload
    python tools/profile_report.py --config seq5 --events 65536
    python tools/profile_report.py app.siddhi           # your app
    python tools/profile_report.py --config join --json # machine-readable
    python tools/profile_report.py --config chain3 --trace /tmp/t.json

Deploys the app, warms the chunk shape once (compiles never pollute the
measurement), enables sampled synchronous step timing
(``runtime.cost_start``, obs/costmodel.py — every chunk by default in
this tool, ``--every N`` to sample), replays synthetic traffic, and
prints one row per cost center ranked by measured wall ms: share of
total, ms/event, p50/p95/p99. The bottom line names the bottleneck the
DAG optimizer / kernel work should attack first (the profile -> rank ->
optimize workflow in docs/performance.md).

Side effects: merges the measured cost table into
``<state dir>/costs.json`` (``--no-save`` to skip) and, with
``--trace PATH``, writes a Chrome trace whose spans carry the measured
device-time annotations (``rt.trace_export``).

Exit status: 0 when the report contains at least one cost center, 1
(with a stderr diagnostic, never a silent empty table) otherwise —
usable as a CI probe like tools/metrics_dump.py. The join configs
additionally require the top-ranked center to be a join side step whose
name carries the kernel that ran (``join/q.left[grid|probe]`` —
docs/performance.md "join kernels").
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

TS0 = 1_700_000_000_000
SYMS = ("IBM", "WSO2", "GOOG", "MSFT")


def _syms(n=None):
    from siddhi_tpu.core.types import GLOBAL_STRINGS
    names = [f"SYM{i:05d}" for i in range(n)] if n else SYMS
    return np.array([GLOBAL_STRINGS.encode(s) for s in names], np.int32)


# every config: the bench workload's app + one generator per stream
# (mirrors bench.py's traffic shapes at profiling scale)
def _cfg_filter():
    ql = """
        @app:playback
        define stream StockStream (symbol string, price float, volume long);
        @info(name = 'q')
        from StockStream[price > 100.0]
        select symbol, price
        insert into OutputStream;
    """
    syms = _syms()

    def gen(rng, ts, n):
        return {"StockStream": [syms[rng.integers(0, len(syms), n)],
                                rng.uniform(0, 200, n).astype(np.float32),
                                rng.integers(1, 1000, n,
                                             dtype=np.int64)]}
    return ql, gen, "q"


def _cfg_chain3():
    ql = """
        @app:playback
        define stream S (sym string, v int, price float);
        @info(name = 'q1')
        from S[v > 3] select sym, v, price insert into S1;
        @info(name = 'q2')
        from S1[price > 10.0] select sym, v, price insert into S2;
        @info(name = 'q3')
        from S2[v < 900] select sym, v, price insert into OutS;
    """
    syms = _syms()

    def gen(rng, ts, n):
        return {"S": [syms[rng.integers(0, len(syms), n)],
                      rng.integers(0, 1000, n).astype(np.int32),
                      rng.uniform(0, 200, n).astype(np.float32)]}
    return ql, gen, "q3"


def _join_cfg(n_symbols):
    # the bench_join shape: the join side steps (left/right) are the
    # expected top cost centers of any profile of this config; the
    # center name carries the kernel that ran
    # (``join/q.left[grid|probe]`` — asserted in main() below)
    ql = """
        @app:playback
        define stream StockStream (symbol string, price float);
        define stream TwitterStream (symbol string, tweets int);
        @info(name = 'q') @cap(window.size='1024', join.pairs='131072')
        from StockStream#window.time(1 sec)
        join TwitterStream#window.time(1 sec)
        on StockStream.symbol == TwitterStream.symbol
        select StockStream.symbol, price, tweets
        insert into OutputStream;
    """
    syms = _syms(n_symbols)

    def gen(rng, ts, n):
        sym = syms[rng.integers(0, len(syms), n)]
        return {"StockStream": [sym,
                                rng.uniform(0, 200, n).astype(np.float32)],
                "TwitterStream": [sym,
                                  rng.integers(0, 50, n)
                                  .astype(np.int32)]}
    return ql, gen, "q"


def _cfg_join():
    return _join_cfg(1024)


def _cfg_join_eq():
    # bench_join_eq: high-cardinality equi key (symbols=8192) — the
    # banded probe kernel's home turf
    return _join_cfg(8192)


def _cfg_seq5():
    ql = """
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
    syms = _syms()

    def gen(rng, ts, n):
        return {"T": [syms[rng.integers(0, len(syms), n)],
                      rng.integers(1, 6, n).astype(np.int32),
                      rng.integers(0, 1000, n).astype(np.int32)]}
    return ql, gen, "q"


CONFIGS = {"filter": _cfg_filter, "chain3": _cfg_chain3,
           "join": _cfg_join, "join_eq": _cfg_join_eq,
           "seq5": _cfg_seq5}


def _numeric_gen(rt):
    """Generator for arbitrary .siddhi apps: ramp traffic into every
    all-numeric stream (the tools/metrics_dump.py approach)."""
    from siddhi_tpu.core.types import AttrType
    numeric = {AttrType.INT: np.int32, AttrType.LONG: np.int64,
               AttrType.FLOAT: np.float32, AttrType.DOUBLE: np.float64}
    dtypes = {}
    for sid in rt.input_handlers:
        ds = [numeric.get(a.type) for a in rt.schemas[sid].attributes]
        if all(d is not None for d in ds):
            dtypes[sid] = ds

    def gen(rng, ts, n):
        return {sid: [(np.arange(n) % 97 + 1).astype(d) for d in ds]
                for sid, ds in dtypes.items()}
    return gen


class _Drain:
    """One-slot device-batch holder (bench.py's _Last): keeps HBM flat
    during the replay without adding per-chunk syncs of its own."""

    def __init__(self):
        self.out = None

    def __call__(self, out):
        self.out = out

    def drain(self):
        if self.out is not None:
            import jax
            jax.block_until_ready(self.out.valid)
            self.out = None


def profile(ql, gen, tail, events, chunk, every,
            trace=None, save=True) -> tuple:
    """Deploy, warm, profile; returns (report, app_name, saved_path).
    The runtime is shut down before returning."""
    from siddhi_tpu import SiddhiManager
    rt = SiddhiManager().create_siddhi_app_runtime(ql)
    drain = _Drain()
    if tail is not None and tail in rt.queries:
        rt.queries[tail].batch_callbacks.append(drain)
    rt.start()
    rng = np.random.default_rng(7)
    clock = [TS0]

    def send(n):
        ts = clock[0] + np.arange(n, dtype=np.int64)
        clock[0] += n
        for sid, cols in gen(rng, ts, n).items():
            rt.get_input_handler(sid).send_arrays(ts, cols)
        drain.drain()

    send(chunk)                      # warm: compiles stay out of the
    send(chunk)                      # measurement (sticky encodings too)
    rt.cost_start(every=every)
    if trace:
        rt.trace_start()
    for _ in range(max(1, events // chunk)):
        send(chunk)
    report = rt.cost_report()
    rt.cost_stop()
    name = rt.name
    if trace:
        rt.trace_export(trace)
    saved = None
    if save and report["steps"]:
        saved = rt.cost_save()
    rt.shutdown()
    return report, name, saved


def render(report: dict, name: str, events: int, saved) -> str:
    prof = report["profiling"]
    lines = [f"pipeline cost report — app '{name}' "
             f"({events} events, every={prof['every']}, "
             f"{prof['samples']} samples)", ""]
    hdr = (f"{'rank':>4}  {'step':<28} {'kind':<10} {'share%':>7} "
           f"{'ms/event':>10} {'ms_total':>10} {'p50_ms':>8} "
           f"{'p95_ms':>8} {'p99_ms':>8} {'samples':>8}")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for i, s in enumerate(report["steps"], 1):
        lines.append(
            f"{i:>4}  {s['step']:<28} {s['kind']:<10} "
            f"{s['share_pct']:>7.2f} "
            f"{s.get('ms_per_event', float('nan')):>10.6f} "
            f"{s['ms_total']:>10.2f} {s.get('p50_ms', 0):>8.3f} "
            f"{s.get('p95_ms', 0):>8.3f} {s.get('p99_ms', 0):>8.3f} "
            f"{s['samples']:>8}")
    if "bottleneck" in report:
        lines += ["", f"bottleneck: {report['bottleneck']['verdict']}"]
    for sid, q in (report.get("queues") or {}).items():
        lines.append(f"queue {sid}: depth={q['depth']} "
                     f"trend={q['trend']}")
    if saved:
        lines += ["", f"cost table saved: {saved}"]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("app", nargs="?",
                    help="path to a .siddhi app (all-numeric streams "
                    "get synthetic ramp traffic)")
    ap.add_argument("--config", choices=sorted(CONFIGS),
                    help="profile a bench.py workload instead of an "
                    "app file")
    ap.add_argument("--events", type=int, default=16384,
                    help="events to replay under profiling (per stream)")
    ap.add_argument("--chunk", type=int, default=2048,
                    help="rows per chunk")
    ap.add_argument("--every", type=int, default=1,
                    help="sample every Nth chunk (1 = time every chunk)")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON")
    ap.add_argument("--trace", metavar="PATH",
                    help="also write a Chrome trace with cost "
                    "annotations merged into the spans")
    ap.add_argument("--no-save", action="store_true",
                    help="skip merging into the persisted costs.json")
    args = ap.parse_args(argv)
    if bool(args.app) == bool(args.config):
        ap.error("pass exactly one of <app.siddhi> or --config")

    if args.config:
        ql, gen, tail = CONFIGS[args.config]()
    else:
        ql, tail = open(args.app).read(), None
        # app-file mode: build the generator from the deployed schemas
        from siddhi_tpu import SiddhiManager
        probe_rt = SiddhiManager().create_siddhi_app_runtime(ql)
        gen = _numeric_gen(probe_rt)
        probe_rt.shutdown()

    report, name, saved = profile(ql, gen, tail, args.events,
                                  args.chunk, args.every,
                                  trace=args.trace,
                                  save=not args.no_save)
    if args.json:
        print(json.dumps({"app": name, "events": args.events,
                          "saved": saved, **report}))
    else:
        print(render(report, name, args.events, saved))
    if not report["steps"]:
        # never exit 0 with an empty table: zero measured centers means
        # the replay produced no dispatches (non-numeric stream schemas
        # in app-file mode, too few --events for the --chunk, or the
        # app's queries never fired) — name the likely causes instead of
        # printing an empty report and calling it success
        target = args.config or args.app
        print(f"profile_report: no cost centers measured for "
              f"'{target}' ({args.events} events, chunk {args.chunk}) — "
              "no step dispatched under profiling. Check that the app's "
              "streams received traffic (app-file mode generates ramps "
              "only for all-numeric streams) and that --events covers "
              "at least one chunk.", file=sys.stderr)
        return 1
    if args.config in ("join", "join_eq"):
        # the join configs' contract: the top-ranked center is a join
        # side step AND its name says which kernel ran — the probe/grid
        # split is the whole point of profiling this workload
        top = report["steps"][0]["step"]
        if not (top.startswith("join/q.")
                and ("[grid]" in top or "[probe]" in top)):
            print(f"profile_report: --config {args.config} expected a "
                  f"join side center named 'join/q.<side>[grid|probe]' "
                  f"on top of the ranking, got '{top}' — the join "
                  "kernel did not dominate (or the center lost its "
                  "kernel tag)", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

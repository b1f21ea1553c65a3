"""Record the small trace the reducer tests read: a traced run of the
first cell of BENCHMARK.json at 1,024-row chunks, its profiler trace and
its aligned spans.

    python3 benchmark/tests/record_trace.py <out_dir>

Run on a TPU. Writes <out_dir>/trace.xplane.pb, <out_dir>/spans.json
and prints each plane of the trace with its lines, event counts and time
range, so the reducer's plane and line names can be checked by eye.
"""
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import run as runmod  # noqa: E402


def main(out: str) -> None:
    state = runmod.environment()
    from harness import drive, spec
    import siddhi_tpu  # noqa: F401
    from jax.profiler import ProfileData
    bench = spec.benchmark()
    cell = spec.Cell(bench, bench["workloads"][0]["name"])
    cell.traffic = {**cell.traffic, "block_events": 16384,
                    "chunk_rows": 1024, "warm_chunks": 2}
    keep = tempfile.mkdtemp(prefix="siddhi_bench_keep_")
    run, cr = drive.execute(cell, 1, 0.6, True, time.perf_counter(),
                            keep_trace=keep)
    shutil.rmtree(state, ignore_errors=True)
    print(json.dumps({"checks": run.checks, "chunks": run.traced.chunks}))
    os.makedirs(out, exist_ok=True)
    path = [os.path.join(d, f) for d, _, fs in os.walk(keep) for f in fs
            if f.endswith(".xplane.pb")][0]
    kept = os.path.join(out, "trace.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(keep, ignore_errors=True)
    with open(os.path.join(out, "spans.json"), "w") as f:
        json.dump({"lo": run.traced.device.lo, "hi": run.traced.device.hi,
                   "events": run.traced.events, "chunks": run.traced.chunks,
                   "spans": run.traced.spans}, f)
    for plane in ProfileData.from_file(kept).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            if evs:
                print("   line", repr(line.name), len(evs),
                      min(e.start_ns for e in evs),
                      max(e.start_ns + e.duration_ns for e in evs),
                      sorted({e.name for e in evs})[:6])


if __name__ == "__main__":
    main(sys.argv[1])

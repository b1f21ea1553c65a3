"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (configuration, traffic mix, chips) and its metrics come from
BENCHMARK.json; their files are found by name (README.md in this
directory). With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a traced
sub-window. Every run compares every row the output callback received
with the plain reference over exactly the events sent, and prints each
number compared beside its limit, last on standard error and last in the
result line. With no TPU, or fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def environment() -> str:
    """The compile cache at a fixed path inside the checkout, and an
    empty run-state directory so no stored cost table steers the plan.
    Returns the state directory, which the caller removes."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    state = tempfile.mkdtemp(prefix="siddhi_bench_state_")
    os.environ["SIDDHI_TPU_STATE_DIR"] = state
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    return state


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU visible (platform {info['platform']!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, {len(devs)} "
                     "visible")
    return info


class NoChip(RuntimeError):
    pass


def result(run, dev: dict) -> dict:
    from harness.spec import reader
    metrics = {}
    for m in run.cell.metrics(run.trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {**dev, "memory_peak_bytes": run.memory_peak_bytes}
    out = {}
    if run.trace:
        tr = run.traced
        device["busy_s"] = tr.device.mean_busy_ns() / 1e9
        device["window_s"] = tr.device.window_ns / 1e9
        out["breakdown"] = {"device_ops": tr.device.top_ops(),
                            "idle_gaps": tr.device.idle_gaps(tr.spans)}
    correct = all(v <= lim if op == "max" else v >= lim
                  for _, v, op, lim in run.checks)
    return {"correct": correct, "attempted": run.events_window,
            "failed": run.failed, "metrics": metrics, "device": device,
            **out, "checks": {k: {"value": v, op: lim}
                              for k, v, op, lim in run.checks}}


def main(argv=None, require_tpu: bool = True) -> int:
    args = parse(argv)
    state = environment()
    try:
        from harness import drive, spec
        cell = spec.Cell(spec.benchmark(), args.workload)
        try:
            dev = device_info(cell.chips, require_tpu)
        except NoChip as e:
            print(f"run.py: {e}; nothing was run", file=sys.stderr)
            return 2
        import siddhi_tpu  # noqa: F401 — 64-bit types before any array
        run, _ = drive.execute(cell, args.seed, args.seconds,
                               bool(args.trace), T_START)
        res = result(run, dev)
    finally:
        shutil.rmtree(state, ignore_errors=True)
    for k, c in res["checks"].items():
        (op, lim), = [(o, c[o]) for o in ("max", "min") if o in c]
        print(f"check {k} {c['value']} {op} {lim}", file=sys.stderr,
              flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

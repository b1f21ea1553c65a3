"""The control's readings at a cell's own size: the configuration's
control (its reference with one stated guarantee broken) put in the
program's place, compared as a run compares the program's rows.

    python3 benchmark/tests/control_readings.py --workload <name> \
        --events <as many as a run sends> --seeds 1 2 3

Prints one JSON line per seed with the comparison's numbers; the limit
of `rows_differing` (0) lies below the smallest of them. Needs no chip:
the control and the reference are plain numpy and Python.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import drive, gen, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--events", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(spec.benchmark(), args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        t = gen.Traffic(cell.config, cell.traffic, seed)
        ts, cols = t.stream(args.events)
        want = cell.reference.reference(ts, cols, t.labels)
        t_ref = time.perf_counter() - t0
        ctrl = cell.reference.control(ts, cols, t.labels)
        how = cell.config["compare"]
        print(json.dumps({
            "workload": args.workload, "seed": seed, "events": args.events,
            "rows_expected": len(want), "control_rows": len(ctrl),
            "rows_differing": drive.rows_differing(ctrl, want, how),
            "reference_s": t_ref}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

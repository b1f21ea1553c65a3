#!/usr/bin/env python
"""Plan explain CLI: render, diff and regression-gate the planner's
decisions for a SiddhiQL app (docs/observability.md "Explain").

    python tools/explain.py app.siddhi              # human-readable
    python tools/explain.py app.siddhi --json       # full report JSON
    python tools/explain.py app.siddhi --dot        # Graphviz digraph
    python tools/explain.py app.siddhi -o plan.json # write report
    python tools/explain.py app.siddhi --expect plan.json
                                        # exit 1 when decisions moved
    python tools/explain.py --diff A.json B.json    # exit 1 on any
                                        # decision-level change

Deploys the app (started, so fusion segments derive exactly as they
would in production), assembles the ExplainReport (obs/explain.py —
zero new compiles, zero device reads), and prints it. ``--diff`` and
``--expect`` compare ONLY the hashed sections (decisions + graph):
live stats and compile wall times never trip the gate. With no app
argument a small built-in demo app explains — a smoke probe like
tools/metrics_dump.py.

Exit status: 0 on success / clean diff; 1 when --diff/--expect finds
any decision change (each change printed as `path: a -> b`); 2 on
usage errors.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

DEMO_APP = """
@app:name('explain_demo')
@app:playback
define stream S (sym string, v int, price double);
@info(name = 'q1') from S[v > 3] select sym, v, price insert into S1;
@info(name = 'q2') from S1[price > 10.0] select sym, v, price
insert into S2;
@info(name = 'q3') from S2#window.lengthBatch(64)
select sym, count(v) as n insert into Out;
"""


def _load_report(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _print_diff(diff: dict, a_name: str, b_name: str) -> None:
    print(f"plan_hash: {diff['plan_hash_a']} ({a_name}) vs "
          f"{diff['plan_hash_b']} ({b_name})")
    if diff["equal"]:
        print("plans are identical (0 decision changes)")
        return
    print(f"{len(diff['changes'])} decision change(s):")
    for ch in diff["changes"]:
        print(f"  {ch['summary']}")


def build_report(path: str = None) -> dict:
    from siddhi_tpu import SiddhiManager
    text = DEMO_APP
    if path is not None:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(text)
    try:
        rt.start()   # fusion segments derive at start
        return rt.explain()
    finally:
        rt.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="explain.py",
        description="render/diff the compiled plan of a SiddhiQL app")
    ap.add_argument("app", nargs="?", default=None,
                    help=".siddhi file (default: built-in demo app)")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON")
    ap.add_argument("--dot", action="store_true",
                    help="print a Graphviz digraph of the plan")
    ap.add_argument("-o", "--output", default=None,
                    help="also write the report JSON to this path")
    ap.add_argument("--expect", default=None, metavar="REPORT.json",
                    help="compare against a stored report; exit 1 on "
                         "any decision change")
    ap.add_argument("--diff", nargs=2, default=None,
                    metavar=("A.json", "B.json"),
                    help="diff two stored reports; exit 1 on any "
                         "decision change")
    args = ap.parse_args(argv)

    from siddhi_tpu.obs.explain import explain_diff, render_text, to_dot

    if args.diff is not None:
        a, b = (_load_report(p) for p in args.diff)
        diff = explain_diff(a, b)
        _print_diff(diff, args.diff[0], args.diff[1])
        return 0 if diff["equal"] else 1

    report = build_report(args.app)
    if args.output:
        with open(args.output, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1, sort_keys=True, default=str)
    if args.expect is not None:
        diff = explain_diff(_load_report(args.expect), report)
        _print_diff(diff, args.expect, args.app or "<demo>")
        return 0 if diff["equal"] else 1
    if args.dot:
        sys.stdout.write(to_dot(report))
    elif args.json:
        print(json.dumps(report, indent=1, sort_keys=True, default=str))
    else:
        sys.stdout.write(render_text(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())

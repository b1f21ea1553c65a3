"""The command as the driver calls it, and BENCHMARK.json's shape."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from tiny import BENCH, spec

ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         spec.benchmark()["workloads"][0]["name"], "--seed", "2147483999", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_2_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")


def test_benchmark_json_names_files_that_exist():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(BENCH, "configs",
                                           c["name"] + ".py"))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    cells = {w["name"]: w for w in b["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)
    for w in cells.values():
        assert w["config"] in configs and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", [])) <= set(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for name in cells:
        reports = [m for m in b["end_to_end"]
                   if name in m.get("workloads", [name])]
        assert len(reports) >= 2
        layer = [m for m in b["per_layer"]
                 if name in m.get("workloads", [name])]
        assert layer
        assert all(m["moves"] in {r["name"] for r in reports}
                   for m in layer)


@pytest.mark.parametrize("name", [c["name"] for c in
                                  spec.benchmark()["configs"]])
def test_config_file_states_what_it_runs(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        c = json.load(f)
    assert c["name"] == name
    for key in ("app", "stream", "output", "columns", "time", "compare",
                "guarantees", "assumed", "reduced", "source"):
        assert key in c
    assert c["compare"] in ("sequence", "multiset")

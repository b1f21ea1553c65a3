"""Tier-1 bench smoke: `bench.py --quick <config>` must exit 0 and print
a parseable JSON line — guards the rc=124 / `"parsed": null` regression
class permanently (BENCH_r05 timed out with an empty tail; bench.py now
flushes a JSON line per config AND each single-config invocation prints
its own line).

Runs at a tiny event scale on the CPU backend so the whole smoke stays
inside the tier-1 budget. The bench child gets its platform and four
forced CPU devices (one v5e host's worth) from its environment alone.
"""
import copy
import json
import os
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "bench.py")
TOOLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "tools")

# one subprocess run per config per session: the bench_diff gate test
# reuses the filter run instead of paying a second ~30s child
_RUNS: dict = {}


def _run_config(name: str) -> dict:
    if name in _RUNS:
        return copy.deepcopy(_RUNS[name])
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        SIDDHI_BENCH_SCALE="0.008",   # ~8k events: smoke, not a benchmark
        SIDDHI_BENCH_REPS="1",
        SIDDHI_BENCH_FRONTIER_ITERS="8",   # frontier smoke, not a curve
    )
    proc = subprocess.run(
        [sys.executable, BENCH, "--quick", name],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, \
        f"bench.py {name} rc={proc.returncode}\n{proc.stderr[-2000:]}"
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"no JSON line in stdout:\n{proc.stdout[-2000:]}"
    parsed = json.loads(lines[-1])
    assert parsed is not None
    _RUNS[name] = copy.deepcopy(parsed)
    return parsed


def _assert_plan(d: dict):
    """Every app-backed config's JSON line carries a parseable `plan`
    block: {plan_hash, decisions} — BENCH_r*.json records WHAT was
    measured, not just how fast (obs/explain.py; the bench_diff gate
    reads the hash)."""
    plan = d["plan"]
    assert "error" not in plan, plan
    assert isinstance(plan["plan_hash"], str) and len(plan["plan_hash"])
    assert isinstance(plan["decisions"], dict)
    assert "window_compaction" in plan["decisions"]


def _assert_audit(d: dict):
    """Every app-backed config's JSON line carries the compiled-program
    audit block: {programs, bytes_est_total, findings} — the artifact
    records that what was measured is statically clean at the jaxpr
    level (donation aliased, no host callbacks, strong dtypes; see
    analysis/programs.py and docs/tpu_hygiene.md "Compiled-program
    audit"). A finding here means the bench measured a hazardous
    program set."""
    audit = d["audit"]
    assert "error" not in audit, audit
    assert audit["programs"] > 0
    assert audit["bytes_est_total"] > 0
    assert audit["findings"] == 0, audit


def test_bench_filter_quick_parses():
    d = _run_config("filter")
    assert d["unit"] == "events/s"
    assert d["value"] > 0 and d["events"] > 0
    # AOT compile phase must be reported (the PR-5 acceptance metric):
    # compile wall ms + dispatch-ready time-to-first-result
    assert d["compile_ms"] > 0
    assert d["ttfr_ms"] > 0
    assert d["warm_programs"] > 0
    # per-config registry dump (BENCH_r06+): must parse as a dict of
    # dotted siddhi.* metrics (docs/observability.md)
    assert isinstance(d["metrics"], dict)
    assert any(k.startswith("siddhi.") for k in d["metrics"])
    _assert_plan(d)
    _assert_audit(d)


def test_bench_chain3_quick_parses_fused_vs_unfused():
    d = _run_config("chain3")
    assert d["unit"] == "events/s"
    assert d["value"] > 0
    # fused vs unfused events/s must both be reported (the chain-fusion
    # acceptance metric)
    assert d["fused_eps"] > 0 and d["unfused_eps"] > 0
    assert d["fused_speedup"] > 0
    assert d["compile_ms"] > 0 and d["ttfr_ms"] > 0
    assert isinstance(d["metrics"], dict)
    assert any(k.startswith("siddhi.") for k in d["metrics"])
    # the plan block must record the fused segment (what was measured)
    _assert_plan(d)
    _assert_audit(d)
    segs = d["plan"]["decisions"]["fusion"]["segments"]
    assert segs and segs[0]["members"] == ["q1", "q2", "q3"]
    # cost attribution of the fused run: ONE chain center, members named
    _assert_breakdown(d, top_kind="chain")


def _assert_breakdown(d: dict, top_kind=None):
    """Per-config `stage_breakdown` (obs/costmodel.py cost_report shape):
    ranked steps whose shares sum to ~100."""
    sb = d["stage_breakdown"]
    assert "error" not in sb, sb
    assert sb["steps"], "no cost centers measured"
    assert abs(sum(s["share_pct"] for s in sb["steps"]) - 100.0) < 1.0
    assert sb["bottleneck"]["step"] == sb["steps"][0]["step"]
    if top_kind is not None:
        assert sb["steps"][0]["kind"] == top_kind, sb["steps"]


def _assert_frontier(d: dict):
    """The recorded latency/throughput frontier (ROADMAP item 3's
    acceptance artifact): one row per chunk size with events/s and
    p50/p95/p99 latency."""
    fr = d["frontier"]
    assert [row["chunk"] for row in fr] == [64, 256, 1024]
    for row in fr:
        assert "error" not in row, row
        assert row["events_per_s"] > 0
        assert row["p99_ms"] >= row["p95_ms"] >= row["p50_ms"] > 0


def test_bench_seq5_quick_parses_frontier_and_breakdown():
    d = _run_config("seq5")
    assert d["unit"] == "events/s"
    assert d["value"] > 0
    assert d["p99_ms"] > 0 and d["p99_ms_1k"] > 0
    _assert_plan(d)
    _assert_audit(d)
    _assert_frontier(d)
    _assert_breakdown(d, top_kind="pattern")


def test_bench_join_quick_parses_frontier_and_breakdown():
    d = _run_config("join")
    assert d["unit"] == "events/s"
    assert d["value"] > 0
    assert d["pairs_dropped"] == 0
    _assert_frontier(d)
    # the join side steps must top the join config's ranking, and the
    # center name must say which kernel ran (docs/performance.md
    # "join kernels")
    _assert_breakdown(d, top_kind="join")
    top = d["stage_breakdown"]["steps"][0]["step"]
    assert top.startswith("join/q.")
    assert "[probe]" in top or "[grid]" in top
    # both kernels measured: the auto pick (probe for this equi ON) and
    # the pinned grid comparison pass, each with a frontier
    assert d["join_kernel"] == "probe"
    # plan block: the kernel decision rides the artifact with a cause
    _assert_plan(d)
    _assert_audit(d)
    jk = d["plan"]["decisions"]["join_kernels"]
    assert jk["q.left"]["kernel"] == "probe"
    assert jk["q.left"]["cause"]
    assert d["grid_eps"] > 0
    assert d["probe_speedup_vs_grid"] > 0
    for row in d["frontier_grid"]:
        assert "error" not in row, row
        assert row["events_per_s"] > 0


def test_bench_multichip_quick_parses():
    """Mesh scale-out config (ROADMAP item 1): on four forced CPU
    devices the child must emit {n_devices, eps_aggregate,
    eps_per_device, scaling_efficiency} per arm and name the platform
    it ran on — guards the rc=124/empty-tail class. Scaling VALUES are
    not asserted: CPU devices share the host's cores."""
    d = _run_config("multichip")
    assert d["unit"] == "events/s"
    assert d["n_devices"] == 4
    assert d["platform"] == "cpu" and d["device_count"] == 4
    assert set(d["arms"]) == {"filter", "seq5", "tenants"}
    for arm, entry in d["arms"].items():
        assert entry["n_devices"] == 4, (arm, entry)
        assert entry["eps_aggregate"] > 0
        assert entry["eps_per_device"] > 0
        assert abs(entry["eps_per_device"] * 4
                   - entry["eps_aggregate"]) < 1.0
        assert entry["eps_1dev"] > 0
        assert entry["scaling_efficiency"] > 0
    assert d["value"] == d["arms"]["filter"]["eps_aggregate"]
    assert d["arms"]["tenants"]["tenants"] > 0


def test_bench_tenants_quick_parses():
    """Multi-tenant serving config (ROADMAP item 2): pooled vs separate
    aggregate events/s with ONE compile-service program set per
    template. The smoke runs tiny pools; the full run measures
    N in {64, 256, 1024}."""
    os.environ.setdefault("SIDDHI_BENCH_TENANTS", "4,8")
    os.environ.setdefault("SIDDHI_BENCH_TENANTS_SEP", "4")
    d = _run_config("tenants")
    assert d["unit"] == "events/s"
    assert d["value"] > 0
    assert d["eps_pooled"] > 0 and d["eps_separate"] > 0
    assert d["speedup"] > 0
    assert d["compile_ms"] > 0
    for n, entry in d["tenants"].items():
        assert entry["program_sets"] == 1, (n, entry)
        assert entry["eps_pooled"] > 0
    # skewed-traffic SLO arm (obs/slo.py): measured p50/p99 attainment
    # vs the configured objective must parse with burn-rate state
    _assert_plan(d)   # the pool's template plan block
    _assert_audit(d)  # ...and its template-keyed program audit
    slo = d["slo"]
    assert slo["objective_p99_ms"] > 0
    assert slo["samples"] > 0, slo
    assert slo["p99_ms"] > 0 and slo["p50_ms"] > 0
    assert 0.0 <= slo["attainment"] <= 1.0
    assert slo["state"] in ("OK", "WARN", "PAGE")
    assert slo["hot_p99_ms"] > 0 and slo["cold_p99_ms_max"] > 0
    assert slo["skew"] > 1
    # QoS fairness arm (docs/serving.md "QoS dials"): hot tenant at 8x
    # with and without QoS — the hot tenant must be throttled with a
    # Retry-After, the starved tenant's p99 must hold the 2x-of-fair
    # bound, and the priority classes must drain high -> normal -> low
    fair = d["fairness"]
    assert fair["skew"] > 1
    assert fair["throttled_429s"] > 0
    assert fair["retry_after_ms"] and fair["retry_after_ms"] > 0
    assert fair["starved_p99_ms_fair"] > 0
    assert fair["starved_p99_ms_qos"] > 0
    assert fair["p99_bounded"] is True, fair
    assert fair["class_drain_order"][0] == "high"
    assert fair["class_drain_order"][-1] == "low"
    assert all(fair["drain_rounds"][t] for t in ("hi", "cold", "lo"))
    # live-migration rebalance arm (docs/serving.md "Live migration &
    # rebalance"): 8x skew on a sharded pool, one migration moves the
    # hot tenant off the shared device — the starved p99 must come
    # back within the 2x-of-fair bound, bit-identically, zero loss
    # (the smoke child gets four forced CPU devices)
    reb = d["rebalance"]
    assert "skipped" not in reb, reb
    assert reb["skew"] == 8
    assert reb["starved_p99_ms_before"] > 0
    assert reb["starved_p99_ms_after"] > 0
    assert reb["starved_p99_ms_fair"] > 0
    assert reb["p99_restored"] is True, reb
    assert reb["bit_identical"] is True, reb
    assert reb["migration_pause_ms"] >= 0
    assert reb["rows_moved"] >= 0
    assert reb["lost"] == 0 and reb["duplicates"] == 0
    # packed pool ingest acceptance (docs/performance.md "Packed pool
    # ingest"): ONE device transfer per ingest stream per fair round —
    # the filter template has one ingest stream, so the per-round
    # transfer count must not exceed 1
    pk = d["packed_ingest"]
    assert 0 < pk["transfers_per_round"] <= 1.0 + 1e-9, pk
    assert pk["rows_packed"] > 0
    assert 0.0 <= pk["pad_frac"] < 1.0
    # operator-class arms (docs/serving.md "Poolable operator
    # classes"): pattern NFA and two-stream equi-join pools measured
    # pooled-vs-separate with the same one-program-set compile story
    for arm, n_streams in (("pattern_template", 1),
                           ("join_template", 2)):
        e = d[arm]
        assert e["eps_pooled"] > 0 and e["eps_separate"] > 0, (arm, e)
        assert e["speedup"] > 0
        assert e["program_sets"] == 1
        assert e["compile_ms"] > 0
        assert e["ingest_streams"] and \
            len(e["ingest_streams"]) == n_streams
        epk = e["packed_ingest"]
        assert 0 < epk["transfers_per_round"] <= n_streams + 1e-9, \
            (arm, epk)
        assert epk["rows_packed"] > 0
        assert 0.0 <= epk["pad_frac"] < 1.0


def test_bench_fanout_quick_parses():
    """Plan-optimizer config (ROADMAP item 5): optimized vs
    SIDDHI_TPU_OPT=0 events/s for the 1-stream -> 4-subscriber shape,
    with the fan-out fusion decision + CSE share classes recorded in
    the plan block. The speedup VALUE is not asserted: on a CPU host the
    shared packed-buffer encode bounds the gap; a speedup is read off a
    chip run only."""
    d = _run_config("fanout")
    assert d["unit"] == "events/s"
    assert d["value"] > 0
    assert d["optimized_eps"] > 0 and d["unoptimized_eps"] > 0
    assert d["opt_speedup"] > 0
    assert d["subscribers"] == 4
    assert d["compile_ms"] > 0 and d["ttfr_ms"] > 0
    _assert_plan(d)
    _assert_audit(d)
    # the plan block records WHAT the optimizer did: the fused group
    # with its cause slug, and the shared-prefix classes
    fan = d["plan"]["decisions"]["optimizer"]["fanout"]["S"]
    assert fan["fused"] is True
    assert fan["cause"] in ("fused-default", "cost-evidence-fused")
    assert fan["members"] == ["q1", "q2", "q3", "q4"]
    assert any(set(c["queries"]) >= {"q1", "q2"} for c in fan["cse"])
    # cost attribution of the optimized run: ONE fanout center
    _assert_breakdown(d, top_kind="fanout")
    assert d["stage_breakdown"]["steps"][0]["step"] == "fanout/S"


def test_bench_ingest_quick_parses():
    """The pipelined-ingest arm: the JSON line must carry the
    `ingest_overlap` block — encode vs dispatch wall time, overlap
    fraction, pipeline-vs-serial events/s — and the zero-copy counters
    must show no defensive copies on conformant columns."""
    d = _run_config("ingest")
    assert d["unit"] == "events/s"
    assert d["value"] > 0 and d["events"] > 0
    ov = d["ingest_overlap"]
    assert ov["chunks_per_send"] >= 2, \
        "smoke must split into multiple pipeline chunks"
    for k in ("encode_s", "dispatch_s", "wall_s", "overlap_s"):
        assert isinstance(ov[k], (int, float)) and ov[k] >= 0, (k, ov)
    assert 0.0 <= ov["overlap_frac"] <= 1.0
    assert ov["eps_pipeline"] > 0 and ov["eps_serial"] > 0
    # conformant numpy columns must encode with ZERO coercion copies
    assert ov["zero_copy"]["coerced_arrays"] == 0, ov
    assert ov["serial_zero_copy"]["coerced_arrays"] == 0, ov
    assert ov["zero_copy"]["view_lanes"] > 0, ov


def test_bench_diff_gates_overlap_drop(tmp_path):
    """Losing the encode/device overlap (ingest_overlap.overlap_frac
    dropping > 0.25 absolute) fails the bench_diff gate even when
    events/s held — the pipeline silently degrading to serial is a
    regression the throughput number can hide on small runs."""
    if str(TOOLS) not in sys.path:
        sys.path.insert(0, str(TOOLS))
    import bench_diff
    base = {"config": "ingest", "value": 1000.0, "unit": "events/s",
            "ingest_overlap": {"overlap_frac": 0.6}}
    a = tmp_path / "a.json"
    a.write_text(json.dumps(base) + "\n")
    assert bench_diff.main([str(a), str(a)]) == 0
    dropped = copy.deepcopy(base)
    dropped["ingest_overlap"]["overlap_frac"] = 0.1
    b = tmp_path / "b.json"
    b.write_text(json.dumps(dropped) + "\n")
    assert bench_diff.main([str(a), str(b)]) == 1
    # small jitter stays under the 0.25 absolute band: clean
    jitter = copy.deepcopy(base)
    jitter["ingest_overlap"]["overlap_frac"] = 0.45
    c = tmp_path / "c.json"
    c.write_text(json.dumps(jitter) + "\n")
    assert bench_diff.main([str(a), str(c)]) == 0


def test_bench_diff_gate_on_optimizer_flip(tmp_path):
    """An OPTIMIZER decision flip (SIDDHI_TPU_OPT=0 plan vs the
    measured optimized plan) is a plan change: tools/bench_diff.py
    exits 1 without --allow-plan-change even when throughput is
    unchanged — the same gate the kernel-flip case trips."""
    if str(TOOLS) not in sys.path:
        sys.path.insert(0, str(TOOLS))
    import bench_diff
    d = _run_config("fanout")      # memoized: shares the fanout child
    a = tmp_path / "a.json"
    a.write_text(json.dumps({"config": "fanout", **d}) + "\n")

    # derive the REAL unoptimized plan in-process (not a doctored hash)
    sys.path.insert(0, os.path.dirname(BENCH))
    import bench
    os.environ["SIDDHI_TPU_OPT"] = "0"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        from siddhi_tpu import SiddhiManager
        rt = SiddhiManager().create_siddhi_app_runtime(bench.FANOUT_APP)
        rt.start()
        plan0 = {"plan_hash": rt.explain(live=False)["plan_hash"],
                 "decisions": rt.explain(live=False)["decisions"]}
        rt.shutdown()
    finally:
        os.environ.pop("SIDDHI_TPU_OPT", None)
    assert plan0["plan_hash"] != d["plan"]["plan_hash"], \
        "optimizer flip must move the plan hash"
    flipped = copy.deepcopy(d)
    flipped["plan"] = plan0
    b = tmp_path / "b.json"
    b.write_text(json.dumps({"config": "fanout", **flipped}) + "\n")
    assert bench_diff.main([str(a), str(b)]) == 1
    assert bench_diff.main([str(a), str(b), "--allow-plan-change"]) == 0


def test_bench_diff_gate(tmp_path):
    """tools/bench_diff.py regression gate: a --quick run diffed
    against itself exits 0; a doctored copy (halved events/s + flipped
    plan_hash) exits 1 — and a plan-only change still exits 1 unless
    --allow-plan-change acknowledges it."""
    if str(TOOLS) not in sys.path:
        sys.path.insert(0, str(TOOLS))
    import bench_diff
    d = _run_config("filter")   # memoized: shares the filter child
    a = tmp_path / "a.json"
    a.write_text(json.dumps({"config": "filter", **d}) + "\n")

    # identical artifacts: clean gate
    assert bench_diff.main([str(a), str(a)]) == 0

    # doctored: regression + plan change -> exit 1
    bad = copy.deepcopy(d)
    bad["value"] = d["value"] * 0.5
    bad["plan"]["plan_hash"] = "0" * 16
    b = tmp_path / "b.json"
    b.write_text(json.dumps({"config": "filter", **bad}) + "\n")
    assert bench_diff.main([str(a), str(b)]) == 1

    # plan-only change: exit 1 without the flag, 0 with it
    planned = copy.deepcopy(d)
    planned["plan"]["plan_hash"] = "f" * 16
    c = tmp_path / "c.json"
    c.write_text(json.dumps({"config": "filter", **planned}) + "\n")
    assert bench_diff.main([str(a), str(c)]) == 1
    assert bench_diff.main([str(a), str(c),
                            "--allow-plan-change"]) == 0

    # the summary-object artifact shape parses too (BENCH_r*.json tail)
    summary = tmp_path / "s.json"
    summary.write_text(json.dumps(
        {"metric": "x", "configs": {"filter": d}}) + "\n")
    assert bench_diff.main([str(a), str(summary)]) == 0

"""Pipeline cost profiler (obs/costmodel.py, docs/observability.md):

- sampled synchronous step timing: per-query / fused-chain / join-side /
  pattern-step / partition-block cost centers
- cost_report() ranking: shares sum to ~100%, join [B,W] grid tops the
  join workload, bottleneck verdict
- registry step_ms histograms + statistics()['cost'] view
- default-OFF contract (zero samples, zero step_ms metrics); the
  sync budget at the default stride is in test_obs.py
- persisted cost table (costs.json merge-on-write) for the DAG optimizer
- Chrome trace export carries measured cost annotations
- tools/profile_report.py end to end (--config join ranks the grid top)
"""
import json
import os
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.types import GLOBAL_STRINGS

TS0 = 1_700_000_000_000

FILTER_JOIN_APP = """
    @app:playback
    define stream StockStream (symbol string, price float);
    define stream TwitterStream (symbol string, tweets int);
    @info(name = 'qf')
    from StockStream[price > 0.0] select symbol, price insert into FOut;
    @info(name = 'qj') @cap(window.size='1024', join.pairs='65536')
    from StockStream#window.time(1 sec)
    join TwitterStream#window.time(1 sec)
    on StockStream.symbol == TwitterStream.symbol
    select StockStream.symbol, price, tweets
    insert into JOut;
"""

CHAIN_APP = """
    @app:playback
    define stream S (v int);
    @info(name = 'q1') from S[v > 0] select v insert into M;
    @info(name = 'q2') from S[v < 1000000] select v insert into Out2;
"""


def _start(ql, fanout_fusion=True):
    # fanout_fusion=False pins SIDDHI_TPU_OPT_FANOUT=0: CHAIN_APP fans
    # S out to q1+q2, which the plan optimizer fuses into ONE
    # `fanout/S` center by default — tests that specifically exercise
    # PER-QUERY dispatch centers opt out (the fused center itself is
    # covered in tests/test_optimizer.py)
    prev = os.environ.get("SIDDHI_TPU_OPT_FANOUT")
    if not fanout_fusion:
        os.environ["SIDDHI_TPU_OPT_FANOUT"] = "0"
    try:
        rt = SiddhiManager().create_siddhi_app_runtime(ql)
        rt.start()
        return rt
    finally:
        if not fanout_fusion:
            if prev is None:
                os.environ.pop("SIDDHI_TPU_OPT_FANOUT", None)
            else:
                os.environ["SIDDHI_TPU_OPT_FANOUT"] = prev


def _send_join_traffic(rt, n=1024, chunks=4, n_syms=64, seed=0):
    hs = rt.get_input_handler("StockStream")
    ht = rt.get_input_handler("TwitterStream")
    rng = np.random.default_rng(seed)
    syms = np.array([GLOBAL_STRINGS.encode(f"S{i}") for i in
                     range(n_syms)], np.int32)
    for i in range(chunks):
        ts = TS0 + np.arange(n, dtype=np.int64) + i * n
        sym = syms[rng.integers(0, n_syms, n)]
        hs.send_arrays(ts, [sym,
                            rng.uniform(0, 200, n).astype(np.float32)])
        ht.send_arrays(ts, [sym,
                            rng.integers(0, 50, n).astype(np.int32)])


# ---------------------------------------------------------------------------
# default-OFF contract
# ---------------------------------------------------------------------------


class TestDefaultOff:
    def test_no_samples_and_no_step_ms_metrics_without_cost_start(self):
        rt = _start(FILTER_JOIN_APP)
        _send_join_traffic(rt, n=256, chunks=2)
        assert rt.cost.samples == 0
        report = rt.cost_report()
        assert report["steps"] == []
        assert report["total_ms"] == 0
        assert "bottleneck" not in report
        flat = rt.metrics.collect()
        assert not any("step_ms" in k for k in flat)
        assert "cost" not in rt.statistics()
        rt.shutdown()

    def test_stop_disables_further_sampling(self):
        rt = _start(CHAIN_APP)
        rt.cost_start(every=1)
        h = rt.get_input_handler("S")
        h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                      [np.ones(64, np.int32)])
        n = rt.cost.samples
        assert n > 0
        rt.cost_stop()
        h.send_arrays(TS0 + 64 + np.arange(64, dtype=np.int64),
                      [np.ones(64, np.int32)])
        assert rt.cost.samples == n
        rt.shutdown()


# ---------------------------------------------------------------------------
# attribution + ranking
# ---------------------------------------------------------------------------


class TestCostReport:
    def test_shares_sum_to_100_ranked_and_join_grid_tops(self):
        """The acceptance shape: on a join workload the join side steps
        are the top cost center, shares sum to ~100%, and the ranking
        is descending by measured wall ms. Side-center names carry the
        kernel that ran (``join/<q>.left[grid|probe]``)."""
        rt = _start(FILTER_JOIN_APP)
        _send_join_traffic(rt, n=1024, chunks=1)   # warm compiles
        rt.cost_start(every=1)
        _send_join_traffic(rt, n=1024, chunks=4, seed=1)
        report = rt.cost_report()
        kernels = rt.statistics()["compile"]["join_kernels"]
        rt.shutdown()
        steps = report["steps"]
        names = {s["step"] for s in steps}
        for side in ("left", "right"):
            kern = kernels[f"qj.{side}"]["kernel"]
            assert kern in ("grid", "probe")
            assert f"join/qj.{side}[{kern}]" in names
        assert "query/qf" in names
        # ranked descending, shares sum to ~100
        totals = [s["ms_total"] for s in steps]
        assert totals == sorted(totals, reverse=True)
        assert sum(s["share_pct"] for s in steps) == \
            pytest.approx(100.0, abs=0.5)
        # the join grid dominates the trivial filter
        assert steps[0]["kind"] == "join"
        assert report["bottleneck"]["step"].startswith("join/qj.")
        assert report["bottleneck"]["step"] in \
            report["bottleneck"]["verdict"]
        for s in steps:
            assert s["samples"] > 0
            assert s["ms_per_event"] >= 0
            assert s["p99_ms"] >= s["p95_ms"] >= s["p50_ms"] >= 0

    def test_fused_chain_is_one_center_with_members(self):
        rt = _start(CHAIN_APP, fanout_fusion=False)
        # q1 -> M has one subscriber? CHAIN_APP's q2 reads S, so both
        # queries dispatch separately: use per-query centers here
        rt.cost_start(every=1)
        h = rt.get_input_handler("S")
        h.send_arrays(TS0 + np.arange(128, dtype=np.int64),
                      [np.arange(1, 129, dtype=np.int32)])
        report = rt.cost_report()
        names = {s["step"] for s in report["steps"]}
        assert {"query/q1", "query/q2"} <= names
        rt.shutdown()
        # and the fused variant: one chain center naming its members
        rt2 = _start("""
            @app:playback
            define stream S (v int);
            @info(name = 'q1') from S[v > 0] select v insert into M;
            @info(name = 'q2') from M[v < 9] select v insert into Out;
        """)
        assert rt2.queries["q1"]._fused_chain is not None
        rt2.cost_start(every=1)
        h2 = rt2.get_input_handler("S")
        h2.send_arrays(TS0 + np.arange(128, dtype=np.int64),
                       [np.arange(1, 129, dtype=np.int32)])
        report2 = rt2.cost_report()
        rt2.shutdown()
        chain = [s for s in report2["steps"] if s["kind"] == "chain"]
        assert len(chain) == 1
        assert chain[0]["step"] == "chain/q1+q2"
        assert chain[0]["members"] == ["q1", "q2"]

    def test_pattern_and_partition_centers(self):
        rt = _start("""
            @app:playback
            define stream T (sym string, stage int);
            @info(name = 'qp')
            from every e1=T[stage == 1]
              -> e2=T[stage == 2 and sym == e1.sym] within 10 sec
            select e1.sym as sym insert into POut;
            partition with (sym of T) begin
              @info(name = 'pq')
              from T select sym, count() as c insert into PC;
            end;
        """)
        rt.cost_start(every=1)
        h = rt.get_input_handler("T")
        rng = np.random.default_rng(3)
        syms = np.array([GLOBAL_STRINGS.encode(f"K{i}") for i in
                         range(8)], np.int32)
        for i in range(2):
            ts = TS0 + np.arange(256, dtype=np.int64) + i * 256
            h.send_arrays(ts, [syms[rng.integers(0, 8, 256)],
                               rng.integers(1, 3, 256).astype(np.int32)])
        report = rt.cost_report()
        rt.shutdown()
        names = {s["step"] for s in report["steps"]}
        assert "pattern/qp.T" in names
        assert any(n.startswith("partition/") for n in names)

    def test_sampling_stride(self):
        """every=4 over 8 chunks -> exactly 2 samples per center (the
        first chunk always samples, then every 4th)."""
        rt = _start(CHAIN_APP)
        rt.cost_start(every=4)
        h = rt.get_input_handler("S")
        for i in range(8):
            h.send_arrays(TS0 + np.arange(64, dtype=np.int64) + i * 64,
                          [np.ones(64, np.int32)])
        report = rt.cost_report()
        rt.shutdown()
        for s in report["steps"]:
            assert s["samples"] == 2, s

    def test_registry_histograms_and_statistics_view(self):
        rt = _start(CHAIN_APP, fanout_fusion=False)
        rt.cost_start(every=1)
        h = rt.get_input_handler("S")
        h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                      [np.ones(64, np.int32)])
        flat = rt.metrics.collect()
        base = f"siddhi.{rt.name}.query.q1.step_ms"
        for suffix in (".p50", ".p95", ".p99", ".count", ".sum"):
            assert base + suffix in flat, base + suffix
        stats = rt.statistics()
        assert stats["cost"]["steps"], "cost view missing"
        assert stats["cost"]["bottleneck"]["step"].startswith("query/")
        rt.shutdown()


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


class TestCostPersistence:
    def test_save_merges_and_load_roundtrips(self, tmp_path):
        from siddhi_tpu.obs.costmodel import load_costs
        path = str(tmp_path / "costs.json")
        rt = _start(CHAIN_APP, fanout_fusion=False)
        rt.cost_start(every=1)
        h = rt.get_input_handler("S")
        h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                      [np.ones(64, np.int32)])
        assert rt.cost_save(path) == path
        table = load_costs(path)
        assert "query/q1" in table[rt.name]
        entry = table[rt.name]["query/q1"]
        assert entry["samples"] > 0 and entry["ms_per_event"] >= 0
        # second save merges (same app key, centers updated not lost)
        h.send_arrays(TS0 + 64 + np.arange(64, dtype=np.int64),
                      [np.ones(64, np.int32)])
        rt.cost_save(path)
        table2 = load_costs(path)
        assert table2[rt.name]["query/q1"]["samples"] >= entry["samples"]
        rt.shutdown()

    def test_load_missing_and_corrupt_read_as_empty(self, tmp_path):
        from siddhi_tpu.obs.costmodel import load_costs
        assert load_costs(str(tmp_path / "nope.json")) == {}
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert load_costs(str(bad)) == {}


# ---------------------------------------------------------------------------
# trace annotations
# ---------------------------------------------------------------------------


def test_trace_export_carries_cost_annotations(tmp_path):
    rt = _start(CHAIN_APP, fanout_fusion=False)
    rt.cost_start(every=1)
    rt.trace_start()
    h = rt.get_input_handler("S")
    h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                  [np.ones(64, np.int32)])
    path = rt.trace_export(str(tmp_path / "trace.json"))
    rt.shutdown()
    events = json.load(open(path))["traceEvents"]
    steps = [e for e in events if e["name"] == "step/q1"]
    assert steps, "no step spans recorded"
    assert steps[0]["args"]["cost_ms_total"] >= 0
    assert steps[0]["args"]["cost_samples"] >= 1
    assert "cost_ms_per_event" in steps[0]["args"]


# ---------------------------------------------------------------------------
# compile-cache key stability: profiling changes no jit options
# ---------------------------------------------------------------------------


def test_profiling_triggers_zero_new_compiles(monkeypatch):
    """cost_start() must not change any jit option: the steps compiled
    before profiling serve identically after (cache-key stability rule,
    docs/compile_cache.md)."""
    import jax
    real_jit = jax.jit
    count = [0]

    def counting_jit(*a, **kw):
        count[0] += 1
        return real_jit(*a, **kw)

    monkeypatch.setattr(jax, "jit", counting_jit)
    rt = _start(CHAIN_APP)
    h = rt.get_input_handler("S")
    h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                  [np.ones(64, np.int32)])
    before = count[0]
    rt.cost_start(every=1)
    h.send_arrays(TS0 + 64 + np.arange(64, dtype=np.int64),
                  [np.ones(64, np.int32)])
    assert rt.cost.samples > 0
    assert count[0] == before, "profiling built new jit wrappers"
    rt.shutdown()


# ---------------------------------------------------------------------------
# tools/profile_report.py
# ---------------------------------------------------------------------------


class TestProfileReportTool:
    def _main(self, argv, capsys):
        import os
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)
        import profile_report
        rc = profile_report.main(argv)
        return rc, capsys.readouterr().out

    def test_config_join_ranks_kernel_tagged_side_top_json(self, capsys):
        rc, out = self._main(["--config", "join", "--events", "2048",
                              "--chunk", "1024", "--json", "--no-save"],
                             capsys)
        assert rc == 0
        report = json.loads(out)
        assert report["steps"], "no cost centers measured"
        # the acceptance criterion: a join side step ranks top AND its
        # center name says which kernel ran (main() exits 1 otherwise)
        assert report["steps"][0]["kind"] == "join"
        top = report["bottleneck"]["step"]
        assert top.startswith("join/q.")
        assert "[probe]" in top or "[grid]" in top
        assert sum(s["share_pct"] for s in report["steps"]) == \
            pytest.approx(100.0, abs=0.5)
        assert report["saved"] is None   # --no-save honored

    def test_config_join_grid_override_names_grid_kernel(self, capsys,
                                                         monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_JOIN_KERNEL", "grid")
        rc, out = self._main(["--config", "join", "--events", "1024",
                              "--chunk", "512", "--json", "--no-save"],
                             capsys)
        assert rc == 0
        report = json.loads(out)
        assert "[grid]" in report["bottleneck"]["step"]

    def test_zero_measured_centers_exits_nonzero_with_message(
            self, capsys, tmp_path):
        # a non-numeric stream schema in app-file mode gets no synthetic
        # traffic -> zero dispatches -> must exit 1 AND say why, never
        # print an empty table and call it success
        app = tmp_path / "silent.siddhi"
        app.write_text("""
            @app:name('silent_probe')
            @app:playback
            define stream S (name string);
            @info(name = 'q') from S select name insert into Out;
        """)
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)
        import profile_report
        rc = profile_report.main([str(app), "--events", "256",
                                  "--chunk", "128", "--no-save"])
        assert rc == 1
        err = capsys.readouterr().err
        assert "no cost centers measured" in err

    def test_config_filter_human_report(self, capsys, tmp_path,
                                        monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_STATE_DIR", str(tmp_path))
        rc, out = self._main(["--config", "filter", "--events", "1024",
                              "--chunk", "512"], capsys)
        assert rc == 0
        assert "pipeline cost report" in out
        assert "query/q" in out
        assert "bottleneck:" in out
        # the persisted table landed in the run-state directory
        from siddhi_tpu.obs.costmodel import load_costs
        table = load_costs(str(tmp_path / "costs.json"))
        assert any("query/q" in centers for centers in table.values())

    def test_app_file_mode(self, capsys, tmp_path):
        app = tmp_path / "probe.siddhi"
        app.write_text("""
            @app:name('cost_probe')
            @app:playback
            define stream S (v int);
            @info(name = 'q') from S[v > 0] select v insert into Out;
        """)
        rc, out = self._main([str(app), "--events", "512", "--chunk",
                              "256", "--json", "--no-save"], capsys)
        assert rc == 0
        report = json.loads(out)
        assert report["app"] == "cost_probe"
        assert any(s["step"] == "query/q" for s in report["steps"])


def test_cost_table_and_flight_records_live_outside_the_compile_cache(
        monkeypatch):
    """The plan optimizer reads the cost table: it must not ride in a
    compile-cache directory that other runs share."""
    import os

    import jax
    from siddhi_tpu.obs.costmodel import default_costs_path, state_dir
    from siddhi_tpu.obs.slo import default_flight_dir
    monkeypatch.delenv("SIDDHI_TPU_STATE_DIR", raising=False)
    monkeypatch.delenv("SIDDHI_TPU_FLIGHT_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert state_dir() == os.path.join(repo, ".siddhi_state")
    cache = os.path.abspath(jax.config.jax_compilation_cache_dir)
    for p in (default_costs_path(), default_flight_dir()):
        assert os.path.commonpath([cache, os.path.abspath(p)]) != cache
    monkeypatch.setenv("SIDDHI_TPU_STATE_DIR", "/elsewhere")
    assert default_costs_path() == os.path.join("/elsewhere", "costs.json")

"""Observability layer (siddhi_tpu/obs/, docs/observability.md):

- MetricsRegistry instruments + Prometheus exposition
- statistics() == registry-view equivalence (plain, fused chains,
  partitions, DETAIL latency)
- sampled-sync budget on the filter shape: BASIC stats, the cost
  profiler and SLO tracking add at most one device wait per stride
- @app:statistics(reporter, interval) parsing + parse-time validation
- periodic reporters (console/jsonl)
- service GET /metrics / /health / /ready (readiness tied to
  CompileService warmup)
- chunk-span tracing -> Chrome trace JSON; profiler hooks
- LatencyTracker.summary() thread-safety regression
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import Event, SiddhiManager, StreamCallback
from siddhi_tpu.core.types import GLOBAL_STRINGS
from siddhi_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                    MetricsRegistry, prom_name)
from siddhi_tpu.ops.expr import CompileError

TS0 = 1_700_000_000_000

CHAIN_APP = """
    @app:playback
    define stream S (v int);
    @info(name = 'q1') from S[v > 0] select v insert into M;
    @info(name = 'q2') from M[v < 1000000] select v insert into Out;
"""


def _playback_app(ql, level=None):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    if level is not None:
        rt.set_statistics_level(level)
    rt.start()
    return rt


def _send_ramp(rt, stream, n, base=TS0):
    h = rt.get_input_handler(stream)
    h.send_arrays(base + np.arange(n, dtype=np.int64),
                  [np.arange(1, n + 1, dtype=np.int32)])


# ---------------------------------------------------------------------------
# registry instruments + exposition
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_instruments(self):
        m = MetricsRegistry()
        m.counter("siddhi.a.events").inc(3)
        m.counter("siddhi.a.events").inc(2)
        m.gauge("siddhi.a.depth").set(7)
        for v in (1.0, 2.0, 100.0):
            m.histogram("siddhi.a.lat").observe(v)
        snap = m.collect()
        assert snap["siddhi.a.events"] == 5
        assert snap["siddhi.a.depth"] == 7
        assert snap["siddhi.a.lat.count"] == 3
        assert snap["siddhi.a.lat.p50"] == 2.0
        # same-name different-kind is a programming error
        with pytest.raises(TypeError):
            m.gauge("siddhi.a.events")

    def test_collector_backed_gauges(self):
        m = MetricsRegistry()
        m.register_collector(lambda: {"siddhi.x.live": 42})
        assert m.collect()["siddhi.x.live"] == 42

    def test_prometheus_text(self):
        m = MetricsRegistry()
        m.counter("siddhi.app-1.stream.S.events").inc(9)
        m.gauge("siddhi.app-1.queue.depth").set(2)
        m.histogram("siddhi.app-1.lat").observe(5.0)
        text = m.prometheus_text()
        assert "# TYPE siddhi_app_1_stream_S_events counter" in text
        assert "siddhi_app_1_stream_S_events 9" in text
        assert "# TYPE siddhi_app_1_queue_depth gauge" in text
        assert "# TYPE siddhi_app_1_lat summary" in text
        assert 'siddhi_app_1_lat{quantile="0.5"} 5.0' in text
        assert "siddhi_app_1_lat_count 1" in text

    def test_histogram_p95_and_prometheus_summary_conventions(self):
        """Summaries expose p50/p95/p99 quantile samples PLUS cumulative
        _sum/_count (proper Prometheus summary conventions, so scrapers
        can rate() them)."""
        m = MetricsRegistry()
        h = m.histogram("siddhi.a.step_ms")
        for v in range(1, 101):
            h.observe(float(v))
        s = h.summary()
        assert s["p50"] == 51.0
        assert s["p95"] == 96.0
        assert s["p99"] == 100.0
        assert s["count"] == 100
        assert s["sum"] == 5050.0
        snap = m.collect()
        assert snap["siddhi.a.step_ms.p95"] == 96.0
        assert snap["siddhi.a.step_ms.sum"] == 5050.0
        text = m.prometheus_text()
        assert 'siddhi_a_step_ms{quantile="0.5"} 51.0' in text
        assert 'siddhi_a_step_ms{quantile="0.95"} 96.0' in text
        assert 'siddhi_a_step_ms{quantile="0.99"} 100.0' in text
        assert "siddhi_a_step_ms_sum 5050.0" in text
        assert "siddhi_a_step_ms_count 100" in text

    def test_histogram_count_sum_cumulative_across_reservoir_wrap(self):
        """_count/_sum are monotonic even after the bounded reservoir
        drops old samples — the rate() contract."""
        m = MetricsRegistry()
        h = m.histogram("siddhi.a.lat")
        old_cap = Histogram.CAP
        Histogram.CAP = 8           # force reservoir churn (slots class)
        try:
            for v in range(100):
                h.observe(1.0)
        finally:
            Histogram.CAP = old_cap
        s = h.summary()
        assert s["count"] == 100
        assert s["sum"] == 100.0

    def test_collect_safe_against_concurrent_registration(self):
        """Regression (ISSUE 7): a /metrics scrape walking the registry
        while another thread deploys an app (registering collectors and
        creating instruments) must neither crash nor return a torn
        snapshot. Hammer both concurrently."""
        m = MetricsRegistry()
        m.counter("siddhi.base.events").inc(1)
        stop = threading.Event()
        errors = []

        def deployer():
            # bounded: each registered collector runs on EVERY later
            # collect(), so an unbounded register loop would make the
            # scrape side quadratically slow
            for i in range(150):
                if stop.is_set():
                    return
                name = f"siddhi.app{i % 31}.q.depth"
                m.register_collector(lambda name=name: {name: 1})
                m.histogram(f"siddhi.app{i % 17}.lat").observe(1.0)

        threads = [threading.Thread(target=deployer) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for _ in range(100):
                try:
                    snap = m.collect()
                    text = m.prometheus_text()
                except Exception as e:  # noqa: BLE001 — the regression
                    errors.append(e)
                    break
                assert snap["siddhi.base.events"] == 1
                assert "siddhi_base_events 1" in text
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors, errors

    def test_prom_name_sanitization(self):
        assert prom_name("siddhi.my app.q-1.latency") == \
            "siddhi_my_app_q_1_latency"
        assert prom_name("0weird")[0] == "_"

    def test_broken_collector_does_not_kill_scrape(self):
        m = MetricsRegistry()
        m.register_collector(lambda: 1 / 0)
        m.gauge("siddhi.ok").set(1)
        assert m.collect()["siddhi.ok"] == 1


# ---------------------------------------------------------------------------
# statistics() <-> registry equivalence
# ---------------------------------------------------------------------------


class TestStatisticsRegistryEquivalence:
    def _assert_query_equiv(self, rt):
        """Every numeric per-query statistics() value must appear in the
        registry dump under siddhi.<app>.query.<q>.* with the same
        value."""
        flat, report = rt._collect_observability()
        prefix = f"siddhi.{rt.name}.query."
        for qname, entry in report.items():
            if not isinstance(entry, dict) or qname.startswith("store:") \
                    or qname in ("stream_errors", "compile"):
                continue
            base = f"{prefix}{qname}"
            for key, metric in (("emitted", "emitted"),
                                ("overflow", "overflow"),
                                ("throughput_eps", "throughput"),
                                ("state_bytes", "state.bytes")):
                if isinstance(entry.get(key), (int, float)):
                    assert flat[f"{base}.{metric}"] == entry[key], \
                        (qname, key)
            for k, v in (entry.get("latency") or {}).items():
                assert flat[f"{base}.latency.{k}"] == v

    def test_fused_chain(self):
        rt = _playback_app(CHAIN_APP, level="BASIC")
        assert rt.queries["q1"]._fused_chain is not None
        _send_ramp(rt, "S", 512)
        _send_ramp(rt, "S", 512, base=TS0 + 512)
        self._assert_query_equiv(rt)
        flat = rt.metrics.collect()
        assert flat[f"siddhi.{rt.name}.query.q1.emitted"] == 1024
        assert flat[f"siddhi.{rt.name}.query.q2.emitted"] == 1024
        # stream-level ingest throughput (the ISSUE's canonical name)
        assert flat[f"siddhi.{rt.name}.stream.S.events"] == 1024
        assert flat[f"siddhi.{rt.name}.stream.S.throughput"] > 0
        rt.shutdown()

    def test_partition(self):
        rt = _playback_app("""
            @app:playback
            define stream S (sym string, v long);
            partition with (sym of S) begin
              @info(name = 'pq')
              from S select sym, sum(v) as total insert into POut;
            end;
        """, level="BASIC")
        h = rt.get_input_handler("S")
        n = 256
        codes = np.array([GLOBAL_STRINGS.encode(f"K{i % 7}")
                          for i in range(n)], np.int32)
        h.send_arrays(TS0 + np.arange(n, dtype=np.int64),
                      [codes, np.ones(n, np.int64)])
        self._assert_query_equiv(rt)
        flat = rt.metrics.collect()
        assert flat[f"siddhi.{rt.name}.query.pq.emitted"] == n
        rt.shutdown()

    def test_detail_latency(self):
        rt = _playback_app(CHAIN_APP)
        rt.lat_sample_every = 1
        rt.set_statistics_level("DETAIL")
        _send_ramp(rt, "S", 64)
        _send_ramp(rt, "S", 64, base=TS0 + 64)
        stats = rt.statistics()
        lat = stats["q1"]["latency"]
        assert lat["samples"] == 2
        flat = rt.metrics.collect()
        base = f"siddhi.{rt.name}.query.q1.latency"
        assert flat[f"{base}.p99_ms"] == lat["p99_ms"]
        assert flat[f"{base}.samples"] == 2
        rt.shutdown()

    def test_scheduler_and_app_gauges_present(self):
        rt = _playback_app(CHAIN_APP, level="BASIC")
        flat = rt.metrics.collect()
        p = f"siddhi.{rt.name}"
        assert flat[f"{p}.scheduler.pending"] >= 0
        assert flat[f"{p}.scheduler.lag_ms"] >= 0
        assert flat[f"{p}.app.running"] == 1
        assert flat[f"{p}.app.ready"] == 1
        assert flat[f"{p}.errorstore.backlog"] == 0
        rt.shutdown()

    def test_async_queue_depth_gauges(self):
        rt = _playback_app("""
            @app:playback
            @Async(buffer.size='64', batch.size.max='16')
            define stream S (v int);
            @info(name = 'q') from S select v insert into Out;
        """, level="BASIC")
        _send_ramp(rt, "S", 128)
        rt.junctions["S"].flush_async()
        flat = rt.metrics.collect()
        p = f"siddhi.{rt.name}.stream.S.async"
        assert flat[f"{p}.capacity"] == 64
        assert flat[f"{p}.depth"] == 0      # drained
        assert flat[f"{p}.pending"] == 0
        rt.shutdown()

    def test_watermark_and_reorder_gauges(self):
        """Event-time robustness metrics (resilience/ordering.py):
        watermark position/lag, reorder-buffer depth and the late/
        dropped counters surface in BOTH the registry dump and
        statistics()['reorder'] (docs/observability.md)."""
        rt = _playback_app("""
            @app:watermark(lateness='16', policy='DROP')
            define stream S (v int);
            @info(name = 'q') from S select v insert into Out;
        """, level="BASIC")
        h = rt.get_input_handler("S")
        h.send_arrays(TS0 + np.arange(64, dtype=np.int64) * 4,
                      [np.arange(64, dtype=np.int32)])
        h.send_arrays(np.array([TS0 + 1], np.int64),
                      [np.array([-1], np.int32)])   # late -> dropped
        flat, report = rt._collect_observability()
        p = f"siddhi.{rt.name}.stream.S"
        wm = TS0 + 63 * 4 - 16
        assert flat[f"{p}.watermark"] == wm
        assert flat[f"{p}.watermark.lag_ms"] == 16
        assert flat[f"{p}.reorder.depth"] > 0      # tail within lateness
        assert flat[f"{p}.reorder.late"] == 1
        assert flat[f"{p}.reorder.late_dropped"] == 1
        assert flat[f"{p}.reorder.released"] + \
            flat[f"{p}.reorder.depth"] == 64
        rep = report["reorder"]["S"]
        assert rep["watermark"] == wm
        assert rep["depth"] == flat[f"{p}.reorder.depth"]
        assert rep["late_dropped"] == 1
        # same numbers through the registry collector walk (/metrics)
        assert rt.metrics.collect()[f"{p}.watermark"] == wm
        text = rt.metrics.prometheus_text()
        assert prom_name(f"{p}.watermark.lag_ms") in text
        assert prom_name(f"{p}.reorder.depth") in text
        rt.shutdown()
        assert rt.metrics.collect()[f"{p}.reorder.depth"] == 0

    def test_checkpoint_age_gauge(self):
        from siddhi_tpu.resilience.supervisor import CheckpointSupervisor
        rt = _playback_app(CHAIN_APP, level="BASIC")
        sup = CheckpointSupervisor(rt, interval_ms=1000).start(
            base_ms=TS0)
        _send_ramp(rt, "S", 16)
        # advance the virtual clock past several checkpoint intervals
        _send_ramp(rt, "S", 16, base=TS0 + 5_000)
        assert sup.checkpoints >= 1
        flat = rt.metrics.collect()
        p = f"siddhi.{rt.name}.checkpoint"
        assert flat[f"{p}.count"] == sup.checkpoints
        assert flat[f"{p}.age_ms"] >= 0
        sup.stop()
        rt.shutdown()


# ---------------------------------------------------------------------------
# sampled-sync budget on the filter shape: BASIC stats, cost profiler, SLO
# ---------------------------------------------------------------------------

FILTER_APP = """
    @app:playback
    {annotation}
    define stream S (sym string, price float, volume long);
    @info(name = 'q')
    from S[price > 100.0] select sym, price insert into Out;
"""
FILTER_CHUNK, FILTER_CHUNKS = 1024, 2 * 64 + 1


# Each feature: (switch(on), stride, records()) — records() counts what
# the feature recorded while on, one record per sampled chunk.
def _basic_stats(rt):
    """Host-boundary throughput counters mark every chunk."""
    qs = rt.queries["q"]._qs()
    return (lambda on: rt.set_statistics_level("BASIC" if on else "OFF"),
            1, lambda: qs.throughput.count // FILTER_CHUNK)


def _cost_profiler(rt):
    """cost_start() resets the stride, so the first chunk samples."""
    return (lambda on: rt.cost_start() if on else rt.cost_stop(),
            rt.cost.every, lambda: rt.cost.samples)


def _slo(rt):
    """The ingest->emit span of a sampled chunk ends at the host decode
    that delivers the rows anyway."""
    engine = rt.slo
    return (lambda on: setattr(rt, "slo", engine if on else None),
            engine.every,
            lambda: engine.evaluate()["scopes"].get("query=q",
                                                    {"count": 0})["count"])


@pytest.mark.parametrize("feature,annotation,stride,syncs_per_record", [
    (_basic_stats, "", 1, 0),
    (_cost_profiler, "", 64, 1),
    (_slo, "@app:slo(p99='60 sec', target='0.9')", 64, 0),
], ids=["basic_stats", "cost_profiler", "slo"])
def test_sampled_sync_budget_on_filter_shape(feature, annotation, stride,
                                             syncs_per_record,
                                             monkeypatch):
    """A feature on at its default stride adds at most one synchronous
    device wait per stride of chunks and no other per-chunk device work.
    Counted, not timed: every ``jax.block_until_ready`` and
    ``jax.device_get`` of a feature-off run against a feature-on run
    over the same number of chunks."""
    import jax
    rt = SiddhiManager().create_siddhi_app_runtime(
        FILTER_APP.format(annotation=annotation))
    seen = [0]
    rt.add_callback("Out", StreamCallback(
        fn=lambda evs: seen.__setitem__(0, seen[0] + len(evs))))
    rt.start()
    switch, every, records = feature(rt)
    h = rt.get_input_handler("S")
    rng = np.random.default_rng(7)
    syms = np.array([GLOBAL_STRINGS.encode(s)
                     for s in ("A", "B", "C", "D")], np.int32)
    clock = [TS0]

    def run():
        n = FILTER_CHUNK
        for _ in range(FILTER_CHUNKS):
            ts = clock[0] + np.arange(n, dtype=np.int64)
            clock[0] += n
            h.send_arrays(ts, [syms[rng.integers(0, 4, n)],
                               rng.uniform(0, 200, n).astype(np.float32),
                               rng.integers(1, 1000, n, dtype=np.int64)])

    syncs = [0]

    def counting(real):
        def wrapped(*a, **kw):
            syncs[0] += 1
            return real(*a, **kw)
        return wrapped

    switch(False)
    run()   # compile every step/encoding before counting
    monkeypatch.setattr(jax, "block_until_ready",
                        counting(jax.block_until_ready))
    monkeypatch.setattr(jax, "device_get", counting(jax.device_get))
    run()
    syncs_off, syncs[0] = syncs[0], 0
    assert records() == 0
    switch(True)
    run()
    syncs_on = syncs[0]
    monkeypatch.undo()
    rt.shutdown()
    assert seen[0] > 0
    assert every == stride              # the documented default stride
    sampled = -(-FILTER_CHUNKS // stride)
    assert records() == sampled, (records(), sampled)
    assert syncs_on == syncs_off + syncs_per_record * sampled, \
        (syncs_off, syncs_on, sampled)


# ---------------------------------------------------------------------------
# @app:statistics annotation surface
# ---------------------------------------------------------------------------


class TestStatisticsAnnotation:
    def test_reporter_and_interval_parsed(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
            @app:statistics(level='DETAIL', reporter='file',
                            interval='100 ms')
            define stream S (v int);
            from S select v insert into Out;
        """)
        assert rt.stats_level == 2
        assert rt._stats_reporter_conf == ("file", 100, None)

    def test_interval_alone_defaults_console(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
            @app:statistics(interval='2 sec')
            define stream S (v int);
            from S select v insert into Out;
        """)
        assert rt.stats_level == 1          # annotation present -> BASIC
        assert rt._stats_reporter_conf == ("console", 2000, None)

    def test_level_only_compat(self):
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime("""
            @app:statistics('DETAIL')
            define stream S (v int);
            from S select v insert into Out;
        """)
        assert rt.stats_level == 2
        assert rt._stats_reporter_conf is None

    def test_unknown_reporter_rejected_at_parse(self):
        with pytest.raises(CompileError, match="statistics-reporter"):
            SiddhiManager().create_siddhi_app_runtime("""
                @app:statistics(reporter='graphite')
                define stream S (v int);
                from S select v insert into Out;
            """)

    def test_bad_interval_rejected_at_parse(self):
        with pytest.raises(CompileError, match="statistics-interval"):
            SiddhiManager().create_siddhi_app_runtime("""
                @app:statistics(reporter='console', interval='soon')
                define stream S (v int);
                from S select v insert into Out;
            """)


# ---------------------------------------------------------------------------
# reporters
# ---------------------------------------------------------------------------


class TestReporters:
    def test_jsonl_reporter_writes_lines(self, tmp_path):
        path = tmp_path / "metrics.jsonl"
        mgr = SiddhiManager()
        rt = mgr.create_siddhi_app_runtime(f"""
            @app:playback
            @app:statistics(reporter='jsonl', interval='50 ms',
                            file='{path}')
            define stream S (v int);
            @info(name = 'q') from S select v insert into Out;
        """)
        rt.start()
        assert rt._reporter is not None
        _send_ramp(rt, "S", 32)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if path.exists() and path.read_text().strip():
                break
            time.sleep(0.05)
        rt.shutdown()
        assert rt._reporter is None        # shutdown stops the reporter
        lines = [json.loads(x) for x in
                 path.read_text().strip().splitlines()]
        assert lines, "reporter never ticked"
        snap = lines[-1]
        assert snap["app"] == rt.name
        assert any(k.startswith("siddhi.") for k in snap)

    def test_console_reporter_emits_json(self):
        import logging
        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        logger = logging.getLogger("siddhi_tpu.metrics")
        h = Capture()
        logger.addHandler(h)
        logger.setLevel(logging.INFO)
        try:
            rt = _playback_app(CHAIN_APP, level="BASIC")
            from siddhi_tpu.obs.reporters import ConsoleReporter
            rep = ConsoleReporter(rt, interval_ms=30).start()
            _send_ramp(rt, "S", 16)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not records:
                time.sleep(0.03)
            rep.stop()
            rt.shutdown()
        finally:
            logger.removeHandler(h)
        assert records, "console reporter never ticked"
        snap = json.loads(records[-1])
        assert snap["app"] == rt.name


# ---------------------------------------------------------------------------
# service endpoints
# ---------------------------------------------------------------------------

SERVICE_APP = """
@app:name('obsapp')
@app:playback
@app:statistics('BASIC')
define stream S (v int);
@info(name = 'q') from S[v > 0] select v insert into Out;
"""


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class TestServiceEndpoints:
    def test_metrics_health_ready(self):
        from siddhi_tpu.core.service import SiddhiService
        svc = SiddhiService()
        svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        code, body = _get(f"{base}/health")
        assert code == 200 and json.loads(body)["status"] == "up"
        code, body = _get(f"{base}/ready")
        assert code == 200          # nothing deployed: trivially ready
        svc.deploy(SERVICE_APP)
        code, text = _get(f"{base}/metrics")
        assert code == 200
        assert "# TYPE siddhi_obsapp_app_ready gauge" in text
        assert "siddhi_obsapp_app_running 1" in text
        code, body = _get(f"{base}/ready")
        assert code == 200 and json.loads(body)["apps"] == {
            "obsapp": True}
        svc.stop()

    def test_ready_flips_with_warmup_in_flight(self):
        """GET /ready must be 503 exactly while a CompileService warmup
        is in flight (the deterministic core of 'ready flips only after
        warmup completes')."""
        from siddhi_tpu.core.service import SiddhiService
        svc = SiddhiService()
        svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        name = svc.deploy(SERVICE_APP)
        rt = svc._deployed[name]
        assert _get(f"{base}/ready")[0] == 200
        rt.compile_service._begin()     # a warmup is now in flight
        code, body = _get(f"{base}/ready")
        assert code == 503
        assert json.loads(body) == {"ready": False,
                                    "apps": {"obsapp": False}}
        rt.compile_service._end()       # ... and it completed
        assert _get(f"{base}/ready")[0] == 200
        svc.stop()

    def test_async_warm_deploy_becomes_ready(self, monkeypatch):
        """End to end: with SIDDHI_TPU_WARM_BUCKETS configured, deploy
        returns immediately, the warmup runs in the background, and
        /ready flips to 200 with warmup telemetry recorded."""
        monkeypatch.setenv("SIDDHI_TPU_WARM_BUCKETS", "16")
        from siddhi_tpu.core.service import SiddhiService
        svc = SiddhiService()
        svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        name = svc.deploy(SERVICE_APP)
        deadline = time.monotonic() + 120.0
        code = 503
        while time.monotonic() < deadline:
            code, _ = _get(f"{base}/ready")
            if code == 200:
                break
            time.sleep(0.05)
        assert code == 200, "async warmup never completed"
        rt = svc._deployed[name]
        assert rt.compile_service.warmups >= 1
        assert rt.statistics()["compile"]["programs"] > 0
        svc.stop()

    def test_metrics_dump_wait_ready_with_background_warmup(
            self, monkeypatch, capsys):
        """tools/metrics_dump.py --wait-ready polls /ready before
        scraping, so the CI smoke probe can't race a background
        SIDDHI_TPU_WARM_BUCKETS warmup (deploy returns while the AOT
        compiles are still in flight)."""
        import os
        import sys
        monkeypatch.setenv("SIDDHI_TPU_WARM_BUCKETS", "16")
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)
        import metrics_dump
        rc = metrics_dump.main(["--wait-ready", "--events", "64"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "siddhi_metrics_probe_app_ready 1" in out

    def test_health_unauthenticated_metrics_authenticated(self):
        from siddhi_tpu.core.service import SiddhiService
        svc = SiddhiService(auth_token="s3cret")
        svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        assert _get(f"{base}/health")[0] == 200    # LB probe: no token
        assert _get(f"{base}/ready")[0] == 200
        assert _get(f"{base}/metrics")[0] == 401   # internals: token
        req = urllib.request.Request(
            f"{base}/metrics",
            headers={"Authorization": "Bearer s3cret"})
        assert urllib.request.urlopen(req).status == 200
        svc.stop()


# ---------------------------------------------------------------------------
# tracing + profiler
# ---------------------------------------------------------------------------


class TestTracing:
    def test_trace_export_chrome_json(self, tmp_path):
        rt = _playback_app(CHAIN_APP)
        rt.trace_start()
        _send_ramp(rt, "S", 128)
        path = rt.trace_export(str(tmp_path / "trace.json"))
        rt.shutdown()
        trace = json.load(open(path))
        events = trace["traceEvents"]
        assert events, "no spans recorded"
        names = {e["name"] for e in events}
        assert "ingest/S" in names
        # fused segment: ONE span, member queries annotated
        assert "chain/q1+q2" in names
        chain = next(e for e in events if e["name"] == "chain/q1+q2")
        assert chain["args"]["members"] == ["q1", "q2"]
        for e in events:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
            assert e["dur"] >= 0

    def test_trace_export_sorted_by_ts(self, tmp_path):
        """The ring buffer holds spans in COMPLETION order — an
        enclosing span (ingest) completes after its children (step), so
        buffer order is start-time-reversed for nests and
        Chrome/Perfetto renders them wrong. Export must sort by ts."""
        from siddhi_tpu.obs.tracing import ChunkTracer
        tracer = ChunkTracer()
        tracer.start()
        # completion order: child first, parent (earlier ts) second —
        # exactly what nested `with` spans produce
        tracer.record("step/q", "step", ts_us=2000, dur_us=10, args={})
        tracer.record("ingest/S", "ingest", ts_us=1000, dur_us=1500,
                      args={})
        tracer.record("sink/out", "sink", ts_us=3000, dur_us=5, args={})
        path = tracer.export(str(tmp_path / "t.json"))
        events = json.load(open(path))["traceEvents"]
        ts = [e["ts"] for e in events]
        assert ts == sorted(ts)
        assert [e["name"] for e in events] == \
            ["ingest/S", "step/q", "sink/out"]

    def test_runtime_trace_export_is_ts_ordered(self, tmp_path):
        rt = _playback_app(CHAIN_APP)
        rt.trace_start()
        _send_ramp(rt, "S", 256)
        _send_ramp(rt, "S", 256, base=TS0 + 256)
        path = rt.trace_export(str(tmp_path / "t.json"))
        rt.shutdown()
        ts = [e["ts"] for e in json.load(open(path))["traceEvents"]]
        assert ts and ts == sorted(ts)

    def test_tracer_disabled_by_default(self):
        rt = _playback_app(CHAIN_APP)
        _send_ramp(rt, "S", 32)
        assert rt.tracer.events() == []
        rt.shutdown()

    def test_step_and_junction_spans_unfused(self, tmp_path):
        import os
        os.environ["SIDDHI_TPU_FUSE"] = "0"
        try:
            rt = _playback_app(CHAIN_APP)
        finally:
            os.environ.pop("SIDDHI_TPU_FUSE", None)
        rt.trace_start()
        _send_ramp(rt, "S", 64)
        path = rt.trace_export(str(tmp_path / "t.json"))
        rt.shutdown()
        names = {e["name"] for e in
                 json.load(open(path))["traceEvents"]}
        assert "step/q1" in names and "step/q2" in names
        assert "junction/M" in names    # per-hop publish

    def test_profile_context_manager(self, tmp_path):
        rt = _playback_app(CHAIN_APP)
        prof_dir = tmp_path / "prof"
        try:
            with rt.profile(str(prof_dir)):
                _send_ramp(rt, "S", 64)
        except Exception as e:  # noqa: BLE001 — backend-dependent
            rt.shutdown()
            pytest.skip(f"jax profiler unavailable: {e}")
        rt.shutdown()
        assert prof_dir.exists() and any(prof_dir.rglob("*"))

    def test_named_scopes_name_query_and_operator_without_moving_the_cache_key(
            self, monkeypatch):
        """Every operator of a step runs under ``<q>/<OpClass>``: the
        compiled HLO's op_name metadata carries it, and the persistent
        compile-cache key equals that of the same step lowered without
        scopes (JAX strips debug info before hashing)."""
        import contextlib
        import re

        import jax
        from jax._src import cache_key, compiler

        from siddhi_tpu.core import runtime as runtime_mod

        rt = _playback_app("""
            define stream S (v int);
            @info(name = 'qs') from S[v > 3] select v * 2 as w
            insert into Out;
        """)
        q = rt.queries["qs"]
        batch = runtime_mod._timer_batch(q.in_schema, TS0)
        args = (q.states, {}, q._emitted_dev, batch, np.int64(TS0))
        scoped = q._make_step().lower(*args)
        monkeypatch.setattr(runtime_mod, "op_scope",
                            lambda name: contextlib.nullcontext())
        plain = q._make_step().lower(*args)
        rt.shutdown()

        assert "qs/FilterOp" not in plain.as_text(debug_info=True)
        # compiled afresh: a persistent-cache hit would return whichever
        # lowering wrote the entry, since both share its key
        from jax._src import config as jax_config
        with jax_config.enable_compilation_cache(False):
            hlo = scoped.compile().as_text()
        assert re.search(r'op_name="[^"]*qs/FilterOp', hlo)
        assert re.search(r'op_name="[^"]*qs/ProjectOp', hlo)

        dev = jax.devices()[0]
        devices = np.array([dev])
        opts = compiler.get_compile_options(num_replicas=1,
                                            num_partitions=1)

        def key(lowered):
            return cache_key.get(lowered.compiler_ir("stablehlo"), devices,
                                 opts, dev.client)

        assert key(scoped) == key(plain)

    def test_ring_capacity_counts_evicted_spans(self, tmp_path):
        rt = _playback_app(CHAIN_APP)
        rt.trace_start(capacity=4)
        for i in range(6):
            _send_ramp(rt, "S", 16, base=TS0 + 16 * i)
        events = rt.tracer.events()
        dropped = rt.trace_stop()
        path = rt.trace_export(str(tmp_path / "t.json"))
        rt.shutdown()
        assert len(events) == 4
        # each send records ingest/S and chain/q1+q2 (+ the output's
        # spans where rows were decoded): 6 sends leave more than 4
        assert dropped >= 2 * 6 - 4
        meta = json.load(open(path))["otherData"]
        assert meta == {"capacity": 4, "dropped": dropped}
        # a fresh start clears the count and keeps the capacity
        rt2 = _playback_app(CHAIN_APP)
        rt2.trace_start()
        assert rt2.tracer.capacity == 8192 and rt2.tracer.dropped == 0
        rt2.shutdown()

    def test_ingest_pipeline_span_removed(self, monkeypatch):
        """A pipelined send (more rows than the split cap) records one
        ingest/<s> span per chunk and no span of its own."""
        monkeypatch.setenv("SIDDHI_TPU_INGEST_PIPELINE_CHUNK", "128")
        rt = _playback_app(CHAIN_APP)
        rt.trace_start()
        _send_ramp(rt, "S", 512)
        names = [e[0] for e in rt.tracer.events()]
        rt.shutdown()
        assert names.count("ingest/S") == 4
        assert not any(n.startswith("ingest_pipeline") for n in names)

    def test_chunk_id_crosses_async_junction(self):
        rt = SiddhiManager().create_siddhi_app_runtime("""
            @app:playback
            @Async(buffer.size='64', batch.size.max='8')
            define stream S (v int);
            @info(name = 'q') from S[v > 0] select v insert into O;
        """)
        got = []
        rt.add_callback("O", StreamCallback(lambda evs: got.extend(evs)))
        rt.start()
        rt.trace_start()
        h = rt.get_input_handler("S")
        for k in range(3):
            h.send([Event(TS0 + 8 * k + i, (i + 1,)) for i in range(8)])
            rt.junctions["S"].flush_async()
        events = rt.tracer.events()
        rt.shutdown()
        assert len(got) == 24
        ingest = [e for e in events if e[0] == "ingest/S"]
        steps = [e for e in events if e[0] == "step/q"]
        assert [e[5]["chunk"] for e in ingest] == [0, 1, 2]
        # the step runs on the junction's worker thread, under the
        # chunk number of the send that queued its batch
        assert {e[4] for e in steps}.isdisjoint({e[4] for e in ingest})
        assert [e[5]["chunk"] for e in steps] == [0, 1, 2]
        for e in events:
            if e[0] in ("wait/q", "d2h/q", "decode/q"):
                assert e[5]["chunk"] in (0, 1, 2)

    def test_profiler_host_plane_holds_every_span_on_one_offset(
            self, tmp_path):
        """Inside a profiler session every ChunkTracer span is also a
        host-plane event of the same name; the two clocks differ by one
        offset."""
        import collections
        import glob

        from jax.profiler import ProfileData
        rt = SiddhiManager().create_siddhi_app_runtime(CHAIN_APP)
        rt.add_callback("Out", StreamCallback(lambda evs: None))
        rt.start()
        prof = tmp_path / "prof"
        try:
            with rt.profile(str(prof)):
                rt.trace_start()
                for i in range(4):
                    _send_ramp(rt, "S", 64, base=TS0 + 64 * i)
                rt.trace_stop()
        except Exception as e:  # noqa: BLE001 — backend-dependent
            rt.shutdown()
            pytest.skip(f"jax profiler unavailable: {e}")
        spans = rt.tracer.events()
        rt.shutdown()
        files = glob.glob(str(prof / "**" / "*.xplane.pb"), recursive=True)
        assert len(files) == 1
        host = collections.defaultdict(list)
        for plane in ProfileData.from_file(files[0]).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name].append(int(ev.start_ns))
        mine = collections.defaultdict(list)
        for name, _, ts_us, _, _, _ in spans:
            mine[name].append(ts_us)
        assert {"ingest/S", "chain/q1+q2", "wait/q2", "d2h/q2",
                "decode/q2"} <= set(mine)
        offsets = []
        for name, ts in mine.items():
            xs = sorted(host[name])
            assert len(xs) == len(ts), name
            offsets += [x - t * 1000 for x, t in zip(xs, sorted(ts))]
        assert max(offsets) - min(offsets) < 100_000


JOIN_APP = """
    @app:playback
    define stream S (k string, v long);
    define stream T (k string, w long);
    define table Tab (k string, w long);
    @info(name = 'load') from T select k, w insert into Tab;
    @info(name = 'j')
    from S join Tab on S.k == Tab.k
    select S.k as k, S.v as v, Tab.w as w insert into Out;
"""

# (app, enclosing span, name of the output-fetch spans)
DISPATCH_PATHS = {
    "single": ("""
        @app:playback
        define stream S (k string, v long);
        @info(name = 'q') from S[v > 2] select k, v insert into Out;
    """, "step/q", "q"),
    "fused_chain": ("""
        @app:playback
        define stream S (k string, v long);
        @info(name = 'q1') from S[v > 2] select k, v insert into M;
        @info(name = 'q2') from M[v < 60] select k, v insert into Out;
    """, "chain/q1+q2", "q2"),
    "join_side": (JOIN_APP, "step/j", "j"),
    "pattern": ("""
        @app:playback
        define stream S (k string, v long);
        @info(name = 'p')
        from every e1=S[v > 0] -> e2=S[k == e1.k and v > e1.v]
        select e1.k as k, e2.v as v insert into Out;
    """, "step/p", "p"),
    "partition": ("""
        @app:playback
        define stream S (k string, v long);
        partition with (k of S) begin
          @info(name = 'pq') from S select k, sum(v) as v insert into Out;
        end;
    """, "partition/", None),
}


def _run_path(path: str, trace: bool):
    ql = DISPATCH_PATHS[path][0]
    rt = SiddhiManager().create_siddhi_app_runtime(ql)
    got = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: got.extend((e.timestamp, e.data) for e in evs)))
    rt.start()
    codes = np.array([GLOBAL_STRINGS.encode(f"K{i % 4}") for i in
                      range(64)], np.int32)
    if path == "join_side":
        rt.get_input_handler("T").send_arrays(
            np.full(4, TS0 - 1, np.int64),
            [codes[:4], np.arange(4, dtype=np.int64)])
    if trace:
        rt.trace_start()
    h = rt.get_input_handler("S")
    for i in range(2):
        h.send_arrays(TS0 + 64 * i + np.arange(64, dtype=np.int64),
                      [codes, np.arange(64, dtype=np.int64)])
    events = rt.tracer.events()
    parts = list(rt.partitions)
    rt.shutdown()
    return got, events, parts


@pytest.mark.parametrize("path", sorted(DISPATCH_PATHS))
def test_output_fetch_spans_nest_in_the_dispatch_span(path):
    """Where a step's output becomes host rows, wait/<q>, d2h/<q> and
    decode/<q> nest, in that order, inside the dispatch's own span, and
    every span carries its chunk's number; d2h carries the rows copied
    (capacity) and bytes, decode the valid rows out."""
    got, events, parts = _run_path(path, trace=True)
    _, outer, q = DISPATCH_PATHS[path]
    if q is None:       # a partition's spans carry the partition's name
        q = parts[0]
        outer = f"partition/{q}"
    assert got
    outers = [e for e in events if e[0] == outer]
    assert outers
    fetch = {k: [e for e in events if e[0] == f"{k}/{q}"]
             for k in ("wait", "d2h", "decode")}
    assert all(fetch.values()), fetch
    for e in events:
        assert e[5]["chunk"] in (0, 1), e
    for kind, spans in fetch.items():
        for name, _, ts, dur, tid, args in spans:
            host = [o for o in outers if o[4] == tid and o[2] <= ts and
                    ts + dur <= o[2] + o[3] and
                    o[5]["chunk"] == args["chunk"]]
            assert host, (name, outers)
    for (_, _, wts, wdur, _, _), (_, _, dts, ddur, _, dargs), \
            (_, _, cts, _, _, cargs) in zip(*fetch.values()):
        assert wts + wdur <= dts and dts + ddur <= cts
        assert dargs["capacity"] >= cargs["rows"] > 0
        assert dargs["bytes"] > 0
    assert sum(a["rows"] for *_, a in fetch["decode"]) == len(got)


@pytest.mark.parametrize("path", sorted(DISPATCH_PATHS))
def test_tracing_off_records_nothing_and_changes_no_row(path):
    got_on, events_on, _ = _run_path(path, trace=True)
    got_off, events_off, _ = _run_path(path, trace=False)
    assert events_on and events_off == []
    assert got_off == got_on


# ---------------------------------------------------------------------------
# stats race regression
# ---------------------------------------------------------------------------


def test_latency_summary_concurrent_with_mark_out():
    """Regression: summary() used to sort self.samples without the lock
    while mark_out deletes+appends under it — a torn read raised or
    returned garbage. Hammer both concurrently."""
    from siddhi_tpu.core.stats import LatencyTracker
    lt = LatencyTracker()
    lt.CAP = 64            # force constant del/append churn
    stop = threading.Event()
    errors = []

    def writer():
        while not stop.is_set():
            lt.mark_in()
            lt.mark_out()

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            try:
                s = lt.summary()
            except Exception as e:  # noqa: BLE001 — the regression
                errors.append(e)
                break
            if s is not None:
                assert s["samples"] > 0
                assert s["p99_ms"] >= s["p50_ms"] >= 0
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors

"""SLO engine (obs/slo.py): burn-rate math, per-tenant/per-query
ingest->emit attribution, labeled metric families, saturation-tagged
429s, the flight recorder, and the /siddhi/slo front door.

Key invariants (ISSUE 11 acceptance):
- per-tenant p99 visible in statistics()['slo'], /metrics (labeled
  samples) and GET /siddhi/slo for a 64-tenant pool;
- a deliberately throttled tenant's breach trips the burn-rate PAGE
  state and dumps a flight-recorder artifact;
- stats collection stays ONE device_get per pool with SLO tracking on;
- SLO tracking ON at the default stride adds no device wait on the
  filter shape (test_obs.py counts it).
"""
import json
import os
import threading
import time
import urllib.request
import urllib.error

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.service import SiddhiService
from siddhi_tpu.core.stream import StreamCallback
from siddhi_tpu.obs.metrics import MetricsRegistry
from siddhi_tpu.obs.slo import (FlightRecorder, SLOEngine, SLOObjective,
                                config_from_annotation, scope_name)
from siddhi_tpu.ops.expr import CompileError
from siddhi_tpu.serving import AdmissionError, TemplateRegistry

TPL = """
define stream In (v double, k long);
@info(name='q')
from In[v > ${lo:double} and v < ${hi:double}]
select v, k insert into Out;
"""

TS0 = 1_000_000


def _mk_pool(slots=8, max_tenants=8, batch_max=None, slo=None,
             template=TPL):
    reg = TemplateRegistry(SiddhiManager())
    kwargs = {}
    if batch_max is not None:
        kwargs["batch_max"] = batch_max
    return reg.pool(template, warm=False, slots=slots,
                    max_tenants=max_tenants, slo=slo, **kwargs)


def _chunk(n, start=TS0):
    ts = start + np.arange(n, dtype=np.int64)
    return ts, [np.random.default_rng(3).uniform(1, 99, n),
                np.arange(n, dtype=np.int64)]


# ---------------------------------------------------------------------------
# engine unit: windows, burn rates, states, transitions
# ---------------------------------------------------------------------------


class TestEngine:
    def test_burn_rates_and_states(self):
        obj = SLOObjective(p99_ms=100.0, target=0.99, every=1)
        eng = SLOEngine("e", objective=obj)
        t0 = 1_000.0
        for i in range(100):
            # 2% of samples bad -> burn = 2 (WARN at warn_burn=2,
            # below page_burn=14.4)
            lat = 500.0 if i % 50 == 0 else 10.0
            eng.observe((), lat, t_wall_ms=t0 + i)
        rep = eng.evaluate(now_ms=t0 + 200)
        e = rep["scopes"]["total"]
        assert e["state"] == "WARN"
        assert e["burn_fast"] == pytest.approx(2.0)
        assert e["attainment"] == pytest.approx(0.98)

    def test_page_requires_both_windows(self):
        # all-bad traffic that stopped an hour ago: the fast window is
        # clean, so min(burn_fast, burn_slow) must NOT page
        obj = SLOObjective(p99_ms=100.0, target=0.99,
                           window_ms=7_200_000, every=1)
        eng = SLOEngine("e", objective=obj)
        t0 = 10_000_000.0
        for i in range(50):
            eng.observe((), 500.0, t_wall_ms=t0 + i)
        # fresh good samples inside the fast window
        now = t0 + 3_600_000
        for i in range(10):
            eng.observe((), 10.0, t_wall_ms=now - 1_000 + i)
        rep = eng.evaluate(now_ms=now)
        e = rep["scopes"]["total"]
        assert e["burn_slow"] > 14.4 and e["burn_fast"] == 0.0
        assert e["state"] == "OK"

    def test_transition_into_page_dumps_once(self, tmp_path):
        obj = SLOObjective(p99_ms=50.0, target=0.99, every=1)
        eng = SLOEngine("e", objective=obj,
                        recorder=FlightRecorder("e",
                                                dirpath=str(tmp_path)))
        t0 = 1_000.0
        for i in range(20):
            eng.observe((("tenant", "hot"),), 400.0, t_wall_ms=t0 + i)
        rep = eng.evaluate(now_ms=t0 + 100)
        assert rep["scopes"]["tenant=hot"]["state"] == "PAGE"
        assert rep["breaches"] == 1
        path = rep["flight_artifact"]
        assert os.path.exists(path)
        art = json.load(open(path))
        assert art["reason"] == "slo-breach"
        assert any(s["kind"] == "slo-state" for s in art["spans"])
        assert "tenant=hot" in art["context"]["paged_scopes"]
        # identity keys present even for a bare engine (no runtime)
        assert set(FlightRecorder.IDENTITY_KEYS) <= set(art["context"])
        # steady PAGE state: no new artifact per scrape
        rep2 = eng.evaluate(now_ms=t0 + 101)
        assert "flight_artifact" not in rep2
        assert rep2["breaches"] == 1
        assert eng.state == "PAGE"

    def test_stride_sampling_first_always(self):
        eng = SLOEngine("e", every=16)
        hits = [eng.tick("site") for _ in range(33)]
        assert hits[0] is True
        assert sum(hits) == 3  # 0, 16, 32

    def test_no_objective_reports_percentiles_only(self):
        eng = SLOEngine("e", every=1)
        eng.observe((("query", "q"),), 5.0, t_wall_ms=1_000.0)
        rep = eng.evaluate(now_ms=2_000.0)
        e = rep["scopes"]["query=q"]
        assert e["p99_ms"] == 5.0 and "state" not in e
        assert rep["state"] is None

    def test_scope_name(self):
        assert scope_name(()) == "total"
        assert scope_name((("tenant", "a"), ("query", "q"))) == \
            "tenant=a,query=q"


class TestConfig:
    def test_annotation_roundtrip(self):
        from siddhi_tpu.lang import ast as A
        ann = A.Annotation(name="slo", elements={
            "p99": "250 ms", "p50": "50 ms", "target": "0.999",
            "window": "30 min", "fast": "1 min", "warn.burn": "3",
            "page.burn": "10", "every": "8"})
        obj = config_from_annotation(ann)
        assert obj.p99_ms == 250.0 and obj.p50_ms == 50.0
        assert obj.target == 0.999
        assert obj.window_ms == 30 * 60 * 1000
        assert obj.fast_ms == 60 * 1000
        assert obj.warn_burn == 3.0 and obj.page_burn == 10.0
        assert obj.every == 8

    @pytest.mark.parametrize("elements,frag", [
        ({}, "latency bound"),
        ({"p99": "banana"}, "cannot parse time"),
        ({"p99": "100 ms", "target": "1.5"}, "in (0, 1)"),
        ({"p99": "100 ms", "target": "0"}, "target"),
        ({"p99": "100 ms", "fast": "2 hours"}, "must not exceed"),
        ({"p99": "100 ms", "warn.burn": "20"}, "warn.burn"),
        ({"p99": "100 ms", "every": "0"}, "every"),
        ({"p99": "-5 ms"}, "p99"),
    ])
    def test_bad_annotation_values_raise(self, elements, frag):
        from siddhi_tpu.lang import ast as A
        with pytest.raises(ValueError) as ei:
            config_from_annotation(A.Annotation(name="slo",
                                                elements=elements))
        assert frag in str(ei.value)

    def test_parse_time_rejects_slo_config_at_parse(self):
        with pytest.raises(CompileError) as ei:
            SiddhiManager().create_siddhi_app_runtime(
                "@app:slo(p99='nope')\n"
                "define stream S (v int);\n"
                "from S select v insert into Out;")
        assert "slo-config" in str(ei.value)


class TestFlightRecorder:
    def test_ring_bounded_and_dump_schema(self, tmp_path):
        rec = FlightRecorder("ring", cap=16, dirpath=str(tmp_path))
        for i in range(100):
            rec.record("span", i=i)
        assert len(rec.snapshot()) == 16
        assert rec.snapshot()[0]["i"] == 84   # oldest retained
        path = rec.dump("test-reason", context={"k": "v"})
        art = json.load(open(path))
        assert art["name"] == "ring" and art["reason"] == "test-reason"
        assert len(art["spans"]) == 16
        # identity keys are UNIFORM on every artifact (None when no
        # identity_fn is wired) — obs/explain.py plan attribution
        assert art["context"] == {"k": "v", "app": None, "pool": None,
                                  "plan_hash": None}
        assert art["dumped_at_ms"] > 0
        assert rec.dumps == [path]

    def test_dump_identity_fn_stamps_app_pool_plan(self, tmp_path):
        rec = FlightRecorder(
            "ident", dirpath=str(tmp_path),
            identity_fn=lambda: {"app": "a1", "pool": "p1",
                                 "plan_hash": "cafe" * 4})
        art = json.load(open(rec.dump("r")))
        ctx = art["context"]
        assert ctx["app"] == "a1" and ctx["pool"] == "p1"
        assert ctx["plan_hash"] == "cafe" * 4

    def test_dump_identity_fn_failure_still_dumps(self, tmp_path):
        def boom():
            raise RuntimeError("identity exploded")
        rec = FlightRecorder("ident2", dirpath=str(tmp_path),
                             identity_fn=boom)
        art = json.load(open(rec.dump("r")))
        assert art["context"]["app"] is None
        assert art["context"]["plan_hash"] is None


# ---------------------------------------------------------------------------
# pool: attribution, visibility, throttled-tenant breach, device reads
# ---------------------------------------------------------------------------


class TestPool:
    def test_64_tenant_pool_p99_visible_everywhere(self):
        """The acceptance surface: per-tenant p99 in statistics()['slo'],
        labeled /metrics samples, and GET /siddhi/slo."""
        svc = SiddhiService()
        svc.start()
        try:
            for i in range(64):
                resp = svc.tenant_deploy({
                    "template": TPL, "tenant": f"t{i}",
                    "bindings": {"lo": 1.0, "hi": 99.0},
                    "pool": {"slots": 64, "max_tenants": 64,
                             "slo": {"p99_ms": 30_000.0, "every": 1}}})
            pool = svc._pool(resp["app"])
            pool.shutdown()   # drive rounds synchronously
            ts, cols = _chunk(16)
            for i in range(64):
                pool.send(f"t{i}", ts, cols)
            pool.flush()
            stats = pool.statistics()
            scopes = stats["slo"]["scopes"]
            for tid in ("t0", "t31", "t63"):
                assert scopes[f"tenant={tid}"]["p99_ms"] > 0
                assert scopes[f"tenant={tid},query=q"]["p99_ms"] > 0
                assert scopes[f"tenant={tid}"]["state"] == "OK"
            assert stats["slo"]["state"] == "OK"
            # /metrics: labeled samples, ONE family header
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/metrics") as r:
                text = r.read().decode()
            assert 'tenant="t63"' in text
            fam = [ln for ln in text.splitlines()
                   if ln.startswith("# TYPE") and "slo_p99_ms" in ln]
            assert len(fam) == 1, fam
            # GET /siddhi/slo
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/siddhi/slo") as r:
                slo = json.loads(r.read())
            rep = slo["pools"][pool.name]
            assert rep["scopes"]["tenant=t63"]["p99_ms"] > 0
            assert slo["state"] == "OK"
        finally:
            svc.stop()

    def test_throttled_tenant_breach_pages_and_dumps(self, tmp_path):
        """One tenant with a throttled drain (big backlog, slow rounds)
        must trip ITS burn-rate PAGE state and dump a flight-recorder
        artifact while unthrottled tenants stay healthier."""
        pool = _mk_pool(slots=8, max_tenants=8, batch_max=64,
                        slo={"p99_ms": 50.0, "target": 0.99, "every": 1,
                             "flight_dir": str(tmp_path)})
        pool.warmup([64])
        for i in range(4):
            pool.add_tenant(f"t{i}", {"lo": 1.0, "hi": 99.0})
        ts, cols = _chunk(64)
        # throttled tenant: 12 chunks queued at once -> its later chunks
        # age in the queue while rounds drain 64 rows/tenant at a time
        for c in range(12):
            pool.send("t0", ts + c * 64, cols)
        for i in range(1, 4):
            pool.send(f"t{i}", ts, cols)
        while pool.pump():
            time.sleep(0.02)   # the throttle: slow round cadence
        rep = pool.slo_report()
        hot = rep["scopes"]["tenant=t0"]
        assert hot["state"] == "PAGE", rep["scopes"]
        assert hot["burn_fast"] >= 14.4
        cold_p99 = max(rep["scopes"][f"tenant=t{i}"]["p99_ms"]
                       for i in range(1, 4))
        assert cold_p99 < hot["p99_ms"]
        # the breach dumped an artifact naming the paged scope
        arts = rep.get("flight_artifacts")
        assert arts, rep
        art = json.load(open(arts[-1]))
        assert art["reason"] == "slo-breach"
        assert "tenant=t0" in art["context"]["paged_scopes"]
        assert art["context"]["runtime"]["pool"] == pool.name
        # pool artifacts carry the FULL identity triple: app/pool name
        # and the template plan hash (obs/explain.py attribution)
        assert art["context"]["app"] == pool.name
        assert art["context"]["pool"] == pool.name
        assert art["context"]["plan_hash"] == pool.plan_hash()
        pool.shutdown()

    def test_stats_collection_one_device_get_with_slo_on(self,
                                                         monkeypatch):
        """SLO tracking must not add device reads to the registry walk:
        still exactly ONE device_get per pool (the PR 10 invariant)."""
        pool = _mk_pool(slots=8, max_tenants=8,
                        slo={"p99_ms": 1_000.0, "every": 1})
        for i in range(8):
            pool.add_tenant(f"t{i}", {"lo": 1.0, "hi": 99.0})
        ts, cols = _chunk(8)
        for i in range(8):
            pool.send(f"t{i}", ts, cols)
        pool.flush()
        calls = [0]
        real = jax.device_get

        def counting(x):
            calls[0] += 1
            return real(x)

        monkeypatch.setattr(jax, "device_get", counting)
        stats = pool.statistics()
        assert calls[0] == 1
        assert stats["slo"]["scopes"]["total"]["count"] > 0
        pool.shutdown()

    def test_threaded_ingest_vs_collect_race(self):
        """Dispatch threads observing latency samples while another
        thread collects/scrapes must never corrupt the windows (the
        PR 7 RLock pattern, applied to the SLO engine)."""
        pool = _mk_pool(slots=8, max_tenants=8,
                        slo={"p99_ms": 1_000.0, "every": 1})
        pool.warmup()
        for i in range(4):
            pool.add_tenant(f"t{i}", {"lo": 1.0, "hi": 99.0})
        errors = []
        stop = threading.Event()

        def ingest():
            ts, cols = _chunk(16)
            k = 0
            try:
                while not stop.is_set():
                    for i in range(4):
                        pool.send(f"t{i}", ts + k, cols)
                    pool.flush()
                    k += 16
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def collect():
            try:
                while not stop.is_set():
                    pool.statistics()
                    pool.metrics.prometheus_text()
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=ingest),
                   threading.Thread(target=collect),
                   threading.Thread(target=collect)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        rep = pool.slo_report()
        assert rep["scopes"]["total"]["count"] > 0
        pool.shutdown()

    def test_backlog_429_carries_saturation_cause(self):
        svc = SiddhiService()
        svc.start()
        try:
            resp = svc.tenant_deploy({
                "template": TPL, "tenant": "acme",
                "bindings": {"lo": 1.0, "hi": 99.0},
                "pool": {"slots": 1, "max_tenants": 1,
                         "pending_cap": 8}})
            pool = svc._pool(resp["app"])
            pool.shutdown()   # no drain: backlog builds
            rows = [[2.5, 1]] * 8
            req = urllib.request.Request(
                f"http://127.0.0.1:{svc.port}"
                f"/siddhi/tenant/ingest/{pool.name}/acme",
                data=json.dumps({"ts": list(range(8)),
                                 "rows": rows}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as r:
                assert r.status == 200
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{svc.port}"
                    f"/siddhi/tenant/ingest/{pool.name}/acme",
                    data=json.dumps({"ts": [9], "rows": rows[:1]}
                                    ).encode(),
                    headers={"Content-Type": "application/json"}))
                pytest.fail("expected 429")
            except urllib.error.HTTPError as e:
                assert e.code == 429
                body = json.loads(e.read())
                sat = body["saturation"]
                assert sat["cause"] == "ingest-backlog"
                assert sat["pending_rows"] >= 8
                assert sat["retry_after_ms"] >= 1
                assert e.headers["Retry-After"] is not None
            # the rejection is counted as a saturation signal
            assert pool.saturation()["rejections"]["ingest-backlog"] == 1
        finally:
            svc.stop()

    def test_admission_429_saturation_cause_slots(self):
        pool = _mk_pool(slots=1, max_tenants=1)
        pool.add_tenant("a", {"lo": 1.0, "hi": 9.0})
        with pytest.raises(AdmissionError) as ei:
            pool.add_tenant("b", {"lo": 1.0, "hi": 9.0})
        assert ei.value.saturation["cause"] == "slots-exhausted"
        assert ei.value.saturation["max_tenants"] == 1
        pool.shutdown()


# ---------------------------------------------------------------------------
# runtime path: @app:slo, per-query attribution, overhead bound
# ---------------------------------------------------------------------------


SLO_APP = """
@app:playback
@app:name('sloapp')
@app:slo(p99='30 sec', target='0.9', every='1')
define stream S (v int);
@info(name = 'q')
from S[v > 0] select v insert into Out;
"""


class TestRuntime:
    def test_statistics_metrics_and_report(self):
        rt = SiddhiManager().create_siddhi_app_runtime(SLO_APP)
        got = []
        rt.add_callback("Out", StreamCallback(fn=got.extend))
        rt.start()
        h = rt.get_input_handler("S")
        ts = TS0 + np.arange(64, dtype=np.int64)
        for k in range(3):
            h.send_arrays(ts + 64 * k, [np.ones(64, np.int32)])
        slo = rt.statistics()["slo"]
        assert slo["scopes"]["query=q"]["count"] >= 1
        assert slo["scopes"]["total"]["p99_ms"] > 0
        assert slo["state"] == "OK"
        assert "scheduler_lag_ms" in slo["saturation"]
        text = rt.metrics.prometheus_text()
        assert 'query="q"' in text
        rep = rt.slo_report()
        assert rep["objective"]["p99_ms"] == 30_000.0
        rt.shutdown()

    def test_no_annotation_means_no_engine(self):
        rt = SiddhiManager().create_siddhi_app_runtime(
            "define stream S (v int);\n"
            "from S select v insert into Out;")
        assert rt.slo is None
        assert rt.slo_report() is None
        rt.start()
        assert "slo" not in rt.statistics()
        rt.shutdown()


# ---------------------------------------------------------------------------
# tools: slo_report CI probe; chaos failure artifacts
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTools:
    def test_slo_report_ok_exit_zero(self, capsys):
        mod = _load_tool("slo_report")
        rc = mod.main(["--watch", "1", "--events", "64"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "query=q" in out and "OK" in out

    def test_slo_report_pages_exit_one(self, tmp_path, capsys):
        mod = _load_tool("slo_report")
        app = tmp_path / "paging.siddhi"
        # objective no real dispatch can meet -> every sample is bad ->
        # burn >> page.burn -> PAGE -> exit 1 (the CI gate contract)
        app.write_text("""
@app:name('slo_paging')
@app:playback
@app:slo(p99='0.001 ms', target='0.999', every='1')
define stream S (v int);
@info(name = 'q')
from S[v > 0] select v insert into Out;
""")
        rc = mod.main([str(app), "--watch", "1", "--events", "64"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "PAGE" in out

    def test_chaos_failure_artifact_path_in_assertion(self, tmp_path):
        from siddhi_tpu.resilience.scenarios import assert_scenario
        result = {"lost": [1, 2], "faults": [
            {"fault": "break_sink", "seed": 7, "rate": 0.5}]}
        with pytest.raises(AssertionError) as ei:
            assert_scenario("unit", False, result,
                            dirpath=str(tmp_path))
        msg = str(ei.value)
        assert "flight-recorder artifact" in msg
        path = msg.split("flight-recorder artifact: ")[1].split(";")[0]
        art = json.load(open(path))
        assert art["context"]["result"]["lost"] == [1, 2]
        armed = [s for s in art["spans"] if s["kind"] == "fault-armed"]
        assert armed and armed[0]["fault"] == "break_sink"
        assert armed[0]["seed"] == 7

    def test_fault_injector_logs_armed_schedule(self):
        from siddhi_tpu.resilience.faults import FaultInjector

        class _Sink:
            def publish(self, payload):
                pass

        with FaultInjector(seed=3) as fi:
            fi.break_sink(_Sink(), rate=0.25)
            assert fi.events == [
                {"fault": "break_sink", "seed": 3, "rate": 0.25}]

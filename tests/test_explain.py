"""Plan explain & live pipeline introspection (obs/explain.py,
docs/observability.md "Explain").

Contracts under test:

- the decisions section is BYTE-STABLE across two deploys of the same
  app in one process, and ``plan_hash`` is equal (the diffability
  contract — golden 5-app corpus: filter, fused chain3, equi join,
  seq5 pattern, partition-on-mesh);
- decisions match ground truth asserted against
  ``statistics()['compile']`` (fusion segments, join kernel picks incl.
  env-override / cost-evidence / no-cost-table causes, mesh placement);
- assembling a report allocates ZERO new jitted programs, changes no
  jit options, and performs ZERO device reads (counting-jit +
  counting-device_get guards — the same class of guard as PR 6/7);
- ``explain_diff`` flags an injected decision flip
  (``SIDDHI_TPU_JOIN_KERNEL=grid``) and two identical deploys diff
  clean; the tools/explain.py CLI exits 1/0 accordingly;
- pools explain once per template (two pools of one template share a
  plan_hash; slot-axis facts ride ``live``), and ``GET /siddhi/explain``
  serves the documents;
- a sweep: explain parses for every ref-corpus app that compiles.
"""
import json
import pathlib
import sys
import urllib.request

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.lang.tokens import SiddhiParserException
from siddhi_tpu.obs.explain import (ExplainReport, compute_plan_hash,
                                    explain_diff, render_text, to_dot)
from siddhi_tpu.ops.expr import CompileError

TS0 = 1_700_000_000_000

TOOLS = pathlib.Path(__file__).parent.parent / "tools"
if str(TOOLS) not in sys.path:
    sys.path.insert(0, str(TOOLS))


# ---------------------------------------------------------------------------
# the golden 5-app corpus
# ---------------------------------------------------------------------------

FILTER_APP = """
@app:name('xp_filter') @app:playback
define stream S (sym string, price double);
@info(name = 'q') from S[price > 100.0]
select sym, price insert into Out;
"""

CHAIN3_APP = """
@app:name('xp_chain3') @app:playback
define stream S (sym string, v int);
@info(name = 'q1') from S[v > 3] select sym, v insert into S1;
@info(name = 'q2') from S1[v < 900] select sym, v insert into S2;
@info(name = 'q3') from S2[v != 7] select sym, v insert into Out;
"""

JOIN_APP = """
@app:name('xp_join') @app:playback
define stream L (sym string, p double);
define stream R (sym string, t int);
@info(name = 'q')
from L#window.time(1 sec) join R#window.time(1 sec)
on L.sym == R.sym
select L.sym, p, t insert into Out;
"""

SEQ5_APP = """
@app:name('xp_seq5') @app:playback
define stream T (sym string, stage int);
@info(name = 'q')
from every e1=T[stage == 1] -> e2=T[stage == 2] -> e3=T[stage == 3]
  -> e4=T[stage == 4] -> e5=T[stage == 5]
within 60 sec
select e1.sym as sym insert into Out;
"""

PARTITION_APP = """
@app:name('xp_part') @app:playback
define stream S (k string, v int);
partition with (k of S) begin
  @info(name = 'pq') from S#window.length(4)
  select k, v insert into POut;
end;
"""

GOLDEN = {
    "filter": FILTER_APP,
    "chain3": CHAIN3_APP,
    "join": JOIN_APP,
    "seq5": SEQ5_APP,
    "partition": PARTITION_APP,
}


def _deploy(ql, **kw):
    rt = SiddhiManager().create_siddhi_app_runtime(ql, **kw)
    rt.start()
    return rt


def _mesh(n=2):
    from siddhi_tpu.parallel.sharding import build_mesh
    return build_mesh(n)


# ---------------------------------------------------------------------------
# golden snapshots: byte-stable decisions, equal hashes
# ---------------------------------------------------------------------------


class TestGoldenStability:
    @pytest.mark.parametrize("name", sorted(GOLDEN))
    def test_two_deploys_decisions_byte_stable(self, name):
        kw = {"mesh": _mesh(2)} if name == "partition" else {}
        a = _deploy(GOLDEN[name], **kw)
        b = _deploy(GOLDEN[name], **kw)
        try:
            ra, rb = a.explain(), b.explain()
            ja = json.dumps(ra["decisions"], sort_keys=True)
            jb = json.dumps(rb["decisions"], sort_keys=True)
            assert ja == jb, name
            assert json.dumps(ra["graph"], sort_keys=True) == \
                json.dumps(rb["graph"], sort_keys=True)
            assert ra["plan_hash"] == rb["plan_hash"]
            d = explain_diff(ra, rb)
            assert d["equal"] and d["changes"] == []
            # the hash is derivable from the hashed sections alone
            assert ra["plan_hash"] == compute_plan_hash(
                ra["graph"], ra["decisions"])
            # the whole report is JSON-serializable (the CLI contract)
            json.dumps(ra, sort_keys=True, default=str)
        finally:
            a.shutdown()
            b.shutdown()

    def test_plan_hash_ignores_live_and_programs(self):
        rt = _deploy(FILTER_APP)
        try:
            before = rt.plan_hash()
            h = rt.get_input_handler("S")
            from siddhi_tpu.core.types import GLOBAL_STRINGS
            sym = np.full(64, GLOBAL_STRINGS.encode("A"), np.int32)
            h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                          [sym, np.linspace(0, 200, 64)])
            rt.warmup(buckets=[64])   # programs section changes
            rep = rt.explain()
            assert rep["programs"]["programs"] > 0
            assert rep["plan_hash"] == before
        finally:
            rt.shutdown()

    def test_app_name_not_hashed(self):
        a = _deploy(FILTER_APP)
        b = _deploy(FILTER_APP.replace("xp_filter", "xp_filter_b"))
        try:
            ra, rb = a.explain(), b.explain()
            assert ra["app"] != rb["app"]
            assert ra["plan_hash"] == rb["plan_hash"]
        finally:
            a.shutdown()
            b.shutdown()


# ---------------------------------------------------------------------------
# ground truth vs statistics()['compile'] and the runtime wiring
# ---------------------------------------------------------------------------


class TestGroundTruth:
    def test_fusion_segments_match_runtime(self):
        rt = _deploy(CHAIN3_APP)
        try:
            fusion = rt.explain()["decisions"]["fusion"]
            ch = rt.queries["q1"]._fused_chain
            assert ch is not None
            assert fusion["segments"] == [
                {"head": "q1", "members": [q.name for q in ch.queries]}]
            assert fusion["segments"][0]["members"] == ["q1", "q2", "q3"]
            for m in ("q1", "q2", "q3"):
                assert fusion["queries"][m]["segment"] == ch.name
        finally:
            rt.shutdown()

    def test_unfused_break_reasons(self):
        rt = _deploy(FILTER_APP)
        try:
            fusion = rt.explain()["decisions"]["fusion"]
            assert fusion["queries"]["q"]["segment"] is None
            # Out has no subscriber — the hop cannot fuse forward
            assert fusion["queries"]["q"]["break"] == "no-subscriber"
        finally:
            rt.shutdown()

    def test_fuse_disabled_reflected(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_FUSE", "0")
        rt = _deploy(CHAIN3_APP)
        try:
            fusion = rt.explain()["decisions"]["fusion"]
            assert fusion["enabled"] is False
            assert fusion["segments"] == []
        finally:
            rt.shutdown()

    def test_join_kernels_match_statistics(self):
        rt = _deploy(JOIN_APP)
        try:
            rep = rt.explain()
            stats = rt.statistics()["compile"]["join_kernels"]
            assert rep["decisions"]["join_kernels"] == stats
            for rec in stats.values():
                assert rec["kernel"] == "probe"
                # a decision NEVER ships without a machine-readable
                # cause, cost table or not (the satellite fix)
                assert rec["cause"] in ("no-cost-table", "equi-default",
                                        "cost-evidence")
                assert rec["reason"]
        finally:
            rt.shutdown()

    def test_join_kernel_env_override_cause(self, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_JOIN_KERNEL", "grid")
        rt = _deploy(JOIN_APP)
        try:
            jk = rt.explain()["decisions"]["join_kernels"]
            assert jk["q.left"]["kernel"] == "grid"
            assert jk["q.left"]["cause"] == "env-override"
            assert jk == rt.statistics()["compile"]["join_kernels"]
        finally:
            rt.shutdown()

    def test_join_kernel_no_equi_cause(self):
        rt = _deploy(JOIN_APP.replace("on L.sym == R.sym",
                                      "on L.p > R.t"))
        try:
            jk = rt.explain()["decisions"]["join_kernels"]
            assert jk["q.left"]["kernel"] == "grid"
            assert jk["q.left"]["cause"] == "no-equi-conjunct"
        finally:
            rt.shutdown()

    def test_join_kernel_cost_evidence_cause(self, tmp_path,
                                             monkeypatch):
        # a persisted cost table showing this join's GRID center
        # dominating flips the recorded cause to evidence-backed
        monkeypatch.setenv("SIDDHI_TPU_STATE_DIR", str(tmp_path))
        (tmp_path / "costs.json").write_text(json.dumps(
            {"xp_join": {"join/q.left[grid]": {"ms_total": 99.0},
                         "query/other": {"ms_total": 1.0}}}))
        rt = _deploy(JOIN_APP)
        try:
            jk = rt.explain()["decisions"]["join_kernels"]
            assert jk["q.left"]["kernel"] == "probe"
            assert jk["q.left"]["cause"] == "cost-evidence"
            assert "join/q.left[grid]" in jk["q.left"]["reason"]
        finally:
            rt.shutdown()

    def test_pattern_decisions(self):
        rt = _deploy(SEQ5_APP)
        try:
            rep = rt.explain()
            q = rep["decisions"]["queries"]["q"]
            assert q["kind"] == "pattern"
            assert q["states"] == 5
            node = rep["graph"]["nodes"]["q"]
            assert node["inputs"] == ["T"]
            assert [s["ref"] for s in node["slots"]] == \
                ["e1", "e2", "e3", "e4", "e5"]
        finally:
            rt.shutdown()

    def test_partition_mesh_placement(self):
        rt = _deploy(PARTITION_APP, mesh=_mesh(2))
        try:
            part = rt.explain()["decisions"]["partitions"]["partition_1"]
            assert part["key_kinds"] == {"S": "value"}
            mesh = part["mesh"]
            assert mesh["n_devices"] == 2
            assert mesh["slots_per_device"] * 2 == part["slots"]
            placement = mesh["placement"]
            # the rule table's ground truth: key-slot table replicates
            # (pre-vmap batch->slot map), per-slot operator state shards
            assert all(v == "replicate" for p, v in placement.items()
                       if p.startswith("slot_tbl/"))
            qleaves = {p: v for p, v in placement.items()
                       if p.startswith("qstates/")}
            assert qleaves
            assert all(v == f"shard({mesh['axis']})"
                       for v in qleaves.values())
        finally:
            rt.shutdown()

    def test_watermark_and_slo_decisions(self):
        rt = _deploy("""
@app:name('xp_wm')
@app:watermark(lateness='500', policy='DROP', dedup='true')
@app:slo(p99='250 ms', target='0.99')
define stream S (v int);
@info(name = 'q') from S[v > 0] select v insert into Out;
""")
        try:
            d = rt.explain()["decisions"]
            assert d["watermarks"]["S"] == {
                "lateness_ms": 500, "policy": "DROP",
                "cap": rt._reorder["S"].conf.cap, "dedup": True}
            assert d["slo"]["p99_ms"] == 250.0
            assert d["playback"] is True
        finally:
            rt.shutdown()


# ---------------------------------------------------------------------------
# assembly invariant: zero compiles, zero device reads
# ---------------------------------------------------------------------------


def test_explain_compiles_nothing_and_reads_nothing(monkeypatch):
    """The PR 6/7-style guard: explain assembly must allocate zero new
    jitted programs (cache keys stay untouched — no jit wrapper is even
    constructed) and perform zero device reads (the ISSUE allows one
    batched read; the implementation needs none)."""
    rt = _deploy(CHAIN3_APP)
    h = rt.get_input_handler("S")
    from siddhi_tpu.core.types import GLOBAL_STRINGS
    sym = np.full(64, GLOBAL_STRINGS.encode("A"), np.int32)
    h.send_arrays(TS0 + np.arange(64, dtype=np.int64),
                  [sym, np.arange(64, dtype=np.int32)])
    jits, gets = [0], [0]
    real_jit, real_get = jax.jit, jax.device_get

    def counting_jit(*a, **kw):
        jits[0] += 1
        return real_jit(*a, **kw)

    def counting_get(*a, **kw):
        gets[0] += 1
        return real_get(*a, **kw)

    monkeypatch.setattr(jax, "jit", counting_jit)
    monkeypatch.setattr(jax, "device_get", counting_get)
    rep = rt.explain()
    hash2 = rt.plan_hash()
    assert jits[0] == 0, "explain built a jit wrapper"
    assert gets[0] <= 1, "explain read the device more than once"
    assert gets[0] == 0, "explain performed a device read"
    assert rep["plan_hash"] == hash2
    monkeypatch.undo()
    rt.shutdown()


# ---------------------------------------------------------------------------
# diff + CLI
# ---------------------------------------------------------------------------


class TestDiff:
    def test_injected_kernel_flip_flags_and_exits_1(self, tmp_path,
                                                    monkeypatch):
        a = _deploy(JOIN_APP)
        ra = a.explain()
        a.shutdown()
        monkeypatch.setenv("SIDDHI_TPU_JOIN_KERNEL", "grid")
        b = _deploy(JOIN_APP)
        rb = b.explain()
        b.shutdown()
        monkeypatch.delenv("SIDDHI_TPU_JOIN_KERNEL")
        d = explain_diff(ra, rb)
        assert not d["equal"]
        assert ra["plan_hash"] != rb["plan_hash"]
        flips = [c for c in d["changes"]
                 if c["path"] == "decisions.join_kernels.q.left.kernel"]
        assert flips and flips[0]["a"] == "probe" \
            and flips[0]["b"] == "grid"
        # CLI: --diff exits 1 on the flip, 0 on identical reports
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(ra, default=str))
        pb.write_text(json.dumps(rb, default=str))
        import explain as explain_cli
        assert explain_cli.main(["--diff", str(pa), str(pb)]) == 1
        assert explain_cli.main(["--diff", str(pa), str(pa)]) == 0

    def test_diff_reports_added_and_removed_decisions(self):
        a = _deploy(FILTER_APP)
        b = _deploy(CHAIN3_APP)
        try:
            d = explain_diff(a.explain(), b.explain())
            assert not d["equal"]
            paths = {c["path"] for c in d["changes"]}
            assert any(p.startswith("decisions.fusion") for p in paths)
        finally:
            a.shutdown()
            b.shutdown()

    def test_renderers(self):
        rt = _deploy(JOIN_APP)
        try:
            rep = rt.explain()
            text = render_text(rep)
            assert "plan_hash" in text and "join kernels" in text
            dot = to_dot(rep)
            assert dot.startswith("digraph") and '"q"' in dot
        finally:
            rt.shutdown()


# ---------------------------------------------------------------------------
# pools: template explains once, slot facts are live
# ---------------------------------------------------------------------------

POOL_TPL = """
define stream In (v double, k long);
@info(name='q')
from In[v > ${lo:double}]#window.lengthBatch(16)
select v, k insert into Out;
"""


class TestPoolExplain:
    def test_two_pools_one_template_share_plan_hash(self):
        from siddhi_tpu.serving import Template, TenantPool
        tpl = Template(POOL_TPL)
        p1 = TenantPool(tpl, name="xp_pool_a", slots=2, max_tenants=8)
        p2 = TenantPool(tpl, name="xp_pool_b", slots=4, max_tenants=8)
        try:
            r1, r2 = p1.explain(), p2.explain()
            assert r1["template"] == r2["template"] == tpl.key
            # the template explains ONCE: pools of one template share
            # the hash; slot-axis facts differ only in `live`
            assert r1["plan_hash"] == r2["plan_hash"]
            assert r1["live"]["slots"] == 2
            assert r2["live"]["slots"] == 4
            assert r1["decisions"]["pool"]["order"] == ["q"]
        finally:
            pass

    def test_slot_growth_keeps_plan_hash(self):
        from siddhi_tpu.serving import Template, TenantPool
        tpl = Template(POOL_TPL)
        pool = TenantPool(tpl, name="xp_pool_g", slots=1, max_tenants=8)
        before = pool.plan_hash()
        for i in range(4):   # forces slot-axis doubling
            pool.add_tenant(f"t{i}", {"lo": float(i)})
        rep = pool.explain()
        assert rep["plan_hash"] == before
        assert rep["live"]["slots"] >= 4
        assert rep["live"]["active_tenants"] == 4

    def test_mesh_pool_placement_decision(self):
        from siddhi_tpu.serving import Template, TenantPool
        tpl = Template(POOL_TPL)
        pool = TenantPool(tpl, name="xp_pool_m", slots=4, max_tenants=8,
                          mesh=_mesh(2))
        rep = pool.explain()
        mesh = rep["decisions"]["mesh"]
        assert mesh["n_devices"] == 2
        assert mesh["placement"]
        assert all(v == f"shard({mesh['axis']})"
                   for v in mesh["placement"].values())


# ---------------------------------------------------------------------------
# service front door
# ---------------------------------------------------------------------------


def test_service_explain_endpoint():
    from siddhi_tpu.core.service import SiddhiService
    svc = SiddhiService(port=0)
    svc.start()
    try:
        name = svc.deploy(FILTER_APP)
        svc.tenant_deploy({"template": POOL_TPL, "tenant": "t1",
                           "bindings": {"lo": 5.0}})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/siddhi/explain") as r:
            body = json.loads(r.read())
        assert name in body["apps"]
        rep = body["apps"][name]
        assert rep["plan_hash"] == svc._deployed[name].plan_hash()
        assert rep["decisions"]["queries"]["q"]["kind"] == "query"
        assert body["pools"], "tenant pool missing from explain"
        pool_rep = next(iter(body["pools"].values()))
        assert pool_rep["plan_hash"]
        assert pool_rep["live"]["active_tenants"] == 1
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# flight-recorder identity: {app, pool, plan_hash} on every artifact
# ---------------------------------------------------------------------------


class TestFlightIdentity:
    def test_runtime_page_artifact_names_app_and_plan(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_FLIGHT_DIR", str(tmp_path))
        rt = _deploy("""
@app:name('xp_slo')
@app:slo(p99='1 ms', target='0.5', warn.burn='1', page.burn='1')
define stream S (v int);
@info(name = 'q') from S[v > 0] select v insert into Out;
""")
        try:
            eng = rt.slo
            now = 1_000_000.0
            for i in range(32):   # every sample busts the 1 ms bound
                eng.observe((("query", "q"),), 100.0,
                            t_wall_ms=now - i * 100)
            rep = eng.evaluate(now_ms=now)
            art_path = rep.get("flight_artifact")
            assert art_path, rep
            art = json.loads(pathlib.Path(art_path).read_text())
            ctx = art["context"]
            assert ctx["app"] == "xp_slo"
            assert ctx["pool"] is None
            assert ctx["plan_hash"] == rt.plan_hash()
        finally:
            rt.shutdown()

    def test_pool_artifact_names_pool_and_plan(self, tmp_path):
        from siddhi_tpu.serving import Template, TenantPool
        tpl = Template(POOL_TPL)
        pool = TenantPool(tpl, name="xp_pool_f", slots=2, max_tenants=4,
                          slo={"p99_ms": 100.0,
                               "flight_dir": str(tmp_path)})
        path = pool.flight.dump("test-reason")
        art = json.loads(pathlib.Path(path).read_text())
        ctx = art["context"]
        assert ctx["app"] == "xp_pool_f"
        assert ctx["pool"] == "xp_pool_f"
        assert ctx["plan_hash"] == pool.plan_hash()

    def test_service_deploy_failure_artifact_has_identity(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("SIDDHI_TPU_FLIGHT_DIR", str(tmp_path))
        from siddhi_tpu.core.service import SiddhiService
        svc = SiddhiService(port=0)
        svc.start()   # stop() joins serve_forever — it must be running
        try:
            with pytest.raises(Exception):
                svc.deploy("@app:name('xp_broken')\n"
                           "define stream S (v int);\n"
                           "from Nope select v insert into Out;")
            arts = sorted(tmp_path.glob("*.json"))
            assert arts, "deploy failure did not dump an artifact"
            art = json.loads(arts[-1].read_text())
            ctx = art["context"]
            # identity keys are UNIFORM on every artifact; the parsed
            # app name survives even though no runtime was built
            assert ctx["app"] == "xp_broken"
            assert "pool" in ctx and "plan_hash" in ctx
            assert ctx["error"]
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# ref-corpus sweep: explain parses for every app that compiles
# ---------------------------------------------------------------------------


def test_explain_parses_for_whole_ref_corpus():
    corpus = pathlib.Path(__file__).parent / "ref_corpus"
    mgr = SiddhiManager()
    n_ok = 0
    for f in sorted(corpus.glob("*.json")):
        for case in json.loads(f.read_text())["cases"]:
            if case.get("expect_error"):
                continue
            try:
                rt = mgr.create_siddhi_app_runtime(
                    "@app:playback " + case["app"])
            except (CompileError, SiddhiParserException):
                continue   # compile-gated cases are out of scope here
            rep = rt.explain(live=False)
            assert rep["plan_hash"]
            # decisions always present (some corpus apps are pure
            # aggregation/table definitions with zero queries)
            assert "queries" in rep["decisions"]
            # every report must serialize (the CLI/endpoint contract)
            json.dumps(rep, sort_keys=True, default=str)
            n_ok += 1
    assert n_ok > 300, f"sweep covered only {n_ok} apps"

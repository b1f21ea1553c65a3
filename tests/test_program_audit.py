"""Compiled-program auditor tests (analysis/programs.py, tools/audit.py,
docs/tpu_hygiene.md "Compiled-program audit").

Four layers, mirroring the PR 16 semantic-lint gate discipline:

- engine invariants: auditing a warmed runtime constructs ZERO new jit
  wrappers, performs ZERO device reads and moves the persistent compile
  cache by ZERO entries — the audit is pure trace/lower introspection;
- the four seeded hazard fixtures (tests/lint_fixtures/bad_program_*)
  each fire exactly their rule through the real CLI, exit 1, and the
  SARIF output validates against the vendored 2.1.0 schema subset and
  names the offending program spec;
- gates: the curated repo suite (tools/audit_suite/) and a bounded,
  deterministic slice of the reference corpus audit CLEAN, compiling
  nothing, against the shipped EMPTY baseline
  (tools/audit_baseline.json); the full struct-deduplicated corpus
  sweep runs under ``-m slow``;
- surfacing: the audit block rides statistics()['compile']['audit'] and
  ExplainReport programs (never the plan hash), the
  ``@app:cap(program.mb=)`` dial gates the estimate, and re-warms
  dedupe already-compiled specs (satellite: CompileService._warmed_keys).
"""
import io
import json
import pathlib

import jax
import pytest

import siddhi_tpu  # noqa: F401  (x64 + platform setup)
from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis.audit_cli import main as audit_main, struct_class
from siddhi_tpu.core import compile as compile_mod

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).parent / "lint_fixtures"
SUITE = REPO / "tools" / "audit_suite"
BASELINE = REPO / "tools" / "audit_baseline.json"
CORPUS = pathlib.Path(__file__).parent / "ref_corpus"

CHAIN_APP = """
@app:name('audit_t_chain')
define stream S (sym string, v int, price double);
@info(name='q1') from S[v > 0] select sym, v, price insert into Mid;
@info(name='q2') from Mid select sym, v, price * 2.0 as price insert into Out;
"""


def _deploy(app):
    return SiddhiManager().create_siddhi_app_runtime(app)


def _cli(*argv):
    """Run the audit CLI in-process against the shipped baseline."""
    out = io.StringIO()
    code = audit_main(list(argv) + ["--root", str(REPO),
                                    "--baseline", str(BASELINE)],
                      stdout=out)
    return code, out.getvalue()


# ---------------------------------------------------------------------------
# engine invariants: zero jits, zero reads, zero cache movement
# ---------------------------------------------------------------------------


def test_audit_of_warmed_runtime_compiles_and_reads_nothing(monkeypatch):
    rt = _deploy(CHAIN_APP)
    rt.warmup(buckets=(1024,))
    before = compile_mod.cache_counts()
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
    summary = rt.audit_programs(buckets=(1024,))
    monkeypatch.undo()
    after = compile_mod.cache_counts()
    assert jits[0] == 0, "audit constructed a jit wrapper"
    assert gets[0] == 0, "audit performed a device read"
    assert after == before, "audit moved the persistent compile cache"
    assert summary["programs"] >= 2
    assert summary["findings"] == 0
    assert summary["donated"] >= 1, "chain steps donate state buffers"
    assert summary["unaliased"] == 0, "runtime donation must all alias"
    rt.shutdown()


def test_audit_surfaces_in_statistics_and_explain_not_hash():
    rt = _deploy(CHAIN_APP)
    rt._build_fused_chains()
    h0 = rt.plan_hash()
    summary = rt.audit_programs(buckets=(1024,))
    stats = rt.statistics()
    assert stats["compile"]["audit"]["programs"] == summary["programs"]
    rep = rt.explain()
    assert rep["programs"]["audit"]["findings"] == 0
    assert rep["plan_hash"] == h0, "audit results moved the plan hash"
    rt.shutdown()


def test_budget_dial_gates_the_program_estimate():
    tight = CHAIN_APP.replace("@app:name('audit_t_chain')",
                              "@app:name('audit_t_tight')\n"
                              "@app:cap(program.mb='0.01')")
    rt = _deploy(tight)
    from siddhi_tpu.analysis.programs import audit_runtime
    rep = audit_runtime(rt, buckets=(1024,), store=False)
    assert [f for f in rep.findings
            if f.rule == "program-memory-budget"], \
        "0.01MB dial must trip on a ~100KB program set"
    assert rep.summary()["budget_mb"] == 0.01
    rt.shutdown()
    # a generous dial stays quiet
    rt2 = _deploy(tight.replace("0.01", "64"))
    rep2 = audit_runtime(rt2, buckets=(1024,), store=False)
    assert not rep2.findings
    rt2.shutdown()


def test_fanout_attribution_names_member_queries():
    app = (SUITE / "fanout.siddhi").read_text()
    rt = _deploy(app.replace("audit_fanout", "audit_t_fanout"))
    rt._build_fused_chains()
    from siddhi_tpu.plan.optimizer import program_attribution
    attr = program_attribution(rt)
    grouped = [k for k in attr if k.startswith("fanout:")]
    assert grouped, "suite fanout app must derive a fan-out group"
    assert len(attr[grouped[0]]) >= 2
    from siddhi_tpu.analysis.programs import audit_runtime
    rep = audit_runtime(rt, buckets=(1024,), store=False)
    labeled = [t["step"] for t in rep.summary()["top"]
               if t["step"].startswith("fanout:") and "[" in t["step"]]
    assert labeled, "fan-out programs must carry member-query labels"
    rt.shutdown()


# ---------------------------------------------------------------------------
# re-warm dedupe (CompileService._warmed_keys)
# ---------------------------------------------------------------------------


def test_rewarm_dedupes_already_compiled_specs():
    rt = _deploy(CHAIN_APP)
    r1 = rt.warmup(buckets=(1024,))
    assert r1["programs"] >= 1 and not r1.get("deduped")
    r2 = rt.warmup(buckets=(1024,))
    assert r2["programs"] == 0
    assert r2["deduped"] == r1["programs"], \
        "identical re-warm must skip every already-compiled spec"
    # a NEW bucket still compiles (only the overlap is skipped)
    r3 = rt.warmup(buckets=(256, 1024))
    assert r3["deduped"] >= 1
    summary = rt.compile_service.summary()
    assert summary["programs"] == r1["programs"] + r3["programs"], \
        "summary counts unique compiled specs only"
    rt.shutdown()


def test_pool_rewarm_dedupes_and_keeps_one_program_set():
    from siddhi_tpu.serving.template import TemplateRegistry
    tpl = """
    @app:name('audit_t_pool')
    define stream S (v int, price double);
    @info(name='q1') from S[price > ${thr}] select v, price insert into Out;
    """
    reg = TemplateRegistry()
    reg.register(tpl, name="audit_t_pool")
    pool = reg.pool("audit_t_pool", shared={"thr": "1.0"})  # auto-warms
    r2 = pool.warmup()
    assert r2["programs"] == 0 and r2["deduped"] >= 1, \
        "a re-warm must skip the template's already-compiled specs"
    pool.add_tenant("t1")
    stats = pool.statistics()
    # the PR 12 invariant the template-keyed specs must preserve: one
    # program set per pool, specs keyed by template content (the pool's
    # display name never reaches a spec key)
    assert stats["compile"]["program_sets"] == 1
    assert stats["compile"]["programs"] >= 1
    for step in (r["step"] for r in
                 pool.proto.compile_service.summary(detail=True)
                 .get("steps", [])):
        assert step.startswith(f"tpl:{pool.template.key}"), step
    # auditing the pool reuses the same spec list and surfaces its
    # summary in the pool's compile stats
    summary = pool.audit_programs()
    assert summary["findings"] == 0
    assert pool.statistics()["compile"]["audit"]["programs"] == \
        summary["programs"]
    reg.shutdown()


# ---------------------------------------------------------------------------
# hazard fixtures through the real CLI (+ SARIF)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule", [
    ("bad_program_unaliased_donation.py", "program-donation-aliasing"),
    ("bad_program_io_callback.py", "program-host-boundary"),
    ("bad_program_weak_f64.py", "program-dtype-drift"),
    ("bad_program_over_budget.py", "program-memory-budget"),
])
def test_hazard_fixture_fires_its_rule_and_exits_1(fixture, rule):
    code, text = _cli(str(FIXTURES / fixture), "-q")
    assert code == 1, text
    assert rule in text, text
    others = set("program-donation-aliasing program-host-boundary "
                 "program-dtype-drift program-memory-budget".split())
    others.discard(rule)
    # donation fixtures legitimately trip nothing else; precision is
    # the point — each seeded hazard fires exactly its own rule
    assert not [r for r in others if r in text], text


def test_doctored_fixture_sarif_names_the_program_spec(tmp_path):
    sarif = tmp_path / "audit.sarif"
    code, _ = _cli(str(FIXTURES / "bad_program_unaliased_donation.py"),
                   "--sarif", str(sarif), "-q")
    assert code == 1
    doc = json.loads(sarif.read_text())
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert len(results) == 1
    r = results[0]
    assert r["ruleId"] == "program-donation-aliasing"
    assert r["level"] == "error"
    assert "fixture/unaliased_donation/row/1024" in r["message"]["text"]
    rules = {x["id"] for x in
             doc["runs"][0]["tool"]["driver"]["rules"]}
    assert "program-donation-aliasing" in rules
    # vendored schema subset (the PR 16 SARIF gate)
    jsonschema = pytest.importorskip("jsonschema")
    schema = json.loads(
        (pathlib.Path(__file__).parent / "sarif_schema_2.1.0.json")
        .read_text())
    jsonschema.validate(doc, schema)


def test_pragma_suppresses_a_program_rule(tmp_path):
    app = tmp_path / "weak.siddhi"
    app.write_text(
        "-- lint: disable=program-memory-budget\n"
        "@app:name('audit_t_pragma')\n"
        "@app:cap(program.mb='0.001')\n"
        "define stream S (v int);\n"
        "@info(name='q1') from S select v insert into Out;\n")
    code, text = _cli(str(app), "-q")
    assert code == 0, text


# ---------------------------------------------------------------------------
# gates: repo suite + bounded corpus slice, EMPTY shipped baseline
# ---------------------------------------------------------------------------


def test_shipped_audit_baseline_is_empty():
    doc = json.loads(BASELINE.read_text())
    assert doc["findings"] == {}, \
        "the audit baseline must stay empty — fix programs, not grandfather"


def test_repo_suite_audits_clean_within_budget():
    # runtime CONSTRUCTION compiles small eager fill programs (persistent
    # cache misses when the cache is cold); a first pass warms those, so
    # the measured pass isolates the audit, which must compile nothing
    _cli(str(SUITE))
    before = compile_mod.cache_counts()
    code, text = _cli(str(SUITE))
    assert code == 0, text
    assert "0 new finding(s)" in text
    after = compile_mod.cache_counts()
    assert after["misses"] == before["misses"], \
        "suite audit compiled new programs"


def _corpus_cases(round_robin=False):
    """Struct-deduplicated corpus app texts, deterministic order.
    ``round_robin`` interleaves one case per corpus file first — the
    bounded tier-1 slice covers join/pattern/sequence/window breadth
    instead of burning its budget inside the first file."""
    seen, per_file = set(), []
    for f in sorted(CORPUS.glob("*.json")):
        cases = []
        for i, case in enumerate(json.loads(f.read_text())["cases"]):
            if case.get("expect_error"):
                continue
            text = "@app:playback " + case["app"]
            cls = struct_class(text)
            if cls in seen:
                continue
            seen.add(cls)
            cases.append((f"{f.stem}#{i}", text))
        per_file.append(cases)
    if not round_robin:
        return [c for cases in per_file for c in cases]
    out, depth = [], 0
    while any(depth < len(cases) for cases in per_file):
        out += [cases[depth] for cases in per_file
                if depth < len(cases)]
        depth += 1
    return out


# the tier-1 slice: ~15 s with a cold compile cache on an 8-core host
CORPUS_SLICE = 8


def test_corpus_slice_audits_clean_within_budget(monkeypatch):
    """PR 16 gate pattern: a bounded, deterministic slice of the
    reference corpus — its first CORPUS_SLICE struct-distinct apps,
    round robin over the corpus files — audits CLEAN (the full sweep
    runs under -m slow and via `tools/audit.py --corpus`). Zero new
    compiles and zero device reads across the whole slice."""
    from siddhi_tpu.analysis.programs import audit_runtime
    from siddhi_tpu.lang.tokens import SiddhiParserException
    from siddhi_tpu.ops.expr import CompileError
    audit_misses = 0
    gets = [0]
    real_get = jax.device_get

    def counting_get(*a, **kw):
        gets[0] += 1
        return real_get(*a, **kw)

    monkeypatch.setattr(jax, "device_get", counting_get)
    mgr = SiddhiManager()
    audited, dirty = 0, []
    for rel, text in _corpus_cases(round_robin=True)[:CORPUS_SLICE]:
        try:
            rt = mgr.create_siddhi_app_runtime(text)
        except (CompileError, SiddhiParserException):
            continue
        m0 = compile_mod.cache_counts()["misses"]
        rep = audit_runtime(rt, buckets=(1024,), path=rel, store=False)
        audit_misses += compile_mod.cache_counts()["misses"] - m0
        dirty += [f"{rel}: {f.render()}" for f in rep.findings]
        audited += 1
    monkeypatch.undo()
    assert not dirty, "\n".join(dirty[:10])
    assert audited == CORPUS_SLICE, f"slice audited only {audited} apps"
    assert gets[0] == 0, "audit performed device reads"
    # runtime CONSTRUCTION may compile eager fills (misses on a cold
    # cache); the audit itself must compile nothing
    assert audit_misses == 0, "corpus audit compiled new programs"


@pytest.mark.slow
def test_full_corpus_audits_clean():
    """Every compilable, struct-distinct corpus app audits clean —
    the whole-sweep version of the tier-1 slice gate."""
    from siddhi_tpu.analysis.programs import audit_runtime
    from siddhi_tpu.lang.tokens import SiddhiParserException
    from siddhi_tpu.ops.expr import CompileError
    mgr = SiddhiManager()
    audited, dirty = 0, []
    for rel, text in _corpus_cases():
        try:
            rt = mgr.create_siddhi_app_runtime(text)
        except (CompileError, SiddhiParserException):
            continue
        rep = audit_runtime(rt, buckets=(1024,), path=rel, store=False)
        dirty += [f"{rel}: {f.render()}" for f in rep.findings]
        audited += 1
    assert not dirty, "\n".join(dirty[:20])
    assert audited > 150, f"sweep covered only {audited} app classes"

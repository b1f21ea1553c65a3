"""AOT compile service (core/compile.py, docs/compile_cache.md).

Covers the deploy-time contract: `start()` / `warmup()` compile every
step program for the configured ingest buckets BEFORE the first chunk
(zero-compiles-after-first-ingest, mirroring tests/test_fusion.py's
recompile guard), the telemetry surfaced through `statistics()`, and
the persistent-cache warm-start behavior (second build of an identical
app hits the disk cache instead of recompiling).
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from siddhi_tpu import Event, SiddhiManager, StreamCallback
from siddhi_tpu.core.types import GLOBAL_STRINGS

TS0 = 1_700_000_000_000

CHAIN_APP = """
    @app:playback
    define stream S (sym string, v int, p float);
    @info(name = 'q1') from S[v > 3] select sym, v, p insert into S1;
    @info(name = 'q2') from S1[p > 1.0] select sym, v, p insert into S2;
    @info(name = 'q3') from S2[v < 900] select sym, v, p insert into OutS;
"""

PARTITION_APP = """
    @app:playback
    define stream S (sym string, v int);
    partition with (sym of S)
    begin
        @info(name = 'pq') from S[v > 0] select sym, v * 2 as v
        insert into POut;
    end;
"""

PATTERN_JOIN_APP = """
    @app:playback
    define stream A (oid int, amt float);
    define stream B (pid int, oid int);
    define stream L (sym string, price float);
    define stream R (sym string, tweets int);
    @info(name = 'seq')
    from e1=A[amt > 10.0] -> e2=B[oid == e1.oid] within 5 sec
    select e1.oid as o, e2.pid as p insert into SeqOut;
    @info(name = 'jq') @cap(window.size='64', join.pairs='256')
    from L#window.time(1 sec) join R#window.time(1 sec)
    on L.sym == R.sym
    select L.sym, price, tweets insert into JOut;
"""


def _const_chunk(n, base):
    """Affine timestamps + constant columns: the encoding stays at the
    encoder's INITIAL tuple, which warmup precompiles."""
    ts = base + np.arange(n, dtype=np.int64)
    sym = np.full(n, GLOBAL_STRINGS.encode("A"), np.int32)
    v = np.full(n, 5, np.int32)
    p = np.full(n, 2.0, np.float32)
    return ts, [sym, v, p]


def _counting_jit(monkeypatch):
    real_jit = jax.jit
    traces = [0]

    def counting(f, *a, **kw):
        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            traces[0] += 1
            return f(*args, **kwargs)
        return real_jit(wrapped, *a, **kw)

    monkeypatch.setattr(jax, "jit", counting)
    return traces


def test_start_compiles_all_before_first_ingest(monkeypatch):
    """With warm buckets configured, start() AOT-compiles every step the
    app can dispatch; the first chunks (columnar packed AND row path)
    then trigger ZERO fresh traces."""
    traces = _counting_jit(monkeypatch)
    monkeypatch.setenv("SIDDHI_TPU_WARM_BUCKETS", "16,128")
    rt = SiddhiManager().create_siddhi_app_runtime(CHAIN_APP)
    outs = []
    rt.add_callback("OutS", StreamCallback(fn=outs.extend))
    rt.start()  # <- all compiles happen here
    assert rt.queries["q1"]._fused_chain is not None
    assert rt.compile_service.warmups == 1
    assert rt.compile_service.programs > 0
    before = traces[0]
    h = rt.get_input_handler("S")
    h.send_arrays(*_const_chunk(100, TS0))          # packed, bucket 128
    h.send(Event(TS0 + 200, ("A", 7, 2.5)))         # row path, bucket 16
    rt.shutdown()
    assert outs, "events did not flow through the warmed chain"
    assert traces[0] == before, \
        f"first ingest triggered {traces[0] - before} fresh traces"


def test_partition_zero_compiles_after_start(monkeypatch):
    traces = _counting_jit(monkeypatch)
    monkeypatch.setenv("SIDDHI_TPU_WARM_BUCKETS", "128")
    rt = SiddhiManager().create_siddhi_app_runtime(PARTITION_APP)
    outs = []
    rt.add_callback("POut", StreamCallback(fn=outs.extend))
    rt.start()
    before = traces[0]
    ts = TS0 + np.arange(64, dtype=np.int64)
    sym = np.full(64, GLOBAL_STRINGS.encode("K"), np.int32)
    v = np.full(64, 3, np.int32)
    rt.get_input_handler("S").send_arrays(ts, [sym, v])
    rt.shutdown()
    assert outs, "partition emitted nothing"
    assert traces[0] == before, \
        f"partition ingest triggered {traces[0] - before} fresh traces"


def test_warmup_enumerates_pattern_join_and_reports_telemetry():
    rt = SiddhiManager().create_siddhi_app_runtime(PATTERN_JOIN_APP)
    rt.start()
    wu = rt.warmup(buckets=[128])
    keys = [s["step"] for s in wu["steps"]]
    assert any("/pattern/A/" in k for k in keys)
    assert any("/pattern/B/" in k for k in keys)
    assert any("/join/L/" in k for k in keys)
    assert any("/join/R/" in k for k in keys)
    # join sides have timer windows -> cap-16 timer shapes warmed too
    assert any(k.endswith("/row/16") and "/join/" in k for k in keys)
    assert not wu.get("errors"), wu.get("errors")
    assert wu["programs"] == len(keys)
    assert wu["compile_ms"] > 0
    # telemetry lands in statistics(); DETAIL adds the per-step list
    stats = rt.statistics()
    assert stats["compile"]["programs"] == wu["programs"]
    assert "steps" not in stats["compile"]
    rt.set_statistics_level("DETAIL")
    assert len(rt.statistics()["compile"]["steps"]) == wu["programs"]
    rt.shutdown()


def test_warmup_samples_derive_sticky_encoding():
    """A traffic sample widens the packed encoding; warmup compiles the
    widened tuple so the sampled traffic shape also dispatches warm."""
    rt = SiddhiManager().create_siddhi_app_runtime(CHAIN_APP)
    rt.start()
    n = 64
    ts = TS0 + np.arange(n, dtype=np.int64)
    sym = np.array([GLOBAL_STRINGS.encode(s)
                    for s in ("A", "B") * (n // 2)], np.int32)
    v = np.arange(n, dtype=np.int32)
    p = np.linspace(0.0, 3.0, n, dtype=np.float32)
    wu = rt.warmup(buckets=[128], samples={"S": (ts, [sym, v, p])})
    keys = [s["step"] for s in wu["steps"]]
    packed = [k for k in keys if "/packed/" in k]
    # initial encoding AND the sample-derived (widened) encoding
    assert len(packed) == 2, packed
    assert any(k.endswith("aff,c,c,c") for k in packed)
    rt.shutdown()


def test_manager_warmup_covers_all_apps():
    mgr = SiddhiManager()
    rt1 = mgr.create_siddhi_app_runtime(
        "@app:name('one') " + CHAIN_APP)
    rt2 = mgr.create_siddhi_app_runtime(
        "@app:name('two') " + PARTITION_APP)
    rt1.start()
    rt2.start()
    out = mgr.warmup(buckets=[16])
    assert set(out) == {"one", "two"}
    assert all(v["programs"] > 0 for v in out.values())
    mgr.shutdown()


def _fresh_cache_dir(tmp_path):
    """Point the persistent compile cache at a hermetic directory."""
    from jax._src import compilation_cache as cc
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()

    def restore():
        jax.config.update("jax_compilation_cache_dir", old)
        cc.reset_cache()
    return restore


def _cache_files(tmp_path):
    return sum(len(fs) for _, _, fs in os.walk(tmp_path))


def test_warm_start_in_process_hits_persistent_cache(tmp_path):
    """Tier-1 warm-start variant: building the SAME app twice, the
    second build's warmup loads every program from the persistent cache
    (cache hits > 0, zero fresh cache entries written)."""
    restore = _fresh_cache_dir(tmp_path)
    try:
        rt1 = SiddhiManager().create_siddhi_app_runtime(CHAIN_APP)
        rt1.start()
        wu1 = rt1.warmup(buckets=[128])
        rt1.shutdown()
        files_after_cold = _cache_files(tmp_path)
        assert files_after_cold > 0, "cold warmup wrote no cache entries"
        assert wu1["cache_misses"] > 0

        rt2 = SiddhiManager().create_siddhi_app_runtime(CHAIN_APP)
        rt2.start()
        wu2 = rt2.warmup(buckets=[128])
        rt2.shutdown()
        assert wu2["cache_hits"] > 0, wu2
        assert wu2["cache_misses"] < wu1["cache_misses"], (wu1, wu2)
        assert _cache_files(tmp_path) == files_after_cold, \
            "warm warmup wrote fresh cache entries"
    finally:
        restore()


_CHILD_SCRIPT = r"""
import json
from siddhi_tpu import SiddhiManager
APP = '''
@app:playback
define stream S (sym string, v int, p float);
@info(name = 'q1') from S[v > 3] select sym, v, p insert into S1;
@info(name = 'q2') from S1[p > 1.0] select sym, v, p insert into OutS;
'''
rt = SiddhiManager().create_siddhi_app_runtime(APP)
rt.start()
wu = rt.warmup(buckets=[128])
rt.shutdown()
print(json.dumps({k: wu[k] for k in
                  ("programs", "cache_hits", "cache_misses")}))
"""


@pytest.mark.slow
def test_warm_start_across_processes(tmp_path):
    """Two subprocesses sharing JAX_COMPILATION_CACHE_DIR: the second
    run reports cache hits > 0 and compiles strictly fewer programs."""
    env = dict(os.environ)
    env.update(JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu")

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD_SCRIPT],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.splitlines()[-1])

    cold = run()
    warm = run()
    assert cold["cache_misses"] > 0
    assert warm["cache_hits"] > 0, (cold, warm)
    assert warm["cache_misses"] < cold["cache_misses"], (cold, warm)


def test_failed_warmup_spec_raises_not_deferred():
    """A spec that fails at warmup raises WarmupError once every spec was
    tried — it is never left to compile lazily on the first chunk."""
    from siddhi_tpu.core.compile import CompileSpec, WarmupError

    def bad():
        raise ValueError("cannot build")

    rt = SiddhiManager().create_siddhi_app_runtime(CHAIN_APP)
    with pytest.raises(WarmupError) as ei:
        rt.compile_service.warm_specs([CompileSpec("bad/row/16", bad)])
    assert ei.value.errors[0]["step"] == "bad/row/16"
    assert rt.compile_service.summary()["errors"] == 1
    assert rt.compile_service.ready
    rt.shutdown()


_CACHE_DIR_SCRIPT = (
    "import json, jax, siddhi_tpu\n"
    "print(json.dumps(jax.config.jax_compilation_cache_dir))\n")


@pytest.mark.parametrize("given", [False, True])
def test_compile_cache_is_placed_from_outside(tmp_path, given):
    """JAX_COMPILATION_CACHE_DIR set: the code names no other directory.
    Unset: <checkout>/.jax_cache, whatever the working directory."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if given:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "given")
    proc = subprocess.run([sys.executable, "-c", _CACHE_DIR_SCRIPT],
                          cwd=str(tmp_path), env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.splitlines()[-1])
    assert got == (str(tmp_path / "given") if given
                   else os.path.join(repo, ".jax_cache"))

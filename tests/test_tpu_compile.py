"""Compile the main path's step programs for a described TPU v5e.

The TPU compiler is installed here even where no chip is attached: it
compiles for a topology that is described, not present. These tests build
the jitted steps chip_smoke.py dispatches — phase ``window`` (filter ->
1 s time window -> group-by sum) and phase ``seq5`` (5-state pattern) —
from their ``CompileService`` specs at 65,536-row chunks, with the packed
encoding the smoke's own traffic settles on, and compile each for one
chip of a described ``v5e:2x2``. What the chip's compiler refuses (or
takes minutes over: 64-bit ``cummax`` once did) fails here at no chip
time. Nothing runs, so nothing here says anything about results or time.

Only one process may load libtpu at a time, so the topology is described
inside a module fixture (never at import), and the file stays one file:
every compile happens in the worker that runs it.
"""
import os
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402
from siddhi_tpu import SiddhiManager  # noqa: E402
from siddhi_tpu.core.compile import abstract_spec_args  # noqa: E402
from siddhi_tpu.core.ingest import encoding_for_sample  # noqa: E402

CHUNK = 65536
HBM_BYTES = 16 * 2 ** 30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ["TPU_SKIP_MDS_QUERY"] = "1"   # else a metadata lookup hangs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_packed_step(app: str, stream: str, sample, one_chip):
    """Compile the app's packed ingest step for the sample's encoding at
    CHUNK rows, for one described chip. Returns the compiled program."""
    rt = SiddhiManager().create_siddhi_app_runtime(app)
    rt.start()
    try:
        enc = ",".join(encoding_for_sample(rt.schemas[stream], *sample))
        specs = [s for s in rt.compile_service.specs(
                     [CHUNK], samples={stream: sample})
                 if s.key.endswith(f"/packed/{CHUNK}/{enc}")]
        assert len(specs) == 1, [s.key for s in specs]
        with abstract_spec_args():
            fn, args = specs[0].build()
    finally:
        rt.shutdown()
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return fn.trace(*args).lower().compile()


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_window_step_compiles_for_v5e(one_chip, no_persistent_cache):
    ts, sym_idx, price, volume = chip_smoke.window_data(
        CHUNK, 1024, 100, seed=0)
    sym = chip_smoke.sym_codes(chip_smoke.sym_names(1024))[sym_idx]
    app = chip_smoke.WINDOW_APP.format(cap=131072, keys=4096)
    compiled = _compile_packed_step(app, "StockStream",
                                    (ts, [sym, price, volume]), one_chip)
    _fits_one_chip(compiled)


def test_seq5_step_compiles_for_v5e(one_chip, no_persistent_cache):
    ts, sym_idx, stage, v = chip_smoke.seq5_data(CHUNK, 1024, 4, seed=0)
    sym = chip_smoke.sym_codes(chip_smoke.sym_names(1024))[sym_idx]
    compiled = _compile_packed_step(chip_smoke.SEQ5_APP, "T",
                                    (ts, [sym, stage, v]), one_chip)
    _fits_one_chip(compiled)
    # the step is one program on one chip: no collectives in it
    assert "all-gather" not in compiled.as_text()


def _linear_road():
    """The benchmark's Linear Road configuration and its generator."""
    import importlib.util
    import json
    base = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
    with open(base / "linear_road_stopped.json") as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "linear_road_stopped", base / "linear_road_stopped.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return config, mod


# ~160 s of compile on one core here, more than tier-1's time allows
@pytest.mark.slow
def test_linear_road_pattern_step_compiles_for_v5e(one_chip,
                                                    no_persistent_cache):
    """The keyed pattern step at the benchmark's size: a pending table of
    1,048,576 rows against 65,536-report chunks."""
    import numpy as np
    config, mod = _linear_road()
    assert "pattern.pending='1048576'" in config["app"]
    offsets, cols = mod.generate(np.random.default_rng(0), CHUNK)
    ts = chip_smoke.TS0 + offsets
    sample = (ts, [cols[c["name"]] for c in config["columns"]])
    compiled = _compile_packed_step(config["app"], config["stream"], sample,
                                    one_chip)
    _fits_one_chip(compiled)

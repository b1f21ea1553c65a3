"""Test configuration: a virtual 8-device CPU platform, set through the
environment before jax loads, so sharding / multi-chip paths run without
TPU hardware (the described-chip compile tests in test_tpu_compile.py
compile for a v5e that is not attached).
"""
import os

# persistent compile cache: identical-structure queries across test cases
# (the ref corpus reuses a handful of query shapes over hundreds of cases)
# compile once per shape instead of once per case. Across runs that holds
# only when an earlier run left the cache warm: a fresh checkout starts
# empty, and tier-1's time limit has to hold without it (cold, a corpus
# reference class takes about twice its warm time). Its own directory:
# XLA warns when it loads CPU results built for another machine's
# feature set (docs/compile_cache.md).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), ".jax_cache", "cpu"))

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running backstop tests, excluded from tier-1 "
        "(-m 'not slow')")

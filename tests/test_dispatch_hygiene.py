"""Dispatch-path hygiene: no module-level jax device arrays.

A jax array created at import time initializes the device backend in
the importing process (a parent that only spawns children would then hold
the chip), and one captured by a jitted step is one more device buffer per
program (ops/sentinels.py). Constants that jitted code touches must be
numpy scalars/arrays, which embed as HLO literals.

The primary guard is now STATIC: the `module-device-array` lint rule
(siddhi_tpu/analysis/jax_rules.py) flags the jnp/device_put call itself
with a file:line anchor, without importing anything. The original
runtime import-walk survives as a slow-marked backstop for arrays built
through paths the AST rule cannot see (getattr tricks, exec, C
extensions).
"""
import os

import pytest

import siddhi_tpu
from siddhi_tpu.analysis import lint_paths

PKG_DIR = os.path.dirname(os.path.abspath(siddhi_tpu.__file__))


def test_no_module_level_device_arrays_static():
    findings = [f for f in lint_paths([PKG_DIR], root=PKG_DIR)
                if f.rule == "module-device-array"]
    assert not findings, (
        "module-level jax arrays poison the dispatch fast path when "
        "captured by jitted steps:\n" + "\n".join(
            f.render() for f in findings))


@pytest.mark.slow
def test_no_module_level_device_arrays_runtime():
    """Backstop: import every module and inspect live attributes."""
    import importlib
    import pkgutil

    import jax

    offenders = []
    mods = [siddhi_tpu]
    for pkg in pkgutil.walk_packages(siddhi_tpu.__path__,
                                     prefix="siddhi_tpu."):
        mods.append(importlib.import_module(pkg.name))
    for mod in mods:
        for name, val in vars(mod).items():
            if isinstance(val, jax.Array):
                offenders.append(f"{mod.__name__}.{name}")
    assert not offenders, (
        "module-level jax arrays poison the dispatch fast path when "
        f"captured by jitted steps: {offenders}")

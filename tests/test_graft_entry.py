"""Smoke tests for the driver entry points (__graft_entry__.py).

The conftest gives this process an 8-device virtual CPU platform through
its environment, so the multichip impl runs in-process here; the
driver-facing dryrun_multichip() runs on whatever devices are visible and
raises when there are fewer than it is asked for.
"""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as ge  # noqa: E402


def test_entry_executes():
    fn, args = ge.entry()
    out = fn(*args)
    jax.block_until_ready(out)
    states, tstates, emitted, out_batch, due = out
    assert out_batch.valid.shape[0] > 0


def test_multichip_impl_8_devices():
    ge._dryrun_multichip_impl(8)


def test_dryrun_multichip_raises_when_devices_are_short():
    with pytest.raises(RuntimeError, match="needs 16 devices, 8 visible"):
        ge.dryrun_multichip(16)

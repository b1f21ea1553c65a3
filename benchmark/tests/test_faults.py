"""With the timed path broken underneath, a run's `correct` comes out
false: once for each fault a cell can have. The harness's look for a
chip is skipped (CPU, tiny sizes); the rest of the run is the real one.

Faults, planted when the window opens:
- a step that returns its state unchanged;
- half of each chunk left out;
- an answer altered where rows are decoded;
- the control (the reference with one guarantee broken) put in the
  program's place.
"""
import jax
import jax.numpy as jnp
import pytest

from tiny import run_tiny, spec, tiny_cell
from harness import drive
import run as runmod

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def _freeze_state(mp):
    """Every step returns the state it had when the window opened."""
    from siddhi_tpu.core import runtime as R
    targets = [(R.QueryRuntime, "_packed_step_for", 0),
               (R.QueryRuntime, "_step_for", 0)]
    for cls, meth, idx in targets:
        orig = getattr(cls, meth)

        def getter(self, *a, _orig=orig, _idx=idx, **k):
            step = _orig(self, *a, **k)
            saved = self.__dict__.setdefault("_bench_frozen", {})

            def frozen(*args):
                key = id(step)
                if key not in saved:
                    saved[key] = jax.tree_util.tree_map(jnp.copy,
                                                        args[_idx])
                res = step(*args)
                keep = jax.tree_util.tree_map(jnp.copy, saved[key])
                return tuple(res[:_idx]) + (keep,) + tuple(res[_idx + 1:])
            return frozen
        mp.setattr(cls, meth, getter)


def _half_batch(mp):
    from siddhi_tpu.core.stream import InputHandler
    orig = InputHandler.send_arrays

    def half(self, ts, cols):
        n = len(ts) // 2
        return orig(self, ts[:n], [c[:n] for c in cols])
    mp.setattr(InputHandler, "send_arrays", half)


def _alter_answer(mp):
    from siddhi_tpu.core import runtime as R
    orig = R.rows_from_batch

    def altered(types, batch):
        rows = orig(types, batch)
        if rows:
            ts, kind, vals = rows[0]
            vals = list(vals)
            vals[-1] = vals[-1] + 1
            rows[0] = (ts, kind, type(rows[0][2])(vals))
        return rows
    mp.setattr(R, "rows_from_batch", altered)


FAULTS = {"state_unchanged": _freeze_state, "half_batch": _half_batch,
          "answer_altered": _alter_answer}


def _run_with(mp, name, plant):
    orig = drive.CellRun.window

    def window(self):
        plant(mp)
        return orig(self)
    mp.setattr(drive.CellRun, "window", window)
    run, cr = run_tiny(tiny_cell(name))
    return runmod.result(run, {"platform": "cpu"})


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_makes_run_incorrect(monkeypatch, name, fault):
    res = _run_with(monkeypatch, name, FAULTS[fault])
    assert res["correct"] is False
    assert res["checks"]["rows_differing"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_incorrect(monkeypatch, name):
    orig = drive.CellRun.compare

    def compare(self):
        ts, cols = self.traffic.stream(self.g)
        rows = self.cell.reference.control(ts, cols, self.traffic.labels)
        self.got = [(0, [_Row(r) for r in rows])]
        return orig(self)
    monkeypatch.setattr(drive.CellRun, "compare", compare)
    run, cr = run_tiny(tiny_cell(name))
    res = runmod.result(run, {"platform": "cpu"})
    assert res["correct"] is False


class _Row:
    """An output row as the callback sees one: timestamp and data."""

    def __init__(self, row):
        self.timestamp, self.data = row[0], row[1:]


def test_sound_run_is_correct():
    run, cr = run_tiny(tiny_cell(CELLS[0]))
    assert runmod.result(run, {"platform": "cpu"})["correct"] is True

"""The references agree with the runtime on the CPU at a tiny size,
through the harness's whole run path, for every cell. Each control
disagrees."""
import pytest

from tiny import run_tiny, tiny_cell
from harness import drive

from tiny import spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_runtime_matches_reference(name):
    run, cr = run_tiny(tiny_cell(name))
    checks = {k: (v, op, lim) for k, v, op, lim in run.checks}
    assert checks["rows_differing"][0] == 0
    assert checks["overflow"][0] == 0
    assert checks["compiles_in_window"][0] == 0
    assert checks["rows_expected"][0] > 0
    assert run.events_window > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_comparison(name):
    cell = tiny_cell(name)
    t = drive.gen.Traffic(cell.config, cell.traffic, 11)
    ts, cols = t.stream(4 * t.block)
    want = cell.reference.reference(ts, cols, t.labels)
    ctrl = cell.reference.control(ts, cols, t.labels)
    assert drive.rows_differing(ctrl, want, cell.config["compare"]) > 0
    assert drive.rows_differing(want, want, cell.config["compare"]) == 0


def test_traced_run_reads_spans():
    run, cr = run_tiny(tiny_cell(CELLS[0]), seconds=1.5,
                       trace=True)
    tr = run.traced
    assert tr.chunks > 0
    assert tr.span_ns_of("ingest/") > 0
    assert tr.span_ns_of("step/") > 0
    assert any(name == "callback" for name, *_ in tr.spans)

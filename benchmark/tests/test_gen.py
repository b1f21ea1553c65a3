"""The generator and the cycle replay are deterministic per seed."""
import numpy as np
import pytest

from tiny import spec
from harness import gen

CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]
TRAFFIC = {"block_events": 4096}


def _cfg(name):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")


@pytest.mark.parametrize("name", CONFIGS)
def test_same_seed_same_stream(name):
    a = gen.Traffic(_cfg(name), TRAFFIC, 2**31 + 17)
    b = gen.Traffic(_cfg(name), TRAFFIC, 2**31 + 17)
    c = gen.Traffic(_cfg(name), TRAFFIC, 2**31 + 18)
    for k in a.cols:
        np.testing.assert_array_equal(a.cols[k], b.cols[k])
    assert any(not np.array_equal(a.cols[k], c.cols[k]) for k in a.cols)


@pytest.mark.parametrize("name", CONFIGS)
def test_replay_tiles_block_and_time_runs_on(name):
    t = gen.Traffic(_cfg(name), TRAFFIC, 3)
    n = 3 * t.block + 100
    ts, cols = t.stream(n)
    assert np.all(np.diff(ts) >= 0)
    assert ts[-1] - ts[0] >= 3 * (ts[t.block - 1] - ts[0])
    for k, v in t.cols.items():
        np.testing.assert_array_equal(cols[k][t.block:2 * t.block], v)
    # a chunk taken at any global index is that slice of the stream
    g0 = 2 * t.block + 64
    np.testing.assert_array_equal(t.timestamps(g0, g0 + 32),
                                  ts[g0:g0 + 32])
    for k, v in t.rows(g0, g0 + 32).items():
        np.testing.assert_array_equal(v, cols[k][g0:g0 + 32])


@pytest.mark.parametrize("seed", [0, -1, 2**31 + 1, 2**70])
def test_any_whole_seed(seed):
    gen.Traffic(_cfg(CONFIGS[0]), TRAFFIC, seed)


def test_event_time_column_is_the_timestamp():
    cfg = {"columns": [{"name": "k", "type": "int", "dist": "uniform_int",
                        "low": 0, "high": 7},
                       {"name": "t", "type": "long", "dist": "event_time"}],
           "time": {"events_per_ms": 4}}
    t = gen.Traffic(cfg, TRAFFIC, 1)
    assert set(t.cols) == {"k"}
    ts, cols = t.stream(5000)
    np.testing.assert_array_equal(cols["t"], ts)
    np.testing.assert_array_equal(
        ts, gen.TS0 + np.arange(5000, dtype=np.int64) // 4)


def test_labels_from_a_format_or_a_list():
    assert gen.labels_for({"labels": "ad-{:04d}", "high": 3}) == \
        ["ad-0000", "ad-0001", "ad-0002"]
    assert gen.labels_for({"labels": ["view", "click"], "high": 2}) == \
        ["view", "click"]


def test_preload_rows_hold_index_over_div():
    rows = gen.preload_rows({"rows": 25, "columns": [
        {"name": "ad", "div": 1}, {"name": "campaign", "div": 10}]})
    np.testing.assert_array_equal(rows["ad"], np.arange(25))
    np.testing.assert_array_equal(rows["campaign"], np.arange(25) // 10)

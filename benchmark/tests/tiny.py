"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the
benchmark's own tests. Only sizes change: the app, the columns' kinds,
the loop and the comparison stay as the cell has them."""
from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import drive, spec  # noqa: E402

# @cap sizes of the cell, and what holds the tiny traffic instead
SIZES = {"'32768'": "'8192'", "'65536'": "'1024'"}


def tiny_cell(name: str):
    cell = spec.Cell(spec.benchmark(), name)
    cell.traffic = {**cell.traffic, "block_events": 8192,
                    "chunk_rows": 1024, "warm_chunks": 2}
    cfg = dict(cell.config)
    for big, small in SIZES.items():
        cfg["app"] = cfg["app"].replace(big, small)
    # event time paced so a 10 s window closes every 2,000 events, a chunk
    # crosses a window's boundary, as the cell's chunks do, and an event
    # falls on each boundary, where the control's window errs
    cfg["time"] = {"ms_per_event": 5}
    cell.config = cfg
    return cell


def run_tiny(cell, seed: int = 2**31 + 5, seconds: float = 1.0,
             trace: bool = False):
    return drive.execute(cell, seed, seconds, trace, time.perf_counter())

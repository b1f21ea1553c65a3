"""The one traffic generator: a configuration's columns, drawn from the
seed, replayed in cycles with event time running on.

A configuration file states the stream's columns in draw order, each
with its distribution, and how event time advances with the event's
global index (``events_per_ms`` or ``ms_per_event``). One block of
``block_events`` events is drawn during set-up; the stream is that block
repeated, event ``g`` carrying the block's row ``g % block`` and the
timestamp of index ``g``. A column with ``"dist": "event_time"`` carries
the event's own timestamp and draws nothing.

A string column draws label indices; its labels are a format string of
the index (``"ad-{:04d}"``, ``high`` of them) or a list.

A configuration may also name rows sent once in set-up, before the
stream (``preload``): a table's contents, row ``i`` of a column holding
label ``i // div``.
"""
from __future__ import annotations

import numpy as np

TS0 = 1_700_000_000_000

_DTYPES = {"int": np.int32, "long": np.int64, "float": np.float32,
           "double": np.float64}


def rng_for(seed: int) -> np.random.Generator:
    # seeds may be any whole number, negative or past 64 bits
    return np.random.default_rng(int(seed) % (1 << 64))


def labels_for(spec: dict) -> list:
    if isinstance(spec["labels"], list):
        return list(spec["labels"])
    return [spec["labels"].format(i) for i in range(spec["high"])]


def _draw(rng, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "uniform_int":
        out = rng.integers(spec["low"], spec["high"], n, dtype=np.int64)
    elif dist == "uniform":
        out = rng.uniform(spec["low"], spec["high"], n)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if spec["type"] == "string":
        return out.astype(np.int64)
    return out.astype(_DTYPES[spec["type"]])


def preload_rows(pre: dict) -> dict:
    """Label indices of a preload's columns: row i holds i // div."""
    i = np.arange(int(pre["rows"]), dtype=np.int64)
    return {c["name"]: i // int(c.get("div", 1)) for c in pre["columns"]}


class Traffic:
    """The block drawn from the seed and the stream replayed from it."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.specs = config["columns"]
        self.time = config["time"]
        self.block = int(traffic["block_events"])
        rng = rng_for(seed)
        self.cols = {s["name"]: _draw(rng, s, self.block)
                     for s in self.specs if s["dist"] != "event_time"}
        self.labels = {s["name"]: labels_for(s) for s in self.specs
                       if s["type"] == "string"}

    def timestamps(self, g0: int, g1: int) -> np.ndarray:
        g = np.arange(g0, g1, dtype=np.int64)
        if "events_per_ms" in self.time:
            return TS0 + g // int(self.time["events_per_ms"])
        return TS0 + g * int(self.time["ms_per_event"])

    def rows(self, g0: int, g1: int) -> dict:
        """Column values of events [g0, g1): the block, tiled."""
        idx = np.arange(g0, g1, dtype=np.int64) % self.block
        out = {}
        for s in self.specs:
            if s["dist"] == "event_time":
                out[s["name"]] = self.timestamps(g0, g1)
            else:
                out[s["name"]] = self.cols[s["name"]][idx]
        return out

    def stream(self, n: int):
        """(ts, cols) of the first ``n`` events, as the reference reads
        them: string columns as label indices."""
        return self.timestamps(0, n), self.rows(0, n)

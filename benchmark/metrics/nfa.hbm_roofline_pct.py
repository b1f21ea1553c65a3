"""nfa.hbm_roofline_pct: the pattern step's share of the chip's HBM
roofline, in %. Bytes are a floor any implementation moves, counted
from the configuration alone (``floor_bytes``): each step reads every
live row at its start and writes every live row at its end, at
``row_bytes`` each, and reads each event once, at ``event_bytes``. The
whole run's live-row counters (``nfa_live_in`` + ``nfa_live_out``,
summed over steps, warm chunks included) give bytes per event; times
the traced sub-window's events, over peak bandwidth, over the traced
device-busy time. Since the bytes are a floor, the share cannot pass
100% unless the step moved less than the table it must read."""
import re

# HBM bandwidth per chip by device kind (Google Cloud documentation,
# "TPU v5e": 819 GB/s)
PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}

WIDTH = {"int": 4, "long": 8, "float": 4, "double": 8, "string": 4,
         "bool": 1}
TS_BYTES = 8


def row_bytes(config: dict) -> int:
    """A pending row: per state, the stream's declared columns and a
    timestamp; and the row's first-capture time (ts0)."""
    states = len(re.findall(r"\be\d+\s*=", config["app"]))
    return states * (event_bytes(config)) + TS_BYTES


def event_bytes(config: dict) -> int:
    return sum(WIDTH[c["type"]] for c in config["columns"]) + TS_BYTES


def floor_bytes(config: dict, stats: dict) -> int:
    live = sum(s.get("nfa_live_in", 0) + s.get("nfa_live_out", 0)
               for s in stats.values())
    events = sum(s.get("nfa_events", 0) for s in stats.values())
    return live * row_bytes(config) + events * event_bytes(config)


def read(run):
    tr = run.traced
    events = sum(s.get("nfa_events", 0) for s in run.stats.values())
    if tr is None or not events or not tr.events or \
            not tr.device.devices():
        return None
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BYTES_PER_S:
        return None          # no peak known: the metric is left out
    busy_s = tr.device.busy_total_ns() / 1e9
    moved = floor_bytes(run.cell.config, run.stats) / events * tr.events
    return 100.0 * moved / PEAK_BYTES_PER_S[kind] / busy_s

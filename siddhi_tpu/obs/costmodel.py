"""Pipeline cost profiler: per-step device-time attribution, bottleneck
ranking, and a persisted cost table the DAG optimizer can consume.

PR 6 gave the runtime *counters* (what flowed where); this module
answers *which step is eating the device time*. The measurement model
follows the DETAIL-latency lesson (core/stats.py): on an async device
pipeline the only honest per-step wall/device number comes from a
``block_until_ready`` around the dispatched step — which serializes the
pipeline — so the profiler samples: every Nth chunk per cost center
(``SIDDHI_TPU_COST_EVERY``, default 64, same stride pattern as
``SIDDHI_TPU_LAT_EVERY``; the first chunk always samples so short runs
still report). The sync lives on the *sampled branch only* — the
host-sync-in-loop lint rule (extended to ``jax.block_until_ready``)
guards the recording paths, and profiling changes ZERO jit options, so
persistent compile-cache keys stay stable (docs/compile_cache.md).

Cost centers mirror the dispatch units the runtime actually executes —
an XLA program per dispatch, never finer:

- ``query/<q>``          one plain query step
- ``chain/<q1+q2+...>``  one fused insert-into segment (the segment IS
                         one XLA program; the per-member split is in a
                         device profile, whose op names carry each
                         member's ``<q>/<OpClass>`` scope — members are
                         listed in the report instead)
- ``join/<q>.left|right[grid|probe]``  one join side step; the suffix
                         names the kernel that ran (the [B,W] broadcast
                         grid or the banded searchsorted probe — the
                         planner's cost-table consultation and
                         tools/profile_report.py read it back)
- ``pattern/<q>.<sid>``  one NFA stream step; ``pattern/<q>.timer`` the
                         absent-deadline timer step
- ``partition/<name>``   one K-vmapped partition block step

Samples accumulate into registry histograms
(``siddhi.<app>.query.<center>.step_ms`` /
``siddhi.<app>.partition.<name>.step_ms``) so ``/metrics`` scrapes and
reporters see the same numbers, and ``runtime.cost_report()`` rolls
them up into a ranked table (ms/event, share of total, queue-depth
trend -> bottleneck verdict). ``runtime.cost_save()`` persists the
table to ``<state_dir()>/costs.json`` (``$SIDDHI_TPU_STATE_DIR``, else
``<checkout>/.siddhi_state``; never the compile cache), keyed
``<kind>/<name>`` in the compile-spec key style — the measured
per-segment costs ROADMAP item 5's cost-aware plan optimizer needs.
"""
from __future__ import annotations

import collections
import json
import math
import os
import threading
import time
from typing import Optional

EVERY_ENV = "SIDDHI_TPU_COST_EVERY"
ENABLE_ENV = "SIDDHI_TPU_COST_PROFILE"
DEFAULT_EVERY = 64

# bounded per-center reservoir for percentile rollups (same windowed
# model as obs/metrics.Histogram)
SAMPLE_CAP = 2048
# queue-depth history per @Async stream (trend detection)
QUEUE_CAP = 64


STATE_DIR_ENV = "SIDDHI_TPU_STATE_DIR"


def state_dir() -> str:
    """Where measured run state lives — the cost table and SLO flight
    records: $SIDDHI_TPU_STATE_DIR, else <checkout>/.siddhi_state. Kept
    apart from the compile cache: a cache directory shared between runs
    must not carry one run's cost table into another run's plan."""
    return os.environ.get(STATE_DIR_ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".siddhi_state")


def default_costs_path() -> str:
    return os.path.join(state_dir(), "costs.json")


class _Probe:
    """One sampled step timing: created right before the dispatch,
    ``done(rows=...)`` after the caller's sampled-branch
    ``block_until_ready``. ``cap`` (the dispatched chunk capacity, when
    the caller passes it) lands the sample in an additional
    per-capacity center ``<kind>/<name>@<cap>`` — the plan optimizer's
    chunk-size evidence (plan/optimizer.py)."""

    __slots__ = ("profiler", "key", "t0", "cap")

    def __init__(self, profiler: "CostProfiler", key: tuple,
                 cap: Optional[int] = None):
        self.profiler = profiler
        self.key = key
        self.cap = cap
        self.t0 = time.perf_counter()

    def done(self, rows: int = 0) -> None:
        dt_ms = (time.perf_counter() - self.t0) * 1000.0
        self.profiler.record(self.key, dt_ms, rows, cap=self.cap)


class _Center:
    """Accumulated cost of one dispatch unit."""

    __slots__ = ("kind", "name", "wall_ms", "events", "samples", "ms")

    def __init__(self, kind: str, name: str):
        self.kind = kind
        self.name = name
        self.wall_ms = 0.0
        self.events = 0
        self.samples = 0
        self.ms: list[float] = []

    def add(self, dt_ms: float, rows: int) -> None:
        self.wall_ms += dt_ms
        self.events += rows
        self.samples += 1
        if len(self.ms) >= SAMPLE_CAP:
            del self.ms[: SAMPLE_CAP // 2]
        self.ms.append(dt_ms)

    def percentiles(self) -> dict:
        s = sorted(self.ms)
        n = len(s)
        if not n:
            return {}
        return {"p50_ms": round(s[n // 2], 3),
                "p95_ms": round(s[min(n - 1, (n * 95) // 100)], 3),
                "p99_ms": round(s[min(n - 1, (n * 99) // 100)], 3)}


class CostProfiler:
    """Per-app sampled synchronous step timing (see module docstring).

    Hot-path contract: when disabled (the default) every dispatch site
    pays ONE attribute check (``app.cost.enabled``) — no locks, no
    syncs, no allocation. When enabled, every chunk bumps a per-center
    counter and every Nth chunk times the step synchronously."""

    def __init__(self, app):
        self.app = app
        self.enabled = os.environ.get(ENABLE_ENV, "") == "1"
        self.every = max(
            1, int(os.environ.get(EVERY_ENV, "") or DEFAULT_EVERY))
        self._lock = threading.Lock()
        self._counters: dict[tuple, int] = {}
        self._centers: dict[tuple, _Center] = {}
        # per-capacity sub-centers keyed (kind, name, cap): persisted as
        # `<kind>/<name>@<cap>` (the optimizer's chunk-size evidence)
        # but EXCLUDED from report() so shares still sum to ~100
        self._cap_centers: dict[tuple, _Center] = {}
        self._queues: dict[str, collections.deque] = {}
        # stale centers the optimizer's load dropped (absent from the
        # current plan — load_costs_for); surfaced in statistics()
        self.stale_centers: Optional[int] = None

    @property
    def samples(self) -> int:
        with self._lock:
            return sum(c.samples for c in self._centers.values())

    # -- lifecycle -------------------------------------------------------
    def start(self, every: Optional[int] = None) -> None:
        """Enable sampled profiling (clears previously accumulated
        costs; ``every=1`` times every chunk — bench's post-measurement
        breakdown pass)."""
        with self._lock:
            self._counters.clear()
            self._centers.clear()
            self._cap_centers.clear()
            self._queues.clear()
        if every is not None:
            self.every = max(1, int(every))
        self.enabled = True

    def stop(self) -> None:
        self.enabled = False

    # -- recording (hot path, only when enabled) -------------------------
    def probe(self, kind: str, name: str,
              cap: Optional[int] = None) -> Optional[_Probe]:
        """Return a timing probe on sampled chunks, else None. Callers
        gate on ``self.enabled`` first so the disabled path never gets
        here. ``cap`` additionally attributes the sample to a
        per-capacity center (see _Probe)."""
        if not self.enabled:
            return None
        key = (kind, name)
        with self._lock:
            n = self._counters.get(key, 0)
            self._counters[key] = n + 1
        if n % self.every:
            return None
        return _Probe(self, key, cap=cap)

    def record(self, key: tuple, dt_ms: float, rows: int,
               cap: Optional[int] = None) -> None:
        kind, name = key
        with self._lock:
            c = self._centers.get(key)
            if c is None:
                c = self._centers[key] = _Center(kind, name)
            c.add(dt_ms, rows)
            if cap is not None:
                ck = (kind, name, int(cap))
                cc = self._cap_centers.get(ck)
                if cc is None:
                    cc = self._cap_centers[ck] = _Center(
                        kind, f"{name}@{int(cap)}")
                cc.add(dt_ms, rows)
            # queue-depth samples ride along: backpressure building up
            # behind a step is the first-class bottleneck signal
            for sid, j in self.app.junctions.items():
                q = getattr(j, "_queue", None)
                if j.async_conf is not None and q is not None:
                    dq = self._queues.get(sid)
                    if dq is None:
                        dq = self._queues[sid] = collections.deque(
                            maxlen=QUEUE_CAP)
                    dq.append(q.qsize())
        # registry histogram: scrapes/reporters see the same samples
        self.app.metrics.histogram(self._metric_name(kind, name)) \
            .observe(round(dt_ms, 4))

    def _metric_name(self, kind: str, name: str) -> str:
        if kind == "partition":
            return f"siddhi.{self.app.name}.partition.{name}.step_ms"
        if kind == "fanout":
            return f"siddhi.{self.app.name}.fanout.{name}.step_ms"
        return f"siddhi.{self.app.name}.query.{name}.step_ms"

    # -- rollup ----------------------------------------------------------
    def _queue_trends(self) -> dict:
        out = {}
        for sid, dq in self._queues.items():
            hist = list(dq)
            if len(hist) < 6:
                continue
            third = max(1, len(hist) // 3)
            head = sum(hist[:third]) / third
            tail = sum(hist[-third:]) / third
            if tail > head * 1.5 + 1:
                trend = "rising"
            elif head > tail * 1.5 + 1:
                trend = "falling"
            else:
                trend = "stable"
            out[sid] = {"depth": hist[-1], "trend": trend,
                        "samples": len(hist)}
        return out

    def report(self) -> dict:
        """Ranked cost table: one row per center, ordered by total
        measured wall ms; ``share_pct`` values sum to ~100."""
        with self._lock:
            centers = sorted(self._centers.values(),
                             key=lambda c: -c.wall_ms)
        total_ms = sum(c.wall_ms for c in centers)
        steps = []
        for c in centers:
            row = {"step": f"{c.kind}/{c.name}",
                   "kind": c.kind,
                   "ms_total": round(c.wall_ms, 3),
                   "events": c.events,
                   "samples": c.samples,
                   "share_pct": round(100.0 * c.wall_ms / total_ms, 2)
                   if total_ms else 0.0,
                   **c.percentiles()}
            if c.events:
                row["ms_per_event"] = round(c.wall_ms / c.events, 6)
                row["events_per_s"] = round(
                    c.events / (c.wall_ms / 1000.0), 1) \
                    if c.wall_ms else math.inf
            if c.kind == "chain":
                row["members"] = c.name.split("+")
            elif c.kind == "fanout":
                row["junction"] = c.name
            steps.append(row)
        queues = self._queue_trends()
        report = {"profiling": {"enabled": self.enabled,
                                "every": self.every,
                                "samples": sum(c.samples
                                               for c in centers)},
                  "total_ms": round(total_ms, 3),
                  "steps": steps}
        if self.stale_centers is not None:
            # centers the optimizer's staleness guard dropped at load
            # (renamed/deleted plan units lingering in costs.json)
            report["stale_centers"] = self.stale_centers
        if queues:
            report["queues"] = queues
        if steps:
            top = steps[0]
            rising = [sid for sid, q in queues.items()
                      if q["trend"] == "rising"]
            verdict = (f"{top['step']} dominates measured step time "
                       f"({top['share_pct']}%)")
            if rising:
                verdict += ("; queue depth rising on "
                            + ", ".join(sorted(rising))
                            + " — upstream outpaces the bottleneck "
                            "(backpressure)")
            report["bottleneck"] = {"step": top["step"],
                                    "share_pct": top["share_pct"],
                                    "verdict": verdict}
        return report

    # -- Chrome trace annotations ---------------------------------------
    def trace_annotations(self) -> dict:
        """``{span_name: {cost_*: ...}}`` merged into ``trace_export``
        events so Perfetto rows carry measured device-time context.
        Join sides and pattern streams aggregate onto their query's
        ``step/<q>`` span (those paths dispatch per side/stream but the
        trace names the query)."""
        with self._lock:
            centers = list(self._centers.values())
        agg: dict[str, list] = {}
        for c in centers:
            if c.kind == "query":
                span = f"step/{c.name}"
            elif c.kind == "chain":
                span = f"chain/{c.name}"
            elif c.kind == "fanout":
                span = f"fanout/{c.name}"
            elif c.kind == "partition":
                span = f"partition/{c.name}"
            else:  # join/pattern: <q>.<side|sid|timer> -> step/<q>
                span = f"step/{c.name.rsplit('.', 1)[0]}"
            agg.setdefault(span, []).append(c)
        out = {}
        for span, cs in agg.items():
            ms = sum(c.wall_ms for c in cs)
            ev = sum(c.events for c in cs)
            ann = {"cost_ms_total": round(ms, 3),
                   "cost_samples": sum(c.samples for c in cs)}
            if ev:
                ann["cost_ms_per_event"] = round(ms / ev, 6)
            out[span] = ann
        return out

    # -- persistence ------------------------------------------------------
    def table(self) -> dict:
        """Flat ``{<kind>/<name>: costs}`` table (compile-spec key
        style) for persistence / the DAG optimizer. Per-capacity
        sub-centers ride along as ``<kind>/<name>@<cap>`` keys — the
        optimizer's chunk-size evidence."""
        with self._lock:
            centers = list(self._centers.values()) + \
                list(self._cap_centers.values())
        out = {}
        for c in centers:
            entry = {"ms_total": round(c.wall_ms, 3),
                     "events": c.events,
                     "samples": c.samples,
                     **c.percentiles()}
            if c.events:
                entry["ms_per_event"] = round(c.wall_ms / c.events, 6)
            out[f"{c.kind}/{c.name}"] = entry
        return out

    def save(self, path: Optional[str] = None) -> str:
        """Merge this app's cost table into the persisted
        ``costs.json`` next to the compile cache (tmp+rename, same
        atomicity contract as the filesystem error store).

        The merged table is PRUNED against the app's current plan
        (``SiddhiAppRuntime._cost_center_valid``): centers from
        renamed/deleted queries would otherwise linger forever and feed
        the plan optimizer stale evidence. Other apps' entries are left
        untouched. Returns the path written."""
        path = path or default_costs_path()
        table = self.table()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        existing: dict = {}
        try:
            with open(path) as f:
                existing = json.load(f)
        except (OSError, ValueError):
            existing = {}
        app_tbl = existing.setdefault(self.app.name, {})
        app_tbl.update(table)
        valid = getattr(self.app, "_cost_center_valid", None)
        if valid is not None:
            existing[self.app.name] = {
                k: v for k, v in app_tbl.items() if valid(k)}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(existing, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path


def load_costs(path: Optional[str] = None) -> dict:
    """Read the persisted cost table (``{app: {<kind>/<name>: costs}}``);
    missing/corrupt files read as empty — costs are advisory."""
    path = path or default_costs_path()
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def load_costs_for(app: str, valid_center,
                   path: Optional[str] = None) -> tuple[dict, int]:
    """One app's cost table through the staleness guard: centers whose
    keys ``valid_center`` rejects (plan units that no longer exist —
    renamed queries, dropped junctions) are ignored rather than fed to
    the optimizer, and counted. Returns ``(table, stale_count)``; the
    count is surfaced in ``statistics()['cost']['stale_centers']``."""
    tbl = load_costs(path).get(app) or {}
    kept = {k: v for k, v in tbl.items() if valid_center(k)}
    return kept, len(tbl) - len(kept)

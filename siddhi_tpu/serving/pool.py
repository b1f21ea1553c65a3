"""TenantPool: vmapped multi-tenant execution of one compiled template.

One pool = one template (+ shared structural bindings) = ONE compiled
program set. Per-tenant state pytrees stack on a leading tenant axis;
`jax.vmap` over the standard operator-class traces advances EVERY
tenant of the template in a single dispatch. Four operator classes
pool (docs/serving.md "Poolable operator classes"):

- **chain** — filter/window/projection insert-into chains over the
  `_chain_body` trace (the original pooled class);
- **pattern** — NFA one-hot transition scans: the pending-match table
  plus selector states stack per slot, one vmapped step per input
  stream (ops/nfa.py), plus a vmapped absent-deadline timer step;
- **join** — banded equi-join probes: both side chains, the selector
  states and the join-cap overflow counter ride ONE donated state dict
  per query (the opposite side is read inside and returned unchanged,
  which keeps whole-dict donation safe), one vmapped step per side;
- **aggregation** — incremental-aggregation bucket tables stack per
  slot; `materialize_tenant` slices one tenant's buckets out.

Templates may name N ingest streams (patterns/joins consume several);
`send(..., stream=)` routes per stream and every fair round ships ONE
packed (slots, total) uint8 buffer PER INGEST STREAM — the PR 19
zero-copy columnar encode, widened round-wide so all slots share one
encoding tuple — slot-routed on device (`SIDDHI_TPU_POOL_PACKED=0`
falls back to the stacked EventBatch transfer). Tenant `${name:type}`
parameters ride the stacked operator state (ops/expr.py tparam
machinery), so tenant add/remove is pure slot assignment —
`.at[slot].set` writes, no retrace, no recompile (counting-jit guarded
in tests/test_serving.py).

Capacity model (`@app:cap(tenants=..., tenant.state.kb=...)` dial or
constructor knobs):

- the slot axis starts small and GROWS BY DOUBLING when tenants exceed
  it (a doubling is a recompile — amortized log2(max) compiles over the
  pool's lifetime; steady-state churn compiles nothing);
- admission control rejects deploys past `max_tenants` or past the
  per-tenant state quota with a reason string the service maps to
  HTTP 429;
- ingest is FAIR ROUND-ROBIN: each tenant contributes at most
  `batch_max` rows per dispatch round (the @Async batch.size.max dial,
  tenant-aware), so one hot tenant cannot starve the rest — its backlog
  just spans more rounds, bounded by `pending_cap` backpressure.

Isolation:

- `statistics()` / the metrics registry namespace per-tenant gauges as
  ``siddhi.<pool>.tenant.<id>.*``, collected with ONE device_get per
  pool (O(templates), not O(tenants) device reads);
- a tenant callback failure routes the events to THAT tenant's error
  store partition (``<pool>/tenant/<id>``, PR 2 error store) and never
  touches other tenants' delivery;
- `snapshot_tenant` / `restore_tenant` slice exactly one index of the
  tenant axis — other tenants' state stays bit-identical.
"""
from __future__ import annotations

import hashlib
import logging
import math
import os
import threading
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.plan_rules import check_template_bindings
from ..core.event import EXPIRED, EventBatch, rows_from_batch
from ..core.runtime import (BATCH_BUCKETS, InsertIntoStreamHandler,
                            QueryRuntime, SiddhiAppRuntime, _as_current,
                            _chain_body, _donate, _fresh_device,
                            bucket_capacity)
from ..core.stream import Event
from ..core.types import AttrType, GLOBAL_STRINGS, np_dtype
from ..lang import ast as A
from ..obs.slo import (EVERY_ENV as _SLO_EVERY_ENV, FlightRecorder,
                       SLOEngine, config_from_annotation as _slo_from_ann,
                       objective_from_dials)
from ..ops.expr import CompileError
from .qos import PoolQoS

QOS_ENV = "SIDDHI_TPU_QOS"   # "0" kills the whole QoS layer
POOL_PACKED_ENV = "SIDDHI_TPU_POOL_PACKED"  # "0" = stacked EventBatch

# _kind slug -> the operator-class name used in quota accounting and
# the 429 per-class state-bytes breakdown (docs/serving.md matrix)
_CLASS_NAMES = {"chain": "chain", "pattern": "pattern", "join": "join",
                "agg": "aggregation"}

log = logging.getLogger("siddhi_tpu.serving")

_DEFAULT_MAX_TENANTS = 1024
_DEFAULT_BATCH_MAX = 1024
_DEFAULT_PENDING_CAP = 1 << 20   # rows buffered per tenant before 429
# SLO sampling stride for pool rounds: one fair round already advances
# EVERY tenant, so rounds are far rarer than per-tenant chunks — an 8x
# stride keeps histograms dense while the sampled block_until_ready
# serializes at most 1-in-8 rounds (SIDDHI_TPU_SLO_EVERY overrides)
_POOL_DEFAULT_EVERY = 8

_TENANT_HELP = {
    "emitted": "events emitted for one tenant across its queries",
    "pending": "rows queued for one tenant awaiting a fair round",
    "errors": "events routed to one tenant's error-store partition",
}


class AdmissionError(Exception):
    """Deploy/ingest rejected by admission control (HTTP 429 at the
    front door); `.reason` names the exhausted resource and
    `.saturation` carries the machine-readable cause (which resource,
    current pressure signals, a Retry-After estimate) so clients and
    autoscalers don't have to parse prose (docs/serving.md)."""

    def __init__(self, reason: str, saturation: Optional[dict] = None):
        super().__init__(reason)
        self.reason = reason
        self.saturation = saturation or {}


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _tree_zeros(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(jnp.shape(x), jnp.result_type(x)), tree)


class TenantPool:
    """Stacked/vmapped runtime for every tenant of one template."""

    def __init__(self, template, shared: Optional[dict] = None,
                 manager=None, name: Optional[str] = None,
                 slots: int = 8, max_tenants: Optional[int] = None,
                 state_quota_bytes: Optional[int] = None,
                 batch_max: Optional[int] = None,
                 pending_cap: int = _DEFAULT_PENDING_CAP,
                 slo: Optional[dict] = None,
                 qos: Optional[dict] = None,
                 mesh=None,
                 device_round_cap: Optional[int] = None):
        """``mesh``: optional ``jax.sharding.Mesh`` — the tenant slot
        axis then shards over its first axis (1/n of the slots per
        device, parallel/sharding.py POOL_STATE_RULES), ingest rounds
        place the stacked batch the same way, and admission control
        accounts per-device slot budgets (docs/serving.md).

        ``device_round_cap``: optional per-DEVICE row budget per fair
        round on a mesh (None = unlimited, the legacy round shape).
        When a device's tenants together hit the cap, later tenants on
        that device wait for the next round — the signal the SLO-driven
        rebalancer (serving/rebalance.py) uses to move a colocated
        victim off a saturated device."""
        from ..core.manager import SiddhiManager
        from ..obs.metrics import MetricsRegistry
        self.template = template
        self.shared = dict(shared or {})
        self.name = name or f"pool_{template.key[:8]}"
        self.manager = manager or SiddhiManager()
        app_ast = template.instantiate(shared=self.shared,
                                       app_name=self.name)
        # prototype runtime: planned once, NEVER started — the pool
        # dispatches vmapped variants of its operator chains and its
        # CompileService carries the pool's one-program-set telemetry
        self.proto = SiddhiAppRuntime(app_ast, manager=None)
        # route the prototype's store lookups through the pool's
        # manager: tenant error partitions and pool checkpoints must
        # live in the SHARED stores so a fresh pool built after a crash
        # (resilience/supervisor.py PoolCheckpointSupervisor) finds them
        self.proto.manager = self.manager
        self._plan_topology()
        self._check_poolable()

        cap_ann = A.find_annotation(app_ast.annotations, "cap")
        if max_tenants is None:
            max_tenants = int(cap_ann.element("tenants")
                              or _DEFAULT_MAX_TENANTS) \
                if cap_ann else _DEFAULT_MAX_TENANTS
        if state_quota_bytes is None and cap_ann is not None:
            kb = cap_ann.element("tenant.state.kb")
            if kb is not None:
                state_quota_bytes = int(kb) * 1024 * max_tenants
        self.max_tenants = int(max_tenants)
        self.state_quota_bytes = state_quota_bytes
        if batch_max is None:
            batch_max = _DEFAULT_BATCH_MAX
        # fair-share row cap per tenant per round; bucketed so dispatch
        # capacities land on warm jit cache keys, and capped by the
        # sort-heavy step limits of the template's queries
        for q in self.proto.queries.values():
            if q.max_step_capacity is not None:
                batch_max = min(batch_max, q.max_step_capacity)
        self.batch_max = bucket_capacity(int(batch_max))
        self.pending_cap = int(pending_cap)

        # -- QoS (serving/qos.py; docs/serving.md "QoS dials") ------------
        # Dials merge constructor `qos={...}` over `@app:cap(...)`
        # elements (the deployment's word wins, like slo=). With the
        # SIDDHI_TPU_QOS=0 kill the layer is None and every call site
        # below runs the exact pre-QoS path; with no dials configured
        # the DRR plan is bit-identical to the fixed batch_max round.
        qos_dials: dict = {}
        if cap_ann is not None:
            for el, key in (("rate.eps", "rate_eps"),
                            ("rate.burst", "rate_burst"),
                            ("breaker.failures", "breaker_failures"),
                            ("breaker.reset.ms", "breaker_reset_ms"),
                            ("qos.max.defer", "max_defer")):
                v = cap_ann.element(el)
                if v is not None:
                    qos_dials[key] = float(v) if "rate" in el else int(v)
        qos_dials.update({k: v for k, v in dict(qos or {}).items()
                          if v is not None})
        if os.environ.get(QOS_ENV, "1") == "0":
            self._qos: Optional[PoolQoS] = None
        else:
            try:
                self._qos = PoolQoS(
                    qos_dials,
                    on_transition=self._on_breaker_transition)
            except ValueError as e:
                raise CompileError(f"pool '{self.name}' qos: {e}")

        # -- mesh (slot-axis sharding over devices) -----------------------
        self.mesh = mesh
        if mesh is not None:
            from ..parallel import sharding as _sh
            self.mesh_axis = mesh.axis_names[0]
            self.n_devices = int(mesh.shape[self.mesh_axis])
            self._sharding = _sh
        else:
            self.mesh_axis = None
            self.n_devices = 1
            self._sharding = None
        self.slots = _pow2(max(1, self.n_devices,
                               min(int(slots), self.max_tenants)))
        self._slot_cap = max(_pow2(self.max_tenants), self.n_devices)
        if mesh is not None:
            # pow2 slot axes divide pow2 meshes; anything else is a
            # config error, caught at pool build not first dispatch
            self._sharding.check_divisible(self.slots, mesh,
                                           f"pool '{self.name}' slots")
        # stacked per-query state: leading axis = tenant slot
        self._states = {qn: self._stack_init(qn, self.slots)
                        for qn in self._order}
        self._emitted = {qn: jnp.zeros((self.slots,), jnp.int64)
                         for qn in self._order}
        self._rows_per_device = [0] * self.n_devices
        self._collect_ms_per_device = [0.0] * self.n_devices
        if mesh is not None:
            self._place_state()   # initial slot-axis placement
        # per-tenant state bytes (quota accounting): one slot's slice of
        # every query state plus its emitted counter, accounted PER
        # OPERATOR CLASS — a join-heavy tenant's window buffers and an
        # aggregation's bucket tables all count against tenant.state.kb,
        # and the 429 payload carries the breakdown (docs/serving.md)
        self.state_bytes_by_class: dict[str, int] = {}
        total_bytes = 0
        for qn in self._order:
            b = 8 + sum(leaf.nbytes // self.slots for leaf in
                        jax.tree_util.tree_leaves(self._states[qn]))
            cls = _CLASS_NAMES[self._kind[qn]]
            self.state_bytes_by_class[cls] = \
                self.state_bytes_by_class.get(cls, 0) + b
            total_bytes += b
        self.state_bytes_per_tenant = total_bytes

        self._tenants: dict[str, int] = {}
        self._bindings: dict[str, dict] = {}      # tid -> bound values
        self._tenant_qos_raw: dict[str, dict] = {}  # tid -> qos dials
        self._free = list(range(self.slots - 1, -1, -1))
        # tid -> {ingest stream -> deque of (ts, cols, t_arrival)}
        self._pending: dict[str, dict] = {}
        self._pending_rows: dict[str, int] = {}
        # packed pool ingest (core/ingest.py): ONE sticky widen-only
        # encoder per ingest stream — all slots of a round share its
        # encoding tuple, so the whole round is ONE (slots, total)
        # uint8 device_put per stream (SIDDHI_TPU_POOL_PACKED=0 falls
        # back to the stacked EventBatch transfer)
        self._packed_on = os.environ.get(POOL_PACKED_ENV, "1") != "0"
        self._encoders: dict[str, object] = {}
        self._ingest_stats = {"transfers": 0, "rows": 0, "cells": 0,
                              "bytes": 0, "rounds": 0}
        self._callbacks: dict[str, list[Callable]] = {}
        self._error_counts: dict[str, int] = {}
        self.batch_callbacks: list[Callable] = []
        self._vsteps: dict = {}
        self._lock = threading.RLock()
        self._now = -(2 ** 62)
        self._rounds = 0
        self._dispatches = 0
        self._grows = 0
        self._warmed = False
        self._running = False
        self._worker: Optional[threading.Thread] = None
        self._work = threading.Condition(self._lock)
        self.metrics = MetricsRegistry()
        self.metrics.register_collector(
            lambda: self._collect_observability()[0])
        # -- SLO engine + saturation signals (obs/slo.py) -----------------
        # Always on for pools (per-tenant p99 is the ROADMAP item 2
        # deliverable); the latency OBJECTIVE is optional and comes from
        # the template's `@app:slo(...)` annotation or the constructor's
        # `slo={...}` dial (dial wins — it is the deployment's word).
        slo_dials = dict(slo or {})
        flight_dir = slo_dials.pop("flight_dir", None)
        objective = None
        if slo_dials:
            objective = objective_from_dials(slo_dials)
        else:
            slo_ann = A.find_annotation(app_ast.annotations, "slo")
            if slo_ann is not None:
                try:
                    objective = _slo_from_ann(slo_ann)
                except ValueError as e:
                    raise CompileError(str(e))
        every = objective.every if objective is not None and \
            objective.every else None
        if every is None:
            env = os.environ.get(_SLO_EVERY_ENV, "")
            every = int(env) if env else _POOL_DEFAULT_EVERY
        self.flight = FlightRecorder(
            self.name, dirpath=flight_dir,
            # every artifact carries {app, pool, plan_hash}: a PAGE
            # dump is attributable to the pool AND the template plan
            # that produced it (obs/slo.py identity contract)
            identity_fn=lambda: {"app": self.name, "pool": self.name,
                                 "plan_hash": self.plan_hash()})
        self.slo_engine = SLOEngine(
            self.name, objective=objective, every=every,
            recorder=self.flight, context_fn=self._flight_context)
        # admission-rejection saturation counters (host-side only)
        self._rejections: dict[str, int] = {}
        self._rejection_times: deque = deque(maxlen=512)
        self._last_pump_wall: Optional[float] = None
        self._round_ms_ema: Optional[float] = None
        # crash recovery bookkeeping (resilience/supervisor.py): the
        # supervisor registers itself here; restore() fills _recovery
        self._checkpoint_supervisor = None
        self._recovery: Optional[dict] = None
        # -- live migration / evacuation (serving/migrate.py;
        #    docs/serving.md "Live migration & rebalance") ---------------
        self.device_round_cap = int(device_round_cap) \
            if device_round_cap else None
        # tid -> {from/to slot+device, cause, parked deque, park_cap, ...}
        self._migrations: dict[str, dict] = {}
        self._migration_log: deque = deque(maxlen=64)
        self._migrations_done = 0
        self._rows_migrated = 0
        self._migration_pause_ms_last: Optional[float] = None
        self._lost_devices: set[int] = set()
        # victims of a lost device, awaiting evacuation: tid -> old slot
        # (their pending queues are RETAINED and drain after evacuation)
        self._lost_tenants: dict[str, int] = {}
        self._evacuations = 0
        self._last_evacuation_wall: Optional[float] = None
        # cached per-device placement + budget, re-derived on EVERY
        # slot-map change (_recompute_placement_locked) — the admission
        # staleness fix: a drained/evacuated device must stop 429-ing
        self._placement_counts: list = [0] * self.n_devices
        self._slot_budget = -(-self.max_tenants
                              // max(1, self.n_devices))

    # -- planning ---------------------------------------------------------

    def _plan_topology(self) -> None:
        """Derive the query wiring from the prototype's junction graph:
        N named ingest streams in, queries (and aggregations) in
        topological order, terminal streams (produced, never consumed)
        out. Every node gets a ``_kind`` (chain/pattern/join/agg) and a
        tuple of labeled inputs — chains one ``("*", sid)``, patterns
        one ``("s:<sid>", sid)`` per distinct engine stream, joins
        ``("L", sid)``/``("R", sid)`` — the label picks the vmapped
        step variant at dispatch."""
        from ..core.runtime import JoinQueryRuntime, PatternQueryRuntime
        p = self.proto
        self._q_in: dict[str, tuple] = {}
        self._q_out: dict[str, Optional[str]] = {}
        self._kind: dict[str, str] = {}
        self._aggs: dict[str, object] = {}
        produced: set[str] = set()
        consumers: dict[str, list[str]] = {}
        for qn, q in p.queries.items():
            if isinstance(q, PatternQueryRuntime):
                self._kind[qn] = "pattern"
                ins = tuple(
                    ("s:" + sid, sid) for sid in
                    sorted({s.stream_id for s in q.engine.slots}))
            elif isinstance(q, JoinQueryRuntime):
                self._kind[qn] = "join"
                ins = tuple(
                    (side, q.in_schemas[side].stream_id)
                    for side in ("L", "R")
                    if side not in q.side_tables)
            else:
                self._kind[qn] = "chain"
                ins = (("*", q.in_schema.stream_id),)
            self._q_in[qn] = ins
            for _lab, sid in ins:
                consumers.setdefault(sid, []).append(qn)
            out = None
            for h in q.output_handlers:
                if isinstance(h, InsertIntoStreamHandler):
                    out = h.junction.stream_id
                    produced.add(out)
            self._q_out[qn] = out
        for aid, ar in p.aggregations.items():
            if aid in self._q_in:
                raise CompileError(
                    f"aggregation '{aid}' collides with a query name")
            self._kind[aid] = "agg"
            self._aggs[aid] = ar
            self._q_in[aid] = (("*", ar.in_schema.stream_id),)
            self._q_out[aid] = None
            consumers.setdefault(ar.in_schema.stream_id, []).append(aid)
        ingest = sorted(sid for sid in consumers if sid not in produced)
        self._ingest_streams = ingest
        # topological order (BFS from the ingest streams; a node places
        # once ALL its labeled inputs are available)
        avail = set(ingest)
        order: list[str] = []
        remaining = {qn: {sid for _lab, sid in ins}
                     for qn, ins in self._q_in.items()}
        while remaining:
            placed = [qn for qn, sids in remaining.items()
                      if sids <= avail]
            if not placed:
                break   # unreachable/cyclic queries — poolability rejects
            for qn in sorted(placed):
                order.append(qn)
                remaining.pop(qn)
                if self._q_out[qn]:
                    avail.add(self._q_out[qn])
        self._order = order
        self._unreachable = sorted(remaining)
        self._terminal = sorted(
            sid for sid in produced if sid not in consumers)

    # classes that still cannot pool: (proto attr, what, why, nearest
    # poolable alternative) — each rejection names its reason AND the
    # closest construct that DOES pool (docs/serving.md matrix)
    _UNPOOLABLE = (
        ("partitions", "partitions",
         "partition state fans out per key value, not per tenant slot",
         "key by an attribute inside a pooled filter/window chain"),
        ("named_windows", "named windows",
         "a named window is one shared instance crossing query (and "
         "tenant) boundaries",
         "give each query its own window(...) inside the template"),
        ("tables", "tables",
         "table state is shared mutable storage updated by host-side "
         "index rebuilds",
         "model reference data as a windowed stream and join it"),
        ("record_tables", "@Store tables",
         "external-store I/O runs host callbacks per operation",
         "pre-join the store data into an ingest stream"),
        ("triggers", "triggers",
         "triggers fire on wall-clock schedules outside the fair round "
         "loop",
         "drive time with advance_time()/pump() rounds"),
    )

    def _check_poolable(self) -> None:
        p = self.proto
        problems = []
        for attr, what, why, alt in self._UNPOOLABLE:
            if getattr(p, attr):
                problems.append(f"{what} ({why}; nearest poolable "
                                f"alternative: {alt})")
        if p.sources or p.sinks:
            problems.append(
                "@source/@sink connectors (connectors own host I/O "
                "threads outside pool rounds; nearest poolable "
                "alternative: pool.send() and per-tenant callbacks at "
                "the service front door)")
        for qn, q in p.queries.items():
            if q.table_deps or getattr(q, "side_tables", None):
                problems.append(
                    f"query '{qn}' reads tables (shared mutable "
                    "storage; nearest poolable alternative: join "
                    "against a windowed stream)")
            elif self._q_out.get(qn) is None:
                problems.append(
                    f"query '{qn}' has a non-insert-into output "
                    "(nearest poolable alternative: insert into a "
                    "stream and attach per-tenant callbacks)")
        if not self._ingest_streams:
            problems.append("no ingest stream (every stream is "
                            "query-produced)")
        if self._unreachable:
            problems.append(
                f"unreachable queries {', '.join(self._unreachable)}")
        if problems:
            raise CompileError(
                f"template '{self.template.name}' is not poolable — "
                "vmapped tenant execution covers filter/window/"
                "projection chains, patterns, joins, and incremental "
                "aggregations over named ingest streams; "
                "found: " + "; ".join(problems))

    @property
    def ingest_stream(self) -> str:
        """First (often only) ingest stream — the single-stream
        compatibility surface (core/service.py rows endpoint)."""
        return self._ingest_streams[0]

    @property
    def ingest_streams(self) -> tuple:
        return tuple(self._ingest_streams)

    def _resolve_stream(self, stream: Optional[str]) -> str:
        if stream is None:
            if len(self._ingest_streams) == 1:
                return self._ingest_streams[0]
            raise ValueError(
                f"pool '{self.name}' has {len(self._ingest_streams)} "
                f"ingest streams {self._ingest_streams} — "
                "send(..., stream=) must name one")
        if stream not in self._ingest_streams:
            raise KeyError(
                f"'{stream}' is not an ingest stream of pool "
                f"'{self.name}' (ingest: {self._ingest_streams})")
        return stream

    # -- mesh placement (parallel/sharding.py) ----------------------------

    @property
    def slots_per_device(self) -> int:
        return self.slots // self.n_devices

    def _device_of_slot(self, slot: int) -> int:
        if self.mesh is None:
            return 0
        # host-side twin of the PartitionSpec placement — one shared
        # definition (parallel/sharding.py device_of_index) so the
        # migration/evacuation target math can never drift from the
        # rule-table layout
        return self._sharding.device_of_index(
            slot, self.slots, self.mesh, axis=self.mesh_axis)

    def _place_state(self) -> None:
        """Shard the stacked tenant states over the mesh's slot axis.
        Runs ONLY on initial build and slot-axis growth (the two events
        that change layout); `shard_pytree` skips leaves that are
        already placed, so even a redundant call transfers nothing
        (the dedupe contract, tests/test_mesh.py counts it)."""
        placed = self._sharding.shard_pytree(
            {"states": self._states, "emitted": self._emitted},
            self.mesh, self._sharding.POOL_STATE_RULES,
            axis=self.mesh_axis)
        self._states = placed["states"]
        self._emitted = placed["emitted"]

    def _place_batch(self, batch):
        """Stacked (slots, cap) round batch -> device(s): sharded over
        the slot axis on a mesh (each device receives ONLY its tenants'
        rows — one transfer either way)."""
        if self.mesh is None:
            return jax.device_put(batch)
        return self._sharding.place_leading(batch, self.mesh,
                                            axis=self.mesh_axis)

    def _device_loads_locked(self) -> list:
        """Tenants currently placed per device (host-side bookkeeping;
        re-entrant — callers already inside the RLock pay nothing,
        admission probes arriving lock-free get a consistent count)."""
        with self._lock:
            loads = [0] * self.n_devices
            for slot in self._tenants.values():
                loads[self._device_of_slot(slot)] += 1
            return loads

    def _recompute_placement_locked(self) -> None:
        """Re-derive the cached per-device placement counts AND the
        per-device slot budget. Called on EVERY slot-map change
        (add/remove/restore/migrate/evacuate/device-loss) — the
        admission-staleness fix: a device drained by removal or
        evacuation stops 429-ing traffic it can now accept, budgets
        split over the SURVIVING devices after a loss, and the 429
        payload's per-device placement reflects reality."""
        self._placement_counts = self._device_loads_locked()
        alive = self.n_devices - len(self._lost_devices)
        self._slot_budget = -(-self.max_tenants // max(1, alive))

    def _alive_devices_locked(self) -> list:
        return [d for d in range(self.n_devices)
                if d not in self._lost_devices]

    def _pick_slot(self) -> int:
        """Pop a free slot, mesh-aware: choose the slot on the device
        with the fewest placed tenants so the vmapped work stays
        balanced across the mesh (single-device pools keep LIFO order).
        Lost devices' slots never sit in ``_free`` (mark_device_lost
        strips them), so a degraded mesh places only on survivors."""
        if self.mesh is None:
            return self._free.pop()
        loads = self._device_loads_locked()
        best = min(range(len(self._free)),
                   key=lambda i: (loads[self._device_of_slot(
                       self._free[i])], -self._free[i]))
        return self._free.pop(best)

    # -- state stacking ---------------------------------------------------
    # Slot-state layout per operator class (docs/serving.md matrix):
    #   chain   -> tuple(op state, ...)                (the original)
    #   pattern -> {"nfa": pending-match table, "sel": tuple(op state)}
    #   join    -> {"sides": {"L": tuple, "R": tuple},
    #               "sel": tuple, "ovf": join-cap drop counter}
    #   agg     -> {duration: bucket-table state dict}
    # Everything downstream (snapshot/restore, migration, growth,
    # quota accounting) is generic tree_map over these pytrees.

    def _unstacked_init(self, qname: str):
        """One tenant's fresh state pytree for one query/aggregation."""
        kind = self._kind[qname]
        if kind == "agg":
            ar = self._aggs[qname]
            return {d: ar._init_state() for d in ar.durations}
        q = self.proto.queries[qname]
        sel = tuple(op.init_state() for op in q.operators)
        if kind == "pattern":
            return {"nfa": q.engine.init_state(), "sel": sel}
        if kind == "join":
            return {"sides": {s: tuple(op.init_state() for op in ops)
                              for s, ops in q.side_ops.items()},
                    "sel": sel, "ovf": jnp.int64(0)}
        return sel

    def _stack_init(self, qname: str, slots: int):
        # Host-side numpy repeat + one transfer per leaf: a jnp.repeat
        # here would compile an XLA fill program per distinct leaf shape
        # at pool CONSTRUCTION time, before warmup ever runs.
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(np.repeat(np.asarray(x)[None], slots,
                                            axis=0)),
            self._unstacked_init(qname))

    @staticmethod
    def _ops_init_with_params(ops, vals: dict):
        states = []
        for op in ops:
            st = op.init_state()
            tps = getattr(op, "tparams", ())
            if tps:
                st = {"tparams": {
                    n: jnp.asarray(TenantPool._encode_param(vals[n][0],
                                                            t),
                                   dtype=np_dtype(t))
                    for n, t in tps}}
            states.append(st)
        return tuple(states)

    def _tenant_init_states(self, qname: str, vals: dict):
        """One tenant's fresh (unstacked) state pytree with its bound
        `${...}` parameter values in place of the zeros (value params
        bind only in chain/selector positions — plan_rules)."""
        kind = self._kind[qname]
        if kind == "agg":
            return self._unstacked_init(qname)
        q = self.proto.queries[qname]
        sel = self._ops_init_with_params(q.operators, vals)
        if kind == "pattern":
            return {"nfa": q.engine.init_state(), "sel": sel}
        if kind == "join":
            return {"sides": {
                        s: self._ops_init_with_params(ops, vals)
                        for s, ops in q.side_ops.items()},
                    "sel": sel, "ovf": jnp.int64(0)}
        return sel

    @staticmethod
    def _encode_param(value, t: AttrType):
        if t is AttrType.STRING:
            return GLOBAL_STRINGS.encode(str(value))
        if t is AttrType.BOOL:
            return bool(value)
        return value

    # -- tenant lifecycle -------------------------------------------------

    def admit(self) -> tuple[bool, str]:
        """Admission control: (ok, reason). Checked by add_tenant and by
        the service front door BEFORE building anything (429 + reason).
        Takes the pool lock: the mesh branch reads the cached placement
        counts, which migrations rewrite under the lock."""
        with self._lock:
            ok, reason, _cause = self._admit_check()
        return ok, reason

    def _admit_check(self) -> tuple[bool, str, str]:
        """(ok, human reason, machine cause) — the cause slug rides the
        429's ``saturation`` payload (docs/serving.md). On a mesh the
        slot budget is accounted PER DEVICE: max_tenants splits evenly
        over the mesh, and admission rejects when every device's budget
        is spent (balanced placement makes this coincide with the
        global cap; an unbalanced restore surfaces here instead of
        overloading one device)."""
        if len(self._tenants) >= self.max_tenants:
            return False, (f"pool '{self.name}' tenant slots exhausted "
                           f"(cap {self.max_tenants})"), "slots-exhausted"
        if self.mesh is not None:
            # CACHED placement + budget (re-derived on every slot-map
            # change by _recompute_placement_locked — never recomputed
            # here, so a stale cache would be an observable bug, and
            # tests/test_migrate.py asserts it never goes stale)
            alive = self._alive_devices_locked()
            budget = self._slot_budget
            loads = self._placement_counts
            if not alive:
                return False, (f"pool '{self.name}' has no surviving "
                               "mesh devices"), "no-devices"
            if min(loads[d] for d in alive) >= budget:
                return False, (
                    f"pool '{self.name}' per-device slot budgets "
                    f"exhausted ({budget} tenants/device x "
                    f"{len(alive)} surviving devices, placed {loads})"), \
                    "slots-exhausted"
        if self.state_quota_bytes is not None:
            need = (len(self._tenants) + 1) * self.state_bytes_per_tenant
            if need > self.state_quota_bytes:
                per_class = ", ".join(
                    f"{k}={v}" for k, v in
                    sorted(self.state_bytes_by_class.items()))
                return False, (
                    f"pool '{self.name}' per-tenant state quota "
                    f"exhausted ({need} > {self.state_quota_bytes} bytes "
                    f"at {self.state_bytes_per_tenant} bytes/tenant: "
                    f"{per_class})"), \
                    "state-quota"
        return True, "", ""

    # -- saturation signals (obs/slo.py; docs/observability.md) -----------

    def _retry_after_ms(self, pending_rows: int) -> int:
        """Backlog drain estimate: rounds needed at the fair-share rate
        times the EMA round duration — the 429's Retry-After hint."""
        rounds = max(1, math.ceil(pending_rows / max(1, self.batch_max)))
        per_round = self._round_ms_ema if self._round_ms_ema else 1.0
        return int(math.ceil(rounds * max(per_round, 1.0)))

    def _retry_after_flip_ms(self) -> int:
        """Retry hint for the `migrating` cause: the parked queue
        releases at the NEXT round boundary (the flip), so the honest
        estimate is ONE EMA round — not the backlog-drain estimate,
        which assumes the whole queue must empty first and over-reports
        the pause by orders of magnitude (the satellite fix)."""
        per_round = self._round_ms_ema if self._round_ms_ema else 1.0
        return int(math.ceil(max(per_round, 1.0)))

    def _reject(self, cause: str, reason: str,
                tenant: Optional[str] = None, **info):
        """Count + flight-record an admission rejection, then raise
        AdmissionError carrying the machine-readable saturation payload
        (caller holds the pool lock)."""
        self._rejections[cause] = self._rejections.get(cause, 0) + 1
        self._rejection_times.append(time.perf_counter())
        sat = {"cause": cause, **info, **self._saturation_locked()}
        if tenant is not None:
            sat["tenant"] = tenant
        self.flight.record("admission-reject", cause=cause,
                           tenant=tenant, reason=reason)
        raise AdmissionError(reason, saturation=sat)

    def _saturation_locked(self) -> dict:
        """Current pressure signals (host-side only; caller holds the
        lock): queue age, backlog, round-drain lag, rejection counts."""
        now = time.perf_counter()
        ages = [now - q[0][2] for qs in self._pending.values()
                for q in qs.values() if q]
        pending_total = sum(self._pending_rows.values())
        lag = 0.0
        if pending_total and self._last_pump_wall is not None:
            lag = (now - self._last_pump_wall) * 1000.0
        recent = sum(1 for t in self._rejection_times if now - t <= 60.0)
        sat = {
            "pending_rows": pending_total,
            "queue_age_ms_max": round(max(ages) * 1000.0, 1)
            if ages else 0.0,
            "drain_lag_ms": round(lag, 1),
            "round_ms_ema": round(self._round_ms_ema, 2)
            if self._round_ms_ema is not None else None,
            "rejections": dict(self._rejections),
            "rejections_last_60s": recent,
        }
        if self.mesh is not None:
            # the 429 payload must show the REAL per-device placement
            # (the cached counts, re-derived on every slot-map change)
            sat["placement"] = {str(d): self._placement_counts[d]
                                for d in range(self.n_devices)}
            sat["slot_budget"] = self._slot_budget
            if self._lost_devices:
                sat["lost_devices"] = sorted(self._lost_devices)
        return sat

    def saturation(self) -> dict:
        with self._lock:
            return self._saturation_locked()

    def _flight_context(self) -> dict:
        """Host-side pool snapshot for flight-recorder dumps (no device
        reads, no registry re-entrancy)."""
        with self._lock:
            return {
                "pool": self.name, "slots": self.slots,
                "active": len(self._tenants), "rounds": self._rounds,
                "pending": dict(self._pending_rows),
                "saturation": self._saturation_locked(),
            }

    def add_tenant(self, tenant_id: str,
                   bindings: Optional[dict] = None,
                   qos: Optional[dict] = None) -> int:
        """Admit a tenant into a slot: validate bindings
        (template-binding rule), reset the slot's state slice, write the
        stacked parameter values. ``qos`` carries per-tenant dials
        (weight / priority / rate_eps / burst) merged over the pool
        defaults (docs/serving.md "QoS dials"). Steady-state adds
        compile NOTHING — only a growth doubling does."""
        with self._lock:
            if tenant_id in self._tenants:
                raise ValueError(
                    f"tenant '{tenant_id}' is already deployed in pool "
                    f"'{self.name}'")
            ok, reason, cause = self._admit_check()
            if not ok:
                self._reject(cause, reason, tenant=tenant_id,
                             active=len(self._tenants),
                             max_tenants=self.max_tenants,
                             state_bytes_per_tenant=
                             self.state_bytes_per_tenant,
                             state_bytes_by_class=dict(
                                 self.state_bytes_by_class))
            vals = check_template_bindings(self.proto.ast,
                                           dict(bindings or {}))
            if self._qos is not None:
                self._qos.add_tenant(tenant_id, qos)
            if not self._free:
                self._grow()
            slot = self._pick_slot()
            for qn in self._order:
                init = self._tenant_init_states(qn, vals)
                self._states[qn] = jax.tree_util.tree_map(
                    lambda full, iv: full.at[slot].set(iv),
                    self._states[qn], init)
                self._emitted[qn] = self._emitted[qn].at[slot].set(0)
            self._tenants[tenant_id] = slot
            self._bindings[tenant_id] = dict(bindings or {})
            self._tenant_qos_raw[tenant_id] = dict(qos or {})
            self._pending[tenant_id] = self._fresh_queues()
            self._pending_rows[tenant_id] = 0
            self._error_counts[tenant_id] = 0
            self._recompute_placement_locked()
            return slot

    def remove_tenant(self, tenant_id: str) -> bool:
        """Free the tenant's slot (state stays masked-out until the slot
        is reassigned — zero recompiles)."""
        with self._lock:
            slot = self._tenants.pop(tenant_id, None)
            if slot is None:
                return False
            self._free.append(slot)
            self._pending.pop(tenant_id, None)
            self._pending_rows.pop(tenant_id, None)
            self._callbacks.pop(tenant_id, None)
            self._error_counts.pop(tenant_id, None)
            self._bindings.pop(tenant_id, None)
            self._tenant_qos_raw.pop(tenant_id, None)
            if self._qos is not None:
                self._qos.remove_tenant(tenant_id)
            mig = self._migrations.pop(tenant_id, None)
            if mig is not None:
                # an undeployed tenant's reserved target slot and
                # parked rows go with it
                self._free.append(mig["to_slot"])
            self._recompute_placement_locked()
            return True

    def _grow(self) -> None:
        new_slots = self.slots * 2
        if new_slots > self._slot_cap:
            self._reject(
                "slots-exhausted",
                f"pool '{self.name}' tenant slots exhausted "
                f"(cap {self.max_tenants})",
                active=len(self._tenants),
                max_tenants=self.max_tenants)
        log.info("pool '%s': growing tenant axis %d -> %d slots "
                 "(programs recompile at the new width)",
                 self.name, self.slots, new_slots)

        def pad(x):
            return jnp.concatenate(
                [x, jnp.zeros((self.slots,) + x.shape[1:], x.dtype)],
                axis=0)
        self._states = {qn: jax.tree_util.tree_map(pad, st)
                        for qn, st in self._states.items()}
        self._emitted = {qn: pad(e) for qn, e in self._emitted.items()}
        self._free.extend(range(new_slots - 1, self.slots - 1, -1))
        self.slots = new_slots
        if self.mesh is not None:
            # slot-axis growth is one of the two re-placement events
            # (the other is restore): the concatenated arrays come back
            # sharded over the NEW width in one placement pass
            self._place_state()
        if self._lost_devices:
            # growth re-derives slot->device (slots_per_device changed);
            # slots now landing on a lost device must not be handed out
            self._free = [s for s in self._free
                          if self._device_of_slot(s)
                          not in self._lost_devices]
        self._recompute_placement_locked()
        self._vsteps.clear()
        self._grows += 1
        self._warmed = False

    def _slot(self, tenant_id: str) -> int:
        slot = self._tenants.get(tenant_id)
        if slot is None:
            raise KeyError(f"no tenant '{tenant_id}' in pool "
                           f"'{self.name}'")
        return slot

    def tenant_partition(self, tenant_id: str) -> str:
        """Error-store partition key for one tenant (PR 2 store SPI keys
        by app name; each tenant gets its own namespace)."""
        return f"{self.name}/tenant/{tenant_id}"

    def add_callback(self, tenant_id: str, fn: Callable) -> None:
        """Per-tenant output callback: fn(events) with the tenant's rows
        of every terminal stream. A raising callback routes ITS events
        to ITS error-store partition; other tenants are unaffected."""
        with self._lock:
            self._slot(tenant_id)
            self._callbacks.setdefault(tenant_id, []).append(fn)

    # -- ingest (fair round-robin batching) -------------------------------

    def _fresh_queues(self) -> dict:
        return {sid: deque() for sid in self._ingest_streams}

    def send(self, tenant_id: str, ts, cols,
             stream: Optional[str] = None) -> None:
        """Queue one columnar chunk for a tenant (numpy ts + columns,
        STRING columns as dictionary codes — the send_arrays contract).
        ``stream`` routes multi-ingest templates (patterns/joins name
        several ingest streams); single-stream templates may omit it.
        Every chunk is stamped with its host arrival time (one
        perf_counter read — the queue-age saturation signal and the
        ingest side of the sampled ingest->emit span). Dispatch happens
        in fair rounds via pump()/flush() or the background worker."""
        sid = self._resolve_stream(stream)
        ts = np.asarray(ts, dtype=np.int64)
        n = int(ts.shape[0])
        if n == 0:
            return
        cols = [np.ascontiguousarray(c) for c in cols]
        t_arr = time.perf_counter()
        with self._lock:
            if tenant_id not in self._lost_tenants:
                # a lost device's victim keeps its queue: rows buffer
                # here through the outage and drain after evacuation
                self._slot(tenant_id)
            if self._qos is not None:
                # token-bucket rate limit (serving/qos.py): over-rate
                # ingest is rejected BEFORE it queues, with the
                # bucket's own accrual time as the Retry-After hint
                ok, retry_ms = self._qos.check_rate(tenant_id, n)
                if not ok:
                    self._reject(
                        "rate-limited",
                        f"tenant '{tenant_id}' over its ingest rate "
                        f"limit ({n} rows rejected; retry in "
                        f"{retry_ms} ms)",
                        tenant=tenant_id, rows=n,
                        retry_after_ms=retry_ms)
            mig = self._migrations.get(tenant_id)
            if mig is not None:
                # migration pause: in-flight chunks park in the bounded
                # migration queue and release after the flip — the 429
                # here carries the `migrating` cause with the flip
                # latency (one round), NOT the backlog-drain estimate
                if mig["parked_rows"] + n > mig["park_cap"]:
                    self._reject(
                        "migrating",
                        f"tenant '{tenant_id}' is migrating to device "
                        f"{mig['to_device']} and its parked-ingest "
                        f"queue is full ({mig['parked_rows']} rows "
                        f"parked, cap {mig['park_cap']}); retry after "
                        "the round-boundary flip",
                        tenant=tenant_id, rows=n,
                        parked_rows=mig["parked_rows"],
                        park_cap=mig["park_cap"],
                        retry_after_ms=self._retry_after_flip_ms())
                mig["parked"].append((sid, ts, cols, t_arr))
                mig["parked_rows"] += n
                return
            if self._pending_rows[tenant_id] + n > self.pending_cap:
                self._reject(
                    "ingest-backlog",
                    f"tenant '{tenant_id}' ingest backlog full "
                    f"({self._pending_rows[tenant_id]} rows pending, "
                    f"cap {self.pending_cap})",
                    tenant=tenant_id,
                    pending_rows=self._pending_rows[tenant_id],
                    pending_cap=self.pending_cap,
                    retry_after_ms=self._retry_after_ms(
                        self._pending_rows[tenant_id]))
            qs = self._pending.setdefault(tenant_id,
                                          self._fresh_queues())
            qs[sid].append((ts, cols, t_arr))
            self._pending_rows[tenant_id] += n
            self._work.notify()

    def _take(self, tenant_id: str, sid: str, limit: int):
        """Up to `limit` rows off a tenant's pending queue for ONE
        ingest stream (splitting a chunk re-queues the remainder at the
        head — order AND arrival stamp preserved). Returns
        (ts, cols, oldest_arrival)."""
        q = self._pending.get(tenant_id, {}).get(sid)
        if not q:
            return None
        ts_parts, col_parts, taken = [], [], 0
        t_oldest = None
        while q and taken < limit:
            ts, cols, t_arr = q.popleft()
            room = limit - taken
            if len(ts) > room:
                q.appendleft((ts[room:], [c[room:] for c in cols], t_arr))
                ts, cols = ts[:room], [c[:room] for c in cols]
            ts_parts.append(ts)
            col_parts.append(cols)
            taken += len(ts)
            if t_oldest is None:
                t_oldest = t_arr
        if not taken:
            return None
        self._pending_rows[tenant_id] -= taken
        ts = np.concatenate(ts_parts)
        cols = [np.concatenate([p[i] for p in col_parts])
                for i in range(len(col_parts[0]))]
        return ts, cols, t_oldest

    def pump(self) -> int:
        """One fair dispatch round: every tenant contributes up to
        batch_max rows, ONE vmapped step per query advances all of them.
        Returns rows dispatched (0 = nothing pending).

        On every ``slo_engine.every``-th round the SLO engine samples:
        the round blocks after each vmapped query step (the sampled
        branch only — the PR 7 stride contract) and attributes
        arrival->emit latency per (tenant), (tenant, query) and
        pool-wide from the chunks' host arrival stamps."""
        t_round0 = time.perf_counter()
        with self._lock:
            # round boundary: requested migrations flip HERE, before any
            # take — the moving tenant is never dispatched between its
            # request and its flip, so the move is atomic w.r.t. rounds
            self._apply_migrations_locked()
            # sid -> {slot -> (ts, cols)} for this round
            per_stream: dict[str, dict] = {}
            stamps: dict[str, float] = {}
            taken = 0
            last_ts = self._now
            # per-tenant take limits: the DRR/priority plan when QoS is
            # live (serving/qos.py — all-default dials produce exactly
            # batch_max per backlogged tenant), the fixed fair share
            # otherwise. A tenant's limit spends across its ingest
            # streams in stream order — the credit is per tenant, not
            # per (tenant, stream).
            limits = None
            if self._qos is not None:
                limits = self._qos.plan_round(dict(self._pending_rows),
                                              self.batch_max)
            # optional per-DEVICE row budget (device_round_cap): tenants
            # colocated on a saturated device wait for later rounds —
            # the contention signal the rebalancer reads
            dev_budget = None
            if self.mesh is not None and self.device_round_cap:
                dev_budget = [self.device_round_cap] * self.n_devices
            for tid, slot in self._tenants.items():
                limit = self.batch_max if limits is None \
                    else limits.get(tid, 0)
                dev = self._device_of_slot(slot)
                if dev_budget is not None:
                    limit = min(limit, dev_budget[dev])
                if limit <= 0:
                    continue
                for sid in self._ingest_streams:
                    if limit <= 0:
                        break
                    got = self._take(tid, sid, limit)
                    if got is None:
                        continue
                    ts_a, cols_a, t_arr = got
                    n = len(ts_a)
                    limit -= n
                    if dev_budget is not None:
                        dev_budget[dev] -= n
                    per_stream.setdefault(sid, {})[slot] = (ts_a, cols_a)
                    stamps[tid] = min(stamps.get(tid, t_arr), t_arr)
                    taken += n
                    last_ts = max(last_ts, int(ts_a[-1]))
            if not taken:
                self._last_pump_wall = time.perf_counter()
                return 0
            self._now = max(self._now, last_ts)
            if self.mesh is not None:
                # per-device ingest attribution (host counters only;
                # the `device=` labeled gauge family)
                for per_slot in per_stream.values():
                    for slot, (ts_a, _c) in per_slot.items():
                        self._rows_per_device[
                            self._device_of_slot(slot)] += len(ts_a)
            # ONE transfer per ingest stream: the packed (slots, total)
            # buffer, or the stacked EventBatch fallback — either way a
            # single device_put per stream per round
            stream_inputs = {
                sid: [self._ingest_entry(sid, per_slot)]
                for sid, per_slot in per_stream.items()}
            self._ingest_stats["rounds"] += 1
            sampled = self.slo_engine.tick("round")
            terminal, qtimes = self._dispatch(stream_inputs, self._now,
                                              sample=sampled)
            self._rounds += 1
            if self._checkpoint_supervisor is not None:
                # periodic whole-pool checkpoint at the round boundary
                # (state updated, delivery not yet run — the error-store
                # replay covers the delivery tail, at-least-once)
                self._checkpoint_supervisor.on_round(self._rounds)
            if sampled and qtimes:
                self._slo_attribute(stamps, qtimes, taken)
            dur_ms = (time.perf_counter() - t_round0) * 1000.0
            self._round_ms_ema = dur_ms if self._round_ms_ema is None \
                else 0.8 * self._round_ms_ema + 0.2 * dur_ms
            self._last_pump_wall = time.perf_counter()
        self._deliver(terminal)
        return taken

    def _slo_attribute(self, stamps: dict, qtimes: dict,
                       taken: int) -> None:
        """Fold one sampled round's per-query completion times into the
        SLO windows (host wall math only — the sync already happened on
        the sampled branch of _dispatch)."""
        eng = self.slo_engine
        t_end = max(qtimes.values()) if qtimes else None
        oldest = min(stamps.values()) if stamps else None
        for tid, t_arr in stamps.items():
            for qn, t_q in qtimes.items():
                eng.observe((("tenant", tid), ("query", qn)),
                            (t_q - t_arr) * 1000.0)
            if t_end is not None:
                eng.observe((("tenant", tid),),
                            (t_end - t_arr) * 1000.0)
        if t_end is not None and oldest is not None:
            lat = (t_end - oldest) * 1000.0
            eng.observe((), lat)
            self.flight.record("round", rows=taken,
                               tenants=len(stamps),
                               lat_ms=round(lat, 3))

    def flush(self) -> int:
        """Drain every pending chunk through fair rounds."""
        total = 0
        while True:
            n = self.pump()
            if n == 0:
                return total
            total += n

    def advance_time(self, now_ms: int) -> None:
        """Drive time-based window boundaries (and pattern absent
        deadlines) with no traffic: one empty-batch dispatch per ingest
        stream at the given event time (all slots masked invalid — same
        compiled programs as a tiny round)."""
        with self._lock:
            self._now = max(self._now, int(now_ms))
            stream_inputs = {
                sid: [("b", self._stacked_batch({}, BATCH_BUCKETS[0],
                                                sid))]
                for sid in self._ingest_streams}
            terminal, _qt = self._dispatch(stream_inputs, self._now)
        self._deliver(terminal)

    # -- dispatch ---------------------------------------------------------

    def _ingest_entry(self, sid: str, per_slot: dict):
        """One ingest stream's round input as a dispatch entry:
        ``("p", (buf, enc, cap))`` packed (the default — ONE uint8
        device_put for all slots) or ``("b", EventBatch)`` stacked
        (SIDDHI_TPU_POOL_PACKED=0). Updates the packed-ingest stats
        either way (transfers per round, rows vs padded cells)."""
        cap = bucket_capacity(
            max(len(r[0]) for r in per_slot.values()))
        st = self._ingest_stats
        st["transfers"] += 1
        st["rows"] += sum(len(t) for t, _c in per_slot.values())
        st["cells"] += self.slots * cap
        if not self._packed_on:
            return ("b", self._stacked_batch(per_slot, cap, sid))
        return self._pack_round(sid, per_slot, cap)

    def _encoder(self, sid: str):
        """The stream's sticky packed encoder (created on first use)."""
        from ..core.ingest import PackedEncoder
        enc_ = self._encoders.get(sid)
        if enc_ is None:
            schema = self.proto.junctions[sid].schema
            enc_ = self._encoders[sid] = PackedEncoder(schema)
        return enc_

    def _pack_round(self, sid: str, per_slot: dict, cap: int):
        """Pack one ingest stream's round into ONE (slots, total) uint8
        buffer (core/ingest.py wire format, one row per slot) and ship
        it with a single device_put (mesh: a single SHARDED put — each
        device receives only its slots' rows).

        The stream's sticky encoder widens ROUND-WIDE first
        (`widen_round`) so every slot's row assembles under the same
        final encoding tuple — the enc tuple is part of the jit cache
        key, so it must be one value per transfer. Empty slots stay
        all-zero except the `now` header slot: every row carries the
        round clock, so idle tenants' windows expire on the same clock
        as active ones (the batch flavor's global `now` twin)."""
        from ..core.ingest import layout
        enc_ = self._encoder(sid)
        chunks = list(per_slot.values())
        enc = enc_.widen_round(chunks)
        _H, _offs, total = layout(len(enc_.schema.types), enc, cap)
        big = np.zeros((self.slots, total), np.uint8)
        # round clock into EVERY slot's header (bytes 16:24 = now)
        big[:, 16:24] = np.frombuffer(np.int64(self._now).tobytes(),
                                      np.uint8)
        for slot, (ts_a, cols_a) in per_slot.items():
            enc_.encode_into(ts_a, cols_a, cap, self._now,
                             out=big[slot])
        self._ingest_stats["bytes"] += big.nbytes
        if self.mesh is None:
            dev = jax.device_put(big)
        else:
            dev = self._sharding.place_leading(big, self.mesh,
                                               axis=self.mesh_axis)
        return ("p", (dev, enc, cap))

    def _stacked_batch(self, per_slot: dict, cap: int,
                       sid: str) -> EventBatch:
        """(slots, cap) stacked EventBatch from per-slot row chunks; one
        device_put for the whole pytree. Slots without rows are
        all-padding (their tenants' states pass through unchanged)."""
        schema = self.proto.junctions[sid].schema
        N = self.slots
        ts = np.zeros((N, cap), np.int64)
        valid = np.zeros((N, cap), np.bool_)
        kind = np.zeros((N, cap), np.int32)
        cols = [np.zeros((N, cap), np_dtype(t)) for t in schema.types]
        for slot, (t, cs) in per_slot.items():
            n = len(t)
            ts[slot, :n] = t
            valid[slot, :n] = True
            for i, c in enumerate(cs):
                cols[i][slot, :n] = c
        batch = EventBatch(
            ts=ts, cols=tuple(cols),
            nulls=tuple(np.zeros((N, cap), np.bool_) for _ in cols),
            kind=kind, valid=valid)
        return self._place_batch(batch)

    def _vstep_for(self, qname: str, label: str, flavor: tuple) \
            -> Callable:
        # warm_specs builders run on compile-pool threads; the lock keeps
        # concurrent builds from double-creating (and double-compiling)
        # the same jit wrapper
        with self._lock:
            return self._vstep_for_locked(qname, label, flavor)

    def _core_body(self, qname: str, label: str) -> Callable:
        """The per-slot step for one (query, input-label): the same
        trace the separate runtimes jit per instance (core/runtime.py
        `_chain_body` / `_step_for_stream` / `_step_for_side`), minus
        table support (poolability rejects tables). Signature
        ``(st, emitted, batch, now) -> (st, emitted, out|None)`` —
        vmapped over the leading slot axis by `_vstep_for_locked`."""
        kind = self._kind[qname]
        rewrite = self._q_out.get(qname) is not None
        if kind == "agg":
            astep = self._aggs[qname]._make_step()

            def agg_body(st, emitted, batch, now):
                st = astep(st, batch)
                emitted = emitted + batch.count().astype(jnp.int64)
                return st, emitted, None
            return agg_body
        q = self.proto.queries[qname]
        sel_ops = q.operators
        if kind == "chain":
            chain = _chain_body(q.operators, q._has_timers, qname)

            def body(states, emitted, batch, now):
                states, _t, emitted, out, _due = chain(
                    states, {}, emitted, batch, now)
                if rewrite:
                    # insert-into kind rewrite inside the trace, exactly
                    # like FusedChain hops
                    out = _as_current(out)
                return states, emitted, out
            return body
        if kind == "pattern":
            nfa_step = q.engine.make_stream_step(label[2:])

            def pbody(st, emitted, batch, now):
                nfa_state, match = nfa_step(st["nfa"], batch, now)
                new_sel = []
                for op, s in zip(sel_ops, st["sel"]):
                    s, match = op.step(s, match, now)
                    new_sel.append(s)
                emitted = emitted + match.count().astype(jnp.int64)
                if rewrite:
                    match = _as_current(match)
                return ({"nfa": nfa_state, "sel": tuple(new_sel)},
                        emitted, match)
            return pbody
        # join: ONE whole-dict donated state per query — the opposite
        # side's leaves are read inside and returned unchanged (an
        # identity alias of the donated input, which is exactly what
        # donation wants), so L and R steps share one state home
        from ..ops.join import combined_schema
        side = label
        opp = "R" if side == "L" else "L"
        my_ops = q.side_ops[side]
        opp_window = q.side_ops[opp][-1] if q.side_ops[opp] else None
        cross = q.crosses[side]
        gate_alive = self.proto._columnar

        def jbody(st, emitted, batch, now):
            sides = st["sides"]
            new_my = []
            for op, s in zip(my_ops, sides[side]):
                s, batch = op.step(s, batch, now)
                new_my.append(s)
            if cross is not None:
                opp_buf = opp_window.findable_buffer(sides[opp][-1])
                joined, lost = cross.cross(batch, opp_buf,
                                           gate_alive=gate_alive)
            else:
                sch = combined_schema("#j", q.in_schemas["L"],
                                      q.in_schemas["R"])
                joined = EventBatch.empty(sch, 16)
                lost = jnp.int64(0)
            new_sel = []
            for op, s in zip(sel_ops, st["sel"]):
                s, joined = op.step(s, joined, now)
                new_sel.append(s)
            emitted = emitted + joined.count().astype(jnp.int64)
            if rewrite:
                joined = _as_current(joined)
            return ({"sides": {side: tuple(new_my),
                               opp: sides[opp]},
                     "sel": tuple(new_sel),
                     "ovf": st["ovf"] + lost},
                    emitted, joined)
        return jbody

    def _vstep_for_locked(self, qname: str, label: str,
                          flavor: tuple) -> Callable:
        """jit(vmap(...)) step for one (query, input label, flavor):
        flavor ``("b", cap)`` takes a stacked EventBatch + global now,
        ``("p", enc, cap)`` unpacks the packed round buffer per slot
        (core/ingest.py — each slot's header carries the round clock),
        ``("t",)`` is the pattern absent-deadline timer step. States
        and emitted donate; the batch/buffer argument never does (a
        fan-out template dispatches the same entry to several
        queries)."""
        key = (qname, label, flavor, self.slots)
        fn = self._vsteps.get(key)
        if fn is None:
            if flavor[0] == "t":
                q = self.proto.queries[qname]
                tstep = q.engine.make_timer_step()
                sel_ops = q.operators
                rewrite = self._q_out.get(qname) is not None

                def tbody(st, emitted, now):
                    nfa_state, match = tstep(st["nfa"], now)
                    new_sel = []
                    for op, s in zip(sel_ops, st["sel"]):
                        s, match = op.step(s, match, now)
                        new_sel.append(s)
                    emitted = emitted + match.count().astype(jnp.int64)
                    if rewrite:
                        match = _as_current(match)
                    return ({"nfa": nfa_state, "sel": tuple(new_sel)},
                            emitted, match)

                fn = jax.jit(jax.vmap(tbody, in_axes=(0, 0, None)),
                             **_donate(0, 1))
            elif flavor[0] == "p":
                from ..core.ingest import unpack_buffer
                _tag, enc, cap = flavor
                core = self._core_body(qname, label)
                sid = next(s for lab, s in self._q_in[qname]
                           if lab == label)
                schema = self.proto.junctions[sid].schema

                def pk_body(st, emitted, buf):
                    batch, now = unpack_buffer(schema, enc, cap, buf)
                    return core(st, emitted, batch, now)

                fn = jax.jit(jax.vmap(pk_body, in_axes=(0, 0, 0)),
                             **_donate(0, 1))
            else:
                core = self._core_body(qname, label)
                fn = jax.jit(jax.vmap(core, in_axes=(0, 0, 0, None)),
                             **_donate(0, 1))
            self._vsteps[key] = fn
        return fn

    def _run_step(self, qname: str, label: str, entry,
                  now_dev) -> Optional[EventBatch]:
        """Advance one query's stacked state over one dispatch entry;
        returns the stacked out batch (None for aggregations)."""
        if entry[0] == "p":
            buf, enc, cap = entry[1]
            step = self._vstep_for(qname, label, ("p", enc, cap))
            args = (self._states[qname], self._emitted[qname], buf)
        else:
            batch = entry[1]
            cap = int(batch.ts.shape[1])
            step = self._vstep_for(qname, label, ("b", cap))
            args = (self._states[qname], self._emitted[qname], batch,
                    now_dev)
        self._states[qname], self._emitted[qname], out = step(*args)
        self._dispatches += 1
        return out

    def _dispatch(self, stream_inputs: dict, now: int,
                  sample: bool = False) -> tuple[dict, dict]:
        """Run the template's query graph over one stacked round;
        ``stream_inputs`` maps ingest stream -> list of entries
        (``("b", batch)`` / ``("p", (buf, enc, cap))``). Returns
        ({terminal stream id: [stacked out batches]} (device),
        {query: host completion time}). Patterns with absent deadlines
        additionally run their vmapped timer step every dispatch (the
        pool's round clock replaces the host scheduler — absent
        matches fire at round boundaries). The completion times are
        only populated when ``sample`` is set: that branch blocks after
        each query's last step (``block_until_ready`` — NOT a
        device_get; the one-device-read-per-pool stats contract is
        untouched) so the per-query ingest->emit attribution is
        honest."""
        now_dev = jnp.asarray(now, dtype=jnp.int64)
        stream_batches = {sid: list(entries)
                          for sid, entries in stream_inputs.items()}
        terminal: dict = {}
        qtimes: dict = {}
        for qname in self._order:
            outs = []
            for label, sid in self._q_in[qname]:
                for entry in stream_batches.get(sid, ()):
                    out = self._run_step(qname, label, entry, now_dev)
                    if out is not None:
                        outs.append(out)
            if self._kind[qname] == "pattern" and \
                    self.proto.queries[qname].engine.has_absent:
                step = self._vstep_for(qname, "timer", ("t",))
                self._states[qname], self._emitted[qname], out = step(
                    self._states[qname], self._emitted[qname], now_dev)
                self._dispatches += 1
                outs.append(out)
            if sample and outs:
                # sampled branch ONLY (1-in-slo_engine.every rounds):
                # the sync is the point — per-query ingest->emit
                # attribution needs the step provably finished
                # (the PR 7 sampled-probe pattern)
                jax.block_until_ready(outs[-1].valid)  # lint: disable=host-sync-in-loop
                qtimes[qname] = time.perf_counter()
            tgt = self._q_out[qname]
            if not outs or tgt is None:
                continue
            if tgt in self._terminal:
                terminal.setdefault(tgt, []).extend(outs)
            else:
                stream_batches.setdefault(tgt, []).extend(
                    ("b", o) for o in outs)
        return terminal, qtimes

    def _deliver(self, terminal: dict) -> None:
        for fn in self.batch_callbacks:
            fn(terminal)   # device batches, zero sync (bench fast path)
        if not self._callbacks or not terminal:
            return
        host = jax.device_get(terminal)   # ONE transfer for every tenant
        with self._lock:
            targets = [(tid, self._tenants[tid], list(cbs))
                       for tid, cbs in self._callbacks.items()
                       if tid in self._tenants]
        for tid, slot, cbs in targets:
            per_sid = []
            for sid, outs in host.items():
                evs = []
                for out in outs:
                    evs.extend(self._decode_slot(sid, out, slot))
                if evs:
                    per_sid.append((sid, evs))
            if not per_sid:
                continue
            self._deliver_tenant(tid, cbs, per_sid)

    def _deliver_tenant(self, tid: str, cbs: list,
                        per_sid: list) -> None:
        """Deliver one tenant's decoded rows through its circuit
        breaker (serving/qos.py): OPEN short-circuits every stream's
        events to the tenant's error-store partition WITHOUT running
        the callback, HALF_OPEN lets exactly one probe delivery
        through, and the delivery outcome feeds the state machine.
        Shared by the round delivery path and replay_errors."""
        gate = "closed"
        if self._qos is not None:  # lint: disable=racy-attribute-read (qos ref rebinds only under restore quiesce; a stale ref delays new dials one round)
            with self._lock:
                # gate() on an elapsed cooldown IS the HALF_OPEN
                # transition, so it runs only when rows are in hand
                gate = self._qos.breaker_gate(tid)
        if gate == "open":
            for sid, events in per_sid:
                self._short_circuit(tid, sid, events)
            return
        failed = False
        for sid, events in per_sid:
            for cb in cbs:
                try:
                    cb(events)
                except Exception as exc:  # noqa: BLE001 — isolate
                    failed = True
                    self._tenant_error(tid, sid, events, exc)
        if self._qos is not None:  # lint: disable=racy-attribute-read (qos ref rebinds only under restore quiesce; a stale ref delays new dials one round)
            with self._lock:
                self._qos.on_delivery(tid, ok=not failed)

    def _short_circuit(self, tid: str, sid: str, events: list) -> None:
        """OPEN-breaker path: the events survive in the tenant's error
        partition (replayable) but its failing callback never runs."""
        from ..resilience.errorstore import ErroredEvent
        with self._lock:
            self._qos.count_short_circuit(tid, len(events))
            self._error_counts[tid] = \
                self._error_counts.get(tid, 0) + len(events)
        try:
            self.proto._error_store().store(
                self.tenant_partition(tid),
                ErroredEvent.from_events(
                    sid, events, "circuit-open: delivery short-circuited",
                    now=self._now))  # lint: disable=racy-attribute-read (monotonic round clock; an error-record timestamp one round stale is tolerable)
        except Exception:  # noqa: BLE001 — isolation must not cascade
            log.exception("pool '%s': error-store write failed for "
                          "short-circuited tenant '%s'", self.name, tid)

    def _on_breaker_transition(self, tid: str, prev: str,
                               state: str) -> None:
        self.flight.record("breaker-transition", tenant=tid,
                           prev=prev, state=state)
        log.warning("pool '%s': tenant '%s' circuit breaker %s -> %s",
                    self.name, tid, prev, state)

    def _decode_slot(self, sid: str, host_out, slot: int) -> list:
        types = self.proto.junctions[sid].schema.types
        row = EventBatch(
            ts=host_out.ts[slot], cols=tuple(c[slot]
                                             for c in host_out.cols),
            nulls=tuple(nl[slot] for nl in host_out.nulls),
            kind=host_out.kind[slot], valid=host_out.valid[slot])
        return [Event(ts, vals, is_expired=(kind == EXPIRED))
                for ts, kind, vals in rows_from_batch(types, row)]

    def _tenant_error(self, tid: str, sid: str, events: list,
                      exc: Exception) -> None:
        """Sink-failure isolation: the failing tenant's events land in
        ITS error-store partition; delivery to other tenants continues."""
        from ..resilience.errorstore import ErroredEvent
        with self._lock:
            self._error_counts[tid] = \
                self._error_counts.get(tid, 0) + len(events)
        try:
            self.proto._error_store().store(
                self.tenant_partition(tid),
                ErroredEvent.from_events(
                    sid, events, f"{type(exc).__name__}: {exc}",
                    now=self._now))  # lint: disable=racy-attribute-read (monotonic round clock; an error-record timestamp one round stale is tolerable)
        except Exception:  # noqa: BLE001 — isolation must not cascade
            log.exception("pool '%s': error-store write failed for "
                          "tenant '%s'", self.name, tid)
        log.warning("pool '%s': tenant '%s' callback failed on stream "
                    "'%s' (%d event(s) -> partition '%s'): %s",
                    self.name, tid, sid, len(events),
                    self.tenant_partition(tid), exc)

    # -- AOT warmup (one program set per template) ------------------------

    def _spec_key_base(self) -> str:
        """Content-addressed spec key prefix: template hash + shared
        structural bindings + mesh width. Two pools instantiating the
        same (template, shared) pair produce byte-identical programs, so
        their specs must carry IDENTICAL keys — the CompileService key
        dedupe and the persistent compile cache both line up on it
        (a pool's display name never reaches the key)."""
        base = f"tpl:{self.template.key}"
        if self.shared:
            blob = repr(sorted(self.shared.items()))
            base += "+" + hashlib.sha256(blob.encode()).hexdigest()[:8]
        if self.n_devices > 1:
            base += f"@mesh{self.n_devices}"
        return base

    def _warm_spec_list(self, caps=None) -> list:
        """The pool's vmapped step specs for the given row caps — the
        list warmup() compiles and the compiled-program auditor
        (analysis/programs.py audit_pool) traces abstractly. Builders
        route every allocation through core/compile.py's mode-aware
        helpers; mesh placement only happens on the concrete (warmup)
        path — placing needs real buffers, and the audit never builds
        any (it sees the single-device twin of each program)."""
        from ..core.compile import (CompileSpec, spec_args_abstract,
                                    zeros_array)
        from ..core.ingest import initial_encoding, layout
        caps = sorted({bucket_capacity(min(int(c), self.batch_max))
                       for c in (caps or (self.batch_max,))})
        base = self._spec_key_base()
        specs = []

        def place(qname, states, emitted, batch=None, buf=None):
            if self.mesh is None or spec_args_abstract():
                return states, emitted, batch, buf
            # warm SHARDED programs: the example args must carry the
            # runtime placement or the AOT compile lands on a
            # different (and never-dispatched) single-device program
            placed = self._sharding.shard_pytree(
                {"states": {qname: states},
                 "emitted": {qname: emitted}},
                self.mesh, self._sharding.POOL_STATE_RULES,
                axis=self.mesh_axis)
            states = placed["states"][qname]
            emitted = placed["emitted"][qname]
            if batch is not None:
                batch = self._place_batch(batch)
            if buf is not None:
                buf = self._sharding.place_leading(
                    buf, self.mesh, axis=self.mesh_axis)
            return states, emitted, batch, buf

        with self._lock:
            slots = self.slots
            ingest = set(self._ingest_streams)
            for cap in caps:
                for qname in self._order:
                    for label, sid in self._q_in[qname]:
                        lab = "" if label == "*" \
                            else "/" + label.replace(":", "-")
                        schema = self.proto.junctions[sid].schema

                        def build(qname=qname, label=label, cap=cap,
                                  schema=schema):
                            fn = self._vstep_for(qname, label,
                                                 ("b", cap))
                            states = _tree_zeros(self._states[qname])
                            emitted = zeros_array((slots,), jnp.int64)
                            N = slots
                            batch = EventBatch(
                                ts=zeros_array((N, cap), jnp.int64),
                                cols=tuple(
                                    zeros_array((N, cap), np_dtype(t))
                                    for t in schema.types),
                                nulls=tuple(
                                    zeros_array((N, cap), jnp.bool_)
                                    for _ in schema.types),
                                kind=zeros_array((N, cap), jnp.int32),
                                valid=zeros_array((N, cap), jnp.bool_))
                            states, emitted, batch, _ = place(
                                qname, states, emitted, batch=batch)
                            return fn, (states, emitted, batch,
                                        zeros_array((), jnp.int64))
                        specs.append(CompileSpec(
                            f"{base}/{qname}{lab}/v{slots}x{cap}",
                            build))
                        if not (self._packed_on and sid in ingest):
                            continue
                        # packed flavor: one spec per current sticky
                        # encoding (the enc tuple is part of the key —
                        # a widened stream warms its new shape)
                        enc_obj = self._encoders.get(sid)
                        enc = enc_obj.encoding if enc_obj is not None \
                            else initial_encoding(schema)

                        def pbuild(qname=qname, label=label, cap=cap,
                                   schema=schema, enc=enc):
                            fn = self._vstep_for(qname, label,
                                                 ("p", enc, cap))
                            states = _tree_zeros(self._states[qname])
                            emitted = zeros_array((slots,), jnp.int64)
                            _H, _o, total = layout(len(schema.types),
                                                   enc, cap)
                            buf = zeros_array((slots, total),
                                              jnp.uint8)
                            states, emitted, _, buf = place(
                                qname, states, emitted, buf=buf)
                            return fn, (states, emitted, buf)
                        specs.append(CompileSpec(
                            f"{base}/{qname}{lab}/v{slots}x{cap}"
                            f"/pk-{'.'.join(enc)}", pbuild))
            # pattern absent-deadline timer steps (cap-independent)
            for qname in self._order:
                if self._kind[qname] != "pattern" or \
                        not self.proto.queries[qname].engine.has_absent:
                    continue

                def tbuild(qname=qname):
                    fn = self._vstep_for(qname, "timer", ("t",))
                    states = _tree_zeros(self._states[qname])
                    emitted = zeros_array((slots,), jnp.int64)
                    states, emitted, _, _ = place(qname, states,
                                                  emitted)
                    return fn, (states, emitted,
                                zeros_array((), jnp.int64))
                specs.append(CompileSpec(
                    f"{base}/{qname}/timer/v{slots}", tbuild))
        return specs

    def warmup(self, caps=None, workers: Optional[int] = None,
               samples: Optional[dict] = None) -> dict:
        """Compile the pool's vmapped step programs through the
        prototype's PR 5 CompileService (parallel lowering + persistent
        cache + telemetry) BEFORE the first tenant's traffic: telemetry
        lands in statistics()['compile'] exactly once per pool no matter
        how many tenants deploy. Specs are keyed by template content
        (not pool name) and the service skips keys it already compiled,
        so re-warms with overlapping cap lists lower only the NEW
        shapes. ``samples`` maps ingest stream ids to a ``(ts, cols)``
        traffic sample (runtime.warmup's contract): the stream's sticky
        packed encoding widens over it first, as the first round would,
        so the packed program traffic dispatches is the one compiled."""
        for sid, sample in (samples or {}).items():
            self._encoder(self._resolve_stream(sid)).widen_round([sample])
        result = self.proto.compile_service.warm_specs(
            self._warm_spec_list(caps), workers=workers)
        self._warmed = True
        return result

    def audit_programs(self, caps=None, **kw) -> dict:
        """Static audit of the pool's vmapped programs (zero
        executions/compiles — analysis/programs.py): donation aliasing,
        host callbacks, dtype drift, @app:cap(program.mb=) budget. The
        summary rides statistics()['compile']['audit']."""
        from ..analysis.programs import audit_pool
        return audit_pool(self, caps=caps, **kw).summary()

    @property
    def ready(self) -> bool:
        """Load-balancer readiness: no AOT warmup in flight."""
        return self.proto.compile_service.ready

    # -- background worker (service front door) ---------------------------

    def start(self) -> None:
        """Run the fair-batching drain loop on a daemon thread (the
        tenant-aware @Async queue worker)."""
        with self._lock:
            if self._running:
                return
            self._running = True
        self._worker = threading.Thread(
            target=self._drain_loop, daemon=True,
            name=f"tenantpool-{self.name}")
        self._worker.start()

    def _drain_loop(self) -> None:
        while True:
            with self._lock:
                while self._running and not any(
                        self._pending_rows.get(t, 0)
                        for t in self._tenants):
                    self._work.wait(timeout=0.5)
                if not self._running:
                    return
            try:
                self.pump()
            except Exception:  # noqa: BLE001 — keep serving other rounds
                log.exception("pool '%s': dispatch round failed",
                              self.name)

    def shutdown(self) -> None:
        with self._lock:
            self._running = False
            self._work.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=5)
            self._worker = None

    # -- per-tenant snapshot / restore ------------------------------------

    def snapshot_tenant(self, tenant_id: str) -> bytes:
        """One tenant's state: the tenant-axis slice of every query
        state + emitted counter, serialized with the restricted
        snapshot pickler (core/persistence.py)."""
        from ..core.persistence import dump_strings, serialize
        with self._lock:
            slot = self._slot(tenant_id)
            payload = {
                "pool": self.name,
                "template": self.template.key,
                "tenant": tenant_id,
                "queries": jax.device_get({
                    qn: {"states": jax.tree_util.tree_map(
                            lambda x: x[slot], self._states[qn]),
                         "emitted": self._emitted[qn][slot]}
                    for qn in self._order}),
                "strings": dump_strings(),
            }
            return serialize(payload)

    def restore_tenant(self, tenant_id: str, data: bytes) -> None:
        """Write a snapshot back into the tenant's slot; every other
        index of the tenant axis is untouched (bit-identical)."""
        from ..core.persistence import deserialize, load_strings
        payload = deserialize(data)
        if payload.get("template") != self.template.key:
            raise ValueError(
                f"snapshot is for template {payload.get('template')!r}, "
                f"pool '{self.name}' runs {self.template.key!r}")
        with self._lock:
            slot = self._slot(tenant_id)
            load_strings(payload["strings"])
            for qn in self._order:
                snap = payload["queries"][qn]
                self._states[qn] = jax.tree_util.tree_map(
                    lambda full, s: full.at[slot].set(jnp.asarray(s)),
                    self._states[qn], snap["states"])
                self._emitted[qn] = self._emitted[qn].at[slot].set(
                    jnp.asarray(snap["emitted"]))

    # -- aggregation query side -------------------------------------------

    def materialize_tenant(self, tenant_id: str, agg_id: str,
                           duration: str, start: Optional[int] = None,
                           end: Optional[int] = None):
        """One tenant's `within/per` view of a pooled incremental
        aggregation: slice the tenant's slot out of the stacked bucket
        tables (one device_get of one slot's slice) and materialize it
        host-side through the aggregation runtime's own projection
        (core/aggregation.py materialize_from) — bit-identical to a
        separate runtime fed the same rows."""
        with self._lock:
            ar = self._aggs.get(agg_id)
            if ar is None:
                raise KeyError(
                    f"no aggregation '{agg_id}' in pool '{self.name}' "
                    f"(aggregations: {sorted(self._aggs)})")
            slot = self._slot(tenant_id)
            d = ar.duration_key(duration)
            host = jax.device_get(jax.tree_util.tree_map(
                lambda x: x[slot], self._states[agg_id][d]))
        return ar.materialize_from(host, d, start, end)

    # -- live slot migration (serving/migrate.py orchestrates; docs/
    # serving.md "Live migration & rebalance") ----------------------------

    def request_migration(self, tenant_id: str, device: int,
                          cause: str = "manual",
                          park_cap: Optional[int] = None) -> dict:
        """Reserve a free slot on ``device`` and start parking the
        tenant's new ingest in a bounded migration queue. The actual
        state move + slot-map flip happens at the NEXT round boundary
        (`_apply_migrations_locked`, called at the top of pump while
        the lock is held). Returns the planned move."""
        if self.mesh is None:
            raise ValueError(
                f"pool '{self.name}' has no mesh — migration moves a "
                "slot between mesh devices")
        with self._lock:
            slot = self._slot(tenant_id)
            if not 0 <= device < self.n_devices:
                raise ValueError(
                    f"device {device} out of range "
                    f"(mesh has {self.n_devices})")
            if device in self._lost_devices:
                raise ValueError(f"device {device} is marked lost")
            src = self._device_of_slot(slot)
            if device == src:
                raise ValueError(
                    f"tenant '{tenant_id}' is already on device "
                    f"{device}")
            if tenant_id in self._migrations:
                raise ValueError(
                    f"tenant '{tenant_id}' already has a migration "
                    "in flight")
            target = None
            for i, s in enumerate(self._free):
                if self._device_of_slot(s) == device:
                    target = self._free.pop(i)
                    break
            if target is None:
                raise ValueError(
                    f"no free slot on device {device} for tenant "
                    f"'{tenant_id}'")
            self._migrations[tenant_id] = {
                "from_slot": slot, "from_device": src,
                "to_slot": target, "to_device": device,
                "cause": cause, "parked": deque(), "parked_rows": 0,
                "park_cap": int(park_cap) if park_cap
                else self.pending_cap,
                "t_req": time.perf_counter(),
            }
            self.flight.record(
                "migration-request", tenant=tenant_id, cause=cause,
                from_={"slot": slot, "device": src},
                to={"slot": target, "device": device})
            return {"tenant": tenant_id, "from_slot": slot,
                    "from_device": src, "to_slot": target,
                    "to_device": device}

    def _apply_migrations_locked(self) -> list:
        """Flip every requested migration at this round boundary
        (caller holds the pool RLock — holding it across a pump round
        IS the boundary). Per move: slice the source slot exactly like
        `snapshot_tenant` (the PR 15 per-slot machinery), write it into
        the reserved target slot with `.at[slot].set` on the SHARDED
        arrays — XLA routes the slice to the target device through the
        PR 12 placement, zero recompiles — then flip the slot map,
        release the parked chunks in arrival order, and assert row
        conservation. Every move is flight-recorded with its cause and
        before/after placement."""
        if not self._migrations:
            return []
        results = []
        t0 = time.perf_counter()
        # ONE host round-trip for every flip this boundary (the
        # snapshot_tenant slice per tenant, batched into a single
        # pytree transfer): fresh buffers on write keep the
        # donation-safe contract
        moved_all = jax.device_get({
            tid: {qn: {"states": jax.tree_util.tree_map(
                           lambda x, s=mig["from_slot"]: x[s],
                           self._states[qn]),
                       "emitted":
                           self._emitted[qn][mig["from_slot"]]}
                  for qn in self._order}
            for tid, mig in self._migrations.items()})
        for tid, mig in list(self._migrations.items()):
            old, new = mig["from_slot"], mig["to_slot"]
            moved = moved_all[tid]
            for qn in self._order:
                snap = moved[qn]
                self._states[qn] = jax.tree_util.tree_map(
                    lambda full, s: full.at[new].set(jnp.asarray(s)),
                    self._states[qn], snap["states"])
                self._emitted[qn] = self._emitted[qn].at[new].set(
                    jnp.asarray(snap["emitted"]))
            self._tenants[tid] = new
            self._free.append(old)
            # release the parked chunks BEHIND the surviving pending
            # tail (they arrived later; arrival stamps ride along), then
            # assert conservation: parked + pending in == pending out
            before = self._pending_rows.get(tid, 0)
            parked = mig["parked_rows"]
            qs = self._pending.setdefault(tid, self._fresh_queues())
            for sid, ts, cols, t_arr in mig["parked"]:
                qs[sid].append((ts, cols, t_arr))
            self._pending_rows[tid] = before + parked
            actual = sum(len(t) for q in qs.values()
                         for t, _c, _a in q)
            assert actual == self._pending_rows[tid], (
                f"migration row conservation broken for '{tid}': "
                f"{actual} queued != {before} pending + {parked} parked")
            pause_ms = (time.perf_counter() - mig["t_req"]) * 1000.0
            flip_ms = (time.perf_counter() - t0) * 1000.0
            rec = {"tenant": tid, "cause": mig["cause"],
                   "from": {"slot": old, "device": mig["from_device"]},
                   "to": {"slot": new, "device": mig["to_device"]},
                   "rows_moved": self._pending_rows[tid],
                   "parked_rows": parked,
                   "pause_ms": round(pause_ms, 3),
                   "flip_ms": round(flip_ms, 3),
                   "round": self._rounds}
            del self._migrations[tid]
            self._migration_log.append(rec)
            self._migrations_done += 1
            self._rows_migrated += rec["rows_moved"]
            self._migration_pause_ms_last = rec["pause_ms"]
            self.flight.record("migration", **rec)
            log.info("pool '%s': migrated tenant '%s' slot %d(d%d) -> "
                     "%d(d%d) (%s, %d rows, pause %.1f ms)",
                     self.name, tid, old, mig["from_device"], new,
                     mig["to_device"], mig["cause"], rec["rows_moved"],
                     rec["pause_ms"])
            results.append(rec)
        if self.mesh is not None:
            # dedupe re-placement pass through the rule tables: every
            # leaf already carries the slot-axis sharding, so this
            # transfers NOTHING (shard_pytree's skip contract) — it re-
            # asserts the layout instead of trusting the .at[] writes
            self._place_state()
        self._recompute_placement_locked()
        self._work.notify()
        return results

    def migrate_tenant(self, tenant_id: str, device: int,
                       cause: str = "manual",
                       park_cap: Optional[int] = None) -> dict:
        """Request + flip in ONE held-lock critical section: the RLock
        spans both, no pump round can interleave, so the call site sees
        a completed move (service endpoint + rebalancer entry point).
        Returns the migration record (cause, before/after placement,
        rows moved, pause ms)."""
        with self._lock:
            self.request_migration(tenant_id, device, cause=cause,
                                   park_cap=park_cap)
            recs = self._apply_migrations_locked()
        return next(r for r in recs if r["tenant"] == tenant_id)

    def migration_log(self) -> list:
        with self._lock:
            return list(self._migration_log)

    # -- device loss & degraded mode (serving/migrate.py evacuate();
    # docs/resilience.md "Device evacuation") -----------------------------

    def mark_device_lost(self, device: int) -> dict:
        """Degraded mode: mark one mesh device lost
        (`FaultInjector.kill_device` arms this). Its slots leave the
        free list, its tenants move to the lost set (pending queues and
        error partitions RETAINED — they drain after evacuation),
        admission budgets re-derive over the survivors, and pump keeps
        serving the surviving slots. `serving.migrate.evacuate`
        restores the victims from the newest pool checkpoint."""
        if self.mesh is None:
            raise ValueError(f"pool '{self.name}' has no mesh")
        with self._lock:
            if not 0 <= device < self.n_devices:
                raise ValueError(
                    f"device {device} out of range "
                    f"(mesh has {self.n_devices})")
            if device in self._lost_devices:
                return {"device": device, "victims": []}
            if len(self._lost_devices) + 1 >= self.n_devices:
                raise ValueError(
                    f"pool '{self.name}': cannot lose device {device} "
                    "— no surviving device would remain")
            self._lost_devices.add(device)
            self._free = [s for s in self._free
                          if self._device_of_slot(s) != device]
            victims = sorted(
                tid for tid, slot in self._tenants.items()
                if self._device_of_slot(slot) == device)
            for tid in victims:
                self._lost_tenants[tid] = self._tenants.pop(tid)
            # cancel any in-flight migration touching the dead device:
            # parked rows fall back onto the pending queue (retained)
            for tid, mig in list(self._migrations.items()):
                if device not in (mig["from_device"],
                                  mig["to_device"]):
                    continue
                if mig["to_device"] != device:
                    self._free.append(mig["to_slot"])
                qs = self._pending.setdefault(tid,
                                              self._fresh_queues())
                for sid, ts, cols, t_arr in mig["parked"]:
                    qs[sid].append((ts, cols, t_arr))
                self._pending_rows[tid] = \
                    self._pending_rows.get(tid, 0) + mig["parked_rows"]
                del self._migrations[tid]
                self.flight.record("migration-cancelled", tenant=tid,
                                   reason=f"device {device} lost")
            self._recompute_placement_locked()
            self.flight.record("device-lost", device=device,
                               victims=victims,
                               survivors=len(self._tenants))
            log.warning("pool '%s': device %d lost — %d victim(s) %s "
                        "await evacuation, %d tenant(s) keep serving",
                        self.name, device, len(victims), victims,
                        len(self._tenants))
            return {"device": device, "victims": victims}

    def lost_tenants(self) -> dict:
        with self._lock:
            return dict(self._lost_tenants)

    # -- whole-pool checkpoint / crash recovery ---------------------------
    # (resilience/supervisor.py PoolCheckpointSupervisor drives these;
    # docs/resilience.md "Pool recovery")

    def snapshot(self) -> bytes:
        """Whole-pool state in ONE device_get: every query's stacked
        (slots, ...) state pytree + emitted counters (the slot-sliced
        per-tenant machinery reads the same arrays one index at a
        time), plus the slot map, tenant bindings, and QoS dials needed
        to rebuild admission bookkeeping on a fresh pool."""
        from ..core.persistence import dump_strings, serialize
        with self._lock:
            payload = {
                "kind": "tenant-pool",
                "pool": self.name,
                "template": self.template.key,
                "shared": dict(self.shared),
                "slots": self.slots,
                "now": self._now,
                "rounds": self._rounds,
                "tenants": {
                    tid: {"slot": slot,
                          "bindings": dict(self._bindings.get(tid, {})),
                          "qos": dict(self._tenant_qos_raw.get(tid, {}))}
                    for tid, slot in self._tenants.items()},
                "queries": jax.device_get({
                    qn: {"states": self._states[qn],
                         "emitted": self._emitted[qn]}
                    for qn in self._order}),
                "strings": dump_strings(),
            }
            return serialize(payload)

    def persist(self) -> str:
        """Checkpoint to the manager's persistence store (the
        filesystem backend writes tmp + rename, so a crash mid-persist
        never leaves a torn revision); returns the revision id."""
        from ..core.persistence import new_revision
        store = self.proto._persistence_store()
        rev = new_revision(self.name)
        store.save(self.name, rev, self.snapshot())
        return rev

    def restore(self, data: bytes) -> None:
        """Write a whole-pool snapshot onto THIS pool (typically a
        fresh one built from the same template after a crash). Stacked
        states land as fresh device buffers (``jnp.asarray`` of the
        host snapshot — the donation-safe `_fresh_device` contract) and
        on a mesh the placement is re-derived through the
        parallel/sharding.py rule tables, never copied from the dead
        process. QoS profiles are rebuilt from the snapshot's dials;
        circuit breakers restart CLOSED (a still-dead sink re-trips
        within `breaker.failures` rounds)."""
        from ..core.persistence import deserialize, load_strings
        payload = deserialize(data)
        if payload.get("kind") != "tenant-pool":
            raise ValueError("snapshot is not a tenant-pool snapshot")
        if payload.get("template") != self.template.key:
            raise ValueError(
                f"snapshot is for template {payload.get('template')!r}, "
                f"pool '{self.name}' runs {self.template.key!r}")
        if dict(payload.get("shared") or {}) != self.shared:
            raise ValueError(
                "snapshot was taken with different shared structural "
                "bindings — that is a different compiled program set")
        with self._lock:
            load_strings(payload["strings"])
            slots = int(payload["slots"])
            if self.mesh is not None:
                self._sharding.check_divisible(
                    slots, self.mesh, f"pool '{self.name}' restored slots")
            if slots != self.slots:
                # restored width != fresh-pool width: programs compile
                # at the snapshot's slot count (same class of event as
                # a growth doubling)
                self._vsteps.clear()
                self._warmed = False
            self.slots = slots
            # _fresh_device, not jnp.asarray: device_put may alias a
            # numpy buffer ZERO-COPY, and these arrays feed DONATED
            # step arguments on the next round (the restore
            # double-free class, core/runtime.py)
            self._states = {
                qn: _fresh_device(payload["queries"][qn]["states"])
                for qn in self._order}
            self._emitted = {
                qn: _fresh_device(payload["queries"][qn]["emitted"])
                for qn in self._order}
            if self.mesh is not None:
                self._place_state()   # rule-table placement, re-derived
            self._now = max(self._now, int(payload.get("now", self._now)))
            self._rounds = int(payload.get("rounds", 0))
            self._tenants = {}
            self._bindings = {}
            self._tenant_qos_raw = {}
            self._pending = {}
            self._pending_rows = {}
            self._error_counts = {}
            if self._qos is not None:
                self._qos = PoolQoS(
                    {k: v for k, v in (
                        ("rate_eps", self._qos.default_rate),
                        ("rate_burst", self._qos.default_burst),
                        ("weight", self._qos.default_weight),
                        ("priority", self._qos.default_priority),
                        ("breaker_failures", self._qos.breaker_failures),
                        ("breaker_reset_ms", self._qos.breaker_reset_ms),
                        ("max_defer", self._qos.max_defer))
                        if v is not None},
                    on_transition=self._on_breaker_transition)
            used = set()
            for tid, entry in payload["tenants"].items():
                slot = int(entry["slot"])
                used.add(slot)
                self._tenants[tid] = slot
                self._bindings[tid] = dict(entry.get("bindings") or {})
                self._tenant_qos_raw[tid] = dict(entry.get("qos") or {})
                self._pending[tid] = self._fresh_queues()
                self._pending_rows[tid] = 0
                self._error_counts[tid] = 0
                if self._qos is not None:
                    self._qos.add_tenant(tid, self._tenant_qos_raw[tid])
            # restore is a whole-pool rebuild: in-flight migrations die
            # with the old slot map (their parked rows were never acked
            # past the snapshot), lost-device marks survive — the
            # hardware did not come back because we restored
            self._migrations = {}
            self._lost_tenants = {}
            self._free = [s for s in range(self.slots - 1, -1, -1)
                          if s not in used
                          and self._device_of_slot(s)
                          not in self._lost_devices]
            self._recompute_placement_locked()
            self._recovery = {
                "restored_wall": time.time(),
                "revision": None,       # restore_revision fills it
                "tenants": len(self._tenants),
                "replayed": 0,
            }

    def restore_revision(self, revision: str) -> None:
        store = self.proto._persistence_store()
        data = store.load(self.name, revision)
        if data is None:
            raise KeyError(f"no revision '{revision}' for pool "
                           f"'{self.name}'")
        self.restore(data)
        with self._lock:
            self._recovery["revision"] = revision

    def replay_errors(self, tenant_id: Optional[str] = None) -> dict:
        """Drain per-tenant ``<pool>/tenant/<id>`` error partitions and
        re-deliver through the owning slot's callbacks in
        ORIGINAL-TIMESTAMP order (the PR 9 replay contract: the store
        interleaves rounds out of event-time order, and a replay in
        store order would re-introduce the disorder). Consecutive
        same-origin runs re-deliver as one batch; deliveries go through
        the tenant's circuit breaker, so replaying against a still-OPEN
        breaker lands the events straight back in the partition
        (at-least-once, nothing lost). A tenant with no callbacks keeps
        its backlog. Returns {tenant: events_replayed}."""
        store = self.proto._error_store()
        with self._lock:
            tids = list(self._tenants) if tenant_id is None \
                else [tenant_id]
            if tenant_id is not None:
                self._slot(tenant_id)
            cbs_of = {tid: list(self._callbacks.get(tid, ()))
                      for tid in tids}
        replayed: dict[str, int] = {}
        for tid in tids:
            part = self.tenant_partition(tid)
            records = store.drain(part)
            if not records:
                continue
            if not cbs_of[tid]:
                for rec in records:     # nowhere to deliver — keep
                    store.store(part, rec)
                continue
            entries = []
            seq = 0
            for rec in records:
                for e in rec.to_events():
                    entries.append((e.timestamp, seq, rec.origin, e))
                    seq += 1
            entries.sort(key=lambda t: (t[0], t[1]))
            n = 0
            batch_origin, batch = None, []
            for _ts, _s, origin, e in entries:
                if origin != batch_origin and batch:
                    self._deliver_tenant(tid, cbs_of[tid],
                                         [(batch_origin, batch)])
                    batch = []
                batch_origin = origin
                batch.append(e)
                n += 1
            if batch:
                self._deliver_tenant(tid, cbs_of[tid],
                                     [(batch_origin, batch)])
            replayed[tid] = n
            log.info("pool '%s': replayed %d event(s) for tenant '%s' "
                     "in original-timestamp order", self.name, n, tid)
        return replayed

    # -- observability ----------------------------------------------------

    def statistics(self) -> dict:
        return self._collect_observability()[1]

    def slo_report(self) -> dict:
        """The SLO/burn-rate view on its own (``GET /siddhi/slo``):
        per-scope latency percentiles, attainment, burn rates, states,
        plus the pool's saturation signals."""
        return self.slo_engine.evaluate(saturation=self.saturation())

    def explain(self, live: bool = True) -> dict:
        """Plan explain for the pool (obs/explain.py): the TEMPLATE
        explains once — its ``plan_hash`` covers the prototype's graph
        and the pool's configured decisions (query order, batch_max,
        admission caps, SLO objective, mesh placement rules) and is
        shared by every pool of the same template in the same
        environment. Slot-axis facts (current slots, active tenants,
        rounds) ride the ``live`` section, never the hash — the slot
        axis grows by doubling with churn."""
        from ..obs.explain import ExplainReport
        with self._lock:
            return ExplainReport.from_pool(self, live=live).as_dict()

    def plan_hash(self) -> str:
        """Stable content hash of the template plan (decisions + graph
        only) — stamped into flight-recorder artifacts."""
        from ..obs.explain import ExplainReport
        return ExplainReport.from_pool(self, live=False).plan_hash

    def _collect_sharded_locked(self) -> dict:
        """Mesh pools collect with ONE read PER DEVICE: each device's
        addressable shard of the stacked emitted counters is fetched
        directly (no cross-device gather ever materializes on the
        mesh), timed per device for the ``collect_ms{device=}`` gauge
        family. Caller holds the pool lock."""
        dev_pos = {d.id: i for i, d in
                   enumerate(self.mesh.devices.flat)}
        times = [0.0] * self.n_devices
        emitted = {}
        for qn in self._order:
            arr = self._emitted[qn]
            out = np.zeros(arr.shape, np.int64)
            for sh in arr.addressable_shards:   # one read per device
                t0 = time.perf_counter()
                data = np.asarray(sh.data)
                times[dev_pos.get(sh.device.id, 0)] += \
                    time.perf_counter() - t0
                out[sh.index] = data
            emitted[qn] = out
        self._collect_ms_per_device = [round(t * 1000.0, 3)
                                       for t in times]
        return {"emitted": emitted}

    def _collect_observability(self) -> tuple[dict, dict]:
        """ONE walk shared by statistics() and the registry collector.
        Device reads are O(templates), not O(tenants): the stacked
        emitted counters come back in a single device_get per pool
        (per DEVICE on a mesh — `_collect_sharded_locked`); the
        per-tenant fan-out below is pure host-side numpy indexing (the
        SLO windows are host-side too — tracking ON adds zero device
        reads here; tests/test_slo.py monkeypatch-counts this)."""
        with self._lock:
            if self.mesh is not None:
                host = self._collect_sharded_locked()
            else:
                host = jax.device_get({"emitted": self._emitted})
            tenants = dict(self._tenants)
            pending = dict(self._pending_rows)
            errors = dict(self._error_counts)
            pool_stats = {
                "slots": self.slots, "active": len(tenants),
                "max_tenants": self.max_tenants,
                "batch_max": self.batch_max,
                "rounds": self._rounds, "dispatches": self._dispatches,
                "grows": self._grows,
                "state_bytes_per_tenant": self.state_bytes_per_tenant,
                "state_bytes_by_class":
                    dict(self.state_bytes_by_class),
            }
            ist = self._ingest_stats
            packed_ingest = {
                "enabled": self._packed_on,
                "transfers_per_round":
                    round(ist["transfers"] / ist["rounds"], 3)
                    if ist["rounds"] else 0.0,
                "rows_packed": ist["rows"],
                "pad_frac":
                    round(1.0 - ist["rows"] / ist["cells"], 4)
                    if ist["cells"] else 0.0,
                "bytes": ist["bytes"],
                "rounds": ist["rounds"],
                "streams": len(self._ingest_streams),
            }
            saturation = self._saturation_locked()
            qos_rep = None
            if self._qos is not None:
                qos_rep = self._qos.report()
                qos_rep["throttled_429s"] = \
                    self._rejections.get("rate-limited", 0)
            sup = self._checkpoint_supervisor
            recovery = None
            if sup is not None or self._recovery is not None:
                # recovery age is the operator's "how stale could a
                # crash make me" number (docs/resilience.md)
                wall = time.time()
                recovery = {}
                if sup is not None:
                    recovery.update({
                        "checkpoints": sup.checkpoints,
                        "checkpoint_failures": sup.failures,
                        "last_revision": sup.last_revision,
                        "checkpoint_age_ms":
                            round((wall - sup.last_checkpoint_wall)
                                  * 1000.0, 1)
                            if sup.last_checkpoint_wall else None,
                    })
                if self._recovery is not None:
                    recovery.update({
                        "restored_revision": self._recovery.get("revision"),
                        "restored_tenants": self._recovery.get("tenants"),
                        "replayed": self._recovery.get("replayed"),
                        "recovery_age_ms":
                            round((wall - self._recovery["restored_wall"])
                                  * 1000.0, 1),
                    })
            mesh_info = None
            if self.mesh is not None:
                loads = list(self._placement_counts)
                wall = time.time()
                mesh_info = {
                    "axis": self.mesh_axis,
                    "n_devices": self.n_devices,
                    "slots_per_device": self.slots_per_device,
                    "lost_devices": sorted(self._lost_devices),
                    "lost_tenants": sorted(self._lost_tenants),
                    "evacuations": self._evacuations,
                    "evacuation_age_ms":
                        round((wall - self._last_evacuation_wall)
                              * 1000.0, 1)
                        if self._last_evacuation_wall else None,
                    "migrations": self._migrations_done,
                    "migrations_in_flight": len(self._migrations),
                    "rows_migrated": self._rows_migrated,
                    "migration_pause_ms_last":
                        self._migration_pause_ms_last,
                    "per_device": {
                        str(d): {
                            "slots_placed": loads[d],
                            "slot_budget": self._slot_budget,
                            "lost": d in self._lost_devices,
                            "rows_ingested": self._rows_per_device[d],
                            "collect_ms":
                                self._collect_ms_per_device[d],
                        } for d in range(self.n_devices)},
                }
        p = f"siddhi.{self.name}"
        flat: dict = {}
        report: dict = {"pool": pool_stats, "tenants": {}}
        emitted = host["emitted"]
        # per-tenant gauges: ONE metric family per measure with a
        # `tenant` (and `query`) label — scrapers see a labeled series
        # family, registry dumps keep the readable dotted name
        # (docs/observability.md "label conventions")
        fams = {key: f"{p}.tenant.{key}" for key in
                ("emitted", "pending", "errors")}
        qfam = f"{p}.tenant.query.emitted"
        keep: dict[str, set] = {f: set() for f in fams.values()}
        keep[qfam] = set()
        for tid, slot in tenants.items():
            per_q = {qn: int(emitted[qn][slot]) for qn in self._order}
            entry = {"slot": slot, "emitted": per_q,
                     "pending": pending.get(tid, 0),
                     "errors": errors.get(tid, 0)}
            if qos_rep is not None and tid in qos_rep["tenants"]:
                q = qos_rep["tenants"][tid]
                entry["qos"] = {
                    "weight": q["weight"], "priority": q["priority"],
                    "breaker": q.get("breaker", {}).get("state"),
                }
            report["tenants"][tid] = entry
            base = f"{p}.tenant.{tid}"
            for key, value in (("emitted", sum(per_q.values())),
                               ("pending", entry["pending"]),
                               ("errors", entry["errors"])):
                dotted = f"{base}.{key}"
                self.metrics.labeled_gauge(
                    fams[key], {"tenant": tid}, dotted=dotted,
                    help=_TENANT_HELP[key]).set(value)
                keep[fams[key]].add(dotted)
            for qn, v in per_q.items():
                dotted = f"{base}.query.{qn}.emitted"
                self.metrics.labeled_gauge(
                    qfam, {"tenant": tid, "query": qn},
                    dotted=dotted,
                    help="events emitted by one tenant's query").set(v)
                keep[qfam].add(dotted)
        for fam, dotted in keep.items():
            # departed tenants must not linger in scrapes
            self.metrics.prune_family(fam, dotted)
        for k, v in pool_stats.items():
            if isinstance(v, dict):
                for kk, vv in v.items():
                    flat[f"{p}.pool.{k}.{kk}"] = vv
            else:
                flat[f"{p}.pool.{k}"] = v
        # packed pool ingest (docs/performance.md "Packed pool
        # ingest"): one transfer per ingest stream per round is the
        # acceptance invariant — transfers_per_round tracks it, and
        # pad_frac shows how much of each (slots, cap) round was
        # padding (bench.py tenants arms export the same block)
        report["packed_ingest"] = packed_ingest
        for k in ("transfers_per_round", "rows_packed", "pad_frac"):
            flat[f"{p}.ingest.{k}"] = packed_ingest[k]
        if mesh_info is not None:
            # per-device labeled gauge FAMILIES (`device=` label — the
            # cardinality-safe shape, docs/observability.md): slots
            # placed, rows ingested, per-device collection read time
            report["mesh"] = mesh_info
            flat[f"{p}.mesh.n_devices"] = mesh_info["n_devices"]
            flat[f"{p}.mesh.slots_per_device"] = \
                mesh_info["slots_per_device"]
            fam_help = {
                "slots_placed": "tenants placed on one mesh device",
                "rows_ingested": "rows dispatched to one mesh device",
                "collect_ms": "stats shard-read time for one device",
            }
            for d, entry in mesh_info["per_device"].items():
                for key in ("slots_placed", "rows_ingested",
                            "collect_ms"):
                    self.metrics.labeled_gauge(
                        f"{p}.mesh.{key}", {"device": d},
                        dotted=f"{p}.mesh.device.{d}.{key}",
                        help=fam_help[key]).set(entry[key])
                self.metrics.labeled_gauge(
                    f"{p}.mesh.device_lost", {"device": d},
                    dotted=f"{p}.mesh.device.{d}.lost",
                    help="1 when this mesh device is marked lost"
                ).set(int(entry["lost"]))
            # migration.* / evacuation.* gauge families
            # (docs/observability.md): live-move and device-loss
            # counters for the rebalance/evacuation loops
            flat[f"{p}.migration.count"] = mesh_info["migrations"]
            flat[f"{p}.migration.in_flight"] = \
                mesh_info["migrations_in_flight"]
            flat[f"{p}.migration.rows_moved"] = \
                mesh_info["rows_migrated"]
            if mesh_info["migration_pause_ms_last"] is not None:
                flat[f"{p}.migration.pause_ms_last"] = \
                    mesh_info["migration_pause_ms_last"]
            flat[f"{p}.evacuation.count"] = mesh_info["evacuations"]
            flat[f"{p}.evacuation.lost_devices"] = \
                len(mesh_info["lost_devices"])
            flat[f"{p}.evacuation.lost_tenants"] = \
                len(mesh_info["lost_tenants"])
            if mesh_info["evacuation_age_ms"] is not None:
                flat[f"{p}.evacuation.age_ms"] = \
                    mesh_info["evacuation_age_ms"]
        # SLO + saturation (obs/slo.py): host-side windows, labeled
        # p99/burn/state families, machine-readable pressure signals
        report["slo"] = self.slo_engine.evaluate(saturation=saturation)
        self.slo_engine.publish(self.metrics, f"{p}.slo")
        for k in ("pending_rows", "queue_age_ms_max", "drain_lag_ms",
                  "rejections_last_60s"):
            v = saturation.get(k)
            if isinstance(v, (int, float)):
                flat[f"{p}.saturation.{k}"] = v
        for cause, n in saturation["rejections"].items():
            self.metrics.labeled_gauge(
                f"{p}.saturation.rejections", {"cause": cause},
                dotted=f"{p}.saturation.rejections.{cause}",
                help="admission rejections by saturation cause").set(n)
        # QoS: DRR credits + breaker state per tenant as labeled gauge
        # families (the cardinality-safe shape), plus the scheduler /
        # breaker / throttle counters (docs/serving.md "QoS dials")
        report["qos"] = qos_rep if qos_rep is not None \
            else {"enabled": False}
        if qos_rep is not None:
            cred_fam = f"{p}.qos.credits"
            brk_fam = f"{p}.qos.breaker_state"
            keep_cred: set = set()
            keep_brk: set = set()
            state_num = {"CLOSED": 0, "HALF_OPEN": 1, "OPEN": 2}
            for tid, q in qos_rep["tenants"].items():
                dotted = f"{p}.qos.tenant.{tid}.credits"
                self.metrics.labeled_gauge(
                    cred_fam, {"tenant": tid}, dotted=dotted,
                    help="unspent DRR scheduler credits for one tenant"
                ).set(q["credits"])
                keep_cred.add(dotted)
                br = q.get("breaker")
                if br is not None:
                    dotted = f"{p}.qos.tenant.{tid}.breaker_state"
                    self.metrics.labeled_gauge(
                        brk_fam, {"tenant": tid}, dotted=dotted,
                        help="circuit-breaker state for one tenant "
                             "(0 closed, 1 half-open, 2 open)"
                    ).set(state_num.get(br["state"], -1))
                    keep_brk.add(dotted)
            self.metrics.prune_family(cred_fam, keep_cred)
            self.metrics.prune_family(brk_fam, keep_brk)
            flat[f"{p}.qos.throttled_429s"] = qos_rep["throttled_429s"]
            flat[f"{p}.qos.short_circuited"] = \
                qos_rep["short_circuited"]
        if recovery is not None:
            report["recovery"] = recovery
            for k in ("checkpoints", "checkpoint_failures",
                      "checkpoint_age_ms", "recovery_age_ms",
                      "replayed"):
                v = recovery.get(k)
                if isinstance(v, (int, float)):
                    flat[f"{p}.recovery.{k}"] = v
        comp = dict(self.proto.compile_service.summary())
        # ONE compiled program set per template, shared by every tenant
        # — the multi-tenant acceptance invariant (bench.py `tenants`)
        comp["program_sets"] = 1
        report["compile"] = comp
        for k in ("warmups", "programs", "compile_ms", "cache_hits",
                  "cache_misses", "program_sets"):
            flat[f"{p}.pool.compile.{k}"] = comp.get(k, 0)
        flat[f"{p}.pool.ready"] = int(self.ready)
        return flat, report

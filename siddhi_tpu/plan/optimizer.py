"""Cost-aware DAG plan optimizer over the junction graph.

``build_plan(rt)`` runs at ``SiddhiAppRuntime.start()`` (via
``_build_fused_chains``) and derives the executable plan — the
generalization of PR 4's linear-chain fusion the ROADMAP calls "the
refactor that unlocks 1-3". Four transformations, each bit-equivalence
guarded and individually kill-switchable, all recorded as
machine-readable decisions with cause slugs in
``ExplainReport.decisions['optimizer']`` (so every flip moves
``plan_hash`` and diffs cleanly via ``explain_diff``):

1. **Fan-out fusion** (``SIDDHI_TPU_OPT_FANOUT=0`` disables): a
   junction with N plain-query subscribers — the shape the
   ``multi-subscriber``/``fan-out`` break slugs used to declare a
   fusion barrier — compiles into ONE jitted :class:`FanoutGroup`
   program per chunk shape. Members keep their own standalone steps for
   timers and direct sends (the FusedChain contract); a member that
   heads a linear fused chain participates as a whole-chain unit, so
   groups and chains compose across junction levels (a group member's
   output publishes into the next junction, where another group may
   intercept it).
2. **Common-subexpression sharing** (``SIDDHI_TPU_OPT_CSE=0``): group
   members whose leading STATELESS operators (filters, projections —
   no window/aggregation state, no template params, no table reads)
   canonicalize to identical signatures (plan/canon.py) evaluate that
   prefix ONCE inside the fused trace. Sharing stops at the first
   stateful operator: window state stays per-query so snapshot layout
   and restore are mode-independent.
3. **Filter pushdown** (``SIDDHI_TPU_OPT_PUSHDOWN=0``): inside a fused
   linear segment, a downstream member's leading filter hoists across
   upstream operators it provably commutes with — other filters,
   projections that pass its referenced columns through unchanged
   (identity `select`), and pure time-sliding windows with expired
   emission disabled (membership is timestamp-only, so
   filter-then-window == window-then-filter bit-exactly) — pruning
   rows before the upstream window ever buffers them. Intermediate
   per-query ``emitted`` counters then count the pruned stream
   (documented in docs/performance.md).
4. **Cost-driven selection** (``SIDDHI_TPU_OPT_COST=0``): the measured
   PR 7 cost table (``<state dir>/costs.json``) is consulted through the
   staleness guard (obs/costmodel.load_costs_for): a measured
   ``fanout/<junction>`` center slower per event than the sum of its
   members declines the fusion (``cost-evidence-unfused``), and
   per-capacity centers (``fanout/<j>@<cap>`` / ``chain/<name>@<cap>``)
   pick the ingest chunk capacity with the best measured ms/event
   (``cost-evidence``). No table, no flip: defaults stay.

``SIDDHI_TPU_OPT=0`` is the master kill switch — the plan degrades to
exactly PR 4's linear-chain fusion. ``SIDDHI_TPU_FUSE=0`` still
disables all fusion outright. Every derived program AOT-compiles
through the CompileService (core/compile.py enumerates group steps),
and template pools plan once per template (the pool explain carries the
prototype's optimizer decisions, serving/pool.py).
"""
from __future__ import annotations

import contextlib
import hashlib
import os
from typing import Optional

import jax
import jax.numpy as jnp

OPT_ENV = "SIDDHI_TPU_OPT"
_SWITCH_ENVS = {
    "fanout": "SIDDHI_TPU_OPT_FANOUT",
    "cse": "SIDDHI_TPU_OPT_CSE",
    "pushdown": "SIDDHI_TPU_OPT_PUSHDOWN",
    "cost": "SIDDHI_TPU_OPT_COST",
}


def opt_enabled(which: Optional[str] = None) -> bool:
    """Env kill switches, read at plan-derivation time (so bench can
    toggle per run, like SIDDHI_TPU_FUSE)."""
    if os.environ.get(OPT_ENV, "1") == "0":
        return False
    if which is None:
        return True
    return os.environ.get(_SWITCH_ENVS[which], "1") != "0"


# ---------------------------------------------------------------------------
# operator classification (CSE / pushdown legality)
# ---------------------------------------------------------------------------


def _shareable(op) -> bool:
    """True when evaluating this operator once and sharing the result
    across queries is bit-equivalent: a canonical signature exists
    (attached by the planner from the AST), and the op carries no state
    (no template params), reads no tables, and contains no device sort
    (sort-heavy ops cap capacities per query)."""
    return (getattr(op, "plan_sig", None) is not None
            and not getattr(op, "tparams", ())
            and not getattr(op, "needs_tables", False)
            and not getattr(op, "sort_heavy", False))


def _movable_filter(op) -> bool:
    from ..ops.operators import FilterOp
    return (type(op) is FilterOp and not op.tparams
            and getattr(op, "ref_names", None) is not None)


def _can_cross(filter_op, prev_op) -> bool:
    """Is hoisting ``filter_op`` above ``prev_op`` bit-equivalent?"""
    from ..ops.operators import FilterOp
    from ..ops.selector import ProjectOp
    from ..ops.windows import WindowOp
    if type(prev_op) is FilterOp and not prev_op.tparams:
        return True  # masks commute
    if isinstance(prev_op, ProjectOp):
        idn = getattr(prev_op, "identity_names", None)
        return idn is not None and filter_op.ref_names <= idn
    if isinstance(prev_op, WindowOp):
        return getattr(prev_op, "filter_pushdown_safe", False)
    return False


# ---------------------------------------------------------------------------
# fused-chain schedule (pushdown)
# ---------------------------------------------------------------------------


def natural_schedule(queries) -> list:
    """The un-optimized execution order of a fused linear segment:
    member ops in declaration order, an ``emitted``-count boundary
    after each member, a CURRENT-kind hop rewrite between members."""
    entries: list = []
    k = len(queries)
    for mi, q in enumerate(queries):
        for oi in range(len(q.operators)):
            entries.append(("op", mi, oi))
        entries.append(("count", mi))
        if mi < k - 1:
            entries.append(("hop", mi))
    return entries


def _pushdown_segment(queries, records: list) -> Optional[list]:
    """Hoist each downstream member's leading filter to the earliest
    bit-equivalent position in the segment schedule. Returns the
    reordered schedule, or None when nothing moved (natural order)."""
    from ..ops.windows import WindowOp
    entries = natural_schedule(queries)
    moved = False
    for mi in range(1, len(queries)):
        q = queries[mi]
        if not q.operators or not _movable_filter(q.operators[0]):
            continue
        f = q.operators[0]
        pos = entries.index(("op", mi, 0))
        j = pos
        crossed: list = []
        crossed_window = False
        while j > 0:
            prev = entries[j - 1]
            if prev[0] in ("count", "hop"):
                j -= 1
                continue
            _, pm, po = prev
            if pm == mi:
                break  # never reorder within the filter's own member
            pop = queries[pm].operators[po]
            if not _can_cross(f, pop):
                break
            crossed.append(f"{queries[pm].name}.{type(pop).__name__}")
            crossed_window |= isinstance(pop, WindowOp)
            j -= 1
        # commit only when the hoist crosses a WINDOW: pruning before
        # the buffer is the payoff. Crossing only filters/projections
        # would shave little and still change the intermediate members'
        # `emitted` counters (they count the pruned stream) — not worth
        # the observability churn.
        if crossed and crossed_window:
            entries.pop(pos)
            entries.insert(j, ("op", mi, 0))
            moved = True
            records.append({
                "filter_of": q.name,
                "hoisted_past": list(reversed(crossed)),
                "cause": "pushdown",
            })
    return entries if moved else None


# ---------------------------------------------------------------------------
# cost evidence (transformation 4)
# ---------------------------------------------------------------------------


def _load_evidence(rt):
    """This app's measured cost table through the staleness guard:
    centers that name plan units which no longer exist are dropped and
    counted (obs/costmodel.py; the count rides statistics()['cost'])."""
    from ..obs.costmodel import load_costs_for
    try:
        tbl, stale = load_costs_for(rt.name, rt._cost_center_valid)
    except Exception:  # noqa: BLE001 — costs are advisory, never fatal
        return {}, None
    return tbl, stale


def _ms_per_event(tbl: dict, *keys) -> Optional[float]:
    for k in keys:
        v = tbl.get(k, {}).get("ms_per_event")
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def _fuse_cost_decision(tbl: dict, sid: str,
                        unit_keys: list) -> tuple[bool, str]:
    """Fuse-or-not from measured evidence: compare the fused
    ``fanout/<junction>`` center against the sum of its members'
    standalone centers (``query/<q>`` / ``chain/<segment>``), per
    event. Insufficient evidence keeps the fused default."""
    fused = _ms_per_event(tbl, f"fanout/{sid}")
    if fused is None:
        return True, "fused-default"
    total = 0.0
    for key in unit_keys:
        mpe = _ms_per_event(tbl, key)
        if mpe is None:
            return True, "fused-default"
        total += mpe
    if fused >= total:
        return False, "cost-evidence-unfused"
    return True, "cost-evidence-fused"


def _chunk_cap_decision(tbl: dict, base: str) -> tuple[Optional[int], str]:
    """Per-center ingest chunk capacity from per-capacity evidence
    (``<base>@<cap>`` centers recorded by the group/chain probes):
    at least two measured capacities flip the default negotiation to
    the best measured ms/event."""
    from ..core.runtime import bucket_capacity
    caps: dict[int, float] = {}
    prefix = base + "@"
    for k, v in tbl.items():
        if not k.startswith(prefix):
            continue
        try:
            cap = int(k[len(prefix):])
        except ValueError:
            continue
        mpe = v.get("ms_per_event")
        if isinstance(mpe, (int, float)) and mpe > 0:
            caps[cap] = float(mpe)
    if len(caps) < 2:
        return None, "no-cost-evidence"
    best = min(sorted(caps), key=lambda c: (caps[c], c))
    return bucket_capacity(best), "cost-evidence"


# ---------------------------------------------------------------------------
# fan-out group derivation
# ---------------------------------------------------------------------------


def _group_candidates(rt, junction):
    """The junction receivers a fan-out group can absorb: plain
    QueryRuntimes (pattern/join/partition/callback receivers keep their
    dedicated dispatch). A receiver that heads a fused linear segment
    participates as the whole-chain unit (resolved at install time)."""
    from ..core.runtime import QueryRuntime
    return [r for r in junction.receivers
            if type(r) is QueryRuntime]


def _cse_classes(receivers, seg_heads: set, records: list) -> list:
    """Share classes over the group's plain members: a prefix TRIE of
    canonical signatures, so partially-overlapping prefixes nest —
    e.g. four queries sharing one filter, two of which also share the
    projection, evaluate the filter once and the projection once (fed
    from the shared filter output). Each class carries its parent class
    and the signature depth range it evaluates; a member's effective
    share depth is its DEEPEST class. Chain-head units run their
    monolithic chain body and do not share prefixes."""
    sigs = {}
    for ui, u in enumerate(receivers):
        if u.name in seg_heads:
            continue
        prefix = []
        for op in u.operators:
            if not _shareable(op):
                break
            prefix.append(op.plan_sig)
        if prefix:
            sigs[ui] = prefix
    classes: list = []

    def build(idxs, depth, parent):
        by_next: dict[str, list] = {}
        for i in idxs:
            if len(sigs[i]) > depth:
                by_next.setdefault(sigs[i][depth], []).append(i)
        for sig in sorted(by_next):
            group = by_next[sig]
            if len(group) < 2:
                continue
            k = depth + 1
            while all(len(sigs[i]) > k for i in group) and \
                    len({sigs[i][k] for i in group}) == 1:
                k += 1
            ci = len(classes)
            classes.append({"rep": group[0], "k": k, "members": group,
                            "parent": parent, "pk": depth})
            records.append({
                "queries": [receivers[i].name for i in group],
                "ops": k,
                "sig": hashlib.sha256("|".join(
                    sigs[group[0]][:k]).encode()).hexdigest()[:12],
            })
            build(group, k, ci)

    build(sorted(sigs), 0, None)
    return classes


# ---------------------------------------------------------------------------
# derivation (pure — shared by build_plan and describe_decisions)
# ---------------------------------------------------------------------------


def _derive_segments(rt) -> list:
    """PR 4's linear-segment walk, unchanged: maximal fusible
    single-subscriber `insert into` runs (core/runtime.py
    _fusible_next_info holds the eligibility rules)."""
    from ..core.runtime import QueryRuntime
    nxt = {}
    for q in rt.queries.values():
        r = rt._fusible_next(q)
        if r is not None:
            nxt[q.name] = r
    targets = {r.name for r in nxt.values()}
    segments = []
    for qn in nxt:
        if qn in targets:  # mid-segment (or part of a pure cycle)
            continue
        seg = [rt.queries[qn]]
        seen = {qn}
        while seg[-1].name in nxt:
            r = nxt[seg[-1].name]
            if r.name in seen:
                break
            seg.append(r)
            seen.add(r.name)
        if len(seg) >= 2:
            segments.append(seg)
    return segments


def derive(rt) -> tuple[dict, dict]:
    """Derive the full plan: ``(decisions, artifacts)``. Pure — builds
    no runtime objects, performs no device work; ``build_plan``
    installs the artifacts, ``describe_decisions`` (pool explain)
    returns the decisions alone."""
    enabled = opt_enabled()
    sw = {k: enabled and opt_enabled(k) for k in _SWITCH_ENVS}
    decisions: dict = {"enabled": enabled, "transforms": dict(sw)}
    artifacts: dict = {"segments": [], "schedules": {}, "chain_caps": {},
                       "groups": []}

    tbl, stale = ({}, None)
    if sw["cost"]:
        tbl, stale = _load_evidence(rt)
    artifacts["stale_centers"] = stale

    segments = _derive_segments(rt)
    artifacts["segments"] = segments

    if sw["pushdown"]:
        pd: dict = {}
        for seg in segments:
            records: list = []
            schedule = _pushdown_segment(seg, records)
            if schedule is not None:
                name = "+".join(q.name for q in seg)
                artifacts["schedules"][seg[0].name] = schedule
                pd[name] = records
        if pd:
            decisions["pushdown"] = pd

    if sw["cost"] and tbl:
        for seg in segments:
            name = "+".join(q.name for q in seg)
            cap, cause = _chunk_cap_decision(tbl, f"chain/{name}")
            if cap is not None:
                artifacts["chain_caps"][seg[0].name] = cap
                decisions.setdefault("chunk_caps", {})[
                    f"chain/{name}"] = {"cap": cap, "cause": cause}

    if sw["fanout"]:
        # units resolve against the linear segments derived above: a
        # receiver that heads a segment joins as the whole-chain unit
        seg_by_head = {seg[0].name: seg for seg in segments}
        fans: dict = {}
        for sid in sorted(rt.junctions):
            junction = rt.junctions[sid]
            receivers = _group_candidates(rt, junction)
            if len(receivers) < 2:
                continue
            unit_names = []
            unit_keys = []
            for r in receivers:
                seg = seg_by_head.get(r.name)
                if seg is not None:
                    name = "+".join(q.name for q in seg)
                    unit_names.append(name)
                    unit_keys.append(f"chain/{name}")
                else:
                    unit_names.append(r.name)
                    unit_keys.append(f"query/{r.name}")
            entry: dict = {"members": unit_names}
            fuse, cause = (True, "fused-default")
            if sw["cost"] and tbl:
                fuse, cause = _fuse_cost_decision(tbl, sid, unit_keys)
            entry["fused"] = fuse
            entry["cause"] = cause
            if fuse:
                cse_records: list = []
                classes = _cse_classes(receivers, set(seg_by_head),
                                       cse_records) \
                    if sw["cse"] else []
                if cse_records:
                    entry["cse"] = cse_records
                cap, cap_cause = (None, "no-cost-evidence")
                if sw["cost"] and tbl:
                    cap, cap_cause = _chunk_cap_decision(
                        tbl, f"fanout/{sid}")
                if cap is not None:
                    entry["chunk_cap"] = {"cap": cap, "cause": cap_cause}
                artifacts["groups"].append(
                    (sid, receivers, classes, cap))
            fans[sid] = entry
        if fans:
            decisions["fanout"] = fans

    return decisions, artifacts


def describe_decisions(rt) -> dict:
    """Optimizer decisions for a runtime WITHOUT installing artifacts —
    the pool-explain path (templates plan once per template; the
    prototype runtime is never started)."""
    return derive(rt)[0]


def program_attribution(rt) -> dict:
    """Map each GROUPED program's spec-key prefix to the member queries
    it serves, for the compiled-program auditor's reports
    (analysis/programs.py). Fan-out specs compile under the junction's
    stream id (``fanout:<sid>/row/<cap>``) which says nothing about who
    runs inside; fused chains at least concatenate member names, but the
    explicit list keeps audit output greppable by query name either
    way. Installed artifacts only — call after ``_build_fused_chains``
    (the audit entry points do)."""
    attr: dict = {}
    for j in rt.junctions.values():
        group = getattr(j, "fanout", None)
        if group is not None:
            attr[f"fanout:{group.name}"] = [q.name for q in
                                            group.queries]
    for q in rt.queries.values():
        ch = getattr(q, "_fused_chain", None)
        if ch is not None and ch.name not in attr:
            attr[ch.name] = [m.name for m in ch.queries]
    return attr


def build_plan(rt) -> dict:
    """Derive and install: fused chains (with pushdown schedules and
    cost-picked chunk caps) on their head queries, fan-out groups on
    their junctions. Caller (``_build_fused_chains``) has already
    cleared previous artifacts and checked ``_fusion_enabled``."""
    from ..core.runtime import FusedChain
    decisions, artifacts = derive(rt)
    if artifacts["stale_centers"] is not None:
        rt.cost.stale_centers = artifacts["stale_centers"]
    for seg in artifacts["segments"]:
        head = seg[0]
        head._fused_chain = FusedChain(
            rt, seg, schedule=artifacts["schedules"].get(head.name))
        cap = artifacts["chain_caps"].get(head.name)
        if cap is not None:
            head.preferred_ingest_cap = cap
    for sid, receivers, classes, cap in artifacts["groups"]:
        junction = rt.junctions[sid]
        # chain heads join as their whole installed segment
        units = [r._fused_chain if r._fused_chain is not None else r
                 for r in receivers]
        group = FanoutGroup(rt, junction, units, classes,
                            preferred_cap=cap)
        junction.fanout = group
        for r in receivers:
            r._fanout_group = group
    rt._opt_decisions = decisions
    return decisions


# ---------------------------------------------------------------------------
# the fused fan-out group
# ---------------------------------------------------------------------------


class FanoutGroup:
    """N subscriber units of one junction compiled into ONE jitted step
    per chunk shape::

        (statesU1..Un, tstates, emittedU1..Un, batch, now)
          -> (states', tstates', emitted', (outU1..outUn), (dueU1..dueUn))

    A unit is a plain QueryRuntime or a whole FusedChain (the member
    heads a linear segment). Shared CSE prefixes evaluate once per
    share class; every unit's output dispatches through its tail's
    normal ``_dispatch_output`` (callbacks, insert-into handlers,
    rate limiters all behave as unfused — a downstream junction with
    its own group intercepts there, so fan-out DAGs compose level by
    level). The junction's batch publish paths call the group ONCE per
    chunk instead of once per receiver; members keep their standalone
    steps for timers and direct sends (the FusedChain contract).
    """

    def __init__(self, app, junction, units, classes,
                 preferred_cap: Optional[int] = None):
        from ..core.runtime import FusedChain, QueryRuntime
        self.app = app
        self.junction = junction
        self.units = list(units)
        self.members = tuple(u.name for u in self.units)  # span args
        self.name = junction.stream_id      # stable cost-center name
        self.display = "|".join(u.name for u in self.units)
        self.queries = [q for u in self.units
                        for q in (u.queries if isinstance(u, FusedChain)
                                  else (u,))]
        self._heads = [u.head if isinstance(u, FusedChain) else u
                       for u in self.units]
        self._tails = [u.tail if isinstance(u, FusedChain) else u
                       for u in self.units]
        self._member_ids = {id(h) for h in self._heads}
        self.table_deps = sorted({t for u in self.units
                                  for t in u.table_deps})
        self.preferred_cap = preferred_cap
        caps = [h.max_step_capacity for h in self._heads
                if h.max_step_capacity is not None]
        self.max_step_capacity = min(caps) if caps else None
        self._scan_cap = QueryRuntime.SCAN_CHUNK_CAP
        # a member's effective class is its DEEPEST trie node: classes
        # are emitted parent-before-child, so the last write wins
        self._cse_class = [None] * len(self.units)
        self._classes = list(classes)
        for ci, cls in enumerate(self._classes):
            for ui in cls["members"]:
                self._cse_class[ui] = ci
        self._chain = self._make_chain()
        self._step = None
        self._packed_steps: dict = {}

    @property
    def max_packed_capacity(self):
        return None if self.max_step_capacity is None \
            else max(self._scan_cap, self.max_step_capacity)

    def covers(self, receiver) -> bool:
        return id(receiver) in self._member_ids

    # -- trace ------------------------------------------------------------
    def _unit_body(self, ui: int):
        from ..core.runtime import FusedChain, _chain_body
        u = self.units[ui]
        if isinstance(u, FusedChain):
            return u._chain
        k = self._classes[self._cse_class[ui]]["k"] \
            if self._cse_class[ui] is not None else 0
        body = _chain_body(u.operators[k:], u._has_timers, u.name)
        if k == 0:
            return body

        def run(states, tstates, emitted, batch, now):
            # the shared prefix is stateless: its state slots pass
            # through untouched so snapshot layout is mode-independent
            st, tstates, emitted, out, due = body(
                tuple(states[k:]), tstates, emitted, batch, now)
            return (tuple(states[:k]) + tuple(st), tstates, emitted,
                    out, due)
        return run

    def _make_chain(self):
        from ..obs.profiler import op_scope
        bodies = [self._unit_body(i) for i in range(len(self.units))]
        classes = self._classes
        cse_class = self._cse_class
        units = self.units

        def chain(states, tstates, emitteds, batch, now):
            # shared prefixes evaluate once per trie node, each fed from
            # its parent node's output (parents precede children)
            shared = {}
            for ci, cls in enumerate(classes):
                cur = batch if cls["parent"] is None \
                    else shared[cls["parent"]]
                rep = units[cls["rep"]]
                for op in rep.operators[cls["pk"]:cls["k"]]:
                    with op_scope(f"{rep.name}/{type(op).__name__}"):
                        _, cur = op.step((), cur, now)
                shared[ci] = cur
            new_states, new_emitted, outs, dues = [], [], [], []
            for i, body in enumerate(bodies):
                inp = shared[cse_class[i]] if cse_class[i] is not None \
                    else batch
                st, tstates, em, out, due = body(
                    states[i], tstates, emitteds[i], inp, now)
                new_states.append(st)
                new_emitted.append(em)
                outs.append(out)
                dues.append(due)
            return (tuple(new_states), tstates, tuple(new_emitted),
                    tuple(outs), tuple(dues))

        return chain

    # -- compile ----------------------------------------------------------
    def _step_for(self):
        from ..core.runtime import _donate
        if self._step is None:
            self._step = jax.jit(self._chain, **_donate(0, 1, 2))
        return self._step

    def _packed_step_for(self, enc: tuple, capacity: int):
        from ..core.runtime import _build_packed_step
        fn = self._packed_steps.get((enc, capacity))
        if fn is None:
            fn = _build_packed_step(self._chain, self.junction.schema,
                                    enc, capacity,
                                    self.max_step_capacity,
                                    self.app._playback)
            self._packed_steps[(enc, capacity)] = fn
        return fn

    # -- locks ------------------------------------------------------------
    def _locks(self):
        stack = contextlib.ExitStack()
        for q in self.queries:  # unit order, segment order within chains
            stack.enter_context(q._lock)
        return stack

    def _table_locks(self):
        stack = contextlib.ExitStack()
        for t in self.table_deps:  # sorted — consistent lock order
            stack.enter_context(self.app.tables[t].lock)
        return stack

    # -- state marshalling ------------------------------------------------
    def _read_states(self):
        from ..core.runtime import FusedChain
        states, emitted = [], []
        for u in self.units:
            if isinstance(u, FusedChain):
                states.append(tuple(q.states for q in u.queries))
                emitted.append(tuple(q._emitted_dev for q in u.queries))
            else:
                states.append(u.states)
                emitted.append(u._emitted_dev)
        return tuple(states), tuple(emitted)

    def _write_states(self, states, emitted) -> None:
        from ..core.runtime import FusedChain
        for u, st, em in zip(self.units, states, emitted):
            if isinstance(u, FusedChain):
                for q, qs, qe in zip(u.queries, st, em):
                    q.states = qs
                    q._emitted_dev = qe
            else:
                u.states = st
                u._emitted_dev = em

    def _run(self, step, *args):
        with self._locks():
            with self._table_locks():
                tstates = {t: self.app.tables[t].state
                           for t in self.table_deps}
                states, emitted = self._read_states()
                states, tstates, emitted, outs, dues = step(
                    states, tstates, emitted, *args)
                for t in self.table_deps:
                    self.app.tables[t].state = tstates[t]
            self._write_states(states, emitted)
        return outs, dues

    # -- runtime ----------------------------------------------------------
    def _schedule_dues(self, dues, ts_min) -> None:
        from ..core.runtime import FusedChain
        for u, due in zip(self.units, dues):
            if isinstance(u, FusedChain):
                u._schedule_dues(due, ts_min)
                continue
            if not u._has_timers:
                continue
            if u._host_due_all and ts_min is not None:
                u._schedule(min(op.host_due_bound(ts_min)
                                for op in u._timer_ops))
            else:
                self.app.defer_due(u, due)

    def process_packed(self, chunk) -> None:
        cost = self.app.cost
        probe = cost.probe("fanout", self.name, cap=chunk.capacity) \
            if cost.enabled else None
        with self.app.tracer.span("fanout", self.name, rows=chunk.n,
                                  members=self.members):
            lats = [lat for h in self._heads
                    if (lat := h._stats_mark(chunk.n)) is not None]
            for q in self.queries:
                q._last_now = max(q._last_now, chunk.last_ts)
            outs, dues = self._run(
                self._packed_step_for(chunk.enc, chunk.capacity),
                chunk.buf)
            if lats or probe is not None:
                jax.block_until_ready([o.valid for o in outs])
                for lat in lats:
                    lat.mark_out()
                if probe is not None:
                    probe.done(rows=chunk.n)
            self._schedule_dues(dues, chunk.ts_min)
            for tail, out in zip(self._tails, outs):
                tail._dispatch_output(out, chunk.last_ts)

    def process_batch(self, batch, timestamp: int,
                      now: Optional[int] = None) -> None:
        from ..core.runtime import QueryRuntime
        cap = self.max_step_capacity
        if cap is not None and batch.capacity > cap:
            for sub in QueryRuntime.split_batch(batch, cap):
                self.process_batch(sub, timestamp, now=now)
            return
        cost = self.app.cost
        probe = cost.probe("fanout", self.name) if cost.enabled else None
        with self.app.tracer.span("fanout", self.name,
                                  members=self.members):
            if now is None:
                now = self.app.current_time()
            lats = [lat for h in self._heads
                    if (lat := h._stats_lat()) is not None]
            for q in self.queries:
                q._last_now = max(q._last_now, int(now))
            now_dev = jnp.asarray(now, dtype=jnp.int64)
            outs, dues = self._run(self._step_for(), batch, now_dev)
            if lats or probe is not None:
                jax.block_until_ready([o.valid for o in outs])
                for lat in lats:
                    lat.mark_out()
                if probe is not None:
                    probe.done(rows=int(batch.capacity))
            self._schedule_dues(dues, None)
            for tail, out in zip(self._tails, outs):
                tail._dispatch_output(out, timestamp)

"""Compiled-program auditor: static jaxpr/StableHLO verification of the
step programs `CompileService.specs` enumerates (docs/tpu_hygiene.md
"Compiled-program audit").

PR 16's semantic lint verifies the Python *source*; nothing verified
what XLA actually *compiled*. This module walks every program an app
can dispatch — row/packed steps, fused chains, fan-out groups,
pattern/timer/due steps, join sides, partition triggers, and the
serving pool's vmapped tenant-axis dispatches — lowers each with
abstract `jax.ShapeDtypeStruct` arguments (`core/compile.py
abstract_spec_args()`: ZERO executions, ZERO device allocations, ZERO
new compiles; trace + lower never reach XLA's backend compiler) and
checks the artifact against four rules:

- ``program-donation-aliasing`` (ERROR): every ``donate_argnums``
  buffer must appear in the lowered input-output alias table. XLA
  reports donated-but-unusable buffers at lowering time; a silent
  aliasing failure means the state update COPIES instead of updating
  in place — the perf-bug class ``_fresh_device`` exists to dance
  around (core/runtime.py). Buffers under ``donate_min_bytes``
  (default 64 KiB, ``SIDDHI_TPU_AUDIT_DONATE_MIN``) are counted but
  not findings: tiny scalars fall below XLA's own aliasing floor.
- ``program-host-boundary`` (ERROR): no ``pure_callback`` /
  ``io_callback`` / ``debug_callback`` (``jax.debug.print``) ops may
  appear anywhere in a hot-path program's jaxpr — a host round-trip
  per dispatched chunk is a silent 1000x.
- ``program-dtype-drift`` (WARNING): no weak-typed outputs on programs
  whose inputs are strongly typed (every spec argument is). A weak
  output is a Python-scalar promotion leaking into the artifact: it
  destabilizes jit cache keys and widens dtypes downstream
  (docs/compile_cache.md). Strong float64 outputs from declared
  DOUBLE schema columns are legitimate Siddhi semantics (``avg(int)``
  returns double) and are surfaced as counters, not findings.
- ``program-memory-budget`` (ERROR): the static per-program
  live-buffer estimate (args + outputs + jaxpr constants) rolled up
  per app/pool must fit the ``@app:cap(program.mb=)`` dial when one is
  set; the top-N largest programs ride the summary either way.

Findings flow through the standard analysis machinery — severities,
baselines, pragmas and SARIF come from `findings.py` / `baseline.py` /
`sarif.py`; rule metadata is registered in `analysis/__init__.py`.
The audit summary is stored on the app's `CompileService` so
`statistics()['compile']['audit']` and `ExplainReport.programs` stay
zero-trace views (the PR 15 explain contract: live telemetry, never
hashed).
"""
from __future__ import annotations

import dataclasses
import math
import os
import re
import warnings
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import jax.extend.core as jax_core
import numpy as np

from .findings import ERROR, WARNING, Finding

# default ingest bucket the audit enumerates specs for when the app has
# no SIDDHI_TPU_WARM_BUCKETS configured: the bench/default dispatch cap
DEFAULT_AUDIT_BUCKET = 1024

# donated-but-unaliased buffers below this are counted, not findings
DEFAULT_DONATE_MIN_BYTES = 64 * 1024

RULE_DONATION = "program-donation-aliasing"
RULE_HOST = "program-host-boundary"
RULE_DTYPE = "program-dtype-drift"
RULE_BUDGET = "program-memory-budget"

PROGRAM_RULES = (RULE_DONATION, RULE_HOST, RULE_DTYPE, RULE_BUDGET)

# jaxpr primitives that cross the host boundary inside a compiled step
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "infeed", "outfeed",
})

# "Some donated buffers were not usable: float64[512,64], int32[8]."
_UNALIASED_RE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")


def donate_min_bytes_from_env() -> int:
    raw = os.environ.get("SIDDHI_TPU_AUDIT_DONATE_MIN", "")
    return int(raw) if raw else DEFAULT_DONATE_MIN_BYTES


# ---------------------------------------------------------------------------
# per-program audit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ProgramAudit:
    """Static facts about one lowered step program."""

    key: str
    bytes_in: int = 0
    bytes_out: int = 0
    bytes_const: int = 0
    eqns: int = 0
    donated: int = 0            # donated argument buffers
    donated_bytes: int = 0
    unaliased: int = 0          # donated buffers XLA could not alias
    unaliased_bytes: int = 0
    weak_outputs: int = 0
    f64_outputs: int = 0
    error: Optional[str] = None  # spec failed to build/trace
    issues: list = dataclasses.field(default_factory=list)

    @property
    def bytes_total(self) -> int:
        return self.bytes_in + self.bytes_out + self.bytes_const


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    dtype = np.dtype(getattr(aval, "dtype", np.int64))
    return int(math.prod(shape)) * dtype.itemsize


def _iter_eqns(jaxpr):
    """Every equation in a jaxpr, recursing through control-flow
    sub-jaxprs (scan/while/cond branches, closed calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            yield from _iter_param_eqns(v)


def _iter_param_eqns(v):
    if isinstance(v, jax_core.ClosedJaxpr):
        yield from _iter_eqns(v.jaxpr)
    elif isinstance(v, jax_core.Jaxpr):
        yield from _iter_eqns(v)
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _iter_param_eqns(item)


def _as_struct(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(jnp.shape(x)), jnp.result_type(x))


def audit_spec(spec, donate_min_bytes: Optional[int] = None) -> ProgramAudit:
    """Trace + lower one CompileSpec abstractly and check the artifact.

    The builder runs inside `abstract_spec_args()` so its argument tree
    is pure `ShapeDtypeStruct`s — no device buffers, no fill programs.
    `fn.trace` gives the closed jaxpr (host-boundary / dtype / memory
    checks); `trace().lower()` runs only when the program donates
    buffers, and the donation-aliasing verdict comes from XLA's own
    "donated buffers were not usable" report captured at lowering.
    Neither step invokes the backend compiler: zero executables are
    built, the persistent-cache counters do not move.
    """
    from ..core.compile import abstract_spec_args
    if donate_min_bytes is None:
        donate_min_bytes = donate_min_bytes_from_env()
    pa = ProgramAudit(key=spec.key)
    try:
        with abstract_spec_args():
            fn, args = spec.build()
        if not hasattr(fn, "trace"):  # plain callable: wrap, no donation
            fn = jax.jit(fn)
        absargs = jax.tree_util.tree_map(_as_struct, args)
        with warnings.catch_warnings(record=True) as wlog:
            warnings.simplefilter("always")
            tr = fn.trace(*absargs)
            donated_flags = [
                bool(getattr(a, "donated", False))
                for a in jax.tree_util.tree_leaves(tr.args_info)]
            if any(donated_flags):
                tr.lower()  # aliasing is decided (and reported) here
    except Exception as e:  # noqa: BLE001 — an unbuildable spec is a
        # fact to report, not a crash: it would also fail to warm
        pa.error = f"{type(e).__name__}: {e}"
        return pa

    jx = tr.jaxpr
    in_avals = list(jx.in_avals)
    out_avals = list(jx.out_avals)
    pa.eqns = sum(1 for _ in _iter_eqns(jx.jaxpr))
    pa.bytes_in = sum(_aval_bytes(a) for a in in_avals)
    pa.bytes_out = sum(_aval_bytes(a) for a in out_avals)
    pa.bytes_const = sum(int(getattr(c, "nbytes", 0)) for c in jx.consts)

    # -- donation-aliasing ------------------------------------------------
    for flag, aval in zip(donated_flags, in_avals):
        if flag:
            pa.donated += 1
            pa.donated_bytes += _aval_bytes(aval)
    for w in wlog:
        msg = str(w.message)
        if "donated buffers were not usable" not in msg:
            continue
        for dt, shp in _UNALIASED_RE.findall(msg):
            shape = tuple(int(s) for s in shp.split(",") if s)
            nbytes = int(math.prod(shape)) * np.dtype(dt).itemsize
            pa.unaliased += 1
            pa.unaliased_bytes += nbytes
            if nbytes >= donate_min_bytes:
                pa.issues.append((RULE_DONATION, ERROR, (
                    f"{spec.key}: donated buffer {dt}[{shp}] "
                    f"({nbytes} bytes) is NOT in the lowered "
                    f"input-output alias table — the 'in-place' state "
                    f"update copies on every dispatch")))

    # -- host-boundary ----------------------------------------------------
    host_ops = sorted({eqn.primitive.name for eqn in _iter_eqns(jx.jaxpr)
                       if eqn.primitive.name in _CALLBACK_PRIMS})
    if host_ops:
        pa.issues.append((RULE_HOST, ERROR, (
            f"{spec.key}: host-boundary op(s) {', '.join(host_ops)} "
            f"baked into a jitted hot-path program — every dispatched "
            f"chunk round-trips to Python")))

    # -- dtype-drift ------------------------------------------------------
    in_f64 = any(np.dtype(getattr(a, "dtype", None)) == np.float64
                 for a in in_avals)
    for i, a in enumerate(out_avals):
        dt = np.dtype(getattr(a, "dtype", np.int64))
        if dt == np.float64:
            pa.f64_outputs += 1
        if getattr(a, "weak_type", False):
            pa.weak_outputs += 1
            extra = "" if in_f64 or dt != np.float64 else \
                " promoted to f64 from non-f64 inputs;"
            pa.issues.append((RULE_DTYPE, WARNING, (
                f"{spec.key}: output {i} is weak-typed {dt.name} —"
                f"{extra} a Python scalar leaked into the artifact; "
                f"jit cache keys and downstream dtypes drift "
                f"(docs/compile_cache.md)")))
    return pa


# ---------------------------------------------------------------------------
# app / pool rollup
# ---------------------------------------------------------------------------


class AuditReport:
    """Audit of one program set (an app runtime or a tenant pool):
    per-program facts, findings adapted to the analysis machinery, and
    a JSON-ready summary for statistics()/explain/bench."""

    def __init__(self, path: str, programs: list[ProgramAudit],
                 budget_mb: Optional[float] = None,
                 attribution: Optional[dict] = None,
                 disabled: Iterable[str] = (), top_n: int = 5):
        self.path = path
        self.programs = programs
        self.budget_mb = budget_mb
        self.attribution = dict(attribution or {})
        self.top_n = top_n
        disabled = set(disabled)
        issues = [iss for p in programs for iss in p.issues]
        total_mb = self.bytes_est_total / 1e6
        if budget_mb is not None and total_mb > float(budget_mb):
            top = ", ".join(f"{p.key}={p.bytes_total / 1e6:.1f}MB"
                            for p in self.top_programs())
            issues.append((RULE_BUDGET, ERROR, (
                f"program set estimates {total_mb:.1f}MB live buffers "
                f"vs @app:cap(program.mb={budget_mb}) — largest: "
                f"{top}")))
        self.findings = [
            Finding(rule=rule, severity=sev, path=path, line=1, col=0,
                    message=msg)
            for rule, sev, msg in issues
            if rule not in disabled and "*" not in disabled]

    @property
    def bytes_est_total(self) -> int:
        return sum(p.bytes_total for p in self.programs)

    def top_programs(self) -> list[ProgramAudit]:
        return sorted(self.programs, key=lambda p: -p.bytes_total)[
            : self.top_n]

    def summary(self) -> dict:
        """The block stored on CompileService.audit: rides
        statistics()['compile']['audit'], ExplainReport.programs and
        each bench config's JSON line. Live view — never hashed."""
        out = {
            "programs": len(self.programs),
            "bytes_est_total": self.bytes_est_total,
            "findings": len(self.findings),
            "donated": sum(p.donated for p in self.programs),
            "unaliased": sum(p.unaliased for p in self.programs),
            "weak_outputs": sum(p.weak_outputs for p in self.programs),
            "f64_outputs": sum(p.f64_outputs for p in self.programs),
            "top": [{"step": self._owned(p.key),
                     "mb": round(p.bytes_total / 1e6, 3)}
                    for p in self.top_programs()],
        }
        if self.budget_mb is not None:
            out["budget_mb"] = float(self.budget_mb)
        errors = [{"step": p.key, "error": p.error}
                  for p in self.programs if p.error]
        if errors:
            out["errors"] = errors
        return out

    def _owned(self, key: str) -> str:
        """Label a program with the member queries it serves (fan-out
        groups and fused chains compile under one key —
        plan/optimizer.py program_attribution)."""
        prefix = key.split("/", 1)[0]
        members = self.attribution.get(prefix)
        if members:
            return f"{key} [{'+'.join(members)}]"
        return key


def audit_specs(specs: list, *, path: str,
                budget_mb: Optional[float] = None,
                donate_min_bytes: Optional[int] = None,
                attribution: Optional[dict] = None,
                disabled: Iterable[str] = (),
                top_n: int = 5) -> AuditReport:
    """Audit an explicit spec list (the engine behind audit_runtime /
    audit_pool / the fixture mode of tools/audit.py)."""
    programs = [audit_spec(s, donate_min_bytes=donate_min_bytes)
                for s in specs]
    return AuditReport(path, programs, budget_mb=budget_mb,
                       attribution=attribution, disabled=disabled,
                       top_n=top_n)


def _budget_from_ast(app_ast) -> Optional[float]:
    """The @app:cap(program.mb=) dial, when the app sets one."""
    from ..lang import ast as A
    try:
        cap = A.find_annotation(app_ast.annotations, "cap")
        if cap is not None:
            raw = cap.element("program.mb")
            if raw is not None:
                return float(raw)
    except Exception:  # noqa: BLE001 — a malformed dial is a plan-rule
        return None    # problem, not an audit crash
    return None


def audit_runtime(rt, buckets=None, samples=None, *,
                  path: Optional[str] = None,
                  budget_mb: Optional[float] = None,
                  donate_min_bytes: Optional[int] = None,
                  disabled: Iterable[str] = (),
                  top_n: int = 5, store: bool = True) -> AuditReport:
    """Audit every program a SiddhiAppRuntime can dispatch for the
    given ingest buckets (default: SIDDHI_TPU_WARM_BUCKETS, else 1024).
    Zero executions, zero compiles, zero device reads; the summary is
    stored on the runtime's CompileService (`store=False` to skip)."""
    from ..core.compile import warm_buckets_from_env
    from ..plan.optimizer import program_attribution
    if not rt.running and rt._opt_decisions is None:
        # segments/groups must exist so the audited programs are the
        # ones traffic will dispatch (the warmup() contract). Skip when
        # a plan is already installed: REBUILDING drops the fused-chain
        # objects and their cached jit wrappers, and a warmed runtime's
        # audit must construct zero new ones
        rt._build_fused_chains()
    if buckets is None:
        buckets = warm_buckets_from_env() or (DEFAULT_AUDIT_BUCKET,)
    specs = rt.compile_service.specs(buckets, samples=samples)
    if budget_mb is None:
        budget_mb = _budget_from_ast(rt.ast)
    rep = audit_specs(
        specs, path=path or f"app/{rt.name}", budget_mb=budget_mb,
        donate_min_bytes=donate_min_bytes,
        attribution=program_attribution(rt), disabled=disabled,
        top_n=top_n)
    if store:
        rt.compile_service.audit = rep.summary()
    return rep


def audit_pool(pool, caps=None, *,
               path: Optional[str] = None,
               budget_mb: Optional[float] = None,
               donate_min_bytes: Optional[int] = None,
               disabled: Iterable[str] = (),
               top_n: int = 5, store: bool = True) -> AuditReport:
    """Audit a TenantPool's vmapped tenant-axis programs (the same
    template-keyed specs warmup() compiles — serving/pool.py). On mesh
    pools the audit sees the single-device twin of each program: slot
    placement needs concrete buffers, and the audit never builds any."""
    if budget_mb is None:
        budget_mb = _budget_from_ast(pool.proto.ast)
    specs = pool._warm_spec_list(caps)
    rep = audit_specs(
        specs, path=path or f"pool/{pool.name}", budget_mb=budget_mb,
        donate_min_bytes=donate_min_bytes, disabled=disabled,
        top_n=top_n)
    if store:
        pool.proto.compile_service.audit = rep.summary()
    return rep

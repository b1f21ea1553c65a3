"""TPU/JAX hygiene lint rules.

Static versions of the invariants this codebase already paid to learn
(see docs/tpu_hygiene.md and tests/test_dispatch_hygiene.py):

- a module-level jax array initializes the device backend at import
  (the importing process then holds the chip) and, captured by a jitted
  step, is one more device buffer per program (ops/sentinels.py);
- host syncs (``jax.device_get``, ``jax.block_until_ready``,
  ``.item()``, ``int()``/``float()`` on device values) inside Python
  loops serialize the device pipeline once per iteration instead of
  once per batch — timing probes must gate the sync on a sampling
  stride (the obs/costmodel.py probe pattern);
- Python control flow on traced values inside ``@jax.jit`` bodies either
  crashes at trace time or silently forces a concretization;
- Python scalars feeding shapes and non-hashable static args recompile
  the step per distinct value;
- explicit float64 dtypes flip on x64 promotion for the whole program.

Every rule reports ``file:line`` anchors and can be silenced with
``# lint: disable=<rule>`` or grandfathered via the checked-in baseline.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator

from .findings import ERROR, WARNING, Finding
from .linter import ModuleContext
from .registry import register

# jnp constructors whose result is a device array when called outside jit
# (dtype scalar constructors included: jnp.int64(0) is a device scalar)
_JNP = ("jax", "numpy")
_SHAPE_FNS = {"zeros", "ones", "empty", "full", "arange", "eye"}


def _finding(rule, severity, ctx, node, message) -> Finding:
    return Finding(rule=rule, severity=severity, path=ctx.path,
                   line=getattr(node, "lineno", 1),
                   col=getattr(node, "col_offset", 0), message=message)


def _runs_at_import(ctx: ModuleContext, node: ast.AST) -> bool:
    """True when `node` executes at import time: module body, class body,
    module-level ifs — and def-time positions (defaults, decorators)."""
    prev = node
    for anc in ctx.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if prev in anc.body:
                return False
        elif isinstance(anc, ast.Lambda):
            if prev is anc.body:
                return False
        prev = anc
    return True


def _mentions_jax(ctx: ModuleContext, node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Name, ast.Attribute)):
            c = ctx.canon(sub)
            if c and c[0] == "jax":
                return True
    return False


def _param_names(fn_node) -> set[str]:
    if isinstance(fn_node, ast.Lambda):
        a = fn_node.args
    else:
        a = fn_node.args
    names = {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


def _mentions_any_name(node: ast.AST, names: set[str]) -> bool:
    return any(isinstance(sub, ast.Name) and sub.id in names
               for sub in ast.walk(node))


def _src(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is best-effort
        return "<expr>"


# ---------------------------------------------------------------------
# rule: module-device-array
# ---------------------------------------------------------------------


@register(
    "module-device-array", ERROR,
    "a module-level jax array initializes the backend at import and is "
    "captured by jitted steps; module constants must be numpy")
def module_device_array(ctx: ModuleContext) -> Iterator[Finding]:
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        c = ctx.canon(node.func)
        if c is None:
            continue
        makes_array = (c[:2] == _JNP and len(c) > 2) \
            or c == ("jax", "device_put")
        if makes_array and _runs_at_import(ctx, node):
            yield _finding(
                "module-device-array", ERROR, ctx, node,
                f"'{'.'.join(c)}(...)' at import time creates a device "
                "array; use a numpy constant so jitted steps embed it as "
                "an HLO literal (module-level jax arrays poison the "
                "dispatch fast path)")


# ---------------------------------------------------------------------
# rule: host-sync-in-loop
# ---------------------------------------------------------------------


def _host_sync_reason(ctx: ModuleContext, call: ast.Call):
    """Classify a call as a device->host sync, or return None."""
    c = ctx.canon(call.func)
    if c == ("jax", "device_get"):
        return "jax.device_get"
    if c == ("jax", "block_until_ready"):
        # the cost-profiler/DETAIL-latency sync: legal only on a SAMPLED
        # branch outside the chunk loop (obs/costmodel.py probe pattern)
        return "jax.block_until_ready"
    if isinstance(call.func, ast.Attribute) and call.func.attr == "item" \
            and not call.args and not call.keywords:
        return f"{_src(call.func.value)}.item()"
    if c in (("numpy", "asarray"), ("numpy", "array")) and call.args \
            and _mentions_jax(ctx, call.args[0]):
        return f"np.{c[-1]} on a jax value"
    if isinstance(call.func, ast.Name) and call.func.id in ("int", "float") \
            and call.func.id not in ctx.alias_map and call.args \
            and _mentions_jax(ctx, call.args[0]):
        return f"{call.func.id}() on a jax value"
    return None


@register(
    "host-sync-in-loop", WARNING,
    "a device->host transfer inside a Python loop blocks the dispatch "
    "pipeline once per iteration; batch the transfers into one "
    "jax.device_get over a pytree")
def host_sync_in_loop(ctx: ModuleContext) -> Iterator[Finding]:
    flagged: dict[int, str] = {}
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        reason = _host_sync_reason(ctx, node)
        if reason and ctx.in_loop(node):
            flagged[id(node)] = reason
    for node in ctx.nodes:
        if id(node) not in flagged:
            continue
        # `int(jax.device_get(x))` is ONE sync: report the outermost call
        if any(id(anc) in flagged for anc in ctx.ancestors(node)):
            continue
        yield _finding(
            "host-sync-in-loop", WARNING, ctx, node,
            f"host sync '{flagged[id(node)]}' inside a loop — hoist it "
            "out or batch the values into a single jax.device_get pytree "
            "transfer")


# ---------------------------------------------------------------------
# rule: host-sync-in-jit
# ---------------------------------------------------------------------


@register(
    "host-sync-in-jit", ERROR,
    "device_get/.item()/int()/float() inside a jit-compiled body forces "
    "a concretization: trace-time failure or a silent host round-trip")
def host_sync_in_jit(ctx: ModuleContext) -> Iterator[Finding]:
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        fn = ctx.enclosing_jitted_function(node)
        if fn is None:
            continue
        c = ctx.canon(node.func)
        reason = None
        if c == ("jax", "device_get"):
            reason = "jax.device_get"
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" \
                and not node.args and not node.keywords:
            reason = f"{_src(node.func.value)}.item()"
        elif isinstance(node.func, ast.Name) \
                and node.func.id in ("int", "float") \
                and node.func.id not in ctx.alias_map \
                and node.args and not isinstance(node.args[0], ast.Constant):
            reason = f"{node.func.id}({_src(node.args[0])})"
        elif c in (("numpy", "asarray"), ("numpy", "array")) and node.args \
                and (_mentions_jax(ctx, node.args[0])
                     or _mentions_any_name(node.args[0], _param_names(fn))):
            reason = f"np.{c[-1]} on a traced value"
        if reason:
            yield _finding(
                "host-sync-in-jit", ERROR, ctx, node,
                f"'{reason}' inside a jit-compiled function — this "
                "concretizes a tracer (trace error) or forces a host "
                "round-trip on every call")


# ---------------------------------------------------------------------
# rule: traced-branch-in-jit
# ---------------------------------------------------------------------


@register(
    "traced-branch-in-jit", ERROR,
    "Python if/while on a traced value inside @jax.jit leaks the tracer; "
    "use jnp.where / jax.lax.cond / jax.lax.while_loop")
def traced_branch_in_jit(ctx: ModuleContext) -> Iterator[Finding]:
    for node in ctx.nodes:
        if not isinstance(node, (ast.If, ast.While)):
            continue
        if ctx.enclosing_jitted_function(node) is None:
            continue
        # a jax-rooted call in the test is a definite tracer boolean
        leaky = any(isinstance(sub, ast.Call)
                    and (ctx.canon(sub.func) or ("",))[0] == "jax"
                    for sub in ast.walk(node.test))
        if leaky:
            kw = "if" if isinstance(node, ast.If) else "while"
            yield _finding(
                "traced-branch-in-jit", ERROR, ctx, node,
                f"Python '{kw} {_src(node.test)}:' inside a jit-compiled "
                "function branches on a traced value — use jnp.where / "
                "jax.lax.cond / jax.lax.while_loop")


# ---------------------------------------------------------------------
# rule: recompile-hazard
# ---------------------------------------------------------------------


@register(
    "recompile-hazard", WARNING,
    "Python scalars feeding shapes, non-hashable static args, and "
    "per-call jax.jit wrapping trigger a fresh trace/compile per call")
def recompile_hazard(ctx: ModuleContext) -> Iterator[Finding]:
    for node in ctx.nodes:
        if isinstance(node, ast.Call):
            c = ctx.canon(node.func)
            if c == ("jax", "jit"):
                # a FRESH jit wrapper per iteration / per call retraces
                # every time: the in-memory jit cache is keyed on the
                # wrapped function object, so a new lambda/closure never
                # hits it (and re-pays persistent-cache lookups). Build
                # the jitted step once and cache it (instance attribute
                # or keyed dict — see core/runtime.py _step_for).
                if ctx.in_loop(node):
                    yield _finding(
                        "recompile-hazard", WARNING, ctx, node,
                        "jax.jit inside a loop builds a fresh jit "
                        "wrapper per iteration — each one retraces and "
                        "defeats the in-memory jit cache; hoist the "
                        "jit out of the loop and reuse it")
                    continue
                parent = ctx.parent(node)
                if isinstance(parent, ast.Call) and parent.func is node \
                        and ctx.enclosing_function(node) is not None:
                    yield _finding(
                        "recompile-hazard", WARNING, ctx, node,
                        "immediately-invoked jax.jit(...) in a per-call "
                        "path — the wrapper (and its trace) is rebuilt "
                        "on every call; cache the jitted function once "
                        "and dispatch through it")
                    continue
            fn = ctx.enclosing_jitted_function(node)
            if fn is None:
                continue
            # a BARE param in shape position is the hazard; x.shape/x.ndim
            # of a traced arg is static metadata and fine
            bare_param = node.args and any(
                isinstance(sub, ast.Name)
                and sub.id in _param_names(fn)
                and not isinstance(ctx.parent(sub), ast.Attribute)
                for sub in ast.walk(node.args[0]))
            if c and c[:2] == _JNP and len(c) == 3 \
                    and c[2] in _SHAPE_FNS and bare_param:
                yield _finding(
                    "recompile-hazard", WARNING, ctx, node,
                    f"parameter-dependent shape '{_src(node.args[0])}' in "
                    f"jnp.{c[2]} inside a jit-compiled function — each "
                    "distinct value recompiles the step (or fails to "
                    "trace); pass shapes via closure or static config")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and ctx.is_jitted(node):
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    yield _finding(
                        "recompile-hazard", WARNING, ctx, d,
                        f"mutable default '{_src(d)}' on jit-compiled "
                        f"'{node.name}' — non-hashable static args defeat "
                        "the jit cache and recompile per call")


# ---------------------------------------------------------------------
# rule: quadratic-grid-hazard
# ---------------------------------------------------------------------


def _broadcast_axis(sl: ast.AST):
    """Classify a subscript slice as a 2-D broadcast reshape:
    ``[:, None]`` -> "col" ([N,1] lanes), ``[None, :]`` -> "row"
    ([1,N] lanes), else None."""
    if not isinstance(sl, ast.Tuple) or len(sl.elts) != 2:
        return None
    a, b = sl.elts

    def is_none(x):
        return isinstance(x, ast.Constant) and x.value is None

    def is_full_slice(x):
        return isinstance(x, ast.Slice) and x.lower is None \
            and x.upper is None and x.step is None

    if is_full_slice(a) and is_none(b):
        return "col"
    if is_none(a) and is_full_slice(b):
        return "row"
    return None


@register(
    "quadratic-grid-hazard", WARNING,
    "an x[:, None] <op> y[None, :] broadcast materializes an [N, M] "
    "cross-product grid — O(B*W) device work/memory per step; use the "
    "banded searchsorted probe (ops/table.py sorted_key_view / the "
    "ops/join.py probe kernel) unless this is the blessed grid fallback")
def quadratic_grid_hazard(ctx: ModuleContext) -> Iterator[Finding]:
    """Flags expressions combining a ``[:, None]`` operand with a
    ``[None, :]`` operand — the broadcast [B, W]-style cross product
    whose cost grows with the PRODUCT of batch and buffer sizes. The
    intentional grid paths (the join grid fallback for non-equi ON
    conditions, table full-scan conditions, the cap-bounded NFA pending
    grids) are grandfathered via the checked-in baseline / inline
    pragmas; any NEW cross product must justify itself the same way."""
    for node in ctx.nodes:
        if not isinstance(node, (ast.BinOp, ast.Compare, ast.BoolOp)):
            continue
        # report the OUTERMOST expression of a grid chain once (an
        # inner BinOp nested through a Call, e.g. jnp.abs(a - b), still
        # belongs to its enclosing compare)
        if any(isinstance(anc, (ast.BinOp, ast.Compare, ast.BoolOp))
               for anc in ctx.ancestors(node)):
            continue
        axes = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Subscript):
                kind = _broadcast_axis(sub.slice)
                if kind:
                    axes.add(kind)
        if {"col", "row"} <= axes:
            yield _finding(
                "quadratic-grid-hazard", WARNING, ctx, node,
                "broadcast cross product ([:, None] against [None, :]) "
                "builds an [N, M] grid — quadratic in window/table "
                "size; probe a sorted key view (two searchsorteds + "
                "interval prefix sums) instead, or baseline/pragma the "
                "intentional grid fallback")


# ---------------------------------------------------------------------
# rule: cross-shard-transfer-hazard
# ---------------------------------------------------------------------

# names that carry a leading slot/shard axis somewhere in this codebase:
# the partition key-slot state (qstates/slot_tbl, parallel/partition.py),
# the tenant-pool stacked states/emitted counters (serving/pool.py), and
# the join side buffers (core/runtime.py) — on a mesh these are SHARDED
# over devices (parallel/sharding.py rule tables)
_SLOT_STATE_NAMES = {"qstates", "_states", "_emitted", "slot_tbl",
                     "side_states"}


def _mentions_slot_state(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _SLOT_STATE_NAMES:
            return True
        if isinstance(sub, ast.Name) and sub.id in _SLOT_STATE_NAMES:
            return True
    return False


@register(
    "cross-shard-transfer-hazard", WARNING,
    "jax.device_get/np.asarray on slot-axis state inside a loop pulls "
    "one (possibly cross-device) shard per iteration; batch per-shard "
    "reads through the one-read-per-device collection path "
    "(x.addressable_shards, or ONE device_get of the whole pytree)")
def cross_shard_transfer_hazard(ctx: ModuleContext) -> Iterator[Finding]:
    """On a mesh, `[K]`-leading / slot-axis state is sharded over
    devices (parallel/sharding.py): a `device_get`/`np.asarray` of it
    inside a Python loop gathers shards across the interconnect once
    per iteration — the multi-chip flavor of host-sync-in-loop.
    Sanctioned shapes: one batched `device_get` of the whole pytree
    outside the loop, or per-DEVICE `addressable_shards` reads (the
    serving/pool.py `_collect_sharded_locked` pattern — those args
    reference the shard objects, not the state names, so they pass)."""
    flagged: dict[int, str] = {}
    for node in ctx.nodes:
        if not isinstance(node, ast.Call) or not node.args:
            continue
        if not ctx.in_loop(node):
            continue
        c = ctx.canon(node.func)
        if c not in (("jax", "device_get"), ("numpy", "asarray"),
                     ("numpy", "array")):
            continue
        arg = node.args[0]
        # blessed: enumerating addressable shards IS the per-device
        # batched path
        if any(isinstance(s, ast.Attribute)
               and s.attr == "addressable_shards"
               for s in ast.walk(arg)):
            continue
        if _mentions_slot_state(arg):
            flagged[id(node)] = ".".join(c)
    for node in ctx.nodes:
        if id(node) not in flagged:
            continue
        if any(id(anc) in flagged for anc in ctx.ancestors(node)):
            continue  # one transfer, report the outermost call
        yield _finding(
            "cross-shard-transfer-hazard", WARNING, ctx, node,
            f"'{flagged[id(node)]}' on slot-axis state inside a loop — "
            "on a mesh this gathers a shard across devices per "
            "iteration; hoist ONE pytree device_get out of the loop or "
            "read per-device addressable_shards")


# ---------------------------------------------------------------------
# rule: unbounded-retry
# ---------------------------------------------------------------------

# exception type names that mark a handler as a transport-retry path
# (the reconnect loops in core/io.py); a generic `except Exception`
# keep-serving loop is NOT a retry loop and stays out of scope
_RETRY_EXC_RE = re.compile(r"Connection|Unavailable|Timeout|Retry",
                           re.I)


def _is_retry_handler(handler: ast.ExceptHandler) -> bool:
    types = []
    t = handler.type
    if isinstance(t, ast.Tuple):
        types = list(t.elts)
    elif t is not None:
        types = [t]
    for x in types:
        name = x.attr if isinstance(x, ast.Attribute) else \
            x.id if isinstance(x, ast.Name) else ""
        if name and _RETRY_EXC_RE.search(name):
            return True
    return False


def _has_backoff_call(node: ast.AST) -> bool:
    """A sleep/backoff inside the loop body: time.sleep(...), any
    .sleep(...) method, or a BackoffRetryCounter-style .next_wait_s()."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        f = sub.func
        if isinstance(f, ast.Attribute) and f.attr in ("sleep",
                                                       "next_wait_s"):
            return True
        if isinstance(f, ast.Name) and f.id == "sleep":
            return True
    return False


@register(
    "unbounded-retry", WARNING,
    "a while-True reconnect/retry loop with neither an attempt cap nor "
    "a backoff sleep hammers a dead transport and, fleet-wide, "
    "synchronizes into a retry storm; bound the attempts or back off "
    "with jitter (core/io.py BackoffRetryCounter)")
def unbounded_retry(ctx: ModuleContext) -> Iterator[Finding]:
    """Flags ``while True`` loops whose except handler catches a
    transport-flavored exception (Connection*/…Unavailable/Timeout/
    Retry) and then loops straight back around: no ``raise``/``break``/
    ``return`` anywhere in the handler (the attempt-cap exit) AND no
    sleep/backoff call anywhere in the loop body. The sanctioned shapes
    — ``attempt >= max_tries: raise`` plus
    ``time.sleep(backoff.next_wait_s())`` (core/io.py) — pass on both
    counts; a loop whose test is a real condition (``while attempt <
    n``) is bounded by construction and out of scope."""
    for node in ctx.nodes:
        if not isinstance(node, ast.While):
            continue
        test = node.test
        if not (isinstance(test, ast.Constant) and test.value):
            continue   # a conditional loop bounds itself
        if _has_backoff_call(node):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Try):
                continue
            for handler in sub.handlers:
                if not _is_retry_handler(handler):
                    continue
                bounded = any(isinstance(x, (ast.Raise, ast.Break,
                                             ast.Return))
                              for h in [handler]
                              for x in ast.walk(h))
                if bounded:
                    continue
                yield _finding(
                    "unbounded-retry", WARNING, ctx, handler,
                    "retry/reconnect loop without an attempt cap or a "
                    "backoff call — the handler swallows "
                    f"'{_src(handler.type) if handler.type else 'all'}' "
                    "and loops straight back; raise after a bounded "
                    "number of attempts or sleep a jittered backoff "
                    "(BackoffRetryCounter.next_wait_s)")


# ---------------------------------------------------------------------
# rule: float64-literal
# ---------------------------------------------------------------------


@register(
    "float64-literal", WARNING,
    "an explicit float64 dtype in device code depends on x64 mode and "
    "doubles memory/ALU cost on TPU; prefer float32 or jnp.float_")
def float64_literal(ctx: ModuleContext) -> Iterator[Finding]:
    f64 = (("jax", "numpy", "float64"), ("numpy", "float64"))
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        c = ctx.canon(node.func)
        if c == ("jax", "numpy", "float64"):
            yield _finding(
                "float64-literal", WARNING, ctx, node,
                "jnp.float64(...) literal promotes to x64 — on TPU this "
                "needs jax_enable_x64 and runs at half throughput")
            continue
        if not (c and c[0] == "jax"):
            continue
        for kw in node.keywords:
            if kw.arg != "dtype":
                continue
            kc = ctx.canon(kw.value)
            is_f64 = kc in f64 or (isinstance(kw.value, ast.Constant)
                                   and kw.value.value == "float64")
            if is_f64:
                yield _finding(
                    "float64-literal", WARNING, ctx, kw.value,
                    f"dtype=float64 in {'.'.join(c)}(...) triggers x64 "
                    "promotion — use float32 (or gate behind an explicit "
                    "x64 config) on TPU")


# ---------------------------------------------------------------------
# rule: bare-gauge-family
# ---------------------------------------------------------------------


@register(
    "bare-gauge-family", WARNING,
    "a labeled gauge family registered without a HELP string scrapes as "
    "an undocumented metric; pass help= to labeled_gauge (or describe() "
    "the family) so /metrics stays self-documenting")
def bare_gauge_family(ctx: ModuleContext) -> Iterator[Finding]:
    """Every ``labeled_gauge(family, labels, ...)`` call must carry a
    ``# HELP`` string: either the ``help=`` keyword (4th positional
    works too) or a ``describe(<same family literal>, ...)`` call in
    the same module. Labeled families are the cardinality-safe
    exposition shape (docs/observability.md "label conventions") —
    a family with no HELP line is a metric nobody can interpret from
    a scrape, which defeats the explain/metrics self-documentation
    contract. Plain ``gauge()`` instruments are exempt: collector-fed
    dotted gauges are documented by the statistics() schema."""
    described: set = set()
    for node in ctx.nodes:
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "describe" and node.args:
            a0 = node.args[0]
            if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
                described.add(a0.value)
    for node in ctx.nodes:
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr != "labeled_gauge":
            continue
        if len(node.args) >= 4:           # positional help=
            continue
        if any(kw.arg == "help" for kw in node.keywords):
            continue
        a0 = node.args[0] if node.args else None
        if isinstance(a0, ast.Constant) and isinstance(a0.value, str) \
                and a0.value in described:
            continue                       # family described() nearby
        yield _finding(
            "bare-gauge-family", WARNING, ctx, node,
            "labeled_gauge(...) without a HELP string — pass help= (or "
            "describe() the family) so the metric family is "
            "self-documenting in /metrics scrapes")


# ---------------------------------------------------------------------
# rule: per-row-encode-hazard
# ---------------------------------------------------------------------

_INGEST_VERBS = ("send", "encode", "ingest", "dispatch", "publish",
                 "flush", "emit")


def _ingest_fn_name(ctx: ModuleContext, node: ast.AST):
    """Name of the nearest enclosing function IF it sits on an ingest
    path (name carries an ingest verb); None otherwise. The name gate
    keeps row-oriented decode/callback helpers (`_decode_rows`, sink
    adapters) out of scope — those are the row API, not the hot path."""
    for anc in ctx.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            low = anc.name.lower()
            if any(v in low for v in _INGEST_VERBS):
                return anc.name
            return None
    return None


_COMP_NODES = (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)


def _loop_iter_exprs(node: ast.AST):
    if isinstance(node, (ast.For, ast.AsyncFor)):
        yield node.iter
    elif isinstance(node, _COMP_NODES):
        for gen in node.generators:
            yield gen.iter


@register(
    "per-row-encode-hazard", WARNING,
    "a Python-level per-row loop over event columns on an ingest path "
    "serializes the encoder at interpreter speed (~1e6 rows/s ceiling); "
    "keep the hot path columnar — numpy slicing and whole-lane bitcasts "
    "(core/ingest.py PackedEncoder), never per-row tuples")
def per_row_encode_hazard(ctx: ModuleContext) -> Iterator[Finding]:
    """Flags loops/comprehensions on ingest-path functions (send/encode/
    ingest/dispatch/publish/flush/emit in the name) whose ITERATION
    SOURCE materializes rows from columns: ``zip(*cols)`` transposes
    columns into per-row tuples, ``arr.tolist()`` boxes every element.
    Iterating columns per-COLUMN (``for c in cols``) stays clean — only
    the row-major blowup is the hazard."""
    for node in ctx.nodes:
        fn_name = None
        for it in _loop_iter_exprs(node):
            reason = None
            for sub in ast.walk(it):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Name) \
                        and sub.func.id == "zip" \
                        and any(isinstance(a, ast.Starred)
                                for a in sub.args):
                    reason = f"'{_src(sub)}' transposes columns into " \
                             "per-row tuples"
                    break
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "tolist" and not sub.args:
                    reason = f"'{_src(sub)}' boxes every element into " \
                             "a Python object"
                    break
            if reason is None:
                continue
            if fn_name is None:
                fn_name = _ingest_fn_name(ctx, node)
            if fn_name is None:
                break  # not an ingest-path function
            yield _finding(
                "per-row-encode-hazard", WARNING, ctx, it,
                f"per-row iteration in ingest-path '{fn_name}': {reason} "
                "— keep the encode columnar (numpy slices / vectorized "
                "ops) so chunk cost stays O(columns), not O(rows)")
            break  # one finding per loop

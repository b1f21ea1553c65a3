"""Expression compiler: query-api expression AST -> vectorized column ops.

The TPU-native replacement for the reference's typed executor trees
(modules/siddhi-core/.../util/parser/ExpressionParser.java:206 and the 164
executor classes under executor/). Instead of a per-event tree walk, each
expression compiles to a pure function over whole columns:

    fn(env: dict[key, Col]) -> Col      # Col = (values[B], nulls[B])

Java/Siddhi semantics preserved exactly:
- binary numeric promotion (int<long<float<double), wrapping int arithmetic
- math on null -> null; divide/modulo by zero -> null (all numeric types,
  executor/math/divide/DivideExpressionExecutorDouble.java:46-48)
- integer division/remainder truncate toward zero (Java `/` `%`)
- compare with null operand -> FALSE, never null
  (executor/condition/compare/CompareConditionExpressionExecutor.java:38-42)
- and/or treat null as false; not(null) -> TRUE
  (AndConditionExpressionExecutor.java:65-73, NotConditionExpressionExecutor.java:43-50)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import (AttrType, GLOBAL_STRINGS, NUMERIC_TYPES,
                          comparable, np_dtype, promote)
from ..lang import ast as A


class CompileError(Exception):
    pass


@dataclasses.dataclass
class Col:
    """A column: device values plus null mask (both [B] or scalar)."""
    values: Any
    nulls: Any

    @classmethod
    def const(cls, value, t: AttrType):
        # numpy scalars, NOT jnp: constants are built at plan time and
        # captured by jitted steps; numpy scalars embed as HLO literals,
        # a captured device array is one more buffer per program
        # (ops/sentinels.py).
        dt = np_dtype(t)
        if value is None:
            v = np.zeros((), dtype=dt)
            n = np.ones((), dtype=np.bool_)
        else:
            if t is AttrType.STRING:
                value = GLOBAL_STRINGS.encode(value)
            v = np.asarray(value, dtype=dt)
            n = np.zeros((), dtype=np.bool_)
        return cls(v, n)


@dataclasses.dataclass
class CompiledExpr:
    type: AttrType
    fn: Callable[[dict], Col]
    const_value: Any = None     # set when the expression is a literal
    is_const: bool = False


class Scope:
    """Variable resolution at compile time.

    Maps a Variable (stream_ref/attribute[/index]) to an env key and type.
    Concrete scopes are provided by the planner (single stream, join sides,
    pattern state events).
    """

    def resolve(self, var: A.Variable) -> tuple[Any, AttrType]:
        raise NotImplementedError

    def resolve_stream_isnull(self, is_null: A.IsNull):
        raise CompileError("stream is null not supported in this context")


class SingleStreamScope(Scope):
    """One input stream: variables resolve to ('attr', index)."""

    def __init__(self, schema, aliases=()):
        self.schema = schema
        self.aliases = {a for a in aliases if a}

    def resolve(self, var: A.Variable):
        ref = var.stream_ref
        if ref is not None and ref != self.schema.stream_id and ref not in self.aliases:
            raise CompileError(
                f"unknown stream reference '{ref}' (expected "
                f"'{self.schema.stream_id}')")
        idx = self.schema.index_of(var.attribute)
        return ("attr", idx), self.schema.types[idx]


def _set_encode_elem(values, t: AttrType):
    """Encode a primitive column to the int64 set-lane representation."""
    if t in (AttrType.FLOAT, AttrType.DOUBLE):
        import jax
        return jax.lax.bitcast_convert_type(
            values.astype(jnp.float64), jnp.int64)
    return values.astype(jnp.int64)


def env_from_batch(batch) -> dict:
    """Standard env for a single-stream batch."""
    env = {("attr", i): Col(batch.cols[i], batch.nulls[i])
           for i in range(len(batch.cols))}
    env["__ts__"] = Col(batch.ts, jnp.zeros_like(batch.valid))
    return env


def collect_template_params(*exprs) -> tuple:
    """((name, AttrType), ...) for every `${name:type}` placeholder in the
    given expression trees, first-use order, deduplicated. Untyped or
    type-conflicting placeholders raise CompileError (the template-binding
    plan rule reports the same conditions with query anchors earlier)."""
    out: list = []
    seen: dict = {}
    for expr in exprs:
        if expr is None:
            continue
        for e in A.walk_expressions(expr):
            if not isinstance(e, A.TemplateParam):
                continue
            if e.type is None:
                raise CompileError(
                    f"template placeholder '${{{e.name}}}' has no "
                    "declared type")
            prev = seen.get(e.name)
            if prev is None:
                seen[e.name] = e.type
                out.append((e.name, e.type))
            elif prev is not e.type:
                raise CompileError(
                    f"template placeholder '${{{e.name}}}' declared with "
                    f"conflicting types {prev.value} and {e.type.value}")
    return tuple(out)


def tparam_env(env: dict, tparams: tuple, state) -> None:
    """Thread per-tenant parameter values from an operator's state pytree
    into a compiled-expression env (scalars per trace; a (slots,) stacked
    axis once the serving pool vmaps the step over tenants)."""
    vals = state["tparams"]
    for name, _t in tparams:
        env[("tparam", name)] = Col(vals[name],
                                    jnp.zeros((), dtype=jnp.bool_))


def tparam_init_state(tparams: tuple) -> dict:
    """Zero-valued parameter state for an operator that reads template
    params ({'tparams': {name: 0-d array}}); the pool overwrites the
    tenant's slot at add_tenant time."""
    return {"tparams": {n: jnp.zeros((), dtype=np_dtype(t))
                        for n, t in tparams}}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _to_dtype(col: Col, t: AttrType) -> Col:
    return Col(col.values.astype(np_dtype(t)), col.nulls)


def _num(e: CompiledExpr, what: str) -> None:
    if e.type not in NUMERIC_TYPES:
        raise CompileError(f"{what} requires a numeric operand, got {e.type}")


# ---------------------------------------------------------------------------
# main compile dispatch
# ---------------------------------------------------------------------------


def compile_expression(expr: A.Expression, scope: Scope,
                       functions: Optional[dict] = None) -> CompiledExpr:
    functions = functions or {}

    def comp(e: A.Expression) -> CompiledExpr:
        if isinstance(e, A.Constant):
            t = e.type
            if e.value is None:
                # NULL literal: typed when the AST says so (e.g. an
                # out-of-range e[i].attr rewritten to the attribute's
                # type), DOUBLE otherwise
                nt = t if isinstance(t, AttrType) else AttrType.DOUBLE
                cv = Col.const(None, nt)
                return CompiledExpr(nt, lambda env, c=cv: c,
                                    const_value=None, is_const=True)
            cv = Col.const(e.value, t)
            return CompiledExpr(t, lambda env, c=cv: c,
                                const_value=e.value, is_const=True)

        if isinstance(e, A.Variable):
            if e.attribute is None:
                raise CompileError(f"bare stream reference '{e.stream_ref}' "
                                   "only valid in IS NULL")
            key, t = scope.resolve(e)
            return CompiledExpr(t, lambda env, k=key: env[k])

        if isinstance(e, A.TemplateParam):
            # tenant-template placeholder: a RUNTIME read of a per-tenant
            # parameter the operator carries in its state pytree (FilterOp
            # / ProjectOp thread them into env under ("tparam", name)).
            # NOT a baked constant — that is what lets every tenant of a
            # template share one jitted step (serving/pool.py vmaps the
            # step over the stacked parameter axis).
            if e.type is None:
                raise CompileError(
                    f"template placeholder '${{{e.name}}}' has no "
                    "declared type — structural placeholders must be "
                    "bound before planning (serving/template.py)")

            def fn(env, name=e.name):
                col = env.get(("tparam", name))
                if col is None:
                    raise CompileError(
                        f"template param '${{{name}}}' reached an "
                        "operator that does not carry tenant parameters "
                        "(params are supported in filter conditions and "
                        "non-aggregating select/having only)")
                return col
            return CompiledExpr(e.type, fn)

        if isinstance(e, A.MathOp):
            return _compile_math(e, comp)

        if isinstance(e, A.Compare):
            return _compile_compare(e, comp)

        if isinstance(e, A.And):
            l, r = comp(e.left), comp(e.right)
            _require_bool(l, "AND"), _require_bool(r, "AND")

            def fn(env):
                lc, rc = l.fn(env), r.fn(env)
                v = (lc.values & ~lc.nulls) & (rc.values & ~rc.nulls)
                return Col(v, jnp.zeros_like(v))
            return CompiledExpr(AttrType.BOOL, fn)

        if isinstance(e, A.Or):
            l, r = comp(e.left), comp(e.right)
            _require_bool(l, "OR"), _require_bool(r, "OR")

            def fn(env):
                lc, rc = l.fn(env), r.fn(env)
                v = (lc.values & ~lc.nulls) | (rc.values & ~rc.nulls)
                return Col(v, jnp.zeros_like(v))
            return CompiledExpr(AttrType.BOOL, fn)

        if isinstance(e, A.Not):
            x = comp(e.expr)
            _require_bool(x, "NOT")

            def fn(env):
                c = x.fn(env)
                v = ~(c.values & ~c.nulls)
                return Col(v, jnp.zeros_like(v))
            return CompiledExpr(AttrType.BOOL, fn)

        if isinstance(e, A.IsNull):
            if e.expr is None:
                return scope.resolve_stream_isnull(e)
            x = comp(e.expr)

            def fn(env):
                c = x.fn(env)
                v = c.nulls | jnp.zeros_like(c.nulls)
                return Col(v, jnp.zeros_like(v))
            return CompiledExpr(AttrType.BOOL, fn)

        if isinstance(e, A.InTable):
            raise CompileError("IN <table> must be planned by the query "
                               "planner (table containment)")

        if isinstance(e, A.AttributeFunction):
            return _compile_function(e, comp, scope, functions)

        raise CompileError(f"cannot compile expression {e!r}")

    return comp(expr)


def _require_bool(e: CompiledExpr, what: str):
    if e.type is not AttrType.BOOL:
        raise CompileError(
            f"{what} requires BOOL operands, got {e.type} "
            "(reference: AndConditionExpressionExecutor type check)")


def _compile_math(e: A.MathOp, comp) -> CompiledExpr:
    l, r = comp(e.left), comp(e.right)
    _num(l, f"'{e.op}'"), _num(r, f"'{e.op}'")
    t = promote(l.type, r.type)
    dt = np_dtype(t)
    op = e.op

    def fn(env):
        lc, rc = l.fn(env), r.fn(env)
        lv = lc.values.astype(dt)
        rv = rc.values.astype(dt)
        nulls = lc.nulls | rc.nulls
        if op == "+":
            v = lv + rv
        elif op == "-":
            v = lv - rv
        elif op == "*":
            v = lv * rv
        elif op == "/":
            zero = rv == 0
            nulls = nulls | zero
            safe_r = jnp.where(zero, jnp.ones_like(rv), rv)
            if t in (AttrType.INT, AttrType.LONG):
                v = jax.lax.div(lv, safe_r)  # truncation toward zero (Java /)
            else:
                v = lv / safe_r
        elif op == "%":
            zero = rv == 0
            nulls = nulls | zero
            safe_r = jnp.where(zero, jnp.ones_like(rv), rv)
            v = jax.lax.rem(lv, safe_r)  # sign of dividend (Java %)
        else:
            raise AssertionError(op)
        v = jnp.where(nulls, jnp.zeros_like(v), v)
        return Col(v, nulls)

    return CompiledExpr(t, fn)


def _compile_compare(e: A.Compare, comp) -> CompiledExpr:
    l, r = comp(e.left), comp(e.right)
    op = e.op
    if not comparable(l.type, r.type):
        # defense in depth for the static `string-numeric-compare` rule:
        # STRING columns are int32 dictionary codes on device, so a
        # STRING vs numeric comparison would relate codes, not text —
        # reject it explicitly instead of ever falling into a numeric
        # path (STRING vs STRING equality stays supported below)
        if (l.type is AttrType.STRING) != (r.type is AttrType.STRING):
            other = r.type if l.type is AttrType.STRING else l.type
            raise CompileError(
                f"cannot compare STRING with {other}: device strings "
                "are int32 dictionary codes — the comparison would "
                "relate codes, not text")
        raise CompileError(f"cannot compare {l.type} with {r.type}")
    if l.type in NUMERIC_TYPES and r.type in NUMERIC_TYPES:
        t = promote(l.type, r.type)
        dt = np_dtype(t)

        def fn(env):
            lc, rc = l.fn(env), r.fn(env)
            lv = lc.values.astype(dt)
            rv = rc.values.astype(dt)
            v = _cmp(op, lv, rv)
            v = v & ~(lc.nulls | rc.nulls)  # null operand -> FALSE
            return Col(v, jnp.zeros_like(v))
        return CompiledExpr(AttrType.BOOL, fn)

    # comparable() guarantees same-type STRING/BOOL here
    if op not in ("==", "!=") and l.type is AttrType.STRING:
        raise CompileError(
            "ordering comparison on STRING is not supported on device")

    def fn(env):
        lc, rc = l.fn(env), r.fn(env)
        v = _cmp(op, lc.values, rc.values)
        v = v & ~(lc.nulls | rc.nulls)
        return Col(v, jnp.zeros_like(v))
    return CompiledExpr(AttrType.BOOL, fn)


def _cmp(op, lv, rv):
    if op == "==":
        return lv == rv
    if op == "!=":
        return lv != rv
    if op == ">":
        return lv > rv
    if op == ">=":
        return lv >= rv
    if op == "<":
        return lv < rv
    if op == "<=":
        return lv <= rv
    raise AssertionError(op)


# ---------------------------------------------------------------------------
# built-in scalar functions
# (reference: executor/function/*.java — cast, convert, coalesce, ifThenElse,
#  instanceOf*, maximum, minimum, eventTimestamp, currentTimeMillis, default)
# ---------------------------------------------------------------------------

_CONVERT_TARGETS = {
    "int": AttrType.INT, "long": AttrType.LONG, "float": AttrType.FLOAT,
    "double": AttrType.DOUBLE, "bool": AttrType.BOOL, "string": AttrType.STRING,
}


def _compile_function(e: A.AttributeFunction, comp, scope, functions) -> CompiledExpr:
    name = (f"{e.namespace}:{e.name}" if e.namespace else e.name)
    key = name.lower()
    params = [comp(p) for p in e.parameters]

    if key in functions:
        return functions[key](params)

    if key in ("convert", "cast"):
        if len(params) != 2:
            raise CompileError(f"{name}() requires 2 arguments")
        target_const = e.parameters[1]
        if not isinstance(target_const, A.Constant):
            raise CompileError(f"{name}() target type must be a constant")
        tname = str(target_const.value).lower()
        if tname not in _CONVERT_TARGETS:
            raise CompileError(f"unknown {name}() target '{tname}'")
        t = _CONVERT_TARGETS[tname]
        src = params[0]
        if t is AttrType.STRING or src.type is AttrType.STRING and t is not AttrType.STRING:
            if not (src.type is AttrType.STRING and t is AttrType.STRING):
                raise CompileError(
                    f"{name}() to/from STRING is host-side only; not "
                    "supported on the device path yet")
        if t is AttrType.BOOL and src.type is not AttrType.BOOL:
            raise CompileError(f"{name}() numeric->BOOL not supported")
        dt = np_dtype(t)

        def fn(env, src=src, dt=dt):
            c = src.fn(env)
            return Col(c.values.astype(dt), c.nulls)
        return CompiledExpr(t, fn)

    if key == "coalesce":
        if not params:
            raise CompileError("coalesce() requires arguments")
        t = params[0].type
        for p in params[1:]:
            if p.type in NUMERIC_TYPES and t in NUMERIC_TYPES:
                t = promote(t, p.type)
            elif p.type != t:
                raise CompileError("coalesce() arguments must share a type")
        dt = np_dtype(t)

        def fn(env):
            cols = [p.fn(env) for p in params]
            v = cols[0].values.astype(dt)
            nulls = cols[0].nulls
            for c in cols[1:]:
                take = nulls & ~c.nulls
                v = jnp.where(take, c.values.astype(dt), v)
                nulls = nulls & c.nulls
            return Col(v, nulls)
        return CompiledExpr(t, fn)

    if key == "ifthenelse":
        if len(params) != 3:
            raise CompileError("ifThenElse() requires 3 arguments")
        cond, a, b = params
        _require_bool(cond, "ifThenElse condition")
        if a.type in NUMERIC_TYPES and b.type in NUMERIC_TYPES:
            t = promote(a.type, b.type)
        elif a.type == b.type:
            t = a.type
        else:
            raise CompileError("ifThenElse() branches must share a type")
        dt = np_dtype(t)

        def fn(env):
            cc, ca, cb = cond.fn(env), a.fn(env), b.fn(env)
            take_a = cc.values & ~cc.nulls
            v = jnp.where(take_a, ca.values.astype(dt), cb.values.astype(dt))
            nulls = jnp.where(take_a, ca.nulls, cb.nulls)
            return Col(v, nulls)
        return CompiledExpr(t, fn)

    if key in ("maximum", "minimum"):
        if not params:
            raise CompileError(f"{name}() requires arguments")
        t = params[0].type
        for p in params:
            _num(p, name)
            t = promote(t, p.type)
        dt = np_dtype(t)
        is_max = key == "maximum"

        def fn(env):
            cols = [p.fn(env) for p in params]
            v, nulls = cols[0].values.astype(dt), cols[0].nulls
            for c in cols[1:]:
                cv = c.values.astype(dt)
                pick = (_cmp(">" if is_max else "<", cv, v) & ~c.nulls) | nulls
                v = jnp.where(pick & ~c.nulls, cv, v)
                nulls = nulls & c.nulls
            v = jnp.where(nulls, jnp.zeros_like(v), v)
            return Col(v, nulls)
        return CompiledExpr(t, fn)

    if key == "uuid":
        # device rows carry the sentinel code; the string table decodes
        # each row to a fresh UUID at the host boundary. Device-side
        # equality between two uuid() columns degenerates (both are the
        # sentinel) — documented; the reference evaluates per event on
        # the host, which is exactly where our decode runs.
        if params:
            raise CompileError("uuid() takes no arguments")
        from ..core.types import UUID_MARKER
        code = GLOBAL_STRINGS.encode(UUID_MARKER)

        def fn(env, code=code):
            ts = env["__ts__"]
            shape = ts.values.shape if hasattr(ts.values, "shape") else ()
            return Col(jnp.full(shape, code, jnp.int32),
                       jnp.zeros(shape, jnp.bool_))
        return CompiledExpr(AttrType.STRING, fn)

    if key == "createset":
        # CreateSetFunctionExecutor.java: singleton java.util.Set. Device
        # design: a SET value is a fixed [1 + SET_LANES] int64 vector —
        # lane 0 a type tag, lanes 1.. the encoded elements (numerics
        # promoted/bit-cast, strings as dictionary codes), empty lanes
        # SET_EMPTY. Set columns are 2D [rows, 1+S] and decode to python
        # frozensets at the host boundary.
        from ..core.types import SET_EMPTY, SET_LANES, set_tag_of
        if len(params) != 1:
            raise CompileError(
                "createSet() function has to have exactly 1 parameter")
        src = params[0]
        tag = set_tag_of(src.type)

        def fn(env, src=src, tag=tag):
            c = src.fn(env)
            v = _set_encode_elem(c.values, src.type)
            v = jnp.where(c.nulls, jnp.int64(SET_EMPTY), v)
            shape = jnp.shape(v)
            lanes = [jnp.broadcast_to(jnp.int64(tag), shape)[..., None],
                     v[..., None]]
            lanes.append(jnp.broadcast_to(
                jnp.int64(SET_EMPTY), shape + (SET_LANES - 1,)))
            return Col(jnp.concatenate(lanes, axis=-1),
                       jnp.zeros(shape, jnp.bool_))
        return CompiledExpr(AttrType.OBJECT, fn)

    if key == "sizeofset":
        from ..core.types import SET_EMPTY
        if len(params) != 1:
            raise CompileError(
                "sizeOfSet() function has to have exactly 1 parameter")
        src = params[0]
        if src.type is not AttrType.OBJECT:
            raise CompileError(
                "sizeOfSet() parameter should be a set object "
                "(createSet()/unionSet() result)")

        def fn(env, src=src):
            c = src.fn(env)
            n = jnp.sum((c.values[..., 1:] != SET_EMPTY)
                        .astype(jnp.int32), axis=-1)
            return Col(n, c.nulls)
        return CompiledExpr(AttrType.INT, fn)

    if key == "eventtimestamp":
        def fn(env):
            return env["__ts__"]
        return CompiledExpr(AttrType.LONG, fn)

    if key == "currenttimemillis":
        def fn(env):
            now = env["__now__"]
            return Col(now, jnp.zeros((), dtype=jnp.bool_))
        return CompiledExpr(AttrType.LONG, fn)

    if key.startswith("instanceof"):
        target = {"instanceofinteger": AttrType.INT,
                  "instanceoflong": AttrType.LONG,
                  "instanceoffloat": AttrType.FLOAT,
                  "instanceofdouble": AttrType.DOUBLE,
                  "instanceofboolean": AttrType.BOOL,
                  "instanceofstring": AttrType.STRING}.get(key)
        if target is None:
            raise CompileError(f"unknown function '{name}'")
        if len(params) != 1:
            raise CompileError(f"{name}() requires 1 argument")
        src = params[0]
        match = src.type is target

        def fn(env, src=src, match=match):
            c = src.fn(env)
            # statically-typed columns: instanceOf is type match AND non-null
            v = jnp.where(c.nulls, False, match)
            return Col(v, jnp.zeros_like(c.nulls))
        return CompiledExpr(AttrType.BOOL, fn)

    if key == "default":
        if len(params) != 2:
            raise CompileError("default() requires 2 arguments")
        src, dflt = params
        if src.type == dflt.type:
            t = src.type
        elif src.type in NUMERIC_TYPES and dflt.type in NUMERIC_TYPES:
            t = promote(src.type, dflt.type)
        else:
            raise CompileError(
                f"default() arguments must share a type, got {src.type} "
                f"and {dflt.type}")
        dt = np_dtype(t)

        def fn(env):
            c, d = src.fn(env), dflt.fn(env)
            v = jnp.where(c.nulls, d.values.astype(dt), c.values.astype(dt))
            return Col(v, c.nulls & d.nulls)
        return CompiledExpr(t, fn)

    if key.startswith("math:"):
        return _compile_math_ns(key[5:], name, params)

    raise CompileError(f"unknown function '{name}'")


_MATH_UNARY = {
    "abs": jnp.abs, "ceil": jnp.ceil, "floor": jnp.floor, "sqrt": jnp.sqrt,
    "exp": jnp.exp, "ln": jnp.log, "log10": jnp.log10, "sin": jnp.sin,
    "cos": jnp.cos, "tan": jnp.tan, "asin": jnp.arcsin, "acos": jnp.arccos,
    "atan": jnp.arctan, "signum": jnp.sign, "round": jnp.round,
}


def _compile_math_ns(fn_name: str, display: str, params) -> CompiledExpr:
    if fn_name in _MATH_UNARY and len(params) == 1:
        src = params[0]
        _num(src, display)
        out_t = AttrType.DOUBLE if fn_name != "abs" else src.type
        jfn = _MATH_UNARY[fn_name]
        dt = np_dtype(out_t)

        def fn(env):
            c = src.fn(env)
            v = jfn(c.values.astype(dt) if out_t is AttrType.DOUBLE else c.values)
            v = jnp.where(c.nulls, jnp.zeros_like(v), v)
            return Col(v.astype(dt), c.nulls)
        return CompiledExpr(out_t, fn)
    if fn_name == "power" and len(params) == 2:
        a, b = params
        _num(a, display), _num(b, display)

        def fn(env):
            ca, cb = a.fn(env), b.fn(env)
            v = jnp.power(ca.values.astype(jnp.float64),
                          cb.values.astype(jnp.float64))
            nulls = ca.nulls | cb.nulls
            return Col(jnp.where(nulls, 0.0, v), nulls)
        return CompiledExpr(AttrType.DOUBLE, fn)
    raise CompileError(f"unknown function '{display}'")

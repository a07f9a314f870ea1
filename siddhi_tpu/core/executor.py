"""Expression compiler: query-api Expression AST -> vectorized jax functions.

The analog of the reference's compiled scalar executor trees
(reference: core/executor/ExpressionExecutor.java and the per-type classes built by
core/util/parser/ExpressionParser.java:215-530) — except each compiled node maps a
whole columnar batch at once: `fn(env) -> Array` where `env` supplies `[B]`- (or
`[B, W]`- for join probes) shaped attribute columns. Type promotion follows the
reference's executor-selection matrix (DOUBLE > FLOAT > LONG > INT); integer
divide/mod use Java truncation semantics via lax.div/lax.rem.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.lax as lax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.types import (
    NUMERIC_TYPES,
    PHYSICAL_DTYPE,
    AttrType,
    InternTable,
    null_value,
    promote,
)
from siddhi_tpu.query_api.expression import (
    Add,
    And,
    AttributeFunction,
    Compare,
    CompareOp,
    Constant,
    Divide,
    Expression,
    In,
    IsNull,
    Mod,
    Multiply,
    Not,
    Or,
    Subtract,
    Variable,
)

# Canonical variable key: (stream_ref, stream_index, attribute). stream_ref is the
# scope-canonicalized alias; TS_ATTR keys the timestamp lane.
VarKey = tuple[str, Optional[int], str]
TS_ATTR = "__ts__"
VALID_ATTR = "__valid__"


class Env:
    """Runtime (trace-time) column provider for a compiled expression."""

    def __init__(
        self,
        columns: dict[VarKey, jnp.ndarray],
        now: jnp.ndarray | None = None,
        tables: dict[str, dict] | None = None,
    ):
        self.columns = columns
        self._now = now
        self.tables = tables or {}

    def read(self, key: VarKey) -> jnp.ndarray:
        try:
            return self.columns[key]
        except KeyError:
            raise KeyError(f"env missing column {key}; has {list(self.columns)}") from None

    def now(self) -> jnp.ndarray:
        if self._now is None:
            raise ValueError("this site does not provide currentTimeMillis")
        return self._now


@dataclasses.dataclass
class CompiledExpr:
    type: AttrType
    fn: Callable[[Env], jnp.ndarray]
    # compile-time constant value, when statically known (for window params etc.)
    const: object = None
    is_const: bool = False

    def __call__(self, env: Env) -> jnp.ndarray:
        return self.fn(env)


class Scope:
    """Compile-time name resolution: Variable -> (VarKey, AttrType).

    Concrete scopes are built by the query parser layer for each expression site
    (filter over one stream, join condition over two, pattern over state refs,
    having over selector outputs...).
    """

    def __init__(self, interner: InternTable, default_ref: str | None = None):
        self.interner = interner
        self.default_ref = default_ref
        # every VarKey any expression compiled against this scope (or a child)
        # resolved — env builders consult this to materialize indexed-capture
        # columns (e1[3], e2[last]) including out-of-range/-negative indices
        self.used_keys: set[VarKey] = set()
        # pattern-node filters resolve unqualified attrs to the CURRENT event's
        # stream even when earlier state refs carry the same attribute
        # (reference: MatchingMetaInfoHolder default stream-event index)
        self.prefer_default = False
        # in-table conditions resolve unqualified attrs against the OUTER
        # (stream) scope before the table's own columns (reference:
        # CollectionExpressionParser matching-side resolution)
        self.prefer_parent = False
        self._streams: dict[str, dict[str, AttrType]] = {}
        self._tables: dict[str, object] = {}
        self._parent: Scope | None = None

    def add_table(self, table) -> "Scope":
        """Register an InMemoryTable handle for `in <table>` conditions."""
        self._tables[table.table_id] = table
        return self

    def resolve_table(self, name: str):
        scope: Scope | None = self
        while scope is not None:
            if name in scope._tables:
                return scope._tables[name]
            scope = scope._parent
        return None

    def add_stream(self, ref: str, attrs: dict[str, AttrType]) -> "Scope":
        self._streams[ref] = dict(attrs)
        if self.default_ref is None:
            self.default_ref = ref
        return self

    def child(self) -> "Scope":
        c = Scope(self.interner, self.default_ref)
        c._parent = self
        return c

    def refs(self) -> list[str]:
        return list(self._streams)

    def record_key(self, key: VarKey) -> None:
        # record at every level so a compile site can read exactly the keys
        # ITS expressions resolved from its own child scope, while the root
        # accumulates the full set for env builders
        scope: Scope | None = self
        while scope is not None:
            scope.used_keys.add(key)
            scope = scope._parent

    def root_used_keys(self) -> set[VarKey]:
        scope: Scope = self
        while scope._parent is not None:
            scope = scope._parent
        return scope.used_keys

    def resolve(self, var: Variable) -> tuple[VarKey, AttrType]:
        key, t = self._resolve(var)
        self.record_key(key)
        return key, t

    def _resolve(self, var: Variable) -> tuple[VarKey, AttrType]:
        if var.stream_id is not None:
            scope: Scope | None = self
            while scope is not None:
                if var.stream_id in scope._streams:
                    attrs = scope._streams[var.stream_id]
                    if var.attribute not in attrs:
                        raise KeyError(
                            f"no attribute '{var.attribute}' in '{var.stream_id}'"
                        )
                    return (
                        (var.stream_id, var.stream_index, var.attribute),
                        attrs[var.attribute],
                    )
                scope = scope._parent
            raise KeyError(f"unknown stream reference '{var.stream_id}'")
        # unqualified: unique attribute across in-scope streams (reference
        # resolves unprefixed attrs the same way)
        if self.prefer_parent and self._parent is not None:
            try:
                return self._parent.resolve(var)
            except KeyError:
                pass
        if self.prefer_default and self.default_ref is not None:
            scope = self
            while scope is not None:
                attrs = scope._streams.get(self.default_ref)
                if attrs is not None and var.attribute in attrs:
                    return (
                        (self.default_ref, var.stream_index, var.attribute),
                        attrs[var.attribute],
                    )
                scope = scope._parent
        scope = self
        while scope is not None:
            hits = [
                (ref, attrs[var.attribute])
                for ref, attrs in scope._streams.items()
                if var.attribute in attrs
            ]
            if len(hits) > 1:
                raise KeyError(f"ambiguous attribute '{var.attribute}' in {sorted(r for r, _ in hits)}")
            if hits:
                ref, t = hits[0]
                return (ref, var.stream_index, var.attribute), t
            scope = scope._parent
        raise KeyError(f"unknown attribute '{var.attribute}'")

    def ts_key(self, ref: str | None = None) -> VarKey:
        return (ref or self.default_ref, None, TS_ATTR)


def _cast(x: jnp.ndarray, t: AttrType) -> jnp.ndarray:
    return x.astype(PHYSICAL_DTYPE[t])


def _const_expr(value, t: AttrType, interner: InternTable) -> CompiledExpr:
    # numpy (NOT jnp): numpy consts embed as HLO literals with no device
    # work, while lowering a jaxpr that captured a concrete jax.Array reads
    # the buffer back to the host to embed it — a blocking device->host
    # transfer inside every trace+lower, on any backend, behind whatever
    # the device has queued (tests/test_no_device_consts.py holds the line).
    if t in (AttrType.STRING, AttrType.OBJECT):
        dev = np.asarray(interner.intern(value), dtype=np.int32)
    elif value is None:
        dev = np.asarray(null_value(t), dtype=PHYSICAL_DTYPE[t])
    else:
        dev = np.asarray(value, dtype=PHYSICAL_DTYPE[t])
    return CompiledExpr(t, lambda env: dev, const=value, is_const=True)


def _arith(op_name: str, le: CompiledExpr, re_: CompiledExpr) -> CompiledExpr:
    t = promote(le.type, re_.type)

    def fn(env: Env) -> jnp.ndarray:
        a, b = _cast(le(env), t), _cast(re_(env), t)
        if op_name == "add":
            return a + b
        if op_name == "sub":
            return a - b
        if op_name == "mul":
            return a * b
        if op_name == "div":
            if t in (AttrType.INT, AttrType.LONG):
                return lax.div(a, b)  # Java truncating integer division
            return a / b
        if op_name == "mod":
            return lax.rem(a, b)  # Java remainder: sign of dividend
        raise AssertionError(op_name)

    const = None
    is_const = le.is_const and re_.is_const
    if is_const:
        py = {"add": lambda a, b: a + b, "sub": lambda a, b: a - b,
              "mul": lambda a, b: a * b,
              "div": (lambda a, b: int(a / b) if t in (AttrType.INT, AttrType.LONG) else a / b),
              "mod": lambda a, b: a - b * int(a / b) if t in (AttrType.INT, AttrType.LONG) else a % b}
        try:
            const = py[op_name](le.const, re_.const)
        except Exception:
            is_const = False
    return CompiledExpr(t, fn, const=const, is_const=is_const)


_CMP = {
    CompareOp.LT: jnp.less,
    CompareOp.LE: jnp.less_equal,
    CompareOp.GT: jnp.greater,
    CompareOp.GE: jnp.greater_equal,
    CompareOp.EQ: jnp.equal,
    CompareOp.NEQ: jnp.not_equal,
}


def _notnull(v: jnp.ndarray, t: AttrType):
    """Mask of rows whose value is NOT the type's null encoding."""
    if t in (AttrType.FLOAT, AttrType.DOUBLE):
        return ~jnp.isnan(v)
    if t in (AttrType.INT, AttrType.LONG):
        return v != np.asarray(null_value(t), dtype=v.dtype)
    if t in (AttrType.STRING, AttrType.OBJECT):
        return v != 0
    return True  # BOOL: never null


def _compare(op: CompareOp, le: CompiledExpr, re_: CompiledExpr) -> CompiledExpr:
    lt, rt = le.type, re_.type
    if lt in NUMERIC_TYPES and rt in NUMERIC_TYPES:
        t = promote(lt, rt)

        def fn(env: Env) -> jnp.ndarray:
            lv, rv = le(env), re_(env)
            # a null operand makes ANY comparison false, NEQ included
            # (reference: CompareConditionExpressionExecutor.java:42)
            ok = _notnull(lv, lt) & _notnull(rv, rt)
            return _CMP[op](_cast(lv, t), _cast(rv, t)) & ok

    elif lt == rt and lt in (AttrType.BOOL, AttrType.STRING, AttrType.OBJECT):
        if op not in (CompareOp.EQ, CompareOp.NEQ):
            raise TypeError(f"operator {op.value} not defined for {lt!r}")

        def fn(env: Env) -> jnp.ndarray:
            lv, rv = le(env), re_(env)
            ok = _notnull(lv, lt) & _notnull(rv, rt)
            return _CMP[op](lv, rv) & ok

    else:
        raise TypeError(f"cannot compare {lt!r} {op.value} {rt!r}")
    return CompiledExpr(AttrType.BOOL, fn)


def _require_bool(c: CompiledExpr, what: str) -> None:
    if c.type is not AttrType.BOOL:
        raise TypeError(f"{what} requires BOOL, got {c.type!r}")


def compile_expression(expr: Expression, scope: Scope) -> CompiledExpr:
    """Recursively compile an expression tree against a name-resolution scope."""
    if isinstance(expr, Constant):
        return _const_expr(expr.value, expr.type, scope.interner)

    if isinstance(expr, Variable):
        key, t = scope.resolve(expr)
        return CompiledExpr(t, lambda env, k=key: env.read(k))

    if isinstance(expr, Add):
        return _arith("add", compile_expression(expr.left, scope), compile_expression(expr.right, scope))
    if isinstance(expr, Subtract):
        return _arith("sub", compile_expression(expr.left, scope), compile_expression(expr.right, scope))
    if isinstance(expr, Multiply):
        return _arith("mul", compile_expression(expr.left, scope), compile_expression(expr.right, scope))
    if isinstance(expr, Divide):
        return _arith("div", compile_expression(expr.left, scope), compile_expression(expr.right, scope))
    if isinstance(expr, Mod):
        return _arith("mod", compile_expression(expr.left, scope), compile_expression(expr.right, scope))

    if isinstance(expr, Compare):
        return _compare(expr.op, compile_expression(expr.left, scope), compile_expression(expr.right, scope))

    if isinstance(expr, And):
        le, re_ = compile_expression(expr.left, scope), compile_expression(expr.right, scope)
        _require_bool(le, "and"), _require_bool(re_, "and")
        return CompiledExpr(AttrType.BOOL, lambda env: le(env) & re_(env))
    if isinstance(expr, Or):
        le, re_ = compile_expression(expr.left, scope), compile_expression(expr.right, scope)
        _require_bool(le, "or"), _require_bool(re_, "or")
        return CompiledExpr(AttrType.BOOL, lambda env: le(env) | re_(env))
    if isinstance(expr, Not):
        ce = compile_expression(expr.expression, scope)
        _require_bool(ce, "not")
        return CompiledExpr(AttrType.BOOL, lambda env: ~ce(env))

    if isinstance(expr, IsNull):
        if expr.expression is not None:
            ce = compile_expression(expr.expression, scope)
            return CompiledExpr(AttrType.BOOL, _is_null_fn(ce))
        # stream-null form (`S1 is null` in patterns): the pattern engine
        # provides a per-state arrival flag column.
        key = (expr.stream_id, expr.stream_index, "__arrived__")
        scope.record_key(key)
        return CompiledExpr(AttrType.BOOL, lambda env, k=key: ~env.read(k))

    if isinstance(expr, In):
        table = scope.resolve_table(expr.source_id)
        if table is None:
            raise KeyError(
                f"'in {expr.source_id}': no such table in scope"
            )
        inner_scope = scope.child()
        inner_scope.add_stream(expr.source_id, table.schema.attr_types)
        inner_scope.prefer_parent = True
        cond = compile_expression(expr.expression, inner_scope)
        _require_bool(cond, "in-table condition")
        tid = table.table_id

        def fn(env: Env) -> jnp.ndarray:
            state = env.tables.get(tid)
            if state is None:
                raise KeyError(
                    f"table '{tid}' state not provided at this site"
                )
            # probe rows [B] -> [B,1]; table rows -> [1,C]; any-match over C
            cols2 = {k: v[:, None] for k, v in env.columns.items()}
            cols2.update(
                {(tid, None, n): v[None, :] for n, v in state["cols"].items()}
            )
            cols2[(tid, None, TS_ATTR)] = state["ts"][None, :]
            env2 = Env(cols2, now=env._now, tables=env.tables)
            pair = cond(env2) & state["valid"][None, :]
            return pair.any(axis=1)

        return CompiledExpr(AttrType.BOOL, fn)

    if isinstance(expr, AttributeFunction):
        return _compile_function(expr, scope)

    raise TypeError(f"cannot compile expression node {type(expr).__name__}")


def _is_null_fn(ce: CompiledExpr):
    t = ce.type

    def fn(env: Env) -> jnp.ndarray:
        v = ce(env)
        if t in (AttrType.FLOAT, AttrType.DOUBLE):
            return jnp.isnan(v)
        if t in (AttrType.STRING, AttrType.OBJECT):
            return v == 0
        if t in (AttrType.INT, AttrType.LONG):
            return v == np.asarray(null_value(t), dtype=v.dtype)
        return jnp.zeros(jnp.shape(v), dtype=jnp.bool_)  # BOOL: never null

    return fn


# ---------------------------------------------------------------------------
# built-in scalar functions
# (reference: core/executor/function/*FunctionExecutor.java — ~20 built-ins)
# ---------------------------------------------------------------------------

_TYPE_NAMES = {
    "string": AttrType.STRING,
    "int": AttrType.INT,
    "long": AttrType.LONG,
    "float": AttrType.FLOAT,
    "double": AttrType.DOUBLE,
    "bool": AttrType.BOOL,
    "object": AttrType.OBJECT,
}

# Aggregator names are handled by the selector layer, never here.
AGGREGATOR_NAMES = {
    "sum", "avg", "count", "min", "max", "stdDev", "stddev",
    "distinctCount", "distinctcount", "minForever", "minforever",
    "maxForever", "maxforever",
}


def is_aggregator(expr: Expression) -> bool:
    return (
        isinstance(expr, AttributeFunction)
        and expr.namespace is None
        and expr.name in AGGREGATOR_NAMES
    )


def _compile_function(expr: AttributeFunction, scope: Scope) -> CompiledExpr:
    if is_aggregator(expr):
        raise TypeError(
            f"aggregator '{expr.name}' is only valid in a select clause"
        )
    name = (f"{expr.namespace}:{expr.name}" if expr.namespace else expr.name)
    params = expr.parameters

    if name in ("cast", "convert"):
        if len(params) != 2 or not isinstance(params[1], Constant):
            raise TypeError(f"{name}(value, 'type') requires a constant type name")
        target = _TYPE_NAMES.get(str(params[1].value).lower())
        if target is None:
            raise TypeError(f"unknown cast target {params[1].value!r}")
        src = compile_expression(params[0], scope)
        if target in (AttrType.STRING, AttrType.OBJECT) or src.type in (
            AttrType.STRING,
            AttrType.OBJECT,
        ):
            if src.type == target:
                return src
            if target is AttrType.STRING and src.type in NUMERIC_TYPES:
                # numeric -> string: host callback formats + interns the
                # distinct values per batch (reference:
                # ConvertFunctionExecutor string conversion)
                from siddhi_tpu.utils.backend import host_callbacks_supported

                if not host_callbacks_supported():
                    raise NotImplementedError(
                        f"{name} to 'string' needs host-callback support, "
                        "which this backend does not provide"
                    )
                interner = scope.interner
                valid_key = (scope.default_ref, None, VALID_ATTR)
                is_int = src.type in (AttrType.INT, AttrType.LONG)
                src_null = _is_null_fn(src)

                def fn(env: Env, _src=src) -> jnp.ndarray:
                    v = _src(env)
                    try:
                        valid = jnp.broadcast_to(env.read(valid_key), jnp.shape(v))
                    except KeyError:
                        valid = jnp.ones(jnp.shape(v), dtype=jnp.bool_)
                    # null inputs convert to null, not to a sentinel's digits
                    # (reference: ConvertFunctionExecutor null propagation)
                    valid = valid & ~src_null(env)

                    def fmt(vals, mask):
                        import numpy as _np

                        flat = _np.asarray(vals).reshape(-1)
                        m = _np.asarray(mask).reshape(-1)
                        out = _np.zeros(flat.shape, dtype=_np.int32)
                        uniq = _np.unique(flat[m])
                        if is_int:
                            strings = [str(int(u)) for u in uniq.tolist()]
                        else:
                            # shortest round-trip form of the DEVICE precision
                            # (f32): widening through float64 repr would print
                            # garbage digits
                            strings = [
                                _np.format_float_positional(
                                    u, unique=True, trim="0"
                                )
                                for u in uniq
                            ]
                        id_arr = _np.array(
                            [interner.intern(s) for s in strings], dtype=_np.int32
                        )
                        if uniq.size:
                            idx = _np.searchsorted(uniq, flat[m])
                            out[m] = id_arr[idx]
                        return out.reshape(_np.shape(vals))

                    import jax
                    from jax.experimental import io_callback

                    return io_callback(
                        fmt,
                        jax.ShapeDtypeStruct(jnp.shape(v), jnp.int32),
                        v, valid,
                    )

                return CompiledExpr(AttrType.STRING, fn)
            raise NotImplementedError(
                f"{name} between {src.type!r} and {target!r} requires host egress"
            )
        if target is AttrType.BOOL or src.type is AttrType.BOOL:
            if src.type == target:
                return src
            raise TypeError(f"cannot {name} {src.type!r} to {target!r}")
        return CompiledExpr(target, lambda env: _cast(src(env), target))

    if name == "coalesce":
        compiled = [compile_expression(p, scope) for p in params]
        t = compiled[0].type
        if any(c.type != t for c in compiled):
            raise TypeError("coalesce requires homogeneous parameter types")

        def fn(env: Env) -> jnp.ndarray:
            out = compiled[-1](env)
            for c in reversed(compiled[:-1]):
                v = c(env)
                out = jnp.where(_is_null_fn(c)(env), out, v)
            return out

        return CompiledExpr(t, fn)

    if name == "ifThenElse":
        cond, a, b = (compile_expression(p, scope) for p in params)
        _require_bool(cond, "ifThenElse condition")
        if a.type in NUMERIC_TYPES and b.type in NUMERIC_TYPES:
            t = promote(a.type, b.type)
        elif a.type == b.type:
            t = a.type
        else:
            raise TypeError(f"ifThenElse branches {a.type!r} vs {b.type!r}")
        return CompiledExpr(
            t, lambda env: jnp.where(cond(env), _cast(a(env), t), _cast(b(env), t))
        )

    if name.startswith("instanceOf"):
        target = _TYPE_NAMES.get(name[len("instanceOf"):].lower())
        if target is None:
            raise TypeError(f"unknown function '{name}'")
        src = compile_expression(params[0], scope)
        matches = src.type == target
        isnull = _is_null_fn(src)
        return CompiledExpr(
            AttrType.BOOL,
            lambda env: (~isnull(env)) & np.asarray(matches),
        )

    if name in ("maximum", "minimum"):
        compiled = [compile_expression(p, scope) for p in params]
        t = compiled[0].type
        for c in compiled[1:]:
            t = promote(t, c.type)
        red = jnp.maximum if name == "maximum" else jnp.minimum

        def fn(env: Env) -> jnp.ndarray:
            out = _cast(compiled[0](env), t)
            for c in compiled[1:]:
                out = red(out, _cast(c(env), t))
            return out

        return CompiledExpr(t, fn)

    if name == "eventTimestamp":
        key = scope.ts_key()
        return CompiledExpr(AttrType.LONG, lambda env: env.read(key))

    if name == "currentTimeMillis":
        return CompiledExpr(AttrType.LONG, lambda env: env.now())

    if name == "UUID":
        # string generation cannot happen on device: a host callback mints
        # one UUID per VALID row and interns it (reference:
        # executor/function/UUIDFunctionExecutor). io_callback (not
        # pure_callback): minting is impure — it must never be CSE'd or
        # replayed, or duplicate/unrecorded ids would appear.
        from siddhi_tpu.utils.backend import host_callbacks_supported

        if not host_callbacks_supported():
            raise NotImplementedError(
                "UUID() needs host-callback support, which this backend "
                "does not provide"
            )
        interner = scope.interner
        valid_key = (scope.default_ref, None, VALID_ATTR)

        def fn(env: Env) -> jnp.ndarray:
            ts = env.read(scope.ts_key())
            try:
                valid = env.read(valid_key)
            except KeyError:
                valid = jnp.ones(jnp.shape(ts), dtype=jnp.bool_)

            def mint(v):
                import uuid as _uuid

                import numpy as _np

                flat = _np.asarray(v).reshape(-1)
                out = _np.zeros(flat.shape, dtype=_np.int32)  # padding: null id
                for i in _np.nonzero(flat)[0]:
                    out[i] = interner.intern(str(_uuid.uuid4()))
                return out.reshape(_np.shape(v))

            import jax
            from jax.experimental import io_callback

            return io_callback(
                mint,
                jax.ShapeDtypeStruct(jnp.shape(valid), jnp.int32),
                valid,
            )

        return CompiledExpr(AttrType.STRING, fn)

    if name == "default":
        src = compile_expression(params[0], scope)
        dflt = compile_expression(params[1], scope)
        if src.type != dflt.type and not (
            src.type in NUMERIC_TYPES and dflt.type in NUMERIC_TYPES
        ):
            raise TypeError(f"default({src.type!r}, {dflt.type!r}) type mismatch")
        t = src.type
        isnull = _is_null_fn(src)
        return CompiledExpr(
            t, lambda env: jnp.where(isnull(env), _cast(dflt(env), t), src(env))
        )

    from siddhi_tpu.core.extension import lookup_function  # cycle-free at call time

    ext = lookup_function(name)
    if ext is not None:
        return ext([compile_expression(p, scope) for p in params], scope)

    raise NotImplementedError(f"unknown function '{name}'")

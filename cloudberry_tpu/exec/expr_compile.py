"""Compile bound expressions to jax.numpy — the ExprState/XLA bridge.

``compile_expr`` returns a function of (columns: dict[str, Array]) → Array.
Everything is vectorized over the batch; XLA fuses the resulting elementwise
graph into the surrounding kernel (the reference gets per-tuple interpreted
evaluation via ExecEvalExpr — here fusion is free).
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
import numpy as np

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.types import DType

Columns = dict[str, jnp.ndarray]


def compile_expr(e: ex.Expr) -> Callable[[Columns], jnp.ndarray]:
    if isinstance(e, ex.ColumnRef):
        name = e.name
        return lambda cols: cols[name]

    if isinstance(e, ex.Literal):
        val = np.asarray(e.value, dtype=e.dtype.np_dtype)
        return lambda cols: jnp.asarray(val)

    if isinstance(e, ex.Param):
        # runtime-bound literal: the Lowerer injects the slot's value next
        # to the columns (from the program's "$params" input), so a generic
        # plan re-executes with new literals WITHOUT retracing. A trace
        # without bindings (non-generic recompile of a rewritten plan)
        # bakes the build-time value — the original statement's constant.
        name = e.input_name
        fallback = None if e.value is None else \
            np.asarray(e.value, dtype=e.dtype.np_dtype)
        if fallback is None:
            return lambda cols: cols[name]
        return lambda cols: cols[name] if name in cols \
            else jnp.asarray(fallback)

    if isinstance(e, ex.BinOp):
        lf, rf = compile_expr(e.left), compile_expr(e.right)
        op = _BINOPS[e.op]
        return lambda cols: op(lf(cols), rf(cols))

    if isinstance(e, ex.UnaryOp):
        f = compile_expr(e.operand)
        if e.op == "not":
            return lambda cols: jnp.logical_not(f(cols))
        if e.op == "-":
            return lambda cols: -f(cols)
        raise NotImplementedError(e.op)

    if isinstance(e, ex.Cast):
        f = compile_expr(e.operand)
        src, dst = e.operand.dtype, e.dtype
        dt = dst.np_dtype
        if src.base == DType.DECIMAL and dst.base == DType.FLOAT64:
            inv = 1.0 / (10.0 ** src.scale)
            return lambda cols: f(cols).astype(dt) * inv
        if src.base == DType.FLOAT64 and dst.base == DType.DECIMAL:
            mul = 10.0 ** dst.scale
            return lambda cols: jnp.rint(f(cols) * mul).astype(dt)
        if src.base == DType.DECIMAL and dst.base == DType.DECIMAL:
            if dst.scale >= src.scale:
                mul = np.int64(10 ** (dst.scale - src.scale))
                return lambda cols: f(cols) * mul
            return lambda cols: _scale_down(f(cols), src.scale - dst.scale)
        if src.base in (DType.INT32, DType.INT64) and dst.base == DType.DECIMAL:
            mul = np.int64(10 ** dst.scale)
            return lambda cols: f(cols).astype(dt) * mul
        if src.base == DType.DECIMAL and dst.base in (DType.INT32, DType.INT64):
            return lambda cols: _scale_down(f(cols), src.scale).astype(dt)
        return lambda cols: f(cols).astype(dt)

    if isinstance(e, ex.Func):
        return _compile_func(e)

    if isinstance(e, ex.CaseWhen):
        whens = [(compile_expr(c), compile_expr(v)) for c, v in e.whens]
        other = compile_expr(e.otherwise) if e.otherwise is not None else None
        zero = np.asarray(0, dtype=e.dtype.np_dtype)

        def run_case(cols):
            out = other(cols) if other is not None else jnp.asarray(zero)
            # Evaluate in reverse so the FIRST matching WHEN wins.
            for cf, vf in reversed(whens):
                out = jnp.where(cf(cols), vf(cols), out)
            return out

        return run_case

    if isinstance(e, ex.DictLookup):
        f = compile_expr(e.column)
        # a long table rides into the program as an input, padded to
        # its rung (exec/executor.py stamp_dict_tables); where the
        # lowerer offers none, the table is the constant it always was
        key = getattr(e, "_table_input", None)

        def lookup(cols):
            table = cols.get(key) if key is not None else None
            if table is None:
                table = jnp.asarray(e.table)
            codes = f(cols)
            # code -1 (value absent from dictionary) must not match predicates
            safe = jnp.clip(codes, 0, table.shape[0] - 1)
            hit = jnp.take(table, safe, axis=0)
            if table.dtype == np.bool_:
                return jnp.where(codes >= 0, hit, False)
            return jnp.where(codes >= 0, hit, -1)

        return lookup

    if isinstance(e, ex.IsValid):
        names, neg = e.mask_names, e.negate

        def valid(cols):
            # mask columns may be bool or 0/1 ints (agg companions)
            v = cols[names[0]].astype(jnp.bool_)
            for n in names[1:]:
                v = jnp.logical_and(v, cols[n].astype(jnp.bool_))
            return jnp.logical_not(v) if neg else v

        return valid

    raise NotImplementedError(type(e).__name__)


def _scale_down(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Rounded (half away from zero) integer division by 10**k — rescales a
    decimal product back to its result scale."""
    if k == 0:
        return x
    d = np.int64(10 ** k)
    half = np.int64(10 ** k // 2)
    return jnp.where(x >= 0, (x + half) // d, -((-x + half) // d))


def _compile_func(e: ex.Func):
    args = [compile_expr(a) for a in e.args]
    name = e.name
    if name == "extract_year":
        # days-since-epoch → civil year (vectorized Hinnant algorithm).
        return lambda cols: _civil_from_days(args[0](cols))[0]
    if name == "extract_month":
        return lambda cols: _civil_from_days(args[0](cols))[1]
    if name == "abs":
        return lambda cols: jnp.abs(args[0](cols))
    if name == "sqrt":
        # guard tiny negative values from the stddev identity's cancellation
        return lambda cols: jnp.sqrt(jnp.maximum(args[0](cols), 0.0))
    if name == "scale_down":
        # args: (decimal expr, literal k) — binder-inserted rescale after
        # decimal multiplication.
        k = int(e.args[1].value)  # type: ignore[attr-defined]
        return lambda cols: _scale_down(args[0](cols), k)
    if name.startswith("udf:"):
        # jit scalar UDF (exec/udf.py): the registered callable traces
        # into the program — a TPU-native function body
        from cloudberry_tpu.exec import udf as U

        u = U.lookup(name[4:])
        if u is not None and u.jit:
            fn = u.fn
            return lambda cols: fn(*[a(cols) for a in args])
    raise NotImplementedError(f"function {name}")


def _civil_from_days(z):
    """days since 1970-01-01 → (year, month, day); Howard Hinnant's
    branchless civil-from-days, exact for all int32 days."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _safe_div(a, b):
    # SQL raises on division by zero; masked-out lanes may legitimately hold
    # zeros, so evaluate total-function style: 0 for zero divisors.
    b = jnp.asarray(b)
    nz = b != 0
    return jnp.where(nz, a / jnp.where(nz, b, 1), 0)


def _safe_mod(a, b):
    # SQL modulo truncates toward zero (fmod semantics), unlike Python's
    # floor-mod; zero divisors evaluate total-function style like _safe_div.
    b = jnp.asarray(b)
    nz = b != 0
    return jnp.where(nz, jnp.fmod(a, jnp.where(nz, b, 1)), 0)


_BINOPS = {
    "+": jnp.add,
    "-": jnp.subtract,
    "*": jnp.multiply,
    "/": _safe_div,
    "%": _safe_mod,
    "=": lambda a, b: a == b,
    "<>": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "and": jnp.logical_and,
    "or": jnp.logical_or,
}

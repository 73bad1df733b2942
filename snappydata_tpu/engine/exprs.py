"""Expression → traced JAX lowering with three-valued (SQL NULL) logic.

Design notes (TPU-first):
- Values are (value, null?) pairs; null masks are only materialized when a
  source is nullable — the common all-non-null path emits zero extra ops.
- Strings never exist on device: a string column is int32 dictionary codes.
  Every predicate `str_col OP literal` is evaluated ONCE over the (host)
  dictionary producing a bool lookup table, shipped as an aux input, and
  applied as a gather — the device cost is O(rows) regardless of the
  string operation's complexity (LIKE, <=, IN…). This generalizes the
  reference's dictionary fast path (DictionaryOptimizedMapAccessor,
  core/.../execution/DictionaryOptimizedMapAccessor.scala).
- Tokenized literals (ParamLiteral) arrive as runtime scalars (numeric) or
  bind-time LUT rebuilds (string), so changing a literal re-runs but never
  re-compiles (ref plan-cache goal, SnappySession.sqlPlan:2571).

Emission is two-phase: `ExprBuilder.emit` runs structurally (no arrays),
registering aux-input builders and returning a closure; the closure runs
inside the jit trace consuming runtime arrays. Builders run at bind time on
host with the current table dictionaries.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from snappydata_tpu import types as T
from snappydata_tpu.sql import ast


class CompileError(Exception):
    pass


# the ONLY functions that may consume an array-typed column on device
# (its (values, lengths, element_nulls) plate layout is opaque to every
# other operator); executor._validate_array_usage enforces the same set
ARRAY_DEVICE_FUNCS = ("size", "element_at", "array_contains")

# string-valued functions computable per-dictionary-value on the host and
# carried as derived dictionaries (codes never leave the device)
STRING_VALUE_FUNCS = frozenset(
    {"upper", "lower", "trim", "ltrim", "rtrim", "substr", "substring",
     "replace", "concat", "lpad", "rpad", "initcap", "repeat", "reverse",
     "translate", "split_part"})


@dataclasses.dataclass
class MapDicts:
    """Dictionary providers of a device-plated MAP<STRING, V> column:
    key codes always, value codes when V is string."""

    key: Callable[[], np.ndarray]
    value: Optional[Callable[[], np.ndarray]] = None


@dataclasses.dataclass
class StructDicts:
    """Per-field value-dictionary providers of a device-plated STRUCT
    column (string fields only)."""

    fields: Dict[str, Callable[[], np.ndarray]] = None


@dataclasses.dataclass
class DVal:
    """A traced value: device array + optional null mask + static type info."""

    value: object                 # traced jnp array
    null: object = None           # traced bool array or None
    dtype: T.DataType = None
    dictionary: Optional[np.ndarray] = None   # static host dict for strings
    # compressed-domain residency (base-table columns bound encoded):
    # cplate is a device_decode.CodePlate (VALUE_DICT codes + sorted
    # per-batch dictionaries), rplate a device_decode.RlePlate (run
    # values + ends).  When set, `value` is the LAZY in-trace decode —
    # XLA fuses (and dead-code-eliminates) it — and comparisons against
    # scalars take the code/run lanes below instead of touching values.
    cplate: object = None
    rplate: object = None
    # run-space residency of a BOOLEAN DVal (RLE predicate results and
    # their conjunctions): rmask is the per-RUN [B, R] bool mask whose
    # _rle_expand over rends equals `value`, rends the cumulative run
    # ends it is aligned to (identity-compared to prove two masks talk
    # about the SAME run partition).  Set only when null is None — a
    # row-level null mask breaks run purity.  This is the run-alignment
    # proof the RLE aggregate lane consumes: a filter whose rmask
    # survived the whole conjunction is run-aligned by construction.
    rmask: object = None
    rends: object = None

    @property
    def is_string(self) -> bool:
        return self.dtype is not None and self.dtype.name == "string"


# per-trace tally of compressed-domain lowerings: the executor installs a
# dict here around a compiled plan's FIRST trace per static key, stores
# the result on the plan, and bumps the code_domain_predicates /
# rle_run_predicates counters by it on every subsequent execution
import contextvars as _contextvars  # noqa: E402

_compressed_notes: _contextvars.ContextVar = _contextvars.ContextVar(
    "compressed_notes", default=None)


def _note_compressed(kind: str) -> None:
    d = _compressed_notes.get()
    if d is not None:
        d[kind] = d.get(kind, 0) + 1


def _compressed_cmp(op: str, col: DVal, lit: DVal) -> Optional[DVal]:
    """Code/run-domain lowering of `col OP scalar-literal` when the
    column is resident in the compressed domain.  Value-domain
    equivalence is exact: code thresholds translate through the sorted
    dictionary in the promoted compare dtype (device_decode.code_cmp_mask)
    and run predicates evaluate the very values the expansion would
    yield.  Returns None when the shape doesn't qualify (derived values,
    non-scalar or string literal) — the generic value compare runs."""
    if col.cplate is None and col.rplate is None:
        return None
    if lit.cplate is not None or lit.rplate is not None:
        return None
    if lit.dtype is not None and lit.dtype.name == "string":
        return None
    # an EXACT decimal literal carries its SCALED int64 value — comparing
    # that against raw dictionary/run values would be off by 10^scale;
    # the generic lane unscales it correctly (float-valued decimal-typed
    # literals, e.g. substituted scalar subqueries, stay eligible)
    if _dec_scale(lit) is not None:
        return None
    if lit.null is not None or jnp.ndim(lit.value) != 0:
        return None
    from snappydata_tpu.storage.device_decode import (code_cmp_mask,
                                                      rle_expand_runs)

    if col.cplate is not None:
        m = code_cmp_mask(op, col.cplate, lit.value)
        _note_compressed("code_preds")
        return DVal(m, _or_null(col.null, lit.null), T.BOOLEAN)
    fns = {"=": lambda a, b: a == b, "!=": lambda a, b: a != b,
           "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
           ">": lambda a, b: a > b, ">=": lambda a, b: a >= b}
    cap = jnp.shape(col.value)[1]
    run_mask = fns[op](col.rplate.values, lit.value)
    m = rle_expand_runs(run_mask, col.rplate.ends, cap)
    _note_compressed("run_preds")
    out = DVal(m, _or_null(col.null, lit.null), T.BOOLEAN)
    if out.null is None:
        # the expanded mask is PROVABLY the expansion of run_mask over
        # this run partition — carry the run form for the aggregate lane
        out.rmask = run_mask
        out.rends = col.rplate.ends
    return out


def _no_string_operands(dvals, name: str) -> None:
    """String DVals carry dictionary CODES — value comparisons across
    columns would compare insertion order, not text. Host path instead."""
    for d in dvals:
        if d.dtype is not None and d.dtype.name == "string":
            raise CompileError(f"{name} over string operands: host path")


def _or_null(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a | b


# ---------------------------------------------------------------------------
# Exact decimals: a DVal whose dtype is an exact DecimalType carries the
# SCALED int64 unscaled value (types.DecimalType docstring). The binop /
# cast emitters below keep +,-,*,%, comparisons and casts in the exact
# integer domain at Spark 2.1's result types for every precision up to
# 38. A result past types.DECIMAL_INT64_PRECISION stays int64 too: each
# product, rescale and alignment that could leave int64 ORs a bound
# check (`_dec_guard`, a float32 magnitude test against 2^62) into the
# statement's overflow flag, and the executor reruns a flagged statement
# exactly on the host. Division, math funcs, IN tables and mixed CASE
# branches receive the PLAIN float domain via _dec_unscale — scaled
# ints must never leak into value-blind float math.
# ---------------------------------------------------------------------------

# the overflow flag's bit for a decimal whose scaled value could leave
# int64 (the executor names it in the host fallback's reason)
DECIMAL_OVERFLOW = 2
_GUARD_BOUND = float(2 ** 62)


@dataclasses.dataclass(frozen=True)
class DecimalAvg(ast.Expr):
    """avg over an exact decimal once reduced: the exact sum over the
    exact count, rounded HALF_UP at the average's scale (`dtype`, Spark
    2.1's DECIMAL(p + 4, s + 4)); NULL over no rows."""

    sum: ast.Expr = None
    count: ast.Expr = None
    dtype: T.DataType = None

    def children(self):
        return (self.sum, self.count)

    def map_children(self, fn):
        return DecimalAvg(fn(self.sum), fn(self.count), self.dtype)


def _f32_abs(v):
    """|v| of int64 values in float32: within 2^-23 of the true
    magnitude, which is all a bound test against 2^62 needs to be sound
    for int64's 2^63 (and int64's minimum has no int64 absolute)."""
    return jnp.abs(jnp.asarray(v).astype(jnp.float32))


def _dec_guard(rt: "Runtime", key, risky) -> None:
    """OR one bound check over the rows in play into the statement's
    overflow flag, under its DECIMAL_OVERFLOW bit, and note `key` (a
    wide expression's shape; None for one not counted) for the
    `decimal_wide_exprs` attr."""
    sink = rt.sink
    if sink is None:
        raise CompileError("a decimal past int64's precision outside a "
                           "guarded region: host path")
    if key is not None:
        sink.decimal_wide.add(key)
    if risky is None:
        return
    if rt.valid is not None and jnp.ndim(risky) == jnp.ndim(rt.valid):
        risky = risky & rt.valid
    sink.overflow = sink.overflow | jnp.where(
        jnp.any(risky), jnp.int32(DECIMAL_OVERFLOW), jnp.int32(0))


def _shape_key(e: ast.Expr):
    """A wide expression as its trace sees it: tokenized literals by
    their type alone, so two copies of one subexpression (each `1` its
    own slot) count once."""
    def strip(x):
        if isinstance(x, (ast.ParamLiteral, ast.Param)):
            return ast.Lit("?", x.dtype)
        return x.map_children(strip)

    return strip(e)


def _dec_scale(d: DVal) -> Optional[int]:
    """Scale when d is an exact scaled-int decimal DVal, else None."""
    dt = d.dtype
    if dt is not None and dt.name == "decimal" \
            and getattr(dt, "is_exact", False) \
            and jnp.issubdtype(jnp.asarray(d.value).dtype, jnp.integer):
        return dt.scale
    return None


def _dec_unscale(d: DVal) -> DVal:
    """Exact decimal -> plain float64 DVal; anything else unchanged."""
    s = _dec_scale(d)
    if s is None:
        return d
    v = d.value.astype(jnp.float64) / (10 ** s)
    return DVal(v, d.null, T.DOUBLE, d.dictionary)


def _dec_wrap_unscaled(run: Callable[["Runtime"], DVal]
                       ) -> Callable[["Runtime"], DVal]:
    """Wrap an emitted closure so consumers see the float domain.
    Preserves the static_param/static_str markers structural consumers
    inspect."""

    def wrapped(rt: "Runtime") -> DVal:
        return _dec_unscale(run(rt))

    for attr in ("static_param", "static_str"):
        if hasattr(run, attr):
            setattr(wrapped, attr, getattr(run, attr))
    return wrapped


def _dec_rescale_int(value, from_scale: int, to_scale: int):
    """Scaled int64 -> scaled int64 at another scale, rounding half away
    from zero on downscale (Spark/java BigDecimal HALF_UP). An upscale
    past int64 wraps: callers guard it (`_dec_aligned`)."""
    if to_scale == from_scale:
        return value
    if to_scale > from_scale:
        return value * (10 ** (to_scale - from_scale))
    f = 10 ** (from_scale - to_scale)
    av = jnp.abs(value)
    return jnp.sign(value) * ((av + f // 2) // f)


def _dec_aligned(value, dt: T.DecimalType, to_scale: int, summed: bool):
    """(value at `to_scale`, its bound check or None). The check is None
    where the operand's type keeps the aligned value inside int64; a
    `summed` operand of a result past int64's precision is held under
    2^62 even unscaled, so that the sum of two stays inside."""
    k = max(to_scale - dt.scale, 0)
    out = _dec_rescale_int(value, dt.scale, to_scale)
    if dt.precision + k <= T.DECIMAL_INT64_PRECISION or not (k or summed):
        return out, None
    return out, _f32_abs(value) * np.float32(10.0 ** k) >= _GUARD_BOUND


def _or_risk(a, b):
    return a if b is None else b if a is None else a | b


def _as_dec_operand(d: DVal):
    """(int64 values, DecimalType) for an operand that can join exact
    integer-domain math — an exact decimal, or an integer typed as
    decimal(digits, 0). (None, None) for float operands."""
    s = _dec_scale(d)
    if s is not None:
        return d.value.astype(jnp.int64), d.dtype
    vdt = jnp.asarray(d.value).dtype
    if not jnp.issubdtype(vdt, jnp.integer):
        return None, None
    name = d.dtype.name if d.dtype is not None else "long"
    digits = T._INT_DIGITS.get(name)
    if digits is None:
        return None, None
    return d.value.astype(jnp.int64), T.DecimalType("decimal", digits, 0)


def _dec_cmp_float_scalar(op: str, d: DVal, s: int, lit) -> DVal:
    """Compare an exact decimal against a float SCALAR (typically a
    tokenized literal) in the scaled-int domain — unscaling to float
    instead would mis-bucket boundary values (an f32 literal 24.05 is
    24.04999...). The threshold math is traced, so tokenized literals
    rebind without recompiles. Handles literals finer than the column
    scale (v <= 24.056 at scale 2 means v <= 24.05) via op-aware
    floor/ceil; literals too large for int64 fall back to the float
    compare lane, selected in-trace."""
    f = 10 ** s
    t = jnp.asarray(lit).astype(jnp.float64) * f
    r = jnp.round(t)
    tol = 1e-6 * jnp.maximum(1.0, jnp.abs(t))
    is_int = jnp.abs(t - r) <= tol
    fl = jnp.floor(t)
    safe = jnp.abs(t) <= 2.0 ** 62
    ts = jnp.where(safe, t, 0.0)
    r64 = jnp.round(ts).astype(jnp.int64)
    fl64 = jnp.floor(ts).astype(jnp.int64)
    del fl
    v = d.value.astype(jnp.int64)
    if op == "=":
        res_i = is_int & (v == r64)
    elif op == "!=":
        res_i = ~is_int | (v != r64)
    elif op == "<":
        res_i = v < jnp.where(is_int, r64, fl64 + 1)
    elif op == "<=":
        res_i = v <= jnp.where(is_int, r64, fl64)
    elif op == ">":
        res_i = v > jnp.where(is_int, r64, fl64)
    else:  # >=
        res_i = v >= jnp.where(is_int, r64, fl64 + 1)
    vf = v.astype(jnp.float64) / f
    lf = jnp.asarray(lit).astype(jnp.float64)
    res_f = {"=": vf == lf, "!=": vf != lf, "<": vf < lf,
             "<=": vf <= lf, ">": vf > lf, ">=": vf >= lf}[op]
    return DVal(jnp.where(safe, res_i, res_f), d.null, T.BOOLEAN)


_FLIP_CMP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
             "=": "=", "!=": "!="}


def _dec_binop(op: str, fn, a: DVal, b: DVal, is_cmp: bool,
               rt: "Runtime", key) -> Optional[DVal]:
    """Exact integer-domain lowering of a binop with >= 1 decimal side.
    None -> the caller unscales both sides and runs plain float math.
    Scale/precision rules shared with the analyzer via
    types.decimal_binop_type, so declared output scale always equals
    the computed representation's. A result past int64's precision, or
    an alignment that could leave int64, is guarded through `rt`'s
    overflow sink; `key` names a wide result for the counter."""
    av, adt = _as_dec_operand(a)
    bv, bdt = _as_dec_operand(b)
    if av is None or bv is None:
        if is_cmp:
            # decimal vs float SCALAR (tokenized literal): exact
            # scaled-int compare instead of a lossy float unscale
            sa, sb = _dec_scale(a), _dec_scale(b)
            if sa is not None and bv is None and jnp.ndim(b.value) == 0:
                out = _dec_cmp_float_scalar(op, a, sa, b.value)
                return DVal(out.value, _or_null(a.null, b.null),
                            T.BOOLEAN)
            if sb is not None and av is None and jnp.ndim(a.value) == 0:
                out = _dec_cmp_float_scalar(_FLIP_CMP[op], b, sb,
                                            a.value)
                return DVal(out.value, _or_null(a.null, b.null),
                            T.BOOLEAN)
        return None
    null = _or_null(a.null, b.null)
    if is_cmp:
        s = max(adt.scale, bdt.scale)
        va, ra = _dec_aligned(av, adt, s, summed=False)
        vb, rb = _dec_aligned(bv, bdt, s, summed=False)
        risky = _or_risk(ra, rb)
        if risky is not None:
            _dec_guard(rt, None, risky)
        return DVal(fn(va, vb), null, T.BOOLEAN)
    out_dt = T.decimal_binop_type(op, adt, bdt)
    if not isinstance(out_dt, T.DecimalType) or not out_dt.is_exact:
        return None
    wide = out_dt.precision > T.DECIMAL_INT64_PRECISION
    if op == "*":
        # scales add under int multiply: result is already at out_dt.scale
        if wide:
            _dec_guard(rt, key,
                       _f32_abs(av) * _f32_abs(bv) >= _GUARD_BOUND)
        return DVal(av * bv, null, out_dt)
    summed = wide and op in ("+", "-")
    va, ra = _dec_aligned(av, adt, out_dt.scale, summed)
    vb, rb = _dec_aligned(bv, bdt, out_dt.scale, summed)
    risky = _or_risk(ra, rb)
    if wide or risky is not None:
        _dec_guard(rt, key if wide else None, risky)
    return DVal(fn(va, vb), null, out_dt)


class Runtime:
    """Runtime arrays handed to emitted closures inside the trace."""

    def __init__(self, cols: Dict[int, DVal], params: Sequence,
                 aux: Sequence, sink=None, valid=None):
        self.cols = cols
        self.params = params  # traced scalars, one per tokenized literal
        self.aux = aux        # traced aux arrays, in registration order
        # the trace context whose overflow flag a wide decimal's bound
        # check joins (`_dec_guard`); None where nothing reads one
        self.sink = sink
        # the rows in play (a filter's survivors): a bound check reads
        # only these; None checks every slot
        self.valid = valid


class ExprBuilder:
    """Structural compiler for one scope.

    col_types[i] — dtype of input ordinal i
    col_nullable[i] — whether ordinal i can produce nulls
    dict_getters[i] — bind-time callable returning the CURRENT host
        dictionary for string ordinal i (dictionaries grow with ingest)
    """

    def __init__(self, col_types: Dict[int, T.DataType],
                 col_nullable: Dict[int, bool],
                 dict_getters: Dict[int, Callable[[], np.ndarray]],
                 int_div_dtype=None):
        self.col_types = col_types
        self.col_nullable = col_nullable
        self.dict_getters = dict_getters
        # aux builders: fn(params: tuple) -> np.ndarray, run at bind time
        self.aux_builders: List[Callable] = []
        self.param_dtypes: Dict[int, T.DataType] = {}
        # float width an integer operand of "/" is cast to. None: the
        # plates' (float32 under the TPU's dtype policy), for a division
        # per row; the post-aggregate scope passes the accumulators'
        # float64, where the operands are exact int64 sums and counts
        # over [G] and float32 would round avg(BIGINT) to 24 bits
        self.int_div_dtype = int_div_dtype

    # -- aux registration --------------------------------------------------

    def _register_aux(self, builder: Callable) -> int:
        self.aux_builders.append(builder)
        return len(self.aux_builders) - 1

    def _string_pred_lut(self, col_idx: int, fn: Callable[[np.ndarray], np.ndarray]
                         ) -> int:
        """Register a bool LUT over the column's dictionary; padded to pow2
        so dictionary growth rarely changes executable shapes."""
        getter = self.dict_getters[col_idx]

        def build(params):
            d = getter()
            lut = fn(d, params).astype(np.bool_)
            n = max(1, len(lut))
            padded = 1 << (n - 1).bit_length()
            if padded > len(lut):
                lut = np.concatenate([lut, np.zeros(padded - len(lut),
                                                    dtype=np.bool_)])
            return lut

        return self._register_aux(build)

    # -- literals ----------------------------------------------------------

    def _param_value(self, e, params):
        if isinstance(e, (ast.ParamLiteral, ast.Param)):
            return params[e.pos]
        if isinstance(e, ast.Lit):
            return e.value
        raise CompileError("expected literal")

    def _is_literalish(self, e) -> bool:
        # prepared-statement '?' Params qualify: every consumer reads the
        # value through a bind-time `lambda params:` closure, exactly
        # like tokenized ParamLiterals.  (Serving sweep finding: a string
        # `?` used to fall through to the numeric param slot — value 0 —
        # so `WHERE name = ?` silently compared dictionary code 0 and
        # returned the wrong rows.)
        return isinstance(e, (ast.Lit, ast.ParamLiteral, ast.Param))

    # -- main emit ---------------------------------------------------------

    def emit(self, e: ast.Expr) -> Callable[[Runtime], DVal]:
        if isinstance(e, ast.Alias):
            return self.emit(e.child)

        if isinstance(e, ast.Col):
            idx = e.index

            def run_col(rt: Runtime) -> DVal:
                return rt.cols[idx]

            return run_col

        if isinstance(e, ast.Lit):
            return self._emit_literal(e.value, e.dtype)

        if isinstance(e, DecimalAvg):
            return self._emit_decimal_avg(e)

        if isinstance(e, (ast.ParamLiteral, ast.Param)):
            pos, dtype = e.pos, e.dtype
            if dtype is not None and dtype.name == "string":
                # string params only appear inside string predicates, which
                # are handled by LUTs; a bare string param can't be lowered
                def run_strparam(rt: Runtime) -> DVal:
                    raise CompileError(
                        "string literal outside a dictionary predicate")

                run_strparam.static_param = (pos, dtype)  # marker
                return run_strparam

            def run_param(rt: Runtime) -> DVal:
                return DVal(rt.params[pos], None, dtype or T.DOUBLE)

            run_param.static_param = (pos, dtype)
            return run_param

        if isinstance(e, ast.BinOp):
            return self._emit_binop(e)

        if isinstance(e, ast.UnaryOp):
            child = self.emit(e.child)
            if e.op == "not":
                def run_not(rt: Runtime) -> DVal:
                    c = child(rt)
                    return DVal(~c.value, c.null, T.BOOLEAN)

                return run_not

            def run_neg(rt: Runtime) -> DVal:
                c = child(rt)
                return DVal(-c.value, c.null, c.dtype)

            return run_neg

        if isinstance(e, ast.IsNull):
            child = self.emit(e.child)
            negated = e.negated

            def run_isnull(rt: Runtime) -> DVal:
                c = child(rt)
                null = c.null if c.null is not None else jnp.zeros(
                    jnp.shape(c.value), dtype=bool)
                v = ~null if negated else null
                return DVal(v, None, T.BOOLEAN)

            return run_isnull

        if isinstance(e, ast.Between):
            lo = ast.BinOp(">=", e.child, e.lo)
            hi = ast.BinOp("<=", e.child, e.hi)
            both = ast.BinOp("and", lo, hi)
            if e.negated:
                both = ast.UnaryOp("not", both)
            return self.emit(both)

        if isinstance(e, ast.InList):
            return self._emit_in(e)

        if isinstance(e, ast.Like):
            return self._emit_like(e)

        if isinstance(e, ast.Case):
            return self._emit_case(e)

        if isinstance(e, ast.Cast):
            return self._emit_cast(e)

        if isinstance(e, ast.Func):
            return self._emit_func(e)

        raise CompileError(f"cannot lower expression {type(e).__name__}")

    # -- pieces ------------------------------------------------------------

    def _emit_literal(self, value, dtype) -> Callable[[Runtime], DVal]:
        if value is None:
            def run_null(rt: Runtime) -> DVal:
                z = jnp.zeros((), dtype=jnp.float32)
                return DVal(z, jnp.ones((), dtype=bool), dtype or T.DOUBLE)

            return run_null
        if dtype is not None and dtype.name == "string":
            def run_str(rt: Runtime) -> DVal:
                raise CompileError(
                    "string literal outside a dictionary predicate")

            run_str.static_str = value
            return run_str
        eff = dtype or (T.DOUBLE if isinstance(value, float) else T.LONG)
        if eff.name == "decimal" and getattr(eff, "is_exact", False):
            # exact-decimal literal (subquery substitution yields
            # Decimal/float values typed decimal): store the SCALED
            # unscaled value — a plain int64 cast would truncate 24.05
            # to 24 and then decode as 0.24 (review finding)
            import decimal as _d

            q = _d.Decimal(value if isinstance(value, (_d.Decimal, int))
                           else repr(float(value)))
            scaled = int(q.scaleb(eff.scale).to_integral_value(
                rounding=_d.ROUND_HALF_UP))
            if abs(scaled) >= 2 ** 63:
                raise CompileError(f"a {eff} literal leaves int64 once "
                                   f"scaled: host path")
            const = np.asarray(scaled, dtype=np.int64)
        else:
            const = np.asarray(value, dtype=eff.device_dtype())

        def run_lit(rt: Runtime) -> DVal:
            return DVal(jnp.asarray(const), None, dtype or T.LONG)

        return run_lit

    def _string_operand_info(self, e: ast.Expr) -> Optional[int]:
        """If e is (an alias of) a raw string column, return its ordinal."""
        if isinstance(e, ast.Alias):
            return self._string_operand_info(e.child)
        if isinstance(e, ast.Col):
            dt = e.dtype if e.dtype is not None \
                else self.col_types.get(e.index)
            if dt is not None and dt.name == "string":
                return e.index
        return None

    def _string_value_transform(self, e: ast.Expr):
        """(col_idx | None, fn: dict value → derived value) for a
        string-valued expression computable from ONE column's dictionary
        values plus literals — compositions like upper(concat(s, '_x'))
        included. col_idx None means literal-only. Raises CompileError
        when not derivable (two columns, non-literal args, ...)."""
        if isinstance(e, ast.Alias):
            return self._string_value_transform(e.child)
        if isinstance(e, ast.Lit):
            lit = None if e.value is None else str(e.value)
            return None, lambda v: lit
        ci = self._string_operand_info(e)
        if ci is not None:
            return ci, lambda v: v
        if not isinstance(e, ast.Func) or \
                e.name not in STRING_VALUE_FUNCS:
            raise CompileError("not a derivable string expression")
        name = e.name
        if name == "concat":
            parts = [self._string_value_transform(a) for a in e.args]
            cis = {c for c, _ in parts if c is not None}
            if len(cis) > 1:
                raise CompileError("concat over two string columns")

            def fn_concat(v, parts=parts):
                out = []
                for _, pf in parts:
                    pv = pf(v)
                    if pv is None:   # SQL concat: any NULL → NULL
                        return None
                    out.append(pv)
                return "".join(out)

            return (cis.pop() if cis else None), fn_concat
        ci, base = self._string_value_transform(e.args[0])
        extra = []
        for a in e.args[1:]:
            if not isinstance(a, ast.Lit):
                raise CompileError(f"{name} with non-literal args")
            extra.append(a.value)

        def op(v):
            if v is None:
                return None
            if name == "upper":
                return v.upper()
            if name == "lower":
                return v.lower()
            if name == "trim":
                return v.strip()
            if name == "ltrim":
                return v.lstrip()
            if name == "rtrim":
                return v.rstrip()
            if name in ("substr", "substring"):
                start = int(extra[0]) - 1 if extra and \
                    extra[0] is not None else 0
                ln = int(extra[1]) if len(extra) > 1 and \
                    extra[1] is not None else None
                return v[start:start + ln] if ln is not None else v[start:]
            if name == "replace":
                if not extra or extra[0] is None or \
                        (len(extra) > 1 and extra[1] is None):
                    # NULL search/replacement → NULL result (Spark):
                    # host path implements that
                    raise CompileError("replace with NULL argument")
                return v.replace(str(extra[0]),
                                 str(extra[1]) if len(extra) > 1 else "")
            if name in ("lpad", "rpad"):
                n2 = int(extra[0])
                if n2 <= 0:
                    return ""
                pad = str(extra[1]) if len(extra) > 1 and \
                    extra[1] is not None else " "
                if len(v) >= n2:
                    return v[:n2]
                fill = (pad * n2)[:n2 - len(v)] if pad else ""
                return fill + v if name == "lpad" else v + fill
            if name == "initcap":
                return " ".join(p[:1].upper() + p[1:].lower()
                                for p in v.split(" "))
            if name == "repeat":
                return v * max(0, int(extra[0]))
            if name == "reverse":
                return v[::-1]
            if name == "translate":
                frm = str(extra[0]) if extra and extra[0] is not None else ""
                to = str(extra[1]) if len(extra) > 1 and \
                    extra[1] is not None else ""
                table = {ord(f): (to[i] if i < len(to) else None)
                         for i, f in enumerate(frm)}
                return v.translate(table)
            if name == "split_part":
                delim = str(extra[0])
                idx = int(extra[1])
                parts = v.split(delim) if delim else [v]
                if idx == 0:
                    raise CompileError("split_part index must not be 0")
                pos = idx - 1 if idx > 0 else len(parts) + idx
                return parts[pos] if 0 <= pos < len(parts) else ""
            raise CompileError(name)

        return ci, lambda v: op(base(v))

    def _emit_binop(self, e: ast.BinOp) -> Callable[[Runtime], DVal]:
        op = e.op
        # --- string predicate vs literal → dictionary LUT ---
        if op in ("=", "!=", "<", "<=", ">", ">="):
            lcol = self._string_operand_info(e.left)
            rcol = self._string_operand_info(e.right)
            if self._is_literalish(e.right):
                ci, fnt = self._try_string_transform(e.left)
                if ci is not None:
                    return self._emit_string_cmp(ci, op, e.right, fnt)
            if self._is_literalish(e.left):
                ci, fnt = self._try_string_transform(e.right)
                if ci is not None:
                    flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
                    return self._emit_string_cmp(ci, flip.get(op, op),
                                                 e.left, fnt)
            if lcol is not None and rcol is not None:
                return self._emit_string_colcmp(lcol, rcol, op)

        left = self.emit(e.left)
        right = self.emit(e.right)

        if op in ("and", "or"):
            is_and = op == "and"

            def run_logic(rt: Runtime) -> DVal:
                a, b = left(rt), right(rt)
                v = (a.value & b.value) if is_and else (a.value | b.value)
                null = None
                if a.null is not None or b.null is not None:
                    an = a.null if a.null is not None else False
                    bn = b.null if b.null is not None else False
                    if is_and:  # Kleene: false and null = false
                        null = (an & bn) | (an & b.value) | (bn & a.value)
                    else:       # true or null = true
                        null = (an & bn) | (an & ~b.value) | (bn & ~a.value)
                    v = v & ~null if is_and else v
                out = DVal(v, null, T.BOOLEAN)
                # run-space conjunction: both sides run-resident over the
                # SAME run partition (identity on ends) combines in O(R)
                # run space — the alignment proof survives the whole
                # filter tree this way
                if (null is None and a.rmask is not None
                        and b.rmask is not None and a.rends is b.rends):
                    out.rmask = (a.rmask & b.rmask) if is_and \
                        else (a.rmask | b.rmask)
                    out.rends = a.rends
                return out

            return run_logic

        fns = {
            "+": lambda a, b: a + b, "-": lambda a, b: a - b,
            "*": lambda a, b: a * b, "%": lambda a, b: a % b,
            "=": lambda a, b: a == b, "!=": lambda a, b: a != b,
            "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
            ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
        }
        is_cmp = op in ("=", "!=", "<", "<=", ">", ">=")
        if op == "/":
            int_to = self.int_div_dtype

            def run_div(rt: Runtime) -> DVal:
                # exact decimals leave the int domain here: SQL decimal
                # division result is DOUBLE in this engine (divergence
                # from the reference's widened-decimal quotient, noted
                # in types.DecimalType)
                a, b = _dec_unscale(left(rt)), _dec_unscale(right(rt))
                av, bv = a.value, b.value
                if jnp.issubdtype(jnp.asarray(av).dtype, jnp.integer):
                    av = av.astype(int_to or _float_dtype())
                if jnp.issubdtype(jnp.asarray(bv).dtype, jnp.integer):
                    bv = bv.astype(int_to or _float_dtype())
                null = _or_null(a.null, b.null)
                null = _or_null(null, b.value == 0)
                safe = jnp.where(b.value == 0, 1, bv)
                return DVal(av / safe, null, T.DOUBLE)

            return run_div

        fn = fns[op]
        key = None if is_cmp else _shape_key(e)

        def run_bin(rt: Runtime) -> DVal:
            a, b = left(rt), right(rt)
            if is_cmp:
                # compressed-domain lane: a code/run-resident column vs a
                # scalar literal compares on codes/runs, never on values
                cm = _compressed_cmp(op, a, b)
                if cm is None:
                    cm = _compressed_cmp(_FLIP_CMP[op], b, a)
                if cm is not None:
                    return cm
            if _dec_scale(a) is not None or _dec_scale(b) is not None:
                out = _dec_binop(op, fn, a, b, is_cmp, rt, key)
                if out is not None:
                    return out
                # result leaves the exact domain (float operand, or the
                # precision outgrew int64): plain float math
                a, b = _dec_unscale(a), _dec_unscale(b)
            v = fn(a.value, b.value)
            dt = T.BOOLEAN if is_cmp else _promote(a.dtype, b.dtype)
            return DVal(v, _or_null(a.null, b.null), dt)

        return run_bin

    def _try_string_transform(self, e: ast.Expr):
        """(col_idx, value fn) when e is a derivable string expression of
        one column (raw column included), else (None, None)."""
        try:
            ci, fnt = self._string_value_transform(e)
        except CompileError:
            return None, None
        return (ci, fnt) if ci is not None else (None, None)

    def _emit_string_cmp(self, col_idx: int, op: str, lit_expr,
                         transform=None) -> Callable[[Runtime], DVal]:
        get_lit = (lambda params: self._param_value(lit_expr, params))
        ops = {"=": np.equal, "!=": np.not_equal,
               "<": np.less, "<=": np.less_equal,
               ">": np.greater, ">=": np.greater_equal}
        cmp = ops[op]
        fnt = transform or (lambda v: v)

        def one(v, params):
            tv = fnt(v)
            return tv is not None and bool(cmp(tv, get_lit(params)))

        aux_i = self._string_pred_lut(
            col_idx, lambda d, params: np.array(
                [one(v, params) for v in d],
                dtype=np.bool_) if len(d) else np.zeros(0, np.bool_))
        return self._lut_runner(col_idx, aux_i)

    def _emit_string_colcmp(self, li: int, ri: int, op: str
                            ) -> Callable[[Runtime], DVal]:
        """string col vs string col — same-dictionary equality only (the
        realistic case: self-comparison or shared table dictionary)."""
        if op not in ("=", "!="):
            raise CompileError("ordering between two string columns "
                               "is not supported on device")
        lg, rg = self.dict_getters.get(li), self.dict_getters.get(ri)
        neg = op == "!="

        def run(rt: Runtime) -> DVal:
            a, b = rt.cols[li], rt.cols[ri]
            da = a.dictionary() if callable(a.dictionary) else a.dictionary
            db = b.dictionary() if callable(b.dictionary) else b.dictionary
            if da is not None and db is not None and da is not db and \
                    list(da) != list(db):
                raise CompileError("cross-dictionary string comparison "
                                   "not supported on device")
            v = (a.value != b.value) if neg else (a.value == b.value)
            return DVal(v, _or_null(a.null, b.null), T.BOOLEAN)

        return run

    def _lut_runner(self, col_idx: int, aux_i: int) -> Callable[[Runtime], DVal]:
        def run(rt: Runtime) -> DVal:
            c = rt.cols[col_idx]
            lut = rt.aux[aux_i]
            v = lut[c.value]
            return DVal(v, c.null, T.BOOLEAN)

        return run

    def _emit_in(self, e: ast.InList) -> Callable[[Runtime], DVal]:
        col_idx = self._string_operand_info(e.child)
        if col_idx is not None:
            getters = [(lambda params, x=v: self._param_value(x, params))
                       for v in e.values]
            negated = e.negated

            aux_i = self._string_pred_lut(
                col_idx,
                lambda d, params: np.isin(
                    np.array([x if x is not None else "" for x in d]),
                    np.array([str(g(params)) for g in getters])))
            base = self._lut_runner(col_idx, aux_i)
            if not negated:
                return base

            def run_negated(rt: Runtime) -> DVal:
                r = base(rt)
                return DVal(~r.value, r.null, T.BOOLEAN)

            return run_negated

        negated = e.negated
        # large literal lists (IN-subquery results): sorted aux array +
        # searchsorted — O(log k) compute, O(1) graph size (a chained-OR
        # lowering took minutes of XLA compile at a few thousand values)
        if len(e.values) > 8 and all(self._is_literalish(v)
                                     for v in e.values):
            getters = [(lambda params, x=v: self._param_value(x, params))
                       for v in e.values]

            def build_sorted(params):
                vals = np.asarray([g(params) for g in getters])
                vals = np.sort(vals.astype(np.float64)
                               if vals.dtype == object else vals)
                pad = (1 << (len(vals) - 1).bit_length()) - len(vals)
                if pad:
                    vals = np.concatenate(
                        [vals, np.full(pad, vals[-1])])
                return vals

            aux_i = self._register_aux(build_sorted)
            child = _dec_wrap_unscaled(self.emit(e.child))

            def run_in_sorted(rt: Runtime) -> DVal:
                c = child(rt)
                table = rt.aux[aux_i]
                # compare in the PROMOTED dtype: truncating a float probe
                # to an int table produced false positives (review finding)
                if jnp.issubdtype(jnp.asarray(c.value).dtype, jnp.floating) \
                        or jnp.issubdtype(table.dtype, jnp.floating):
                    # f64 even on TPU: f32 would alias distinct int keys
                    table_c = table.astype(jnp.float64)
                    cv = c.value.astype(jnp.float64)
                else:
                    table_c = table.astype(jnp.int64)
                    cv = c.value.astype(jnp.int64)
                pos = jnp.clip(jnp.searchsorted(table_c, cv), 0,
                               table_c.shape[0] - 1)
                hit = table_c[pos] == cv
                if negated:
                    hit = ~hit
                return DVal(hit, c.null, T.BOOLEAN)

            return run_in_sorted

        child = _dec_wrap_unscaled(self.emit(e.child))
        values = [_dec_wrap_unscaled(self.emit(v)) for v in e.values]

        def run_in(rt: Runtime) -> DVal:
            c = child(rt)
            acc = None
            null = c.null
            for v in values:
                dv = v(rt)
                hit = c.value == dv.value
                null = _or_null(null, dv.null)
                acc = hit if acc is None else (acc | hit)
            if negated:
                acc = ~acc
            return DVal(acc, null, T.BOOLEAN)

        return run_in

    def _emit_like(self, e: ast.Like) -> Callable[[Runtime], DVal]:
        col_idx, fnt = self._try_string_transform(e.child)
        if col_idx is None:
            raise CompileError("LIKE requires a string column")
        # SQL LIKE: % = any run, _ = any single char
        regex = re.compile(
            "^" + re.escape(e.pattern).replace("%", ".*").replace("_", ".")
            .replace("\\%", "%").replace("\\_", "_") + "$", re.DOTALL)
        negated = e.negated

        def one(v):
            tv = fnt(v)
            return tv is not None and regex.match(tv) is not None

        aux_i = self._string_pred_lut(
            col_idx, lambda d, params: np.array(
                [one(v) for v in d], dtype=np.bool_))
        base = self._lut_runner(col_idx, aux_i)
        if not negated:
            return base

        def run_neg(rt: Runtime) -> DVal:
            r = base(rt)
            return DVal(~r.value, r.null, T.BOOLEAN)

        return run_neg

    def _emit_case(self, e: ast.Case) -> Callable[[Runtime], DVal]:
        # branch values unscale exact decimals: branches mix with
        # literals/other types, and scaled ints must not meet plain
        # values in one jnp.where lattice
        whens = [(self.emit(c), _dec_wrap_unscaled(self.emit(v)))
                 for c, v in e.whens]
        other = _dec_wrap_unscaled(self.emit(e.otherwise)) \
            if e.otherwise is not None else None

        def run_case(rt: Runtime) -> DVal:
            branches = [(c(rt), v(rt)) for c, v in whens]
            # result type promotes across ALL branches (ELSE 0 must not
            # demote a double CASE to int — it truncated aggregates)
            dt = None
            for _, v_dv in branches:
                dt = _promote(dt, v_dv.dtype)
            if other is not None:
                out = other(rt)
                dt = _promote(dt, out.dtype)
                acc_v, acc_n = out.value, out.null
            else:
                first_v = branches[0][1]
                acc_v = jnp.zeros_like(first_v.value)
                acc_n = True  # no branch matched → NULL
            for cond, val in reversed(branches):
                cv = cond.value
                if cond.null is not None:
                    cv = cv & ~cond.null
                acc_v = jnp.where(cv, val.value, acc_v)
                vn = val.null if val.null is not None else False
                if acc_n is True:
                    acc_n_arr = jnp.where(cv, vn, True)
                    acc_n = acc_n_arr
                elif acc_n is None and val.null is None:
                    acc_n = None
                else:
                    an = acc_n if acc_n is not None else False
                    acc_n = jnp.where(cv, vn, an)
            if acc_n is True:
                acc_n = jnp.ones(jnp.shape(acc_v), dtype=bool)
            return DVal(acc_v, acc_n, dt)

        return run_case

    def _emit_decimal_avg(self, e: DecimalAvg) -> Callable[[Runtime], DVal]:
        """The sum at its scale s, brought to the average's scale t by
        10^(t - s) under the guard (so that 2|n| stays inside int64),
        then n / count rounded half away from zero: sign(n) *
        ((2|n| + c) // 2c)."""
        srun, crun, dt = self.emit(e.sum), self.emit(e.count), e.dtype

        def run_avg(rt: Runtime) -> DVal:
            sv, cv = srun(rt), crun(rt)
            up = 10 ** (dt.scale - sv.dtype.scale)
            n = sv.value.astype(jnp.int64)
            _dec_guard(rt, None, _f32_abs(n) * np.float32(2 * up)
                       >= _GUARD_BOUND)
            n = n * up
            c = cv.value.astype(jnp.int64)
            empty = c == 0
            c = jnp.where(empty, 1, c)
            q = jnp.sign(n) * ((2 * jnp.abs(n) + c) // (2 * c))
            return DVal(q, _or_null(_or_null(sv.null, cv.null), empty), dt)

        return run_avg

    def _emit_cast(self, e: ast.Cast) -> Callable[[Runtime], DVal]:
        child = self.emit(e.child)
        to = e.to
        if to.name == "string":
            raise CompileError("CAST to string not supported on device")
        np_dt = to.device_dtype()
        to_exact = to.name == "decimal" and getattr(to, "is_exact", False)
        # a target past int64's precision: the scaled value is guarded
        to_wide = to_exact and to.precision > T.DECIMAL_INT64_PRECISION

        def run_cast(rt: Runtime) -> DVal:
            c = child(rt)
            s_from = _dec_scale(c)
            if s_from is not None:
                if to_exact:  # decimal -> decimal: integer rescale
                    v, risky = _dec_aligned(c.value.astype(jnp.int64),
                                            c.dtype, to.scale, False)
                    if risky is not None:
                        _dec_guard(rt, None, risky)
                    return DVal(v, c.null, to)
                if T.is_integral(to):
                    # decimal -> int truncates toward zero (Spark), done
                    # exactly in the int domain
                    f = 10 ** s_from
                    iv = c.value.astype(jnp.int64)
                    tv = jnp.sign(iv) * (jnp.abs(iv) // f)
                    return DVal(tv.astype(np_dt), c.null, to)
                c = _dec_unscale(c)
            if to_exact:
                v = c.value
                f = 10 ** to.scale
                if jnp.issubdtype(jnp.asarray(v).dtype, jnp.integer):
                    if to_wide:
                        _dec_guard(rt, None, _f32_abs(v) * np.float32(f)
                                   >= _GUARD_BOUND)
                    return DVal(v.astype(jnp.int64) * f, c.null, to)
                # HALF_UP (half away from zero), matching
                # decimal_to_unscaled / _dec_rescale_int — jnp.round
                # would tie to even
                vf = v.astype(jnp.float64) * f
                scaled = jnp.sign(vf) * jnp.floor(jnp.abs(vf) + 0.5)
                if to_wide:
                    _dec_guard(rt, None, jnp.abs(vf) >= _GUARD_BOUND)
                return DVal(scaled.astype(jnp.int64), c.null, to)
            return DVal(c.value.astype(np_dt), c.null, to)

        return run_cast

    def _arg_array_col(self, e: ast.Expr):
        return self._arg_typed_col(e, T.ArrayType)

    def _arg_typed_col(self, e: ast.Expr, type_cls):
        """(dtype, column ordinal) of an argument that is (an alias of)
        a raw column of `type_cls`, else (None, None)."""
        if isinstance(e, ast.Alias):
            return self._arg_typed_col(e.child, type_cls)
        if isinstance(e, ast.Col):
            dt = e.dtype if e.dtype is not None else \
                self.col_types.get(e.index)
            if isinstance(dt, type_cls):
                return dt, e.index
        return None, None

    def _arg_map_col(self, e: ast.Expr):
        return self._arg_typed_col(e, T.MapType)

    def _literal_code_aux(self, lit_expr, getter) -> int:
        """Register an aux array resolving a literal at bind time to
        [dictionary code, needle_is_null] — -1 = absent (matches no
        code); a NULL literal flags [1]==1 so runners propagate NULL.
        Shared by string-array contains and map element_at (review
        finding: two byte-identical builders)."""
        get_lit = (lambda params: self._param_value(lit_expr, params))

        def build(params, getter=getter):
            lit = get_lit(params)
            if lit is None:
                return np.array([-1, 1], np.int32)
            hit = np.flatnonzero(
                np.asarray(getter(), dtype=object) == str(lit))
            return np.array([hit[0] if hit.size else -1, 0], np.int32)

        return self._register_aux(build)

    def _arg_array_type(self, e: ast.Expr):
        """Static ArrayType of an argument expression, else None."""
        if isinstance(e, ast.Col):
            dt = e.dtype if e.dtype is not None else \
                self.col_types.get(e.index)
            return dt if isinstance(dt, T.ArrayType) else None
        if isinstance(e, ast.Alias):
            return self._arg_array_type(e.child)
        return None

    def _emit_func(self, e: ast.Func) -> Callable[[Runtime], DVal]:
        name = e.name
        if name in ast.AGG_FUNCS:
            raise CompileError(
                f"aggregate {name} outside aggregation context")
        args = [self.emit(a) for a in e.args]
        # scalar functions consume exact decimals in the plain float
        # domain — their value math (round, sqrt, coalesce-with-
        # literals, ...) is blind to the scaled-int representation.
        # Aggregates never reach here (executor handles them exactly).
        args = [_dec_wrap_unscaled(r) for r in args]

        # device lowering for numeric fixed-width arrays: the column binds
        # as (values [.., L], lengths, element_nulls) plates; padding and
        # NULL elements are excluded via the length/element-null masks
        # (ref: SerializedArray; round-1 gap: every array op was host)
        if name == "element_at" and len(e.args) == 2:
            s0, s_ci = self._arg_typed_col(e.args[0], T.StructType)
            if s0 is not None:
                # STRUCT field access: the field name is STRUCTURAL
                # (tokenization keeps it a literal) and selects one
                # [B, C] plate statically at compile time
                sdicts = self.dict_getters.get(s_ci)
                if not isinstance(sdicts, StructDicts):
                    raise CompileError(
                        "struct column without device plates: host path")
                if not isinstance(e.args[1], ast.Lit):
                    raise CompileError(
                        "element_at over a struct needs a literal "
                        "field name: host path")
                want = str(e.args[1].value).lower()
                fidx = next((k for k, (fn, _t) in enumerate(s0.fields)
                             if fn.lower() == want), None)
                if fidx is None:
                    raise CompileError(
                        f"no struct field {want!r}: host path")
                fname, ftype = s0.fields[fidx]
                arr_run = args[0]

                def run_sfield(rt: Runtime) -> DVal:
                    d = arr_run(rt)
                    fvals, fnuls = d.value
                    null = _or_null(d.null, fnuls[fidx])
                    return DVal(fvals[fidx], null, ftype,
                                dictionary=sdicts.fields.get(fname)
                                if ftype.name == "string" else None)

                return run_sfield

        if name in ("size", "element_at") and e.args:
            m0, m_ci = self._arg_map_col(e.args[0])
            if m0 is not None:
                mdicts = self.dict_getters.get(m_ci)
                if not isinstance(mdicts, MapDicts):
                    raise CompileError(
                        "map column without device plates: host path")
                arr_run = args[0]
                if name == "size":
                    def run_msize(rt: Runtime) -> DVal:
                        d = arr_run(rt)
                        _k, _v, lengths, _vn = d.value
                        return DVal(lengths.astype(jnp.int32), d.null,
                                    T.INT)

                    return run_msize
                # element_at(map, 'key'): literal key -> key-dictionary
                # CODE at bind; first matching entry's value (string
                # values decode through the value dictionary)
                if not self._is_literalish(e.args[1]):
                    raise CompileError(
                        "element_at over a map needs a literal key: "
                        "host path")
                aux_i = self._literal_code_aux(e.args[1], mdicts.key)
                val_t = m0.value
                val_is_str = val_t.name == "string"

                def run_melem(rt: Runtime) -> DVal:
                    d = arr_run(rt)
                    kcodes, vals, lengths, vnul = d.value
                    L = kcodes.shape[-1]
                    code = rt.aux[aux_i][0]
                    key_null = rt.aux[aux_i][1] == 1
                    in_range = jnp.arange(L) < lengths[..., None]
                    hit = (kcodes == code) & in_range
                    found = hit.any(axis=-1)
                    idx = jnp.argmax(hit, axis=-1)
                    out = jnp.take_along_axis(
                        vals, idx[..., None], axis=-1)[..., 0]
                    vn = jnp.take_along_axis(
                        vnul, idx[..., None], axis=-1)[..., 0]
                    null = _or_null(
                        d.null,
                        ~found | vn
                        | jnp.broadcast_to(key_null, found.shape))
                    return DVal(out, null, val_t,
                                dictionary=mdicts.value
                                if val_is_str else None)

                return run_melem

        if name in ARRAY_DEVICE_FUNCS and e.args:
            t0 = self._arg_array_type(e.args[0])
            if t0 is not None:
                is_str_elem = t0.element.name == "string"
                _adt, a_ci = self._arg_array_col(e.args[0])
                elem_dict = self.dict_getters.get(a_ci) \
                    if a_ci is not None else None
                if not T.is_numeric(t0.element) and not (
                        is_str_elem and elem_dict is not None):
                    raise CompileError(
                        "array element type has no device plates: "
                        "host path")
                arr_run = args[0]
                if name == "size":
                    def run_size(rt: Runtime) -> DVal:
                        d = arr_run(rt)
                        _vals, lengths, _en = d.value
                        return DVal(lengths.astype(jnp.int32), d.null,
                                    T.INT)

                    return run_size
                other = args[1]
                if name == "element_at":
                    def run_elem(rt: Runtime) -> DVal:
                        d = arr_run(rt)
                        iv = other(rt)
                        vals, lengths, enul = d.value
                        pos = jnp.asarray(iv.value).astype(jnp.int32) - 1
                        pos_b = jnp.broadcast_to(pos, lengths.shape)
                        safe = jnp.clip(pos_b, 0, vals.shape[-1] - 1)
                        out = jnp.take_along_axis(
                            vals, safe[..., None], axis=-1)[..., 0]
                        el_null = jnp.take_along_axis(
                            enul, safe[..., None], axis=-1)[..., 0]
                        bad = (pos_b < 0) | (pos_b >= lengths) | el_null
                        nl = _or_null(_or_null(d.null, iv.null), bad)
                        # string elements are CODES: the DVal carries
                        # the element dictionary so projections decode
                        # (executor run_project picks dv.dictionary up)
                        return DVal(out, nl, t0.element,
                                    dictionary=elem_dict
                                    if is_str_elem else None)

                    return run_elem

                if is_str_elem:
                    # array_contains(a, 'lit'): resolve the needle to
                    # its element-dictionary CODE at bind time (absent
                    # value -> -1, which no code matches)
                    if not self._is_literalish(e.args[1]):
                        raise CompileError(
                            "array_contains over a string array needs "
                            "a literal needle: host path")
                    aux_i = self._literal_code_aux(e.args[1], elem_dict)

                    def run_contains_str(rt: Runtime) -> DVal:
                        d = arr_run(rt)
                        vals, lengths, enul = d.value
                        L = vals.shape[-1]
                        code = rt.aux[aux_i][0]
                        needle_null = rt.aux[aux_i][1] == 1
                        eq = vals == code
                        in_range = (jnp.arange(L) < lengths[..., None]) \
                            & ~enul
                        out = (eq & in_range).any(axis=-1)
                        null = _or_null(
                            d.null, jnp.broadcast_to(needle_null,
                                                     out.shape))
                        return DVal(out, null, T.BOOLEAN)

                    return run_contains_str

                def run_contains(rt: Runtime) -> DVal:
                    d = arr_run(rt)
                    xv = other(rt)
                    vals, lengths, enul = d.value
                    L = vals.shape[-1]
                    needle = jnp.asarray(xv.value)
                    if t0.element.name == "decimal" \
                            and getattr(t0.element, "is_exact", False) \
                            and jnp.issubdtype(vals.dtype, jnp.integer):
                        # element plates hold SCALED ints: the needle
                        # scales the same way (HALF_UP)
                        nf = needle.astype(jnp.float64) \
                            * (10 ** t0.element.scale)
                        needle = (jnp.sign(nf)
                                  * jnp.floor(jnp.abs(nf) + 0.5)
                                  ).astype(jnp.int64)
                    x = jnp.broadcast_to(needle, lengths.shape)
                    # compare under jnp promotion (a fractional needle
                    # must NOT truncate into the int element domain)
                    eq = vals == x[..., None]
                    in_range = (jnp.arange(L) < lengths[..., None]) & ~enul
                    out = (eq & in_range).any(axis=-1)
                    return DVal(out, _or_null(d.null, xv.null), T.BOOLEAN)

                return run_contains

        if name == "coalesce":
            def run_coalesce(rt: Runtime) -> DVal:
                vals = [a(rt) for a in args]
                out = vals[-1]
                acc_v, acc_n = out.value, out.null
                for v in reversed(vals[:-1]):
                    isnull = v.null if v.null is not None else \
                        jnp.zeros(jnp.shape(v.value), dtype=bool)
                    acc_v = jnp.where(isnull, acc_v, v.value)
                    if acc_n is None:
                        acc_n = None if v.null is None else None
                    else:
                        acc_n = isnull & acc_n
                    if v.null is None:
                        acc_n = None
                return DVal(acc_v, acc_n, vals[0].dtype)

            return run_coalesce

        if name == "abs":
            return self._unary_math(args[0], jnp.abs, keep_type=True)
        if name == "sqrt":
            return self._unary_math(args[0], lambda x: jnp.sqrt(
                x.astype(_float_dtype())))
        if name in ("ln", "log"):
            return self._unary_math(args[0], lambda x: jnp.log(
                x.astype(_float_dtype())))
        if name == "exp":
            return self._unary_math(args[0], lambda x: jnp.exp(
                x.astype(_float_dtype())))
        if name == "round":
            digits = 0
            if len(e.args) == 2 and isinstance(
                    e.args[1], (ast.Lit, ast.ParamLiteral, ast.Param)):
                if isinstance(e.args[1], ast.Lit):
                    digits = int(e.args[1].value)
                else:
                    # tokenized literal or prepared '?': traced scalar
                    # (a '?' here used to silently round to 0 digits)
                    digits_pos = e.args[1].pos
                    digits = None
            # negative digits: divide by the exact integer power (0.001 is
            # not binary-exact; round(x*0.001)/0.001 drifted sums)
            def run_round(rt: Runtime) -> DVal:
                c = args[0](rt)
                if digits is not None:  # static digits
                    if digits >= 0:
                        mult = float(10 ** digits)
                        v = jnp.round(c.value * mult) / mult
                    else:
                        scale = float(10 ** (-digits))
                        v = jnp.round(c.value / scale) * scale
                else:  # tokenized digits: traced scalar
                    d = rt.params[digits_pos].astype(jnp.float64)
                    scale = jnp.round(jnp.power(10.0, jnp.abs(d)))
                    v = jnp.where(d >= 0,
                                  jnp.round(c.value * scale) / scale,
                                  jnp.round(c.value / scale) * scale)
                return DVal(v, c.null, c.dtype)

            return run_round
        if name in ("pow", "power"):
            def run_pow(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                return DVal(jnp.power(a.value.astype(_float_dtype()),
                                      b.value),
                            _or_null(a.null, b.null), T.DOUBLE)

            return run_pow

        if name in ("year", "month", "day", "dayofmonth", "quarter",
                    "dayofyear", "dayofweek", "weekofyear"):
            part = "day" if name == "dayofmonth" else name

            def run_datepart(rt: Runtime) -> DVal:
                c = args[0](rt)
                days = _to_days(c)
                y, m, d = _civil_from_days(days)
                if part in ("year", "month", "day"):
                    out = {"year": y, "month": m, "day": d}[part]
                elif part == "quarter":
                    out = (m + 2) // 3
                elif part == "dayofyear":
                    out = days - _days_from_civil(y, jnp.ones_like(m),
                                                  jnp.ones_like(d)) + 1
                elif part == "dayofweek":
                    # Spark: 1=Sunday..7=Saturday (1970-01-01 Thu → 5)
                    out = (days + 4) % 7 + 1
                else:  # weekofyear: ISO-8601 week via the Thursday trick
                    wd = (days + 3) % 7 + 1          # ISO weekday, Mon=1
                    thu = days + (4 - wd)
                    ty, _, _ = _civil_from_days(thu)
                    jan1 = _days_from_civil(ty, jnp.ones_like(ty,
                                            dtype=jnp.int32),
                                            jnp.ones_like(ty,
                                            dtype=jnp.int32))
                    out = (thu - jan1) // 7 + 1
                return DVal(out.astype(jnp.int32), c.null, T.INT)

            return run_datepart

        if name in ("hour", "minute", "second"):
            divisor, modulo = {"hour": (3_600_000_000, 24),
                               "minute": (60_000_000, 60),
                               "second": (1_000_000, 60)}[name]

            def run_timepart(rt: Runtime) -> DVal:
                c = args[0](rt)
                if c.dtype is not None and c.dtype.name == "timestamp":
                    out = (c.value // divisor) % modulo
                else:  # DATE has no time component
                    out = jnp.zeros_like(c.value)
                return DVal(out.astype(jnp.int32), c.null, T.INT)

            return run_timepart

        if name in ("date_add", "date_sub"):
            sign = 1 if name == "date_add" else -1

            def run_dateadd(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                out = _to_days(a) + sign * b.value.astype(jnp.int32)
                return DVal(out.astype(jnp.int32),
                            _or_null(a.null, b.null), T.DATE)

            return run_dateadd

        if name == "datediff":
            def run_datediff(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                return DVal((_to_days(a) - _to_days(b)).astype(jnp.int32),
                            _or_null(a.null, b.null), T.INT)

            return run_datediff

        if name == "add_months":
            def run_addmonths(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                y, m, d = _civil_from_days(_to_days(a))
                m0 = y.astype(jnp.int64) * 12 + (m - 1) + \
                    b.value.astype(jnp.int64)
                y2 = (m0 // 12).astype(jnp.int32)
                m2 = (m0 % 12 + 1).astype(jnp.int32)
                d2 = jnp.minimum(d, _days_in_month(y2, m2))
                return DVal(_days_from_civil(y2, m2, d2),
                            _or_null(a.null, b.null), T.DATE)

            return run_addmonths

        if name == "last_day":
            def run_lastday(rt: Runtime) -> DVal:
                c = args[0](rt)
                y, m, _ = _civil_from_days(_to_days(c))
                return DVal(_days_from_civil(y, m, _days_in_month(y, m)),
                            c.null, T.DATE)

            return run_lastday

        if name == "trunc":
            fmt = e.args[1].value if len(e.args) > 1 and \
                isinstance(e.args[1], ast.Lit) else None
            if fmt is None:
                raise CompileError("trunc needs a literal format")
            fmt = str(fmt).upper()

            def run_trunc(rt: Runtime) -> DVal:
                c = args[0](rt)
                days = _to_days(c)
                y, m, d = _civil_from_days(days)
                one = jnp.ones_like(m)
                if fmt in ("YEAR", "YYYY", "YY"):
                    out = _days_from_civil(y, one, one)
                elif fmt in ("MONTH", "MM", "MON"):
                    out = _days_from_civil(y, m, one)
                elif fmt in ("QUARTER", "Q"):
                    out = _days_from_civil(y, ((m - 1) // 3) * 3 + 1, one)
                elif fmt == "WEEK":
                    out = days - (days + 3) % 7   # ISO Monday
                else:
                    raise CompileError(f"trunc format {fmt!r}")
                return DVal(out.astype(jnp.int32), c.null, T.DATE)

            return run_trunc

        if name == "months_between":
            def run_mb(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                y1, m1, d1 = _civil_from_days(_to_days(a))
                y2, m2, d2 = _civil_from_days(_to_days(b))
                whole = ((y1 - y2) * 12 + (m1 - m2)).astype(_float_dtype())
                last1 = _days_in_month(y1, m1)
                last2 = _days_in_month(y2, m2)
                same = (d1 == d2) | ((d1 == last1) & (d2 == last2))
                frac = jnp.where(same, 0.0,
                                 (d1 - d2).astype(_float_dtype()) / 31.0)
                return DVal(whole + frac, _or_null(a.null, b.null),
                            T.DOUBLE)

            return run_mb

        if name == "unix_timestamp":
            def run_unix(rt: Runtime) -> DVal:
                c = args[0](rt)
                if c.dtype is not None and c.dtype.name == "timestamp":
                    out = c.value // 1_000_000
                else:
                    out = c.value.astype(jnp.int64) * 86_400
                return DVal(out.astype(jnp.int64), c.null, T.LONG)

            return run_unix

        if name == "to_date" and args:
            # date/timestamp input: pure conversion; a string COLUMN is
            # handled below via the dictionary int-LUT path
            try:
                self._string_value_transform(e.args[0])
                string_input = True
            except CompileError:
                string_input = False
            if not string_input:
                def run_todate(rt: Runtime) -> DVal:
                    c = args[0](rt)
                    return DVal(_to_days(c), c.null, T.DATE)

                return run_todate

        if name == "sign":
            return self._unary_math(args[0], lambda x: jnp.sign(
                x.astype(_float_dtype())))
        if name in ("floor", "ceil", "ceiling"):
            jfn = jnp.floor if name == "floor" else jnp.ceil

            def run_fc(rt: Runtime) -> DVal:
                c = args[0](rt)
                return DVal(jfn(c.value.astype(_float_dtype()))
                            .astype(jnp.int64), c.null, T.LONG)

            return run_fc
        if name in ("mod", "pmod"):
            pos = name == "pmod"

            def run_mod(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                zero = b.value == 0
                bs = jnp.where(zero, jnp.ones_like(b.value), b.value)
                # mod keeps the dividend's sign (Spark %); pmod >= 0
                out = jnp.mod(jnp.mod(a.value, bs) + bs, bs) if pos \
                    else jnp.fmod(a.value, bs)
                null = _or_null(_or_null(a.null, b.null),
                                jnp.broadcast_to(zero, jnp.shape(out)))
                return DVal(out, null, _promote(a.dtype, b.dtype))

            return run_mod
        if name == "nullif":
            def run_nullif(rt: Runtime) -> DVal:
                a, b = args[0](rt), args[1](rt)
                _no_string_operands((a, b), name)
                eq = a.value == b.value
                if b.null is not None:
                    eq = eq & ~b.null
                return DVal(a.value,
                            eq if a.null is None else (a.null | eq),
                            a.dtype)

            return run_nullif
        if name in ("greatest", "least"):
            pickmax = name == "greatest"

            def run_gl(rt: Runtime) -> DVal:
                dvs = [a(rt) for a in args]
                _no_string_operands(dvs, name)
                dt = None
                for d in dvs:
                    dt = _promote(dt, d.dtype)
                np_dt = dt.device_dtype()
                if jnp.issubdtype(np_dt, jnp.floating):
                    ident = -jnp.inf if pickmax else jnp.inf
                else:
                    info = np.iinfo(np_dt)
                    ident = info.min if pickmax else info.max
                acc = None
                for d in dvs:
                    v = d.value.astype(np_dt)
                    if d.null is not None:
                        # a NULL argument is skipped, not contagious
                        v = jnp.where(d.null, ident, v)
                    acc = v if acc is None else (
                        jnp.maximum(acc, v) if pickmax
                        else jnp.minimum(acc, v))
                if any(d.null is None for d in dvs):
                    out_null = None   # NULL only when EVERY arg is NULL
                else:
                    out_null = dvs[0].null
                    for d in dvs[1:]:
                        out_null = out_null & d.null
                return DVal(acc, out_null, dt)

            return run_gl

        # string functions via derived dictionaries (incl. compositions:
        # upper(concat(s, '_x')), instr(lower(s), 'q'), ...)
        if name in STRING_VALUE_FUNCS or name in ("length", "instr",
                                                  "ascii", "to_date"):
            return self._emit_string_func(e)

        # SQL-registered functions (CREATE FUNCTION): the python body
        # runs on the TRACED values, so a jnp-compatible UDF fuses into
        # the same XLA program as the rest of the plan (ref:
        # SnappyDDLParser.scala:765 createFunction — codegen'd JVM UDFs
        # there). String args stay on the host path (device values are
        # dictionary codes the body must not see).
        from snappydata_tpu.sql import udf as _udf

        u = _udf.lookup(name)
        if u is not None:
            from snappydata_tpu.sql.analyzer import expr_type

            for a in e.args:
                try:
                    at = expr_type(a)
                except Exception:
                    at = None
                if at is not None and at.name == "string":
                    raise CompileError(
                        f"UDF {name} over string arguments runs on host")
            ret = u.returns or T.DOUBLE
            fn = u.fn

            def run_udf(rt: Runtime) -> DVal:
                dvs = [a(rt) for a in args]
                try:
                    v = jnp.asarray(fn(*[d.value for d in dvs]))
                except Exception as ex:
                    raise CompileError(
                        f"UDF {name} failed under tracing: {ex}")
                out_null = None
                for d in dvs:
                    if d.null is not None:
                        out_null = d.null if out_null is None \
                            else (out_null | d.null)
                return DVal(v, out_null, ret)

            return run_udf

        raise CompileError(f"unsupported function on device: {name}")

    def _unary_math(self, arg, fn, keep_type=False):
        def run(rt: Runtime) -> DVal:
            c = arg(rt)
            return DVal(fn(c.value), c.null,
                        c.dtype if keep_type else T.DOUBLE)

        return run

    def _emit_string_func(self, e: ast.Func) -> Callable[[Runtime], DVal]:
        """String expressions as DERIVED DICTIONARIES: codes stay on
        device untouched; the per-distinct-value transform runs once over
        the (small) dictionary on the host. length/instr additionally
        lower to int LUT gathers so they compose with device filters."""
        name = e.name

        if name in ("length", "instr", "ascii", "to_date"):
            col_idx, base = self._string_value_transform(e.args[0])
            if col_idx is None:
                raise CompileError(f"{name} of literal-only expression")
            out_dtype = T.DATE if name == "to_date" else T.INT
            if name == "instr":
                if len(e.args) < 2 or not isinstance(e.args[1], ast.Lit):
                    raise CompileError("instr with non-literal needle")
                needle = str(e.args[1].value)

                def val_of(v):
                    bv = base(v)
                    return bv.find(needle) + 1 if bv is not None else 0
            elif name == "ascii":
                def val_of(v):
                    bv = base(v)
                    return ord(bv[0]) if bv else 0
            elif name == "to_date":
                import datetime as _dt

                epoch = _dt.date(1970, 1, 1).toordinal()
                _BAD = np.iinfo(np.int32).min   # unparseable sentinel

                def val_of(v):
                    bv = base(v)
                    if bv is None:
                        return _BAD
                    try:
                        return _dt.date.fromisoformat(
                            str(bv)[:10]).toordinal() - epoch
                    except ValueError:
                        return _BAD   # → NULL via the sentinel mask
            else:
                def val_of(v):
                    bv = base(v)
                    return len(bv) if bv is not None else 0

            getter = self.dict_getters[col_idx]

            def build_ilut(params):
                d = getter()
                lut = np.array([val_of(v) for v in d], dtype=np.int32)
                n = max(1, len(lut))
                padded = 1 << (n - 1).bit_length()
                if padded > len(lut):
                    lut = np.concatenate([lut, np.zeros(padded - len(lut),
                                                        np.int32)])
                return lut

            aux_i = self._register_aux(build_ilut)
            wants_bad_mask = name == "to_date"

            def run_ilut(rt: Runtime) -> DVal:
                c = rt.cols[col_idx]
                out = rt.aux[aux_i][c.value]
                null = c.null
                if wants_bad_mask:
                    bad = out == np.iinfo(np.int32).min
                    out = jnp.where(bad, 0, out)
                    null = _or_null(null, bad)
                return DVal(out, null, out_dtype)

            return run_ilut

        col_idx, fn = self._string_value_transform(e)
        if col_idx is None:
            raise CompileError("literal-only string expression")
        getter = self.dict_getters[col_idx]

        def derived_dict():
            # CALLABLE dictionary: re-derived from the CURRENT table
            # dictionary at assemble time, so codes minted after this
            # plan was traced still decode correctly
            return np.array([fn(v) for v in getter()], dtype=object)

        def run_strfn(rt: Runtime) -> DVal:
            c = rt.cols[col_idx]
            return DVal(c.value, c.null, T.STRING, dictionary=derived_dict)

        return run_strfn


def _promote(a: Optional[T.DataType], b: Optional[T.DataType]) -> T.DataType:
    if a is None:
        return b or T.DOUBLE
    if b is None:
        return a
    try:
        return T.common_type(a, b)
    except TypeError:
        return a


def _float_dtype():
    from snappydata_tpu import config

    return jnp.float64 if config.use_float64() else jnp.float32


def _to_days(c: "DVal"):
    """date/timestamp DVal → days-since-epoch int32."""
    if c.dtype is not None and c.dtype.name == "timestamp":
        return (c.value // 86_400_000_000).astype(jnp.int32)
    return c.value.astype(jnp.int32)


def _days_from_civil(y, m, d):
    """(year, month, day) → days-since-epoch, vectorized (inverse of
    _civil_from_days; Hinnant's days_from_civil)."""
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


def _days_in_month(y, m):
    dim = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31],
                      dtype=jnp.int32)[m - 1]
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    return jnp.where((m == 2) & leap, 29, dim).astype(jnp.int32)


def _civil_from_days(days):
    """Days-since-epoch → (year, month, day), vectorized integer math
    (Howard Hinnant's civil_from_days, public-domain algorithm)."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)

"""Data type system.

Covers the SQL surface the reference supports for column/row tables
(ref: SnappyDDLParser column data types; encoders/.../encoding/
ColumnEncoding.scala typeId registry :766-774). Physical mapping is
TPU-first: every type lowers to a fixed-width device dtype; variable-width
types (STRING/DECIMAL) lower to dictionary codes / scaled integers so the
hot loops stay vectorized with static shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class DataType:
    name: str

    def __str__(self) -> str:  # pragma: no cover - trivial
        return self.name

    @property
    def np_dtype(self) -> np.dtype:
        if self.name in ("array", "map", "struct"):
            return np.dtype(object)
        return _NP[self.name]

    def device_dtype(self) -> np.dtype:
        """dtype of the decoded on-device representation."""
        from snappydata_tpu import config

        if self.name == "string":
            return np.dtype(np.int32)  # dictionary codes
        if self.name == "decimal":
            if getattr(self, "is_exact", False):
                return np.dtype(np.int64)  # scaled unscaled-value ints
            return np.dtype(np.float64 if config.use_float64() else np.float32)
        if self.name in ("double", "float") and not config.use_float64():
            return np.dtype(np.float32)
        return self.np_dtype


@dataclasses.dataclass(frozen=True)
class ArrayType(DataType):
    """ARRAY<T>: stored as python lists (host); queries referencing array
    columns evaluate on the host path (device arrays are a later round)."""

    element: "DataType" = None

    def __str__(self):
        return f"array<{self.element}>"


@dataclasses.dataclass(frozen=True)
class MapType(DataType):
    """MAP<K,V>: python dicts, host-evaluated like ARRAY."""

    key: "DataType" = None
    value: "DataType" = None

    def __str__(self):
        return f"map<{self.key},{self.value}>"


@dataclasses.dataclass(frozen=True)
class StructType(DataType):
    """STRUCT<name: type, ...>: python dicts keyed by field name (host
    values); field access via element_at(col, 'name') / named_struct
    literals (ref: SerializedRow complex values,
    encoders/.../catalyst/util/SerializedRow.scala)."""

    fields: tuple = ()   # Tuple[Tuple[str, DataType], ...]

    def __str__(self):
        inner = ", ".join(f"{n}: {t}" for n, t in self.fields)
        return f"struct<{inner}>"

    def field_type(self, name: str) -> Optional["DataType"]:
        for n, t in self.fields:
            if n.lower() == name.lower():
                return t
        return None


@dataclasses.dataclass(frozen=True)
class DecimalType(DataType):
    """DECIMAL(p, s). TPU-first physical mapping (ref: exact BigDecimal
    semantics, encoders/.../encoding/ColumnEncoding.scala:137-140
    readDecimal):

    - "exact" (the `decimal_exact` property, on by default), for every
      p up to 38: the DEVICE representation is the scaled int64
      unscaled value (v * 10^s). SUM/AVG/MIN/MAX/COUNT/GROUP BY and
      +,-,*,% / comparisons run as native integer ops at Spark 2.1's
      result types and stay EXACT; results decode to decimal.Decimal
      at the client edge. Up to p = 18 every value fits int64 by its
      type. Past it the value rides int64 under a guard: each product,
      rescale, alignment and sum whose true value could leave int64
      ORs a bound check into the statement's overflow flag, and a
      tripped flag reruns the whole statement on the host in Python
      integers (engine/hosteval.py), never clamping and never
      wrapping. The HOST mirror (plates, WAL, deltas, and cross-server
      partial aggregates re-entering the distributed merge) stays
      float64, which round-trips any <= 15-significant-digit decimal
      exactly: a stored value is exact through p = 15, a computed
      result through p = 38.
    - Division and math functions unscale to float64 (DOUBLE results).
    - `decimal_exact` off: every decimal is a float plate (f32 on the
      TPU with f64 accumulators, <= 1e-6 relative).
    """

    precision: int = 38
    scale: int = 2

    def __str__(self) -> str:  # pragma: no cover - trivial
        return f"decimal({self.precision},{self.scale})"

    @property
    def is_exact(self) -> bool:
        from snappydata_tpu import config

        return config.global_properties().decimal_exact

    @property
    def scale_factor(self) -> int:
        return 10 ** self.scale


BOOLEAN = DataType("boolean")
BYTE = DataType("byte")
SHORT = DataType("short")
INT = DataType("int")
LONG = DataType("long")
FLOAT = DataType("float")
DOUBLE = DataType("double")
STRING = DataType("string")
DATE = DataType("date")          # int32 days since epoch
TIMESTAMP = DataType("timestamp")  # int64 microseconds since epoch
DECIMAL = DecimalType("decimal")

_NP = {
    "boolean": np.dtype(np.bool_),
    "byte": np.dtype(np.int8),
    "short": np.dtype(np.int16),
    "int": np.dtype(np.int32),
    "long": np.dtype(np.int64),
    "float": np.dtype(np.float32),
    "double": np.dtype(np.float64),
    "string": np.dtype(object),
    "date": np.dtype(np.int32),
    "timestamp": np.dtype(np.int64),
    "decimal": np.dtype(np.float64),
}

_BY_NAME = {
    "boolean": BOOLEAN, "bool": BOOLEAN,
    "byte": BYTE, "tinyint": BYTE,
    "short": SHORT, "smallint": SHORT,
    "int": INT, "integer": INT,
    "long": LONG, "bigint": LONG,
    "float": FLOAT, "real": FLOAT,
    "double": DOUBLE,
    "string": STRING, "varchar": STRING, "char": STRING, "clob": STRING,
    "date": DATE,
    "timestamp": TIMESTAMP,
    "decimal": DECIMAL, "numeric": DECIMAL,
}


def parse_type(name: str, args: Optional[list] = None,
               element: Optional[DataType] = None,
               key: Optional[DataType] = None,
               fields: Optional[list] = None) -> DataType:
    if name.lower() == "array":
        return ArrayType("array", element or DOUBLE)
    if name.lower() == "map":
        return MapType("map", key or STRING, element or DOUBLE)
    if name.lower() == "struct":
        return StructType("struct", tuple(fields or ()))
    base = _BY_NAME.get(name.lower())
    if base is None:
        raise ValueError(f"unknown data type: {name}")
    if base.name == "decimal" and args:
        prec = int(args[0])
        scale = int(args[1]) if len(args) > 1 else 0
        return DecimalType("decimal", prec, scale)
    return base


def is_numeric(dt: DataType) -> bool:
    return dt.name in ("byte", "short", "int", "long", "float", "double",
                       "decimal", "date", "timestamp")


def is_integral(dt: DataType) -> bool:
    return dt.name in ("byte", "short", "int", "long", "date", "timestamp")


def is_floating(dt: DataType) -> bool:
    return dt.name in ("float", "double", "decimal")


def common_type(a: DataType, b: DataType) -> DataType:
    """Numeric type promotion for binary expressions."""
    if a.name == b.name:
        if a.name == "decimal" and a != b:
            return _decimal_align_type(a, b)
        return a
    if "decimal" in (a.name, b.name):
        dec, other = (a, b) if a.name == "decimal" else (b, a)
        if other.name in ("float", "double"):
            return DOUBLE
        if other.name in _INT_DIGITS:
            return _decimal_align_type(dec, _int_as_decimal(other))
        if other.name == "string":
            return STRING
        return DOUBLE
    order = ["boolean", "byte", "short", "int", "date", "long", "timestamp",
             "float", "decimal", "double"]
    if a.name in order and b.name in order:
        return _BY_NAME[max(a.name, b.name, key=order.index)]
    if STRING in (a, b):
        return STRING
    raise TypeError(f"incompatible types: {a} vs {b}")


# ---------------------------------------------------------------------------
# Exact-decimal type algebra (shared by the analyzer's expr_type and the
# runtime's scaled-int lowering so declared scale always matches the
# computed representation). Result precision/scale follow Spark 2.1's
# DecimalPrecision rules, bounded at 38 (`DecimalType.bounded`); an
# integer operand is DECIMAL(digits, 0) (INT 10, BIGINT 20). A result
# past DECIMAL_INT64_PRECISION still rides int64, under the overflow
# guard (DecimalType docstring).
# ---------------------------------------------------------------------------

DECIMAL_MAX_PRECISION = 38
# the widest precision whose every value fits int64 by its type alone
DECIMAL_INT64_PRECISION = 18

_INT_DIGITS = {"boolean": 1, "byte": 3, "short": 5, "int": 10, "long": 20}


# wide enough for any DECIMAL(38, s) digit string, so that scaling one
# never rounds
EXACT_CONTEXT = __import__("decimal").Context(prec=80)


class DecimalOverflow(ValueError):
    """A decimal value whose scaled form leaves int64: the statement
    takes the exact host path."""


def _int_as_decimal(t: DataType) -> "DecimalType":
    return DecimalType("decimal", _INT_DIGITS[t.name], 0)


def decimal_bounded(p: int, s: int) -> "DecimalType":
    """Spark's DecimalType.bounded: precision and scale capped at 38."""
    return DecimalType("decimal", min(p, DECIMAL_MAX_PRECISION),
                       min(s, DECIMAL_MAX_PRECISION))


def _decimal_align_type(a: "DecimalType", b: "DecimalType") -> DataType:
    s = max(a.scale, b.scale)
    return decimal_bounded(
        max(a.precision - a.scale, b.precision - b.scale) + s, s)


def decimal_binop_type(op: str, a: DataType, b: DataType
                       ) -> Optional[DataType]:
    """Result type of a +,-,*,%,/ over operands where at least one side
    is decimal (Spark 2.1). None = not a decimal-typed operation
    (caller falls back to common_type). DOUBLE = the operation leaves
    the exact domain: division, a float operand, or a product of
    float-plated decimals."""
    if "decimal" not in (a.name, b.name):
        return None
    if op == "/":
        return DOUBLE
    for t in (a, b):
        if t.name in ("float", "double") or (
                t.name not in _INT_DIGITS and t.name != "decimal"):
            return DOUBLE
    da = a if a.name == "decimal" else _int_as_decimal(a)
    db = b if b.name == "decimal" else _int_as_decimal(b)
    if op == "*":
        if not (isinstance(da, DecimalType) and da.is_exact
                and isinstance(db, DecimalType) and db.is_exact):
            return DOUBLE
        return decimal_bounded(da.precision + db.precision + 1,
                               da.scale + db.scale)
    s = max(da.scale, db.scale)
    if op in ("+", "-"):
        return decimal_bounded(
            max(da.precision - da.scale, db.precision - db.scale) + s + 1,
            s)
    if op == "%":
        return decimal_bounded(
            min(da.precision - da.scale, db.precision - db.scale) + s, s)
    return None


def decimal_sum_type(dt: DataType) -> DataType:
    """SUM over a decimal: DECIMAL(p + 10, s), bounded (Spark 2.1). The
    in-trace overflow check reroutes to the host path if a group total
    could leave int64."""
    if not isinstance(dt, DecimalType) or not dt.is_exact:
        return DOUBLE
    return decimal_bounded(dt.precision + 10, dt.scale)


def decimal_avg_type(dt: DataType) -> DataType:
    """AVG over a decimal: DECIMAL(p + 4, s + 4), bounded (Spark 2.1),
    the exact sum over the count rounded HALF_UP at that scale."""
    if not isinstance(dt, DecimalType) or not dt.is_exact:
        return DOUBLE
    return decimal_bounded(dt.precision + 4, dt.scale + 4)


def decimal_to_unscaled(dt: DataType, arr) -> np.ndarray:
    """Host-domain (float) decimal values -> scaled int64 unscaled
    values, rounding half away from zero at the column scale (HALF_UP,
    matching _dec_rescale_int and java BigDecimal — np.round would tie
    to even and disagree with the device rescale path)."""
    a = np.asarray(arr, dtype=np.float64) * float(dt.scale_factor)
    r = np.floor(np.abs(a) + 0.5)
    if r.size and np.max(r) >= 2.0 ** 63:
        raise DecimalOverflow(
            f"a {dt} value leaves int64 once scaled: host path")
    return (np.sign(a) * r).astype(np.int64)


def unscaled_to_python(dt: DataType, v: int):
    """Scaled integer -> decimal.Decimal at the column scale; None (SQL
    NULL, Spark's CheckOverflow) where it has more digits than the
    type's precision."""
    import decimal as _d

    v = int(v)
    if abs(v) >= 10 ** dt.precision:
        return None
    return _d.Decimal(v).scaleb(-dt.scale, context=EXACT_CONTEXT)


def decimal_float_converter(dt: DataType):
    """Column-level converter: float-domain decimal value ->
    decimal.Decimal quantized at the column scale, with the quantizer
    hoisted once (per-cell construction was measurable on streamed
    exports). Exact whenever the f64 faithfully represents the decimal,
    i.e. <= 15 significant digits."""
    import decimal as _d

    q = _d.Decimal(1).scaleb(-dt.scale)

    def conv(v):
        return _d.Decimal(repr(float(v))).quantize(
            q, rounding=_d.ROUND_HALF_UP)

    return conv


def float_to_python_decimal(dt: DataType, v: float):
    """One-off variant of decimal_float_converter."""
    return decimal_float_converter(dt)(v)


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    dtype: DataType
    nullable: bool = True


@dataclasses.dataclass(frozen=True)
class Schema:
    fields: tuple

    def __init__(self, fields):
        object.__setattr__(self, "fields", tuple(fields))

    def names(self):
        return [f.name for f in self.fields]

    def field(self, name: str) -> Field:
        name_l = name.lower()
        for f in self.fields:
            if f.name.lower() == name_l:
                return f
        raise KeyError(f"no such column: {name}")

    def index(self, name: str) -> int:
        name_l = name.lower()
        for i, f in enumerate(self.fields):
            if f.name.lower() == name_l:
                return i
        raise KeyError(f"no such column: {name}")

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)


def python_value(dt: DataType, v: Any) -> Any:
    """Coerce a parsed literal to the column's python/numpy domain."""
    if v is None:
        return None
    if dt.name in ("byte", "short", "int", "long", "date", "timestamp"):
        return int(v)
    if dt.name in ("float", "double", "decimal"):
        return float(v)
    if dt.name == "boolean":
        return bool(v)
    return str(v)

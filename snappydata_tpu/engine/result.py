"""Query results: named host columns with null masks."""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from snappydata_tpu import types as T


@dataclasses.dataclass
class Result:
    names: List[str]
    columns: List[np.ndarray]          # host arrays (strings materialized)
    nulls: List[Optional[np.ndarray]]  # bool masks or None
    dtypes: List[T.DataType]

    @property
    def num_rows(self) -> int:
        return int(self.columns[0].shape[0]) if self.columns else 0

    def rows(self) -> List[tuple]:
        out = []
        for i in range(self.num_rows):
            row = []
            for c, nmask in zip(self.columns, self.nulls):
                if nmask is not None and nmask[i]:
                    row.append(None)
                else:
                    v = c[i]
                    row.append(v.item() if hasattr(v, "item") else v)
            out.append(tuple(row))
        return out

    def column(self, name: str) -> np.ndarray:
        return self.columns[[n.lower() for n in self.names].index(name.lower())]

    def to_pandas(self):
        import pandas as pd

        data = {}
        for name, c, nmask in zip(self.names, self.columns, self.nulls):
            if nmask is not None and nmask.any():
                obj = c.astype(object)
                obj[nmask] = None
                data[name] = obj
            else:
                data[name] = c
        return pd.DataFrame(data)

    def __repr__(self):
        head = self.rows()[:20]
        return (f"Result({self.num_rows} rows: {', '.join(self.names)})\n"
                + "\n".join(str(r) for r in head))


def unscale_decimal_col(c: np.ndarray, dt) -> np.ndarray:
    """One column out of the exact-decimal domain (the device's scaled
    int64, or the exact host rerun's Decimal objects) into plain
    float64 (no-op for anything else) — the SINGLE implementation every
    host consumer shares."""
    if dt is None or dt.name != "decimal" \
            or not getattr(dt, "is_exact", False):
        return c
    arr = np.asarray(c)
    if np.issubdtype(arr.dtype, np.integer):
        return np.asarray(c, dtype=np.float64) / (10 ** dt.scale)
    if arr.dtype == object:
        return np.array([np.nan if v is None else float(v) for v in arr],
                        dtype=np.float64)
    return c


def to_host_domain(res: Result) -> Result:
    """Result with exact-decimal scaled-int64 columns unscaled to the
    plain float64 HOST domain — what ingest consumers (CTAS /
    INSERT..SELECT coercion into host plates) and host numeric code
    expect. Without this, a scaled column would be stored verbatim and
    read back 10^scale too large (review finding)."""
    cols = [unscale_decimal_col(c, dt)
            for c, dt in zip(res.columns, res.dtypes)]
    if all(a is b for a, b in zip(cols, res.columns)):
        return res
    return Result(res.names, cols, res.nulls, res.dtypes)


def finalize_decimals(res: Result) -> Result:
    """User-boundary decode of DECIMAL columns to decimal.Decimal
    objects (the JDBC-BigDecimal analogue; ref readDecimal,
    encoders/.../encoding/ColumnEncoding.scala:137-140). Inside the
    engine an exact decimal of any precision up to 38 rides as scaled
    int64 at Spark 2.1's result type (SUM DECIMAL(p+10, s), AVG
    DECIMAL(p+4, s+4), a product of two money columns DECIMAL(32, 4));
    the exact host rerun of a statement whose int64 guard tripped hands
    back Decimal objects at that type, and plain floats come from a
    float-plated decimal (`decimal_exact` off) or a host-evaluated
    column:

    - integer column + exact DecimalType -> Decimal(v) * 10^-s, EXACT;
    - object column -> kept (Decimal objects already, or host objects);
    - float column + DecimalType -> Decimal quantized at the column
      scale (exact whenever the f64 faithfully held the value).

    Applied once, by the session/front-door layers — never
    mid-pipeline, where numeric host ops still need numpy domains."""
    changed = False
    cols = list(res.columns)
    for i, (c, dt) in enumerate(zip(res.columns, res.dtypes)):
        if dt is None or dt.name != "decimal":
            continue
        arr = np.asarray(c)
        if arr.dtype == object:
            continue  # already decoded (or host objects)
        if np.issubdtype(arr.dtype, np.integer) \
                and getattr(dt, "is_exact", False):
            out = np.array([T.unscaled_to_python(dt, v) for v in arr],
                           dtype=object)
        elif np.issubdtype(arr.dtype, np.floating):
            out = np.array([T.float_to_python_decimal(dt, v)
                            for v in arr], dtype=object)
        else:
            continue
        cols[i] = out
        changed = True
    if not changed:
        return res
    return Result(res.names, cols, res.nulls, res.dtypes)


def empty_result(names, dtypes) -> Result:
    cols = [np.empty(0, dtype=dt.np_dtype if dt.name != "string" else object)
            for dt in dtypes]
    return Result(list(names), cols, [None] * len(names), list(dtypes))

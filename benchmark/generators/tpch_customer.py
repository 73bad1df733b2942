"""TPC-H CUSTOMER from `--seed`, to the specification's eight columns and
their domains (cl 1.4.1, 4.2.3), in bulk NumPy like its neighbour `tpch`:

- SF x 150,000 rows with C_CUSTKEY 1..N, the range the `tpch` generator
  draws O_CUSTKEY from, so every order's customer exists;
- C_NAME `Customer#` and nine digits of the key; C_NATIONKEY 0..24;
  C_PHONE by cl 4.2.2.9 (country code C_NATIONKEY + 10, then three,
  three and four digits); C_ACCTBAL -999.99..9,999.99 in cents;
  C_MKTSEGMENT uniform over the five segments;
- C_ADDRESS (10-40 characters) and C_COMMENT (29-116) cut from the
  seed's pool of pseudo-text, the one `tpch` cuts its comments from.
  dbgen's address is a random v-string and not words: the column is
  never read by a query of the benchmark, only loaded at its width.
"""

from __future__ import annotations

import numpy as np

import tpch as _tpch

CUSTOMERS_PER_SF = 150_000
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
                     "HOUSEHOLD"], dtype=object)


def n_customers(sf: float) -> int:
    # the bound `tpch._orders` draws O_CUSTKEY under
    return max(3, int(CUSTOMERS_PER_SF * sf))


def _digits(values, width: int):
    return np.char.zfill(np.asarray(values).astype(f"U{width}"), width)


def generate(table: str, sf: float, seed: int) -> dict:
    """The loaded table at scale `sf`: column name -> array, in the DDL's
    order."""
    if table != "customer":
        raise KeyError(f"generator tpch_customer makes customer, not "
                       f"{table!r}")
    n = n_customers(sf)
    rng = np.random.default_rng([seed, 107])
    pool = _tpch._pool(seed)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n, dtype=np.int32)
    phone = _digits(nation + 10, 2)
    for lo, hi, width in ((100, 1000, 3), (100, 1000, 3), (1000, 10000, 4)):
        phone = np.char.add(np.char.add(phone, "-"),
                            _digits(rng.integers(lo, hi, n), width))
    return {
        "c_custkey": key,
        "c_name": np.char.add("Customer#", _digits(key, 9)).astype(object),
        "c_address": _tpch._text(rng, pool, n, 10, 40),
        "c_nationkey": nation,
        "c_phone": phone.astype(object),
        "c_acctbal": rng.integers(-99_999, 999_999 + 1, n) / 100.0,
        "c_mktsegment": SEGMENTS[rng.integers(0, 5, n)],
        "c_comment": _tpch._text(rng, pool, n, 29, 116),
    }


def release() -> None:
    """Forget the pool this module's copy of `tpch` kept."""
    _tpch._pools.clear()

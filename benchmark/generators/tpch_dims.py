"""TPC-H SUPPLIER, NATION and REGION from `--seed`, to the specification's
columns and domains (cl 1.4.1, 4.2.3), in bulk NumPy like its neighbours
`tpch` and `tpch_customer`:

- SUPPLIER: SF x 10,000 rows with S_SUPPKEY 1..N, the range the `tpch`
  generator draws L_SUPPKEY from, so every line's supplier exists;
  S_NAME `Supplier#` and nine digits of the key; S_NATIONKEY uniform over
  0..24; S_PHONE by cl 4.2.2.9 (country code S_NATIONKEY + 10, then
  three, three and four digits); S_ACCTBAL -999.99..9,999.99 in cents;
  S_ADDRESS (10-40 characters) and S_COMMENT (25-100) cut from the
  seed's pool of pseudo-text, the one `tpch` cuts its comments from
  (dbgen's address is a random v-string, and it plants "Customer
  Complaints" in a few comments: no query of the benchmark reads either);
- NATION and REGION: the spec's fixed 25 and 5 rows (cl 4.2.3) with their
  N_REGIONKEY; only N_COMMENT and R_COMMENT (31-114 and 31-115
  characters) come from the seed.
"""

from __future__ import annotations

import numpy as np

import tpch as _tpch
from tpch_customer import _digits

SUPPLIERS_PER_SF = 10_000
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# cl 4.2.3: (N_NAME, N_REGIONKEY) by N_NATIONKEY
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]


def n_suppliers(sf: float) -> int:
    # the bound `tpch._lineitem` draws L_SUPPKEY under
    return max(2, int(SUPPLIERS_PER_SF * sf))


def _supplier(sf: float, seed: int) -> dict:
    n = n_suppliers(sf)
    rng = np.random.default_rng([seed, 109])
    pool = _tpch._pool(seed)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n, dtype=np.int32)
    phone = _digits(nation + 10, 2)
    for lo, hi, width in ((100, 1000, 3), (100, 1000, 3), (1000, 10000, 4)):
        phone = np.char.add(np.char.add(phone, "-"),
                            _digits(rng.integers(lo, hi, n), width))
    return {
        "s_suppkey": key,
        "s_name": np.char.add("Supplier#", _digits(key, 9)).astype(object),
        "s_address": _tpch._text(rng, pool, n, 10, 40),
        "s_nationkey": nation,
        "s_phone": phone.astype(object),
        "s_acctbal": rng.integers(-99_999, 999_999 + 1, n) / 100.0,
        "s_comment": _tpch._text(rng, pool, n, 25, 100),
    }


def _nation(seed: int) -> dict:
    rng = np.random.default_rng([seed, 113])
    return {
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int32),
        "n_name": np.array([n for n, _ in NATIONS], dtype=object),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int32),
        "n_comment": _tpch._text(rng, _tpch._pool(seed), len(NATIONS),
                                 31, 114),
    }


def _region(seed: int) -> dict:
    rng = np.random.default_rng([seed, 127])
    return {
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int32),
        "r_name": np.array(REGIONS, dtype=object),
        "r_comment": _tpch._text(rng, _tpch._pool(seed), len(REGIONS),
                                 31, 115),
    }


def generate(table: str, sf: float, seed: int) -> dict:
    """The loaded table at scale `sf`: column name -> array, in the DDL's
    order."""
    if table == "supplier":
        return _supplier(sf, seed)
    if table == "nation":
        return _nation(seed)
    if table == "region":
        return _region(seed)
    raise KeyError(f"generator tpch_dims makes supplier, nation and "
                   f"region, not {table!r}")


def release() -> None:
    """Forget the pool this module's copy of `tpch` kept."""
    _tpch._pools.clear()

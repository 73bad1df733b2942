"""TPC-H ORDERS and LINEITEM from `--seed`, to the specification's column
list, domains and dependencies (cl 4.2.3), in bulk NumPy and not dbgen's
row-at-a-time streams:

- an order has one to seven lines; the counts are a fixed multiset in an
  order drawn from the seed, so every seed gives the same number of
  rows, four an order (23,999,997 at SF 4; dbgen: 6,001,215 at SF 1),
  and the same work;
- order keys are sparse as dbgen's are (the first 8 of every 32 numbers);
- L_EXTENDEDPRICE is L_QUANTITY x the part's retail price (cl 4.2.3's
  formula on L_PARTKEY); ship, commit and receipt dates hang on the
  order's date; return flag and line status on CURRENTDATE 1995-06-17;
  O_ORDERSTATUS and O_TOTALPRICE are worked out from the order's lines;
- the text columns have the spec's widths: L_SHIPINSTRUCT, L_SHIPMODE,
  O_ORDERPRIORITY from their lists, O_CLERK `Clerk#` and nine digits over
  SF x 1,000 clerks, L_COMMENT (10-43 characters) and O_COMMENT (19-78)
  cut, as dbgen cuts them, from one pool of pseudo-text at a drawn place
  and length. The pool is 8 MiB and not dbgen's 300 MB.

What is left of dbgen's own streams (the exact text grammar, L_SUPPKEY as
one of the part's four suppliers, customers without orders) is under the
configuration's `assumed`. A refresh batch (RF1, cl 2.5-2.6) is `k`-th of
a run: new orders on the next sparse keys above the loaded ones, with
their lines.
"""

from __future__ import annotations

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


STARTDATE, ENDDATE = _days("1992-01-01"), _days("1998-12-31")
CURRENTDATE = _days("1995-06-17")
ORDERS_PER_SF = 1_500_000
_INSTRUCT = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                      "TAKE BACK RETURN"], dtype=object)
_MODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                   "FOB"], dtype=object)
_PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM",
                        "4-NOT SPECIFIED", "5-LOW"], dtype=object)
_WORDS = ("furiously sly carefully blithe quick fluffy slow quiet ruthless "
          "thin close dogged daring brave stealthy permanent enticing idle "
          "busy regular final ironic even bold silent packages requests "
          "accounts deposits foxes ideas theodolites pinto beans "
          "instructions dependencies excuses platelets asymptotes courts "
          "dolphins multipliers sauternes warthogs frets dinos attainments "
          "somas Tiresias' nag sleep wake are cajole haggle hinder boost "
          "use affix detect integrate maintain nod was lose sublate solve "
          "thrash promise engage impress run dazzle believe unwind wake "
          "about above according to across after against along alongside "
          "of among around at atop before behind beneath beside besides "
          "between beyond by despite during except for from in place of "
          "inside instead of into near of on outside over past since "
          "through throughout to toward under until up upon without with "
          "within").split()
_POOL_BYTES = 1 << 23


def sparse_key(i):
    """dbgen's `mk_sparse`: the i-th order (from 0) has the key that
    keeps the low three bits and leaves the next two free."""
    i = np.asarray(i, dtype=np.int64)
    return ((i >> 3) << 5) + (i & 7) + 1


def n_orders(sf: float) -> int:
    return max(250, int(round(ORDERS_PER_SF * sf)))


_pools = {}


def _pool(seed: int) -> np.ndarray:
    """The seed's pseudo-text, made once a process."""
    if seed not in _pools:
        _pools.clear()
        _pools[seed] = _make_pool(np.random.default_rng([seed, 103]))
    return _pools[seed]


def _make_pool(rng) -> np.ndarray:
    words = rng.integers(0, len(_WORDS), _POOL_BYTES // 4)
    text = " ".join(_WORDS[i] for i in words)
    punct = rng.integers(0, 40, len(text))
    raw = np.frombuffer(text.encode("ascii"), dtype=np.uint8).copy()
    sp = raw == 32
    raw[sp & (punct == 0)] = ord(",")
    raw[sp & (punct == 1)] = ord(".")
    return raw[:_POOL_BYTES]


def _text(rng, pool, n: int, lo: int, hi: int) -> np.ndarray:
    """`n` strings of `lo`..`hi` characters, each a cut of the pool."""
    width = hi
    windows = np.lib.stride_tricks.sliding_window_view(pool, width)
    at = rng.integers(0, len(windows), n)
    length = rng.integers(lo, hi + 1, n)
    out = np.empty(n, dtype=object)
    step = 1 << 20
    for s in range(0, n, step):         # blocks, so the bytes stay small
        block = windows[at[s:s + step]].copy()
        block[np.arange(width)[None, :] >= length[s:s + step, None]] = 0
        out[s:s + step] = block.view(f"S{width}").ravel() \
            .astype(f"U{width}").astype(object)
    return out


def _orders_core(n: int, seed: int, first: int = 0) -> dict:
    """What both tables hang on: keys, dates and line counts of `n`
    orders, the `first`-th onward."""
    rng = np.random.default_rng([seed, 101, first])
    return {
        "key": sparse_key(np.arange(first, first + n)),
        "date": rng.integers(STARTDATE, ENDDATE - 151 + 1, n,
                             dtype=np.int32),
        "lines": rng.permutation(np.arange(n) % 7 + 1).astype(np.int64),
        "rng": rng,
        "pool": _pool(seed),
    }


def _lineitem(core: dict, sf: float) -> dict:
    rng, lines = core["rng"], core["lines"]
    n = int(lines.sum())
    order = np.repeat(np.arange(len(lines)), lines)
    starts = np.cumsum(lines) - lines
    odate = core["date"][order]
    part = rng.integers(1, max(2, int(200_000 * sf)) + 1, n, dtype=np.int64)
    qty = rng.integers(1, 51, n)
    # cl 4.2.3: P_RETAILPRICE, in cents
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1_000)
    ship = odate + rng.integers(1, 122, n, dtype=np.int32)
    receipt = ship + rng.integers(1, 31, n, dtype=np.int32)
    flag = np.where(rng.integers(0, 2, n) == 0, "R", "A").astype(object)
    flag[receipt > CURRENTDATE] = "N"
    return {
        "l_orderkey": core["key"][order],
        "l_partkey": part,
        "l_suppkey": rng.integers(1, max(2, int(10_000 * sf)) + 1, n,
                                  dtype=np.int64),
        "l_linenumber": (np.arange(n) - starts[order] + 1).astype(np.int32),
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": (qty * retail) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": flag,
        "l_linestatus": np.where(ship > CURRENTDATE, "O", "F")
        .astype(object),
        "l_shipdate": ship,
        "l_commitdate": odate + rng.integers(30, 91, n, dtype=np.int32),
        "l_receiptdate": receipt,
        "l_shipinstruct": _INSTRUCT[rng.integers(0, 4, n)],
        "l_shipmode": _MODES[rng.integers(0, 7, n)],
        "l_comment": _text(rng, core["pool"], n, 10, 43),
    }


def _orders(core: dict, li: dict, sf: float) -> dict:
    rng, lines = core["rng"], core["lines"]
    n = len(lines)
    ends = np.cumsum(lines)
    starts = ends - lines
    charge = np.rint(li["l_extendedprice"] * 100 * (1 + li["l_tax"])
                     * (1 - li["l_discount"]))
    total = np.add.reduceat(charge, starts) / 100.0
    open_ = np.add.reduceat((li["l_linestatus"] == "O").astype(np.int64),
                            starts)
    status = np.full(n, "P", dtype=object)
    status[open_ == 0] = "F"
    status[open_ == lines] = "O"
    clerks = max(1, int(round(1_000 * sf)))
    cust = rng.integers(1, max(3, int(150_000 * sf)) + 1, n, dtype=np.int64)
    cust[cust % 3 == 0] -= 1        # a third of the customers has no order
    cust[cust == 0] = 1
    return {
        "o_orderkey": core["key"],
        "o_custkey": cust,
        "o_orderstatus": status,
        "o_totalprice": total,
        "o_orderdate": core["date"],
        "o_orderpriority": _PRIORITIES[rng.integers(0, 5, n)],
        "o_clerk": np.char.add("Clerk#", np.char.zfill(
            rng.integers(1, clerks + 1, n).astype("U9"), 9)).astype(object),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _text(rng, core["pool"], n, 19, 78),
    }


_made = {}      # (sf, seed, first, n) -> {"lineitem":, "orders":}


def _tables(sf: float, seed: int, first: int, n: int, want) -> dict:
    """Both tables come from one stream; the one not asked for yet is
    kept until it is, so a cell that loads both makes them once."""
    key = (sf, seed, first, n)
    if key not in _made:
        _made.clear()
        core = _orders_core(n, seed, first)
        li = _lineitem(core, sf)
        _made[key] = {"lineitem": li, "orders": None, "core": core}
    got = _made[key]
    if want == "orders" and got["orders"] is None:
        got["orders"] = _orders(got["core"], got["lineitem"], sf)
    return got


def generate(table: str, sf: float, seed: int) -> dict:
    """The loaded table at scale `sf`: column name -> array, in the DDL's
    order."""
    if table not in ("lineitem", "orders"):
        raise KeyError(f"generator tpch makes lineitem and orders, not "
                       f"{table!r}")
    return _tables(sf, seed, 0, n_orders(sf), table)[table]


def release() -> None:
    """Forget what was kept for a table not asked for."""
    _made.clear()


def refresh(sf: float, seed: int, k: int, orders: int) -> dict:
    """The k-th RF1 of a run: `orders` new orders above every loaded and
    every earlier inserted key, and their lines."""
    first = n_orders(sf) + k * orders
    core = _orders_core(orders, seed, first)
    li = _lineitem(core, sf)
    return {"orders": _orders(core, li, sf), "lineitem": li}


def key_range(sf: float, k: int, orders: int) -> tuple:
    """The k-th RF2 of a run: the keys [lo, hi) of the `orders` lowest
    loaded orders still live."""
    return (int(sparse_key(k * orders)), int(sparse_key((k + 1) * orders)))

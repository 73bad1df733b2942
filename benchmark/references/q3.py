"""TPC-H Q3, the Shipping Priority Query (cl 2.4.3): the ten unshipped
orders of highest revenue, of customers of market segment SEGMENT, placed
before DATE with lines shipped after it.

No sort and no merge: a customer is looked up by its key in a dense
table of flags, an order by its key in a dense table of row numbers, and
revenue is summed by order row with `np.bincount` (float64) or, for the
control's narrower accumulators, `np.add.at`. O_ORDERKEY is ORDERS'
primary key, so (l_orderkey, o_orderdate, o_shippriority) is one group
an order. Each product is rounded to the plates' width once, as the
program rounds it.
"""

import numpy as np

COLUMNS = {
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "customer": ["c_custkey", "c_mktsegment"],
}

LIMIT = 10


class Reference:
    def __init__(self, world):
        self.world = world
        self.built = None

    def on_insert(self, table, ch) -> None:
        self.built = None

    def on_delete(self, table, ch, mask) -> None:
        self.built = None

    def _build(self) -> None:
        """What no parameter changes: the live columns, each line's
        order row, and each line's discounted price."""
        live = self.world.live
        okey = live("orders", "o_orderkey").astype(np.int64)
        if len(okey) and np.bincount(okey).max() > 1:
            raise ValueError("Q3's reference takes O_ORDERKEY as a key")
        row_of = np.full(int(okey.max(initial=0)) + 2, -1, dtype=np.int64)
        row_of[okey] = np.arange(len(okey))
        lkey = live("lineitem", "l_orderkey").astype(np.int64)
        one = live("lineitem", "l_discount").dtype.type(1.0)
        ocust = live("orders", "o_custkey").astype(np.int64)
        ckey = live("customer", "c_custkey").astype(np.int64)
        self.built = {
            "okey": okey,
            "ocust": ocust,
            "custkeys": int(max(ckey.max(initial=0),
                                ocust.max(initial=0))) + 1,
            "odate": live("orders", "o_orderdate"),
            "oprio": live("orders", "o_shippriority"),
            "ckey": ckey,
            "cseg": live("customer", "c_mktsegment"),
            "line_order": row_of[np.minimum(lkey, len(row_of) - 1)],
            "ship": live("lineitem", "l_shipdate"),
            "price": live("lineitem", "l_extendedprice")
            * (one - live("lineitem", "l_discount")),
        }

    def answer(self, p: dict) -> list:
        if self.built is None:
            self._build()
        b = self.built
        date = int(p["days"])
        in_segment = np.zeros(b["custkeys"], dtype=bool)
        in_segment[b["ckey"][b["cseg"] == p["segment"]]] = True
        order_in = in_segment[b["ocust"]] & (b["odate"] < date)
        order = b["line_order"]
        line_in = (order >= 0) & (b["ship"] > date)
        line_in[line_in] = order_in[order[line_in]]
        rows, price = order[line_in], b["price"][line_in]
        n = len(b["okey"])
        lines = np.bincount(rows, minlength=n)
        if self.world.acc == np.float64:
            revenue = np.bincount(rows, weights=price.astype(np.float64),
                                  minlength=n)
        else:
            revenue = np.zeros(n, dtype=self.world.acc)
            np.add.at(revenue, rows, price.astype(self.world.acc))
        groups = np.flatnonzero(lines)
        rev = revenue[groups].astype(np.float64)
        first = groups[np.lexsort((b["odate"][groups], -rev))[:LIMIT]]
        # a DATE comes back from `SnappySession.sql(...).rows()` as its
        # days since 1970-01-01
        return [(int(b["okey"][g]), float(revenue[g]),
                 int(b["odate"][g]), int(b["oprio"][g])) for g in first]

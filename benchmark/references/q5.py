"""TPC-H Q5, the Local Supplier Volume Query (cl 2.4.5): revenue by nation
of the region REGION, over the lines of orders placed in the year from
DATE whose customer and supplier are both of that nation.

No sort and no merge: every key is looked up in a dense table by its
value (a customer's and a supplier's nation, an order's row, a nation's
region), the five equalities and the filters are applied as the text
writes them, and revenue is summed by nation key with `np.bincount`
(float64) or, for the control's narrower accumulators, `np.add.at`. The
group key N_NAME comes from NATION, whose key is N_NATIONKEY, so a nation
is one group. Each product is rounded to the plates' width once, as the
program rounds it.
"""

import numpy as np

COLUMNS = {
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                 "l_discount"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "customer": ["c_custkey", "c_nationkey"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}


def _dense(keys, values, fill):
    """A table indexed by key: values[i] at keys[i], `fill` elsewhere.
    The keys are the table's primary key."""
    keys = keys.astype(np.int64)
    if len(keys) and np.bincount(keys).max() > 1:
        raise ValueError("Q5's reference takes each key column as a key")
    out = np.full(int(keys.max(initial=0)) + 2, fill,
                  dtype=np.asarray(values).dtype)
    out[keys] = values
    return out


def _at(table, keys):
    """table[keys], with keys past the table's end reading its last
    (filler) slot."""
    return table[np.minimum(keys.astype(np.int64), len(table) - 1)]


class Reference:
    def __init__(self, world):
        self.world = world
        self.built = None

    def on_insert(self, table, ch) -> None:
        self.built = None

    def on_delete(self, table, ch, mask) -> None:
        self.built = None

    def _build(self) -> None:
        """What no parameter changes: each line's order row, customer
        nation and supplier nation, and its discounted price."""
        live = self.world.live
        okey = live("orders", "o_orderkey")
        order_row = _dense(okey, np.arange(len(okey)), -1)
        cust_nation = _dense(live("customer", "c_custkey"),
                             live("customer", "c_nationkey")
                             .astype(np.int64), -1)
        supp_nation = _dense(live("supplier", "s_suppkey"),
                             live("supplier", "s_nationkey")
                             .astype(np.int64), -2)
        line_order = _at(order_row, live("lineitem", "l_orderkey"))
        has_order = line_order >= 0
        line_cust = np.full(len(line_order), -1, dtype=np.int64)
        line_cust[has_order] = _at(
            cust_nation,
            live("orders", "o_custkey")[line_order[has_order]])
        one = live("lineitem", "l_discount").dtype.type(1.0)
        nkey = live("nation", "n_nationkey").astype(np.int64)
        self.built = {
            "odate": live("orders", "o_orderdate"),
            "line_order": line_order,
            "line_cust_nation": line_cust,
            "line_supp_nation": _at(supp_nation,
                                    live("lineitem", "l_suppkey")),
            "price": live("lineitem", "l_extendedprice")
            * (one - live("lineitem", "l_discount")),
            "nkey": nkey,
            "nname": live("nation", "n_name"),
            "nregion": live("nation", "n_regionkey").astype(np.int64),
            "rkey": live("region", "r_regionkey").astype(np.int64),
            "rname": live("region", "r_name"),
        }

    def answer(self, p: dict) -> list:
        if self.built is None:
            self._build()
        b = self.built
        nation = b["line_supp_nation"]
        # nation key -> in the region REGION
        regions = b["rkey"][b["rname"] == p["region"]]
        in_region = np.zeros(int(max(b["nkey"].max(initial=0),
                                     nation.max(initial=0))) + 1, dtype=bool)
        in_region[b["nkey"][np.isin(b["nregion"], regions)]] = True
        order = b["line_order"]
        odate = np.full(len(order), -1, dtype=np.int64)
        odate[order >= 0] = b["odate"][order[order >= 0]]
        line_in = (order >= 0) & (odate >= int(p["days"])) \
            & (odate < int(p["end_days"])) \
            & (b["line_cust_nation"] == nation) & (nation >= 0)
        line_in[line_in] = in_region[nation[line_in]]
        rows, price = nation[line_in], b["price"][line_in]
        n = len(in_region)
        lines = np.bincount(rows, minlength=n)
        if self.world.acc == np.float64:
            revenue = np.bincount(rows, weights=price.astype(np.float64),
                                  minlength=n)
        else:
            revenue = np.zeros(n, dtype=self.world.acc)
            np.add.at(revenue, rows, price.astype(self.world.acc))
        name_of = dict(zip(b["nkey"].tolist(), b["nname"].tolist()))
        groups = np.flatnonzero(lines)
        rev = revenue[groups].astype(np.float64)
        first = groups[np.argsort(-rev, kind="stable")]
        return [(name_of[int(g)], float(revenue[g])) for g in first]

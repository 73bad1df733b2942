"""TPC-H-shaped data generator (statistical, not spec-dbgen) + query text.

Used by the correctness tests and bench.py, mirroring the reference's
in-tree TPC-H harness (cluster/src/test/scala/io/snappydata/benchmark/
TPCH_Queries.scala, TPCHColumnPartitionedTable.scala): lineitem/orders/
customer with the columns, domains and correlations the headline queries
(Q1/Q3/Q6) touch.
"""

from __future__ import annotations

import datetime
from typing import Dict

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


LINEITEM_ROWS_PER_SF = 6_000_000
ORDERS_ROWS_PER_SF = 1_500_000
CUSTOMER_ROWS_PER_SF = 150_000

RETURNFLAGS = np.array(["A", "N", "R"], dtype=object)
LINESTATUS = np.array(["F", "O"], dtype=object)
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                     "MACHINERY"], dtype=object)
SHIPMODES = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP",
                      "TRUCK"], dtype=object)


def gen_lineitem(num_rows: int, seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    orderkey = rng.integers(1, max(2, num_rows // 4), num_rows,
                            dtype=np.int64)
    ship = rng.integers(_days("1992-01-02"), _days("1998-12-01"), num_rows,
                        dtype=np.int32)
    qty = rng.integers(1, 51, num_rows).astype(np.float64)
    price = np.round(rng.uniform(900.0, 105_000.0, num_rows), 2)
    disc = np.round(rng.integers(0, 11, num_rows) * 0.01, 2)
    tax = np.round(rng.integers(0, 9, num_rows) * 0.01, 2)
    # linestatus correlates with shipdate in real dbgen (O after 1995-06)
    status = np.where(ship > _days("1995-06-17"), "O", "F").astype(object)
    flag = RETURNFLAGS[rng.integers(0, 3, num_rows)]
    flag[status == "O"] = "N"
    return {
        "l_orderkey": orderkey,
        "l_partkey": rng.integers(1, 200_000, num_rows, dtype=np.int64),
        "l_suppkey": rng.integers(1, 10_000, num_rows, dtype=np.int64),
        "l_linenumber": rng.integers(1, 8, num_rows).astype(np.int32),
        "l_quantity": qty,
        "l_extendedprice": price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": flag,
        "l_linestatus": status,
        "l_shipdate": ship,
        "l_commitdate": ship + rng.integers(-30, 30, num_rows,
                                            dtype=np.int32),
        "l_receiptdate": ship + rng.integers(1, 30, num_rows,
                                             dtype=np.int32),
        "l_shipmode": SHIPMODES[rng.integers(0, len(SHIPMODES), num_rows)],
    }


def gen_orders(num_rows: int, num_customers: int, seed: int = 1
               ) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    return {
        "o_orderkey": np.arange(1, num_rows + 1, dtype=np.int64),
        "o_custkey": rng.integers(1, max(2, num_customers + 1), num_rows,
                                  dtype=np.int64),
        "o_orderstatus": np.array(["F", "O", "P"], dtype=object)[
            rng.integers(0, 3, num_rows)],
        "o_totalprice": np.round(rng.uniform(850.0, 560_000.0, num_rows), 2),
        "o_orderdate": rng.integers(_days("1992-01-01"), _days("1998-08-02"),
                                    num_rows, dtype=np.int32),
        "o_orderpriority": np.array(
            ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"],
            dtype=object)[rng.integers(0, 5, num_rows)],
        "o_shippriority": np.zeros(num_rows, dtype=np.int32),
    }


def gen_customer(num_rows: int, seed: int = 2) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    return {
        "c_custkey": np.arange(1, num_rows + 1, dtype=np.int64),
        "c_name": np.array([f"Customer#{i:09d}" for i in
                            range(1, num_rows + 1)], dtype=object),
        "c_nationkey": rng.integers(0, 25, num_rows, dtype=np.int32),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, num_rows), 2),
        "c_mktsegment": SEGMENTS[rng.integers(0, len(SEGMENTS), num_rows)],
    }


NATIONS = np.array(
    ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
     "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
     "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
     "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
     "UNITED STATES"], dtype=object)
REGIONS = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"],
                   dtype=object)
_NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0,
                           0, 1, 2, 3, 4, 2, 3, 3, 1], dtype=np.int32)


def gen_supplier(num_rows: int, seed: int = 3):
    rng = np.random.default_rng(seed)
    return {
        "s_suppkey": np.arange(1, num_rows + 1, dtype=np.int64),
        "s_name": np.array([f"Supplier#{i:09d}" for i in
                            range(1, num_rows + 1)], dtype=object),
        "s_nationkey": rng.integers(0, 25, num_rows, dtype=np.int32),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, num_rows), 2),
    }


def gen_part(num_rows: int, seed: int = 4):
    rng = np.random.default_rng(seed)
    types = np.array([f"{a} {b} {c}" for a in
                      ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                       "PROMO")
                      for b in ("ANODIZED", "BURNISHED", "PLATED")
                      for c in ("TIN", "NICKEL", "BRASS", "STEEL",
                                "COPPER")], dtype=object)
    containers = np.array([f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO",
                                                "WRAP")
                           for b in ("CASE", "BOX", "BAG", "JAR", "PKG",
                                     "PACK", "CAN", "DRUM")], dtype=object)
    brands = np.array([f"Brand#{i}{j}" for i in range(1, 6)
                       for j in range(1, 6)], dtype=object)
    return {
        "p_partkey": np.arange(1, num_rows + 1, dtype=np.int64),
        "p_brand": brands[rng.integers(0, len(brands), num_rows)],
        "p_type": types[rng.integers(0, len(types), num_rows)],
        "p_size": rng.integers(1, 51, num_rows).astype(np.int32),
        "p_container": containers[rng.integers(0, len(containers),
                                               num_rows)],
        "p_retailprice": np.round(rng.uniform(900, 2000, num_rows), 2),
    }


def gen_partsupp(num_parts: int, num_supps: int, seed: int = 6):
    """4 suppliers per part with DISTINCT supplier keys per part (the
    (ps_partkey, ps_suppkey) pair is the TPC-H primary key)."""
    rng = np.random.default_rng(seed)
    pk = np.repeat(np.arange(1, num_parts + 1, dtype=np.int64), 4)
    n = len(pk)
    j = np.tile(np.arange(4, dtype=np.int64), num_parts)
    sk = ((pk - 1 + j * max(1, num_supps // 4)) % num_supps) + 1
    return {
        "ps_partkey": pk,
        "ps_suppkey": sk.astype(np.int64),
        "ps_availqty": rng.integers(1, 10_000, n).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n), 2),
    }


def gen_nation():
    return {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": NATIONS.copy(),
        "n_regionkey": _NATION_REGION.astype(np.int64),
    }


def gen_region():
    return {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": REGIONS.copy(),
    }


SUPPLIER_DDL = """CREATE TABLE supplier (
    s_suppkey BIGINT, s_name STRING, s_nationkey INT, s_acctbal DOUBLE
) USING column"""

PART_DDL = """CREATE TABLE part (
    p_partkey BIGINT, p_brand STRING, p_type STRING, p_size INT,
    p_container STRING, p_retailprice DOUBLE
) USING column"""

PARTSUPP_DDL = """CREATE TABLE partsupp (
    ps_partkey BIGINT, ps_suppkey BIGINT, ps_availqty INT,
    ps_supplycost DOUBLE
) USING column"""

NATION_DDL = """CREATE TABLE nation (
    n_nationkey BIGINT, n_name STRING, n_regionkey BIGINT
) USING row"""

REGION_DDL = """CREATE TABLE region (
    r_regionkey BIGINT, r_name STRING
) USING row"""

LINEITEM_DDL = """CREATE TABLE lineitem (
    l_orderkey BIGINT, l_partkey BIGINT, l_suppkey BIGINT,
    l_linenumber INT, l_quantity DOUBLE, l_extendedprice DOUBLE,
    l_discount DOUBLE, l_tax DOUBLE, l_returnflag STRING,
    l_linestatus STRING, l_shipdate DATE, l_commitdate DATE,
    l_receiptdate DATE, l_shipmode STRING
) USING column OPTIONS (partition_by 'l_orderkey')"""

ORDERS_DDL = """CREATE TABLE orders (
    o_orderkey BIGINT, o_custkey BIGINT, o_orderstatus STRING,
    o_totalprice DOUBLE, o_orderdate DATE, o_orderpriority STRING,
    o_shippriority INT
) USING column OPTIONS (partition_by 'o_orderkey', colocate_with 'lineitem')"""

CUSTOMER_DDL = """CREATE TABLE customer (
    c_custkey BIGINT, c_name STRING, c_nationkey INT, c_acctbal DOUBLE,
    c_mktsegment STRING
) USING column OPTIONS (partition_by 'c_custkey')"""

Q1 = """SELECT l_returnflag, l_linestatus,
    sum(l_quantity) AS sum_qty,
    sum(l_extendedprice) AS sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
    avg(l_quantity) AS avg_qty,
    avg(l_extendedprice) AS avg_price,
    avg(l_discount) AS avg_disc,
    count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus"""

Q6 = """SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1995-01-01'
  AND l_discount BETWEEN 0.05 AND 0.07
  AND l_quantity < 24"""

Q3 = """SELECT l_orderkey,
    sum(l_extendedprice * (1 - l_discount)) AS revenue,
    o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING'
  AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate
LIMIT 10"""

# Q3-class bench shape for the device join engine: the one-to-many
# orders->lineitem expansion (LEFT keeps the probe side as written — a
# non-unique lineitem build that used to drop to the pandas host join),
# revenue aggregated over the expanded pairs, grouped by a probe-side
# dictionary key.  The filtered subquery keeps the host-path comparison
# honest (both paths filter orders BEFORE joining).
Q3C = """SELECT o_orderpriority,
    count(l_orderkey) AS line_count,
    sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM (SELECT * FROM orders WHERE o_orderdate < DATE '1995-03-15') o
    LEFT JOIN lineitem ON o_orderkey = l_orderkey
GROUP BY o_orderpriority
ORDER BY o_orderpriority"""


def load_tpch(session, sf: float = 0.001, seed: int = 0,
              all_tables: bool = False) -> Dict[str, Dict[str, np.ndarray]]:
    """Create + populate the TPC-H tables at the given scale factor.
    Default: the three headline-benchmark tables; all_tables adds
    supplier/part/nation/region for the wider query set. Returns the
    generated lineitem/orders/customer arrays by table name, exactly as
    inserted, for a caller that builds an oracle from them."""
    n_l = max(1000, int(LINEITEM_ROWS_PER_SF * sf))
    n_o = max(250, int(ORDERS_ROWS_PER_SF * sf))
    n_c = max(25, int(CUSTOMER_ROWS_PER_SF * sf))
    n_s = max(10, int(10_000 * sf))
    n_p = max(50, int(200_000 * sf))
    session.sql(LINEITEM_DDL)
    session.sql(ORDERS_DDL)
    session.sql(CUSTOMER_DDL)
    li = gen_lineitem(n_l, seed)
    li["l_orderkey"] = np.minimum(li["l_orderkey"], n_o)  # FK into orders
    li["l_suppkey"] = (li["l_suppkey"] % n_s) + 1
    li["l_partkey"] = (li["l_partkey"] % n_p) + 1
    orders = gen_orders(n_o, n_c, seed + 1)
    customer = gen_customer(n_c, seed + 2)
    session.insert_arrays("lineitem", list(li.values()))
    session.insert_arrays("orders", list(orders.values()))
    session.insert_arrays("customer", list(customer.values()))
    if all_tables:
        session.sql(SUPPLIER_DDL)
        session.sql(PART_DDL)
        session.sql(NATION_DDL)
        session.sql(REGION_DDL)
        session.insert_arrays("supplier",
                              list(gen_supplier(n_s, seed + 3).values()))
        session.insert_arrays("part", list(gen_part(n_p, seed + 4).values()))
        session.sql(PARTSUPP_DDL)
        session.insert_arrays(
            "partsupp", list(gen_partsupp(n_p, n_s, seed + 6).values()))
        session.insert_arrays("nation", list(gen_nation().values()))
        session.insert_arrays("region", list(gen_region().values()))
    return {"lineitem": li, "orders": orders, "customer": customer}


Q4 = """SELECT o_orderpriority, count(*) AS order_count
FROM orders
WHERE o_orderdate >= DATE '1993-07-01'
  AND o_orderdate < DATE '1993-10-01'
  AND EXISTS (
    SELECT 1 FROM lineitem
    WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority ORDER BY o_orderpriority"""

Q5 = """SELECT n_name, sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM customer, orders, lineitem, supplier, nation, region
WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
  AND l_suppkey = s_suppkey AND c_nationkey = s_nationkey
  AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
  AND r_name = 'ASIA'
  AND o_orderdate >= DATE '1994-01-01'
  AND o_orderdate < DATE '1995-01-01'
GROUP BY n_name ORDER BY revenue DESC"""

Q10 = """SELECT c_custkey, c_name,
    sum(l_extendedprice * (1 - l_discount)) AS revenue, c_acctbal, n_name
FROM customer, orders, lineitem, nation
WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
  AND o_orderdate >= DATE '1993-10-01'
  AND o_orderdate < DATE '1994-01-01'
  AND l_returnflag = 'R' AND c_nationkey = n_nationkey
GROUP BY c_custkey, c_name, c_acctbal, n_name
ORDER BY revenue DESC LIMIT 20"""

Q12 = """SELECT l_shipmode,
    sum(CASE WHEN o_orderpriority = '1-URGENT'
             OR o_orderpriority = '2-HIGH' THEN 1 ELSE 0 END)
        AS high_line_count,
    sum(CASE WHEN o_orderpriority != '1-URGENT'
             AND o_orderpriority != '2-HIGH' THEN 1 ELSE 0 END)
        AS low_line_count
FROM orders, lineitem
WHERE o_orderkey = l_orderkey
  AND l_shipmode IN ('MAIL', 'SHIP')
  AND l_receiptdate >= DATE '1994-01-01'
  AND l_receiptdate < DATE '1995-01-01'
GROUP BY l_shipmode ORDER BY l_shipmode"""

Q14 = """SELECT 100.00 *
    sum(CASE WHEN p_type LIKE 'PROMO%'
        THEN l_extendedprice * (1 - l_discount) ELSE 0 END) /
    sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
FROM lineitem, part
WHERE l_partkey = p_partkey
  AND l_shipdate >= DATE '1995-09-01'
  AND l_shipdate < DATE '1995-10-01'"""

Q18 = """SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
    sum(l_quantity) AS total_qty
FROM customer, orders, lineitem
WHERE o_orderkey IN (
    SELECT l_orderkey FROM lineitem
    GROUP BY l_orderkey HAVING sum(l_quantity) > 150)
  AND c_custkey = o_custkey AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate LIMIT 100"""
Q2 = """SELECT s_acctbal, s_name, n_name, p_partkey, p_type
FROM part, supplier, partsupp, nation, region
WHERE p_partkey = ps_partkey AND s_suppkey = ps_suppkey
  AND p_size = 15 AND s_nationkey = n_nationkey
  AND n_regionkey = r_regionkey AND r_name = 'EUROPE'
  AND ps_supplycost = (
    SELECT min(ps_supplycost)
    FROM partsupp, supplier, nation, region
    WHERE p_partkey = ps_partkey AND s_suppkey = ps_suppkey
      AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
      AND r_name = 'EUROPE')
ORDER BY s_acctbal DESC, n_name, s_name, p_partkey LIMIT 100"""

Q17 = """SELECT sum(l_extendedprice) / 7.0 AS avg_yearly
FROM lineitem, part
WHERE p_partkey = l_partkey AND p_brand = 'Brand#23'
  AND p_container = 'MED BOX'
  AND l_quantity < (
    SELECT 0.2 * avg(l_quantity) FROM lineitem
    WHERE l_partkey = p_partkey)"""

Q20 = """SELECT s_name FROM supplier, nation
WHERE s_suppkey IN (
    SELECT ps_suppkey FROM partsupp
    WHERE ps_partkey IN (
        SELECT p_partkey FROM part WHERE p_type LIKE 'STANDARD%')
      AND ps_availqty > (
        SELECT 0.5 * sum(l_quantity) FROM lineitem
        WHERE l_partkey = ps_partkey AND l_suppkey = ps_suppkey
          AND l_shipdate >= DATE '1994-01-01'
          AND l_shipdate < DATE '1995-01-01'))
  AND s_nationkey = n_nationkey AND n_name = 'CANADA'
ORDER BY s_name"""

Q21 = """SELECT s_name, count(*) AS numwait
FROM supplier, lineitem l1, orders, nation
WHERE s_suppkey = l1.l_suppkey AND o_orderkey = l1.l_orderkey
  AND o_orderstatus = 'F' AND l1.l_receiptdate > l1.l_commitdate
  AND EXISTS (
    SELECT 1 FROM lineitem l2
    WHERE l2.l_orderkey = l1.l_orderkey
      AND l2.l_suppkey <> l1.l_suppkey)
  AND NOT EXISTS (
    SELECT 1 FROM lineitem l3
    WHERE l3.l_orderkey = l1.l_orderkey
      AND l3.l_suppkey <> l1.l_suppkey
      AND l3.l_receiptdate > l3.l_commitdate)
  AND s_nationkey = n_nationkey AND n_name = 'SAUDI ARABIA'
GROUP BY s_name ORDER BY numwait DESC, s_name LIMIT 100"""

# The remaining queries, adapted to the generator's columns the same way
# the single-node suite adapts them (tests/test_tpch_full.py) — together
# with Q1-Q21 above this is the full 22-query set (ref harness:
# cluster/src/test/scala/io/snappydata/benchmark/TPCH_Queries.scala).

Q7 = """SELECT n1.n_name, n2.n_name, sum(l_extendedprice * (1 - l_discount)) AS rev
FROM supplier, lineitem, orders, customer, nation n1, nation n2
WHERE s_suppkey = l_suppkey AND o_orderkey = l_orderkey
  AND c_custkey = o_custkey AND s_nationkey = n1.n_nationkey
  AND c_nationkey = n2.n_nationkey
  AND ((n1.n_name = 'FRANCE' AND n2.n_name = 'GERMANY')
       OR (n1.n_name = 'GERMANY' AND n2.n_name = 'FRANCE'))
GROUP BY n1.n_name, n2.n_name ORDER BY 1, 2"""

Q8 = """SELECT n_name, sum(CASE WHEN o_shippriority = 1
                   THEN l_extendedprice * (1 - l_discount)
                   ELSE 0 END) / sum(l_extendedprice * (1 - l_discount)) AS share
FROM lineitem, orders, supplier, nation
WHERE o_orderkey = l_orderkey AND s_suppkey = l_suppkey
  AND s_nationkey = n_nationkey
GROUP BY n_name ORDER BY n_name"""

Q9 = """SELECT n_name, sum(l_extendedprice * (1 - l_discount)
                   - ps_supplycost * l_quantity) AS profit
FROM lineitem, partsupp, supplier, nation, part
WHERE ps_partkey = l_partkey AND ps_suppkey = l_suppkey
  AND s_suppkey = l_suppkey AND s_nationkey = n_nationkey
  AND p_partkey = l_partkey AND p_type LIKE 'PROMO%'
GROUP BY n_name ORDER BY profit DESC, n_name"""

Q11 = """SELECT ps_partkey, sum(ps_supplycost * ps_availqty) AS val
FROM partsupp, supplier, nation
WHERE ps_suppkey = s_suppkey AND s_nationkey = n_nationkey
  AND n_name = 'GERMANY'
GROUP BY ps_partkey
HAVING sum(ps_supplycost * ps_availqty) > (
    SELECT sum(ps_supplycost * ps_availqty) * 0.05
    FROM partsupp, supplier, nation
    WHERE ps_suppkey = s_suppkey AND s_nationkey = n_nationkey
      AND n_name = 'GERMANY')
ORDER BY val DESC, ps_partkey"""

Q13 = """SELECT c_count, count(*) AS custdist FROM (
    SELECT c_custkey, count(o_orderkey) AS c_count
    FROM customer LEFT JOIN orders ON c_custkey = o_custkey
    GROUP BY c_custkey) c_orders
GROUP BY c_count ORDER BY custdist DESC, c_count DESC"""

Q15_VIEW = """CREATE OR REPLACE VIEW revenue_v AS
SELECT l_suppkey AS supplier_no,
       sum(l_extendedprice * (1 - l_discount)) AS total_rev
FROM lineitem GROUP BY l_suppkey"""

Q15 = """SELECT s_suppkey, s_name, total_rev
FROM supplier, revenue_v
WHERE s_suppkey = supplier_no
  AND total_rev = (SELECT max(total_rev) FROM revenue_v)
ORDER BY s_suppkey"""

Q16 = """SELECT p_brand, p_size, count(DISTINCT ps_suppkey) AS supplier_cnt
FROM partsupp, part
WHERE p_partkey = ps_partkey AND p_brand <> 'Brand#45'
  AND p_size IN (1, 4, 7)
  AND ps_suppkey NOT IN (
    SELECT s_suppkey FROM supplier WHERE s_acctbal < -900)
GROUP BY p_brand, p_size
ORDER BY supplier_cnt DESC, p_brand, p_size"""

Q19 = """SELECT sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM lineitem, part
WHERE p_partkey = l_partkey AND (
    (p_brand = 'Brand#12' AND p_size BETWEEN 1 AND 5
     AND l_quantity >= 1 AND l_quantity <= 11)
    OR (p_brand = 'Brand#23' AND p_size BETWEEN 1 AND 10
        AND l_quantity >= 10 AND l_quantity <= 20)
    OR (p_brand = 'Brand#34' AND p_size BETWEEN 1 AND 15
        AND l_quantity >= 20 AND l_quantity <= 30))"""

Q22 = """SELECT c_nationkey, count(*) AS numcust, sum(c_acctbal) AS totacctbal
FROM customer
WHERE c_nationkey IN (1, 3, 5, 7)
  AND c_acctbal > (SELECT avg(c_acctbal) FROM customer
                   WHERE c_acctbal > 0.0
                     AND c_nationkey IN (1, 3, 5, 7))
  AND NOT EXISTS (SELECT 1 FROM orders
                  WHERE o_custkey = c_custkey)
GROUP BY c_nationkey ORDER BY c_nationkey"""

#: qnum → SQL for all 22 queries (Q15 additionally needs Q15_VIEW first)
ALL_QUERIES = {1: Q1, 2: Q2, 3: Q3, 4: Q4, 5: Q5, 6: Q6, 7: Q7, 8: Q8,
               9: Q9, 10: Q10, 11: Q11, 12: Q12, 13: Q13, 14: Q14,
               15: Q15, 16: Q16, 17: Q17, 18: Q18, 19: Q19, 20: Q20,
               21: Q21, 22: Q22}


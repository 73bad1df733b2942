"""Exact decimals past int64's precision: Spark 2.1's result types for
+, -, *, %, SUM and AVG up to DECIMAL(38, s), computed on the device as
scaled int64 under the overflow guard and equal to a Python `Decimal`
oracle to the last digit, under both plate policies; a value forced past
int64 trips the guard and comes back exact through the host rerun.
"""

import decimal
from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu import types as T
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability import tracing


@pytest.fixture(params=[False, True], ids=["f64-plates", "f32-plates"])
def session(request):
    """Both float storage policies: a decimal's exactness must not
    depend on the DOUBLE plate dtype (the TPU config is f32 plates)."""
    props = config.global_properties()
    saved = (props.decimal_as_float64, props.tracing_enabled)
    props.decimal_as_float64 = not request.param
    props.tracing_enabled = True
    s = SnappySession(catalog=Catalog())
    yield s
    s.stop()
    props.decimal_as_float64, props.tracing_enabled = saved


def _dec(p, s):
    return T.DecimalType("decimal", p, s)


# ---- Spark 2.1's types -------------------------------------------------------

@pytest.mark.parametrize("op, a, b, out", [
    ("+", _dec(15, 2), _dec(15, 2), _dec(16, 2)),
    ("-", T.INT, _dec(15, 2), _dec(16, 2)),        # 1 - l_discount
    ("+", _dec(6, 2), _dec(6, 3), _dec(8, 3)),
    ("*", _dec(15, 2), _dec(16, 2), _dec(32, 4)),  # price * (1 - disc)
    ("*", _dec(32, 4), _dec(16, 2), _dec(38, 6)),  # bounded at 38
    ("*", T.LONG, _dec(15, 2), _dec(36, 2)),       # BIGINT is (20, 0)
    ("*", _dec(6, 2), _dec(6, 3), _dec(13, 5)),
    ("%", _dec(15, 2), _dec(10, 3), _dec(10, 3)),
    ("%", _dec(38, 0), _dec(5, 4), _dec(5, 4)),
    ("+", _dec(38, 10), _dec(38, 2), _dec(38, 10)),
    ("-", _dec(20, 0), _dec(20, 0), _dec(21, 0)),
    ("/", _dec(15, 2), _dec(15, 2), T.DOUBLE),     # still float
    ("*", T.DOUBLE, _dec(15, 2), T.DOUBLE),
])
def test_binop_types_are_spark_21s(op, a, b, out):
    assert T.decimal_binop_type(op, a, b) == out


@pytest.mark.parametrize("arg, sum_t, avg_t", [
    (_dec(15, 2), _dec(25, 2), _dec(19, 6)),
    (_dec(32, 4), _dec(38, 4), _dec(36, 8)),
    (_dec(38, 6), _dec(38, 6), _dec(38, 10)),
    (_dec(36, 36), _dec(38, 36), _dec(38, 38)),
    (_dec(1, 0), _dec(11, 0), _dec(5, 4)),
])
def test_sum_and_avg_types_are_spark_21s(arg, sum_t, avg_t):
    assert T.decimal_sum_type(arg) == sum_t
    assert T.decimal_avg_type(arg) == avg_t


def test_types_reach_the_result(session):
    session.sql("CREATE TABLE ty (a DECIMAL(15,2), b DECIMAL(15,2), "
                "k BIGINT) USING column")
    session.sql("INSERT INTO ty VALUES (1.25, 2.50, 3)")
    res = session.sql(
        "SELECT a * (1 - b), a * (1 - b) * (1 + b), a * k, a % b, "
        "sum(a), sum(a * b), avg(a), avg(a * b), a / b FROM ty "
        "GROUP BY a, b, k")
    assert [str(t) for t in res.dtypes] == [
        "decimal(32,4)", "decimal(38,6)", "decimal(36,2)",
        "decimal(15,2)", "decimal(25,2)", "decimal(38,4)",
        "decimal(19,6)", "decimal(35,8)", "double"]
    row = res.rows()[0]
    assert row[:8] == (Decimal("-1.8750"), Decimal("-6.562500"),
                       Decimal("3.75"), Decimal("1.25"), Decimal("1.25"),
                       Decimal("3.1250"), Decimal("1.250000"),
                       Decimal("3.12500000"))
    assert row[8] == pytest.approx(0.5)


def test_the_knob_off_keeps_the_float_path():
    """`decimal_exact` off: the float plates, and a product or an average
    typed DOUBLE, as they have always been."""
    props = config.global_properties()
    saved = props.decimal_exact
    props.decimal_exact = False
    try:
        assert T.decimal_binop_type("*", _dec(15, 2), _dec(15, 2)) \
            == T.DOUBLE
        assert T.decimal_avg_type(_dec(15, 2)) == T.DOUBLE
        assert T.decimal_sum_type(_dec(15, 2)) == T.DOUBLE
    finally:
        props.decimal_exact = saved


# ---- the device's answers against a Decimal oracle -------------------------

def _cents(rng, n, lo, hi):
    return rng.integers(lo, hi, n)


def _table(session, seed, n=20_000):
    """Money with negatives, in DECIMAL(15,2) and DECIMAL(12,4)."""
    rng = np.random.default_rng(seed)
    a = _cents(rng, n, -99_999_999_999, 99_999_999_999)   # +/- 1e9
    b = _cents(rng, n, -1_000_000, 1_000_000)             # +/- 100.0000
    g = rng.integers(0, 5, n)
    session.sql("CREATE TABLE m (g BIGINT, a DECIMAL(15,2), "
                "b DECIMAL(12,4)) USING column")
    session.insert_arrays("m", [g, a / 100.0, b / 10_000.0])
    return g, [Decimal(int(x)).scaleb(-2) for x in a], \
        [Decimal(int(x)).scaleb(-4) for x in b]


def _half_up(x: Decimal, scale: int) -> Decimal:
    ctx = decimal.Context(prec=80)
    return x.quantize(Decimal(1).scaleb(-scale), rounding=ROUND_HALF_UP,
                      context=ctx)


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_products_sums_and_averages_equal_a_decimal_oracle(session, seed):
    g, a, b = _table(session, seed)
    rows = session.sql(
        "SELECT g, sum(a * b), sum(a * b * (1 + b)), avg(a * b), "
        "avg(a), sum(a - b), count(*) FROM m GROUP BY g ORDER BY g").rows()
    ctx = decimal.Context(prec=80)
    assert len(rows) == 5
    for gi, s_ab, s_abb, avg_ab, avg_a, s_amb, cnt in rows:
        idx = np.flatnonzero(g == gi)
        ab = [ctx.multiply(a[i], b[i]) for i in idx]
        assert cnt == len(idx)
        assert s_ab == sum(ab, Decimal(0))
        assert s_abb == sum((ctx.multiply(x, 1 + b[i])
                             for x, i in zip(ab, idx)), Decimal(0))
        assert avg_ab == _half_up(ctx.divide(sum(ab, Decimal(0)), cnt), 10)
        assert avg_a == _half_up(ctx.divide(sum((a[i] for i in idx),
                                                Decimal(0)), cnt), 6)
        assert s_amb == sum((a[i] - b[i] for i in idx), Decimal(0))
    # row by row: the products themselves, each at scale 6
    got = session.sql("SELECT a * b, a * (1 - b) FROM m").rows()
    for (ab, a1b), x, y in zip(got, a, b):
        assert ab == ctx.multiply(x, y) and ab.as_tuple().exponent == -6
        assert a1b == ctx.multiply(x, 1 - y)


def test_average_ties_round_half_up(session):
    """0.01 over 20,000 rows is 0.0000005 exactly: HALF_UP gives
    0.000001 (half-even would give 0) and -0.000001 below zero."""
    n = 20_000
    v = np.zeros(2 * n)
    v[0], v[n] = 0.01, -0.01
    g = np.repeat([0, 1], n)
    session.sql("CREATE TABLE ti (g BIGINT, v DECIMAL(15,2)) USING column")
    session.insert_arrays("ti", [g, v])
    rows = session.sql("SELECT g, avg(v) FROM ti GROUP BY g "
                       "ORDER BY g").rows()
    assert rows == [(0, Decimal("0.000001")), (1, Decimal("-0.000001"))]


# ---- past int64: the guard and the exact host rerun -------------------------

def _fallback_reasons():
    out = []

    def walk(sp):
        if sp["name"] == "host_fallback":
            out.append(sp["attrs"].get("reason", ""))
        for c in sp.get("children", ()):
            walk(c)
    walk(tracing.ring().last().to_dict()["root"])
    return out


def test_values_past_int64_come_back_exact_from_the_host(session):
    a = [Decimal("9999999999999.99"), Decimal("1234567890123.45"),
         Decimal("-9876543210987.65"), Decimal("5.00")]
    b = [Decimal("9999999999999.99"), Decimal("8888888888888.88"),
         Decimal("7777777777777.77"), Decimal("3.00")]
    session.sql("CREATE TABLE big (g BIGINT, a DECIMAL(15,2), "
                "b DECIMAL(15,2)) USING column")
    session.insert_arrays("big", [np.array([1, 1, 2, 2]),
                                  np.array([float(x) for x in a]),
                                  np.array([float(x) for x in b])])
    ctx = decimal.Context(prec=80)
    ab = [ctx.multiply(x, y) for x, y in zip(a, b)]
    rows = session.sql("SELECT g, sum(a * b), avg(a * b) FROM big "
                       "GROUP BY g ORDER BY g").rows()
    (reason,) = _fallback_reasons()
    assert "decimal overflow" in reason
    assert rows == [
        (1, ctx.add(ab[0], ab[1]),
         _half_up(ctx.divide(ctx.add(ab[0], ab[1]), 2), 8)),
        (2, ctx.add(ab[2], ab[3]),
         _half_up(ctx.divide(ctx.add(ab[2], ab[3]), 2), 8))]
    got = session.sql("SELECT a * b FROM big").rows()
    assert "decimal overflow" in _fallback_reasons()[0]
    assert [r[0] for r in got] == ab
    # a result past DECIMAL(38, s) itself is NULL, as Spark's
    # CheckOverflow gives it
    assert session.sql("SELECT sum(a * b * b) FROM big").rows() == [(None,)]


def test_guard_trips_past_int64_and_not_well_below(session):
    """3,037,000,500^2 is past 2^63 - 1; 10^9^2 is well inside. Both are
    DECIMAL(21, 0) products, so both ride the guard."""
    session.sql("CREATE TABLE edge (k BIGINT, v DECIMAL(10,0)) "
                "USING column")
    session.insert_arrays("edge", [np.array([1, 2]),
                                   np.array([3037000500.0, 1e9])])
    assert session.sql("SELECT sum(v * v) FROM edge WHERE k = 2") \
        .rows() == [(Decimal(10 ** 18),)]
    assert _fallback_reasons() == []
    assert session.sql("SELECT sum(v * v) FROM edge").rows() \
        == [(Decimal(3037000500 ** 2 + 10 ** 18),)]
    assert "decimal overflow" in _fallback_reasons()[0]


def test_the_counters_say_what_was_lowered(session):
    session.sql("CREATE TABLE cn (a DECIMAL(15,2), b DECIMAL(15,2), "
                "x DOUBLE) USING column")
    session.sql("INSERT INTO cn VALUES (1.25, 2.50, 1.0)")

    def attrs(sql):
        session.sql(sql)
        found = []

        def walk(sp):
            if "decimal_wide_exprs" in sp.get("attrs", {}):
                found.append(sp["attrs"])
            for c in sp.get("children", ()):
                walk(c)
        walk(tracing.ring().last().to_dict()["root"])
        (a,) = found
        return a["decimal_wide_exprs"], a["decimal_exact_slots"]

    assert attrs("SELECT sum(a * b), sum(a * b) + 1, avg(a), "
                 "avg(a * (1 - b)) FROM cn") == (3, 3)
    assert attrs("SELECT a * b FROM cn") == (1, 0)
    assert attrs("SELECT sum(a + b), avg(b) FROM cn") == (0, 2)
    assert attrs("SELECT sum(x), avg(x) FROM cn") == (0, 0)


def test_a_stored_value_past_int64_is_read_on_the_host(session):
    """A DECIMAL(38,0) value of 10^30 has no int64 plate: the bind sends
    the statement to the host, which sums it exactly."""
    session.sql("CREATE TABLE wide (v DECIMAL(38,0)) USING column")
    session.insert_arrays("wide", [np.array([1e30, 7.0])])
    assert session.sql("SELECT sum(v) FROM wide").rows() \
        == [(Decimal(10 ** 30 + 7),)]
    (reason,) = _fallback_reasons()
    assert "leaves int64" in reason

"""Exact DECIMAL semantics (round-4 verdict Missing #1 / task 3):
scaled-int64 device plates, integer aggregation, scale tracking through
+,-,*,% and comparisons, Decimal results at the user boundary
(tests/test_decimal_wide.py: past precision 18, under the guard).

The done-gate: money columns declared DECIMAL(12,2) produce sums
BYTE-IDENTICAL to a Python decimal.Decimal oracle — including on the
f32-plate TPU storage config (decimal plates are int64 either way).
Ref: real fixed-point decimals via BigDecimal,
/root/reference/encoders/src/main/scala/org/apache/spark/sql/execution/
columnar/encoding/ColumnEncoding.scala:137-140 (readDecimal).
"""

from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog


@pytest.fixture(params=[False, True], ids=["f64-plates", "f32-plates"])
def session(request):
    """Both float storage policies: decimal exactness must not depend
    on the DOUBLE plate dtype (the TPU config is f32 plates)."""
    old = config.global_properties().decimal_as_float64
    config.global_properties().decimal_as_float64 = not request.param
    s = SnappySession(catalog=Catalog())
    yield s
    s.stop()
    config.global_properties().decimal_as_float64 = old


def _money(n, seed=0):
    rng = np.random.default_rng(seed)
    cents = rng.integers(-10_000_000, 10_000_000, n)  # +/- 100k.00
    return cents, cents.astype(np.float64) / 100.0


def test_sum_byte_identical_to_decimal_oracle(session):
    n = 200_000
    cents, vals = _money(n, seed=1)
    session.sql("CREATE TABLE m (k BIGINT, price DECIMAL(12,2)) "
                "USING column")
    session.insert_arrays("m", [np.arange(n, dtype=np.int64), vals])
    got = session.sql("SELECT sum(price), min(price), max(price), "
                      "count(price) FROM m").rows()[0]
    oracle = sum(Decimal(int(c)) for c in cents) / Decimal(100)
    assert isinstance(got[0], Decimal)
    assert got[0] == oracle                      # byte-identical
    assert got[1] == Decimal(int(cents.min())) / Decimal(100)
    assert got[2] == Decimal(int(cents.max())) / Decimal(100)
    assert got[3] == n


def test_grouped_sum_and_avg_exact(session):
    n = 120_000
    cents, vals = _money(n, seed=2)
    g = (np.arange(n) % 7).astype(np.int64)
    session.sql("CREATE TABLE gm (g BIGINT, price DECIMAL(12,2)) "
                "USING column")
    session.insert_arrays("gm", [g, vals])
    rows = session.sql("SELECT g, sum(price), avg(price), count(*) "
                       "FROM gm GROUP BY g ORDER BY g").rows()
    assert len(rows) == 7
    for gi, sv, av, cnt in rows:
        sel = g == gi
        oracle = sum(Decimal(int(c)) for c in cents[sel]) / Decimal(100)
        assert sv == oracle, gi
        assert cnt == int(sel.sum())
        # avg = exact sum / exact count, Spark 2.1's DECIMAL(16, 6):
        # rounded HALF_UP at scale 6, to the last digit
        assert isinstance(av, Decimal)
        assert av == (oracle / cnt).quantize(Decimal("1e-6"),
                                             rounding=ROUND_HALF_UP)


def test_arithmetic_scale_tracking(session):
    session.sql("CREATE TABLE a (x DECIMAL(6,2), y DECIMAL(6,3)) "
                "USING column")
    session.sql("INSERT INTO a VALUES (1.25, 2.125), (10.50, 0.375),"
                " (-3.75, 1.005)")
    rows = session.sql(
        "SELECT x + y, x - y, x * y, x / y FROM a ORDER BY x").rows()
    oracle = [(Decimal("-3.75"), Decimal("1.005")),
              (Decimal("1.25"), Decimal("2.125")),
              (Decimal("10.50"), Decimal("0.375"))]
    for (ax, sx, mx, dx), (x, y) in zip(rows, oracle):
        assert ax == x + y            # exact: scale 3
        assert sx == x - y
        assert mx == x * y            # exact: scale 5
        assert dx == pytest.approx(float(x) / float(y), rel=1e-12)


def test_comparison_boundaries_exact(session):
    session.sql("CREATE TABLE c (v DECIMAL(10,2)) USING column")
    session.sql("INSERT INTO c VALUES (24.04), (24.05), (24.06)")
    assert session.sql(
        "SELECT count(*) FROM c WHERE v < 24.05").rows()[0][0] == 1
    assert session.sql(
        "SELECT count(*) FROM c WHERE v <= 24.05").rows()[0][0] == 2
    assert session.sql(
        "SELECT count(*) FROM c WHERE v = 24.05").rows()[0][0] == 1
    # decimal vs integer literal
    session.sql("INSERT INTO c VALUES (25.00)")
    assert session.sql(
        "SELECT count(*) FROM c WHERE v = 25").rows()[0][0] == 1


def test_casts(session):
    session.sql("CREATE TABLE t (d DOUBLE, x DECIMAL(10,3)) USING column")
    session.sql("INSERT INTO t VALUES (1.2345, 12.3456), (-1.2355, -0.9)")
    r = session.sql("SELECT CAST(d AS DECIMAL(8,3)), CAST(x AS INT), "
                    "CAST(x AS DECIMAL(8,1)), CAST(x AS DOUBLE) "
                    "FROM t ORDER BY d").rows()
    assert r[1][0] == Decimal("1.234") or r[1][0] == Decimal("1.235")
    assert r[0][0] == Decimal("-1.236") or r[0][0] == Decimal("-1.235")
    assert r[1][1] == 12 and r[0][1] == 0          # truncation toward 0
    assert r[1][2] == Decimal("12.3")              # HALF_UP at scale 1
    assert r[1][3] == pytest.approx(12.3456, abs=5e-4)


def test_nulls_update_delete(session):
    session.sql("CREATE TABLE u (k BIGINT, v DECIMAL(10,2)) USING column")
    session.sql("INSERT INTO u VALUES (1, 1.10), (2, NULL), (3, 3.30),"
                " (4, 4.40)")
    assert session.sql("SELECT sum(v) FROM u").rows()[0][0] \
        == Decimal("8.80")
    session.sql("UPDATE u SET v = 9.99 WHERE k = 3")
    assert session.sql("SELECT sum(v) FROM u").rows()[0][0] \
        == Decimal("15.49")
    session.sql("DELETE FROM u WHERE k = 4")
    assert session.sql("SELECT sum(v) FROM u").rows()[0][0] \
        == Decimal("11.09")
    rows = session.sql("SELECT k, v FROM u ORDER BY k").rows()
    assert rows == [(1, Decimal("1.10")), (2, None), (3, Decimal("9.99"))]


def test_order_by_having_group_key(session):
    n = 50_000
    cents, vals = _money(n, seed=3)
    g = (np.arange(n) % 5).astype(np.int64)
    session.sql("CREATE TABLE oh (g BIGINT, v DECIMAL(12,2)) USING column")
    session.insert_arrays("oh", [g, vals])
    rows = session.sql(
        "SELECT g, sum(v) AS s FROM oh GROUP BY g "
        "HAVING sum(v) > -100000000 ORDER BY s DESC LIMIT 3").rows()
    assert len(rows) == 3
    oracle = sorted(
        (sum(Decimal(int(c)) for c in cents[g == gi]) / Decimal(100)
         for gi in range(5)), reverse=True)[:3]
    assert [r[1] for r in rows] == oracle
    # GROUP BY a decimal column (exact int64 grouping keys)
    session.sql("CREATE TABLE gk (v DECIMAL(6,2)) USING column")
    session.sql("INSERT INTO gk VALUES (1.10), (1.10), (2.20)")
    rows = session.sql("SELECT v, count(*) FROM gk GROUP BY v "
                       "ORDER BY v").rows()
    assert rows == [(Decimal("1.10"), 2), (Decimal("2.20"), 1)]


def test_sum_overflow_falls_back_not_wraps(session):
    # DECIMAL(18,0) near int64: the in-trace bound check must reroute to
    # the host path, which sums in Python integers: exact, at the sum's
    # DECIMAL(28,0), instead of wrapping silently
    n = 64
    session.sql("CREATE TABLE big (v DECIMAL(18,0)) USING column")
    session.insert_arrays(
        "big", [np.full(n, 9.0e17, dtype=np.float64)])
    got = session.sql("SELECT sum(v) FROM big").rows()[0][0]
    exact = Decimal(9 * 10 ** 17 * n)   # 5.76e19 — far beyond int64
    assert got == exact
    assert got > 0              # int64 wraparound would go negative


def test_sum_overflow_guard_covers_merged_total_across_tiles(session):
    """Under scan_tile_bytes tiling, each tile can pass the per-tile
    max|v|*count < 2^62 bound while the merged total wraps int64 — the
    guard must scale its bound by the tile count so the MERGED total is
    covered (advisor round 5). 5 tiles x 4 rows x 9e17: per-tile sum
    3.6e18 < 2^62, merged 1.8e19 > int64 max."""
    saved = session.conf.scan_tile_bytes
    try:
        session.sql("CREATE TABLE tile_big (v DECIMAL(18,0)) USING column "
                    "OPTIONS (column_batch_rows '4', "
                    "column_max_delta_rows '4')")
        session.insert_arrays(
            "tile_big", [np.full(20, 9.0e17, dtype=np.float64)])
        session.conf.scan_tile_bytes = 60   # one 4-row batch per tile
        got = session.sql("SELECT sum(v) FROM tile_big").rows()[0][0]
        exact = 9.0e17 * 20                 # 1.8e19
        # rel covers f32-plate rounding of the approximate fallback
        # (~2e-8); a silent int64 wrap would be negative / off by >2x
        assert float(got) == pytest.approx(exact, rel=1e-6)
        assert float(got) > 0               # int64 wrap would go negative
    finally:
        session.conf.scan_tile_bytes = saved


def test_tile_host_fallback_reads_only_its_tile(session):
    """When ONE tile reroutes to the host path (its per-tile bound
    fires), the host evaluation must honor the scan window: reading the
    whole table from inside a tile made the merge double-count every
    other tile (observed 3.96e19 for an exact total of 1.8e19)."""
    saved = session.conf.scan_tile_bytes
    try:
        # 8-row tiles: per-tile 8 x 9e17 = 7.2e18 >= 2^62 -> every tile
        # falls back to host, which must see ONLY its own 8 rows
        session.sql("CREATE TABLE tile_hf (v DECIMAL(18,0)) USING column "
                    "OPTIONS (column_batch_rows '8', "
                    "column_max_delta_rows '8')")
        session.insert_arrays(
            "tile_hf", [np.full(20, 9.0e17, dtype=np.float64)])
        session.conf.scan_tile_bytes = 100
        got = session.sql("SELECT sum(v) FROM tile_hf").rows()[0][0]
        # rel covers f32-plate rounding (~2e-8); the whole-table
        # double-count bug this guards against was off by 2.2x
        assert float(got) == pytest.approx(9.0e17 * 20, rel=1e-6)
    finally:
        session.conf.scan_tile_bytes = saved


def test_scan_scale_uses_nominal_tile_width(session):
    """The overflow-guard tile scale must come from the pass's NOMINAL
    window width: the last tile may be truncated (10 units in tiles of
    4 → window (8,10)) and a width of 2 would claim 5 tiles where 3
    exist, over-scaling the guard into spurious host fallbacks."""
    from snappydata_tpu.storage.device import (current_scan_scale,
                                               scan_window)

    session.sql("CREATE TABLE ts_w (v BIGINT) USING column OPTIONS "
                "(column_batch_rows '4', column_max_delta_rows '4')")
    session.insert_arrays("ts_w", [np.arange(40, dtype=np.int64)])
    data = session.catalog.describe("ts_w").data
    m = data.snapshot()
    assert len(m.views) == 10
    with scan_window(data, 8, 10, m, tile_units=4):
        assert current_scan_scale(data) == 3.0
    with scan_window(data, 0, 4, m, tile_units=4):
        assert current_scan_scale(data) == 3.0
    assert current_scan_scale(data) == 1.0   # outside any pass


def test_wide_precision_keeps_float_path(session):
    """Past precision 18 a decimal is exact too (scaled int64 under the
    overflow guard, Spark's DECIMAL(38, s) sum); only division keeps
    the float path, as at every precision."""
    session.sql("CREATE TABLE wp (v DECIMAL(28,2)) USING column")
    session.sql("INSERT INTO wp VALUES (1.25), (2.50)")
    res = session.sql("SELECT sum(v), sum(v) / 4 FROM wp WHERE v > 2")
    assert [str(t) for t in res.dtypes] == ["decimal(38,2)", "double"]
    assert res.rows() == [(Decimal("2.50"), pytest.approx(0.625))]
    got = session.sql("SELECT sum(v) FROM wp").rows()[0][0]
    assert got == Decimal("3.75")


def test_row_table_decimal(session):
    session.sql("CREATE TABLE rt (k INT PRIMARY KEY, v DECIMAL(10,2)) "
                "USING row")
    session.sql("INSERT INTO rt VALUES (1, 10.01), (2, 20.02)")
    assert session.sql("SELECT sum(v) FROM rt").rows()[0][0] \
        == Decimal("30.03")
    # PK point lookup path decodes decimals too
    r = session.sql("SELECT v FROM rt WHERE k = 2").rows()
    assert r == [(Decimal("20.02"),)]


def test_persistence_roundtrip(tmp_path):
    d = str(tmp_path / "store")
    s = SnappySession(data_dir=d)
    s.sql("CREATE TABLE p (k BIGINT, v DECIMAL(12,2)) USING column")
    n = 5000
    cents, vals = _money(n, seed=4)
    s.insert_arrays("p", [np.arange(n, dtype=np.int64), vals])
    oracle = sum(Decimal(int(c)) for c in cents) / Decimal(100)
    assert s.sql("SELECT sum(v) FROM p").rows()[0][0] == oracle
    s.checkpoint()
    s.stop()
    s2 = SnappySession(data_dir=d)
    assert s2.sql("SELECT sum(v) FROM p").rows()[0][0] == oracle
    f = s2.catalog.lookup_table("p").schema.field("v")
    assert f.dtype.precision == 12 and f.dtype.scale == 2
    s2.stop()


@pytest.mark.slow
def test_distributed_sum_exact():
    """Decimal exactness across the cluster plane: per-server partial
    sums are exact int64, ship as Arrow decimal128, and re-enter the
    merge through the float64 host domain — so the merged total equals
    the Decimal oracle while every partial fits 15 significant digits
    (~9e13 at scale 2; beyond that the merge degrades to f64 like the
    host fallback, a documented bound in types.DecimalType)."""
    from snappydata_tpu.cluster import LocatorNode, ServerNode
    from snappydata_tpu.cluster.distributed import DistributedSession

    locator = LocatorNode().start()
    servers = [ServerNode(locator.address,
                          SnappySession(catalog=Catalog())).start()
               for _ in range(2)]
    ds = DistributedSession(
        server_addresses=[s.flight_address for s in servers])
    try:
        ds.sql("CREATE TABLE dm (k BIGINT, g BIGINT, v DECIMAL(12,2)) "
               "USING column OPTIONS (partition_by 'k')")
        n = 40_000
        cents, vals = _money(n, seed=5)
        k = np.arange(n, dtype=np.int64)
        g = (k % 3).astype(np.int64)
        ds.insert_arrays("dm", [k, g, vals])
        rows = ds.sql("SELECT g, sum(v), count(*) FROM dm GROUP BY g "
                      "ORDER BY g").rows()
        assert len(rows) == 3
        for gi, sv, cnt in rows:
            sel = g == gi
            oracle = sum(Decimal(int(c))
                         for c in cents[sel]) / Decimal(100)
            assert Decimal(str(sv)) == oracle, (gi, sv, oracle)
            assert cnt == int(sel.sum())
    finally:
        ds.close()
        for sv in servers:
            sv.stop()
        locator.stop()


def test_mesh_sharded_sum_exact():
    """Under the 8-device virtual mesh, decimal plates shard on the
    batch axis and the psum stays in int64 — exactness survives GSPMD."""
    from snappydata_tpu.parallel import MeshContext, data_mesh

    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE mm (k BIGINT, v DECIMAL(12,2)) USING column")
    n = 30_000
    cents, vals = _money(n, seed=6)
    s.insert_arrays("mm", [np.arange(n, dtype=np.int64), vals])
    oracle = sum(Decimal(int(c)) for c in cents) / Decimal(100)
    assert s.sql("SELECT sum(v) FROM mm").rows()[0][0] == oracle
    with MeshContext(data_mesh(8)):
        s.executor.clear_cache()
        assert s.sql("SELECT sum(v) FROM mm").rows()[0][0] == oracle
    s.executor.clear_cache()
    s.stop()


def test_tiled_scan_sum_exact():
    """Tiny scan_tile_bytes forces the multi-tile partial-merge path:
    per-tile int64 partials must re-combine exactly."""
    old = config.global_properties().scan_tile_bytes
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE ts (k BIGINT, v DECIMAL(12,2)) USING column "
              "OPTIONS (column_max_delta_rows '2000')")
        n = 20_000
        cents, vals = _money(n, seed=7)
        s.insert_arrays("ts", [np.arange(n, dtype=np.int64), vals])
        oracle = sum(Decimal(int(c)) for c in cents) / Decimal(100)
        config.global_properties().scan_tile_bytes = 64 * 1024
        s.executor.clear_cache()
        got = s.sql("SELECT sum(v), count(*) FROM ts").rows()[0]
        assert got[1] == n
        assert got[0] == oracle
    finally:
        config.global_properties().scan_tile_bytes = old
        s.stop()


def test_subquery_literal_substitution(session):
    """Scalar-subquery results substitute as Decimal literals — they
    must scale into the exact domain, not truncate to int (review
    finding: Lit(24.05, DECIMAL) cast straight to int64 became 0.24)."""
    session.sql("CREATE TABLE sq (k BIGINT, v DECIMAL(10,2)) USING column")
    session.sql("INSERT INTO sq VALUES (1, 24.05), (2, 10.00), (3, 24.05)")
    rows = session.sql(
        "SELECT k FROM sq WHERE v = (SELECT max(v) FROM sq) "
        "ORDER BY k").rows()
    assert [r[0] for r in rows] == [1, 3]


def test_union_and_intersect_mixed_scales(session):
    session.sql("CREATE TABLE ua (v DECIMAL(10,2)) USING column")
    session.sql("CREATE TABLE ub (v DECIMAL(10,3)) USING column")
    session.sql("INSERT INTO ua VALUES (24.05), (1.10)")
    session.sql("INSERT INTO ub VALUES (24.050), (2.200)")
    got = sorted(float(r[0]) for r in session.sql(
        "SELECT v FROM ua UNION ALL SELECT v FROM ub").rows())
    assert got == pytest.approx([1.10, 2.20, 24.05, 24.05])
    inter = session.sql(
        "SELECT v FROM ua INTERSECT SELECT v FROM ub").rows()
    assert len(inter) == 1 and float(inter[0][0]) == pytest.approx(24.05)
    # the union type widens over both branches: a finer right-branch
    # scale must survive decode (review finding — left-anchored dtype
    # quantized 1.005 to 1.00/1.01)
    session.sql("INSERT INTO ub VALUES (1.005)")
    got2 = sorted(str(r[0]) for r in session.sql(
        "SELECT v FROM ua UNION ALL SELECT v FROM ub").rows())
    assert "1.005" in got2
    # set_op output decodes at the widened scale too: a scaled left
    # branch must not be re-read at the finer right-branch scale
    # (review finding: 24.05 decoded as 2.405)
    inter2 = session.sql(
        "SELECT v FROM ua INTERSECT SELECT v FROM ub").rows()
    assert [float(r[0]) for r in inter2] == pytest.approx([24.05])


def test_ctas_and_insert_select_keep_values(session):
    """CTAS / INSERT..SELECT from an exact-decimal column must store
    the VALUE, not the scaled representation (review finding: 24.05
    stored as 2405.00)."""
    session.sql("CREATE TABLE src (k BIGINT, v DECIMAL(10,2)) USING column")
    session.sql("INSERT INTO src VALUES (1, 24.05), (2, 1.10)")
    session.sql("CREATE TABLE ct AS SELECT k, v FROM src")
    assert session.sql("SELECT sum(v) FROM ct").rows()[0][0] \
        == Decimal("25.15")
    session.sql("CREATE TABLE tgt (k BIGINT, v DECIMAL(10,2)) USING column")
    session.sql("INSERT INTO tgt SELECT k, v FROM src")
    assert session.sql("SELECT v FROM tgt WHERE k = 1").rows() \
        == [(Decimal("24.05"),)]


def test_half_up_rounding_ties(session):
    # 0.125 at scale 2: HALF_UP -> 0.13 (np.round's half-even would
    # give 0.12 and disagree with the BigDecimal contract)
    session.sql("CREATE TABLE hu (v DECIMAL(6,2)) USING column")
    session.insert_arrays("hu", [np.array([0.125, -0.125])])
    rows = session.sql("SELECT v FROM hu ORDER BY v").rows()
    assert rows == [(Decimal("-0.13"),), (Decimal("0.13"),)]


def test_decimal_in_scalar_functions_unscales(session):
    session.sql("CREATE TABLE sf (v DECIMAL(8,2)) USING column")
    session.sql("INSERT INTO sf VALUES (2.25), (-3.50)")
    rows = session.sql("SELECT round(v), abs(v), sqrt(abs(v)) FROM sf "
                       "ORDER BY v").rows()
    assert rows[0][0] == pytest.approx(-4.0)   # Spark round half up? -3.5 → -4
    assert rows[0][1] == pytest.approx(3.5)
    assert rows[1][2] == pytest.approx(1.5)

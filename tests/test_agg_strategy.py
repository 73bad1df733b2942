"""Single-pass grouped aggregation: packed slot fusion, the backend-aware
reduction strategy table (ops/reduction.py), the group-index cache, and
the tiled scan's on-device partial merge.

Covers the perf-guard contracts the CI must hold:
- reduction dispatches per grouped query are O(1) in slot count (the
  old path issued one masked reduction per group per slot);
- tile partials merge on device (scan_tile_device_merges) and never take
  the per-tile host round trip when the group space is tile-aligned;
- unroll / scatter / matmul agree bit-for-bit on
  exactly-summable inputs across dtypes, null patterns, empty groups,
  and G around the 64-group unroll boundary;
- the count accumulator widens past the int32 row bound.
"""

import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.ops import reduction


@pytest.fixture
def props():
    p = config.global_properties()
    saved = (p.agg_reduce_strategy, p.gidx_cache_bytes,
             p.column_batch_rows, p.scan_tile_bytes,
             p.decimal_as_float64, p.agg_on_codes)
    yield p
    (p.agg_reduce_strategy, p.gidx_cache_bytes,
     p.column_batch_rows, p.scan_tile_bytes,
     p.decimal_as_float64, p.agg_on_codes) = saved


def _counter(name: str) -> int:
    return global_registry().counter(name)


# ---------------------------------------------------------------------
# strategy table + packed kernels (ops/reduction.py)
# ---------------------------------------------------------------------

def test_resolve_strategy_degrades_invalid_requests():
    # matmul refused for exact int sums and min/max, and past the
    # one-hot byte budget; unroll degrades to scatter past the boundary
    assert reduction.resolve_strategy(
        "matmul", "cpu", 8, 1000, "isum", jnp.int64) != "matmul"
    assert reduction.resolve_strategy(
        "matmul", "cpu", 8, 1000, "minmax", jnp.float64) != "matmul"
    huge_n = reduction.MATMUL_ONEHOT_MAX_BYTES  # n*G*8 >> budget
    assert reduction.resolve_strategy(
        "matmul", "cpu", 8, huge_n, "fsum", jnp.float64) == "scatter"
    assert reduction.resolve_strategy(
        "unroll", "cpu", reduction.UNROLL_MAX_SEGMENTS + 1, 1000,
        "fsum", jnp.float64) == "scatter"
    # auto: cpu float sums take the matmul (gemm) when the one-hot fits
    assert reduction.resolve_strategy(
        "auto", "cpu", 9, 100_000, "fsum", jnp.float64) == "matmul"
    # auto: tpu keeps the measured unroll in the dictionary regime
    assert reduction.resolve_strategy(
        "auto", "tpu", 9, 100_000, "fsum", jnp.float64) == "unroll"
    assert reduction.resolve_strategy(
        "auto", "tpu", 1000, 100_000, "fsum", jnp.float64) == "scatter"


@pytest.mark.parametrize("nseg", [1, 2, 63, 64, 65, 200])
@pytest.mark.parametrize("dtype", [np.float64, np.float32, np.int64])
def test_packed_strategies_bit_identical(nseg, dtype):
    """unroll / scatter / matmul produce bit-identical group results on
    exactly-summable values (integer-valued, so summation order cannot
    matter) across dtypes, null patterns, empty groups, and G around
    the 64-group unroll boundary."""
    rng = np.random.default_rng(nseg)
    n = 4096
    # leave the last segment (and, for nseg>2, segment 0) empty
    lo = 1 if nseg > 2 else 0
    gidx = jnp.asarray(rng.integers(lo, max(1, nseg - 1), n))
    vals = rng.integers(-50, 50, (n, 3)).astype(dtype)
    mask = rng.random(n) < 0.8  # null pattern
    masked = np.where(mask[:, None], vals, 0).astype(dtype)
    cols = [jnp.asarray(masked[:, j]) for j in range(3)]
    outs = {}
    for strat in ("unroll", "scatter", "matmul"):
        eff = reduction.resolve_strategy(
            strat, "cpu", nseg, n, "isum" if dtype == np.int64 else "fsum",
            jnp.dtype(dtype))
        outs[strat] = np.asarray(
            reduction.packed_sum(cols, gidx, nseg, eff))
    assert (outs["unroll"] == outs["scatter"]).all()
    assert (outs["unroll"] == outs["matmul"]).all()
    # oracle
    for g in range(nseg):
        sel = (np.asarray(gidx) == g) & mask
        np.testing.assert_array_equal(
            outs["scatter"][g], vals[sel].sum(axis=0).astype(dtype)
            if sel.any() else np.zeros(3, dtype))
    # min/max: unroll vs scatter, empty groups keep the identity filler
    mn_fill = np.where(mask[:, None], vals,
                       reduction._extreme_of(jnp.dtype(dtype), True))
    mm_cols = [jnp.asarray(mn_fill.astype(dtype)[:, j])
               for j in range(3)]
    for kind in ("min", "max"):
        a = np.asarray(reduction.packed_minmax(
            kind, mm_cols, gidx, nseg,
            "unroll" if nseg <= 64 else "scatter"))
        b = np.asarray(reduction.packed_minmax(
            kind, mm_cols, gidx, nseg, "scatter"))
        assert (a == b).all()


def test_matmul_nonfinite_values_stay_group_isolated(props):
    """A NaN/Inf value must poison ONLY its own group: the matmul
    strategy's finite-guard falls back to the isolating scatter."""
    props.agg_reduce_strategy = "matmul"
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE nf (k STRING, v DOUBLE) USING column")
    s.insert_arrays("nf", [
        np.array(["a", "a", "b", "b"], dtype=object),
        np.array([1.0, np.nan, 2.0, 3.0])])
    rows = s.sql("SELECT k, sum(v) FROM nf GROUP BY k ORDER BY k").rows()
    assert rows[0][0] == "a" and np.isnan(rows[0][1])
    assert rows[1] == ("b", 5.0)
    s.stop()


# ---------------------------------------------------------------------
# engine-level equivalence + knob behavior
# ---------------------------------------------------------------------

def _mk_session():
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE t (k STRING, b BOOLEAN, v DOUBLE, i BIGINT) "
          "USING column")
    rng = np.random.default_rng(11)
    n = 20_000
    k = rng.choice(np.array(["a", "b", "c", "d", "e"], dtype=object), n)
    b = rng.random(n) < 0.5
    v = rng.integers(0, 10_000, n).astype(np.float64)  # exactly summable
    i = rng.integers(-100, 100, n, dtype=np.int64)
    nulls = rng.random(n) < 0.2
    s.catalog.describe("t").data.insert_arrays(
        [k, b, v, i], nulls=[None, None, nulls, None])
    return s


ENGINE_Q = ("SELECT k, b, count(*), count(v), sum(v), avg(v), min(v), "
            "max(v), sum(i), stddev(v) FROM t GROUP BY k, b "
            "ORDER BY k, b")


def test_engine_strategies_identical_and_respecialize(props):
    """All strategies return identical rows through the engine, and the
    knob re-specializes via the static key — no plan-cache clear."""
    s = _mk_session()
    props.agg_reduce_strategy = "auto"
    base = s.sql(ENGINE_Q).rows()
    assert len(base) == 10
    for strat in ("unroll", "scatter", "matmul"):
        props.agg_reduce_strategy = strat
        before = _counter(f"agg_strategy_{strat}")
        got = s.sql(ENGINE_Q).rows()
        for a, b in zip(got, base):
            # integer-valued doubles: sums are exact under any order, so
            # equality is exact (stddev divides — compare approx)
            assert a[:9] == b[:9], (strat, a, b)
            assert a[9] == pytest.approx(b[9], rel=1e-12)
        assert _counter(f"agg_strategy_{strat}") > before, \
            f"{strat} was not picked despite the knob"
    s.stop()


# Query shapes under the chip's dtype policy (float32 plates, float64
# accumulators), each against a NumPy oracle that rounds inputs to
# float32 as the plates do, a product to float32 once (as
# benchmark/references/q1.py does) and sums in float64.  Each builder
# returns (session, sql, expected rows).

F32 = np.float32


def _f64(a):
    return a.astype(F32).astype(np.float64)


def _shape_q1():
    """Two string keys, ten aggregates, a filter."""
    rng = np.random.default_rng(2)
    n = 120_000
    flag = rng.choice(np.array(["A", "N", "R"], dtype=object), n)
    status = rng.choice(np.array(["F", "O"], dtype=object), n)
    qty = np.round(rng.random(n) * 50, 0)
    price = np.round(rng.random(n) * 2e4, 2)
    disc = np.round(rng.random(n) * 0.1, 2)
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE li (flag STRING, status STRING, qty DOUBLE,"
          " price DOUBLE, disc DOUBLE) USING column")
    s.insert_arrays("li", [flag, status, qty, price, disc])
    sql = ("SELECT flag, status, sum(qty), sum(price),"
           " sum(price * (1 - disc)), avg(qty), avg(disc), count(*),"
           " min(price), max(price)"
           " FROM li WHERE qty < 45 GROUP BY flag, status"
           " ORDER BY flag, status")
    dp = (price.astype(F32) * (F32(1) - disc.astype(F32))) \
        .astype(np.float64)
    exp = []
    for f in ("A", "N", "R"):
        for st in ("F", "O"):
            m = (flag == f) & (status == st) & (qty < 45)
            c = int(m.sum())
            q, p, d = _f64(qty)[m], _f64(price)[m], _f64(disc)[m]
            exp.append((f, st, float(q.sum()), float(p.sum()),
                        float(dp[m].sum()), float(q.sum()) / c,
                        float(d.sum()) / c, c, float(p.min()),
                        float(p.max())))
    return s, sql, exp


def _shape_wide():
    """12 sums, 6 mins and count(*) over three groups."""
    rng = np.random.default_rng(5)
    n = 5_000
    k = rng.choice(np.array(["x", "y", "z"], dtype=object), n)
    cols = [np.round(rng.random(n) * 100, 2) for _ in range(12)]
    s = SnappySession(catalog=Catalog())
    decls = ", ".join(f"c{i} DOUBLE" for i in range(12))
    s.sql(f"CREATE TABLE w (k STRING, {decls}) USING column")
    s.insert_arrays("w", [k] + cols)
    sums = ", ".join(f"sum(c{i})" for i in range(12))
    mins = ", ".join(f"min(c{i})" for i in range(6))
    sql = f"SELECT k, {sums}, {mins}, count(*) FROM w GROUP BY k ORDER BY k"
    exp = []
    for key in ("x", "y", "z"):
        m = k == key
        exp.append((key,) + tuple(float(_f64(c)[m].sum()) for c in cols)
                   + tuple(float(_f64(c)[m].min()) for c in cols[:6])
                   + (int(m.sum()),))
    return s, sql, exp


def _shape_nullable_key():
    """A nullable key (the extra code slot) and an exact int sum beside
    float slots; every value is exact in float32."""
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE t (k STRING, v DOUBLE, i INT) USING column")
    s.sql("INSERT INTO t VALUES ('a', 1.5, 10), ('a', 2.5, 20),"
          " (NULL, 4.0, 40), ('b', 8.0, 80), (NULL, 0.5, 5)")
    sql = ("SELECT k, sum(v), sum(i), count(v), min(v), max(v) FROM t"
           " GROUP BY k ORDER BY k")
    exp = [(None, 4.5, 45, 2, 0.5, 4.0), ("a", 4.0, 30, 2, 1.5, 2.5),
           ("b", 8.0, 80, 1, 8.0, 8.0)]
    return s, sql, exp


def _shape_global():
    """No group: a bare sum, an average and a product under a filter."""
    rng = np.random.default_rng(3)
    n = 500_000
    v = np.round(rng.random(n) * 2e4, 2)
    q = rng.integers(1, 50, n).astype(np.float64)
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE pr (v DOUBLE, q DOUBLE) USING column")
    s.insert_arrays("pr", [v, q])
    sql = "SELECT sum(v), avg(v), sum(v * q) FROM pr WHERE q < 25"
    m = q < 25
    sv = float(_f64(v)[m].sum())
    vq = (v.astype(F32) * q.astype(F32)).astype(np.float64)
    exp = [(sv, sv / int(m.sum()), float(vq[m].sum()))]
    return s, sql, exp


_F32_SHAPES = {"q1": _shape_q1, "wide": _shape_wide,
               "nullable_key": _shape_nullable_key,
               "global": _shape_global}


@pytest.mark.parametrize("strategy", ["unroll", "scatter", "matmul"])
@pytest.mark.parametrize("shape", list(_F32_SHAPES))
def test_engine_f32_plate_shapes_match_oracle(props, shape, strategy):
    """Float32 plates with float64 accumulators is the pairing every
    cell runs on the chip.  Under each forced strategy: keys, counts,
    NULLs, int sums and row order exact; float sums, averages, min and
    max to 1e-9 of max(|oracle|, 1), the benchmark's own limit; and the
    float family really took the forced strategy."""
    props.decimal_as_float64 = False
    props.agg_reduce_strategy = strategy
    s, sql, exp = _F32_SHAPES[shape]()
    before = _counter(f"agg_strategy_{strategy}")
    got = s.sql(sql).rows()
    s.stop()
    assert _counter(f"agg_strategy_{strategy}") > before
    assert len(got) == len(exp)
    for rg, re in zip(got, exp):
        assert len(rg) == len(re)
        for a, b in zip(rg, re):
            if isinstance(b, float):
                assert isinstance(a, float), (rg, re)
                assert abs(a - b) <= 1e-9 * max(abs(b), 1.0), (rg, re)
            else:
                assert a == b and type(a) is type(b), (rg, re)


def test_explain_and_stats_name_every_lane_the_executor_counts(props):
    """EXPLAIN ANALYZE and /status/api/v1/scan report from the one tuple
    of names run_main counts under: a dictionary-space sum is named by
    both, and no `agg_strategy_*` counter exists outside the tuple."""
    from snappydata_tpu.observability.stats_service import scan_snapshot

    props.agg_on_codes = "on"
    rng = np.random.default_rng(23)
    n = 20_000
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE ac (g BIGINT, q DOUBLE) USING column")
    s.insert_arrays("ac", [
        rng.integers(0, 6, n).astype(np.int64),
        rng.choice(np.array([0.5, 1.25, 2.0, 3.75, 8.5]), n)])
    c0 = global_registry().counters_snapshot()
    rows = s.sql("EXPLAIN ANALYZE SELECT g, sum(q), count(*) FROM ac "
                 "GROUP BY g").rows()
    c1 = global_registry().counters_snapshot()
    s.stop()
    raised = {k[len("agg_strategy_"):] for k in c1
              if k.startswith("agg_strategy_") and c1[k] > c0.get(k, 0)}
    assert "dict_space" in raised
    assert raised <= set(reduction.REPORTED_STRATEGIES)
    agg = [r[0] for r in rows if "strategy=" in r[0]]
    assert len(agg) == 1
    named = agg[0].split("strategy=")[1].split()[0].rstrip("]").split(",")
    assert set(named) == raised
    assert raised <= set(scan_snapshot()["agg_strategies"])


def test_reduce_passes_constant_in_slot_count(props):
    """CI perf guard: fused reduction dispatches are O(1) in the number
    of aggregate slots — a wide aggregate packs into the same per-family
    passes as a narrow one."""
    props.agg_reduce_strategy = "auto"
    s = SnappySession(catalog=Catalog())
    decls = ", ".join(f"c{j} DOUBLE" for j in range(8))
    s.sql(f"CREATE TABLE w (k STRING, {decls}) USING column")
    rng = np.random.default_rng(5)
    n = 5000
    s.insert_arrays("w", [
        rng.choice(np.array(["x", "y", "z"], dtype=object), n)]
        + [np.round(rng.random(n) * 100, 2) for _ in range(8)])

    def passes_of(q):
        s.sql(q)  # warm/compile
        c0 = _counter("agg_reduce_passes")
        s.sql(q)
        return _counter("agg_reduce_passes") - c0

    narrow = passes_of(
        "SELECT k, sum(c0), min(c0), count(*) FROM w GROUP BY k")
    sums = ", ".join(f"sum(c{j})" for j in range(8))
    avgs = ", ".join(f"avg(c{j})" for j in range(8))
    mins = ", ".join(f"min(c{j})" for j in range(4))
    wide = passes_of(
        f"SELECT k, {sums}, {avgs}, {mins}, count(*) FROM w GROUP BY k")
    assert narrow > 0
    assert wide == narrow, (wide, narrow)
    s.stop()


def test_count_accumulator_widens_past_int32(monkeypatch):
    """Regression for the int32 count accumulator: jnp.sum of int32 ones
    keeps int32 and could wrap past 2**31 rows.  The packed count dtype
    now widens by an explicit row-count bound (N is a static shape), and
    counts riding the f64 matmul pack are exact below 2**53."""
    assert reduction.count_pack_dtype(2 ** 31 - 1) == jnp.int32
    assert reduction.count_pack_dtype(2 ** 31) == jnp.int64
    assert reduction.count_pack_dtype(2 ** 40) == jnp.int64
    # behavioral check at a shrunken bound: with the threshold forced
    # tiny, the engine must pick int64 and still count exactly
    monkeypatch.setattr(reduction, "COUNT_I32_MAX_ROWS", 100)
    assert reduction.count_pack_dtype(101) == jnp.int64
    gidx = jnp.asarray(np.zeros(500, dtype=np.int64))
    ones = jnp.asarray(np.ones(500, dtype=np.int32)).astype(
        reduction.count_pack_dtype(500))
    out = reduction.packed_sum([ones], gidx, 2, "scatter")
    assert out.dtype == jnp.int64
    assert int(out[0, 0]) == 500


def test_gidx_cache_hits_and_invalidation(props):
    """Repeated dashboard queries skip group-index recomputation; a
    mutation rotates the bind identity and invalidates the entry."""
    props.agg_reduce_strategy = "auto"
    s = _mk_session()
    q = "SELECT k, count(*) FROM t GROUP BY k ORDER BY k"
    s.sql(q)  # compile + first run (miss)
    h0, m0 = _counter("gidx_cache_hits"), _counter("gidx_cache_misses")
    s.sql(q)
    s.sql(q)
    assert _counter("gidx_cache_hits") == h0 + 2
    assert _counter("gidx_cache_misses") == m0
    s.sql("INSERT INTO t VALUES ('a', true, 1.0, 1)")
    rows = s.sql(q).rows()
    assert _counter("gidx_cache_misses") == m0 + 1
    assert sum(r[1] for r in rows) == 20_001
    # disabling the cache budget bypasses the two-phase split entirely
    props.gidx_cache_bytes = 0
    h1 = _counter("gidx_cache_hits")
    m1 = _counter("gidx_cache_misses")
    assert s.sql(q).rows() == rows
    assert _counter("gidx_cache_hits") == h1
    assert _counter("gidx_cache_misses") == m1
    s.stop()


# ---------------------------------------------------------------------
# tiled scan: on-device merge + REST surface
# ---------------------------------------------------------------------

def test_tile_merges_stay_on_device(props):
    """CI perf guard: a tile-aligned grouped aggregate merges its [G]
    partials on device — no per-tile host round trip (the host-merge
    counter must not move)."""
    props.column_batch_rows = 256
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE big (k STRING, v DOUBLE) USING column")
    rng = np.random.default_rng(9)
    n = 4096
    k = rng.choice(np.array(["a", "b", "c"], dtype=object), n)
    v = rng.integers(0, 1000, n).astype(np.float64)
    s.catalog.describe("big").data.insert_arrays([k, v])
    q = "SELECT k, count(*), sum(v), min(v) FROM big GROUP BY k ORDER BY k"
    untiled = s.sql(q).rows()
    props.scan_tile_bytes = 4 * 256 * 16
    t0, d0, h0 = (_counter("scan_tiles"),
                  _counter("scan_tile_device_merges"),
                  _counter("scan_tile_host_merges"))
    got = s.sql(q).rows()
    tiles = _counter("scan_tiles") - t0
    assert tiles > 1, "expected a multi-tile pass"
    assert _counter("scan_tile_device_merges") - d0 == tiles - 1
    assert _counter("scan_tile_host_merges") == h0
    assert got == untiled
    # a direct numeric key now groups through its table-global value
    # domain (vdict): the group-index space is data-independent across
    # tiles, so the merge stays on device too — with identical values
    q2 = "SELECT v, count(*) FROM big GROUP BY v ORDER BY v LIMIT 3"
    props.scan_tile_bytes = 0
    flat2 = s.sql(q2).rows()
    props.scan_tile_bytes = 4 * 256 * 16
    h1 = _counter("scan_tile_host_merges")
    assert s.sql(q2).rows() == flat2
    assert _counter("scan_tile_host_merges") == h1
    # an EXPRESSION key has no table-global domain: generic hash path,
    # host merge, exactly once
    q3 = "SELECT v + 0.5, count(*) FROM big GROUP BY v + 0.5 LIMIT 3"
    h2 = _counter("scan_tile_host_merges")
    d1 = _counter("scan_tile_device_merges")
    s.sql(q3)
    assert _counter("scan_tile_host_merges") == h2 + 1
    assert _counter("scan_tile_device_merges") == d1
    s.stop()


def test_rest_scan_endpoint(props):
    from snappydata_tpu.cluster.rest import RestService
    from snappydata_tpu.observability.stats_service import \
        TableStatsService

    s = _mk_session()
    s.sql("SELECT k, count(*) FROM t GROUP BY k")
    svc = RestService(s, TableStatsService(s.catalog), port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://{svc.host}:{svc.port}/status/api/v1/scan",
                timeout=5) as resp:
            body = json.loads(resp.read())
        assert body["agg_reduce_strategy"] == \
            props.get("agg_reduce_strategy")
        assert body["agg_reduce_passes"] > 0
        assert isinstance(body["agg_strategies"], dict) \
            and body["agg_strategies"]
        assert {"gidx_cache_hits", "scan_tiles",
                "scan_tile_device_merges",
                "scan_tile_prefetch_overlap"} <= set(body)
        # dashboard renders the Aggregation section
        with urllib.request.urlopen(
                f"http://{svc.host}:{svc.port}/dashboard",
                timeout=5) as resp:
            html = resp.read().decode()
        assert "Aggregation engine" in html
    finally:
        svc.stop()
        s.stop()

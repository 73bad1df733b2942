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
    # matmul refused for min/max and past the one-hot byte budget; kept
    # for exact int sums (the limb product, whose walker bounds its own
    # transient); unroll degrades to scatter past the boundary
    assert reduction.resolve_strategy(
        "matmul", "cpu", 8, 1000, "isum", jnp.int64) == "matmul"
    assert reduction.resolve_strategy(
        "matmul", "tpu", 100_000, 1 << 40, "isum", jnp.int64) == "matmul"
    assert reduction.resolve_strategy(
        "matmul", "cpu", 8, 1000, "minmax", jnp.float64) != "matmul"
    assert reduction.resolve_strategy(
        "matmul", "tpu", 128, 1000, "minmax", jnp.int64) != "matmul"
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
    # auto: the tpu's exact-integer family takes the limb product from
    # one past the unroll to the swept bound, the scatter past it; the
    # cpu (which materialises the one-hots) keeps what it had
    top = reduction.LIMB_MATMUL_MAX_SEGMENTS
    for nseg, want in ((reduction.UNROLL_MAX_SEGMENTS, "unroll"),
                       (reduction.UNROLL_MAX_SEGMENTS + 1, "matmul"),
                       (top, "matmul"), (top + 1, "scatter")):
        assert reduction.resolve_strategy(
            "auto", "tpu", nseg, 100_000_000, "isum", jnp.int64) == want
    for nseg, want in ((4, "unroll"), (5, "scatter"), (65, "scatter"),
                       (top, "scatter")):
        assert reduction.resolve_strategy(
            "auto", "cpu", nseg, 100_000, "isum", jnp.int64) == want
    assert reduction.resolve_strategy(
        "auto", "tpu", 128, 100_000, "minmax", jnp.int64) == "scatter"


_I64 = np.iinfo(np.int64)


def _limb_case(name: str):
    """(cols, gidx, G) of one exactness case of the limb product; every
    case holds rows on the dump segment (gidx == G)."""
    rng = np.random.default_rng(len(name))
    chunk = reduction.LIMB_CHUNK_ROWS
    groups = {"g65": 65, "g1000": 1000}.get(name, 128)
    n = {"no_rows": 0, "one_row": 1, "one_chunk": chunk,
         "masked_chunk": 2 * chunk + 17}.get(name, chunk + 4097)
    gidx = rng.integers(0, groups + 1, n).astype(np.int32)
    vals = rng.integers(-2**62, 2**62, n, dtype=np.int64)
    cols = [vals]
    if name == "extremes":
        # +-2**62 and the two ends of int64 pile into group 3 and wrap
        ext = np.array([-1, 0, 2**62, -2**62, _I64.max, _I64.min,
                        _I64.max, _I64.max, _I64.min], dtype=np.int64)
        vals[:ext.size] = ext
        gidx[:ext.size] = 3
        vals[ext.size:2 * ext.size] = ext
    elif name == "masked_chunk":
        gidx[chunk:2 * chunk] = groups     # a whole chunk on the dump
    elif name == "two_sums_two_masks":
        cols = [vals, rng.integers(_I64.min, _I64.max, n, dtype=np.int64),
                rng.random(n) < 0.5, np.ones(n, bool)]
    elif name == "null_masked":
        w = rng.random(n) < 0.6
        cols = [np.where(w, vals, 0), w]
    elif name == "int32_column":
        cols = [rng.integers(-2**31, 2**31 - 1, n).astype(np.int32)]
    return cols, gidx, groups


@pytest.mark.parametrize("name", [
    "g65", "g128", "g1000", "no_rows", "one_row", "one_chunk",
    "masked_chunk",
    "extremes", "two_sums_two_masks", "null_masked", "int32_column"])
def test_limb_product_matches_scatter_bit_for_bit(name):
    """The integer form of `matmul` against the int64 `segment_sum` and
    NumPy's `add.at`: every bit, wrap-around included."""
    import jax

    cols, gidx, groups = _limb_case(name)
    got = {strat: np.asarray(jax.jit(
        lambda *c, strat=strat: reduction.packed_sum(
            [x if strat == "matmul" else x.astype(jnp.int64)
             for x in c[1:]], c[0], groups, strat).astype(jnp.int64)
    )(gidx, *cols)) for strat in ("matmul", "scatter")}
    want = np.zeros((groups + 1, len(cols)), np.int64)
    with np.errstate(over="ignore"):
        for j, c in enumerate(cols):
            np.add.at(want[:, j], gidx, c.astype(np.int64))
    assert got["matmul"].dtype == np.int64
    np.testing.assert_array_equal(got["matmul"], got["scatter"])
    np.testing.assert_array_equal(got["matmul"], want[:groups])


def test_limb_steps_cover_every_row_within_the_budget():
    """The walker's plan: chunks x rows + tail is N, a chunk never
    passes the exact range of the float32 accumulator, a step's
    would-be operands stay inside LIMB_STEP_BYTES."""
    for n, groups, width in ((100_663_296, 128, 9), (100_663_296, 65536, 9),
                             (6_291_456, 8192, 17), (131_072 * 763, 128, 9),
                             (1, 65, 1), (65_537, 1000, 8)):
        rows, step, steps, rest, tail = reduction._limb_steps(
            n, groups, width)
        assert (steps * step + rest) * rows + tail == n
        assert tail < rows <= reduction.LIMB_CHUNK_ROWS
        assert ((1 << reduction.LIMB_BITS) - 1) * rows \
            < reduction.LIMB_ACC_EXACT
        assert step * rows * (groups + width) * 2 \
            <= max(reduction.LIMB_STEP_BYTES, 128 * (groups + width) * 2)
    # the quick-start cell's shape: 128 steps of 12 chunks, nothing odd
    assert reduction._limb_steps(100_663_296, 128, 9) \
        == (65536, 12, 128, 0, 0)


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


def _main_dispatch_attrs():
    """Attrs of the last traced statement's main dispatch span."""
    from snappydata_tpu.observability import tracing

    def spans(sp):
        yield sp
        for c in sp.get("children", ()):
            yield from spans(c)

    (sp,) = [sp for sp in spans(tracing.ring().last().to_dict()["root"])
             if sp["name"] in ("jit_compile", "device_execute")
             and sp["attrs"].get("phase", "main") == "main"]
    return sp["attrs"]


def _steer(monkeypatch, props, plan: str) -> None:
    """`knob`: the explicit request, which is how the CPU backend
    reaches the limb product.  `chip`: the plan `auto` builds on the
    TPU (float families past 64 groups scatter, the integer ones and
    the counts take the product), with the backend steered here, in the
    test, as tests/test_tpu_compile.py does."""
    if plan == "knob":
        props.agg_reduce_strategy = "matmul"
    else:
        import jax

        props.agg_reduce_strategy = "auto"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("plan", ["knob", "chip"])
def test_engine_limb_product_is_exact_and_says_so(props, monkeypatch, plan):
    """The quick-start statement's family over ~300 K rows and 100 syms,
    ids near 2**40 (a float64 dot would round the sums): NumPy's exact
    answer, and the span says which integer columns the product took.
    Under the knob the counts stay in the float64 one-hot pack, as
    before; on the chip's plan the count mask rides the int64 pack's
    product."""
    _steer(monkeypatch, props, plan)
    n = 300_007
    ids = np.arange(n, dtype=np.int64) * ((1 << 40) // n) - (1 << 39)
    syms = np.array([f"sym{k}" for k in range(100)], dtype=object)
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (id BIGINT NOT NULL, sym VARCHAR(10) "
              "NOT NULL) USING column")
        s.insert_arrays("t", [ids, syms[np.arange(n) % 100]])
        before = _counter("agg_strategy_matmul")
        for _ in range(2):      # the second from the group-index cache
            rows = s.sql("select sym, sum(id), avg(id), count(*) from t "
                         "group by sym").rows()
            attrs = _main_dispatch_attrs()
            assert attrs["limb_matmul_slots"] == (1 if plan == "knob" else 2)
            assert attrs["isum_scatter_slots"] == 0
            assert attrs["scatter_slots"] == 0
            assert attrs["group_slots"] == 128
        assert attrs["gidx_cache_hit"] == 1
        assert _counter("agg_strategy_matmul") >= before + 2
    finally:
        s.stop()
    want = {}
    for k in range(100):
        mine = ids[np.arange(n) % 100 == k]
        total = int(mine.sum())
        want[f"sym{k}"] = (total, total / len(mine), len(mine))
    assert {r[0]: tuple(r[1:]) for r in rows} == want


@pytest.mark.parametrize("plan", ["knob", "chip"])
def test_decimal_sum_past_64_groups_keeps_its_overflow_guard(
        props, monkeypatch, plan):
    """A DECIMAL(15,2) sum over 100 groups by the limb product is the
    Decimal oracle's, and a group whose absmax x count passes 2**62
    still reroutes the statement to the host (the guard is upstream of
    the product)."""
    from decimal import Decimal

    _steer(monkeypatch, props, plan)
    rng = np.random.default_rng(33)
    n = 40_000
    keys = (np.arange(n) % 100).astype(np.int32)
    cents = rng.integers(-10**14, 10**14, n)
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE d (k INT NOT NULL, v DECIMAL(15,2)) "
              "USING column")
        nulls = rng.random(n) < 0.1
        s.catalog.describe("d").data.insert_arrays(
            [keys, cents.astype(np.float64) / 100.0], nulls=[None, nulls])
        rows = s.sql("SELECT k, sum(v), count(v) FROM d GROUP BY k").rows()
        attrs = _main_dispatch_attrs()
        assert attrs["limb_matmul_slots"] == (1 if plan == "knob" else 3)
        assert attrs["isum_scatter_slots"] == 0
        got = {r[0]: (r[1], r[2]) for r in rows}
        for k in range(100):
            sel = (keys == k) & ~nulls
            stored = np.round(cents[sel].astype(np.float64) / 100.0 * 100)
            assert got[k] == (Decimal(int(stored.sum())) / 100, sel.sum())
        # 6,000 rows of 9,999,999,999,999.99 in one group: 6e18 > 2**62
        reg = global_registry()
        fallbacks = reg.counter("host_fallbacks")
        s.catalog.describe("d").data.insert_arrays(
            [np.full(6000, 7, np.int32), np.full(6000, 9999999999999.99)])
        rows = s.sql("SELECT k, sum(v) FROM d GROUP BY k").rows()
        assert reg.counter("host_fallbacks") == fallbacks + 1
        big = {r[0]: float(r[1]) for r in rows}[7]
        assert big == pytest.approx(
            6000 * 9999999999999.99 + float(got[7][0]), rel=1e-9)
        assert len(rows) == 100
    finally:
        s.stop()


def test_limb_product_under_the_mesh(props, monkeypatch):
    """The chip's plan through `mesh_exec`'s shard_map: each shard
    reduces its rows by the limb product (its loop's carry has to vary
    over the mesh axis as its body's sum does), the partials merge by
    addition; sums that wrap int64 come back as NumPy's."""
    from snappydata_tpu.parallel import MeshContext, data_mesh

    _steer(monkeypatch, props, "chip")
    props.column_batch_rows = 4096
    n = 100_000
    ids = np.arange(n, dtype=np.int64) * 9_000_000_000_000 - 2**62
    keys = (np.arange(n) % 100).astype(np.int32)
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (id BIGINT NOT NULL, k INT NOT NULL) "
              "USING column")
        s.insert_arrays("t", [ids, keys])
        execs = _counter("mesh_shard_execs")
        with MeshContext(data_mesh(8)):
            rows = s.sql("SELECT k, sum(id), count(*) FROM t GROUP BY k "
                         "ORDER BY k").rows()
        assert _counter("mesh_shard_execs") == execs + 1
    finally:
        s.stop()
    with np.errstate(over="ignore"):
        want = [(k, int(ids[keys == k].sum()), n // 100)
                for k in range(100)]
    assert [tuple(r) for r in rows] == want


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

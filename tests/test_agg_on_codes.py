"""Aggregate-on-codes (PR 19 tentpole): GROUP BY keys and SUM/AVG/COUNT
inputs consume ENCODED plates directly — dict-encoded group keys map to
group indices by pure code arithmetic (no gather, no decode), dict
measures reduce in dictionary space (bincount the codes, dot the
dictionary), RLE measures reduce in run space (value x run-length).
Every lane is value-asserted against the decoded path
(`agg_on_codes=off`) across op x encoding x NULL group keys x
out-of-dictionary literals x `?` binds x empty batches, on the
single-device, tiled, and mesh execution lanes."""

import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability.metrics import global_registry


def _props():
    return config.global_properties()


@pytest.fixture(autouse=True)
def _restore_knobs():
    saved = (_props().get("agg_on_codes"),
             _props().get("scan_compressed_domain"))
    yield
    _props().set("agg_on_codes", saved[0])
    _props().set("scan_compressed_domain", saved[1])


def _counters():
    return dict(global_registry().snapshot()["counters"])


def _delta(c0, key):
    return _counters().get(key, 0) - c0.get(key, 0)


def _agg_session(n=20_000, with_nulls=True, seed=23):
    """One table exercising every aggregate lane: g (shuffled low-card
    BIGINT -> VALUE_DICT group key), q (low-card DOUBLE -> VALUE_DICT
    measure), r (sorted low-card DOUBLE -> RUN_LENGTH measure), name
    (STRING dictionary key), v (PLAIN measure)."""
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE ac (k BIGINT, g BIGINT, q DOUBLE, r DOUBLE, "
          "name STRING, v DOUBLE) USING column")
    rng = np.random.default_rng(seed)
    k = np.arange(n, dtype=np.int64)
    g = rng.integers(0, 6, n).astype(np.int64)
    q = rng.choice(np.array([0.5, 1.25, 2.0, 3.75, 8.5]), n)
    r = np.sort(rng.choice(np.array([1.0, 2.0, 5.0, 9.0]), n))
    name = np.array([f"n{i % 7}" for i in range(n)], dtype=object)
    v = rng.random(n) * 1000
    s.insert_arrays("ac", [k, g, q, r, name, v])
    if with_nulls:
        # NULL group keys AND NULL measures ride the row buffer, then
        # roll into a batch with validity masks
        for i in range(8):
            s.sql(f"INSERT INTO ac VALUES ({n + i}, NULL, NULL, NULL, "
                  f"NULL, {float(i)})")
    data = s.catalog.describe("ac").data
    data.force_rollover()
    return s, dict(k=k, g=g, q=q, r=r, name=name, v=v), data


def _both(s, sql, params=None):
    """(code-domain rows, decoded rows) of one query — the equivalence
    harness.  The knob rides the STATIC key: no cache flush between."""
    _props().set("agg_on_codes", "on")
    on = s.sql(sql, params).rows() if params else s.sql(sql).rows()
    _props().set("agg_on_codes", "off")
    off = s.sql(sql, params).rows() if params else s.sql(sql).rows()
    _props().set("agg_on_codes", "auto")
    return on, off


def _assert_rows_equal(a, b):
    assert len(a) == len(b), (a, b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb), (ra, rb)
        for x, y in zip(ra, rb):
            if isinstance(x, float) and isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-9, abs=1e-9), (ra, rb)
            else:
                assert x == y, (ra, rb)


def test_grouped_matrix_code_vs_decoded(decode_form):
    """The core equivalence sweep: every aggregate op x numeric/string/
    NULL-bearing group keys x dict/RLE/plain measures x in- and out-of-
    dictionary filter literals, each value-asserted on == off, under
    both forms of the dictionary decode (the group-key remap of `g`
    among them)."""
    s, cols, _ = _agg_session()
    queries = [
        "SELECT g, count(*), sum(q), avg(q), min(q), max(q) FROM ac "
        "GROUP BY g ORDER BY g",
        "SELECT g, sum(v), count(q) FROM ac GROUP BY g ORDER BY g",
        "SELECT name, count(*), sum(q) FROM ac GROUP BY name ORDER BY name",
        "SELECT g, name, sum(q), count(*) FROM ac GROUP BY g, name "
        "ORDER BY g, name",
        "SELECT sum(q), count(q), avg(q) FROM ac",
        "SELECT sum(r), count(r) FROM ac",
        "SELECT sum(r), count(*) FROM ac WHERE r < 5.0",
        "SELECT g, sum(q) FROM ac WHERE q = 1.25 GROUP BY g ORDER BY g",
        # out-of-dictionary literals: equality miss and between-codes edge
        "SELECT g, count(*) FROM ac WHERE q = 24.5 GROUP BY g ORDER BY g",
        "SELECT g, sum(q) FROM ac WHERE q > 2.1 GROUP BY g ORDER BY g",
        "SELECT g, count(*) FROM ac WHERE q IS NULL GROUP BY g ORDER BY g",
        "SELECT g, sum(q) FROM ac WHERE q IS NOT NULL GROUP BY g "
        "ORDER BY g",
        "SELECT count(*), sum(v) FROM ac WHERE g = 3",
    ]
    for qy in queries:
        on, off = _both(s, qy)
        _assert_rows_equal(on, off)
    s.stop()


def test_lane_counters_fire_with_exact_values():
    """All three lane counters fire, and each lane's answer equals the
    decoded answer AND the numpy ground truth."""
    s, cols, _ = _agg_session(with_nulls=False)
    g, q, r = cols["g"], cols["q"], cols["r"]

    c0 = _counters()
    on, off = _both(s, "SELECT g, sum(q), count(*) FROM ac "
                       "GROUP BY g ORDER BY g")
    _assert_rows_equal(on, off)
    assert _delta(c0, "agg_code_domain") > 0, \
        "numeric dict key must take the code-domain group-by lane"
    assert _delta(c0, "agg_dict_space") > 0, \
        "dict measure sum must take the dictionary-space lane"
    for gv, sq, cnt in on:
        m = g == int(gv)
        assert cnt == int(m.sum())
        assert sq == pytest.approx(float(q[m].sum()), rel=1e-9)

    c1 = _counters()
    on, off = _both(s, "SELECT sum(r), count(r) FROM ac WHERE r < 5.0")
    _assert_rows_equal(on, off)
    assert _delta(c1, "agg_rle_runs") > 0, \
        "run-aligned global sum/count must take the run-space lane"
    m = r < 5.0
    assert on[0][0] == pytest.approx(float(r[m].sum()), rel=1e-9)
    assert on[0][1] == int(m.sum())
    s.stop()


def test_misaligned_rle_filter_falls_back_counted():
    """A filter on a DIFFERENT column than the RLE measure breaks the
    run-alignment proof: the lane must decline COUNTED
    (compressed_fallback_rle_agg), never silently, and the decoded
    answer must be exact."""
    s, cols, _ = _agg_session(with_nulls=False)
    _props().set("agg_on_codes", "on")
    c0 = _counters()
    got = s.sql("SELECT sum(r), count(r) FROM ac WHERE v < 500.0").rows()
    assert _delta(c0, "compressed_fallback_rle_agg") > 0, \
        "misaligned run filter must be a counted fallback"
    m = cols["v"] < 500.0
    assert got[0][0] == pytest.approx(float(cols["r"][m].sum()), rel=1e-9)
    assert got[0][1] == int(m.sum())
    s.stop()


def test_prepared_binds_take_the_same_lanes():
    """`?` binds (PR 7 serving path) through the grouped code-domain
    lanes: bound literals translate to codes exactly like inline ones,
    including out-of-dictionary bind values."""
    s, cols, _ = _agg_session(with_nulls=False)
    g, q, v = cols["g"], cols["q"], cols["v"]
    _props().set("agg_on_codes", "on")
    h = s.prepare("SELECT g, count(*), sum(v) FROM ac WHERE q = ? "
                  "GROUP BY g ORDER BY g")
    for lit in (1.25, 24.5, -3.0, 8.5):
        got = h.execute((lit,)).rows()
        mm = q == lit
        exp = sorted(set(g[mm]))
        assert [int(row[0]) for row in got] == [int(x) for x in exp]
        for gv, cnt, sv in got:
            m = mm & (g == int(gv))
            assert cnt == int(m.sum())
            assert sv == pytest.approx(float(v[m].sum()), rel=1e-9)
    s.stop()


def test_null_group_keys_match_decoded():
    """NULL keys form their own group on both paths; a declined key
    domain (NaN rows in the numeric domain scan) degrades to the
    generic hash lane, never a wrong group."""
    s, cols, _ = _agg_session(with_nulls=True)
    on, off = _both(
        s, "SELECT g, count(*), sum(v) FROM ac GROUP BY g ORDER BY g")
    _assert_rows_equal(on, off)
    # the 8 NULL-key rows land in exactly one NULL group
    nulls = [row for row in on if row[0] is None]
    assert len(nulls) == 1 and nulls[0][1] == 8
    s.stop()


def test_empty_table_and_all_deleted_batches():
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE eac (g BIGINT, x DOUBLE) USING column")
    on, off = _both(s, "SELECT g, count(*), sum(x) FROM eac "
                       "GROUP BY g ORDER BY g")
    _assert_rows_equal(on, off)
    assert on == []
    # rows arrive, roll over, then all die: batch exists, zero live rows
    s.insert_arrays("eac", [np.repeat(np.arange(4, dtype=np.int64), 250),
                            np.full(1000, 2.5)])
    s.catalog.describe("eac").data.force_rollover()
    s.sql("DELETE FROM eac WHERE g >= 0")
    on, off = _both(s, "SELECT g, sum(x) FROM eac GROUP BY g ORDER BY g")
    _assert_rows_equal(on, off)
    assert on == []
    s.stop()


def test_tiled_lane_matches_untiled():
    """The tiled scan merges per-tile partials ON DEVICE for numeric
    dict keys (the table-global domain is data-independent, so partial
    group vectors align across tiles)."""
    props = _props()
    old_rows, old_tile = props.column_batch_rows, props.scan_tile_bytes
    props.column_batch_rows = 256
    try:
        s, cols, _ = _agg_session(n=4000, with_nulls=False)
        qy = ("SELECT g, count(*), sum(q), sum(v) FROM ac "
              "GROUP BY g ORDER BY g")
        _props().set("agg_on_codes", "on")
        untiled = s.sql(qy).rows()
        props.scan_tile_bytes = 3 * 256 * 32
        reg = global_registry()
        t0 = reg.counter("scan_tiles")
        tiled = s.sql(qy).rows()
        assert reg.counter("scan_tiles") > t0, "tiled path must engage"
        _assert_rows_equal(tiled, untiled)
        props.scan_tile_bytes = old_tile
        on, off = _both(s, qy)
        _assert_rows_equal(on, off)
        s.stop()
    finally:
        props.column_batch_rows = old_rows
        props.scan_tile_bytes = old_tile


def test_bench_check_guards_code_agg_lane():
    """--check: dead lane counters and a measured (auto) rate below
    SNAPPY_BENCH_CODE_AGG_RATIO x the decode-throughput-law prediction
    both fail; records predating the lane stay comparable."""
    import bench

    ca = {"grouped_rows_per_s_auto": 100.0, "predicted_rows_per_s": 100.0,
          "lane_counters": {"agg_code_domain": 2, "agg_dict_space": 2,
                            "agg_rle_runs": 2}}
    rec = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 5},
        "compressed": {"code_domain_predicates": 9,
                       "resident_bytes_per_row": 10.0,
                       "code_agg": dict(ca)}}}
    assert bench.check_regression(rec, rec) == []
    dead = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 5},
        "compressed": {"code_domain_predicates": 9,
                       "resident_bytes_per_row": 10.0,
                       "code_agg": {**ca, "lane_counters":
                                    {"agg_code_domain": 2,
                                     "agg_dict_space": 0,
                                     "agg_rle_runs": 2}}}}}
    assert any("agg_dict_space" in f
               for f in bench.check_regression(dead, rec))
    slow = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 5},
        "compressed": {"code_domain_predicates": 9,
                       "resident_bytes_per_row": 10.0,
                       "code_agg": {**ca,
                                    "grouped_rows_per_s_auto": 70.0}}}}
    assert any("decode-throughput-law" in f
               for f in bench.check_regression(slow, rec))
    old = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 5},
        "compressed": {"code_domain_predicates": 9,
                       "resident_bytes_per_row": 10.0}}}
    assert bench.check_regression(old, rec) == []


@pytest.mark.mesh
def test_mesh_lane_matches_single_device(decode_form):
    from snappydata_tpu.parallel import MeshContext, data_mesh

    s, cols, _ = _agg_session(n=16_000, with_nulls=False)
    ctx = MeshContext(data_mesh(8))
    for qy in ("SELECT g, count(*), sum(q), sum(v) FROM ac "
               "GROUP BY g ORDER BY g",
               "SELECT sum(q), count(q) FROM ac WHERE q > 2.1",
               "SELECT name, sum(q) FROM ac GROUP BY name ORDER BY name"):
        _props().set("agg_on_codes", "on")
        single = s.sql(qy).rows()
        with ctx:
            mesh_on = s.sql(qy).rows()
            _props().set("agg_on_codes", "off")
            mesh_off = s.sql(qy).rows()
            _props().set("agg_on_codes", "auto")
        _assert_rows_equal(mesh_on, single)
        _assert_rows_equal(mesh_off, single)
    s.stop()


# ----------------------------------------------------------------------
# PR 26: the count step is a per-batch one-hot product, not a scatter
# ----------------------------------------------------------------------

_CAP = 512


def _count_inputs(b, dp, code_dtype, nseg, seed):
    """Codes, dictionaries, group index and weights with every kind of
    row the count step must leave out: `w` false, the dump segment, and
    one batch that is padding from end to end."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, dp, (b, _CAP)).astype(code_dtype)
    dicts = np.sort(rng.random((b, dp)) * 1e3, axis=1)
    gidx = rng.integers(0, nseg, b * _CAP).astype(np.int32)  # dump incl.
    w = rng.random(b * _CAP) < 0.9
    pad = b // 2                      # a wholly padded batch
    gidx[pad * _CAP:(pad + 1) * _CAP] = nseg - 1
    w[pad * _CAP:(pad + 1) * _CAP] = False
    codes[pad] = 0
    return codes, dicts, gidx, w


@pytest.mark.parametrize("b, batches_a_step", [
    (6, 6), (6, 3), (7, 3)], ids=["one_step", "steps_divide", "steps_pad"])
@pytest.mark.parametrize("dp, code_dtype, nseg", [
    (16, np.uint8, 9), (64, np.uint8, 9), (256, np.uint8, 2),
    (256, np.uint16, 9), (1024, np.uint16, 4)],
    ids=["d16_u8", "d64_u8", "d256_u8_global", "d256_u16", "d1024_u16"])
def test_dict_space_counts_equal_bincount(monkeypatch, dp, code_dtype,
                                          nseg, b, batches_a_step):
    """counts[g, b, c] == np.bincount exactly, whatever the chunking of
    the batch axis; the sums equal the decoded path's to 1e-12."""
    import jax.numpy as jnp

    from snappydata_tpu.ops import code_agg

    ngroups = nseg - 1
    monkeypatch.setattr(
        code_agg, "DICT_SPACE_CHUNK_BYTES",
        batches_a_step * _CAP * (ngroups + dp) * 2)
    step = code_agg._chunk_batches(b, _CAP, ngroups, dp)
    assert step == batches_a_step
    assert (b % step != 0) == (b == 7)
    codes, dicts, gidx, w = _count_inputs(b, dp, code_dtype, nseg,
                                          seed=dp + b)
    got = np.asarray(code_agg.dict_space_counts(
        jnp.asarray(codes), jnp.asarray(gidx), jnp.asarray(w), nseg, dp))
    keep = w & (gidx < ngroups)
    batch = np.repeat(np.arange(b), _CAP)
    joint = (gidx.astype(np.int64) * b + batch) * dp + codes.reshape(-1)
    want = np.bincount(joint[keep], minlength=ngroups * b * dp) \
        .reshape(ngroups, b, dp)
    assert got.shape == want.shape
    assert (got == want).all()
    assert got[:, b // 2].sum() == 0          # the padded batch
    sums = np.asarray(code_agg.dict_space_sum(
        jnp.asarray(codes), jnp.asarray(dicts), jnp.asarray(gidx),
        jnp.asarray(w), nseg))
    vals = np.take_along_axis(dicts, codes.astype(np.int64), 1).reshape(-1)
    decoded = np.bincount(gidx[keep], weights=vals[keep],
                          minlength=ngroups)
    assert sums.shape == (nseg,) and sums[-1] == 0.0
    np.testing.assert_allclose(sums[:ngroups], decoded, rtol=1e-12)


def _hlo_of_main(monkeypatch, s, sql):
    """Compiled HLO text of the statement's main (or single) phase, and
    the plan's trace-time aggregate note."""
    from snappydata_tpu.engine.executor import CompiledPlan

    seen = []
    orig = CompiledPlan._noted_call

    def spy(self, static, phase, fn, args):
        seen.append((self, static, phase, fn, args))
        return orig(self, static, phase, fn, args)

    monkeypatch.setattr(CompiledPlan, "_noted_call", spy)
    s.sql(sql).rows()
    monkeypatch.setattr(CompiledPlan, "_noted_call", orig)
    plan, static, _phase, fn, args = [
        x for x in seen if x[2] in ("main", "single")][-1]
    return fn.lower(*args).compile().as_text(), plan.agg_notes[static]


def _scatters_under(hlo: str, scope: str):
    return [ln for ln in hlo.splitlines()
            if " scatter(" in ln and f"/{scope}/" in ln]


def test_q1_plan_holds_no_scatter_under_group_reduce(monkeypatch):
    """The Q1-shaped plan as the chip specializes it (float32 plates,
    the lane on, the small-G families on `unroll`): no `scatter` op is
    left under `group_reduce`.  The control is the same plan with the
    families forced onto `scatter`, where the guard finds them."""
    from snappydata_tpu.utils import tpch

    props = _props()
    saved = (props.decimal_as_float64, props.agg_reduce_strategy)
    try:
        props.decimal_as_float64 = False
        props.agg_reduce_strategy = "unroll"
        props.set("agg_on_codes", "on")
        s = SnappySession(catalog=Catalog())
        tpch.load_tpch(s, sf=0.002, seed=11)
        hlo, note = _hlo_of_main(monkeypatch, s, tpch.Q1)
        assert note["dict_space_slots"] == 2 and note["scatter_slots"] == 0
        assert "dict_space" in note["lanes"]
        assert "/group_reduce/" in hlo
        assert _scatters_under(hlo, "group_reduce") == []
        props.agg_reduce_strategy = "scatter"
        hlo, note = _hlo_of_main(monkeypatch, s, tpch.Q1)
        # the lane's two slots still count without one; the packed
        # families' do not
        assert note["dict_space_slots"] == 2 and note["scatter_slots"] > 0
        assert _scatters_under(hlo, "group_reduce")
        s.stop()
    finally:
        props.decimal_as_float64, props.agg_reduce_strategy = saved


def _gathers_under(hlo: str, scope: str):
    """Op names of `gather` primitives traced under `scope` (a fused
    gather may keep the scope on the fusion's line alone)."""
    import re

    return re.findall(rf'op_name="[^"]*/{scope}/[^"]*gather"', hlo)


@pytest.mark.parametrize("label", ["q1", "q6"])
def test_tpch_plans_hold_no_gather_under_dict_gather(monkeypatch, label):
    """Q1 `main` and Q6 at the TPC-H widths (l_discount and l_tax 16
    slots, l_quantity 64), float32 plates as on the chip: the step named
    `dict_gather` is there and holds no `gather` op.  The control is
    the same plan with the constant at 0, where the guard finds one."""
    from snappydata_tpu.storage import device_decode
    from snappydata_tpu.utils import tpch

    props = _props()
    saved = props.decimal_as_float64
    sql = {"q1": tpch.Q1, "q6": tpch.Q6}[label]
    try:
        props.decimal_as_float64 = False
        props.set("agg_on_codes", "on")
        s = SnappySession(catalog=Catalog())
        tpch.load_tpch(s, sf=0.002, seed=11)
        hlo, _ = _hlo_of_main(monkeypatch, s, sql)
        assert "/dict_gather/" in hlo
        assert _gathers_under(hlo, "dict_gather") == []
        s.stop()
        monkeypatch.setattr(device_decode, "DICT_SELECT_MAX_WIDTH", 0)
        s = SnappySession(catalog=Catalog())
        tpch.load_tpch(s, sf=0.002, seed=11)
        hlo, _ = _hlo_of_main(monkeypatch, s, sql)
        assert _gathers_under(hlo, "dict_gather")
        s.stop()
    finally:
        props.decimal_as_float64 = saved


def test_past_the_product_bound_the_slot_rides_the_packed_family():
    """8 padded groups x 16,384 codes is past DICT_SPACE_MAX_PRODUCT: the
    lane does not engage even forced on, the slot reduces with the
    packed family (counted), and the answer is the decoded one.  A
    narrow dictionary on the same table still engages."""
    from snappydata_tpu.ops import code_agg

    n = 100_000
    rng = np.random.default_rng(3)
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE wide (g BIGINT, q DOUBLE, r DOUBLE) USING column")
    s.insert_arrays("wide", [
        rng.integers(0, 6, n).astype(np.int64),
        rng.integers(0, 10_000, n).astype(np.float64) / 8,
        rng.integers(0, 40, n).astype(np.float64) / 8])
    assert not code_agg.dict_space_engages(7, (1, n), (1, 16384))
    assert code_agg.dict_space_engages(7, (1, n), (1, 64))
    assert not code_agg.dict_space_engages(
        7, (1, code_agg.DICT_SPACE_MAX_CAP + 1), (1, 64))
    c0 = _counters()
    on, off = _both(s, "SELECT g, sum(q), count(*) FROM wide "
                       "GROUP BY g ORDER BY g")
    _assert_rows_equal(on, off)
    assert _delta(c0, "agg_dict_space") == 0
    # both runs reduced through a packed family: passes were counted
    assert _delta(c0, "agg_reduce_passes") >= 2
    c0 = _counters()
    on, off = _both(s, "SELECT g, sum(r), count(*) FROM wide "
                       "GROUP BY g ORDER BY g")
    _assert_rows_equal(on, off)
    assert _delta(c0, "agg_dict_space") == 1
    s.stop()

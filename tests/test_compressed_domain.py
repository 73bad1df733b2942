"""Compressed-domain execution (r06 tentpole): predicates and aggregate
inputs evaluate directly over ENCODED batches — VALUE_DICT columns stay
resident as uint8/uint16 code plates (literals translate to code
thresholds through the sorted per-batch dictionaries), RLE columns stay
as runs (per-run predicate evaluation), bitset columns stay packed —
decoding only what survives, in-trace, fused by XLA.  Every result here
is value-asserted against the decoded path (scan_compressed_domain=off),
across encodings × NULLs × empty batches × out-of-dictionary literals ×
prepared-statement `?` binds."""

import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.storage import device_decode
from snappydata_tpu.storage.encoding import Encoding


def _props():
    return config.global_properties()


@pytest.fixture(autouse=True)
def _restore_knob():
    saved = _props().get("scan_compressed_domain")
    yield
    _props().set("scan_compressed_domain", saved)


def _mixed_session(n=60_000, with_nulls=True):
    """One table exercising every encoding: PLAIN (v), DICTIONARY
    (name), VALUE_DICT uint8 (qty), VALUE_DICT uint16 (wide),
    RUN_LENGTH (grp), BOOLEAN_BITSET (flag)."""
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE mx (k BIGINT, qty DOUBLE, wide DOUBLE, "
          "grp BIGINT, flag BOOLEAN, name STRING, v DOUBLE) USING column")
    rng = np.random.default_rng(17)
    k = np.arange(n, dtype=np.int64)
    qty = np.floor(rng.random(n) * 50) + 1.0
    wide = rng.integers(0, 5000, n).astype(np.float64) * 0.5
    grp = np.sort(rng.integers(0, 6, n)).astype(np.int64)
    flag = (k % 3 == 0)
    name = np.array([f"n{i % 7}" for i in range(n)], dtype=object)
    v = rng.random(n) * 1000
    s.insert_arrays("mx", [k, qty, wide, grp, flag, name, v])
    if with_nulls:
        # NULL rows ride the row buffer, then roll into the batch with a
        # validity mask — nulls over every compressible column
        for i in range(8):
            s.sql(f"INSERT INTO mx VALUES ({n + i}, NULL, NULL, NULL, "
                  f"NULL, NULL, {float(i)})")
    data = s.catalog.describe("mx").data
    data.force_rollover()
    return s, dict(k=k, qty=qty, wide=wide, grp=grp, flag=flag,
                   name=name, v=v), data


def _both(s, sql, params=None):
    """(compressed rows, decoded rows) of one query — the equivalence
    harness.  The knob rides the STATIC key: no cache flush between."""
    _props().set("scan_compressed_domain", "auto")
    on = s.sql(sql, params).rows() if params else s.sql(sql).rows()
    _props().set("scan_compressed_domain", "off")
    off = s.sql(sql, params).rows() if params else s.sql(sql).rows()
    _props().set("scan_compressed_domain", "auto")
    return on, off


def _assert_rows_equal(a, b):
    assert len(a) == len(b), (a, b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb), (ra, rb)
        for x, y in zip(ra, rb):
            if isinstance(x, float) and isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-12, abs=1e-12), (ra, rb)
            else:
                assert x == y, (ra, rb)


def test_encodings_at_rest_are_what_the_suite_assumes():
    s, cols, data = _mixed_session()
    m = data.snapshot()
    enc = {i: m.views[0].batch.columns[i].encoding for i in range(7)}
    assert enc[1] == Encoding.VALUE_DICT          # qty
    assert enc[2] == Encoding.VALUE_DICT          # wide (uint16)
    assert m.views[0].batch.columns[2].data.dtype == np.uint16
    assert m.views[0].batch.columns[1].data.dtype == np.uint8
    assert enc[3] == Encoding.RUN_LENGTH          # grp
    assert enc[4] == Encoding.BOOLEAN_BITSET      # flag
    assert enc[5] == Encoding.DICTIONARY          # name
    assert enc[6] == Encoding.PLAIN               # v
    s.stop()


def test_property_matrix_code_vs_decoded(decode_form):
    """The core equivalence sweep: every comparison op × in/out-of-
    dictionary/boundary literals × every encoding × NULL rows, each
    value-asserted compressed == decoded, under both forms of the
    dictionary decode (`qty` 64 slots; `wide` 8,192, the gather in
    both)."""
    s, cols, _ = _mixed_session()
    queries = []
    for op in ("=", "!=", "<", "<=", ">", ">="):
        for lit in ("24", "24.5", "-1", "999"):   # in-dict, miss, edges
            queries.append(f"SELECT count(*), sum(v) FROM mx "
                           f"WHERE qty {op} {lit}")
        queries.append(f"SELECT count(*) FROM mx WHERE wide {op} 1250.0")
        queries.append(f"SELECT count(*) FROM mx WHERE grp {op} 3")
    queries += [
        "SELECT count(*), sum(v) FROM mx WHERE qty BETWEEN 10 AND 20",
        "SELECT count(*) FROM mx WHERE qty = 10 AND grp >= 2",
        "SELECT count(*) FROM mx WHERE flag",
        "SELECT count(*) FROM mx WHERE NOT flag",
        "SELECT count(*) FROM mx WHERE name = 'n3'",
        "SELECT count(*) FROM mx WHERE name = 'absent'",
        "SELECT grp, count(*), sum(qty), min(wide), max(qty) FROM mx "
        "GROUP BY grp ORDER BY grp",
        "SELECT count(*) FROM mx WHERE qty IS NULL",
        "SELECT count(*), sum(qty) FROM mx WHERE qty IS NOT NULL",
        "SELECT sum(qty * v), avg(wide) FROM mx WHERE grp <= 4",
    ]
    for q in queries:
        on, off = _both(s, q)
        _assert_rows_equal(on, off)
    s.stop()


def test_decimal_literal_takes_the_generic_lane():
    """Exact-decimal literals (scaled-int64 representation from scalar
    subquery substitution) must NOT enter the code-compare lane — the
    threshold would be off by 10^scale."""
    s, cols, _ = _mixed_session(with_nulls=False)
    s.sql("CREATE TABLE dlim (d DECIMAL(6,2)) USING row")
    s.sql("INSERT INTO dlim VALUES (24.05)")
    q = "SELECT count(*) FROM mx WHERE qty < (SELECT max(d) FROM dlim)"
    on, off = _both(s, q)
    _assert_rows_equal(on, off)
    assert on[0][0] == int((cols["qty"] < 24.05).sum())
    s.stop()


def test_out_of_dictionary_equality_skips_batches():
    s, cols, _ = _mixed_session(with_nulls=False)
    reg = global_registry()
    c0 = reg.snapshot()["counters"].get("batches_skipped_dict", 0)
    r = s.sql("SELECT count(*) FROM mx WHERE qty = 24.5")
    assert r.rows()[0][0] == 0
    c1 = global_registry().snapshot()["counters"].get(
        "batches_skipped_dict", 0)
    assert c1 > c0, "out-of-dictionary equality must skip whole batches"
    # a string equality literal absent from the table dictionary skips
    # the whole relation the same way
    c2 = c1
    assert s.sql("SELECT count(*) FROM mx "
                 "WHERE name = 'nope'").rows()[0][0] == 0
    c3 = global_registry().snapshot()["counters"].get(
        "batches_skipped_dict", 0)
    assert c3 > c2
    s.stop()


def test_prepared_binds_take_the_same_lanes():
    """`?` binds from the PR 7 serving path: code-domain compares AND
    dictionary-domain batch skipping both read the bind value."""
    s, cols, _ = _mixed_session(with_nulls=False)
    h = s.prepare("SELECT count(*), sum(v) FROM mx WHERE qty = ?")
    qty, v = cols["qty"], cols["v"]
    for lit in (10.0, 24.5, -3.0, 50.0):
        got = h.execute((lit,)).rows()[0]
        mm = qty == lit
        assert got[0] == int(mm.sum()), (lit, got)
        if got[0]:
            assert got[1] == pytest.approx(float(v[mm].sum()))
    # range over the uint16-widened column via bind
    h2 = s.prepare("SELECT count(*) FROM mx WHERE wide >= ?")
    for lit in (0.0, 1250.0, 99999.0):
        assert h2.execute((lit,)).rows()[0][0] == \
            int((cols["wide"] >= lit).sum())
    reg = global_registry().snapshot()["counters"]
    assert reg.get("code_domain_predicates", 0) > 0
    s.stop()


def test_empty_table_and_empty_batches():
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE emx (a DOUBLE, b BIGINT) USING column")
    on, off = _both(s, "SELECT count(*), sum(a) FROM emx WHERE a < 5")
    _assert_rows_equal(on, off)
    # rows arrive, roll over, then are all deleted: batch exists, all dead
    s.insert_arrays("emx", [np.full(1000, 7.0), np.arange(1000,
                                                          dtype=np.int64)])
    s.catalog.describe("emx").data.force_rollover()
    s.sql("DELETE FROM emx WHERE b >= 0")
    on, off = _both(s, "SELECT count(*), sum(a) FROM emx WHERE a = 7.0")
    _assert_rows_equal(on, off)
    assert on[0][0] == 0
    s.stop()


def test_code_plates_stay_resident_and_small():
    """The capacity lever: a code-bound column's device-cache entry
    holds uint8 codes + a tiny dictionary, not an f64 plate."""
    from snappydata_tpu.storage.device import (build_device_table,
                                               device_cache_bytes_by_table)
    from snappydata_tpu.storage.device_decode import CodePlate

    s, cols, data = _mixed_session(with_nulls=False)
    device_decode.reset_counters()
    data._device_cache.clear()
    dt = build_device_table(data, None, [1])   # qty
    assert isinstance(dt.columns[1], CodePlate)
    assert np.dtype(dt.columns[1].codes.dtype) == np.uint8
    c = device_decode.counters()
    assert c["batches_code_bound"] >= 1
    resident = device_cache_bytes_by_table([("mx", data)])["mx"]
    rows = data.snapshot().total_rows()
    # uint8 codes + valid bitmap ≈ 2 B/row; the decoded f64 plate would
    # be 8 B/row for the column alone
    assert resident < rows * 8, (resident, rows)
    # decoded path for comparison
    _props().set("scan_compressed_domain", "off")
    data._device_cache.clear()
    build_device_table(data, None, [1])
    decoded = device_cache_bytes_by_table([("mx", data)])["mx"]
    assert decoded > resident, (decoded, resident)
    s.stop()


def test_no_implicit_transfers_on_code_domain_predicates():
    """A code-domain predicate query runs end to end without any
    IMPLICIT device↔host transfer: encoded arrays go up explicitly at
    bind, results come home through one explicit device_get — no decoded
    plate ever crosses to host."""
    import jax

    s, cols, _ = _mixed_session(with_nulls=False)
    q = ("SELECT count(*), sum(v) FROM mx "
         "WHERE qty < 24 AND grp >= 1 AND grp <= 4")
    expect = s.sql(q).rows()   # warm: compile + bind outside the guard
    with jax.transfer_guard("disallow"):
        got = s.sql(q).rows()
    _assert_rows_equal(got, expect)
    s.stop()


def test_update_deltas_and_mixed_encodings_fall_back_counted():
    s, cols, data = _mixed_session(with_nulls=False)
    reg = global_registry()
    s.sql("UPDATE mx SET qty = 3.0 WHERE k < 10")
    c0 = dict(reg.snapshot()["counters"])
    on, off = _both(s, "SELECT count(*), sum(qty) FROM mx WHERE qty = 3.0")
    _assert_rows_equal(on, off)
    c1 = reg.snapshot()["counters"]
    assert c1.get("compressed_fallback_deltas", 0) \
        > c0.get("compressed_fallback_deltas", 0)
    # a second batch with different encodings (constant qty -> RLE or
    # value-dict with different profile is fine; force PLAIN by high
    # cardinality) makes the column mixed -> counted fallback
    n2 = 40_000
    rng = np.random.default_rng(5)
    s.insert_arrays("mx", [
        np.arange(n2, dtype=np.int64) + 10_000_000,
        rng.random(n2) * 1e9,                     # qty: now PLAIN here
        rng.random(n2) * 1e9,                     # wide: PLAIN here
        rng.integers(0, 1 << 40, n2),             # grp: PLAIN here
        rng.random(n2) < 0.5,
        np.array(["zz"] * n2, dtype=object),
        rng.random(n2)])
    data.force_rollover()
    c2 = dict(reg.snapshot()["counters"])
    on, off = _both(s, "SELECT count(*) FROM mx WHERE wide >= 1250.0")
    _assert_rows_equal(on, off)
    c3 = reg.snapshot()["counters"]
    assert c3.get("compressed_fallback_mixed_encoding", 0) \
        > c2.get("compressed_fallback_mixed_encoding", 0)
    s.stop()


def test_knob_off_and_join_relations_decode():
    s, cols, data = _mixed_session(with_nulls=False)
    reg = global_registry()
    _props().set("scan_compressed_domain", "off")
    c0 = dict(reg.snapshot()["counters"])
    data._device_cache.clear()
    s.sql("SELECT count(*) FROM mx WHERE qty < 10")
    c1 = dict(reg.snapshot()["counters"])
    assert c1.get("compressed_fallback_disabled", 0) \
        > c0.get("compressed_fallback_disabled", 0)
    _props().set("scan_compressed_domain", "auto")
    # join relations bind decoded (cached build artifacts read flat
    # layouts): counted, and values still exact
    s.sql("CREATE TABLE dim (grp BIGINT, label STRING) USING column")
    s.insert_arrays("dim", [np.arange(6, dtype=np.int64),
                            np.array([f"g{i}" for i in range(6)],
                                     dtype=object)])
    got = s.sql("SELECT d.label, count(*) FROM mx m JOIN dim d "
                "ON m.grp = d.grp GROUP BY d.label ORDER BY d.label").rows()
    grp = cols["grp"]
    for label, cnt in got:
        g = int(label[1:])
        assert cnt == int((grp == g).sum()), (label, cnt)
    c2 = reg.snapshot()["counters"]
    assert c2.get("compressed_fallback_join_key", 0) > 0
    s.stop()


def test_static_key_respecializes_without_cache_flush():
    """Flipping the knob must re-specialize (different STATIC key), not
    serve a stale trace — and must not clear the plan cache."""
    s, cols, _ = _mixed_session(with_nulls=False)
    reg = global_registry()
    q = "SELECT count(*) FROM mx WHERE qty < 24"
    _props().set("scan_compressed_domain", "auto")
    r1 = s.sql(q).rows()[0][0]
    c0 = reg.snapshot()["counters"].get("plan_cache_evictions", 0)
    _props().set("scan_compressed_domain", "off")
    r2 = s.sql(q).rows()[0][0]
    _props().set("scan_compressed_domain", "auto")
    r3 = s.sql(q).rows()[0][0]
    assert r1 == r2 == r3 == int((cols["qty"] < 24).sum())
    c1 = reg.snapshot()["counters"].get("plan_cache_evictions", 0)
    assert c1 == c0, "knob flip must not evict plans"
    s.stop()


def test_rle_run_arithmetic_matches_expansion():
    """O(runs) filter/count/sum arithmetic == the expanded O(rows)
    answer: mask runs, multiply values by run lengths."""
    import jax.numpy as jnp

    from snappydata_tpu.storage.device_decode import (
        RlePlate, rle_cmp_mask, rle_masked_sum_count, rle_run_lengths,
        rle_values)

    rng = np.random.default_rng(3)
    vals = np.array([[5.0, 2.0, 9.0, 9.0], [1.0, 1.0, 1.0, 1.0]])
    ends = np.array([[10, 25, 40, 40], [7, 7, 7, 7]])  # padded runs
    plate = RlePlate(jnp.asarray(vals), jnp.asarray(ends))
    cap = 64
    expanded = np.asarray(rle_values(plate, cap))
    # run lengths: padded runs are zero-length
    lens = np.asarray(rle_run_lengths(plate.ends))
    assert lens.tolist() == [[10, 15, 15, 0], [7, 0, 0, 0]]
    run_mask = np.asarray(vals) >= 5.0
    total, count = rle_masked_sum_count(plate, jnp.asarray(run_mask))
    exp_cnt, exp_sum = 0, 0.0
    for b in range(2):
        n_real = int(ends[b, -1])
        rowvals = expanded[b, :n_real]
        m = rowvals >= 5.0
        exp_cnt += int(m.sum())
        exp_sum += float(rowvals[m].sum())
    assert int(count) == exp_cnt
    assert float(total) == pytest.approx(exp_sum)
    # per-run predicate + expansion == expanded predicate
    mask_rows = np.asarray(rle_cmp_mask(
        lambda v, lit: v >= lit, plate, jnp.asarray(5.0), cap))
    assert (mask_rows == (expanded >= 5.0)).all()


def _decode_width(width):
    """The parametrised widths name the constant as shipped."""
    c = device_decode.DICT_SELECT_MAX_WIDTH
    return {"constant": c, "past_constant": c + 1}.get(width, width)


@pytest.mark.parametrize("force", ["select", "gather", "as_shipped"])
@pytest.mark.parametrize("table_dtype", ["float32", "float64", "int64"])
@pytest.mark.parametrize("code_dtype", ["uint8", "uint16"])
@pytest.mark.parametrize("width", [1, 2, 16, 64, "constant",
                                   "past_constant"])
def test_dict_decode_is_take_along_axis_bit_for_bit(
        monkeypatch, width, code_dtype, table_dtype, force):
    """`dict_decode(dicts, codes)` is `dicts[b, codes[b, j]]` to the bit
    in either form: NaN, -0.0 and the infinities pass through, a
    dictionary padded by repeating its last value decodes as one that is
    not, a wholly padded batch (codes 0 over a row of zeros) reads 0."""
    import jax
    import jax.numpy as jnp

    dp = _decode_width(width)
    monkeypatch.setattr(device_decode, "DICT_SELECT_MAX_WIDTH",
                        {"select": dp, "gather": 0}.get(
                            force, device_decode.DICT_SELECT_MAX_WIDTH))
    want_form = {"select": device_decode.DECODE_SELECT,
                 "gather": device_decode.DECODE_GATHER}.get(
        force, device_decode.DECODE_SELECT if width != "past_constant"
        else device_decode.DECODE_GATHER)
    assert device_decode.dict_decode_form(dp) == want_form
    b, cap = 5, 384
    rng = np.random.default_rng(dp * 7 + len(code_dtype))
    dt = np.dtype(table_dtype)
    if dt.kind == "f":
        dicts = np.sort(rng.standard_normal((b, dp)), axis=1).astype(dt)
        special = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf], dtype=dt)
        for i, v in enumerate(special[:dp]):
            dicts[i % b, (i * 3) % dp] = v
    else:
        dicts = np.sort(rng.integers(-2**62, 2**62, (b, dp)), axis=1)
        dicts.flat[0], dicts.flat[-1] = np.iinfo(dt).min, np.iinfo(dt).max
    # batch 1: a short dictionary padded by repeating its last value;
    # batch 2: wholly padded
    real = max(1, dp // 3)
    dicts[1, real:] = dicts[1, real - 1]
    dicts[2, :] = 0
    hi = min(dp, np.iinfo(code_dtype).max + 1)
    codes = rng.integers(0, hi, (b, cap)).astype(code_dtype)
    codes[0, :hi] = np.arange(hi)[:cap]         # every slot met
    codes[1] = rng.integers(0, min(real, hi), cap)
    codes[2] = 0
    want = np.take_along_axis(dicts, codes.astype(np.int64), axis=1)

    def decode(d, c):       # a function of its own: jit caches by function
        return device_decode.dict_decode(d, c)

    got = np.asarray(jax.jit(decode)(jnp.asarray(dicts),
                                     jnp.asarray(codes)))
    assert got.dtype == dt and got.shape == (b, cap)
    bits = {4: np.uint32, 8: np.uint64}[dt.itemsize]
    assert (got.view(bits) == want.view(bits)).all()
    hlo = jax.jit(decode).lower(jnp.asarray(dicts),
                                jnp.asarray(codes)).as_text()
    assert ("gather" in hlo) == (want_form == device_decode.DECODE_GATHER)


def test_scan_snapshot_and_rest_surface():
    import json
    import urllib.request

    s, cols, _ = _mixed_session(with_nulls=False)
    s.sql("SELECT count(*) FROM mx WHERE qty < 24")
    from snappydata_tpu.observability.stats_service import (encoding_mix,
                                                            scan_snapshot)

    snap = scan_snapshot(s.catalog)
    assert snap["scan_compressed_domain"] == "auto"
    assert snap["code_domain_predicates"] > 0
    assert snap["batches_code_bound"] > 0
    assert "compressed_fallback_reasons" in snap
    mx = snap["tables"]["mx"]
    assert mx["encoding_mix"].get("VALUE_DICT", 0) >= 2
    assert mx["at_rest_bytes"] < mx["decoded_bytes"]
    assert mx["resident_bytes_per_row"] is not None
    mix = encoding_mix(s.catalog)["mx"]
    assert mix["at_rest_ratio"] < 1.0
    # REST endpoint carries the same block
    from snappydata_tpu.cluster.rest import RestService
    from snappydata_tpu.observability.stats_service import \
        TableStatsService

    srv = RestService(s, TableStatsService(s.catalog), port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/status/api/v1/scan",
                timeout=10) as resp:
            body = json.loads(resp.read())
        assert body["code_domain_predicates"] > 0
        assert "tables" in body and "mx" in body["tables"]
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/dashboard",
                timeout=10) as resp:
            html = resp.read().decode()
        assert "Scan &amp; decode" in html
    finally:
        srv.stop()
    s.stop()


def test_bench_check_guards_compressed_axes():
    import bench

    base = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 5},
        "compressed": {"code_domain_predicates": 9,
                       "resident_bytes_per_row": 10.0}}}
    good = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 7},
        "compressed": {"code_domain_predicates": 4,
                       "resident_bytes_per_row": 11.0}}}
    assert bench.check_regression(good, base) == []
    dead = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 0},
        "compressed": {"code_domain_predicates": 0,
                       "resident_bytes_per_row": 10.0}}}
    fails = bench.check_regression(dead, base)
    assert any("batches_device_decoded" in f for f in fails)
    assert any("code_domain_predicates" in f for f in fails)
    fat = {"value": 1e6, "detail": {
        "load_s": 10,
        "device_decode": {"batches_device_decoded": 5},
        "compressed": {"code_domain_predicates": 9,
                       "resident_bytes_per_row": 40.0}}}
    assert any("resident_bytes_per_row" in f
               for f in bench.check_regression(fat, base))
    # records predating the section stay comparable (no spurious fails)
    old = {"value": 1e6, "detail": {"load_s": 10}}
    assert bench.check_regression(old, base) == []

"""Materialized-view subsystem (views/matview.py): definition
validation, O(delta) maintenance by folding ingest batches through the
compiled partial program, exact subtraction on deletes for invertible
slot families, staleness for the rest, bucket-ladder state growth,
WAL-fenced durability, broker ledger accounting, and the REST surface.
"""

import gc
import json
import urllib.request

import numpy as np
import pytest

from snappydata_tpu import SnappySession
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.views import MatViewError, matviews, view_snapshot

pytestmark = pytest.mark.views


def _counter(name: str) -> int:
    return global_registry().counter(name)


def _mk(rows=True):
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE base (k INT, name STRING, v DOUBLE, n BIGINT) "
          "USING column")
    if rows:
        s.insert("base", (1, "a", 1.5, 10), (1, "b", 2.5, 20),
                 (2, "a", 10.0, 30), (3, None, 4.0, 40))
    return s


def _rows(s, sql):
    return s.sql(sql).rows()


# -- definition / lifecycle ----------------------------------------------

def test_create_read_fold_basic():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv, "
          "count(*) AS c, sum(n) AS sn FROM base GROUP BY k")
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [
        (1, 4.0, 2, 30), (2, 10.0, 1, 30), (3, 4.0, 1, 40)]
    f0 = _counter("view_delta_folds")
    r0 = _counter("view_full_refreshes")
    s.insert("base", (2, "z", 5.0, 5), (4, "q", 7.0, 7))
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [
        (1, 4.0, 2, 30), (2, 15.0, 2, 35), (3, 4.0, 1, 40),
        (4, 7.0, 1, 7)]
    assert _counter("view_delta_folds") == f0 + 1
    assert _counter("view_full_refreshes") == r0, \
        "a delta append must fold, not rescan"
    # the view backing table composes with the normal engine
    assert _rows(s, "SELECT sum(sv) FROM mv WHERE k <= 2") == [(19.0,)]
    s.stop()


def test_create_over_empty_table_grouped_and_global():
    s = _mk(rows=False)
    s.sql("CREATE MATERIALIZED VIEW g AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    s.sql("CREATE MATERIALIZED VIEW tot AS SELECT count(*) AS c, "
          "sum(v) AS sv FROM base")
    assert _rows(s, "SELECT * FROM g") == []
    # global aggregate over nothing: match the ENGINE's own semantics
    # (view read ≡ re-running the aggregate; this engine says sum()=0.0
    # over zero rows, count 0)
    assert _rows(s, "SELECT * FROM tot") == \
        _rows(s, "SELECT count(*), sum(v) FROM base")
    s.insert("base", (1, "a", 2.0, 1), (1, "a", 3.0, 2))
    assert _rows(s, "SELECT * FROM g") == [(1, 2)]
    assert _rows(s, "SELECT * FROM tot") == [(2, 5.0)]
    s.stop()


def test_duplicate_name_and_if_not_exists():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    with pytest.raises(ValueError, match="already exists"):
        s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
              "FROM base GROUP BY k")
    s.sql("CREATE MATERIALIZED VIEW IF NOT EXISTS mv AS "
          "SELECT k, count(*) AS c FROM base GROUP BY k")   # no-op
    # name collisions with tables/views are refused both ways
    with pytest.raises(ValueError, match="already exists"):
        s.sql("CREATE MATERIALIZED VIEW base AS SELECT k, count(*) AS c "
              "FROM base GROUP BY k")
    with pytest.raises(ValueError):
        s.sql("CREATE TABLE mv (x INT) USING column")
    s.stop()


def test_drop_frees_ledgered_state_bytes():
    from snappydata_tpu.resource.broker import global_broker

    gc.collect()
    led0 = global_broker().ledger()["matview_state_bytes"]
    s = _mk()
    s.insert_arrays("base", [
        np.arange(5000, dtype=np.int32) % 512,
        np.array(["x"] * 5000, dtype=object),
        np.ones(5000), np.ones(5000, dtype=np.int64)])
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    led1 = global_broker().ledger()["matview_state_bytes"]
    assert led1 > led0, "view state must appear in the broker ledger"
    snap = view_snapshot(s.catalog)
    assert snap["view_state_bytes"] > 0
    s.sql("DROP MATERIALIZED VIEW mv")
    led2 = global_broker().ledger()["matview_state_bytes"]
    assert led2 <= led0, "DROP must free the ledgered bytes immediately"
    assert _rows(s, "SELECT count(*) FROM base")[0][0] == 5004
    with pytest.raises(ValueError, match="not found"):
        s.sql("DROP MATERIALIZED VIEW mv")
    s.sql("DROP MATERIALIZED VIEW IF EXISTS mv")   # no-op
    s.stop()


def test_unsupported_definitions_raise():
    s = _mk()
    s.sql("CREATE TABLE other (k INT, w DOUBLE) USING column")
    for ddl, why in [
        ("SELECT k FROM base", "aggregate"),
        ("SELECT k, count(*) c FROM base GROUP BY k ORDER BY k",
         "ORDER BY"),
        ("SELECT DISTINCT k FROM base", ""),
        ("SELECT k, count(DISTINCT name) c FROM base GROUP BY k",
         "DISTINCT"),
        ("SELECT b.k, count(*) c FROM base b JOIN other o ON b.k = o.k "
         "GROUP BY b.k", "single-relation"),
        ("SELECT k, min(name) m FROM base GROUP BY k", "string"),
    ]:
        with pytest.raises((MatViewError, ValueError)):
            s.sql(f"CREATE MATERIALIZED VIEW bad AS {ddl}")
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    with pytest.raises(MatViewError, match="materialized views"):
        s.sql("CREATE MATERIALIZED VIEW mv2 AS SELECT k, sum(c) AS s "
              "FROM mv GROUP BY k")
    s.stop()


def test_view_writes_and_ddl_rejected():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    with pytest.raises(ValueError, match="materialized view"):
        s.sql("INSERT INTO mv VALUES (9, 9)")
    with pytest.raises(ValueError, match="materialized view"):
        s.insert("mv", (9, 9))
    with pytest.raises(ValueError, match="materialized view"):
        s.sql("UPDATE mv SET c = 0 WHERE k = 1")
    with pytest.raises(ValueError, match="materialized view"):
        s.sql("DELETE FROM mv WHERE k = 1")
    with pytest.raises(ValueError, match="materialized view"):
        s.sql("TRUNCATE TABLE mv")
    with pytest.raises(ValueError, match="MATERIALIZED"):
        s.sql("DROP TABLE mv")
    with pytest.raises(ValueError, match="materialized view"):
        s.sql("ALTER TABLE mv ADD COLUMN x INT")
    s.stop()


# -- delta folding -------------------------------------------------------

def test_fold_all_new_vs_all_existing_groups():
    s = _mk(rows=False)
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(n) AS sn "
          "FROM base GROUP BY k")
    mv = matviews(s.catalog)["mv"]
    s.insert_arrays("base", [
        np.arange(100, dtype=np.int32),
        np.array(["a"] * 100, dtype=object),
        np.ones(100), np.arange(100, dtype=np.int64)])
    s.sql("SELECT * FROM mv")
    snap1 = mv.snapshot()
    assert snap1["groups"] == 100
    regrow1 = _counter("view_state_regrows")
    # all-EXISTING groups: state must not regrow, values must merge
    s.insert_arrays("base", [
        np.arange(100, dtype=np.int32),
        np.array(["b"] * 100, dtype=object),
        np.ones(100), np.full(100, 1000, dtype=np.int64)])
    got = _rows(s, "SELECT sum(sn) FROM mv")
    assert got == [(int(np.arange(100).sum()) + 100 * 1000,)]
    assert mv.snapshot()["groups"] == 100
    assert _counter("view_state_regrows") == regrow1
    # all-NEW groups: group space doubles through the bucket ladder
    s.insert_arrays("base", [
        np.arange(100, 300, dtype=np.int32),
        np.array(["c"] * 200, dtype=object),
        np.ones(200), np.ones(200, dtype=np.int64)])
    assert _rows(s, "SELECT count(*) FROM mv") == [(300,)]
    snap3 = mv.snapshot()
    assert snap3["groups"] == 300
    assert _counter("view_state_regrows") > regrow1
    # capacity follows the {2^k, 1.5*2^k} ladder
    cap = snap3["capacity"]
    assert cap in (512, 384), cap
    s.stop()


def test_null_group_keys_and_null_values_fold():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT name, sum(v) AS sv, "
          "count(v) AS cv, count(*) AS c FROM base GROUP BY name")
    base = _rows(s, "SELECT name, sum(v), count(v), count(*) FROM base "
                    "GROUP BY name ORDER BY name")
    assert sorted(_rows(s, "SELECT * FROM mv"),
                  key=lambda r: (r[0] is not None, r[0])) == \
        sorted(base, key=lambda r: (r[0] is not None, r[0]))
    s.insert("base", (7, None, None, 1))   # NULL key AND NULL value
    got = {r[0]: r for r in _rows(s, "SELECT * FROM mv")}
    assert got[None][2] == 1 and got[None][3] == 2   # count(v) skips NULL
    assert got[None][1] == 4.0
    s.stop()


def test_avg_and_having_views():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, avg(v) AS av "
          "FROM base GROUP BY k HAVING count(*) > 1")
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [(1, 2.0)]
    s.insert("base", (2, "x", 20.0, 0))
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [(1, 2.0),
                                                       (2, 15.0)]
    s.stop()


def test_delete_subtraction_exact_f64_int64():
    s = _mk(rows=False)
    rng = np.random.default_rng(5)
    k = (np.arange(4000, dtype=np.int32) % 16)
    v = rng.integers(0, 1 << 40, 4000).astype(np.float64)  # f64-exact ints
    n = rng.integers(-(1 << 50), 1 << 50, 4000)
    s.insert_arrays("base", [k, np.array(["s"] * 4000, dtype=object), v, n])
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv, "
          "sum(n) AS sn, count(*) AS c, count(v) AS cv "
          "FROM base GROUP BY k")
    sub0 = _counter("view_subtract_folds")
    r0 = _counter("view_full_refreshes")
    s.sql("DELETE FROM base WHERE k >= 8")
    assert _counter("view_subtract_folds") == sub0 + 1
    keep = k < 8
    expect = sorted(
        (int(g), float(v[keep & (k == g)].sum()),
         int(n[keep & (k == g)].sum()), int((keep & (k == g)).sum()),
         int((keep & (k == g)).sum()))
        for g in range(8))
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [
        tuple(e) for e in expect]
    assert _counter("view_full_refreshes") == r0, \
        "subtractable delete must not rescan"
    # fully-deleted groups drop out exactly like a re-aggregation
    s.sql("DELETE FROM base WHERE k = 3")
    assert _rows(s, "SELECT count(*) FROM mv") == [(7,)]
    s.stop()


def test_minmax_delete_marks_stale_then_recovers_by_rescan():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, max(v) AS mx, "
          "min(n) AS mn FROM base GROUP BY k")
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [
        (1, 2.5, 10), (2, 10.0, 30), (3, 4.0, 40)]
    # inserts still fold incrementally (max merges)
    f0 = _counter("view_delta_folds")
    s.insert("base", (1, "z", 9.0, 5))
    assert _rows(s, "SELECT mx, mn FROM mv WHERE k = 1") == [(9.0, 5)]
    assert _counter("view_delta_folds") == f0 + 1
    # a delete cannot un-see the max: stale → next read re-aggregates
    st0 = _counter("view_stale_marks")
    r0 = _counter("view_full_refreshes")
    s.sql("DELETE FROM base WHERE v = 9.0")
    assert _counter("view_stale_marks") == st0 + 1
    assert matviews(s.catalog)["mv"].stale
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [
        (1, 2.5, 10), (2, 10.0, 30), (3, 4.0, 40)]
    assert _counter("view_full_refreshes") == r0 + 1
    assert not matviews(s.catalog)["mv"].stale
    s.stop()


def test_update_and_keyed_put_mark_stale():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    s.sql("SELECT * FROM mv")
    st0 = _counter("view_stale_marks")
    s.sql("UPDATE base SET v = v + 1 WHERE k = 1")
    assert _counter("view_stale_marks") == st0 + 1
    assert _rows(s, "SELECT sv FROM mv WHERE k = 1") == [(6.0,)]
    s.stop()


def test_column_put_upsert_stays_fresh_and_exact():
    from snappydata_tpu import types as T

    s = SnappySession(catalog=Catalog())
    s.catalog.create_table(
        "kv", T.Schema([T.Field("id", T.LONG, False),
                        T.Field("v", T.DOUBLE, True)]),
        "column", {}, key_columns=("id",))
    s.put_arrays("kv", [np.arange(10, dtype=np.int64),
                        np.ones(10)])
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT count(*) AS c, "
          "sum(v) AS sv FROM kv")
    assert _rows(s, "SELECT * FROM mv") == [(10, 10.0)]
    r0 = _counter("view_full_refreshes")
    # upsert: 5 replaced (subtract+fold), 5 new (fold)
    s.put_arrays("kv", [np.arange(5, 15, dtype=np.int64),
                        np.full(10, 3.0)])
    assert _rows(s, "SELECT * FROM mv") == [(15, 5 * 1.0 + 10 * 3.0)]
    assert _counter("view_full_refreshes") == r0, \
        "column-table PUT should fold exactly, not rescan"
    s.stop()


def test_truncate_resets_view():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    s.sql("SELECT * FROM mv")
    s.sql("TRUNCATE TABLE base")
    assert _rows(s, "SELECT * FROM mv") == []
    s.insert("base", (5, "a", 1.0, 1))
    assert _rows(s, "SELECT * FROM mv") == [(5, 1)]
    s.stop()


def test_alter_base_marks_stale_and_rebinds():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    s.sql("SELECT * FROM mv")
    s.sql("ALTER TABLE base ADD COLUMN extra DOUBLE")
    assert matviews(s.catalog)["mv"].stale
    s.insert("base", (1, "n", 1.0, 1, 8.5))
    assert _rows(s, "SELECT sv FROM mv WHERE k = 1") == [(5.0,)]
    assert not matviews(s.catalog)["mv"].stale
    s.stop()


def test_drop_base_table_cascades():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    s.sql("DROP TABLE base")
    assert "mv" not in matviews(s.catalog)
    from snappydata_tpu.sql.analyzer import AnalysisError

    with pytest.raises(AnalysisError, match="not found"):
        s.sql("SELECT * FROM mv")
    s.stop()


def test_refresh_statement_and_eviction():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    r0 = _counter("view_full_refreshes")
    s.sql("REFRESH MATERIALIZED VIEW mv")
    assert _counter("view_full_refreshes") == r0 + 1
    with pytest.raises(ValueError, match="not found"):
        s.sql("REFRESH MATERIALIZED VIEW nope")
    # broker degradation evicts state → stale → one rescan at next read
    from snappydata_tpu.views.matview import evict_all_states

    assert evict_all_states() > 0
    assert matviews(s.catalog)["mv"].stale
    assert _rows(s, "SELECT * FROM mv ORDER BY k") == [
        (1, 4.0), (2, 10.0), (3, 4.0)]
    assert _counter("view_full_refreshes") == r0 + 2
    s.stop()


def test_streaming_sink_folds_deltas():
    """Kafka → exactly-once sink → keyless column table: every sink
    batch folds O(delta) into dependent views (the dashboard-over-
    streaming-ingest scenario the subsystem exists for)."""
    from snappydata_tpu import types as T
    from snappydata_tpu.streaming.kafka import InProcessBroker, KafkaSource
    from snappydata_tpu.streaming.query import StreamingQuery

    s = SnappySession(catalog=Catalog())
    schema = T.Schema([T.Field("id", T.LONG, False),
                       T.Field("v", T.DOUBLE, True)])
    s.catalog.create_table("ev_t", schema, "column", {})
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT count(*) AS c, "
          "sum(v) AS sv FROM ev_t")
    f0 = _counter("view_delta_folds")
    r0 = _counter("view_full_refreshes")
    broker = InProcessBroker(num_partitions=2)
    broker.produce("ev", [{"id": i, "v": float(i)} for i in range(5000)])
    src = KafkaSource(s, "q", broker, "ev", ["id", "v"],
                      max_records_per_batch=1000)
    q = StreamingQuery(s, "q", src, "ev_t")
    q.process_available()
    assert _rows(s, "SELECT * FROM mv") == [(5000, float(sum(range(5000))))]
    assert _counter("view_delta_folds") > f0, "sink batches must fold"
    assert _counter("view_full_refreshes") == r0, "and never rescan"
    s.stop()


# -- durability ----------------------------------------------------------

def test_recovery_replays_only_the_tail_no_double_fold(tmp_path):
    d = str(tmp_path)
    s = SnappySession(catalog=Catalog(), data_dir=d, recover=False)
    s.sql("CREATE TABLE t (k INT, v DOUBLE) USING column")
    s.insert("t", (1, 1.0), (2, 2.0))
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv, "
          "count(*) AS c FROM t GROUP BY k")
    # checkpoint persists the state at fence W...
    s.checkpoint()
    # ...then a tail past the fence that must re-fold EXACTLY once
    s.insert("t", (1, 10.0), (3, 30.0))
    s.stop()
    s.disk_store.close()

    rp0 = _counter("view_replay_folds")
    rf0 = _counter("view_full_refreshes")
    s2 = SnappySession(data_dir=d, recover=True)
    mv = matviews(s2.catalog)["mv"]
    assert not mv.stale, "checkpointed state + tail replay, no rescan"
    assert _counter("view_replay_folds") == rp0 + 1
    assert _rows(s2, "SELECT * FROM mv ORDER BY k") == [
        (1, 11.0, 2), (2, 2.0, 1), (3, 30.0, 1)]
    assert _counter("view_full_refreshes") == rf0, \
        "recovery must not full-rescan a fenced view"
    # and equals a cold full refresh of the same definition
    assert _rows(s2, "SELECT k, sum(v), count(*) FROM t GROUP BY k "
                     "ORDER BY k") == [(1, 11.0, 2), (2, 2.0, 1),
                                       (3, 30.0, 1)]
    s2.stop()
    s2.disk_store.close()

    # recovery is idempotent: boot again → identical view state
    s3 = SnappySession(data_dir=d, recover=True)
    assert _rows(s3, "SELECT * FROM mv ORDER BY k") == [
        (1, 11.0, 2), (2, 2.0, 1), (3, 30.0, 1)]
    s3.stop()
    s3.disk_store.close()


def test_drop_base_cascade_removes_persisted_state(tmp_path):
    import os

    d = str(tmp_path)
    s = SnappySession(catalog=Catalog(), data_dir=d, recover=False)
    s.sql("CREATE TABLE t (k INT) USING column")
    s.insert("t", (1,))
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM t GROUP BY k")
    spath = os.path.join(d, "views", "mv.state")
    assert os.path.exists(spath)
    s.sql("DROP TABLE t")
    assert not os.path.exists(spath), "cascade must drop durable state"
    assert "mv" not in getattr(s.catalog, "_matview_ddl", {})
    s.stop()
    s.disk_store.close()
    s2 = SnappySession(data_dir=d, recover=True)
    assert "mv" not in matviews(s2.catalog)
    s2.stop()
    s2.disk_store.close()


def test_drop_removes_persisted_state(tmp_path):
    import os

    d = str(tmp_path)
    s = SnappySession(catalog=Catalog(), data_dir=d, recover=False)
    s.sql("CREATE TABLE t (k INT) USING column")
    s.insert("t", (1,))
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, count(*) AS c "
          "FROM t GROUP BY k")
    spath = os.path.join(d, "views", "mv.state")
    assert os.path.exists(spath)
    s.sql("DROP MATERIALIZED VIEW mv")
    assert not os.path.exists(spath)
    s.stop()
    s.disk_store.close()
    s2 = SnappySession(data_dir=d, recover=True)
    assert "mv" not in matviews(s2.catalog)
    assert s2.catalog.lookup_table("mv") is None
    s2.stop()
    s2.disk_store.close()


# -- observability -------------------------------------------------------

def test_view_snapshot_and_rest_endpoint():
    from snappydata_tpu.cluster.rest import RestService
    from snappydata_tpu.observability.stats_service import \
        TableStatsService

    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    s.insert("base", (9, "x", 1.0, 1))
    s.sql("SELECT * FROM mv")
    snap = view_snapshot(s.catalog)
    assert [v["name"] for v in snap["views"]] == ["mv"]
    v = snap["views"][0]
    assert v["base_table"] == "base" and v["groups"] == 4
    assert v["delta_folds"] >= 1 and not v["stale"]
    assert snap["view_delta_folds"] >= 1
    svc = RestService(s, TableStatsService(s.catalog), port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://{svc.host}:{svc.port}/status/api/v1/views",
                timeout=5) as resp:
            body = json.loads(resp.read())
        assert [w["name"] for w in body["views"]] == ["mv"]
        assert {"view_delta_folds", "view_rows_folded",
                "view_full_refreshes", "view_state_bytes"} <= set(body)
        with urllib.request.urlopen(
                f"http://{svc.host}:{svc.port}/dashboard",
                timeout=5) as resp:
            html = resp.read().decode()
        assert "Materialized views" in html and "mv" in html
    finally:
        svc.stop()
        s.stop()


# -- bench guard (satellite: geomean/load_s cannot silently slide) -------

def test_bench_check_guard_logic():
    import bench

    base = {"value": 100.0, "detail": {"load_s": 30.0}}
    assert bench.check_regression(
        {"value": 90.0, "detail": {"load_s": 33.0}}, base) == []
    fails = bench.check_regression(
        {"value": 50.0, "detail": {"load_s": 30.0}}, base)
    assert len(fails) == 1 and "geomean" in fails[0]
    fails = bench.check_regression(
        {"value": 100.0, "detail": {"load_s": 120.0}}, base)
    assert len(fails) == 1 and "load_s" in fails[0]
    # both slide → both reported
    assert len(bench.check_regression(
        {"value": 10.0, "detail": {"load_s": 500.0}}, base)) == 2
    # missing fields are tolerated (a failed bench run has nulls)
    assert bench.check_regression(
        {"value": None, "detail": {}}, base) == []


# the two historical driver records the guard was built on (CPU runs;
# shape as the driver wrote them: the bench's own line under "parsed")
_R04 = {"n": 4, "rc": 0, "parsed": {
    "value": 17299257.0, "detail": {"platform": "cpu", "load_s": 30.6}}}
_R05 = {"n": 5, "rc": 0, "parsed": {
    "value": 15133431.4, "detail": {"platform": "cpu", "load_s": 119.79}}}


def test_bench_check_catches_the_recorded_r05_slide():
    """The guard trips on exactly the regression the r04→r05 records
    carried (load_s 30.6→119.8) and passes the in-tolerance geomean
    wobble."""
    import bench

    fails = bench.check_regression(_R05, _R04)
    assert any("load_s" in f for f in fails)
    assert not any("geomean" in f for f in fails), \
        "the -12.7% geomean wobble is within the noise tolerance"


# -- review-fix regressions ----------------------------------------------

def test_repeated_delete_does_not_double_subtract():
    """A DELETE predicate that re-matches already-deleted rows must not
    subtract them from dependent views a second time (the storage
    intersects with its live mask AFTER the predicate runs; the capture
    wrapper has to apply the same mask)."""
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE t (k INT, v DOUBLE) USING column")
    s.insert("t", (1, 5.0), (1, 7.0), (2, 3.0))
    s.sql("CREATE MATERIALIZED VIEW dd AS SELECT k, sum(v) AS sv, "
          "count(*) AS c FROM t GROUP BY k")
    assert s.sql("DELETE FROM t WHERE v = 5.0").rows() == [(1,)]
    assert s.sql("DELETE FROM t WHERE v = 5.0").rows() == [(0,)]
    assert _rows(s, "SELECT * FROM dd ORDER BY k") == [
        (1, 7.0, 1), (2, 3.0, 1)]
    # same shape on a row table (separate live-mask plumbing)
    s.sql("CREATE TABLE r (k INT, v DOUBLE) USING row")
    s.insert("r", (1, 5.0), (1, 7.0))
    s.sql("CREATE MATERIALIZED VIEW ddr AS SELECT k, sum(v) AS sv, "
          "count(*) AS c FROM r GROUP BY k")
    s.sql("DELETE FROM r WHERE v = 5.0")
    s.sql("DELETE FROM r WHERE v = 5.0")
    assert _rows(s, "SELECT * FROM ddr") == [(1, 7.0, 1)]
    s.stop()


def test_refresh_accepts_schema_qualified_name():
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW q AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    s.sql("REFRESH MATERIALIZED VIEW app.q")  # _norm, not .lower()
    assert _rows(s, "SELECT count(*) FROM q") == [(3,)]
    s.stop()


def test_ctas_and_mutation_subqueries_see_fresh_view():
    """Reads that do not go through ast.Query (CTAS source, UPDATE/DELETE
    WHERE subqueries) must sync referenced views too."""
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW f AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    s.insert("base", (9, "z", 1.0, 1))   # fold marks dirty, no read yet
    s.sql("CREATE TABLE snap AS SELECT * FROM f")
    assert (9, 1.0) in _rows(s, "SELECT * FROM snap")
    s.sql("CREATE TABLE pick (k INT) USING column")
    s.insert("pick", (9,), (50,))
    s.insert("base", (50, "y", 2.0, 2))  # dirty again
    assert s.sql("DELETE FROM pick WHERE k IN "
                 "(SELECT k FROM f)").rows() == [(2,)]
    s.stop()


def test_state_nbytes_is_metadata_only():
    """The ledger/metrics gauge must not force a device→host copy of the
    view state (it runs on the admission hot path)."""
    import jax

    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW nb AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    mv = matviews(s.catalog)["nb"]
    with jax.transfer_guard("disallow"):
        assert mv.state_nbytes() > 0
    s.stop()


def test_stale_view_read_races_concurrent_committers():
    """Regression for the sync()/fold lock-order inversion: readers of a
    stale view (view refresh takes mutation_lock → view lock) must not
    deadlock against committers (mutation_lock → view lock via fold)."""
    import threading

    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW race AS SELECT k, count(*) AS c "
          "FROM base GROUP BY k")
    mv = matviews(s.catalog)["race"]
    stop = threading.Event()
    errs = []

    def writer():
        try:
            while not stop.is_set():
                s.insert("base", (7, "w", 1.0, 1))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def reader():
        try:
            for _ in range(20):
                mv.mark_stale("test")  # force the refresh_full path
                s.sql("SELECT count(*) FROM race")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    w = threading.Thread(target=writer, daemon=True)
    r = threading.Thread(target=reader, daemon=True)
    w.start(); r.start()
    r.join(timeout=120)
    alive = r.is_alive()
    stop.set()
    w.join(timeout=30)
    assert not alive and not w.is_alive(), "reader/writer deadlocked"
    assert not errs, errs
    s.stop()


def test_unmanaged_direct_write_marks_stale_not_diverges():
    """A raw data-layer insert (bench loaders, embedders poking storage
    directly) bypasses the WAL and the fold hook — the guard must mark
    dependent views stale so the next read re-aggregates instead of
    serving rows the view never folded."""
    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW uw AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    u0 = _counter("view_unmanaged_writes")
    s.catalog.describe("base").data.insert_arrays(
        [np.array([9], dtype=np.int32), np.array(["x"], dtype=object),
         np.array([2.5]), np.array([1], dtype=np.int64)])
    assert matviews(s.catalog)["uw"].stale
    assert _counter("view_unmanaged_writes") == u0 + 1
    rows = _rows(s, "SELECT * FROM uw WHERE k = 9")
    assert rows == [(9, 2.5)], rows
    # managed inserts never trip the guard
    u1 = _counter("view_unmanaged_writes")
    s.insert("base", (9, "y", 1.0, 1))
    assert _counter("view_unmanaged_writes") == u1
    assert not matviews(s.catalog)["uw"].stale
    s.stop()


def test_recovery_base_rows_mismatch_degrades_to_stale(tmp_path):
    """View state checkpointed over unjournaled base rows must come up
    STALE after a crash (the WAL can never replay those rows) — correct
    answers via one re-aggregation, never the divergent fast path."""
    dirn = str(tmp_path / "store")
    s = SnappySession(data_dir=dirn)
    s.sql("CREATE TABLE t (k INT, v DOUBLE) USING column")
    s.catalog.describe("t").data.insert_arrays(
        [np.arange(100, dtype=np.int32) % 4, np.ones(100)])  # no WAL
    s.sql("CREATE MATERIALIZED VIEW rm AS SELECT k, sum(v) AS sv, "
          "count(*) AS c FROM t GROUP BY k")
    s.insert("t", (0, 5.0))
    s2 = SnappySession(data_dir=dirn)   # crash-shape reopen
    view = _rows(s2, "SELECT * FROM rm ORDER BY k")
    base = _rows(s2, "SELECT k, sum(v), count(*) FROM t GROUP BY k "
                     "ORDER BY k")
    assert view == base, (view, base)
    s2.stop()
    s.stop()


def test_flight_do_put_into_backing_table_refused():
    """Flight bulk ingest must refuse a view's backing table like every
    other write lane — acked rows there would vanish at the next sync."""
    from snappydata_tpu.cluster.client import SnappyClient
    from snappydata_tpu.cluster.flight_server import SnappyFlightServer
    import threading

    s = _mk()
    s.sql("CREATE MATERIALIZED VIEW fp AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")
    srv = SnappyFlightServer(s)
    threading.Thread(target=srv.serve, daemon=True).start()
    srv.wait_ready()
    try:
        c = SnappyClient(f"127.0.0.1:{srv.actual_port}")
        with pytest.raises(Exception, match="materialized view"):
            c.insert("fp", {"k": np.array([9], dtype=np.int32),
                            "sv": np.array([1.0])})
        # the view still serves the maintained state
        assert _rows(s, "SELECT * FROM fp WHERE k = 9") == []
        c.close()
    finally:
        srv.shutdown()
        s.stop()


def test_row_table_null_delete_capture_exact():
    """NULL contributions in deleted row-table rows must not be
    subtracted as values (the typed delete-predicate arrays coerce None
    to NaN/0 — the capture needs the null masks)."""
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE rt (g INT, id INT, v DOUBLE) USING row")
    s.sql("CREATE MATERIALIZED VIEW rn AS SELECT g, sum(v) AS sv, "
          "count(*) AS c FROM rt GROUP BY g")
    s.sql("INSERT INTO rt VALUES (1, 1, NULL)")
    s.sql("INSERT INTO rt VALUES (1, 2, 3.0)")
    s.sql("DELETE FROM rt WHERE id = 1")
    assert _rows(s, "SELECT * FROM rn") == [(1, 3.0, 1)]
    # deleting the only non-null contribution: view must keep matching
    # a cold re-aggregation exactly (engine semantics, whatever they
    # are for the all-NULL group, are the oracle)
    s.sql("INSERT INTO rt VALUES (2, 3, NULL)")
    s.sql("INSERT INTO rt VALUES (2, 4, 7.0)")
    s.sql("DELETE FROM rt WHERE id = 4")
    cold = _rows(s, "SELECT g, sum(v), count(*) FROM rt GROUP BY g "
                    "ORDER BY g")
    assert _rows(s, "SELECT * FROM rn ORDER BY g") == cold
    s.stop()


def test_create_failure_rolls_back_registration():
    """A failed initial refresh must not leave a half-created view that
    blocks the retried CREATE."""
    from unittest import mock

    from snappydata_tpu.views.matview import MaterializedView

    s = _mk()
    with mock.patch.object(MaterializedView, "refresh_full",
                           side_effect=RuntimeError("injected")):
        with pytest.raises(RuntimeError, match="injected"):
            s.sql("CREATE MATERIALIZED VIEW cf AS SELECT k, sum(v) AS sv "
                  "FROM base GROUP BY k")
    assert "cf" not in matviews(s.catalog)
    assert s.catalog.lookup_table("cf") is None
    s.sql("CREATE MATERIALIZED VIEW cf AS SELECT k, sum(v) AS sv "
          "FROM base GROUP BY k")   # retry succeeds
    assert len(_rows(s, "SELECT * FROM cf")) == 3
    s.stop()


def test_bench_check_candidate_is_newest_record(tmp_path, monkeypatch):
    """--check <newest BENCH_r*.json> must compare against its
    PREDECESSOR, not against itself (always-pass)."""
    import bench

    for rec in (_R04, _R05):
        with open(tmp_path / f"BENCH_r{rec['n']:02d}.json", "w") as fh:
            json.dump(rec, fh)
    real_records = bench._bench_records
    monkeypatch.setattr(bench, "_bench_records",
                        lambda _root: real_records(str(tmp_path)))
    records = bench._bench_records(None)
    # r05 carries the recorded load_s regression vs r04: checking it by
    # path (as CI would check a just-written record) must compare it
    # against r04 and trip — a self-compare would always pass
    r05 = str(tmp_path / "BENCH_r05.json")
    assert r05 in records
    assert bench.run_check([r05]) == 1

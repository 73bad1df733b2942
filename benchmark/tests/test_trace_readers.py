"""The readers and entries PR 25 added: a span attr as a median or a
sum, summed self time per statement of a kind, and the nine metrics that
use them, on hand-built span trees; the rule that a program which
records the evidence reads a number (0 included) and one that does not
reads None; the manifest with the new entries.

    python -m pytest benchmark/tests -q
"""

import json
import os

import pytest

import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ["xla_compiles_in_window.scan", "xla_compiles_in_window.served",
       "rebind_upload_mb", "rebind_upload_ms", "put_decode_ms",
       "wal_append_ms.rf1", "apply_ms.rf1", "wal_sync_ms.rf1",
       "rollover_ms_per_rf1"]


def sp(name, ms, *children, **attrs):
    out = {"name": name, "ms": float(ms)}
    if attrs:
        out["attrs"] = attrs
    if children:
        out["children"] = list(children)
    return out


def stmt(kind, *roots, name=None, ok=True):
    return {"name": name or kind, "kind": kind, "ok": ok, "ms": 1.0,
            "traces": [{"kind": k, "root": r} for k, r in roots]}


def query(upload_bytes=16, upload_ms=0.3, compiles=0, bind_compiles=None):
    b = {"upload_bytes": upload_bytes, "upload_ms": upload_ms}
    if bind_compiles:
        b["xla_compiles"] = bind_compiles
    return stmt("query", ("client", sp("request", 120)), ("server", sp(
        "request", 100, sp("parse", 1), sp("bind", 10, **b),
        sp("device_execute", 2, xla_compiles=compiles),
        sp("transfer", 80, wait_ms=79.0, copy_ms=0.5, bytes=8))),
        name="q6")


def put(decode, append, apply_ms, sync, rollover=None):
    kids = [sp("rollover", rollover, rows=9000, batches_cut=1)] \
        if rollover is not None else []
    return ("server", sp(
        "request", decode + append + apply_ms + sync + 1.0,
        sp("decode", decode, rows=3000, bytes=1 << 20),
        sp("wal_append", append, bytes=1 << 19),
        sp("apply", apply_ms, *kids, rows=3000),
        sp("wal_sync", sync, forced=True)))


def rf1(orders, lines):
    return stmt("insert_rows", ("client", sp("request", 1)), orders,
                ("client", sp("request", 1)), lines, name="rf1")


def window():
    """Two refresh cycles: the second RF1 rolls the orders buffer over;
    the first query after each write uploads, the others do not."""
    return [
        rf1(put(4, 6, 20, 3), put(16, 24, 300, 9)),
        query(upload_bytes=50_000_000, upload_ms=40.0),
        query(), query(),
        stmt("delete_range", ("server", sp("request", 30, sp("apply", 20))),
             name="rf2"),
        query(upload_bytes=30_000_000, upload_ms=20.0, bind_compiles=2),
        query(),
        rf1(put(4, 6, 3020, 3, rollover=3000), put(16, 24, 300, 9)),
        query(upload_bytes=70_000_000, upload_ms=60.0, compiles=1),
        query(),
    ]


def ctx(statements):
    return {"statements": statements, "back": "server", "front": "client"}


def test_manifest_takes_the_new_entries():
    m = manifest.Manifest(ROOT)
    assert manifest.problems(m) == []
    names = [p["name"] for p in m.doc["per_layer"]]
    # in this order, wherever later PRs put theirs
    assert [n for n in names if n in NEW] == NEW
    for n in NEW:
        cells = next(p for p in m.doc["per_layer"]
                     if p["name"] == n)["workloads"]
        assert len(cells) == 1
        assert n in [x["name"] for x in m.metrics_of(cells[0],
                                                     "per_layer")]
    refresh = [x["name"] for x in
               m.metrics_of("tpch_sf2.refresh", "per_layer")]
    assert set(NEW[1:]) <= set(refresh) and NEW[0] not in refresh


def test_new_metrics_on_a_hand_built_window():
    m = manifest.Manifest(ROOT)
    c = ctx(window())
    got = {n: m.read(n, c) for n in NEW}
    # compiles: 2 under a bind, 1 under a dispatch, wherever they sit
    assert got["xla_compiles_in_window.served"] == 3
    assert got["xla_compiles_in_window.scan"] == 3     # the same reader
    # first query after a write: 50, 30 and 70 MB; 40, 20, 60 ms
    assert got["rebind_upload_mb"] == pytest.approx(50.0)
    assert got["rebind_upload_ms"] == pytest.approx(40.0)
    # span_self_ms is per put: orders and lineitem puts of both RF1s
    assert got["put_decode_ms"] == pytest.approx(10.0)     # 4,4,16,16
    assert got["wal_append_ms.rf1"] == pytest.approx(15.0)
    assert got["wal_sync_ms.rf1"] == pytest.approx(6.0)
    # apply's self time leaves the roll-over out: 20, 20, 300, 300
    assert got["apply_ms.rf1"] == pytest.approx(160.0)
    # one roll-over of 3,000 ms over two RF1s
    assert got["rollover_ms_per_rf1"] == pytest.approx(1500.0)


def test_a_quiet_window_reads_zero_not_none():
    m = manifest.Manifest(ROOT)
    quiet = [rf1(put(4, 6, 20, 3), put(16, 24, 300, 9)),
             query(upload_bytes=0, upload_ms=0.0), query()]
    c = ctx(quiet)
    for n in NEW:
        v = m.read(n, c)
        assert v is not None and v >= 0, n
    assert m.read("xla_compiles_in_window.served", c) == 0
    assert m.read("rollover_ms_per_rf1", c) == 0.0
    assert m.read("rebind_upload_mb", c) == 0.0


def test_a_program_without_the_evidence_reads_none_and_does_not_raise():
    """The parent's trees: no attrs on bind/transfer/dispatch, a put's
    server trace with nothing under its root."""
    m = manifest.Manifest(ROOT)
    old_query = stmt("query", ("client", sp("request", 120)),
                     ("server", sp("request", 100, sp("parse", 1),
                                   sp("bind", 10), sp("device_execute", 2),
                                   sp("transfer", 80))), name="q6")
    old_rf1 = stmt("insert_rows", ("client", sp("request", 1)),
                   ("server", sp("request", 300)), name="rf1")
    c = ctx([old_rf1, old_query, old_query])
    for n in NEW:
        assert m.read(n, c) is None, n
    # and statements that carry no trace at all (a --trace 0 log)
    bare = [{"name": "q6", "kind": "query", "ok": True, "ms": 1.0}]
    for n in NEW:
        assert m.read(n, ctx(bare)) is None, n


def test_failed_statements_and_the_clients_side_are_left_out():
    m = manifest.Manifest(ROOT)
    bad = query(compiles=5)
    bad["ok"] = False
    mine = stmt("query", ("client", sp("request", 5, sp(
        "device_execute", 1, xla_compiles=7))), name="q6")
    c = ctx([bad, mine, query(compiles=1)])
    assert m.read("xla_compiles_in_window.served", c) == 1


def test_span_attr_filters():
    m = manifest.Manifest(ROOT)
    read = m.module("readers", "span_attr").read
    c = ctx(window())
    # `names` narrows the spans, `kinds` the statements
    assert read(c, "xla_compiles", names=["bind"], stat="sum") == 2
    assert read(c, "rows", names=["decode"], kinds=["insert_rows"],
                stat="sum") == 12000
    assert read(c, "wait_ms", names=["transfer"], kinds=["query"]) == 79.0
    assert read(c, "no_such_attr") is None


def test_metric_files_say_what_the_manifest_says():
    m = manifest.Manifest(ROOT)
    for n in NEW:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               n + ".json")) as f:
            mf = json.load(f)
        entry = next(p for p in m.doc["per_layer"] if p["name"] == n)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert mf[key] == entry[key], (n, key)
        assert mf["reader"] in ("span_attr", "span_self_ms",
                                "span_self_ms_per_stmt")

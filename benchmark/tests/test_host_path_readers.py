"""The sixteen metrics of a statement's host path: the root's time under
no span (`root_self_ms`), the front door beyond the server's root
(`frontdoor_ms`), the steps' self times and the collector's pauses, on
hand-built span trees; numbers where the program opens the spans, None
where it does not (the parent), and the manifest with the entries.

    python -m pytest benchmark/tests -q
"""

import json
import os

import pytest

import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = {"scan": "tpch_sf2.scan", "quickstart": "quickstart_100m.groupby",
         "served": "tpch_sf2.refresh"}
NEW = ["unspanned_ms.scan", "unspanned_ms.quickstart", "unspanned_ms.served",
       "admit_ms.scan", "admit_ms.quickstart", "plan_lookup_ms.scan",
       "plan_lookup_ms.quickstart", "launch_ms.scan", "launch_ms.quickstart",
       "finish_ms.scan", "finish_ms.quickstart", "encode_ms.served",
       "frontdoor_ms.served", "gc_ms.scan", "gc_ms.quickstart",
       "gc_ms.served"]


def sp(name, start, ms, *children, **attrs):
    out = {"name": name, "start_ms": float(start), "ms": float(ms)}
    if attrs:
        out["attrs"] = attrs
    if children:
        out["children"] = list(children)
    return out


def statement_root(gc_ms=0.0, hole=0.1, encode=False):
    """A warm statement's root of 10 ms: its children tile it but for
    `hole` ms after `bind`; the collector paused `gc_ms` of it."""
    kids = [sp("parse", 0.0, 1.0), sp("admit", 1.0, 0.5),
            sp("optimize", 1.5, 0.5), sp("analyze", 2.0, 1.0),
            sp("plan_lookup", 3.0, 0.5), sp("bind", 3.5, 1.0 - hole),
            sp("device_execute", 4.5, 0.5, xla_compiles=0),
            sp("transfer", 5.0, 3.0), sp("finish", 8.0, 1.0 if encode
                                         else 2.0)]
    if encode:
        kids.append(sp("encode", 9.0, 1.0))
    return sp("request", 0.0, 10.0, *kids, gc_ms=gc_ms)


def parent_root():
    """The same statement on a program without the steps: holes where
    the steps would be, and no `gc_ms`."""
    return sp("request", 0.0, 10.0, sp("parse", 0.0, 1.0),
              sp("optimize", 1.5, 0.5), sp("analyze", 2.0, 1.0),
              sp("bind", 3.5, 1.0), sp("device_execute", 4.5, 0.5),
              sp("transfer", 5.0, 3.0))


def embedded(kind, root):
    return {"name": kind, "kind": kind, "ok": True, "ms": root["ms"],
            "traces": [{"kind": "session", "trace_id": "t",
                        "root": root}]}


def served(root, client_ms, tid="a"):
    return {"name": "q6", "kind": "query", "ok": True, "ms": client_ms,
            "traces": [{"kind": "client", "trace_id": tid,
                        "root": sp("request", 0.0, client_ms, sp(
                            "flight_sql", 0.1, client_ms - 0.2))},
                       {"kind": "server", "trace_id": tid, "root": root}]}


def ctx(statements, entry="embedded"):
    front, back = ("session", "session") if entry == "embedded" \
        else ("client", "server")
    return {"statements": statements, "front": front, "back": back}


def read_all(m, c, suffix):
    return {n: m.read(n, c) for n in NEW if n.endswith("." + suffix)}


def test_manifest_takes_the_sixteen_metrics():
    m = manifest.Manifest(ROOT)
    assert manifest.problems(m) == []
    entries = {p["name"]: p for p in m.doc["per_layer"]}
    names = [p["name"] for p in m.doc["per_layer"]]
    # appended, in this order, after every metric that was there
    assert names[-len(NEW):] == NEW
    for n in NEW:
        e = entries[n]
        cell = CELLS[n.rsplit(".", 1)[1]]
        assert e["workloads"] == [cell]
        assert e["source"] == "program_span" and e["unit"] == "ms"
        assert e["moves"] == ("stmt_p50_ms" if cell == "tpch_sf2.refresh"
                              else "query_rows_per_s")
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               n + ".json")) as f:
            mf = json.load(f)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert mf[key] == e[key], (n, key)
        assert n in [x["name"] for x in m.metrics_of(cell, "per_layer")]


def test_embedded_cells_read_the_steps():
    m = manifest.Manifest(ROOT)
    scan = [embedded("query", statement_root(gc_ms=g, hole=h))
            for g, h in ((0.0, 0.1), (150.0, 0.2), (0.0, 0.3))]
    got = read_all(m, ctx(scan), "scan")
    assert got["unspanned_ms.scan"] == pytest.approx(0.2)
    assert got["admit_ms.scan"] == pytest.approx(0.5)
    assert got["plan_lookup_ms.scan"] == pytest.approx(0.5)
    assert got["launch_ms.scan"] == pytest.approx(0.5)
    assert got["finish_ms.scan"] == pytest.approx(2.0)
    # a sum over the window, where a median would read 0
    assert got["gc_ms.scan"] == pytest.approx(150.0)
    # the quick-start cell reads its own kind alone
    assert all(v is None for v in read_all(m, ctx(scan),
                                           "quickstart").values())
    qs = [dict(r, kind="query_set") for r in scan]
    got = read_all(m, ctx(qs), "quickstart")
    assert got["unspanned_ms.quickstart"] == pytest.approx(0.2)
    assert got["gc_ms.quickstart"] == pytest.approx(150.0)


def test_a_quiet_window_reads_zero_not_none():
    m = manifest.Manifest(ROOT)
    full = sp("request", 0.0, 10.0, *statement_root(hole=0.0)["children"],
              gc_ms=0)
    got = read_all(m, ctx([embedded("query", full)]), "scan")
    assert got["unspanned_ms.scan"] == 0.0
    assert got["gc_ms.scan"] == 0


def test_the_served_cell_reads_the_servers_trace_and_the_front_door():
    m = manifest.Manifest(ROOT)
    window = [served(statement_root(gc_ms=3.0, encode=True), 12.5, "a"),
              served(statement_root(encode=True), 13.0, "b"),
              served(statement_root(encode=True), 14.5, "c")]
    got = read_all(m, ctx(window, "flight"), "served")
    assert got["unspanned_ms.served"] == pytest.approx(0.1)
    assert got["encode_ms.served"] == pytest.approx(1.0)
    assert got["frontdoor_ms.served"] == pytest.approx(3.0)   # 13 - 10
    assert got["gc_ms.served"] == pytest.approx(3.0)
    # the client's side of a statement is never read as the server's
    client_only = [dict(r, traces=r["traces"][:1]) for r in window]
    assert all(v is None for v in
               read_all(m, ctx(client_only, "flight"), "served").values())
    # a front door pairs the two roots by trace id
    crossed = [served(statement_root(encode=True), 12.0, "a")]
    crossed[0]["traces"][1]["trace_id"] = "other"
    assert m.read("frontdoor_ms.served", ctx(crossed, "flight")) is None
    # an entry whose front door is the system itself has none
    assert m.read("frontdoor_ms.served", ctx(window)) is None


def test_a_program_without_the_spans_reads_none_and_does_not_raise():
    """The parent's trees: no steps, no `encode`, no `gc_ms`; the launch
    was a span there already."""
    m = manifest.Manifest(ROOT)
    old = [embedded("query", parent_root()) for _ in range(3)]
    got = read_all(m, ctx(old), "scan")
    assert got.pop("launch_ms.scan") == pytest.approx(0.5)
    assert got == {n: None for n in got}
    old_served = [served(parent_root(), 14.0) for _ in range(3)]
    got = read_all(m, ctx(old_served, "flight"), "served")
    assert got == {n: None for n in got}
    # and a log with no trace at all (a --trace 0 run)
    bare = [{"name": "q6", "kind": "query", "ok": True, "ms": 1.0}]
    for suffix in CELLS:
        assert all(v is None for v in read_all(m, ctx(bare),
                                               suffix).values())


def test_failed_statements_are_left_out():
    m = manifest.Manifest(ROOT)
    bad = embedded("query", statement_root(gc_ms=99.0, hole=5.0))
    bad["ok"] = False
    good = embedded("query", statement_root(hole=0.1))
    got = read_all(m, ctx([bad, good]), "scan")
    assert got["unspanned_ms.scan"] == pytest.approx(0.1)
    assert got["gc_ms.scan"] == 0


def test_overlapping_children_are_not_covered_twice():
    root_self = manifest.Manifest(ROOT).module("readers", "root_self_ms")
    # two parallel legs 1..6 and 2..8 cover 1..8; a child past the root
    # is clipped to it (9.5..10): 0..1 and 8..9.5 are left
    root = sp("request", 0.0, 10.0, sp("member", 1.0, 5.0),
              sp("member", 2.0, 6.0), sp("finish", 9.5, 3.0))
    assert root_self._uncovered_ms(root) == pytest.approx(2.5)

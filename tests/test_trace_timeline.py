"""The trace as a timeline (PR 25): spans carry their start, sit in the
JAX profiler's host trace on the profiler's clock, count the XLA
compiles that happen under them whatever they are called, and cover the
write path (decode / wal_append / apply / rollover / wal_sync); the
compiled plans and their operators carry names into the HLO.

Everything runs on the CPU backend in seconds: values, nesting and
counts — never a device time.
"""

import glob
import os
import re
import threading

import jax
import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability import tracing
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.observability.tracing import Span, Trace

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def _restore_knobs():
    props = config.global_properties()
    keys = ("tracing_enabled", "trace_ring_entries", "column_batch_rows",
            "column_max_delta_rows", "decimal_as_float64")
    saved = {k: getattr(props, k) for k in keys}
    props.tracing_enabled = True
    yield props
    for k, v in saved.items():
        setattr(props, k, v)


def _mk_session(n: int = 20000) -> SnappySession:
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE t (k INT, g STRING, v DOUBLE) USING column")
    rng = np.random.default_rng(0)
    s.insert_arrays("t", [
        np.arange(n, dtype=np.int32),
        np.array(["a", "b", "c"], dtype=object)[rng.integers(0, 3, n)],
        rng.random(n)])
    return s


def _walk(span: dict, parent=None):
    yield span, parent
    for c in span.get("children", ()):
        yield from _walk(c, span)


GROUPED = "SELECT g, sum(v), count(*) FROM t WHERE k < 15000 GROUP BY g"


# ----------------------------------------------------------------------
# A. a span is a timeline entry
# ----------------------------------------------------------------------

def test_start_ms_children_inside_parent_and_siblings_ordered():
    s = _mk_session()
    for _ in range(2):      # cold (compile spans) and warm
        s.sql(GROUPED).rows()
        root = tracing.ring().last().to_dict()["root"]
        assert root["start_ms"] == 0.0
        n = 0
        for sp, parent in _walk(root):
            n += 1
            assert "start_ms" in sp and sp["start_ms"] >= 0.0
            if parent is not None:
                eps = 0.01      # both ends are rounded to 1e-4 ms
                assert sp["start_ms"] >= parent["start_ms"] - eps
                assert sp["start_ms"] + sp["ms"] <= \
                    parent["start_ms"] + parent["ms"] + eps
            sibs = sp.get("children", [])
            for a, b in zip(sibs, sibs[1:]):
                # one thread: a sibling starts after the one before ends
                assert b["start_ms"] >= a["start_ms"] + a["ms"] - 0.01
        assert n >= 6


def _span(name, t0, dur, *children) -> Span:
    sp = Span(name)
    sp._t0, sp.duration_s = t0, dur
    sp.children = list(children)
    return sp


def test_phase_seconds_is_self_time_and_sums_to_covered_time():
    tr = Trace("q", "u", "session")
    # root 0..10; a 1..5 holds b 2..3 and b 3..4.5; c 6..9 holds two
    # PARALLEL legs (a fan-out) 6..8 and 7..9
    tr.root._t0, tr.root.duration_s = 0.0, 10.0
    tr.root.children = [
        _span("a", 1.0, 4.0, _span("b", 2.0, 1.0), _span("b", 3.0, 1.5)),
        _span("c", 6.0, 3.0, _span("leg", 6.0, 2.0),
              _span("leg", 7.0, 2.0)),
    ]
    ph = tr.phase_seconds()
    assert ph["a"] == pytest.approx(1.5)       # 4 less 2.5 of b
    assert ph["b"] == pytest.approx(2.5)
    assert ph["c"] == pytest.approx(0.0)       # the legs cover all of it
    assert ph["leg"] == pytest.approx(4.0)
    # a sequential tree: the phases sum to what the root's children cover
    seq = Trace("q", "u", "session")
    seq.root._t0, seq.root.duration_s = 0.0, 10.0
    seq.root.children = [
        _span("a", 1.0, 4.0, _span("b", 2.0, 1.0), _span("b", 3.0, 1.5)),
        _span("c", 6.0, 3.0, _span("d", 6.5, 1.0)),
    ]
    assert sum(seq.phase_seconds().values()) == pytest.approx(7.0)
    assert seq.root.self_seconds() == pytest.approx(3.0)
    assert seq.to_dict()["phases_ms"]["a"] == pytest.approx(1500.0)
    # a span still open is skipped, and takes nothing out of its parent
    seq.root.children[1].children[0].duration_s = None
    assert seq.phase_seconds()["c"] == pytest.approx(3.0)


def test_real_trace_phases_sum_to_covered_time():
    s = _mk_session()
    s.sql(GROUPED).rows()
    tr = tracing.ring().last()
    covered = tr.root.duration_s - tr.root.self_seconds()
    assert sum(tr.phase_seconds().values()) == pytest.approx(covered,
                                                            rel=1e-6)
    assert covered <= tr.duration_s


# ----------------------------------------------------------------------
# A. the profiler's host trace holds the same spans, on its clock
# ----------------------------------------------------------------------

def _snappy_events(trace_dir: str) -> dict:
    """thread line -> [(name, start_ns, dur_ns, stats)] of `snappy:*`
    events of the host plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(tracing.PROFILE_PREFIX):
                    out.setdefault(ln.name, []).append(
                        (ev.name, float(ev.start_ns),
                         float(ev.duration_ns), dict(ev.stats)))
    return out


def test_profiler_host_plane_holds_one_event_per_span(tmp_path):
    s = _mk_session()
    s.sql(GROUPED).rows()           # compiled before the session opens
    assert not tracing._profiling()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing._profiling()
        s.sql(GROUPED).rows()
    finally:
        jax.profiler.stop_trace()
    tr = tracing.ring().last().to_dict()
    lines = _snappy_events(str(tmp_path))
    assert len(lines) == 1, lines.keys()      # the statement's thread
    evs = sorted(next(iter(lines.values())), key=lambda e: e[1])
    spans = sorted(((sp, parent) for sp, parent in _walk(tr["root"])),
                   key=lambda sp: sp[0]["start_ms"])
    # one event per span, same names in the same order of start
    assert [e[0] for e in evs] == \
        [tracing.PROFILE_PREFIX + sp["name"] for sp, _ in spans]
    root_ev = evs[0]
    assert root_ev[3]["trace_id"] == tr["trace_id"]
    assert root_ev[3]["kind"] == "session"
    for (name, start, dur, _), (sp, parent) in zip(evs, spans):
        # durations: within 0.2 ms + 10 % (the annotation opens just
        # before the span and closes just after it)
        assert dur / 1e6 == pytest.approx(sp["ms"], rel=0.10, abs=0.2), \
            (name, dur / 1e6, sp["ms"])
        # same clock: offsets from the root agree with start_ms
        assert (start - root_ev[1]) / 1e6 == pytest.approx(
            sp["start_ms"], abs=0.2), name
        # same nesting: every event lies inside its parent's event
        if parent is not None:
            pe = evs[[s_ for s_, _ in spans].index(parent)]
            assert pe[1] - 1e3 <= start and \
                start + dur <= pe[1] + pe[2] + 1e3, name


def test_no_profiler_session_no_annotation():
    with tracing.request_scope("x", kind="session") as tr:
        cm = tracing.span("probe")
        with cm as sp:
            assert isinstance(sp, Span)
        assert cm._ann is None
    assert tr is not None


# ----------------------------------------------------------------------
# B. XLA compiles are counted where they happen
# ----------------------------------------------------------------------

def _attr_sum(root: dict, key: str):
    return sum(sp.get("attrs", {}).get(key, 0) for sp, _ in _walk(root))


def test_recompile_inside_dispatch_is_counted(_restore_knobs):
    props = _restore_knobs
    props.column_batch_rows = 1024
    props.column_max_delta_rows = 256
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE grow (k INT, v DOUBLE) USING column")
    rng = np.random.default_rng(1)

    def insert(n):
        s.insert_arrays("grow", [np.arange(n, dtype=np.int32),
                                 rng.random(n)])

    def query() -> dict:
        s.sql("SELECT sum(v) FROM grow WHERE k < 900").rows()
        return tracing.ring().last().to_dict()["root"]

    insert(2048)                    # two batches: bucket 2
    cold = query()
    names = [sp["name"] for sp, _ in _walk(cold)]
    assert "jit_compile" in names and _attr_sum(cold, "xla_compiles") >= 1
    assert _attr_sum(query(), "xla_compiles") == 0
    reg = global_registry()
    c0, t0 = reg.counter("xla_compiles"), reg.counter("jit_retraces")
    insert(1024)                    # three batches: the next bucket
    grown = query()
    names = [sp["name"] for sp, _ in _walk(grown)]
    # the static key did not change, so the executor calls the dispatch
    # `device_execute` — and XLA compiled inside it all the same
    assert "jit_compile" not in names and "compile" not in names
    dispatch = [sp for sp, _ in _walk(grown)
                if sp["name"] == "device_execute"]
    assert len(dispatch) == 1
    assert dispatch[0]["attrs"]["xla_compiles"] >= 1
    assert dispatch[0]["attrs"]["xla_compile_ms"] > 0
    assert dispatch[0]["attrs"]["retraces"] >= 1
    assert reg.counter("xla_compiles") - c0 >= 1
    assert reg.counter("jit_retraces") - t0 >= 1
    assert reg.snapshot()["timers"]["xla_compile"]["count"] >= 1
    after = query()
    assert _attr_sum(after, "xla_compiles") == 0
    # the dispatch span always says so itself: 0, not absent
    assert [sp["attrs"]["xla_compiles"] for sp, _ in _walk(after)
            if sp["name"] == "device_execute"] == [0]


def test_bind_and_transfer_carry_their_evidence():
    s = _mk_session()
    s.sql(GROUPED).rows()
    cold = {sp["name"]: sp for sp, _ in
            _walk(tracing.ring().last().to_dict()["root"])}
    assert cold["bind"]["attrs"]["plates_built"] == 3
    assert cold["bind"]["attrs"]["upload_bytes"] > 20000 * 8
    assert cold["bind"]["attrs"]["upload_ms"] > 0
    s.sql(GROUPED).rows()
    root = tracing.ring().last().to_dict()["root"]
    warm = {sp["name"]: sp for sp, _ in _walk(root)}
    b = warm["bind"]["attrs"]
    assert b["plates_built"] == 0 and b["plates_cached"] == 3
    assert b["upload_bytes"] < 1024        # the bound literal only
    t = warm["transfer"]
    assert set(("wait_ms", "copy_ms", "bytes")) <= set(t["attrs"])
    assert t["attrs"]["wait_ms"] + t["attrs"]["copy_ms"] <= t["ms"] + 0.01
    assert t["attrs"]["bytes"] > 0
    # evidence is attrs: nothing was opened beneath the spans the
    # benchmark's readers take self time of
    for name in ("parse", "analyze", "optimize", "bind", "transfer"):
        assert "children" not in warm[name], name


# ----------------------------------------------------------------------
# B. the put path
# ----------------------------------------------------------------------

def _serve(session):
    from snappydata_tpu.cluster import SnappyClient
    from snappydata_tpu.cluster.flight_server import SnappyFlightServer

    server = SnappyFlightServer(session, "127.0.0.1", 0)
    threading.Thread(target=server.serve, daemon=True).start()
    server.wait_ready(timeout=10)
    return server, SnappyClient(address=f"127.0.0.1:{server.actual_port}")


def _server_trace_of_last_put() -> dict:
    client = [t for t in tracing.ring().traces(20)
              if t["kind"] == "client"][0]
    both = tracing.ring().get(client["trace_id"])
    server = [t for t in both if t["kind"] == "server"]
    assert len(server) == 1, [t["kind"] for t in both]
    return server[0]


def test_flight_put_is_covered_by_its_spans(tmp_path, _restore_knobs):
    props = _restore_knobs
    props.column_max_delta_rows = 5000
    s = SnappySession(catalog=Catalog(), data_dir=str(tmp_path))
    s.sql("CREATE TABLE p (k BIGINT, name STRING, v DOUBLE) USING column")
    server, client = _serve(s)
    try:
        def put(n, base):
            client.insert("p", {
                "k": np.arange(base, base + n, dtype=np.int64),
                "name": np.array([f"n{i % 5000}" for i in range(n)],
                                 dtype=object),
                "v": np.random.default_rng(base).random(n)})
            return _server_trace_of_last_put()

        # a put that cuts its batches directly (n >= max_delta_rows)
        tr = put(200_000, 0)
        by = {sp["name"]: sp for sp, _ in _walk(tr["root"])}
        for name in ("decode", "wal_append", "apply", "wal_sync"):
            assert name in by, (name, sorted(by))
        assert by["decode"]["attrs"]["rows"] == 200_000
        assert by["decode"]["attrs"]["bytes"] > 200_000 * 16
        assert by["wal_append"]["attrs"]["bytes"] > 200_000   # framed, packed
        assert by["apply"]["attrs"]["rows"] == 200_000
        assert by["wal_sync"]["attrs"]["forced"] is True
        assert "rollover" not in by
        assert "lock_wait_ms" in tr["root"]["attrs"]
        ph = tr["phases_ms"]
        covered = sum(ph[n] for n in ("decode", "wal_append", "apply",
                                      "wal_sync"))
        assert covered >= 0.90 * tr["root"]["ms"], (ph, tr["root"]["ms"])
        # the four phases follow one another on the timeline
        starts = [by[n]["start_ms"] for n in
                  ("decode", "wal_append", "apply", "wal_sync")]
        assert starts == sorted(starts)

        # small puts land in the row buffer; the one that fills it rolls
        # it over, and the roll-over is a child of `apply`
        put(3000, 1_000_000)
        tr = put(3000, 2_000_000)
        apply_sp = [sp for sp, _ in _walk(tr["root"])
                    if sp["name"] == "apply"][0]
        roll = [c for c in apply_sp.get("children", ())
                if c["name"] == "rollover"]
        assert len(roll) == 1, apply_sp
        assert roll[0]["attrs"]["rows"] == 6000
        assert roll[0]["attrs"]["batches_cut"] == 1
        assert roll[0]["attrs"]["dict_entries_copied"] == 5000
        assert tr["phases_ms"]["rollover"] > 0
        assert s.sql("SELECT count(*) FROM p").rows()[0][0] == 206_000
    finally:
        client.close()
        server.shutdown()
        s.stop()


def test_journaled_sql_statement_uses_the_same_span_names(tmp_path):
    s = SnappySession(catalog=Catalog(), data_dir=str(tmp_path))
    s.sql("CREATE TABLE j (k INT, v DOUBLE) USING column")
    s.insert_arrays("j", [np.arange(100, dtype=np.int32),
                          np.arange(100, dtype=np.float64)])
    s.sql("DELETE FROM j WHERE k < 10")
    tr = tracing.ring().last().to_dict()
    by = {sp["name"]: sp for sp, _ in _walk(tr["root"])}
    for name in ("wal_append", "apply", "wal_sync"):
        assert name in by, sorted(by)
    assert by["apply"]["attrs"]["rows"] == 10
    assert by["wal_append"]["attrs"]["bytes"] > 0
    assert by["wal_sync"]["attrs"]["forced"] is False
    assert "lock_wait_ms" in tr["root"]["attrs"]
    s.stop()


# ----------------------------------------------------------------------
# C. names on the device side
# ----------------------------------------------------------------------

def test_q1_q6_hlo_carries_plan_and_operator_names(monkeypatch,
                                                   _restore_knobs):
    from snappydata_tpu.engine.executor import CompiledPlan
    from snappydata_tpu.utils import tpch

    # the chip's dtype policy: float32 plates keep their dictionaries on
    # the device, so the per-row dictionary gather is in the plan
    _restore_knobs.decimal_as_float64 = False
    seen = []
    orig = CompiledPlan._noted_call

    def spy(self, static, phase, fn, args):
        seen.append((phase, fn, args))
        return orig(self, static, phase, fn, args)

    monkeypatch.setattr(CompiledPlan, "_noted_call", spy)
    s = SnappySession(catalog=Catalog())
    tpch.load_tpch(s, sf=0.002, seed=7)
    found = {}
    for label, sql in (("q1", tpch.Q1), ("q6", tpch.Q6)):
        seen.clear()
        s.sql(sql).rows()
        assert seen, label
        for phase, fn, args in list(seen):
            hlo = fn.lower(*args).compile().as_text()
            module = hlo.split("\n", 1)[0].split()[1].rstrip(",")
            assert module.startswith("jit_snappy_"), module
            assert "unnamed" not in module
            ops = set(re.findall(r'op_name="([^"]*)"', hlo))
            assert any(n.startswith("jit(snappy_") for n in ops)
            scopes = {sc for sc in tracing.OP_SCOPES
                      if any(f"/{sc}/" in n for n in ops)}
            found[(label, phase)] = (module, scopes)
    assert found[("q1", "pre")][0] == "jit_snappy_agg_pre"
    assert found[("q1", "main")][0] == "jit_snappy_agg_main"
    assert found[("q6", "single")][0] == "jit_snappy_global_agg"
    assert {"filter", "group_index"} <= found[("q1", "pre")][1]
    assert {"dict_gather", "group_reduce"} <= found[("q1", "main")][1]
    assert {"filter", "dict_gather", "group_reduce"} <= \
        found[("q6", "single")][1]


def test_op_scope_takes_names_of_the_list_only():
    assert "dict_gather" in tracing.OP_SCOPES
    with tracing.op_scope("filter"):
        pass
    with pytest.raises(ValueError):
        tracing.op_scope("fliter")


# ----------------------------------------------------------------------
# D. the main dispatch says how its aggregate slots reduced (PR 26)
# ----------------------------------------------------------------------

def _main_dispatch(root: dict) -> dict:
    """The statement's main dispatch span: `phase` "main", or the
    single-phase span, which carries no phase."""
    found = [sp for sp, _ in _walk(root)
             if sp["name"] in ("device_execute", "jit_compile")
             and sp.get("attrs", {}).get("phase", "main") == "main"]
    assert len(found) == 1, [sp["name"] for sp, _ in _walk(root)]
    return found[0]


@pytest.mark.parametrize("label, lane, strategy, want", [
    ("q1", "on", "auto", (2, 0)),
    ("q6", "on", "auto", (0, 0)),
    ("q1", "off", "auto", (0, 0)),
    # Q1's nine slots: two in dictionary space, three float sums and
    # four counts through the packed families
    ("q1", "on", "scatter", (2, 7)),
], ids=["q1_lane", "q6", "q1_lane_off", "q1_families_on_scatter"])
def test_main_dispatch_carries_slot_counts(_restore_knobs, label, lane,
                                           strategy, want):
    """`dict_space_slots` / `scatter_slots` on the main dispatch span,
    cold (`jit_compile`) and warm (`device_execute`), 0 where none."""
    from snappydata_tpu.utils import tpch

    props = _restore_knobs
    props.decimal_as_float64 = False
    saved = (props.get("agg_on_codes"), props.agg_reduce_strategy)
    try:
        props.set("agg_on_codes", lane)
        props.agg_reduce_strategy = strategy
        s = SnappySession(catalog=Catalog())
        tpch.load_tpch(s, sf=0.002, seed=7)
        sql = {"q1": tpch.Q1, "q6": tpch.Q6}[label]
        for expect_name in ("jit_compile", "device_execute"):
            s.sql(sql).rows()
            sp = _main_dispatch(tracing.ring().last().to_dict()["root"])
            assert sp["name"] == expect_name
            assert (sp["attrs"]["dict_space_slots"],
                    sp["attrs"]["scatter_slots"]) == want
            # Q1's two dictionary keys: no run-head group index, no runs
            assert sp["attrs"]["gidx_run_lane"] == 0
            assert sp["attrs"]["run_reduce_slots"] == 0
        s.stop()
    finally:
        props.set("agg_on_codes", saved[0])
        props.agg_reduce_strategy = saved[1]


def _bench_manifest():
    """The benchmark's own manifest (and its directory), sound."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import manifest

    m = manifest.Manifest(root)
    assert manifest.problems(m) == []
    return m, bench


def test_slot_metrics_read_the_attrs():
    """The two metric files PR 26 added, through the benchmark's own
    manifest and `span_attr` reader on hand-built trees: a median over
    the window's queries; None, not an error, on a program that does
    not set the attrs."""
    import json

    m, bench = _bench_manifest()
    names = ["dict_space_slots.scan", "scatter_slots.scan"]
    listed = [p["name"] for p in m.doc["per_layer"]]
    assert [n for n in listed if n in names] == names
    for n in names:
        entry = next(p for p in m.doc["per_layer"] if p["name"] == n)
        assert entry["workloads"] == ["tpch_sf2.scan"]
        with open(os.path.join(bench, "metrics", n + ".json")) as f:
            mf = json.load(f)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert mf[key] == entry[key], (n, key)
        assert mf["reader"] == "span_attr"

    def stmt(name, kind, **attrs):
        dispatch = {"name": "device_execute", "ms": 2.0}
        if attrs:
            dispatch["attrs"] = attrs
        return {"name": name, "kind": kind, "ok": True, "ms": 1.0,
                "traces": [{"kind": "embedded", "root": {
                    "name": "request", "ms": 100.0, "children": [
                        {"name": "bind", "ms": 1.0}, dispatch,
                        {"name": "transfer", "ms": 90.0}]}}]}

    def read(statements):
        c = {"statements": statements, "back": "embedded",
             "front": "embedded"}
        return [m.read(n, c) for n in names]

    q1 = stmt("q1", "query", phase="main", xla_compiles=0,
              dict_space_slots=2, scatter_slots=0)
    q6 = stmt("q6", "query", xla_compiles=0, dict_space_slots=0,
              scatter_slots=0)
    assert read([q1, q6, q1, q6]) == [1.0, 0.0]
    assert read([q1, q1, q6]) == [2, 0]
    # the parent's scatter form, had it carried the attrs
    old_q1 = stmt("q1", "query", dict_space_slots=2, scatter_slots=2)
    assert read([old_q1, q6]) == [1.0, 1.0]
    # other kinds of statement are left out; a program without the
    # attrs (the parent as it is) reads None
    put = stmt("rf1", "insert_rows", dict_space_slots=9, scatter_slots=9)
    assert read([put, q1, q6]) == [1.0, 0.0]
    bare = stmt("q1", "query", xla_compiles=0)
    assert read([bare, bare]) == [None, None]


def _decode_session():
    """g: six BIGINT values (a dictionary-encoded numeric group key);
    q: five DOUBLE values; wide: a dictionary past the constant (BIGINT:
    a 4-byte value's dictionary ends at 256 entries, and DOUBLE is
    float32 under the chip's plate policy)."""
    from snappydata_tpu.storage import device_decode

    n = 60_000
    distinct = 2 * device_decode.DICT_SELECT_MAX_WIDTH + 7
    assert distinct < n // 4
    rng = np.random.default_rng(30)
    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE dd (k BIGINT, g BIGINT, q DOUBLE, wide BIGINT, "
          "v DOUBLE) USING column")
    s.insert_arrays("dd", [
        np.arange(n, dtype=np.int64),
        rng.integers(0, 6, n).astype(np.int64),
        rng.choice(np.array([0.5, 1.25, 2.0, 3.75, 8.5]), n),
        rng.integers(0, distinct, n).astype(np.int64) * 3,
        rng.random(n)])
    s.catalog.describe("dd").data.force_rollover()
    return s


def _decode_tpch():
    from snappydata_tpu.utils import tpch

    s = SnappySession(catalog=Catalog())
    tpch.load_tpch(s, sf=0.002, seed=7)
    return s


_DECODE_CASES = {
    # statement, session, knobs, (select, gather) on the main dispatch
    "q6_code_plates": ("tpch:Q6", {}, (2, 0)),
    "q1_code_plates": ("tpch:Q1", {}, (3, 0)),
    "past_the_constant": ("SELECT sum(wide), count(*) FROM dd",
                          {}, (0, 1)),
    "both_widths": ("SELECT sum(wide * q) FROM dd", {}, (1, 1)),
    "decoded_plates": ("SELECT sum(wide * q) FROM dd",
                       {"scan_compressed_domain": "off"}, (0, 0)),
    # the key's own plate and its remap through the table's domain
    "group_key_remap": ("SELECT g, count(*), sum(v) FROM dd GROUP BY g "
                        "ORDER BY g", {"agg_on_codes": "on"}, (2, 0)),
    "group_key_decoded": ("SELECT g, count(*), sum(v) FROM dd GROUP BY g "
                          "ORDER BY g", {"agg_on_codes": "off"}, (1, 0)),
}


@pytest.mark.parametrize("case", list(_DECODE_CASES))
def test_decode_form_metrics_read_the_attrs(_restore_knobs, case):
    """`dict_select_plates` / `dict_gather_plates` on the main dispatch
    span of real statements, cold and warm, read through the benchmark's
    own manifest and `span_attr` reader (the four metric files PR 30
    added): one a site, counted at trace time and so before XLA drops
    the decodes nothing reads (Q6 meets l_quantity on its codes alone
    and still counts two); the group-key remap is a site of its own and
    returns the decoded path's rows."""
    from snappydata_tpu.utils import tpch

    m, _ = _bench_manifest()
    cells = {"scan": "tpch_sf2.scan", "served": "tpch_sf2.refresh"}
    for suffix, cell in cells.items():
        for attr in ("dict_select_plates", "dict_gather_plates"):
            entry = next(p for p in m.doc["per_layer"]
                         if p["name"] == f"{attr}.{suffix}")
            assert entry["workloads"] == [cell]
            assert entry["layer"] == "kernels"
            assert entry["source"] == "program_counter"

    sql, knobs, want = _DECODE_CASES[case]
    props = _restore_knobs
    props.decimal_as_float64 = False
    saved = {k: props.get(k) for k in
             ("scan_compressed_domain", "agg_on_codes")}
    try:
        for k, v in knobs.items():
            props.set(k, v)
        if sql.startswith("tpch:"):
            s, sql = _decode_tpch(), getattr(tpch, sql[5:])
        else:
            s = _decode_session()
        statements = []
        for expect_name in ("jit_compile", "device_execute"):
            rows = s.sql(sql).rows()
            tr = tracing.ring().last().to_dict()
            assert _main_dispatch(tr["root"])["name"] == expect_name
            statements.append({"name": case, "kind": "query", "ok": True,
                               "ms": tr["root"]["ms"],
                               "traces": [{"kind": "embedded",
                                           "root": tr["root"]}]})
        ctx = {"statements": statements, "back": "embedded",
               "front": "embedded"}
        for suffix in cells:
            assert (m.read(f"dict_select_plates.{suffix}", ctx),
                    m.read(f"dict_gather_plates.{suffix}", ctx)) == want
        if case == "group_key_remap":
            props.set("scan_compressed_domain", "off")
            assert s.sql(sql).rows() == rows
        s.stop()
    finally:
        for k, v in saved.items():
            props.set(k, v)

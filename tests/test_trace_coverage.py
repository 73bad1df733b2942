"""A statement's host time, named end to end: the root's children tile
it (the steps `admit`, `plan_lookup` and `finish`, and the Flight
server's `encode`), the spans whose self time the benchmark reads hold
no children, the profiler's host trace holds the steps on its clock,
and the collector's pauses land on the span they interrupted.

CPU backend: values, nesting and shares of a root, never a device time.
"""

import gc
import glob
import os
import statistics
import threading

import jax
import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.engine.executor import CompiledPlan
from snappydata_tpu.observability import tracing
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.utils import tpch

pytestmark = pytest.mark.observability

# the spans whose self time the benchmark's readers take
READ_SPANS = ("parse", "analyze", "optimize", "bind", "transfer")
HEAD = ["parse", "admit", "optimize", "analyze", "plan_lookup", "bind",
        "device_execute", "transfer", "finish"]
# top-level children of a warm statement's root, in order (consecutive
# dispatches of a split plan taken as one)
ORDER = {
    "q6": HEAD,
    "q1": HEAD + ["host_ops", "finish"],       # ORDER BY on the host
    "q3": HEAD + ["host_ops", "finish"],       # ORDER BY, LIMIT
    "groupby": HEAD,
    "served_q6": HEAD + ["encode"],
}
GROUPBY = "SELECT sym, avg(id) FROM qs GROUP BY sym"


@pytest.fixture(autouse=True)
def _tracing_on():
    props = config.global_properties()
    saved = props.tracing_enabled
    props.tracing_enabled = True
    yield props
    props.tracing_enabled = saved


@pytest.fixture(scope="module")
def session():
    s = SnappySession(catalog=Catalog())
    tpch.load_tpch(s, sf=0.002, seed=7)
    n = 20_000
    ids = np.arange(n, dtype=np.int64)
    s.sql("CREATE TABLE qs (id BIGINT NOT NULL, sym VARCHAR(10) NOT NULL) "
          "USING column")
    s.insert_arrays("qs", [ids, np.array([f"sym{i % 100}" for i in ids],
                                         dtype=object)])
    yield s
    s.stop()


def _uncovered_ms(root: dict) -> float:
    """The root's duration less the union of its children's intervals."""
    covered, at = 0.0, 0.0
    for c in sorted(root.get("children", ()), key=lambda c: c["start_ms"]):
        lo, hi = max(c["start_ms"], at), min(c["start_ms"] + c["ms"],
                                              root["ms"])
        if hi > lo:
            covered, at = covered + hi - lo, hi
    return max(0.0, root["ms"] - covered)


def _walk(span: dict):
    yield span
    for c in span.get("children", ()):
        yield from _walk(c)


def _serve(session):
    from snappydata_tpu.cluster import SnappyClient
    from snappydata_tpu.cluster.flight_server import SnappyFlightServer

    server = SnappyFlightServer(session, "127.0.0.1", 0)
    threading.Thread(target=server.serve, daemon=True).start()
    server.wait_ready(timeout=10)
    return server, SnappyClient(address=f"127.0.0.1:{server.actual_port}")


def _roots(session, case: str, n: int = 10) -> list:
    """The roots of `n` warm statements of `case` (the server's trace of
    a served one), after two statements that compile and fill caches."""
    if case == "served_q6":
        server, client = _serve(session)
        try:
            out = []
            for i in range(n + 2):
                client.sql(tpch.Q6)
                tid = [t for t in tracing.ring().traces(10)
                       if t["kind"] == "client"][0]["trace_id"]
                (srv,) = [t for t in tracing.ring().get(tid)
                          if t["kind"] == "server"]
                out.append(srv["root"])
            return out[2:]
        finally:
            client.close()
            server.shutdown()
    sql = {"q6": tpch.Q6, "q1": tpch.Q1, "q3": tpch.Q3,
           "groupby": GROUPBY}[case]
    out = []
    for _ in range(n + 2):
        session.sql(sql).rows()
        out.append(tracing.ring().last().to_dict()["root"])
    return out[2:]


@pytest.mark.parametrize("case", list(ORDER))
def test_the_roots_children_tile_it(session, case):
    roots = _roots(session, case)
    shares = [_uncovered_ms(r) / r["ms"] for r in roots]
    # no more than a tenth of a statement sits under no child span
    assert statistics.median(shares) <= 0.10, (case, shares)
    for root in roots:
        names = [c["name"] for c in root["children"]]
        collapsed = [x for i, x in enumerate(names)
                     if i == 0 or x != names[i - 1]]
        assert collapsed == ORDER[case], names
        for c in root["children"]:
            # the steps hold no children; nor do the spans readers take
            # self time of
            if c["name"] in tracing.HOST_SPANS or c["name"] in READ_SPANS:
                assert "children" not in c, (case, c["name"])
        # siblings follow one another on one thread
        kids = root["children"]
        for a, b in zip(kids, kids[1:]):
            assert b["start_ms"] >= a["start_ms"] + a["ms"] - 0.01
        assert "gc_ms" in root["attrs"]
    if case == "groupby":
        # every warm statement took its group index from the cache
        hits = [sp["attrs"]["gidx_cache_hit"] for r in roots
                for sp in _walk(r) if sp["name"] == "device_execute"
                and sp["attrs"].get("phase") == "main"]
        assert hits == [1] * len(roots)


def test_host_spans_are_one_tuple():
    assert tracing.HOST_SPANS == ("admit", "plan_lookup", "finish",
                                  "encode")
    with pytest.raises(ValueError):
        with tracing.request_scope("x", kind="session"):
            tracing.step("admt")
    # untraced, a step is a no-op
    assert tracing.current() is None
    tracing.step("admit")


def test_a_step_ends_where_the_next_child_starts_or_the_parent_closes():
    with tracing.request_scope("x", kind="session") as tr:
        tracing.step("admit")
        tracing.annotate("seen", 1)         # lands on the parent
        with tracing.span("optimize"):
            pass
        tracing.step("plan_lookup")
        tracing.step("finish")              # a step ends the step before
    root = tr.to_dict()["root"]
    assert [c["name"] for c in root["children"]] == \
        ["admit", "optimize", "plan_lookup", "finish"]
    assert root["attrs"]["seen"] == 1
    a, o, p, f = root["children"]
    assert o["start_ms"] >= a["start_ms"] + a["ms"] - 1e-3
    assert f["start_ms"] >= p["start_ms"] + p["ms"] - 1e-3
    # the last step closed with its parent, no later
    assert f["start_ms"] + f["ms"] <= root["ms"] + 1e-3
    assert all(c["ms"] >= 0 for c in root["children"])


# ----------------------------------------------------------------------
# the collector
# ----------------------------------------------------------------------

def _collect_inside(monkeypatch, cls, name):
    real = getattr(cls, name)

    def with_collection(self, *a, **k):
        gc.collect()
        return real(self, *a, **k)

    monkeypatch.setattr(cls, name, with_collection)


@pytest.mark.parametrize("method, span", [
    ("_bind_inner", "bind"),
    # `_assemble` runs in the `finish` step, which is never current: the
    # pause lands on the step's parent
    ("_assemble", "request"),
])
def test_a_collection_lands_on_the_span_it_paused(session, monkeypatch,
                                                  method, span):
    session.sql(tpch.Q6).rows()
    _collect_inside(monkeypatch, CompiledPlan, method)
    session.sql(tpch.Q6).rows()
    root = tracing.ring().last().to_dict()["root"]
    (sp,) = [s for s in _walk(root) if s["name"] == span]
    assert sp["attrs"]["gc_ms"] > 0
    assert sp["attrs"]["gc_full"] >= 1


def test_a_root_without_a_collection_reads_zero(session):
    session.sql(tpch.Q6).rows()
    gc.disable()
    try:
        session.sql(tpch.Q6).rows()
    finally:
        gc.enable()
    root = tracing.ring().last().to_dict()["root"]
    assert root["attrs"]["gc_ms"] == 0
    assert sum(s.get("attrs", {}).get("gc_ms", 0)
               for s in _walk(root)) == 0


def test_an_untraced_collection_adds_to_the_registry_only(
        session, monkeypatch, _tracing_on):
    reg = global_registry()
    session.sql(tpch.Q6).rows()
    _tracing_on.tracing_enabled = False
    _collect_inside(monkeypatch, CompiledPlan, "_assemble")
    recorded = tracing.ring().recorded
    c0 = reg.counter("gc_collections")
    p0 = reg.snapshot()["gauges"]["gc_pause_ms"]
    session.sql(tpch.Q6).rows()
    assert tracing.ring().recorded == recorded
    assert reg.counter("gc_collections") > c0
    assert reg.counters_snapshot()["gc_collections"] > c0
    assert reg.snapshot()["gauges"]["gc_pause_ms"] > p0
    prom = reg.to_prometheus()
    assert "snappy_tpu_gc_collections_total" in prom
    assert "# TYPE snappy_tpu_gc_pause_ms gauge" in prom


# ----------------------------------------------------------------------
# the profiler's host trace
# ----------------------------------------------------------------------

def _snappy_events(trace_dir: str) -> list:
    """[(name, start_ns, dur_ns)] of the `snappy:*` events of the host
    plane, in order of start."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.extend((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)) for ev in ln.events
                           if ev.name.startswith(tracing.PROFILE_PREFIX))
    return sorted(out, key=lambda e: e[1])


def test_the_steps_tile_the_roots_event_in_the_profile(session, tmp_path):
    session.sql(tpch.Q6).rows()            # compiled before the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        session.sql(tpch.Q6).rows()
    finally:
        jax.profiler.stop_trace()
    root = tracing.ring().last().to_dict()["root"]
    evs = _snappy_events(str(tmp_path))
    assert evs[0][0] == tracing.PROFILE_PREFIX + "request"
    kids = evs[1:]
    # a warm Q6's tree is flat: one event a child of the root, in order
    assert [e[0] for e in kids] == [tracing.PROFILE_PREFIX + c["name"]
                                    for c in root["children"]]
    for step in ("admit", "plan_lookup", "finish"):
        assert tracing.PROFILE_PREFIX + step in [e[0] for e in kids]
    _, r0, rdur = evs[0]
    for (_, a0, adur), (_, b0, _) in zip(kids, kids[1:]):
        assert b0 >= a0 + adur - 1e3       # one after another
    assert kids[0][1] >= r0 - 1e3
    assert kids[-1][1] + kids[-1][2] <= r0 + rdur + 1e3
    assert sum(e[2] for e in kids) >= 0.9 * rdur

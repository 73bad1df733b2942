"""The join cell `tpch_sf1.join` (PR 27) on the CPU: its customer
generator against the spec's domains, its plain reference against a pandas
merge-and-groupby and against `SnappySession.sql`, its rehearsal whole
and broken, its manifest entries, and what a traced Q3 carries for the
cell's per-layer metrics. Values and counts, never a device time.
"""

import datetime
import json
import os
import sys

import numpy as np
import pandas as pd
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import control            # noqa: E402
import manifest           # noqa: E402
import roofline           # noqa: E402
import run as bench       # noqa: E402
from reference import World, compare   # noqa: E402

pytestmark = pytest.mark.observability

CELL = "tpch_sf1.join"
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
METRICS = ["q3_stmt_ms", "plan_ms.join", "bind_ms.join",
           "device_wait_ms.join", "dispatch_ms.join", "device_idle_pct.join",
           "join_roofline", "xla_compiles_in_window.join",
           "join_build_sorts.join", "host_fallbacks.join",
           "join_device_joins.join", "scatter_slots.join",
           "join_merge_probes.join", "join_search_loops.join",
           "gidx_run_lanes.join", "run_reduce_slots.join"]
DRAWS = [("BUILDING", "1995-03-15"), ("MACHINERY", "1995-03-01"),
         ("HOUSEHOLD", "1995-03-31")]
_EPOCH = datetime.date(1970, 1, 1)


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


@pytest.fixture(autouse=True)
def _restore_knobs():
    """A rehearsal sets the chip's dtype policy for the process."""
    props = config.global_properties()
    saved = (props.decimal_as_float64, props.tracing_enabled)
    props.tracing_enabled = True
    yield props
    props.decimal_as_float64, props.tracing_enabled = saved


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(ROOT)


def _tables(man, sf, seed):
    cfg = man.config("tpch_sf1")
    out = {}
    for table in man.mix("join_q3")["tables"]:
        gen = man.module("generators", cfg["tables"][table]["generator"])
        out[table] = gen.generate(table, sf, seed)
    return out


def _world(man, tables):
    w = World(man)
    keep = man.module("references", "q3").COLUMNS
    for table, cols in tables.items():
        w.insert(table, {c: cols[c] for c in keep[table]})
    return w


def _session(man, tables):
    cfg = man.config("tpch_sf1")
    s = SnappySession(catalog=Catalog())
    for table, cols in tables.items():
        s.sql(cfg["tables"][table]["ddl"])
        s.insert_arrays(table, list(cols.values()))
    return s


def _q3(man, segment, date):
    return man.mix("join_q3")["statements"]["q3"]["sql"].format(
        segment=segment, date=date)


# ---- the generator ---------------------------------------------------------

def test_customer_generator_meets_the_specs_domains(man):
    gen = man.module("generators", "tpch_customer")
    sf, seed = 0.02, 2147483659
    c = gen.generate("customer", sf, seed)
    assert list(c) == ["c_custkey", "c_name", "c_address", "c_nationkey",
                       "c_phone", "c_acctbal", "c_mktsegment", "c_comment"]
    n = 3000
    assert all(len(v) == n for v in c.values())
    assert (c["c_custkey"] == np.arange(1, n + 1)).all()
    assert c["c_custkey"].dtype == np.int64
    assert c["c_name"][0] == "Customer#000000001"
    assert c["c_name"][-1] == "Customer#000003000"
    assert c["c_nationkey"].min() == 0 and c["c_nationkey"].max() == 24
    for key, nation, phone in zip(c["c_custkey"][:200],
                                  c["c_nationkey"][:200],
                                  c["c_phone"][:200]):
        cc, a, b, d = phone.split("-")
        assert int(cc) == nation + 10 and len(phone) == 15
        assert 100 <= int(a) <= 999 and 100 <= int(b) <= 999
        assert 1000 <= int(d) <= 9999
    assert -999.99 <= c["c_acctbal"].min() and c["c_acctbal"].max() <= 9999.99
    assert c["c_acctbal"].min() < -900 and c["c_acctbal"].max() > 9900
    assert np.allclose(np.round(c["c_acctbal"] * 100),
                       c["c_acctbal"] * 100)
    share = pd.Series(c["c_mktsegment"]).value_counts(normalize=True)
    assert sorted(share.index) == sorted(SEGMENTS)
    assert share.min() > 0.17 and share.max() < 0.23
    for col, lo, hi in (("c_address", 10, 40), ("c_comment", 29, 116)):
        lens = np.array([len(x) for x in c[col]])
        assert lens.min() >= lo and lens.max() <= hi
        assert lens.min() <= lo + 2 and lens.max() >= hi - 2
    # the same seed gives the same table, another seed another
    again = gen.generate("customer", sf, seed)
    assert all((c[k] == again[k]).all() for k in c)
    other = gen.generate("customer", sf, seed + 1)
    assert (other["c_mktsegment"] != c["c_mktsegment"]).any()
    with pytest.raises(KeyError):
        gen.generate("orders", sf, seed)


@pytest.mark.parametrize("sf", [0.002, 0.02, 0.05])
def test_every_order_has_its_customer(man, sf):
    t = _tables(man, sf, 77)
    assert np.isin(t["orders"]["o_custkey"],
                   t["customer"]["c_custkey"]).all()
    # a third of the customers has no order, as in dbgen's stream
    without = ~np.isin(t["customer"]["c_custkey"], t["orders"]["o_custkey"])
    assert 0.25 < without.mean() < 0.45 or sf < 0.01


# ---- the reference ---------------------------------------------------------

def _pandas_q3(tables, segment, days):
    """Q3 as its text reads, by merge and groupby; float32 plates, each
    product rounded once, float64 sums."""
    li = pd.DataFrame({k: tables["lineitem"][k] for k in (
        "l_orderkey", "l_extendedprice", "l_discount", "l_shipdate")})
    od = pd.DataFrame({k: tables["orders"][k] for k in (
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")})
    cu = pd.DataFrame({k: tables["customer"][k] for k in (
        "c_custkey", "c_mktsegment")})
    j = cu[cu.c_mktsegment == segment].merge(
        od[od.o_orderdate < days], left_on="c_custkey",
        right_on="o_custkey").merge(
        li[li.l_shipdate > days], left_on="o_orderkey",
        right_on="l_orderkey")
    price = j.l_extendedprice.to_numpy().astype(np.float32)
    disc = j.l_discount.to_numpy().astype(np.float32)
    j["rev"] = (price * (np.float32(1) - disc)).astype(np.float64)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["rev"].sum()
    g = g.sort_values(["rev", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    return [(int(r.l_orderkey), float(r.rev), int(r.o_orderdate),
             int(r.o_shippriority)) for r in g.itertuples()]


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_reference_equals_a_pandas_merge_and_groupby(man, seed):
    tables = _tables(man, 0.01, seed)
    w = _world(man, tables)
    for segment, date in DRAWS:
        days = _days(date)
        got = w.answer("q3", {"segment": segment, "date": date,
                              "days": days})
        exp = _pandas_q3(tables, segment, days)
        assert len(got) == 10
        assert compare(got, exp) == (0.0, 0)
        assert [type(v) for v in got[0]] == [int, float, int, int]
        assert [r[1] for r in got] == sorted((r[1] for r in got),
                                             reverse=True)


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_reference_equals_the_program(man, seed, _restore_knobs):
    """`session.sql` of the mix's Q3 text under the chip's dtype policy
    (float32 plates, float64 sums), three SEGMENT/DATE draws a seed."""
    _restore_knobs.decimal_as_float64 = False
    tables = _tables(man, 0.01, seed)
    w = _world(man, tables)
    s = _session(man, tables)
    try:
        for segment, date in DRAWS:
            got = [tuple(r) for r in s.sql(_q3(man, segment, date)).rows()]
            exp = w.answer("q3", {"segment": segment, "date": date,
                                  "days": _days(date)})
            gap, wrong = compare(got, exp)
            assert wrong == 0 and gap <= 1e-9, (segment, date, got, exp)
    finally:
        s.stop()


def test_control_in_float32_accumulators_is_not_correct(man):
    out = control.control_gap(man, CELL, seed=77, cycles=6, sf=0.05)
    assert out["compared"] == 7
    assert out["sum_rel_gap"] > 3 * out["limit"]


# ---- the manifest ------------------------------------------------------------

def test_manifest_takes_the_join_cell(man):
    assert manifest.problems(man) == []
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch_sf1", "join_q3", 1)
    e2e = {e["name"]: e for e in man.doc["end_to_end"]}
    assert CELL in e2e["query_rows_per_s"]["workloads"]
    assert [m["name"] for m in man.metrics_of(CELL, "end_to_end")] == \
        ["query_rows_per_s", "setup_s"]
    assert [m["name"] for m in man.metrics_of(CELL, "per_layer")] == METRICS
    cfg, sf2 = man.config("tpch_sf1"), man.config("tpch_sf2")
    entry = next(c for c in man.doc["configs"] if c["name"] == "tpch_sf1")
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(cfg["reduced"]) == ["sf"]
    assert cfg["sf"] == 1 and cfg["rehearsal_sf"] == 0.05
    # nothing of tpch_sf2 is stated more weakly
    assert cfg["precision"] == sf2["precision"]
    assert cfg["limits"] == sf2["limits"]
    assert cfg["guarantees"][:len(sf2["guarantees"])] == sf2["guarantees"]
    assert cfg["assumed"][:len(sf2["assumed"])] == sf2["assumed"]
    for t in ("lineitem", "orders"):
        assert cfg["tables"][t] == sf2["tables"][t]
    mix = man.mix("join_q3")
    draws = mix["statements"]["q3"]["draws"]
    assert [d["segment"] for d in draws["segment"]] == SEGMENTS
    assert [d["days"] for d in draws["date"]] == \
        [_days(d["date"]) for d in draws["date"]]
    assert draws["date"][0]["date"] == "1995-03-01" and \
        draws["date"][-1]["date"] == "1995-03-31" and len(draws["date"]) == 31
    # what the benchmark adds under its paths besides data: one
    # generator and one reference
    assert mix["cycle"] == mix["warmup"] == ["q3"]


def test_a_seed_walks_all_155_combinations(man):
    import traffic

    mix, cfg = man.mix("join_q3"), man.config("tpch_sf1")
    seen = []
    for seed in (1, 2):
        t = traffic.Traffic(man, mix, cfg, 0.01, seed)
        sts = [t.cycle()[0] for _ in range(155)]
        combos = {(st.subst["segment"], st.subst["date"]) for st in sts}
        assert len(combos) == 155
        assert all(st.subst["days"] == _days(st.subst["date"])
                   and f"DATE '{st.subst['date']}'" in st.sql
                   and f"'{st.subst['segment']}'" in st.sql for st in sts)
        seen.append([st.sql for st in sts])
    assert seen[0] != seen[1]


# ---- a run, whole and broken -------------------------------------------------

def _rehearse(capsys, monkeypatch, trace=0, seed=2147483659):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", str(trace), "--cpu-rehearsal",
                     "--root", ROOT])
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1])


def _break_query(monkeypatch, alter):
    """The embedded entry's `Engine.query` answers through `alter`: the
    timed path broken underneath the harness."""
    real_module = manifest.Manifest.module

    def module(self, group, name):
        mod = real_module(self, group, name)
        if group == "entries" and not hasattr(mod, "broken"):
            real = mod.Engine.query
            mod.Engine.query = lambda eng, sql, params: alter(
                real(eng, sql, params))
            mod.broken = True
        return mod
    monkeypatch.setattr(manifest.Manifest, "module", module)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_is_correct(capsys, monkeypatch, man, trace):
    res = _rehearse(capsys, monkeypatch, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    checks = res["checks"]
    assert checks["sum_rel_gap"]["value"] <= checks["sum_rel_gap"]["limit"] \
        == 1e-9
    assert checks["exact_mismatches"]["value"] == 0
    assert checks["unanswered"]["value"] == 0
    if not trace:
        assert set(res["metrics"]) == {"query_rows_per_s", "setup_s"}
        assert res["metrics"]["query_rows_per_s"]["value"] > 0
        return
    # every per-layer metric but the device's own two reads a number
    assert set(res["metrics"]) == set(METRICS) - {"device_idle_pct.join",
                                                  "join_roofline"}
    value = {k: v["value"] for k, v in res["metrics"].items()}
    assert value["host_fallbacks.join"] == 0
    assert value["xla_compiles_in_window.join"] == 0
    assert value["join_build_sorts.join"] == 0
    assert value["join_device_joins.join"] == 2
    assert value["scatter_slots.join"] == 0
    assert value["gidx_run_lanes.join"] == 1
    assert value["run_reduce_slots.join"] == 1


def test_rehearsal_with_the_families_forced_to_scatter_reduces_over_runs(
        capsys, monkeypatch, _restore_knobs):
    """`agg_reduce_strategy` `scatter` on Q3's generic plan is the run
    reduce, the lane the chip takes at SF 1: here end to end against the
    NumPy reference, exact."""
    monkeypatch.setattr(_restore_knobs, "agg_reduce_strategy", "scatter")
    res = _rehearse(capsys, monkeypatch, trace=1, seed=3900000017)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["sum_rel_gap"]["value"] == 0.0
    assert res["checks"]["exact_mismatches"]["value"] == 0
    value = {k: v["value"] for k, v in res["metrics"].items()}
    assert value["scatter_slots.join"] == 0
    assert value["run_reduce_slots.join"] == 1
    assert value["gidx_run_lanes.join"] == 1


def _off_by_a_millionth(rows):
    return [tuple(v * (1 + 1e-6) if isinstance(v, float) else v for v in r)
            for r in rows]


def _swap_two_rows(rows):
    return [rows[1], rows[0]] + rows[2:]


@pytest.mark.parametrize("alter, number", [
    (_off_by_a_millionth, "sum_rel_gap"),
    (_swap_two_rows, "exact_mismatches"),
], ids=["one_answer_off_by_1e-6", "two_rows_of_the_ten_swapped"])
def test_a_broken_answer_is_not_correct(capsys, monkeypatch, alter, number):
    _break_query(monkeypatch, alter)
    res = _rehearse(capsys, monkeypatch)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


# ---- what a traced Q3 carries ------------------------------------------------

def _spans(root):
    yield root
    for c in root.get("children", ()):
        yield from _spans(c)


def _named(root, name):
    return [sp for sp in _spans(root) if sp["name"] == name]


def _three_traced_q3(man):
    """Three Q3 on one session, each with its trace: the first binds
    cold, the others bring a fresh SEGMENT and DATE."""
    props = config.global_properties()
    saved = (props.decimal_as_float64, props.tracing_enabled)
    props.decimal_as_float64, props.tracing_enabled = False, True
    tables = _tables(man, 0.01, 5)
    s = _session(man, tables)
    recs = []
    try:
        for segment, date in DRAWS:
            rows = [tuple(r) for r in s.sql(_q3(man, segment, date)).rows()]
            tr = tracing.ring().last().to_dict()
            recs.append({"name": "q3", "kind": "query", "ok": True,
                         "window": True, "traced": True, "ms": tr["root"]["ms"],
                         "answer": rows, "traces": [tr],
                         "rows_read": len(tables["lineitem"]["l_orderkey"]),
                         "bytes_per_row": 26})
    finally:
        s.stop()
        props.decimal_as_float64, props.tracing_enabled = saved
    return recs


@pytest.fixture(scope="module")
def traced_q3(man):
    """As the CPU backend lowers it: the probe's searches are loops."""
    return _three_traced_q3(man)


@pytest.fixture(scope="module")
def traced_q3_merged(man):
    """As the chip lowers it at SF 1 (PR 28): the resolver is steered to
    the sort-merge here, in the test; the program has no switch."""
    from snappydata_tpu.ops import join as dj

    saved = dj.probe_lowering
    dj.probe_lowering = lambda backend, n_probe, n_build: dj.PROBE_MERGE
    try:
        return _three_traced_q3(man)
    finally:
        dj.probe_lowering = saved


def _main_dispatch(root):
    (sp,) = [sp for sp in _spans(root)
             if sp["name"] in ("jit_compile", "device_execute")]
    return sp["attrs"]


def test_a_traced_q3_says_how_its_probes_lowered(traced_q3,
                                                 traced_q3_merged):
    """Two probes as one merge each and no search loop left; in the loop
    form none merged and six loops emitted: two bounds a join and the
    located row of each filtered build (XLA drops the customer's, whose
    columns nothing reads: five `while` in the HLO)."""
    for rec in traced_q3_merged:
        attrs = _main_dispatch(rec["traces"][0]["root"])
        assert attrs["join_device_joins"] == 2
        assert attrs["join_merge_probes"] == 2
        assert attrs["join_search_loops"] == 0
    for rec in traced_q3:
        attrs = _main_dispatch(rec["traces"][0]["root"])
        assert attrs["join_merge_probes"] == 0
        assert attrs["join_search_loops"] == 6
    # the same ten rows either way
    assert [r["answer"] for r in traced_q3_merged] \
        == [r["answer"] for r in traced_q3]


def test_a_traced_q3_stays_on_the_device_and_says_so(traced_q3):
    first, second, third = (r["traces"][0]["root"] for r in traced_q3)
    for root in (first, second, third):
        assert not _named(root, "host_fallback")
        (bind,) = _named(root, "bind")
        dispatch = [sp for sp in _spans(root)
                    if sp["name"] in ("jit_compile", "device_execute")]
        assert len(dispatch) == 1
        attrs = dispatch[0]["attrs"]
        assert attrs["join_device_joins"] == 2
        assert attrs["join_expand_out_rows"] == 0
        assert attrs["groups_overflow"] == 0
        assert attrs["scatter_slots"] == 0 and attrs["dict_space_slots"] == 0
        # Q3's three keys take the generic group index, by run heads, and
        # its sum is reduced over the rows' runs, not scattered
        assert attrs["gidx_run_lane"] == 1
        assert attrs["run_reduce_slots"] == 1
        # the generic group index: min(max_groups, padded rows) segments
        assert attrs["group_slots"] == 65536
        # both joins probe on the lineitem side's padded slots
        assert attrs["join_probe_rows"] % 2 == 0
        assert attrs["join_probe_rows"] // 2 >= 59999
        (host,) = _named(root, "host_ops")
        assert host["attrs"]["ops"] == "Sort,Limit"
        assert host["attrs"]["rows_out"] == 10 <= host["attrs"]["rows_in"]
        assert bind["attrs"]["join_builds_sorted"] \
            + bind["attrs"]["join_builds_cached"] == 2
    # the builds are sorted by the first statement's bind and cached after
    builds = _named(first, "join_build")
    assert len(builds) == 2
    assert _named(first, "bind")[0]["attrs"]["join_builds_sorted"] == 2
    assert all(b["attrs"]["unique"] is True and b["attrs"]["nbytes"]
               == 24 * b["attrs"]["build_rows"] for b in builds)
    for root in (second, third):
        assert not _named(root, "join_build")
        assert _named(root, "bind")[0]["attrs"]["join_builds_cached"] == 2
        # a fresh SEGMENT and DATE compile nothing anew
        assert not _named(root, "jit_compile")
        assert not _named(root, "compile")
        assert sum(sp.get("attrs", {}).get("xla_compiles", 0)
                   for sp in _spans(root)) == 0


def test_a_plan_without_a_join_carries_zeros():
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k INT, v DOUBLE) USING column")
        s.insert_arrays("t", [np.arange(1000, dtype=np.int32) % 7,
                              np.ones(1000)])
        s.sql("SELECT k, sum(v) FROM t GROUP BY k").rows()
        root = tracing.ring().last().to_dict()["root"]
    finally:
        s.stop()
    (bind,) = _named(root, "bind")
    assert bind["attrs"]["join_builds_sorted"] == 0
    assert bind["attrs"]["join_builds_cached"] == 0
    main = [sp for sp in _spans(root)
            if sp["name"] in ("jit_compile", "device_execute")
            and sp["attrs"].get("phase", "main") == "main"]
    assert len(main) == 1
    for key in ("join_device_joins", "join_probe_rows",
                "join_expand_out_rows", "join_merge_probes",
                "join_search_loops", "groups_overflow"):
        assert main[0]["attrs"][key] == 0
    assert main[0]["attrs"]["group_slots"] >= 7
    assert not _named(root, "host_ops")


def test_more_groups_than_slots_is_flagged_and_named():
    """Past `group_slots` distinct keys the main dispatch says
    `groups_overflow` 1 and the statement reruns under `host_fallback`,
    with its reason: never a silent truncation."""
    n = 70000
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k BIGINT, j BIGINT, v DOUBLE) USING column")
        s.insert_arrays("t", [np.arange(n, dtype=np.int64) * 3,
                              np.arange(n, dtype=np.int64) % 5, np.ones(n)])
        rows = s.sql("SELECT k, j, sum(v) FROM t GROUP BY k, j").rows()
        root = tracing.ring().last().to_dict()["root"]
    finally:
        s.stop()
    assert len(rows) == n
    main = [sp for sp in _spans(root)
            if sp["name"] in ("jit_compile", "device_execute")
            and sp["attrs"].get("phase", "main") == "main"]
    assert len(main) == 1
    assert main[0]["attrs"]["group_slots"] == 65536 < n
    assert main[0]["attrs"]["groups_overflow"] == 1
    (fallback,) = _named(root, "host_fallback")
    assert "device overflow" in fallback["attrs"]["reason"]


@pytest.mark.parametrize("name", METRICS)
def test_every_new_metric_reads_a_number_from_the_trace(
        man, traced_q3_merged, name):
    window = traced_q3_merged[1:]      # the first statement is the warm-up
    ctx = {"statements": window, "back": "session", "front": "session",
           "device": {"busy_s": 2.0, "window_s": 2.5}, "window_s": 2.5,
           "peaks": roofline.peaks_for("TPU v5 lite")}
    value = man.read(name, ctx)
    assert isinstance(value, (int, float)) and not isinstance(value, bool)
    expected = {
        "host_fallbacks.join": 0, "xla_compiles_in_window.join": 0,
        "join_build_sorts.join": 0, "join_device_joins.join": 2,
        "scatter_slots.join": 0, "device_idle_pct.join": 20.0,
        "join_merge_probes.join": 2, "join_search_loops.join": 0,
        "gidx_run_lanes.join": 1, "run_reduce_slots.join": 1,
        "join_roofline": 100.0 * (2 * window[0]["rows_read"] * 26 / 819e9)
        / 2.0}
    if name in expected:
        assert value == pytest.approx(expected[name])
    else:
        assert value > 0
    # with the warm-up among them the builds show: the reader counts spans
    if name == "join_build_sorts.join":
        assert man.read(name, dict(ctx, statements=traced_q3_merged)) == 2
    # a program from before the attrs: None, not an error
    attr = {"join_device_joins.join": "join_device_joins",
            "join_merge_probes.join": "join_merge_probes",
            "join_search_loops.join": "join_search_loops",
            "gidx_run_lanes.join": "gidx_run_lane",
            "run_reduce_slots.join": "run_reduce_slots"}.get(name)
    if attr is not None:
        bare = json.loads(json.dumps(window))
        for r in bare:
            for sp in _spans(r["traces"][0]["root"]):
                sp.get("attrs", {}).pop(attr, None)
        assert man.read(name, dict(ctx, statements=bare)) is None

"""The snowflake cell `tpch_sf2_snowflake.join_q5` (PR 36) on the CPU: its
supplier, nation and region generator against the spec, its plain Q5
reference against a pandas merge chain and against `SnappySession.sql`
over three row tables, its control, its rehearsal whole and broken, its
manifest entries, and what a traced Q5 carries for the cell's per-layer
metrics. Values and counts, never a device time.
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

CELL = "tpch_sf2_snowflake.join_q5"
CONFIG = "tpch_sf2_snowflake"
TABLES = ["lineitem", "orders", "customer", "supplier", "nation", "region"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
METRICS = ["q5_stmt_ms", "plan_ms.q5", "bind_ms.q5", "dispatch_ms.q5",
           "device_wait_ms.q5", "device_idle_pct.q5",
           "xla_compiles_in_window.q5", "host_fallbacks.q5", "q5_roofline",
           "join_device_joins.q5", "join_merge_probes.q5",
           "join_search_loops.q5", "join_probe_rows.q5",
           "join_build_rows.q5", "join_row_builds.q5",
           "join_multikey_joins.q5"]
# the counters this PR added to the program: left out on the parent
NEW_ATTRS = {"join_build_rows.q5": "join_build_rows",
             "join_row_builds.q5": "join_row_builds",
             "join_multikey_joins.q5": "join_multikey_joins"}
DRAWS = [("ASIA", 1994), ("EUROPE", 1996), ("AMERICA", 1993)]
_EPOCH = datetime.date(1970, 1, 1)


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _params(region, year):
    date, end = f"{year}-01-01", f"{year + 1}-01-01"
    return {"region": region, "date": date, "end": end,
            "days": _days(date), "end_days": _days(end)}


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
    cfg = man.config(CONFIG)
    out = {}
    for table in man.mix("join_q5")["tables"]:
        gen = man.module("generators", cfg["tables"][table]["generator"])
        out[table] = gen.generate(table, sf, seed)
    return out


def _world(man, tables):
    w = World(man)
    keep = man.module("references", "q5").COLUMNS
    for table, cols in tables.items():
        w.insert(table, {c: cols[c] for c in keep[table]})
    return w


def _session(man, tables):
    cfg = man.config(CONFIG)
    s = SnappySession(catalog=Catalog())
    for table, cols in tables.items():
        s.sql(cfg["tables"][table]["ddl"])
        s.insert_arrays(table, list(cols.values()))
    return s


def _q5(man, p):
    return man.mix("join_q5")["statements"]["q5"]["sql"].format(**p)


# ---- the generator ---------------------------------------------------------

def test_supplier_generator_meets_the_specs_domains(man):
    gen = man.module("generators", "tpch_dims")
    sf, seed = 0.05, 2147483659
    s = gen.generate("supplier", sf, seed)
    assert list(s) == ["s_suppkey", "s_name", "s_address", "s_nationkey",
                       "s_phone", "s_acctbal", "s_comment"]
    n = 500
    assert all(len(v) == n for v in s.values())
    assert (s["s_suppkey"] == np.arange(1, n + 1)).all()
    assert s["s_suppkey"].dtype == np.int64
    assert s["s_name"][0] == "Supplier#000000001"
    assert s["s_name"][-1] == "Supplier#000000500"
    assert s["s_nationkey"].min() == 0 and s["s_nationkey"].max() == 24
    for nation, phone in zip(s["s_nationkey"], s["s_phone"]):
        cc, a, b, d = phone.split("-")
        assert int(cc) == nation + 10 and len(phone) == 15
        assert 100 <= int(a) <= 999 and 100 <= int(b) <= 999
        assert 1000 <= int(d) <= 9999
    assert -999.99 <= s["s_acctbal"].min() and s["s_acctbal"].max() <= 9999.99
    assert np.allclose(np.round(s["s_acctbal"] * 100), s["s_acctbal"] * 100)
    for col, lo, hi in (("s_address", 10, 40), ("s_comment", 25, 100)):
        lens = np.array([len(x) for x in s[col]])
        assert lens.min() >= lo and lens.max() <= hi
    again = gen.generate("supplier", sf, seed)
    assert all((s[k] == again[k]).all() for k in s)
    other = gen.generate("supplier", sf, seed + 1)
    assert (other["s_nationkey"] != s["s_nationkey"]).any()
    with pytest.raises(KeyError):
        gen.generate("partsupp", sf, seed)


def test_nation_and_region_are_the_specs_fixed_rows(man):
    gen = man.module("generators", "tpch_dims")
    n = gen.generate("nation", 2, 5)
    r = gen.generate("region", 2, 5)
    assert list(n) == ["n_nationkey", "n_name", "n_regionkey", "n_comment"]
    assert list(r) == ["r_regionkey", "r_name", "r_comment"]
    assert (n["n_nationkey"] == np.arange(25)).all()
    assert (r["r_regionkey"] == np.arange(5)).all()
    assert list(r["r_name"]) == REGIONS
    # cl 4.2.3, a few rows of the table and five nations a region
    by_key = dict(zip(n["n_nationkey"].tolist(),
                      zip(n["n_name"], n["n_regionkey"].tolist())))
    assert by_key[0] == ("ALGERIA", 0) and by_key[8] == ("INDIA", 2)
    assert by_key[20] == ("SAUDI ARABIA", 4)
    assert by_key[24] == ("UNITED STATES", 1)
    assert np.bincount(n["n_regionkey"]).tolist() == [5] * 5
    # the scale changes nothing; the seed only the comments
    assert (gen.generate("nation", 0.01, 5)["n_name"] == n["n_name"]).all()
    lens = [len(x) for x in n["n_comment"]] + [len(x) for x in r["r_comment"]]
    assert min(lens) >= 31 and max(lens) <= 115


@pytest.mark.parametrize("sf", [0.002, 0.02, 0.05])
def test_every_line_has_its_supplier_and_every_customer_a_nation(man, sf):
    t = _tables(man, sf, 77)
    assert np.isin(t["lineitem"]["l_suppkey"],
                   t["supplier"]["s_suppkey"]).all()
    assert np.isin(t["customer"]["c_nationkey"],
                   t["nation"]["n_nationkey"]).all()
    assert np.isin(t["supplier"]["s_nationkey"],
                   t["nation"]["n_nationkey"]).all()


# ---- the reference ---------------------------------------------------------

def _pandas_q5(tables, p):
    """Q5 as its text reads, by a chain of merges and a groupby; float32
    plates, each product rounded once, float64 sums."""
    def frame(table, cols):
        return pd.DataFrame({k: tables[table][k] for k in cols})

    li = frame("lineitem", ["l_orderkey", "l_suppkey", "l_extendedprice",
                            "l_discount"])
    od = frame("orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    cu = frame("customer", ["c_custkey", "c_nationkey"])
    su = frame("supplier", ["s_suppkey", "s_nationkey"])
    na = frame("nation", ["n_nationkey", "n_name", "n_regionkey"])
    re_ = frame("region", ["r_regionkey", "r_name"])
    od = od[(od.o_orderdate >= p["days"]) & (od.o_orderdate < p["end_days"])]
    j = (cu.merge(od, left_on="c_custkey", right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey")
         .merge(su, left_on=["l_suppkey", "c_nationkey"],
                right_on=["s_suppkey", "s_nationkey"])
         .merge(na, left_on="s_nationkey", right_on="n_nationkey")
         .merge(re_[re_.r_name == p["region"]], left_on="n_regionkey",
                right_on="r_regionkey"))
    price = j.l_extendedprice.to_numpy().astype(np.float32)
    disc = j.l_discount.to_numpy().astype(np.float32)
    j["rev"] = (price * (np.float32(1) - disc)).astype(np.float64)
    g = j.groupby("n_name", as_index=False)["rev"].sum()
    g = g.sort_values("rev", ascending=False, kind="stable")
    return [(r.n_name, float(r.rev)) for r in g.itertuples()]


@pytest.mark.parametrize("seed", [11, 2147483659, 3600000019])
def test_reference_equals_a_pandas_merge_and_groupby(man, seed):
    tables = _tables(man, 0.01, seed)
    w = _world(man, tables)
    for region, year in DRAWS:
        p = _params(region, year)
        got = w.answer("q5", p)
        exp = _pandas_q5(tables, p)
        # a nation of the region with no line is no group (SF 0.01
        # has 100 suppliers)
        assert 3 <= len(got) <= 5
        assert compare(got, exp) == (0.0, 0)
        assert [type(v) for v in got[0]] == [str, float]
        assert [r[1] for r in got] == sorted((r[1] for r in got),
                                             reverse=True)


@pytest.mark.parametrize("seed", [11, 2147483659, 3600000019])
def test_reference_equals_the_program(man, seed, _restore_knobs):
    """`session.sql` of the mix's Q5 text over the three row tables under
    the chip's dtype policy (float32 plates, float64 sums), three
    REGION/year draws a seed."""
    _restore_knobs.decimal_as_float64 = False
    tables = _tables(man, 0.01, seed)
    w = _world(man, tables)
    s = _session(man, tables)
    try:
        for region, year in DRAWS:
            p = _params(region, year)
            got = [tuple(r) for r in s.sql(_q5(man, p)).rows()]
            exp = w.answer("q5", p)
            gap, wrong = compare(got, exp)
            assert wrong == 0 and gap <= 1e-9, (region, year, got, exp)
    finally:
        s.stop()


def test_control_in_float32_accumulators_is_not_correct(man):
    out = control.control_gap(man, CELL, seed=77, cycles=6, sf=0.05)
    assert out["compared"] == 7
    assert out["sum_rel_gap"] > 3 * out["limit"]


# ---- the manifest ------------------------------------------------------------

def test_manifest_takes_the_snowflake_cell(man):
    assert manifest.problems(man) == []
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "join_q5", 1)
    # the fifth cell, appended after the four before it
    assert cell == man.doc["workloads"][4]
    e2e = {e["name"]: e for e in man.doc["end_to_end"]}
    assert e2e["query_rows_per_s"]["workloads"][3] == CELL
    assert [m["name"] for m in man.metrics_of(CELL, "end_to_end")] == \
        ["query_rows_per_s", "setup_s"]
    assert [m["name"] for m in man.metrics_of(CELL, "per_layer")] == METRICS
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["workloads"] == [CELL] and m["moves"] == "query_rows_per_s"
    cfg, sf1, sf2 = (man.config(n) for n in (CONFIG, "tpch_sf1", "tpch_sf2"))
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for word in ("cl 2.4.5", "TPCHColumnPartitionedTable.scala", "SF 2"):
        assert word in entry["source"]
    assert entry["reduced"] == list(cfg["reduced"]) == ["sf", "layout"]
    assert cfg["sf"] == 2 and cfg["rehearsal_sf"] == 0.05
    # nothing of tpch_sf1 is stated more weakly
    assert cfg["guarantees"] == sf1["guarantees"]
    assert cfg["precision"] == sf1["precision"]
    assert cfg["limits"] == sf1["limits"] == {"sum_rel_gap": 1e-9}
    assert cfg["assumed"][:len(sf2["assumed"])] == sf2["assumed"]
    assert list(cfg["tables"]) == TABLES
    for t in ("lineitem", "orders"):
        assert cfg["tables"][t] == sf2["tables"][t]
    assert cfg["tables"]["customer"] == sf1["tables"]["customer"]
    for t in ("supplier", "nation", "region"):
        assert cfg["tables"][t]["generator"] == "tpch_dims"
        assert cfg["tables"][t]["ddl"].endswith(") USING row")
    assert cfg["layout"]["row"] == ["supplier", "nation", "region"]
    mix = man.mix("join_q5")
    assert mix["tables"] == TABLES
    assert mix["cycle"] == mix["warmup"] == ["q5"]
    assert (mix["entry"], mix["loop"], mix["durable"]) == \
        ("embedded", "closed", False)
    q5 = mix["statements"]["q5"]
    assert (q5["table"], q5["reference"], q5["bytes_per_row"]) == \
        ("lineitem", "q5", 29)
    draws = q5["draws"]
    assert [d["region"] for d in draws["region"]] == REGIONS
    assert [d["date"] for d in draws["year"]] == \
        [f"{y}-01-01" for y in range(1993, 1998)]
    for d in draws["year"]:
        p = _params("", int(d["date"][:4]))
        assert d == {k: p[k] for k in ("date", "end", "days", "end_days")}


def test_a_seed_walks_all_25_combinations(man):
    import traffic

    mix, cfg = man.mix("join_q5"), man.config(CONFIG)
    seen = []
    for seed in (1, 2):
        t = traffic.Traffic(man, mix, cfg, 0.01, seed)
        sts = [t.cycle()[0] for _ in range(25)]
        combos = {(st.subst["region"], st.subst["date"]) for st in sts}
        assert len(combos) == 25
        assert all(f"r_name = '{st.subst['region']}'" in st.sql
                   and f">= DATE '{st.subst['date']}'" in st.sql
                   and f"< DATE '{st.subst['end']}'" in st.sql
                   and st.subst["end_days"] - st.subst["days"] in (365, 366)
                   for st in sts)
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
    lines = [json.loads(x) for x in out.out.strip().splitlines()]
    return lines[-1], next(x for x in lines if x.get("line") == "setup")


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
    res, setup = _rehearse(capsys, monkeypatch, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    # all six tables, at the rehearsal's scale
    assert setup["rows"] == {"lineitem": 299995, "orders": 75000,
                             "customer": 7500, "supplier": 500,
                             "nation": 25, "region": 5}
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
    assert set(res["metrics"]) == set(METRICS) - {"device_idle_pct.q5",
                                                  "q5_roofline"}
    value = {k: v["value"] for k, v in res["metrics"].items()}
    assert value["host_fallbacks.q5"] == 0
    assert value["xla_compiles_in_window.q5"] == 0
    assert value["join_device_joins.q5"] == 5
    assert value["join_multikey_joins.q5"] == 1
    assert value["join_row_builds.q5"] == 3
    # five probes of lineitem's three padded batches; the builds are
    # orders and customer (one padded batch each) and the row tables'
    # own rows
    assert value["join_probe_rows.q5"] == 5 * 3 * 131072
    assert value["join_build_rows.q5"] == 2 * 131072 + 500 + 25 + 5


def _drop_a_nation(rows):
    return rows[:-1]


def _off_by_a_millionth(rows):
    return [(rows[0][0], rows[0][1] * (1 + 1e-6))] + rows[1:]


@pytest.mark.parametrize("alter, number", [
    (_drop_a_nation, "exact_mismatches"),
    (_off_by_a_millionth, "sum_rel_gap"),
], ids=["a_nation_dropped", "one_revenue_off_by_1e-6"])
def test_a_broken_answer_is_not_correct(capsys, monkeypatch, alter, number):
    _break_query(monkeypatch, alter)
    res, _ = _rehearse(capsys, monkeypatch)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


# ---- what a traced Q5 carries ------------------------------------------------

def _spans(root):
    yield root
    for c in root.get("children", ()):
        yield from _spans(c)


def _named(root, name):
    return [sp for sp in _spans(root) if sp["name"] == name]


def _main_dispatch(root):
    (sp,) = [sp for sp in _spans(root)
             if sp["name"] in ("jit_compile", "device_execute")]
    return sp["attrs"]


def _three_traced_q5(man):
    """Three Q5 on one session, each with its trace: the first binds
    cold, the others bring a fresh REGION and year."""
    props = config.global_properties()
    saved = (props.decimal_as_float64, props.tracing_enabled)
    props.decimal_as_float64, props.tracing_enabled = False, True
    tables = _tables(man, 0.01, 5)
    s = _session(man, tables)
    recs = []
    try:
        for region, year in DRAWS:
            p = _params(region, year)
            rows = [tuple(r) for r in s.sql(_q5(man, p)).rows()]
            tr = tracing.ring().last().to_dict()
            recs.append({"name": "q5", "kind": "query", "ok": True,
                         "window": True, "traced": True, "ms": tr["root"]["ms"],
                         "answer": rows, "traces": [tr],
                         "rows_read": len(tables["lineitem"]["l_orderkey"]),
                         "bytes_per_row": 29})
    finally:
        s.stop()
        props.decimal_as_float64, props.tracing_enabled = saved
    return recs


@pytest.fixture(scope="module")
def traced_q5(man):
    """As the CPU backend lowers it: the probe's searches are loops."""
    return _three_traced_q5(man)


@pytest.fixture(scope="module")
def traced_q5_merged(man):
    """As the chip lowers it at SF 2: the resolver is steered to the
    sort-merge here, in the test; the program has no switch."""
    from snappydata_tpu.ops import join as dj

    saved = dj.probe_lowering
    dj.probe_lowering = lambda backend, n_probe, n_build: dj.PROBE_MERGE
    try:
        return _three_traced_q5(man)
    finally:
        dj.probe_lowering = saved


def test_a_traced_q5_says_what_its_five_joins_are(traced_q5,
                                                  traced_q5_merged):
    """Five device joins, each probing lineitem's padded slots; one on
    two key pairs (supplier: suppkey and nation); three builds on row
    tables (supplier, nation, region); five merges on the chip's path
    and no loop left, the same rows either way."""
    for recs, merges in ((traced_q5, 0), (traced_q5_merged, 5)):
        for rec in recs:
            attrs = _main_dispatch(rec["traces"][0]["root"])
            assert attrs["join_device_joins"] == 5
            assert attrs["join_multikey_joins"] == 1
            assert attrs["join_row_builds"] == 3
            assert attrs["join_merge_probes"] == merges
            assert (attrs["join_search_loops"] == 0) == (merges == 5)
            assert attrs["join_expand_out_rows"] == 0
            assert attrs["groups_overflow"] == 0
            # five nations grouped on their codes, not by run heads, and
            # reduced without the runs
            assert attrs["gidx_run_lane"] == 0
            assert attrs["run_reduce_slots"] == 0
            assert attrs["join_probe_rows"] == 5 * 131072
            # orders and customer (one padded batch each) and the row
            # tables' own 100, 25 and 5 rows
            assert attrs["join_build_rows"] == 2 * 131072 + 100 + 25 + 5
    assert [r["answer"] for r in traced_q5_merged] \
        == [r["answer"] for r in traced_q5]


def test_a_traced_q5_stays_on_the_device_and_a_fresh_region_compiles_nothing(
        traced_q5):
    first, second, third = (r["traces"][0]["root"] for r in traced_q5)
    for root in (first, second, third):
        assert not _named(root, "host_fallback")
        (host,) = _named(root, "host_ops")
        assert host["attrs"]["ops"] == "Sort"
        assert host["attrs"]["rows_out"] == host["attrs"]["rows_in"] == 5
    # the five builds are sorted by the first statement's bind, cached after
    assert _named(first, "bind")[0]["attrs"]["join_builds_sorted"] == 5
    for root in (second, third):
        assert not _named(root, "join_build")
        assert _named(root, "bind")[0]["attrs"]["join_builds_cached"] == 5
        assert not _named(root, "jit_compile")
        assert not _named(root, "compile")
        assert sum(sp.get("attrs", {}).get("xla_compiles", 0)
                   for sp in _spans(root)) == 0


@pytest.mark.parametrize("name", METRICS)
def test_every_new_metric_reads_a_number_from_the_trace(
        man, traced_q5_merged, name):
    window = traced_q5_merged[1:]      # the first statement is the warm-up
    ctx = {"statements": window, "back": "session", "front": "session",
           "device": {"busy_s": 2.0, "window_s": 2.5}, "window_s": 2.5,
           "peaks": roofline.peaks_for("TPU v5 lite")}
    value = man.read(name, ctx)
    assert isinstance(value, (int, float)) and not isinstance(value, bool)
    expected = {
        "host_fallbacks.q5": 0, "xla_compiles_in_window.q5": 0,
        "join_device_joins.q5": 5, "join_merge_probes.q5": 5,
        "join_search_loops.q5": 0, "join_row_builds.q5": 3,
        "join_multikey_joins.q5": 1, "join_probe_rows.q5": 5 * 131072,
        "join_build_rows.q5": 2 * 131072 + 130, "device_idle_pct.q5": 20.0,
        "q5_roofline": 100.0 * (2 * window[0]["rows_read"] * 29 / 819e9)
        / 2.0}
    if name in expected:
        assert value == pytest.approx(expected[name])
    else:
        assert value > 0
    # a program from before the attrs (the parent): None, not an error
    attr = NEW_ATTRS.get(name)
    if attr is not None:
        bare = json.loads(json.dumps(window))
        for r in bare:
            for sp in _spans(r["traces"][0]["root"]):
                sp.get("attrs", {}).pop(attr, None)
        assert man.read(name, dict(ctx, statements=bare)) is None

"""The quick-start cell `quickstart_100m.groupby` (PR 32) on the CPU: its
generator against the source's rule, its plain reference against a pandas
groupby and against `SnappySession.sql`, the kind that compares a GROUP
BY's rows as a set, its rehearsal whole and broken, its manifest entries,
and what a traced statement carries for the cell's
per-layer metrics. Values and counts, never a device time.
"""

import json
import os
import sys

import numpy as np
import pandas as pd
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability import tracing
from snappydata_tpu.storage.device import batch_bucket

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

CELL = "quickstart_100m.groupby"
SQL = "select sym, avg(id) from testtable group by sym"
METRICS = ["groupby_stmt_ms", "plan_ms.quickstart", "bind_ms.quickstart",
           "device_wait_ms.quickstart", "dispatch_ms.quickstart",
           "device_idle_pct.quickstart", "xla_compiles_in_window.quickstart",
           "host_fallbacks.quickstart", "quickstart_roofline",
           "scatter_slots.quickstart", "dict_space_slots.quickstart",
           "group_slots.quickstart", "gidx_cache_hits.quickstart",
           "isum_scatter_slots.quickstart", "reduce_padded_rows.quickstart",
           "limb_matmul_slots.quickstart", "unspanned_ms.quickstart",
           "admit_ms.quickstart", "plan_lookup_ms.quickstart",
           "launch_ms.quickstart", "finish_ms.quickstart", "gc_ms.quickstart"]
NEW_ATTRS = {"gidx_cache_hits.quickstart": "gidx_cache_hit",
             "isum_scatter_slots.quickstart": "isum_scatter_slots",
             "reduce_padded_rows.quickstart": "reduce_padded_rows",
             "limb_matmul_slots.quickstart": "limb_matmul_slots"}
BATCH_ROWS = 1 << 17


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


def _table(man, sf, seed=1):
    return man.module("generators", "quickstart").generate(
        "testtable", sf, seed)


def _world(man, cols, accumulate="float64"):
    w = World(man, accumulate=accumulate)
    w.insert("testtable", cols)
    return w


def _session(man, cols):
    s = SnappySession(catalog=Catalog())
    s.sql(man.config("quickstart_100m")["tables"]["testtable"]["ddl"])
    s.insert_arrays("testtable", list(cols.values()))
    return s


# ---- the generator ---------------------------------------------------------

@pytest.mark.parametrize("sf, n", [(0.001, 100_000), (0.015, 1_500_000)])
def test_generator_follows_the_sources_rule(man, sf, n):
    c = _table(man, sf, 2147483659)
    assert list(c) == ["id", "sym"]
    assert c["id"].dtype == np.int64 and c["sym"].dtype == object
    assert len(c["id"]) == len(c["sym"]) == n
    assert (c["id"] == np.arange(n)).all()
    assert set(c["sym"]) == {f"sym{k}" for k in range(100)}
    for i in (0, 1, 99, 100, 101, n // 2, n - 1):
        assert c["sym"][i] == "sym" + str(i % 100)
    assert (pd.Series(c["sym"]).value_counts() == n // 100).all()
    assert max(len(s) for s in set(c["sym"])) <= 10     # VARCHAR(10)
    # the source's data has no randomness: the seed changes nothing
    other = _table(man, sf, 7)
    assert (other["id"] == c["id"]).all() and (other["sym"] == c["sym"]).all()
    with pytest.raises(KeyError):
        man.module("generators", "quickstart").generate("lineitem", sf, 1)


def test_full_scale_is_the_sources_100_million_rows(man):
    gen = man.module("generators", "quickstart")
    cfg = man.config("quickstart_100m")
    assert gen.n_rows(cfg["sf"]) == 100_000_000
    assert 1_000_000 <= gen.n_rows(cfg["rehearsal_sf"]) <= 2_000_000
    # 763 batches ride the 768 bucket: 0.66 % of the slots are padding
    batches = -(-100_000_000 // BATCH_ROWS)
    assert (batches, batch_bucket(batches)) == (763, 768)


# ---- the reference ---------------------------------------------------------

def _pandas_answer(cols):
    g = pd.DataFrame(cols).groupby("sym")["id"].mean()
    return [(k, float(v)) for k, v in g.items()]


@pytest.mark.parametrize("sf", [0.001, 0.0123457, 0.015])
def test_reference_equals_a_pandas_groupby_mean(man, sf):
    cols = _table(man, sf)
    w = _world(man, cols)
    got = w.answer("groupby_sym", {})
    assert len(got) == 100
    assert [type(v) for v in got[0]] == [str, float]
    assert compare(got, _pandas_answer(cols)) == (0.0, 0)
    # once per state of the world, and the statement as its text reads
    ref = w.refs["groupby_sym"]
    built = ref.built
    assert w.answer("groupby_sym", {}) == got and ref.built is built
    assert compare(ref.direct({}), got) == (0.0, 0)


def test_reference_follows_writes_and_odd_tables(man):
    """Groups of unequal size, a sym that is a prefix of another, ids
    whose sums pass 2**53, a delete and a second insert."""
    ids = np.array([2 ** 60, 2 ** 60 + 2, 5, 7, 9, -4, 11], dtype=np.int64)
    sym = np.array(["a", "a", "ab", "ab", "ab", "b", "tenletters"],
                   dtype=object)
    w = _world(man, {"id": ids, "sym": sym})
    assert w.answer("groupby_sym", {}) == [
        ("a", float(2 ** 60 + 1)), ("ab", 7.0), ("b", -4.0),
        ("tenletters", 11.0)]
    w.delete_range("testtable", "id", 5, 6)
    w.insert("testtable", {"id": np.array([6], dtype=np.int64),
                           "sym": np.array(["b"], dtype=object)})
    assert w.answer("groupby_sym", {}) == [
        ("a", float(2 ** 60 + 1)), ("ab", 8.0), ("b", 1.0),
        ("tenletters", 11.0)]
    wide = _world(man, {"id": ids[:1],
                        "sym": np.array(["elevenchars"], dtype=object)})
    with pytest.raises(ValueError):
        wide.answer("groupby_sym", {})


@pytest.mark.parametrize("sf", [0.001, 0.015])
def test_reference_equals_the_program(man, sf, _restore_knobs):
    """`session.sql` of the mix's text under the chip's dtype policy: the
    rows as a set, every average exact."""
    _restore_knobs.decimal_as_float64 = False
    cols = _table(man, sf)
    exp = _world(man, cols).answer("groupby_sym", {})
    assert man.mix("groupby_sym")["statements"]["groupby_sym"]["sql"] == SQL
    s = _session(man, cols)
    try:
        for _ in range(2):      # the second finds the group index cached
            got = sorted(tuple(r) for r in s.sql(SQL).rows())
            assert compare(got, exp) == (0.0, 0)
    finally:
        s.stop()


@pytest.mark.parametrize("policy", [False, True])
def test_avg_of_a_bigint_divides_in_float64(policy, _restore_knobs):
    """An exact int64 sum over an exact count, divided in float64 under
    either dtype policy: float32 holds 24 bits, these ids need 41."""
    _restore_knobs.decimal_as_float64 = policy
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k BIGINT NOT NULL, g INT NOT NULL) "
              "USING column")
        k = 2 ** 40 + np.arange(1, 20001, dtype=np.int64) * 2
        s.insert_arrays("t", [k, (np.arange(20000) % 2).astype(np.int32)])
        assert s.sql("SELECT avg(k) FROM t").rows()[0][0] \
            == float(2 ** 40 + 20001)
        rows = sorted(tuple(r) for r in s.sql(
            "SELECT g, avg(k), sum(k) / count(*) FROM t GROUP BY g").rows())
        assert rows == [(0, float(2 ** 40 + 20000), float(2 ** 40 + 20000)),
                        (1, float(2 ** 40 + 20002), float(2 ** 40 + 20002))]
    finally:
        s.stop()


@pytest.mark.parametrize("policy", [False, True])
def test_having_and_order_by_divide_integer_aggregates_in_float64(
        policy, _restore_knobs):
    """HAVING and ORDER BY take the post-aggregate builder too: an
    average near 2**40 is met exactly, and two that float32 cannot tell
    apart keep their order."""
    _restore_knobs.decimal_as_float64 = policy
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k BIGINT NOT NULL, g INT NOT NULL) "
              "USING column")
        k = 2 ** 40 + np.arange(1, 20001, dtype=np.int64) * 2
        s.insert_arrays("t", [k, (np.arange(20000) % 2).astype(np.int32)])
        assert [tuple(r) for r in s.sql(
            f"SELECT g FROM t GROUP BY g HAVING avg(k) = {2 ** 40 + 20002}"
        ).rows()] == [(1,)]
        assert [tuple(r) for r in s.sql(
            "SELECT g, count(*) FROM t GROUP BY g "
            f"HAVING sum(k) / count(*) > {2 ** 40 + 20001}").rows()] \
            == [(1, 10000)]
        assert [tuple(r) for r in s.sql(
            "SELECT g, avg(k) a FROM t GROUP BY g ORDER BY a DESC").rows()] \
            == [(1, float(2 ** 40 + 20002)), (0, float(2 ** 40 + 20000))]
    finally:
        s.stop()


def test_control_in_float32_accumulators_is_not_correct(man):
    out = control.control_gap(man, CELL, seed=77, cycles=2, sf=0.015)
    assert out["compared"] == 3 and out["exact_mismatches"] == 0
    assert out["sum_rel_gap"] > 10 * out["limit"]


# ---- a GROUP BY's rows are a set -------------------------------------------

class _Statement:
    def __init__(self, spec):
        self.spec, self.subst = spec, {}


def _judge(man, world, answer):
    """What `run.replay` does with one answered statement of the kind."""
    kind = man.module("kinds", "query_set")
    spec = man.mix("groupby_sym")["statements"]["groupby_sym"]
    rec = {"answer": answer}
    exp = kind.expected(world, _Statement(spec), rec)
    assert rec["rows_read"] == world.rows["testtable"]
    return compare(rec["answer"], exp)


def test_query_set_compares_rows_as_a_set_and_forgives_nothing_else(man):
    world = _world(man, _table(man, 0.001))
    good = world.answer("groupby_sym", {})
    rng = np.random.default_rng(5)
    for _ in range(3):
        shuffled = [good[i] for i in rng.permutation(len(good))]
        assert shuffled != good
        assert _judge(man, world, shuffled) == (0.0, 0)
    # a missing group, a doubled group, an average off by 1e-6
    gap, wrong = _judge(man, world, good[:40] + good[41:])
    assert wrong > 0
    gap, wrong = _judge(man, world, good + [good[17]])
    assert wrong > 0
    moved = list(good)
    moved[63] = (moved[63][0], moved[63][1] * (1 + 1e-6))
    gap, wrong = _judge(man, world, moved)
    assert wrong == 0 and 0.9e-6 < gap < 1.1e-6
    # a group under another's name is not the same set
    renamed = list(good)
    renamed[3], renamed[4] = (good[3][0], good[4][1]), (good[4][0], good[3][1])
    gap, wrong = _judge(man, world, renamed)
    assert gap > 1e-9 and wrong == 0
    # NULL keys sort, and first
    kind = man.module("kinds", "query_set")
    assert kind._by_key([("b", 1.0), (None, 2.0), ("a", 3.0)], {}) \
        == [(None, 2.0), ("a", 3.0), ("b", 1.0)]


def test_query_set_is_query_in_everything_else(man):
    import traffic

    mix, cfg = man.mix("groupby_sym"), man.config("quickstart_100m")
    t = traffic.Traffic(man, mix, cfg, 0.001, 9)
    assert t.columns() == {"testtable": ["id", "sym"]}
    sts = t.warmup() + t.cycle() + t.cycle()
    assert [(st.name, st.kind, st.sql, st.params) for st in sts] \
        == [("groupby_sym", "query_set", SQL, [])] * 3


# ---- the manifest ------------------------------------------------------------

def test_manifest_takes_the_quickstart_cell(man):
    assert manifest.problems(man) == []
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("quickstart_100m", "groupby_sym", 1)
    e2e = {e["name"]: e for e in man.doc["end_to_end"]}
    # the three stay, in this order; later PRs append their cells
    before = ["tpch_sf2.scan", "tpch_sf1.join", CELL]
    assert [c for c in e2e["query_rows_per_s"]["workloads"]
            if c in before] == before
    assert [m["name"] for m in man.metrics_of(CELL, "end_to_end")] == \
        ["query_rows_per_s", "setup_s"]
    assert [m["name"] for m in man.metrics_of(CELL, "per_layer")] == METRICS
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["workloads"] == [CELL] and m["moves"] == "query_rows_per_s"
    cfg, sf2 = man.config("quickstart_100m"), man.config("tpch_sf2")
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "quickstart_100m")
    # present where PR 32 put them, after the entries before it; later
    # PRs append theirs
    configs = [c["name"] for c in man.doc["configs"]]
    cells = [w["name"] for w in man.doc["workloads"]]
    assert configs[:3] == ["tpch_sf2", "tpch_sf1", "quickstart_100m"]
    assert cells.index(CELL) == 3
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for word in ("performance_apache_spark.md", "Quickstart.scala", "100 M"):
        assert word in entry["source"]
    # nothing was cut: every shape and the scale are the source's
    assert entry["reduced"] == list(cfg["reduced"]) == []
    assert cfg["sf"] == 1 and cfg["limits"] == sf2["limits"]
    assert cfg["precision"]["accumulate"] == "float64"
    assert cfg["guarantees"][0] == sf2["guarantees"][0] == "snapshot reads"
    assert list(cfg["tables"]) == ["testtable"]
    assert "id BIGINT NOT NULL, sym VARCHAR(10) NOT NULL" in \
        cfg["tables"]["testtable"]["ddl"]
    mix = man.mix("groupby_sym")
    assert mix["cycle"] == mix["warmup"] == ["groupby_sym"]
    assert (mix["loop"], mix["durable"], mix["trace_seconds"]) == \
        ("closed", False, 3)
    st = mix["statements"]["groupby_sym"]
    assert st["sql"] == SQL and "draws" not in st and "bind" not in st
    assert (st["kind"], st["table"], st["bytes_per_row"]) == \
        ("query_set", "testtable", 9)


# ---- the entry ---------------------------------------------------------------

def test_mix_rides_the_embedded_entry_that_was_there(man):
    """ISSUE 32 names the traffic's entry as `embedded`: the deployment
    brings no entry of its own, and `correct` alone judges a program
    that cannot hold the configuration's precision."""
    assert man.mix("groupby_sym")["entry"] == "embedded"
    assert sorted(os.listdir(os.path.join(BENCH, "entries"))) \
        == ["embedded.py", "flight.py"]
    entry = man.module("entries", "embedded")
    assert (entry.FRONT, entry.BACK) == ("session", "session")


# ---- a run, whole and broken -------------------------------------------------

def _rehearse(capsys, monkeypatch, trace=0, seed=2147483659):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = bench.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.3", "--trace", str(trace), "--cpu-rehearsal",
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
    assert set(res["metrics"]) == set(METRICS) - {
        "device_idle_pct.quickstart", "quickstart_roofline"}
    value = {k: v["value"] for k, v in res["metrics"].items()}
    assert value["host_fallbacks.quickstart"] == 0
    assert value["xla_compiles_in_window.quickstart"] == 0
    # every statement of the window found the warm-up's group index
    assert value["gidx_cache_hits.quickstart"] == 1
    assert value["isum_scatter_slots.quickstart"] == 1
    # the CPU backend's `auto` keeps the scatter: the limb product is
    # the chip's (tests/test_agg_strategy.py walks it here)
    assert value["limb_matmul_slots.quickstart"] == 0
    assert value["dict_space_slots.quickstart"] == 0
    assert value["group_slots.quickstart"] == 128
    assert value["reduce_padded_rows.quickstart"] == 12 * BATCH_ROWS


def _drop_a_group(rows):
    return rows[:-1]


def _move_an_average(rows):
    return [rows[0][:1] + (rows[0][1] * (1 + 1e-6),)] + rows[1:]


def _float32_accumulators(rows):
    """Each group's ids (the source's rule at the rehearsal's 1,500,000
    rows) summed in float32, pairwise as NumPy adds."""
    out = []
    for sym, _avg in rows:
        ids = np.arange(int(sym[3:]), 1_500_000, 100, dtype=np.int64)
        out.append((sym, float(ids.sum(dtype=np.float32)) / len(ids)))
    return out


@pytest.mark.parametrize("alter, number", [
    (_drop_a_group, "exact_mismatches"),
    (_move_an_average, "sum_rel_gap"),
    (_float32_accumulators, "sum_rel_gap"),
], ids=["a_group_dropped", "an_average_off_by_1e-6", "float32_accumulators"])
def test_a_broken_answer_is_not_correct(capsys, monkeypatch, alter, number):
    _break_query(monkeypatch, alter)
    res = _rehearse(capsys, monkeypatch)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


# ---- what a traced statement carries ---------------------------------------

def _spans(root):
    yield root
    for c in root.get("children", ()):
        yield from _spans(c)


def _main_dispatch(root):
    (sp,) = [sp for sp in _spans(root)
             if sp["name"] in ("jit_compile", "device_execute")
             and sp["attrs"].get("phase", "main") == "main"]
    return sp["attrs"]


ROWS = 300_000      # three batches, the last partly full: the 3 bucket


@pytest.fixture(scope="module")
def traced(man):
    """Three statements on one session under the chip's dtype policy,
    each with its trace: the first fills the group-index cache."""
    props = config.global_properties()
    saved = (props.decimal_as_float64, props.tracing_enabled)
    props.decimal_as_float64, props.tracing_enabled = False, True
    cols = _table(man, ROWS / 100_000_000)
    s = _session(man, cols)
    recs = []
    try:
        for _ in range(3):
            rows = [tuple(r) for r in s.sql(SQL).rows()]
            tr = tracing.ring().last().to_dict()
            recs.append({"name": "groupby_sym", "kind": "query_set",
                         "ok": True, "window": True, "traced": True,
                         "ms": tr["root"]["ms"], "answer": rows,
                         "traces": [tr], "rows_read": ROWS,
                         "bytes_per_row": 9})
    finally:
        s.stop()
        props.decimal_as_float64, props.tracing_enabled = saved
    return recs


def test_a_traced_statement_says_where_its_group_index_came_from(traced):
    first, second, third = (_main_dispatch(r["traces"][0]["root"])
                            for r in traced)
    assert first["gidx_cache_hit"] == 0
    assert second["gidx_cache_hit"] == third["gidx_cache_hit"] == 1
    # the first ran both phases, the others the main phase alone
    phases = [[sp["attrs"].get("phase") for sp in _spans(r["traces"][0]["root"])
               if sp["name"] in ("jit_compile", "device_execute")]
              for r in traced]
    assert phases == [["pre", "main"], ["main"], ["main"]]
    for attrs in (first, second, third):
        # one BIGINT sum, by the scatter (100 groups are past the unroll)
        assert attrs["isum_scatter_slots"] == 1
        assert attrs["scatter_slots"] >= attrs["isum_scatter_slots"]
        assert attrs["dict_space_slots"] == 0
        assert attrs["group_slots"] == 128       # 100 syms, padded
        assert attrs["reduce_padded_rows"] == batch_bucket(3) * BATCH_ROWS \
            == 3 * BATCH_ROWS > ROWS
        assert attrs["groups_overflow"] == 0
        # a dictionary key: the group index is no run-head index, and no
        # family reduces over runs
        assert attrs["gidx_run_lane"] == 0
        assert attrs["run_reduce_slots"] == 0
    assert sorted(traced[0]["answer"]) == sorted(traced[2]["answer"])


def test_the_new_attrs_are_zero_where_there_is_nothing_to_count():
    """A float sum over few groups is no integer scatter; a plan that
    aggregates nothing walks no slots; a statement whose group index
    cannot be cached (a join) says 0."""
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k INT, v DOUBLE) USING column")
        s.insert_arrays("t", [np.arange(1000, dtype=np.int32) % 7,
                              np.ones(1000)])
        s.sql("SELECT k, sum(v) FROM t GROUP BY k").rows()
        grouped = _main_dispatch(tracing.ring().last().to_dict()["root"])
        s.sql("SELECT k, v FROM t WHERE k = 3").rows()
        plain = _main_dispatch(tracing.ring().last().to_dict()["root"])
    finally:
        s.stop()
    assert grouped["isum_scatter_slots"] == 0
    assert grouped["reduce_padded_rows"] >= 1000
    assert grouped["gidx_cache_hit"] == 0
    for key in ("gidx_cache_hit", "isum_scatter_slots", "reduce_padded_rows",
                "scatter_slots", "group_slots"):
        assert plain[key] == 0


@pytest.mark.parametrize("name", METRICS)
def test_every_new_metric_reads_a_number_from_the_trace(man, traced, name):
    window = traced[1:]        # the first statement is the warm-up
    ctx = {"statements": window, "back": "session", "front": "session",
           "device": {"busy_s": 2.0, "window_s": 2.5}, "window_s": 2.5,
           "peaks": roofline.peaks_for("TPU v5 lite")}
    value = man.read(name, ctx)
    assert isinstance(value, (int, float)) and not isinstance(value, bool)
    expected = {
        "host_fallbacks.quickstart": 0,
        "xla_compiles_in_window.quickstart": 0,
        "dict_space_slots.quickstart": 0, "group_slots.quickstart": 128,
        "gidx_cache_hits.quickstart": 1,
        "isum_scatter_slots.quickstart": 1,
        "reduce_padded_rows.quickstart": 3 * BATCH_ROWS,
        "limb_matmul_slots.quickstart": 0,
        "device_idle_pct.quickstart": 20.0,
        "quickstart_roofline": 100.0 * (2 * ROWS * 9 / 819e9) / 2.0}
    if name in expected:
        assert value == pytest.approx(expected[name])
    elif name == "gc_ms.quickstart":
        assert value >= 0       # 0 where the collector never ran
    else:
        assert value > 0
    # with the warm-up among them the median still says hit; its sum not
    if name == "gidx_cache_hits.quickstart":
        assert man.read(name, dict(ctx, statements=traced)) == 1
        assert man.read(name, dict(ctx, statements=traced[:1])) == 0
    # a program from before the attrs (the parent): None, not an error
    attr = NEW_ATTRS.get(name)
    if attr is not None:
        bare = json.loads(json.dumps(window))
        for r in bare:
            for sp in _spans(r["traces"][0]["root"]):
                sp.get("attrs", {}).pop(attr, None)
        assert man.read(name, dict(ctx, statements=bare)) is None
    # the readers count this kind alone: a `query` beside it is not read
    if name in NEW_ATTRS or name.startswith(("plan_ms", "bind_ms",
                                             "device_wait_ms")):
        other = [dict(r, kind="query") for r in window]
        assert man.read(name, dict(ctx, statements=other)) is None

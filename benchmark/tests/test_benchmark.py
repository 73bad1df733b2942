"""Tests of the yardstick: the manifest, the arithmetic, the generator,
the reference against itself, its control, and a CPU rehearsal of every
cell with the timed path whole and broken.

    python -m pytest benchmark/tests -q
"""

import copy
import json
import os
import shutil

import numpy as np
import pytest

import control
import devtrace
import manifest
import roofline
import run as bench
import spans
from reference import World, compare

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
CELLS = ["tpch_sf2.scan", "tpch_sf2.refresh"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


def rehearse(capsys, workload, trace=0, root=ROOT, seed=2147483659):
    rc = bench.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "0.5", "--trace", str(trace),
                     "--cpu-rehearsal", "--root", root])
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out


# ---- the manifest --------------------------------------------------------

def test_manifest_is_what_the_driver_takes():
    m = manifest.Manifest(ROOT)
    assert manifest.problems(m) == []
    # the cells PR 24 defined, in its order; later PRs add their own
    assert [w["name"] for w in m.doc["workloads"]
            if w["name"] in CELLS] == CELLS
    assert all(w["chips"] == 1 for w in m.doc["workloads"])


@pytest.mark.parametrize("edit, what", [
    (lambda d: d["configs"][0].update(source="TPC-H — SF 4"), "source"),
    (lambda d: d["configs"][0].update(source="x" * 201), "source"),
    (lambda d: d["end_to_end"][0].update(unit="rows per second"), "unit"),
    (lambda d: d["workloads"][0].update(name="tpch sf2/scan"), "name"),
    (lambda d: d["workloads"][0].update(traffic="no_such_mix"), "mix"),
    (lambda d: d["per_layer"][0].update(moves="stmt_p50_ms"),
     "does not report"),
    (lambda d: [w.update(chips=4) for w in d["workloads"]], "four-chip"),
    (lambda d: d["per_layer"][0].update(why="x"), "keys"),
    (lambda d: d["per_layer"][0].update(name="no_such_metric"), "metric"),
    (lambda d: d["end_to_end"][0].update(unit="rows"), "differs from its"),
])
def test_manifest_check_refuses(edit, what):
    m = manifest.Manifest(ROOT)
    m.doc = copy.deepcopy(m.doc)
    edit(m.doc)
    found = manifest.problems(m)
    assert any(what in p for p in found), found


# ---- arithmetic ------------------------------------------------------------

def test_self_time_does_not_count_nested_spans_twice():
    tree = {"name": "request", "ms": 100.0, "children": [
        {"name": "parse", "ms": 2.0},
        {"name": "bind", "ms": 30.0, "children": [
            {"name": "transfer", "ms": 10.0},
            {"name": "bind", "ms": 5.0}]},
        {"name": "transfer", "ms": 50.0}]}
    by = spans.self_ms_by_name(tree)
    # bind: 30 - 15 of its own, + 5 nested; transfer: 10 + 50
    assert by == {"parse": 2.0, "bind": 20.0, "transfer": 60.0}
    assert sum(by.values()) + spans.self_ms(tree) == 100.0
    assert spans.count(tree, ("bind", "transfer")) == 4
    assert spans.median([3, 1, 2, 10]) == 2.5
    assert spans.percentile(list(range(101)), 95) == 95


def test_trace_reduction_on_the_recorded_fixture():
    with open(os.path.join(FIXTURES, "trace_events.json")) as f:
        fx = json.load(f)
    got = devtrace.reduce(fx["events"])
    for key, exp in fx["expect"].items():
        assert got[key] == pytest.approx(exp, rel=1e-9), key
    assert got["busy_s"] <= got["window_s"]
    assert got["device_ops"][0][1] >= got["device_ops"][-1][1]
    assert [g[0].split(" ")[0] for g in got["idle_gaps"]] == fx["gap_labels"]
    with pytest.raises(ValueError):
        devtrace.reduce({"devices": {}, "host": fx["events"]["host"]})
    # and by hand: two devices, overlapping ops, a window that clips
    hand = {"devices": {"/device:TPU:0": [["a", 0, 40], ["b", 30, 30],
                                          ["a", 80, 40]],
                        "/device:TPU:1": [["a", 10, 20]]},
            "host": [[devtrace.WINDOW, 10, 100],
                     ["bench:q6", 55, 30]]}
    # with the statement's spans laid out from its mark, the gap's
    # middle (70 ns) falls in `bind`
    named = devtrace.reduce(hand, phases=[[["parse", 0.0, 10e-6],
                                           ["bind", 10e-6, 15e-6]]])
    assert [g[0].split(" ")[0] for g in named["idle_gaps"]] == ["q6/bind"]
    got = devtrace.reduce(hand)
    # device 0: [10,60) + [80,110) = 80 ns; device 1: 20 ns; mean 50
    assert got["busy_s"] == pytest.approx(50e-9)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["device_ops"] == [["a", pytest.approx(40e-9)],
                                 ["b", pytest.approx(15e-9)]]
    assert [g[0].split(" ")[0] for g in got["idle_gaps"]] == ["q6"]
    assert got["idle_gaps"][0][1] == pytest.approx(20e-9)


def test_roofline_bytes_from_shapes():
    m = manifest.Manifest(ROOT)
    mix = m.mix("scan_q1_q6")
    recs = [{"kind": "query", "rows_read": 24_000_000,
             "bytes_per_row": mix["statements"][n]["bytes_per_row"]}
            for n in ("q1", "q6")] + [{"kind": "insert_orders"}]
    assert roofline.logical_bytes(recs) == 24_000_000 * (22 + 16)
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    # 819 MB read in 10 ms of busy time is a tenth of the peak
    assert roofline.roofline_pct(819_000_000, 0.010, peaks) == \
        pytest.approx(10.0)
    assert roofline.roofline_pct(0, 1.0, peaks) is None
    assert roofline.roofline_pct(10, 0.0, peaks) is None
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9")


# ---- the generator ---------------------------------------------------------

def test_generator_keeps_the_specs_shapes():
    m = manifest.Manifest(ROOT)
    gen = m.module("generators", "tpch")
    li, o = gen.generate("lineitem", 0.02, 77), gen.generate("orders",
                                                             0.02, 77)
    cfg = m.config("tpch_sf2")
    for table, cols in (("lineitem", li), ("orders", o)):
        # the DDL's columns, in its order: 16 and 9 as cl 1.4 lists them
        ddl = cfg["tables"][table]["ddl"]
        declared = [w.split()[0] for w in
                    ddl[ddl.index("(") + 1:ddl.index(") USING")].split(",")]
        assert list(cols) == declared
    assert len(li) == 16 and len(o) == 9
    n = len(o["o_orderkey"])
    assert n == 30000 and len(li["l_orderkey"]) == \
        int((np.arange(n) % 7 + 1).sum()) == 119995
    keys, lines = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, o["o_orderkey"])
    assert lines.min() == 1 and lines.max() == 7
    assert np.array_equal(o["o_orderkey"][:10],
                          [1, 2, 3, 4, 5, 6, 7, 8, 33, 34])
    assert li["l_linenumber"][:int(lines[0])].tolist() == \
        list(range(1, int(lines[0]) + 1))
    part = li["l_partkey"]
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    assert np.array_equal(np.rint(li["l_extendedprice"] * 100),
                          li["l_quantity"] * retail)
    odate = np.repeat(o["o_orderdate"], lines)
    assert ((li["l_shipdate"] - odate >= 1)
            & (li["l_shipdate"] - odate <= 121)).all()
    assert ((li["l_receiptdate"] - li["l_shipdate"] >= 1)
            & (li["l_receiptdate"] - li["l_shipdate"] <= 30)).all()
    assert set(li["l_returnflag"][li["l_receiptdate"] > gen.CURRENTDATE]) \
        == {"N"}
    assert set(li["l_linestatus"][li["l_shipdate"] > gen.CURRENTDATE]) \
        == {"O"}
    width = np.vectorize(len)
    assert 10 <= width(li["l_comment"]).min() and \
        width(li["l_comment"]).max() <= 43
    assert 19 <= width(o["o_comment"]).min() and \
        width(o["o_comment"]).max() <= 78
    assert set(width(o["o_clerk"])) == {15}
    assert len(set(li["l_shipinstruct"])) == 4 and \
        len(set(li["l_shipmode"])) == 7
    # the same seed, the same data; another seed, other data, same size
    gen.release()
    again = gen.generate("lineitem", 0.02, 77)
    assert all(np.array_equal(again[c], li[c]) for c in li)
    other = gen.generate("lineitem", 0.02, 78)
    assert len(other["l_orderkey"]) == len(li["l_orderkey"])
    assert not np.array_equal(other["l_partkey"], li["l_partkey"])
    # RF1 above every loaded key, batch after batch; RF2 from the bottom
    b0, b1 = gen.refresh(0.02, 77, 0, 30), gen.refresh(0.02, 77, 1, 30)
    assert b0["orders"]["o_orderkey"].min() > o["o_orderkey"].max()
    assert b1["orders"]["o_orderkey"].min() > b0["orders"]["o_orderkey"].max()
    assert len(b0["lineitem"]["l_orderkey"]) == \
        int((np.arange(30) % 7 + 1).sum())
    lo, hi = gen.key_range(0.02, 1, 30)
    assert ((o["o_orderkey"] >= lo) & (o["o_orderkey"] < hi)).sum() == 30
    assert lo == o["o_orderkey"][30]


# ---- the reference and its control -----------------------------------------

def _world(accumulate="float64", sf=0.02, seed=77):
    m = manifest.Manifest(ROOT)
    gen = m.module("generators", "tpch")
    w = World(m, accumulate=accumulate)
    keep = {"lineitem": m.module("references", "q1").COLUMNS["lineitem"]
            + ["l_orderkey"], "orders": ["o_orderkey"]}
    for t in ("lineitem", "orders"):
        cols = gen.generate(t, sf, seed)
        w.insert(t, {c: cols[c] for c in keep[t]})
    return m, gen, w, keep


def test_reference_histograms_equal_the_statements_as_written():
    m, gen, w, keep = _world()
    q6 = {"year": 1995, "disc": 0.06, "qty": 24}
    q1 = {"delta": 73}
    w.answer("q6", q6), w.answer("q1", q1)
    for step in range(3):
        for name, p in (("q6", q6), ("q1", q1)):
            assert compare(w.answer(name, p), w.refs[name].direct(p)) == \
                (pytest.approx(0.0, abs=1e-13), 0)
        before = w.answer("q6", q6)
        batch = gen.refresh(0.02, 77, step, 30)
        for t in ("orders", "lineitem"):
            w.insert(t, {c: batch[t][c] for c in keep[t]})
        lo, hi = gen.key_range(0.02, step, 30)
        assert w.delete_range("orders", "o_orderkey", lo, hi) == 30
        assert w.delete_range("lineitem", "l_orderkey", lo, hi) > 0
        assert w.answer("q6", q6) != before
    assert w.rows["orders"] == 30000
    assert len(w.live("orders", "o_orderkey")) == 30000


@pytest.mark.parametrize("workload", CELLS)
def test_control_in_float32_accumulators_is_not_correct(workload):
    """The reference in the program's place with float32 accumulators, at
    a size a test can hold (the readings at the cells' own sizes are in
    PERF.md): it has to fail the number a run compares."""
    out = control.control_gap(manifest.Manifest(ROOT), workload, seed=77,
                              cycles=1, sf=0.02)
    assert out["compared"] > 0 and out["exact_mismatches"] == 0
    assert out["sum_rel_gap"] > 3 * out["limit"]


# ---- a run, whole and broken ---------------------------------------------

@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_ends_in_the_contracts_line(capsys, workload):
    m = manifest.Manifest(ROOT)
    for trace, group in ((0, "end_to_end"), (1, "per_layer")):
        res, out = rehearse(capsys, workload, trace)
        assert set(res) == RESULT_KEYS and list(res)[-1] == "checks"
        assert res["correct"] is True and res["failed"] == 0
        assert res["attempted"] > 0
        assert res["device"]["platform"] == "cpu"
        named = {x["name"] for x in m.metrics_of(workload, group)}
        assert set(res["metrics"]) <= named
        if not trace:
            assert set(res["metrics"]) == named
            assert all(v["value"] > 0 for v in res["metrics"].values())
        else:
            # no device number from a CPU run
            assert not any(x["source"] == "device_trace"
                           and x["name"] in res["metrics"]
                           for x in m.metrics_of(workload, group))
            assert "busy_s" not in res["device"]
        assert out.err.strip().splitlines()[-1].startswith("check ")


def _break(monkeypatch, method, wrap):
    """Every entry's `Engine.<method>` goes through `wrap(real)`: the
    timed path broken underneath the harness."""
    real_module = manifest.Manifest.module

    def module(self, group, name):
        mod = real_module(self, group, name)
        if group == "entries" and not hasattr(mod, "broken"):
            setattr(mod.Engine, method, wrap(getattr(mod.Engine, method)))
            mod.broken = True
        return mod
    monkeypatch.setattr(manifest.Manifest, "module", module)


def _alter_answer(monkeypatch):
    _break(monkeypatch, "query", lambda real: lambda self, sql, params: [
        tuple(v * (1 + 1e-6) if isinstance(v, float) else v for v in r)
        for r in real(self, sql, params)])


def _drop_writes(monkeypatch):
    _break(monkeypatch, "insert", lambda real: lambda self, t, cols: None)


def _half_the_batch(monkeypatch):
    _break(monkeypatch, "insert", lambda real: lambda self, t, cols: real(
        self, t, {k: v[:len(v) // 2] for k, v in cols.items()}))


def _stale_read(monkeypatch):
    _break(monkeypatch, "execute", lambda real: lambda self, sql, p: None)


@pytest.mark.parametrize("workload, fault, number", [
    ("tpch_sf2.scan", _alter_answer, "sum_rel_gap"),
    ("tpch_sf2.refresh", _alter_answer, "sum_rel_gap"),
    ("tpch_sf2.refresh", _drop_writes, "rowcount_diff"),
    ("tpch_sf2.refresh", _half_the_batch, "rowcount_diff"),
    ("tpch_sf2.refresh", _stale_read, "sum_rel_gap"),
])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, workload,
                                            fault, number):
    fault(monkeypatch)
    res, _ = rehearse(capsys, workload)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]
    assert res["failed"] > 0 or number == "rowcount_diff"


EXTRA = {
    "generators/events.py": """
import numpy as np


def generate(table, sf, seed):
    rng = np.random.default_rng(seed)
    n = max(100, int(200000 * sf))
    return {"e_id": np.arange(n, dtype=np.int64),
            "e_day": rng.integers(0, 30, n).astype(np.int32),
            "e_val": rng.integers(0, 1000, n) / 4.0}
""",
    "references/sum_by_day.py": """
import numpy as np

COLUMNS = {"events": ["e_day", "e_val"]}


class Reference:
    def __init__(self, world):
        self.world = world

    def answer(self, p):
        day, val = (self.world.live("events", c) for c in COLUMNS["events"])
        m = day < p["day"]
        return [(int(d), self.world.sum(val[m & (day == d)]),
                 int((m & (day == d)).sum())) for d in np.unique(day[m])]
""",
    "kinds/trim.py": """
from traffic import Statement


def make(traffic, name, spec, k, warmup):
    return Statement(name, "trim", spec, keys=(spec["below"] * k,
                                               spec["below"] * (k + 1)))


def columns(manifest, spec):
    return {"events": ["e_id"]}


def send(engine, st, rec, rows):
    engine.execute(st.spec["sql"], list(st.keys))


def apply(world, st, keep):
    world.delete_range("events", "e_id", *st.keys)
""",
    "readers/stmt_count.py": """
def read(ctx, statement):
    return sum(1 for r in ctx['statements'] if r['name'] == statement)
""",
}


def test_a_cell_is_added_by_files_and_entries_alone(capsys, tmp_path):
    """A new query on a new table: a configuration, a generator, a
    reference, a kind of statement, a mix, a cell and a layer metric
    with its reader, all in a directory of their own. No file of the
    benchmark is edited."""
    m = manifest.Manifest(ROOT)
    new = tmp_path / "extra"
    for d in ("configs", "mixes", "metrics"):
        (new / d).mkdir(parents=True)
    for path, text in EXTRA.items():
        (new / path).parent.mkdir(exist_ok=True)
        (new / path).write_text(text)
    cfg = m.config("tpch_sf2")
    cfg.update(name="events_tiny", rehearsal_sf=0.01, tables={"events": {
        "generator": "events", "ddl": "CREATE TABLE events (e_id BIGINT, "
        "e_day INT, e_val DOUBLE) USING column"}})
    (new / "configs" / "events_tiny.json").write_text(json.dumps(cfg))
    mix = {"entry": "embedded", "loop": "closed", "tables": ["events"],
           "durable": False, "readback": ["events"], "statements": {
               "by_day": {"kind": "query", "reference": "sum_by_day",
                          "table": "events", "bytes_per_row": 12,
                          "sql": "SELECT e_day, sum(e_val), count(*) FROM "
                                 "events WHERE e_day < {day} GROUP BY e_day "
                                 "ORDER BY e_day",
                          "draws": {"day": {"int_range": [5, 25],
                                            "name": "day"}}},
               "trim": {"kind": "trim", "below": 7, "sql":
                        "DELETE FROM events WHERE e_id >= ? AND e_id < ?"}},
           "cycle": ["by_day", "trim", "by_day"]}
    (new / "mixes" / "events_mix.json").write_text(json.dumps(mix))
    lm = {"name": "by_day_count", "layer": "device execute",
          "unit": "count", "better": "higher", "source": "program_counter",
          "moves": "query_rows_per_s", "reader": "stmt_count",
          "args": {"statement": "by_day"}}
    (new / "metrics" / "by_day_count.json").write_text(json.dumps(lm))
    doc = copy.deepcopy(m.doc)
    doc["paths"].append("extra")
    doc["configs"].append({"name": "events_tiny", "source": "a throwaway",
                           "file": "extra/configs/events_tiny.json",
                           "reduced": ["sf"], "why": "a throwaway"})
    cell = "events_tiny.events_mix"
    doc["workloads"].append({"name": cell, "config": "events_tiny",
                             "traffic": "events_mix", "chips": 1,
                             "why": "a throwaway"})
    doc["end_to_end"][0]["workloads"].append(cell)
    doc["per_layer"].append(dict(
        {k: lm[k] for k in ("name", "unit", "better", "source", "layer",
                            "moves")}, workloads=[cell]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    shutil.copytree(os.path.join(ROOT, "benchmark", "configs"),
                    tmp_path / "benchmark" / "configs")
    assert manifest.problems(manifest.Manifest(str(tmp_path))) == []
    res, out = rehearse(capsys, cell, trace=1, root=str(tmp_path))
    assert res["correct"] is True and res["attempted"] >= 3
    assert res["metrics"]["by_day_count"]["value"] == \
        2 * res["attempted"] // 3
    window = [json.loads(ln) for ln in out.out.splitlines()
              if '"line": "window"' in ln][0]
    assert window["readback"]["events"] < 2000 and window["compared"] > 0
    res, _ = rehearse(capsys, cell, root=str(tmp_path))
    assert res["correct"] is True and set(res["metrics"]) == {
        "query_rows_per_s", "setup_s"}

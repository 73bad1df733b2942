"""The statistic the bounds were set by (`spread.py`), the reader that
counts a window's stalls, and the manifest as PR 35 left it.

    python -m pytest benchmark/tests -q
"""

import json
import os
import statistics

import pytest

import manifest
import spread

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---- the manifest ----------------------------------------------------------

def test_manifest_has_no_problem_and_the_restated_bounds_are_on_the_grid():
    m = manifest.Manifest(ROOT)
    assert manifest.problems(m) == []
    bounds = {e["name"]: e["bound"] for e in m.doc["end_to_end"]}
    # PERF.md section 2: a multiple of 0.005, and never over 0.10,
    # past which a bound guards nothing
    for name in ("query_rows_per_s", "stmt_p50_ms"):
        assert 0.01 <= bounds[name] <= 0.10
        assert bounds[name] / 0.005 == pytest.approx(
            round(bounds[name] / 0.005), abs=1e-9)
    assert m.doc["run_seconds"] == 45


@pytest.mark.parametrize("name, cell, moves, args", [
    ("stall_share.scan", "tpch_sf2.scan", "query_rows_per_s", {}),
    ("stall_share.served", "tpch_sf2.refresh", "stmt_p50_ms",
     {"kinds": ["query"]}),
])
def test_stall_share_entries_and_files_agree(name, cell, moves, args):
    m = manifest.Manifest(ROOT)
    entry = next(p for p in m.doc["per_layer"] if p["name"] == name)
    assert entry == {"name": name, "unit": "share", "better": "lower",
                     "source": "host_clock", "layer": "device execute",
                     "moves": moves, "workloads": [cell]}
    mf = m.metric(name)
    assert mf["reader"] == "stall_share" and mf.get("args", {}) == args
    assert name in [p["name"] for p in m.metrics_of(cell, "per_layer")]


@pytest.mark.parametrize("name", ["rf1_stmt_ms", "compiles_in_window.scan"])
def test_retired_metrics_are_gone_with_their_files(name):
    m = manifest.Manifest(ROOT)
    assert name not in [p["name"] for p in m.doc["per_layer"]]
    with pytest.raises(FileNotFoundError):
        m.metric(name)


# ---- spread.py on known vectors --------------------------------------------

def test_six_equal_runs_read_zero():
    s = spread.summary([7.5] * 6)
    assert s == {"n": 6, "median": 7.5, "spread": 0.0,
                 "spread_without_farthest": 0.0, "range": 0.0}


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = statistics.quantiles(values, n=4)    # 100.75, 104.25
    assert (q1, q3) == (100.75, 104.25)
    assert spread.spread(values) == pytest.approx(3.5 / 102.5)
    assert spread.spread([5.0]) is None
    # a count that reads 0 in every traced run has no relative spread
    assert spread.summary([0, 0, 0]) == {
        "n": 3, "median": 0, "spread": None,
        "spread_without_farthest": None, "range": None}
    assert spread.summary(values)["range"] == pytest.approx(5 / 102.5)


@pytest.mark.parametrize("values, narrows", [
    # one run far off: leaving it out narrows the spread
    ([100, 101, 102, 103, 104, 150], True),
    # two clusters: without the farthest run the quartiles lie wider
    # apart over a smaller median, so every run stays in
    ([100, 100, 100, 110, 110, 111], False),
])
def test_the_farthest_run_is_left_out_only_where_that_narrows(values,
                                                              narrows):
    whole = spread.spread(values)
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    assert (spread.spread(rest) < whole) is narrows
    got = spread.spread_without_farthest(values)
    assert got == pytest.approx(spread.spread(rest) if narrows else whole)
    assert got <= whole


QUIET = [1150, 1155, 1158, 1160, 1165, 1157]
WIDE = [1140, 1150, 1157, 1160, 1175, 1156]
LOW = [100, 100, 101, 101, 100, 101]
HIGH = [110, 111, 110, 111, 110, 111]


@pytest.mark.parametrize("parent, change, bound, better, verdict", [
    # medians 0.09 % apart, the change spreading past a bound of 1 %:
    # the shape of PR 34's line in the scan cell, then under 5 %
    (QUIET, WIDE, 0.01, "higher", "unresolved"),
    (QUIET, WIDE, 0.05, "higher", "unchanged"),
    (LOW, HIGH, 0.05, "higher", "gain"),
    (LOW, HIGH, 0.05, "lower", "regression"),
])
def test_judge_follows_the_drivers_rule(parent, change, bound, better,
                                        verdict):
    assert spread.judge(parent, change, bound, better) == verdict


def test_slow_statements_count_past_five_medians_of_their_own_name():
    series = [["q1", 15.0]] * 9 + [["q1", 76.0], ["q1", 74.0]] \
        + [["q6", 5.0]] * 4 + [["q6", 25.0]]
    got = spread.slow_statements(series)
    # 75 ms is five medians of a Q1: 76 is past it, 74 is not; a Q6 of
    # 25 ms is exactly five medians, which is not slower than five
    assert got["q1"] == {"n": 11, "median_ms": 15.0, "slow_n": 1,
                         "slow_ms": 76.0}
    assert got["q6"] == {"n": 5, "median_ms": 5.0, "slow_n": 0,
                         "slow_ms": 0}
    assert spread.slow_statements([]) == {}


def result_file(path, cell_statements, seed, value, series):
    with open(path, "w") as f:
        for line in (
                {"line": "device", "seed": seed},
                {"line": "window", "statements": {
                    n: {"n": 1} for n in cell_statements}},
                {"line": "series", "ms": series},
                {"correct": True, "attempted": len(series), "failed": 0,
                 "metrics": {"query_rows_per_s": {"value": value,
                                                  "unit": "rows/s"},
                             "setup_s": {"value": 70.0, "unit": "s"}},
                 "device": {}}):
            f.write(json.dumps(line) + "\n")


def test_result_files_are_grouped_by_cell_and_set(tmp_path, capsys):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    quiet = [["q1", 15.0], ["q6", 5.0]] * 10
    for d, values in ((a, [100, 102, 104]), (b, [101, 103, 105])):
        for i, v in enumerate(values):
            result_file(d / f"scan.{i}.out", ["q1", "q6"], 10 + i, v,
                        quiet + [["q1", 2000.0]] * (i == 2))
    result_file(a / "join.0.out", ["q3"], 10, 7.0, [["q3", 3850.0]] * 3)
    (a / "torn.out").write_text('{"line": "device", "seed": 1}\n')
    assert spread.main([str(a), str(b), "--split", "--root", ROOT]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    by_cell = {x["cell"]: x for x in lines}
    assert set(by_cell) == {"tpch_sf2.scan", "tpch_sf1.join"}
    scan = by_cell["tpch_sf2.scan"]
    assert [s["set"] for s in scan["sets"]] == [str(a), str(b)]
    assert [r["seed"] for r in scan["sets"][0]["runs"]] == [10, 11, 12]
    assert scan["sets"][0]["metrics"]["query_rows_per_s"]["median"] == 102
    assert scan["all"]["query_rows_per_s"]["n"] == 6
    assert scan["all"]["query_rows_per_s"]["median"] == 102.5
    last = scan["sets"][0]["runs"][2]["slow"]["q1"]
    assert (last["slow_n"], last["slow_ms"]) == (1, 2000.0)
    # 100, 104, 103 against 102, 101, 105: inside any bound on offer
    split = scan["split"]["query_rows_per_s"]
    assert split["even_as_parent"] in ("unchanged", "unresolved")
    assert set(scan["split"]) == {"query_rows_per_s", "setup_s"}
    assert scan["split"]["setup_s"] == {
        "bound": 0.25, "even_as_parent": "unchanged",
        "odd_as_parent": "unchanged"}
    assert by_cell["tpch_sf1.join"]["all"]["query_rows_per_s"]["n"] == 1


# ---- the reader ------------------------------------------------------------

def window(ms, name="q6", kind="query"):
    return [{"name": name, "kind": kind, "ok": True, "ms": v} for v in ms]


def test_stall_share_on_a_fixture_log():
    m = manifest.Manifest(ROOT)
    quiet = {"statements": window([10.0] * 100), "window_s": 3.0}
    assert m.read("stall_share.scan", quiet) == 0
    # one statement of 2 s among 99 of 10 ms in a 3 s window
    stalled = {"statements": window([10.0] * 99 + [2000.0]),
               "window_s": 3.0}
    assert m.read("stall_share.scan", stalled) == pytest.approx(2 / 3)
    assert round(m.read("stall_share.scan", stalled), 3) == 0.667
    # a statement that failed while it stalled still took the time
    stalled["statements"][-1]["ok"] = False
    assert m.read("stall_share.scan", stalled) == pytest.approx(2 / 3)
    assert m.read("stall_share.scan",
                  {"statements": [], "window_s": 3.0}) is None


def test_stall_share_is_by_name_and_the_served_one_by_kind():
    m = manifest.Manifest(ROOT)
    # a Q1 of 60 ms is four medians of a Q1, though twelve of a Q6
    mixed = window([15.0] * 9 + [60.0], name="q1") + window([5.0] * 10)
    assert m.read("stall_share.scan",
                  {"statements": mixed, "window_s": 1.0}) == 0
    # the served metric moves the median of the queries and counts
    # them alone: a put that rolls a buffer over is not its stall
    served = window([8.0] * 20 + [160.0]) + window(
        [500.0] * 3 + [3000.0], name="rf1", kind="insert_rows")
    c = {"statements": served, "window_s": 10.0}
    assert m.read("stall_share.served", c) == pytest.approx(0.016)
    assert m.read("stall_share.scan", c) == pytest.approx(0.316)

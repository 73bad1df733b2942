"""The decimal cell `tpch_sf2_decimal.scan_q1` on the CPU: its exact Q1
reference against a Python `Decimal` oracle and against `SnappySession.sql`
over DECIMAL(15,2) money, its control, its rehearsal whole and broken
(one cent moved, the products lowered to float as before Spark 2.1's
types were kept), its manifest entries, and what a traced Q1 carries for
the cell's per-layer metrics. Values and counts, never a device time.
"""

import datetime
import decimal
import json
import os
import sys

import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu import types as T
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

CELL = "tpch_sf2_decimal.scan_q1"
CONFIG = "tpch_sf2_decimal"
MIX = "scan_q1_decimal"
METRICS = ["q1dec_stmt_ms", "plan_ms.q1dec", "bind_ms.q1dec",
           "dispatch_ms.q1dec", "device_wait_ms.q1dec",
           "device_idle_pct.q1dec", "xla_compiles_in_window.q1dec",
           "host_fallbacks.q1dec", "q1dec_roofline",
           "decimal_wide_exprs.q1dec", "decimal_exact_slots.q1dec"]
# the counters this PR added to the program: left out on the parent
NEW_ATTRS = {"decimal_wide_exprs.q1dec": "decimal_wide_exprs",
             "decimal_exact_slots.q1dec": "decimal_exact_slots"}
MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
# Spark 2.1's result types of Q1's ten outputs
TYPES = ["string", "string", "decimal(25,2)", "decimal(25,2)",
         "decimal(38,4)", "decimal(38,6)", "decimal(19,6)",
         "decimal(19,6)", "decimal(19,6)", "long"]
_END = (datetime.date(1998, 12, 1) - datetime.date(1970, 1, 1)).days
D = decimal.Decimal


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


def _lineitem(man, sf, seed):
    gen = man.module("generators", "tpch")
    return gen.generate("lineitem", sf, seed)


def _world(man, li):
    w = World(man, plates="float64", accumulate="float64")
    keep = man.module("references", "q1_decimal").COLUMNS["lineitem"]
    w.insert("lineitem", {c: li[c] for c in keep})
    return w


def _q1(man, delta):
    return man.mix(MIX)["statements"]["q1dec"]["sql"].format(delta=delta)


def _oracle(li, delta):
    """Q1 in Python `Decimal`s, row by row in its text's order of
    operations: Spark 2.1's types, averages HALF_UP at scale 6."""
    cut = _END - delta
    ctx = decimal.Context(prec=60, rounding=decimal.ROUND_HALF_UP)
    groups = {}
    money = [li[c] for c in MONEY]
    for i in np.flatnonzero(li["l_shipdate"] <= cut):
        q, p, d, t = (D(repr(float(m[i]))) for m in money)
        key = (li["l_returnflag"][i], li["l_linestatus"][i])
        acc = groups.setdefault(key, [D(0)] * 5 + [0])
        dp = p * (1 - d)
        for k, v in enumerate((q, p, dp, dp * (1 + t), d)):
            acc[k] += v
        acc[5] += 1
    six = D("1e-6")
    return [(f, s, a[0], a[1], a[2], a[3],
             ctx.divide(a[0], a[5]).quantize(six, context=ctx),
             ctx.divide(a[1], a[5]).quantize(six, context=ctx),
             ctx.divide(a[4], a[5]).quantize(six, context=ctx), a[5])
            for (f, s), a in sorted(groups.items())]


def _floats(rows):
    return [tuple(float(v) if isinstance(v, D) else v for v in r)
            for r in rows]


# ---- the reference -----------------------------------------------------------

@pytest.mark.parametrize("seed", [11, 2147483659])
def test_reference_equals_a_decimal_oracle(man, seed):
    li = _lineitem(man, 0.002, seed)
    w = _world(man, li)
    for delta in (60, 90, 120):
        got = w.answer("q1_decimal", {"delta": delta})
        exp = _oracle(li, delta)
        assert got == _floats(exp)
        assert compare(got, _floats(exp)) == (0.0, 0)
        assert [type(v) for v in got[0]] == [str, str] + [float] * 7 + [int]


@pytest.mark.parametrize("seed", [7, 3600000019])
def test_reference_equals_the_program(man, seed, _restore_knobs):
    """`session.sql` of the mix's Q1 text over DECIMAL(15,2) money under
    the chip's dtype policy: Decimal answers at Spark 2.1's types, equal
    to the exact reference to the last digit."""
    _restore_knobs.decimal_as_float64 = False
    li = _lineitem(man, 0.01, seed)
    w = _world(man, li)
    s = SnappySession(catalog=Catalog())
    try:
        s.sql(man.config(CONFIG)["tables"]["lineitem"]["ddl"])
        s.insert_arrays("lineitem", list(li.values()))
        for delta in (61, 93, 118):
            res = s.sql(_q1(man, delta))
            assert [str(t) for t in res.dtypes] == TYPES
            rows = [tuple(r) for r in res.rows()]
            assert all(isinstance(v, D) for r in rows for v in r[2:9])
            assert compare(rows, w.answer("q1_decimal", {"delta": delta})) \
                == (0.0, 0)
            if delta == 93:
                assert _floats(rows) == _floats(_oracle(li, delta))
    finally:
        s.stop()


def test_control_in_float32_accumulators_is_not_correct(man):
    out = control.control_gap(man, CELL, seed=77, cycles=6, sf=0.05)
    assert out["compared"] == 7
    assert out["limit"] == 0.0
    assert out["sum_rel_gap"] > 1e-7


# ---- the manifest ------------------------------------------------------------

def test_manifest_takes_the_decimal_cell(man):
    assert manifest.problems(man) == []
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, MIX, 1)
    assert cell == man.doc["workloads"][5]
    e2e = {e["name"]: e for e in man.doc["end_to_end"]}
    assert e2e["query_rows_per_s"]["workloads"][4] == CELL
    assert [m["name"] for m in man.metrics_of(CELL, "end_to_end")] == \
        ["query_rows_per_s", "setup_s"]
    assert [m["name"] for m in man.metrics_of(CELL, "per_layer")] == METRICS
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["workloads"] == [CELL] and m["moves"] == "query_rows_per_s"
    cfg, sf1, sf2 = (man.config(n) for n in (CONFIG, "tpch_sf1", "tpch_sf2"))
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for word in ("cl 2.4.1", "DECIMAL(15,2)", "SF 2"):
        assert word in entry["source"]
    assert entry["reduced"] == list(cfg["reduced"]) == ["sf"]
    assert cfg["sf"] == 2 and cfg["rehearsal_sf"] == 0.05
    assert cfg["guarantees"] == sf1["guarantees"]
    assert cfg["limits"] == {"sum_rel_gap": 0.0}
    assert cfg["precision"]["plates"] == cfg["precision"]["accumulate"] \
        == "float64"
    assert [a for a in sf2["assumed"] if "declared DOUBLE" not in a] \
        == cfg["assumed"][:len(sf2["assumed"]) - 1]
    assert list(cfg["tables"]) == ["lineitem"]
    ddl = cfg["tables"]["lineitem"]["ddl"]
    for col in MONEY:
        assert f"{col} DECIMAL(15,2)" in ddl
    assert ddl.replace("DECIMAL(15,2)", "DOUBLE") \
        == sf2["tables"]["lineitem"]["ddl"]
    mix = man.mix(MIX)
    assert mix["tables"] == ["lineitem"]
    assert mix["cycle"] == mix["warmup"] == ["q1dec"]
    assert (mix["entry"], mix["loop"], mix["durable"]) == \
        ("embedded", "closed", False)
    q1 = mix["statements"]["q1dec"]
    assert (q1["table"], q1["reference"], q1["bytes_per_row"]) == \
        ("lineitem", "q1_decimal", 38)
    # Q1's text word for word, and its draws, as the scan cell has them
    scan = man.mix("scan_q1_q6")["statements"]["q1"]
    assert (q1["sql"], q1["draws"]) == (scan["sql"], scan["draws"])


def test_a_seed_walks_all_61_deltas(man):
    import traffic

    mix, cfg = man.mix(MIX), man.config(CONFIG)
    seen = []
    for seed in (1, 2):
        t = traffic.Traffic(man, mix, cfg, 0.01, seed)
        sts = [t.cycle()[0] for _ in range(61)]
        assert sorted(st.subst["delta"] for st in sts) == list(range(60, 121))
        assert all(f"INTERVAL '{st.subst['delta']}' DAY" in st.sql
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


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_is_correct(capsys, monkeypatch, man, trace):
    res, setup = _rehearse(capsys, monkeypatch, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    assert setup["rows"] == {"lineitem": 299995}
    checks = res["checks"]
    assert checks["sum_rel_gap"] == {"value": 0.0, "limit": 0.0}
    assert checks["exact_mismatches"]["value"] == 0
    assert checks["unanswered"]["value"] == 0
    if not trace:
        assert set(res["metrics"]) == {"query_rows_per_s", "setup_s"}
        assert res["metrics"]["query_rows_per_s"]["value"] > 0
        return
    # every per-layer metric but the device's own two reads a number
    assert set(res["metrics"]) == set(METRICS) - {"device_idle_pct.q1dec",
                                                  "q1dec_roofline"}
    value = {k: v["value"] for k, v in res["metrics"].items()}
    assert value["host_fallbacks.q1dec"] == 0
    assert value["xla_compiles_in_window.q1dec"] == 0
    assert value["decimal_wide_exprs.q1dec"] == 2
    assert value["decimal_exact_slots.q1dec"] == 7


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


def _one_cent_more(rows):
    """sum_base_price of the first group one cent off."""
    first = list(rows[0])
    first[3] += D("0.01")
    return [tuple(first)] + rows[1:]


def test_one_cent_moved_is_not_correct(capsys, monkeypatch):
    _break_query(monkeypatch, _one_cent_more)
    res, _ = _rehearse(capsys, monkeypatch)
    assert res["correct"] is False and res["failed"] > 0
    gap = res["checks"]["sum_rel_gap"]
    assert 0.0 < gap["value"] < 1e-9 and gap["limit"] == 0.0


def test_products_lowered_to_float_are_not_correct(capsys, monkeypatch):
    """The lowering before Spark 2.1's types were kept: a product past
    precision 18 typed DOUBLE (float plates' width on the chip)."""
    real = T.decimal_binop_type

    def capped(op, a, b):
        out = real(op, a, b)
        if isinstance(out, T.DecimalType) and op == "*" \
                and out.precision > T.DECIMAL_INT64_PRECISION:
            return T.DOUBLE
        return out
    monkeypatch.setattr(T, "decimal_binop_type", capped)
    res, _ = _rehearse(capsys, monkeypatch)
    assert res["correct"] is False and res["failed"] > 0
    gap = res["checks"]["sum_rel_gap"]
    assert gap["value"] > 0.0 and gap["limit"] == 0.0


# ---- what a traced Q1 carries ------------------------------------------------

def _spans(root):
    yield root
    for c in root.get("children", ()):
        yield from _spans(c)


@pytest.fixture(scope="module")
def traced_q1(man):
    """Three Q1 on one session under the chip's dtype policy, each with
    its trace: the first compiles, the others bring a fresh DELTA."""
    props = config.global_properties()
    saved = (props.decimal_as_float64, props.tracing_enabled)
    props.decimal_as_float64, props.tracing_enabled = False, True
    li = _lineitem(man, 0.01, 5)
    s = SnappySession(catalog=Catalog())
    recs = []
    try:
        s.sql(man.config(CONFIG)["tables"]["lineitem"]["ddl"])
        s.insert_arrays("lineitem", list(li.values()))
        for delta in (70, 95, 111):
            rows = [tuple(r) for r in s.sql(_q1(man, delta)).rows()]
            tr = tracing.ring().last().to_dict()
            recs.append({"name": "q1dec", "kind": "query", "ok": True,
                         "window": True, "traced": True,
                         "ms": tr["root"]["ms"], "answer": rows,
                         "traces": [tr],
                         "rows_read": len(li["l_orderkey"]),
                         "bytes_per_row": 38})
    finally:
        s.stop()
        props.decimal_as_float64, props.tracing_enabled = saved
    return recs


def test_a_traced_q1_stays_on_the_device_and_counts_its_decimals(traced_q1):
    for rec in traced_q1:
        root = rec["traces"][0]["root"]
        assert not [sp for sp in _spans(root)
                    if sp["name"] == "host_fallback"]
        (main,) = [sp for sp in _spans(root)
                   if sp["name"] in ("jit_compile", "device_execute")
                   and "decimal_wide_exprs" in sp.get("attrs", {})]
        assert main["attrs"]["decimal_wide_exprs"] == 2
        assert main["attrs"]["decimal_exact_slots"] == 7
        assert main["attrs"]["groups_overflow"] == 0


@pytest.mark.parametrize("name", METRICS)
def test_every_new_metric_reads_a_number_from_the_trace(man, traced_q1,
                                                        name):
    window = traced_q1[1:]      # the first statement is the warm-up
    ctx = {"statements": window, "back": "session", "front": "session",
           "device": {"busy_s": 2.0, "window_s": 2.5}, "window_s": 2.5,
           "peaks": roofline.peaks_for("TPU v5 lite")}
    value = man.read(name, ctx)
    assert isinstance(value, (int, float)) and not isinstance(value, bool)
    expected = {
        "host_fallbacks.q1dec": 0, "xla_compiles_in_window.q1dec": 0,
        "decimal_wide_exprs.q1dec": 2, "decimal_exact_slots.q1dec": 7,
        "device_idle_pct.q1dec": 20.0,
        "q1dec_roofline": 100.0 * (2 * window[0]["rows_read"] * 38 / 819e9)
        / 2.0}
    if name in expected:
        assert value == pytest.approx(expected[name])
    else:
        assert value > 0
    # a program from before the attrs (the parent): None, not an error
    attr = NEW_ATTRS.get(name)
    if attr is not None:
        bare = json.loads(json.dumps(window, default=str))
        for r in bare:
            for sp in _spans(r["traces"][0]["root"]):
                sp.get("attrs", {}).pop(attr, None)
        assert man.read(name, dict(ctx, statements=bare)) is None

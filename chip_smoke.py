#!/usr/bin/env python3
"""Chip smoke: the store's main path, once, on the TPU, value-checked.

    python chip_smoke.py [--seed N] [--sf F]

One process, the normal entry points (`SnappySession`, `session.sql`,
`insert_arrays`, the Flight front door), default `config.Properties`:

  device    platform / kind / count, versions, compile cache, native encoder
  load      TPC-H lineitem/orders/customer at SF 16 (96 M lineitem rows) from
            the in-tree generators: synthetic stand-ins for dbgen, not dbgen
  q1 q6     each once cold and a few times warm, every value against a NumPy
            oracle built from the generated arrays; lane counters printed,
            zero host fallbacks required
  q3c       the join leg, on the device with default settings (at a cut
            scale where the defaults would reroute it to the host: see
            `reduced`), against a NumPy oracle
  mesh      with more than one device: Q1/Q6/Q3C under MeshContext, answers
            equal to single-device, bytes resident on every device, and the
            composed two-server topology once
  mutate    INSERT, UPDATE, DELETE on lineitem, then Q6 against the oracle
            with the same mutation applied (an acknowledged write is read)
  serve     SnappyFlightServer on the same session: one statement as text,
            one with bound parameters, against the embedded answers

Every line printed is one JSON object. Each leg's line names the platform;
the line before last is the summary `{"ok": true, "device": {...}, ...,
"claim": null}`, whose seconds are labelled readings, not claims; the last
line is the result the driver reads, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as JAX reports it. A leg that fails raises: the exit code
is non-zero and neither line is printed.

Without a TPU the script exits 2 and prints no result. `--cpu-rehearsal`
together with JAX_PLATFORMS=cpu in the environment runs the same legs at a
tiny scale on the CPU, labelled `platform: cpu` in every line: it is what
the tier-1 test runs, and it proves values and control flow, no rate.
"""

from __future__ import annotations

import argparse
import datetime
import importlib.metadata
import json
import os
import sys
import threading
import time

import numpy as np

FULL_SF = 16.0        # bench.py's documented scale: 96 M lineitem rows
MIN_SF = 4.0          # never cut the chip run below this
JOIN_SF = 4.0         # the join leg's scale when the main one is larger
REHEARSAL_SF = 0.05   # three column batches: enough for every lane
SUM_TOL = 2e-6        # f32 plates + f64 accumulators vs the f32-rounded oracle
JOIN_TOL = 5e-5       # the repo's own bound for the expanded join's revenue
# mesh vs single device: same plates, so merge order only — except that the
# mesh lane's finalize (session._merge_partial_pieces) passes the merged
# partials through a scratch column table, whose DOUBLE is an f32 plate
# under the TPU dtype policy: one f32 rounding per value
MESH_TOL_F64 = 1e-9
MESH_TOL_F32 = 2.4e-7

_PLATFORM = "?"


def say(leg: str, **fields) -> None:
    print(json.dumps({"platform": _PLATFORM, "leg": leg, **fields},
                     default=str), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


def close(got: float, exp: float, tol: float) -> bool:
    return abs(float(got) - float(exp)) <= tol * max(abs(float(exp)), 1.0)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


# ---------------------------------------------------------------------------
# oracles: plain NumPy over the generated arrays, independent of the engine
# ---------------------------------------------------------------------------

def plate(a, f32: bool) -> np.ndarray:
    """A DOUBLE column as the device holds it, widened back to f64: an f32
    plate on the TPU (config.use_float64() False)."""
    a = np.asarray(a, dtype=np.float64)
    return a.astype(np.float32).astype(np.float64) if f32 else a


def plate_mul(a, b, f32: bool) -> np.ndarray:
    """A product as the device forms it: in the plates' width."""
    if f32:
        return (a.astype(np.float32) * b.astype(np.float32)) \
            .astype(np.float64)
    return a * b


class Oracle:
    """The lineitem columns the checked queries read, as the device holds
    them (see plate); sums accumulate in f64 there and here."""

    def __init__(self, li: dict, f32: bool):
        import pandas as pd

        self.f32 = f32
        self.okey = np.asarray(li["l_orderkey"])
        self.ship = np.asarray(li["l_shipdate"])
        self.qty = self._plate(li["l_quantity"])
        self.price = self._plate(li["l_extendedprice"])
        self.disc = self._plate(li["l_discount"])
        self.tax = self._plate(li["l_tax"])
        # exact hundredths: the predicate `BETWEEN 0.05 AND 0.07` means
        # these, whatever width the plate has
        self.disc100 = np.rint(np.asarray(li["l_discount"]) * 100) \
            .astype(np.int8)
        self.rf_code, self.rf_names = pd.factorize(li["l_returnflag"])
        self.ls_code, self.ls_names = pd.factorize(li["l_linestatus"])
        self.live = np.ones(len(self.okey), dtype=bool)

    def _plate(self, a) -> np.ndarray:
        return plate(a, self.f32)

    def _mul(self, a, b) -> np.ndarray:
        return plate_mul(a, b, self.f32)

    def append(self, rows: list) -> None:
        """rows: (orderkey, qty, price, disc, tax, flag, status, shipdate)."""
        cols = list(zip(*rows))
        self.okey = np.concatenate([self.okey, np.asarray(cols[0], np.int64)])
        self.qty = np.concatenate([self.qty, self._plate(cols[1])])
        self.price = np.concatenate([self.price, self._plate(cols[2])])
        self.disc = np.concatenate([self.disc, self._plate(cols[3])])
        self.disc100 = np.concatenate([
            self.disc100,
            np.rint(np.asarray(cols[3]) * 100).astype(np.int8)])
        self.tax = np.concatenate([self.tax, self._plate(cols[4])])
        self.ship = np.concatenate([
            self.ship, np.asarray([days(d) for d in cols[7]], np.int32)])
        self.live = np.concatenate([self.live, np.ones(len(rows), bool)])
        # q1 is not re-checked after a mutation: its codes stay as loaded

    def q1(self) -> dict:
        """(returnflag, linestatus) -> the ten Q1 output columns."""
        n = len(self.rf_code)
        keep = self.live[:n] & (self.ship[:n] <= days("1998-12-01") - 90)
        nls = len(self.ls_names)
        g = (self.rf_code * nls + self.ls_code)[keep]
        G = len(self.rf_names) * nls
        qty, price = self.qty[:n][keep], self.price[:n][keep]
        disc, tax = self.disc[:n][keep], self.tax[:n][keep]
        dp = self._mul(price, 1.0 - disc)
        ch = self._mul(dp, 1.0 + tax)

        def gsum(w):
            return np.bincount(g, weights=w, minlength=G)

        cnt = np.bincount(g, minlength=G)
        s_qty, s_price, s_disc = gsum(qty), gsum(price), gsum(disc)
        s_dp, s_ch = gsum(dp), gsum(ch)
        out = {}
        for gi in np.nonzero(cnt)[0]:
            c = int(cnt[gi])
            out[(str(self.rf_names[gi // nls]),
                 str(self.ls_names[gi % nls]))] = (
                s_qty[gi], s_price[gi], s_dp[gi], s_ch[gi],
                s_qty[gi] / c, s_price[gi] / c, s_disc[gi] / c, c)
        return out

    def q6_mask(self, qty_below: float = 24) -> np.ndarray:
        return (self.live
                & (self.ship >= days("1994-01-01"))
                & (self.ship < days("1995-01-01"))
                & (self.disc100 >= 5) & (self.disc100 <= 7)
                & (self.qty < qty_below))

    def q6(self) -> tuple:
        m = self.q6_mask()
        return (float(self._mul(self.price[m], self.disc[m]).sum()),
                int(m.sum()))


def q3c_oracle(li: dict, orders: dict, f32: bool) -> dict:
    """o_orderpriority -> (count(l_orderkey), sum(price * (1 - disc))) of
    orders before 1995-03-15 LEFT JOIN lineitem: per-order aggregates by
    bincount (o_orderkey is 1..n), then per-priority over the kept orders."""
    import pandas as pd

    dp = plate_mul(plate(li["l_extendedprice"], f32),
                   1.0 - plate(li["l_discount"], f32), f32)
    okey = np.asarray(li["l_orderkey"])
    n_o = len(orders["o_orderkey"])
    check(np.array_equal(orders["o_orderkey"],
                         np.arange(1, n_o + 1, dtype=np.int64)),
          "generator contract: o_orderkey is 1..n")
    cnt_o = np.bincount(okey, minlength=n_o + 1)
    rev_o = np.bincount(okey, weights=dp, minlength=n_o + 1)
    keep = np.asarray(orders["o_orderdate"]) < days("1995-03-15")
    pcode, pnames = pd.factorize(orders["o_orderpriority"])
    idx = np.asarray(orders["o_orderkey"])[keep]
    cnt_p = np.bincount(pcode[keep], weights=cnt_o[idx],
                        minlength=len(pnames))
    rev_p = np.bincount(pcode[keep], weights=rev_o[idx],
                        minlength=len(pnames))
    return {str(p): (int(round(cnt_p[i])), float(rev_p[i]))
            for i, p in enumerate(pnames)}


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

_LANE_PREFIXES = ("agg_", "host_fallbacks", "join_", "compressed_fallback",
                  "scan_tile", "mesh_", "gidx_cache", "code_domain_pred",
                  "batches_device_decoded")
_ALWAYS = ("host_fallbacks", "join_device_joins", "join_host_fallbacks",
           "agg_reduce_passes", "agg_code_domain", "agg_dict_space",
           "agg_rle_runs")


def lanes(c0: dict, c1: dict) -> dict:
    """Counter deltas of the lane evidence; the keys the checks read are
    present even at zero."""
    out = {k: 0 for k in _ALWAYS}
    for k, v in c1.items():
        d = v - c0.get(k, 0)
        if d and k.startswith(_LANE_PREFIXES):
            out[k] = d
    return out


def timed_query(session, sql: str, warm: int):
    """(rows, cold seconds, warm seconds list, lane deltas). Each timing
    ends in rows on the host, so it includes the device."""
    from snappydata_tpu.observability.metrics import global_registry

    reg = global_registry()
    c0 = reg.counters_snapshot()
    t0 = time.perf_counter()
    rows = session.sql(sql).rows()
    cold = time.perf_counter() - t0
    warms = []
    for _ in range(warm):
        t0 = time.perf_counter()
        again = session.sql(sql).rows()
        warms.append(time.perf_counter() - t0)
        check(again == rows, "a warm run changed the answer")
    return rows, cold, warms, lanes(c0, reg.counters_snapshot())


def report(leg: str, rows_scanned: int, cold: float, warms: list,
           ev: dict, **extra) -> float:
    med = float(np.median(warms))
    say(leg, rows=rows_scanned, cold_s=cold, warm_s=warms,
        warm_median_s=med, lanes=ev, **extra)
    return med


def diff_q1(rows: list, exp: dict, tol: float) -> tuple:
    """(largest relative error, what is wrong) of Q1 rows against the
    oracle's {(flag, status): columns}."""
    got = {(r[0], r[1]): r for r in rows}
    if set(got) != set(exp):
        return float("inf"), [f"groups {sorted(got)} != {sorted(exp)}"]
    worst, wrong = 0.0, []
    for key, e in exp.items():
        r = got[key]
        if r[9] != e[7]:
            wrong.append(f"{key}: count {r[9]} != {e[7]}")
        for name, g, x in zip(
                ("sum_qty", "sum_base_price", "sum_disc_price",
                 "sum_charge", "avg_qty", "avg_price", "avg_disc"),
                r[2:9], e[:7]):
            rel = abs(g - x) / max(abs(x), 1.0)
            worst = max(worst, rel)
            if rel > tol:
                wrong.append(f"{key} {name}: {g} vs {x} (rel {rel:.2e})")
    return worst, wrong


def diff_q3c(rows: list, exp: dict, tol: float) -> tuple:
    if [r[0] for r in rows] != sorted(exp):
        return float("inf"), [f"groups {[r[0] for r in rows]} != "
                              f"{sorted(exp)}"]
    worst, wrong = 0.0, []
    for r in rows:
        cnt, rev = exp[r[0]]
        if r[1] != cnt:
            wrong.append(f"{r[0]}: line_count {r[1]} != {cnt}")
        rel = abs(r[2] - rev) / max(abs(rev), 1.0)
        worst = max(worst, rel)
        if rel > tol:
            wrong.append(f"{r[0]}: revenue {r[2]} vs {rev} (rel {rel:.2e})")
    return worst, wrong


def rows_equal(a: list, b: list, tol: float) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        for x, y in zip(ra, rb):
            if isinstance(x, float) or isinstance(y, float):
                if not close(x, y, tol):
                    return False
            elif x != y:
                return False
    return True


Q6_COUNT = """SELECT sum(l_extendedprice * l_discount) AS revenue,
    count(*) AS n
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1995-01-01'
  AND l_discount BETWEEN {lo} AND {hi}
  AND l_quantity < {q}"""


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_mesh(s, js, n_dev: int, single: dict, tol: float,
             n_rows: int, join_rows: int) -> dict:
    """Q1/Q6 on the main tables and Q3C on the join leg's, under
    MeshContext(data_mesh(n)): answers equal single-device, the shard_map
    lane ran, every device holds its share; then the composed topology."""
    from __graft_entry__ import composed_topology
    from snappydata_tpu.observability.metrics import global_registry
    from snappydata_tpu.parallel import MeshContext, data_mesh
    from snappydata_tpu.storage.device import device_cache_bytes_by_device
    from snappydata_tpu.utils import tpch

    reg = global_registry()
    out = {"devices": n_dev}

    def drop_device_caches(sess):
        sess.executor.clear_cache()
        for ti in sess.catalog.list_tables():
            cache = getattr(ti.data, "_device_cache", None)
            if cache is not None:
                cache.clear()

    sessions = [s] if js is s else [s, js]
    for sess in sessions:
        drop_device_caches(sess)
    c0 = reg.counters_snapshot()
    with MeshContext(data_mesh(n_dev)):
        for name, sess, sql, warm, scanned in (
                ("q1", s, tpch.Q1, 2, n_rows),
                ("q6", s, tpch.Q6, 2, n_rows),
                ("q3c", js, tpch.Q3C, 1, join_rows)):
            rows, cold, warms, ev = timed_query(sess, sql, warm)
            out[f"{name}_cold_s"] = cold
            out[f"{name}_warm_median_s"] = report(
                f"mesh_{name}", scanned, cold, warms, ev, devices=n_dev)
            check(rows_equal(rows, single[name], tol),
                  f"mesh {name} differs from single-device: {rows[:2]} "
                  f"vs {single[name][:2]}")
            check(ev["host_fallbacks"] == 0,
                  f"mesh {name}: host fallback ({ev})")
            if name == "q6":
                # scans only: Q3C's decoded join plates must not blur
                # the per-device share of the encoded table
                per_dev = device_cache_bytes_by_device(
                    (ti.name, ti.data) for ti in s.catalog.list_tables())
    c1 = reg.counters_snapshot()
    ev = lanes(c0, c1)
    check(ev.get("mesh_shard_execs", 0) > 0,
          f"mesh_shard_execs is 0: the shard_map lane never ran ({ev})")
    check(len(per_dev) == n_dev,
          f"plates resident on {len(per_dev)} of {n_dev} devices: {per_dev}")
    fair = sum(per_dev.values()) / n_dev
    check(min(per_dev.values()) >= 0.5 * fair
          and max(per_dev.values()) <= 2.0 * fair,
          f"uneven residency across devices: {per_dev}")
    out["device_cache_bytes_by_device"] = per_dev
    out["mesh_shard_execs"] = ev.get("mesh_shard_execs", 0)
    out["mesh_fallbacks"] = {k: v for k, v in ev.items()
                             if k.startswith("mesh_fallback_")}
    out["mesh_joins"] = {k: v for k, v in ev.items()
                         if k.startswith("mesh_join_")}
    for sess in sessions:
        drop_device_caches(sess)
    t0 = time.perf_counter()
    out["composed"] = composed_topology(n_dev)
    out["composed_s"] = time.perf_counter() - t0
    say("mesh", **out)
    return out


def leg_mutate(s, ora: Oracle) -> tuple:
    """A small INSERT, UPDATE and DELETE on lineitem through session.sql,
    then Q6 and its row count against the oracle with the same mutation
    applied: an acknowledged write is read back on the device path."""
    from snappydata_tpu.utils import tpch

    rev0, n0 = ora.q6()
    base = ora.q6_mask()
    # UPDATE target: a row only its quantity keeps out of Q6
    cand = ora.q6_mask(qty_below=1e9) & ~base
    k_upd = int(ora.okey[np.argmax(cand)])
    # DELETE target: a row inside Q6 under another key
    k_del = int(ora.okey[np.argmax(base & (ora.okey != k_upd))])
    check(cand.any() and base.any() and k_upd != k_del,
          "no mutation targets in the generated data")
    # INSERT: rows inside Q6's window, priced to move the sum far beyond
    # the check's tolerance (key 0 is outside the generated key range)
    new_rows = [(0, 3.0, 1.0e7 + 1000.0 * i, 0.06, 0.02, "N", "O",
                 "1994-06-15") for i in range(8)]
    values = ", ".join(
        f"({k}, 1, 1, {i + 1}, {q}, {p}, {d}, {t}, '{f}', '{st}', "
        f"DATE '{sd}', DATE '{sd}', DATE '{sd}', 'MAIL')"
        for i, (k, q, p, d, t, f, st, sd) in enumerate(new_rows))
    t0 = time.perf_counter()
    s.sql(f"INSERT INTO lineitem VALUES {values}")
    s.sql(f"UPDATE lineitem SET l_quantity = 1.0 WHERE l_orderkey = {k_upd}")
    s.sql(f"DELETE FROM lineitem WHERE l_orderkey = {k_del}")
    dml_s = time.perf_counter() - t0
    ora.qty[ora.okey == k_upd] = 1.0
    ora.live[ora.okey == k_del] = False
    ora.append(new_rows)
    rev1, n1 = ora.q6()
    check(n1 != n0 and not close(rev1, rev0, 10 * SUM_TOL),
          "the mutation does not move the oracle's answer")

    rows, cold, warms, ev = timed_query(s, tpch.Q6, 2)
    check(close(rows[0][0], rev1, SUM_TOL),
          f"Q6 after mutation: {rows[0][0]} vs oracle {rev1} "
          f"(before mutation {rev0})")
    cnt_sql = Q6_COUNT.format(lo=0.05, hi=0.07, q=24)
    crow = s.sql(cnt_sql).rows()[0]
    check(crow[1] == n1, f"row count after mutation: {crow[1]} vs oracle "
                         f"{n1} (before mutation {n0})")
    check(ev["host_fallbacks"] == 0, f"Q6 after mutation fell back: {ev}")
    report("mutate", len(ora.live), cold, warms, ev, dml_s=dml_s,
           inserted=len(new_rows), updated_key=k_upd, deleted_key=k_del,
           q6_rows_before=n0, q6_rows_after=n1)
    return rows[0][0], crow


def leg_serve(s, q6_rev: float, q6_count_row: tuple) -> dict:
    """The Flight front door on the same session, in this process (one
    process owns the chip): a statement as text and one with bound
    parameters, against the embedded answers."""
    from snappydata_tpu.cluster import SnappyClient
    from snappydata_tpu.cluster.flight_server import SnappyFlightServer
    from snappydata_tpu.utils import tpch

    srv = SnappyFlightServer(s, port=0)
    th = threading.Thread(target=srv.serve, daemon=True)
    th.start()
    srv.wait_ready()
    client = SnappyClient(address=f"127.0.0.1:{srv.actual_port}")
    try:
        t0 = time.perf_counter()
        text = client.sql(tpch.Q6)
        text_s = time.perf_counter() - t0
        got = text.column(0).to_pylist()[0]
        check(close(got, q6_rev, 1e-12),
              f"served Q6 {got} vs embedded {q6_rev}")
        t0 = time.perf_counter()
        bound = client.sql(Q6_COUNT.format(lo="?", hi="?", q="?"),
                           params=[0.05, 0.07, 24])
        bound_s = time.perf_counter() - t0
        brow = (bound.column(0).to_pylist()[0],
                bound.column(1).to_pylist()[0])
        check(brow[1] == q6_count_row[1]
              and close(brow[0], q6_count_row[0], 1e-12),
              f"served bound statement {brow} vs embedded {q6_count_row}")
    finally:
        client.close()
        srv.shutdown()
        th.join(timeout=10)
    check(not th.is_alive(), "the Flight server thread did not stop")
    out = {"text_s": text_s, "bound_s": bound_s}
    say("serve", statements=2, **out)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(sf: float, seed: int, reduced: list) -> dict:
    t_start = time.perf_counter()

    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from snappydata_tpu import SnappySession, config, native
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.storage.device import device_cache_bytes_by_device
    from snappydata_tpu.utils import tpch

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None   # a label only; absent on hosts without a TPU
    cache_dir = jax.config.jax_compilation_cache_dir
    cache_entries = len(os.listdir(cache_dir)) \
        if cache_dir and os.path.isdir(cache_dir) else 0
    nat = native.build_info()
    say("device", device_kind=device["kind"], count=device["count"],
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        compile_cache_dir=cache_dir,
        compile_cache_enabled=bool(jax.config.jax_enable_compilation_cache),
        compile_cache_entries_at_start=cache_entries,
        native_encoder=nat["available"], native_build_s=nat["build_s"],
        memory_stats=devs[0].memory_stats())
    check(nat["available"], f"native encoder not built: {nat['error']}")
    f32 = not config.use_float64()
    seconds = {"native_build_s": nat["build_s"]}

    # ---- load ----------------------------------------------------------
    s = SnappySession(catalog=Catalog())
    t0 = time.perf_counter()
    tables = tpch.load_tpch(s, sf=sf, seed=seed)
    seconds["load_s"] = time.perf_counter() - t0
    li, orders = tables["lineitem"], tables["orders"]
    n_rows = s.catalog.lookup_table("lineitem").data.snapshot().total_rows()
    check(n_rows == len(li["l_orderkey"]), "loaded row count")
    say("load", sf=sf, seed=seed, lineitem_rows=n_rows,
        orders_rows=len(orders["o_orderkey"]),
        customer_rows=len(tables["customer"]["c_custkey"]),
        load_s=seconds["load_s"], plates="f32" if f32 else "f64",
        data="in-tree generators (utils/tpch.py): synthetic stand-ins "
             "for dbgen, not dbgen")
    t0 = time.perf_counter()
    ora = Oracle(li, f32)
    seconds["oracle_s"] = time.perf_counter() - t0

    # ---- the join leg's tables -------------------------------------------
    # Default join_expand_max_bytes (2 GiB) sends Q3C to the host join
    # once bucket(lineitem + orders rows) x ~39 B/row passes it, near
    # SF 6.7; the 1200 s limit leaves room for the join's cold compile at
    # no more than SF 4
    if sf > JOIN_SF:
        reduced.append({
            "leg": "q3c", "sf": JOIN_SF, "of": sf,
            "reason": "default join_expand_max_bytes (2 GiB) reroutes "
                      "Q3C to the host join above about SF 6.7, and the "
                      "join's cold compile and runs at SF 4 are what fits "
                      "the 1200 s limit beside the SF 16 scan legs"})
        js = SnappySession(catalog=Catalog())
        t0 = time.perf_counter()
        jt = tpch.load_tpch(js, sf=JOIN_SF, seed=seed)
        seconds["join_load_s"] = time.perf_counter() - t0
        q3c_exp = q3c_oracle(jt["lineitem"], jt["orders"], f32)
        join_rows = len(jt["lineitem"]["l_orderkey"])
        del jt
    else:
        js = s
        q3c_exp = q3c_oracle(li, orders, f32)
        join_rows = n_rows
    del tables, li, orders

    # ---- Q1 / Q6 / Q3C ---------------------------------------------------
    # each leg prints its reading first and is judged after, so a run
    # that fails a check still shows what it measured
    single = {}
    rows, cold, warms, ev = timed_query(s, tpch.Q1, 3)
    err, wrong = diff_q1(rows, ora.q1(), SUM_TOL)
    seconds["q1_cold_s"] = cold
    seconds["q1_warm_median_s"] = report("q1", n_rows, cold, warms, ev,
                                         max_rel_err=err)
    check(not wrong, f"Q1 against the oracle (tol {SUM_TOL}): {wrong}")
    check(ev["host_fallbacks"] == 0, f"Q1 fell back to the host: {ev}")
    single["q1"] = rows

    rows, cold, warms, ev = timed_query(s, tpch.Q6, 5)
    q6_exp, _n = ora.q6()
    seconds["q6_cold_s"] = cold
    seconds["q6_warm_median_s"] = report(
        "q6", n_rows, cold, warms, ev,
        max_rel_err=abs(rows[0][0] - q6_exp) / max(abs(q6_exp), 1.0))
    check(close(rows[0][0], q6_exp, SUM_TOL),
          f"Q6: {rows[0][0]} vs oracle {q6_exp}")
    check(ev["host_fallbacks"] == 0, f"Q6 fell back to the host: {ev}")
    single["q6"] = rows

    per_dev = device_cache_bytes_by_device(
        (ti.name, ti.data) for ti in s.catalog.list_tables())
    say("resident", after="q1+q6", device_cache_bytes_by_device=per_dev,
        bytes_per_lineitem_row=sum(per_dev.values()) / n_rows,
        memory_stats=devs[0].memory_stats())

    rows, cold, warms, ev = timed_query(js, tpch.Q3C, 2)
    err, wrong = diff_q3c(rows, q3c_exp, JOIN_TOL)
    seconds["q3c_cold_s"] = cold
    seconds["q3c_warm_median_s"] = report(
        "q3c", join_rows, cold, warms, ev, max_rel_err=err,
        sf=min(sf, JOIN_SF))
    check(not wrong, f"Q3C against the oracle (tol {JOIN_TOL}): {wrong}")
    check(ev["join_device_joins"] > 0 and ev["join_host_fallbacks"] == 0
          and ev["host_fallbacks"] == 0,
          f"Q3C did not stay on the device: {ev}")
    single["q3c"] = rows

    # ---- mesh (pre-mutation table state) -----------------------------------
    mesh = leg_mesh(s, js, len(devs), single,
                    MESH_TOL_F32 if f32 else MESH_TOL_F64, n_rows,
                    join_rows) if len(devs) > 1 else None
    if js is not s:
        js.stop()
    del js

    # ---- mutate, serve -----------------------------------------------------
    q6_after, q6_count_row = leg_mutate(s, ora)
    serve = leg_serve(s, q6_after, q6_count_row)
    s.stop()

    seconds["total_s"] = time.perf_counter() - t_start
    stats = devs[0].memory_stats() or {}
    return {
        "ok": True,
        "device": device,
        "sf": sf,
        "seed": seed,
        "lineitem_rows": n_rows,
        "reduced": reduced,
        "seconds": seconds,
        "serve": serve,
        "mesh": mesh,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": cache_entries,
        "claim": None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=17,
                    help="seed of the generated data")
    ap.add_argument("--sf", type=float, default=None,
                    help=f"TPC-H scale factor (default {FULL_SF:g}; a cut "
                         f"is listed under `reduced`, never below "
                         f"{MIN_SF:g} on the chip)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="with JAX_PLATFORMS=cpu in the environment: the "
                         "same legs at a tiny scale on the CPU")
    args = ap.parse_args(argv)

    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.cpu_rehearsal and not cpu_asked:
        print("chip_smoke: --cpu-rehearsal needs JAX_PLATFORMS=cpu in the "
              "environment", file=sys.stderr)
        return 2

    import snappydata_tpu  # noqa: F401  (x64 + compile cache before JAX runs)
    import jax

    global _PLATFORM
    _PLATFORM = platform = jax.devices()[0].platform
    reduced = []
    if args.cpu_rehearsal:
        if platform != "cpu":
            print(f"chip_smoke: rehearsal found platform {platform}",
                  file=sys.stderr)
            return 2
        sf = args.sf if args.sf is not None else REHEARSAL_SF
        reduced.append({"leg": "all", "sf": sf, "of": FULL_SF,
                        "reason": "CPU rehearsal: values and control flow "
                                  "only, no rate"})
        # the chip's dtype policy (f32 plates, f64 accumulators), so the
        # rehearsal walks the same gates
        from snappydata_tpu import config

        config.global_properties().decimal_as_float64 = False
    else:
        if platform != "tpu":
            print(f"chip_smoke: no TPU (JAX found platform {platform!r}); "
                  f"nothing was run", file=sys.stderr)
            return 2
        sf = args.sf if args.sf is not None else FULL_SF
        if sf < MIN_SF:
            print(f"chip_smoke: --sf {sf:g} is below {MIN_SF:g}",
                  file=sys.stderr)
            return 2
        if sf < FULL_SF:
            reduced.append({"leg": "all", "sf": sf, "of": FULL_SF,
                            "reason": "--sf on the command line"})
    summary = run(sf, args.seed, reduced)
    print(json.dumps(summary, default=str), flush=True)
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

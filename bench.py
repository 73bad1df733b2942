"""Headline benchmark: TPC-H Q1 + Q6 scan+aggregate throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

`python bench.py --check [candidate.json]` instead compares a bench
result against the previous BENCH_r*.json record and exits nonzero when
the Q1/Q6 geomean or load_s regresses beyond tolerance — the CI guard
that keeps either from silently sliding again (the r04→r05 load_s 4×
record turned out to be bench-machine contention, but nothing TRIPPED).
With no candidate argument it checks the newest record against the one
before it.  Tolerances (fractional, env-overridable): geomean may drop
up to SNAPPY_BENCH_GEOMEAN_TOL (default 0.35 — measured machine noise
on this container is ~25%), load_s may grow up to
SNAPPY_BENCH_LOAD_TOL (default 1.0, i.e. 2× — the r05 slide was 2.9×),
and the serving axis's detail.qps.prepared_qps may drop up to
SNAPPY_BENCH_QPS_TOL (default 0.5 — concurrency benches are noisier
than single-stream scans; skipped against pre-qps records).

Baseline context (BASELINE.md): the reference's headline claim is the
quickstart scan+group-by over a 100M-row column table at 16-20x a Spark
2.1.1 cached DataFrame on a laptop-class JVM (docs/quickstart/
performance_apache_spark.md:2-6). No absolute rows/sec is published
in-repo; we peg the baseline at 66M rows/s (100M rows in ~1.5s, the
midpoint implied by that scenario) and report vs_baseline against it.

Scale via SNAPPY_BENCH_SF (default 16.0 → 96M lineitem rows, matching the
reference's 100M-row quickstart scenario; ~2.7GB of touched columns in
HBM, ~2min load through the native ingest path).

The bench runs on the TPU and fails when JAX finds none; an explicit
JAX_PLATFORMS=cpu in the environment asks for a CPU run, which the record
labels `platform: cpu` (values and counts, no device rate). A leg that
raises is reported, the record is still printed, and the exit code is 1.
No chip reading of today's code is recorded here: see PERF.md.
"""

import json
import os
import sys
import time

import numpy as np


def check_regression(candidate: dict, baseline: dict,
                     geomean_tol: float = 0.35,
                     load_tol: float = 1.0,
                     qps_tol: float = 0.5,
                     resident_tol: float = 0.25,
                     trace_tol: float = 3.0,
                     htap_tol: float = 10.0,
                     mesh_eff: float = 0.7,
                     outofcore_ratio: float = 0.5,
                     fault_recovery: float = 1.0,
                     code_agg_ratio: float = 0.8) -> list:
    """Pure comparison used by `--check`: returns a list of human-readable
    failure strings (empty = no regression).  `candidate`/`baseline` are
    bench result records ({"value", "detail": {"load_s", ...}}).  The
    serving axis guards like the others: detail.qps.prepared_qps may drop
    at most qps_tol vs the previous record (skipped when either record
    predates the qps section — older BENCH_r*.json stay comparable).
    Compressed-domain guards (skipped on pre-compressed records): the
    stock workload must keep batches_device_decoded > 0 AND
    code_domain_predicates > 0 (the scan path actually ran over encoded
    batches), and detail.compressed.resident_bytes_per_row may grow at
    most resident_tol vs the previous record — the capacity win can't
    silently slide back to decoded plates."""
    # driver-written BENCH_r*.json wraps the bench's own record under
    # "parsed" (alongside the runner's cmd/rc/tail); accept either shape
    candidate = candidate.get("parsed") or candidate
    baseline = baseline.get("parsed") or baseline
    fails = []
    new_v, old_v = candidate.get("value"), baseline.get("value")
    if isinstance(new_v, (int, float)) and isinstance(old_v, (int, float)) \
            and old_v > 0 and new_v < old_v * (1.0 - geomean_tol):
        fails.append(
            f"geomean rows/s regressed {old_v:,.0f} -> {new_v:,.0f} "
            f"({new_v / old_v - 1.0:+.1%}; tolerance -{geomean_tol:.0%})")
    new_l = (candidate.get("detail") or {}).get("load_s")
    old_l = (baseline.get("detail") or {}).get("load_s")
    if isinstance(new_l, (int, float)) and isinstance(old_l, (int, float)) \
            and old_l > 0 and new_l > old_l * (1.0 + load_tol):
        fails.append(
            f"load_s regressed {old_l} -> {new_l} "
            f"({new_l / old_l - 1.0:+.1%}; tolerance +{load_tol:.0%})")
    new_q = (((candidate.get("detail") or {}).get("qps")) or {}) \
        .get("prepared_qps")
    old_q = (((baseline.get("detail") or {}).get("qps")) or {}) \
        .get("prepared_qps")
    if isinstance(new_q, (int, float)) and isinstance(old_q, (int, float)) \
            and old_q > 0 and new_q < old_q * (1.0 - qps_tol):
        fails.append(
            f"prepared_qps regressed {old_q:,.0f} -> {new_q:,.0f} "
            f"({new_q / old_q - 1.0:+.1%}; tolerance -{qps_tol:.0%})")
    # --- compressed-domain axes (skipped on records predating them) -----
    comp = ((candidate.get("detail") or {}).get("compressed")) or {}
    if comp and "error" not in comp:
        dd = ((candidate.get("detail") or {}).get("device_decode")) or {}
        if not dd.get("batches_device_decoded"):
            fails.append("batches_device_decoded is 0 — the default scan "
                         "path stopped engaging device decode")
        if not comp.get("code_domain_predicates"):
            fails.append("code_domain_predicates is 0 — the stock TPC-H "
                         "workload stopped evaluating predicates in the "
                         "code domain")
        new_r = comp.get("resident_bytes_per_row")
        old_r = (((baseline.get("detail") or {}).get("compressed")) or {}) \
            .get("resident_bytes_per_row")
        if isinstance(new_r, (int, float)) and \
                isinstance(old_r, (int, float)) and old_r > 0 \
                and new_r > old_r * (1.0 + resident_tol):
            fails.append(
                f"resident_bytes_per_row regressed {old_r} -> {new_r} "
                f"({new_r / old_r - 1.0:+.1%}; tolerance "
                f"+{resident_tol:.0%})")
        # aggregate-on-codes lane (skipped on records predating it):
        # all three lane counters must fire on the stock workload, and
        # measured throughput must reach code_agg_ratio of what the
        # decode-throughput law predicts from the decoded run
        ca = comp.get("code_agg") or {}
        if ca and "error" not in ca:
            lanes = ca.get("lane_counters") or {}
            for k in ("agg_code_domain", "agg_dict_space",
                      "agg_rle_runs"):
                if not lanes.get(k):
                    fails.append(
                        f"{k} is 0 — the aggregate-on-codes lane "
                        f"stopped engaging on the stock workload")
            meas = ca.get("grouped_rows_per_s_auto")
            pred = ca.get("predicted_rows_per_s")
            if isinstance(meas, (int, float)) and \
                    isinstance(pred, (int, float)) and pred > 0 \
                    and meas < pred * code_agg_ratio:
                fails.append(
                    f"aggregate-on-codes {meas:,.0f} rows/s is below "
                    f"{code_agg_ratio:.0%} of the decode-throughput-law "
                    f"prediction {pred:,.0f}")
    # --- tracing-overhead axis (skipped on records predating it) --------
    # enabling request tracing must cost < trace_tol percent on the
    # stock Q1/Q6 geomean — the span layer stays cheap enough to leave
    # ON in production (candidate-only: an absolute bound, no baseline)
    trc = ((candidate.get("detail") or {}).get("tracing")) or {}
    ov = trc.get("overhead_pct")
    if isinstance(ov, (int, float)) and ov > trace_tol:
        fails.append(
            f"tracing overhead {ov:.2f}% exceeds {trace_tol:.2f}% on the "
            f"stock workload geomean (on={trc.get('geomean_on')}, "
            f"off={trc.get('geomean_off')} rows/s)")
    # --- HTAP axis (skipped on records predating it) --------------------
    # concurrent scan+ingest is the MVCC claim: every snapshot read must
    # be value-correct (mismatches are a hard fail, candidate-only), and
    # the concurrent scan p50 may blow up at most htap_tol× over the
    # serialized baseline's p50 — isolation can't silently regress into
    # readers stalling behind the write path again (p99 stays unguarded:
    # it legitimately absorbs a batch-bucket re-specialization)
    ht = ((candidate.get("detail") or {}).get("htap")) or {}
    if ht and "error" not in ht:
        if ht.get("value_mismatches"):
            fails.append(
                f"htap snapshot reads diverged from the serialized "
                f"replay ({ht['value_mismatches']} mismatches)")
        new_p = (ht.get("concurrent") or {}).get("scan_p50_ms")
        ser_p = (ht.get("serialized") or {}).get("scan_p50_ms")
        if isinstance(new_p, (int, float)) and \
                isinstance(ser_p, (int, float)) and ser_p > 0 \
                and new_p > ser_p * htap_tol:
            fails.append(
                f"htap concurrent scan p50 {new_p}ms exceeds "
                f"{htap_tol:.0f}x the serialized baseline ({ser_p}ms) — "
                f"scans are stalling behind ingest again")
    # --- out-of-core axis (skipped on records predating it) -------------
    # the tiered-storage claim: capping the device budget below 10% of
    # the table must stream answers that are VALUE-IDENTICAL (hard
    # fail), the double buffer must actually overlap upload with
    # compute (prefetch_overlap_ms > 0), and the constricted scan keeps
    # >= outofcore_ratio of the in-HBM rows/s (candidate-only guards)
    oc = ((candidate.get("detail") or {}).get("outofcore")) or {}
    if oc and "error" not in oc:
        if oc.get("value_mismatches"):
            fails.append(
                f"out-of-core answers diverged from in-HBM "
                f"({oc['value_mismatches']} mismatches)")
        if not oc.get("prefetch_overlap_ms"):
            fails.append("prefetch_overlap_ms is 0 — the double-buffered "
                         "prefetcher never overlapped an upload with "
                         "compute on the constricted scan")
        ratio = oc.get("throughput_ratio")
        if isinstance(ratio, (int, float)) and ratio < outofcore_ratio:
            fails.append(
                f"out-of-core throughput ratio {ratio} below "
                f"{outofcore_ratio} of in-HBM "
                f"({oc.get('outofcore_rows_per_s')} vs "
                f"{oc.get('inhbm_rows_per_s')} rows/s at "
                f"{oc.get('budget_fraction')} device budget)")
    # --- mesh axis (skipped on records predating it) --------------------
    # sharded execution is the scale claim: every mesh answer must equal
    # single-device (hard fail), the shard_map lane must actually run,
    # per-device scaling efficiency at 8 devices (aggregate-throughput
    # retention on a serialized-core rig) must hold >= mesh_eff, and the
    # sharded per-device residency must stay at ENCODED parity with the
    # single-device number (candidate-only guards — the whole section
    # is self-contained evidence)
    mc = (candidate.get("detail") or {}).get("multichip")
    if isinstance(mc, dict) and mc and "error" not in mc:
        if mc.get("value_mismatches"):
            fails.append(
                f"multichip sharded answers diverged from single-device "
                f"({mc['value_mismatches']} mismatches)")
        if not mc.get("mesh_shard_execs"):
            fails.append("mesh_shard_execs is 0 — the shard_map partial "
                         "lane never ran on the mesh workload")
        e8 = (mc.get("scaling_efficiency") or {}).get("8")
        if isinstance(e8, (int, float)) and e8 < mesh_eff:
            fails.append(
                f"mesh scaling efficiency at 8 devices {e8} below "
                f"{mesh_eff} (per-device throughput retention)")
        shr = mc.get("resident_bytes_per_row_sharded")
        sgl = mc.get("resident_bytes_per_row_single")
        if isinstance(shr, (int, float)) and isinstance(sgl, (int, float)) \
                and sgl > 0 and shr > sgl * (1.0 + resident_tol):
            fails.append(
                f"sharded resident bytes/row {shr} exceeds single-device "
                f"{sgl} by more than {resident_tol:.0%} — sharded tables "
                f"stopped staying encoded per device")
    # --- fault-storm axis (skipped on records predating it) -------------
    # the self-healing claim: every fault the seeded storm injects must
    # end in recovery or a typed retryable error — never a wrong row
    # (value_mismatches is a hard fail) and never unaccounted
    # (recovered + typed_errors >= fault_recovery * injected, default
    # 1.0 via SNAPPY_BENCH_FAULT_RECOVERY — fully accounted)
    fs = ((candidate.get("detail") or {}).get("faultstorm")) or {}
    if fs and "error" not in fs:
        if fs.get("value_mismatches"):
            fails.append(
                f"fault storm produced wrong rows "
                f"({fs['value_mismatches']} value mismatches: "
                f"{(fs.get('unexpected') or ['?'])[:3]})")
        if fs.get("unexpected"):
            fails.append(
                f"fault storm hit untyped/unaccounted failures: "
                f"{fs['unexpected'][:3]}")
        ratio = fs.get("recovery_ratio")
        if isinstance(ratio, (int, float)) and fs.get("injected") \
                and ratio < fault_recovery:
            fails.append(
                f"fault storm recovery ratio {ratio} below "
                f"{fault_recovery} ({fs.get('accounted')} of "
                f"{fs.get('injected')} injected faults accounted as "
                f"recovered or typed-retryable)")
    return fails


def _bench_records(root: str) -> list:
    """BENCH_r*.json paths in round order."""
    import glob
    import re

    paths = glob.glob(os.path.join(root, "BENCH_r*.json"))
    return sorted(paths, key=lambda p: int(
        re.search(r"BENCH_r(\d+)", p).group(1)))


def run_check(argv: list) -> int:
    root = os.path.dirname(os.path.abspath(__file__))
    records = _bench_records(root)
    if argv:
        cand_path = argv[0]
        # baseline = newest record that is NOT the candidate itself: a
        # just-written BENCH_r*.json checked by path must compare against
        # its predecessor, never against itself (always-pass)
        cand_real = os.path.realpath(cand_path)
        others = [p for p in records
                  if os.path.realpath(p) != cand_real]
        base_path = others[-1] if others else None
    else:
        cand_path = records[-1] if len(records) >= 2 else None
        base_path = records[-2] if len(records) >= 2 else None
    if cand_path is None or base_path is None:
        print("bench --check: need at least two records (or a candidate "
              "file + one BENCH_r*.json)", file=sys.stderr)
        return 2
    with open(cand_path) as fh:
        candidate = json.load(fh)
    with open(base_path) as fh:
        baseline = json.load(fh)
    fails = check_regression(
        candidate, baseline,
        geomean_tol=float(os.environ.get("SNAPPY_BENCH_GEOMEAN_TOL",
                                         "0.35")),
        load_tol=float(os.environ.get("SNAPPY_BENCH_LOAD_TOL", "1.0")),
        qps_tol=float(os.environ.get("SNAPPY_BENCH_QPS_TOL", "0.5")),
        resident_tol=float(os.environ.get("SNAPPY_BENCH_RESIDENT_TOL",
                                          "0.25")),
        trace_tol=float(os.environ.get("SNAPPY_BENCH_TRACE_TOL", "3.0")),
        htap_tol=float(os.environ.get("SNAPPY_BENCH_HTAP_TOL", "10.0")),
        mesh_eff=float(os.environ.get("SNAPPY_BENCH_MESH_EFF", "0.7")),
        outofcore_ratio=float(os.environ.get(
            "SNAPPY_BENCH_OUTOFCORE_RATIO", "0.5")),
        fault_recovery=float(os.environ.get(
            "SNAPPY_BENCH_FAULT_RECOVERY", "1.0")),
        code_agg_ratio=float(os.environ.get(
            "SNAPPY_BENCH_CODE_AGG_RATIO", "0.8")))
    rel = os.path.basename
    if fails:
        for f in fails:
            print(f"bench --check FAIL ({rel(cand_path)} vs "
                  f"{rel(base_path)}): {f}", file=sys.stderr)
        return 1
    print(f"bench --check OK: {rel(cand_path)} within tolerance of "
          f"{rel(base_path)}", file=sys.stderr)
    return 0


def main() -> None:
    repeats = int(os.environ.get("SNAPPY_BENCH_REPEATS", "5"))

    import jax

    dev0 = jax.devices()[0]
    platform = dev0.platform
    device = {"platform": platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform != "tpu" and not (platform == "cpu" and cpu_asked):
        print(f"bench: no TPU (JAX found {device}); set JAX_PLATFORMS=cpu "
              f"to ask for a CPU run explicitly", file=sys.stderr)
        sys.exit(2)
    print(f"bench: device {device}", file=sys.stderr, flush=True)
    # legs that raised: reported in the record, and the run exits 1
    failures = []
    sf_default = "4.0" if platform == "cpu" else "16.0"
    sf = float(os.environ.get("SNAPPY_BENCH_SF", sf_default))

    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.utils import tpch

    # pin the dtype policy NOW so nothing re-queries backend state mid-run
    config.global_properties().decimal_as_float64 = platform == "cpu"

    # TPU smoke: one small query compiled + executed + VALUE-ASSERTED on
    # the real backend before the big load, so numeric regressions surface
    # here with a clear message instead of as a wrong headline number
    smoke = SnappySession(catalog=Catalog())
    smoke.sql("CREATE TABLE smoke (g BIGINT, v DOUBLE) USING column")
    smoke.insert_arrays("smoke", [
        np.arange(1000, dtype=np.int64) % 4,
        np.arange(1000, dtype=np.float64)])
    row = smoke.sql("SELECT g, count(*), sum(v) FROM smoke GROUP BY g "
                    "ORDER BY g").rows()
    assert [r[0] for r in row] == [0, 1, 2, 3], row
    assert all(r[1] == 250 for r in row), row
    exp = [float(sum(range(g, 1000, 4))) for g in range(4)]
    for r, e in zip(row, exp):
        assert abs(r[2] - e) <= 1e-6 * e, (r, e)
    print(f"bench: {platform} smoke OK (grouped agg value-asserted)",
          file=sys.stderr, flush=True)

    s = SnappySession(catalog=Catalog())
    t0 = time.time()
    tpch.load_tpch(s, sf=sf, seed=17)
    load_s = time.time() - t0
    n_rows = s.catalog.lookup_table("lineitem").data.snapshot().total_rows()

    # ---- full-value Q1 assertion against an exact float64 oracle -------
    # (round-3 verdict task 2: the shipping TPU dtype policy — f32 plates
    # + f64 accumulators — must keep TPC-H aggregates within 1e-6)
    q1_max_rel_err = _assert_q1_values(s, sf)
    print(f"bench: Q1 full-value check OK (max rel err "
          f"{q1_max_rel_err:.2e})", file=sys.stderr, flush=True)

    from snappydata_tpu.observability.metrics import global_registry

    timings = {}
    agg_detail = {}
    for name, q in (("q1", tpch.Q1), ("q6", tpch.Q6)):
        s.sql(q)  # compile + first run
        c0 = dict(global_registry().snapshot()["counters"])
        best = float("inf")
        for _ in range(repeats):
            t0 = time.time()
            s.sql(q)
            best = min(best, time.time() - t0)
        timings[name] = best
        # chosen reduction strategy + fused-pass counts, so the bench
        # trajectory explains ITSELF (which strategy the auto table
        # picked, whether the group-index cache carried the repeats)
        c1 = global_registry().snapshot()["counters"]

        def delta(key):
            return c1.get(key, 0) - c0.get(key, 0)

        agg_detail[name] = {
            "reduce_passes_per_run":
                round(delta("agg_reduce_passes") / repeats, 2),
            "strategies": {
                st: delta(f"agg_strategy_{st}")
                for st in ("unroll", "scatter", "matmul", "runs")
                if delta(f"agg_strategy_{st}")},
            "gidx_cache_hits": delta("gidx_cache_hits"),
            "gidx_cache_misses": delta("gidx_cache_misses"),
        }

    # ---- tracing: per-query phase breakdown + enabling-cost guard ------
    # one traced run per headline query pulls the span tree apart into
    # compile/bind/execute/transfer seconds (device_execute ≈ async
    # dispatch; transfer absorbs the compute wait — see executor notes),
    # then the SAME best-of-repeats loop re-runs with tracing disabled:
    # the on-vs-off geomean delta is the enabling cost `--check` guards
    # at < SNAPPY_BENCH_TRACE_TOL percent (default 3)
    from snappydata_tpu.observability import tracing as _tracing

    props = config.global_properties()
    saved_tracing = props.tracing_enabled
    phases_detail = {}
    try:
        props.tracing_enabled = True   # phase capture needs a trace
        for name, q in (("q1", tpch.Q1), ("q6", tpch.Q6)):
            s.sql(q)
            tr = _tracing.ring().last()
            ph = tr.phase_seconds() if tr is not None else {}
            phases_detail[name] = {
                "compile_s": round(ph.get("compile", 0.0)
                                   + ph.get("jit_compile", 0.0), 6),
                "bind_s": round(ph.get("bind", 0.0), 6),
                "execute_s": round(ph.get("device_execute", 0.0), 6),
                "transfer_s": round(ph.get("transfer", 0.0), 6),
            }
    except Exception as e:
        failures.append(f"phases: {type(e).__name__}: {e}")
        phases_detail = {"error": str(e)}
    finally:
        props.tracing_enabled = saved_tracing

    tracing_detail = None
    try:
        # measure BOTH legs explicitly (never reuse the headline loop:
        # it ran under whatever the operator configured) and restore
        # the configured value, whatever it was
        legs = {}
        try:
            for flag in (True, False):
                props.tracing_enabled = flag
                dest = legs.setdefault(flag, {})
                for name, q in (("q1", tpch.Q1), ("q6", tpch.Q6)):
                    s.sql(q)
                    best = float("inf")
                    for _ in range(repeats):
                        t0 = time.time()
                        s.sql(q)
                        best = min(best, time.time() - t0)
                    dest[name] = best
        finally:
            props.tracing_enabled = saved_tracing
        geo_on = float(np.sqrt((n_rows / legs[True]["q1"])
                               * (n_rows / legs[True]["q6"])))
        geo_off = float(np.sqrt((n_rows / legs[False]["q1"])
                                * (n_rows / legs[False]["q6"])))
        tracing_detail = {
            "geomean_on": round(geo_on, 1),
            "geomean_off": round(geo_off, 1),
            "overhead_pct":
                round(max(0.0, (geo_off - geo_on) / geo_off * 100.0), 3),
        }
        print(f"bench: tracing overhead "
              f"{tracing_detail['overhead_pct']}% (on "
              f"{geo_on:,.0f} vs off {geo_off:,.0f} rows/s geomean)",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"tracing: {type(e).__name__}: {e}")
        print(f"bench: tracing overhead bench failed: {e}",
              file=sys.stderr, flush=True)
        tracing_detail = {"error": str(e)}

    # ---- device-only timings (jitted fn on resident arrays) ------------
    # separates XLA execute time from the session/bind/host overhead the
    # end-to-end numbers include (round-2/3 instrumentation ask)
    device = {}
    for name, q in (("q1", tpch.Q1), ("q6", tpch.Q6)):
        try:
            device[name] = _device_only_best(s, q, repeats)
        except Exception as e:  # instrumentation must not kill the bench
            failures.append(f"device_only: {type(e).__name__}: {e}")
            print(f"bench: device-only timing for {name} failed: {e}",
                  file=sys.stderr, flush=True)
            device[name] = None

    # Compressed-domain evidence: code-domain predicates + dictionary
    # batch skipping + resident-bytes-per-row vs the decoded path, with
    # full Q1/Q6 value assertions between the two (the knob rides the
    # compiled plan's STATIC key, so flipping it re-specializes without
    # cache flushes)
    compressed = None
    try:
        compressed = _compressed_bench(s)
        compressed["code_agg"] = _code_agg_bench(s, repeats)
        ca = compressed["code_agg"]
        print(f"bench: aggregate-on-codes "
              f"{ca['grouped_rows_per_s_on']:,.0f} rows/s on vs "
              f"{ca['grouped_rows_per_s_off']:,.0f} off, auto "
              f"{ca['grouped_rows_per_s_auto']:,.0f} (predicted "
              f"{ca['predicted_rows_per_s']:,.0f}, byte ratio "
              f"{ca['byte_ratio']}x), lanes {ca['lane_counters']}",
              file=sys.stderr, flush=True)
        print(f"bench: compressed-domain resident "
              f"{compressed['resident_bytes_per_row']} B/row vs decoded "
              f"{compressed['resident_bytes_per_row_decoded']} "
              f"({compressed['resident_reduction']}x), "
              f"{compressed['code_domain_predicates']} code preds, "
              f"{compressed['batches_skipped_dict']} dict-skipped "
              f"batches, values asserted identical",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"compressed: {type(e).__name__}: {e}")
        print(f"bench: compressed-domain bench failed: {e}",
              file=sys.stderr, flush=True)
        compressed = {"error": str(e)}

    # Q3-class device join+aggregate (the one-to-many expansion path)
    # vs the r05-era host pandas-merge path, value-asserted
    q3 = None
    try:
        q3 = _join_bench(s, n_rows, repeats)
        print(f"bench: Q3C device {q3['q3_s']}s vs host "
              f"{q3['q3_host_s']}s ({q3['q3_speedup']}x), "
              f"fallbacks={q3['q3_join']['host_fallbacks']}",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"q3: {type(e).__name__}: {e}")
        print(f"bench: join bench failed: {e}", file=sys.stderr,
              flush=True)
        q3 = {"q3_error": str(e)}

    # materialized-view maintenance: delta appends fold O(delta) while
    # repeated view reads stay O(G) — vs re-running the aggregate O(N)
    matview = None
    try:
        matview = _matview_bench(s, repeats)
        print(f"bench: matview read {matview['view_read_s']}s vs "
              f"re-aggregate {matview['equiv_agg_s']}s "
              f"({matview['view_read_speedup']}x), "
              f"{matview['view_delta_folds']} delta folds / "
              f"{matview['full_refreshes_during_folds']} rescans",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"matview: {type(e).__name__}: {e}")
        print(f"bench: matview bench failed: {e}", file=sys.stderr,
              flush=True)
        matview = {"matview_error": str(e)}

    # high-QPS serving: prepared+micro-batched vs naive per-query sql()
    # on a mixed point-lookup/small-agg workload, N concurrent clients
    qps = None
    try:
        qps = _qps_bench()
        print(f"bench: qps naive {qps['naive_qps']} vs prepared+batched "
              f"{qps['prepared_qps']} ({qps['qps_speedup']}x, "
              f"occupancy {qps['batch_occupancy']}, p50 {qps['p50_ms']}ms "
              f"p99 {qps['p99_ms']}ms, "
              f"{qps['recompiles_after_warmup']} recompiles after warmup)",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"qps: {type(e).__name__}: {e}")
        print(f"bench: qps bench failed: {e}", file=sys.stderr,
              flush=True)
        qps = {"qps_error": str(e)}

    # availability trajectory: QPS/p99 through a scripted kill/rejoin
    # window (steady → degraded → recovered), value-asserted throughout
    resilience = None
    try:
        resilience = _resilience_bench()
        print(f"bench: resilience qps steady "
              f"{resilience['steady']['qps']} → degraded "
              f"{resilience['degraded']['qps']} → recovered "
              f"{resilience['recovered']['qps']} (p99 "
              f"{resilience['steady']['p99_ms']}/"
              f"{resilience['degraded']['p99_ms']}/"
              f"{resilience['recovered']['p99_ms']}ms, "
              f"{resilience['rejoin_clean_buckets']} clean + "
              f"{resilience['rejoin_copied_buckets']} copied buckets on "
              f"rejoin, {resilience['degraded_buckets_after_rejoin']} "
              f"degraded after)", file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"resilience: {type(e).__name__}: {e}")
        print(f"bench: resilience bench failed: {e}", file=sys.stderr,
              flush=True)
        resilience = {"resilience_error": str(e)}

    # HTAP: concurrent scan+ingest on one table under MVCC snapshot
    # pins vs the serialized schedule, value-asserted per scan
    htap = None
    try:
        htap = _htap_bench()
        print(f"bench: htap scan p50/p99 "
              f"{htap['concurrent']['scan_p50_ms']}/"
              f"{htap['concurrent']['scan_p99_ms']}ms concurrent vs "
              f"{htap['serialized']['scan_p50_ms']}/"
              f"{htap['serialized']['scan_p99_ms']}ms serialized, "
              f"ingest {htap['concurrent']['ingest_rows_per_s']} vs "
              f"{htap['serialized']['ingest_rows_per_s']} rows/s, "
              f"{htap['value_mismatches']} value mismatches, "
              f"{htap['retained_epoch_bytes_after']} retained bytes "
              f"after drain", file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"htap: {type(e).__name__}: {e}")
        print(f"bench: htap bench failed: {e}", file=sys.stderr,
              flush=True)
        htap = {"error": str(e)}

    # Out-of-core: same scan in-HBM vs device budget capped < 10% of
    # the table (tier ladder + double-buffered host→HBM tile prefetch),
    # value-asserted
    outofcore = None
    try:
        outofcore = _outofcore_bench()
        print(f"bench: outofcore {outofcore['outofcore_rows_per_s']:,} "
              f"rows/s at {outofcore['budget_fraction']:.1%} device "
              f"budget vs {outofcore['inhbm_rows_per_s']:,} in-HBM "
              f"(ratio {outofcore['throughput_ratio']}, "
              f"{outofcore['scan_tiles']} tiles, "
              f"{outofcore['prefetch_windows_warmed']} windows warmed, "
              f"overlap {outofcore['prefetch_overlap_ms']}ms, "
              f"{outofcore['tier_demotions_hbm']} HBM demotions, "
              f"{outofcore['value_mismatches']} value mismatches)",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"outofcore: {type(e).__name__}: {e}")
        print(f"bench: outofcore bench failed: {e}", file=sys.stderr,
              flush=True)
        outofcore = {"error": str(e)}

    # Fault storm: seeded fault injection over the constricted HTAP
    # workload; every injected fault must be accounted as recovered or
    # typed-retryable, with zero wrong rows (guarded by --check)
    faultstorm = None
    try:
        faultstorm = _faultstorm_bench()
        print(f"bench: faultstorm {faultstorm['injected']} faults "
              f"injected (seed {faultstorm['seed']}), "
              f"{faultstorm['recovered']} recovered in place, "
              f"{faultstorm['typed_errors']} typed errors, ratio "
              f"{faultstorm['recovery_ratio']}, "
              f"{faultstorm['crash_recoveries']} crash-recoveries, "
              f"{faultstorm['value_mismatches']} value mismatches, "
              f"scan p50/p99 {faultstorm['scan_p50_ms']}/"
              f"{faultstorm['scan_p99_ms']}ms vs clean "
              f"{faultstorm['clean']['scan_p50_ms']}/"
              f"{faultstorm['clean']['scan_p99_ms']}ms, "
              f"tier {faultstorm['tier']}, in {faultstorm['storm_s']}s",
              file=sys.stderr, flush=True)
    except Exception as e:
        failures.append(f"faultstorm: {type(e).__name__}: {e}")
        print(f"bench: faultstorm bench failed: {e}", file=sys.stderr,
              flush=True)
        faultstorm = {"error": str(e)}

    # Mesh-sharded execution at 1/2/4/8 VIRTUAL CPU devices (a
    # forced-topology subprocess — XLA's device-count flag must precede
    # backend init), every sharded answer value-asserted against
    # single-device. It runs only on an explicit CPU run: a chip record
    # must not carry CPU rates, and a child cannot share the parent's
    # chip. The mesh on real chips is chip_smoke.py's mesh leg.
    multichip = "not measured"
    if platform == "cpu" \
            and os.environ.get("SNAPPY_BENCH_MULTICHIP", "1") != "0":
        try:
            multichip = _multichip_bench()
            print(f"bench: multichip sf={multichip['sf']} efficiency "
                  f"2/4/8 dev = "
                  f"{multichip['scaling_efficiency']['2']}/"
                  f"{multichip['scaling_efficiency']['4']}/"
                  f"{multichip['scaling_efficiency']['8']}, "
                  f"{multichip['value_mismatches']} value mismatches, "
                  f"resident {multichip['resident_bytes_per_row_sharded']}"
                  f" B/row sharded vs "
                  f"{multichip['resident_bytes_per_row_single']} single, "
                  f"{multichip['mesh_shard_execs']} shard_map execs",
                  file=sys.stderr, flush=True)
        except Exception as e:
            failures.append(f"multichip: {type(e).__name__}: {e}")
            print(f"bench: multichip bench failed: {e}", file=sys.stderr,
                  flush=True)
            multichip = {"error": str(e)}

    ingest_rows_per_s = sink_events_per_s = durable_ingest = None
    try:   # secondary benches must not kill the headline numbers
        ingest_rows_per_s = _ingest_bench()
        sink_events_per_s = _sink_bench()
        durable_ingest = _durable_ingest_bench()
    except Exception as e:
        failures.append(f"ingest: {type(e).__name__}: {e}")
        print(f"bench: ingest/sink bench failed: {e}",
              file=sys.stderr, flush=True)

    rows_per_s = {k: n_rows / v for k, v in timings.items()}
    geomean = float(np.sqrt(rows_per_s["q1"] * rows_per_s["q6"]))
    baseline = 66e6  # see module docstring
    print(json.dumps({
        "metric": "rows/sec scanned+aggregated (TPC-H Q1/Q6 geomean, "
                  f"{n_rows}-row column table)",
        "value": round(geomean, 1),
        "unit": "rows/s",
        "vs_baseline": round(geomean / baseline, 3),
        "detail": {
            "platform": platform,
            "device": device,
            "failures": failures,
            "sf": sf,
            "rows": n_rows,
            "load_s": round(load_s, 2),
            # ingest throughput tracked alongside Q1/Q6 (the r04→r05
            # per-append-fsync regression was only visible by diffing
            # load_s by hand)
            "load_rows_per_s": round(n_rows / load_s, 1),
            "q1_s": round(timings["q1"], 4),
            "q6_s": round(timings["q6"], 4),
            "q1_rows_per_s": round(rows_per_s["q1"], 1),
            "q6_rows_per_s": round(rows_per_s["q6"], 1),
            "q1_device_s": None if device.get("q1") is None
            else round(device["q1"], 4),
            "q6_device_s": None if device.get("q6") is None
            else round(device["q6"], 4),
            "q1_device_rows_per_s": None if device.get("q1") is None
            else round(n_rows / device["q1"], 1),
            "q6_device_rows_per_s": None if device.get("q6") is None
            else round(n_rows / device["q6"], 1),
            "q1_max_rel_err": q1_max_rel_err,
            # reduction-strategy evidence per headline query (strategy
            # picked by the auto table, fused passes per run, gidx
            # cache behavior across the repeats)
            "agg": agg_detail,
            # per-query phase breakdown read off the request trace's
            # span tree (compile_s sums plan compile + first-dispatch
            # jit; execute_s is the async dispatch; transfer_s absorbs
            # the compute wait — the device_s fields above are the
            # blocking ground truth)
            "phases": phases_detail,
            # enabling-cost evidence for the --check guard: the stock
            # Q1/Q6 geomean with tracing on (the headline) vs off,
            # overhead_pct guarded < SNAPPY_BENCH_TRACE_TOL (3%)
            "tracing": tracing_detail,
            # Q3-class join+aggregate evidence (device join engine):
            # q3_s/q3_rows_per_s time the DEVICE path (best of repeats),
            # q3_host_s the r05-era pandas host join (one timed run,
            # device_join=off), q3_speedup their ratio; q3_join carries
            # the per-run strategy detail — host_fallbacks MUST be 0
            # (the query stayed on device), build_sorts counts argsorts
            # across all repeats (1 = the artifact cache carried the
            # rest), expand_factor is output rows per probe row
            "q3": q3,
            # materialized-view maintenance evidence: view_read_s times
            # SELECT * over the maintained state (O(G)), equiv_agg_s
            # re-runs the defining aggregate over the base (O(N));
            # view_delta_folds counts one fold per delta append with
            # full_refreshes_during_folds == 0 proving no rescans, and
            # rows_folded == the delta rows (O(delta) maintenance)
            "matview": matview,
            # serving-axis evidence: naive_qps times per-query sql()
            # (parse+plan every statement), prepared_qps the serving
            # registry + micro-batcher on the SAME workload (results
            # value-asserted identical inside the bench);
            # batch_occupancy is fused requests per device dispatch,
            # recompiles_after_warmup MUST be 0 (compile-once claim) and
            # plan_key_builds 0 (no per-execute re-tokenization)
            "qps": qps,
            # availability-axis evidence: point-read qps + p99 through a
            # scripted kill → rejoin window on a redundancy-1 cluster.
            # steady/degraded/recovered give availability a TRAJECTORY
            # next to rows/s and qps; every query in every phase is
            # value-asserted, and degraded_buckets_after_rejoin MUST be
            # 0 (the watermark resync restored redundancy without a
            # manual restore_redundancy())
            "resilience": resilience,
            # HTAP-axis evidence (MVCC snapshot isolation): scan p50/p99
            # + ingest rows/s with both workloads hammering ONE table
            # concurrently vs serialized; every concurrent scan reads a
            # pinned epoch and is value-asserted (value_mismatches MUST
            # be 0, guarded by --check along with a p99-blowup bound);
            # retained_epoch_bytes_after proves retention drains once
            # readers release
            "htap": htap,
            # out-of-core-axis evidence (tiered storage): the same scan
            # with the device budget capped < 10% of the table, streamed
            # tile-by-tile through the double-buffered host→HBM
            # prefetcher; value_mismatches MUST be 0 and
            # prefetch_overlap_ms > 0 (upload really overlapped
            # compute), with outofcore/in-HBM rows/s guarded ≥
            # SNAPPY_BENCH_OUTOFCORE_RATIO by --check
            "outofcore": outofcore,
            # fault-storm-axis evidence (failpoints + self-healing):
            # seeded injection across WAL/checkpoint/tier/prefetch/
            # admission seams; recovery_ratio is recovered+typed over
            # injected (guarded ≥ SNAPPY_BENCH_FAULT_RECOVERY by
            # --check, default 1.0) and value_mismatches MUST be 0 —
            # an injected fault may slow an answer or fail it with a
            # typed error, never change it
            "faultstorm": faultstorm,
            # mesh-axis evidence (explicit CPU runs only; "not
            # measured" on a chip): sharded Q1/Q6/Q3C at 1/2/4/8 virtual
            # CPU devices, value-asserted vs single-device.
            # scaling_efficiency is aggregate-throughput RETENTION per
            # mesh size (serialized-core rig: ideal = 1.0; real
            # multi-chip lanes show >1) guarded ≥ SNAPPY_BENCH_MESH_EFF;
            # resident_bytes_per_row_sharded proves plates stay ENCODED
            # per device (guarded vs the single-device number)
            "multichip": multichip,
            "ingest_rows_per_s": ingest_rows_per_s,
            "sink_events_per_s": sink_events_per_s,
            # durable (WAL'd) ingest per wal_fsync_mode, with the fsync
            # count each mode paid — the group-commit write path's
            # evidence record
            "durable_ingest": durable_ingest,
            # in-trace decode counters: bytes actually shipped over the
            # host->device link for RLE/bitset binds vs the decoded
            # plate bytes they replaced (round-4 device_decode feature,
            # now evidenced in the bench record)
            "device_decode": _decode_counters(),
            # compressed-domain execution evidence: predicates served on
            # codes/runs, dictionary-domain batch skipping, per-reason
            # decode-first fallbacks, and resident HBM bytes/row vs the
            # decoded path (the capacity lever) — all value-asserted
            # against the decoded path inside _compressed_bench
            "compressed": compressed,
        },
    }))
    if failures:
        print(f"bench: {len(failures)} leg(s) failed: {failures}",
              file=sys.stderr, flush=True)
        sys.exit(1)


def _multichip_child() -> None:
    """Child process for the multichip detail: forces an 8-virtual-CPU
    device topology (XLA_FLAGS must precede jax init — hence the
    subprocess), loads the mesh workload once, and measures REAL sharded
    Q1/Q6/Q3C execution at 1/2/4/8 devices — every mesh answer
    value-asserted against the single-device run of the same data.
    Prints ONE JSON line; the parent embeds it as detail.multichip and
    in the record `--multichip <path>` writes."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.observability.metrics import global_registry
    from snappydata_tpu.parallel import MeshContext, data_mesh
    from snappydata_tpu.storage.device import device_cache_bytes_by_device
    from snappydata_tpu.utils import tpch

    config.global_properties().decimal_as_float64 = True
    sf = float(os.environ.get("SNAPPY_BENCH_MESH_SF", "1.0"))
    reps = int(os.environ.get("SNAPPY_BENCH_MESH_REPEATS", "3"))
    s = SnappySession(catalog=Catalog())
    t0 = time.time()
    tpch.load_tpch(s, sf=sf, seed=17)
    load_s = time.time() - t0
    n_rows = s.catalog.lookup_table(
        "lineitem").data.snapshot().total_rows()
    reg = global_registry()
    queries = (("q1", tpch.Q1), ("q6", tpch.Q6), ("q3c", tpch.Q3C))

    def _clear_caches():
        s.executor.clear_cache()
        for ti in s.catalog.list_tables():
            if hasattr(ti.data, "_device_cache"):
                ti.data._device_cache.clear()

    def _resident_per_row() -> float:
        per_dev = device_cache_bytes_by_device(
            (i.name, i.data) for i in s.catalog.list_tables())
        return round(sum(per_dev.values()) / max(1, n_rows), 2)

    def _rows_cmp(a, b) -> int:
        bad = 0
        if len(a) != len(b):
            return max(1, abs(len(a) - len(b)))
        for ra, rb in zip(a, b):
            for x, y in zip(ra, rb):
                if isinstance(x, float) or isinstance(y, float):
                    if not (abs(float(x) - float(y))
                            <= 1e-9 * max(1.0, abs(float(x)))):
                        bad += 1
                elif x != y:
                    bad += 1
        return bad

    def _measure():
        """best-of-reps per query + resident bytes/row measured from a
        fresh cache after the SCAN queries only (Q3C's decoded join
        plates must not pollute the encoded-residency comparison —
        the r06 compressed-bench review finding)."""
        out = {}
        _clear_caches()
        for name, q in queries[:2]:
            rows = s.sql(q).rows()   # compile + warm
            best = float("inf")
            for _ in range(reps):
                t1 = time.time()
                s.sql(q)
                best = min(best, time.time() - t1)
            out[name] = {"s": round(best, 4),
                         "rows_per_s": round(n_rows / best, 1),
                         "rows": rows}
        out["resident_bytes_per_row"] = _resident_per_row()
        for name, q in queries[2:]:
            rows = s.sql(q).rows()
            best = float("inf")
            for _ in range(reps):
                t1 = time.time()
                s.sql(q)
                best = min(best, time.time() - t1)
            out[name] = {"s": round(best, 4),
                         "rows_per_s": round(n_rows / best, 1),
                         "rows": rows}
        return out

    single = _measure()
    mesh_runs = {}
    mismatches = 0
    c0 = dict(reg.snapshot()["counters"])
    for nd in (1, 2, 4, 8):
        with MeshContext(data_mesh(nd)):
            m = _measure()
        for name, _q in queries:
            mismatches += _rows_cmp(single[name]["rows"], m[name]["rows"])
            m[name].pop("rows")
        mesh_runs[str(nd)] = m
    c1 = reg.snapshot()["counters"]
    for name, _q in queries:
        single[name].pop("rows")

    def eff(nd: str) -> float:
        vals = [mesh_runs[nd][n]["rows_per_s"]
                / max(1e-9, mesh_runs["1"][n]["rows_per_s"])
                for n, _ in queries]
        return round(float(np.prod(vals) ** (1.0 / len(vals))), 3)

    result = {
        "platform": "cpu",   # virtual devices: values and counts, no rate
        "sf": sf,
        "rows": int(n_rows),
        "load_s": round(load_s, 2),
        "n_devices": 8,
        "single": single,
        "mesh": mesh_runs,
        "value_mismatches": int(mismatches),
        # aggregate-throughput retention per mesh size (geomean over
        # Q1/Q6/Q3C of rows/s at D vs the 1-device mesh run): on a
        # serialized-core CPU rig ideal scaling is FLAT (1.0 — the
        # collectives and padding are the only cost), on a real
        # multi-chip lane the same number shows true speedup.  Per-device
        # efficiency at D is retention(D): each device retains that
        # fraction of its fair share.
        "scaling_efficiency": {nd: eff(nd) for nd in ("2", "4", "8")},
        "resident_bytes_per_row_single":
            single["resident_bytes_per_row"],
        "resident_bytes_per_row_sharded":
            mesh_runs["8"]["resident_bytes_per_row"],
        "mesh_shard_execs":
            c1.get("mesh_shard_execs", 0) - c0.get("mesh_shard_execs", 0),
        "mesh_psum_merges":
            c1.get("mesh_psum_merges", 0) - c0.get("mesh_psum_merges", 0),
        "mesh_join_broadcast":
            c1.get("mesh_join_broadcast", 0)
            - c0.get("mesh_join_broadcast", 0),
        "mesh_join_shuffle":
            c1.get("mesh_join_shuffle", 0)
            - c0.get("mesh_join_shuffle", 0),
        "mesh_fallbacks": {
            k[len("mesh_fallback_"):]: c1.get(k, 0) - c0.get(k, 0)
            for k in c1 if k.startswith("mesh_fallback_")
            and c1.get(k, 0) - c0.get(k, 0)},
    }
    print(json.dumps(result))


def _multichip_bench() -> dict:
    """Run the multichip child under the forced 8-device CPU topology
    and parse its record — real measured sharded rows/s, replacing the
    dry-run-only MULTICHIP record shape."""
    import subprocess

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--multichip-child"],
        capture_output=True, text=True, env=env,
        timeout=float(os.environ.get("SNAPPY_BENCH_MESH_TIMEOUT", "1800")))
    if proc.returncode != 0 or not proc.stdout.strip():
        raise RuntimeError(
            f"multichip child rc={proc.returncode}: "
            f"{(proc.stderr or '')[-400:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _join_bench(s, n_rows: int, repeats: int) -> dict:
    """Q3-class join+aggregate (tpch.Q3C: orders LEFT JOIN lineitem —
    a one-to-many expansion on a NON-unique build) on the device join
    engine vs the r05-era host pandas-merge path, value-asserted.

    The host baseline flips the `device_join` knob (a per-bind check,
    no cache flush needed) for ONE timed run; the device side reports
    best-of-repeats plus the join engine's own evidence counters."""
    from snappydata_tpu import config
    from snappydata_tpu.observability.metrics import global_registry
    from snappydata_tpu.utils import tpch

    props = config.global_properties()
    reg = global_registry()
    saved_cap = props.join_expand_max_bytes
    # expanded output ~ (lineitem + orders) rows x ~40B/row: at SF16 the
    # default 2GB cap would reroute to host — size it for the bench
    props.join_expand_max_bytes = 8 << 30
    try:
        props.set("device_join", False)
        t0 = time.time()
        host_rows = s.sql(tpch.Q3C).rows()
        host_s = time.time() - t0
        props.set("device_join", True)
        c0 = dict(reg.snapshot()["counters"])
        s.sql(tpch.Q3C)  # compile + first run (pays the ONE build argsort)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.time()
            dev_rows = s.sql(tpch.Q3C).rows()
            best = min(best, time.time() - t0)
        c1 = reg.snapshot()["counters"]

        def delta(key):
            return c1.get(key, 0) - c0.get(key, 0)

        # full value assertion against the host join (counts exact,
        # revenue within float tolerance — TPU plates are f32)
        assert len(dev_rows) == len(host_rows), (dev_rows, host_rows)
        max_rel = 0.0
        for h, d in zip(host_rows, dev_rows):
            assert h[0] == d[0] and h[1] == d[1], (h, d)
            rel = abs(h[2] - d[2]) / max(abs(h[2]), 1.0)
            max_rel = max(max_rel, rel)
            assert rel <= 5e-5, (h, d, rel)
        out_rows = delta("join_expand_out_rows")
        probe_rows = delta("join_expand_probe_rows")
        return {
            "q3_s": round(best, 4),
            "q3_host_s": round(host_s, 4),
            "q3_speedup": round(host_s / best, 2),
            "q3_rows_per_s": round(n_rows / best, 1),
            "q3_max_rel_err": max_rel,
            "q3_join": {
                "host_fallbacks": delta("join_host_fallbacks"),
                "device_joins": delta("join_device_joins"),
                "build_sorts": delta("join_build_sorts"),
                "build_cache_hits": delta("join_build_cache_hits"),
                "expand_factor":
                    round(out_rows / probe_rows, 2) if probe_rows
                    else None,
            },
        }
    finally:
        props.join_expand_max_bytes = saved_cap
        props.set("device_join", True)


def _matview_bench(s, repeats: int, k_deltas: int = 8,
                   delta_rows: int = 50_000) -> dict:
    """Materialized-view maintenance over the loaded lineitem table:
    CREATE view (one full aggregation), K delta appends (each folds
    O(delta) through the compiled partial program), then repeated view
    reads vs re-running the defining aggregate, value-asserted.  Runs
    AFTER the Q1/Q6/Q3 sections — the appends grow lineitem."""
    from snappydata_tpu.observability.metrics import global_registry
    from snappydata_tpu.utils import tpch

    reg = global_registry()
    agg_sql = ("SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sq, "
               "sum(l_extendedprice) AS sp, "
               "sum(l_extendedprice * (1 - l_discount)) AS sd, "
               "count(*) AS cnt FROM lineitem "
               "GROUP BY l_returnflag, l_linestatus")
    s.sql("CREATE MATERIALIZED VIEW bench_mv AS " + agg_sql)
    try:
        c0 = dict(reg.snapshot()["counters"])
        t0 = time.time()
        for i in range(k_deltas):
            li = tpch.gen_lineitem(delta_rows, seed=1000 + i)
            s.insert_arrays("lineitem", list(li.values()))
        fold_s = time.time() - t0
        c1 = dict(reg.snapshot()["counters"])

        def delta(key):
            return c1.get(key, 0) - c0.get(key, 0)

        s.sql("SELECT * FROM bench_mv")   # pays the one O(G) re-merge
        best_view = float("inf")
        for _ in range(max(repeats, 3)):
            t0 = time.time()
            view_rows = s.sql("SELECT * FROM bench_mv ORDER BY "
                              "l_returnflag, l_linestatus").rows()
            best_view = min(best_view, time.time() - t0)
        best_agg = float("inf")
        for _ in range(max(repeats, 3)):
            t0 = time.time()
            agg_rows = s.sql(agg_sql + " ORDER BY l_returnflag, "
                             "l_linestatus").rows()
            best_agg = min(best_agg, time.time() - t0)
        # value assertion: maintained state == fresh aggregation (sums
        # within fp tolerance — fold order differs from scan order)
        assert len(view_rows) == len(agg_rows), (view_rows, agg_rows)
        for v, a in zip(view_rows, agg_rows):
            assert v[0] == a[0] and v[1] == a[1], (v, a)
            assert v[5] == a[5], (v, a)   # counts exact
            for x, y in zip(v[2:5], a[2:5]):
                assert abs(x - y) <= 1e-9 * max(abs(y), 1.0), (v, a)
        return {
            "view_read_s": round(best_view, 4),
            "equiv_agg_s": round(best_agg, 4),
            "view_read_speedup": round(best_agg / best_view, 1),
            "delta_append_total_s": round(fold_s, 3),
            "delta_rows_per_append": delta_rows,
            "view_delta_folds": delta("view_delta_folds"),
            "view_rows_folded": delta("view_rows_folded"),
            "full_refreshes_during_folds": delta("view_full_refreshes"),
            "groups": len(view_rows),
        }
    finally:
        s.sql("DROP MATERIALIZED VIEW IF EXISTS bench_mv")


def _qps_bench(n_clients: int = 8, point_rows: int = 50_000,
               txn_rows: int = 64_000, naive_iters: int = 60,
               prepared_iters: int = 250) -> dict:
    """High-QPS serving axis: a mixed point-lookup/small-aggregate
    workload under N concurrent clients, naive per-query `session.sql`
    (parse+plan every statement) vs the prepared+micro-batched serving
    path — results value-asserted identical between the two.  Reports
    qps for both sides, prepared-path p50/p99 latency, fused-dispatch
    occupancy, and the zero-recompile evidence (plan compiles + vmapped
    variants built DURING the timed run, after warmup primed them)."""
    import threading

    from snappydata_tpu import SnappySession
    from snappydata_tpu import types as T
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.observability.metrics import global_registry

    from snappydata_tpu import config as _config

    reg = global_registry()
    props = _config.global_properties()
    saved_batch_rows = props.column_batch_rows
    # serving-sized column batches: the default 128Ki-row capacity means
    # a 64k-row table still scans 128Ki padded lanes per query — a
    # serving deployment sizes batches to its small tables (both sides
    # of the comparison read the same tables, so this is neutral)
    props.column_batch_rows = 16384
    try:
        s = SnappySession(catalog=Catalog())
        rng = np.random.default_rng(29)
        ids = np.arange(point_rows, dtype=np.int64)
        balances = rng.random(point_rows) * 1e4
        s.create_table("accounts", [("id", T.LONG), ("balance", T.DOUBLE)],
                       provider="row", key_columns=("id",))
        s.insert_arrays("accounts", [ids, balances])
        region = rng.integers(0, 64, txn_rows).astype(np.int64)
        amount = rng.random(txn_rows)
        s.create_table("txns", [("region_id", T.LONG),
                                ("amount", T.DOUBLE)],
                       provider="column")
        s.insert_arrays("txns", [region, amount])
    finally:
        props.column_batch_rows = saved_batch_rows

    point_sql = "SELECT balance FROM accounts WHERE id = ?"
    agg_sql = ("SELECT count(*), sum(amount) FROM txns "
               "WHERE region_id = ?")
    # per-region oracle for the value assertions
    agg_expect = {r: (int((region == r).sum()),
                      float(amount[region == r].sum()))
                  for r in range(64)}

    def workload(client: int, iters: int):
        """Deterministic 70/30 point/small-agg mix per client (the
        millions-of-users shape: mostly per-user point reads, a steady
        minority of dashboard-tile aggregates)."""
        r = np.random.default_rng(1000 + client)
        out = []
        for _ in range(iters):
            if r.random() < 0.7:
                out.append(("point", int(r.integers(0, point_rows))))
            else:
                out.append(("agg", int(r.integers(0, 64))))
        return out

    def check(kind, arg, rows):
        if kind == "point":
            assert len(rows) == 1 and \
                abs(rows[0][0] - balances[arg]) <= 1e-9, (arg, rows)
        else:
            cnt, sm = agg_expect[arg]
            assert rows[0][0] == cnt and \
                abs(rows[0][1] - sm) <= 1e-6 * max(sm, 1.0), (arg, rows)

    def run_clients(iters, fn):
        lats: list = []
        errors: list = []
        barrier = threading.Barrier(n_clients)

        def client(ci):
            mine = []
            try:
                work = workload(ci, iters)
                barrier.wait()
                for kind, arg in work:
                    t0 = time.time()
                    rows = fn(kind, arg)
                    mine.append(time.time() - t0)
                    check(kind, arg, rows)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            lats.extend(mine)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(n_clients)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        if errors:
            raise errors[0]
        return wall, lats

    # ---- naive side: parse+analyze+plan per statement ------------------
    def naive(kind, arg):
        sql = point_sql if kind == "point" else agg_sql
        return s.sql(sql, (arg,)).rows()

    naive(  # one warm call per shape so the naive side isn't paying
        "point", 0)  # first-compile either (same courtesy as prepared)
    naive("agg", 0)
    # best-of-passes on BOTH sides, same convention as Q1/Q6/Q3: this
    # container's contention noise swings absolute wall times ~3x, and
    # the least-contended pass is the honest measure of each path
    naive_n = n_clients * naive_iters
    naive_qps = 0.0
    for _ in range(2):
        naive_wall, _ = run_clients(naive_iters, naive)
        naive_qps = max(naive_qps, naive_n / naive_wall)

    # ---- prepared + micro-batched side ---------------------------------
    ph = s.prepare(point_sql)
    ah = s.prepare(agg_sql)

    def prepared(kind, arg):
        h = ph if kind == "point" else ah
        return h.execute((arg,)).rows()

    # warmup: prime every vmapped batch-size bucket an N-client load can
    # hit (inference-server warmup), plus one straight execute per shape
    ah.warm_batches((0,))
    prepared("point", 0)
    prepared("agg", 0)
    c0 = dict(reg.snapshot()["counters"])
    t0_compiles = reg.snapshot()["timers"].get("plan_compile",
                                               {}).get("count", 0)
    prep_n = n_clients * prepared_iters
    prep_qps, lats = 0.0, []
    for _ in range(2):
        prep_wall, pass_lats = run_clients(prepared_iters, prepared)
        if prep_n / prep_wall > prep_qps:
            prep_qps, lats = prep_n / prep_wall, pass_lats
    c1 = dict(reg.snapshot()["counters"])
    t1_compiles = reg.snapshot()["timers"].get("plan_compile",
                                               {}).get("count", 0)

    def delta(key):
        return c1.get(key, 0) - c0.get(key, 0)

    dispatches = delta("serving_batched_dispatches")
    fused = delta("serving_batch_requests")
    lats_ms = np.asarray(lats) * 1e3
    out = {
        "clients": n_clients,
        "naive_queries": naive_n,
        "naive_qps": round(naive_qps, 1),
        "prepared_queries": prep_n,
        "prepared_qps": round(prep_qps, 1),
        "qps_speedup": round(prep_qps / naive_qps, 2),
        "p50_ms": round(float(np.percentile(lats_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lats_ms, 99)), 3),
        "serving_prepared_hits": delta("serving_prepared_hits"),
        "serving_batched_dispatches": dispatches,
        "batch_occupancy": round(fused / dispatches, 2) if dispatches
        else None,
        "straight_through": delta("serving_straight_through"),
        "batch_fallbacks": delta("serving_batch_fallbacks"),
        # zero-recompile evidence: XLA plan compiles + vmapped variants
        # built during the TIMED run (warmup primed them) — must be 0
        "recompiles_after_warmup":
            (t1_compiles - t0_compiles) + delta("serving_vmap_compiles"),
        # re-tokenization guard: plan-repr walks during the timed run
        # (the prepared path computes its key once at prepare)
        "plan_key_builds": delta("plan_key_builds"),
    }
    s.stop()
    return out


def _htap_bench(n_rows: int = 200_000, scans: int = 12,
                batch_rows: int = 5000, ingest_batches: int = 24) -> dict:
    """HTAP axis (MVCC snapshot isolation): an analytic scan stream and
    sustained ingest hammer ONE column table, concurrently vs
    serialized.  Every concurrent scan runs under a pinned snapshot
    epoch and is value-asserted against the single-epoch invariant
    (ingest batches are (0, 1.0)×batch_rows, so a consistent snapshot
    must satisfy count == n_rows + m·batch_rows AND sum == base_sum +
    (count − n_rows) — a scan mixing two epochs breaks the linkage).

    The CONCURRENT phase runs first (scans race a bounded, paced ingest
    budget — unbounded tight-loop ingest degenerates into measuring XLA
    re-specialization as the batch axis doubles, not isolation); the
    SERIALIZED phase then times the same scans alone and the same
    ingest alone on the settled table.  --check guards
    value_mismatches == 0 and the p50 blow-up (p99 is reported but
    unguarded: it legitimately absorbs a batch-bucket re-specialization
    when ingest crosses a shape boundary)."""
    import threading

    from snappydata_tpu import SnappySession
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.storage import mvcc

    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE htap (k INT, v DOUBLE) USING column")
    ks = (np.arange(n_rows) % 16).astype(np.int32)
    vs = (np.arange(n_rows) % 100).astype(np.float64)
    s.catalog.describe("htap").data.insert_arrays([ks, vs])
    base_sum = float(vs.sum())
    scan_sql = "SELECT count(*), sum(v) FROM htap"
    s.sql(scan_sql)   # warm the compiled plan
    bk = np.zeros(batch_rows, dtype=np.int32)
    bv = np.ones(batch_rows, dtype=np.float64)
    mismatches = [0]

    def one_scan(sess):
        t0 = time.perf_counter()
        cnt, sm = sess.sql(scan_sql).rows()[0]
        dt = time.perf_counter() - t0
        cnt, sm = int(cnt), float(sm)
        extra = cnt - n_rows
        if extra % batch_rows or abs(sm - (base_sum + extra)) > 1e-6 * max(
                1.0, abs(sm)):
            mismatches[0] += 1
        return dt

    def ingest_run(stop=None, pace_s=0.01):
        """Paced ingest of the fixed budget; returns (rows, seconds of
        actual ingest work — pacing sleeps excluded, so rows/s measures
        the write path, not the pacing)."""
        w = SnappySession(catalog=s.catalog)
        work = 0.0
        done = 0
        for _ in range(ingest_batches):
            if stop is not None and stop.is_set():
                break
            t0 = time.perf_counter()
            w.insert_arrays("htap", [bk, bv])
            work += time.perf_counter() - t0
            done += batch_rows
            if pace_s:
                time.sleep(pace_s)
        return done, work

    def pcts(times):
        times = sorted(times)
        return (round(times[len(times) // 2] * 1e3, 3),
                round(times[min(len(times) - 1,
                               int(len(times) * 0.99))] * 1e3, 3))

    # ---- concurrent: scans race the paced ingest budget ---------------
    stop = threading.Event()
    ing_out = {}

    def ingest_thread():
        rows, work = ingest_run(stop=stop)
        ing_out["rows"], ing_out["work_s"] = rows, work

    th = threading.Thread(target=ingest_thread, daemon=True)
    th.start()
    conc_times = [one_scan(s) for _ in range(scans)]
    # signal BEFORE joining: a slow machine's paced ingest must stop at
    # the scans' end, not keep running into the serialized baseline
    # (which would inflate it and soften the p50 guard)
    stop.set()
    th.join(timeout=120)
    p50c, p99c = pcts(conc_times)
    concurrent = {
        "scan_p50_ms": p50c, "scan_p99_ms": p99c,
        "ingest_rows_per_s": round(
            ing_out.get("rows", 0) / max(ing_out.get("work_s", 0), 1e-9),
            1),
        "ingested_rows": ing_out.get("rows", 0),
    }
    # ---- serialized baseline: same scans alone, same ingest alone -----
    ser_times = [one_scan(s) for _ in range(scans)]
    rows, work = ingest_run()
    p50s, p99s = pcts(ser_times)
    serialized = {
        "scan_p50_ms": p50s, "scan_p99_ms": p99s,
        "ingest_rows_per_s": round(rows / max(work, 1e-9), 1),
        "ingested_rows": rows,
    }
    data = s.catalog.describe("htap").data
    mvcc.trim_unpinned([("htap", data)])
    retained_after = mvcc.retained_bytes_of(data)
    out = {
        "rows": n_rows,
        "scans": scans,
        "batch_rows": batch_rows,
        "serialized": serialized,
        "concurrent": concurrent,
        "value_mismatches": mismatches[0],
        # bounded-retention evidence: after readers drain (and the trim
        # the degradation ladder would run), old epochs hold no bytes
        "retained_epoch_bytes_after": int(retained_after),
    }
    s.stop()
    return out


def _outofcore_bench(n_rows: int = 3_200_000, repeats: int = 5) -> dict:
    """Out-of-core axis (tiered storage + double-buffered prefetch): the
    SAME filter+aggregate scan measured fully in-HBM vs with the device
    budget capped BELOW 10% of the table, so every pass streams tiles
    host→HBM through storage/prefetch.py while the tier ladder
    (storage/tier.py) demotes what falls cold.  On this CPU rig the cap
    is an emulation (`tier_device_bytes` + a tile-sized scan window) —
    the transfer/compute overlap it exercises is the real mechanism.
    --check guards: zero value mismatches (out-of-core must be invisible
    to answers), prefetch_overlap_ms > 0 (the double buffer actually
    overlapped upload with compute), and out-of-core rows/s >=
    SNAPPY_BENCH_OUTOFCORE_RATIO (default 0.5) of in-HBM — the
    streaming bound min(compute, transfer) can't silently decay into
    bind-per-tile serialization."""
    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.observability.metrics import global_registry
    from snappydata_tpu.storage.hoststore import batch_resident_bytes

    props = config.global_properties()
    saved = (props.column_batch_rows, props.column_max_delta_rows,
             props.scan_tile_bytes, props.tier_device_bytes,
             props.tier_host_bytes, props.tier_prefetch_depth)
    mismatches = 0
    try:
        props.column_batch_rows = 65536
        props.column_max_delta_rows = 65536
        s = SnappySession(catalog=Catalog())
        s.sql("CREATE TABLE oc (k INT, v DOUBLE) USING column")
        ks = (np.arange(n_rows) % 16).astype(np.int32)
        vs = ((np.arange(n_rows) * 7919) % 10_000).astype(np.float64)
        s.catalog.describe("oc").data.insert_arrays([ks, vs])
        data = s.catalog.describe("oc").data
        table_bytes = sum(batch_resident_bytes(v.batch)
                          for v in data._manifest.views)
        q = ("SELECT count(*), sum(v), min(v), max(v) FROM oc "
             "WHERE v < 9000")

        def best_of(runs):
            times = []
            for _ in range(runs):
                t0 = time.perf_counter()
                rows = s.sql(q).rows()
                times.append(time.perf_counter() - t0)
            return min(times), rows[0]

        # ---- in-HBM baseline: whole table bound, plates stay cached
        s.sql(q)  # warm compile + bind
        t_in, ref = best_of(repeats)

        # ---- constricted: device budget < 10% of the table ------------
        budget = max(1, table_bytes // 10)
        # tile = 4 of ~50 batches (8% of the table) — each pass streams
        # the table through a window under the cap, double-buffered two
        # windows deep so the upload hides behind the tile aggregate
        props.scan_tile_bytes = 4 * 65536 * (4 + 8)
        props.tier_device_bytes = budget
        props.tier_prefetch_depth = 2
        reg = global_registry()
        c0 = dict(reg.snapshot()["counters"])
        # the warm pass stays inside the counter window: it is where
        # the over-cap in-HBM plates get demoted off the device tier
        s.sql(q)
        t_oc, got = best_of(repeats)
        c1 = dict(reg.snapshot()["counters"])

        def delta(key):
            return c1.get(key, 0) - c0.get(key, 0)

        if int(got[0]) != int(ref[0]):
            mismatches += 1
        for gi, ri in zip(got[1:], ref[1:]):
            if abs(float(gi) - float(ri)) > 1e-9 * max(1.0,
                                                       abs(float(ri))):
                mismatches += 1
        in_rps = n_rows / t_in
        oc_rps = n_rows / t_oc
        return {
            "rows": n_rows,
            "table_bytes": int(table_bytes),
            "device_budget_bytes": int(budget),
            "budget_fraction": round(budget / table_bytes, 4),
            "inhbm_rows_per_s": round(in_rps, 1),
            "outofcore_rows_per_s": round(oc_rps, 1),
            "throughput_ratio": round(oc_rps / in_rps, 4),
            "scan_tiles": delta("scan_tiles"),
            "prefetch_windows_warmed": delta("prefetch_windows_warmed"),
            "prefetch_overlap_ms": delta("prefetch_overlap_ms"),
            "prefetch_window_waits": delta("prefetch_window_waits"),
            "tier_demotions_hbm": delta("tier_demotions_hbm"),
            "value_mismatches": mismatches,
        }
    finally:
        (props.column_batch_rows, props.column_max_delta_rows,
         props.scan_tile_bytes, props.tier_device_bytes,
         props.tier_host_bytes, props.tier_prefetch_depth) = saved


def _faultstorm_bench() -> dict:
    """Fault-storm axis (reliability/faultstorm.py): a seeded schedule
    injects one fault per round — WAL append/fsync, checkpoint
    write/publish, tier write corruption/short-write, memmap EIO,
    prefetch-worker death, admission failure — into the constricted
    HTAP workload and reconciles the ledger: every fired fault must end
    as `recovered` (self-healed in place: quarantine+rebuild, worker
    restart, bounded re-read) or `typed_errors` (a typed retryable
    failure followed by verified crash-recovery).  --check guards
    value_mismatches == 0, no untyped failures, and recovery_ratio >=
    SNAPPY_BENCH_FAULT_RECOVERY (default 1.0 — fully accounted)."""
    import shutil
    import tempfile

    from snappydata_tpu.reliability import faultstorm

    seed = int(os.environ.get("SNAPPY_FAILPOINT_SEED", "1717"))
    rounds = int(os.environ.get("SNAPPY_BENCH_FAULT_ROUNDS", "30"))
    tmp = tempfile.mkdtemp(prefix="snappy_faultstorm_")
    try:
        t0 = time.perf_counter()
        res = faultstorm.run_storm(tmp, seed=seed, rounds=rounds)
        res["storm_s"] = round(time.perf_counter() - t0, 2)
        # the clean baseline: the SAME seeded op schedule, no fault
        # armed — what the storm's scan p50/p99 and qps compare against
        clean_dir = tempfile.mkdtemp(prefix="snappy_faultstorm_clean_")
        try:
            clean = faultstorm.run_storm(clean_dir, seed=seed,
                                         rounds=rounds, inject=False)
            res["clean"] = {k: clean[k] for k in
                            ("scans", "scan_p50_ms", "scan_p99_ms",
                             "scans_per_s", "value_mismatches")}
        finally:
            shutil.rmtree(clean_dir, ignore_errors=True)
        return res
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _resilience_bench(n_rows: int = 20_000, phase_s: float = 1.5) -> dict:
    """Availability trajectory: point-read QPS and p99 through a
    scripted kill → rejoin window on a 2-server cluster with
    redundancy 1 — three measured phases:

      steady     both members up;
      degraded   one member hard-killed mid-phase (the first query pays
                 the failover probe + replica promotion; replicas keep
                 every answer complete);
      recovered  the member restarted from its recovered data dir and
                 re-admitted via rejoin_server (watermark delta resync)
                 — redundancy restored, no manual restore_redundancy().

    Every query in every phase is VALUE-asserted (v == k/2), so the
    availability numbers can't hide wrong answers; `correct` reports
    that every returned row checked out."""
    import shutil
    import tempfile

    from snappydata_tpu import SnappySession
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.cluster import LocatorNode, ServerNode
    from snappydata_tpu.cluster.distributed import DistributedSession

    tmp = tempfile.mkdtemp(prefix="snappy_resilience_")
    locator = LocatorNode().start()
    sessions = [SnappySession(catalog=Catalog(),
                              data_dir=os.path.join(tmp, f"srv{i}"),
                              recover=False) for i in range(2)]
    servers = [ServerNode(locator.address, s).start() for s in sessions]
    ds = DistributedSession(
        server_addresses=[s.flight_address for s in servers],
        locator=locator.address)
    rng = np.random.default_rng(47)
    try:
        ds.sql("CREATE TABLE res_kv (k BIGINT, v DOUBLE) USING column "
               "OPTIONS (partition_by 'k', redundancy '1')")
        ks = np.arange(n_rows, dtype=np.int64)
        ds.insert_arrays("res_kv", [ks, ks * 0.5])

        def run_phase(seconds: float) -> dict:
            lats = []
            end = time.time() + seconds
            while time.time() < end:
                k = int(rng.integers(0, n_rows))
                t0 = time.time()
                rows = ds.sql(
                    f"SELECT v FROM res_kv WHERE k = {k}").rows()
                lats.append(time.time() - t0)
                assert len(rows) == 1 and \
                    abs(rows[0][0] - k * 0.5) <= 1e-9, (k, rows)
            lats_ms = np.asarray(lats) * 1e3
            return {"queries": len(lats),
                    "qps": round(len(lats) / seconds, 1),
                    "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                    "p99_ms": round(float(np.percentile(lats_ms, 99)), 2)}

        ds.sql("SELECT count(*) FROM res_kv")   # warm compiles
        steady = run_phase(phase_s)

        # hard kill one member; the NEXT query pays the failover
        servers[1].stop()
        sessions[1].disk_store.close()
        degraded = run_phase(phase_s)

        # restart from the recovered data dir + automatic resync
        sessions[1] = SnappySession(data_dir=os.path.join(tmp, "srv1"),
                                    recover=True)
        servers[1] = ServerNode(locator.address, sessions[1]).start()
        rejoin = ds.rejoin_server(1, servers[1].flight_address)
        recovered = run_phase(phase_s)

        return {
            "rows": n_rows,
            "steady": steady,
            "degraded": degraded,
            "recovered": recovered,
            "rejoin_clean_buckets": rejoin["clean_primary_buckets"]
            + rejoin["clean_replica_buckets"],
            "rejoin_copied_buckets": rejoin["copied_buckets"],
            "degraded_buckets_after_rejoin": rejoin["degraded_buckets"],
            "correct": True,   # every phase value-asserted above
        }
    finally:
        ds.close()
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass
        locator.stop()
        for s in sessions:
            try:
                s.disk_store.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _compressed_bench(s) -> dict:
    """Compressed-domain evidence over the loaded SF lineitem table:

    * a dictionary-skip probe (equality literal that misses every
      sorted VALUE_DICT dictionary) must skip whole batches at bind —
      `batches_skipped_dict > 0` on the stock workload;
    * Q1 + Q6 run once with the knob on and once with it OFF (decoded
      plates) and every value is asserted identical;
    * resident HBM bytes/row are measured for BOTH binds — the capacity
      lever the --check guard protects."""
    from snappydata_tpu import config
    from snappydata_tpu.observability.metrics import global_registry
    from snappydata_tpu.observability.stats_service import encoding_mix
    from snappydata_tpu.utils import tpch

    props = config.global_properties()
    reg = global_registry()
    data = s.catalog.lookup_table("lineitem").data

    # dictionary-skip probe: l_discount holds multiples of 0.01, so
    # 0.055 misses every batch dictionary — zero device work
    c0 = dict(reg.snapshot()["counters"])
    miss = s.sql(
        "SELECT count(*) FROM lineitem WHERE l_discount = 0.055"
    ).rows()[0][0]
    assert miss == 0, miss
    c1 = dict(reg.snapshot()["counters"])
    skipped = c1.get("batches_skipped_dict", 0) \
        - c0.get("batches_skipped_dict", 0)

    # SYMMETRIC residency measurement: both sides bind Q1/Q6's columns
    # into a FRESH device cache (earlier bench sections leave decoded
    # join plates and artifacts around that would inflate the 'on' side
    # and skew the guarded resident_bytes_per_row — review finding)
    data._device_cache.clear()
    q1_on = s.sql(tpch.Q1).rows()
    q6_on = s.sql(tpch.Q6).rows()
    mix_on = encoding_mix(s.catalog).get("lineitem", {})
    # counter snapshot AFTER this section's own queries, so the record
    # reflects this workload even if the section runs standalone
    counters = dict(reg.snapshot()["counters"])
    saved = props.get("scan_compressed_domain")
    try:
        props.set("scan_compressed_domain", "off")
        data._device_cache.clear()
        q1_off = s.sql(tpch.Q1).rows()
        q6_off = s.sql(tpch.Q6).rows()
        mix_off = encoding_mix(s.catalog).get("lineitem", {})
    finally:
        props.set("scan_compressed_domain", saved)
        data._device_cache.clear()
        s.sql(tpch.Q6)   # re-prime the compressed binds for later sections

    # full value assertion compressed vs decoded (identical inputs and
    # reduction order — tolerance only covers fp noise)
    assert len(q1_on) == len(q1_off), (q1_on, q1_off)
    for a, b in zip(q1_on, q1_off):
        assert a[0] == b[0] and a[1] == b[1] and a[9] == b[9], (a, b)
        for x, y in zip(a[2:9], b[2:9]):
            assert abs(x - y) <= 1e-9 * max(abs(y), 1.0), (a, b)
    assert abs(q6_on[0][0] - q6_off[0][0]) \
        <= 1e-9 * max(abs(q6_off[0][0]), 1.0), (q6_on, q6_off)

    rb_on = mix_on.get("resident_bytes_per_row")
    rb_off = mix_off.get("resident_bytes_per_row")
    return {
        "code_domain_predicates": counters.get("code_domain_predicates", 0),
        "rle_run_predicates": counters.get("rle_run_predicates", 0),
        "batches_skipped_dict": skipped,
        "fallback_reasons": {
            k[len("compressed_fallback_"):]: v
            for k, v in sorted(counters.items())
            if k.startswith("compressed_fallback_")},
        "encoding_mix": mix_on.get("encoding_mix"),
        "at_rest_ratio": mix_on.get("at_rest_ratio"),
        "resident_bytes_per_row": rb_on,
        "resident_bytes_per_row_decoded": rb_off,
        "resident_reduction":
            round(rb_off / rb_on, 2) if rb_on and rb_off else None,
        "values_asserted": True,
    }


def _code_agg_bench(s, repeats: int) -> dict:
    """Aggregate-on-codes lane (the dictionary-space tentpole): the SAME
    grouped aggregate runs once with `agg_on_codes` forced ON
    (code-domain group-by + dictionary-space sums) and once OFF (decoded
    gathers), every value asserted identical, rows/s recorded both ways;
    a dedicated sorted low-cardinality probe (TPC-H distributions leave
    lineitem with no RUN_LENGTH column) exercises the run-space lane the
    same way.

    The decode-throughput law prices the lane: the decoded path must
    move decoded-bytes/encoded-bytes more data over the same aggregate,
    so on a bandwidth-bound accelerator `predicted_on = off_rate x
    byte_ratio`; on compute-bound CPU the gather itself dominates and
    the law degenerates to `predicted_on = off_rate`.  `--check` guards
    measured >= SNAPPY_BENCH_CODE_AGG_RATIO (default 0.8x) of predicted,
    and that all three lane counters actually fired."""
    import jax

    from snappydata_tpu import config
    from snappydata_tpu.observability.metrics import global_registry

    props = config.global_properties()
    reg = global_registry()
    data = s.catalog.lookup_table("lineitem").data
    rows = data.snapshot().total_rows()

    # string dict keys -> code-domain group-by; VALUE_DICT measures ->
    # dictionary-space sums
    q_group = ("SELECT l_returnflag, l_linestatus, count(*), "
               "sum(l_quantity), sum(l_discount) FROM lineitem "
               "GROUP BY l_returnflag, l_linestatus "
               "ORDER BY l_returnflag, l_linestatus")
    # run-space probe: single RLE column, run-aligned filter
    nprobe = int(min(max(rows, 1 << 16), 1 << 22))
    rng = np.random.default_rng(7)
    s.sql("CREATE TABLE code_agg_rle (r DOUBLE) USING column")
    rvals = np.sort(rng.choice(
        np.array([1.0, 2.0, 5.0, 9.0, 12.0]), nprobe))
    s.insert_arrays("code_agg_rle", [rvals])
    s.catalog.describe("code_agg_rle").data.force_rollover()
    q_rle = "SELECT sum(r), count(r) FROM code_agg_rle WHERE r < 9.0"

    def best_of(q):
        s.sql(q)                      # compile + first run
        best = float("inf")
        for _ in range(repeats):
            t0 = time.time()
            out = s.sql(q).rows()
            best = min(best, time.time() - t0)
        return best, out

    saved = props.get("agg_on_codes")
    try:
        props.set("agg_on_codes", "on")
        c0 = dict(reg.snapshot()["counters"])
        tg_on, g_on = best_of(q_group)
        tr_on, r_on = best_of(q_rle)
        c1 = dict(reg.snapshot()["counters"])
        props.set("agg_on_codes", "off")
        tg_off, g_off = best_of(q_group)
        tr_off, r_off = best_of(q_rle)
        # the PRODUCTION leg the throughput guard prices: auto resolves
        # per backend (dictionary-space scatter is serial on CPU, so
        # auto keeps it for accelerators; forced-on above still proves
        # lane counters + value equality everywhere)
        props.set("agg_on_codes", "auto")
        tg_auto, g_auto = best_of(q_group)
    finally:
        props.set("agg_on_codes", saved)

    # identical values both ways (same inputs, fp-noise tolerance only)
    assert len(g_on) == len(g_off), (g_on, g_off)
    for a, b in zip(g_on, g_off):
        assert a[0] == b[0] and a[1] == b[1] and a[2] == b[2], (a, b)
        for x, y in zip(a[3:], b[3:]):
            assert abs(x - y) <= 1e-9 * max(abs(y), 1.0), (a, b)
    assert r_on[0][1] == r_off[0][1], (r_on, r_off)
    assert abs(r_on[0][0] - r_off[0][0]) \
        <= 1e-9 * max(abs(r_off[0][0]), 1.0), (r_on, r_off)
    assert [r[:3] for r in g_auto] == [r[:3] for r in g_off], \
        (g_auto, g_off)

    # decode-throughput law over the grouped query's columns: encoded
    # at-rest bytes vs the 8 B/row the decoded gather path must stream
    enc_b = dec_b = 0
    for v in data.snapshot().views:
        for ci in (4, 6, 8, 9):   # quantity, discount, returnflag, status
            enc_b += v.batch.columns[ci].nbytes
            dec_b += v.batch.num_rows * 8
    byte_ratio = round(dec_b / enc_b, 2) if enc_b else 1.0
    off_rate = rows / tg_off
    predicted = off_rate * (byte_ratio
                            if jax.default_backend() == "tpu" else 1.0)

    lanes = {k: c1.get(k, 0) - c0.get(k, 0)
             for k in ("agg_code_domain", "agg_dict_space",
                       "agg_rle_runs")}
    return {
        "grouped_rows_per_s_on": round(rows / tg_on, 1),
        "grouped_rows_per_s_off": round(off_rate, 1),
        "grouped_rows_per_s_auto": round(rows / tg_auto, 1),
        "rle_rows_per_s_on": round(nprobe / tr_on, 1),
        "rle_rows_per_s_off": round(nprobe / tr_off, 1),
        "byte_ratio": byte_ratio,
        "predicted_rows_per_s": round(predicted, 1),
        "lane_counters": lanes,
        "values_asserted": True,
    }


def _decode_counters():
    try:
        from snappydata_tpu.storage import device_decode

        return device_decode.counters()
    except Exception:  # pragma: no cover - instrumentation only
        return None


def _device_only_best(s, q: str, repeats: int) -> float:
    """Best wall time of the COMPILED query program on device-resident
    arrays (block_until_ready) — no session, no bind, no host decode."""
    import functools

    import jax
    import jax.numpy as jnp

    from snappydata_tpu.engine.executor import Compiler, _param_scalar
    from snappydata_tpu.sql.analyzer import tokenize_plan
    from snappydata_tpu.sql.optimizer import optimize
    from snappydata_tpu.sql.parser import parse

    plan = optimize(parse(q).plan, s.catalog)
    resolved, _ = s.analyzer.analyze_plan(plan)
    node = resolved
    while not hasattr(node, "agg_exprs"):
        node = node.children()[0]
    tokenized, params = tokenize_plan(node)
    compiled = Compiler(s.catalog, s.conf).compile(tokenized)
    tables = [r.bind() for r in compiled.relations]
    arrays = []
    for r, dt in zip(compiled.relations, tables):
        for ci in r.used:
            arrays.append((dt.columns[ci], dt.nulls.get(ci)))
        arrays.append(dt.valid)
    aux = tuple(jnp.asarray(b(params)) for b in compiled.aux_builders)
    static = tuple(p() for p in compiled.static_providers)
    pvals = tuple(_param_scalar(v) for v in params)
    fn = jax.jit(functools.partial(compiled.traced, static))
    jax.block_until_ready(fn(tuple(arrays), aux, pvals))  # compile
    best = float("inf")
    for _ in range(max(repeats, 3)):
        t0 = time.time()
        jax.block_until_ready(fn(tuple(arrays), aux, pvals))
        best = min(best, time.time() - t0)
    return best


def _assert_q1_values(s, sf: float) -> float:
    """Engine Q1 vs an exact numpy float64 oracle over the same
    (f32-rounded when on TPU) inputs; returns max relative error and
    raises if it exceeds 2e-6."""
    import datetime

    from snappydata_tpu import config
    from snappydata_tpu.utils import tpch

    n_l = max(1000, int(tpch.LINEITEM_ROWS_PER_SF * sf))
    col = tpch.gen_lineitem(n_l, 17)
    f32 = not config.use_float64()

    def dev(a):
        a = np.asarray(a, dtype=np.float64)
        return a.astype(np.float32).astype(np.float64) if f32 else a

    qty, price = dev(col["l_quantity"]), dev(col["l_extendedprice"])
    disc, tax = dev(col["l_discount"]), dev(col["l_tax"])
    rf, ls = col["l_returnflag"], col["l_linestatus"]
    lim = (datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
           - datetime.date(1970, 1, 1)).days
    keep = col["l_shipdate"] <= lim
    if f32:
        dp = (price.astype(np.float32)
              * (1 - disc).astype(np.float32)).astype(np.float64)
        ch = (dp.astype(np.float32)
              * (1 + tax).astype(np.float32)).astype(np.float64)
    else:
        dp = price * (1 - disc)
        ch = dp * (1 + tax)
    got = {(r[0], r[1]): r for r in s.sql(tpch.Q1).rows()}
    max_rel = 0.0
    for key in {(a, b) for a, b in zip(rf[keep], ls[keep])}:
        m = keep & (rf == key[0]) & (ls == key[1])
        row = got[key]
        oracle = [qty[m].sum(), price[m].sum(), dp[m].sum(), ch[m].sum()]
        for got_v, exact_v in zip(row[2:6], oracle):
            rel = abs(got_v - exact_v) / max(abs(exact_v), 1.0)
            max_rel = max(max_rel, rel)
            assert rel <= 2e-6, (key, got_v, exact_v, rel)
        assert row[9] == int(m.sum()), key
    return max_rel


def _ingest_bench(n: int = 2_000_000) -> float:
    """Bulk columnar ingest rows/s through the native (_fastingest)
    path: ints + floats + a dictionary-encoded string column."""
    from snappydata_tpu import SnappySession
    from snappydata_tpu.catalog import Catalog

    s = SnappySession(catalog=Catalog())
    s.sql("CREATE TABLE ingest_t (k BIGINT, name STRING, v DOUBLE) "
          "USING column")
    rng = np.random.default_rng(23)
    k = np.arange(n, dtype=np.int64)
    name = np.array([f"n{i & 1023}" for i in range(n)], dtype=object)
    v = rng.random(n)
    t0 = time.time()
    s.insert_arrays("ingest_t", [k, name, v])
    dt = time.time() - t0
    s.stop()
    return round(n / dt, 1)


def _durable_ingest_bench(n_stmts: int = 64,
                          rows_per_stmt: int = 20_000) -> dict:
    """Durable ingest rows/s + WAL fsync count per wal_fsync_mode —
    `group` (default) vs `always` (the pre-group-commit behavior). The
    per-statement stream is the shape where grouping matters: `group`
    coalesces concurrent commits and pipelines encode against the
    fsync, `always` pays one fsync per record."""
    import shutil
    import tempfile
    import threading

    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.observability.metrics import global_registry

    out = {}
    props = config.global_properties()
    saved = props.get("wal_fsync_mode")
    # warmup outside the timed region: the first durable session pays
    # one-time import/encode costs that would bias whichever mode ran
    # first
    wd = tempfile.mkdtemp(prefix="snappy_bench_wal_warm_")
    w = SnappySession(catalog=Catalog(), data_dir=wd, recover=False)
    w.sql("CREATE TABLE w (k BIGINT, v DOUBLE) USING column")
    for i in range(8):
        w.insert_arrays("w", [np.arange(1000, dtype=np.int64),
                              np.ones(1000)])
    w.stop()
    w.disk_store.close()
    shutil.rmtree(wd, ignore_errors=True)
    try:
        for mode in ("group", "always"):
            props.set("wal_fsync_mode", mode)
            d = tempfile.mkdtemp(prefix=f"snappy_bench_wal_{mode}_")
            s = SnappySession(catalog=Catalog(), data_dir=d,
                              recover=False)
            s.sql("CREATE TABLE w (k BIGINT, v DOUBLE) USING column")
            fsync0 = global_registry().counter("wal_fsync_count")
            chunks = [np.arange(i * rows_per_stmt, (i + 1) * rows_per_stmt,
                                dtype=np.int64) for i in range(n_stmts)]
            t0 = time.time()
            # 4 concurrent committers: the group-commit coalescing shape
            workers = []
            for w in range(4):
                def run(lo=w):
                    for i in range(lo, n_stmts, 4):
                        s.insert_arrays("w", [chunks[i],
                                              chunks[i] * 0.5])
                workers.append(threading.Thread(target=run))
            for t in workers:
                t.start()
            for t in workers:
                t.join()
            dt = time.time() - t0
            fsyncs = global_registry().counter("wal_fsync_count") - fsync0
            out[mode] = {
                "rows_per_s": round(n_stmts * rows_per_stmt / dt, 1),
                "fsyncs": fsyncs,
                "statements": n_stmts,
            }
            s.stop()
            s.disk_store.close()
            shutil.rmtree(d, ignore_errors=True)
    finally:
        props.set("wal_fsync_mode", saved)
    return out


def _sink_bench(n: int = 200_000) -> float:
    """Kafka→table events/s through the exactly-once sink (BASELINE.md
    north-star: 1M events/s)."""
    from snappydata_tpu import SnappySession
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.streaming.kafka import InProcessBroker, KafkaSource
    from snappydata_tpu.streaming.query import StreamingQuery

    from snappydata_tpu import types as T

    s = SnappySession(catalog=Catalog())
    schema = T.Schema([T.Field("id", T.LONG, False),
                       T.Field("v", T.DOUBLE, True)])
    s.catalog.create_table("sink_t", schema, "column", {},
                           key_columns=("id",))
    broker = InProcessBroker(num_partitions=8)
    broker.produce("ev", [{"id": i, "v": 1.0} for i in range(n)])
    src = KafkaSource(s, "bench_q", broker, "ev", ["id", "v"],
                      max_records_per_batch=100_000)
    q = StreamingQuery(s, "bench_q", src, "sink_t")
    t0 = time.time()
    q.process_available()
    dt = time.time() - t0
    got = s.sql("SELECT count(*) FROM sink_t").rows()[0][0]
    assert got == n, (got, n)
    s.stop()
    return round(n / dt, 1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--check":
        sys.exit(run_check(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "--multichip-child":
        _multichip_child()
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "--multichip":
        # standalone multichip run: prints the record and (with an
        # output path) writes it there
        rec = _multichip_bench()
        rec_out = {"n_devices": rec.get("n_devices", 8), "rc": 0,
                   "ok": rec.get("value_mismatches", 1) == 0,
                   "skipped": False, "measured": rec}
        print(json.dumps(rec_out, indent=1))
        if len(sys.argv) > 2:
            with open(sys.argv[2], "w") as fh:
                json.dump(rec_out, fh, indent=1)
        sys.exit(0 if rec_out["ok"] else 1)
    main()

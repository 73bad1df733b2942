#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process that owns the chip. It makes the cell's tables from `--seed`,
loads them through the normal ingest path (`insert_arrays`, default
`config.Properties`, journaled where the mix is durable), warms the
statement shapes the mix's cycle meets, drives the mix's loop for at
least `--seconds` (the cycle in flight at the deadline is finished: a
rate is over whole cycles and all their time), reads the device's memory
peak, frees the program's state, replays the log through the plain
reference and compares every answer of the window. Every line it prints
is one JSON object; the last is the result.

The harness knows no table, query, kind of statement or metric by name:
each is a file that `BENCHMARK.json`, the configuration or the mix names
(`manifest.py`).

Without a TPU it exits 2 and prints no result. `--cpu-rehearsal` with
`JAX_PLATFORMS=cpu` in the environment runs the same path at the
configuration's `rehearsal_sf`, labelled `platform: cpu`: values and
control flow, never a device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import resource      # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import devtrace      # noqa: E402
import spans         # noqa: E402
from manifest import Manifest   # noqa: E402
from reference import World, compare   # noqa: E402
from roofline import peaks_for  # noqa: E402
from traffic import Traffic     # noqa: E402


def say(line: str, **fields) -> None:
    print(json.dumps({"line": line, **fields}, default=str), flush=True)


class Run:
    """Sends statements through the entry and keeps the log the
    reference replays."""

    def __init__(self, man: Manifest, engine, front: str, rows: dict,
                 trace: bool):
        self.man, self.engine, self.front = man, engine, front
        self.rows = dict(rows)
        self.trace = trace
        self.annotate = None        # marks a statement in the profile
        self.window = False
        self.log = []
        if trace:
            from snappydata_tpu.observability import tracing

            self._ring = tracing.ring()

    def _traces(self, k: int) -> list:
        """The span trees of the statement that has just returned: of the
        `k` traces recorded since it was issued, those minted at this
        cell's front door, and whatever else carries their ids (the
        server's side of a served statement)."""
        out, ids = [], set()
        for summary in reversed(self._ring.traces(limit=max(k, 1))[:k]):
            tid = summary["trace_id"]
            if summary["kind"] == self.front and tid not in ids:
                ids.add(tid)
                out.extend(self._ring.get(tid))
        return out

    def issue(self, st) -> dict:
        """One statement, timed by the host's clock from send to rows (or
        acknowledgement) on the host."""
        rec = {"name": st.name, "kind": st.kind, "window": self.window,
               "ok": True, "traced": self.annotate is not None, "st": st}
        if self.trace:
            seen = self._ring.recorded
        with self.annotate(devtrace.PREFIX + st.name) if self.annotate \
                else contextlib.nullcontext():
            t0 = time.perf_counter()
            try:
                self.man.module("kinds", st.kind).send(
                    self.engine, st, rec, self.rows)
            except Exception as e:   # a failed statement is counted
                rec["ok"] = False
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["ms"] = (time.perf_counter() - t0) * 1e3
        if self.trace:
            rec["traces"] = self._traces(self._ring.recorded - seen)
        self.log.append(rec)
        return rec


def _phases(rec: dict, back: str) -> list:
    """A traced query's top-level spans laid out from the statement's
    start, in milliseconds: the server's side of a served statement is
    taken to sit in the middle of the client's latency. Spans carry no
    start time, so children are taken to follow one another."""
    trees = [t for t in rec.get("traces", ()) if t["kind"] == back]
    if "answer" not in rec or len(trees) != 1:
        return []
    root = trees[0]["root"]
    at = max(0.0, (rec["ms"] - float(root["ms"])) / 2.0)
    out = []
    for child in root.get("children", ()):
        out.append([child["name"], at, float(child["ms"])])
        at += float(child["ms"])
    return out


def _device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _counters() -> dict:
    from snappydata_tpu.observability.metrics import global_registry

    return global_registry().counters_snapshot()


def replay(man: Manifest, log: list, world: World, keep: dict,
           limits: dict) -> dict:
    """The log through the reference, in order: acknowledged writes
    applied, every query of the window compared. Returns the numbers
    compared."""
    gap, wrong, unanswered, compared = 0.0, 0, 0, 0
    for rec in log:
        st, answered = rec["st"], rec["ok"]
        kind = man.module("kinds", st.kind)
        if rec["window"] and not answered:
            unanswered += 1
        if hasattr(kind, "apply"):
            if answered:
                kind.apply(world, st, keep)
            continue
        exp = kind.expected(world, st, rec)
        if not (rec["window"] and answered):
            continue
        g, w = compare(rec["answer"], exp)
        if g > limits["sum_rel_gap"] or w:
            rec.update(ok=False, gap=g, expected=exp)
        gap, wrong, compared = max(gap, g), wrong + w, compared + 1
    return {"sum_rel_gap": gap, "exact_mismatches": wrong,
            "unanswered": unanswered, "compared": compared}


def read_trace(trace_dir: str, traced: list, back: str) -> tuple:
    """The profiler's trace reduced (`devtrace`), and its raw lists."""
    events = devtrace.extract(devtrace.find_xplane(trace_dir))
    dev = devtrace.reduce(events, phases=[_phases(r, back) for r in traced])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return dev, events


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="with JAX_PLATFORMS=cpu in the environment: the "
                         "same path at the configuration's rehearsal_sf")
    ap.add_argument("--root", default=ROOT,
                    help="the directory that holds BENCHMARK.json")
    args = ap.parse_args(argv)

    man = Manifest(args.root)
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    mix = man.mix(cell["traffic"])
    entry = man.module("entries", mix["entry"])
    loop = man.module("loops", mix["loop"])

    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.cpu_rehearsal and not cpu_asked:
        print("benchmark: --cpu-rehearsal needs JAX_PLATFORMS=cpu in the "
              "environment", file=sys.stderr)
        return 2

    import snappydata_tpu  # noqa: F401  (x64 and the compile cache first)
    import jax

    devs = jax.devices()
    device = _device_info(devs)
    if args.cpu_rehearsal:
        if device["platform"] != "cpu":
            print(f"benchmark: rehearsal found {device['platform']}",
                  file=sys.stderr)
            return 2
        sf = float(config["rehearsal_sf"])
        # the chip's dtype policy (float32 plates, float64 accumulators),
        # which the configuration states and the reference follows
        snappydata_tpu.config.global_properties().decimal_as_float64 = False
    else:
        if device["platform"] != "tpu" or len(devs) < cell["chips"]:
            print(f"benchmark: cell {cell['name']} needs {cell['chips']} "
                  f"TPU chip(s); JAX found {device}", file=sys.stderr)
            return 2
        sf = float(config["sf"])
        peaks_for(device["kind"])      # an unknown device is an error
    devs = devs[:cell["chips"]]
    device["count"] = len(devs)
    on_chip = device["platform"] == "tpu"
    say("device", **device, sf=sf, seed=args.seed,
        cache_dir=jax.config.jax_compilation_cache_dir,
        rehearsal=bool(args.cpu_rehearsal))

    # ---- set-up: data, load, warm-up ----------------------------------
    engine = entry.Engine(mix)
    traffic = Traffic(man, mix, config, sf, args.seed)
    keep = traffic.columns()
    ref_cols, rows, made_by = {}, {}, set()
    t0 = time.perf_counter()
    for table in mix["tables"]:
        spec = config["tables"][table]
        gen = man.module("generators", spec["generator"])
        made_by.add(gen)
        engine.create(spec["ddl"])
        cols = gen.generate(table, sf, args.seed)
        rows[table] = len(next(iter(cols.values())))
        engine.load(table, cols)
        ref_cols[table] = {c: cols[c] for c in keep.get(table, ())}
        del cols
    for gen in made_by:
        if hasattr(gen, "release"):
            gen.release()
    load_s = time.perf_counter() - t0
    engine.serve()
    run = Run(man, engine, entry.FRONT, rows, trace=bool(args.trace))
    t0 = time.perf_counter()
    warm = [run.issue(st) for st in traffic.warmup()]
    bad = [r for r in warm if not r["ok"]]
    if bad:
        raise RuntimeError(f"warm-up statement failed: {bad[0]['error']}")
    say("setup", load_s=load_s, warmup_s=time.perf_counter() - t0,
        rows=rows, warmup_ms=[[r["name"], r["ms"]] for r in warm])

    # ---- the window ------------------------------------------------------
    profiling = bool(args.trace) and on_chip
    trace_dir = None
    c0 = _counters()
    gc.collect()
    tracing = contextlib.ExitStack()
    run.window = True
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    if profiling:
        trace_dir = tempfile.mkdtemp(prefix="snappybench-trace-")
        run.annotate = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(trace_dir)
        tracing.callback(jax.profiler.stop_trace)
        tracing.enter_context(run.annotate(devtrace.WINDOW))
    trace_for = float(mix.get("trace_seconds", 3))

    def after_cycle(elapsed: float) -> None:
        if run.annotate and elapsed >= trace_for:
            # the profiler traced whole cycles; the rest run without it
            tracing.close()
            run.annotate = None

    cycles = loop.drive(run.issue, traffic, args.seconds, after_cycle)
    tracing.close()
    run.annotate = None
    window_s = time.perf_counter() - t_open
    run.window = False
    c1 = _counters()
    memory_peak = _memory_peak(devs)

    # ---- read back what was acknowledged, then free the program ----------
    readback = {}
    for table in mix.get("readback", []):
        readback[table] = engine.query(f"SELECT count(*) FROM {table}",
                                       [])[0][0]
    disk_bytes = engine.disk_bytes()
    engine.close()
    del engine, run.engine
    gc.collect()

    # ---- the reference ---------------------------------------------------
    t0 = time.perf_counter()
    limits = dict(config["limits"])
    world = World(man, plates=config["precision"]["plates"],
                  accumulate=config["precision"]["accumulate"])
    for table, cols in ref_cols.items():
        world.insert(table, cols)
    ref_cols.clear()
    numbers = replay(man, run.log, world, keep, limits)
    numbers["rowcount_diff"] = sum(
        abs(int(n) - int(world.rows.get(t, 0)))
        for t, n in readback.items())
    reference_s = time.perf_counter() - t0
    checks = {
        "sum_rel_gap": {"value": numbers["sum_rel_gap"],
                        "limit": limits["sum_rel_gap"]},
        "exact_mismatches": {"value": numbers["exact_mismatches"],
                             "limit": 0},
        "unanswered": {"value": numbers["unanswered"], "limit": 0},
        "rowcount_diff": {"value": numbers["rowcount_diff"], "limit": 0},
    }
    win = [r for r in run.log if r["window"]]
    failed = sum(1 for r in win if not r["ok"])
    correct = failed == 0 and len(win) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    # ---- metrics ---------------------------------------------------------
    by_name = {}
    for r in win:
        by_name.setdefault(r["name"], []).append(r["ms"])
    lanes = {k: v - c0.get(k, 0) for k, v in c1.items()
             if v != c0.get(k, 0)}
    answered = sorted(r["ms"] for r in win if "answer" in r)
    say("window", window_s=window_s, cycles=cycles, attempted=len(win),
        failed=failed, compared=numbers["compared"],
        reference_s=reference_s, memory_peak_bytes=memory_peak,
        statements={n: {"n": len(v), "median_ms": spans.median(v),
                        "max_ms": max(v)} for n, v in by_name.items()},
        query_ms={f"p{q}": spans.percentile(answered, q)
                  for q in (50, 90, 95, 99)},
        slowest_query_ms=answered[-16:],
        counters=lanes, readback=readback, disk_bytes=disk_bytes,
        host_rss_peak_bytes=resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024)
    # every statement of the window in order, for whoever reads a tail
    say("series", ms=[[r["name"], round(r["ms"], 3)] for r in win[:4000]])
    for r in win:
        if not r["ok"]:
            say("failed_statement", name=r["name"], error=r.get("error"),
                gap=r.get("gap"), got=r.get("answer"),
                expected=r.get("expected"), params=r["st"].subst)
            break

    result = {"correct": bool(correct), "attempted": len(win),
              "failed": failed, "metrics": {}, "device": dict(device)}
    result["device"]["memory_peak_bytes"] = memory_peak
    dev = None
    if profiling:
        t0 = time.perf_counter()
        dev, events = read_trace(trace_dir,
                                 [r for r in win if r["traced"]],
                                 entry.BACK)
        result["device"]["busy_s"] = dev["busy_s"]
        result["device"]["window_s"] = dev["window_s"]
        result["breakdown"] = {"device_ops": dev["device_ops"],
                               "idle_gaps": dev["idle_gaps"]}
        say("trace", reduce_s=time.perf_counter() - t0,
            planes=events["lines"], devices=dev["devices"],
            host_marks=len(events["host"]))
    ctx = {"statements": win, "device": dev, "mix": mix, "config": config,
           "cell": cell, "setup_s": setup_s, "window_s": window_s,
           "front": entry.FRONT, "back": entry.BACK,
           "peaks": peaks_for(device["kind"]) if on_chip else None}
    group = "per_layer" if args.trace else "end_to_end"
    for m in man.metrics_of(cell["name"], group):
        value = man.read(m["name"], ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

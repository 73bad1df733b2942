#!/usr/bin/env python3
"""How widely runs of one commit spread, by the driver's own statistic.

    python3 benchmark/spread.py [--split] <result file or directory> ...

Each argument is one set of runs: a file that holds what `run.py` printed,
a pattern of such files or a directory of them (`*.out`). For every cell
found it prints one JSON line: per set and over all sets, each end-to-end
metric's median, its spread (the distance between the first and the third
quartile, as `statistics.quantiles(values, n=4)` gives them, over the
median), the same with the run farthest from the median left out where
that narrows it (the ledger's `reason` and `notes` define the driver's
spread so), and the range over the median; per run, from its `series` line,
every statement name's median latency with the count and the summed
milliseconds of statements slower than `SLOW_FACTOR` times that median.
With `--split` the cell's runs, in the order given (a directory's by seed),
go alternately to a "parent" and a "change" side and each metric is judged
by the driver's rule against the bound in `BENCHMARK.json`, both ways round.

A result file does not name its cell: it is the cell whose mix cycles
through exactly the statement names of the run's `window` line. Nothing of
the program is imported.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from manifest import Manifest   # noqa: E402

SLOW_FACTOR = 5.0


def spread(values) -> float | None:
    """Quartile distance over the median; None under two values and
    where the median is 0 (a count that reads 0 has no relative spread)."""
    values = list(values)
    mid = statistics.median(values) if values else 0
    if len(values) < 2 or mid == 0:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return abs(q3 - q1) / abs(mid)


def spread_without_farthest(values) -> float | None:
    """`spread`, with the run farthest from the median left out where
    that narrows it."""
    values = list(values)
    whole = spread(values)
    if whole is None or len(values) < 3:
        return whole
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    narrower = spread(rest)
    return whole if narrower is None else min(whole, narrower)


def summary(values) -> dict:
    values = list(values)
    mid = statistics.median(values)
    return {"n": len(values), "median": mid, "spread": spread(values),
            "spread_without_farthest": spread_without_farthest(values),
            "range": (max(values) - min(values)) / abs(mid) if mid else None}


def slow_statements(statements) -> dict:
    """`statements`: (name, ms) pairs. Per name the count, the median
    and what ran slower than `SLOW_FACTOR` medians of its own name."""
    by_name = {}
    for name, ms in statements:
        by_name.setdefault(name, []).append(float(ms))
    out = {}
    for name, ms in by_name.items():
        mid = statistics.median(ms)
        slow = [v for v in ms if v > SLOW_FACTOR * mid]
        out[name] = {"n": len(ms), "median_ms": mid, "slow_n": len(slow),
                     "slow_ms": sum(slow)}
    return out


def judge(parent, change, bound: float, better: str) -> str:
    """The driver's rule for one metric of one cell, as its ledger words
    it: `unresolved` where either side spreads (farthest run left out)
    by the bound or more; else `unchanged` where the change's median is
    within the bound of the parent's; else `gain` or `regression`."""
    p, c = statistics.median(parent), statistics.median(change)
    room = bound * abs(p)
    if max(spread_without_farthest(parent) * abs(p),
           spread_without_farthest(change) * abs(c)) >= room:
        return "unresolved"
    if abs(c - p) <= room:
        return "unchanged"
    return "gain" if (c > p) == (better == "higher") else "regression"


def read_run(path: str) -> dict | None:
    """One result file; None where it holds no result line."""
    lines, res = {}, None
    with open(path, encoding="utf-8") as f:
        for text in f:
            if not text.startswith("{"):
                continue
            try:
                obj = json.loads(text)
            except ValueError:
                continue
            if "line" in obj:
                lines[obj["line"]] = obj
            elif "metrics" in obj:
                res = obj
    if res is None or "window" not in lines:
        return None
    return {"file": path, "seed": lines.get("device", {}).get("seed"),
            "correct": res["correct"], "failed": res["failed"],
            "names": sorted(lines["window"]["statements"]),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "slow": slow_statements(lines.get("series", {}).get("ms", []))}


def read_sets(man: Manifest, paths: list) -> dict:
    """cell -> [(set name, [run, ...]), ...] in the order given."""
    cell_of = {tuple(sorted(set(man.mix(w["traffic"])["cycle"]))): w["name"]
               for w in man.doc["workloads"]}
    cells = {}
    for path in paths:
        files = sorted(glob.glob(os.path.join(path, "*.out")
                                 if os.path.isdir(path) else path))
        runs = [r for r in map(read_run, files) if r is not None]
        runs.sort(key=lambda r: (r["seed"] is None, r["seed"]))
        by_cell = {}
        for r in runs:
            by_cell.setdefault(cell_of.get(tuple(r.pop("names"))),
                               []).append(r)
        for cell, rs in by_cell.items():
            cells.setdefault(cell, []).append((path, rs))
    return cells


def by_metric(runs) -> dict:
    """metric name -> its values over `runs`, in their order."""
    out = {}
    for r in runs:
        for k, v in r["metrics"].items():
            out.setdefault(k, []).append(v)
    return out


def report(man: Manifest, cell: str, sets: list, split: bool) -> dict:
    everything = [r for _, rs in sets for r in rs]
    out = {"cell": cell,
           "sets": [{"set": name, "runs": rs,
                     "metrics": {k: summary(v)
                                 for k, v in by_metric(rs).items()}}
                    for name, rs in sets],
           "all": {k: summary(v) for k, v in by_metric(everything).items()}}
    if split:
        e2e = {e["name"]: e for e in man.doc["end_to_end"]}
        even, odd = by_metric(everything[0::2]), by_metric(everything[1::2])
        out["split"] = {
            k: {"bound": e2e[k]["bound"],
                "even_as_parent": judge(even[k], odd[k], e2e[k]["bound"],
                                        e2e[k]["better"]),
                "odd_as_parent": judge(odd[k], even[k], e2e[k]["bound"],
                                       e2e[k]["better"])}
            for k in even
            if k in e2e and min(len(even[k]), len(odd.get(k, ()))) > 1}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sets", nargs="+", help="one set of runs: a result "
                    "file, a pattern of them or a directory of them")
    ap.add_argument("--split", action="store_true",
                    help="judge alternate runs against each other")
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="the directory that holds BENCHMARK.json")
    args = ap.parse_args(argv)
    man = Manifest(args.root)
    for cell, sets in read_sets(man, args.sets).items():
        print(json.dumps(report(man, cell, sets, args.split)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

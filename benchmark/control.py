#!/usr/bin/env python3
"""The control of a cell's `correct`: the plain reference put in the
program's place, with its sums carried one width below what the
configuration states (float32 for float64), at the cell's own size.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--cycles N]

For each seed it makes the cell's data, draws the statements of `--cycles`
cycles as a run would, answers them with the control and with the
reference (writes applied to both), and prints the widest relative gap:
the number a run compares. The smallest over the seeds is the upper
reading that `sum_rel_gap`'s limit has to stay under. The benchmark's own
runs never call this; `tests/test_benchmark.py` keeps it at a small size.
It touches neither JAX nor the program, so it reads the same on any
machine.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from manifest import Manifest    # noqa: E402
from reference import World, compare   # noqa: E402
from traffic import Traffic      # noqa: E402


def control_gap(man: Manifest, workload: str, seed: int, cycles: int,
                sf: float = None, accumulate: str = "float32") -> dict:
    cell = man.cell(workload)
    config, mix = man.config(cell["config"]), man.mix(cell["traffic"])
    sf = float(config["sf"]) if sf is None else sf
    traffic = Traffic(man, mix, config, sf, seed)
    keep = traffic.columns()
    ref = World(man, accumulate=config["precision"]["accumulate"])
    low = World(man, accumulate=accumulate)
    for table in mix["tables"]:
        gen = man.module("generators",
                         config["tables"][table]["generator"])
        cols = gen.generate(table, sf, seed)
        for w in (ref, low):
            w.insert(table, {c: cols[c] for c in keep.get(table, ())})
    gap, wrong, n = 0.0, 0, 0
    for st in traffic.warmup() + [s for _ in range(cycles)
                                  for s in traffic.cycle()]:
        kind = man.module("kinds", st.kind)
        if hasattr(kind, "apply"):
            data = st.data
            for w in (ref, low):
                st.data = data      # the first apply lets it go
                kind.apply(w, st, keep)
        else:
            g, w = compare(kind.expected(low, st, {}),
                           kind.expected(ref, st, {}))
            gap, wrong, n = max(gap, g), wrong + w, n + 1
    return {"workload": workload, "seed": seed, "sf": sf, "compared": n,
            "control": f"{accumulate} accumulators", "sum_rel_gap": gap,
            "exact_mismatches": wrong,
            "limit": config["limits"]["sum_rel_gap"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    man = Manifest(args.root)
    gaps = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = control_gap(man, args.workload, seed, args.cycles)
        out["seconds"] = time.perf_counter() - t0
        gaps.append(out["sum_rel_gap"])
        print(json.dumps(out), flush=True)
    print(json.dumps({"workload": args.workload, "upper_reading": min(gaps),
                      "limit": out["limit"],
                      "fails_as_it_must": min(gaps) > out["limit"]}),
          flush=True)
    return 0 if min(gaps) > out["limit"] else 1


if __name__ == "__main__":
    sys.exit(main())

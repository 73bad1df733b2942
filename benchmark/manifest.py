"""`BENCHMARK.json` and the files it names: loading, and the check that
they agree (what `tests/test_benchmark.py` runs, so that a manifest the
driver would refuse before any run is refused here first).

Whatever belongs to one configuration, one mix, one metric, one table
generator, one query's reference, one kind of statement, one entry or
one loop is a file found by its name under one of `paths`:
`configs/<config>.json`, `mixes/<traffic>.json`, `metrics/<metric>.json`
and the reader it names, `readers/<reader>.py`;
`generators/<name>.py`, `references/<name>.py`, `kinds/<name>.py`,
`entries/<name>.py`, `loops/<name>.py`. A later PR adds one by adding a
file, in a directory of its own listed in `paths`, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
TEXT = re.compile(r"[\x20-\x7e]{1,200}\Z")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json"),
                  encoding="utf-8") as f:
            self.doc = json.load(f)
        self.dirs = [os.path.join(self.root, p) for p in self.doc["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)
        self._modules = {}

    def find(self, *parts: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, *parts)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(
            f"{os.path.join(*parts)} under none of {self.dirs}")

    def _json(self, *parts: str) -> dict:
        with open(self.find(*parts), encoding="utf-8") as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"]),
                          encoding="utf-8") as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return self._json("mixes", name + ".json")

    def metrics_of(self, cell: str, group: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]

    def metric(self, name: str) -> dict:
        return self._json("metrics", name + ".json")

    def module(self, group: str, name: str):
        """`<group>/<name>.py` under one of `paths`, loaded once. Its
        directory joins `sys.path`, so that it can import its
        neighbours and the benchmark's own modules."""
        key = (group, name)
        if key not in self._modules:
            path = self.find(group, name + ".py")
            if os.path.dirname(path) not in sys.path:
                sys.path.append(os.path.dirname(path))
            spec = importlib.util.spec_from_file_location(
                f"bench_{group}_" + re.sub(r"\W", "_", name), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def read(self, name: str, ctx: dict):
        """The metric `name` read from `ctx` by the reader its file
        names; None where the reader found nothing to read."""
        m = self.metric(name)
        return self.module("readers", m["reader"]).read(
            ctx, **m.get("args", {}))


def problems(m: Manifest, max_four_chip_share: float = 0.5) -> list:
    """Everything about the manifest that the driver's rules, as the
    builder's instructions give them, would refuse before a run."""
    doc, out = m.doc, []

    def bad(what):
        out.append(what)

    if set(doc) != KEYS["top"]:
        bad(f"top-level keys {sorted(doc)}")
    if not (isinstance(doc.get("run_seconds"), int)
            and 1 <= doc["run_seconds"] <= 51):
        bad(f"run_seconds {doc.get('run_seconds')!r}")
    for word in doc.get("command", []):
        if not TEXT.match(word) or word.startswith("/") or ".." in word:
            bad(f"command word {word!r}")
    for p in doc.get("paths", []):
        if not re.match(r"[A-Za-z0-9_./-]{1,200}\Z", p) or \
                p.startswith("/") or ".." in p:
            bad(f"path {p!r}")
    names = {g: [e.get("name") for e in doc.get(g, [])]
             for g in ("configs", "workloads", "end_to_end", "per_layer")}
    for g, ns in names.items():
        for n in ns:
            if not isinstance(n, str) or not NAME.match(n):
                bad(f"{g} name {n!r}")
    for ns in (names["configs"], names["workloads"],
               names["end_to_end"] + names["per_layer"]):
        if len(set(ns)) != len(ns):
            bad(f"duplicate names among {ns}")
    files = []
    for c in doc.get("configs", []):
        if set(c) != KEYS["configs"]:
            bad(f"config {c.get('name')}: keys {sorted(c)}")
        for key in ("source", "why"):
            if not TEXT.match(c.get(key, "")):
                bad(f"config {c.get('name')}: {key} must be 1 to 200 "
                    f"printable ASCII characters")
        for r in c.get("reduced", []):
            if not NAME.match(r):
                bad(f"config {c.get('name')}: reduced key {r!r}")
        f = c.get("file", "")
        files.append(f)
        if not any(f.startswith(p.rstrip("/") + "/") for p in doc["paths"]):
            bad(f"config {c.get('name')}: file {f!r} outside paths")
        elif not os.path.isfile(os.path.join(m.root, f)):
            bad(f"config {c.get('name')}: no file {f!r}")
        if c.get("name") not in [w.get("config") for w in doc["workloads"]]:
            bad(f"config {c.get('name')}: used by no cell")
    if len(set(files)) != len(files):
        bad("two configurations share a file")
    def check_mix(w):
        """Everything the cell's mix and configuration name is a file."""
        mix = m.mix(w["traffic"])
        cfg = m.config(w["config"])
        m.find("entries", mix["entry"] + ".py")
        m.find("loops", mix["loop"] + ".py")
        for t in mix["tables"]:
            m.find("generators", cfg["tables"][t]["generator"] + ".py")
        for n in set(mix["cycle"]) | set(mix.get("warmup", [])):
            spec = mix["statements"][n]
            m.find("kinds", spec["kind"] + ".py")
            for group in ("generator", "reference"):
                if group in spec:
                    m.find(group + "s", spec[group] + ".py")

    pairs = []
    for w in doc.get("workloads", []):
        if set(w) != KEYS["workloads"]:
            bad(f"workload {w.get('name')}: keys {sorted(w)}")
        if not TEXT.match(w.get("why", "")):
            bad(f"workload {w.get('name')}: why")
        if w.get("chips") not in (1, 4):
            bad(f"workload {w.get('name')}: chips {w.get('chips')!r}")
        if w.get("config") not in names["configs"]:
            bad(f"workload {w.get('name')}: unknown config")
        if not NAME.match(str(w.get("traffic", ""))):
            bad(f"workload {w.get('name')}: traffic name")
        else:
            try:
                check_mix(w)
            except (OSError, ValueError, KeyError) as e:
                bad(f"workload {w.get('name')}: mix: {e}")
        pairs.append((w.get("config"), w.get("traffic")))
    if len(set(pairs)) != len(pairs):
        bad("a pair of configuration and traffic appears twice")
    four = sum(1 for w in doc.get("workloads", []) if w.get("chips") == 4)
    if four > max(1, int(len(doc.get("workloads", []))
                         * max_four_chip_share)):
        bad(f"{four} four-chip cells")
    cells = set(names["workloads"])
    e2e = {e["name"]: e for e in doc.get("end_to_end", [])}

    def check_file(entry, keys):
        """The metric's own file says the same and names a reader."""
        n = entry.get("name")
        try:
            mf = m.metric(n)
            for key in keys:
                if mf.get(key) != entry.get(key):
                    bad(f"metric {n}: {key} differs from its file")
            m.find("readers", mf["reader"] + ".py")
        except (OSError, ValueError, KeyError) as e:
            bad(f"metric {n}: {e}")
    if "setup_s" not in e2e:
        bad("no setup_s")
    for e in doc.get("end_to_end", []):
        if set(e) - {"workloads"} != KEYS["end_to_end"]:
            bad(f"end_to_end {e.get('name')}: keys {sorted(e)}")
        if not UNIT.match(e.get("unit", "")):
            bad(f"end_to_end {e.get('name')}: unit {e.get('unit')!r}")
        if e.get("better") not in ("lower", "higher"):
            bad(f"end_to_end {e.get('name')}: better")
        if e.get("source") not in ("host_clock", "device_trace"):
            bad(f"end_to_end {e.get('name')}: source")
        if not (isinstance(e.get("bound"), float)
                and 0.01 <= e["bound"] <= 0.25):
            bad(f"end_to_end {e.get('name')}: bound {e.get('bound')!r}")
        if set(e.get("workloads", [])) - cells:
            bad(f"end_to_end {e.get('name')}: unknown cells")
        check_file(e, ("name", "unit", "better", "source"))
    if "workloads" in e2e.get("setup_s", {}):
        bad("setup_s is not reported by every cell")
    for p in doc.get("per_layer", []):
        n = p.get("name")
        if set(p) - {"workloads"} != KEYS["per_layer"]:
            bad(f"per_layer {n}: keys {sorted(p)}")
        if not UNIT.match(p.get("unit", "")):
            bad(f"per_layer {n}: unit {p.get('unit')!r}")
        if p.get("better") not in ("lower", "higher"):
            bad(f"per_layer {n}: better")
        if p.get("source") not in SOURCES:
            bad(f"per_layer {n}: source")
        if not TEXT.match(p.get("layer", "")):
            bad(f"per_layer {n}: layer")
        if set(p.get("workloads", [])) - cells:
            bad(f"per_layer {n}: unknown cells")
        moved = e2e.get(p.get("moves"))
        if moved is None:
            bad(f"per_layer {n}: moves {p.get('moves')!r}")
        else:
            for c in p.get("workloads", cells):
                if c not in moved.get("workloads", cells):
                    bad(f"per_layer {n}: cell {c} does not report "
                        f"{p['moves']}")
        check_file(p, ("name", "unit", "better", "source", "layer",
                       "moves"))
    for c in cells:
        mine = [e for e in doc.get("end_to_end", [])
                if c in e.get("workloads", [c])]
        if len(mine) < 2:
            bad(f"cell {c}: no end-to-end metric besides setup_s")
        if not any(c in p.get("workloads", [c])
                   for p in doc.get("per_layer", [])):
            bad(f"cell {c}: no per-layer metric")
    if len(json.dumps(doc)) > 64 * 1024:
        bad("BENCHMARK.json over 64 KiB")
    return out

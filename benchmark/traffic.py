"""The one traffic generator: a mix file and a seed give the statements.

A mix (`mixes/<name>.json`) holds:

- `entry`: how the statements reach the system, a module under
  `entries/` (`embedded`: `SnappySession.sql`; `flight`: `SnappyClient`
  against a `SnappyFlightServer` on the same session);
- `loop`: who sends them, a module under `loops/` (`closed`: one caller
  that waits for each reply);
- `tables`: which of the configuration's tables the cell loads;
- `durable`: whether the session has a `data_dir` and a WAL;
- `statements`: name -> template. Its `kind` is a module under `kinds/`
  that draws it from the seed, sends it and tells the reference what it
  did (`query`, `insert_rows`, `delete_range`); the other keys are that
  kind's own;
- `cycle`: the statements of one cycle in order; `warmup`: the statements
  set-up runs before the window (the shapes the cycle will meet);
- `readback`: tables whose row count is read back after the close.

Every seed walks the same set of parameter combinations, in an order of
its own (`draws`): the product of a statement's choices is permuted from
the seed and taken in turn, so no combination repeats before all were
used, and runs on different seeds do the same work.
"""

from __future__ import annotations

import itertools
import zlib

import numpy as np


def _choices(spec) -> list:
    if isinstance(spec, dict):
        lo, hi = spec["int_range"]
        return [{spec["name"]: v} for v in range(lo, hi + 1)]
    return list(spec)


class Statement:
    """One statement as issued: what to send, and what the reference
    needs to answer or apply it."""

    __slots__ = ("name", "kind", "sql", "params", "subst", "spec", "data",
                 "keys")

    def __init__(self, name, kind, spec, sql=None, params=(), subst=None,
                 data=None, keys=None):
        self.name, self.kind, self.spec = name, kind, spec
        self.sql, self.params, self.subst = sql, list(params), subst or {}
        self.data, self.keys = data, keys


class Traffic:
    def __init__(self, manifest, mix: dict, config: dict, sf: float,
                 seed: int):
        self.manifest = manifest
        self.mix, self.config, self.sf, self.seed = mix, config, sf, seed
        self.counts = {}        # statement name -> how many were made
        self._combos = {}

    def kind(self, name: str):
        return self.manifest.module(
            "kinds", self.mix["statements"][name]["kind"])

    def draws(self, name: str) -> list:
        """The statement's parameter combinations in this seed's order."""
        if name not in self._combos:
            spec = self.mix["statements"][name]
            dims = [_choices(c) for c in spec.get("draws", {}).values()]
            combos = [dict(itertools.chain.from_iterable(
                d.items() for d in pick))
                for pick in itertools.product(*dims)]
            rng = np.random.default_rng(
                [self.seed, zlib.crc32(name.encode("utf-8"))])
            self._combos[name] = [combos[i]
                                  for i in rng.permutation(len(combos))]
        return self._combos[name]

    def statement(self, name: str, warmup: bool = False) -> Statement:
        spec = self.mix["statements"][name]
        k = self.counts.get((name, warmup), 0)
        self.counts[(name, warmup)] = k + 1
        return self.kind(name).make(self, name, spec, k, warmup)

    def made(self, name: str) -> int:
        """How many statements `name` were made, warm-up included."""
        return sum(v for (n, _), v in self.counts.items() if n == name)

    def columns(self) -> dict:
        """table -> the columns the reference has to keep for this mix."""
        out = {}
        for name, spec in self.mix["statements"].items():
            for table, cols in self.kind(name).columns(
                    self.manifest, spec).items():
                have = out.setdefault(table, [])
                have.extend(c for c in cols if c not in have)
        return out

    def cycle(self) -> list:
        return [self.statement(n) for n in self.mix["cycle"]]

    def warmup(self) -> list:
        return [self.statement(n, warmup=True)
                for n in self.mix.get("warmup", self.mix["cycle"])]

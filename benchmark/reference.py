"""The plain reference's side of a run: the tables as NumPy arrays, the
writes applied to them, and the comparison of two answers. It imports
nothing of the program.

A query's reference is a module `references/<name>.py`, found by the name
the mix gives the statement. It declares the columns it reads
(`COLUMNS`: table -> names; the harness keeps no others) and a class
`Reference(world)` with `answer(params)` and, where it keeps running
sums, `on_insert(table, chunk)` and `on_delete(table, chunk, mask)`.

DOUBLE columns are held as the configuration states them (`plates`, a
NumPy float type: float32 under the TPU's dtype policy) and sums are
carried in `accumulate` (float64). A world
with `accumulate` one width lower is the control: it has to come out as
not correct.
"""

from __future__ import annotations

import decimal

import numpy as np


class Chunk:
    """Rows of one table added together: the loaded table, or one
    insert. `cols` holds the columns some reference reads."""

    def __init__(self, cols: dict, plates):
        self.cols = {}
        for name, a in cols.items():
            a = np.asarray(a)
            self.cols[name] = a.astype(plates) if a.dtype.kind == "f" else a
        self.n = len(next(iter(cols.values())))
        self.live = np.ones(self.n, dtype=bool)
        self.derived = {}       # a reference's own arrays, by name

    def column(self, name: str, live_only: bool = False):
        a = self.cols[name]
        return a[self.live] if live_only and not self.live.all() else a


class World:
    def __init__(self, manifest, plates: str = "float32",
                 accumulate: str = "float64"):
        self.manifest = manifest
        self.plates = np.dtype(plates)
        self.acc = np.dtype(accumulate)
        self.chunks, self.rows, self.refs = {}, {}, {}

    # -- state -----------------------------------------------------------

    def insert(self, table: str, cols: dict) -> None:
        ch = Chunk(cols, self.plates)
        self.chunks.setdefault(table, []).append(ch)
        self.rows[table] = self.rows.get(table, 0) + ch.n
        for ref in self.refs.values():
            if hasattr(ref, "on_insert"):
                ref.on_insert(table, ch)

    def delete_range(self, table: str, column: str, lo, hi) -> int:
        """Rows of `table` with lo <= column < hi. Returns how many."""
        gone = 0
        for ch in self.chunks.get(table, ()):
            a = ch.cols[column]
            m = ch.live & (a >= lo) & (a < hi)
            k = int(m.sum())
            if k:
                for ref in self.refs.values():
                    if hasattr(ref, "on_delete"):
                        ref.on_delete(table, ch, m)
                ch.live[m] = False
                gone += k
        self.rows[table] = self.rows.get(table, 0) - gone
        return gone

    def live(self, table: str, column: str) -> np.ndarray:
        """A column over the live rows of every chunk."""
        parts = [ch.column(column, live_only=True)
                 for ch in self.chunks.get(table, ())]
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    # -- answers ---------------------------------------------------------

    def sum(self, values) -> float:
        """A sum carried in `acc` (pairwise, as NumPy adds: the most a
        narrower accumulator could keep)."""
        if self.acc == np.float64:
            return float(values.astype(np.float64).sum())
        return float(values.sum(dtype=self.acc))

    def answer(self, name: str, params: dict) -> list:
        if name not in self.refs:
            self.refs[name] = self.manifest.module(
                "references", name).Reference(self)
        return self.refs[name].answer(params)


def compare(got: list, exp: list) -> tuple:
    """(widest relative gap of a float, exact mismatches) between two
    answers. Strings, integers, NULLs and the number of rows are exact; a
    float's gap is taken against the reference's magnitude, or 1 where
    that is smaller, as `chip_smoke.py`'s `close` takes it."""
    if len(got) != len(exp):
        return float("inf"), 1
    gap, wrong = 0.0, 0
    for rg, re_ in zip(got, exp):
        if len(rg) != len(re_):
            return float("inf"), 1
        for g, e in zip(rg, re_):
            if isinstance(g, decimal.Decimal):
                g = float(g)        # an exact DECIMAL result at the edge
            if isinstance(e, float) and isinstance(g, (float, int)) \
                    and not isinstance(g, bool):
                gap = max(gap, abs(float(g) - e) / max(abs(e), 1.0))
            elif g != e:
                wrong += 1
    return gap, wrong

"""`select sym, avg(id) from testtable group by sym` (the upstream
quick-start benchmark's statement): one row a distinct `sym`, the
average of its BIGINT `id`s.

The sums and counts are integers, int64 and exact in any order; the
average is one float64 division of the two. They are worked out once
per state of the world and answer every equal statement until a write.
The rows come back by `sym`; the statement itself promises no order.
The control reads the statement as its text reads, a group at a time,
with the sums carried in its narrower float width.
"""

import numpy as np

COLUMNS = {"testtable": ["id", "sym"]}

_WIDTH = 10         # VARCHAR(10)
_SLICE = 1 << 20    # rows `np.unique` sorts at a time


def _codes(ch):
    """The chunk's syms as small integers, and the names they stand for:
    `np.unique` over the strings as fixed-width bytes, a slice of the rows
    at a time (a sort of a million rows stays in the cache), and the
    slices' names numbered together."""
    if "sym_codes" not in ch.derived:
        raw = np.asarray(ch.cols["sym"]).astype(f"S{_WIDTH + 1}")
        parts = [np.unique(raw[lo:lo + _SLICE], return_inverse=True)
                 for lo in range(0, len(raw), _SLICE)]
        names = np.unique(np.concatenate([u for u, _ in parts]))
        if max(map(len, names)) > _WIDTH:
            raise ValueError(f"a sym wider than VARCHAR({_WIDTH})")
        code = np.concatenate([np.searchsorted(names, u)[inv]
                               for u, inv in parts])
        ch.derived["sym_codes"] = (code, [n.decode("ascii") for n in names])
    return ch.derived["sym_codes"]


class Reference:
    def __init__(self, world):
        self.world = world
        self.built = None

    def on_insert(self, table, ch) -> None:
        self.built = None

    def on_delete(self, table, ch, mask) -> None:
        self.built = None

    def _live(self):
        """(names, codes, ids) over the live rows, a chunk at a time."""
        for ch in self.world.chunks.get("testtable", ()):
            code, names = _codes(ch)
            ids = ch.cols["id"]
            if not ch.live.all():
                code, ids = code[ch.live], ids[ch.live]
            yield names, code, ids

    def _build(self) -> None:
        sums, counts = {}, {}
        for names, code, ids in self._live():
            s = np.zeros(len(names), dtype=np.int64)
            np.add.at(s, code, ids)
            c = np.bincount(code, minlength=len(names))
            for g, name in enumerate(names):
                if c[g]:
                    sums[name] = sums.get(name, 0) + int(s[g])
                    counts[name] = counts.get(name, 0) + int(c[g])
        self.built = [(name, float(sums[name]) / counts[name])
                      for name in sorted(sums)]

    def answer(self, p: dict) -> list:
        if self.world.acc != np.float64:
            return self.direct(p)
        if self.built is None:
            self._build()
        return list(self.built)

    def direct(self, p: dict) -> list:
        """The statement as its text reads, a group at a time with the
        world's own sum: the control's path, and what the sums above are
        tested against."""
        sums, counts = {}, {}
        for names, code, ids in self._live():
            for g, name in enumerate(names):
                mine = ids[code == g]
                if len(mine):
                    sums[name] = sums.get(name, 0.0) + self.world.sum(mine)
                    counts[name] = counts.get(name, 0) + len(mine)
        return [(name, sums[name] / counts[name]) for name in sorted(sums)]

"""TPC-H Q6: `revenue` = sum(l_extendedprice * l_discount) over one
calendar year of ship dates, a band of three discount hundredths and
quantities below a bound (cl 2.4.6).

Sums and counts are kept by (ship year, discount hundredth, quantity),
built in one pass and moved by each insert and delete, so a statement is
a rectangle sum and a window's every Q6 is compared at the cost of one
scan. Each product is rounded to the plates' width once, as the program
rounds it; the sums are float64. Where the world's accumulator is
narrower (the control) the statement is read as its text reads, one mask
over the rows, with the sum carried in that width.
"""

import datetime

import numpy as np

COLUMNS = {"lineitem": ["l_shipdate", "l_quantity", "l_extendedprice",
                        "l_discount"]}

_EPOCH = datetime.date(1970, 1, 1)
_YEAR0, _N_YEARS, _N_DISC, _N_QTY = 1992, 8, 12, 64


def _year_start(year: int) -> int:
    return (datetime.date(year, 1, 1) - _EPOCH).days


class Reference:
    def __init__(self, world):
        self.world = world
        self._year_of_day = np.zeros(_year_start(_YEAR0 + _N_YEARS),
                                     dtype=np.int8)
        for i in range(_N_YEARS):
            self._year_of_day[_year_start(_YEAR0 + i):] = i
        size = _N_YEARS * _N_DISC * _N_QTY
        self.rev = np.zeros(size, dtype=np.float64)
        self.cnt = np.zeros(size, dtype=np.int64)
        for ch in world.chunks.get("lineitem", ()):
            self._add(ch, None if ch.live.all() else ch.live, +1)

    @staticmethod
    def _disc100(ch):
        # exact hundredths: `BETWEEN 0.05 AND 0.07` means these, whatever
        # width the plate has
        if "disc100" not in ch.derived:
            ch.derived["disc100"] = np.rint(
                ch.cols["l_discount"].astype(np.float64) * 100) \
                .astype(np.int8)
        return ch.derived["disc100"]

    def _add(self, ch, mask, sign: int) -> None:
        def col(a):
            return a if mask is None else a[mask]

        key = self._year_of_day[col(ch.cols["l_shipdate"])] \
            .astype(np.int32)
        key *= _N_DISC
        key += col(self._disc100(ch))
        key *= _N_QTY
        key += col(ch.cols["l_quantity"]).astype(np.int32)
        prod = (col(ch.cols["l_extendedprice"])
                * col(ch.cols["l_discount"])).astype(np.float64)
        self.rev += sign * np.bincount(key, weights=prod,
                                       minlength=len(self.rev))
        self.cnt += sign * np.bincount(key, minlength=len(self.cnt))

    def on_insert(self, table, ch) -> None:
        if table == "lineitem":
            self._add(ch, None, +1)

    def on_delete(self, table, ch, mask) -> None:
        if table == "lineitem":
            self._add(ch, mask, -1)

    def answer(self, p: dict) -> list:
        if self.world.acc != np.float64:
            return self.direct(p)
        y = int(p["year"]) - _YEAR0
        d = int(round(float(p["disc"]) * 100))
        shape = (_N_YEARS, _N_DISC, _N_QTY)
        box = (y, slice(d - 1, d + 2), slice(0, int(p["qty"])))
        if int(self.cnt.reshape(shape)[box].sum()) == 0:
            return [(None,)]
        return [(float(self.rev.reshape(shape)[box].sum()),)]

    def direct(self, p: dict) -> list:
        """Q6 as its text reads: what the sums above are tested
        against, and the control's path."""
        total, n = 0.0, 0
        lo, hi = _year_start(int(p["year"])), _year_start(int(p["year"]) + 1)
        d = int(round(float(p["disc"]) * 100))
        for ch in self.world.chunks.get("lineitem", ()):
            ship, d100 = ch.cols["l_shipdate"], self._disc100(ch)
            m = (ch.live & (ship >= lo) & (ship < hi)
                 & (d100 >= d - 1) & (d100 <= d + 1)
                 & (ch.cols["l_quantity"] < float(p["qty"])))
            total += self.world.sum(ch.cols["l_extendedprice"][m]
                                    * ch.cols["l_discount"][m])
            n += int(m.sum())
        return [(total if n else None,)]

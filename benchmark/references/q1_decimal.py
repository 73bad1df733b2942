"""TPC-H Q1 (cl 2.4.1) over money declared DECIMAL(15,2), exact: six sums,
three averages and a count by (l_returnflag, l_linestatus) over ship dates
up to 1998-12-01 less DELTA days, at Spark 2.1's result types.

Every money value is its whole number of cents, `np.rint(x * 100)` as
int64 (the generator's values are cents over 100). The discounted price
is price x (100 - discount) at scale 4 and the charge that times (100 +
tax) at scale 6, both in int64 under an asserted bound. Sums are kept by
(group, ship date clipped to DELTA's domain 60-120) as int64 prefix sums,
so one pass answers every Q1; an average is its sum over its count rounded
HALF_UP at scale 6 in Python integers, DECIMAL(p+4, s+4) for p <= 15.
Every number is returned as the float of its exact decimal: two exact
answers compare with a gap of 0.

The control (`accumulate` narrower than float64) reads the statement as a
float program would: the products in the plates' width, the sums and the
averages in the narrower one.
"""

import datetime
import decimal

import numpy as np

COLUMNS = {"lineitem": ["l_shipdate", "l_quantity", "l_extendedprice",
                        "l_discount", "l_tax", "l_returnflag",
                        "l_linestatus"]}

_END = (datetime.date(1998, 12, 1) - datetime.date(1970, 1, 1)).days
_DELTA = (60, 120)
_MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def _byte(ch, name):
    """The two group columns hold one ASCII character: its byte."""
    if name not in ch.derived:
        ch.derived[name] = np.asarray(ch.cols[name]).astype("S1") \
            .view(np.uint8)
    return ch.derived[name]


def _cents(a) -> np.ndarray:
    return np.rint(np.asarray(a, dtype=np.float64) * 100).astype(np.int64)


def _decimal(v: int, scale: int) -> float:
    return float(decimal.Decimal(int(v)).scaleb(-scale))


def _avg(total: int, count: int, up: int) -> int:
    """total / count at `up` more digits of scale, rounded HALF_UP."""
    num = total * 10 ** up
    return (1 if num >= 0 else -1) * ((2 * abs(num) + count) // (2 * count))


class Reference:
    def __init__(self, world):
        self.world = world
        self.built = None

    def on_insert(self, table, ch) -> None:
        self.built = None

    def on_delete(self, table, ch, mask) -> None:
        self.built = None

    def _live(self, name):
        parts = []
        for ch in self.world.chunks.get("lineitem", ()):
            a = _byte(ch, name) if name in ("l_returnflag",
                                            "l_linestatus") \
                else ch.cols[name]
            parts.append(a if ch.live.all() else a[ch.live])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def _build(self) -> None:
        lo_delta, hi_delta = _DELTA
        first = _END - hi_delta             # the earliest cutoff
        nb = hi_delta - lo_delta + 2        # buckets; the last is never in
        flag, status = self._live("l_returnflag"), self._live("l_linestatus")
        fnames = np.flatnonzero(np.bincount(flag, minlength=256))
        snames = np.flatnonzero(np.bincount(status, minlength=256))
        code = np.zeros((2, 256), dtype=np.int64)
        code[0, fnames] = np.arange(len(fnames))
        code[1, snames] = np.arange(len(snames))
        bucket = np.clip(self._live("l_shipdate").astype(np.int64) - first,
                         0, nb - 1)
        G = len(fnames) * len(snames)
        key = (code[0][flag] * len(snames) + code[1][status]) * nb + bucket
        qty, price, disc, tax = (_cents(self._live(c)) for c in _MONEY)
        disc_price = price * (100 - disc)           # scale 4
        charge = disc_price * (100 + tax)           # scale 6
        # no row, and no sum of all rows, leaves int64
        assert float(np.abs(charge).astype(np.float64).sum()) < 2.0 ** 62
        order = np.argsort(key, kind="stable")
        k = key[order]
        heads = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
        sums = []
        for w in (qty, price, disc_price, charge, disc):
            dense = np.zeros(G * nb, dtype=np.int64)
            if len(k):
                dense[k[heads]] = np.add.reduceat(w[order], heads)
            sums.append(np.cumsum(dense.reshape(G, nb), axis=1))
        cnt = np.bincount(key, minlength=G * nb)
        self.built = {
            "names": [(chr(f), chr(s)) for f in fnames for s in snames],
            "sums": sums,
            "cnt": np.cumsum(cnt.reshape(G, nb), axis=1),
        }

    def answer(self, p: dict) -> list:
        if self.world.acc != np.float64:
            return self.control(p)
        if self.built is None:
            self._build()
        delta = int(p["delta"])
        if not _DELTA[0] <= delta <= _DELTA[1]:
            raise ValueError(f"Q1 DELTA {delta} outside {_DELTA}")
        b = _DELTA[1] - delta               # cutoff = first + b
        out = []
        for g, (flag, status) in enumerate(self.built["names"]):
            c = int(self.built["cnt"][g, b])
            if not c:
                continue
            s_qty, s_price, s_dp, s_ch, s_disc = (
                int(s[g, b]) for s in self.built["sums"])
            out.append((flag, status, _decimal(s_qty, 2),
                        _decimal(s_price, 2), _decimal(s_dp, 4),
                        _decimal(s_ch, 6),
                        _decimal(_avg(s_qty, c, 4), 6),
                        _decimal(_avg(s_price, c, 4), 6),
                        _decimal(_avg(s_disc, c, 4), 6), c))
        return out

    def control(self, p: dict) -> list:
        """Q1 as a float program computes it: the control's path."""
        cutoff = _END - int(p["delta"])
        groups = {}
        one = self.world.plates.type(1.0)
        for ch in self.world.chunks.get("lineitem", ()):
            c = {n: ch.cols[n].astype(self.world.plates) for n in _MONEY}
            flag, status = _byte(ch, "l_returnflag"), \
                _byte(ch, "l_linestatus")
            m = ch.live & (ch.cols["l_shipdate"] <= cutoff)
            dp = c["l_extendedprice"] * (one - c["l_discount"])
            charge = dp * (one + c["l_tax"])
            for f in np.unique(flag[m]):
                for s in np.unique(status[m]):
                    g = m & (flag == f) & (status == s)
                    if not g.any():
                        continue
                    acc = groups.setdefault((chr(f), chr(s)),
                                            [0.0] * 5 + [0])
                    for i, w in enumerate((c["l_quantity"],
                                           c["l_extendedprice"], dp,
                                           charge, c["l_discount"])):
                        acc[i] += self.world.sum(w[g])
                    acc[5] += int(g.sum())
        return [(f, s, a[0], a[1], a[2], a[3], a[0] / a[5], a[1] / a[5],
                 a[4] / a[5], a[5]) for (f, s), a in sorted(groups.items())]

"""TPC-H Q1: six sums, three averages and a count by (l_returnflag,
l_linestatus) over ship dates up to 1998-12-01 less DELTA days (cl
2.4.1).

Sums are kept by (group, ship date clipped to DELTA's domain 60-120) and
a statement is a prefix sum: one pass over the live rows answers every
Q1 until the next write. Each product is rounded to the plates' width
once, as the program rounds it; the sums are float64. The control reads
the statement as its text reads, with the sums in its narrower width.
"""

import datetime

import numpy as np

COLUMNS = {"lineitem": ["l_shipdate", "l_quantity", "l_extendedprice",
                        "l_discount", "l_tax", "l_returnflag",
                        "l_linestatus"]}

_END = (datetime.date(1998, 12, 1) - datetime.date(1970, 1, 1)).days
_DELTA = (60, 120)


def _byte(ch, name):
    """The two group columns hold one ASCII character: its byte."""
    if name not in ch.derived:
        ch.derived[name] = np.asarray(ch.cols[name]).astype("S1") \
            .view(np.uint8)
    return ch.derived[name]


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
        qty, price, disc, tax = (self._live(c) for c in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
        one = np.float32(1.0)
        dp = price * (one - disc)
        charge = dp * (one + tax)
        sums = [np.bincount(key, weights=w.astype(np.float64),
                            minlength=G * nb)
                for w in (qty, price, dp, charge, disc)]
        cnt = np.bincount(key, minlength=G * nb)
        self.built = {
            "names": [(chr(f), chr(s)) for f in fnames for s in snames],
            "sums": [np.cumsum(s.reshape(G, nb), axis=1) for s in sums],
            "cnt": np.cumsum(cnt.reshape(G, nb), axis=1),
        }

    def answer(self, p: dict) -> list:
        if self.world.acc != np.float64:
            return self.direct(p)
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
                float(s[g, b]) for s in self.built["sums"])
            out.append((flag, status, s_qty, s_price, s_dp, s_ch,
                        s_qty / c, s_price / c, s_disc / c, c))
        return out

    def direct(self, p: dict) -> list:
        """Q1 as its text reads: what the sums above are tested
        against, and the control's path."""
        cutoff = _END - int(p["delta"])
        groups = {}
        one = np.float32(1.0)
        for ch in self.world.chunks.get("lineitem", ()):
            c = ch.cols
            flag, status = _byte(ch, "l_returnflag"), \
                _byte(ch, "l_linestatus")
            m = ch.live & (c["l_shipdate"] <= cutoff)
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

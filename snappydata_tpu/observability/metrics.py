"""Metrics: counters, gauges, log-bucketed histogram timers with a JSON
snapshot surface and Prometheus text exposition.

Reference equivalents: per-operator SQLMetrics (ColumnTableScan.getMetrics
:115-130 — columnBatchesSeen/Skipped, numRowsBuffer), the Spark
MetricsSystem JSON servlet (docs/monitoring/metrics.md:8 — lead:5050/
metrics/json), and SnappyMetricsSystem's 5s gauge push
(cluster/.../metrics/SnappyMetricsSystem.scala:36-212).

Timers are HISTOGRAMS, not min/max pairs: every recorded duration lands
in a log-spaced bucket (4 buckets per octave from 1µs), so every timer
reports p50/p99/p99.9 in snapshots and proper histogram exposition —
means hide exactly the tail contention "Global Hash Tables Strike
Back!" shows group-bys developing under concurrency.
"""

from __future__ import annotations

import json
import math
import threading
from snappydata_tpu.utils import locks
import time
import zlib
from collections import defaultdict
from typing import Callable, Dict, List, Optional

# log-bucket geometry: bucket 0 holds (0, 1µs]; bucket i>0 holds
# (1µs·r^(i-1), 1µs·r^i] with r = 2^(1/4) (4 buckets/octave ⇒ worst-case
# quantile error ~19% before intra-bucket interpolation); 142 buckets
# reach ~4.4e4 s — anything beyond clamps into the last bucket, whose
# upper edge is the observed max.
_H_MIN = 1e-6
_H_RATIO = 2.0 ** 0.25
_H_LOG_R = math.log(_H_RATIO)
_H_BUCKETS = 142


def _bucket_index(seconds: float) -> int:
    if seconds <= _H_MIN:
        return 0
    return min(_H_BUCKETS - 1,
               1 + int(math.log(seconds / _H_MIN) / _H_LOG_R))


def _bucket_upper(i: int) -> float:
    return _H_MIN * (_H_RATIO ** i)


class Timer:
    """Log-bucketed latency histogram (plus exact count/sum/min/max)."""

    __slots__ = ("count", "total_s", "min_s", "max_s", "buckets")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.buckets: Optional[List[int]] = None   # lazy: many timers idle

    def record(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.min_s = min(self.min_s, seconds)
        self.max_s = max(self.max_s, seconds)
        if self.buckets is None:
            self.buckets = [0] * _H_BUCKETS
        self.buckets[_bucket_index(seconds)] += 1

    def quantile(self, q: float) -> float:
        """Histogram quantile with linear intra-bucket interpolation,
        clamped to the exact observed [min, max]."""
        if not self.count or self.buckets is None:
            return 0.0
        target = q * self.count
        cum = 0.0
        for i, c in enumerate(self.buckets):
            if not c:
                continue
            if cum + c >= target:
                lo = 0.0 if i == 0 else _bucket_upper(i - 1)
                hi = min(_bucket_upper(i), self.max_s) \
                    if i < _H_BUCKETS - 1 else self.max_s
                hi = max(hi, lo)
                frac = (target - cum) / c
                v = lo + (hi - lo) * frac
                return min(max(v, self.min_s), self.max_s)
            cum += c
        return self.max_s

    def to_dict(self) -> dict:
        return {
            "count": self.count,
            "total_s": round(self.total_s, 6),
            "mean_s": round(self.total_s / self.count, 6) if self.count else 0,
            "min_s": round(self.min_s, 6) if self.count else 0,
            "max_s": round(self.max_s, 6),
            "p50_s": round(self.quantile(0.50), 6),
            "p99_s": round(self.quantile(0.99), 6),
            "p999_s": round(self.quantile(0.999), 6),
        }

    def prometheus_buckets(self) -> List:
        """(upper_bound_seconds, cumulative_count) pairs at per-OCTAVE
        boundaries (every 4th fine bucket), stopping at the first bound
        covering max_s — compact, still a valid cumulative histogram."""
        out = []
        if self.buckets is None:
            return out
        cum = 0
        for i in range(0, _H_BUCKETS, 4):
            cum += sum(self.buckets[i:i + 4])
            ub = _bucket_upper(i + 3)
            out.append((ub, cum))
            if ub >= self.max_s:
                break
        return out


class _TimeCtx:
    __slots__ = ("registry", "name", "t0")

    def __init__(self, registry, name):
        self.registry = registry
        self.name = name

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        # locklint: metric-dynamic plumbing: the name was validated by
        # the lint at the .time(name) call site that built this ctx
        self.registry.record_time(self.name, time.time() - self.t0)
        return False


class MetricsRegistry:
    def __init__(self):
        self._lock = locks.named_lock("observability.metrics_registry")
        self._counters: Dict[str, int] = defaultdict(int)
        self._gauges: Dict[str, Callable[[], float]] = {}
        # counters kept outside the registry by code that may not take
        # its lock (the collector's hook), read with the others
        self._counter_fns: Dict[str, Callable[[], int]] = {}
        self._timers: Dict[str, Timer] = defaultdict(Timer)

    def inc(self, name: str, value: int = 1) -> None:
        with self._lock:
            self._counters[name] += value

    def gauge(self, name: str, fn: Callable[[], float]) -> None:
        with self._lock:
            self._gauges[name] = fn

    def counter_fn(self, name: str, fn: Callable[[], int]) -> None:
        """A counter whose value `fn` keeps: it takes no lock and reads
        a plain int, and it is reported among the counters."""
        with self._lock:
            self._counter_fns[name] = fn

    def _counts(self) -> Dict[str, int]:
        """Every counter, those kept outside included (lock held)."""
        out = dict(self._counters)
        for name, fn in self._counter_fns.items():
            out[name] = fn()
        return out

    def time(self, name: str):
        # one prebuilt context class: defining it per call cost ~20µs of
        # __build_class__ on every timed query (visible on the serving
        # short-query profile)
        return _TimeCtx(self, name)

    def record_time(self, name: str, seconds: float) -> None:
        with self._lock:
            self._timers[name].record(seconds)

    def counter(self, name: str) -> int:
        with self._lock:
            fn = self._counter_fns.get(name)
            return fn() if fn is not None else self._counters.get(name, 0)

    def counters_snapshot(self) -> Dict[str, int]:
        """Counters only — the cheap delta-capture surface EXPLAIN
        ANALYZE and the bench use (no gauge evaluation)."""
        with self._lock:
            return self._counts()

    def snapshot(self) -> dict:
        # gauge callables run OUTSIDE the lock: a gauge that touches the
        # registry (broker.ledger() refreshing a gauge cache via inc())
        # used to self-deadlock on this non-reentrant lock
        with self._lock:
            gauge_fns = list(self._gauges.items())
            counters = self._counts()
            timers = {k: t.to_dict() for k, t in self._timers.items()}
        gauges = {}
        for name, fn in gauge_fns:
            try:
                gauges[name] = fn()
            except Exception:
                gauges[name] = None
        return {
            "counters": counters,
            "gauges": gauges,
            "timers": timers,
            "ts": time.time(),
        }

    def to_json(self) -> str:
        return json.dumps(self.snapshot())

    def to_prometheus(self) -> str:
        """Prometheus text exposition (the modern sink next to the
        reference's JSON/JMX/CSV/Graphite list): # HELP/# TYPE lines,
        collision-proof sanitized names, histogram buckets + quantile
        gauges for every timer."""
        with self._lock:
            counters = self._counts()
            gauge_fns = list(self._gauges.items())
            timers = {k: (t.to_dict(), t.prometheus_buckets())
                      for k, t in self._timers.items()}
        gauges = {}
        for name, fn in gauge_fns:
            try:
                gauges[name] = fn()
            except Exception:
                gauges[name] = None
        lines: List[str] = []
        used: Dict[str, str] = {}
        for k, v in sorted(counters.items()):
            base = f"snappy_tpu_{_prom_name(k, used)}_total"
            lines.append(f"# HELP {base} counter {k}")
            lines.append(f"# TYPE {base} counter")
            lines.append(f"{base} {v}")
        for k, v in sorted(gauges.items()):
            if v is None:
                continue
            base = f"snappy_tpu_{_prom_name(k, used)}"
            lines.append(f"# HELP {base} gauge {k}")
            lines.append(f"# TYPE {base} gauge")
            lines.append(f"{base} {v}")
        for k, (d, buckets) in sorted(timers.items()):
            base = f"snappy_tpu_{_prom_name(k, used)}_seconds"
            lines.append(f"# HELP {base} timer {k}")
            lines.append(f"# TYPE {base} histogram")
            for ub, cum in buckets:
                lines.append(f'{base}_bucket{{le="{ub:.9g}"}} {cum}')
            lines.append(f'{base}_bucket{{le="+Inf"}} {d["count"]}')
            lines.append(f"{base}_sum {d['total_s']}")
            lines.append(f"{base}_count {d['count']}")
            # quantiles as a sibling gauge family (mixing quantile
            # series into a histogram family is invalid exposition)
            qbase = f"{base}_q"
            lines.append(f"# TYPE {qbase} gauge")
            for label, key in (("0.5", "p50_s"), ("0.99", "p99_s"),
                               ("0.999", "p999_s")):
                lines.append(f'{qbase}{{quantile="{label}"}} {d[key]}')
        return "\n".join(lines) + "\n"


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def _prom_name(raw: str, used: Dict[str, str]) -> str:
    """Sanitized metric name, collision-proof: two DISTINCT raw names
    mapping to one sanitized form ("a.b" vs "a_b") used to silently
    overwrite each other — the later one now gets a deterministic crc
    suffix instead."""
    s = _sanitize(raw)
    owner = used.get(s)
    if owner is None or owner == raw:
        used[s] = raw
        return s
    s2 = f"{s}_{zlib.crc32(raw.encode('utf-8')) & 0xffff:04x}"
    used[s2] = raw
    return s2


_global = MetricsRegistry()


def global_registry() -> MetricsRegistry:
    return _global

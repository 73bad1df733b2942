"""The share of the window's seconds spent in statements slower than
five times the median latency of their own name (`spread.py`'s rule,
which reads the same from a run's `series` line), answered or not; of
the given `kinds` alone where given. A window with none reads 0."""

import spread


def read(ctx, kinds=None):
    slow = spread.slow_statements(
        (r["name"], r["ms"]) for r in ctx["statements"]
        if kinds is None or r["kind"] in kinds)
    if not slow or not ctx.get("window_s"):
        return None
    return sum(s["slow_ms"] for s in slow.values()) / 1e3 / ctx["window_s"]

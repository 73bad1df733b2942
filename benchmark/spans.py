"""Arithmetic on the program's span trees (`tracing.ring()`, as
`Trace.to_dict()` gives them: `{"name", "ms", "children"}`).

A span's self time is its duration less what its children cover.
`Trace.phase_seconds` adds nested spans of one name twice over; nothing
here does.
"""

from __future__ import annotations

import statistics

import numpy as np


def self_ms(span: dict) -> float:
    return max(0.0, float(span.get("ms", 0.0))
               - sum(float(c.get("ms", 0.0))
                     for c in span.get("children", ())))


def walk(span: dict):
    yield span
    for c in span.get("children", ()):
        yield from walk(c)


def self_ms_by_name(root: dict) -> dict:
    """Span name -> summed self time over the whole tree, the root left
    out (what it does not hand to a child is the statement's own)."""
    out = {}
    for sp in walk(root):
        if sp is not root:
            out[sp["name"]] = out.get(sp["name"], 0.0) + self_ms(sp)
    return out


def count(root: dict, names) -> int:
    return sum(1 for sp in walk(root) if sp is not root
               and sp["name"] in names)


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def percentile(values, q: float):
    """The q-th percentile by linear interpolation between order
    statistics, over every value given."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None

"""Reduction of a profiler trace to device numbers.

`extract` reads an `.xplane.pb` with `jax.profiler.ProfileData` into plain
lists; `reduce` is arithmetic on those lists alone, so it is checked on a
small recorded trace (`tests/fixtures/trace_events.json`) without a chip.

Busy time is the union of the intervals in which an operation ran on a
device (its `XLA Ops` line), clipped to the traced window and averaged
over the devices used. The window is the harness's own annotation
`bench:window`, which the host tracer writes on the same clock. Idle gaps
are the complement on the first device, each named by the harness
annotation (`bench:<statement>`) that was open at its middle, or
`between` where none was. Where the caller lays a statement's spans out
from its mark (`phases`, milliseconds from the mark's start), the name
also says which span was open: `q6/bind`.
"""

from __future__ import annotations

import glob
import os

WINDOW = "bench:window"
PREFIX = "bench:"
OPS_LINE = "XLA Ops"
NAME_CHARS = 120    # an op's name is its whole HLO line: the head says which


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
        "host": [[name, start_ns, dur_ns], ...],   # bench:* annotations
        "lines": {plane: [line names]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            evs = [[ev.name[:NAME_CHARS], int(ev.start_ns),
                    int(ev.duration_ns)]
                   for ln in ops for ev in ln.events]
            if evs:
                out["devices"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(events: dict, top: int = 10, phases=None) -> dict:
    """busy_s, window_s, device_ops and idle_gaps from `extract`'s lists.
    `phases[i]` lists `[name, start_ms, dur_ms]` for the i-th statement
    mark in time order. Raises where no operation ran on a device inside
    the window."""
    host = events.get("host", [])
    win = [h for h in host if h[0] == WINDOW]
    dev_all = [e for evs in events["devices"].values() for e in evs]
    if not dev_all:
        raise ValueError("no device operation in the trace")
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
    else:
        w0 = min(e[1] for e in dev_all)
        w1 = max(e[1] + e[2] for e in dev_all)
    busy, per_op, first_union = [], {}, None
    for plane in sorted(events["devices"]):
        clipped = []
        for name, s, d in events["devices"][plane]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append([a, b])
                per_op[name] = per_op.get(name, 0) + (b - a)
        merged = _union(clipped)
        if first_union is None:
            first_union = merged
        busy.append(sum(e - s for s, e in merged))
    n_dev = len(busy)
    busy_s = sum(busy) / n_dev / 1e9
    if busy_s <= 0:
        raise ValueError("no device operation inside the traced window")
    # per_op sums nested ops too; it ranks, it is not a share of busy
    device_ops = [[n, t / n_dev / 1e9] for n, t in
                  sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]
    marks = sorted((h for h in host if h[0] != WINDOW), key=lambda h: h[1])
    gaps, prev = {}, w0
    for s, e in first_union + [[w1, w1]]:
        if s > prev:
            mid = (prev + s) // 2
            label = "between"
            for i, (name, hs, hd) in enumerate(marks):
                if hs <= mid < hs + hd:
                    label = name[len(PREFIX):]
                    at = (mid - hs) / 1e6
                    for ph, p0, pd in (phases[i] if phases
                                       and i < len(phases) else ()):
                        if p0 <= at < p0 + pd:
                            label += "/" + ph
                            break
                    break
            g = gaps.setdefault(label, [0, 0])
            g[0] += s - prev
            g[1] = max(g[1], s - prev)
        prev = max(prev, e)
    idle_gaps = [[f"{n} (longest {g[1] / 1e6:.3f} ms)", g[0] / 1e9]
                 for n, g in sorted(gaps.items(),
                                    key=lambda kv: -kv[1][0])[:top]]
    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "devices": n_dev, "device_ops": device_ops,
            "idle_gaps": idle_gaps}

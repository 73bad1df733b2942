"""Median, over the window's statements of the given `kinds`, of the
root span of the trace on the system's side less the union of its
children's intervals (by `start_ms`): the statement's host time that no
span names.

A statement counts where its root holds a child called one of `within`
(a span the program opens on every statement of these kinds), so that a
program whose spans do not tile the root, as before the spans named in
`within` existed, reads None and not a number that means something
else."""

import spans


def _uncovered_ms(root: dict) -> float:
    total = float(root["ms"])
    covered, at = 0.0, 0.0
    for c in sorted(root.get("children", ()),
                    key=lambda c: float(c.get("start_ms", 0.0))):
        lo = max(float(c.get("start_ms", 0.0)), at)
        hi = min(float(c.get("start_ms", 0.0)) + float(c["ms"]), total)
        if hi > lo:
            covered += hi - lo
            at = hi
    return max(0.0, total - covered)


def read(ctx, kinds, within):
    per_statement = []
    for r in ctx["statements"]:
        if r["kind"] not in kinds or not r["ok"]:
            continue
        for tr in r.get("traces", ()):
            if tr["kind"] == ctx["back"] and any(
                    c["name"] in within
                    for c in tr["root"].get("children", ())):
                per_statement.append(_uncovered_ms(tr["root"]))
    return spans.median(per_statement)

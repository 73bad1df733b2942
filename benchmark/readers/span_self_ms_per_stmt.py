"""Summed self time of the spans called `names`, over the window's
statements of the given `kinds`, divided by the number of those
statements: what a rare, long span (a row buffer's roll-over) costs the
average statement of its kind, which a median hides.

A statement counts where its trace on the system's side holds a span of
`within` (the span the named ones are children of), so that a program
that opens no such span reads None and one that does reads a number, 0
where none of the named spans ran."""

import spans


def read(ctx, names, kinds, within):
    total, n = 0.0, 0
    for r in ctx["statements"]:
        if r["kind"] not in kinds or not r["ok"]:
            continue
        by_name = {}
        for tr in r.get("traces", ()):
            if tr["kind"] == ctx["back"]:
                for k, v in spans.self_ms_by_name(tr["root"]).items():
                    by_name[k] = by_name.get(k, 0.0) + v
        if any(w in by_name for w in within):
            n += 1
            total += sum(by_name.get(k, 0.0) for k in names)
    return total / n if n else None

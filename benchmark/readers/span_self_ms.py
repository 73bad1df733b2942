"""Median, over the window's statements of the given kinds, of the summed
self time of the named spans in the statement's trace on the system's
side (the server's, where the statement was served). With `after`, only
statements that directly follow one of those kinds: the first query
after a write."""

import spans


def read(ctx, names, kinds, after=None):
    per_statement, prev = [], None
    for r in ctx["statements"]:
        follows, prev = prev, r["kind"]
        if r["kind"] not in kinds or not r["ok"]:
            continue
        if after is not None and follows not in after:
            continue
        for tr in r.get("traces", ()):
            if tr["kind"] == ctx["back"]:
                by_name = spans.self_ms_by_name(tr["root"])
                if any(n in by_name for n in names):
                    per_statement.append(sum(by_name.get(n, 0.0)
                                             for n in names))
    return spans.median(per_statement)

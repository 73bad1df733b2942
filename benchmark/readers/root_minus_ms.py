"""Median, over the window's queries, of the root span of the trace on
the system's side less the self times of the spans in `minus`: what is
left of the statement once the named layers are taken out."""

import spans


def read(ctx, minus):
    per_statement = []
    for r in ctx["statements"]:
        if "answer" not in r or not r["ok"]:
            continue
        for tr in r.get("traces", ()):
            if tr["kind"] == ctx["back"]:
                by_name = spans.self_ms_by_name(tr["root"])
                per_statement.append(max(0.0, float(tr["root"]["ms"]) - sum(
                    by_name.get(n, 0.0) for n in minus)))
    return spans.median(per_statement)

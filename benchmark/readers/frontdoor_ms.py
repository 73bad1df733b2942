"""Median, over the window's statements of the given `kinds`, of the
root of the trace at the cell's front door (the client's) less the root
of the trace on the system's side (the server's) under the same trace
id: the wire, the RPC layer and the client's decode.

A statement counts where the server's root holds a child called one of
`within` (the span that puts the server's encode inside its root), so a
program whose server trace leaves part of its own work outside the root
reads None. An entry whose front and back are one trace reads None."""

import spans


def read(ctx, kinds, within):
    if ctx["front"] == ctx["back"]:
        return None
    per_statement = []
    for r in ctx["statements"]:
        if r["kind"] not in kinds or not r["ok"]:
            continue
        front = {tr["trace_id"]: tr for tr in r.get("traces", ())
                 if tr["kind"] == ctx["front"] and "trace_id" in tr}
        for tr in r.get("traces", ()):
            fr = front.get(tr.get("trace_id"))
            if tr["kind"] != ctx["back"] or fr is None:
                continue
            if any(c["name"] in within
                   for c in tr["root"].get("children", ())):
                per_statement.append(max(0.0, float(fr["root"]["ms"])
                                         - float(tr["root"]["ms"])))
    return spans.median(per_statement)

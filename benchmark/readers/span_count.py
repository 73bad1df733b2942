"""How many spans of the given names the window's statements opened on
the system's side. None where no statement carried a trace."""

import spans


def read(ctx, names):
    n, seen = 0, False
    for r in ctx["statements"]:
        for tr in r.get("traces", ()):
            if tr["kind"] == ctx["back"]:
                seen = True
                n += spans.count(tr["root"], names)
    return n if seen else None

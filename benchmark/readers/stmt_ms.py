"""Median client-side latency of the window's statements named
`statement`, by the host's clock."""

import spans


def read(ctx, statement):
    return spans.median([r["ms"] for r in ctx["statements"]
                         if r["name"] == statement and r["ok"]])

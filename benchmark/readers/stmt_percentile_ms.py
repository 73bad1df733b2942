"""The q-th percentile of the client-side latency of all the window's
statements of the given kinds, answered or not."""

import spans


def read(ctx, q, kinds):
    return spans.percentile([r["ms"] for r in ctx["statements"]
                             if r["kind"] in kinds], q)

"""Rows counted under `field` by every statement of the window that was
answered and correct, over the whole window's seconds."""


def read(ctx, field):
    return sum(r.get(field) or 0 for r in ctx["statements"]
               if r["ok"]) / ctx["window_s"]

"""The least time the chip could take to read what the queries of the
traced window had to read (rows x the mix's bytes per row, over the HBM
peak), over the seconds the device was busy in that window."""

from roofline import logical_bytes, roofline_pct


def read(ctx):
    if ctx["device"] is None:
        return None
    traced = [r for r in ctx["statements"] if r["traced"] and r["ok"]]
    return roofline_pct(logical_bytes(traced), ctx["device"]["busy_s"],
                        ctx["peaks"])

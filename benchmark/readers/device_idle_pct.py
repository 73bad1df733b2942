"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    dev = ctx["device"]
    if dev is None or not dev["window_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])

"""Process start to the window's first statement: data, load, warm-up
and, in a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]

"""Rows of several tables under one range of keys (RF2): the statement's
`generator` module gives the k-th range of a run (`key_range(sf, k, n)`
-> [lo, hi), the `per_sf` x SF lowest keys still live), and each of
`deletes` sends its `sql` with (lo, hi) bound and names the `table` and
key `column` for the reference."""

from traffic import Statement


def make(traffic, name, spec, k, warmup):
    k = traffic.made(name) - 1
    gen = traffic.manifest.module("generators", spec["generator"])
    n = max(1, int(round(spec["per_sf"] * traffic.sf)))
    return Statement(name, "delete_range", spec,
                     keys=gen.key_range(traffic.sf, k, n))


def columns(manifest, spec) -> dict:
    return {d["table"]: [d["column"]] for d in spec["deletes"]}


def send(engine, st, rec, rows) -> None:
    for d in st.spec["deletes"]:
        engine.execute(d["sql"], list(st.keys))


def apply(world, st, keep) -> None:
    for d in st.spec["deletes"]:
        world.delete_range(d["table"], d["column"], *st.keys)

"""New rows for several tables at once (RF1): the statement's
`generator` module makes the k-th batch of a run, `per_sf` x SF parent
rows with their children (`refresh(sf, seed, k, n)` -> table ->
columns), and each of `tables` is inserted in that order."""

from traffic import Statement


def count(spec, sf) -> int:
    return max(1, int(round(spec["per_sf"] * sf)))


def make(traffic, name, spec, k, warmup):
    # warm-up batches are the run's first: they stay in the tables
    k = traffic.made(name) - 1
    gen = traffic.manifest.module("generators", spec["generator"])
    return Statement(name, "insert_rows", spec, data=gen.refresh(
        traffic.sf, traffic.seed, k, count(spec, traffic.sf)))


def columns(manifest, spec) -> dict:
    return {}


def send(engine, st, rec, rows) -> None:
    rec["rows_written"] = 0
    for table in st.spec["tables"]:
        engine.insert(table, st.data[table])
        n = len(next(iter(st.data[table].values())))
        rows[table] += n
        rec["rows_written"] += n


def apply(world, st, keep) -> None:
    for table in st.spec["tables"]:
        world.insert(table, {c: st.data[table][c]
                             for c in keep.get(table, ())})
    st.data = None

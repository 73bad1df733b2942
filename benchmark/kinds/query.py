"""A query: its `sql` with `{name}` places for text substitution or `?`
places filled from `bind`, its `draws` (one list of choices per
substitution parameter, each choice a dictionary of the values it sets,
or `{"int_range": [lo, hi], "name": n}` for whole numbers), the
`reference` module that answers it, the `table` whose rows it reads and
the logical `bytes_per_row` it has to touch."""

from traffic import Statement


def make(traffic, name, spec, k, warmup):
    combos = traffic.draws(name)
    # warm-up takes from the far end of the permutation, so that the
    # window does not start on values a cache has just seen
    subst = combos[-(k % len(combos)) - 1] if warmup \
        else combos[k % len(combos)]
    return Statement(name, "query", spec, sql=spec["sql"].format(**subst),
                     params=[subst[b] for b in spec.get("bind", ())],
                     subst=subst)


def columns(manifest, spec) -> dict:
    return manifest.module("references", spec["reference"]).COLUMNS


def send(engine, st, rec, rows) -> None:
    rec["rows_read"] = rows[st.spec["table"]]
    rec["bytes_per_row"] = st.spec.get("bytes_per_row")
    rec["answer"] = engine.query(st.sql, st.params)


def expected(world, st, rec) -> list:
    # the harness does not see how many rows a delete removed
    rec["rows_read"] = world.rows[st.spec["table"]]
    return world.answer(st.spec["reference"], st.subst)

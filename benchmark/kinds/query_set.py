"""A query whose answer is a set of rows: what `query` is (its `sql`,
`draws`, `bind`, `reference`, `table` and `bytes_per_row`), for a
statement without an ORDER BY. `compare` is positional, and a GROUP BY
promises no order, so before the two answers meet, the program's rows
and the reference's are each put in the order of their first `key_columns`
columns (the group key; 1 where the mix does not say). Nothing else is
forgiven: a missing or doubled group shifts or lengthens the rows and is
an exact mismatch, and every value is compared as `query` compares it.
"""

import query
from query import columns, send  # noqa: F401  (the kind's interface)


def make(traffic, name, spec, k, warmup):
    st = query.make(traffic, name, spec, k, warmup)
    st.kind = "query_set"
    return st


def _by_key(rows, spec) -> list:
    width = int(spec.get("key_columns", 1))
    # NULL keys first, then by value: a total order over one column type
    return sorted(rows, key=lambda r: tuple((v is not None, v)
                                            for v in r[:width]))


def expected(world, st, rec) -> list:
    """Relies on `run.replay`'s order: it asks for `expected` and then
    compares `rec["answer"]` with it, so the program's rows are put in
    key order here. The rows in the order the program sent them stay
    under `answer_as_sent`."""
    if "answer" in rec:
        rec["answer_as_sent"] = rec["answer"]
        rec["answer"] = _by_key(rec["answer"], st.spec)
    return _by_key(query.expected(world, st, rec), st.spec)

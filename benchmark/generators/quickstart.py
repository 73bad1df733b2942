"""The table of the upstream quick-start benchmark (SnappyData 1.3.0,
`docs/quickstart/performance_apache_spark.md`, `Quickstart.scala`): the
source makes it as `range(N)` with `sym = concat('sym', cast((id % 100)
as string))`, so it has no randomness and `--seed` changes nothing in it:

- `id`: 0..N-1 in insert order, int64; N is SF x 100,000,000;
- `sym`: `'sym' + str(id % 100)`, the 100 strings of the source.

`sym` is an object array whose elements are the 100 strings themselves
(one pointer a row), which is what `insert_arrays` takes for a string
column; nothing is kept between calls.
"""

from __future__ import annotations

import numpy as np

ROWS_PER_SF = 100_000_000
SYMS = 100


def n_rows(sf: float) -> int:
    return max(SYMS, int(ROWS_PER_SF * sf))


def generate(table: str, sf: float, seed: int) -> dict:
    """The loaded table at scale `sf`: column name -> array, in the DDL's
    order."""
    if table != "testtable":
        raise KeyError(f"generator quickstart makes testtable, not "
                       f"{table!r}")
    ids = np.arange(n_rows(sf), dtype=np.int64)
    names = np.array([f"sym{k}" for k in range(SYMS)], dtype=object)
    return {"id": ids, "sym": names[ids % SYMS]}

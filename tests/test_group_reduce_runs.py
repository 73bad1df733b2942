"""The run reduce (`ops/reduction.py` `run_reduce`, and the executor's
`_run_head_key`) against the scatter formula it replaced in the generic
branch: `segment_sum` / `segment_min` / `segment_max` over the group
index, and each key as the `segment_max` of its column.  Counts, keys and
their NULL flags, int64 sums (wrap-around included) and min/max (the
fillers on empty groups included) are the same bit for bit; float64 sums
to 1e-12 of the group's size, since a run adds its rows in another order
than the scatter.  The cases are those of tests/test_group_index_runs.py,
whose index the run reduce reads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.engine.executor import (DVal, _run_head_index,
                                            _run_head_key)
from snappydata_tpu.observability import tracing
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.ops import reduction
from snappydata_tpu.ops.join import combine_key_arrays

N = 4096


def _distinct(rng, count):
    vals = rng.choice(2 ** 23, size=count, replace=False) * 2 ** 40 - 2 ** 62
    keys = np.concatenate([vals, rng.choice(vals, N - count)])
    valid = np.concatenate([np.ones(count, bool),
                            rng.random(N - count) < 0.7])
    order = rng.permutation(N)
    return keys[order], valid[order]


def _random(rng):
    keys = rng.integers(-2 ** 63, 2 ** 63 - 1, 300, dtype=np.int64)
    return rng.choice(keys, N), rng.random(N) < 0.6, None, 1024


def _nulls_in_a_multi_key_combine(rng):
    a = rng.integers(0, 40, N).astype(np.int64)
    b = rng.integers(0, 7, N).astype(np.int32)
    a_null = rng.random(N) < 0.1
    keys = np.asarray(combine_key_arrays([(jnp.asarray(a),
                                           jnp.asarray(a_null)),
                                          (jnp.asarray(b), None)]))
    return keys, rng.random(N) < 0.8, a_null, 1024


def _all_invalid(rng):
    return rng.integers(0, 9, N).astype(np.int64), np.zeros(N, bool), None, 512


def _one_group(rng):
    return np.full(N, -17, np.int64), rng.random(N) < 0.5, None, 512


def _exactly_num_groups(rng):
    return _distinct(rng, 256) + (None, 256)


def _one_past_num_groups(rng):
    return _distinct(rng, 257) + (None, 256)


CASES = [_random, _nulls_in_a_multi_key_combine, _all_invalid, _one_group,
         _exactly_num_groups, _one_past_num_groups]


def _columns(rng, valid):
    """The families as run_main masks them: a float64 sum, an int64 sum
    whose groups wrap, a count mask of a nullable column, a float64 min
    and an int32 max, each masked into its identity."""
    null = rng.random(N) < 0.2
    w = valid & ~null
    f = np.where(valid, rng.normal(0, 1e5, N), 0.0)
    i = np.where(valid, rng.integers(2 ** 62, 2 ** 63 - 1, N), 0)
    mn = np.where(w, rng.normal(0, 1e3, N), np.inf)
    mx = np.where(valid, rng.integers(-2 ** 31, 2 ** 31 - 1, N),
                  np.iinfo(np.int32).min).astype(np.int32)
    return ([jnp.asarray(f), jnp.asarray(i, jnp.int64),
             jnp.asarray(w.astype(np.int32)), jnp.asarray(mn),
             jnp.asarray(mx)], ["sum", "sum", "sum", "min", "max"])


def _scatter(cols, kinds, gidx, num_groups):
    seg = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
           "max": jax.ops.segment_max}
    return [np.asarray(seg[k](c, gidx, num_segments=num_groups))
            for c, k in zip(cols, kinds)]


def _segment_key(value, null, valid, gidx, num_groups):
    """A generic key as the scatter read it, as it was written (an empty
    group read the filler, and a NULL flag of True from it: no statement
    reads either, its output row being masked by its count)."""
    filler = jnp.iinfo(value.dtype).min
    k_arr = jax.ops.segment_max(jnp.where(valid, value, filler), gidx,
                                num_segments=num_groups + 1)[:num_groups]
    k_null = None
    if null is not None:
        k_null = jax.ops.segment_max(
            (null & valid).astype(jnp.int32), gidx,
            num_segments=num_groups + 1)[:num_groups].astype(bool)
    return k_arr, k_null


@pytest.mark.parametrize("case", CASES, ids=[c.__name__[1:] for c in CASES])
def test_run_reduce_gives_the_scatters_answers(case):
    rng = np.random.default_rng(39)
    keys, valid, null, num_groups = case(rng)
    keys, valid = jnp.asarray(keys, jnp.int64), jnp.asarray(valid)
    gidx, overflow = _run_head_index(keys, valid, num_groups)
    cols, kinds = _columns(rng, np.asarray(valid))
    runs = jax.jit(reduction.run_reduce, static_argnums=(1, 3))(
        gidx, num_groups, cols, tuple(kinds))
    want = _scatter(cols, kinds, gidx, num_groups)
    got = [np.asarray(t) for t in runs.tails]
    for g, w_, k in zip(got, want, kinds):
        assert g.dtype == w_.dtype and g.shape == (num_groups,)
    # the float64 sum to 1e-12 of the group's absolute size; the rest
    # bit for bit (int64 sums past 2**63 wrap alike)
    scale = np.asarray(jax.ops.segment_sum(jnp.abs(cols[0]), gidx,
                                           num_segments=num_groups))
    assert (np.abs(got[0] - want[0]) <= 1e-12 * np.maximum(scale, 1)).all()
    for g, w_ in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(g, w_)
    counts = np.asarray(jax.ops.segment_sum(valid.astype(jnp.int32), gidx,
                                            num_segments=num_groups))
    np.testing.assert_array_equal(np.asarray(runs.counts), counts)
    # empty groups read the identity: 0, +inf, the int32 minimum
    empty = counts == 0
    assert (got[1][empty] == 0).all() and (got[3][empty] == np.inf).all()
    assert (got[4][empty] == np.iinfo(np.int32).min).all()
    # the keys at the run heads, and their NULL flags, of every group
    # that has a row
    key_null = None if null is None else jnp.asarray(null)
    k_arr, k_null = _run_head_key(DVal(keys, key_null, None), valid, runs)
    old_arr, old_null = _segment_key(keys, key_null, valid, gidx, num_groups)
    np.testing.assert_array_equal(np.asarray(k_arr)[~empty],
                                  np.asarray(old_arr)[~empty])
    if null is None:
        assert k_null is None
    else:
        assert np.asarray(old_null)[~empty].any()
        np.testing.assert_array_equal(np.asarray(k_null)[~empty],
                                      np.asarray(old_null)[~empty])
    # past the slots the flag still sends the statement to the host
    assert bool(overflow) == (case is _one_past_num_groups)


def test_a_nan_and_an_inf_stay_in_their_group():
    rng = np.random.default_rng(7)
    gidx = jnp.asarray(np.sort(rng.integers(0, 50, N)).astype(np.int32))
    gidx = gidx[rng.permutation(N)]
    vals = rng.normal(0, 1, N)
    hot = np.flatnonzero(np.asarray(gidx) == 17)
    vals[hot[0]], vals[hot[-1]] = np.nan, np.inf
    cold = np.flatnonzero(np.asarray(gidx) == 18)
    vals[cold[0]] = np.inf
    runs = reduction.run_reduce(gidx, 64, [jnp.asarray(vals)], ["sum"])
    sums = np.asarray(runs.tails[0])
    assert np.isnan(sums[17]) and sums[18] == np.inf
    others = np.delete(np.arange(64), [17, 18])
    assert np.isfinite(sums[others]).all()
    want = np.asarray(jax.ops.segment_sum(jnp.asarray(vals), gidx,
                                          num_segments=64))
    assert np.allclose(sums[others], want[others], rtol=1e-12, atol=1e-12)


def test_segmented_scan_restarts_at_every_head():
    head = jnp.asarray([True, False, False, True, True, False, True])
    x = jnp.asarray([1, 2, 3, 10, 20, 5, -1], jnp.int64)
    (s,) = reduction.segmented_scan(head, [x], ["sum"])
    assert np.asarray(s).tolist() == [1, 3, 6, 10, 20, 25, -1]
    (m,) = reduction.segmented_scan(head, [x], ["max"])
    assert np.asarray(m).tolist() == [1, 2, 3, 10, 20, 20, -1]


@pytest.fixture
def props():
    p = config.global_properties()
    saved = (p.agg_reduce_strategy, p.decimal_as_float64, p.max_groups,
             p.tracing_enabled)
    p.tracing_enabled = True
    yield p
    (p.agg_reduce_strategy, p.decimal_as_float64, p.max_groups,
     p.tracing_enabled) = saved


def _main_dispatch_attrs():
    def spans(sp):
        yield sp
        for c in sp.get("children", ()):
            yield from spans(c)

    (sp,) = [sp for sp in spans(tracing.ring().last().to_dict()["root"])
             if sp["name"] in ("jit_compile", "device_execute")
             and sp["attrs"].get("phase", "main") == "main"]
    return sp["attrs"]


@pytest.mark.parametrize("strategy", ["auto", "scatter", "unroll"])
def test_a_generic_group_by_reduces_over_its_runs(props, strategy):
    """A GROUP BY of a BIGINT expression (a generic key: no value
    dictionary) and a nullable INT over 300 groups: every
    family that would scatter takes the runs (`run_reduce_slots`, and
    no `scatter_slots`), and the rows are NumPy's, the int64 sums
    wrapping as the scatter's did."""
    props.agg_reduce_strategy = strategy
    rng = np.random.default_rng(39)
    n = 20_000
    k = rng.integers(0, 100, n).astype(np.int64) * 1_000_003
    j_null = rng.random(n) < 0.05
    # a NULL's slot holds 0, as an INSERT of NULL leaves it: the hash of
    # a multi-key combine folds the slot's value in beside the flag
    j = np.where(j_null, 0, rng.integers(0, 3, n)).astype(np.int32)
    big = rng.integers(2 ** 61, 2 ** 62, n).astype(np.int64)
    x = np.round(rng.random(n) * 1e4, 2)
    x_null = rng.random(n) < 0.1
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k BIGINT, j INT, big BIGINT, x DOUBLE) "
              "USING column")
        s.catalog.describe("t").data.insert_arrays(
            [k, j, big, x], nulls=[None, j_null, None, x_null])
        rows = s.sql("SELECT k + 1, j, sum(x), sum(big), count(x), "
                     "count(*), min(x), max(big) FROM t "
                     "WHERE x IS NULL OR x > 5 GROUP BY k + 1, j").rows()
        attrs = _main_dispatch_attrs()
    finally:
        s.stop()
    assert attrs["gidx_run_lane"] == 1
    assert attrs["scatter_slots"] == 0 and attrs["run_reduce_slots"] >= 3
    keep = x_null | (x > 5)
    want = {}
    for key in set(zip(k[keep].tolist(),
                       np.where(j_null, -1, j)[keep].tolist())):
        m = keep & (k == key[0]) & (np.where(j_null, -1, j) == key[1])
        xs = x[m & ~x_null]
        with np.errstate(over="ignore"):
            total = int(big[m].sum())
        want[(key[0] + 1, None if key[1] == -1 else key[1])] = (
            float(xs.sum()), total, len(xs), int(m.sum()), float(xs.min())
            if len(xs) else None, int(big[m].max()))
    got = {(r[0], r[1]): tuple(r[2:]) for r in rows}
    assert set(got) == set(want)
    for key, (fs, isum, cx, cs, mn, mx) in want.items():
        g = got[key]
        assert abs(g[0] - fs) <= 1e-9 * max(abs(fs), 1)
        assert g[1:4] == (isum, cx, cs) and g[5] == mx
        assert g[4] == mn


def test_more_groups_than_slots_still_reruns_on_the_host(props):
    """300 keys in 256 slots: the index's overflow flag still comes home
    beside the run reduce's sums, and the statement reruns exactly on
    the host."""
    props.max_groups = 256
    props.agg_reduce_strategy = "scatter"
    n = 3000
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE t (k BIGINT, v DOUBLE) USING column")
        s.insert_arrays("t", [np.arange(n, dtype=np.int64) % 300,
                              np.ones(n)])
        before = global_registry().counter("host_fallbacks")
        rows = s.sql("SELECT k + 0, sum(v), count(*) FROM t "
                     "GROUP BY k + 0").rows()
        attrs = _main_dispatch_attrs()
    finally:
        s.stop()
    assert attrs["groups_overflow"] == 1 and attrs["run_reduce_slots"] >= 1
    assert global_registry().counter("host_fallbacks") == before + 1
    assert sorted(tuple(r) for r in rows) == [(g, 10.0, 10)
                                              for g in range(300)]

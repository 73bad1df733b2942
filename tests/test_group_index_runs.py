"""The generic group index by run heads over the sorted keys
(`engine/executor.py` `_run_head_index`) against the formula it replaced:
the sorted distinct keys by `jnp.unique`, each row's id by `searchsorted`
into them, overflow where the sentinel left the unique set.  The ids and
the flag are the same bit for bit, with two differences in what a
statement never reads: an id past the slots is clipped to `num_groups`
(the old formula could write `num_groups + 1` while the overflow flag was
on its way home), and a valid key equal to the sentinel beside
`num_groups` other keys is an overflow (the old formula gave it the
invalid rows' slot and lost its group without a flag).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from snappydata_tpu.engine.executor import _I64_MAX, _run_head_index
from snappydata_tpu.ops.join import combine_key_arrays

N = 4096


def _unique_searchsorted(keys, valid, num_groups):
    """The formula the run heads replaced, as it was written."""
    n = keys.shape[0]
    combined = jnp.where(valid, keys, _I64_MAX)
    uniq = jnp.unique(combined, size=num_groups + 1, fill_value=_I64_MAX)
    overflow = jnp.asarray(False)
    if num_groups < n:
        overflow = uniq[-1] != _I64_MAX
    gidx = jnp.searchsorted(uniq, combined)
    return jnp.where(valid, gidx, num_groups).astype(jnp.int32), overflow


def _distinct(rng, count, sentinel=False):
    """`count` distinct int64 keys over the whole range (one of them the
    sentinel where asked), each on at least one valid row, beside invalid
    rows with keys of their own."""
    vals = rng.choice(2 ** 23, size=count, replace=False) * 2 ** 40 - 2 ** 62
    if sentinel:
        vals[0] = _I64_MAX
    keys = np.concatenate([vals, rng.choice(vals, N - count)])
    valid = np.concatenate([np.ones(count, bool),
                            rng.random(N - count) < 0.7])
    order = rng.permutation(N)
    junk = rng.integers(-2 ** 63, 2 ** 63 - 1, N, dtype=np.int64)
    keys, valid = keys[order], valid[order]
    return np.where(valid, keys, junk), valid


def _random(rng):
    keys = rng.integers(-2 ** 63, 2 ** 63 - 1, 300, dtype=np.int64)
    keys = rng.choice(keys, N)
    return keys, rng.random(N) < 0.6, 1024


def _nulls_in_a_multi_key_combine(rng):
    a = rng.integers(0, 40, N).astype(np.int64)
    b = rng.integers(0, 7, N).astype(np.int32)
    a_null = rng.random(N) < 0.1
    keys = np.asarray(combine_key_arrays([(jnp.asarray(a),
                                           jnp.asarray(a_null)),
                                          (jnp.asarray(b), None)]))
    return keys, rng.random(N) < 0.8, 1024


def _all_invalid(rng):
    return rng.integers(0, 9, N).astype(np.int64), np.zeros(N, bool), 512


def _one_group(rng):
    return np.full(N, -17, np.int64), rng.random(N) < 0.5, 512


def _exactly_num_groups(rng):
    return _distinct(rng, 256) + (256,)


def _one_past_num_groups(rng):
    return _distinct(rng, 257) + (256,)


def _far_past_num_groups(rng):
    return _distinct(rng, 600) + (256,)


def _a_valid_key_at_the_sentinel(rng):
    return _distinct(rng, 100, sentinel=True) + (256,)


def _the_sentinel_beside_num_groups_keys(rng):
    return _distinct(rng, 257, sentinel=True) + (256,)


CASES = [_random, _nulls_in_a_multi_key_combine, _all_invalid, _one_group,
         _exactly_num_groups, _one_past_num_groups, _far_past_num_groups,
         _a_valid_key_at_the_sentinel, _the_sentinel_beside_num_groups_keys]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__[1:] for c in CASES])
def test_run_heads_give_the_ids_of_unique_and_searchsorted(case):
    rng = np.random.default_rng(37)
    keys, valid, num_groups = case(rng)
    keys, valid = jnp.asarray(keys, jnp.int64), jnp.asarray(valid)
    gidx, overflow = jax.jit(_run_head_index, static_argnums=2)(
        keys, valid, num_groups)
    old_gidx, old_overflow = _unique_searchsorted(keys, valid, num_groups)
    gidx, old_gidx = np.asarray(gidx), np.asarray(old_gidx)
    assert gidx.dtype == np.int32 and gidx.shape == (N,)
    assert overflow.dtype == np.bool_ and overflow.shape == ()
    np.testing.assert_array_equal(gidx, np.minimum(old_gidx, num_groups))
    # the old formula missed the group at the sentinel past the slots
    past = (_one_past_num_groups, _far_past_num_groups)
    assert bool(old_overflow) == (case in past)
    assert bool(overflow) == (case in past + (
        _the_sentinel_beside_num_groups_keys,))
    # every invalid row, and nothing else unless the slots overflowed,
    # reads the slot past the groups
    v = np.asarray(valid)
    assert (gidx[~v] == num_groups).all()
    if not overflow:
        assert (gidx[v] < num_groups).all()
        # dense: the ids of the valid rows are 0..groups-1, in key order
        ids = np.unique(gidx[v])
        assert (ids == np.arange(ids.size)).all()
        gid_of = dict(zip(np.asarray(keys)[v].tolist(), gidx[v].tolist()))
        assert sorted(gid_of, key=gid_of.get) == sorted(gid_of)
        assert len(gid_of) == ids.size
    else:
        assert gidx.max() == num_groups

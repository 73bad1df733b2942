"""Compressed-domain aggregate primitives (r07): SUM in DICTIONARY
space and SUM/COUNT in RUN space.

The "GPU Acceleration of SQL Analytics on Compressed Data" formulation:
a SUM over a dictionary-encoded column equals Σ_c count[c]·dict[c], so
the O(N) work touches only the small integer codes and the O(D)
contraction over the tiny dictionary replaces N value gathers.
Per-batch dictionaries make the cell space (batch, group, code).  The
counts are a per-batch product of two 0/1 one-hots, group index against
code, contracted over the batch's rows on the MXU: a scatter into the
same cells runs serially on the TPU (12.4 M updates a second measured
at SF 2, 86 % of the scan cell; PERF.md, PR 26), the product runs at
the rate the codes are read.  RLE goes further: with a per-run boolean
mask the filter and the reduction are both O(runs) arithmetic over
(value, length) pairs — see storage/device_decode.rle_masked_sum_count
for the single-plate form this generalizes.

Counts are exact integers; the contraction with the dictionaries is
float64 throughout, the same accumulator the packed fsum family uses.
Only summation ORDER differs (per-code partials instead of per-row), so
results agree with the decoded path to f64 reassociation — well inside
the 1e-9 relative band the equivalence tests and the bench assert.
Exact int64 accumulators (exact decimals, integer sums) must NOT use
these: Σ count·value in f64 rounds above 2^53.  Callers gate on the
accumulator dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from snappydata_tpu.observability import tracing
from snappydata_tpu.ops.reduction import divisor_step

# The one-hot product's work grows with groups × dictionary width (the
# scatter it replaced did not), so that product is the engagement bound:
# the largest at which the lane still beat what it displaces — one more
# dict_gather of the value plate plus the slot's share of a packed
# reduction — at every shape measured on the v5e over 12.58 M rows.  The
# narrowest shape is the costliest (8 padded groups: 40 ms at 4,096
# codes, 181 ms at 16,384, against 131 ms displaced; PERF.md section 6,
# PR 26).  Past it the slot rides the packed families as under
# agg_on_codes=off.
DICT_SPACE_MAX_PRODUCT = 1 << 16

# a per-batch cell counts at most `cap` rows in float32: exact below 2^24
DICT_SPACE_MAX_CAP = 1 << 24

# bytes of one-hot operands one step of the count may hold if XLA
# materialises them (the v5e compiler fuses them into the product and
# holds none): the batch axis is walked in chunks of this size, so the
# transient does not grow with the batch count
DICT_SPACE_CHUNK_BYTES = 256 << 20

_SUBLANES = 8


def _padded_groups(nseg: int) -> int:
    """Real groups (the dump segment is never counted), padded to the
    sublane tile the product's left operand is laid out in."""
    return -(-max(int(nseg) - 1, 1) // _SUBLANES) * _SUBLANES


def dict_space_engages(nseg: int, codes_shape, dicts_shape) -> bool:
    """The static engagement bound (every factor is a trace-time
    constant): padded groups × padded dictionary width, and a batch
    capacity whose per-cell counts stay exact in float32."""
    return (_padded_groups(nseg) * int(dicts_shape[1])
            <= DICT_SPACE_MAX_PRODUCT
            and int(codes_shape[1]) <= DICT_SPACE_MAX_CAP)


def _chunk_batches(b: int, cap: int, ngroups: int, dp: int) -> int:
    """Batches per step of the count: as many as DICT_SPACE_CHUNK_BYTES
    of bfloat16 one-hots allow (reduction.divisor_step)."""
    per_batch = cap * (ngroups + dp) * 2
    return divisor_step(b, max(1, min(b, DICT_SPACE_CHUNK_BYTES // per_batch)))


def _counts_of(codes, gidx, ngroups: int, dp: int):
    """counts[b, g, c] of a stack of batches: onehot(gidx)ᵀ · onehot(code)
    contracted over the batch's rows.  0/1 operands are exact in
    bfloat16 and the float32 accumulator is exact below 2^24 rows a
    cell.  A row whose gidx is outside [0, ngroups) is an all-zero
    one-hot row and counts nowhere."""
    oh_g = (gidx[:, None, :] == jnp.arange(
        ngroups, dtype=jnp.int32)[None, :, None]).astype(jnp.bfloat16)
    oh_c = (codes[:, None, :].astype(jnp.int32) == jnp.arange(
        dp, dtype=jnp.int32)[None, :, None]).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        oh_g, oh_c, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def dict_space_counts(codes, gidx, w, nseg: int, dp: int):
    """Row counts per (group, batch, code) cell, [nseg - 1, B, dp]
    float32 holding exact integers: rows with `w` false and rows on the
    dump segment (gidx == nseg - 1) count nowhere.

    The batch axis is walked in steps of _chunk_batches; each step
    slices its own rows out of the flat `gidx` and `w`, so whatever a
    step materialises (the masked group indexes, a relayout of the
    codes, the one-hots if the compiler does not fuse them) is a
    step's worth and does not grow with the batch count."""
    b, cap = codes.shape
    ngroups = nseg - 1
    step = _chunk_batches(b, cap, ngroups, dp)

    def counts_at(lo, nb: int):
        def rows(x):
            return jax.lax.dynamic_slice_in_dim(
                x, lo * cap, nb * cap).reshape(nb, cap)

        g = jnp.where(rows(w), rows(gidx).astype(jnp.int32), ngroups)
        return _counts_of(jax.lax.dynamic_slice_in_dim(codes, lo, nb),
                          g, ngroups, dp)

    nsteps, rest = divmod(b, step)
    if nsteps == 1:
        counts = counts_at(0, step)
    else:
        counts = jax.lax.map(
            lambda i: counts_at(i * step, step),
            jnp.arange(nsteps, dtype=jnp.int32)
        ).reshape(nsteps * step, ngroups, dp)
    if rest:
        counts = jnp.concatenate(
            [counts, counts_at(nsteps * step, rest)])
    return counts.transpose(1, 0, 2)


@tracing.op_scope("group_reduce")
def dict_space_sum(codes, dicts, gidx, w, nseg: int):
    """SUM over a VALUE_DICT column in dictionary space.

    codes: [B, cap] uint8/uint16 plate codes; dicts: [B, Dp] per-batch
    dictionaries (device dtype); gidx: [N] int32 flat group index with
    invalid rows already pointing at the dump segment; w: [N] bool row
    weights (valid & not-null).  Returns [nseg] float64 group sums; the
    dump segment's, which nothing reads, is 0.

    One O(N) pass over codes and group indexes (dict_space_counts),
    then an O(nseg·B·Dp) contraction with the dictionary stack — the
    decoded value plate is never gathered.
    """
    counts = dict_space_counts(codes, gidx, w, nseg, dicts.shape[1])
    # multiply-and-reduce, not a dot: the TPU runs an f64 dot through the
    # MXU at reduced precision (Q1's sum(l_quantity) over 24 M rows came
    # back 2.5e-6 off the exact integer on the v5e), while elementwise
    # f64 keeps the accumulator's width; the cell grid is small
    # (<= B * DICT_SPACE_MAX_PRODUCT)
    sums = jnp.sum(counts.astype(jnp.float64)
                   * dicts.astype(jnp.float64)[None], axis=(1, 2))
    return jnp.concatenate([sums, jnp.zeros(1, sums.dtype)])


@tracing.op_scope("group_reduce")
def run_space_sum_count(values, ends, run_mask):
    """Global SUM + COUNT over an RLE plate in run space.

    values/ends: [B, R] run values and cumulative end offsets; run_mask:
    [B, R] bool per-run survivors (the whole filter conjunction reduced
    in run space — the caller's alignment proof).  Returns (total
    float64 scalar, count int64 scalar): count = Σ len·mask, total =
    Σ value·len·mask — O(runs) arithmetic, no row-space expansion.
    Padded runs repeat the last end, so their length is exactly 0 and
    they contribute nothing regardless of their mask bit.
    """
    from snappydata_tpu.storage.device_decode import rle_run_lengths

    lens = rle_run_lengths(ends)
    lm = jnp.where(run_mask, lens, jnp.zeros_like(lens))
    count = jnp.sum(lm).astype(jnp.int64)
    total = jnp.sum(values.astype(jnp.float64) * lm)
    return total, count

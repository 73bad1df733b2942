"""Compressed-domain aggregate primitives (r07): SUM in DICTIONARY
space and SUM/COUNT in RUN space.

The "GPU Acceleration of SQL Analytics on Compressed Data" formulation:
a SUM over a dictionary-encoded column equals Σ_c count[c]·dict[c], so
the O(N) work touches only the small integer codes (a bincount) and the
O(D) dot over the tiny dictionary replaces N value gathers.  Per-batch
dictionaries make the cell space (group, batch, code); the dot then
contracts the (batch, code) axes against the per-batch dictionary
stack.  RLE goes further: with a per-run boolean mask the filter and
the reduction are both O(runs) arithmetic over (value, length) pairs —
see storage/device_decode.rle_masked_sum_count for the single-plate
form this generalizes.

Accumulation is float64 throughout, the same accumulator the packed
fsum family uses; only summation ORDER differs (per-code partials
instead of per-row), so results agree with the decoded path to f64
reassociation — well inside the 1e-9 relative band the equivalence
tests and the bench assert.  Exact int64 accumulators (exact decimals,
integer sums) must NOT use these: Σ count·value in f64 rounds above
2^53.  Callers gate on the accumulator dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from snappydata_tpu.observability import tracing

# static cell budget for the (group, batch, code) bincount space: past
# this the scatter output outweighs what the lane saves, so callers
# keep the gather path
DICT_SPACE_MAX_CELLS = 1 << 22


def dict_space_cells(nseg: int, codes_shape, dicts_shape) -> int:
    """Cell count of the joint (group, batch, code) space — the static
    engagement bound (all three factors are trace-time constants)."""
    return int(nseg) * int(codes_shape[0]) * int(dicts_shape[1])


@tracing.op_scope("group_reduce")
def dict_space_sum(codes, dicts, gidx, w, nseg: int):
    """SUM over a VALUE_DICT column in dictionary space.

    codes: [B, cap] uint8/uint16 plate codes; dicts: [B, Dp] per-batch
    dictionaries (device dtype); gidx: [N] int32 flat group index with
    invalid rows already pointing at the dump segment; w: [N] bool row
    weights (valid & not-null).  Returns [nseg] float64 group sums.

    One O(N) scatter of 0/1 into (group, batch, code) cells, then an
    O(nseg·B·Dp) contraction with the dictionary stack — the decoded
    value plate is never gathered.  Counts are exact in f64 below 2^53
    rows per cell.
    """
    b, cap = codes.shape
    dp = dicts.shape[1]
    code = codes.reshape(-1).astype(jnp.int32)
    batch = (jnp.arange(b * cap, dtype=jnp.int32) // cap)
    joint = (gidx.astype(jnp.int32) * b + batch) * dp + code
    counts = jax.ops.segment_sum(
        jnp.where(w, 1.0, 0.0), joint, num_segments=nseg * b * dp)
    counts = counts.reshape(nseg, b, dp)
    # multiply-and-reduce, not a dot: the TPU runs an f64 dot through the
    # MXU at reduced precision (Q1's sum(l_quantity) over 24 M rows came
    # back 2.5e-6 off the exact integer on the v5e), while elementwise
    # f64 keeps the accumulator's width; the cell grid is small
    # (<= DICT_SPACE_MAX_CELLS)
    return jnp.sum(counts * dicts.astype(jnp.float64)[None], axis=(1, 2))


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

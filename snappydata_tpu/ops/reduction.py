"""Backend-aware fused segmented reductions for grouped aggregation.

One grouped query used to issue one masked full-table reduction PER GROUP
PER SLOT (`_seg_reduce` unroll) — a strategy tuned for TPU scatter costs
that is pessimal on CPU: TPC-H Q1 (G=9, ~9 slots) paid ~72 full passes
over a 24M-row table (r05: 2.3M rows/s vs Q6's 100M on the same data).
The executor now packs all compatible aggregate slots into one [N, S]
value matrix per accumulator-dtype family and reduces EVERY slot of the
family in a single fused dispatch.  This module owns the per-family
strategy table and the fused kernels:

  unroll   G masked reductions over the packed [N, S] block — the
           measured-good TPU regime for G <= 64 (dispatch-floor masked
           sums; r01: Q1 at 827M rows/s on one v5e)
  scatter  jax.ops.segment_{sum,min,max} along axis 0 — one pass, the
           safe default for large G on any backend
  matmul   one-hot [S,N]@[N,G] in the accumulator dtype — on CPU the
           one-hot feeds a multithreaded BLAS gemm (measured on the dev
           container, 24M rows, G=9: gemm with a prebuilt one-hot 0.7s
           vs 3.0s scatter vs 4.2s packed unroll), and the one-hot is
           exactly what the executor's group-index cache can reuse
           across repeated dashboard queries

`agg_reduce_strategy` (config.py) picks one explicitly; `auto` keys on
backend + G + S + N (see `resolve_strategy`).  Counts ride the float
family as 0.0/1.0 columns — exact below 2**53 rows, which also fixes
the old int32 count accumulator (`jnp.sum` of int32 ones kept int32 and
could wrap beyond 2**31 rows); the unroll/scatter count path widens by
an explicit row-count bound instead (`count_pack_dtype`).

Exactness contract per family:
  float sums  f64 accumulation everywhere (reordered summation only —
              measured max rel err vs math.fsum at Q1 scale: ~8e-14)
  int sums    int64 scatter/unroll only, NEVER matmul (f64 dot loses
              bits above 2**53)
  counts      exact on every strategy (f64 0/1 columns < 2**53, or
              bound-checked int accumulators)
  min/max     order-independent; empty groups keep the same +/-inf and
              integer-extreme fillers the unrolled path produced
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from snappydata_tpu.observability import tracing

STRATEGIES = ("auto", "unroll", "scatter", "matmul")

# Every name run_main (engine/executor.py) can put in a plan's
# note["strategies"], so every `agg_strategy_<name>` counter there is:
# what a packed family resolved to, plus the two lanes that take a slot
# before it is packed (ops/code_agg.py). EXPLAIN ANALYZE and the stats
# service report from this tuple.
REPORTED_STRATEGIES = tuple(s for s in STRATEGIES if s != "auto") \
    + ("dict_space", "rle_runs")

# unroll's G-masked-reductions shape only ever wins in the small-G
# dictionary regime; past this it degrades to scatter even if requested
UNROLL_MAX_SEGMENTS = 64

# On CPU, vectorized masked reductions are ~5x faster per pass than a
# scatter (measured: one masked [N] f64 sum 0.37s vs 2.0s segment_sum at
# 24M rows), so for a handful of segments — global aggregates and tiny
# groupings, TPC-H Q6's shape — unroll wins outright; beyond this the
# G-pass cost loses to one matmul/scatter pass.  (First bench run
# mis-routed Q6's 2-segment global sum to matmul: 0.24s -> 2.16s.)
CPU_UNROLL_MAX_SEGMENTS = 4

# matmul materializes (or caches) a [N, G] one-hot in the accumulator
# dtype: bound it so a large-G or huge-N aggregate falls back to scatter
# instead of exploding memory (TPC-H Q1 at SF4 with pow2 batch padding
# is 33.5M rows x 9 segments x 8B = 2.4GB — deliberately inside this
# bound)
MATMUL_ONEHOT_MAX_BYTES = 4 << 30

# int32 count accumulators are exact only while a group can hold fewer
# than 2**31 rows; above that the packed count dtype widens to int64
COUNT_I32_MAX_ROWS = (1 << 31) - 1


def count_pack_dtype(n_rows: int):
    """Accumulator dtype for packed int counts: int32 while no group can
    reach 2**31 rows (N is a static shape, so this is a trace-time
    decision), int64 beyond — the explicit widening for the old
    `jnp.sum(int32 ones)` overflow."""
    return jnp.int32 if n_rows <= COUNT_I32_MAX_ROWS else jnp.int64


def onehot_bytes(n_rows: int, num_segments: int, acc_dtype) -> int:
    return int(n_rows) * int(num_segments) * jnp.dtype(acc_dtype).itemsize


def resolve_strategy(requested: str, backend: str, num_segments: int,
                     n_rows: int, family: str, acc_dtype) -> str:
    """Pick the fused strategy for one accumulator family.

    family: "fsum" (float sums + counts-as-f64), "isum" (exact int64
    sums), "minmax".  Invalid requests degrade rather than fail:
    matmul is refused for int sums (inexact) and min/max (not a dot),
    and for one-hots past MATMUL_ONEHOT_MAX_BYTES; unroll degrades to
    scatter past UNROLL_MAX_SEGMENTS.
    """
    if requested not in STRATEGIES:
        requested = "auto"
    if requested == "matmul" and (
            family != "fsum"
            or onehot_bytes(n_rows, num_segments, acc_dtype)
            > MATMUL_ONEHOT_MAX_BYTES):
        requested = "auto"
    if requested == "unroll" and num_segments > UNROLL_MAX_SEGMENTS:
        requested = "scatter"
    if requested != "auto":
        return requested
    small = num_segments <= (UNROLL_MAX_SEGMENTS if backend == "tpu"
                             else CPU_UNROLL_MAX_SEGMENTS)
    if small:
        # TPU: unrolled masked reductions are at the dispatch floor for
        # dictionary-card G (measured r01 — XLA lowers scatter serially
        # there); CPU: they beat one-hot materialization while the pass
        # count stays tiny (global aggregates, Q6)
        return "unroll"
    if family == "fsum" and backend != "tpu" and onehot_bytes(
            n_rows, num_segments, acc_dtype) <= MATMUL_ONEHOT_MAX_BYTES:
        # CPU dictionary regime: the one-hot gemm is the measured winner
        # (24M rows, G=9: gemm with a prebuilt one-hot 0.7s vs 3.0s
        # scatter vs 4.2s packed unroll), and the one-hot is exactly
        # what the group-index cache amortizes across repeated queries
        return "matmul"
    return "scatter"


@tracing.op_scope("group_index")
def make_onehot(gidx, num_segments: int, acc_dtype):
    """[N, G] one-hot of the (already validity-masked) group index in
    the accumulator dtype.  Callers pass the REAL group count: rows
    whose gidx points at the excluded overflow segment match no column
    and become all-zero rows, contributing nothing to any group — so
    invalid rows need no per-slot masking on the matmul path."""
    return (gidx[:, None]
            == jnp.arange(num_segments)[None, :]).astype(acc_dtype)


def _pack(cols):
    """[N, S] matrix from a family's columns.  Only the scatter/matmul
    strategies pay this materialization; unroll reduces straight from
    the source columns so XLA fuses each mask+reduce chain with the
    expressions that produced the column (measured: packing Q6's single
    global sum cost ~0.4s of pure stack traffic at 24M rows)."""
    if len(cols) == 1:
        return cols[0][:, None]
    return jnp.stack(cols, axis=1)


@tracing.op_scope("group_reduce")
def packed_sum(cols, gidx, num_segments: int, strategy: str,
               onehot=None):
    """Fused segmented SUM of a family's columns (list of [N] arrays)
    -> [num_segments, S].  Rows must already be masked into the
    additive identity (0).

    matmul caveat: NaN/Inf values leak across groups through the dot
    (NaN * one-hot-zero is NaN), so the matmul branch carries a
    runtime finite-check and falls back to the group-isolating scatter
    via lax.cond when any packed value is non-finite."""
    if strategy == "unroll" and num_segments <= UNROLL_MAX_SEGMENTS:
        outs = []
        for k in range(num_segments):
            m = gidx == k
            outs.append(jnp.stack([
                jnp.sum(jnp.where(m, c, jnp.zeros((), c.dtype)))
                for c in cols]))
        return jnp.stack(outs)
    packed = _pack(cols)
    if strategy == "matmul":
        oh = make_onehot(gidx, num_segments, packed.dtype) \
            if onehot is None else onehot
        if jnp.issubdtype(packed.dtype, jnp.floating):
            return jax.lax.cond(
                jnp.all(jnp.isfinite(packed)),
                lambda p, o: (p.T @ o).T,
                lambda p, _o: jax.ops.segment_sum(
                    p, gidx, num_segments=num_segments),
                packed, oh)
        return (packed.T @ oh).T
    return jax.ops.segment_sum(packed, gidx, num_segments=num_segments)


@tracing.op_scope("group_reduce")
def packed_minmax(kind: str, cols, gidx, num_segments: int,
                  strategy: str):
    """Fused segmented MIN/MAX of a family's columns (list of [N]
    arrays).  Rows must already be masked to the identity filler
    (+/-inf or integer extremes); empty segments yield that filler,
    matching what the old per-slot unroll produced (scatter's
    segment_min/max use the same identity)."""
    if strategy == "unroll" and num_segments <= UNROLL_MAX_SEGMENTS:
        op = jnp.min if kind == "min" else jnp.max
        fill = _extreme_of(cols[0].dtype, kind == "min")
        outs = []
        for k in range(num_segments):
            m = gidx == k
            outs.append(jnp.stack([op(jnp.where(m, c, fill))
                                   for c in cols]))
        return jnp.stack(outs)
    packed = _pack(cols)
    seg = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
    return seg(packed, gidx, num_segments=num_segments)


def _extreme_of(dtype, positive: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if positive else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if positive else info.min, dtype)

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
  matmul   float sums: one-hot [S,N]@[N,G] in the accumulator dtype — on
           CPU the one-hot feeds a multithreaded BLAS gemm (measured on
           the dev container, 24M rows, G=9: gemm with a prebuilt
           one-hot 0.7s vs 3.0s scatter vs 4.2s packed unroll), and the
           one-hot is exactly what the executor's group-index cache can
           reuse across repeated dashboard queries.  Exact integers
           (int64 sums, count masks): a chunked one-hot product over
           bfloat16 limbs on the MXU (limb_matmul_sum), what `auto`
           picks on the TPU past 64 groups, where a scatter runs
           serially (int64 sum + count over 100.66 M rows into 128
           slots: 9.06 s by scatter, 21 ms by the product; PERF.md
           section 6, PR 33)
  runs     not a request but what `scatter` becomes where the executor
           built its group index from sorted keys (generic keys): the
           rows sorted by group index, each group reduced over its
           contiguous run by a segmented scan (run_reduce)

`agg_reduce_strategy` (config.py) picks one explicitly; `auto` keys on
backend + G + S + N (see `resolve_strategy`).  Counts ride the float
family as 0.0/1.0 columns — exact below 2**53 rows, which also fixes
the old int32 count accumulator (`jnp.sum` of int32 ones kept int32 and
could wrap beyond 2**31 rows); the unroll/scatter count path widens by
an explicit row-count bound instead (`count_pack_dtype`).

Exactness contract per family:
  float sums  f64 accumulation everywhere (reordered summation only —
              measured max rel err vs math.fsum at Q1 scale: ~8e-14)
  int sums    int64 by scatter, unroll or the limb product (whose
              float32 partials stay under 2**24 and recombine modulo
              2**64: the scatter's bits, wrap-around included), NEVER
              an f64 dot (it loses bits above 2**53)
  counts      exact on every strategy (f64 0/1 columns < 2**53,
              bound-checked int accumulators, or a 0/1 limb of the
              limb product)
  min/max     order-independent; empty groups keep the same +/-inf and
              integer-extreme fillers the unrolled path produced
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from snappydata_tpu.observability import tracing

STRATEGIES = ("auto", "unroll", "scatter", "matmul")

# Every name run_main (engine/executor.py) can put in a plan's
# note["strategies"], so every `agg_strategy_<name>` counter there is:
# what a packed family resolved to, `runs` where a generic-key family
# that resolved to `scatter` reduced over its rows' runs instead
# (run_reduce), plus the two lanes that take a slot before it is packed
# (ops/code_agg.py). EXPLAIN ANALYZE and the stats service report from
# this tuple.
REPORTED_STRATEGIES = tuple(s for s in STRATEGIES if s != "auto") \
    + ("runs", "dict_space", "rle_runs")

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

# The integer form of `matmul` (limb_matmul_sum): an int64 is LIMB_COUNT
# unsigned limbs of LIMB_BITS bits, each exact in bfloat16 (every integer
# up to 256 is), and one contraction sums a limb over LIMB_CHUNK_ROWS
# rows into a float32, which holds every integer below 2**24.  The
# invariant is largest limb x rows a contraction < 2**24:
# 255 x 65,536 = 16,711,680 < 16,777,216.
LIMB_BITS = 8
LIMB_COUNT = 64 // LIMB_BITS
LIMB_CHUNK_ROWS = 1 << 16
LIMB_ACC_EXACT = 1 << 24
assert ((1 << LIMB_BITS) - 1) * LIMB_CHUNK_ROWS < LIMB_ACC_EXACT

# Past this many group slots `auto` leaves the exact-integer families on
# the scatter: the one-hot's work grows with G and the scatter's does
# not.  Set from a sweep on the v5e over 100,663,296 rows (PERF.md
# section 6, PR 33; seconds, product against scatter): an int64 sum and
# a count mask 0.021 / 9.06 at 128 slots, 0.088 / 9.36 at 1,024,
# 0.361 / 10.5 at 4,096, 0.756 / 11.5 at 8,192, 5.43 / 13.6 at 65,536;
# a count mask alone 0.012 / 0.88, 0.068 / 0.88, 0.266 / 0.67-0.88,
# then 1.58 / 0.67-0.88 at 8,192 (its one-limb product lowers worse):
# the largest power of two at which both packs win.
LIMB_MATMUL_MAX_SEGMENTS = 4096

# bytes of bfloat16 operands (one-hot and limbs) one step of the product
# may hold if XLA materialises them (the CPU backend does; the v5e
# compiler fuses them into the product and holds none): the rows are
# walked in steps of this size, so the transient does not grow with N
LIMB_STEP_BYTES = 256 << 20


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
    sums, and the count masks where the float family would scatter),
    "minmax".  Invalid requests degrade rather than fail: matmul is
    refused for min/max (not a dot) and for float one-hots past
    MATMUL_ONEHOT_MAX_BYTES (the integer form walks its rows in bounded
    steps and is never refused); unroll degrades to scatter past
    UNROLL_MAX_SEGMENTS.
    """
    if requested not in STRATEGIES:
        requested = "auto"
    if requested == "matmul" and (
            family == "minmax"
            or (family == "fsum"
                and onehot_bytes(n_rows, num_segments, acc_dtype)
                > MATMUL_ONEHOT_MAX_BYTES)):
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
    if family == "isum" and backend == "tpu" \
            and num_segments <= LIMB_MATMUL_MAX_SEGMENTS:
        # the TPU runs a scatter serially (81-128 ns a row for an
        # int64, 6.7-8.8 for an int32); the limb product's work grows
        # with G (0.083 ns a row and group)
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


def divisor_step(total: int, most: int) -> int:
    """Units a step of a walk over `total` units bounded by `most` a
    step: a divisor of `total` within a factor of two of `most` if there
    is one, so no odd-sized last step is compiled; else `most`."""
    return next((c for c in range(most, (most + 1) // 2 - 1, -1)
                 if total % c == 0), most)


def _limb_steps(n: int, num_segments: int, width: int):
    """(rows a chunk, chunks a step, steps, chunks of the remainder step,
    rows of the tail chunk) for limb_matmul_sum over `n` rows: a chunk is
    one contraction (at most LIMB_CHUNK_ROWS rows), a step a batch of
    chunks whose operands fit LIMB_STEP_BYTES (divisor_step)."""
    row_bytes = (num_segments + width) * 2
    rows = max(1, min(n, LIMB_CHUNK_ROWS,
                      max(128, LIMB_STEP_BYTES // row_bytes // 128 * 128)))
    chunks, tail = divmod(n, rows)
    step = divisor_step(chunks, max(1, min(
        chunks, LIMB_STEP_BYTES // (rows * row_bytes))))
    return (rows, step) + divmod(chunks, step) + (tail,)


@tracing.op_scope("group_reduce")
def limb_matmul_sum(cols, gidx, num_segments: int):
    """Exact segmented SUM of integer columns (list of [N] arrays: any
    signed integer dtype, or bool for a count mask) -> [num_segments, S]
    int64, bit for bit the int64 `segment_sum` (wrap-around included),
    by a one-hot product on the MXU instead of a scatter, which the TPU
    runs serially (81 ns a row for an int64; PERF.md section 6, PR 33).

    Each column is its two's-complement bits cut into LIMB_COUNT
    unsigned limbs held as bfloat16 (a bool mask is one 0/1 limb); the
    limbs of a chunk of rows are contracted with onehot(gidx) over the
    REAL groups (a row on the dump segment is an all-zero one-hot row
    and sums nowhere, so such rows need no masking) in one batched
    dot_general with a float32 accumulator: exact, because limb x rows
    stays under 2**24 (LIMB_CHUNK_ROWS).  The chunks' partials are
    added in uint64 and recombined as sum_k partial_k << (LIMB_BITS*k)
    modulo 2**64.  Rows are walked in steps bounded by LIMB_STEP_BYTES;
    each step slices its own rows out of the flat inputs, so what a
    step materialises does not grow with N."""
    n = gidx.shape[0]
    # limb row l of the operand is (source >> shifts[l]) & mask: a source
    # is a uint32 half of an int64 column (its limbs low to high) or a
    # count mask (one row); ends[k] is one past source k's last row
    half = list(range(0, 32, LIMB_BITS))
    shifts, ends = [], []
    for c in cols:
        for rows_of_source in ([[0]] if c.dtype == jnp.bool_
                               else [half, half]):
            shifts += rows_of_source
            ends.append(len(shifts))
    rows, step, steps, rest, tail = _limb_steps(n, num_segments,
                                                len(shifts))
    groups = jnp.arange(num_segments, dtype=jnp.int32)[None, :, None]
    limb = jnp.arange(len(shifts), dtype=jnp.int32)[None, :, None]
    shift = jnp.asarray(shifts, jnp.uint32)[None, :, None]

    def sums_at(lo, c: int, r: int):
        """[G, limbs] uint64 sums over the `c` chunks of `r` rows that
        start at row `lo`."""
        def chunks_of(x):
            return jax.lax.dynamic_slice_in_dim(x, lo, c * r).reshape(c, r)

        oh = (chunks_of(gidx).astype(jnp.int32)[:, None, :]
              == groups).astype(jnp.bfloat16)
        sources = []
        for col in cols:
            x = chunks_of(col)
            if x.dtype == jnp.bool_:
                sources.append(x.astype(jnp.uint32))
            else:
                x = x.astype(jnp.int64)
                sources += [x.astype(jnp.uint32),
                            (x >> 32).astype(jnp.uint32)]
        # the operand is built whole, every limb row a select of its
        # source and one shift, not stacked from rows: a stack is a
        # sublane shuffle that cost more than the product itself on the
        # v5e (10 of 27 ms a statement; PERF.md section 6, PR 33)
        src = sources[-1][:, None, :]
        for s_, end in zip(sources[-2::-1], ends[-2::-1]):
            src = jnp.where(limb < end, s_[:, None, :], src)
        operand = ((src >> shift) & jnp.uint32((1 << LIMB_BITS) - 1)) \
            .astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            oh, operand, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return jnp.sum(part.astype(jnp.uint32).astype(jnp.uint64), axis=0)

    # the loop's carry starts from the first step, not from zeros: under
    # shard_map a constant carry is unvarying over the mesh axis and the
    # step's sum is not, which the scan refuses
    parts = []
    if steps:
        first = sums_at(0, step, rows)
        parts.append(first if steps == 1 else jax.lax.fori_loop(
            1, steps,
            lambda i, a: a + sums_at(i * (step * rows), step, rows), first))
    if rest:
        parts.append(sums_at(steps * step * rows, rest, rows))
    if tail:
        parts.append(sums_at(n - tail, 1, tail))
    acc = sum(parts) if parts else jnp.zeros(      # no rows at all
        (num_segments, len(shifts)), jnp.uint64)
    out, at = [], 0
    for c in cols:
        width = 1 if c.dtype == jnp.bool_ else LIMB_COUNT
        out.append(sum(acc[:, at + k] << jnp.uint64(LIMB_BITS * k)
                       for k in range(width)))
        at += width
    return jax.lax.bitcast_convert_type(jnp.stack(out, axis=1), jnp.int64)


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
    if strategy == "matmul" and not jnp.issubdtype(cols[0].dtype,
                                                   jnp.floating):
        return limb_matmul_sum(cols, gidx, num_segments)
    packed = _pack(cols)
    if strategy == "matmul":
        oh = make_onehot(gidx, num_segments, packed.dtype) \
            if onehot is None else onehot
        return jax.lax.cond(
            jnp.all(jnp.isfinite(packed)),
            lambda p, o: (p.T @ o).T,
            lambda p, _o: jax.ops.segment_sum(
                p, gidx, num_segments=num_segments),
            packed, oh)
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


class Runs(NamedTuple):
    """The rows in group order (run_reduce): group g is the run
    rows[bounds[g]:bounds[g + 1]], and tails[k] is column k reduced
    over each group's run."""
    rows: jax.Array      # [N] int32 row numbers, sorted by group index
    bounds: jax.Array    # [G + 1] int32 run starts; bounds[G]: the dump's
    tails: tuple         # [G] a column, in its own dtype

    @property
    def counts(self):
        """[G] int32 rows a group: its run's length."""
        return self.bounds[1:] - self.bounds[:-1]


_RUN_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _identity(dtype, kind: str):
    """What an empty group reads: 0 for a sum, the filler for min/max."""
    if kind == "sum":
        return jnp.zeros((), dtype)
    return _extreme_of(dtype, kind == "min")


def segmented_scan(head, cols, kinds):
    """Inclusive scans of `cols` ([N] arrays) by `kinds` that restart
    at every row where `head` is set: after the step of distance d a
    row holds its run's reduction over the d rows up to it, so
    ceil(log2 N) elementwise steps over shifted copies (Hillis and
    Steele), each a contiguous slice the TPU's compiler lowers at once.
    A row that starts its run keeps its own value whatever came before
    it: a NaN or an Inf never leaves its run."""
    def down(x, d, fill):
        """x moved d rows on, its first d rows `fill`."""
        return jnp.concatenate([jnp.full((d,), fill, x.dtype), x[:-d]])

    ops = [_RUN_OPS[k] for k in kinds]
    fills = [_identity(c.dtype, k) for c, k in zip(cols, kinds)]
    cols = list(cols)
    d = 1
    while d < head.shape[0]:
        cols = [jnp.where(head, x, op(down(x, d, fill), x))
                for op, x, fill in zip(ops, cols, fills)]
        head = head | down(head, d, False)
        d *= 2
    return cols


@tracing.op_scope("group_reduce")
def run_reduce(gidx, num_segments: int, cols, kinds):
    """Segmented reductions of `cols` (list of [N] arrays, masked into
    their identity as for the scatter) by group index, where each group
    is reduced over its contiguous run of rows instead of scattered:
    the TPU runs a scatter serially (87 ns a row for a float64 sum into
    65,536 slots; PERF.md section 5), a sort and a scan it does not.

    One sort keyed on `gidx` carries the row numbers and the columns
    (a row of the dump segment, `num_segments`, sorts last); a segmented
    scan over the sorted columns (segmented_scan) is read at each run's
    tail.  A sum stays in its column's dtype (float64, or int64 wrapping
    as `segment_sum` does); `kinds[k]` is "sum", "min" or "max", and an
    empty group reads the identity (0, or the min/max filler the column
    was masked with, as `segment_min`/`max` give).  The run starts are
    found by a search of the `num_segments + 1` group ids over the
    sorted index, unrolled: no row-sized loop."""
    n = gidx.shape[0]
    out = jax.lax.sort((gidx, jnp.arange(n, dtype=jnp.int32))
                       + tuple(cols), num_keys=1)
    sorted_gidx, rows, sorted_cols = out[0], out[1], out[2:]
    bounds = jnp.searchsorted(
        sorted_gidx, jnp.arange(num_segments + 1, dtype=sorted_gidx.dtype),
        method="scan_unrolled").astype(jnp.int32)
    tails = ()
    if cols:
        head = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                sorted_gidx[1:] != sorted_gidx[:-1]])
        scanned = segmented_scan(head, sorted_cols, kinds)
        nonempty = bounds[1:] > bounds[:-1]
        tail = jnp.maximum(bounds[1:] - 1, 0)
        tails = tuple(jnp.where(nonempty, s[tail], _identity(s.dtype, k))
                      for s, k in zip(scanned, kinds))
    return Runs(rows, bounds, tails)


def _extreme_of(dtype, positive: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if positive else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if positive else info.min, dtype)

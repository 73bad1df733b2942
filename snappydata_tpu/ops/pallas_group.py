"""Pallas kernel: fused grouped aggregation for the dictionary fast path.

The flagship scan shape (TPC-H Q1: GROUP BY two dictionary-encoded
columns, a handful of SUM/AVG/COUNT slots) otherwise runs the packed
per-family reductions (ops/reduction.py) — on TPU the auto strategy is
G unrolled masked reductions over the packed block, each widening to
emulated float64 (accurate but ~3% of HBM bandwidth, round-4 verdict).
This kernel instead does the whole slot batch in ONE streaming pass:

- the [rows, 128] f32 plates stream block-by-block through VMEM;
- each of the 8x128 vector lanes keeps an independent Kahan
  (compensated) partial PER GROUP — carry shape [G, 8, 128] — so the
  hot loop is pure native-f32 vector ops (select + 4 adds per group),
  no f64 emulation and no scatter;
- all slots of the aggregate share the single group-index load: the
  kernel takes K value columns + per-slot null masks and produces K
  sets of partials in the same pass;
- the tiny [G, 8, 128] (sum, compensation) partials combine in exact
  float64 OUTSIDE the kernel: total = sum(s) - sum(c) (the Kahan
  c-holds-the-excess convention, same as ops/pallas_reduce.py).

COUNT accumulates in f32 (each lane's partial stays far below 2^24 —
exact) and combines in int64; MIN/MAX keep plain masked partials with
the same +/-inf fillers as the packed families, so empty groups match
the unrolled path bit-for-bit.

Gated behind `properties.pallas_group_reduce` (default OFF: it compiles
and matches on the v5e, chip_smoke.py checks that; its rate is not
measured).  Eligibility mirrors the global kernel: f32
value plates only (the TPU storage contract already stores DOUBLE as
f32 plates), dictionary/bool fast-path group indexes with
G <= MAX_GROUPS, and the documented compensated-summation caveat
(error bounded vs sum(|v|), not |sum(v)|).  CPU runs use the
interpreter for correctness tests only.

Ref parity: SnappyHashAggregateExec's dictionary-key fast path — one
generated loop updating per-dictionary-code accumulators
(/root/reference/core/src/main/scala/org/apache/spark/sql/execution/
aggregate/SnappyHashAggregateExec.scala:73-109); this is the TPU-native
equivalent, with vector-lane-parallel compensated partials instead of
JVM double accumulators.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from snappydata_tpu.ops.pallas_reduce import (_CODE_STEP, _LANES, _SUBLANES,
                                              interpret_default)

# rows per grid step. Smaller than pallas_reduce's 2048: the per-group
# carries cost ops * [G, 8, 128] f32 VMEM (G=64, 4 sums + count ->
# 9 * 256KB = 2.3MB), plus K+1 input blocks of [1024, 128].
_BLOCK_ROWS = 1024

# G cap, counting the +1 overflow segment the executor reserves for
# invalid rows. Matches reduction.UNROLL_MAX_SEGMENTS — the same
# dictionary-card regime where unrolled masked reductions beat scatters.
MAX_GROUPS = 64

_KINDS = ("sum", "count", "min", "max")

# Conservative VMEM budget for one fused call: double-buffered input
# blocks + the [G, 8, 128] carries must fit alongside pallas overhead
# in ~16MB. Callers use op_vmem_bytes() to stop fusing (overflow slots
# take the packed-family reductions) before a wide aggregate would fail
# the Mosaic compile outright.
VMEM_BUDGET = 12 * 1024 * 1024


def base_vmem_bytes() -> int:
    """Fixed cost: the double-buffered gidx input block."""
    return _BLOCK_ROWS * _LANES * 4 * 2


def op_vmem_bytes(kind: str, num_segments: int,
                  shared_mask: bool = False,
                  shared_value: bool = False) -> int:
    """Estimated VMEM this op adds: its input blocks (value f32 + mask
    bool, double-buffered) and its [G, 8, 128] f32 carries (two for
    Kahan sums). `shared_mask`/`shared_value`: the op reuses an
    already-counted input array — grouped_reduce deduplicates inputs
    by identity, so the block costs nothing extra."""
    blk = _BLOCK_ROWS * _LANES
    mask = 0 if shared_mask else blk * 1 * 2
    val = 0 if (kind == "count" or shared_value) else blk * 4 * 2
    carry = (num_segments * _SUBLANES * _LANES * 4
             * (2 if kind == "sum" else 1))
    return mask + val + carry


def _outs_of(kind: str) -> int:
    return 2 if kind == "sum" else 1


@functools.lru_cache(maxsize=64)
def _make_kernel(spec: Tuple[Tuple[str, Optional[int], int], ...],
                 n_in: int, G: int):
    """spec: one (kind, value_input_index, mask_input_index) per op —
    indices point into the DEDUPLICATED input list, so ops sharing a
    value or mask array (all of Q1's slots share one validity mask)
    read it from HBM once per block instead of once per op."""
    steps = _BLOCK_ROWS // _SUBLANES

    def kernel(gidx_ref, *refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:]
        pid = pl.program_id(0)
        shape = (G, _SUBLANES, _LANES)

        @pl.when(pid == 0)
        def _init():
            oi = 0
            for k, _vi, _mi in spec:
                if k == "sum":
                    out_refs[oi][...] = jnp.zeros(shape, jnp.float32)
                    out_refs[oi + 1][...] = jnp.zeros(shape, jnp.float32)
                    oi += 2
                elif k == "count":
                    out_refs[oi][...] = jnp.zeros(shape, jnp.float32)
                    oi += 1
                elif k == "min":
                    out_refs[oi][...] = jnp.full(shape, jnp.inf, jnp.float32)
                    oi += 1
                else:  # max
                    out_refs[oi][...] = jnp.full(shape, -jnp.inf, jnp.float32)
                    oi += 1

        # continue the running chains from the previous block (or the
        # identities just written): output blocks map to the same
        # buffer at every grid step, so they persist across steps
        carry0 = tuple(r[...] for r in out_refs)
        garange = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

        def body(i, carry):
            sl = pl.ds(pl.multiple_of(i * _SUBLANES, _SUBLANES),
                       _SUBLANES)
            gblk = gidx_ref[sl, :]
            gm = gblk[None].astype(jnp.int32) == garange  # [G, 8, 128]
            # one VMEM load + one group-select per UNIQUE input block
            loaded = {}
            sels = {}

            def sel_of(mi):
                if mi not in sels:
                    sels[mi] = gm & in_refs[mi][sl, :][None]
                return sels[mi]

            def val_of(vi):
                if vi not in loaded:
                    loaded[vi] = in_refs[vi][sl, :]
                return loaded[vi]

            new = []
            oi = 0
            for k, vi, mi in spec:
                sel = sel_of(mi)
                if k == "count":
                    new.append(carry[oi]
                               + jnp.where(sel, 1.0, 0.0).astype(jnp.float32))
                    oi += 1
                    continue
                v = val_of(vi)
                if k == "sum":
                    s, c = carry[oi], carry[oi + 1]
                    vv = jnp.where(sel, v[None], 0.0)
                    # Kahan: masked-out lanes add 0.0, which re-folds the
                    # compensation into s (harmless: s - c is preserved)
                    y = vv - c
                    t = s + y
                    new.append(t)
                    new.append((t - s) - y)
                    oi += 2
                elif k == "min":
                    new.append(jnp.minimum(
                        carry[oi], jnp.where(sel, v[None], jnp.inf)))
                    oi += 1
                else:  # max
                    new.append(jnp.maximum(
                        carry[oi], jnp.where(sel, v[None], -jnp.inf)))
                    oi += 1
            return tuple(new)

        final = jax.lax.fori_loop(0, steps, body, carry0)
        for r, val in zip(out_refs, final):
            r[...] = val

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("spec", "G", "interpret"))
def _grouped_call(gidx2d, ins,
                  spec: Tuple[Tuple[str, Optional[int], int], ...],
                  G: int, interpret: bool):
    rows = gidx2d.shape[0]
    nblocks = rows // _BLOCK_ROWS
    blk = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    out_blk = pl.BlockSpec((G, _SUBLANES, _LANES), lambda i: (0, 0, 0))
    kinds = tuple(k for k, _, _ in spec)
    n_out = sum(_outs_of(k) for k in kinds)
    with jax.enable_x64(False):   # see pallas_reduce._kahan_call
        outs = pl.pallas_call(
            _make_kernel(spec, len(ins), G),
            grid=(nblocks,),
            in_specs=[blk] * (1 + len(ins)),
            out_specs=(out_blk,) * n_out,
            out_shape=tuple(
                jax.ShapeDtypeStruct((G, _SUBLANES, _LANES), jnp.float32)
                for _ in range(n_out)),
            interpret=interpret,
            name="snappy_group_reduce",
        )(gidx2d, *ins)

    results = []
    oi = 0
    for k in kinds:
        if k == "sum":
            s, c = outs[oi], outs[oi + 1]
            oi += 2
            results.append(jnp.sum(s.astype(jnp.float64), axis=(1, 2))
                           - jnp.sum(c.astype(jnp.float64), axis=(1, 2)))
        elif k == "count":
            # per-lane f32 partials are exact integers (< 2^24 each);
            # the cross-lane combine happens in int64
            results.append(jnp.sum(outs[oi].astype(jnp.int64), axis=(1, 2)))
            oi += 1
        elif k == "min":
            results.append(jnp.min(outs[oi], axis=(1, 2)))
            oi += 1
        else:
            results.append(jnp.max(outs[oi], axis=(1, 2)))
            oi += 1
    return tuple(results)


def grouped_reduce(ops: Sequence[Tuple[str, Optional[jnp.ndarray],
                                       jnp.ndarray]],
                   gidx: jnp.ndarray, num_segments: int,
                   interpret: Optional[bool] = None) -> List[jnp.ndarray]:
    """Fused segmented reduction of all `ops` in one streaming pass.

    ops: (kind, values, mask) per aggregate slot — kind in
    sum/count/min/max, values an f32 array (None for count), mask the
    slot's validity (row valid AND value non-null). gidx: int group
    index per element, < num_segments <= MAX_GROUPS. Returns one
    [num_segments] array per op: f64 for sums, int64 for counts, f32
    (with +/-inf empty-group fillers, matching the packed families)
    for min/max.
    """
    assert 1 <= num_segments <= MAX_GROUPS, num_segments
    kinds = tuple(k for k, _, _ in ops)
    assert all(k in _KINDS for k in kinds), kinds
    if interpret is None:
        interpret = interpret_default()

    n = gidx.reshape(-1).shape[0]
    tile = _BLOCK_ROWS * _LANES
    padded = max(tile, ((n + tile - 1) // tile) * tile)

    def prep(a, dtype):
        flat = a.reshape(-1).astype(dtype)
        if padded != n:
            flat = jnp.pad(flat, (0, padded - n))
        return flat.reshape(-1, _LANES)

    # padded rows carry mask=False, so their gidx value is irrelevant
    gidx2d = prep(gidx, jnp.int32)
    # deduplicate inputs by source-array identity: slots that share a
    # validity mask (Q1: all of them) or a value column (sum(x)+min(x))
    # cross HBM once per block, not once per op
    ins: List[jnp.ndarray] = []
    index_of: Dict[Tuple[int, str], int] = {}

    def intern(arr, role: str, dtype) -> int:
        key = (id(arr), role)
        got = index_of.get(key)
        if got is None:
            got = len(ins)
            ins.append(prep(arr, dtype))
            index_of[key] = got
        return got

    spec = []
    for k, v, m in ops:
        vi = None if k == "count" else intern(v, "v", jnp.float32)
        mi = intern(m, "m", jnp.bool_)
        spec.append((k, vi, mi))

    outs = _grouped_call(gidx2d, tuple(ins), tuple(spec), num_segments,
                         interpret)
    return list(outs)


# ==========================================================================
# Fused decode+filter+grouped-aggregate: the TPC-H Q1 shape over ENCODED
# batches.  Value inputs arrive as VALUE_DICT code plates plus per-batch
# dictionaries; each sum slot is a product of an optional PLAIN factor
# and any number of CODE factors, decoded INSIDE the kernel from SMEM
# dictionaries (so `sum(price * (1 - disc))` passes price plain and disc
# codes with a HOST-transformed dictionary 1-dict — dictionary-space
# preprocessing is O(D), row-space stays encoded).  Grid is
# (batch, block) so dictionaries index by batch; the per-group per-lane
# Kahan discipline matches grouped_reduce above.  All slots share one
# row mask (the Q1 shape: one filter, null-free measure columns) — the
# generic engine keeps per-slot null masks.
# ==========================================================================

_CBLOCK_ROWS = 512   # multiple of _CODE_STEP


@functools.lru_cache(maxsize=32)
def _make_code_kernel(spec: Tuple, n_vmem: int, n_dict: int, G: int):
    """spec: per slot ("count",) or ("sum", plain_idx_or_None,
    ((code_vmem_idx, dict_idx), ...)) — VMEM indices point into the
    [gidx, mask, *values] block list, dict indices into the SMEM list."""
    steps = _CBLOCK_ROWS // _CODE_STEP

    def kernel(*refs):
        gidx_ref = refs[0]
        mask_ref = refs[1]
        vmem = refs[:n_vmem]
        dicts = refs[n_vmem:n_vmem + n_dict]
        out_refs = refs[n_vmem + n_dict:]
        b = pl.program_id(0)
        s = pl.program_id(1)
        shape = (G, _SUBLANES, _LANES)

        @pl.when((b == 0) & (s == 0))
        def _init():
            for r in out_refs:
                r[...] = jnp.zeros(shape, jnp.float32)

        garange = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

        def body(i, carry):
            # code plates load one small-int tile (32 rows) at a time
            # and fold into the [G, 8, 128] chains as four sub-steps
            sl = pl.ds(pl.multiple_of(i * _CODE_STEP, _CODE_STEP),
                       _CODE_STEP)
            gblk = gidx_ref[0, sl, :].astype(jnp.int32)
            mblk = mask_ref[0, sl, :]
            vals = []
            for op in spec:
                if op[0] == "count":
                    vals.append(None)
                    continue
                _, plain_idx, factors = op
                v = vmem[plain_idx][0, sl, :] if plain_idx is not None \
                    else jnp.ones((_CODE_STEP, _LANES), jnp.float32)
                for cvi, dvi in factors:
                    codes = vmem[cvi][0, sl, :].astype(jnp.int32)
                    dref = dicts[dvi]
                    dval = jnp.zeros((_CODE_STEP, _LANES), jnp.float32)

                    def dec(k, acc, _c=codes, _d=dref):
                        return jnp.where(_c == k, _d[0, 0, k], acc)

                    dval = jax.lax.fori_loop(0, dref.shape[2], dec, dval)
                    v = v * dval
                vals.append(v)
            carry = list(carry)
            for r in range(0, _CODE_STEP, _SUBLANES):
                rows = slice(r, r + _SUBLANES)
                sel = (gblk[rows][None] == garange) & mblk[rows][None]
                oi = 0
                for v in vals:
                    if v is None:
                        carry[oi] = carry[oi] + jnp.where(sel, 1.0, 0.0)
                        oi += 1
                        continue
                    sm, cp = carry[oi], carry[oi + 1]
                    y = jnp.where(sel, v[rows][None], 0.0) - cp
                    t = sm + y
                    carry[oi] = t
                    carry[oi + 1] = (t - sm) - y
                    oi += 2
            return tuple(carry)

        final = jax.lax.fori_loop(0, steps, body,
                                  tuple(r[...] for r in out_refs))
        for r, val in zip(out_refs, final):
            r[...] = val

    return kernel


@functools.partial(jax.jit, static_argnames=("spec", "G", "dshapes",
                                             "interpret"))
def _grouped_code_call(vmem_ins, dict_ins, spec, G: int, dshapes,
                       interpret: bool):
    B, capr, _ = vmem_ins[0].shape
    S = capr // _CBLOCK_ROWS
    n_out = sum(1 if op[0] == "count" else 2 for op in spec)
    with jax.enable_x64(False):   # see pallas_reduce._kahan_call
        blk = pl.BlockSpec((1, _CBLOCK_ROWS, _LANES),
                           lambda b, s: (b, s, 0))
        out_blk = pl.BlockSpec((G, _SUBLANES, _LANES),
                               lambda b, s: (0, 0, 0))
        outs = pl.pallas_call(
            _make_code_kernel(spec, len(vmem_ins), len(dict_ins), G),
            grid=(B, S),
            # dictionaries ride SMEM as [B, 1, D]: one batch's block
            # spans the array's last two dimensions whole
            in_specs=[blk] * len(vmem_ins) + [
                pl.BlockSpec((1, 1, d), lambda b, s: (b, 0, 0),
                             memory_space=pltpu.SMEM) for d in dshapes],
            out_specs=(out_blk,) * n_out,
            out_shape=tuple(
                jax.ShapeDtypeStruct((G, _SUBLANES, _LANES), jnp.float32)
                for _ in range(n_out)),
            interpret=interpret,
            name="snappy_code_group_reduce",
        )(*vmem_ins, *dict_ins)
    results = []
    oi = 0
    for op in spec:
        if op[0] == "count":
            results.append(jnp.sum(outs[oi].astype(jnp.int64),
                                   axis=(1, 2)))
            oi += 1
        else:
            s, c = outs[oi], outs[oi + 1]
            oi += 2
            results.append(jnp.sum(s.astype(jnp.float64), axis=(1, 2))
                           - jnp.sum(c.astype(jnp.float64), axis=(1, 2)))
    return tuple(results)


def grouped_code_reduce(gidx, mask, slots, num_segments: int,
                        interpret: Optional[bool] = None):
    """Fused decode+filter+grouped reduction over code plates.

    gidx: [B, cap] int group index (< num_segments <= MAX_GROUPS);
    mask: [B, cap] bool shared row mask (valid & filter);
    slots: sequence of ("count",) or ("sum", plain_or_None, factors)
      with plain a [B, cap] float array and factors a sequence of
      (codes [B, cap] uint8/uint16, dicts [B, D] float) pairs — the
      slot value is plain * Π decode(codes_k).
    Returns one [num_segments] array per slot: int64 for counts,
    float64 for sums."""
    assert 1 <= num_segments <= MAX_GROUPS, num_segments
    if interpret is None:
        interpret = interpret_default()
    gidx = jnp.asarray(gidx)
    B, cap = gidx.shape
    capr = cap // _LANES
    pad_r = ((capr + _CBLOCK_ROWS - 1) // _CBLOCK_ROWS) * _CBLOCK_ROWS
    pad_cap = pad_r * _LANES

    def shape3(a, dtype):
        a = jnp.asarray(a)
        if pad_cap != cap:
            a = jnp.pad(a, ((0, 0), (0, pad_cap - cap)))
        return a.reshape(B, pad_r, _LANES).astype(dtype)

    vmem: List = [shape3(gidx, jnp.int32), shape3(mask, jnp.bool_)]
    dict_ins: List = []
    spec = []
    for slot in slots:
        if slot[0] == "count":
            spec.append(("count",))
            continue
        _, plain, factors = slot
        pi = None
        if plain is not None:
            pi = len(vmem)
            vmem.append(shape3(plain, jnp.float32))
        fs = []
        for codes, dicts in factors:
            cvi = len(vmem)
            vmem.append(shape3(codes, jnp.asarray(codes).dtype))
            dvi = len(dict_ins)
            dict_ins.append(
                jnp.asarray(dicts, dtype=jnp.float32)[:, None, :])
            fs.append((cvi, dvi))
        spec.append(("sum", pi, tuple(fs)))
    dshapes = tuple(int(d.shape[2]) for d in dict_ins)
    return list(_grouped_code_call(tuple(vmem), tuple(dict_ins),
                                   tuple(spec), int(num_segments),
                                   dshapes, bool(interpret)))

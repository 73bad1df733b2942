"""Pallas kernel: masked compensated (Kahan) reduction.

Motivation (the numerics/bandwidth trade the aggregate accumulators
face): DOUBLE aggregates need ~1e-6-grade accuracy, so the XLA path
widens the accumulator to float64 — which TPUs EMULATE in software at a
large per-op cost. This kernel instead runs ONE pass over the f32
plates keeping a per-lane Kahan compensation term in VMEM: each of the
8x128 vector lanes owns an independent compensated chain over its
~rows/8 elements (error ~eps, not ~n*eps), and the tiny [8,128]
(sum, compensation) partials combine in exact-enough float64 OUTSIDE
the kernel. Accuracy matches the f64 path to <=1e-6 relative while the
hot loop stays entirely in native f32 vector ops.

Used for global (ungrouped) SUM/AVG over float32 plates — the TPC-H
Q6 shape — behind `properties.pallas_reduce` (**default OFF**: it
compiles and matches on the v5e, chip_smoke.py checks that; its rate
is not measured). Scope caveats the gate enforces and the docs own:
only float32 inputs qualify (an f64 input would be truncated — the TPU
storage contract already stores DOUBLE as f32 plates, so on TPU this
loses nothing), and compensated summation bounds error relative to
Σ|v|, not |Σv| — under heavy cancellation (Σ|v| >> |Σv|) the emulated-
f64 segment path remains the accurate choice. CPU runs use the
interpreter (no Mosaic lowering) and exist for correctness tests only.

Ref parity note: the reference leans on JVM codegen'd loops with
double accumulators (SnappyHashAggregateExec); this is the TPU-native
equivalent of "accumulate wider than the data".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8


def interpret_default() -> bool:
    """Interpreter only on the CPU backend (the tests' backend, which has
    no Mosaic lowering); any accelerator process compiles the kernel or
    fails loudly."""
    return jax.default_backend() == "cpu"


# rows per grid step: 2048x128 f32 block = 1MB data + 256KB mask in
# VMEM — far under the ~16MB budget, so arbitrarily long columns
# stream block by block instead of requiring the whole array resident
_BLOCK_ROWS = 2048


def _kahan_kernel(x_ref, m_ref, sum_ref, comp_ref):
    """One grid step = one [_BLOCK_ROWS, LANES] f32 block + bool mask.
    Per-lane-element Kahan accumulation over the row axis via
    lax.fori_loop, writing this block's [SUBLANES, LANES] sum +
    compensation tiles."""
    steps = _BLOCK_ROWS // _SUBLANES

    def body(i, carry):
        s, c = carry
        sl = pl.ds(pl.multiple_of(i * _SUBLANES, _SUBLANES), _SUBLANES)
        blk = x_ref[sl, :]
        msk = m_ref[sl, :]
        v = jnp.where(msk, blk, 0.0)
        # Kahan: y = v - c; t = s + y; c = (t - s) - y; s = t
        y = v - c
        t = s + y
        c_new = (t - s) - y
        return t, c_new

    zero = jnp.zeros((_SUBLANES, _LANES), dtype=jnp.float32)
    s, c = jax.lax.fori_loop(0, steps, body, (zero, zero))
    sum_ref[:, :, :] = s[None]
    comp_ref[:, :, :] = c[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kahan_call(x2d: jnp.ndarray, mask2d: jnp.ndarray,
                interpret: bool = False):
    rows = x2d.shape[0]
    nblocks = rows // _BLOCK_ROWS
    # Mosaic has no 64-bit types and the package runs with x64 on: trace
    # the kernel (loop indices, index maps, literals) in 32-bit mode
    with jax.enable_x64(False):
        sums, comps = pl.pallas_call(
            _kahan_kernel,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, _SUBLANES, _LANES), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, _SUBLANES, _LANES), lambda i: (i, 0, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((nblocks, _SUBLANES, _LANES),
                                     jnp.float32),
                jax.ShapeDtypeStruct((nblocks, _SUBLANES, _LANES),
                                     jnp.float32),
            ),
            interpret=interpret,
            name="snappy_kahan_sum",
        )(x2d, mask2d)
    # exact f64 combine of the small per-block partials. Kahan's
    # c = (t - s) - y holds the EXCESS already folded into s, so the
    # true chain total is s - c (review finding: + doubled the residual
    # instead of cancelling it)
    return (jnp.sum(sums.astype(jnp.float64))
            - jnp.sum(comps.astype(jnp.float64)))


def masked_kahan_sum(values: jnp.ndarray, mask: jnp.ndarray,
                     interpret=None) -> jnp.ndarray:
    """Compensated sum of values[mask] -> float64 scalar.

    `values`: any-shape f32/f64 array; `mask`: same-shape bool. The
    flattened data pads to a [rows, 128] layout with rows a multiple of
    8 (TPU native tiling). `interpret=None` is interpret_default()."""
    if interpret is None:
        interpret = interpret_default()
    flat = values.reshape(-1).astype(jnp.float32)
    m = mask.reshape(-1)
    n = flat.shape[0]
    tile = _BLOCK_ROWS * _LANES
    padded = ((n + tile - 1) // tile) * tile
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
        m = jnp.pad(m, (0, padded - n))
    x2d = flat.reshape(-1, _LANES)
    m2d = m.reshape(-1, _LANES)
    return _kahan_call(x2d, m2d, interpret=interpret)


# ==========================================================================
# Fused decode+filter+aggregate: the TPC-H Q6 shape over ENCODED batches.
#
# Inputs stay in the compressed domain end to end: the filter columns are
# VALUE_DICT code plates (uint8/uint16) compared against PER-BATCH code
# thresholds (the host translates each literal through the batch's sorted
# dictionary ONCE — out-of-dictionary literals become thresholds that
# match nothing), and the discount factor decodes INSIDE the kernel from
# the batch's tiny dictionary held in SMEM — a decoded plate never exists
# in HBM, and per-row filter traffic is 1-2 bytes/column instead of 8.
#
# Grid is (batch, block): each grid step streams one [_FBLOCK_ROWS, 128]
# block of one batch through VMEM, so per-batch dictionaries/thresholds
# index naturally by the first grid axis.  Sums keep the same per-lane
# Kahan discipline as _kahan_kernel; the count partial rides f32 (exact
# below 2^24 per lane) and combines in int64 outside.
#
# CPU runs use the interpreter (correctness + the opt-in
# SNAPPY_BENCH_PALLAS=1 bench lane); the Mosaic lowering engages on TPU.
# Codes load as uint8/uint16 and widen in-register.
# ==========================================================================

_FBLOCK_ROWS = 512   # multiple of _CODE_STEP
# rows per inner-loop load: the native tile of a one-byte plate is
# (32, 128) (two-byte: (16, 128)), so code plates load 32 rows at a time
# and fold into the [8, 128] Kahan chains as four f32-tile sub-steps
_CODE_STEP = 32


def _fused_q6_kernel(qty_ref, disc_ref, ship_ref, price_ref, valid_ref,
                     dict_ref, qhi_ref, dlo_ref, dhi_ref, slo_ref, shi_ref,
                     sum_ref, comp_ref, cnt_ref):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when((b == 0) & (s == 0))
    def _init():
        zero = jnp.zeros((_SUBLANES, _LANES), jnp.float32)
        sum_ref[...] = zero
        comp_ref[...] = zero
        cnt_ref[...] = zero

    steps = _FBLOCK_ROWS // _CODE_STEP
    d_pad = dict_ref.shape[2]
    qhi = qhi_ref[0, 0, 0]
    dlo = dlo_ref[0, 0, 0]
    dhi = dhi_ref[0, 0, 0]
    slo = slo_ref[0, 0]
    shi = shi_ref[0, 0]

    def body(i, carry):
        sm, cp, ct = carry
        sl = pl.ds(pl.multiple_of(i * _CODE_STEP, _CODE_STEP), _CODE_STEP)
        q = qty_ref[0, sl, :].astype(jnp.int32)
        d = disc_ref[0, sl, :].astype(jnp.int32)
        sh = ship_ref[0, sl, :]
        pz = price_ref[0, sl, :]
        ok = (valid_ref[0, sl, :]
              & (q < qhi) & (d >= dlo) & (d <= dhi)
              & (sh >= slo) & (sh < shi))
        # in-register dictionary decode: D selects (D is tiny — the
        # VALUE_DICT acceptance rule caps it at rows/8, and Q6's
        # discount dictionary is 11 entries)
        dval = jnp.zeros_like(pz)

        def dec(k, acc):
            return jnp.where(d == k, dict_ref[0, 0, k], acc)

        dval = jax.lax.fori_loop(0, d_pad, dec, dval)
        v = jnp.where(ok, pz * dval, 0.0)
        one = jnp.where(ok, 1.0, 0.0)
        for r in range(0, _CODE_STEP, _SUBLANES):
            y = v[r:r + _SUBLANES] - cp
            t = sm + y
            cp = (t - sm) - y
            sm = t
            ct = ct + one[r:r + _SUBLANES]
        return sm, cp, ct

    carry0 = (sum_ref[...], comp_ref[...], cnt_ref[...])
    sm, cp, ct = jax.lax.fori_loop(0, steps, body, carry0)
    sum_ref[...] = sm
    comp_ref[...] = cp
    cnt_ref[...] = ct


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_q6_call(qty, disc, ship, price, valid, dicts,
                   qhi, dlo, dhi, slo, shi, interpret: bool = False):
    B, capr, _ = price.shape
    S = capr // _FBLOCK_ROWS
    out_sds = jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.float32)
    with jax.enable_x64(False):   # see _kahan_call
        blk = pl.BlockSpec((1, _FBLOCK_ROWS, _LANES),
                           lambda b, s: (b, s, 0))
        # per-batch scalars ride SMEM as [B, 1, n] so one batch's block
        # spans the array's last two dimensions whole
        smem_dict = pl.BlockSpec((1, 1, dicts.shape[2]),
                                 lambda b, s: (b, 0, 0),
                                 memory_space=pltpu.SMEM)
        smem_b = pl.BlockSpec((1, 1, 1), lambda b, s: (b, 0, 0),
                              memory_space=pltpu.SMEM)
        smem_g = pl.BlockSpec((1, 1), lambda b, s: (0, 0),
                              memory_space=pltpu.SMEM)
        out_blk = pl.BlockSpec((_SUBLANES, _LANES), lambda b, s: (0, 0))
        sums, comps, cnts = pl.pallas_call(
            _fused_q6_kernel,
            grid=(B, S),
            in_specs=[blk, blk, blk, blk, blk, smem_dict,
                      smem_b, smem_b, smem_b, smem_g, smem_g],
            out_specs=(out_blk, out_blk, out_blk),
            out_shape=(out_sds, out_sds, out_sds),
            interpret=interpret,
            name="snappy_code_filter_sum",
        )(qty, disc, ship, price, valid, dicts, qhi, dlo, dhi, slo, shi)
    total = (jnp.sum(sums.astype(jnp.float64))
             - jnp.sum(comps.astype(jnp.float64)))
    count = jnp.sum(cnts.astype(jnp.int64))
    return total, count


def fused_code_filter_sum(qty_codes, disc_codes, ship, price, valid,
                          disc_dicts, qty_hi_codes, disc_lo_codes,
                          disc_hi_codes, ship_lo, ship_hi,
                          interpret=None):
    """Fused decode+filter+SUM over encoded batches (the Q6 shape):

        sum(price * disc), count(*)
        WHERE qty_code < qty_hi_code[b]          (code domain)
          AND disc_lo_code[b] <= disc_code <= disc_hi_code[b]
          AND ship_lo <= ship < ship_hi          (value domain, int32)

    qty_codes/disc_codes: [B, cap] uint8/uint16 code plates;
    ship: [B, cap] int32; price: [B, cap] float; valid: [B, cap] bool;
    disc_dicts: [B, D] per-batch sorted dictionaries (decode target);
    *_codes thresholds: [B] int32, translated on HOST through each
    batch's sorted dictionary (one searchsorted per batch — the
    "translate the literal once" contract; a miss yields a threshold
    that matches nothing).  Returns (float64 sum, int64 count)."""
    if interpret is None:
        interpret = interpret_default()
    B, cap = price.shape
    capr = cap // _LANES
    pad_r = ((capr + _FBLOCK_ROWS - 1) // _FBLOCK_ROWS) * _FBLOCK_ROWS
    pad_cap = pad_r * _LANES

    def shape3(a, dtype):
        a = jnp.asarray(a)
        if pad_cap != cap:
            a = jnp.pad(a, ((0, 0), (0, pad_cap - cap)))
        return a.reshape(B, pad_r, _LANES).astype(dtype)

    qty = shape3(qty_codes, jnp.asarray(qty_codes).dtype)
    disc = shape3(disc_codes, jnp.asarray(disc_codes).dtype)
    sh = shape3(ship, jnp.int32)
    pz = shape3(price, jnp.float32)
    vd = shape3(valid, jnp.bool_)

    def col_b(a):
        return jnp.asarray(a, dtype=jnp.int32).reshape(B, 1, 1)

    return _fused_q6_call(
        qty, disc, sh, pz, vd,
        jnp.asarray(disc_dicts, dtype=jnp.float32)[:, None, :],
        col_b(qty_hi_codes), col_b(disc_lo_codes), col_b(disc_hi_codes),
        jnp.asarray([[int(ship_lo)]], dtype=jnp.int32),
        jnp.asarray([[int(ship_hi)]], dtype=jnp.int32),
        interpret=bool(interpret))

"""In-trace device decode: encoded bytes cross the PCIe/DMA link, the
decode to capacity-row plates happens on the accelerator.

Reference parity: the reference decodes dictionary/RLE/delta INSIDE the
generated scan code at batch-read time (ColumnTableScan.scala:684
genCodeColumnBuffer), so encodings save memory end to end. Here the
equivalents are vectorized XLA programs applied at cold bind:

* RUN_LENGTH: upload (run_values [R], run_end_offsets [R]) and expand to
  the plate with a vmapped searchsorted-gather — the batched form of
  `jnp.repeat(values, runs, total_repeat_length=cap)`. Transfer shrinks
  from cap×itemsize to 2×R×itemsize (R = #runs).
* BOOLEAN_BITSET: upload the packed bits (uint8 [cap/8]) and unpack with
  shift/mask ops — an 8× transfer reduction.
* VALUE_DICT: low-cardinality numeric columns upload uint8/uint16 codes
  [cap] plus the tiny value dictionary [D] and decode on device
  (`dict_decode`: a tree of selects over the code's bits up to
  `DICT_SELECT_MAX_WIDTH` slots, a gather past it) — an itemsize× (≥4×)
  transfer reduction. This is the encoding the default TPC-H scan
  engages (l_quantity/l_discount/l_tax are 50/11/9 distinct values,
  padded to 64/16/16 slots), so the bench's device_decode counters are
  nonzero on the stock workload.

Compressed-domain execution (r06) goes one step further: under
`scan_compressed_domain` the plates THEMSELVES stay encoded in HBM
(CodePlate/RlePlate/BitPlate below), predicates run on codes/runs, and
values decode lazily in-trace only where consumed — see the builders
and in-trace consumers at the bottom of this module.

Dictionary string columns need no device decode: their int32 codes ARE
the on-device representation (group-by/join run on codes). Batches with
update deltas take the host decode path — the delta merge is host-side
state.

Lanes past a batch's last run decode to the final run's value rather
than zero; every consumer masks by the table validity plate, so padding
content is unobservable (same contract as the zero padding of host
decode).
"""

from __future__ import annotations

import functools
import weakref
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from snappydata_tpu.observability import tracing
from snappydata_tpu.utils import locks

# bind-transfer accounting (powers the bench/device-decode metric and the
# tests' "compressed bytes actually crossed the link" assertion).
# batches_code_bound counts batches whose column stayed RESIDENT in the
# compressed domain (no decoded plate in HBM at all — the r06
# compressed-domain execution path), a subset of batches_device_decoded.
_counters: Dict[str, int] = {"bytes_encoded": 0, "bytes_decoded_equiv": 0,
                             "batches_device_decoded": 0,
                             "batches_code_bound": 0}


# --- compressed-domain column plates --------------------------------------
# A code-domain bind stores one of these in DeviceTable.columns[ci]
# instead of a decoded [B, cap] plate.  They are NamedTuples, so they ride
# the jit boundary as pytrees, survive the bind-time batch-skip gather
# (field-wise jnp.take along axis 0), and make_ctx recognizes them
# structurally at trace time — no side-channel metadata needed.

class CodePlate(NamedTuple):
    """VALUE_DICT column resident in the code domain.
    codes: [B, cap] uint8/uint16 device array;
    dicts: [B, D] device array, each row SORTED ascending and padded by
    repeating its last value (keeps searchsorted semantics exact)."""

    codes: object
    dicts: object


class RlePlate(NamedTuple):
    """RUN_LENGTH column resident as runs.
    values: [B, R] run values; ends: [B, R] int32 cumulative run end
    offsets (padded runs repeat the last end)."""

    values: object
    ends: object


class BitPlate(NamedTuple):
    """BOOLEAN_BITSET column resident as packed bits [B, ceil(cap/8)]."""

    packed: object


def counters() -> Dict[str, int]:
    return dict(_counters)


def reset_counters() -> None:
    for k in _counters:
        _counters[k] = 0


@functools.partial(jax.jit, static_argnames=("cap",))
def _rle_expand(values: jnp.ndarray, ends: jnp.ndarray, cap: int):
    """values/ends: [N, R] (R padded; unused runs carry end=last_end).
    Returns [N, cap] plates: lane j takes values[searchsorted(ends, j,
    'right')] — the run whose half-open [prev_end, end) interval holds j.
    """
    pos = jnp.arange(cap, dtype=ends.dtype)

    def one(vals, end):
        seg = jnp.searchsorted(end, pos, side="right")
        seg = jnp.minimum(seg, vals.shape[0] - 1)
        return vals[seg]

    with tracing.op_scope("decode"):
        return jax.vmap(one)(values, ends)


@functools.partial(jax.jit, static_argnames=("cap",))
def _bitset_expand(packed: jnp.ndarray, cap: int):
    """packed: [N, ceil(cap/8)] uint8 (LSB-first, numpy packbits
    bitorder='little') → bool [N, cap]."""
    with tracing.op_scope("decode"):
        idx = jnp.arange(cap)
        byte = packed[:, idx // 8]
        return ((byte >> (idx % 8).astype(jnp.uint8))
                & 1).astype(jnp.bool_)


def rle_views_to_plate(rle_cols, cap: int, dt,
                       place=jnp.asarray) -> jnp.ndarray:
    """Stack N encoded RLE columns into device plates [N, cap].

    `rle_cols`: list of EncodedColumn with .data (run values) and .runs
    (run lengths). Returns the decoded [N, cap] device array."""
    r_max = max(1, max(len(c.data) for c in rle_cols))
    n = len(rle_cols)
    vals = np.zeros((n, r_max), dtype=dt)
    ends = np.zeros((n, r_max), dtype=np.int64)
    for i, c in enumerate(rle_cols):
        r = len(c.data)
        vals[i, :r] = c.data
        e = np.cumsum(c.runs, dtype=np.int64)
        ends[i, :r] = e
        if r < r_max:
            vals[i, r:] = vals[i, r - 1] if r else 0
            ends[i, r:] = e[-1] if r else 0
        _counters["bytes_encoded"] += int(vals[i].nbytes + ends[i].nbytes)
        _counters["bytes_decoded_equiv"] += int(cap * vals.dtype.itemsize)
        _counters["batches_device_decoded"] += 1
    return _rle_expand(place(vals), place(ends), cap)


# A dictionary of at most this many (padded) slots decodes by selects, a
# wider one by the gather.  Set from a sweep on the chip (PERF.md section
# 6, PR 30; one v5e, a plate of 96 x 131,072 rows, float32): the gather
# takes 105-130 ms whatever the width; the select tree 0.7 / 0.9 / 1.9 /
# 5.6 / 19.6 ms at 16 / 64 / 256 / 1,024 / 4,096 slots, so it never loses
# on run time in the range swept.  What grows is the compile: 0.2 / 0.7 /
# 9 / 33 / 155 s a fusion.  256 is where uint8 codes end (every 4-byte
# value's dictionary, so every float32 one under the TPU's plate policy)
# and the last width whose compile a few dozen executions pay back.
DICT_SELECT_MAX_WIDTH = 256

DECODE_SELECT = "select"
DECODE_GATHER = "gather"


def dict_decode_form(width: int) -> str:
    """Which form `dict_decode` emits for a table of `width` slots a
    batch: the one resolver, asked at trace time (the width is a static
    shape), and by `make_ctx` for the plan's engagement note."""
    return DECODE_SELECT if width <= DICT_SELECT_MAX_WIDTH \
        else DECODE_GATHER


def dict_decode(dicts: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """`dicts[b, codes[b, j]]`, bit for bit: the one decode of a per-batch
    table `dicts` [B, D] by codes [B, cap] (uint8/uint16, every code
    below D).  The scope names the step, not the HLO op.

    Up to `DICT_SELECT_MAX_WIDTH` slots it is a tree of selects over the
    code's bits: level l keeps, of each pair of neighbouring candidates,
    the one bit l of the code names, so D - 1 selects and log2 D bit
    tests an element, all elementwise, which XLA fuses into whatever
    reads the values.  The first level's candidates are the table's
    columns `dicts[:, k:k+1]`, broadcast over the row axis alone, so
    under a mesh the decode shards on the batch axis as the gather did.
    A select passes its operand's bits through (NaN payloads, -0.0).
    Past the constant it is `take_along_axis`, whose cost on the TPU
    hardly depends on D (8-10 ns an element)."""
    with tracing.op_scope("dict_gather"):
        idx = codes.astype(jnp.int32)
        if dict_decode_form(dicts.shape[1]) == DECODE_GATHER:
            return jnp.take_along_axis(dicts, idx, axis=1)
        cands = [dicts[:, k:k + 1] for k in range(dicts.shape[1])]
        level = 0
        while len(cands) > 1:
            bit = ((idx >> level) & 1).astype(jnp.bool_)
            # an odd candidate out has bit `level` clear in every code
            # that can still name it: it passes through
            cands = [jnp.where(bit, cands[i + 1], cands[i])
                     for i in range(0, len(cands) - 1, 2)] \
                + ([cands[-1]] if len(cands) % 2 else [])
            level += 1
        return jnp.broadcast_to(cands[0], codes.shape)


@jax.jit
def _valdict_expand(codes: jnp.ndarray, dicts: jnp.ndarray):
    """codes: [N, cap] uint8/uint16; dicts: [N, D] (D padded per call).
    Lane j of row i takes dicts[i, codes[i, j]]: the eager bind-time
    decode to a resident [N, cap] plate."""
    return dict_decode(dicts, codes)


def _valdict_code_dtype(vd_cols) -> np.dtype:
    """Narrowest common code dtype across the stacked batches (uint16
    VALUE_DICT widening: per-batch code dtypes can mix u8/u16)."""
    return np.dtype(np.uint16) if any(
        c.data.dtype.itemsize > 1 for c in vd_cols) else np.dtype(np.uint8)


def valdict_views_to_plate(vd_cols, cap: int, dt,
                           place=jnp.asarray) -> jnp.ndarray:
    """Stack N value-dict columns into decoded plates [N, cap]: the
    uint8/uint16 codes and the (padded) dictionaries cross the link, the
    decode (`dict_decode`) runs on the device."""
    d_max = max(1, max(len(c.dictionary) for c in vd_cols))
    n = len(vd_cols)
    codes = np.zeros((n, cap), dtype=_valdict_code_dtype(vd_cols))
    dicts = np.zeros((n, d_max), dtype=dt)
    for i, c in enumerate(vd_cols):
        codes[i, :c.data.shape[0]] = c.data
        d = np.asarray(c.dictionary, dtype=dt)
        dicts[i, :d.shape[0]] = d
        _counters["bytes_encoded"] += int(c.data.nbytes + d.nbytes)
        _counters["bytes_decoded_equiv"] += int(cap * dicts.dtype.itemsize)
        _counters["batches_device_decoded"] += 1
    return _valdict_expand(place(codes), place(dicts))


def bitset_views_to_plate(bit_cols, cap: int,
                          place=jnp.asarray) -> jnp.ndarray:
    """Stack N boolean-bitset columns into decoded bool plates [N, cap]."""
    nbytes = (cap + 7) // 8
    n = len(bit_cols)
    packed = np.zeros((n, nbytes), dtype=np.uint8)
    for i, c in enumerate(bit_cols):
        raw = np.asarray(c.data, dtype=np.uint8)
        packed[i, :raw.shape[0]] = raw
        _counters["bytes_encoded"] += int(raw.nbytes)
        _counters["bytes_decoded_equiv"] += int(cap)
        _counters["batches_device_decoded"] += 1
    return _bitset_expand(place(packed), cap)


# ==========================================================================
# Compressed-domain binds: the column STAYS encoded in HBM (CodePlate /
# RlePlate / BitPlate in DeviceTable.columns) and every consumer either
# works on the encoded form directly (code-threshold predicates, per-run
# predicates) or decodes lazily IN-TRACE, where XLA fuses the expansion
# into the consuming kernel — a decoded capacity-row plate never exists.
# ==========================================================================

def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def compressed_fallback(reason: str, n: int = 1, table=None) -> None:
    """Count a decode-first reroute (a column that did NOT bind in the
    compressed domain), itemized by reason so every reroute is visible
    on the scan dashboard: compressed_fallback_<reason> + total.

    With `table` (the ColumnTableData the reroute happened on) the count
    also lands in a per-table registry — the background compactor's
    trigger signal (storage/compact.py picks tables whose FOLDABLE
    reasons keep firing) and the per-table triage view that
    stats_service.encoding_mix surfaces."""
    from snappydata_tpu.observability.metrics import global_registry

    reg = global_registry()
    reg.inc("compressed_fallbacks", n)
    reg.inc("compressed_fallback_" + reason, n)
    if table is not None:
        with _table_fb_lock:
            d = _table_fallbacks.setdefault(table, {})
            d[reason] = d.get(reason, 0) + n


# per-table fallback tallies: weak keys so a dropped table takes its
# tally with it.  Guarded by a declared LEAF lock (nothing is acquired
# under it), read by the compactor and the stats service.
_table_fallbacks: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_table_fb_lock = locks.named_lock("storage.table_fallbacks")


def table_fallbacks(table) -> Dict[str, int]:
    """Per-table compressed-fallback counts since the last reset."""
    with _table_fb_lock:
        return dict(_table_fallbacks.get(table, ()))


def reset_table_fallbacks(table) -> None:
    """Zero a table's tally — the compactor calls this after a rewrite
    pass so the next window measures only post-compaction reroutes."""
    with _table_fb_lock:
        _table_fallbacks.pop(table, None)


def code_plates(vd_cols, b: int, cap: int, dt, place=jnp.asarray):
    """VALUE_DICT views → a resident CodePlate plus the HOST-side sorted
    dictionary stack the bind-time sarg skipper reads.

    Returns (CodePlate, host_dicts [b, Dp] float64, sizes [b] int64).
    Dictionary rows pad by REPEATING the last value so each row stays
    sorted — the property the in-trace searchsorted threshold
    translation and the host membership probe both rely on.
    `place` is the bind's device-placement hook: under a mesh the plate
    leaves shard on the batch axis like decoded plates (codes AND
    per-batch dictionaries are [b, ...]-leading)."""
    d_pad = _next_pow2(max(1, max(len(c.dictionary) for c in vd_cols)))
    codes = np.zeros((b, cap), dtype=_valdict_code_dtype(vd_cols))
    dicts = np.zeros((b, d_pad), dtype=dt)
    host = np.zeros((b, d_pad), dtype=np.float64)
    sizes = np.zeros(b, dtype=np.int64)
    for i, c in enumerate(vd_cols):
        codes[i, :c.data.shape[0]] = c.data
        d = np.asarray(c.dictionary, dtype=dt)
        dicts[i, :d.shape[0]] = d
        if d.shape[0] and d.shape[0] < d_pad:
            dicts[i, d.shape[0]:] = d[-1]
        host[i, :d.shape[0]] = np.asarray(c.dictionary, dtype=np.float64)
        if d.shape[0] and d.shape[0] < d_pad:
            host[i, d.shape[0]:] = host[i, d.shape[0] - 1]
        sizes[i] = d.shape[0]
        _counters["bytes_encoded"] += int(c.data.nbytes + d.nbytes)
        _counters["bytes_decoded_equiv"] += int(cap * d.dtype.itemsize)
        _counters["batches_device_decoded"] += 1
        _counters["batches_code_bound"] += 1
    return (CodePlate(place(codes), place(dicts)),
            host, sizes)


def rle_plates(rle_cols, b: int, cap: int, dt,
               place=jnp.asarray) -> RlePlate:
    """RUN_LENGTH views → a resident RlePlate (run values + cumulative
    end offsets, O(runs) bytes in HBM instead of O(cap))."""
    r_pad = _next_pow2(max(1, max(len(c.data) for c in rle_cols)))
    vals = np.zeros((b, r_pad), dtype=dt)
    ends = np.zeros((b, r_pad), dtype=np.int64)
    for i, c in enumerate(rle_cols):
        r = len(c.data)
        vals[i, :r] = c.data
        e = np.cumsum(c.runs, dtype=np.int64)
        ends[i, :r] = e
        if r and r < r_pad:
            vals[i, r:] = vals[i, r - 1]
            ends[i, r:] = e[-1]
        _counters["bytes_encoded"] += int(
            c.data.nbytes + np.asarray(c.runs).nbytes)
        _counters["bytes_decoded_equiv"] += int(cap * vals.dtype.itemsize)
        _counters["batches_device_decoded"] += 1
        _counters["batches_code_bound"] += 1
    return RlePlate(place(vals), place(ends))


def bit_plates(bit_cols, b: int, cap: int, place=jnp.asarray) -> BitPlate:
    """BOOLEAN_BITSET views → a resident BitPlate (8x fewer HBM bytes)."""
    nbytes = (cap + 7) // 8
    packed = np.zeros((b, nbytes), dtype=np.uint8)
    for i, c in enumerate(bit_cols):
        raw = np.asarray(c.data, dtype=np.uint8)
        packed[i, :raw.shape[0]] = raw
        _counters["bytes_encoded"] += int(raw.nbytes)
        _counters["bytes_decoded_equiv"] += int(cap)
        _counters["batches_device_decoded"] += 1
        _counters["batches_code_bound"] += 1
    return BitPlate(place(packed))


# --- in-trace consumers ---------------------------------------------------

def code_values(plate: CodePlate) -> jnp.ndarray:
    """Lazy decode of a CodePlate (`dict_decode`), which XLA fuses into
    whatever consumes the values (the fused decode+filter+aggregate form
    of the default scan) and drops where nothing does."""
    return dict_decode(plate.dicts, plate.codes)


def rle_values(plate: RlePlate, cap: int) -> jnp.ndarray:
    """Lazy in-trace expansion of an RlePlate to [B, cap] values."""
    return _rle_expand(plate.values, plate.ends, cap)


def bit_values(plate: BitPlate, cap: int) -> jnp.ndarray:
    """Lazy in-trace unpack of a BitPlate to [B, cap] bools."""
    return _bitset_expand(plate.packed, cap)


@tracing.op_scope("filter")
def code_cmp_mask(op: str, plate: CodePlate, lit) -> jnp.ndarray:
    """Code-domain lowering of `column OP literal` over a CodePlate:
    the literal translates to per-batch code thresholds through the
    SORTED dictionaries (one searchsorted per batch, O(B log D)) and the
    comparison runs on the small integer codes — the decoded plate never
    materializes and per-row work touches 1-2 bytes, not 8.

    Exactness: the dictionary and the literal are both promoted to
    their common compare dtype first, so boundary behavior is
    bit-identical to comparing the decoded values (f32 dictionaries vs
    f64 literals compare in f64, exactly like the decoded plate would).
    Out-of-dictionary equality literals yield a constant-false mask
    (code -1 matches nothing); NaN literals follow IEEE semantics
    (every comparison false except !=)."""
    codes = plate.codes.astype(jnp.int32)
    cd = jnp.result_type(plate.dicts.dtype, jnp.asarray(lit).dtype)
    d = plate.dicts.astype(cd)
    v = jnp.asarray(lit).astype(cd)
    if op in ("=", "!="):
        pos = jax.vmap(
            lambda row: jnp.searchsorted(row, v, side="left"))(d)
        posc = jnp.clip(pos, 0, d.shape[1] - 1).astype(jnp.int32)
        hit = jnp.take_along_axis(d, posc[:, None], axis=1)[:, 0] == v
        code_eq = jnp.where(hit, posc, -1)
        return codes == code_eq[:, None] if op == "=" \
            else codes != code_eq[:, None]
    # values >= lit  <=>  code >= searchsorted(dict, lit, left); the
    # right-side variants shift the threshold past equal values
    side = "left" if op in (">=", "<") else "right"
    pos = jax.vmap(
        lambda row: jnp.searchsorted(row, v, side=side))(d)
    pos = pos.astype(jnp.int32)
    m = codes >= pos[:, None] if op in (">=", ">") \
        else codes < pos[:, None]
    if op in ("<", "<=") and jnp.issubdtype(cd, jnp.floating):
        # x < NaN is False, but NaN sorts past every dictionary entry
        # (threshold = D → all codes pass) — guard explicitly
        m = m & ~jnp.isnan(v)
    return m


@tracing.op_scope("filter")
def rle_cmp_mask(fn, plate: RlePlate, lit, cap: int) -> jnp.ndarray:
    """Run-arithmetic filter over an RlePlate: evaluate the predicate
    per RUN (O(runs) compares) and expand the boolean run mask — the
    full-width value plate is never produced."""
    run_mask = fn(plate.values, lit)
    return _rle_expand(run_mask, plate.ends, cap)


def rle_expand_runs(run_array: jnp.ndarray, ends: jnp.ndarray,
                    cap: int) -> jnp.ndarray:
    """Expand any per-run [B, R] array (values, boolean run masks) to
    row space [B, cap] over the given cumulative end offsets."""
    return _rle_expand(run_array, ends, cap)


def rle_run_lengths(ends: jnp.ndarray) -> jnp.ndarray:
    """Per-run lengths from cumulative end offsets (padded runs repeat
    the last end, so their length is exactly 0)."""
    prev = jnp.concatenate(
        [jnp.zeros_like(ends[:, :1]), ends[:, :-1]], axis=1)
    return ends - prev


def rle_masked_sum_count(plate: RlePlate, run_mask: jnp.ndarray):
    """O(runs) filter+aggregate arithmetic: with a per-run boolean mask,
    count = Σ len·mask and sum = Σ value·len·mask — multiply values by
    run lengths instead of touching O(rows) lanes.  Valid only when the
    surviving row set is run-aligned (no row-level deletes inside runs —
    the code-domain bind already excludes delta-bearing batches).

    Status: a TESTED building block (equivalence-asserted against the
    expanded path in tests/test_compressed_domain.py), not yet on the
    default aggregate path — the packed-family reduction consumes row
    plates with row-level validity, so wiring this in needs a
    run-alignment proof over the whole filter; the engine's WIRED run
    arithmetic today is the per-run predicate lane (rle_cmp_mask)."""
    lens = rle_run_lengths(plate.ends)
    lm = jnp.where(run_mask, lens, 0)
    count = jnp.sum(lm)
    total = jnp.sum(plate.values.astype(jnp.float64) * lm)
    return total, count

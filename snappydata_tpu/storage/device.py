"""Manifest → stacked device arrays (the input side of every jitted plan).

TPU equivalent of the reference's ColumnBatchIterator + per-column decoders
feeding whole-stage-codegen (ColumnTableScan.doProduce core/.../columnar/
ColumnTableScan.scala:186): instead of a generated scalar loop pulling one
batch at a time, a table snapshot is materialized as ONE [num_batches,
capacity] device array per referenced column plus a shared validity mask
(row-count + delete-mask + delta merges already applied). Batch count is
padded to a power of two so the jitted plan's input shapes — and therefore
the XLA executable — are stable as the table grows.

Per-batch min/max stats ride along host-side for predicate batch skipping
(ref: stats-row filter codegen, columnBatchesSkipped metric,
ColumnTableScan.scala:115-130).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
import weakref
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from snappydata_tpu import types as T
from snappydata_tpu.observability import tracing
from snappydata_tpu.storage.table_store import ColumnTableData, Manifest
from snappydata_tpu.utils import locks


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def batch_bucket(n: int) -> int:
    """Padded BATCH-axis size: the smallest of {2^k, 1.5 * 2^k} >= n.
    Pure pow2 bucketing wasted up to ~50% of every device pass on dead
    padded batches (TPC-H SF4: 184 batches -> 256, +39% rows swept by
    every reduction); the intermediate 1.5x buckets cap the waste at
    ~33% while still bounding executable shapes to two per octave."""
    if n <= 1:
        return 1
    p = 1 << (n - 1).bit_length()
    return p * 3 // 4 if p * 3 // 4 >= n else p


# --- tiled scans: bind a WINDOW of the batch axis ------------------------
# For tables whose decoded columns exceed the HBM budget, the session
# streams scan units (column batches + row-buffer chunks) through the same
# compiled program tile by tile (ref: batch-at-a-time iteration in
# ColumnFormatIterator, SURVEY §5 "long-context" — table ≫ HBM).

_scan_windows: contextvars.ContextVar = contextvars.ContextVar(
    "scan_windows", default=None)


@contextlib.contextmanager
def scan_window(data, lo: int, hi: int, manifest=None, tile_units=None):
    """Restrict build_device_table for `data` to units [lo, hi).
    `manifest` pins one snapshot across a multi-tile pass so concurrent
    mutations can't make tiles disagree about the table version.
    `tile_units` is the NOMINAL window width of the pass — the last
    window may be truncated, and current_scan_scale needs the nominal
    width to compute the true tile count."""
    cur = dict(_scan_windows.get() or {})
    cur[id(data)] = (int(lo), int(hi), manifest,
                     int(tile_units) if tile_units else int(hi - lo))
    tok = _scan_windows.set(cur)
    try:
        yield
    finally:
        _scan_windows.reset(tok)


def scan_window_active() -> bool:
    """True inside any scan_window context (a tiled pass is binding)."""
    return bool(_scan_windows.get())


def scan_unit_count(data, manifest=None) -> int:
    """Number of bindable units (column batches + row-buffer chunks)."""
    if manifest is None:
        from snappydata_tpu.storage import mvcc

        manifest = mvcc.snapshot_of(data)
    n_chunks = -(-manifest.row_count // data.capacity) \
        if manifest.row_count > 0 else 0
    return len(manifest.views) + n_chunks


@dataclasses.dataclass
class DeviceTable:
    schema: T.Schema
    num_batches: int           # padded
    capacity: int
    valid: jnp.ndarray         # bool [B, C]
    # col_idx -> [B, C] decoded plate, OR a compressed-domain plate
    # (device_decode.CodePlate/RlePlate/BitPlate) when the column stays
    # resident encoded — consumers branch structurally
    columns: Dict[int, jnp.ndarray]
    dictionaries: Dict[int, np.ndarray]      # string col -> host values
    stats_min: Dict[int, np.ndarray]         # numeric col -> host [B]
    stats_max: Dict[int, np.ndarray]
    total_rows: int
    nulls: Dict[int, Optional[jnp.ndarray]] = dataclasses.field(
        default_factory=dict)                # col_idx -> bool [B, C] or None
    # col_idx -> (sorted host dicts [B, Dp] f64, sizes [B]) for every
    # column with VALUE_DICT batches — the dictionary-domain batch
    # skipper probes equality literals here at bind time (sizes[i] == 0
    # means batch i carries no dictionary: always keep)
    dict_domains: Dict[int, tuple] = dataclasses.field(default_factory=dict)

    def column(self, idx: int) -> jnp.ndarray:
        return self.columns[idx]


def _compressed_mode(is_str: bool, dec_exact: bool, use_dd: bool,
                     cols_enc, any_delta: bool, has_row_chunks: bool,
                     code_ok: bool, count: bool = False,
                     table=None) -> Optional[str]:
    """Per-column compressed-domain decision: 'dict' | 'rle' | 'bitset'
    when the column can stay resident encoded, None for a decoded bind.
    With count=True (the cache-miss build), every decode-first reroute
    of a compressible column is counted by reason
    (compressed_fallback_*); under scan_compressed_domain='on' even
    never-compressible columns count, so a misconfigured table is
    diagnosable from the dashboard."""
    from snappydata_tpu import config
    from snappydata_tpu.storage.device_decode import compressed_fallback
    from snappydata_tpu.storage.encoding import Encoding

    knob = str(config.global_properties().get(
        "scan_compressed_domain", "auto") or "auto").lower()
    comp = {Encoding.VALUE_DICT: "dict", Encoding.RUN_LENGTH: "rle",
            Encoding.BOOLEAN_BITSET: "bitset"}
    encs = {c.encoding for c in cols_enc}
    compressible = bool(encs & set(comp))
    forced = knob == "on"
    if is_str or not cols_enc:
        return None   # string codes ARE the compressed domain already
    if knob == "off" or knob not in ("on", "auto"):
        if count and compressible:
            compressed_fallback("disabled", table=table)
        return None

    def reject(reason: str, always: bool = False) -> None:
        if count and (compressible or (forced and always)):
            compressed_fallback(reason, table=table)

    if dec_exact:
        reject("decimal_exact")
        return None
    if not use_dd:
        reject("device_decode_off")
        return None
    if not code_ok:
        reject("join_key")
        return None
    if any_delta:
        reject("deltas")
        return None
    if has_row_chunks:
        reject("row_buffer")
        return None
    if len(encs) == 1 and next(iter(encs)) in comp:
        return comp[next(iter(encs))]
    reject("mixed_encoding" if compressible else "not_encoded",
           always=True)
    return None


def build_device_table(data: ColumnTableData, manifest: Optional[Manifest],
                       col_indices: Sequence[int],
                       code_ok: bool = True) -> DeviceTable:
    """Materialize `col_indices` of a snapshot on device, with caching keyed
    on manifest version (so repeated queries over an unchanged table upload
    nothing).  `code_ok=False` (device-join relations, whose cached build
    artifacts index flat decoded layouts) forces decoded plates."""
    from snappydata_tpu.parallel.mesh import MeshContext

    ctx = MeshContext.current()
    # shared unit-splitting contract with the host fallback (_scan_units):
    # pinned snapshot, batches-then-row-chunks order, window slice
    manifest, views, row_chunks, window = _scan_units(data, manifest)
    # cache key includes the mesh token (placement differs under a mesh;
    # token is process-unique, unlike id() which gets reused after GC)
    # and the scan window (tiles of one version coexist under the LRU)
    cache_key = (manifest.version, ctx.token if ctx else None, window)
    cache = data._device_cache.setdefault(cache_key, {})
    # prune stale versions AND stale mesh placements (keep only this exact
    # placement + the previous version of it) so a loop that recreates
    # meshes doesn't pin duplicate device copies of every column —
    # EXCEPT versions an active snapshot pin holds: a long pinned scan
    # re-binding its (old) epoch per tile must not have its plates
    # evicted by concurrent ingest binding newer versions (the
    # degradation ladder can still trim them via mvcc.trim_unpinned)
    from snappydata_tpu.storage import mvcc as _mvcc

    _pinned_vers = _mvcc.pinned_versions(data)
    # list() snapshots are C-atomic under the GIL: a prefetch worker
    # (storage/prefetch.py) inserts window entries concurrently, and a
    # plain comprehension over the live dict would raise RuntimeError
    for k in [k for k in list(data._device_cache)
              if k != cache_key and k[0] not in _pinned_vers
              and not (k[1] == cache_key[1]
                       and k[0] >= manifest.version - 1)]:
        data._device_cache.pop(k, None)
        _cache_budget.forget(data._device_cache, k)
    if window is not None and not _cache_budget.enabled():
        # no byte budget to evict for us: a tile pass must not accumulate
        # every window's arrays (the table is oversized by definition —
        # that would re-materialize it on device); keep only this tile.
        # The session's double-buffered tile pass still holds the
        # PREVIOUS tile's plates alive through its in-flight dispatch —
        # dropping the cache entry here only releases our reference, so
        # peak residency is bounded at two tiles, exactly the pipeline
        # depth the pass throttles to.
        # …EXCEPT windows a live prefetch pass owns (storage/prefetch):
        # evicting the look-ahead tile the worker just uploaded would
        # turn the prefetcher into a strict slowdown
        from snappydata_tpu.storage import prefetch as _prefetch

        _kept = _prefetch.keep_windows(data)
        for k in [k for k in list(data._device_cache)
                  if k != cache_key and k[2] is not None
                  and k[2] not in _kept]:
            data._device_cache.pop(k, None)
            _cache_budget.forget(data._device_cache, k)

    schema = data.schema
    cap = data.capacity
    b_actual = len(views) + len(row_chunks)
    b = batch_bucket(b_actual) if data_pow2() else max(1, b_actual)
    b = max(b, 1)
    if ctx is not None:
        # batch axis is the sharded axis: pad to a MESH-DIVISIBLE ladder
        # size (shard_bucket keeps the padded size on the same
        # {2^k, 1.5·2^k} ladder the single-device bind uses, so a
        # resharded table reuses executable shapes instead of
        # re-specializing every static key)
        from snappydata_tpu.parallel.mesh import round_up_to, shard_bucket

        b = shard_bucket(b, ctx.num_devices) if data_pow2() \
            else round_up_to(b, ctx.num_devices)

    # device.transfer failpoint: one hit per table build (not per column
    # — the build is the unit a caller can retry); an injected raise
    # models a flaky accelerator runtime rejecting the host→HBM upload
    from snappydata_tpu.fault import failpoints

    failpoints.hit("device.transfer")

    # evidence for the executor's `bind` span, where this build runs
    # under one: bytes handed to the device and host time inside those
    # calls go on it as attrs — no child span, so `bind`'s self time
    # stays what it was.  Untraced binds, prefetch workers and callers
    # outside a `bind` span pay nothing and leave no stray attr.
    sp = tracing.current_span()
    if sp is not None and sp.name != "bind":
        sp = None

    def _counted(put):
        if sp is None:
            return put

        def counted(host_array):
            t0 = time.perf_counter()
            out = put(host_array)
            sp.add("upload_ms", (time.perf_counter() - t0) * 1e3)
            sp.add("upload_bytes", int(host_array.nbytes))
            return out
        return counted

    def _place_on(host_array):
        from snappydata_tpu.parallel.mesh import shard_batches

        return shard_batches(host_array, ctx) if ctx is not None \
            else jnp.asarray(host_array)

    _place = _counted(_place_on)
    _upload = _counted(jnp.asarray)   # the unsharded assembly lanes
    plates = [0, 0]   # column plates [built, found in the device cache]

    if "valid" in cache:
        # a partially-filled entry pins the padded batch shape: a
        # MIGRATED cache (live mesh rebalance) keeps its old-mesh
        # padding, and a column bound fresh into it must match — mixing
        # paddings inside one entry produced (old_b, cap) valid vs
        # (new_b, cap) plates (found by the rebalance-under-traffic
        # test).  Old paddings stay shard-able: migration only runs
        # when the new mesh size divides them.
        b = int(cache["valid"].shape[0])
    if "valid" not in cache:
        valid = np.zeros((b, cap), dtype=np.bool_)
        for i, v in enumerate(views):
            valid[i] = v.live_mask()
        for j, (_, take) in enumerate(row_chunks):
            valid[len(views) + j, :take] = True
        if window is not None:  # tile row count ≠ manifest total
            cache["nrows"] = int(valid.sum())
        cache["valid"] = _place(valid)

    columns: Dict[int, jnp.ndarray] = {}
    dicts: Dict[int, np.ndarray] = {}
    stats_min: Dict[int, np.ndarray] = {}
    stats_max: Dict[int, np.ndarray] = {}
    nulls: Dict[int, Optional[jnp.ndarray]] = {}
    dict_domains: Dict[int, tuple] = {}
    for ci in col_indices:
        f = schema.fields[ci]
        if isinstance(f.dtype, T.StructType) \
                and struct_device_eligible(f.dtype):
            # STRUCT: one [B, C] plate per field (string fields as
            # per-field dictionary codes) — element_at field access
            # becomes a static plate pick in the compiled program
            key = ("scol", ci)
            plates[key in cache] += 1
            if key not in cache:
                cache[key] = _build_struct_column(
                    data, manifest, views, row_chunks, ci, f, b, cap,
                    _place)
            columns[ci], stats_min[ci], stats_max[ci], nulls[ci] = cache[key]
            continue
        if isinstance(f.dtype, T.MapType) and map_device_eligible(f.dtype):
            # MAP<STRING, V>: key-code plates + value plates (numeric
            # values as-is, string values as codes) + lengths +
            # value-null bits — feeds the device element_at lowering
            key = ("mcol", ci)
            plates[key in cache] += 1
            if key not in cache:
                cache[key] = _build_map_column(
                    data, manifest, views, row_chunks, ci, f, b, cap,
                    _place)
            columns[ci], stats_min[ci], stats_max[ci], nulls[ci] = cache[key]
            continue
        if isinstance(f.dtype, T.ArrayType) and (
                T.is_numeric(f.dtype.element)
                or f.dtype.element.name == "string"):
            # fixed-width device layout for numeric AND string arrays:
            # value plates [B, C, L] (string elements ride as int32
            # dictionary codes, like scalar string columns) + lengths
            # [B, C] + element-null bits — feeds the device lowering of
            # size/element_at/array_contains (ref: SerializedArray
            # fixed-width fast path)
            key = ("acol", ci)
            plates[key in cache] += 1
            if key not in cache:
                cache[key] = _build_array_column(
                    data, manifest, views, row_chunks, ci, f, b, cap,
                    _place)
            columns[ci], stats_min[ci], stats_max[ci], nulls[ci] = cache[key]
            continue
        is_str = f.dtype.name == "string"
        if is_str:
            dicts[ci] = data.dictionary(ci)
        from snappydata_tpu import config
        from snappydata_tpu.storage.encoding import (Encoding,
                                                     decode_validity)

        dt = f.dtype.device_dtype()
        # exact decimals: HOST plates are float64 (the SQL value
        # domain — WAL, deltas, stats, hosteval all ride it); the
        # DEVICE plate is the scaled int64 unscaled value, converted
        # here at bind (types.DecimalType docstring)
        dec_exact = f.dtype.name == "decimal" and dt.kind == "i"
        # compressed-domain eligibility is mesh-agnostic: encoded plates
        # are [B, ...]-leading pytrees, so they shard over the mesh the
        # same way decoded plates do (per-device HBM keeps the encoded
        # capacity win — the decoded plate never materializes globally)
        use_dd_col = (not is_str and not dec_exact
                      and config.global_properties().device_decode)
        cols_enc = [v.batch.columns[ci] for v in views]
        # only deltas that target THIS column disqualify its encoded
        # form (update deltas replace values; deletes ride live_mask)
        any_delta = any(any(d[0] == ci for d in v.deltas) for v in views)
        cd_mode = _compressed_mode(is_str, dec_exact, use_dd_col,
                                   cols_enc, any_delta, bool(row_chunks),
                                   code_ok)
        key = ("ccol", ci) if cd_mode else ("col", ci)
        plates[key in cache] += 1
        if key not in cache:
            # itemized fallback counting happens exactly once per build
            # (cache miss), decoded OR compressed — so every decode-first
            # reroute of a compressible column shows up
            _compressed_mode(is_str, dec_exact, use_dd_col,
                             cols_enc, any_delta, bool(row_chunks),
                             code_ok, count=True, table=data)
        if cd_mode and key not in cache:
            # compressed-domain bind: the column stays RESIDENT encoded;
            # predicates run on codes/runs, values decode lazily
            # in-trace (engine/exprs.py) — no decoded plate in HBM
            from snappydata_tpu.storage import device_decode as _dd
            from snappydata_tpu.storage import bitmask

            null_mask = np.zeros((b, cap), dtype=np.bool_)
            any_null = False
            smin = np.full(b, np.nan)
            smax = np.full(b, np.nan)
            for i, (v, col) in enumerate(zip(views, cols_enc)):
                nm = v.null_mask(ci)
                if nm is not None:
                    null_mask[i] = nm
                    any_null = True
                st = col.stats
                if st is not None and st.min is not None:
                    smin[i], smax[i] = float(st.min), float(st.max)
                elif cd_mode == "dict" and len(col.dictionary):
                    smin[i] = float(np.min(col.dictionary))
                    smax[i] = float(np.max(col.dictionary))
                elif cd_mode == "rle" and len(col.data):
                    smin[i] = float(np.min(col.data))
                    smax[i] = float(np.max(col.data))
                elif cd_mode == "bitset" and col.num_rows:
                    bits = bitmask.unpack(col.data, col.num_rows)
                    smin[i] = float(bits.min())
                    smax[i] = float(bits.max())
            if cd_mode == "dict":
                plate, host_dicts, dict_sizes = _dd.code_plates(
                    cols_enc, b, cap, dt, place=_place)
                cache[("dictdom", ci)] = (host_dicts, dict_sizes)
            elif cd_mode == "rle":
                plate = _dd.rle_plates(cols_enc, b, cap, dt, place=_place)
            else:
                plate = _dd.bit_plates(cols_enc, b, cap, place=_place)
            cache[key] = (plate, smin, smax,
                          _place(null_mask) if any_null else None)
        if key not in cache:
            stacked = np.zeros((b, cap), dtype=dt)
            null_mask = np.zeros((b, cap), dtype=np.bool_)
            any_null = False
            smin = np.full(b, np.nan)
            smax = np.full(b, np.nan)
            # in-trace decode: RLE / bitset batches without deltas ship
            # their ENCODED arrays to the device and expand there (ref
            # decode-at-scan: ColumnTableScan.scala:684). Mesh binds keep
            # host decode on THIS decoded-plate path (the eager .at[].set
            # assembly below places unsharded) — fully-encoded columns
            # skip it entirely via the sharded compressed plates above.
            # Encoded decimal forms are host-domain floats, so the exact
            # path keeps host decode + scaled conversion.
            use_dd = use_dd_col and ctx is None
            dd_rle: list = []      # (batch row, EncodedColumn)
            dd_bits: list = []
            dd_vd: list = []       # VALUE_DICT: uint8 codes + value dict
            for i, v in enumerate(views):
                col = v.batch.columns[ci]
                device_decodable = (
                    use_dd and not v.deltas
                    and col.encoding in (Encoding.RUN_LENGTH,
                                         Encoding.BOOLEAN_BITSET,
                                         Encoding.VALUE_DICT))
                nm = v.null_mask(ci)  # delta-aware (updates can set/clear)
                if nm is not None:
                    null_mask[i] = nm
                    any_null = True
                st = col.stats
                if st is not None and not v.deltas and not is_str \
                        and st.min is not None:
                    smin[i], smax[i] = float(st.min), float(st.max)
                elif device_decodable:
                    # stats over the compact encoded form: a SUPERSET of
                    # the live range (deletes ignored), so predicate
                    # batch-skipping stays conservative-correct
                    if col.encoding == Encoding.RUN_LENGTH and \
                            len(col.data):
                        smin[i] = float(np.min(col.data))
                        smax[i] = float(np.max(col.data))
                    elif col.encoding == Encoding.BOOLEAN_BITSET and \
                            col.num_rows:
                        from snappydata_tpu.storage import bitmask

                        bits = bitmask.unpack(col.data, col.num_rows)
                        smin[i] = float(bits.min())
                        smax[i] = float(bits.max())
                    elif col.encoding == Encoding.VALUE_DICT and \
                            len(col.dictionary):
                        smin[i] = float(np.min(col.dictionary))
                        smax[i] = float(np.max(col.dictionary))
                if device_decodable:
                    if col.encoding == Encoding.RUN_LENGTH:
                        dd_rle.append((i, col))
                    elif col.encoding == Encoding.VALUE_DICT:
                        dd_vd.append((i, col))
                    else:
                        dd_bits.append((i, col))
                    continue
                decoded = v.decoded_column(ci)
                stacked[i] = T.decimal_to_unscaled(f.dtype, decoded) \
                    if dec_exact else decoded
                if not (st is not None and not v.deltas and not is_str
                        and st.min is not None) \
                        and not is_str and v.batch.num_rows:
                    live = decoded[v.live_mask()]
                    if live.size:
                        smin[i], smax[i] = float(live.min()), float(live.max())
            for j, (pos, take) in enumerate(row_chunks):
                src = manifest.row_arrays[ci][pos:pos + take]
                chunk_nulls = None
                if manifest.row_nulls and manifest.row_nulls[ci] is not None:
                    chunk_nulls = manifest.row_nulls[ci][pos:pos + take]
                if is_str:
                    lookup = data._dict_lookup[ci]
                    # None (SQL NULL) maps to code 0; nullability is carried
                    # by validity, not the code stream
                    vals = np.fromiter(
                        (lookup[x] if x is not None else 0 for x in src),
                        dtype=np.int32, count=take)
                    none_mask = np.fromiter((x is None for x in src),
                                            dtype=np.bool_, count=take)
                    chunk_nulls = none_mask if chunk_nulls is None \
                        else (chunk_nulls | none_mask)
                elif dec_exact:
                    vals = T.decimal_to_unscaled(f.dtype, src)
                else:
                    vals = np.asarray(src).astype(dt)
                if chunk_nulls is not None and chunk_nulls.any():
                    null_mask[len(views) + j, :take] = chunk_nulls
                    any_null = True
                stacked[len(views) + j, :take] = vals
                if not is_str and take:
                    # stats stay in the HOST (unscaled) domain — that's
                    # what sargable predicate literals compare against
                    stat_src = np.asarray(src, dtype=np.float64) \
                        if dec_exact else vals
                    smin[len(views) + j] = float(stat_src.min())
                    smax[len(views) + j] = float(stat_src.max())
            if dd_rle or dd_bits or dd_vd:
                # only the NON-device-decoded rows cross the link as
                # decoded plates: upload them compactly and assemble the
                # full [b, cap] plate on device (HBM-side scatter copies,
                # not PCIe transfer)
                dd_set = {i for i, _ in dd_rle} | {i for i, _ in dd_bits} \
                    | {i for i, _ in dd_vd}
                keep = [i for i in range(b) if i not in dd_set]
                placed = jnp.zeros((b, cap), dtype=dt)
                nonzero_keep = [i for i in keep if i < b_actual]
                if nonzero_keep:
                    placed = placed.at[np.array(nonzero_keep)].set(
                        _upload(stacked[np.array(nonzero_keep)]))
                if dd_rle:
                    from snappydata_tpu.storage.device_decode import \
                        rle_views_to_plate

                    idxs = np.array([i for i, _ in dd_rle])
                    dec = rle_views_to_plate([c for _, c in dd_rle],
                                             cap, dt, place=_upload)
                    placed = placed.at[idxs].set(dec.astype(dt))
                if dd_bits:
                    from snappydata_tpu.storage.device_decode import \
                        bitset_views_to_plate

                    idxs = np.array([i for i, _ in dd_bits])
                    dec = bitset_views_to_plate([c for _, c in dd_bits],
                                                cap, place=_upload)
                    placed = placed.at[idxs].set(dec.astype(dt))
                if dd_vd:
                    from snappydata_tpu.storage.device_decode import \
                        valdict_views_to_plate

                    idxs = np.array([i for i, _ in dd_vd])
                    dec = valdict_views_to_plate([c for _, c in dd_vd],
                                                 cap, dt, place=_upload)
                    placed = placed.at[idxs].set(dec)
            else:
                placed = _place(stacked)
            cache[key] = (placed, smin, smax,
                          _place(null_mask) if any_null else None)
            if not is_str:
                dom = _dict_domain(views, cols_enc, ci, b)
                if dom is not None:
                    cache[("dictdom", ci)] = dom
        columns[ci], stats_min[ci], stats_max[ci], nulls[ci] = cache[key]
        dom = cache.get(("dictdom", ci))
        if dom is not None:
            dict_domains[ci] = dom

    if sp is not None:
        sp.add("plates_built", plates[0])
        sp.add("plates_cached", plates[1])
    if _cache_budget.enabled():
        _cache_budget.touch(data._device_cache, cache_key,
                            _entry_bytes(cache), data=data)
    return DeviceTable(schema, b, cap, cache["valid"], columns, dicts,
                       stats_min, stats_max,
                       cache.get("nrows", manifest.total_rows()), nulls,
                       dict_domains)


def _dict_domain(views, cols_enc, ci: int, b: int):
    """(sorted host dicts [b, Dp] f64, sizes [b]) of a column's
    VALUE_DICT batches — the dictionary-domain batch skipper's probe
    surface.  Batches without a usable dictionary (other encodings, or
    update deltas touching this column) report size 0 = always keep."""
    from snappydata_tpu.storage.encoding import Encoding

    vd = [(i, c) for i, (v, c) in enumerate(zip(views, cols_enc))
          if c.encoding == Encoding.VALUE_DICT
          and not any(d[0] == ci for d in v.deltas)
          and c.dictionary is not None and len(c.dictionary)]
    if not vd:
        return None
    d_pad = max(len(c.dictionary) for _, c in vd)
    host = np.zeros((b, d_pad), dtype=np.float64)
    sizes = np.zeros(b, dtype=np.int64)
    for i, c in vd:
        d = np.asarray(c.dictionary, dtype=np.float64)
        host[i, :d.shape[0]] = d
        if d.shape[0] < d_pad:
            host[i, d.shape[0]:] = d[-1]
        sizes[i] = d.shape[0]
    return host, sizes


def numeric_key_domain(data, ci: int, max_card: int):
    """Table-global sorted value domain of a numeric column at the
    current (pinned) snapshot — the code space of the vdict group-by
    lane (engine/executor._emit_aggregate).  A group index computed as
    searchsorted(domain, value) is dense and data-independent across
    batches, so dict-encoded key plates group by PURE CODE ARITHMETIC
    (per-batch codes remapped through this domain) with no gather.

    Returned in the column's DEVICE dtype: the per-batch plate
    dictionaries and decoded plates are cast to the same dtype from the
    same host values, so searchsorted hits are exact even where f32
    rounding collapses distinct f64 inputs (the decoded path would
    merge those groups identically).

    Returns None — the caller's cue to keep the generic hash group-by —
    when the column exceeds `max_card` distinct values or the domain
    contains NaN (NaN breaks searchsorted ordering).  Cached per
    (manifest version, column); stale versions evict on access."""
    from snappydata_tpu.storage import mvcc
    from snappydata_tpu.storage.encoding import Encoding

    man = mvcc.snapshot_of(data)
    cache = data.__dict__.setdefault("_key_domain_cache", {})
    key = (man.version, ci, max_card)
    if key in cache:
        return cache[key]
    dt = data.schema.fields[ci].dtype.device_dtype()
    parts = []
    for v in man.views:
        col = v.batch.columns[ci]
        untouched = not any(d[0] == ci for d in v.deltas)
        if untouched and col.encoding == Encoding.VALUE_DICT \
                and col.dictionary is not None:
            parts.append(np.asarray(col.dictionary))
        elif untouched and col.encoding == Encoding.RUN_LENGTH:
            parts.append(np.asarray(col.data))
        else:
            # mixed encodings / deltas: the domain must still cover the
            # values a decoded fallback bind will group by
            parts.append(np.asarray(v.decoded_column(ci)))
    if man.row_count:
        parts.append(np.asarray(man.row_arrays[ci][:man.row_count]))
    if parts:
        dom = np.unique(np.concatenate(
            [p.astype(dt, copy=False).ravel() for p in parts]))
    else:
        dom = np.zeros(0, dtype=dt)
    if len(dom) > max_card or (dom.dtype.kind == "f" and len(dom)
                               and np.isnan(dom[-1])):
        dom = None
    for k in [k for k in cache if k[0] != man.version]:
        del cache[k]
    cache[key] = dom
    return dom


def map_device_eligible(dt) -> bool:
    """MAP<STRING, numeric|string> gets device plates; other key/value
    types stay host-evaluated."""
    return (getattr(dt, "key", None) is not None
            and dt.key.name == "string"
            and (T.is_numeric(dt.value) or dt.value.name == "string"))


def struct_device_eligible(dt) -> bool:
    """STRUCT with only numeric/string fields gets per-field plates;
    nested complex fields keep the host path."""
    fields = getattr(dt, "fields", ())
    return bool(fields) and all(
        T.is_numeric(ft) or ft.name == "string" for _n, ft in fields)


def _complex_column_sources(manifest, views, row_chunks, ci):
    """(batch row, decoded cells, null mask) triples for a complex
    column — the one assembly all three complex-plate builders share
    (review finding: three diverging copies)."""
    sources = []
    for i, v in enumerate(views):
        sources.append((i, v.decoded_column(ci), v.null_mask(ci)))
    for j, (pos, take) in enumerate(row_chunks):
        src = np.asarray(manifest.row_arrays[ci][pos:pos + take],
                         dtype=object)
        rn = None
        if manifest.row_nulls and manifest.row_nulls[ci] is not None:
            rn = manifest.row_nulls[ci][pos:pos + take]
        sources.append((len(views) + j, src, rn))
    return sources


def _value_plate_dtype(vt) -> np.dtype:
    """Fill dtype for a complex-type VALUE plate: exact decimals fill
    as plain float64 and convert to scaled int64 afterwards — writing
    raw values straight into the int64 device dtype TRUNCATED them
    (review finding, verified: 1.50 decoded as 0.01)."""
    dt = vt.device_dtype()
    if vt.name == "decimal" and dt.kind == "i":
        return np.dtype(np.float64)
    return dt


def _finish_value_plate(vt, plate: np.ndarray) -> np.ndarray:
    """Host-domain fill plate -> device plate (scale exact decimals)."""
    dt = vt.device_dtype()
    if vt.name == "decimal" and dt.kind == "i":
        return T.decimal_to_unscaled(vt, plate)
    return plate


def _build_struct_column(data, manifest, views, row_chunks, ci, f, b,
                         cap, _place):
    """STRUCT column → ((field value plates tuple, field null plates
    tuple) in the dtype's field order, nan-stats, row-null mask).
    String fields encode against per-field append-only dictionaries."""
    import itertools

    from snappydata_tpu.storage.table_store import _struct_get

    sources = _complex_column_sources(manifest, views, row_chunks, ci)
    fnames = [n for n, _t in f.dtype.fields]
    ftypes = [t for _n, t in f.dtype.fields]
    str_fields = [fn for fn, ft in zip(fnames, ftypes)
                  if ft.name == "string"]
    # all string fields intern in ONE pass over the cells (review
    # finding: one full scan per field)
    str_lookups = data.intern_struct_fields(
        ci, str_fields, itertools.chain.from_iterable(
            dec for _bi, dec, _nm in sources)) if str_fields else {}
    lookups = [str_lookups.get(fn) if ft.name == "string" else None
               for fn, ft in zip(fnames, ftypes)]
    fvals = [np.zeros((b, cap), dtype=np.int32 if lk is not None
                      else _value_plate_dtype(ft))
             for lk, ft in zip(lookups, ftypes)]
    fnuls = [np.zeros((b, cap), dtype=np.bool_) for _ in fnames]
    null_mask = np.zeros((b, cap), dtype=np.bool_)
    any_null = False
    for bi, dec, nm in sources:
        for r, x in enumerate(dec):
            if isinstance(x, dict):
                for k, (fn, lk) in enumerate(zip(fnames, lookups)):
                    v = _struct_get(x, fn)
                    if v is None:
                        fnuls[k][bi, r] = True
                    elif lk is not None:
                        fvals[k][bi, r] = lk[str(v)]
                    else:
                        fvals[k][bi, r] = v
            else:
                null_mask[bi, r] = True
                any_null = True
        if nm is not None:
            null_mask[bi, :len(nm)] |= np.asarray(nm, dtype=bool)
            any_null = True
    fvals = [a if lk is not None else _finish_value_plate(ft, a)
             for a, lk, ft in zip(fvals, lookups, ftypes)]
    return ((tuple(_place(a) for a in fvals),
             tuple(_place(a) for a in fnuls)),
            np.full(b, np.nan), np.full(b, np.nan),
            _place(null_mask) if any_null else None)


def _build_map_column(data, manifest, views, row_chunks, ci, f, b, cap,
                      _place):
    """MAP<STRING, V> column → (((kcodes [b,cap,L], vals [b,cap,L],
    lengths [b,cap], value_nulls [b,cap,L])), nan-stats, row-null mask).
    Keys (and string values) encode against the table's append-only
    map dictionaries, so plates from any pinned manifest stay valid."""
    import itertools

    val_is_str = f.dtype.value.name == "string"
    vdt = np.dtype(np.int32) if val_is_str \
        else _value_plate_dtype(f.dtype.value)
    sources = _complex_column_sources(manifest, views, row_chunks, ci)
    klookup, vlookup = data.intern_map_entries(
        ci, itertools.chain.from_iterable(
            dec for _bi, dec, _nm in sources))
    maxlen = 1
    for _bi, dec, _nm in sources:
        for x in dec:
            if isinstance(x, dict) and len(x) > maxlen:
                maxlen = len(x)
    L = _next_pow2(maxlen)
    kcodes = np.full((b, cap, L), -1, dtype=np.int32)
    vals = np.zeros((b, cap, L), dtype=vdt)
    lens = np.zeros((b, cap), dtype=np.int32)
    vnul = np.zeros((b, cap, L), dtype=np.bool_)
    null_mask = np.zeros((b, cap), dtype=np.bool_)
    any_null = False
    for bi, dec, nm in sources:
        for r, x in enumerate(dec):
            if isinstance(x, dict):
                lens[bi, r] = len(x)
                for k, (mk, mv) in enumerate(x.items()):
                    kcodes[bi, r, k] = klookup[str(mk)]
                    if mv is None:
                        vnul[bi, r, k] = True
                    elif val_is_str:
                        vals[bi, r, k] = vlookup[str(mv)]
                    else:
                        vals[bi, r, k] = mv
            else:
                null_mask[bi, r] = True
                any_null = True
        if nm is not None:
            null_mask[bi, :len(nm)] |= np.asarray(nm, dtype=bool)
            any_null = True
    if not val_is_str:
        vals = _finish_value_plate(f.dtype.value, vals)
    return ((_place(kcodes), _place(vals), _place(lens), _place(vnul)),
            np.full(b, np.nan), np.full(b, np.nan),
            _place(null_mask) if any_null else None)


def array_element_dictionary(data, ci: int) -> np.ndarray:
    """Element dictionary of an ARRAY<STRING> column — delegates to the
    table's APPEND-ONLY intern store (same protocol as scalar string
    dictionaries: codes never shift, so plates from any pinned manifest
    version decode correctly against every later dictionary read)."""
    return data.array_element_dictionary(ci)


def _build_array_column(data, manifest, views, row_chunks, ci, f, b, cap,
                        _place):
    """Numeric/string ARRAY column → ((values [b,cap,L], lengths
    [b,cap], element_nulls [b,cap,L]), nan-stats, row-null mask).
    String elements encode as int32 dictionary codes interned into the
    table's append-only element dictionary — size/element_at/
    array_contains then run on device exactly like their numeric forms."""
    is_str = f.dtype.element.name == "string"
    sources = _complex_column_sources(manifest, views, row_chunks, ci)
    if is_str:
        import itertools

        edt = np.dtype(np.int32)
        # intern THIS pinned manifest's cells in ONE call (append-only,
        # cheap once hot) so the bind is self-sufficient across recovery
        # and concurrent mutation — a review finding killed the previous
        # sorted-per-version dictionary whose codes shifted under writes
        lookup = data.intern_array_elements(
            ci, itertools.chain.from_iterable(
                dec for _bi, dec, _nm in sources))
    else:
        edt = _value_plate_dtype(f.dtype.element)
    maxlen = 1
    for _bi, dec, _nm in sources:
        for x in dec:
            if isinstance(x, (list, tuple, np.ndarray)) and \
                    len(x) > maxlen:
                maxlen = len(x)
    L = _next_pow2(maxlen)
    vals = np.zeros((b, cap, L), dtype=edt)
    lens = np.zeros((b, cap), dtype=np.int32)
    enul = np.zeros((b, cap, L), dtype=np.bool_)
    null_mask = np.zeros((b, cap), dtype=np.bool_)
    any_null = False
    for bi, dec, nm in sources:
        for r, x in enumerate(dec):
            if isinstance(x, (list, tuple, np.ndarray)):
                lx = len(x)
                lens[bi, r] = lx
                for k, el in enumerate(x):
                    if el is None:
                        enul[bi, r, k] = True
                    elif is_str:
                        vals[bi, r, k] = lookup[str(el)]
                    else:
                        vals[bi, r, k] = el
            else:
                null_mask[bi, r] = True
                any_null = True
        if nm is not None:
            null_mask[bi, :len(nm)] |= np.asarray(nm, dtype=bool)
            any_null = True
    if not is_str:
        vals = _finish_value_plate(f.dtype.element, vals)
    return ((_place(vals), _place(lens), _place(enul)),
            np.full(b, np.nan), np.full(b, np.nan),
            _place(null_mask) if any_null else None)


def data_pow2() -> bool:
    from snappydata_tpu import config

    return config.global_properties().batches_pow2_bucketing


class _DeviceCacheBudget:
    """Process-wide accounting of cached device arrays with LRU eviction
    (ref: SnappyUnifiedMemoryManager evicting regions to disk under
    memory pressure — here eviction drops device copies back to host,
    from which they rebuild transparently on next bind)."""

    def __init__(self):
        import threading

        self._lock = locks.named_lock("storage.device_cache")
        # (id(table_cache_dict), cache_key) -> (bytes, tick, cache_ref)
        self._entries: Dict = {}
        self._tick = 0

    def _budget(self) -> int:
        from snappydata_tpu import config

        return config.global_properties().device_cache_bytes

    def enabled(self) -> bool:
        return self._budget() > 0

    def forget(self, table_cache: Dict, cache_key) -> None:
        """Version pruning dropped this entry: stop counting its bytes
        (otherwise every rebuild inflated the budget and evicted
        innocents)."""
        with self._lock:
            self._entries.pop((id(table_cache), repr(cache_key)), None)

    def touch(self, table_cache: Dict, cache_key, nbytes: int,
              data=None) -> None:
        budget = self._budget()
        if budget <= 0:
            return
        with self._lock:
            self._tick += 1
            # strong ref to the owning cache dict: it lives with its table
            # anyway, and eviction empties it (bounded residue).  The
            # table itself is a weakref: it is only consulted to spare
            # MVCC-pinned epochs, never kept alive.
            self._entries[(id(table_cache), repr(cache_key))] = (
                nbytes, self._tick, table_cache, cache_key,
                weakref.ref(data) if data is not None else None)
            total = sum(e[0] for e in self._entries.values())
            if total <= budget:
                return
            from snappydata_tpu.observability.metrics import global_registry
            from snappydata_tpu.storage.mvcc import pinned_versions_peek

            for key, (b, _, owner, ck, dref) in sorted(
                    self._entries.items(), key=lambda kv: kv[1][1]):
                if total <= budget:
                    break
                d = dref() if dref is not None else None
                if d is not None:
                    # NEVER evict a pinned epoch's plates out from under
                    # a live scan (the tier ladder's contract) — the
                    # lock-free peek keeps mvcc.clock out from under the
                    # budget lock (no device_cache -> clock edge)
                    pins = pinned_versions_peek(d)
                    if pins is None or ck[0] in pins:
                        global_registry().inc("tier_pinned_skips")
                        continue
                owner.pop(ck, None)  # device arrays released
                self._entries.pop(key, None)
                total -= b
                global_registry().inc("device_cache_evictions")


_cache_budget = _DeviceCacheBudget()


def _entry_bytes(entry) -> int:
    def arr_bytes(v) -> int:
        if isinstance(v, tuple):  # array-column plates nest one level
            return sum(arr_bytes(x) for x in v)
        return int(v.nbytes) if hasattr(v, "nbytes") else 0

    # row tables cache a whole DeviceTable (executor's replicated-bind
    # path), column tables a per-column dict — the tier ladder and the
    # broker ledger walk both shapes
    if isinstance(entry, DeviceTable):
        return (arr_bytes(entry.valid)
                + sum(arr_bytes(v) for v in list(entry.columns.values()))
                + sum(arr_bytes(v) for v in list(entry.nulls.values())
                      if v is not None))
    # list() is a C-atomic snapshot: a prefetch worker may still be
    # filling this entry while a ledger/tier walk measures it
    return sum(arr_bytes(v) for v in list(entry.values()))


def _map_cache_leaves(entry, fn):
    """Apply `fn` to every DEVICE-array leaf of one device-cache entry
    dict, preserving structure (host stats/dictdom tuples pass through).
    The single traversal migrate_mesh_cache and the per-device ledger
    share — cache-entry shapes must not drift between them.  Snapshots
    the items: a concurrent reader may fill the entry mid-walk."""
    out = {}
    for k, v in list(entry.items()):
        if k == "valid":
            out[k] = fn(v)
        elif k == "nrows":
            out[k] = v
        elif isinstance(k, tuple) and k[0] == "dictdom":
            out[k] = v                       # host-side probe surface
        elif isinstance(k, tuple) and isinstance(v, tuple) and len(v) == 4:
            plate, smin, smax, nulls = v

            def leaf(x):
                if x is None:
                    return None
                if isinstance(x, tuple):  # plates nest (CodePlate, acol)
                    parts = [leaf(p) for p in x]
                    return type(x)(*parts) if hasattr(x, "_fields") \
                        else tuple(parts)
                return fn(x)

            out[k] = (leaf(plate), smin, smax, leaf(nulls))
        else:
            out[k] = v
    return out


def migrate_mesh_cache(data, old_token, new_ctx) -> Tuple[int, int]:
    """Live bucket rebalance of one table's resident plates: re-place
    every cache entry bound under `old_token` onto `new_ctx`'s mesh via
    jax.device_put (device-to-device moves — no host rebuild, the world
    is NOT invalidated).  Returns (entries_moved, bytes_moved).  Entries
    whose padded batch axis the new mesh size doesn't divide are left to
    rebuild from host on next bind (counted by the caller)."""
    import jax

    moved = bytes_moved = 0
    nd = new_ctx.num_devices
    for key in [k for k in list(data._device_cache)
                if len(k) >= 2 and k[1] == old_token]:
        entry = data._device_cache.get(key)
        if entry is None:
            continue
        valid = entry.get("valid")
        if valid is None or valid.shape[0] % nd != 0:
            continue
        counted = [0]

        def _replace(x, _c=counted):
            _c[0] += int(getattr(x, "nbytes", 0))
            return jax.device_put(x, new_ctx.sharding_for(x))

        new_entry = _map_cache_leaves(entry, _replace)
        new_key = (key[0], new_ctx.token) + tuple(key[2:])
        data._device_cache[new_key] = new_entry
        data._device_cache.pop(key, None)
        _cache_budget.forget(data._device_cache, key)
        if _cache_budget.enabled():
            _cache_budget.touch(data._device_cache, new_key,
                                _entry_bytes(new_entry), data=data)
        moved += 1
        bytes_moved += counted[0]
    return moved, bytes_moved


def device_cache_bytes_by_device(tables) -> Dict[str, int]:
    """Per-DEVICE resident bytes of every cached plate — the mesh
    dashboard's proof that sharded tables stay encoded per device
    (read off each array's addressable shards, so replicated build
    plates correctly count full bytes on every device)."""
    out: Dict[str, int] = {}

    def leaf(x):
        if x is None or isinstance(x, (int, float)):
            return
        if isinstance(x, tuple):
            for p in x:
                leaf(p)
            return
        try:
            shards = getattr(x, "addressable_shards", None)
            if shards:
                for sh in shards:
                    k = str(sh.device)
                    out[k] = out.get(k, 0) + int(sh.data.nbytes)
            elif hasattr(x, "nbytes"):
                for d in getattr(x.sharding, "device_set", []):
                    out[str(d)] = out.get(str(d), 0) + int(x.nbytes)
        except Exception:
            pass

    for _name, data in tables:
        caches = getattr(data, "_device_cache", None)
        if not caches:
            continue
        for entry in list(caches.values()):
            for k, v in list(entry.items()):
                if k == "valid":
                    leaf(v)
                elif isinstance(k, tuple) and k[0] != "dictdom" \
                        and isinstance(v, tuple) and len(v) == 4:
                    leaf(v[0])
                    leaf(v[3])
    return out


def device_cache_bytes_by_table(tables) -> Dict[str, int]:
    """Device-side ledger for the resource broker: cached decoded plate
    bytes per table, read straight off each table's `_device_cache`
    (pull-based, so dropped tables simply stop appearing — nothing is
    pinned). `tables` is an iterable of (name, data)."""
    out: Dict[str, int] = {}
    for name, data in tables:
        caches = getattr(data, "_device_cache", None)
        if not caches:
            continue
        try:  # same-named tables of different catalogs sum, not replace
            out[name] = out.get(name, 0) + sum(
                _entry_bytes(c) for c in list(caches.values()))
        except Exception:
            out.setdefault(name, 0)
    return out


def current_scan_scale(data) -> float:
    """How many windows the active tile pass splits `data`'s scan into
    (1.0 outside a tile pass). The exact-decimal sum overflow guard
    multiplies its per-tile max|v|·count bound by this so the bound
    covers the MERGED total across tiles, not just each tile (several
    tiles could each pass the per-tile bound while their int64 partial-
    merge total wraps silently — advisor round 5)."""
    wentry = (_scan_windows.get() or {}).get(id(data))
    if wentry is None:
        return 1.0
    lo, hi, manifest = wentry[:3]
    total = scan_unit_count(data, manifest)
    # nominal width, not this window's: the last tile of a pass may be
    # truncated (e.g. 10 units in tiles of 4 → (8,10)), and deriving the
    # count from a truncated width would over-scale the overflow guard,
    # rerouting a safely-summable final tile to the slow host path
    width = max(1, wentry[3] if len(wentry) > 3 else hi - lo)
    return float(max(1, -(-total // width)))


def _scan_units(data, manifest=None):
    """THE unit-splitting contract shared by the device bind and the
    host fallback: (manifest, views, row_chunks, window) honoring the
    active scan window — pinned snapshot, unit order (batches then
    row-buffer chunks of `capacity` rows), [lo, hi) slice. Both sides
    MUST read through this one helper: if they ever disagreed on unit
    order, a tile falling back to host would silently read different
    rows than the device tile it replaces (the double-count bug class).
    row_chunks are (start, take) row-buffer slices."""
    wentry = (_scan_windows.get() or {}).get(id(data))
    window = None
    if wentry is not None:
        window = (wentry[0], wentry[1])
        if wentry[2] is not None:
            manifest = wentry[2]
    if manifest is None:
        # the ambient pinned snapshot (storage/mvcc): EVERY read this
        # contract serves — device bind, host fallback, LIMIT-n scan —
        # resolves the statement's pinned epoch, so concurrent ingest
        # publishing new manifests never changes a query mid-flight
        from snappydata_tpu.storage import mvcc

        manifest = mvcc.snapshot_of(data)
    # (wentry[3], when present, is the pass's nominal tile width — used
    # only by current_scan_scale, never for unit slicing)
    views = list(manifest.views)
    row_chunks = []
    cap = data.capacity
    if manifest.row_count > 0:
        pos = 0
        while pos < manifest.row_count:
            take = min(cap, manifest.row_count - pos)
            row_chunks.append((pos, take))
            pos += take
    if window is not None:
        units = [("v", v) for v in views] + [("r", rc) for rc in row_chunks]
        units = units[window[0]:window[1]]
        views = [u for k, u in units if k == "v"]
        row_chunks = [u for k, u in units if k == "r"]
    return manifest, views, row_chunks, window


def host_scan_units(data, manifest=None):
    """(manifest, views, row_chunks) for a HOST-side scan of `data` —
    the host fallback's view of the same units build_device_table
    binds (see _scan_units)."""
    manifest, views, row_chunks, _window = _scan_units(data, manifest)
    return manifest, views, row_chunks

"""Plan compiler + executor.

One resolved logical plan lowers to ONE traced JAX function over stacked
column-batch arrays — the whole-stage-codegen analogue (ref:
ColumnTableScan.doProduce core/.../columnar/ColumnTableScan.scala:186,
SnappyHashAggregateExec, HashJoinExec):

  Relation  → stacked [B,C] device arrays (storage/device.py)
  Filter    → valid &= predicate
  Project   → expression re-map
  Join      → sorted build + match RANGES per probe row (sort-merge or
              searchsorted, ops/join.probe_lowering)
              (the HashJoinExec replicated/collocated case).  Unique
              builds gather directly; non-unique builds prefix-sum the
              ranges into a {2^k, 1.5*2^k}-bucketed expanded output
              (inner/left/right/full/semi/anti — ops/join.py); sorted
              build artifacts are cached per snapshot so repeated joins
              skip the argsort.  Non-equi and residual-on-outer shapes
              fall back to the host hash join, counted by reason.
  Aggregate → segment_sum/min/max over a combined group index; dictionary
              fast path mirrors the reference's dictionary-key aggregation
              (SnappyHashAggregateExec dictionary fast path :83-95)

Everything above the aggregate (HAVING/ORDER BY/LIMIT/DISTINCT/outer
projects) runs on host over the (small) reduced result — matching the
reference's driver-side CollectAggregateExec merge (ExistingPlans.scala:106).

Compiled executables are cached on (structural plan, static sizes); the
jit layer re-specializes per array shape — together these are the plan
cache (ref: SnappySession plan cache :2560-2566, PlanCacheSize 3000).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
import weakref
from snappydata_tpu.utils import locks
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from snappydata_tpu import config
from snappydata_tpu import types as T
from snappydata_tpu.engine import hosteval
from snappydata_tpu.engine.exprs import (DECIMAL_OVERFLOW,
                                         STRING_VALUE_FUNCS, CompileError,
                                         DecimalAvg, DVal, ExprBuilder,
                                         Runtime, _or_null)
from snappydata_tpu.engine.result import Result, empty_result
from snappydata_tpu.observability import tracing
from snappydata_tpu.resource.context import check_current
from snappydata_tpu.sql import ast
from snappydata_tpu.sql.analyzer import expr_type, _expr_name

_I64_MAX = np.iinfo(np.int64).max


@dataclasses.dataclass
class OutCol:
    name: str
    dtype: T.DataType
    dict_provider: Optional[Callable[[], np.ndarray]] = None


@dataclasses.dataclass
class RelOut:
    """Traced output of a device node: ordinal -> DVal + validity mask."""

    cols: Dict[int, DVal]
    valid: object  # traced bool array
    # run-space purity of the row set w.r.t. ONE run partition (the
    # RLE-aggregate alignment proof, threaded through the device tree):
    #   "pure"        no filter applied yet — every scanned row survives,
    #                 trivially aligned to ANY plate's runs
    #   (ends, mask)  the surviving rows are exactly the expansion of
    #                 per-run `mask` over cumulative run `ends` — the
    #                 whole filter conjunction stayed in run space
    #   None          impure (row-level predicate, join, null mask, …)
    # Default None: only run_scan asserts purity, everything else must
    # prove it survived.
    runf: object = None


class _RelationInput:
    """One base-table leaf: binds current snapshot arrays at exec time.

    `sargs` holds sargable conjuncts (col ordinal, op, literal-getter) the
    binder evaluates against per-batch min/max stats to skip whole batches
    before they reach the device kernel (ref: stats-row batch skipping +
    columnBatchesSkipped metric, ColumnTableScan.scala:115-130)."""

    def __init__(self, info, used: List[int]):
        self.info = info
        self.used = used
        self.sargs: List[Tuple[int, str, Callable]] = []
        # string-equality conjuncts (col ordinal, literal-getter): an
        # equality literal absent from the table dictionary can't match
        # any row — the binder skips EVERY batch (batches_skipped_dict)
        self.str_sargs: List[Tuple[int, Callable]] = []
        # artifact-backed join builds: the cached sorted-key order
        # indexes the FULL flat plate layout, so bind-time batch
        # skipping (which gathers a subset of batches) must not reshape
        # this relation's arrays — the in-trace pass mask applies the
        # filter instead
        self.no_skip = False
        # join relations bind decoded plates: cached build artifacts and
        # probe-key encodes read flat [B*cap] value layouts directly
        self.allow_code = True

    def bind(self):
        from snappydata_tpu.storage.device import build_device_table
        from snappydata_tpu.storage.table_store import RowTableData

        try:
            if isinstance(self.info.data, RowTableData):
                return _row_table_device(self.info, self.used)
            return build_device_table(self.info.data, None, self.used,
                                      code_ok=self.allow_code)
        except T.DecimalOverflow as e:
            # a stored decimal too wide for its int64 plate: the exact
            # host path reads it instead
            raise CompileError(str(e)) from e

    def keep_mask(self, dt, params) -> Optional[np.ndarray]:
        """bool [B] of batches that can contain matches; None = keep all."""
        if (not self.sargs and not self.str_sargs) or self.no_skip:
            return None
        keep = None
        for ci, op, get_lit in self.sargs:
            smin = dt.stats_min.get(ci)
            smax = dt.stats_max.get(ci)
            if smin is None:
                continue
            try:
                v = float(get_lit(params))
            except (TypeError, ValueError):
                continue
            # unknown stats (NaN) always keep
            if op in (">", ">="):
                k = ~(smax < v) if op == ">=" else ~(smax <= v)
            elif op in ("<", "<="):
                k = ~(smin > v) if op == "<=" else ~(smin >= v)
            elif op == "=":
                k = ~((smin > v) | (smax < v))
            else:
                continue
            k = k | np.isnan(smin)
            keep = k if keep is None else (keep & k)
        keep = self._dict_keep(dt, params, keep)
        return keep

    def _dict_keep(self, dt, params, keep) -> Optional[np.ndarray]:
        """Dictionary-domain batch skipping (satellite of the
        compressed-domain path, but active on decoded binds too): an
        equality literal missing from a batch's sorted VALUE_DICT
        dictionary — or from a string column's table dictionary — can't
        match a row of that batch, even when it sits inside the
        min/max range.  Counted as batches_skipped_dict, on top of
        whatever the stats skipper already removed."""
        from snappydata_tpu.observability.metrics import global_registry

        extra = None
        for ci, op, get_lit in self.sargs:
            if op != "=":
                continue
            dom = dt.dict_domains.get(ci)
            if dom is None:
                continue
            try:
                v = float(get_lit(params))
            except (TypeError, ValueError):
                continue
            host, sizes = dom
            present = np.ones(host.shape[0], dtype=np.bool_)
            for i in range(host.shape[0]):
                sz = int(sizes[i])
                if sz == 0:
                    continue   # no dictionary for this batch: keep
                p = int(np.searchsorted(host[i, :sz], v))
                present[i] = p < sz and host[i, p] == v
            extra = present if extra is None else (extra & present)
        for ci, get_lit in self.str_sargs:
            d = dt.dictionaries.get(ci)
            if d is None or not len(d):
                continue
            try:
                v = get_lit(params)
            except Exception:
                continue
            if v is None:
                continue
            if not bool(np.any(d == v)):
                # absent from the table-wide dictionary: no batch of
                # this relation can match the conjunct
                extra = np.zeros(dt.num_batches, dtype=np.bool_)
        if extra is None:
            return keep
        base = keep if keep is not None \
            else np.ones(dt.num_batches, dtype=np.bool_)
        newly = int((base & ~extra).sum())
        if newly:
            global_registry().inc("batches_skipped_dict", newly)
        return base & extra


def _row_table_device(info, used):
    """Row tables present the same [1, N] stacked-array interface. Under a
    mesh they are fully replicated — the reference's replicated row tables
    whose joins never shuffle (HashJoinExec.replicatedTableJoin).

    The built DeviceTable is cached per (mutation version, mesh, columns):
    rebuilding the string-code lookup of the whole table on EVERY bind was
    O(table) host work per query (round-1 weak finding)."""
    from snappydata_tpu.storage import mvcc
    from snappydata_tpu.storage.device import DeviceTable
    from snappydata_tpu.parallel.mesh import MeshContext

    ctx = MeshContext.current()
    cache = getattr(info.data, "_device_cache", None)
    if cache is None:
        cache = info.data._device_cache = {}
    # a pinned statement reads its captured host snapshot (row tables
    # mutate in place) and keys the cache by the CAPTURED version — the
    # version the arrays actually reflect, not whatever is live now;
    # unpinned binds keep the cheap hit path (no host materialization)
    pin = mvcc.current_pin()
    if pin is not None:
        arrays, row_masks, n, ver = pin.row_snapshot(info.data)
    else:
        arrays = None
        ver = info.data.version
    key = (ver, ctx.token if ctx else None, tuple(used))
    hit = cache.get(key)
    if hit is not None:
        return hit

    def _place(host_array):
        if ctx is None:
            return jnp.asarray(host_array)
        return jax.device_put(host_array, ctx.replicated)

    if arrays is None:
        arrays, row_masks, n = info.data.to_arrays_with_nulls()
    cap = max(1, n)
    cols = {}
    dicts = {}
    nulls = {}
    for ci in used:
        f = info.schema.fields[ci]
        nmask = None
        if f.dtype.name == "string":
            d = info.data.string_dict(ci)
            dicts[ci] = d
            lookup = {v: i for i, v in enumerate(d.tolist())}
            vals = np.fromiter(
                (lookup.get(v if v is not None else "", 0)
                 for v in arrays[ci]), dtype=np.int32, count=n)
        elif f.dtype.name == "decimal" \
                and f.dtype.device_dtype().kind == "i":
            # exact decimal: host rows -> scaled int64 device plate
            vals = T.decimal_to_unscaled(f.dtype,
                                         np.asarray(arrays[ci],
                                                    dtype=np.float64))
        else:
            vals = np.asarray(arrays[ci]).astype(f.dtype.device_dtype())
        if row_masks[ci] is not None:
            nmask = np.zeros((1, cap), dtype=np.bool_)
            nmask[0, :n] = row_masks[ci]
        padded = np.zeros(cap, dtype=vals.dtype)
        padded[:n] = vals
        cols[ci] = _place(padded[None, :])
        nulls[ci] = _place(nmask) if nmask is not None else None
    valid = np.zeros((1, cap), dtype=np.bool_)
    valid[0, :n] = True
    dt = DeviceTable(info.schema, 1, cap, _place(valid), cols, dicts,
                     {}, {}, n, nulls)
    from snappydata_tpu.storage.device import _cache_budget

    _pinned_vers = mvcc.pinned_row_versions(info.data)
    _live_ver = info.data.version
    for k in [k for k in cache
              if k[0] != key[0] and k[0] != _live_ver
              and k[0] not in _pinned_vers]:
        # old-version entries are dead — unless pinned, or the LIVE
        # version (a pinned bind at an older capture must not evict the
        # entry concurrent unpinned traffic is hitting)
        cache.pop(k, None)
        _cache_budget.forget(cache, k)
    cache[key] = dt
    if _cache_budget.enabled():
        nbytes = int(dt.valid.nbytes) + sum(
            int(c.nbytes) for c in dt.columns.values()) + sum(
            int(nl.nbytes) for nl in dt.nulls.values() if nl is not None)
        _cache_budget.touch(cache, key, nbytes)
    return dt


class CompiledPlan:
    """A device region compiled to a jitted function + bind metadata.

    Aggregates may additionally carry a two-phase split (`traced_pre` /
    `traced_main`): phase A computes the combined group index + validity
    mask (+ the matmul one-hot), phase B evaluates the slots.  Phase A's
    device outputs are cached in a module-level LRU keyed on (plan,
    static sizes, params, bound table identity) so repeated dashboard
    queries over an unchanged table skip gidx recomputation entirely
    (`gidx_cache_hits`).  Partial-raw compiles (the tiled scan's device
    merge) instead expose `execute_raw`, which returns the device
    outputs without the device_get/assemble round trip."""

    def __init__(self, relations: List[_RelationInput],
                 aux_builders: List[Callable],
                 static_providers: List[Callable[[], int]],
                 traced: Callable,
                 out_scope: List["_ScopeCol"],
                 is_aggregate: bool,
                 bind_checks: Optional[List[Callable]] = None,
                 traced_pre: Optional[Callable] = None,
                 traced_main: Optional[Callable] = None,
                 agg_notes: Optional[Dict] = None,
                 tile_merge: Optional[Dict] = None,
                 kind: str = "plan",
                 join_notes: Optional[Dict] = None,
                 decode_notes: Optional[Dict] = None,
                 decimal_notes: Optional[Dict] = None):
        self.relations = relations
        # what the plan IS (agg / global_agg / scan, join_ in front when
        # it joins): the stem of the names its jitted functions carry
        # into the profile's `XLA Modules` line and an HLO dump
        self.kind = kind
        self.aux_builders = aux_builders
        self.static_providers = static_providers
        self.traced = traced
        self.out_scope = out_scope  # dict_provider read at assemble time
        self.is_aggregate = is_aggregate
        self.bind_checks = bind_checks or []
        self.traced_pre = traced_pre
        self.traced_main = traced_main
        # trace-time notes per static key: chosen reduction strategies +
        # fused dispatch count, surfaced as per-execution metrics
        self.agg_notes = agg_notes
        # trace-time notes of the plan's joins per static key (how many
        # lowered, probe keys searched, expanded output slots)
        self.join_notes = join_notes
        # trace-time notes of the plan's CodePlate decodes per (static
        # key, phase): site -> the form `dict_decode` emitted it in
        self.decode_notes = {} if decode_notes is None else decode_notes
        # and, per (static key, phase), the shapes of the decimal
        # expressions past int64's precision it guarded
        self.decimal_notes = {} if decimal_notes is None \
            else decimal_notes
        # partial-raw merge metadata: per-output merge ops + group-card
        # check for the tiled scan's on-device partial merge
        self.tile_merge = tile_merge
        self._jitted: Dict[tuple, Callable] = {}
        self._jitted_pre: Dict[tuple, Callable] = {}
        self._jitted_main: Dict[tuple, Callable] = {}
        # vmapped variants for the serving micro-batcher, keyed
        # (static sizes, padded batch size)
        self._jitted_vmap: Dict[tuple, Callable] = {}
        # shard_map variants for the mesh execution lane, keyed
        # (static sizes, mesh token, strategy) — engine/mesh_exec.py
        self._jitted_mesh: Dict[tuple, Callable] = {}
        # per-join distribution metadata (set by Compiler.compile)
        self.join_meta: List[Dict] = []
        # compressed-domain trace notes per (static, phase): how many
        # predicates lowered to the code/run lanes in that trace —
        # tallied once at trace time, re-counted per execution
        self._code_notes: Dict[tuple, dict] = {}

    def _jit_target(self, traced, static, suffix: str = ""):
        """`traced` closed over its static sizes, named for what it is
        (`snappy_agg_main`), never for its parameters."""
        return tracing.name_jit_target(
            functools.partial(traced, static),
            f"snappy_{self.kind}{suffix}")

    def _noted_call(self, static, phase: str, fn, args):
        """Dispatch `fn` with the compressed-domain trace tally
        installed: a (re)trace fills a fresh note dict; cached
        executions leave it empty and keep the stored note."""
        from snappydata_tpu.engine.exprs import _compressed_notes

        fresh: dict = {}
        tok = _compressed_notes.set(fresh)
        try:
            return fn(*args)
        finally:
            _compressed_notes.reset(tok)
            if fresh or (static, phase) not in self._code_notes:
                self._code_notes[(static, phase)] = fresh

    def _count_compressed(self, reg, static, phases) -> None:
        for ph in phases:
            note = self._code_notes.get((static, ph))
            if not note:
                continue
            if note.get("code_preds"):
                reg.inc("code_domain_predicates", note["code_preds"])
            if note.get("run_preds"):
                reg.inc("rle_run_predicates", note["run_preds"])

    def _bind(self, params: Tuple):
        with tracing.span("bind") as sp:
            if isinstance(sp, tracing._NoopSpan):
                return self._bind_inner(params, sp)
            # traced bind: also capture compressed-domain fallback
            # evidence (the decode-first reroutes device.py counts by
            # reason happen inside this bind)
            from snappydata_tpu.observability.metrics import \
                global_registry

            reg = global_registry()
            fb0 = reg.counter("compressed_fallbacks")
            out = self._bind_inner(params, sp)
            fb = reg.counter("compressed_fallbacks") - fb0
            if fb:
                sp.set("compressed_fallbacks", fb)
            # what went up, always present on a traced bind (0 where the
            # device cache served every plate): bytes handed to the
            # device by storage/device.build_device_table and by the
            # small per-execution puts below, and host time inside those
            # calls.  Attrs, not a child span: `bind`'s self time is
            # what readers of this span have always read.
            # Join builds this bind sorted or found cached
            # (ops/join.build_artifact counts them here): 0 on a plan
            # that joins nothing.
            for key in ("upload_bytes", "upload_ms", "plates_built",
                        "plates_cached", "join_builds_sorted",
                        "join_builds_cached"):
                sp.attrs.setdefault(key, 0)
            sp.attrs["upload_ms"] = round(sp.attrs["upload_ms"], 4)
            return out

    def _bind_inner(self, params: Tuple, sp):
        from snappydata_tpu.observability.metrics import global_registry

        # one compiled dispatch is the atomic unit of work — the
        # cooperative cancellation point sits right before it
        check_current()
        reg = global_registry()
        traced_bind = not isinstance(sp, tracing._NoopSpan)

        def put(x):
            """jax.device_put of a small host value, counted into the
            traced bind's upload evidence."""
            if not traced_bind:
                return jax.device_put(x)
            t0 = time.perf_counter()
            out = jax.device_put(x)
            sp.add("upload_ms", (time.perf_counter() - t0) * 1e3)
            sp.add("upload_bytes", int(getattr(x, "nbytes", 0)))
            return out

        # data-dependent validity (e.g. join build-key uniqueness): raises
        # CompileError -> executor reroutes to the host path
        for check in self.bind_checks:
            check()
        tables = [r.bind() for r in self.relations]
        arrays: List = []
        for r, dt in zip(self.relations, tables):
            keep = r.keep_mask(dt, params)
            take_idx = None
            if keep is not None and not keep.all():
                # batch skipping: gather only qualifying batches (padded
                # to a {2^k, 1.5*2^k} bucket so executable shapes stay
                # stable — same bucketing as the bind; under a mesh the
                # bucket must ALSO divide by the shard count or the
                # gathered arrays couldn't re-shard evenly)
                from snappydata_tpu.parallel.mesh import (MeshContext,
                                                          shard_bucket)
                from snappydata_tpu.storage.device import batch_bucket

                kept = np.flatnonzero(keep)
                reg.inc("column_batches_skipped",
                        int(dt.num_batches - len(kept)))
                sp.add("batches_skipped", int(dt.num_batches - len(kept)))
                mctx = MeshContext.current()
                b_new = shard_bucket(len(kept), mctx.num_devices) \
                    if mctx is not None else batch_bucket(len(kept))
                pad_valid = np.zeros(b_new, dtype=bool)
                pad_valid[:len(kept)] = True
                idx = np.zeros(b_new, dtype=np.int64)
                idx[:len(kept)] = kept
                take_idx = put(idx)
                pad_mask = put(pad_valid)[:, None]
            reg.inc("column_batches_seen", int(dt.num_batches))
            sp.add("batches_seen", int(dt.num_batches))
            for ci in r.used:
                col = dt.columns[ci]
                nl = dt.nulls.get(ci)
                if take_idx is not None:
                    if isinstance(col, tuple):
                        # array-column plates AND compressed-domain
                        # plates (CodePlate/RlePlate/BitPlate): gather
                        # every field along the batch axis, preserving
                        # the NamedTuple type the trace branches on
                        parts = [jnp.take(c, take_idx, axis=0)
                                 for c in col]
                        col = type(col)(*parts) \
                            if hasattr(col, "_fields") else tuple(parts)
                    else:
                        col = jnp.take(col, take_idx, axis=0)
                    nl = jnp.take(nl, take_idx, axis=0) \
                        if nl is not None else None
                arrays.append((col, nl))
            valid = dt.valid
            if take_idx is not None:
                valid = jnp.take(valid, take_idx, axis=0) & pad_mask
            arrays.append(valid)
        # EXPLICIT device placement (jax.device_put, not jnp.asarray) for
        # the small per-execution uploads — literal scalars and aux LUTs.
        # With the column plates cached on device, a warm query then runs
        # under jax.transfer_guard("disallow"): the compressed-domain
        # tests' proof that no decoded plate ever crosses the link.
        def _up(x):
            # join-artifact aux builds already return device arrays —
            # re-wrapping them through numpy would pull them to host
            return x if isinstance(x, jnp.ndarray) \
                else put(np.asarray(x))

        aux = [_up(b(params)) for b in self.aux_builders]
        static = tuple(p() for p in self.static_providers)
        pvals = tuple(put(_param_scalar(v)) for v in params)
        return tables, arrays, aux, static, pvals

    def _run_device(self, params: Tuple):
        """Bind + dispatch; returns (tables, outs, main dispatch span)
        with outs still ON DEVICE (async) — callers decide when/whether
        to transfer.

        Under an active mesh every dispatch serializes on
        parallel.mesh.dispatch_lock and BLOCKS to completion inside the
        hold: concurrent multi-device programs interleave their XLA CPU
        collective participants into one rendezvous and deadlock (see
        the lock's comment); single-device execution keeps the async
        fast path untouched."""
        import contextlib

        from snappydata_tpu.observability.metrics import global_registry
        from snappydata_tpu.parallel.mesh import MeshContext, dispatch_lock

        mesh_active = MeshContext.current() is not None

        @contextlib.contextmanager
        def _dispatch_scope():
            if not mesh_active:
                yield
                return
            with dispatch_lock:
                yield

        def _settle(outs):
            if mesh_active:
                # locklint: blocking-under-lock the dispatch lock exists
                # exactly to fence device collectives; it is a leaf —
                # nothing is acquired under it
                jax.block_until_ready(outs)
            return outs

        reg = global_registry()
        tables, arrays, aux, static, pvals = self._bind(params)
        from snappydata_tpu.storage.device import scan_window_active

        # tile windows rotate bind identity every tile — the split-phase
        # cache could never hit and would churn LRU entries dashboards
        # actually reuse, so windowed binds run the fused single phase
        use_pre = self.traced_pre is not None \
            and (config.global_properties().gidx_cache_bytes or 0) > 0 \
            and not scan_window_active()
        if use_pre:
            try:
                hash(params)
                pkey = params
            except TypeError:  # unhashable literal: skip caching
                pkey = None
        if use_pre and pkey is not None:
            pre = _pre_cache_get(self, static, pkey, tables)
            ran_pre = pre is None
            if ran_pre:
                reg.inc("gidx_cache_misses")
                fnp = self._jitted_pre.get(static)
                first = fnp is None
                if first:
                    fnp = jax.jit(self._jit_target(self.traced_pre, static,
                                                   "_pre"))
                    self._jitted_pre[static] = fnp
                # first call of a static key traces + XLA-compiles inside
                # the dispatch — surfaced as its own span so a trace shows
                # compile time apart from steady-state execution.  `first`
                # is this dict's view only: when the batch count passes a
                # bucket the static key is unchanged, jax.jit retraces and
                # XLA compiles inside a span named device_execute.  The
                # `xla_compiles` attr (0 here; tracing's jax.monitoring
                # listener adds to it) says what really happened.
                with tracing.span("jit_compile" if first
                                  else "device_execute", phase="pre",
                                  xla_compiles=0), \
                        _dispatch_scope():
                    pre = _settle(self._noted_call(
                        static, "pre", fnp,
                        (tuple(arrays), tuple(aux), pvals)))
                _pre_cache_put(self, static, pkey, tables, pre)
            else:
                reg.inc("gidx_cache_hits")
            fn = self._jitted_main.get(static)
            first = fn is None
            if first:
                fn = jax.jit(self._jit_target(self.traced_main, static,
                                              "_main"))
                self._jitted_main[static] = fn
            with tracing.span("jit_compile" if first
                              else "device_execute", phase="main",
                              xla_compiles=0) as sp, \
                    _dispatch_scope():
                outs = _settle(self._noted_call(
                    static, "main", fn,
                    (tuple(arrays), tuple(aux), pvals, pre)))
                self._note_slots(sp, static, ("pre", "main"),
                                 gidx_cache_hit=not ran_pre)
            # a gidx-cache hit SKIPPED the pre pass — its code predicates
            # didn't run this execution (review finding: they were
            # re-counted in proportion to the hit rate)
            self._count_compressed(
                reg, static, ("pre", "main") if ran_pre else ("main",))
        else:
            fn = self._jitted.get(static)
            first = fn is None
            if first:
                fn = jax.jit(self._jit_target(self.traced, static))
                self._jitted[static] = fn
            with tracing.span("jit_compile" if first
                              else "device_execute",
                              xla_compiles=0) as sp, \
                    _dispatch_scope():
                outs = _settle(self._noted_call(
                    static, "single", fn,
                    (tuple(arrays), tuple(aux), pvals)))
                self._note_slots(sp, static, ("single",))
            self._count_compressed(reg, static, ("single",))
        self._count_agg_notes(reg, static)
        return tables, outs, sp

    def _note_slots(self, sp, static, phases,
                    gidx_cache_hit: bool = False) -> None:
        """The main dispatch span says how its aggregate slots reduced,
        from the trace-time notes (so after the call that may trace): how
        many the dictionary-space lane took, how many of any family
        were emitted as a `segment_*` scatter, how many of those belong
        to the exact-integer family, how many integer columns (int64
        sums and count masks) the limb product took instead, and how many
        slots the run reduce took (`run_reduce_slots`: a generic-key
        family that would scatter, reduced over its rows' runs in group
        order).  0 where none, and on a plan that aggregates nothing.
        `group_slots` is the static number of group segments the reduce
        ran over and `reduce_padded_rows` the slots it walked (batch
        bucket x batch capacity, padding included).  `gidx_cache_hit` is 1 where the
        statement took its group index from the cache and ran the main
        phase alone.  `gidx_run_lane` is 1 where the statement built its
        group index by run heads over the sorted keys (the generic
        branch of `compute_pre`), 0 where none or the cache gave it."""
        note = self.agg_notes.get(static) if self.agg_notes else None
        for key in ("dict_space_slots", "scatter_slots",
                    "isum_scatter_slots", "limb_matmul_slots",
                    "run_reduce_slots", "group_slots",
                    "reduce_padded_rows"):
            sp.set(key, note[key] if note else 0)
        sp.set("gidx_cache_hit", int(gidx_cache_hit))
        sp.set("gidx_run_lane", note["gidx_run_lane"]
               if note and not gidx_cache_hit else 0)
        # and what its joins were: how many lowered to the device, the
        # probe keys they searched (the probe side's padded slots, one
        # search a join), the build side's padded slots, the expanded
        # output slots of one-to-many builds (0 where every build is
        # unique), how many builds were row tables and how many joins
        # matched on more than one key pair
        jnote = self.join_notes.get(static) if self.join_notes else None
        for key in _JOIN_NOTE_KEYS:
            sp.set(key, jnote[key] if jnote else 0)
        # and its dictionary decodes by form: the CodePlate columns
        # make_ctx decoded (counted at trace time, so the ones XLA then
        # dropped as unread are in) and the group-key remaps, once a
        # site over the statement's phases (both phases of a split plan
        # decode the same plates)
        from snappydata_tpu.storage.device_decode import (DECODE_GATHER,
                                                          DECODE_SELECT)

        sites = {}
        for phase in phases:
            sites.update(self.decode_notes.get((static, phase), {}))
        forms = list(sites.values())
        sp.set("dict_select_plates", forms.count(DECODE_SELECT))
        sp.set("dict_gather_plates", forms.count(DECODE_GATHER))
        # and its exact decimals: the expressions past int64's precision
        # lowered to guarded int64 (distinct shapes, the most any one
        # phase traced), and the SUM and AVG over a decimal reduced as
        # exact scaled int64
        sp.set("decimal_wide_exprs", max(
            (len(self.decimal_notes.get((static, phase), ()))
             for phase in phases), default=0))
        sp.set("decimal_exact_slots",
               note["decimal_exact_slots"] if note else 0)
        # 1 once the outputs are home and the overflow flag is set
        # (CompiledPlan.execute): the statement then reruns on the host
        sp.set("groups_overflow", 0)

    def _count_agg_notes(self, reg, static) -> None:
        """Per-execution metrics from the trace-time aggregate notes:
        reduction passes + strategies, the compressed-domain lanes the
        plan engaged (agg_code_domain / agg_dict_space / agg_rle_runs),
        and counted run-misalignment fallbacks — an RLE plate that was
        ELIGIBLE but whose filter left run space never degrades
        silently."""
        note = self.agg_notes.get(static) if self.agg_notes else None
        if note is None:
            return
        reg.inc("agg_reduce_passes", note["passes"])
        for s in note["strategies"]:
            reg.inc("agg_strategy_" + s)
        lanes = note.get("lanes", ())
        if "code_domain" in lanes:
            reg.inc("agg_code_domain")
        if "dict_space" in lanes:
            reg.inc("agg_dict_space")
        if "rle_runs" in lanes:
            reg.inc("agg_rle_runs")
        if note.get("rle_fallbacks"):
            from snappydata_tpu.storage.device_decode import \
                compressed_fallback

            tref = note.get("table")
            compressed_fallback("rle_agg", note["rle_fallbacks"],
                                table=tref() if tref is not None else None)

    def execute(self, params: Tuple) -> Result:
        """Bind, dispatch, bring the outputs home, assemble the Result.

        Dispatch is asynchronous: `device_execute` (`jit_compile` on a
        static key's first call) ends when the program is ENQUEUED, so
        the `transfer` span holds two things — the wait for the device
        to finish the program, then the single bulk device→host copy
        (per-array .asarray would cost one round trip each).  Its attrs
        split them: `wait_ms` (`jax.block_until_ready`, the copies
        already queued behind the program), `copy_ms` (`jax.device_get`
        of outputs already complete: what is left of the copy) and
        `bytes` copied.  The span's extent is what it has always been."""
        tables, outs, dispatch = self._run_device(params)
        outs = _transfer(outs)
        # the Result's assembly and whatever else the statement does
        # before it closes
        tracing.step("finish")
        flag = int(np.asarray(outs[2]))
        if flag:
            dispatch.set("groups_overflow", 1)
            if flag & DECIMAL_OVERFLOW:
                raise CompileError(
                    "decimal overflow: an exact decimal's scaled value "
                    "could leave int64: exact host path")
            raise CompileError(
                "device overflow (group-by cardinality beyond max_groups "
                "or a join expansion past its bound): host path")
        return self._assemble(outs, tables)

    def execute_raw(self, params: Tuple):
        """Run the compiled region and return (mask, pairs, overflow)
        still on device — the tiled scan merges per-tile partials there
        instead of round-tripping each tile through the host."""
        _tables, outs, _dispatch = self._run_device(params)
        return outs

    def execute_batched(self, params_list: Sequence[Tuple]):
        """Fused dispatch over a stack of bind vectors (the serving
        micro-batcher): bind the relations ONCE, stack each parameter
        position (and each aux build) along a new leading axis, and run
        ONE `jax.vmap`-over-the-parameter-axis dispatch for the whole
        batch — then ONE bulk device→host transfer.  Returns (tables,
        outs) with every leaf of `outs` carrying a leading batch axis;
        slice request i with `(outs[0][i], [(v[i], ...)], outs[2][i])`
        and feed it to `_assemble`.

        Batch skipping is intentionally OFF here (different bind values
        could keep different batch subsets — the in-trace predicate
        still filters, skipping is only a pruning optimization), and the
        gidx split-phase cache is bypassed (its key is per-params).
        Raises ValueError when per-request aux builds don't stack (e.g.
        value-dependent LUT shapes) and CompileError on bind-check
        failure — callers fall back to per-request execution."""
        from snappydata_tpu.observability.metrics import global_registry

        reg = global_registry()
        for check in self.bind_checks:
            check()
        tables = [r.bind() for r in self.relations]
        arrays: List = []
        for r, dt in zip(self.relations, tables):
            for ci in r.used:
                arrays.append((dt.columns[ci], dt.nulls.get(ci)))
            arrays.append(dt.valid)
        naux = len(self.aux_builders)
        per_req_aux = [[np.asarray(b(p)) for b in self.aux_builders]
                       for p in params_list]
        # np.stack raises ValueError on ragged shapes — the caller's cue
        # that this plan's aux builds are value-dependent and can't fuse
        aux = tuple(jnp.asarray(np.stack([a[j] for a in per_req_aux]))
                    for j in range(naux))
        static = tuple(p() for p in self.static_providers)
        nparams = len(params_list[0])
        pvals = tuple(
            jnp.asarray(np.stack([_param_scalar(p[k])
                                  for p in params_list]))
            for k in range(nparams))
        key = (static, len(params_list))
        fn = self._jitted_vmap.get(key)
        first = fn is None
        if first:
            reg.inc("serving_vmap_compiles")
            fn = jax.jit(jax.vmap(
                self._jit_target(self.traced, static, "_vmap"),
                in_axes=(None, 0, 0)))
            self._jitted_vmap[key] = fn
        with tracing.span("jit_compile" if first else "device_execute",
                          batched=len(params_list), xla_compiles=0):
            outs = self._noted_call(key, "vmap", fn,
                                    (tuple(arrays), aux, pvals))
        self._count_compressed(reg, key, ("vmap",))
        self._count_agg_notes(reg, static)
        # the whole batch comes home in ONE transfer — the amortization
        # the micro-batcher buys (vs one device_get per request)
        outs = _transfer(outs)
        reg.inc("serving_bulk_transfers")
        return tables, outs

    def tile_merge_ok(self) -> bool:
        """Bind-time check that a partial-raw compile's group-index space
        is data-independent and small enough for aligned [G] merging."""
        if not self.tile_merge:
            return False
        try:
            return self.tile_merge["cards"]() <= self.tile_merge["max_groups"]
        except CompileError:
            return False

    def _assemble(self, outs, tables) -> Result:
        """Device outputs → host Result.
        outs = (mask, [(val, null)...], overflow_flag)."""
        mask_dev, pairs, _overflow = outs
        mask = np.asarray(mask_dev).reshape(-1)
        names, cols, nulls, dtypes = [], [], [], []
        for oc, (v, nl) in zip(self.out_scope, pairs):
            data = np.asarray(v).reshape(-1)[mask.nonzero()[0]] \
                if data_needs_mask(v, mask) else np.asarray(v).reshape(-1)
            nmask = None
            if nl is not None:
                nmask = np.asarray(nl).reshape(-1)[mask.nonzero()[0]] \
                    if data_needs_mask(nl, mask) else np.asarray(nl).reshape(-1)
            if oc.dict_provider is not None:
                d = oc.dict_provider()
                if len(d) == 0:
                    data = np.full(data.shape, None, dtype=object)
                else:
                    data = np.asarray(d, dtype=object)[
                        np.clip(data, 0, len(d) - 1)]
            names.append(oc.name)
            cols.append(data)
            nulls.append(nmask)
            dtypes.append(oc.dtype)
        return Result(names, cols, nulls, dtypes)


def _transfer(outs):
    """Device outputs → host under the `transfer` span, which carries
    the split of its own time as attrs: the wait for the asynchronous
    dispatch to finish, then the copy (see CompiledPlan.execute)."""
    with tracing.span("transfer") as sp:
        if isinstance(sp, tracing._NoopSpan):
            return jax.device_get(outs)
        t0 = time.perf_counter()
        # start the copies now, as a bare device_get would: they queue
        # behind the program, so waiting for it first adds no round trip
        for x in jax.tree_util.tree_leaves(outs):
            x.copy_to_host_async()
        jax.block_until_ready(outs)
        t1 = time.perf_counter()
        host = jax.device_get(outs)
        t2 = time.perf_counter()
        sp.set("wait_ms", round((t1 - t0) * 1e3, 4))
        sp.set("copy_ms", round((t2 - t1) * 1e3, 4))
        sp.set("bytes", sum(int(x.nbytes)
                            for x in jax.tree_util.tree_leaves(host)))
        return host


def data_needs_mask(v, mask) -> bool:
    return int(np.prod(np.shape(v))) == mask.shape[0]


# --- group-index (phase A) cache -----------------------------------------
# Aggregate plans split into a cacheable prefix — validity mask, combined
# group index, and (on the matmul strategy) the one-hot — and a main
# phase.  Entries key on (plan identity, static sizes, params) and pin
# the exact DeviceTable objects they were computed from: table mutation
# rotates the device cache to new objects, which invalidates the entry
# without any explicit version plumbing (tile windows and mesh
# placements produce distinct DeviceTables too, so they can never alias).
# LRU, byte-capped by properties.gidx_cache_bytes.

_PRE_CACHE: "Dict[tuple, dict]" = {}
_PRE_CACHE_BYTES = [0]
# concurrent sessions (Flight server threads, jobserver workers) execute
# compiled plans in parallel — every cache mutation holds this lock so
# eviction races can't KeyError a query or corrupt the byte accounting
_PRE_CACHE_LOCK = locks.named_lock("executor.pre_cache")


def gidx_cache_nbytes() -> int:
    """Bytes of device arrays pinned by the group-index cache — the
    resource broker folds this into its unified device ledger."""
    return int(_PRE_CACHE_BYTES[0])


def _bind_identity(tables):
    """Per-bind identity tokens: the `valid` arrays live in the device
    cache's per-(version, window, mesh) entry and are REUSED across
    binds while that snapshot is current — the DeviceTable wrapper
    itself is rebuilt per bind, so it can't serve as the token.  A
    mutation (or window/mesh change) rotates to fresh arrays, which
    invalidates cache entries without explicit version plumbing."""
    return [t.valid for t in tables]


def _pre_cache_get(plan, static, pkey, tables):
    key = (id(plan), static, pkey)
    ident = _bind_identity(tables)
    with _PRE_CACHE_LOCK:
        entry = _PRE_CACHE.get(key)
        if entry is None:
            return None
        if entry["plan"]() is not plan \
                or len(entry["binds"]) != len(ident) \
                or any(r() is not t
                       for r, t in zip(entry["binds"], ident)):
            _PRE_CACHE.pop(key, None)
            _PRE_CACHE_BYTES[0] -= entry["nbytes"]
            return None
        entry["tick"] = _pre_cache_tick()
        return entry["pre"]


_pre_tick = [0]


def _pre_cache_tick() -> int:
    _pre_tick[0] += 1
    return _pre_tick[0]


def _pre_cache_put(plan, static, pkey, tables, pre) -> None:
    import weakref

    budget = int(config.global_properties().gidx_cache_bytes or 0)
    nbytes = sum(int(getattr(a, "nbytes", 0))
                 for a in jax.tree_util.tree_leaves(pre))
    if nbytes > budget:
        return  # one oversized entry would evict everything for nothing
    binds = tuple(weakref.ref(t) for t in _bind_identity(tables))
    with _PRE_CACHE_LOCK:
        # entries of GC'd plans (plan-cache eviction, dropped sessions)
        # or rotated binds (table mutated: old device arrays collected,
        # and a changed-literal pkey means the stale key is never probed
        # again) are dead weight until LRU pressure — purge them eagerly
        for k in [k for k, e in _PRE_CACHE.items()
                  if e["plan"]() is None
                  or any(r() is None for r in e["binds"])]:
            _PRE_CACHE_BYTES[0] -= _PRE_CACHE.pop(k)["nbytes"]
        while _PRE_CACHE and _PRE_CACHE_BYTES[0] + nbytes > budget:
            victim = min(_PRE_CACHE, key=lambda k: _PRE_CACHE[k]["tick"])
            _PRE_CACHE_BYTES[0] -= _PRE_CACHE.pop(victim)["nbytes"]
        old = _PRE_CACHE.pop((id(plan), static, pkey), None)
        if old is not None:  # concurrent miss on one key: replace once
            _PRE_CACHE_BYTES[0] -= old["nbytes"]
        _PRE_CACHE[(id(plan), static, pkey)] = {
            "plan": weakref.ref(plan), "binds": binds,
            "pre": pre, "nbytes": nbytes, "tick": _pre_cache_tick()}
        _PRE_CACHE_BYTES[0] += nbytes


def clear_gidx_cache() -> None:
    with _PRE_CACHE_LOCK:
        _PRE_CACHE.clear()
        _PRE_CACHE_BYTES[0] = 0


# the single source of truth for strategy names lives in ops/reduction —
# the token index mapping below must stay aligned with resolve_strategy
from snappydata_tpu.ops.reduction import STRATEGIES as _STRATEGY_NAMES  # noqa: E402


def _compressed_token() -> int:
    """scan_compressed_domain as a small int on the STATIC key."""
    s = str(config.global_properties().get(
        "scan_compressed_domain", "auto") or "auto").lower()
    return ("off", "auto", "on").index(s) if s in ("off", "auto", "on") \
        else 1


def _strategy_token(props) -> int:
    """agg_reduce_strategy as a small int riding the compiled plan's
    STATIC key — flipping the knob re-specializes instead of serving a
    stale trace."""
    s = str(props.get("agg_reduce_strategy", "auto") or "auto").lower()
    return _STRATEGY_NAMES.index(s) if s in _STRATEGY_NAMES else 0


_CODE_AGG_TOKENS = {"off": 0, "auto": 1, "on": 2}


def _code_agg_token(props) -> int:
    """agg_on_codes as a small int on the compiled plan's STATIC key —
    flipping the knob re-specializes, no plan-cache flush."""
    s = str(props.get("agg_on_codes", "auto") or "auto").lower()
    return _CODE_AGG_TOKENS.get(s, 1)


def _numeric_domain_provider(info, ci: int, max_card: int):
    """vdict key-domain provider for a direct numeric column of a base
    COLUMN table, or None when the shape can't carry one."""
    from snappydata_tpu.storage.table_store import RowTableData

    data = info.data
    if isinstance(data, RowTableData):
        return None

    def provider():
        from snappydata_tpu.storage.device import numeric_key_domain

        return numeric_key_domain(data, ci, max_card)

    return provider


def _vdict_card(dom, max_groups: int) -> int:
    """Static card of a vdict key: padded domain size — or max_groups+1
    when the domain declined (too many distincts / NaN), which pushes
    shape_info off the fast path onto the generic hash group-by."""
    return _padded_size(len(dom)) if dom is not None else max_groups + 1


def _vdict_lut(dom) -> np.ndarray:
    """Aux LUT of a vdict key: the sorted domain padded to its static
    card by repeating the last value (stays sorted; searchsorted
    side='left' maps the pad value to its first occurrence)."""
    if dom is None or len(dom) == 0:
        return np.zeros(1, dtype=np.float64)
    pad = _padded_size(len(dom))
    out = np.empty(pad, dtype=dom.dtype)
    out[:len(dom)] = dom
    out[len(dom):] = dom[-1]
    return out


def _rle_agg_ready(data) -> int:
    """Static gate of the run-space aggregate lane: run arithmetic sums
    WHOLE runs, so any delete mask (row-level holes runs can't see)
    disqualifies the snapshot.  Deltas and row-buffer rows already
    disqualify the compressed bind itself.  Rides the static key, so
    background compaction folding the deletes flips the lane back on
    with a re-specialize, no plan-cache flush."""
    from snappydata_tpu.storage import mvcc
    from snappydata_tpu.storage.table_store import RowTableData

    if isinstance(data, RowTableData):
        return 0
    man = mvcc.snapshot_of(data)
    return int(not any(v.delete_mask is not None for v in man.views))


def _rle_run_mask(runf, rpl):
    """Per-run survivor mask of `rpl` under the relation's run-space
    filter state, or None when the alignment proof doesn't cover this
    plate (filter over a different run partition, or impure)."""
    if runf == "pure":
        return jnp.ones(jnp.shape(rpl.ends), dtype=jnp.bool_)
    if isinstance(runf, tuple) and runf[0] is rpl.ends:
        return runf[1]
    return None


def _row_count_of(info) -> int:
    from snappydata_tpu.storage import mvcc
    from snappydata_tpu.storage.table_store import RowTableData

    if isinstance(info.data, RowTableData):
        return info.data.count()
    return mvcc.snapshot_of(info.data).total_rows()


def _join_reject(reason: str, msg: str) -> None:
    """Reasoned device-join fallback: count the rejection (total + per
    reason string, so operators can see WHY joins leave the device) and
    reroute to the exact host join via CompileError."""
    from snappydata_tpu.observability.metrics import global_registry

    reg = global_registry()
    reg.inc("join_host_fallbacks")
    reg.inc("join_fallback_" + reason)
    raise CompileError(msg)


def _check_device_join_enabled(props) -> None:
    """Per-execution master switch (a bind check, so flipping the conf
    knob needs no plan-cache flush — the bench uses it to time the
    r05-era host-join path side by side)."""
    if not props.get("device_join", True) \
            or not config.global_properties().get("device_join", True):
        _join_reject("disabled", "device_join=off: host path")


def _count_device_join() -> None:
    from snappydata_tpu.observability.metrics import global_registry

    global_registry().inc("join_device_joins")


_expand_cap_warned: set = set()


def _warn_expand_cap(est: int, cap: int) -> None:
    """The expansion-cap fallback must be LOUD (ISSUE requirement): the
    query silently dropping to a single-threaded pandas join reads as a
    hang to operators.  Once per (estimate bucket, cap)."""
    import sys

    key = (est.bit_length(), cap)
    if key in _expand_cap_warned:
        return
    _expand_cap_warned.add(key)
    print(f"warning: device join expansion (~{est:,} bytes) exceeds "
          f"join_expand_max_bytes ({cap:,}) — query runs on the HOST "
          f"join path (single-threaded); raise the knob to keep it on "
          f"device", file=sys.stderr)


_absmax_cache: Dict[Tuple[int, int, int], tuple] = {}


def _require_f64_exact_int_key(info, ordinal: int) -> None:
    """Mixed int/float equi keys compare in the float64 domain; an int64
    key with |v| >= 2^53 would falsely match/miss after the cast.
    Verified per bind (cached per mutation version) — values at risk
    reroute to the exact host join."""
    import weakref

    from snappydata_tpu.storage import mvcc
    from snappydata_tpu.storage.table_store import RowTableData

    data = info.data
    if isinstance(data, RowTableData):
        # version only: the pin's captured version when pinned, else the
        # live attribute — row_snapshot_of would MATERIALIZE the whole
        # table on the unpinned path just to read an int
        pin = mvcc.current_pin()
        ver = pin.row_snapshot(data)[3] if pin is not None \
            else data.version
    else:
        ver = mvcc.snapshot_of(data).version
    key = (id(data), ver, ordinal)
    ok = None
    entry = _absmax_cache.get(key)
    if entry is not None:
        ref, cached_ok = entry
        if ref() is data:
            ok = cached_ok
    if ok is None:
        col = _host_key_columns(info, (ordinal,))[0]
        if col.size == 0:
            ok = True
        else:
            vals = np.abs(np.asarray(
                [0 if v is None else v for v in col], dtype=np.int64)) \
                if col.dtype == object else np.abs(col.astype(np.int64))
            ok = int(vals.max()) < (1 << 53)
        if len(_absmax_cache) > 4096:
            _absmax_cache.clear()
        _absmax_cache[key] = (weakref.ref(data), ok)
    if not ok:
        _join_reject(
            "int_float_key_2p53",
            f"join key {info.name}.{info.schema.fields[ordinal].name} "
            f"holds int values at |v| >= 2^53 — the float64 key domain "
            f"would be inexact; host path")


def _host_key_columns(info, ordinals: Tuple[int, ...]) -> List[np.ndarray]:
    from snappydata_tpu.storage import mvcc
    from snappydata_tpu.storage.table_store import RowTableData

    data = info.data
    if isinstance(data, RowTableData):
        arrays, _, n, _ver = mvcc.row_snapshot_of(data)
        return [np.asarray(arrays[i])[:n] for i in ordinals]
    m = mvcc.snapshot_of(data)
    out = []
    for i in ordinals:
        name = info.schema.fields[i].name
        parts = []
        for view in m.views:
            live = view.live_mask()
            parts.append(np.asarray(data._decode_all(view)[name])[live])
        if m.row_count:
            parts.append(np.asarray(m.row_arrays[i])[:m.row_count])
        out.append(np.concatenate(parts) if parts
                   else np.empty(0, dtype=object))
    return out


def _param_scalar(v):
    if isinstance(v, bool):
        return np.asarray(v)
    if isinstance(v, int):
        return np.asarray(v, dtype=np.int64)
    if isinstance(v, float):
        dt = np.float64 if config.use_float64() else np.float32
        return np.asarray(v, dtype=dt)
    # strings ride only through LUT aux builders; position still needs a slot
    return np.asarray(0, dtype=np.int32)


# ==========================================================================
# Compiler
# ==========================================================================

class Compiler:
    """Compiles one device region (Relation/Filter/Project/Join[/Aggregate
    root]) into a CompiledPlan."""

    def __init__(self, catalog, props, partial_raw: bool = False):
        self.catalog = catalog
        self.props = props
        # partial-raw mode (tiled scans): compile a partial-aggregate
        # plan whose outputs stay mergeable [G] arrays — group cards are
        # forced data-independent (nullable keys always get their NULL
        # code slot) so every tile shares one aligned group-index space
        self.partial_raw = partial_raw
        self.relations: List[_RelationInput] = []
        self.aux_builders: List[Callable] = []
        self.static_providers: List[Callable] = []
        self.bind_checks: List[Callable] = []
        # per-join metadata the mesh execution lane reads to pick and
        # apply a distribution strategy (broadcast-build vs
        # shuffle-on-key) — see engine/mesh_exec.py
        self.join_meta: List[Dict] = []

    # -- static/aux plumbing ----------------------------------------------

    def _add_static(self, provider: Callable[[], int]) -> int:
        self.static_providers.append(provider)
        return len(self.static_providers) - 1

    # -- relation scan ----------------------------------------------------

    def compile(self, plan: ast.Plan) -> CompiledPlan:
        is_agg = isinstance(plan, ast.Aggregate)
        _validate_array_usage(plan)
        # scan_compressed_domain rides the compiled plan's STATIC key —
        # flipping the knob re-specializes (and re-binds the matching
        # plate kind) without any plan-cache flush
        self._add_static(_compressed_token)
        # column pruning: per-relation needed ordinals, DFS leaf order
        # (HBM-bandwidth saver; ref analogue: Catalyst column pruning into
        # ColumnTableScan's per-column decoders)
        self._pruned: List[set] = []
        _collect_used(plan, None, self._pruned)
        self._prune_cursor = 0
        emitter, out_cols = self._emit_node(plan)

        n_rel = len(self.relations)

        # trace-time note per (static key, phase): the form each
        # CodePlate decode was emitted in, by site
        # (CompiledPlan._note_slots)
        decode_notes: Dict[tuple, Dict[tuple, str]] = {}
        # and the wide decimal expressions it guarded, by shape
        decimal_notes: Dict[tuple, set] = {}

        def make_ctx(static, arrays, aux, params,
                     phase="single") -> "_TraceCtx":
            from snappydata_tpu.storage.device_decode import (
                BitPlate, CodePlate, RlePlate, bit_values, code_values,
                dict_decode_form, rle_values)

            # a fresh note each trace: a retrace under the same static
            # key (the plates re-bound decoded after a write) starts over
            decode_note = decode_notes[(static, phase)] = {}
            decimal_wide = decimal_notes[(static, phase)] = set()
            # unpack per-relation arrays
            rel_runtimes = []
            pos = 0
            for ri, r in enumerate(self.relations):
                entries = []
                for ci in r.used:
                    entries.append(arrays[pos])
                    pos += 1
                valid = arrays[pos]
                pos += 1
                cap = int(jnp.shape(valid)[1])
                cols = {}
                for ci, (col_arr, null_arr) in zip(r.used, entries):
                    f = r.info.schema.fields[ci]
                    if isinstance(col_arr, CodePlate):
                        # compressed-domain column: value is the LAZY
                        # in-trace dictionary gather (fused/DCE'd by
                        # XLA); comparisons take the code lane
                        dv = DVal(code_values(col_arr), null_arr,
                                  f.dtype, _dict_provider(r.info, ci))
                        dv.cplate = col_arr
                        # counted here, so before XLA drops the decodes
                        # nothing reads (a column met on its codes alone)
                        decode_note[(ri, ci)] = dict_decode_form(
                            col_arr.dicts.shape[1])
                    elif isinstance(col_arr, RlePlate):
                        dv = DVal(rle_values(col_arr, cap), null_arr,
                                  f.dtype, _dict_provider(r.info, ci))
                        dv.rplate = col_arr
                    elif isinstance(col_arr, BitPlate):
                        dv = DVal(bit_values(col_arr, cap), null_arr,
                                  f.dtype, _dict_provider(r.info, ci))
                    else:
                        dv = DVal(col_arr, null_arr, f.dtype,
                                  _dict_provider(r.info, ci))
                    cols[ci] = dv
                rel_runtimes.append((cols, valid))
            return _TraceCtx(rel_runtimes, aux, params, static,
                             decode_note, decimal_wide)

        join_notes = {} if n_rel > 1 else None

        def traced(static, arrays, aux, params):
            ctx = make_ctx(static, arrays, aux, params)
            outs = emitter(ctx)
            if join_notes is not None:
                join_notes[static] = ctx.join_note
            return outs

        traced_pre = traced_main = None
        pre_emit = getattr(self, "_agg_pre_emit", None)
        if pre_emit is not None and not self.partial_raw \
                and self._pre_cacheable(plan):
            main_emit = self._agg_main_emit

            def traced_pre(static, arrays, aux, params):
                return pre_emit(make_ctx(static, arrays, aux, params,
                                         "pre"))

            def traced_main(static, arrays, aux, params, pre):
                return main_emit(make_ctx(static, arrays, aux, params,
                                          "main"), pre)

        out_scope = [oc if isinstance(oc, _ScopeCol)
                     else _ScopeCol(oc.name, oc.dtype, oc.dict_provider)
                     for oc in out_cols]
        cp = CompiledPlan(self.relations, self.aux_builders,
                          self.static_providers, traced, out_scope, is_agg,
                          self.bind_checks,
                          traced_pre=traced_pre, traced_main=traced_main,
                          agg_notes=getattr(self, "_agg_notes", None),
                          tile_merge=getattr(self, "_tile_merge", None),
                          join_notes=join_notes,
                          decode_notes=decode_notes,
                          decimal_notes=decimal_notes,
                          kind=("join_" if n_rel > 1 else "")
                          + (("agg" if plan.group_exprs else "global_agg")
                             if is_agg else "scan")
                          + ("_partial" if self.partial_raw else ""))
        cp.join_meta = self.join_meta
        return cp

    def _pre_cacheable(self, plan: ast.Plan) -> bool:
        """Is the aggregate's prefix (valid + gidx) safe and worthwhile
        to cache?  Requires GROUP BY (a global aggregate's gidx is
        trivial), a single relation (no join for phase B to re-run), and
        no user-defined functions (device-lowered builtins are all
        deterministic; UDF determinism is unknowable)."""
        if not isinstance(plan, ast.Aggregate) or not plan.group_exprs:
            return False
        if len(self.relations) != 1:
            return False
        udfs = getattr(self.catalog, "_functions", None) or {}
        if udfs:
            names = {n.lower() for n in udfs}

            def any_udf(p) -> bool:
                for e in ast.plan_exprs(p):
                    for sub in ast.walk(e):
                        if isinstance(sub, ast.Func) \
                                and sub.name.lower() in names:
                            return True
                return any(any_udf(k) for k in p.children())

            if any_udf(plan):
                return False
        return True

    # -- node emitters -----------------------------------------------------

    def _emit_node(self, plan: ast.Plan):
        """Returns (emitter(ctx) -> (mask, [(val,null)...]), out_cols) for
        the region ROOT, delegating to _emit_rel for the relational body."""
        if isinstance(plan, ast.Aggregate):
            return self._emit_aggregate(plan)
        if isinstance(plan, ast.WindowProject):
            return self._emit_window(plan)
        rel_emit, scope = self._emit_rel(plan)

        def run_root(ctx) -> tuple:
            out = rel_emit(ctx)
            pairs = []
            for i in range(len(scope)):
                dv = out.cols[i]
                if isinstance(dv.value, tuple):
                    raise CompileError(
                        "array-valued output column: host path")
                v = _broadcast_to_mask(dv.value, out.valid)
                nl = dv.null
                pairs.append((v, nl))
            return out.valid, tuple(pairs), ctx.overflow

        return run_root, scope

    # -- window ------------------------------------------------------------

    _WINDOW_DEVICE_FUNCS = frozenset({
        "row_number", "rank", "dense_rank", "sum", "count", "avg", "min",
        "max", "lag", "lead"})

    def _emit_window(self, plan: "ast.WindowProject"):
        """Device OVER(): one lexsort per distinct (PARTITION BY, ORDER BY)
        pair, then SEGMENTED SCANS in the sorted domain — cumulative
        sums/mins via `lax.associative_scan` with a reset-flag monoid,
        rank/row_number from segment- and tie-boundary positions computed
        with `searchsorted` over the (sorted) segment ids — and an inverse
        permutation back to table order. Everything is static-shaped and
        branch-free, which is what the TPU wants (the reference runs
        windows through its execution engine via the PushDownWindow rule,
        SnappySessionState.scala:261; hosteval keeps the general
        fallback)."""
        child, scope = self._emit_rel(plan.child)
        wfs: List[ast.WindowFunc] = []

        def collect(e):
            if isinstance(e, ast.WindowFunc):
                if e not in wfs:
                    wfs.append(e)
                return
            for c in e.children():
                collect(c)

        for e in plan.exprs:
            collect(e)
        if not wfs:
            raise CompileError("window project without window functions")

        builder = self._builder_for(scope)
        groups: Dict[tuple, dict] = {}
        specs = []
        for wf in wfs:
            if wf.name not in self._WINDOW_DEVICE_FUNCS:
                raise CompileError(f"window {wf.name}: host path")
            if wf.name in ("rank", "dense_rank") and not wf.order_by:
                raise CompileError("rank without ORDER BY: host path")
            for oe, *_ in wf.order_by:
                odt = expr_type(oe)
                if odt is None or odt.name in ("string", "array", "map"):
                    raise CompileError("window ORDER BY on non-numeric "
                                       "key: host path")
            arg_run = None
            arg_dtype = None
            offset = 1
            if wf.name in ("sum", "avg", "min", "max"):
                arg_dtype = expr_type(wf.args[0])
                if arg_dtype is None or not T.is_numeric(arg_dtype):
                    raise CompileError("window aggregate over non-numeric "
                                       "argument: host path")
                arg_run = builder.emit(wf.args[0])
            elif wf.name == "count" and wf.args:
                arg_run = builder.emit(wf.args[0])
            elif wf.name in ("lag", "lead"):
                if not wf.order_by:
                    raise CompileError("lag/lead without ORDER BY")
                if len(wf.args) > 2:
                    raise CompileError("lag/lead default value: host path")
                arg_dtype = expr_type(wf.args[0])
                if arg_dtype is not None and arg_dtype.name == "string":
                    raise CompileError("lag/lead over strings: host path")
                if len(wf.args) > 1:
                    if not isinstance(wf.args[1], ast.Lit):
                        raise CompileError("non-literal lag/lead offset")
                    offset = int(wf.args[1].value)
                arg_run = builder.emit(wf.args[0])
            gk = (wf.partition_by, wf.order_by)
            if gk not in groups:
                groups[gk] = {
                    "part": [builder.emit(p) for p in wf.partition_by],
                    "order": [(builder.emit(o[0]), o[1],
                               o[2] if len(o) > 2 else None)
                              for o in wf.order_by],
                }
            specs.append((wf, gk, arg_run, arg_dtype, offset))

        # select list with window values as appended pseudo-columns
        ext_scope = list(scope) + [
            _ScopeCol(f"__w{i}", expr_type(wf) or T.DOUBLE, None, True)
            for i, wf in enumerate(wfs)]

        def rewrite(e):
            if isinstance(e, ast.WindowFunc):
                i = wfs.index(e)
                return ast.Col(f"__w{i}", None, len(scope) + i,
                               ext_scope[len(scope) + i].dtype)
            return e.map_children(rewrite)

        out_exprs = [rewrite(e) for e in plan.exprs]
        ext_builder = self._builder_for(ext_scope)
        out_runs = [ext_builder.emit(
            e.child if isinstance(e, ast.Alias) else e) for e in out_exprs]
        out_scope = [
            _ScopeCol(_expr_name(orig), expr_type(orig) or T.DOUBLE,
                      self._derived_dict_provider(
                          e.child if isinstance(e, ast.Alias) else e,
                          ext_scope), True)
            for orig, e in zip(plan.exprs, out_exprs)]

        fdt = jnp.float64 if config.use_float64() else jnp.float32

        def run_window(ctx) -> tuple:
            out = child(ctx)
            valid2 = out.valid
            flatmask = valid2.reshape(-1)
            n = int(flatmask.shape[0])
            idx = jnp.arange(n)
            rt = Runtime(out.cols, ctx.params, ctx.aux_slice(builder),
                         ctx, out.valid)

            def flat(dv: DVal):
                v = _broadcast_to_mask(dv.value, valid2).reshape(-1)
                nl = _broadcast_to_mask(dv.null, valid2).reshape(-1) \
                    if dv.null is not None else None
                return v, nl

            gdata: Dict[tuple, dict] = {}
            for gk, g in groups.items():
                part_flat = []
                for r in g["part"]:
                    dv = r(rt)
                    v, nl = flat(dv)
                    part_flat.append(DVal(v, nl, dv.dtype, dv.dictionary))
                pk = _combine_keys(part_flat) if part_flat \
                    else jnp.zeros(n, dtype=jnp.int64)
                pk = jnp.where(flatmask, pk, jnp.int64(_I64_MAX))
                okeys = []
                for r, asc, nf in g["order"]:
                    v, nl = flat(r(rt))
                    if v.dtype == jnp.bool_:
                        v = v.astype(jnp.int32)
                    kv = v if asc else -v
                    if nl is not None:
                        # Spark: ASC → NULLS FIRST, DESC → NULLS LAST,
                        # unless an explicit NULLS FIRST/LAST overrides
                        nulls_first = nf if nf is not None else asc
                        if jnp.issubdtype(kv.dtype, jnp.floating):
                            ext = jnp.asarray(
                                -np.inf if nulls_first else np.inf,
                                dtype=kv.dtype)
                        else:
                            info = np.iinfo(np.dtype(kv.dtype.name))
                            ext = jnp.asarray(
                                info.min if nulls_first else info.max,
                                dtype=kv.dtype)
                        kv = jnp.where(nl, ext, kv)
                    okeys.append(kv)
                perm = jnp.lexsort(tuple(reversed(okeys)) + (pk,))
                inv = jnp.argsort(perm)
                gs = pk[perm]
                one = jnp.ones(1, dtype=bool)
                new_seg = jnp.concatenate([one, gs[1:] != gs[:-1]])
                seg_id = jnp.cumsum(new_seg) - 1
                seg_first = jnp.searchsorted(seg_id, seg_id, side="left")
                seg_last = jnp.searchsorted(seg_id, seg_id,
                                            side="right") - 1
                d = dict(perm=perm, inv=inv, new_seg=new_seg,
                         seg_id=seg_id, seg_first=seg_first,
                         seg_last=seg_last)
                if okeys:
                    tie_new = new_seg
                    for kv in okeys:
                        ks = kv[perm]
                        tie_new = tie_new | jnp.concatenate(
                            [one, ks[1:] != ks[:-1]])
                    tie_id = jnp.cumsum(tie_new) - 1
                    d["tie_id"] = tie_id
                    d["tie_first"] = jnp.searchsorted(tie_id, tie_id,
                                                      side="left")
                    d["tie_last"] = jnp.searchsorted(tie_id, tie_id,
                                                     side="right") - 1
                gdata[gk] = d

            def segscan(op, vals, new_seg):
                """Inclusive segmented scan: reset at segment starts."""
                def comb(a, b):
                    af, av = a
                    bf, bv = b
                    return af | bf, jnp.where(bf, bv, op(av, bv))

                _f, outv = jax.lax.associative_scan(
                    comb, (new_seg, vals))
                return outv

            win_vals: List[DVal] = []
            for wf, gk, arg_run, arg_dtype, offset in specs:
                d = gdata[gk]
                perm, inv = d["perm"], d["inv"]
                frame_end = d["tie_last"] if wf.order_by else d["seg_last"]
                if wf.name == "row_number":
                    res = idx - d["seg_first"] + 1
                    win_vals.append(DVal(res[inv], None, T.LONG))
                    continue
                if wf.name == "rank":
                    res = d["tie_first"] - d["seg_first"] + 1
                    win_vals.append(DVal(res[inv], None, T.LONG))
                    continue
                if wf.name == "dense_rank":
                    res = d["tie_id"] - d["tie_id"][d["seg_first"]] + 1
                    win_vals.append(DVal(res[inv], None, T.LONG))
                    continue
                if wf.name in ("lag", "lead"):
                    dv = arg_run(rt)
                    v, nl = flat(dv)
                    vs = v[perm]
                    nls = nl[perm] if nl is not None else None
                    k = offset if wf.name == "lag" else -offset
                    src = idx - k
                    ok = (src >= d["seg_first"]) & (src <= d["seg_last"])
                    srcc = jnp.clip(src, 0, n - 1)
                    val_s = vs[srcc]
                    null_s = ~ok
                    if nls is not None:
                        null_s = null_s | nls[srcc]
                    win_vals.append(DVal(val_s[inv], null_s[inv],
                                         arg_dtype or dv.dtype))
                    continue
                # aggregates: sum / count / avg / min / max
                if arg_run is not None:
                    dv = arg_run(rt)
                    v, nl = flat(dv)
                else:  # count(*)
                    v = jnp.ones(n, dtype=jnp.int64)
                    nl = None
                vs = v[perm]
                notnull = jnp.ones(n, dtype=bool) if nl is None \
                    else ~nl[perm]
                notnull = notnull & flatmask[perm]
                cnt = segscan(jnp.add, notnull.astype(jnp.int64),
                              d["new_seg"])[frame_end]
                if wf.name == "count":
                    win_vals.append(DVal(cnt[inv], None, T.LONG))
                    continue
                if wf.name in ("sum", "avg"):
                    acc_dt = fdt if wf.name == "avg" or \
                        jnp.issubdtype(vs.dtype, jnp.floating) else jnp.int64
                    contrib = jnp.where(notnull, vs, 0).astype(acc_dt)
                    ssum = segscan(jnp.add, contrib, d["new_seg"])[frame_end]
                    if wf.name == "avg":
                        res = ssum / jnp.maximum(cnt, 1).astype(fdt)
                    else:
                        res = ssum
                    win_vals.append(DVal(res[inv], (cnt == 0)[inv],
                                         expr_type(wf) or T.DOUBLE))
                    continue
                # min / max
                if jnp.issubdtype(vs.dtype, jnp.floating):
                    sent = jnp.asarray(np.inf if wf.name == "min"
                                       else -np.inf, dtype=vs.dtype)
                else:
                    ii = np.iinfo(np.dtype(vs.dtype.name))
                    sent = jnp.asarray(ii.max if wf.name == "min"
                                       else ii.min, dtype=vs.dtype)
                contrib = jnp.where(notnull, vs, sent)
                op = jnp.minimum if wf.name == "min" else jnp.maximum
                res = segscan(op, contrib, d["new_seg"])[frame_end]
                win_vals.append(DVal(res[inv], (cnt == 0)[inv],
                                     arg_dtype or T.DOUBLE))

            ext_cols: Dict[int, DVal] = {}
            for i, dv in out.cols.items():
                v, nl = flat(dv)
                ext_cols[i] = DVal(v, nl, dv.dtype, dv.dictionary)
            for i, dv in enumerate(win_vals):
                ext_cols[len(scope) + i] = dv
            rt2 = Runtime(ext_cols, ctx.params,
                          ctx.aux_slice(ext_builder), ctx)
            pairs = []
            for r in out_runs:
                dv = r(rt2)
                pairs.append((_broadcast_to_mask(dv.value, flatmask),
                              dv.null))
            return flatmask, tuple(pairs), ctx.overflow

        return run_window, out_scope

    def _emit_rel(self, plan: ast.Plan):
        """Relational body → (emitter(ctx)->RelOut, scope list[_ScopeCol])."""
        if isinstance(plan, ast.Relation):
            info = self.catalog.lookup_table(plan.name)
            pruned = self._pruned[self._prune_cursor] \
                if self._prune_cursor < len(self._pruned) else None
            self._prune_cursor += 1
            used = sorted(pruned) if pruned is not None \
                else list(range(len(info.schema)))
            from snappydata_tpu.storage.device import (
                map_device_eligible, struct_device_eligible)
            from snappydata_tpu.storage.table_store import RowTableData

            col_store = not isinstance(info.data, RowTableData)
            for uci in used:
                fdt = info.schema.fields[uci].dtype
                ok_complex = col_store and (
                    (fdt.name == "array"
                     and (T.is_numeric(fdt.element)
                          or fdt.element.name == "string"))
                    or (fdt.name == "map" and map_device_eligible(fdt))
                    or (fdt.name == "struct"
                        and struct_device_eligible(fdt)))
                if fdt.name in ("map", "struct", "array") \
                        and not ok_complex:
                    # numeric/string-element arrays, MAP<STRING, V> and
                    # flat STRUCTs have device plates (string parts
                    # ride as dictionary codes); nested complex types
                    # stay host
                    raise CompileError(
                        "complex-typed columns evaluate on the host path")
            rel_idx = len(self.relations)
            self.relations.append(_RelationInput(info, used))
            scope = [
                _ScopeCol(f.name, f.dtype, _dict_provider(info, i),
                          f.nullable)
                for i, f in enumerate(info.schema.fields)]

            def run_scan(ctx) -> RelOut:
                cols, valid = ctx.rels[rel_idx]
                return RelOut(dict(cols), valid, runf="pure")

            return run_scan, scope

        if isinstance(plan, ast.SubqueryAlias):
            return self._emit_rel(plan.child)

        if isinstance(plan, ast.Filter):
            child, scope = self._emit_rel(plan.child)
            # sargable conjuncts directly over a base scan feed per-batch
            # stats skipping at bind time (optimizer pushdown puts
            # single-table predicates right here)
            inner = plan.child
            while isinstance(inner, ast.SubqueryAlias):
                inner = inner.child
            if isinstance(inner, ast.Relation) and self.relations:
                _collect_sargs(plan.condition, self.relations[-1])
            builder = self._builder_for(scope)
            pred = builder.emit(plan.condition)

            def run_filter(ctx) -> RelOut:
                out = child(ctx)
                rt = Runtime(out.cols, ctx.params,
                             ctx.aux_slice(builder), ctx, out.valid)
                with tracing.op_scope("filter"):
                    p = pred(rt)
                    keep = p.value
                    if p.null is not None:
                        keep = keep & ~p.null
                # run-space bookkeeping for the RLE aggregate lane: the
                # filter stays pure only if THIS predicate survived in
                # run space over the same run partition as every one
                # before it
                runf = None
                if p.rmask is not None and p.null is None:
                    if out.runf == "pure":
                        runf = (p.rends, p.rmask)
                    elif (isinstance(out.runf, tuple)
                          and out.runf[0] is p.rends):
                        runf = (p.rends, out.runf[1] & p.rmask)
                return RelOut(out.cols, out.valid & keep, runf=runf)

            return run_filter, scope

        if isinstance(plan, ast.Project):
            child, scope = self._emit_rel(plan.child)
            builder = self._builder_for(scope)
            runs = [builder.emit(e) for e in plan.exprs]
            out_scope = [
                _ScopeCol(_expr_name(e), expr_type(e),
                          self._derived_dict_provider(e, scope), True)
                for e in plan.exprs]

            def run_project(ctx) -> RelOut:
                out = child(ctx)
                rt = Runtime(out.cols, ctx.params,
                             ctx.aux_slice(builder), ctx, out.valid)
                cols = {}
                for i, r in enumerate(runs):
                    dv = r(rt)
                    if dv.dictionary is not None:
                        out_scope[i].dict_provider = dv.dictionary \
                            if callable(dv.dictionary) else (lambda d=dv.dictionary: d)
                    cols[i] = dv
                return RelOut(cols, out.valid, runf=out.runf)

            return run_project, out_scope

        if isinstance(plan, ast.Join):
            return self._emit_join(plan)

        raise CompileError(
            f"node {type(plan).__name__} not supported in device region")

    # -- join --------------------------------------------------------------

    def _emit_join(self, plan: ast.Join):
        """General device join: sorted build + match RANGES per probe key.

        How a probe key finds its range is ops/join.probe_lowering's
        choice at trace time, from the backend and the two shapes: one
        sort-merge of build and probe keys (the TPU, unless the probe is
        far smaller than its build) or searchsorted loops; the trace-time
        note says which (`join_merge_probes`, `join_search_loops`).
        Unique builds (the dim/PK case) gather their single passing match
        directly on the probe shape — under the merge that row comes out
        of the merge itself; non-unique builds prefix-sum the
        range widths into a bind-time-bucketed expanded output
        (ops/join.expand) — one-to-many/many-to-many inner, left, right
        and full outer all stay on device.  The sorted build keys +
        argsort order are a cached artifact keyed on the build's bind
        identity (ops/join.build_artifact), so repeated executions skip
        the per-execution argsort; query filters on the build side apply
        through a pass mask over the sorted order instead of re-sorting.
        Shapes with no device lowering reroute to the exact host join via
        reasoned `join_fallback_*` counters."""
        from snappydata_tpu.ops import join as _dj

        props = self.props
        rel_lo = len(self.relations)
        left, lscope = self._emit_rel(plan.left)
        rel_mid = len(self.relations)
        right, rscope = self._emit_rel(plan.right)
        rel_hi = len(self.relations)
        nleft = len(lscope)
        how = plan.how
        # join relations bind DECODED plates: build artifacts and probe
        # key encodes read flat [B*cap] value layouts outside the trace
        # (counted compressed_fallback_join_key when a compressible
        # column decodes because of this)
        for r in self.relations[rel_lo:rel_hi]:
            r.allow_code = False

        equi, residual = _split_equi(plan.condition, nleft)
        if not equi:
            _join_reject("non_equi",
                         "non-equi/cross join not supported on device")
        if residual is not None and how != "inner":
            # an ON-clause residual on an outer join NULL-extends failing
            # pairs — the device's post-join filter would DROP them; and
            # semi/anti drop the right columns before the residual could
            # run. Host path evaluates residuals per candidate pair.
            _join_reject("residual_outer",
                         f"{how} join with residual: host path")
        self.bind_checks.append(
            lambda _p=self.props: _check_device_join_enabled(_p))

        # -- per-pair key domain: how both sides encode into int64 --------
        enc_spec: List[str] = []
        for li, ri in equi:
            ldt = lscope[li].dtype
            rdt = rscope[ri - nleft].dtype
            if ldt is None or rdt is None:
                _join_reject("untyped_key",
                             "join key without a static type: host path")
            if ldt.name == "string" or rdt.name == "string":
                if ldt.name != rdt.name:
                    _join_reject("string_nonstring_key",
                                 "string vs non-string join key: host path")
                enc_spec.append("raw")
                continue
            l_ex = ldt.name == "decimal" \
                and np.dtype(ldt.device_dtype()).kind == "i"
            r_ex = rdt.name == "decimal" \
                and np.dtype(rdt.device_dtype()).kind == "i"
            if l_ex or r_ex:
                # exact decimals carry SCALED int64 plates — comparable
                # only against the same scale's scaled domain
                if not (l_ex and r_ex and ldt.scale == rdt.scale):
                    _join_reject("decimal_key_mix",
                                 "exact-decimal join key against a "
                                 "different value domain: host path")
                enc_spec.append("raw")
                continue
            lk = np.dtype(ldt.device_dtype())
            rk = np.dtype(rdt.device_dtype())
            if (lk.kind == "f" or rk.kind == "f") and lk != rk:
                # mixed int/float (or f32/f64): compare in float64 —
                # exact for the float side; int sides are bind-checked
                # below to stay under 2^53
                enc_spec.append("f64")
            else:
                enc_spec.append("raw")

        # -- base-source resolution (build AND probe sides) ---------------
        bsources = [self._resolve_join_source(plan.right, ri - nleft,
                                              rel_mid, rel_hi)
                    for _, ri in equi]
        psources = [self._resolve_join_source(plan.left, li,
                                              rel_lo, rel_mid)
                    for li, _ in equi]
        build_rel = build_ords = None
        if all(s is not None for s in bsources) \
                and len({id(s[0]) for s in bsources}) == 1:
            build_rel = bsources[0][0]
            build_ords = tuple(s[2] for s in bsources)
        probe_rel = None
        if all(s is not None for s in psources) \
                and len({id(s[0]) for s in psources}) == 1:
            probe_rel = psources[0][0]

        # mixed int/float exactness: bind-check every INT side's values —
        # a derived int key can't be proven under 2^53
        for pi, (li, ri) in enumerate(equi):
            if enc_spec[pi] != "f64":
                continue
            for side_dt, src in ((lscope[li].dtype, psources[pi]),
                                 (rscope[ri - nleft].dtype, bsources[pi])):
                if np.dtype(side_dt.device_dtype()).kind not in ("i", "u"):
                    continue
                if src is None:
                    _join_reject("mixed_key_unprovable",
                                 "mixed int/float join key on a derived "
                                 "column (2^53 exactness unprovable): "
                                 "host path")
                self.bind_checks.append(
                    lambda _i=src[1], _o=src[2]:
                    _require_f64_exact_int_key(_i, _o))

        # string join keys: each table has its OWN dictionary, so codes
        # are not comparable across tables — translate left codes into
        # the right table's code space via a vectorized LUT (unmatched
        # values → -1, which equals no real code), cached per dictionary
        # version when both are base-table dictionaries
        str_trans: Dict[int, int] = {}
        trans_getters: Dict[int, Callable] = {}
        for pi, (li, ri) in enumerate(equi):
            lprov = lscope[li].dict_provider
            rprov = rscope[ri - nleft].dict_provider
            if lprov is None or rprov is None:
                continue
            ck = owners = None
            if psources[pi] is not None and bsources[pi] is not None:
                ck = ("trans", id(psources[pi][1].data), psources[pi][2],
                      id(bsources[pi][1].data), bsources[pi][2])
                owners = (psources[pi][1].data, bsources[pi][1].data)

            def build_trans(params, _lp=lprov, _rp=rprov, _ck=ck,
                            _ow=owners):
                return _dj.translate_codes(_lp(), _rp(), cache_key=_ck,
                                           owners=_ow)

            self.aux_builders.append(build_trans)
            str_trans[pi] = len(self.aux_builders) - 1
            trans_getters[pi] = (
                lambda _lp=lprov, _rp=rprov, _ck=ck, _ow=owners:
                _dj.translate_codes(_lp(), _rp(), cache_key=_ck,
                                    owners=_ow))

        artifact_mode = build_rel is not None
        if not artifact_mode and how not in ("semi", "anti"):
            # semi/anti only need membership (any build works, sorted
            # in-trace); everything else needs the artifact's uniqueness
            # verdict / expansion bound, both of which require base
            # columns to read outside the trace
            _join_reject("derived_build",
                         "join build side is a derived relation: "
                         "host path")

        # a build side with NO in-trace filter keeps every row of a real
        # key's sorted run live (dead/NULL rows are key-sentineled to the
        # end) — the dense range math skips the pass prefix-sum and its
        # per-execution searchsorteds (the hot Q3-class shape)
        def _has_filter(p: ast.Plan) -> bool:
            return isinstance(p, ast.Filter) \
                or any(_has_filter(k) for k in p.children())

        build_filtered = _has_filter(plan.right)

        art_aux = None
        artifact_of = None
        shuf_si = None
        if artifact_mode:
            # mesh shuffle-on-key: when the mesh lane's bucketed
            # exchange re-laid both sides out bucket-aligned, the trace
            # sorts its LOCAL build slice in-trace instead of indexing
            # the global artifact (whose order permutation describes the
            # pre-exchange layout).  Rides the STATIC key, so shuffled
            # and unshuffled executions are distinct specializations.
            def shuffle_provider() -> int:
                from snappydata_tpu.engine import mesh_exec

                return 1 if mesh_exec.shuffle_active() else 0

            shuf_si = self._add_static(shuffle_provider)
            build_rel.no_skip = True  # order indexes the FULL flat layout
            enc_sig = tuple(enc_spec)

            def artifact_of(_rel=build_rel, _ords=build_ords,
                            _sig=enc_sig):
                dt = _rel.bind()

                def compute():
                    pairs = []
                    anynull = None
                    for ci, spec in zip(_ords, _sig):
                        v = dt.columns[ci].reshape(-1)
                        nl = dt.nulls.get(ci)
                        nl = nl.reshape(-1) if nl is not None else None
                        if spec == "f64":
                            v = v.astype(jnp.float64)
                        pairs.append((v, nl))
                        anynull = _or_null(anynull, nl)
                    return _dj.encode_build_keys(
                        pairs, dt.valid.reshape(-1), anynull)

                return _dj.build_artifact(dt.valid, (_ords, _sig), compute)

            # _bind evaluates aux builders BEFORE static providers, so
            # stashing the artifact here lets mode_provider reuse it —
            # otherwise a cache-disabled (or over-budget) bind pays the
            # build argsort + uniqueness device_get TWICE per execution
            art_tls = threading.local()

            def _aux_artifact(params):
                from snappydata_tpu.engine import mesh_exec

                if mesh_exec.shuffle_active():
                    # shuffle binds sort per-shard in-trace — feeding the
                    # GLOBAL sorted artifact would replicate it to every
                    # device for nothing (mode_provider re-derives the
                    # uniqueness verdict/bound via artifact_of directly)
                    return np.zeros((2, 1), dtype=np.int64)
                art = artifact_of()
                if how not in ("semi", "anti"):
                    # mode_provider is the stash's only consumer; a
                    # semi/anti bind must not leave the artifact pinned
                    # in the thread-local (invisible to the cache ledger)
                    art_tls.art = art
                return art["packed"]

            self.aux_builders.append(_aux_artifact)
            art_aux = len(self.aux_builders) - 1

        mode_si = bucket_si = None
        if artifact_mode and how not in ("semi", "anti"):
            tls = threading.local()
            null_extend = how in ("left", "full")

            def _row_width() -> int:
                """Approximate bytes per expanded output row (value +
                null byte per used column of both sides + the mask)."""
                w = 1
                for r in (probe_rel, build_rel):
                    if r is None:
                        continue
                    for ci in r.used:
                        f = r.info.schema.fields[ci]
                        try:
                            w += np.dtype(
                                f.dtype.device_dtype()).itemsize + 1
                        except Exception:
                            w += 9
                return w

            def _check_expand_cap(slots: int) -> None:
                cap = int(props.get("join_expand_max_bytes", 0) or 0)
                est = slots * _row_width()
                if cap and est > cap:
                    _warn_expand_cap(est, cap)
                    _join_reject(
                        "expand_bytes",
                        f"join expansion needs ~{est:,} bytes > "
                        f"join_expand_max_bytes={cap:,}: host path")

            def mode_provider() -> int:
                from snappydata_tpu.observability.metrics import \
                    global_registry

                reg = global_registry()
                art = getattr(art_tls, "art", None)
                art_tls.art = None  # consume: never reuse across binds
                if art is None:
                    art = artifact_of()
                # right/full outer appends F build-extension slots (one
                # per build flat row) to every output column — they count
                # against the byte cap exactly like expansion slots
                fext = int(art["skeys"].shape[0]) \
                    if how in ("right", "full") else 0
                # join_device_joins counts only once the bind can no
                # longer reject — a reroute below must not ALSO show up
                # as a device join in the dashboard's device/host split
                if art["unique"]:
                    if fext:
                        probe_slots = int(probe_rel.bind().valid.size) \
                            if probe_rel is not None else 0
                        _check_expand_cap(probe_slots + fext)
                    tls.bucket = 0
                    reg.inc("join_device_joins")
                    return 0
                if probe_rel is None:
                    _join_reject(
                        "derived_probe_nonunique",
                        "one-to-many join with a derived probe side "
                        "(expansion bound unprovable): host path")
                dtp = probe_rel.bind()

                def compute_pkeys():
                    pairs = []
                    anynull = None
                    for pi2, (s, spec) in enumerate(
                            zip(psources, enc_spec)):
                        v = dtp.columns[s[2]].reshape(-1)
                        nl = dtp.nulls.get(s[2])
                        nl = nl.reshape(-1) if nl is not None else None
                        getter = trans_getters.get(pi2)
                        if getter is not None:
                            trans = jnp.asarray(getter())
                            v = trans[jnp.clip(v, 0, trans.shape[0] - 1)]
                        if spec == "f64":
                            v = v.astype(jnp.float64)
                        pairs.append((v, nl))
                        anynull = _or_null(anynull, nl)
                    return (_dj.encode_probe_keys(pairs, anynull),
                            dtp.valid.reshape(-1))

                bound = _dj.probe_expand_bound(
                    art, dtp.valid, tuple(s[2] for s in psources),
                    null_extend, compute_pkeys)
                from snappydata_tpu.engine import mesh_exec

                nd = mesh_exec.bind_devices()
                if nd > 1:
                    # mesh lane: each shard expands only ITS slice of
                    # the probe — size the per-shard output axis to the
                    # shard's own bound instead of replicating the
                    # GLOBAL bucket on every device.  Broadcast shards
                    # on batch position: the top-ceil(B/D) per-batch
                    # bound is exact-sound; a key-bucket shuffle gets
                    # fair-share with 2x skew headroom.  An
                    # under-estimate trips the in-trace overflow flag
                    # (loud reroute), never silent row loss.
                    if mesh_exec.shuffle_active():
                        bound = min(bound, -(-bound // nd) * 2)
                    else:
                        bound = min(bound, _dj.probe_expand_bound_per_shard(
                            art, dtp.valid,
                            tuple(s[2] for s in psources), null_extend,
                            compute_pkeys, nd, tuple(dtp.valid.shape)))
                bucket = _dj.expand_bucket(max(1, bound))
                _check_expand_cap(bucket + fext)
                reg.inc("join_device_joins")
                reg.inc("join_expand_out_rows", bucket)
                reg.inc("join_expand_probe_rows",
                        max(1, int(dtp.total_rows)))
                tls.bucket = bucket
                return 1

            mode_si = self._add_static(mode_provider)
            # registered AFTER mode_provider: _bind evaluates statics in
            # order, so the thread-local bucket is always fresh
            bucket_si = self._add_static(
                lambda: int(getattr(tls, "bucket", 0)))
        else:
            self.bind_checks.append(_count_device_join)

        if how in ("semi", "anti"):
            out_scope = [_ScopeCol(s.name, s.dtype, s.dict_provider,
                                   s.nullable) for s in lscope]
        else:
            lnul = how in ("right", "full")
            rnul = how in ("left", "full")
            out_scope = [_ScopeCol(s.name, s.dtype, s.dict_provider,
                                   True if lnul else s.nullable)
                         for s in lscope] + \
                        [_ScopeCol(s.name, s.dtype, s.dict_provider,
                                   True if rnul else s.nullable)
                         for s in rscope]
        builder = self._builder_for(lscope + rscope)
        residual_run = builder.emit(residual) if residual is not None \
            else None

        # distribution metadata for the mesh lane (engine/mesh_exec.py):
        # which relations carry the probe/build sides, how their keys
        # encode into the shared int64 domain, and the static/aux slots
        # the shuffle specialization rides
        self.join_meta.append({
            "how": how,
            "artifact_mode": artifact_mode,
            "probe_rel": probe_rel,
            "build_rel": build_rel,
            "probe_ords": tuple(s[2] for s in psources)
            if all(s is not None for s in psources) else None,
            "build_ords": build_ords,
            "enc_spec": tuple(enc_spec),
            "trans_getters": dict(trans_getters),
            "art_aux": art_aux,
            "shuf_si": shuf_si,
            "build_filtered": build_filtered,
        })

        def run_join(ctx) -> RelOut:
            return join_body(ctx, left(ctx), right(ctx))

        from snappydata_tpu.storage.table_store import RowTableData

        row_build = int(build_rel is not None
                        and isinstance(build_rel.info.data, RowTableData))

        # everything of this join is under `join` in the HLO's op_name;
        # its parts (ops/join.py) nest their own names
        @tracing.op_scope("join")
        def join_body(ctx, lo, ro) -> RelOut:
            ctx.join_note["join_device_joins"] += 1
            ctx.join_note["join_probe_rows"] += int(lo.valid.size)
            ctx.join_note["join_build_rows"] += int(ro.valid.size)
            ctx.join_note["join_row_builds"] += row_build
            ctx.join_note["join_multikey_joins"] += int(len(equi) > 1)
            lpairs = [lo.cols[k] for k, _ in equi]
            rpairs = [ro.cols[k - nleft] for _, k in equi]
            # translate left string codes into right code space first
            for pi, aux_i in str_trans.items():
                trans = ctx.aux[aux_i]
                lv = lpairs[pi]
                codes = jnp.clip(lv.value, 0, trans.shape[0] - 1)
                lpairs[pi] = DVal(trans[codes], lv.null, lv.dtype)
            # mixed-domain pairs compare in float64 (bind-checked exact)
            for pi, spec in enumerate(enc_spec):
                if spec == "f64":
                    a, b = lpairs[pi], rpairs[pi]
                    lpairs[pi] = DVal(a.value.astype(jnp.float64),
                                      a.null, a.dtype)
                    rpairs[pi] = DVal(b.value.astype(jnp.float64),
                                      b.null, b.dtype)
            # probe keys on the probe row shape; NULL keys get a sentinel
            # absent from the build (NULL never matches — SQL semantics)
            lpairs = [DVal(_broadcast_to_mask(d.value, lo.valid),
                           _broadcast_to_mask(d.null, lo.valid)
                           if d.null is not None else None, d.dtype)
                      for d in lpairs]
            pnull = None
            for d in lpairs:
                pnull = _or_null(pnull, d.null)
            pkeys = _combine_keys(lpairs)
            if pnull is not None:
                pkeys = jnp.where(pnull,
                                  jnp.int64(_dj.PROBE_NULL_SENTINEL),
                                  pkeys)

            use_art = artifact_mode and (
                shuf_si is None or ctx.static[shuf_si] == 0)
            # how a probe key finds its build rows: one sort-merge or
            # searchsorted loops, from the backend and the two shapes
            lowering = _dj.probe_lowering(
                jax.default_backend(), int(pkeys.size),
                int(ctx.aux[art_aux].shape[1] if use_art
                    else ro.valid.size))
            merge = lowering == _dj.PROBE_MERGE
            note = ctx.join_note
            note["join_merge_probes"] += int(merge)
            looped = int(not merge)     # loops one search emits
            # a unique build answers inner/left with one row a probe key
            direct = mode_si is not None and ctx.static[mode_si] == 0 \
                and how in ("inner", "left")
            found = bpos = None
            if use_art:
                packed = ctx.aux[art_aux]
                skeys, order = packed[0], packed[1]
                pass_flat = ro.valid.reshape(-1)
                if merge and direct:
                    # the merge carries the row and its pass bit: no
                    # ranges, no prefix sum, no order[...] gather
                    found, bpos = _dj.merge_unique(
                        skeys, order,
                        pass_flat[order] if build_filtered else None,
                        pkeys)
                elif build_filtered:
                    # the artifact sorts the FULL snapshot; query filters
                    # on the build side apply through this pass mask
                    # instead of a re-sort
                    counts, basec, cum = _dj.match_ranges(
                        skeys, order, pass_flat, pkeys, lowering)
                    note["join_search_loops"] += 2 * looped

                    def locate(b, r):
                        note["join_search_loops"] += looped
                        return _dj.nth_match(b, r, cum, order, lowering)
                else:
                    counts, basec = _dj.match_ranges_dense(
                        skeys, pkeys, lowering)
                    note["join_search_loops"] += 2 * looped

                    def locate(b, r):
                        return _dj.nth_match_dense(b, r, order)
            else:
                # derived build (semi/anti) OR a mesh shuffle bind: sort
                # in-trace — the key sentinel already excludes filtered/
                # NULL/dead rows (ro.valid carries the in-trace build
                # filter), so the dense range math applies; under
                # shuffle every shard sorts only ITS bucket slice
                rpairs_b = [DVal(_broadcast_to_mask(d.value, ro.valid),
                                 _broadcast_to_mask(d.null, ro.valid)
                                 if d.null is not None else None, d.dtype)
                            for d in rpairs]
                bnull = None
                for d in rpairs_b:
                    bnull = _or_null(bnull, d.null)
                bkeys = _dj.encode_build_keys(
                    [(d.value.reshape(-1),
                      d.null.reshape(-1) if d.null is not None else None)
                     for d in rpairs_b],
                    ro.valid.reshape(-1),
                    bnull.reshape(-1) if bnull is not None else None)
                order = jnp.argsort(bkeys)
                skeys = bkeys[order]
                pass_flat = ro.valid.reshape(-1)
                counts, basec = _dj.match_ranges_dense(
                    skeys, pkeys, lowering)
                note["join_search_loops"] += 2 * looped

                def locate(b, r):
                    return _dj.nth_match_dense(b, r, order)
            if found is None:
                found = counts > 0
            if how == "semi":
                return RelOut(dict(lo.cols), lo.valid & found)
            if how == "anti":
                return RelOut(dict(lo.cols), lo.valid & ~found)

            if direct:
                # unique build: at most ONE passing match per probe row —
                # direct gather on the probe shape, no expansion overhead
                if bpos is None:
                    bpos = locate(basec, jnp.int64(0))
                cols: Dict[int, DVal] = dict(lo.cols)
                for i in sorted(ro.cols.keys()):
                    src = ro.cols[i]
                    with tracing.op_scope("join_gather"):
                        flat_v = _broadcast_to_mask(src.value, ro.valid) \
                            .reshape(-1)
                        gv = flat_v[bpos]
                        gnull = None
                        if src.null is not None:
                            gnull = _broadcast_to_mask(
                                src.null, ro.valid).reshape(-1)[bpos]
                    if how == "left":
                        gnull = _or_null(gnull, ~found)
                    cols[nleft + i] = DVal(gv, gnull, src.dtype,
                                           src.dictionary)
                valid = lo.valid & found if how == "inner" else lo.valid
                out = RelOut(cols, valid)
            else:
                # one-to-many expansion (and right/full NULL-extension of
                # unmatched build rows): FLAT bucketed output
                pvalid_flat = lo.valid.reshape(-1)
                counts_f = jnp.where(pvalid_flat, counts.reshape(-1),
                                     jnp.int64(0))
                base_f = basec.reshape(-1)
                bucket = ctx.static[bucket_si] \
                    if ctx.static[mode_si] == 1 \
                    else int(pvalid_flat.shape[0])
                if how in ("left", "full"):
                    # unmatched (or NULL-key) probe rows keep one slot
                    counts_eff = jnp.where(pvalid_flat,
                                           jnp.maximum(counts_f, 1),
                                           jnp.int64(0))
                else:
                    counts_eff = counts_f
                note["join_expand_out_rows"] += int(bucket)
                # the expansion's own search stays a loop
                note["join_search_loops"] += 1
                probe_of, rank, matched, slot_valid, total = _dj.expand(
                    counts_f, counts_eff, bucket)
                bpos = locate(base_f[probe_of], rank)
                # filters only shrink the bound, so this can fire only on
                # a probe/build mutation racing the bind — reroute to the
                # exact host path rather than drop rows silently
                ctx.overflow = ctx.overflow | (total > bucket)
                ext = how in ("right", "full")
                F = int(order.shape[0])

                def flat_pair(dv, mask2d):
                    v = _broadcast_to_mask(dv.value, mask2d).reshape(-1)
                    nl = _broadcast_to_mask(dv.null, mask2d).reshape(-1) \
                        if dv.null is not None else None
                    return v, nl

                cols = {}
                for i in sorted(lo.cols.keys()):
                    dv = lo.cols[i]
                    if isinstance(dv.value, tuple):
                        raise CompileError("array-plate column through "
                                           "an expanding join: host path")
                    with tracing.op_scope("join_gather"):
                        v, nl = flat_pair(dv, lo.valid)
                        gv = v[probe_of]
                        gnull = nl[probe_of] if nl is not None else None
                    if ext:  # build-extension slots: left side is NULL
                        gv = jnp.concatenate(
                            [gv, jnp.zeros((F,), gv.dtype)])
                        gnull = jnp.concatenate(
                            [gnull if gnull is not None
                             else jnp.zeros((bucket,), jnp.bool_),
                             jnp.ones((F,), jnp.bool_)])
                    cols[i] = DVal(gv, gnull, dv.dtype, dv.dictionary)
                ext_valid = None
                if ext:
                    # mark build rows consumed by a matched slot via
                    # scatter; the rest NULL-extend (right/full outer)
                    consumed = jnp.zeros((F,), jnp.bool_).at[
                        jnp.where(matched, bpos, F)].set(True, mode="drop")
                    ext_valid = pass_flat & ~consumed
                for i in sorted(ro.cols.keys()):
                    src = ro.cols[i]
                    if isinstance(src.value, tuple):
                        raise CompileError("array-plate column through "
                                           "an expanding join: host path")
                    with tracing.op_scope("join_gather"):
                        v, nl = flat_pair(src, ro.valid)
                        gv = v[bpos]
                        gnull = nl[bpos] if nl is not None else None
                    if how in ("left", "full"):
                        gnull = _or_null(gnull, ~matched)
                    if ext:
                        gv = jnp.concatenate([gv, v])
                        gnull = jnp.concatenate(
                            [gnull if gnull is not None
                             else jnp.zeros((bucket,), jnp.bool_),
                             nl if nl is not None
                             else jnp.zeros((F,), jnp.bool_)])
                    cols[nleft + i] = DVal(gv, gnull, src.dtype,
                                           src.dictionary)
                valid = slot_valid
                if ext:
                    valid = jnp.concatenate([valid, ext_valid])
                out = RelOut(cols, valid)
            if residual_run is not None:
                rt = Runtime(out.cols, ctx.params,
                             ctx.aux_slice(builder), ctx, out.valid)
                p = residual_run(rt)
                keep = p.value
                if p.null is not None:
                    keep = keep & ~p.null
                out = RelOut(out.cols, out.valid & keep)
            return out

        return run_join, out_scope

    def _resolve_join_source(self, plan: ast.Plan, ordinal: int,
                             rel_lo: int, rel_hi: int):
        """Resolve a join-side scope ordinal to (_RelationInput, TableInfo,
        base ordinal) — the leaf whose device plates the build artifact /
        expansion bound read outside the trace.  None when the column is
        derived, spans a nested join, or the side references the same
        base table more than once (ambiguous)."""
        got = self._resolve_build_source(plan, ordinal)
        if got is None:
            return None
        info, ci = got
        rels = [r for r in self.relations[rel_lo:rel_hi] if r.info is info]
        if len(rels) != 1:
            return None
        return rels[0], info, ci

    def _resolve_build_source(self, plan: ast.Plan, ordinal: int
                              ) -> Optional[Tuple[object, int]]:
        """Map a build-side scope ordinal to its base (TableInfo, schema
        ordinal), following filters/aliases/plain-column projections.
        Filters only REMOVE rows, so uniqueness of the base column implies
        uniqueness of the filtered build side (conservative the safe way
        round). None = unprovable."""
        if isinstance(plan, (ast.SubqueryAlias, ast.Filter)):
            return self._resolve_build_source(plan.child, ordinal)
        if isinstance(plan, ast.Relation):
            info = self.catalog.lookup_table(plan.name)
            return None if info is None else (info, ordinal)
        if isinstance(plan, ast.Project):
            e = plan.exprs[ordinal]
            if isinstance(e, ast.Alias):
                e = e.child
            if isinstance(e, ast.Col) and e.index is not None:
                return self._resolve_build_source(plan.child, e.index)
            return None
        return None

    # -- aggregate ---------------------------------------------------------

    def _emit_aggregate(self, plan: ast.Aggregate):
        child, scope = self._emit_rel(plan.child)
        builder = self._builder_for(scope)
        props = self.props

        groups = list(plan.group_exprs)
        key_runs = [builder.emit(g) for g in groups]

        # the single base COLUMN table behind a Filter*/alias* chain:
        # the shape whose direct numeric keys can group in code space
        # (vdict) and whose RLE plates can aggregate in run space
        inner = plan.child
        while isinstance(inner, (ast.SubqueryAlias, ast.Filter)):
            inner = inner.child
        base_info = self.relations[-1].info \
            if isinstance(inner, ast.Relation) and self.relations else None

        # collect primitive agg slots (decomposing avg→sum+count etc.)
        slots: List[Tuple[str, Optional[ast.Expr]]] = []  # (kind, arg)

        def slot_of(kind: str, arg: Optional[ast.Expr]) -> int:
            key = (kind, arg)
            for i, s in enumerate(slots):
                if s == key:
                    return i
            slots.append(key)
            return len(slots) - 1

        # the SUM and AVG calls over an exact decimal: reduced as exact
        # scaled int64 (the `decimal_exact_slots` attr)
        exact_decimal_calls = set()

        def exact_decimal(arg) -> bool:
            at = expr_type(arg) if arg is not None else None
            return isinstance(at, T.DecimalType) and at.is_exact

        def rewrite(e: ast.Expr) -> ast.Expr:
            if isinstance(e, ast.Func) and e.name in ast.AGG_FUNCS:
                arg = e.args[0] if e.args else None
                if e.name in ("sum", "avg") and exact_decimal(arg):
                    exact_decimal_calls.add((e.name, arg))
                if e.name == "count":
                    return _SlotRef(slot_of("count", arg), T.LONG)
                if e.name in ("count_distinct", "approx_count_distinct"):
                    return _SlotRef(slot_of("count_distinct", arg), T.LONG)
                if e.name == "sum":
                    return _SlotRef(slot_of("sum", arg), expr_type(e))
                if e.name in ("min", "max", "first", "last"):
                    kind = {"first": "min", "last": "max"}.get(e.name, e.name)
                    return _SlotRef(slot_of(kind, arg), expr_type(arg))
                if e.name == "avg":
                    # the sum slot may be shared with an explicit
                    # sum(x): for exact decimals it holds scaled int64,
                    # so the slot ref carries the decimal type, and the
                    # average is the exact sum over the exact count
                    # rounded HALF_UP at Spark's DECIMAL(p+4, s+4)
                    at = expr_type(arg) if arg is not None else T.DOUBLE
                    st = T.decimal_sum_type(at) if at.name == "decimal" \
                        else T.DOUBLE
                    s = _SlotRef(slot_of("sum", arg), st)
                    c = _SlotRef(slot_of("count", arg), T.LONG)
                    if exact_decimal(arg):
                        return DecimalAvg(s, c, T.decimal_avg_type(at))
                    return ast.BinOp("/", s, c)
                if e.name in ("stddev", "variance"):
                    if arg is not None \
                            and expr_type(arg).name == "decimal":
                        # sumsq would square the SCALED representation:
                        # run these moments in the plain float domain
                        arg = ast.Cast(arg, T.DOUBLE)
                    s = _SlotRef(slot_of("sum", arg), T.DOUBLE)
                    s2 = _SlotRef(slot_of("sumsq", arg), T.DOUBLE)
                    c = _SlotRef(slot_of("count", arg), T.LONG)
                    mean = ast.BinOp("/", s, c)
                    var = ast.BinOp("-", ast.BinOp("/", s2, c),
                                    ast.BinOp("*", mean, mean))
                    if e.name == "variance":
                        return var
                    return ast.Func("sqrt", (var,))
                raise CompileError(f"aggregate {e.name} not supported yet")
            # group expression structural match → key ref
            for gi, g in enumerate(groups):
                if e == g:
                    return _KeyRef(gi, expr_type(g))
            return e.map_children(rewrite)

        select_rewritten = [rewrite(e.child if isinstance(e, ast.Alias) else e)
                            for e in plan.agg_exprs]
        slot_arg_runs = [builder.emit(arg) if arg is not None else None
                         for _, arg in slots]

        def _slot_dtype(kind: str, arg) -> T.DataType:
            """Static type of a slot's [G] array — the post-agg scope
            needs it so exact-decimal slot values (scaled int64) are
            recognized by the decimal-aware expression lowering."""
            if kind in ("count", "count_distinct"):
                return T.LONG
            if kind == "sumsq":
                return T.DOUBLE
            at = expr_type(arg) if arg is not None else T.DOUBLE
            if kind == "sum":
                return T.decimal_sum_type(at) if at.name == "decimal" \
                    else at
            return at  # min / max

        slot_dtypes = [_slot_dtype(k, a) for k, a in slots]

        # key cardinalities (static): string keys use padded dict size
        key_infos = []
        for g in groups:
            gt = expr_type(g)
            if gt.name == "string":
                provider = self._derived_dict_provider(g, scope)
                if provider is None:
                    raise CompileError(
                        "string group key without a dictionary: host path")
                base_g = g.child if isinstance(g, ast.Alias) else g
                if not isinstance(base_g, ast.Col):
                    # grouping is by CODE: a non-injective derived value
                    # map (upper() collapsing 'a'/'A') would silently
                    # split groups — verified per bind, host path if so
                    provider = _unique_dict_or_host(provider)
                si = self._add_static(
                    lambda p=provider: _padded_size(len(p())))
                key_infos.append(("dict", si, provider))
            elif gt.name == "boolean":
                key_infos.append(("bool", None, None))
            else:
                # vdict: a direct numeric (non-decimal) key of a base
                # column table groups through its table-global sorted
                # value domain — dict-encoded plates remap per-batch
                # CODES through it (no gather), decoded plates
                # searchsorted their values.  The domain provider can
                # decline per bind (cardinality/NaN), which pushes the
                # static card past max_groups → generic hash path.
                base_g = g.child if isinstance(g, ast.Alias) else g
                vd = None
                if (base_info is not None and isinstance(base_g, ast.Col)
                        and base_g.index is not None
                        and gt.name not in ("decimal", "string")
                        and T.is_numeric(gt)):
                    vd = _numeric_domain_provider(
                        base_info, base_g.index, props.max_groups)
                if vd is not None:
                    mg = props.max_groups
                    si = self._add_static(
                        lambda p=vd, m=mg: _vdict_card(p(), m))
                    aux_ix = len(self.aux_builders)
                    self.aux_builders.append(
                        lambda params, p=vd: _vdict_lut(p()))
                    key_infos.append(("vdict", si, (vd, aux_ix)))
                else:
                    key_infos.append(("generic", None, None))

        max_groups = props.max_groups
        partial_raw = self.partial_raw

        # Direct-column keys + forced NULL extension: in partial-raw mode
        # a nullable base-column key claims its extra NULL code slot even
        # when the bound plate happens to carry no null mask — whether a
        # window of the table contains NULLs is data-dependent, and the
        # tiled merge needs every tile to agree on the group-index space.
        key_direct: List[bool] = []
        key_force_null: List[bool] = []
        for g in groups:
            base = g.child if isinstance(g, ast.Alias) else g
            direct = isinstance(base, ast.Col) and base.index is not None
            key_direct.append(direct)
            key_force_null.append(bool(partial_raw and direct
                                       and scope[base.index].nullable))

        # reduction-strategy knob rides the static key: flipping
        # agg_reduce_strategy re-specializes the executable, no plan
        # cache flush needed
        strategy_si = self._add_static(lambda p=props: _strategy_token(p))
        # aggregate-on-codes knob + run-space readiness both ride the
        # static key: knob flips and compaction folding the last delete
        # mask re-specialize without a plan-cache flush
        code_agg_si = self._add_static(lambda p=props: _code_agg_token(p))
        rle_gate_si = self._add_static(
            lambda d=base_info.data: _rle_agg_ready(d)) \
            if base_info is not None else None
        # weak: the plan cache must not keep a dropped table alive just
        # to attribute its fallback counts
        base_table_ref = weakref.ref(base_info.data) \
            if base_info is not None else None
        notes = self._agg_notes = {}

        # post-aggregation expression evaluation over [G] arrays
        out_types = [expr_type(e) for e in plan.agg_exprs]
        post_scope_types: Dict[int, T.DataType] = {}
        post_dicts: Dict[int, Callable] = {}
        for gi, g in enumerate(groups):
            post_scope_types[gi] = expr_type(g)
            if expr_type(g).name == "string":
                post_dicts[gi] = key_infos[gi][2]
        # avg(BIGINT) is an exact int64 sum over an exact count: divided
        # in the accumulators' width, as a float sum is
        post_builder = ExprBuilder(post_scope_types, {}, post_dicts,
                                   int_div_dtype=_acc_dtype(T.DOUBLE))
        post_runs = [post_builder.emit(_slots_to_cols(e, len(groups)))
                     for e in select_rewritten]
        self.aux_builders.extend(post_builder.aux_builders)
        post_aux_off = len(self.aux_builders) - len(post_builder.aux_builders)
        builder_aux_off = 0  # builder auxes registered first (see _builder_for)

        # scan-tile scale (dynamic aux, so the jitted program is shared
        # across tiles): under scan_tile_bytes tiling each execution sees
        # one window of the table, and the exact-decimal sum overflow
        # guard must bound the MERGED total across all tiles — per-tile
        # bounds can each pass while the int64 partial-merge total wraps
        # silently (advisor round 5). 1.0 outside a tile pass.
        rel_inputs = list(self.relations)
        tile_scale_aux = len(self.aux_builders)

        def _tile_scale(params, _rels=rel_inputs):
            from snappydata_tpu.storage.device import current_scan_scale

            scale = 1.0
            for r in _rels:
                scale = max(scale, current_scan_scale(r.info.data))
            return np.float64(scale)

        self.aux_builders.append(_tile_scale)

        out_cols = []
        for e_out, e_rw, dt in zip(plan.agg_exprs, select_rewritten, out_types):
            provider = None
            if dt.name == "string" and isinstance(e_rw, _KeyRef):
                provider = key_infos[e_rw.key][2]
            out_cols.append(OutCol(_expr_name(e_out), dt, provider))

        # partial-raw merge metadata: one merge op per output column so
        # the tiled scan can fold per-tile [G] partials on device.  Only
        # sound when every output is a bare key/slot ref and every key is
        # a direct dict/bool column — data-independent cards mean every
        # tile shares one aligned group-index space.
        if partial_raw:
            tags: List[tuple] = []
            merge_ok = True
            for e_rw in select_rewritten:
                if isinstance(e_rw, _KeyRef):
                    tags.append(("key", e_rw.key))
                elif isinstance(e_rw, _SlotRef):
                    op = {"count": "sum", "sum": "sum", "sumsq": "sum",
                          "min": "min", "max": "max"}.get(
                              slots[e_rw.slot][0])
                    if op is None:
                        merge_ok = False
                    tags.append(("slot", op))
                else:
                    merge_ok = False
            for ki, (kind, _si, _prov) in enumerate(key_infos):
                if kind == "generic" or not key_direct[ki]:
                    merge_ok = False
            if merge_ok:
                def _cards_total(_infos=list(key_infos),
                                 _force=list(key_force_null)) -> int:
                    total = 1
                    for (kind, _si, prov), force in zip(_infos, _force):
                        if kind == "bool":
                            card = 2
                        elif kind == "vdict":
                            card = _vdict_card(prov[0](), max_groups)
                        else:
                            card = _padded_size(len(prov()))
                        total *= card + (1 if force else 0)
                    return total

                self._tile_merge = {"tags": tags, "cards": _cards_total,
                                    "max_groups": max_groups}

        def shape_info(ctx, kdvals, n):
            """Static group-shape decision shared by both phases:
            (fast, cards, eff_cards, num_groups)."""
            cards = []
            fast = True
            for (kind, si, _), kd in zip(key_infos, kdvals):
                if kind in ("dict", "vdict"):
                    cards.append(ctx.static[si])
                elif kind == "bool":
                    cards.append(2)
                else:
                    fast = False
                    cards.append(None)
            # NULL group keys form their own group (SQL semantics): a
            # nullable key gets one extra code slot = card, claimed by
            # rows whose key is NULL (partial-raw forces the slot for
            # nullable base columns — see key_force_null)
            eff_cards = [c + 1 if c is not None
                         and (kd.null is not None or force) else c
                         for c, kd, force in zip(cards, kdvals,
                                                 key_force_null)]
            if fast and int(np.prod(eff_cards)) <= max_groups:
                num_groups = int(np.prod(eff_cards))
            else:
                fast = False
                # bound segments by the (static) padded row count: a
                # table smaller than max_groups can never overflow
                num_groups = min(max_groups, n)
            return fast, cards, eff_cards, num_groups

        @tracing.op_scope("group_index")
        def compute_pre(ctx, rt, out, valid):
            """Combined group index + overflow flag — the cacheable
            prefix of every grouped aggregate."""
            n = valid.shape[0]
            overflow = jnp.asarray(False)
            if not groups:
                return jnp.where(valid, 0, 1).astype(jnp.int32), overflow
            kdvals = [kr(rt) for kr in key_runs]
            fast, cards, eff_cards, num_groups = shape_info(ctx, kdvals, n)
            if fast:
                gidx = jnp.zeros(n, dtype=jnp.int64)
                for kd, card, ecard, ki in zip(kdvals, cards, eff_cards,
                                               key_infos):
                    if ki[0] == "vdict":
                        # group index straight from the table-global
                        # value domain: a dict-encoded plate remaps its
                        # per-batch CODES through the domain (pure code
                        # arithmetic: `remap` [B, Dp] is the table the
                        # codes decode through, the value plate is never
                        # read); anything else searchsorts its values
                        gd = jnp.asarray(ctx.aux[ki[2][1]])
                        if (kd.cplate is not None
                                and ctx.static[code_agg_si] != 0):
                            from snappydata_tpu.storage.device_decode \
                                import dict_decode, dict_decode_form

                            remap = jnp.searchsorted(
                                gd, kd.cplate.dicts).astype(jnp.int64)
                            kv = dict_decode(
                                remap, kd.cplate.codes).reshape(-1)
                            ctx.decode_note[("group_remap", ki[2][1])] = \
                                dict_decode_form(remap.shape[1])
                        else:
                            vals = _broadcast_to_mask(
                                kd.value, out.valid).reshape(-1)
                            kv = jnp.searchsorted(gd, vals) \
                                .astype(jnp.int64)
                    else:
                        kv = _broadcast_to_mask(kd.value, out.valid) \
                            .reshape(-1).astype(jnp.int64)
                    if kd.null is not None:
                        nb = _broadcast_to_mask(kd.null, out.valid) \
                            .reshape(-1)
                        kv = jnp.where(nb, card, kv)
                    gidx = gidx * ecard + kv
                # int32 group index: num_groups <= max_groups (65536)
                # always fits, and it halves the cached-gidx bytes +
                # one-hot comparison traffic
                return (jnp.where(valid, gidx, num_groups)
                        .astype(jnp.int32), overflow)
            combined = _combine_keys(
                [DVal(_broadcast_to_mask(k.value, out.valid).reshape(-1),
                      _broadcast_to_mask(k.null, out.valid).reshape(-1)
                      if k.null is not None else None,
                      k.dtype) for k in kdvals])
            return _run_head_index(combined, valid, num_groups)

        def run_pre(ctx):
            """Phase A: (valid, gidx, onehot-or-None, overflow) — the
            group-index-cache entry."""
            from snappydata_tpu.ops import reduction

            out = child(ctx)
            rt = Runtime(out.cols, ctx.params, ctx.aux_slice(builder),
                         ctx, out.valid)
            valid = out.valid.reshape(-1)
            gidx, overflow = compute_pre(ctx, rt, out, valid)
            n = valid.shape[0]
            if groups:
                kdvals = [kr(rt) for kr in key_runs]
                num_groups = shape_info(ctx, kdvals, n)[3]
            else:
                num_groups = 1
            onehot = None
            if reduction.resolve_strategy(
                    _STRATEGY_NAMES[ctx.static[strategy_si]],
                    jax.default_backend(), num_groups, n, "fsum",
                    jnp.float64) == "matmul":
                # one-hot over the REAL groups only: an invalid row's
                # one-hot row is all-zero, so it contributes nothing —
                # the overflow segment is never consumed downstream
                onehot = reduction.make_onehot(gidx, num_groups,
                                               jnp.float64)
            # a wide decimal's bound checks in the filter or the keys
            return valid, gidx, onehot, overflow | ctx.overflow

        def run_main(ctx, pre=None) -> tuple:
            from snappydata_tpu.ops import code_agg, reduction

            out = child(ctx)
            rt = Runtime(out.cols, ctx.params, ctx.aux_slice(builder),
                         ctx, out.valid)
            if pre is None:
                valid = out.valid.reshape(-1)
                gidx, overflow = compute_pre(ctx, rt, out, valid)
                onehot = None
            else:
                # phase A's cached prefix: XLA DCEs the re-emitted filter
                # predicate and key-combination math this phase skips
                valid, gidx, onehot, overflow = pre
            n = valid.shape[0]
            if groups:
                kdvals = [kr(rt) for kr in key_runs]
                fast, cards, eff_cards, num_groups = shape_info(
                    ctx, kdvals, n)
                key_vals = kdvals
            else:
                fast, cards, eff_cards, num_groups = True, [], [], 1
                key_vals: List[DVal] = []
            nseg = num_groups + 1
            backend = jax.default_backend()
            req = _STRATEGY_NAMES[ctx.static[strategy_si]]
            # the generic branch has no small dense index to offer: a
            # family that would scatter there reduces over the rows' runs
            # in group order instead (`reduction.run_reduce`), one sort
            # for every such family, and the keys are read at the runs'
            # heads
            by_runs = bool(groups) and not fast

            def strategy_of(family: str, dtype) -> str:
                s = reduction.resolve_strategy(req, backend, num_groups, n,
                                               family, dtype)
                return "runs" if by_runs and s == "scatter" else s

            fsum_strat = strategy_of("fsum", jnp.float64)
            istrat = strategy_of("isum", jnp.int64)
            if pre is None and fsum_strat == "matmul":
                onehot = reduction.make_onehot(gidx, num_groups,
                                               jnp.float64)
            # accumulated during tracing, PUBLISHED (frozen) at the end
            # of this function — a concurrent execution of the same
            # plan must never iterate a set another thread's in-flight
            # trace is still mutating
            note = {"passes": 0, "strategies": set(), "lanes": set(),
                    "rle_fallbacks": 0, "dict_space_slots": 0,
                    "scatter_slots": 0, "isum_scatter_slots": 0,
                    "limb_matmul_slots": 0, "run_reduce_slots": 0}
            tok = ctx.static[code_agg_si]
            # dictionary-space SUM counts by a one-hot product shaped
            # for the MXU: auto engages it on the accelerator only (the
            # CPU backend materialises the one-hots, which costs more
            # than the gather it saves); "on" forces it everywhere,
            # "off" kills it.  The code-domain group index and run-space
            # lanes are cheap arithmetic — only "off" disables those.
            code_agg_on = tok == 2 or (tok == 1 and backend != "cpu")

            def dict_space_takes(cpl) -> bool:
                return (cpl is not None and code_agg_on
                        and code_agg.dict_space_engages(
                            nseg, cpl.codes.shape, cpl.dicts.shape))

            def family_pass(strategy: str, nslots: int) -> None:
                """One packed family reduced `nslots` aggregate slots."""
                note["passes"] += 1
                note["strategies"].add(strategy)
                if strategy == "scatter":
                    note["scatter_slots"] += nslots
                elif strategy == "runs":
                    note["run_reduce_slots"] += nslots
            rle_ok = (tok != 0 and rle_gate_si is not None
                      and bool(ctx.static[rle_gate_si])
                      and jnp.ndim(out.valid) == 2)
            if groups and fast and any(ki[0] in ("dict", "vdict")
                                       for ki in key_infos):
                note["lanes"].add("code_domain")

            # --- slots ---
            # Evaluate slot inputs once, dedup by argument expression:
            # slots over the SAME argument (sum(x)+min(x), avg's
            # sum+count beside an explicit sum) share array OBJECTS, so
            # count_col collapses counts over one mask to one packed
            # column.
            evaluated: List[tuple] = []
            arg_vw: Dict[object, tuple] = {}
            for (kind, arg), run in zip(slots, slot_arg_runs):
                if run is None:  # count(*)
                    evaluated.append(("count", None, valid, None, False,
                                      None, None))
                    continue
                hit = arg_vw.get(arg)
                if hit is None:
                    dv = run(rt)
                    v = _broadcast_to_mask(dv.value, out.valid).reshape(-1)
                    w = valid
                    if dv.null is not None:
                        w = w & ~_broadcast_to_mask(
                            dv.null, out.valid).reshape(-1)
                    # bare stored columns are finite on excluded/padded
                    # rows (zero-initialized plates); computed
                    # expressions can be Inf/NaN exactly where the
                    # filter excluded them (sum(a/b) WHERE b <> 0), so
                    # only bare columns may skip the matmul pre-mask
                    raw = isinstance(arg, ast.Col)
                    # the plates ride along so the sum/count slot loop
                    # can aggregate in code/run space without decoding;
                    # only bare columns carry them (an expression over
                    # a plate is row-space math by definition)
                    hit = arg_vw[arg] = (v, w, dv.dtype, raw,
                                         dv.cplate if raw else None,
                                         dv.rplate if raw else None)
                evaluated.append((kind,) + hit)

            # Packed accumulator families: every remaining slot joins one
            # [N, S] matrix per family and the family reduces in ONE
            # fused dispatch (ops/reduction.py strategy table) — the old
            # path issued one masked reduction per group per slot.
            slot_arrays: List = [None] * len(slots)
            fsum_cols: List[tuple] = []     # (slot idx, f64 contrib)
            count_ws: List = []             # unique count masks
            count_of: Dict[int, int] = {}   # id(mask) -> column
            count_users: List[tuple] = []   # (slot idx, column)
            isum_cols: List[tuple] = []     # (slot idx, int64 contrib)
            minmax: Dict[tuple, list] = {}  # (kind, dtype) -> entries
            guards: List[dict] = []         # decimal int64 bound checks

            def count_col(w) -> int:
                c = count_of.get(id(w))
                if c is None:
                    c = len(count_ws)
                    count_ws.append(w)
                    count_of[id(w)] = c
                return c

            for i, (kind, v, w, sdt, raw_col, cpl,
                    rpl) in enumerate(evaluated):
                if kind == "count":
                    rm = None
                    if (rle_ok and rpl is not None and not groups
                            and w is valid):
                        rm = _rle_run_mask(out.runf, rpl)
                        if rm is None:
                            # eligible plate, filter left run space —
                            # COUNTED fallback, never silent
                            note["rle_fallbacks"] += 1
                    if rm is not None:
                        # run-space COUNT: Σ run-length over surviving
                        # runs.  batch-skip pad batches duplicate real
                        # plates with an all-False validity window, so
                        # mask whole dead batches out of the run mask.
                        live = out.valid.any(axis=1)
                        _tot, cnt = code_agg.run_space_sum_count(
                            rpl.values, rpl.ends, rm & live[:, None])
                        slot_arrays[i] = jnp.stack(
                            [cnt, jnp.zeros((), cnt.dtype)])
                        note["passes"] += 1
                        note["strategies"].add("rle_runs")
                        note["lanes"].add("rle_runs")
                    else:
                        count_users.append((i, count_col(w)))
                elif kind == "count_distinct":
                    # exact: sort (group, value-bits) pairs, count group
                    # boundaries where the value changes (sort-based
                    # distinct — no hash table needed on TPU)
                    vb = _key_bits(v)
                    gw = jnp.where(w, gidx, num_groups)
                    order = jnp.lexsort((vb, gw))
                    g_s = gw[order]
                    v_s = vb[order]
                    new = jnp.ones_like(g_s, dtype=bool)
                    new = new.at[1:].set((g_s[1:] != g_s[:-1])
                                         | (v_s[1:] != v_s[:-1]))
                    slot_arrays[i] = jax.ops.segment_sum(
                        new.astype(jnp.int64), g_s, num_segments=nseg)
                    note["passes"] += 1
                    note["scatter_slots"] += 1
                elif kind == "sum":
                    acc_dt = _acc_dtype(sdt, jnp.asarray(v).dtype)
                    # run-space SUM: Σ value·length over surviving runs
                    # — O(runs), no row-space expansion.  f64-exact
                    # accumulators only; exact int64 (decimal/integer)
                    # sums stay on the packed path.
                    rm = None
                    if (rle_ok and rpl is not None and not groups
                            and w is valid and acc_dt != jnp.int64):
                        rm = _rle_run_mask(out.runf, rpl)
                        if rm is None:
                            note["rle_fallbacks"] += 1
                    if rm is not None:
                        live = out.valid.any(axis=1)
                        total, _cnt = code_agg.run_space_sum_count(
                            rpl.values, rpl.ends, rm & live[:, None])
                        slot_arrays[i] = jnp.stack(
                            [total, jnp.zeros((), total.dtype)])
                        note["passes"] += 1
                        note["strategies"].add("rle_runs")
                        note["lanes"].add("rle_runs")
                        continue
                    # dictionary-space SUM: count codes per (group,
                    # batch, code) cell by a one-hot product, contract
                    # with the dictionary stack — the value plate is
                    # never gathered (ops/code_agg.py).  Past the
                    # lane's groups x dictionary bound the slot rides
                    # the packed families below.
                    if acc_dt != jnp.int64 and dict_space_takes(cpl):
                        slot_arrays[i] = code_agg.dict_space_sum(
                            cpl.codes, cpl.dicts, gidx, w, nseg)
                        note["passes"] += 1
                        note["strategies"].add("dict_space")
                        note["lanes"].add("dict_space")
                        note["dict_space_slots"] += 1
                        continue
                    acc = v.astype(acc_dt)
                    if acc_dt == jnp.int64:
                        if sdt is not None and sdt.name == "decimal":
                            # exact scaled-int decimal sum: a group
                            # total CAN exceed int64 — bound-check
                            # max|v| * count (scaled by the tile count
                            # so a scan_tile_bytes pass bounds the
                            # MERGED total) and reroute to the host
                            # path instead of wrapping silently.  The
                            # absmax rides the minmax family with the
                            # int64-min filler: an all-masked group has
                            # count 0, so filler * 0 never trips the
                            # bound.
                            tag = ("guard", len(guards))
                            minmax.setdefault(("max", "int64"), []) \
                                .append((tag, jnp.where(
                                    w, jnp.abs(acc),
                                    jnp.iinfo(jnp.int64).min)))
                            guards.append({"absmax": tag,
                                           "cnt": count_col(w)})
                        if istrat == "matmul" and w is valid:
                            # the limb product drops a row on the dump
                            # segment by its all-zero one-hot row, and an
                            # integer has no NaN to leak through it: no
                            # select pass, and no widened copy either
                            # (the product widens a chunk at a time)
                            isum_cols.append(
                                (i, v if jnp.issubdtype(
                                    v.dtype, jnp.signedinteger) else acc))
                        else:
                            isum_cols.append(
                                (i, jnp.where(w, acc, jnp.int64(0))))
                    elif fsum_strat == "matmul" and w is valid \
                            and raw_col:
                        # bare non-null column: an invalid row's one-hot
                        # row is all-zero and its plate value is finite,
                        # so the select pass is pure overhead
                        # (packed_sum's finite-guard still covers NaN
                        # DATA, falling back to the isolating scatter)
                        fsum_cols.append((i, acc))
                    else:
                        fsum_cols.append((i, jnp.where(w, acc, 0.0)))
                elif kind == "sumsq":
                    acc = v.astype(_acc_dtype(T.DOUBLE))
                    fsum_cols.append((i, jnp.where(w, acc * acc, 0.0)))
                elif kind in ("min", "max"):
                    fill = _extreme(v.dtype, kind == "min")
                    minmax.setdefault(
                        (kind, jnp.asarray(v).dtype.name), []).append(
                        (("slot", i), jnp.where(w, v, fill)))
                else:
                    raise CompileError(kind)

            # the gvalid count joins the count family (and dedups with
            # any count slot over the plain validity mask)
            gvalid_col = count_col(valid)

            # --- family dispatch: one fused reduction each ---
            # (name, strategy, columns, kind) a family; the families that
            # take the runs share ONE sort of the group index
            families: List[tuple] = []
            join_counts = bool(count_ws) and fsum_strat == "matmul"
            if fsum_cols or join_counts:
                cols = [c for _, c in fsum_cols]
                if join_counts:
                    # counts ride the f64 matmul pack as 0/1 columns —
                    # exact below 2**53 rows, and an invalid row's
                    # one-hot row is all-zero, so the plain-validity
                    # count is literally a ones column
                    for w in count_ws:
                        cols.append(jnp.ones(n, jnp.float64) if w is valid
                                    else jnp.where(w, 1.0, 0.0))
                families.append(("fsum", fsum_strat, cols, "sum"))
                family_pass(fsum_strat, len(fsum_cols))
            # counts follow the float family's strategy (matmul was
            # handled by joining above): on the unroll path that keeps
            # the old fast int32 masked sums.  Where that would be a
            # scatter they resolve as the exact-integer family does, and
            # under its limb product the masks ride the int64 pack's one
            # product as 0/1 columns (one read of gidx, one one-hot).
            # Over the runs the validity mask's count is its run's
            # length, and only the other masks are summed.
            limb_counts = (bool(count_ws) and not join_counts
                           and fsum_strat == "scatter"
                           and istrat == "matmul")
            if count_ws and not join_counts and not limb_counts:
                cdt = reduction.count_pack_dtype(n)
                families.append(("count", fsum_strat, [
                    w.astype(cdt) for w in count_ws
                    if not (fsum_strat == "runs" and w is valid)], "sum"))
                family_pass(fsum_strat, len(count_users))
            if isum_cols or limb_counts:
                icols = [c for _, c in isum_cols] \
                    + (count_ws if limb_counts else [])
                families.append(("isum", istrat, icols, "sum"))
                family_pass(istrat, len(isum_cols))
                if istrat == "scatter":
                    note["isum_scatter_slots"] += len(isum_cols)
                elif istrat == "matmul":
                    note["limb_matmul_slots"] += len(icols)
            for mkey, entries in minmax.items():
                mcols = [c for _, c in entries]
                mstrat = strategy_of("minmax", mcols[0].dtype)
                families.append((mkey, mstrat, mcols, mkey[0]))
                family_pass(mstrat, sum(t[0] == "slot" for t, _ in entries))
            fres: Dict[object, object] = {}
            for name, strategy, cols, kind in families:
                if strategy == "runs":
                    continue
                fres[name] = reduction.packed_sum(
                    cols, gidx, num_groups, strategy,
                    onehot=onehot if name == "fsum" else None) \
                    if kind == "sum" else reduction.packed_minmax(
                        kind, cols, gidx, num_groups, strategy)
            runs = None
            if by_runs:
                queued = [f for f in families if f[1] == "runs"]
                runs = reduction.run_reduce(
                    gidx, num_groups, [c for f in queued for c in f[2]],
                    [f[3] for f in queued for _ in f[2]])
                at = 0
                for name, _s, cols, _k in queued:
                    if cols:
                        fres[name] = jnp.stack(
                            runs.tails[at:at + len(cols)], axis=1)
                    at += len(cols)

            for pos, (i, _) in enumerate(fsum_cols):
                slot_arrays[i] = fres["fsum"][:, pos]
            if join_counts:
                count_res = jnp.round(
                    fres["fsum"][:, len(fsum_cols):]).astype(jnp.int64)
            elif limb_counts:
                count_res = fres["isum"][:, len(isum_cols):]
            elif fsum_strat == "runs":
                # the other masks' sums, in the order they were queued
                summed = iter(range(len(count_ws)))
                count_res = jnp.stack(
                    [runs.counts if w is valid
                     else fres["count"][:, next(summed)]
                     for w in count_ws], axis=1).astype(jnp.int64)
            else:
                count_res = fres["count"].astype(jnp.int64)
            for pos, (i, _) in enumerate(isum_cols):
                slot_arrays[i] = fres["isum"][:, pos]
            for i, c in count_users:
                slot_arrays[i] = count_res[:, c]
            guard_res: Dict[tuple, object] = {}
            for mkey, entries in minmax.items():
                mres = fres[mkey]
                for pos, (tag, _) in enumerate(entries):
                    if tag[0] == "slot":
                        slot_arrays[tag[1]] = mres[:, pos]
                    else:
                        guard_res[tag] = mres[:, pos]
            for g in guards:
                absmax = guard_res[g["absmax"]]
                cnt_w = count_res[:, g["cnt"]]
                tscale = jnp.asarray(ctx.aux[tile_scale_aux], jnp.float64)
                overflow = overflow | jnp.where(jnp.any(
                    absmax.astype(jnp.float64)
                    * cnt_w.astype(jnp.float64) * tscale >= 2.0 ** 62),
                    jnp.int32(DECIMAL_OVERFLOW), jnp.int32(0))

            counts = count_res[:, gvalid_col]
            if groups:
                gvalid = counts[:num_groups] > 0
            else:
                # SQL global aggregate always yields one row, even on
                # empty input (count()=0, sum()=0-as-proxy-for-null)
                gvalid = jnp.ones(1, dtype=bool)

            # --- group key values per segment (+ per-group key null masks:
            # the extra code slot / null-segregated hash group) ---
            key_arrays = []
            key_nulls: List[Optional[jnp.ndarray]] = []
            if groups:
                if fast:
                    # decode mixed-radix group index back to key codes
                    ar = jnp.arange(num_groups, dtype=jnp.int64)
                    strides = []
                    acc = 1
                    for ecard in reversed([c if c else 1 for c in eff_cards]):
                        strides.append(acc)
                        acc *= ecard
                    strides = list(reversed(strides))
                    for (card, ecard, stride, kd, ki) in zip(
                            cards, eff_cards, strides, key_vals,
                            key_infos):
                        kv = ((ar // stride) % ecard)
                        if ecard > card:  # nullable key: code==card → NULL
                            key_nulls.append(kv == card)
                            kv = jnp.minimum(kv, card - 1)
                        else:
                            key_nulls.append(None)
                        if ki[0] == "vdict":
                            # domain code → key value via the aux LUT
                            # (padded to the static card, so every code
                            # is in range)
                            gd = jnp.asarray(ctx.aux[ki[2][1]])
                            vv = jnp.take(gd, kv)
                            key_arrays.append(vv.astype(
                                kd.dtype.device_dtype()
                                if kd.dtype else vv.dtype))
                        else:
                            key_arrays.append(kv.astype(
                                kd.dtype.device_dtype()
                                if kd.dtype else jnp.int64))
                else:
                    for kd in key_vals:
                        k_arr, k_null = _run_head_key(kd, out.valid, runs)
                        key_arrays.append(k_arr)
                        key_nulls.append(k_null)
                key_arrays = [k[:num_groups] if k.shape[0] > num_groups else k
                              for k in key_arrays]

            # --- evaluate select expressions over [G] arrays ---
            post_cols: Dict[int, DVal] = {}
            for gi, karr in enumerate(key_arrays):
                post_cols[gi] = DVal(karr, key_nulls[gi],
                                     post_scope_types[gi])
            slot_cols: Dict[int, DVal] = {}
            for si, arr in enumerate(slot_arrays):
                slot_cols[len(groups) + si] = DVal(
                    arr[:num_groups], None, slot_dtypes[si])
            post_rt = Runtime({**post_cols, **slot_cols}, ctx.params,
                              ctx.aux_range(post_aux_off,
                                            len(post_builder.aux_builders)),
                              ctx)
            pairs = []
            for run, dt in zip(post_runs, out_types):
                dv = run(post_rt)
                pairs.append((dv.value, dv.null))
            notes[ctx.static] = {
                "passes": note["passes"],
                "strategies": frozenset(note["strategies"]),
                "lanes": frozenset(note["lanes"]),
                "rle_fallbacks": note["rle_fallbacks"],
                "dict_space_slots": note["dict_space_slots"],
                "scatter_slots": note["scatter_slots"],
                "isum_scatter_slots": note["isum_scatter_slots"],
                "limb_matmul_slots": note["limb_matmul_slots"],
                "run_reduce_slots": note["run_reduce_slots"],
                "decimal_exact_slots": len(exact_decimal_calls),
                # compute_pre's generic branch: the index by run heads
                "gidx_run_lane": int(bool(groups) and not fast),
                "group_slots": num_groups,
                "reduce_padded_rows": n,
                "table": base_table_ref}
            # nested data-dependent overflows (join expansion past its
            # bucket) ride the same flag: the executor reruns on host
            return gvalid, tuple(pairs), overflow | ctx.overflow

        self._agg_pre_emit = run_pre
        self._agg_main_emit = run_main

        def run_agg(ctx) -> tuple:
            return run_main(ctx, None)

        return run_agg, out_cols

    # -- helpers -----------------------------------------------------------

    def _builder_for(self, scope) -> ExprBuilder:
        col_types = {i: s.dtype for i, s in enumerate(scope)}
        nullable = {i: s.nullable for i, s in enumerate(scope)}
        dict_getters = {i: s.dict_provider for i, s in enumerate(scope)
                        if s.dict_provider is not None}
        b = ExprBuilder(col_types, nullable, dict_getters)
        b._aux_offset = len(self.aux_builders)
        # LUT aux arrays are appended to the compiler's global list as they
        # are emitted; emitted closures index builder-locally and the
        # _AuxView at run time adds _aux_offset back
        def register(builder_fn):
            self.aux_builders.append(builder_fn)
            b.aux_builders.append(builder_fn)
            return len(b.aux_builders) - 1

        b._register_aux = register
        return b

    def _derived_dict_provider(self, e: ast.Expr, scope):
        base = e
        while isinstance(base, ast.Alias):
            base = base.child
        if isinstance(base, ast.Col) and base.dtype is not None \
                and base.dtype.name == "string":
            return scope[base.index].dict_provider
        if isinstance(base, ast.Func) and base.name in STRING_VALUE_FUNCS:
            # derivable transforms (concat(s, '_x'), upper(s), ...) share
            # the base column's codes with a value-mapped dictionary
            try:
                ci, fn = self._builder_for(scope)._string_value_transform(
                    base)
            except CompileError:
                return None
            if ci is None or scope[ci].dict_provider is None:
                return None
            prov = scope[ci].dict_provider
            return lambda: np.array([fn(v) for v in prov()], dtype=object)
        return None


@dataclasses.dataclass
class _ScopeCol:
    name: str
    dtype: T.DataType
    dict_provider: Optional[Callable] = None
    nullable: bool = True


@dataclasses.dataclass(frozen=True)
class _SlotRef(ast.Expr):
    slot: int = 0
    dtype: T.DataType = None


@dataclasses.dataclass(frozen=True)
class _KeyRef(ast.Expr):
    key: int = 0
    dtype: T.DataType = None


def _slots_to_cols(e: ast.Expr, n_groups: int) -> ast.Expr:
    """Rewrite _SlotRef/_KeyRef into Col(index) for the post-agg scope."""
    if isinstance(e, _SlotRef):
        return ast.Col(f"__slot{e.slot}", None, n_groups + e.slot, e.dtype)
    if isinstance(e, _KeyRef):
        return ast.Col(f"__key{e.key}", None, e.key, e.dtype)
    return e.map_children(lambda c: _slots_to_cols(c, n_groups))


def _run_head_index(keys, valid, num_groups: int):
    """The generic group index from one sort: (gidx int32, overflow).
    A valid row's id is its key's rank among the distinct valid keys,
    the rank `searchsorted` over the sorted distinct keys gives.  The
    keys are sorted with their row numbers; a run of equal keys starts
    where a sorted key differs from the one before it, and the running
    count of run heads is the rank.  A sort on the row numbers brings
    the ids home, as the join's merge brings its probes home
    (ops/join.py `_probe_order`).  Invalid rows sort last under
    `_I64_MAX` and read `num_groups`, as does any id past the slots:
    `overflow` says there were more distinct valid keys than
    `num_groups` (silent truncation would return WRONG results, so the
    executor reruns on the exact host path)."""
    n = keys.shape[0]
    keys = jnp.where(valid, keys, _I64_MAX)
    # equal keys share a rank whatever their order: no stable sort needed
    skeys, rows = jax.lax.sort((keys, jnp.arange(n, dtype=jnp.int32)),
                               num_keys=1)
    head = jnp.concatenate([jnp.ones(skeys[:1].shape, jnp.bool_),
                            skeys[1:] != skeys[:-1]])
    rank = jnp.cumsum(head.astype(jnp.int32)) - 1
    _, gidx = jax.lax.sort((rows, jnp.minimum(rank, num_groups)),
                           num_keys=1)
    overflow = jnp.asarray(False)
    if num_groups < n:
        # the sentinel's run is the last, and a group only where a valid
        # key equals the sentinel
        distinct = (rank[-1] + 1 - (skeys[-1] == _I64_MAX)
                    + jnp.any(valid & (keys == _I64_MAX)))
        overflow = distinct > num_groups
    return jnp.where(valid, gidx, num_groups).astype(jnp.int32), overflow


@tracing.op_scope("group_keys")
def _run_head_key(kd, mask2d, runs):
    """One generic GROUP BY key's value a group, read at the head of the
    group's run (every row of a group holds the same), and its NULL flag
    where it has one.  An empty group reads some row's: its output row
    is masked by the group's count."""
    head = runs.rows[jnp.minimum(runs.bounds[:-1], runs.rows.shape[0] - 1)]
    k_arr = _broadcast_to_mask(kd.value, mask2d).reshape(-1)[head]
    k_null = None
    if kd.null is not None:
        k_null = _broadcast_to_mask(kd.null, mask2d).reshape(-1)[head]
    return k_arr, k_null


def _cards_of(key_infos, ctx):
    out = []
    for kind, si, _ in key_infos:
        if kind == "dict":
            out.append(ctx.static[si])
        elif kind == "bool":
            out.append(2)
        else:
            out.append(1)
    return out


_JOIN_NOTE_KEYS = ("join_device_joins", "join_probe_rows",
                   "join_expand_out_rows", "join_merge_probes",
                   "join_search_loops", "join_build_rows",
                   "join_row_builds", "join_multikey_joins")


class _TraceCtx:
    def __init__(self, rels, aux, params, static, decode_note,
                 decimal_wide=None):
        self.rels = rels
        self.aux = aux
        self.params = params
        self.static = static
        # site -> form of the plan's dictionary decodes (make_ctx's, and
        # the group-key remaps add theirs)
        self.decode_note = decode_note
        # trace-time side channel: nested nodes (the expanding join) OR
        # their data-dependent overflow flags here; the region root folds
        # it into the compiled output's third slot so the executor can
        # reroute to the exact host path
        self.overflow = jnp.asarray(False)
        # and what the joins were (CompiledPlan._note_slots)
        self.join_note = dict.fromkeys(_JOIN_NOTE_KEYS, 0)
        # the shapes of the decimal expressions past int64's precision
        # lowered under the guard (exprs._dec_guard)
        self.decimal_wide = set() if decimal_wide is None else decimal_wide

    def aux_slice(self, builder) -> List:
        off = getattr(builder, "_aux_offset", 0)
        # builder's auxes were appended to global list starting at off
        return _AuxView(self.aux, off)

    def aux_range(self, off, n) -> List:
        return _AuxView(self.aux, off)


class _AuxView:
    def __init__(self, aux, off):
        self._aux = aux
        self._off = off

    def __getitem__(self, i):
        return self._aux[self._off + i]


def _dict_provider(info, ci):
    f = info.schema.fields[ci]
    from snappydata_tpu.storage.table_store import RowTableData

    if isinstance(f.dtype, T.ArrayType) and f.dtype.element.name == \
            "string" and not isinstance(info.data, RowTableData):
        # ARRAY<STRING> plates carry element CODES: the provider is the
        # element dictionary (element_at decodes through it; contains
        # literals resolve to codes against it)
        from snappydata_tpu.storage.device import array_element_dictionary

        return lambda: array_element_dictionary(info.data, ci)
    if isinstance(f.dtype, T.MapType) \
            and not isinstance(info.data, RowTableData):
        from snappydata_tpu.engine.exprs import MapDicts
        from snappydata_tpu.storage.device import map_device_eligible

        if map_device_eligible(f.dtype):
            return MapDicts(
                lambda: info.data.map_key_dictionary(ci),
                (lambda: info.data.map_value_dictionary(ci))
                if f.dtype.value.name == "string" else None)
    if isinstance(f.dtype, T.StructType) \
            and not isinstance(info.data, RowTableData):
        from snappydata_tpu.engine.exprs import StructDicts
        from snappydata_tpu.storage.device import struct_device_eligible

        if struct_device_eligible(f.dtype):
            return StructDicts({
                fn: (lambda fn=fn:
                     info.data.struct_field_dictionary(ci, fn))
                for fn, ft in f.dtype.fields if ft.name == "string"})
    if f.dtype.name != "string":
        return None
    if isinstance(info.data, RowTableData):
        return lambda: info.data.string_dict(ci)
    return lambda: info.data.dictionary(ci)


def _unique_dict_or_host(provider):
    """Wrap a derived-dictionary provider: grouping relies on code↔value
    bijection, so duplicate derived values reroute to the host path."""
    def wrapped():
        d = provider()
        vals = d.tolist()
        if len(set(vals)) != len(vals):
            raise CompileError(
                "derived group dictionary is not value-unique: host path")
        return d

    return wrapped


def _padded_size(n: int) -> int:
    return 1 << max(0, (max(1, n) - 1).bit_length())


# The per-slot `_seg_reduce` (one masked reduction per group per slot)
# was replaced by the packed per-family fused reductions in
# ops/reduction.py — see Compiler._emit_aggregate's family dispatch.


def merge_tile_outs(a, b, tags):
    """Elementwise on-device merge of two raw (mask, pairs, overflow)
    partial outputs over one ALIGNED group-index space (partial-raw
    compiles force data-independent cards, so slot i of tile A and tile
    B describe the same group).  Keys are decoded from the group index —
    identical across tiles — so either side's array serves; sum slots
    add (0 identity), min/max fold through their +/-inf fillers; the
    masks and overflow flags OR."""
    pairs = []
    for (va, na), (vb, _nb), tag in zip(a[1], b[1], tags):
        if tag[0] == "key":
            pairs.append((va, na))
        elif tag[1] == "min":
            pairs.append((jnp.minimum(va, vb), None))
        elif tag[1] == "max":
            pairs.append((jnp.maximum(va, vb), None))
        else:  # sum (covers counts and sumsq)
            pairs.append((va + vb, None))
    return (a[0] | b[0], tuple(pairs), a[2] | b[2])


def _acc_dtype(dt: Optional[T.DataType], value_dtype=None):
    """Aggregate ACCUMULATOR dtype. float64 for DOUBLE/FLOAT outputs —
    on TPU the element plates stay float32 (storage and elementwise
    compute ride the fast path) but the segment reductions widen to
    f64: summing ~1e8 values of magnitude 1e4 into 1e10 group totals in
    f32 leaves ~3 trustworthy digits (round-3 verdict), while
    f32-rounded inputs accumulated in f64 keep relative error ≤1e-6.
    DECIMAL with scaled-int64 values (the exact path, any precision)
    accumulates in int64 under the sum's overflow guard — EXACT,
    matching the reference's BigDecimal contract
    (encoders/.../encoding/ColumnEncoding.scala:137-140 readDecimal)
    with native int ops instead of emulated f64; float-domain decimals
    (`decimal_exact` off) keep the f64 accumulator. XLA emulates f64
    adds on TPU; reductions are bandwidth-bound, so the extra ALU cost
    does not move the bottleneck."""
    if dt is not None and dt.name == "decimal":
        if value_dtype is not None \
                and jnp.issubdtype(value_dtype, jnp.integer):
            return jnp.int64
        return jnp.float64
    if dt is not None and dt.name in ("float", "double"):
        return jnp.float64
    return jnp.int64


def _extreme(np_dtype, positive: bool):
    """Identity filler for min/max — delegates to ops/reduction so the
    packed kernels and the executor's pack/key-decode fillers can never
    drift apart (empty-group results must stay bit-identical across
    strategies)."""
    from snappydata_tpu.ops.reduction import _extreme_of

    return _extreme_of(np_dtype, positive)


def _key_bits(v):
    """Exact int64 representation of a grouping/join key: floats BITCAST
    (a plain cast truncated 2.1 and 2.9 both to 2, collapsing float
    groups), with ±0.0 normalized so they group together.  Single
    implementation in ops/join.py — the cached build artifact and the
    bind-time expansion bound encode keys OUTSIDE the trace, and the
    domains must never drift."""
    from snappydata_tpu.ops.join import key_bits

    return key_bits(v)


def _combine_keys(dvals: List[DVal]):
    """Combine N key DVals into one int64 key. Single key: exact (NULL maps
    to a reserved sentinel — collision odds with a real value hitting that
    exact bit pattern are ~2⁻⁶⁴). Multiple: mixed via a 64-bit hash with
    the null flag folded in exactly (documented collision risk ~ n²·2⁻⁶⁴;
    exact multi-key via packing/sort lands with the generic hash table).
    NULL keys hash to their own group per SQL GROUP BY semantics.
    Delegates to ops/join.py (see _key_bits)."""
    from snappydata_tpu.ops.join import combine_key_arrays

    return combine_key_arrays([(d.value, d.null) for d in dvals])


def _broadcast_to_mask(v, mask):
    if jnp.shape(v) == jnp.shape(mask):
        return v
    return jnp.broadcast_to(v, jnp.shape(mask))


def _collect_sargs(cond: ast.Expr, rel: _RelationInput) -> None:
    """Extract `numeric_col OP literal` conjuncts for stats skipping."""
    conjuncts: List[ast.Expr] = []

    def flatten(e):
        if isinstance(e, ast.BinOp) and e.op == "and":
            flatten(e.left)
            flatten(e.right)
        else:
            conjuncts.append(e)

    flatten(cond)
    flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}
    for c in conjuncts:
        if not (isinstance(c, ast.BinOp) and c.op in flip):
            continue
        col, lit, op = None, None, c.op
        # '?' Params skip batches like tokenized literals — the getter
        # reads the bind value at execution time either way
        if isinstance(c.left, ast.Col) and isinstance(
                c.right, (ast.Lit, ast.ParamLiteral, ast.Param)):
            col, lit = c.left, c.right
        elif isinstance(c.right, ast.Col) and isinstance(
                c.left, (ast.Lit, ast.ParamLiteral, ast.Param)):
            col, lit, op = c.right, c.left, flip[c.op]
        if col is None or col.dtype is None:
            continue
        if isinstance(lit, (ast.ParamLiteral, ast.Param)):
            get = (lambda params, p=lit.pos: params[p])
        else:
            get = (lambda params, v=lit.value: v)
        if col.dtype.name == "string":
            # string equality skips via the table dictionary (an absent
            # literal matches nothing anywhere) — `?` binds included,
            # read through the same getter at execution time
            if op == "=":
                rel.str_sargs.append((col.index, get))
            continue
        if not T.is_numeric(col.dtype):
            continue
        rel.sargs.append((col.index, op, get))


def _expr_cols(e: Optional[ast.Expr]) -> set:
    if e is None:
        return set()
    return {x.index for x in ast.walk(e) if isinstance(x, ast.Col)}


def _plan_width(plan: ast.Plan) -> int:
    if isinstance(plan, ast.Relation):
        return len(plan.schema)
    if isinstance(plan, ast.SubqueryAlias):
        return _plan_width(plan.child)
    if isinstance(plan, ast.Filter):
        return _plan_width(plan.child)
    if isinstance(plan, ast.Project):
        return len(plan.exprs)
    if isinstance(plan, ast.Aggregate):
        return len(plan.agg_exprs)
    if isinstance(plan, ast.Join):
        if plan.how in ("semi", "anti"):
            return _plan_width(plan.left)
        return _plan_width(plan.left) + _plan_width(plan.right)
    if isinstance(plan, ast.WindowProject):
        return len(plan.exprs)
    raise CompileError(f"width of {type(plan).__name__}")




def _validate_array_usage(plan: ast.Plan) -> None:
    """Array-typed columns may appear on device ONLY as the first argument
    of size/element_at/array_contains (their plate layout is opaque to
    every other operator) — anything else reroutes to the host path."""
    def check_expr(e: ast.Expr, allowed: bool) -> None:
        if isinstance(e, ast.Col) \
                and isinstance(e.dtype, (T.ArrayType, T.MapType,
                                         T.StructType)) \
                and not allowed:
            raise CompileError(
                "array/map/struct column outside size/element_at/"
                "array_contains: host path")
        from snappydata_tpu.engine.exprs import ARRAY_DEVICE_FUNCS

        for i, c in enumerate(e.children()):
            ok = isinstance(e, ast.Func) and i == 0 and \
                e.name in ARRAY_DEVICE_FUNCS
            check_expr(c, ok)

    def walk(p: ast.Plan) -> None:
        if isinstance(p, ast.Filter):
            check_expr(p.condition, False)
        elif isinstance(p, (ast.Project, ast.WindowProject)):
            for e in p.exprs:
                check_expr(e, False)
        elif isinstance(p, ast.Aggregate):
            for e in list(p.group_exprs) + list(p.agg_exprs):
                check_expr(e, False)
        elif isinstance(p, ast.Join) and p.condition is not None:
            check_expr(p.condition, False)
        for k in p.children():
            walk(k)

    walk(plan)


def _collect_used(plan: ast.Plan, needed: Optional[set], out: List[set]) -> None:
    """Top-down pruning: which output ordinals of each Relation leaf (in
    DFS order) are actually consumed."""
    if isinstance(plan, ast.Relation):
        out.append(set(range(len(plan.schema))) if needed is None
                   else set(needed))
        return
    if isinstance(plan, (ast.SubqueryAlias,)):
        _collect_used(plan.child, needed, out)
        return
    if isinstance(plan, ast.Filter):
        need = set(range(_plan_width(plan.child))) if needed is None \
            else set(needed)
        need |= _expr_cols(plan.condition)
        _collect_used(plan.child, need, out)
        return
    if isinstance(plan, ast.Project):
        need = set()
        for e in plan.exprs:
            need |= _expr_cols(e)
        _collect_used(plan.child, need, out)
        return
    if isinstance(plan, ast.Aggregate):
        need = set()
        for e in plan.group_exprs:
            need |= _expr_cols(e)
        for e in plan.agg_exprs:
            need |= _expr_cols(e)
        _collect_used(plan.child, need, out)
        return
    if isinstance(plan, ast.Join):
        wl = _plan_width(plan.left)
        wr = _plan_width(plan.right)
        if needed is None:
            top = wl if plan.how in ("semi", "anti") else wl + wr
            needed = set(range(top))
        needed = set(needed) | _expr_cols(plan.condition)
        _collect_used(plan.left, {i for i in needed if i < wl}, out)
        _collect_used(plan.right, {i - wl for i in needed if i >= wl}, out)
        return
    if isinstance(plan, ast.WindowProject):
        need = set()
        for e in plan.exprs:
            need |= _expr_cols(e)  # walk() covers args/partition/order keys
        _collect_used(plan.child, need, out)
        return
    raise CompileError(f"prune: {type(plan).__name__}")


def _split_equi(cond: Optional[ast.Expr], nleft: int):
    """Split a join condition into equi pairs (left_idx, right_idx) and a
    residual expression."""
    if cond is None:
        return [], None
    conjuncts = []

    def flatten(e):
        if isinstance(e, ast.BinOp) and e.op == "and":
            flatten(e.left)
            flatten(e.right)
        else:
            conjuncts.append(e)

    flatten(cond)
    equi, rest = [], []
    for c in conjuncts:
        if isinstance(c, ast.BinOp) and c.op == "=" \
                and isinstance(c.left, ast.Col) and isinstance(c.right, ast.Col):
            li, ri = c.left.index, c.right.index
            if li < nleft <= ri:
                equi.append((li, ri))
                continue
            if ri < nleft <= li:
                equi.append((ri, li))
                continue
        rest.append(c)
    residual = None
    for c in rest:
        residual = c if residual is None else ast.BinOp("and", residual, c)
    return equi, residual


# ==========================================================================
# Executor: peel host ops, run device region, post-process
# ==========================================================================

class Executor:
    def __init__(self, catalog, props=None):
        import collections

        self.catalog = catalog
        self.props = props or config.global_properties()
        # LRU: hitting plan_cache_size evicts the COLDEST entry only
        # (plan_cache_evictions) — the old clear-the-world wipe dropped
        # every hot dashboard/prepared plan on one unlucky miss
        self._plan_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._depth = 0
        # plan caches are the first thing the resource broker evicts
        # under memory pressure (weak registration — executors die with
        # their sessions)
        from snappydata_tpu.resource import global_broker

        global_broker().register_executor(self)

    def clear_cache(self):
        from snappydata_tpu.ops.join import clear_join_caches

        self._plan_cache.clear()
        clear_gidx_cache()
        clear_join_caches()

    # -- plan-cache LRU ----------------------------------------------------
    # concurrent sessions (Flight threads, jobserver workers) share one
    # executor; individual OrderedDict ops are GIL-atomic, and the
    # move_to_end/popitem races that remain are benign (a concurrently
    # evicted key just recompiles) — guarded with try/except instead of
    # a lock on the hot path

    def _cache_get(self, key):
        hit = self._plan_cache.get(key)
        if hit is not None:
            try:
                self._plan_cache.move_to_end(key)
            except KeyError:
                pass
        return hit

    def _cache_put(self, key, value) -> None:
        from snappydata_tpu.observability.metrics import global_registry

        while len(self._plan_cache) >= self.props.plan_cache_size:
            try:
                self._plan_cache.popitem(last=False)
                global_registry().inc("plan_cache_evictions")
            except KeyError:
                break
        self._plan_cache[key] = value

    def compiled_core(self, node: ast.Plan,
                      key_str: Optional[str] = None
                      ) -> Optional[CompiledPlan]:
        """CompiledPlan for a device-region node via the plan cache, or
        None when the node has no device lowering (the caller keeps the
        host/engine path).  The serving subsystem uses this to hold the
        compiled program for a prepared handle — fused batch dispatches
        go straight to it without re-walking the plan per execute."""
        from snappydata_tpu.observability.metrics import global_registry

        key = (key_str if key_str is not None
               else _plan_key(node, self.catalog), self.catalog.generation)
        compiled = self._cache_get(key)
        if compiled is None:
            reg = global_registry()
            try:
                with reg.time("plan_compile"), tracing.span("compile"):
                    compiled = Compiler(self.catalog,
                                        self.props).compile(node)
            except CompileError:
                return None
            self._cache_put(key, compiled)
        return compiled

    def compiled_partial(self, node: ast.Plan) -> Optional[CompiledPlan]:
        """Compile an analyzed/tokenized partial-aggregate plan in
        partial-raw mode for the tiled scan's on-device merge.  Plan-
        cache aware (negative results cached too); None when the device
        region can't lower it — the caller keeps the host-merge path."""
        from snappydata_tpu.observability.metrics import global_registry

        key = ("__partial_raw__", _plan_key(node, self.catalog),
               self.catalog.generation)
        hit = self._cache_get(key)
        if hit is None:
            reg = global_registry()
            try:
                with reg.time("plan_compile"):
                    hit = Compiler(self.catalog, self.props,
                                   partial_raw=True).compile(node)
            except CompileError:
                hit = False
            self._cache_put(key, hit)
        return hit or None

    def execute(self, plan: ast.Plan, params: Tuple = (),
                plan_key: Optional[str] = None) -> Result:
        from snappydata_tpu.observability.metrics import global_registry

        # the plan cache up to `bind` (a miss's `compile` beside it)
        tracing.step("plan_lookup")
        check_current()  # cancellation point: every (sub)plan execution
        if self._depth:  # nested calls (unions, host fallback) count once
            return self._execute_with_host_ops(plan, params, plan_key)
        reg = global_registry()
        reg.inc("queries")
        self._depth += 1
        try:
            with reg.time("query"):
                result = self._execute_with_host_ops(plan, params, plan_key)
        finally:
            self._depth -= 1
        reg.inc("rows_returned", result.num_rows)
        return result

    def _execute_with_host_ops(self, plan: ast.Plan, params: Tuple,
                               plan_key: Optional[str] = None) -> Result:
        host_ops, node = peel_host_ops(plan)

        # executeTake early-stop (ref: CachedDataFrame.executeTake:766):
        # a bare LIMIT over a scan chain decodes batches incrementally and
        # stops as soon as enough rows survive — never materializing the
        # full table
        if len(host_ops) == 1 and isinstance(host_ops[0], ast.Limit):
            taken = self._try_take(node, host_ops[0].n, params)
            if taken is not None:
                return taken

        result = self._execute_core(node, params, plan_key)
        if not host_ops:
            return result
        # what is left above the device region (ORDER BY, LIMIT,
        # DISTINCT, HAVING-level filters and projections over an
        # aggregate's rows) runs here in NumPy, on the rows the region
        # returned: a span of its own, so the host's part of a statement
        # that "stayed on the device" is in its trace
        with tracing.span("host_ops", rows_in=result.num_rows,
                          ops=",".join(type(op).__name__
                                       for op in reversed(host_ops))) as sp:
            for op in reversed(host_ops):
                result = self._apply_host_op(op, result, params)
            sp.set("rows_out", result.num_rows)
        tracing.step("finish")
        return result

    # -- core -------------------------------------------------------------

    def _execute_core(self, node: ast.Plan, params: Tuple,
                      plan_key: Optional[str] = None) -> Result:
        if isinstance(node, ast.Values):
            return hosteval.eval_values(node, params)
        if isinstance(node, ast.Union):
            left = self.execute(node.left, params)
            right = self.execute(node.right, params)
            return hosteval.union(left, right)
        if isinstance(node, ast.SetOp):
            left = self.execute(node.left, params)
            right = self.execute(node.right, params)
            return hosteval.set_op(left, right, node.op)

        from snappydata_tpu.observability.metrics import global_registry

        reg = global_registry()
        fast = self._try_point_lookup(node, params)
        if fast is not None:
            return fast

        key = (plan_key if plan_key is not None
               else _plan_key(node, self.catalog), self.catalog.generation)
        compiled = self._cache_get(key)
        if compiled is None:
            reg.inc("plan_cache_misses")
            tracing.annotate("plan_cache", "miss")
            try:
                with reg.time("plan_compile"), tracing.span("compile"):
                    compiled = Compiler(self.catalog,
                                        self.props).compile(node)
            except CompileError as e:
                reg.inc("host_fallbacks")
                with tracing.span("host_fallback",
                                  reason=str(e)[:120]):
                    return self._host_fallback(node, params)
            self._cache_put(key, compiled)
        else:
            reg.inc("plan_cache_hits")
            tracing.annotate("plan_cache", "hit")
        try:
            return compiled.execute(params)
        except CompileError as e:
            reg.inc("host_fallbacks")
            with tracing.span("host_fallback", reason=str(e)[:120]):
                return self._host_fallback(node, params)

    def _try_take(self, node: ast.Plan, n: int, params: Tuple
                  ) -> Optional[Result]:
        """LIMIT-n over Project?/Filter?/Relation on a column table:
        decode one batch at a time, keep qualifying rows, stop at n."""
        from snappydata_tpu.storage.table_store import RowTableData

        proj = filt = None
        cur = node
        if isinstance(cur, ast.Project):
            proj, cur = cur, cur.child
        while isinstance(cur, ast.SubqueryAlias):
            cur = cur.child
        if isinstance(cur, ast.Filter):
            filt, cur = cur, cur.child
        while isinstance(cur, ast.SubqueryAlias):
            cur = cur.child
        if not isinstance(cur, ast.Relation) or n <= 0:
            return None
        info = self.catalog.lookup_table(cur.name)
        if info is None or isinstance(info.data, RowTableData):
            return None  # row tables answer from indexes / are small
        checked = ([e for e in proj.exprs] if proj else []) + \
            ([filt.condition] if filt else [])
        for e in checked:
            for x in ast.walk(e):
                if isinstance(x, (ast.WindowFunc, ast.ScalarSubquery,
                                  ast.InSubquery, ast.ExistsSubquery)):
                    return None
                if isinstance(x, ast.Func) and x.name in ast.AGG_FUNCS:
                    return None
        data = info.data
        from snappydata_tpu.storage import mvcc

        m = mvcc.snapshot_of(data)
        schema = info.schema
        if proj is not None:
            names = [_expr_name(e) for e in proj.exprs]
            dtypes = [expr_type(e) or T.STRING for e in proj.exprs]
        else:
            names = schema.names()
            dtypes = [f.dtype for f in schema.fields]
        out_cols: List[List[np.ndarray]] = [[] for _ in names]
        out_nulls: List[List[Optional[np.ndarray]]] = [[] for _ in names]
        have = 0
        decoded = 0

        def consume(cols, nulls, cnt) -> int:
            nonlocal have
            if cnt == 0:
                return 0
            if filt is not None:
                v, nl = hosteval.eval_expr(filt.condition, cols, nulls,
                                           params, cnt)
                keep = np.broadcast_to(v, (cnt,)).astype(bool)
                if nl is not None:
                    keep = keep & ~np.broadcast_to(nl, (cnt,))
                idx = np.flatnonzero(keep)
                if idx.size == 0:
                    return 0
                cols = [c[idx] for c in cols]
                nulls = [nm[idx] if nm is not None else None
                         for nm in nulls]
                cnt = idx.size
            take = min(cnt, n - have)
            if proj is not None:
                for j, e in enumerate(proj.exprs):
                    v, nl = hosteval.eval_expr(e, cols, nulls, params, cnt)
                    v = np.broadcast_to(v, (cnt,))
                    out_cols[j].append(v[:take])
                    out_nulls[j].append(
                        np.broadcast_to(nl, (cnt,))[:take]
                        if nl is not None else None)
            else:
                for j in range(len(names)):
                    out_cols[j].append(cols[j][:take])
                    out_nulls[j].append(nulls[j][:take]
                                        if nulls[j] is not None else None)
            have += take
            return take

        for view in m.views:
            if have >= n:
                break
            check_current()  # batch boundary = cancellation point
            decoded += 1
            live = view.live_mask()
            lazy = data._decode_all(view)
            cnt = int(live.sum())
            cols = [np.asarray(lazy[f.name])[live] for f in schema.fields]
            nulls = []
            for i in range(len(schema.fields)):
                nm = view.null_mask(i)
                nulls.append(nm[live] if nm is not None else None)
            consume(cols, nulls, cnt)
        if have < n and m.row_count:
            cols = [np.asarray(a)[:m.row_count] for a in m.row_arrays]
            nulls = [nm[:m.row_count] if nm is not None else None
                     for nm in (m.row_nulls or [None] * len(cols))]
            consume(cols, nulls, m.row_count)

        from snappydata_tpu.observability.metrics import global_registry

        reg = global_registry()
        if decoded < len(m.views):
            reg.inc("take_early_stops")
        reg.inc("take_batches_decoded", decoded)
        final_cols, final_nulls = [], []
        for j, dt in enumerate(dtypes):
            if out_cols[j]:
                vals = np.concatenate(out_cols[j])
            else:
                vals = np.empty(0, dtype=object if dt.name == "string"
                                else dt.np_dtype)
            parts = out_nulls[j]
            if any(p is not None for p in parts):
                nm = np.concatenate(
                    [p if p is not None else
                     np.zeros(len(c), dtype=bool)
                     for p, c in zip(parts, out_cols[j])])
            else:
                nm = None
            final_cols.append(vals)
            final_nulls.append(nm)
        return Result(names, final_cols, final_nulls, dtypes)

    def _try_point_lookup(self, node: ast.Plan, params: Tuple
                          ) -> Optional[Result]:
        """Point/key queries on row tables answer straight from the PK or
        a secondary index, never entering the XLA engine (ref:
        ExecutionEngineArbiter routing simple queries to the store's own
        engine, docs/architecture/cluster_architecture.md:31-33)."""
        from snappydata_tpu.storage.table_store import RowTableData

        proj = None
        n = node
        if isinstance(n, ast.Project):
            proj, n = n, n.child
        while isinstance(n, ast.SubqueryAlias):
            n = n.child
        if not isinstance(n, ast.Filter):
            return None
        inner = n.child
        while isinstance(inner, ast.SubqueryAlias):
            inner = inner.child
        if not isinstance(inner, ast.Relation):
            return None
        info = self.catalog.lookup_table(inner.name)
        if info is None or not isinstance(info.data, RowTableData):
            return None
        # all conjuncts must be col = literal
        pairs: Dict[str, object] = {}

        def flatten(e) -> bool:
            if isinstance(e, ast.BinOp) and e.op == "and":
                return flatten(e.left) and flatten(e.right)
            # prepared-statement '?' Params qualify exactly like tokenized
            # literals (found on the serving point-lookup profile: a
            # prepared `WHERE pk = ?` paid a full device scan + transfer
            # per execute instead of this O(1) index probe)
            if isinstance(e, ast.BinOp) and e.op == "=" \
                    and isinstance(e.left, ast.Col) \
                    and isinstance(e.right, (ast.Lit, ast.ParamLiteral,
                                             ast.Param)):
                v = e.right.value if isinstance(e.right, ast.Lit) \
                    else params[e.right.pos]
                name = e.left.name.lower()
                if name in pairs and pairs[name] != v:
                    return False  # contradictory k=1 AND k=2: engine path
                pairs[name] = v
                return True
            return False

        if not flatten(n.condition):
            return None
        # projection must be plain columns (or absent = all)
        if proj is not None and not all(
                isinstance(e.child if isinstance(e, ast.Alias) else e,
                           ast.Col) for e in proj.exprs):
            return None
        key_set = frozenset(pairs)
        rows: Optional[List[tuple]] = None
        if info.key_columns and key_set == frozenset(info.key_columns):
            got = info.data.get(tuple(pairs[k] for k in info.key_columns))
            rows = [got] if got is not None else []
        else:
            idx = info.data.index_for_columns(sorted(key_set))
            if idx is None:
                return None
            cols_order = info.data._indexes[idx]
            rows = info.data.index_lookup(
                idx, tuple(pairs[c] for c in cols_order))
        from snappydata_tpu.observability.metrics import global_registry

        global_registry().inc("point_lookups")
        schema = info.schema
        if proj is not None:
            sel = [(e.child if isinstance(e, ast.Alias) else e)
                   for e in proj.exprs]
            names = [_expr_name(e) for e in proj.exprs]
            idxs = [c.index for c in sel]
            dtypes = [schema.fields[i].dtype for i in idxs]
            out_rows = [tuple(r[i] for i in idxs) for r in rows]
        else:
            names = schema.names()
            dtypes = [f.dtype for f in schema.fields]
            out_rows = rows
        cols = []
        nulls = []
        for j, dt in enumerate(dtypes):
            vals = [r[j] for r in out_rows]
            nmask = np.array([v is None for v in vals]) if vals else None
            if dt.name == "string":
                cols.append(np.array(vals, dtype=object))
            else:
                cols.append(np.array([0 if v is None else v for v in vals],
                                     dtype=dt.np_dtype))
            nulls.append(nmask if nmask is not None and nmask.any()
                         else None)
        return Result(names, cols, nulls, dtypes)

    def _host_fallback(self, node: ast.Plan, params: Tuple) -> Result:
        """CodegenSparkFallback analogue (core/.../execution/
        CodegenSparkFallback.scala:33): when device lowering can't handle a
        construct, evaluate on host via numpy."""
        self._warn_large_host_fallback(node)
        if isinstance(node, ast.WindowProject):
            return hosteval.eval_window(node, params, self)
        return hosteval.eval_plan(node, params, self)

    def _warn_large_host_fallback(self, node: ast.Plan) -> None:
        """Host-path perf cliffs must not be SILENT (round-1 weak finding):
        when a fallback touches a big table, say so once per plan shape so
        operators can see why a query takes minutes."""
        threshold = int(self.props.get("host_fallback_warn_rows",
                                       1_000_000) or 0)
        if threshold <= 0:
            return
        # dedup BEFORE the O(rows) count — the count itself must not tax
        # every execution of the already-slow path it warns about
        key = _plan_key(node, self.catalog)
        seen = getattr(self, "_fallback_warned", None)
        if seen is None:
            seen = self._fallback_warned = set()
        if key in seen:
            return
        total = 0

        def rec(p):
            nonlocal total
            if isinstance(p, ast.Relation):
                info = self.catalog.lookup_table(p.name)
                if info is not None:
                    try:
                        total += _row_count_of(info)
                    except Exception:
                        pass
            for k in p.children():
                rec(k)

        rec(node)
        if total < threshold:
            return
        seen.add(key)
        import sys

        print(f"warning: query over ~{total:,} rows is running on the "
              f"HOST path (single-threaded) — a construct in it has no "
              f"device lowering yet; see the host_fallbacks metric",
              file=sys.stderr)

    # -- host post-ops ----------------------------------------------------

    def _apply_host_op(self, op, result: Result, params) -> Result:
        if isinstance(op, ast.Limit):
            return hosteval.limit(result, op.n)
        if isinstance(op, ast.Distinct):
            return hosteval.distinct(result)
        if isinstance(op, ast.Sort):
            return hosteval.sort(result, op.orders, params)
        if isinstance(op, ast.Filter):
            return hosteval.filter_result(result, op.condition, params)
        if isinstance(op, ast.Project):
            return hosteval.project_result(result, op.exprs, params)
        raise CompileError(f"unknown host op {type(op).__name__}")


def peel_host_ops(plan: ast.Plan) -> Tuple[List, ast.Plan]:
    """Split a plan into (host_ops outermost-first, device-region core).
    Shared by the executor's dispatch and the serving subsystem's
    prepared handles — both must agree on what the core node is, or a
    caller-supplied plan key would label the wrong node."""
    host_ops: List = []
    node = plan
    while True:
        if isinstance(node, (ast.Sort, ast.Limit, ast.Distinct)):
            host_ops.append(node)
            node = node.children()[0]
            continue
        if isinstance(node, ast.Filter) and _is_result_level(node.child):
            host_ops.append(node)
            node = node.child
            continue
        if isinstance(node, ast.Project) and _is_result_level(node.child):
            host_ops.append(node)
            node = node.child
            continue
        break
    return host_ops, node


def _is_result_level(child: ast.Plan) -> bool:
    """True when `child` produces a (small) materialized result whose
    parent ops should run on host: anything above an Aggregate."""
    if isinstance(child, (ast.Aggregate, ast.WindowProject)):
        return True
    if isinstance(child, (ast.Sort, ast.Limit, ast.Distinct)):
        return True
    if isinstance(child, (ast.Filter, ast.Project, ast.SubqueryAlias)):
        return _is_result_level(child.children()[0])
    return False


def _plan_key(plan: ast.Plan, catalog) -> str:
    """Structural cache key: the tokenized plan repr is stable because
    literals are ParamLiteral positions, not values.  The repr walk is
    O(plan) per call — hot callers (the serving subsystem's prepared
    executes) compute it once and pass it back in; `plan_key_builds`
    counts the walks so a per-execute regression is CI-guardable."""
    from snappydata_tpu.observability.metrics import global_registry

    global_registry().inc("plan_key_builds")
    return repr(plan)

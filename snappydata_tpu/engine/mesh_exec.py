"""Mesh-sharded execution of compile-once partial-aggregate programs.

The read path's mesh dimension (ROADMAP item 1; PAPER.md L0's
partitioned regions + bucket placement): with a `jax.sharding` mesh
active, a tilable aggregate shape — single-relation scans (Q1/Q6) and
probe-leftmost join trees (Q3C) — runs its PARTIAL program (the PR 4
decomposition the tiled scan already compiles once) per-shard under
`shard_map`: every device scans only its batch slice of the
still-ENCODED plates, computes the group index in the shared [G] space
(dictionary codes are table-global, so per-shard gidx needs no
coordination), reduces its per-family [G] partials locally, and the
partials merge IN-TRACE with `psum`/`pmin`/`pmax` over the mesh axis —
the reference's partial aggregation + CollectAggregateExec merge
(SnappyStrategies.scala:347) expressed as collectives.

Joins pick a distribution strategy per bind, counted like the join
engine's fallback reasons:

* **broadcast-build** — the build side's plates + sorted artifact are
  replicated to every device (one explicit placement, cached per bind
  identity) and the probe stays batch-sharded: each shard probes the
  full build locally (ref: replicated-table HashJoinExec build
  broadcast, joins/HashJoinExec.scala:63).
* **shuffle-on-key** — both sides are exchanged BUCKET-WISE on the join
  key: the encoded int64 key domain (shared by both sides — string
  codes translate first) hashes through parallel/hashing's murmur3 into
  `num_devices` buckets, and each side's rows re-lay out so device d
  holds exactly bucket d of both sides.  Matching keys are then
  collocated, the per-shard trace sorts its LOCAL build slice in-trace
  (the `shuf_si` static specialization in _emit_join), and no probe or
  build row crosses a device during execution.  The exchange itself is
  one bucketed gather dispatched with sharded output — and it is
  CACHED per (bind identity, mesh, params), so repeated executions of
  an unchanged table re-exchange nothing.

Everything this lane cannot express falls back to plain GSPMD jit over
the sharded bind (still distributed, still value-correct), counted
`mesh_fallback_<reason>`.
"""

from __future__ import annotations

import collections
import contextvars
import functools
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from snappydata_tpu.observability import tracing
from snappydata_tpu.utils import locks

from jax import shard_map
from jax.sharding import PartitionSpec as P

# -- shuffle specialization flag ------------------------------------------
# Read by _emit_join's shuffle static provider and _aux_artifact during a
# bind this module drives; a contextvar so concurrent sessions on other
# threads bind unaffected.

_shuffle_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "mesh_shuffle", default=False)
# set to the mesh size while THIS module drives a bind (both
# strategies): _emit_join's mode_provider divides the join-expansion
# bucket by it — each shard expands only its probe slice, so expansion
# memory/work shrinks with the mesh instead of replicating the global
# output axis on every device
_bind_devices: contextvars.ContextVar = contextvars.ContextVar(
    "mesh_bind_devices", default=0)

_cache_lock = locks.named_lock("engine.mesh_exec")


def shuffle_active() -> bool:
    return bool(_shuffle_ctx.get())


def bind_devices() -> int:
    """Mesh size of the bind in flight on this thread (0 = not a mesh
    lane bind)."""
    return int(_bind_devices.get())


def _reg():
    from snappydata_tpu.observability.metrics import global_registry

    return global_registry()


# -- strategy selection ----------------------------------------------------

def choose_join_strategy(compiled, build_bytes: int,
                         probe_data) -> Tuple[str, Optional[str]]:
    """('broadcast'|'shuffle', decline_reason_or_None).

    The decline reason says why AUTO (or a forced 'shuffle') could not
    shuffle and fell back to broadcast — counted
    mesh_join_shuffle_fallback_<reason> by the caller, mirroring the
    join engine's itemized host-fallback reasons."""
    from snappydata_tpu import config

    props = config.global_properties()
    knob = str(props.get("mesh_join_strategy", "auto") or "auto").lower()
    if not compiled.join_meta:
        return "broadcast", None
    if knob == "broadcast":
        return "broadcast", None
    reason = _shuffle_ineligible(compiled, probe_data)
    if knob == "shuffle":
        return ("broadcast", reason) if reason else ("shuffle", None)
    # auto: broadcast small builds (replication is one placement and the
    # probe-side trace keeps the cached-artifact fast path); shuffle
    # once the replicated build would dominate per-device HBM
    limit = int(props.get("mesh_broadcast_build_bytes", 64 << 20) or 0)
    if limit and build_bytes > limit:
        return ("broadcast", reason) if reason else ("shuffle", None)
    return "broadcast", None


def _shuffle_ineligible(compiled, probe_data) -> Optional[str]:
    if len(compiled.join_meta) != 1:
        return "multi_join"
    meta = compiled.join_meta[0]
    if not meta["artifact_mode"] or meta["shuf_si"] is None:
        return "derived_build"
    if meta["probe_rel"] is None or meta["probe_ords"] is None:
        return "derived_probe"
    if meta["probe_rel"].info.data is not probe_data:
        return "probe_mismatch"
    if meta["how"] not in ("inner", "left", "semi", "anti"):
        return "outer_extension"
    return None


# -- bind-side helpers -----------------------------------------------------

def _array_layout(compiled) -> List[Tuple[object, int, int]]:
    """[(relation, first_index, valid_index)] into the flat `arrays`
    list a _bind returns — the one layout contract this module and
    make_ctx both derive from compiled.relations."""
    out = []
    pos = 0
    for r in compiled.relations:
        out.append((r, pos, pos + len(r.used)))
        pos += len(r.used) + 1
    return out


def _encoded_keys(meta, side: str, arrays, layout) -> Tuple:
    """(flat int64 encoded keys ON DEVICE, flat valid) for one join
    side of the CURRENT bind — the exact key domain the trace compares
    in (string codes translated to the build's code space, f64 pairs
    cast), so host-side bucket placement and in-trace matching agree
    bit-for-bit."""
    from snappydata_tpu.ops import join as _dj

    rel = meta["probe_rel"] if side == "probe" else meta["build_rel"]
    ords = meta["probe_ords"] if side == "probe" else meta["build_ords"]
    entry = next(e for e in layout if e[0] is rel)
    _r, base, vpos = entry
    pairs = []
    anynull = None
    for pi, (ci, spec) in enumerate(zip(ords, meta["enc_spec"])):
        apos = base + rel.used.index(ci)
        v, nl = arrays[apos]
        if isinstance(v, tuple):
            raise _Ineligible("complex_plate")
        v = v.reshape(-1)
        nl = nl.reshape(-1) if nl is not None else None
        if side == "probe":
            getter = meta["trans_getters"].get(pi)
            if getter is not None:
                trans = jnp.asarray(getter())
                v = trans[jnp.clip(v, 0, trans.shape[0] - 1)]
        if spec == "f64":
            v = v.astype(jnp.float64)
        pairs.append((v, nl))
        if nl is not None:
            anynull = nl if anynull is None else (anynull | nl)
    valid_flat = arrays[vpos].reshape(-1)
    if side == "probe":
        keys = _dj.encode_probe_keys(pairs, anynull)
    else:
        keys = _dj.encode_build_keys(pairs, valid_flat, anynull)
    return keys, valid_flat


class _Ineligible(Exception):
    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


def _bucket_layout(keys: np.ndarray, valid: np.ndarray, cap: int,
                   nd: int, old_batches: int):
    """Bucket-wise exchange plan for one side: rows hash into `nd`
    buckets over the encoded key domain (Spark-compatible murmur3 —
    parallel/hashing), bucket d's rows pack into device d's batch
    slice.  Returns (perm [B_new*cap] source flat indices, live mask,
    B_new, moved_rows)."""
    from snappydata_tpu.parallel.hashing import bucket_of_np
    from snappydata_tpu.parallel.mesh import _ladder

    live_idx = np.flatnonzero(valid)
    buckets = bucket_of_np(keys[live_idx].astype(np.int64), nd)
    per_dev = [live_idx[buckets == d] for d in range(nd)]
    max_rows = max((len(p) for p in per_dev), default=0)
    s_batches = _ladder(max(1, -(-max_rows // cap)))
    b_new = nd * s_batches
    perm = np.zeros(b_new * cap, dtype=np.int64)
    live = np.zeros(b_new * cap, dtype=bool)
    moved = 0
    for d, rows in enumerate(per_dev):
        base = d * s_batches * cap
        perm[base:base + len(rows)] = rows
        live[base:base + len(rows)] = True
        # a row "moves" when its source device block differs from its
        # bucket's owner — the exchange-bytes evidence
        src_dev = (rows // cap) * nd // max(1, old_batches)
        moved += int(np.count_nonzero(src_dev != d))
    return perm, live, b_new, moved


def _exchange_relation(arrays, layout, rel, perm, live, b_new, cap, ctx):
    """Re-lay one relation's bound arrays bucket-wise: a single gather
    per leaf dispatched with SHARDED output (device d receives exactly
    its bucket's rows — the all-to-all, done by XLA's resharding of the
    gather result).  Returns ({array_index: new_leaf}, exchanged_bytes)."""
    entry = next(e for e in layout if e[0] is rel)
    _r, base, vpos = entry
    perm_d = jnp.asarray(perm)
    live_d = jnp.asarray(live.reshape(b_new, cap))

    def shard2d(x):
        return jax.device_put(x, ctx.sharding_for(x))

    gather = jax.jit(
        tracing.name_jit_target(
            lambda flat: flat.reshape(-1)[perm_d].reshape(b_new, cap),
            "snappy_mesh_exchange"),
        out_shardings=ctx.batch_sharding)
    replaced: Dict[int, object] = {}
    nbytes = 0
    for i in range(base, vpos):
        v, nl = arrays[i]
        if isinstance(v, tuple):
            raise _Ineligible("complex_plate")
        v2 = gather(v)
        nl2 = gather(nl) if nl is not None else None
        nbytes += int(v2.nbytes) + (int(nl2.nbytes) if nl2 is not None
                                    else 0)
        replaced[i] = (v2, nl2)
    valid2 = gather(arrays[vpos]) & shard2d(live_d)
    nbytes += int(valid2.nbytes)
    replaced[vpos] = valid2
    return replaced, nbytes


def _replicate_relation(arrays, layout, rel, ctx):
    """Explicitly place one build relation's bound arrays REPLICATED
    (the broadcast): one device_put per leaf, so repeated executions
    pay no per-dispatch all-gather.  Returns ({index: leaf}, bytes)."""
    entry = next(e for e in layout if e[0] is rel)
    _r, base, vpos = entry

    def rep(x):
        return jax.device_put(x, ctx.replicated)

    replaced: Dict[int, object] = {}
    nbytes = 0
    for i in range(base, vpos + 1):
        a = arrays[i]
        if i == vpos:
            replaced[i] = rep(a)
            nbytes += int(a.nbytes)
            continue
        v, nl = a
        if isinstance(v, tuple):
            parts = tuple(rep(x) for x in v)
            v2 = type(v)(*parts) if hasattr(v, "_fields") else parts
            nbytes += sum(int(x.nbytes) for x in v)
        else:
            v2 = rep(v)
            nbytes += int(v.nbytes)
        nl2 = rep(nl) if nl is not None else None
        nbytes += int(nl.nbytes) if nl is not None else 0
        replaced[i] = (v2, nl2)
    return replaced, nbytes


# -- the lane --------------------------------------------------------------

def run_partial(compiled, params: Tuple, probe_data, ctx,
                build_bytes: int = 0):
    """Bind + shard_map-execute a partial-raw compiled plan over the
    active mesh; returns HOST outs (mask, pairs, overflow) ready for
    compiled._assemble, or None when this lane must decline (caller
    falls back to GSPMD, counted by reason there)."""
    from snappydata_tpu.engine.exprs import CompileError

    reg = _reg()
    strategy, decline = ("scan", None) if not compiled.join_meta else \
        choose_join_strategy(compiled, build_bytes, probe_data)
    if decline:
        reg.inc("mesh_join_shuffle_fallback_" + decline)

    def _bind_with(strat):
        tok = _shuffle_ctx.set(strat == "shuffle")
        tok_d = _bind_devices.set(ctx.num_devices)
        try:
            return compiled._bind(params)
        finally:
            _shuffle_ctx.reset(tok)
            _bind_devices.reset(tok_d)

    tables, arrays, aux, static, pvals = _bind_with(strategy)
    layout = _array_layout(compiled)
    sharded_rels = {id(e[0]) for e in layout
                    if e[0].info.data is probe_data}
    if strategy == "shuffle":
        try:
            meta = compiled.join_meta[0]
            arrays, _xbytes = _apply_shuffle(
                compiled, meta, arrays, layout, tables, static, params,
                ctx, reg)
            sharded_rels.add(id(meta["build_rel"]))
            reg.inc("mesh_join_shuffle")
        except _Ineligible as e:
            # an exchange-time ineligibility (e.g. a complex plate on a
            # join side) DECLINES TO BROADCAST like the plan-time checks
            # — it must not abandon the shard_map lane entirely.  The
            # bind re-runs with the shuffle specialization off (the
            # shuf_si static and artifact aux differ).
            reg.inc("mesh_join_shuffle_fallback_" + e.reason)
            strategy = "broadcast"
            tables, arrays, aux, static, pvals = _bind_with(strategy)
            layout = _array_layout(compiled)
            sharded_rels = {id(e[0]) for e in layout
                            if e[0].info.data is probe_data}
    if strategy == "broadcast":
        arrays = _apply_broadcast(
            compiled, arrays, layout, sharded_rels, tables, static,
            params, ctx, reg)
        reg.inc("mesh_join_broadcast")

    tags = compiled.tile_merge["tags"]
    # keyed on the DEVICE TUPLE, not the context token: two contexts
    # over the same devices lower identically, and a shard_map jit is
    # expensive enough that rotating it per context would make every
    # fresh MeshContext recompile the world
    key = (static, tuple(ctx.mesh.devices.ravel().tolist()), strategy)
    fn = compiled._jitted_mesh.get(key)
    first = fn is None
    if first:
        fn = _build_mesh_fn(compiled, static, tags, ctx, layout,
                            sharded_rels, arrays, aux, pvals)
        compiled._jitted_mesh[key] = fn
    n_merges = sum(1 for t in tags if t[0] != "key")
    from snappydata_tpu.parallel.mesh import dispatch_lock
    from snappydata_tpu.reliability import failpoints as rfail

    # mesh_dispatch entry seam — before the leaf lock (fenced region
    # must acquire nothing), so an injected raise fails the statement
    # before any collective rendezvous starts
    rfail.hit("mesh.dispatch")
    with tracing.span("jit_compile" if first else "device_execute",
                      phase="mesh", devices=ctx.num_devices,
                      xla_compiles=0), \
            dispatch_lock:
        outs = compiled._noted_call(
            static, "mesh", fn, (tuple(arrays), tuple(aux), pvals))
        # locklint: blocking-under-lock the dispatch lock exists exactly
        # to fence concurrent device collectives (see parallel/mesh.py);
        # it is a leaf — nothing is acquired under it
        jax.block_until_ready(outs)
    reg.inc("mesh_shard_execs")
    reg.inc("mesh_psum_merges", n_merges)
    note = compiled.agg_notes.get(static) if compiled.agg_notes else None
    if note is not None:
        reg.inc("agg_reduce_passes", note["passes"])
        for s in note["strategies"]:
            reg.inc("agg_strategy_" + s)
    host = jax.device_get(outs)
    if bool(np.asarray(host[2])):
        raise CompileError(
            "mesh partial overflow (group cardinality or join expansion "
            "past its bound): host path")
    return host, tables


def _build_mesh_fn(compiled, static, tags, ctx, layout, sharded_rels,
                   arrays, aux, pvals):
    """jit(shard_map(traced + collective merges)) for one (static,
    mesh, strategy) specialization.  in_specs: probe-side (and
    shuffled-build) relation leaves split on the batch axis, everything
    else replicated; out_specs replicated — after the psum/pmin/pmax
    tree every shard holds the full merged partials."""

    def leaf_spec(leaf, shard: bool):
        if leaf is None:
            return None
        return P("data", *([None] * (np.ndim(leaf) - 1))) if shard \
            else P()

    arr_specs: List = []
    for r, base, vpos in layout:
        shard = id(r) in sharded_rels
        for i in range(base, vpos):
            v, nl = arrays[i]
            if isinstance(v, tuple):
                parts = tuple(leaf_spec(x, shard) for x in v)
                vs = type(v)(*parts) if hasattr(v, "_fields") else parts
            else:
                vs = leaf_spec(v, shard)
            arr_specs.append((vs, leaf_spec(nl, shard)))
        arr_specs.append(leaf_spec(arrays[vpos], shard))

    def merged_fn(arrays_l, aux_l, pvals_l):
        mask, pairs, overflow = compiled.traced(
            static, arrays_l, aux_l, pvals_l)
        out_pairs = []
        for (va, na), tag in zip(pairs, tags):
            if tag[0] == "key":
                # key columns decode from the shared [G] index space —
                # identical on every shard, no merge needed
                out_pairs.append((va, na))
            elif tag[1] == "min":
                out_pairs.append((jax.lax.pmin(va, "data"), None))
            elif tag[1] == "max":
                out_pairs.append((jax.lax.pmax(va, "data"), None))
            else:  # sum family (covers counts and sumsq)
                out_pairs.append((jax.lax.psum(va, "data"), None))
        mask = jax.lax.psum(mask.astype(jnp.int32), "data") > 0
        overflow = jax.lax.psum(overflow.astype(jnp.int32), "data") > 0
        return mask, tuple(out_pairs), overflow

    aux_specs = jax.tree.map(lambda _: P(), tuple(aux))
    p_specs = jax.tree.map(lambda _: P(), tuple(pvals))
    tracing.name_jit_target(merged_fn, f"snappy_{compiled.kind}_mesh")
    return jax.jit(shard_map(
        merged_fn, mesh=ctx.mesh,
        in_specs=(tuple(arr_specs), aux_specs, p_specs),
        out_specs=P()))


# -- shuffle/broadcast bind caches ----------------------------------------
# Keyed on (mesh token, static, bind identity, params): an unchanged
# table version re-uses the exchanged layout; a mutation rotates the
# bind identity (the per-version `valid` array) and the entry ages out.

# per-plan layout caches register in a WeakKeyDictionary so the byte
# gauge WALKS live entries instead of keeping a counter ledger — a
# counter drifted on concurrent same-key recomputes and leaked forever
# when plan-cache eviction dropped a CompiledPlan (review finding)
_LAYOUT_CACHES = weakref.WeakKeyDictionary()


def _layout_cache(compiled) -> "collections.OrderedDict":
    with _cache_lock:
        cache = _LAYOUT_CACHES.get(compiled)
        if cache is None:
            cache = collections.OrderedDict()
            _LAYOUT_CACHES[compiled] = cache
    return cache


def mesh_layout_cache_nbytes() -> int:
    with _cache_lock:
        return sum(entry[1] for cache in _LAYOUT_CACHES.values()
                   for entry in cache.values())


def trim_layout_caches(target_bytes: int) -> int:
    """Tier ladder's HBM rung for the exchange caches: drop
    least-recently-used exchanged layouts until the total fits
    `target_bytes`.  Returns bytes freed; dropped layouts rebuild from
    the next bind (one re-exchange), exactly like an evicted plate."""
    freed = 0
    with _cache_lock:
        total = sum(entry[1] for cache in _LAYOUT_CACHES.values()
                    for entry in cache.values())
        for cache in list(_LAYOUT_CACHES.values()):
            while cache and total > max(0, int(target_bytes)):
                _k, entry = cache.popitem(last=False)
                total -= entry[1]
                freed += entry[1]
            if total <= max(0, int(target_bytes)):
                break
    return freed


def _cache_key(tables, static, params, ctx, kind: str):
    try:
        hash(params)
    except TypeError:
        return None
    return (kind, ctx.token, static,
            tuple(id(dt.valid) for dt in tables), params)


def _cache_get_put(compiled, key, tables, compute):
    import weakref

    from snappydata_tpu import config

    if key is None:
        value, nbytes = compute()
        return value, nbytes, False
    cache = _layout_cache(compiled)
    with _cache_lock:
        hit = cache.get(key)
        # the key carries id(valid) per bound table — verify the weakrefs
        # still point at those exact arrays (ids get reused after GC; a
        # stale hit would serve another version's exchanged layout)
        if hit is not None and all(
                r() is dt.valid for r, dt in zip(hit[2], tables)):
            cache.move_to_end(key)
            return hit[0], hit[1], True
    value, nbytes = compute()
    cap = int(config.global_properties().get(
        "mesh_shuffle_cache_entries", 4) or 0)
    refs = tuple(weakref.ref(dt.valid) for dt in tables)
    with _cache_lock:
        cache[key] = (value, nbytes, refs)
        while cap and len(cache) > cap:
            cache.popitem(last=False)
    return value, nbytes, False


def _apply_shuffle(compiled, meta, arrays, layout, tables, static,
                   params, ctx, reg):
    """Bucketed exchange of BOTH join sides (cached per bind identity);
    returns (new arrays list, exchanged bytes)."""
    key = _cache_key(tables, static, params, ctx, "shuf")

    def compute():
        # the exchange runs MULTI-DEVICE programs end to end — the key
        # encodes/device_gets read sharded arrays eagerly and the
        # bucketed gathers dispatch with sharded out_shardings — so the
        # whole computation holds the collective-rendezvous fence like
        # every other sharded dispatch (review finding: a concurrent
        # sharded query could interleave participants and deadlock)
        from snappydata_tpu.parallel.mesh import dispatch_lock

        with dispatch_lock:
            # locklint: blocking-under-lock the dispatch lock exists
            # exactly to fence device collectives; it is a leaf
            cap = int(jnp.shape(arrays[layout[0][2]])[1])
            replaced: Dict[int, object] = {}
            nbytes = 0
            moved_rows = 0
            for side, rel in (("probe", meta["probe_rel"]),
                              ("build", meta["build_rel"])):
                keys_d, valid_d = _encoded_keys(meta, side, arrays,
                                                layout)
                # locklint: blocking-under-lock the dispatch fence must
                # cover the eager sharded reads — that IS its purpose
                keys = np.asarray(jax.device_get(keys_d))
                # locklint: blocking-under-lock same fence invariant
                valid = np.asarray(jax.device_get(valid_d))
                old_b = valid.size // cap
                perm, live, b_new, moved = _bucket_layout(
                    keys, valid, cap, ctx.num_devices, old_b)
                rep, nb = _exchange_relation(
                    arrays, layout, rel, perm, live, b_new, cap, ctx)
                # locklint: blocking-under-lock the exchange completes
                # INSIDE the fence (leaf lock; nothing acquired under it)
                jax.block_until_ready(list(rep.values()))
                replaced.update(rep)
                nbytes += nb
                moved_rows += moved
        reg.inc("mesh_exchange_bytes", nbytes)
        reg.inc("mesh_exchange_rows", moved_rows)
        return replaced, nbytes

    replaced, _nb, hit = _cache_get_put(compiled, key, tables, compute)
    if hit:
        reg.inc("mesh_exchange_cache_hits")
    out = list(arrays)
    for i, v in replaced.items():
        out[i] = v
    return out, _nb


def _apply_broadcast(compiled, arrays, layout, sharded_rels, tables,
                     static, params, ctx, reg):
    """Replicate every non-probe relation's bound arrays (cached per
    bind identity); returns the new arrays list."""
    build_rels = [e[0] for e in layout if id(e[0]) not in sharded_rels]
    if not build_rels:
        return arrays
    key = _cache_key(tables, static, params, ctx, "bcast")

    def compute():
        replaced: Dict[int, object] = {}
        nbytes = 0
        for rel in build_rels:
            rep, nb = _replicate_relation(arrays, layout, rel, ctx)
            replaced.update(rep)
            nbytes += nb
        # broadcast volume stays under its OWN metric — the
        # mesh_exchange_* family is the shuffle exchange's evidence
        # (review finding: a pure-broadcast workload read as shuffling)
        reg.inc("mesh_broadcast_bytes", nbytes * ctx.num_devices)
        return replaced, nbytes

    replaced, _nb, hit = _cache_get_put(compiled, key, tables, compute)
    if hit:
        reg.inc("mesh_broadcast_cache_hits")
    out = list(arrays)
    for i, v in replaced.items():
        out[i] = v
    return out

"""Device join primitives: key encoding, cached build artifacts,
one-to-many expansion.

The device join is sort + rank (ref: HashJoinExec keeping
replicated/collocated joins shuffle-free, PAPER.md): build keys sort
once, every probe row finds its match RANGE in the sorted build — by one
sort-merge of build and probe keys or by searchsorted loops, whichever
`probe_lowering` picks at trace time from the backend and the two
shapes.  This module holds the pieces the executor's join emitter
composes:

- **Key encoding** (`key_bits` / `combine_key_arrays` /
  `encode_build_keys`): the single int64 key domain both sides compare
  in.  It lives HERE (the executor delegates) because the cached build
  artifact and the bind-time expansion bound encode keys OUTSIDE the
  trace — one implementation or the domains drift and joins silently
  mismatch.

- **Build artifact cache** (`build_artifact`): sorted keys + argsort
  order + joint-key uniqueness per (bind identity, key ordinals/encode
  signature), LRU byte-capped by `join_build_cache_bytes` and ledgered
  by the resource broker — repeated dashboard joins skip the
  per-execution argsort (`join_build_sorts` stays O(1) per build-side
  version).  Bind identity is the DeviceTable's `valid` array, exactly
  like the group-index cache: mutations rotate the device cache to new
  arrays, which invalidates entries with no version plumbing.

- **Expansion bound** (`probe_expand_bound`): bind-time upper bound on
  the expanded output size — per-probe match-range widths summed over
  the UNFILTERED probe leaf (query filters only shrink validity, so the
  bound is sound) — memoized on the artifact per probe bind identity.

- **The probe** (`probe_lowering`, `sorted_rank`, `merge_unique`,
  `match_ranges*`, `nth_match*`): the bounds of each probe key's run in
  the sorted build and, for a unique build, the one matching row with
  its pass bit straight out of the merge.

- **One-to-many expansion** (`expand`): prefix-summed match counts map
  a static `{2^k, 1.5*2^k}`-bucketed output axis back to (probe row,
  k-th passing build row) pairs with two searchsorteds — static-shaped
  and branch-free, which is what the TPU wants.

- **String-key translation** (`translate_codes`): left dictionary codes
  mapped into the right table's code space via one vectorized
  np.searchsorted over the sorted right dictionary (the old per-element
  Python dict loop was O(dict) host work per bind), cached per
  (left-dict version, right-dict version) — dictionaries are
  append-only, so their LENGTH is the version token.
"""

from __future__ import annotations

import threading
from snappydata_tpu.utils import locks
import weakref
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from snappydata_tpu import config
from snappydata_tpu.observability import tracing
from snappydata_tpu.parallel import mesh
# the expanded-output axis reuses the batch axis' two-shapes-per-octave
# bucketing ({2^k, 1.5*2^k}) — one policy, so a waste-bound tweak there
# reaches the join expansion too
from snappydata_tpu.storage.device import batch_bucket as expand_bucket

I64_MAX = np.iinfo(np.int64).max
# Build-side NULL keys and dead/padded rows collapse into this sentinel
# (sorts to the end, excluded from uniqueness); probe-side NULL keys get
# a DISTINCT sentinel so they can never match it.  A real key hitting
# either exact bit pattern is the documented ~2^-63 collision caveat.
BUILD_NULL_SENTINEL = I64_MAX
PROBE_NULL_SENTINEL = I64_MAX - 7


# --- key encoding ---------------------------------------------------------

def key_bits(v):
    """Exact int64 representation of a join/grouping key: floats BITCAST
    (a plain cast truncated 2.1 and 2.9 both to 2), with +/-0.0
    normalized so they compare equal."""
    arr = jnp.asarray(v)
    if jnp.issubdtype(arr.dtype, jnp.floating):
        arr = jnp.where(arr == 0, jnp.zeros((), dtype=arr.dtype), arr)
        if arr.dtype == jnp.float64:
            return jax.lax.bitcast_convert_type(arr, jnp.int64)
        return jax.lax.bitcast_convert_type(
            arr.astype(jnp.float32), jnp.int32).astype(jnp.int64)
    return arr.astype(jnp.int64)


def combine_key_arrays(pairs: List[Tuple[object, Optional[object]]]):
    """Combine N (value, null-or-None) key columns into one int64 key.
    Single key: exact (NULL maps to a reserved sentinel).  Multiple:
    64-bit hash with the null flag folded in exactly (collision risk
    ~ n^2 * 2^-64, same contract as the aggregate's combined key).  The
    caller overrides any-null rows with the side's sentinel afterwards,
    so the single/multi null encodings never need to agree."""
    if len(pairs) == 1:
        v, nl = pairs[0]
        bits = key_bits(v)
        if nl is not None:
            bits = jnp.where(nl, I64_MAX - 1, bits)
        return bits
    acc = jnp.zeros(jnp.shape(pairs[0][0]), dtype=jnp.uint64)
    for v, nl in pairs:
        k = key_bits(v).astype(jnp.uint64)
        k = (k ^ (k >> 30)) * jnp.uint64(0xbf58476d1ce4e5b9)
        k = (k ^ (k >> 27)) * jnp.uint64(0x94d049bb133111eb)
        k = k ^ (k >> 31)
        acc = acc * jnp.uint64(0x100000001b3) + k
        if nl is not None:
            acc = acc * jnp.uint64(2) + nl.astype(jnp.uint64)
    return acc.astype(jnp.int64)


def encode_probe_keys(pairs, null_flat):
    """Flat probe keys with NULLs sentineled (NULL keys never match —
    SQL semantics).  Structurally-invalid probe rows keep their raw key;
    the caller masks their match COUNTS instead."""
    keys = combine_key_arrays(pairs).reshape(-1)
    if null_flat is not None:
        keys = jnp.where(null_flat, jnp.int64(PROBE_NULL_SENTINEL), keys)
    return keys


def encode_build_keys(pairs, valid_flat, null_flat):
    """Flat build keys with NULL keys AND dead/padded rows collapsed
    into the build sentinel (sorts to the end, matches nothing)."""
    keys = combine_key_arrays(pairs).reshape(-1)
    keep = valid_flat if null_flat is None else (valid_flat & ~null_flat)
    return jnp.where(keep, keys, jnp.int64(BUILD_NULL_SENTINEL))


# --- build artifact cache -------------------------------------------------

_CACHE_LOCK = locks.named_lock("join.build_cache")
_BUILD_CACHE: dict = {}      # (id(ident), token) -> entry
_BUILD_BYTES = [0]
_tick = [0]


def _next_tick() -> int:
    _tick[0] += 1
    return _tick[0]


def join_build_cache_nbytes() -> int:
    """Bytes of device arrays pinned by the build-artifact cache — the
    resource broker folds this into its unified device ledger."""
    return int(_BUILD_BYTES[0])


def clear_join_caches() -> None:
    with _CACHE_LOCK:
        _BUILD_CACHE.clear()
        _BUILD_BYTES[0] = 0
        _TRANS_CACHE.clear()


def _count_on_bind(key: str) -> None:
    """One more build sorted, or served from the cache, on the `bind`
    span that asked (CompiledPlan._bind sets both to 0 on every traced
    bind, so a reader finds a number, 0 included)."""
    sp = tracing.current_span()
    if sp is not None and sp.name == "bind":
        sp.add(key, 1)


def build_artifact(ident, token, compute: Callable[[], object]) -> dict:
    """Sorted-build artifact for one (bind identity, key signature).

    `ident` is the build DeviceTable's `valid` array — reused across
    binds while the snapshot is current, rotated by mutations (and by
    window/mesh changes), so it invalidates entries without explicit
    versions.  `compute()` returns the flat sentineled build keys; runs
    only on a miss.  Returns {"skeys", "packed", "unique", "nbytes"}."""
    from snappydata_tpu.observability.metrics import global_registry

    reg = global_registry()
    budget = int(config.global_properties().join_build_cache_bytes or 0)
    key = (id(ident), token)
    with _CACHE_LOCK:
        e = _BUILD_CACHE.get(key)
        if e is not None:
            if e["ident"]() is ident:
                e["tick"] = _next_tick()
                reg.inc("join_build_cache_hits")
                _count_on_bind("join_builds_cached")
                return e
            # id() reuse after GC: the weakref proves staleness
            _BUILD_BYTES[0] -= _BUILD_CACHE.pop(key)["nbytes"]
    reg.inc("join_build_cache_misses")
    _count_on_bind("join_builds_sorted")
    # the whole eager build — key materialization, argsort, dup probe,
    # pack — lowers to multi-device programs under a mesh (sharded
    # inputs) and fences like any other dispatch; the cache stores and
    # counter increments stay OUTSIDE (dispatch_lock is a leaf).  It is
    # a span of its own under the bind that missed: a sort of the whole
    # build side, paid once a build-side version
    with tracing.span("join_build") as sp, mesh.eager_fence():
        bkeys = compute()
        order = jnp.argsort(bkeys).astype(jnp.int64)
        skeys = bkeys[order]
        if skeys.shape[0] > 1:
            dup = jnp.any((skeys[1:] == skeys[:-1])
                          & (skeys[:-1] != jnp.int64(BUILD_NULL_SENTINEL)))
            unique = not bool(jax.device_get(dup))
        else:
            unique = True
        # `packed` [2, F] stacks (skeys, order) so the executor ships the
        # artifact through ONE aux input slot; `skeys` is kept separate
        # for the bind-time expansion bound's searchsorted
        packed = jnp.stack([skeys, order])
        nbytes = int(skeys.nbytes) * 3
        sp.set("build_rows", int(skeys.shape[0]))
        sp.set("unique", unique)
        sp.set("nbytes", nbytes)
    reg.inc("join_build_sorts")
    entry = {"skeys": skeys, "packed": packed,
             "unique": unique,
             "nbytes": nbytes,
             "ident": weakref.ref(ident), "tick": _next_tick(),
             "bounds": {}}
    if budget <= 0 or entry["nbytes"] > budget:
        return entry  # uncached: every bind of this shape re-sorts
    with _CACHE_LOCK:
        # purge entries whose bind identity was collected (table mutated
        # or dropped — the old device arrays are gone)
        for k in [k for k, e2 in _BUILD_CACHE.items()
                  if e2["ident"]() is None]:
            _BUILD_BYTES[0] -= _BUILD_CACHE.pop(k)["nbytes"]
        while _BUILD_CACHE and _BUILD_BYTES[0] + entry["nbytes"] > budget:
            victim = min(_BUILD_CACHE, key=lambda k: _BUILD_CACHE[k]["tick"])
            _BUILD_BYTES[0] -= _BUILD_CACHE.pop(victim)["nbytes"]
        old = _BUILD_CACHE.pop(key, None)
        if old is not None:  # concurrent miss on one key: replace once
            _BUILD_BYTES[0] -= old["nbytes"]
        _BUILD_CACHE[key] = entry
        _BUILD_BYTES[0] += entry["nbytes"]
    return entry


def probe_expand_bound(artifact: dict, probe_ident, probe_token,
                       null_extend: bool,
                       compute_pkeys: Callable[[], tuple]) -> int:
    """Upper bound on the expanded output rows for (probe bind, build
    artifact): per-probe match-range widths over the UNFILTERED probe
    leaf summed, plus one slot per probe row when the join NULL-extends
    unmatched probe rows (left/full).  Query filters only shrink the
    in-trace validity, so the bound is sound.  Memoized ON the artifact
    entry keyed by (probe bind identity, `probe_token`) — the token
    carries the probe KEY ordinals, so two queries probing the same
    snapshot on different columns never share a bound; a probe mutation
    rotates the identity, an artifact invalidation drops the memo."""
    key = (id(probe_ident), probe_token, bool(null_extend))
    with _CACHE_LOCK:
        hit = artifact["bounds"].get(key)
        if hit is not None and hit[0]() is probe_ident:
            return hit[1]
    # eager searchsorteds over (possibly sharded) probe keys: fenced
    # like a dispatch; the memo store stays outside (leaf discipline)
    with mesh.eager_fence():
        pkeys, valid_flat = compute_pkeys()
        skeys = artifact["skeys"]
        lo = jnp.searchsorted(skeys, pkeys, side="left")
        hi = jnp.searchsorted(skeys, pkeys, side="right")
        counts = jnp.where(valid_flat, (hi - lo).astype(jnp.int64), 0)
        total = counts.sum()
        if null_extend:
            total = total + valid_flat.sum().astype(jnp.int64)
        bound = int(jax.device_get(total))
    with _CACHE_LOCK:
        if len(artifact["bounds"]) > 64:
            artifact["bounds"].clear()
        artifact["bounds"][key] = (weakref.ref(probe_ident), bound)
    return bound


def probe_expand_bound_per_shard(artifact: dict, probe_ident,
                                 probe_token, null_extend: bool,
                                 compute_pkeys: Callable[[], tuple],
                                 num_shards: int,
                                 batch_shape: tuple) -> int:
    """PER-SHARD upper bound on expanded output rows for a mesh bind
    whose probe shards on the batch axis: the sum of the ceil(B/D)
    LARGEST per-batch expansion bounds.  Sound under ANY assignment of
    at most that many batches to a shard — which covers both the plain
    contiguous split and whatever subset a bind-time batch skip gathers
    onto each device.  Sizing each shard's output axis to this instead
    of the GLOBAL bound is what makes join expansion memory/work shrink
    with the mesh.  Memoized like probe_expand_bound."""
    key = (id(probe_ident), probe_token, bool(null_extend),
           "shard", int(num_shards))
    with _CACHE_LOCK:
        hit = artifact["bounds"].get(key)
        if hit is not None and hit[0]() is probe_ident:
            return hit[1]
    with mesh.eager_fence():
        pkeys, valid_flat = compute_pkeys()
        skeys = artifact["skeys"]
        lo = jnp.searchsorted(skeys, pkeys, side="left")
        hi = jnp.searchsorted(skeys, pkeys, side="right")
        counts = jnp.where(valid_flat, (hi - lo).astype(jnp.int64), 0)
        if null_extend:
            counts = counts + valid_flat.astype(jnp.int64)
        per_batch = counts.reshape(batch_shape).sum(axis=1)
        k = max(1, -(-int(batch_shape[0]) // int(num_shards)))
        top = jax.lax.top_k(per_batch, min(k, int(batch_shape[0])))[0]
        bound = int(jax.device_get(top.sum()))
    with _CACHE_LOCK:
        if len(artifact["bounds"]) > 64:
            artifact["bounds"].clear()
        artifact["bounds"][key] = (weakref.ref(probe_ident), bound)
    return bound


# --- in-trace probe -------------------------------------------------------
# How a probe key finds its build rows is one of two lowerings, chosen at
# trace time by `probe_lowering` from the backend and the two shapes:
#   loop  — `jnp.searchsorted`, a `while` of ceil(log2 B) dependent
#           gathers a probe row.  Right where a gather is cheap (the CPU
#           backend) or the probe is far smaller than its build.
#   merge — sort the B build and P probe keys together once (build before
#           probe on an equal key), read every answer off prefix scans of
#           the merged order, and bring it home with a second sort on the
#           carried index.  No gather: on the TPU both of Q3's probes at
#           SF 1 take 57 ms each this way and 5,969 and 3,517 ms as
#           loops (PERF.md section 6, PR 28).
PROBE_LOOP = "loop"
PROBE_MERGE = "merge"
# The merge moves every element of the padded merged list at 6.7 ns (two
# sorts and two scans: 57 ms at 8,388,608); a search gathers one element
# a probe key a step at 14.9 ns (an int64 is two words), and a probe is
# at least two searches.  Equal cost where the elements gathered by ONE
# search are 6.7 / (2 * 14.9) of those sorted; measured on the chip from
# P/B 1/64 (merge 40 ms, loops 45) to 64 (40 ms against 2,359), PERF.md
# section 6, PR 28.
MERGE_MIN_GATHERED_PER_SORTED = 0.225
# the merged order's tags and positions are int32 (twice a position fits)
_MERGE_MAX_ELEMENTS = 2 ** 30 - 1


def probe_lowering(backend: str, n_probe: int, n_build: int) -> str:
    """`merge` or `loop` for one probe of `n_probe` keys against a sorted
    build of `n_build`: what the code can observe, never a knob.  The
    CPU backend gathers cheaply and sorts dearly (Q3's two loops 0.93 s,
    the merge 7.8 s there): always the loop."""
    if backend != "tpu":
        return PROBE_LOOP
    gathered = n_probe * max(1, int(n_build).bit_length())
    sorted_ = expand_bucket(n_probe + n_build)
    if sorted_ <= _MERGE_MAX_ELEMENTS \
            and gathered >= MERGE_MIN_GATHERED_PER_SORTED * sorted_:
        return PROBE_MERGE
    return PROBE_LOOP


def _merged_order(skeys, qkeys, bval):
    """Sort build and probe keys as one list, build before probe on an
    equal key (the sort is stable and the build comes first).  Each
    element carries one int32 tag that says where it came from: a build
    element `bval` + 1 (`bval` >= 0), probe i the value i - P (< 0), so
    a sort on the tag brings the probes home in order.  The list is
    padded to the batch axis' bucket with keys that sort last and a tag
    of 0, so that two joins over one probe, and a table that grows
    inside its bucket, share one compiled sort.  Returns (tags,
    is_build, run_start) in merged order; `run_start` marks the first
    element of each run of equal keys."""
    nb, nq = skeys.shape[0], qkeys.shape[0]
    size = expand_bucket(nb + nq)
    if size > _MERGE_MAX_ELEMENTS:    # probe_lowering never sends one
        raise ValueError(f"merge probe of {nq} keys against {nb}: "
                         "past the int32 index domain")
    pad = size - nb - nq
    keys, tags = jax.lax.sort(
        (jnp.concatenate([skeys, qkeys,
                          jnp.full((pad,), I64_MAX, jnp.int64)]),
         jnp.concatenate([bval + 1,
                          jnp.arange(-nq, 0, dtype=jnp.int32),
                          jnp.zeros((pad,), jnp.int32)])),
        num_keys=1, is_stable=True)
    run_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), keys[1:] != keys[:-1]])
    return tags, tags > 0, run_start


def _probe_order(tags, n_probe: int, *vals):
    """Merged-order values of the probe elements, back in probe order."""
    out = jax.lax.sort((tags,) + vals, num_keys=1)
    return [v[:n_probe] for v in out[1:]]


def sorted_rank(skeys, qkeys, lowering: str):
    """(lo, hi) of `jnp.searchsorted(skeys, qkeys)`, side `left` and
    `right`, int64 in `qkeys`' shape."""
    if lowering == PROBE_LOOP:
        return (jnp.searchsorted(skeys, qkeys, side="left")
                .astype(jnp.int64),
                jnp.searchsorted(skeys, qkeys, side="right")
                .astype(jnp.int64))
    flat = qkeys.reshape(-1)
    tags, is_build, run_start = _merged_order(
        skeys, flat, jnp.zeros(skeys.shape, jnp.int32))
    # build elements at or before a probe are those with key <= its key;
    # the count before its run of equal keys, those with key < its key
    hi = jnp.cumsum(is_build.astype(jnp.int32))
    lo = jax.lax.cummax(jnp.where(run_start, hi - is_build, 0))
    lo, hi = _probe_order(tags, flat.shape[0], lo, hi)
    return (lo.astype(jnp.int64).reshape(qkeys.shape),
            hi.astype(jnp.int64).reshape(qkeys.shape))


@tracing.op_scope("join_probe")
def merge_unique(skeys, order, pass_sorted, pkeys):
    """One merge for a probe against a UNIQUE build: (found, bpos) per
    probe key, `found` where a build row has the key and passes the
    build's filter (`pass_sorted` in sorted order; None: no filter),
    `bpos` its flat row (0 where not found).  The build's one element
    with a key, if any, heads the key's run in the merged order.  Two
    prefix scans hand it down the run: build elements carry the step
    from the row before them in sorted order, so a running sum reads the
    row of the last build element seen, and a running max of the run
    heads says whether that element heads the probe's own run."""
    row = order.astype(jnp.int32) + 1       # 0: the build's filter drops it
    if pass_sorted is not None:
        row = jnp.where(pass_sorted, row, 0)
    step = row - jnp.concatenate([jnp.zeros((1,), jnp.int32), row[:-1]])
    nb = skeys.shape[0]
    flat = pkeys.reshape(-1)
    # a step lies in [-nb, nb]: shifted, it is a tag of a build element
    tags, is_build, run_start = _merged_order(skeys, flat, step + nb)
    last_row = jnp.cumsum(jnp.where(is_build, tags - (nb + 1), 0))
    pos = jnp.arange(tags.shape[0], dtype=jnp.int32)
    head = jax.lax.cummax(jnp.where(run_start, 2 * pos + is_build, 0))
    hit = jnp.where(head % 2 == 1, last_row, 0) - 1
    (hit,) = _probe_order(tags, flat.shape[0], hit)
    hit = hit.reshape(pkeys.shape)
    return hit >= 0, jnp.maximum(hit, 0).astype(jnp.int64)


# Two range flavors:
#   dense      — the build has NO in-trace filter.  Dead/padded and
#                NULL-key rows are already key-sentineled by the artifact
#                encode and sort to the END, so every row inside a real
#                key's [lo, hi) run is live: counts come straight from
#                the two bounds and the k-th match is order[lo + k].
#   pass-aware — a WHERE applies to the build side in-trace.  A prefix
#                sum over the sorted pass mask counts the PASSING rows of
#                each range, and the k-th passing row is located with one
#                more rank into that prefix sum.

@tracing.op_scope("join_probe")
def match_ranges_dense(skeys, pkeys, lowering: str):
    """(counts, lo) per probe key against an unfiltered sorted build;
    `lo` is in the sorted POSITION domain (k-th match at order[lo+k])."""
    lo, hi = sorted_rank(skeys, pkeys, lowering)
    return hi - lo, lo


@tracing.op_scope("join_probe")
def match_ranges(skeys, order, pass_flat, pkeys, lowering: str):
    """Pass-aware flavor: returns (counts, base, cum) where `counts[p]`
    is the number of PASSING build rows whose key equals `pkeys[p]`,
    `base[p]` the count of passing rows strictly before the range, and
    `cum` the inclusive prefix-sum of the sorted pass mask (the index
    `nth_match` uses to locate the k-th passing row)."""
    pass_sorted = pass_flat[order]
    cum = jnp.cumsum(pass_sorted.astype(jnp.int64))
    lo, hi = sorted_rank(skeys, pkeys, lowering)
    zero = jnp.zeros((), dtype=jnp.int64)
    base = jnp.where(lo > 0, cum[jnp.maximum(lo - 1, 0)], zero)
    top = jnp.where(hi > 0, cum[jnp.maximum(hi - 1, 0)], zero)
    return top - base, base, cum


@tracing.op_scope("join_gather")
def nth_match(base, rank, cum, order, lowering: str):
    """Flat build position of the (rank+1)-th PASSING row of a match
    range (garbage when the range has fewer passing rows — callers mask
    with their `matched` flag)."""
    maxc = jnp.maximum(cum[-1], 1)
    target = jnp.clip(base + rank + 1, 1, maxc)
    if lowering == PROBE_LOOP:
        pos = jnp.searchsorted(cum, target, side="left")
    else:
        pos, _ = sorted_rank(cum, target, lowering)
    return order[jnp.clip(pos, 0, cum.shape[0] - 1)]


@tracing.op_scope("join_gather")
def nth_match_dense(base, rank, order):
    """Dense flavor: the k-th match of a range starting at sorted
    position `base` is simply order[base + k]."""
    return order[jnp.clip(base + rank, 0, order.shape[0] - 1)]


@tracing.op_scope("join_expand")
def expand(counts, counts_eff, bucket: int):
    """Static-shape one-to-many expansion bookkeeping.

    `counts_eff` is counts with the NULL-extension floor already applied
    (left/full: max(counts, 1) on valid probe rows; invalid rows 0).
    Returns (probe_of, rank, matched, slot_valid, total_real) — all
    [bucket] except the scalar total; `matched` false on a slot means
    its probe row NULL-extends (no passing build row).  The caller maps
    (probe_of, rank) to a build position with nth_match[_dense]."""
    cumc = jnp.cumsum(counts_eff)
    total = cumc[-1]
    out_idx = jnp.arange(bucket, dtype=jnp.int64)
    probe_of = jnp.searchsorted(cumc, out_idx, side="right")
    probe_of = jnp.clip(probe_of, 0, counts_eff.shape[0] - 1)
    start = cumc[probe_of] - counts_eff[probe_of]
    rank = out_idx - start
    slot_valid = out_idx < total
    matched = slot_valid & (rank < counts[probe_of])
    return probe_of, rank, matched, slot_valid, total


# --- string-key translation LUT -------------------------------------------

_TRANS_CACHE: dict = {}   # cache_key -> (owner weakrefs, trans array)


def translate_codes(ld: np.ndarray, rd: np.ndarray,
                    cache_key=None, owners=None) -> np.ndarray:
    """Left-dictionary codes -> right-table code space (-1 = no such
    value, which equals no real code), padded to a pow2 size so the LUT
    aux shape is stable as dictionaries grow within an octave.

    Vectorized: one np.searchsorted over the sorted right dictionary
    instead of the old per-element Python dict loop.  `cache_key` (when
    the caller can prove both dictionaries are base-table dictionaries)
    keys a process-wide memo; append-only dictionaries make their length
    the version, so the key embeds both lengths.  `owners` are the two
    owning table-data objects — weakref-validated so an id() reused by a
    recreated table can never serve a stale LUT."""
    from snappydata_tpu.observability.metrics import global_registry

    key = None
    if cache_key is not None and owners is not None:
        key = cache_key + (len(ld), len(rd))
        with _CACHE_LOCK:
            hit = _TRANS_CACHE.get(key)
            if hit is not None:
                refs, trans = hit
                if all(r() is o for r, o in zip(refs, owners)):
                    global_registry().inc("join_trans_cache_hits")
                    return trans
                _TRANS_CACHE.pop(key, None)
    n = len(ld)
    if n == 0 or len(rd) == 0:
        trans = np.full(n, -1, dtype=np.int32)
    else:
        lvals = np.asarray([v if v is not None else "" for v in ld.tolist()],
                           dtype=np.str_)
        rvals = np.asarray([v if v is not None else "" for v in rd.tolist()],
                           dtype=np.str_)
        rorder = np.argsort(rvals, kind="stable")
        rs = rvals[rorder]
        pos = np.searchsorted(rs, lvals)
        posc = np.minimum(pos, len(rs) - 1)
        trans = np.where(rs[posc] == lvals, rorder[posc], -1) \
            .astype(np.int32)
    size = max(1, 1 << (max(1, n) - 1).bit_length())
    if size > n:
        trans = np.concatenate(
            [trans, np.full(size - n, -1, dtype=np.int32)])
    if key is not None:
        with _CACHE_LOCK:
            if len(_TRANS_CACHE) > 512:
                _TRANS_CACHE.clear()
            _TRANS_CACHE[key] = (tuple(weakref.ref(o) for o in owners),
                                 trans)
    return trans

"""End-to-end request tracing: one trace id per request, span trees
across client → locator failover/hedge → server → engine.

Reference: the SnappyData SQL UI stitches per-operator SQLMetrics into
one plan view per query (SnappySQLListener + CachedDataFrame's
`withNewExecutionId`), and its cluster dashboard joins client-visible
latency to server-side execution through the statement id.  Here the
same join key is an explicit **trace id**, minted at whichever front
door a request enters (REST ``POST /sql``, Flight/FlightSQL tickets,
``SnappyClient``, ``DistributedSession``, a plain
``SnappySession.sql``) and propagated exactly the way the PR 8 deadline
rides: a contextvar locally, a ``trace_id`` request-body/ticket field
across the wire.  A server receiving a traced request opens its OWN
trace under the SAME id, so the per-process trace rings are joinable —
one distributed query shows up as a lead trace (with per-member fan-out
leg spans) plus one server trace per member, all carrying one id.

Span tree invariants:

- ``request_scope`` mints at most one trace per logical request — an
  ambient trace absorbs nested scopes (tile partials, matview-sync
  scratch queries, the serving path re-entering session.sql), so the
  whole request is ONE tree.
- ``span(name)`` is ~free when no trace is active (one contextvar read,
  no allocation) — the tracing-disabled overhead guard in bench.py
  leans on this.
- Spans cap their direct children (`_MAX_CHILDREN`) so a 10k-tile scan
  can't balloon a trace; truncation is visible
  (``children_truncated`` on the parent).
- Worker threads do not inherit contextvars: a thread acting for a
  traced request re-enters with ``attach(trace, span)`` (the hedged
  replica-read workers in cluster/distributed.py do).
- A span is a timeline entry: ``start_ms`` is its start as an offset
  from its trace's root (``perf_counter``), parentage is the tree.
- While a profiler session is active (``jax.profiler.start_trace``, or
  a capture through the profiler server) every span of an active trace
  is ALSO open in the profiler's host trace as ``snappy:<name>``
  (``jax.profiler.TraceAnnotation``; the root carries the trace id and
  ``kind``), on the thread that opened it — so the program's spans sit
  on the device trace's clock by construction, no clock conversion.
  With no session the bridge costs one ``is_enabled()`` read per span.
- XLA compiles are counted where they happen: one process-wide
  ``jax.monitoring`` listener adds ``xla_compiles`` / ``xla_compile_ms``
  / ``retraces`` to whichever span is current (and to the registry),
  whatever that span is called.
- A statement's host time is named end to end: where one step of it
  starts in one function and ends in another (``admit``,
  ``plan_lookup``, ``finish``: `HOST_SPANS`), ``step(name)`` opens a
  child that lasts until its parent's next child starts or the parent
  closes, so the root's children tile it.
- Python's collector is timed where it pauses the program: one
  process-wide ``gc.callbacks`` hook adds ``gc_ms`` (and ``gc_full`` for
  the oldest generation) to whichever span is current, and to two
  module totals the registry reads (``gc_pause_ms``, ``gc_collections``).

Completed traces land in a bounded in-process ring
(``trace_ring_entries``) served by ``GET /status/api/v1/traces``; any
trace slower than ``slow_query_ms`` is ALSO kept in a separate
slow-query ring so one burst of fast queries can't wash an outlier out
of the evidence.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import threading
from snappydata_tpu.utils import locks
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

from snappydata_tpu import config

# the profiler's host-trace prefix of a span's interval
PROFILE_PREFIX = "snappy:"
# is a profiler session recording host events right now?  One atomic
# read in the profiler's own runtime (~25 ns): the whole cost of the
# bridge on a traced span while nobody profiles
_profiling = TraceAnnotation.is_enabled

# children per span beyond which further same-level spans collapse into
# a truncation counter (a per-tile bind span tree must stay bounded)
_MAX_CHILDREN = 256

# trace ids: one random process prefix + a counter — uuid4 per trace
# costs ~4µs of urandom on every short serving request, and ids only
# need to be unique across the processes sharing a monitoring surface
_ID_PREFIX = uuid.uuid4().hex[:8]
_ID_COUNTER = itertools.count(1)


class Span:
    """One timed phase. `attrs` carries the phase's evidence (batch
    counts, cache verdicts, member addresses); children nest."""

    __slots__ = ("name", "attrs", "children", "_t0", "duration_s",
                 "_step")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        self.name = name
        self.attrs: dict = attrs or {}
        self.children: List["Span"] = []
        self._t0 = time.perf_counter()
        self.duration_s: Optional[float] = None
        # the child `step` opened and no later child has ended yet, with
        # its profiler annotation
        self._step = None

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def add(self, key: str, value) -> None:
        self.attrs[key] = self.attrs.get(key, 0) + value

    def end_step(self) -> None:
        """Close the step this span has open, if any."""
        st, self._step = self._step, None
        if st is not None:
            st[0].close()
            if st[1] is not None:
                st[1].__exit__(None, None, None)

    def close(self) -> None:
        if self.duration_s is None:
            if self._step is not None:
                self.end_step()
            self.duration_s = time.perf_counter() - self._t0

    def to_dict(self, t_root: Optional[float] = None) -> dict:
        """`start_ms` is this span's start as an offset from its
        trace's root (`t_root`; a span serialized alone is its own
        root)."""
        if t_root is None:
            t_root = self._t0
        out = {"name": self.name,
               "start_ms": round((self._t0 - t_root) * 1e3, 4),
               "ms": round((self.duration_s or 0.0) * 1e3, 4)}
        if self.attrs:
            # defensive copy: a straggling worker (a losing hedge leg)
            # may still be inserting attrs while the ring serializes —
            # retry the copy through the resize, degrade rather than
            # let a RuntimeError escape into the REST handler
            for _ in range(4):
                try:
                    out["attrs"] = dict(self.attrs)
                    break
                except RuntimeError:
                    continue
            else:
                out["attrs"] = {"attrs_unstable": True}
        if self.children:
            out["children"] = [c.to_dict(t_root)
                               for c in list(self.children)]
        return out

    def self_seconds(self) -> float:
        """Duration less what the children cover: the union of their
        intervals clipped to this span's, so parallel children (hedge
        legs, member fan-out) are not taken out twice."""
        end = self._t0 + (self.duration_s or 0.0)
        covered, at = 0.0, self._t0
        for c in sorted((c for c in list(self.children)
                         if c.duration_s is not None),
                        key=lambda c: c._t0):
            lo = max(c._t0, at)
            hi = min(c._t0 + c.duration_s, end)
            if hi > lo:
                covered += hi - lo
                at = hi
        return max(0.0, (self.duration_s or 0.0) - covered)


class Trace:
    """One request's span tree plus its identity (trace id, sql, user,
    kind, origin). `kind` names the front door that minted it —
    session | client | lead | server | rest | job | explain."""

    __slots__ = ("trace_id", "sql", "user", "kind", "origin", "ts",
                 "root", "status", "error", "duration_s")

    def __init__(self, sql: str, user: str, kind: str,
                 trace_id: Optional[str] = None,
                 origin: Optional[str] = None):
        self.trace_id = trace_id or \
            f"{_ID_PREFIX}{next(_ID_COUNTER):08x}"
        # truncate at construction: the ring retains up to
        # trace_ring_entries+SLOW_ENTRIES traces, and a bulk INSERT's
        # multi-MB literal list must not pin memory until eviction
        # (summaries cap at 200 chars anyway; 2000 keeps detail useful)
        self.sql = sql if len(sql) <= 2000 else sql[:2000] + "…"
        self.user = user
        self.kind = kind
        self.origin = origin
        self.ts = time.time()
        # 0 where no collection ran, so a reader reads 0 and not None
        self.root = Span("request", {"gc_ms": 0})
        self.status = "ok"
        self.error: Optional[str] = None
        self.duration_s: Optional[float] = None

    def finish(self) -> None:
        self.root.close()
        self.duration_s = self.root.duration_s

    def span_count(self) -> int:
        n = 0
        stack = [self.root]
        while stack:
            sp = stack.pop()
            n += 1
            stack.extend(sp.children)
        return n

    def phase_seconds(self) -> Dict[str, float]:
        """SELF seconds per span NAME across the whole tree (a span's
        duration less what its children cover) — the per-phase
        breakdown EXPLAIN ANALYZE and the ring's `phases_ms` report, so
        the phases sum to the part of the request that sits under some
        span and a nested span is not counted in its parent again.
        Spans still open (crashed mid-phase) are skipped."""
        out: Dict[str, float] = {}
        stack = list(self.root.children)
        while stack:
            sp = stack.pop()
            if sp.duration_s is not None:
                out[sp.name] = out.get(sp.name, 0.0) + sp.self_seconds()
            stack.extend(sp.children)
        return out

    def summary(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "kind": self.kind,
            "origin": self.origin,
            "sql": (self.sql or "")[:200],
            "user": self.user,
            "ts": self.ts,
            "ms": round((self.duration_s or 0.0) * 1e3, 3),
            "status": self.status,
            "error": self.error,
            "spans": self.span_count() - 1,
        }

    def to_dict(self) -> dict:
        out = self.summary()
        out["root"] = self.root.to_dict()
        out["phases_ms"] = {k: round(v * 1e3, 4)
                            for k, v in sorted(self.phase_seconds().items())}
        return out


# -----------------------------------------------------------------------
# ambient trace/span (contextvars; threads re-enter via attach())
# -----------------------------------------------------------------------

_trace: contextvars.ContextVar = contextvars.ContextVar(
    "snappy_trace", default=None)
_span: contextvars.ContextVar = contextvars.ContextVar(
    "snappy_trace_span", default=None)


def enabled() -> bool:
    return bool(config.global_properties().tracing_enabled)


def current() -> Optional[Trace]:
    return _trace.get()


def current_span() -> Optional[Span]:
    return _span.get()


def current_trace_id() -> Optional[str]:
    tr = _trace.get()
    return tr.trace_id if tr is not None else None


def wire_id() -> Optional[str]:
    """The trace id to ship in a request body/ticket, or None (no
    active trace).  Kept as its own helper so call sites read as wire
    propagation, not introspection."""
    return current_trace_id()


class request_scope:
    """Mint (or join) the request's trace.  An ambient trace absorbs
    the scope (nested executions stay one tree); otherwise a new trace
    starts when tracing is enabled (or `force`, which EXPLAIN ANALYZE
    uses so it works with tracing off).  On exit the trace finalizes
    into the ring + slow-query log.  Enters to the active Trace or
    None.  Class-based CM: the @contextmanager generator machinery cost
    ~4µs per request on the serving point-lookup profile."""

    __slots__ = ("sql", "user", "kind", "trace_id", "origin", "force",
                 "_tr", "_tok_t", "_tok_s", "_ann")

    def __init__(self, sql: str = "", user: str = "",
                 kind: str = "session", trace_id: Optional[str] = None,
                 origin: Optional[str] = None, force: bool = False):
        self.sql = sql
        self.user = user
        self.kind = kind
        self.trace_id = trace_id
        self.origin = origin
        self.force = force
        self._tr = None

    def __enter__(self):
        ambient = _trace.get()
        if ambient is not None:
            return ambient
        if not (self.force or enabled()):
            return None
        tr = Trace(self.sql, self.user, self.kind,
                   trace_id=self.trace_id, origin=self.origin)
        self._tr = tr
        self._ann = _annotation(tr.root.name, trace_id=tr.trace_id,
                                kind=tr.kind) if _profiling() else None
        self._tok_t = _trace.set(tr)
        self._tok_s = _span.set(tr.root)
        return tr

    def __exit__(self, et, ev, tb):
        tr = self._tr
        if tr is None:
            return False
        if et is not None:
            tr.status = "error"
            tr.error = f"{et.__name__}: {ev}"[:300]
        _span.reset(self._tok_s)
        _trace.reset(self._tok_t)
        tr.finish()
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        _RING.record(tr)
        return False


def _annotation(name: str, **meta) -> TraceAnnotation:
    """Open `snappy:<name>` in the profiler's host trace, on this
    thread, from now until its `__exit__`."""
    ann = TraceAnnotation(PROFILE_PREFIX + name, **meta)
    ann.__enter__()
    return ann


class _NoopSpan:
    __slots__ = ()

    def set(self, key, value):
        pass

    def add(self, key, value):
        pass


_NOOP = _NoopSpan()


class span:
    """A timed child span of the current span — a no-op (one contextvar
    read, no allocation) when no trace is active.  Enters to the span
    so callers can `.set()` evidence on it."""

    __slots__ = ("name", "attrs", "_sp", "_tok", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._sp = None

    def __enter__(self):
        parent = _span.get()
        if parent is None:
            return _NOOP
        if parent._step is not None:
            parent.end_step()
        if len(parent.children) >= _MAX_CHILDREN:
            parent.attrs["children_truncated"] = \
                parent.attrs.get("children_truncated", 0) + 1
            return _NOOP
        self._ann = _annotation(self.name) if _profiling() else None
        sp = Span(self.name, self.attrs or None)
        parent.children.append(sp)
        self._sp = sp
        self._tok = _span.set(sp)
        return sp

    def __exit__(self, et, ev, tb):
        sp = self._sp
        if sp is not None:
            _span.reset(self._tok)
            sp.close()
            if self._ann is not None:
                self._ann.__exit__(et, ev, tb)
        return False


def step(name: str) -> None:
    """Open the step `name` of `HOST_SPANS` as a child of the current
    span, for host time that starts in one function and ends in another
    further along the statement: it lasts until the current span's next
    child (span or step) starts, or the current span closes. A step is
    never the current span, so it holds no children and what runs
    inside it lands on its parent (attrs, the collector's `gc_ms`). A
    no-op (one contextvar read) when no trace is active."""
    parent = _span.get()
    if parent is None:
        return
    if name not in HOST_SPANS:
        raise ValueError(f"unknown host span {name!r}")
    if parent._step is not None:
        parent.end_step()
    if len(parent.children) >= _MAX_CHILDREN:
        parent.attrs["children_truncated"] = \
            parent.attrs.get("children_truncated", 0) + 1
        return
    ann = _annotation(name) if _profiling() else None
    sp = Span(name)
    parent.children.append(sp)
    parent._step = (sp, ann)


def annotate(key: str, value) -> None:
    """Attach evidence to the CURRENT span (no-op untraced)."""
    sp = _span.get()
    if sp is not None:
        sp.attrs[key] = value


# -----------------------------------------------------------------------
# XLA compiles, wherever they happen
# -----------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _on_jax_duration(event: str, duration_s: float, **_meta) -> None:
    """`jax.monitoring` duration listener.  JAX fires it on the thread
    that traces/compiles, so the contextvar names the span the compile
    really happened under — `device_execute` when a batch-count bucket
    re-specializes a plan whose static key did not change.  A load from
    the persistent compile cache fires the backend event too: it is a
    shape first met."""
    if event not in (_COMPILE_EVENT, _RETRACE_EVENT):
        return
    from snappydata_tpu.observability.metrics import global_registry

    reg = global_registry()
    sp = _span.get()
    if event == _COMPILE_EVENT:
        reg.inc("xla_compiles")
        reg.record_time("xla_compile", duration_s)
        if sp is not None:
            sp.add("xla_compiles", 1)
            sp.add("xla_compile_ms", round(duration_s * 1e3, 3))
    else:
        reg.inc("jit_retraces")
        if sp is not None:
            sp.add("retraces", 1)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# -----------------------------------------------------------------------
# Python's collector, wherever it pauses the program
# -----------------------------------------------------------------------

_gc_t0 = 0.0
_gc_pause_s = 0.0
_gc_collections = 0


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook. A collection can start at any bytecode
    boundary, the registry's lock held or not, so this takes no lock:
    module floats and ints, and the current span's attrs, under the GIL.
    Collections do not overlap (the collector does not re-enter), so one
    start time serves."""
    global _gc_t0, _gc_pause_s, _gc_collections
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    dt = time.perf_counter() - _gc_t0
    _gc_pause_s += dt
    _gc_collections += 1
    sp = _span.get()
    if sp is not None:
        sp.add("gc_ms", round(dt * 1e3, 4))
        if info.get("generation") == 2:
            sp.add("gc_full", 1)


def gc_pause_ms() -> float:
    """Milliseconds the collector has paused this process, all threads."""
    return _gc_pause_s * 1e3


def gc_collections() -> int:
    """Collections the collector has run in this process, all threads."""
    return _gc_collections


gc.callbacks.append(_on_gc)


def _register_gc_metrics() -> None:
    from snappydata_tpu.observability.metrics import global_registry

    reg = global_registry()
    reg.gauge("gc_pause_ms", gc_pause_ms)
    reg.counter_fn("gc_collections", gc_collections)


_register_gc_metrics()


# -----------------------------------------------------------------------
# names on the device side
# -----------------------------------------------------------------------

# operator scopes of a compiled plan (`jax.named_scope`): they ride the
# HLO's op_name metadata, so a profile's device ops and an HLO dump say
# which operator an XLA fusion came from.  Metadata only — the compiled
# code does not change.  One list, so a trace reader can name them all.
# A join is `join` with its parts nested inside it: `join_probe` (the
# probe keys' binary search of the sorted build keys), `join_gather`
# (build-side columns fetched through the match positions) and
# `join_expand` (the one-to-many output axis); the innermost name says
# which.  `group_keys` is a generic GROUP BY's key columns read at each
# group's run head, one value a group.
OP_SCOPES = ("filter", "decode", "dict_gather", "group_index",
             "group_reduce", "group_keys", "join", "join_probe",
             "join_gather", "join_expand")

# the spans that name a statement's host time between the others, so
# that the root's children tile it: `admit` from the end of `parse` to
# `optimize` (the governor's estimate and admission, the stream-window,
# tiled and mesh checks), `plan_lookup` from `Executor.execute` to `bind`
# (the plan cache, a miss's `compile` beside it), `finish` from the end
# of `transfer` (or `host_ops`) to the statement's close (the Result's
# assembly, decimals, the query log, the governor's release), opened by
# `step`; and `encode`, a served statement's Arrow encode, a span of the
# Flight server's trace
HOST_SPANS = ("admit", "plan_lookup", "finish", "encode")


def op_scope(name: str):
    """`jax.named_scope(name)` for a name of `OP_SCOPES`."""
    if name not in OP_SCOPES:
        raise ValueError(f"unknown operator scope {name!r}")
    return jax.named_scope(name)


def name_jit_target(fn, name: str):
    """Give the callable handed to `jax.jit` a stable `__name__`, so
    the profile's `XLA Modules` line and an HLO dump read `jit_<name>`
    and not `jit__unnamed_wrapped_function_` (what a bare
    `functools.partial` gets)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@contextlib.contextmanager
def attach(trace: Optional[Trace], at_span: Optional[Span] = None):
    """Re-enter a trace from a worker thread (contextvars do not cross
    threads).  Spans opened under it append to `at_span` (default: the
    trace root); list append is GIL-atomic, so concurrent workers may
    share a parent.  A trace that already FINISHED (the primary won and
    the request returned while this worker — a losing hedge leg — was
    still running) is not re-entered: its tree is published to the ring
    and must stop changing."""
    if trace is None or trace.duration_s is not None:
        yield
        return
    tok_t = _trace.set(trace)
    tok_s = _span.set(at_span or trace.root)
    try:
        yield
    finally:
        _span.reset(tok_s)
        _trace.reset(tok_t)


# -----------------------------------------------------------------------
# completed-trace ring + slow-query log
# -----------------------------------------------------------------------

class TraceRing:
    """Bounded ring of completed traces plus the separate slow-query
    ring (`slow_query_ms`) — a burst of fast queries can't evict the
    over-threshold outlier an operator is hunting."""

    SLOW_ENTRIES = 64

    def __init__(self):
        self._lock = locks.named_lock("tracing.rings")
        self._ring: "deque[Trace]" = deque()
        self._slow: "deque[Trace]" = deque(maxlen=self.SLOW_ENTRIES)
        self.recorded = 0
        self.slow_recorded = 0

    def record(self, trace: Trace) -> None:
        props = config.global_properties()
        cap = max(1, int(props.trace_ring_entries or 1))
        slow_ms = float(props.slow_query_ms or 0.0)
        is_slow = slow_ms > 0 and (trace.duration_s or 0.0) * 1e3 >= slow_ms
        with self._lock:
            self._ring.append(trace)
            while len(self._ring) > cap:
                self._ring.popleft()
            self.recorded += 1
            if is_slow:
                self._slow.append(trace)
                self.slow_recorded += 1
        if is_slow:
            from snappydata_tpu.observability.metrics import global_registry

            global_registry().inc("slow_queries")

    def traces(self, limit: int = 50) -> List[dict]:
        with self._lock:
            items = list(self._ring)[-max(1, limit):]
        return [t.summary() for t in reversed(items)]

    def get(self, trace_id: str) -> List[dict]:
        """Every local trace carrying `trace_id` (a distributed query
        in one process — the test cluster — may record a lead trace AND
        per-server traces under one id), full span trees."""
        with self._lock:
            items = [t for t in self._ring if t.trace_id == trace_id]
        return [t.to_dict() for t in items]

    def slow(self) -> List[dict]:
        with self._lock:
            items = list(self._slow)
        return [t.to_dict() for t in reversed(items)]

    def last(self) -> Optional[Trace]:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()


_RING = TraceRing()


def ring() -> TraceRing:
    return _RING


def tracing_snapshot() -> dict:
    """Knobs + ring state for `GET /status/api/v1/traces` and the
    dashboard's Tracing section."""
    props = config.global_properties()
    r = _RING
    with r._lock:
        held = len(r._ring)
        slow_held = len(r._slow)
    return {
        "tracing_enabled": bool(props.tracing_enabled),
        "trace_ring_entries": int(props.trace_ring_entries),
        "slow_query_ms": float(props.slow_query_ms or 0.0),
        "traces_recorded": r.recorded,
        "traces_held": held,
        "slow_queries_recorded": r.slow_recorded,
        "slow_queries_held": slow_held,
    }

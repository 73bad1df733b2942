"""SnappySession — the user entry point.

Mirrors the reference's session surface (core/.../SnappySession.scala:
sql:179, createTable:1049, insert:1983, put:2024, update:2047, delete:2112,
truncateTable, dropTable) and its execution pipeline (sqlPlan:2571 →
parse → analyze → plan-cache lookup keyed on tokenized plan → execute).
"""

from __future__ import annotations

import threading
import time
from snappydata_tpu.utils import locks
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from snappydata_tpu import config
from snappydata_tpu import types as T
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.engine.executor import Executor
from snappydata_tpu.engine.result import Result, empty_result
from snappydata_tpu.engine import hosteval
from snappydata_tpu.sql import ast
from snappydata_tpu.sql.analyzer import (Analyzer, AnalysisError,
                                         _expr_name, tokenize_plan)
from snappydata_tpu.sql.parser import parse
from snappydata_tpu.storage.table_store import ColumnTableData, RowTableData


class SnappySession:
    """One user session. Sessions share the catalog/storage of their
    SnappyCluster (or a process-local default), mirroring embedded mode."""

    _default_catalog: Optional[Catalog] = None
    _default_lock = locks.named_lock("session.default_registry")

    def __init__(self, catalog: Optional[Catalog] = None, conf=None,
                 data_dir: Optional[str] = None, recover: bool = True,
                 user: str = "admin"):
        """`data_dir` attaches a DiskStore (ref: sys-disk-dir): DML becomes
        WAL-durable, `checkpoint()` persists batches/manifests, and when
        `recover` the catalog+data are rebuilt from disk at startup.
        `user` is the session principal for GRANT/REVOKE checks (ref:
        LDAP-auth'd connections; "admin" is the superuser)."""
        self.user = user.lower()
        self.disk_store = None
        needs_recovery = False
        if data_dir is not None:
            from snappydata_tpu.storage.persistence import DiskStore

            self.disk_store = DiskStore(data_dir)
            # the store's write-once batch files double as the tier
            # quarantine's rebuild source (storage/tier.py self-healing)
            from snappydata_tpu.storage import tier as _tier

            _tier.attach_store(self.disk_store)
            if catalog is None and recover:
                # recovery must replay against THIS session (not a
                # throwaway) so anything it re-registers — stream queries
                # above all — binds the DURABLE session. A stream bound to
                # a store-less replay session silently stopped journaling
                # every post-recovery write (round-4 Kafka SIGKILL
                # battery caught the loss).
                needs_recovery = True
                catalog = Catalog()   # placeholder; recovery swaps it in
        if catalog is None:
            with SnappySession._default_lock:
                if SnappySession._default_catalog is None:
                    SnappySession._default_catalog = Catalog()
                catalog = SnappySession._default_catalog
        self.catalog = catalog
        self.conf = conf or config.global_properties()
        self.analyzer = Analyzer(catalog)
        self.executor = Executor(catalog, self.conf)
        # optional per-session device mesh: queries run sharded over it
        # (a data server's local chips — see ServerNode(mesh=…));
        # the MeshContext is cached (see _mesh_context) and swaps under
        # _mesh_resize_lock on a live resize_mesh() rebalance
        self.default_mesh = None
        self._mesh_ctx = None
        self._mesh_resize_lock = locks.named_lock("session.mesh")
        # reusable tile-merge scratch sessions keyed by partial schema
        # (see _merge_partial_pieces) — GIL-atomic list pop/append, no
        # lock: a throwaway session per merge re-COMPILED the merge
        # aggregate every tiled statement (~100ms of XLA per query)
        self._tile_merge_pool: Dict[str, list] = {}
        # set by the tiled lane; consumed (and cleared) after the
        # statement's read pin releases — see execute_statement
        self._tier_enforce_pending = False
        if needs_recovery:
            self.disk_store.recover_catalog(session=self)


    def _rewrite_stream_windows(self, plan: ast.Plan) -> ast.Plan:
        """FROM t WINDOW (DURATION d [, SLIDE s]) → arrival-time filter
        over the stream table's hidden __arrival_ts column, evaluated at
        EXECUTION time. The cutoff is a plain literal, so tokenization
        turns it into a rebindable param — the cached compiled plan serves
        every window evaluation (ref: WindowLogicalPlan/SchemaDStream)."""
        import dataclasses as _dc
        import time as _time

        def has_window(p) -> bool:
            if isinstance(p, ast.WindowedRelation):
                return True
            for k in p.children():
                if has_window(k):
                    return True
            for e in ast.plan_exprs(p):
                for x in ast.walk(e):
                    if isinstance(x, (ast.ScalarSubquery, ast.InSubquery,
                                      ast.ExistsSubquery)) \
                            and has_window(x.plan):
                        return True
            return False

        if not has_window(plan):
            return plan  # the common case allocates nothing

        def rec(p: ast.Plan) -> ast.Plan:
            if isinstance(p, ast.WindowedRelation):
                inner = p.child
                nm = inner.name if isinstance(inner,
                                              ast.UnresolvedRelation) else None
                info = self.catalog.lookup_table(nm) if nm else None
                if info is None:
                    raise AnalysisError(f"table or view not found: {nm}")
                if all(f.name != "__arrival_ts"
                       for f in info.schema.fields):
                    raise AnalysisError(
                        "WINDOW (DURATION ...) applies only to STREAM "
                        "tables")
                start = _time.time() - p.duration_s
                if p.slide_s:
                    start = int(start / p.slide_s) * p.slide_s
                cond = ast.BinOp(
                    ">=",
                    ast.Col("__arrival_ts",
                            inner.alias or nm.split(".")[-1]),
                    ast.Lit(int(start * 1e6), T.TIMESTAMP))
                return ast.Filter(inner, cond)
            kids = p.children()
            if not kids:
                return p
            if isinstance(p, (ast.Join, ast.Union, ast.SetOp)):
                p = _dc.replace(p, left=rec(p.left), right=rec(p.right))
            else:
                p = _dc.replace(p, child=rec(kids[0]))
            return p

        def sub_fn(e: ast.Expr) -> ast.Expr:
            # windows inside subquery expressions (EXISTS/IN/scalar) must
            # rewrite BEFORE decorrelation splices their plans into joins
            if isinstance(e, (ast.ScalarSubquery, ast.InSubquery,
                              ast.ExistsSubquery)):
                return _dc.replace(e, plan=rec(e.plan))
            return e

        return ast.transform_plan_exprs(rec(plan), sub_fn)

    def _log_query(self, sql_text: str, ms: float, rows: int) -> None:
        import collections
        import time as _time

        log = getattr(self.catalog, "_query_log", None)
        if log is None:
            log = self.catalog._query_log = collections.deque(maxlen=200)
            self.catalog._query_seq = 0
        self.catalog._query_seq += 1
        # stable id, NOT the deque position: a full ring shifts positions
        log.append({"id": self.catalog._query_seq, "sql": sql_text,
                    "ms": round(ms, 2), "rows": rows,
                    "ts": _time.time(), "user": self.user})

    def recent_queries(self) -> List[dict]:
        """Ring buffer of recent queries (sql, ms, rows, ts, user) shared
        by every session of this catalog — the dashboard's SQL tab."""
        return list(getattr(self.catalog, "_query_log", ()))

    def for_user(self, user: str, remote: bool = True,
                 authenticated: bool = False) -> "SnappySession":
        """A session for `user` sharing this session's catalog, conf and
        disk store — the per-request principal on network surfaces (ref:
        SnappySessionPerConnection, SparkSQLExecuteImpl.scala:99). `remote`
        marks it network-derived (gates EXEC PYTHON); `authenticated` means
        the principal was established by a verified credential."""
        s = SnappySession(catalog=self.catalog, conf=self.conf, user=user)
        s.disk_store = self.disk_store
        # plan cache + analyzer state are user-independent (RLS predicates
        # are injected per-plan at resolution) — share them so per-request
        # sessions keep the compiled-plan cache warm
        s.analyzer = self.analyzer
        s.executor = self.executor
        s.default_mesh = self.default_mesh
        # share the cached MeshContext: a fresh token per derived session
        # would rotate the device cache on every network request
        s._mesh_ctx = self._mesh_ctx
        s._mesh_resize_lock = self._mesh_resize_lock
        s.remote = remote
        s.authenticated = authenticated
        return s

    def checkpoint(self) -> None:
        """Persist all tables + catalog to the attached disk store and fold
        the WAL (ref: disk-store flush / backup base image)."""
        if self.disk_store is None:
            raise ValueError("no data_dir configured on this session")
        self.disk_store.checkpoint(self.catalog)

    # ------------------------------------------------------------------
    # SQL entry (ref SnappySession.sql:179)
    # ------------------------------------------------------------------

    def sql(self, sql_text: str, params: Sequence[Any] = (),
            query_ctx=None) -> Result:
        from snappydata_tpu.observability import tracing

        # one trace per logical request: a nested call (tile partials,
        # matview sync, subquery rewrites) finds the ambient trace and
        # attaches spans instead of minting a second id
        with tracing.request_scope(sql_text, user=self.user,
                                   kind="session"):
            return self._sql_traced(sql_text, params, query_ctx)

    def _sql_traced(self, sql_text: str, params: Sequence[Any] = (),
                    query_ctx=None) -> Result:
        from snappydata_tpu.observability import tracing

        with tracing.span("parse"):
            stmt = parse(sql_text)
        if isinstance(stmt, ast.Query):
            # live query log feeding the dashboard / REST plan UI (ref:
            # SnappySQLListener capturing plan info for the SQL tab)
            import time as _time

            t0 = _time.time()
            result = self._governed_query(sql_text, stmt, tuple(params),
                                          query_ctx)
            self._log_query(sql_text, (_time.time() - t0) * 1000.0,
                            result.num_rows)
            from snappydata_tpu.engine.result import finalize_decimals

            return finalize_decimals(result)
        if query_ctx is not None:
            # jobserver submissions govern non-SELECT statements too: the
            # pre-created context is admitted (estimate 0 — DML cost has
            # no scan estimate yet) so CANCEL and query_timeout_s apply,
            # e.g. to INSERT INTO ... SELECT through the executor's
            # cooperative checks
            from snappydata_tpu import resource

            if resource.current_query() is None:
                broker = resource.global_broker()
                if not query_ctx.sql:
                    query_ctx.sql = sql_text
                try:
                    broker.admit(query_ctx, 0,
                                 float(self.conf.query_timeout_s or 0.0))
                    with resource.query_scope(query_ctx):
                        return self._sql_statement(stmt, sql_text,
                                                   tuple(params))
                finally:
                    broker.release(query_ctx)
        return self._sql_statement(stmt, sql_text, tuple(params))

    def prepare(self, sql_text: str):
        """Compile-once prepared statement (ref: the thrift/DRDA layer's
        prepared statements; serving/prepared.py): parse + analyze +
        tokenize + compile happen ONCE, every `handle.execute(binds)`
        feeds the `?` values straight into the jitted program as runtime
        arguments — and concurrent executes of one handle fuse into a
        single vmapped device dispatch (serving_batch_max)."""
        from snappydata_tpu.serving import registry_for

        return registry_for(self.catalog).prepare(self, sql_text)

    def serving_sql(self, sql_text: str, params: Sequence[Any] = (),
                    query_ctx=None) -> Result:
        """Front-door query entry: route through the prepared-statement
        serving registry (compile-once + micro-batched dispatch), falling
        back to the plain sql() pipeline for statements the registry
        can't hold (DDL/DML and friends)."""
        from snappydata_tpu.serving import ServingError

        try:
            handle = self.prepare(sql_text)
        except ServingError:
            return self.sql(sql_text, params, query_ctx=query_ctx)
        return handle.execute(tuple(params), query_ctx=query_ctx)

    def _named_prepared(self) -> Dict:
        """SQL-level PREPARE name registry, keyed (user, name) on the
        shared catalog so network front doors can PREPARE in one request
        and EXECUTE in the next."""
        if not hasattr(self.catalog, "_named_prepared"):
            self.catalog._named_prepared = {}
        return self.catalog._named_prepared

    def _sql_statement(self, stmt: ast.Statement, sql_text: str,
                       params) -> Result:
        ds = self.disk_store
        if ds is not None and isinstance(
                stmt, (ast.InsertInto, ast.UpdateStmt, ast.DeleteStmt,
                       ast.TruncateTable, ast.AlterTable)):
            # authorize BEFORE journaling: a denied statement must never
            # reach the WAL (replay runs as admin and would apply it);
            # non-journaled paths authorize once in execute_statement
            self._authorize(stmt)
            import contextlib as _ctx

            ddl_gate = _ctx.nullcontext()
            if isinstance(stmt, ast.AlterTable) and not stmt.add:
                # DROP COLUMN vs an active pinned snapshot raises a typed
                # 40001 — the gate is entered BEFORE journaling (the WAL
                # must never hold a statement that did not apply: replay
                # would run it) and HELD across journal+apply, so a pin
                # admitted between check and remap can't make the 40001
                # fire post-append and diverge the log from memory
                from snappydata_tpu.storage import mvcc as _mvcc

                info = self.catalog.lookup_table(stmt.table)
                if info is not None:
                    ddl_gate = _mvcc.ddl_scope(
                        info.data, "ALTER TABLE DROP COLUMN")
            # journal BEFORE applying, under the mutation lock shared with
            # checkpoints (WAL invariant: on-disk log ≥ in-memory state)
            table = getattr(stmt, "table", None) or stmt.name
            from snappydata_tpu.catalog.catalog import _norm

            # a network front door's client-stamped statement id rides
            # the record header: recovery replay re-seeds the mutation
            # dedup window from it (reliability.py), so a lost-ack retry
            # that lands after a server restart still dedups
            from snappydata_tpu.reliability import current_stmt_id

            sid = current_stmt_id()
            from snappydata_tpu.observability import tracing
            from snappydata_tpu.storage import mvcc

            # the write path's spans, one set of names for this branch
            # and _journal_then: `wal_append` and `wal_sync` are opened
            # by the store itself (storage/persistence.py), `apply`
            # here; the wait for the mutation lock rides the span that
            # was open when it was taken, as `lock_wait_ms`
            t_lock = time.perf_counter()
            with ddl_gate, ds.mutation_lock:
                _note_lock_wait(t_lock)
                seq = ds.wal_append(_norm(table), "sql", sql=sql_text,
                                    params=tuple(params),
                                    extra={"stmt_id": sid} if sid else None)
                # the WAL seq IS the commit timestamp: manifests this
                # statement publishes carry it (mvcc epoch fences)
                with mvcc.commit_scope(seq), tracing.span("apply") as sp:
                    # locklint: blocking-under-lock nested reads under a
                    # DML's mutation hold run on STORE-LESS scratch
                    # sessions (tile-merge scratch, matview folds) whose
                    # _sql_statement never reaches wal_sync/fsync; device
                    # waits here are the cost of journal->apply atomicity
                    result = self.execute_statement(stmt, tuple(params))
                    sp.set("rows", _affected_rows(result))
            # ack gate (group commit): the record may still sit in the
            # commit buffer — wal_sync blocks until the covering fsync,
            # OUTSIDE the mutation lock so concurrent committers coalesce
            # into one group fsync instead of serializing on it
            ds.wal_sync(seq)
            return result
        result = self.execute_statement(stmt, tuple(params))
        if ds is not None:
            from snappydata_tpu.catalog.catalog import _norm

            if isinstance(stmt, ast.CreateTable):
                if not hasattr(self.catalog, "_view_ddl"):
                    self.catalog._view_ddl = {}
                if stmt.stream:
                    # stream feeds re-register on recovery via DDL replay
                    # (review finding: tables silently stopped being fed)
                    if not hasattr(self.catalog, "_aux_ddl"):
                        self.catalog._aux_ddl = {}
                    self.catalog._aux_ddl[
                        f"stream:{stmt.name.lower()}"] = sql_text
                ds.save_catalog(self.catalog)
                if stmt.as_select is not None:
                    # CTAS rows exist only in memory: checkpoint the new
                    # table immediately (they were never WAL'd)
                    info = self.catalog.lookup_table(stmt.name)
                    if info is not None:
                        with ds.mutation_lock:
                            ds.checkpoint_table(info, ds.current_wal_seq())
            elif isinstance(stmt, ast.DropTable):
                ds.drop_table_dir(_norm(stmt.name))
                getattr(self.catalog, "_aux_ddl", {}).pop(
                    f"stream:{_norm(stmt.name)}", None)
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, ast.CreateView):
                if not hasattr(self.catalog, "_view_ddl"):
                    self.catalog._view_ddl = {}
                self.catalog._view_ddl[_norm(stmt.name)] = sql_text
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, ast.CreateMaterializedView):
                if not hasattr(self.catalog, "_matview_ddl"):
                    self.catalog._matview_ddl = {}
                self.catalog._matview_ddl.setdefault(_norm(stmt.name),
                                                     sql_text)
                ds.save_catalog(self.catalog)
                # first durable image of the fresh state (watermark =
                # everything journaled so far, which the initial refresh
                # just aggregated)
                mv = getattr(self.catalog, "_matviews", {}).get(
                    _norm(stmt.name))
                if mv is not None:
                    with ds.mutation_lock:
                        ds.checkpoint_matview(mv, mv.wal_seq,
                                              catalog=self.catalog)
            elif isinstance(stmt, ast.DropMaterializedView):
                getattr(self.catalog, "_matview_ddl", {}).pop(
                    _norm(stmt.name), None)
                ds.drop_matview_state(_norm(stmt.name))
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, ast.DropView):
                getattr(self.catalog, "_view_ddl", {}).pop(
                    _norm(stmt.name), None)
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, (ast.CreatePolicy, ast.CreateIndex)):
                if not hasattr(self.catalog, "_aux_ddl"):
                    self.catalog._aux_ddl = {}
                kind = "policy" if isinstance(stmt, ast.CreatePolicy) \
                    else "index"
                # namespaced key: a policy and an index may share a name
                # (review finding: one flat dict let an index overwrite a
                # policy's persisted DDL)
                self.catalog._aux_ddl[f"{kind}:{stmt.name.lower()}"] = \
                    sql_text
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, (ast.DropPolicy, ast.DropIndex)):
                kind = "policy" if isinstance(stmt, ast.DropPolicy) \
                    else "index"
                getattr(self.catalog, "_aux_ddl", {}).pop(
                    f"{kind}:{stmt.name.lower()}", None)
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, ast.CreateFunction):
                if not hasattr(self.catalog, "_aux_ddl"):
                    self.catalog._aux_ddl = {}
                self.catalog._aux_ddl[
                    f"function:{stmt.name.lower()}"] = sql_text
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, ast.DropFunction):
                getattr(self.catalog, "_aux_ddl", {}).pop(
                    f"function:{stmt.name.lower()}", None)
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, (ast.GrantStmt, ast.RevokeStmt)):
                ds.save_catalog(self.catalog)  # grants persist like DDL
            elif isinstance(stmt, ast.DeployStmt):
                # persist a DDL that points at the STORED copies so
                # recovery replays cleanly even after the original source
                # path disappears
                entry = self._deployed().get(stmt.name.lower())
                if not hasattr(self.catalog, "_aux_ddl"):
                    self.catalog._aux_ddl = {}
                self.catalog._aux_ddl[f"deploy:{stmt.name.lower()}"] = (
                    f"DEPLOY {stmt.kind.upper()} {stmt.name} "
                    f"'{', '.join(entry['files'])}'")
                ds.save_catalog(self.catalog)
            elif isinstance(stmt, ast.UndeployStmt):
                getattr(self.catalog, "_aux_ddl", {}).pop(
                    f"deploy:{stmt.name.lower()}", None)
                ds.save_catalog(self.catalog)
        return result

    def _governed_query(self, sql_text: str, stmt: ast.Query, params,
                        query_ctx=None) -> Result:
        """Resource-governor choke point for top-level queries: submit a
        memory estimate, get admitted/queued/rejected, run under a
        QueryContext so CANCEL/timeout/broker kills stop the scan at the
        next tile boundary (ref: SnappyUnifiedMemoryManager admission +
        CancelException checks in generated scan loops). Nested
        executions — tile partials, the tiled-merge scratch session,
        subquery rewrites — inherit the outer context and skip
        re-admission."""
        from snappydata_tpu import resource
        from snappydata_tpu.observability import tracing

        if resource.current_query() is not None:
            return self.execute_statement(stmt, params)
        # the estimate, admission, the snapshot pin and the route checks
        # up to `optimize`, which ends the step
        tracing.step("admit")
        broker = resource.global_broker()
        ctx = query_ctx or resource.new_query(sql_text, self.user)
        if not ctx.sql:
            ctx.sql = sql_text
        # the estimate walk (per-table row counts) only matters when an
        # actual byte budget meters it — skip the cost on the default
        # ungoverned config, where admit() is register-only
        estimate = 0
        if broker.accounting_enabled():
            estimate = resource.estimate_statement_bytes(self.catalog, stmt)
            tile = self._tile_budget()
            shaped = self._tilable_agg_shape(stmt.plan) \
                if tile > 0 and not params else None
            if shaped is not None:
                # the engine streams this shape tile-by-tile under
                # scan_tile_bytes: peak memory is ~one tile, not the
                # full decoded table — charging the full table would
                # make every out-of-core aggregate un-admittable.  Join
                # build sides stay FULLY device-resident across tiles,
                # so they are charged on top; and when the builds alone
                # exceed the tile budget the tile pass declines and the
                # query runs untiled — admit it at full cost
                bb = self._join_build_side_bytes(shaped[4], shaped[5])
                if bb is not None and bb < tile:
                    estimate = min(estimate, tile + bb)
        try:
            # admit INSIDE the try: release() also clears a watched
            # (jobserver-submitted) context when admission fails
            broker.admit(ctx, estimate,
                         float(self.conf.query_timeout_s or 0.0))
            with resource.query_scope(ctx):
                return self.execute_statement(stmt, params)
        finally:
            broker.release(ctx)

    def _snapshot_tables_for(self, stmt: ast.Statement):
        """Tables a statement's READS should pin at one consistent epoch
        (storage/mvcc): the query plan's relations, a CTAS source, an
        INSERT ... SELECT source, and UPDATE/DELETE WHERE-subquery
        relations.  None = statement has no snapshot-shaped reads."""
        if isinstance(stmt, ast.Query):
            return _referenced_tables(stmt.plan)
        if isinstance(stmt, ast.CreateTable) and stmt.as_select is not None:
            return _referenced_tables(stmt.as_select)
        if isinstance(stmt, ast.InsertInto) \
                and not isinstance(stmt.source, ast.Values):
            return _referenced_tables(stmt.source) or None
        if isinstance(stmt, ast.UpdateStmt):
            names = []
            for e in [stmt.where] + [x for _, x in stmt.assignments]:
                if e is not None:
                    names.extend(_expr_subquery_tables(e))
            return names or None
        if isinstance(stmt, ast.DeleteStmt) and stmt.where is not None:
            return _expr_subquery_tables(stmt.where) or None
        return None

    def execute_statement(self, stmt: ast.Statement, user_params=()) -> Result:
        """Statement entry: reads pin ONE snapshot epoch for the whole
        statement (matview syncs, subquery rewrites, tile passes and
        host fallbacks all traverse it), so a long scan and concurrent
        ingest never block each other and never mix table versions.
        Nested executions find the ambient pin and extend it."""
        from snappydata_tpu.storage import mvcc

        names = self._snapshot_tables_for(stmt)
        try:
            if names is not None and mvcc.current_pin() is None:
                with mvcc.pinned_scope(self.catalog, names):
                    return self._execute_statement_body(stmt, user_params)
            return self._execute_statement_body(stmt, user_params)
        finally:
            # a tiled pass inside the statement may have left a tier
            # over its knob; the ladder walk has to wait until the
            # statement pin is gone or demote_device pin-skips the very
            # entries it must drop.  An ambient caller-held pin defers
            # to that caller's next unpinned statement.
            if self._tier_enforce_pending and mvcc.current_pin() is None:
                self._tier_enforce_pending = False
                from snappydata_tpu.storage import tier

                tier.maybe_demote()

    def _execute_statement_body(self, stmt: ast.Statement,
                                user_params=()) -> Result:
        self._authorize(stmt)
        if isinstance(stmt, ast.Query):
            # materialized views referenced by the query re-merge their
            # maintained [G] state into the backing rows when dirty —
            # O(G), never a base-table rescan unless the view is stale
            self._sync_referenced_matviews(stmt.plan)
            # HAC surface: WITH ERROR and/or error functions route
            # through stratified estimation (ref hac_contracts.md:38-82)
            if stmt.with_error is not None or \
                    getattr(self.catalog, "_sample_maintainers", None):
                from snappydata_tpu.aqp.error_estimation import (
                    execute_error_query, query_has_error_surface)

                if query_has_error_surface(stmt):
                    return execute_error_query(self, stmt, user_params)
            return self._run_query(stmt.plan, user_params)
        if isinstance(stmt, ast.GrantStmt):
            if self.user != "admin":
                raise PermissionError("only admin may GRANT")
            if self.catalog.lookup_table(stmt.table) is None and \
                    self.catalog.lookup_view(stmt.table) is None:
                raise ValueError(f"table or view not found: {stmt.table}")
            grants = self._grants()
            key = (stmt.grantee.lower(), _table_key(self.catalog, stmt.table))
            privs = grants.setdefault(key, set())
            privs.update(_expand_privs(stmt.privileges))
            return _status()
        if isinstance(stmt, ast.RevokeStmt):
            if self.user != "admin":
                raise PermissionError("only admin may REVOKE")
            grants = self._grants()
            key = (stmt.grantee.lower(), _table_key(self.catalog, stmt.table))
            if key in grants:
                grants[key] -= _expand_privs(stmt.privileges)
                if not grants[key]:
                    del grants[key]
            return _status()
        if isinstance(stmt, ast.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, ast.DropTable):
            pre = self.catalog.lookup_table(stmt.name)
            if pre is not None and pre.options.get("materialized_view"):
                raise ValueError(
                    f"{stmt.name} is a materialized view — use DROP "
                    "MATERIALIZED VIEW")
            dropped = self.catalog.drop_table(stmt.name, stmt.if_exists)
            if dropped:
                # cascade: policies/indexes of the dropped table must not
                # haunt a future table of the same name (review finding)
                from snappydata_tpu.catalog.catalog import _norm

                tname = _norm(stmt.name)
                pols = getattr(self.catalog, "_policies", {})
                for pname in [p for p, (t, _) in pols.items() if t == tname]:
                    pols.pop(pname)
                    getattr(self.catalog, "_aux_ddl", {}).pop(
                        f"policy:{pname}", None)
                idxs = getattr(self.catalog, "_indexes", {})
                for iname in [i for i, (t, _) in idxs.items() if t == tname]:
                    idxs.pop(iname)
                    getattr(self.catalog, "_aux_ddl", {}).pop(
                        f"index:{iname}", None)
                # grants must not survive onto a recreated namesake table
                grants = getattr(self.catalog, "_grants", {})
                for gk in [k for k in grants if k[1] == tname]:
                    grants.pop(gk)
                # stream tables: stop the feeding query
                stream = getattr(self.catalog, "_streams", {}).pop(tname,
                                                                   None)
                if stream is not None:
                    stream.stop()
                # TopKs over the dropped table: deregister (a persisted
                # stale def would crash recovery — review finding)
                defs = getattr(self.catalog, "_topk_defs", {})
                for nm in [n for n, d in defs.items()
                           if d["base_table"] == tname]:
                    defs.pop(nm)
                    getattr(self.catalog, "_topks", {}).pop(nm, None)
                # materialized views over the dropped table go with it
                # (like policies/indexes — a namesake recreate must not
                # resurrect folds against a different table); their DDL
                # and durable state go too, or recovery replays orphans
                mvs = getattr(self.catalog, "_matviews", {})
                for vn in [v for v, m in mvs.items()
                           if m.base_table == tname]:
                    mv = mvs.pop(vn)
                    mv.dispose()
                    self.catalog.drop_table(vn, if_exists=True)
                    getattr(self.catalog, "_matview_ddl", {}).pop(vn,
                                                                  None)
                    if self.disk_store is not None:
                        self.disk_store.drop_matview_state(vn)
                # sample maintainers of/over the dropped table
                maints = getattr(self.catalog, "_sample_maintainers", {})
                for nm in [n for n, m in maints.items()
                           if n == tname or m.base_info.name == tname]:
                    m = maints.pop(nm)
                    try:  # unhook the base feed (else it leaks per drop)
                        m.base_info.data.on_insert.remove(m.on_insert)
                    except (ValueError, AttributeError):
                        pass
            return _status()
        if isinstance(stmt, ast.TruncateTable):
            info = self.catalog.describe(stmt.name)
            if info.options.get("materialized_view"):
                raise ValueError(
                    f"{stmt.name} is a materialized view; it is "
                    "maintained automatically (DROP MATERIALIZED VIEW to "
                    "remove it)")
            info.data.truncate()
            from snappydata_tpu.views import matview as _mv

            _mv.on_truncate(self.catalog, info.name,
                            self.disk_store.current_wal_seq()
                            if self.disk_store else 0)
            return _status()
        if isinstance(stmt, ast.CreateFunction):
            # UDF bodies are python code: same gate as EXEC PYTHON
            self._gate_code_surface("CREATE FUNCTION")
            from snappydata_tpu.sql import udf as _udf

            if not stmt.or_replace and stmt.name.lower() in \
                    getattr(self.catalog, "_functions", {}):
                raise ValueError(f"function already exists: {stmt.name}")
            _udf.register(self.catalog, stmt.name, stmt.body,
                          stmt.returns)
            return _status()
        if isinstance(stmt, ast.DropFunction):
            from snappydata_tpu.sql import udf as _udf

            _udf.unregister(self.catalog, stmt.name, stmt.if_exists)
            return _status()
        if isinstance(stmt, ast.AlterTable):
            return self._alter_table(stmt)
        if isinstance(stmt, ast.CreateView):
            if _contains_subquery(stmt.query):
                raise AnalysisError(
                    "subqueries in view definitions are not supported yet")
            def _contains_window(p):
                if isinstance(p, ast.WindowedRelation):
                    return True
                return any(_contains_window(k) for k in p.children())

            if _contains_window(stmt.query):
                raise AnalysisError(
                    "WINDOW (DURATION ...) is not supported inside views "
                    "yet — query the stream table with the window directly")
            self.analyzer.analyze_plan(stmt.query)  # validate now
            # store UNRESOLVED: views re-analyze per query, so policies
            # created or dropped later apply correctly (review finding:
            # baked-resolved views bypassed row-level security)
            self.catalog.create_view(stmt.name, stmt.query, stmt.or_replace)
            return _status()
        if isinstance(stmt, ast.DropView):
            self.catalog.drop_view(stmt.name, stmt.if_exists)
            return _status()
        if isinstance(stmt, ast.CreateMaterializedView):
            return self._create_matview(stmt)
        if isinstance(stmt, ast.DropMaterializedView):
            return self._drop_matview(stmt)
        if isinstance(stmt, ast.RefreshMaterializedView):
            from snappydata_tpu.catalog.catalog import _norm
            from snappydata_tpu.views import matviews

            # _norm, not .lower(): REFRESH app.mv must find the view
            # CREATE registered under the schema-stripped name
            mv = matviews(self.catalog).get(_norm(stmt.name))
            if mv is None:
                raise ValueError(
                    f"materialized view not found: {stmt.name}")
            mv.refresh_full(self)
            mv.sync(self)
            return _status()
        if isinstance(stmt, ast.InsertInto):
            n = self._insert(stmt, user_params)
            return _count_result(n)
        if isinstance(stmt, ast.UpdateStmt):
            return _count_result(self._update(stmt, user_params))
        if isinstance(stmt, ast.DeleteStmt):
            return _count_result(self._delete(stmt, user_params))
        if isinstance(stmt, ast.ShowTables):
            infos = self.catalog.list_tables()
            return Result(
                ["tableName", "provider", "rowCount"],
                [np.array([i.name for i in infos], dtype=object),
                 np.array([i.provider for i in infos], dtype=object),
                 np.array([_row_count(i) for i in infos], dtype=np.int64)],
                [None, None, None], [T.STRING, T.STRING, T.LONG])
        if isinstance(stmt, ast.DescribeTable):
            info = self.catalog.describe(stmt.name)
            fields = [f for f in info.schema.fields
                      if not f.name.startswith("__")]  # internal cols
            return Result(
                ["col_name", "data_type", "nullable"],
                [np.array([f.name for f in fields], dtype=object),
                 np.array([str(f.dtype) for f in fields], dtype=object),
                 np.array([f.nullable for f in fields])],
                [None, None, None], [T.STRING, T.STRING, T.BOOLEAN])
        if isinstance(stmt, ast.SetConf):
            self.conf.set(stmt.key, stmt.value)
            return _status()
        if isinstance(stmt, ast.ExecCode):
            self._gate_code_surface("EXEC PYTHON")
            return self._exec_code(stmt.code)
        if isinstance(stmt, ast.DeployStmt):
            # deploying artifacts makes them importable from EXEC PYTHON —
            # same code-execution surface, same gate
            self._gate_code_surface("DEPLOY")
            return self._deploy(stmt)
        if isinstance(stmt, ast.UndeployStmt):
            self._gate_code_surface("UNDEPLOY")
            return self._undeploy(stmt.name)
        if isinstance(stmt, ast.ListDeployed):
            return self._list_deployed(stmt.kind)
        if isinstance(stmt, ast.ExplainStmt):
            return self._explain(stmt.query, analyze=stmt.analyze)
        if isinstance(stmt, ast.CreatePolicy):
            info = self.catalog.describe(stmt.table)
            for node in ast.walk(stmt.using):
                if isinstance(node, (ast.ScalarSubquery, ast.InSubquery,
                                     ast.ExistsSubquery)):
                    raise AnalysisError(
                        "subqueries in policy predicates are not supported")
            if not hasattr(self.catalog, "_policies"):
                self.catalog._policies = {}
            self.catalog._policies[stmt.name.lower()] = (info.name,
                                                         stmt.using)
            self.catalog.generation += 1
            return _status()
        if isinstance(stmt, ast.DropPolicy):
            pols = getattr(self.catalog, "_policies", {})
            if stmt.name.lower() not in pols and not stmt.if_exists:
                raise ValueError(f"policy not found: {stmt.name}")
            pols.pop(stmt.name.lower(), None)
            self.catalog.generation += 1
            return _status()
        if isinstance(stmt, ast.CreateIndex):
            info = self.catalog.describe(stmt.table)
            if not isinstance(info.data, RowTableData):
                raise ValueError(
                    "indexes are supported on row tables (column tables "
                    "use batch-stats skipping instead)")
            if not hasattr(self.catalog, "_indexes"):
                self.catalog._indexes = {}
            if stmt.name.lower() in self.catalog._indexes:
                if stmt.if_not_exists:
                    return _status()
                raise ValueError(f"index already exists: {stmt.name}")
            for c in stmt.columns:
                info.schema.index(c)  # validates
            info.data.create_index(stmt.name, stmt.columns)
            self.catalog._indexes[stmt.name.lower()] = (
                info.name, tuple(c.lower() for c in stmt.columns))
            return _status()
        if isinstance(stmt, ast.DropIndex):
            idxs = getattr(self.catalog, "_indexes", {})
            entry = idxs.pop(stmt.name.lower(), None)
            if entry is None:
                if stmt.if_exists:
                    return _status()
                raise ValueError(f"index not found: {stmt.name}")
            self.catalog.describe(entry[0]).data.drop_index(stmt.name)
            return _status()
        if isinstance(stmt, ast.PrepareStmt):
            # registers the shared compile-once entry AND the (user, name)
            # alias; authorization against the query's tables happens in
            # registry.prepare (and again per EXECUTE — grants can change
            # under a held handle)
            self.prepare(stmt.query_sql)
            self._named_prepared()[(self.user, stmt.name.lower())] = \
                stmt.query_sql
            return _status()
        if isinstance(stmt, ast.ExecuteStmt):
            from snappydata_tpu.serving import ServingError

            sql_text = self._named_prepared().get(
                (self.user, stmt.name.lower()))
            if sql_text is None:
                raise ServingError(
                    f"no prepared statement named {stmt.name!r} "
                    f"for user {self.user!r} (PREPARE it first)")
            return self.prepare(sql_text).execute(tuple(stmt.args))
        if isinstance(stmt, ast.DeallocateStmt):
            from snappydata_tpu.serving import ServingError

            if self._named_prepared().pop(
                    (self.user, stmt.name.lower()), None) is None:
                raise ServingError(
                    f"no prepared statement named {stmt.name!r} "
                    f"for user {self.user!r}")
            return _status()
        raise ValueError(f"unsupported statement {type(stmt).__name__}")

    # ------------------------------------------------------------------
    # Materialized views (views/matview.py — delta-folded aggregates)
    # ------------------------------------------------------------------

    def _sync_referenced_matviews(self, plan: ast.Plan) -> None:
        self._sync_matviews_by_name(_referenced_tables(plan))

    def _sync_expr_matviews(self, exprs) -> None:
        """Sync matviews read through subqueries inside expressions (the
        UPDATE/DELETE WHERE path — plans go through
        _sync_referenced_matviews; a stale view read through a WHERE
        subquery would otherwise see pre-fold backing rows)."""
        names = []
        for e in exprs:
            if e is not None:
                names.extend(_expr_subquery_tables(e))
        if names:
            self._sync_matviews_by_name(names)

    def _sync_matviews_by_name(self, names) -> None:
        mvs = getattr(self.catalog, "_matviews", None)
        if not mvs or getattr(self, "_in_mv_sync", False):
            return
        from snappydata_tpu.catalog.catalog import _norm

        names = {_norm(n) for n in names}
        hit = [mvs[n] for n in names if n in mvs]
        if not hit:
            return
        from snappydata_tpu.observability.metrics import global_registry

        self._in_mv_sync = True
        try:
            for mv in hit:
                mv.sync(self)
                global_registry().inc("view_reads")
        finally:
            self._in_mv_sync = False

    def _create_matview(self, stmt: ast.CreateMaterializedView) -> Result:
        from snappydata_tpu.catalog.catalog import _norm
        from snappydata_tpu.views import matview as _mv
        from snappydata_tpu.views.matview import MaterializedView

        name = _norm(stmt.name)
        if not hasattr(self.catalog, "_matviews"):
            self.catalog._matviews = {}
        if name in self.catalog._matviews:
            if stmt.if_not_exists:
                return _status()
            raise ValueError(
                f"materialized view already exists: {stmt.name}")
        if self.catalog.lookup_table(name) is not None or \
                self.catalog.lookup_view(name) is not None:
            raise ValueError(f"table or view already exists: {stmt.name}")
        mv = MaterializedView.define(self, name, stmt.query, "")
        # backing table: queryable through the normal engine (filters,
        # joins, sorts over the view all work); writes are refused
        self.catalog.create_table(name, mv.output_schema, "column",
                                  {"materialized_view": "true"})
        self.catalog._matviews[name] = mv
        self.catalog.generation += 1
        _mv.ledger_catalog(self.catalog)
        base_info = self.catalog.lookup_table(mv.base_table)
        if base_info is not None:
            _mv.register_unmanaged_write_guard(self.catalog, base_info)
        if not getattr(self, "_mv_recovering", False):
            try:
                mv.refresh_full(self)
                mv.sync(self)
            except BaseException:
                # a failed initial refresh (timeout, admission reject,
                # injected fault) must not leave a half-created view
                # that blocks a retried CREATE
                self.catalog._matviews.pop(name, None)
                mv.dispose()
                self.catalog.drop_table(name, if_exists=True)
                self.catalog.generation += 1
                raise
        return _status()

    def _drop_matview(self, stmt: ast.DropMaterializedView) -> Result:
        from snappydata_tpu.catalog.catalog import _norm

        name = _norm(stmt.name)
        mvs = getattr(self.catalog, "_matviews", {})
        mv = mvs.get(name)
        if mv is None:
            if stmt.if_exists:
                return _status()
            raise ValueError(f"materialized view not found: {stmt.name}")
        mvs.pop(name)
        mv.dispose()   # frees the broker-ledgered state bytes
        self.catalog.drop_table(name, if_exists=True)
        self.catalog.generation += 1
        return _status()

    def _reject_matview_write(self, info) -> None:
        if info.options.get("materialized_view"):
            raise ValueError(
                f"{info.name} is a materialized view; it is maintained "
                "automatically from its base table")

    def _fold_views(self, info, arrays, nulls, out):
        """Post-apply ingest hook: fold the delta into every dependent
        view (runs inside the journal mutation scope, so checkpoints see
        view state consistent with table state)."""
        from snappydata_tpu.views import matview as _mv

        _mv.fold_ingest(self.catalog, info.name, arrays, nulls)
        return out

    def _fold_row_put(self, info, arrays, nulls=None) -> None:
        """View maintenance for a row-table PUT: a keyed upsert may have
        REPLACED rows whose old image is not visible here, so dependent
        views go stale; a keyless put is a plain insert and folds."""
        from snappydata_tpu.views import matview as _mv

        if info.key_columns:
            _mv.mark_stale(self.catalog, info.name, "keyed put")
        else:
            _mv.fold_ingest(self.catalog, info.name, arrays, nulls)

    def _explain(self, plan: ast.Plan, analyze: bool = False) -> Result:
        """EXPLAIN [ANALYZE]: optimized + resolved plan tree, one node
        per line (ref: the plan info SnappySQLListener feeds the SQL
        UI).  ANALYZE additionally EXECUTES the query under a (forced)
        request trace and annotates the tree with runtime stats read off
        the engine's own counters — batches scanned vs skipped (min/max
        stats vs dictionary probes), reduction strategy chosen,
        code-domain vs decoded predicate lanes, join device/host
        verdicts, host-fallback evidence — plus a runtime footer with
        rows out, per-phase seconds from the trace's span tree, and the
        trace id (joinable against /status/api/v1/traces)."""
        from snappydata_tpu.sql.optimizer import optimize
        from snappydata_tpu.sql.analyzer import _expr_name

        run_stats = self._explain_execute(plan) if analyze else None
        plan = self._rewrite_stream_windows(plan)
        plan = self._decorrelate(plan)
        optimized = optimize(plan, self.catalog)
        resolved, _ = self.analyzer.analyze_plan(optimized)
        lines: List[str] = []

        def describe(p: ast.Plan) -> str:
            if isinstance(p, ast.Relation):
                info = self.catalog.lookup_table(p.name)
                extra = ""
                if info is not None and info.partition_by:
                    extra = f" partition_by={','.join(info.partition_by)}"
                return f"Scan {p.name}{extra}"
            if isinstance(p, ast.Filter):
                return "Filter"
            if isinstance(p, ast.Project):
                return ("Project [" +
                        ", ".join(_expr_name(e) for e in p.exprs) + "]")
            if isinstance(p, ast.WindowProject):
                return "WindowProject (host)"
            if isinstance(p, ast.Aggregate):
                keys = ", ".join(_expr_name(g) for g in p.group_exprs)
                return f"HashAggregate keys=[{keys}]"
            if isinstance(p, ast.Join):
                return f"Join {p.how} (sort+searchsorted)"
            if isinstance(p, ast.Sort):
                return "Sort (host)"
            if isinstance(p, ast.Limit):
                return f"Limit {p.n}"
            if isinstance(p, ast.Distinct):
                return "Distinct (host)"
            if isinstance(p, ast.Union):
                return "Union"
            if isinstance(p, ast.SetOp):
                return p.op.capitalize() + " (host)"
            if isinstance(p, ast.SubqueryAlias):
                return f"SubqueryAlias {p.alias}"
            if isinstance(p, ast.Values):
                return f"Values ({len(p.rows)} rows)"
            return type(p).__name__

        def count_nodes(p: ast.Plan, kinds: dict) -> None:
            for K in (ast.Relation, ast.Aggregate, ast.Join):
                if isinstance(p, K):
                    kinds[K] = kinds.get(K, 0) + 1
            for k in p.children():
                count_nodes(k, kinds)

        kinds: Dict = {}
        if run_stats is not None:
            count_nodes(resolved, kinds)

        def annotate(p: ast.Plan) -> str:
            """Runtime suffix for EXPLAIN ANALYZE.  The engine's
            counters are plan-wide, so inline per-node annotation only
            happens when the node is the plan's ONLY one of its kind
            (the footer always carries the full numbers)."""
            st = run_stats
            if st is None:
                return ""
            if isinstance(p, ast.Relation) and kinds.get(ast.Relation) == 1:
                info = self.catalog.lookup_table(p.name)
                rows_in = 0
                if info is not None:
                    try:
                        rows_in = info.data.count() if isinstance(
                            info.data, RowTableData) else \
                            info.data.snapshot().total_rows()
                    except Exception:
                        rows_in = 0
                return (f" [rows={rows_in}"
                        f" batches_seen={st['batches_seen']}"
                        f" skipped_stats={st['batches_skipped_stats']}"
                        f" skipped_dict={st['batches_skipped_dict']}"
                        f" code_domain="
                        f"{'yes' if st['code_domain_predicates'] else 'no'}]")
            if isinstance(p, ast.Aggregate) and \
                    kinds.get(ast.Aggregate) == 1:
                strat = ",".join(st["strategies"]) or "host"
                return (f" [strategy={strat}"
                        f" rows_out={st['rows_out']}]")
            if isinstance(p, ast.Join) and kinds.get(ast.Join) == 1:
                if st["join_host_fallbacks"]:
                    return " [path=host]"
                if st["join_device_joins"]:
                    return " [path=device]"
            return ""

        def walk_plan(p: ast.Plan, depth: int) -> None:
            lines.append("  " * depth + describe(p) + annotate(p))
            for k in p.children():
                walk_plan(k, depth + 1)

        walk_plan(resolved, 0)
        if run_stats is not None:
            st = run_stats
            lines.append("== runtime (EXPLAIN ANALYZE) ==")
            lines.append(
                f"rows_out={st['rows_out']} "
                f"elapsed_ms={st['elapsed_s'] * 1e3:.3f} "
                f"trace_id={st['trace_id']}")
            lines.append(
                f"plan_cache={st['plan_cache']} "
                f"host_fallbacks={st['host_fallbacks']} "
                f"batches_seen={st['batches_seen']} "
                f"skipped_stats={st['batches_skipped_stats']} "
                f"skipped_dict={st['batches_skipped_dict']} "
                f"code_domain_predicates={st['code_domain_predicates']} "
                f"rle_run_predicates={st['rle_run_predicates']}")
            if st["compressed_fallbacks"]:
                lines.append("compressed_fallbacks=" +
                             ",".join(f"{k}:{v}" for k, v in
                                      st["compressed_fallbacks"].items()))
            if st["host_fallback_reasons"]:
                lines.append("host_fallback_reason=" +
                             "; ".join(st["host_fallback_reasons"]))
            lines.append("phases: " + " ".join(
                f"{k}={v * 1e3:.3f}ms"
                for k, v in sorted(st["phases"].items())))
        return Result(["plan"], [np.array(lines, dtype=object)],
                      [None], [T.STRING])

    def _explain_execute(self, plan: ast.Plan) -> dict:
        """EXPLAIN ANALYZE's execution pass: run the query under a
        FORCED request trace (works with tracing_enabled=False) and
        capture engine-counter deltas — the same counters the dashboard
        reports, so the annotations are value-joinable against them."""
        import time as _time

        from snappydata_tpu.observability import tracing
        from snappydata_tpu.observability.metrics import global_registry
        from snappydata_tpu.ops import reduction

        reg = global_registry()
        c0 = reg.counters_snapshot()
        t0 = _time.perf_counter()
        with tracing.request_scope("EXPLAIN ANALYZE", user=self.user,
                                   kind="explain", force=True) as tr:
            result = self._governed_query("EXPLAIN ANALYZE",
                                          ast.Query(plan), ())
        elapsed = _time.perf_counter() - t0
        c1 = reg.counters_snapshot()

        def d(key: str) -> int:
            return c1.get(key, 0) - c0.get(key, 0)

        seen_total = d("column_batches_seen")
        skipped = d("column_batches_skipped")
        dict_skipped = d("batches_skipped_dict")
        # prefer THIS request's own bind-span evidence for the batch
        # numbers — counter deltas are process-global, so concurrent
        # traffic on a shared server would pollute them (the remaining
        # delta-sourced fields — dict-skip split, strategies, cache
        # verdicts — stay approximate under concurrency)
        fallback_reasons = []
        if tr is not None:
            bind_seen = bind_skipped = 0
            bound = False
            stack = [tr.root]
            while stack:
                sp = stack.pop()
                if sp.name == "host_fallback" and sp.attrs.get("reason"):
                    fallback_reasons.append(sp.attrs["reason"])
                if sp.name == "bind":
                    bound = True
                    bind_seen += sp.attrs.get("batches_seen", 0)
                    bind_skipped += sp.attrs.get("batches_skipped", 0)
                stack.extend(sp.children)
            if bound:
                seen_total, skipped = bind_seen, bind_skipped
        return {
            "rows_out": result.num_rows,
            "elapsed_s": elapsed,
            "trace_id": tr.trace_id if tr is not None else None,
            "phases": tr.phase_seconds() if tr is not None else {},
            "host_fallback_reasons": fallback_reasons,
            "plan_cache": "hit" if d("plan_cache_hits") else
                          ("miss" if d("plan_cache_misses") else "n/a"),
            "host_fallbacks": d("host_fallbacks"),
            "batches_seen": seen_total,
            "batches_skipped_stats": max(0, skipped - dict_skipped),
            "batches_skipped_dict": dict_skipped,
            "code_domain_predicates": d("code_domain_predicates"),
            "rle_run_predicates": d("rle_run_predicates"),
            "join_device_joins": d("join_device_joins"),
            "join_host_fallbacks": d("join_host_fallbacks"),
            "strategies": [s for s in reduction.REPORTED_STRATEGIES
                           if d(f"agg_strategy_{s}")],
            "compressed_fallbacks": {
                k[len("compressed_fallback_"):]: c1.get(k, 0) - c0.get(k, 0)
                for k in c1
                if k.startswith("compressed_fallback_")
                and c1.get(k, 0) - c0.get(k, 0)},
        }

    # -- tiled scans: table ≫ HBM (SURVEY §5 "long-context" analogue) ----

    def _tile_budget(self) -> int:
        """Effective byte budget for one scan tile. conf.scan_tile_bytes:
        >0 explicit, 0 auto (half the accelerator's reported memory when
        known), <0 disabled."""
        b = self.conf.scan_tile_bytes
        if b != 0:
            return max(0, b)
        import jax

        dev = jax.local_devices()[0]
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if limit:
            return int(limit) // 2
        if dev.platform != "cpu":
            # an accelerator that cannot say how much memory it has would
            # turn "table ≫ HBM" into an allocation failure mid-query
            raise RuntimeError(
                f"{dev.platform} device reports no bytes_limit in "
                f"memory_stats(); set scan_tile_bytes explicitly")
        return 0  # the CPU backend reports none: tiling off unless explicit

    def _tilable_agg_shape(self, plan: ast.Plan):
        """Shared shape probe for the tile pass and the governor's
        admission estimate: ([Sort|Limit]* [Filter(having)]
        Aggregate(column table [joined to build tables])), no
        subqueries/windows.  Joins are tilable on the PROBE side only:
        the leftmost leaf relation streams in windows while every build
        side binds fully each tile (its cached join artifact stays
        device-resident); right/full outer joins would re-emit their
        NULL-extended build rows per tile, so they never tile.
        Returns (outer, having, node, info, exprs, build_infos) or
        None."""
        outer: List[ast.Plan] = []
        node = plan
        while isinstance(node, (ast.Sort, ast.Limit)):
            outer.append(node)
            node = node.children()[0]
        having = None
        if isinstance(node, ast.Filter) and isinstance(node.child,
                                                       ast.Aggregate):
            having = node.condition
            node = node.child
        if not isinstance(node, ast.Aggregate):
            return None
        if node.grouping_sets:
            return None  # expands to a union at analysis; never tile raw

        rels: List[str] = []
        exprs: List[ast.Expr] = []
        join_hows: List[str] = []

        def rec(p):
            if isinstance(p, (ast.WindowedRelation, ast.WindowProject,
                              ast.Values, ast.Union,
                              ast.SetOp, ast.Distinct)):
                rels.append("__unsupported__")
                return
            if isinstance(p, ast.Join):
                join_hows.append(p.how)
            if isinstance(p, ast.UnresolvedRelation):
                rels.append(p.name)
            import dataclasses as _dc

            for fld in _dc.fields(p):
                v = getattr(p, fld.name)
                items = v if isinstance(v, tuple) else (v,)
                for x in items:
                    if isinstance(x, ast.Expr):
                        exprs.append(x)
            for k in p.children():
                rec(k)

        rec(node)
        if having is not None:
            exprs.append(having)
        if not rels or "__unsupported__" in rels:
            return None
        if any(h in ("right", "full") for h in join_hows):
            return None
        for e in exprs:
            for sub in ast.walk(e):
                if isinstance(sub, (ast.ScalarSubquery, ast.InSubquery,
                                    ast.ExistsSubquery, ast.WindowFunc)):
                    return None
        # probe = leftmost leaf (children() order is (left, right), so
        # DFS leaf order puts the probe chain's base table first)
        probe_name = rels[0]
        if sum(1 for r in rels if r.lower() == probe_name.lower()) > 1:
            return None  # self-join: a window would constrain BOTH sides
        info = self.catalog.lookup_table(probe_name)
        if info is None or not isinstance(info.data, ColumnTableData):
            return None
        build_infos = []
        for rn in rels[1:]:
            bi = self.catalog.lookup_table(rn)
            if bi is None or bi.data is info.data:
                return None
            build_infos.append(bi)
        return outer, having, node, info, exprs, build_infos

    @staticmethod
    def _decoded_col_width(f) -> Optional[int]:
        """Decoded device bytes per row for one column (value plate +
        null byte), or None for complex plates (which neither tile nor
        budget-estimate yet).  Single source of truth for the tile
        pass's unit math and the governor's build-side charge — the two
        must not drift, or admission desynchronizes from the budget."""
        if isinstance(f.dtype, (T.ArrayType, T.MapType, T.StructType)):
            return None
        per = 4 if f.dtype.name == "string" \
            else np.dtype(f.dtype.device_dtype()).itemsize
        return per + 1

    def _join_build_side_bytes(self, exprs, build_infos):
        """Decoded bytes a tilable join+aggregate's build sides pin on
        device across EVERY tile (0 for single-relation shapes), or
        None when a complex build plate makes the shape untilable.
        Shared by the tile pass and the governor's admission estimate —
        admitting the shape at one tile's cost without charging the
        device-resident builds would under-admit by whole tables."""
        if not build_infos:
            return 0
        from snappydata_tpu.storage import mvcc
        from snappydata_tpu.storage.table_store import RowTableData

        used = {c.name.lower() for e in exprs for c in ast.walk(e)
                if isinstance(c, ast.Col)}
        total = 0
        for bi in build_infos:
            rows = bi.data.count() if isinstance(bi.data, RowTableData) \
                else mvcc.snapshot_of(bi.data).total_rows()
            w = 1
            for f in bi.schema.fields:
                cw = self._decoded_col_width(f)
                if cw is None:
                    return None
                if f.name.lower() not in used:
                    continue
                w += cw
            total += rows * w
        return total

    def _maybe_tiled_aggregate(self, plan: ast.Plan,
                               user_params) -> Optional[Result]:
        """Execute an aggregate over ONE oversized column table as a
        streamed tile pass: bind `scan_tile_bytes`-sized windows of the
        batch axis through the SAME compiled partial program, then merge
        partials (avg = sum/count etc.) — the reference scans batch-at-a-
        time off disk for the same reason (ColumnFormatIterator read-ahead,
        core/.../columnar/impl/ColumnFormatIterator.scala:60-162); HBM
        never holds the whole table. Returns None → run untiled."""
        if getattr(self, "_in_tile", False) or user_params:
            return None
        budget = self._tile_budget()
        if budget <= 0:
            return None
        shaped = self._tilable_agg_shape(plan)
        if shaped is None:
            return None
        outer, having, node, info, exprs, build_infos = shaped
        data = info.data

        from snappydata_tpu.storage import mvcc
        from snappydata_tpu.storage.device import (scan_unit_count,
                                                   scan_window)

        # the tile pass pins ONE manifest across every window — read it
        # through the statement's ambient pin so a tiled aggregate and
        # an untiled one see the same epoch
        manifest = mvcc.snapshot_of(data)
        units = scan_unit_count(data, manifest)
        if units <= 1:
            return None
        used = {c.name.lower() for e in exprs for c in ast.walk(e)
                if isinstance(c, ast.Col)}
        # join build sides stay fully device-resident across every tile
        # (that is the point — the cached build artifact is reused); they
        # must fit the budget alongside one probe tile, and complex
        # plates don't tile on either side yet
        build_bytes = self._join_build_side_bytes(exprs, build_infos)
        if build_bytes is None or build_bytes >= budget:
            return None
        cap = data.capacity
        unit_bytes = cap  # shared validity mask
        for f in info.schema.fields:
            if f.name.lower() not in used:
                continue
            cw = self._decoded_col_width(f)
            if cw is None:
                return None  # complex plates don't tile yet
            unit_bytes += cap * cw
        if unit_bytes * units <= budget - build_bytes:
            return None
        tile_units = max(1, int((budget - build_bytes) // unit_bytes))
        if self.conf.batches_pow2_bucketing and tile_units > 1:
            tile_units = 1 << (tile_units.bit_length() - 1)

        from snappydata_tpu.engine.partial_agg import (
            NotDecomposableError, decompose_aggregate, ddl_type)
        from snappydata_tpu.sql.render import RenderError, render_expr, \
            render_plan

        try:
            partial_plan, merged_select, _, merge_having = \
                decompose_aggregate(node, having)
            partial_sql = render_plan(partial_plan)
        except (NotDecomposableError, RenderError):
            return None
        # outer ORDER BY must reference output columns by name/position
        out_names = [_expr_name(e).lower() for e in node.agg_exprs]
        for op in outer:
            if isinstance(op, ast.Sort):
                for o in op.orders:
                    tgt = o[0].child if isinstance(o[0], ast.Alias) else o[0]
                    if isinstance(tgt, ast.Col) and \
                            tgt.name.lower() in out_names:
                        continue
                    if isinstance(tgt, ast.Lit) and \
                            isinstance(tgt.value, int):
                        continue
                    return None

        from snappydata_tpu.observability.metrics import global_registry

        from snappydata_tpu.resource import check_current

        # Compile the partial program ONCE: the old loop re-entered
        # self.sql() per tile, re-parsing and re-analyzing partial_sql
        # every tile.  Tiles now share one compiled executable, and when
        # the partial's group-index space is provably tile-aligned
        # (direct dict/bool keys — data-independent cards) the per-tile
        # [G] partials tree-merge ON DEVICE, replacing the per-tile
        # device_get -> scratch-table insert -> second SQL round trip.
        tokenized = compiled = None
        params: Tuple = ()
        try:
            from snappydata_tpu.sql.optimizer import optimize as _optimize
            from snappydata_tpu.sql.parser import parse as _parse

            pplan = _optimize(_parse(partial_sql).plan, self.catalog)
            resolved_p, _ = self.analyzer.analyze_plan(pplan)
            if self.conf.tokenize and self.conf.plan_caching:
                tokenized, lit_params = tokenize_plan(resolved_p)
            else:
                from snappydata_tpu.sql.analyzer import \
                    assign_param_positions

                tokenized, lit_params = \
                    assign_param_positions(resolved_p, 0), ()
            params = tuple(lit_params)
            compiled = self.executor.compiled_partial(tokenized)
        except Exception:  # noqa: BLE001 — any analysis hiccup: SQL path
            tokenized = None

        merged: Optional[Result] = None
        pieces: List[Result] = []
        self._in_tile = True
        try:
            if compiled is not None and self.default_mesh is None \
                    and compiled.tile_merge is not None \
                    and compiled.tile_merge_ok():
                merged = self._tiled_device_pass(
                    compiled, params, data, manifest, units, tile_units)
            if merged is None:
                from snappydata_tpu.storage.prefetch import TilePrefetcher

                from snappydata_tpu.parallel.mesh import MeshContext

                # the worker warms through the consumer's mesh context
                # (ambient, else the session's cached one) so its cache
                # keys carry the token the consumer's binds will look up
                mesh_ctx = MeshContext.current() or (
                    self._mesh_context()
                    if self.default_mesh is not None else None)
                pf = TilePrefetcher.maybe(data, manifest, units,
                                          tile_units, mesh_ctx)
                try:
                    for lo in range(0, units, tile_units):
                        # tile boundary = cancellation point: CANCEL
                        # <id>, statement timeouts and broker kills land
                        # here, within one tile of the signal
                        check_current()
                        if pf is not None:
                            pf.await_window(lo)
                        with scan_window(data, lo,
                                         min(lo + tile_units, units),
                                         manifest, tile_units=tile_units):
                            if tokenized is not None:
                                pieces.append(self._execute_partial(
                                    tokenized, params))
                            else:  # analysis failed: per-tile SQL path
                                pieces.append(self.sql(partial_sql))
                        if pf is not None:
                            pf.advance(lo)
                        global_registry().inc("scan_tiles")
                finally:
                    if pf is not None:
                        pf.close()
                global_registry().inc("scan_tile_host_merges")
        finally:
            self._in_tile = False
        # steady-state tier enforcement: an out-of-core pass may leave a
        # tier over its knob (tier_device_bytes / tier_host_bytes).  The
        # statement's own read pin still covers the current epoch here,
        # so demote_device would pin-skip every entry it should drop —
        # defer the ladder walk to execute_statement's pin release.
        self._tier_enforce_pending = True
        if merged is not None:
            pieces = [merged]
        return self._merge_partial_pieces(pieces, node, merged_select,
                                          merge_having, outer)

    def _merge_partial_pieces(self, pieces, node, merged_select,
                              merge_having, outer) -> Result:
        """Partial [G] results → final aggregate: the finalize step the
        tiled scan AND the mesh shard_map lane share (avg = sum/count,
        HAVING over merged slots, outer sort/limit re-applied)."""
        from snappydata_tpu.engine.partial_agg import ddl_type
        from snappydata_tpu.sql.render import render_expr

        # merge in a pooled in-memory scratch session (never journaled/
        # persisted), keyed by the partial schema and truncated between
        # uses: the merge aggregate's compiled plan lives in the scratch
        # executor, so a throwaway session here re-paid its full XLA
        # compile (~100ms) on EVERY tiled statement — the pool is what
        # makes the out-of-core lane's steady state transfer-bound
        # instead of compile-bound
        from snappydata_tpu.catalog import Catalog as _Cat
        from snappydata_tpu.engine.result import to_host_domain

        first = pieces[0]
        fields_sql = ", ".join(
            f"{nm} {ddl_type(dt)}"
            for nm, dt in zip(first.names, first.dtypes))
        pool = self._tile_merge_pool.setdefault(fields_sql, [])
        try:
            scratch_sess = pool.pop()   # GIL-atomic claim
        except IndexError:
            scratch_sess = SnappySession(catalog=_Cat(), conf=self.conf)
            # the merge select must never re-enter the tile pass:
            # partials of a generic-key aggregate can exceed the (tiny)
            # tile budget, and a tiled merge would spawn scratch
            # sessions recursively — each level re-emitting ~G partial
            # rows, never converging
            scratch_sess._in_tile = True
            scratch_sess.sql(f"CREATE TABLE __tile_partials "
                             f"({fields_sql}) USING column")
        sdata = scratch_sess.catalog.describe("__tile_partials").data
        for piece in pieces:
            if piece.num_rows:
                # executor results carry exact decimals as scaled int64 —
                # unscale into the host float domain the scratch DOUBLE
                # columns expect (self.sql pieces arrive pre-finalized)
                piece = to_host_domain(piece)
                nmask = piece.nulls \
                    if any(m is not None for m in piece.nulls) else None
                sdata.insert_arrays(piece.columns, nulls=nmask)
        merge_items = ", ".join(render_expr(e) for e in merged_select)
        msql = f"SELECT {merge_items} FROM __tile_partials"
        if node.group_exprs:
            msql += " GROUP BY " + ", ".join(
                f"__g{gi}" for gi in range(len(node.group_exprs)))
        if merge_having is not None:
            msql += f" HAVING {render_expr(merge_having)}"
        result = scratch_sess.sql(msql)
        result.names = [_expr_name(e) for e in node.agg_exprs]
        # result columns are materialized arrays — safe to recycle the
        # scratch table underneath them (bounded pool: extras are
        # dropped, e.g. under concurrent tiled merges of one schema)
        sdata.truncate()
        if len(pool) < 4:
            pool.append(scratch_sess)
        from snappydata_tpu.cluster.distributed import _apply_outer

        return _apply_outer(result, outer, self)

    def _execute_partial(self, tokenized, params) -> Result:
        """One tile of the host-merge path through the pre-analyzed plan
        (mirrors _run_query_inner's mesh composition)."""
        if self.default_mesh is not None:
            from snappydata_tpu.parallel.mesh import MeshContext

            if MeshContext.current() is None:
                with self._mesh_context():
                    return self.executor.execute(tokenized, params)
        return self.executor.execute(tokenized, params)

    def _mesh_context(self):
        """The session's cached MeshContext for default_mesh.  Cached
        because the device cache keys on the context's process-unique
        token: a FRESH context per query (the old composition) rotated
        the token every statement, so every mesh query re-uploaded every
        plate — the mesh path could never hold a warm working set.

        The miss path re-checks under _mesh_resize_lock: a query thread
        racing resize_mesh() could otherwise observe the new
        default_mesh with the old _mesh_ctx and clobber the freshly
        migrated context with a throwaway token — orphaning every plate
        the rebalance just moved (review finding)."""
        from snappydata_tpu.parallel.mesh import MeshContext

        ctx = self._mesh_ctx
        if ctx is not None and ctx.mesh is self.default_mesh:
            return ctx
        with self._mesh_resize_lock:
            ctx = self._mesh_ctx
            if ctx is None or ctx.mesh is not self.default_mesh:
                ctx = MeshContext(self.default_mesh)
                self._mesh_ctx = ctx
            return ctx

    def resize_mesh(self, num_devices: Optional[int] = None,
                    devices=None) -> dict:
        """Live mesh resize — the in-process twin of the cluster layer's
        kill→rejoin bucket rebalance (PR 8 rejoin_server): the shard
        placement rebalances bucket ownership onto the new device set
        and every RESIDENT plate migrates device-to-device
        (storage/device.migrate_mesh_cache) instead of invalidating the
        world.  Queries already in flight keep their bound arrays on
        the old placement and stay value-correct; new statements bind
        under the new one.  Returns a summary for the caller/dashboard."""
        from snappydata_tpu.observability.metrics import global_registry
        from snappydata_tpu.parallel.mesh import MeshContext, data_mesh, \
            submesh
        from snappydata_tpu.storage.device import migrate_mesh_cache

        reg = global_registry()
        with self._mesh_resize_lock:
            old_ctx = self._mesh_ctx
            if old_ctx is None and self.default_mesh is not None:
                # construct directly — _mesh_context()'s miss path
                # re-acquires the NON-REENTRANT lock we already hold
                # (review finding: resize before any mesh query ran
                # self-deadlocked)
                old_ctx = MeshContext(self.default_mesh)
            new_mesh = submesh(devices) if devices is not None \
                else data_mesh(num_devices)
            placement = old_ctx.placement.rebalance(new_mesh.devices.size) \
                if old_ctx is not None else None
            new_ctx = MeshContext(new_mesh, placement=placement)
            moved_entries = moved_bytes = 0
            if old_ctx is not None:
                for ti in self.catalog.list_tables():
                    if hasattr(ti.data, "_device_cache"):
                        e, b = migrate_mesh_cache(ti.data, old_ctx.token,
                                                  new_ctx)
                        moved_entries += e
                        moved_bytes += b
            self.default_mesh = new_mesh
            self._mesh_ctx = new_ctx
            moved_buckets = placement.moved_from_previous \
                if placement is not None else 0
            reg.inc("mesh_rebalances")
            reg.inc("mesh_buckets_moved", moved_buckets)
            reg.inc("mesh_cache_moves", moved_entries)
            reg.inc("mesh_moved_bytes", moved_bytes)
            return {"num_devices": new_ctx.num_devices,
                    "buckets_moved": moved_buckets,
                    "cache_entries_moved": moved_entries,
                    "bytes_moved": moved_bytes,
                    "placement_generation":
                        new_ctx.placement.generation}

    def _maybe_mesh_aggregate(self, plan: ast.Plan,
                              user_params) -> Optional[Result]:
        """Mesh-sharded execution of a tilable aggregate shape: the
        compile-once PARTIAL program runs per-shard under shard_map with
        psum/pmin/pmax merges (engine/mesh_exec.py), then the shared
        scratch merge finalizes — Q1/Q6/Q3C and friends scan only their
        device's slice of the (still-encoded) plates.  Returns None to
        fall back to plain GSPMD jit over the sharded bind, counted
        mesh_fallback_<reason> so a shape that silently leaves the lane
        is diagnosable from the dashboard."""
        from snappydata_tpu.observability.metrics import global_registry
        from snappydata_tpu.parallel.mesh import MeshContext

        if getattr(self, "_in_tile", False):
            return None
        ctx = MeshContext.current()
        if ctx is None and self.default_mesh is None:
            return None
        from snappydata_tpu import config as _config

        if str(_config.global_properties().get(
                "mesh_shard_exec", "auto") or "auto").lower() \
                not in ("auto", "on"):
            return None
        reg = global_registry()
        if user_params:
            # `?` binds ride the GSPMD lane (still sharded): the merge
            # decomposition renders literal SQL, which params are not
            reg.inc("mesh_fallback_params")
            return None
        shaped = self._tilable_agg_shape(plan)
        if shaped is None:
            reg.inc("mesh_fallback_shape")
            return None
        outer, having, node, info, exprs, build_infos = shaped
        data = info.data

        from snappydata_tpu.storage import mvcc
        from snappydata_tpu.storage.device import scan_unit_count

        build_bytes = self._join_build_side_bytes(exprs, build_infos)
        if build_bytes is None:
            reg.inc("mesh_fallback_complex")
            return None
        budget = self._tile_budget()
        if budget > 0:
            # oversized tables keep the tiled streaming pass (mesh ×
            # tiling does not compose yet — per-device HBM is the same
            # HBM the tile budget protects)
            manifest = mvcc.snapshot_of(data)
            units = scan_unit_count(data, manifest)
            used = {c.name.lower() for e in exprs for c in ast.walk(e)
                    if isinstance(c, ast.Col)}
            unit_bytes = data.capacity
            for f in info.schema.fields:
                if f.name.lower() not in used:
                    continue
                cw = self._decoded_col_width(f)
                if cw is None:
                    reg.inc("mesh_fallback_complex")
                    return None
                unit_bytes += data.capacity * cw
            if units > 1 and build_bytes < budget \
                    and unit_bytes * units > budget - build_bytes:
                reg.inc("mesh_fallback_budget")
                return None

        from snappydata_tpu.engine.partial_agg import (
            NotDecomposableError, decompose_aggregate)
        from snappydata_tpu.sql.render import RenderError, render_plan

        try:
            partial_plan, merged_select, _, merge_having = \
                decompose_aggregate(node, having)
            partial_sql = render_plan(partial_plan)
        except (NotDecomposableError, RenderError):
            reg.inc("mesh_fallback_decompose")
            return None
        # outer ORDER BY must reference output columns by name/position
        # (same admission the tiled merge applies)
        out_names = [_expr_name(e).lower() for e in node.agg_exprs]
        for op in outer:
            if isinstance(op, ast.Sort):
                for o in op.orders:
                    tgt = o[0].child if isinstance(o[0], ast.Alias) \
                        else o[0]
                    if isinstance(tgt, ast.Col) and \
                            tgt.name.lower() in out_names:
                        continue
                    if isinstance(tgt, ast.Lit) and \
                            isinstance(tgt.value, int):
                        continue
                    reg.inc("mesh_fallback_outer_sort")
                    return None

        try:
            from snappydata_tpu.sql.optimizer import optimize as _optimize
            from snappydata_tpu.sql.parser import parse as _parse

            pplan = _optimize(_parse(partial_sql).plan, self.catalog)
            resolved_p, _ = self.analyzer.analyze_plan(pplan)
            if self.conf.tokenize and self.conf.plan_caching:
                tokenized, lit_params = tokenize_plan(resolved_p)
            else:
                from snappydata_tpu.sql.analyzer import \
                    assign_param_positions

                tokenized, lit_params = \
                    assign_param_positions(resolved_p, 0), ()
            params = tuple(lit_params)
            compiled = self.executor.compiled_partial(tokenized)
        except Exception:  # noqa: BLE001 — any analysis hiccup: GSPMD
            reg.inc("mesh_fallback_compile")
            return None
        if compiled is None or compiled.tile_merge is None \
                or not compiled.tile_merge_ok():
            reg.inc("mesh_fallback_merge_space")
            return None
        for oc in compiled.out_scope:
            # exact decimals ride scaled int64 on device; the scratch
            # merge finalizes through host DOUBLE columns and would
            # silently demote the exactness contract — GSPMD keeps the
            # int64 partial sums exact end to end, so that lane serves
            if oc.dtype is not None and oc.dtype.name == "decimal" \
                    and np.dtype(oc.dtype.device_dtype()).kind == "i":
                reg.inc("mesh_fallback_decimal_exact")
                return None

        from snappydata_tpu.engine import mesh_exec
        from snappydata_tpu.engine.exprs import CompileError

        try:
            if ctx is not None:
                ran = mesh_exec.run_partial(compiled, params, data, ctx,
                                            build_bytes)
            else:
                with self._mesh_context() as c2:
                    ran = mesh_exec.run_partial(compiled, params, data,
                                                c2, build_bytes)
        except CompileError:
            reg.inc("mesh_fallback_overflow")
            return None
        except Exception:  # noqa: BLE001 — lane must never break a query
            reg.inc("mesh_fallback_error")
            import traceback

            traceback.print_exc()
            return None
        if ran is None:
            return None
        host, tables = ran
        partial_res = compiled._assemble(host, tables)
        from snappydata_tpu.parallel.mesh import no_mesh

        # the finalize merges a [G]-row partial table — mask any ambient
        # mesh so it binds single-device instead of sharding G rows
        # over the whole device set
        with no_mesh():
            return self._merge_partial_pieces([partial_res], node,
                                              merged_select,
                                              merge_having, outer)

    def _tiled_device_pass(self, compiled, params, data, manifest, units,
                           tile_units) -> Optional[Result]:
        """Stream scan tiles through ONE compiled partial executable and
        tree-merge the per-tile [G] partial slots ON DEVICE (sum/min/max
        over the shared group-index space).  JAX's async dispatch
        double-buffers the pass: execute_raw never transfers, so the
        host binds/uploads tile t+1 while the device reduces tile t — a
        depth-2 throttle (block on tile t-1 after dispatching t) keeps
        at most two tiles' plates in flight.  Returns the merged partial
        Result, or None to fall back to the host-merge path (device
        lowering refused a bind, or the int64 decimal bound tripped —
        the exact host merge decides)."""
        import jax

        from snappydata_tpu.engine.executor import merge_tile_outs
        from snappydata_tpu.engine.exprs import CompileError
        from snappydata_tpu.observability.metrics import global_registry
        from snappydata_tpu.resource import check_current
        from snappydata_tpu.storage import device as device_mod

        from snappydata_tpu.storage.prefetch import TilePrefetcher

        reg = global_registry()
        tags = compiled.tile_merge["tags"]
        outs: List[tuple] = []
        from snappydata_tpu.parallel.mesh import MeshContext

        # out-of-core lane: a background worker warms window k+1's
        # plates while window k aggregates on device (tier_prefetch_depth
        # windows of look-ahead); the pass works identically without it.
        # The worker re-enters the consumer's AMBIENT mesh context (if
        # any) so its cache keys carry the same token.
        pf = TilePrefetcher.maybe(data, manifest, units, tile_units,
                                  MeshContext.current())
        try:
            try:
                for lo in range(0, units, tile_units):
                    check_current()  # tile boundary = cancellation point
                    if pf is not None:
                        pf.await_window(lo)
                    with device_mod.scan_window(
                            data, lo, min(lo + tile_units, units),
                            manifest, tile_units=tile_units):
                        outs.append(compiled.execute_raw(params))
                    if pf is not None:
                        pf.advance(lo)
                    # counts WORK, not queries: when this pass aborts
                    # (bind CompileError / decimal overflow) the host
                    # rerun counts its tiles again — the query genuinely
                    # scanned twice
                    reg.inc("scan_tiles")
                    if len(outs) >= 2:
                        prev = outs[-2]
                        try:
                            ready = prev[0].is_ready()
                        except AttributeError:  # older jax: assume done
                            ready = True
                        if not ready:
                            # this tile's bind/upload overlapped the
                            # previous tile's device compute — the
                            # pipelining evidence
                            reg.inc("scan_tile_prefetch_overlap")
                            jax.block_until_ready(prev)
            except CompileError:
                return None
        finally:
            if pf is not None:
                pf.close()
        if len(outs) > 1:
            reg.inc("scan_tile_device_merges", len(outs) - 1)
        while len(outs) > 1:  # pairwise tree merge, all on device
            nxt = [merge_tile_outs(outs[j], outs[j + 1], tags)
                   for j in range(0, len(outs) - 1, 2)]
            if len(outs) % 2:
                nxt.append(outs[-1])
            outs = nxt
        host = jax.device_get(outs[0])
        if bool(np.asarray(host[2])):
            return None  # overflow flagged: exact host path decides
        return compiled._assemble(host, [])

    def _gate_code_surface(self, what: str) -> None:
        """Code-execution surfaces (EXEC PYTHON, DEPLOY) on network-derived
        sessions require an AUTHENTICATED admin principal — an
        unauthenticated network caller must never reach them (advisor
        finding: REST/Flight ran as the admin superuser, an RCE)."""
        if getattr(self, "remote", False) and not (
                getattr(self, "authenticated", False)
                and self.user == "admin"):
            raise PermissionError(
                f"{what} is refused on network surfaces unless an "
                "authenticated admin principal is established "
                "(configure auth_tokens and pass the admin token)")

    # -- DEPLOY JAR/PACKAGE (ref: DeployCommand/UnDeployCommand/
    # ListPackageJarsCommand, core/.../execution/ddl.scala; the reference
    # resolves maven coordinates and installs jars on every member's
    # classloader — here artifacts are Python wheels/zips/modules added to
    # the interpreter path, copied into the disk store so they survive
    # restarts) ----------------------------------------------------------

    def _deployed(self) -> Dict[str, dict]:
        if not hasattr(self.catalog, "_deployed"):
            self.catalog._deployed = {}
        return self.catalog._deployed

    def _deploy(self, stmt: ast.DeployStmt) -> Result:
        import os
        import shutil

        name = stmt.name.lower()
        paths = [p.strip() for p in stmt.coordinates.split(",")
                 if p.strip()]
        if not paths:
            raise ValueError("DEPLOY: empty artifact list")
        resolved = []
        for p in paths:
            if not os.path.exists(p):
                hint = ("" if os.sep in p else
                        " (this build has no network egress: DEPLOY takes "
                        "local wheel/zip/.py paths, not remote "
                        "maven/pypi coordinates)")
                raise ValueError(f"DEPLOY: artifact not found: {p!r}{hint}")
            resolved.append(os.path.abspath(p))
        stored = resolved
        if self.disk_store is not None:
            root = os.path.join(self.disk_store.path, "deploy", name)
            os.makedirs(root, exist_ok=True)
            stored = []
            bases = [os.path.basename(p) for p in resolved]
            for i, p in enumerate(resolved):
                base = bases[i]
                if bases.count(base) > 1:  # '/a/util.py, /b/util.py'
                    base = f"{i}_{base}"   # must not silently overwrite
                d = os.path.abspath(os.path.join(root, base))
                if d != p:  # recovery replay re-deploys the stored copy
                    if os.path.isdir(p):
                        shutil.copytree(p, d, dirs_exist_ok=True)
                    else:
                        shutil.copy2(p, d)
                stored.append(d)
        deployed = self._deployed()
        old = deployed.pop(name, None)
        deployed[name] = {"kind": stmt.kind, "files": list(stored),
                          "coordinates": stmt.coordinates}
        if old is not None:
            self._sys_path_sync()
        for f in stored:
            self._sys_path_add(f)
        self.catalog.generation += 1
        return _status()

    def _undeploy(self, name: str) -> Result:
        import os
        import shutil

        key = name.lower()
        deployed = self._deployed()
        if key not in deployed:
            raise ValueError(f"nothing deployed as {name!r}")
        deployed.pop(key)
        self._sys_path_sync()
        if self.disk_store is not None:
            shutil.rmtree(
                os.path.join(self.disk_store.path, "deploy", key),
                ignore_errors=True)
        self.catalog.generation += 1
        return _status()

    def _list_deployed(self, kind: str) -> Result:
        want = "package" if kind == "packages" else "jar"
        rows = [(n, e["coordinates"], e["kind"] == "package")
                for n, e in sorted(self._deployed().items())
                if e["kind"] == want]
        return Result(
            ["name", "coordinates", "isPackage"],
            [np.array([r[0] for r in rows], dtype=object),
             np.array([r[1] for r in rows], dtype=object),
             np.array([r[2] for r in rows], dtype=bool)],
            [None, None, None], [T.STRING, T.STRING, T.BOOLEAN])

    @staticmethod
    def _import_root(path: str) -> str:
        """sys.path entry that makes `path` importable: zips/wheels import
        via zipimport directly, a module file imports via its parent dir."""
        import os

        low = path.lower()
        if os.path.isdir(path) or low.endswith(
                (".whl", ".zip", ".egg", ".jar")):
            return path
        return os.path.dirname(path)

    def _sys_path_add(self, f: str) -> None:
        import importlib
        import sys as _sys

        root = self._import_root(f)
        if root not in _sys.path:
            _sys.path.append(root)
        if not hasattr(self.catalog, "_deploy_roots"):
            self.catalog._deploy_roots = set()
        self.catalog._deploy_roots.add(root)
        importlib.invalidate_caches()

    def _sys_path_sync(self) -> None:
        """Drop sys.path entries no longer referenced by any deployed
        artifact (two artifacts may share an import root — only remove
        roots with zero remaining references)."""
        import sys as _sys

        live = {self._import_root(f)
                for e in self._deployed().values() for f in e["files"]}
        added = getattr(self.catalog, "_deploy_roots", set())
        for root in added - live:
            while root in _sys.path:
                _sys.path.remove(root)
        self.catalog._deploy_roots = added & live

    def _exec_code(self, code: str) -> Result:
        """EXEC PYTHON: per-session interpreter namespace persisting across
        statements (ref: RemoteInterpreterStateHolder holds a Scala REPL
        per connection on the lead). The namespace binds `session` and
        `np`; set `result` to a Result or list of rows to return data,
        otherwise stdout is returned."""
        import contextlib
        import io

        if not hasattr(self, "_interp_ns"):
            self._interp_ns = {"session": self, "np": np}
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            exec(code, self._interp_ns)  # noqa: S102 — interpreter feature
        out = self._interp_ns.pop("result", None)
        if isinstance(out, Result):
            return out
        if isinstance(out, (list, tuple)) and out:
            rows = [r if isinstance(r, (list, tuple)) else (r,)
                    for r in out]
            width = len(rows[0])
            if any(len(r) != width for r in rows):
                raise ValueError("EXEC result rows have uneven arity")
            cols = list(zip(*rows))
            return Result(
                [f"c{i}" for i in range(len(cols))],
                [np.array(c, dtype=object) for c in cols],
                [None] * len(cols), [T.STRING] * len(cols))
        text = buf.getvalue()
        return Result(["output"], [np.array([text], dtype=object)], [None],
                      [T.STRING])

    def _run_query(self, plan: ast.Plan, user_params=()) -> Result:
        if getattr(self.catalog, "_functions", None):
            # expose this catalog's SQL-registered functions to the
            # analyzer / compilers / host evaluator for this execution
            from snappydata_tpu.sql import udf as _udf

            with _udf.using(self.catalog):
                return self._run_query_inner(plan, user_params)
        return self._run_query_inner(plan, user_params)

    def _run_query_inner(self, plan: ast.Plan, user_params=()) -> Result:
        from snappydata_tpu.observability import tracing

        if getattr(self.catalog, "_sample_maintainers", None):
            self._refresh_samples()
        plan = self._rewrite_stream_windows(plan)
        tiled = self._maybe_tiled_aggregate(plan, user_params)
        if tiled is not None:
            return tiled
        meshed = self._maybe_mesh_aggregate(plan, user_params)
        if meshed is not None:
            return meshed
        with tracing.span("optimize"):
            plan = self._decorrelate(plan)
            plan = self._rewrite_subqueries(plan, user_params)
            from snappydata_tpu.sql.optimizer import optimize

            plan = optimize(plan, self.catalog)
        with tracing.span("analyze"):
            resolved, _ = self.analyzer.analyze_plan(plan)
            if self.conf.tokenize and self.conf.plan_caching:
                tokenized, lit_params = tokenize_plan(resolved)
            else:
                from snappydata_tpu.sql.analyzer import \
                    assign_param_positions

                tokenized, lit_params = \
                    assign_param_positions(resolved, 0), ()
        params = tuple(lit_params) + tuple(user_params)
        if self.default_mesh is not None:
            from snappydata_tpu.parallel.mesh import MeshContext

            if MeshContext.current() is None:
                # mesh × cluster composition: a data server that owns a
                # local device submesh runs EVERY query GSPMD-sharded
                # over it, so distributed execution is scatter →
                # per-server SPMD → merge (ref: embedded executors per
                # store JVM, ExecutorInitiator.scala:45-105); the
                # context is session-cached so the device cache stays
                # warm across statements (see _mesh_context)
                with self._mesh_context():
                    return self.executor.execute(tokenized, params)
        return self.executor.execute(tokenized, params)

    # ------------------------------------------------------------------
    # Programmatic API (ref SnappySession.createTable/insert/put/...)
    # ------------------------------------------------------------------

    def create_table(self, name: str, schema, provider: str = "column",
                     options: Optional[Dict[str, str]] = None,
                     if_not_exists: bool = False,
                     key_columns: Sequence[str] = ()):
        if not isinstance(schema, T.Schema):
            schema = T.Schema([T.Field(n, dt) for n, dt in schema])
        return self.catalog.create_table(name, schema, provider,
                                         options or {}, if_not_exists,
                                         key_columns)

    def table_rows(self, name: str) -> Result:
        return self.sql(f"SELECT * FROM {name}")

    def query_schema(self, sql_text: str) -> T.Schema:
        """Output schema of a query WITHOUT executing it (ref:
        CachedDataFrame exposes the analyzed schema; Flight
        get_flight_info uses this instead of running the query)."""
        stmt = parse(sql_text)
        if not isinstance(stmt, ast.Query):
            return T.Schema([T.Field("status", T.STRING)])
        plan = self._rewrite_stream_windows(stmt.plan)
        plan = self._decorrelate(plan)

        def sub_placeholder(e: ast.Expr) -> ast.Expr:
            # type-only placeholders: subqueries must not EXECUTE here
            if isinstance(e, ast.ScalarSubquery):
                sub_resolved, _ = self.analyzer.analyze_plan(
                    self._decorrelate(e.plan))
                dt = _output_schema(sub_resolved).fields[0].dtype
                return ast.Lit(None, dt)
            if isinstance(e, (ast.InSubquery, ast.ExistsSubquery)):
                return ast.Lit(True, T.BOOLEAN)
            return e

        plan = ast.transform_plan_exprs(plan, sub_placeholder)
        resolved, _ = self.analyzer.analyze_plan(plan)
        return _output_schema(resolved)

    def _journal_then(self, info, kind: str, arrays, nulls, apply_fn,
                      sync_force: bool = False):
        """WAL-then-apply under the mutation lock, then ack after the
        covering group fsync (no-op without a store). The journal append
        only BUFFERS the framed record; while apply_fn encodes/cuts
        batches the background flusher can already be fsyncing the group
        — encode CPU work overlaps disk latency — and wal_sync releases
        the ack once the fsync covers this record's seq. `sync_force`
        makes the ack wait for the fsync even under
        wal_fsync_mode=interval — network surfaces (Flight do_put,
        replica promotion) set it, scoped to exactly THIS record's seq
        so one put never waits on (or fails for) other sessions'
        records."""
        from snappydata_tpu.observability import tracing
        from snappydata_tpu.views import matview as _mv

        def applied():
            # span `apply` (attr `rows`): the in-memory apply — encode,
            # cut batches, fold views; a row-buffer roll-over shows as
            # its `rollover` child (storage/table_store.py)
            with tracing.span("apply", rows=len(arrays[0])):
                return apply_fn()

        ds = self.disk_store
        if ds is None:
            with _mv.managed_base_write():
                return applied()
        from snappydata_tpu.reliability import current_stmt_id

        sid = current_stmt_id()
        from snappydata_tpu.storage import mvcc

        t_lock = time.perf_counter()
        with ds.mutation_lock:
            _note_lock_wait(t_lock)
            seq = ds.wal_append(info.name, kind, arrays=arrays,
                                nulls=nulls,
                                extra={"stmt_id": sid} if sid else None)
            with mvcc.commit_scope(seq), _mv.managed_base_write():
                # locklint: callback-under-lock journal->apply under ONE
                # mutation hold IS the WAL invariant (on-disk log >=
                # in-memory state); apply_fn is the statement's own
                # apply, not a foreign registry callback
                out = applied()
        ds.wal_sync(seq, force=sync_force)
        return out

    def insert(self, table: str, *rows) -> int:
        self._require(table, "insert")
        info = self.catalog.describe(table)
        self._reject_matview_write(info)
        arrays, nulls = _rows_to_arrays(info.schema, rows)
        if isinstance(info.data, RowTableData):
            raw = _restore_none_arrays(arrays, nulls)
            return self._journal_then(
                info, "insert", raw, None,
                lambda: self._fold_views(info, raw, None,
                                         info.data.insert_arrays(raw)))
        return self._journal_then(
            info, "insert", arrays, nulls,
            lambda: self._fold_views(
                info, arrays, nulls,
                info.data.insert_arrays(arrays, nulls=nulls)))

    def insert_arrays(self, table: str, arrays: Sequence[np.ndarray]) -> int:
        self._require(table, "insert")
        info = self.catalog.describe(table)
        self._reject_matview_write(info)
        arrays = [np.asarray(a) for a in arrays]
        return self._journal_then(
            info, "insert", arrays, None,
            lambda: self._fold_views(info, arrays, None,
                                     info.data.insert_arrays(arrays)))

    def put(self, table: str, *rows) -> int:
        self._require(table, "insert")
        self._require(table, "update")
        info = self.catalog.describe(table)
        arrays, _ = _rows_to_arrays(info.schema, rows)
        return self.put_arrays(table, arrays)

    def put_arrays(self, table: str, arrays: Sequence[np.ndarray]) -> int:
        self._require(table, "insert")
        self._require(table, "update")
        info = self.catalog.describe(table)
        self._reject_matview_write(info)
        arrays = [np.asarray(a) for a in arrays]

        def apply():
            if isinstance(info.data, RowTableData):
                out = info.data.put_arrays(arrays)
                self._fold_row_put(info, arrays)
                return out
            return self._column_put(info, arrays)

        return self._journal_then(info, "put", arrays, None, apply)

    def delete_keys(self, table: str, key_columns: Sequence[str],
                    key_arrays: Sequence[np.ndarray]) -> int:
        """Delete rows whose key tuple appears in `key_arrays` (CDC delete
        path; WAL kind 'delete_keys')."""
        self._require(table, "delete")
        info = self.catalog.describe(table)
        self._reject_matview_write(info)
        key_arrays = [np.asarray(a) for a in key_arrays]
        keys = {tuple(c[i] for c in key_arrays)
                for i in range(len(key_arrays[0]))}

        def pred(cols):
            stacked = [np.asarray(cols[k]) for k in key_columns]
            n = stacked[0].shape[0]
            hits = np.zeros(n, dtype=bool)
            for r in range(n):
                if tuple(c[r] for c in stacked) in keys:
                    hits[r] = True
            return hits

        from snappydata_tpu.views import matview as _mv

        def apply():
            wrapped, captured = _mv.wrap_delete_predicate(
                self.catalog, info.name, pred)
            out = info.data.delete(wrapped)
            if captured:
                _mv.fold_deleted(self.catalog, info.name, captured)
            return out

        if self.disk_store is None:
            return apply()
        from snappydata_tpu.reliability import current_stmt_id

        extra = {"key_columns": list(key_columns)}
        if current_stmt_id():
            extra["stmt_id"] = current_stmt_id()
        from snappydata_tpu.storage import mvcc

        from snappydata_tpu.observability import tracing

        with self.disk_store.mutation_lock:
            seq = self.disk_store.wal_append(
                info.name, "delete_keys", arrays=key_arrays, extra=extra)
            with mvcc.commit_scope(seq), tracing.span("apply") as sp:
                out = apply()
                sp.set("rows", int(out or 0))
        self.disk_store.wal_sync(seq)   # ack after the covering fsync
        return out

    def update(self, table: str, where_sql: str, new_values: Dict[str, Any]
               ) -> int:
        """Programmatic UPDATE — routed through sql() so it is journaled
        like any statement (review finding: it used to bypass the WAL)."""
        sets = ", ".join(f"{k} = {_sql_literal(v)}"
                         for k, v in new_values.items())
        text = f"UPDATE {table} SET {sets}" + \
            (f" WHERE {where_sql}" if where_sql else "")
        return int(self.sql(text).rows()[0][0])

    def delete(self, table: str, where_sql: str) -> int:
        text = f"DELETE FROM {table}" + \
            (f" WHERE {where_sql}" if where_sql else "")
        return int(self.sql(text).rows()[0][0])

    def get(self, table: str, key: tuple):
        """Point lookup on a row table's primary key — never enters the
        query engine (ref: ExecutionEngineArbiter fast path)."""
        self._require(table, "select")
        info = self.catalog.describe(table)
        if not isinstance(info.data, RowTableData):
            raise ValueError("get() requires a row table with a primary key")
        return info.data.get(key)

    def stop(self):
        self.executor.clear_cache()

    def clear_plan_cache(self):
        self.executor.clear_cache()

    # ------------------------------------------------------------------
    # DML internals
    # ------------------------------------------------------------------

    def _alter_table(self, stmt: ast.AlterTable) -> Result:
        """ALTER TABLE ADD/DROP COLUMN (ref SnappySession.alterTable:1628,
        SnappyDDLParser.scala:697-713). Supported for both row and column
        tables; existing rows read the added column as NULL."""
        info = self.catalog.describe(stmt.table)
        if info.provider == "sample":
            raise ValueError("ALTER TABLE is not supported on sample tables")
        if info.options.get("materialized_view"):
            raise ValueError(
                f"{stmt.table} is a materialized view; its schema follows "
                "the view definition")
        from snappydata_tpu.views import matview as _mview

        # schema change invalidates the compiled maintenance programs:
        # dependent views re-derive them at the stale-exit refresh
        for mv in _mview.matviews_on(self.catalog, info.name):
            mv.mark_stale("alter table")
            mv.invalidate_scratch()
        if stmt.add:
            cd = stmt.column
            if any(f.name.lower() == cd.name.lower()
                   for f in info.schema.fields):
                raise ValueError(f"column already exists: {cd.name}")
            info.data.add_column(T.Field(cd.name, cd.dtype, cd.nullable))
        else:
            cname = stmt.name
            info.schema.index(cname)  # validates existence
            low = cname.lower()
            if low in info.partition_by:
                raise ValueError(
                    f"cannot drop partitioning column {cname}")
            if low in info.key_columns:
                raise ValueError(f"cannot drop primary key column {cname}")
            for iname, (t, icols) in getattr(self.catalog, "_indexes",
                                             {}).items():
                if t == info.name and low in icols:
                    raise ValueError(
                        f"column {cname} is referenced by index {iname}")
            info.data.drop_column(cname)
        info.schema = info.data.schema
        self.catalog.generation += 1
        if self.disk_store is not None:
            self.disk_store.save_catalog(self.catalog)
        return _status()

    def _create_table(self, stmt: ast.CreateTable) -> Result:
        if not stmt.name.split(".")[-1].startswith("__"):
            # '__' column names are RESERVED for internal columns (hidden
            # from SELECT */DESCRIBE, auto-filled on INSERT) — a user
            # column there would silently disappear. Internal scratch
            # tables (themselves '__'-named) may use them freely.
            for c in stmt.columns:
                if c.name.startswith("__"):
                    raise ValueError(
                        f"column names starting with '__' are reserved "
                        f"({c.name!r})")
        if stmt.provider == "sample":
            return self._create_sample_table(stmt)
        if stmt.stream:
            return self._create_stream_table(stmt)
        if stmt.as_select is not None:
            if stmt.if_not_exists and \
                    self.catalog.lookup_table(stmt.name) is not None:
                return _status()  # no-op, do NOT re-append (review finding)
            from snappydata_tpu.engine.result import to_host_domain

            # CTAS reads like a query: referenced matviews must re-merge
            # their maintained state first or the snapshot copies stale
            # pre-fold backing rows (review finding)
            self._sync_referenced_matviews(stmt.as_select)
            # CTAS ingests into host plates: exact-decimal columns must
            # leave the scaled-int domain first (else 24.05 stores 2405)
            result = to_host_domain(self._run_query(stmt.as_select))
            if not stmt.name.split(".")[-1].startswith("__"):
                for n in result.names:
                    if n.startswith("__"):
                        raise ValueError(
                            f"column names starting with '__' are "
                            f"reserved ({n!r}); alias the CTAS output")
            schema = T.Schema([
                T.Field(n, dt) for n, dt in zip(result.names, result.dtypes)])
            info = self.catalog.create_table(stmt.name, schema, stmt.provider,
                                             stmt.options, stmt.if_not_exists)
            if result.num_rows:
                arrays, nulls = _result_to_arrays(result, schema)
                if isinstance(info.data, RowTableData):
                    info.data.insert_arrays(arrays)
                else:
                    info.data.insert_arrays(arrays, nulls=nulls)
            return _status()
        schema = T.Schema([T.Field(c.name, c.dtype, c.nullable)
                           for c in stmt.columns])
        keys = tuple(c.name for c in stmt.columns if c.primary_key)
        self.catalog.create_table(stmt.name, schema, stmt.provider,
                                  stmt.options, stmt.if_not_exists,
                                  key_columns=keys)
        return _status()

    # ------------------------------------------------------------------
    # authorization (GRANT/REVOKE; ref grantRevokeExternal + LDAP auth —
    # session-user based here, "admin" is superuser)
    # ------------------------------------------------------------------

    def _grants(self) -> Dict:
        if not hasattr(self.catalog, "_grants"):
            self.catalog._grants = {}
        return self.catalog._grants

    def _has_priv(self, table: str, priv: str) -> bool:
        if self.user == "admin":
            return True
        key = (self.user, _table_key(self.catalog, table))
        return priv in self._grants().get(key, set())

    def _require(self, table: str, priv: str) -> None:
        if not self._has_priv(table, priv):
            raise PermissionError(
                f"user {self.user!r} lacks {priv.upper()} on {table}")

    def _authorize(self, stmt: ast.Statement) -> None:
        if self.user == "admin":
            return
        if isinstance(stmt, ast.Query):
            for t in _referenced_tables(stmt.plan):
                self._require(t, "select")
            return
        if isinstance(stmt, ast.ExplainStmt):
            for t in _referenced_tables(stmt.query):
                self._require(t, "select")
            return
        if isinstance(stmt, ast.InsertInto):
            self._require(stmt.table, "insert")
            if stmt.put:
                self._require(stmt.table, "update")  # upsert updates rows
            if stmt.overwrite:
                self._require(stmt.table, "delete")  # overwrite truncates
            for t in _referenced_tables(stmt.source):
                self._require(t, "select")
            return
        if isinstance(stmt, ast.UpdateStmt):
            self._require(stmt.table, "update")
            for e in [stmt.where] + [x for _, x in stmt.assignments]:
                if e is not None:
                    for t in _expr_subquery_tables(e):
                        self._require(t, "select")
            return
        if isinstance(stmt, ast.DeleteStmt):
            self._require(stmt.table, "delete")
            if stmt.where is not None:
                for t in _expr_subquery_tables(stmt.where):
                    self._require(t, "select")
            return
        if isinstance(stmt, (ast.CreateTable, ast.DropTable,
                             ast.TruncateTable, ast.AlterTable,
                             ast.CreatePolicy,
                             ast.DropPolicy, ast.CreateIndex,
                             ast.DropIndex, ast.ExecCode, ast.SetConf,
                             ast.CreateView, ast.DropView,
                             ast.CreateMaterializedView,
                             ast.DropMaterializedView,
                             ast.RefreshMaterializedView,
                             ast.CreateFunction, ast.DropFunction,
                             ast.DeployStmt, ast.UndeployStmt)):
            raise PermissionError(
                f"user {self.user!r} may not run "
                f"{type(stmt).__name__} (DDL is admin-only)")
        # SHOW/DESCRIBE stay open (metadata reads)

    # (row-level policy injection lives in the analyzer's relation
    # resolution so views and every other path are covered)

    def _decorrelate(self, plan: ast.Plan) -> ast.Plan:
        """Rewrite correlated [NOT] EXISTS filters into semi/anti joins —
        the classic decorrelation for the TPC-H Q4/Q21/Q22 pattern
        (ref: Catalyst RewritePredicateSubquery does the same):

          Filter(child, EXISTS(SELECT ... FROM inner WHERE inner.a =
          outer.b AND <inner-only preds>))
            → Join(child, Filter(inner, preds), 'semi', a = b)

        Only the single-block shape with conjunctive predicates is
        handled; anything else keeps its (clear) unsupported error."""

        def split_correlation(subplan, outer_names, want_select=False):
            """If subplan is SELECT ... FROM <rel chain> WHERE <conj>,
            split conjuncts into correlation equalities (inner_col =
            outer_col) and inner-only predicates. With `want_select`, also
            return the projected select expressions (for IN rewrites)."""
            node = subplan
            select_exprs = None
            # strip projection-only tops (SELECT 1 / SELECT cols)
            while isinstance(node, (ast.Project, ast.SubqueryAlias,
                                    ast.Distinct)):
                if isinstance(node, ast.Project) and select_exprs is None:
                    select_exprs = node.exprs
                node = node.children()[0]
            if not isinstance(node, ast.Filter):
                return None
            inner_rel = node.child
            conjuncts: List[ast.Expr] = []

            def flat(e):
                if isinstance(e, ast.BinOp) and e.op == "and":
                    flat(e.left)
                    flat(e.right)
                else:
                    conjuncts.append(e)

            flat(node.condition)

            inner_cols = _relation_columns(inner_rel, self.catalog)

            def col_side(c):
                """'outer' if the Col can only resolve in the outer scope,
                'inner' if in the subquery's own relations."""
                if c.qualifier:
                    # a qualifier names its scope unambiguously (covers
                    # self-join correlation t2.a = t.a on the same table)
                    return "inner" if c.qualifier.lower() in inner_cols[1] \
                        else "outer"
                return "inner" if c.name.lower() in inner_cols[0] \
                    else "outer"

            corr = []
            inner_only = []
            corr_residual = []
            for c in conjuncts:
                if isinstance(c, ast.BinOp) and c.op == "=" \
                        and isinstance(c.left, ast.Col) \
                        and isinstance(c.right, ast.Col):
                    sides = (col_side(c.left), col_side(c.right))
                    if sides == ("inner", "outer"):
                        corr.append((c.right, c.left))
                        continue
                    if sides == ("outer", "inner"):
                        corr.append((c.left, c.right))
                        continue
                has_outer = any(
                    isinstance(x, ast.Col) and col_side(x) == "outer"
                    for x in ast.walk(c))
                if has_outer:
                    # non-equi correlation (Q21's l2.suppkey <> l1.suppkey)
                    # rides as a residual on the decorrelated join
                    corr_residual.append(c)
                    continue
                inner_only.append(c)
            if not corr and not corr_residual:
                return None   # uncorrelated: not this rewrite's job
            if want_select:
                return inner_rel, corr, inner_only, select_exprs, \
                    corr_residual
            return inner_rel, corr, inner_only, corr_residual

        def split_scalar_agg(subplan):
            """Correlated scalar aggregate subquery → pieces for the
            aggregate-then-join rewrite (TPC-H Q2/Q17/Q20 shape):

              (SELECT <expr over AGG(inner cols)> FROM inner
               WHERE inner.k = outer.k AND <inner preds>)

            Returns (inner_rel, corr, inner_only, select_expr) or None."""
            node = subplan
            while isinstance(node, ast.SubqueryAlias):
                node = node.child
            if not isinstance(node, ast.Aggregate) or node.group_exprs \
                    or len(node.agg_exprs) != 1:
                return None
            sel = node.agg_exprs[0]
            if isinstance(sel, ast.Alias):
                sel = sel.child
            aggs = [x for x in ast.walk(sel)
                    if isinstance(x, ast.Func) and x.name in ast.AGG_FUNCS]
            # empty-group semantics: sum/avg/min/max yield NULL (the inner
            # join's dropped row ≡ comparison-with-NULL = false); count
            # yields 0, which needs a LEFT join + coalesce(__sv, 0) so
            # outer rows with no inner match still compare against 0
            if not aggs or any(a.name not in ("sum", "avg", "min", "max",
                                              "count") for a in aggs):
                return None
            needs_left = any(a.name == "count" for a in aggs)
            inner = node.child
            if not isinstance(inner, ast.Filter):
                return None
            got = split_correlation(inner, None)
            if got is None or got[3] or not got[1]:
                return None  # non-equi correlation: can't group-then-join
            inner_rel, corr, inner_only, _res = got
            # every column in the select must belong to the inner scope
            inner_cols = _relation_columns(inner_rel, self.catalog)
            for x in ast.walk(sel):
                if isinstance(x, ast.Col):
                    in_inner = (x.qualifier.lower() in inner_cols[1]
                                if x.qualifier
                                else x.name.lower() in inner_cols[0])
                    if not in_inner:
                        return None
            return inner_rel, corr, inner_only, sel, needs_left

        import itertools as _it

        sq_counter = _it.count()

        def _and_all(exprs):
            cond = exprs[0]
            for x in exprs[1:]:
                cond = ast.BinOp("and", cond, x)
            return cond

        def rewrite_filter(p: ast.Plan) -> ast.Plan:
            if not isinstance(p, ast.Filter):
                return p
            conjuncts: List[ast.Expr] = []

            def flat(e):
                if isinstance(e, ast.BinOp) and e.op == "and":
                    flat(e.left)
                    flat(e.right)
                else:
                    conjuncts.append(e)

            flat(p.condition)
            child = p.child
            rest: List[ast.Expr] = []    # untouched conjuncts (stay BELOW)
            post: List[ast.Expr] = []    # rewritten comparisons (go ABOVE)
            join_specs: List[tuple] = []  # (inner_rel, how, cond)
            changed = False
            for c in conjuncts:
                negated = False
                e = c
                if isinstance(e, ast.UnaryOp) and e.op == "not" \
                        and isinstance(e.child, ast.ExistsSubquery):
                    negated, e = True, e.child
                if isinstance(e, ast.ExistsSubquery):
                    got = split_correlation(e.plan, None)
                    if got is not None:
                        inner_rel, corr, inner_only, corr_res = got
                        if inner_only:
                            inner_rel = ast.Filter(inner_rel,
                                                   _and_all(inner_only))
                        join_cond = _and_all(
                            [ast.BinOp("=", oc, ic) for oc, ic in corr]
                            + corr_res)
                        join_specs.append(
                            (inner_rel, "anti" if negated else "semi",
                             join_cond))
                        changed = True
                        continue
                # correlated scalar aggregate in a comparison →
                # aggregate-then-join (ref: Catalyst's scalar-subquery
                # decorrelation; unlocks TPC-H Q2/Q17/Q20)
                if isinstance(e, ast.BinOp) and e.op in (
                        "<", "<=", ">", ">=", "=", "<>", "!="):
                    done = False
                    for side in ("left", "right"):
                        side_expr = getattr(e, side)
                        # the subquery may sit INSIDE arithmetic on the
                        # comparison side (TPC-DS q6's `price > 1.2 *
                        # (SELECT avg ...)`) — find exactly one and
                        # splice the decorrelated value back in place
                        subs = [x for x in ast.walk(side_expr)
                                if isinstance(x, ast.ScalarSubquery)]
                        if len(subs) != 1:
                            continue
                        sub = subs[0]
                        got = split_scalar_agg(sub.plan)
                        if got is None:
                            continue
                        inner_rel, corr, inner_only, sel, needs_left = got
                        if inner_only:
                            inner_rel = ast.Filter(inner_rel,
                                                   _and_all(inner_only))
                        alias = f"__sq{next(sq_counter)}"
                        group = tuple(ic for _oc, ic in corr)
                        # count's empty group is 0, not NULL: LEFT join
                        # keeps unmatched outer rows, and each COUNT term
                        # is coalesced to 0 INDIVIDUALLY — a whole-expr
                        # coalesce would turn count(*)+sum(x) (NULL for an
                        # empty group: 0 + NULL) or count(*)+1 (1) into a
                        # bare 0 (advisor r3 finding). sum/avg/min/max
                        # terms stay NULL so mixed expressions keep
                        # single-node semantics; all-non-count selects
                        # keep the inner join (their NULL compares false,
                        # dropping the row).
                        slot_funcs: List[ast.Func] = []

                        def _slot(f: ast.Func) -> int:
                            for k, g in enumerate(slot_funcs):
                                if g == f:
                                    return k
                            slot_funcs.append(f)
                            return len(slot_funcs) - 1

                        def _externalize(x: ast.Expr) -> ast.Expr:
                            if isinstance(x, ast.Func) and \
                                    x.name in ast.AGG_FUNCS:
                                ref: ast.Expr = ast.Col(
                                    f"__sv{_slot(x)}", alias)
                                if needs_left and x.name == "count":
                                    ref = ast.Func(
                                        "coalesce",
                                        (ref, ast.Lit(0, T.LONG)))
                                return ref
                            return x.map_children(_externalize)

                        sv = _externalize(sel)

                        def _splice(x: ast.Expr) -> ast.Expr:
                            if x == sub:
                                return sv
                            return x.map_children(_splice)

                        sv = _splice(side_expr)
                        aggs = tuple(
                            ast.Alias(ic, f"__ck{j}")
                            for j, (_oc, ic) in enumerate(corr)
                        ) + tuple(ast.Alias(f, f"__sv{k}")
                                  for k, f in enumerate(slot_funcs))
                        sq = ast.SubqueryAlias(
                            ast.Aggregate(inner_rel, group, aggs), alias)
                        join_cond = _and_all([
                            ast.BinOp("=", oc,
                                      ast.Col(f"__ck{j}", alias))
                            for j, (oc, _ic) in enumerate(corr)])
                        join_specs.append(
                            (sq, "left" if needs_left else "inner",
                             join_cond))
                        import dataclasses as _dc2

                        post.append(_dc2.replace(e, **{side: sv}))
                        changed = done = True
                        break
                    if done:
                        continue
                # correlated IN → semi join on (value, correlation keys)
                if isinstance(e, ast.InSubquery) and not e.negated:
                    got = split_correlation(e.plan, None, want_select=True)
                    if got is not None and got[3] and len(got[3]) == 1:
                        inner_rel, corr, inner_only, sel_exprs, corr_res \
                            = got
                        sel = sel_exprs[0]
                        if isinstance(sel, ast.Alias):
                            sel = sel.child
                        if inner_only:
                            inner_rel = ast.Filter(inner_rel,
                                                   _and_all(inner_only))
                        join_cond = _and_all(
                            [ast.BinOp("=", e.child, sel)] +
                            [ast.BinOp("=", oc, ic) for oc, ic in corr]
                            + corr_res)
                        join_specs.append((inner_rel, "semi", join_cond))
                        changed = True
                        continue
                rest.append(c)
            if not changed:
                return p
            # decorrelation joins stack ABOVE the remaining filter so the
            # optimizer still sees the original Filter-over-FROM-chain and
            # can order it by size (burying a comma-joined FROM under a
            # semi join used to leave it an unordered cross product)
            base = ast.Filter(child, _and_all(rest)) if rest else child
            for inner_rel, how2, cond2 in join_specs:
                base = ast.Join(base, inner_rel, how2, cond2)
            if post:
                base = ast.Filter(base, _and_all(post))
            return base

        def walk_plans(p: ast.Plan) -> ast.Plan:
            import dataclasses as _dc

            if isinstance(p, ast.Filter):
                p = rewrite_filter(p)
            kids = p.children()
            if not kids:
                return p
            if isinstance(p, (ast.Join, ast.Union, ast.SetOp)):
                return _dc.replace(p, left=walk_plans(p.left),
                                   right=walk_plans(p.right))
            return _dc.replace(p, child=walk_plans(kids[0]))

        return walk_plans(plan)

    def _rewrite_subqueries(self, plan: ast.Plan, user_params) -> ast.Plan:
        """Pre-evaluate UNCORRELATED subqueries and substitute literals
        (scalar → Lit, IN → InList, EXISTS → bool). Correlated subqueries
        were already decorrelated into joins by _decorrelate; any shape
        it cannot handle surfaces a clear unsupported error here."""
        return ast.transform_plan_exprs(plan, self._subquery_fn(user_params))

    def _subquery_fn(self, user_params):
        def fn(e: ast.Expr) -> ast.Expr:
            if isinstance(e, ast.ScalarSubquery):
                res = self._run_subquery(e.plan, user_params)
                if res.num_rows == 0:
                    return ast.Lit(None, res.dtypes[0])
                if res.num_rows > 1:
                    raise AnalysisError(
                        "scalar subquery returned more than one row")
                v = res.columns[0][0]
                if res.nulls[0] is not None and res.nulls[0][0]:
                    return ast.Lit(None, res.dtypes[0])
                return ast.Lit(v.item() if hasattr(v, "item") else v,
                               res.dtypes[0])
            if isinstance(e, ast.InSubquery):
                res = self._run_subquery(e.plan, user_params)
                dtype = res.dtypes[0]
                has_null = res.nulls[0] is not None and bool(
                    res.nulls[0].any())
                if e.negated and has_null:
                    # SQL: x NOT IN (set containing NULL) is never TRUE
                    return ast.Lit(False, T.BOOLEAN)
                vals = tuple(
                    ast.Lit(v.item() if hasattr(v, "item") else v, dtype)
                    for i, v in enumerate(res.columns[0])
                    if not (res.nulls[0] is not None and res.nulls[0][i]))
                if not vals:
                    return ast.Lit(e.negated, T.BOOLEAN)
                return ast.InList(e.child, vals, negated=e.negated)
            if isinstance(e, ast.ExistsSubquery):
                res = self._run_subquery(ast.Limit(e.plan, 1), user_params)
                return ast.Lit(res.num_rows > 0, T.BOOLEAN)
            return e

        return fn

    def _run_subquery(self, subplan: ast.Plan, user_params) -> Result:
        from snappydata_tpu.engine.result import finalize_decimals
        from snappydata_tpu.sql.analyzer import AnalysisError as AErr

        try:
            # decode exact decimals BEFORE literal substitution: a raw
            # scaled-int column value (2405 for 24.05) substituted as a
            # Lit would be re-scaled by the literal emitter
            return finalize_decimals(self._run_query(subplan, user_params))
        except AErr as e:
            if "cannot resolve column" in str(e):
                raise AnalysisError(
                    f"correlated subqueries are not supported yet ({e})")
            raise

    # ------------------------------------------------------------------
    # AQP (plug-in surface; ref SnappyContextFunctions :42-78)
    # ------------------------------------------------------------------

    def _create_sample_table(self, stmt: ast.CreateTable) -> Result:
        """CREATE SAMPLE TABLE s ON base OPTIONS (qcs 'a,b', buckets...,
        reservoir_size 'n') — stratified reservoir over the base table,
        schema = base schema + snappy_sampler_weight."""
        from snappydata_tpu.aqp.sampling import (
            RESERVOIR_WEIGHT_COLUMN, STRATUM_ID_COLUMN,
            SampleTableMaintainer, StratifiedReservoir)

        opts = {k.lower(): str(v) for k, v in stmt.options.items()}
        base_name = opts.get("basetable") or opts.get("base_table")
        if not base_name:
            raise ValueError("sample table requires OPTIONS (baseTable ...)")
        if stmt.if_not_exists and \
                self.catalog.lookup_table(stmt.name) is not None:
            return _status()  # don't double-register the maintainer
        base = self.catalog.describe(base_name)
        schema = T.Schema(list(base.schema.fields)
                          + [T.Field(RESERVOIR_WEIGHT_COLUMN, T.DOUBLE,
                                     False),
                             T.Field(STRATUM_ID_COLUMN, T.LONG, False)])
        info = self.catalog.create_table(stmt.name, schema, "sample",
                                         stmt.options, stmt.if_not_exists)
        self.register_sample(info)
        return _status()

    def _create_stream_table(self, stmt: ast.CreateTable) -> Result:
        """CREATE STREAM TABLE name (schema) USING file_stream|memory_stream
        OPTIONS (directory '...', interval '...', conflation 'true',
        key_columns '...') — a queryable table continuously fed by a
        micro-batch source (ref: stream DDL SnappyDDLParser.scala:716 and
        the stream sources in core/.../sql/streaming; exactly-once via the
        sink state table)."""
        from snappydata_tpu.streaming import FileSource, MemorySource
        from snappydata_tpu.streaming.query import StreamingQuery

        opts = {k.lower(): str(v) for k, v in stmt.options.items()}
        # hidden arrival-time column powers DStream-style WINDOW queries
        # (ref: WindowLogicalPlan); '__'-prefixed fields are invisible to
        # SELECT * / DESCRIBE and auto-stamped on INSERT
        schema = T.Schema([T.Field(c.name, c.dtype, c.nullable)
                           for c in stmt.columns]
                          + [T.Field("__arrival_ts", T.TIMESTAMP, False)])
        # key columns: inline PRIMARY KEY or the keyColumns relation
        # option (ref: the sink reads keyColumns off the table options,
        # SnappySinkCallback.scala:68-80 — exactly-once replay dedup
        # REQUIRES them)
        keys = tuple(c.name for c in stmt.columns if c.primary_key)
        opt_keys = opts.get("key_columns") or opts.get("keycolumns")
        if not keys and opt_keys:
            keys = tuple(c.strip() for c in opt_keys.split(",")
                         if c.strip())
        provider = stmt.provider if stmt.provider in ("file_stream",
                                                      "memory_stream",
                                                      "kafka_stream",
                                                      "socket_stream") \
            else opts.get("provider", "memory_stream")
        if not hasattr(self.catalog, "_streams"):
            self.catalog._streams = {}
        tname = stmt.name.lower()
        if tname in self.catalog._streams:
            if stmt.if_not_exists:
                return _status()  # keep the running query; don't leak one
            raise ValueError(f"stream table already exists: {stmt.name}")
        # validate options BEFORE creating storage (a failed CREATE must
        # not leave an orphan table — review finding)
        interval = float(opts.get("interval", "0.1"))
        if provider == "file_stream":
            directory = opts.get("directory")
            if not directory:
                raise ValueError(
                    "file_stream requires OPTIONS (directory '...')")
            source = FileSource(directory, schema.names())
        elif provider == "socket_stream":
            from snappydata_tpu.streaming.query import SocketSource

            host = opts.get("hostname") or opts.get("host")
            port = opts.get("port")
            if not host or not port:
                raise ValueError("socket_stream requires OPTIONS "
                                 "(hostname '...', port '...')")
            source = SocketSource(
                host, int(port),
                [n for n in schema.names() if not n.startswith("__")])
        elif provider == "kafka_stream":
            from snappydata_tpu.streaming.kafka import (KafkaSource,
                                                        resolve_broker)

            topic = opts.get("topic") or opts.get("subscribe")
            brokers = opts.get("brokers") or opts.get(
                "kafka.bootstrap.servers")
            if not topic or not brokers:
                raise ValueError("kafka_stream requires OPTIONS "
                                 "(topic '...', brokers '...')")
            source = KafkaSource(
                self, f"stream_{tname}", resolve_broker(brokers), topic,
                [n for n in schema.names() if not n.startswith("__")],
                max_records_per_batch=int(
                    opts.get("maxrecordsperbatch", "10000")))
        else:
            source = MemorySource()
        # backing storage: a normal column table holding the stream's
        # materialized contents (queryable like any table); if_not_exists
        # also covers recovery, where the table was already restored
        self.catalog.create_table(stmt.name, schema, "column", stmt.options,
                                  if_not_exists=True, key_columns=keys)
        query = StreamingQuery(
            self, f"stream_{tname}", source, stmt.name,
            conflation=opts.get("conflation", "false").lower() == "true",
            interval_s=interval, stamp_arrivals=True)
        self.catalog._streams[tname] = query
        query.start()
        return _status()

    def streaming_queries(self) -> List[dict]:
        """Progress of every registered stream (ref:
        StreamingQueryManager.active + the structured-streaming UI)."""
        return [q.progress() for q in
                getattr(self.catalog, "_streams", {}).values()]

    def stream_source(self, table: str):
        """The MemorySource feeding a memory_stream table (programmatic
        batch injection)."""
        q = getattr(self.catalog, "_streams", {}).get(table.lower())
        if q is None:
            raise ValueError(f"not a stream table: {table}")
        return q.source

    def register_sample(self, info) -> None:
        """(Re)wire a sample table's reservoir + base-table feed — also
        called on recovery (review finding: samples froze after restart)."""
        from snappydata_tpu.aqp.sampling import (SampleTableMaintainer,
                                                 StratifiedReservoir)

        opts = info.options
        base = self.catalog.describe(opts.get("basetable")
                                     or opts.get("base_table"))
        from snappydata_tpu.aqp.sampling import STRATUM_ID_COLUMN

        # migration: sample tables persisted before error estimation
        # lack the hidden stratum-id column; the sample's contents are
        # rebuilt from the reservoir on refresh anyway, so adding the
        # field is complete
        if all(f.name.lower() != STRATUM_ID_COLUMN
               for f in info.schema.fields):
            info.data.add_column(T.Field(STRATUM_ID_COLUMN, T.LONG, False))
            info.schema = info.data.schema   # analyzer resolves from info
        qcs = [c.strip().lower() for c in opts.get("qcs", "").split(",")
               if c.strip()]
        reservoir = StratifiedReservoir(
            [base.schema.index(c) for c in qcs], len(base.schema),
            reservoir_size=int(opts.get("reservoir_size", 50)),
            seed=int(opts.get("seed", 0)))
        maintainer = SampleTableMaintainer(info, base, reservoir)
        base.data.on_insert.append(maintainer.on_insert)
        if not hasattr(self.catalog, "_sample_maintainers"):
            self.catalog._sample_maintainers = {}
        self.catalog._sample_maintainers[info.name] = maintainer
        # seed with existing base content
        from snappydata_tpu.engine.hosteval import _eval_rel

        cols, _, _, _, n = _eval_rel(
            ast.Relation(base.name, base.schema), (), self.executor)
        if n:
            reservoir.observe(cols)

    def _refresh_samples(self) -> None:
        for m in getattr(self.catalog, "_sample_maintainers", {}).values():
            m.refresh()

    def approx_sql(self, sql_text: str, params: Sequence[Any] = ()) -> Result:
        """Run an aggregate approximately over registered sample tables
        (ref: AQP error-bounded rewrite, docs/aqp.md:43)."""
        from snappydata_tpu.aqp.rewrite import approx_rewrite

        stmt = parse(sql_text)
        if not isinstance(stmt, ast.Query):
            raise ValueError("approx_sql expects a query")
        self._authorize(stmt)  # same privileges as the exact query
        from snappydata_tpu.aqp.error_estimation import (
            execute_error_query, query_has_error_surface)

        if query_has_error_surface(stmt):
            return execute_error_query(self, stmt, tuple(params))
        rewritten = approx_rewrite(stmt.plan, self.catalog)
        if rewritten is None:
            return self._run_query(stmt.plan, tuple(params))
        self._refresh_samples()
        return self._run_query(rewritten, tuple(params))

    def create_topk(self, name: str, base_table: str, key_column: str,
                    k: int = 50, time_column: Optional[str] = None,
                    bucket_seconds: int = 60) -> None:
        """Register a TopK structure fed by base-table inserts (ref:
        SnappyContextFunctions.createTopK :42). With `time_column`, a
        Hokusai-style time-bucketed TopK supporting start/end-time
        queries (ref TopK trait time axis, TopK.scala:23)."""
        from snappydata_tpu.aqp.sketches import TimeDecayedTopK, TopKSummary

        self._require(base_table, "select")
        base = self.catalog.describe(base_table)
        ci = base.schema.index(key_column)
        ti = base.schema.index(time_column) if time_column else None
        topk = TimeDecayedTopK(k=k, bucket_seconds=bucket_seconds) \
            if time_column else TopKSummary(k=k)
        if not hasattr(self.catalog, "_topks"):
            self.catalog._topks = {}
            self.catalog._topk_defs = {}
        self.catalog._topks[name.lower()] = topk
        self.catalog._topk_defs[name.lower()] = {
            "base_table": base.name, "key_column": key_column.lower(),
            "k": k, "time_column": time_column.lower() if time_column
            else None, "bucket_seconds": bucket_seconds}
        if self.disk_store is not None:
            self.disk_store.save_catalog(self.catalog)

        def feed(arrays, nulls=None, _ci=ci, _ti=ti, _t=topk):
            if _ti is None:
                _t.observe(np.asarray(arrays[_ci]))
            else:
                _t.observe(np.asarray(arrays[_ci]),
                           np.asarray(arrays[_ti], dtype=np.float64))

        base.data.on_insert.append(feed)
        from snappydata_tpu.engine.hosteval import _eval_rel

        cols, _, _, _, n = _eval_rel(
            ast.Relation(base.name, base.schema), (), self.executor)
        if n:
            if ti is None:
                topk.observe(cols[ci])
            else:
                topk.observe(cols[ci],
                             np.asarray(cols[ti], dtype=np.float64))

    def query_topk(self, name: str, n: Optional[int] = None,
                   start_time: Optional[float] = None,
                   end_time: Optional[float] = None) -> Result:
        topk = getattr(self.catalog, "_topks", {}).get(name.lower())
        if topk is None:
            raise ValueError(f"no such TopK: {name}")
        defs = getattr(self.catalog, "_topk_defs", {}).get(name.lower())
        if defs is not None:
            self._require(defs["base_table"], "select")
        from snappydata_tpu.aqp.sketches import TimeDecayedTopK

        if isinstance(topk, TimeDecayedTopK):
            items = topk.top(n, start_time=start_time, end_time=end_time)
        else:
            items = topk.top(n)
        return Result(
            ["key", "estimated_count"],
            [np.array([k for k, _ in items], dtype=object),
             np.array([c for _, c in items], dtype=np.int64)],
            [None, None], [T.STRING, T.LONG])

    def _insert(self, stmt: ast.InsertInto, user_params) -> int:
        info = self.catalog.describe(stmt.table)
        self._reject_matview_write(info)
        target_schema = info.schema
        if not isinstance(stmt.source, ast.Values):
            # INSERT INTO t SELECT ... FROM some_matview must read a
            # synced view
            self._sync_referenced_matviews(stmt.source)
        if isinstance(stmt.source, ast.Values):
            resolved, _ = self.analyzer.analyze_plan(stmt.source)
            src = hosteval.eval_values(resolved, user_params)
        else:
            from snappydata_tpu.engine.result import to_host_domain

            # INSERT..SELECT: same host-domain requirement as CTAS
            src = to_host_domain(self._run_query(stmt.source, user_params))
        if stmt.columns:
            name_to_src = {c.lower(): i for i, c in enumerate(stmt.columns)}
            if len(stmt.columns) != len(src.columns):
                raise ValueError("INSERT column count mismatch")
        else:
            visible = [f for f in target_schema.fields
                       if not f.name.startswith("__")]
            if len(src.columns) not in (len(target_schema), len(visible)):
                raise ValueError(
                    f"INSERT arity mismatch: {len(src.columns)} vs "
                    f"{len(visible)}")
            # internal columns (e.g. a stream table's __arrival_ts) are
            # invisible to plain INSERTs and auto-stamped below
            base = target_schema.fields \
                if len(src.columns) == len(target_schema) else visible
            name_to_src = {f.name.lower(): i for i, f in enumerate(base)}
        arrays = []
        null_masks = []
        n = src.num_rows
        import time as _time

        now_us = int(_time.time() * 1e6)
        for f in target_schema.fields:
            i = name_to_src.get(f.name.lower())
            if i is None and f.name == "__arrival_ts":
                arrays.append(np.full(n, now_us, dtype=np.int64))
                null_masks.append(np.zeros(n, dtype=np.bool_))
                continue
            if i is None:  # unmentioned column → all NULL
                arrays.append(np.zeros(n, dtype=f.dtype.np_dtype)
                              if f.dtype.name != "string"
                              else np.full(n, None, dtype=object))
                null_masks.append(np.ones(n, dtype=np.bool_))
                continue
            arr, nmask = _coerce(src.columns[i], src.nulls[i], f.dtype)
            arrays.append(arr)
            null_masks.append(nmask)
        from snappydata_tpu.views import matview as _mv

        if stmt.overwrite:
            info.data.truncate()
            _mv.on_truncate(self.catalog, info.name,
                            self.disk_store.current_wal_seq()
                            if self.disk_store else 0)
        if stmt.put:
            if isinstance(info.data, RowTableData):
                raw = _restore_none_arrays(arrays, null_masks)
                out = info.data.put_arrays(raw)
                self._fold_row_put(info, raw)
                return out
            return self._column_put(info, arrays, null_masks)
        if isinstance(info.data, RowTableData):
            raw = _restore_none_arrays(arrays, null_masks)
            out = info.data.insert_arrays(raw)
            _mv.fold_ingest(self.catalog, info.name, raw, None)
            return out
        out = info.data.insert_arrays(arrays, nulls=null_masks)
        _mv.fold_ingest(self.catalog, info.name, arrays, null_masks)
        return out

    def _column_put(self, info, arrays, nulls=None) -> int:
        """PUT INTO a column table: upsert join on key_columns (ref:
        ColumnPutIntoExec = update-matched + insert-rest)."""
        from snappydata_tpu.views import matview as _mv

        keys = info.key_columns
        if not keys:
            out = info.data.insert_arrays(arrays)
            _mv.fold_ingest(self.catalog, info.name, arrays, nulls)
            return out
        key_idx = [info.schema.index(k) for k in keys]
        incoming = {tuple(np.asarray(arrays[i])[r] for i in key_idx): r
                    for r in range(len(np.asarray(arrays[0])))}

        def pred(cols):
            stacked = np.stack([_key_col(cols, info, i) for i in key_idx])
            hits = np.zeros(stacked.shape[1], dtype=bool)
            for r, key in enumerate(zip(*stacked)):
                hits[r] = tuple(key) in incoming
            return hits

        def _key_col(cols, info, i):
            return np.asarray(cols[info.schema.fields[i].name])

        # delete matched, then insert everything (same visible effect as
        # update+insert under the single-statement snapshot).  Dependent
        # views see the put as subtract-matched + fold-incoming — exact
        # for sum/count families, stale for min/max (via fold_deleted)
        wrapped, captured = _mv.wrap_delete_predicate(
            self.catalog, info.name, pred)
        info.data.delete(wrapped)
        if captured:
            _mv.fold_deleted(self.catalog, info.name, captured)
        out = info.data.insert_arrays(arrays)
        _mv.fold_ingest(self.catalog, info.name, arrays, nulls)
        return out

    def _resolve_where(self, table_info, where, user_params):
        from snappydata_tpu.sql.analyzer import (Scope, ScopeEntry,
                                                 fold_constants)

        # UPDATE/DELETE WHERE may carry subqueries: pre-evaluate them like
        # queries do (review finding: they used to leak to host eval)
        where = ast.transform(where, self._subquery_fn(user_params))
        alias = table_info.name.split(".")[-1]
        scope = Scope([ScopeEntry(alias, f.name, f.dtype, f.nullable)
                       for f in table_info.schema.fields])
        resolved = self.analyzer.resolve_expr(where, scope)
        return fold_constants(resolved)

    @staticmethod
    def _assign_expr_params(e: ast.Expr, counter: list) -> ast.Expr:
        """Positional '?' assignment for mutation statements: the query
        path does this in assign_param_positions, but UPDATE/DELETE
        expressions are resolved standalone — without this every '?'
        kept pos=-1 and evaluated to params[-1] (round-4 finding: a
        two-param DELETE bound both markers to the LAST value)."""
        def rec(node: ast.Expr) -> ast.Expr:
            if isinstance(node, ast.Param) and node.pos < 0:
                p = ast.Param(counter[0], node.dtype)
                counter[0] += 1
                return p
            return node.map_children(rec)

        return rec(e)

    def _update(self, stmt: ast.UpdateStmt, user_params) -> int:
        info = self.catalog.describe(stmt.table)
        self._reject_matview_write(info)
        self._sync_expr_matviews(
            [stmt.where] + [e for _, e in stmt.assignments])
        # '?' positions follow SQL text order: SET expressions, then WHERE
        counter = [0]
        assignments = [(name, self._assign_expr_params(e, counter))
                       for name, e in stmt.assignments]
        raw_where = self._assign_expr_params(stmt.where, counter) \
            if stmt.where is not None else None
        where = self._resolve_where(info, raw_where, user_params) \
            if raw_where is not None else ast.Lit(True, T.BOOLEAN)
        assigns = {}
        for name, e in assignments:
            resolved = self._resolve_where(info, e, user_params)
            assigns[name] = self._host_value_fn(info, resolved, user_params)
        pred = self._host_pred_fn(info, where, user_params)
        touched = info.data.update(pred, assigns)
        if touched:
            from snappydata_tpu.views import matview as _mv

            # the old image is gone by the time we see the update: any
            # dependent view re-aggregates at its next read
            _mv.mark_stale(self.catalog, info.name, "update")
        return touched

    def _delete(self, stmt: ast.DeleteStmt, user_params) -> int:
        info = self.catalog.describe(stmt.table)
        self._reject_matview_write(info)
        self._sync_expr_matviews([stmt.where])
        raw_where = self._assign_expr_params(stmt.where, [0]) \
            if stmt.where is not None else None
        where = self._resolve_where(info, raw_where, user_params) \
            if raw_where is not None else ast.Lit(True, T.BOOLEAN)
        pred = self._host_pred_fn(info, where, user_params)
        from snappydata_tpu.views import matview as _mv

        wrapped, captured = _mv.wrap_delete_predicate(
            self.catalog, info.name, pred)
        out = info.data.delete(wrapped)
        if captured:
            _mv.fold_deleted(self.catalog, info.name, captured)
        return out

    def _host_pred_fn(self, info, resolved_where, user_params):
        names = info.schema.names()

        def pred(cols: Dict[str, np.ndarray]) -> np.ndarray:
            arrays = _ColsByIndex(cols, names)  # decode only touched cols
            n = arrays.num_rows(resolved_where)
            v, nl = hosteval.eval_expr(resolved_where, arrays,
                                       _NoneSeq(), tuple(user_params), n)
            out = np.broadcast_to(v, (n,)).astype(bool)
            if nl is not None:
                out = out & ~np.broadcast_to(nl, (n,))
            return out

        return pred

    def _host_value_fn(self, info, resolved_expr, user_params):
        names = info.schema.names()

        def value(cols: Dict[str, np.ndarray]):
            if isinstance(resolved_expr, ast.Lit):
                return resolved_expr.value  # incl. None = SQL NULL
            arrays = _ColsByIndex(cols, names)
            n = arrays.num_rows(resolved_expr)
            v, _ = hosteval.eval_expr(resolved_expr, arrays,
                                      _NoneSeq(), tuple(user_params), n)
            return v if np.shape(v) == () else np.broadcast_to(v, (n,))

        return value


class _ColsByIndex:
    """Ordinal-indexed view over a {name: values} mapping that fetches (and
    therefore decodes, when backed by LazyBatchColumns) only the columns an
    expression actually touches (review finding)."""

    def __init__(self, cols, names):
        self._cols = cols
        self._names = names

    def __getitem__(self, i: int) -> np.ndarray:
        return np.asarray(self._cols[self._names[i]])

    def __len__(self):
        return len(self._names)

    def num_rows(self, expr: ast.Expr) -> int:
        for node in ast.walk(expr):
            if isinstance(node, ast.Col):
                return int(self[node.index].shape[0])
        # no column refs (e.g. WHERE 1=1): any column's length works
        return int(self[0].shape[0]) if self._names else 0


class _NoneSeq:
    def __getitem__(self, i):
        return None


def _expand_privs(privs) -> set:
    out = set()
    for p in privs:
        if p == "all":
            out.update({"select", "insert", "update", "delete"})
        else:
            out.add(p)
    return out


def _table_key(catalog, table: str) -> str:
    from snappydata_tpu.catalog.catalog import _norm

    return _norm(table)


def _expr_subquery_tables(e: ast.Expr):
    out = []
    for node in ast.walk(e):
        if isinstance(node, (ast.ScalarSubquery, ast.InSubquery,
                             ast.ExistsSubquery)):
            out.extend(_referenced_tables(node.plan))
    return out


def _output_schema(plan: ast.Plan) -> T.Schema:
    """Output fields of a RESOLVED plan (schema without execution)."""
    from snappydata_tpu.sql.analyzer import _expr_name as _en
    from snappydata_tpu.sql.analyzer import expr_type as _et

    if isinstance(plan, (ast.Project, ast.WindowProject)):
        return T.Schema([T.Field(_en(e), _et(e) or T.STRING)
                         for e in plan.exprs])
    if isinstance(plan, ast.Aggregate):
        return T.Schema([T.Field(_en(e), _et(e) or T.DOUBLE)
                         for e in plan.agg_exprs])
    if isinstance(plan, (ast.Sort, ast.Limit, ast.Distinct, ast.Filter,
                         ast.SubqueryAlias)):
        return _output_schema(plan.children()[0])
    if isinstance(plan, ast.Relation):
        return plan.schema
    if isinstance(plan, ast.Join):
        if plan.how in ("semi", "anti"):
            return _output_schema(plan.left)
        left = _output_schema(plan.left)
        right = _output_schema(plan.right)
        return T.Schema(list(left.fields) + list(right.fields))
    if isinstance(plan, (ast.Union, ast.SetOp)):
        return _output_schema(plan.left)
    if isinstance(plan, ast.Values):
        row = plan.rows[0]
        return T.Schema([T.Field(f"c{i}", _et(e) or T.STRING)
                         for i, e in enumerate(row)])
    raise ValueError(f"no output schema for {type(plan).__name__}")


def _referenced_tables(plan: ast.Plan):
    out = []

    def rec(p):
        if isinstance(p, ast.UnresolvedRelation):
            out.append(p.name)
        for e in _plan_exprs(p):
            for node in ast.walk(e):
                if isinstance(node, (ast.ScalarSubquery, ast.InSubquery,
                                     ast.ExistsSubquery)):
                    rec(node.plan)
        for k in p.children():
            rec(k)

    def _plan_exprs(p):
        if isinstance(p, ast.Filter):
            return [p.condition]
        if isinstance(p, (ast.Project, ast.WindowProject)):
            return list(p.exprs)
        if isinstance(p, ast.Aggregate):
            return list(p.group_exprs) + list(p.agg_exprs)
        if isinstance(p, ast.Join) and p.condition is not None:
            return [p.condition]
        if isinstance(p, ast.Values):
            return [e for row in p.rows for e in row]
        if isinstance(p, ast.Sort):
            return [e for e, *_ in p.orders]
        return []

    rec(plan)
    return out


def _restore_none_arrays(arrays, nulls):
    """Row tables store python values: rebuild object arrays with None
    where the null mask is set (numeric NULL fidelity)."""
    out = []
    for a, m in zip(arrays, nulls or [None] * len(arrays)):
        if m is not None and np.asarray(m).any():
            obj = np.asarray(a, dtype=object).copy()
            obj[np.asarray(m)] = None
            out.append(obj)
        else:
            out.append(a)
    return out


def _status() -> Result:
    return empty_result(["status"], [T.STRING])


def _count_result(n: int) -> Result:
    return Result(["count"], [np.array([n], dtype=np.int64)], [None], [T.LONG])


def _row_count(info) -> int:
    if isinstance(info.data, RowTableData):
        return info.data.count()
    return info.data.snapshot().total_rows()


def _rows_to_arrays(schema: T.Schema, rows):
    if len(rows) == 1 and isinstance(rows[0], (list, tuple)) and rows[0] \
            and isinstance(rows[0][0], (list, tuple)):
        rows = rows[0]
    arrays, nulls = [], []
    for i, f in enumerate(schema.fields):
        vals = [r[i] for r in rows]
        nmask = np.array([v is None for v in vals])
        if f.dtype.name in ("string", "array", "map"):
            arr = np.empty(len(vals), dtype=object)
            for j, v in enumerate(vals):
                arr[j] = v
            arrays.append(arr)
        else:
            arrays.append(np.array(
                [0 if v is None else v for v in vals], dtype=f.dtype.np_dtype))
        nulls.append(nmask if nmask.any() else None)
    return arrays, nulls


def _result_to_arrays(result: Result, schema: T.Schema):
    arrays, nulls = [], []
    for i, f in enumerate(schema.fields):
        arr, nmask = _coerce(result.columns[i], result.nulls[i], f.dtype)
        arrays.append(arr)
        nulls.append(nmask)
    return arrays, nulls


def _coerce(col: np.ndarray, nmask, dtype: T.DataType):
    """→ (storage array, null mask | None): NULLs become fillers + mask
    instead of being silently written as 0 (review finding)."""
    if dtype.name in ("array", "map"):
        out = np.empty(len(col), dtype=object)
        for i, v in enumerate(col):
            if isinstance(v, (list, tuple, np.ndarray)):
                out[i] = list(v)
            else:
                out[i] = v  # dicts/None pass through
        if nmask is not None:
            out[np.asarray(nmask)] = None
        return out, (np.asarray(nmask) if nmask is not None else None)
    if dtype.name == "string":
        out = np.array([_s(v) for v in col], dtype=object)
        if nmask is not None:
            out[nmask] = None
        return out, (np.asarray(nmask) if nmask is not None else None)
    arr = np.asarray(col)
    obj_nulls = None
    if arr.dtype == object:
        obj_nulls = np.array([v is None for v in arr])
        arr = np.array([0 if v is None else v for v in arr])
    combined = nmask
    if obj_nulls is not None and obj_nulls.any():
        combined = obj_nulls if combined is None else (combined | obj_nulls)
    return arr.astype(dtype.np_dtype), \
        (np.asarray(combined) if combined is not None else None)


def _s(v):
    return None if v is None else str(v)


def _relation_columns(plan: ast.Plan, catalog):
    """(set of column names, set of aliases) reachable in a FROM subtree."""
    cols: set = set()
    aliases: set = set()

    def rec(p):
        if isinstance(p, ast.UnresolvedRelation):
            info = catalog.lookup_table(p.name)
            if info is not None:
                cols.update(n.lower() for n in info.schema.names())
            aliases.add((p.alias or p.name.split(".")[-1]).lower())
            return
        if isinstance(p, ast.SubqueryAlias):
            aliases.add(p.alias.lower())
        for k in p.children():
            rec(k)

    rec(plan)
    return cols, aliases


def _contains_subquery(plan: ast.Plan) -> bool:
    found = [False]

    def fn(e: ast.Expr) -> ast.Expr:
        if isinstance(e, (ast.ScalarSubquery, ast.InSubquery,
                          ast.ExistsSubquery)):
            found[0] = True
        return e

    ast.transform_plan_exprs(plan, fn)
    return found[0]


def _note_lock_wait(t_asked: float) -> None:
    """`lock_wait_ms` on the span open now: how long the store's
    mutation lock, asked for at `t_asked`, took to get."""
    from snappydata_tpu.observability import tracing

    tracing.annotate("lock_wait_ms",
                     round((time.perf_counter() - t_asked) * 1e3, 4))


def _affected_rows(result) -> int:
    """The row count a DML statement's one-cell Result carries (0 for
    a statement that reports none) — the `apply` span's `rows`."""
    try:
        return int(result.rows()[0][0])
    except (IndexError, TypeError, ValueError):
        return 0


def _sql_literal(v) -> str:
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "TRUE" if v else "FALSE"
    if isinstance(v, (int, float)):
        return repr(v)
    if hasattr(v, "item"):
        return repr(v.item())
    escaped = str(v).replace("'", "''")
    return f"'{escaped}'"

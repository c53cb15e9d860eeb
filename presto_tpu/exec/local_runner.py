"""Single-process query runner: SQL string -> rows.

Reference parity: ``LocalQueryRunner`` (presto-main testing) — full
parse -> plan -> execute in one process, no HTTP, no scheduler
(SURVEY.md §4.2). It is both the correctness-test harness and the
single-chip execution engine.

TPU-first execution model (SURVEY.md §7 "Design stance"): the WHOLE
optimized plan compiles to ONE ``jax.jit`` program over the staged scan
pages — operators are trace-time kernel compositions, XLA fuses across
them, and there is no per-operator host round trip. Data-dependent
capacity overruns (group counts, join fan-out) surface as overflow flags
returned from the program; the host reacts by scaling the static
capacity buckets and re-running (the dynamic-shape protocol of SURVEY.md
§7 "Hard parts").

Scalar subqueries execute first (recursively), and their results are
substituted as literals before the main plan compiles — a Param is a
plan-time placeholder, never a runtime value.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu import expr as E
from presto_tpu.connectors import create_connector
from presto_tpu.exec.staging import (
    DEFAULT_CACHE_BYTES,
    CatalogManager,
    SplitCache,
    block_nbytes,
    bucket_capacity,
    columns_of_page,
    page_nbytes,
    page_of_columns,
    stage_page,
    stage_params,
)
from presto_tpu.ops import (
    filter_project,
    hash_aggregate,
    hash_join,
    limit as limit_op,
    order_by as order_by_op,
    project,
    unnest as unnest_op,
    window as window_op,
)
from presto_tpu.ops.aggregation import record_row_moves
from presto_tpu.ops.join import (
    JoinBuild,
    cut_build,
    join_build,
    record_probe_lanes,
)
from presto_tpu.page import (
    Block,
    Page,
    compact_page,
    compact_page_window,
)
from presto_tpu.plan import nodes as N
from presto_tpu.plan.optimizer import (
    prune_columns,
    push_scan_constraints,
)
from presto_tpu.plan.planner import Plan, plan_statement
from presto_tpu.session import Session
from presto_tpu.sql import parse_statement
from presto_tpu.sql import ast
from presto_tpu.utils import tracing
from presto_tpu.utils.telemetry import DEVICE


class ExecutionError(RuntimeError):
    pass


class QueryResult:
    def __init__(self, columns: Tuple[str, ...], page: Page):
        self.columns = columns
        self.page = page

    def rows(self) -> List[tuple]:
        return [
            tuple(r[c] for c in self.columns) for r in self.page.to_pylist()
        ]

    def row_dicts(self) -> List[dict]:
        return self.page.to_pylist()


class LocalQueryRunner:
    """Parse -> analyze/plan -> optimize -> one-jit-program execution."""

    # each retry scales capacity buckets 4x, so 6 tries = up to 1024x
    # over the initial estimate — stats-less derived relations (CTE
    # self-joins on 5 keys, q47-class) can be orders of magnitude under
    # the true fan-out before the residual filter prunes it
    MAX_RETRIES = 6

    def __init__(
        self,
        catalogs: Optional[CatalogManager] = None,
        session: Optional[Session] = None,
        memory_pool=None,
        staging_cache_bytes: Optional[int] = None,
        plan_cache_entries: int = 256,
        history_path: Optional[str] = None,
        history_max_entries: int = 256,
    ):
        from presto_tpu.exec.stats import QueryHistory

        if catalogs is None:
            catalogs = CatalogManager()
            catalogs.register("tpch", create_connector("tpch"))
            catalogs.register("tpcds", create_connector("tpcds"))
        self.catalogs = catalogs
        self.session = session or Session()
        self.history = QueryHistory()
        #: optional utils.memory.MemoryPool; staged pages reserve
        #: against it (reference: QueryContext -> MemoryPool accounting)
        self.memory_pool = memory_pool
        #: per-thread pool-owner override: a server embedding this
        #: runner sets it to ITS query id so pool holders, kill-policy
        #: victims, and client-visible queries share one id space
        self._owner_override = threading.local()
        if not catalogs.has("system"):
            from presto_tpu.connectors.system_catalog import SystemConnector

            catalogs.register("system", SystemConnector(runner=self))
        # query-event sink (reference: EventListener SPI): one JSONL
        # record per finished/failed query, so benchmark runs produce
        # machine-readable traces. Configured by env var here; servers
        # additionally wire it from config (event-listener.path).
        import os

        event_log = os.environ.get("PRESTO_TPU_EVENT_LOG")
        if event_log:
            from presto_tpu.exec.stats import JsonlQueryEventListener

            self.history.add_listener(JsonlQueryEventListener(event_log))
        # history-based statistics store (plan/history.py): crash-safe
        # on-disk per-operator actuals keyed by canonical plan
        # fingerprints, registered on the SAME query-completed path as
        # the event sink; estimate_rows consults it before connector
        # stats (session enable_history_stats). Unconfigured = None:
        # planning is bit-exact pre-history
        self.history_store = None
        hist_path = history_path or os.environ.get(
            "PRESTO_TPU_HISTORY_PATH"
        )
        if hist_path:
            from presto_tpu.plan.history import QueryHistoryStore

            self.history_store = QueryHistoryStore(
                hist_path, history_max_entries
            )
            self.history.add_listener(self.history_store)
        # slow-query JSONL sidecar (exec/stats.SlowQueryLog): env hook
        # for embedded/bench runs; servers additionally wire it from
        # config (slow-query.threshold-ms / slow-query.path)
        slow_path = os.environ.get("PRESTO_TPU_SLOW_QUERY_LOG")
        if slow_path:
            try:
                slow_ms = float(
                    os.environ.get("PRESTO_TPU_SLOW_QUERY_MS", "0")
                )
            except ValueError:
                slow_ms = 0.0
            if slow_ms > 0:
                from presto_tpu.exec.stats import SlowQueryLog

                self.history.add_listener(
                    SlowQueryLog(slow_path, slow_ms)
                )
            else:
                # a path without a positive threshold would register a
                # listener that can never fire — refuse loudly, like
                # the server config path does
                import warnings

                warnings.warn(
                    "PRESTO_TPU_SLOW_QUERY_LOG is set but "
                    "PRESTO_TPU_SLOW_QUERY_MS is missing or <= 0; "
                    "the slow-query log is disabled",
                    stacklevel=2,
                )
        self._compiled: Dict[object, object] = {}
        # one entry-creation lock: 50 concurrent literal-variants of one
        # shape must produce ONE jitted closure (and so one XLA
        # compile), not a thundering herd of per-thread traces
        self._compile_mu = threading.Lock()
        # canonical fingerprints whose PARAMETERIZED form failed to
        # trace (a hoisted literal fed a structure-demanding kernel):
        # those shapes recompile in classic literal form, forever
        self._no_hoist: set = set()
        # canonical fingerprints whose BATCHED (vmapped) form failed to
        # trace or execute: those shapes serve scalar-only, forever —
        # a micro-batch must never fail a query the scalar path can run
        self._no_batch: set = set()
        # statement-level parameterized plan cache (plan/canonical.py):
        # canonical AST -> planned+optimized plan; warm EXECUTE /
        # repeated query shapes skip parse-analysis, planning and
        # optimization entirely (tier-1 plan.cache-entries)
        from presto_tpu.plan.canonical import PlanCache

        self.plan_cache = PlanCache(plan_cache_entries)
        # per-execution RuntimeParam ordinal -> E.Literal bound values
        # (thread-local: concurrent server queries each carry their own)
        self._bound_local = threading.local()
        self._prepared: Dict[str, object] = {}
        #: device-resident staged-page cache (exec.staging.SplitCache):
        #: whole-table entries and the columns of split batches
        #: (cacheable connectors), under one LRU byte budget
        #: (staging.cache-bytes; 0 keeps nothing) enforced through the
        #: memory pool's shared "table-cache" owner
        self.split_cache = SplitCache(
            DEFAULT_CACHE_BYTES
            if staging_cache_bytes is None
            else staging_cache_bytes,
            pool=memory_pool,
        )
        # host-spill attribution: restage traffic a query pays (its
        # scan hit a spilled-to-host page) lands on its stats sink —
        # the per-query spilled_bytes QueryInfo/EXPLAIN ANALYZE report
        self.split_cache.on_restage = self._note_spilled
        # materialized views (exec/mview.py): registry created lazily
        # at the first MV statement — plain query paths pay nothing
        self._mview_registry = None
        # serving-plane result cache (server/result_cache.py):
        # attached by the embedding coordinator when
        # result-cache.enabled could ever gate on; None = the write
        # fan-in below skips it, bit-exact pre-cache
        self.result_cache = None
        # streaming ingest lane (server/ingest.py): attached by the
        # embedding coordinator (ingest.wal-path) or tests; None =
        # the legacy write path, bit-exact pre-ingest
        self.ingest = None
        # QueryStats while a query is in flight — THREAD-local: a
        # server embedding this runner executes admitted queries on
        # concurrent threads, and a shared slot races (one thread's
        # restore-to-None between another's is-not-None check and its
        # attribute writes)
        self._qs_local = threading.local()
        # guards read-modify-write (+=) on a SHARED stats sink: the
        # prefetch thread of a streamed scan stages into the same
        # TaskStats / QueryStats the thread running the batches writes
        self._qs_mu = threading.Lock()

    @property
    def mview_registry(self):
        """The materialized-view registry (exec/mview.py), created on
        first use; :attr:`_mview_registry` stays None until then so
        the hot write/read seams can skip it for free."""
        if self._mview_registry is None:
            from presto_tpu.exec.mview import MViewRegistry

            self._mview_registry = MViewRegistry(self)
        return self._mview_registry

    @property
    def _active_qs(self):
        return getattr(self._qs_local, "value", None)

    @_active_qs.setter
    def _active_qs(self, qs) -> None:
        self._qs_local.value = qs

    # ------------------------------------------------------------ backend

    def _exec_device(self):
        """Execution device for the ``tpu_offload`` session gate
        (BASELINE.json tier-3 property; SURVEY.md preamble dual-backend
        seam): None = the platform default (TPU when present); the first
        CPU device when offload is disabled — same plans, same compiled
        programs, different executor, mirroring the reference's
        Java-worker / native-worker swap at the protocol boundary."""
        import jax

        if self.session.get("tpu_offload"):
            return None
        try:
            return jax.devices("cpu")[0]
        except RuntimeError as e:
            raise ExecutionError(
                "tpu_offload=false requires a CPU backend; none is "
                f"registered in this process ({e})"
            )

    def _device_scope(self):
        import contextlib

        import jax

        dev = self._exec_device()
        return (
            jax.default_device(dev)
            if dev is not None
            else contextlib.nullcontext()
        )

    # ------------------------------------------------------------- public

    def execute(self, sql: str) -> QueryResult:
        stmt = parse_statement(sql)
        if isinstance(stmt, ast.SetSession):
            self.session.set(stmt.name, stmt.value)
            return QueryResult(("result",), _message_page("SET SESSION"))
        if isinstance(stmt, ast.Explain):
            from presto_tpu.exec.explain import explain_text

            text = explain_text(self, stmt, sql)
            return QueryResult(("Query Plan",), _lines_page(text))
        if isinstance(stmt, ast.ShowSession):
            from presto_tpu.session import SYSTEM_SESSION_PROPERTIES

            lines = [
                f"{k}={self.session.get(k)}"
                for k in sorted(SYSTEM_SESSION_PROPERTIES)
            ]
            return QueryResult(
                ("Session",), _lines_page("\n".join(lines), "Session")
            )
        if isinstance(stmt, (ast.Insert, ast.CreateTableAs)):
            return self._execute_write(stmt)
        if isinstance(stmt, ast.ShowColumns):
            return self._execute_show_columns(stmt)
        if isinstance(stmt, ast.CreateTable):
            return self._execute_create_table(stmt)
        if isinstance(stmt, ast.DropTable):
            return self._execute_drop_table(stmt)
        if isinstance(stmt, ast.CreateMaterializedView):
            self.mview_registry.create(stmt, sql)
            return QueryResult(
                ("result",), _message_page("CREATE MATERIALIZED VIEW")
            )
        if isinstance(stmt, ast.RefreshMaterializedView):
            self.mview_registry.refresh(stmt.target)
            return QueryResult(
                ("result",), _message_page("REFRESH MATERIALIZED VIEW")
            )
        if isinstance(stmt, ast.DropMaterializedView):
            self.mview_registry.drop(stmt.target, stmt.if_exists)
            return QueryResult(
                ("result",), _message_page("DROP MATERIALIZED VIEW")
            )
        if isinstance(stmt, ast.Delete):
            return self._execute_delete(stmt)
        if isinstance(stmt, ast.Update):
            return self._execute_update(stmt)
        if isinstance(stmt, ast.Prepare):
            self._prepared[stmt.name] = stmt.statement
            return QueryResult(("result",), _message_page("PREPARE"))
        if isinstance(stmt, ast.Deallocate):
            if stmt.name not in self._prepared:
                raise ExecutionError(
                    f"prepared statement {stmt.name!r} not found"
                )
            del self._prepared[stmt.name]
            return QueryResult(
                ("result",), _message_page("DEALLOCATE")
            )
        if isinstance(stmt, ast.Execute):
            return self._execute_prepared(stmt)
        if isinstance(stmt, ast.ShowSchemas):
            conn = self.catalogs.get(stmt.catalog or self.session.catalog)
            return QueryResult(
                ("Schema",),
                _lines_page(
                    "\n".join(conn.metadata().list_schemas()), "Schema"
                ),
            )
        if isinstance(stmt, ast.ShowTables):
            conn = self.catalogs.get(self.session.catalog)
            return QueryResult(
                ("Table",),
                _lines_page(
                    "\n".join(
                        conn.metadata().list_tables(
                            stmt.schema or self.session.schema
                        )
                    ),
                    "Table",
                ),
            )
        from presto_tpu.utils.metrics import REGISTRY
        from presto_tpu.utils.tracing import Trace

        qs = self.history.begin(sql)
        trace = Trace()
        qs.trace = trace
        qs.trace_id = trace.trace_id
        REGISTRY.counter("queries.submitted").update()
        t0 = time.perf_counter()
        try:
            with trace.span("query", query_id=qs.query_id):
                with trace.span("plan"):
                    if isinstance(stmt, ast.Select):
                        # the stats sink is live DURING planning so an
                        # adaptive replan attributes its flag/note to
                        # this query (the coordinator path installs it
                        # earlier for the same reason)
                        prev_qs = self._active_qs
                        self._active_qs = qs
                        try:
                            plan, qs.plan_cache_hit = self.plan_cached(
                                stmt
                            )
                        finally:
                            self._active_qs = prev_qs
                    else:
                        plan = self._plan_statement(stmt)
                qs.planning_ms = (time.perf_counter() - t0) * 1000.0
                REGISTRY.distribution("plan.planning_ms").add(
                    qs.planning_ms
                )
                qs.state = "RUNNING"
                with trace.span("execute"):
                    result = self.execute_plan(plan, qs=qs)
        except Exception as e:
            REGISTRY.counter("queries.failed").update()
            self.history.finish(qs, error=f"{type(e).__name__}: {e}")
            self.release_pins(qs)
            if self.memory_pool is not None:
                self.memory_pool.release(qs.query_id)
            raise
        self.release_pins(qs)
        if self.memory_pool is not None:
            self.memory_pool.release(qs.query_id)
        self.history.finish(qs)
        REGISTRY.counter("queries.finished").update()
        REGISTRY.distribution("query.output_rows").add(qs.output_rows)
        return result

    def _execute_show_columns(self, stmt) -> QueryResult:
        """SHOW COLUMNS FROM t / DESCRIBE t (reference: ShowColumns
        rewritten onto the metadata catalog)."""
        from presto_tpu.connectors.spi import TableHandle

        parts = stmt.target
        catalog, schema_name = self.session.catalog, self.session.schema
        if len(parts) == 3:
            catalog, schema_name, table = parts
        elif len(parts) == 2:
            schema_name, table = parts
        else:
            (table,) = parts
        conn = self.catalogs.get(catalog)
        tschema = conn.metadata().get_table_schema(
            TableHandle(catalog, schema_name, table)
        )
        page = Page.from_pydict(
            {
                "Column": list(tschema),
                "Type": [str(t) for t in tschema.values()],
            },
            {"Column": T.VARCHAR, "Type": T.VARCHAR},
        )
        return QueryResult(("Column", "Type"), page)

    def _invalidate_table_caches(self, handle) -> None:
        """Drop cached pages (whole-table AND split granularity) of a
        written/deleted table, releasing their reservations — the
        writable-connector invalidation hook of the split cache. The
        statement-level plan cache invalidates on the same hook: a
        DROP/recreate can change the schema a cached plan resolved
        against (plain INSERTs keep plans valid, but the hook is the
        one audited write-path seam and a replan costs microseconds).
        The materialized-view registry's staleness epoch rides the
        same seam: every write (legacy or ingest commit) bumps the
        written table's epoch for the read gate."""
        self.split_cache.invalidate(handle)
        self.plan_cache.invalidate(handle)
        if self._mview_registry is not None:
            self._mview_registry.note_write(handle)
        # the serving-plane result cache rides the same seam: a write
        # (legacy or ingest commit) marks every cached result scanning
        # the table STALE — served only within the session's bounded-
        # staleness window, dropped otherwise
        if self.result_cache is not None:
            self.result_cache.note_write(handle)

    def _resolve_write_handle(self, parts):
        from presto_tpu.connectors.spi import TableHandle

        catalog, schema_name = self.session.catalog, self.session.schema
        if len(parts) == 3:
            catalog, schema_name, table = parts
        elif len(parts) == 2:
            schema_name, table = parts
        else:
            (table,) = parts
        return TableHandle(catalog, schema_name, table), self.catalogs.get(
            catalog
        )

    def _execute_create_table(self, stmt) -> QueryResult:
        """CREATE TABLE t (col type, ...) — plain DDL against a
        writable connector."""
        handle, conn = self._resolve_write_handle(stmt.target)
        if not conn.supports_writes():
            raise ExecutionError(
                f"catalog {handle.catalog} is read-only"
            )
        tschema = {
            name: T.parse_type(tname) for name, tname in stmt.columns
        }
        conn.create_table(handle, tschema)
        return QueryResult(
            ("result",), _message_page("CREATE TABLE")
        )

    def _execute_drop_table(self, stmt) -> QueryResult:
        handle, conn = self._resolve_write_handle(stmt.target)
        if not hasattr(conn, "drop_table"):
            raise ExecutionError(
                f"catalog {handle.catalog} does not support DROP TABLE"
            )
        dropped = conn.drop_table(handle)
        if not dropped and not stmt.if_exists:
            raise ExecutionError(
                f"table {handle.schema}.{handle.table} does not exist"
            )
        self._invalidate_table_caches(handle)
        return QueryResult(("result",), _message_page("DROP TABLE"))

    def _execute_delete(self, stmt) -> QueryResult:
        """DELETE FROM t [WHERE pred]: keep the complement (rows where
        the predicate is FALSE or NULL — SQL deletes only TRUE rows)
        through the normal query path, then replace the table's
        contents (reference: Delete via connector rowid strategies;
        the memory connector replaces wholesale)."""
        from presto_tpu.connectors.spi import TableHandle

        parts = stmt.target
        catalog, schema_name = self.session.catalog, self.session.schema
        if len(parts) == 3:
            catalog, schema_name, table = parts
        elif len(parts) == 2:
            schema_name, table = parts
        else:
            (table,) = parts
        handle = TableHandle(catalog, schema_name, table)
        conn = self.catalogs.get(catalog)
        if not hasattr(conn, "replace_rows"):
            raise ExecutionError(
                f"catalog {catalog} does not support DELETE"
            )
        tschema = conn.metadata().get_table_schema(handle)
        # row count without a table scan: splits carry the global row
        # space (review: the SQL-text count(*) round trip staged the
        # whole table a second time)
        before = 0
        src = conn.get_splits(handle)
        while not src.exhausted:
            for sp in src.next_batch(256):
                before += sp.num_rows
        if stmt.where is None:
            keep_sel = None
        else:
            # build the keep-select AST directly — a text round trip
            # breaks on keyword-named or mixed-case identifiers
            keep_where = ast.BinaryOp(
                "or",
                ast.UnaryOp("not", stmt.where),
                ast.IsNullExpr(stmt.where),
            )
            keep_sel = ast.Select(
                items=tuple(
                    ast.SelectItem(ast.Ident((c,)), None)
                    for c in tschema
                ),
                from_=ast.TableRef((catalog, schema_name, table)),
                where=keep_where,
            )
        if keep_sel is None:
            kept = {c: [] for c in tschema}
            n_kept = 0
        else:
            res = self.execute_plan(
                plan_statement(keep_sel, self.catalogs, self.session)
            )
            payload = _result_columns(res)
            kept = {c: payload[c] for c in tschema}
            n_kept = int(res.page.num_valid)
        conn.replace_rows(handle, kept)
        self._invalidate_table_caches(handle)
        page = Page.from_pydict(
            {"rows": [before - n_kept]}, {"rows": T.BIGINT}
        )
        return QueryResult(("rows",), page)

    def _execute_update(self, stmt) -> QueryResult:
        """UPDATE t SET c = e [WHERE pred]: the new contents are ONE
        select over the table — assigned columns become
        ``case when <pred> then <expr> else c end`` (a NULL predicate
        leaves the row unchanged, matching SQL update semantics) —
        then the table replaces wholesale."""
        handle, conn = self._resolve_write_handle(stmt.target)
        if not hasattr(conn, "replace_rows"):
            raise ExecutionError(
                f"catalog {handle.catalog} does not support UPDATE"
            )
        tschema = conn.metadata().get_table_schema(handle)
        assigns = dict(stmt.assignments)
        unknown = set(assigns) - set(tschema)
        if unknown:
            raise ExecutionError(
                f"UPDATE of unknown column(s) {sorted(unknown)}"
            )
        items = []
        changed_rows_pred = None
        for c in tschema:
            if c in assigns:
                e = assigns[c]
                if stmt.where is not None:
                    e = ast.CaseExpr(
                        None,
                        ((stmt.where, e),),
                        ast.Ident((c,)),
                    )
                items.append(ast.SelectItem(e, c))
            else:
                items.append(ast.SelectItem(ast.Ident((c,)), c))
        sel = ast.Select(
            items=tuple(items),
            from_=ast.TableRef(
                (handle.catalog, handle.schema, handle.table)
            ),
        )
        # affected-row count BEFORE replacing (the predicate must see
        # the pre-update contents)
        if stmt.where is not None:
            cnt_sel = ast.Select(
                items=(
                    ast.SelectItem(ast.FuncCall("count", ()), "c"),
                ),
                from_=ast.TableRef(
                    (handle.catalog, handle.schema, handle.table)
                ),
                where=stmt.where,
            )
            n = int(
                self.execute_plan(
                    plan_statement(
                        cnt_sel, self.catalogs, self.session
                    )
                ).rows()[0][0]
            )
        res = self.execute_plan(
            plan_statement(sel, self.catalogs, self.session)
        )
        if stmt.where is None:
            n = int(res.page.num_valid)
        payload = _result_columns(res)
        conn.replace_rows(handle, {c: payload[c] for c in tschema})
        self._invalidate_table_caches(handle)
        page = Page.from_pydict({"rows": [n]}, {"rows": T.BIGINT})
        return QueryResult(("rows",), page)

    def _execute_prepared(self, stmt) -> QueryResult:
        """EXECUTE name [USING v, ...]: substitute ? markers in the
        prepared AST with the literal arguments, then run the
        statement through the plan-cached path (reference: prepared
        statements carried per-session). A warm EXECUTE — the
        statement's canonical shape already planned — does zero
        parsing of the prepared text, zero planning, and (the argument
        literals binding straight into the cached program's parameter
        vector) zero compilation."""
        inner = self._prepared.get(stmt.name)
        if inner is None:
            raise ExecutionError(
                f"prepared statement {stmt.name!r} not found"
            )
        n_markers = _count_param_markers(inner)
        if n_markers != len(stmt.params):
            raise ExecutionError(
                f"EXECUTE {stmt.name}: statement has {n_markers} "
                f"parameter(s), {len(stmt.params)} given"
            )
        bound = _bind_param_markers(inner, stmt.params)
        return self.execute_bound(bound)

    def execute_bound(self, bound) -> QueryResult:
        """Run an already-bound statement AST (EXECUTE after marker
        substitution — also the coordinator's prepared-statement entry
        point, so the HTTP fast lane and the embedded one share one
        dispatch)."""
        if isinstance(bound, (ast.Insert, ast.CreateTableAs)):
            return self._execute_write(bound)
        if isinstance(bound, ast.Delete):
            return self._execute_delete(bound)
        if isinstance(bound, ast.Update):
            return self._execute_update(bound)
        if isinstance(bound, ast.Select):
            plan, _hit = self.plan_cached(bound)
        else:
            plan = self._plan_statement(bound)
        return self.execute_plan(plan)

    def _history_scope(self):
        """History-based-statistics planning scope: installs the
        configured store as the thread-local provider estimate_rows
        consults (plan/history.py), gated on session
        ``enable_history_stats``. No store / flag off = null scope —
        planning math bit-exact pre-history."""
        import contextlib

        from presto_tpu.plan import history as plan_history

        if self.history_store is None or not self.session.get(
            "enable_history_stats"
        ):
            return contextlib.nullcontext()
        return plan_history.using(self.history_store)

    def _plan_statement(self, stmt) -> Plan:
        """plan_statement under the history scope — the one audited
        planning entry for runner-owned statements."""
        with self._history_scope():
            return plan_statement(stmt, self.catalogs, self.session)

    def plan_cached(self, stmt) -> Tuple[Plan, bool]:
        plan, hit, _key = self.plan_cached_keyed(stmt)
        return plan, hit

    def plan_cached_keyed(self, stmt) -> Tuple[Plan, bool, Optional[str]]:
        """plan_cached plus the canonical statement cache key (None
        when the statement bypassed the cache) — the coordinator's
        micro-batch queue groups concurrent same-key statements.

        Also the ONE select-planning seam every read path funnels
        through (execute, EXECUTE, micro-batch lane, distributed
        dispatch), which is where the materialized-view staleness read
        gate sits: a referenced stale view refreshes before the
        statement plans (``mview.max-staleness-s``)."""
        if self._mview_registry is not None:
            self._mview_registry.read_gate(stmt)
            # MV-aware rewrite (session mview_auto_rewrite): an
            # eligible aggregate over a base table rewrites onto the
            # maintained view BEFORE canonicalization, so plan-cache
            # keys derive from what actually executes. The match/gate
            # logic is the audited seam in server/result_cache.py;
            # any failure falls open to the original statement.
            if self.session.get("mview_auto_rewrite"):
                from presto_tpu.server.result_cache import mview_rewrite

                rewritten = mview_rewrite(
                    stmt, self._mview_registry, self.session
                )
                if rewritten is not None:
                    stmt, mv = rewritten
                    qs = self._active_qs
                    if qs is not None:
                        with self._qs_mu:
                            qs.mview_rewritten = ".".join(mv.parts)
        plan, hit, key = self._plan_cached(stmt)
        if hit:
            # a server embedding this runner installs its QueryStats as
            # the thread-local sink before planning: attribute the hit
            qs = self._active_qs
            if qs is not None:
                with self._qs_mu:
                    qs.plan_cache_hit = True
        return plan, hit, key

    def _plan_cached(self, stmt) -> Tuple[Plan, bool, Optional[str]]:
        """Statement-level parameterized plan cache -> (plan, hit).

        The statement canonicalizes (comparison-operand literals become
        BoundParam placeholders — plan/canonical.py); the canonical
        AST keys a bounded LRU of planned + pre-optimized plans whose
        RuntimeParam slots the current literal values bind into. A
        shape whose canonical form cannot plan (a hoisted literal in a
        structural position) is marked BYPASS and planned with literals
        in place from then on — the cache degrades to classic planning,
        never to a failed query."""
        from presto_tpu.plan import canonical
        from presto_tpu.utils.metrics import REGISTRY

        if not self.session.get("enable_plan_cache"):
            return (
                self._plan_statement(stmt),
                False,
                None,
            )
        t0 = time.perf_counter()
        try:
            key, canon, lits = canonical.canonicalize_statement(
                stmt, self.session
            )
        except Exception:
            # canonicalization must never fail a query
            return (
                self._plan_statement(stmt),
                False,
                None,
            )
        finally:
            REGISTRY.distribution("plan.canonicalize_ms").add(
                (time.perf_counter() - t0) * 1000.0
            )
        bound = {i: lit for i, lit in enumerate(lits)}
        entry = self.plan_cache.get(key)
        if isinstance(entry, canonical.PlanCacheEntry):
            # adaptive execution: an epoch-stale entry replans instead
            # of serving the plan its worst early estimates built
            # (None = entry still fresh, or the plane is off)
            replanned = self._adaptive_replan(key, entry, canon, bound)
            if replanned is not None:
                return replanned
            return (
                Plan(
                    root=entry.root,
                    params=entry.params,
                    output_names=entry.output_names,
                    bound_values=bound,
                    preoptimized=entry.preoptimized,
                ),
                True,
                key,
            )
        if entry is canonical.BYPASS:
            return (
                self._plan_statement(stmt),
                False,
                None,
            )
        try:
            # capture which history fingerprints (and which estimates)
            # this optimization consulted: the evidence the entry's
            # later staleness checks re-validate against (null scope
            # when the adaptive plane is off — see _capture_scope)
            with self._capture_scope() as consulted:
                plan = self._plan_statement(canon)
        except Exception:
            # parameterized planning failed (hoisted literal in a
            # structural position): permanent literal-form lane
            self.plan_cache.put(key, canonical.BYPASS)
            return (
                self._plan_statement(stmt),
                False,
                None,
            )
        handles = canonical.plan_handles(plan)
        if any(
            self.catalogs.get(h.catalog).prunes_splits()
            for h in handles
        ):
            # split-pruning connectors (hive partitions, parquet row
            # groups, ORC stripes) read equality/IN literals as scan
            # constraints; a parameterized plan blocks that extraction
            # and would silently cost them their pruning — those
            # statements keep classic literal planning (the compile-
            # level canonicalizer still shares programs where the
            # constraints agree)
            self.plan_cache.put(key, canonical.BYPASS)
            return (
                self._plan_statement(stmt),
                False,
                None,
            )
        return self._store_canonical_entry(
            key, plan, consulted, bound, handles, len(lits)
        )

    def _capture_scope(self):
        """Consult capture for canonical-statement planning — active
        only when the adaptive plane could ever read the evidence
        (session ``adaptive_enabled``): the default path must not pay
        per-consult store reads or retain consulted dicts nothing
        will judge. Entries planned with the plane OFF therefore
        carry no evidence and are never replanned — flipping adaptive
        on mid-process adapts newly (re)planned shapes, not cached
        ones retroactively."""
        import contextlib

        from presto_tpu.plan import history as plan_history

        if self.session.get("adaptive_enabled"):
            return plan_history.capture_consults()
        return contextlib.nullcontext({})

    def _store_canonical_entry(
        self, key, plan, consulted, bound, handles, n_slots
    ):
        """Build + store the statement-cache entry for a planned
        canonical statement; -> its bound ``(plan, False, key)``
        triple. The ONE entry constructor the miss path and the
        adaptive replan share — entries built by either must never
        diverge in shape or preoptimization."""
        from presto_tpu.plan import canonical

        root, preopt = plan.root, False
        if not plan.params:
            # value-independent over a canonical root: optimize ONCE at
            # store time so cache hits skip it (plans with scalar-
            # subquery params keep the execute-time prune+push order —
            # binding substitutes Params first)
            root = push_scan_constraints(prune_columns(root))
            preopt = True
        self.plan_cache.put(
            key,
            canonical.PlanCacheEntry(
                root=root,
                params=plan.params,
                output_names=plan.output_names,
                preoptimized=preopt,
                handles=handles,
                n_slots=n_slots,
                consulted=dict(consulted),
            ),
        )
        return (
            Plan(
                root=root,
                params=plan.params,
                output_names=plan.output_names,
                bound_values=bound,
                preoptimized=preopt,
            ),
            False,
            key,
        )

    def _adaptive_replan(self, key, entry, canon, bound):
        """Epoch-versioned plan cache (adaptive execution, ROADMAP
        item 2): a statement-cache HIT whose consulted history
        estimates have materially diverged (plan/canonical.
        stale_consults — the shared divergence test) replans the
        canonical statement against TODAY's learned cardinalities and
        REPLACES the entry, so the hottest shapes stop paying for
        their worst early guesses. Fail-open: any replan failure
        serves the cached plan — never a failed query. Returns the
        ``(plan, hit=False, key)`` triple, or None when the entry is
        still fresh / the plane is off."""
        from presto_tpu.plan import canonical
        from presto_tpu.plan import history as plan_history
        from presto_tpu.utils.metrics import REGISTRY

        if not self.session.get("adaptive_enabled"):
            return None
        store = self.history_store
        if (
            store is None
            or not self.session.get("enable_history_stats")
            or not entry.consulted
        ):
            return None
        factor = float(self.session.get("adaptive_divergence_factor"))
        stale = canonical.stale_consults(entry.consulted, store, factor)
        if stale is None:
            return None
        fp, old_epoch, new_epoch = stale
        REGISTRY.counter("adaptive.divergence_detected").update()
        try:
            with plan_history.capture_consults() as consulted:
                plan = self._plan_statement(canon)
            out = self._store_canonical_entry(
                key, plan, consulted, bound,
                canonical.plan_handles(plan), entry.n_slots,
            )
        except Exception:
            # replan failure: the cached plan still answers correctly
            # (its estimates were stale, not its semantics) — serve it
            REGISTRY.counter("plan.replan_failures").update()
            return None
        REGISTRY.counter("plan.replans").update()
        self.plan_cache.note_replan()
        qs = self._active_qs
        if qs is not None:
            with self._qs_mu:
                qs.replanned = True
                qs.adaptive_notes.append(
                    f"REPLANNED (epoch {old_epoch}→{new_epoch}) "
                    f"node {fp}"
                )
        return out

    def _execute_write(self, stmt) -> QueryResult:
        """Table writer (reference: TableWriterOperator + the SPI's
        ConnectorPageSink): INSERT INTO ... SELECT | VALUES, and
        CREATE TABLE AS, against any connector with supports_writes()."""
        from presto_tpu.connectors.spi import TableHandle

        parts = stmt.target
        catalog, schema_name = self.session.catalog, self.session.schema
        if len(parts) == 3:
            catalog, schema_name, table = parts
        elif len(parts) == 2:
            schema_name, table = parts
        else:
            (table,) = parts
        handle = TableHandle(catalog, schema_name, table)
        conn = self.catalogs.get(catalog)
        if not conn.supports_writes():
            raise ExecutionError(f"catalog {catalog} is read-only")

        if isinstance(stmt, ast.CreateTableAs):
            res = self.execute_plan(
                plan_statement(stmt.query, self.catalogs, self.session)
            )
            tschema = {
                name: blk.dtype
                for name, blk in zip(res.page.names, res.page.blocks)
            }
            conn.create_table(handle, tschema)
            cols = _result_columns(res)
            conn.append_rows(handle, cols)
            n = int(res.page.num_valid)
        elif stmt.query is not None:
            tschema = conn.metadata().get_table_schema(handle)
            res = self.execute_plan(
                plan_statement(stmt.query, self.catalogs, self.session)
            )
            if len(res.columns) != len(tschema):
                raise ExecutionError(
                    f"INSERT column count mismatch: query has "
                    f"{len(res.columns)}, table has {len(tschema)}"
                )
            src = _result_columns(res)
            cols = {
                tcol: src[qcol]
                for tcol, qcol in zip(tschema, res.columns)
            }
            conn.append_rows(handle, cols)
            n = int(res.page.num_valid)
        else:
            tschema = conn.metadata().get_table_schema(handle)
            names = list(tschema)
            rows = []
            for row in stmt.values:
                if len(row) != len(names):
                    raise ExecutionError(
                        f"INSERT VALUES arity {len(row)} != table "
                        f"columns {len(names)}"
                    )
                rows.append([_literal_value(e) for e in row])
            from presto_tpu.exec.staging import obj_array

            cols = {
                name: obj_array([r[i] for r in rows])
                for i, name in enumerate(names)
            }
            conn.append_rows(handle, cols)
            n = len(rows)
        # a write invalidates every cached page of the written table —
        # whole-table AND split granularity — else a cacheable writable
        # connector (memory) silently serves stale pages on re-run
        self._invalidate_table_caches(handle)
        page = Page.from_pydict({"rows": [n]}, {"rows": T.BIGINT})
        return QueryResult(("rows",), page)

    def execute_plan(self, plan: Plan, qs=None) -> QueryResult:
        from presto_tpu.exec.host_ops import apply_host_ops, peel_host_ops

        prev, self._active_qs = self._active_qs, qs
        prev_bound = getattr(self._bound_local, "value", None)
        if plan.bound_values is not None:
            # cached canonical plan: the execution's literal values ride
            # thread-local to _run_with_pages, where they bind into the
            # compiled program's parameter vector
            self._bound_local.value = plan.bound_values
        try:
            root = self._bind_params(plan)
            if not plan.preoptimized:
                t_opt = time.perf_counter()
                with self._history_scope():
                    root = push_scan_constraints(prune_columns(root))
                if qs is not None and hasattr(qs, "optimization_ms"):
                    qs.optimization_ms += (
                        time.perf_counter() - t_opt
                    ) * 1000.0
            if (
                qs is not None
                and hasattr(qs, "plan_fingerprint")
                and not qs.plan_fingerprint
                and self.session.get("enable_operator_stats")
            ):
                # canonical statement identity: keys the history-store
                # record and enriches the query-completed event
                try:
                    from presto_tpu.plan import history as plan_history

                    qs.plan_fingerprint = plan_history.plan_fingerprint(
                        root
                    )
                except Exception:
                    pass
            host_ops: List[N.PlanNode] = []
            if self.session.get("host_root_stage"):
                root, host_ops = peel_host_ops(root)
            t0 = time.perf_counter()
            page = self._run(root)
            if host_ops:
                page = apply_host_ops(page, host_ops)
            if qs is not None:
                qs.execution_ms += (time.perf_counter() - t0) * 1000.0
                qs.output_rows = int(page.num_valid)
        finally:
            self._active_qs = prev
            self._bound_local.value = prev_bound
        return QueryResult(plan.output_names, page)

    def execute_plan_analyzed(self, plan: Plan, sql: str = ""):
        """EXPLAIN ANALYZE support: run the plan exactly as execute_plan
        does (including the host root stage peel) with per-node row
        counters traced as extra program outputs. Returns
        (QueryResult, List[PlanNodeStats] for the device tree,
        List[int] rows-after-each-host-op innermost-first,
        bound pre-peel root, device root executed, host ops peeled,
        id(node) -> (planning-time estimate, provenance) map) —
        the trees are returned so EXPLAIN ANALYZE annotates the exact
        nodes that ran (param binding may rewrite the plan, so
        re-deriving them can diverge; peel preserves node identity, so
        the bound root renders the full tree with matching ids).
        Single-device trace path — counts are identical under
        distribution."""
        from presto_tpu.exec.host_ops import apply_host_ops, peel_host_ops
        from presto_tpu.exec.stats import collect_node_stats

        bound_root = push_scan_constraints(
            prune_columns(self._bind_params(plan))
        )
        root = bound_root
        host_ops: List[N.PlanNode] = []
        if self.session.get("host_root_stage"):
            root, host_ops = peel_host_ops(root)
        scans = [n for n in N.walk(root) if isinstance(n, N.TableScanNode)]
        # PLANNING-time estimates, captured BEFORE the instrumented run
        # (and before its actuals reach the history store): the
        # est-vs-actual error EXPLAIN ANALYZE prints must reflect what
        # the optimizer believed going in — a warm run's history-fed
        # estimates shrink that error, a cold run's do not
        from presto_tpu.exec.explain import _estimate_map

        with self._history_scope():
            est_map = _estimate_map(root, self.catalogs)
        pages = [self._load_table(s) for s in scans]
        stats_cell: List = []
        page = LocalQueryRunner._run_with_pages(
            self, root, scans, pages, stats_out=stats_cell
        )
        host_rows: List[int] = []
        if host_ops:
            page = apply_host_ops(page, host_ops, rows_out=host_rows)
        stats = collect_node_stats(stats_cell)
        self._record_history(root, stats, stmt_root=bound_root, sql=sql)
        return (
            QueryResult(plan.output_names, page),
            stats,
            host_rows,
            bound_root,
            root,
            host_ops,
            est_map,
        )

    def _record_history(
        self,
        droot: N.PlanNode,
        stats,
        stmt_root: Optional[N.PlanNode] = None,
        sql: str = "",
    ) -> None:
        """Persist an analyzed run's per-node actuals to the history
        store — the EXPLAIN ANALYZE twin of the query-completed write
        path (the explain branch never creates a QueryStats, but its
        instrumented run measured the same truth). The statement key
        comes from ``stmt_root`` — the PRE-peel bound root, the same
        tree execute_plan fingerprints — so an analyzed run updates
        the normal run's index entry instead of forking a second one
        when host ops were peeled."""
        if self.history_store is None:
            return
        try:
            from presto_tpu.plan import history as plan_history

            fps = plan_history.node_fingerprints(droot)
            by_walk = {i: n for i, n in enumerate(N.walk(droot))}
            nodes = {}
            for s in stats:
                n = by_walk.get(s.node_id)
                if n is None or s.output_rows < 0:
                    continue
                fp = fps.get(id(n), "")
                if fp:
                    nodes[fp] = {
                        "rows": int(s.output_rows),
                        "label": s.label,
                    }
            self.history_store.record_query(
                plan_history.plan_fingerprint(
                    droot if stmt_root is None else stmt_root
                ),
                sql,
                nodes,
            )
        except Exception:
            pass  # a broken store must never fail EXPLAIN ANALYZE

    # ------------------------------------------------- params (subqueries)

    def _bind_params(self, plan: Plan) -> N.PlanNode:
        bindings: Dict[int, E.Literal] = {}
        for pid, sub in plan.params:
            sub_root = self._bind_params(sub)
            sub_root = push_scan_constraints(prune_columns(sub_root))
            # running the subquery is execution, though the caller may
            # still be planning the statement around it
            with tracing.phase("exec", site="subquery"):
                page = self._run(sub_root)
            col = sub.output_names[0]
            bindings[pid] = _scalar_literal(page, col)
        if not bindings:
            return plan.root
        return _substitute_params_node(plan.root, bindings)

    # ---------------------------------------------------------- execution

    def _run(self, root: N.PlanNode) -> Page:
        from presto_tpu.exec import streaming

        if streaming.needs_streaming(root, self.catalogs, self.session):
            # larger-than-HBM input: split-streamed partial aggregation
            # with hash-bucketed host spill (exec.streaming)
            return streaming.run_streamed(self, root)
        budget = int(self.session.get("max_fragment_weight"))
        if budget > 0 and _plan_weight(root) > budget:
            return self._run_fragmented(root, budget)
        scans = [
            n for n in N.walk(root) if isinstance(n, N.TableScanNode)
        ]
        pages = [self._load_table(s) for s in scans]
        return self._run_with_pages(root, scans, pages)

    # ------------------------------------------- stage-at-a-time execution

    def _run_fragmented(self, root: N.PlanNode, budget: int) -> Page:
        """Execute a heavy plan stage-at-a-time: heavy subtrees compile
        and run as their OWN bounded-size XLA programs, their outputs
        stay device-resident, and the remaining tree consumes them as
        leaves.

        Reference parity: tasks execute plan *fragments*, never a whole
        plan as one unit (SURVEY.md §3.3) — the whole-plan-as-one-program
        model produces pathologically large XLA programs exactly when
        plans get big (Q64's 17-table star join, Q18's semi-join + big
        aggregation), whose compiles run to many minutes. Per-fragment
        cost is one extra control round trip (not measured on the
        chip), paid only by plans heavy enough to fragment.
        """
        pages_map: Dict[int, Page] = {}
        reduced = self._reduce_fragment(root, budget, pages_map)
        leaves, pages = self.leaf_pages(reduced, pages_map)
        return self._run_with_pages(reduced, leaves, pages)

    def leaf_pages(
        self, root: N.PlanNode, pages_map: Optional[Dict[int, Page]] = None,
        skip: Optional[N.PlanNode] = None,
    ) -> Tuple[List[N.PlanNode], List[Page]]:
        """Collect a fragment's leaves (scans + remote sources) and
        their input pages: scans load (cached) tables, remote sources
        resolve through ``pages_map`` (id(node) -> already-produced
        page); ``skip``, a scan the caller stages by batch, gets None.
        The one leaf-resolution path for every fragment executor."""
        pages_map = pages_map or {}
        leaves = [
            n
            for n in N.walk(root)
            if isinstance(n, (N.TableScanNode, N.RemoteSourceNode))
        ]
        pages = [
            None if n is skip
            else pages_map[id(n)]
            if isinstance(n, N.RemoteSourceNode)
            else self._load_table(n)
            for n in leaves
        ]
        return leaves, pages

    def _reduce_fragment(
        self, node: N.PlanNode, budget: int, pages_map: Dict[int, Page]
    ) -> N.PlanNode:
        """Bottom-up: shrink ``node``'s subtree to at most ``budget``
        weight by executing its heaviest child subtrees as standalone
        fragments (device-resident results become RemoteSourceNode
        leaves). A node whose own weight exceeds the budget with only
        leaf children runs as one program anyway — it cannot be cut
        smaller."""
        changes = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, N.PlanNode):
                changes[f.name] = self._reduce_fragment(
                    v, budget, pages_map
                )
            elif (
                isinstance(v, tuple)
                and v
                and isinstance(v[0], N.PlanNode)
            ):
                changes[f.name] = tuple(
                    self._reduce_fragment(x, budget, pages_map)
                    for x in v
                )
        if changes:
            node = dataclasses.replace(node, **changes)
        while _plan_weight(node) > budget:
            cands = [
                c
                for c in node.children()
                if not isinstance(
                    c,
                    (
                        N.TableScanNode,
                        N.RemoteSourceNode,
                        N.ValuesNode,
                    ),
                )
            ]
            if not cands:
                break
            # BUILD side first when reducing a join (reference pipeline
            # order: HashBuilder before LookupJoin) — its executed page
            # then feeds a dynamic filter into the probe side
            if isinstance(node, N.JoinNode) and node.right in cands:
                child = node.right
            else:
                child = max(cands, key=_plan_weight)
            leaf = self._execute_to_leaf(child, pages_map)
            swaps = {}
            for f in dataclasses.fields(node):
                v = getattr(node, f.name)
                if v is child:
                    swaps[f.name] = leaf
                elif isinstance(v, tuple) and any(
                    x is child for x in v
                ):
                    swaps[f.name] = tuple(
                        leaf if x is child else x for x in v
                    )
            node = dataclasses.replace(node, **swaps)
            node = self._apply_dynamic_filter(node, leaf, pages_map)
        return node

    def _apply_dynamic_filter(
        self, node: N.PlanNode, leaf: N.RemoteSourceNode, pages_map
    ) -> N.PlanNode:
        """Dynamic filtering (reference: runtime dynamic filters flowing
        from the join build side into probe-side scans — SURVEY.md
        §3.2): when a stage-at-a-time JOIN's BUILD side has just
        executed, fetch its join-key summary (min/max in the key's
        native dtype, present-value LUT for small dictionary string
        keys — one round trip; exec.dynfilter owns the construction)
        and pre-filter the still-unexecuted probe side — probe rows
        outside the build's key domain cannot match, so inner/semi
        joins may drop them early (cuts join out_capacity pressure and
        overflow retries on star joins). The filter node is marked
        ``dynamic``: its pruned-row count is traced out of the program
        (dynamic_filter.rows_pruned)."""
        if not self.session.get("enable_dynamic_filtering"):
            return node
        if not (
            isinstance(node, N.JoinNode)
            and node.right is leaf
            and node.join_type in ("inner", "semi")
            and not isinstance(
                node.left, (N.RemoteSourceNode, N.ValuesNode)
            )
        ):
            return node
        from presto_tpu.exec import dynfilter
        from presto_tpu.utils.metrics import REGISTRY

        build = pages_map[id(leaf)]
        conjuncts, n_filters = dynfilter.device_conjuncts(
            build,
            list(zip(node.left_keys, node.right_keys)),
            node.left.output_schema(),
            ndv_limit=int(
                self.session.get("dynamic_filtering_ndv_limit")
            ),
        )
        if not conjuncts:
            return node
        REGISTRY.counter("dynamic_filter.built").update()
        REGISTRY.counter("dynamic_filter.applied").update(n_filters)
        self._fold_dyn_stat("dynamic_filters", n_filters)
        pred = (
            conjuncts[0]
            if len(conjuncts) == 1
            else E.And(tuple(conjuncts))
        )
        return dataclasses.replace(
            node,
            left=N.FilterNode(
                source=node.left, predicate=pred, dynamic=True
            ),
        )

    def _execute_to_leaf(
        self, subtree: N.PlanNode, pages_map: Dict[int, Page]
    ) -> N.RemoteSourceNode:
        """Run one fragment as its own program; the result stays on
        device, re-bucketed to its live prefix so the consuming
        fragment's program size tracks actual (not worst-case)
        intermediate cardinality."""
        leaves, pages = self.leaf_pages(subtree, pages_map)
        page, _n = self._run_with_pages(
            subtree, leaves, pages, fetch_result=False
        )
        if self._active_qs is not None:
            with self._qs_mu:
                self._active_qs.device_fragments += 1
        remote = N.RemoteSourceNode(fragment_root=subtree)
        pages_map[id(remote)] = page
        return remote

    def prepare_builds(
        self,
        root: N.PlanNode,
        partitioned: N.PlanNode,
        pages_map: Optional[Dict[int, Page]] = None,
        batch_rows: Optional[int] = None,
    ) -> Tuple[N.PlanNode, Dict[int, JoinBuild]]:
        """Compute once what every split batch of ``root`` over the
        ``partitioned`` scan would otherwise build again: the build side
        of each join that holds no partitioned scan (a replicated
        subtree: scans, filters, projections and the joins among them).
        Each runs as a program of its own that ends in the sort by key
        (``N.JoinBuildNode``), and is cut to the bucket of the rows a
        probe can match by the one read of their count (:meth:`_collect`).
        A FULL join keeps its build in the batch's program: its
        unmatched build rows are output too. Returns ``root`` with every
        such build side replaced by a leaf, and the prepared builds by
        the leaves' ids: the batch program takes them as arguments and
        runs only the probe and the output of those joins.
        ``pages_map`` resolves the build sides' remote leaves
        (``leaf_pages``); ``batch_rows`` is a split batch's rows
        (``page_capacity`` where not given), the most a build side's own
        join runs over in one program (:meth:`_build_in_batches`)."""
        pages_map = dict(pages_map or {})
        builds: Dict[int, JoinBuild] = {}

        def rewrite(node: N.PlanNode) -> N.PlanNode:
            if (
                isinstance(node, N.JoinNode)
                and node.join_type != "full"
                and not any(n is partitioned for n in N.walk(node.right))
            ):
                side = self._build_in_batches(
                    node, pages_map,
                    batch_rows or int(self.session.get("page_capacity")),
                )
                leaf = self._execute_to_leaf(
                    N.JoinBuildNode(
                        side, tuple(node.right_keys),
                        tuple(node.payload), node.key_ranges,
                    ),
                    pages_map,
                )
                builds[id(leaf)] = pages_map[id(leaf)]
                if DEVICE.enabled:
                    DEVICE.count_join_build(builds[id(leaf)].capacity)
                return dataclasses.replace(
                    node, left=rewrite(node.left), right=leaf
                )
            return N.map_children(node, rewrite)

        with tracing.phase("exec", site="join_build"):
            return rewrite(root), builds

    def _build_in_batches(
        self, join: N.JoinNode, pages_map: Dict[int, Page], rows: int
    ) -> N.PlanNode:
        """A join's build side that joins within itself over a table of
        more than ``rows`` rows (a split batch's), run over that table's
        split batches — one program of a batch's size, as a stage's,
        that ends in the sort by the join's key (``N.JoinBuildNode``
        with every column riding: the sort is the compaction, where a
        program's own would gather every column) — and the batches'
        matchable rows laid end to end on the device
        (``parallel.exchange.lay_end_to_end``): a leaf of ``pages_map``
        in its place. A join's rank scans over more rows than a page
        compile for many minutes on the chip (TPC-H Q18's ``orders``
        filtered by its IN subquery, 15 M rows at SF10). Any other side
        is returned as it is."""
        from presto_tpu.parallel.exchange import lay_end_to_end
        from presto_tpu.server.scheduler import (
            _edge_distributive,
            _path_to,
            scan_rows,
        )

        side = join.right
        scans = [n for n in N.walk(side) if isinstance(n, N.TableScanNode)]
        if not scans or not any(
            isinstance(n, N.JoinNode) for n in N.walk(side)
        ):
            return side
        big = max(scans, key=lambda n: scan_rows(self.catalogs, n))
        total = scan_rows(self.catalogs, big)
        path = _path_to(side, big)
        if total <= rows or not all(
            _edge_distributive(a, b) for a, b in zip(path, path[1:])
        ):
            return side
        schema = dict(side.output_schema())
        # its own replicated build sides (the IN subquery's keys) once
        inner, builds = self.prepare_builds(side, big, pages_map, rows)
        sort = N.JoinBuildNode(
            inner, tuple(join.right_keys), tuple(schema), join.key_ranges
        )
        leaves, pages = self.leaf_pages(
            sort, {**pages_map, **builds}, skip=big
        )
        resolved = self._resolve(sort, leaves, batches=-(-total // rows))
        outs = []
        for lo in range(0, total, rows):
            page, release = self.stage_split(
                big, lo, min(lo + rows, total), rows
            )
            try:
                outs.append(self._collect(
                    self._dispatch(resolved, [
                        page if n is big else p for n, p in zip(leaves, pages)
                    ]),
                    fetch_result=False,
                ))
            finally:
                release()
        with self._device_scope(), tracing.phase("dispatch"):
            merged, _slots = lay_end_to_end(
                [b.page for b, _ in outs], [n for _, n in outs], schema
            )
        if DEVICE.enabled:
            DEVICE.count_dispatch()
            self._fold_device_stat(device_dispatches=1)
        leaf = N.RemoteSourceNode(fragment_root=side)
        pages_map[id(leaf)] = merged
        return leaf

    def _make_trace(
        self, croot, cscan_ids, counted, analyzed, out_capacity=None
    ):
        """Build the scalar trace closure for one canonical root — the
        ONE program constructor. The scalar compile entry jits it
        directly; the micro-batch entry wraps it in the canonical
        vmap-over-params form (plan/canonical.vmap_program), so both
        lanes execute the same per-member operator composition.

        ``out_capacity`` (micro-batch entries only): compact the
        program output to this window instead of the full capacity
        bucket — the batch demux fetches at most the speculative
        window per lane, so gathering the full bucket per lane would
        multiply the dominant memory traffic by the batch width for
        rows nobody reads. The UNCLAMPED live count rides out as a
        sixth output; lanes whose true count exceeds the window fall
        out of the batch at demux (scalar re-run) — never a truncated
        answer. ``None`` = the exact scalar program, 5-tuple, with
        bit-identical full-capacity output.

        The fourth cell returned (``_Tally``) holds the slots of each
        join build the program sorts itself (its build side is no
        prepared leaf: :meth:`prepare_builds`), the row moves of its
        grouped aggregations and the key lanes its join probes sort,
        filled when it is traced."""
        from presto_tpu.plan import canonical

        msgs_cell: List[str] = []
        nodes_cell: List = []
        tally = _Tally()

        def trace(
            pages_in,
            params_in,
            _root=croot,
            _ids=cscan_ids,
            _m=msgs_cell,
            _n=nodes_cell,
            _t=tally,
        ):
            flags: List = []
            errors: List = []
            counters: Optional[List] = (
                [] if counted else None
            )
            dyn: List = []
            _t.builds.clear()
            with canonical.active_params(params_in):
                with record_row_moves() as moves, \
                        record_probe_lanes() as lanes:
                    out = _execute_node(
                        _root, pages_in, _ids, flags, errors,
                        counters, dyn, count_all=analyzed, builds=_t.builds,
                    )
                _t.agg_row_moves = moves[0]
                _t.join_probe_lanes = lanes[0]
                # program boundary: host materialization /
                # exchanges need prefix form (lazy selection
                # masks stop here). num_valid is the TRUE live
                # count in both page forms — captured before a
                # windowed compaction clamps it
                true_n = out.num_valid
                if out_capacity is not None:
                    out = compact_page_window(out, out_capacity)
                elif not isinstance(out, JoinBuild):
                    # a build side in key order (N.JoinBuildNode) is
                    # compact already: its dead rows sort last
                    out = compact_page(out)
            _m.clear()
            _m.extend(m for m, _ in errors)
            _n.clear()
            if counters is not None:
                from presto_tpu.exec.stats import node_label
                from presto_tpu.plan import (
                    history as plan_history,
                )

                walk_ids = {
                    id(n): i
                    for i, n in enumerate(N.walk(_root))
                }
                depths = _node_depths(_root)
                try:
                    # canonical sub-fingerprints: the
                    # history keys of these operators
                    # (computed ONCE per compile)
                    fps = plan_history.node_fingerprints(
                        _root
                    )
                except Exception:
                    fps = {}
                counted_ids = {
                    id(node) for node, _, _, _ in counters
                }

                def child_walks(n):
                    # nearest COUNTED descendants: with
                    # cardinality-preserving nodes skipped
                    # on the always-on path, a join's
                    # input_rows still sums its sides'
                    # real row sources
                    out_ids = []
                    for c in n.children():
                        if id(c) in counted_ids:
                            out_ids.append(
                                walk_ids.get(id(c), -1)
                            )
                        else:
                            out_ids.extend(child_walks(c))
                    return out_ids

                _n.extend(
                    (
                        walk_ids.get(id(node), -1),
                        node_label(node),
                        cap,
                        nbytes,
                        depths.get(id(node), 0),
                        fps.get(id(node), ""),
                        tuple(child_walks(node)),
                    )
                    for node, _, cap, nbytes in counters
                )
                cnts = [c for _, c, _, _ in counters]
            else:
                cnts = []
            # stack control outputs: ONE device->host fetch
            # per run (each separate scalar fetch costs a
            # full host<->device round trip; not measured
            # on the chip); dyn holds per-dynamic-filter
            # pruned-row counts
            base = (
                out,
                _stack_bools(flags),
                _stack_bools([e for _, e in errors]),
                _stack_i32(cnts),
                _stack_i32(dyn),
            )
            if out_capacity is None:
                return base
            return base + (jnp.asarray(true_n, jnp.int32),)

        return trace, msgs_cell, nodes_cell, tally

    # ------------------------------------------------ micro-batched serving

    def microbatch_plan_eligible(self, plan) -> bool:
        """Cheap structural screen before a statement may join a
        micro-batch: a cached canonical plan (bound values present),
        no scalar-subquery pre-passes, already pre-optimized, small
        enough to compile whole, and not a streamed scan. Everything
        else keeps the scalar path — batching can cost a wait, never
        a wrong answer or a failed query."""
        from presto_tpu.exec import streaming

        if (
            plan.bound_values is None
            or plan.params
            or not plan.preoptimized
        ):
            return False
        root = plan.root
        if streaming.needs_streaming(root, self.catalogs, self.session):
            return False
        budget = int(self.session.get("max_fragment_weight"))
        if budget > 0 and _plan_weight(root) > budget:
            return False
        return True

    def execute_plan_microbatch(self, plans, qs_list):
        """Answer N same-canonical-shape plans (one plan-cache entry,
        N bound-value vectors) with ONE device dispatch: the members'
        hoisted parameter vectors stack along a new leading batch axis
        and the scalar program runs vmapped with the staged pages
        broadcast (plan/canonical owns the batch-axis constructs).

        Returns a list aligned with ``plans``: a QueryResult for every
        lane the batch served, ``None`` for members that fall out —
        trace failure, non-hoistable shape, capacity overflow, error
        lanes, over-window output — which the caller re-runs on the
        existing scalar path. All-None means the shape itself is
        batch-ineligible."""
        from presto_tpu.exec.host_ops import apply_host_ops, peel_host_ops
        from presto_tpu.plan import canonical
        from presto_tpu.utils.metrics import REGISTRY

        n = len(plans)
        none: List = [None] * n
        if n < 2:
            return none
        plan0 = plans[0]
        root = plan0.root
        host_ops: List[N.PlanNode] = []
        if self.session.get("host_root_stage"):
            root, host_ops = peel_host_ops(root)
        # the demux slices flat (scalar/dictionary) blocks; nested
        # output shapes keep the scalar path
        try:
            if any(
                t.is_nested for t in root.output_schema().values()
            ):
                return none
        except Exception:
            return none
        spec = int(self.session.get("speculative_result_rows"))
        if spec <= 0:
            return none
        counted = bool(self.session.get("enable_operator_stats"))
        offload = self.session.get("tpu_offload")
        # per-member hoist over the SHARED root object: canonical
        # fingerprints agree by construction, values differ only in
        # the parameter vectors
        vectors: List[tuple] = []
        croot = None
        for p in plans:
            cr, params = canonical.hoist_params(
                root, bound=p.bound_values, hoist_literals=True
            )
            if cr is root or not params:
                return none  # nothing hoisted: no batch axis to stack
            if croot is None:
                croot = cr
            vectors.append(params)
        cfp = croot.fingerprint()
        if cfp in self._no_hoist or cfp in self._no_batch:
            return none
        # stage the shared scan pages under the LEADER's sink (pins +
        # staging attribution); served followers fold their own
        # input-rows share below
        scans = [
            s for s in N.walk(root) if isinstance(s, N.TableScanNode)
        ]
        prev_qs = self._active_qs
        self._active_qs = qs_list[0]
        try:
            pages = [self._load_table(s) for s in scans]
        finally:
            self._active_qs = prev_qs
        in_rows = sum(int(p.num_valid) for p in pages)
        in_bytes = sum(
            int(b.data.nbytes) for p in pages for b in p.blocks
        )
        if qs_list[0] is not None:
            # undo _load_table's input fold on the leader NOW, on
            # every exit path: only lanes the batch actually SERVES
            # re-attribute the scan below — a member that falls out
            # (or a batch that fails wholesale) re-runs scalar, where
            # _load_table attributes it again
            with self._qs_mu:
                qs_list[0].input_rows -= in_rows
                qs_list[0].input_bytes -= in_bytes
        scan_ids = {id(s): i for i, s in enumerate(scans)}
        # canonical leaves correspond 1:1 by walk position (the same
        # remap discipline as the scalar path)
        leaf_types = (N.TableScanNode, N.RemoteSourceNode)
        orig_leaves = [
            x for x in N.walk(root) if isinstance(x, leaf_types)
        ]
        new_leaves = [
            x for x in N.walk(croot) if isinstance(x, leaf_types)
        ]
        cscan_ids = dict(scan_ids)
        for o, nn in zip(orig_leaves, new_leaves):
            if id(o) in scan_ids:
                cscan_ids[id(nn)] = scan_ids[id(o)]
        try:
            lanes = canonical.batch_lanes(n)
            stacked = canonical.stack_param_vectors(vectors, lanes)
        except ValueError:
            return none
        # the batched program compacts each lane to the speculative
        # WINDOW, not the full capacity bucket: the demux fetches at
        # most ``spec`` rows per lane, and a full-bucket gather per
        # lane would multiply the dominant memory traffic by the batch
        # width for rows nobody reads. The window is part of the
        # compile key (a session change recompiles, same as capacity
        # bucketing everywhere else).
        key = canonical.batch_entry_key(
            cfp, counted, offload, lanes, spec
        )
        with self._compile_mu:
            entry = self._compiled.get(key)
            fresh = entry is None
            if fresh:
                trace, msgs_cell, nodes_cell, tally = (
                    self._make_trace(
                        croot, cscan_ids, counted, False,
                        out_capacity=spec,
                    )
                )
                batched = canonical.vmap_program(trace)
                batched.__name__ = _program_name(croot, cfp)
                entry = (
                    jax.jit(batched), msgs_cell, nodes_cell, tally
                )
                self._compiled[key] = entry
        REGISTRY.counter(
            "compile.cache_miss" if fresh else "compile.cache_hit"
        ).update()
        fn, msgs_cell, nodes_cell, _builds = entry
        t_disp = time.perf_counter()
        try:
            with self._device_scope(), tracing.phase("dispatch"):
                (
                    page, flags_arr, err_arr, cnt_arr, dyn_arr,
                    true_n_arr,
                ) = fn(pages, stacked)
        except Exception:
            # the batched form failed to trace/execute (a kernel with
            # no batching rule): retire the SHAPE from batching —
            # scalar serving still works, so this must never raise
            self._no_batch.add(cfp)
            with self._compile_mu:
                self._compiled.pop(key, None)
            return none
        k = int(page.blocks[0].data.shape[1]) if page.blocks else 0
        # ONE device->host fetch for every lane: control outputs +
        # per-lane TRUE counts + the windowed k-row prefix per block
        leaves: List = [
            flags_arr, err_arr, cnt_arr, dyn_arr, true_n_arr,
        ]
        for blk in page.blocks:
            leaves.append(blk.data[:, :k])
            if blk.valid is not None:
                leaves.append(blk.valid[:, :k])
        t_disped = time.perf_counter()
        with tracing.phase("fetch", site="microbatch"):
            fetched = jax.device_get(leaves)
        DEVICE.count_sync()
        t_fetched = time.perf_counter()
        # device-plane accounting: the batch is ONE real dispatch +
        # one fetch on the process counters; per-lane attribution
        # happens below for SERVED lanes only (each answer required
        # this dispatch), with fetch bytes split evenly
        batch_d2h = 0
        if DEVICE.enabled:
            batch_d2h = sum(
                int(getattr(leaf, "nbytes", 0)) for leaf in fetched
            )
            DEVICE.count_dispatch()
            DEVICE.count_program_out(_static_page_nbytes(page))
            DEVICE.count_d2h(batch_d2h)
            if fresh:
                DEVICE.count_compile((t_disped - t_disp) * 1000.0)
        flags_np, err_np, cnt_np, dyn_np, nv_np = fetched[:5]
        prefix = fetched[5:]
        wall_ms = (t_fetched - t_disp) * 1000.0
        device_ms = (t_fetched - t_disped) * 1000.0
        results: List = [None] * n
        served = 0
        for i in range(n):
            if err_np.size and err_np[i].any():
                continue  # scalar path raises the member's real error
            if flags_np.size and flags_np[i].any():
                continue  # capacity overflow: scalar path retries
            n_i = int(nv_np[i])
            if n_i > k:
                continue  # over-window output: scalar materialization
            lane_page = _page_from_prefix(
                page, [leaf[i] for leaf in prefix], n_i
            )
            if host_ops:
                lane_page = apply_host_ops(lane_page, host_ops)
            results[i] = QueryResult(plan0.output_names, lane_page)
            served += 1
            qs = qs_list[i]
            if qs is None:
                continue
            with self._qs_mu:
                qs.batched = True
                qs.batch_size = n
                qs.output_rows = int(lane_page.num_valid)
                qs.execution_ms += wall_ms / n
                if fresh:
                    qs.compile_cache_hit = False
                # every SERVED lane scanned the shared pages (the
                # leader's staging-time fold was undone above)
                qs.input_rows += in_rows
                qs.input_bytes += in_bytes
                # device attribution: the shared dispatch, counted
                # once per served lane (micro-batch lanes have no
                # stages, so roll_up's delta fold never races this)
                if DEVICE.enabled:
                    qs.device_dispatches += 1
                    qs.device_d2h_bytes += batch_d2h // n
                    if fresh:
                        qs.device_compiles += 1
            if counted and nodes_cell:
                self._active_qs = qs
                try:
                    self._fold_operator_stats(
                        nodes_cell,
                        cnt_np[i],
                        wall_ms=wall_ms / n,
                        device_ms=device_ms / n,
                        prog=croot,
                    )
                    if dyn_np.size:
                        pruned = int(dyn_np[i].sum())
                        if pruned:
                            REGISTRY.counter(
                                "dynamic_filter.rows_pruned"
                            ).update(pruned)
                            self._fold_dyn_stat(
                                "dynamic_filter_rows_pruned", pruned
                            )
                finally:
                    self._active_qs = prev_qs
        REGISTRY.counter("serving.batches").update()
        REGISTRY.counter("serving.batched_statements").update(served)
        REGISTRY.distribution("serving.batch_occupancy").add(served)
        return results

    def _run_with_pages(
        self,
        root: N.PlanNode,
        scans: List[N.PlanNode],
        pages: List[Page],
        stats_out: Optional[List] = None,
        fetch_result: bool = True,
        cut_agg: bool = False,
    ) -> Page:
        """Run the compiled whole-plan program, retrying on capacity
        overflow: :meth:`_resolve`, :meth:`_dispatch`, :meth:`_collect`
        in a row (a task that runs many batches through one fragment
        resolves once and repeats the other two).
        With ``stats_out``, per-node row counters are traced as
        extra outputs (EXPLAIN ANALYZE); stats_out receives
        (walk_id, label, rows, capacity) records.

        ``fetch_result=False`` (stage-at-a-time execution): the result
        stays ON DEVICE — only the control flags + live count are
        fetched (one round trip) — and the return value is
        ``(device_page_rebucketed, n)`` instead of a host page."""
        resolved = self._resolve(
            root, scans, analyzed=stats_out is not None, cut_agg=cut_agg
        )
        return self._collect(
            self._dispatch(resolved, pages),
            stats_out=stats_out,
            fetch_result=fetch_result,
        )

    def _resolve(
        self,
        root: N.PlanNode,
        scans: List[N.PlanNode],
        analyzed: bool = False,
        prog: Optional[N.PlanNode] = None,
        batches: int = 1,
        cut_agg: bool = False,
    ) -> "_Resolved":
        """What is the same for every batch a task runs through
        ``root``: the canonical root and its fingerprint, the parameter
        vector, the page indices re-mapped onto the canonical leaves
        and the compiled entry. ``prog`` is the program-instance token
        of an overflow retry (the unscaled root). A caller that will
        dispatch several ``batches`` gets the parameter vector on the
        device: handed over from the host, each of its scalars is a
        transfer of its own at every call (0.8 ms of a 2.3 ms Q1 call
        on the chip; the one put costs about as much: PERF.md §7).
        ``cut_agg``: ``root`` is the partial step of a cut aggregation
        run over one split batch, so each page it returns is counted
        (``DEVICE.count_agg_page``)."""
        from presto_tpu.plan import canonical

        scan_ids = {id(s): i for i, s in enumerate(scans)}
        # per-operator observability (exec/stats.OperatorStats): trace
        # the per-node row counters on EVERY run, not just EXPLAIN
        # ANALYZE — the history store and QueryInfo read them. Part of
        # the compile key: flipping enable_operator_stats compiles the
        # exact pre-PR program (no counter outputs)
        counted = analyzed or bool(
            self.session.get("enable_operator_stats")
        )
        # key by structural fingerprint, not object identity: every
        # execute_plan rebuilds the tree (prune/bind), and a retrace
        # per call would redo XLA cache lookups costing seconds.
        # The fingerprint is taken over the CANONICAL root —
        # literals hoisted into RuntimeParam slots whose values ride
        # in as the program's parameter vector — so literal-variant
        # plans of one shape share ONE compiled program
        # (plan/canonical.py; enable_plan_cache=false keeps the
        # pre-cache literal fingerprints bit-for-bit).
        offload = self.session.get("tpu_offload")
        bound = getattr(self._bound_local, "value", None)
        # analyzed (EXPLAIN ANALYZE) keeps literals in place: node
        # labels print the predicate exprs, and those must show the
        # query's actual values
        hoist = (
            bool(self.session.get("enable_plan_cache"))
            and not analyzed
        )
        croot, params = canonical.hoist_params(
            root, bound=bound, hoist_literals=hoist
        )
        # fingerprint() is a full-tree repr: computed ONCE here (it
        # keys the compile cache, the no-hoist check, and the failure
        # handler of _dispatch)
        cfp = croot.fingerprint()
        if croot is not root and cfp in self._no_hoist:
            # this shape's parameterized form failed to trace once:
            # permanent classic literal-form lane
            croot, params = canonical.bind_literal_root(
                root, bound
            ), ()
            cfp = croot.fingerprint()
        if croot is root:
            cscan_ids = scan_ids
        else:
            # the canonical tree is a rebuilt copy: its leaves are
            # NEW objects wherever an ancestor/field changed, but
            # the rewrite preserves tree shape, so leaves correspond
            # 1:1 by walk position — remap the identity-keyed page
            # indices onto the canonical leaves
            leaf_types = (N.TableScanNode, N.RemoteSourceNode)
            orig_leaves = [
                n for n in N.walk(root) if isinstance(n, leaf_types)
            ]
            new_leaves = [
                n
                for n in N.walk(croot)
                if isinstance(n, leaf_types)
            ]
            cscan_ids = dict(scan_ids)
            for o, nn in zip(orig_leaves, new_leaves):
                if id(o) in scan_ids:
                    cscan_ids[id(nn)] = scan_ids[id(o)]
        key = (cfp, analyzed, counted, offload)
        with self._compile_mu:
            entry = self._compiled.get(key)
            fresh = entry is None
            if fresh:
                trace, msgs_cell, nodes_cell, tally = (
                    self._make_trace(croot, cscan_ids, counted, analyzed)
                )
                trace.__name__ = _program_name(croot, cfp)
                entry = (jax.jit(trace), msgs_cell, nodes_cell, tally)
                self._compiled[key] = entry
        fn, msgs_cell, nodes_cell, tally = entry
        if batches > 1 and params:
            with self._device_scope():
                params = stage_params(params)
        return _Resolved(
            root=root,
            scans=scans,
            # program-instance token for operator-stats folding:
            # streamed batches re-enter with the SAME root object
            # (their folds sum), while distinct programs of one query —
            # scalar-subquery pre-passes, sibling fragments — are
            # different objects even when their shapes (and walk
            # positions) coincide
            prog=root if prog is None else prog,
            analyzed=analyzed,
            counted=counted,
            key=key,
            params=params,
            fn=fn,
            msgs_cell=msgs_cell,
            nodes_cell=nodes_cell,
            tally=tally,
            fresh=fresh,
            cut_agg=cut_agg,
        )

    def _dispatch(
        self, resolved: "_Resolved", pages: List[Page]
    ) -> "_Pending":
        """Launch the resolved program over one batch's pages and
        return its outputs on the device, unread: the call does not
        wait for the program (:meth:`_collect` does)."""
        from presto_tpu.utils.metrics import REGISTRY

        while True:
            # compile-amortization counters (bench.py runs read these):
            # a miss pays trace + XLA compile; steady state is all
            # hits. jit compiles lazily, at the entry's FIRST call
            fresh, resolved.fresh = resolved.fresh, False
            REGISTRY.counter(
                "compile.cache_miss" if fresh else "compile.cache_hit"
            ).update()
            if fresh and self._active_qs is not None:
                self._active_qs.compile_cache_hit = False
            t_disp = time.perf_counter()
            try:
                with self._device_scope(), tracing.phase("dispatch"):
                    page, flags_arr, err_arr, cnt_arr, dyn_arr = (
                        resolved.fn(pages, resolved.params)
                    )
                break
            except Exception:
                if not resolved.params:
                    raise
                # the canonical form failed (usually a hoisted
                # literal feeding a structure-demanding kernel at
                # trace time): retire it and recompile this shape
                # in literal form — a query the literal path can
                # run must never fail because of hoisting. Guarded
                # on params alone (not _no_hoist membership): a
                # CONCURRENT thread that fetched the same entry
                # before the first failure retired it must also
                # fall back, not re-raise. The literal lane always
                # has params=(), so this cannot loop. The task's
                # later batches take it too: ``resolved`` is theirs.
                self._no_hoist.add(resolved.key[0])
                with self._compile_mu:
                    self._compiled.pop(resolved.key, None)
                literal = self._resolve(
                    resolved.root, resolved.scans,
                    analyzed=resolved.analyzed, prog=resolved.prog,
                    cut_agg=resolved.cut_agg,
                )
                resolved.__dict__.update(literal.__dict__)
        t_disped = time.perf_counter()
        # device-plane accounting (utils/telemetry.py): one real
        # dispatch; a fresh entry's dispatch window carries trace +
        # XLA compile (documented approximation). Counted for runs
        # that overflow too: they still dispatched.
        if DEVICE.enabled:
            compile_ms = (t_disped - t_disp) * 1000.0 if fresh else 0.0
            DEVICE.count_dispatch()
            DEVICE.count_program_out(_static_page_nbytes(page))
            # the builds the program sorts itself, the rows its
            # aggregations move and the key lanes its probes sort, once a
            # dispatch
            for slots in resolved.tally.builds:
                DEVICE.count_join_build(slots)
            DEVICE.count_agg_row_moves(resolved.tally.agg_row_moves)
            DEVICE.count_join_probe_lanes(resolved.tally.join_probe_lanes)
            if fresh:
                DEVICE.count_compile(compile_ms)
            self._fold_device_stat(
                device_dispatches=1,
                device_compiles=1 if fresh else 0,
                device_compile_ms=compile_ms,
            )
        return _Pending(
            resolved=resolved,
            pages=pages,
            page=page,
            control=(flags_arr, err_arr, cnt_arr, dyn_arr),
            t_disp=t_disp,
        )

    def _collect(
        self,
        pending: "_Pending",
        stats_out: Optional[List] = None,
        fetch_result: bool = True,
        tries: int = 0,
        cut: bool = True,
    ):
        """Read a dispatched batch. Round-trip discipline: ONE
        ``jax.device_get`` for all control outputs + the result row
        count + a SPECULATIVE prefix of every result block. When the
        result fits the speculative window (the common aggregate /
        top-N shape) the batch is ONE round trip total; otherwise
        materialize_page fetches the full live prefix. What a read
        costs on the chip is its leaves, not its being a round trip
        (my chip runs, PR 32: 76 us of host time to issue a leaf's
        copy; fifteen reads of 25 leaves 33.2 ms, one read of 375
        leaves 31.0 ms — PERF.md §7). An error flag raises; a capacity
        overflow runs the batch again at four times the capacities.
        ``fetch_result=False`` leaves the page on the device as
        ``(page, rows)``, cut to the bucket of its rows unless ``cut``
        is False."""
        page = pending.page
        res = pending.resolved
        spec = (
            min(
                int(self.session.get("speculative_result_rows")),
                page.capacity,
            )
            if fetch_result
            else 0
        )
        leaves: List = [*pending.control, page.num_valid]
        if spec > 0:
            leaves.extend(page.prefix_leaves(spec))
        t_wait = time.perf_counter()
        with tracing.phase("fetch", site="control"):
            fetched = jax.device_get(leaves)
        t_fetched = time.perf_counter()
        if DEVICE.enabled:
            d2h = sum(
                int(getattr(leaf, "nbytes", 0)) for leaf in fetched
            )
            DEVICE.count_sync()
            DEVICE.count_d2h(d2h)
            self._fold_device_stat(device_d2h_bytes=d2h)
        flags_np, err_np, cnt_np, dyn_np, n_out = fetched[:5]
        for msg, flag in zip(res.msgs_cell, err_np):
            if bool(flag):
                raise ExecutionError(msg)
        if flags_np.any():
            tries += 1
            if tries >= self.MAX_RETRIES:
                raise ExecutionError(
                    "capacity overflow persisted after retries "
                    "(join fan-out or group count beyond buckets)"
                )
            if self._active_qs is not None:
                with self._qs_mu:
                    self._active_qs.retries += 1
            scaled = self._resolve(
                _scale_capacities(res.root, 4), res.scans,
                analyzed=res.analyzed, prog=res.prog,
                cut_agg=res.cut_agg,
            )
            return self._collect(
                self._dispatch(scaled, pending.pages),
                stats_out=stats_out,
                fetch_result=fetch_result,
                tries=tries,
                cut=cut,
            )
        if res.analyzed:
            stats_out.clear()
            stats_out.extend(
                (walk_id, label, int(c), cap)
                for (
                    walk_id, label, cap, _nb, _dp, _fp, _ch
                ), c in zip(res.nodes_cell, cnt_np)
            )
        if res.counted and res.nodes_cell:
            # fold per-operator actuals into the active stats
            # sink (TaskStats on workers, QueryStats locally);
            # only the SUCCESSFUL run counts — overflow retries
            # re-execute the same rows
            self._fold_operator_stats(
                res.nodes_cell,
                cnt_np,
                wall_ms=(t_fetched - pending.t_disp) * 1000.0,
                device_ms=(t_fetched - t_wait) * 1000.0,
                prog=res.prog,
            )
        if dyn_np.size:
            # attribute only on the SUCCESSFUL run: overflow
            # retries re-execute the filter over the same rows
            pruned = int(dyn_np.sum())
            if pruned:
                from presto_tpu.utils.metrics import REGISTRY

                REGISTRY.counter(
                    "dynamic_filter.rows_pruned"
                ).update(pruned)
                self._fold_dyn_stat(
                    "dynamic_filter_rows_pruned", pruned
                )
        n = int(n_out)
        # output capacity-bucket padding waste: the rows this
        # program computed over vs the rows anyone will read
        if DEVICE.enabled:
            DEVICE.count_padding(n, page.capacity)
            if res.cut_agg:
                DEVICE.count_agg_page(n, page.capacity)
            self._fold_device_stat(
                device_pad_rows=page.capacity - n,
                device_live_rows=n,
            )
        if not fetch_result:
            if isinstance(page, JoinBuild):
                # a build side in key order (N.JoinBuildNode): the
                # bucket of the rows a probe can match, one program
                k = bucket_capacity(n)
                if k < page.capacity:
                    with self._device_scope(), tracing.phase("dispatch"):
                        page = _cut_program(page, k)
                    if DEVICE.enabled:
                        DEVICE.count_dispatch()
                        self._fold_device_stat(device_dispatches=1)
                return page, n
            if not cut:
                # the page as the program left it, for a program of
                # static shapes that reads many (exchange_spi.device_merge)
                return page, n
            from presto_tpu.page import pad_capacity

            return pad_capacity(page, bucket_capacity(n)), n
        if 0 < spec and n <= spec:
            return _page_from_prefix(page, fetched[5:], n)
        return materialize_page(page, n)

    def _fold_dyn_stat(self, attr: str, n: int) -> None:
        """Add ``n`` to the active sink's dynamic-filter counter under
        the right lock(s): ``_qs_mu`` serializes the threads that
        share one sink, and a QueryStats sink ALSO folds worker-task
        deltas into the same fields under its ``_roll_lock`` (stats.roll_up)
        — both writers must serialize on it or an increment silently
        vanishes. The ONE implementation for every runner-side
        dynamic-filter stat write."""
        qs = self._active_qs
        if qs is None:
            return
        with self._qs_mu:
            sink_lock = getattr(qs, "_roll_lock", None)
            if sink_lock is not None:
                with sink_lock:
                    setattr(qs, attr, getattr(qs, attr) + n)
            else:
                setattr(qs, attr, getattr(qs, attr) + n)

    def _fold_device_stat(self, **fields) -> None:
        """Add device-plane quantities (utils/telemetry.py families)
        to the active sink under the ``_fold_dyn_stat`` locking
        discipline — a QueryStats sink also folds worker-task deltas
        into these same fields under its ``_roll_lock``. No-op when
        the telemetry plane is disabled, so per-query attribution
        tracks the process counters exactly (zero-delta off)."""
        qs = self._active_qs
        if qs is None or not DEVICE.enabled:
            return
        with self._qs_mu:
            sink_lock = getattr(qs, "_roll_lock", None)
            if sink_lock is not None:
                with sink_lock:
                    for attr, n in fields.items():
                        if n:
                            setattr(qs, attr, getattr(qs, attr) + n)
            else:
                for attr, n in fields.items():
                    if n:
                        setattr(qs, attr, getattr(qs, attr) + n)

    def _fold_operator_stats(
        self,
        cells,
        counts,
        wall_ms: float,
        device_ms: float,
        prog=None,
    ) -> None:
        """Merge one program execution's per-node actuals into the
        active stats sink's ``operators`` list, keyed by node instance
        (program identity + walk position + canonical sub-fingerprint)
        — streamed/worker batches of one program SUM into the same
        OperatorStats, while same-shape nodes in DIFFERENT programs of
        one query (scalar-subquery pre-passes reuse walk positions)
        stay separate instead of teaching the history store multiplied
        rows. ``prog`` is pinned on the sink so its id can't be reused
        by a later program's tree within the query. The whole program's dispatch->
        fetch window is attributed to the program ROOT operator (XLA
        fuses across operator boundaries; there is no per-operator
        device clock). Locked like every other shared-sink fold."""
        from presto_tpu.exec.stats import OperatorStats

        qs = self._active_qs
        if qs is None or not hasattr(qs, "operators"):
            return
        rows_by_walk = {
            cell[0]: int(c) for cell, c in zip(cells, counts)
        }
        root_walk = min(rows_by_walk)
        with self._qs_mu:
            index = qs.__dict__.get("_op_index")
            if index is None:
                index = {}
                qs.__dict__["_op_index"] = index
            if prog is not None:
                qs.__dict__.setdefault("_op_pins", {})[
                    id(prog)
                ] = prog
            for (
                walk_id, label, cap, nbytes, depth, fp, child_ids
            ), c in zip(cells, counts):
                # instance key: batches of ONE program sum (same
                # program + walk position), while two distinct
                # same-shape nodes — a self-join's two scans in one
                # program, or the same subtree across sibling
                # programs — stay separate; summing them would teach
                # the history store a multiple of the true cardinality
                key = (id(prog), walk_id, fp or label)
                op = index.get(key)
                if op is None:
                    op = OperatorStats(
                        node_id=walk_id,
                        label=label,
                        fingerprint=fp,
                        depth=depth,
                    )
                    index[key] = op
                    qs.operators.append(op)
                rows = int(c)
                op.output_rows += rows
                op.batches += 1
                op.output_capacity = max(op.output_capacity, cap)
                op.peak_page_bytes = max(op.peak_page_bytes, nbytes)
                op.input_rows += (
                    sum(rows_by_walk.get(ci, 0) for ci in child_ids)
                    if child_ids
                    else rows  # leaves read what they emit
                )
                if walk_id == root_walk:
                    op.wall_ms += wall_ms
                    op.device_ms += device_ms

    def _note_spilled(self, nbytes: int) -> None:
        """Attribute host-spill restage bytes to the active stats sink
        (the split cache's ``on_restage`` hook)."""
        qs = self._active_qs
        if qs is None:
            return
        with self._qs_mu:
            qs.spilled_bytes = (
                getattr(qs, "spilled_bytes", 0) + int(nbytes)
            )

    def _note_cache_hit(self) -> None:
        """Attribute one split-cache hit to the active stats sink."""
        if self._active_qs is not None:
            with self._qs_mu:
                self._active_qs.staging_cache_hits = (
                    getattr(self._active_qs, "staging_cache_hits", 0) + 1
                )

    def _note_pinned_key(self, key) -> None:
        """Record a cache key pinned on behalf of the active query so
        :meth:`release_pins` can drop it when the query/task ends."""
        qs = self._active_qs
        if qs is None:
            return
        with self._qs_mu:
            pins = getattr(qs, "_pinned_keys", None)
            if pins is None:
                pins = []
                qs._pinned_keys = pins
            pins.append(key)

    def release_pins(self, qs) -> None:
        """Unpin every whole-table cache entry ``qs`` pinned (the
        query/task-end twin of the per-batch release in stage_split).
        Idempotent; safe for stats sinks that never pinned."""
        if qs is None:
            return
        with self._qs_mu:
            keys = getattr(qs, "_pinned_keys", None) or []
            if keys:
                qs._pinned_keys = []
        for k in keys:
            self.split_cache.unpin(k)

    def _load_table(self, scan: N.TableScanNode) -> Page:
        # constraint is part of the identity: a partition-pruned page
        # must never serve an unconstrained (or differently-constrained)
        # scan of the same table; the "table" tag keeps whole-table
        # entries distinct from split-batch entries in the one cache
        key = (
            scan.handle,
            scan.columns,
            scan.constraint,
            self.session.get("tpu_offload"),
            "table",
        )
        cacheable = self.catalogs.get(scan.handle.catalog).cacheable()
        # pin for the active query's lifetime: eviction must not drop
        # the page's pool accounting while a plan is executing over it
        # (released by release_pins at query/task end)
        pin = cacheable and self._active_qs is not None
        page = (
            self.split_cache.get(key, pin=pin) if cacheable else None
        )
        if page is not None:
            self._note_cache_hit()
            if pin:
                self._note_pinned_key(key)
        if page is None:
            from presto_tpu.utils.metrics import REGISTRY

            t0 = time.perf_counter()
            with tracing.phase("staging", site="load_table"):
                merged = self._load_merged_payload(scan)
                with self._device_scope():
                    page = stage_page(merged, dict(scan.schema))
            nbytes = _page_nbytes(page)
            REGISTRY.distribution("staging.bytes").add(nbytes)
            # per-query h2d attribution (the process counter lives in
            # staging.stage_page); cache hits above transferred nothing
            self._fold_device_stat(device_h2d_bytes=nbytes)
            cached = cacheable and self.split_cache.put(
                key, page, nbytes, reserve_required=True, pin=pin
            )
            if cached and pin:
                self._note_pinned_key(key)
            if not cached and self.memory_pool is not None:
                # not cache-owned (non-cacheable connector, or bigger
                # than the cache budget): account under the query
                override = getattr(self._owner_override, "value", None)
                owner = override or (
                    self._active_qs.query_id
                    if self._active_qs is not None
                    else "adhoc"
                )
                self.memory_pool.reserve(owner, nbytes)
            if self._active_qs is not None:
                self._active_qs.staging_ms += (
                    time.perf_counter() - t0
                ) * 1000.0
        if self._active_qs is not None:
            self._active_qs.input_rows += int(page.num_valid)
            self._active_qs.input_bytes += sum(
                int(b.data.nbytes) for b in page.blocks
            )
        return page

    def stage_split(
        self,
        scan: N.TableScanNode,
        lo: int,
        hi: int,
        capacity: int,
        owner: Optional[str] = None,
        page_source=None,
    ) -> Tuple[Page, object]:
        """Stage ONE split batch [lo, hi) of a scan to device at a
        fixed capacity, column by column through the device-resident
        split cache: each of the scan's columns is looked up on its
        own, only the columns that missed are read from the connector
        and staged, every staged column is offered to the cache, and
        the batch's ``Page`` is assembled from the parts. Statements
        with different column sets over one table share what overlaps,
        and a repeated pass skips the connector read AND the
        host->device transfer (SURVEY.md §5.7: the table cache at split
        granularity). Whether a column stays resident is the cache's
        answer (its byte budget, 0 keeps nothing; a full, all-pinned
        cache admits nothing); a connector whose data may change under
        the cache (``cacheable()`` false) is never looked up or kept,
        the rule :meth:`_load_table` applies.

        Returns ``(page, release)``: the caller invokes ``release()``
        once the batch's device execution is done. With an ``owner``,
        every cache-served (or freshly cached) column is PINNED for
        that window — eviction must not drop its pool accounting while
        it is live on device — and release unpins them; columns that
        were not admitted reserve their bytes under ``owner`` and
        release returns them. Without an owner nothing is held per
        batch and release does nothing.

        The pushed constraint is deliberately NOT part of the identity:
        split page sources read raw split ranges (constraints act at
        enumeration/filter time), so the staged batch is
        constraint-independent.

        ``page_source(columns)`` overrides the connector read of the
        missing columns (the worker routes it through its
        ``_load_range`` hook)."""
        from presto_tpu.connectors.spi import ConnectorSplit
        from presto_tpu.utils.metrics import REGISTRY

        conn = self.catalogs.get(scan.handle.catalog)
        cacheable = conn.cacheable()
        schema = dict(scan.schema)
        offload = self.session.get("tpu_offload")
        keys = {
            c: (scan.handle, c, lo, hi, capacity, offload) for c in schema
        }
        # owner callers (worker drivers) release per batch; without an
        # owner, an active query still pins — released wholesale at
        # query end (release_pins) — so pressure eviction never
        # un-accounts a column some plan is executing over
        per_batch = owner is not None
        pin = per_batch or self._active_qs is not None
        parts, pinned = {}, []

        def unpin():
            for key in pinned:
                self.split_cache.unpin(key)

        def resident(c, part):
            parts[c] = part
            if per_batch:
                pinned.append(keys[c])
            elif pin:
                self._note_pinned_key(keys[c])

        try:
            if cacheable:
                for c, key in keys.items():
                    got = self.split_cache.get(key, pin=pin)
                    if got is not None:
                        resident(c, got)
                DEVICE.count_stage_columns(
                    len(parts), len(keys) - len(parts)
                )
            missing = [c for c in schema if c not in parts]
            uncached = 0
            if missing:
                t0 = time.perf_counter()
                with tracing.phase("staging", site="stage_column"):
                    payload = (
                        page_source(missing)
                        if page_source is not None
                        else conn.create_page_source(
                            ConnectorSplit(scan.handle, lo, hi), missing
                        )
                    )
                    with self._device_scope():
                        staged = columns_of_page(stage_page(
                            payload,
                            {c: schema[c] for c in missing},
                            capacity=capacity,
                        ))
                staged_bytes = 0
                for c, part in staged.items():
                    nbytes = block_nbytes(part.block)
                    staged_bytes += nbytes
                    # a cache-owned column is reserved under the shared
                    # owner via try_reserve (the staged column serves
                    # THIS batch either way; a full pool just means it
                    # isn't cached — a cache fill never kills a query)
                    if cacheable and self.split_cache.put(
                        keys[c], part, nbytes, pin=pin
                    ):
                        resident(c, part)
                    else:
                        parts[c] = part
                        uncached += nbytes
                REGISTRY.distribution("staging.bytes").add(staged_bytes)
                # per-query h2d attribution of the split transfer
                # (resident columns moved nothing)
                self._fold_device_stat(device_h2d_bytes=staged_bytes)
                if self._active_qs is not None:
                    # locked: the prefetch thread and the thread
                    # running the batches share one stats sink
                    with self._qs_mu:
                        self._active_qs.staging_ms += (
                            time.perf_counter() - t0
                        ) * 1000.0
            else:
                self._note_cache_hit()
            if not per_batch or self.memory_pool is None:
                uncached = 0
            if uncached:
                # live (uncached) residency accounts to the query
                self.memory_pool.reserve(owner, uncached)
        except BaseException:
            unpin()
            raise

        def release():
            unpin()
            if uncached:
                self.memory_pool.release(owner, uncached)

        return page_of_columns(tuple(schema), parts), release

    def _load_merged_payload(self, scan: N.TableScanNode) -> Dict:
        """Fetch all splits of a scan and merge their column payloads.
        The scan's pushed constraint reaches the connector here (hive
        partition pruning; other connectors ignore it)."""
        conn = self.catalogs.get(scan.handle.catalog)
        src = conn.get_splits(
            scan.handle,
            target_split_rows=1 << 22,
            constraint=scan.constraint,
        )
        datas = []
        while not src.exhausted:
            for split in src.next_batch(64):
                datas.append(
                    conn.create_page_source(split, list(scan.columns))
                )
        return _merge_split_payloads(datas, list(scan.columns))




def _count_param_markers(node) -> int:
    n = 0
    if isinstance(node, ast.ParamMarker):
        return 1
    if not isinstance(node, ast.Node):
        return 0
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, ast.Node):
            n += _count_param_markers(v)
        elif isinstance(v, tuple):
            for x in v:
                if isinstance(x, ast.Node):
                    n += _count_param_markers(x)
                elif isinstance(x, tuple):
                    for y in x:
                        if isinstance(y, ast.Node):
                            n += _count_param_markers(y)
    return n


def _bind_param_markers(node, params):
    """Replace ? markers (by index) with the EXECUTE arguments."""
    if isinstance(node, ast.ParamMarker):
        return params[node.index]
    if not isinstance(node, ast.Node):
        return node
    kwargs = {}
    changed = False
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, ast.Node):
            nv = _bind_param_markers(v, params)
        elif isinstance(v, tuple):
            nv = tuple(
                _bind_param_markers(x, params)
                if isinstance(x, ast.Node)
                else (
                    tuple(
                        _bind_param_markers(y, params)
                        if isinstance(y, ast.Node)
                        else y
                        for y in x
                    )
                    if isinstance(x, tuple)
                    else x
                )
                for x in v
            )
        else:
            nv = v
        kwargs[f.name] = nv
        changed |= nv is not v
    return dataclasses.replace(node, **kwargs) if changed else node


#: memory-pool reservation unit for staged/cached pages (ONE
#: implementation: exec.staging.page_nbytes)
_page_nbytes = page_nbytes


def _page_from_prefix(page: Page, prefix_leaves, n: int) -> Page:
    """Host Page from an ALREADY-FETCHED speculative prefix (the
    single-round-trip fast path of _run_with_pages). Same re-padding
    discipline as materialize_page: capacity rounds up to the
    power-of-two bucket so downstream programs hit the compile cache."""
    fetched = iter(prefix_leaves)
    cap = bucket_capacity(n)
    blocks = []
    for blk in page.blocks:
        if blk.dtype.is_map or blk.dtype.is_row:
            # leaf order mirrors Page.prefix_leaves: [offsets[:n+1]]
            # (map only), per child data (+child valid), parent valid
            offsets = None
            if blk.dtype.is_map:
                opref = next(fetched)
                offsets = np.zeros((cap + 1,), np.int32)
                offsets[: n + 1] = opref[: n + 1]
                offsets[n + 1:] = offsets[n]
            children = []
            for ch in blk.children:
                chd = np.asarray(next(fetched))
                chv = None
                if ch.valid is not None:
                    chv = np.asarray(next(fetched))
                if blk.dtype.is_row:
                    # row children are row-capacity blocks: re-pad
                    d = np.zeros(
                        (cap,) + chd.shape[1:], page_np_dtype(ch)
                    )
                    d[:n] = chd[:n]
                    v = None
                    if chv is not None:
                        v = np.zeros((cap,), bool)
                        v[:n] = chv[:n]
                    chd, chv = d, v
                children.append(
                    dataclasses.replace(ch, data=chd, valid=chv)
                )
            if blk.valid is not None:
                vpref = next(fetched)
                valid = np.zeros((cap,), bool)
                valid[:n] = vpref[:n]
            else:
                valid = None
            blocks.append(
                dataclasses.replace(
                    blk,
                    data=np.zeros((cap, 0), np.int8),
                    valid=valid,
                    offsets=offsets,
                    children=tuple(children),
                )
            )
            continue
        if blk.offsets is not None:
            # array block leaves: offsets[:n+1] + the full values array
            opref = next(fetched)
            vals = next(fetched)
            offsets = np.zeros((cap + 1,), np.int32)
            offsets[: n + 1] = opref[: n + 1]
            offsets[n + 1:] = offsets[n]  # padding rows read empty
            if blk.valid is not None:
                vpref = next(fetched)
                valid = np.zeros((cap,), bool)
                valid[:n] = vpref[:n]
            else:
                valid = None
            blocks.append(
                dataclasses.replace(
                    blk,
                    data=np.asarray(vals),
                    valid=valid,
                    offsets=offsets,
                )
            )
            continue
        pref = next(fetched)
        data = np.zeros((cap,) + pref.shape[1:], page_np_dtype(blk))
        data[:n] = pref[:n]
        if blk.valid is not None:
            vpref = next(fetched)
            valid = np.zeros((cap,), bool)
            valid[:n] = vpref[:n]
        else:
            valid = None
        blocks.append(dataclasses.replace(blk, data=data, valid=valid))
    return Page(
        blocks=tuple(blocks),
        num_valid=np.int32(n),
        names=page.names,
    )


@functools.lru_cache(maxsize=None)
def _prefix_program(k: int):
    """ONE program that cuts every block of a page to its first ``k``
    rows (``Page.prefix_leaves``). Sliced eagerly, a page cost a device
    program a leaf, and an exact length a compile of each: Q15 at SF10
    reads 120 partial pages of 31-34 thousand groups a statement —
    1,088 small compiles in every process's set-up, some 90 s, and 800
    of a statement's 935 device programs (my chip runs, PR 35)."""
    def page_prefix(page):
        return page.prefix_leaves(k)

    page_prefix.__name__ = f"page_prefix_{k}"
    return jax.jit(page_prefix)


#: a build side's first ``k`` rows in key order (``ops/join.cut_build``):
#: one program a bucket
_cut_program = jax.jit(cut_build, static_argnums=1)


def materialize_page(page: Page, n: int) -> Page:
    """Fetch the live prefix of a (prefix-form) device page to host in
    ONE batched transfer: cut every block on the device, in one
    program, to the power-of-two bucket of its ``n`` live rows (a
    length among few, so the program is compiled once a bucket and not
    once a row count; at most twice the live bytes cross the link),
    then a single ``jax.device_get`` for all of them. Downstream host
    work (host root stage, wire serialization, to_pylist) then runs on
    numpy with zero further device round trips.

    Capacity is re-padded host-side to the same bucket (numpy zeros —
    far cheaper than the round trip saved) so a materialized page that
    is fed back into a later program (streamed fragments) still hits
    the per-bucket compile cache."""
    if not page.blocks or page.is_host:
        return page
    k = bucket_capacity(n)
    with tracing.phase("fetch", site="materialize"):
        if k >= page.capacity:
            leaves = page.prefix_leaves(page.capacity)
        else:
            leaves = _prefix_program(k)(page)
        leaves = jax.device_get(leaves)
    DEVICE.count_sync()
    DEVICE.count_d2h(sum(int(getattr(x, "nbytes", 0)) for x in leaves))
    return _page_from_prefix(page, leaves, n)


def page_np_dtype(blk: Block):
    """numpy dtype of a block's device leaf (x64-faithful)."""
    return np.dtype(blk.data.dtype)


#: compile-cost weight per plan node: joins/aggregations/sorts/windows
#: each lower to a multi-kernel XLA subgraph (sorts dominate compile
#: time on TPU), row-wise nodes fuse away. Weights are a compile-size
#: proxy, not a runtime cost model.
_HEAVY_NODES = (
    N.JoinNode,
    N.AggregationNode,
    N.DistinctNode,
    N.SortNode,
    N.WindowNode,
    N.UnnestNode,
)


def _plan_weight(root: N.PlanNode) -> int:
    """Compile-size proxy for the stage-at-a-time cut decision. Does not
    descend into already-executed fragments (RemoteSourceNode children()
    is empty)."""
    return sum(
        6 if isinstance(n, _HEAVY_NODES) else 1 for n in N.walk(root)
    )


# ---------------------------------------------------------- trace helpers

#: nodes that only rename, reorder or cut columns: a program is named
#: after the first operator under them
_WRAPPER_NODES = (N.OutputNode, N.ProjectNode)


def _program_name(croot: N.PlanNode, cfp: str) -> str:
    """``<root operator>_<6 hex of the canonical fingerprint>``: the
    jitted fragment's name (XLA module ``jit_<name>``), so a device
    profile tells one statement's programs from another's. At most 26
    characters — the benchmark's reduction keeps 30 with ``jit_``."""
    import hashlib

    node = croot
    while isinstance(node, _WRAPPER_NODES) and node.children():
        node = node.children()[0]
    op = type(node).__name__.removesuffix("Node").lower()
    return f"{op[:19]}_{hashlib.sha1(cfp.encode()).hexdigest()[:6]}"



def _node_depths(root: N.PlanNode) -> Dict[int, int]:
    """id(node) -> tree depth under ``root`` (operator-stats
    rendering)."""
    out: Dict[int, int] = {}

    def rec(n: N.PlanNode, d: int) -> None:
        out[id(n)] = d
        for c in n.children():
            rec(c, d + 1)

    rec(root, 0)
    return out


@dataclasses.dataclass
class _Resolved:
    """A fragment root resolved to its compiled program
    (``LocalQueryRunner._resolve``): what every batch of one task
    shares. ``fresh`` is true until the entry's first dispatch."""

    root: N.PlanNode
    scans: List[N.PlanNode]
    prog: N.PlanNode
    analyzed: bool
    counted: bool
    key: tuple
    params: tuple
    fn: object
    msgs_cell: list
    nodes_cell: list
    tally: "_Tally"
    fresh: bool
    cut_agg: bool = False


@dataclasses.dataclass
class _Tally:
    """What a program's trace counted (``LocalQueryRunner._make_trace``)
    for ``_dispatch`` to add at each dispatch: the slots of each join
    build the program sorts itself, the page-sized row moves of its
    grouped aggregations (``ops.aggregation.record_row_moves``) and the
    key lanes its join probes sort (``ops.join.record_probe_lanes``)."""

    builds: List[int] = dataclasses.field(default_factory=list)
    agg_row_moves: int = 0
    join_probe_lanes: int = 0


@dataclasses.dataclass
class _Pending:
    """One dispatched batch whose outputs are still on the device
    (``LocalQueryRunner._dispatch`` -> ``_collect``): the result
    ``page`` and the four ``control`` outputs (overflow flags, error
    flags, operator row counters, dynamic-filter counts). ``pages``
    are its inputs, kept for an overflow retry."""

    resolved: _Resolved
    pages: List[Page]
    page: Page
    control: tuple
    t_disp: float


def _static_page_nbytes(page: Page) -> int:
    """Static device footprint of a (possibly traced) page: shapes and
    dtypes are fixed at trace time, so this is exact without touching
    any tracer value — the per-operator ``peak_page_bytes``."""

    def arr(a) -> int:
        try:
            n = 1
            for s in a.shape:
                n *= int(s)
            return n * np.dtype(a.dtype).itemsize
        except Exception:
            return 0

    if isinstance(page, JoinBuild):
        return sum(arr(x) for x in jax.tree_util.tree_leaves(page))
    total = 0
    for b in page.blocks:
        total += arr(b.data)
        if b.valid is not None:
            total += arr(b.valid)
        if getattr(b, "offsets", None) is not None:
            total += arr(b.offsets)
        for ch in getattr(b, "children", None) or ():
            total += arr(ch.data)
            if ch.valid is not None:
                total += arr(ch.valid)
    return total


def _stack_bools(xs: List) -> jnp.ndarray:
    if not xs:
        return jnp.zeros((0,), jnp.bool_)
    return jnp.stack([jnp.asarray(x, jnp.bool_).reshape(()) for x in xs])


def _stack_i32(xs: List) -> jnp.ndarray:
    if not xs:
        return jnp.zeros((0,), jnp.int32)
    return jnp.stack([jnp.asarray(x, jnp.int32).reshape(()) for x in xs])


#: nodes whose output rows carry cardinality SIGNAL (the history
#: store's value: scan sizes, filter selectivity, join fan-out, group
#: counts). Cardinality-preserving / structurally-bounded nodes
#: (Project, Output, Window, Sort, Limit) are skipped on the always-on
#: path — each traced counter keeps one more live scalar in the XLA
#: program, and counting every node measured ~1.5x compile time on
#: TPC-H plans. EXPLAIN ANALYZE (analyzed mode) still counts ALL nodes.
_COUNTED_NODES = (
    N.TableScanNode,
    N.RemoteSourceNode,
    N.FilterNode,
    N.JoinNode,
    N.CrossJoinNode,
    N.AggregationNode,
    N.DistinctNode,
    N.UnnestNode,
    N.UnionAllNode,
)


def _execute_node(
    node, pages, scan_ids, flags, errors, counters=None, dyn=None,
    count_all=True, builds=None,
) -> Page:
    """Execute one plan node at trace time. ``counters``, when given,
    accumulates (node, traced num_valid, capacity, static bytes) per
    counted node — the EXPLAIN ANALYZE / OperatorStats row-count
    instrumentation (stats.py); ``count_all=False`` restricts it to
    the cardinality-determining ``_COUNTED_NODES``. ``dyn``
    accumulates the traced pruned-row count of every dynamic
    FilterNode (dynamic_filter.rows_pruned observability), ``builds``
    the slots of every join build the program sorts itself
    (``device.join_build_slots``)."""
    # the operator's name on every XLA op it lowers to (nested under
    # its consumers'): a device profile says which plan node an
    # operation belongs to
    with jax.named_scope(type(node).__name__.removesuffix("Node")):
        out = _execute_node_inner(
            node, pages, scan_ids, flags, errors, counters, dyn,
            count_all, builds,
        )
    if counters is not None and (
        count_all or isinstance(node, _COUNTED_NODES)
    ):
        # capacity and page bytes are STATIC at trace time (shapes are
        # fixed); only the row count rides out as a program output
        counters.append(
            (node, out.num_valid, out.capacity,
             _static_page_nbytes(out))
        )
    return out


def _execute_node_inner(
    node, pages, scan_ids, flags, errors, counters=None, dyn=None,
    count_all=True, builds=None,
) -> Page:
    run = lambda n: _execute_node(  # noqa: E731
        n, pages, scan_ids, flags, errors, counters, dyn, count_all,
        builds,
    )

    if isinstance(node, (N.TableScanNode, N.RemoteSourceNode)):
        return pages[scan_ids[id(node)]]
    if isinstance(node, N.ValuesNode):
        return Page(
            blocks=(
                Block(
                    data=jnp.zeros((8,), jnp.int64), valid=None, dtype=T.BIGINT
                ),
            ),
            num_valid=jnp.asarray(1, jnp.int32),
            names=("$dummy",),
        )
    if isinstance(node, N.FilterNode):
        src = run(node.source)
        schema = node.source.output_schema()
        projs = [(n, E.ColumnRef(n, t)) for n, t in schema.items()]
        out = filter_project(src, node.predicate, projs)
        if dyn is not None and node.dynamic:
            dyn.append(src.num_valid - out.num_valid)
        return out
    if isinstance(node, N.ProjectNode):
        return project(run(node.source), node.projections)
    if isinstance(node, N.AggregationNode):
        out, overflow = hash_aggregate(
            run(node.source),
            node.group_keys,
            node.aggs,
            node.max_groups,
            errors_out=errors,
            key_ranges=node.key_ranges,
        )
        flags.append(overflow)
        return out
    if isinstance(node, N.DistinctNode):
        from presto_tpu.ops import distinct as distinct_op

        out, overflow = distinct_op(run(node.source), node.max_groups)
        flags.append(overflow)
        return out
    if isinstance(node, N.JoinBuildNode):
        build = join_build(
            run(node.source), node.keys, node.payload, node.key_ranges
        )
        if build.stale is not None:
            # a key left its stated range: built again on two lanes
            # (_scale_capacities), before any batch probes it
            flags.append(build.stale)
        return build
    if isinstance(node, N.JoinNode):
        probe = run(node.left)
        build = run(node.right)
        if isinstance(build, Page):
            # not prepared for this program (prepare_builds): built here
            build = join_build(
                build, node.right_keys, node.payload, node.key_ranges
            )
            if builds is not None:
                builds.append(build.capacity)
        out, overflow = hash_join(
            probe,
            build,
            node.left_keys,
            node.right_keys,
            join_type=node.join_type,
            build_payload=node.payload,
            build_unique=node.build_unique,
            out_capacity=node.out_capacity,
            payload_rename=dict(node.payload_rename),
        )
        flags.append(overflow)
        if node.residual is not None:
            schema = out.schema()
            projs = [(n, E.ColumnRef(n, t)) for n, t in schema.items()]
            out = filter_project(out, node.residual, projs)
        return out
    if isinstance(node, N.CrossJoinNode):
        left = run(node.left)
        right = run(node.right)
        if node.out_capacity is not None:
            from presto_tpu.ops.join import cross_join

            out, overflow = cross_join(left, right, node.out_capacity)
            flags.append(overflow)
            return out
        # single-row broadcast (scalar-aggregate shape); >1 row is a hard
        # error, not a capacity overflow — retries cannot fix it
        errors.append(("cross join build produced more than one row",
                       right.num_valid > 1))
        return cross_join_single_row(left, right)
    if isinstance(node, N.SortNode):
        return order_by_op(run(node.source), node.keys, limit=node.limit)
    if isinstance(node, N.LimitNode):
        return limit_op(run(node.source), node.count)
    if isinstance(node, N.WindowNode):
        return window_op(
            run(node.source), node.partition_by, node.order_by, node.calls
        )
    if isinstance(node, N.UnnestNode):
        if node.array_column is not None:
            from presto_tpu.ops import unnest_column

            out, overflow = unnest_column(
                run(node.source),
                node.array_column,
                node.out_name,
                node.out_type,
                node.ordinality_name,
                node.out_capacity,
            )
            flags.append(overflow)
            return out
        return unnest_op(
            run(node.source),
            node.elements,
            node.out_name,
            node.out_type,
            node.ordinality_name,
        )
    if isinstance(node, N.UnionAllNode):
        from presto_tpu.ops import union_all

        return union_all([run(s) for s in node.sources])
    if isinstance(node, N.OutputNode):
        src = run(node.source)
        blocks = []
        for out, col in node.columns:
            blocks.append(src.block(col))
        return Page(
            blocks=tuple(blocks),
            num_valid=src.num_valid,
            names=tuple(o for o, _ in node.columns),
            live=src.live,
        )
    raise ExecutionError(f"cannot execute {type(node).__name__}")


def cross_join_single_row(left: Page, right: Page) -> Page:
    """Cross product against a single-row right side (scalar-aggregate
    broadcast). Caller is responsible for flagging right.num_valid > 1."""
    right = compact_page(right)  # row 0 must really be the single row
    blocks = list(left.blocks)
    names = list(left.names)
    for bname, blk in zip(right.names, right.blocks):
        v = blk.valid[0] if blk.valid is not None else None
        data = jnp.broadcast_to(blk.data[0], (left.capacity,))
        valid = (
            None if v is None else jnp.broadcast_to(v, (left.capacity,))
        )
        blocks.append(dataclasses.replace(blk, data=data, valid=valid))
        names.append(bname)
    num = jnp.where(right.num_valid > 0, left.num_valid, 0).astype(jnp.int32)
    live = (
        None
        if left.live is None
        else left.live & (right.num_valid > 0)
    )
    return Page(
        blocks=tuple(blocks), num_valid=num, names=tuple(names), live=live
    )


# ----------------------------------------------------------- param binding


def _substitute_params_expr(e: E.Expr, bindings) -> E.Expr:
    if isinstance(e, E.Param):
        lit = bindings.get(e.param_id)
        if lit is None:
            raise ExecutionError(f"unbound param {e.param_id}")
        return lit
    if not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, E.Expr):
            nv = _substitute_params_expr(v, bindings)
            if nv is not v:
                changes[f.name] = nv
        elif isinstance(v, tuple):
            nt = tuple(
                _substitute_params_expr(x, bindings)
                if isinstance(x, E.Expr)
                else (
                    tuple(
                        _substitute_params_expr(y, bindings)
                        if isinstance(y, E.Expr)
                        else y
                        for y in x
                    )
                    if isinstance(x, tuple)
                    else x
                )
                for x in v
            )
            if nt != v:
                changes[f.name] = nt
    return dataclasses.replace(e, **changes) if changes else e


def _substitute_params_node(node: N.PlanNode, bindings) -> N.PlanNode:
    changes = {}
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, N.PlanNode):
            changes[f.name] = _substitute_params_node(v, bindings)
        elif isinstance(v, tuple) and v and isinstance(v[0], N.PlanNode):
            changes[f.name] = tuple(
                _substitute_params_node(x, bindings) for x in v
            )
        elif isinstance(v, E.Expr):
            changes[f.name] = _substitute_params_expr(v, bindings)
        elif isinstance(v, tuple) and v and isinstance(v[0], tuple):
            nt = []
            for item in v:
                # (a key's range in ``key_ranges`` may be None)
                nt.append(
                    tuple(
                        _substitute_params_expr(x, bindings)
                        if isinstance(x, E.Expr)
                        else x
                        for x in item
                    )
                    if isinstance(item, tuple)
                    else item
                )
            changes[f.name] = tuple(nt)
        elif isinstance(v, tuple):
            nt2 = []
            for item in v:
                if isinstance(item, E.Expr):
                    nt2.append(_substitute_params_expr(item, bindings))
                elif hasattr(item, "arg") and isinstance(
                    getattr(item, "arg", None), E.Expr
                ):
                    nt2.append(
                        dataclasses.replace(
                            item,
                            arg=_substitute_params_expr(item.arg, bindings),
                        )
                    )
                elif hasattr(item, "expr") and isinstance(
                    getattr(item, "expr", None), E.Expr
                ):
                    nt2.append(
                        dataclasses.replace(
                            item,
                            expr=_substitute_params_expr(item.expr, bindings),
                        )
                    )
                else:
                    nt2.append(item)
            changes[f.name] = tuple(nt2)
    return dataclasses.replace(node, **changes) if changes else node


def _scale_capacities(node: N.PlanNode, factor: int) -> N.PlanNode:
    if isinstance(node, N.RemoteSourceNode):
        # fragment already executed; identity keeps gathered-page mapping
        return node
    changes = {}
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, N.PlanNode):
            changes[f.name] = _scale_capacities(v, factor)
        elif isinstance(v, tuple) and v and isinstance(v[0], N.PlanNode):
            changes[f.name] = tuple(
                _scale_capacities(x, factor) for x in v
            )
    if isinstance(node, (N.AggregationNode, N.DistinctNode)):
        changes["max_groups"] = node.max_groups * factor
    if (
        isinstance(node, (N.AggregationNode, N.JoinNode, N.JoinBuildNode))
        and node.key_ranges
    ):
        # the overflow may be a key outside its stated range
        # (ops.aggregation._key_lanes, ops.join._key_of): run again with
        # nothing stated
        changes["key_ranges"] = ()
    if (
        isinstance(node, (N.JoinNode, N.CrossJoinNode, N.UnnestNode))
        and node.out_capacity is not None
    ):
        changes["out_capacity"] = node.out_capacity * factor
    return dataclasses.replace(node, **changes) if changes else node


# ----------------------------------------------------------------- helpers


def _scalar_literal(page: Page, col: str) -> E.Literal:
    blk = page.block(col)
    n = int(page.num_valid)
    if n == 0:
        return E.Literal(None, blk.dtype)
    if n > 1:
        raise ExecutionError("scalar subquery returned more than one row")
    data, valid = blk.to_numpy(1)
    if not valid[0]:
        return E.Literal(None, blk.dtype)
    v = data[0]
    if blk.dtype.is_string:
        return E.Literal(str(blk.dictionary.values[int(v)]), blk.dtype)
    if blk.dtype.is_decimal or blk.dtype.is_integer or blk.dtype.name in (
        "date",
        "timestamp",
    ):
        return E.Literal(int(v), blk.dtype)
    if blk.dtype.name == "boolean":
        return E.Literal(bool(v), blk.dtype)
    return E.Literal(float(v), blk.dtype)


def _merge_split_payloads(datas: List[Dict], columns: List[str]) -> Dict:
    """Merge per-split payloads; dictionary columns union + remap when
    splits carry different dictionaries (file connectors) with a
    same-dictionary fast path (closed-form generators), and masked
    chunks merge mask-correctly (exec.staging.merge_column_chunks —
    the round-3 fix for multi-split string/null scans)."""
    from presto_tpu.exec.staging import merge_column_chunks

    if len(datas) == 1:
        return datas[0]
    return {
        c: merge_column_chunks([d[c] for d in datas]) for c in columns
    }


def _result_columns(res: QueryResult) -> Dict[str, np.ndarray]:
    """QueryResult -> {column: object ndarray of python values} (the
    write-SPI row format; None = NULL)."""
    from presto_tpu.exec.staging import obj_array

    dicts = res.page.to_pylist()
    return {
        c: obj_array([r[c] for r in dicts]) for c in res.columns
    }


def _literal_value(e):
    """INSERT VALUES literal -> python value (numbers, strings, bools,
    NULL; unary minus)."""
    from presto_tpu.sql import ast as A

    if isinstance(e, A.NumberLit):
        t = e.text.lower()
        if "." in t or "e" in t:  # 1.5, 1e3: float
            return float(t)
        return int(t)
    if isinstance(e, A.StringLit):
        return e.value
    if isinstance(e, A.NullLit):
        return None
    if isinstance(e, A.ArrayLit):
        return [_literal_value(x) for x in e.items]
    if isinstance(e, A.BoolLit):
        return e.value
    if isinstance(e, A.UnaryOp) and e.op == "-":
        v = _literal_value(e.arg)
        return -v
    raise ExecutionError(
        "INSERT VALUES supports literal values only "
        f"(got {type(e).__name__})"
    )


def _message_page(msg: str) -> Page:
    return Page.from_pydict(
        {"result": [msg]}, {"result": T.VARCHAR}, capacity=1
    )


def _lines_page(text: str, column: str = "Query Plan") -> Page:
    lines = text.split("\n")
    return Page.from_pydict(
        {column: lines}, {column: T.VARCHAR}, capacity=len(lines)
    )

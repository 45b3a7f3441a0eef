"""Statement executor: AST -> scan -> device reduce -> InfluxDB JSON rows.

The single-node equivalent of the reference's StatementExecutor
(lifted/influx/coordinator/statement_executor.go:206) + executor.Select
(engine/executor/select.go:52) + the store-side cursor/agg stack
(engine/iterators.go, aggregate_cursor.go): shard mapping, index search,
chunk scan with pre-agg skipping, then one jitted segmented-reduction
program per aggregate (models/templates.py), then fill/limit/format.

Results use influx wire shape:
    {"results": [{"statement_id": 0, "series": [
        {"name": ..., "tags": {...}, "columns": [...], "values": [[...]]}]}]}
Times in values are int ns; the HTTP layer formats RFC3339/epoch.
"""

from __future__ import annotations

import contextlib
import math
import os
import re
import threading as _threading
from opengemini_tpu.utils import lockdep
import time as _time
from dataclasses import dataclass

import numpy as np

from opengemini_tpu.models import launch, layoutplan, ragged, templates
from opengemini_tpu.ops import aggregates as aggmod
from opengemini_tpu.parallel import cluster as pcluster
from opengemini_tpu.ops import window as winmod
from opengemini_tpu.query import condition as cond
from opengemini_tpu.query import functions as fnmod
from opengemini_tpu.query import render as qrender
from opengemini_tpu.record import FieldType, FieldTypeConflict
from opengemini_tpu.sql import ast
from opengemini_tpu.storage import colcache as colcache_mod
from opengemini_tpu.storage import scanpool
from opengemini_tpu.storage.shard import FileQuarantined
from opengemini_tpu.storage.tsf import CorruptFile
from opengemini_tpu.meta.users import AuthError as _AuthError
from opengemini_tpu.storage.engine import WriteError
from opengemini_tpu.utils import devobs
from opengemini_tpu.utils import tracing
from opengemini_tpu.utils.governor import GOVERNOR
from opengemini_tpu.utils.querytracker import (GLOBAL as TRACKER,
                                               QueryKilled, redact as _redact)
from opengemini_tpu.utils.stats import GLOBAL as STATS
from opengemini_tpu.sql.parser import parse

from opengemini_tpu.query.qhelpers import *  # noqa: F401,F403 — split helpers
from opengemini_tpu.query.qhelpers import (  # noqa: F401
    NS, MAX_SELECT_BUCKETS, QueryError,
)
from opengemini_tpu.query.hostpath import HostPathMixin
from opengemini_tpu.query.showddl import ShowDdlMixin
from opengemini_tpu.query.subquery import SubqueryMixin


@dataclass
class ScanContext:
    """Output of the shared select prologue (_scan_context)."""

    sc: object
    shards: list
    tmin: int
    tmax: int
    schema: dict
    tag_keys: set
    group_time: object
    aligned: int
    W: int
    group_tags: list
    group_keys: list
    scan_plan: list
    live: list | None = None  # cluster live set pinned by the remote round





def pick_batch(schema, agg_names, field: str, dtype, grid_ctx=None,
               plans=None):
    """Batch implementation for one field given the aggregate names that
    will run on it; `plans` is the statement's layoutplan.Plans, through
    which the dense batches that are fed the same rows build their
    layout's plan once. With a GROUP BY time() context (`grid_ctx` =
    (W, every_ns)), dense-capable aggregates try the regular-grid
    windows-on-lanes batch first (models/grid.py — the fastest layout,
    with built-in fallback when the scanned data is not constant-stride);
    otherwise they use the ragged->dense bucketed batch
    (models/ragged.py); rank-based ones
    (percentile/median/count_distinct) keep the lexsort AggBatch. Shared
    by the local aggregate path and the data-node partial computation
    (query/partials.py) so both sides pick identical numerics."""
    from opengemini_tpu.models import grid as _grid
    from opengemini_tpu.models import ragged as _ragged
    from opengemini_tpu.models import templates as _templates

    if (
        schema.get(field) == FieldType.INT
        and all(n in _ragged.INT_EXACT_AGGS for n in agg_names)
        and any(n in ("sum", "mean") for n in agg_names)
    ):
        # int64-exact host path: float compute would corrupt ints beyond
        # the mantissa (2^24 on-TPU f32). count alone is value-independent
        # and stays on the fast device path.
        return _ragged.IntExactBatch()
    # NOTE: a configured device mesh no longer reroutes dense-capable
    # aggregates to AggBatch — the grid and bucketed layouts themselves go
    # multi-chip by sharding their independent row axes (zero-collective
    # GSPMD partitioning, distributed.shard_leading_axis), so multi-chip
    # keeps the dense kernels instead of the scatter family. AggBatch's
    # shard_map path still serves its own cases.
    if (
        grid_ctx is not None
        and schema.get(field) in (FieldType.FLOAT, FieldType.INT)
        and all(n in _grid.GRID_AGGS for n in agg_names)
    ):
        return _grid.GridBatch(dtype, grid_ctx[0], grid_ctx[1], plans)
    if all(n in _ragged.DENSE_AGGS for n in agg_names):
        return _ragged.BucketedBatch(dtype, plans)
    return _templates.AggBatch(dtype)




# sliced-scan tuning: slice when the estimated scan exceeds this many
# rows; each slice targets this many rows (bounds the dense grid well
# under models/grid._MAX_GRID_CELLS and overlaps decode with compute)
SLICE_THRESHOLD_ROWS = int(os.environ.get("OGTPU_SLICE_THRESHOLD", "0")) \
    or 24_000_000
SLICE_TARGET_ROWS = int(os.environ.get("OGTPU_SLICE_TARGET", "0")) \
    or 2_000_000


def _plan_scan_slices(shards, mst, scan_plan, aligned, every_ns, W,
                      tmin, tmax):
    """Window-aligned slice plan [(w0, W_s, lo, hi)] covering
    [tmin, tmax), or None when the scan is small enough to run in one
    pass. Row counts come from chunk metadata (no decode)."""
    total_rows = 0
    total_chunks = 0
    for sh in shards:
        approx = getattr(sh, "approx_rows", None)
        if approx is None:
            return None  # remote/duck-typed shard: no cheap estimate
        r, c = approx(mst, tmin, tmax)
        total_rows += r
        total_chunks += c
    if total_rows < SLICE_THRESHOLD_ROWS:
        return None
    rows_per_window = max(total_rows // W, 1)
    # plain target-based width. Chunk-span-aligned slices were tried and
    # measured SLOWER at 1B (512s vs 373s warm): the decoded-column LRU
    # already amortizes adjacent-slice re-decodes of a straddling chunk,
    # while wider slices pay real grid-assembly and merge costs.
    W_s = max(int(SLICE_TARGET_ROWS // rows_per_window), 1)
    if W_s >= W:
        return None
    n_slices = -(-W // W_s)
    if total_chunks * n_slices > max(total_rows // 64, 65536):
        # every slice re-sweeps the chunk metadata: with many tiny
        # chunks that sweep would dominate the decode it saves (the
        # budget still admits billion-row scans over ~64k-row chunks:
        # 15k chunks x 500 slices = 7.6M sweeps vs 15.6M allowed)
        return None
    plan = []
    w0 = 0
    while w0 < W:
        ws = min(W_s, W - w0)
        lo = aligned + w0 * every_ns
        hi = aligned + (w0 + ws) * every_ns
        plan.append((w0, ws, max(lo, tmin), min(hi, tmax)))
        w0 += ws
    return plan


def _device_scan_token(db, rp, mst, sc, group_time, group_tags, all_tags,
                       tmin, tmax, aligned, W, dtype, scan_ranges, shards):
    """Scan signature for the decoded-column cache's device tier
    (storage/colcache.py): everything that determines a GridBatch's
    assembled (values, mask) grids — the statement's non-time shape (like
    resultcache.fingerprint), the resolved time geometry, the actually
    scanned ranges (the incremental cache may shrink them per execution),
    and every shard's (path, data_version).  data_version bumps on any
    logical-content change (writes, deletes, rewrites) but not on
    flush/compact, whose merged reads are bit-identical by construction —
    the same trust the incremental result cache is built on.  Returns
    None when any shard lacks the versioning contract (remote proxies)."""
    import json as _json

    from opengemini_tpu.sql import astjson

    sigs = []
    for sh in shards:
        ver = getattr(sh, "data_version", None)
        path = getattr(sh, "path", None)
        if ver is None or path is None:
            return None
        sigs.append((path, ver))
    return _json.dumps(
        [
            db, rp or "", mst,
            astjson.to_json(sc.tag_expr),
            astjson.to_json(sc.field_expr),
            astjson.to_json(sc.mixed_expr),
            bool(sc.mixed_series_level),
            group_time.every_ns, group_time.offset_ns,
            list(group_tags), bool(all_tags),
            tmin, tmax, aligned, W, str(dtype),
            [list(r) for r in scan_ranges], sorted(sigs),
        ],
        separators=(",", ":"),
    )


class _ScanStager:
    """Batched column materialization for the per-series scan tail: the
    serial loop fed each tiny per-series record into the device batches
    one add() at a time — at high cardinality that is hundreds of
    thousands of numpy slivers the batch freeze must re-concatenate.
    The stager accumulates the per-record column views and flushes ONE
    contiguous array set per field (values cast once on the big array),
    preserving the exact row order of the serial path so results are
    bit-identical.  Record boundaries are forwarded to batches that want
    them (GridBatch run detection) — per-shard sid numbering is
    independent, so equal sid values from different shards must not fuse
    into one stride run."""

    def __init__(self, needed_fields, dtype, batches, time_aggs,
                 time_segs, time_vals, aligned):
        self.needed_fields = needed_fields
        self.dtype = dtype
        self.batches = batches
        self.time_aggs = time_aggs
        self.time_segs = time_segs
        self.time_vals = time_vals
        self.aligned = aligned
        # shared per-record arrays: [(times, seg, sid)]
        self._recs: list[tuple] = []
        # field -> [(record index, values|None, mask)]
        self._per_field: dict[str, list] = {f: [] for f in needed_fields}

    def add(self, rec, seg, fmask, sid):
        if self.time_aggs:
            m = fmask if fmask is not None else slice(None)
            self.time_segs.append(seg[m])
            self.time_vals.append(rec.times[m])
        ri = len(self._recs)
        self._recs.append((rec.times, seg, sid))
        for fname in self.needed_fields:
            col = rec.columns.get(fname)
            if col is None:
                continue
            m = col.valid if fmask is None else (col.valid & fmask)
            batch = self.batches[fname]
            if isinstance(batch, ragged.IntExactBatch):
                vals = col.values  # int64 end-to-end, no float cast
            elif col.ftype == FieldType.STRING:
                vals = None  # count-only payload: zeros at flush
            else:
                vals = col.values  # cast once per flush, not per record
            self._per_field[fname].append((ri, vals, m))

    def _gather(self, rec_idx):
        """(times, seg, sids, rel, boundaries) over the given records —
        concatenated ONCE and shared by every field present in all
        records (the common schema-complete case)."""
        times = np.concatenate([self._recs[i][0] for i in rec_idx])
        seg = np.concatenate([self._recs[i][1] for i in rec_idx])
        sids = np.concatenate([
            np.full(len(self._recs[i][0]), self._recs[i][2], np.int64)
            for i in rec_idx])
        lens = np.asarray(
            [len(self._recs[i][0]) for i in rec_idx], np.int64)
        return times, seg, sids, times - self.aligned, np.cumsum(lens)[:-1]

    def flush(self):
        shared = None  # lazy: only fields present in EVERY record share
        all_idx = list(range(len(self._recs)))
        for fname, entries in self._per_field.items():
            if not entries:
                continue
            batch = self.batches[fname]
            rec_idx = [e[0] for e in entries]
            if rec_idx == all_idx:
                if shared is None:
                    shared = self._gather(all_idx)
                times, seg, sids, rel, bounds = shared
            else:
                times, seg, sids, rel, bounds = self._gather(rec_idx)
            mask = np.concatenate([e[2] for e in entries])
            # value payloads dispatch PER RECORD, exactly like the serial
            # _add_record_to_batches: a field may be numeric in one shard
            # and string (None marker -> zero payload) in another
            parts = [
                np.zeros(len(self._recs[ri][0]), dtype=self.dtype)
                if v is None else v
                for ri, v, _m in entries
            ]
            vals = parts[0] if len(parts) == 1 else np.concatenate(parts)
            if not isinstance(batch, ragged.IntExactBatch):
                vals = vals.astype(self.dtype)
            if getattr(batch, "accepts_boundaries", False):
                batch.add(vals, rel, seg, mask, times, sids=sids,
                          boundaries=bounds)
            else:
                batch.add(vals, rel, seg, mask, times, sids=sids)
            self._per_field[fname] = []
        self._recs = []


def _stitch_sliced(sliced_out, spec, params, field_name, num_groups, W,
                   num_segments):
    """Combine per-slice run() outputs into the global segment arrays.
    Window-aligned slices make every (group, window) segment live in
    exactly one slice, so stitching is pure placement — no cross-slice
    combine for ANY per-window aggregate. sel is not stitched: selector
    timestamps are only consulted without GROUP BY time(), and slicing
    requires GROUP BY time()."""
    out = counts = None
    for w0, W_s, sbatches in sliced_out:
        b = sbatches[field_name]
        if b.n == 0:
            continue
        if getattr(b, "supports_want_sel", False):
            o, _sel, c = b.run(spec, num_groups * W_s, params,
                               want_sel=False)
        else:
            o, _sel, c = b.run(spec, num_groups * W_s, params)
        if out is None:
            out = np.zeros(num_segments, dtype=o.dtype)
            counts = np.zeros(num_segments, dtype=c.dtype)
        out.reshape(num_groups, W)[:, w0:w0 + W_s] = \
            o.reshape(num_groups, W_s)
        counts.reshape(num_groups, W)[:, w0:w0 + W_s] = \
            c.reshape(num_groups, W_s)
    if out is None:
        out = np.zeros(num_segments, dtype=np.float64)
        counts = np.zeros(num_segments, dtype=np.int64)
    return out, None, counts


_READONLY_STMTS = (
    ast.SelectStatement,
    ast.UnionStatement,
    ast.ShowDatabases,
    ast.ShowMeasurements,
    ast.ShowTagKeys,
    ast.ShowTagValues,
    ast.ShowFieldKeys,
    ast.ShowSeries,
    ast.ShowRetentionPolicies,
    ast.ShowContinuousQueries,
    ast.ShowUsers,
    ast.ShowGrants,
    ast.ShowMeasurementCardinality,
    ast.ShowSeriesCardinality,
    ast.ShowSeriesExactCardinality,
    ast.ShowShards,
    ast.ShowStats,
    ast.ShowDiagnostics,
    ast.ShowStreams,
    ast.ShowSubscriptions,
    ast.ShowQueries,
    ast.ShowModels,
)



def _is_readonly(stmt) -> bool:
    if isinstance(stmt, ast.ExplainStatement):
        # EXPLAIN ANALYZE executes the inner select — INTO would mutate
        return stmt.select is None or stmt.select.into is None
    if not isinstance(stmt, _READONLY_STMTS):
        return False
    # SELECT ... INTO mutates
    return not (isinstance(stmt, ast.SelectStatement) and stmt.into is not None)




class Executor(ShowDdlMixin, SubqueryMixin, HostPathMixin):
    def __init__(self, engine, users=None, auth_enabled: bool = False,
                 meta_store=None):
        from opengemini_tpu.meta.users import UserStore

        self.engine = engine
        self.users = users if users is not None else UserStore(
            os.path.join(engine.root, "users.json")
        )
        self.auth_enabled = auth_enabled
        # when clustered, database/RP/user DDL replicates through raft
        self.meta_store = meta_store
        # multi-node data plane (parallel/cluster.DataRouter): peers serve
        # raw columns, aggregation stays on this node's device
        self.router = None
        # serializes leader-side user DDL: check-then-propose must not race
        # across HTTP threads (duplicate CREATE USER would silently replace
        # the first user's credentials)
        self._user_ddl_lock = lockdep.Lock()
        # incremental GROUP BY time() result cache (query/resultcache.py)
        from opengemini_tpu.query.resultcache import IncrementalCache

        self._inc_cache = IncrementalCache()
        # per-thread stack of CTE names being expanded (cycle detection)
        self._cte_state = _threading.local()


    def execute(
        self, text: str, db: str = "", now_ns: int | None = None,
        read_only: bool = False, user=None, frames: bool = False,
    ) -> dict:
        """read_only=True (HTTP GET) rejects mutating statements — influx
        1.x requires POST for anything but SELECT/SHOW. `user` is the
        authenticated user when auth is enabled (privilege checks).
        frames=True is a caller that writes the response's bytes itself:
        an aggregate SELECT's result may then carry `"frames"` (a list of
        `query.render.Frame`) in place of `"series"`."""
        if now_ns is None:
            now_ns = _time.time_ns()
        try:
            with tracing.span("sql_parse"):
                stmts = parse(text)
        except ValueError as e:
            return {"results": [{"statement_id": 0, "error": f"error parsing query: {e}"}]}
        STATS.incr("executor", "queries")
        # admission control (utils/governor.py): may raise
        # AdmissionRejected, which the HTTP layer maps to 503 +
        # Retry-After and flight to UNAVAILABLE — deliberately NOT a
        # statement error in a 200.  Pass-through (no lock, no wait)
        # when the governor is disabled.
        # t0 BEFORE admit(): a query that spent 5s in the admission
        # queue and 10ms executing is slow BY 5s — the slow log must see
        # client-perceived duration or overload (its prime use case)
        # escapes capture, and admission_wait could exceed duration_ms
        t0 = _time.perf_counter_ns()
        token = GOVERNOR.admit()
        qid = None
        # per-query span tree (OGT_TRACE=1): the HTTP front end's, whose
        # root is the whole request; a caller without one (the Flight
        # path) gets a tree of its own here.  Active thread-locally, so
        # deep callees — cluster RPC fan-out, the partials path — attach
        # spans and wire ctx without a parameter threaded through every
        # signature
        trace = tracing.active_trace()
        own = None
        if trace is None and tracing.trace_enabled():
            trace = own = tracing.Trace("query")
        try:
            qid = TRACKER.register(text, db)
            with tracing.activate(own) if own is not None \
                    else contextlib.nullcontext():
                if token.waited_ns:
                    # the admission wait is a stage like any other
                    # (/debug/queries stages, /debug/vars query_stages)
                    tracing.record_stage("admission_wait", token.waited_ns)
                if trace is not None:
                    trace.qid = qid
                    trace.root.add_field("statement", _redact(text))
                    trace.root.add_field("database", db)
                    TRACKER.set_trace(qid, trace)
                return self._execute_statements(
                    stmts, db, now_ns, read_only, user, frames)
        finally:
            dur_ns = _time.perf_counter_ns() - t0
            if own is not None:
                own.finish()
                tracing.note_finished(qid, own, {"database": db})
            from opengemini_tpu.utils.slowlog import GLOBAL as SLOWLOG

            if SLOWLOG.enabled():
                # capture BEFORE unregister: a statement with no root
                # above it loses its account there
                SLOWLOG.note(qid, text, db, dur_ns / 1e6, trace=trace)
            if qid is not None:
                TRACKER.unregister(qid)
            token.release()


    def _execute_statements(self, stmts, db, now_ns, read_only, user,
                            frames=False) -> dict:
        results = []
        for i, stmt in enumerate(stmts):
            try:
                # a killed query must not run its REMAINING statements
                # either (the next one might be destructive DDL)
                TRACKER.check()
                if read_only and not _is_readonly(stmt):
                    raise QueryError(
                        f"{type(stmt).__name__} queries must be sent via POST"
                    )
                if self.auth_enabled:
                    if len(self.users) == 0:
                        # bootstrap: ONLY creating the first admin is open
                        if not (isinstance(stmt, ast.CreateUser) and stmt.admin):
                            raise _AuthError(
                                "create an admin user first: CREATE USER <name> "
                                "WITH PASSWORD '<pw>' WITH ALL PRIVILEGES"
                            )
                    else:
                        self._authorize(stmt, user, db)
                if self.engine.read_disabled and isinstance(
                    stmt, (ast.SelectStatement, ast.ExplainStatement)
                ):
                    raise QueryError("reads are disabled (syscontrol)")
                res = self.execute_statement(stmt, db, now_ns, user=user,
                                             frames=frames)
            except (
                QueryError, cond.ConditionError, KeyError, ValueError,
                re.error, FieldTypeConflict, WriteError, QueryKilled,
                FileQuarantined,
            ) as e:
                # _AuthError deliberately NOT caught: authorization failures
                # must surface as HTTP 401/403, not statement errors in a 200.
                # FileQuarantined IS caught: the detecting query fails as a
                # clean per-statement error (the file is already out of the
                # read set; a retry succeeds) instead of a dropped connection
                res = {"error": str(e)}
            res["statement_id"] = i
            results.append(res)
        return {"results": results}


    def _authorize(self, stmt, user, db: str) -> None:
        """Privilege checks (reference: httpd auth + meta user privileges).
        READ for selects/shows, WRITE for SELECT INTO, admin for DDL and
        user management; SET PASSWORD allowed for self."""
        from opengemini_tpu.meta.users import AuthError

        if user is None:
            raise AuthError("authorization required")
        if user.admin:
            return
        if isinstance(stmt, ast.SetPassword) and stmt.name == user.name:
            return
        if isinstance(stmt, ast.ShowDatabases):
            return  # any authenticated user; rows are filtered to
            # authorized dbs in execute_statement (influx semantics)
        select = None
        if isinstance(stmt, ast.ExplainStatement):
            select = stmt.select
        elif isinstance(stmt, ast.SelectStatement):
            select = stmt
        elif isinstance(stmt, ast.UnionStatement):
            for sel in stmt.selects:
                self._authorize(sel, user, db)
            return
        if select is not None:
            # READ must hold on EVERY source database — including
            # per-source overrides (FROM "otherdb"..m) and subquery inner
            # sources — not just the request's db param; WRITE likewise on
            # the INTO target's own database.
            for sdb in sorted(self._select_source_dbs(select, db)):
                if not user.can("READ", sdb):
                    raise AuthError(f"user {user.name!r} lacks READ on {sdb!r}")
            # checked on the SELECT itself whether it arrived bare or
            # wrapped in EXPLAIN [ANALYZE] — analyze executes the write
            if select.into is not None:
                tdb = select.into.database or db
                if not user.can("WRITE", tdb):
                    raise AuthError(f"user {user.name!r} lacks WRITE on {tdb!r}")
            return
        if isinstance(
            stmt,
            (ast.ShowMeasurements, ast.ShowTagKeys, ast.ShowTagValues,
             ast.ShowFieldKeys, ast.ShowSeries, ast.ShowRetentionPolicies,
             ast.ShowContinuousQueries, ast.ShowMeasurementCardinality,
             ast.ShowSeriesCardinality, ast.ShowSeriesExactCardinality),
        ):
            if user.can("READ", getattr(stmt, "database", "") or db):
                return
            raise AuthError(f"user {user.name!r} lacks READ on {db!r}")
        raise AuthError(f"user {user.name!r} is not authorized (admin required)")


    @staticmethod
    def _select_source_dbs(select, default_db: str) -> set:
        """Every database a SELECT reads from, recursing into subqueries."""
        dbs = set()

        seen: set[int] = set()

        def walk(s):
            if s is None or id(s) in seen:
                return
            seen.add(id(s))
            if isinstance(s, ast.UnionStatement):
                for sel in s.selects:
                    walk(sel)
                return
            if not s.sources:
                dbs.add(default_db)
            for src in s.sources:
                walk_src(src, s)
            walk_cond(s.condition)

        def walk_src(src, owner):
            if isinstance(src, ast.SubQuery):
                walk(src.stmt)
            elif isinstance(src, ast.JoinSource):
                walk_src(src.left, owner)
                walk_src(src.right, owner)
            elif owner.ctes and src.name in owner.ctes:
                walk(owner.ctes[src.name])
            else:
                dbs.add(src.database or default_db)

        def walk_cond(e):
            if e is None:
                return
            if isinstance(e, ast.InSubquery):
                walk(e.stmt)
            elif isinstance(e, ast.BinaryExpr):
                walk_cond(e.lhs)
                walk_cond(e.rhs)
            elif isinstance(e, (ast.ParenExpr, ast.UnaryExpr)):
                walk_cond(e.expr)

        walk(select)
        return dbs


    def _explain(self, stmt: ast.ExplainStatement, db: str, now_ns: int) -> dict:
        """EXPLAIN [ANALYZE] SELECT (reference:
        executeExplainAnalyzeStatement, statement_executor.go:943)."""
        sel = stmt.select
        if stmt.analyze:
            trace = tracing.Trace("EXPLAIN ANALYZE")
            # activated so cluster RPCs under the analyze run carry wire
            # ctx and replica subtrees stitch into THIS tree
            with tracing.activate(trace):
                self._select(sel, db, now_ns, trace=trace)
            trace.finish()
            lines = trace.render()
            return _series_result(
                "", None, ["EXPLAIN ANALYZE"], [[line] for line in lines]
            )
        # EXPLAIN: describe the plan without executing (same validation
        # as _select so the output never lies about a missing database)
        lines = []
        path = {
            "raw": "RAW SCAN (host merge)",
            "device": "DEVICE SEGMENTED REDUCTION (jit plan template)",
            "host": "HOST FUNCTION PIPELINE",
        }[_classify_select(sel)]
        for src in sel.sources:
            if isinstance(src, ast.SubQuery):
                raise QueryError("subqueries are not supported yet")
            src_db = src.database or db
            if not src_db:
                raise QueryError("database name required")
            if src_db not in self.engine.databases:
                raise QueryError(f"database not found: {src_db}")
            names = self._resolve_measurements(src, src_db)
            for mst in names:
                ctx = self._scan_context(sel, src_db, src.rp or None, mst, now_ns)
                lines.append(f"QUERY PLAN for {mst}: {path}")
                if ctx is None:
                    lines.append("    no matching shards/series")
                    continue
                lines.append(f"    shards: {len(ctx.shards)}")
                lines.append(f"    series: {len(ctx.scan_plan)}")
                lines.append(f"    groups: {len(ctx.group_keys)}  windows: {ctx.W}")
                lines.append(
                    f"    time range: [{ctx.tmin}, {ctx.tmax})  "
                    f"segments: {len(ctx.group_keys) * ctx.W}"
                )
        return _series_result("", None, ["QUERY PLAN"], [[line] for line in lines])


    def _select(self, stmt: ast.SelectStatement, db: str, now_ns: int,
                trace=tracing.NOOP, frames: bool = False) -> dict:
        """`frames`: the caller renders `qrender.Frame`s itself, so a
        statement that is nothing but aggregates over measurements
        answers {"frames": [...]}; every other caller reads the tree."""
        if trace is tracing.NOOP:
            # adopt the per-query tree the executor activated (OGT_TRACE);
            # EXPLAIN ANALYZE passes its own trace explicitly
            trace = tracing.current()
        stmt = self._rewrite_in_subqueries(stmt, db, now_ns)
        if stmt is None:
            return {}  # IN (empty subquery result): no rows can match
        if len(stmt.fields) == 1:
            only = _strip_expr(stmt.fields[0].expr)
            if isinstance(only, ast.Call) and only.name == "compare":
                return self._select_compare(stmt, only, db, now_ns)
            from opengemini_tpu.query import tablefunc as tfmod

            if isinstance(only, ast.Call) and only.name in tfmod.TABLE_FUNCTIONS:
                return self._select_table_function(stmt, only, db, now_ns)
        # constant (string-literal) columns: allowed only WITH an alias
        # and only alongside at least one variable field (reference
        # TestServer_Query_Constant_Column; error text matches)
        n_const = 0
        for f in stmt.fields:
            if isinstance(_strip_expr(f.expr), ast.StringLiteral):
                if not f.alias:
                    raise QueryError("field must contain at least one variable")
                n_const += 1
        if n_const == len(stmt.fields):
            return {}  # only constants: empty result, no error
        multi = self._multi_source_plan(stmt, db)
        if multi == "rewrite":
            # aggregates over multiple sources run on the UNION of rows
            # (reference: count(age) FROM mst,mst1 = one combined count,
            # TestServer_Query_MultiMeasurements) — rewrite as the same
            # select over a raw SELECT * subquery spanning every source
            import copy as _copy

            inner = ast.SelectStatement(
                fields=[ast.Field(expr=ast.Wildcard())],
                sources=list(stmt.sources),
                ctes=stmt.ctes,
            )
            outer = _copy.copy(stmt)
            outer.sources = [ast.SubQuery(inner)]
            return self._select(outer, db, now_ns, trace)
        # a caller that renders frames gets them only for a statement
        # nothing below post-processes as rows
        frames = (frames and multi is None and stmt.into is None
                  and not stmt.soffset and not stmt.slimit)
        parts = []  # a source's series dicts, or its qrender.Frame
        for src in stmt.sources:
            if isinstance(src, ast.JoinSource):
                from opengemini_tpu.query import join as joinmod

                parts.append(joinmod.select_join(self, stmt, src, db, now_ns))
                continue
            if (isinstance(src, ast.Measurement) and stmt.ctes
                    and src.name in stmt.ctes):
                parts.append(self._select_cte(stmt, src, db, now_ns, trace))
                continue
            if isinstance(src, ast.SubQuery):
                parts.append(
                    self._select_from_subquery(stmt, src, db, now_ns, trace))
                continue
            src_db = src.database or db
            if not src_db:
                raise QueryError("database name required")
            if src_db not in self.engine.databases:
                raise QueryError(f"database not found: {src_db}")
            names = self._resolve_measurements(src, src_db)
            for mst in names:
                with trace.span(f"select: {mst}"):
                    parts.append(self._select_measurement(
                        stmt, src_db, src.rp or None, mst, now_ns, trace,
                        frames))
        if parts and all(isinstance(p, qrender.Frame) for p in parts):
            parts = [p for p in parts if p.keys]
            return {"frames": parts} if parts else {}
        all_series = []
        for p in parts:
            all_series.extend(
                p.series() if isinstance(p, qrender.Frame) else p)
        if multi == "merge":
            all_series = _merge_multi_source(all_series, stmt)
        # SLIMIT/SOFFSET over series
        if stmt.soffset:
            all_series = all_series[stmt.soffset :]
        if stmt.slimit:
            all_series = all_series[: stmt.slimit]
        if stmt.into is not None:
            written = self._write_into(stmt.into, db, all_series)
            return _series_result("result", None, ["time", "written"], [[0, written]])
        if not all_series:
            return {}
        return {"series": all_series}


    def _multi_source_plan(self, stmt, db: str) -> str | None:
        """How a multi-source FROM combines (reference
        TestServer_Query_MultiMeasurements: sources UNION into one series
        named 'mst,mst1'):
          - None: single effective source (or joins/CTEs — their own
            machinery), no combining
          - 'merge': raw projection — evaluate per source, merge output
            series by tagset (name-joined, column-unioned, rows coalesced)
          - 'rewrite': aggregates — re-run as agg over a raw SELECT *
            subquery so the aggregation sees the UNION of rows
        """
        srcs = stmt.sources
        if any(isinstance(s, ast.JoinSource) for s in srcs):
            return None
        if any(isinstance(s, ast.Measurement) and stmt.ctes
               and s.name in stmt.ctes for s in srcs):
            return None
        n_effective = 0
        for s in srcs:
            if isinstance(s, ast.SubQuery):
                n_effective += 1
            elif isinstance(s, ast.Measurement):
                if s.regex:
                    try:
                        n_effective += len(
                            self._resolve_measurements(s, s.database or db)
                        )
                    except Exception:  # noqa: BLE001 — resolution errors surface later
                        n_effective += 1
                else:
                    n_effective += 1
        if n_effective <= 1:
            return None
        if _classify_select(stmt) == "raw":
            return "merge"
        if len(srcs) <= 1:
            # a single regex source with aggregates keeps per-measurement
            # series (influx semantics); only EXPLICIT multi-source
            # aggregates union their rows
            return None
        # already inside the rewrite's own inner (SELECT * is raw) can't
        # reach here; anything aggregating combines via the union rewrite
        return "rewrite"


    def _select_cte(self, stmt, src: ast.Measurement, db: str, now_ns: int,
                    trace=tracing.NOOP) -> list[dict]:
        """FROM <cte-name>: execute the WITH binding as a subquery, with
        cycle detection (reference error text: CTE_Query expectations)."""
        name = src.name
        active = getattr(self._cte_state, "active", None)
        if active is None:
            active = self._cte_state.active = set()
        if name in active:
            raise QueryError(
                f"Unsupported feature: recursive call to itself {name}")
        active.add(name)
        try:
            sub = ast.SubQuery(stmt.ctes[name], alias=src.alias or name)
            return self._select_from_subquery(stmt, sub, db, now_ns, trace)
        finally:
            active.discard(name)


    def _rewrite_in_subqueries(self, stmt, db: str, now_ns: int):
        """Replace `<ref> IN (SELECT ...)` predicates with OR-chains of
        equalities against the subquery's first output column.  Returns
        None when an IN set is empty (the predicate can never match)."""
        if stmt.condition is None or not _has_in_subquery(stmt.condition):
            return stmt
        import copy

        empty = []

        def resolve(e, under_or=False):
            if isinstance(e, ast.InSubquery):
                # CTE refs inside the IN-subquery resolve with cycle checks
                res = self._select(e.stmt, db, now_ns)
                values = []
                seen = set()
                for s in res.get("series", []):
                    for row in s.get("values", []):
                        if len(row) < 2 or row[1] is None:
                            continue
                        if row[1] not in seen:
                            seen.add(row[1])
                            values.append(row[1])
                if not values:
                    if under_or:
                        # an always-false leaf under OR must not erase the
                        # other branch; no representable false leaf exists
                        # in the condition machinery yet
                        raise QueryError(
                            "IN (empty subquery result) under OR is not supported")
                    empty.append(True)
                    return e
                out = None
                for v in values:
                    if isinstance(v, bool):
                        lit = ast.BooleanLiteral(v)
                    elif isinstance(v, (int,)):
                        lit = ast.IntegerLiteral(v)
                    elif isinstance(v, float):
                        lit = ast.NumberLiteral(v)
                    else:
                        lit = ast.StringLiteral(str(v))
                    eq = ast.BinaryExpr("=", e.ref, lit)
                    out = eq if out is None else ast.BinaryExpr("OR", out, eq)
                return out
            if isinstance(e, ast.BinaryExpr):
                sub_or = under_or or e.op.upper() == "OR"
                return ast.BinaryExpr(
                    e.op, resolve(e.lhs, sub_or), resolve(e.rhs, sub_or))
            if isinstance(e, ast.ParenExpr):
                return ast.ParenExpr(resolve(e.expr, under_or))
            if isinstance(e, ast.UnaryExpr):
                return ast.UnaryExpr(e.op, resolve(e.expr, True))
            return e

        new_cond = resolve(stmt.condition)
        if empty:
            return None
        stmt = copy.copy(stmt)
        stmt.condition = new_cond
        return stmt


    def _select_compare(self, stmt, call, db: str, now_ns: int) -> dict:
        """compare(ref, off...): evaluate the source over the WHERE range
        and over each range shifted back by `off` seconds (or a duration),
        align rows by (tags, time+off), and emit ref1..refN plus
        ref1/refK ratio columns (reference: openGemini compare UDF,
        TestServer_Query_Compare_Functions)."""
        import copy as _copy
        from dataclasses import replace as _dc_replace

        if len(call.args) < 2:
            raise QueryError(
                "invalid number of arguments for compare, expected more "
                f"than one arguments, got {len(call.args)}")
        ref_e = _strip_expr(call.args[0])
        if not isinstance(ref_e, ast.VarRef):
            raise QueryError("compare() first argument must be a column")
        ref = ref_e.name
        offsets = []
        for a in call.args[1:]:
            v = _call_param_value(a)
            # bare integers are seconds; durations come in as ns
            offsets.append(int(v) * NS if isinstance(v, int) and
                           not isinstance(_strip_expr(a), ast.DurationLiteral)
                           else int(v))
        if not stmt.sources:
            raise QueryError("compare() requires a FROM source")
        src = stmt.sources[0]
        if isinstance(src, ast.SubQuery):
            inner = src.stmt
        elif isinstance(src, ast.Measurement):
            # raw field compare: first(field) over the range
            inner = ast.SelectStatement(
                fields=[ast.Field(ast.Call("first", (ast.VarRef(ref),)),
                                  alias=ref)],
                sources=[src],
            )
            inner.ctes = stmt.ctes
        else:
            raise QueryError("compare() source must be a measurement or subquery")

        sc = cond.split(stmt.condition, set(), now_ns)
        if sc.tmin == cond.MIN_TIME or sc.tmax == cond.MAX_TIME:
            raise QueryError("compare() requires an explicit time range")

        runs = []
        for off in [0] + offsets:
            bound = ast.BinaryExpr(
                "AND",
                ast.BinaryExpr(">=", ast.VarRef("time"),
                               ast.IntegerLiteral(sc.tmin - off)),
                ast.BinaryExpr("<", ast.VarRef("time"),
                               ast.IntegerLiteral(sc.tmax - off)),
            )
            run_inner = _copy.copy(inner)
            gt = getattr(run_inner, "group_by_time", None)
            if gt is not None and not gt.offset_ns:
                # openGemini anchors compare() windows at the (shifted)
                # RANGE START, not the epoch grid: the reference output
                # rows carry tmin-aligned times
                # (TestServer_Query_Compare_Functions#10). A NON-ZERO
                # user GROUP BY time offset is respected; an explicit 0s
                # offset is indistinguishable from the default in the AST
                # and re-anchors too (InfluxQL treats the forms
                # identically).
                run_inner.group_by_time = _dc_replace(
                    gt, offset_ns=(sc.tmin - off) % gt.every_ns)
            run_stmt = ast.SelectStatement(
                fields=[ast.Field(ast.VarRef(ref))],
                sources=[ast.SubQuery(run_inner)],
                condition=bound,
                group_by_all_tags=True,
            )
            run_stmt.ctes = stmt.ctes
            res = self._select(run_stmt, db, now_ns)
            data: dict[tuple, dict[int, object]] = {}
            name = "compare"
            for ser in res.get("series", []):
                name = ser.get("name", name)
                key = tuple(sorted((ser.get("tags") or {}).items()))
                bucket = data.setdefault(key, {})
                ci = ser["columns"].index(ref) if ref in ser["columns"] else 1
                for row in ser["values"]:
                    if row[ci] is not None:
                        bucket[row[0] + off] = row[ci]
            runs.append((name, data))

        src_name = runs[0][0] if runs else "compare"
        all_keys = sorted({k for _n, d in runs for k in d})
        k_runs = len(runs)
        columns = (["time"] + [f"{ref}{i+1}" for i in range(k_runs)]
                   + [f"{ref}1/{ref}{i+1}" for i in range(1, k_runs)])
        out_series = []
        for key in all_keys:
            times = sorted({t for _n, d in runs for t in d.get(key, {})})
            rows = []
            for t in times:
                vals = [d.get(key, {}).get(t) for _n, d in runs]
                ratios = []
                for i in range(1, k_runs):
                    a, b = vals[0], vals[i]
                    ratios.append(
                        a / b if a is not None and b not in (None, 0) else None)
                rows.append([t] + vals + ratios)
            if not rows:
                continue
            series = {"name": src_name, "columns": columns, "values": rows}
            if key:
                series["tags"] = dict(key)
            out_series.append(series)
        return {"series": out_series} if out_series else {}


    def _resolve_measurements(self, src: ast.Measurement, db: str) -> list[str]:
        if src.name:
            return [src.name]
        rx = re.compile(src.regex)
        shards = self.engine.shards_for_range(db, src.rp or None, cond.MIN_TIME, cond.MAX_TIME)
        names = set()
        for sh in shards:
            for m in sh.measurements():
                if rx.search(m):
                    names.add(m)
        if self.router is not None:
            try:
                remote = self.router.remote_measurements(db, src.rp or None)
            except Exception as e:  # noqa: BLE001
                raise QueryError(str(e)) from e
            names.update(m for m in remote if rx.search(m))
        return sorted(names)


    def _measurement_schema(self, db, rp, mst) -> dict:
        schema: dict = {}
        for sh in self.engine.shards_for_range(db, rp, cond.MIN_TIME, cond.MAX_TIME):
            schema.update(sh.schema(mst))
        return schema


    def _select_measurement(self, stmt, db, rp, mst, now_ns,
                            trace=tracing.NOOP, frames=False):
        """The measurement's series dicts; with `frames`, a device
        aggregate's `qrender.Frame` where it made one."""
        if _has_call_wildcard(stmt):
            stmt = _expand_call_wildcards(
                stmt, self._measurement_schema(db, rp, mst)
            )
        # percentile_approx: answered from chunk histogram sketches
        if len(stmt.fields) == 1:
            only = _strip_expr(stmt.fields[0].expr)
            if isinstance(only, ast.Call) and only.name == "percentile_approx":
                return self._select_percentile_approx(
                    stmt, db, rp, mst, now_ns, only
                )
        aux_plan = _selector_aux_plan(stmt)
        if aux_plan is not None:
            return self._select_selector_aux(stmt, db, rp, mst, now_ns, aux_plan)
        kind = _classify_select(stmt)
        if kind == "device" and _needs_string_host_path(
            stmt, lambda: self._measurement_schema(db, rp, mst)
        ):
            # first/last/etc on STRING fields: the device batch layout is
            # numeric; the host path computes them exactly
            kind = "host"
        if kind == "raw":
            return self._select_raw(stmt, db, rp, mst, now_ns)
        if kind == "device":
            return self._select_agg(
                stmt, db, rp, mst, now_ns, _collect_calls(stmt.fields), trace,
                frames,
            )
        return self._select_host(stmt, db, rp, mst, now_ns)

    # -- shared scan planning ----------------------------------------------


    def _all_shards_with_remote(self, db, rp, mst, condition, now_ns,
                                remote_mode="raw"):
        """Local shards + remote representation from peer data nodes
        (when clustered routing is on). remote_mode:
          "raw"  — RemoteShard row proxies (full column exchange);
          "meta" — one MetaShard carrying remote tag keys / schema /
                   extent only; the rows stay put and arrive later as
                   per-(group, window) partials (aggregate pushdown).
        Returns (shards, live_node_list | None)."""
        shards = self.engine.shards_for_range(db, rp, cond.MIN_TIME, cond.MAX_TIME)
        live = None
        if self.router is not None:
            from opengemini_tpu.parallel.cluster import MetaShard

            pre = cond.split(condition, set(), now_ns)
            try:
                if remote_mode == "meta":
                    meta, live = self.router.select_meta(
                        db, rp, mst, pre.tmin, pre.tmax
                    )
                    remote = []
                    if meta is not None and meta["dmin"] is not None:
                        remote = [MetaShard(
                            mst, meta["tag_keys"], meta["schema"],
                            meta["dmin"], meta["dmax"],
                        )]
                else:
                    remote, live = self.router.scan_shards(
                        db, rp, mst, pre.tmin, pre.tmax
                    )
            except pcluster.PartialsUnavailable:
                # a live peer rejected the metadata round (governor
                # shed / rolling upgrade): propagate so the pushdown
                # driver falls back to the raw column exchange instead
                # of flattening this into a hard QueryError
                raise
            except Exception as e:  # noqa: BLE001 — partial data = wrong data
                raise QueryError(str(e)) from e
            if self.router.rf > 1:
                # replicated groups: keep only those WE are primary for
                # among the live set; replicas held here would double-count
                shards = [
                    sh for sh in shards
                    if self.router.is_primary(db, rp, sh.tmin, live)
                ]
            shards = shards + remote
        return shards, live


    def _scan_context(self, stmt, db, rp, mst, now_ns, remote_mode="raw"):
        """Shared prologue of every select path: schema/tag keys, WHERE
        split, shard mapping, data-driven range clamp, window grid, group
        construction (reference: the Prepare + MapShards steps,
        SURVEY.md §3.2). Returns None when nothing matches."""
        if self.engine.is_measurement_dropped(db, mst):
            return None  # mark-deleted: hidden from SELECT pre-purge
        shards_all, live = self._all_shards_with_remote(
            db, rp, mst, stmt.condition, now_ns, remote_mode
        )
        tag_keys: set[str] = set()
        schema: dict[str, FieldType] = {}
        for sh in shards_all:
            tag_keys.update(sh.index.tag_keys(mst))
            schema.update(sh.schema(mst))
        if not schema and stmt.group_by_all_tags:
            raise QueryError("measurement not found")  # see _select_raw
        sc = cond.split(stmt.condition, tag_keys, now_ns)
        tmin, tmax = sc.tmin, sc.tmax
        explicit_tmin = tmin != cond.MIN_TIME
        explicit_tmax = tmax != cond.MAX_TIME
        shards = [sh for sh in shards_all if sh.tmax > tmin and sh.tmin < tmax]
        if not shards:
            return None
        # data-driven clamp of an unbounded range (influx uses epoch 0/now)
        if not explicit_tmin or not explicit_tmax:
            dmin, dmax = _data_time_range(shards, mst)
            if dmin is None:
                return None
            if not explicit_tmin:
                tmin = dmin
            if not explicit_tmax:
                tmax = dmax + 1
        if tmax <= tmin:
            return None
        group_time = stmt.group_by_time
        if group_time:
            aligned = int(winmod.window_start(tmin, group_time.every_ns, group_time.offset_ns))
            every = group_time.every_ns
            if not explicit_tmax and stmt.limit and stmt.ascending:
                # unbounded upper + LIMIT: the reference iterates windows
                # to now(); emitting exactly offset+limit windows from the
                # data start is equivalent and bounded
                want = stmt.offset + stmt.limit
                tmax = max(tmax, min(now_ns, aligned + want * every))
            W = winmod.num_windows(tmin, tmax, every, group_time.offset_ns)
            if W > MAX_SELECT_BUCKETS:
                raise QueryError(
                    f"GROUP BY time({every}ns) would create {W} buckets "
                    f"(max {MAX_SELECT_BUCKETS})"
                )
        else:
            # output timestamp of whole-range aggregates: the explicit WHERE
            # lower bound, else epoch 0 (influx semantics; the data-driven
            # clamp above must not leak into result rows)
            aligned = tmin if explicit_tmin else 0
            W = 1
        group_tags = self._group_tags(stmt, shards, mst)
        # ordered group keys + per-(shard, sid) membership
        gid_of: dict[tuple, int] = {}
        group_keys: list[tuple] = []
        scan_plan = []  # (shard, sid, gid)
        # GROUP BY time emits fill rows even for series with zero matching
        # rows — pruning those series would change the emitted series set,
        # so the index only prunes un-windowed scans
        match_terms = (
            [] if group_time else cond.conjunctive_match_terms(sc.field_expr)
        )
        # /*+ full_series|specific_series */: the WHERE identifies whole
        # series — evaluate mixed tag/field trees at the series level and
        # skip their per-row filter (reference: hybrid store reader hints)
        hinted = bool({"full_series", "specific_series"}
                      & set(getattr(stmt, "hints", ())))
        exact_tags = (
            cond.exact_series_tags(stmt.condition, tag_keys)
            if "full_series" in getattr(stmt, "hints", ()) else None
        ) or None  # no tag equalities -> the hint pins nothing
        for sh in shards:
            # sorted int64 arrays end-to-end: the columnar label tier
            # answers the tag tree and the mixed-tree prunes intersect
            # without per-shard Python set materialization
            sids = cond.eval_tag_sids(sc.tag_expr, sh.index, mst)
            if sc.mixed_expr is not None and sids.size:
                if hinted:
                    sids = np.intersect1d(
                        sids, cond.series_only_arr(
                            sc.mixed_expr, sh.index, mst, sc.tag_keys),
                        assume_unique=True)
                else:
                    sids = np.intersect1d(
                        sids, cond.tag_superset_arr(
                            sc.mixed_expr, sh.index, mst, sc.tag_keys),
                        assume_unique=True)
            if exact_tags is not None and sids.size:
                keep = [s for s in sids.tolist()
                        if sh.index.tags_of(s) == exact_tags]
                sids = np.asarray(keep, np.int64)
            sids = _prune_text_sids(sh, mst, sids, match_terms)
            for sid in sids.tolist():
                # no GROUP BY tag: one group, and no series' tags are read
                # (a count() over 1,000,000 series spent 28 s in tags_of)
                tags = sh.index.tags_of(sid) if group_tags else {}
                key = tuple(tags.get(k, "") for k in group_tags)
                gid = gid_of.get(key)
                if gid is None:
                    gid = len(group_keys)
                    gid_of[key] = gid
                    group_keys.append(key)
                scan_plan.append((sh, sid, gid))
        if hinted:
            sc.mixed_series_level = True  # consumed at the series level
        if not scan_plan and not (remote_mode == "meta" and live is not None):
            # clustered "meta" scans proceed with an empty local plan:
            # the groups may exist only as remote partials
            return None
        return ScanContext(
            sc, shards, tmin, tmax, schema, tag_keys, group_time, aligned, W,
            group_tags, group_keys, scan_plan, live,
        )

    # -- aggregate path -----------------------------------------------------


    def _select_agg(self, stmt, db, rp, mst, now_ns, calls,
                    trace=tracing.NOOP, frames=False):
        from opengemini_tpu.query import partials as pmod

        # resolve agg specs + fields (before planning: the set decides
        # whether remote data arrives as partials or raw columns)
        aggs = []  # (out_name, spec, params, field_name)
        for f in stmt.fields:
            for call in _calls_in(f.expr):
                spec, params, field_name = _resolve_call(call)
                aggs.append((call, spec, params, field_name))

        pushdown = (
            self.router is not None
            # getattr: duck-typed router stubs without the full surface
            # keep the raw column-exchange path
            and getattr(self.router, "has_peers", lambda: False)()
            and all(
                spec.name in pmod.MERGEABLE
                or spec.name in pmod.MULTISET_MERGEABLE
                for _c, spec, _p, _f in aggs
            )
            and not any(f.lower() == "time" for _c, _s, _p, f in aggs)
        )
        attempts = max(self.router.rf, 1) if pushdown else 1
        for attempt in range(attempts):
            try:
                return self._select_agg_run(
                    stmt, db, rp, mst, now_ns, aggs, pushdown, trace, frames
                )
            except pcluster.PartialsUnavailable:
                # a live peer cannot serve partials (e.g. rolling
                # upgrade): the raw column exchange still works
                return self._select_agg_run(
                    stmt, db, rp, mst, now_ns, aggs, False, trace, frames
                )
            except pcluster.PartialsRetry as e:
                # a peer died mid-query: primary ownership shifted, the
                # whole plan (live set, local primary filter) is stale
                if attempt == attempts - 1:
                    raise QueryError(str(e)) from e
        raise AssertionError("unreachable")


    def _select_agg_run(self, stmt, db, rp, mst, now_ns, aggs, pushdown,
                        trace=tracing.NOOP, frames=False):
        from opengemini_tpu.query import partials as pmod

        with trace.span("map_shards") as sp:
            ctx = self._scan_context(
                stmt, db, rp, mst, now_ns,
                remote_mode="meta" if pushdown else "raw",
            )
            if ctx is not None:
                sp.add_field("shards", len(ctx.shards))
                sp.add_field("series", len(ctx.scan_plan))
                sp.add_field("groups x windows", f"{len(ctx.group_keys)} x {ctx.W}")
        if ctx is None:
            return []
        sc, shards = ctx.sc, ctx.shards
        tmin, tmax = ctx.tmin, ctx.tmax
        group_time, aligned, W = ctx.group_time, ctx.aligned, ctx.W
        group_tags, group_keys, scan_plan = ctx.group_tags, ctx.group_keys, ctx.scan_plan
        schema = ctx.schema

        num_groups = len(group_keys)
        num_segments = num_groups * W

        # aggregates over the `time` pseudo-field (count/first/last/min/max
        # of row timestamps) are computed host-side from scanned row times
        time_aggs = [a for a in aggs if a[3].lower() == "time"]
        for _c, spec, _p, _f in time_aggs:
            if spec.name not in ("count", "first", "last", "min", "max"):
                raise QueryError(f"{spec.name}(time) is not supported")
        aggs = [a for a in aggs if a[3].lower() != "time"]
        # influx: COUNT/COUNT(DISTINCT ...) over a TAG answers a constant
        # 0 (tags are not countable fields; server_test.go
        # Aggregates_IntMany 'count distinct select tag')
        tag_count_aggs = [
            a for a in aggs
            if a[1].name in ("count", "count_distinct")
            and a[3] not in schema and a[3] in sc.tag_keys
        ]
        aggs = [a for a in aggs if a not in tag_count_aggs]

        needed_fields = sorted({a[3] for a in aggs})
        field_filter_fields = sorted(cond.row_filter_refs(sc))
        read_fields = sorted(set(needed_fields) | set(field_filter_fields))
        if time_aggs and not read_fields:
            read_fields = None  # time-only aggregates: read every field

        dtype = templates.compute_dtype()
        per_field_aggs: dict[str, list] = {}
        for _call, spec, _params, fname in aggs:
            per_field_aggs.setdefault(fname, []).append(spec.name)
        grid_ctx = (W, group_time.every_ns) if group_time else None
        plans = layoutplan.Plans()  # this statement's, shared by its fields
        batches: dict[str, object] = {
            f: pick_batch(schema, per_field_aggs[f], f, dtype, grid_ctx,
                          plans)
            for f in needed_fields
        }

        # incremental result cache (reference inc_agg_transform +
        # lib/resultcache): GROUP BY time() windows whose shards took no
        # writes since the last execution are served from cached
        # (value, count) columns; only the stale hull is scanned/computed
        cache_plan = None
        if (
            group_time is not None
            and W >= 1
            and aggs  # tag-count-only statements have nothing to cache
            # OGT_RESULT_CACHE=0 opts out (A/B runs must see every
            # execution, not one per panel)
            and os.environ.get("OGT_RESULT_CACHE", "1") not in ("", "0")
            and self.router is None
            and ctx.live is None
            and not time_aggs
            and len(ctx.group_keys) <= 20_000  # cache growth gate
            and W <= 16_384  # > _MAX_WINDOWS would evict itself every run
            and all(hasattr(sh, "data_version") for sh in shards)
        ):
            from opengemini_tpu.query import resultcache as rcache

            fp = rcache.fingerprint(
                db, rp, mst, sc, group_time, group_tags,
                stmt.group_by_all_tags,
                [(spec.name, params, fname)
                 for _c, spec, params, fname in aggs],
            )
            cache_plan = rcache.CachePlan(
                self._inc_cache, fp, shards, aligned,
                group_time.every_ns, W, len(aggs), tmin, tmax)
        full_hit = cache_plan is not None and not cache_plan.scan_ranges
        scan_ranges = [(tmin, tmax)]
        if cache_plan is not None and cache_plan.scan_ranges:
            # disjoint stale runs: a now()-relative dashboard query scans
            # only its partial edge windows + actually-written windows
            scan_ranges = [
                (max(tmin, lo), min(tmax, hi))
                for lo, hi in cache_plan.scan_ranges
            ]

        # materialized-rollup splice (storage/rollup.py + rollupplan.py):
        # windows below the rollup watermark and not dirty are answered
        # from persisted rollup cells; the raw scan shrinks to the live
        # tail + re-dirtied windows.  Runs INSIDE the result-cache's
        # stale set so both layers compose; nothing here executes when no
        # rollup spec matches (engine.rollup_mgr is None pass-through).
        rollup_plan = None
        if (
            not full_hit
            and group_time is not None
            and aggs
            and not time_aggs
            and self.router is None
            and ctx.live is None
            and getattr(self.engine, "rollup_mgr", None) is not None
        ):
            from opengemini_tpu.query import rollupplan as rplan

            rollup_plan = rplan.try_plan(
                self.engine.rollup_mgr, db, rp, mst, sc, ctx, aggs,
                schema, cache_plan, tmin, tmax)
        if rollup_plan is not None:
            with trace.span("rollup") as sp:
                rollup_plan.fetch()
                sp.add_field("windows_spliced", len(rollup_plan.serve))
                sp.add_field("rollup_rows", rollup_plan.rows_read)
            if rollup_plan.serve:
                scan_ranges = rollup_plan.scan_ranges
            else:
                rollup_plan = None
        # no raw scan at all: every window comes from the result cache
        # and/or the rollup splice
        no_scan = full_hit or (rollup_plan is not None and not scan_ranges)

        # string fields: count counts, mean answers influx's constant 0,
        # stddev answers null (server_test.go Aggregates_String — the
        # zero payload of string columns makes both fall out below);
        # everything else is rejected (reference supports first/last on
        # strings — host path, later round)
        for call, spec, params, field_name in aggs:
            if schema.get(field_name) == FieldType.STRING and \
                    spec.name not in ("count", "mean", "stddev"):
                raise QueryError(
                    f"{spec.name}() is not supported on string field {field_name!r}"
                )
        # selector ordering uses an int32 (hi, lo) split of rel ns; guard the
        # 2^61 ns (~73 year) cliff explicitly rather than wrapping silently
        if tmax - aligned >= (1 << 61):
            raise QueryError("time range too large (over ~73 years) for aggregation")

        # pre-aggregation fast path (reference: immutable/pre_aggregation.go
        # block skipping, SURVEY.md §7 'before device transfer'): for
        # full-range count/sum/mean with no field filter, chunks wholly
        # inside the range contribute their stored (count, sum) WITHOUT
        # being decoded or transferred. Safe only when the series' sources
        # cannot overlap (no memtable rows in range, non-overlapping chunks).
        pre_eligible = (
            not group_time
            and not time_aggs
            and not sc.has_row_filter
            and all(spec.name in ("count", "sum", "mean") for _c, spec, _p, _f in aggs)
            # remote proxies carry no chunk metadata: full decode for them
            and all(getattr(sh, "supports_preagg", False) for sh in shards)
        )
        # pre-agg accumulators: int64 for INT fields (stored vsum values are
        # exact python ints), float64 otherwise
        def _pre_dtype(f):
            return np.int64 if schema.get(f) == FieldType.INT else np.float64

        pre_count = (
            {f: np.zeros(num_segments, np.int64) for f in needed_fields}
            if pre_eligible else {}
        )
        pre_sum = (
            {f: np.zeros(num_segments, _pre_dtype(f)) for f in needed_fields}
            if pre_eligible else {}
        )
        sum_fields = {f for _c, spec, _p, f in aggs if spec.name != "count"}

        time_segs: list[np.ndarray] = []
        time_vals: list[np.ndarray] = []
        pre_used = False
        sliced_out = None

        # decoded-column cache, device tier (storage/colcache.py): stamp
        # grid batches with a scan signature so their padded device
        # buffers are retained and a repeated identical scan skips the
        # host->device transfer (and the grid scatter). Local
        # deterministic scans only — no remote peers. Under a device
        # mesh the retained buffers are MESH-SHARDED (grid.py puts the
        # cold grid straight into the sharded layout), so warm mesh
        # queries skip the per-query shard_leading_axis copy entirely.
        device_token = None
        if (
            group_time is not None
            and self.router is None
            and ctx.live is None
            and colcache_mod.GLOBAL.device_enabled()
        ):
            device_token = _device_scan_token(
                db, rp, mst, sc, group_time, group_tags,
                stmt.group_by_all_tags, tmin, tmax, aligned, W, dtype,
                scan_ranges, shards)
        if device_token is not None:
            for f, b in batches.items():
                if hasattr(b, "device_cache_token"):
                    b.device_cache_token = f"{device_token}|{f}"

        # at-spec scans: window-aligned time slicing bounds host/device
        # memory and overlaps decode with device compute (reference
        # analogue: the record-plan batch reader streams chunks,
        # engine/record_plan.go:75)
        slice_plan = None
        if (
            group_time is not None
            and not time_aggs
            and not pre_eligible
            and not no_scan
            and self.router is None
            and ctx.live is None
            and W >= 8
        ):
            slice_plan = _plan_scan_slices(
                shards, mst, scan_plan, aligned, group_time.every_ns, W,
                tmin, tmax)

        cc_before = (colcache_mod.GLOBAL.counters()
                     if colcache_mod.GLOBAL.enabled() else None)
        # per-query working-set reservation (utils/governor.py): charge
        # the chunk-meta estimate against the unified memory ledger for
        # the scan's duration; a reservation that would overdraw the
        # ledger kills this query through the tracker (clean error, no
        # OOM).  Zero-cost no-op when the governor is disabled.
        reservation = contextlib.nullcontext()
        if GOVERNOR.enabled() and not no_scan:
            est = estimate_scan_bytes(
                shards, mst, tmin, tmax,
                len(read_fields) if read_fields is not None else
                len(schema) or 1)
            reservation = GOVERNOR.scan_reservation(
                TRACKER.current_qid(), est)
        with reservation, trace.span("scan") as scan_span:
            if no_scan:
                rows_scanned = 0
            elif slice_plan is not None:
                rows_scanned, sliced_out = self._scan_sliced(
                    slice_plan, scan_plan, scan_ranges, sc, mst, group_time,
                    needed_fields, read_fields, dtype, schema,
                    per_field_aggs, num_groups, device_token,
                )
            else:
                rows_scanned, pre_used = self._scan_monolithic(
                    scan_plan, scan_ranges, sc, mst, group_time, tmin, W,
                    needed_fields, read_fields, dtype, aligned, batches,
                    time_aggs, time_segs, time_vals, pre_eligible,
                    pre_count, pre_sum, sum_fields, tmax,
                )
            scan_span.add_field("rows", rows_scanned)
            if slice_plan is not None:
                scan_span.add_field("slices", len(slice_plan))
        STATS.incr("executor", "rows_scanned", rows_scanned)
        # decoded-column cache attribution for EXPLAIN ANALYZE / query
        # stage stats: the scan-interval delta of the process-global
        # counters (concurrent queries can bleed in; the per-query exact
        # time also lands on this query via querytracker stages)
        if cc_before is not None:
            cc_after = colcache_mod.GLOBAL.counters()
            with trace.span("colcache") as sp:
                for key in ("hits", "misses", "device_hits",
                            "device_misses"):
                    sp.add_field(key, cc_after[key] - cc_before[key])
                sp.add_field(
                    "time_ms",
                    round((cc_after["time_ns"] - cc_before["time_ns"])
                          / 1e6, 3))
                sp.add_field("bytes_resident", cc_after["bytes"])
                sp.add_field("device_bytes", cc_after["device_bytes"])

        # run aggregates on device
        agg_results = {}  # id(call) -> (values, sel, counts)
        dv_before = devobs.span_snapshot() if devobs.enabled() else None
        with trace.span("device_compute") as sp:
            if not no_scan and sliced_out is None:
                # one launch and one fetch a statement, not one a field:
                # the batches that froze to the same geometry ride in one
                # program (models/launch.py); run() below only combines.
                # GROUP BY time() never consults selector timestamps
                # (the window start renders), hence want_sel
                launch.run([
                    it for f, b in batches.items()
                    if hasattr(b, "launch_items")
                    for it in b.launch_items(
                        num_segments, per_field_aggs[f],
                        want_sel=not group_time)])
            for call, spec, params, field_name in aggs:
                TRACKER.check()  # kill between aggregates
                if no_scan:
                    # every window served from cache/rollup: no scan, no
                    # device work
                    dt = (np.int64 if isinstance(
                        batches[field_name], ragged.IntExactBatch)
                        and spec.name in ("sum", "count") else np.float64)
                    agg_results[id(call)] = (
                        np.zeros(num_segments, dt), None,
                        np.zeros(num_segments, np.int64), spec,
                        field_name, None)
                    continue
                if sliced_out is not None:
                    out, sel, counts = _stitch_sliced(
                        sliced_out, spec, params, field_name,
                        num_groups, W, num_segments)
                elif group_time and getattr(
                        batches[field_name], "supports_want_sel", False):
                    # GROUP BY time(): selector timestamps are never
                    # consulted (window start renders instead), so skip
                    # the selector-index kernels entirely — the imat
                    # build + lex scans were most of the grid path's
                    # cost for max()/min() scans
                    out, sel, counts = batches[field_name].run(
                        spec, num_segments, params, want_sel=False)
                else:
                    out, sel, counts = batches[field_name].run(
                        spec, num_segments, params)
                if spec.name == "percentile" and params:
                    # influx: rank floor(n*q/100+0.5)-1 < 0 yields NO row
                    # for the window (the device kernel clamps to the
                    # minimum sample; zero the counts so it renders empty)
                    qv = float(params[0])
                    ok = np.floor(counts * qv / 100.0 + 0.5) >= 1
                    if not ok.all():
                        counts = np.where(ok, counts, 0)
                if spec.name == "stddev" and \
                        schema.get(field_name) == FieldType.STRING:
                    # string stddev renders null rows (influx
                    # Aggregates_String; numeric singletons stay 0 — the
                    # reference's NewStdDevReduce rule)
                    out = np.where(counts > 0, np.nan, out)
                if pre_used:
                    # combine device partials with pre-agg contributions
                    pc = pre_count[field_name]
                    ps = pre_sum[field_name]
                    if spec.name == "count":
                        out = out + pc
                    elif spec.name == "sum":
                        out = out + ps
                    else:  # mean = (dev_sum + pre_sum) / (dev_cnt + pre_cnt)
                        dev_sum, _s, _c = batches[field_name].run(
                            aggmod.get("sum"), num_segments
                        )
                        total_c = counts + pc
                        out = (dev_sum + ps) / np.maximum(total_c, 1)
                    counts = counts + pc.astype(counts.dtype)
                agg_results[id(call)] = (out, sel, counts, spec, field_name, None)
            if time_aggs:
                import dataclasses as _dc

                seg_all = (
                    np.concatenate(time_segs) if time_segs
                    else np.empty(0, np.int32)
                )
                t_all = (
                    np.concatenate(time_vals) if time_vals
                    else np.empty(0, np.int64)
                )
                tcounts = np.bincount(seg_all, minlength=num_segments).astype(np.int64)
            for call, spec, params, field_name in tag_count_aggs:
                out = np.zeros(num_segments, np.int64)
                # the constant-0 row emits in EVERY window: under
                # GROUP BY time() the reference renders the shortcut per
                # window (window 0 alone would truncate the series to one
                # row); without time grouping W == 1 and this is the
                # single constant row as before
                counts = np.ones(num_segments, np.int64)  # rows render as 0
                agg_results[id(call)] = (out, None, counts, spec,
                                         field_name, None)
            for call, spec, _params, _f in time_aggs:
                if spec.name == "count":
                    tout = tcounts
                elif spec.name in ("last", "max"):
                    tout = np.full(num_segments, np.iinfo(np.int64).min, np.int64)
                    np.maximum.at(tout, seg_all, t_all)
                else:  # first/min
                    tout = np.full(num_segments, np.iinfo(np.int64).max, np.int64)
                    np.minimum.at(tout, seg_all, t_all)
                spec2 = _dc.replace(spec, int_output=True)
                agg_results[id(call)] = (tout, None, tcounts, spec2, "time", tout)
            sp.add_field("aggregates", len(aggs))
            sp.add_field("segments", num_segments)
            if sliced_out is not None:
                sp.add_field(
                    "batch_rows",
                    {f: sum(sb[f].n for _w0, _ws, sb in sliced_out)
                     for f in needed_fields})
                sp.add_field(
                    "layouts",
                    {f: "sliced[" + ",".join(sorted(
                        {sb[f].layout_name() for _w0, _ws, sb in sliced_out}
                        or {"empty"})) + "]"
                     for f in needed_fields})
            else:
                sp.add_field(
                    "batch_rows", {f: b.n for f, b in batches.items()}
                )
                # EXPLAIN ANALYZE shows which layout actually executed per
                # field (a GridBatch may have fallen back internally, or
                # not have run at all on a full cache hit)
                sp.add_field(
                    "layouts", {f: b.layout_name() for f, b in batches.items()}
                )
            # counts the statement's aggregates, not its launches (those
            # are query_stages/device_launch_count, one a launch group)
            STATS.incr("executor", "device_batches", len(aggs))
            if dv_before is not None:
                # devobs delta attribution (compiles + transfer bytes
                # this span caused; concurrent queries can bleed in —
                # the per-query exact time lands via the device_*
                # tracker stages)
                dv_after = devobs.span_snapshot()
                for key in ("compiles", "h2d_bytes", "d2h_bytes",
                            "reshard_bytes"):
                    sp.add_field(key, dv_after[key] - dv_before[key])
                sp.add_field("compile_wall_ms", round(
                    dv_after["compile_wall_ms"]
                    - dv_before["compile_wall_ms"], 3))

        has_remote_data = any(
            isinstance(sh, pcluster.MetaShard) for sh in shards
        )
        if pushdown and ctx.live is not None and has_remote_data:
            # aggregate pushdown: peers computed the same grid over their
            # shards; merge their O(groups x windows) partial arrays
            # (reference: rpc_transform partial agg + merge_transform)
            from opengemini_tpu.sql import astjson

            with trace.span("remote_partials") as sp:
                req = {
                    "db": db, "rp": rp, "mst": mst,
                    "tmin": tmin, "tmax": tmax, "aligned": aligned,
                    "every_ns": group_time.every_ns if group_time else 0,
                    "offset_ns": group_time.offset_ns if group_time else 0,
                    "W": W, "group_tags": group_tags,
                    "aggs": per_field_aggs,
                    "tag_expr": astjson.to_json(sc.tag_expr),
                    "field_expr": astjson.to_json(sc.field_expr),
                    "mixed_expr": astjson.to_json(sc.mixed_expr),
                    "mixed_series_level": sc.mixed_series_level,
                    # the COORDINATOR's tag-key view: peers must evaluate
                    # mixed trees against the same classification — a tag
                    # absent from a peer's local index must still inject
                    # as an empty-string column
                    "tag_keys": sorted(sc.tag_keys),
                }
                peer_docs = self.router.select_partials(req, ctx.live)
                for doc in peer_docs:
                    # stitch each replica's span subtree (shipped in the
                    # partials header) under this RPC span — parentage
                    # was fixed by the wire ctx the request carried
                    trace.graft(doc.pop("trace", None))
                if peer_docs:
                    pmod.merge_remote_partials(
                        agg_results, aggs, batches, group_keys, W,
                        peer_docs, group_tags,
                    )
                sp.add_field("peers", len(peer_docs))

        if rollup_plan is not None:
            # before the cache merge: the cache persists the spliced
            # windows (they sit in its stale set) from these arrays
            group_keys = rollup_plan.merge(agg_results, aggs, group_keys)
        if cache_plan is not None:
            with trace.span("inc_cache"):
                group_keys = cache_plan.merge(agg_results, aggs, group_keys)
        with trace.span("render"):
            answer = self._render_agg(
                stmt, mst, group_tags, group_keys, aligned, W, agg_results,
                batches, schema,
            )
            if isinstance(answer, qrender.Frame) and not frames:
                return answer.series()
            return answer


    def _scan_monolithic(
        self, scan_plan, scan_ranges, sc, mst, group_time, tmin, W,
        needed_fields, read_fields, dtype, aligned, batches,
        time_aggs, time_segs, time_vals, pre_eligible,
        pre_count, pre_sum, sum_fields, tmax,
    ) -> tuple[int, bool]:
        """The classic single-pass scan: decode every series in range into
        `batches`. Returns (rows_scanned, pre_used).

        Pipelined (storage/scanpool.py): bulk shard reads double-buffer —
        unit N+1 decodes on a prefetch thread (which itself fans chunk
        decodes across the worker pool) while unit N's rows feed the
        device batches. Per-series records coalesce through a staging
        buffer so each field takes ONE contiguous batch add per scan
        instead of one tiny append per series."""
        rows_scanned = 0
        pre_used = False
        fmask = None

        def _scan_record(rec, seg, sids=None):
            if time_aggs:
                m = fmask if fmask is not None else slice(None)
                time_segs.append(seg[m])
                time_vals.append(rec.times[m])
            _add_record_to_batches(
                rec, seg, aligned, needed_fields, batches, dtype, fmask,
                sids=sids,
            )

        # batched multi-series path: one bulk decode per shard when
        # many series are scanned (packed colstore chunks decode once
        # for all their series, with no per-sid Python loop: config #5
        # of BASELINE.md scans 1M series)
        mem_kw: dict[int, dict] = {}    # read_series' `mem`, by shard
        by_shard: dict[int, tuple] = {}
        for sh, sid, gid in scan_plan:
            by_shard.setdefault(id(sh), (sh, []))[1].append((sid, gid))
        remaining_plan = []
        units = []  # thunks: () -> (sh, sid_sorted, gid_sorted, sid_arr, rec)
        for sh, pairs in by_shard.values():
            if pre_eligible:
                # a packed chunk's stored sums are the chunk's, not a
                # series': its series would each be refused by
                # `_scan_preagg` and decoded one by one (150 s of a count()
                # over 1,000,000 series), so they take the bulk decode
                # here, and only the others ask for their chunks' sums
                pairs, stored = _split_packed(sh, mst, pairs, tmin, tmax)
                remaining_plan.extend((sh, sid, gid) for sid, gid in stored)
            if len(pairs) < 64 or not hasattr(sh, "read_series_bulk"):
                remaining_plan.extend(
                    (sh, sid, gid) for sid, gid in pairs)
                # rows not yet flushed: taken once a shard for all its
                # series of the tail (span `mem_read`), not once a
                # series and a scan range; a proxy has no such view
                view = sh.mem_view(
                    mst, [sid for sid, _gid in pairs], read_fields) \
                    if hasattr(sh, "mem_view") and not pre_eligible else None
                if view is not None:
                    mem_kw[id(sh)] = {"mem": view}
                continue
            sid_list = np.asarray([p[0] for p in pairs], np.int64)
            gid_list = np.asarray([p[1] for p in pairs], np.int64)
            o = np.argsort(sid_list)
            sid_sorted, gid_sorted = sid_list[o], gid_list[o]
            for rlo, rhi in scan_ranges:
                units.append(
                    lambda sh=sh, ss=sid_sorted, gs=gid_sorted,
                    rlo=rlo, rhi=rhi:
                    (sh, ss, gs) + sh.read_series_bulk(
                        mst, ss, rlo, rhi, fields=read_fields))
        for sh, sid_sorted, gid_sorted, sid_arr, rec in \
                scanpool.prefetch_ordered(units):
            TRACKER.check()
            if len(rec) == 0:
                continue
            rows_scanned += len(rec)
            fmask = (
                cond.eval_row_filter(sc, rec, sid_arr=sid_arr,
                                     index=sh.index)
                if sc.has_row_filter
                else None
            )
            gid_rows = gid_sorted[
                np.searchsorted(sid_sorted, sid_arr)]
            if group_time:
                widx, _ = winmod.window_index(
                    rec.times, tmin, group_time.every_ns,
                    group_time.offset_ns)
                seg = (gid_rows * W + widx.astype(np.int64)
                       ).astype(np.int32)
            else:
                seg = gid_rows.astype(np.int32)
            _scan_record(rec, seg, sids=sid_arr)
        # per-series tail: stage rows and materialize ONE contiguous
        # array set per field at the end (per-chunk concatenation in this
        # loop was the executor-side hot spot at high cardinality)
        stager = _ScanStager(needed_fields, dtype, batches, time_aggs,
                             time_segs, time_vals, aligned) \
            if not pre_eligible and remaining_plan else None
        for sh, sid, gid in remaining_plan:
            TRACKER.check()  # KILL QUERY cancellation point
            if pre_eligible:
                handled, got_rows = self._scan_preagg(
                    sh, mst, sid, gid, tmin, tmax, needed_fields,
                    batches, pre_count, pre_sum, dtype, aligned, sum_fields,
                )
                if handled:
                    pre_used = True
                    rows_scanned += got_rows
                    continue
            for rlo, rhi in scan_ranges:
                rec = sh.read_series(mst, sid, rlo, rhi, fields=read_fields,
                                     **mem_kw.get(id(sh), {}))
                if len(rec) == 0:
                    continue
                rows_scanned += len(rec)
                fmask = (
                    cond.eval_row_filter(
                        sc, rec, tags=sh.index.tags_of(sid))
                    if sc.has_row_filter
                    else None
                )
                if group_time:
                    widx, _ = winmod.window_index(
                        rec.times, tmin, group_time.every_ns,
                        group_time.offset_ns)
                    seg = (gid * W + widx.astype(np.int64)
                           ).astype(np.int32)
                else:
                    seg = np.full(len(rec), gid, dtype=np.int32)
                if stager is not None:
                    stager.add(rec, seg, fmask, sid)
                else:
                    _scan_record(rec, seg, sids=sid)
        if stager is not None:
            stager.flush()
        return rows_scanned, pre_used

    def _scan_sliced(
        self, slice_plan, scan_plan, scan_ranges, sc, mst, group_time,
        needed_fields, read_fields, dtype, schema, per_field_aggs,
        num_groups, device_token=None,
    ) -> tuple[int, list]:
        """Window-aligned sliced scan: each slice decodes into its own
        batch set, then the device kernels for that slice are DISPATCHED
        (not materialized) before the next slice decodes — on a real
        accelerator the device crunches slice k while the host decodes
        k+1 (double-buffering). Returns
        (rows_scanned, [(w0, W_s, {field: batch})])."""
        rows_scanned = 0
        out = []
        STATS.incr("executor", "sliced_scans")
        for (w0, W_s, lo, hi) in slice_plan:
            TRACKER.check()
            ranges = [(max(lo, rlo), min(hi, rhi))
                      for rlo, rhi in scan_ranges
                      if max(lo, rlo) < min(hi, rhi)]
            if not ranges:
                continue
            plans = layoutplan.Plans()  # a slice's rows are its own
            sbatches = {
                f: pick_batch(schema, per_field_aggs[f], f, dtype,
                              (W_s, group_time.every_ns), plans)
                for f in needed_fields
            }
            if device_token is not None:
                # per-slice signature: same scan, distinct window span
                for f, b in sbatches.items():
                    if hasattr(b, "device_cache_token"):
                        b.device_cache_token = \
                            f"{device_token}|{f}|{w0}:{W_s}"
            got, _pre = self._scan_monolithic(
                scan_plan, ranges, sc, mst, group_time, lo, W_s,
                needed_fields, read_fields, dtype, lo, sbatches,
                [], [], [], False, {}, {}, set(), hi,
            )
            rows_scanned += got
            for f, b in sbatches.items():
                prefetch = getattr(b, "prefetch", None)
                if prefetch is not None:
                    prefetch(num_groups * W_s, per_field_aggs[f])
            out.append((w0, W_s, sbatches))
        return rows_scanned, out

    def _scan_preagg(
        self, sh, mst, sid, gid, tmin, tmax, needed_fields,
        batches, pre_count, pre_sum, dtype, aligned, sum_fields,
    ) -> tuple[bool, int]:
        """Try the pre-agg path for one series. Returns (handled, rows):
        handled=False -> caller does the normal decode+batch scan. No side
        effects until the whole series validates."""
        needs_merge, srcs = _series_needs_merged_decode(sh, mst, sid, tmin, tmax)
        if needs_merge:
            return False, 0  # dedup required: decode via read_series
        if not srcs:
            return True, 0  # nothing in range at all
        # validate: every fully-covered chunk must carry a sum for fields
        # that need one (bool/string columns store count-only pre-agg)
        contrib: list[tuple[str, int, float | None]] = []
        full_rows = 0
        partials = []
        for r, c in srcs:
            if tmin <= c.tmin and c.tmax < tmax:
                for fname in needed_fields:
                    loc = c.cols.get(fname)
                    if loc is None:
                        continue
                    pre = loc["pre"]
                    if not pre.count:
                        continue
                    if fname in sum_fields and pre.vsum is None:
                        return False, 0
                    contrib.append((fname, pre.count, pre.vsum))
                full_rows += c.rows
            else:
                partials.append((r, c))
        for fname, cnt, vsum in contrib:
            pre_count[fname][gid] += cnt
            if vsum is not None:
                pre_sum[fname][gid] += vsum
        rows = full_rows
        for r, c in partials:
            try:
                rec = r.read_chunk(
                    mst, c, needed_fields).slice_time(tmin, tmax)
            except CorruptFile as e:
                # media damage on the pre-agg decode path: quarantine
                # through the owning shard (raises FileQuarantined)
                # rather than surfacing a raw codec error
                handler = getattr(sh, "note_corrupt", None)
                if handler is not None:
                    handler(e)
                raise
            if not len(rec):
                continue
            rows += len(rec)
            seg = np.full(len(rec), gid, dtype=np.int32)
            _add_record_to_batches(
                rec, seg, aligned, needed_fields, batches, dtype, None,
                sids=sid,
            )
        return True, rows


    def _group_tags(self, stmt, shards, mst) -> list[str]:
        if stmt.group_by_all_tags:
            keys: set[str] = set()
            for sh in shards:
                keys.update(sh.index.tag_keys(mst))
            return sorted(keys)
        return list(stmt.group_by_tags)


    def _render_agg(
        self, stmt, mst, group_tags, group_keys, aligned, W, agg_results,
        batches, schema,
    ):
        """The reduce's arrays to the answer: a `qrender.Frame` (columns
        evaluated and filled as arrays; its reader makes the tree or the
        response's bytes of it), or the list of series dicts where the
        shape needs the per-row walker."""
        columns, col_exprs = _output_columns(stmt)
        # selector fast path: a single selector call (bare, or wrapped in
        # scalar math like `max(rx) * 1`), no GROUP BY time -> result time
        # is the selected point's own timestamp (reference
        # TestServer_Query_Aggregates_Math#2)
        single_selector = None
        if not stmt.group_by_time and len(col_exprs) == 1:
            calls = _calls_in(col_exprs[0])
            if len(calls) == 1:
                entry = agg_results.get(id(calls[0]))
                if entry and entry[3].is_selector:
                    single_selector = entry

        cells = len(group_keys) * W * len(col_exprs)
        frame = None
        if single_selector is None:
            # every row's time is its window's: one array a column
            try:
                frame = qrender.build_frame(
                    stmt, mst, columns, col_exprs, group_tags, group_keys,
                    aligned, W, agg_results, schema)
            except qrender.NotColumnar:
                pass
        STATS.add("query", (("render_cells", cells),
                            ("render_bulk_cells",
                             0 if frame is None else cells)))
        if frame is not None:
            return frame
        host_times = (
            batches[single_selector[4]].host_times()
            if single_selector is not None and single_selector[5] is None
            else None
        )
        return _render_agg_rows(
            stmt, mst, columns, col_exprs, group_tags, group_keys, aligned,
            W, agg_results, schema, single_selector, host_times)

    # -- percentile_approx (chunk-histogram sketches) ------------------------



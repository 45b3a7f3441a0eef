"""Engine: databases -> retention policies -> time-partitioned shards.

Reference: engine/engine.go:112 (NewEngine, WriteRows:1203,
CreateShard:1270, loadShards:299) plus the shard-group time partitioning
from the meta data model (lib/util/lifted/influx/meta data.go). Round-1
scope: a single-node engine embedding its own metadata (the distributed
meta plane lives in opengemini_tpu/meta and layers on top).
"""

from __future__ import annotations

import collections.abc
import contextlib
import json
import os
import shutil
import threading
from opengemini_tpu.utils import lockdep, tracing
import time as _time

from opengemini_tpu.ingest import line_protocol as lp
from opengemini_tpu.record import FieldTypeConflict
from opengemini_tpu.storage.shard import Shard
from opengemini_tpu.utils.failpoint import inject as _fp
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
DEFAULT_SHARD_DURATION = 7 * 24 * 3600 * NS  # influx 1w default for infinite RPs

# Go time.Time zero (year 1, Jan 1 — a Monday) relative to the Unix epoch:
# the reference aligns shard groups with Go's Truncate, which rounds to
# multiples of the duration SINCE THE ZERO TIME (meta/data.go:2348), so 7d
# groups start on Mondays, not the epoch's Thursday grid. The offset in ns
# overflows int64, so alignment uses its residue mod the duration (the
# phase) — same grid, int64-safe (works for numpy vectorized forms too).
_GO_ZERO_S = -62135596800  # seconds; *NS overflows int64


# -- multi-core ingest pool (reference: influx.ScheduleUnmarshalWork) ----
_INGEST_WORKERS = int(os.environ.get("OGT_INGEST_WORKERS", "0")) or (
    len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
    else (os.cpu_count() or 1))
_INGEST_SEGMENT_BYTES = 1 << 20  # split target; bodies below 2MB stay inline
_NEEDS_PYTHON_PARSER = object()  # _write_segmented: skip native re-parse
_ingest_pool_obj = None
_ingest_pool_lock = lockdep.Lock()


def _ingest_pool():
    """Shared parse pool, or None on single-core hosts (threads would only
    add overhead when the C parser has one core to release the GIL to)."""
    global _ingest_pool_obj
    if _INGEST_WORKERS < 2:
        return None
    if _ingest_pool_obj is None:
        from concurrent.futures import ThreadPoolExecutor

        with _ingest_pool_lock:
            if _ingest_pool_obj is None:
                _ingest_pool_obj = ThreadPoolExecutor(
                    max_workers=_INGEST_WORKERS,
                    thread_name_prefix="ogt-ingest")
    return _ingest_pool_obj


def _split_lp_segments(raw: bytes, n: int) -> list[bytes]:
    """Split a line-protocol body into <= n segments at line boundaries."""
    target = max(len(raw) // n, _INGEST_SEGMENT_BYTES)
    segs, start = [], 0
    while start < len(raw) and len(segs) < n - 1:
        cut = raw.find(b"\n", start + target)
        if cut == -1:
            break
        segs.append(raw[start:cut + 1])
        start = cut + 1
    if start < len(raw):
        segs.append(raw[start:])
    return segs


class WrittenPoints(collections.abc.Sequence):
    """What a write observer is handed: the rows of one committed write
    as a read-only sequence of (measurement, tags, t_ns, {field: (type,
    value)}) tuples, in body order.

    A natively parsed write stays columnar: `len()` answers from the
    batches, and the first read (iteration, indexing) builds the tuples
    once with ColumnarBatch.to_points and keeps them, so every later
    reader of this write shares the one build.  A write that already
    holds its points (the Python parser, write_rows) is wrapped as it is.
    Read it on the notifying thread; an observer that defers its work
    takes `list(points)` first."""

    __slots__ = ("_batches", "_points", "_n")

    def __init__(self, batches=(), points: list | None = None):
        self._batches = batches
        self._points = points
        self._n = (len(points) if points is not None
                   else sum(len(b) for b in batches))

    def __len__(self) -> int:
        return self._n

    def _built(self) -> list:
        points = self._points
        if points is None:
            points = []
            for batch in self._batches:
                points.extend(batch.to_points())
            STATS.incr("write", "observer_rows_built", len(points))
            self._points = points
            self._batches = ()
        return points

    def __iter__(self):
        return iter(self._built())

    def __getitem__(self, i):
        return self._built()[i]


def _check_namespace_name(name: str, what: str) -> None:
    """db/rp names become directory components AND 'db|rp|start' keys in
    the balancer's load reports and placement overrides — separators and
    path characters must be rejected at creation."""
    if not name or any(c in name for c in "|/\\\n\r\0") or name in (".", ".."):
        raise WriteError(f"invalid {what} name {name!r}")


def _go_phase_ns(dur_ns: int) -> int:
    return (_GO_ZERO_S * NS) % dur_ns  # python ints: exact, non-negative


def shard_group_start(t_ns: int, dur_ns: int) -> int:
    """Shard-group start containing t_ns: Go Truncate alignment."""
    phase = _go_phase_ns(dur_ns)
    return (t_ns - phase) // dur_ns * dur_ns + phase


class RetentionPolicy:
    def __init__(self, name: str, duration_ns: int = 0, shard_duration_ns: int = DEFAULT_SHARD_DURATION):
        self.name = name
        self.duration_ns = duration_ns  # 0 = infinite
        self.shard_duration_ns = shard_duration_ns

    def to_json(self):
        return {
            "name": self.name,
            "duration_ns": self.duration_ns,
            "shard_duration_ns": self.shard_duration_ns,
        }

    @classmethod
    def from_json(cls, j):
        return cls(j["name"], j["duration_ns"], j["shard_duration_ns"])


class ContinuousQuery:
    """A registered CQ (reference: meta data model continuous queries +
    services/continuousquery scheduler)."""

    def __init__(self, name: str, select_text: str, resample_every_ns: int = 0,
                 resample_for_ns: int = 0, last_run_ns: int = 0):
        self.name = name
        self.select_text = select_text
        self.resample_every_ns = resample_every_ns
        self.resample_for_ns = resample_for_ns
        self.last_run_ns = last_run_ns

    def to_json(self):
        return {
            "name": self.name,
            "select_text": self.select_text,
            "resample_every_ns": self.resample_every_ns,
            "resample_for_ns": self.resample_for_ns,
            "last_run_ns": self.last_run_ns,
        }

    @classmethod
    def from_json(cls, j):
        return cls(j["name"], j["select_text"], j.get("resample_every_ns", 0),
                   j.get("resample_for_ns", 0), j.get("last_run_ns", 0))


class DownsamplePolicy:
    """Shard-rewrite policy (reference: downsample policies in the meta data
    model, engine_downsample.go): shards older than `age_ns` are rewritten
    at `every_ns` resolution."""

    def __init__(self, age_ns: int, every_ns: int, field_aggs: dict | None = None):
        self.age_ns = age_ns
        self.every_ns = every_ns
        self.field_aggs = field_aggs or {}  # field type name -> agg name

    def to_json(self):
        return {"age_ns": self.age_ns, "every_ns": self.every_ns,
                "field_aggs": self.field_aggs}

    @classmethod
    def from_json(cls, j):
        return cls(j["age_ns"], j["every_ns"], j.get("field_aggs", {}))


class StreamTask:
    """At-ingest window aggregation task (reference: services/stream +
    app/ts-store/stream tag_task/time_task)."""

    def __init__(self, name: str, select_text: str, delay_ns: int = 0):
        self.name = name
        self.select_text = select_text
        self.delay_ns = delay_ns

    def to_json(self):
        return {"name": self.name, "select_text": self.select_text,
                "delay_ns": self.delay_ns}

    @classmethod
    def from_json(cls, j):
        return cls(j["name"], j["select_text"], j.get("delay_ns", 0))


class Database:
    def __init__(self, name: str):
        self.name = name
        self.rps: dict[str, RetentionPolicy] = {}
        self.default_rp = "autogen"
        self.continuous_queries: dict[str, ContinuousQuery] = {}
        # rp name -> [DownsamplePolicy]
        self.downsample: dict[str, list[DownsamplePolicy]] = {}
        self.streams: dict[str, StreamTask] = {}
        self.subscriptions: dict[str, object] = {}
        # declared materialized rollups (storage/rollup.RollupSpec):
        # maintained incrementally on ingest, spliced into eligible
        # GROUP BY time() plans by the executor
        self.rollups: dict[str, object] = {}
        # DROP MEASUREMENT is a mark + deferred purge (reference:
        # MarkMeasurementDelete, lifted/influx/coordinator/
        # statement_executor.go:894): queries hide marked measurements
        # immediately, SHOW SERIES keeps their series until the purge
        # actually runs (the reference black-box suite asserts this,
        # tests/server_test.go TestServer_Query_ShowSeries)
        self.dropped_msts: set[str] = set()


class WriteError(Exception):
    pass


class DatabaseNotFound(WriteError):
    def __init__(self, name: str):
        super().__init__(f"database not found: {name!r}")


class Engine:
    """Single-node storage engine with embedded metadata."""

    def __init__(
        self,
        root: str,
        sync_wal: bool = False,
        flush_threshold_bytes: int = 64 << 20,
        tag_arrays: bool = False,
    ):
        self.root = root
        self.sync_wal = sync_wal
        self.flush_threshold_bytes = flush_threshold_bytes
        # openGemini tag-array expansion (`host=[a,b]`), opt-in like the
        # reference's per-database enableTagArray — brackets are legal
        # literal tag bytes when off
        self.tag_arrays = tag_arrays
        os.makedirs(root, exist_ok=True)
        # hot class: every write/query path serializes through it, so a
        # blocking call here stalls the whole engine (lockdep-enforced;
        # threshold flushes already run outside it, PR 3)
        self._lock = lockdep.mark_hot(lockdep.RLock(), "engine._lock")
        # syscontrol toggles (reference: lib/syscontrol disable write/read)
        self.write_disabled = False
        self.read_disabled = False
        self._write_observers: list = []
        # object-storage tier (reference: lib/fileops obs): shard groups
        # offloaded to the store, hydrated back lazily on query
        self.obs_store = None
        self.obs_shards: set[tuple[str, str, int]] = set()
        self.databases: dict[str, Database] = {}
        # (db, rp, group_start) -> Shard
        self._shards: dict[tuple[str, str, int], Shard] = {}
        self._load_meta()
        self._models = None  # lazy ModelStore (castor)
        # inbound two-phase migrations: mig_id -> (db, rp, start, Shard);
        # staging shards are NEVER in _shards (invisible to queries)
        self._staging: dict[str, tuple] = {}
        # mig_ids whose commit fold is running RIGHT NOW (popped from
        # _staging, marker not yet durable): a retried commit racing the
        # fold must wait for the marker, not 400 "unknown migration"
        self._folding: set[str] = set()
        self._load_shards()
        # materialized-rollup manager (storage/rollup.py): constructed
        # only when a spec is declared AND OGT_ROLLUP != 0 — None keeps
        # every write/query path bit-identical (one attribute check)
        self.rollup_mgr = None
        self._maybe_init_rollups()
        # continuous rule engine (promql/rules.py): set by RuleManager
        # when OGT_RULES enables it — None keeps every write path
        # bit-identical (one attribute check, same contract as rollups)
        self.rules_hook = None
        # live acked-vs-durable gauges ride /debug/vars (utils/stats
        # provider; close() unregisters so dead engines drop out)
        self._durability_provider = self._durability_gauges
        STATS.register_provider("durability", self._durability_provider)
        # quarantined-file gauge (media-fault containment): current
        # count of files pulled from the read set, next to the
        # detection counters the shards increment
        self._quarantine_provider = self._quarantine_gauges
        STATS.register_provider("quarantine", self._quarantine_provider)
        # memtable+WAL backlog joins the resource governor's unified
        # memory ledger and drives the /write backpressure watermark
        # (utils/governor.py; multiple engines sum process-wide)
        from opengemini_tpu.utils.governor import GOVERNOR as _GOVERNOR

        self._governor_provider = self.mem_backlog_bytes
        _GOVERNOR.register_component("memtable", self._governor_provider)

    # -- metadata -----------------------------------------------------------

    @property
    def models(self):
        """Fitted anomaly-detection models (castor fit pipeline),
        persisted under <root>/models/."""
        if self._models is None:
            from opengemini_tpu.services.castor import ModelStore

            self._models = ModelStore(os.path.join(self.root, "models"))
        return self._models

    def _meta_path(self) -> str:
        return os.path.join(self.root, "meta.json")

    def _load_meta(self) -> None:
        p = self._meta_path()
        if not os.path.exists(p):
            return
        with open(p, encoding="utf-8") as f:
            j = json.load(f)
        for dbj in j.get("databases", []):
            db = Database(dbj["name"])
            db.default_rp = dbj.get("default_rp", "autogen")
            for rpj in dbj.get("rps", []):
                rp = RetentionPolicy.from_json(rpj)
                db.rps[rp.name] = rp
            for cqj in dbj.get("cqs", []):
                cq = ContinuousQuery.from_json(cqj)
                db.continuous_queries[cq.name] = cq
            for rp_name, pols in dbj.get("downsample", {}).items():
                db.downsample[rp_name] = [DownsamplePolicy.from_json(p) for p in pols]
            for sj in dbj.get("streams", []):
                st = StreamTask.from_json(sj)
                db.streams[st.name] = st
            from opengemini_tpu.services.subscriber import Subscription

            for sj in dbj.get("subscriptions", []):
                sub = Subscription.from_json(sj)
                db.subscriptions[sub.name] = sub
            db.dropped_msts = set(dbj.get("dropped_msts", []))
            if dbj.get("rollups"):
                from opengemini_tpu.storage.rollup import RollupSpec

                for rj in dbj["rollups"]:
                    spec = RollupSpec.from_json(rj)
                    db.rollups[spec.name] = spec
            self.databases[db.name] = db
        self.obs_shards = {
            (d, r, int(s)) for d, r, s in j.get("obs_shards", [])
        }

    def _save_meta(self) -> None:
        j = {
            "obs_shards": sorted(list(k) for k in self.obs_shards),
            "databases": [
                {
                    "name": db.name,
                    "default_rp": db.default_rp,
                    "rps": [rp.to_json() for rp in db.rps.values()],
                    "cqs": [cq.to_json() for cq in db.continuous_queries.values()],
                    "downsample": {
                        rp: [p.to_json() for p in pols]
                        for rp, pols in db.downsample.items()
                    },
                    "streams": [s.to_json() for s in db.streams.values()],
                    "subscriptions": [
                        s.to_json() for s in db.subscriptions.values()
                    ],
                    "dropped_msts": sorted(db.dropped_msts),
                    "rollups": [r.to_json() for r in db.rollups.values()],
                }
                for db in self.databases.values()
            ]
        }
        from opengemini_tpu.storage import diskfault

        tmp = self._meta_path() + ".tmp"
        if diskfault.armed():
            diskfault.check("write", self._meta_path(),
                            site="meta-save-write")
        # audited (lockdep): the meta fsync runs under the engine lock —
        # DDL is rare control-plane work, and the lock is what keeps the
        # in-memory mutation and its durable record atomic (a failed
        # save raises INSIDE the op; tests/test_diskfault.py pins that).
        # Unlike the PR 7 rollup-state fsync this is not a hot path.
        with lockdep.allow_blocking("engine meta save under DDL lock"), \
                open(tmp, "w", encoding="utf-8") as f:
            json.dump(j, f)
            f.flush()
            if diskfault.armed():
                diskfault.on_fsync(self._meta_path(),
                                   site="meta-save-fsync")
            os.fsync(f.fileno())
        os.replace(tmp, self._meta_path())

    def create_database(self, name: str) -> None:
        _check_namespace_name(name, "database")
        with self._lock:
            if name in self.databases:
                return
            db = Database(name)
            db.rps["autogen"] = RetentionPolicy("autogen")
            self.databases[name] = db
            self._save_meta()

    def drop_database(self, name: str) -> None:
        import shutil

        obs_purge = []
        with self._lock:
            if name not in self.databases:
                return
            for key in [k for k in self._shards if k[0] == name]:
                shard = self._shards.pop(key)
                shard.close()
                _remove_shard_dir(shard.path)  # follows cold-tier symlinks
            obs_purge = self._purge_obs(lambda k: k[0] == name)
            del self.databases[name]
            self._save_meta()
            p = os.path.join(self.root, "data", name)
            if os.path.exists(p):
                shutil.rmtree(p)
            if self.rollup_mgr is not None:
                # a recreated database must not inherit this one's
                # rollup watermarks (stale-clean windows would splice
                # as empty over the new incarnation's data)
                self.rollup_mgr.drop_db_state(name)
            else:
                shutil.rmtree(os.path.join(self.root, "rollup", name),
                              ignore_errors=True)
            if self.rules_hook is not None:
                # same stale-state hazard for rule groups: a recreated
                # db must not inherit watermarks/alert state
                self.rules_hook.drop_db_state(name)
            else:
                shutil.rmtree(os.path.join(self.root, "rules", name),
                              ignore_errors=True)
        self._delete_obs_prefixes(obs_purge)

    def drop_retention_policy(self, db: str, name: str) -> None:
        obs_purge = []
        with self._lock:
            d = self.databases.get(db)
            if d and name in d.rps:
                del d.rps[name]
                d.downsample.pop(name, None)  # policies die with their rp
                for key in [k for k in self._shards
                            if k[0] == db and k[1] == name]:
                    shard = self._shards.pop(key)
                    shard.close()
                    _remove_shard_dir(shard.path)
                obs_purge = self._purge_obs(
                    lambda k: k[0] == db and k[1] == name)
                if d.default_rp == name:
                    d.default_rp = "autogen" if "autogen" in d.rps else next(
                        iter(d.rps), "autogen"
                    )
                self._save_meta()
        self._delete_obs_prefixes(obs_purge)

    def create_retention_policy(
        self, db: str, name: str, duration_ns: int, shard_duration_ns: int | None = None,
        default: bool = False,
    ) -> None:
        _check_namespace_name(name, "retention policy")
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            if not shard_duration_ns:  # absent or 0 = auto (influx meta)
                shard_duration_ns = _auto_shard_duration(duration_ns)
            d.rps[name] = RetentionPolicy(name, duration_ns, shard_duration_ns)
            if default:
                d.default_rp = name
            self._save_meta()

    def alter_retention_policy(
        self, db: str, name: str, duration_ns: int | None = None,
        shard_duration_ns: int | None = None, default: bool = False,
    ) -> None:
        """Mutate an existing RP in place; None fields stay as they are.
        New shard duration only affects shard groups created after the
        change, matching influx semantics."""
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            rp = d.rps.get(name)
            if rp is None:
                raise ValueError(f"retention policy not found: {name}")
            new_dur = rp.duration_ns if duration_ns is None else duration_ns
            if shard_duration_ns is None:
                new_sd = rp.shard_duration_ns
            else:  # explicit 0 = recompute the auto layout (influx meta)
                new_sd = shard_duration_ns or _auto_shard_duration(new_dur)
            if new_dur and new_dur < new_sd:
                # influx rejects this combination rather than silently
                # rewriting the shard layout (ErrIncompatibleDurations)
                raise ValueError(
                    "retention policy duration must be greater than the "
                    "shard duration")
            rp.duration_ns = new_dur
            rp.shard_duration_ns = new_sd
            if default:
                d.default_rp = name
            self._save_meta()

    def disk_usage(self) -> dict:
        """{"total": bytes, "groups": {"db|rp|start": bytes}} for live
        shard dirs — the load signal the balancer compares across nodes
        (reference: store load report feeding balance_manager.go)."""
        groups: dict[str, int] = {}
        total = 0
        with self._lock:
            items = list(self._shards.items())
        for (db, rp, start), sh in items:
            n = 0
            try:
                for dirpath, _dirs, files in os.walk(
                        os.path.realpath(sh.path)):
                    for f in files:
                        try:
                            n += os.path.getsize(os.path.join(dirpath, f))
                        except OSError:
                            pass
            except OSError:
                pass
            groups[f"{db}|{rp}|{start}"] = n
            total += n
        return {"total": total, "groups": groups}

    def database_names(self) -> list[str]:
        return sorted(self.databases)

    # -- shards -------------------------------------------------------------

    def _shard_dir(self, db: str, rp: str, group_start: int) -> str:
        return os.path.join(self.root, "data", db, rp, str(group_start))

    def _load_shards(self) -> None:
        data_dir = os.path.join(self.root, "data")
        if not os.path.isdir(data_dir):
            return
        for db in os.listdir(data_dir):
            for rp in os.listdir(os.path.join(data_dir, db)):
                rp_obj = self.databases.get(db)
                rp_meta = rp_obj.rps.get(rp) if rp_obj else None
                dur = rp_meta.shard_duration_ns if rp_meta else DEFAULT_SHARD_DURATION
                for g in os.listdir(os.path.join(data_dir, db, rp)):
                    start = int(g)
                    self._shards[(db, rp, start)] = Shard(
                        self._shard_dir(db, rp, start), start, start + dur,
                        self.sync_wal, tag_arrays=self.tag_arrays,
                    )

    def _get_or_create_shard(self, db: str, rp: str, t_ns: int) -> Shard:
        d = self.databases.get(db)
        if d is None:
            raise DatabaseNotFound(db)
        rp_meta = d.rps.get(rp)
        if rp_meta is None:
            raise WriteError(f"retention policy not found: {db}.{rp}")
        dur = rp_meta.shard_duration_ns
        group_start = shard_group_start(t_ns, dur)
        key = (db, rp, group_start)
        shard = self._shards.get(key)
        if shard is None:
            if key in self.obs_shards:
                # writes into an offloaded range must land in the HYDRATED
                # group — a fresh empty shard here would later be clobbered
                # by hydration and the writes silently lost
                shard = self._hydrate_shard(db, rp, group_start)
                if shard is not None:
                    return shard
            shard = Shard(
                self._shard_dir(db, rp, group_start),
                group_start,
                group_start + dur,
                self.sync_wal,
                tag_arrays=self.tag_arrays,
            )
            self._shards[key] = shard
        return shard

    # -- DROP MEASUREMENT: mark + deferred purge ----------------------------

    def mark_measurement_delete(self, db: str, mst: str) -> None:
        """The reference's MarkMeasurementDelete: DROP MEASUREMENT only
        marks; SELECT/SHOW MEASUREMENTS hide it immediately, the data and
        its index entries survive until purge_dropped_measurements runs
        (retention tick, or synchronously before a new write to the name)."""
        d = self.databases.get(db)
        if d is None:
            raise DatabaseNotFound(db)
        to_reset = []
        with self._lock:
            d.dropped_msts.add(mst)
            if self.rollup_mgr is not None:
                # rollups of a dropped measurement drop WITH it: delete
                # their target rows (scoped to the _rollup RP — the
                # db-wide dropped_msts mark would collide with a raw
                # measurement of the same name) and reset the watermark
                # so a recreated name re-folds from scratch
                for spec in d.rollups.values():
                    if spec.measurement == mst:
                        self._purge_rollup_target(db, spec.target)
                        to_reset.append(spec.name)
            self._save_meta()
        for name in to_reset:
            # outside the engine lock: invalidation serializes against
            # in-flight maintenance (st.m_lock), which itself takes
            # engine locks while folding — lock order maintenance-lock
            # before engine lock, never the reverse
            self.rollup_mgr.invalidate(db, name)

    def is_measurement_dropped(self, db: str, mst: str) -> bool:
        d = self.databases.get(db)
        return d is not None and mst in d.dropped_msts

    def purge_dropped_measurements(self, db: str | None = None) -> int:
        """Physically delete mark-dropped measurements. Returns the number
        purged. Driven by the retention service; also runs synchronously
        before writes to a database with pending marks so old rows cannot
        resurface under a recreated measurement name."""
        n = 0
        with self._lock:
            for name, d in self.databases.items():
                if db is not None and name != db:
                    continue
                if not d.dropped_msts:
                    continue
                # offloaded (object-store) groups hold data too: hydrate
                # them first or the purge misses rows that would resurface
                # on the next query-driven hydration
                for (sdb, rp, g) in sorted(self.obs_shards):
                    if sdb == name:
                        self._hydrate_shard(sdb, rp, g)
                for mst in sorted(d.dropped_msts):
                    for (sdb, _rp, _g), sh in list(self._shards.items()):
                        if sdb == name:
                            sh.delete_data(mst)
                    n += 1
                d.dropped_msts.clear()
            if n:
                self._save_meta()
        return n

    def attach_object_store(self, store) -> None:
        self.obs_store = store
        # reconcile a crash between offload's registry save and the local
        # removal: a group present BOTH locally and in the registry keeps
        # the local copy (same or newer) and drops the stale store copy
        from opengemini_tpu.storage.objstore import shard_prefix

        with self._lock:
            stale = [k for k in self.obs_shards if k in self._shards]
        # bucket deletes are HTTP round trips: outside the engine lock
        # (lockdep), like drop_expired_shards
        for db, rp, start in stale:
            store.delete_prefix(shard_prefix(db, rp, start))
        with self._lock:
            for k in stale:
                self.obs_shards.discard(k)
            if stale:
                self._save_meta()

    def offload_shard(self, db: str, rp: str, group_start: int) -> bool:
        """Move one whole shard group into the object store (reference:
        the obs cold tier). Readers holding fds keep working (files are
        unlinked, not truncated); the group hydrates back on next query."""
        from opengemini_tpu.storage.objstore import shard_prefix

        if self.obs_store is None:
            return False
        import shutil as _shutil

        key = (db, rp, group_start)
        with self._lock:
            shard = self._shards.get(key)
            if shard is None:
                return False
        # UPLOAD PHASE — network IO under the SHARD's flush lock only
        # (lockdep caught the old shape: the whole upload ran under
        # engine._lock, stalling every write/query in the process behind
        # one shard's bucket transfer).  _flush_lock freezes the FILE
        # SET — flush/compact/delete/downsample all take it first —
        # while writes stay live; a write landing mid-upload bumps
        # data_version and the swap below aborts, leaving the shard
        # local (the obstier tick retries; attach reconcile prefers
        # local over any orphaned bucket objects).
        with shard._flush_lock:
            shard.flush()
            with shard._lock:
                v0 = shard.data_version
            if shard.mem_backlog_bytes() != 0:
                return False  # raced a write mid-flush: not idle
            prefix = shard_prefix(db, rp, group_start)
            # clear the prefix FIRST: an earlier aborted/crashed upload
            # (swap lost to a mid-upload write) left orphan objects
            # here, and uploading a since-compacted file set OVER them
            # would make a later hydration re-download retired files —
            # resurrecting deleted rows.  The registry never points
            # here until the swap below succeeds, so the delete races
            # no reader.
            self.obs_store.delete_prefix(prefix)
            # follow a cold-tier symlink: files live at the target;
            # recurse so the seriesidx/ mergeset dir travels too
            real = os.path.realpath(shard.path)
            for dirpath, _dirs, files in os.walk(real):
                for fname in sorted(files):
                    full = os.path.join(dirpath, fname)
                    rel = os.path.relpath(full, real)
                    self.obs_store.put(f"{prefix}/{rel}", full)
        # SWAP PHASE — revalidate + retire under the engine lock (no
        # shard lock held on entry: engine -> shard order preserved)
        with self._lock:
            if self._shards.get(key) is not shard:
                return False  # dropped/replaced mid-upload
            with shard._lock:
                dirty = (shard.data_version != v0
                         or shard.mem_backlog_bytes() != 0)
            if dirty:
                return False  # rows landed mid-upload: bucket copy is
                # stale — keep serving local, next tick re-offloads
            # audited (lockdep): retiring an idle fully-synced shard —
            # the close fsyncs are cheap no-ops here and the engine
            # lock is what makes the registry swap atomic
            with lockdep.allow_blocking("cold-tier retire of idle shard"), \
                    shard._flush_lock, shard._lock:
                shard.wal.close()
                shard.index.close()
                # cold-tier offload retires the local files: release the
                # shard's decoded-column cache entries (colcache)
                shard.drop_cached_columns()
            del self._shards[key]
            # registry FIRST: a crash before the local removal leaves both
            # copies (attach_object_store reconciles, preferring local); the
            # reverse order would strand the data in the bucket unreferenced
            self.obs_shards.add(key)
            self._save_meta()
            _remove_shard_dir(shard.path)  # follows cold-tier symlinks
            return True

    # -- two-phase migration staging (reference engine_ha.go Pre*/Rollback) --

    def _staging_root(self) -> str:
        return os.path.join(self.root, "staging")

    def begin_staging(self, db: str, rp: str, group_start: int,
                      mig_id: str) -> None:
        """PreAssign: open an INVISIBLE staging shard for an inbound
        migration (never in self._shards, so queries cannot see half-
        migrated rows). Idempotent — a retried begin reuses the dir."""
        if not mig_id or "/" in mig_id or mig_id.startswith("."):
            raise WriteError(f"bad migration id {mig_id!r}")
        d = self.databases.get(db)
        if d is None:
            raise DatabaseNotFound(db)
        rp_meta = d.rps.get(rp or d.default_rp)
        if rp_meta is None:
            raise WriteError(f"retention policy not found: {db}.{rp}")
        with self._lock:
            if mig_id in self._staging:
                return
            path = os.path.join(self._staging_root(), mig_id)
            dur = rp_meta.shard_duration_ns
            sh = Shard(path, group_start, group_start + dur,
                       self.sync_wal, tag_arrays=self.tag_arrays)
            self._staging[mig_id] = [db, rp or d.default_rp, group_start, sh,
                                     _time.perf_counter()]

    def write_staging(self, mig_id: str, points: list) -> int:
        with self._lock:
            got = self._staging.get(mig_id)
            if got is None:
                raise WriteError(f"unknown migration {mig_id!r}")
            got[4] = _time.perf_counter()  # idle clock, NOT dir mtime: WAL
            # appends never touch the directory timestamp
            sh = got[3]
            n, ticket = sh.write_points_structured(points,
                                                   defer_commit=True)
        # the sync-WAL fsync waits OUTSIDE the engine lock (the deferred-
        # commit discipline of the main write paths, PR 3; caught here by
        # lockdep) — migration staging ingest must not serialize the
        # whole destination engine behind its disk.  A TTL expiry racing
        # the released lock closes the staging WAL with _synced caught
        # up, so commit() returns instantly rather than livelocking.
        sh.wal.commit(ticket)
        return n

    def commit_staging(self, mig_id: str) -> int:
        """Assign: fold the staged rows into the LIVE shard (LWW-idempotent
        structured writes) and discard the staging area. Returns rows.

        IDEMPOTENT: a durable committed-marker is written after the fold,
        so a re-commit of the same mig_id — the pusher retrying because
        the first commit's ACK was lost in transit — answers ok instead
        of failing the pusher into aborting (and re-streaming) a move
        that already completed.  A retry that re-staged rows first (full
        begin/write/commit replay) re-folds them; the structured write
        path is last-write-wins on (series, timestamp), so the fold can
        never duplicate rows."""
        with self._lock:
            got = self._staging.pop(mig_id, None)
            if got is not None:
                self._folding.add(mig_id)
        if got is None:
            # a retried commit can arrive while the FIRST commit is
            # still folding (its RPC timed out client-side, the work
            # did not): wait out the fold, then answer from the marker
            while True:
                with self._lock:
                    inflight = mig_id in self._folding
                if not inflight:
                    break
                _time.sleep(0.05)
            if os.path.exists(self._committed_marker(mig_id)):
                return 0  # already folded; the previous ack was lost
            raise WriteError(f"unknown migration {mig_id!r}")
        try:
            db, rp, _start, sh, _ts = got
            from opengemini_tpu.storage.shard import iter_structured_batches

            rows = 0
            for batch in iter_structured_batches(sh, 20_000):
                rows += self.write_rows(db, batch, rp=rp)
            # a crash HERE (fold durable via WAL, marker absent) is safe:
            # the pusher's retry re-stages + re-folds, LWW dedups
            _fp("engine-staging-commit-before-marker")
            self._write_committed_marker(mig_id, rows)
            self._discard_staging_dir(sh)
        finally:
            with self._lock:
                self._folding.discard(mig_id)
        return rows

    def _committed_marker(self, mig_id: str) -> str:
        return os.path.join(self._staging_root(), mig_id + ".committed")

    def _write_committed_marker(self, mig_id: str, rows: int) -> None:
        """Durable (fsynced, atomic-rename) record that `mig_id` folded:
        the commit-idempotence token, TTL-expired with the staging dirs."""
        os.makedirs(self._staging_root(), exist_ok=True)
        path = self._committed_marker(mig_id)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            # wall-clock record: operator forensics metadata only (the
            # TTL reaper ages markers by file mtime, never this field)
            f.write(json.dumps(
                {"rows": rows, "ts": _time.time()}))  # ogtlint: disable=OGT040
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def staging_ids(self) -> list[str]:
        """In-flight migration staging ids, snapshotted under the engine
        lock (introspection must not race a concurrent begin/commit)."""
        with self._lock:
            return sorted(self._staging)

    def abort_staging(self, mig_id: str) -> bool:
        """Rollback: drop the staging area; live data was never touched."""
        with self._lock:
            got = self._staging.pop(mig_id, None)
        if got is None:
            return False
        self._discard_staging_dir(got[3])
        return True

    def close_staging(self) -> None:
        with self._lock:
            for entry in self._staging.values():
                entry[3].close()
            self._staging.clear()

    def _discard_staging_dir(self, sh) -> None:
        import shutil

        path = sh.path
        sh.close()
        shutil.rmtree(path, ignore_errors=True)

    def expire_staging(self, ttl_s: float = 900.0) -> int:
        """Janitor half of the rollback story: a pusher that died
        mid-stream leaves a staging dir behind; anything older than the
        TTL is discarded — live data is untouched by construction, so
        expiry IS the rollback (reference: the migrate state machine's
        recovery + Rollback RPCs, engine_ha.go:33-258)."""
        import shutil
        import time as _t

        root = self._staging_root()
        if not os.path.isdir(root):
            return 0
        # two clocks: active registrations idle out on the in-process
        # duration clock; orphan DIRS compare against file mtimes, which
        # only the wall clock can be compared to
        now_pc = _t.perf_counter()
        now = _t.time()  # ogtlint: disable=OGT040
        dropped = 0
        with self._lock:
            # ACTIVE registrations expire on IDLE time (last write seen;
            # an in-progress stream keeps refreshing it, so a long
            # migration never self-destructs mid-flight)
            for name, entry in list(self._staging.items()):
                if now_pc - entry[4] >= ttl_s:
                    self._staging.pop(name, None)
                    self._discard_staging_dir(entry[3])
                    dropped += 1
            # ORPHAN dirs (no in-memory entry — e.g. this node restarted
            # mid-migration) expire by their newest content mtime;
            # committed-markers (commit-idempotence tokens) age out the
            # same way once no pusher can still be retrying that commit
            for name in os.listdir(root):
                if name in self._staging or name in self._folding:
                    # a fold in flight is NOT an orphan: its commit
                    # popped the registration but is still reading the
                    # dir (the lock is not held across the fold)
                    continue
                path = os.path.join(root, name)
                if name.endswith(".committed") and os.path.isfile(path):
                    try:
                        if now - os.path.getmtime(path) >= ttl_s:
                            os.remove(path)
                    except OSError:
                        pass
                    continue
                try:
                    newest = max(
                        (os.path.getmtime(os.path.join(path, f))
                         for f in os.listdir(path)),
                        default=os.path.getmtime(path))
                except OSError:
                    continue
                if now - newest < ttl_s:
                    continue
                shutil.rmtree(path, ignore_errors=True)
                dropped += 1
        return dropped

    def drop_shard(self, db: str, rp: str, group_start: int) -> bool:
        """Remove one local shard group entirely (post-migration cleanup:
        the data now lives on its new rendezvous owners). Unlike the
        cold-tier offload above, nothing is registered — ownership moved
        away (reference: migrate_state_machine.go segment cleanup)."""
        key = (db, rp, group_start)
        with self._lock:
            shard = self._shards.pop(key, None)
            if shard is None:
                return False
            shard.close()
            obs_purge = self._purge_obs(lambda k: k == key)
            self._save_meta()
            _remove_shard_dir(shard.path)
        self._delete_obs_prefixes(obs_purge)
        return True

    def _purge_obs(self, match) -> list[str]:
        """Drop offloaded-group registry entries whose key satisfies
        `match` — DROP DATABASE/RP must not let a recreated namespace
        resurrect old offloaded data.  Caller holds the lock and saves
        meta; the returned bucket prefixes must be fed to
        _delete_obs_prefixes AFTER the lock is released (lockdep: the
        deletes are HTTP round trips).  Registry-first ordering means a
        crash mid-delete leaves unreferenced orphan objects (a leak the
        operator can sweep), never a registry entry pointing at a
        half-deleted group (which would fail every later hydration)."""
        from opengemini_tpu.storage.objstore import shard_prefix

        purged = []
        for key in [k for k in self.obs_shards if match(k)]:
            if self.obs_store is not None:
                purged.append((key, shard_prefix(*key)))
            self.obs_shards.discard(key)
        return purged

    def _delete_obs_prefixes(self, purged: list[tuple]) -> None:
        """Bucket-object deletes for _purge_obs — call with NO engine
        lock held.  Each delete RE-CHECKS the registry first: between
        the purge and this call the namespace may have been recreated
        and a fresh offload registered the SAME deterministic prefix —
        deleting it then would erase the only remaining copy of live
        data (the local files are gone after a successful offload)."""
        for key, prefix in purged:
            with self._lock:
                if key in self.obs_shards or key in self._shards:
                    continue  # the prefix belongs to a live incarnation
            if self.obs_store is not None:
                self.obs_store.delete_prefix(prefix)

    def _download_group(self, db: str, rp: str, group_start: int) -> None:
        """Pull an offloaded group's files into its shard dir. NO engine
        lock held — with a real bucket this is seconds of network I/O and
        must not stall every other query/write.

        Downloads land in a staging dir OUTSIDE data/ and swap in whole:
        a crash or torn download must never leave a partial dir that
        _load_shards would install as a live shard (the reconcile in
        attach_object_store would then delete the bucket copy — data
        loss from a half-hydrated shard)."""
        from opengemini_tpu.storage.objstore import shard_prefix

        prefix = shard_prefix(db, rp, group_start)
        keys = self.obs_store.list(prefix)
        if not keys:
            raise WriteError(
                f"offloaded group {db}/{rp}/{group_start} has no objects "
                "in the bucket")
        import uuid

        # unique per-attempt staging dir: two concurrent hydrations of
        # the same group must not clobber each other's downloads
        tmp = os.path.join(self.root, ".hydrate-tmp",
                           f"{db}_{rp}_{group_start}.{uuid.uuid4().hex[:8]}")
        try:
            for key in keys:
                rel = key[len(prefix) + 1 :]  # may be nested (seriesidx/)
                target = os.path.join(tmp, rel)
                os.makedirs(os.path.dirname(target), exist_ok=True)
                self.obs_store.get(key, target)
            dest = self._shard_dir(db, rp, group_start)
            # swap under the engine lock: the loser of a concurrent
            # hydration discards its copy instead of replacing a dir the
            # winner may already have OPEN as a live shard
            with self._lock:
                if (db, rp, group_start) in self._shards:
                    shutil.rmtree(tmp, ignore_errors=True)
                    return
                shutil.rmtree(dest, ignore_errors=True)
                os.makedirs(os.path.dirname(dest), exist_ok=True)
                os.replace(tmp, dest)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    def _install_hydrated(self, db: str, rp: str, group_start: int,
                          save: bool = True) -> "Shard":
        """Open a downloaded group and register it (caller holds the
        lock). Idempotent: an already-live shard is returned untouched —
        never clobbered. The store copy is kept for future re-offload."""
        key = (db, rp, group_start)
        existing = self._shards.get(key)
        if existing is not None:
            self.obs_shards.discard(key)
            return existing
        d = self.databases[db]
        dur = d.rps[rp].shard_duration_ns
        shard = Shard(self._shard_dir(db, rp, group_start), group_start,
                      group_start + dur, self.sync_wal,
                      tag_arrays=self.tag_arrays)
        self._shards[key] = shard
        self.obs_shards.discard(key)
        if save:
            self._save_meta()
        return shard

    def _hydrate_shard(self, db: str, rp: str, group_start: int) -> "Shard | None":
        """Download + install in one step (write path; caller holds the
        engine lock — rare enough that blocking is acceptable there)."""
        if self.obs_store is None:
            return None
        if (db, rp, group_start) in self._shards:
            return self._install_hydrated(db, rp, group_start)
        # audited (lockdep): a backfill write into an aged-out cold
        # group downloads it under the engine lock by documented design
        # — rare, and routing is mid-flight; the QUERY path hydrates
        # outside the lock (shards_for_range)
        with lockdep.allow_blocking("write-path cold hydration"):
            self._download_group(db, rp, group_start)
        return self._install_hydrated(db, rp, group_start)

    def shards_for_range(self, db: str, rp: str | None, tmin: int, tmax: int) -> list[Shard]:
        """Shards overlapping [tmin, tmax) — the shard-mapping step
        (reference coordinator/shard_mapper.go:61 MapShards). Offloaded
        (object-store) groups in range hydrate back first."""
        d = self.databases.get(db)
        if d is None:
            return []
        rp = rp or d.default_rp
        if self.obs_shards and self.obs_store is not None:
            with self._lock:
                rp_meta = d.rps.get(rp)
                dur = rp_meta.shard_duration_ns if rp_meta else 0
                todo = [
                    k for k in sorted(self.obs_shards)
                    if k[0] == db and k[1] == rp and dur
                    and k[2] + dur > tmin and k[2] < tmax
                ]
            for odb, orp, start in todo:
                try:
                    # download OUTSIDE the lock (bucket I/O must not stall
                    # unrelated queries/writes), install under it
                    if (odb, orp, start) not in self._shards:
                        self._download_group(odb, orp, start)
                    with self._lock:
                        self._install_hydrated(odb, orp, start, save=False)
                except Exception as e:  # noqa: BLE001
                    import logging

                    logging.getLogger("opengemini_tpu.engine").exception(
                        "hydration of %s/%s/%d failed", odb, orp, start
                    )
                    # fail LOUDLY: silently answering without the
                    # offloaded shard would return incomplete results
                    raise WriteError(
                        f"shard {odb}/{orp}/{start} is in the object "
                        f"store and could not be hydrated: {e}") from e
            if todo:
                with self._lock:
                    self._save_meta()
        out = []
        for (sdb, srp, _start), shard in sorted(self._shards.items()):
            if sdb == db and srp == rp and shard.tmin < tmax and shard.tmax > tmin:
                out.append(shard)
        return out

    def all_shards(self) -> list[Shard]:
        return list(self._shards.values())

    def shards_of_db(self, db: str) -> list[Shard]:
        """Every shard of a database across ALL retention policies."""
        return [sh for (sdb, _rp, _s), sh in sorted(self._shards.items()) if sdb == db]

    # -- write path ---------------------------------------------------------

    def write_lines(
        self,
        db: str,
        lines: str | bytes,
        precision: str = "ns",
        rp: str | None = None,
        now_ns: int | None = None,
    ) -> int:
        """Parse + route + apply a line-protocol batch
        (reference write path, SURVEY.md §3.1). Returns points written."""
        if self.write_disabled:
            raise WriteError("writes are disabled (syscontrol)")
        d = self.databases.get(db)
        if d is None:
            raise DatabaseNotFound(db)
        if d.dropped_msts:
            # a marked measurement being rewritten must not resurface its
            # old rows: purge before accepting the batch
            self.purge_dropped_measurements(db)
        rp = rp or d.default_rp
        if now_ns is None:
            now_ns = _time.time_ns()
        raw = lines.encode("utf-8") if isinstance(lines, str) else lines

        # fast path: native columnar parse -> slab writes (reference:
        # pooled VM protoparser feeding the record writer). Falls back to
        # the exact Python parser when the batch uses escapes or the
        # library is absent.
        from opengemini_tpu.ingest import native_lp

        batch = None
        if not (self.tag_arrays and b"=[" in raw):
            # tag-array batches take the exact Python parser (expansion)
            # large bodies fan the native parse out across cores — the C
            # call releases the GIL (reference:
            # httpd/handler.go:1633 influx.ScheduleUnmarshalWork pool)
            n = self._write_segmented(db, rp, raw, precision, now_ns)
            if n is _NEEDS_PYTHON_PARSER:
                pass  # segments already proved native can't parse this
            elif n is not None:
                return n
            else:
                with tracing.span("lp_parse", bytes=len(raw)):
                    batch = native_lp.parse_columnar(raw, precision, now_ns)
        if batch is not None:
            if len(batch) == 0:
                return 0
            STATS.incr("write", "points", len(batch))
            rtok = utok = None
            with tracing.span("write_hooks"):
                if self.rollup_mgr is not None:
                    # PRE-apply: a late write's dirty mark is durable
                    # before the rows are (storage/rollup.py watermark
                    # contract); write_done releases the in-flight fold
                    # floor
                    rtok = self.rollup_mgr.note_write_columnar(
                        db, rp, batch)
                if self.rules_hook is not None:
                    utok = self.rules_hook.note_write_columnar(
                        db, rp, batch)
            try:
                tickets: list = []
                touched: list = []
                with self._write_lock():
                    n = self._write_columnar_locked(
                        db, rp, batch, raw, precision, now_ns, tickets,
                        touched)
                self._commit_wal_tickets(tickets)
                self._flush_over_threshold(touched)
                self._notify_write(db, rp, WrittenPoints((batch,)))
                return n
            finally:
                if rtok is not None:
                    self.rollup_mgr.write_done(rtok)
                if utok is not None:
                    self.rules_hook.write_done(utok)

        with tracing.span("lp_parse", bytes=len(raw)):
            points = lp.parse_lines(lines, precision, now_ns,
                                    expand_tag_arrays=self.tag_arrays)
        if not points:
            return 0
        STATS.incr("write", "points", len(points))
        rtok = utok = None
        with tracing.span("write_hooks"):
            if self.rollup_mgr is not None:
                rtok = self.rollup_mgr.note_write_points(db, rp, points)
            if self.rules_hook is not None:
                utok = self.rules_hook.note_write_points(db, rp, points)
        try:
            tickets: list = []
            with self._write_lock():
                # group points by target shard (time routing)
                by_shard: dict[int, list] = {}
                shards: dict[int, Shard] = {}
                for p in points:
                    shard = self._get_or_create_shard(db, rp, p[2])
                    key = id(shard)
                    shards[key] = shard
                    by_shard.setdefault(key, []).append(p)
                n = 0
                for key, pts in by_shard.items():
                    got, t = shards[key].write_points(
                        pts, raw, precision, now_ns, defer_commit=True)
                    n += got
                    tickets.append((shards[key], t))
            self._commit_wal_tickets(tickets)  # fsyncs coalesce off-lock
            self._flush_over_threshold(shards.values())
            self._notify_write(db, rp, WrittenPoints(points=points))
            return n
        finally:
            if rtok is not None:
                self.rollup_mgr.write_done(rtok)
            if utok is not None:
                self.rules_hook.write_done(utok)

    def _write_segmented(self, db: str, rp: str, raw: bytes,
                         precision: str, now_ns: int):
        """Multi-core ingest: split a large body at line boundaries, parse
        the segments concurrently (the native parser releases the GIL),
        then apply in order. Returns None when the body is small or the
        pool is unavailable (caller takes the single-batch path), or the
        _NEEDS_PYTHON_PARSER sentinel when a segment proved the body
        needs the exact Python parser. Reference:
        lib/util/lifted/influx/httpd/handler.go:1633
        (influx.ScheduleUnmarshalWork worker pool)."""
        from opengemini_tpu.ingest import native_lp
        from opengemini_tpu.ingest.line_protocol import ParseError

        pool = _ingest_pool()
        if pool is None or len(raw) < 2 * _INGEST_SEGMENT_BYTES:
            return None
        if native_lp.load() is None:
            return None
        errs: list = []

        def parse_one(idx_seg):
            idx, seg = idx_seg
            try:
                return native_lp.parse_columnar(seg, precision, now_ns)
            except ParseError as e:
                errs.append((idx, e))
                return None
        with tracing.span("lp_parse", bytes=len(raw)) as sp:
            segs = _split_lp_segments(raw, _INGEST_WORKERS)
            if len(segs) < 2:
                return None
            sp.add_field("segments", len(segs))
            parsed = list(pool.map(parse_one, enumerate(segs)))
        if errs:
            # report the FIRST bad line of the body, not whichever worker
            # thread finished first
            idx, e = min(errs)
            off = sum(s.count(b"\n") for s in segs[:idx])
            raise ParseError(off + e.lineno, e.msg)
        if any(b is None for b in parsed):
            return _NEEDS_PYTHON_PARSER  # escapes etc.
        # cross-segment field-type check BEFORE applying anything: the
        # single-batch path rejects an internally-conflicting body with
        # nothing persisted; segments must not differ
        with tracing.span("type_check"):
            body_types: dict[tuple[str, str], object] = {}
            for batch in parsed:
                for mst_id, name, ftype, _values, valid in batch.cols:
                    if not valid.any():
                        continue
                    key = (batch.measurements[mst_id], name)
                    have = body_types.get(key)
                    if have is None:
                        body_types[key] = ftype
                    elif have != ftype:
                        raise FieldTypeConflict(name, have, ftype)
        total = 0
        rtoks = []
        utoks = []
        try:
            with tracing.span("write_hooks"):
                if self.rollup_mgr is not None:
                    # inside the try: a note hook failing for batch k
                    # must still release batches <k's in-flight floors
                    # via the finally, or the watermark stalls forever
                    for batch in parsed:
                        if len(batch):
                            t = self.rollup_mgr.note_write_columnar(
                                db, rp, batch)
                            if t is not None:
                                rtoks.append(t)
                if self.rules_hook is not None:
                    for batch in parsed:
                        if len(batch):
                            t = self.rules_hook.note_write_columnar(
                                db, rp, batch)
                            if t is not None:
                                utoks.append(t)
            with self._write_lock():
                # ONE lock acquisition for the whole body, with every
                # segment pre-validated against the LIVE shard schemas
                # before the first applies: the old per-segment lock
                # dance let a mid-batch schema conflict (or a racing
                # writer) leave a partial write the single-batch path can
                # never produce.  Routing runs ONCE per segment and is
                # reused for the apply.
                routed = []
                with tracing.span("index_route"):
                    for seg, batch in zip(segs, parsed):
                        if len(batch) == 0:
                            continue
                        route = list(
                            self._route_columnar_locked(db, rp, batch))
                        for shard, rows in route:
                            shard._check_columnar_types(batch, rows)
                        routed.append((seg, batch, route))
                tickets: list = []
                touched: list = []
                for seg, batch, route in routed:
                    STATS.incr("write", "points", len(batch))
                    for shard, rows in route:
                        got, t = shard.write_columnar(
                            batch, rows, seg, precision, now_ns,
                            defer_commit=True)
                        total += got
                        tickets.append((shard, t))
                        touched.append(shard)
            self._commit_wal_tickets(tickets)  # fsyncs coalesce off-lock
            self._flush_over_threshold(touched)
            if total:
                # observers see the body ONCE, post-commit, like
                # write_lines: one view over every segment, in order
                self._notify_write(db, rp, WrittenPoints(parsed))
            return total
        finally:
            for t in rtoks:
                self.rollup_mgr.write_done(t)
            for t in utoks:
                self.rules_hook.write_done(t)

    @contextlib.contextmanager
    def _write_lock(self):
        """The engine lock for a write's apply, with the wait for it a
        stage of its own: writers queue here behind each other and
        behind everything else that takes the engine lock."""
        with tracing.span("write_lock_wait"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _route_columnar_locked(self, db: str, rp: str, batch):
        """Yield (shard, rows) for a ColumnarBatch — ONE routing
        implementation (vectorized Go-Truncate alignment) shared by
        pre-validation and apply, so a segmented body is checked against
        exactly the shards it will write to. Caller holds the engine
        lock. Target shards are created here if missing (a body rejected
        by pre-validation can leave empty shards behind — the same
        behavior as the point write path, which also creates shards
        before type checks)."""
        import numpy as np

        d = self.databases.get(db)
        if d is None:
            # a concurrent DROP DATABASE can land between segments of a
            # segmented body (the lock is per body, drops take it too)
            raise DatabaseNotFound(db)
        rp_meta = d.rps.get(rp)
        if rp_meta is None:
            raise WriteError(f"retention policy not found: {db}.{rp}")
        dur = rp_meta.shard_duration_ns
        phase = _go_phase_ns(dur)
        groups = (batch.ts - phase) // dur * dur + phase
        uniq = np.unique(groups)
        for g in uniq:
            shard = self._get_or_create_shard(db, rp, int(g))
            rows = None if len(uniq) == 1 else np.flatnonzero(groups == g)
            yield shard, rows

    @staticmethod
    def _commit_wal_tickets(tickets) -> None:
        """Finish deferred sync-WAL commits AFTER the engine lock drops:
        concurrent request threads pile onto the WAL's group commit and
        share fsyncs instead of serializing them under the engine lock
        (no-ops instantly when sync is off or a flush already made the
        entries durable)."""
        # lock handoff: engine lock dropped, rows applied, ack pending on
        # the group-commit fsync — a kill here must never lose a row that
        # a caller was told about (the ack happens after this returns)
        _fp("engine-before-wal-commit")
        with tracing.span("wal_commit"):
            for shard, ticket in tickets:
                shard.wal.commit(ticket)

    def _flush_over_threshold(self, shards) -> None:
        """Threshold flushes AFTER the engine lock drops: the off-lock
        flush (snapshot-and-swap, storage/shard.py) would otherwise run
        its whole encode+write+fsync while holding the engine lock and
        stall every other writer for the flush duration.  flush_if_over
        re-checks the size under the shard's flush lock (and skips when
        a flush is already in flight), so concurrent writers that all
        saw the same over-threshold memtable trigger ONE flush.  A shard
        dropped/offloaded between the lock release and here fails its
        flush benignly (drop discarded the data on purpose) — re-raise
        only if the shard is still registered."""
        _fp("engine-before-threshold-flush")  # engine lock released
        with tracing.span("flush_inline"):
            self._flush_tolerating_drop(
                shards,
                lambda sh: sh.flush_if_over(self.flush_threshold_bytes))

    def _flush_tolerating_drop(self, shards, flush_fn) -> None:
        """Flush each distinct shard OFF the engine lock, swallowing a
        failure ONLY when a concurrent DROP removed the shard mid-flush
        (its data is gone by design) — a live shard's flush failure
        re-raises.  Shared by the threshold path and flush_all."""
        seen: set[int] = set()
        for shard in shards:
            if id(shard) in seen:
                continue
            seen.add(id(shard))
            try:
                flush_fn(shard)
            except Exception:  # noqa: BLE001 — see docstring
                with self._lock:
                    alive = any(s is shard for s in self._shards.values())
                if alive:
                    raise

    def _write_columnar_locked(self, db: str, rp: str, batch,
                               raw: bytes, precision: str, now_ns: int,
                               tickets: list, touched: list) -> int:
        """Route a ColumnarBatch to its time shards (vectorized: one
        floor-divide over all timestamps) and slab-write each. Caller
        holds the engine lock; deferred WAL commits append to `tickets`
        and written shards to `touched` for the caller to finish
        (commit + threshold flush) off-lock."""
        n = 0
        with tracing.span("index_route"):
            route = list(self._route_columnar_locked(db, rp, batch))
        for shard, rows in route:
            got, t = shard.write_columnar(
                batch, rows, raw, precision, now_ns, defer_commit=True)
            n += got
            tickets.append((shard, t))
            touched.append(shard)
        return n

    # -- continuous queries / downsample ----------------------------------

    def create_continuous_query(self, db: str, cq: "ContinuousQuery") -> None:
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            d.continuous_queries[cq.name] = cq
            self._save_meta()

    def drop_continuous_query(self, db: str, name: str) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d and name in d.continuous_queries:
                del d.continuous_queries[name]
                self._save_meta()

    def save_cq_state(self) -> None:
        with self._lock:
            self._save_meta()

    def create_subscription(self, db: str, sub) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            d.subscriptions[sub.name] = sub
            self._save_meta()

    def drop_subscription(self, db: str, name: str) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d and name in d.subscriptions:
                del d.subscriptions[name]
                self._save_meta()

    def create_stream(self, db: str, task: "StreamTask") -> None:
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            d.streams[task.name] = task
            self._save_meta()

    def drop_stream(self, db: str, name: str) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d and name in d.streams:
                del d.streams[name]
                self._save_meta()

    # -- materialized rollups (storage/rollup.py) --------------------------

    def _maybe_init_rollups(self) -> None:
        from opengemini_tpu.storage import rollup as _rollup

        if (self.rollup_mgr is None and _rollup.enabled_by_env()
                and any(d.rollups for d in self.databases.values())):
            self.rollup_mgr = _rollup.RollupManager(self)

    def create_rollup(self, db: str, spec) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            src_rp = spec.rp or d.default_rp
            if src_rp not in d.rps:
                raise WriteError(f"retention policy not found: {db}.{src_rp}")
            _check_namespace_name(spec.name, "rollup")
            if spec.name == spec.measurement:
                # the spec name doubles as the target measurement AND as
                # the dropped-measurement marker on drop_rollup — a name
                # collision with the source would hide the source rows
                raise WriteError(
                    "rollup name must differ from its source measurement")
            if spec.name in d.rollups:
                # silently replacing would leave the old grid's rows and
                # watermark behind — a redeclared interval would then
                # double-count in the splice.  Drop first (the re-fold
                # bootstrap zero-fills the old grid's cells).
                raise WriteError(
                    f"rollup already exists: {db}.{spec.name} "
                    "(drop it first)")
            d.rollups[spec.name] = spec
            self._save_meta()
        self._maybe_init_rollups()

    def drop_rollup(self, db: str, name: str) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d and name in d.rollups:
                spec = d.rollups.pop(name)
                # the persisted cells drop with the spec, scoped to the
                # _rollup RP (orphaned rows would leak disk and answer
                # stale aggregates; a db-wide dropped_msts mark could
                # nuke an unrelated raw measurement sharing the name)
                self._purge_rollup_target(db, spec.target)
                self._save_meta()
        if self.rollup_mgr is not None:
            self.rollup_mgr.drop_state(db, name)
        else:
            # OGT_ROLLUP=0: still remove the state file, or a later
            # re-declare under a re-enabled env resurrects a stale
            # watermark over a purged target
            try:
                os.remove(os.path.join(self.root, "rollup", db,
                                       f"{name}.json"))
            except OSError:
                pass

    def _purge_rollup_target(self, db: str, target: str) -> None:
        """Delete a rollup target's rows from the _rollup RP's shards
        only (caller holds the engine lock)."""
        from opengemini_tpu.storage.rollup import ROLLUP_RP

        for (sdb, rp, _g), sh in list(self._shards.items()):
            if sdb == db and rp == ROLLUP_RP:
                sh.delete_data(target)

    def ensure_rollup_rp(self, db: str) -> None:
        """The system RP rollup rows persist under — infinite retention
        (rollups deliberately outlive their raw source data)."""
        from opengemini_tpu.storage.rollup import ROLLUP_RP

        with self._lock:
            d = self.databases.get(db)
            if d is not None and ROLLUP_RP not in d.rps:
                d.rps[ROLLUP_RP] = RetentionPolicy(
                    ROLLUP_RP, 0, DEFAULT_SHARD_DURATION)
                self._save_meta()

    def add_write_observer(self, fn) -> None:
        """fn(db, rp, points) is called once after every committed write,
        on the writer's thread — the stream engine's ingest hook
        (reference: stream-aware PointsWriter, coordinator/
        points_writer.go stream rows).  `points` is a WrittenPoints view:
        `len()` is free, and the point tuples are built only when an
        observer reads them, once for all observers of that write.  An
        observer with nothing to do for `db` returns before reading."""
        self._write_observers.append(fn)

    def _notify_write(self, db: str, rp: str | None,
                      points: WrittenPoints) -> None:
        """The span covers the calls and whatever build they cause."""
        if not self._write_observers:
            return
        with tracing.span("write_observers"):
            STATS.incr("write", "observer_rows_offered", len(points))
            for fn in self._write_observers:
                try:
                    fn(db, rp, points)
                except Exception:  # noqa: BLE001 — observers never break ingest
                    import logging

                    logging.getLogger("opengemini_tpu.engine").exception(
                        "write observer failed"
                    )

    def add_downsample_policy(self, db: str, rp: str, policy: "DownsamplePolicy") -> None:
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            d.downsample.setdefault(rp, []).append(policy)
            self._save_meta()

    def set_downsample_policies(self, db: str, rp: str,
                                policies: list["DownsamplePolicy"],
                                ttl_ns: int = 0) -> None:
        """Replace the rp's whole policy set (replace semantics keep the
        raft-listener replay idempotent; already-exists is the DDL
        layer's check, not the engine's). A nonzero ttl_ns also becomes
        the rp's retention duration (reference: CREATE DOWNSAMPLE's
        Duration is assigned to the rp, data.go SetDownSamplePolicy)."""
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                raise DatabaseNotFound(db)
            if rp not in d.rps:
                raise WriteError(f"retention policy not found: {db}.{rp}")
            d.downsample[rp] = list(policies)
            if ttl_ns:
                d.rps[rp].duration_ns = ttl_ns
            self._save_meta()

    def drop_downsample_policies(self, db: str, rp: str | None = None) -> None:
        with self._lock:
            d = self.databases.get(db)
            if d is None:
                return
            if rp is None:
                d.downsample.clear()
            else:
                d.downsample.pop(rp, None)
            self._save_meta()

    def shards_due_downsample(self, now_ns: int | None = None):
        """[(shard, policy)] whose whole range has aged past a policy and
        whose resolution is still finer (tracked via a marker file)."""
        if now_ns is None:
            now_ns = _time.time_ns()
        due = []
        with self._lock:
            for (db, rp, _start), shard in sorted(self._shards.items()):
                d = self.databases.get(db)
                pols = d.downsample.get(rp, []) if d else []
                best = None
                for p in pols:
                    if shard.tmax <= now_ns - p.age_ns:
                        if best is None or p.every_ns > best.every_ns:
                            best = p
                if best is not None and _downsample_level(shard.path) < best.every_ns:
                    due.append((shard, best))
        return due

    def run_downsample(self, now_ns: int | None = None) -> int:
        """Execute all due downsample rewrites; returns shards rewritten.
        Per-shard failures (e.g. a concurrent retention drop removing the
        directory) are logged and skipped, never aborting the sweep."""
        import logging

        n = 0
        for shard, policy in self.shards_due_downsample(now_ns):
            try:
                shard.rewrite_downsampled(policy.every_ns, policy.field_aggs)
                _set_downsample_level(shard.path, policy.every_ns)
                n += 1
            except Exception:  # noqa: BLE001
                logging.getLogger("opengemini_tpu.engine").exception(
                    "downsample of shard %s failed", shard.path
                )
        return n

    def write_rows(self, db: str, points: list, rp: str | None = None) -> int:
        """Structured write path: points are
        (measurement, tags tuple, t_ns, {field: (FieldType, value)}) —
        used by SELECT INTO and internal services; values never round-trip
        through line-protocol text (reference RecordWriter analogue,
        coordinator/record_writer.go)."""
        if self.write_disabled:
            raise WriteError("writes are disabled (syscontrol)")
        d = self.databases.get(db)
        if d is None:
            raise DatabaseNotFound(db)
        if d.dropped_msts:
            self.purge_dropped_measurements(db)
        rp = rp or d.default_rp
        rtok = None
        if self.rollup_mgr is not None:
            rtok = self.rollup_mgr.note_write_points(db, rp, points)
        utok = None
        if self.rules_hook is not None:
            utok = self.rules_hook.note_write_points(db, rp, points)
        try:
            tickets: list = []
            with self._lock:
                by_shard: dict[int, list] = {}
                shards: dict[int, Shard] = {}
                for p in points:
                    shard = self._get_or_create_shard(db, rp, p[2])
                    key = id(shard)
                    shards[key] = shard
                    by_shard.setdefault(key, []).append(p)
                n = 0
                for key, pts in by_shard.items():
                    got, t = shards[key].write_points_structured(
                        pts, defer_commit=True)
                    n += got
                    tickets.append((shards[key], t))
            self._commit_wal_tickets(tickets)  # fsyncs coalesce off-lock
            self._flush_over_threshold(shards.values())
            self._notify_write(db, rp, WrittenPoints(points=points))
            return n
        finally:
            if rtok is not None:
                self.rollup_mgr.write_done(rtok)
            if utok is not None:
                self.rules_hook.write_done(utok)

    def flush_all(self) -> None:
        # snapshot under the lock, flush OUTSIDE it: shard.flush encodes
        # + fsyncs, and holding the engine lock across that stalls every
        # write path behind one shard's disk — the PR 3 threshold-flush
        # stall class, caught on this explicit path by lockdep's
        # blocking-under-hot-lock check
        with self._lock:
            shards = list(self._shards.values())
        self._flush_tolerating_drop(shards, lambda sh: sh.flush())

    # -- durability ledger (PR 4) ------------------------------------------

    def durability_snapshot(self) -> dict:
        """Aggregate + per-shard acked-vs-durable ledgers (see
        storage/shard.DurabilityLedger).  Per shard, `missing` > 0 means
        acked rows are not accounted for in mem or published files —
        silent loss; < 0 means a snapshot published twice.  The TOTAL
        sums absolute values: a loss on one shard must never cancel a
        double-publish on another in the gauge operators alert on."""
        with self._lock:
            shards = list(self._shards.items())
        agg = {"acked": 0, "replayed": 0, "published": 0, "tsf_rows": 0,
               "mem_rows": 0, "missing": 0, "dirty_shards": 0,
               "shards": len(shards)}
        per_shard = {}
        for (db, rp, start), sh in shards:
            snap = sh.ledger_snapshot()
            per_shard[f"{db}|{rp}|{start}"] = snap
            for k in ("acked", "replayed", "published", "tsf_rows",
                      "mem_rows"):
                agg[k] += snap[k]
            agg["missing"] += abs(snap["missing"])
            agg["dirty_shards"] += 1 if snap["dirty"] else 0
        return {"totals": agg, "shards": per_shard}

    def durability_check(self, snapshot: dict | None = None) -> list[dict]:
        """Online invariant checker: every clean shard's ledger must
        conserve rows (acked + replayed == published + mem).  Returns
        violations (empty = healthy); the torture harness and
        /debug/ctrl?mod=durability call this live.  Pass a
        durability_snapshot() to check exactly the state being reported
        (no second pass over the shard locks)."""
        snap = snapshot if snapshot is not None else self.durability_snapshot()
        return [
            {"shard": key, **s}
            for key, s in snap["shards"].items()
            if not s["dirty"] and s["missing"] != 0
        ]

    def _durability_gauges(self) -> dict:
        return self.durability_snapshot()["totals"]

    # -- quarantine (media-fault containment) ------------------------------

    def quarantine_snapshot(self) -> dict:
        """Every quarantined file across shards: {"files": [{shard,
        path, why}], "total": n} — the /debug/ctrl?mod=scrub view."""
        with self._lock:
            shards = list(self._shards.items())
        files = []
        for (db, rp, start), sh in shards:
            for path, why in sorted(sh.quarantined().items()):
                files.append({"shard": f"{db}|{rp}|{start}",
                              "path": path, "why": why})
        return {"files": files, "total": len(files)}

    def _quarantine_gauges(self) -> dict:
        with self._lock:
            shards = list(self._shards.values())
        n = sum(len(sh.quarantined()) for sh in shards)
        return {"files_current": n} if n else {}

    def purge_quarantined(self) -> int:
        """Delete quarantined files + markers from disk across all
        shards (operator action after repair / accepted loss)."""
        with self._lock:
            shards = list(self._shards.values())
        return sum(sh.purge_quarantined() for sh in shards)

    def mem_backlog_bytes(self) -> int:
        """Un-flushed resident bytes (live + frozen memtables + live WAL
        logs) across every shard — the write-backpressure input of the
        resource governor's ledger (utils/governor.py)."""
        with self._lock:
            shards = list(self._shards.values())
        return sum(sh.mem_backlog_bytes() for sh in shards)

    def drop_expired_shards(self, now_ns: int | None = None) -> list[tuple[str, str, int]]:
        """Retention enforcement (reference services/retention/service.go:81):
        drop shards whose whole range is past the RP duration."""
        import shutil

        if now_ns is None:
            now_ns = _time.time_ns()
        dropped = []
        with self._lock:
            for key in list(self._shards):
                db, rp, start = key
                d = self.databases.get(db)
                rp_meta = d.rps.get(rp) if d else None
                if rp_meta is None or rp_meta.duration_ns == 0:
                    continue
                shard = self._shards[key]
                if shard.tmax <= now_ns - rp_meta.duration_ns:
                    shard.close()
                    _remove_shard_dir(shard.path)
                    del self._shards[key]
                    dropped.append(key)
            # offloaded groups age out too (delete the store copy) —
            # only COLLECTED here; the bucket deletes are HTTP calls and
            # run outside the engine lock below (lockdep: retention must
            # not stall every write/query behind object-store round
            # trips)
            purged = []
            for key in sorted(self.obs_shards):
                db, rp, start = key
                d = self.databases.get(db)
                rp_meta = d.rps.get(rp) if d else None
                if rp_meta is None or rp_meta.duration_ns == 0:
                    continue
                if start + rp_meta.shard_duration_ns <= now_ns - rp_meta.duration_ns:
                    from opengemini_tpu.storage.objstore import shard_prefix

                    self.obs_shards.discard(key)
                    dropped.append(key)
                    if self.obs_store is not None:
                        purged.append((key, shard_prefix(*key)))
            if dropped:
                self._save_meta()
        # registry-first, deletes off-lock with re-check — same ordering
        # and race protection as _purge_obs/_delete_obs_prefixes
        self._delete_obs_prefixes(purged)
        return dropped

    def close(self) -> None:
        STATS.unregister_provider("durability", self._durability_provider)
        STATS.unregister_provider("quarantine", self._quarantine_provider)
        if self.rollup_mgr is not None:
            self.rollup_mgr.close()
        from opengemini_tpu.utils.governor import GOVERNOR as _GOVERNOR

        _GOVERNOR.unregister_component("memtable", self._governor_provider)
        # the HTTP layer may have pointed the process-global querytracker
        # at this engine's ledger: a closed engine must neither serve
        # frozen durability state as live nor stay pinned in memory
        from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER

        _TRACKER.detach_durability_provider(self.durability_snapshot)
        with self._lock:
            # audited (lockdep): shutdown fsyncs (each shard's final WAL
            # flush) run under the engine lock deliberately — the lock
            # is what makes close atomic against in-flight writes, and
            # nothing productive contends with a closing engine
            with lockdep.allow_blocking("engine.close shutdown fsyncs"):
                for shard in self._shards.values():
                    shard.close()
                self._shards.clear()
                for entry in self._staging.values():
                    entry[3].close()
                self._staging.clear()


def _remove_shard_dir(path: str) -> None:
    """Delete a shard directory, following a cold-tier symlink: the cold
    copy is removed too, then the link — expired tiered data must not leak
    or resurrect on restart."""
    import shutil as _shutil

    if os.path.islink(path):
        target = os.path.realpath(path)
        _shutil.rmtree(target, ignore_errors=True)
        try:
            os.unlink(path)
        except OSError:
            pass
    else:
        _shutil.rmtree(path, ignore_errors=True)


def _downsample_level(shard_path: str) -> int:
    """Current resolution of a shard (0 = raw), persisted as a marker file
    (the reference tracks per-shard downsample levels in meta,
    engine_downsample.go:23 GetShardDownSampleLevel)."""
    p = os.path.join(shard_path, "downsample.level")
    try:
        with open(p, encoding="utf-8") as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return 0


def _set_downsample_level(shard_path: str, every_ns: int) -> None:
    p = os.path.join(shard_path, "downsample.level")
    with open(p, "w", encoding="utf-8") as f:
        f.write(str(every_ns))


def _auto_shard_duration(duration_ns: int) -> int:
    """Influx defaults: RP < 2d -> 1h groups, < 6mo -> 1d, else 7d."""
    day = 24 * 3600 * NS
    if duration_ns == 0:
        return 7 * day
    if duration_ns < 2 * day:
        return 3600 * NS
    if duration_ns < 180 * day:
        return day
    return 7 * day

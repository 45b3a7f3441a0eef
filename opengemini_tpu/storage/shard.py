"""Shard: a time-ranged slice of one database/RP — WAL + memtable +
immutable TSF files + series index.

Reference: engine/shard.go:117 (WriteRows :512, Snapshot/flush :731,
Compact :688, commitSnapshot :1008) and the per-shard WAL replay
(engine/wal.go:390).
"""

from __future__ import annotations

import contextlib
import os
import itertools
import threading
from opengemini_tpu.utils import lockdep, tracing

import numpy as np

from opengemini_tpu.ingest import line_protocol as lp
from opengemini_tpu.index.mergeset import open_series_index
from opengemini_tpu.record import (
    Column, FieldTypeConflict, Record, merge_bulk_parts,
    merge_sorted_records, _zeroed as _rec_zeroed,
)
from opengemini_tpu.storage import colcache, scanpool, tsf
from opengemini_tpu.storage.memtable import MemTable, _series_slice
from opengemini_tpu.storage.tsf import CorruptFile, TSFReader, TSFWriter
from opengemini_tpu.storage.wal import WAL, WALCorruption
from opengemini_tpu.utils.failpoint import inject as _fp
from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER
from opengemini_tpu.utils.stats import GLOBAL as _STATS
from opengemini_tpu.utils.stats import histogram as _stats_histogram

# flush wall-time distribution (ogt_flush_seconds at /metrics) — the
# counters above it carry totals; the histogram carries the p99 an
# operator actually pages on
_H_FLUSH = _stats_histogram("flush_seconds")


def _pack_entries(buffer: list) -> tuple[np.ndarray, Record]:
    """[(sid, rec)] (sid-ascending, per-rec time-sorted) -> one PK-sorted
    packed block: sid column + union-schema field columns (absent fields
    pad invalid)."""
    total = sum(len(rec) for _sid, rec in buffer)
    sids = np.concatenate(
        [np.full(len(rec), sid, np.int64) for sid, rec in buffer])
    times = np.concatenate([rec.times for _sid, rec in buffer])
    ftypes: dict[str, object] = {}
    for _sid, rec in buffer:
        for name, col in rec.columns.items():
            ftypes.setdefault(name, col.ftype)
    cols = {}
    for name, ftype in ftypes.items():
        # zero-init (see record.merge_bulk_parts): garbage in invalid slots
        # would persist into packed chunks and break digest equality
        values = _rec_zeroed(ftype, total)
        valid = np.zeros(total, dtype=np.bool_)
        at = 0
        for _sid, rec in buffer:
            n = len(rec)
            col = rec.columns.get(name)
            if col is not None:
                values[at:at + n] = col.values
                valid[at:at + n] = col.valid
            at += n
        cols[name] = Column(ftype, values, valid)
    return sids, Record(times, cols)


# bulk (sid, time) merge lives in record.py; shard call sites keep the
# old private name
_merge_bulk_parts = merge_bulk_parts


def _merge_counted(parts, lo_t: int, hi_t: int):
    """A bulk read's merge, saying what it did: `scan/merges`, the
    branch it took (`merges_inorder`, `merges_single_sid`,
    `merges_interleaved`, `merges_sorted`) and `rows_merged`, the rows
    that entered a concatenation or sort once every part was trimmed to
    the range."""
    told: dict = {}
    out = merge_bulk_parts(parts, lo_t, hi_t, told)
    _STATS.add("scan", (("merges", 1), ("merges_" + told["branch"], 1),
                        ("rows_merged", told["rows"])))
    return out


def _sid_entries(rec: Record, uniq, starts, ends):
    """(sid, per-series Record) views over one (sid, time)-sorted bulk
    table — the flush path's bridge from memtable tables to chunk writes.
    Column slicing + all-invalid drop shares memtable._series_slice so the
    per-series shape (and content_digest) can never diverge by path."""
    for sid, lo, hi in zip(uniq, starts, ends):
        yield int(sid), _series_slice(rec, lo, hi)


def _write_packed(w: TSFWriter, mst: str, buffer: list) -> None:
    """One buffer of (sid, rec) entries as packed chunks.  Short series
    (`packed_segments` 1): one chunk, as ever.  Long ones: the packed
    block is cut along time, at the times that split its rows into equal
    shares, and each share is written as a packed chunk of its own —
    (sid, time)-sorted as the block is, with its own narrow tmin/tmax,
    smin/smax, sparse index and pre-aggregates — so that the reader's
    time pruning reaches the stretch a statement asks for.  A buffer too
    small for its segments (the tail of a measurement) is cut into fewer,
    none under a quarter of a PACK_ROWS."""
    sids, packed = _pack_entries(buffer)
    rows = len(sids)
    n_seg = min(tsf.packed_segments(rows, len(buffer)),
                rows // (tsf.PACK_ROWS // 4))
    if n_seg <= 1:
        w.add_packed_chunk(mst, sids, packed)
        return
    at = [rows * j // n_seg for j in range(1, n_seg)]
    bounds = np.unique(np.partition(packed.times, at)[at])
    seg = np.searchsorted(bounds, packed.times, "right")
    written = 0
    for j in range(len(bounds) + 1):
        idx = np.flatnonzero(seg == j)  # ascending: stays (sid, time)-sorted
        if len(idx):
            w.add_packed_chunk(mst, sids[idx], packed.take(idx))
            written += 1
    _STATS.add("tsf", (("packed_buffers_cut", 1),
                       ("packed_segments_written", written)))


def _write_measurement_chunks(w: TSFWriter, tidx, mst: str, entries,
                              n_series: int | None = None) -> int:
    """Write one measurement's series records: per-sid chunks at low
    cardinality, PK-sorted packed chunks (reference: colstore) once a
    flush carries >= PACK_MIN_SERIES series.  `entries` iterates
    (sid, rec) in ascending sid order; records stream out every
    PACK_ROWS rows so compaction never holds a whole measurement.  Where
    the series runs buffered are long (`tsf.packed_segments` > 1) the
    buffer grows to a PACK_ROWS a segment (SEGMENT_BUFFER at most) and
    is then cut along time (`_write_packed`): a series' rows then lie in
    several packed chunks of the file, ascending and disjoint in time.
    Flush, compact(), compact_level() and compact_out_of_order() all
    write through here.  Returns rows submitted to the writer — the
    flush path feeds this into the durability ledger's tsf_rows
    counter."""
    rows = 0
    if n_series is None:
        entries = list(entries)
        n_series = len(entries)
    if n_series < tsf.PACK_MIN_SERIES:
        for sid, rec in entries:
            w.add_chunk(mst, sid, rec)
            tidx.add(mst, sid, rec)
            rows += len(rec)
        return rows
    buffer: list = []
    buffered = 0
    for sid, rec in entries:
        if len(rec) == 0:
            continue
        tidx.add(mst, sid, rec)     # once a series a file, however cut
        buffer.append((sid, rec))
        buffered += len(rec)
        rows += len(rec)
        if buffered >= tsf.PACK_ROWS * min(
                tsf.packed_segments(buffered, len(buffer)),
                tsf.SEGMENT_BUFFER):
            _write_packed(w, mst, buffer)
            buffer, buffered = [], 0
    if buffer:
        _write_packed(w, mst, buffer)
    return rows


def iter_structured_batches(sh, chunk_rows: int):
    """Yield a shard's full content as structured-point batches
    (measurement, tags, t_ns, {field: (type, value)}) of <= chunk_rows —
    the ONE extraction loop shared by migration pushes
    (parallel/cluster._push_shard) and staging commits
    (engine.commit_staging)."""
    batch: list = []
    for mst in sh.measurements():
        for sid in sorted(sh.index.series_ids(mst)):
            rec = sh.read_series(mst, sid)
            if not len(rec):
                continue
            _m, tags = sh.index.series_entry(sid)
            cols = list(rec.columns.items())
            for i in range(len(rec)):
                fields = {}
                for name, col in cols:
                    if col.valid[i]:
                        v = col.values[i]
                        fields[name] = (
                            col.ftype,
                            v.item() if hasattr(v, "item") else v,
                        )
                if fields:
                    batch.append((mst, tags, int(rec.times[i]), fields))
                if len(batch) >= chunk_rows:
                    yield batch
                    batch = []
    if batch:
        yield batch


_DATA_VERSIONS = itertools.count(1)  # see Shard.data_version
_MUT_LOG_MAX = 512  # bounded mutation history; overflow = assume-changed


class FileQuarantined(Exception):
    """A read hit media damage in an immutable file; the file has been
    QUARANTINED (out of the read set, durable `.quar` marker) and this
    query failed cleanly before any wrong value was produced.  The NEXT
    query over this shard skips the file; at rf>1 the coordinator's scan
    failover classifies the resulting 500 as node-down for the round and
    serves the ranges from a replica instead."""

    def __init__(self, path: str, why: str):
        super().__init__(
            f"file quarantined after media fault: {path}: {why}")
        self.path = path
        self.why = why


class DurabilityLedger:
    """Acked-rows vs durable-rows accounting for one shard (PR 4).

    Flow conservation: every row the shard ACCEPTED (acked at the write
    call's return, or re-applied by WAL replay on open) is either still
    in an in-memory part (live memtable or a frozen flush snapshot) or
    was handed to exactly one PUBLISHED TSF.  `published` counts rows at
    the memtable's accounting (frozen.row_count, pre-dedup), so

        acked + replayed == published + rows_in_mem_parts

    holds at every instant the shard lock is held — a dropped snapshot
    shows as a positive `missing`, a double-published one as negative.
    `tsf_rows` counts rows actually written into published flush files
    (post last-write-wins dedup): `published - tsf_rows` is legitimate
    duplicate-timestamp collapse, and for a unique-timestamp workload
    (the stress/torture harnesses) any nonzero gap is silent row loss —
    exactly how the PR-4 consolidation-cache bug was pinned down.

    All mutation happens under the shard lock; `dirty` marks shards
    whose content was rewritten by delete/downsample (accounting
    rebased — conservation no longer checkable)."""

    __slots__ = ("acked", "replayed", "published", "tsf_rows", "dirty")

    def __init__(self):
        self.acked = 0
        self.replayed = 0
        self.published = 0
        self.tsf_rows = 0
        self.dirty = False

    def snapshot(self, mem_rows: int) -> dict:
        missing = (self.acked + self.replayed - self.published - mem_rows)
        return {
            "acked": self.acked,
            "replayed": self.replayed,
            "published": self.published,
            "tsf_rows": self.tsf_rows,
            "mem_rows": mem_rows,
            "dirty": self.dirty,
            # >0: acked rows vanished; <0: a snapshot published twice
            "missing": 0 if self.dirty else missing,
        }


class Shard:
    supports_preagg = True  # RemoteShard proxies set False (no chunk meta)

    def __init__(self, path: str, tmin: int, tmax: int, sync_wal: bool = False,
                 tag_arrays: bool = False):
        self.path = path
        self.tag_arrays = tag_arrays  # WAL replay must expand like ingest
        self.tmin = tmin  # inclusive ns
        self.tmax = tmax  # exclusive ns
        os.makedirs(path, exist_ok=True)
        self.index = open_series_index(path)
        # LOGICAL-content version + bounded mutation log: versions are
        # drawn from a process-global counter so a (path, version) pair
        # can never repeat — a dropped-and-recreated shard at the same
        # path cannot alias a stale cache signature. The log records each
        # mutation's TIME RANGE so the incremental result cache
        # (query/resultcache.py) invalidates only the touched windows of
        # this shard, not all of them (a 7d shard covers every window of a
        # dashboard query). Flush/compact change layout, not content, and
        # do not bump. Reference analogue: the query iterID + write
        # tracking of inc_agg_transform.go / lib/resultcache.
        self.data_version = next(_DATA_VERSIONS)
        self._mut_floor = self.data_version  # history unknown at/below
        self._mutations: list[tuple[int, int, int]] = []
        # decoded-column cache namespace (storage/colcache.py): a
        # process-unique shard id stamped onto every reader this shard
        # opens, so cache keys identify (shard, file, chunk) even when a
        # dropped-and-recreated shard reuses a path
        self.cache_ns = next(_DATA_VERSIONS)
        # measurement -> field -> FieldType; owned here so it survives
        # memtable generations and is seeded from immutable files on open.
        self.schemas: dict[str, dict] = {}
        self.mem = MemTable(self.schemas)
        # hot class (lockdep): fsync/sleep/socket under it is a
        # violation — the one audited exception is WAL.rotate's fsync,
        # which this very lock fences (see storage/wal.py)
        self._lock = lockdep.mark_hot(lockdep.RLock(), "shard._lock")
        # flush/rewrite serialization. Lock ORDER: _flush_lock before
        # _lock, always — flush holds _flush_lock across its off-lock
        # encode while taking _lock only to freeze and to publish;
        # anything that both holds _lock and (transitively) flushes
        # (delete/downsample rewrites, tier offload) must take
        # _flush_lock first or it deadlocks against an in-flight flush.
        self._flush_lock = lockdep.name_class(
            lockdep.RLock(), "shard._flush_lock")
        # snapshot-and-swap flush state: memtables frozen under the lock,
        # encoded + written OFF it. Each entry is (frozen memtable,
        # rotated WAL segment path | None); readers merge frozen
        # snapshots between the files and the live memtable until the
        # TSF is published (engine/shard.go Snapshot/commitSnapshot).
        # An immutable TUPLE replaced on every change, so hot per-series
        # probes (_mem_parts) can snapshot it with one attribute read —
        # no lock acquisition per series.
        self._frozen: tuple[tuple[MemTable, str | None], ...] = ()
        self._wal_seg_seq = 1
        # rotated segments found at open (crash between publish and
        # segment removal) or left by a failed flush: their rows replay
        # into the memtable / stay in files, so the next successful
        # flush removes them
        self._stale_wal_segs: list[str] = []
        self._files: list[TSFReader] = []
        self._tidx_cache: dict[str, object] = {}  # tsf path -> parsed | None
        self._next_file_seq = 1
        # acked-vs-durable row accounting (see DurabilityLedger);
        # _replaying routes replay-applied rows into the replayed bucket
        self.ledger = DurabilityLedger()
        self._replaying = False
        # media-damaged files pulled out of the read set: path -> why.
        # Durable `.quar` markers keep quarantine sticky across reopens;
        # the file itself stays on disk as evidence (and for operator
        # purge via /debug/ctrl?mod=scrub&op=purge) — at rf>1 the scrub
        # service heals the lost rows back in through anti-entropy.
        self._quarantined: dict[str, str] = {}
        self._load_files()
        for r in self._files:
            for mst in r.measurements():
                self.schemas.setdefault(mst, {}).update(r.schema(mst))
        # replay BEFORE opening the live WAL handle: interior-corruption
        # recovery may quarantine + rewrite wal.log on disk, and the
        # append handle must open over the REWRITTEN file
        self._replay_wal()
        self.wal = WAL(os.path.join(path, "wal.log"), sync=sync_wal)

    def _adopt(self, reader: TSFReader) -> TSFReader:
        """Stamp the shard's cache namespace onto a freshly-opened reader
        (decoded-column cache key component, storage/colcache.py)."""
        reader.owner_ns = self.cache_ns
        return reader

    def drop_cached_columns(self) -> int:
        """Invalidate every decoded-column cache entry of this shard's
        CURRENT files (close/offload hook; file-set swaps invalidate the
        retired readers at the swap site). Returns entries dropped."""
        return colcache.GLOBAL.invalidate_gens([r.gen for r in self._files])

    # -- quarantine (media-fault containment) -------------------------------

    def _write_quar_marker(self, path: str, why: str) -> None:
        """Durable `.quar` marker write+fsync.  Lock-free by design
        (lockdep: the fsync must not stall writers/readers behind
        media-fault bookkeeping) and idempotent — concurrent detectors
        just rewrite the same marker."""
        import json as _json

        _fp("quarantine-before-mark")  # detected, marker not yet durable
        marker = _quar_marker(path)
        tmp = marker + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                # wall-clock record: operator forensics metadata only
                _json.dump({"why": why,
                            "ts": __import__("time").time()},  # ogtlint: disable=OGT040
                           f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, marker)
        except OSError:
            pass  # marker is sticky-convenience; in-memory state governs

    def _record_quarantined(self, path: str, why: str) -> None:
        """In-memory quarantine record + counters (marker already
        durable — see _write_quar_marker)."""
        import logging

        self._quarantined[path] = why
        _STATS.incr("quarantine", "tsf_files_total")
        logging.getLogger("opengemini_tpu.shard").error(
            "quarantined TSF file %s: %s", path, why)
        from opengemini_tpu.utils.governor import GOVERNOR as _GOV

        _GOV.trigger_diagnostic(f"TSF file quarantined: {path}: {why}")

    def _quarantine_path(self, path: str, why: str) -> None:
        """Record + durably mark one file quarantined (no reader swap —
        open-time path, or the reader is already gone).  The `.quar`
        marker keeps quarantine sticky across reopens; a crash between
        detection and the marker just re-detects next open."""
        self._write_quar_marker(path, why)
        self._record_quarantined(path, why)

    def quarantine_file(self, path: str, why: str) -> bool:
        """Runtime quarantine: pull a damaged file out of the read set.
        Returns True when THIS call quarantined it (False = already
        quarantined or not one of this shard's files).  Queries that
        were mid-scan keep their reader refs (POSIX fds survive);
        every later scan snapshot simply excludes the file."""
        with self._lock:
            if not any(r.path == path for r in self._files):
                return False
        # durable marker OFF the shard lock (lockdep: the fsync must not
        # stall writers/readers behind media-fault bookkeeping); written
        # before the swap so detection stays sticky even if we crash
        # mid-quarantine, and idempotent under concurrent detectors
        self._write_quar_marker(path, why)
        with self._lock:
            idx = next((i for i, r in enumerate(self._files)
                        if r.path == path), None)
            if idx is None:
                return False  # lost the race: another detector (or a
                # compaction retire) already pulled the file
            reader = self._files[idx]
            self._record_quarantined(path, why)
            self._files = self._files[:idx] + self._files[idx + 1:]
            self._tidx_cache.pop(path, None)
            colcache.GLOBAL.invalidate_gens([reader.gen])
            # logical content changed (rows vanished until repair):
            # cached query results over the file's range must not mix
            # with post-quarantine scans
            lo = reader.tmin if reader.tmin is not None else self.tmin
            hi = reader.tmax + 1 if reader.tmax is not None else self.tmax
            self._note_mutation(lo, hi)
        return True

    def note_corrupt(self, exc: CorruptFile):
        """Read-path handler: quarantine the damaged file and fail THIS
        query cleanly (FileQuarantined) — detection always beats serving
        a wrong value.  Unaffected queries (and retries of this one)
        proceed without the file."""
        self.quarantine_file(exc.path, exc.why)
        raise FileQuarantined(exc.path, exc.why) from exc

    def quarantined(self) -> dict[str, str]:
        """{path: why} of this shard's quarantined files."""
        with self._lock:
            return dict(self._quarantined)

    def purge_quarantined(self) -> int:
        """Operator/scrub cleanup: delete quarantined files + markers
        from disk (after rf>1 repair re-replicated the rows, or the
        operator accepted the loss).  Returns files purged."""
        with self._lock:
            doomed = list(self._quarantined)
            self._quarantined.clear()
        n = 0
        for path in doomed:
            for p in (path, _quar_marker(path), _tidx_path(path)):
                try:
                    os.remove(p)
                    n += p == path
                except OSError:
                    pass
        return n

    def _note_mutation(self, lo: int, hi: int) -> None:
        """Record a logical-content change over [lo, hi) ns."""
        self.data_version = next(_DATA_VERSIONS)
        self._mutations.append((self.data_version, lo, hi))
        if len(self._mutations) > _MUT_LOG_MAX:
            drop = len(self._mutations) // 2
            self._mut_floor = self._mutations[drop - 1][0]
            # REPLACE, never truncate in place: lockless readers iterate
            # their own snapshot (a shrinking list would silently end a
            # reversed() iterator early and hide recent mutations)
            self._mutations = self._mutations[drop:]

    def changed_since(self, version: int, lo: int, hi: int) -> bool:
        """Did any mutation newer than `version` touch [lo, hi)?
        Conservative: truncated history answers True."""
        if version < self._mut_floor:
            return True
        muts = self._mutations  # snapshot ref (list is replaced, not cut)
        for v, mlo, mhi in reversed(muts):
            if v <= version:
                break
            if mhi > lo and mlo < hi:
                return True
        return False

    # -- open/recovery ------------------------------------------------------

    def _load_files(self) -> None:
        import json as _json

        # sweep crash leftovers: a .merge/.tmp that never reached its
        # os.replace would otherwise accumulate as full-size garbage
        for f in os.listdir(self.path):
            if f.endswith((".merge", ".tmp")):
                try:
                    os.remove(os.path.join(self.path, f))
                except OSError:
                    pass
        names = sorted(
            f for f in os.listdir(self.path) if f.endswith(".tsf")
        )
        for name in names:
            full = os.path.join(self.path, name)
            # the sequence advances past EVERY file, quarantined or not:
            # a later flush must never reuse a damaged file's name
            seq = int(name.split(".")[0])
            self._next_file_seq = max(self._next_file_seq, seq + 1)
            marker = _quar_marker(full)
            if os.path.exists(marker):
                try:
                    with open(marker, encoding="utf-8") as f:
                        why = _json.load(f).get("why", "marker present")
                except (OSError, ValueError):
                    why = "marker present"
                self._quarantined[full] = why
                continue
            try:
                reader = TSFReader(full)
            except CorruptFile as e:
                # damaged trailer/meta/magic: the old behavior crashed
                # the whole shard open (every query on every other file
                # died with it) — quarantine the one file instead
                self._quarantine_path(full, e.why)
                continue
            self._files.append(self._adopt(reader))

    def _replay_wal(self) -> None:
        self._replaying = True
        try:
            self._replay_wal_inner()
        finally:
            self._replaying = False

    def _replay_wal_inner(self) -> None:
        wal_path = os.path.join(self.path, "wal.log")
        # rotated segments first (oldest → newest), then the live log:
        # the append order every last-write-wins rank derives from. A
        # segment present at open means a crash hit the window between
        # WAL rotation and segment removal — its rows either replay fresh
        # (TSF never published) or dedup against the published file.
        for seg in WAL.segments(wal_path):
            self._stale_wal_segs.append(seg)
            seq = seg.rsplit(".", 1)[-1]
            if seq.isdigit():
                self._wal_seg_seq = max(self._wal_seg_seq, int(seq) + 1)
            self._replay_one(seg)
        self._replay_one(wal_path)

    def _replay_one(self, wal_path: str) -> None:
        try:
            for entry in WAL.replay(wal_path):
                self._replay_entry(entry)
        except WALCorruption as e:
            self._recover_wal_corruption(wal_path, e)

    def _recover_wal_corruption(self, wal_path: str, e: WALCorruption) -> None:
        """Interior WAL damage (media fault, never a crash artifact):
        re-apply the salvaged suffix — every frame after the damage
        holds rows that were ACKED — preserve the damaged log as a
        quarantine sidecar, and rewrite a clean log from the decodable
        frames so the recovered rows stay durable and the next reopen
        replays cleanly (reopen idempotence).  At most the one destroyed
        frame is lost, and LOUDLY: counters, log line, sherlock dump."""
        import logging
        import shutil as _shutil

        for entry in e.salvaged_entries():
            self._replay_entry(entry)
        n_good = len(e.clean_frames) + len(e.salvaged_frames)
        qdir = os.path.join(self.path, "quarantine")
        os.makedirs(qdir, exist_ok=True)
        qpath = os.path.join(
            qdir, os.path.basename(wal_path) + f".corrupt-{e.offset}")
        try:
            if not os.path.exists(qpath):  # keep the FIRST evidence copy
                _shutil.copy2(wal_path, qpath)
        except OSError:
            qpath = None  # evidence copy is best-effort, recovery is not
        # rewrite the log with every decodable frame, atomically: the
        # salvaged rows must not live only in this process's memtable
        import zlib as _z

        from opengemini_tpu.storage.wal import _HEADER as _WH

        tmp = wal_path + ".tmp"
        with open(tmp, "wb") as f:
            for kind, payload in (*e.clean_frames, *e.salvaged_frames):
                f.write(_WH.pack(len(payload), _z.crc32(payload), kind)
                        + payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, wal_path)
        _STATS.incr("wal", "interior_corruptions")
        _STATS.incr("wal", "salvaged_frames", len(e.salvaged_frames))
        _STATS.incr("quarantine", "wal_salvages_total")
        logging.getLogger("opengemini_tpu.shard").error(
            "WAL %s: interior corruption at offset %d — one frame "
            "destroyed, %d frame(s) salvaged, damaged log preserved at "
            "%s", wal_path, e.offset, len(e.salvaged_frames), qpath)
        from opengemini_tpu.utils.governor import GOVERNOR as _GOV

        _GOV.trigger_diagnostic(
            f"WAL interior corruption in {wal_path} (offset {e.offset}, "
            f"{n_good} frames recovered)")

    def _replay_entry(self, entry) -> None:
        from opengemini_tpu.ingest import native_lp

        if entry[0] == "lines":
            _, lines, precision, now_ns = entry
            batch = None
            try:
                if not (self.tag_arrays and b"=[" in lines):
                    batch = native_lp.parse_columnar(
                        lines, precision, now_ns)
            except lp.ParseError:
                batch = None
            if batch is not None:
                try:
                    self._apply_columnar(batch, check_types=True)
                except FieldTypeConflict:
                    # partial-write semantics: a batch rejected at write
                    # time must not poison replay either
                    pass
                return
            points = lp.parse_lines(lines, precision, now_ns,
                                    expand_tag_arrays=self.tag_arrays)
        else:
            points = entry[1]
        replayed = 0
        for p in points:
            mst, tags, t, fields = p
            if self.tmin <= t < self.tmax:
                sid = self.index.get_or_create(mst, tags)
                try:
                    self.mem.write_row(sid, mst, t, fields)
                except FieldTypeConflict:
                    continue
                replayed += 1
        if replayed:  # one batched credit per entry, not per row
            self._ledger_accept(replayed)

    # -- write path ---------------------------------------------------------

    def write_points(self, points: list, raw_lines: bytes, precision: str,
                     now_ns: int, defer_commit: bool = False):
        """Apply pre-parsed points in this shard's range; `raw_lines` is the
        original batch logged for replay (replay re-filters by time range).
        Returns rows written. Raises FieldTypeConflict BEFORE touching the
        WAL — a rejected batch must not poison replay.

        The sync-WAL durability wait happens OUTSIDE the shard lock, so
        concurrent writers coalesce into one fsync (WAL group commit)
        instead of serializing an fsync each under the lock.  With
        `defer_commit=True` returns (rows, ticket) and the CALLER owns
        the `wal.commit(ticket)` — the engine lifts the wait out of its
        own lock too, so fsyncs coalesce across server threads.

        Sync-failure semantics (group commit): rows apply to the
        memtable BEFORE the fsync barrier, so a write erroring at
        commit() is already readable and will become durable with the
        next successful sync/flush.  The old inline path had the mirror
        inconsistency (the frame was written pre-fsync, so error-acked
        rows resurfaced via replay after restart); either way an
        errored ack means durability UNKNOWN, not rejected."""
        with self._lock:
            self._check_types(points)
            ticket = self.wal.append_lines(raw_lines, precision, now_ns)
            n = self._apply(points)
        if defer_commit:
            return n, ticket
        self.wal.commit(ticket)
        return n

    def write_points_structured(self, points: list,
                                defer_commit: bool = False):
        """Same as write_points but WAL-logged as structured points (kind 2)
        — the SELECT INTO / internal write path, no line-protocol text."""
        with self._lock:
            self._check_types(points)
            ticket = self.wal.append_points(points)
            n = self._apply(points)
        if defer_commit:
            return n, ticket
        self.wal.commit(ticket)
        return n

    def write_columnar(self, batch, rows: np.ndarray | None,
                       raw_lines: bytes, precision: str, now_ns: int,
                       defer_commit: bool = False):
        """Apply a native-parsed ColumnarBatch (ingest/native_lp.py). `rows`
        selects this shard's row indices (None = all rows). WAL-logs the
        ORIGINAL batch text (replay re-filters by time range, exactly like
        write_points). Type conflicts raise BEFORE the WAL append."""
        with self._lock:
            self._check_columnar_types(batch, rows)
            with tracing.span("wal_append", bytes=len(raw_lines)):
                ticket = self.wal.append_lines(raw_lines, precision, now_ns)
            with tracing.span("memtable_apply"):
                n = self._apply_columnar(batch, rows=rows)
        if defer_commit:
            return n, ticket
        self.wal.commit(ticket)  # see write_points: group-commit wait
        return n

    def _check_columnar_types(self, batch, rows) -> None:
        pending: dict[tuple[int, str], object] = {}
        for mst_id, name, ftype, _values, valid in batch.cols:
            sel = valid if rows is None else valid[rows]
            if not sel.any():
                continue
            mst = batch.measurements[mst_id]
            schema = self.schemas.get(mst, {})
            have = schema.get(name) or pending.get((mst_id, name))
            if have is None:
                pending[(mst_id, name)] = ftype
            elif have != ftype:
                raise FieldTypeConflict(name, have, ftype)

    def _resolve_sids(self, batch, refs: np.ndarray) -> np.ndarray:
        """Map unique series refs -> sids via the series index (new series
        register here). Returns an array indexed by ref."""
        sid_by_ref = np.zeros(len(batch.series_keys), np.int64)
        bulk = getattr(self.index, "get_or_create_bulk", None)
        if bulk is not None and len(refs) > 8:
            ref_list = [int(r) for r in refs]
            sids = bulk([batch.series_keys[r] for r in ref_list])
            sid_by_ref[ref_list] = sids
            return sid_by_ref
        for ref in refs:
            sid_by_ref[ref] = self.index.get_or_create_by_key(
                batch.series_keys[int(ref)])
        return sid_by_ref

    def _apply_columnar(self, batch, rows: np.ndarray | None = None,
                        check_types: bool = False) -> int:
        """Memtable-apply the batch's selected rows (per-measurement slab
        appends). `check_types=True` is the WAL-replay path (no prior
        _check_columnar_types call; conflicts raise before any mutation).
        Rows outside [tmin, tmax) are filtered here — replay feeds whole
        batches."""
        ts = batch.ts if rows is None else batch.ts[rows]
        in_range = (ts >= self.tmin) & (ts < self.tmax)
        if not in_range.all():
            rows = (np.flatnonzero(in_range) if rows is None
                    else rows[in_range])
            ts = batch.ts[rows]
        if len(ts) == 0:
            return 0
        if check_types:
            self._check_columnar_types(batch, rows)
        refs = batch.series_ref if rows is None else batch.series_ref[rows]
        sid_by_ref = self._resolve_sids(batch, np.unique(refs))
        sids = sid_by_ref[refs]
        row_mst = batch.series_mst[refs]
        n = 0
        for mst_id in np.unique(row_mst):
            mst = batch.measurements[int(mst_id)]
            sel = row_mst == mst_id
            all_rows = sel.all()
            idx = None if all_rows else np.flatnonzero(sel)
            cols = {}
            for c_mst, name, ftype, values, valid in batch.cols:
                if c_mst != mst_id:
                    continue
                v = values if rows is None else values[rows]
                ok = valid if rows is None else valid[rows]
                if not all_rows:
                    v, ok = v[idx], ok[idx]
                if ok.any():
                    cols[name] = (ftype, v, ok)
            m_sids = sids if all_rows else sids[idx]
            m_ts = ts if all_rows else ts[idx]
            self.mem.write_columnar(mst, m_sids, m_ts, cols)
            n += len(m_ts)
        if n:
            self._note_mutation(int(ts.min()), int(ts.max()) + 1)
            self._ledger_accept(n)
        return n

    def _ledger_accept(self, n: int) -> None:
        """Rows entered the memtable (caller holds the shard lock):
        credit the acked bucket — or replayed, when WAL replay is the
        writer (those rows were acked in a previous process life).
        /debug/vars durability gauges come from the live ledgers (stats
        provider), never from separate counters — two diverging copies
        of the same number would poison the alerting surface."""
        if self._replaying:
            self.ledger.replayed += n
        else:
            self.ledger.acked += n

    def _check_types(self, points: list) -> None:
        pending: dict[str, dict] = {}
        for mst, _tags, _t, fields in points:
            schema = self.schemas.get(mst, {})
            batch_schema = pending.setdefault(mst, {})
            for name, (ftype, _v) in fields.items():
                have = schema.get(name) or batch_schema.get(name)
                if have is None:
                    batch_schema[name] = ftype
                elif have != ftype:
                    raise FieldTypeConflict(name, have, ftype)

    def _apply(self, points: list) -> int:
        n = 0
        for mst, tags, t, fields in points:
            sid = self.index.get_or_create(mst, tags)
            self.mem.write_row(sid, mst, t, fields)
            n += 1
        if n:
            self._note_mutation(
                min(p[2] for p in points), max(p[2] for p in points) + 1)
            self._ledger_accept(n)
        return n

    def flush(self) -> None:
        """Memtable -> new TSF file, then drop the covering WAL segment.

        Snapshot-and-swap (reference Snapshot/commitSnapshot,
        engine/shard.go:731/:1008): under the shard lock the memtable is
        FROZEN, the WAL rotates to a fresh segment, and a new memtable
        installs — microseconds.  Encoding (pipelined through the encode
        pool) and file writing then run OFF the shard lock, so concurrent
        ingest and reads proceed for the whole encode+write+fsync;
        readers merge the frozen snapshot between the files and the live
        memtable until the new TSF publishes.  Crash-safe ordering is
        unchanged: the file is fsynced and atomically renamed BEFORE the
        rotated segment (and only it) is removed; a crash anywhere
        replays the surviving segments over whatever published, and
        last-write-wins dedup makes the overlap idempotent.  A failed
        flush keeps its frozen snapshot queued (readable, recoverable);
        the next flush drains it first, oldest first.

        Measurement chunks emit in sorted-name order (since r3): TSF file
        layout can differ from files written by older versions for
        multi-measurement shards. Replica comparison is CONTENT-based
        (content_digest hashes logical rows, not file bytes), so
        mixed-version replicas still agree."""
        with self._flush_lock:
            with self._lock:
                if len(self.mem) == 0 and not self._frozen:
                    return
                self.index.flush()
                if len(self.mem):
                    seg = os.path.join(
                        self.path, f"wal.log.{self._wal_seg_seq:06d}")
                    self._wal_seg_seq += 1
                    seg = self.wal.rotate(seg)
                    self.mem.freeze()
                    self._frozen = self._frozen + ((self.mem, seg),)
                    self.mem = MemTable(self.schemas)
                    # armed site between the freeze/rotate/swap (done,
                    # still under both locks) and the off-lock encode —
                    # a kill here leaves a rotated segment + frozen
                    # snapshot that replay must fully recover
                    _fp("shard-flush-after-rotate")
            # off the shard lock: encode + write + fsync + publish, one
            # file per frozen snapshot, oldest first (file append order =
            # write order keeps last-write-wins ranking exact)
            while True:
                with self._lock:
                    if not self._frozen:
                        return
                    frozen, seg = self._frozen[0]
                    path = os.path.join(
                        self.path, f"{self._next_file_seq:08d}.tsf")
                    self._next_file_seq += 1
                self._flush_frozen(frozen, seg, path)

    def flush_if_over(self, threshold_bytes: int) -> bool:
        """Threshold-path flush: N concurrent writers that all saw the
        same over-threshold memtable must trigger ONE flush, not N
        cascading rotations of a few trickle rows each.  Non-blocking: a
        flush already in flight covers this crossing (rows written after
        its freeze accumulate toward the next one), so the caller —
        usually a request thread — never queues behind a full
        encode+fsync just to re-check and no-op."""
        if not self._flush_lock.acquire(blocking=False):
            return False
        try:
            if self.mem.approx_bytes <= threshold_bytes and not self._frozen:
                return False
            self.flush()
            return True
        finally:
            self._flush_lock.release()

    def _flush_frozen(self, frozen: MemTable, seg: str | None,
                      path: str) -> None:
        """Encode+write one frozen memtable into `path`, publish it, then
        remove the WAL segment(s) its rows came from.  Caller holds
        _flush_lock but NOT _lock (except re-entrantly, when a rewrite
        op flushes inline)."""
        import time as _time

        t0 = _time.perf_counter_ns()
        _fp("shard-flush-before-encode")  # off-lock encode begins
        w = TSFWriter(path, kind="flush")
        tidx = _TextSidecar()
        tsf_rows = 0
        try:
            for mst, sid_arr, rec in frozen.measurement_tables():
                uniq, starts = np.unique(sid_arr, return_index=True)
                ends = np.append(starts[1:], len(sid_arr))
                tsf_rows += _write_measurement_chunks(
                    w, tidx, mst,
                    _sid_entries(rec, uniq, starts, ends),
                    n_series=len(uniq))
            # post-dedup rows can only ever SHRINK vs the snapshot's
            # accepted-row count; more means duplicated rows — abort
            # BEFORE finish() makes the bad file durable
            if tsf_rows > frozen.row_count:
                raise RuntimeError(
                    f"flush wrote {tsf_rows} rows from a "
                    f"{frozen.row_count}-row snapshot (duplication)")
            _fp("shard-flush-before-publish")  # reference: engine/shard.go:457
            w.finish()
        except BaseException:
            w.abort()
            raise
        with self._lock:
            reader = self._adopt(TSFReader(path))
            self._files.append(reader)
            # publish + un-freeze atomically: a reader snapshots either
            # (old files + frozen) or (files + new TSF) — never neither
            self._frozen = self._frozen[1:]
            if seg is not None:
                self._stale_wal_segs.append(seg)
            # ledger: the snapshot's rows moved from mem-parts to a
            # published file — same lock hold as the swap, so the
            # conservation invariant never wobbles mid-publish (gauges
            # ride the stats provider; see _ledger_accept)
            self.ledger.published += frozen.row_count
            self.ledger.tsf_rows += tsf_rows
        _fp("shard-flush-after-publish")
        # sidecar AFTER adoption: w.finish() already made the TSF
        # visible on disk, so a sidecar failure here must not leave the
        # snapshot queued (a retry would write the same rows into a
        # SECOND file next to the adopted-on-reopen orphan). The brief
        # no-sidecar window only disables text pruning — reads stay
        # exact. Written under the lock, and only while OUR reader still
        # owns the path: an in-place compaction that already replaced
        # this file wrote the MERGED sidecar, which a stale write here
        # must not clobber (silent text-prune under-reporting).
        with self._lock:
            if any(r is reader for r in self._files):
                tidx.write(path)
                self._tidx_cache.pop(path, None)
        _STATS.incr("flush", "flushes")
        _STATS.incr("flush", "rows", frozen.row_count)
        _STATS.incr("flush", "total_ns", _time.perf_counter_ns() - t0)
        _H_FLUSH.observe_ns(_time.perf_counter_ns() - t0)
        _fp("shard-flush-before-wal-truncate")
        # rows are durable in the published file: the rotated segment —
        # and any stale ones from crashes/failed flushes — can go
        stale, self._stale_wal_segs = self._stale_wal_segs, []
        for p in stale:
            try:
                os.remove(p)
            except OSError:
                pass
        _fp("shard-flush-after-wal-truncate")

    @staticmethod
    def _merge_readers(readers, w: "TSFWriter", tidx: "_TextSidecar") -> None:
        """Shared merge body of compact()/compact_level(): all chunks per
        series across `readers` (oldest first: timestamp last-write-wins
        dedup holds), written merged into `w` + the text sidecar.  Output
        re-packs into PK-sorted multi-series chunks at high cardinality."""
        per_mst: dict[str, set[int]] = {}
        for r in readers:
            for mst in r.measurements():
                sids = per_mst.setdefault(mst, set())
                for c in r.chunks(mst):
                    if c.packed:
                        sids.update(
                            int(s) for s in
                            np.unique(r.read_packed_sids(c, cache=False)))
                    else:
                        sids.add(c.sid)
        BATCH = 65536  # sids per merge batch: bounds resident rows
        for mst in sorted(per_mst):
            sids_sorted = sorted(per_mst[mst])
            n_series = len(sids_sorted)

            def merged_entries():
                for b0 in range(0, n_series, BATCH):
                    batch = np.asarray(sids_sorted[b0:b0 + BATCH], np.int64)
                    batch_set = set(batch.tolist())
                    # one decode per chunk per batch (cache=False: the
                    # soon-to-be-retired readers must not pin memory);
                    # decodes fan across the scan pool, yielding in file
                    # order so last-write-wins ranking is unchanged
                    def decode(r, c):
                        if c.packed:
                            s_arr, rec = r.read_packed_bulk(
                                mst, c, None, sid_filter=batch, cache=False)
                            return (s_arr, rec) if len(rec) else None
                        rec = r.read_chunk(mst, c, cache=False)
                        return (np.full(len(rec), c.sid, np.int64), rec)

                    jobs = []
                    ests = []
                    for r in readers:
                        for c in r.chunks(mst):
                            if c.packed:
                                if c.smax < batch[0] or c.smin > batch[-1]:
                                    continue
                            elif c.sid not in batch_set:
                                continue
                            jobs.append(lambda r=r, c=c: decode(r, c))
                            ests.append(scanpool.est_chunk_bytes(c, None))
                    parts = [p for p in scanpool.map_ordered(jobs, ests)
                             if p is not None]
                    sid_arr, rec = _merge_bulk_parts(
                        parts, -(2**63), 2**63 - 1)
                    uniq, starts = np.unique(sid_arr, return_index=True)
                    ends = np.append(starts[1:], len(sid_arr))
                    for sid, lo, hi in zip(uniq, starts, ends):
                        yield int(sid), Record(
                            rec.times[lo:hi],
                            {
                                name: Column(col.ftype, col.values[lo:hi],
                                             col.valid[lo:hi])
                                for name, col in rec.columns.items()
                            },
                        )

            _write_measurement_chunks(
                w, tidx, mst, merged_entries(), n_series=n_series)

    def file_count(self) -> int:
        with self._lock:
            return len(self._files)

    @staticmethod
    def _find_run(cur: list, run: list) -> int | None:
        """Position of `run` inside `cur` — matched by READER IDENTITY,
        contiguous and in order — or None when any member vanished
        (quarantine pulled a file, or a delete/downsample rewrite swapped
        the whole set).  The off-lock compaction swap revalidates its
        snapshot through this before publishing."""
        if not run:
            return None
        for j, r in enumerate(cur):
            if r is run[0]:
                if (j + len(run) <= len(cur)
                        and all(cur[j + k] is run[k]
                                for k in range(1, len(run)))):
                    return j
                return None
        return None

    def _compact_offlock(self, pick, *, full: bool) -> bool:
        """Shared snapshot -> off-lock merge -> revalidated-swap engine
        behind compact()/compact_level()/compact_out_of_order() (the PR 3
        flush publish discipline applied to background rewrites).

        `pick(files)` inspects an immutable snapshot and returns the
        contiguous run [i0, i0+n) to merge, or None for nothing to do.
        `full=True` collapses the run into a file under a FRESH sequence
        number; `full=False` lands the output at the run's first path
        (in-place run merge, file order — and with it timestamp LWW
        rank — preserved).

        Locking: the snapshot (and, for a full merge, the output seq
        reservation) happens under `_flush_lock` + `_lock`; the merge,
        encode and fsync run with NO lock held, so ingest/flush/queries
        never stall behind a compaction.  The seq-order == publish-order
        rule survives because the output seq is reserved BEFORE going
        off-lock, exactly like flush reserves its path: a flush that
        publishes mid-merge takes a strictly higher seq, so the merged
        (older) rows can never outrank it by name on reopen.  The swap
        re-acquires both locks and revalidates the snapshot by identity —
        files appended meanwhile (flush publishes) are preserved after
        the spliced output; a vanished input (quarantine, delete or
        downsample rewrite) aborts the whole merge (output removed,
        inputs untouched, next tick retries) because publishing it could
        resurrect rows the concurrent rewrite dropped."""
        with self._flush_lock, self._lock:
            files = list(self._files)
            sel = pick(files)
            if sel is None:
                return False
            i0, n = sel
            run = files[i0:i0 + n]
            if full:
                out_path = os.path.join(
                    self.path, f"{self._next_file_seq:08d}.tsf")
                self._next_file_seq += 1
            else:
                out_path = run[0].path
        # merge into a `.merge` temp OFF both locks: invisible to queries
        # and swept by _load_files if we crash before the swap
        tmp = out_path + ".merge"
        w = TSFWriter(tmp, kind="compact")
        tidx = _TextSidecar()
        try:
            self._merge_readers(run, w, tidx)
            w.finish()  # atomically lands at tmp, fsynced
        except CorruptFile as e:
            # damaged merge input: quarantine it so the NEXT compaction
            # (and every query) proceeds without it — merging a corrupt
            # block into the output would launder the damage past its
            # checksum forever
            w.abort()
            self.note_corrupt(e)
        except BaseException:
            w.abort()
            raise
        # self-verify the output OFF-lock before it may replace an
        # input: an in-place merge clobbers run[0] at the swap, so a
        # torn write / bitflip on the output (diskfault tier) must
        # abort HERE with every input intact — publishing first and
        # trusting read-path CRCs would quarantine the merged file
        # and lose the run's rows on a single replica
        try:
            rv = TSFReader(tmp)
            try:
                for loc in rv.data_locs():
                    rv.verify_block(loc)
            finally:
                rv.close()
        except Exception:  # noqa: BLE001 — any unreadable output aborts
            try:
                os.remove(tmp)
            except OSError:
                pass
            _STATS.incr("compact", "output_verify_aborts")
            return False
        published = False
        try:
            _fp("compact-before-replace")
            with self._flush_lock, self._lock:
                j = self._find_run(self._files, run)
                if j is None:
                    # input vanished mid-merge (quarantine / rewrite):
                    # abort — the next tick retries over the new set
                    _STATS.incr("compact", "swap_aborts")
                    return False
                os.replace(tmp, out_path)
                _fp("compact-after-replace")
                published = True
                tidx.write(out_path)
                new_reader = self._adopt(TSFReader(out_path))
                self._files = (
                    self._files[:j] + [new_reader] + self._files[j + n:]
                )
                self._tidx_cache = {}
                _fp("compact-before-retire")  # new set live, old not gone
                if full:
                    _retire_files(run)
                else:
                    _retire_files(run[1:])  # old run[0] reader keeps its fd
                    # run[0]'s OLD reader was replaced in place (same
                    # path, new generation): its path needs no unlink,
                    # but its cached decoded columns can never hit again
                    # and would otherwise pin budget forever
                    colcache.GLOBAL.invalidate_gens([run[0].gen])
            _STATS.incr("compact", "offlock_merges")
            return True
        finally:
            if not published:
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    def compact(self, max_files: int = 1) -> bool:
        """Full merge of immutable files (level compaction analogue,
        reference engine/immutable/compact.go LevelCompact:120). Rewrites
        all chunks per series merged+deduped into one file under a fresh
        sequence number. Returns whether a merge happened (False both for
        nothing-to-do and for a merge aborted by the revalidating swap)."""
        def pick(files):
            if len(files) <= max_files:
                return None
            return (0, len(files))

        return self._compact_offlock(pick, full=True)

    @staticmethod
    def _file_level(path: str) -> int:
        """Size-tiered level: L0 < 1MB, each level 8x larger (reference:
        immutable LevelCompact's level groups, compact.go:120 — here the
        level derives from size, no extra metadata)."""
        import math

        try:
            size = os.path.getsize(path)
        except OSError:
            return 0
        if size < (1 << 20):
            return 0
        return 1 + int(math.log(size / (1 << 20), 8))

    def compact_level(self, fanout: int = 4) -> bool:
        """Merge ONE run of >= fanout consecutive same-level files into a
        single file, preserving file order (the merged output replaces the
        run's FIRST file in place, so timestamp last-write-wins dedup
        across remaining files stays correct). O(run) per call instead of
        the full-merge's O(shard) — bounded write amplification."""
        fanout = max(2, fanout)  # fanout=1 would rewrite a file in place

        def pick(files):
            if len(files) < fanout:
                return None
            levels = [self._file_level(r.path) for r in files]
            run_start = run_len = 0
            for i in range(len(levels)):
                if i > 0 and levels[i] == levels[i - 1]:
                    run_len += 1
                else:
                    run_start, run_len = i, 1
                if run_len >= fanout:
                    # merge exactly `fanout` files per call: bounded work,
                    # deterministic, and repeated ticks converge
                    return (run_start, fanout)
            return None

        return self._compact_offlock(pick, full=False)

    def has_time_overlap(self) -> bool:
        """True when any two immutable files' time ranges overlap (the
        out-of-order state that inflates every read with merge work)."""
        with self._lock:
            ranges = sorted(
                (r.tmin, r.tmax) for r in self._files if r.tmin is not None
            )
        for (a_lo, a_hi), (b_lo, b_hi) in zip(ranges, ranges[1:]):
            if b_lo <= a_hi:
                return True
        return False

    def compact_out_of_order(self, max_files: int = 4) -> bool:
        """Merge time-OVERLAPPING files regardless of level (reference:
        engine/immutable/merge_out_of_order.go).  Late-arriving data
        lands in new files whose ranges overlap old ones; leveled
        compaction alone only merges once >= fanout same-level files
        pile up, so overlap — and with it per-read merge amplification —
        could persist indefinitely.  Merges the contiguous run from the
        first overlapping file toward its overlap partner, capped at
        `max_files` per call; repeated calls converge to disjoint
        ranges."""
        def pick(files):
            if len(files) < 2:
                return None
            ranges = [(r.tmin, r.tmax) for r in files]
            for i in range(len(ranges)):
                if ranges[i][0] is None:
                    continue
                for j in range(i + 1, len(ranges)):
                    if ranges[j][0] is None:
                        continue
                    if (ranges[j][0] <= ranges[i][1]
                            and ranges[i][0] <= ranges[j][1]):
                        # the run must stay contiguous (an intervening
                        # file's rows must not change rank relative to
                        # the merge output)
                        return (i, min(j - i + 1, max(2, max_files)))
            return None

        return self._compact_offlock(pick, full=False)

    def rewrite_downsampled(self, every_ns: int, field_aggs: dict | None = None) -> int:
        """Rewrite this shard at `every_ns` resolution (reference:
        engine_downsample StartDownSampleTask). Returns rows written.
        Flushes the memtable first; replaces all files atomically at the
        end (write-new-then-swap, reference compaction_file_info.go)."""
        from opengemini_tpu.storage.downsample import downsample_records

        # _flush_lock FIRST (see __init__ lock-order note): the inline
        # flush below re-enters it, and holding it for the whole rewrite
        # keeps a concurrent off-lock flush from publishing a pre-rewrite
        # snapshot AFTER the file-set swap resurrects dropped rows
        # audited (lockdep): unlike compaction (now fully off-lock, see
        # _compact_offlock), this rewrite derives its output from the
        # LIVE memtable+file state, so it must exclude ingest for its
        # whole read-rewrite-swap span — the exemption stays audited
        with lockdep.allow_blocking("downsample rewrite under shard lock"), \
                self._flush_lock, self._lock:
            self.flush()
            path = os.path.join(self.path, f"{self._next_file_seq:08d}.tsf")
            w = TSFWriter(path, kind="downsample")
            rows = 0
            # schema changes are staged and applied only after the new file
            # is durable — a mid-rewrite failure must not leave in-memory
            # schemas diverged from on-disk (still raw) data
            staged_schemas: dict[str, dict] = {}
            try:
                for mst in self.measurements():
                    per_sid: dict[int, Record] = {}
                    for sid in sorted(self.index.series_ids(mst)):
                        rec = self.read_series(mst, sid)
                        if len(rec):
                            per_sid[sid] = rec
                    out, new_schema = downsample_records(
                        per_sid, self.schema(mst), self.tmin, self.tmax,
                        every_ns, field_aggs,
                    )
                    staged_schemas[mst] = new_schema
                    for sid in sorted(out):
                        w.add_chunk(mst, sid, out[sid])
                        rows += len(out[sid])
                w.finish()
            except BaseException:
                w.abort()
                raise
            _TextSidecar().write(path)  # downsampled output drops strings
            self.schemas.update(staged_schemas)
            self._next_file_seq += 1
            old = self._files
            self._files = [self._adopt(TSFReader(path))]
            self._tidx_cache = {}
            _retire_files(old)
            self._note_mutation(self.tmin, self.tmax)  # after swap (see delete_data)
            self.ledger.dirty = True  # content rebased: counts no longer reconcile
            return rows

    def delete_data(
        self,
        measurement: str,
        sids: set[int] | None = None,
        tmin: int | None = None,
        tmax: int | None = None,
    ) -> None:
        """Delete rows (whole measurement, whole series, or a time range)
        by rewriting immutable files without the deleted rows — the
        reference's drop/delete paths also rewrite/tombstone immutable data
        (engine DropMeasurement / DeleteSeries). Flushes first so the
        memtable participates."""
        # _flush_lock first: see rewrite_downsampled
        # audited (lockdep): like rewrite_downsampled (and unlike the
        # off-lock compactions), the rewrite reads live state and must
        # exclude ingest end-to-end — the exemption stays audited
        with lockdep.allow_blocking("delete rewrite under shard lock"), \
                self._flush_lock, self._lock:
            self.flush()
            if measurement not in self.measurements():
                return
            if sids is not None:
                sids = set(sids) & self.index.series_ids(measurement)
                if not sids:
                    return
            lo = tmin if tmin is not None else -(2**62)
            hi = tmax if tmax is not None else 2**62
            full_series_delete = tmin is None and tmax is None
            path = os.path.join(self.path, f"{self._next_file_seq:08d}.tsf")
            w = TSFWriter(path, kind="delete")
            wrote = False
            try:
                for mst in self.measurements():
                    for sid in sorted(self.index.series_ids(mst)):
                        rec = self.read_series(mst, sid)
                        if len(rec) == 0:
                            continue
                        if mst == measurement and (sids is None or sid in sids):
                            if full_series_delete:
                                continue
                            keep = (rec.times < lo) | (rec.times >= hi)
                            if not keep.any():
                                continue
                            rec = rec.take(np.nonzero(keep)[0])
                        w.add_chunk(mst, sid, rec)
                        wrote = True
                w.finish()
            except BaseException:
                w.abort()
                raise
            self._next_file_seq += 1
            old = self._files
            self._files = [self._adopt(TSFReader(path))] if wrote else []
            if not wrote:
                os.remove(path)
            _retire_files(old)
            self.ledger.dirty = True  # rows dropped: accounting rebased
            # version bump AFTER the swap: a concurrent query that scanned
            # the old files must cache under the OLD version so the next
            # execution invalidates it (bump-before would let pre-delete
            # rows be served from cache under the post-delete version)
            self._note_mutation(
                tmin if tmin is not None else self.tmin,
                tmax if tmax is not None else self.tmax)
            # index + schema cleanup for fully-deleted series
            if full_series_delete:
                doomed = sids if sids is not None else self.index.series_ids(measurement)
                self.index.remove_sids(set(doomed))
                if not self.index.series_ids(measurement):
                    self.schemas.pop(measurement, None)

    # -- read path ----------------------------------------------------------

    def _scan_state(self) -> tuple[list, list]:
        """(files, memtables oldest → newest, live last) in ONE lock
        acquisition: a flush publish swaps (append file, pop frozen)
        atomically under the same lock, so a reader sees the rows in the
        frozen snapshot or in the new file — never in neither."""
        with self._lock:
            mems = [m for m, _seg in self._frozen]
            mems.append(self.mem)
            return list(self._files), mems

    def _mem_parts(self) -> list:
        """Memtable snapshots a read must merge, oldest → newest (frozen
        flush snapshots first, live memtable last).  LOCK-FREE: _frozen
        is an immutable tuple replaced on change, so per-series hot
        paths pay one attribute read, not a lock acquisition."""
        return [m for m, _seg in self._frozen] + [self.mem]

    def mem_overlaps_range(self, sid: int, tmin: int, tmax: int) -> bool:
        """Does ANY in-memory part (frozen snapshots or live memtable)
        hold rows of `sid` in [tmin, tmax]?  Probes each part separately
        — no merge, no lock — for the per-series fast-path checks."""
        for m in self._mem_parts():
            rec = m.record_for(sid)
            if rec is not None and len(rec.slice_time(tmin, tmax)):
                return True
        return False

    def mem_record_for(self, sid: int):
        """Merged in-memory rows of one series across frozen flush
        snapshots + the live memtable (newest last, last-write-wins) —
        what `self.mem.record_for` meant before off-lock flush."""
        recs = [r for r in (m.record_for(sid) for m in self._mem_parts())
                if r is not None]
        if not recs:
            return None
        return recs[0] if len(recs) == 1 else merge_sorted_records(recs)

    def _mem_rows(self, mems: list, measurement: str, sids: np.ndarray,
                  fields: list[str] | None) -> list:
        """[(sid_arr, Record)] of `sids` (int64) out of `mems`, oldest
        first, cut to `fields`: the in-memory parts of one shard
        read.  The caller holds the `mem_read` span; the rows and parts
        taken, after the sid filter and before any range cut, are counted
        here, once."""
        parts = []
        for m in mems:  # frozen snapshots oldest first, live memtable last
            for sid_arr, rec in m.bulk_parts(measurement, sids):
                if fields is not None:
                    rec = Record(rec.times, {k: v for k, v in
                                             rec.columns.items()
                                             if k in fields})
                parts.append((sid_arr, rec))
        _STATS.add("scan", (("mem_rows", sum(len(r) for _s, r in parts)),
                            ("mem_parts", len(parts))))
        return parts

    def mem_view(self, measurement: str, sids,
                 fields: list[str] | None = None) -> list | None:
        """The in-memory rows of `sids`, taken ONCE for a statement's
        per-series reads of this shard (`read_series(..., mem=view)`): one
        `mem_read` span and one consolidation lookup a shard, where
        `record_for` inside `read_series` would be one a series and a scan
        range.  None where no in-memory part holds a row of the
        measurement: such a read opens no span and pays nothing.  The view
        is older than the files `read_series` then lists, so a flush in
        between leaves its rows in both, never in neither."""
        mems = [m for m in self._mem_parts() if m.holds(measurement)]
        if not mems:
            return None
        with tracing.span("mem_read", series=len(sids)):
            return self._mem_rows(mems, measurement,
                                  np.asarray(sids, np.int64), fields)

    def mem_sids_for(self, measurement: str) -> set[int]:
        out: set[int] = set()
        for m in self._mem_parts():
            out |= m.sids_for(measurement)
        return out

    def mem_time_range(self) -> tuple[int | None, int | None]:
        """(min, max) ns across frozen + live memtables (None = no rows)."""
        tmin = tmax = None
        for m in self._mem_parts():
            if m.min_time is not None:
                tmin = m.min_time if tmin is None else min(tmin, m.min_time)
                tmax = m.max_time if tmax is None else max(tmax, m.max_time)
        return tmin, tmax

    def mem_backlog_bytes(self) -> int:
        """Un-flushed resident bytes: live + frozen memtables plus the
        live WAL log.  LOCK-FREE (one _frozen tuple read + int reads) —
        the resource governor polls this on every governed /write
        (utils/governor.py write watermark; engine sums it per process)."""
        return (sum(m.backlog_bytes for m in self._mem_parts())
                + self.wal.backlog_bytes)

    def measurements(self) -> list[str]:
        msts = set(self.index.measurements())
        for r in self._files:
            msts.update(r.measurements())
        return sorted(msts)

    def schema(self, measurement: str) -> dict:
        return dict(self.schemas.get(measurement, {}))

    def file_chunks(self, measurement: str, sids=None, tmin=None, tmax=None):
        """[(reader, ChunkMeta)] oldest file first — the merge order that
        makes last-write-wins correct."""
        out = []
        for r in self._files:
            for c in r.chunks(measurement, sids, tmin, tmax):
                out.append((r, c))
        return out

    def approx_rows(self, measurement: str, tmin=None, tmax=None
                    ) -> tuple[int, int]:
        """(row count, chunk count) for the measurement in the time range,
        from chunk metadata + memtable — no decode. Over-counts rows of
        chunks straddling the range edges; the scan-slice planner only
        needs the order of magnitude."""
        rows = 0
        chunks = 0
        files, mems = self._scan_state()
        for r in files:
            for c in r.chunks(measurement, None, tmin, tmax):
                rows += c.rows
                chunks += 1
        # memtable rows (frozen flush snapshots included) count whole
        # (order-of-magnitude estimate; the memtable has no
        # per-measurement row bookkeeping)
        return rows + sum(len(m) for m in mems), chunks

    def text_match_sids(self, mst: str, field: str, token: str):
        """Series whose PERSISTED rows may contain `token` in `field`
        (pruning set; rows are verified exactly afterwards), or None when
        any file predates the sidecar format (no pruning possible).
        Memtable rows are unindexed — callers must union live-memtable
        sids before intersecting."""
        import json as _json

        from opengemini_tpu.native.textindex import query_grams

        if token.isascii():
            # pure-ASCII terms are whole lowercased tokens in the index
            grams = [token.lower()]
        else:
            # mixed/CJK terms: prune on the NON-ASCII single-char grams
            # only (raw bytes — the index never case-folds non-ASCII).
            # ASCII fragments of a mixed term may be substrings of longer
            # indexed tokens ('log' inside 'logfile') and must not
            # constrain the pruning set.
            grams = [g for g in query_grams(token) if not g.isascii()]
        out: set[int] = set()
        # whole lookup under the shard lock: compact() swaps the file set
        # and resets the cache; populating the cache outside the lock
        # would re-insert entries for retired files forever (RLock —
        # sidecar JSONs are small, so the hold is short)
        with self._lock:
            for r in self._files:
                cached = self._tidx_cache.get(r.path, False)
                if cached is False:
                    try:
                        with open(_tidx_path(r.path), encoding="utf-8") as f:
                            cached = _json.load(f)
                    except (OSError, ValueError):
                        cached = None
                    self._tidx_cache[r.path] = cached
                if cached is None:
                    return None
                toks = cached.get(mst, {}).get(field, {})
                # multi-gram terms (CJK) intersect their grams' postings
                per_file: set[int] | None = None
                for g in grams:
                    got = set(toks.get(g, []))
                    per_file = got if per_file is None else per_file & got
                out.update(per_file or ())
        return out

    def read_series(
        self,
        measurement: str,
        sid: int,
        tmin: int | None = None,
        tmax: int | None = None,
        fields: list[str] | None = None,
        mem: list | None = None,
    ) -> Record:
        """Merged view of one series: immutable chunks (oldest first) +
        memtable last, deduped last-wins, then time-sliced.  `mem`: this
        shard's `mem_view` of the series (and `fields`), in place of a
        look into every in-memory part here.  Multi-chunk
        decodes fan out across the scan pool (storage/scanpool.py) in
        file order; KILL QUERY still interrupts mid-series — the pool's
        ordered yield re-checks the tracker per chunk exactly like the
        old serial loop did (reference:
        ts-store/transport/query/manager.go:130 IsKilled checked inside
        cursor loops)."""
        files, mems = self._scan_state()
        chunks = [(r, c) for r in files
                  for c in r.chunks(measurement, {sid}, tmin, tmax)]
        n_fields = len(fields) if fields is not None else None

        def decode(r, c):
            if c.packed:
                return r.read_packed_sid(measurement, c, sid, fields)
            return r.read_chunk(measurement, c, fields)

        # decoded-column cache consult BEFORE pool dispatch
        # (storage/colcache.py): fully-cached chunks assemble inline and
        # never enter the pool; misses fill through it, so the in-flight
        # backpressure budget keeps applying to everything that decodes
        recs: list = [None] * len(chunks)
        jobs, ests, miss_at = [], [], []
        for i, (r, c) in enumerate(chunks):
            # a fully-cached scan submits nothing to the pool, so the
            # pool's per-chunk kill points never run — keep KILL QUERY
            # responsive per chunk on the warm path too
            _TRACKER.check()
            got = (r.read_packed_sid_if_cached(measurement, c, sid, fields)
                   if c.packed
                   else r.read_chunk_if_cached(measurement, c, fields))
            if got is not None:
                recs[i] = got
            else:
                jobs.append(lambda r=r, c=c: decode(r, c))
                ests.append(scanpool.est_chunk_bytes(c, n_fields))
                miss_at.append(i)
        try:
            for i, out in zip(miss_at, scanpool.map_ordered(jobs, ests)):
                recs[i] = out
        except CorruptFile as e:
            # media damage surfaced mid-scan (block CRC / short read):
            # quarantine the file, fail THIS query cleanly — never
            # return a partial/garbage record
            self.note_corrupt(e)
        # frozen flush snapshots (oldest first) then the live memtable:
        # both are newer than every file, live is newest of all
        if mem is not None:
            mems = ()
            for sid_arr, part in mem:       # each sorted by (sid, time)
                lo = int(np.searchsorted(sid_arr, sid, "left"))
                hi = int(np.searchsorted(sid_arr, sid, "right"))
                if lo < hi:
                    recs.append(_series_slice(part, lo, hi))
        for m in mems:
            mem_rec = m.record_for(sid)
            if mem_rec is None:
                continue
            if fields is not None:
                mem_rec = Record(
                    mem_rec.times,
                    {k: v for k, v in mem_rec.columns.items() if k in fields},
                )
            recs.append(mem_rec)
        merged = merge_sorted_records(recs)
        if tmin is not None or tmax is not None:
            lo = tmin if tmin is not None else -(2**63)
            hi = tmax if tmax is not None else 2**63 - 1
            merged = merged.slice_time(lo, hi)
        return merged

    def read_series_bulk(
        self,
        measurement: str,
        sids: np.ndarray,
        tmin: int | None = None,
        tmax: int | None = None,
        fields: list[str] | None = None,
    ) -> tuple[np.ndarray, Record]:
        """Batched multi-series read: (sid_column, record) for every
        requested series, rows grouped by sid and time-sorted within a
        sid, last-write-wins deduped.  Packed chunks decode ONCE for all
        their series, with no per-sid Python loop (BASELINE.md config #5
        reads 1M series)."""
        sids = np.sort(np.asarray(sids, dtype=np.int64))
        lo_t = tmin if tmin is not None else -(2**63)
        hi_t = tmax if tmax is not None else 2**63 - 1
        # parts MUST append in file order (oldest first): _merge_bulk_parts
        # ranks later parts as newer for last-write-wins; interleaving
        # packed and per-sid chunks out of file order would let stale
        # rows win
        parts: list[tuple[np.ndarray, Record]] = []
        sid_set = set(sids.tolist())
        files, mems = self._scan_state()
        n_fields = len(fields) if fields is not None else None

        def decode_packed(r, c):
            s_arr, rec = r.read_packed_bulk(
                measurement, c, fields, sid_filter=sids)
            return (s_arr, rec) if len(rec) else None

        def decode_single(r, c):
            rec = r.read_chunk(measurement, c, fields)
            return (np.full(len(rec), c.sid, np.int64), rec)

        # chunk decodes fan out across the scan pool; map_ordered yields
        # in submission (= file) order, so the parts list is identical to
        # the old serial loop's and last-write-wins ranking is unchanged.
        # Per-chunk kill points live inside map_ordered (see read_series).
        # Fully-cached chunks (decoded-column cache, storage/colcache.py)
        # assemble inline and skip the pool; `slots` keeps file order.
        jobs = []
        ests = []
        slots: list = []
        miss_at = []
        rows_decoded = 0
        packed_met = 0
        for r in files:
            for c in r.chunks(measurement, None, tmin, tmax):
                if c.packed:
                    packed_met += 1
                    if c.smax < sids[0] or c.smin > sids[-1]:
                        continue
                    _TRACKER.check()  # warm-path kill point (see read_series)
                    got = r.read_packed_bulk_if_cached(
                        measurement, c, fields, sid_filter=sids)
                    if got is not None:
                        slots.append(got if len(got[1]) else None)
                        continue
                    jobs.append(lambda r=r, c=c: decode_packed(r, c))
                elif c.sid in sid_set:
                    _TRACKER.check()  # warm-path kill point
                    got = r.read_chunk_if_cached(measurement, c, fields)
                    if got is not None:
                        slots.append(
                            (np.full(len(got), c.sid, np.int64), got))
                        continue
                    jobs.append(lambda r=r, c=c: decode_single(r, c))
                else:
                    continue
                miss_at.append(len(slots))
                slots.append(None)
                ests.append(scanpool.est_chunk_bytes(c, n_fields))
                rows_decoded += c.rows
        if jobs:
            # the column cache missed: decode, one span per bulk read
            with tracing.span("decode", chunks=len(jobs)):
                try:
                    for i, part in zip(
                            miss_at, scanpool.map_ordered(jobs, ests)):
                        slots[i] = part
                except CorruptFile as e:
                    self.note_corrupt(e)  # see read_series
        parts.extend(p for p in slots if p is not None)
        # what the time pruning of `chunks` spared this read: a long
        # series' file is cut into time segments, each a packed chunk
        _STATS.incr("scan", "packed_skipped_by_time", sum(
            r.packed_count(measurement) for r in files) - packed_met)
        mems = [m for m in mems if m.holds(measurement)]
        with contextlib.ExitStack() as stack:
            if mems:
                # rows not yet flushed: one span a bulk read, over taking
                # them and, where every chunk came from the cache (no
                # `scan_merge`), over merging them with the files' parts
                stack.enter_context(
                    tracing.span("mem_read", series=len(sids)))
                parts.extend(self._mem_rows(mems, measurement, sids, fields))
            if not jobs:    # every chunk came from the cache
                return _merge_counted(parts, lo_t, hi_t)
        # a read that decoded says what became of it: a chunk decodes
        # whole, and the merge trims every part to [tmin, tmax) before
        # it joins or sorts anything, so what it works on (`rows_merged`)
        # is what the statement keeps, not what was decoded
        with tracing.span("scan_merge", parts=len(parts)):
            sid_arr, rec = _merge_counted(parts, lo_t, hi_t)
        _STATS.add("scan", (("rows_decoded", rows_decoded),
                            ("rows_kept", len(rec))))
        return sid_arr, rec

    def content_digest(self) -> dict:
        """Per-measurement logical content digest: {mst: [rows, hash64]}.
        Order-independent (per-series hashes fold with XOR) and keyed by
        canonical series KEYS, never sids (sids differ across replicas).
        Two replicas holding identical logical rows produce identical
        digests regardless of file layout (reference: anti-entropy
        digests for replicated shards, engine/engine_replication.go).
        Cached until the file set or memtable changes."""
        import zlib as _z

        from opengemini_tpu.ingest.line_protocol import series_key

        with self._lock:
            state = (
                tuple((r.path, os.path.getsize(r.path)) for r in self._files
                      if os.path.exists(r.path)),
                tuple(len(m) for m, _seg in self._frozen),
                len(self.mem),
            )
            cached = getattr(self, "_digest_cache", None)
            if cached is not None and cached[0] == state:
                return cached[1]
        out: dict[str, list] = {}
        for mst in self.measurements():
            rows = 0
            acc = 0
            for sid in sorted(self.index.series_ids(mst)):
                rec = self.read_series(mst, sid)
                if not len(rec):
                    continue
                rows += len(rec)
                _m, tags = self.index.series_entry(sid)
                h = _z.crc32(series_key(mst, tags).encode())
                h = _z.crc32(np.ascontiguousarray(rec.times).tobytes(), h)
                for name in sorted(rec.columns):
                    col = rec.columns[name]
                    h = _z.crc32(name.encode(), h)
                    vals = col.values
                    if vals.dtype == object:
                        payload = "\x00".join(
                            "" if v is None else str(v) for v in vals
                        ).encode()
                    else:
                        payload = np.ascontiguousarray(vals).tobytes()
                    h = _z.crc32(payload, h)
                    h = _z.crc32(np.ascontiguousarray(col.valid).tobytes(), h)
                acc ^= h
            if rows:
                out[mst] = [rows, acc]
        with self._lock:
            self._digest_cache = (state, out)
        return out

    def mem_overlaps(self, measurement: str, sid: int) -> bool:
        return any(m.record_for(sid) is not None for m in self._mem_parts())

    def ledger_snapshot(self) -> dict:
        """Consistent acked-vs-durable snapshot (see DurabilityLedger).
        Taken under the shard lock, so a concurrent write or flush
        publish can never show a half-applied state."""
        with self._lock:
            mem_rows = sum(len(m) for m in self._mem_parts())
            return self.ledger.snapshot(mem_rows)

    def close(self) -> None:
        # _flush_lock first: an in-flight off-lock flush finishes (or we
        # get in line ahead of the next one) before handles close
        # audited (lockdep): the final WAL fsync runs under the shard
        # lock — close must be atomic against in-flight writes
        with lockdep.allow_blocking("shard.close shutdown fsyncs"), \
                self._flush_lock, self._lock:
            self.wal.flush()
            self.wal.close()
            self.index.flush()
            self.index.close()
            # retention drops / DROP DATABASE / engine close all arrive
            # here: release every decoded-column cache entry this shard
            # pinned (in-flight readers keep their arrays via refcounts)
            self.drop_cached_columns()
            for r in self._files:
                r.close()

def _retire_files(readers: list) -> None:
    """Unlink replaced immutable files WITHOUT closing their readers:
    in-flight queries hold (reader, chunk) pairs outside the shard lock, and
    POSIX keeps unlinked files readable through existing fds. The fds close
    when the reader objects are garbage-collected after the last query
    releases them (the reference's file-set swap works the same way).
    Decoded-column cache entries of the retired generations drop here too
    (compaction / downsample / delete rewrites); queries mid-scan keep
    any arrays they already hold via normal refcounting."""
    import os as _os

    colcache.GLOBAL.invalidate_gens([r.gen for r in readers])
    for r in readers:
        for p in (r.path, _tidx_path(r.path)):
            try:
                _os.remove(p)
            except OSError:
                pass


def _tidx_path(tsf_path: str) -> str:
    return tsf_path[:-4] + ".tidx" if tsf_path.endswith(".tsf") else tsf_path + ".tidx"


def _quar_marker(tsf_path: str) -> str:
    """Durable quarantine marker path for a damaged immutable file."""
    return tsf_path + ".quar"


class _TextSidecar:
    """Per-file inverted text index over string fields, built as chunks
    are written (reference: the logstore per-segment token index,
    lib/logstore + engine/index/textindex — here a token -> sids map used
    to PRUNE series before decode; rows are still verified exactly)."""

    def __init__(self):
        self.idx: dict[str, dict[str, dict[str, set]]] = {}

    def add(self, mst: str, sid: int, rec) -> None:
        from opengemini_tpu.native.textindex import tokenize
        from opengemini_tpu.record import FieldType

        for name, col in rec.columns.items():
            if col.ftype != FieldType.STRING:
                continue
            toks = self.idx.setdefault(mst, {}).setdefault(name, {})
            for v, ok in zip(col.values, col.valid):
                if ok and isinstance(v, str):
                    for t in set(tokenize(v)):
                        toks.setdefault(t, set()).add(sid)

    def write(self, tsf_path: str) -> None:
        import json as _json

        p = _tidx_path(tsf_path)
        data = {
            m: {f: {t: sorted(s) for t, s in toks.items()}
                for f, toks in flds.items()}
            for m, flds in self.idx.items()
        }
        tmp = p + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            _json.dump(data, f)
        os.replace(tmp, p)  # crash before this: missing sidecar = no prune

"""TSF — the immutable columnar file format (TSSP analogue).

Reference: engine/immutable/tssp_file.go:65-146 (trailer + chunk meta +
bloom), pre_aggregation.go:40 (per-column-segment count/min/max/sum that
lets aggregate queries skip data blocks entirely).

Layout (format revision 2 — "survive the disk"):
    "OGTSF02\\n"                      8-byte magic
    column blocks, each SEALED: [encoded bytes][u32 crc32] — the
          end-to-end per-block checksum verified on every decode
          (self-describing payloads, see storage/encoding.py)
    meta: "BM02" + zlib(binary chunk meta — storage/chunkmeta.py,
          reference chunk_meta_codec.go); legacy zlib(JSON) still reads
    trailer: [u64 meta_off][u32 meta_len][u32 meta_crc]"OGTSFEND"

Revision 1 files ("OGTSF01\\n", CRC-less blocks) remain readable: the
head magic selects per-block verification, so a flipped bit in a v2
data block raises CorruptFile at decode time — before any wrong value
reaches a query — instead of silently decoding garbage (or crashing the
codec).  Block locs cover the sealed length; `_read` strips the seal.

Chunks are either one series' rows for one flush (time + field columns,
validity masks, numeric pre-aggregation) or PK-sorted packed
multi-series blocks (colstore layout, see add_packed_chunk).  A packed
chunk covers a sid span and a stretch of time: where the series of a
file are long, the writer cuts them along time (`packed_segments`), so
a series' rows may lie in several packed chunks of one file, in
ascending, disjoint time ranges — each an ordinary packed chunk with its
own tmin/tmax, sparse index and pre-aggregates, which the time pruning
of `TSFReader.chunks` skips like any other.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import struct
import threading
from opengemini_tpu.utils import lockdep
import time
import zlib
from collections import OrderedDict

import numpy as np

from opengemini_tpu.record import Column, FieldType, Record
from opengemini_tpu.storage import colcache, diskfault, encodepool, encoding
from opengemini_tpu.utils import tracing
from opengemini_tpu.utils.bloom import BloomFilter
from opengemini_tpu.utils.stats import GLOBAL as _STATS

MAGIC = b"OGTSF01\n"   # revision 1: CRC-less blocks (read-only legacy)
MAGIC2 = b"OGTSF02\n"  # revision 2: per-block crc32 seals (written)
END_MAGIC = b"OGTSFEND"
_TRAILER = struct.Struct("<QII")
_BLOCK_CRC = struct.Struct("<I")
_SIDS = "\x00sids"     # a packed chunk's sid column, among its field names


HIST_BINS = 32


class PreAgg:
    """count/min/max/sum of the valid values of one numeric column chunk,
    plus a small equi-width histogram — the sketch that serves
    percentile_approx() from metadata alone (reference: OGSketch
    quantile sketches, engine/executor/ogsketch.go, except persisted
    per chunk so queries skip data blocks entirely)."""

    __slots__ = ("count", "vmin", "vmax", "vsum", "hist")

    def __init__(self, count: int, vmin, vmax, vsum, hist=None):
        self.count = count
        self.vmin = vmin
        self.vmax = vmax
        self.vsum = vsum
        self.hist = hist  # HIST_BINS int counts over [vmin, vmax], or None

    @classmethod
    def of(cls, col: Column) -> "PreAgg | None":
        if col.ftype not in (FieldType.FLOAT, FieldType.INT):
            return cls(int(col.valid.sum()), None, None, None)
        vals = col.values[col.valid]
        if len(vals) == 0:
            return cls(0, None, None, None)
        vmin = vals.min().item()
        vmax = vals.max().item()
        finite = np.isfinite(np.asarray(vals, dtype=np.float64))
        hist = None
        if finite.all() and vmax > vmin:
            hist = np.histogram(
                vals.astype(np.float64), bins=HIST_BINS, range=(vmin, vmax)
            )[0].tolist()
        return cls(len(vals), vmin, vmax, vals.sum().item(), hist)

    def to_json(self):
        return [self.count, self.vmin, self.vmax, self.vsum, self.hist]

    @classmethod
    def from_json(cls, j) -> "PreAgg":
        # older files carry 4-element pre-agg entries (no histogram)
        return cls(*j) if len(j) >= 5 else cls(*j, None)


class ChunkMeta:
    __slots__ = ("sid", "rows", "tmin", "tmax", "time_loc", "cols",
                 "smin", "smax", "sid_loc", "sparse")

    def __init__(self, sid, rows, tmin, tmax, time_loc, cols,
                 smin=None, smax=None, sid_loc=None, sparse=None):
        self.sid = sid  # None for packed (multi-series) chunks
        self.rows = rows
        self.tmin = tmin
        self.tmax = tmax
        self.time_loc = time_loc  # (off, len)
        # field -> {"v": (off,len), "m": (off,len)|None, "pre": PreAgg}
        self.cols = cols
        # packed chunks (PK-sorted column store, reference
        # engine/immutable/colstore): rows sorted by (sid, time); the
        # sid column is its own block and `sparse` is the sparse
        # primary-key index [(sid, row_offset)] every SPARSE_K rows
        # (reference engine/index/sparseindex/primary_index.go)
        self.smin = smin
        self.smax = smax
        self.sid_loc = sid_loc
        self.sparse = sparse

    @property
    def packed(self) -> bool:
        return self.sid is None


# packed-chunk tuning: pack when a measurement flushes many series; the
# sparse PK index records every SPARSE_K-th row boundary
PACK_MIN_SERIES = 64
PACK_ROWS = 131072
SPARSE_K = 1024
# about how many rows of ONE series a packed chunk holds at most: longer
# series runs are cut along time into segments of about this length
# (reference: a TSSP chunk's row segments, each with its own time range
# and pre-aggregates, on the order of 1,000 rows; here a quarter of that,
# so that an hour of 10-15 s samples, 240-360 rows, meets two segments
# and a read of it decodes under twice what it keeps)
SEGMENT_ROWS = 256
# a buffer that will be cut grows to a PACK_ROWS a segment first, so the
# segments come out chunk-sized, not slivers — up to this many PACK_ROWS
# (the buffer is copied once when it is packed: 2 M rows bound that)
SEGMENT_BUFFER = 16


def packed_segments(rows: int, series: int) -> int:
    """Into how many time segments the chunk writer wants a buffer of
    `rows` rows of `series` series cut: rows a series over SEGMENT_ROWS,
    rounded to the nearest, so a run under one and a half segments (an
    hour at a 10 s step) stays whole.  Reads only what the writer sees."""
    return max(1, (rows // max(series, 1) + SEGMENT_ROWS // 2)
               // SEGMENT_ROWS)


def _col_nbytes(col: Column) -> int:
    """Encode-input size estimate of one column (pipeline backpressure)."""
    values = col.values
    if getattr(values, "dtype", None) is not None and values.dtype == object:
        nb = 32 * len(values)
    else:
        nb = int(getattr(values, "nbytes", 8 * len(values)))
    return nb + int(col.valid.nbytes)


class TSFWriter:
    """Writes one TSF file.  Chunk encodes pipeline through the encode
    pool (storage/encodepool.py): add_chunk submits the pure
    numpy/zlib/gorilla encode of chunk N+1 while chunk N's blocks are
    written, draining in submission order so offsets — and file bytes —
    are identical to the serial path (OGT_ENCODE_WORKERS=1 degrades to
    exactly that path).  `kind` tags the /debug/vars counters
    ({kind}_encode_ns / {kind}_write_ns / {kind}_bytes under `tsfwrite`)
    so flush vs compaction vs downsample encode time stays attributable.

    NOT thread-safe: one writer thread owns the file (offsets and meta
    are assigned at drain time on that thread)."""

    def __init__(self, path: str, kind: str = "write"):
        self.path = path
        self._kind = kind
        self._tmp = path + ".tmp"
        self._f = open(self._tmp, "wb")
        self._f.write(MAGIC2)
        self._off = len(MAGIC2)
        # mst -> {"schema": {field: int}, "chunks": [meta json]}
        self._meta: dict = {}
        self._pipe = encodepool.OrderedEncodePipe(self._write_encoded)

    def _write_block(self, buf: bytes) -> tuple[int, int]:
        """Seal + write one block: [payload][u32 crc32(payload)] — the
        ONE chokepoint every data block flows through, so the end-to-end
        checksum can never be skipped by a new writer path.  Offsets and
        lengths cover the sealed bytes; `TSFReader._read` verifies and
        strips.  The diskfault hook may tear/corrupt what the media
        actually holds — the writer still accounts the full sealed
        length (a real torn sector lies to the writer the same way)."""
        sealed = buf + _BLOCK_CRC.pack(zlib.crc32(buf))
        off = self._off
        out = sealed
        if diskfault.armed():
            out = diskfault.on_write(self.path, sealed,
                                     site="tsf-block-write")
        self._f.write(out)
        if len(out) != len(sealed):  # torn write: keep file offsets true
            self._f.seek(off + len(sealed))
        self._off += len(sealed)
        return (off, len(sealed))

    def _check_schema(self, m: dict, rec: Record) -> None:
        """Synchronous (submit-time) schema merge: a type conflict raises
        at the add_chunk call that introduced it, exactly like the serial
        path — never later from inside a drained encode job."""
        schema = m["schema"]
        for name, col in rec.columns.items():
            have = schema.get(name)
            if have is None:
                schema[name] = int(col.ftype)
            elif have != int(col.ftype):
                raise ValueError(
                    f"field type conflict in file for {name!r}: {have} vs {int(col.ftype)}"
                )

    @staticmethod
    def _encode_job(measurement: str, sid, sids, rec: Record):
        """Pure per-chunk encode (runs on a pool worker): every buffer and
        pre-agg this chunk needs, NO offsets — those are assigned at
        drain time in submission order."""
        t0 = time.perf_counter_ns()
        time_buf = encoding.encode_ints(rec.times)
        sid_buf = encoding.encode_ints(sids) if sids is not None else None
        cols = []
        for name, col in rec.columns.items():
            vbuf, mbuf = encoding.encode_column(col)
            cols.append((name, vbuf, mbuf, PreAgg.of(col).to_json()))
        return (measurement, sid, sids, rec, time_buf, sid_buf, cols,
                time.perf_counter_ns() - t0)

    def _write_encoded(self, item) -> None:
        """Drain stage (writer thread): assign offsets, write blocks,
        append the chunk's meta entry."""
        (measurement, sid, sids, rec, time_buf, sid_buf, cols,
         encode_ns) = item
        t0 = time.perf_counter_ns()
        m = self._meta[measurement]
        time_loc = self._write_block(time_buf)
        entry: dict = {
            "rows": len(rec),
            "time": time_loc,
        }
        if sid_buf is not None:
            entry["packed"] = 1
            entry["smin"] = int(sids[0])
            entry["smax"] = int(sids[-1])
            entry["sids"] = self._write_block(sid_buf)
            entry["sparse"] = [
                [int(sids[i]), i] for i in range(0, len(sids), SPARSE_K)]
            entry["tmin"] = int(rec.times.min())
            entry["tmax"] = int(rec.times.max())
        else:
            entry["sid"] = sid
            entry["tmin"] = int(rec.times[0])
            entry["tmax"] = int(rec.times[-1])
        out_cols = {}
        nbytes = len(time_buf) + (len(sid_buf) if sid_buf else 0)
        for name, vbuf, mbuf, pre in cols:
            vloc = self._write_block(vbuf)
            mloc = self._write_block(mbuf) if mbuf else None
            nbytes += len(vbuf) + (len(mbuf) if mbuf else 0)
            out_cols[name] = {"v": vloc, "m": mloc, "pre": pre}
        entry["cols"] = out_cols
        m["chunks"].append(entry)
        _STATS.incr("tsfwrite", f"{self._kind}_encode_ns", encode_ns)
        _STATS.incr("tsfwrite", f"{self._kind}_write_ns",
                    time.perf_counter_ns() - t0)
        _STATS.incr("tsfwrite", f"{self._kind}_bytes", nbytes)

    def add_chunk(self, measurement: str, sid: int, rec: Record) -> None:
        """rec must be time-sorted ascending and deduped.  The record's
        arrays must stay unmutated until finish()/abort() — the encode
        job may run concurrently (flush encodes a FROZEN memtable;
        compaction/downsample records are freshly built)."""
        if len(rec) == 0:
            return
        m = self._meta.setdefault(measurement, {"schema": {}, "chunks": []})
        self._check_schema(m, rec)
        est = int(rec.times.nbytes) + sum(
            _col_nbytes(c) for c in rec.columns.values())
        self._pipe.submit(
            lambda: self._encode_job(measurement, sid, None, rec), est)

    def add_packed_chunk(self, measurement: str, sids: np.ndarray,
                         rec: Record) -> None:
        """One multi-series chunk: rows sorted by (sid, time) — the
        PK-sorted column store layout (reference:
        engine/immutable/colstore/chunk_builder.go).  `sids` is int64,
        aligned with rec rows, non-decreasing; rows of one sid are
        time-sorted and deduped."""
        if len(rec) == 0:
            return
        m = self._meta.setdefault(measurement, {"schema": {}, "chunks": []})
        self._check_schema(m, rec)
        est = int(rec.times.nbytes) + int(sids.nbytes) + sum(
            _col_nbytes(c) for c in rec.columns.values())
        self._pipe.submit(
            lambda: self._encode_job(measurement, None, sids, rec), est)

    def finish(self) -> None:
        from opengemini_tpu.storage import chunkmeta

        self._pipe.drain()  # every chunk lands before the meta freezes
        # binary chunk meta (format v2, reference chunk_meta_codec.go):
        # decode cost stays flat as chunk counts grow; v1 zlib-JSON files
        # remain readable
        meta_buf = b"BM02" + zlib.compress(chunkmeta.encode_meta(self._meta), 1)
        meta_off = self._off
        tail = (meta_buf
                + _TRAILER.pack(meta_off, len(meta_buf), zlib.crc32(meta_buf))
                + END_MAGIC)
        if diskfault.armed():
            tail = diskfault.on_write(self.path, tail, site="tsf-meta-write")
        self._f.write(tail)
        self._f.flush()
        if diskfault.armed():
            diskfault.on_fsync(self.path, site="tsf-fsync")
        os.fsync(self._f.fileno())
        self._f.close()
        os.replace(self._tmp, self.path)  # atomic visibility

    def abort(self) -> None:
        self._pipe.abort()
        self._f.close()
        if os.path.exists(self._tmp):
            os.remove(self._tmp)


# process-global file generations: a reader opened over a path that a
# compaction later rewrites IN PLACE (os.replace) gets a fresh number, so
# a (generation, chunk) cache key can never alias stale decoded data
_READER_GEN = itertools.count(1)


class TSFReader:
    def __init__(self, path: str):
        self.path = path
        # decoded-column cache identity (storage/colcache.py): gen is the
        # invalidation handle; owner_ns is stamped by the owning Shard
        self.gen = next(_READER_GEN)
        self.owner_ns: int | None = None
        self._f = open(path, "rb")
        self._f.seek(0, os.SEEK_END)
        size = self._f.tell()
        tail = _TRAILER.size + len(END_MAGIC)
        if size < len(MAGIC) + tail:
            raise CorruptFile(path, "too small")
        head = os.pread(self._f.fileno(), len(MAGIC), 0)
        if diskfault.armed():
            head = diskfault.on_read(path, head, site="tsf-open-read")
        if head == MAGIC2:
            # revision 2: every block carries a crc32 seal, verified on
            # every decode (including colcache fills) in _read
            self.block_crc = True
        elif head == MAGIC:
            self.block_crc = False  # legacy: readable, nothing to verify
        else:
            raise CorruptFile(path, "bad magic")
        self._f.seek(size - tail)
        trailer = self._f.read(tail)
        if diskfault.armed():
            trailer = diskfault.on_read(path, trailer, site="tsf-open-read")
        if trailer[-len(END_MAGIC) :] != END_MAGIC:
            raise CorruptFile(path, "bad end magic")
        meta_off, meta_len, meta_crc = _TRAILER.unpack(trailer[: _TRAILER.size])
        self._f.seek(meta_off)
        meta_buf = self._f.read(meta_len)
        if diskfault.armed():
            meta_buf = diskfault.on_read(path, meta_buf, site="tsf-open-read")
        if zlib.crc32(meta_buf) != meta_crc:
            raise CorruptFile(path, "meta crc mismatch")
        if meta_buf[:4] == b"BM02":
            from opengemini_tpu.storage import chunkmeta

            raw = chunkmeta.decode_meta(zlib.decompress(meta_buf[4:]))
        else:
            raw = json.loads(zlib.decompress(meta_buf))
        # mst -> (schema, [ChunkMeta])
        self.meta: dict[str, tuple[dict, list[ChunkMeta]]] = {}
        self.tmin: int | None = None
        self.tmax: int | None = None
        for mst, m in raw.items():
            schema = {k: FieldType(v) for k, v in m["schema"].items()}
            chunks = []
            for c in m["chunks"]:
                cols = {
                    name: {
                        "v": tuple(cc["v"]),
                        "m": tuple(cc["m"]) if cc["m"] else None,
                        "pre": PreAgg.from_json(cc["pre"]),
                    }
                    for name, cc in c["cols"].items()
                }
                if c.get("packed"):
                    cm = ChunkMeta(
                        None, c["rows"], c["tmin"], c["tmax"],
                        tuple(c["time"]), cols,
                        smin=c["smin"], smax=c["smax"],
                        sid_loc=tuple(c["sids"]),
                        sparse=[(p0, p1) for p0, p1 in c["sparse"]],
                    )
                else:
                    cm = ChunkMeta(c["sid"], c["rows"], c["tmin"], c["tmax"],
                                   tuple(c["time"]), cols)
                chunks.append(cm)
                if self.tmin is None or cm.tmin < self.tmin:
                    self.tmin = cm.tmin
                if self.tmax is None or cm.tmax > self.tmax:
                    self.tmax = cm.tmax
            self.meta[mst] = (schema, chunks)
        # per-measurement sid bloom (reference: lib/bloomfilter): single-
        # series lookups reject in O(k) instead of scanning chunk metas —
        # built from in-memory metadata, so no format change
        self._col_cache: OrderedDict = OrderedDict()
        self._cache_bytes = 0
        self._cache_lock = lockdep.Lock()
        self._sid_bloom: dict[str, BloomFilter] = {}
        # per-(mst, sid) chunk lists: single-series lookups are O(own
        # chunks); without this a scan over S series costs S x all-chunks
        # meta filtering — quadratic at high cardinality
        self._sid_chunks: dict[str, dict[int, list[ChunkMeta]]] = {}
        # packed chunks are listed separately: a single-sid lookup takes
        # its per-sid chunks PLUS the packed chunks whose [smin, smax]
        # span covers the sid (sparse index narrows the rows at read time)
        self._packed_chunks: dict[str, list[ChunkMeta]] = {}
        for mst, (_s, chunks) in self.meta.items():
            bf = BloomFilter(len(chunks))
            by_sid: dict[int, list[ChunkMeta]] = {}
            packed: list[ChunkMeta] = []
            for c in chunks:
                if c.packed:
                    packed.append(c)
                    continue
                bf.add(c.sid)
                by_sid.setdefault(c.sid, []).append(c)
            self._sid_bloom[mst] = bf
            self._sid_chunks[mst] = by_sid
            self._packed_chunks[mst] = packed

    def close(self) -> None:
        self._f.close()

    def measurements(self) -> list[str]:
        return list(self.meta)

    def schema(self, measurement: str) -> dict[str, FieldType]:
        entry = self.meta.get(measurement)
        return entry[0] if entry else {}

    def packed_count(self, measurement: str) -> int:
        """How many packed chunks the file holds of the measurement."""
        return len(self._packed_chunks.get(measurement, ()))

    def chunks(
        self,
        measurement: str,
        sids: set[int] | None = None,
        tmin: int | None = None,
        tmax: int | None = None,
    ) -> list[ChunkMeta]:
        """Chunk metas matching series + time range (tmax exclusive) —
        the block-skip step (reference location.go / pre-agg pruning)."""
        entry = self.meta.get(measurement)
        if entry is None:
            return []
        packed = self._packed_chunks.get(measurement, ())
        if sids is not None and len(sids) == 1:
            sid = next(iter(sids))
            bf = self._sid_bloom.get(measurement)
            if bf is not None and sid not in bf:
                cand = ()
            else:
                cand = self._sid_chunks.get(measurement, {}).get(sid, ())
        else:
            cand = entry[1]
        out = []
        for c in cand:
            if c.packed:
                continue  # appended below with the sid-span filter
            if sids is not None and c.sid not in sids:
                continue
            if tmin is not None and c.tmax < tmin:
                continue
            if tmax is not None and c.tmin >= tmax:
                continue
            out.append(c)
        for c in packed:
            if sids is not None and not any(
                    c.smin <= s_ <= c.smax for s_ in sids):
                continue
            if tmin is not None and c.tmax < tmin:
                continue
            if tmax is not None and c.tmin >= tmax:
                continue
            out.append(c)
        return out

    def _read(self, loc: tuple[int, int]) -> bytes:
        # positioned read: concurrent query threads share this fd, and an
        # interleaved seek+read pair would decode bytes from the wrong
        # offset (and the column cache would then serve the garbage forever)
        buf = os.pread(self._f.fileno(), loc[1], loc[0])
        if diskfault.armed():
            buf = diskfault.on_read(self.path, buf, site="tsf-block-read")
        if len(buf) != loc[1]:
            # a short pread means the media lost the block's tail (file
            # truncated under us): surface it, never decode a prefix
            raise CorruptFile(
                self.path,
                f"short read at {loc[0]}: {len(buf)}/{loc[1]} bytes")
        if not self.block_crc:
            return buf  # legacy revision-1 file: no seal to verify
        payload, seal = buf[:-_BLOCK_CRC.size], buf[-_BLOCK_CRC.size:]
        if zlib.crc32(payload) != _BLOCK_CRC.unpack(seal)[0]:
            raise CorruptFile(
                self.path, f"block crc mismatch at offset {loc[0]}")
        return payload

    # decoded-column caching (reference: lib/readcache — hot chunks
    # decode once, not per query). Safe because TSF files are immutable
    # and no read path mutates decoded arrays in place. Two regimes:
    # with the process-global decoded-column cache enabled
    # (storage/colcache.py, OGT_COLCACHE_MB > 0) columns live there,
    # keyed (shard, file generation, chunk, series, field) with explicit
    # invalidation at every file-set swap; with it disabled, the original
    # per-open-file byte-budgeted LRU below serves bit-identically. Bulk
    # one-pass scans (compaction, downsample, export) bypass BOTH
    # (cache=False) so soon-to-be-retired readers never pin decoded
    # arrays.
    _CACHE_BYTES = 16 << 20  # decoded-bytes budget per open file

    @staticmethod
    def _val_nbytes(val) -> int:
        if isinstance(val, Column):
            return int(val.values.nbytes if hasattr(val.values, "nbytes")
                       else len(val.values) * 64) + int(val.valid.nbytes)
        return int(getattr(val, "nbytes", 64))

    def _colcache_key(self, chunk: ChunkMeta, name):
        # (shard id, file generation, chunk id, series, field): the sid
        # is the chunk's own for per-series chunks, None for packed
        # multi-series chunks (whose columns cache whole; per-sid slicing
        # is a cheap binary search over the cached arrays)
        return (self.owner_ns, self.gen, id(chunk), chunk.sid, name)

    def _cache_get(self, chunk: ChunkMeta, name):
        """Counted decode-once lookup of one column of one chunk, in
        whichever cache serves (see above): `name` is the field name,
        None for the time column, _SIDS for a packed chunk's sid column."""
        cc = colcache.GLOBAL
        if cc.enabled():
            return cc.get(self._colcache_key(chunk, name))
        key = (id(chunk), name)
        with self._cache_lock:
            got = self._col_cache.get(key)
            if got is not None:
                self._col_cache.move_to_end(key)
            return got

    def _cache_put(self, chunk: ChunkMeta, name, val) -> None:
        cc = colcache.GLOBAL
        if cc.enabled():
            cc.put(self._colcache_key(chunk, name), val)
            return
        nb = self._val_nbytes(val)
        if nb > self._CACHE_BYTES:
            return  # a single oversized column never enters the cache
        key = (id(chunk), name)
        with self._cache_lock:
            if key not in self._col_cache:
                self._col_cache[key] = val
                self._cache_bytes += nb
            self._col_cache.move_to_end(key)
            while self._cache_bytes > self._CACHE_BYTES and self._col_cache:
                _k, old = self._col_cache.popitem(last=False)
                self._cache_bytes -= self._val_nbytes(old)

    def _load_columns(self, chunk: ChunkMeta, wanted, cache: bool) -> dict:
        """{name: decoded column} for `wanted`, a list of (name, block
        locs, codec) in output order; a loc of None reads as b"".  What
        the cache holds comes from it.  The rest is the miss path, in
        three stages that each take all the chunk's missing columns, so
        that a chunk costs three spans and two counter updates however
        many columns it has: `block_read` (pread + CRC), `codec`,
        `colcache_fill` (the puts and the evictions they cause).  On a
        scan-pool thread these spans have no parent frame: they sum, over
        the workers, to CPU time beside the dispatcher's `decode`."""
        out = {name: (self._cache_get(chunk, name) if cache else None)
               for name, _locs, _codec in wanted}
        todo = [w for w in wanted if out[w[0]] is None]
        if not todo:
            return out
        with tracing.span("block_read"):
            bufs = [[self._read(loc) if loc else b"" for loc in locs]
                    for _name, locs, _codec in todo]
        read = [loc[1] for _name, locs, _codec in todo for loc in locs if loc]
        with tracing.span("codec"):
            for (name, _locs, codec), blocks in zip(todo, bufs):
                out[name] = codec(*blocks)
        if cache:
            with tracing.span("colcache_fill"):
                for name, _locs, _codec in todo:
                    self._cache_put(chunk, name, out[name])
        _STATS.add("tsf", (("read_bytes", sum(read)),
                           ("blocks_read", len(read))))
        _STATS.incr("scan", "decoded_bytes",
                    sum(self._val_nbytes(out[w[0]]) for w in todo))
        return out

    def _chunk_columns(
        self, measurement: str, chunk: ChunkMeta, fields: list[str] | None,
        cache: bool, with_sids: bool,
    ) -> tuple[np.ndarray | None, Record]:
        """(sid column or None, record) of one chunk: one `_load_columns`
        for the times, the sids where asked and every field."""
        schema = self.schema(measurement)
        wanted = [(None, (chunk.time_loc,), encoding.decode_ints)]
        if with_sids:
            wanted.append((_SIDS, (chunk.sid_loc,), encoding.decode_ints))
        for name in (fields if fields is not None else list(chunk.cols)):
            loc = chunk.cols.get(name)
            if loc is not None:
                wanted.append((name, (loc["v"], loc["m"]), functools.partial(
                    encoding.decode_column, schema[name])))
        cols = self._load_columns(chunk, wanted, cache)
        times = cols.pop(None)
        return cols.pop(_SIDS, None), Record(times, cols)

    def read_chunk(
        self, measurement: str, chunk: ChunkMeta,
        fields: list[str] | None = None, cache: bool = True,
    ) -> Record:
        return self._chunk_columns(measurement, chunk, fields, cache,
                                   with_sids=False)[1]

    def _chunk_from_cache(self, chunk: ChunkMeta,
                          fields: list[str] | None) -> Record | None:
        """The consult-before-dispatch fast path: assemble a chunk Record
        purely from already-cached columns, or None on ANY miss (the
        caller then decodes through the scan pool, whose in-flight-bytes
        backpressure keeps bounding memory). No IO, no decode."""
        import time as _time

        cc = colcache.GLOBAL
        if not cc.enabled():
            return None
        t0 = _time.perf_counter_ns()
        times = cc.peek(self._colcache_key(chunk, None))
        if times is None:
            return None
        cols = {}
        names = fields if fields is not None else list(chunk.cols)
        for name in names:
            if name not in chunk.cols:
                continue
            col = cc.peek(self._colcache_key(chunk, name))
            if col is None:
                return None
            cols[name] = col
        cc.count_peek(1 + len(cols), _time.perf_counter_ns() - t0)
        return Record(times, cols)

    def read_chunk_if_cached(
        self, measurement: str, chunk: ChunkMeta,
        fields: list[str] | None = None,
    ) -> Record | None:
        return self._chunk_from_cache(chunk, fields)


    # -- packed (PK-sorted column store) reads ------------------------------

    def read_packed_sids(self, chunk: ChunkMeta, cache: bool = True) -> np.ndarray:
        """The sid column of a packed chunk (non-decreasing int64)."""
        return self._load_columns(
            chunk, [(_SIDS, (chunk.sid_loc,), encoding.decode_ints)],
            cache)[_SIDS]

    @staticmethod
    def _sid_row_range(chunk: ChunkMeta, sids: np.ndarray,
                       sid: int) -> tuple[int, int]:
        """[lo, hi) row window of one sid inside a packed chunk: the
        sparse PK index bounds the candidates, an exact binary search on
        the sid column finds the run."""
        import bisect

        sp = chunk.sparse or []
        entry_sids = [e[0] for e in sp]
        j = bisect.bisect_left(entry_sids, sid)
        w_lo = sp[j - 1][1] if j > 0 else 0
        k = bisect.bisect_right(entry_sids, sid)
        w_hi = sp[k][1] if k < len(sp) else chunk.rows
        win = sids[w_lo:w_hi]
        lo = w_lo + int(np.searchsorted(win, sid, "left"))
        hi = w_lo + int(np.searchsorted(win, sid, "right"))
        return lo, hi

    @staticmethod
    def _slice_rows(rec: Record, lo: int, hi: int) -> Record:
        """Row window [lo, hi) of a chunk record, as views."""
        cols = {name: Column(col.ftype, col.values[lo:hi], col.valid[lo:hi])
                for name, col in rec.columns.items()}
        return Record(rec.times[lo:hi], cols)

    def read_packed_sid(
        self, measurement: str, chunk: ChunkMeta, sid: int,
        fields: list[str] | None = None, cache: bool = True,
    ) -> Record:
        """One series' rows out of a packed chunk: the sparse PK index
        bounds the candidate row window (and rejects out-of-span sids
        without touching data), then an exact binary search on the
        (cached) sid column finds the rows — the hybrid store reader
        (reference engine/immutable/colstore reader +
        sparseindex/primary_index.go)."""
        if sid < chunk.smin or sid > chunk.smax:
            return Record(np.empty(0, np.int64), {})
        sids = self.read_packed_sids(chunk, cache)
        lo, hi = self._sid_row_range(chunk, sids, sid)
        if lo == hi:
            return Record(np.empty(0, np.int64), {})
        rec = self.read_chunk(measurement, chunk, fields, cache)
        return self._slice_rows(rec, lo, hi)

    def read_packed_sid_if_cached(
        self, measurement: str, chunk: ChunkMeta, sid: int,
        fields: list[str] | None = None,
    ) -> Record | None:
        """read_packed_sid served purely from cached columns, or None on
        any miss.  Out-of-span sids answer the empty record directly (no
        decode would have happened either way)."""
        if sid < chunk.smin or sid > chunk.smax:
            return Record(np.empty(0, np.int64), {})
        cc = colcache.GLOBAL
        if not cc.enabled():
            return None
        sids = cc.peek(self._colcache_key(chunk, _SIDS))
        if sids is None:
            return None
        lo, hi = self._sid_row_range(chunk, sids, sid)
        if lo == hi:
            cc.count_peek(1)
            return Record(np.empty(0, np.int64), {})
        rec = self._chunk_from_cache(chunk, fields)
        if rec is None:
            return None
        cc.count_peek(1)  # the sid-column peek on top of the record's
        return self._slice_rows(rec, lo, hi)

    def read_packed_bulk(
        self, measurement: str, chunk: ChunkMeta,
        fields: list[str] | None = None,
        sid_filter: np.ndarray | None = None, cache: bool = True,
    ) -> tuple[np.ndarray, Record]:
        """(sids, record) of a packed chunk in ONE decode; when
        `sid_filter` (sorted int64 array) is given, rows are masked to
        those series — the batched multi-series scan that replaces
        per-sid Python loops at high cardinality."""
        sids, rec = self._chunk_columns(measurement, chunk, fields, cache,
                                        with_sids=True)
        return self._packed_bulk_filter(sids, rec, sid_filter)

    @staticmethod
    def _packed_bulk_filter(sids, rec, sid_filter):
        if sid_filter is None:
            return sids, rec
        keep = np.isin(sids, sid_filter)
        if keep.all():
            return sids, rec
        return sids[keep], Record(
            rec.times[keep],
            {
                name: Column(col.ftype, col.values[keep], col.valid[keep])
                for name, col in rec.columns.items()
            },
        )

    # -- integrity scrub surface (services/scrub.py) ------------------------

    def data_locs(self) -> list[tuple[int, int]]:
        """Every data-block (off, len) of this file in a stable order —
        the scrub service's work list.  Pure metadata walk, no IO."""
        out: list[tuple[int, int]] = []
        for mst in sorted(self.meta):
            for c in self.meta[mst][1]:
                out.append(c.time_loc)
                if c.sid_loc:
                    out.append(c.sid_loc)
                for name in sorted(c.cols):
                    cc = c.cols[name]
                    out.append(cc["v"])
                    if cc["m"]:
                        out.append(cc["m"])
        return out

    def verify_block(self, loc: tuple[int, int]) -> int:
        """Read + CRC-verify one block WITHOUT decoding or caching it;
        returns bytes read.  Raises CorruptFile on any mismatch."""
        self._read(loc)
        return loc[1]

    def read_packed_bulk_if_cached(
        self, measurement: str, chunk: ChunkMeta,
        fields: list[str] | None = None,
        sid_filter: np.ndarray | None = None,
    ) -> tuple[np.ndarray, Record] | None:
        """read_packed_bulk served purely from cached columns, or None on
        any miss (the sid filter is applied per call — cached columns
        stay whole so every sid set shares one entry)."""
        cc = colcache.GLOBAL
        if not cc.enabled():
            return None
        sids = cc.peek(self._colcache_key(chunk, _SIDS))
        if sids is None:
            return None
        rec = self._chunk_from_cache(chunk, fields)
        if rec is None:
            return None
        cc.count_peek(1)
        return self._packed_bulk_filter(sids, rec, sid_filter)


class CorruptFile(Exception):
    """Media-level damage detected in a TSF file (bad magic/trailer,
    meta CRC mismatch, short block read, block CRC mismatch).  Carries
    the path so the shard's read paths can QUARANTINE the file — the
    error taxonomy's boundary between "this query failed" and "this
    file is damaged" (storage/shard.py quarantine)."""

    def __init__(self, path: str, why: str):
        super().__init__(f"corrupt TSF file {path}: {why}")
        self.path = path
        self.why = why

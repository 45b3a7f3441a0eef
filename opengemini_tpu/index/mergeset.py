"""ctypes binding for the C++ mergeset series index
(native/seriesindex.cpp) — the high-cardinality replacement for the
dict-based SeriesIndex, same API.

Role of the reference's tsi mergeset index
(engine/index/tsi/mergeset_index.go over lib/util/lifted/vm/mergeset):
sorted immutable posting runs on disk (mmap, binary search) + a
WAL-backed memtable, merged inline — million-series indexes open in
seconds with bounded RSS instead of rebuilding Python dicts from a JSON
log. Regex matching stays in Python (re semantics) over the C-side
distinct tag-value enumeration; everything exact runs native.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import re
import struct
import threading
from opengemini_tpu.utils import lockdep

import numpy as np

from opengemini_tpu.ingest.line_protocol import series_key

_LIB = None
_TRIED = False


def load():
    """The series-index library or None (native.open_library builds a
    missing one and rebuilds a stale one; the reason it did not load is
    in native.report())."""
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        from opengemini_tpu import native

        _LIB = native.open_library("seriesindex", _bind)
    return _LIB


def _bind(lib) -> None:
    u64 = ctypes.c_uint64
    p = ctypes.c_void_p
    cp = ctypes.c_char_p
    u64p = ctypes.POINTER(u64)
    for name, res, args in [
        ("msi_open", p, [cp]),
        ("msi_close", None, [p]),
        ("msi_free", None, [p]),
        ("msi_insert", u64, [p, cp, u64, u64]),
        ("msi_insert_keys", u64, [p, cp, u64, u64, u64p]),
        ("msi_lookup", u64, [p, cp, u64]),
        ("msi_has_live", ctypes.c_int, [p, cp, u64]),
        ("msi_series_ids", p, [p, cp, u64, u64p]),
        ("msi_match_eq", p, [p, cp, u64, cp, u64, cp, u64, u64p]),
        ("msi_enum_field", p, [p, ctypes.c_char, cp, u64,
                               ctypes.c_uint32, u64p, u64p]),
        ("msi_key_of", p, [p, u64, u64p]),
        ("msi_keys_of", p, [p, u64p, u64, u64p]),
        ("msi_remove_sids", None, [p, u64p, u64]),
        ("msi_flush", None, [p]),
        ("msi_compact", None, [p]),
        ("msi_stats", None, [p, u64p, u64p, u64p, u64p]),
    ]:
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = args


def _field(b: bytes) -> bytes:
    return struct.pack("<I", len(b)) + b


def _pack_series(key: str, mst: str, tags: tuple) -> bytes:
    out = [_field(key.encode()), _field(mst.encode()),
           struct.pack("<I", len(tags))]
    for k, v in tags:
        out.append(_field(k.encode()))
        out.append(_field(v.encode()))
    return b"".join(out)


def _unpack_series(blob: bytes):
    off = 0

    def field():
        nonlocal off
        (n,) = struct.unpack_from("<I", blob, off)
        off += 4
        f = blob[off : off + n]
        off += n
        return f

    key = field().decode()
    mst = field().decode()
    (ntags,) = struct.unpack_from("<I", blob, off)
    off += 4
    tags = tuple(
        (field().decode(), field().decode()) for _ in range(ntags)
    )
    return key, mst, tags


_TAGS_CACHE_MAX = 200_000


class MergesetIndex:
    """Drop-in for index.inverted.SeriesIndex backed by the native
    mergeset engine. `path` is a DIRECTORY (runs + wal live inside)."""

    def __init__(self, path: str):
        lib = load()
        if lib is None:
            raise OSError("native series index library unavailable")
        self._lib = lib
        self.path = path
        os.makedirs(path, exist_ok=True)
        self._h = lib.msi_open(path.encode())
        if not self._h:
            raise OSError(f"msi_open failed for {path!r}")
        self._lock = lockdep.RLock()
        # sid -> (mst, tags): bounded decode cache for the render path
        self._tags_cache: dict[int, tuple] = {}
        # series key -> sid: the ingest hot path is overwhelmingly repeat
        # series; skip the native call for those
        self._key_cache: dict[str, int] = {}
        # label-engine invalidation protocol: per-measurement insert
        # generation + index-wide removal epoch (index.labels snapshots
        # and the tag_values cache key off label_gen())
        self._label_gens: dict[str, int] = {}
        self._label_epoch = 0
        # (measurement, key) -> (label_gen, sorted values)
        self._tagvals_cache: dict[tuple, tuple] = {}

    @contextlib.contextmanager
    def _native(self):
        """Serialized access to the live native handle. A closed index
        raises a clean OSError; holding the (reentrant) lock for the
        call's duration means a racing close() can never free the handle
        under a reader (use-after-free -> process crash)."""
        with self._lock:
            if not self._h:
                raise OSError("series index is closed")
            yield self._h

    def label_gen(self, measurement: str) -> tuple:
        return (self._label_epoch, self._label_gens.get(measurement, 0))

    def _label_bump(self, measurement: str) -> None:
        self._label_gens[measurement] = \
            self._label_gens.get(measurement, 0) + 1

    # -- write side ---------------------------------------------------------

    def get_or_create(self, measurement: str, tags: tuple) -> int:
        key = series_key(measurement, tags)
        sid = self._key_cache.get(key)
        if sid is not None:
            return sid
        return self._insert_series(key, measurement, tags)

    def get_or_create_by_key(self, key: str) -> int:
        """Canonical-key ingest path (native parser output); repeat series
        never reconstruct tags."""
        sid = self._key_cache.get(key)
        if sid is not None:
            return sid
        from opengemini_tpu.index.inverted import parse_series_key

        measurement, tags = parse_series_key(key)
        return self._insert_series(key, measurement, tags)

    def _insert_series(self, key: str, measurement: str, tags: tuple) -> int:
        blob = _pack_series(key, measurement, tags)
        with self._native() as h:
            sid = int(self._lib.msi_insert(h, blob, len(blob), 0))
        self._label_bump(measurement)
        if len(self._key_cache) >= _TAGS_CACHE_MAX:
            self._key_cache.clear()
        self._key_cache[key] = sid
        return sid

    def get_or_create_bulk(self, keys: list[str]) -> list[int]:
        """Batched canonical-key ingest: ONE native call parses and
        inserts every escape-free new key (the per-key Python parse +
        pack + ctypes crossing dominated 1M-series ingest). Keys with
        backslash escapes keep the exact per-key path."""
        out = [0] * len(keys)
        plain_i: list[int] = []
        parts: list[bytes] = []
        cache = self._key_cache
        for i, key in enumerate(keys):
            sid = cache.get(key)
            if sid is not None:
                out[i] = sid
            elif "\\" in key:
                out[i] = self.get_or_create_by_key(key)
            else:
                kb = key.encode()
                parts.append(struct.pack("<I", len(kb)) + kb)
                plain_i.append(i)
        if plain_i:
            if len(cache) + len(plain_i) >= _TAGS_CACHE_MAX:
                cache.clear()
            # chunked native calls: one giant batch would hold the index
            # mutex for the whole 1M-series insert and stall every
            # concurrent reader (lookup/match share the same lock)
            CHUNK = 32_768
            for lo in range(0, len(plain_i), CHUNK):
                idxs = plain_i[lo:lo + CHUNK]
                blob = b"".join(parts[lo:lo + CHUNK])
                sids = (ctypes.c_uint64 * len(idxs))()
                with self._native() as h:
                    done = int(self._lib.msi_insert_keys(
                        h, blob, len(blob), len(idxs), sids))
                if done != len(idxs):
                    raise OSError("series index batch insert failed")
                for i, sid in zip(idxs, sids):
                    out[i] = int(sid)
                    cache[keys[i]] = int(sid)
                    # plain keys carry no escapes, so the measurement is
                    # exactly the prefix before the first comma
                    self._label_bump(keys[i].split(",", 1)[0])
        return out

    def flush(self) -> None:
        with self._native() as h:
            self._lib.msi_flush(h)

    def compact(self) -> None:
        with self._native() as h:
            self._lib.msi_compact(h)

    def close(self) -> None:
        with self._lock:
            if self._h:
                self._lib.msi_close(self._h)
                self._h = None

    # -- read side ----------------------------------------------------------

    def _sid_buf(self, ptr, n: int) -> set[int]:
        try:
            if not n:
                return set()
            raw = ctypes.string_at(ptr, n * 8)
            return set(np.frombuffer(raw, "<u8").tolist())
        finally:
            self._lib.msi_free(ptr)

    def series_ids(self, measurement: str) -> set[int]:
        m = measurement.encode()
        n = ctypes.c_uint64()
        with self._native() as h:
            ptr = self._lib.msi_series_ids(h, m, len(m), ctypes.byref(n))
        return self._sid_buf(ptr, int(n.value))

    def _match_eq_raw(self, measurement: str, key: str,
                      value: str) -> set[int]:
        m, k, v = measurement.encode(), key.encode(), value.encode()
        n = ctypes.c_uint64()
        with self._native() as h:
            ptr = self._lib.msi_match_eq(
                h, m, len(m), k, len(k), v, len(v), ctypes.byref(n))
        return self._sid_buf(ptr, int(n.value))

    def _with_key(self, measurement: str, key: str) -> set[int]:
        """Series carrying the tag key at all (any value — including an
        EXPLICIT empty value, hence the raw match: the ''-special
        match_eq would recurse). Only empty-value match paths pay
        this union."""
        out: set[int] = set()
        for v in self.tag_values(measurement, key):
            out |= self._match_eq_raw(measurement, key, v)
        return out

    def _match_eq_walk(self, measurement: str, key: str,
                       value: str) -> set[int]:
        """The pre-tier mergeset walk — the oracle the columnar tier is
        fuzzed against (tests/test_labels.py)."""
        if value == "":
            # influx: a missing tag equals the empty string; an explicit
            # '' value stored in the index matches too (raw lookup)
            return (self.series_ids(measurement)
                    - self._with_key(measurement, key)) | \
                self._match_eq_raw(measurement, key, "")
        return self._match_eq_raw(measurement, key, value)

    def _match_neq_walk(self, measurement: str, key: str,
                        value: str) -> set[int]:
        return self.series_ids(measurement) - self._match_eq_walk(
            measurement, key, value)

    def _tier_match(self, op: str, measurement: str, key: str,
                    value: str) -> set[int] | None:
        """Columnar-tier answer as a set (the index API's type), or None
        when the tier is knob-disabled."""
        from opengemini_tpu.index import labels

        tier = labels.tier_for(self)
        if tier is None:
            return None
        arr = labels.match_tier(tier.snapshot(measurement), op, key, value)
        return None if arr is None else set(arr.tolist())

    def match_eq(self, measurement: str, key: str, value: str) -> set[int]:
        if value == "":
            # the empty-value walk pays one cgo match_eq per distinct
            # value (_with_key) — one posting-tier mask replaces it
            got = self._tier_match("=", measurement, key, value)
            if got is not None:
                return got
        return self._match_eq_walk(measurement, key, value)

    def match_neq(self, measurement: str, key: str, value: str) -> set[int]:
        # the walk rebuilds the full series_ids set to subtract from
        got = self._tier_match("!=", measurement, key, value)
        if got is not None:
            return got
        return self._match_neq_walk(measurement, key, value)

    def _enum(self, kind: bytes, pfx: bytes, idx: int) -> list[str]:
        n = ctypes.c_uint64()
        blen = ctypes.c_uint64()
        with self._native() as h:
            ptr = self._lib.msi_enum_field(
                h, kind, pfx, len(pfx), idx, ctypes.byref(n),
                ctypes.byref(blen))
        try:
            raw = ctypes.string_at(ptr, blen.value)
        finally:
            self._lib.msi_free(ptr)
        out = []
        off = 0
        for _ in range(n.value):
            (ln,) = struct.unpack_from("<I", raw, off)
            off += 4
            out.append(raw[off : off + ln].decode())
            off += ln
        return out

    def tag_keys(self, measurement: str) -> list[str]:
        return sorted(self._enum(b"P", _field(measurement.encode()), 1))

    _TAGVALS_CACHE_MAX = 4096

    def tag_values(self, measurement: str, key: str) -> list[str]:
        # generation-keyed cache: match_regex re-enumerated (and
        # re-sorted) the whole value list through cgo on EVERY call —
        # twice per query for empty-matching selectors. Callers get the
        # cached list itself; the meta/match paths never mutate it.
        gen = self.label_gen(measurement)
        got = self._tagvals_cache.get((measurement, key))
        if got is not None and got[0] == gen:
            return got[1]
        pfx = _field(measurement.encode()) + _field(key.encode())
        vals = sorted(self._enum(b"P", pfx, 2))
        if len(self._tagvals_cache) >= self._TAGVALS_CACHE_MAX:
            self._tagvals_cache.clear()
        self._tagvals_cache[(measurement, key)] = (gen, vals)
        return vals

    def match_regex(self, measurement: str, key: str, pattern: str,
                    negate: bool = False) -> set[int]:
        got = self._tier_match("!~" if negate else "=~",
                               measurement, key, pattern)
        if got is not None:
            return got
        return self._match_regex_walk(measurement, key, pattern, negate)

    def _match_regex_walk(self, measurement: str, key: str, pattern: str,
                          negate: bool = False) -> set[int]:
        rx = re.compile(pattern)
        hit: set[int] = set()
        empty_matches = bool(rx.search(""))  # missing tag is "" (influx)
        with_key: set[int] = set()
        for v in self.tag_values(measurement, key):
            if rx.search(v):
                got = self._match_eq_raw(measurement, key, v)
                hit |= got
                if empty_matches:
                    with_key |= got
            elif empty_matches:
                with_key |= self._match_eq_raw(measurement, key, v)
        if empty_matches:
            hit |= self.series_ids(measurement) - with_key
        if negate:
            return self.series_ids(measurement) - hit
        return hit

    def tags_of(self, sid: int) -> dict[str, str]:
        got = self._tags_cache.get(sid)
        if got is None:
            n = ctypes.c_uint64()
            with self._native() as h:
                ptr = self._lib.msi_key_of(h, sid, ctypes.byref(n))
            try:
                raw = ctypes.string_at(ptr, n.value)
            finally:
                self._lib.msi_free(ptr)
            if not raw:
                raise KeyError(sid)
            _key, mst, tags = _unpack_series(raw)
            if len(self._tags_cache) >= _TAGS_CACHE_MAX:
                self._tags_cache.clear()
            got = self._tags_cache[sid] = (mst, tags)
        return dict(got[1])

    def series_entry(self, sid: int) -> tuple[str, tuple]:
        self.tags_of(sid)  # populate the cache
        mst, tags = self._tags_cache[sid]
        return mst, tags

    def entries_bulk(self, sids,
                     cache: bool = True) -> list[tuple[str, tuple] | None]:
        """Batch series_entry: ONE native call for all sids (the per-sid
        ctypes round-trip dominates high-cardinality label assembly).
        Missing sids yield None. ``cache=False`` skips populating the
        shared tags cache — million-row label-tier builds must not evict
        the render path's working set (or balloon it past the bound)."""
        import numpy as _np

        sids = [int(s) for s in _np.asarray(sids, dtype=_np.uint64).tolist()]
        # results assemble into a local map FIRST: evicting the shared
        # cache must never drop answers for already-cached sids in this
        # very request
        local = {s: self._tags_cache[s] for s in sids if s in self._tags_cache}
        missing = [s for s in sids if s not in local]
        if missing:
            arr = (ctypes.c_uint64 * len(missing))(*missing)
            n = ctypes.c_uint64()
            with self._native() as h:
                ptr = self._lib.msi_keys_of(h, arr, len(missing), ctypes.byref(n))
            try:
                raw = ctypes.string_at(ptr, n.value)
            finally:
                self._lib.msi_free(ptr)
            off = 0
            for sid in missing:
                (ln,) = struct.unpack_from("<I", raw, off)
                off += 4
                if ln:
                    _key, mst, tags = _unpack_series(raw[off:off + ln])
                    local[sid] = (mst, tags)
                off += ln
            if cache:
                if len(self._tags_cache) + len(missing) >= _TAGS_CACHE_MAX:
                    self._tags_cache.clear()
                self._tags_cache.update(local)
        return [local.get(s) for s in sids]

    def iter_series_entries(self):
        for m in self.measurements():
            for sid in sorted(self.series_ids(m)):
                yield self.series_entry(sid)

    def measurements(self) -> list[str]:
        # a measurement whose every series was removed must not list:
        # membership postings are tombstone-filtered, 'M' items are not.
        # msi_has_live early-exits — never decodes whole posting sets
        out = []
        for m in self._enum(b"M", b"", 0):
            mb = m.encode()
            with self._native() as h:
                if self._lib.msi_has_live(h, mb, len(mb)):
                    out.append(m)
        return sorted(out)

    # -- deletion ------------------------------------------------------------

    def remove_sids(self, sids: set[int]) -> None:
        if not sids:
            return
        arr = (ctypes.c_uint64 * len(sids))(*sorted(sids))
        with self._native() as h:
            self._lib.msi_remove_sids(h, arr, len(sids))
        for sid in sids:
            self._tags_cache.pop(sid, None)
        self._key_cache.clear()  # deletes are rare; a full drop is fine
        # removals don't know their measurements: the index-wide epoch
        # invalidates every label-tier snapshot and tag_values entry
        self._label_epoch += 1
        self._tagvals_cache.clear()

    def stats(self) -> dict:
        a, b, c, d = (ctypes.c_uint64() for _ in range(4))
        with self._native() as h:
            self._lib.msi_stats(h, *(ctypes.byref(x) for x in (a, b, c, d)))
        return {"mem_items": a.value, "runs": b.value,
                "run_items": c.value, "next_sid": d.value}


def open_series_index(shard_path: str):
    """Index factory for a shard directory: the native mergeset engine
    when available, migrating any legacy series.log once; the dict
    SeriesIndex otherwise."""
    from opengemini_tpu.index.inverted import SeriesIndex

    legacy_log = os.path.join(shard_path, "series.log")
    msi_dir = os.path.join(shard_path, "seriesidx")
    if load() is None:
        if os.path.isdir(msi_dir) and os.listdir(msi_dir):
            # the shard's series live ONLY in the mergeset dir: a silent
            # dict fallback would restart sid numbering at 1 and alias
            # unrelated series onto existing TSF chunks
            raise OSError(
                f"native series index library unavailable but {msi_dir!r} "
                "holds this shard's index — rebuild native/ (make -C native)"
            )
        return SeriesIndex(legacy_log)
    idx = MergesetIndex(msi_dir)
    if os.path.exists(legacy_log):
        legacy = SeriesIndex(legacy_log)
        for sid, (mst, tags) in sorted(legacy.sid_to_series.items()):
            blob = _pack_series(series_key(mst, tags), mst, tags)
            idx._lib.msi_insert(idx._h, blob, len(blob), sid)
        legacy.close()
        idx.compact()
        idx.flush()
        os.replace(legacy_log, legacy_log + ".migrated")
    return idx

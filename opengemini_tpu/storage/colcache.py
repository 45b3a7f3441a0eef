"""Decoded-column cache for immutable shard chunks (host + device tiers).

Every hot read decodes TSSP/TSF chunks into columnar batches; PR 1
parallelized that decode (storage/scanpool.py) but a warm repeated query
still pays the full decode — and the host->device transfer — for data
that has not changed.  Flushed chunks are immutable until a compaction /
downsample / delete rewrites them, which is exactly the invariant a
decoded cache needs.  This module keeps hot chunks resident in DECODED
form near the compute (the "cache decompressed data on the device" move
of GPU-accelerated SQL-on-compressed-data systems, arxiv 2506.10092, and
the near-compute buffering of Taurus NDP, arxiv 2506.20010; reference
analogue: lib/readcache, per-file there, process-global here).

Two tiers, one byte-budgeted LRU each:

  host tier    decoded numpy column arrays, keyed by
               (shard id, file generation, chunk id, series, field).
               File generations are drawn from a process-global counter
               at TSFReader open, so a compaction that rewrites a file
               IN PLACE (os.replace, same path) can never alias a stale
               entry — the new reader carries a new generation.  Misses
               fill through the scan pool (storage/scanpool.py), so the
               in-flight-bytes backpressure still bounds memory.

  device tier  the padded `jax.device_put` grid buffers GridBatch
               (models/grid.py) builds for GROUP BY time() scans, keyed
               by a scan signature that embeds every shard's
               (path, data_version) — the same logical-content version
               the incremental result cache trusts (bumped by
               writes/deletes/rewrites, NOT by flush/compact, which
               change layout only; the merged read is bit-identical
               across layouts by construction).  A repeated identical
               scan skips decode (host tier) AND H2D (device tier).

Invalidation — every mutation of chunk identity:
  flush                adds a new file (new generation); existing chunks
                       are untouched, so nothing can go stale — the next
                       read simply decodes (and caches) the new chunks
  compact / downsample
  / delete rewrite     retired readers' generations are invalidated at
                       the file-set swap (shard._retire_files and
                       _compact_offlock)
  retention drop,
  shard close/offload  Shard.close / Engine.offload_shard invalidate the
                       generations of every open file
Device-tier entries need no explicit invalidation: their keys embed the
shard data_versions, so any content change keys a different entry and
the stale one ages out of the LRU.  Entries additionally record the
device MESH they were sharded for (multi-chip execution,
parallel/runtime.py): under a mesh the cold scan device_puts the padded
grid straight into the sharded layout (one transfer, no replicated
intermediate), warm scans reuse the sharded buffers with zero
transfers, and a runtime.set_mesh() change reshards retained entries
device-to-device with the stale buffers donated
(parallel/distributed.py donate_reshard) instead of holding both
layouts.

Knobs (documented in README.md):
  OGT_COLCACHE_MB         host-tier decoded-bytes budget (0 disables the
                          whole subsystem; the per-file 16MB reader LRU
                          then serves exactly as before — bit-identical)
  OGT_COLCACHE_DEVICE=1   enable the device tier
  OGT_COLCACHE_DEVICE_MB  device-tier budget (default: OGT_COLCACHE_MB)

Counters (utils/stats.py, module "colcache"): hits, misses, fills,
evictions, invalidations, bytes, device_hits, device_misses,
device_bytes, time_ns.  Per-query cache time is also attributed to the
running query (utils/querytracker.py stages) and surfaced as the
executor's `colcache` trace span.
"""

from __future__ import annotations

import os
import threading
from opengemini_tpu.utils import lockdep
import time
from collections import OrderedDict

from opengemini_tpu.utils import devobs, tracing
from opengemini_tpu.utils.governor import _env_int
from opengemini_tpu.utils.stats import GLOBAL as _STATS

_DEFAULT_MB = 256


def _nbytes(val) -> int:
    """Decoded size of a cached value: a Record Column or a bare array.
    Mirrors TSFReader._val_nbytes so both caches account alike (object
    dtype — strings — estimates 64 bytes/element)."""
    vals = getattr(val, "values", None)
    if vals is not None:  # Column
        if getattr(vals, "dtype", None) is not None and vals.dtype == object:
            nb = len(vals) * 64
        else:
            nb = int(getattr(vals, "nbytes", len(vals) * 64))
        return nb + int(val.valid.nbytes)
    return int(getattr(val, "nbytes", 64))


class ColumnCache:
    """Thread-safe two-tier LRU of decoded chunk columns.

    Host keys: (shard id, file generation, chunk id, series, field) —
    generation at index 1 (the invalidation handle).  Values are whatever
    the reader decoded (numpy time/sid arrays, record Columns); they are
    IMMUTABLE by the read-path contract (no caller mutates decoded
    arrays in place), so entries are shared across queries without
    copies, and an invalidation only drops the cache's reference — a
    reader mid-scan keeps its arrays alive through normal refcounting.
    """

    def __init__(self, budget_mb: int | None = None,
                 device: bool | None = None,
                 device_budget_mb: int | None = None):
        self._lock = lockdep.Lock()
        # serializes device-tier relayouts: donation deletes the source
        # buffers, so two threads chasing the same mesh swap must never
        # both donate one entry's arrays (device compute stays OFF the
        # main cache lock)
        self._reshard_lock = lockdep.Lock()
        self._host: OrderedDict = OrderedDict()  # key -> (value, nbytes)
        self._by_gen: dict[int, set] = {}
        self._host_bytes = 0
        # tombstones of recently invalidated generations (bounded
        # recency window): a query that snapshotted the file set before a
        # swap may still be filling through retired readers — those late
        # put()s must not re-create entries no hook will ever invalidate
        self._retired: OrderedDict = OrderedDict()
        self._dev: OrderedDict = OrderedDict()  # token -> (entry, nbytes)
        self._dev_bytes = 0
        if budget_mb is None:
            budget_mb = max(0, _env_int("OGT_COLCACHE_MB", _DEFAULT_MB))
        if device is None:
            device = os.environ.get("OGT_COLCACHE_DEVICE", "0") not in ("", "0")
        if device_budget_mb is None:
            device_budget_mb = max(0,
                                   _env_int("OGT_COLCACHE_DEVICE_MB",
                                            budget_mb))
        self._budget = int(budget_mb) << 20
        self._dev_budget = int(device_budget_mb) << 20
        self._device = bool(device)

    # -- configuration ----------------------------------------------------

    def enabled(self) -> bool:
        return self._budget > 0

    def device_enabled(self) -> bool:
        return self._device and self._budget > 0

    def config(self) -> dict:
        """Public snapshot of the knobs, in the configure() units —
        save/restore for bench A/B blocks and test fixtures."""
        with self._lock:
            return {
                "budget_mb": self._budget >> 20,
                "device": self._device,
                "device_budget_mb": self._dev_budget >> 20,
            }

    def configure(self, budget_mb: int | None = None,
                  device: bool | None = None,
                  device_budget_mb: int | None = None) -> None:
        """Runtime re-configuration (tests, bench A/B). Shrinking a
        budget evicts immediately; disabling clears the tier. Each knob
        changes only when passed — budget_mb does NOT reset an
        operator-set device budget."""
        with self._lock:
            if budget_mb is not None:
                self._budget = int(budget_mb) << 20
            if device is not None:
                self._device = bool(device)
            if device_budget_mb is not None:
                self._dev_budget = int(device_budget_mb) << 20
            if self._budget <= 0:
                self._host.clear()
                self._by_gen.clear()
                self._host_bytes = 0
            else:
                self._evict_host_locked()
            if self._dev_budget <= 0 or not self.device_enabled():
                self._drop_dev_all_locked()
            else:
                self._evict_dev_locked()
            self._publish_locked()

    def clear(self) -> None:
        with self._lock:
            self._host.clear()
            self._by_gen.clear()
            self._host_bytes = 0
            self._drop_dev_all_locked()
            self._publish_locked()

    def _drop_dev_all_locked(self) -> None:
        for ent, _nb in self._dev.values():
            devobs.LEDGER.drop(ent.pop("_ledger", None))
        self._dev.clear()
        self._dev_bytes = 0

    # -- host tier --------------------------------------------------------

    def get(self, key):
        """Counted lookup (the fill path calls this once per column)."""
        t0 = time.perf_counter_ns()
        with self._lock:
            got = self._host.get(key)
            if got is not None:
                self._host.move_to_end(key)
        if got is not None:
            _STATS.incr("colcache", "hits")
        else:
            _STATS.incr("colcache", "misses")
        self._note_time(time.perf_counter_ns() - t0)
        return got[0] if got is not None else None

    def peek(self, key):
        """Uncounted lookup for the consult-before-dispatch fast path:
        a partially cached chunk falls through to the pool fill, which
        does its own counted get() per column — peeks stay silent so a
        near-miss is not double-counted.  Hits still refresh recency."""
        with self._lock:
            got = self._host.get(key)
            if got is None:
                return None
            self._host.move_to_end(key)
            return got[0]

    def count_peek(self, hits: int, time_ns: int = 0) -> None:
        """Fold a successful consult-before-dispatch assembly (N column
        peeks that all hit) into the counters."""
        if hits:
            _STATS.incr("colcache", "hits", hits)
        if time_ns:
            self._note_time(time_ns)

    def put(self, key, value) -> None:
        t0 = time.perf_counter_ns()
        nb = _nbytes(value)
        if nb > self._budget:
            return  # a single oversized column never enters the cache
        with self._lock:
            if self._budget <= 0 or key[1] in self._retired:
                # retired-generation tombstone: a decode racing the
                # file-set swap must not resurrect dead keys
                return
            if key not in self._host:
                self._host[key] = (value, nb)
                self._host_bytes += nb
                self._by_gen.setdefault(key[1], set()).add(key)
            self._host.move_to_end(key)
            self._evict_host_locked()
            self._publish_locked()
        _STATS.incr("colcache", "fills")
        self._note_time(time.perf_counter_ns() - t0)

    def _drop_host_locked(self, key) -> None:
        val = self._host.pop(key, None)
        if val is None:
            return
        self._host_bytes -= val[1]
        keys = self._by_gen.get(key[1])
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_gen[key[1]]

    def _evict_host_locked(self) -> None:
        n = 0
        while self._host_bytes > self._budget and self._host:
            k = next(iter(self._host))
            self._drop_host_locked(k)
            n += 1
        if n:
            _STATS.incr("colcache", "evictions", n)

    def invalidate_gens(self, gens) -> int:
        """Drop every host entry of the given file generations (the
        file-set-swap hook: compaction, downsample, delete rewrite,
        retention drop, shard close).  Readers holding decoded arrays
        keep them alive — only the cache's references drop."""
        n = 0
        with self._lock:
            for gen in gens:
                # tombstone first (bounded recency window — in-flight
                # decodes of the retired readers race this by at most
                # one scan's duration)
                self._retired[gen] = None
                self._retired.move_to_end(gen)
                while len(self._retired) > 65536:
                    self._retired.popitem(last=False)
                keys = self._by_gen.pop(gen, None)
                if not keys:
                    continue
                for key in keys:
                    got = self._host.pop(key, None)
                    if got is not None:
                        self._host_bytes -= got[1]
                        n += 1
            if n:
                self._publish_locked()
        if n:
            _STATS.incr("colcache", "invalidations", n)
        return n

    # -- device tier ------------------------------------------------------

    def device_get(self, token, shape, dtype: str, mesh=None):
        """The retained device-grid entry for a scan signature, or None.
        Shape/dtype are verified defensively (the signature already pins
        them; a mismatch is treated as a miss, never an error).

        ``mesh`` is the caller's CURRENT layout decision (the configured
        device mesh, or None for single-device). Entries are keyed by the
        mesh they were sharded for; a hit laid out for a DIFFERENT mesh
        (runtime.set_mesh changed — config reload) is resharded in place
        device-to-device with the stale buffers DONATED
        (distributed.donate_reshard), so the swap never re-decodes, never
        re-transfers from host, and never holds both layouts resident."""
        if not self.device_enabled():
            return None
        t0 = time.perf_counter_ns()
        with self._lock:
            got = self._dev.get(token)
            if got is not None:
                self._dev.move_to_end(token)
        ent = got[0] if got is not None else None
        if ent is not None and (ent["shape"] != tuple(shape)
                                or ent["dtype"] != dtype):
            ent = None
        if ent is not None and ent.get("mesh") is not mesh:
            ent = self._device_reshard(token, ent, mesh)
        _STATS.incr("colcache",
                    "device_hits" if ent is not None else "device_misses")
        self._note_time(time.perf_counter_ns() - t0)
        return ent

    def _device_reshard(self, token, ent, mesh):
        """Relayout a retained entry onto ``mesh`` (None = single device),
        donating the stale buffers. Returns the updated entry, or None
        (drop -> miss) when the rows cannot shard evenly over the new
        mesh — the caller then rebuilds from host rows at a compatible
        padded shape.

        Serialized by ``_reshard_lock`` and re-validated under the cache
        lock so concurrent getters chasing one mesh swap never
        double-donate the same buffers.  A query that took the entry
        BEFORE the swap may still observe deleted buffers on backends
        that implement donation — the inherent cost of a live mesh
        reload, bounded to queries in flight at the admin event."""
        from opengemini_tpu.parallel import distributed as _dist

        with self._reshard_lock:
            with self._lock:
                got = self._dev.get(token)
                live = got[0] if got is not None else None
                if live is not ent:
                    # replaced while we waited: usable only if the
                    # replacement already fits the requested mesh
                    return (live if live is not None
                            and live.get("mesh") is mesh else None)
                if ent.get("mesh") is mesh:
                    return ent  # another thread finished the swap
                arrays = [ent["vt"], ent["mt"]]
                if ent.get("imat") is not None:
                    arrays.append(ent["imat"])
            rows = ent["shape"][0]
            if mesh is not None and (rows < mesh.size or rows % mesh.size):
                with self._lock:
                    got = self._dev.get(token)
                    if got is not None and got[0] is ent:
                        del self._dev[token]
                        self._dev_bytes -= got[1]
                        devobs.LEDGER.drop(ent.pop("_ledger", None))
                        self._publish_locked()
                _STATS.incr("colcache", "device_reshard_drops")
                return None
            if mesh is not None:
                spec = _dist.leading_axis_sharding(mesh, arrays[0].ndim)
            else:
                import jax

                spec = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            out = _dist.donate_reshard(spec, *arrays)
            with self._lock:
                ent["vt"], ent["mt"] = out[0], out[1]
                if len(out) > 2:
                    ent["imat"] = out[2]
                elif ent.get("imat") is not None:
                    # an imat attached between our snapshot and the swap
                    # (device_add_imat racing the reshard) carries the
                    # OLD mesh layout — drop it so the next selector
                    # query rebuilds it sharded for the new mesh, and
                    # give its bytes back to the budget
                    stale = ent["imat"]
                    ent["imat"] = None
                    got = self._dev.get(token)
                    if got is not None and got[0] is ent:
                        self._dev[token] = (ent,
                                            got[1] - int(stale.nbytes))
                        self._dev_bytes -= int(stale.nbytes)
                        devobs.LEDGER.update(ent.get("_ledger"),
                                             got[1] - int(stale.nbytes))
                        self._publish_locked()
                ent["mesh"] = mesh
                devobs.LEDGER.update(ent.get("_ledger"),
                                     mesh_epoch=self._mesh_epoch(mesh))
        _STATS.incr("colcache", "device_reshards")
        return ent

    def device_put_grid(self, token, vt, mt, shape, dtype: str, mesh=None):
        """Retain freshly transferred grid buffers; returns the entry
        (callers use the returned dict so concurrent puts converge on
        one live object). ``mesh`` records the layout the buffers were
        sharded for (None = single device) — device_get reshards or
        rebuilds when the process mesh changes."""
        ent = {"vt": vt, "mt": mt, "imat": None,
               "shape": tuple(shape), "dtype": dtype, "mesh": mesh}
        nb = int(vt.nbytes) + int(mt.nbytes)
        if not self.device_enabled() or nb > self._dev_budget:
            return ent  # still usable by the caller, just not retained
        with self._lock:
            got = self._dev.get(token)
            if got is not None:
                if (got[0]["shape"] == ent["shape"]
                        and got[0]["dtype"] == ent["dtype"]
                        and got[0].get("mesh") is mesh):
                    self._dev.move_to_end(token)
                    return got[0]
                # same token, different geometry (the defensive mismatch
                # device_get treats as a miss): replace, never hand back
                del self._dev[token]
                self._dev_bytes -= got[1]
                devobs.LEDGER.drop(got[0].pop("_ledger", None))
            self._dev[token] = (ent, nb)
            self._dev_bytes += nb
            ent["_ledger"] = devobs.LEDGER.register(
                "colcache_device", nb, mesh_epoch=self._mesh_epoch(mesh),
                label=str(token)[:120])
            self._evict_dev_locked()
            self._publish_locked()
        return ent

    @staticmethod
    def _mesh_epoch(mesh):
        """Ledger epoch stamp: the live mesh epoch for sharded entries,
        None for single-device ones (not mesh-dependent)."""
        if mesh is None:
            return None
        from opengemini_tpu.parallel import runtime as _prt

        return _prt.mesh_epoch()

    def device_add_imat(self, token, ent, imat, mesh=None):
        """Attach the lazily-built selector index grid to a retained
        entry. Returns the WINNING imat: a concurrent builder that lost
        the race gets the already-attached one, and the loser's bytes
        are never double-counted against the device budget. ``mesh`` is
        the layout the caller built ``imat`` for — if a concurrent
        reshard moved the entry to a different mesh meanwhile, the
        stale-layout imat is used caller-locally but never attached
        (mixed-mesh entries would feed kernels incompatible devices)."""
        with self._lock:
            got = self._dev.get(token)
            if got is None or got[0] is not ent:
                # entry no longer retained: caller-local use only
                if ent.get("imat") is None:
                    ent["imat"] = imat
                return ent["imat"]
            if ent.get("imat") is not None:
                return ent["imat"]
            if ent.get("mesh") is not mesh:
                return imat  # entry resharded since the caller's put
            ent["imat"] = imat
            self._dev[token] = (ent, got[1] + int(imat.nbytes))
            self._dev_bytes += int(imat.nbytes)
            devobs.LEDGER.update(ent.get("_ledger"),
                                 got[1] + int(imat.nbytes))
            self._evict_dev_locked()
            self._publish_locked()
        return imat

    def _evict_dev_locked(self) -> None:
        n = 0
        while self._dev_bytes > self._dev_budget and self._dev:
            _k, (ent, nb) = self._dev.popitem(last=False)
            self._dev_bytes -= nb
            devobs.LEDGER.drop(ent.pop("_ledger", None))
            n += 1
        if n:
            _STATS.incr("colcache", "evictions", n)

    # -- introspection ----------------------------------------------------

    def counters(self) -> dict:
        """Process-global counter snapshot (bench hit-rate lines, the
        executor's per-scan delta for the `colcache` trace span).
        Counters-only read: the full stats snapshot runs gauge PROVIDERS
        (durability ledger sweeps over every shard lock) — far too heavy
        for a per-query call."""
        snap = _STATS.counters("colcache")
        with self._lock:
            snap["bytes"] = self._host_bytes
            snap["device_bytes"] = self._dev_bytes
            snap["entries"] = len(self._host)
            snap["device_entries"] = len(self._dev)
        for k in ("hits", "misses", "fills", "evictions", "invalidations",
                  "device_hits", "device_misses", "device_reshards",
                  "device_reshard_drops", "time_ns"):
            snap.setdefault(k, 0)
        return snap

    def ledger_bytes(self) -> int:
        """Host-tier resident bytes (resource-governor ledger component,
        utils/governor.py)."""
        with self._lock:
            return self._host_bytes

    def device_ledger_bytes(self) -> int:
        """Device-tier resident bytes (resource-governor ledger)."""
        with self._lock:
            return self._dev_bytes

    def _publish_locked(self) -> None:
        _STATS.set("colcache", "bytes", self._host_bytes)
        _STATS.set("colcache", "device_bytes", self._dev_bytes)

    @staticmethod
    def _note_time(dt_ns: int) -> None:
        _STATS.incr("colcache", "time_ns", dt_ns)
        tracing.record_stage("colcache", dt_ns)


# process-wide cache (the reference's readcache singleton)
GLOBAL = ColumnCache()


def _register_with_governor() -> None:
    # both cache tiers join the unified memory ledger
    from opengemini_tpu.utils.governor import GOVERNOR

    GOVERNOR.register_component("colcache_host", GLOBAL.ledger_bytes)
    GOVERNOR.register_component("colcache_device", GLOBAL.device_ledger_bytes)


_register_with_governor()

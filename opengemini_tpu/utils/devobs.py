"""Device-runtime telemetry: compile, transfer, and device-memory
accounting for the accelerator tier.

PR 8 made the HOST side observable (stitched traces, log2 histograms,
/metrics, slow log); this module does the same for the device tier the
multi-chip work built — jit compiles (models/templates.py, models/
grid.py, models/ragged.py, ops/prom.py ShardedTiled, parallel/
distributed.py), host<->device transfers (colcache fills, grid/bucket
sharding, donate-resharding, result fetches), and retained device
buffers (the colcache device tier, frozen-batch mesh arrays, the
ShardedTiled caches).  Offload engines live or die by knowing exactly
what transfer, compile, and residency cost each query pays (the
GPU-offloading OLAP literature, arXiv:2601.19911).

Four concerns, one arming model (the PR 8 idiom — `OGT_DEVOBS=1`, or
`/debug/ctrl?mod=devobs&arm=1` at runtime; results are bit-identical
armed or not):

  compile accounting   every jit lowering site calls note_compile() on
      a program-cache miss.  ALWAYS cheap-counted (compiles are rare —
      counters, the per-(kernel, geometry, mesh-epoch) inventory, the
      bounded recent-compile ring, and the recompile TRIPWIRE run even
      disarmed, replacing the old bare `device/compile_cache_misses`).
      Armed additionally: backend compile WALL TIME via the
      jax.monitoring duration events, attributed to the kernel label
      and to the running query's `device_compile` stage
      (tracing.record_stage).

      The tripwire: mark_warm() (test warm loops, or the ctrl op)
      snapshots "everything is compiled now"; ANY lowering-site miss
      after the mark increments `recompiles_after_warm_total` and flags
      the ring entry — the classic silent 10x regression in jit systems
      (shape churn, unstable cache keys, evicted programs).  Repeat
      compiles of an already-seen (kernel, geometry, mesh-epoch) triple
      are counted separately (`repeat_compiles_total`) with no mark
      needed: the same program lowering twice always means a cache lost
      an entry.

  transfer accounting  note_transfer(direction, site, nbytes, seconds)
      is the single chokepoint for h2d / d2h / reshard byte accounting
      (it owns the `device/{h2d,d2h,reshard}_bytes` counters the ad-hoc
      sites used to bump inline).  Armed additionally: per-site
      `ogt_device_{h2d,d2h,reshard}_{bytes,seconds}` histograms.
      launch() is the one way to call a compiled program: a
      `device_launch` span (utils/tracing.py; always on) around the
      call, and the host arrays among its arguments — the implicit H2D
      of a single-chip launch — counted as h2d bytes.  fetch_np() and
      fetch_tree() wrap the device->host materialization in a
      `device_fetch` span, one a launch, with the wait for the program
      (`device_wait`) and the copies (`device_copy`) as its children,
      and count its bytes.

  device-memory ledger every RETAINED device buffer registers (owner,
      nbytes, mesh-epoch): the colcache device tier, grid `mesh_arrays`
      / ragged `_Bucket._mesh_arrays` sharded copies, the ShardedTiled
      per-query caches and TiledPrepared device values.  Entries anchor
      to their holder via weakref.finalize, so a dropped batch can
      never leak a ledger row; /debug/device answers "what is resident
      and who owns it" by owner, and /metrics exports the gauges
      (cross-checked against jax per-device memory_stats() where the
      backend reports them — CPU does not).  Armed-only: register sites
      check enabled(), so arm BEFORE the workload you want inventoried.

  capability probes    backend_capabilities() answers what this jax
      backend can actually run — today: Pallas support (probed by
      executing a tiny self-contained kernel).  On the CPU the tier-1
      pallas suite skips-with-reason where the probe fails instead of
      reporting 12 undiagnosable failures, and fails for real where it
      succeeds.  On a TPU a failing probe raises: no route may read it
      as "unsupported" and quietly take another path.

An on-demand `jax.profiler` capture (start_profile / /debug/ctrl
op=profile&seconds=N) rounds out the ops surface — single-capture
guarded, writing a TensorBoard-loadable trace directory.  It runs
without the python tracer: the host events in it are the runtime's own
and the program's spans (`ogt:<stage>`, utils/tracing.py), on the same
clock as the device's operations.  `python=1` asks for frames as well.

Knobs (README "Device observability"): OGT_DEVOBS (1 = armed),
OGT_DEVOBS_RING (recent-compile ring bound, default 256).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from contextlib import contextmanager

import numpy as _np

from opengemini_tpu.utils import lockdep
from opengemini_tpu.utils.stats import GLOBAL as _STATS

_ON = os.environ.get("OGT_DEVOBS", "") in ("1", "true")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


_RING_MAX = max(16, _env_int("OGT_DEVOBS_RING", 256))

# geometry-inventory bound per kernel: past this only the count grows
# (a kernel compiling thousands of distinct geometries IS the finding)
_GEOMETRIES_MAX = 512

_lock = lockdep.Lock()
_ring: deque = deque(maxlen=_RING_MAX)
_inventory: dict[str, dict] = {}   # kernel -> {compiles, geometries: {},
#                                    geometry_overflow, repeats}
_warm_marked = False
_compiles_since_warm = 0
_compile_wall_ns = 0               # armed-only accumulation
_started_pc = time.perf_counter()

# thread-local label of the most recently built kernel: the backend
# compile duration event fires on the SAME thread during the program's
# first invocation, immediately after the lowering-site miss, so "last
# built label on this thread" attributes it correctly for every
# instrumented site (un-instrumented compiles attribute to "other")
_tls = threading.local()

_listener_registered = False


def enabled() -> bool:
    return _ON


def set_enabled(on: bool) -> None:
    global _ON
    _ON = bool(on)
    if _ON:
        _ensure_listener()


def _ensure_listener() -> None:
    """Register the jax.monitoring listeners once (utils/backend.init at
    start-up, or first arming — idempotent-guarded here)."""
    global _listener_registered
    if _listener_registered:
        return
    _listener_registered = True
    import jax.monitoring as _mon

    _mon.register_event_duration_secs_listener(_on_jax_duration)
    _mon.register_event_listener(_on_jax_event)


watch_compiles = _ensure_listener


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# what XLA itself built or loaded, counted armed or not: the lowering-
# site counters above see only the sites that call note_compile(), and
# cannot tell a compile from a persistent-cache load
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        "persistent_cache_requests_total",
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits_total",
}


def _on_jax_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _STATS.incr("device", name)


def _on_jax_duration(event: str, duration_s: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    # one per executable XLA produced for this process, compiled or
    # read back from the persistent cache
    _STATS.incr("device", "xla_programs_total")
    if not _ON:
        return
    global _compile_wall_ns
    ns = int(duration_s * 1e9)
    kernel = getattr(_tls, "kernel", None) or "other"
    with _lock:
        _compile_wall_ns += ns
        ent = getattr(_tls, "ring_entry", None)
        if ent is not None and ent.get("kernel") == kernel:
            ent["wall_ms"] = round(ent.get("wall_ms", 0.0) + ns / 1e6, 3)
        # last-compile wall on the INVENTORY entry too: the offload
        # planner's compile-cost prior (query/offload.py) reads it from
        # inventory() per (kernel, geometry), not from the bounded ring
        geo = getattr(_tls, "geo_entry", None)
        if geo is not None:
            geo["wall_ms"] = round(geo.get("wall_ms", 0.0) + ns / 1e6, 3)
    from opengemini_tpu.utils.stats import observe_ns

    observe_ns("device_compile_seconds", ns, kernel=kernel)
    from opengemini_tpu.utils import tracing

    tracing.record_stage("device_compile", ns)


# per-(family, site) histogram cache: note_transfer is on the armed hot
# path (every fetch/put), and the registry's get-or-create does a
# sorted-tuple key build per call — cache the objects like every other
# fixed-label call site does
_hist_cache: dict[tuple, object] = {}


def _hist(family: str, site: str, unit: str, mesh: bool = False):
    key = (family, site, mesh)
    h = _hist_cache.get(key)
    if h is None:
        from opengemini_tpu.utils.stats import histogram

        labels = {"site": site}
        if mesh:
            # the mesh dimension only appears on sharded transfers, so
            # every pre-existing site keeps its exact label set
            labels["mesh"] = "on"
        h = _hist_cache[key] = histogram(family, unit=unit, **labels)
    return h


# -- compile accounting -------------------------------------------------------


def _mesh_epoch() -> int:
    from opengemini_tpu.parallel import runtime as _prt

    return _prt.mesh_epoch()


def note_compile(kernel: str, geometry=()) -> None:
    """Record one jit lowering-site program-cache MISS.  Called at every
    site that builds a device program (templates._jitted_build, the grid
    and bucket stat kernels, the ShardedTiled program cache, the mesh
    batch-agg and reshard programs).  Always-on: compiles are rare, and
    the inventory/tripwire is precisely the thing you need when the
    system is misbehaving and nobody thought to arm anything."""
    global _compiles_since_warm
    geo = str(geometry)
    epoch = _mesh_epoch()
    _STATS.incr("device", "compiles_total")
    _STATS.incr("device", "compile_cache_misses")  # pre-PR-14 spelling
    entry = {
        "kernel": kernel, "geometry": geo, "mesh_epoch": epoch,
        "uptime_s": round(time.perf_counter() - _started_pc, 3),
    }
    with _lock:
        geo_ent = _geo_entry_locked(kernel, geo, epoch)
        inv = _inventory[kernel]
        inv["compiles"] += 1
        if geo_ent is not None:
            if geo_ent["compiles"]:
                inv["repeats"] += 1
                entry["repeat"] = True
                _STATS.incr("device", "repeat_compiles_total")
            geo_ent["compiles"] += 1
        if _warm_marked:
            _compiles_since_warm += 1
            entry["after_warm"] = True
            _STATS.incr("device", "recompiles_after_warm_total")
        _ring.append(entry)
        _tls.kernel = kernel
        _tls.ring_entry = entry
        _tls.geo_entry = geo_ent


def _geo_entry_locked(kernel: str, geo: str, epoch) -> dict | None:
    """The per-(geometry, mesh-epoch) inventory record for one kernel
    (created on first sight, None past the per-kernel bound — the
    overflow count is the finding then).  Caller holds _lock."""
    inv = _inventory.get(kernel)
    if inv is None:
        inv = _inventory[kernel] = {
            "compiles": 0, "geometries": OrderedDict(),
            "geometry_overflow": 0, "repeats": 0}
    key = (geo, epoch)
    ent = inv["geometries"].get(key)
    if ent is None:
        if len(inv["geometries"]) >= _GEOMETRIES_MAX:
            inv["geometry_overflow"] += 1
            return None
        ent = inv["geometries"][key] = {
            "compiles": 0, "hits": 0, "wall_ms": 0.0}
    return ent


def note_use(kernel: str, geometry=()) -> None:
    """Record one WARM dispatch of an already-compiled (kernel,
    geometry) program — the shape-recurrence signal the offload
    planner's amortization (query/offload.py) and the pre-warmer's
    top-K ranking feed on.  Always-on and cheap (two dict lookups under
    the lock, once per kernel launch)."""
    with _lock:
        ent = _geo_entry_locked(kernel, str(geometry), _mesh_epoch())
        if ent is not None:
            ent["hits"] += 1


def mark_warm() -> None:
    """Arm the recompile tripwire: everything needed is compiled NOW;
    any lowering-site miss from here on is a flagged recompile.  Test
    warm loops call this after their compile warmup; operators via
    /debug/ctrl?mod=devobs&op=mark_warm once a service is warm."""
    global _warm_marked, _compiles_since_warm
    with _lock:
        _warm_marked = True
        _compiles_since_warm = 0
    # the same epoch for the slowest requests and the pulse's maximum
    from opengemini_tpu.utils import tracing

    tracing.mark()


def clear_warm() -> None:
    global _warm_marked, _compiles_since_warm
    with _lock:
        _warm_marked = False
        _compiles_since_warm = 0


def compiles_since_warm() -> int:
    """Lowering-site misses since mark_warm() (0 when never marked)."""
    with _lock:
        return _compiles_since_warm


def jit_inventory() -> dict:
    """Per-kernel program-cache view: compile counts, distinct
    geometries (per mesh epoch), repeat compiles."""
    with _lock:
        return {
            k: {
                "compiles": v["compiles"],
                # use-only records (note_use before any compile) are not
                # compiled geometries; the pre-PR counting stands
                "distinct_geometries": sum(
                    1 for e in v["geometries"].values() if e["compiles"]),
                "geometry_overflow": v["geometry_overflow"],
                "repeat_compiles": v["repeats"],
            }
            for k, v in sorted(_inventory.items())
        }


def inventory() -> dict:
    """Structured per-(kernel, geometry) snapshot for the offload
    planner's cost model (query/offload.py): each kernel maps to its
    aggregate counts plus one record per (geometry, mesh-epoch) carrying
    the compile count, the warm-dispatch hit count (note_use), and the
    accumulated backend compile wall for that geometry — the
    recurrence + compile-cost inputs the amortization math needs.
    jit_inventory() stays the render-only aggregate view."""
    with _lock:
        return {
            k: {
                "compiles": v["compiles"],
                "repeat_compiles": v["repeats"],
                "geometry_overflow": v["geometry_overflow"],
                "geometries": [
                    {"geometry": geo, "mesh_epoch": epoch,
                     "compiles": e["compiles"], "hits": e["hits"],
                     "wall_ms": e["wall_ms"]}
                    for (geo, epoch), e in v["geometries"].items()
                ],
            }
            for k, v in sorted(_inventory.items())
        }


def recent_compiles() -> list[dict]:
    """Newest-first bounded ring of recent compiles with shapes."""
    with _lock:
        return [dict(e) for e in reversed(_ring)]


# -- transfer accounting ------------------------------------------------------


def note_transfer(direction: str, site: str, nbytes: int,
                  seconds: float | None = None,
                  mesh: bool = False) -> None:
    """The single chokepoint for device transfer accounting.  Always
    owns the `device/{h2d,d2h,reshard}_bytes` counters; armed it adds
    the per-site byte/latency histograms.  ``mesh=True`` marks a
    transfer made under a configured device mesh (a `mesh="on"` label on
    the site's histograms — the sharded-decode H2D is distinguishable
    from the single-device one at the same site)."""
    nbytes = int(nbytes)
    # counter spelled *_total so the unlabeled family name stays free
    # for the per-site histogram of the same quantity
    _STATS.incr("device", direction + "_bytes_total", nbytes)
    if not _ON:
        return
    _hist("device_" + direction + "_bytes", site, "bytes",
          mesh).observe_ns(nbytes)
    if seconds is not None:
        _hist("device_" + direction + "_seconds", site, "seconds",
              mesh).observe_ns(int(seconds * 1e9))


def _fetch(x, site: str):
    """np.asarray of one array; a device array's bytes (and, armed, its
    fetch wall) are counted as d2h."""
    import jax

    if not isinstance(x, jax.Array):
        return _np.asarray(x)
    t0 = time.perf_counter_ns() if _ON else 0
    a = _np.asarray(x)
    note_transfer("d2h", site, a.nbytes,
                  (time.perf_counter_ns() - t0) / 1e9 if _ON else None)
    return a


def fetch_np(x, site: str = "result-fetch"):
    """np.asarray with d2h accounting: a device array is fetched as
    `fetch_tree` fetches a result of one array, and its bytes counted;
    host arrays pass straight through."""
    import jax

    if not isinstance(x, jax.Array):
        return _np.asarray(x)
    return fetch_tree(x, site)


def fetch_tree(outs, site: str = "result-fetch"):
    """The arrays of ONE launch's result (any pytree: a launch group's
    packed pair, a result dict) brought to the host as ONE `device_fetch`
    span with two children, so that a slow fetch says which half it
    waited in: `device_wait`, until the program has finished, then
    `device_copy`, one np.asarray an array.  The copies are asked for
    before the wait, so they queue behind the program and travel
    together: the wait costs no round trip of its own (on a v5e a
    panel's two arrays 0.83 ms so, 1.19 ms by np.asarray alone, 1.30 ms
    waiting first and asking then: PERF.md, PR 39)."""
    import jax

    from opengemini_tpu.utils import tracing

    with tracing.span("device_fetch") as sp:
        with tracing.span("device_wait"):
            for x in jax.tree_util.tree_leaves(outs):
                if isinstance(x, jax.Array):
                    x.copy_to_host_async()
            jax.block_until_ready(outs)
        with tracing.span("device_copy"):
            got = jax.tree_util.tree_map(lambda x: _fetch(x, site), outs)
        nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(got))
        sp.add_field("bytes", nbytes)
        tracing.note_d2h(nbytes)
    return got


def launch(fn, args, program: str, xfer_site: str):
    """Call the compiled program `fn(*args)` inside a `device_launch`
    span: the dispatch (jax returns before the device is done; the wait
    is the fetch's) with the implicit H2D of whatever host arrays are
    among the leaves of `args` (a launch group passes its fields as one
    nested tuple), whose bytes count as h2d at `xfer_site`.  A program
    is passed only what it reads, so the count is what crosses."""
    import jax

    from opengemini_tpu.utils import tracing

    h2d = sum(a.nbytes for a in jax.tree_util.tree_leaves(args)
              if isinstance(a, _np.ndarray))
    with tracing.span("device_launch", program=program, h2d_bytes=h2d):
        out = fn(*args)
    if h2d:
        note_transfer("h2d", xfer_site, h2d)
    return out


def span_snapshot() -> dict:
    """Cheap counters-only snapshot for per-span delta attribution (the
    executor's device_compute span fields)."""
    snap = _STATS.counters("device")
    with _lock:
        wall = _compile_wall_ns
    return {
        "compiles": snap.get("compiles_total", 0),
        "compile_wall_ms": round(wall / 1e6, 3),
        "h2d_bytes": snap.get("h2d_bytes_total", 0),
        "d2h_bytes": snap.get("d2h_bytes_total", 0),
        "reshard_bytes": snap.get("reshard_bytes_total", 0),
        "recompiles_after_warm": snap.get("recompiles_after_warm_total", 0),
    }


# -- device-memory ledger -----------------------------------------------------


class DeviceLedger:
    """Registry of retained device buffers: (owner, nbytes, mesh_epoch)
    per entry.  Entries registered with an ``anchor`` drop automatically
    when the anchor is collected — a per-query batch that dies
    mid-flight can never leak a row.  The finalizer does NOT take the
    ledger lock (a GC pass can fire finalizers inside a ledger method
    that already holds it — dict mutation allocates); it appends the
    handle to a lock-free deque drained at the next ledger operation.
    Armed-only by the register sites' enabled() guard; register()
    itself returns None disarmed so holders store-and-forget the
    handle."""

    def __init__(self) -> None:
        self._lock = lockdep.Lock()
        self._next = 1
        self._entries: dict[int, dict] = {}
        # GC-finalizer drop queue: deque.append is atomic and takes no
        # lock, so it is safe to run at ANY allocation point
        self._pending_drops: deque = deque()

    def _drain_locked(self) -> None:
        while True:
            try:
                handle = self._pending_drops.popleft()
            except IndexError:
                return
            self._entries.pop(handle, None)

    def register(self, owner: str, nbytes: int, mesh_epoch=None,
                 label: str = "", anchor=None) -> int | None:
        if not _ON:
            return None
        with self._lock:
            self._drain_locked()
            handle = self._next
            self._next += 1
            self._entries[handle] = {
                "owner": owner, "nbytes": int(nbytes),
                "mesh_epoch": mesh_epoch, "label": label,
            }
        if anchor is not None:
            weakref.finalize(anchor, self._pending_drops.append, handle)
        return handle

    def update(self, handle: int | None, nbytes: int | None = None,
               mesh_epoch=...) -> None:
        if handle is None:
            return
        with self._lock:
            self._drain_locked()
            ent = self._entries.get(handle)
            if ent is None:
                return
            if nbytes is not None:
                ent["nbytes"] = int(nbytes)
            if mesh_epoch is not ...:
                ent["mesh_epoch"] = mesh_epoch

    def drop(self, handle: int | None) -> None:
        if handle is None:
            return
        with self._lock:
            self._drain_locked()
            self._entries.pop(handle, None)

    def total_bytes(self) -> int:
        with self._lock:
            self._drain_locked()
            return sum(e["nbytes"] for e in self._entries.values())

    def by_owner(self) -> dict:
        """{owner: {bytes, entries, stale_epoch_entries}} — the
        /debug/device residency answer.  An entry is stale when its
        recorded mesh epoch no longer matches the live one (a buffer
        laid out for a dead mesh, pending reshard or eviction)."""
        live = _mesh_epoch()
        out: dict[str, dict] = {}
        with self._lock:
            self._drain_locked()
            for e in self._entries.values():
                o = out.setdefault(e["owner"], {
                    "bytes": 0, "entries": 0, "stale_epoch_entries": 0})
                o["bytes"] += e["nbytes"]
                o["entries"] += 1
                if e["mesh_epoch"] is not None and e["mesh_epoch"] != live:
                    o["stale_epoch_entries"] += 1
        return out

    def entries(self, limit: int = 256) -> list[dict]:
        with self._lock:
            self._drain_locked()
            rows = sorted(self._entries.values(),
                          key=lambda e: -e["nbytes"])[:limit]
            return [dict(e) for e in rows]

    def clear(self) -> None:
        with self._lock:
            self._drain_locked()
            self._entries.clear()


LEDGER = DeviceLedger()


def _ledger_gauges() -> dict:
    """Stats provider: ledger residency gauges ride /debug/vars and
    /metrics (module `device` -> ogt_device_ledger_* families) when
    armed; {} pass-through disarmed, the governor-provider idiom."""
    if not _ON:
        return {}
    out = {"ledger_bytes": LEDGER.total_bytes()}
    for owner, doc in LEDGER.by_owner().items():
        safe = "".join(c if c.isalnum() else "_" for c in owner.lower())
        out["ledger_" + safe + "_bytes"] = doc["bytes"]
        out["ledger_" + safe + "_entries"] = doc["entries"]
    return out


_STATS.register_provider("device", _ledger_gauges)


# -- backend capabilities -----------------------------------------------------

_caps_lock = lockdep.Lock()
_caps: dict | None = None


def backend_capabilities(probe: bool = True) -> dict:
    """What this jax backend can actually run, probed once per process.
    `pallas`: executes a tiny SELF-CONTAINED pallas_call (interpret mode
    off-TPU, Mosaic on TPU) exercising the same backend capability the
    product kernels need — an int-typed masked reduce stored into an
    int32 out ref (what interpret mode rejects under x64 without the
    explicit cast).  Deliberately NOT one of the product kernels:
    a regression in ops/pallas_segment.py must fail its tests, not
    convert them into skips.

    ``probe=False`` answers from the cache only (the /debug/device
    handler must never run a compile inline on a serving thread)."""
    global _caps
    with _caps_lock:
        if _caps is not None:
            return _caps
    if not probe:
        return {"probed": False, "pallas": {
            "supported": None,
            "reason": "unprobed (pallas_supported() runs the probe)"}}
    import jax

    caps: dict = {"probed": True, "backend": jax.default_backend(),
                  "device_count": len(jax.devices())}
    ok, why = _probe_pallas()
    caps["pallas"] = {"supported": ok, "reason": why}
    with _caps_lock:
        _caps = caps
    return caps


def _probe_pallas() -> tuple[bool, str]:
    """(True, "") when the probe kernel ran.  On the CPU a failure is an
    answer — interpret mode cannot run here, tests skip with the reason.
    On a TPU it is an error and raises: Mosaic refusing a kernel must
    never turn into "unsupported" and a quiet jnp route."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(m_ref, cnt_ref):
        # the product kernels' idiom: a masked integer reduce with an
        # EXPLICIT int32 result stored into an int32 ref.  The
        # explicit cast is load-bearing — x64 interpret mode widens
        # bare integer reduces to int64, which int32 refs reject —
        # so the kernels in ops/pallas_segment.py cast the same way,
        # and the probe passes wherever they can actually run.
        cnt_ref[...] = ((m_ref[...] != 0)
                        .sum(axis=1, keepdims=True)
                        .astype(jnp.int32))

    on_cpu = jax.default_backend() == "cpu"
    # one native int8 tile, so Mosaic's (32, 128) tiling accepts it
    m = _np.ones((32, 128), _np.int8)
    try:
        out = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((32, 1), jnp.int32),
            interpret=on_cpu,
        )(m)
    except Exception as e:  # noqa: BLE001 — on the CPU, any failure = skip
        if not on_cpu:
            raise
        return False, (f"pallas probe failed on this backend: "
                       f"{type(e).__name__}: {e}")
    if int(_np.asarray(out)[0, 0]) != 128:
        raise RuntimeError("pallas probe kernel computed a wrong count")
    return True, ""


def pallas_supported() -> tuple[bool, str]:
    """(supported, reason) — what tests/test_pallas.py gates on."""
    cap = backend_capabilities()["pallas"]
    return cap["supported"], cap["reason"]


# -- on-demand profiler capture ----------------------------------------------

_profile_lock = lockdep.Lock()
_profile = {"active": False, "dir": None, "started_uptime_s": None,
            "started_perf_ns": None, "seconds": None, "last": None}


def start_profile(seconds: float, logdir: str | None = None,
                  python: bool = False) -> dict:
    """Start a single-capture-guarded jax.profiler trace for
    ``seconds`` (clamped to [0.05, 120]); a background thread stops it.
    Raises RuntimeError while a capture is already active.  Returns the
    status dict (dir included) immediately — the trace directory is
    TensorBoard / XProf loadable once `active` goes false.

    The python tracer is off unless ``python``: it stretches a request
    3-5x and takes tens of seconds to stop, and the program's own spans
    (tracing.span -> `ogt:<stage>` annotations, on while `active`) say
    what the host was doing."""
    import tempfile

    seconds = min(max(float(seconds), 0.05), 120.0)
    with _profile_lock:
        if _profile["active"]:
            raise RuntimeError(
                f"profiler capture already active in {_profile['dir']}")
        if logdir is None:
            logdir = tempfile.mkdtemp(prefix="ogt-devobs-profile-")
        _profile.update(active=True, dir=logdir, seconds=seconds,
                        started_uptime_s=round(
                            time.perf_counter() - _started_pc, 3))
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python else 0
    try:
        # the capture's start on the clock of request and stall records
        # (tracing: `t0_ns`, `t_ns`), to place one on it by subtraction
        _profile["started_perf_ns"] = time.perf_counter_ns()
        jax.profiler.start_trace(logdir, profiler_options=options)
    except Exception as e:  # noqa: BLE001 — surface, don't wedge the guard
        with _profile_lock:
            _profile.update(active=False,
                            last={"dir": logdir, "ok": False,
                                  "error": f"{type(e).__name__}: {e}"})
        raise RuntimeError(f"profiler start failed: {e}") from e

    def _stop():
        time.sleep(seconds)
        doc = {"dir": logdir, "seconds": seconds, "ok": True,
               "started_perf_ns": _profile["started_perf_ns"]}
        t_stop = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            doc = {"dir": logdir, "seconds": seconds, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        # what stopping and writing the capture cost, for the operator
        doc["stop_s"] = round(time.perf_counter() - t_stop, 3)
        with _profile_lock:
            _profile.update(active=False, last=doc)

    threading.Thread(target=_stop, name="devobs-profile-stop",
                     daemon=True).start()
    return profile_status()


def profile_status() -> dict:
    with _profile_lock:
        return dict(_profile)


# -- /debug/device ------------------------------------------------------------


def device_table() -> list[dict]:
    """One row per jax device, with per-device memory stats where the
    backend reports them (TPU/GPU; CPU answers null) — the cross-check
    against the ledger's own residency accounting."""
    import jax

    return [{"id": d.id, "platform": d.platform,
             "device_kind": d.device_kind,
             "memory_stats": d.memory_stats()} for d in jax.devices()]


def debug_doc() -> dict:
    """The GET /debug/device payload."""
    from opengemini_tpu.parallel import runtime as _prt

    mesh = _prt.get_mesh()
    with _lock:
        warm = {"marked": _warm_marked,
                "compiles_since_warm": _compiles_since_warm}
        wall_ms = round(_compile_wall_ns / 1e6, 3)
    return {
        "enabled": _ON,
        # cache-only: the first debug scrape must never run the probe's
        # kernel compile inline on a serving thread
        "capabilities": backend_capabilities(probe=False),
        "devices": device_table(),
        # the shape by axis name and the ids of the devices the mesh
        # spans, row-major, as `devices` above lists them
        "mesh": {"configured": mesh is not None,
                 "size": getattr(mesh, "size", None),
                 "epoch": _prt.mesh_epoch(),
                 "axes": None if mesh is None else
                 {name: int(n) for name, n in mesh.shape.items()},
                 "device_ids": None if mesh is None else
                 [int(d.id) for d in mesh.devices.flat]},
        "counters": _STATS.counters("device"),
        "compile_wall_ms": wall_ms,
        "jit_cache": jit_inventory(),
        "recent_compiles": recent_compiles(),
        "warm": warm,
        "ledger": {
            "total_bytes": LEDGER.total_bytes(),
            "by_owner": LEDGER.by_owner(),
            "entries": LEDGER.entries(),
        },
        "profile": profile_status(),
    }


def reset() -> None:
    """Test/bench hygiene: clear the ring, inventory, warm mark, and
    compile-wall accumulation (counters in the stats registry are the
    registry's to reset)."""
    global _compile_wall_ns
    with _lock:
        _ring.clear()
        _inventory.clear()
        _compile_wall_ns = 0
    clear_warm()


@contextmanager
def armed(on: bool = True):
    """Scoped arm/disarm (tests, bench A/B legs)."""
    prev = _ON
    set_enabled(on)
    try:
        yield
    finally:
        set_enabled(prev)


if _ON:
    _ensure_listener()

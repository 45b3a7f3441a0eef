"""shard_map distributed segmented window aggregation.

Design (SURVEY.md §7 step 4): each device owns a row-slice of the scan
batch (its "shards"), computes dense per-segment partial aggregates
locally — the store-side partial agg of the reference
(engine/aggregate_cursor.go) — and the cross-device merge that the
reference does with RPC + merge transforms becomes one XLA collective:
  sum/count -> psum,  min -> pmin,  max -> pmax,
  first/last -> lexicographic (hi, lo, idx) combine via psum of one-hot
                winners (associative, rides ICI).

Everything is jit-compatible and partitions over an arbitrary 1D/2D mesh;
multi-host meshes work unchanged because shard_map + collectives are
device-count agnostic (DCN vs ICI is the runtime's concern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opengemini_tpu.ops import segment as seg

_BIG_I32 = 2**31 - 1


def make_mesh(n_devices: int | None = None, axes: tuple[str, ...] = ("shard",),
              shape: tuple[int, ...] | None = None) -> Mesh:
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    devs = devs[:n_devices]
    if shape is None:
        shape = (n_devices,) if len(axes) == 1 else _factor(n_devices, len(axes))
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axes)


def _factor(n: int, k: int) -> tuple[int, ...]:
    """Split n into k roughly-even factors (8, 2 axes -> (4, 2))."""
    shape = [1] * k
    i = 0
    d = 2
    while n > 1:
        while n % d:
            d += 1
        shape[i % k] *= d
        n //= d
        i += 1
    shape.sort(reverse=True)
    return tuple(shape)


def _local_partials(values, rel_hi, rel_lo, seg_ids, mask, num_segments):
    """Per-device dense partial aggregates over the local row slice."""
    s = seg.seg_sum(values, seg_ids, num_segments, mask)
    c = seg.seg_count(seg_ids, num_segments, mask)
    mn = seg.seg_min(values, seg_ids, num_segments, mask)
    mx = seg.seg_max(values, seg_ids, num_segments, mask)
    # local first: (hi, lo) of earliest valid row + its value
    fv, fsel = seg.seg_first(values, rel_hi, rel_lo, seg_ids, num_segments, mask)
    safe = jnp.clip(fsel, 0, values.shape[0] - 1)
    f_hi = jnp.where(c > 0, rel_hi[safe], _BIG_I32)
    f_lo = jnp.where(c > 0, rel_lo[safe], _BIG_I32)
    lv, lsel = seg.seg_last(values, rel_hi, rel_lo, seg_ids, num_segments, mask)
    safe_l = jnp.clip(lsel, 0, values.shape[0] - 1)
    l_hi = jnp.where(c > 0, rel_hi[safe_l], -_BIG_I32)
    l_lo = jnp.where(c > 0, rel_lo[safe_l], -_BIG_I32)
    return s, c, mn, mx, (fv, f_hi, f_lo), (lv, l_hi, l_lo)


def _merge_time_extreme(value, hi, lo, axes, earliest: bool):
    """Cross-device lexicographic (hi, lo) winner — exact int32 compares,
    no float encoding (f32 cannot order ns pairs). Two collective rounds:
    pmin/pmax on hi, then on the hi-masked lo. Devices holding the winning
    timestamp contribute value via psum; identical timestamps on several
    devices are averaged deterministically (they tie in the reference too,
    where scan order decides)."""
    if earliest:
        red = jax.lax.pmin
        big = _BIG_I32
    else:
        red = jax.lax.pmax
        big = -_BIG_I32
    hi_best = hi
    for ax in axes:
        hi_best = red(hi_best, ax)
    cand = hi == hi_best
    lo_masked = jnp.where(cand, lo, big)
    lo_best = lo_masked
    for ax in axes:
        lo_best = red(lo_best, ax)
    cand &= lo == lo_best
    # exact-time ties: larger value wins (reference FirstReduce/LastReduce)
    fbig = jnp.array(jnp.inf, value.dtype)
    v_best = jnp.where(cand, value, -fbig)
    for ax in axes:
        v_best = jax.lax.pmax(v_best, ax)
    cand &= value == v_best
    # remaining ties across devices: lowest device rank wins (deterministic,
    # one actual row's value — never an average of tied rows)
    rank = jnp.zeros((), jnp.int32)
    for ax in axes:
        rank = rank * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    rank_masked = jnp.where(cand, rank, _BIG_I32)
    rank_best = rank_masked
    for ax in axes:
        rank_best = jax.lax.pmin(rank_best, ax)
    is_winner = cand & (rank == rank_best)
    wsum = value * is_winner
    for ax in axes:
        wsum = jax.lax.psum(wsum, ax)
    return wsum


def build_dist_agg(mesh: Mesh, num_segments: int):
    """Compile the distributed query step: sharded batch -> replicated
    {sum, count, mean, min, max, first, last} per segment.

    The jitted function takes row-sharded arrays (padded to a multiple of
    the mesh size) and returns replicated outputs — the equivalent of the
    reference's store-scan + exchange + merge pipeline as ONE XLA program.
    """
    axes = mesh.axis_names
    row_spec = P(axes)  # rows sharded over every mesh axis

    def step(values, rel_hi, rel_lo, seg_ids, mask):
        s, c, mn, mx, first_t, last_t = _local_partials(
            values, rel_hi, rel_lo, seg_ids, mask, num_segments
        )
        for ax in axes:
            s = jax.lax.psum(s, ax)
            c = jax.lax.psum(c, ax)
            mn = jax.lax.pmin(mn, ax)
            mx = jax.lax.pmax(mx, ax)
        fv = _merge_time_extreme(*first_t, axes, earliest=True)
        lv = _merge_time_extreme(*last_t, axes, earliest=False)
        mean = s / jnp.maximum(c, 1).astype(s.dtype)
        return {
            "sum": s, "count": c, "mean": mean,
            "min": mn, "max": mx, "first": fv, "last": lv,
        }

    # replication checking off: the collectives produce replicated
    # outputs by construction and the checker rejects the one-hot
    # winner combines
    sharded = jax.shard_map(step, mesh=mesh, in_specs=(row_spec,) * 5,
                            out_specs=P(), check_vma=False)
    return jax.jit(sharded)


# aggregates the mesh batch step can serve (everything the executor's
# device path computes except rank-based ones — median/percentile — and
# stddev, which keep the single-device kernels)
MESH_AGGS = {"count", "sum", "mean", "min", "max", "first", "last", "spread"}

_BIG_F = jnp.inf


def _reduce(x, axes, op):
    for ax in axes:
        x = op(x, ax)
    return x


def _winner(keys, valid, axes):
    """Cross-device lexicographic winner one-hot. keys: [(array,
    minimize)], narrowed key by key; ties resolve to the lowest device
    rank — exactly one device wins per segment, deterministically."""
    cand = valid
    for arr, minimize in keys:
        if jnp.issubdtype(arr.dtype, jnp.floating):
            sent = _BIG_F if minimize else -_BIG_F
        else:
            sent = _BIG_I32 if minimize else -_BIG_I32
        masked = jnp.where(cand, arr, sent)
        best = _reduce(masked, axes, jax.lax.pmin if minimize else jax.lax.pmax)
        cand = cand & (masked == best)
    rank = jnp.zeros((), jnp.int32)
    for ax in axes:
        rank = rank * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    rank_masked = jnp.where(cand, rank, _BIG_I32)
    rank_best = _reduce(rank_masked, axes, jax.lax.pmin)
    return cand & (rank == rank_best)


def _pick(x, w, axes):
    """Replicate the winning device's x (w: winner one-hot). where, not
    multiply: inf * 0 would poison the psum with NaN."""
    return _reduce(jnp.where(w, x, jnp.zeros((), x.dtype)), axes, jax.lax.psum)


def build_batch_agg(mesh: Mesh, num_segments: int,
                    sel_names: tuple = ()):
    """The executor's aggregate batch step over a device mesh: the exact
    multi-chip equivalent of templates.AggBatch's single-device kernels.

    Takes row-sharded (values, rel_hi, rel_lo, seg_ids, mask, global_idx)
    and returns replicated per-segment outputs. count/sum/mean and
    min/max/spread VALUES are plain psum/pmin/pmax; the winner one-hot
    machinery (several collective rounds each) is built only for the
    selectors in `sel_names` — their `<name>_sel` outputs are global row
    indices the executor resolves against host-side ns times exactly like
    the single-device sel contract (reference: the store-side aggregate
    cursors + coordinator merge collapsed into one SPMD program)."""
    axes = mesh.axis_names

    def step(values, rel_hi, rel_lo, seg_ids, mask, gidx):
        n_rows = values.shape[0]

        def tkeys(sel):
            safe = jnp.clip(sel, 0, n_rows - 1)
            return rel_hi[safe], rel_lo[safe], gidx[safe]

        c = seg.seg_count(seg_ids, num_segments, mask)
        s = seg.seg_sum(values, seg_ids, num_segments, mask)
        valid = c > 0
        totc = _reduce(c, axes, jax.lax.psum)
        tots = _reduce(s, axes, jax.lax.psum)
        mn = _reduce(seg.seg_min(values, seg_ids, num_segments, mask),
                     axes, jax.lax.pmin)
        mx = _reduce(seg.seg_max(values, seg_ids, num_segments, mask),
                     axes, jax.lax.pmax)
        out = {
            "count": totc,
            "sum": tots,
            "mean": tots / jnp.maximum(totc, 1).astype(tots.dtype),
            "min": mn,
            "max": mx,
            "spread": mx - mn,
        }
        local_sel = {
            "min": lambda: seg.seg_min_selector(
                values, rel_hi, rel_lo, seg_ids, num_segments, mask),
            "max": lambda: seg.seg_max_selector(
                values, rel_hi, rel_lo, seg_ids, num_segments, mask),
            "first": lambda: seg.seg_first(
                values, rel_hi, rel_lo, seg_ids, num_segments, mask),
            "last": lambda: seg.seg_last(
                values, rel_hi, rel_lo, seg_ids, num_segments, mask),
        }
        for name in sel_names:
            v, sel = local_sel[name]()
            th, tl, gsel = tkeys(sel)
            if name == "min":
                keys = [(v, True), (th, True), (tl, True)]
            elif name == "max":
                keys = [(v, False), (th, True), (tl, True)]
            elif name == "first":
                # time ties take the larger value (reference FirstReduce)
                keys = [(th, True), (tl, True), (v, False)]
            else:
                keys = [(th, False), (tl, False), (v, False)]
            w = _winner(keys, valid, axes)
            out[name] = _pick(v, w, axes)
            out[name + "_sel"] = _pick(gsel, w, axes)
        return out

    sharded = jax.shard_map(step, mesh=mesh, in_specs=(P(axes),) * 6,
                            out_specs=P(), check_vma=False)
    return jax.jit(sharded)


_BATCH_AGG_CACHE: dict = {}


def batch_agg_jit(mesh: Mesh, num_segments: int, sel_names: tuple = ()):
    key = (mesh, num_segments, sel_names)
    fn = _BATCH_AGG_CACHE.get(key)
    if fn is None:
        from opengemini_tpu.utils import devobs

        devobs.note_compile("mesh_batch_agg",
                            (mesh.size, num_segments, sel_names))
        fn = _BATCH_AGG_CACHE[key] = build_batch_agg(
            mesh, num_segments, sel_names)
    return fn


def shard_rows(mesh: Mesh, *arrays, xfer_site: str = "agg-batch"):
    """Pad 1D row arrays to a multiple of the mesh size (padding masked
    out by callers via the mask array convention) and device_put them with
    the row sharding — the 1D special case of shard_leading_axis."""
    return shard_leading_axis(mesh, *arrays, xfer_site=xfer_site)


def shard_leading_axis(mesh: Mesh, *arrays, xfer_site: str = "mesh-shard"):
    """device_put matrices with their LEADING axis sharded over every mesh
    axis (remaining axes replicated per device). This is how the dense
    layouts (models/ragged.py bucket matrices, models/grid.py grids) go
    multi-chip: their rows are independent — one segment/series-run lives
    in exactly one row — so the per-row dense reduces partition with ZERO
    collectives; GSPMD compiles the same kernels row-parallel and the host
    gathers (num_rows,)-shaped outputs. The reference needs an exchange +
    merge pipeline here (rpc_transform.go:117); the dense layout makes the
    merge a no-op by construction.

    Rows are padded (zeros -> masked out by the kernels' mask plane or
    sliced off by the [:g] caller convention) to a multiple of mesh.size.

    The pad and the puts are one `mesh_shard` span of the request that
    asked for them (`query_stages/mesh_shard_ns`; in a capture the idle
    gap it causes is named `ogt:mesh_shard`).  It times the pad
    (np.concatenate) and the ENQUEUE of the puts: `device_put` returns
    before the bytes have moved, as `prom_values_h2d` does, so the wait
    for them stays where it is paid, in the fetch's `device_wait`.
    Counters, one update a call: `device/mesh_dense_batches`,
    `mesh_h2d_bytes`, and — a row is a slice of the leading axis, counted
    once an array — `mesh_put_rows` (rows put, padding included) and
    `mesh_pad_rows` (of them, rows the padding added), so that padding is
    a share and not a guess; `mesh_shard_devices` is set to the devices
    the newest batch landed on.
    """
    import time as _time

    from opengemini_tpu.utils import devobs, tracing
    from opengemini_tpu.utils.stats import GLOBAL as _STATS

    n_dev = mesh.size
    n = arrays[0].shape[0]
    npad = (n + n_dev - 1) // n_dev * n_dev
    out = []
    nbytes = 0
    with tracing.span("mesh_shard", arrays=len(arrays), rows=n,
                      pad_rows=npad - n) as sp:
        t0 = _time.perf_counter_ns()
        for a in arrays:
            if npad != n:
                pad = np.zeros((npad - n,) + a.shape[1:], dtype=a.dtype)
                a = np.concatenate([a, pad])
            out.append(jax.device_put(a, leading_axis_sharding(mesh, a.ndim)))
            nbytes += int(a.nbytes)
        enqueue_s = (_time.perf_counter_ns() - t0) / 1e9
        sp.add_field("bytes", nbytes)
    # every byte here is a host->device transfer a warm mesh query should
    # NOT repeat (the colcache device tier retains the sharded buffers);
    # tests/test_multichip.py asserts mesh_h2d_bytes is flat across warm runs
    _STATS.add("device", (("mesh_dense_batches", 1),
                          ("mesh_h2d_bytes", nbytes),
                          ("mesh_put_rows", npad * len(arrays)),
                          ("mesh_pad_rows", (npad - n) * len(arrays))))
    # how many devices the newest batch really landed on: a mesh that
    # put every shard on the first chip would read 1 here
    _STATS.set("device", "mesh_shard_devices",
               len({s.device.id for s in out[0].addressable_shards}))
    devobs.note_transfer("h2d", xfer_site, nbytes, enqueue_s)
    return tuple(out)


def leading_axis_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """The explicit NamedSharding of shard_leading_axis: leading axis
    partitioned over EVERY mesh axis, remaining axes replicated."""
    return NamedSharding(mesh, P(mesh.axis_names, *([None] * (ndim - 1))))


@functools.lru_cache(maxsize=64)
def _reshard_jit(out_shardings, avals):
    """Compiled identity resharding program, cached per (target sharding,
    shapes/dtypes). donate_argnums frees the stale source layout as the
    new one materializes — a mesh swap never holds both copies resident
    (donation is a no-op on backends that don't implement it, e.g. the
    CPU virtual mesh; the warning is suppressed at the call site)."""
    from opengemini_tpu.utils import devobs

    devobs.note_compile("reshard", avals)
    n = len(avals)
    return jax.jit(
        lambda *xs: xs,
        out_shardings=(out_shardings,) * n,
        donate_argnums=tuple(range(n)),
    )


def donate_reshard(target_sharding, *arrays):
    """Device-to-device relayout of already-resident arrays onto
    ``target_sharding``, DONATING the inputs. This is how the colcache
    device tier follows a runtime.set_mesh() change: the retained grid
    buffers move to the new mesh layout without a host round trip and
    without doubling resident bytes.

    jit only accepts donation when source and target span the SAME
    device set; a mesh shrink/grow (8 -> 4 devices) relayouts via
    jax.device_put instead — no donation there, the stale buffers free
    by refcount the moment the caller swaps them out."""
    import time as _time
    import warnings

    from opengemini_tpu.utils import devobs
    from opengemini_tpu.utils.stats import GLOBAL as _STATS

    _STATS.incr("device", "mesh_reshards")
    nbytes = sum(int(a.nbytes) for a in arrays)
    t0 = _time.perf_counter_ns()
    same_devices = all(
        set(a.sharding.device_set) == set(target_sharding.device_set)
        for a in arrays)
    if not same_devices:
        out = tuple(jax.device_put(a, target_sharding) for a in arrays)
        devobs.note_transfer("reshard", "reshard", nbytes,
                             (_time.perf_counter_ns() - t0) / 1e9)
        return out
    avals = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
    fn = _reshard_jit(target_sharding, avals)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message=".*donated buffers were not usable.*")
        out = fn(*arrays)
    devobs.note_transfer("reshard", "reshard", nbytes,
                         (_time.perf_counter_ns() - t0) / 1e9)
    return out

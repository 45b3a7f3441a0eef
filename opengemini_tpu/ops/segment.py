"""Masked segmented reductions — the device hot loop.

Each aggregate over (series-group, time-window) segments is a masked
segmented reduction with segment id ``group_id * num_windows + window_id``.
Rows arrive series-major and time-sorted within a series, so segment ids are
sorted within each series run — ``indices_are_sorted`` is still False
globally (multiple series interleave). These scatter-based forms are the
general fallback; the hot paths are the dense layouts (``grid_window_agg_t``
here, bucket matrices in ``models/ragged.py``), whose fused Pallas tile
kernels live in ``ops/pallas_segment.py`` and engage on TPU backends.

This replaces the reference's generated scalar reduce loops
(engine/series_agg_func.gen.go: floatSumReduce:47 etc., 45 fns;
series_agg_reducer.gen.go, 148 fns): one masked-segment-reduce per aggregate
instead of one hand-written loop per (type, agg).

All functions are pure and jit-traceable; ``num_segments`` must be static.
Null semantics: ``mask`` False rows contribute nothing; empty segments
produce count==0 and the executor renders them as null/fill values
(reference nil-bitmap semantics, lib/record/column.go:30).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# plain int (not jnp scalar): keeps module import free of backend init
_BIG_I32 = 2**31 - 1


def seg_sum(values, seg_ids, num_segments: int, mask):
    data = jnp.where(mask, values, jnp.zeros((), values.dtype))
    return jax.ops.segment_sum(data, seg_ids, num_segments=num_segments)


def seg_count(seg_ids, num_segments: int, mask):
    data = mask.astype(jnp.int32)
    return jax.ops.segment_sum(data, seg_ids, num_segments=num_segments)


def seg_min(values, seg_ids, num_segments: int, mask):
    big = _type_max(values.dtype)
    data = jnp.where(mask, values, big)
    return jax.ops.segment_min(data, seg_ids, num_segments=num_segments)


def seg_max(values, seg_ids, num_segments: int, mask):
    small = _type_min(values.dtype)
    data = jnp.where(mask, values, small)
    return jax.ops.segment_max(data, seg_ids, num_segments=num_segments)


def seg_mean(values, seg_ids, num_segments: int, mask):
    s = seg_sum(values, seg_ids, num_segments, mask)
    c = seg_count(seg_ids, num_segments, mask)
    return s / jnp.maximum(c, 1).astype(s.dtype)


def seg_sumsq(values, seg_ids, num_segments: int, mask):
    data = jnp.where(mask, values * values, jnp.zeros((), values.dtype))
    return jax.ops.segment_sum(data, seg_ids, num_segments=num_segments)


def seg_stddev(values, seg_ids, num_segments: int, mask):
    """Sample stddev, n-1 denominator (influx stddev semantics, reference
    engine/series_agg_func.gen.go float stddev reducers).

    Two-pass (mean, then squared deviations): the one-pass sum-of-squares
    formula cancels catastrophically for large means, especially in f32 on
    TPU. Cost is still two segment-sums — same shape on device.
    """
    mean = seg_mean(values, seg_ids, num_segments, mask)
    dev = values - mean[seg_ids]
    ssd = jax.ops.segment_sum(
        jnp.where(mask, dev * dev, jnp.zeros((), values.dtype)),
        seg_ids,
        num_segments=num_segments,
    )
    c = seg_count(seg_ids, num_segments, mask).astype(values.dtype)
    var = ssd / jnp.maximum(c - 1, 1)
    return jnp.sqrt(jnp.maximum(var, 0))


def seg_first(values, rel_hi, rel_lo, seg_ids, num_segments: int, mask):
    """(value, row_idx) of the earliest valid row per segment.

    Timestamps arrive as an EXACT lexicographic int32 pair
    (rel_hi = rel_ns >> 30, rel_lo = rel_ns & (2^30-1)) so ns-precision
    ordering survives on devices without int64. True ns ties pick the
    LARGER VALUE — the reference first/last rule (engine/executor/
    agg_func.go FirstReduce: `times == && v > firstValue`,
    TestServer_Query_Aggregates_IdenticalTime); value ties then fall to
    scan order."""
    return _seg_extreme_by_time(
        values, rel_hi, rel_lo, seg_ids, num_segments, mask, latest=False
    )


def seg_last(values, rel_hi, rel_lo, seg_ids, num_segments: int, mask):
    return _seg_extreme_by_time(
        values, rel_hi, rel_lo, seg_ids, num_segments, mask, latest=True
    )


def _seg_extreme_by_time(values, rel_hi, rel_lo, seg_ids, num_segments, mask, latest):
    n = values.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    smax = lambda d: jax.ops.segment_max(d, seg_ids, num_segments=num_segments)  # noqa: E731
    smin = lambda d: jax.ops.segment_min(d, seg_ids, num_segments=num_segments)  # noqa: E731
    if latest:
        hi_ext = smax(jnp.where(mask, rel_hi, -_BIG_I32))
        cand = mask & (rel_hi == hi_ext[seg_ids])
        lo_ext = smax(jnp.where(cand, rel_lo, -_BIG_I32))
        cand &= rel_lo == lo_ext[seg_ids]
    else:
        hi_ext = smin(jnp.where(mask, rel_hi, _BIG_I32))
        cand = mask & (rel_hi == hi_ext[seg_ids])
        lo_ext = smin(jnp.where(cand, rel_lo, _BIG_I32))
        cand &= rel_lo == lo_ext[seg_ids]
    # exact-time ties: larger value wins (reference FirstReduce/LastReduce)
    v_ext = smax(jnp.where(cand, values, _type_min(values.dtype)))
    cand &= values == v_ext[seg_ids]
    sel = smin(jnp.where(cand, idx, _BIG_I32))
    safe = jnp.clip(sel, 0, n - 1)
    return values[safe], sel


def seg_min_selector(values, rel_hi, rel_lo, seg_ids, num_segments: int, mask):
    """min() as a *selector*: also returns the row index of the selected
    row — InfluxQL bare-selector queries return the point's own time
    (reference MinReduce keeps the row, series_agg_func.gen.go); the host
    resolves the index against its exact int64 ns times. Value ties break
    by EARLIEST TIMESTAMP (then scan order), matching the reference's
    time-ordered merge — batch scan order alone is series-major, not
    time-ordered, across series in one group."""
    return _seg_extreme_by_value(
        values, rel_hi, rel_lo, seg_ids, num_segments, mask, want_max=False
    )


def seg_max_selector(values, rel_hi, rel_lo, seg_ids, num_segments: int, mask):
    return _seg_extreme_by_value(
        values, rel_hi, rel_lo, seg_ids, num_segments, mask, want_max=True
    )


def _seg_extreme_by_value(values, rel_hi, rel_lo, seg_ids, num_segments, mask, want_max):
    n = values.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    smin = lambda d: jax.ops.segment_min(d, seg_ids, num_segments=num_segments)  # noqa: E731
    if want_max:
        v_ext = seg_max(values, seg_ids, num_segments, mask)
    else:
        v_ext = seg_min(values, seg_ids, num_segments, mask)
    cand = mask & (values == v_ext[seg_ids])
    hi_best = smin(jnp.where(cand, rel_hi, _BIG_I32))
    cand &= rel_hi == hi_best[seg_ids]
    lo_best = smin(jnp.where(cand, rel_lo, _BIG_I32))
    cand &= rel_lo == lo_best[seg_ids]
    sel = smin(jnp.where(cand, idx, _BIG_I32))
    return v_ext, sel


def _sort_by_segment(values, seg_ids, num_segments, mask):
    """Shared prologue for rank-based aggregates: rows sorted by
    (segment, value) with invalid rows pushed into a trailing dummy segment.
    Returns (sorted_values, sorted_seg, counts, starts)."""
    sort_seg = jnp.where(mask, seg_ids, num_segments)
    order = jnp.lexsort((values, sort_seg))
    counts = seg_count(seg_ids, num_segments, mask)
    starts = jnp.cumsum(counts) - counts
    return values[order], sort_seg[order], counts, starts


def seg_percentile(values, seg_ids, num_segments: int, mask, q: float):
    """Nearest-rank percentile per segment (InfluxQL percentile(): returns
    an actual sample, rank = floor(n*q/100 + 0.5) — the lifted influx rule
    (FloatPercentileReduceSlice); reference engine/executor/agg_func.go
    percentile processors)."""
    n = values.shape[0]
    sorted_vals, _, counts, starts = _sort_by_segment(values, seg_ids, num_segments, mask)
    rank = jnp.floor(q / 100.0 * counts + 0.5).astype(jnp.int32)
    rank = jnp.clip(rank - 1, 0, jnp.maximum(counts - 1, 0))
    sel = jnp.clip(starts + rank, 0, n - 1)
    return sorted_vals[sel]


def seg_median(values, seg_ids, num_segments: int, mask):
    """InfluxQL median(): middle value, or mean of the two middles for even
    counts (reference agg_func.go median handling)."""
    n = values.shape[0]
    sorted_vals, _, counts, starts = _sort_by_segment(values, seg_ids, num_segments, mask)
    lo = starts + jnp.maximum((counts - 1) // 2, 0)
    hi = starts + jnp.maximum(counts // 2, 0)
    lo_v = sorted_vals[jnp.clip(lo, 0, n - 1)]
    hi_v = sorted_vals[jnp.clip(hi, 0, n - 1)]
    return (lo_v + hi_v) / 2


def seg_count_distinct(values, seg_ids, num_segments: int, mask):
    """count(distinct(field)) — sort by (seg, value), count run heads."""
    sv, ss, _, _ = _sort_by_segment(values, seg_ids, num_segments, mask)
    head = jnp.ones_like(ss, dtype=jnp.int32)
    same = (ss[1:] == ss[:-1]) & (sv[1:] == sv[:-1])
    head = head.at[1:].set(jnp.where(same, 0, 1))
    head = jnp.where(ss < num_segments, head, 0)
    return jax.ops.segment_sum(head, jnp.clip(ss, 0, num_segments - 1), num_segments=num_segments)


def grid_window_agg(values, mask, windows_per_series: int):
    """Regular-grid fast path: when a chunk's timestamps are a constant
    stride (the TSF encoder already detects this — storage/encoding.py
    _T_CONST blocks) and windows divide the grid evenly, windowed
    aggregation is a pure dense reshape-reduce: (S, R) -> (S, W, R/W) ->
    reduce. No scatter; memory-bound optimal on TPU (VPU/MXU friendly,
    XLA fuses the mask). This replaces the reference's pre-aggregation
    block skipping *and* its per-row interval loop for the regular case
    (engine/immutable/pre_aggregation.go, aggregate_cursor.go:343).

    values, mask: (num_series, rows_per_series); rows_per_series must be a
    multiple of windows_per_series. Returns dict of (S, W) arrays.
    """
    s_dim, r = values.shape
    w = windows_per_series
    k = r // w
    v = values.reshape(s_dim, w, k)
    m = mask.reshape(s_dim, w, k)
    vz = jnp.where(m, v, jnp.zeros((), values.dtype))
    cnt = m.sum(axis=-1, dtype=jnp.int32)
    s = vz.sum(axis=-1)
    mn = jnp.where(m, v, _type_max(values.dtype)).min(axis=-1)
    mx = jnp.where(m, v, _type_min(values.dtype)).max(axis=-1)
    mean = s / jnp.maximum(cnt, 1).astype(s.dtype)
    return {"sum": s, "count": cnt, "mean": mean, "min": mn, "max": mx}


def grid_window_agg_t(values_t, mask_t):
    """Regular-grid fast path in the TPU-native layout: values_t is
    (num_series, samples_per_window, num_windows) — windows on the LANE
    axis, within-window samples on sublanes, so every per-window stat is a
    sublane-axis reduce (against the last-axis layout above: not
    measured on the present code).
    Production wiring: models/grid.py GridBatch assembles scanned chunks
    directly in this layout when the data is stride-regular (pick_batch
    routes GROUP BY time() aggregates there).

    Returns dict of (num_series, num_windows) arrays.
    """
    vz = jnp.where(mask_t, values_t, jnp.zeros((), values_t.dtype))
    cnt = mask_t.sum(axis=1, dtype=jnp.int32)
    s = vz.sum(axis=1)
    mn = jnp.where(mask_t, values_t, _type_max(values_t.dtype)).min(axis=1)
    mx = jnp.where(mask_t, values_t, _type_min(values_t.dtype)).max(axis=1)
    mean = s / jnp.maximum(cnt, 1).astype(s.dtype)
    return {"sum": s, "count": cnt, "mean": mean, "min": mn, "max": mx}


# ---------------------------------------------------------------------------
# Tiled interval reductions (time-centric batch operators, TiLT
# arXiv:2301.12030): per-(series, tile) partials answered per window from
# cumulative tile prefixes.  Shared by the PromQL range-vector engine
# (ops/prom.py TiledPrepared): every window is an exact union of
# left-open/right-closed time tiles, so these helpers replace the per-window
# sample walks (vmap'd searchsorted + dense membership tensors) with O(1)
# prefix lookups.  `xp` is numpy or jax.numpy — the host path answers in
# numpy (no dispatch/compile cost on CPU backends), the device path traces
# the identical code under jit.
# ---------------------------------------------------------------------------


def tile_window_sums(tile_vals, ca, cb, xp=None):
    """Per-window sums over contiguous compact-tile ranges [ca, cb) from
    ONE cumulative pass over the tile partials.

    tile_vals: (S, C) per-(series, tile) partial sums; ca/cb: (S, K) int
    compact positions (cb exclusive).  Returns (S, K)."""
    if xp is None:
        xp = jnp
    s_dim = tile_vals.shape[0]
    cc = xp.cumsum(tile_vals, axis=1)
    cc = xp.concatenate(
        [xp.zeros((s_dim, 1), dtype=tile_vals.dtype), cc], axis=1)
    return (xp.take_along_axis(cc, cb, axis=1)
            - xp.take_along_axis(cc, ca, axis=1))


def _accumulate_extreme(x, axis, want_min: bool, reverse: bool, xp):
    if xp is not jnp:  # numpy host path
        import numpy as _np

        op = _np.minimum if want_min else _np.maximum
        if reverse:
            x = _np.flip(x, axis=axis)
        out = op.accumulate(x, axis=axis)
        return _np.flip(out, axis=axis) if reverse else out
    from jax import lax

    fn = lax.cummin if want_min else lax.cummax
    return fn(x, axis=axis, reverse=reverse)


def tile_sliding_extreme(tile_vals, win_tiles: int, start_pos, want_min: bool,
                         xp=None):
    """min/max over EXACTLY win_tiles consecutive tiles starting at compact
    position start_pos (S, K): the fixed-length sliding-extreme trick —
    block the tile axis at the window length, scan each block prefix-from-
    left and suffix-from-right, and any length-L range [i, i+L) spans at
    most two blocks, so its extreme is suffix_at(i) combined with
    prefix_at(i+L-1).  O(C) build, O(1) per window — no dense membership
    tensor, no per-sample rescan (the old chunked (S, 256, N) path)."""
    if xp is None:
        xp = jnp
    import numpy as _np

    s_dim, c_dim = tile_vals.shape
    # identity element computed with numpy dtype logic: the host path must
    # not touch a jax backend just to pick +/-inf
    ndt = _np.dtype(str(tile_vals.dtype))
    if _np.issubdtype(ndt, _np.floating):
        fill = ndt.type(_np.inf if want_min else -_np.inf)
    else:
        info = _np.iinfo(ndt)
        fill = ndt.type(info.max if want_min else info.min)
    ln = max(int(win_tiles), 1)
    blocks = (c_dim + ln - 1) // ln
    pad = blocks * ln - c_dim
    x = xp.concatenate(
        [tile_vals, xp.full((s_dim, pad), fill, dtype=tile_vals.dtype)],
        axis=1) if pad else tile_vals
    x3 = x.reshape(s_dim, blocks, ln)
    suf = _accumulate_extreme(x3, 2, want_min, reverse=True, xp=xp)
    pre = _accumulate_extreme(x3, 2, want_min, reverse=False, xp=xp)
    suf = suf.reshape(s_dim, blocks * ln)
    pre = pre.reshape(s_dim, blocks * ln)
    hi = xp.clip(start_pos + (ln - 1), 0, blocks * ln - 1)
    lo = xp.clip(start_pos, 0, blocks * ln - 1)
    a = xp.take_along_axis(suf, lo, axis=1)
    b = xp.take_along_axis(pre, hi, axis=1)
    return xp.minimum(a, b) if want_min else xp.maximum(a, b)


def _type_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _type_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)

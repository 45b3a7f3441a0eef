"""PromQL range-vector functions: tiled interval reductions + dense kernels.

Reference: the store-side prom cursors + reducers
(engine/prom_range_vector_cursor.go, prom_function_reducers.go:633) which
walk samples per series per step.

Two generations live here:

  * The TILED engine (TilePlan / TiledPrepared, bottom of the module —
    the production path): time-interval-centric batch operators in the
    TiLT style (arXiv:2301.12030).  Window edges define a ms tile
    lattice, samples bucket by integer arithmetic, and every
    (series, step) window answers from cumulative tile prefixes plus two
    boundary refinements — O(1) per window, no searchsorted, no dense
    membership tensors.  One xp-generic code path runs as host numpy,
    eager jax.numpy, or traced under jit (the engine's accelerator
    path is eager today).

  * The DENSE kernels (top of the module): padded (num_series,
    max_samples) matrices, vmap'd searchsorted window bounds, chunked
    (S, chunk, N) membership tensors for the non-prefix-able forms.
    They remain as the fallback for window grids the tile lattice cannot
    express (sub-ms edges, over-budget tile counts) and for
    quantile/mad/holt_winters, and as the reference the tests hold the
    tiled engine equal to.

Semantics follow Prometheus exactly (promql/functions.go extrapolatedRate):
  - counter resets: correction[i] = v[i-1] if v[i] < v[i-1], restricted
    to sample pairs fully inside the window
  - extrapolation to window bounds, limited to 1.1x average sample
    interval, and clamped to zero-crossing for counters.

All timestamps here are int64 milliseconds (prom's unit) on the HOST;
kernels see float seconds relative to a base — callers produce them via
`prepare_matrix_runs` (dense) or `prepare_tiled`.
"""

from __future__ import annotations

import functools as _functools

import jax.numpy as jnp
import numpy as np

from opengemini_tpu.utils import tracing


def prepare_matrix(series_samples: list[tuple[np.ndarray, np.ndarray]], dtype=np.float32):
    """[(times_ms int64, values f64)] -> padded matrices.

    Returns (times_s f64-as-dtype relative to base, values, counts, base_ms).
    Times must be sorted ascending per series.
    """
    S = len(series_samples)
    n_max = max((len(t) for t, _v in series_samples), default=0)
    n_max = max(n_max, 1)
    base_ms = min((int(t[0]) for t, _v in series_samples if len(t)), default=0)
    times = np.zeros((S, n_max), dtype=np.float64)
    values = np.zeros((S, n_max), dtype=dtype)
    counts = np.zeros(S, dtype=np.int32)
    for i, (t, v) in enumerate(series_samples):
        k = len(t)
        counts[i] = k
        times[i, :k] = (t - base_ms) / 1000.0
        values[i, :k] = v
        if k:  # pad tail with a huge time so searchsorted never picks it
            times[i, k:] = np.inf
        else:
            times[i, :] = np.inf
    return times, values, counts, base_ms


def prepare_matrix_runs(t_ms_all, v_all, lens, dtype=np.float32):
    """prepare_matrix over run-encoded input: one concatenated (times_ms,
    values) pair with per-series lengths, filled by ONE flat scatter — no
    per-series Python loop (an instant query of BASELINE.md config #5
    spans 1M series)."""
    lens = np.asarray(lens, np.int64)
    S = len(lens)
    n_max = max(1, int(lens.max()) if S else 1)
    times = np.full((S, n_max), np.inf, dtype=np.float64)
    values = np.zeros((S, n_max), dtype=dtype)
    base_ms = _runs_base_ms(t_ms_all, lens)
    if int(lens.sum()):
        flat = _scatter_index(lens, n_max)
        times.reshape(-1)[flat] = (np.asarray(t_ms_all) - base_ms) / 1000.0
        values.reshape(-1)[flat] = v_all
    return times, values, lens.astype(np.int32), base_ms


def _runs_base_ms(t_ms_all, lens) -> int:
    """What a prepare's seconds are relative to: the earliest sample.
    Times are ascending per series, so the global min is the min of each
    non-empty series' first sample; 0 where there is none."""
    starts = np.cumsum(lens) - lens
    first = starts[lens > 0]
    return int(np.asarray(t_ms_all)[first].min()) if len(first) else 0


def _scatter_index(lens, n_max: int):
    """The flat cell of a padded (S, n_max) matrix each run-encoded sample
    lands in: sample j of the concatenation is column j - starts[i] of its
    series' row i, so the index is j plus one per-series offset."""
    starts = np.cumsum(lens) - lens
    return (np.arange(int(lens.sum()), dtype=np.int64)
            + np.repeat(np.arange(len(lens), dtype=np.int64) * n_max - starts,
                        lens))


def window_bounds(times, counts, step_starts, step_ends):
    """Per (series, step) first/last sample indices inside (start, end].

    times: (S, N) seconds; step_starts/step_ends: (K,) seconds.
    Returns (first_idx, last_idx, has_samples) each (S, K).
    Prom windows are left-OPEN right-CLOSED: (t-w, t].
    """
    first_idx = _vmap_searchsorted(times, step_starts, "right")
    last_idx = _vmap_searchsorted(times, step_ends, "right") - 1
    has = (last_idx >= first_idx) & (first_idx < counts[:, None])
    return first_idx, last_idx, has


def _vmap_searchsorted(times, keys, side):
    import jax

    return jax.vmap(lambda row: jnp.searchsorted(row, keys, side=side))(times)


def _gather_rows(mat, idx):
    return jnp.take_along_axis(mat, idx, axis=1)


def reset_corrections(values, counts):
    """Per-series prefix sum of counter-reset corrections:
    C[i] = sum_{j<=i} (v[j-1] if v[j] < v[j-1] else 0). (S, N)."""
    prev = jnp.concatenate([values[:, :1], values[:, :-1]], axis=1)
    drop = jnp.where(values < prev, prev, jnp.zeros((), values.dtype))
    drop = drop.at[:, 0].set(0)
    n = values.shape[1]
    valid = jnp.arange(n)[None, :] < counts[:, None]
    return jnp.cumsum(jnp.where(valid, drop, 0), axis=1)


def extrapolated_rate(
    times, values, counts, step_starts, step_ends,
    window_s: float, is_counter: bool, is_rate: bool,
):
    """Prometheus extrapolatedRate for every (series, step).

    Returns (out (S, K), valid (S, K)); valid requires >= 2 samples in the
    window (prom semantics).
    """
    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    safe_first = jnp.clip(first_idx, 0, times.shape[1] - 1)
    safe_last = jnp.clip(last_idx, 0, times.shape[1] - 1)
    t_first = _gather_rows(times, safe_first)
    t_last = _gather_rows(times, safe_last)
    v_first = _gather_rows(values, safe_first)
    v_last = _gather_rows(values, safe_last)
    n_samples = last_idx - first_idx + 1
    valid = has & (n_samples >= 2)

    delta = v_last - v_first
    if is_counter:
        cum = reset_corrections(values, counts)
        c_first = _gather_rows(cum, safe_first)
        c_last = _gather_rows(cum, safe_last)
        delta = delta + (c_last - c_first)

    # prom extrapolation (promql/functions.go extrapolatedRate)
    sampled_interval = t_last - t_first
    sampled_interval = jnp.where(sampled_interval <= 0, 1.0, sampled_interval)
    avg_interval = sampled_interval / jnp.maximum(n_samples - 1, 1).astype(times.dtype)
    dur_to_start = t_first - step_starts[None, :]
    dur_to_end = step_ends[None, :] - t_last
    extrap_threshold = avg_interval * 1.1
    dur_to_start = jnp.where(dur_to_start > extrap_threshold, avg_interval / 2, dur_to_start)
    dur_to_end = jnp.where(dur_to_end > extrap_threshold, avg_interval / 2, dur_to_end)
    if is_counter:
        # a counter cannot extrapolate below zero (prom applies this only
        # for delta > 0 AND v_first >= 0, promql/functions.go)
        dur_zero = jnp.where(
            (delta > 0) & (v_first >= 0),
            sampled_interval * (v_first / jnp.maximum(delta, 1e-30)),
            jnp.inf,
        )
        dur_to_start = jnp.minimum(dur_to_start, dur_zero)
    extrapolated = sampled_interval + dur_to_start + dur_to_end
    out = delta.astype(times.dtype) * (extrapolated / sampled_interval)
    if is_rate:
        out = out / window_s
    return out, valid


def over_time(times, values, counts, step_starts, step_ends, func: str):
    """xxx_over_time functions: avg/min/max/sum/count/last. (S, K).

    sum/avg/count/last use the O(S*K) prefix-sum+gather scheme (no dense
    (S, K, N) tensor). min/max have no prefix form; they use a dense
    window-membership tensor computed in step CHUNKS so peak memory stays
    bounded at S * 256 * N booleans.
    """
    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    n = times.shape[1]
    if func in ("sum", "avg", "count", "last"):
        if func == "last":
            safe_last = jnp.clip(last_idx, 0, n - 1)
            return _gather_rows(values, safe_last), has
        valid_cols = jnp.arange(n)[None, :] < counts[:, None]
        csum = jnp.cumsum(jnp.where(valid_cols, values, 0), axis=1)
        csum = jnp.concatenate([jnp.zeros_like(csum[:, :1]), csum], axis=1)  # (S, N+1)
        safe_f = jnp.clip(first_idx, 0, n)
        safe_l1 = jnp.clip(last_idx + 1, 0, n)
        wsum = _gather_rows(csum, safe_l1) - _gather_rows(csum, safe_f)
        wcnt = (last_idx - first_idx + 1).astype(values.dtype)
        wcnt = jnp.where(has, wcnt, 0)
        if func == "count":
            return wcnt, has
        if func == "sum":
            return jnp.where(has, wsum, 0), has
        return jnp.where(has, wsum, 0) / jnp.maximum(wcnt, 1), has
    if func in ("stddev", "stdvar"):
        # population variance over window samples (prom funcStddevOverTime)
        # via prefix sums. Variance is shift-invariant, so values are
        # centered on the per-series mean FIRST: raw v^2 prefix sums over
        # a long series of large-magnitude samples (e.g. ~1.7e9 unix-
        # timestamp gauges) reach ~3e22 and the window difference loses
        # every significant digit (verified: naive form returned -4e5
        # where the true variance was 0.65)
        valid_cols = jnp.arange(n)[None, :] < counts[:, None]
        vz_raw = jnp.where(valid_cols, values, 0)
        series_n = jnp.maximum(counts, 1).astype(values.dtype)[:, None]
        center = vz_raw.sum(axis=1, keepdims=True) / series_n
        vz = jnp.where(valid_cols, values - center, 0)
        c1 = jnp.cumsum(vz, axis=1)
        c2 = jnp.cumsum(vz * vz, axis=1)
        zcol = jnp.zeros_like(c1[:, :1])
        c1 = jnp.concatenate([zcol, c1], axis=1)
        c2 = jnp.concatenate([zcol, c2], axis=1)
        safe_f = jnp.clip(first_idx, 0, n)
        safe_l1 = jnp.clip(last_idx + 1, 0, n)
        ws = _gather_rows(c1, safe_l1) - _gather_rows(c1, safe_f)
        wss = _gather_rows(c2, safe_l1) - _gather_rows(c2, safe_f)
        wcnt = jnp.where(has, (last_idx - first_idx + 1), 0).astype(values.dtype)
        denom = jnp.maximum(wcnt, 1)
        mean = ws / denom
        var = jnp.maximum(wss / denom - mean * mean, 0)
        out = var if func == "stdvar" else jnp.sqrt(var)
        return jnp.where(has, out, 0), has
    if func == "present":
        return jnp.where(has, 1.0, 0.0).astype(values.dtype), has
    if func in ("min", "max"):
        k = step_starts.shape[0]
        chunk = 256
        outs = []
        fill = jnp.inf if func == "min" else -jnp.inf
        for c0 in range(0, k, chunk):
            in_win, v = _window_tensor(times, values, counts, first_idx,
                                       last_idx, c0, chunk)
            if func == "min":
                outs.append(jnp.where(in_win, v, fill).min(axis=2))
            else:
                outs.append(jnp.where(in_win, v, fill).max(axis=2))
        return jnp.concatenate(outs, axis=1), has
    raise ValueError(f"unsupported over_time func {func!r}")


def _window_tensor(times, values, counts, first_idx, last_idx, c0, chunk):
    """Masked (S, C, N) membership view for one step chunk: (in_win, v)."""
    n = values.shape[1]
    fi = first_idx[:, c0 : c0 + chunk, None]
    li = last_idx[:, c0 : c0 + chunk, None]
    col = jnp.arange(n)[None, None, :]
    in_win = (col >= fi) & (col <= li) & (col < counts[:, None, None])
    return in_win, values[:, None, :]


def quantile_over_time(times, values, counts, step_starts, step_ends, q: float):
    """phi-quantile with linear interpolation over window samples (prom
    funcQuantileOverTime). Dense chunked like min/max; NaN-padded windows
    + nanquantile keep the masked samples out."""
    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    k = step_starts.shape[0]
    chunk = 256
    outs = []
    for c0 in range(0, k, chunk):
        in_win, v = _window_tensor(times, values, counts, first_idx, last_idx, c0, chunk)
        vw = jnp.where(in_win, v, jnp.nan)
        outs.append(jnp.nanquantile(vw, jnp.clip(q, 0.0, 1.0), axis=2))
    out = jnp.concatenate(outs, axis=1)
    if q < 0:
        out = jnp.full_like(out, -jnp.inf)
    elif q > 1:
        out = jnp.full_like(out, jnp.inf)
    return out, has


def mad_over_time(times, values, counts, step_starts, step_ends):
    """median(|v - median(v)|) over window samples (prom mad_over_time)."""
    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    k = step_starts.shape[0]
    chunk = 128  # two dense passes live at once
    outs = []
    for c0 in range(0, k, chunk):
        in_win, v = _window_tensor(times, values, counts, first_idx, last_idx, c0, chunk)
        vw = jnp.where(in_win, v, jnp.nan)
        med = jnp.nanmedian(vw, axis=2, keepdims=True)
        outs.append(jnp.nanmedian(jnp.abs(vw - med), axis=2))
    return jnp.concatenate(outs, axis=1), has


def linear_regression(times, values, counts, step_starts, step_ends):
    """Per-(series, step) least-squares over window samples, centered at
    the window END (the prom eval time): returns (slope per second,
    intercept at eval time, has_2plus). deriv() is the slope;
    predict_linear(v, d) = intercept + slope * d
    (prom promql/functions.go linearRegression)."""
    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    k = step_starts.shape[0]
    chunk = 128
    slopes, intercepts = [], []
    for c0 in range(0, k, chunk):
        in_win, v = _window_tensor(times, values, counts, first_idx, last_idx, c0, chunk)
        t_rel = times[:, None, :] - step_ends[None, c0 : c0 + chunk, None]
        tw = jnp.where(in_win, t_rel, 0.0)
        vw = jnp.where(in_win, v, 0.0)
        cnt = in_win.sum(axis=2).astype(values.dtype)
        denom_n = jnp.maximum(cnt, 1)
        st = tw.sum(axis=2)
        sv = vw.sum(axis=2)
        stt = (tw * tw).sum(axis=2)
        stv = (tw * vw).sum(axis=2)
        cov = stv - st * sv / denom_n
        var = stt - st * st / denom_n
        slope = cov / jnp.where(var == 0, 1.0, var)
        slope = jnp.where(var == 0, 0.0, slope)
        intercept = sv / denom_n - slope * (st / denom_n)
        slopes.append(slope)
        intercepts.append(intercept)
    first_t = _gather_rows(times, jnp.clip(first_idx, 0, times.shape[1] - 1))
    last_t = _gather_rows(times, jnp.clip(last_idx, 0, times.shape[1] - 1))
    has2 = has & (last_t > first_t)
    return (jnp.concatenate(slopes, axis=1), jnp.concatenate(intercepts, axis=1),
            has2)


def holt_winters_window(times, values, counts, step_starts, step_ends,
                        sf: float, tf: float):
    """Prom double exponential smoothing per window
    (funcHoltWinters/double_exponential_smoothing): sequential over the
    window's samples — a lax.scan across the sample axis carrying
    (level, trend) per (series, step), masked to each window's members.
    Windows with <2 samples yield no result."""
    from jax import lax

    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    vj = jnp.asarray(values)  # dynamic scan indexing needs a jax array
    n = values.shape[1]
    k = step_starts.shape[0]
    chunk = 128
    outs, valids = [], []
    for c0 in range(0, k, chunk):
        in_win, _v = _window_tensor(times, values, counts, first_idx, last_idx,
                                    c0, chunk)
        shape = in_win[:, :, 0].shape  # (S, C)

        def body(carry, i):
            # prom recurrence (funcDoubleExponentialSmoothing): sample 0
            # seeds the level; sample 1 seeds the trend then smooths with
            # it; sample j>=2 first updates the trend from the two
            # PREVIOUS levels, then smooths. Result = final level.
            s_prev, s_curr, b, seen = carry
            x = jnp.broadcast_to(vj[:, i][:, None], shape)
            m = in_win[:, :, i]
            is_first = m & (seen == 0)
            is_second = m & (seen == 1)
            later = m & (seen >= 2)
            b_new = jnp.where(later, tf * (s_curr - s_prev) + (1 - tf) * b, b)
            b_new = jnp.where(is_second, x - s_curr, b_new)
            smooth = sf * x + (1 - sf) * (s_curr + b_new)
            upd = is_second | later
            new_s_prev = jnp.where(upd, s_curr, s_prev)
            new_s_curr = jnp.where(upd, smooth, jnp.where(is_first, x, s_curr))
            return (new_s_prev, new_s_curr, b_new,
                    seen + m.astype(jnp.int32)), None

        z = jnp.zeros(shape, values.dtype)
        (s_prev, s_curr, b, seen), _ = lax.scan(
            body, (z, z, z, jnp.zeros(shape, jnp.int32)), jnp.arange(n)
        )
        outs.append(s_curr)
        valids.append(seen >= 2)
    return (jnp.concatenate(outs, axis=1),
            has & jnp.concatenate(valids, axis=1))


def changes_resets(times, values, counts, step_starts, step_ends, kind: str):
    """changes()/resets() per (series, step): transitions between
    consecutive in-window samples, via prefix sums of per-pair indicators
    (prom promql/functions.go funcChanges/funcResets)."""
    first_idx, last_idx, has = window_bounds(times, counts, step_starts, step_ends)
    n = values.shape[1]
    prev = jnp.concatenate([values[:, :1], values[:, :-1]], axis=1)
    if kind == "changes":
        ind = (values != prev).astype(values.dtype)
    else:  # resets
        ind = (values < prev).astype(values.dtype)
    ind = ind.at[:, 0].set(0)
    valid_cols = jnp.arange(n)[None, :] < counts[:, None]
    cum = jnp.cumsum(jnp.where(valid_cols, ind, 0), axis=1)
    cum = jnp.concatenate([jnp.zeros_like(cum[:, :1]), cum], axis=1)  # (S, N+1)
    safe_f = jnp.clip(first_idx + 1, 0, n)  # pairs with i in (first, last]
    safe_l1 = jnp.clip(last_idx + 1, 0, n)
    out = _gather_rows(cum, safe_l1) - _gather_rows(cum, safe_f)
    valid = has & (last_idx >= first_idx)
    return jnp.where(valid, out, 0), valid


def instant_rate(times, values, counts, starts, ends, per_second: bool):
    """irate/idelta from the last two samples in each (series, step)
    window (prom funcIrate/funcIdelta).  Dense fallback form (searchsorted
    bounds); the tiled form lives on TiledPrepared.instant_rate."""
    first_idx, last_idx, has = window_bounds(times, counts, starts, ends)
    n = times.shape[1]
    prev_idx = jnp.clip(last_idx - 1, 0, n - 1)
    safe_last = jnp.clip(last_idx, 0, n - 1)
    valid = has & (last_idx - first_idx >= 1)
    v_last = _gather_rows(values, safe_last)
    v_prev = _gather_rows(values, prev_idx)
    t_last = _gather_rows(times, safe_last)
    t_prev = _gather_rows(times, prev_idx)
    dv = v_last - v_prev
    if per_second:
        dv = jnp.where(dv < 0, v_last, dv)  # counter reset
        dt = jnp.maximum(t_last - t_prev, 1e-9)
        return dv / dt, valid
    return dv, valid


# Rows of at most this many samples select by comparison, longer ones by
# binary search and a row gather: the longest row at which the comparison
# lost on neither backend measured (PERF.md section 6, PR 49).  Compiled for
# a v5e, 1,000,000 series x 5 steps: the gather takes the compiler 84-186 s
# at 8 to 256 samples a row and leaves 200 MB of code with 2.3 GB of
# temporaries (84 s at 33), the comparison under 2 s with no temporaries at
# 8 and 256 MB at 32.  On the CPU, 10,000 series x 240 steps, the
# comparison is five to thirteen times the faster at 16 and 32 samples, and
# at 64 XLA no longer fuses its (S, K, N) cells into the sum (1.2 GB of
# them) and it is seven times the slower.
INSTANT_COMPARE_MAX_SAMPLES = 32


def instant_values(times, values, counts, eval_times, lookback_s: float = 300.0):
    """Instant vector selection: latest sample within [t - lookback, t].
    Returns (vals (S, K), valid (S, K)) — prom staleness semantics (without
    explicit staleness markers, which the influx data model doesn't carry).
    `times` rows ascend and are padded with +inf (`prepare_matrix_runs`).
    """
    if times.shape[1] <= INSTANT_COMPARE_MAX_SAMPLES:
        # the newest sample at or before t is the one whose successor is
        # after t: one cell a (series, step), picked without an index.  The
        # successor is shifted in the (S, N) rows, so that the (S, K, N)
        # cells are compared and summed and never stored
        after = jnp.concatenate(
            [times[:, 1:], jnp.full_like(times[:, :1], jnp.inf)], axis=1)
        t = eval_times[None, :, None]
        newest = (times[:, None, :] <= t) & (after[:, None, :] > t)
        t_at = jnp.where(newest, times[:, None, :], 0).sum(axis=2)
        v_at = jnp.where(newest, values[:, None, :], 0).sum(axis=2)
        valid = (times[:, :1] <= eval_times[None, :]) & (
            t_at >= eval_times[None, :] - lookback_s)
        return v_at, valid
    idx = _vmap_searchsorted(times, eval_times, "right") - 1
    safe = jnp.clip(idx, 0, times.shape[1] - 1)
    t_at = _gather_rows(times, safe)
    v_at = _gather_rows(values, safe)
    valid = (idx >= 0) & (t_at >= eval_times[None, :] - lookback_s) & (
        idx < counts[:, None]
    )
    return v_at, valid


def prom_instant(times, values, counts, eval_times, lookback_s):
    """`instant_values` under the name a profiler capture lists it by
    (`jit_prom_instant`): one program a query."""
    return instant_values(times, values, counts, eval_times, lookback_s)


@_functools.lru_cache(maxsize=64)
def _instant_jit(geometry: tuple):
    """One compiled instant selection a geometry (series, samples, steps,
    dtype), counted where it is built."""
    import jax

    from opengemini_tpu.utils import devobs

    devobs.note_compile("prom_instant", geometry)
    return jax.jit(prom_instant)


def instant_select(times, values, counts, eval_times, lookback_s: float):
    """`instant_values` as ONE named device program, in the dtype the
    device computes in by statement: the host narrows `times` (seconds
    from the query's first sample: a day is exact in float32 to 8 ms, the
    lookback behind a few steps to 30 us), `values` and the step times
    HERE, explicitly — jax would narrow float64 numpy on the way in,
    silently — and what comes back are those values, selected, never
    recomputed.  Host numpy in and out: ((S, K) values, (S, K) valid);
    the launch and the fetch are counted transfer sites."""
    import jax

    from opengemini_tpu.utils import devobs

    dtype = np.dtype(jax.dtypes.canonicalize_dtype(np.float64))
    # the comparison reads no counts: a program is passed what it reads,
    # so that the bytes counted are the bytes that cross
    by_compare = np.shape(times)[1] <= INSTANT_COMPARE_MAX_SAMPLES
    args = (np.asarray(times, dtype), np.asarray(values, dtype),
            None if by_compare else np.asarray(counts, np.int32),
            np.asarray(eval_times, dtype), dtype.type(lookback_s))
    geometry = (*args[0].shape, len(args[3]), str(dtype))
    devobs.note_use("prom_instant", geometry)
    out = devobs.launch(_instant_jit(geometry), args, program="prom_instant",
                        xfer_site="prom-launch")
    return devobs.fetch_tree(out, "prom-fetch")


# ---------------------------------------------------------------------------
# Time-centric tiled range-vector engine (TiLT, arXiv:2301.12030).
#
# The kernels above resolve every (series, step) window with a vmap'd
# searchsorted and, for min/max, dense (S, 256, N) membership tensors —
# per-series/per-sample lookups that lose an order of magnitude on every
# backend (the measured prom_rate_10k 50x hole).  The tiled engine replaces
# them with time-interval-centric batch operators:
#
#   1. All window edges of one range query live on a millisecond lattice;
#      g = gcd of the edge spacings defines a fixed grid of
#      left-open/right-closed time tiles (t0 + i*g, t0 + (i+1)*g], so every
#      window (s, e] is an EXACT union of w/g consecutive tiles — no
#      boundary sample ever straddles a window edge's tile.
#   2. Samples bucket onto tiles by integer arithmetic on their ms
#      timestamps ((t - t0 - 1) // g — no searchsorted anywhere), giving
#      per-(series, tile) sample-count prefixes; the first/last sample
#      index of ANY window is a prefix lookup at its edge tiles.
#   3. Per-(series, tile) partials (sum, sum-of-squares, min, max,
#      counter-reset drops, change/reset pair indicators) are masked
#      reductions over a compact gather of ONLY the tiles any window
#      covers (the want_sel-pruning idea from the grid path: a
#      step>window range query touches a fraction of the samples).
#   4. Every window then answers from cumulative tile prefixes
#      (ops/segment.py tile_window_sums / tile_sliding_extreme) plus two
#      boundary refinements: the pair quantities (counter resets, changes)
#      subtract the one pair that straddles the window start, and
#      first/last values gather at the prefix-resolved sample indices.
#
# The same code answers in numpy (host path — CPU backends skip jax
# dispatch and per-shape compiles entirely) or traces under jit with
# xp=jax.numpy (device path), so host/device parity holds by construction.
# ---------------------------------------------------------------------------

_MS_PER_S = 1000


def _value_form(kernel: str, **opts) -> str:
    """The form in which a tiled kernel reads its values on a device
    that computes in float32 (TiledPrepared._narrowed makes them).  One
    table for the kernels, which ask for the form, and for ShardedTiled,
    which ships it."""
    if kernel == "rate":
        return "mono" if opts["is_counter"] else "rel"
    if kernel == "instant_rate":
        return "mono" if opts["per_second"] else "rel"
    if kernel == "over_time" and opts["func"] in ("last", "min", "max"):
        return "abs"
    return "rel"


class TilePlan:
    """Time-tile grid for one range query: all window edges on the
    anchor + i*g_ms lattice.  Built host-side by plan_tiles (None when the
    query is ineligible and must take the dense fallback path)."""

    __slots__ = ("g_ms", "anchor_ms", "num_tiles", "a_idx", "b_idx",
                 "win_tiles", "cov", "tile2c", "ca", "cb", "window_s")

    def __init__(self, g_ms, anchor_ms, num_tiles, a_idx, b_idx, win_tiles,
                 cov, tile2c, ca, cb, window_s):
        self.g_ms = g_ms
        self.anchor_ms = anchor_ms
        self.num_tiles = num_tiles
        self.a_idx = a_idx      # (K,) start-edge tile index per window
        self.b_idx = b_idx      # (K,) end-edge tile index per window
        self.win_tiles = win_tiles  # tiles per window (w == win_tiles * g)
        self.cov = cov          # sorted covered tile ids, (C,)
        self.tile2c = tile2c    # tile id -> compact position (or -1)
        self.ca = ca            # (K,) compact start position per window
        self.cb = cb            # (K,) compact end position (exclusive)
        self.window_s = window_s


def plan_tiles(starts_s, ends_s, tmin_ms: int, tmax_ms: int,
               max_tiles: int) -> "TilePlan | None":
    """Tile grid for windows (starts_s[k], ends_s[k]] (seconds, shared
    width).  Returns None when ineligible: edges off the ms lattice,
    non-constant width, or a grid larger than max_tiles (the dense path
    stays correct for those)."""
    starts_s = np.asarray(starts_s, np.float64)
    ends_s = np.asarray(ends_s, np.float64)
    if starts_s.size == 0 or not (
            np.isfinite(starts_s).all() and np.isfinite(ends_s).all()):
        return None
    s_ms = np.rint(starts_s * _MS_PER_S)
    e_ms = np.rint(ends_s * _MS_PER_S)
    # edges must be exactly on the ms lattice (sub-ms windows keep the
    # float-comparison fallback: quantizing them would MOVE a boundary)
    if (np.abs(s_ms - starts_s * _MS_PER_S).max() > 1e-6
            or np.abs(e_ms - ends_s * _MS_PER_S).max() > 1e-6):
        return None
    s_ms = s_ms.astype(np.int64)
    e_ms = e_ms.astype(np.int64)
    w_ms = e_ms - s_ms
    if (w_ms != w_ms[0]).any() or w_ms[0] <= 0:
        return None
    edges = np.unique(np.concatenate([s_ms, e_ms]))
    g_ms = int(np.gcd.reduce(np.diff(edges))) if len(edges) > 1 else int(w_ms[0])
    anchor_ms = int(edges[0])
    if tmin_ms <= anchor_ms:
        # every sample must land at tile index >= 0: pull the anchor back
        # onto the lattice point strictly below the earliest sample
        anchor_ms -= ((anchor_ms - tmin_ms) // g_ms + 1) * g_ms
    a_idx = ((s_ms - anchor_ms) // g_ms).astype(np.int64)
    b_idx = ((e_ms - anchor_ms) // g_ms).astype(np.int64)
    num_tiles = int(max(int(b_idx.max()),
                        (max(tmax_ms, anchor_ms + 1) - anchor_ms - 1) // g_ms + 1)) + 1
    if num_tiles > max_tiles:
        return None
    win_tiles = int(w_ms[0]) // g_ms
    # covered-tile union by interval marking — O(num_tiles), never
    # materializing per-window tile lists (K * win_tiles could dwarf the
    # grid itself for overlapping windows)
    mark = np.zeros(num_tiles + 1, np.int64)
    np.add.at(mark, a_idx, 1)
    np.add.at(mark, b_idx, -1)
    cov = np.flatnonzero(np.cumsum(mark[:-1]) > 0)
    tile2c = np.full(num_tiles + 1, -1, np.int64)
    tile2c[cov] = np.arange(len(cov))
    ca = tile2c[a_idx]
    cb = tile2c[b_idx - 1] + 1
    return TilePlan(g_ms, anchor_ms, num_tiles, a_idx, b_idx, win_tiles,
                    cov, tile2c, ca.astype(np.int32), cb.astype(np.int32),
                    float(w_ms[0]) / _MS_PER_S)


class TiledPrepared:
    """Prepared tiled state for one (series set, window grid) pair.

    Built once per query on the host from run-encoded samples (integer ms
    timestamps); every kernel method then answers all (series, step)
    windows in O(1) per window.  `xp` selects numpy (host) or jax.numpy
    (device); `values`/`value_shift` let callers re-run the value-dependent
    part with fresh values against the same prepared time structure (the
    device jit path)."""

    def __init__(self, plan: TilePlan, t_ms_all, v_all, lens,
                 dtype=np.float64, max_gather_cols: int | None = None,
                 lane_quantum: int = 1):
        lens = np.asarray(lens, np.int64)
        t_ms_all = np.asarray(t_ms_all, np.int64)
        self.plan = plan
        self.dtype = np.dtype(dtype)
        S = len(lens)
        N = max(1, int(lens.max()) if S else 1)
        self.S, self.N = S, N
        self.K = len(plan.a_idx)
        # backend-aware lane padding (models/grid.py quantum): the window
        # axis is the lane axis of every (S, K) output — pad it by
        # repeating the last window so device reduces tile cleanly, and
        # callers slice [:, :k_real]
        self.k_real = self.K
        if lane_quantum > 1 and self.K % lane_quantum:
            pad_k = (-self.K) % lane_quantum
            plan = TilePlan(
                plan.g_ms, plan.anchor_ms, plan.num_tiles,
                np.concatenate([plan.a_idx, np.repeat(plan.a_idx[-1:], pad_k)]),
                np.concatenate([plan.b_idx, np.repeat(plan.b_idx[-1:], pad_k)]),
                plan.win_tiles, plan.cov, plan.tile2c,
                np.concatenate([plan.ca, np.repeat(plan.ca[-1:], pad_k)]),
                np.concatenate([plan.cb, np.repeat(plan.cb[-1:], pad_k)]),
                plan.window_s)
            self.plan = plan
            self.K = len(plan.a_idx)
        # what the lazily built structures are made from: references, not
        # copies (the engine keeps both alive through the kernel anyway)
        self._t_ms_all, self._lens = t_ms_all, lens
        self.counts = lens.astype(np.int32)
        self.base_ms = _runs_base_ms(t_ms_all, lens)
        # the padded (S, N) value matrix: the dense path's flat scatter
        # and zero padding (prepare_matrix_runs).  The times matrix that
        # fill also makes is built on its first read, below
        with tracing.span("prom_fill"):
            self.values = self._pad_values(v_all)
        with tracing.span("prom_tile_index"):
            self._index_tiles(plan, t_ms_all, lens, max_gather_cols)

    def _pad_values(self, v_all) -> np.ndarray:
        values = np.zeros((self.S, self.N), dtype=self.dtype)
        if len(v_all):
            values.reshape(-1)[_scatter_index(self._lens, self.N)] = v_all
        return values

    def _index_tiles(self, plan: TilePlan, t_ms_all, lens,
                     max_gather_cols: int | None) -> None:
        """The time structure every kernel answers from: per-(series,
        tile) sample counts and their prefixes, each window's first and
        last sample index and the times of those samples, and the size of
        the covered-tile gather layout — which decides tiled against
        dense here, while the layout itself waits for its first reader."""
        S, N = self.S, self.N
        total = int(lens.sum())
        # -- integer-arithmetic tile bucketing (no searchsorted) --
        from opengemini_tpu.ops.window import tile_index

        T = plan.num_tiles
        tid = np.clip(tile_index(t_ms_all, plan.anchor_ms, plan.g_ms),
                      0, T - 1)
        if total:
            # int32 throughout: counts and prefixes are bounded by N <
            # 2^31, and these (S, T) arrays are the prepare path's
            # dominant allocation
            tid += np.repeat(np.arange(S, dtype=np.int64) * T, lens)
            cnt = np.bincount(tid, minlength=S * T).reshape(S, T).astype(
                np.int32)
        else:
            cnt = np.zeros((S, T), np.int32)
        tile_cum = np.zeros((S, T + 1), np.int32)
        np.cumsum(cnt, axis=1, out=tile_cum[:, 1:])
        # first/last sample index per window: prefix lookups at edge tiles
        first_idx = tile_cum[:, plan.a_idx]
        last_idx = tile_cum[:, plan.b_idx] - 1
        n_samp = last_idx - first_idx + 1
        self.has1 = n_samp >= 1
        self.has2 = n_samp >= 2
        self.n_samp = n_samp.astype(self.dtype)
        lim = np.maximum(lens, 1)[:, None] - 1
        self.safe_f = np.clip(first_idx, 0, lim).astype(np.int32)
        self.safe_l = np.clip(last_idx, 0, lim).astype(np.int32)
        self.safe_fm1 = np.clip(first_idx - 1, 0, lim).astype(np.int32)
        self.safe_lm1 = np.clip(last_idx - 1, 0, lim).astype(np.int32)
        self.fmask = first_idx >= 1  # the straddling boundary pair exists
        self.t_first = self._times_at(self.safe_f)
        self.t_last = self._times_at(self.safe_l)
        self.t_lm1 = self._times_at(self.safe_lm1)

        # -- the compact covered-tile gather layout's size --
        cov = plan.cov
        C = len(cov)
        cnt_cov = cnt[:, cov]
        pmax = int(cnt_cov.max()) if total else 0
        self.occupancy = pmax
        budget = max_gather_cols if max_gather_cols is not None else 8 * N + 64
        if C * (pmax + 1) > max(budget, 64):
            raise TileBudgetExceeded(
                f"gather layout {C}x{pmax + 1} over budget {budget}")
        self.C, self.pmax = C, pmax
        self._tile_cum, self._cnt_cov = tile_cum, cnt_cov
        # (1, K): take_along_axis broadcasts the non-gather dim, so the
        # per-series copy would be S redundant rows of the same indices
        self.ca2 = plan.ca[None, :].astype(np.int32)
        self.cb2 = plan.cb[None, :].astype(np.int32)
        # window edges, base-relative seconds, kernel dtype
        self.starts_rel = ((np.rint(np.asarray(plan.a_idx) * plan.g_ms
                                    + plan.anchor_ms) - self.base_ms)
                           / 1000.0).astype(self.dtype)
        self.ends_rel = ((np.rint(np.asarray(plan.b_idx) * plan.g_ms
                                  + plan.anchor_ms) - self.base_ms)
                         / 1000.0).astype(self.dtype)

    def _times_at(self, idx) -> np.ndarray:
        """`take_along_axis(self.times, idx)` without the matrix: the same
        samples gathered from the run-encoded times, then shifted and
        scaled by the same float64 operations, so the same bits.  An empty
        series reads +inf, as its all-padding row of the matrix does."""
        lens, t_ms_all = self._lens, self._t_ms_all
        if not len(t_ms_all):
            return np.full(idx.shape, np.inf, dtype=self.dtype)
        # idx stays inside its series (safe_* clip to its length); an empty
        # series' start may be the end of the array, or a neighbour's
        flat = np.minimum((np.cumsum(lens) - lens)[:, None] + idx,
                          len(t_ms_all) - 1)
        out = ((t_ms_all[flat] - self.base_ms) / 1000.0).astype(self.dtype)
        out[lens == 0] = np.inf
        return out

    # -- built on first read ---------------------------------------------
    #
    # Two structures only some kernels read.  Which ones a query needs is
    # known only once the planner has picked its route (rate() of a
    # counter gathers tiles on the host and not where the device narrows),
    # and that is after the prepare: so each is built by its first reader,
    # once, under a span of its own.  _TiledShardView assigns the same
    # names as plain attributes, which a cached_property lets it do.

    @_functools.cached_property
    def times(self) -> np.ndarray:
        """The padded float64 (S, N) times matrix, base-relative seconds,
        +inf padding (prepare_matrix_runs' contract): linear_regression
        gathers it, ShardedTiled ships it."""
        with tracing.span("prom_times_matrix"):
            times = np.full((self.S, self.N), np.inf, dtype=np.float64)
            if len(self._t_ms_all):
                times.reshape(-1)[_scatter_index(self._lens, self.N)] = (
                    (self._t_ms_all - self.base_ms) / 1000.0)
            return times

    @_functools.cached_property
    def _gather_layout(self) -> tuple:
        """(gidx, gmask, pairmask, ownmask): the compact covered-tile
        gather layout, (S, C, pmax+1).  Slot 0 = the sample BEFORE the
        tile's first (any tile — pair quantities need the previous sample
        wherever it lives); slots 1..pmax = the tile's own samples."""
        with tracing.span("prom_gather_layout"):
            S, N, pmax = self.S, self.N, self.pmax
            cnt_cov = self._cnt_cov
            lim = np.maximum(self._lens, 1)[:, None] - 1
            # (S, C) first sample ordinal in tile
            tile_start = self._tile_cum[:, self.plan.cov]
            gidx_local = (tile_start[:, :, None]
                          + np.arange(-1, pmax)[None, None, :])
            own_valid = (np.arange(pmax)[None, None, :] < cnt_cov[:, :, None])
            prev_valid = tile_start > 0
            gmask = np.concatenate([prev_valid[:, :, None], own_valid], axis=2)
            gidx_local = np.clip(gidx_local, 0, lim[:, :, None])
            gidx = np.arange(S, dtype=np.int64)[:, None, None] * N + gidx_local
            if S * N <= np.iinfo(np.int32).max:
                # what a device without x64 indexes in: narrowed here, once
                # and checked, not wherever jax would wrap it silently
                gidx = gidx.astype(np.int32)
            return (gidx, gmask, gmask[:, :, 1:] & gmask[:, :, :-1],
                    gmask[:, :, 1:])

    gidx = _functools.cached_property(lambda self: self._gather_layout[0])
    gmask = _functools.cached_property(lambda self: self._gather_layout[1])
    pairmask = _functools.cached_property(lambda self: self._gather_layout[2])
    ownmask = _functools.cached_property(lambda self: self._gather_layout[3])
    # row-LOCAL gather columns (gidx minus its row offset): the mesh path
    # gathers per series row so GSPMD can shard the series axis without
    # collectives; ShardedTiled derives and ships it, the view reads it
    gidx_col = None

    def unbuilt(self) -> tuple:
        """Which of ("layout", "times") no reader has asked for."""
        return tuple(name for name, attr in (("layout", "_gather_layout"),
                                             ("times", "times"))
                     if attr not in self.__dict__)

    # -- kernel building blocks ------------------------------------------

    def _ftype(self, xp) -> np.dtype:
        """The float dtype the kernels compute in: the prepared dtype on
        the host, what x64 allows on the device (float32 without it) —
        named here so that no astype/zeros asks jax for a float64 it
        would truncate with a warning."""
        if xp is np:
            return self.dtype
        import jax

        return np.dtype(jax.dtypes.canonicalize_dtype(self.dtype))

    def _narrowed(self, form: str) -> np.ndarray:
        """The (S, N) value matrix in the float32 a device without x64
        computes in, narrowed HERE, after the float64 arithmetic that
        float32 cannot do.  jax would narrow on the way in, silently,
        and a counter near 1e9 keeps its value only to 64: a kernel that
        differences neighbours would difference rounding error.

          abs   the values themselves, each one rounding away from its
                float64: for kernels that select a value;
          rel   relative to the series' first sample — exact in float64,
                small in float32: for kernels that difference, compare
                or centre values (good while the series stays within
                2^24 of its resolution from that sample);
          mono  rel plus the cumulative counter-reset corrections: the
                monotone counter Prometheus defines.  rate()/irate()
                difference it directly, so no reset is left for float32
                to cancel against a 1e9 correction."""
        raw = self.values
        if form == "abs":
            return raw.astype(self._ftype(jnp))
        out = raw - raw[:, :1]
        if form == "mono" and self.N > 1:
            prev = raw[:, :-1]
            pair = (np.arange(1, self.N)[None, :]
                    < np.asarray(self.counts)[:, None])
            out[:, 1:] += np.cumsum(
                np.where((raw[:, 1:] < prev) & pair, prev, 0.0), axis=1)
        return out.astype(self._ftype(jnp))

    def _narrows(self, xp, values) -> bool:
        """True where the kernels read _narrowed values: on a device
        whose float is narrower than the prepared dtype (a server: x64
        off).  Never on the host, under x64, or for values the caller
        supplies in the dtype it chose."""
        return (xp is not np and values is None
                and self._ftype(xp) != self.dtype)

    def _values_for(self, xp, form: str = "abs"):
        """The prepared value matrix in xp's array type (one cached device
        copy per form for the traced path, so gathers run on device; a
        device that does not narrow has the one exact copy for every
        form)."""
        if xp is np:
            return self.values
        narrow = self._narrows(xp, None)
        if not narrow:
            form = "abs"
        cache = self.__dict__.setdefault("_dev_values", {})
        dev = cache.get(form)
        if dev is None:
            import time as _time

            from opengemini_tpu.utils import devobs

            if narrow:
                with tracing.span("prom_narrow", form=form):
                    mat = self._narrowed(form)
            else:
                mat = self.values
            with tracing.span("prom_values_h2d", bytes=int(mat.nbytes)):
                t0 = _time.perf_counter_ns()
                dev = xp.asarray(mat)
                devobs.note_transfer(
                    "h2d", "prom-values", int(mat.nbytes),
                    (_time.perf_counter_ns() - t0) / 1e9)
            devobs.LEDGER.register(
                "prom_dev_values", int(mat.nbytes),
                label="tiled-values", anchor=self)
            cache[form] = dev
        return dev

    def _narrowed_level(self, which: str) -> np.ndarray:
        """A level the narrowed forms drop, float32, for the kernels
        that need one back: "first" (S, K), each window's first sample
        itself — rate()'s zero-point clamp divides it by the increase,
        and mono values cannot give it back; "base" (S, 1), what rel
        values are relative to — a sum, a mean and the regression's
        intercept return a level."""
        raw = self.values
        out = (np.take_along_axis(raw, self.safe_f, axis=1)
               if which == "first" else raw[:, :1])
        return out.astype(self._ftype(jnp))

    def _level(self, xp, which: str):
        """_narrowed_level on the device (one cached copy).  Only where
        _narrows."""
        cache = self.__dict__.setdefault("_dev_levels", {})
        dev = cache.get(which)
        if dev is None:
            with tracing.span("prom_narrow", level=which):
                mat = self._narrowed_level(which)
            with tracing.span("prom_values_h2d", bytes=int(mat.nbytes)):
                dev = cache[which] = xp.asarray(mat)
        return dev

    def _vals(self, xp, values, value_shift, form: str = "rel"):
        v = self._values_for(xp, form) if values is None else values
        vg = self._gather_tiles(xp, v)
        v_first = xp.take_along_axis(v, self.safe_f, axis=1)
        v_last = xp.take_along_axis(v, self.safe_l, axis=1)
        if value_shift is not None:
            vg = vg + value_shift
            v_first = v_first + value_shift
            v_last = v_last + value_shift
        return v, vg, v_first, v_last

    def _gather_tiles(self, xp, mat):
        """(S, C, pmax+1) covered-tile gather of a (S, N) matrix. The flat
        form is one big take on the host; the row-local form (gidx_col)
        keeps every gather inside its own series row, which is what lets
        the mesh path shard the series axis with zero collectives."""
        if self.gidx_col is not None:
            return xp.take_along_axis(mat[:, None, :], self.gidx_col, axis=2)
        if xp is not np and self.gidx.dtype != np.int32:
            raise ValueError(
                f"flat gather over {self.S}x{self.N} samples overflows the "
                "device's int32 index")
        return mat.reshape(-1)[self.gidx]

    def _window_sums(self, xp, tile_vals):
        from opengemini_tpu.ops import segment as seg

        return seg.tile_window_sums(tile_vals, self.ca2, self.cb2, xp=xp)

    def _gather1(self, xp, v, idx, value_shift):
        out = xp.take_along_axis(v, idx, axis=1)
        return out if value_shift is None else out + value_shift

    # -- kernels ----------------------------------------------------------

    def rate(self, xp=np, values=None, value_shift=None, *,
             is_counter: bool, is_rate: bool):
        """rate/increase/delta over every (series, step) window:
        tile-prefix counter-reset corrections + first/last gathers,
        prom extrapolatedRate semantics (identical formulas to
        extrapolated_rate above)."""
        # a device that narrows reads counters with their resets already
        # folded in on the host, in float64 (_narrowed "mono"): the
        # increase is one small difference and nothing is left to correct
        folded = is_counter and self._narrows(xp, values)
        v = (self._values_for(xp, _value_form("rate", is_counter=is_counter))
             if values is None else values)
        v_first = self._gather1(xp, v, self.safe_f, value_shift)
        v_last = self._gather1(xp, v, self.safe_l, value_shift)
        delta = v_last - v_first
        if is_counter and not folded:
            vg = self._gather_tiles(xp, v)
            if value_shift is not None:
                vg = vg + value_shift
            drop = xp.where((vg[:, :, 1:] < vg[:, :, :-1]) & self.pairmask,
                            vg[:, :, :-1], xp.zeros((), vg.dtype))
            corr = self._window_sums(xp, drop.sum(axis=2))
            # boundary refinement: the tile diff counts the one pair that
            # straddles the window start (its earlier sample sits at
            # first_idx - 1, OUTSIDE the window) — subtract it
            v_fm1 = self._gather1(xp, v, self.safe_fm1, value_shift)
            drop_f = xp.where((v_first < v_fm1) & self.fmask, v_fm1,
                              xp.zeros((), v_first.dtype))
            delta = delta + (corr - drop_f)
        valid = self.has2
        sampled = self.t_last - self.t_first
        sampled = xp.where(sampled <= 0, 1.0, sampled)
        avg_int = sampled / xp.maximum(self.n_samp - 1, 1)
        d2s = self.t_first - self.starts_rel[None, :]
        d2e = self.ends_rel[None, :] - self.t_last
        thr = avg_int * 1.1
        d2s = xp.where(d2s > thr, avg_int / 2, d2s)
        d2e = xp.where(d2e > thr, avg_int / 2, d2e)
        if is_counter:
            if folded:
                v_first = self._level(xp, "first")
            dz = xp.where((delta > 0) & (v_first >= 0),
                          sampled * (v_first / xp.maximum(delta, 1e-30)),
                          xp.asarray(np.inf, dtype=sampled.dtype)
                          if xp is np else jnp.inf)
            d2s = xp.minimum(d2s, dz)
        out = delta * ((sampled + d2s + d2e) / sampled)
        if is_rate:
            out = out / self.plan.window_s
        return out, valid

    def instant_rate(self, xp=np, values=None, value_shift=None, *,
                     per_second: bool):
        """irate/idelta: last two samples per window, prefix-resolved."""
        v = (self._values_for(
                 xp, _value_form("instant_rate", per_second=per_second))
             if values is None else values)
        v_last = self._gather1(xp, v, self.safe_l, value_shift)
        v_prev = self._gather1(xp, v, self.safe_lm1, value_shift)
        valid = self.has2
        dv = v_last - v_prev
        if per_second:
            # a counter reset; where the device narrows, the host has
            # folded it in already (see rate())
            if not self._narrows(xp, values):
                dv = xp.where(dv < 0, v_last, dv)
            dt = xp.maximum(self.t_last - self.t_lm1, 1e-9)
            return dv / dt, valid
        return dv, valid

    def over_time(self, xp=np, values=None, value_shift=None, *, func: str):
        """sum/count/avg/last/present/stddev/stdvar/min/max _over_time.

        Prefix-able forms answer from cumulative tile sums; min/max from
        the fixed-length sliding-extreme over tile partials — no dense
        (S, chunk, N) membership tensor anywhere."""
        has = self.has1
        ft = self._ftype(xp)
        wcnt = xp.where(has, self.n_samp, xp.zeros((), ft))
        if func == "count":
            return wcnt, has
        if func == "present":
            return xp.where(has, xp.ones((), ft), 0), has
        form = _value_form("over_time", func=func)
        if func == "last":
            v = self._values_for(xp, form) if values is None else values
            return self._gather1(xp, v, self.safe_l, value_shift), has
        v, vg, _vf, _vl = self._vals(xp, values, value_shift, form)
        if func in ("sum", "avg"):
            vz = xp.where(self.ownmask, vg[:, :, 1:], xp.zeros((), vg.dtype))
            wsum = self._window_sums(xp, vz.sum(axis=2))
            wsum = xp.where(has, wsum, xp.zeros((), wsum.dtype))
            if func == "avg":
                wsum = wsum / xp.maximum(wcnt, 1)
            if self._narrows(xp, values):
                # the float32 prefix sums ran over small rel values; the
                # level goes back in as often as the answer holds it
                times = wcnt if func == "sum" else xp.where(
                    has, xp.ones((), ft), 0)
                wsum = wsum + self._level(xp, "base") * times
            return wsum, has
        if func in ("stddev", "stdvar"):
            # center on the per-series mean first (see over_time above: raw
            # v^2 prefixes cancel catastrophically for large magnitudes)
            valid_cols = xp.arange(self.N)[None, :] < self.counts[:, None]
            series_n = xp.maximum(self.counts, 1).astype(ft)[:, None]
            vz_raw = xp.where(valid_cols, v, xp.zeros((), v.dtype))
            center = vz_raw.sum(axis=1, keepdims=True) / series_n
            vc = xp.where(self.ownmask, vg[:, :, 1:] - center[:, :, None],
                          xp.zeros((), vg.dtype))
            ws = self._window_sums(xp, vc.sum(axis=2))
            wss = self._window_sums(xp, (vc * vc).sum(axis=2))
            denom = xp.maximum(wcnt, 1)
            mean = ws / denom
            var = xp.maximum(wss / denom - mean * mean, 0)
            out = var if func == "stdvar" else xp.sqrt(var)
            return xp.where(has, out, xp.zeros((), out.dtype)), has
        if func in ("min", "max"):
            from opengemini_tpu.ops import segment as seg

            want_min = func == "min"
            fill = ft.type(np.inf if want_min else -np.inf)
            if self.pmax == 0:  # no samples in any covered tile
                tile_ext = xp.full((self.S, self.C), fill, dtype=ft)
            elif want_min:
                tile_ext = xp.where(self.ownmask, vg[:, :, 1:], fill).min(axis=2)
            else:
                tile_ext = xp.where(self.ownmask, vg[:, :, 1:], fill).max(axis=2)
            out = seg.tile_sliding_extreme(
                tile_ext, self.plan.win_tiles, self.ca2, want_min, xp=xp)
            return out, has
        raise ValueError(f"unsupported over_time func {func!r}")

    def changes_resets(self, xp=np, values=None, value_shift=None, *, kind: str):
        """changes()/resets(): pair-indicator tile sums + the straddling
        boundary-pair refinement (same shape as the rate correction)."""
        v, vg, v_first, _vl = self._vals(xp, values, value_shift)
        cur, prev = vg[:, :, 1:], vg[:, :, :-1]
        if kind == "changes":
            ind = (cur != prev) & self.pairmask
        else:
            ind = (cur < prev) & self.pairmask
        ft = self._ftype(xp)
        wind = self._window_sums(xp, ind.astype(ft).sum(axis=2))
        v_fm1 = self._gather1(xp, v, self.safe_fm1, value_shift)
        if kind == "changes":
            bnd = (v_first != v_fm1) & self.fmask
        else:
            bnd = (v_first < v_fm1) & self.fmask
        out = wind - bnd.astype(ft)
        valid = self.has1
        return xp.where(valid, out, xp.zeros((), out.dtype)), valid

    def linear_regression(self, xp=np, values=None, value_shift=None):
        """Least-squares slope/intercept per window centered at the window
        end (prom linearRegression), from tile partials of {v, t, t^2, tv}
        — the O(S*chunk*N) dense pass becomes four prefix lookups."""
        v, vg, _vf, _vl = self._vals(xp, values, value_shift)
        tg = self._gather_tiles(xp, self.times)[:, :, 1:].astype(
            self._ftype(xp))
        z = xp.zeros((), vg.dtype)
        vz = xp.where(self.ownmask, vg[:, :, 1:], z)
        tz = xp.where(self.ownmask, tg, z)
        sv = self._window_sums(xp, vz.sum(axis=2))
        st_abs = self._window_sums(xp, tz.sum(axis=2))
        stt_abs = self._window_sums(xp, (tz * tz).sum(axis=2))
        stv_abs = self._window_sums(xp, (tz * vz).sum(axis=2))
        e = self.ends_rel[None, :]
        cnt = xp.where(self.has1, self.n_samp, 0)
        denom_n = xp.maximum(cnt, 1)
        st = st_abs - e * cnt
        stt = stt_abs - 2 * e * st_abs + e * e * cnt
        stv = stv_abs - e * sv
        cov = stv - st * sv / denom_n
        var = stt - st * st / denom_n
        slope = cov / xp.where(var == 0, 1.0, var)
        slope = xp.where(var == 0, 0.0, slope)
        intercept = sv / denom_n - slope * (st / denom_n)
        if self._narrows(xp, values):
            # the slope of rel values is the slope; the level is not
            intercept = intercept + self._level(xp, "base")
        has2 = self.has2 & (self.t_last > self.t_first)
        return slope, intercept, has2


    def sharded(self, mesh) -> "ShardedTiled":
        """The mesh view of this prepared state (cached per mesh object:
        one sharding transfer per query however many kernels run)."""
        cached = getattr(self, "_sharded_view", None)
        if cached is not None and cached[0] is mesh:
            return cached[1]
        view = ShardedTiled(self, mesh)
        self._sharded_view = (mesh, view)
        return view


# ---------------------------------------------------------------------------
# Multi-chip tiled kernels: series-axis sharding over a device mesh.
#
# Every TiledPrepared tensor is either per-series (leading axis S: the
# values/times matrices, the covered-tile gather and its masks, the
# per-window prefix lookups and boundary-refinement gathers) or per-window
# (the compact range positions ca/cb and the window edges). Series are
# independent — no kernel ever combines two series rows — so sharding the
# S axis partitions the WHOLE program with zero collectives, exactly the
# GSPMD style of distributed.shard_leading_axis for the grid layout. The
# boundary refinements (the straddling pair subtraction, first/last value
# gathers) are row-local gathers and stay per-shard by construction once
# the flat covered-tile gather is rewritten row-locally (gidx_col).
# ---------------------------------------------------------------------------

# per-series tensors (leading axis S — sharded over every mesh axis)
_TILED_SHARD_ATTRS = (
    "counts", "times", "ownmask", "pairmask", "fmask",
    "has1", "has2", "n_samp", "safe_f", "safe_l", "safe_fm1", "safe_lm1",
    "t_first", "t_last", "t_lm1",
)
# per-window tensors (replicated: every shard answers all K windows for
# its own series rows)
_TILED_REPL_ATTRS = ("ca2", "cb2", "starts_rel", "ends_rel")


class _TiledShardView(TiledPrepared):
    """TiledPrepared stand-in rebuilt inside the jit trace: tensor
    attributes are traced (sharded) arrays, statics are Python scalars.
    The kernel methods run unmodified against it."""

    def __init__(self, arrays):  # attrs are assigned by the trace, not prepared
        self.__dict__.update(arrays)

    def _values_for(self, xp, form: str = "abs"):
        return self.values  # ShardedTiled shipped the form this kernel reads

    def _level(self, xp, which: str):
        return getattr(self, "level_" + which)


class _PlanView:
    __slots__ = ("win_tiles", "window_s")

    def __init__(self, win_tiles: int, window_s: float):
        self.win_tiles = win_tiles
        self.window_s = window_s


@_functools.lru_cache(maxsize=128)
def _sharded_tiled_jit(kernel: str, opts: tuple, meta: tuple):
    """One compiled sharded program per (kernel, static opts, geometry).
    Tensors arrive as a pytree argument (never closed over — constants
    would be baked into the program) and carry their NamedSharding in;
    GSPMD propagates it through every op."""
    import jax

    from opengemini_tpu.utils import devobs

    devobs.note_compile("prom_" + kernel, (opts, meta))
    s_pad, n_cols, k_win, c_cov, pmax, dtype_str, win_tiles, window_s = meta
    kwargs = dict(opts)

    def fn(arrays):
        view = _TiledShardView(arrays)
        view.gidx = None  # force the row-local gather form
        view.S, view.N, view.K = s_pad, n_cols, k_win
        view.C, view.pmax = c_cov, pmax
        view.dtype = np.dtype(dtype_str)
        view.plan = _PlanView(win_tiles, window_s)
        return getattr(TiledPrepared, kernel)(view, jnp, **kwargs)

    return jax.jit(fn)


class ShardedTiled:
    """Mesh execution of one TiledPrepared: per-series tensors device_put
    with the series axis sharded (explicit NamedSharding, rows padded to a
    multiple of mesh.size — padding rows carry all-False masks so they
    answer as empty windows and are sliced off by the caller), per-window
    tensors replicated. Kernel methods mirror TiledPrepared's but run as
    one sharded jit program each; outputs are (S_pad, K)-sharded arrays
    the caller slices to [:prep.S, :prep.k_real]."""

    def __init__(self, prep: TiledPrepared, mesh):
        import jax

        from opengemini_tpu.parallel import distributed as dist

        self.prep = prep
        self.mesh = mesh
        n_dev = mesh.size
        self.S_pad = max(1, (prep.S + n_dev - 1) // n_dev * n_dev)
        # row-local covered-tile gather: flat gidx minus its row offset
        rows = (np.arange(prep.S, dtype=np.int64) * prep.N)[:, None, None]
        gidx_col = (prep.gidx - rows).astype(np.int32)
        series = {name: getattr(prep, name) for name in _TILED_SHARD_ATTRS}
        series["gidx_col"] = gidx_col
        # the value matrix follows per kernel, in the form it reads
        # (_values_in); a device that narrows gets the two levels as well
        self.narrow = jax.dtypes.canonicalize_dtype(prep.dtype) != prep.dtype
        if self.narrow:
            for which in ("first", "base"):
                series["level_" + which] = prep._narrowed_level(which)
        self._values: dict = {}
        sharded = dist.shard_leading_axis(mesh, *series.values(),
                                          xfer_site="prom-shard")
        self.arrays = dict(zip(series.keys(), sharded))
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(mesh, P())
        for name in _TILED_REPL_ATTRS:
            self.arrays[name] = jax.device_put(
                np.asarray(getattr(prep, name)), repl)
        self._meta = (self.S_pad, prep.N, prep.K, prep.C, prep.pmax,
                      str(prep.dtype), prep.plan.win_tiles,
                      float(prep.plan.window_s))
        from opengemini_tpu.utils import devobs
        from opengemini_tpu.parallel import runtime as _prt

        devobs.LEDGER.register(
            "prom_sharded",
            sum(int(a.nbytes) for a in self.arrays.values()),
            mesh_epoch=_prt.mesh_epoch(), label="sharded-tiled",
            anchor=self)

    def _values_in(self, form: str):
        """The sharded (S_pad, N) value matrix in one form: a transfer per
        form a query's kernels read, not per kernel."""
        from opengemini_tpu.parallel import distributed as dist
        from opengemini_tpu.parallel import runtime as _prt
        from opengemini_tpu.utils import devobs

        dev = self._values.get(form)
        if dev is None:
            host = (self.prep._narrowed(form) if self.narrow
                    else self.prep.values)
            (dev,) = dist.shard_leading_axis(self.mesh, host,
                                             xfer_site="prom-shard")
            devobs.LEDGER.register(
                "prom_sharded", int(dev.nbytes),
                mesh_epoch=_prt.mesh_epoch(), label="sharded-tiled-values",
                anchor=self)
            self._values[form] = dev
        return dev

    def _run(self, kernel: str, **opts):
        from opengemini_tpu.query import offload
        from opengemini_tpu.utils import devobs

        form = _value_form(kernel, **opts) if self.narrow else "abs"
        arrays = dict(self.arrays, values=self._values_in(form))
        opts_t = tuple(sorted(opts.items()))
        devobs.note_use("prom_" + kernel, (opts_t, self._meta))
        offload.register_builder(
            "prom_" + kernel, (opts_t, self._meta),
            lambda k=kernel, o=opts_t, m=self._meta:
                _sharded_tiled_jit(k, o, m))
        fn = _sharded_tiled_jit(kernel, opts_t, self._meta)
        return devobs.launch(fn, (arrays,), program="prom_" + kernel,
                             xfer_site="prom-launch")

    def rate(self, *, is_counter: bool, is_rate: bool):
        return self._run("rate", is_counter=is_counter, is_rate=is_rate)

    def instant_rate(self, *, per_second: bool):
        return self._run("instant_rate", per_second=per_second)

    def over_time(self, *, func: str):
        return self._run("over_time", func=func)

    def changes_resets(self, *, kind: str):
        return self._run("changes_resets", kind=kind)

    def linear_regression(self):
        return self._run("linear_regression")


class TileBudgetExceeded(ValueError):
    """Raised by TiledPrepared when the compact gather layout would exceed
    its memory budget (pathological occupancy skew); callers fall back to
    the dense kernels."""


def prepare_tiled(plan: TilePlan, t_ms_all, v_all, lens, dtype=np.float64,
                  max_gather_cols: int | None = None, lane_quantum: int = 1):
    """TiledPrepared or None (budget exceeded -> dense fallback)."""
    try:
        return TiledPrepared(plan, t_ms_all, v_all, lens, dtype=dtype,
                             max_gather_cols=max_gather_cols,
                             lane_quantum=lane_quantum)
    except TileBudgetExceeded:
        return None

# -- incremental tile-state tier (promql/rules.py) ----------------------------
#
# The continuous rule engine maintains PER-TILE partials as durable-ish
# STATE between ticks instead of recomputing them per query: each tile of
# the group's ms lattice carries one mergeable record per series, the
# ingest path dirties tiles, and a tick refolds only the dirtied tiles
# (fold_tile_partials) before answering every rule window from a
# left-to-right merge of its covering tiles (merge_tile_partials +
# partials_answer).  The record is the TiLT partial (arXiv:2301.12030)
# the batch engine above computes transiently, plus the boundary-pair
# inputs (first/last sample) that let cross-tile merges reconstruct the
# straddling reset/change corrections exactly.
#
# All arithmetic here is HOST numpy float64 on purpose: the rule engine's
# acceptance contract is BITWISE identity between the incremental leg
# (merge cached + refolded tiles) and the from-scratch leg (fold every
# tile off one full-window scan, merge identically), which holds only
# under a deterministic reduction order.  Device/mesh routing still
# happens per group — for the matcher probe (label tier) and for the
# full-rescan fallback leg, which evaluates through the ordinary planner-
# routed engine kernels.

# field -> fill value for an EMPTY (series, tile) cell; merge order is
# the tuple order
TILE_PARTIAL_FIELDS = (
    ("n", 0.0), ("sum", 0.0), ("sumsq", 0.0),
    ("mn", np.inf), ("mx", -np.inf),
    ("t_first", 0.0), ("v_first", 0.0), ("t_last", 0.0), ("v_last", 0.0),
    ("drop", 0.0), ("changes", 0.0), ("resets", 0.0),
)

# range-vector functions the partial record answers exactly (everything
# else takes the rule engine's full-rescan fallback through the engine)
PARTIAL_RATE_FUNCS = frozenset({"rate", "increase", "delta"})
PARTIAL_OVER_TIME = frozenset({
    "sum", "count", "avg", "min", "max", "stddev", "stdvar", "last",
    "present"})
PARTIAL_PAIR_FUNCS = frozenset({"changes", "resets"})


def empty_tile_partials(n_series: int) -> dict:
    """One tile's record columns for `n_series` series, all empty."""
    return {f: np.full(n_series, fill, np.float64)
            for f, fill in TILE_PARTIAL_FIELDS}


def fold_tile_partials(t_ms_all, v_all, lens, anchor_ms: int, g_ms: int,
                       lo_tile: int, hi_tile: int) -> dict[int, dict]:
    """Fold run-encoded samples into per-tile partial records.

    Input is the engine's run-encoded collection (concatenated int64 ms
    timestamps + float64 values with per-series lengths, ascending per
    series); only samples landing in lattice tiles [lo_tile, hi_tile)
    contribute.  Returns {tile_idx: {field: (S,) float64}} holding ONLY
    tiles that received at least one sample — absent means empty, so the
    caller can overlay the result onto cached state.

    Pair quantities (drop/changes/resets) count sample pairs fully INSIDE
    one tile; pairs straddling tiles are reconstructed at merge time from
    (v_last, v_first) of consecutive non-empty tiles, which is exact
    because tiles partition the time axis and samples are time-ordered.
    """
    from opengemini_tpu.ops.window import tile_index

    lens = np.asarray(lens, np.int64)
    S = len(lens)
    t_ms_all = np.asarray(t_ms_all, np.int64)
    v_all = np.asarray(v_all, np.float64)
    if t_ms_all.size == 0:
        return {}
    tid = tile_index(t_ms_all, anchor_ms, g_ms)
    rows = np.repeat(np.arange(S, dtype=np.int64), lens)
    keep = (tid >= lo_tile) & (tid < hi_tile)
    span = hi_tile - lo_tile
    # rows are blockwise-ascending and t (hence tid) ascends per series,
    # so key is globally non-decreasing: segment reductions are plain
    # reduceat over change points — no sort, no hashing
    key = rows * span + (tid - lo_tile)
    # pair columns BEFORE masking: a pair exists when sample i-1 and i
    # share a (series, tile) cell
    same = np.zeros(len(key), bool)
    if len(key) > 1:
        same[1:] = key[1:] == key[:-1]
    prev_v = np.empty_like(v_all)
    prev_v[0] = 0.0
    prev_v[1:] = v_all[:-1]
    p_reset = same & (v_all < prev_v)
    p_drop = np.where(p_reset, prev_v, 0.0)
    p_change = (same & (v_all != prev_v)).astype(np.float64)
    if not keep.all():
        key = key[keep]
        t_k = t_ms_all[keep]
        v_k = v_all[keep]
        p_drop = p_drop[keep]
        p_change = p_change[keep]
        p_resets = p_reset[keep].astype(np.float64)
    else:
        t_k = t_ms_all
        v_k = v_all
        p_resets = p_reset.astype(np.float64)
    if key.size == 0:
        return {}
    starts = np.flatnonzero(np.diff(key)) + 1
    starts = np.concatenate([[0], starts])
    seg_key = key[starts]
    seg_n = np.diff(np.concatenate([starts, [key.size]]))
    seg_sum = np.add.reduceat(v_k, starts)
    seg_sumsq = np.add.reduceat(v_k * v_k, starts)
    seg_mn = np.minimum.reduceat(v_k, starts)
    seg_mx = np.maximum.reduceat(v_k, starts)
    seg_drop = np.add.reduceat(p_drop, starts)
    seg_changes = np.add.reduceat(p_change, starts)
    seg_resets = np.add.reduceat(p_resets, starts)
    ends = starts + seg_n - 1
    out: dict[int, dict] = {}
    seg_row = seg_key // span
    seg_tile = seg_key % span + lo_tile
    for tile in np.unique(seg_tile):
        sel = seg_tile == tile
        r = seg_row[sel]
        rec = empty_tile_partials(S)
        rec["n"][r] = seg_n[sel]
        rec["sum"][r] = seg_sum[sel]
        rec["sumsq"][r] = seg_sumsq[sel]
        rec["mn"][r] = seg_mn[sel]
        rec["mx"][r] = seg_mx[sel]
        rec["t_first"][r] = t_k[starts[sel]]
        rec["v_first"][r] = v_k[starts[sel]]
        rec["t_last"][r] = t_k[ends[sel]]
        rec["v_last"][r] = v_k[ends[sel]]
        rec["drop"][r] = seg_drop[sel]
        rec["changes"][r] = seg_changes[sel]
        rec["resets"][r] = seg_resets[sel]
        out[int(tile)] = rec
    return out


def merge_tile_partials(tiles: list[dict | None], n_series: int) -> dict:
    """Left-to-right merge of per-tile records into one window record.

    `tiles` lists the window's covering tiles in time order (None =
    empty tile).  Boundary pairs between consecutive NON-EMPTY tiles add
    the straddling reset/change corrections the per-tile fold could not
    see.  Deterministic (same tile order -> same bits), which is the
    incremental-vs-rescan identity contract."""
    m = empty_tile_partials(n_series)
    for rec in tiles:
        if rec is None:
            continue
        t_has = rec["n"] > 0
        if not t_has.any():
            continue
        m_has = m["n"] > 0
        both = m_has & t_has
        bd_reset = both & (rec["v_first"] < m["v_last"])
        m["drop"] += np.where(bd_reset, m["v_last"], 0.0) \
            + np.where(t_has, rec["drop"], 0.0)
        m["resets"] += bd_reset + np.where(t_has, rec["resets"], 0.0)
        m["changes"] += (both & (rec["v_first"] != m["v_last"])) \
            + np.where(t_has, rec["changes"], 0.0)
        m["n"] += np.where(t_has, rec["n"], 0.0)
        m["sum"] += np.where(t_has, rec["sum"], 0.0)
        m["sumsq"] += np.where(t_has, rec["sumsq"], 0.0)
        m["mn"] = np.where(t_has, np.minimum(m["mn"], rec["mn"]), m["mn"])
        m["mx"] = np.where(t_has, np.maximum(m["mx"], rec["mx"]), m["mx"])
        first = t_has & ~m_has
        m["t_first"] = np.where(first, rec["t_first"], m["t_first"])
        m["v_first"] = np.where(first, rec["v_first"], m["v_first"])
        m["t_last"] = np.where(t_has, rec["t_last"], m["t_last"])
        m["v_last"] = np.where(t_has, rec["v_last"], m["v_last"])
    return m


def partials_answer(m: dict, func: str, ws_ms: int, we_ms: int):
    """(values, valid) for one rule window from a merged record.

    Same semantics as the batch kernels above: extrapolatedRate with the
    1.1x-average-interval clamp and counter zero-crossing for
    rate/increase/delta, pair counts for changes/resets, moment algebra
    for the *_over_time forms (stddev/stdvar from sum/sumsq — adequate
    for monitoring magnitudes; the engine's per-query centered form is
    not reachable from mergeable per-tile state)."""
    n = m["n"]
    has1 = n >= 1
    if func == "count":
        return np.where(has1, n, 0.0), has1
    if func == "present":
        return np.where(has1, 1.0, 0.0), has1
    if func == "last":
        return m["v_last"], has1
    if func == "sum":
        return np.where(has1, m["sum"], 0.0), has1
    if func == "avg":
        return m["sum"] / np.maximum(n, 1.0), has1
    if func == "min":
        return m["mn"], has1
    if func == "max":
        return m["mx"], has1
    if func in ("stddev", "stdvar"):
        denom = np.maximum(n, 1.0)
        mean = m["sum"] / denom
        var = np.maximum(m["sumsq"] / denom - mean * mean, 0.0)
        return (var if func == "stdvar" else np.sqrt(var)), has1
    if func in ("changes", "resets"):
        out = m["changes"] if func == "changes" else m["resets"]
        return np.where(has1, out, 0.0), has1
    if func in PARTIAL_RATE_FUNCS:
        is_counter = func in ("rate", "increase")
        valid = n >= 2
        delta = m["v_last"] - m["v_first"]
        if is_counter:
            delta = delta + m["drop"]
        # int64 ms differences -> exact float seconds (the batch path's
        # base-relative precision argument, with the window start as base)
        sampled = (m["t_last"] - m["t_first"]) / 1000.0
        sampled = np.where(sampled <= 0, 1.0, sampled)
        avg_int = sampled / np.maximum(n - 1, 1.0)
        d2s = (m["t_first"] - ws_ms) / 1000.0
        d2e = (we_ms - m["t_last"]) / 1000.0
        thr = avg_int * 1.1
        d2s = np.where(d2s > thr, avg_int / 2, d2s)
        d2e = np.where(d2e > thr, avg_int / 2, d2e)
        if is_counter:
            dz = np.where((delta > 0) & (m["v_first"] >= 0),
                          sampled * (m["v_first"] / np.maximum(delta, 1e-30)),
                          np.inf)
            d2s = np.minimum(d2s, dz)
        out = delta * ((sampled + d2s + d2e) / sampled)
        if func == "rate":
            out = out / ((we_ms - ws_ms) / 1000.0)
        return out, valid
    raise ValueError(f"unsupported partials func {func!r}")

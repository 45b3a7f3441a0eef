"""What `correct` is decided with: tolerances, the error measure and the
lower-precision control.  Copied from chip_smoke.py (`TOL`, `rel_err`,
`oracle_rate`, PR 21); the limits are the configurations' stated guarantees
(PERF.md §2 gives the readings each was checked against)."""

from __future__ import annotations

import numpy as np

# Relative tolerances, device float32 against the float64 oracle.
TOL = {
    "selector": 2e-7,   # first/last/min/max: one float32 rounding
    "mean": 2e-5,       # float32 partial sums, float64 combine on the host
    "rate": 2e-4,       # float32 differences of host-made monotone counters
}


class Mismatch(Exception):
    """An answer whose shape, times or counts differ from the oracle's."""


def read_count(doc: dict) -> int:
    """The one number of a `SELECT count(field)` answer; 0 where no row
    came back."""
    series = doc["results"][0].get("series", [])
    return int(series[0]["values"][0][1]) if series else 0


def rel_err(got, want) -> float:
    """Largest |got - want| / max(|want|, 1): relative to the value, and
    absolute below 1 so that a mean near zero cannot blow the ratio up."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise Mismatch(f"shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise Mismatch("non-finite value in an answer")
    if got.size == 0:
        raise Mismatch("empty answer")
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1.0)).max())


def to_bf16(x) -> np.ndarray:
    """float64 -> the nearest bfloat16 (round to nearest even), as float64:
    the precision below the served path's float32, for the control."""
    f = np.ascontiguousarray(x, np.float32)
    bits = f.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def oracle_rate(vals: np.ndarray, t_s: np.ndarray, ends_s: np.ndarray,
                range_s: float, narrow=None) -> np.ndarray:
    """Prometheus extrapolatedRate for counters, window (end-range, end],
    per series over all steps: (series, steps) float64, NaN where a window
    holds fewer than two samples.  `narrow` (the control) rounds the
    reset-corrected, first-sample-relative window values, as the served
    path narrows them on their way to the device."""
    v = vals.astype(np.float64)
    out = np.full((v.shape[1], len(ends_s)), np.nan)
    for k, end in enumerate(ends_s):
        inside = np.flatnonzero((t_s > end - range_s) & (t_s <= end))
        if len(inside) < 2:
            continue
        w = v[inside]                          # (n, series)
        t = t_s[inside].astype(np.float64)
        prev, cur = w[:-1], w[1:]
        first = w[0]
        delta = w[-1] - w[0] + np.where(cur < prev, prev, 0.0).sum(axis=0)
        if narrow is not None:
            delta, first = narrow(delta), narrow(first)
        sampled = t[-1] - t[0]
        avg = sampled / (len(inside) - 1)
        to_start = t[0] - (end - range_s)
        to_end = end - t[-1]
        to_start = np.full_like(delta, avg / 2 if to_start > avg * 1.1
                                else to_start)
        to_end = avg / 2 if to_end > avg * 1.1 else to_end
        with np.errstate(divide="ignore", invalid="ignore"):
            to_zero = np.where((delta > 0) & (first >= 0),
                               sampled * (first / delta), np.inf)
        to_start = np.minimum(to_start, to_zero)
        r = delta * ((sampled + to_start + to_end) / sampled) / range_s
        out[:, k] = narrow(r) if narrow is not None else r
    return out

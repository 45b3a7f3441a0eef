"""The table of peaks and the functions that say how many bytes and
operations the device work of a statement needs.  The roofline share of
the traced kernels is (least time at the peaks) / (their device time)."""

from __future__ import annotations

# Published peaks of one chip, keyed by `device_kind` as JAX reports it.
# An unknown device is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "flops_per_s": 197e12,      # bf16; the served path computes float32
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       "benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]


def bucketed_reduce(points: int, groups: int) -> dict:
    """`mean(field) GROUP BY time(), hostname` on the bucketed layout
    (`models/ragged.py` `basic`): a mean needs each value once (float32,
    4 B) with its validity bit (stored as a byte, 1 B), one add and one
    count per point, and writes a sum and a count per group (8 B).  The
    padding of bucket rows, the time columns (`hi`, `lo`, `idx`) the
    program also ships, and the min/max/ssd it computes beside the mean
    are not needed by the statement and are not counted."""
    return {"bytes": 5 * points + 8 * groups, "flops": 2 * points}


def tiled_rate(points: int, groups: int) -> dict:
    """`rate(counter[range])` over a range (`ops/prom.py`, tiled range
    vectors): every sample is read once (float32 relative to the series'
    first sample, 4 B; the scrape times are one shared grid and free),
    each window (`groups` = series x steps of them) needs its first and
    last sample, a difference and the extrapolation (about 10 operations)
    and writes one float32."""
    return {"bytes": 4 * points + 4 * groups, "flops": 10 * groups}


NEEDS = {"bucketed_reduce": bucketed_reduce, "tiled_rate": tiled_rate}


def least_seconds(need: dict, peaks: dict) -> float:
    """The larger of bytes over peak bandwidth and operations over peak
    rate: what the chip could not beat."""
    return max(need["bytes"] / peaks["hbm_bytes_per_s"],
               need["flops"] / peaks["flops_per_s"])

"""Self-monitoring statistics registry + latency histograms + the
Prometheus text-format renderer.

Reference: lib/statisticsPusher (~40 statistic modules accumulated and
pushed to file/http/_internal). Here: a process-wide registry of named
counters, exposed at /debug/vars (the influxdb expvar convention) and
pushable into an `_internal` database by the monitor service; plus
fixed-log-bucket Histograms (HTTP endpoints, query stages, per-peer
RPCs, WAL fsync, flush, rollup folds) exported — together with every
counter/gauge — at GET /metrics under the `ogt_*` naming scheme.
"""

from __future__ import annotations

import os
import re
import threading
from opengemini_tpu.utils import lockdep
import time
from collections import defaultdict


class Statistics:
    def __init__(self) -> None:
        self._lock = lockdep.Lock()
        self._counters: dict[str, dict[str, int]] = defaultdict(lambda: defaultdict(int))
        # computed gauge sections: module -> [fn() -> {name: int}].
        # Providers are evaluated at snapshot time (live state — e.g. the
        # per-shard durability ledgers aggregate, failpoint hit counts)
        # and their values must be ints: the monitor service pushes every
        # snapshot field into `_internal` as INT points.
        self._providers: dict[str, list] = defaultdict(list)
        # uptime is a DURATION: perf_counter, not wall clock (an NTP
        # step mid-run would bend every scraped ogt_uptime_seconds)
        self.started_pc = time.perf_counter()

    def incr(self, module: str, name: str, delta: int = 1) -> None:
        with self._lock:
            self._counters[module][name] += delta

    def add(self, module: str, items) -> None:
        """Several counters of one module under one lock acquisition:
        `items` is an iterable of (name, delta).  The span primitive
        (utils/tracing.py) closes every stage through here."""
        with self._lock:
            sect = self._counters[module]
            for name, delta in items:
                sect[name] += delta

    def set(self, module: str, name: str, value: int) -> None:
        with self._lock:
            self._counters[module][name] = value

    def register_provider(self, module: str, fn) -> None:
        """Attach a live gauge section to every snapshot(). Multiple
        providers of one module merge by summing shared keys (several
        engines in one process report process-wide totals)."""
        with self._lock:
            self._providers[module].append(fn)

    def unregister_provider(self, module: str, fn) -> None:
        with self._lock:
            fns = self._providers.get(module)
            if fns and fn in fns:
                fns.remove(fn)
            if fns is not None and not fns:
                del self._providers[module]

    def counters(self, module: str) -> dict:
        """One module's RAW counter section — no gauge providers run.
        Hot paths (the executor reads colcache counters twice per query)
        must not pay the providers' engine/shard-lock sweeps just to
        read a plain counter dict."""
        with self._lock:
            return dict(self._counters.get(module, ()))

    def snapshot(self) -> dict:
        with self._lock:
            out = {m: dict(vals) for m, vals in self._counters.items()}
            providers = [(m, fn) for m, fns in self._providers.items()
                         for fn in fns]
        for module, fn in providers:  # outside the lock: providers lock
            try:                      # their own structures (shard locks)
                vals = fn()
            except Exception:  # noqa: BLE001 — a dying provider (e.g. a
                continue       # closed engine) must not break /debug/vars
            if not vals:
                continue  # keep empty sections out of pushed snapshots
            sect = out.setdefault(module, {})
            for k, v in vals.items():
                sect[k] = sect.get(k, 0) + int(v)
        return out

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()


# process-wide registry (the reference's statistics singletons)
GLOBAL = Statistics()


def _failpoint_hits() -> dict:
    from opengemini_tpu.utils import failpoint

    return failpoint.all_hits()


# failpoint hit counts ride every stats snapshot (/debug/vars): the
# torture harness and operators can see WHICH armed sites actually fired
GLOBAL.register_provider("failpoints", _failpoint_hits)


def _governor_gauges() -> dict:
    from opengemini_tpu.utils import governor

    return governor.GOVERNOR.gauges()


# governor ledger/admission gauges ride /debug/vars when the governor is
# enabled (OGT_MEM_BUDGET_MB set); the provider answers {} pass-through
GLOBAL.register_provider("governor", _governor_gauges)

# lock-order validator findings (OGT_LOCKDEP=1 only): the torture
# harnesses assert violations == 0 on live nodes via /debug/vars
if lockdep.enabled():
    GLOBAL.register_provider("lockdep", lockdep.stats_snapshot)


# -- latency histograms ------------------------------------------------------
# Fixed log2 buckets over nanoseconds: bounds 2^10 ns (~1µs) .. 2^35 ns
# (~34s), 26 finite buckets + overflow.  The fixed layout makes every
# histogram of a family mergeable by plain element-wise addition (the
# concurrency/merge-exactness contract the tests assert) and keeps the
# Prometheus export cumulative-bucket math trivial.

_H_LO = 10                      # first bound: 2^10 ns
_NBOUNDS = 26                   # bounds 2^10 .. 2^35
_BOUNDS_NS = [1 << (_H_LO + i) for i in range(_NBOUNDS)]
_BOUNDS_S = [b / 1e9 for b in _BOUNDS_NS]

# histogram arming: OGT_TRACE=0 short-circuits every observe() to one
# global read.  Unset/1 = armed (a default
# /metrics scrape sees live latency data without any knob).
_OBS_ON = os.environ.get("OGT_TRACE", "") != "0"


def obs_enabled() -> bool:
    return _OBS_ON


def set_obs_enabled(on: bool) -> None:
    global _OBS_ON
    _OBS_ON = bool(on)


class Histogram:
    """Lock-cheap fixed-bucket latency histogram.  observe_ns computes
    the bucket outside the lock and holds it for three int updates; the
    lock is what makes concurrent counts EXACT (a bare `counts[i] += 1`
    loses increments across bytecode boundaries under threads).

    ``unit`` selects how the fixed 2^10..2^35 bounds export: "seconds"
    (values are nanoseconds, le bounds and sum scale by 1e-9 — every
    latency family) or "bytes" (values are raw bytes, bounds 1KiB..32GiB
    export unscaled — the devobs transfer-size families)."""

    __slots__ = ("name", "labels", "_lock", "counts", "count", "sum_ns",
                 "unit")

    def __init__(self, name: str, labels: tuple = (),
                 unit: str = "seconds"):
        self.name = name
        self.labels = labels  # sorted ((k, v), ...) — family identity
        self.unit = unit
        self._lock = lockdep.Lock()
        self.counts = [0] * (_NBOUNDS + 1)  # [+Inf] last
        self.count = 0
        self.sum_ns = 0

    def observe_ns(self, ns: int) -> None:
        if not _OBS_ON:
            return
        ns = int(ns)
        if ns < 0:
            ns = 0
        # smallest bound >= ns: (ns-1).bit_length() rounds exact powers
        # of two DOWN into their own bucket (le is inclusive)
        idx = (ns - 1).bit_length() - _H_LO
        if idx < 0:
            idx = 0
        elif idx > _NBOUNDS:
            idx = _NBOUNDS
        with self._lock:
            self.counts[idx] += 1
            self.count += 1
            self.sum_ns += ns

    def merge(self, other: "Histogram") -> None:
        """Element-wise fold of `other` into self (exact: fixed shared
        bucket layout)."""
        with other._lock:
            oc = list(other.counts)
            ocount, osum = other.count, other.sum_ns
        with self._lock:
            for i, c in enumerate(oc):
                self.counts[i] += c
            self.count += ocount
            self.sum_ns += osum

    def snapshot(self) -> dict:
        with self._lock:
            return {"counts": list(self.counts), "count": self.count,
                    "sum_ns": self.sum_ns, "unit": self.unit}

    def percentile_s(self, q: float) -> float:
        return snapshot_percentile_s(self.snapshot(), q)


def snapshot_percentile_s(hsnap: dict, q: float) -> float:
    """Approximate quantile in SECONDS from a Histogram.snapshot(): the
    upper bound of the bucket holding the rank (overflow reports the
    last finite bound doubled).  Good to one log2 bucket — what the
    monitor service self-writes as p50/p99."""
    return snapshot_percentile(dict(hsnap, unit="seconds"), q)


def snapshot_percentile(hsnap: dict, q: float) -> float:
    """Quantile in the histogram's own unit (seconds for latency
    families, raw bytes for the devobs transfer-size families)."""
    bounds = _BOUNDS_S if hsnap.get("unit", "seconds") == "seconds" \
        else _BOUNDS_NS
    total = hsnap["count"]
    if total <= 0:
        return 0.0
    rank = max(1, int(q / 100.0 * total + 0.5))
    acc = 0
    for i, c in enumerate(hsnap["counts"]):
        acc += c
        if acc >= rank:
            return bounds[i] if i < _NBOUNDS else bounds[-1] * 2
    return bounds[-1] * 2


_HIST_LOCK = lockdep.Lock()
_HISTOGRAMS: dict[tuple, Histogram] = {}


def histogram_key(name: str, **labels) -> tuple:
    """The registry key of (name, labels), for call sites that look one
    histogram up per event and want to build the key once."""
    return (name, tuple(sorted(labels.items())))


def histogram(name: str, unit: str = "seconds", **labels) -> Histogram:
    """Get-or-create the process-wide histogram for (name, labels).
    Call sites with fixed labels should cache the returned object —
    observe_ns() itself is the hot path, not this lookup.  ``unit`` is
    fixed at first creation (a family never changes units)."""
    return histogram_at(histogram_key(name, **labels), unit)


def histogram_at(key: tuple, unit: str = "seconds") -> Histogram:
    """histogram() by a key histogram_key() built earlier: one dict
    lookup, and still correct after reset_histograms()."""
    h = _HISTOGRAMS.get(key)
    if h is None:
        with _HIST_LOCK:
            h = _HISTOGRAMS.get(key)
            if h is None:
                h = Histogram(key[0], key[1], unit=unit)
                _HISTOGRAMS[key] = h
    return h


def observe_ns(name: str, ns: int, **labels) -> None:
    if not _OBS_ON:
        return
    histogram(name, **labels).observe_ns(ns)


def histograms_snapshot() -> list[tuple[str, tuple, dict]]:
    """Every registered histogram as (name, labels, snapshot), grouped
    by family name (stable export order)."""
    with _HIST_LOCK:
        items = sorted(_HISTOGRAMS.items())
    return [(name, labels, h.snapshot()) for (name, labels), h in items]


def reset_histograms() -> None:
    with _HIST_LOCK:
        _HISTOGRAMS.clear()


# -- Prometheus text-format export (GET /metrics) ----------------------------
# The statisticsPusher analogue: every counter/gauge section of the
# registry plus the histograms, under `ogt_*` names, text format 0.0.4.

_NAME_OK = re.compile(r"[^a-zA-Z0-9_:]")

# registry sections whose metric already reads naturally as a Prometheus
# name get explicit stable spellings; everything else derives
# mechanically as ogt_<module>_<key>
_RENAMES = {
    ("write", "points"): ("ogt_write_rows_total", "counter"),
}


def _san(name: str) -> str:
    name = _NAME_OK.sub("_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _esc_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{_san(str(k))}="{_esc_label(str(v))}"'
                     for k, v in labels)
    return "{" + inner + "}"


def _fmt_val(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


def render_prometheus(version: str = "") -> str:
    lines: list[str] = []
    if version:
        lines.append("# HELP ogt_build_info build metadata")
        lines.append("# TYPE ogt_build_info gauge")
        lines.append(
            f'ogt_build_info{{version="{_esc_label(version)}"}} 1')
    lines.append("# HELP ogt_uptime_seconds process uptime")
    lines.append("# TYPE ogt_uptime_seconds gauge")
    lines.append(
        f"ogt_uptime_seconds "
        f"{_fmt_val(time.perf_counter() - GLOBAL.started_pc)}")

    # counters + provider gauges, one family per (module, key).  Two
    # distinct registry keys can sanitize to one family name (e.g.
    # failpoint sites differing only by '-' vs '_'): the first wins —
    # a duplicate TYPE line would fail any strict scraper
    seen: set[str] = {"ogt_build_info", "ogt_uptime_seconds"}
    snap = GLOBAL.snapshot()
    for module in sorted(snap):
        sect = snap[module]
        for key in sorted(sect):
            val = sect[key]
            if not isinstance(val, (int, float)):
                continue
            renamed = _RENAMES.get((module, key))
            if renamed:
                fam, typ = renamed
            else:
                fam = _san(f"ogt_{module}_{key}")
                typ = "counter" if key.endswith("_total") else "gauge"
            if fam in seen:
                continue
            seen.add(fam)
            lines.append(f"# TYPE {fam} {typ}")
            lines.append(f"{fam} {_fmt_val(val)}")

    # histograms: families share one TYPE header across label sets
    prev_fam = None
    skip_fam = None
    for name, labels, hsnap in histograms_snapshot():
        fam = _san(f"ogt_{name}")
        if fam == skip_fam:
            continue
        if fam != prev_fam:
            if fam in seen:  # name collision with a scalar family
                skip_fam = fam
                continue
            seen.add(fam)
            lines.append(f"# TYPE {fam} histogram")
            prev_fam = fam
        seconds = hsnap.get("unit", "seconds") == "seconds"
        bounds = _BOUNDS_S if seconds else _BOUNDS_NS
        acc = 0
        for i, c in enumerate(hsnap["counts"]):
            acc += c
            le = ("+Inf" if i == _NBOUNDS
                  else repr(bounds[i]) if seconds else str(bounds[i]))
            lab = _fmt_labels(tuple(labels) + (("le", le),))
            lines.append(f"{fam}_bucket{lab} {acc}")
        lab = _fmt_labels(labels)
        total = hsnap["sum_ns"] / 1e9 if seconds else hsnap["sum_ns"]
        lines.append(f"{fam}_sum{lab} {_fmt_val(total)}")
        lines.append(f"{fam}_count{lab} {hsnap['count']}")
    return "\n".join(lines) + "\n"

"""Per-layer metrics as data.  Each metric of BENCHMARK.json's `per_layer`
has a file `benchmark/metrics/<name>.json` that names a reader and its
parameters.  Readers that need no code are here; any other is one file
`benchmark/readers/<reader>.py` with `read(ctx, params)`.  A reader that
finds nothing to read returns None and the metric is left out of the line.

`ctx`: `vars0`/`vars1` (/debug/vars at the window's start and end, with the
client's own counts under the group `client`), `dev0`/`dev1` (/debug/device),
`client` (latency statistics), `trace` (the reduction of a profiler capture,
or None), `peaks`, `needs` (bytes and operations the traced work needs)."""

from __future__ import annotations

import json
import os

import numpy as np

from harness import load_module

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class MetricError(Exception):
    """A metric file that does not fit BENCHMARK.json or names no reader."""


def counter(doc: dict, path: str) -> float:
    """`group/name`; a counter nothing has incremented yet reads 0."""
    group, _, name = path.partition("/")
    return float(doc.get(group, {}).get(name, 0))


def _delta(ctx: dict, paths: list[str]) -> float:
    return sum(counter(ctx["vars1"], p) - counter(ctx["vars0"], p)
               for p in paths)


def vars_delta(ctx: dict, p: dict):
    return _delta(ctx, p["counters"]) * float(p.get("scale", 1))


def vars_ratio(ctx: dict, p: dict):
    den = _delta(ctx, p["den"])
    if den <= 0:
        return None
    return float(p.get("scale", 1)) * _delta(ctx, p["num"]) / den


def device_field(ctx: dict, p: dict):
    """The largest value of a per-device field, e.g.
    `memory_stats/peak_bytes_in_use`, at the window's end."""
    out = []
    for d in ctx["dev1"]["devices"]:
        v = d
        for key in p["field"].split("/"):
            v = v.get(key) if isinstance(v, dict) else None
        if v is not None:
            out.append(float(v))
    return max(out) if out else None


def window_decisions(ctx: dict) -> list[dict]:
    """The planner's decisions made inside the window, oldest first: as many
    of the ring's newest entries as `offload/decisions_total` grew by (one
    count an entry; a frozen planner goes on counting and writing its ring,
    and no longer moves a geometry's `uses`)."""
    p0, p1 = ctx["dev0"]["planner"], ctx["dev1"]["planner"]
    made = int(p1["counters"].get("decisions_total", 0)
               - p0["counters"].get("decisions_total", 0))
    ring = p1["decisions"]                                   # newest first
    return ring[:max(0, made)][::-1]


def planner_ring(ctx: dict, p: dict):
    """`stat`: `host_share` (% of the window's decisions that kept a stage
    on the host) or `flips` (decisions that took another route than the one
    before it for the same kernel and geometry).  The ring holds the last
    128 decisions; nothing decided in the window -> nothing to read."""
    ds = window_decisions(ctx)
    if not ds:
        return None
    if p["stat"] == "host_share":
        return 100.0 * sum(d["route"] == "host" for d in ds) / len(ds)
    last: dict[tuple, str] = {}
    flips = 0
    for d in ds:
        key = (d["kernel"], d["geometry"])
        flips += key in last and last[key] != d["route"]
        last[key] = d["route"]
    return float(flips)


def client(ctx: dict, p: dict):
    return ctx["client"].get(p["stat"])


BUILTIN = {"vars_delta": vars_delta, "vars_ratio": vars_ratio,
           "device_field": device_field, "planner_ring": planner_ring,
           "client": client}


def load(name: str, entry: dict):
    """(reader function, params) for one `per_layer` entry; raises where
    the file is missing, names an unknown reader, or states another unit or
    `moves` than BENCHMARK.json does."""
    path = os.path.join(HERE, "metrics", name + ".json")
    try:
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
    except OSError as e:
        raise MetricError(f"metric {name}: {e}")
    for key in ("unit", "moves", "source", "layer"):
        if spec.get(key) != entry.get(key):
            raise MetricError(
                f"metric {name}: its file says {key}={spec.get(key)!r}, "
                f"BENCHMARK.json says {entry.get(key)!r}")
    reader = spec.get("reader")
    if reader in BUILTIN:
        return BUILTIN[reader], spec.get("params", {})
    code = os.path.join(HERE, "readers", f"{reader}.py")
    if not isinstance(reader, str) or not os.path.isfile(code):
        raise MetricError(f"metric {name}: unknown reader {reader!r}")
    return load_module(code, f"reader_{reader}").read, spec.get("params", {})


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (tools/loadgen.py's rule)."""
    vals = np.sort(np.asarray(values, np.float64))
    k = min(len(vals) - 1, max(0, int(len(vals) * q / 100.0)))
    return float(vals[k])

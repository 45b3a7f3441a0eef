"""From a profiler capture (`*.xplane.pb`) to numbers: device busy time as
the union of the intervals in which an operation ran, the idle gaps and
what the host was doing in the longest of them, and device time by
operation and by program.

Runs as a child process (`python3 trace_reduce.py <capture dir>`) after the
server has stopped, because reading the capture imports JAX; prints one
JSON object.  Checked against a recorded capture in benchmark/selftest/."""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _program(name: str) -> str:
    """`jit_basic(123456789)` -> `jit_basic`."""
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """`%fusion.1 = f32[65536]{0} fusion(...)` -> `fusion.1`."""
    return name.split(" = ")[0].lstrip("%")[:80]


def reduce_planes(planes: list[dict]) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}].  Pure arithmetic, so that the self-test can drive it."""
    lo = min((s for p in planes for ln in p["lines"]
              for _, s, _ in ln["events"]), default=0.0)
    hi = max((s + d for p in planes for ln in p["lines"]
              for _, s, d in ln["events"]), default=0.0)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    busy_s, by_op, by_program, launches = [], {}, {}, {}
    gaps_dev: list[tuple[float, float]] = []
    for plane in devices:
        ops = [e for ln in plane["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]]
        mods = sorted((s, s + d, _program(name)) for ln in plane["lines"]
                      if ln["name"] == MODULES_LINE
                      for name, s, d in ln["events"])
        for _, _, prog in mods:
            launches[prog] = launches.get(prog, 0) + 1
        k = 0
        for name, s, d in sorted(ops, key=lambda e: e[1]):
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            prog = mods[k][2] if k < len(mods) and mods[k][0] <= s else "-"
            key = f"{prog}/{_op(name)}"
            by_op[key] = by_op.get(key, 0.0) + d
            by_program[prog] = by_program.get(prog, 0.0) + d
        merged = union([(s, s + d) for _, s, d in ops])
        if not merged:
            continue
        busy_s.append(sum(b - a for a, b in merged) / 1e9)
        if not gaps_dev:            # the first chip that did any work
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps_dev = [(edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i]]
    gaps = sorted(gaps_dev, key=lambda g: g[0] - g[1])[:TOP]
    hosts = [p for p in planes if not DEVICE_PLANE.match(p["name"])]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "devices_traced": len(devices), "devices_busy": len(busy_s),
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "program_s": {k: v / 1e9 for k, v in by_program.items()},
        "launches": launches,
        "idle_gaps": attribute(gaps, hosts),
    }


def attribute(gaps, host_planes) -> list[list]:
    """Each of the longest idle gaps with the host frame that best says
    what the host was doing: the shortest frame covering half the gap or
    more, else the frame overlapping it most."""
    if not gaps:
        return []
    floor = min(b - a for a, b in gaps) * 0.25
    best: list = [None] * len(gaps)       # (rank, label)
    for p in host_planes:
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if d < floor:
                    continue
                for i, (a, b) in enumerate(gaps):
                    over = min(b, s + d) - max(a, s)
                    if over <= 0:
                        continue
                    rank = (0, d) if over >= 0.5 * (b - a) else (1, -over)
                    if best[i] is None or rank < best[i][0]:
                        label = f"{ln['name']}:{name.lstrip('$').strip()}"
                        best[i] = (rank, label[:100])
    return [[best[i][1] if best[i] else "(no host event)", (b - a) / 1e9]
            for i, (a, b) in enumerate(gaps)]


def read_capture(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [{"name": p.name, "lines": [
        {"name": ln.name,
         "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in ln.events]} for ln in p.lines]}
        for p in data.planes]


def find_capture(root: str) -> str:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def main() -> int:
    path = sys.argv[1]
    if os.path.isdir(path):
        path = find_capture(path)
    planes = read_capture(path)
    if "--planes" in sys.argv:      # a look at a capture by hand
        for p in planes:
            print(p["name"], [(ln["name"], len(ln["events"]))
                              for ln in p["lines"]][:40], file=sys.stderr)
    print(json.dumps(reduce_planes(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

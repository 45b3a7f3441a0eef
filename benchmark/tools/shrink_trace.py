#!/usr/bin/env python3
"""Cut a profiler capture down to a size the repository can keep, and say
what an independent reader finds in what is left.

    python3 benchmark/tools/shrink_trace.py <in.xplane.pb> <out stem> [t0_s t1_s]

Keeps the device planes (inside the slice [t0_s, t1_s) of the capture, if
given) and, of the host planes, only events of a millisecond or more; writes
`<stem>.xplane.pb` and `<stem>.expected.json`: busy time as a brute-force
union over a grid of the device events, the window, time by operation and
the longest gap, computed here from the protobuf itself (tensorflow's
`xplane_pb2`, picoseconds) and not by harness/trace_reduce.py, which the
self-test then holds to them.  A tool for whoever records a capture; no run
and no test imports it."""

from __future__ import annotations

import json
import re
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def main() -> int:
    src, stem = sys.argv[1], sys.argv[2]
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    starts = [ln.timestamp_ns * 1000 + e.offset_ps for p in space.planes
              for ln in p.lines for e in ln.events]
    base = min(starts)
    lo, hi = base, max(starts) + 1
    if len(sys.argv) > 4:
        lo = base + int(float(sys.argv[3]) * 1e12)
        hi = base + int(float(sys.argv[4]) * 1e12)
    for p in space.planes:
        device = bool(DEVICE.match(p.name))
        for ln in p.lines:
            keep = [e for e in ln.events
                    if lo <= ln.timestamp_ns * 1000 + e.offset_ps < hi
                    and (device or e.duration_ps >= 10**9)]
            del ln.events[:]
            ln.events.extend(keep)
        used = {e.metadata_id for ln in p.lines for e in ln.events}
        for k in [k for k in p.event_metadata if k not in used]:
            del p.event_metadata[k]
        for k in list(p.stat_metadata):
            del p.stat_metadata[k]
        for ln in p.lines:
            for e in ln.events:
                del e.stats[:]
        for k in p.event_metadata:
            del p.event_metadata[k].stats[:]
        del p.stats[:]
    with open(stem + ".xplane.pb", "wb") as f:
        f.write(space.SerializeToString())

    # the independent reading, in picoseconds
    every = [(ln.timestamp_ns * 1000 + e.offset_ps, e.duration_ps)
             for p in space.planes for ln in p.lines for e in ln.events]
    w0 = min(s for s, _ in every)
    w1 = max(s + d for s, d in every)
    plane = next(p for p in space.planes if DEVICE.match(p.name)
                 and any(ln.name == "XLA Ops" and ln.events for ln in p.lines))
    ops = [(ln.timestamp_ns * 1000 + e.offset_ps, e.duration_ps,
            plane.event_metadata[e.metadata_id].name)
           for ln in plane.lines if ln.name == "XLA Ops" for e in ln.events]
    mods = [(ln.timestamp_ns * 1000 + e.offset_ps, e.duration_ps,
             re.sub(r"\(\d+\)$", "", plane.event_metadata[e.metadata_id].name))
            for ln in plane.lines if ln.name == "XLA Modules"
            for e in ln.events]
    # busy: sweep over the sorted edges (+1 at a start, -1 at an end)
    edges = sorted([(s, 1) for s, _, _ in ops] + [(s + d, -1) for s, d, _ in ops])
    busy = depth = 0
    gap_from, longest = w0, 0
    for at, step in edges:
        if depth == 0 and step == 1:
            longest = max(longest, at - gap_from)
            since = at
        depth += step
        if depth == 0:
            busy += at - since
            gap_from = at
    longest = max(longest, w1 - gap_from)
    by_op: dict[str, int] = {}
    for s, d, name in ops:
        prog = next((m for ms, md, m in mods if ms <= s < ms + md), "-")
        key = f"{prog}/{name.split(' = ')[0].lstrip('%')[:80]}"
        by_op[key] = by_op.get(key, 0) + d
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:5]
    devices_busy = sum(
        any(ln.name == "XLA Ops" and ln.events for ln in p.lines)
        for p in space.planes if DEVICE.match(p.name))
    with open(stem + ".expected.json", "w") as f:
        json.dump({"busy_s": busy / 1e12, "window_s": (w1 - w0) / 1e12,
                   "devices_busy": devices_busy,
                   "longest_gap_s": longest / 1e12,
                   "device_ops": {k: v / 1e12 for k, v in top},
                   "recorded_from": src.split("/")[-1]}, f, indent=1)
        f.write("\n")
    print(open(stem + ".expected.json").read())
    return 0


if __name__ == "__main__":
    sys.exit(main())

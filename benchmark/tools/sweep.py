#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one set-up, then a short window
at each of a few fixed rates.

    python3 benchmark/tools/sweep.py --workload tsbs_host_panels --seed 7 \
        --rates 1,2,4,8,16,32 --seconds 12

Prints, per rate: requests due and answered, completions per second, the
median and 95th percentile of latency from when a request was due, and how
late the generator sent.  The knee is the highest rate at which completions
keep up and lateness does not grow; the traffic file then fixes a whole
number of q/s under it and records what each rate gave.  Not part of a
run: the benchmark never searches for a rate."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run                      # noqa: E402
from harness import metrics, traffic         # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", default="1,2,4,8,16,32")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--cpu-dry-run", action="store_true")
    a = ap.parse_args()
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=0,
                              cpu_dry_run=a.cpu_dry_run, keep_trace=None)
    cell = bench_run.Cell(args, bench_run.load_json(bench_run.ROOT,
                                                    "BENCHMARK.json"))
    rows = []
    try:
        _, ref, _, _ = cell.set_up()
        for k, rate in enumerate(float(r) for r in a.rates.split(",")):
            t = dict(cell.traffic, loop=dict(cell.traffic["loop"],
                                             rate_qps=rate))
            plan = traffic.build(t, ref, a.seed + 1000 * (k + 1), a.seconds)
            traffic.run(plan, cell.srv.port, a.seconds)
            ok = [r for r in plan.results if r.ok]
            lat = [1e3 * (r.done - r.due) for r in ok]
            late = [1e3 * (r.sent - r.due) for r in plan.results]
            row = {"rate_qps": rate, "due": len(plan.results),
                   "answered": len(ok),
                   "completed_per_s": len(ok) / (plan.t_end - plan.t_start),
                   "drain_s": plan.t_end - plan.t_start - a.seconds,
                   "p50_ms": metrics.percentile(lat, 50),
                   "p95_ms": metrics.percentile(lat, 95),
                   "late_p95_ms": metrics.percentile(late, 95)}
            rows.append(row)
            print("sweep " + json.dumps(row), flush=True)
            time.sleep(1.0)
    finally:
        if cell.srv is not None:
            cell.srv.stop()
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

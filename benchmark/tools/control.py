#!/usr/bin/env python3
"""The control of `correct`, at the cells' own sizes.

    python3 benchmark/tools/control.py --workload <name> --seeds 1,2,3

For a read cell: the plain reference computed in bfloat16 (the precision
below the served path's float32) stands in the program's place; its answers
to the cell's own statements, at the cell's own size, are compared with the
float64 reference by the run's own comparison, and the smallest number the
control gives is printed beside the limit it has to break.  Needs no server.

For the write cell: the control breaks the guarantee the configuration
states (every acknowledged row is read back) — one /write is reported
acknowledged and never sent — through a whole run against a real server
(--seconds, default 8), and `correct` has to come out false.

The benchmark's own runs never run this; benchmark/selftest/ keeps both at
a size a test run can hold."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run                      # noqa: E402
from harness import traffic                  # noqa: E402
from harness.oracle import to_bf16           # noqa: E402


def drop_one_write(send):
    def broken(client, req, res, keep):
        if res.index == 5:
            res.sent = res.done = res.due
            res.status, res.ok = 204, True      # "acknowledged", never sent
            return
        send(client, req, res, keep)
    return broken


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--statements", type=int, default=3)
    ap.add_argument("--cpu-dry-run", action="store_true")
    a = ap.parse_args()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    failed = 0
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(
            workload=a.workload, seed=seed, seconds=a.seconds, trace=0,
            cpu_dry_run=a.cpu_dry_run, keep_trace=None)
        cell = bench_run.Cell(args, bench)
        if cell.traffic["kind"] == "lp_stream":
            real_send = traffic.send
            traffic.send = drop_one_write(real_send)
            try:
                out = cell.run()
            finally:
                traffic.send = real_send
                cell.srv.stop()
            print(f"control {a.workload} seed {seed}: correct="
                  f"{out['correct']} (has to be false)", flush=True)
            failed += out["correct"] is not False
            continue
        ref = cell.reference()
        plan = traffic.build(cell.traffic, ref, seed, 1.0)
        smallest: dict[str, float] = {}
        for req in plan.requests[:a.statements]:
            nums = ref.numbers(req.stmt, ref.want(req.stmt, narrow=to_bf16))
            for name, (value, limit) in nums.items():
                smallest[name] = min(value, smallest.get(name, value))
                failed += value <= limit
        for name, value in smallest.items():
            print(f"control {a.workload} seed {seed}: smallest {name} = "
                  f"{value:.6g} over {a.statements} statement(s) at "
                  f"{ref.rows} rows (limit {nums[name][1]:.6g}, has to be "
                  "above it)", flush=True)
    print(json.dumps({"control_came_out_not_correct": failed == 0}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

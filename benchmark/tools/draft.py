#!/usr/bin/env python3
"""Drive whole runs of the cell that reads while it writes, its rates varied.

    python3 benchmark/tools/draft.py --seeds 1,2,3 --rows-per-s 400,4000 \
        --seconds 51 --trace 1
    python3 benchmark/tools/draft.py --seeds 1 --control stale
    python3 benchmark/tools/draft.py --seeds 7 --sweep 2.5,5,10,20 --seconds 12

PR 31 drafted `tsbs_dash_refresh` (panels re-asked over a trailing hour
beside a paced write stream) with this tool, from two files under
`selftest/draft/`; PR 32 made them the real cell's and PR 41 deleted the
copies, so this drives the cell BENCHMARK.json has: whole runs through
`run.Cell`, one JSON line a run, with the ingest's rate varied
(`--rows-per-s`): the numbers an issue names a mixed cell's rates with.
`--control` breaks the timed path (`tools/control.py`: `drop` one
acknowledged /write, a `stale` answer) and the run has to come out
`correct: false`; `--sweep` is `tools/sweep.py` over the cell, its ingest on.
None of this is a run of the benchmark, and nothing it prints is a ledger
metric."""

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
from tools import control, sweep             # noqa: E402

CELL = "tsbs_dash_refresh"
# per million rows the ingest wrote, as the cell's own metric files will
# divide (`client/rows_written`); the load cell's divide by its `units`
PER_MROW = ("flush_inline", "write_lock_wait", "lp_parse", "memtable_apply",
            "write_observers")


def cell(seed: int, seconds: float, trace: int, dry: bool,
         rows_per_s: float | None = None):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace, cpu_dry_run=dry, keep_trace=None)
    bench_run.T_PROCESS = time.monotonic()   # a run's set-up counts from here
    c = bench_run.Cell(args, bench_run.load_json(bench_run.ROOT,
                                                 "BENCHMARK.json"))
    if rows_per_s is not None:
        c.traffic["ingest"] = dict(c.traffic["ingest"], rows_per_s=rows_per_s)
    return c


def numbers(c, out: dict) -> dict:
    """One run's line: the result line's own keys, then what the issue
    asks of a draft run, from the same `ctx` the metric files read."""
    ctx, cl = c.ctx, c.ctx["client"]
    v1 = ctx["vars1"]["client"]

    def ratio(num, den, scale=1.0):
        return metrics.vars_ratio(ctx, {"num": num, "den": den,
                                        "scale": scale})

    row = {"seed": c.args.seed, "rows_per_s": c.traffic["ingest"]["rows_per_s"],
           "refresh_s": c.traffic["panels"]["refresh_s"],
           "correct": out["correct"], "failed": out["failed"],
           "attempted": out["attempted"],
           "completed_share": 100.0 * v1["completed"] / max(1, v1["attempted"]),
           "p50_ms": cl["p50_ms"], "p95_ms": cl["p95_ms"],
           "gen_late_p95_ms": cl["late_p95_ms"],
           "write_late_p95_ms": cl["write_late_p95_ms"],
           "rows_written": metrics.vars_delta(
               ctx, {"counters": ["client/rows_written"]}),
           "setup_s": ctx["setup_s"], "window_s": ctx["window_s"],
           "resultcache_reuse_share": ratio(
               ["executor/inc_cache_windows_reused"], ["client/windows"], 100),
           "resultcache_full_hit_share": ratio(
               ["executor/inc_cache_full_hits"], ["client/completed"], 100),
           "bg_merges_in_window": metrics.vars_delta(
               ctx, {"counters": bench_run.MERGE_COUNTERS}),
           "flushes_in_window": metrics.vars_delta(
               ctx, {"counters": ["flush/flushes"]}),
           "compiles_in_window": metrics.vars_delta(
               ctx, {"counters": ["device/xla_programs_total"]}),
           "checks": {k: list(v) for k, v in c.checks.worst.items()},
           "memory_peak_bytes": out["device"].get("memory_peak_bytes")}
    for stage in PER_MROW:
        row[f"{stage}_ms_per_mrow"] = ratio(
            [f"write_stages/{stage}_ns"], ["client/rows_written"], 1.0)
    if "busy_s" in out["device"]:
        row["device_idle_share"] = 100.0 * (
            1.0 - out["device"]["busy_s"] / out["device"]["window_s"])
        row["per_layer"] = {k: m["value"] for k, m in out["metrics"].items()}
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rows-per-s", default=None,
                    help="comma-separated; default: the traffic file's")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", choices=("drop", "stale"))
    ap.add_argument("--sweep", metavar="RATES",
                    help="panels' q/s to try behind one set-up")
    ap.add_argument("--cpu-dry-run", action="store_true")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    rates = [float(r) for r in a.rows_per_s.split(",")] \
        if a.rows_per_s else [None]
    bad = 0
    for rate in rates:
        for seed in seeds:
            c = cell(seed, a.seconds, a.trace, a.cpu_dry_run, rate)
            if a.sweep:
                rows = sweep.sweep(c, [float(r) for r in a.sweep.split(",")],
                                   a.seconds, seed)
                print("draft " + json.dumps({
                    "sweep": rows, "seed": seed,
                    "rows_per_s": c.traffic["ingest"]["rows_per_s"]}),
                    flush=True)
                continue
            real_send = traffic.send
            if a.control:
                traffic.send = {"drop": control.drop_one_write,
                                "stale": control.stale_answer}[a.control](
                                    real_send)
            try:
                row = numbers(c, c.run())
            finally:
                traffic.send = real_send
                c.stop()
            if a.control:
                row["control"] = a.control
                bad += row["correct"] is not False
            else:
                bad += row["correct"] is not True or row["failed"] != 0
            print("draft " + json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

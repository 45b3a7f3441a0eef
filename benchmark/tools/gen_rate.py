#!/usr/bin/env python3
"""What the load generator alone sustains: a cell's `lp_stream`, made and
sent as a run makes and sends it, against a sink that reads each body and
answers 204 at once.

    python3 benchmark/tools/gen_rate.py --workload tsbs_load --seconds 10

The sink is a child process (its own interpreter, so no lock is shared);
this one goes onto one core, as `run.py`'s does before a window.  The rate
it prints is the ceiling a server could be measured at; a cell is sound
while its server stays well under it (PERF.md §2)."""

from __future__ import annotations

import argparse
import http.server
import json
import os
import resource
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run                      # noqa: E402
from harness import traffic                  # noqa: E402


class Sink(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        self.send_response(204)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, *args):
        pass


def sink() -> int:
    srv = http.server.HTTPServer(("127.0.0.1", 0), Sink)
    print(srv.server_address[1], flush=True)
    srv.serve_forever()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="tsbs_load")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--sink", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.sink:
        return sink()
    args = argparse.Namespace(workload=a.workload, seed=a.seed, trace=0,
                              seconds=a.seconds, cpu_dry_run=a.cpu_dry_run,
                              keep_trace=None)
    cell = bench_run.Cell(args, bench_run.load_json(bench_run.ROOT,
                                                    "BENCHMARK.json"))
    child = subprocess.Popen([sys.executable, os.path.abspath(__file__),
                              "--sink"], stdout=subprocess.PIPE, text=True)
    try:
        port = int(child.stdout.readline())
        plan = traffic.build(cell.traffic, cell.reference(), a.seed, a.seconds)
        bench_run.onto_one_core()
        traffic.run(plan, port, a.seconds)
    finally:
        child.kill()
        child.wait()
    took = plan.t_end - plan.t_start
    rows = sum(plan.requests[r.index].units for r in plan.results if r.ok)
    print(json.dumps({
        "generator_rows_per_s": rows / took, "batches": len(plan.results),
        "failed": sum(not r.ok for r in plan.results),
        "ms_per_batch": 1e3 * took / max(1, len(plan.results)),
        "bodies_held": int(plan.stream.made is not None),
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10,
        "seconds": took}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <name> --cpu-dry-run    # control flow only

This process stays off JAX.  It builds the native libraries, starts ONE
server through its CLI entry point with the server's defaults, exits
non-zero unless the server reports platform `tpu` with as many chips as the
cell asks for, makes the data from --seed, loads it, quiesces the server,
warms up until no program is built and the planner stands still, measures
for --seconds, verifies answers against the plain reference, stops the
server and prints one JSON line.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own under benchmark/, found by the name in BENCHMARK.json."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()      # set-up counts from here

import argparse                   # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import resource                   # noqa: E402
import shutil                     # noqa: E402
import subprocess                 # noqa: E402
import sys                        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import load_module, metrics, peaks, traffic  # noqa: E402
from harness.metrics import counter                       # noqa: E402
from harness.oracle import Mismatch, read_count            # noqa: E402
from harness.server import (BenchFailure, Client, Server,  # noqa: E402
                            build_native)

WORK = os.path.join(ROOT, ".bench_work")
MERGE_COUNTERS = ["compaction/leveled_merges", "compaction/out_of_order_merges",
                  "compaction/full_merges", "compact/offlock_merges"]


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS:7.1f}s] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def with_dry(doc: dict, dry: bool) -> dict:
    return {**doc, **doc.get("dry_run", {})} if dry else doc


def onto_one_core() -> None:
    """This thread, and those it starts from here on, onto the machine's
    last core: the generator leaves the others to the server."""
    if len(os.sched_getaffinity(0)) > 2:
        os.sched_setaffinity(0, {max(os.sched_getaffinity(0))})


class Checks:
    """Every number compared, printed beside its limit; the worst per name
    is printed again at the end."""

    def __init__(self):
        self.worst: dict[str, tuple[float, float]] = {}
        self.bad = 0

    def add(self, name: str, value: float, limit: float, what: str = "") -> bool:
        ok = bool(value <= limit)
        self.bad += not ok
        if name not in self.worst or value > self.worst[name][0]:
            self.worst[name] = (value, limit)
        if what or not ok:
            log(f"check {name} = {value:.6g} (limit {limit:.6g}) "
                f"{'ok' if ok else 'FAILED'} {what}")
        return ok

    def fail(self, what: str) -> None:
        self.bad += 1
        log(f"check FAILED: {what}")


class Cell:
    def __init__(self, args, bench: dict):
        self.args = args
        self.dry = args.cpu_dry_run
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise BenchFailure(f"BENCHMARK.json has no workload "
                               f"{args.workload!r}")
        self.cell = cell
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.cfg = with_dry(load_json(ROOT, conf["file"]), self.dry)
        self.traffic = with_dry(
            load_json(HERE, "traffic", cell["traffic"] + ".json"), self.dry)
        self.e2e = [m for m in bench["end_to_end"]
                    if cell["name"] in m.get("workloads", [cell["name"]])]
        self.layer = [m for m in bench["per_layer"]
                      if cell["name"] in m.get("workloads", [cell["name"]])]
        # a traffic or metric file that does not fit fails before anything
        # starts
        traffic.check(self.traffic, self.cfg)
        self.readers = {m["name"]: metrics.load(m["name"], m)
                        for m in self.layer}
        for m in self.e2e:
            if m["name"] not in END_TO_END:
                raise BenchFailure(f"no code computes the end-to-end metric "
                                   f"{m['name']!r}")
        self.checks = Checks()
        self.srv: Server | None = None
        self.trace_dir = os.path.join(WORK, "trace")
        self.trace_at = 0.0             # client clock, capture requested
        self.phase = None               # the traced phase's plan

    # -- set-up ---------------------------------------------------------------

    def reference(self):
        mod = load_module(os.path.join(HERE, "configs", self.cfg["reference"]),
                          "reference")
        if self.traffic["kind"] == "lp_stream":
            # TSBS's loader: rows made as they are sent, none stored
            return mod.Reference(self.cfg, self.args.seed, stored=False)
        return mod.Reference(self.cfg, self.args.seed)

    def start(self) -> dict:
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        log(f"native libraries ready in {build_native(ROOT):.1f}s")
        self.srv = Server(ROOT, WORK, self.dry)
        log(f"server ready {self.srv.wait_ready():.1f}s after its start")
        devices = self.srv.device()["devices"]
        device = {"platform": devices[0]["platform"],
                  "kind": devices[0]["device_kind"], "count": len(devices)}
        log(f"device: {device}")
        want = "cpu" if self.dry else "tpu"
        if device["platform"] != want:
            raise BenchFailure(
                f"the server runs on {device['platform']!r}, not {want!r}: "
                "a cell is measured on a TPU (--cpu-dry-run is the control-"
                "flow run)")
        if device["count"] < self.cell["chips"]:
            raise BenchFailure(f"{device['count']} chip(s), the cell asks "
                               f"for {self.cell['chips']}")
        if not self.dry:
            peaks.peaks_for(device["kind"])
        return device

    def load(self, ref) -> None:
        """The set-up load, in the many-rows-per-series shape, then flush,
        quiesce, and the guarantee: every acknowledged row is read back."""
        srv = self.srv
        t0 = time.monotonic()
        acked = 0
        for body, rows in ref.load_requests():
            status, data = srv.call("POST", "/write", body, db=ref.db)
            if status != 204:
                raise BenchFailure(f"set-up /write -> HTTP {status}: "
                                   f"{data[:300]!r}")
            acked += rows
        log(f"loaded {acked} rows in {time.monotonic() - t0:.1f}s "
            f"({acked / (time.monotonic() - t0):.0f} rows/s)")
        self.quiesce()
        got = read_count(srv.query(ref.count_q, ref.db))
        self.checks.add("rows_acked_not_read_back", abs(acked - got), 0,
                        f"(acked {acked}, count() {got})")

    def quiesce(self) -> None:
        """Flush the memtables, then wait until flushes and merges have
        stood still: a read window begins with nothing in the background."""
        srv, q = self.srv, self.cfg["quiesce"]
        t0 = time.monotonic()
        srv.json("POST", "/debug/ctrl", mod="flush")
        watch = ["flush/flushes", "encodepool/queue_depth"] + MERGE_COUNTERS
        last, still = None, 0
        while still < q["still_polls"]:
            if time.monotonic() - t0 > q["timeout_s"]:
                raise BenchFailure("the server did not quiesce in "
                                   f"{q['timeout_s']}s: {last}")
            time.sleep(q["poll_s"])
            v = srv.vars()
            now = [counter(v, p) for p in watch]
            still = still + 1 if now == last and now[1] == 0 else 0
            last = now
        files = [os.path.join(d, f) for d, _, fs in os.walk(WORK) for f in fs
                 if f.endswith(".tsf")]
        log(f"flushed and quiet after {time.monotonic() - t0:.1f}s: "
            + ", ".join(f"{p}={int(x)}" for p, x in zip(watch, last))
            + f"; {len(files)} data file(s), "
            f"{sum(map(os.path.getsize, files)) >> 20} MB")

    def warm_up(self, plan, ref) -> list:
        """Touch every column the window can touch, then repeat the cell's
        statement shape until the last three repeats built no XLA program
        and each kernel's last five planner decisions name one route."""
        srv, w = self.srv, self.traffic["warm"]
        client = Client(srv.port)
        kept = []

        def ask(req):
            res = traffic.Result(len(kept), due=time.perf_counter())
            traffic.send(client, req, res, keep=True)
            kept.append((req, res))
            return res

        if self.traffic["kind"] == "lp_stream":
            srv.query("CREATE DATABASE warm")
            for req in plan.warm_touch:
                status, _ = srv.call("POST", "/write", req.body, db="warm")
                if status != 204:
                    raise BenchFailure(f"warm-up /write -> HTTP {status}")
            srv.query("DROP DATABASE warm")
            client.close()
            return kept
        for req in plan.warm_touch:
            log(f"warm touch: {ask(req).done - kept[-1][1].sent:.2f}s")
        built = [counter(srv.vars(), "device/xla_programs_total")]
        for n, req in enumerate(plan.warm_repeat, 1):
            res = ask(req)
            built.append(counter(srv.vars(), "device/xla_programs_total"))
            routes: dict[tuple, list[str]] = {}
            for d in srv.device()["planner"]["decisions"]:     # newest first
                routes.setdefault((d["kernel"], d["geometry"]), []).append(
                    d["route"])
            moving = [k for k, r in routes.items() if len(set(r[:5])) > 1]
            steady = (n >= w["repeats_min"] and built[-1] == built[-4]
                      and not moving)
            log(f"warm repeat {n}: {res.done - res.sent:.3f}s, "
                f"{int(built[-1] - built[-2])} program(s) built, "
                f"{len(moving)} kernel(s) with a moving route"
                + (" - steady" if steady else ""))
            if steady:
                break
        else:
            log(f"warm-up reached its limit of {w['repeats_max']} repeats "
                "without standing still; the guards will show it")
        client.close()
        if plan.loop["kind"] == "open":
            # the window's requests overlap; so do the last of the warm-up's
            left = plan.warm_repeat[n:]
            burst = traffic.Plan([], [], left, plan.loop, [True] * len(left))
            traffic.run_open(burst, srv.port, float(w["burst_s"]))
            kept.extend((left[r.index], r) for r in burst.results)
            took = [1e3 * (r.done - r.due) for r in burst.results]
            log(f"warm burst of {w['burst_s']}s at the cell's rate: "
                f"{len(took)} requests, slowest {max(took):.0f} ms")
        return kept

    # -- the window -----------------------------------------------------------

    def snapshot(self, plan=None) -> tuple[dict, dict]:
        v, d = self.srv.vars(), self.srv.device()
        results = plan.results if plan else []
        done = [plan.requests[r.index] for r in results if r.ok]
        v["client"] = {
            "completed": len(done), "attempted": len(results),
            "units": sum(q.units for q in done),
            "windows": sum(q.stmt.get("windows", 0) for q in done),
        }
        return v, d

    def capture(self, plan, ref) -> None:
        """The traced phase, after the window and its counters: start the
        server's profiler capture, go on sending the same traffic for the
        seconds the traffic file names, and wait for the capture to end.
        The write cell then reads its rows back inside the capture: its one
        device operation."""
        t = self.traffic["trace"]
        self.phase = traffic.rest(plan)
        t0 = time.perf_counter()
        self.srv.json("POST", "/debug/ctrl", mod="devobs", op="profile",
                      seconds=float(t["seconds"]), dir=self.trace_dir)
        log(f"capture of {t['seconds']}s began "
            f"({time.perf_counter() - t0:.1f}s to start)")
        self.trace_at = t0
        traffic.run(self.phase, self.srv.port, float(t["send_s"]))
        if self.traffic["kind"] == "lp_stream":
            self.verify_writes(plan, ref)
        self.wait_trace()
        log(f"capture ended; {len(self.phase.results)} requests sent in it")

    def wait_trace(self) -> None:
        while self.srv.json("POST", "/debug/ctrl",
                            mod="devobs")["profile"]["active"]:
            time.sleep(0.25)
        last = self.srv.json("POST", "/debug/ctrl", mod="devobs")["profile"]
        if not (last.get("last") or {}).get("ok"):
            raise BenchFailure(f"the profiler capture failed: {last}")

    def verify(self, plan, ref, warm) -> int:
        """Answers against the plain reference: every warm-up answer and
        the seed-chosen sample of the window's.  Returns how many of the
        window's requests failed (bad status or shape, or a wrong answer)."""
        failed = sum(not r.ok for r in plan.results)
        for r in plan.results:
            if not r.ok:
                log(f"request {r.index}: HTTP {r.status}, "
                    f"{(r.body or b'')[:200]!r}")
        sample = [(plan.requests[r.index], r, True) for r in plan.results
                  if r.ok and r.body is not None]
        for req, res, timed in [(q, r, False) for q, r in warm] + sample:
            if req.stmt["kind"] == "write":
                continue
            try:
                if not res.ok:
                    raise Mismatch(f"HTTP {res.status}: {res.body[:200]!r}")
                got = ref.parse(req.stmt, json.loads(res.body))
                for name, (value, limit) in ref.numbers(req.stmt, got).items():
                    if not self.checks.add(name, value, limit):
                        failed += timed
            except Mismatch as e:
                self.checks.fail(f"{req.stmt['q'][:80]}: {e}")
                failed += timed
        log(f"verified {len(sample)} of the window's {len(plan.results)} "
            f"answers and {len(warm)} warm-up answers against the oracle")
        return failed

    def verify_writes(self, plan, ref) -> None:
        srv = self.srv
        acked = sum(p.requests[r.index].units
                    for p in (plan, self.phase) if p is not None
                    for r in p.results if r.ok)
        got = read_count(srv.query(ref.count_q, ref.db))
        self.checks.add("rows_acked_not_read_back", abs(acked - got), 0,
                        f"(acked {acked}, count() {got})")
        dur = srv.json("POST", "/debug/ctrl", mod="durability")
        self.checks.add("durability_violations", len(dur["violations"]), 0,
                        f"(status {dur['status']})")

    # -- the run --------------------------------------------------------------

    def set_up(self):
        """Everything before the clock starts: server, data, load, quiesce,
        the request list, warm-up; then this process, which from here on
        only sends and times, goes onto one core of its own."""
        a = self.args
        device = self.start()
        ref = self.reference()
        log(f"data from seed {a.seed}: {ref.rows} rows")
        self.srv.query(f"CREATE DATABASE {ref.db}")
        if self.traffic["kind"] != "lp_stream":
            self.load(ref)
        more = float(self.traffic["trace"]["send_s"]) if a.trace else 0.0
        plan = traffic.build(self.traffic, ref, a.seed, a.seconds + more)
        warm = self.warm_up(plan, ref)
        traffic.join_walk(plan, len(warm) - len(plan.warm_touch))
        onto_one_core()
        return device, ref, plan, warm

    def run(self) -> dict:
        a = self.args
        device, ref, plan, warm = self.set_up()
        srv = self.srv
        writes = self.traffic["kind"] == "lp_stream"
        srv.json("POST", "/debug/ctrl", mod="devobs", op="mark_warm")
        vars0, dev0 = self.snapshot()
        setup_s = time.monotonic() - T_PROCESS
        log(f"set-up took {setup_s:.1f}s; the window of {a.seconds}s begins "
            + ("(a stream made from the seed as it is sent, "
               if plan.stream else
               f"({len(plan.requests)} requests made from the seed, ")
            + f"loop {plan.loop})")
        traffic.run(plan, srv.port, a.seconds)
        vars1, dev1 = self.snapshot(plan)
        window_s = plan.t_end - plan.t_start
        took = [1e3 * (r.done - r.due) for r in plan.results] or [0.0]
        log(f"window closed after {window_s:.3f}s: {len(plan.results)} sent, "
            f"{vars1['client']['completed']} answered in shape; latency ms "
            "p10/p50/p90/max " + "/".join(
                f"{metrics.percentile(took, q):.0f}" for q in (10, 50, 90, 100)))
        if writes:
            served = 1e-9 * (counter(vars1, "http/write_ns")
                             - counter(vars0, "http/write_ns"))
            log(f"the client's share of a batch: ({window_s:.3f}s - "
                f"{served:.3f}s inside the server's http_write) / "
                f"{len(plan.results)} = "
                f"{1e3 * (window_s - served) / max(1, len(plan.results)):.2f}"
                " ms; peak resident memory of this process "
                f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10}"
                " MB")
        if plan.exhausted:
            log("the request list ran out before the window did: the rate "
                "is over the time the requests took (raise the traffic "
                "file's max_qps)")
        if a.trace:
            self.capture(plan, ref)
        elif writes:
            self.verify_writes(plan, ref)
        failed = self.verify(plan, ref, warm)
        mem = metrics.device_field({"dev1": srv.device()},
                                   {"field": "memory_stats/peak_bytes_in_use"})
        srv.stop()
        log("server stopped")

        ok = [r for r in plan.results if r.ok]
        lat = [1e3 * (r.done - r.due) for r in ok]
        late = [1e3 * (r.sent - r.due) for r in plan.results]
        shed = sum(r.status in (429, 503) for r in plan.results)
        ctx = {
            "vars0": vars0, "vars1": vars1, "dev0": dev0, "dev1": dev1,
            "window_s": window_s, "setup_s": setup_s, "trace": None,
            "units": vars1["client"]["units"],
            "client": {
                "p50_ms": metrics.percentile(lat, 50) if lat else None,
                "p95_ms": metrics.percentile(lat, 95) if lat else None,
                "max_ms": max(lat) if lat else None,
                "late_p95_ms": metrics.percentile(late, 95) if late else None,
                "shed_share": 100.0 * shed / max(1, len(plan.results)),
            },
        }
        device["memory_peak_bytes"] = int(mem or 0)
        out = {"correct": self.checks.bad == 0, "attempted": len(plan.results),
               "failed": failed, "metrics": {}, "device": device}
        if a.trace:
            ctx["trace"] = self.reduce_trace()
            ctx["traced"] = self.traced_work(ctx["trace"])
            ctx["peaks"] = None if self.dry else peaks.peaks_for(device["kind"])
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            out["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                                "idle_gaps": ctx["trace"]["idle_gaps"]}
            for m in self.layer:
                read, params = self.readers[m["name"]]
                value = read(ctx, params)
                if value is not None:
                    out["metrics"][m["name"]] = {"value": float(value),
                                                 "unit": m["unit"]}
        else:
            for m in self.e2e:
                value = END_TO_END[m["name"]](ctx)
                if value is None:
                    self.checks.fail(f"{m['name']}: nothing completed")
                    value = 0.0
                out["metrics"][m["name"]] = {"value": float(value),
                                             "unit": m["unit"]}
        for name, (value, limit) in self.checks.worst.items():
            log(f"worst {name} = {value:.6g} (limit {limit:.6g})")
        out["correct"] = self.checks.bad == 0
        if self.dry:
            log("CPU DRY RUN: control flow only; what it timed is no device "
                f"number and goes on no result line: {out['metrics']}")
            out["metrics"] = {}
            out["cpu_dry_run"] = True
        return out

    def reduce_trace(self) -> dict:
        """The capture, reduced in a child process: reading it imports JAX,
        and this process stays off JAX."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
             self.trace_dir], capture_output=True, text=True, env=env,
            timeout=240)
        if r.returncode != 0:
            raise BenchFailure("trace reduction failed:\n" + r.stderr[-2000:])
        red = json.loads(r.stdout.strip().splitlines()[-1])
        log(f"trace: window {red['window_s']:.3f}s, device busy "
            f"{red['busy_s'] * 1e3:.3f}ms on {red['devices_busy']} of "
            f"{red['devices_traced']} device plane(s); launches "
            f"{red['launches']}")
        if self.args.keep_trace:
            shutil.copytree(self.trace_dir, self.args.keep_trace,
                            dirs_exist_ok=True)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return red

    def traced_work(self, red: dict) -> dict:
        """How much of the traffic's work the capture holds.  Where the
        traffic file names the program each request launches a known number
        of times, the launches in the capture count the requests; else each
        request of the traced phase counts by the share of its time that
        the capture covers (the tracer stretches requests, so this is the
        coarser count)."""
        work = self.traffic.get("device_work", {})
        ok = [r for r in self.phase.results if r.ok and r.done > r.sent]
        t0, t1 = self.trace_at, self.trace_at + red["window_s"]
        n = sum(max(0.0, min(r.done, t1) - max(r.sent, t0))
                / (r.done - r.sent) for r in ok)
        if work.get("launch_program") in red["launches"]:
            n = red["launches"][work["launch_program"]] \
                / float(work["launches_per_request"])
        reqs = [self.phase.requests[r.index] for r in ok] \
            or self.phase.requests[:1]
        per = max(1, len(reqs))
        return {"requests": n, "needs": work.get("needs"),
                "points": n * sum(q.units for q in reqs) / per,
                "groups": n * sum(q.stmt.get("groups", 0) for q in reqs) / per}


def _rate(ctx):
    return ctx["units"] / ctx["window_s"] if ctx["units"] else None


# What each end-to-end metric is, by name: taken by this process's clock
# over all the work and all the time of the window.
END_TO_END = {
    "scan_points_per_s": _rate,
    "ingest_rows_per_s": _rate,
    "query_p50_ms": lambda ctx: ctx["client"]["p50_ms"],
    "query_p95_ms": lambda ctx: ctx["client"]["p95_ms"],
    "setup_s": lambda ctx: ctx["setup_s"],
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the profiler capture there, to look at by hand")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU: control flow only")
    args = ap.parse_args()
    cell = None
    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        if args.seconds is None:
            args.seconds = 4.0 if args.cpu_dry_run else bench["run_seconds"]
        cell = Cell(args, bench)
        out = cell.run()
    except (BenchFailure, metrics.MetricError, OSError, KeyError,
            ValueError) as e:
        print(f"benchmark FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        if cell is not None and cell.srv is not None:
            cell.srv.stop()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

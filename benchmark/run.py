#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <name> --cpu-dry-run    # control flow only

This process stays off JAX.  It builds the native libraries, starts ONE
server through its CLI entry point with the server's defaults, exits
non-zero unless the server reports platform `tpu` with as many chips as the
cell asks for, makes the data from --seed, loads it, quiesces the server,
warms up until no program is built and the planner stands still, pins the
planner's model there (`/debug/ctrl?mod=offload&freeze=1`: the route is
the one the server chose; no OGT_* variable is set and nothing is forced),
measures for --seconds, verifies answers against the plain reference, stops
the server and prints one JSON line.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own under benchmark/, found by the name in BENCHMARK.json.

A run ends in a result line that means what it says, or exits non-zero
having printed why (`benchmark FAILED: ...`), and in both cases no process
of its own outlives it (PR 41): a warm-up that did not stand still, a route
the cell was not built to drive, a capture that holds no device operation
are reasons, not results; SIGTERM, SIGHUP and SIGINT end the run through
the same clean-up, and a SIGKILL takes the server with it
(`harness/server.py`)."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()      # set-up counts from here

import argparse                   # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import resource                   # noqa: E402
import shutil                     # noqa: E402
import signal                     # noqa: E402
import statistics                 # noqa: E402
import subprocess                 # noqa: E402
import sys                        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import load_module, metrics, peaks, traffic  # noqa: E402
from harness.metrics import counter                       # noqa: E402
from harness.oracle import Mismatch, read_count            # noqa: E402
from harness.server import (STOP_ENDED_S, STOP_S,          # noqa: E402
                            BenchFailure, Client, Server,
                            build_native, die_with_parent)

WORK = os.path.join(ROOT, ".bench_work")
MERGE_COUNTERS = ["compaction/leveled_merges", "compaction/out_of_order_merges",
                  "compaction/full_merges", "compact/offlock_merges"]
ENDINGS = (signal.SIGTERM, signal.SIGHUP, signal.SIGINT)
# A capture that `trace.requests` lengthens (readings: PERF.md section 6,
# my chip runs, PR 41).  A request of 3-25 s took 0.98-1.09 times its
# slowest steady warm repeat under the profiler, a phase's first the most:
# TRACE_STRETCH leaves room.  CAPTURE_MAX_S is the longest capture a stored
# cell has (the load cell's); what a longer one would cost is a run's 360 s,
# not the reduction (42 s of 67 PromQL requests: 6.5 MB, reduced in 3.8 s).
TRACE_STRETCH = 1.25
CAPTURE_MAX_S = 60.0
REDUCE_TIMEOUT_S = 240


class Ended(BenchFailure):
    """The run is being ended from outside."""

    def __init__(self, signum: int):
        super().__init__(f"ended from outside by {signal.Signals(signum).name}"
                         ": no result; the server is stopped within seconds")
        self.signum = signum


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
        self.loaded = 0                 # rows the set-up load had acknowledged
        self.steady_s: list[float] = []  # the warm repeats that built nothing
        self.pinned: dict[str, dict] = {}  # planner kernel -> {geometry: route}
        self.capture_s = float(self.traffic["trace"]["seconds"])
        self.ingest = traffic.Ingest(None, None)   # set_up's, with the data
        self.ctx: dict = {}             # what the metrics read, for the tools

    # -- set-up ---------------------------------------------------------------

    def reference(self):
        mod = load_module(os.path.join(HERE, "configs", self.cfg["reference"]),
                          "reference")
        if self.traffic["kind"] == "lp_stream":
            # TSBS's loader: rows made as they are sent, none stored
            return mod.Reference(self.cfg, self.args.seed, stored=False)
        return mod.Reference(self.cfg, self.args.seed)

    def start(self) -> dict:
        Server.refuse_beside_live(WORK)
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        log(f"native libraries ready in {build_native(ROOT):.1f}s")
        self.srv = Server(ROOT, WORK, self.dry, self.cfg.get("server"))
        log(f"server pid {self.srv.proc.pid}, in a session of its own")
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
        self.loaded = acked
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
        and each kernel's last five planner decisions name one route.  Then
        the cell's `ingest`, if it has one, begins and goes on to the end of
        the run: the warm burst already overlaps it.  Last, the planner's
        model is pinned as it stands (`pin_planner`)."""
        srv, w = self.srv, self.traffic["warm"]
        client = Client(srv.port)
        kept = []

        def ask(req):
            req = traffic.bound(plan, req)
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
        moving: list = []
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
        self.steady_s = [r.done - r.sent for _, r in kept[-3:]]
        client.close()
        if self.ingest.spec is not None:
            onto_one_core()             # the sender's thread with the others
            self.ingest.start(srv.port)
            log(f"ingest began: {self.ingest.spec}")
        if plan.loop["kind"] == "open":
            # the window's requests overlap; so do the last of the warm-up's
            left = plan.warm_repeat[n:]
            burst = plan.part(left, [True] * len(left))
            traffic.run_open(burst, srv.port, float(w["burst_s"]))
            kept.extend((left[r.index], r) for r in burst.results)
            took = [1e3 * (r.done - r.due) for r in burst.results]
            log(f"warm burst of {w['burst_s']}s at the cell's rate: "
                f"{len(took)} requests, slowest {max(took):.0f} ms")
        self.pin_planner(moving)
        return kept

    def pin_planner(self, moving: list) -> None:
        """The warm-up stands still: the offload planner's model is pinned
        as it stands, so that one stalled sample in the window or the
        capture cannot move a kernel to another route for the rest of the
        run (PERF.md section 7, "The planner and a stall").  A frozen
        planner drops samples and stops exploring; it goes on writing its
        decision ring and its counters, which `host_route_share` and
        `route_flips_in_window` read.  The run stops here where a route was
        still moving at the warm-up's limit, or where the kernel the
        traffic file names (`device_work.planner_kernel`) is pinned to the
        host: the cell would time another regime under its name."""
        doc = self.srv.json("POST", "/debug/ctrl", mod="offload", freeze=1)
        chosen: dict[tuple, str] = {}
        for d in doc["decisions"]:                          # newest first
            chosen.setdefault((d["kernel"], d["geometry"]), d["route"])
        for m in doc["model"]:
            # sampled without a decision: a stage on its static route
            route = chosen.get((m["kernel"], m["geometry"]), "static")
            self.pinned.setdefault(m["kernel"], {})[m["geometry"]] = route
            log(f"planner pinned (frozen {doc['frozen']}): {m['kernel']} "
                f"{m['geometry']} -> {route} (its last decision, or its "
                "static route where it has made none); "
                + ", ".join(f"{r} {s['count']} sample(s) ewma {s['ewma_ms']} ms"
                            for r, s in m["routes"].items()))
        if not doc["model"]:
            log(f"planner pinned (frozen {doc['frozen']}): it has decided "
                "nothing yet, and will keep to its static routes")
        why = None
        if moving:
            why = (f"the warm-up reached its limit of "
                   f"{self.traffic['warm']['repeats_max']} repeats with the "
                   f"route of {moving} still moving")
        kernel = self.traffic.get("device_work", {}).get("planner_kernel")
        if kernel is not None and "host" in self.pinned.get(kernel, {}).values():
            why = (f"the planner routes {kernel}, the kernel this cell was "
                   "built to drive on the device, to the host "
                   f"({self.pinned[kernel]}; the log has its samples)")
        if why is not None:
            self.stop_here(why + ": the cell would time another regime under "
                           "its name")

    def stop_here(self, why: str) -> None:
        """The reason a run on the chip ends with.  The control-flow run,
        whose routes and capture are the CPU's, says it and goes on."""
        if not self.dry:
            raise BenchFailure(why)
        log(f"CPU DRY RUN: a run on the chip would stop here: {why}")

    def capture_seconds(self) -> tuple[float, float]:
        """(seconds the capture lasts, seconds the traced phase sends).
        The traffic file's `trace.seconds` and `trace.send_s`; where it
        carries `trace.requests`, the capture lasts at least that many times
        the slowest steady warm repeat times TRACE_STRETCH, so that it holds
        whole requests, and `send_s` follows it at the same distance."""
        t = self.traffic["trace"]
        seconds, send_s = float(t["seconds"]), float(t["send_s"])
        if "requests" not in t or not self.steady_s:
            return seconds, send_s
        need = float(t["requests"]) * max(self.steady_s) * TRACE_STRETCH
        if need > CAPTURE_MAX_S:
            raise BenchFailure(
                f"a capture of {t['requests']} whole requests needs "
                f"{need:.1f}s (slowest steady warm repeat "
                f"{max(self.steady_s):.2f}s x {TRACE_STRETCH}), over the "
                f"{CAPTURE_MAX_S:.0f}s a capture may last: the request is "
                "too long for this cell to be traced")
        longer = max(seconds, need)
        return longer, send_s + longer - seconds

    # -- the window -----------------------------------------------------------

    def snapshot(self, plan=None) -> tuple[dict, dict]:
        v, d = self.srv.vars(), self.srv.device()
        results = plan.results if plan else []
        done = [plan.requests[r.index] for r in results if r.ok]
        wrote = self.ingest.sent()      # since it began: a delta is a window's
        v["client"] = {
            "completed": len(done), "attempted": len(results),
            "units": sum(q.units for q in done),
            "windows": sum(q.stmt.get("windows", 0) for q in done),
            "rows_written": self.ingest.rows(wrote),
            "write_batches": len(wrote),
        }
        return v, d

    def capture(self, plan, ref) -> None:
        """The traced phase, after the window and its counters: start the
        server's profiler capture, go on sending the same traffic for the
        seconds the traffic file names, and wait for the capture to end.
        The write cell then reads its rows back inside the capture: its one
        device operation."""
        self.capture_s, send_s = self.capture_seconds()
        self.phase = traffic.rest(plan)
        t0 = time.perf_counter()
        self.srv.json("POST", "/debug/ctrl", mod="devobs", op="profile",
                      seconds=self.capture_s, dir=self.trace_dir)
        log(f"capture of {self.capture_s}s began "
            f"({time.perf_counter() - t0:.1f}s to start)")
        self.trace_at = t0
        traffic.run(self.phase, self.srv.port, send_s)
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
        """Every row acknowledged, by the set-up load, a stream or the
        ingest, is read back, and the durability ledger is clean."""
        srv = self.srv
        acked = self.loaded + sum(
            p.requests[r.index].units
            for p in (plan, self.phase, self.ingest) if p is not None
            for r in p.results
            if r.ok and p.requests[r.index].stmt["kind"] == "write")
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
        self.ingest = traffic.Ingest(self.traffic.get("ingest"), ref)
        self.srv.query(f"CREATE DATABASE {ref.db}")
        for q in self.cfg.get("setup_q", []):   # streams, policies: once
            res = self.srv.query(q, ref.db)["results"][0]
            if "error" in res:
                raise BenchFailure(f"setup_q {q!r}: {res['error']}")
        if self.traffic["kind"] != "lp_stream":
            self.load(ref)
        t = self.traffic["trace"]
        more = 0.0
        if a.trace:     # `trace.requests`: as long as a capture may get
            more = float(t["send_s"]) + ("requests" in t) * max(
                0.0, CAPTURE_MAX_S - float(t["seconds"]))
        plan = traffic.build(self.traffic, ref, a.seed, a.seconds + more)
        warm = self.warm_up(plan, ref)
        traffic.join_walk(plan, len(warm) - len(plan.warm_touch))
        onto_one_core()
        return device, ref, plan, warm

    def stop(self, ended: bool = False) -> None:
        """Both senders' ends: the ingest's thread, then the server.  A run
        that is being `ended` from outside waits for neither: the sender is
        a daemon thread, and the server gets seconds."""
        try:
            if ended:
                self.ingest.halt.set()
            else:
                self.ingest.stop()
        finally:
            if self.srv is not None:
                self.srv.stop(STOP_ENDED_S if ended else STOP_S)

    def run(self) -> dict:
        a = self.args
        device, ref, plan, warm = self.set_up()
        srv = self.srv
        writes = self.traffic["kind"] == "lp_stream"
        srv.json("POST", "/debug/ctrl", mod="devobs", op="mark_warm")
        vars0, dev0 = self.snapshot()
        cpu0 = time.process_time()
        setup_s = time.monotonic() - T_PROCESS
        log(f"set-up took {setup_s:.1f}s; the window of {a.seconds}s begins "
            + ("(a stream made from the seed as it is sent, "
               if plan.stream else
               f"({len(plan.requests)} requests made from the seed, ")
            + f"loop {plan.loop})")
        traffic.run(plan, srv.port, a.seconds)
        vars1, dev1 = self.snapshot(plan)
        gen_cpu = time.process_time() - cpu0
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
        wrote = self.ingest.sent(plan.t_start, plan.t_end)
        w_late = [1e3 * (r.sent - r.due) for r in wrote]
        if self.ingest.spec is not None:
            log(f"ingest in the window: {len(wrote)} batches, "
                f"{self.ingest.rows(wrote)} rows acknowledged, sent late ms "
                "p50/p95/max " + "/".join(
                    f"{metrics.percentile(w_late or [0.0], q):.1f}"
                    for q in (50, 95, 100))
                + f"; the generator used {100 * gen_cpu / window_s:.0f} % of "
                "its core")
            if gen_cpu > 0.85 * window_s:
                log("the generator itself is the bottleneck: its two senders "
                    "filled their one core, and what was sent late was late "
                    "by the generator, not by the server")
        if a.trace:
            self.capture(plan, ref)
        self.ingest.stop()
        if self.ingest.spec is not None or (writes and not a.trace):
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
                "write_late_p95_ms":
                    metrics.percentile(w_late, 95) if w_late else None,
            },
        }
        self.ctx = ctx
        device["memory_peak_bytes"] = int(mem or 0)
        out = {"correct": self.checks.bad == 0, "attempted": len(plan.results),
               "failed": failed, "metrics": {}, "device": device}
        if a.trace:
            ctx["trace"] = self.reduce_trace()
            self.held_by_capture(ctx["trace"])
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
        size = sum(os.path.getsize(os.path.join(d, f))
                   for d, _, fs in os.walk(self.trace_dir) for f in fs)
        t0 = time.monotonic()
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
             self.trace_dir], capture_output=True, text=True, env=env,
            timeout=REDUCE_TIMEOUT_S, preexec_fn=die_with_parent)
        if r.returncode != 0:
            raise BenchFailure("trace reduction failed:\n" + r.stderr[-2000:])
        red = json.loads(r.stdout.strip().splitlines()[-1])
        log(f"trace: window {red['window_s']:.3f}s, device busy "
            f"{red['busy_s'] * 1e3:.3f}ms on {red['devices_busy']} of "
            f"{red['devices_traced']} device plane(s); launches "
            f"{red['launches']}; {size / 1e6:.1f} MB of capture reduced in "
            f"{time.monotonic() - t0:.1f}s")
        if self.args.keep_trace:
            shutil.copytree(self.trace_dir, self.args.keep_trace,
                            dirs_exist_ok=True)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return red

    def held_by_capture(self, red: dict) -> None:
        """A traced run shows the device busy, or says why it cannot: a
        capture with no device operation is a reason, never a result line
        whose `busy_s` reads 0."""
        if red["busy_s"] > 0 and red["device_ops"]:
            return
        t0, t1 = self.trace_at, self.trace_at + red["window_s"]
        sent = self.phase.results if self.phase else []
        began = sum(t0 <= r.sent < t1 for r in sent)
        whole = sum(t0 <= r.sent and r.done <= t1 for r in sent)
        why = (f"the capture of {self.capture_s}s (traced {red['window_s']:.3f}s"
               f", {red['devices_traced']} device plane(s)) holds no device "
               f"operation: busy_s {red['busy_s']}; of the traced phase's "
               f"{len(sent)} request(s) {began} began and {whole} both began "
               "and ended inside it; median steady warm repeat "
               f"{statistics.median(self.steady_s or [0.0]):.3f}s beside "
               f"trace.seconds {self.traffic['trace']['seconds']}; the "
               f"planner's pinned routes: {self.pinned or 'none'}; launches "
               f"in the capture: {red['launches'] or 'none'}")
        if whole == 0 and sent:
            why += (".  No request lies whole inside the capture: give the "
                    "traffic file `trace.requests`")
        self.stop_here(why)

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

    def end(signum, frame):
        for s in ENDINGS:       # the clean-up below is not ended a second time
            signal.signal(s, signal.SIG_IGN)
        raise Ended(signum)

    for s in ENDINGS:
        signal.signal(s, end)
    cell, ended = None, False
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
        ended = isinstance(e, Ended)
        return 128 + e.signum if ended else 1
    finally:
        if cell is not None:
            cell.stop(ended)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The one general load generator.  A traffic mix is a data file
(`benchmark/traffic/<name>.json`): a statement `kind` with its parameters,
how statements are drawn from the seed, and the loop that sends them.
This module builds the request list before the window and, inside it, only
sends, times and keeps bodies.

Kinds: `influxql_template` (a GROUP BY time statement whose fields and
hosts the seed draws), `promql_range` (one range query, repeated: PromQL is
not result-cached) and `lp_stream` (the data set itself, in time order, in
batches, over /write).  Loops: `closed` (one client, the next request when
the last one is answered) and `open` (a fixed rate and a fixed realisation
of Poisson arrivals; latency counts from when a request was due, and how
late it left is kept)."""

from __future__ import annotations

import queue
import threading
import time
import urllib.parse
from dataclasses import dataclass, field

import numpy as np

from harness.server import Client

@dataclass
class Request:
    method: str
    path: str
    body: bytes | None
    stmt: dict                   # what the reference needs to check it
    units: int                   # points covered, or rows carried


@dataclass
class Result:
    index: int
    due: float
    sent: float = 0.0
    done: float = 0.0
    status: int = 0
    ok: bool = False             # status and shape, checked on arrival
    nbytes: int = 0
    body: bytes | None = None    # kept for the sample only


@dataclass
class Plan:
    warm_touch: list[Request]
    warm_repeat: list[Request]
    requests: list[Request]
    loop: dict
    keep: np.ndarray             # bool per request index: body kept
    results: list[Result] = field(default_factory=list)
    t_start: float = 0.0
    t_end: float = 0.0           # the last completion
    exhausted: bool = False      # ran out of requests before the deadline


# -- statements ----------------------------------------------------------------


def _influx_request(t: dict, ref, fields: list[int], hosts, over: dict) -> Request:
    p = {**t, **over}
    names = [ref.field_names[i] for i in fields]
    t0, t1 = ref.start_s, ref.start_s + int(ref.cfg["span_s"])
    where = ""
    if hosts is not None:
        where = "(" + " or ".join(f"hostname = 'host_{h}'" for h in hosts) \
            + ") AND "
    q = p["template"].format(
        aggs=", ".join(f"{p['agg']}({n})" for n in names), where=where,
        t0=t0, t1=t1, every_s=p["every_s"],
        by_host=", hostname" if p["group_by_host"] else "")
    stmt = {"kind": "influxql", "q": q, "agg": p["agg"], "fields": names,
            "every_s": int(p["every_s"]), "t0": t0, "t1": t1,
            "hosts": None if hosts is None else [int(h) for h in hosts],
            "group_by_host": bool(p["group_by_host"])}
    groups = (len(hosts) if hosts is not None else ref.hosts) \
        if p["group_by_host"] else 1
    stmt["marker"], stmt["marker_count"] = b'"columns"', groups
    stmt["windows"] = (t1 - t0) // stmt["every_s"]
    stmt["groups"] = stmt["windows"] * groups * len(names)
    path = "/query?" + urllib.parse.urlencode(
        {"q": q, "db": ref.db, "epoch": "ns"})
    return Request("POST", path, b"", stmt, ref.points(stmt))


def _influx_statements(t: dict, ref, rng, n_warm: int, n: int):
    nf, total = int(t["n_fields"]), len(ref.field_names)
    seen: set[tuple] = set()

    def draw() -> Request:
        for _ in range(1000):
            fields = (list(rng.permutation(total)[:nf])
                      if t["fields"] == "draw_ordered" else list(range(nf)))
            hosts = (None if t["hosts"] is None else sorted(
                rng.choice(ref.hosts, size=min(int(t["hosts"]), ref.hosts),
                           replace=False)))
            key = (tuple(fields), None if hosts is None else tuple(hosts))
            if key not in seen:
                seen.add(key)
                return _influx_request(t, ref, fields, hosts, {})
        raise ValueError("the traffic file cannot draw enough distinct "
                         "statements for this window")

    touch = []
    for over in t["warm"]["touch"]:
        over = dict(over)
        fields = over.pop("fields")
        hosts = over.pop("hosts", None)
        if not over:        # the cell's own shape: never asked again
            seen.add((tuple(fields), None if hosts is None else tuple(hosts)))
        touch.append(_influx_request(t, ref, fields, hosts, over))
    return touch, [draw() for _ in range(n_warm)], [draw() for _ in range(n)]


def _promql_statements(t: dict, ref, n_warm: int, n: int):
    start = ref.start_s + int(t["range_s"])
    end = ref.start_s + int(ref.cfg["span_s"])
    stmt = {"kind": "promql", "q": t["query"], "range_s": int(t["range_s"]),
            "step_s": int(t["step_s"]), "start": start, "end": end,
            "marker": b'"metric"', "marker_count": ref.series}
    stmt["windows"] = (end - start) // stmt["step_s"] + 1
    stmt["groups"] = stmt["windows"] * ref.series
    path = "/api/v1/query_range?" + urllib.parse.urlencode(
        {"query": t["query"], "start": start, "end": end,
         "step": t["step_s"], "db": ref.db})
    req = Request("GET", path, None, stmt, ref.points(stmt))
    return [], [req] * n_warm, [req] * n


def sample_mask(verify: dict, rng, n: int) -> np.ndarray:
    """Which answers are kept for the oracle: `keep` of each `of_each`
    consecutive requests, drawn from the seed."""
    block, k = int(verify["of_each"]), int(verify["keep"])
    keep = np.zeros(n, bool)
    for lo in range(0, n, block):
        keep[lo + rng.permutation(min(block, n - lo))[:k]] = True
    return keep


def build(traffic: dict, ref, seed: int, seconds: float) -> Plan:
    """Everything the window will send, made from the seed."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    loop = traffic["loop"]
    kind = traffic["kind"]
    if kind == "lp_stream":
        cap = int(traffic["max_rows_per_s"] * seconds)
        reqs = [Request("POST", "/write?db=" + ref.db, body,
                        {"kind": "write"}, rows)
                for body, rows in ref.stream_requests(
                    int(traffic["batch_rows"]), cap)]
        return Plan([], [], reqs, loop, np.zeros(len(reqs), bool))
    n = int(np.ceil((loop.get("rate_qps") or traffic["max_qps"]) * seconds))
    n_warm = int(traffic["warm"]["repeats_max"])
    if kind == "influxql_template":
        touch, warm, reqs = _influx_statements(traffic, ref, rng, n_warm, n)
    elif kind == "promql_range":
        touch, warm, reqs = _promql_statements(traffic, ref, n_warm, n)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    return Plan(touch, warm, reqs, loop, sample_mask(traffic["verify"], rng, n))


def rest(plan: Plan) -> Plan:
    """The requests the window did not send, as a plan of their own (the
    traced phase that follows the window); none of its bodies is kept."""
    used = len(plan.results)
    left = plan.requests[used:]
    return Plan([], [], left, plan.loop, np.zeros(len(left), bool))


# -- sending -------------------------------------------------------------------


def quick_ok(req: Request, status: int, body: bytes) -> bool:
    """Status and shape, cheap enough for the loop: the right code, no
    error member, and as many series as the statement has groups."""
    if req.stmt["kind"] == "write":
        return status == 204
    if status != 200 or b'"error"' in body[:300]:
        return False
    return body.count(req.stmt["marker"]) == req.stmt["marker_count"]


def send(client: Client, req: Request, res: Result, keep: bool) -> None:
    res.sent = time.perf_counter()
    res.status, body = client.request(req.method, req.path, req.body)
    res.done = time.perf_counter()
    res.nbytes = len(body)
    res.ok = quick_ok(req, res.status, body)
    if keep or not res.ok:
        res.body = body


def run_closed(plan: Plan, port: int, seconds: float) -> None:
    """One client; the window closes with the answer to the last request
    sent before `seconds` were up."""
    client = Client(port)
    plan.t_start = now = time.perf_counter()
    deadline = now + seconds
    for i, req in enumerate(plan.requests):
        if now >= deadline:
            break
        res = Result(i, due=now)
        send(client, req, res, bool(plan.keep[i]))
        plan.results.append(res)
        now = res.done
    plan.t_end = now if plan.results else deadline
    plan.exhausted = now < deadline
    client.close()


def arrival_times(loop: dict, seconds: float) -> np.ndarray:
    """Arrivals at `rate_qps`: one fixed realisation of a Poisson process
    (exponential gaps from the traffic file's `arrival_seed`), scaled to
    fill the window exactly.  Every seed offers the same arrivals; the seed
    draws what is asked."""
    n = int(round(loop["rate_qps"] * seconds))
    gaps = np.random.default_rng(int(loop["arrival_seed"])).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    return np.concatenate(([0.0], np.cumsum(gaps)[:-1]))


def run_open(plan: Plan, port: int, seconds: float) -> None:
    due = arrival_times(plan.loop, seconds)
    todo: queue.Queue = queue.Queue()
    plan.results = [Result(i, due=0.0) for i in range(len(due))]

    def worker() -> None:
        client = Client(port)
        while True:
            i = todo.get()
            if i is None:
                break
            send(client, plan.requests[i], plan.results[i], bool(plan.keep[i]))
        client.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(plan.loop["workers"]))]
    for th in threads:
        th.start()
    plan.t_start = time.perf_counter()
    for i, at in enumerate(due):
        plan.results[i].due = plan.t_start + at
        delay = plan.results[i].due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        todo.put(i)
    for _ in threads:
        todo.put(None)
    for th in threads:
        th.join(timeout=600)
        if th.is_alive():
            raise RuntimeError("a generator worker did not finish")
    plan.t_end = max(plan.t_start + seconds,
                     max((r.done for r in plan.results), default=0.0))


def run(plan: Plan, port: int, seconds: float) -> None:
    if plan.loop["kind"] == "closed":
        run_closed(plan, port, seconds)
    elif plan.loop["kind"] == "open":
        run_open(plan, port, seconds)
    else:
        raise ValueError(f"unknown loop kind {plan.loop['kind']!r}")

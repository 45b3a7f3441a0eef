"""The one general load generator.  A traffic mix is a data file
(`benchmark/traffic/<name>.json`): a statement `kind` with its parameters,
how statements are drawn from the seed, and the loop that sends them.
This module builds the request list before the window and, inside it, only
sends, times and keeps bodies; an `lp_stream` makes each batch while the
server works on the one before it, and keeps no body once it is sent.

Kinds: `influxql_template` (a GROUP BY time statement whose fields and
hosts the seed draws; `range_s` and `range_walk` set the part of the span a
statement covers and how it moves from statement to statement),
`promql_range` (one range query, repeated: PromQL is not result-cached) and
`lp_stream` (TSBS's loader: the deployment's rows in time order, in
batches, over /write, without end).  Loops: `closed` (one client, the next
request when the last one is answered) and `open` (a fixed rate and a fixed
realisation of Poisson arrivals; latency counts from when a request was
due, and how late it left is kept)."""

from __future__ import annotations

import itertools
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


class Stream:
    """The requests of an `lp_stream`, one made ahead of the sender: each
    while the server works on the one before it, on the sender's own core
    (`Client.meanwhile`), so there is no thread and nothing shared.  It
    holds one body, whatever the server's rate and however long the window."""

    def __init__(self, requests):
        self.requests = requests         # an iterator, as a rule endless
        self.made: Request | None = None
        self.make()

    def make(self) -> None:
        if self.made is None:    # and stays None once a finite stream ends
            self.made = next(self.requests, None)

    def next(self) -> Request | None:
        """The request to send now; made here where no `meanwhile` ran."""
        self.make()
        req, self.made = self.made, None
        return req


@dataclass
class Plan:
    warm_touch: list[Request]
    warm_repeat: list[Request]
    requests: list[Request]      # of a stream: those sent, without bodies
    loop: dict
    keep: np.ndarray             # bool per request index: body kept
    results: list[Result] = field(default_factory=list)
    t_start: float = 0.0
    t_end: float = 0.0           # the last completion
    exhausted: bool = False      # ran out of requests before the deadline
    stream: Stream | None = None
    cycle: int = 1               # offsets a range walk goes round


# -- statements ----------------------------------------------------------------


def _influx_request(t: dict, ref, fields: list[int], hosts, over: dict,
                    t0: int, t1: int) -> Request:
    p = {**t, **over}
    names = [ref.field_names[i] for i in fields]
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


def statement_ranges(t: dict, cfg: dict) -> tuple[int, int]:
    """(seconds a statement covers, offsets its range goes round) of an
    `influxql_template`.  `range_s` absent: the whole span, one offset.
    `range_walk` `cycle`: with k = span_s // range_s, the n-th statement
    made (touches, warm repeats and the window's in one sequence) covers
    [start_s + (n mod k) * range_s, + range_s), so no statement meets a
    range again sooner than k statements later.  A range that does not fit
    the span or the statements' windows is an error, not a rounding."""
    span = int(cfg["span_s"])
    walk = t.get("range_walk")
    if walk not in (None, "cycle"):
        raise ValueError(f"unknown range_walk {walk!r}")
    if "range_s" not in t:
        if walk is not None:
            raise ValueError("range_walk without range_s")
        return span, 1
    range_s = int(t["range_s"])
    every = {int(t["every_s"])} | {int(o["every_s"]) for o in
                                   t["warm"]["touch"] if "every_s" in o}
    if range_s <= 0 or span % range_s or any(range_s % e for e in every):
        raise ValueError(
            f"range_s {t['range_s']} has to divide span_s {span} and be a "
            f"multiple of every_s {sorted(every)}")
    return range_s, span // range_s if walk else 1


def _influx_statements(t: dict, ref, rng, n_warm: int, n: int):
    nf, total = int(t["n_fields"]), len(ref.field_names)
    range_s, k = statement_ranges(t, ref.cfg)
    seen: set[tuple] = set()
    made = 0                     # statements so far, in the order sent

    def request(fields, hosts, over) -> Request:
        nonlocal made
        t0 = ref.start_s + made % k * range_s
        made += 1
        return _influx_request(t, ref, fields, hosts, over, t0, t0 + range_s)

    def key(fields, hosts) -> tuple:
        return (tuple(fields), None if hosts is None else tuple(hosts),
                made % k)

    def draw() -> Request:
        for _ in range(1000):
            fields = (list(rng.permutation(total)[:nf])
                      if t["fields"] == "draw_ordered" else list(range(nf)))
            hosts = (None if t["hosts"] is None else sorted(
                rng.choice(ref.hosts, size=min(int(t["hosts"]), ref.hosts),
                           replace=False)))
            if key(fields, hosts) not in seen:
                seen.add(key(fields, hosts))
                return request(fields, hosts, {})
        raise ValueError("the traffic file cannot draw enough distinct "
                         "statements for this window")

    touch = []
    for over in t["warm"]["touch"]:
        over = dict(over)
        fields = over.pop("fields")
        hosts = over.pop("hosts", None)
        if not over:        # the cell's own shape: never asked again
            seen.add(key(fields, hosts))
        touch.append(request(fields, hosts, over))
    return (touch, [draw() for _ in range(n_warm)],
            [draw() for _ in range(n)], k)


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


def check(traffic: dict, cfg: dict) -> None:
    """What `build` would refuse, found before a server is started."""
    if traffic["kind"] == "influxql_template":
        statement_ranges(traffic, cfg)
    elif traffic["kind"] not in ("promql_range", "lp_stream"):
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")


def build(traffic: dict, ref, seed: int, seconds: float) -> Plan:
    """Everything the window will send, made from the seed."""
    check(traffic, ref.cfg)
    rng = np.random.default_rng([seed, 0x7AFF1C])
    loop = traffic["loop"]
    kind = traffic["kind"]
    if kind == "lp_stream":
        def writes():
            for body, rows in ref.stream_requests(int(traffic["batch_rows"])):
                yield Request("POST", "/write?db=" + ref.db, body,
                              {"kind": "write"}, rows)
        # the warm-up's batches are the stream's first, made apart
        warm = list(itertools.islice(writes(), int(traffic["warm"]["batches"])))
        return Plan(warm, [], [], loop, np.zeros(0, bool),
                    stream=Stream(writes()))
    n = int(np.ceil((loop.get("rate_qps") or traffic["max_qps"]) * seconds))
    n_warm = int(traffic["warm"]["repeats_max"])
    if kind == "influxql_template":
        touch, warm, reqs, k = _influx_statements(traffic, ref, rng, n_warm, n)
    else:
        touch, warm, reqs = _promql_statements(traffic, ref, n_warm, n)
        k = 1
    return Plan(touch, warm, reqs, loop, sample_mask(traffic["verify"], rng, n),
                cycle=k)


def rest(plan: Plan) -> Plan:
    """The requests the window did not send, as a plan of their own (the
    traced phase that follows the window); none of its bodies is kept.  A
    stream goes on where the window left it."""
    if plan.stream is not None:
        return Plan([], [], [], plan.loop, np.zeros(0, bool),
                    stream=plan.stream)
    used = len(plan.results)
    left = plan.requests[used:]
    return Plan([], [], left, plan.loop, np.zeros(len(left), bool))


def join_walk(plan: Plan, warm_sent: int) -> None:
    """A range walk goes on across the seam between warm-up and window.
    The window's statements were made after all `warm_repeat`; where the
    warm-up sent only `warm_sent` of those, drop the window's first few, so
    that its first covers the range after the last one sent."""
    skip = (warm_sent - len(plan.warm_repeat)) % plan.cycle
    del plan.requests[:skip]
    plan.keep = plan.keep[skip:]


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
    if plan.stream is not None:
        client.meanwhile = plan.stream.make
    i = len(plan.results)
    while now < deadline:
        if plan.stream is None:
            req = plan.requests[i] if i < len(plan.requests) else None
        else:
            req = plan.stream.next()
        if req is None:
            plan.exhausted = True
            break
        res = Result(i, due=now)
        send(client, req, res, i < len(plan.keep) and bool(plan.keep[i]))
        if plan.stream is not None:      # sent: its units and stmt stay
            req.body = None
            plan.requests.append(req)
        plan.results.append(res)
        now = res.done
        i += 1
    plan.t_end = now if plan.results else deadline
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

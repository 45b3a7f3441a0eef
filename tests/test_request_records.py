"""A request's own account (utils/tracing.py): the root span's stage map,
the per-route tail of the slowest requests since the mark, the pulse and
its stall records, the two halves of a fetch, and the slow log taking the
same record for a /write and a PromQL query."""

import gc
import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from opengemini_tpu.server.http import HttpService
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import devobs, slowlog, tracing
from opengemini_tpu.utils.querytracker import GLOBAL as TRACKER
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 10**9
BASE = 1_700_000_000
MS = 1_000_000


@pytest.fixture(autouse=True)
def _state():
    prev_slow = slowlog.GLOBAL.threshold_ms
    tracing.mark()
    yield
    slowlog.GLOBAL.configure(slow_ms=prev_slow)
    slowlog.GLOBAL.clear()
    tracing.mark()


def _delta(group: str, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in STATS.counters(group).items()
            if v != before.get(k, 0)}


def _one(route: str) -> dict:
    """The one record the route's tail holds."""
    [rec] = tracing.tail_doc()[route]
    return rec


# -- the stage map -------------------------------------------------------------


def test_a_roots_stage_map_is_the_sum_of_the_spans_closed_under_it():
    q0 = STATS.counters("query_stages")
    with tracing.request("t_sum"):
        for _ in range(3):
            with tracing.span("t_a"):
                with tracing.span("t_b"):
                    time.sleep(0.002)
        tracing.record_stage("t_noted", 7 * MS)
    d = _delta("query_stages", q0)
    stages = _one("t_sum")["stages"]
    assert set(stages) == {"t_a", "t_b", "t_noted"}
    for name, (ns, self_ns, count) in stages.items():
        assert ns == d[name + "_ns"]
        assert self_ns == d[name + "_self_ns"]
        assert count == d[name + "_count"]
    assert stages["t_a"][2] == stages["t_b"][2] == 3


def test_pool_threads_count_through_handoff_and_adopt():
    q0 = STATS.counters("query_stages")
    strangers = []

    def worker(handed):
        with tracing.adopt(handed):
            with tracing.span("t_pool"):
                time.sleep(0.002)
        with tracing.span("t_stranger"):      # after the block: nobody's
            strangers.append(1)

    with tracing.request("t_pooled") as root:
        with tracing.span("t_dispatch"):
            handed = tracing.handoff()
            threads = [threading.Thread(target=worker, args=(handed,))
                       for _ in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=10)
        # the owner's map is its own thread's; the helpers' lie beside it
        assert set(root.acct.stages) == {"t_dispatch"}
        assert set(root.acct.pool.stages) == {"t_pool"}
    d = _delta("query_stages", q0)
    stages = _one("t_pooled")["stages"]
    assert len(strangers) == 4 and "t_stranger" not in stages
    assert stages["t_pool"] == [d["t_pool_ns"], d["t_pool_self_ns"], 4]
    # a helper's span has no parent frame: it takes nothing from the
    # dispatching span's self time
    assert stages["t_dispatch"][1] == stages["t_dispatch"][0]


def test_self_times_and_the_roots_own_add_up_to_its_time():
    with tracing.request("t_adds"):
        with tracing.span("t_outer"):
            with tracing.span("t_inner"):
                time.sleep(0.003)
            tracing.record_stage("t_noted", 1 * MS)
        with tracing.span("t_second"):
            time.sleep(0.001)
        time.sleep(0.002)
    rec = _one("t_adds")
    assert sum(m[1] for m in rec["stages"].values()) + rec["self_ns"] \
        == rec["ns"]
    assert rec["self_ns"] >= 2 * MS
    assert rec["offcpu_ns"] == rec["ns"] - rec["cpu_ns"] >= 5 * MS
    assert rec["qids"] == [] and rec["launches"] == 0
    assert rec["route"] == "t_adds" and rec["t0_ns"] > 0


def test_a_statement_with_no_root_above_it_keeps_a_map_of_its_own():
    qid = TRACKER.register("t", "db")
    try:
        with tracing.span("t_bare"):
            pass
        inner = TRACKER.register("t inner", "db")   # a rule's expression
        with tracing.span("t_bare"):
            pass
        TRACKER.unregister(inner)
        with tracing.span("t_bare"):                # still the outer's
            pass
        assert TRACKER.stages_of(qid).keys() == {"t_bare"}
        assert tracing.current_record()["stages"]["t_bare"][2] == 3
    finally:
        TRACKER.unregister(qid)
    assert tracing.current_record() is None
    with tracing.span("t_bare"):                    # nobody's now
        pass
    assert tracing.handoff() is None


# -- the tail ------------------------------------------------------------------


def _requests(route: str, sleeps_ms) -> None:
    for ms in sleeps_ms:
        with tracing.request(route):
            time.sleep(ms / 1e3)


def test_the_tail_holds_the_sixteen_slowest_since_the_mark():
    sleeps = [1 + (7 * i) % 24 for i in range(24)]      # 1..24 ms, shuffled
    _requests("t_tail", sleeps)
    recs = tracing.tail_doc()["t_tail"]
    assert len(recs) == 16
    took = [r["ns"] for r in recs]
    assert took == sorted(took, reverse=True)           # slowest first
    # the eight fastest (1..8 ms) are the ones that are not there
    assert min(took) >= 9 * MS * 0.95


def test_a_request_that_does_not_enter_the_tail_builds_no_record(monkeypatch):
    _requests("t_lazy", [6] * 16)
    built = []
    real = tracing.request.record
    monkeypatch.setattr(tracing.request, "record",
                        lambda self, *a: built.append(1) or real(self, *a))
    _requests("t_lazy", [0] * 5)
    assert built == []
    _requests("t_lazy", [12])
    assert built == [1]
    assert len(tracing.tail_doc()["t_lazy"]) == 16


def test_the_tail_forgets_at_mark_warm():
    _requests("t_forget", [2, 2, 2])
    assert len(tracing.tail_doc()["t_forget"]) == 3
    devobs.mark_warm()
    try:
        assert "t_forget" not in tracing.tail_doc()
        _requests("t_forget", [1])
        assert len(tracing.tail_doc()["t_forget"]) == 1
    finally:
        devobs.clear_warm()


def test_seq_separates_two_windows():
    _requests("t_seq", [1, 1, 1])
    count0 = STATS.counters("http")["t_seq_count"]      # a window's vars0
    _requests("t_seq", [1, 1])
    recs = tracing.tail_doc()["t_seq"]
    assert len(recs) == 5
    inside = [r for r in recs if r["seq"] > count0]
    assert len(inside) == 2
    assert sorted(r["seq"] for r in recs) == list(range(count0 - 2,
                                                        count0 + 3))


# -- the pulse -----------------------------------------------------------------


def _gil_holder(seconds: float):
    """One C call that never lets go of the interpreter, sized here by
    the fastest of three tries (a loaded machine stretches the others)."""
    n, took = 2_000_000, []
    for _ in range(3):
        t0 = time.perf_counter()
        sum(range(n))
        took.append(time.perf_counter() - t0)
    n = int(seconds * n / min(took))
    return lambda: sum(range(n))


def _across(route: str, work) -> tuple[dict, list[dict]]:
    """(the record of a request open across `work` on another thread,
    the stall records it left)."""
    tracing.watch_pulse()
    time.sleep(0.1)                     # the pulse is on its schedule
    began = time.perf_counter_ns()
    tracing.mark()
    def linger():
        work()
        time.sleep(0.1)                 # still there when the beat lands

    th = threading.Thread(target=linger, name="t_worker_thread")
    with tracing.request(route):
        with tracing.span("t_waiting"):
            th.start()
            th.join(timeout=30)
            time.sleep(0.03)            # still at work when the beat lands
    time.sleep(0.06)
    return _one(route), [s for s in tracing.stalls_doc()
                         if s["t_ns"] >= began]


def test_a_thread_that_holds_the_gil_leaves_a_late_beat():
    for attempt in range(3):            # a loaded machine blurs one try
        rec, stalls = _across("t_gil", _gil_holder(0.5))
        stall = max(stalls, key=lambda s: s["late_ns"], default=None)
        if stall and rec["stalled_ns"] >= 200 * MS <= stall["late_ns"] \
                and stall["cpu_ns"] >= 0.5 * stall["late_ns"]:
            break
    assert rec["stalled_ns"] >= 200 * MS
    assert stall["late_ns"] >= 200 * MS
    # a thread of ours ran through it: CPU grew about as much
    assert 0.5 * stall["late_ns"] <= stall["cpu_ns"] <= 3 * stall["late_ns"]
    assert stall["roots_open"] >= 1 and "t_waiting" in stall["standing"]
    # ... and it says which: the one whose CPU time grew most
    assert stall["busiest"] == "t_worker_thread"
    assert stall["busiest_cpu_ns"] >= 0.5 * stall["late_ns"]
    assert stall["t_ns"] >= rec["t0_ns"]
    gauges = STATS.snapshot()["runtime"]
    assert gauges["pulse_late_max_ns"] >= 200 * MS
    assert gauges["pulse_late_ns"] >= rec["stalled_ns"]
    assert gauges["pulse_beats"] > 0


def test_a_thread_asleep_leaves_no_late_beat():
    for attempt in range(3):
        rec, stalls = _across("t_asleep", lambda: time.sleep(0.3))
        if not stalls and rec["stalled_ns"] == 0:
            break
    assert stalls == [] and rec["stalled_ns"] == 0
    assert rec["offcpu_ns"] >= 250 * MS     # it waited, off the CPU
    assert STATS.snapshot()["runtime"]["pulse_late_max_ns"] < 100 * MS


def test_the_runtime_gauges_carry_the_hosts_counters():
    tracing.watch_pulse()
    g = STATS.snapshot()["runtime"]
    for key in ("majflt", "nivcsw", "pulse_beats", "pulse_late_ns",
                "pulse_late_max_ns", "gc_pause_ns"):
        assert isinstance(g[key], int) and g[key] >= 0, key
    if tracing._HAS_SCHEDSTAT:
        ran, delay = tracing._schedstat()
        assert ran > 0 and 0 <= g["run_delay_ns"] <= delay + 10**9
    else:                               # a kernel that keeps none (gVisor)
        assert tracing._schedstat() is None and "run_delay_ns" not in g


def test_a_collection_under_a_root_is_its_pause():
    tracing.watch_gc()
    junk = [[i] for i in range(50_000)]
    gc.disable()                        # no collection but the one asked for
    try:
        with tracing.request("t_gc"):
            del junk
            gc.collect()
    finally:
        gc.enable()
    rec = _one("t_gc")
    assert rec["gc_gen2"] == 1
    assert 0 < rec["gc_ns"] <= rec["ns"]
    with tracing.request("t_nogc"):
        pass
    assert _one("t_nogc")["gc_gen2"] == 0


def test_a_collection_still_open_when_the_beat_lands_is_in_the_record():
    """A collection holds the GIL to its end, and the pulse is let in at
    the first bytecode of the collector's `stop` callback: before the
    pause has been added up.  The record counts the open one itself."""
    now = time.perf_counter_ns()
    before = tracing._sample()
    tracing._gc["t0"] = now - 150 * MS          # began 150 ms ago, not closed
    try:
        tracing._beat(now - 140 * MS, before, tracing._Baseline())
    finally:
        tracing._gc["t0"] = 0
    stall = tracing.stalls_doc()[-1]
    assert 140 * MS <= stall["late_ns"] < 200 * MS
    assert 150 * MS <= stall["gc_ns"] < 210 * MS
    tracing._beat(time.perf_counter_ns() - 140 * MS, tracing._sample(),
                  tracing._Baseline())           # none open: what was summed
    assert tracing.stalls_doc()[-1]["gc_ns"] < 50 * MS


# -- the two halves of a fetch ---------------------------------------------------


def test_a_fetch_says_which_half_it_waited_in():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2, (x + 1).sum()))
    f(jnp.ones((64, 64)))                           # compiled before the root
    q0 = STATS.counters("query_stages")
    with tracing.request("t_fetch"):
        out = f(jnp.ones((64, 64)))
        got = devobs.fetch_tree(out)
        one = devobs.fetch_np(out[0])
        assert devobs.fetch_np(np.ones(3)).shape == (3,)    # no span
    assert isinstance(got[0], np.ndarray) and one.shape == (64, 64)
    rec = _one("t_fetch")
    st = rec["stages"]
    assert st["device_fetch"][2] == st["device_wait"][2] \
        == st["device_copy"][2] == 2                # one pair a fetch
    assert st["device_wait"][0] + st["device_copy"][0] <= st["device_fetch"][0]
    assert st["device_fetch"][1] == st["device_fetch"][0] \
        - st["device_wait"][0] - st["device_copy"][0] >= 0
    assert rec["d2h_bytes"] == got[0].nbytes + got[1].nbytes + one.nbytes
    d = _delta("query_stages", q0)
    assert d["device_wait_count"] == d["device_copy_count"] == 2


# -- the served documents --------------------------------------------------------


@pytest.fixture
def server(tmp_path):
    engine = Engine(str(tmp_path / "data"))
    engine.create_database("db")
    engine.create_database("prom")
    svc = HttpService(engine, "127.0.0.1", 0)
    svc.start()
    yield svc
    svc.stop()
    engine.close()


def _http(port, method, path, body=None, **params):
    url = f"http://127.0.0.1:{port}{path}?" + urllib.parse.urlencode(params)
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, r.read()


def _slow_records(port, kind: str) -> list[dict]:
    """A root closes just after its response is sent: poll for it."""
    deadline = time.monotonic() + 10
    while True:
        doc = json.loads(_http(port, "GET", "/debug/slow")[1])
        recs = [r for r in doc["records"] if r.get("kind") == kind]
        if recs or time.monotonic() > deadline:
            return recs
        time.sleep(0.02)


def test_a_slow_write_is_in_the_slow_log_with_its_stages(server):
    slowlog.GLOBAL.configure(slow_ms=0.0)
    lines = "\n".join(f"cpu,host=h{i} v={i} {(BASE + i) * NS}"
                      for i in range(50)).encode()
    assert _http(server.port, "POST", "/write", lines, db="db")[0] == 204
    [rec] = _slow_records(server.port, "write")
    assert rec["statement"] == "http_write" and rec["database"] == "db"
    assert {"lp_parse", "memtable_apply", "send"} <= set(rec["stages_ms"])
    req = rec["request"]
    assert req["route"] == "write" and req["qids"] == []
    assert req["ns"] == pytest.approx(rec["duration_ms"] * 1e6, rel=1e-3)
    # the same record the tail holds
    assert req in tracing.tail_doc()["write"]


def test_a_slow_promql_query_is_in_the_slow_log_with_its_stages(server):
    lines = "\n".join(f"m,job=a value={i} {(BASE + 15 * i) * NS}"
                      for i in range(40)).encode()
    assert _http(server.port, "POST", "/write", lines, db="prom")[0] == 204
    slowlog.GLOBAL.configure(slow_ms=0.0)
    status, body = _http(server.port, "GET", "/api/v1/query_range",
                         query="rate(m[2m])", start=BASE + 120,
                         end=BASE + 540, step=60)
    assert status == 200 and json.loads(body)["status"] == "success"
    [rec] = _slow_records(server.port, "promql")
    assert rec["statement"] == "rate(m[2m])"
    assert "prom_collect" in rec["stages_ms"]
    # taken inside the root: the request so far, under the statement's qid
    assert rec["request"]["route"] == "prom"
    assert rec["request"]["qids"] == [rec["qid"]]
    # once noted, by the statement: the root adds no second record
    time.sleep(0.1)
    doc = json.loads(_http(server.port, "GET", "/debug/slow")[1])
    assert [r.get("kind") for r in doc["records"]].count("prom") == 0
    assert doc["tail"]["prom"][0]["stages"]["prom_collect"][2] == 1


def test_the_tail_and_the_stalls_stay_under_64_kb_with_every_ring_full(server):
    names = ["select: cpu_usage_of_hosts"] + [
        f"stage_of_a_query_{i:02d}" for i in range(23)]
    others = [tracing.request("t_open") for _ in range(16)]
    for r in others:                    # sixteen requests a stall stands in
        r.__enter__()
        r._t0 -= 3_000_000_000          # ... open since before it began
    try:
        for route in ("query", "prom", "write"):
            for i in range(20):
                with tracing.request(route):
                    qid = TRACKER.register("q", "db")
                    for name in names:
                        tracing.record_stage(name, 1_234_567_890 + i)
                    tracing.note_d2h(7_864_320)
                    TRACKER.unregister(qid)
        for _ in range(20):
            tracing._beat(time.perf_counter_ns() - 2_000_000_000,
                          tracing._sample(), tracing._Baseline())
        doc = json.loads(_http(server.port, "GET", "/debug/vars")[1])
    finally:
        for r in reversed(others):
            r.__exit__(None, None, None)
    assert set(doc["tail"]) == {"query", "prom", "write"}
    assert {len(v) for v in doc["tail"].values()} == {16}
    assert len(doc["stalls"]) == 16
    assert all(len(s["standing"]) == 16 for s in doc["stalls"])
    rec = doc["tail"]["prom"][0]
    # the sixteen largest stages, and the other eight summed
    assert len(rec["stages"]) == 17 and rec["stages"]["other"][2] == 8
    assert (sum(m[0] for m in rec["stages"].values())
            - 24 * 1_234_567_890) % 24 == 0
    assert len(rec["qids"]) == 1 and rec["d2h_bytes"] == 7_864_320
    served = json.dumps({"tail": doc["tail"], "stalls": doc["stalls"]})
    assert len(served) < 64 * 1024, len(served)
    # /metrics walks numbers only and never meets them
    text = _http(server.port, "GET", "/metrics")[1].decode()
    assert "ogt_runtime_pulse_late_max_ns" in text
    assert "ogt_tail" not in text and "ogt_stalls" not in text


def test_an_operator_marks_through_ctrl(server):
    _requests("query", [1, 1])
    assert tracing.tail_doc()["query"]
    status, _ = _http(server.port, "POST", "/debug/ctrl", b"", mod="obs",
                      mark="1")
    assert status == 200
    assert tracing.tail_doc() == {}


# -- the capture ------------------------------------------------------------------


def test_a_capture_names_the_pulse_and_an_idle_connection(server, tmp_path):
    import glob
    import http.client
    import os

    from jax.profiler import ProfileData

    logdir = str(tmp_path / "capture")
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    t_before = time.perf_counter_ns()
    devobs.start_profile(0.6, logdir=logdir)
    try:
        assert devobs.profile_status()["started_perf_ns"] >= t_before
        for _ in range(3):
            conn.request("GET", "/ping")
            conn.getresponse().read()
            time.sleep(0.1)             # the connection waits, annotated
    finally:
        while devobs.profile_status()["active"]:
            time.sleep(0.02)
        conn.close()
    last = devobs.profile_status()["last"]
    assert last["ok"] and t_before <= last["started_perf_ns"] \
        <= time.perf_counter_ns()
    [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True)
    events = [(e.name, e.duration_ns) for p in ProfileData.from_file(
        path).planes for ln in p.lines for e in ln.events
        if e.name.startswith("ogt:")]
    pulses = [d for n, d in events if n == "ogt:pulse"]
    idles = [d for n, d in events if n == "ogt:conn_idle"]
    assert len(pulses) >= 10 and max(pulses) < 0.2 * NS
    assert idles and max(idles) >= 0.05 * NS
    # no capture: no annotation, and nothing else either
    assert tracing.annotated("conn_idle") is None

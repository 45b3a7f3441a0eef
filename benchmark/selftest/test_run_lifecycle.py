"""A run of a cell ends in a result or in a stated reason, and never leaves
its server behind (PR 41).  On the CPU, at --cpu-dry-run's sizes:

- `run.py` ended from outside by SIGTERM and by SIGKILL, during the load and
  during the window, leaves no server; SIGTERM is a stated reason and an
  exit code of its own; a pid the work directory still names alive refuses
  the next start;
- the planner is pinned when the warm-up stands still: `/debug/device`
  reports `planner.frozen` true at `mark_warm` (false in the write cell,
  which plans nothing), and the replay of PERF.md section 7 — routes of 70
  and 180 ms, then one sample of 1.3 s — flips a live model for good and a
  pinned one not at all;
- a reduced trace with no device operation is a `BenchFailure` whose text
  names the cause; `trace.requests` lengthens a capture and its absence
  leaves the traffic file's seconds;
- a configuration's `server` sections reach `server.toml` after `[data]`
  and `[http]`, and without them the file is byte for byte what it was."""

import argparse
import os
import signal
import subprocess
import sys
import time

import pytest

import run as bench_run
from harness import metrics, traffic
from harness import server as harness_server
from harness.server import BenchFailure, Server, server_toml

from conftest import BENCH, ROOT

MARK = {"load": "data from seed", "window": "the window of"}


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _gone_within(pid: int, seconds: float) -> bool:
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        if not _alive(pid):
            return True
        time.sleep(0.1)
    return False


def cell(workload="prom_rate_range", dry=False, trace=1):
    args = argparse.Namespace(workload=workload, seed=1, seconds=2.0,
                              trace=trace, cpu_dry_run=dry, keep_trace=None)
    return bench_run.Cell(args, bench_run.load_json(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("phase", ["load", "window"])
@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGKILL],
                         ids=lambda s: s.name)
def test_a_run_ended_from_outside_leaves_no_server(sig, phase, tmp_path):
    out = tmp_path / "out"
    with open(out, "w") as f, open(tmp_path / "err", "w") as e:
        p = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             "prom_rate_range", "--cpu-dry-run", "--seconds", "6"],
            stdout=f, stderr=e, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        t0 = time.monotonic()
        while MARK[phase] not in out.read_text():
            assert p.poll() is None and time.monotonic() - t0 < 120, \
                out.read_text()[-1000:]
            time.sleep(0.05)
        with open(os.path.join(bench_run.WORK, "server.pid")) as f:
            pid = int(f.read())
        assert _alive(pid) and os.getsid(pid) == pid != os.getsid(p.pid)
        if phase == "window":
            time.sleep(1.0)
        os.kill(p.pid, sig)
        rc = p.wait(timeout=30)
        assert _gone_within(pid, 15.0), "the server outlived its run"
        if sig == signal.SIGTERM:
            assert rc == 128 + signal.SIGTERM
            assert "ended from outside by SIGTERM" in \
                (tmp_path / "err").read_text()
            assert not os.path.exists(
                os.path.join(bench_run.WORK, "server.pid"))
        else:
            assert rc == -signal.SIGKILL
        assert '"correct"' not in out.read_text()      # no result line
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()


def test_the_next_start_refuses_to_begin_beside_a_live_server(tmp_path):
    Server.refuse_beside_live(str(tmp_path))            # no file: nothing
    pidfile = tmp_path / harness_server.PID_FILE
    pidfile.write_text(f"{os.getpid()}\n")               # alive, no server
    Server.refuse_beside_live(str(tmp_path))
    p = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)",
                          "opengemini_tpu.server.app"])
    try:
        pidfile.write_text(f"{p.pid}\n")
        t0 = time.monotonic()       # until the child has become its command
        while not harness_server._is_server(p.pid):
            assert time.monotonic() - t0 < 10
            time.sleep(0.02)
        with pytest.raises(BenchFailure, match=f"pid {p.pid}.*still alive"):
            Server.refuse_beside_live(str(tmp_path))
    finally:
        p.kill()
        p.wait()
    Server.refuse_beside_live(str(tmp_path))            # gone: nothing


@pytest.mark.parametrize("workload, frozen", [("prom_rate_range", True),
                                              ("tsbs_load", False)])
def test_the_planner_is_pinned_at_mark_warm(workload, frozen, monkeypatch):
    seen = []
    real = Server.json

    def spy(self, method, path, **params):
        if params.get("op") == "mark_warm":
            seen.append(self.device()["planner"])
        return real(self, method, path, **params)

    monkeypatch.setattr(Server, "json", spy)
    c = cell(workload, dry=True, trace=0)
    try:
        out = c.run()
    finally:
        c.stop()
    assert out["correct"] is True and out["failed"] == 0
    assert [p["frozen"] for p in seen] == [frozen]
    if frozen:      # the model it pinned is the one the log names
        assert c.pinned == {m["kernel"]: {m["geometry"]: d["route"]}
                            for m in seen[0]["model"]
                            for d in seen[0]["decisions"][:1]}
        # frozen, it still counts its decisions and writes its ring
        ctx = c.ctx
        assert ctx["dev1"]["planner"]["frozen"] is True
        made = ctx["dev1"]["planner"]["counters"]["decisions_total"] \
            - ctx["dev0"]["planner"]["counters"]["decisions_total"]
        assert made == out["attempted"] > 0
        assert metrics.planner_ring(ctx, {"stat": "flips"}) == 0.0


@pytest.mark.parametrize("pinned", [False, True])
def test_a_stalled_sample_flips_a_live_model_and_not_a_pinned_one(pinned):
    """PERF.md section 7, "The planner and a stall", replayed on
    `offload.Planner` alone: the device route costs 70 ms, the host route
    180; then one device sample of 1.3 s."""
    sys.path.insert(0, ROOT)
    from opengemini_tpu.query import offload

    cost = {"device": 0.070, "host": 0.180}
    p, geo = offload.Planner(), (10000, 240, 56)
    was_on = offload._ON
    offload.set_enabled(True)
    try:
        def ask():
            return p.decide("prom_rate", geo, ("host", "device"), "device")

        warm = []
        for _ in range(30):
            warm.append(ask())
            p.observe("prom_rate", geo, warm[-1], cost[warm[-1]])
        assert warm.count("host") == 2 and warm[-5:] == ["device"] * 5
        p.set_frozen(pinned)
        p.observe("prom_rate", geo, "device", 1.3)        # the stalled fetch
        after = []
        for _ in range(200):
            after.append(ask())
            p.observe("prom_rate", geo, after[-1], cost[after[-1]])
        assert set(after) == ({"device"} if pinned else {"host"})
        assert len(p.decisions()) >= 128                  # the ring goes on
    finally:
        offload.set_enabled(was_on)


def _phase(c, spans):
    c.trace_at = 100.0
    c.phase = traffic.Plan([], [], [], {"kind": "closed"}, [])
    c.phase.results = [traffic.Result(i, due=100.0 + a, sent=100.0 + a,
                                      done=100.0 + b, ok=True)
                       for i, (a, b) in enumerate(spans)]


def test_a_capture_with_no_device_operation_is_a_stated_reason():
    c = cell()
    c.steady_s, c.pinned = [7.9, 8.1, 8.0], {"prom_rate": {"(5000,)": "device"}}
    _phase(c, [(0.0, 8.4), (8.4, 16.5)])
    red = {"busy_s": 0.0, "window_s": 5.98, "device_ops": [], "launches": {},
           "devices_traced": 1, "devices_busy": 0}
    with pytest.raises(BenchFailure) as e:
        c.held_by_capture(red)
    text = str(e.value)
    assert "holds no device operation" in text and "busy_s 0.0" in text
    assert "of the traced phase's 2 request(s) 1 began and 0 both began " \
        "and ended inside it" in text
    assert "median steady warm repeat 8.000s beside trace.seconds 6.0" in text
    assert "'prom_rate': {'(5000,)': 'device'}" in text
    assert "give the traffic file `trace.requests`" in text
    # whole requests inside it and still nothing on the device: the route
    _phase(c, [(0.0, 0.7), (0.7, 1.4)])
    c.pinned = {"prom_rate": {"(10000,)": "host"}}
    with pytest.raises(BenchFailure) as e:
        c.held_by_capture(red)
    assert "2 both began and ended" in str(e.value)
    assert "'host'" in str(e.value) and "trace.requests" not in str(e.value)
    # a capture that shows the device busy is a result
    c.held_by_capture({**red, "busy_s": 0.2,
                       "device_ops": [["jit_x/fusion", 0.2]]})
    # and the control-flow run, which has no device plane, only says so
    c.dry = True
    c.held_by_capture(red)


def test_trace_requests_lengthens_a_capture_and_its_absence_leaves_it():
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        doc = bench_run.load_json(BENCH, "traffic", name)
        assert "requests" not in doc["trace"], name
    c = cell()
    c.steady_s = [7.9, 8.1, 8.0]
    assert c.capture_seconds() == (6.0, 8.0)
    c.traffic["trace"] = dict(c.traffic["trace"], requests=2)
    seconds, send_s = c.capture_seconds()
    assert seconds == pytest.approx(2 * 8.1 * bench_run.TRACE_STRETCH)
    assert send_s == pytest.approx(seconds + 2.0)
    c.steady_s = [0.70, 0.72, 0.69]         # short requests: the file's 6 s
    assert c.capture_seconds() == (6.0, 8.0)
    c.steady_s = [40.0]
    with pytest.raises(BenchFailure, match="too long for this cell"):
        c.capture_seconds()


def test_a_configuration_s_server_sections_reach_server_toml(tmp_path):
    work = str(tmp_path)
    plain = (f'[data]\ndir = "{work}/data"\n'
             '[http]\nbind-address = "127.0.0.1:8086"\n')
    assert server_toml(work, 8086, None) == plain == server_toml(work, 8086, {})
    sections = {"device": {"mesh-axes": ["shard"], "mesh-devices": 1}}
    assert server_toml(work, 8086, sections) == plain + \
        '[device]\nmesh-axes = ["shard"]\nmesh-devices = 1\n'
    for bad in ({"http": {"x": 1}}, {"device": 3}, {"device": {"x": {}}}):
        with pytest.raises(BenchFailure):
            server_toml(work, 8086, bad)
    # no configuration carries one yet; a server started with one builds it
    for name in os.listdir(os.path.join(BENCH, "configs")):
        if name.endswith(".json"):
            assert "server" not in bench_run.load_json(BENCH, "configs", name)
    srv = Server(ROOT, work, True, sections)
    try:
        srv.wait_ready()
        assert "device mesh: {'shard': 1}" in srv.log_tail(200)
    finally:
        srv.stop()
    assert not _alive(srv.proc.pid)
    assert not os.path.exists(os.path.join(work, harness_server.PID_FILE))

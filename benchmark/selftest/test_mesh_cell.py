"""The cell `tsbs_fleet_groupby_mesh4` (PR 44) is data files and one reader:
the configuration `tsbs-devops-cpu-4000-mesh4` (the hot hour's deployment
with a `server` member, `[device] mesh-axes` over four chips), the traffic
mix `fleet_groupby_mesh4` (the fleet cell's statement, `launches_per_request`
4: one partitioned launch a statement, seen on each of four device planes),
three `vars_ratio` metric files and `readers/mesh_devices_busy.py`.  They load
through the checks `run.py` makes before it starts a server; the two files
differ from their one-chip twins only in the members that make the mesh; the
reduction of a capture of four device planes gives the mean busy time, the
launches summed over the planes and `devices_busy` 4, and `traced_work`
divides by 4 to count the requests that were there; the new metric files read
nothing or 0, without raising, where a program has no such span or counter
(the parent)."""

import argparse

import numpy as np
import pytest

import run as bench_run
from harness import metrics, peaks, trace_reduce as tr, traffic
from harness.server import server_toml

from conftest import BENCH, ROOT

CELL, TWIN = "tsbs_fleet_groupby_mesh4", "tsbs_fleet_groupby"
CONFIG, TRAFFIC = "tsbs-devops-cpu-4000-mesh4", "fleet_groupby_mesh4"
NEW = {"mesh_shard_ms_per_q": "Layout", "mesh_pad_share": "Layout",
       "mesh_unsharded_item_share": "Layout",
       "mesh_devices_busy": "Device runtime"}

_json = bench_run.load_json


def cell():
    args = argparse.Namespace(workload=CELL, seed=1, seconds=51.0, trace=1,
                              cpu_dry_run=False, keep_trace=None)
    return bench_run.Cell(args, _json(ROOT, "BENCHMARK.json"))


def test_the_files_load_and_the_cell_reports_what_its_twin_does():
    c, bench = cell(), _json(ROOT, "BENCHMARK.json")
    assert c.cell == {**c.cell, "config": CONFIG, "traffic": TRAFFIC,
                      "chips": 4}
    traffic.check(c.traffic, c.cfg)
    conf = next(x for x in bench["configs"] if x["name"] == CONFIG)
    assert conf["reduced"] == c.cfg["reduced"] == ["span_s"]
    assert conf["source"] == c.cfg["source"] and len(conf["source"]) <= 200
    assert len(c.cell["why"]) <= 200
    assert [m["name"] for m in c.e2e] == ["scan_points_per_s", "setup_s"]
    mine = {m["name"] for m in c.layer}
    twin = {m["name"] for m in bench["per_layer"] if TWIN in m["workloads"]}
    assert mine == twin | set(NEW) and not twin & set(NEW)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert (m["layer"], m["moves"]) == (NEW[m["name"]],
                                                "scan_points_per_s")
        elif TWIN in m["workloads"]:    # appended: the last of its list
            assert m["workloads"][-1] == CELL
    # the one four-chip cell, within the cap of half the cells
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [CELL] and len(four) <= len(bench["workloads"]) // 2
    assert len(bench["per_layer"]) <= 128


def test_the_deployment_and_the_traffic_are_their_twins_with_a_mesh():
    one = _json(BENCH, "configs", "tsbs-devops-cpu-4000.json")
    four = _json(BENCH, "configs", CONFIG + ".json")
    differ = {k for k in one.keys() | four.keys() if one.get(k) != four.get(k)}
    assert differ == {"name", "source", "server", "assumed", "guarantees"}
    assert four["reference"] == "tsbs_cpu_only.py"      # the same file
    assert four["server"] == {"device": {"mesh-axes": ["shard"],
                                         "mesh-devices": 4}}
    assert server_toml("/w", 1, four["server"]).endswith(
        '[device]\nmesh-axes = ["shard"]\nmesh-devices = 4\n')
    # the one-chip file's guarantees word for word, and one more
    assert {k: v for k, v in four["guarantees"].items()
            if k != "sharded_equals_unsharded"} == one["guarantees"]
    assert {k for k in four["assumed"] if four["assumed"][k]
            != one["assumed"].get(k)} == {"deployment", "server"}
    a = _json(BENCH, "traffic", "fleet_groupby.json")
    b = _json(BENCH, "traffic", TRAFFIC + ".json")
    assert {k for k in a.keys() | b.keys() if a.get(k) != b.get(k)} == {
        "name", "why", "device_work"}
    assert {k: v for k, v in b["device_work"].items() if k != "why"} == {
        **{k: v for k, v in a["device_work"].items() if k != "why"},
        "launches_per_request": 4}


def planes(chips: int = 4, launches: int = 3):
    """`launches` partitioned launches of `jit_bucket_basic`, each seen on
    every one of `chips` device planes for 50 + 10 x chip ns, 1,000 ns
    apart; a fifth plane that did nothing."""
    devs = []
    for c in range(chips):
        dur = 50 + 10 * c
        devs.append({"name": f"/device:TPU:{c}", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_bucket_basic(77)", 1000 * k + c, dur)
                for k in range(launches)]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 1000 * k + c, dur) for k in range(launches)]}]})
    idle = {"name": f"/device:TPU:{chips}", "lines": [
        {"name": "XLA Ops", "events": []}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("ogt:mesh_shard", 100, 800), ("ogt:http_query", 0, 3000)]}]}
    return devs + [idle, host]


def test_four_planes_reduce_to_their_mean_busy_time_and_summed_launches():
    red = tr.reduce_planes(planes())
    assert (red["devices_traced"], red["devices_busy"]) == (5, 4)
    assert red["launches"] == {"jit_bucket_basic": 12}
    # busy: the mean over the chips that did work, 3 x (50, 60, 70, 80) ns
    assert red["busy_s"] == pytest.approx(3 * 65e-9)
    # the roofline's denominator: every plane's device time, summed
    assert red["program_s"]["jit_bucket_basic"] == pytest.approx(3 * 260e-9)
    assert "ogt:mesh_shard" in red["idle_gaps"][0][0]
    read, params = metrics.load("mesh_devices_busy", next(
        m for m in _json(ROOT, "BENCHMARK.json")["per_layer"]
        if m["name"] == "mesh_devices_busy"))
    assert read({"trace": red}, params) == 4
    assert read({"trace": tr.reduce_planes(planes(chips=1))}, params) == 1
    assert read({"trace": None}, params) is None


def test_traced_work_counts_the_requests_that_were_there():
    c = cell()
    req = traffic.Request("POST", "/query", b"", {"kind": "influxql",
                                                  "groups": 240000}, 7200000)
    c.phase = traffic.Plan([], [], [req] * 4, {"kind": "closed"},
                           np.zeros(4, bool), [
        traffic.Result(i, due=i, sent=float(i), done=i + 0.9, status=200,
                       ok=True) for i in range(4)])
    c.trace_at = 0.0
    red = {**tr.reduce_planes(planes()), "window_s": 2.95}
    got = c.traced_work(red)
    # twelve launches over four planes are three statements (by the share of
    # their time the capture covers, 2.9 / 0.9, it would be 3.06)
    assert got == {"requests": 3.0, "needs": "bucketed_reduce",
                   "points": 3 * 7200000.0, "groups": 3 * 240000.0}
    # with the one-chip file's 1 the same capture would count 12, and the
    # share of the roofline would read four times too high
    c.traffic = {**c.traffic, "device_work": {
        **c.traffic["device_work"], "launches_per_request": 1}}
    assert c.traced_work(red)["requests"] == 12.0
    # the share: the statements' need at one chip's peak over the summed
    # device time of four planes; it cannot pass 100 while each plane's
    # share of the work takes at least a quarter of the least time
    need = peaks.bucketed_reduce(got["points"], got["groups"])
    assert need == {"bytes": 5 * 21600000 + 8 * 720000, "flops": 2 * 21600000}
    read, params = c.readers["device_kernels_roofline"]
    ctx = {"trace": red, "traced": got,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    assert read(ctx, params) == pytest.approx(
        100 * (need["bytes"] / 819e9) / (3 * 260e-9))
    read, params = c.readers["device_busy_ms_per_q"]
    assert read(ctx, params) == pytest.approx(65e-6)


def test_the_new_metric_files_read_the_program_s_span_and_counters():
    c = cell()
    ctx = {"vars0": {"query_stages": {"mesh_shard_ns": 1_000_000},
                     "device": {"mesh_put_rows": 10},
                     "client": {"completed": 0}},
           "vars1": {"query_stages": {"mesh_shard_ns": 9_000_000},
                     "device": {"mesh_put_rows": 2590, "mesh_pad_rows": 20,
                                "mesh_items_sharded": 15,
                                "mesh_items_unsharded": 5},
                     "client": {"completed": 4}}}
    got = {n: c.readers[n][0](ctx, c.readers[n][1]) for n in NEW
           if n != "mesh_devices_busy"}
    assert got == {"mesh_shard_ms_per_q": 2.0,
                   "mesh_pad_share": pytest.approx(100 * 20 / 2580),
                   "mesh_unsharded_item_share": 25.0}
    # the parent's program, or no mesh: no such span, no such counter
    bare = {"vars0": {"client": {"completed": 0}},
            "vars1": {"client": {"completed": 4}, "device": {}}}
    got = {n: c.readers[n][0](bare, c.readers[n][1]) for n in NEW}
    assert got == {"mesh_shard_ms_per_q": 0.0, "mesh_pad_share": None,
                   "mesh_unsharded_item_share": None,
                   "mesh_devices_busy": None}

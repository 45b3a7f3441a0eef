"""The cell `prom_topk_highcard` (PR 49) is data files and one reference: the
configuration `prom-highcard-gauges` (`BASELINE.json` config #5 at its stated
1,000,000 series), the traffic mix `topk_highcard` and three `vars_ratio`
metric files that wait for room in `per_layer`.  They load through the checks
`run.py` makes before it starts a server; the request the generator builds
counts the series of its ANSWER; a capture of this cell holds two whole
requests (what `test_day_cell.py` and `test_run_lifecycle.py` assert of the
stored traffic files no longer holds since this file carries `trace.requests`
too, and those are not this PR's to edit: held here for both).  The
control-flow run and the parity with the reference on the served path are
tier-1's: `tests/test_prom_highcard_reference.py`."""

import argparse
import os

import pytest

import run as bench_run
from harness import traffic

from conftest import BENCH, ROOT
from test_oracles import reference

CELL, CONFIG, TRAFFIC = ("prom_topk_highcard", "prom-highcard-gauges",
                         "topk_highcard")
WAITING = ("prom_select_ms_per_q", "prom_labels_ms_per_q",
           "prom_fast_agg_share")

_json = bench_run.load_json


def cell():
    args = argparse.Namespace(workload=CELL, seed=1, seconds=51.0, trace=1,
                              cpu_dry_run=False, keep_trace=None)
    return bench_run.Cell(args, _json(ROOT, "BENCHMARK.json"))


def test_the_files_load_and_the_cell_reports_what_a_promql_cell_does():
    c, bench = cell(), _json(ROOT, "BENCHMARK.json")
    assert c.cell == {**c.cell, "config": CONFIG, "traffic": TRAFFIC,
                      "chips": 1}
    assert [m["name"] for m in c.e2e] == ["scan_points_per_s", "setup_s"]
    mine = {m["name"] for m in c.layer}
    day = {m["name"] for m in bench["per_layer"]
           if "prom_rate_range_24h" in m["workloads"]}
    # the day cell's, less what this path never opens or asks
    assert day - mine == {
        "device_kernels_roofline", "host_route_share", "route_flips_in_window",
        "prom_fill_ms_per_q", "prom_tile_index_ms_per_q",
        "prom_narrow_ms_per_q", "prom_values_h2d_enqueue_ms_per_q"}
    # and the fleet cells' launches a query: one named program here
    assert mine - day == {"device_launches_per_q"}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL
            assert m["moves"] == "scan_points_per_s"
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # the three that wait are files without entries: the list is full
    assert len(bench["per_layer"]) == 128
    have = {m["name"] for m in bench["per_layer"]}
    for name in WAITING:
        assert name not in have
        spec = _json(BENCH, "metrics", name + ".json")
        assert spec["reader"] == "vars_ratio" and spec["name"] == name
        assert spec["moves"] == "scan_points_per_s"


def test_the_request_counts_the_series_of_its_answer():
    c = cell()
    mod, cfg = reference(CONFIG)                        # the dry-run size
    assert cfg["stored_series"] == 6000 and cfg["span_s"] == 120
    ref = mod.Reference(cfg, 5)
    more = float(c.traffic["trace"]["send_s"]) + 54.0   # trace.requests
    plan = traffic.build(c.traffic, ref, 5, 51.0 + more)
    assert plan.warm_touch == [] and len(plan.warm_repeat) == 10
    req = plan.requests[0]
    assert all(r is req for r in plan.requests)         # not result-cached
    assert len(plan.requests) == 2 * (51 + 62)          # max_qps 2
    assert req.stmt["windows"] == 5
    assert req.stmt["marker_count"] == ref.series and 10 <= ref.series <= 50
    assert req.units == ref.points(req.stmt) == 8 * 6000
    assert plan.keep.all()                  # every answer is held to the oracle


def test_a_capture_of_this_cell_holds_two_whole_requests():
    for name in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        doc = _json(BENCH, "traffic", name)
        assert ("requests" in doc["trace"]) == (
            name in (TRAFFIC + ".json", "rate_range_24h.json")), name
    c = cell()
    c.steady_s = [1.5, 1.7, 1.6]
    seconds, send_s = c.capture_seconds()
    assert seconds == 6.0                   # two short requests: the file's
    assert send_s == pytest.approx(seconds + 2.0)
    c.steady_s = [3.0, 3.4, 3.1]
    seconds, send_s = c.capture_seconds()
    assert seconds == pytest.approx(2 * 3.4 * bench_run.TRACE_STRETCH)
    assert send_s == pytest.approx(seconds + 2.0)
    # launches of the named program count the requests a capture holds
    work = c.traffic["device_work"]
    assert (work["launch_program"], work["launches_per_request"]) == (
        "jit_prom_instant", 1)
    c.phase = traffic.Plan([], [], [], c.traffic["loop"], [])
    got = c.traced_work({"window_s": 8.5, "launches": {"jit_prom_instant": 2}})
    assert got["requests"] == 2.0 and got["needs"] is None

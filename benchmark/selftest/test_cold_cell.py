"""The cell `tsbs_fleet_groupby_cold` (PR 27) is data files: a configuration
that is the hot one with six hours kept, a traffic mix that is the hot one
with an hour a statement and the hour cycling, and nine `vars_ratio` metric
files.  They load through the checks `run.py` makes before it starts a
server; the plan asks no hour again within six statements from the first
touch to the traced phase; the metric files read the program's counters and
read nothing, without raising, where a program has none (the parent); and
the control-flow run of the cell exits 0."""

import argparse
import json
import os
import subprocess
import sys

import pytest

import run as bench_run
from harness import traffic

from conftest import BENCH, ROOT
from test_oracles import reference

CELL = "tsbs_fleet_groupby_cold"
NEW = ("decode_ms_per_q", "decode_read_ms_per_q", "decode_codec_ms_per_q",
       "colcache_fill_ms_per_q", "scan_merge_ms_per_q", "decode_parallelism",
       "tsf_read_mb_per_q", "decode_mb_per_s", "decode_amplification")


_json = bench_run.load_json


def cell(dry=False):
    args = argparse.Namespace(workload=CELL, seed=1, seconds=51.0, trace=1,
                              cpu_dry_run=dry, keep_trace=None)
    return bench_run.Cell(args, _json(ROOT, "BENCHMARK.json"))


def test_the_files_load_and_the_cell_reports_what_the_hot_one_does():
    c, bench = cell(), _json(ROOT, "BENCHMARK.json")
    assert c.cfg["span_s"] == 21600 and c.cfg["hosts"] == 4000
    assert traffic.statement_ranges(c.traffic, c.cfg) == (3600, 6)
    assert [m["name"] for m in c.e2e] == ["scan_points_per_s", "setup_s"]
    mine = {m["name"] for m in c.layer}
    hot = {m["name"] for m in bench["per_layer"]
           if "tsbs_fleet_groupby" in m["workloads"]}
    assert len(hot) == 25 and mine == hot | set(NEW)
    # a new metric is the cold cell's alone, and the last of its list
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["layer"] == "Scan + decode"


def test_the_deployment_is_the_hot_one_with_six_hours_kept():
    hot = _json(BENCH, "configs", "tsbs-devops-cpu-4000.json")
    cold = _json(BENCH, "configs", "tsbs-devops-cpu-4000-6h.json")
    differ = {k for k in hot.keys() | cold.keys() if hot.get(k) != cold.get(k)}
    assert differ == {"name", "source", "span_s", "quiesce", "reduced_why",
                      "assumed"}
    assert cold["reduced"] == ["span_s"] and len(cold["source"]) <= 200
    assert cold["quiesce"] == {**hot["quiesce"], "timeout_s": 300}
    assert cold["assumed"].keys() - hot["assumed"].keys() \
        == {"storage_layout", "cache_regime"}
    assert all(cold["assumed"][k] == v for k, v in hot["assumed"].items())
    # the statements are the hot cell's, but for the range and the capture
    a = _json(BENCH, "traffic", "fleet_groupby.json")
    b = _json(BENCH, "traffic", "fleet_groupby_cold.json")
    assert {k for k in a.keys() | b.keys() if a.get(k) != b.get(k)} == {
        "name", "why", "who", "range_s", "range_walk", "range_why", "trace"}
    assert (b["trace"]["seconds"], b["trace"]["send_s"]) == (12.0, 14.0)


@pytest.mark.parametrize("warm_sent", [7, 8, 12, 30])
def test_no_hour_is_asked_again_within_six_statements(warm_sent):
    """Touches, the warm repeats the server needed, the window's 16 and the
    traced phase's: over 40 statements and more, the n-th at hour n mod 6."""
    c = cell()
    mod, cfg = reference("tsbs-devops-cpu-4000-6h")     # 24 hosts, 6 h
    ref = mod.Reference(cfg, 9)
    more = float(c.traffic["trace"]["send_s"])
    plan = traffic.build(c.traffic, ref, 9, 51.0 + more)
    assert plan.cycle == 6 and len(plan.warm_touch) == 2
    traffic.join_walk(plan, warm_sent)
    plan.results = [None] * 16                           # the window's
    sent = plan.warm_touch + plan.warm_repeat[:warm_sent] \
        + plan.requests[:16] + traffic.rest(plan).requests[:24]
    assert len(sent) >= 40
    hours = [(q.stmt["t0"] - ref.start_s) // 3600 for q in sent]
    assert all(q.stmt["t1"] - q.stmt["t0"] == 3600 for q in sent)
    assert hours == [n % 6 for n in range(len(sent))]
    assert len({q.path for q in sent}) == len(sent)      # none asked twice


def test_the_new_metric_files_read_the_program_s_counters():
    c = cell()
    ctx = {"vars0": {}, "vars1": {
        "client": {"completed": 16},
        "query_stages": {"decode_ns": 16 * 1_800_000_000,
                         "block_read_ns": 16 * 900_000_000,
                         "codec_ns": 16 * 12_000_000_000,
                         "colcache_fill_ns": 16 * 40_000_000,
                         "scan_merge_ns": 16 * 300_000_000},
        "scanpool": {"busy_ns": 16 * 1_800_000_000 * 9},
        "tsf": {"read_bytes": 16 * 120_000_000},
        "scan": {"decoded_bytes": 16 * 527_040_000,
                 "rows_decoded": 16 * 8_640_000, "rows_kept": 16 * 1_440_000},
    }}
    got = {name: c.readers[name][0](ctx, c.readers[name][1]) for name in NEW}
    assert got == pytest.approx({
        "decode_ms_per_q": 1800.0, "decode_read_ms_per_q": 900.0,
        "decode_codec_ms_per_q": 12000.0, "colcache_fill_ms_per_q": 40.0,
        "scan_merge_ms_per_q": 300.0, "decode_parallelism": 9.0,
        "tsf_read_mb_per_q": 120.0, "decode_mb_per_s": 292.8,
        "decode_amplification": 6.0})
    # a program without these spans and counters (the parent), or a window
    # in which nothing decoded: a number or nothing, never an exception
    for vars1 in ({}, {"client": {"completed": 16}}):
        for name in NEW:
            read, params = c.readers[name]
            assert read({"vars0": {}, "vars1": vars1}, params) in (None, 0.0)


def test_the_control_flow_run_exits_0():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--cpu-dry-run", "--seconds", "1", "--trace", "1"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["cpu_dry_run"] is True and out["metrics"] == {}

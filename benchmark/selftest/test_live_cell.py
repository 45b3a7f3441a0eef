"""The cell `tsbs_dash_refresh` (PR 32) is data files: the configuration
`tsbs-devops-cpu-4000-live` (the hot deployment with its agents still
reporting, which states freshness and cached = computed), the traffic mix
`dash_refresh` and eight metric files with built-in readers.  (PR 31's
draft of the two files, `selftest/draft/`, went in PR 41; `tools/draft.py`
drives the real cell.)  They load through the checks `run.py` makes before
it starts a server; the metric files read the program's span and counters, and read
nothing, without raising, where a program has none (the parent); and the
control-flow run of the cell exits 0."""

import argparse
import json
import os
import subprocess
import sys

import pytest

import run as bench_run
from harness import metrics, traffic
from tools import draft

from conftest import BENCH, ROOT

CELL, LIKE = "tsbs_dash_refresh", "tsbs_host_panels"
CONFIG, TRAFFIC = "tsbs-devops-cpu-4000-live", "dash_refresh"
NEW = {"mem_read_ms_per_q.live": "Scan + decode",
       "mem_rows_per_q.live": "Scan + decode",
       "resultcache_touched_windows_per_q.live": "Plan",
       "resultcache_cut_windows_per_q.live": "Plan",
       "resultcache_evictions_in_window.live": "Plan",
       "flushes_in_window.live": "Storage",
       "write_ms_per_mrow.live": "Ingest",
       "write_late_p95_ms.live": "HTTP front end"}

_json = bench_run.load_json


def cell(dry=False):
    args = argparse.Namespace(workload=CELL, seed=1, seconds=51.0, trace=1,
                              cpu_dry_run=dry, keep_trace=None)
    return bench_run.Cell(args, _json(ROOT, "BENCHMARK.json"))


def test_the_deployment_is_the_hot_one_still_reporting():
    hot = _json(BENCH, "configs", "tsbs-devops-cpu-4000.json")
    live = _json(BENCH, "configs", CONFIG + ".json")
    differ = {k for k in hot.keys() | live.keys() if hot.get(k) != live.get(k)}
    assert differ == {"name", "source", "setup_q", "assumed", "guarantees"}
    assert live["setup_q"] == [] and live["reduced"] == ["span_s"]
    assert len(live["source"]) <= 200 and "single-groupby-5-8-1" in \
        live["source"]
    for group, more in [("assumed", {"ingest_rate", "dashboard", "no_stream"}),
                        ("guarantees", {"freshness",
                                        "cached_equals_computed"})]:
        assert live[group].keys() - hot[group].keys() == more
        assert all(live[group][k] == v for k, v in hot[group].items())
    # the statement is the panels cell's; the rest is what makes it live
    a = _json(BENCH, "traffic", "host_panels.json")
    b = _json(BENCH, "traffic", TRAFFIC + ".json")
    assert {k for k in a.keys() | b.keys() if a.get(k) != b.get(k)} == {
        "name", "why", "who", "range_s", "range_end", "panels", "ingest",
        "loop", "warm", "dry_run"}
    assert (b["range_s"], b["range_end"]) == (3600, "newest_acked")
    assert b["panels"] == {"count": 50, "refresh_s": 10}
    assert {k: b["ingest"][k] for k in ("batch_rows", "rows_per_s",
                                        "clients")} == {
        "batch_rows": 4000, "rows_per_s": 4000, "clients": 1}
    assert b["loop"]["workers"] == 16 and "rate_qps" not in b["loop"]
    assert b["verify"] == a["verify"] == {"of_each": 64, "keep": 3}
    assert b["trace"] == a["trace"]


def test_the_cell_loads_and_reports_what_the_panels_cell_does():
    c, bench = cell(), _json(ROOT, "BENCHMARK.json")
    traffic.check(c.traffic, c.cfg)
    assert traffic.statement_ranges(c.traffic, c.cfg) == (3600, 1)
    assert c.cell == {**c.cell, "config": CONFIG, "traffic": TRAFFIC,
                      "chips": 1}
    conf = next(x for x in bench["configs"] if x["name"] == CONFIG)
    assert conf["source"] == c.cfg["source"] and conf["reduced"] == ["span_s"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [m["name"] for m in c.e2e] == ["query_p50_ms", "setup_s"]
    mine = {m["name"] for m in c.layer}
    like = {m["name"] for m in bench["per_layer"] if LIKE in m["workloads"]}
    # whatever the panels cell reports (16 then, 33 since PR 40: later PRs
    # append), this cell reports, and eight of its own, found by name
    assert len(like) >= 16 and mine == like | set(NEW)
    own = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert {m["name"] for m in own} == set(NEW)
    for m in own:
        assert m["moves"] == "query_p50_ms"
        assert m["layer"] == NEW[m["name"]]
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] in metrics.BUILTIN and spec["what"]
    # `tools/draft.py` drives this cell, from these files
    d = draft.cell(seed=1, seconds=51.0, trace=1, dry=False)
    assert d.cfg == c.cfg and d.traffic == c.traffic


def test_the_new_metric_files_read_the_program_s_span_and_counters():
    c = cell()
    ctx = {"vars0": {"executor": {"inc_cache_evictions": 3},
                     "flush": {"flushes": 1}},
           "vars1": {
        "client": {"completed": 255, "rows_written": 204_000},
        "query_stages": {"mem_read_ns": 255 * 7_000_000},
        "scan": {"mem_rows": 255 * 8 * 35},
        "executor": {"inc_cache_windows_touched": 510,
                     "inc_cache_windows_cut": 425,
                     "inc_cache_evictions": 3},
        "flush": {"flushes": 2},
        "http": {"write_ns": 204_000 * 5_000},
    }, "client": {"write_late_p95_ms": 1.2}}
    got = {name: c.readers[name][0](ctx, c.readers[name][1]) for name in NEW}
    assert got == pytest.approx({
        "mem_read_ms_per_q.live": 7.0, "mem_rows_per_q.live": 280.0,
        "resultcache_touched_windows_per_q.live": 2.0,
        "resultcache_cut_windows_per_q.live": 425 / 255,
        "resultcache_evictions_in_window.live": 0.0,
        "flushes_in_window.live": 1.0, "write_ms_per_mrow.live": 5000.0,
        "write_late_p95_ms.live": 1.2})
    # a program without the span and the counters (the parent), or a window
    # in which nothing was written: a number or nothing, never an exception
    for vars1 in ({}, {"client": {"completed": 255, "rows_written": 0}}):
        for name in NEW:
            read, params = c.readers[name]
            assert read({"vars0": {}, "vars1": vars1, "client": {}},
                        params) in (None, 0.0)


def test_the_control_flow_run_exits_0():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--cpu-dry-run", "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["cpu_dry_run"] is True and out["metrics"] == {}
    assert out["attempted"] == 12           # 6 panels a second, 2 s

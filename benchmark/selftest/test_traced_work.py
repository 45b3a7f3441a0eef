"""`run.py` `Cell.traced_work`: a traced phase that sent nothing reads as
nothing traced (it divided by zero until PR 26: the driver refused PR 25
once for it), and one with requests counts them by the capture's share of
their time."""

import argparse
import json

import numpy as np

import run as bench_run
from harness import traffic

from conftest import ROOT

RED = {"window_s": 25.0, "launches": {}}


def cell_with_phase(requests, results=()):
    args = argparse.Namespace(workload="tsbs_load", seed=1, seconds=2.0,
                              trace=1, cpu_dry_run=True, keep_trace=None)
    with open(f"{ROOT}/BENCHMARK.json") as f:
        cell = bench_run.Cell(args, json.load(f))
    cell.phase = traffic.Plan([], [], list(requests), {"kind": "closed"},
                              np.zeros(len(requests), bool), list(results))
    cell.trace_at = 0.0
    return cell


def test_an_empty_traced_phase_reads_as_nothing_traced():
    assert cell_with_phase([]).traced_work(RED) == {
        "requests": 0.0, "needs": None, "points": 0.0, "groups": 0.0}


def test_a_phase_with_requests_counts_what_the_capture_covers():
    req = traffic.Request("POST", "/write?db=x", None, {"kind": "write"},
                          10000)
    inside = traffic.Result(0, due=1.0, sent=1.0, done=2.0, status=204,
                            ok=True)
    half = traffic.Result(1, due=24.0, sent=24.0, done=26.0, status=204,
                          ok=True)
    got = cell_with_phase([req, req], [inside, half]).traced_work(RED)
    assert got == {"requests": 1.5, "needs": None, "points": 15000.0,
                   "groups": 0.0}


def test_the_load_cell_s_metric_files_name_builtin_readers():
    cell = cell_with_phase([])
    read, params = cell.readers["observer_rows_built_share"]
    ctx = {"vars0": {"write": {"observer_rows_offered": 10}},
           "vars1": {"write": {"observer_rows_offered": 30,
                               "observer_rows_built": 5}}}
    assert read(ctx, params) == 25.0
    assert read({"vars0": {}, "vars1": {}}, params) is None   # the parent

"""The stopgap in readers/vars_ratio_list_ran_out.py: with no request left
for the traced phase `Cell.traced_work` answers "nothing traced" and does
not divide by zero; with a request left it is the function run.py has.
The first test fails once run.py is repaired: delete the stopgap then."""

import argparse
import json

import numpy as np
import pytest

import run as bench_run
from harness import traffic

from conftest import ROOT

RED = {"window_s": 25.0, "launches": {}}


def cell_with_phase(requests, results=()):
    args = argparse.Namespace(workload="tsbs_load", seed=1, seconds=2.0,
                              trace=1, cpu_dry_run=True, keep_trace=None)
    with open(f"{ROOT}/BENCHMARK.json") as f:
        cell = bench_run.Cell(args, json.load(f))     # loads the readers
    cell.phase = traffic.Plan([], [], list(requests), {"kind": "closed"},
                              np.zeros(len(requests), bool), list(results))
    return cell


def test_run_py_still_needs_the_stopgap():
    cell = cell_with_phase([])
    with pytest.raises(ZeroDivisionError):
        bench_run.Cell.traced_work.__wrapped__(cell, RED)


def test_an_empty_traced_phase_reads_as_nothing_traced():
    assert cell_with_phase([]).traced_work(RED) == {
        "requests": 0.0, "needs": None, "points": 0.0, "groups": 0.0}


def test_a_phase_with_requests_is_run_pys_own_count():
    req = traffic.Request("POST", "/write?db=x", b"", {"kind": "write"}, 10000)
    res = traffic.Result(0, due=1.0, sent=1.0, done=2.0, status=204, ok=True)
    cell = cell_with_phase([req], [res])
    cell.trace_at = 0.0
    got = cell.traced_work(RED)
    assert got == bench_run.Cell.traced_work.__wrapped__(cell, RED)
    assert got["requests"] == 1.0 and got["points"] == 10000.0


def test_the_reading_is_vars_ratio():
    cell = cell_with_phase([])
    read, params = cell.readers["observer_rows_built_share"]
    ctx = {"vars0": {"write": {"observer_rows_offered": 10}},
           "vars1": {"write": {"observer_rows_offered": 30,
                               "observer_rows_built": 5}}}
    assert read(ctx, params) == 25.0
    assert read({"vars0": {}, "vars1": {}}, params) is None   # the parent

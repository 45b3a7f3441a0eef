"""A run whose timed path is broken underneath comes out `correct: false`.

Each test drives a whole run (`run.Cell.run`) at the tiny sizes of
--cpu-dry-run against a real server process on the CPU — the look for a
chip is what --cpu-dry-run skips — with the one function every timed
request passes through (`traffic.send`) altered where the answer is
produced: one value of one sampled answer moved by a thousandth; one /write
reported acknowledged that the server never saw; and, in the cell that
reads while it writes (`tsbs_dash_refresh`), that /write of its ingest, or
one sampled answer replaced by what its panel was answered a refresh
earlier — a cached window served stale."""

import argparse
import json

import pytest

import run as bench_run
from harness import traffic
from tools import draft
from tools.control import drop_one_write, stale_answer

from conftest import ROOT


def drive(workload: str, monkeypatch, break_send=None) -> dict:
    if break_send is not None:
        monkeypatch.setattr(traffic, "send", break_send(traffic.send))
    args = argparse.Namespace(workload=workload, seed=2147483659, seconds=2.0,
                              trace=0, cpu_dry_run=True, keep_trace=None)
    with open(f"{ROOT}/BENCHMARK.json") as f:
        cell = bench_run.Cell(args, json.load(f))
    try:
        return cell.run()
    finally:
        cell.stop()


def alter_one_answer(send):
    state = {"done": False}

    def broken(client, req, res, keep):
        send(client, req, res, keep)
        if keep and res.ok and not state["done"] and res.index == 3:
            doc = json.loads(res.body)
            if "results" in doc:
                doc["results"][0]["series"][0]["values"][0][1] *= 1.001
            else:
                v = doc["data"]["result"][0]["values"][0]
                v[1] = repr(float(v[1]) * 1.001)
            res.body = json.dumps(doc).encode()
            state["done"] = True
    return broken


CELLS = ["tsbs_fleet_groupby", "prom_rate_range", "tsbs_host_panels",
         "tsbs_load"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload, monkeypatch):
    out = drive(workload, monkeypatch)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}   # no device name


BROKEN = [(w, drop_one_write if w == "tsbs_load" else alter_one_answer)
          for w in CELLS] + [(draft.CELL, drop_one_write),
                             (draft.CELL, stale_answer)]


@pytest.mark.parametrize("workload, brk", BROKEN, ids=[
    f"{w}-{b.__name__}" for w, b in BROKEN])
def test_a_broken_timed_path_is_not_correct(workload, brk, monkeypatch):
    out = drive(workload, monkeypatch, brk)
    assert out["correct"] is False

"""A metric file with an unknown reader, unit or `moves` fails loudly, and
every metric file of BENCHMARK.json loads."""

import json
import os

import pytest

from harness import metrics

from conftest import ROOT

ENTRY = {"name": "m", "unit": "count", "source": "program_counter",
         "layer": "Plan", "moves": "query_p50_ms"}


def _write(tmp_path, monkeypatch, **over):
    monkeypatch.setattr(metrics, "HERE", str(tmp_path))
    os.makedirs(tmp_path / "metrics", exist_ok=True)
    spec = {**ENTRY, "reader": "vars_delta",
            "params": {"counters": ["executor/queries"]}, **over}
    (tmp_path / "metrics" / "m.json").write_text(json.dumps(spec))


def test_a_fitting_file_loads_and_reads(tmp_path, monkeypatch):
    _write(tmp_path, monkeypatch)
    read, params = metrics.load("m", ENTRY)
    ctx = {"vars0": {"executor": {"queries": 3}},
           "vars1": {"executor": {"queries": 10}}}
    assert read(ctx, params) == 7


@pytest.mark.parametrize("over", [{"reader": "no_such_reader"},
                                  {"unit": "ms"}, {"moves": "setup_s"},
                                  {"layer": "Kernels"},
                                  {"source": "host_clock"}])
def test_a_misfit_fails_loudly(tmp_path, monkeypatch, over):
    _write(tmp_path, monkeypatch, **over)
    with pytest.raises(metrics.MetricError):
        metrics.load("m", ENTRY)


def test_a_missing_file_fails_loudly(tmp_path, monkeypatch):
    monkeypatch.setattr(metrics, "HERE", str(tmp_path))
    with pytest.raises(metrics.MetricError):
        metrics.load("m", ENTRY)


def test_every_metric_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        metrics.load(m["name"], m)
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)


def test_readers_without_code():
    ctx = {"vars0": {"colcache": {"hits": 10, "misses": 10}},
           "vars1": {"colcache": {"hits": 40, "misses": 20}, "client":
                     {"completed": 5}},
           "dev1": {"devices": [{"memory_stats": {"peak_bytes_in_use": 7}},
                                {"memory_stats": None}]}}
    assert metrics.vars_ratio(ctx, {"num": ["colcache/hits"], "den": [
        "colcache/hits", "colcache/misses"], "scale": 100}) == 75.0
    assert metrics.vars_ratio(ctx, {"num": ["x/y"], "den": ["x/z"]}) is None
    assert metrics.vars_delta(ctx, {"counters": ["colcache/evictions"]}) == 0
    assert metrics.device_field(
        ctx, {"field": "memory_stats/peak_bytes_in_use"}) == 7


def test_planner_ring_counts_only_the_window():
    geo = "(1, 2)"
    ring = [  # newest first, as /debug/device gives it
        {"kernel": "k", "geometry": geo, "route": "host", "uses": 6},
        {"kernel": "k", "geometry": geo, "route": "device", "uses": 5},
        {"kernel": "k", "geometry": geo, "route": "device", "uses": 4},
        {"kernel": "k", "geometry": geo, "route": "host", "uses": 3},
    ]
    # the window's are as many of the newest as `decisions_total` grew by
    ctx = {"dev0": {"planner": {"counters": {"decisions_total": 3}}},
           "dev1": {"planner": {"decisions": ring,
                                "counters": {"decisions_total": 6}}}}
    assert metrics.planner_ring(ctx, {"stat": "flips"}) == 1
    assert metrics.planner_ring(ctx, {"stat": "host_share"}) == \
        pytest.approx(100 / 3)
    # a frozen planner counts and writes its ring, and moves no `uses`
    for d in ring:
        d["uses"] = 3
    assert metrics.planner_ring(ctx, {"stat": "flips"}) == 1
    ctx["dev1"]["planner"]["counters"]["decisions_total"] = 3
    assert metrics.planner_ring(ctx, {"stat": "flips"}) is None
    # a program that has decided nothing has no counter yet
    ctx["dev0"]["planner"]["counters"] = ctx["dev1"]["planner"]["counters"] = {}
    assert metrics.planner_ring(ctx, {"stat": "host_share"}) is None

"""`idle_named_share` on hand-made gap lists: the share of idle time whose
label is a span of the program, and nothing where there is nothing to
read (the parent commit's captures hold no `ogt:` span)."""

import json
import os

import pytest

from harness import metrics, trace_reduce as tr

from conftest import ROOT

ENTRY = {"name": "idle_named_share", "unit": "%", "source": "device_trace",
         "layer": "Device runtime", "moves": "scan_points_per_s"}


def read(ctx):
    fn, params = metrics.load("idle_named_share", ENTRY)
    return fn(ctx, params)


def test_the_share_is_by_time_not_by_count():
    gaps = [["python3:ogt:layout_build", 3.0], ["python3:ogt:render", 1.0],
            ["tf_XLATfrtCpuClient:ThreadPool wait", 0.5],
            ["(no host event)", 0.5]]
    assert read({"trace": {"idle_gaps": gaps}}) == pytest.approx(80.0)


@pytest.mark.parametrize("ctx", [
    {"trace": None}, {}, {"trace": {"idle_gaps": []}},
    # the parent commit: frames of the python tracer, no span to find
    {"trace": {"idle_gaps": [["python3:qhelpers.py:870 _eval", 12.0],
                             ["python3:_unknown__poll", 0.9]]}}])
def test_nothing_to_read(ctx):
    assert read(ctx) is None


def test_on_the_reductions_own_labels():
    """From planes to the share: the rule that names a gap picks the
    innermost span covering half of it, so a gap inside `ogt:layout_build`
    inside `ogt:device_compute` reads as the inner one."""
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_basic(1)", 100, 10),
                                            ("jit_basic(1)", 900, 10)]},
        {"name": "XLA Ops", "events": [("fusion", 100, 10),
                                        ("fusion", 900, 10)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("ogt:http_query", 0, 1000), ("ogt:device_compute", 105, 800),
        ("ogt:layout_build", 120, 700)]}]}
    red = tr.reduce_planes([dev, host])
    labels = [label for label, _ in red["idle_gaps"]]
    assert labels[0] == "python3:ogt:layout_build"      # 110..900
    assert set(labels) == {"python3:ogt:layout_build",
                           "python3:ogt:http_query"}
    assert read({"trace": red}) == pytest.approx(100.0)


def test_the_entry_is_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        [m] = [m for m in json.load(f)["per_layer"]
               if m["name"] == "idle_named_share"]
    assert m["workloads"] == ["tsbs_fleet_groupby", "prom_rate_range"]
    assert {k: m[k] for k in ENTRY} == ENTRY

"""`readers/tail.py` over a recorded `/debug/vars` pair and over the same
pair with a stall put into it by hand.

`recorded/tail_vars_quiet.json`: `vars0`/`vars1` of a quiet traced run of
`tsbs_dash_refresh` on the chip (its `how` member says which), cut to the
groups the readers look at.  The stalled pair is that one with 2 s of
waiting for the device added to 4 of its requests — to the counters every
span of those requests adds to, and to their records, which the wait makes
the tail's slowest — as the ledger's PR 38 parent line held one."""

import copy
import json
import os

import pytest

from harness import metrics

from conftest import BENCH, ROOT

STALL_NS, STALLED = 2_000_000_000, 4
PANELS = ("slowest_req_server_ms", "tail_ms_per_q", "tail_gc_share",
          "tail_stalled_share", "tail_device_wait_share", "tail_offcpu_share",
          "device_compute_trim_ms_per_q", "scan_trim_ms_per_q",
          "device_wait_ms_per_q", "device_copy_ms_per_q",
          "pulse_late_max_ms", "run_delay_ms_in_window",
          "device_compute_ms_per_q", "scan_ms_per_q", "device_fetch_ms_per_q")


def _entries() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def _read(ctx: dict, name: str):
    fn, params = metrics.load(name, _entries()[name])
    return fn(ctx, params)


def _panels(ctx: dict) -> dict:
    return {n: _read(ctx, n + ".panels") for n in PANELS}


@pytest.fixture(scope="module")
def quiet() -> dict:
    with open(os.path.join(BENCH, "selftest", "recorded",
                           "tail_vars_quiet.json")) as f:
        doc = json.load(f)
    return {"vars0": doc["vars0"], "vars1": doc["vars1"]}


def with_stall(ctx: dict) -> dict:
    """`STALLED` of the window's requests waited `STALL_NS` longer for the
    device: inside `device_wait`, so inside `device_fetch`, `device_compute`,
    `select: cpu` and the root, and off the CPU."""
    out = copy.deepcopy(ctx)
    v1 = out["vars1"]
    for name in ("device_wait", "device_fetch", "device_compute",
                 "select: cpu"):
        v1["query_stages"][name + "_ns"] += STALLED * STALL_NS
    v1["query_stages"]["device_wait_self_ns"] += STALLED * STALL_NS
    for name in ("query_ns", "query_offcpu_ns"):
        v1["http"][name] += STALLED * STALL_NS
    tail = v1["tail"]["query"]
    # the four that waited: requests of the window the tail did not hold,
    # each with the stage times of such a request (the counters' delta less
    # the tail's, over the requests outside the tail) and the wait on top
    done = v1["client"]["completed"] - out["vars0"]["client"]["completed"]
    model = copy.deepcopy(tail[-1])
    for name, m in model["stages"].items():
        held = sum(r["stages"].get(name, (0,))[0] for r in tail)
        whole = ctx["vars1"]["query_stages"].get(name + "_ns", 0) \
            - ctx["vars0"]["query_stages"].get(name + "_ns", 0)
        m[0] = m[1] = max(whole - held, 0) // (done - len(tail))
    model["ns"] = model["stages"]["select: cpu"][0] + 2_000_000
    model["offcpu_ns"] = model["ns"] // 4
    model["gc_ns"] = model["stalled_ns"] = 0
    for i in range(STALLED):
        rec = copy.deepcopy(model)
        rec["seq"] = tail[0]["seq"] + 1 + i
        rec["ns"] += STALL_NS
        rec["offcpu_ns"] += STALL_NS
        for name in ("device_wait", "device_fetch", "device_compute",
                     "select: cpu"):
            rec["stages"][name][0] += STALL_NS
        rec["stages"]["device_wait"][1] += STALL_NS
        tail.append(rec)
    tail.sort(key=lambda r: -r["ns"])
    del tail[16:]                       # the tail keeps its sixteen slowest
    return out


def test_the_recorded_window_is_quiet(quiet):
    m = _panels(quiet)
    assert m["tail_stalled_share"] == 0
    assert m["pulse_late_max_ms"] < 150
    assert m["device_compute_trim_ms_per_q"] == pytest.approx(
        m["device_compute_ms_per_q"], rel=0.05)
    # not so `scan` in this cell: one statement in five pays the memtable's
    # consolidation (`mem_read`, 65-70 ms by the window's end), and those
    # are the window's sixteen slowest requests, stall or no stall
    assert 0.5 * m["scan_ms_per_q"] < m["scan_trim_ms_per_q"] \
        < m["scan_ms_per_q"]
    # the two halves of a fetch are all of it but its own few microseconds
    assert m["device_wait_ms_per_q"] + m["device_copy_ms_per_q"] \
        == pytest.approx(m["device_fetch_ms_per_q"], abs=0.2)
    assert 0 < m["tail_ms_per_q"] < 16 * m["slowest_req_server_ms"]
    assert 0 <= m["tail_gc_share"] <= 100 and m["run_delay_ms_in_window"] >= 0
    assert 0 < m["tail_device_wait_share"] < m["tail_offcpu_share"] <= 100


def test_a_stall_moves_the_mean_and_not_the_trimmed_mean(quiet):
    q, s = _panels(quiet), _panels(with_stall(quiet))
    done = quiet["vars1"]["client"]["completed"]
    assert s["device_compute_ms_per_q"] == pytest.approx(
        q["device_compute_ms_per_q"] + STALLED * STALL_NS / 1e6 / done)
    assert s["device_compute_ms_per_q"] > 5 * s["device_compute_trim_ms_per_q"]
    assert s["device_compute_trim_ms_per_q"] == pytest.approx(
        q["device_compute_trim_ms_per_q"], rel=0.05)
    # the four push four consolidating statements out of the tail, whose
    # 65 ms of `scan` each go back among the rest
    assert s["scan_trim_ms_per_q"] == pytest.approx(
        q["scan_trim_ms_per_q"], rel=0.2)
    assert s["slowest_req_server_ms"] >= STALL_NS / 1e6
    assert s["tail_ms_per_q"] > q["tail_ms_per_q"] + 0.9 * STALLED * 2e3 / done
    # where the stalled requests were: waiting for the device, off the
    # CPU, the pulse on time and no collection running
    assert s["tail_device_wait_share"] > 80
    assert s["tail_offcpu_share"] > 80
    assert s["tail_stalled_share"] == 0 and s["tail_gc_share"] < 5


def test_a_record_from_before_the_window_does_not_count(quiet):
    ctx = copy.deepcopy(quiet)
    slowest = max(ctx["vars1"]["tail"]["query"], key=lambda r: r["ns"])
    before = _read(ctx, "slowest_req_server_ms.panels")
    assert before == slowest["ns"] / 1e6
    slowest["seq"] = int(ctx["vars0"]["http"]["query_count"])    # closed by then
    assert _read(ctx, "slowest_req_server_ms.panels") < before
    # a plain name merges the routes it is given; this window has one
    assert _read(quiet, "slowest_req_server_ms") == before


def test_a_program_without_the_account_reads_nothing(quiet):
    """The parent commit under this PR's files: no `tail`, no pulse, no
    `device_wait`: the tail's metrics are left out, the counter ones read 0."""
    ctx = copy.deepcopy(quiet)
    for v in (ctx["vars0"], ctx["vars1"]):
        v.pop("tail"), v.pop("stalls", None)
        v["runtime"] = {k: n for k, n in v["runtime"].items()
                        if k.startswith("gc_")}
        v["query_stages"] = {k: n for k, n in v["query_stages"].items()
                             if not k.startswith(("device_wait",
                                                  "device_copy"))}
    m = _panels(ctx)
    for name in ("slowest_req_server_ms", "tail_ms_per_q", "tail_gc_share",
                 "tail_stalled_share", "tail_device_wait_share",
                 "tail_offcpu_share", "device_compute_trim_ms_per_q",
                 "scan_trim_ms_per_q", "pulse_late_max_ms"):
        assert m[name] is None, name
    for name in ("device_wait_ms_per_q", "device_copy_ms_per_q",
                 "run_delay_ms_in_window"):
        assert m[name] == 0, name
    assert m["device_compute_ms_per_q"] > 0


def test_the_entries_name_their_cells():
    entries = _entries()
    for name in PANELS[:12]:
        assert entries[name + ".panels"]["workloads"] == [
            "tsbs_host_panels", "tsbs_dash_refresh"]
        if "trim" not in name:
            assert entries[name]["workloads"] == [
                "tsbs_fleet_groupby", "prom_rate_range",
                "tsbs_fleet_groupby_cold"]
    for name in ("pulse_late_max_ms.load", "run_delay_ms_in_window.load"):
        assert entries[name]["workloads"] == ["tsbs_load"]
        assert entries[name]["moves"] == "ingest_rows_per_s"

"""The mixed cell's three members (PR 31), at sizes a test can hold: fixed
`panels` asked again every `refresh_s`, a `range_end` that follows the
newest tick acknowledged in full, and an `ingest` beside them, with an
oracle that grows by the live ticks and answers windows the range cuts.
`traffic/dash_refresh.json` is the cell as data (PR 31's draft of it went in
PR 41); a whole run of it is correct and is answered from the result cache (`test_broken_path.py` holds its controls)."""

import json
import os
import time

import numpy as np
import pytest

from harness import traffic
from harness.server import BenchFailure
from tools import draft

from conftest import BENCH
from test_oracles import reference


def mix(**over):
    with open(os.path.join(BENCH, "traffic", "dash_refresh.json")) as f:
        t = json.load(f)
    t.update(t.pop("dry_run"))
    t.update(over)
    return t


def ref_for(seed=5, **over):
    mod, cfg = reference("tsbs-devops-cpu-4000", **over)
    return mod.Reference(cfg, seed)


# -- (a) panels ----------------------------------------------------------------


def test_the_panels_and_when_each_is_due_come_from_the_seed_alone():
    t = mix(panels={"count": 6, "refresh_s": 2.0})
    a = traffic.build(t, ref_for(), 11, 8.0)
    b = traffic.build(t, ref_for(), 11, 8.0)
    c = traffic.build(t, ref_for(), 12, 8.0)

    def asked(plan):
        return [(q.stmt["panel"], q.stmt["unbound"]) for q in plan.requests]
    assert asked(a) == asked(b) != asked(c)
    assert [p for p, _ in asked(a)] == [i % 6 for i in range(24)]
    assert len({repr(u) for _, u in asked(a)}) == 6        # six panels, fixed
    # request j + i*N is due at (j + i*N) * R/N: every panel again each R,
    # window / refresh_s times in all
    due = traffic.arrival_times(a.loop, 8.0)
    assert np.allclose(due, np.arange(24) * 2.0 / 6) and a.loop["rate_qps"] == 3
    per_panel = np.bincount([p for p, _ in asked(a)])
    assert list(per_panel) == [4] * 6 == [int(8.0 / 2.0)] * 6
    for j in range(6):
        assert np.allclose(np.diff(due[j::6]), 2.0)
    # the warm repeats are the same panels, and so is the traced phase's rest
    assert [q.stmt["panel"] for q in a.warm_repeat[:12]] == list(range(6)) * 2
    a.results = [None] * 20
    assert [q.stmt["panel"] for q in traffic.rest(a).requests] == [2, 3, 4, 5]


def test_without_a_trailing_range_a_panel_is_one_statement_asked_again():
    t = mix()
    del t["range_end"], t["ingest"]
    plan = traffic.build(t, ref_for(), 3, 4.0)
    assert plan.bind is None
    assert len({q.path for q in plan.requests}) == 6
    assert plan.requests[0] is plan.requests[6] is plan.warm_repeat[0]
    # and traffic without `panels` still never repeats a statement
    del t["panels"]
    t["loop"]["rate_qps"], t["loop"]["arrival_seed"] = 6, 1
    plan = traffic.build(t, ref_for(), 3, 4.0)
    assert len({q.path for q in plan.requests}) == len(plan.requests) == 24


@pytest.mark.parametrize("over, says", [
    ({"loop": {"kind": "open", "workers": 4, "rate_qps": 5}}, "rate_qps"),
    ({"loop": {"kind": "closed", "clients": 1}}, "open loop"),
    ({"range_end": "now"}, "newest_acked"),
    ({"range_walk": "cycle"}, "without range_walk"),
    ({"range_s": 7200}, "at most span_s"),
    ({"range_s": 3605}, "multiple of interval_s"),
    ({"ingest": {"batch_rows": 24, "rows_per_s": 48, "clients": 2}},
     "one client"),
    ({"kind": "promql_range"}, "influxql_template"),
])
def test_a_file_that_does_not_fit_is_refused_before_a_server_starts(over,
                                                                    says):
    with pytest.raises(ValueError, match=says):
        traffic.check(mix(**over), ref_for().cfg)


# -- (b) the trailing range and the cut windows --------------------------------


def brute(ref, stmt):
    """`want` one row at a time: every (tick, host) row whose time lies in
    [t0, t1) goes to the window its own time falls in."""
    every = stmt["every_s"]
    first = stmt["t0"] // every
    cols = [ref.field_names.index(f) for f in stmt["fields"]]
    hosts = stmt["hosts"] if stmt["hosts"] is not None else range(ref.hosts)
    seen: dict[tuple, list] = {}
    for tick in range(ref.ticks):
        ts = ref.start_s + tick * ref.interval_s
        if not stmt["t0"] <= ts < stmt["t1"]:
            continue
        for g, h in enumerate(hosts):
            key = (ts // every - first, g if stmt["group_by_host"] else 0)
            seen.setdefault(key, []).append(
                [ref.hundredths[tick, h, c] / 100.0 for c in cols])
    nw = max(w for w, _ in seen) + 1
    ng = max(g for _, g in seen) + 1
    out = np.full((nw, ng, len(cols)), np.nan)
    for (w, g), rows in seen.items():
        rows = np.array(rows)
        out[w, g] = rows.mean(axis=0) if stmt["agg"] == "mean" \
            else rows.max(axis=0)
    return out


@pytest.mark.parametrize("cut_s", [0, 10, 30, 50])
@pytest.mark.parametrize("agg, by_host, hosts", [
    ("max", False, [1, 4, 9, 23]), ("mean", True, None),
    ("mean", False, [0, 2]), ("max", True, [3, 5, 8])])
def test_the_oracle_over_cut_windows_is_the_per_row_loop(cut_s, agg, by_host,
                                                         hosts):
    ref = ref_for(span_s=1200)
    t1 = ref.start_s + 900 + cut_s
    stmt = {"agg": agg, "fields": ["usage_idle", "usage_user"], "every_s": 60,
            "t0": t1 - 600, "t1": t1, "hosts": hosts, "group_by_host": by_host}
    want = ref.want(stmt)
    assert want.shape[0] == (10 if cut_s == 0 else 11)
    np.testing.assert_allclose(want, brute(ref, stmt), rtol=1e-13)
    # the served answer's shape: a cut window is reported at its aligned start
    starts, _ = ref.window_ticks(stmt)
    assert starts[0] == stmt["t0"] // 60 * 60 and starts[0] <= stmt["t0"]
    ng = want.shape[1]
    doc = {"results": [{"series": [
        {"tags": {"hostname": f"host_{h}"},
         "values": [[int(s) * 10**9] + list(want[w, g])
                    for w, s in enumerate(starts)]}
        for g, h in enumerate(hosts if hosts is not None and by_host
                              else range(ng))]}]}
    assert np.array_equal(ref.parse(stmt, doc), want)


class Acks:
    """A server that acknowledges every /write but those it is told to
    refuse, and counts them."""
    refuse: set = set()

    def __init__(self, port):
        self.meanwhile, self.n = None, 0

    def request(self, method, path, body):
        assert body and path.startswith("/write")
        if self.meanwhile is not None:
            self.meanwhile()
        self.n += 1
        return (503 if self.n in self.refuse else 204), b""

    def close(self):
        pass


def run_ingest(ref, monkeypatch, spec, until, refuse=()):
    monkeypatch.setattr(traffic, "Client", Acks)
    monkeypatch.setattr(Acks, "refuse", set(refuse))
    ing = traffic.Ingest(spec, ref)
    ing.start(0)
    deadline = time.monotonic() + 20
    while len(ing.results) < until and time.monotonic() < deadline:
        time.sleep(0.005)
    ing.stop()
    assert not ing.thread.is_alive() and len(ing.results) >= until
    return ing


def test_a_statement_ends_at_the_newest_tick_acknowledged_in_full(monkeypatch):
    ref = ref_for()
    plan = traffic.build(mix(), ref, 5, 4.0)
    end = ref.start_s + 3600
    q = traffic.bound(plan, plan.requests[0])
    assert (q.stmt["t0"], q.stmt["t1"]) == (end - 3600, end)
    assert q.stmt["windows"] == 60 and q.stmt["panel"] == 0
    assert traffic.bound(plan, q) is q                      # made once
    # 36 rows a batch of 24 hosts: a tick and a half; batch 7 is refused
    ing = run_ingest(ref, monkeypatch, {"batch_rows": 36, "rows_per_s": 3600,
                                        "clients": 1}, until=10, refuse={7})
    assert [r.ok for r in ing.results[:8]] == [True] * 6 + [False, True]
    # six batches in full, 216 rows, nine ticks: it stands still from the gap
    assert ref.acked_ticks == 360 + 9 and ref.ticks >= 360 + 15
    assert ing.rows(ing.sent()) == 36 * (len(ing.results) - 1)
    q = traffic.bound(plan, plan.requests[1])
    assert q.stmt["t1"] == end + 90 and q.stmt["t0"] == end + 90 - 3600
    assert q.stmt["windows"] == 61                          # both ends cut
    assert q.units == 5 * 8 * 360 and q.stmt["marker_count"] == 1
    assert f"time >= {q.stmt['t0']}s AND time < {q.stmt['t1']}s" in q.stmt["q"]
    # paced: batch k was due k * batch_rows / rows_per_s after the first
    due = np.array([r.due for r in ing.results])
    assert np.allclose(np.diff(due), 0.01) and all(
        r.sent >= r.due for r in ing.results)
    # and the oracle holds the live ticks the range now covers
    assert ref.want(q.stmt).shape == (61, 1, 5)


def test_no_ingest_nothing_starts_and_the_range_stands_still():
    ref = ref_for()
    ing = traffic.Ingest(None, ref)
    ing.start(0)
    assert ing.thread is None and ing.sent() == [] and ing.rows([]) == 0
    ing.stop()
    assert ref.acked_ticks == ref.ticks == 360


# -- (c) stored, then live: one walk -------------------------------------------


@pytest.mark.parametrize("batch_rows", [24, 36, 100])
def test_stored_then_streamed_is_the_longer_span_s_walk(batch_rows):
    short, long = ref_for(seed=9), ref_for(seed=9, span_s=3600 + 250)
    assert short.keys == ref_for(seed=9).keys
    stream = short.stream_requests(batch_rows, live=True)
    bodies = [next(stream) for _ in range(-(-25 * 24 // batch_rows))]
    assert short.ticks >= 385 and short.rows == 360 * 24
    short.want({"agg": "max", "fields": ["usage_user"], "every_s": 60,
                "t0": short.start_s, "t1": short.start_s + 60, "hosts": None,
                "group_by_host": False})                    # joins the live
    assert np.array_equal(short.hundredths[:385], long.hundredths)
    assert np.array_equal(short.values[:385], long.values)
    # the rows sent are those ticks, in the loader's order, at their times
    from test_stream import lines_of
    sent = b"".join(b for b, _ in bodies)
    want = b"".join(lines_of(short, short.hundredths[360:], 360 * 24))
    assert sent == want[:len(sent)] and len(sent) >= 25 * 24 * 100
    # what a stored reference streams without `live` is unchanged: its rows
    again = ref_for(seed=9)
    assert sum(n for _, n in again.stream_requests(100)) == again.rows


# -- (d) a whole run -----------------------------------------------------------


STREAM = ("CREATE STREAM cpu_1m ON SELECT max(usage_user) INTO cpu_1m FROM cpu "
          "GROUP BY time(1m), hostname")


def test_a_whole_draft_run_is_correct_and_answered_from_the_cache():
    c = draft.cell(seed=2147483659, seconds=3.0, trace=0, dry=True)
    c.cfg["setup_q"] = [STREAM]          # the deployment's, before the load
    try:
        out = c.run()
    finally:
        c.stop()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 18 and out["metrics"] == {}
    row = draft.numbers(c, out)
    v0, v1 = c.ctx["vars0"], c.ctx["vars1"]
    reused = v1["executor"]["inc_cache_windows_reused"] \
        - v0["executor"]["inc_cache_windows_reused"]
    assert reused > 0 and 50 < row["resultcache_reuse_share"] <= 100
    # the writes went on beside the reads, and every one of them is counted
    assert row["rows_written"] >= 4 * 24 and row["write_late_p95_ms"] < 100
    assert row["checks"]["rows_acked_not_read_back"] == [0, 0]
    assert row["checks"]["selector_rel_err"][0] <= 2e-7
    assert c.ingest.ref.acked_ticks > 360                  # the range moved
    # the stream of `setup_q` stands: every write was offered to an observer
    assert v1["write"]["observer_rows_offered"] >= 360 * 24 + row["rows_written"]


def test_a_setup_statement_the_server_refuses_ends_the_run():
    c = draft.cell(seed=1, seconds=1.0, trace=0, dry=True)
    c.cfg["setup_q"] = ["CREATE STREAM bad ON SELECT percentile(usage_user, "
                        "99) INTO x FROM cpu GROUP BY time(1m)"]
    try:
        with pytest.raises(BenchFailure, match="setup_q"):
            c.run()
    finally:
        c.stop()

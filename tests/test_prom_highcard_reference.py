"""The high-cardinality tenant (`benchmark/configs/prom-highcard-gauges.json`,
cell `prom_topk_highcard`), small, on the CPU, through the served /write and
/api/v1/query_range paths: 6,000 series of one gauge x 8 scrapes (over the
4,096 series at which the lazy-label aggregation path takes a statement),
then the cell's own statement, built by the benchmark's generator from
`benchmark/traffic/topk_highcard.json`, held to the plain reference
`benchmark/configs/prom_highcard.py` at the configuration's own limits with
x64 off, as a server has it: the set of series and the steps at which each
holds a value exact, every value within 2e-7.  Beside `topk(10, ..)`:
`bottomk(3, ..)`, `count_values("v", ..)` over a gauge of 7 distinct values,
and a matcher that leaves 4,500 series.

And what PR 49 added to the program: the lazy-label path's spans (the eager
path's by name, and `prom_select`, `prom_labels`), its counters in group
`prom` with a fallback counted by its reason, the instant selection as one
named program in the device's dtype by statement, and two repairs of an
ungrouped InfluxQL aggregate over very many series (the set-up's count())."""

import contextlib
import json
import os
import subprocess
import sys
import time
import urllib.parse
import urllib.request

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import load_module, metrics, traffic  # noqa: E402
from harness.oracle import TOL, to_bf16  # noqa: E402

from opengemini_tpu.ops import prom as promops  # noqa: E402
from opengemini_tpu.promql import engine as promengine  # noqa: E402
from opengemini_tpu.query import executor as qexec  # noqa: E402
from opengemini_tpu.server.http import HttpService  # noqa: E402
from opengemini_tpu.storage.engine import Engine  # noqa: E402
from opengemini_tpu.utils import tracing  # noqa: E402

CELL = "prom_topk_highcard"
SERIES, SEED, NS = 6000, 42, 10**9
M = "container_memory_working_set_bytes"
STATEMENTS = {
    "topk": f"topk(10, {M})",
    "bottomk": f"bottomk(3, {M})",
    "matcher": f'topk(10, {M}{{container!="init-config"}})',
}
COUNT_VALUES = f'count_values("v", {M})'
# the three per_layer entries CHANGES.md (PR 49) proposes for the
# `benchmark` PR that makes room in the list
PROPOSED = [
    {"name": "prom_select_ms_per_q", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "Kernels",
     "moves": "scan_points_per_s", "workloads": [CELL]},
    {"name": "prom_labels_ms_per_q", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "HTTP front end",
     "moves": "scan_points_per_s", "workloads": [CELL]},
    {"name": "prom_fast_agg_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "Plan",
     "moves": "scan_points_per_s", "workloads": [CELL]},
]


def _json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def cell_files() -> tuple[dict, dict]:
    """The cell's configuration, at its dry-run size, and its traffic file,
    found as run.py finds them."""
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _json(ROOT, conf["file"])
    return ({**cfg, **cfg["dry_run"]},
            _json(BENCH, "traffic", cell["traffic"] + ".json"))


def reference(cfg: dict, query: str | None = None, seed: int = SEED):
    mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                      "reference")
    if query is not None:
        cfg = {**cfg, "statement": {**cfg["statement"], "query": query}}
    return mod.Reference(cfg, seed)


@contextlib.contextmanager
def as_served():
    """x64 off, process-wide while a request is served (the handler's thread
    is not this one): float32 on the device, as a server computes."""
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", x64)


class Served:
    """One server over one store with the tenant's gauges loaded, and the
    reference that made them."""

    def __init__(self, path, query=None, vals=None, **over):
        cfg, self.mix = cell_files()
        cfg.update(over)
        self.cfg = cfg
        self.ref = ref = reference(cfg, query)
        if vals is not None:
            ref.vals = vals(ref)
            ref.series = ref.answer_series()
        assert (ref.stored_series, ref.ticks, ref.rows) == (
            SERIES, 8, 8 * SERIES)
        self.engine = Engine(str(path))
        self.engine.create_database(ref.db)
        if "shard_s" in over:
            self.engine.create_retention_policy(
                ref.db, "short", 0, over["shard_s"] * NS, default=True)
        self.svc = HttpService(self.engine, "127.0.0.1", 0)
        self.svc.start()
        for body, rows in ref.load_requests():
            assert rows == 8 * cfg["load_block"]["series"]
            assert self.http("POST", "/write", body, db=ref.db)[0] == 204
        self.http("POST", "/debug/ctrl", mod="flush")

    def sibling(self, query: str):
        """The reference of another statement over the same stored gauges:
        at this size and seed no statement's near-tie rule re-draws any."""
        ref = reference(self.cfg, query)
        assert ref.redrawn == self.ref.redrawn == 0
        assert np.array_equal(ref.vals, self.ref.vals)
        return ref

    def request(self, ref, query: str | None = None):
        """The statement as the benchmark's generator builds it from the
        cell's traffic file."""
        mix = self.mix if query is None else {**self.mix, "query": query}
        return traffic.build(mix, ref, SEED, 1.0).requests[0]

    def http(self, method, path, body=None, **params):
        url = f"http://127.0.0.1:{self.svc.port}{path}"
        if params:
            url += ("&" if "?" in path else "?") + urllib.parse.urlencode(
                params)
        req = urllib.request.Request(url, data=body, method=method)
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, r.read()

    def ask(self, req) -> bytes:
        with as_served():
            status, body = self.http(req.method, req.path)
        assert status == 200
        return body

    def vars(self) -> dict:
        return json.loads(self.http("GET", "/debug/vars")[1])

    def close(self):
        self.svc.stop()
        self.engine.close()


def _served(tmp_path_factory, name: str, **kw):
    srv = Served(tmp_path_factory.mktemp(name), **kw)
    yield srv
    srv.close()


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    yield from _served(tmp_path_factory, "fleet")


@pytest.fixture(scope="module")
def restarts(tmp_path_factory):
    """The same series holding a gauge of 7 distinct values (a restart
    count), which is what `count_values` is sensible of."""
    def seven(ref):
        rng = np.random.default_rng(SEED + 1)
        return rng.integers(0, 7, size=ref.vals.shape)

    yield from _served(tmp_path_factory, "restarts", query=COUNT_VALUES,
                       vals=seven)


@pytest.fixture(scope="module")
def two_shards(tmp_path_factory):
    """The same gauges, their first four scrapes in one shard group of an
    hour and the last four in the next."""
    start = _json(BENCH, "configs", "prom-highcard-gauges.json")["start_s"]
    yield from _served(tmp_path_factory, "two_shards", shard_s=3600,
                       start_s=start + 3600 - 60)


def case(request, which: str):
    """(server, reference, request) of one of the four statements."""
    if which == "count_values":
        srv = request.getfixturevalue("restarts")
        return srv, srv.ref, srv.request(srv.ref, COUNT_VALUES)
    srv = request.getfixturevalue("fleet")
    if which == "topk":
        return srv, srv.ref, srv.request(srv.ref)
    ref = srv.sibling(STATEMENTS[which])
    return srv, ref, srv.request(ref, STATEMENTS[which])


ALL = ["topk", "bottomk", "count_values", "matcher"]


# -- the files ----------------------------------------------------------------


def test_the_files_state_the_deployment_and_the_cell():
    bench = _json(ROOT, "BENCHMARK.json")
    full = _json(BENCH, "configs", "prom-highcard-gauges.json")
    cfg, mix = cell_files()
    conf = next(c for c in bench["configs"]
                if c["name"] == "prom-highcard-gauges")
    assert conf["source"] == full["source"] and len(conf["source"]) <= 200
    assert conf["reduced"] == full["reduced"] == ["span_s"]
    assert full["reduced_why"].keys() == {"span_s"}
    assert full["stored_series"] == 1_000_000 == (
        full["nodes"] * full["pods_per_node"] * full["containers_per_pod"])
    assert cfg["stored_series"] == SERIES == (
        cfg["nodes"] * cfg["pods_per_node"] * cfg["containers_per_pod"])
    assert SERIES > promengine.FAST_AGG_MIN_SERIES
    assert full["span_s"] // full["scrape_s"] == 8
    # the statement is stated twice, and the reference refuses a difference
    assert full["statement"] == {k: mix[k]
                                 for k in ("query", "range_s", "step_s")}
    assert mix["query"] == STATEMENTS["topk"]
    # no guarantee is weaker than the day cell's
    day = _json(BENCH, "configs", "prom-counters-24h.json")["guarantees"]
    assert full["guarantees"]["acked_rows_read_back"] \
        == day["acked_rows_read_back"]
    assert full["guarantees"]["step_times_and_series_set"].startswith("exact")
    assert "2e-7" in full["guarantees"]["selector"] \
        and TOL["selector"] == 2e-7 < TOL["rate"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "scan_points_per_s")["workloads"]
    work = mix["device_work"]
    assert work["launch_program"] == "jit_" + promops.prom_instant.__name__
    assert "needs" not in work and "planner_kernel" not in work


def test_a_checkout_without_the_timed_program_is_refused_at_once(tmp_path):
    """The driver tries a new cell on the parent's program under this PR's
    benchmark files, and a parent that is killed there refuses the PR.  That
    program can answer the statement, minutes after a run's time is over:
    the reference refuses it with the reason instead (a ValueError ends
    `run.py` with exit code 1 and stops the server).  What it looks for is
    the name the traffic file already gives the harness, anywhere in the
    program's sources."""
    mod = load_module(os.path.join(BENCH, "configs", "prom_highcard.py"),
                      "reference")
    cfg, mix = cell_files()
    need = cfg["timed_program"]
    assert mix["device_work"]["launch_program"] == "jit_" + need["jit"]
    assert need["jit"] == promops.prom_instant.__name__
    mod.require_program(need)                     # this checkout has it
    with pytest.raises(ValueError, match="no source under opengemini_tpu/"):
        mod.require_program(need, str(tmp_path))  # no program at all
    src = tmp_path / "opengemini_tpu" / "ops"
    src.mkdir(parents=True)
    (src / "prom.py").write_text("def instant_values(times, values):\n"
                                 "    return prom_instant_like\n")
    with pytest.raises(ValueError, match="has no jit_prom_instant"):
        mod.require_program(need, str(tmp_path))
    # the program may keep it in any file, under any kind of definition
    (src / "moved.py").write_text("prom_instant = jax.jit(select)\n")
    mod.require_program(need, str(tmp_path))


# -- the answers --------------------------------------------------------------


@pytest.mark.parametrize("which", ALL)
def test_each_statement_is_the_references(which, request):
    srv, ref, req = case(request, which)
    assert req.stmt["windows"] == 5
    assert req.units == ref.points(req.stmt) == 8 * len(ref._matched(req.stmt))
    before = srv.vars().get("prom", {})
    got = ref.parse(req.stmt, json.loads(srv.ask(req)))
    numbers = ref.numbers(req.stmt, got)    # raises on another series set
    assert numbers.keys() == {"value_rel_err"}
    assert numbers["value_rel_err"][0] <= numbers["value_rel_err"][1] \
        == TOL["selector"]
    after = srv.vars()["prom"]
    assert after["fast_agg_queries"] - before.get("fast_agg_queries", 0) == 1
    if which == "count_values":
        # 6,000 series over 7 values, every step: Prometheus's spelling
        assert sorted(got) == [str(v) for v in range(7)]
        assert all(sum(got[v][t] for v in got) == SERIES
                   for t in got["0"])
    else:
        k = 3 if which == "bottomk" else 10
        steps = sorted({t for pts in got.values() for t in pts})
        assert len(steps) == 5 and k <= len(got) <= 5 * k
        assert all(sum(t in pts for pts in got.values()) == k for t in steps)
        # a float32 rounding is there to be seen: the device computed it
        if which != "bottomk":
            assert numbers["value_rel_err"][0] > 0


@pytest.mark.parametrize("which", ALL)
def test_the_lazy_label_path_equals_the_eager_path(which, request,
                                                   monkeypatch):
    srv, _ref, req = case(request, which)
    fast = srv.ask(req)
    before = srv.vars()["prom"]
    monkeypatch.setattr(promengine.PromEngine, "_collect_runs",
                        lambda self, *a, **k: "few_series")
    eager = srv.ask(req)
    after = srv.vars()["prom"]
    assert after["fast_agg_queries"] == before["fast_agg_queries"]
    assert after["fast_agg_fallbacks"] \
        - before.get("fast_agg_fallbacks", 0) == 1
    assert json.loads(fast) == json.loads(eager)


@pytest.mark.parametrize("which", ["topk", "bottomk", "matcher"])
def test_the_bfloat16_control_fails_the_limit(which, request):
    """The oracle's values narrowed to the precision below float32: a path
    that computed there could not pass."""
    srv, ref, req = case(request, which)
    got = ref.parse(req.stmt, json.loads(srv.ask(req)))
    assert ref.numbers(req.stmt, got)["value_rel_err"][0] <= TOL["selector"]
    value, limit = ref.numbers(req.stmt, got, narrow=to_bf16)["value_rel_err"]
    assert value > 100 * limit


def test_another_series_set_or_other_steps_are_a_mismatch(fleet):
    mod = load_module(os.path.join(BENCH, "configs", "prom_highcard.py"),
                      "reference")
    req = fleet.request(fleet.ref)
    got = fleet.ref.parse(req.stmt, json.loads(fleet.ask(req)))
    first = sorted(got)[0]
    less = {k: v for k, v in got.items() if k != first}
    with pytest.raises(mod.Mismatch, match="1 of the oracle's are missing"):
        fleet.ref.numbers(req.stmt, less)
    moved = {**got, first: {t + 15.0: v for t, v in got[first].items()}}
    with pytest.raises(mod.Mismatch, match="at other steps"):
        fleet.ref.numbers(req.stmt, moved)
    twice = json.loads(fleet.ask(req))
    twice["data"]["result"].append(twice["data"]["result"][0])
    with pytest.raises(mod.Mismatch, match="twice"):
        fleet.ref.parse(req.stmt, twice)


def test_reference_series_is_what_the_harness_counts(fleet, restarts):
    """`traffic.quick_ok` counts `"metric"` in a body against
    `Reference.series`: the series of the answer, not the stored ones."""
    for srv, query in ((fleet, None), (restarts, COUNT_VALUES)):
        req = srv.request(srv.ref, query)
        body = srv.ask(req)
        assert req.stmt["marker_count"] == srv.ref.series
        assert body.count(req.stmt["marker"]) == srv.ref.series
        assert traffic.quick_ok(req, 200, body)
    assert 10 <= fleet.ref.series <= 50 and restarts.ref.series == 7
    assert fleet.ref.stored_series == SERIES


def test_a_traffic_file_with_another_grid_is_refused(fleet):
    with pytest.raises(ValueError, match="is not the configuration's"):
        traffic.build({**fleet.mix, "step_s": 30}, fleet.ref, SEED, 1.0)


# -- the generator ------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_011])
def test_no_step_has_a_near_tie_at_the_tenth_place(seed):
    """At 100,000 series the ten largest of a scrape lie 85 KB apart on
    average, 1e-5 of their level: most seeds have a near tie in some scrape
    before the re-draws, none after."""
    cfg, _ = cell_files()
    cfg.update(nodes=250, stored_series=100_000)
    ref = reference(cfg, seed=seed)
    assert ref.near_ties(10, True, ref._matched(ref._grid(cfg["statement"]))) \
        == []
    top = np.sort(ref.vals, axis=1)[:, -11:].astype(np.float64)
    assert ((top[:, 1] - top[:, 0]) >= 2e-6 * top[:, 1]).all()
    again = reference(cfg, seed=seed)
    assert again.redrawn == ref.redrawn
    assert np.array_equal(again.vals, ref.vals)


def test_a_planted_near_tie_is_redrawn():
    cfg, _ = cell_files()
    ref = reference(cfg)
    rows = np.arange(ref.stored_series)
    order = np.argsort(-ref.vals[3])
    ref.vals[3, order[10]] = ref.vals[3, order[9]] - 1      # 1 byte in 8 GiB
    assert [j for j, _ in ref.near_ties(10, True, rows)] == [3]
    assert ref.settle(10, True, rows, SEED) >= 1
    assert ref.near_ties(10, True, rows) == []


def test_prometheus_spells_a_float_without_an_exponent():
    mod = load_module(os.path.join(BENCH, "configs", "prom_highcard.py"),
                      "reference")
    spelt = {3.0: "3", 0.5: "0.5", 1e21: "1000000000000000000000",
             1e-7: "0.0000001", -2.0: "-2", 8589934592.0: "8589934592",
             float("inf"): "+Inf", float("-inf"): "-Inf", 0.0: "0"}
    assert {v: mod.prom_float(v) for v in spelt} == spelt
    assert mod.prom_float(float("nan")) == "NaN"


# -- one named program, in the device's dtype by statement --------------------


def test_the_instant_selection_is_one_named_program_in_float32():
    rng = np.random.default_rng(5)
    times = np.tile(np.arange(8) * 15.0, (64, 1))
    values = rng.uniform(64 << 20, 8 << 30, size=(64, 8))     # float64
    counts = np.full(64, 8, np.int32)
    rel = np.array([60.0, 75.0, 90.0, 105.0, 120.0])
    with as_served():
        vals, valid = promops.instant_select(times, values, counts, rel, 300.0)
        text = jax.jit(promops.prom_instant).lower(
            times.astype(np.float32), values.astype(np.float32), counts,
            rel.astype(np.float32), np.float32(300.0)).as_text()
    assert "jit_prom_instant" in text
    assert type(vals) is np.ndarray and vals.dtype == np.float32
    assert valid.dtype == bool and valid.all()
    # what comes back is what the host narrowed, selected: not recomputed
    assert np.array_equal(vals, values.astype(np.float32)[:, [4, 5, 6, 7, 7]])
    # with x64 on (these tests' default) nothing is narrowed
    vals64, _ = promops.instant_select(times, values, counts, rel, 300.0)
    assert vals64.dtype == np.float64
    assert np.array_equal(vals64, values[:, [4, 5, 6, 7, 7]])


@pytest.mark.parametrize("width", [8, 32, 33])
def test_short_rows_select_by_comparison_and_long_ones_by_search(
        width, monkeypatch):
    """Two forms of one selection (`ops/prom.py` `instant_values`): rows of
    few samples compare, longer ones search and gather — the cell's rows of
    8, and a row either side of the constant.  Ragged rows, steps before
    the first sample, on a sample and past the lookback."""
    rng = np.random.default_rng(6)
    lens = rng.integers(0, width + 1, size=300)
    lens[:3] = (0, 1, width)
    t_ms = np.concatenate([np.sort(rng.choice(120_000, n, replace=False))
                           for n in lens]).astype(np.int64)
    v_all = rng.normal(size=int(lens.sum()))
    times, values, counts, base_ms = promops.prepare_matrix_runs(
        t_ms, v_all, lens, dtype=np.float64)
    rel = np.concatenate([[-1.0, 0.0, 30.0, 119.999, 500.0],
                          times[2, :3]]) - 0.0
    assert times.shape[1] == width
    assert (width <= promops.INSTANT_COMPARE_MAX_SAMPLES) == (width < 33)
    got = {}
    for form, limit in (("compare", width), ("search", 0)):
        monkeypatch.setattr(promops, "INSTANT_COMPARE_MAX_SAMPLES", limit)
        promops._instant_jit.cache_clear()
        got[form] = promops.instant_select(times, values, counts, rel, 45.0)
    promops._instant_jit.cache_clear()
    by_compare, by_search = got["compare"], got["search"]
    assert np.array_equal(by_compare[1], by_search[1])
    assert 0 < by_compare[1].sum() < by_compare[1].size
    assert not by_compare[1][0].any() and not by_compare[1][:, 0].any()
    assert np.array_equal(by_compare[0][by_compare[1]],
                          by_search[0][by_search[1]])


# -- spans and counters -------------------------------------------------------


def _tree(port: int) -> dict:
    """The newest retained http_prom tree; a root closes after its response
    is sent: wait for it."""
    def get(**params):
        url = f"http://127.0.0.1:{port}/debug/trace?" \
            + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.loads(r.read())

    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        hit = [d for d in get()["recent"] if d["name"] == "http_prom"]
        if hit:
            return get(trace_id=hit[0]["trace_id"])["trace"]["root"]
        time.sleep(0.01)
    raise AssertionError("no http_prom tree was retained")


def _spans(node: dict, parent=None, out=None) -> dict:
    out = {} if out is None else out
    out.setdefault(node["name"], []).append((node, parent))
    for child in node["children"]:
        _spans(child, node, out)
    return out


@pytest.fixture
def traced():
    prev = tracing.trace_enabled()
    tracing.clear_recent()
    tracing.set_trace_enabled(True)
    yield
    tracing.set_trace_enabled(prev)
    tracing.clear_recent()


def test_a_lazy_label_query_opens_the_stages_by_name(fleet, traced):
    req = fleet.request(fleet.ref)
    fleet.ask(req)                        # the program built, the cache filled
    time.sleep(0.2)
    tracing.clear_recent()
    fleet.ask(req)
    spans = _spans(_tree(fleet.svc.port))
    under = {"prom_collect": "http_prom", "prom_prepare": "http_prom",
             "prom_kernel": "http_prom", "prom_select": "http_prom",
             "prom_labels": "http_prom", "prom_render": "http_prom",
             "prom_match": "prom_collect", "prom_read": "prom_collect",
             "prom_assemble": "prom_collect", "device_launch": "prom_kernel",
             "device_fetch": "prom_kernel", "device_wait": "device_fetch",
             "device_copy": "device_fetch"}
    for name, parent in under.items():
        (node, above), = spans[name]
        assert above["name"] == parent and node["elapsed_ns"] > 0, name
    fields = {name: dict(nodes[0][0]["fields"])
              for name, nodes in spans.items()}
    assert fields["device_launch"]["program"] == "prom_instant"
    assert fields["device_launch"]["h2d_bytes"] == 2 * SERIES * 8 * 4 + 5 * 4
    assert fields["device_fetch"]["bytes"] == SERIES * 5 * (4 + 1)
    assert fields["prom_select"]["series"] == SERIES
    assert fields["prom_labels"]["series"] == fleet.ref.series
    # the request is its stages: what no span covers is a small part of it
    root, = (n for n, _ in spans["http_prom"])
    inside = sum(c["elapsed_ns"] for c in root["children"])
    assert inside >= 0.8 * root["elapsed_ns"]


def test_the_counters_are_the_numbers_of_the_query(fleet):
    """Read through the metric files' own `params`, as a traced run does."""
    req = fleet.request(fleet.ref)
    fleet.ask(req)
    vars0 = fleet.vars()
    fleet.ask(req)
    vars1 = fleet.vars()
    vars0["client"], vars1["client"] = {"completed": 0}, {"completed": 1}
    ctx = {"vars0": vars0, "vars1": vars1}
    names = ["prom_samples_per_q", "prom_cells_per_sample",
             "prom_collect_ns_per_sample", "prom_prepare_ns_per_sample",
             "prom_collect_ms_per_q", "prom_prepare_ms_per_q",
             "prom_kernel_ms_per_q", "prom_match_ms_per_q",
             "prom_read_ms_per_q", "prom_assemble_ms_per_q",
             "prom_render_ms_per_q", "device_launch_ms_per_q",
             "device_launches_per_q", "device_fetch_ms_per_q",
             "device_wait_ms_per_q",
             "device_copy_ms_per_q", "h2d_bytes_per_q", "d2h_bytes_per_q"] \
        + [e["name"] for e in PROPOSED]
    got = {}
    for name in names:
        spec = _json(BENCH, "metrics", name + ".json")
        reader = metrics.BUILTIN[spec["reader"]]
        got[name] = reader(ctx, spec["params"])
        assert got[name] is not None and got[name] > 0, name
    assert got["prom_samples_per_q"] == 8 * SERIES == req.units
    assert got["prom_cells_per_sample"] == 1.0
    assert got["prom_fast_agg_share"] == 100.0
    assert got["device_launches_per_q"] == 1.0    # one named program a query
    # float32 times and values, the steps; back: values and validity
    assert got["h2d_bytes_per_q"] == 2 * SERIES * 8 * 4 + 5 * 4
    assert got["d2h_bytes_per_q"] == SERIES * 5 * (4 + 1)
    moved = {k: v - vars0["prom"].get(k, 0) for k, v in vars1["prom"].items()}
    assert moved["fast_agg_queries"] == 1
    assert moved["fast_agg_series"] == moved["collect_series"] \
        == moved["collect_parts"] == SERIES
    assert moved["prepare_windows"] == 5 * SERIES
    assert not moved.get("fast_agg_fallbacks")
    # a program without the spans and counters (the parent): a number or
    # nothing, never an exception
    for vars1 in ({}, {"client": {"completed": 1}}):
        for entry in PROPOSED:
            params = _json(BENCH, "metrics", entry["name"] + ".json")["params"]
            assert metrics.vars_ratio({"vars0": {}, "vars1": vars1},
                                      params) in (None, 0.0)


def test_the_new_metric_files_load_against_the_proposed_entries():
    bench = _json(ROOT, "BENCHMARK.json")
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len(bench["per_layer"]) == 128         # no room: files, no entries
    for entry in PROPOSED:
        assert entry["name"] not in {m["name"] for m in bench["per_layer"]}
        assert entry["layer"] in layers
        read, params = metrics.load(entry["name"], entry)
        assert read is metrics.vars_ratio and params["den"] \
            == ["client/completed"]
    with pytest.raises(metrics.MetricError, match="layer"):
        metrics.load(PROPOSED[0]["name"], {**PROPOSED[0], "layer": "Plan"})


@pytest.mark.parametrize("why, query", [
    ("shards", None),
    ("few_series", f'topk(10, {M}{{node="node-0003",container="app"}})'),
])
def test_a_fallback_is_counted_with_its_reason_and_answers_right(
        why, query, request):
    """A range that spans two shards, and a match of 100 series: the eager
    path answers, and the lazy-label path says why it did not."""
    srv = request.getfixturevalue("two_shards" if why == "shards" else "fleet")
    ref = srv.ref if query is None else srv.sibling(query)
    req = srv.request(ref, query)
    if why == "shards":
        t0 = (ref.start_s - 240) * NS
        assert len(srv.engine.shards_for_range(
            ref.db, None, t0, t0 + 400 * NS)) == 2
    else:
        assert len(ref._matched(req.stmt)) == 100
    before = srv.vars().get("prom", {})
    got = ref.parse(req.stmt, json.loads(srv.ask(req)))
    for name, (value, limit) in ref.numbers(req.stmt, got).items():
        assert value <= limit, name
    after = srv.vars()["prom"]
    moved = {k: v - before.get(k, 0) for k, v in after.items()}
    assert moved["fast_agg_fallbacks"] == 1
    assert moved["fast_agg_fallback_" + why] == 1
    assert not moved.get("fast_agg_queries")
    assert sum(v for k, v in moved.items()
               if k.startswith("fast_agg_fallback_")) == 1


# -- the set-up's count() over very many series -------------------------------


def test_an_ungrouped_count_reads_no_series_tags(fleet, monkeypatch):
    """`SELECT count(value) FROM m` over 1,000,000 series spent 28 s reading
    each series' tags for a group key that has none."""
    ref = fleet.ref
    calls = []
    index = fleet.engine.shards_for_range(
        ref.db, None, ref.start_s * NS, (ref.start_s + 120) * NS)[0].index
    monkeypatch.setattr(type(index), "tags_of", lambda self, sid: calls.append(
        sid) or {})
    doc = json.loads(fleet.http("GET", "/query", q=ref.count_q, db=ref.db)[1])
    assert doc["results"][0]["series"][0]["values"][0][1] == ref.rows
    assert calls == []


class Mixed:
    """One shard holding both layouts: 10 series of 50 rows flushed alone
    (a chunk a series, each with its stored count and sum) and then 200
    series of 4 rows (one packed chunk, whose stored sums are the chunk's);
    a float field `f` and an integer field `n` past 2^24."""

    LONG, SHORT = (10, 50), (200, 4)

    def __init__(self, path):
        self.engine = Engine(str(path))
        self.engine.create_database("d")
        self.svc = HttpService(self.engine, "127.0.0.1", 0)
        self.svc.start()
        rng = np.random.default_rng(SEED)
        self.f, self.n = {}, {}
        for kind, (series, rows) in (("a", self.LONG), ("b", self.SHORT)):
            f = rng.uniform(1e3, 1e6, size=(series, rows))
            n = rng.integers(1 << 30, 1 << 40, size=(series, rows))
            self.f[kind], self.n[kind] = f, n
            body = "\n".join(
                f"m,host={kind}{i:03d} f={float(f[i, j])!r},n={n[i, j]}i "
                f"{(1_700_000_000 + 10 * j) * NS}"
                for i in range(series) for j in range(rows)).encode()
            assert self.http("POST", "/write", body, db="d")[0] == 204
            self.http("POST", "/debug/ctrl", mod="flush")
        self.shard = self.engine.shards_for_range(
            "d", None, 1_700_000_000 * NS, 1_700_001_000 * NS)[0]

    http = Served.http

    def close(self):
        self.svc.stop()
        self.engine.close()


@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    srv = Mixed(tmp_path_factory.mktemp("mixed"))
    yield srv
    srv.close()


@pytest.mark.parametrize("agg", ["count", "sum", "mean"])
@pytest.mark.parametrize("which", ["stored", "packed", "both"])
def test_a_full_range_aggregate_reads_stored_sums_or_one_bulk_decode(
        which, agg, mixed, monkeypatch):
    """A series with chunks of its own answers count/sum/mean from their
    stored counts and sums, in float64 and undecoded; a series of a packed
    chunk has none of its own and was decoded alone, one `read_series`
    each (150 s of a count() over 1,000,000 series): those take one bulk
    decode.  Which way a series goes is what its chunks are, at any
    number of series; an integer sum is exact either way."""
    alone, bulk, chunks = [], [], mixed.shard.file_chunks("m")
    assert sorted(c.packed for _r, c in chunks) == [False] * 10 + [True]
    kind = type(mixed.shard)
    for name, seen in (("read_series", alone), ("read_series_bulk", bulk)):
        monkeypatch.setattr(kind, name, lambda self, *a, _f=getattr(
            kind, name), _s=seen, **kw: _s.append(a[1]) or _f(self, *a, **kw))
    where = {"stored": " WHERE host =~ /^a/", "packed": " WHERE host =~ /^b/",
             "both": ""}[which]
    kinds = {"stored": "a", "packed": "b", "both": "ab"}[which]
    with as_served():
        doc = json.loads(mixed.http(
            "GET", "/query", db="d",
            q=f"SELECT {agg}(f), {agg}(n) FROM m{where}")[1])
    _t, got_f, got_n = doc["results"][0]["series"][0]["values"][0]
    f = np.concatenate([mixed.f[k].ravel() for k in kinds])
    n = [int(x) for k in kinds for x in mixed.n[k].ravel()]
    want = {"count": (len(f), len(n)), "sum": (f.sum(), sum(n)),
            "mean": (f.mean(), sum(n) / len(n))}[agg]
    assert abs(got_f - want[0]) <= TOL["mean"] * abs(want[0])
    if agg == "mean":
        assert abs(got_n - want[1]) <= TOL["mean"] * abs(want[1])
    else:
        assert got_n == want[1] and isinstance(got_n, int)
    if agg == "count":
        assert got_f == want[0]
    assert alone == []                  # no series was decoded by itself
    assert len(bulk) == (which != "stored")
    if which == "stored" and agg != "count":
        # the stored sums are float64: nothing of the device's float32
        assert abs(got_f - want[0]) <= 1e-12 * abs(want[0])


# -- the cell -----------------------------------------------------------------


def test_the_control_flow_run_of_the_cell_ends_correct():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--cpu-dry-run"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["cpu_dry_run"] is True

"""The counter fleet asked over a whole day
(`benchmark/configs/prom-counters-24h.json`, cell `prom_rate_range_24h`),
small, on the CPU, through the served /write and /api/v1/query_range paths:
40 series x 24 h of 15 s scrapes, loaded in blocks with flushes between
them so that the day lies in several files, then the cell's own statement
(`benchmark/traffic/rate_range_24h.json` through the benchmark's generator:
`rate(http_requests_total[5m])` at a 60 s step, 1,436 steps) held to the
plain reference `benchmark/configs/prom_counters.py` at the configuration's
own limit, 2e-4, step times and series set exact.

Both routes the offload planner can take are held: host numpy in float64,
and jax.numpy with x64 off, as a server has it — float32 on the device, the
values narrowed on the host after the float64 arithmetic float32 cannot do
(`ops/prom.py` `TiledPrepared._narrowed`).  And what PR 42 added to the
program: the spans under `prom_collect`, `prom_prepare` and `device_launch`
and the counters of group `prom`, read as the benchmark's metric files read
them.  And what PR 45 changed: the covered-tile gather layout and the
(S, N) times matrix are built by the first kernel that reads them, under
spans of their own, and `rate()` of a counter on the device reads neither."""

import contextlib
import glob
import json
import os
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
from harness.oracle import TOL, rel_err  # noqa: E402

from opengemini_tpu.ops import prom as promops  # noqa: E402
from opengemini_tpu.query import offload  # noqa: E402
from opengemini_tpu.server.http import HttpService  # noqa: E402
from opengemini_tpu.storage import colcache  # noqa: E402
from opengemini_tpu.storage.engine import Engine  # noqa: E402
from opengemini_tpu.utils import tracing  # noqa: E402

CELL = "prom_rate_range_24h"
SERIES, SEED, NS = 40, 42, 10**9
TICKS, STEPS = 5760, 1436
# a decoded day of 40 series is 3.9 MB by the cache's accounting
REGIMES = {"off": 0, "evicting": 1, "roomy": 64}
NEW = ("prom_collect_ns_per_sample", "prom_prepare_ns_per_sample",
       "prom_match_ms_per_q", "prom_read_ms_per_q", "prom_assemble_ms_per_q",
       "prom_fill_ms_per_q", "prom_tile_index_ms_per_q",
       "prom_narrow_ms_per_q", "prom_values_h2d_enqueue_ms_per_q",
       "prom_cells_per_sample", "prom_samples_per_q",
       "prom_layout_skipped_share")
LATE = ("prom_gather_layout", "prom_times_matrix")
CHILDREN = {"prom_collect": ("prom_match", "prom_read", "prom_assemble"),
            "prom_prepare": ("prom_tile_plan", "prom_fill", "prom_tile_index"),
            "device_launch": ("prom_narrow", "prom_values_h2d")}


def _json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def cell_files() -> tuple[dict, dict]:
    """The cell's configuration and traffic files, found as run.py finds
    them."""
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (_json(ROOT, conf["file"]),
            _json(BENCH, "traffic", cell["traffic"] + ".json"))


def busy_counters(ticks: int, series: int) -> np.ndarray:
    """Counters that move 15,000 a scrape, 86 M over the day: past 2^24,
    where a float32 no longer holds every integer.  Series 0 restarts once,
    as the reference's do."""
    rng = np.random.default_rng(SEED + 1)
    inc = rng.integers(14_000, 16_001, size=(ticks, series))
    inc[0] = 0
    vals = rng.integers(0, 10**9, size=series)[None, :] + np.cumsum(inc, axis=0)
    at = ticks // 2 + 7
    vals[at:, 0] = np.cumsum(inc[at:, 0])
    return vals


@contextlib.contextmanager
def route(name: str):
    """`host`: the tiled kernels in numpy, float64.  `device`: in jax.numpy
    with x64 off, process-wide while the request is served (the handler's
    thread is not this one) — what a server computes in."""
    mode, x64 = offload.prom_host_kernels_mode(), jax.config.jax_enable_x64
    offload.set_prom_host_kernels_mode("1" if name == "host" else "0")
    if name == "device":
        jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", x64)
        offload.set_prom_host_kernels_mode(mode)


class Served:
    """One server over one store with the day loaded, the reference that
    made it, and the cell's statement."""

    def __init__(self, path, vals=None, shard_s: int = 0):
        cfg, mix = cell_files()
        assert cfg["guarantees"]["rate"].startswith(
            "every rate() window within 2e-4") and TOL["rate"] == 2e-4
        cfg.update(series=SERIES, targets=SERIES // 20,
                   load_block=cfg["dry_run"]["load_block"])
        mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                          "reference")
        self.ref = ref = mod.Reference(cfg, SEED)
        if vals is not None:
            ref.vals = vals
        assert (ref.ticks, ref.rows) == (TICKS, TICKS * SERIES)
        self.engine = Engine(str(path))
        self.engine.create_database(ref.db)
        if shard_s:
            self.engine.create_retention_policy(
                ref.db, "short", 0, shard_s * NS, default=True)
        self.svc = HttpService(self.engine, "127.0.0.1", 0)
        self.svc.start()
        # two blocks of 20 series, a block 24 requests of an hour each; a
        # flush every 12: four files, each half a day of a block of series
        for n, (body, _rows) in enumerate(ref.load_requests(), 1):
            assert self.http("POST", "/write", body, db=ref.db)[0] == 204
            if n % 12 == 0:
                self.http("POST", "/debug/ctrl", mod="flush")
        self.files = glob.glob(os.path.join(str(path), "**", "*.tsf"),
                               recursive=True)
        self.req = traffic.build(mix, ref, SEED, 1.0).requests[0]
        assert self.req.stmt["windows"] == STEPS
        assert self.req.units == ref.points(self.req.stmt) \
            == (TICKS - 1) * SERIES

    def http(self, method, path, body=None, **params):
        url = f"http://127.0.0.1:{self.svc.port}{path}"
        if params:
            url += ("&" if "?" in path else "?") + urllib.parse.urlencode(
                params)
        req = urllib.request.Request(url, data=body, method=method)
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, r.read()

    def ask(self, on: str = "host") -> bytes:
        with route(on):
            status, body = self.http(self.req.method, self.req.path)
        assert status == 200
        return body

    def answer(self, on: str) -> np.ndarray:
        """(series, steps); `parse` raises where the series set or a step
        time differs from the reference's."""
        got = self.ref.parse(self.req.stmt, json.loads(self.ask(on)))
        assert got.shape == (SERIES, STEPS)
        return got

    def error(self, got: np.ndarray) -> float:
        (value, limit), = self.ref.numbers(self.req.stmt, got).values()
        assert limit == TOL["rate"]
        return value

    def vars(self) -> dict:
        return json.loads(self.http("GET", "/debug/vars")[1])

    def close(self):
        self.svc.stop()
        self.engine.close()


def _served(tmp_path_factory, name: str, **kw):
    before = colcache.GLOBAL.config()
    srv = Served(tmp_path_factory.mktemp(name), **kw)
    yield srv
    srv.close()
    colcache.GLOBAL.configure(**before)


@pytest.fixture(scope="module")
def day(tmp_path_factory):
    yield from _served(tmp_path_factory, "day")


@pytest.fixture(scope="module")
def two_shards(tmp_path_factory):
    """The same day in shards of 12 h: every series spans two."""
    yield from _served(tmp_path_factory, "two_shards", shard_s=12 * 3600)


@pytest.fixture(scope="module")
def busy(tmp_path_factory):
    yield from _served(tmp_path_factory, "busy",
                       vals=busy_counters(TICKS, SERIES))


# -- the answers --------------------------------------------------------------


def test_the_day_lies_in_several_files(day, two_shards):
    assert len(day.files) == 4
    assert len(two_shards.files) >= 4
    t0 = day.ref.start_s * NS
    assert len(day.engine.shards_for_range(
        day.ref.db, None, t0, t0 + 86400 * NS)) == 1
    assert len(two_shards.engine.shards_for_range(
        day.ref.db, None, t0, t0 + 86400 * NS)) == 2


@pytest.mark.parametrize("on", ["host", "device"])
def test_each_route_is_the_references(day, on):
    assert day.error(day.answer(on)) <= TOL["rate"]


def test_the_routes_agree(day):
    assert rel_err(day.answer("device"), day.answer("host")) <= TOL["rate"]


@pytest.mark.parametrize("on", ["host", "device"])
def test_the_windows_of_a_reset_are_the_references(day, on):
    """Series 0 restarts inside the day: twenty windows hold the drop."""
    vals, stmt = day.ref.vals[:, 0], day.req.stmt
    at, = np.flatnonzero(np.diff(vals) < 0) + 1
    t_reset = day.ref.t_s[at]
    ends = np.arange(stmt["start"], stmt["end"] + 1, stmt["step_s"])
    hit = np.flatnonzero((ends >= t_reset) & (ends - stmt["range_s"]
                                              < t_reset - day.ref.scrape_s))
    assert len(hit) == 5                  # 5 m of windows a 60 s step apart
    got, want = day.answer(on)[0, hit], day.ref.want(stmt)[0, hit]
    assert rel_err(got, want) <= TOL["rate"]
    # a reset left uncorrected would read a rate below zero there
    assert (got > 0).all()


@pytest.mark.parametrize("on", ["host", "device"])
def test_a_day_cut_across_two_shards_is_merged_by_key(two_shards, on):
    srv = two_shards
    before = srv.vars().get("prom", {})
    assert srv.error(srv.answer(on)) <= TOL["rate"]
    after = srv.vars()["prom"]
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved["collect_series"] == SERIES
    assert moved["collect_parts"] == 2 * SERIES
    assert moved["collect_samples"] == TICKS * SERIES


def test_the_cache_s_regime_does_not_show_in_the_answer(day):
    bodies = {}
    for name, mb in REGIMES.items():
        colcache.GLOBAL.configure(budget_mb=mb)
        colcache.GLOBAL.clear()
        seen = day.vars()["colcache"]
        bodies[name] = [day.ask(), day.ask()]
        moved = {k: v - seen.get(k, 0)
                 for k, v in day.vars()["colcache"].items()}
        if name == "off":
            assert moved.get("hits", 0) == 0
        elif name == "evicting":
            assert moved["evictions"] > 0
        else:
            assert moved["evictions"] == 0 and moved["hits"] > 0
    assert len({b for pair in bodies.values() for b in pair}) == 1


# -- float32 ------------------------------------------------------------------


def test_a_busy_counter_stays_inside_the_limit_in_float32(busy):
    """What the device reads is each counter relative to its first sample
    in the query, resets folded in: exact in float64, narrowed to float32.
    A float32 holds 24 bits, so once a counter has moved M since that
    sample the two values a window gathers are each off by up to
    M x 2^-24 and their difference by up to M x 2^-23.  Against a
    window's increase D that is a relative error of (M / D) x 1.2e-7:
    inside 2e-4 while M / D < 1,678.  A counter of steady rate has
    M / D = the query's range over the window's width, whatever its rate:
    288 for a day of 5 m windows (here: about 3e-5), 1,678 after 5.8 days.
    Where the narrowing stops holding the guarantee is a busy counter's
    idle window: the error is absolute, M x 2^-23 / 300 s = 0.03/s at 86 M,
    and a window whose true rate is under 1/s is held to 2e-4 absolute.
    The benchmark's value model moves at most 99 x 5,760 = 570,240 a day,
    under 2^24: every narrowed value is exact."""
    moved = busy.ref.vals[-1, 1:] - busy.ref.vals[0, 1:]
    assert moved.min() > 4 * 2**24
    on_device = busy.error(busy.answer("device"))
    assert 1e-6 < on_device <= TOL["rate"]
    assert busy.error(busy.answer("host")) < 1e-9


def test_raw_float32_counters_would_fail(day, monkeypatch):
    """The narrowing skipped: counters near 1e9 as jax would narrow them
    on the way in, to the nearest 64.  The limit catches it."""
    monkeypatch.setattr(
        promops.TiledPrepared, "_narrowed",
        lambda self, form: self.values.astype(np.float32))
    got = day.answer("device")
    quiet = np.arange(1, SERIES)          # series 0 restarts
    assert rel_err(got[quiet], day.ref.want(day.req.stmt)[quiet]) \
        > 10 * TOL["rate"]


# -- spans and counters -------------------------------------------------------


def _tree(port: int, without: str | None = None) -> dict:
    """The newest retained http_prom tree.  A root closes after its
    response is sent: wait for it — and, since the root of the ask before
    may close after the `clear_recent` that followed it, for one with no
    span `without` where the ask differs from that one by it."""
    def get(**params):
        url = f"http://127.0.0.1:{port}/debug/trace?" \
            + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.loads(r.read())

    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        hit = [d for d in get()["recent"] if d["name"] == "http_prom"]
        if hit:
            root = get(trace_id=hit[0]["trace_id"])["trace"]["root"]
            if without not in _spans(root):
                return root
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


@pytest.mark.parametrize("which, shards", [("day", 1), ("two_shards", 2)])
def test_the_new_spans_account_for_their_parents(which, shards, traced,
                                                 request):
    served = request.getfixturevalue(which)
    served.ask("device")                  # programs built, cache filled
    # spans sum by name, one a shard: a match of the range's shards and one
    # of each shard's sids, a read a shard, an assembly a shard and the
    # merge by key; one plan of the tiles, one fill, one tile index; the
    # narrowing and the copy twice, of the value matrix and of the windows'
    # first samples
    times = {"prom_match": 1 + shards, "prom_read": shards,
             "prom_assemble": shards + 1, "prom_tile_plan": 1, "prom_fill": 1,
             "prom_tile_index": 1, "prom_narrow": 2, "prom_values_h2d": 2}
    shares = []
    for _ in range(6):
        tracing.clear_recent()
        served.ask("device")
        spans = _spans(_tree(served.svc.port))
        share = {}
        for parent, names in CHILDREN.items():
            (node, _), = spans[parent]
            for name in names:
                assert [p["name"] for _, p in spans[name]] \
                    == [parent] * times[name], name
            inside = sum(s["elapsed_ns"] for n in names for s, _ in spans[n])
            assert inside <= node["elapsed_ns"]
            share[parent] = inside / node["elapsed_ns"]
        # a read that only hits opens none of the miss path's spans
        assert not {"decode", "scan_merge", "mem_read"} & spans.keys()
        # rate() of a counter where the device narrows reads neither the
        # gather layout nor the times matrix: nothing built them
        assert not set(LATE) & spans.keys()
        shares.append(share)
        # at 40 series a prepare is 7 ms: the least disturbed of six asks
        if min(share["prom_collect"], share["prom_prepare"]) >= 0.95:
            break
    else:
        raise AssertionError(f"under 95 % of a parent in six asks: {shares}")
    # the host route corrects the resets from the gathered tiles: its
    # kernel builds the layout, once, and is where that time shows
    tracing.clear_recent()
    served.ask("host")
    spans = _spans(_tree(served.svc.port, without="device_launch"))
    assert [p["name"] for _, p in spans["prom_gather_layout"]] \
        == ["prom_kernel"]
    assert "prom_times_matrix" not in spans
    # ISSUE 42 asked the same of `device_launch`, and it does NOT hold
    # (PERF.md section 3): the launch does work of its own beside these two,
    # the dispatch of the eager chain with the implicit copies of the (S, K)
    # index matrices, and that stays its self time


@pytest.mark.parametrize("on", ["host", "device"])
def test_the_counters_are_the_numbers_of_the_query(day, on):
    """Read through the metric files' own `params`, as a traced run does."""
    day.ask(on)
    vars0 = day.vars()
    day.ask(on)
    vars1 = day.vars()
    vars0["client"], vars1["client"] = {"completed": 0}, {"completed": 1}
    ctx = {"vars0": vars0, "vars1": vars1}
    got = {}
    for name in NEW:
        spec = _json(BENCH, "metrics", name + ".json")
        assert spec["reader"] == "vars_ratio"
        got[name] = metrics.vars_ratio(ctx, spec["params"])
    samples = TICKS * SERIES              # the read takes the first scrape too
    assert got["prom_samples_per_q"] == samples
    assert abs(samples - day.req.units) == SERIES       # within one scrape
    assert got["prom_cells_per_sample"] == 1.0
    assert got["prom_layout_skipped_share"] == (100.0 if on == "device"
                                                else 0.0)

    def stage(name):
        return (vars1["query_stages"][name + "_ns"]
                - vars0["query_stages"].get(name + "_ns", 0))

    assert got["prom_collect_ns_per_sample"] == pytest.approx(
        stage("prom_collect") / samples)
    assert got["prom_prepare_ns_per_sample"] == pytest.approx(
        stage("prom_prepare") / samples)
    for name in ("match", "read", "assemble", "fill", "tile_index"):
        assert got[f"prom_{name}_ms_per_q"] == pytest.approx(
            stage("prom_" + name) * 1e-6) and got[f"prom_{name}_ms_per_q"] > 0
    for name in ("narrow", "values_h2d_enqueue"):
        ms = got[f"prom_{name}_ms_per_q"]
        assert ms > 0 if on == "device" else ms == 0
    moved = {k: v - vars0["prom"].get(k, 0) for k, v in vars1["prom"].items()}
    assert moved["collect_series"] == moved["collect_parts"] == SERIES
    assert moved["prepare_cells"] == SERIES * TICKS
    assert moved["prepare_windows"] == SERIES * STEPS
    assert moved["tiled_kernels"] == 1 and not moved.get("dense_kernels")
    # rate() never reads the times matrix; the layout only off the device
    assert moved["tiled_times_skipped"] == 1
    assert moved.get("tiled_layout_skipped", 0) == (on == "device")
    # a program without the spans and counters (the parent): a number or
    # nothing, never an exception
    for vars1 in ({}, {"client": {"completed": 1}}):
        for name in NEW:
            params = _json(BENCH, "metrics", name + ".json")["params"]
            assert metrics.vars_ratio({"vars0": {}, "vars1": vars1},
                                      params) in (None, 0.0)

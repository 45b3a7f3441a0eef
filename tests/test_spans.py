"""The span primitive (utils/tracing.py) and its three sinks: counters
with self time, the request's tree under OGT_TRACE=1 on the three served
paths, and `ogt:` annotations in a profiler capture; the garbage-
collection hook; H2D bytes counted at a single-device launch."""

import gc
import glob
import json
import os
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from opengemini_tpu.models import ragged
from opengemini_tpu.ops import aggregates as aggmod
from opengemini_tpu.parallel import runtime as prt
from opengemini_tpu.server.http import HttpService
from opengemini_tpu.storage import colcache, scanpool
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import devobs, tracing
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 10**9
BASE = 1_700_000_000
DAY = 86_400

MISS_SPANS = {"decode", "pool_wait", "block_read", "codec", "colcache_fill",
              "scan_merge"}
QUERY_SPANS = {"sql_parse", "select: cpu", "map_shards", "scan", *MISS_SPANS,
               "mem_read", "colcache", "device_compute", "layout_build",
               "device_launch", "device_fetch", "device_wait", "device_copy",
               "host_combine", "inc_cache",
               "render", "format", "serialize", "send"}
PROM_SPANS = {"prom_parse", "prom_collect", "prom_match", "prom_read",
              "prom_assemble", *MISS_SPANS, "mem_read",
              "prom_prepare", "prom_tile_plan", "prom_fill", "prom_tile_index",
              "prom_gather_layout", "prom_times_matrix",
              "prom_kernel", "device_launch", "prom_narrow",
              "prom_values_h2d", "device_fetch", "device_wait",
              "device_copy", "prom_render", "serialize", "send"}
WRITE_SPANS = {"read_body", "lp_parse", "type_check", "write_hooks",
               "write_lock_wait", "index_route", "memtable_apply",
               "wal_append", "wal_commit", "flush_inline", "write_observers",
               "send"}


@pytest.fixture(autouse=True)
def _state():
    prev = tracing.trace_enabled()
    tracing.set_trace_enabled(False)
    tracing.clear_recent()
    yield
    tracing.set_trace_enabled(prev)
    tracing.clear_recent()


def _counters(group: str) -> dict:
    return STATS.counters(group)


def _delta(group: str, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _counters(group).items()
            if v != before.get(k, 0)}


# -- sink 1: counters ---------------------------------------------------------


def test_self_time_is_elapsed_minus_children():
    q0, h0 = _counters("query_stages"), _counters("http")
    with tracing.request("query"):
        with tracing.span("t_outer"):
            with tracing.span("t_inner"):
                time.sleep(0.02)
            tracing.record_stage("t_measured", 3_000_000)
            time.sleep(0.01)
    d = _delta("query_stages", q0)
    assert d["t_outer_count"] == d["t_inner_count"] == 1
    assert d["t_inner_self_ns"] == d["t_inner_ns"] >= 20_000_000
    assert d["t_measured_ns"] == d["t_measured_self_ns"] == 3_000_000
    # exact: the frame's accumulator holds what the children recorded
    assert d["t_outer_self_ns"] == \
        d["t_outer_ns"] - d["t_inner_ns"] - d["t_measured_ns"]
    assert d["t_outer_self_ns"] >= 7_000_000    # its own 10 ms, less the 3
    h = _delta("http", h0)
    assert h["query_count"] == 1
    assert h["query_self_ns"] == h["query_ns"] - d["t_outer_ns"]
    assert h["query_offcpu_ns"] == h["query_ns"] - h["query_cpu_ns"]
    assert h["query_offcpu_ns"] >= 30_000_000      # it slept


@pytest.mark.parametrize("route, group", [("query", "query_stages"),
                                          ("prom", "query_stages"),
                                          ("write", "write_stages")])
def test_a_root_names_its_childrens_group(route, group):
    before = _counters(group)
    with tracing.request(route):
        with tracing.span("t_grouped"):
            pass
    assert _delta(group, before)["t_grouped_count"] == 1


def test_a_span_on_another_thread_leaves_the_parents_self_time_alone():
    before = _counters("query_stages")

    def worker():
        with tracing.span("t_worker"):
            time.sleep(0.02)

    with tracing.span("t_parent"):
        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    d = _delta("query_stages", before)
    assert d["t_parent_self_ns"] == d["t_parent_ns"] >= d["t_worker_ns"]


def test_the_stage_lands_on_the_bound_query():
    from opengemini_tpu.utils.querytracker import GLOBAL as TRACKER

    qid = TRACKER.register("t", "db")
    try:
        with tracing.span("t_tracked"):
            pass
        tracing.record_stage("t_noted", 5)
        stages = TRACKER.stages_of(qid)
    finally:
        TRACKER.unregister(qid)
    assert stages["t_tracked"] > 0 and stages["t_noted"] == 5


# -- sink 2: the request's tree ----------------------------------------------


def _http(port, method, path, body=None, **params):
    url = f"http://127.0.0.1:{port}{path}?" + urllib.parse.urlencode(params)
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, r.read()


def _trace_of(port, root_name: str) -> dict:
    """The newest retained tree with that root, from /debug/trace.  A
    root closes after its response is sent: wait for it."""
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        recent = json.loads(_http(port, "GET", "/debug/trace")[1])["recent"]
        hit = [d for d in recent if d["name"] == root_name]
        if hit:
            doc = json.loads(_http(port, "GET", "/debug/trace",
                                   trace_id=hit[0]["trace_id"])[1])
            assert doc["trace"]["trace_id"] == hit[0]["trace_id"]
            return doc
        time.sleep(0.01)
    raise AssertionError(f"no {root_name} tree was retained")


def _walk(node: dict, parent=None):
    yield node, parent
    for child in node["children"]:
        yield from _walk(child, node)


def _check_tree(doc: dict, root_name: str, vocabulary: set) -> dict:
    """Root, vocabulary, parent links; returns {name: [(span, parent)]}."""
    root = doc["trace"]["root"]
    assert root["name"] == root_name and root["elapsed_ns"] > 0
    by_name: dict = {}
    ids = set()
    for span, parent in _walk(root):
        assert span["span_id"] not in ids
        ids.add(span["span_id"])
        if parent is not None:
            assert span["parent_id"] == parent["span_id"]
            # a full collection may interrupt any stage of a traced
            # request and leaves a span of its own there (tracing._on_gc)
            assert span["name"] in vocabulary | {"gc"}, span["name"]
            assert span["start_ns"] >= root["start_ns"]
        by_name.setdefault(span["name"], []).append((span, parent))
    return by_name


@pytest.fixture
def server(tmp_path, monkeypatch):
    # the scan pool on, however few cores the test machine has
    monkeypatch.setattr(scanpool, "WORKERS", 4)
    engine = Engine(str(tmp_path / "data"))
    engine.create_database("db")
    engine.create_database("prom")
    svc = HttpService(engine, "127.0.0.1", 0)
    svc.start()
    yield svc
    svc.stop()
    engine.close()


def test_a_query_leaves_a_tree(server):
    port = server.port
    # two shards (a week apart) of 70 series each, on disk: two bulk
    # reads, the second on the scan pool's prefetch thread
    for t0 in (BASE, BASE + 8 * DAY):
        lines = "\n".join(
            f"cpu,host=h{h} v={h + k} {(t0 + k * 600) * NS}"
            for h in range(70) for k in range(6))
        assert _http(port, "POST", "/write", lines.encode(),
                     db="db")[0] == 204
    _http(port, "POST", "/debug/ctrl", mod="flush")
    colcache.GLOBAL.clear()
    tracing.set_trace_enabled(True)
    status, body = _http(
        port, "GET", "/query", db="db",
        q=f"SELECT mean(v) FROM cpu WHERE time >= {BASE * NS} AND "
          f"time < {(BASE + 9 * DAY) * NS} GROUP BY time(1d), host")
    assert status == 200 and "error" not in json.loads(body)["results"][0]
    doc = _trace_of(port, "http_query")
    spans = _check_tree(doc, "http_query", QUERY_SPANS)
    assert doc["qid"] is not None       # the executor's query id
    for name in ("sql_parse", "select: cpu", "format", "serialize", "send"):
        [(_, parent)] = spans[name]
        assert parent["name"] == "http_query"
    for name in ("map_shards", "scan", "device_compute", "render"):
        assert all(p["name"] == "select: cpu" for _, p in spans[name])
    for name in ("layout_build", "device_launch", "device_fetch",
                 "host_combine"):
        assert all(p["name"] == "device_compute" for _, p in spans[name])
    # a fetch's two halves, once each a fetch, in that order
    for fetch, _ in spans["device_fetch"]:
        assert [c["name"] for c in fetch["children"]
                if c["name"] != "gc"] == ["device_wait", "device_copy"]
        assert sum(c["elapsed_ns"] for c in fetch["children"]) \
            <= fetch["elapsed_ns"]
    # the bulk reads missed the cache and decoded, one span each, from
    # the prefetch thread, under the scan that dispatched them
    assert len(spans["decode"]) == 2
    assert all(p["name"] == "scan" for _, p in spans["decode"])
    # each decoded its one chunk in three stages, and then merged
    for name in ("block_read", "codec", "colcache_fill"):
        assert [p["name"] for _, p in spans[name]] == ["decode"] * 2, name
    assert [p["name"] for _, p in spans["scan_merge"]] == ["scan"] * 2
    launch = dict(map(tuple, spans["device_launch"][0][0]["fields"]))
    assert launch["program"] and launch["h2d_bytes"] > 0
    fields = dict(map(tuple, doc["trace"]["root"]["fields"]))
    assert fields["status"] == 200 and fields["bytes_out"] == len(body)


@pytest.mark.parametrize("devices", [4, 0], ids=["mesh_of_four", "no_mesh"])
def test_mesh_shard_is_a_stage_of_a_request_only_on_a_mesh(server, devices):
    """With `[device] mesh-axes` the pad and the sharded puts of a
    statement's matrices are the span `mesh_shard`
    (parallel/distributed.py shard_leading_axis): in the tree under
    `device_compute`, in the request's one stage map under its root, in
    `query_stages`.  With no mesh it never opens."""
    from opengemini_tpu.parallel import distributed as dist

    port = server.port
    lines = "\n".join(f"cpu,host=h{h} v={h + k} {(BASE + k * 600) * NS}"
                      for h in range(12) for k in range(6))
    assert _http(port, "POST", "/write", lines.encode(), db="db")[0] == 204
    tracing.set_trace_enabled(True)
    tracing.mark()
    q0 = _counters("query_stages")
    prt.set_mesh(dist.make_mesh(devices) if devices else None)
    try:
        status, body = _http(
            port, "GET", "/query", db="db",
            q=f"SELECT mean(v) FROM cpu WHERE time >= {BASE * NS} AND "
              f"time < {(BASE + 3600) * NS} GROUP BY time(10m), host")
    finally:
        prt.set_mesh(None)
    assert status == 200 and "error" not in json.loads(body)["results"][0]
    doc = _trace_of(port, "http_query")         # the root has closed
    spans = _check_tree(doc, "http_query", QUERY_SPANS | {"mesh_shard"})
    [rec] = tracing.tail_doc()["query"]
    d = _delta("query_stages", q0)
    if not devices:
        assert "mesh_shard" not in spans and "mesh_shard" not in rec["stages"]
        assert not [k for k in d if k.startswith("mesh_shard")]
        return
    assert all(p["name"] == "device_compute" for _, p in spans["mesh_shard"])
    ns, self_ns, count = rec["stages"]["mesh_shard"]
    assert (ns, self_ns, count) == (
        d["mesh_shard_ns"], d["mesh_shard_self_ns"], d["mesh_shard_count"])
    assert count == len(spans["mesh_shard"]) >= 1 and 0 < ns == self_ns
    assert ns <= rec["stages"]["device_compute"][0]
    put = dict(map(tuple, spans["mesh_shard"][0][0]["fields"]))
    assert put["arrays"] == 2 and put["pad_rows"] == 0 and put["bytes"] > 0


@pytest.mark.parametrize("series", [70, 8], ids=["bulk", "per_series"])
def test_rows_not_yet_flushed_are_read_under_one_span_a_shard(server, series):
    """Two shards a week apart, one flushed and one not: the read of the
    second opens `mem_read` under `scan`, once, whether its series are read
    in bulk (64 and more) or one by one; the flushed shard opens none."""
    port = server.port
    for t0 in (BASE, BASE + 8 * DAY):
        lines = "\n".join(
            f"cpu,host=h{h} v={h + k} {(t0 + k * 600) * NS}"
            for h in range(series) for k in range(6))
        assert _http(port, "POST", "/write", lines.encode(),
                     db="db")[0] == 204
        if t0 == BASE:
            _http(port, "POST", "/debug/ctrl", mod="flush")
    before = _counters("scan")
    tracing.set_trace_enabled(True)
    status, body = _http(
        port, "GET", "/query", db="db",
        q=f"SELECT max(v) FROM cpu WHERE time >= {BASE * NS} AND "
          f"time < {(BASE + 9 * DAY) * NS} GROUP BY time(1d), host")
    assert status == 200 and "error" not in json.loads(body)["results"][0]
    spans = _check_tree(_trace_of(port, "http_query"), "http_query",
                        QUERY_SPANS)
    [(span, parent)] = spans["mem_read"]
    assert parent["name"] == "scan"
    assert dict(map(tuple, span["fields"]))["series"] == series
    moved = _delta("scan", before)
    assert (moved["mem_rows"], moved["mem_parts"]) == (series * 6, 1)


def test_a_promql_query_leaves_a_tree(server):
    port = server.port
    lines = "\n".join(
        f"http_requests_total,job=j{j} value={k * (j + 1)} "
        f"{(BASE + k * 15) * NS}" for j in range(5) for k in range(200))
    assert _http(port, "POST", "/write", lines.encode(), db="prom")[0] == 204
    tracing.set_trace_enabled(True)
    status, body = _http(port, "GET", "/api/v1/query_range",
                         query="rate(http_requests_total[5m])",
                         start=BASE + 600, end=BASE + 2400, step=60)
    assert status == 200 and json.loads(body)["status"] == "success"
    doc = _trace_of(port, "http_prom")
    spans = _check_tree(doc, "http_prom", PROM_SPANS)
    assert doc["qid"] is not None
    for name in ("prom_parse", "prom_collect", "prom_prepare", "prom_kernel",
                 "prom_render", "serialize", "send"):
        assert [p["name"] for _, p in spans[name]] == ["http_prom"], name
    # a collect is a match, a read a shard and an assembly; a prepare the
    # fill of the padded matrices and the tile index (PR 42)
    # (one shard: the range's shards and its sids are two matches, its
    # slices and the merge by key two assemblies)
    for name in ("prom_match", "prom_read", "prom_assemble"):
        assert {p["name"] for _, p in spans[name]} == {"prom_collect"}, name
    assert [len(spans[name]) for name in
            ("prom_match", "prom_read", "prom_assemble")] == [2, 1, 2]
    for name in ("prom_tile_plan", "prom_fill", "prom_tile_index"):
        assert [p["name"] for _, p in spans[name]] == ["prom_prepare"], name
    # what only some kernels read is built by its first reader (PR 45): the
    # gather layout under the kernel that gathers tiles, never the prepare
    for name in ("prom_gather_layout", "prom_times_matrix"):
        assert {p["name"] for _, p in spans.get(name, [])} \
            <= {"prom_kernel", "device_launch"}, name
    # nothing was flushed: the read took the memtable's rows, under one span
    assert [p["name"] for _, p in spans["mem_read"]] == ["prom_read"]
    # where the device computed in a narrower float, the narrowing and the
    # copy of its values are the launch's children
    for name in ("prom_narrow", "prom_values_h2d"):
        assert {p["name"] for _, p in spans.get(name, [])} \
            <= {"device_launch"}, name
    # where the answer came from the device, each fetch has its two halves
    for name in ("device_wait", "device_copy"):
        assert [p["name"] for _, p in spans.get(name, [])] \
            == ["device_fetch"] * len(spans.get("device_fetch", [])), name


def test_a_range_answer_counts_the_points_it_rendered(server):
    port = server.port
    lines = "\n".join(
        f"http_requests_total,job=j{j} value={k * (j + 1)} "
        f"{(BASE + k * 15) * NS}" for j in range(5) for k in range(200))
    assert _http(port, "POST", "/write", lines.encode(), db="prom")[0] == 204
    assert _http(port, "POST", "/write", f"cpu v=1 {BASE * NS}".encode(),
                 db="db")[0] == 204
    before, stages = _counters("prom"), _counters("query_stages")
    # neither an InfluxQL answer nor an instant vector is a matrix body
    assert _http(port, "GET", "/query", db="db",
                 q="SELECT count(v) FROM cpu")[0] == 200
    assert _http(port, "GET", "/api/v1/query", time=BASE + 900,
                 query="rate(http_requests_total[5m])")[0] == 200
    moved = _delta("prom", before)
    assert "render_points" not in moved and "render_native_points" not in moved
    status, body = _http(port, "GET", "/api/v1/query_range",
                         query="rate(http_requests_total[5m])",
                         start=BASE + 600, end=BASE + 2400, step=60)
    result = json.loads(body)["data"]["result"]
    points = sum(len(s["values"]) for s in result)
    assert status == 200 and len(result) == 5 and points == 5 * 31
    moved = _delta("prom", before)
    assert moved["render_points"] == moved["render_native_points"] == points
    # one render, one envelope and one socket write for the matrix (the
    # two answers before it serialized and sent theirs too); a `send`
    # closes just after the client has the whole body: wait for it
    deadline = time.monotonic() + 10.0
    while (_delta("query_stages", stages).get("send_count") != 3
           and time.monotonic() < deadline):
        time.sleep(0.01)
    d = _delta("query_stages", stages)
    assert d["prom_render_count"] == 2 and d["serialize_count"] == 3
    assert d["send_count"] == 3


def test_a_write_leaves_a_tree(server):
    port = server.port
    tracing.set_trace_enabled(True)
    before = _counters("write_stages")
    observed = []
    server.engine.add_write_observer(
        lambda db, rp, points: observed.append(len(points)))
    lines = "\n".join(f"m,host=h{i % 9} v={i} {(BASE + i) * NS}"
                      for i in range(500))
    assert _http(port, "POST", "/write", lines.encode(), db="db")[0] == 204
    doc = _trace_of(port, "http_write")
    spans = _check_tree(doc, "http_write", WRITE_SPANS)
    for name in ("read_body", "lp_parse", "write_hooks", "write_lock_wait",
                 "index_route", "wal_append", "memtable_apply", "wal_commit",
                 "flush_inline", "write_observers", "send"):
        assert [p["name"] for _, p in spans[name]] == ["http_write"], name
    assert observed == [500]
    d = _delta("write_stages", before)
    assert d["lp_parse_count"] == d["memtable_apply_count"] == 1


def test_a_large_body_parses_in_segments(server, monkeypatch):
    from opengemini_tpu.storage import engine as engmod

    monkeypatch.setattr(engmod, "_INGEST_SEGMENT_BYTES", 4096)
    if engmod._ingest_pool() is None:
        pytest.skip("no ingest pool on this machine")
    tracing.set_trace_enabled(True)
    lines = "\n".join(f"m,host=h{i % 50} v={i} {(BASE + i) * NS}"
                      for i in range(2000))
    assert _http(server.port, "POST", "/write", lines.encode(),
                 db="db")[0] == 204
    spans = _check_tree(_trace_of(server.port, "http_write"), "http_write",
                        WRITE_SPANS)
    [(parse, _)] = spans["lp_parse"]
    assert dict(map(tuple, parse["fields"]))["segments"] >= 2
    assert len(spans["type_check"]) == 1
    assert len(spans["memtable_apply"]) == len(spans["wal_append"]) >= 2


def test_no_tree_without_the_knob(server):
    _http(server.port, "POST", "/write", f"m v=1 {BASE * NS}".encode(),
          db="db")
    _http(server.port, "GET", "/query", db="db", q="SELECT count(v) FROM m")
    time.sleep(0.05)
    assert tracing.recent_traces() == []


# -- sink 3: the profiler capture --------------------------------------------


def _capture_events(logdir: str) -> dict:
    """{line name: [(event name, start, end, stats)]} of the host plane."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for n, line in enumerate(plane.lines):
            out[f"{n}:{line.name}"] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats) if e.name.startswith("ogt:") else None)
                for e in line.events]
    return out


def _wait_capture() -> dict:
    deadline = time.monotonic() + 60.0
    while devobs.profile_status()["active"]:
        assert time.monotonic() < deadline, "the capture never stopped"
        time.sleep(0.02)
    last = devobs.profile_status()["last"]
    assert last["ok"], last
    return last


def _traced_work():
    def worker():
        with tracing.span("t_pool_stage"):
            time.sleep(0.01)

    with tracing.request("query"):
        with tracing.span("t_stage", rows=3):
            with tracing.span("t_leaf"):
                time.sleep(0.01)
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=10)


@pytest.mark.parametrize("python", [False, True])
def test_a_capture_holds_the_spans_on_its_own_clock(tmp_path, python):
    logdir = str(tmp_path / "capture")
    devobs.start_profile(1.0, logdir=logdir, python=python)
    assert devobs.profile_status()["active"]
    _traced_work()
    last = _wait_capture()
    assert last["stop_s"] < 30
    lines = _capture_events(logdir)
    by = {name: (start, end, line, stats) for line, evs in lines.items()
          for name, start, end, stats in evs if name.startswith("ogt:")}
    # where an earlier test's server started the pulse, its sleeps are
    # annotated too (tests/test_request_records.py holds that)
    assert set(by) - {"ogt:pulse"} == {"ogt:http_query", "ogt:t_stage",
                                       "ogt:t_leaf", "ogt:t_pool_stage"}
    root, stage, leaf = by["ogt:http_query"], by["ogt:t_stage"], \
        by["ogt:t_leaf"]
    # nested as the spans were, on one thread's line
    assert root[2] == stage[2] == leaf[2]
    assert root[0] <= stage[0] <= leaf[0] < leaf[1] <= stage[1] <= root[1]
    pool = by["ogt:t_pool_stage"]
    assert pool[2] != root[2]                      # its own thread's line
    assert stage[0] <= pool[0] < pool[1] <= stage[1]
    # the span's fields ride the annotation
    assert stage[3] == {"rows": 3}
    frames = [name for evs in lines.values() for name, _, _, _ in evs
              if name.startswith("$")]
    assert bool(frames) == python, frames[:5]
    # no capture, no annotation object
    with tracing.span("t_after") as sp:
        assert sp._ann is None


# -- the garbage-collection hook ---------------------------------------------


def test_the_gc_hook_counts_a_full_collection():
    tracing.watch_gc()
    tracing.watch_gc()                              # idempotent
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = STATS.snapshot()["runtime"]
    gc.collect(2)
    after = STATS.snapshot()["runtime"]
    assert after["gc_gen2_collections"] == before["gc_gen2_collections"] + 1
    assert after["gc_collections"] >= before["gc_collections"] + 1
    assert after["gc_gen2_pause_ns"] > before["gc_gen2_pause_ns"]
    assert after["gc_pause_ns"] - before["gc_pause_ns"] >= \
        after["gc_gen2_pause_ns"] - before["gc_gen2_pause_ns"]
    gc.collect(0)
    last = STATS.snapshot()["runtime"]
    assert last["gc_gen2_collections"] == after["gc_gen2_collections"]
    assert last["gc_collections"] > after["gc_collections"]


def test_a_full_collection_inside_a_traced_request_is_a_span():
    tracing.watch_gc()
    tracing.set_trace_enabled(True)
    with tracing.request("query") as root:
        with tracing.span("t_stage"):
            gc.collect(2)
    [stage] = root.trace.root.children
    [pause] = [c for c in stage.children if c.name == "gc"]
    assert 0 < pause.elapsed_ns <= stage.elapsed_ns


# -- H2D bytes at a single-device launch --------------------------------------


def _bucketed(rows: int = 600) -> ragged.BucketedBatch:
    rng = np.random.default_rng(7)
    b = ragged.BucketedBatch(np.float64)
    seg = np.sort(rng.integers(0, 40, rows))
    b.add(rng.normal(size=rows), np.arange(rows, dtype=np.int64), seg,
          np.ones(rows, bool), np.arange(rows, dtype=np.int64))
    return b


def test_h2d_bytes_count_the_host_arrays_of_a_launch():
    """A launch is passed what its program reads: `basic` the values and
    the mask of a bucket, not the three time and index matrices beside
    them, and those bytes are what h2d counts (ROADMAP R-A9.5).  A lone
    batch is a launch group of one a bucket."""
    import jax

    assert prt.get_mesh() is None
    batch = _bucketed()
    buckets = batch._freeze(40)
    want = sum(b.values.nbytes + b.mask.nbytes for b in buckets)
    h0 = _counters("device").get("h2d_bytes_total", 0)
    q0 = _counters("query_stages")
    out, _sel, counts = batch.run(aggmod.get("mean"), 40)
    assert counts.sum() == 600
    assert _counters("device")["h2d_bytes_total"] - h0 == want
    d = _delta("query_stages", q0)
    assert d["device_launch_count"] == d["device_fetch_count"] == \
        d["host_combine_count"] == len(buckets)
    # ... and until a selector is asked for those three are not built
    assert all(b.plan._selector_mats is None for b in buckets)
    # a selector reads all five matrices
    h1 = _counters("device")["h2d_bytes_total"]
    batch.run(aggmod.get("first"), 40)
    assert _counters("device")["h2d_bytes_total"] - h1 == want + sum(
        a.nbytes for b in buckets for a in b.plan.selector_mats())
    # the same launch over arrays that already live on the device
    again = _bucketed()
    for b in again._freeze(40):
        b.values, b.mask = jax.device_put(b.values), jax.device_put(b.mask)
    h1 = _counters("device")["h2d_bytes_total"]
    out2, _sel, _counts = again.run(aggmod.get("mean"), 40)
    assert _counters("device")["h2d_bytes_total"] == h1
    np.testing.assert_allclose(out2, out)


def test_an_aggregate_answer_counts_its_cells_once(server):
    """query/render_cells, render_bulk_cells, render_native_cells: one
    update a statement, equal for the fleet statement's shape, and the
    stages of a response written from arrays keep their names."""
    from opengemini_tpu.query import render as qrender

    port = server.port
    lines = "\n".join(
        f"cpu,hostname=host_{h} " + ",".join(
            f"usage_{f}={(h * 7 + k + ord(f)) % 11 / 3}" for f in "abcde")
        + f" {(BASE + k * 10) * NS}" for h in range(25) for k in range(360))
    assert _http(port, "POST", "/write", lines.encode(), db="db")[0] == 204
    q = ("SELECT " + ", ".join(f"mean(usage_{f})" for f in "abcde")
         + f" FROM cpu WHERE time >= {BASE * NS} AND time < "
         f"{(BASE + 3600) * NS} GROUP BY time(5m), hostname")
    before, stages = _counters("query"), _counters("query_stages")
    status, body = _http(port, "GET", "/query", db="db", q=q, epoch="ns")
    series = json.loads(body)["results"][0]["series"]
    cells = sum(len(s["values"]) for s in series) * 5
    assert status == 200 and len(series) == 25 and cells >= 25 * 12 * 5
    moved = _delta("query", before)
    assert moved["render_cells"] == moved["render_bulk_cells"] == cells
    assert moved.get("render_native_cells", 0) == (
        cells if qrender._native.load() is not None else 0)
    deadline = time.monotonic() + 10.0
    while (_delta("query_stages", stages).get("send_count") != 1
           and time.monotonic() < deadline):
        time.sleep(0.01)
    d = _delta("query_stages", stages)
    for name in ("render", "format", "serialize", "send"):
        assert d[name + "_count"] == 1 and d[name + "_ns"] > 0, name
    # a raw select renders no aggregate cell
    assert _http(port, "GET", "/query", db="db",
                 q="SELECT usage_a FROM cpu LIMIT 3")[0] == 200
    assert _delta("query", before) == moved

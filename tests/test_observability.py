"""Observability tier (PR 8): hierarchical cross-node tracing, latency
histograms, the Prometheus /metrics exporter, and slow-query capture.

Covers the acceptance contract: a GROUP BY time() query against a real
2-node HTTP cluster yields ONE stitched trace at the coordinator with
replica-side spans (scan/decode/partial_merge) under correct parentage;
/metrics parses clean under a strict text-format parser; histograms are
exact under concurrency and merge; the slow log honors its threshold,
ring bound, and ctrl tuning; and with every knob unset the layer is
inert (bit-identical results, no span trees allocated).
"""

import json
import re
import threading
import urllib.error
import urllib.parse
import urllib.request

import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import slowlog, stats, tracing

NS = 10**9
BASE = 1_700_000_000


@pytest.fixture(autouse=True)
def _obs_state():
    """Every test starts from knobs-unset state and restores it: the
    trace/hist flags and slow log are process-global."""
    prev_trace = tracing.trace_enabled()
    prev_hist = stats.obs_enabled()
    prev_slow = slowlog.GLOBAL.threshold_ms
    prev_max = slowlog.GLOBAL.max_records
    tracing.set_trace_enabled(False)
    stats.set_obs_enabled(True)
    yield
    tracing.set_trace_enabled(prev_trace)
    stats.set_obs_enabled(prev_hist)
    slowlog.GLOBAL.configure(slow_ms=prev_slow, slow_max=prev_max)
    slowlog.GLOBAL.clear()
    tracing.clear_recent()


def _url(port, path, **params):
    u = f"http://127.0.0.1:{port}{path}"
    if params:
        u += "?" + urllib.parse.urlencode(params)
    return u


def _get(port, path, **params):
    try:
        with urllib.request.urlopen(_url(port, path, **params),
                                    timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _post(port, path, body=b"", **params):
    req = urllib.request.Request(_url(port, path, **params), data=body,
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _recent(name: str) -> list[dict]:
    """Retained traces whose root span is `name`.  A request's root
    closes after its response is sent, so the client can be back here a
    moment before the trace is retained: wait for it."""
    import time

    deadline = time.monotonic() + 5.0
    while True:
        docs = [d for d in tracing.recent_traces() if d["name"] == name]
        if docs or time.monotonic() > deadline:
            return docs
        time.sleep(0.01)


# -- histograms --------------------------------------------------------------


class TestHistogram:
    def test_bucket_bounds_inclusive(self):
        h = stats.Histogram("t")
        h.observe_ns(1 << 10)       # exactly the first bound
        h.observe_ns((1 << 10) + 1)  # just over it
        snap = h.snapshot()
        assert snap["counts"][0] == 1
        assert snap["counts"][1] == 1
        assert snap["count"] == 2
        assert snap["sum_ns"] == (1 << 10) * 2 + 1

    def test_concurrent_exactness(self):
        h = stats.Histogram("conc")
        N, PER = 8, 5000

        def worker(k):
            for i in range(PER):
                h.observe_ns((i % 40) * 1_000_000 + k)

        ts = [threading.Thread(target=worker, args=(k,)) for k in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = h.snapshot()
        assert snap["count"] == N * PER
        assert sum(snap["counts"]) == N * PER
        assert snap["sum_ns"] == sum(
            (i % 40) * 1_000_000 + k for k in range(N) for i in range(PER))

    def test_merge_exactness(self):
        import random

        rng = random.Random(7)
        vals = [rng.randrange(0, 1 << 36) for _ in range(10_000)]
        whole = stats.Histogram("whole")
        parts = [stats.Histogram(f"p{i}") for i in range(4)]
        for i, v in enumerate(vals):
            whole.observe_ns(v)
            parts[i % 4].observe_ns(v)
        merged = stats.Histogram("merged")
        for p in parts:
            merged.merge(p)
        assert merged.snapshot() == whole.snapshot()

    def test_percentile_bucket_accuracy(self):
        h = stats.Histogram("pct")
        for _ in range(99):
            h.observe_ns(1_000_000)  # ~1ms
        h.observe_ns(30_000_000_000)  # one 30s outlier
        p50 = h.percentile_s(50)
        p99 = h.percentile_s(99)
        # log2 buckets: the quantile lands in the right bucket (within
        # one power of two of the true value)
        assert 0.0005 <= p50 <= 0.002
        assert p99 <= 0.002
        assert h.percentile_s(100) >= 30.0

    def test_disarmed_observe_is_inert(self):
        h = stats.Histogram("off")
        stats.set_obs_enabled(False)
        h.observe_ns(123456)
        assert h.snapshot()["count"] == 0
        stats.set_obs_enabled(True)
        h.observe_ns(123456)
        assert h.snapshot()["count"] == 1


# -- strict Prometheus text-format parser ------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>[^ ]+)$")
_LABEL_RE = re.compile(
    r'^(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"$')


def parse_prometheus_strict(text: str) -> dict:
    """Strict text-format 0.0.4 parser: validates names, label syntax,
    TYPE declarations (once per family, before its samples, samples
    contiguous), histogram bucket monotonicity and +Inf/count/sum
    consistency.  Returns {family: {"type": t, "samples":
    [(name, {labels}, value)]}}."""
    families: dict = {}
    cur = None
    seen_done: set = set()
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4, f"line {ln}: bad TYPE {line!r}"
            fam, typ = parts[2], parts[3]
            assert _NAME_RE.match(fam), f"line {ln}: bad family {fam!r}"
            assert typ in ("counter", "gauge", "histogram", "summary",
                           "untyped"), f"line {ln}: bad type {typ!r}"
            assert fam not in families, \
                f"line {ln}: duplicate TYPE for {fam}"
            assert fam not in seen_done, \
                f"line {ln}: family {fam} not contiguous"
            if cur is not None:
                seen_done.add(cur)
            families[fam] = {"type": typ, "samples": []}
            cur = fam
            continue
        assert not line.startswith("#"), f"line {ln}: bad comment {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"line {ln}: unparseable sample {line!r}"
        name = m.group("name")
        labels = {}
        if m.group("labels"):
            for item in _split_labels(m.group("labels")):
                lm = _LABEL_RE.match(item)
                assert lm, f"line {ln}: bad label {item!r}"
                assert lm.group("k") not in labels, \
                    f"line {ln}: duplicate label {lm.group('k')}"
                labels[lm.group("k")] = lm.group("v")
        if m.group("value") in ("+Inf", "-Inf", "NaN"):
            value = float(m.group("value").replace("Inf", "inf"))
        else:
            value = float(m.group("value"))  # raises on malformed
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and base in families and \
                    families[base]["type"] == "histogram":
                fam = base
                break
        assert fam in families, f"line {ln}: sample {name} before TYPE"
        assert fam == cur, f"line {ln}: family {fam} not contiguous"
        families[fam]["samples"].append((name, labels, value))
    _validate_histograms(families)
    return families


def _split_labels(raw: str):
    out, depth_q, cur = [], False, []
    i = 0
    while i < len(raw):
        c = raw[i]
        if c == "\\" and depth_q:
            cur.append(raw[i : i + 2])
            i += 2
            continue
        if c == '"':
            depth_q = not depth_q
        if c == "," and not depth_q:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(c)
        i += 1
    if cur:
        out.append("".join(cur))
    return out


def _validate_histograms(families: dict) -> None:
    for fam, doc in families.items():
        if doc["type"] != "histogram":
            continue
        by_labels: dict = {}
        for name, labels, value in doc["samples"]:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"))
            entry = by_labels.setdefault(
                key, {"buckets": [], "sum": None, "count": None})
            if name == fam + "_bucket":
                assert "le" in labels, f"{fam}: bucket without le"
                entry["buckets"].append(
                    (float(labels["le"].replace("Inf", "inf")), value))
            elif name == fam + "_sum":
                entry["sum"] = value
            elif name == fam + "_count":
                entry["count"] = value
        for key, entry in by_labels.items():
            bs = entry["buckets"]
            assert bs, f"{fam}{dict(key)}: no buckets"
            les = [le for le, _v in bs]
            assert les == sorted(les), f"{fam}: le not increasing"
            counts = [v for _le, v in bs]
            assert counts == sorted(counts), \
                f"{fam}: buckets not cumulative"
            assert les[-1] == float("inf"), f"{fam}: missing +Inf bucket"
            assert entry["count"] is not None and entry["sum"] is not None
            assert counts[-1] == entry["count"], \
                f"{fam}: +Inf bucket != count"


class TestMetricsEndpoint:
    @pytest.fixture
    def server(self, tmp_path):
        from opengemini_tpu.server.http import HttpService

        engine = Engine(str(tmp_path / "data"))
        engine.create_database("db")
        svc = HttpService(engine, "127.0.0.1", 0)
        svc.start()
        yield svc
        svc.stop()
        engine.close()

    def test_metrics_parse_strict(self, server):
        status, _ = _post(
            server.port, "/write",
            f"cpu,host=a v=1 {BASE * NS}\ncpu,host=a v=2 {(BASE + 60) * NS}"
            .encode(), db="db")
        assert status == 204
        _get(server.port, "/query", db="db", q="SELECT mean(v) FROM cpu")
        status, body = _get(server.port, "/metrics")
        assert status == 200
        fams = parse_prometheus_strict(body.decode())
        # the renamed ingest counter and mechanical families are present
        assert fams["ogt_write_rows_total"]["type"] == "counter"
        [(name, labels, val)] = fams["ogt_write_rows_total"]["samples"]
        assert val >= 2
        assert "ogt_executor_queries" in fams
        assert "ogt_uptime_seconds" in fams
        # HTTP endpoint histogram observed this scrape's own traffic
        hist = fams["ogt_http_request_seconds"]
        assert hist["type"] == "histogram"
        routes = {lab.get("route") for _n, lab, _v in hist["samples"]}
        assert "write" in routes and "query" in routes
        # query-stage histograms (span channel) recorded the SELECT
        stages = fams["ogt_query_stage_seconds"]
        stage_names = {lab.get("stage") for _n, lab, _v in
                       stages["samples"]}
        assert "scan" in stage_names and "render" in stage_names

    def test_metrics_rows_match_acked(self, server):
        _, body0 = _get(server.port, "/metrics")
        fams0 = parse_prometheus_strict(body0.decode())
        before = fams0["ogt_write_rows_total"]["samples"][0][2] \
            if "ogt_write_rows_total" in fams0 else 0
        n = 37
        lines = "\n".join(
            f"m,host=h{i % 3} v={i} {(BASE + i) * NS}" for i in range(n))
        status, _ = _post(server.port, "/write", lines.encode(), db="db")
        assert status == 204
        _, body1 = _get(server.port, "/metrics")
        fams1 = parse_prometheus_strict(body1.decode())
        after = fams1["ogt_write_rows_total"]["samples"][0][2]
        assert after - before == n


# -- 2-node cluster trace stitching ------------------------------------------


def _mk_cluster(tmp_path, rf=2, nids=("nA", "nB")):
    from opengemini_tpu.parallel.cluster import DataRouter
    from opengemini_tpu.server.http import HttpService

    nodes, addrs = {}, {}
    for nid in nids:
        e = Engine(str(tmp_path / nid))
        e.create_database("db")
        svc = HttpService(e, "127.0.0.1", 0)
        svc.start()
        addrs[nid] = f"127.0.0.1:{svc.port}"
        nodes[nid] = (e, svc)

    class FsmStub:
        def __init__(self):
            self.nodes = {n: {"addr": a, "role": "data"}
                          for n, a in addrs.items()}

    class StoreStub:
        fsm = FsmStub()
        token = ""

    for nid, (e, svc) in nodes.items():
        svc.router = DataRouter(e, StoreStub(), nid, addrs[nid], rf=rf)
        svc.executor.router = svc.router
    return nodes, addrs


def _close(nodes):
    for _nid, (e, svc) in nodes.items():
        svc.stop()
        e.close()


def _spans_by_name(root: dict) -> dict:
    out = {}

    def walk(s):
        out.setdefault(s["name"], []).append(s)
        for c in s["children"]:
            walk(c)

    walk(root)
    return out


class TestClusterTraceStitching:
    def test_groupby_time_stitches_replica_spans(self, tmp_path):
        nodes, addrs = _mk_cluster(tmp_path, rf=2)
        try:
            tracing.set_trace_enabled(True)
            (eA, svcA) = nodes["nA"]
            port = svcA.port
            lines = "\n".join(
                f"cpu,host=h{i % 4} v={i} {(BASE + i * 30) * NS}"
                for i in range(40))
            status, _ = _post(port, "/write", lines.encode(), db="db")
            assert status == 204
            status, body = _get(
                port, "/query", db="db", epoch="ns",
                q=f"SELECT mean(v), count(v) FROM cpu WHERE "
                  f"time >= {BASE * NS} AND time < {(BASE + 1200) * NS} "
                  "GROUP BY time(5m)")
            assert status == 200
            res = json.loads(body)["results"][0]
            assert "error" not in res, res
            # count across all windows == every written row, cluster-wide
            total = sum(r[2] for r in res["series"][0]["values"] if r[2])
            assert total == 40

            # one stitched tree at the coordinator
            docs = _recent("http_query")
            assert docs, "no query trace retained"
            doc = tracing.get_trace(qid=docs[0]["qid"])
            root = doc["trace"]["root"]
            spans = _spans_by_name(root)
            [rp_span] = spans["remote_partials"]
            [remote] = spans["select_partials"]
            # cross-node parentage: the replica subtree hangs off the
            # RPC span that issued it, same trace id end to end
            assert remote["node"] == "nB"
            assert remote["parent_id"] == rp_span["span_id"]
            for stage in ("scan", "decode", "partial_merge"):
                [st] = [s for s in spans[stage] if s["node"] == "nB"]
                assert st["parent_id"] == remote["span_id"]
                assert st["elapsed_ns"] >= 0
            # replica-side decode span carries row attribution
            [dec] = [s for s in spans["decode"] if s["node"] == "nB"]
            assert dict(f[0:2] for f in [tuple(x) for x in
                        dec["fields"]]).get("rows", 0) > 0

            # the same tree is served over HTTP at /debug/trace?qid=
            status, body = _get(port, "/debug/trace",
                                qid=docs[0]["qid"])
            assert status == 200
            served = json.loads(body)
            assert served["trace"]["trace_id"] == doc["trace"]["trace_id"]

            # routed-write stitching: the write trace carries the
            # replica's internal_write/apply subtree
            wdocs = _recent("http_write")
            assert wdocs
            wdoc = tracing.get_trace(trace_id=wdocs[0]["trace_id"])
            wspans = _spans_by_name(wdoc["trace"]["root"])
            [iw] = wspans["internal_write"]
            assert iw["node"] == "nB"
            [ap] = wspans["apply"]
            assert ap["parent_id"] == iw["span_id"]
        finally:
            _close(nodes)

    def test_failover_mid_query_still_one_tree(self, tmp_path):
        """A replica that dies mid-query (every /internal/* dropped)
        fails over; the query still answers exactly and the coordinator
        still emits ONE coherent trace — with no spans from the dead
        node."""
        from opengemini_tpu.parallel import netfault

        nodes, addrs = _mk_cluster(tmp_path, rf=2)
        try:
            tracing.set_trace_enabled(True)
            (eA, svcA) = nodes["nA"]
            port = svcA.port
            lines = "\n".join(
                f"cpu,host=h{i % 4} v={i} {(BASE + i * 30) * NS}"
                for i in range(40))
            status, _ = _post(port, "/write", lines.encode(), db="db")
            assert status == 204
            tracing.clear_recent()
            # partition nB away from nA for the whole data plane: the
            # metadata round classifies it dead and fails over to the
            # surviving replica set (rf=2 over 2 nodes: nA holds all)
            netfault.set_rule("nA", addrs["nB"], "/internal/*", "drop")
            try:
                status, body = _get(
                    port, "/query", db="db", epoch="ns",
                    q=f"SELECT mean(v), count(v) FROM cpu WHERE "
                      f"time >= {BASE * NS} AND "
                      f"time < {(BASE + 1200) * NS} GROUP BY time(5m)")
                assert status == 200
                res = json.loads(body)["results"][0]
                assert "error" not in res, res
                total = sum(
                    r[2] for r in res["series"][0]["values"] if r[2])
                assert total == 40  # exact despite the failover
            finally:
                netfault.clear_all()
            docs = _recent("http_query")
            assert docs
            doc = tracing.get_trace(qid=docs[0]["qid"])
            spans = _spans_by_name(doc["trace"]["root"])
            all_nodes = {s["node"] for lst in spans.values() for s in lst}
            assert "nB" not in all_nodes
            assert "render" in spans  # the tree is complete
        finally:
            _close(nodes)


# -- slow-query capture ------------------------------------------------------


class TestSlowLog:
    @pytest.fixture
    def server(self, tmp_path):
        from opengemini_tpu.server.http import HttpService

        engine = Engine(str(tmp_path / "data"))
        engine.create_database("db")
        svc = HttpService(engine, "127.0.0.1", 0)
        svc.start()
        yield svc
        svc.stop()
        engine.close()

    def test_threshold_ring_and_ctrl(self, server):
        port = server.port
        _post(server.port, "/write",
              f"m v=1 {BASE * NS}".encode(), db="db")
        # arm via ctrl: every query is "slow", ring bounded at 3
        status, body = _post(port, "/debug/ctrl", mod="obs",
                             slow_ms="0", slow_max="3", trace="1")
        assert status == 200
        doc = json.loads(body)
        assert doc["slow_ms"] == 0 and doc["slow_max"] == 3
        for i in range(5):
            _get(port, "/query", db="db",
                 q=f"SELECT count(v) FROM m WHERE time >= {i}")
        status, body = _get(port, "/debug/slow")
        assert status == 200
        slow = json.loads(body)
        assert slow["captured"] >= 5
        assert len(slow["records"]) == 3  # ring bound holds
        rec = slow["records"][-1]
        assert rec["database"] == "db"
        assert "SELECT count(v) FROM m" in rec["statement"]
        assert rec["duration_ms"] >= 0
        # tracing was armed: the record embeds the span tree
        assert rec["trace"] is not None
        assert rec["trace"]["root"]["name"] == "http_query"
        # disable via ctrl: capture stops
        _post(port, "/debug/ctrl", mod="obs", slow_ms="off", trace="0")
        before = json.loads(_get(port, "/debug/slow")[1])["captured"]
        _get(port, "/query", db="db", q="SELECT count(v) FROM m")
        after = json.loads(_get(port, "/debug/slow")[1])["captured"]
        assert after == before
        # bad knob = 400, never a silent default
        status, _ = _post(port, "/debug/ctrl", mod="obs", slow_ms="wat")
        assert status == 400

    def test_statement_redaction(self, server):
        slowlog.GLOBAL.configure(slow_ms=0.0)
        status, _ = _post(server.port, "/query", db="db",
                          q="CREATE USER u WITH PASSWORD 'hunter2'")
        assert status == 200
        snap = slowlog.GLOBAL.snapshot()
        assert snap["records"]
        for rec in snap["records"]:
            assert "hunter2" not in rec["statement"]

    def test_keepalive_after_ctrl_with_body(self, server):
        """POST bodies on the new ctrl endpoint are drained before the
        reply (the PR 6 keep-alive gotcha): the SAME connection serves
        the next request cleanly."""
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        try:
            conn.request("POST", "/debug/ctrl?mod=obs",
                         body=b"x" * 4096)
            r = conn.getresponse()
            r.read()
            assert r.status == 200
            conn.request("GET", "/debug/slow")
            r = conn.getresponse()
            r.read()
            assert r.status == 200
        finally:
            conn.close()


# -- pass-through inertness --------------------------------------------------


class TestPassThrough:
    def test_unset_knobs_allocate_nothing_and_match(self, tmp_path):
        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        eng.write_lines("db", "\n".join(
            f"cpu,host=h{i % 3} v={i} {(BASE + i) * NS}"
            for i in range(200)))
        eng.flush_all()
        ex = Executor(eng)
        q = (f"SELECT mean(v), max(v) FROM cpu WHERE time >= {BASE * NS}"
             f" AND time < {(BASE + 200) * NS} GROUP BY time(1m)")
        tracing.clear_recent()
        # knobs unset: no trace captured, no slow records
        out_off = ex.execute(q, db="db")
        assert not tracing.recent_traces()
        assert slowlog.GLOBAL.snapshot()["records"] == []
        # armed: identical bits
        tracing.set_trace_enabled(True)
        slowlog.GLOBAL.configure(slow_ms=0.0)
        out_on = ex.execute(q, db="db")
        assert json.dumps(out_off, sort_keys=True) == \
            json.dumps(out_on, sort_keys=True)
        assert tracing.recent_traces()
        assert slowlog.GLOBAL.snapshot()["records"]
        eng.close()

    def test_trace_ring_bounded(self):
        tracing.clear_recent()
        for i in range(tracing._RECENT_MAX + 50):
            t = tracing.Trace("query")
            t.finish()
            tracing.note_finished(i, t)
        assert len(tracing.recent_traces()) == tracing._RECENT_MAX
        # newest retained, oldest evicted
        assert tracing.get_trace(qid=0) is None
        assert tracing.get_trace(qid=tracing._RECENT_MAX + 49) is not None


# -- monitor self-writes -----------------------------------------------------


class TestMonitorSelfWrite:
    def test_monitor_pushes_ogt_series(self, tmp_path):
        from opengemini_tpu.services.monitor import (MONITOR_DB,
                                                     MonitorService)

        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        eng.write_lines("db", f"m v=1 {BASE * NS}")
        ex = Executor(eng)
        ex.execute("SELECT count(v) FROM m", db="db")
        # ensure at least one histogram family has data
        stats.observe_ns("query_stage_seconds", 2_000_000, stage="scan")
        svc = MonitorService(eng, interval_s=3600)
        svc.tick()
        assert MONITOR_DB in eng.databases
        res = ex.execute("SELECT last(ogt_executor_queries) FROM ogt",
                         db=MONITOR_DB)["results"][0]
        assert "error" not in res, res
        assert res["series"][0]["values"][0][1] >= 1
        res = ex.execute(
            "SELECT last(p50), last(p99) FROM ogt_query_stage_seconds "
            "WHERE stage = 'scan'", db=MONITOR_DB)["results"][0]
        assert "error" not in res, res
        row = res["series"][0]["values"][0]
        assert row[1] > 0 and row[2] >= row[1]
        # ogt_write_rows_total rides under its exported name too
        res = ex.execute("SELECT last(ogt_write_rows_total) FROM ogt",
                         db=MONITOR_DB)["results"][0]
        assert "error" not in res, res
        assert res["series"][0]["values"][0][1] >= 1
        eng.close()


# -- loadgen scrape consistency ----------------------------------------------


class TestLoadgenMetricsPoll:
    def test_scrape_vs_observed_consistency(self, tmp_path):
        import os
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
        from opengemini_tpu.server.http import HttpService
        from tools.loadgen import run_load

        engine = Engine(str(tmp_path / "data"))
        engine.create_database("load")
        svc = HttpService(engine, "127.0.0.1", 0)
        svc.start()
        try:
            out = run_load("127.0.0.1", svc.port, "load", clients=2,
                           duration_s=1.0, write_frac=1.0, batch_rows=10,
                           metrics_poll_s=0.2)
            mp = out["metrics_poll"]
            assert mp["scrapes"] >= 2
            assert mp["scrape_errors"] == 0
            assert out["acked_rows"] > 0
            assert mp["metric_delta_rows"] == out["acked_rows"]
            assert mp["consistent"] is True
        finally:
            svc.stop()
            engine.close()


# -- sherlock embeds the slow log --------------------------------------------


class TestSherlockEmbedsSlowLog:
    def test_dump_contains_slow_section(self, tmp_path):
        from opengemini_tpu.services.sherlock import SherlockService

        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        eng.write_lines("db", f"m v=1 {BASE * NS}")
        slowlog.GLOBAL.configure(slow_ms=0.0)
        ex = Executor(eng)
        ex.execute("SELECT count(v) FROM m", db="db")
        assert slowlog.GLOBAL.snapshot()["records"]
        svc = SherlockService(eng, cooldown_s=0.0)
        path = svc.diagnose("test")
        with open(path, encoding="utf-8") as f:
            text = f.read()
        assert "== slow queries ==" in text
        assert "SELECT count(v) FROM m" in text
        eng.close()


# -- one stage map a request (PR 39) ------------------------------------------


class _CountingLock:
    """A lock that counts how often it is taken."""

    def __init__(self, lock):
        self.lock, self.taken = lock, 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


class TestOneStageMap:
    def test_the_trackers_stages_are_a_view_of_the_roots_account(self):
        from opengemini_tpu.utils.querytracker import GLOBAL as TRACKER

        with tracing.request("t_onemap") as root:
            qid = TRACKER.register("SELECT 1", "db")
            try:
                with tracing.span("t_viewed"):
                    pass
                tracing.record_stage("t_viewed", 5)
                # the registry keeps the account itself, and no map beside it
                info = TRACKER._running[qid]
                assert info["account"] is root.acct
                assert "stages" not in info
                ns = root.acct.stages["t_viewed"][0]
                assert TRACKER.stages_of(qid) == {"t_viewed": ns}
                [snap] = [q for q in TRACKER.snapshot() if q["qid"] == qid]
                assert snap["stages"] == {"t_viewed": ns // 1_000_000}
                assert root.acct.qids == [qid]
            finally:
                TRACKER.unregister(qid)
            # the root's account outlives the statement: `send` is still its
            with tracing.span("t_after"):
                pass
            assert set(root.acct.stages) == {"t_viewed", "t_after"}

    def test_a_spans_close_takes_the_registrys_lock_and_the_histograms_only(
            self, monkeypatch):
        from opengemini_tpu.utils.querytracker import GLOBAL as TRACKER

        with tracing.span("t_locks"):       # its keys and histogram exist
            pass
        hist = stats.histogram("query_stage_seconds", stage="t_locks")
        locks = {"tracker": _CountingLock(TRACKER._lock),
                 "registry": _CountingLock(stats.GLOBAL._lock),
                 "pool": _CountingLock(tracing._POOL_LOCK),
                 "tail": _CountingLock(tracing._TAIL_LOCK),
                 "histogram": _CountingLock(hist._lock)}
        with tracing.request("t_lockroute"):
            qid = TRACKER.register("SELECT 1", "db")
            try:
                monkeypatch.setattr(TRACKER, "_lock", locks["tracker"])
                monkeypatch.setattr(stats.GLOBAL, "_lock", locks["registry"])
                monkeypatch.setattr(tracing, "_POOL_LOCK", locks["pool"])
                monkeypatch.setattr(tracing, "_TAIL_LOCK", locks["tail"])
                monkeypatch.setattr(hist, "_lock", locks["histogram"])
                for _ in range(10):
                    with tracing.span("t_locks"):
                        pass
                monkeypatch.undo()
            finally:
                TRACKER.unregister(qid)
        assert {k: v.taken for k, v in locks.items()} == {
            "tracker": 0, "registry": 10, "pool": 0, "tail": 0,
            "histogram": 10}

"""Distributed aggregate pushdown: peers ship per-(group, window)
partials, never raw columns, and the merged result matches a single-node
engine holding all the data (reference: rpc_transform + merge_transform
store-side partial aggregation)."""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.sql import ast, astjson
from opengemini_tpu.sql.parser import parse
from opengemini_tpu.storage.engine import Engine

NS = 10**9
BASE = 1_700_000_040  # minute-aligned


def _mk_cluster(tmp_path, rf=1, nids=("nA", "nB", "nC")):
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


def _query(addrs, nid, q):
    url = (f"http://{addrs[nid]}/query?" +
           urllib.parse.urlencode({"q": q, "db": "db", "epoch": "ns"}))
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


DATA_LINES = []
for w in range(12):  # one point per week -> distinct shard groups
    t = (BASE + w * 7 * 86400) * NS
    host = ["a", "b"][w % 2]
    DATA_LINES.append(f"cpu,host={host} v={w * 1.5},c={w}i {t}")
    DATA_LINES.append(f"cpu,host={host} v={w * 1.5 + 0.25} {t + 30 * NS}")


QUERIES = [
    "SELECT count(v), sum(v), mean(v) FROM cpu",
    "SELECT min(v), max(v), spread(v), stddev(v) FROM cpu",
    "SELECT first(v), last(v) FROM cpu",
    "SELECT sum(c) FROM cpu",  # int64-exact partials
    "SELECT mean(v) FROM cpu GROUP BY host",
    "SELECT count(v), mean(v) FROM cpu GROUP BY time(2w)",
    "SELECT max(v) FROM cpu WHERE host = 'a' GROUP BY time(4w)",
    "SELECT sum(v) FROM cpu WHERE v > 3",  # field-filter pushdown
    # mixed tag/field trees push down too (peers re-evaluate with tag
    # columns injected; coordinator ships mixed_expr on the wire)
    "SELECT sum(v), count(v) FROM cpu WHERE host = 'a' OR v > 3",
    "SELECT max(v) FROM cpu WHERE host = 'b' OR c = 4 GROUP BY host",
    # rank-based aggregates push down via (value, count) multiset partials
    "SELECT percentile(v, 50), median(v) FROM cpu",
    "SELECT percentile(v, 90) FROM cpu GROUP BY host",
    "SELECT count(distinct(c)) FROM cpu",
    "SELECT median(v) FROM cpu GROUP BY time(4w)",
    "SELECT mean(v) FROM cpu GROUP BY *",
    "SELECT count(v) FROM cpu WHERE time >= {t0} AND time < {t1}",
]


class TestPushdownParity:
    def test_three_node_results_match_single_node(self, tmp_path):
        # oracle: one engine holding everything
        solo = Engine(str(tmp_path / "solo"))
        solo.create_database("db")
        solo.write_lines("db", "\n".join(DATA_LINES))
        oracle = Executor(solo)

        nodes, addrs = _mk_cluster(tmp_path)
        url = f"http://{addrs['nA']}/write?db=db"
        req = urllib.request.Request(
            url, data="\n".join(DATA_LINES).encode(), method="POST")
        urllib.request.urlopen(req, timeout=30).read()
        # data genuinely split across nodes
        per_node = [
            sum(len(sh.read_series("cpu", sid).times)
                for sh in e.shards_for_range("db", None, -(2**62), 2**62)
                for sid in sh.index.series_ids("cpu"))
            for e, _svc in nodes.values()
        ]
        assert sum(per_node) == len(DATA_LINES)
        assert sum(1 for n in per_node if n) >= 2, per_node

        t0 = (BASE + 7 * 86400) * NS
        t1 = (BASE + 9 * 7 * 86400) * NS
        for q in QUERIES:
            q = q.format(t0=t0, t1=t1)
            want = oracle.execute(q, db="db")["results"][0]
            assert "error" not in want, (q, want)
            for nid in nodes:
                got = _query(addrs, nid, q)["results"][0]
                assert "error" not in got, (q, nid, got)
                self._assert_series_close(q, want, got)
        solo.close()
        _close(nodes)

    def _assert_series_close(self, q, want, got):
        ws = {tuple(sorted((s.get("tags") or {}).items())): s
              for s in want.get("series", [])}
        gs = {tuple(sorted((s.get("tags") or {}).items())): s
              for s in got.get("series", [])}
        assert ws.keys() == gs.keys(), (q, want, got)
        for k in ws:
            wrows, grows = ws[k]["values"], gs[k]["values"]
            assert len(wrows) == len(grows), (q, k, wrows, grows)
            for wr, gr in zip(wrows, grows):
                assert wr[0] == gr[0], (q, k, wr, gr)  # timestamps exact
                for wv, gv in zip(wr[1:], gr[1:]):
                    if wv is None or gv is None:
                        assert wv == gv, (q, k, wr, gr)
                    else:
                        assert gv == pytest.approx(wv, rel=1e-6), (q, k, wr, gr)

    def test_selector_time_from_remote_point(self, tmp_path):
        """Bare first()/last()/min()/max() report the exact ns timestamp
        of the winning point even when it lives on a peer."""
        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        week = 7 * 86400
        lines = "\n".join(
            f"m v={w} {(BASE + w * week) * NS + 123456789}" for w in range(8))
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db", data=lines.encode(),
            method="POST")
        urllib.request.urlopen(req, timeout=30).read()
        for nid in nodes:
            res = _query(addrs, nid, "SELECT first(v) FROM m")
            [row] = res["results"][0]["series"][0]["values"]
            assert row == [BASE * NS + 123456789, 0.0], (nid, row)
            res = _query(addrs, nid, "SELECT last(v) FROM m")
            [row] = res["results"][0]["series"][0]["values"]
            assert row == [(BASE + 7 * week) * NS + 123456789, 7.0], (nid, row)
            res = _query(addrs, nid, "SELECT max(v) FROM m")
            [row] = res["results"][0]["series"][0]["values"]
            assert row == [(BASE + 7 * week) * NS + 123456789, 7.0], (nid, row)
        _close(nodes)

    def test_remote_only_group_appears(self, tmp_path):
        """A tag value whose series live entirely on peers still shows up
        in GROUP BY results on the coordinator."""
        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        week = 7 * 86400
        lines = "\n".join(
            f"m,host=h{w % 4} v={w} {(BASE + w * week) * NS}"
            for w in range(8))
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db", data=lines.encode(),
            method="POST")
        urllib.request.urlopen(req, timeout=30).read()
        for nid in nodes:
            res = _query(addrs, nid, "SELECT sum(v) FROM m GROUP BY host")
            by_host = {s["tags"]["host"]: s["values"][0][1]
                       for s in res["results"][0]["series"]}
            assert by_host == {"h0": 0 + 4, "h1": 1 + 5, "h2": 2 + 6,
                               "h3": 3 + 7}, (nid, by_host)
        _close(nodes)


class TestWireShape:
    def test_aggregate_query_never_ships_raw_columns(self, tmp_path):
        """The money property: an eligible aggregate query fans out
        select_meta + select_partials only — /internal/scan (raw rows)
        is never touched, and the partial payload is O(groups x windows),
        independent of row count."""
        from opengemini_tpu.parallel import cluster as cl

        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        week = 7 * 86400
        lines = []
        for w in range(4):
            base = (BASE + w * week) * NS
            lines += [f"m v={i} {base + i * NS}" for i in range(500)]
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db",
            data="\n".join(lines).encode(), method="POST")
        urllib.request.urlopen(req, timeout=60).read()

        (eA, svcA) = nodes["nA"]
        router = svcA.router
        calls = []
        orig = router._post_raw

        def spy(addr, path, body, timeout=None):
            data, ct = orig(addr, path, body, timeout=timeout)
            calls.append((path, len(data)))
            return data, ct

        router._post_raw = spy
        res = _query(addrs, "nA", "SELECT mean(v) FROM m GROUP BY time(1w)")
        assert "error" not in res["results"][0], res
        paths = {p for p, _n in calls}
        assert "/internal/select_partials" in paths, calls
        assert "/internal/scan" not in paths, calls
        partial_bytes = sum(n for p, n in calls
                            if p == "/internal/select_partials")
        # 2000 rows of raw f64 columns would be ~50KB+; partials for
        # 1 group x ~5 windows are a few hundred bytes
        assert partial_bytes < 4096, calls

        # the raw exchange for the same data really is O(rows)
        raw = cl.serialize_series_binary(
            nodes["nB"][0], "db", None, "m", -(2**62), 2**62)
        assert len(raw) > 10 * partial_bytes
        _close(nodes)

    def test_non_mergeable_falls_back_to_raw(self, tmp_path):
        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        week = 7 * 86400
        lines = "\n".join(
            f"m v={w} {(BASE + w * week) * NS}" for w in range(8))
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db", data=lines.encode(),
            method="POST")
        urllib.request.urlopen(req, timeout=30).read()
        router = nodes["nA"][1].router
        calls = []
        orig = router._post_raw

        def spy(addr, path, body, timeout=None):
            data, ct = orig(addr, path, body, timeout=timeout)
            calls.append(path)
            return data, ct

        router._post_raw = spy
        # mode() is host-path, not partial-mergeable -> raw exchange
        res = _query(addrs, "nA", "SELECT mode(v) FROM m")
        assert "error" not in res["results"][0], res
        assert "/internal/scan" in calls, calls
        assert "/internal/select_partials" not in calls, calls
        _close(nodes)

    def test_percentile_ships_multiset_not_raw(self, tmp_path):
        """Rank aggregates push down: wire bytes scale with distinct
        values per group, not rows."""
        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        week = 7 * 86400
        lines = []
        for w in range(4):
            base = (BASE + w * week) * NS
            # 2000 rows/shard-group but only 7 distinct values
            lines += [f"m v={i % 7} {base + i * NS}" for i in range(2000)]
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db",
            data="\n".join(lines).encode(), method="POST")
        urllib.request.urlopen(req, timeout=60).read()
        router = nodes["nA"][1].router
        calls = []
        orig = router._post_raw

        def spy(addr, path, body, timeout=None):
            data, ct = orig(addr, path, body, timeout=timeout)
            calls.append((path, len(data)))
            return data, ct

        router._post_raw = spy
        res = _query(
            addrs, "nA",
            "SELECT percentile(v, 50), count(distinct(v)) FROM m")
        assert "error" not in res["results"][0], res
        paths = {p for p, _n in calls}
        assert "/internal/select_partials" in paths, calls
        assert "/internal/scan" not in paths, calls
        partial_bytes = sum(n for p, n in calls
                            if p == "/internal/select_partials")
        # 8000 raw f64 rows would be ~128KB+; 7-distinct multisets for a
        # handful of segments are well under 4KB
        assert partial_bytes < 4096, calls
        _close(nodes)


class TestAstJson:
    def test_round_trip_condition_trees(self):
        [stmt] = parse(
            "SELECT mean(v) FROM cpu WHERE (host = 'a' OR host =~ /b.*/) "
            "AND v > 3.5 AND ok = true AND s != 'x' "
            "GROUP BY time(1m), host fill(previous)")
        doc = astjson.to_json(stmt.condition)
        back = astjson.from_json(doc)
        assert back == stmt.condition
        # whole statements round-trip too
        doc2 = astjson.to_json(stmt)
        assert astjson.from_json(doc2) == stmt

    def test_unknown_node_rejected(self):
        with pytest.raises(TypeError):
            astjson.to_json(object())
        with pytest.raises(ValueError):
            astjson.from_json({"_n": "Nope"})


class TestMergeEdgeCases:
    def test_peer_with_other_measurements_only(self, tmp_path):
        """A peer holding rows only for OTHER measurements still answers
        the partial round (with empty docs); the merged mean must equal
        the local mean, including when the local side used the
        pre-aggregation fast path."""
        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        # same shard group: route key decides the owner; write via nA so
        # cpu lands wherever it lands, and write 'other' the same way
        week = 7 * 86400
        lines = []
        for w in range(6):
            t = (BASE + w * week) * NS
            lines.append(f"cpu v={w} {t}")
            lines.append(f"other u={w * 10} {t}")
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db",
            data="\n".join(lines).encode(), method="POST")
        urllib.request.urlopen(req, timeout=30).read()
        for nid in nodes:
            res = _query(addrs, nid, "SELECT mean(v), count(v) FROM cpu")
            [row] = res["results"][0]["series"][0]["values"]
            assert row[1] == pytest.approx(2.5) and row[2] == 6, (nid, row)
        _close(nodes)


class TestSelectorTieBreak:
    def test_min_value_tie_breaks_by_earliest_time(self, tmp_path):
        """Equal min values on different nodes: the reported time must be
        the EARLIEST occurrence, matching the single-device kernels."""
        nodes, addrs = _mk_cluster(tmp_path, nids=("nA", "nB"))
        week = 7 * 86400
        # same value 1.0 in two different shard groups (different owners)
        lines = "\n".join([
            f"m v=1.0 {BASE * NS}",
            f"m v=1.0 {(BASE + week) * NS}",
            f"m v=9.0 {(BASE + 2 * week) * NS}",
        ])
        req = urllib.request.Request(
            f"http://{addrs['nA']}/write?db=db", data=lines.encode(),
            method="POST")
        urllib.request.urlopen(req, timeout=30).read()
        for nid in nodes:
            res = _query(addrs, nid, "SELECT min(v) FROM m")
            [row] = res["results"][0]["series"][0]["values"]
            assert row == [BASE * NS, 1.0], (nid, row)
        _close(nodes)

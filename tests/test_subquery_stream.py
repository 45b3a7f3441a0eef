"""Subqueries + stream engine tests."""

import numpy as np
import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.services.stream import StreamService
from opengemini_tpu.storage.engine import Engine, NS

BASE = 1_700_000_040


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("db")
    yield e, Executor(e)
    e.close()


def q(ex, text):
    return ex.execute(text, db="db", now_ns=(BASE + 10_000) * NS)


def series_of(res, i=0):
    return res["results"][0]["series"][i]


class TestSubqueries:
    def test_agg_over_subquery_agg(self, env):
        e, ex = env
        # per-host minute means, then the max of those means
        lines = "\n".join(
            f"cpu,host=h{i%3} v={(i%3)*10 + i%5} {(BASE + i*10) * NS}"
            for i in range(18)
        )
        e.write_lines("db", lines)
        res = q(
            ex,
            f"SELECT max(mean) FROM (SELECT mean(v) FROM cpu WHERE "
            f"time >= {BASE*NS} AND time < {(BASE+180)*NS} "
            f"GROUP BY time(1m), host)",
        )
        s = series_of(res)
        # h2 has the largest values; its worst-case mean is still > h1/h0
        inner = q(ex, f"SELECT mean(v) FROM cpu WHERE time >= {BASE*NS} AND "
                      f"time < {(BASE+180)*NS} GROUP BY time(1m), host")
        best = max(
            v for srs in inner["results"][0]["series"] for _t, v in srs["values"]
        )
        assert s["values"][0][1] == pytest.approx(best)

    def test_subquery_preserves_tags_for_group_by(self, env):
        e, ex = env
        e.write_lines("db", "\n".join([
            f"m,h=a v=1 {BASE*NS}", f"m,h=a v=3 {(BASE+1)*NS}",
            f"m,h=b v=10 {BASE*NS}",
        ]))
        res = q(
            ex,
            "SELECT sum(v) FROM (SELECT v FROM m) GROUP BY h",
        )
        series = {s["tags"]["h"]: s["values"][0][1] for s in res["results"][0]["series"]}
        assert series == {"a": 4.0, "b": 10.0}

    def test_nested_subquery(self, env):
        e, ex = env
        e.write_lines("db", "\n".join(f"m v={i} {(BASE+i)*NS}" for i in range(10)))
        res = q(ex, "SELECT count(v) FROM (SELECT v FROM (SELECT v FROM m))")
        assert series_of(res)["values"][0][1] == 10

    def test_subquery_where_on_inner_column(self, env):
        e, ex = env
        e.write_lines("db", "\n".join(f"m v={i} {(BASE+i)*NS}" for i in range(10)))
        res = q(ex, "SELECT count(v) FROM (SELECT v FROM m) WHERE v >= 5")
        assert series_of(res)["values"][0][1] == 5


class TestStream:
    CS = ("CREATE STREAM s1 ON SELECT sum(v), count(v) INTO cpu_1m FROM cpu "
          "GROUP BY time(1m), host")

    def test_create_show_drop(self, env):
        e, ex = env
        res = q(ex, self.CS)
        assert "error" not in res["results"][0]
        s = series_of(q(ex, "SHOW STREAMS"))
        assert s["values"][0][0] == "s1"
        q(ex, "DROP STREAM s1")
        res = q(ex, "SHOW STREAMS")
        assert all(not srs["values"] for srs in res["results"][0].get("series", []))

    def test_stream_persisted(self, env):
        e, ex = env
        q(ex, self.CS)
        e.close()
        e2 = Engine(e.root)
        assert "s1" in e2.databases["db"].streams
        e2.close()

    def test_unsupported_agg_rejected(self, env):
        e, ex = env
        res = q(ex, "CREATE STREAM sx ON SELECT percentile(v, 99) INTO x FROM cpu "
                    "GROUP BY time(1m)")
        assert "supports only" in res["results"][0]["error"]

    def test_ingest_window_flush(self, env):
        e, ex = env
        svc = StreamService(e, interval_s=3600)
        q(ex, self.CS)
        # two closed windows + one open
        lines = "\n".join(
            f"cpu,host=h0 v={i} {(BASE + i*10) * NS}" for i in range(13)
        )
        e.write_lines("db", lines)
        flushed = svc.handle(now_ns=(BASE + 125) * NS)
        assert flushed == 2
        out = q(ex, "SELECT sum, count FROM cpu_1m GROUP BY host")
        s = series_of(out)
        assert s["tags"]["host"] == "h0"
        vals = s["values"]
        assert vals[0][1] == sum(range(6)) and vals[0][2] == 6
        assert vals[1][1] == sum(range(6, 12)) and vals[1][2] == 6
        # open window not flushed yet
        assert len(vals) == 2
        # later tick flushes the rest
        assert svc.handle(now_ns=(BASE + 240) * NS) == 1

    def test_delay_holds_window(self, env):
        e, ex = env
        svc = StreamService(e, interval_s=3600)
        q(ex, "CREATE STREAM s2 ON SELECT mean(v) INTO m_1m FROM m "
              "GROUP BY time(1m) DELAY 30s")
        e.write_lines("db", f"m v=4 {BASE*NS}")
        assert svc.handle(now_ns=(BASE + 70) * NS) == 0  # inside delay
        assert svc.handle(now_ns=(BASE + 95) * NS) == 1
        out = q(ex, "SELECT mean FROM m_1m")
        assert series_of(out)["values"][0][1] == 4.0


class TestReviewRegressions:
    def test_late_data_dropped_not_reaggregated(self, env):
        e, ex = env
        svc = StreamService(e, interval_s=3600)
        q(ex, TestStream.CS)
        lines = "\n".join(f"cpu,host=h0 v={i} {(BASE + i*10) * NS}" for i in range(6))
        e.write_lines("db", lines)
        assert svc.handle(now_ns=(BASE + 70) * NS) == 1
        # late point for the already-flushed window: must be dropped
        e.write_lines("db", f"cpu,host=h0 v=100 {(BASE + 5) * NS}")
        assert svc.handle(now_ns=(BASE + 130) * NS) == 0
        out = q(ex, "SELECT sum FROM cpu_1m")
        vals = [r[1] for r in series_of(out)["values"]]
        assert vals == [sum(range(6))]  # not overwritten by 100

    def test_self_feed_rejected_even_qualified(self, env):
        e, ex = env
        res = q(ex, "CREATE STREAM bad ON SELECT sum(v) INTO db..cpu FROM cpu "
                    "GROUP BY time(1m)")
        assert "differ from its source" in res["results"][0]["error"]
        res = q(ex, "CREATE STREAM bad2 ON SELECT sum(v) INTO x FROM db2..cpu "
                    "GROUP BY time(1m)")
        assert "unqualified" in res["results"][0]["error"]

    def test_subquery_time_pushdown_correct(self, env):
        e, ex = env
        week = 7 * 24 * 3600
        e.write_lines("db", f"m v=1 {BASE * NS}\nm v=2 {(BASE + week) * NS}")
        res = ex.execute(
            f"SELECT count(v) FROM (SELECT v FROM m) WHERE time >= {(BASE + week - 60) * NS}",
            db="db", now_ns=(BASE + week + 100) * NS,
        )
        assert series_of(res)["values"][0][1] == 1

    def test_concurrent_stream_ddl_does_not_break_ingest(self, env):
        import threading

        e, ex = env
        svc = StreamService(e, interval_s=3600)
        q(ex, TestStream.CS)
        stop = threading.Event()

        def ddl_loop():
            i = 0
            while not stop.is_set():
                q(ex, f"CREATE STREAM tmp{i} ON SELECT sum(v) INTO t{i} FROM src "
                      f"GROUP BY time(1m)")
                q(ex, f"DROP STREAM tmp{i}")
                i += 1

        t = threading.Thread(target=ddl_loop)
        t.start()
        try:
            for k in range(20):
                e.write_lines("db", f"cpu,host=h0 v={k} {(BASE + k) * NS}")
        finally:
            stop.set()
            t.join()
        svc.handle(now_ns=(BASE + 200) * NS)
        out = q(ex, "SELECT count FROM cpu_1m")
        assert series_of(out)["values"][0][1] == 20  # no dropped batches


class TestChunkedSubquery:
    """Chunked inner evaluation: big inner scans
    materialize chunk-by-chunk into the spill engine; results must be
    identical to single-shot evaluation."""

    def _both(self, ex, query, monkeypatch):
        from opengemini_tpu.query import subquery as sq

        single = q(ex, query)
        monkeypatch.setattr(sq, "SUBQUERY_CHUNK_ROWS", 100)
        monkeypatch.setattr(sq, "SUBQUERY_CHUNK_TARGET", 500)
        chunked = q(ex, query)
        monkeypatch.setattr(sq, "SUBQUERY_CHUNK_ROWS", 5_000_000)
        monkeypatch.setattr(sq, "SUBQUERY_CHUNK_TARGET", 2_000_000)
        return single, chunked

    def _write(self, e, hosts=4, points=2500):
        lines = "\n".join(
            f"cpu,host=h{i % hosts} v={(i % 7) + (i % hosts)} "
            f"{(BASE + i) * NS}"
            for i in range(points * hosts))
        e.write_lines("db", lines)
        e.flush_all()

    def test_agg_outer_over_agg_inner(self, env, monkeypatch):
        e, ex = env
        self._write(e)
        query = (
            "SELECT max(mean), count(mean) FROM "
            f"(SELECT mean(v) FROM cpu WHERE time >= {BASE * NS} AND "
            f"time < {(BASE + 10000) * NS} GROUP BY time(1m), host) "
            f"WHERE time >= {BASE * NS} AND time < {(BASE + 10000) * NS} "
            "GROUP BY time(10m)")
        single, chunked = self._both(ex, query, monkeypatch)
        assert "error" not in single["results"][0]
        assert single == chunked

    def test_raw_inner_with_filter_outer(self, env, monkeypatch):
        e, ex = env
        self._write(e)
        query = (
            "SELECT count(v) FROM "
            f"(SELECT v FROM cpu WHERE time >= {BASE * NS} AND "
            f"time < {(BASE + 10000) * NS}) WHERE v > 3")
        single, chunked = self._both(ex, query, monkeypatch)
        assert single == chunked

    def test_transform_inner_not_chunked(self, env, monkeypatch):
        """difference() needs neighbors across chunk boundaries: the
        planner must refuse to chunk it (and results stay right)."""
        from opengemini_tpu.query import subquery as sq

        e, ex = env
        self._write(e, hosts=1, points=500)
        query = (
            "SELECT max(difference) FROM "
            "(SELECT difference(mean(v)) AS difference FROM cpu WHERE "
            f"time >= {BASE * NS} AND time < {(BASE + 1000) * NS} "
            "GROUP BY time(1m))")
        single = q(ex, query)
        inner = __import__("opengemini_tpu.sql.parser",
                           fromlist=["parse_one"]).parse_one(
            f"SELECT difference(mean(v)) FROM cpu WHERE time >= {BASE*NS} "
            f"AND time < {(BASE+1000)*NS} GROUP BY time(1m)")
        assert not sq._subquery_chunk_safe(inner)
        monkeypatch.setattr(sq, "SUBQUERY_CHUNK_ROWS", 10)
        chunked = q(ex, query)
        assert single == chunked  # un-chunkable: same single-shot path

    def test_row_cap_fails_loudly(self, env, monkeypatch):
        from opengemini_tpu.query import subquery as sq

        e, ex = env
        self._write(e, hosts=2, points=300)
        monkeypatch.setattr(sq, "SUBQUERY_MAX_ROWS", 100)
        res = q(ex, "SELECT count(v) FROM (SELECT v FROM cpu)")
        assert "more than 100 rows" in res["results"][0]["error"]

"""Services + SELECT INTO + downsample tests (reference: services/ tests
and engine_downsample paths)."""

import numpy as np
import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.services.continuous import ContinuousQueryService
from opengemini_tpu.services.retention import RetentionService
from opengemini_tpu.storage.engine import DownsamplePolicy, Engine, NS

BASE = 1_700_000_040  # minute-aligned


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("db")
    yield e, Executor(e)
    e.close()


def series_of(res, i=0):
    return res["results"][0]["series"][i]


def q(ex, text, now=None):
    return ex.execute(text, db="db", now_ns=(now or (BASE + 10_000)) * NS)


class TestSelectInto:
    def test_into_writes_aggregates(self, env):
        e, ex = env
        lines = "\n".join(
            f"cpu,host=h{i%2} v={i} {(BASE + i * 10) * NS}" for i in range(30)
        )
        e.write_lines("db", lines)
        res = q(
            ex,
            f"SELECT mean(v) INTO cpu_1m FROM cpu WHERE time >= {BASE*NS} AND "
            f"time < {(BASE+300)*NS} GROUP BY time(1m), host",
        )
        [row] = res["results"][0]["series"][0]["values"]
        assert row[1] == 10  # 5 windows x 2 hosts
        out = q(ex, "SELECT mean FROM cpu_1m GROUP BY host")
        series = out["results"][0]["series"]
        assert len(series) == 2
        assert series[0]["columns"] == ["time", "mean"]
        assert len(series[0]["values"]) == 5

    def test_into_preserves_int_and_bool(self, env):
        e, ex = env
        e.write_lines("db", f"m i=5i,b=true {BASE*NS}")
        q(ex, f"SELECT last(i), last(b) INTO m2 FROM m WHERE time >= {BASE*NS}")
        out = q(ex, "SELECT last, last_1 FROM m2")
        [row] = out["results"][0]["series"][0]["values"]
        assert row[1] == 5 and row[2] is True


class TestContinuousQueries:
    CQ = (
        'CREATE CONTINUOUS QUERY cq1 ON db BEGIN '
        'SELECT mean(v) INTO cpu_1m FROM cpu GROUP BY time(1m), host END'
    )

    def test_create_show_drop(self, env):
        e, ex = env
        res = q(ex, self.CQ)
        assert "error" not in res["results"][0]
        res = q(ex, "SHOW CONTINUOUS QUERIES")
        series = {s["name"]: s for s in res["results"][0]["series"]}
        assert series["db"]["values"][0][0] == "cq1"
        assert "SELECT mean(v) INTO cpu_1m" in series["db"]["values"][0][1]
        q(ex, "DROP CONTINUOUS QUERY cq1 ON db")
        res = q(ex, "SHOW CONTINUOUS QUERIES")
        assert all(not s["values"] for s in res["results"][0].get("series", []))

    def test_cq_persisted_across_reopen(self, env, tmp_path):
        e, ex = env
        q(ex, self.CQ)
        e.close()
        e2 = Engine(e.root)
        assert "cq1" in e2.databases["db"].continuous_queries
        e2.close()

    def test_cq_service_materializes_windows(self, env):
        e, ex = env
        q(ex, self.CQ)
        lines = "\n".join(
            f"cpu,host=h0 v={i} {(BASE + i * 10) * NS}" for i in range(24)
        )
        e.write_lines("db", lines)  # 4 minutes of data
        svc = ContinuousQueryService(e, ex, interval_s=3600)
        # influx default: each run computes only the most recently closed
        # window [end-every, end)
        ran = svc.handle(now_ns=(BASE + 180) * NS)
        assert ran == 1
        out = q(ex, "SELECT mean FROM cpu_1m")
        vals = out["results"][0]["series"][0]["values"]
        assert [v for _t, v in vals] == [14.5]  # window [120, 180)
        # second tick immediately: nothing new closed
        assert svc.handle(now_ns=(BASE + 185) * NS) == 0
        # a minute later the next window [180, 240) closes
        assert svc.handle(now_ns=(BASE + 248) * NS) == 1
        out = q(ex, "SELECT mean FROM cpu_1m")
        vals = out["results"][0]["series"][0]["values"]
        assert [v for _t, v in vals] == [14.5, 20.5]

    def test_cq_resample_for_extends_lookback(self, env):
        e, ex = env
        q(
            ex,
            'CREATE CONTINUOUS QUERY cq2 ON db RESAMPLE FOR 3m BEGIN '
            'SELECT mean(v) INTO cpu_1m_r FROM cpu GROUP BY time(1m) END',
        )
        lines = "\n".join(
            f"cpu,host=h0 v={i} {(BASE + i * 10) * NS}" for i in range(18)
        )
        e.write_lines("db", lines)
        svc = ContinuousQueryService(e, ex, interval_s=3600)
        assert svc.handle(now_ns=(BASE + 180) * NS) == 1
        out = q(ex, "SELECT mean FROM cpu_1m_r")
        vals = out["results"][0]["series"][0]["values"]
        assert [v for _t, v in vals] == [2.5, 8.5, 14.5]


class TestDownsample:
    def test_rewrite_downsampled_means(self, env):
        e, ex = env
        lines = "\n".join(
            f"cpu,host=h{i%2} v={i}.0,c={i}i {(BASE + i * 10) * NS}" for i in range(60)
        )
        e.write_lines("db", lines)
        [shard] = e.all_shards()
        rows_before = 60
        written = shard.rewrite_downsampled(60 * NS)
        assert 0 < written < rows_before
        out = q(ex, "SELECT v FROM cpu WHERE host = 'h0'")
        vals = out["results"][0]["series"][0]["values"]
        # h0 points: i even; first minute window holds i in {0,2,4} -> mean 2
        assert vals[0][1] == pytest.approx(2.0)
        # int field defaults to sum and stays int
        out = q(ex, "SELECT c FROM cpu WHERE host = 'h0'")
        v0 = out["results"][0]["series"][0]["values"][0][1]
        assert v0 == 0 + 2 + 4 and isinstance(v0, int)

    def test_downsample_policy_service_flow(self, env):
        e, ex = env
        e.write_lines("db", f"cpu v=1 {BASE * NS}\ncpu v=3 {(BASE + 30) * NS}")
        e.add_downsample_policy("db", "autogen", DownsamplePolicy(
            age_ns=1 * NS, every_ns=60 * NS))
        week = 7 * 24 * 3600
        now = (BASE + 2 * week) * NS
        assert e.run_downsample(now_ns=now) == 1
        # idempotent: already at level
        assert e.run_downsample(now_ns=now) == 0
        out = q(ex, "SELECT v FROM cpu")
        [row] = out["results"][0]["series"][0]["values"]
        assert row[1] == pytest.approx(2.0)

    def test_policy_persisted(self, env):
        e, ex = env
        e.add_downsample_policy("db", "autogen", DownsamplePolicy(1, 60 * NS))
        e.close()
        e2 = Engine(e.root)
        assert e2.databases["db"].downsample["autogen"][0].every_ns == 60 * NS
        e2.close()


class TestRetentionService:
    def test_tick_drops_expired(self, env, monkeypatch):
        e, ex = env
        e.create_retention_policy("db", "short", duration_ns=24 * 3600 * NS, default=True)
        e.write_lines("db", f"cpu v=1 {1 * NS}")  # ancient
        svc = RetentionService(e, interval_s=3600)
        import opengemini_tpu.storage.engine as eng_mod

        monkeypatch.setattr(
            eng_mod._time, "time_ns", lambda: (BASE + 10_000) * NS
        )
        svc.tick()
        assert e.shards_for_range("db", "short", 0, 2**62) == []


class TestReadOnlyGating:
    def test_show_cq_allowed_on_get_into_rejected(self, env):
        e, ex = env
        res = ex.execute("SHOW CONTINUOUS QUERIES", db="db", read_only=True)
        assert "error" not in res["results"][0]
        res = ex.execute("SELECT mean(v) INTO x FROM cpu", db="db", read_only=True)
        assert "must be sent via POST" in res["results"][0]["error"]


class TestReviewRegressions:
    def test_into_with_weird_tag_values(self, env):
        """Tags with spaces/commas must survive SELECT INTO (structured
        write path, no line-protocol round trip)."""
        import opengemini_tpu.ingest.line_protocol as lp

        e, ex = env
        e.write_lines("db", r"m,host=web\ server\,1 v=4 %d" % (BASE * NS))
        res = q(ex, f"SELECT mean(v) INTO m2 FROM m WHERE time >= {BASE*NS} GROUP BY host")
        assert res["results"][0]["series"][0]["values"][0][1] == 1
        out = q(ex, "SELECT mean FROM m2 GROUP BY host")
        s = out["results"][0]["series"][0]
        assert s["tags"]["host"] == "web server,1"
        assert s["values"][0][1] == 4.0

    def test_into_type_conflict_is_statement_error(self, env):
        e, ex = env
        e.write_lines("db", f"tgt mean=1i {BASE*NS}")  # mean is INT in target
        e.write_lines("db", f"m v=1.5 {(BASE+1)*NS}")
        res = q(ex, f"SELECT mean(v) INTO tgt FROM m WHERE time >= {BASE*NS}")
        assert "type conflict" in res["results"][0]["error"]

    def test_downsample_int_sum_exact_above_f32(self, env):
        """Ints > 2^24 must survive downsampling exactly (host int64 path)."""
        e, ex = env
        big = 100_000_001
        e.write_lines(
            "db", f"m c={big}i {BASE*NS}\nm c={big}i {(BASE+10)*NS}"
        )
        [shard] = e.all_shards()
        shard.rewrite_downsampled(60 * NS)
        out = q(ex, "SELECT c FROM m")
        [row] = out["results"][0]["series"][0]["values"]
        assert row[1] == 2 * big

    def test_failing_cq_does_not_starve_others(self, env):
        e, ex = env
        # cq_bad writes into a dropped database; cq_ok must still run
        q(ex, 'CREATE CONTINUOUS QUERY a_bad ON db BEGIN '
              'SELECT mean(v) INTO missing_db..x FROM cpu GROUP BY time(1m) END')
        q(ex, 'CREATE CONTINUOUS QUERY b_ok ON db BEGIN '
              'SELECT mean(v) INTO ok_1m FROM cpu GROUP BY time(1m) END')
        e.write_lines("db", "\n".join(
            f"cpu v={i} {(BASE + i*10)*NS}" for i in range(12)))
        svc = ContinuousQueryService(e, ex, interval_s=3600)
        ran = svc.handle(now_ns=(BASE + 120) * NS)
        assert ran == 1  # only b_ok
        out = q(ex, "SELECT mean FROM ok_1m")
        assert out["results"][0]["series"][0]["values"]

    def test_structured_wal_replay(self, env):
        """Kind-2 WAL entries (INTO writes) must replay after a crash."""
        e, ex = env
        e.write_lines("db", f"m v=7 {BASE*NS}")
        q(ex, f"SELECT last(v) INTO m2 FROM m WHERE time >= {BASE*NS}")
        for sh in e.all_shards():
            sh.wal.flush()
        root = e.root
        # crash: reopen without close
        e2 = Engine(root)
        ex2 = Executor(e2)
        out = ex2.execute("SELECT last FROM m2", db="db", now_ns=(BASE+100)*NS)
        assert out["results"][0]["series"][0]["values"][0][1] == 7.0
        e2.close()


class TestMonitorService:
    def test_stats_pushed_to_internal(self, env):
        from opengemini_tpu.services.monitor import MonitorService
        from opengemini_tpu.utils.stats import GLOBAL

        e, ex = env
        GLOBAL.incr("executor", "queries", 5)
        svc = MonitorService(e, interval_s=3600, hostname="n1")
        svc.tick()
        res = ex.execute("SELECT last(queries) FROM executor", db="_internal",
                         now_ns=None)
        v = res["results"][0]["series"][0]["values"][0][1]
        assert v >= 5


class TestBackupRestore:
    def test_full_and_incremental_roundtrip(self, env, tmp_path):
        import time as _t

        from opengemini_tpu.tools import backup as bk
        from opengemini_tpu.storage.engine import Engine

        e, ex = env
        e.write_lines("db", f"m v=1 {BASE*NS}")
        e.flush_all()
        full_dir = str(tmp_path / "bk_full")
        m = bk.backup(e.root, full_dir)
        assert m["kind"] == "full" and any(f.endswith(".tsf") for f in m["files"])
        since = _t.time_ns()
        e.write_lines("db", f"m v=2 {(BASE+60)*NS}")
        e.flush_all()
        inc_dir = str(tmp_path / "bk_inc")
        m2 = bk.backup(e.root, inc_dir, since_ns=since)
        assert m2["kind"] == "incremental"
        # restore into a fresh dir: full then incremental
        restore_dir = str(tmp_path / "restored")
        bk.restore(full_dir, restore_dir)
        bk.restore(inc_dir, restore_dir)
        e2 = Engine(restore_dir)
        ex2 = Executor(e2)
        res = ex2.execute("SELECT count(v) FROM m", db="db",
                          now_ns=(BASE + 10_000) * NS)
        assert res["results"][0]["series"][0]["values"][0][1] == 2
        e2.close()


class TestPreAggFastPath:
    def _flushed_env(self, e, ex, n=100):
        lines = "\n".join(
            f"cpu,host=h{i%2} v={i}.5,c={i}i {(BASE + i) * NS}" for i in range(n)
        )
        e.write_lines("db", lines)
        e.flush_all()

    def test_preagg_matches_decode_path(self, env):
        e, ex = env
        self._flushed_env(e, ex)
        # full-range count/sum/mean: served by pre-agg (single flushed chunk)
        res = q(ex, "SELECT count(v), sum(v), mean(v) FROM cpu GROUP BY host")
        for s in res["results"][0]["series"]:
            h = int(s["tags"]["host"][1])
            vals = [i + 0.5 for i in range(100) if i % 2 == h]
            t, cnt, total, mean = s["values"][0]
            assert cnt == len(vals)
            assert total == pytest.approx(sum(vals))
            assert mean == pytest.approx(sum(vals) / len(vals))

    def test_preagg_skips_decode(self, env, monkeypatch):
        from opengemini_tpu.storage import tsf

        e, ex = env
        self._flushed_env(e, ex)
        calls = {"n": 0}
        orig = tsf.TSFReader.read_chunk

        def counting(self, *a, **kw):
            calls["n"] += 1
            return orig(self, *a, **kw)

        monkeypatch.setattr(tsf.TSFReader, "read_chunk", counting)
        q(ex, "SELECT count(v), mean(v) FROM cpu")
        assert calls["n"] == 0  # no chunk decode at all

    def test_preagg_partial_range_and_memtable_fallback(self, env):
        e, ex = env
        self._flushed_env(e, ex)
        # partial time range: must slice, not use whole-chunk preagg
        res = q(ex, f"SELECT count(v) FROM cpu WHERE time >= {(BASE + 50) * NS}")
        assert series_of(res)["values"][0][1] == 50
        # memtable overlap disables the fast path (dedup risk)
        e.write_lines("db", f"cpu,host=h0 v=999 {BASE * NS}")  # overwrites i=0
        res = q(ex, "SELECT sum(v) FROM cpu WHERE host = 'h0'")
        vals = [i + 0.5 for i in range(100) if i % 2 == 0]
        expect = sum(vals) - 0.5 + 999
        assert series_of(res)["values"][0][1] == pytest.approx(expect)

    def test_preagg_with_field_filter_disabled(self, env):
        e, ex = env
        self._flushed_env(e, ex)
        res = q(ex, "SELECT count(v) FROM cpu WHERE v >= 50")
        assert series_of(res)["values"][0][1] == 50


class TestCompactionService:
    def test_tick_compacts_fragmented_shards(self, env):
        from opengemini_tpu.services.compaction import CompactionService

        e, ex = env
        for i in range(6):
            e.write_lines("db", f"m v={i} {(BASE + i) * NS}")
            e.flush_all()
        [shard] = e.all_shards()
        assert len(shard._files) == 6
        svc = CompactionService(e, interval_s=3600, max_files=4)
        assert svc.handle() == 1  # leveled: merges one 4-file run
        assert len(shard._files) == 3
        assert svc.handle() == 0  # below fanout: no further merge
        res = q(ex, "SELECT count(v) FROM m")
        assert series_of(res)["values"][0][1] == 6


def test_compaction_does_not_break_inflight_readers(tmp_path):
    """Readers obtained before a compaction must stay usable (files are
    unlinked, not closed, while queries hold them — POSIX semantics)."""
    import opengemini_tpu.ingest.line_protocol as lp
    from opengemini_tpu.storage.shard import Shard

    sh = Shard(str(tmp_path / "s"), 0, 10**18)
    for i in range(3):
        line = f"m v={i} {(i+1)}000000000"
        sh.write_points(lp.parse_lines(line), line.encode(), "ns", 0)
        sh.flush()
    sid = sh.index.get_or_create("m", ())
    pairs = sh.file_chunks("m", {sid})  # in-flight query state
    assert sh.compact() is True
    # old readers still serve reads after their files were unlinked
    for r, c in pairs:
        rec = r.read_chunk("m", c)
        assert len(rec) == 1
    sh.close()


class TestHierarchicalService:
    def test_cold_move_keeps_shard_usable(self, env, tmp_path):
        from opengemini_tpu.services.hierarchical import HierarchicalService

        e, ex = env
        e.write_lines("db", f"m v=1 {BASE*NS}\nm v=3 {(BASE+1)*NS}")
        e.flush_all()
        cold = str(tmp_path / "cold")
        svc = HierarchicalService(e, cold, age_ns=1, interval_s=3600)
        week = 7 * 24 * 3600
        assert svc.handle(now_ns=(BASE + 2 * week) * NS) == 1
        [shard] = e.all_shards()
        import os
        assert os.path.islink(shard.path)
        # reads still work through the symlinked hot path
        res = q(ex, "SELECT sum(v) FROM m")
        assert series_of(res)["values"][0][1] == 4.0
        # writes too (WAL reopened at cold location)
        e.write_lines("db", f"m v=10 {(BASE+2)*NS}")
        res = q(ex, "SELECT sum(v) FROM m")
        assert series_of(res)["values"][0][1] == 14.0
        # idempotent
        assert svc.handle(now_ns=(BASE + 2 * week) * NS) == 0


class TestParquetExport:
    def test_export_roundtrip(self, env, tmp_path):
        import pyarrow.parquet as pq

        from opengemini_tpu.tools.export import export_measurement

        e, ex = env
        e.write_lines("db", "\n".join([
            f'cpu,host=a usage=1.5,n=2i,ok=true,msg="hi" {BASE*NS}',
            f"cpu,host=b usage=2.5 {(BASE+1)*NS}",
        ]))
        out = str(tmp_path / "cpu.parquet")
        n = export_measurement(e, "db", "cpu", out)
        assert n == 2
        table = pq.read_table(out)
        assert set(table.column_names) == {"time", "host", "usage", "n", "ok", "msg"}
        d = table.to_pydict()
        assert sorted(d["host"]) == ["a", "b"]
        assert d["n"][d["host"].index("a")] == 2
        assert d["usage"] == [1.5, 2.5] or sorted(d["usage"]) == [1.5, 2.5]


class TestHierarchicalRegressions:
    def test_relative_cold_dir_absolutized(self, env, tmp_path, monkeypatch):
        from opengemini_tpu.services.hierarchical import HierarchicalService
        import os

        e, ex = env
        e.write_lines("db", f"m v=1 {BASE*NS}")
        e.flush_all()
        monkeypatch.chdir(tmp_path)
        svc = HierarchicalService(e, "cold-rel", age_ns=1, interval_s=3600)
        week = 7 * 24 * 3600
        assert svc.handle(now_ns=(BASE + 2 * week) * NS) == 1
        [shard] = e.all_shards()
        target = os.readlink(shard.path)
        assert os.path.isabs(target) and os.path.isdir(target)
        res = q(ex, "SELECT count(v) FROM m")
        assert series_of(res)["values"][0][1] == 1

    def test_inflight_readers_survive_tiering(self, env, tmp_path):
        from opengemini_tpu.services.hierarchical import HierarchicalService

        e, ex = env
        e.write_lines("db", f"m v=7 {BASE*NS}")
        e.flush_all()
        [shard] = e.all_shards()
        sid = shard.index.get_or_create("m", ())
        pairs = shard.file_chunks("m", {sid})
        svc = HierarchicalService(e, str(tmp_path / "cold"), age_ns=1)
        week = 7 * 24 * 3600
        assert svc.handle(now_ns=(BASE + 2 * week) * NS) == 1
        for r, c in pairs:  # old readers still serve after the move
            assert r.read_chunk("m", c).columns["v"].values.tolist() == [7.0]

    def test_retention_removes_cold_copy(self, env, tmp_path, monkeypatch):
        from opengemini_tpu.services.hierarchical import HierarchicalService
        import os

        e, ex = env
        e.create_retention_policy("db", "short", duration_ns=24 * 3600 * NS,
                                  default=True)
        e.write_lines("db", f"m v=1 {BASE*NS}")
        e.flush_all()
        cold = str(tmp_path / "cold")
        svc = HierarchicalService(e, cold, age_ns=1)
        week = 7 * 24 * 3600
        assert svc.handle(now_ns=(BASE + week) * NS) == 1
        dropped = e.drop_expired_shards(now_ns=(BASE + 10 * week) * NS)
        assert len(dropped) == 1
        # neither the symlink nor the cold copy may remain
        assert not any("autogen" in r or f for r, d, f in os.walk(cold) for f in f)
        data_dir = os.path.join(e.root, "data", "db", "short")
        assert not os.path.exists(data_dir) or not os.listdir(data_dir)

    def test_export_includes_all_rps(self, env, tmp_path):
        import pyarrow.parquet as pq
        from opengemini_tpu.tools.export import export_measurement

        e, ex = env
        e.create_retention_policy("db", "rp2", duration_ns=0)
        e.write_lines("db", f"m v=1 {BASE*NS}")  # autogen
        e.write_lines("db", f"m v=2 {BASE*NS}", rp="rp2")
        out = str(tmp_path / "m.parquet")
        n = export_measurement(e, "db", "m", out)
        assert n == 2
        assert sorted(pq.read_table(out).to_pydict()["v"]) == [1.0, 2.0]


class TestIoDetector:
    @pytest.fixture(autouse=True)
    def _clear_io_alarm(self):
        """An alarm pauses background work in the process-global governor
        for OGT_BG_IO_PAUSE_S (30 s): do not leave it to the next file."""
        yield
        from opengemini_tpu.utils.governor import GOVERNOR

        GOVERNOR.reset()

    def test_probe_ok(self, env):
        from opengemini_tpu.services.iodetector import IoDetectorService

        e, ex = env
        svc = IoDetectorService(e, interval_s=3600, probe_timeout_s=5)
        assert svc.handle() is True
        assert svc.alarms == 0

    def test_hang_raises_alarm(self, env, monkeypatch):
        import time

        from opengemini_tpu.services import iodetector as iod

        e, ex = env
        svc = iod.IoDetectorService(e, interval_s=3600, probe_timeout_s=0.05)
        real_fsync = iod.os.fsync
        monkeypatch.setattr(iod.os, "fsync", lambda fd: time.sleep(0.5))
        try:
            assert svc.handle() is False
            assert svc.alarms == 1
        finally:
            monkeypatch.setattr(iod.os, "fsync", real_fsync)

    def test_hung_probe_not_stacked(self, env, monkeypatch):
        import threading
        import time

        from opengemini_tpu.services import iodetector as iod

        e, ex = env
        svc = iod.IoDetectorService(e, interval_s=3600, probe_timeout_s=0.05)
        release = threading.Event()
        real_fsync = iod.os.fsync
        monkeypatch.setattr(iod.os, "fsync", lambda fd: release.wait(5))
        try:
            assert svc.handle() is False  # starts the stuck probe
            before = threading.active_count()
            assert svc.handle() is False  # does NOT start a second thread
            assert threading.active_count() == before
            assert svc.alarms == 2
        finally:
            release.set()
            monkeypatch.setattr(iod.os, "fsync", real_fsync)
            time.sleep(0.05)


class TestSherlock:
    def test_below_watermark_no_dump(self, env):
        from opengemini_tpu.services.sherlock import SherlockService

        e, ex = env
        svc = SherlockService(e, mem_mb_watermark=10**6, thread_watermark=10**6)
        assert svc.handle() is None

    def test_watermark_dump_and_cooldown(self, env):
        import os

        from opengemini_tpu.services.sherlock import SherlockService

        e, ex = env
        svc = SherlockService(e, mem_mb_watermark=0.001, cooldown_s=600)
        path = svc.handle()
        assert path and os.path.exists(path)
        content = open(path).read()
        assert "thread stacks" in content and "trigger: rss" in content
        # cooldown suppresses the next dump
        assert svc.handle() is None
        assert svc.dumps == 1

    def test_first_dump_immediate_despite_cooldown(self, env):
        # monotonic() epoch is arbitrary; a fresh service must dump on the
        # first crossing even when monotonic() < cooldown_s
        from opengemini_tpu.services.sherlock import SherlockService

        e, ex = env
        svc = SherlockService(e, mem_mb_watermark=0.001, cooldown_s=10**9)
        assert svc.handle() is not None

    def test_failed_dump_does_not_burn_cooldown(self, env, monkeypatch):
        from opengemini_tpu.services import sherlock as sh

        e, ex = env
        svc = sh.SherlockService(e, mem_mb_watermark=0.001, cooldown_s=600)
        calls = []

        def boom(*a):
            calls.append(1)
            raise OSError("disk full")

        monkeypatch.setattr(svc, "_dump", boom)
        import pytest as _pytest

        with _pytest.raises(OSError):
            svc.handle()
        assert svc.dumps == 0
        monkeypatch.undo()
        assert svc.handle() is not None  # retried immediately, not cooled down
        assert svc.dumps == 1


class TestDownsampleSQL:
    def test_create_show_drop(self, env):
        e, ex = env
        res = q(ex, "CREATE DOWNSAMPLE ON autogen (float(mean), integer(sum)) "
                    "WITH TTL 30d SAMPLEINTERVAL 1h,25h TIMEINTERVAL 1m,30m")
        assert "error" not in res["results"][0], res
        pols = e.databases["db"].downsample["autogen"]
        assert [(p.age_ns, p.every_ns) for p in pols] == [
            (3600 * NS, 60 * NS), (25 * 3600 * NS, 1800 * NS)]
        assert pols[0].field_aggs == {"float": "mean", "integer": "sum"}
        out = q(ex, "SHOW DOWNSAMPLES")
        vals = out["results"][0]["series"][0]["values"]
        assert vals == [
            ["autogen", "float(mean),integer(sum)", "1h0m0s", "0h1m0s"],
            ["autogen", "float(mean),integer(sum)", "25h0m0s", "0h30m0s"]]
        # duplicate rejected
        r2 = ex.execute("CREATE DOWNSAMPLE ON autogen WITH TTL 30d "
                        "SAMPLEINTERVAL 1h TIMEINTERVAL 1m", db="db")
        assert "already exists" in r2["results"][0]["error"]
        q(ex, "DROP DOWNSAMPLE ON autogen")
        assert not e.databases["db"].downsample

    def test_sql_policy_drives_rewrite(self, env):
        e, ex = env
        e.write_lines("db", f"cpu v=1 {BASE * NS}\ncpu v=3 {(BASE + 30) * NS}")
        q(ex, "CREATE DOWNSAMPLE ON autogen (float(mean)) WITH TTL 52w "
              "SAMPLEINTERVAL 2m TIMEINTERVAL 1m")
        # tight intervals so the shard ages past level 0 immediately
        week = 7 * 24 * 3600
        assert e.run_downsample(now_ns=(BASE + 2 * week) * NS) == 1

    def test_validation_errors(self, env):
        e, ex = env

        def err(sql):
            return ex.execute(sql, db="db")["results"][0]["error"]

        assert "same number of levels" in err(
            "CREATE DOWNSAMPLE ON autogen WITH TTL 7d "
            "SAMPLEINTERVAL 1h,25h TIMEINTERVAL 1m")
        assert "must be finer" in err(
            "CREATE DOWNSAMPLE ON autogen WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 2h")
        assert "ascending" in err(
            "CREATE DOWNSAMPLE ON autogen WITH TTL 7d "
            "SAMPLEINTERVAL 25h,1h TIMEINTERVAL 1m,30m")
        assert "TTL must cover" in err(
            "CREATE DOWNSAMPLE ON autogen WITH TTL 1h "
            "SAMPLEINTERVAL 25h TIMEINTERVAL 1m")
        assert "unknown downsample field type" in err(
            "CREATE DOWNSAMPLE ON autogen (string(mean)) WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m")
        assert "is not supported for" in err(
            "CREATE DOWNSAMPLE ON autogen (float(bogus)) WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m")
        assert "retention policy not found" in err(
            "CREATE DOWNSAMPLE ON nope WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m")

    def test_type_aggs_respected_in_rewrite(self, env):
        e, ex = env
        # integer(max): int field keeps max, not the default sum
        e.write_lines("db", f"cpu c=2i {BASE * NS}\ncpu c=5i {(BASE + 30) * NS}")
        q(ex, "CREATE DOWNSAMPLE ON autogen (integer(max)) WITH TTL 52w "
              "SAMPLEINTERVAL 2m TIMEINTERVAL 1m")
        week = 7 * 24 * 3600
        assert e.run_downsample(now_ns=(BASE + 2 * week) * NS) == 1
        out = q(ex, "SELECT c FROM cpu")
        [row] = out["results"][0]["series"][0]["values"]
        assert row[1] == 5

    def test_unexecutable_agg_rejected(self, env):
        e, ex = env
        # integer(count) would die on the exact host int64 path at rewrite
        # time; percentile lacks its parameter in every path
        for sql in (
            "CREATE DOWNSAMPLE ON autogen (integer(count)) WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m",
            "CREATE DOWNSAMPLE ON autogen (float(percentile)) WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m",
            "CREATE DOWNSAMPLE ON autogen (integer(spread)) WITH TTL 7d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m",
        ):
            errtxt = ex.execute(sql, db="db")["results"][0]["error"]
            assert "is not supported for" in errtxt, errtxt
        assert not e.databases["db"].downsample

    def test_ttl_sets_rp_duration(self, env):
        e, ex = env
        q(ex, "CREATE DOWNSAMPLE ON autogen (float(mean)) WITH TTL 30d "
              "SAMPLEINTERVAL 1h TIMEINTERVAL 1m")
        assert e.databases["db"].rps["autogen"].duration_ns == 30 * 86400 * NS

    def test_drop_rp_removes_policies(self, env):
        e, ex = env
        q(ex, "CREATE RETENTION POLICY rpx ON db DURATION 90d REPLICATION 1")
        q(ex, "CREATE DOWNSAMPLE ON db.rpx (float(mean)) WITH TTL 30d "
              "SAMPLEINTERVAL 1h TIMEINTERVAL 1m")
        assert e.databases["db"].downsample["rpx"]
        q(ex, "DROP RETENTION POLICY rpx ON db")
        assert "rpx" not in e.databases["db"].downsample
        # re-create cycle works: no stale already-exists
        q(ex, "CREATE RETENTION POLICY rpx ON db DURATION 90d REPLICATION 1")
        res = ex.execute(
            "CREATE DOWNSAMPLE ON db.rpx (float(mean)) WITH TTL 30d "
            "SAMPLEINTERVAL 1h TIMEINTERVAL 1m", db="db")
        assert "error" not in res["results"][0], res


class TestCastorUDF:
    def test_udf_loads_and_runs_via_sql(self, env, tmp_path):
        import numpy as np

        from opengemini_tpu.services import castor

        udf_dir = tmp_path / "udfs"
        udf_dir.mkdir()
        (udf_dir / "spike.py").write_text(
            "def detect(values, threshold):\n"
            "    thr = 100.0 if threshold is None else threshold\n"
            "    return values > thr\n"
        )
        (udf_dir / "broken.py").write_text("def detect(:\n")  # syntax error
        (udf_dir / "mad.py").write_text("def detect(v, t): return v > 0\n")
        try:
            loaded = castor.load_udfs(str(udf_dir))
            assert loaded == ["spike"]  # broken skipped, builtin shadow skipped
            e, ex = env
            e.write_lines("db", "\n".join(
                f"m v={v} {(BASE + i) * NS}"
                for i, v in enumerate([1, 2, 500, 3])))
            out = q(ex, "SELECT detect(v, 'spike') FROM m")
            vals = out["results"][0]["series"][0]["values"]
            assert [r[1] for r in vals] == [500.0]
            # threshold param reaches the udf
            out = q(ex, "SELECT detect(v, 'spike', 2.5) FROM m")
            assert [r[1] for r in out["results"][0]["series"][0]["values"]] == [500.0, 3.0]
            # unknown algorithm error names udfs too
            r = ex.execute("SELECT detect(v, 'nope') FROM m", db="db")
            assert "spike" in r["results"][0]["error"]
        finally:
            castor._UDFS.clear()

    def test_bad_udf_shape_is_clean_error(self, env, tmp_path):
        from opengemini_tpu.services import castor

        udf_dir = tmp_path / "udfs2"
        udf_dir.mkdir()
        (udf_dir / "badshape.py").write_text(
            "def detect(values, threshold):\n    return values[:1] > 0\n")
        try:
            castor.load_udfs(str(udf_dir))
            e, ex = env
            e.write_lines("db", f"m v=1 {BASE * NS}\nm v=2 {(BASE + 1) * NS}")
            r = ex.execute("SELECT detect(v, 'badshape') FROM m", db="db")
            assert "expected (2,)" in r["results"][0]["error"]
        finally:
            castor._UDFS.clear()

    def test_udf_runtime_error_is_clean(self, env, tmp_path):
        from opengemini_tpu.services import castor

        udf_dir = tmp_path / "udfs3"
        udf_dir.mkdir()
        (udf_dir / "wrongarity.py").write_text(
            "def detect(values):\n    return values > 0\n")
        try:
            castor.load_udfs(str(udf_dir))
            e, ex = env
            e.write_lines("db", f"m v=1 {BASE * NS}")
            r = ex.execute("SELECT detect(v, 'wrongarity') FROM m", db="db")
            err = r["results"][0]["error"]
            assert "wrongarity" in err and "failed" in err
        finally:
            castor._UDFS.clear()

    def test_load_udfs_idempotent(self, env, tmp_path):
        from opengemini_tpu.services import castor

        d1 = tmp_path / "u1"; d1.mkdir()
        (d1 / "one.py").write_text("def detect(v, t): return v > 0\n")
        d2 = tmp_path / "u2"; d2.mkdir()
        (d2 / "two.py").write_text("def detect(v, t): return v > 0\n")
        try:
            assert castor.load_udfs(str(d1)) == ["one"]
            assert castor.load_udfs(str(d2)) == ["two"]
            assert set(castor._UDFS) == {"two"}  # 'one' did not linger
        finally:
            castor._UDFS.clear()


@pytest.fixture(params=["fs", "http"])
def obs_store_factory(request, tmp_path):
    """Builds clients for one persistent bucket backend: the filesystem
    impl or the remote S3-subset HTTP impl (MiniBucketServer)."""
    if request.param == "fs":
        from opengemini_tpu.storage.objstore import FSObjectStore

        yield lambda: FSObjectStore(str(tmp_path / "bucket"))
        return
    from opengemini_tpu.storage.objstore import (
        HTTPObjectStore, MiniBucketServer,
    )

    srv = MiniBucketServer().start()
    try:
        yield lambda: HTTPObjectStore(srv.url)
    finally:
        srv.stop()


class TestObsTier:
    def _obs_env(self, tmp_path, make_store):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "data"))
        e.create_database("db")
        store = make_store()
        e.attach_object_store(store)
        week = 7 * 86400
        lines = "\n".join(
            f"m,host=h{w % 2} v={w} {(BASE + w * week) * NS}"
            for w in range(4))
        e.write_lines("db", lines)
        e.flush_all()
        return e, Executor(e), store

    def test_offload_hydrate_round_trip(self, tmp_path, obs_store_factory):
        import os

        from opengemini_tpu.services.obstier import ObsTierService

        e, ex, store = self._obs_env(tmp_path, obs_store_factory)
        week = 7 * 86400
        n_before = len(e._shards)
        svc = ObsTierService(e, age_ns=2 * week * NS)
        # "now" = base + 4 weeks: the first two groups have aged out
        moved = svc.handle(now_ns=(BASE + 4 * week) * NS)
        assert moved == 2
        assert len(e._shards) == n_before - 2
        assert len(e.obs_shards) == 2
        assert store.list("shards/db/autogen")  # files in the bucket
        # the local dirs are gone
        gone = [k for k in e.obs_shards]
        for db, rp, start in gone:
            assert not os.path.exists(e._shard_dir(db, rp, start))
        # query touching the offloaded range hydrates + returns everything
        out = q(ex, "SELECT count(v), sum(v) FROM m")
        row = out["results"][0]["series"][0]["values"][0]
        assert row[1] == 4 and row[2] == 0 + 1 + 2 + 3
        assert len(e.obs_shards) == 0  # hydrated back
        e.close()

    def test_restart_keeps_offloaded_groups_queryable(self, tmp_path,
                                                       obs_store_factory):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.services.obstier import ObsTierService
        from opengemini_tpu.storage.engine import Engine

        e, ex, store = self._obs_env(tmp_path, obs_store_factory)
        week = 7 * 86400
        ObsTierService(e, age_ns=2 * week * NS).handle(
            now_ns=(BASE + 4 * week) * NS)
        assert e.obs_shards
        e.close()
        e2 = Engine(str(tmp_path / "data"))
        e2.attach_object_store(obs_store_factory())
        assert len(e2.obs_shards) == 2  # registry persisted
        out = Executor(e2).execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 4
        e2.close()

    def test_retention_deletes_store_copies(self, tmp_path,
                                             obs_store_factory):
        from opengemini_tpu.services.obstier import ObsTierService

        e, ex, store = self._obs_env(tmp_path, obs_store_factory)
        week = 7 * 86400
        ObsTierService(e, age_ns=1 * week * NS).handle(
            now_ns=(BASE + 10 * week) * NS)
        assert len(e.obs_shards) == 4
        q(ex, "CREATE RETENTION POLICY short ON db DURATION 1h REPLICATION 1")
        # shrink autogen's duration directly (ALTER analogue)
        e.databases["db"].rps["autogen"].duration_ns = 1 * week * NS
        dropped = e.drop_expired_shards(now_ns=(BASE + 100 * week) * NS)
        assert len(dropped) == 4
        assert not e.obs_shards
        assert store.list("shards/db/autogen") == []  # bucket emptied
        e.close()

    def test_write_into_offloaded_range_merges(self, tmp_path,
                                                obs_store_factory):
        """Writes landing in an offloaded group's range must hydrate the
        group first — not create a fresh shard hydration later clobbers."""
        from opengemini_tpu.services.obstier import ObsTierService

        e, ex, store = self._obs_env(tmp_path, obs_store_factory)
        week = 7 * 86400
        ObsTierService(e, age_ns=1 * week * NS).handle(
            now_ns=(BASE + 10 * week) * NS)
        assert len(e.obs_shards) == 4
        # write a NEW point into the first offloaded group's range
        e.write_lines("db", f"m,host=h0 v=100 {(BASE + 3600) * NS}")
        out = q(ex, "SELECT count(v), sum(v) FROM m")
        row = out["results"][0]["series"][0]["values"][0]
        assert row[1] == 5 and row[2] == 0 + 1 + 2 + 3 + 100  # old + new
        e.close()

    def test_crash_between_registry_and_removal_prefers_local(
            self, tmp_path, obs_store_factory):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.storage.objstore import shard_prefix

        e, ex, store = self._obs_env(tmp_path, obs_store_factory)
        # simulate the crash window: registry written, local dir kept
        key = sorted(e._shards)[0]
        db, rp, start = key
        prefix = shard_prefix(db, rp, start)
        sh = e._shards[key]
        sh.flush()
        import os

        for fname in sorted(os.listdir(sh.path)):
            full = os.path.join(sh.path, fname)
            if os.path.isfile(full):
                store.put(f"{prefix}/{fname}", full)
        e.obs_shards.add(key)
        e._save_meta()
        e.close()
        e2 = Engine(str(tmp_path / "data"))
        e2.attach_object_store(obs_store_factory())
        assert key not in e2.obs_shards  # reconciled: local wins
        assert store.list(prefix) == []  # stale bucket copy removed
        out = Executor(e2).execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 4
        e2.close()

    def test_drop_database_purges_bucket(self, tmp_path, obs_store_factory):
        from opengemini_tpu.services.obstier import ObsTierService

        e, ex, store = self._obs_env(tmp_path, obs_store_factory)
        week = 7 * 86400
        ObsTierService(e, age_ns=1 * week * NS).handle(
            now_ns=(BASE + 10 * week) * NS)
        e.drop_database("db")
        assert not e.obs_shards
        assert store.list("shards/db") == []
        # recreate: nothing resurrects
        e.create_database("db")
        from opengemini_tpu.query.executor import Executor

        out = Executor(e).execute("SELECT count(v) FROM m", db="db")
        assert "series" not in out["results"][0]
        e.close()


class TestRuntimeConfigReload:
    def test_apply_changes_intervals(self, tmp_path):
        from opengemini_tpu.server.app import _apply_runtime_config, build

        cfg = {
            "data": {"dir": str(tmp_path / "rc")},
            "http": {"bind-address": "127.0.0.1:0"},
            "services": {"compact-interval-s": 600, "compact-max-files": 4},
        }
        svc = build(cfg)
        comp = next(s for s in svc.services if s.name == "compaction")
        assert comp.interval_s == 600
        changed = _apply_runtime_config(svc, {
            "services": {"compact-interval-s": 30, "compact-max-files": 8,
                         "retention-interval-s": 1800}})
        assert "compaction.interval_s=30.0" in changed
        assert "compaction.max_files=8" in changed
        assert comp.interval_s == 30.0 and comp.max_files == 8
        ret = next(s for s in svc.services if s.name == "retention")
        assert ret.interval_s == 1800.0
        # idempotent: no spurious changes
        assert _apply_runtime_config(svc, {
            "services": {"compact-interval-s": 30}}) == []
        # atomic: one bad value rejects the whole reload
        import pytest as _p

        with _p.raises(ValueError):
            _apply_runtime_config(svc, {"services": {
                "retention-interval-s": 60, "compact-max-files": "four"}})
        ret = next(s for s in svc.services if s.name == "retention")
        assert ret.interval_s == 1800.0  # earlier change NOT applied
        svc.httpd.server_close()
        svc.engine.close()


class TestCastorModels:
    """Castor fit pipeline: CREATE MODEL -> persisted artifact ->
    detect(field, '<model>') -> SHOW MODELS / DROP MODEL
    (reference services/castor fit flow)."""

    BASE = 1_700_000_000

    def _mk(self, root):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(root, sync_wal=False)
        if "db" not in e.databases:
            e.create_database("db")
        return e, Executor(e)

    def test_fit_persist_detect_roundtrip(self, tmp_path):
        NS = 10**9
        e, ex = self._mk(str(tmp_path))
        # training window: calm data around 10
        lines = [f"m v={10 + (i % 3)} {(self.BASE + i) * NS}"
                 for i in range(60)]
        # later window: one wild outlier the TRAINING baseline must flag
        lines += [f"m v=11 {(self.BASE + 100) * NS}",
                  f"m v=500 {(self.BASE + 101) * NS}"]
        e.write_lines("db", "\n".join(lines))
        r = ex.execute(
            "CREATE MODEL calm WITH ALGORITHM 'mad' FROM "
            f"(SELECT v FROM m WHERE time < {(self.BASE + 60) * NS})",
            db="db")
        assert "error" not in r["results"][0], r
        # artifact on disk
        doc = e.models.get("calm")
        assert doc["algorithm"] == "mad" and doc["trained_rows"] == 60
        # detect with the fitted baseline over the LATER window
        r2 = ex.execute(
            f"SELECT detect(v, 'calm') FROM m "
            f"WHERE time >= {(self.BASE + 100) * NS}", db="db")
        vals = r2["results"][0]["series"][0]["values"]
        assert [v[1] for v in vals] == [500.0], vals
        # SHOW MODELS lists it
        r3 = ex.execute("SHOW MODELS", db="db")
        row = r3["results"][0]["series"][0]["values"][0]
        assert row[0] == "calm" and row[1] == "mad" and row[3] == 60
        e.close()
        # restart: the model survives and still detects
        e2, ex2 = self._mk(str(tmp_path))
        r4 = ex2.execute(
            f"SELECT detect(v, 'calm') FROM m "
            f"WHERE time >= {(self.BASE + 100) * NS}", db="db")
        assert [v[1] for v in r4["results"][0]["series"][0]["values"]] == [500.0]
        # DROP MODEL removes it; detect falls back to unknown-algorithm error
        ex2.execute("DROP MODEL calm", db="db")
        assert e2.models.get("calm") is None
        r5 = ex2.execute("SELECT detect(v, 'calm') FROM m", db="db")
        assert "error" in r5["results"][0]
        e2.close()

    def test_fit_rejects_builtin_shadow_and_thin_data(self, tmp_path):
        NS = 10**9
        e, ex = self._mk(str(tmp_path))
        e.write_lines("db", f"m v=1 {self.BASE * NS}")
        r = ex.execute(
            "CREATE MODEL mad WITH ALGORITHM 'mad' FROM (SELECT v FROM m)",
            db="db")
        assert "shadows" in r["results"][0].get("error", "")
        r2 = ex.execute(
            "CREATE MODEL tiny WITH ALGORITHM 'sigma' FROM (SELECT v FROM m)",
            db="db")
        assert ">= 8" in r2["results"][0].get("error", "")
        e.close()


class TestMonitorAgent:
    """ts-monitor external agent (reference app/ts-monitor/collector):
    watches nodes from OUTSIDE and reports monitor series."""

    def test_collect_and_report_round(self, tmp_path):
        import os

        from opengemini_tpu.server.http import HttpService
        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.tools import monitor_agent as ma

        e = Engine(str(tmp_path / "node"), sync_wal=False)
        e.create_database("d")
        e.write_lines("d", "m v=1 1700000000000000000")
        svc = HttpService(e, "127.0.0.1", 0)
        svc.start()
        target = f"127.0.0.1:{svc.port}"
        pidfile = tmp_path / "node.pid"
        pidfile.write_text(str(os.getpid()))
        try:
            rc = ma.main([
                "-targets", f"{target},127.0.0.1:1",  # second target: down
                "-report", target, "-db", "monitor",
                "-pidfiles", f"{target}={pidfile}", "-once"])
            assert rc == 0
            res = svc.executor.execute(
                "SELECT up, ping_ms FROM ogmonitor_up GROUP BY target",
                db="monitor")["results"][0]
            by_tag = {s["tags"]["target"]: s["values"] for s in res["series"]}
            assert by_tag[target][0][1] == 1
            assert by_tag["127.0.0.1:1"][0][1] == 0  # down node observed
            res2 = svc.executor.execute(
                "SELECT write_points FROM ogmonitor_stats", db="monitor"
            )["results"][0]
            assert res2["series"][0]["values"][0][1] >= 1  # counters flowed
            res3 = svc.executor.execute(
                "SELECT rss_kb FROM ogmonitor_proc", db="monitor"
            )["results"][0]
            assert res3["series"][0]["values"][0][1] > 0
        finally:
            svc.stop()
            e.close()

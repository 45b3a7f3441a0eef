"""Materialized rollups: incremental maintenance (storage/rollup.py),
the governed maintenance service, and the planner splice
(query/rollupplan.py) — including the splice-vs-raw equality fuzz (late
data racing maintenance), watermark crash durability, idempotent
re-folds, and no-specs pass-through."""

import json
import os
import threading
import urllib.parse
import urllib.request

import numpy as np
import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine, NS
from opengemini_tpu.storage.rollup import ROLLUP_RP, RollupSpec
from opengemini_tpu.utils import failpoint
from opengemini_tpu.utils.failpoint import FailpointError
from opengemini_tpu.utils.stats import GLOBAL as STATS

BASE = 1_700_000_040  # minute-aligned


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("db")
    yield e, Executor(e)
    failpoint.disable_all()
    e.close()


def declare(e, name="cpu_1m", mst="cpu", every_s=60, **kw):
    spec = RollupSpec(name, mst, every_s * NS, **kw)
    e.create_rollup("db", spec)
    return spec


def write_series(e, n=600, step_s=2, base=BASE, mst="cpu", hosts=3):
    lines = "\n".join(
        f"{mst},host=h{i % hosts} v={i}i,f={float(i % 7)} "
        f"{(base + i * step_s) * NS}"
        for i in range(n)
    )
    e.write_lines("db", lines)


def run(e, q, now):
    """Execute on a FRESH executor (no shared incremental result cache —
    the raw oracle must not be answered from cells the splice seeded)."""
    return Executor(e).execute(q, db="db", now_ns=now)


def splice_vs_raw(e, q, now):
    spliced = run(e, q, now)
    e.rollup_mgr.read_enabled = False
    try:
        raw = run(e, q, now)
    finally:
        e.rollup_mgr.read_enabled = True
    return spliced, raw


def assert_spliced_equal(e, q, now, expect_windows=None):
    before = STATS.counters("rollup").get("splice_windows", 0)
    spliced, raw = splice_vs_raw(e, q, now)
    assert json.dumps(spliced, sort_keys=True) == \
        json.dumps(raw, sort_keys=True)
    served = STATS.counters("rollup").get("splice_windows", 0) - before
    if expect_windows is not None:
        assert served == expect_windows
    return spliced, served


QUERY = (
    "SELECT mean(v), sum(v), count(v), min(f), max(f), percentile(f, 90) "
    "FROM cpu WHERE time >= {lo} AND time < {hi} GROUP BY time(1m), host"
)


class TestRollupMaintenance:
    def test_fold_and_status(self, env):
        e, ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        folded = e.rollup_mgr.maintain(now_ns=now)
        assert folded == 20  # 1200s of data / 60s windows
        st = e.rollup_mgr.status(now_ns=now)["db.cpu_1m"]
        assert st["watermark_ns"] == (BASE + 1260) * NS
        assert st["dirty_windows"] == 0
        # rollup rows are ordinary queryable rows under the system RP
        res = ex.execute(
            f'SELECT count(c_v) FROM "db"."{ROLLUP_RP}".cpu_1m GROUP BY host',
            db="db", now_ns=now)
        series = res["results"][0]["series"]
        assert len(series) == 3
        assert all(s["values"][0][1] == 20 for s in series)

    def test_spec_persists_across_reopen(self, env, tmp_path):
        e, _ex = env
        declare(e, fields=["v"], sketch=False)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        wm = e.rollup_mgr.status(now_ns=now)["db.cpu_1m"]["watermark_ns"]
        e.close()
        e2 = Engine(str(tmp_path / "data"))
        try:
            assert e2.rollup_mgr is not None
            spec = e2.databases["db"].rollups["cpu_1m"]
            assert spec.fields == ["v"] and spec.sketch is False
            st = e2.rollup_mgr.status(now_ns=now)["db.cpu_1m"]
            assert st["watermark_ns"] == wm  # durable watermark
            assert e2.rollup_mgr.maintain(now_ns=now) == 0  # idle: no work
        finally:
            e2.close()

    def test_refold_is_idempotent(self, env):
        e, ex = env
        declare(e)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        rows_before = ex.execute(
            f'SELECT count(c_v) FROM "db"."{ROLLUP_RP}".cpu_1m',
            db="db", now_ns=now)
        e.rollup_mgr.invalidate("db", "cpu_1m", BASE * NS, (BASE + 240) * NS)
        assert e.rollup_mgr.maintain(now_ns=now) > 0
        rows_after = ex.execute(
            f'SELECT count(c_v) FROM "db"."{ROLLUP_RP}".cpu_1m',
            db="db", now_ns=now)
        assert rows_before == rows_after  # LWW overwrite: no duplicates
        assert_spliced_equal(
            e, QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS), now)


class TestSplice:
    def test_equality_and_scan_shrink(self, env):
        e, _ex = env
        declare(e)
        write_series(e)
        e.flush_all()
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        lo, hi = BASE * NS, (BASE + 1200) * NS
        _res, served = assert_spliced_equal(
            e, QUERY.format(lo=lo, hi=hi), now, expect_windows=20)
        before_rows = STATS.counters("executor").get("rows_scanned", 0)
        run(e, QUERY.format(lo=lo, hi=hi), now)
        # fully-spliced: the raw scan read NOTHING
        assert STATS.counters("executor").get("rows_scanned", 0) \
            == before_rows

    def test_coarser_grid_and_tag_filter(self, env):
        e, _ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        lo, hi = BASE * NS, (BASE + 1200) * NS
        assert_spliced_equal(
            e, f"SELECT mean(v), percentile(v, 50) FROM cpu WHERE "
               f"time >= {lo} AND time < {hi} GROUP BY time(3m)", now)
        assert_spliced_equal(
            e, f"SELECT sum(v), count(f) FROM cpu WHERE time >= {lo} AND "
               f"time < {hi} AND host = 'h1' GROUP BY time(2m)", now)

    def test_raw_tail_beyond_watermark(self, env):
        e, _ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        # extend past the watermark: the tail must come from raw rows
        write_series(e, n=90, base=BASE + 1200)
        assert_spliced_equal(
            e, QUERY.format(lo=BASE * NS, hi=(BASE + 1400) * NS), now)

    def test_ineligible_shapes_fall_through(self, env):
        e, _ex = env
        declare(e, sketch=False)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        lo, hi = BASE * NS, (BASE + 240) * NS
        before = STATS.counters("rollup").get("splice_hits", 0)
        # row-level field filter, non-derivable agg, off-grid interval,
        # percentile without sketches: all must stay raw (and correct)
        for q in (
            f"SELECT sum(v) FROM cpu WHERE time >= {lo} AND time < {hi} "
            f"AND v > 3 GROUP BY time(1m)",
            f"SELECT stddev(v) FROM cpu WHERE time >= {lo} AND "
            f"time < {hi} GROUP BY time(1m)",
            f"SELECT sum(v) FROM cpu WHERE time >= {lo} AND time < {hi} "
            f"GROUP BY time(90s)",
            f"SELECT percentile(v, 50) FROM cpu WHERE time >= {lo} AND "
            f"time < {hi} GROUP BY time(1m)",
        ):
            s, r = splice_vs_raw(e, q, now)
            assert json.dumps(s, sort_keys=True) == \
                json.dumps(r, sort_keys=True)
        assert STATS.counters("rollup").get("splice_hits", 0) == before

    def test_composes_with_result_cache(self, env):
        e, _ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        ex = Executor(e)
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 1200) * NS)
        first = ex.execute(q, db="db", now_ns=now)
        hits_before = STATS.counters("executor").get(
            "inc_cache_full_hits", 0)
        second = ex.execute(q, db="db", now_ns=now)
        assert first == second
        # the cache persisted the spliced windows: run 2 is a full hit
        assert STATS.counters("executor").get("inc_cache_full_hits", 0) \
            == hits_before + 1


class TestLateData:
    def test_late_write_redirties_durably(self, env, tmp_path):
        e, _ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        e.write_lines("db", f"cpu,host=h1 v=99999i,f=3.0 {(BASE + 65) * NS}")
        st = e.rollup_mgr.status(now_ns=now)["db.cpu_1m"]
        assert st["dirty_windows"] == 1
        # the mark is durable BEFORE the rows: visible on disk already
        state = json.load(open(
            tmp_path / "data" / "rollup" / "db" / "cpu_1m.json"))
        assert state["dirty"] == [(BASE + 60) * NS]
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 1200) * NS)
        # pre-refold: the dirty window is raw-scanned, the rest spliced
        assert_spliced_equal(e, q, now, expect_windows=19)
        assert e.rollup_mgr.maintain(now_ns=now) >= 1
        assert_spliced_equal(e, q, now, expect_windows=20)

    def test_retention_trim_delete_invalidates(self, env):
        """`DELETE FROM m WHERE time < X` removes the SOURCE rows before
        note_delete runs — the invalidation span must come from the
        persisted rollup rows (which still cover the folded windows),
        not from the surviving source data."""
        e, _ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        ex = Executor(e)
        cut = (BASE + 300) * NS
        ex.execute(f"DELETE FROM cpu WHERE time < {cut}", db="db",
                   now_ns=now)
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 1200) * NS)
        # the trimmed windows are dirty -> raw-scanned: still equal
        assert_spliced_equal(e, q, now)
        e.rollup_mgr.maintain(now_ns=now)
        # re-folded (stale cells zero-filled): fully spliced and equal
        assert_spliced_equal(e, q, now, expect_windows=20)

    def test_vanished_field_zero_fills(self, env):
        """A field deleted from a still-live window must not survive in
        the rollup cell (field-level LWW cannot remove old row fields —
        the re-fold writes an explicit count=0)."""
        e, _ex = env
        declare(e)
        e.write_lines("db", "\n".join([
            f"cpu,host=h0 u=5i {(BASE + 5) * NS}",
            f"cpu,host=h0 v=7i {(BASE + 20) * NS}",
        ]))
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        ex = Executor(e)
        ex.execute(f"DELETE FROM cpu WHERE time < {(BASE + 10) * NS}",
                   db="db", now_ns=now)
        e.rollup_mgr.maintain(now_ns=now)
        q = (f"SELECT count(u), sum(u), count(v) FROM cpu WHERE "
             f"time >= {BASE * NS} AND time < {(BASE + 60) * NS} "
             f"GROUP BY time(1m)")
        spliced, raw = splice_vs_raw(e, q, now)
        assert json.dumps(spliced, sort_keys=True) == \
            json.dumps(raw, sort_keys=True)
        [row] = spliced["results"][0]["series"][0]["values"]
        assert row[1:] == [0, None, 1]  # u gone, v still counted

    def test_drop_measurement_blocks_fold_until_purge(self, env):
        """A maintenance tick between DROP MEASUREMENT's mark and the
        deferred purge must not re-materialize the dropped rows into
        rollup cells that outlive the purge."""
        e, _ex = env
        declare(e)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        ex = Executor(e)
        ex.execute("DROP MEASUREMENT cpu", db="db", now_ns=now)
        assert e.rollup_mgr.maintain(now_ns=now) == 0  # fold is gated
        e.purge_dropped_measurements("db")
        # recreate the name with one fresh point
        e.write_lines("db", f"cpu,host=h9 v=1i,f=1.0 {(BASE + 7) * NS}")
        e.rollup_mgr.maintain(now_ns=now)
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS)
        spliced, raw = splice_vs_raw(e, q, now)
        assert json.dumps(spliced, sort_keys=True) == \
            json.dumps(raw, sort_keys=True)
        series = spliced["results"][0]["series"]
        assert [s["tags"]["host"] for s in series] == ["h9"]  # old data gone

    def test_drop_database_resets_rollup_state(self, env, tmp_path):
        """A recreated database must not inherit the previous
        incarnation's watermark — stale-clean windows would splice as
        empty over the new data."""
        e, _ex = env
        declare(e)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        e.drop_database("db")
        assert not (tmp_path / "data" / "rollup" / "db").exists()
        e.create_database("db")
        write_series(e, n=120)  # same (old) time range, new incarnation
        declare(e)
        e.rollup_mgr.maintain(now_ns=now)
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS)
        assert_spliced_equal(e, q, now, expect_windows=4)

    def test_drop_rollup_purges_target_rows(self, env):
        e, ex = env
        declare(e)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        e.drop_rollup("db", "cpu_1m")
        e.purge_dropped_measurements("db")
        res = ex.execute(
            f'SELECT count(c_v) FROM "db"."{ROLLUP_RP}".cpu_1m',
            db="db", now_ns=now)
        assert "series" not in res["results"][0]  # cells gone with the spec

    def test_redeclare_rejected(self, env):
        from opengemini_tpu.storage.engine import WriteError

        e, _ex = env
        declare(e)
        with pytest.raises(WriteError, match="already exists"):
            declare(e, every_s=300)
        e.drop_rollup("db", "cpu_1m")
        declare(e, every_s=300)  # drop-then-redeclare is the sanctioned path

    def test_delete_invalidates(self, env):
        e, _ex = env
        declare(e)
        write_series(e)
        now = (BASE + 1320) * NS
        e.rollup_mgr.maintain(now_ns=now)
        ex = Executor(e)
        ex.execute(
            f"DELETE FROM cpu WHERE time >= {(BASE + 120) * NS} AND "
            f"time < {(BASE + 240) * NS}", db="db", now_ns=now)
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 1200) * NS)
        assert_spliced_equal(e, q, now)  # deleted span is raw-scanned
        e.rollup_mgr.maintain(now_ns=now)
        assert_spliced_equal(e, q, now, expect_windows=20)


class TestCrashDurability:
    def test_crash_between_fold_and_state_save(self, env, tmp_path):
        """A fold whose rows persisted but whose watermark didn't must
        re-fold the same span after restart — idempotently."""
        e, _ex = env
        declare(e)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        failpoint.enable("rollup-fold-after-write", "error")
        with pytest.raises(FailpointError):
            e.rollup_mgr.maintain(now_ns=now)
        failpoint.disable("rollup-fold-after-write")
        e.close()
        e2 = Engine(str(tmp_path / "data"))
        try:
            st = e2.rollup_mgr.status(now_ns=now)["db.cpu_1m"]
            assert st["watermark_ns"] is None  # never advanced
            assert e2.rollup_mgr.maintain(now_ns=now) == 4
            assert_spliced_equal(
                e2, QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS), now,
                expect_windows=4)
            ex2 = Executor(e2)
            res = ex2.execute(
                f'SELECT count(c_v) FROM "db"."{ROLLUP_RP}".cpu_1m GROUP BY host',
                db="db", now_ns=now)
            # the double fold left exactly one row per (series, window)
            assert all(s["values"][0][1] == 4
                       for s in res["results"][0]["series"])
        finally:
            e2.close()

    def test_crash_before_late_dirty_mark_aborts_write(self, env):
        """The dirty mark is write-ahead: if persisting it fails, the
        late write itself fails — an acked late write can never be
        invisible to the rollup."""
        e, _ex = env
        declare(e)
        write_series(e, n=120)
        now = (BASE + 400) * NS
        e.rollup_mgr.maintain(now_ns=now)
        failpoint.enable("rollup-mark-dirty", "error")
        with pytest.raises(FailpointError):
            e.write_lines("db", f"cpu,host=h0 v=7i,f=1.0 {(BASE + 5) * NS}")
        failpoint.disable("rollup-mark-dirty")
        assert_spliced_equal(
            e, QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS), now)


class TestPassThrough:
    def test_no_specs_is_inert(self, env):
        e, ex = env
        assert e.rollup_mgr is None  # no spec: no manager at all
        before = STATS.snapshot().get("rollup")
        write_series(e, n=60)
        res = ex.execute(
            f"SELECT mean(v) FROM cpu WHERE time >= {BASE * NS} AND "
            f"time < {(BASE + 240) * NS} GROUP BY time(1m)",
            db="db", now_ns=(BASE + 400) * NS)
        assert "error" not in res["results"][0]
        # no rollup counters moved (the stats registry is process-global,
        # so compare against the session's pre-existing section)
        assert STATS.snapshot().get("rollup") == before

    def test_env_kill_switch(self, tmp_path, monkeypatch):
        monkeypatch.setenv("OGT_ROLLUP", "0")
        e = Engine(str(tmp_path / "d2"))
        try:
            e.create_database("db")
            declare(e)
            assert e.rollup_mgr is None  # declared but force-disabled
            write_series(e, n=60)
        finally:
            e.close()

    def test_results_bit_identical_without_specs(self, tmp_path):
        """Same workload on a spec-less engine and a spec-ed engine with
        the splice forced off: byte-identical responses."""
        now = (BASE + 400) * NS
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS)
        outs = []
        for i, with_spec in enumerate((False, True)):
            e = Engine(str(tmp_path / f"eng{i}"))
            try:
                e.create_database("db")
                if with_spec:
                    declare(e)
                write_series(e, n=120)
                if with_spec:
                    e.rollup_mgr.maintain(now_ns=now)
                    e.rollup_mgr.read_enabled = False
                outs.append(json.dumps(
                    Executor(e).execute(q, db="db", now_ns=now),
                    sort_keys=True))
            finally:
                e.close()
        assert outs[0] == outs[1]


class TestFuzz:
    def test_splice_equals_raw_under_churn(self, env):
        """Randomized ingest (out-of-order and late writes racing
        maintenance ticks): every derivable aggregate answers the same
        through the splice as through a raw scan, at every step."""
        e, _ex = env
        declare(e)
        rng = np.random.default_rng(7)
        now_s = BASE
        queries = [
            QUERY,
            "SELECT sum(v), percentile(f, 25) FROM cpu WHERE time >= {lo} "
            "AND time < {hi} GROUP BY time(2m)",
            "SELECT count(v), max(v) FROM cpu WHERE time >= {lo} AND "
            "time < {hi} AND host = 'h0' GROUP BY time(1m), host",
        ]
        maint_err: list = []

        for round_i in range(8):
            # a live batch (moves time forward) + sometimes a late batch.
            # Row counts stay small enough that every merged percentile
            # cell fits the sketch's exact mode — strict equality is the
            # whole point of the fuzz (the degraded t-digest mode is
            # documented approximate and exercised in test_sketch.py)
            n = int(rng.integers(20, 40))
            lines = []
            for k in range(n):
                t = now_s + int(rng.integers(0, 120))
                v = int(rng.integers(-50, 50))
                lines.append(
                    f"cpu,host=h{int(rng.integers(0, 3))} "
                    f"v={v}i,f={float(int(rng.integers(0, 9)))} {t * NS}")
            if round_i > 2 and rng.random() < 0.7:
                t = BASE + int(rng.integers(0, max(now_s - BASE - 120, 60)))
                lines.append(f"cpu,host=h1 v=123i,f=4.0 {t * NS}")  # late
            body = "\n".join(lines)
            # maintenance racing the write on another thread
            def maint():
                try:
                    e.rollup_mgr.maintain(now_ns=(now_s + 150) * NS)
                except Exception as exc:  # noqa: BLE001
                    maint_err.append(exc)
            th = threading.Thread(target=maint)
            th.start()
            e.write_lines("db", body)
            th.join()
            assert not maint_err
            if rng.random() < 0.3:
                e.flush_all()
            now_s += int(rng.integers(60, 150))
            now = (now_s + 60) * NS
            lo = BASE * NS
            hi = (now_s + 120) * NS
            for q in queries:
                s, r = splice_vs_raw(e, q.format(lo=lo, hi=hi), now)
                assert json.dumps(s, sort_keys=True) == \
                    json.dumps(r, sort_keys=True), \
                    f"round {round_i}: {q.format(lo=lo, hi=hi)}"
        # the fuzz must actually have exercised the splice
        assert STATS.counters("rollup").get("splice_windows", 0) > 0


class TestServiceAndGovernor:
    def test_service_ticks_and_tenant_charges(self, env):
        from opengemini_tpu.services.rollup import RollupService
        from opengemini_tpu.utils.governor import GOVERNOR

        e, _ex = env
        declare(e)
        write_series(e, n=120)
        svc = RollupService(e, interval_s=3600)
        # the governor is process-global: an IO alarm raised by an earlier
        # file on this worker (tests/test_services.py TestIoDetector) closes
        # the background gate for 30 s and would shed this tenant
        GOVERNOR.reset()
        GOVERNOR.configure(budget_mb=64)
        try:
            folded = svc.handle(now_ns=(BASE + 400) * NS)
            assert folded == 4
            acct = GOVERNOR.tenant_accounts()["db"]
            assert acct["rollup_windows"] == 4
            gauges = GOVERNOR.gauges()
            assert gauges["tenant_db_rollup_windows"] == 4
        finally:
            GOVERNOR.configure(budget_mb=0)
            GOVERNOR.reset()

    def test_service_inert_without_manager(self, env):
        from opengemini_tpu.services.rollup import RollupService

        e, _ex = env
        assert RollupService(e).handle() == 0


class TestCtrlAndVars:
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

    @staticmethod
    def _post(svc, path, **params):
        url = (f"http://127.0.0.1:{svc.port}{path}?"
               + urllib.parse.urlencode(params))
        req = urllib.request.Request(url, data=b"", method="POST")
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read() or b"{}")
        except urllib.error.HTTPError as err:
            return err.code, json.loads(err.read() or b"{}")

    def test_ctrl_rollup_lifecycle(self, server):
        svc = server
        write_series(svc.engine, n=120)
        code, out = self._post(svc, "/debug/ctrl", mod="rollup",
                               op="declare", db="db", name="cpu_1m",
                               measurement="cpu", every_s="60")
        assert code == 200 and "db.cpu_1m" in out["specs"]
        code, out = self._post(svc, "/debug/ctrl", mod="rollup", op="flush")
        assert code == 200 and out["folded"] > 0
        code, out = self._post(svc, "/debug/ctrl", mod="rollup",
                               op="invalidate", db="db", name="cpu_1m")
        assert code == 200 and out["invalidated"] == 1
        code, out = self._post(svc, "/debug/ctrl", mod="rollup",
                               op="status")
        assert out["specs"]["db.cpu_1m"]["watermark_ns"] is None
        # /debug/vars carries the rollup section once specs exist
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/debug/vars") as r:
            vars_doc = json.loads(r.read())
        assert "rollup" in vars_doc
        assert vars_doc["rollup"]["windows_folded"] > 0
        code, out = self._post(svc, "/debug/ctrl", mod="rollup",
                               op="drop", db="db", name="cpu_1m")
        assert code == 200 and out["specs"] == {}
        code, out = self._post(svc, "/debug/ctrl", mod="rollup", op="bogus")
        assert code == 400

    def test_query_stage_attribution(self, server):
        svc = server
        write_series(svc.engine, n=120)
        self._post(svc, "/debug/ctrl", mod="rollup", op="declare", db="db",
                   name="cpu_1m", measurement="cpu", every_s="60")
        self._post(svc, "/debug/ctrl", mod="rollup", op="flush")
        q = QUERY.format(lo=BASE * NS, hi=(BASE + 240) * NS)
        url = (f"http://127.0.0.1:{svc.port}/query?"
               + urllib.parse.urlencode({"db": "db", "q": q}))
        with urllib.request.urlopen(url) as r:
            assert r.status == 200
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/debug/vars") as r:
            vars_doc = json.loads(r.read())
        # the splice cost is a first-class query stage
        assert vars_doc["query_stages"]["rollup_count"] >= 1

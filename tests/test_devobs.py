"""Device-runtime observability (ISSUE 14, utils/devobs.py): compile
accounting + recompile tripwire, transfer histograms, the device-memory
ledger, /debug/device + ctrl surface, and the armed/disarmed contract.

Acceptance coverage here: a live /metrics scrape with devobs armed
under a forced 4-device virtual mesh strict-parses with the ledger
gauges, transfer histograms, and compile counters present; disarmed
pass-through is bit-identical; and the /debug/device ledger totals
reconcile with the colcache device tier's own byte accounting.
"""

import gc
import json
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from opengemini_tpu.parallel import distributed as dist
from opengemini_tpu.parallel import runtime as prt
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage import colcache
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import devobs

from test_observability import parse_prometheus_strict

NS = 10**9
BASE = 1_700_000_000


@pytest.fixture(autouse=True)
def _devobs_state():
    """Every test starts disarmed with a clean ring/ledger and restores
    the process-global state (mesh, colcache config) on exit."""
    prev = devobs.enabled()
    prior_cc = colcache.GLOBAL.config()
    devobs.set_enabled(False)
    devobs.reset()
    devobs.LEDGER.clear()
    yield
    devobs.set_enabled(prev)
    devobs.reset()
    devobs.LEDGER.clear()
    prt.set_mesh(None)
    colcache.GLOBAL.clear()
    colcache.GLOBAL.configure(**prior_cc)


@pytest.fixture
def mesh4():
    return dist.make_mesh(4, ("shard",))


def _mk_engine(tmp_path, hosts=16, points=120):
    eng = Engine(str(tmp_path / "data"))
    eng.create_database("db")
    lines = []
    for i in range(points):
        t = (BASE + i) * NS
        for h in range(hosts):
            lines.append(f"m,host=h{h} v={(h + i) % 7} {t}")
    eng.write_lines("db", "\n".join(lines))
    eng.flush_all()
    return eng


_Q = ("SELECT mean(v), count(v), max(v) FROM m "
      "GROUP BY time(1m), host")


# -- compile accounting + tripwire -------------------------------------------


class TestCompileAccounting:
    def test_inventory_ring_and_repeats(self):
        devobs.note_compile("grid_basic", ((8, 4, 16), "float64"))
        devobs.note_compile("grid_basic", ((16, 4, 16), "float64"))
        devobs.note_compile("grid_basic", ((8, 4, 16), "float64"))  # repeat
        inv = devobs.jit_inventory()["grid_basic"]
        assert inv["compiles"] == 3
        assert inv["distinct_geometries"] == 2
        assert inv["repeat_compiles"] == 1
        ring = devobs.recent_compiles()
        assert ring[0]["kernel"] == "grid_basic"  # newest first
        assert ring[0].get("repeat") is True
        assert all("geometry" in e and "mesh_epoch" in e for e in ring)

    def test_recompile_tripwire(self):
        devobs.note_compile("k", (1,))
        assert devobs.compiles_since_warm() == 0  # unmarked: no tripwire
        devobs.mark_warm()
        assert devobs.compiles_since_warm() == 0
        devobs.note_compile("k", (2,))
        assert devobs.compiles_since_warm() == 1
        assert devobs.recent_compiles()[0].get("after_warm") is True
        devobs.clear_warm()
        devobs.note_compile("k", (3,))
        assert devobs.compiles_since_warm() == 0

    def test_lowering_sites_feed_inventory(self, tmp_path):
        from opengemini_tpu.models.launch import _program

        eng = _mk_engine(tmp_path, hosts=4, points=40)
        try:
            # the jit program cache is process-global and may be warm
            # from earlier tests: clear it so THIS query's lowering
            # lands in the per-test devobs inventory
            _program.cache_clear()
            Executor(eng).execute(_Q, db="db")
            inv = devobs.jit_inventory()
            # the GROUP BY time() grid path lowered at least its basic
            # kernel through the instrumented site
            assert any(k.startswith("grid_") for k in inv), inv
        finally:
            eng.close()


# -- device-memory ledger -----------------------------------------------------


class TestLedger:
    def test_register_update_drop_armed_only(self):
        assert devobs.LEDGER.register("x", 100) is None  # disarmed
        devobs.set_enabled(True)
        h = devobs.LEDGER.register("x", 100, mesh_epoch=7, label="a")
        assert h is not None
        assert devobs.LEDGER.total_bytes() == 100
        devobs.LEDGER.update(h, 250)
        assert devobs.LEDGER.by_owner()["x"]["bytes"] == 250
        devobs.LEDGER.drop(h)
        assert devobs.LEDGER.total_bytes() == 0
        devobs.LEDGER.drop(h)  # idempotent
        devobs.LEDGER.update(h, 1)  # dead handle: no-op, no error

    def test_anchor_autodrop_on_gc(self):
        devobs.set_enabled(True)

        class Holder:
            pass

        holder = Holder()
        devobs.LEDGER.register("anchored", 64, anchor=holder)
        assert devobs.LEDGER.by_owner()["anchored"]["entries"] == 1
        del holder
        gc.collect()
        assert "anchored" not in devobs.LEDGER.by_owner()

    def test_stale_epoch_flagging(self, mesh4):
        devobs.set_enabled(True)
        prt.set_mesh(mesh4)
        devobs.LEDGER.register("o", 10, mesh_epoch=prt.mesh_epoch())
        assert devobs.LEDGER.by_owner()["o"]["stale_epoch_entries"] == 0
        prt.set_mesh(None)  # epoch bump
        assert devobs.LEDGER.by_owner()["o"]["stale_epoch_entries"] == 1

    def test_ledger_reconciles_with_colcache_device_tier(self, tmp_path,
                                                         mesh4):
        """Acceptance: /debug/device ledger totals == the colcache
        device tier's own retained-byte accounting, on the virtual
        mesh, across fill + warm hit + clear."""
        devobs.set_enabled(True)
        colcache.GLOBAL.configure(budget_mb=64, device=True,
                                  device_budget_mb=64)
        prt.set_mesh(mesh4)
        eng = _mk_engine(tmp_path)
        try:
            ex = Executor(eng)
            ex.execute(_Q, db="db")   # cold: fills the device tier
            ex._inc_cache.clear()
            ex.execute(_Q, db="db")   # warm: device-tier hit
            cc_bytes = colcache.GLOBAL.device_ledger_bytes()
            assert cc_bytes > 0, "device tier never filled"
            owners = devobs.LEDGER.by_owner()
            assert owners["colcache_device"]["bytes"] == cc_bytes
            # the debug doc carries the same reconciled totals
            doc = devobs.debug_doc()
            assert doc["ledger"]["by_owner"]["colcache_device"]["bytes"] \
                == cc_bytes
            colcache.GLOBAL.clear()
            assert "colcache_device" not in devobs.LEDGER.by_owner()
        finally:
            eng.close()

    def test_grid_mesh_arrays_register_and_autodrop(self, mesh4):
        """A frozen GridBatch's mesh-sharded arrays appear in the
        ledger while the batch lives and vanish when it is collected
        (weakref anchor) — per-query residency can never leak rows."""
        from opengemini_tpu.models.grid import GridBatch
        from opengemini_tpu.ops.aggregates import REGISTRY

        devobs.set_enabled(True)
        prt.set_mesh(mesh4)
        W = 4
        S = 8
        k = 3
        batch = GridBatch(np.float64, W, every_ns=60 * NS)
        for s in range(S):
            rel = np.arange(k * W, dtype=np.int64) * 20 * NS
            seg = (rel // (60 * NS)) % W
            batch.add(np.arange(k * W, dtype=np.float64), rel,
                      seg, np.ones(k * W, bool), rel, sids=s)
        out, _sel, counts = batch.run(REGISTRY["mean"], W)
        assert counts.sum() == S * k * W
        owners = devobs.LEDGER.by_owner()
        assert owners.get("grid_mesh", {}).get("bytes", 0) > 0, owners
        del batch
        gc.collect()
        assert "grid_mesh" not in devobs.LEDGER.by_owner()


# -- armed /metrics scrape under the virtual mesh ----------------------------


def _get(port, path, **params):
    url = f"http://127.0.0.1:{port}{path}"
    if params:
        url += "?" + urllib.parse.urlencode(params)
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _post(port, path, **params):
    url = f"http://127.0.0.1:{port}{path}"
    if params:
        url += "?" + urllib.parse.urlencode(params)
    req = urllib.request.Request(url, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture
def server(tmp_path, mesh4):
    from opengemini_tpu.server.http import HttpService

    devobs.set_enabled(True)
    colcache.GLOBAL.configure(budget_mb=64, device=True,
                              device_budget_mb=64)
    prt.set_mesh(mesh4)
    eng = _mk_engine(tmp_path)
    svc = HttpService(eng, "127.0.0.1", 0)
    svc.start()
    yield svc
    svc.stop()
    eng.close()


class TestMetricsArmedUnderMesh:
    def test_scrape_strict_parses_with_device_families(self, server):
        port = server.port
        q = urllib.parse.urlencode({"db": "db", "q": _Q})
        for _ in range(2):  # cold fill + warm device-tier hit
            status, _ = _get(port, "/query", db="db", q=_Q)
            assert status == 200
        status, body = _get(port, "/metrics")
        assert status == 200
        fams = parse_prometheus_strict(body.decode())
        # compile counters (unified spelling + legacy alias)
        assert fams["ogt_device_compiles_total"]["type"] == "counter"
        assert fams["ogt_device_compiles_total"]["samples"][0][2] >= 1
        # transfer: counter totals AND per-site histograms coexist
        assert fams["ogt_device_h2d_bytes_total"]["type"] == "counter"
        h2d = fams["ogt_device_h2d_bytes"]
        assert h2d["type"] == "histogram"
        sites = {lab.get("site") for _n, lab, _v in h2d["samples"]}
        assert "colcache-fill" in sites
        d2h = fams["ogt_device_d2h_seconds"]
        assert d2h["type"] == "histogram"
        assert {lab.get("site") for _n, lab, _v in d2h["samples"]} \
            >= {"result-fetch"}
        # byte-unit histograms export raw integer bounds (1KiB first)
        les = sorted(float(lab["le"].replace("Inf", "inf"))
                     for _n, lab, _v in h2d["samples"]
                     if _n.endswith("_bucket")
                     and lab.get("site") == "colcache-fill")
        assert les[0] == 1024.0
        # ledger residency gauges
        assert fams["ogt_device_ledger_bytes"]["samples"][0][2] > 0
        assert fams["ogt_device_ledger_colcache_device_bytes"][
            "samples"][0][2] > 0
        # compile wall-time histogram labeled by kernel
        comp = fams["ogt_device_compile_seconds"]
        assert comp["type"] == "histogram"
        kernels = {lab.get("kernel") for _n, lab, _v in comp["samples"]}
        assert any(k and k.startswith("grid_") for k in kernels)

    def test_debug_device_doc(self, server):
        from opengemini_tpu.models.launch import _program

        port = server.port
        # the jit program cache is process-global and may be warm from
        # earlier tests: clear it so THIS query's lowering lands in the
        # per-test devobs inventory
        _program.cache_clear()
        _get(port, "/query", db="db", q=_Q)
        status, body = _get(port, "/debug/device")
        assert status == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["mesh"]["configured"] is True and doc["mesh"]["size"] == 4
        assert len(doc["devices"]) >= 4
        assert all("platform" in d for d in doc["devices"])
        # cache-only on the handler thread: unprobed (supported None)
        # until something called pallas_supported() in this process
        cap = doc["capabilities"]["pallas"]
        assert cap["supported"] in (True, False, None)
        assert "reason" in cap
        assert any(k.startswith("grid_") for k in doc["jit_cache"])
        assert doc["recent_compiles"], "compile ring empty"
        assert doc["ledger"]["total_bytes"] == sum(
            o["bytes"] for o in doc["ledger"]["by_owner"].values())
        assert doc["counters"].get("h2d_bytes_total", 0) > 0

    def test_ctrl_arm_warm_and_profile_guard(self, server):
        port = server.port
        status, body = _post(port, "/debug/ctrl", mod="devobs")
        assert status == 200
        assert json.loads(body)["armed"] is True
        # warm-mark then force a compile: tripwire counts it
        status, _ = _post(port, "/debug/ctrl", mod="devobs",
                          op="mark_warm")
        assert status == 200
        devobs.note_compile("ctrl_test", ())
        status, body = _post(port, "/debug/ctrl", mod="devobs")
        assert json.loads(body)["compiles_since_warm"] == 1
        _post(port, "/debug/ctrl", mod="devobs", op="clear_warm")
        # profiler capture: single-capture guard answers 409 while
        # a capture is active; the capture itself completes
        status, body = _post(port, "/debug/ctrl", mod="devobs",
                             op="profile", seconds="0.2")
        if status == 200:
            st2, _ = _post(port, "/debug/ctrl", mod="devobs",
                           op="profile", seconds="0.2")
            assert st2 == 409
            import time as _t

            deadline = _t.perf_counter() + 10
            while _t.perf_counter() < deadline:
                doc = json.loads(_post(port, "/debug/ctrl",
                                       mod="devobs")[1])
                if not doc["profile"]["active"]:
                    break
                _t.sleep(0.05)
            assert not doc["profile"]["active"]
        else:
            # backends without profiler support answer 409 with the
            # start error — the guard must not be wedged afterwards
            assert status == 409
            doc = json.loads(_post(port, "/debug/ctrl", mod="devobs")[1])
            assert not doc["profile"]["active"]
        # unknown op is a 400, never a silent default
        status, _ = _post(port, "/debug/ctrl", mod="devobs", op="wat")
        assert status == 400

    def test_bad_profile_seconds_is_400(self, server):
        status, _ = _post(server.port, "/debug/ctrl", mod="devobs",
                          op="profile", seconds="nope")
        assert status == 400


# -- per-query device stages --------------------------------------------------


class TestQueryStages:
    def test_device_stages_land_in_slowlog(self, tmp_path, mesh4):
        from opengemini_tpu.utils import slowlog

        devobs.set_enabled(True)
        colcache.GLOBAL.configure(budget_mb=64, device=True,
                                  device_budget_mb=64)
        prt.set_mesh(mesh4)
        eng = _mk_engine(tmp_path)
        prev_slow = slowlog.GLOBAL.threshold_ms
        slowlog.GLOBAL.configure(slow_ms=0.0)
        try:
            Executor(eng).execute(_Q, db="db")
            recs = slowlog.GLOBAL.snapshot()["records"]
            assert recs
            stages = recs[-1]["stages_ms"]
            assert "device_launch" in stages, stages
            assert "device_fetch" in stages, stages
        finally:
            slowlog.GLOBAL.configure(slow_ms=prev_slow)
            slowlog.GLOBAL.clear()
            eng.close()


# -- pass-through -------------------------------------------------------------


class TestPassThrough:
    def test_disarmed_bit_identity(self, tmp_path, mesh4):
        """Armed vs disarmed produce byte-identical results on the same
        mesh + device-tier configuration (the arming only observes)."""
        colcache.GLOBAL.configure(budget_mb=64, device=True,
                                  device_budget_mb=64)
        prt.set_mesh(mesh4)
        eng = _mk_engine(tmp_path)
        try:
            ex = Executor(eng)
            devobs.set_enabled(False)
            out_off = ex.execute(_Q, db="db")
            ex._inc_cache.clear()
            devobs.set_enabled(True)
            out_on = ex.execute(_Q, db="db")
            assert json.dumps(out_off, sort_keys=True) == \
                json.dumps(out_on, sort_keys=True)
        finally:
            eng.close()

    def test_warm_repeats_compile_nothing_armed_or_not(self, tmp_path):
        """Arming and disarming between warm repeats of one statement
        builds no new device program (the recompile tripwire stays 0)
        and changes no result."""
        eng = _mk_engine(tmp_path)
        try:
            ex = Executor(eng)

            def run(armed):
                devobs.set_enabled(armed)
                ex._inc_cache.clear()  # execute, not a cache lookup
                return json.dumps(ex.execute(_Q, db="db"), sort_keys=True)

            first = {run(False), run(True)}  # compile warm-up, both ways
            devobs.mark_warm()
            warm = {run(armed) for armed in (False, True, False, True)}
            assert devobs.compiles_since_warm() == 0
            assert warm == first and len(warm) == 1
        finally:
            eng.close()

    def test_disarmed_records_nothing(self, tmp_path):
        from opengemini_tpu.utils.stats import histograms_snapshot

        def device_hist_counts():
            # histograms are process-global (earlier armed tests may
            # have created families): assert on the DELTA, not absence
            return sum(s["count"] for name, _l, s in histograms_snapshot()
                       if name.startswith("device_"))

        eng = _mk_engine(tmp_path, hosts=4, points=40)
        try:
            assert not devobs.enabled()
            before = device_hist_counts()
            Executor(eng).execute(_Q, db="db")
            assert device_hist_counts() == before
            assert devobs.LEDGER.total_bytes() == 0
        finally:
            eng.close()


# -- monitor self-writes ------------------------------------------------------


class TestMonitorDeviceSelfWrite:
    def test_device_families_queryable_in_monitor_db(self, tmp_path,
                                                     mesh4):
        from opengemini_tpu.services.monitor import (MONITOR_DB,
                                                     MonitorService)

        devobs.set_enabled(True)
        colcache.GLOBAL.configure(budget_mb=64, device=True,
                                  device_budget_mb=64)
        prt.set_mesh(mesh4)
        eng = _mk_engine(tmp_path)
        try:
            ex = Executor(eng)
            ex.execute(_Q, db="db")
            svc = MonitorService(eng, interval_s=3600)
            svc.tick()
            # transfer-size histogram: byte-unit fields (sum_bytes, and
            # p99 in raw bytes)
            res = ex.execute(
                "SELECT last(p99), last(sum_bytes) FROM "
                "ogt_device_h2d_bytes WHERE site = 'colcache-fill'",
                db=MONITOR_DB)["results"][0]
            assert "error" not in res, res
            row = res["series"][0]["values"][0]
            assert row[1] > 0 and row[2] > 0
            # ledger gauge rides the scalar measurement
            res = ex.execute(
                "SELECT last(ogt_device_ledger_bytes) FROM ogt",
                db=MONITOR_DB)["results"][0]
            assert "error" not in res, res
            assert res["series"][0]["values"][0][1] > 0
        finally:
            eng.close()


# -- capability probe ---------------------------------------------------------


class TestCapabilities:
    def test_probe_shape_and_consistency(self):
        caps = devobs.backend_capabilities()
        assert caps["probed"] is True
        assert caps["backend"] == "cpu"  # conftest forces CPU
        assert caps["device_count"] >= 4
        ok, why = devobs.pallas_supported()
        assert isinstance(ok, bool)
        if not ok:
            assert why  # a failing probe always explains itself
        # cached: second call returns the identical dict, and the
        # cache-only form now answers from it too
        assert devobs.backend_capabilities() is caps
        assert devobs.backend_capabilities(probe=False) is caps

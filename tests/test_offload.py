"""Adaptive host/device offload planner (ISSUE 17, query/offload.py):
the per-(kernel, geometry) cost model, the decision ladder
(forced / amortize / prewarm / prior / explore / model), freeze
semantics, the static-gate prior, the background pre-warmer, and the
ctrl + /debug/device surfaces.

The live flip host->device cannot be demonstrated on a 1-core CPU
backend (the host route's scattered grid goes device-resident and warm
repeats bypass decide() entirely), so the flip machinery is exercised
synthetically here: observe() samples and compile-wall priors are fed
directly and every decision reason is asserted.  The bit-identity
contract (OGT_OFFLOAD=0 and a cold model both mirror the static gates
exactly) is checked both unit-level and over a real grid query.
"""

import json
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from opengemini_tpu.query import offload
from opengemini_tpu.query.offload import Planner, _geo_cells
from opengemini_tpu.storage import colcache
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import devobs

NS = 10**9
BASE = 1_700_000_000

GEO = ((8, 4, 16), "float64")
GEO2 = ((32, 4, 16), "float64")


@pytest.fixture(autouse=True)
def _offload_state():
    """Every test starts with an enabled, empty, unfrozen planner and
    restores the process-global planner/pre-warmer state on exit."""
    prev = offload.enabled()
    offload.reset()
    offload.set_enabled(True)
    offload.set_force(None)
    offload.GLOBAL.configure(min_samples=2, explore_after=3,
                             amortize=4.0, ewma=0.3)
    yield
    offload.reset()
    offload.set_enabled(prev)
    offload.set_force(None)
    devobs.reset()


def _no_compile(monkeypatch):
    monkeypatch.setattr(offload, "_compile_estimate_s", lambda k: 0.0)


def _compile_cost(monkeypatch, seconds):
    monkeypatch.setattr(offload, "_compile_estimate_s",
                        lambda k: float(seconds))


# -- geometry cells + route record -------------------------------------------


class TestModelPrimitives:
    def test_geo_cells_flattens_and_ignores_non_numeric(self):
        assert _geo_cells(((8, 4, 16), "float64")) == 8 * 4 * 16
        assert _geo_cells((2, (3, (4,)), "f8", None)) == 24
        # bools and non-positive extents are not size
        assert _geo_cells((True, 8, 0, -3)) == 8
        assert _geo_cells("float64") == 1

    def test_route_record_cold_then_warm_ewma(self):
        r = offload._Route()
        r.add(2.0, alpha=0.5)  # cold: carries the compile
        assert r.cold_s == 2.0 and r.ewma_s == 2.0 and r.count == 1
        r.add(0.1, alpha=0.5)  # first warm sample REPLACES the ewma
        assert r.ewma_s == pytest.approx(0.1)
        r.add(0.3, alpha=0.5)  # then normal ewma blending
        assert r.ewma_s == pytest.approx(0.1 * 0.5 + 0.3 * 0.5)
        assert r.cold_s == 2.0  # cold wall preserved for amortization

    def test_compile_estimate_prefix_matches_inventory(self, monkeypatch):
        inv = {
            "prom_rate": {"geometries": [
                {"geometry": "a", "wall_ms": 800.0},
                {"geometry": "b", "wall_ms": 1200.0},
            ]},
            "prom_rate_sharded": {"geometries": [
                {"geometry": "a", "wall_ms": 400.0},
            ]},
            "bucket_stats": {"geometries": [
                {"geometry": "a", "wall_ms": 50.0},
            ]},
        }
        monkeypatch.setattr(devobs, "inventory", lambda: inv)
        # "prom_rate" covers both compile sites (prefix match)
        est = offload._compile_estimate_s("prom_rate")
        assert est == pytest.approx((800 + 1200 + 400) / 3 / 1e3)
        assert offload._compile_estimate_s("bucket_stats") == \
            pytest.approx(0.05)
        assert offload._compile_estimate_s("nope") == 0.0
        assert offload._compile_estimate_s("") == 0.0


# -- the decision ladder ------------------------------------------------------


class TestDecisionLadder:
    def test_cold_model_mirrors_static_gate(self, monkeypatch):
        """Bit-identity: a cold planner answers the static choice with
        reason 'prior', whatever that choice is."""
        _no_compile(monkeypatch)
        p = Planner()
        for static in ("host", "device"):
            assert p.decide("k", GEO, ("host", "device"),
                            static=static) == static
        recs = p.decisions()
        assert all(r["reason"] == "prior" for r in recs)

    def test_disabled_planner_is_pass_through(self):
        offload.set_enabled(False)
        p = Planner()
        p.observe("k", GEO, "host", 0.5)  # dropped
        assert p.model_snapshot() == []
        assert p.decide("k", GEO, ("host", "device"),
                        static="device") == "device"
        assert p.decisions() == []  # no ring entry either

    def test_prior_to_measured_transition(self, monkeypatch):
        """Below min_samples the static choice wins; once the incumbent
        is measured and a cheaper measured candidate exists, the model
        flips — no prewarm gate because the winner has real samples."""
        _no_compile(monkeypatch)
        p = Planner()
        p.configure(min_samples=2, explore_after=0)
        # one host sample only: still prior
        p.observe("k", GEO, "host", 0.010)
        assert p.decide("k", GEO, ("host", "device"),
                        static="host") == "host"
        assert p.decisions()[0]["reason"] == "prior"
        # incumbent measured; device measured cheaper -> model flip
        p.observe("k", GEO, "host", 0.010)
        p.observe("k", GEO, "device", 0.001)
        p.observe("k", GEO, "device", 0.001)
        assert p.decide("k", GEO, ("host", "device"),
                        static="host") == "device"
        assert p.decisions()[0]["reason"] == "model"
        # the measured winner holds from either static starting point
        assert p.decide("k", GEO, ("host", "device"),
                        static="device") == "device"

    def test_model_ties_resolve_to_static(self, monkeypatch):
        _no_compile(monkeypatch)
        p = Planner()
        p.configure(min_samples=1, explore_after=0)
        for route in ("host", "device"):
            p.observe("k", GEO, route, 0.005)
            p.observe("k", GEO, route, 0.005)
        assert p.decide("k", GEO, ("host", "device"),
                        static="host") == "host"
        assert p.decide("k", GEO, ("host", "device"),
                        static="device") == "device"

    def test_explore_trials_unmeasured_candidate(self, monkeypatch):
        _no_compile(monkeypatch)
        p = Planner()
        p.configure(min_samples=2, explore_after=3)
        p.observe("k", GEO, "host", 0.010)
        p.observe("k", GEO, "host", 0.010)
        routes = []
        for _ in range(6):
            routes.append(p.decide("k", GEO, ("host", "device"),
                                   static="host"))
        reasons = [r["reason"] for r in reversed(p.decisions())]
        # first explore_after uses stay on the incumbent, then a trial
        assert "explore" in reasons
        first_explore = reasons.index("explore")
        assert first_explore >= 3  # uses must exceed explore_after
        assert routes[first_explore] == "device"

    def test_explore_deferred_by_amortization(self, monkeypatch):
        """A huge predicted compile wall defers the device trial until
        recurrence covers it — no compile data, recurrence alone
        gates."""
        _compile_cost(monkeypatch, 1000.0)  # never amortizes at 10ms/use
        p = Planner()
        p.configure(min_samples=2, explore_after=2, amortize=4.0)
        p.observe("k", GEO, "host", 0.010)
        p.observe("k", GEO, "host", 0.010)
        for _ in range(8):
            assert p.decide("k", GEO, ("host", "device"),
                            static="host") == "host"
        assert all(r["route"] == "host" for r in p.decisions())
        ctr = _stats_counters()
        assert ctr.get("explore_deferred_total", 0) >= 1

    def test_kernel_wide_per_cell_prior_scales(self, monkeypatch):
        """A new geometry of a measured kernel inherits the family's
        per-cell cost: a 4x-bigger shape estimates ~4x the wall, so the
        model can rank routes before this exact shape is measured."""
        _no_compile(monkeypatch)
        p = Planner()
        p.configure(min_samples=1, explore_after=10**6)  # model only
        cells = _geo_cells(GEO)
        # host is expensive per cell, device cheap — both measured on GEO
        p.observe("k", GEO, "host", 1e-6 * cells)
        p.observe("k", GEO, "host", 1e-6 * cells)
        p.observe("k", GEO, "device", 1e-8 * cells)
        p.observe("k", GEO, "device", 1e-8 * cells)
        # GEO2 never observed: host estimate comes from the kernel
        # aggregate; the device flip is gated behind prewarm because
        # GEO2's device program never compiled — with zero compile cost
        # the gate stands aside and the model flips directly
        p.observe("k", GEO2, "host", 1e-6 * _geo_cells(GEO2))
        assert p.decide("k", GEO2, ("host", "device"),
                        static="host") == "device"
        rec = p.decisions()[0]
        assert rec["reason"] == "model"
        assert rec["est_ms"]["device"] < rec["est_ms"]["host"]


# -- amortization + pre-warm flip --------------------------------------------


class TestAmortizeAndPrewarm:
    def test_amortize_holds_device_static_on_host(self, monkeypatch):
        """static=device geometry that never compiled stays on the host
        until recurrence covers the compile wall, then waits for the
        background compile (reason 'prewarm')."""
        _compile_cost(monkeypatch, 1.0)  # 1s compile
        p = Planner()
        p.configure(min_samples=2, amortize=4.0)
        p.observe("k", GEO, "host", 0.050)  # 50ms host per use
        p.observe("k", GEO, "host", 0.050)
        # 1.0 <= 4.0 * 0.05 * uses  =>  uses >= 5
        reasons = []
        for _ in range(6):
            route = p.decide("k", GEO, ("host", "device"),
                             static="device")
            assert route == "host"  # never the device before the warm
            reasons.append(p.decisions()[0]["reason"])
        assert reasons[:4] == ["amortize"] * 4
        assert "prewarm" in reasons[4:]
        # decide() flagged it for the pre-warmer
        assert offload.wants_prewarm("k", GEO)

    def test_amortize_inert_without_compile_data(self, monkeypatch):
        """Bit-identity: no compile wall anywhere -> the amortize
        override must NOT hold a static-device geometry on the host."""
        _no_compile(monkeypatch)
        p = Planner()
        assert p.decide("k", GEO, ("host", "device"),
                        static="device") == "device"
        assert p.decisions()[0]["reason"] == "prior"

    def test_flip_waits_for_background_compile_then_lands(
            self, monkeypatch):
        """The full host->device flip: model says device (byte-hinted),
        geometry never compiled -> 'prewarm' + host; builder registered
        -> background compile runs; next decide routes to the device."""
        _compile_cost(monkeypatch, 0.5)
        p = Planner()
        p.configure(min_samples=2, explore_after=10**6)  # model only
        p.observe("k", GEO, "host", 0.100)  # expensive host
        p.observe("k", GEO, "host", 0.100)
        hint = {"device": 1024}  # ~1us at the default throughput prior
        route = p.decide("k", GEO, ("host", "device"), static="host",
                         bytes_hint=hint)
        assert route == "host"
        assert p.decisions()[0]["reason"] == "prewarm"
        assert offload.wants_prewarm("k", GEO)
        compiled = []
        offload.register_builder("k", GEO, lambda: compiled.append(1))
        deadline = time.time() + 5
        while not offload.geometry_warm("k", GEO):
            assert time.time() < deadline, "background compile never ran"
            time.sleep(0.01)
        assert compiled == [1]
        assert not offload.wants_prewarm("k", GEO)  # consumed
        route = p.decide("k", GEO, ("host", "device"), static="host",
                         bytes_hint=hint)
        assert route == "device"
        assert p.decisions()[0]["reason"] == "model"

    def test_prewarm_once_ranks_by_hits_and_arms_tripwire(self):
        built = []
        offload.register_builder("hotk", GEO,
                                 lambda: built.append("hot"))
        offload.register_builder("coldk", GEO,
                                 lambda: built.append("cold"))
        # devobs inventory hit counts rank hotk first
        devobs.note_compile("hotk", GEO)
        for _ in range(10):
            devobs.note_use("hotk", GEO)
        devobs.note_compile("coldk", GEO)
        ran = offload.prewarm_once(topk=1)
        assert [r["kernel"] for r in ran] == ["hotk"]
        assert built == ["hot"] and ran[0]["ok"]
        assert offload.geometry_warm("hotk", GEO)
        assert not offload.geometry_warm("coldk", GEO)
        # the sweep arms the recompile tripwire
        assert devobs.compiles_since_warm() == 0
        devobs.note_compile("late", ())
        assert devobs.compiles_since_warm() == 1
        st = offload.prewarm_status()
        assert st["registered"] == 2 and st["warm"] == 1
        assert st["last"] == {"ran": 1, "ok": 1}

    def test_prewarm_once_one_bad_builder_does_not_starve(self):
        def boom():
            raise RuntimeError("no backend")

        built = []
        offload.register_builder("a", GEO, boom)
        offload.register_builder("b", GEO, lambda: built.append("b"))
        ran = offload.prewarm_once(topk=4)
        by_k = {r["kernel"]: r for r in ran}
        assert not by_k["a"]["ok"] and "RuntimeError" in by_k["a"]["error"]
        assert by_k["b"]["ok"] and built == ["b"]

    def test_start_stop_prewarmer_thread(self):
        assert offload.start_prewarmer(interval_s=0.2)
        assert not offload.start_prewarmer(interval_s=0.2)  # idempotent
        assert offload.prewarm_status()["thread_alive"]
        offload.stop_prewarmer()
        assert not offload.prewarm_status()["thread_alive"]


# -- freeze / force / gate prior ---------------------------------------------


def _stats_counters():
    from opengemini_tpu.utils.stats import GLOBAL as STATS

    return STATS.counters("offload")


class TestFreezeForceGate:
    def test_frozen_planner_is_pinned(self, monkeypatch):
        _no_compile(monkeypatch)
        p = Planner()
        p.configure(min_samples=1, explore_after=0)
        p.observe("k", GEO, "host", 0.010)
        p.observe("k", GEO, "device", 0.001)
        assert p.decide("k", GEO, ("host", "device"),
                        static="host") == "device"
        uses_before = p.model_snapshot()[0]["uses"]
        p.set_frozen(True)
        # frozen: samples dropped, uses not incremented, model answers
        p.observe("k", GEO, "device", 99.0)
        snap = p.model_snapshot()[0]
        assert snap["routes"]["device"]["count"] == 1
        assert p.decide("k", GEO, ("host", "device"),
                        static="host") == "device"
        assert p.model_snapshot()[0]["uses"] == uses_before
        p.set_frozen(False)
        p.observe("k", GEO, "device", 0.002)
        assert p.model_snapshot()[0]["routes"]["device"]["count"] == 2

    def test_frozen_planner_does_not_explore(self, monkeypatch):
        _no_compile(monkeypatch)
        p = Planner()
        p.configure(min_samples=2, explore_after=0)
        p.observe("k", GEO, "host", 0.010)
        p.observe("k", GEO, "host", 0.010)
        p.set_frozen(True)
        for _ in range(5):
            assert p.decide("k", GEO, ("host", "device"),
                            static="host") == "host"
        assert all(r["reason"] != "explore" for r in p.decisions())

    def test_forced_route_overrides_everything(self, monkeypatch):
        _no_compile(monkeypatch)
        offload.set_force("device")
        p = Planner()
        p.observe("k", GEO, "host", 0.001)
        p.observe("k", GEO, "host", 0.001)
        assert p.decide("k", GEO, ("host", "device"),
                        static="host") == "device"
        # not a candidate -> the force stands aside
        assert p.decide("k", GEO, ("host",), static="host") == "host"
        with pytest.raises(ValueError):
            offload.set_force("gpu")

    def test_prom_host_kernels_mode_validation(self):
        offload.set_prom_host_kernels_mode("1")
        assert offload.prom_host_kernels_mode() == "1"
        offload.set_prom_host_kernels_mode("auto")
        assert offload.prom_host_kernels_mode() == ""
        with pytest.raises(ValueError):
            offload.set_prom_host_kernels_mode("maybe")


# -- bit-identity over a real query ------------------------------------------


def _mk_engine(tmp_path, hosts=8, points=90):
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


class TestBitIdentity:
    def test_grid_query_identical_planner_on_off(self, tmp_path):
        """OGT_OFFLOAD=0 (and equally a cold model) must reproduce the
        static-gate results bit-identically over a real grid query."""
        from opengemini_tpu.query.executor import Executor

        eng = _mk_engine(tmp_path)
        try:
            ex = Executor(eng)

            def run():
                colcache.GLOBAL.clear()
                return json.dumps(ex.execute(_Q, db="db"),
                                  sort_keys=True)

            offload.set_enabled(True)
            offload.GLOBAL.clear()
            on_cold = [run() for _ in range(3)]
            offload.set_enabled(False)
            off = [run() for _ in range(3)]
            assert on_cold == off
            assert len(set(on_cold)) == 1
        finally:
            eng.close()
            colcache.GLOBAL.clear()


# -- ctrl + debug surfaces ----------------------------------------------------


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
def server(tmp_path):
    from opengemini_tpu.server.http import HttpService

    eng = _mk_engine(tmp_path)
    svc = HttpService(eng, "127.0.0.1", 0)
    svc.start()
    yield svc
    svc.stop()
    eng.close()


class TestCtrlAndDebug:
    def test_ctrl_status_and_knobs(self, server):
        port = server.port
        status, body = _post(port, "/debug/ctrl", mod="offload")
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ok" and doc["enabled"]
        assert doc["knobs"]["min_samples"] == 2
        status, body = _post(port, "/debug/ctrl", mod="offload",
                             min_samples=5, amortize="2.5", freeze=1,
                             host_kernels="1", force="device")
        assert status == 200
        doc = json.loads(body)
        assert doc["knobs"]["min_samples"] == 5
        assert doc["knobs"]["amortize"] == 2.5
        assert doc["knobs"]["prom_host_kernels"] == "1"
        assert doc["knobs"]["force"] == "device"
        assert doc["frozen"]
        assert offload.GLOBAL.frozen()
        # disarm + clear + unforce restores
        status, body = _post(port, "/debug/ctrl", mod="offload",
                             arm=0, freeze=0, clear=1, force="none",
                             host_kernels="auto")
        doc = json.loads(body)
        assert not doc["enabled"] and not doc["frozen"]
        assert doc["knobs"]["force"] == "none"
        assert doc["model"] == [] and doc["decisions"] == []

    def test_ctrl_rejects_bad_values(self, server):
        port = server.port
        assert _post(port, "/debug/ctrl", mod="offload",
                     force="gpu")[0] == 400
        assert _post(port, "/debug/ctrl", mod="offload",
                     host_kernels="maybe")[0] == 400
        assert _post(port, "/debug/ctrl", mod="offload",
                     min_samples="lots")[0] == 400
        assert _post(port, "/debug/ctrl", mod="offload",
                     op="frobnicate")[0] == 400

    def test_ctrl_prewarm_op(self, server):
        built = []
        offload.register_builder("k", GEO, lambda: built.append(1))
        status, body = _post(server.port, "/debug/ctrl", mod="offload",
                             op="prewarm")
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ok"
        assert [r["kernel"] for r in doc["prewarmed"]] == ["k"]
        assert built == [1]

    def test_debug_device_has_planner_section(self, server):
        offload.GLOBAL.observe("k", GEO, "host", 0.005)
        offload.GLOBAL.decide("k", GEO, ("host", "device"),
                              static="host", stage="prom_kernel")
        status, body = _get(server.port, "/debug/device")
        assert status == 200
        doc = json.loads(body)
        pl = doc["planner"]
        assert pl["enabled"] and not pl["frozen"]
        assert set(pl["knobs"]) >= {"min_samples", "explore_after",
                                    "amortize", "ewma", "force",
                                    "prom_host_kernels"}
        assert pl["model"][0]["kernel"] == "k"
        assert pl["model"][0]["routes"]["host"]["count"] == 1
        dec = pl["decisions"][0]
        assert dec["stage"] == "prom_kernel"
        assert dec["route"] == "host" and dec["reason"] == "prior"
        assert "est_ms" in dec
        assert set(pl["prewarm"]) >= {"registered", "warm", "wanted",
                                      "inflight", "thread_alive"}

    def test_planner_counters_in_metrics(self, server):
        offload.GLOBAL.decide("k", GEO, ("host", "device"),
                              static="host")
        status, body = _get(server.port, "/metrics")
        assert status == 200
        text = body.decode()
        assert "ogt_offload_decisions_total" in text
        assert "ogt_offload_route_host_total" in text

"""Multi-chip sharded execution (ISSUE 13): the tiled PromQL kernels,
the grid/bucketed dense layouts, and the colcache device tier over the
virtual 8-device CPU mesh — series axes sharded, results equal to
single-device, warm mesh scans transfer-free, and mesh swaps (hot config
reloads) resharding instead of serving dead-mesh shards."""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from opengemini_tpu.ops import prom as promops
from opengemini_tpu.parallel import distributed as dist
from opengemini_tpu.parallel import runtime as prt
from opengemini_tpu.utils.stats import GLOBAL as STATS


def _counter(module, name):
    return STATS.snapshot().get(module, {}).get(name, 0)


@pytest.fixture(scope="module")
def mesh():
    return dist.make_mesh(8, ("shard",))


@pytest.fixture(autouse=True)
def _no_leaked_mesh():
    yield
    prt.set_mesh(None)


def _synth_series(rng, n_series, lo=40, hi=160):
    """Irregular per-series counter samples on a 250ms lattice, with a
    mid-stream reset so the correction path is exercised."""
    lens = rng.integers(lo, hi, size=n_series)
    base_ms = 1_700_000_000_000
    t_parts, v_parts = [], []
    for length in lens:
        t = np.sort(rng.choice(np.arange(0, 3_600_000, 250), size=length,
                               replace=False)) + base_ms
        v = np.cumsum(rng.random(length))
        v[length // 2:] -= v[length // 2] * 0.5
        t_parts.append(t)
        v_parts.append(v)
    t_all = np.concatenate(t_parts)
    v_all = np.concatenate(v_parts)
    ends = (base_ms + np.arange(24) * 150_000 + 600_000) / 1000.0
    return t_all, v_all, lens, ends


def _prep(rng, n_series):
    t_all, v_all, lens, ends = _synth_series(rng, n_series)
    plan = promops.plan_tiles(ends - 300.0, ends, int(t_all.min()),
                              int(t_all.max()), 1 << 20)
    assert plan is not None
    prep = promops.prepare_tiled(plan, t_all, v_all, lens, dtype=np.float64)
    assert prep is not None
    return prep


class TestShardedTiledProm:
    """ops/prom.py ShardedTiled vs the host-numpy reference: every
    kernel, series counts deliberately uneven vs the mesh (S % 8 != 0 and
    S < 8 both shard via padding with masked-off rows)."""

    # S=13: uneven; S=5: fewer series than devices; S=16: even
    @pytest.mark.parametrize("n_series", [13, 5, 16])
    def test_kernels_match_host(self, rng, mesh, n_series):
        prep = _prep(rng, n_series)
        sh = prep.sharded(mesh)
        assert len(sh.arrays["times"].addressable_shards) == mesh.size
        cases = [
            ("rate", lambda p, xp: p.rate(xp, is_counter=True, is_rate=True),
             lambda s: s.rate(is_counter=True, is_rate=True), 0.0),
            ("delta", lambda p, xp: p.rate(xp, is_counter=False,
                                           is_rate=False),
             lambda s: s.rate(is_counter=False, is_rate=False), 0.0),
            ("irate", lambda p, xp: p.instant_rate(xp, per_second=True),
             lambda s: s.instant_rate(per_second=True), 0.0),
            ("changes", lambda p, xp: p.changes_resets(xp, kind="changes"),
             lambda s: s.changes_resets(kind="changes"), 0.0),
            ("resets", lambda p, xp: p.changes_resets(xp, kind="resets"),
             lambda s: s.changes_resets(kind="resets"), 0.0),
            ("sum", lambda p, xp: p.over_time(xp, func="sum"),
             lambda s: s.over_time(func="sum"), 0.0),
            ("min", lambda p, xp: p.over_time(xp, func="min"),
             lambda s: s.over_time(func="min"), 0.0),
            ("max", lambda p, xp: p.over_time(xp, func="max"),
             lambda s: s.over_time(func="max"), 0.0),
            ("last", lambda p, xp: p.over_time(xp, func="last"),
             lambda s: s.over_time(func="last"), 0.0),
            ("count", lambda p, xp: p.over_time(xp, func="count"),
             lambda s: s.over_time(func="count"), 0.0),
            # near-zero variance windows cancel in the last ulps (the
            # documented over_time stddev sensitivity) — atol, not exact
            ("stddev", lambda p, xp: p.over_time(xp, func="stddev"),
             lambda s: s.over_time(func="stddev"), 1e-6),
            ("stdvar", lambda p, xp: p.over_time(xp, func="stdvar"),
             lambda s: s.over_time(func="stdvar"), 1e-6),
        ]
        S = prep.S
        for name, host_fn, mesh_fn, atol in cases:
            h_val, h_ok = host_fn(prep, np)
            m_val, m_ok = mesh_fn(sh)
            m_val = np.asarray(m_val)[:S, :prep.k_real]
            m_ok = np.asarray(m_ok)[:S, :prep.k_real]
            assert np.array_equal(np.asarray(h_ok), m_ok), name
            np.testing.assert_allclose(
                np.where(h_ok, h_val, 0), np.where(m_ok, m_val, 0),
                rtol=1e-9, atol=atol, err_msg=name)

    def test_linear_regression_matches_host(self, rng, mesh):
        prep = _prep(rng, 13)
        sh = prep.sharded(mesh)
        h_slope, h_icept, h_ok = prep.linear_regression(np)
        m_slope, m_icept, m_ok = sh.linear_regression()
        S = prep.S
        m_ok = np.asarray(m_ok)[:S, :prep.k_real]
        assert np.array_equal(np.asarray(h_ok), m_ok)
        for h, m in ((h_slope, m_slope), (h_icept, m_icept)):
            np.testing.assert_allclose(
                np.where(h_ok, h, 0),
                np.where(m_ok, np.asarray(m)[:S, :prep.k_real], 0),
                rtol=1e-9, atol=1e-9)

    def test_sharded_view_cached_per_mesh(self, rng, mesh):
        prep = _prep(rng, 13)
        assert prep.sharded(mesh) is prep.sharded(mesh)
        other = dist.make_mesh(4, ("shard",))
        assert prep.sharded(other) is not prep.sharded(mesh)

    def test_engine_mesh_results_match_solo(self, tmp_path, mesh):
        """PromQL end-to-end: rate/over_time under a mesh equal the
        solo run within float ulps, and the mesh kernel counter proves
        the sharded path served them."""
        from opengemini_tpu.promql.engine import PromEngine
        from opengemini_tpu.storage.engine import Engine

        NS = 10**9
        base = 1_700_000_000
        e = Engine(str(tmp_path / "prom"))
        e.create_database("db")
        lines = []
        for s in range(11):  # 11 series: uneven vs the 8-device mesh
            for i in range(120):
                t = (base + i * 15 + (s % 3)) * NS
                lines.append(
                    f"reqs,host=h{s} value={i * 2 + s * 0.5} {t}")
        e.write_lines("db", "\n".join(lines))
        pe = PromEngine(e)
        queries = ["rate(reqs[5m])", "sum_over_time(reqs[10m])",
                   "max_over_time(reqs[5m])", "deriv(reqs[5m])"]
        for q in queries:
            solo = pe.query_range(q, base + 600, base + 1500, 60, db="db")
            before = _counter("prom", "tiled_mesh_kernels")
            prt.set_mesh(mesh)
            try:
                meshed = pe.query_range(q, base + 600, base + 1500, 60,
                                        db="db")
            finally:
                prt.set_mesh(None)
            assert _counter("prom", "tiled_mesh_kernels") > before, q
            assert len(solo["result"]) == len(meshed["result"])
            for a, b in zip(solo["result"], meshed["result"]):
                assert a["metric"] == b["metric"]
                for (ta, va), (tb, vb) in zip(a["values"], b["values"]):
                    assert ta == tb
                    assert math.isclose(float(va), float(vb),
                                        rel_tol=1e-9, abs_tol=1e-12), q
        e.close()

    def test_mesh_opt_out_knob(self, rng, mesh, monkeypatch):
        from opengemini_tpu.promql import engine as pengine

        prt.set_mesh(mesh)
        monkeypatch.setenv("OGT_PROM_MESH", "0")
        assert pengine._mesh_for_tiled() is None
        monkeypatch.delenv("OGT_PROM_MESH")
        assert pengine._mesh_for_tiled() is mesh


class TestUnevenGridAndBucketed:
    """Satellite: S not divisible by mesh.size (and S below it) stays
    bit-identical to single-device for the grid and bucketed layouts."""

    def _engine(self, tmp_path, n_hosts):
        from opengemini_tpu.storage.engine import Engine

        NS = 10**9
        base = 1_700_000_040
        e = Engine(str(tmp_path / f"u{n_hosts}"))
        e.create_database("db")
        lines = []
        for i in range(90):
            t = (base + i) * NS
            for h in range(n_hosts):
                lines.append(f"m,host=h{h} v={(h * 13 + i) % 9} {t}")
        e.write_lines("db", "\n".join(lines))
        return e

    @pytest.mark.parametrize("n_hosts", [5, 13, 20])
    def test_grid_and_bucketed_match_solo(self, tmp_path, mesh, n_hosts):
        from opengemini_tpu.query.executor import Executor

        e = self._engine(tmp_path, n_hosts)
        ex = Executor(e)
        queries = [
            # grid layout (GROUP BY time over regular data)
            "SELECT mean(v), count(v), max(v) FROM m GROUP BY time(1m), host",
            # grid selectors: the sharded imat (sample-index grid) path
            "SELECT first(v), last(v) FROM m GROUP BY time(1m), host",
            # bucketed layout (bare selector, exact point time)
            "SELECT min(v) FROM m GROUP BY host",
            "SELECT first(v), last(v) FROM m",
        ]
        solo = [ex.execute(q, db="db") for q in queries]
        prt.set_mesh(mesh)
        try:
            ex._inc_cache.clear()
            meshed = [ex.execute(q, db="db") for q in queries]
        finally:
            prt.set_mesh(None)
        for q, a, b in zip(queries, solo, meshed):
            assert a == b, q
        e.close()

    def test_rows_below_mesh_size_fall_back_replicated(self, mesh):
        from opengemini_tpu.models.grid import GridBatch

        # fewer grid rows than devices: the batch must keep the
        # single-device layout (padding 7 rows onto 8 devices would
        # leave idle shards and a degenerate partition)
        assert GridBatch._mesh_for_rows(mesh.size - 1) is None
        prt.set_mesh(mesh)
        try:
            assert GridBatch._mesh_for_rows(mesh.size - 1) is None
            assert GridBatch._mesh_for_rows(mesh.size) is mesh
        finally:
            prt.set_mesh(None)


class TestStaleMeshReload:
    """Satellite: a hot config reload that swaps the mesh mid-batch must
    reshard — never serve shards laid out for the dead mesh."""

    def _grid_batch(self, rng, n_rows=16, W=8):
        from opengemini_tpu.models.grid import GridBatch

        NS = 10**9
        b = GridBatch(np.float64, W=W, every_ns=60 * NS)
        n_pts = 60
        for s in range(n_rows):
            rel = np.arange(n_pts, dtype=np.int64) * (8 * NS)
            seg = (rel // (60 * NS)) % W
            vals = rng.random(n_pts) * 10
            b.add(vals, rel, seg, np.ones(n_pts, bool), rel, sids=s)
        return b

    def test_grid_batch_reshards_on_set_mesh(self):
        from opengemini_tpu.ops.aggregates import get as agg_get

        ref = self._grid_batch(np.random.default_rng(99))
        b = self._grid_batch(np.random.default_rng(99))  # identical data
        out_ref, _, _ = ref.run(agg_get("sum"), 8)
        ssd_ref, _, _ = ref.run(agg_get("stddev"), 8)

        mesh_a = dist.make_mesh(8, ("shard",))
        prt.set_mesh(mesh_a)
        try:
            out_a, _, _ = b.run(agg_get("sum"), 8)  # basic kernel, mesh A
            epoch_a = b._state.get("mesh_epoch")
            mesh_b = dist.make_mesh(4, ("shard",))
            prt.set_mesh(mesh_b)  # hot reload mid-batch
            ssd_b, _, _ = b.run(agg_get("stddev"), 8)  # ssd kernel, mesh B
            epoch_b = b._state.get("mesh_epoch")
        finally:
            prt.set_mesh(None)
        np.testing.assert_allclose(out_a, out_ref, rtol=1e-12)
        np.testing.assert_allclose(ssd_b, ssd_ref, rtol=1e-12)
        assert epoch_a is not None and epoch_b is not None
        assert epoch_b != epoch_a, "mesh swap must rekey the sharded cache"

    def test_bucket_reshards_on_set_mesh(self, rng):
        from opengemini_tpu.models.ragged import BucketedBatch
        from opengemini_tpu.ops.aggregates import get as agg_get

        def build():
            r = np.random.default_rng(7)
            b = BucketedBatch(np.float64)
            NS = 10**9
            for s in range(12):
                n_pts = 40
                rel = np.arange(n_pts, dtype=np.int64) * NS
                seg = np.full(n_pts, s % 8, np.int64)
                b.add(r.random(n_pts), rel, seg, np.ones(n_pts, bool), rel)
            return b

        ref = build()
        sum_ref, _, _ = ref.run(agg_get("sum"), 8, want_sel=False)
        first_ref, _, _ = ref.run(agg_get("first"), 8)

        b = build()
        prt.set_mesh(dist.make_mesh(8, ("shard",)))
        try:
            sum_a, _, _ = b.run(agg_get("sum"), 8, want_sel=False)
            prt.set_mesh(dist.make_mesh(4, ("shard",)))  # hot reload
            first_b, _, _ = b.run(agg_get("first"), 8)
        finally:
            prt.set_mesh(None)
        np.testing.assert_allclose(sum_a, sum_ref, rtol=1e-12)
        np.testing.assert_allclose(first_b, first_ref, rtol=1e-12)


class TestColcacheMeshTier:
    """The device tier under a mesh: cold scans put the padded grid
    straight into the sharded layout, warm scans are transfer-free, and
    mesh swaps reshard the retained entry (donating stale buffers)."""

    @pytest.fixture
    def cache_on(self):
        from opengemini_tpu.storage import colcache

        prior = colcache.GLOBAL.config()
        colcache.GLOBAL.clear()
        colcache.GLOBAL.configure(budget_mb=64, device=True,
                                  device_budget_mb=64)
        yield colcache.GLOBAL
        colcache.GLOBAL.clear()
        colcache.GLOBAL.configure(**prior)

    def _run_warm(self, tmp_path, cache_on, mesh):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        NS = 10**9
        base = 1_700_000_040
        e = Engine(str(tmp_path / "cc"))
        e.create_database("db")
        lines = []
        for i in range(120):
            t = (base + i) * NS
            for h in range(20):
                lines.append(f"m,host=h{h} v={(h + i) % 7} {t}")
        e.write_lines("db", "\n".join(lines))
        e.flush_all()
        ex = Executor(e)
        q = "SELECT mean(v), count(v), max(v) FROM m GROUP BY time(1m), host"
        return e, ex, q

    def test_warm_mesh_scan_is_transfer_free(self, tmp_path, cache_on,
                                             mesh):
        e, ex, q = self._run_warm(tmp_path, cache_on, mesh)
        solo = ex.execute(q, db="db")
        prt.set_mesh(mesh)
        try:
            ex._inc_cache.clear()
            cold = ex.execute(q, db="db")
            ex._inc_cache.clear()
            h2d0 = _counter("device", "mesh_h2d_bytes")
            hits0 = cache_on.counters()["device_hits"]
            warm = ex.execute(q, db="db")
            h2d1 = _counter("device", "mesh_h2d_bytes")
            hits1 = cache_on.counters()["device_hits"]
        finally:
            prt.set_mesh(None)
        assert solo == cold == warm
        assert h2d1 == h2d0, "warm mesh scan must not re-shard"
        assert hits1 > hits0
        # the retained entry is mesh-sharded: one shard per device
        ent = next(iter(cache_on._dev.values()))[0]
        assert ent["mesh"] is mesh
        assert len(ent["vt"].addressable_shards) == mesh.size
        e.close()

    def test_mesh_swap_reshards_entry_with_donation(self, tmp_path,
                                                    cache_on, mesh):
        e, ex, q = self._run_warm(tmp_path, cache_on, mesh)
        solo = ex.execute(q, db="db")
        prt.set_mesh(mesh)
        try:
            ex._inc_cache.clear()
            ex.execute(q, db="db")  # cold: sharded put at 8 devices
            mesh4 = dist.make_mesh(4, ("shard",))
            prt.set_mesh(mesh4)  # hot reload
            ex._inc_cache.clear()
            reshards0 = cache_on.counters()["device_reshards"]
            swapped = ex.execute(q, db="db")
            reshards1 = cache_on.counters()["device_reshards"]
        finally:
            prt.set_mesh(None)
        assert solo == swapped
        assert reshards1 > reshards0, "mesh swap must reshard in place"
        ent = next(iter(cache_on._dev.values()))[0]
        assert ent["mesh"] is mesh4
        assert len(ent["vt"].addressable_shards) == 4
        # back to single-device: the entry follows
        ex._inc_cache.clear()
        back = ex.execute(q, db="db")
        assert back == solo
        ent = next(iter(cache_on._dev.values()))[0]
        assert ent["mesh"] is None
        assert len(ent["vt"].addressable_shards) == 1
        e.close()


class TestEntryDropRecovery:
    """A mesh swap whose geometry cannot reshard the retained entry
    (rows % mesh.size != 0) drops it — a batch that skipped the host
    scatter on the freeze-time device hit must rebuild from raw rows,
    not crash."""

    @pytest.fixture
    def cache_on(self):
        from opengemini_tpu.storage import colcache

        prior = colcache.GLOBAL.config()
        colcache.GLOBAL.clear()
        colcache.GLOBAL.configure(budget_mb=64, device=True,
                                  device_budget_mb=64)
        yield colcache.GLOBAL
        colcache.GLOBAL.clear()
        colcache.GLOBAL.configure(**prior)

    def test_grid_rebuilds_after_entry_drop(self, cache_on, mesh):
        from opengemini_tpu.models.grid import GridBatch
        from opengemini_tpu.ops.aggregates import get as agg_get

        NS = 10**9

        def build(token):
            # np.dtype, not the np.float64 class: the device-tier key
            # compares str(dtype) and the executor always passes a dtype
            b = GridBatch(np.dtype(np.float64), W=8, every_ns=60 * NS)
            r = np.random.default_rng(3)
            for s in range(16):
                n_pts = 48
                rel = np.arange(n_pts, dtype=np.int64) * (10 * NS)
                seg = (rel // (60 * NS)) % 8
                b.add(r.random(n_pts), rel, seg, np.ones(n_pts, bool),
                      rel, sids=s)
            b.device_cache_token = token
            return b

        ref = build(None)
        out_ref, _, _ = ref.run(agg_get("sum"), 8)
        prt.set_mesh(mesh)
        try:
            warmer = build("tok-rebuild")
            out_a, _, _ = warmer.run(agg_get("sum"), 8)  # cold sharded put
            second = build("tok-rebuild")
            second._freeze(8)  # device hit: host scatter skipped
            assert second._state["arrays"] is None
            # 16 rows cannot shard over 3 devices -> the entry drops on
            # next consult; the batch must rebuild its host grid
            prt.set_mesh(dist.make_mesh(3, ("shard",)))
            drops0 = cache_on.counters()["device_reshard_drops"]
            out_b, _, _ = second.run(agg_get("sum"), 8)
            assert cache_on.counters()["device_reshard_drops"] > drops0
        finally:
            prt.set_mesh(None)
        np.testing.assert_allclose(out_a, out_ref, rtol=1e-12)
        np.testing.assert_allclose(out_b, out_ref, rtol=1e-12)


def test_server_mesh_hot_reload(mesh):
    """[device] is SIGHUP-reloadable: geometry changes swap the mesh
    (bumping the epoch so sharded caches reshard), identical config is a
    no-op (no epoch churn), and an empty section turns the mesh off."""
    from opengemini_tpu.server.app import _apply_mesh_config

    prt.set_mesh(None)
    assert _apply_mesh_config({"mesh-axes": ["shard"], "mesh-devices": 8})
    assert prt.get_mesh() is not None and prt.get_mesh().size == 8
    epoch = prt.mesh_epoch()
    assert _apply_mesh_config({"mesh-axes": ["shard"],
                               "mesh-devices": 8}) == []
    assert prt.mesh_epoch() == epoch, "no-op reload must not bump epoch"
    assert _apply_mesh_config({"mesh-axes": ["shard"], "mesh-devices": 4})
    assert prt.get_mesh().size == 4 and prt.mesh_epoch() != epoch
    assert _apply_mesh_config({}) == ["device.mesh=off"]
    assert prt.get_mesh() is None


def test_downsample_records_match_solo_under_mesh(mesh):
    """The downsample rewrite path (storage/downsample.py -> AggBatch ->
    the shard_map mesh program) produces identical records under the
    8-device mesh — destructive rewrites tolerate zero divergence."""
    from opengemini_tpu.record import Column, FieldType, Record
    from opengemini_tpu.storage.downsample import downsample_records

    NS = 10**9
    rng = np.random.default_rng(11)
    series = {}
    for sid in range(10):  # uneven vs the 8-device mesh
        n = 90
        times = (np.arange(n, dtype=np.int64) * NS
                 + sid * 7_000_000 + 1_700_000_000 * NS)
        series[sid] = Record(times, {
            "f": Column(FieldType.FLOAT, rng.random(n) * 100,
                        rng.random(n) < 0.95),
            "i": Column(FieldType.INT, rng.integers(0, 1 << 30, n),
                        np.ones(n, bool)),
        })
    schema = {"f": FieldType.FLOAT, "i": FieldType.INT}
    tmin = int(min(r.times[0] for r in series.values()))
    tmax = int(max(r.times[-1] for r in series.values())) + 1
    args = (series, schema, tmin, tmax, 60 * NS)
    solo_recs, solo_schema = downsample_records(*args)
    prt.set_mesh(mesh)
    try:
        mesh_recs, mesh_schema = downsample_records(*args)
    finally:
        prt.set_mesh(None)
    assert solo_schema == mesh_schema
    assert sorted(solo_recs) == sorted(mesh_recs)
    for sid in solo_recs:
        a, b = solo_recs[sid], mesh_recs[sid]
        np.testing.assert_array_equal(a.times, b.times)
        assert a.columns.keys() == b.columns.keys()
        for name in a.columns:
            ca, cb = a.columns[name], b.columns[name]
            np.testing.assert_array_equal(ca.valid, cb.valid)
            np.testing.assert_allclose(
                ca.values[ca.valid].astype(np.float64),
                cb.values[cb.valid].astype(np.float64), rtol=1e-12)


def test_forced_device_count_subprocess():
    """CI tier-1 smoke independent of conftest's 8-device mesh: a child
    with a forced 6-device host platform shards the tiled prom kernel
    and matches the host reference (the bench multichip child pattern,
    small shapes)."""
    code = r"""
import json
import numpy as np
import __graft_entry__ as graft
graft._force_cpu_devices(6)
import jax
jax.config.update("jax_enable_x64", True)
from opengemini_tpu.ops import prom as promops
from opengemini_tpu.parallel import distributed as dist
assert len(jax.devices()) == 6
mesh = dist.make_mesh(6, ("shard",))
rng = np.random.default_rng(3)
S = 7  # uneven vs 6 devices
lens = rng.integers(20, 40, size=S)
base = 1_700_000_000_000
tp, vp = [], []
for L in lens:
    t = np.sort(rng.choice(np.arange(0, 600_000, 500), size=L,
                           replace=False)) + base
    tp.append(t)
    vp.append(np.cumsum(rng.random(L)))
t_all, v_all = np.concatenate(tp), np.concatenate(vp)
ends = (base + np.arange(8) * 60_000 + 120_000) / 1000.0
plan = promops.plan_tiles(ends - 120.0, ends, int(t_all.min()),
                          int(t_all.max()), 1 << 20)
prep = promops.prepare_tiled(plan, t_all, v_all, lens, dtype=np.float64)
sh = prep.sharded(mesh)
assert len(sh.arrays["times"].addressable_shards) == 6
h, hk = prep.rate(np, is_counter=True, is_rate=True)
m, mk = sh.rate(is_counter=True, is_rate=True)
m = np.asarray(m)[:S, :prep.k_real]
mk = np.asarray(mk)[:S, :prep.k_real]
assert np.array_equal(np.asarray(hk), mk)
np.testing.assert_allclose(np.where(hk, h, 0), np.where(mk, m, 0),
                           rtol=1e-9)
print("FORCED-MESH-OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child forces its own device count
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=180, cwd=root, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FORCED-MESH-OK" in r.stdout

"""Distributed execution tests on the virtual 8-device CPU mesh
(the mock_tsdb_system strategy: exchange logic without a cluster)."""

import numpy as np
import pytest

import jax

from opengemini_tpu.parallel import distributed as dist
from opengemini_tpu.ops import segment as seg
import jax.numpy as jnp


@pytest.fixture(scope="module")
def mesh():
    return dist.make_mesh(8, ("shard",))


@pytest.fixture(scope="module")
def mesh2d():
    return dist.make_mesh(8, ("shard", "time"))


def make_batch(rng, n=4000, num_segments=37):
    values = rng.normal(size=n)
    rel_ns = np.sort(rng.integers(0, 2**40, size=n)).astype(np.int64)
    rel_hi = (rel_ns >> 30).astype(np.int32)
    rel_lo = (rel_ns & (2**30 - 1)).astype(np.int32)
    seg_ids = rng.integers(0, num_segments, size=n).astype(np.int32)
    mask = rng.random(n) > 0.15
    return values, rel_hi, rel_lo, seg_ids, mask, rel_ns


@pytest.mark.parametrize("mesh_name", ["mesh", "mesh2d"])
def test_distributed_matches_single_device(request, rng, mesh_name):
    mesh = request.getfixturevalue(mesh_name)
    num_segments = 37
    values, rel_hi, rel_lo, seg_ids, mask, rel_ns = make_batch(rng)
    step = dist.build_dist_agg(mesh, num_segments)
    sharded = dist.shard_rows(mesh, values, rel_hi, rel_lo, seg_ids, mask)
    out = jax.tree.map(np.asarray, step(*sharded))

    jv, jh, jl, js, jm = map(jnp.asarray, (values, rel_hi, rel_lo, seg_ids, mask))
    ref_sum = np.asarray(seg.seg_sum(jv, js, num_segments, jm))
    ref_cnt = np.asarray(seg.seg_count(js, num_segments, jm))
    ref_min = np.asarray(seg.seg_min(jv, js, num_segments, jm))
    ref_max = np.asarray(seg.seg_max(jv, js, num_segments, jm))
    fv, _ = seg.seg_first(jv, jh, jl, js, num_segments, jm)
    lv, _ = seg.seg_last(jv, jh, jl, js, num_segments, jm)

    np.testing.assert_allclose(out["sum"], ref_sum, rtol=1e-12)
    np.testing.assert_array_equal(out["count"], ref_cnt)
    np.testing.assert_array_equal(out["min"], ref_min)
    np.testing.assert_array_equal(out["max"], ref_max)
    valid = ref_cnt > 0
    np.testing.assert_allclose(out["first"][valid], np.asarray(fv)[valid], rtol=1e-12)
    np.testing.assert_allclose(out["last"][valid], np.asarray(lv)[valid], rtol=1e-12)
    np.testing.assert_allclose(
        out["mean"][valid], ref_sum[valid] / ref_cnt[valid], rtol=1e-12
    )


def test_first_last_cross_device_boundary(mesh):
    """The global first lives on the last device (reversed times): the
    collective lexicographic merge must find it."""
    n, num_segments = 800, 3
    rel_ns = np.arange(n, 0, -1).astype(np.int64) * 1_000_000  # decreasing
    values = np.arange(n, dtype=np.float64)
    seg_ids = np.zeros(n, dtype=np.int32)
    mask = np.ones(n, dtype=bool)
    rel_hi = (rel_ns >> 30).astype(np.int32)
    rel_lo = (rel_ns & (2**30 - 1)).astype(np.int32)
    step = dist.build_dist_agg(mesh, num_segments)
    out = jax.tree.map(
        np.asarray,
        step(*dist.shard_rows(mesh, values, rel_hi, rel_lo, seg_ids, mask)),
    )
    # smallest time is the LAST row (values n-1)
    assert out["first"][0] == values[-1]
    assert out["last"][0] == values[0]


def test_graft_entry_single_and_multichip():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert int(np.asarray(out["count"]).sum()) == int(args[4].sum())
    g.dryrun_multichip(8)


def test_first_tie_not_averaged(mesh):
    """Equal earliest timestamps on different devices: result must be one
    actual row's value, never an average. Exact-time ties take the larger
    value (reference agg_func.go FirstReduce,
    TestServer_Query_Aggregates_IdenticalTime)."""
    n, num_segments = 800, 1
    rel_ns = np.full(n, 1_000_000, dtype=np.int64)  # all rows tie
    values = np.arange(n, dtype=np.float64)
    seg_ids = np.zeros(n, dtype=np.int32)
    mask = np.ones(n, dtype=bool)
    rel_hi = (rel_ns >> 30).astype(np.int32)
    rel_lo = (rel_ns & (2**30 - 1)).astype(np.int32)
    step = dist.build_dist_agg(mesh, num_segments)
    out = jax.tree.map(
        np.asarray, step(*dist.shard_rows(mesh, values, rel_hi, rel_lo, seg_ids, mask))
    )
    assert out["first"][0] == values.max()
    assert out["last"][0] == values.max()


class TestExecutorMeshPath:
    """The executor's aggregate path over a configured device mesh must
    return bit-identical results to the single-device path (rows sharded
    across 8 virtual devices, collective merges)."""

    def test_mesh_results_match_single_device(self, tmp_path):
        import jax

        from opengemini_tpu.parallel import distributed as dist
        from opengemini_tpu.parallel import runtime as prt
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        if len(jax.devices()) < 8:
            import pytest

            pytest.skip("needs 8 virtual devices")

        ns = 10**9
        base = 1_700_000_040
        lines = []
        for i in range(500):
            t = (base + i * 7) * ns + (i % 97) * 1000 + 13
            lines.append(f"m,host=h{i % 5} v={(i * 37) % 11 - 3} {t}")

        e = Engine(str(tmp_path / "mesh"))
        e.create_database("db")
        e.write_lines("db", "\n".join(lines))
        ex = Executor(e)
        queries = [
            "SELECT count(v), sum(v), mean(v) FROM m GROUP BY time(5m)",
            "SELECT min(v), max(v), spread(v) FROM m GROUP BY host",
            "SELECT first(v) FROM m",
            "SELECT last(v) FROM m",
            "SELECT max(v) FROM m",  # bare selector: exact point time
        ]
        solo = [ex.execute(q, db="db") for q in queries]
        prt.set_mesh(dist.make_mesh(8, ("shard", "time")))
        try:
            meshed = [ex.execute(q, db="db") for q in queries]
        finally:
            prt.set_mesh(None)
        for q, a, b in zip(queries, solo, meshed):
            assert a == b, (q, a, b)
        e.close()


    def test_mesh_uses_dense_layouts(self, tmp_path):
        """With a mesh set, GROUP BY time() over regular data must run the
        grid layout row-sharded over the mesh — not the scatter AggBatch
        (multi-chip used to select the slowest kernels)."""
        import jax
        import pytest

        from opengemini_tpu.parallel import distributed as dist
        from opengemini_tpu.parallel import runtime as prt
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.utils.stats import GLOBAL as STATS

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")

        ns = 10**9
        base = 1_700_000_040
        lines = []
        for i in range(60):
            for h in range(16):
                lines.append(f"m,host=h{h} v={(h + i) % 9} {(base + i) * ns}")
        e = Engine(str(tmp_path / "dense"))
        e.create_database("db")
        e.write_lines("db", "\n".join(lines))
        ex = Executor(e)

        def counter(module, name):
            return STATS.snapshot().get(module, {}).get(name, 0)

        prt.set_mesh(dist.make_mesh(8, ("shard",)))
        try:
            g0 = counter("executor", "grid_batches")
            m0 = counter("device", "mesh_dense_batches")
            res = ex.execute(
                "SELECT mean(v), count(v) FROM m GROUP BY time(1m), host",
                db="db")
            assert "series" in res["results"][0]
            assert counter("executor", "grid_batches") > g0
            assert counter("device", "mesh_dense_batches") > m0
        finally:
            prt.set_mesh(None)
        e.close()

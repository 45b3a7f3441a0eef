"""Pallas tile kernels (ops/pallas_segment.py) vs the XLA oracle.

Runs in interpret mode on the CPU-forced test backend; the kernels must
match models/ragged._stats_fn and ops/segment.grid_window_agg_t exactly,
including empty-segment identities and lexicographic tie-breaks.

Kernel-executing tests gate on the devobs backend-capability probe
(utils/devobs.py backend_capabilities): where interpret mode cannot
execute at all on this CPU they SKIP with the probe's reason instead of
failing 12 times with the same undiagnosable traceback; where the probe
passes they run (and fail) for real.  The routing test runs everywhere:
it never executes a kernel.  What Mosaic accepts on the chip is checked
by tools/pallas_chip_check.py, not here."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from opengemini_tpu.ops import pallas_segment as ps  # noqa: E402
from opengemini_tpu.ops import segment as seg  # noqa: E402
from opengemini_tpu.utils import devobs  # noqa: E402

_PALLAS_OK, _PALLAS_WHY = devobs.pallas_supported()
needs_pallas = pytest.mark.skipif(not _PALLAS_OK, reason=_PALLAS_WHY)


def _rand_bucket(g, w, seed, empty_rows=True, dtype=np.float32):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((g, w)).astype(dtype) * 10
    m = rng.random((g, w)) < 0.7
    if empty_rows:
        m[:: max(g // 4, 1)] = False  # some fully-empty segments
    rel = rng.integers(0, 2**40, size=(g, w)).astype(np.int64)
    hi = (rel >> 30).astype(np.int32)
    lo = (rel & ((1 << 30) - 1)).astype(np.int32)
    idx = rng.permutation(g * w).reshape(g, w).astype(np.int32)
    # duplicate values inside one row to exercise value-tie selection
    v[0, : w // 2] = 7.5
    return v, hi, lo, idx, m


def _xla_stats(kind):
    """The jnp oracle regardless of pallas routing: the per-field kernel
    a launch group's program runs (models/launch.py), compiled alone."""
    import jax

    from opengemini_tpu.models import ragged

    try:
        os.environ["OGTPU_PALLAS"] = "0"
        ps.use_pallas.cache_clear()
        return jax.jit(ragged._stats_fn(kind))
    finally:
        os.environ.pop("OGTPU_PALLAS", None)
        ps.use_pallas.cache_clear()


@pytest.mark.parametrize("g,w", [(8, 16), (32, 64), (64, 256), (16, 1024)])
@needs_pallas
def test_bucket_basic_matches_xla(g, w):
    v, hi, lo, idx, m = _rand_bucket(g, w, seed=g + w)
    want = {k: np.asarray(x) for k, x in _xla_stats("basic")(v, m).items()}
    got = {k: np.asarray(x) for k, x in ps.bucket_stats_basic(v, hi, lo, idx, m).items()}
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5, err_msg=k)


@pytest.mark.parametrize("g,w", [(8, 16), (32, 64), (16, 1024)])
@needs_pallas
def test_bucket_selectors_match_xla(g, w):
    v, hi, lo, idx, m = _rand_bucket(g, w, seed=100 + g + w)
    want = {k: np.asarray(x) for k, x in _xla_stats("selectors")(v, hi, lo, idx, m).items()}
    got = {k: np.asarray(x) for k, x in ps.bucket_stats_selectors(v, hi, lo, idx, m).items()}
    assert set(got) == set(want)
    # selector indices on fully-empty rows are clipped garbage in BOTH
    # implementations (host gates on count>0) — compare valid rows only
    valid = np.asarray(m).any(axis=1)
    for k in want:
        np.testing.assert_array_equal(got[k][valid], want[k][valid], err_msg=k)


@needs_pallas
def test_bucket_all_rows_empty():
    g, w = 8, 64
    v = np.zeros((g, w), np.float32)
    z = np.zeros((g, w), np.int32)
    m = np.zeros((g, w), bool)
    out = ps.bucket_stats_basic(v, z, z, z, m)
    assert np.all(np.asarray(out["count"]) == 0)
    assert np.all(np.asarray(out["sum"]) == 0)
    assert np.all(np.asarray(out["min"]) == np.inf)
    assert np.all(np.asarray(out["max"]) == -np.inf)


@pytest.mark.parametrize("s,spw,w", [(8, 60, 136), (16, 7, 512), (3, 13, 40)])
@needs_pallas
def test_grid_window_matches_xla(s, spw, w):
    rng = np.random.default_rng(s * spw)
    v_t = (rng.standard_normal((s, spw, w)) * 5 + 50).astype(np.float32)
    m_t = rng.random((s, spw, w)) < 0.8
    m_t[:, :, 0] = False  # an empty window per series
    want = {k: np.asarray(x) for k, x in seg.grid_window_agg_t(v_t, m_t).items()}
    got = {k: np.asarray(x) for k, x in ps.grid_window_agg_t(v_t, m_t).items()}
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5, err_msg=k)


def test_routing_prefers_pallas_on_tpu_only(monkeypatch):
    ps.use_pallas.cache_clear()
    monkeypatch.setenv("OGTPU_PALLAS", "1")
    ps.use_pallas.cache_clear()
    assert ps.use_pallas()
    monkeypatch.setenv("OGTPU_PALLAS", "0")
    ps.use_pallas.cache_clear()
    assert not ps.use_pallas()
    monkeypatch.delenv("OGTPU_PALLAS")
    ps.use_pallas.cache_clear()
    # CPU-forced test env: default routing must stay on XLA
    assert ps.use_pallas() == (jax.default_backend() == "tpu")
    ps.use_pallas.cache_clear()


@needs_pallas
def test_ragged_batch_end_to_end_with_pallas(monkeypatch):
    """Force the pallas route through the real BucketedBatch pipeline and
    compare a full aggregate set against the XLA route."""
    from opengemini_tpu.models import ragged
    from opengemini_tpu.ops.aggregates import REGISTRY

    rng = np.random.default_rng(7)
    n, nseg = 5000, 37
    seg_ids = np.sort(rng.integers(0, nseg, size=n)).astype(np.int64)
    vals = rng.standard_normal(n) * 20
    mask = rng.random(n) < 0.9
    rel = np.sort(rng.integers(0, 2**40, size=n)).astype(np.int64)

    def run(force_pallas: bool):
        monkeypatch.setenv("OGTPU_PALLAS", "1" if force_pallas else "0")
        ps.use_pallas.cache_clear()
        try:
            b = ragged.BucketedBatch()
            b.add(vals, rel, seg_ids, mask, rel)
            out = {}
            for name in ("mean", "sum", "count", "min", "max", "stddev",
                         "first", "last", "spread"):
                vals_out, sel, counts = b.run(REGISTRY[name], nseg)
                out[name] = (np.asarray(vals_out), None if sel is None else np.asarray(sel),
                             np.asarray(counts))
            return out
        finally:
            monkeypatch.delenv("OGTPU_PALLAS")
            ps.use_pallas.cache_clear()

    want = run(False)
    got = run(True)
    for name in want:
        np.testing.assert_allclose(got[name][0], want[name][0], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
        np.testing.assert_array_equal(got[name][2], want[name][2], err_msg=name)
        if want[name][1] is not None:
            np.testing.assert_array_equal(got[name][1], want[name][1], err_msg=name)

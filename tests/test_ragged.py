"""Ragged->dense bucketed batching: parity with the scatter path."""

import numpy as np
import pytest

from opengemini_tpu.models import ragged, templates
from opengemini_tpu.ops import aggregates as aggmod


def make_ragged(rng, num_segments=50, max_rows=200):
    """Heavily skewed segment sizes incl. empty segments."""
    vals, rels, segs, masks, times = [], [], [], [], []
    t = 0
    for s in range(num_segments):
        n = int(rng.integers(0, max_rows)) if s % 7 else 0
        if s == 3:
            n = 1  # singleton
        for _ in range(n):
            t += int(rng.integers(1, 10_000))
            vals.append(rng.normal())
            rels.append(t)
            segs.append(s)
            masks.append(rng.random() > 0.2)
            times.append(t + 1_000_000)
    return (
        np.asarray(vals),
        np.asarray(rels, np.int64),
        np.asarray(segs, np.int64),
        np.asarray(masks, bool),
        np.asarray(times, np.int64),
    )


@pytest.mark.parametrize(
    "agg", ["sum", "count", "mean", "min", "max", "first", "last", "spread", "stddev"]
)
def test_bucketed_matches_scatter(rng, agg):
    num_segments = 50
    vals, rels, segs, masks, times = make_ragged(rng)
    spec = aggmod.get(agg)

    dense = ragged.BucketedBatch()
    scatter = templates.AggBatch()
    # feed in several chunks (exercises multi-add concat)
    for lo in range(0, len(vals), 97):
        sl = slice(lo, lo + 97)
        dense.add(vals[sl], rels[sl], segs[sl], masks[sl], times[sl])
        scatter.add(vals[sl], rels[sl], segs[sl].astype(np.int32), masks[sl], times[sl])

    d_out, d_sel, d_cnt = dense.run(spec, num_segments, spec.params)
    s_out, s_sel, s_cnt = scatter.run(spec, num_segments, spec.params)
    np.testing.assert_array_equal(d_cnt, s_cnt)
    present = d_cnt > 0
    np.testing.assert_allclose(d_out[present], s_out[present], rtol=1e-10)
    if d_sel is not None:
        # selector: both paths must pick the same row
        ht = dense.host_times()
        np.testing.assert_array_equal(d_sel[present], s_sel[present])
        assert ht.shape == scatter.host_times().shape


def test_bucket_shapes_canonical(rng):
    vals, rels, segs, masks, times = make_ragged(rng)
    b = ragged.BucketedBatch()
    b.add(vals, rels, segs, masks, times)
    buckets = b._freeze(50)
    assert all(bk.width in ragged.WIDTHS for bk in buckets)
    for bk in buckets:
        g_pad = bk.values.shape[0]
        assert (g_pad & (g_pad - 1)) == 0  # pow2-padded row counts
    # every non-empty segment appears exactly once
    seen = np.concatenate([bk.segs for bk in buckets])
    assert len(seen) == len(np.unique(seen))


def test_split_segments_combine(rng):
    """Segments wider than the max width split into sub-rows and combine
    exactly (incl. stddev k-way variance and selector picks)."""
    from opengemini_tpu.ops import aggregates as aggmod

    n_big = 5000  # > 1024 -> split into sub-rows
    vals = np.concatenate([rng.normal(size=n_big) + 100, rng.normal(size=3)])
    segs = np.concatenate([np.zeros(n_big, np.int64), np.ones(3, np.int64)])
    rels = np.arange(len(vals), dtype=np.int64) * 1000
    masks = np.ones(len(vals), bool)
    times = rels + 10**15
    b = ragged.BucketedBatch()
    b.add(vals, rels, segs, masks, times)
    for agg, ref in (
        ("sum", vals[:n_big].sum()),
        ("stddev", vals[:n_big].std(ddof=1)),
        ("min", vals[:n_big].min()),
        ("first", vals[0]),
        ("last", vals[n_big - 1]),
    ):
        out, sel, cnt = b.run(aggmod.get(agg), 2)
        assert cnt[0] == n_big
        assert out[0] == pytest.approx(ref, rel=1e-9), agg
    out, sel, cnt = b.run(aggmod.get("last"), 2)
    assert sel[0] == n_big - 1  # exact row index across sub-rows


def test_empty_batch(rng):
    b = ragged.BucketedBatch()
    out, sel, cnt = b.run(aggmod.get("sum"), 10)
    assert cnt.sum() == 0


def test_stddev_singleton_is_zero(tmp_path, rng):
    """Reference parity: stddev over one sample is 0, not null
    (engine/executor/agg_func.go NewStdDevReduce n==1 case)."""
    from opengemini_tpu.query.executor import Executor
    from opengemini_tpu.storage.engine import Engine

    e = Engine(str(tmp_path / "d"))
    e.create_database("db")
    e.write_lines("db", "m v=5 1700000000000000000")
    ex = Executor(e)
    res = ex.execute("SELECT stddev(v) FROM m", db="db", now_ns=1700001000 * 10**9)
    assert res["results"][0]["series"][0]["values"][0][1] == 0.0
    e.close()


class TestIntExactPath:
    def test_sum_exact_beyond_f64_mantissa(self, tmp_path):
        """Ints > 2^53: sum must be EXACT (float compute rounds them)."""
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        big = 2**53 + 1  # not representable in f64
        e.write_lines(
            "db",
            f"m c={big}i 1700000000000000000\nm c=2i 1700000001000000000",
        )
        ex = Executor(e)
        res = ex.execute("SELECT sum(c), count(c), mean(c) FROM m", db="db",
                         now_ns=1700001000 * 10**9)
        [(t, s, c, mean)] = res["results"][0]["series"][0]["values"]
        assert s == big + 2  # exact int64, would be off under f64
        assert isinstance(s, int) and c == 2
        assert mean == pytest.approx((big + 2) / 2)
        e.close()

    def test_exact_with_preagg_after_flush(self, tmp_path):
        """Pure pre-agg path (all chunks flushed, no memtable overlap):
        the int64 pre_sum combine itself must be exact."""
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        big = 2**53 + 1
        e.write_lines("db", f"m c={big}i 1700000000000000000")
        e.flush_all()
        e.write_lines("db", "m c=4i 1700000005000000000")
        e.flush_all()  # two non-overlapping chunks, no memtable rows
        ex = Executor(e)
        # confirm the pre-agg path actually engages (no chunk decode)
        from opengemini_tpu.storage import tsf as tsf_mod

        calls = {"n": 0}
        orig = tsf_mod.TSFReader.read_chunk

        def counting(self, *a, **kw):
            calls["n"] += 1
            return orig(self, *a, **kw)

        tsf_mod.TSFReader.read_chunk = counting
        try:
            res = ex.execute("SELECT sum(c) FROM m", db="db",
                             now_ns=1700001000 * 10**9)
        finally:
            tsf_mod.TSFReader.read_chunk = orig
        assert calls["n"] == 0  # served from pre-agg metadata
        assert res["results"][0]["series"][0]["values"][0][1] == big + 4

        # mixed pre-agg + memtable: falls back per series but stays exact
        e.write_lines("db", "m c=1i 1700000006000000000")
        res = ex.execute("SELECT sum(c) FROM m", db="db",
                         now_ns=1700001000 * 10**9)
        assert res["results"][0]["series"][0]["values"][0][1] == big + 5
        e.close()

    def test_mixed_aggs_fall_back_to_device(self, tmp_path):
        """INT field with a selector agg keeps the device path (sel works)."""
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        e.write_lines("db", "m c=5i 1700000000000000000\nm c=9i 1700000001000000000")
        ex = Executor(e)
        res = ex.execute("SELECT max(c) FROM m", db="db", now_ns=1700001000 * 10**9)
        [(t, v)] = res["results"][0]["series"][0]["values"]
        assert v == 9 and t == 1700000001000000000  # selector time intact
        e.close()

"""GridBatch (windows-on-lanes fast path): parity with BucketedBatch,
fallback rules, and executor wiring."""

import numpy as np
import pytest

from opengemini_tpu.models import grid, ragged
from opengemini_tpu.ops import aggregates as aggmod
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
EVERY = 60 * NS  # 1m windows
DT = 10 * NS  # 10s stride

GRID_AGG_LIST = sorted(grid.GRID_AGGS)


def make_regular(rng, n_series=7, groups=3, W=5, mask_p=0.15, gap_p=0.0,
                 phase=False):
    """Per-series chunks of constant-stride data (optionally with row gaps
    and per-series phase shifts). Returns list of
    (vals, rel, seg, mask, times, sid)."""
    chunks = []
    for s in range(n_series):
        gid = s % groups
        start_w = int(rng.integers(0, 2))
        n = (W - start_w) * (EVERY // DT)
        ph = int(rng.integers(0, DT // NS)) * NS if phase else 0
        rel = start_w * EVERY + ph + DT * np.arange(n, dtype=np.int64)
        if gap_p:
            keep = rng.random(n) > gap_p
            keep[0] = True
            rel = rel[keep]
            n = len(rel)
        vals = rng.normal(size=n) * 10
        mask = rng.random(n) > mask_p
        seg = (gid * W + rel // EVERY).astype(np.int64)
        times = rel + 1_700_000_000 * NS
        chunks.append((vals, rel, seg, mask, times, s))
    return chunks


def fill_batches(chunks, W):
    g = grid.GridBatch(np.float64, W, EVERY)
    b = ragged.BucketedBatch(np.float64)
    for vals, rel, seg, mask, times, sid in chunks:
        g.add(vals, rel, seg, mask, times, sids=sid)
        b.add(vals, rel, seg, mask, times)
    return g, b


def assert_parity(g, b, num_segments, aggs=GRID_AGG_LIST):
    for name in aggs:
        spec = aggmod.get(name)
        g_out, g_sel, g_cnt = g.run(spec, num_segments, spec.params)
        b_out, b_sel, b_cnt = b.run(spec, num_segments, spec.params)
        np.testing.assert_array_equal(g_cnt, b_cnt, err_msg=name)
        present = g_cnt > 0
        np.testing.assert_allclose(
            np.asarray(g_out)[present], np.asarray(b_out)[present],
            rtol=1e-9, err_msg=name)
        if b_sel is not None and g_sel is not None:
            # both paths must select the same physical row
            gt = g.host_times()
            bt = b.host_times()
            np.testing.assert_array_equal(
                gt[np.asarray(g_sel)[present]],
                bt[np.asarray(b_sel)[present]], err_msg=name)


def test_grid_engages_and_matches_bucketed(rng):
    W, groups = 5, 3
    chunks = make_regular(rng, n_series=7, groups=groups, W=W)
    g, b = fill_batches(chunks, W)
    assert_parity(g, b, groups * W)
    assert g._state is not None, "regular data must take the grid path"
    assert g._state["k"] == EVERY // DT


def test_grid_handles_gaps_and_phase(rng):
    """Row gaps and per-series phase shifts still grid (gcd stride)."""
    W, groups = 6, 2
    chunks = make_regular(rng, n_series=5, groups=groups, W=W,
                          gap_p=0.2, phase=True)
    g, b = fill_batches(chunks, W)
    assert_parity(g, b, groups * W)
    assert g._state is not None


def test_grid_single_sample_series(rng):
    """All-singleton runs degenerate to k=1 and still match."""
    W, groups = 3, 4
    chunks = []
    for s in range(30):
        rel = np.asarray([int(rng.integers(0, W)) * EVERY +
                          int(rng.integers(0, EVERY // NS)) * NS], np.int64)
        seg = (s % groups) * W + rel // EVERY
        chunks.append((rng.normal(size=1), rel, seg.astype(np.int64),
                       np.ones(1, bool), rel + 5 * NS, s))
    g, b = fill_batches(chunks, W)
    assert_parity(g, b, groups * W)
    assert g._state is not None and g._state["k"] == 1


def test_irregular_falls_back(rng):
    """Jittered (ns-irregular) timestamps refuse the grid but still give
    exact results via the internal bucketed fallback."""
    W, groups = 4, 2
    chunks = []
    for s in range(5):
        n = 40
        rel = np.cumsum(rng.integers(1, 3 * NS, size=n)).astype(np.int64)
        rel = rel[rel < W * EVERY]
        seg = (s % groups) * W + rel // EVERY
        chunks.append((rng.normal(size=len(rel)), rel, seg.astype(np.int64),
                       np.ones(len(rel), bool), rel + NS, s))
    g, b = fill_batches(chunks, W)
    assert_parity(g, b, groups * W)
    assert g._state is None and g._fallback is not None


def test_no_sids_falls_back(rng):
    W = 3
    chunks = make_regular(rng, n_series=3, groups=1, W=W)
    g = grid.GridBatch(np.float64, W, EVERY)
    b = ragged.BucketedBatch(np.float64)
    for vals, rel, seg, mask, times, _sid in chunks:
        g.add(vals, rel, seg, mask, times)  # no series identity
        b.add(vals, rel, seg, mask, times)
    assert_parity(g, b, W)
    assert g._state is None


def test_series_split_across_chunks(rng):
    """The same sid added in two chunks gets two independent runs (stride
    need not hold across the chunk joint)."""
    W = 4
    vals = np.arange(24, dtype=np.float64)
    rel = DT * np.arange(24, dtype=np.int64)
    seg = rel // EVERY
    mask = np.ones(24, bool)
    times = rel + NS
    g = grid.GridBatch(np.float64, W, EVERY)
    b = ragged.BucketedBatch(np.float64)
    # split mid-window; second chunk resumes 3 samples later (gap at joint)
    g.add(vals[:10], rel[:10], seg[:10], mask[:10], times[:10], sids=7)
    g.add(vals[13:], rel[13:], seg[13:], mask[13:], times[13:], sids=7)
    b.add(vals[:10], rel[:10], seg[:10], mask[:10], times[:10])
    b.add(vals[13:], rel[13:], seg[13:], mask[13:], times[13:])
    assert_parity(g, b, W)
    assert g._state is not None and g._state["S"] == 2


def test_executor_grid_counter(tmp_path):
    """A GROUP BY time() query over regular data demonstrably executes the
    grid path (stats counter) with correct results."""
    from opengemini_tpu.query.executor import Executor
    from opengemini_tpu.storage.engine import Engine

    base = 1_700_000_040  # 1m-aligned epoch
    eng = Engine(str(tmp_path), sync_wal=False)
    eng.create_database("g")
    lines = []
    for p in range(180):  # 3 windows of 1m @ 1s stride
        for h in range(4):
            lines.append(
                f"cpu,host=h{h} usage={50 + (h * 7 + p) % 10} {(base + p) * NS}")
    eng.write_lines("g", "\n".join(lines))
    ex = Executor(eng)
    before = STATS.snapshot().get("executor", {}).get("grid_batches", 0)
    res = ex.execute(
        "SELECT mean(usage), max(usage), count(usage) FROM cpu "
        f"WHERE time >= {base * NS} AND time < {(base + 180) * NS} "
        "GROUP BY time(1m)",
        db="g", now_ns=(base + 180) * NS)
    after = STATS.snapshot().get("executor", {}).get("grid_batches", 0)
    assert after > before, "query must execute the grid fast path"
    series = res["results"][0]["series"][0]
    assert len(series["values"]) == 3
    for row in series["values"]:
        assert row[3] == 4 * 60  # count: 4 hosts x 60 samples
        # values are (50 + k%10): mean in [50, 59], max <= 59
        assert 50 <= row[1] <= 59 and row[2] <= 59
    # exact oracle for window 0
    v = np.asarray([50 + (h * 7 + p) % 10 for p in range(60)
                    for h in range(4)], np.float64)
    np.testing.assert_allclose(series["values"][0][1], v.mean())
    assert series["values"][0][2] == v.max()
    eng.close()


def test_executor_grid_matches_irregular_oracle(tmp_path):
    """Same data, regular vs jittered: grid path result equals the
    bucketed-path result computed from identical values."""
    from opengemini_tpu.query.executor import Executor
    from opengemini_tpu.storage.engine import Engine

    base = 1_700_000_040  # 1m-aligned epoch
    rng = np.random.default_rng(7)
    offs_regular = np.arange(120) * 2  # 2s stride
    # jitter breaks the stride grid -> bucketed path; same values/windows
    offs_jitter = np.sort(rng.choice(np.arange(0, 240_000, 7), 120,
                                     replace=False))
    results = []
    for tag, offs, scale in (("r", offs_regular, NS), ("j", offs_jitter,
                                                       NS // 1000)):
        eng = Engine(str(tmp_path / tag), sync_wal=False)
        eng.create_database("d")
        lines = [
            f"m,host=a v={float(i % 13)} {base * NS + int(o) * scale}"
            for i, o in enumerate(offs)
        ]
        eng.write_lines("d", "\n".join(lines))
        ex = Executor(eng)
        res = ex.execute(
            "SELECT sum(v), min(v), stddev(v) FROM m "
            f"WHERE time >= {base * NS} AND time < {base * NS + 240 * NS} "
            "GROUP BY time(1m)",
            db="d", now_ns=base * NS + 240 * NS)
        results.append(res["results"][0]["series"][0]["values"])
        eng.close()
    # window membership differs between the two layouts, but the window
    # sums partition the same 120 values: totals must agree exactly
    assert len(results[0]) == len(results[1]) == 4
    tot_r = sum(r[1] for r in results[0] if r[1] is not None)
    tot_j = sum(r[1] for r in results[1] if r[1] is not None)
    np.testing.assert_allclose(tot_r, tot_j)

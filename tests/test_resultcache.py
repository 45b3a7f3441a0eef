"""Incremental GROUP BY time() result cache (reference
inc_agg_transform.go + lib/resultcache)."""

import gc
import time

import numpy as np
import pytest

from opengemini_tpu.query import resultcache as rc
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
BASE = 1_700_000_040  # 1m-aligned


def counter(name):
    return STATS.snapshot().get("executor", {}).get(name, 0)


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path), sync_wal=False)
    e.create_database("db")
    lines = []
    for p in range(600):  # 10 windows of 1m
        for h in range(4):
            lines.append(
                f"cpu,host=h{h} v={(h * 3 + p) % 11},iv={p % 7}i "
                f"{(BASE + p) * NS}")
    e.write_lines("db", "\n".join(lines))
    yield e, Executor(e)
    e.close()


Q = ("SELECT mean(v), max(v), count(v) FROM cpu "
     f"WHERE time >= {BASE * NS} AND time < {(BASE + 600) * NS} "
     "GROUP BY time(1m), host")


def test_repeat_query_served_from_cache(env):
    e, ex = env
    r1 = ex.execute(Q, db="db")
    hits0 = counter("inc_cache_full_hits")
    rows0 = counter("rows_scanned")
    t0 = time.perf_counter()
    r2 = ex.execute(Q, db="db")
    dt = time.perf_counter() - t0
    assert r1 == r2
    assert counter("inc_cache_full_hits") == hits0 + 1
    assert counter("rows_scanned") == rows0, "cache hit must not scan"
    assert dt < 0.25, f"cached repeat took {dt:.3f}s"  # <10ms typical; CI slack


def test_append_invalidates_only_trailing_windows(env):
    e, ex = env
    ex.execute(Q, db="db")
    # append new points into the LAST window only
    e.write_lines("db", "\n".join(
        f"cpu,host=h0 v=3 {(BASE + 599) * NS + (i + 1) * 1000}"
        for i in range(5)))
    rows0 = counter("rows_scanned")
    r = ex.execute(Q, db="db")
    scanned = counter("rows_scanned") - rows0
    # only the trailing window rescans: 60s x 4 hosts + 5 new points
    assert 0 < scanned <= 60 * 4 + 5, scanned
    # correctness: trailing window count includes appended rows
    for s in r["results"][0]["series"]:
        if s["tags"]["host"] == "h0":
            assert s["values"][-1][3] == 60 + 5
        else:
            assert s["values"][-1][3] == 60


def test_results_identical_with_and_without_cache(env):
    """Every agg family: cached second run == fresh run on a cold
    executor (incl. int-exact sums and selectors)."""
    e, ex = env
    queries = [
        Q,
        ("SELECT sum(iv), mean(iv) FROM cpu "
         f"WHERE time >= {BASE * NS} AND time < {(BASE + 600) * NS} "
         "GROUP BY time(2m)"),
        ("SELECT first(v), last(v), min(v), max(v), stddev(v), spread(v) "
         f"FROM cpu WHERE time >= {BASE * NS} AND time < {(BASE + 600) * NS} "
         "GROUP BY time(1m)"),
        ("SELECT count(v) FROM cpu "
         f"WHERE time >= {BASE * NS} AND time < {(BASE + 600) * NS} "
         "GROUP BY time(1m) fill(0)"),
        ("SELECT mean(v) FROM cpu WHERE host = 'h1' "
         f"AND time >= {BASE * NS} AND time < {(BASE + 600) * NS} "
         "GROUP BY time(3m) fill(previous)"),
    ]
    warm = [ex.execute(q, db="db") for q in queries]
    cached = [ex.execute(q, db="db") for q in queries]
    fresh_ex = Executor(e)
    fresh = [fresh_ex.execute(q, db="db") for q in queries]
    for q, w, c, f in zip(queries, warm, cached, fresh):
        assert w == c == f, q


def test_mid_range_write_invalidates_that_window(env):
    e, ex = env
    r1 = ex.execute(Q, db="db")
    # write into window 3 only
    t = (BASE + 3 * 60 + 30) * NS + 7
    e.write_lines("db", f"cpu,host=h2 v=100 {t}")
    r2 = ex.execute(Q, db="db")
    for s1, s2 in zip(r1["results"][0]["series"], r2["results"][0]["series"]):
        for w, (row1, row2) in enumerate(zip(s1["values"], s2["values"])):
            if w == 3 and s1 is not s2 and s2["tags"]["host"] == "h2":
                assert row2[3] == row1[3] + 1  # one more point
            else:
                assert row1 == row2 or w == 3


def test_unbounded_range_and_moving_window(env):
    """Dashboard-style moving range: extending the range reuses the old
    windows' cache entries (same fingerprint, absolute window keys)."""
    e, ex = env
    q1 = (f"SELECT count(v) FROM cpu WHERE time >= {BASE * NS} "
          f"AND time < {(BASE + 300) * NS} GROUP BY time(1m)")
    q2 = (f"SELECT count(v) FROM cpu WHERE time >= {BASE * NS} "
          f"AND time < {(BASE + 600) * NS} GROUP BY time(1m)")
    ex.execute(q1, db="db")
    rows0 = counter("rows_scanned")
    r2 = ex.execute(q2, db="db")
    scanned = counter("rows_scanned") - rows0
    assert scanned <= 300 * 4, scanned  # only the new half scans
    vals = r2["results"][0]["series"][0]["values"]
    assert len(vals) == 10 and all(v[1] == 240 for v in vals)


def test_concurrent_writes_never_wrong(env):
    """Interleaved writes and queries: every response equals a cold
    executor's answer at that instant."""
    e, ex = env
    for i in range(5):
        e.write_lines(
            "db", f"cpu,host=h1 v={i} {(BASE + 120 * i + 30) * NS + i}")
        got = ex.execute(Q, db="db")
        want = Executor(e).execute(Q, db="db")
        assert got == want, f"iteration {i}"


def test_unaligned_range_scans_only_edges(env):
    """now()-relative shape: unaligned tmin/tmax make both edge windows
    partial (always recomputed), but the middle stays cached — the scan
    covers disjoint edge runs, not the hull."""
    e, ex = env
    q = (f"SELECT count(v) FROM cpu WHERE time >= {(BASE + 30) * NS} "
         f"AND time < {(BASE + 570) * NS} GROUP BY time(1m)")
    r1 = ex.execute(q, db="db")
    rows0 = counter("rows_scanned")
    r2 = ex.execute(q, db="db")
    scanned = counter("rows_scanned") - rows0
    assert r1 == r2
    # edge windows only: 30s + 30s of 4-host data (not the 540s range)
    assert 0 < scanned <= 2 * 30 * 4, scanned


# -- the columnar cell store (PR 40) -------------------------------------------

BIG = 2 ** 53 + 1  # not a float64


def _series_of(res):
    return res["results"][0]["series"]


def test_mixed_int_float_statement_is_exact_from_cache(tmp_path):
    """An integer aggregate beside a float one keeps its own dtype in the
    cache: sums over 2^53 answer the same cached as computed."""
    e = Engine(str(tmp_path), sync_wal=False)
    try:
        e.create_database("db")
        e.write_lines("db", "\n".join(
            f"big,host=h{h} v={p % 5}.5,iv={BIG}i {(BASE + p) * NS}"
            for p in range(120) for h in range(2)))
        q = ("SELECT sum(iv), mean(v) FROM big "
             f"WHERE time >= {BASE * NS} AND time < {(BASE + 120) * NS} "
             "GROUP BY time(1m), host")
        ex = Executor(e)
        first = ex.execute(q, db="db")
        hits0 = counter("inc_cache_full_hits")
        cached = ex.execute(q, db="db")
        assert counter("inc_cache_full_hits") == hits0 + 1
        assert _series_of(first)[0]["values"][0][1] == 60 * BIG
        assert first == cached == Executor(e).execute(q, db="db")
    finally:
        e.close()


@pytest.fixture
def sparse(tmp_path):
    """h0 in every window, h1 only in windows 0-2 and 5, h2 only in 0-4."""
    e = Engine(str(tmp_path), sync_wal=False)
    e.create_database("db")
    have = {0: range(10), 1: (0, 1, 2, 5), 2: range(5)}
    e.write_lines("db", "\n".join(
        f"cpu,host=h{h} v={h + w + p % 3},iv={p % 7}i "
        f"{(BASE + 60 * w + p) * NS}"
        for h, ws in have.items() for w in ws for p in range(0, 60, 10)))
    yield e
    e.close()


@pytest.mark.parametrize("fill", ["none", "0", "previous"])
def test_sparse_groups_equal_fresh_under_fill(sparse, fill):
    """Windows in which a host has no rows store no cell for it; the
    merged answer (cached windows + the recomputed tail) renders as a
    fresh executor's does, series order included."""
    e = sparse
    q = ("SELECT mean(v), sum(iv), count(v) FROM cpu "
         f"WHERE time >= {BASE * NS} AND time < {(BASE + 600) * NS} "
         f"GROUP BY time(1m), host fill({fill})")
    ex = Executor(e)
    first = ex.execute(q, db="db")
    assert first == ex.execute(q, db="db") == Executor(e).execute(q, db="db")
    held = next(iter(ex._inc_cache._store.values()))
    n_with_data = {ws: (len(w[1]) if w[2] is None else len(w[2]))
                   for ws, w in held.items()}
    assert sorted(n_with_data.values()) == [1, 1, 1, 1, 2, 2, 2, 3, 3, 3]
    # the tail is touched and recomputed; h1 and h2 come from the cache
    e.write_lines("db", f"cpu,host=h0 v=50 {(BASE + 599) * NS + 5}")
    reused0 = counter("inc_cache_windows_reused")
    got = ex.execute(q, db="db")
    assert counter("inc_cache_windows_reused") == reused0 + 9
    want = Executor(e).execute(q, db="db")
    assert got == want
    assert [s["tags"]["host"] for s in _series_of(got)] == ["h0", "h1", "h2"]


def test_moving_panel_shares_one_key_set(env):
    """A panel re-asked over a moving range after appends: its windows
    are stored by three executions and hold ONE key tuple between them."""
    e, ex = env

    def panel(lo, hi):
        return ("SELECT mean(v), max(v) FROM cpu "
                f"WHERE time >= {(BASE + lo) * NS} "
                f"AND time < {(BASE + hi) * NS} GROUP BY time(1m), host")

    stored = shared = 0
    for step, (lo, hi) in enumerate([(0, 300), (60, 360), (120, 420)]):
        e.write_lines(
            "db", f"cpu,host=h3 v={step} {(BASE + hi - 1) * NS + 9}")
        q = panel(lo, hi)
        stored -= counter("inc_cache_windows_stored")
        shared -= counter("inc_cache_keysets_shared")
        got = ex.execute(q, db="db")
        stored += counter("inc_cache_windows_stored")
        shared += counter("inc_cache_keysets_shared")
        assert got == Executor(e).execute(q, db="db")
    # 5 windows, then the one new trailing window of each later range
    assert stored == shared == 5 + 1 + 1
    (held,) = ex._inc_cache._store.values()
    assert len(held) == 7
    assert len({id(w[1]) for w in held.values()}) == 1
    assert sorted(next(iter(held.values()))[1]) == [
        ("h0",), ("h1",), ("h2",), ("h3",)]


# the plan alone, over arrays of the test's making: what the executor
# hands merge() and what merge() leaves in the store

class _Shard:
    path, tmin, tmax = "sh", 0, 1 << 62

    def __init__(self):
        self.data_version = 1
        self.touched = set()    # window starts written since the store

    def changed_since(self, v, ws, we):
        return ws in self.touched


def _plan(cache, shard, W, fp="fp"):
    return rc.CachePlan(cache, fp, [shard], 0, 60, W, 0, 0, 60 * W)


def _results(aggs, G, W, dtypes, rng, stale, empty=()):
    """id(call) -> the executor's tuple: values and counts only in the
    stale windows (the rest were not scanned), none in `empty` cells."""
    res = {}
    live = np.zeros((G, W), bool)
    live[:, sorted(stale)] = True
    for g, w in empty:
        live[g, w] = False
    for (call, spec, _p, fname), dt in zip(aggs, dtypes):
        vals = rng.integers(1, 1 << 40, (G, W)).astype(dt)
        if np.dtype(dt).kind == "i":
            vals += BIG
        out = np.where(live, vals, 0).astype(dt).reshape(-1)
        cnt = np.where(live, 6, 0).astype(np.int64).reshape(-1)
        res[id(call)] = (out, None, cnt, spec, fname, None)
    return res


def _aggs(n):
    return [(object(), None, (), f"f{i}") for i in range(n)]


def test_stored_form_is_columns_not_cells():
    """400 groups x 12 windows x 5 aggregates: a stored window holds one
    array an aggregate in that aggregate's dtype, one shared key tuple,
    and storing the statement leaves under 1,000 tracked objects."""
    G, W = 400, 12
    dtypes = [np.int64, np.float64, np.float32, np.float64, np.int64]
    aggs = _aggs(len(dtypes))
    cache, shard = rc.IncrementalCache(), _Shard()
    keys = [(f"host_{g}",) for g in range(G)]
    rng = np.random.default_rng(40)
    plan = _plan(cache, shard, W)
    res = _results(aggs, G, W, dtypes, rng, plan.stale,
                   empty=[(7, 3), (9, 3)])
    want = {k: (v[0].copy(), v[2].copy()) for k, v in res.items()}
    stored0 = counter("inc_cache_windows_stored")
    shared0 = counter("inc_cache_keysets_shared")
    gc.collect()
    gc.disable()
    try:
        before = len(gc.get_objects())
        plan.merge(res, aggs, list(keys))
        grown = len(gc.get_objects()) - before
    finally:
        gc.enable()
    del plan
    assert grown < 1000, grown
    assert counter("inc_cache_windows_stored") - stored0 == W
    assert counter("inc_cache_keysets_shared") - shared0 == W
    held = cache.lookup("fp")
    assert len(held) == W
    assert len({id(w[1]) for w in held.values()}) == 1
    for ws, (_sig, kt, idx, vals, cnts) in held.items():
        assert type(kt) is tuple and list(kt) == keys
        if ws == 3 * 60:
            assert idx.dtype == np.int64 and len(idx) == G - 2
            assert 7 not in idx and 9 not in idx
        else:
            assert idx is None
        n = G if idx is None else len(idx)
        assert [v.dtype for v in vals] == [np.dtype(d) for d in dtypes]
        assert all(isinstance(v, np.ndarray) and v.shape == (n,)
                   for v in vals)
        assert cnts.dtype == np.int64 and cnts.shape == (len(dtypes), n)
        assert all(v.base is None for v in vals), "a view pins the answer"
    # and the full hit reads back what was computed, bit for bit
    again = _plan(cache, shard, W)
    assert not again.stale
    res2 = {id(c): (np.zeros(G * W, dt), None, np.zeros(G * W, np.int64),
                    None, f, None)
            for (c, _s, _p, f), dt in zip(aggs, dtypes)}
    again.merge(res2, aggs, list(keys))
    for k, (out, cnt) in want.items():
        assert res2[k][0].dtype == out.dtype
        assert np.array_equal(res2[k][0], out)
        assert np.array_equal(res2[k][2], cnt)


def test_cache_only_group_extends_the_keys_where_it_has_data():
    """A group the statement no longer computes but a reused window holds
    is appended to group_keys (after the computed ones) with its cells;
    one that has data only in windows being recomputed is not."""
    W, dtypes = 4, [np.int64, np.float64]
    aggs = _aggs(2)
    cache, shard = rc.IncrementalCache(), _Shard()
    rng = np.random.default_rng(41)
    keys = [("a",), ("gone",), ("b",), ("tail_only",)]
    plan = _plan(cache, shard, W)
    # "tail_only" has data in window 3 alone, "gone" not in window 3
    res = _results(aggs, 4, W, dtypes, rng, plan.stale,
                   empty=[(3, 0), (3, 1), (3, 2), (1, 3)])
    plan.merge(res, aggs, list(keys))
    first = {k: (v[0].reshape(4, W).copy(), v[2].reshape(4, W).copy())
             for k, v in res.items()}
    shard.touched = {3 * 60}
    plan = _plan(cache, shard, W)
    assert plan.stale == {3}
    now = [("b",), ("c",), ("a",)]    # another order, two groups gone
    res = _results(aggs, 3, W, dtypes, rng, plan.stale)
    tail = {k: v[0].reshape(3, W)[:, 3].copy() for k, v in res.items()}
    out_keys = plan.merge(res, aggs, list(now))
    assert out_keys == now + [("gone",)]
    src = [2, None, 0, 1]   # row of the first answer behind each new row
    for k, (out0, cnt0) in first.items():
        out = res[k][0].reshape(4, W)
        cnt = res[k][2].reshape(4, W)
        assert out.dtype == out0.dtype
        for g, g0 in enumerate(src):
            if g0 is None:      # "c": new, nothing cached
                assert not cnt[g, :3].any() and not out[g, :3].any()
            else:
                assert np.array_equal(out[g, :3], out0[g0, :3])
                assert np.array_equal(cnt[g, :3], cnt0[g0, :3])
        assert np.array_equal(out[:3, 3], tail[k])
        assert cnt[3, 3] == 0 and out[3, 3] == 0
    # the window stored now holds the merged key order, its own tuple
    held = cache.lookup("fp")
    assert list(held[3 * 60][1]) == out_keys
    assert held[3 * 60][2].tolist() == [0, 1, 2]
    assert list(held[0][1]) == keys


def test_eviction_still_counts_windows_and_fingerprints():
    W, dtypes = 5, [np.float64]
    aggs = _aggs(1)
    cache = rc.IncrementalCache(max_queries=2, max_windows=3)
    rng = np.random.default_rng(42)
    ev0 = counter("inc_cache_evictions")
    for n, fp in enumerate(["p1", "p2", "p3"]):
        plan = _plan(cache, _Shard(), W, fp)
        plan.merge(_results(aggs, 2, W, dtypes, rng, plan.stale), aggs,
                   [("x",), ("y",)])
        # 5 windows into room for 3; the third fingerprint pushes p1 out
        assert counter("inc_cache_evictions") - ev0 == 2 * (n + 1) + (n == 2)
    assert cache.lookup("p1") == {}
    assert sorted(cache.lookup("p3")) == [120, 180, 240]


def test_plan_keeps_the_windows_it_validated():
    """lookup() hands out a copy and entries are never written again: a
    plan built before a concurrent update() (another thread storing or
    evicting) still merges the windows it validated."""
    W, dtypes = 4, [np.int64, np.float64]
    aggs = _aggs(2)
    cache, shard = rc.IncrementalCache(), _Shard()
    rng = np.random.default_rng(43)
    keys = [("a",), ("b",), ("c",)]
    plan = _plan(cache, shard, W)
    res = _results(aggs, 3, W, dtypes, rng, plan.stale)
    plan.merge(res, aggs, list(keys))
    want = {k: (v[0].copy(), v[2].copy()) for k, v in res.items()}
    reader = _plan(cache, shard, W)          # validated all four
    assert not reader.stale
    # meanwhile: the live entry is overwritten with other numbers under
    # another key order, then dropped
    other = _plan(rc.IncrementalCache(), shard, W)
    other.cache = cache
    other.merge(_results(aggs, 3, W, dtypes, rng, other.stale), aggs,
                [("c",), ("a",), ("b",)])
    assert [w[1][0] for w in cache.lookup("fp").values()] == [("c",)] * W
    cache.clear()
    res_r = {id(c): (np.zeros(3 * W, dt), None, np.zeros(3 * W, np.int64),
                     None, f, None)
             for (c, _s, _p, f), dt in zip(aggs, dtypes)}
    assert reader.merge(res_r, aggs, list(keys)) == keys
    for k, (out, cnt) in want.items():
        assert np.array_equal(res_r[k][0], out)
        assert np.array_equal(res_r[k][2], cnt)

"""Incremental GROUP BY time() result cache (reference
inc_agg_transform.go + lib/resultcache)."""

import time

import numpy as np
import pytest

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

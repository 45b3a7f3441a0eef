"""Window-aligned sliced scan: the at-spec pipeline must
produce byte-identical results to the monolithic scan — every per-window
aggregate, fill behavior, group-by-tag layout, partial edge windows, and
irregular (bucketed-layout) data.

Reference analogue: the record-plan batch reader streams chunks
(engine/record_plan.go:75) instead of materializing the whole scan.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from opengemini_tpu.query import executor as exmod
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine

NS = 1_000_000_000
BASE = 1_700_000_000


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path / "data"), sync_wal=False)
    e.create_database("db")
    yield e, Executor(e)
    e.close()


def _write_regular(e, hosts=6, points=600, step_s=10):
    lines = []
    for h in range(hosts):
        for p in range(points):
            lines.append(
                f"cpu,host=h{h} v={(h * 7 + p) % 23}.5,u={p % 11}i "
                f"{(BASE + p * step_s) * NS}")
    e.write_lines("db", "\n".join(lines))
    e.flush_all()


def _write_irregular(e, hosts=5, points=500):
    rng = np.random.default_rng(7)
    lines = []
    t = BASE
    for p in range(points):
        t += int(rng.integers(1, 9))  # uneven spacing -> bucketed layout
        for h in range(hosts):
            if rng.random() < 0.8:
                lines.append(f"mem,host=h{h} v={float(rng.random()) * 50} {t * NS}")
    e.write_lines("db", "\n".join(lines))
    e.flush_all()
    return t


def _run_both(ex, q, monkeypatch):
    """Execute monolithic, then force slicing, and return both results."""
    mono = ex.execute(q, db="db")
    monkeypatch.setattr(exmod, "SLICE_THRESHOLD_ROWS", 1)
    monkeypatch.setattr(exmod, "SLICE_TARGET_ROWS", 200)
    ex._inc_cache.clear()
    sliced = ex.execute(q, db="db")
    monkeypatch.setattr(exmod, "SLICE_THRESHOLD_ROWS", 24_000_000)
    monkeypatch.setattr(exmod, "SLICE_TARGET_ROWS", 8_000_000)
    return mono, sliced


def _assert_equiv(a, b, path="$"):
    """Structural equality, with floats bounded instead of exact.

    The sliced path reduces each slice's bucket matrix separately and the
    shapes differ from the monolithic scan's, so XLA's f32 `sum` may pick
    a different accumulation order; `mean` on the irregular/bucketed
    layout then differs in the last f32 ulp (~6e-8 relative observed).
    Everything structural — keys, ordering, counts, ints, strings, nulls
    — must still match exactly; floats get a tolerance with >10x margin
    over the observed divergence but far below any real aggregation bug.
    """
    assert type(a) is type(b), f"{path}: {type(a)} != {type(b)}"
    if isinstance(a, dict):
        assert a.keys() == b.keys(), f"{path}: keys {a.keys()} != {b.keys()}"
        for k in a:
            _assert_equiv(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{path}: len {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_equiv(x, y, f"{path}[{i}]")
    elif isinstance(a, float):
        ok = (a == b or (math.isnan(a) and math.isnan(b))
              or math.isclose(a, b, rel_tol=1e-6, abs_tol=1e-12))
        assert ok, f"{path}: {a!r} !~ {b!r}"
    else:
        assert a == b, f"{path}: {a!r} != {b!r}"


QUERIES = [
    "SELECT mean(v), max(v), count(v) FROM cpu WHERE time >= {lo} AND "
    "time < {hi} GROUP BY time(1m)",
    "SELECT min(v), sum(v), spread(v), stddev(v) FROM cpu WHERE "
    "time >= {lo} AND time < {hi} GROUP BY time(2m), host",
    "SELECT first(v), last(v) FROM cpu WHERE time >= {lo} AND time < {hi} "
    "GROUP BY time(90s) fill(previous)",
    "SELECT count(u), sum(u) FROM cpu WHERE time >= {lo} AND time < {hi} "
    "GROUP BY time(1m) fill(0)",
    # partial edge windows: range not aligned to the interval
    "SELECT mean(v), count(v) FROM cpu WHERE time >= {lo_off} AND "
    "time < {hi_off} GROUP BY time(1m)",
    # field filter forces row masks through the sliced path
    "SELECT mean(v), count(v) FROM cpu WHERE time >= {lo} AND "
    "time < {hi} AND v > 10 GROUP BY time(1m), host",
]


class TestSlicedEqualsMonolithic:
    @pytest.mark.parametrize("qt", QUERIES)
    def test_regular(self, env, monkeypatch, qt):
        e, ex = env
        _write_regular(e)
        lo, hi = BASE * NS, (BASE + 6000) * NS
        q = qt.format(lo=lo, hi=hi, lo_off=lo + 37 * NS, hi_off=hi - 41 * NS)
        mono, sliced = _run_both(ex, q, monkeypatch)
        assert "error" not in mono["results"][0], mono
        assert mono == sliced, q

    def test_irregular_bucketed(self, env, monkeypatch):
        e, ex = env
        t_end = _write_irregular(e)
        q = (f"SELECT mean(v), count(v), max(v) FROM mem WHERE "
             f"time >= {BASE * NS} AND time < {(t_end + 1) * NS} "
             "GROUP BY time(30s), host")
        mono, sliced = _run_both(ex, q, monkeypatch)
        # exact equality does not hold here: see _assert_equiv — the
        # bucketed layout's per-slice f32 sums accumulate in a different
        # order than the monolithic scan's, so mean() drifts by one ulp
        _assert_equiv(mono, sliced)

    def test_memtable_rows_included(self, env, monkeypatch):
        e, ex = env
        _write_regular(e, hosts=2, points=100)
        # extra unflushed rows live only in the memtable
        e.write_lines("db", "\n".join(
            f"cpu,host=h0 v=99.5 {(BASE + 995 + i) * NS}" for i in range(5)))
        q = (f"SELECT mean(v), count(v) FROM cpu WHERE time >= {BASE * NS} "
             f"AND time < {(BASE + 1100) * NS} GROUP BY time(1m)")
        mono, sliced = _run_both(ex, q, monkeypatch)
        assert mono == sliced

    def test_slice_plan_covers_range_once(self):
        plan = exmod._plan_scan_slices(
            [], "cpu", [], BASE * NS, 60 * NS, 100, BASE * NS,
            (BASE + 6000) * NS)
        assert plan is None  # no shards -> zero rows -> no slicing

    def test_sliced_layout_reported(self, env, monkeypatch):
        e, ex = env
        _write_regular(e)
        monkeypatch.setattr(exmod, "SLICE_THRESHOLD_ROWS", 1)
        monkeypatch.setattr(exmod, "SLICE_TARGET_ROWS", 200)
        r = ex.execute(
            f"EXPLAIN ANALYZE SELECT mean(v) FROM cpu WHERE "
            f"time >= {BASE * NS} AND time < {(BASE + 6000) * NS} "
            "GROUP BY time(1m)", db="db")
        import json

        txt = json.dumps(r)
        assert "sliced[" in txt, txt[:500]

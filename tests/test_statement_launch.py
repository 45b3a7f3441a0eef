"""One launch and one fetch a statement, not one a field (models/launch.py).

The batches of a statement that froze to the same geometry ride in one
compiled program; what decides the groups is what the code can observe
(program, kernel, shapes, dtypes, placement).  The reference throughout
is the path a lone batch takes: each batch `run()` alone, a group of one
through the same code, which is what the parent did field by field.
Every answer has to be the same to the bit."""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from opengemini_tpu.models import grid, launch, ragged
from opengemini_tpu.ops import aggregates as aggmod
from opengemini_tpu.query import executor as executor_mod
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.server.http import HttpService
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import devobs
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
EVERY = 60 * NS
DT = 10 * NS
BASE = 1_700_000_040  # 1m-aligned epoch
W, GROUPS, SERIES = 6, 4, 8
SEGMENTS = GROUPS * W
AGGS = ("max", "min", "mean", "sum", "count", "spread", "stddev", "first",
        "last")


def _stages() -> dict:
    return STATS.counters("query_stages")


def _moved(before: dict, name: str) -> int:
    return _stages().get(name, 0) - before.get(name, 0)


def _field_batches(layout: str, fields: int, seed: int = 3) -> list:
    """`fields` batches over the same rows (one series-run a series, 10 s
    stride, 1 m windows), each with its own values and mask: a panel's
    five fields.  `grid_fallback` jitters the times, so the grid refuses
    and every batch delegates to its bucketed fallback."""
    rng = np.random.default_rng(seed)
    rel = DT * np.arange(W * (EVERY // DT), dtype=np.int64)
    if layout == "grid_fallback":
        rel = rel + rng.integers(0, 7, len(rel)) * 1_000_003
    out = []
    for _ in range(fields):
        b = (ragged.BucketedBatch(np.float64) if layout == "bucketed"
             else grid.GridBatch(np.float64, W, EVERY))
        for sid in range(SERIES):
            seg = (sid % GROUPS) * W + rel // EVERY
            b.add(rng.normal(size=len(rel)) * 10, rel, seg,
                  rng.random(len(rel)) > 0.15, rel + BASE * NS, sids=sid)
        out.append(b)
    return out


def _answers(batch, want_sel: bool) -> dict:
    return {name: batch.run(aggmod.get(name), SEGMENTS, want_sel=want_sel)
            for name in AGGS}


def _same(got: dict, want: dict) -> None:
    for name in want:
        for g, w in zip(got[name], want[name]):
            assert (g is None) == (w is None), name
            if w is not None:
                assert g.dtype == w.dtype, name
                np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("want_sel", [False, True])
@pytest.mark.parametrize("fields", [1, 5, 10])
@pytest.mark.parametrize("layout", ["grid", "bucketed", "grid_fallback"])
def test_a_group_answers_as_its_fields_do_alone(layout, fields, want_sel):
    alone = _field_batches(layout, fields)
    q0 = _stages()
    want = [_answers(b, want_sel) for b in alone]
    per_field = _moved(q0, "device_launch_count")
    assert per_field == _moved(q0, "device_fetch_count") > 0

    grouped = _field_batches(layout, fields)
    q0 = _stages()
    launch.run([it for b in grouped
                for it in b.launch_items(SEGMENTS, AGGS, want_sel=want_sel)])
    launches = _moved(q0, "device_launch_count")
    # one launch a kernel and geometry, however many fields ride in it
    assert launches * fields == per_field
    assert _moved(q0, "device_fetch_count") == launches
    assert all(b.layout_name() == alone[0].layout_name() for b in grouped)
    assert alone[0].layout_name() == {
        "grid": "grid", "bucketed": "bucketed",
        "grid_fallback": "grid->bucketed"}[layout]
    # ... and run() then only combines: nothing more is launched
    for b, w in zip(grouped, want):
        _same(_answers(b, want_sel), w)
    assert _moved(q0, "device_launch_count") == launches


def test_items_are_grouped_by_geometry_not_by_count():
    """Two grids of one shape and one of another: two launches; a second
    dispatch of the same items launches nothing."""
    a, b = _field_batches("grid", 2)
    (c,) = _field_batches("grid", 1, seed=5)
    c.add(np.ones(6), DT * np.arange(6, dtype=np.int64),
          np.zeros(6, np.int64), np.ones(6, bool),
          DT * np.arange(6, dtype=np.int64), sids=99)  # a ninth series-run
    items = [it for x in (a, b, c)
             for it in x.launch_items(SEGMENTS, ["max"], want_sel=False)]
    assert a._state["shape"] == b._state["shape"] != c._state["shape"]
    q0 = _stages()
    launch.dispatch(items)
    assert _moved(q0, "device_launch_count") == 2
    assert _moved(q0, "device_fetch_count") == 0  # still on the device
    assert items[0].flight is items[1].flight is not items[2].flight
    assert all(it.args == () for it in items)  # the matrices are let go
    launch.dispatch(items)
    assert _moved(q0, "device_launch_count") == 2
    for it in items:
        it.flight.land()
    assert _moved(q0, "device_fetch_count") == 2
    for x in (a, b, c):
        assert not x.launch_items(SEGMENTS, ["max"], want_sel=False)


# -- through the executor -----------------------------------------------------


@pytest.fixture
def engine(tmp_path, monkeypatch):
    # a verbatim repeat must reach the device, not the result cache
    monkeypatch.setenv("OGT_RESULT_CACHE", "0")
    eng = Engine(str(tmp_path / "data"), sync_wal=False)
    eng.create_database("db")
    lines = []
    for h in range(24):
        for k in range(36):
            fields = [f"f{j}={(h * 7 + k * (j + 3)) % 23 / 3 + j}"
                      for j in range(10)]
            fields.append(f"i1={(h + k) % 9}i")
            if h % 2 == 0:
                fields.append(f"fh={(h + k) % 5 / 7}")
            lines.append(f"cpu,host=h{h} " + ",".join(fields)
                         + f" {(BASE + k * 10) * NS}")
    eng.write_lines("db", "\n".join(lines))
    eng.flush_all()
    yield eng
    eng.close()


_RANGE = (f"WHERE time >= {BASE * NS} AND time < {(BASE + 360) * NS}")


def _execute(eng, q: str):
    q0 = _stages()
    res = Executor(eng).execute(q, db="db", now_ns=(BASE + 360) * NS)
    assert "error" not in res["results"][0], res
    return (json.dumps(res, sort_keys=True),
            _moved(q0, "device_launch_count"),
            _moved(q0, "device_fetch_count"))


def _field_by_field(monkeypatch, eng, q: str):
    """The same statement with no statement-level group: every batch
    launches alone when its first aggregate runs, as the parent did."""
    with monkeypatch.context() as m:
        m.setattr(executor_mod.launch, "run", lambda items: None)
        return _execute(eng, q)


@pytest.mark.parametrize("fields", [1, 5, 10])
def test_a_statement_is_one_launch(engine, monkeypatch, fields):
    q = ("SELECT " + ", ".join(f"max(f{j})" for j in range(fields))
         + f" FROM cpu {_RANGE} GROUP BY time(1m), host")
    body, launches, fetches = _execute(engine, q)
    assert launches == fetches == 1
    want, alone, _ = _field_by_field(monkeypatch, engine, q)
    assert alone == fields
    assert body == want


def test_a_mixed_statement_splits_by_its_geometry(engine, monkeypatch):
    """f0, f1: grids of one shape, one launch.  fh is absent from half the
    series: other rows, another shape, a launch of its own.  i1 takes the
    int-exact host path: none.  percentile() keeps the lexsort AggBatch
    and its own two programs (the aggregate, the counts)."""
    q = ("SELECT max(f0), max(f1), sum(i1), percentile(f2, 90), max(fh) "
         f"FROM cpu {_RANGE} GROUP BY time(1m), host")
    b0 = STATS.counters("executor").get("grid_batches", 0)
    body, launches, fetches = _execute(engine, q)
    assert STATS.counters("executor")["grid_batches"] - b0 == 3
    assert launches == 2 + 2
    want, alone, _ = _field_by_field(monkeypatch, engine, q)
    assert alone == 3 + 2
    assert body == want
    assert fetches >= launches - 1  # AggBatch fetches out and counts apart


def test_a_bucketed_statement_is_one_launch_a_bucket(engine, monkeypatch):
    """Without GROUP BY time() there is no grid: five bucketed batches of
    the same buckets, and selectors (`want_sel`) beside `basic`."""
    q = ("SELECT " + ", ".join(f"max(f{j})" for j in range(5))
         + f" FROM cpu {_RANGE} GROUP BY host")
    body, launches, fetches = _execute(engine, q)
    want, alone, _ = _field_by_field(monkeypatch, engine, q)
    assert launches == fetches and launches * 5 == alone
    assert launches == 2  # one bucket width: basic + selectors
    assert body == want


def test_stddev_rides_in_the_group_too(engine, monkeypatch):
    q = ("SELECT " + ", ".join(f"stddev(f{j})" for j in range(5))
         + f" FROM cpu {_RANGE} GROUP BY time(1m), host")
    body, launches, fetches = _execute(engine, q)
    assert launches == fetches == 2  # grid basic + ssd
    want, alone, _ = _field_by_field(monkeypatch, engine, q)
    assert alone == 10
    assert body == want


def test_a_second_identical_statement_compiles_nothing(engine):
    q = ("SELECT " + ", ".join(f"max(f{j})" for j in range(5))
         + f" FROM cpu {_RANGE} GROUP BY time(1m), host")
    _execute(engine, q)
    devobs.mark_warm()
    try:
        assert _execute(engine, q)[1] == 1
        # other fields, the same geometry: the same program
        other = q.replace("max(f0)", "max(f5)").replace("max(f1)", "min(f6)")
        assert _execute(engine, other)[1] == 1
        assert devobs.compiles_since_warm() == 0
        inv = devobs.inventory()["grid_basic"]["geometries"]
        assert any(g["hits"] >= 3 for g in inv), inv
    finally:
        devobs.clear_warm()


def test_a_served_query_counts_one_launch_a_group(engine):
    svc = HttpService(engine, "127.0.0.1", 0)
    svc.start()
    try:
        for fields, groups in ((5, 1), (1, 1)):
            q = ("SELECT " + ", ".join(f"max(f{j})" for j in range(fields))
                 + f" FROM cpu {_RANGE} GROUP BY time(1m), host")
            url = (f"http://127.0.0.1:{svc.port}/query?"
                   + urllib.parse.urlencode({"db": "db", "q": q}))
            q0 = _stages()
            e0 = STATS.counters("executor")
            with urllib.request.urlopen(url, timeout=60) as r:
                doc = json.loads(r.read())
            assert len(doc["results"][0]["series"]) == 24
            assert _moved(q0, "device_launch_count") == groups
            assert _moved(q0, "device_fetch_count") == groups
            # `layout_build` opens once a batch: the first's covers the
            # statement's one plan and its own fill, the others' their
            # fills of the plan they took from it (models/layoutplan.py)
            assert _moved(q0, "layout_build_count") == fields
            e1 = STATS.counters("executor")
            assert e1["grid_batches"] - e0.get("grid_batches", 0) == fields
            assert e1.get("layout_plans_shared", 0) \
                - e0.get("layout_plans_shared", 0) == fields - 1
    finally:
        svc.stop()

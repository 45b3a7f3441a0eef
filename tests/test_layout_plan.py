"""A statement's layout is planned once, not once a field
(models/layoutplan.py).

The batches of a statement that were handed the same row arrays share
their layout's plan — the grid's stride analysis, refusal and indexes,
the buckets' run analysis, sub-rows and scatter index, the selector
kernels' time and row-index matrices — and each scatters only its own
values and mask.  The reference throughout is what a batch built alone
gives (a group of one through the same code, which is what the parent
did field by field): every statistic has to be the same to the bit."""

import json

import numpy as np
import pytest

from opengemini_tpu.models import grid, launch, layoutplan, ragged, templates
from opengemini_tpu.ops import aggregates as aggmod
from opengemini_tpu.parallel import distributed as dist
from opengemini_tpu.parallel import runtime as prt
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
EVERY = 60 * NS
DT = 10 * NS
BASE = 1_700_000_040  # 1m-aligned epoch
W, GROUPS, SERIES = 6, 4, 8
SEGMENTS = GROUPS * W
FIELDS = 5
AGGS = ("max", "min", "mean", "sum", "count", "spread", "stddev", "first",
        "last")
LAYOUTS = ("grid", "grid_fallback", "bucketed", "bucketed_split")


def _executor() -> dict:
    return STATS.counters("executor")


def _moved(before: dict, *names: str) -> int:
    now = _executor()
    return sum(now.get(n, 0) - before.get(n, 0) for n in names)


def _rows(layout: str, seed: int = 3):
    """(rel, seg, times, sids) of one scan: SERIES series on a 10 s
    stride under 1 m windows, as ONE add — what a bulk shard read hands
    every field.  `grid_fallback` jitters the times so the grid refuses
    into buckets; `bucketed_split` has no windows and 1,500 rows a
    series, so every segment splits into sub-rows of 1,024."""
    rng = np.random.default_rng(seed)
    per = 1500 if layout == "bucketed_split" else W * (EVERY // DT)
    one = DT * np.arange(per, dtype=np.int64)
    if layout == "grid_fallback":
        one = one + rng.integers(0, 7, per) * 1_000_003
    rel = np.tile(one, SERIES)
    sids = np.repeat(np.arange(SERIES, dtype=np.int64), per)
    if layout == "bucketed_split":
        seg = (sids % GROUPS).astype(np.int32)
    else:
        seg = ((sids % GROUPS) * W + rel // EVERY).astype(np.int32)
    return rel, seg, rel + BASE * NS, sids


def _segments(layout: str) -> int:
    return GROUPS if layout == "bucketed_split" else SEGMENTS


def _batch(layout: str, plans=None):
    if layout.startswith("bucketed"):
        return ragged.BucketedBatch(np.float64, plans)
    return grid.GridBatch(np.float64, W, EVERY, plans)


def _payloads(n: int, fields: int = FIELDS, seed: int = 11) -> list:
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=n) * 10, rng.random(n) > 0.15)
            for _ in range(fields)]


def _statement(layout: str, shared: bool, rows=None, payloads=None,
               plans=None) -> list:
    """FIELDS batches over one row set.  `shared`: the same arrays and one
    Plans, as the executor hands them; else each batch alone — arrays and
    Plans of its own."""
    rows = rows or _rows(layout)
    payloads = payloads or _payloads(len(rows[0]))
    plans = (plans or layoutplan.Plans()) if shared else None
    out = []
    for vals, mask in payloads:
        rel, seg, times, sids = rows if shared else \
            tuple(a.copy() for a in rows)
        b = _batch(layout, plans)
        b.add(vals, rel, seg, mask, times, sids=sids)
        out.append(b)
    return out


def _answers(batch, segments: int, want_sel: bool) -> dict:
    return {name: batch.run(aggmod.get(name), segments, want_sel=want_sel)
            for name in AGGS}


def _same(got: dict, want: dict) -> None:
    for name in want:
        for g, w in zip(got[name], want[name]):
            assert (g is None) == (w is None), name
            if w is not None:
                assert g.dtype == w.dtype, name
                np.testing.assert_array_equal(g, w, err_msg=name)


# -- (a) one plan a row set ---------------------------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
def test_five_fields_fed_the_same_arrays_build_one_plan(layout):
    e0 = _executor()
    for b in _statement(layout, shared=True):
        b.launch_items(_segments(layout), ["mean"], want_sel=False)
    assert _moved(e0, "layout_plans_shared") == FIELDS - 1
    dense = 0 if layout.startswith("bucketed") else FIELDS
    assert _moved(e0, "grid_batches", "grid_fallbacks") == dense
    assert _moved(e0, "grid_fallbacks") == \
        (FIELDS if layout == "grid_fallback" else 0)

    e0 = _executor()
    for b in _statement(layout, shared=False):
        b.launch_items(_segments(layout), ["mean"], want_sel=False)
    assert _moved(e0, "layout_plans_shared") == 0
    assert _moved(e0, "grid_batches", "grid_fallbacks") == dense


@pytest.mark.parametrize("layout", ["grid", "grid_fallback"])
def test_the_plan_itself_is_one_object(layout):
    batches = _statement(layout, shared=True)
    for b in batches:
        b.launch_items(SEGMENTS, AGGS, want_sel=True)
    if layout == "grid":
        firsts = [b._state for b in batches]
        for key in ("flat", "rel", "row_order", "gid_starts"):
            assert all(st[key] is firsts[0][key] for st in firsts), key
        vts = [st["arrays"][0] for st in firsts]
        assert all(v is not vts[0] for v in vts[1:])  # the fill is its own
    else:
        assert all(b._state is None for b in batches)
        plans = [[bk.plan for bk in b._fallback._frozen] for b in batches]
        assert all(p is q for ps in plans[1:] for p, q in zip(ps, plans[0]))


# -- (b) the same numbers -----------------------------------------------------


@pytest.mark.parametrize("want_sel", [False, True])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_every_statistic_is_what_a_batch_built_alone_gives(layout, want_sel):
    """basic, ssd (stddev) and the selectors — with split sub-rows in
    `bucketed_split`, whose host combine reads the plan's `rel`."""
    n_seg = _segments(layout)
    want = [_answers(b, n_seg, want_sel)
            for b in _statement(layout, shared=False)]
    together = _statement(layout, shared=True)
    launch.run([it for b in together
                for it in b.launch_items(n_seg, AGGS, want_sel=want_sel)])
    for b, w in zip(together, want):
        _same(_answers(b, n_seg, want_sel), w)
    if layout == "bucketed_split":
        assert all((bk.n_sub > 1).all() for bk in together[0]._frozen)
    # ... and one by one, each launching alone, as the cluster's partials do
    for b, w in zip(_statement(layout, shared=True), want):
        _same(_answers(b, n_seg, want_sel), w)


# -- (c) who does not share ---------------------------------------------------


def _half(rows):
    """The rows of the even series only: a field absent from half."""
    keep = rows[3] % 2 == 0
    return tuple(a[keep] for a in rows)


@pytest.mark.parametrize("layout", ["grid", "grid_fallback", "bucketed"])
def test_a_field_absent_from_half_the_series_plans_for_itself(layout):
    rows = _rows(layout)
    half = _half(rows)
    payloads = _payloads(len(rows[0]), 3)
    (hv, hm), = _payloads(len(half[0]), 1, seed=13)
    plans = layoutplan.Plans()
    full = _statement(layout, True, rows, payloads, plans)
    absent = _batch(layout, plans)
    absent.add(hv, half[0], half[1], hm, half[2], sids=half[3])
    e0 = _executor()
    launch.run([it for b in (*full, absent)
                for it in b.launch_items(SEGMENTS, AGGS, want_sel=True)])
    assert _moved(e0, "layout_plans_shared") == 2  # of the three full ones

    alone = _batch(layout)
    alone.add(hv, *(a.copy() for a in half[:2]), hm, half[2], sids=half[3])
    _same(_answers(absent, SEGMENTS, True), _answers(alone, SEGMENTS, True))
    for b, w in zip(full, _statement(layout, False, rows, payloads)):
        _same(_answers(b, SEGMENTS, True), _answers(w, SEGMENTS, True))


@pytest.mark.parametrize("layout", ["grid", "grid_fallback", "bucketed"])
def test_two_scan_ranges_are_two_adds_of_one_plan(layout):
    """A result cache's stale hull scans two ranges: every field is added
    twice, each time the same arrays — one plan over both adds; a field
    that saw only the first range has another."""
    rows = _rows(layout)
    cut = rows[0] < 3 * EVERY
    first, second = (tuple(a[m] for a in rows) for m in (cut, ~cut))
    payloads = _payloads(len(rows[0]), 4)

    def fed(i, plans):
        b = _batch(layout, plans)
        vals, mask = payloads[i]
        for part, m in ((first, cut), (second, ~cut))[:1 if i == 3 else 2]:
            rel, seg, times, sids = part if plans is not None else \
                tuple(a.copy() for a in part)
            b.add(vals[m], rel, seg, mask[m], times, sids=sids)
        return b

    plans = layoutplan.Plans()
    batches = [fed(i, plans) for i in range(4)]
    e0 = _executor()
    launch.run([it for b in batches
                for it in b.launch_items(SEGMENTS, AGGS, want_sel=True)])
    assert _moved(e0, "layout_plans_shared") == 2
    for i, b in enumerate(batches):
        _same(_answers(b, SEGMENTS, True),
              _answers(fed(i, None), SEGMENTS, True))


def test_another_geometry_over_the_same_arrays_is_another_plan():
    rel, seg, times, sids = _rows("grid")
    plans = layoutplan.Plans()
    (vals, mask), = _payloads(len(rel), 1)
    a = grid.GridBatch(np.float64, W, EVERY, plans)
    b = grid.GridBatch(np.float64, W // 2, 2 * EVERY, plans)
    seg_b = ((sids % GROUPS) * (W // 2) + rel // (2 * EVERY)).astype(np.int32)
    a.add(vals, rel, seg, mask, times, sids=sids)
    b.add(vals, rel, seg_b, mask, times, sids=sids)
    e0 = _executor()
    a.launch_items(SEGMENTS, ["mean"], want_sel=False)
    b.launch_items(SEGMENTS // 2, ["mean"], want_sel=False)
    assert _moved(e0, "layout_plans_shared") == 0
    assert a._state["shape"] != b._state["shape"]


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
                      for j in range(5)]
            fields.append(f"i1={(h + k) % 9}i")
            if h % 2 == 0:
                fields.append(f"fh={(h + k) % 5 / 7}")
            lines.append(f"cpu,host=h{h} " + ",".join(fields)
                         + f" {(BASE + k * 10) * NS}")
    eng.write_lines("db", "\n".join(lines))
    eng.flush_all()
    yield eng
    eng.close()


_RANGE = f"WHERE time >= {BASE * NS} AND time < {(BASE + 360) * NS}"
_STATEMENTS = {
    # (statement, batches that take a sibling's plan)
    "five_fields": (
        "SELECT " + ", ".join(f"mean(f{j})" for j in range(5))
        + f" FROM cpu {_RANGE} GROUP BY time(1m), host", 4),
    # fh is absent from half the series; i1 sums on the int-exact host
    # path; percentile() keeps the lexsort AggBatch: none of them plans
    # with f0 and f1
    "mixed_kinds": (
        "SELECT max(f0), max(f1), sum(i1), percentile(f2, 90), max(fh) "
        f"FROM cpu {_RANGE} GROUP BY time(1m), host", 1),
    "selectors_no_windows": (
        "SELECT " + ", ".join(f"first(f{j})" for j in range(5))
        + f" FROM cpu {_RANGE} GROUP BY host", 4),
    "one_field": (
        f"SELECT mean(f3) FROM cpu {_RANGE} GROUP BY time(1m), host", 0),
}


@pytest.mark.parametrize("name", sorted(_STATEMENTS))
def test_a_statement_answers_as_its_fields_planned_alone(
        engine, monkeypatch, name):
    q, shared = _STATEMENTS[name]

    def execute():
        e0 = _executor()
        res = Executor(engine).execute(q, db="db", now_ns=(BASE + 360) * NS)
        assert "error" not in res["results"][0], res
        return json.dumps(res, sort_keys=True), \
            _moved(e0, "layout_plans_shared")

    body, moved = execute()
    assert moved == shared
    with monkeypatch.context() as m:
        # every batch a Plans of its own: the parent's freeze, field by field
        m.setattr(layoutplan.Plans, "get",
                  lambda self, geometry, parts, build: (build(), False))
        want, moved = execute()
    assert moved == 0
    assert body == want


# -- (d) what a fill builds ---------------------------------------------------


def _buckets(batches) -> list:
    return [bk for b in batches
            for bk in getattr(b, "_fallback", b)._frozen]


def _selector_mats_built(batches) -> list:
    return [bk.plan._selector_mats is not None for bk in _buckets(batches)]


@pytest.mark.parametrize("layout", ["grid_fallback", "bucketed"])
def test_only_a_selector_builds_the_time_and_index_matrices(layout):
    """`mean` under GROUP BY time() launches `basic`, which reads the
    values and the mask: the three matrices beside them are not built.
    `first`/`last` build them — once for all the fields."""
    batches = _statement(layout, shared=True)
    launch.run([it for b in batches
                for it in b.launch_items(SEGMENTS, ["mean"], want_sel=False)])
    for b in batches:
        b.run(aggmod.get("mean"), SEGMENTS, want_sel=False)
    assert not any(_selector_mats_built(batches))

    launch.run([it for b in batches
                for it in b.launch_items(SEGMENTS, ["first"],
                                         want_sel=False)])
    assert all(_selector_mats_built(batches))
    mats = [bk.plan.selector_mats() for bk in _buckets(batches)]
    assert all(m is mats[0] for m in mats)
    assert all(m.dtype == np.int32 for m in mats[0])


# -- (e) prefetch lets go of its own references only --------------------------


@pytest.mark.parametrize("first_to_go", [0, 2, 4])
def test_prefetch_on_one_batch_leaves_its_siblings_able_to_run(first_to_go):
    want = [_answers(b, SEGMENTS, False)
            for b in _statement("grid", shared=False)]
    batches = _statement("grid", shared=True)
    gone = batches[first_to_go]
    gone.prefetch(SEGMENTS, AGGS)
    assert gone._state["flat"] is None and gone._rel is None
    for b, w in zip(batches, want):
        _same(_answers(b, SEGMENTS, False), w)
    # the selectors' index grid is built from the plan's `flat`
    for i, b in enumerate(batches):
        if i != first_to_go:
            assert b.run(aggmod.get("first"), SEGMENTS)[1] is not None


# -- (f) under a mesh ---------------------------------------------------------


@pytest.fixture
def mesh4():
    prt.set_mesh(dist.make_mesh(4, ("shard",)))
    yield prt.get_mesh()
    prt.set_mesh(None)


@pytest.mark.parametrize("layout", ["grid", "grid_fallback"])
def test_a_configured_mesh_still_row_shards(mesh4, layout):
    before = STATS.counters("device").get("mesh_dense_batches", 0)
    batches = _statement(layout, shared=True)
    items = [it for b in batches
             for it in b.launch_items(SEGMENTS, AGGS, want_sel=True)]
    for it in items:
        assert all(len(a.sharding.device_set) == 4 for a in it.args), \
            it.program
    if layout == "grid":
        assert batches[0]._state["shape"][0] % 4 == 0
    launch.run(items)
    assert STATS.counters("device")["mesh_dense_batches"] > before
    got = [_answers(b, SEGMENTS, True) for b in batches]
    prt.set_mesh(None)
    for g, b in zip(got, _statement(layout, shared=False)):
        _same(g, _answers(b, SEGMENTS, True))


def test_an_aggbatch_and_an_int_exact_batch_take_no_plan():
    """They are handed the same arrays and keep their own copies: no
    plan, no count, and the dense batches beside them share as ever."""
    rel, seg, times, sids = _rows("grid")
    (vals, mask), = _payloads(len(rel), 1)
    plans = layoutplan.Plans()
    dense = [grid.GridBatch(np.float64, W, EVERY, plans) for _ in range(2)]
    others = [ragged.IntExactBatch(), templates.AggBatch(np.float64)]
    for b in (*dense, *others):
        b.add(vals.astype(np.int64) if b is others[0] else vals,
              rel, seg, mask, times, sids=sids)
    e0 = _executor()
    for b in (*dense, *others):
        b.run(aggmod.get("sum"), SEGMENTS)
    assert _moved(e0, "layout_plans_shared") == 1
    assert len(plans._memo) == 1

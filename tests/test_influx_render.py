"""An aggregate SELECT's answer from its arrays (query/render.py).

Three things are held equal, over everything the statement's shape can
vary: the tree `Frame.series()` builds in bulk and the tree the per-row
walker (`qhelpers._render_agg_rows`, what `_render_agg` was before the
frame) builds a cell at a time; the bytes `rows_json` writes and what
`json.dumps(format_result(tree, epoch), allow_nan=False)` makes of that
tree; and the bytes of `native/render.cpp` and of the bulk Python
writer.  Numbers are compared through their JSON text, so an int that
became a float, or a `true` that became `1`, fails.
"""

import copy
import itertools
import json
import struct

import numpy as np
import pytest

from opengemini_tpu.query import render as qr
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.query.qhelpers import (_apply_fill, _calls_in,
                                           _output_columns, _render_agg_rows,
                                           _resolve_call)
from opengemini_tpu.record import FieldType
from opengemini_tpu.server import http as srv
from opengemini_tpu.sql.parser import parse
from opengemini_tpu.utils.stats import GLOBAL as STATS

SCHEMA = {"f": FieldType.FLOAT, "g": FieldType.FLOAT, "i": FieldType.INT,
          "j": FieldType.INT, "b": FieldType.BOOL}
ALIGNED = 1_700_000_040 * 10**9
EPOCHS = [None, "ns", "u", "ms", "s"]
FILLS = ["null", "none", "0", "-2.5", "previous", "linear"]
MIXED = "mean(f), max(i), first(b), count(f), stddev(g)"

needs_native = pytest.mark.skipif(
    qr._native.load() is None, reason="native/libogtrender.so did not load")


def _results(stmt, G, W, seed, *, int_scale=1 << 40, int_as=None,
             float_as=np.float32):
    """agg_results as the reduce leaves them: one (out, sel, counts, spec,
    field, times) a call, with empty windows, a window empty in every
    column, a group empty altogether and non-finite floats."""
    rng = np.random.default_rng(seed)
    n = G * W
    dead = rng.random(n) < 0.15         # no point in any column
    if G > 1:
        dead.reshape(G, W)[1] = True    # a group with no point at all
    out = {}
    for f in stmt.fields:
        for call in _calls_in(f.expr):
            spec, _params, fname = _resolve_call(call)
            counts = np.where(dead, 0, rng.integers(0, 3, n)).astype(np.int64)
            ftype = SCHEMA[fname]
            if spec.int_output:
                vals = counts.astype(int_as or np.int64)
            elif ftype == FieldType.INT and spec.name in qr._INT_EXACT_AGGS:
                vals = rng.integers(-int_scale, int_scale, n, dtype=np.int64)
                vals[rng.random(n) < 0.2] = 0       # divisors of zero
                if int_as is not None:
                    vals = vals.astype(int_as)
            elif ftype == FieldType.BOOL:
                vals = rng.integers(0, 2, n).astype(np.float32)
            else:
                vals = (rng.standard_normal(n) * 10.0 ** rng.integers(-6, 9, n)
                        ).astype(float_as)
                vals[rng.random(n) < 0.1] = 0.0
                if spec.name == "stddev":
                    vals = np.where(counts == 1, 0, np.abs(vals))
                vals[rng.random(n) < 0.05] = np.nan
                vals[rng.random(n) < 0.03] = np.inf
            out[id(call)] = (vals, None, counts, spec, fname, None)
    return out


def _keys(G, seed, tags=1):
    rng = np.random.default_rng(seed + 1)
    return [tuple(f"host_{k}" for k in rng.permutation(1000)[:tags])
            for _ in range(G)]


def _case(sql, G=7, W=9, seed=0, tags=("hostname",), keys=None, **kw):
    stmt = parse(sql)[0]
    results = _results(stmt, G, W, seed, **kw)
    keys = keys if keys is not None else (
        _keys(G, seed, len(tags)) if tags else [()])
    columns, col_exprs = _output_columns(stmt)
    args = (stmt, "cpu", columns, col_exprs, list(tags), keys, ALIGNED, W,
            results, SCHEMA)
    return args


def _text(tree) -> str:
    return json.dumps(tree)     # NaN and Infinity compare as their text


def _expected_bytes(tree, epoch) -> bytes:
    """What the front end wrote of a tree before this module."""
    doc = srv.format_result(
        {"results": [{"series": copy.deepcopy(tree)}]}, epoch)
    return srv._dumps(doc["results"][0]["series"])[1:-1].encode("utf-8")


def _div(epoch):
    return srv._EPOCH_DIV.get(epoch, 1) if epoch else None


def _check(args, epochs=EPOCHS):
    frame = qr.build_frame(*args)
    tree = frame.series()
    assert _text(tree) == _text(_render_agg_rows(*args))
    for epoch in epochs:
        assert qr.rows_json(frame, _div(epoch)) == _expected_bytes(tree, epoch)
    return frame, tree


def _sql(cols=MIXED, fill=None, time="time(1m), ", tail=""):
    fill = f" fill({fill})" if fill else ""
    return (f"SELECT {cols} FROM cpu WHERE time >= 0 "
            f"GROUP BY {time}hostname{fill}{tail}")


# -- the tree and the bytes, over the statement's shape ------------------------


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("tail", ["", " ORDER BY time DESC"])
def test_fill_and_order(fill, tail):
    frame, tree = _check(_case(_sql(fill=fill, tail=tail), seed=3))
    assert tree     # something was rendered
    if fill == "none":
        assert frame.rowmask is not None and not frame.rowmask.all()


@pytest.mark.parametrize("epoch", EPOCHS + ["m", "h", "bogus", ""])
def test_epochs(epoch):
    _check(_case(_sql(), seed=5), epochs=[epoch])


@pytest.mark.parametrize("fill", ["null", "none", "previous"])
@pytest.mark.parametrize("tail", [
    " LIMIT 3", " OFFSET 2", " LIMIT 2 OFFSET 4", " LIMIT 50", " OFFSET 50",
    " ORDER BY time DESC LIMIT 3 OFFSET 1", " ORDER BY time DESC LIMIT 1",
])
def test_limit_and_offset(fill, tail):
    frame, tree = _check(_case(_sql(fill=fill, tail=tail), seed=7),
                         epochs=["ns", None])
    if tail == " OFFSET 50":
        assert tree == [] and qr.rows_json(frame, 1) == b""


@pytest.mark.parametrize("cols", [
    "mean(f)", "max(i)", "first(b)", "last(b), min(b), max(b)", "count(f)",
    "count(i), count(distinct(j))", "stddev(f)", "sum(i), spread(j)",
    "sum(f), min(f), max(f), first(f), last(f)", "median(i), mean(i)",
])
@pytest.mark.parametrize("fill", ["null", "linear", "7"])
def test_column_types(cols, fill):
    _check(_case(_sql(cols, fill), seed=11), epochs=["ns"])


@pytest.mark.parametrize("int_as,float_as,scale", [
    (np.float32, np.float32, 1 << 20), (np.float64, np.float64, 1 << 50),
    (np.int32, np.float64, 1 << 20),
])
def test_the_reduce_output_in_another_dtype(int_as, float_as, scale):
    # counts and int-field results that arrive as floats are int()'d
    # (rounded first for a field's values), a narrower float is widened
    # as float() widens it
    _check(_case(_sql(), seed=13, int_scale=scale, int_as=int_as,
                 float_as=float_as), epochs=["ns"])


def test_ints_past_two_to_the_53rd_stay_exact():
    args = _case(_sql("max(i), sum(j)"), seed=17, int_scale=1 << 62)
    frame, tree = _check(args, epochs=["ns"])
    big = [v for s in tree for row in s["values"] for v in row[1:]
           if v is not None and abs(v) > 1 << 53]
    assert big and all(isinstance(v, int) for v in big)
    assert any(v % 2 for v in big)      # a float64 could not hold these


@pytest.mark.parametrize("cols", [
    "mean(f) / mean(g)", "mean(f) % mean(g)", "max(i) / max(j)",
    "max(i) % max(j)", "max(i) + max(j), max(i) - max(j), max(i) * 3",
    "-mean(f), -max(i), -first(b)", "first(b) + last(b), first(b) * 2.5",
    "count(f) / count(g), count(f) % count(g)", "mean(f) * 2, 3 / mean(f)",
    "max(i) / 2, max(i) / 2.0, max(i) % 7, 7 % max(i)",
    "(mean(f) + mean(g)) / (count(f) - count(g))", "mean(f) / 0, max(i) % 0",
    "mean(f) * 1e308 * 10", "5 + 2, mean(f)", "count(f) + 1",
])
@pytest.mark.parametrize("fill", ["null", "none", "0", "previous", "linear"])
def test_arithmetic_between_calls(cols, fill):
    # ints small enough that int64 and float64 do what Python's ints do
    _check(_case(_sql(cols, fill), seed=19, int_scale=1 << 20),
           epochs=["ns"])


@pytest.mark.parametrize("fill", FILLS)
def test_empty_windows_an_empty_group_and_non_finite_results(fill):
    args = _case(_sql("mean(f), count(f), max(i)", fill), G=5, W=8, seed=23)
    results, keys = args[8], args[5]
    mean = next(e for e in results.values() if e[3].name == "mean")
    seen = np.flatnonzero(mean[2] > 0)
    mean[0][seen[:3]] = np.nan, np.inf, -np.inf
    frame, tree = _check(args, epochs=["ns"])
    # the group with no point is no series, whatever the fill
    assert keys[1] not in [tuple(s["tags"].values()) for s in tree]
    assert b"NaN" not in qr.rows_json(frame, 1)
    assert b"Infinity" not in qr.rows_json(frame, 1)


def test_a_non_finite_product_is_null_in_the_bytes_and_inf_in_the_tree():
    frame, tree = _check(_case(_sql("mean(f) * 1e308 * 1e10"), seed=29,
                               float_as=np.float64), epochs=["ns"])
    flat = [row[1] for s in tree for row in s["values"]]
    assert float("inf") in flat or float("-inf") in flat


@pytest.mark.parametrize("time", ["", "time(1m), "])
@pytest.mark.parametrize("cols", ["mean(f), count(f)", "max(i), min(i)",
                                  "mean(f) / count(f)"])
def test_without_group_by_time(time, cols):
    W = 6 if time else 1
    frame, tree = _check(_case(_sql(cols, "5", time), W=W, seed=31))
    if not time:
        # one row a series at most, fill ignored: a series has a point
        assert all(len(s["values"]) == 1 for s in tree)


def test_no_tags_is_one_series_without_a_tags_member():
    sql = "SELECT mean(f), max(i) FROM cpu WHERE time >= 0 GROUP BY time(1m)"
    frame, tree = _check(_case(sql, G=1, seed=37, tags=()))
    assert len(tree) == 1 and "tags" not in tree[0]


def test_names_tags_and_aliases_are_escaped_by_the_library():
    sql = ('SELECT mean(f) AS "a \\"quoted\\" é", max(i) AS "b\\\\c" '
           "FROM cpu WHERE time >= 0 GROUP BY time(1m), hostname, dc")
    keys = [('h"1', "düs"), ("h\n2", "\\x"), ("", " "), ("z", "</")]
    args = _case(sql, G=4, seed=41, tags=("hostname", 'd"c'), keys=keys)
    args = args[:1] + ('m"st é',) + args[2:]
    frame, tree = _check(args)
    assert json.loads(b"[" + qr.rows_json(frame, 1) + b"]") == \
        json.loads(_text(tree))


def test_duplicate_column_names_are_numbered():
    _frame, tree = _check(_case(_sql("mean(f), mean(g), mean(f) AS mean"),
                                seed=43), epochs=["ns"])
    assert tree[0]["columns"] == ["time", "mean", "mean_1", "mean_2"]


def test_series_come_sorted_by_key_and_reverse_with_the_statement():
    args = _case(_sql(tail=" ORDER BY time DESC"), G=12, seed=47)
    frame = qr.build_frame(*args)
    keys = [tuple(s["tags"].values()) for s in frame.series()]
    assert keys == sorted(keys)
    back = frame.reversed()
    assert _text(back.series()) == _text(frame.series()[::-1])
    for epoch in ("ns", None):
        assert qr.rows_json(back, _div(epoch)) == \
            _expected_bytes(back.series(), epoch)


def test_no_groups_is_no_series():
    args = _case(_sql(), G=0, seed=53, keys=[])
    frame, tree = _check(args)
    assert tree == [] and qr.rows_json(frame, None) == b""


# -- the array fill against the row fill ------------------------------------------


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("seed", range(4))
def test_array_fill_is_the_row_fill(fill, seed):
    """`_apply_fill` (still what query/hostpath.py fills its row lists
    with) on each series' rows, against the frame's fill along the
    window axis."""
    args = _case(_sql("mean(f), max(i), first(b), count(g)", fill), G=6,
                 W=14, seed=100 + seed, int_scale=1 << 30)
    stmt, _m, columns, col_exprs, _t, keys, _a, W, results, schema = args
    tree = {tuple(s["tags"].values()): s["values"]
            for s in qr.build_frame(*args).series()}
    from opengemini_tpu.query.qhelpers import _eval_output_expr

    for g, key in enumerate(keys):
        rows = []
        for w in range(W):
            got = [_eval_output_expr(e, results, g * W + w, schema)
                   for e in col_exprs]
            rows.append((ALIGNED + w * 60 * 10**9, [v for v, _p in got],
                         any(p for _v, p in got)))
        if not any(p for _t, _v, p in rows):
            assert key not in tree
            continue
        want = [[t] + v for t, v, _p in _apply_fill(rows, stmt, columns, (3,))]
        assert _text(tree[key]) == _text(want)


# -- shapes the arrays do not express -----------------------------------------------


def _render_agg(args, batches=None):
    stmt, mst, _c, _e, tags, keys, aligned, W, results, schema = args
    return Executor._render_agg(None, stmt, mst, tags, keys, aligned, W,
                                results, batches, schema)


def _moved(before):
    now = STATS.counters("query")
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in ("render_cells", "render_bulk_cells",
                      "render_native_cells")}


def test_the_fleet_shape_is_all_bulk_and_counted_once():
    args = _case(_sql("mean(f), mean(g), mean(f), mean(g), mean(f)"),
                 G=40, W=12, seed=59)
    before = STATS.counters("query")
    frame = _render_agg(args)
    assert isinstance(frame, qr.Frame)
    assert _moved(before) == {"render_cells": 2400, "render_bulk_cells": 2400,
                              "render_native_cells": 0}
    body = qr.rows_json(frame, 1)
    rows = sum(len(s["values"]) for s in frame.series())
    assert _moved(before)["render_native_cells"] == (
        rows * 5 if qr._native.load() is not None else 0)
    assert body == _expected_bytes(frame.series(), "ns")


@pytest.mark.parametrize("cols,scale", [
    ("max(i) * max(j)", 1 << 62),           # a product past int64
    ("max(i) + max(j)", 1 << 62),
    ("max(i) / max(j)", 1 << 60),           # int / int past 2^53
    ("-max(i)", None),                      # -int64.min
    ("max(i) * 99999999999999999999", 4),   # a literal past int64
])
def test_integers_python_holds_and_int64_does_not_take_the_walker(cols, scale):
    args = _case(_sql(cols), seed=61, int_scale=scale or 4)
    if scale is None:
        for entry in args[8].values():
            entry[0][entry[2] > 0] = np.iinfo(np.int64).min
    with pytest.raises(qr.NotColumnar):
        qr.build_frame(*args)
    before = STATS.counters("query")
    tree = _render_agg(args)
    assert isinstance(tree, list) and tree
    assert _text(tree) == _text(_render_agg_rows(*args))
    moved = _moved(before)
    assert moved["render_cells"] == 7 * 9 and moved["render_bulk_cells"] == 0


def test_linear_fill_of_huge_integers_takes_the_walker():
    args = _case(_sql("max(i)", "linear"), seed=67, int_scale=1 << 60)
    with pytest.raises(qr.NotColumnar):
        qr.build_frame(*args)
    assert _text(_render_agg(args)) == _text(_render_agg_rows(*args))


def test_a_single_selector_without_group_by_time_keeps_the_points_own_time():
    sql = "SELECT max(f) FROM cpu WHERE time >= 0 GROUP BY hostname"
    args = _case(sql, G=5, W=1, seed=71)
    (entry,) = args[8].values()
    times = ALIGNED + np.arange(5, dtype=np.int64) * 1234567
    args[8][next(iter(args[8]))] = entry[:5] + (times,)
    before = STATS.counters("query")
    tree = _render_agg(args)
    assert isinstance(tree, list)
    got = {s["values"][0][0] for s in tree}
    assert got and got <= set(times.tolist())
    moved = _moved(before)
    assert moved["render_cells"] == 5 and moved["render_bulk_cells"] == 0


# -- the native writer against the Python writer ----------------------------------------


def _frame_of(cols, times, rowmask=None, tags=("hostname",)):
    n = cols[0].kind.shape[0]
    return qr.Frame("cpu", ["time"] + [f"c{k}" for k in range(len(cols))],
                    list(tags), [(f"h{g}",) for g in range(n)],
                    np.asarray(times, dtype=np.int64), cols, rowmask)


def _writers(frame, div=1):
    ts = [str(t // div) for t in frame.times.tolist()]
    head = '{"name": "cpu", "columns": %s, "values": [' % json.dumps(frame.columns)
    tails = ['], "tags": {"hostname": "%s"}}' % k for (k,) in frame.keys]
    return (qr._rows_native(frame, ts, head, tails),
            qr._rows_py(frame, ts, head, tails))


@needs_native
@pytest.mark.parametrize("seed", range(6))
def test_native_bytes_over_random_bit_patterns(seed):
    rng = np.random.default_rng(seed)
    G, W = 50, 40
    bits = rng.integers(0, 1 << 64, (G, W), dtype=np.uint64)
    floats = bits.view(np.float64)
    ints = rng.integers(-(1 << 63), (1 << 63) - 1, (G, W), dtype=np.int64,
                        endpoint=True)
    kinds = rng.integers(0, 4, (G, W)).astype(np.uint8)
    cols = [
        qr.Column(np.where(kinds == qr.NULL, qr.NULL, qr.FLOAT).astype(np.uint8),
                  None, floats),
        qr.Column(np.where(kinds == qr.FLOAT, qr.NULL, kinds).astype(np.uint8),
                  ints, None),
        qr.Column(kinds, ints, floats),
    ]
    mask = rng.random((G, W)) < 0.7 if seed % 2 else None
    native, py = _writers(_frame_of(cols, ALIGNED + np.arange(W)), 1000)
    assert native == py
    tree = _frame_of(cols, ALIGNED + np.arange(W), mask).series()
    assert qr.rows_json(_frame_of(cols, ALIGNED + np.arange(W), mask), 1000) \
        == _expected_bytes(tree, "u")


@needs_native
def test_native_floats_at_the_edges_of_repr():
    edge = [0.0, -0.0, 1.0, -1.0, 1e16, 9999999999999998.0, 1e-4, 9.9e-5,
            1e-5, 5e-324, 2.2250738585072014e-308, 1.7976931348623157e308,
            -1.2345678901234567e-308, 123456789012345680.0, 0.1, 1 / 3,
            1e22, 1e23, float("nan"), float("inf"), float("-inf"),
            struct.unpack("<d", struct.pack("<Q", 0x7FEFFFFFFFFFFFFF))[0]]
    f = np.asarray([edge], dtype=np.float64)
    col = qr.Column(np.full(f.shape, qr.FLOAT, np.uint8), None, f)
    native, py = _writers(_frame_of([col], range(len(edge))))
    assert native == py
    assert b"nan" not in native and b"inf" not in native


@needs_native
def test_the_buffer_bound_holds_for_the_longest_row():
    """Every cell at its widest — a 24-byte float, a 20-byte int64,
    `false` — with the longest time text, in every column."""
    G, W, C = 3, 5, 9
    f = np.full((G, W), -1.2345678901234567e-308)
    i = np.full((G, W), np.iinfo(np.int64).min)
    assert len(repr(float(f[0, 0]))) == 24 and len(str(int(i[0, 0]))) == 20
    cols = []
    for c in range(C):
        kind = (qr.FLOAT, qr.INT, qr.BOOL)[c % 3]
        cols.append(qr.Column(np.full((G, W), kind, np.uint8),
                              np.zeros((G, W), np.int64) if kind == qr.BOOL
                              else i, f))
    frame = _frame_of(cols, np.full(W, np.iinfo(np.int64).max))
    native, py = _writers(frame)
    assert native == py
    for epoch in (None, "ns"):
        assert qr.rows_json(frame, _div(epoch)) == \
            _expected_bytes(frame.series(), epoch)


def test_without_the_library_the_python_writer_answers(monkeypatch):
    args = _case(_sql(), seed=73)
    frame = qr.build_frame(*args)
    want = {e: qr.rows_json(frame, _div(e)) for e in EPOCHS}
    monkeypatch.setattr(qr._native, "load", lambda: None)
    before = STATS.counters("query")
    for e in EPOCHS:
        assert qr.rows_json(frame, _div(e)) == want[e] == \
            _expected_bytes(frame.series(), e)
    assert _moved(before)["render_native_cells"] == 0


# -- through the executor: who gets which ---------------------------------------------


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from opengemini_tpu.storage.engine import Engine

    e = Engine(str(tmp_path_factory.mktemp("render")))
    e.create_database("db")
    lines = []
    for h, t in itertools.product(range(6), range(40)):
        if h == 2 and 10 <= t < 25:
            continue    # windows with no point
        lines.append(
            f"cpu,hostname=host_{h},dc=d{h % 2} "
            f"usage={(h * 7 + t) % 13 / 3.0},n={h * 100 + t}i,"
            f"up={'true' if (h + t) % 3 else 'false'} "
            f"{(1_700_000_000 + t * 15) * 10**9}")
    e.write_lines("db", "\n".join(lines))
    yield e
    e.close()


RANGE = "time >= 1700000000s AND time < 1700000600s"
STATEMENTS = [
    f"SELECT mean(usage), max(n), count(up) FROM cpu WHERE {RANGE} "
    "GROUP BY time(1m), hostname",
    f"SELECT mean(usage) FROM cpu WHERE {RANGE} GROUP BY time(1m), hostname "
    "fill(none) ORDER BY time DESC LIMIT 4",
    f"SELECT max(n) / count(n), first(up) FROM cpu WHERE {RANGE} "
    "GROUP BY time(2m), dc fill(previous)",
    f"SELECT mean(usage), sum(n) FROM cpu WHERE {RANGE} GROUP BY hostname",
    f"SELECT max(usage) FROM cpu WHERE {RANGE} GROUP BY hostname",
    f"SELECT mean(usage) FROM cpu WHERE {RANGE} GROUP BY time(1m) fill(linear)",
    f"SELECT mean(usage) FROM /cp./ WHERE {RANGE} GROUP BY time(5m), dc",
]


def _tree_of(res):
    return {k: ([s for f in v for s in f.series()] if k == "frames" else v)
            for k, v in res.items()}


@pytest.mark.parametrize("sql", STATEMENTS)
def test_frames_go_only_to_the_caller_that_asks(engine, sql):
    ex = Executor(engine)
    tree = ex.execute(sql, db="db")
    assert "frames" not in json.dumps(tree)     # plain JSON, as ever
    framed = ex.execute(sql, db="db", frames=True)
    (res,) = framed["results"]
    if "GROUP BY hostname" in sql and "max(usage)" in sql:
        assert "frames" not in res              # the walker's shape
    else:
        assert all(isinstance(f, qr.Frame) for f in res["frames"])
    renamed = {("series" if k == "frames" else k): v
               for k, v in _tree_of(res).items()}
    assert _text(renamed) == _text(tree["results"][0])
    assert list(renamed) == list(tree["results"][0])


@pytest.mark.parametrize("sql,why", [
    (f"SELECT mean(usage) FROM cpu WHERE {RANGE} GROUP BY time(1m), hostname "
     "SLIMIT 2 SOFFSET 1", "series are cut as rows"),
    (f"SELECT mean(m) FROM (SELECT max(usage) AS m FROM cpu WHERE {RANGE} "
     "GROUP BY time(1m), hostname) GROUP BY time(5m)", "a subquery's reader"),
    (f"SELECT mean(usage) INTO copy FROM cpu WHERE {RANGE} "
     "GROUP BY time(1m), hostname", "INTO reads the rows"),
    (f"SELECT usage FROM cpu WHERE {RANGE} LIMIT 3", "a raw select"),
    ("SHOW MEASUREMENTS", "not a select"),
])
def test_statements_whose_rows_are_read_in_process_stay_trees(engine, sql, why):
    ex = Executor(engine)
    res = ex.execute(sql, db="db", frames=True)["results"][0]
    assert "frames" not in res, why
    assert "error" not in res, res
    json.dumps(res)


def test_an_empty_answer_is_an_empty_statement_either_way(engine):
    ex = Executor(engine)
    sql = ("SELECT mean(usage) FROM cpu WHERE time >= 10s AND time < 20s "
           "GROUP BY time(1s), hostname")
    assert ex.execute(sql, db="db", frames=True) == ex.execute(sql, db="db") \
        == {"results": [{"statement_id": 0}]}

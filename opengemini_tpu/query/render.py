"""An aggregate SELECT's answer, from the reduce's arrays to the response.

`build_frame` evaluates every output column once over the whole
(group, window) grid — the typing, null, fill, ORDER BY / OFFSET / LIMIT
and "no point in the range, no series" rules of InfluxQL as array
operations — and leaves a `Frame`: one tagged array a column.  Two
renderers read it.  `Frame.series()` is the tree of series dicts every
in-process reader gets (subqueries, SELECT INTO, joins, the cluster's
RPC, chunked and pretty responses).  `rows_json` is the same answer as
the bytes `json.dumps(format_result(tree, epoch), allow_nan=False)`
would make of that tree, written in bulk: no list per row and no boxed
number per cell, so neither `json.dumps` nor the cyclic collector walks
the 48,000 rows of a fleet-wide GROUP BY.  Names, tags and column names
still go through `json.dumps` (escaping is the library's), a window's
time is formatted once a statement, and the cells are written by
`native/render.cpp` where the library loaded, else by `_rows_py`, which
is also the reference the native bytes are tested against
(tests/test_influx_render.py).

A statement whose arithmetic numpy cannot do exactly as Python does it
(integers past int64, or past 2^53 where a division or a linear fill
would round twice) raises `NotColumnar`: the executor's per-row walker
answers it, and `query/render_cells` − `query/render_bulk_cells` counts
how often.

Reference: engine/executor fill_transform.go, httpd response writer.
"""

from __future__ import annotations

import ctypes
import json
from dataclasses import dataclass, replace

import numpy as np

from opengemini_tpu.promql import render as _native
from opengemini_tpu.query import condition as cond
from opengemini_tpu.query.qhelpers import QueryError, _strip_expr
from opengemini_tpu.record import FieldType
from opengemini_tpu.sql import ast
from opengemini_tpu.utils.stats import GLOBAL as STATS

# what a cell holds; native/render.cpp switches on the same numbers
NULL, FLOAT, INT, BOOL = 0, 1, 2, 3

_EXACT_F64 = float(1 << 53)     # integers a float64 holds exactly
_SAFE_I64 = float(1 << 62)      # a float64 estimate under this fits int64
_INT_EXACT_AGGS = ("sum", "min", "max", "first", "last", "spread")


class NotColumnar(Exception):
    """The statement's arithmetic needs Python's unbounded integers."""


@dataclass
class Column:
    """One output column over (series, row): `kind` says which of `i`
    (INT, BOOL) and `f` (FLOAT) holds a cell; either is None where no
    cell of the column is of its kind."""

    kind: np.ndarray            # uint8
    i: np.ndarray | None        # int64
    f: np.ndarray | None        # float64


@dataclass
class Frame:
    name: str
    columns: list               # "time" first
    group_tags: list
    keys: list                  # a series' tag values, in output order
    times: np.ndarray           # int64 ns, one a row
    cols: list                  # Column, arrays (len(keys), len(times))
    rowmask: np.ndarray | None  # bool; None: every row of every series

    def reversed(self) -> "Frame":
        """The same series, last first (ORDER BY time DESC reverses the
        series of a statement too)."""
        back = slice(None, None, -1)
        return replace(
            self, keys=self.keys[back],
            rowmask=None if self.rowmask is None else self.rowmask[back],
            cols=[_cut(c, groups=back) for c in self.cols])

    def series(self) -> list[dict]:
        """The tree: what `_render_agg` has always returned."""
        times = self.times.tolist()
        cells = [_cells(c) for c in self.cols]
        masks = None if self.rowmask is None else self.rowmask.tolist()
        out = []
        for g, key in enumerate(self.keys):
            rows = zip(times, *[c[g] for c in cells])
            if masks is not None:
                rows = (r for r, keep in zip(rows, masks[g]) if keep)
            s = {"name": self.name, "columns": self.columns,
                 "values": list(map(list, rows))}
            if self.group_tags:
                s["tags"] = dict(zip(self.group_tags, key))
            out.append(s)
        return out


def _cells(col: Column) -> list:
    """The column's cells as nested lists of float, int, bool or None."""
    kind = col.kind
    gaps = kind == NULL
    held = np.unique(kind[~gaps]).tolist()
    if len(held) == 1:
        # one kind and nulls, the common column: one tolist(), then the nulls
        k = held[0]
        out = (col.f if k == FLOAT else col.i != 0 if k == BOOL
               else col.i).tolist()
        for g, w in zip(*(ix.tolist() for ix in np.nonzero(gaps))):
            out[g][w] = None
        return out
    out = np.full(kind.shape, None, dtype=object)
    for k, vals in ((FLOAT, col.f), (INT, col.i),
                    (BOOL, None if col.i is None else col.i != 0)):
        if k in held:
            m = kind == k
            out[m] = vals[m].astype(object)
    return out.tolist()


# -- evaluation --------------------------------------------------------------


@dataclass
class _Val:
    """An expression over the grid: typed values (float64, int64 or bool;
    0-d for a literal), where it is null, and where a call under it saw a
    point."""

    vals: np.ndarray
    null: np.ndarray
    present: np.ndarray


def _to_int64(v: np.ndarray, have: np.ndarray, rint: bool) -> np.ndarray:
    """int(v) (or int(round(v))) of the cells `have` marks."""
    if v.dtype.kind in "iub":
        if v.dtype.kind == "u" and v.size and v.max() > np.iinfo(np.int64).max:
            raise NotColumnar("unsigned value past int64")
        return v.astype(np.int64)
    f = np.where(have, v, 0).astype(np.float64)
    if rint:
        f = np.rint(f)          # round-half-even, as Python's round()
    if not (np.abs(f) < _SAFE_I64).all():
        raise NotColumnar("non-finite or huge value to an int column")
    return f.astype(np.int64)   # truncates, as int()


def _eval_call(call, agg_results, schema, shape) -> _Val:
    entry = agg_results.get(id(call))
    if entry is None:
        raise QueryError(f"unplanned call {call.name}")
    out, _sel, counts, spec, fname, _times = entry
    have = np.asarray(counts).reshape(shape) != 0
    v = np.asarray(out).reshape(shape)
    ftype = schema.get(fname)
    if spec.int_output:
        return _Val(_to_int64(v, have, rint=False), ~have, have)
    if ftype == FieldType.INT and spec.name in _INT_EXACT_AGGS:
        # the int64-exact path yields integer arrays: never round-trip
        # them through float (the 2^53 cliff)
        return _Val(_to_int64(v, have, rint=True), ~have, have)
    if ftype == FieldType.BOOL and spec.name in ("first", "last", "min", "max"):
        return _Val(np.rint(np.where(have, v, 0).astype(np.float64)) != 0,
                    ~have, have)
    # float(v) of any dtype; a single-sample stddev is the reduce's 0
    # (reference NewStdDevReduce); non-finite marshals as null
    f = v.astype(np.float64, copy=False)    # read only until _column copies
    return _Val(f, ~have | ~np.isfinite(f), have)


def _as_number(vals: np.ndarray) -> np.ndarray:
    # bool is an int to Python's arithmetic (True + True == 2)
    return vals.astype(np.int64) if vals.dtype == bool else vals


def _check_int(estimate: np.ndarray, null: np.ndarray) -> None:
    if not (np.abs(np.where(null, 0.0, estimate)) < _SAFE_I64).all():
        raise NotColumnar("integer arithmetic past int64")


def _eval_binary(op: str, a: _Val, b: _Val) -> _Val:
    null = a.null | b.null
    present = a.present | b.present
    x, y = _as_number(a.vals), _as_number(b.vals)
    ints = x.dtype.kind == "i" and y.dtype.kind == "i"
    with np.errstate(all="ignore"):
        if op in ("+", "-", "*"):
            fn = {"+": np.add, "-": np.subtract, "*": np.multiply}[op]
            if ints:
                _check_int(fn(x.astype(np.float64), y.astype(np.float64)), null)
            return _Val(fn(x, y), null, present)
        if op in ("/", "%"):
            zero = y == 0
            null = null | zero          # division by zero is null
            y = np.where(zero, 1, y)
            if op == "%":
                return _Val(np.mod(x, y), null, present)    # divisor's sign
            if ints:
                # int / int is the correctly rounded quotient in Python;
                # float64 division is that only of exact operands
                for side in (x, y):
                    if not (np.abs(np.where(null, 0, side)) <= _EXACT_F64).all():
                        raise NotColumnar("int / int past 2^53")
            return _Val(np.true_divide(x, y), null, present)
    raise QueryError(f"unsupported output expression: ({op})")


def _eval(expr, agg_results, schema, shape) -> _Val:
    expr = _strip_expr(expr)
    if isinstance(expr, ast.Call):
        return _eval_call(expr, agg_results, schema, shape)
    if isinstance(expr, (ast.NumberLiteral, ast.IntegerLiteral)):
        lit = np.asarray(expr.val)
        if lit.dtype not in (np.float64, np.int64):
            raise NotColumnar("literal past int64")
        return _Val(lit, np.False_, np.False_)
    if isinstance(expr, ast.UnaryExpr) and expr.op == "-":
        v = _eval(expr.expr, agg_results, schema, shape)
        x = _as_number(v.vals)
        if x.dtype.kind == "i" and (x == np.iinfo(np.int64).min).any():
            raise NotColumnar("-int64.min")
        return _Val(-x, v.null, v.present)
    if isinstance(expr, ast.BinaryExpr):
        return _eval_binary(
            expr.op, _eval(expr.lhs, agg_results, schema, shape),
            _eval(expr.rhs, agg_results, schema, shape))
    raise QueryError(f"unsupported output expression: {expr}")


def _column(v: _Val, order: np.ndarray, shape) -> Column:
    """The groups `order` names, in that order, as writable arrays."""
    vals, null = v.vals, v.null
    if vals.shape != shape:                 # a literal
        vals = np.broadcast_to(vals, shape)
    if null.shape != shape:
        null = np.broadcast_to(null, shape)
    vals, null = vals[order], null[order]
    if vals.dtype.kind == "f":
        return Column(np.where(null, NULL, FLOAT).astype(np.uint8), None, vals)
    kind = BOOL if vals.dtype == bool else INT
    return Column(np.where(null, NULL, kind).astype(np.uint8),
                  vals.astype(np.int64), None)


def _cut(col: Column, groups=slice(None), rows=slice(None)) -> Column:
    def pick(a):
        return None if a is None else a[groups][:, rows]
    return Column(pick(col.kind), pick(col.i), pick(col.f))


# -- fill ----------------------------------------------------------------------


def _set_floats(col: Column, where: np.ndarray, values) -> None:
    if col.f is None:
        col.f = np.zeros(col.kind.shape, np.float64)
    col.f[where] = values
    col.kind[where] = FLOAT


def _fill_number(col: Column, value: float) -> None:
    _set_floats(col, col.kind == NULL, value)


def _take_rows(col: Column, idx: np.ndarray, where: np.ndarray) -> None:
    """col[g, w] = col[g, idx[g, w]] in the cells `where` marks."""
    at = np.where(where, idx, 0)
    for name in ("kind", "i", "f"):
        a = getattr(col, name)
        if a is not None:
            a[where] = np.take_along_axis(a, at, axis=1)[where]


def _known_before(kind: np.ndarray) -> np.ndarray:
    """Per cell, the last row at or before it that is not null; -1: none."""
    rows = np.arange(kind.shape[1])
    return np.maximum.accumulate(np.where(kind != NULL, rows, -1), axis=1)


def _known_after(kind: np.ndarray) -> np.ndarray:
    """Per cell, the first row at or after it that is not null; W: none."""
    W = kind.shape[1]
    rows = np.arange(W)
    nxt = np.where(kind != NULL, rows, W)[:, ::-1]
    return np.minimum.accumulate(nxt, axis=1)[:, ::-1]


def _fill_previous(col: Column) -> None:
    prev = _known_before(col.kind)
    _take_rows(col, prev, (col.kind == NULL) & (prev >= 0))


def _fill_linear(col: Column) -> None:
    """va + (vb - va) * (i - a) / (b - a) between the known cells a < b
    round each run of nulls; always a float, whatever the column holds."""
    kind = col.kind
    W = kind.shape[1]
    a, b = _known_before(kind), _known_after(kind)
    gap = (kind == NULL) & (a >= 0) & (b < W)
    if not gap.any():
        return
    if col.i is not None:
        # Python subtracts and multiplies the integers exactly before it
        # divides; float64 does the same only while they fit 53 bits
        top = np.abs(np.where(kind == NULL, 0, col.i)).max(initial=0)
        if float(top) * 2.0 * W > _EXACT_F64:
            raise NotColumnar("linear fill of integers past 2^53")
        known = col.i.astype(np.float64)
        if col.f is not None:
            known = np.where(kind == FLOAT, col.f, known)
    else:
        known = col.f
    at_a, at_b = np.where(gap, a, 0), np.where(gap, b, 0)
    va = np.take_along_axis(known, at_a, axis=1)
    vb = np.take_along_axis(known, at_b, axis=1)
    rows = np.arange(W)
    with np.errstate(all="ignore"):
        mid = va + (vb - va) * (rows - at_a) / np.where(gap, at_b - at_a, 1)
    _set_floats(col, gap, mid[gap])


# -- the frame -----------------------------------------------------------------


def build_frame(stmt, mst, columns, col_exprs, group_tags, group_keys,
                aligned, W, agg_results, schema) -> Frame:
    """The answer of one aggregate SELECT over one measurement."""
    G = len(group_keys)
    group_time = stmt.group_by_time
    vals = [_eval(e, agg_results, schema, (G, W)) for e in col_exprs]
    present = np.zeros((G, W), dtype=bool)
    for v in vals:
        present |= v.present
    # no point in the whole range: no series at all, whatever the fill
    # (TestServer_Query_Fill#2)
    seen = present.any(axis=1).tolist()
    order = np.asarray(
        [g for g in sorted(range(G), key=lambda g: group_keys[g]) if seen[g]],
        dtype=np.int64)
    keys = [group_keys[g] for g in order.tolist()]
    cols = [_column(v, order, (G, W)) for v in vals]
    every = group_time.every_ns if group_time else 0
    times = (aligned or 0) + np.arange(W, dtype=np.int64) * every

    rowmask = None
    fill = stmt.fill_option
    if not group_time or fill == "none":
        rowmask = present[order]
    elif fill == "null":
        # a bare count() renders 0 for an empty window (Fill#6)
        for col, e in zip(cols, col_exprs):
            e = _strip_expr(e)
            if isinstance(e, ast.Call) and e.name in ("count", "count_distinct"):
                gap = col.kind == NULL
                col.i[gap] = 0
                col.kind[gap] = INT
    elif fill == "number":
        for col in cols:
            _fill_number(col, stmt.fill_value)
    elif fill == "previous":
        for col in cols:
            _fill_previous(col)
    elif fill == "linear":
        for col in cols:
            _fill_linear(col)

    if not stmt.ascending:
        back = slice(None, None, -1)
        times = times[back]
        cols = [_cut(c, rows=back) for c in cols]
        if rowmask is not None:
            rowmask = rowmask[:, back]
    skip = stmt.offset or 0
    if rowmask is None:
        if skip or stmt.limit:
            cut = slice(skip, skip + stmt.limit if stmt.limit else None)
            times = times[cut]
            cols = [_cut(c, rows=cut) for c in cols]
        alive = np.full(len(keys), len(times) > 0)
    else:
        if skip or stmt.limit:
            nth = np.cumsum(rowmask, axis=1)    # 1-based among the kept
            rowmask = rowmask & (nth > skip)
            if stmt.limit:
                rowmask &= nth <= skip + stmt.limit
        alive = rowmask.any(axis=1)
    if not alive.all():                         # a series with no row left
        keys = [k for k, ok in zip(keys, alive.tolist()) if ok]
        cols = [_cut(c, groups=alive) for c in cols]
        if rowmask is not None:
            rowmask = rowmask[alive]
    return Frame(mst, columns, list(group_tags), keys, times, cols, rowmask)


# -- the bytes -------------------------------------------------------------------


def rows_json(frame: Frame, epoch_div: int | None) -> bytes:
    """The frame's series, joined by ", ", as `json.dumps` writes the
    tree's after `format_result`: times as `t // epoch_div`, or as
    RFC3339 strings for None; a non-finite float as `null` (what
    `_send_json` makes of one)."""
    if not frame.keys:
        return b""
    if epoch_div is None:
        ts = [json.dumps(cond.format_rfc3339(t)) for t in frame.times.tolist()]
    else:
        ts = [str(t // epoch_div) for t in frame.times.tolist()]
    head = '{"name": %s, "columns": %s, "values": [' % (
        json.dumps(frame.name), json.dumps(frame.columns))
    if frame.group_tags:
        tails = ['], "tags": %s}' % json.dumps(dict(zip(frame.group_tags, key)))
                 for key in frame.keys]
    else:
        tails = ["]}"] * len(frame.keys)
    n_rows = (len(frame.keys) * len(ts) if frame.rowmask is None
              else int(np.count_nonzero(frame.rowmask)))
    body = _rows_native(frame, ts, head, tails)
    STATS.add("query", (("render_native_cells",
                         0 if body is None else n_rows * len(frame.cols)),))
    if body is None:
        body = _rows_py(frame, ts, head, tails)
    return body


def _rows_native(frame: Frame, ts, head, tails) -> bytes | None:
    """The series, joined, from native/render.cpp; None if it is not loaded."""
    lib = _native.load()
    if lib is None:
        return None
    n_series, n_rows, n_cols = len(frame.keys), len(ts), len(frame.cols)
    keep = []       # the contiguous copies, alive across the call

    def pointers(name, dtype):
        arr = (ctypes.c_void_p * n_cols)()
        for k, col in enumerate(frame.cols):
            a = getattr(col, name)
            if a is not None:
                a = np.ascontiguousarray(a, dtype=dtype)
                keep.append(a)
                arr[k] = a.ctypes.data
        return arr

    kinds = pointers("kind", np.uint8)
    ivals = pointers("i", np.int64)
    fvals = pointers("f", np.float64)
    mask = None
    if frame.rowmask is not None:
        mask = np.ascontiguousarray(frame.rowmask, dtype=np.uint8)
    # json.dumps escaped whatever was not ASCII: a character is a byte
    ts_off, tail_off = _native._offsets(ts), _native._offsets(tails)
    ts_buf = "".join(ts).encode("ascii")
    tail_buf = "".join(tails).encode("ascii")
    head_buf = head.encode("ascii")
    widest = int(np.diff(ts_off).max(initial=0)) + 4 + 26 * n_cols  # a row, see .cpp
    cap = (n_series * (len(head_buf) + 2) + int(tail_off[-1])
           + n_series * n_rows * widest)
    out = np.empty(cap, dtype=np.uint8)
    n = lib.ogt_render_rows(
        n_cols, kinds, ivals, fvals,
        None if mask is None else mask.ctypes.data, n_series, n_rows,
        ts_buf, ts_off.ctypes.data, head_buf, len(head_buf),
        tail_buf, tail_off.ctypes.data, out.ctypes.data, cap)
    if n < 0:
        raise RuntimeError("render buffer too small")   # a bug, not a state
    return out[:n].tobytes()


def _cell_texts(col: Column) -> list:
    """The JSON text of every cell of a column, row-major."""
    kind = col.kind.ravel()
    out = np.full(kind.shape, "null", dtype=object)
    if col.f is not None:
        m = kind == FLOAT
        f = col.f.ravel()[m]
        text = np.asarray(list(map(repr, f.tolist())), dtype=object)
        text[~np.isfinite(f)] = "null"
        out[m] = text
    if col.i is not None:
        m = kind == INT
        out[m] = np.asarray(list(map(str, col.i.ravel()[m].tolist())), dtype=object)
        m = kind == BOOL
        out[m] = np.where(col.i.ravel()[m] != 0, "true", "false").astype(object)
    return out.tolist()


def _rows_py(frame: Frame, ts, head, tails) -> bytes:
    """The series, joined, in bulk Python: one list of texts a column,
    one join a row."""
    W = len(ts)
    texts = [_cell_texts(c) for c in frame.cols]
    rows = ["[%s]" % ", ".join(r)
            for r in zip(ts * len(frame.keys), *texts)]
    masks = None if frame.rowmask is None else frame.rowmask.tolist()
    out = []
    for g, tail in enumerate(tails):
        mine = rows[g * W:(g + 1) * W]
        if masks is not None:
            mine = [r for r, keep in zip(mine, masks[g]) if keep]
        out.append(head + ", ".join(mine) + tail)
    return ", ".join(out).encode("ascii")

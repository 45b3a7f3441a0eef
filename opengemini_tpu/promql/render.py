"""A PromQL range answer, from its arrays to the response.

`matrix_dict` is the tree in-process callers read (`PromEngine.query_range`).
`matrix_json` is the same answer as the bytes `json.dumps` would make of
that tree, written in bulk: no list and no numpy scalar per point, so
neither `json.dumps` nor the cyclic collector ever walks 560,000 points of
a fleet-wide `rate()`.  Labels still go through `json.dumps` (escaping is
the library's), a timestamp is formatted once a query, and the values are
formatted from one float64 matrix — by `native/render.cpp` where the
library loaded, else by `_series_py`, which is also the reference the
native bytes are tested against (tests/test_prom_render.py).

Reference: handler_prom.go writes the response from the result's slices.
"""

from __future__ import annotations

import ctypes
import json
import math
from itertools import compress

import numpy as np

from opengemini_tpu.utils.stats import GLOBAL as STATS

_LIB = None
_TRIED = False


def _bind(lib) -> None:
    ptr, i64 = ctypes.c_void_p, ctypes.c_int64
    lib.ogt_repr_f64.restype = i64
    lib.ogt_repr_f64.argtypes = [ptr, i64, ptr, ptr]
    lib.ogt_render_matrix.restype = i64
    lib.ogt_render_matrix.argtypes = [ptr, ptr, i64, ptr, i64, ptr, ptr,
                                      ptr, ptr, ptr, i64]
    # an InfluxQL aggregate's rows (query/render.py)
    lib.ogt_render_rows.restype = i64
    lib.ogt_render_rows.argtypes = [i64, ptr, ptr, ptr, ptr, i64, i64, ptr,
                                    ptr, ptr, i64, ptr, ptr, ptr, i64]


def load():
    """The render library or None (native.open_library builds a missing
    one; the reason it did not load is in native.report())."""
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        from opengemini_tpu import native

        _LIB = native.open_library("render", _bind)
    return _LIB


def fmt_value(v: float) -> str:
    """A sample value as Prometheus writes it: repr digits, NaN, ±Inf."""
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return repr(float(v))


def _label_order(labels: list[dict], rows) -> list[int]:
    """`rows` in the stable by-labels order every matrix answer has."""
    return sorted(rows, key=lambda i: sorted(labels[i].items()))


def matrix_dict(frame, steps) -> dict:
    """{"resultType": "matrix", "result": [...]}; a series with no valid
    point is left out."""
    values, valid = frame.values, frame.valid
    series = {}
    for i in range(len(frame.labels)):
        pts = [
            [float(steps[k]), fmt_value(values[i, k])]
            for k in range(len(steps))
            if valid[i, k]
        ]
        if pts:
            series[i] = {"metric": frame.labels[i], "values": pts}
    return {"resultType": "matrix",
            "result": [series[i] for i in _label_order(frame.labels, series)]}


def matrix_json(frame, steps) -> bytes:
    """`json.dumps(matrix_dict(frame, steps))`, byte for byte."""
    # float(v) of any dtype the frame holds, all at once
    values = np.ascontiguousarray(frame.values, dtype=np.float64)
    valid = np.ascontiguousarray(frame.valid, dtype=bool)
    rows = _label_order(frame.labels, np.flatnonzero(valid.any(axis=1)).tolist())
    heads = ['{"metric": %s, "values": ' % json.dumps(frame.labels[i])
             for i in rows]
    ts = [repr(float(t)) for t in steps]     # as json.dumps writes a float
    n_points = int(np.count_nonzero(valid))
    body = _series_native(values, valid, rows, heads, ts)
    STATS.add("prom", (("render_points", n_points),
                       ("render_native_points", 0 if body is None else n_points)))
    if body is None:
        body = _series_py(values, valid, rows, heads, ts)
    return b'{"resultType": "matrix", "result": [' + body + b"]}"


def _offsets(parts: list[str]) -> np.ndarray:
    """Where each part starts in their concatenation (ASCII parts)."""
    off = np.zeros(len(parts) + 1, dtype=np.int64)
    np.cumsum([len(p) for p in parts], out=off[1:])
    return off


def _series_native(values, valid, rows, heads, ts) -> bytes | None:
    """The series, joined, from native/render.cpp; None if it is not loaded."""
    lib = load()
    if lib is None:
        return None
    # json.dumps escaped whatever was not ASCII: a character is a byte
    head_off, ts_off = _offsets(heads), _offsets(ts)
    head_buf, ts_buf = "".join(heads).encode("ascii"), "".join(ts).encode("ascii")
    order = np.asarray(rows, dtype=np.int64)
    widest = int(np.diff(ts_off).max(initial=0)) + 32       # a point, see .cpp
    cap = int(head_off[-1]) + len(rows) * (len(ts) * widest + 8)
    out = np.empty(cap, dtype=np.uint8)
    n = lib.ogt_render_matrix(
        values.ctypes.data, valid.ctypes.data, len(ts),
        order.ctypes.data, len(rows),
        ts_buf, ts_off.ctypes.data, head_buf, head_off.ctypes.data,
        out.ctypes.data, cap)
    if n < 0:
        raise RuntimeError("render buffer too small")   # a bug, not a state
    return out[:n].tobytes()


def _series_py(values, valid, rows, heads, ts) -> bytes:
    """The series, joined, in bulk Python: one `tolist()`, one template of
    the timestamps per distinct valid-mask, `repr` mapped over a row."""
    plain = np.isfinite(values).all(axis=1).tolist()
    vals, masks = values.tolist(), valid.tolist()
    templates: dict[bytes, str] = {}
    out = []
    for i, head in zip(rows, heads):
        key = valid[i].tobytes()
        tpl = templates.get(key)
        if tpl is None:
            tpl = templates[key] = "[%s]}" % ", ".join(
                '[%s, "%%s"]' % t for t in compress(ts, masks[i]))
        row = compress(vals[i], masks[i])
        out.append(head + tpl % tuple(
            map(repr, row) if plain[i] else map(fmt_value, row)))
    return ", ".join(out).encode("ascii")


def repr_floats(values: np.ndarray) -> list[str] | None:
    """`[repr(float(v)) for v in values]` through the library's formatter
    (the one `ogt_render_matrix` writes with; any float column's renderer
    can call it), or None where the library is not loaded."""
    lib = load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(values, dtype=np.float64).ravel()
    out = np.empty(24 * len(vals), dtype=np.uint8)
    off = np.empty(len(vals) + 1, dtype=np.int64)
    n = lib.ogt_repr_f64(vals.ctypes.data, len(vals), out.ctypes.data,
                         off.ctypes.data)
    text = out[:n].tobytes().decode("ascii")
    bounds = off.tolist()
    return [text[a:b] for a, b in zip(bounds, bounds[1:])]

"""ctypes binding for the native batch line-protocol parser
(native/lineproto.cpp) — the ingest hot path.

Role of the reference's pooled protoparser
(lib/util/lifted/vm/protoparser/influx/parser.go driven from
lib/util/lifted/influx/httpd/handler.go:1633): parse /write bodies at
millions of rows/s. The output here is COLUMNAR — numpy value/validity
arrays per (measurement, field), a deduplicated canonical-series table,
and int64 timestamps — so the storage layer appends whole slabs instead
of iterating rows (see storage/memtable.py write_columnar).

`parse_columnar` returns None when the library is unavailable or the
batch needs the exact Python parser (escape sequences, '_' digit
separators, pathological width); callers then fall back to
ingest/line_protocol.py, which remains the semantic reference.
"""

from __future__ import annotations

import ctypes

import numpy as np

from opengemini_tpu.ingest.line_protocol import PRECISIONS, ParseError
from opengemini_tpu.record import FieldType

_LIB = None
_TRIED = False


class _LpBatch(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("ts", ctypes.POINTER(ctypes.c_int64)),
        ("series_ref", ctypes.POINTER(ctypes.c_int32)),
        ("n_series", ctypes.c_int64),
        ("skey_off", ctypes.POINTER(ctypes.c_int64)),
        ("skey_arena", ctypes.POINTER(ctypes.c_char)),
        ("series_mst", ctypes.POINTER(ctypes.c_int32)),
        ("n_msts", ctypes.c_int32),
        ("mst_off", ctypes.POINTER(ctypes.c_int64)),
        ("mst_arena", ctypes.POINTER(ctypes.c_char)),
        ("n_cols", ctypes.c_int32),
        ("col_name_off", ctypes.POINTER(ctypes.c_int64)),
        ("col_name_arena", ctypes.POINTER(ctypes.c_char)),
        ("col_mst", ctypes.POINTER(ctypes.c_int32)),
        ("col_type", ctypes.POINTER(ctypes.c_int8)),
        ("col_vals", ctypes.POINTER(ctypes.POINTER(ctypes.c_int64))),
        ("col_valid", ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))),
        ("str_arena", ctypes.POINTER(ctypes.c_char)),
        ("str_arena_len", ctypes.c_int64),
        ("status", ctypes.c_int32),
        ("err_line", ctypes.c_int64),
        ("err_msg", ctypes.c_char * 240),
    ]


def _bind(lib) -> None:
    lib.ogt_lp_parse.restype = ctypes.POINTER(_LpBatch)
    lib.ogt_lp_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.ogt_lp_free.restype = None
    lib.ogt_lp_free.argtypes = [ctypes.POINTER(_LpBatch)]


def load():
    """The line-protocol parser library or None (native.open_library
    builds a missing one; the reason it did not load is in
    native.report())."""
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        from opengemini_tpu import native

        _LIB = native.open_library("lineproto", _bind)
    return _LIB


class ColumnarBatch:
    """One parsed /write body in columnar form.

    ts[i], series_ref[i] describe row i; series_keys[series_ref[i]] is its
    canonical series key (identical bytes to line_protocol.series_key).
    cols is [(mst_id, field_name, FieldType, values, valid)] where values
    and valid are dense over ALL rows (rows of other measurements are
    simply invalid).
    """

    __slots__ = ("ts", "series_ref", "series_keys", "series_mst",
                 "measurements", "cols")

    def __init__(self, ts, series_ref, series_keys, series_mst,
                 measurements, cols):
        self.ts = ts
        self.series_ref = series_ref
        self.series_keys = series_keys
        self.series_mst = series_mst
        self.measurements = measurements
        self.cols = cols

    def __len__(self) -> int:
        return len(self.ts)

    def row_mst(self) -> np.ndarray:
        """Measurement id per row."""
        return self.series_mst[self.series_ref]

    def to_points(self) -> list:
        """Rebuild (measurement, tags, t_ns, {field: (type, value)}) tuples,
        one per row in row order — the shape write observers consume.
        Slow (a dict and boxed values a row): the engine hands observers a
        WrittenPoints view and calls this only when one of them reads it,
        i.e. when the written database has a stream or a subscription."""
        from opengemini_tpu.index.inverted import parse_series_key

        tag_cache = [None] * len(self.series_keys)

        def series_tuple(ref: int):
            cached = tag_cache[ref]
            if cached is None:
                cached = tag_cache[ref] = parse_series_key(self.series_keys[ref])
            return cached

        per_row_fields: list[dict] = [dict() for _ in range(len(self.ts))]
        row_mst = self.row_mst()
        for mst_id, name, ftype, values, valid in self.cols:
            rows = np.flatnonzero(valid & (row_mst == mst_id))
            for r in rows:
                v = values[r]
                if ftype == FieldType.FLOAT:
                    v = float(v)
                elif ftype == FieldType.INT:
                    v = int(v)
                elif ftype == FieldType.BOOL:
                    v = bool(v)
                per_row_fields[r][name] = (ftype, v)
        out = []
        for i in range(len(self.ts)):
            mst, tags = series_tuple(int(self.series_ref[i]))
            out.append((mst, tags, int(self.ts[i]), per_row_fields[i]))
        return out


def _offsets_to_strings(arena_ptr, off: np.ndarray) -> list[str]:
    if len(off) <= 1:
        return []
    blob = ctypes.string_at(arena_ptr, int(off[-1])) if off[-1] else b""
    return [blob[off[i]:off[i + 1]].decode("utf-8", errors="replace")
            for i in range(len(off) - 1)]


def _copy_arr(ptr, n: int, dtype) -> np.ndarray:
    if n == 0:
        return np.empty(0, dtype=dtype)
    itemsize = np.dtype(dtype).itemsize
    return np.frombuffer(ctypes.string_at(ptr, n * itemsize), dtype=dtype).copy()


def parse_columnar(data: bytes, precision: str = "ns",
                   now_ns: int | None = None,
                   max_bytes: int = 512 << 20) -> ColumnarBatch | None:
    """Parse a line-protocol batch natively. Returns None when the caller
    must fall back to the Python parser; raises ParseError on malformed
    input (same contract as line_protocol.parse_lines)."""
    lib = load()
    if lib is None:
        return None
    mult = PRECISIONS.get(precision)
    if mult is None:
        raise ValueError(f"invalid precision {precision!r}")
    if now_ns is None:
        import time as _time

        now_ns = _time.time_ns()
    if isinstance(data, str):
        data = data.encode("utf-8")
    bp = lib.ogt_lp_parse(data, len(data), mult, now_ns, max_bytes)
    if not bp:
        return None
    try:
        b = bp.contents
        if b.status == 1:  # needs the exact Python parser
            return None
        if b.status == 2:
            raise ParseError(int(b.err_line),
                             b.err_msg.decode("utf-8", errors="replace"))
        n = int(b.n_rows)
        ts = _copy_arr(b.ts, n, np.int64)
        series_ref = _copy_arr(b.series_ref, n, np.int32)
        skey_off = _copy_arr(b.skey_off, int(b.n_series) + 1, np.int64)
        series_keys = _offsets_to_strings(b.skey_arena, skey_off)
        series_mst = _copy_arr(b.series_mst, int(b.n_series), np.int32)
        mst_off = _copy_arr(b.mst_off, int(b.n_msts) + 1, np.int64)
        measurements = _offsets_to_strings(b.mst_arena, mst_off)
        name_off = _copy_arr(b.col_name_off, int(b.n_cols) + 1, np.int64)
        col_names = _offsets_to_strings(b.col_name_arena, name_off)
        col_mst = _copy_arr(b.col_mst, int(b.n_cols), np.int32)
        col_type = _copy_arr(b.col_type, int(b.n_cols), np.int8)
        str_blob = (ctypes.string_at(b.str_arena, int(b.str_arena_len))
                    if b.str_arena_len else b"")
        cols = []
        for c in range(int(b.n_cols)):
            slots = _copy_arr(b.col_vals[c], n, np.int64)
            valid = _copy_arr(b.col_valid[c], n, np.uint8).astype(np.bool_)
            t = int(col_type[c])
            if t == 1:
                values = slots.view(np.float64)
                ftype = FieldType.FLOAT
            elif t == 2:
                values = slots
                ftype = FieldType.INT
            elif t == 3:
                values = slots.astype(np.bool_)
                ftype = FieldType.BOOL
            else:
                ftype = FieldType.STRING
                values = np.empty(n, dtype=object)
                offs = (slots >> 32).astype(np.int64)
                lens = (slots & 0xFFFFFFFF).astype(np.int64)
                for r in np.flatnonzero(valid):
                    o, ln = int(offs[r]), int(lens[r])
                    values[r] = str_blob[o:o + ln].decode(
                        "utf-8", errors="replace")
            cols.append((int(col_mst[c]), col_names[c], ftype, values, valid))
        return ColumnarBatch(ts, series_ref, series_keys, series_mst,
                             measurements, cols)
    finally:
        lib.ogt_lp_free(bp)

"""Columnar in-memory record format.

The device-friendly analogue of the reference's `lib/record.Record`
(record.go:57) / `ColVal` (column.go:30): struct-of-arrays with explicit
validity masks instead of packed nil-bitmaps, so columns map 1:1 onto
(values, mask) device array pairs.

Field types follow InfluxDB semantics: float64, int64, bool, string.
Strings never go to the device; group keys are dictionary-encoded on the CPU
before transfer.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

import numpy as np


class FieldType(enum.IntEnum):
    """Field types (reference: lib/record/record.go influx.Field_Type_*)."""

    FLOAT = 1
    INT = 2
    BOOL = 3
    STRING = 4

    @property
    def np_dtype(self) -> np.dtype:
        return _NP_DTYPES[self]


_NP_DTYPES = {
    FieldType.FLOAT: np.dtype(np.float64),
    FieldType.INT: np.dtype(np.int64),
    FieldType.BOOL: np.dtype(np.bool_),
    FieldType.STRING: np.dtype(object),
}

TIME_COL = "time"


def np_to_field_type(dtype: np.dtype) -> FieldType:
    if dtype.kind == "f":
        return FieldType.FLOAT
    if dtype.kind in ("i", "u"):
        return FieldType.INT
    if dtype.kind == "b":
        return FieldType.BOOL
    return FieldType.STRING


@dataclass
class Column:
    """A single column: values plus a validity mask (True = present).

    Equivalent of the reference ColVal's Val+Bitmap (lib/record/column.go:30),
    unpacked for device friendliness.
    """

    ftype: FieldType
    values: np.ndarray
    valid: np.ndarray

    @classmethod
    def empty(cls, ftype: FieldType) -> "Column":
        return cls(ftype, np.empty(0, dtype=ftype.np_dtype), np.empty(0, dtype=np.bool_))

    @classmethod
    def from_values(cls, ftype: FieldType, values, valid=None) -> "Column":
        arr = np.asarray(values, dtype=ftype.np_dtype)
        if valid is None:
            v = np.ones(len(arr), dtype=np.bool_)
        else:
            v = np.asarray(valid, dtype=np.bool_)
        return cls(ftype, arr, v)

    def __len__(self) -> int:
        return len(self.values)

    def take(self, idx: np.ndarray) -> "Column":
        return Column(self.ftype, self.values[idx], self.valid[idx])

    def concat(self, other: "Column") -> "Column":
        assert self.ftype == other.ftype
        return Column(
            self.ftype,
            np.concatenate([self.values, other.values]),
            np.concatenate([self.valid, other.valid]),
        )


@dataclass
class Record:
    """A batch of rows for one series (or one measurement slice): a time
    column plus named field columns, all equal length.

    times are int64 nanoseconds since epoch (InfluxDB convention).
    """

    times: np.ndarray  # int64 ns
    columns: dict[str, Column] = field(default_factory=dict)

    @classmethod
    def empty(cls) -> "Record":
        return cls(np.empty(0, dtype=np.int64), {})

    def __len__(self) -> int:
        return len(self.times)

    @property
    def field_names(self) -> list[str]:
        return list(self.columns.keys())

    def take(self, idx: np.ndarray) -> "Record":
        return Record(self.times[idx], {k: c.take(idx) for k, c in self.columns.items()})

    def concat(self, other: "Record") -> "Record":
        if len(self) == 0:
            return other
        if len(other) == 0:
            return self
        cols: dict[str, Column] = {}
        names = list(self.columns.keys()) + [
            k for k in other.columns if k not in self.columns
        ]
        n_self, n_other = len(self), len(other)
        for k in names:
            a = self.columns.get(k)
            b = other.columns.get(k)
            if a is None:
                a = _null_column(b.ftype, n_self)
            if b is None:
                b = _null_column(a.ftype, n_other)
            cols[k] = a.concat(b)
        return Record(np.concatenate([self.times, other.times]), cols)

    def sort_by_time(self, descending: bool = False) -> "Record":
        """Stable sort by time. With duplicate timestamps the LAST occurrence
        wins on dedup (reference last-write-wins merge semantics,
        lib/record/merge.go)."""
        if not descending and (
                len(self) <= 1 or not (self.times[1:] < self.times[:-1]).any()):
            # already ascending (every TSF chunk, most merged reads):
            # records are immutable on the read path, so the identity
            # return is safe
            return self
        order = np.argsort(self.times, kind="stable")
        if descending:
            order = order[::-1]
        return self.take(order)

    def dedup_last_wins(self) -> "Record":
        """Assumes time-sorted ascending; keeps the last row per timestamp."""
        if len(self) <= 1:
            return self
        keep = np.empty(len(self), dtype=np.bool_)
        keep[:-1] = self.times[:-1] != self.times[1:]
        keep[-1] = True
        if keep.all():
            return self
        return self.take(np.nonzero(keep)[0])

    def slice_time(self, t_min: int, t_max: int) -> "Record":
        """Rows with t_min <= time < t_max (assumes nothing about order)."""
        m = (self.times >= t_min) & (self.times < t_max)
        if m.all():
            return self
        return self.take(np.nonzero(m)[0])


def _zeroed(ftype: FieldType, n: int) -> np.ndarray:
    if ftype == FieldType.STRING:
        return np.full(n, None, dtype=object)
    return np.zeros(n, dtype=ftype.np_dtype)


def _null_column(ftype: FieldType, n: int) -> Column:
    return Column(ftype, _zeroed(ftype, n), np.zeros(n, dtype=np.bool_))


class RecordBuilder:
    """Row-at-a-time appender producing a Record; used by the memtable.

    Maintains per-field python lists and converts to numpy on build — O(1)
    amortized appends without numpy realloc churn.
    """

    def __init__(self) -> None:
        self._times: list[int] = []
        self._cols: dict[str, tuple[FieldType, list, list]] = {}

    def __len__(self) -> int:
        return len(self._times)

    def append_row(self, t: int, fields: dict[str, tuple[FieldType, object]]) -> None:
        # Validate the whole point before mutating any state: a rejected
        # point must not leave a phantom row behind (the reference rejects
        # whole points at routeAndMapOriginRows, coordinator/points_writer.go:381).
        for name, (ftype, _) in fields.items():
            col = self._cols.get(name)
            if col is not None and col[0] != ftype:
                raise FieldTypeConflict(name, col[0], ftype)
        row_i = len(self._times)
        self._times.append(t)
        for name, (ftype, value) in fields.items():
            col = self._cols.get(name)
            if col is None:
                col = (ftype, [], [])
                self._cols[name] = col
            _, vals, idxs = col
            vals.append(value)
            idxs.append(row_i)

    def build(self) -> Record:
        n = len(self._times)
        times = np.asarray(self._times, dtype=np.int64)
        cols: dict[str, Column] = {}
        for name, (ftype, vals, idxs) in self._cols.items():
            valid = np.zeros(n, dtype=np.bool_)
            idx_arr = np.asarray(idxs, dtype=np.int64)
            valid[idx_arr] = True
            if ftype == FieldType.STRING:
                values = np.full(n, None, dtype=object)
            else:
                values = np.zeros(n, dtype=ftype.np_dtype)
            values[idx_arr] = np.asarray(vals, dtype=ftype.np_dtype)
            cols[name] = Column(ftype, values, valid)
        return Record(times, cols)


class FieldTypeConflict(Exception):
    """Write with a field type conflicting with the existing schema
    (reference rejects these at routeAndMapOriginRows,
    coordinator/points_writer.go:381)."""

    def __init__(self, name: str, have: FieldType, got: FieldType):
        super().__init__(
            f"field type conflict for {name!r}: have {have.name}, got {got.name}"
        )
        self.field = name
        self.have = have
        self.got = got


def _join_plain(ftype: FieldType, cols: list, lens: list[int],
                dest: np.ndarray | None = None) -> Column:
    """The parts' columns (None where a part lacks the column) end to
    end — or, given `dest`, every row of that
    concatenation where `dest` puts it (`_interleave`): the output
    allocated once, each part copied into its place once.  Zero-init,
    not np.empty: a slot no part fills stays invalid, but its value
    bytes still flow into flushed chunks and content_digest — heap
    garbage there breaks the replica-identical digest guarantee."""
    total = sum(lens)
    values = _zeroed(ftype, total)
    valid = np.zeros(total, dtype=np.bool_)
    at = 0
    for col, m in zip(cols, lens):
        if col is not None:
            to = slice(at, at + m) if dest is None else dest[at:at + m]
            values[to] = col.values
            valid[to] = col.valid
        at += m
    return Column(ftype, values, valid)


def _join_column(ftype: FieldType, cols: list, lens: list[int]) -> Column:
    """One output column over the parts, built in one pass."""
    if len(cols) == 1 and cols[0] is not None:
        return cols[0]      # one part: its own arrays, no copy
    return _join_plain(ftype, cols, lens)


def _trim_part(s: np.ndarray, r: Record, lo_t: int, hi_t: int):
    """The rows of one part inside [lo_t, hi_t).  A part wholly inside
    is handed back as it is — two reductions, no copy: what a hot read,
    a whole-range read, the memtable's unbounded calls and, of a file
    cut into time segments, every segment the range covers pay.  A
    straddling part (a whole-range chunk of short series or of a file
    written before the cut; the one or two segments a range's ends fall
    in) is masked (packed parts are (sid, time)-sorted, not
    time-sorted: a mask, not two searchsorted; a mask over a sorted part
    leaves it sorted) and copied once, as views where the rows kept are
    one run (a single-series chunk).  Parts wholly outside never come
    this far where the reader's time pruning could tell: a chunk is
    skipped by its own tmin/tmax."""
    t = r.times
    if t.min() >= lo_t and t.max() < hi_t:
        return s, r
    idx = np.flatnonzero((t >= lo_t) & (t < hi_t))
    if len(idx) and idx[-1] - idx[0] + 1 == len(idx):
        lo, hi = int(idx[0]), int(idx[-1]) + 1
        return s[lo:hi], Record(t[lo:hi], {
            k: Column(c.ftype, c.values[lo:hi], c.valid[lo:hi])
            for k, c in r.columns.items()})
    return s[idx], r.take(idx)


def _strictly_increasing(sid: np.ndarray, t: np.ndarray) -> bool:
    """Are the rows strictly (sid, time)-sorted?  Comparisons of
    neighbours only: no difference array is built."""
    if not (sid[1:] >= sid[:-1]).all():
        return False
    ok = t[1:] > t[:-1]
    ok |= sid[1:] != sid[:-1]
    return bool(ok.all())


def _stable_order(sid: np.ndarray, t: np.ndarray) -> np.ndarray:
    """The stable (sid, time) sort order of the rows.  Where both keys
    fit one int64 (sid span x time span under 2^63: every store this
    side of a million series over centuries of nanoseconds) it is ONE
    stable sort of that key, and numpy's stable sort of int64 is a
    timsort, which merges the sorted runs it finds: parts that are each
    (sid, time)-sorted and interleave at a few seams cost a pass, not
    n log n.  Else a two-key lexsort, stable too."""
    s0, t0 = int(sid.min()), int(t.min())
    span = int(t.max()) - t0 + 1
    if (int(sid.max()) - s0 + 1) * span >= 2**63:
        return np.lexsort((t, sid))
    key = sid - s0
    key *= span
    key += t
    key -= t0
    return np.argsort(key, kind="stable")


def _by_single_sid(live) -> list | None:
    """The parts regrouped by sid where every part is ONE series' rows
    (a per-series chunk) and they do not lie in sid order yet, else
    None.  CONSTANT sid required — endpoints alone are not enough: a
    time-sorted memtable part can interleave sids and still have
    s[0] == s[-1].  The sort is stable: parts of one series keep
    oldest-first order."""
    keys = []
    for s, _r in live:
        if s[0] != s[-1] or not (s == s[0]).all():
            return None
        keys.append(int(s[0]))
    at = sorted(range(len(live)), key=keys.__getitem__)
    if at == list(range(len(live))):
        return None
    return [live[i] for i in at]


def _interleave(lens, sid_all, t_all):
    """Where every part lies sid-ascending: (dest, sids, times), the
    rows laid sid after sid with the parts' runs of one sid one after
    the other in part order — `dest[i]` is where row i of the parts'
    concatenation goes.  That is the (sid, time) order whenever a sid's
    rows lie in part order in ascending, disjoint times: the segments a
    file's long series are cut into (`[span A, seg 0][span A, seg 1]
    [span B, seg 0]...`), and files that meet at a seam in sids.  It is
    checked in one pass over the rows laid out; None where it does not
    hold (overlap, duplicates) or a part is not sid-ascending (a
    memtable slab in arrival order): the general merge's cases.  The
    work is on the runs (a part's rows of one sid), a few thousand, and
    three passes over the rows; nothing is sorted but the runs' sids."""
    n = len(sid_all)
    first = np.empty(n, np.bool_)       # the first row of each run
    first[0] = True
    np.not_equal(sid_all[1:], sid_all[:-1], out=first[1:])
    part_at = np.cumsum(lens[:-1], dtype=np.int64)
    first[part_at] = True
    starts = np.flatnonzero(first)
    run_sid = sid_all[starts]
    falls = run_sid[1:] < run_sid[:-1]  # allowed where a part begins
    falls[np.searchsorted(starts, part_at) - 1] = False
    if falls.any():
        return None
    run_len = np.diff(starts, append=n)
    order = np.argsort(run_sid, kind="stable")  # equal sids: part order
    off = np.empty(len(starts), np.int64)       # where each run goes
    len_o = run_len[order]
    off[order] = np.cumsum(len_o) - len_o
    dest = np.repeat(off - starts, run_len)
    dest += np.arange(n)
    sids = np.repeat(run_sid[order], len_o)
    times = np.empty(n, np.int64)
    times[dest] = t_all
    ok = times[1:] > times[:-1]
    ok |= sids[1:] != sids[:-1]
    return (dest, sids, times) if ok.all() else None


def merge_bulk_parts(
    parts: list[tuple[np.ndarray, Record]], lo_t: int, hi_t: int,
    told: dict | None = None,
) -> tuple[np.ndarray, Record]:
    """Vectorized multi-series merge: `parts` is [(sid_arr, record)] in
    oldest-to-newest order; output rows are those with lo_t <= time <
    hi_t, sorted by (sid, time); duplicate (sid, time) pairs keep the
    newest ROW whole (matching merge_sorted_records / dedup_last_wins
    row semantics exactly).

    The work is in proportion to the rows KEPT.  First every part is
    trimmed to the range (`_trim_part`; a part wholly outside gives no
    rows but still its columns' names and types, so a column only it
    carries comes out all-invalid, zero-filled).  Then, over the trimmed
    parts, in this order:

    - `inorder`: their concatenation is already strictly
      (sid, time)-sorted — one part (the memtable consolidation, one
      packed chunk), packed chunks written series-ascending (short
      series: a flush streams a chunk every PACK_ROWS rows, a whole
      series at a time; long ones cut along time, of which the range
      meets one segment a sid span), or files that overlap in sids only
      outside the range asked.  Nothing is sorted;
    - `single_sid`: every part is one series' rows and, grouped by sid,
      they are strictly sorted: one monotonicity pass instead of a sort;
    - `interleaved`: every part is sid-ascending and a sid's rows lie in
      part order in ascending, disjoint times — the time segments of one
      sid span (a range that crosses a segment boundary, every
      whole-range read of long series) and files that meet at a seam.
      The rows need interleaving, not sorting: their places are computed
      from the parts' runs (`_interleave`), checked in one pass;
    - `sorted`: the general merge, one stable sort of the rows kept
      (`_stable_order`; equal (sid, time) keep part order, so the last
      of a group is its newest row, and no rank array is needed).

    All but the last build every output column once: the first two join
    the parts (`_join_column`); `interleaved` writes each part to its
    places (`_join_plain` with `dest`).  `told`, where given, is filled
    with `branch` (one of the four names) and `rows` (rows that entered
    the concatenation or sort, after the trim)."""
    ftypes: dict[str, FieldType] = {}
    live = []
    for s, r in parts:
        if not len(r):
            continue
        for name, col in r.columns.items():
            ftypes.setdefault(name, col.ftype)
        s, r = _trim_part(s, r, lo_t, hi_t)
        if len(r):
            live.append((s, r))
    branch = "inorder"
    if not live:
        sid_all = t_all = np.empty(0, np.int64)
    elif len(live) == 1:
        sid_all, t_all = live[0][0], live[0][1].times
    else:
        sid_all = np.concatenate([s for s, _r in live])
        t_all = np.concatenate([r.times for _s, r in live])
    if not _strictly_increasing(sid_all, t_all):
        by_sid = _by_single_sid(live)
        if by_sid is not None:
            live = by_sid
            sid_all = np.concatenate([s for s, _r in live])
            t_all = np.concatenate([r.times for _s, r in live])
        branch = ("single_sid" if by_sid is not None
                  and _strictly_increasing(sid_all, t_all) else "sorted")
    lens = [len(r) for _s, r in live]
    if branch == "sorted":
        laid = _interleave(lens, sid_all, t_all)
        if laid is not None:
            branch = "interleaved"
    if told is not None:
        told["branch"], told["rows"] = branch, len(t_all)
    if branch == "interleaved":
        dest, sid_out, t_out = laid
        return sid_out, Record(t_out, {
            name: _join_plain(
                ftype, [r.columns.get(name) for _s, r in live], lens, dest)
            for name, ftype in ftypes.items()})
    if branch == "sorted":
        # overlap, duplicates, a part in arrival order: the general merge
        return _merge_sorted(live, ftypes, sid_all, t_all)
    return sid_all, Record(t_all, {
        name: _join_column(
            ftype, [r.columns.get(name) for _s, r in live], lens)
        for name, ftype in ftypes.items()})


def _merge_sorted(live, ftypes, sid_all, t_all) -> tuple[np.ndarray, Record]:
    """merge_bulk_parts' general merge over its trimmed, non-empty
    parts and their concatenated sids and times: overlapping chunks,
    duplicate (sid, time) pairs, parts in any row order."""
    # the sort is stable and the parts lie oldest first, so rows of one
    # (sid, time) keep part order: the group's last is its newest
    order = _stable_order(sid_all, t_all)
    sid_s = sid_all[order]
    t_s = t_all[order]
    last = np.empty(len(order), np.bool_)
    last[-1] = True
    np.not_equal(sid_s[1:], sid_s[:-1], out=last[:-1])
    last[:-1] |= t_s[1:] != t_s[:-1]
    if not last.all():
        # newest row of each (sid, time) group wins whole
        keep = np.flatnonzero(last)
        order, sid_s, t_s = order[keep], sid_s[keep], t_s[keep]
    lens = [len(r) for _s, r in live]
    cols = {}
    for name, ftype in ftypes.items():
        col = _join_plain(
            ftype, [r.columns.get(name) for _s, r in live], lens)
        cols[name] = Column(ftype, col.values[order], col.valid[order])
    return sid_s, Record(t_s, cols)


def merge_sorted_records(records: list[Record]) -> Record:
    """Merge time-sorted records into one sorted, deduped record.

    Later entries in `records` win on duplicate timestamps (caller passes
    older files first, memtable last — the reference's out-of-order merge
    ordering, engine/immutable/merge_tool.go)."""
    recs = [r for r in records if len(r)]
    if not recs:
        return Record.empty()
    if len(recs) == 1:
        return recs[0].sort_by_time().dedup_last_wins()
    merged = recs[0]
    for r in recs[1:]:
        merged = merged.concat(r)
    return merged.sort_by_time().dedup_last_wins()

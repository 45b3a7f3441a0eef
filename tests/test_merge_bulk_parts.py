"""`record.merge_bulk_parts` against a plain reference kept here: put every
row of every part in one Python list, sort it by (sid, time, part rank),
keep the newest row of each (sid, time) whole, cut to the range.  The
reference reads the parts' arrays and nothing of `record.py`'s helpers; the
comparison is bit for bit — dtypes, the bytes under invalid slots and the
column set included — and each case also names the branch the merge has to
take over its TRIMMED parts."""

import numpy as np
import pytest

from opengemini_tpu.record import (
    Column, FieldType, Record, merge_bulk_parts,
)

F, I, B, S = (FieldType.FLOAT, FieldType.INT, FieldType.BOOL,
              FieldType.STRING)
ALL = (-(2**63), 2**63 - 1)


# -- the plain reference ------------------------------------------------------


def reference(parts, lo, hi):
    ftypes, rows = {}, []
    for rank, (sids, rec) in enumerate(parts):
        if not len(rec.times):
            continue
        for name, col in rec.columns.items():
            ftypes.setdefault(name, col.ftype)
        cells = {name: (col.values, col.valid)
                 for name, col in rec.columns.items()}
        for i in range(len(rec.times)):
            rows.append((int(sids[i]), int(rec.times[i]), rank,
                         {n: (v[i], bool(ok[i]))
                          for n, (v, ok) in cells.items()}))
    rows.sort(key=lambda row: row[:3])      # stable: a part keeps its order
    newest = {}
    for row in rows:                        # the last of a pair is its newest
        newest[row[:2]] = row
    kept = [row for row in newest.values() if lo <= row[1] < hi]
    kept.sort(key=lambda row: row[:2])
    out = {}
    for name, ftype in ftypes.items():
        values = (np.full(len(kept), None, dtype=object) if ftype == S
                  else np.zeros(len(kept), dtype=ftype.np_dtype))
        valid = np.zeros(len(kept), dtype=np.bool_)
        for at, row in enumerate(kept):
            if name in row[3]:
                values[at], valid[at] = row[3][name]
        out[name] = (ftype, values, valid)
    return (np.array([row[0] for row in kept], dtype=np.int64),
            np.array([row[1] for row in kept], dtype=np.int64), out)


def same_bits(got: np.ndarray, want: np.ndarray):
    assert got.dtype == want.dtype and got.shape == want.shape
    if want.dtype == object:
        assert got.tolist() == want.tolist()
    else:       # NaN payloads and the sign of zero too
        assert np.ascontiguousarray(got).tobytes() == want.tobytes()


# -- the parts ----------------------------------------------------------------


def column(rng, ftype, n, holes=True):
    """Values with garbage under the invalid slots: what a part carries
    there has to come out as it went in."""
    if ftype == F:
        values = rng.normal(size=n).round(3)
        values[rng.random(n) < 0.05] = np.nan
    elif ftype == I:
        values = rng.integers(-2**40, 2**40, n)
    elif ftype == B:
        values = rng.random(n) < 0.5
    else:
        values = np.array([f"s{k}" for k in rng.integers(0, 9, n)],
                          dtype=object)
    valid = rng.random(n) < 0.8 if holes else np.ones(n, np.bool_)
    return Column(ftype, np.asarray(values, dtype=ftype.np_dtype), valid)


def part(rng, sids, times, fields):
    sids = np.asarray(sids, dtype=np.int64)
    times = np.asarray(times, dtype=np.int64)
    return sids, Record(times, {name: column(rng, ftype, len(times))
                                for name, ftype in fields.items()})


def packed(rng, sid_lo, sid_hi, t_lo, t_hi, fields, step=10):
    """A packed chunk: series sid_lo..sid_hi-1, each all of t_lo..t_hi."""
    ticks = np.arange(t_lo, t_hi, step)
    n = sid_hi - sid_lo
    return part(rng, np.repeat(np.arange(sid_lo, sid_hi), len(ticks)),
                np.tile(ticks, n), fields)


TWO = {"usage_user": F, "usage_system": F}
MIX = {"f": F, "i": I, "b": B, "s": S}


def _packed_files(rng, fields=TWO):
    """Three files of packed chunks, series-major, every chunk all of
    0..600: what a compacted store's bulk read hands the merge."""
    return [packed(rng, lo, lo + 4, 0, 600, fields) for lo in (0, 4, 8)]


def _seam(rng, fields=TWO):
    """Two files that overlap in sids 3 and 4 at their seam: the first has
    their rows before 300, the second those from 300 on."""
    a = packed(rng, 0, 3, 0, 600, fields)
    a_seam = packed(rng, 3, 5, 0, 300, fields)
    b_seam = packed(rng, 3, 5, 300, 600, fields)
    b = packed(rng, 5, 8, 0, 600, fields)

    def join(x, y):     # one file's two chunks as one part
        return (
            np.concatenate([x[0], y[0]]),
            Record(np.concatenate([x[1].times, y[1].times]),
                   {k: Column(c.ftype,
                              np.concatenate([c.values,
                                              y[1].columns[k].values]),
                              np.concatenate([c.valid,
                                              y[1].columns[k].valid]))
                    for k, c in x[1].columns.items()}))

    return [join(a, a_seam), join(b_seam, b)]


def _segmented(rng, n_seg, fields=TWO, spans=((0, 4), (4, 8)), t0=0):
    """One file whose long series the writer cut along time: each sid
    span as `n_seg` packed chunks in ascending, disjoint times, span
    after span — [A, seg 0][A, seg 1]...[B, seg 0]...  Sid 0 has rows in
    its first segment only (a series that stopped reporting)."""
    edge = [t0 + 600 * j // n_seg // 10 * 10 for j in range(n_seg + 1)]
    out = []
    for lo, hi in spans:
        for j, (a, b) in enumerate(zip(edge, edge[1:])):
            out.append(packed(rng, lo + (j > 0 and lo == 0), hi, a, b,
                              fields))
    return out


def _memtable_on_top(rng, fields=TWO, again=()):
    """A segmented file and, newest, the memtable's consolidated part:
    (sid, time)-sorted rows of sids 2..5 after the file's, and those of
    `again`, (sid, time) pairs the file holds too."""
    rows = sorted([(sid, t) for sid in range(2, 6)
                   for t in range(600, 650, 10)] + list(again))
    return _segmented(rng, 3, fields) + [
        part(rng, [r[0] for r in rows], [r[1] for r in rows], fields)]


def _duplicates(rng):
    """The same (sid, time) pairs in three parts with different column
    sets: the newest row wins WHOLE, so a column only the older part
    carries comes out invalid there."""
    return [packed(rng, 0, 3, 0, 100, {"a": F, "b": I}),
            packed(rng, 1, 4, 50, 150, {"b": I, "c": S}),
            packed(rng, 0, 2, 90, 120, {"a": F, "d": B})]


def _outside_carries_a_column(rng):
    return [packed(rng, 0, 3, 0, 100, {"a": F}),
            packed(rng, 0, 3, 500, 600, {"a": F, "only_here": I})]


def _single_sid(rng, fields=TWO, overlap=False):
    """Per-series chunks, two a series, the files not in sid order."""
    out = []
    for sid in (4, 1, 3):
        out.append(part(rng, [sid] * 30, np.arange(0, 300, 10), fields))
    for sid in (1, 4, 3):
        lo = 250 if overlap else 300
        out.append(part(rng, [sid] * 30, np.arange(lo, lo + 300, 10), fields))
    return out


def _memtable_slab(rng):
    """One part in arrival order: sids interleaved, one pair twice."""
    sids = [2, 1, 2, 1, 3, 2, 1]
    times = [10, 10, 20, 20, 10, 10, 30]
    return [part(rng, sids, times, MIX)]


def _wide_keys(rng):
    """sid span x time span past 2^63: no single int64 sort key."""
    return [part(rng, [0, 0, 2**40, 2**40], [5, 2**41, 5, 7], TWO),
            part(rng, [0, 1, 2**40], [-2**41, 3, 5], TWO)]


CASES = {
    # name: (parts, (lo, hi), branch)
    "packed_parts_straddle_the_range":
        (lambda r: _packed_files(r), (200, 300), "inorder"),
    "packed_parts_wholly_inside":
        (lambda r: _packed_files(r), (0, 600), "inorder"),
    "packed_parts_every_type":
        (lambda r: _packed_files(r, MIX), (100, 450), "inorder"),
    "seam_overlap_cut_away_by_the_range":
        (lambda r: _seam(r), (300, 400), "inorder"),
    "seam_overlap_inside_the_range":
        (lambda r: _seam(r, MIX), (250, 350), "interleaved"),
    "seam_overlap_unbounded":
        (lambda r: _seam(r), ALL, "interleaved"),
    "duplicates_newest_row_wins_whole":
        (lambda r: _duplicates(r), (0, 200), "sorted"),
    "duplicates_unbounded":
        (lambda r: _duplicates(r), ALL, "sorted"),
    "duplicates_trimmed_away":
        (lambda r: _duplicates(r), (0, 50), "inorder"),
    "a_part_outside_alone_carries_a_column":
        (lambda r: _outside_carries_a_column(r), (0, 200), "inorder"),
    "single_sid_parts":
        (lambda r: _single_sid(r), (100, 500), "single_sid"),
    "single_sid_parts_every_type":
        (lambda r: _single_sid(r, MIX), ALL, "single_sid"),
    "single_sid_parts_overlapping":
        (lambda r: _single_sid(r, overlap=True), ALL, "sorted"),
    "single_sid_parts_in_sid_order":
        (lambda r: sorted(_single_sid(r), key=lambda p: int(p[0][0])),
         (0, 1000), "inorder"),
    "one_part":
        (lambda r: _packed_files(r)[:1], (100, 200), "inorder"),
    "one_part_unbounded":
        (lambda r: _packed_files(r, MIX)[:1], ALL, "inorder"),
    "one_part_in_arrival_order":
        (lambda r: _memtable_slab(r), ALL, "sorted"),
    "one_part_in_arrival_order_cut":
        (lambda r: _memtable_slab(r), (10, 20), "sorted"),
    "no_parts":
        (lambda r: [], ALL, "inorder"),
    "only_empty_parts":
        (lambda r: [part(r, [], [], TWO)], ALL, "inorder"),
    "an_empty_part_among_others":
        (lambda r: [_packed_files(r)[0], part(r, [], [], {"gone": I}),
                    _packed_files(r)[1]], (0, 600), "inorder"),
    "nothing_in_the_range":
        (lambda r: _seam(r), (1000, 2000), "inorder"),
    "keys_too_wide_for_one_sort_key":
        (lambda r: _wide_keys(r), ALL, "sorted"),
    "negative_times":
        (lambda r: [packed(r, 0, 3, -300, 300, TWO),
                    packed(r, 2, 5, -100, 100, TWO)], (-200, 50), "sorted"),
    "packed_parts_float_and_int_wholly_inside":
        (lambda r: _packed_files(r, {"f": F, "i": I}), (0, 600), "inorder"),
    "packed_parts_float_and_int_straddle":
        (lambda r: _packed_files(r, {"f": F, "i": I}), (200, 300),
         "inorder"),
    "packed_parts_one_field_straddle":
        (lambda r: _packed_files(r, {"f": F}), (200, 300), "inorder"),
    "packed_parts_one_field_wholly_inside":
        (lambda r: _packed_files(r, {"f": F}), (0, 600), "inorder"),
    "single_sid_parts_float_and_int":
        (lambda r: _single_sid(r, {"f": F, "i": I}), (100, 500),
         "single_sid"),
    "one_int_part_trimmed":
        (lambda r: _packed_files(r, {"i": I})[:1], (100, 200), "inorder"),
    "seam_overlap_float_and_int_unbounded":
        (lambda r: _seam(r, {"f": F, "i": I}), ALL, "interleaved"),
    "packed_parts_overlapping_in_sids_and_times":
        (lambda r: [packed(r, 0, 3, 0, 100, {"f": F, "i": I}),
                    packed(r, 1, 4, 50, 150, {"f": F, "i": I})],
         ALL, "sorted"),
    "packed_parts_of_two_draws":
        (lambda r: _packed_files(r)[:2] + _packed_files(r)[2:],
         (0, 600), "inorder"),
    # a file's long series cut into time segments (and files like it)
    **{f"segments_{n}_whole_range":
       (lambda r, n=n: _segmented(r, n), ALL, "interleaved")
       for n in (2, 3, 8)},
    "segments_every_type_whole_range":
        (lambda r: _segmented(r, 3, MIX), (0, 600), "interleaved"),
    "segments_range_inside_one_segment":
        (lambda r: _segmented(r, 3), (210, 390), "inorder"),
    "segments_range_crosses_a_boundary":
        (lambda r: _segmented(r, 3, MIX), (150, 250), "interleaved"),
    "segments_range_crosses_two_boundaries":
        (lambda r: _segmented(r, 8), (100, 300), "interleaved"),
    "segments_of_two_files_one_after_the_other":
        (lambda r: _segmented(r, 3) + _segmented(r, 2, t0=600), ALL,
         "interleaved"),
    "segments_of_two_files_duplicates_across_files":
        (lambda r: _segmented(r, 3) + _segmented(r, 2), ALL, "sorted"),
    "segments_of_two_files_duplicates_cut_away":
        (lambda r: _segmented(r, 3) + _segmented(r, 3, t0=200), (0, 200),
         "inorder"),
    "segments_where_a_span_lacks_a_column":
        (lambda r: _segmented(r, 3, {"f": F}, spans=((0, 4),))
         + _segmented(r, 3, {"f": F, "g": I}, spans=((4, 8),)), ALL,
         "interleaved"),
    "segments_with_a_memtable_part_on_top":
        (lambda r: _memtable_on_top(r, MIX), ALL, "interleaved"),
    "segments_with_a_memtable_part_that_rewrites_a_row":
        (lambda r: _memtable_on_top(r, again=[(3, 590), (4, 0)]), ALL,
         "sorted"),
    "segments_with_a_memtable_part_range_in_the_file":
        (lambda r: _memtable_on_top(r), (0, 150), "inorder"),
    "segments_float_and_int_whole_range":
        (lambda r: _segmented(r, 3, {"f": F, "i": I}), ALL, "interleaved"),
    "segments_one_field_range_crosses_two_boundaries":
        (lambda r: _segmented(r, 3, {"f": F}), (150, 450), "interleaved"),
    "segments_float_and_int_inside_one_segment":
        (lambda r: _segmented(r, 3, {"f": F, "i": I}), (210, 390),
         "inorder"),
    "packed_parts_where_one_lacks_a_column":
        (lambda r: [packed(r, 0, 2, 0, 100, {"f": F}),
                    packed(r, 2, 4, 0, 100, {"f": F, "g": F})],
         ALL, "inorder"),
}


@pytest.mark.parametrize("name", CASES)
def test_the_merge_is_the_references_bit_for_bit(name):
    build, (lo, hi), branch = CASES[name]
    parts = build(np.random.default_rng(43))
    told = {}
    sid, rec = merge_bulk_parts(parts, lo, hi, told)
    want_sid, want_t, want_cols = reference(parts, lo, hi)

    same_bits(sid, want_sid)
    same_bits(rec.times, want_t)
    assert list(rec.columns) == list(want_cols)
    assert told["branch"] == branch
    # rows that entered the join or sort: those in range, pairs counted twice
    assert told["rows"] == sum(
        int(((r.times >= lo) & (r.times < hi)).sum()) for _s, r in parts)
    for col_name, (ftype, values, valid) in want_cols.items():
        col = rec.columns[col_name]
        assert type(col) is Column, col_name
        assert col.ftype == ftype
        same_bits(col.valid, valid)
        same_bits(col.values, values)
        assert len(col) == len(want_sid)


@pytest.mark.parametrize(
    "name", [n for n, case in CASES.items() if case[2] == "interleaved"])
def test_interleaving_gives_what_the_general_merge_gives(name, monkeypatch):
    """The same parts through `sorted` (the branch they took before there
    was `interleaved`): the same bits, dtypes and column order."""
    from opengemini_tpu import record

    build, (lo, hi), _branch = CASES[name]
    told = {}
    sid, rec = merge_bulk_parts(build(np.random.default_rng(43)), lo, hi,
                                told)
    assert told["branch"] == "interleaved"
    monkeypatch.setattr(record, "_interleave", lambda *a: None)
    want_sid, want = merge_bulk_parts(build(np.random.default_rng(43)),
                                      lo, hi, told)
    assert told["branch"] == "sorted"
    same_bits(sid, want_sid)
    same_bits(rec.times, want.times)
    assert list(rec.columns) == list(want.columns)
    for col_name, col in want.columns.items():
        assert type(rec.columns[col_name]) is type(col) is Column
        assert rec.columns[col_name].ftype == col.ftype
        same_bits(rec.columns[col_name].values, col.values)
        same_bits(rec.columns[col_name].valid, col.valid)


def test_interleaving_builds_every_output_column_once(monkeypatch):
    """Not a timing: the arrays of the answer are the only row-long
    arrays of a column's type that the branch makes — no concatenation
    of the parts that a gather then reads."""
    from opengemini_tpu import record

    parts = _segmented(np.random.default_rng(5), 8, {"f": F, "g": F})
    made = []
    real = record._zeroed

    def counted(ftype, n):
        made.append(real(ftype, n))
        return made[-1]

    monkeypatch.setattr(record, "_zeroed", counted)
    told = {}
    _sid, rec = merge_bulk_parts(parts, *ALL, told)
    assert told["branch"] == "interleaved"
    assert [id(a) for a in made] == [id(c.values)
                                     for c in rec.columns.values()]


def test_a_part_wholly_inside_is_handed_on_as_it_is():
    """One comparison pass and no copy: what the hot cell's parts, a
    whole-range read and the memtable's unbounded calls pay."""
    (sids, rec), = _packed_files(np.random.default_rng(1), MIX)[:1]
    for lo, hi in (ALL, (0, 600)):
        out_sid, out = merge_bulk_parts([(sids, rec)], lo, hi)
        assert out_sid is sids and out.times is rec.times
        for name, col in rec.columns.items():
            assert out.columns[name] is col


# -- parts as the reader hands them -------------------------------------------

NS, T0, STEP_S = 10**9, 1_700_000_000, 10
SERIES, TICKS = 64, 420


def _lines(series, ticks, salt=0) -> str:
    """Line protocol of every type, series after series; a tenth of the
    rows leave `f` out, another tenth `s`."""
    out = []
    for s in series:
        rng = np.random.default_rng(s * 7919 + salt)
        f = rng.normal(size=TICKS).round(3).tolist()
        i = rng.integers(-2**40, 2**40, TICKS).tolist()
        gone = rng.integers(0, 10, TICKS).tolist()
        for k in ticks:
            fields = [f"i={i[k]}i", f"b={'true' if i[k] % 2 else 'false'}"]
            if gone[k] != 0:
                fields.append(f"f={f[k]!r}")
            if gone[k] != 1:
                fields.append(f's="s{i[k] % 9}"')
            out.append(f"cpu,host=h{s:03d} {','.join(fields)} "
                       f"{(T0 + k * STEP_S) * NS}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """{"cut": a shard whose one file holds 64 series x 420 rows as three
    sid spans of two time segments and a tail (the chunk writer's cut, PR
    46, with `tsf.PACK_ROWS` lowered to this size), "rewritten": the same
    and a second file, a chunk a series, that writes a stretch of every
    third series anew}."""
    from opengemini_tpu.storage import colcache, tsf
    from opengemini_tpu.storage.engine import Engine

    cache = colcache.GLOBAL.config()
    colcache.GLOBAL.configure(budget_mb=0)
    engines, shards = [], {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tsf, "PACK_ROWS", 4096)
        for name in ("cut", "rewritten"):
            e = Engine(str(tmp_path_factory.mktemp(name)))
            e.create_database("db")
            e.write_lines("db", _lines(range(SERIES), range(TICKS)))
            e.flush_all()
            if name == "rewritten":
                e.write_lines("db", _lines(range(0, SERIES, 3),
                                           range(150, 270), salt=1))
                e.flush_all()
            engines.append(e)
            (shards[name],) = e.shards_for_range(
                "db", None, T0 * NS, (T0 + TICKS * STEP_S) * NS)
    yield shards
    for e in engines:
        e.close()
    colcache.GLOBAL.configure(**cache)


def reader_parts(sh, lo, hi, fields=None, every=1):
    """[(sids, record)] as `Shard.read_series_bulk` gathers them: the
    chunks the reader's pruning leaves, in file order, a packed one
    through `read_packed_bulk`, a series' own through `read_chunk`."""
    sids = np.array(sorted(sh.index.series_ids("cpu"))[::every], np.int64)
    parts = []
    for r in sh._files:
        for c in r.chunks("cpu", set(sids.tolist()), lo, hi):
            if c.packed:
                s_arr, rec = r.read_packed_bulk(
                    "cpu", c, fields, sid_filter=sids, cache=False)
            else:
                rec = r.read_chunk("cpu", c, fields, cache=False)
                s_arr = np.full(len(rec), c.sid, np.int64)
            if len(rec):
                parts.append((s_arr, rec))
    return sids, parts


def _t(k: int) -> int:
    return (T0 + k * STEP_S) * NS


READER_CASES = {
    # name: (store, (first tick, end tick) or None, fields, every nth
    #        series, branch, parts)
    "whole_range": ("cut", None, None, 1, "interleaved", 7),
    "inside_one_segment": ("cut", (20, 150), None, 1, "inorder", 4),
    "across_the_cut": ("cut", (100, 330), None, 1, "interleaved", 7),
    "one_tick": ("cut", (209, 210), None, 1, "inorder", 4),
    "every_third_series": ("cut", None, None, 3, "interleaved", 7),
    "one_field_of_four": ("cut", (5, 415), ["i"], 1, "interleaved", 7),
    "rewritten_rows_newest_wins": ("rewritten", None, None, 1, "sorted",
                                   7 + 22),
    "rewritten_rows_cut_away": ("rewritten", (270, 420), None, 1,
                                "inorder", 4),
}


@pytest.mark.parametrize("name", READER_CASES)
def test_parts_from_the_reader_merge_as_the_reference_merges(written, name):
    """The parts are what `read_packed_bulk` decodes from a segment-cut
    file, not columns built by hand; the merge of them is the row-by-row
    reference's, bit for bit, and what `read_series_bulk` answers."""
    store, ticks, fields, every, branch, n_parts = READER_CASES[name]
    sh = written[store]
    lo, hi = (None, None) if ticks is None else (_t(ticks[0]), _t(ticks[1]))
    sids, parts = reader_parts(sh, lo, hi, fields, every)
    assert len(parts) == n_parts
    assert all(type(c) is Column for _s, r in parts
               for c in r.columns.values())
    lo_t, hi_t = ALL if ticks is None else (lo, hi)
    told = {}
    sid, rec = merge_bulk_parts(parts, lo_t, hi_t, told)
    assert told["branch"] == branch
    want_sid, want_t, want_cols = reference(parts, lo_t, hi_t)
    rows = len(sids) * (TICKS if ticks is None else ticks[1] - ticks[0])
    assert len(want_sid) == rows
    same_bits(sid, want_sid)
    same_bits(rec.times, want_t)
    assert list(rec.columns) == list(want_cols)
    for col_name, (ftype, values, valid) in want_cols.items():
        col = rec.columns[col_name]
        assert type(col) is Column and col.ftype == ftype
        same_bits(col.valid, valid)
        same_bits(col.values, values)
    got_sid, got = sh.read_series_bulk("cpu", sids, lo, hi, fields)
    same_bits(got_sid, sid)
    same_bits(got.times, rec.times)
    for col_name, col in rec.columns.items():
        same_bits(got.columns[col_name].valid, col.valid)
        same_bits(got.columns[col_name].values, col.values)

"""Time-segmented packed chunks (PR 46), at the writer's real constants: a
shard of 256 series x 2,160 rows (six hours at a 10 s step, the shape of
`tsbs-devops-cpu-4000-6h`) written three ways — cut along time as the
chunk writer now cuts long series, whole as it wrote them before
(`tsf.SEGMENT_ROWS` out of reach: byte for byte PR 45's file), and in time
order.  What has to hold whatever the layout: the rows read back, a
series read alone, the digest, the row accounting, last-write-wins; and
what the cut is for: a bulk read of one hour decodes under twice the rows
it keeps, after a flush and after every kind of compaction."""

import hashlib
import os

import numpy as np
import pytest

from opengemini_tpu.ingest import native_lp
from opengemini_tpu.record import FieldType
from opengemini_tpu.storage import colcache, tsf
from opengemini_tpu.storage.shard import Shard
from opengemini_tpu.storage.tsf import TSFReader
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 10**9
BASE = 1_700_000_000
STEP = 10
SERIES, ROWS, HOUR = 256, 2160, 360
SEGMENTS = 8            # round(2160 / 256): 270 rows a series each


def lines(series, rows, field="a", salt=0) -> bytes:
    """Line protocol, series after series: two float fields and the tag."""
    out = []
    for s in series:
        v = np.random.default_rng(s * 7919 + salt).normal(
            size=(max(rows) + 1, 2)).round(2)
        out.extend(
            f"cpu,host=h{s:04d} {field}={v[r, 0]},b={v[r, 1]} "
            f"{(BASE + r * STEP) * NS}" for r in rows)
    return "\n".join(out).encode()


def write(sh: Shard, body: bytes) -> None:
    sh.write_columnar(native_lp.parse_columnar(body, "ns", 0), None, body,
                      "ns", 0)


def open_shard(path) -> Shard:
    return Shard(str(path), BASE * NS - NS, (BASE + 10**7) * NS)


def load(path, layout: str) -> Shard:
    sh = open_shard(path)
    with pytest.MonkeyPatch.context() as mp:
        if layout == "uncut":
            mp.setattr(tsf, "SEGMENT_ROWS", 10**9)
        if layout == "time_ordered":
            for h in range(ROWS // HOUR):
                write(sh, lines(range(SERIES),
                                range(h * HOUR, (h + 1) * HOUR)))
                sh.flush()
        else:
            write(sh, lines(range(SERIES), range(ROWS)))
            sh.flush()
    return sh


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    before = colcache.GLOBAL.config()
    colcache.GLOBAL.configure(budget_mb=0)
    made = {layout: load(tmp_path_factory.mktemp(layout), layout)
            for layout in ("cut", "uncut", "time_ordered")}
    yield made
    for sh in made.values():
        sh.close()
    colcache.GLOBAL.configure(**before)


def chunks(sh: Shard) -> list:
    return [c for r in sh._files for c in r.chunks("cpu")]


def bulk(sh: Shard, lo=None, hi=None, fields=None):
    """(sids, record, what the scan counted) of one bulk read of every
    series over rows [lo, hi) of the six hours."""
    sids = np.array(sorted(sh.index.series_ids("cpu")), dtype=np.int64)
    before = STATS.counters("scan")
    sid_arr, rec = sh.read_series_bulk(
        "cpu", sids, None if lo is None else (BASE + lo * STEP) * NS,
        None if hi is None else (BASE + hi * STEP) * NS, fields)
    after = STATS.counters("scan")
    return sid_arr, rec, {k: after[k] - before.get(k, 0) for k in after}


def same(got, want) -> None:
    (g_sid, g), (w_sid, w) = got, want
    assert g_sid.tobytes() == w_sid.tobytes()
    assert g.times.tobytes() == w.times.tobytes()
    assert list(g.columns) == list(w.columns)
    for name, col in w.columns.items():
        assert g.columns[name].ftype == col.ftype
        assert g.columns[name].values.tobytes() == col.values.tobytes()
        assert g.columns[name].valid.tobytes() == col.valid.tobytes()


# -- the writer's rule --------------------------------------------------------


@pytest.mark.parametrize("rows, series, want", [
    (360 * 4000, 4000, 1),      # tsbs-devops-cpu-4000, -live, -mesh4
    (240 * 10000, 10000, 1),    # prom-counters-10k
    (250 * 4000, 4000, 1),      # a flush of tsbs_load
    (383 * 64, 64, 1), (384 * 64, 64, 2),
    (2160 * 250, 250, 8),       # tsbs-devops-cpu-4000-6h
    (5760 * 286, 286, 23),      # prom-counters-24h
    (100, 64, 1), (0, 0, 1),
])
def test_the_rule_reads_rows_a_series_and_nothing_else(rows, series, want):
    assert tsf.packed_segments(rows, series) == want


def test_long_series_are_cut_into_segments_of_their_own_time_range(shards):
    segs = chunks(shards["cut"])
    assert len(segs) == SEGMENTS and all(c.packed for c in segs)
    assert [c.rows for c in segs] == [SERIES * ROWS // SEGMENTS] * SEGMENTS
    assert all((c.smin, c.smax) == (segs[0].smin, segs[0].smax)
               for c in segs)
    step = ROWS // SEGMENTS * STEP
    assert [(c.tmin, c.tmax) for c in segs] == [
        ((BASE + j * step) * NS, (BASE + (j + 1) * step - STEP) * NS)
        for j in range(SEGMENTS)]
    whole = chunks(shards["uncut"])
    assert [c.rows for c in whole] == [61 * ROWS] * 4 + [12 * ROWS]
    assert all((c.tmin, c.tmax) == (segs[0].tmin, segs[-1].tmax)
               for c in whole)


def test_each_segment_carries_its_own_pre_aggregates_and_sparse_index(shards):
    sh = shards["cut"]
    r, = sh._files
    for c in r.chunks("cpu"):
        sids, rec = r.read_packed_bulk("cpu", c, cache=False)
        assert [list(e) for e in c.sparse] == [
            [int(sids[i]), i] for i in range(0, c.rows, tsf.SPARSE_K)]
        for name, col in rec.columns.items():
            pre = c.cols[name]["pre"]
            assert pre.count == c.rows
            assert pre.vmin == col.values.min()
            assert pre.vmax == col.values.max()
            assert pre.vsum == pytest.approx(col.values.sum())
            assert sum(pre.hist) == c.rows


def test_short_series_are_written_byte_for_byte_as_before(tmp_path):
    """360 rows a series (an hour at a 10 s step) stay whole: the file is
    the one a writer that never cuts leaves."""
    body = lines(range(400), range(HOUR))
    digests = {}
    for layout in ("cut", "uncut"):
        sh = open_shard(tmp_path / layout)
        with pytest.MonkeyPatch.context() as mp:
            if layout == "uncut":
                mp.setattr(tsf, "SEGMENT_ROWS", 10**9)
            before = STATS.counters("tsf")
            write(sh, body)
            sh.flush()
            after = STATS.counters("tsf")
        assert after.get("packed_buffers_cut", 0) \
            == before.get("packed_buffers_cut", 0)
        r, = sh._files
        assert [c.rows for c in r.chunks("cpu")] == [365 * HOUR, 35 * HOUR]
        with open(r.path, "rb") as f:
            digests[layout] = hashlib.sha256(f.read()).hexdigest()
        sh.close()
    assert digests["cut"] == digests["uncut"]


def test_the_writer_counts_the_buffers_it_cut(tmp_path):
    sh = open_shard(tmp_path / "s")
    before = STATS.counters("tsf")
    write(sh, lines(range(SERIES), range(ROWS)))
    sh.flush()
    after = STATS.counters("tsf")
    assert after["packed_buffers_cut"] \
        - before.get("packed_buffers_cut", 0) == 1
    assert after["packed_segments_written"] \
        - before.get("packed_segments_written", 0) == SEGMENTS
    snap = sh.ledger_snapshot()     # every row once, however cut
    assert snap["tsf_rows"] == snap["published"] == SERIES * ROWS
    assert snap["missing"] == 0
    sh.close()


# -- the reader prunes to the hour --------------------------------------------


@pytest.mark.parametrize("hour", range(ROWS // HOUR))
def test_an_hour_decodes_under_twice_what_it_keeps(shards, hour):
    lo, hi = hour * HOUR, (hour + 1) * HOUR
    want = bulk(shards["uncut"], lo, hi, ["a"])
    got = bulk(shards["cut"], lo, hi, ["a"])
    same(got[:2], want[:2])
    same(bulk(shards["time_ordered"], lo, hi, ["a"])[:2], want[:2])
    d, d0 = got[2], want[2]
    assert d0["rows_decoded"] == SERIES * ROWS and d0["merges_inorder"] == 1
    assert d0.get("packed_skipped_by_time", 0) == 0
    assert d["rows_kept"] == SERIES * HOUR
    assert d["rows_kept"] <= d["rows_decoded"] <= 1.5 * d["rows_kept"]
    assert d["packed_skipped_by_time"] == SEGMENTS - 2
    assert d["merges_interleaved"] == 1 and d.get("merges_sorted", 0) == 0
    assert d["rows_merged"] == d["rows_kept"]
    assert shards["cut"].approx_rows(
        "cpu", (BASE + lo * STEP) * NS, (BASE + hi * STEP) * NS) \
        == (d["rows_decoded"], 2)


def test_a_range_inside_one_segment_meets_it_alone_and_is_in_order(shards):
    got = bulk(shards["cut"], 280, 500)
    same(got[:2], bulk(shards["uncut"], 280, 500)[:2])
    assert got[2]["packed_skipped_by_time"] == SEGMENTS - 1
    assert got[2]["merges_inorder"] == 1
    assert got[2]["rows_decoded"] == SERIES * ROWS // SEGMENTS


def test_a_whole_range_read_interleaves_every_segment(shards):
    want = bulk(shards["uncut"])
    got = bulk(shards["cut"])
    same(got[:2], want[:2])
    assert got[2]["merges_interleaved"] == 1
    assert got[2].get("merges_sorted", 0) == 0
    assert got[2].get("packed_skipped_by_time", 0) == 0
    assert got[2]["rows_merged"] == got[2]["rows_decoded"] == SERIES * ROWS


# -- a series' rows lie in several chunks of one file -------------------------


@pytest.mark.parametrize("rng_rows", [None, (0, 100), (200, 300),
                                      (269, 271), (500, 1700)])
def test_read_series_joins_the_segments_oldest_first(shards, rng_rows):
    lo, hi = (None, None) if rng_rows is None else (
        (BASE + rng_rows[0] * STEP) * NS, (BASE + rng_rows[1] * STEP) * NS)
    for sid in sorted(shards["uncut"].index.series_ids("cpu"))[::51]:
        want = shards["uncut"].read_series("cpu", sid, lo, hi)
        assert len(want) == (ROWS if rng_rows is None
                             else rng_rows[1] - rng_rows[0])
        for layout in ("cut", "time_ordered"):
            got = shards[layout].read_series("cpu", sid, lo, hi, ["a", "b"])
            zero = np.zeros(len(want), np.int64)
            same((zero, got), (zero, want))


def test_read_packed_sid_gives_the_series_stretch_in_each_segment(shards):
    sh = shards["cut"]
    r, = sh._files
    sid = sorted(sh.index.series_ids("cpu"))[7]
    whole = shards["uncut"].read_series("cpu", sid)
    at = 0
    for c in r.chunks("cpu", {sid}):
        rec = r.read_packed_sid("cpu", c, sid, cache=False)
        assert len(rec) == ROWS // SEGMENTS
        assert (rec.times == whole.times[at:at + len(rec)]).all()
        assert (rec.columns["b"].values
                == whole.columns["b"].values[at:at + len(rec)]).all()
        at += len(rec)
    assert at == ROWS
    # pruned by time like any chunk, and outside the span nothing is read
    assert len(r.chunks("cpu", {sid}, (BASE + 300 * STEP) * NS,
                        (BASE + 500 * STEP) * NS)) == 1
    c = r.chunks("cpu")[0]
    assert len(r.read_packed_sid("cpu", c, c.smax + 1)) == 0


def test_the_digest_and_the_counts_do_not_depend_on_the_layout(shards):
    digests = {k: sh.content_digest() for k, sh in shards.items()}
    assert digests["cut"] == digests["uncut"] == digests["time_ordered"]
    assert digests["cut"]["cpu"][0] == SERIES * ROWS
    for sh in shards.values():
        assert sh.approx_rows("cpu")[0] == SERIES * ROWS


# -- last write wins, across files and the memtable ---------------------------


def test_newer_files_and_the_memtable_win_over_segments(tmp_path):
    """A second file rewrites an hour of half the series (its rows are
    short: whole chunks), the memtable a few rows more: the bulk read
    takes the general merge and equals the same writes over uncut
    files; so does every series read alone."""
    made = {}
    for layout in ("cut", "uncut"):
        sh = open_shard(tmp_path / layout)
        with pytest.MonkeyPatch.context() as mp:
            if layout == "uncut":
                mp.setattr(tsf, "SEGMENT_ROWS", 10**9)
            write(sh, lines(range(128), range(ROWS)))
            sh.flush()
            write(sh, lines(range(64, 128), range(300, 660), salt=1))
            sh.flush()
        write(sh, lines(range(100, 110), range(600, 700), salt=2))
        write(sh, lines(range(5), range(ROWS, ROWS + 10), salt=2))
        made[layout] = sh
    assert len(chunks(made["cut"])) == SEGMENTS + 1
    want, got = bulk(made["uncut"]), bulk(made["cut"])
    same(got[:2], want[:2])
    assert len(got[1]) == 128 * ROWS + 50
    assert got[2]["merges_sorted"] == 1
    for lo, hi in ((0, 300), (250, 700), (ROWS - 5, ROWS + 5)):
        same(bulk(made["cut"], lo, hi)[:2], bulk(made["uncut"], lo, hi)[:2])
    for sid in sorted(made["cut"].index.series_ids("cpu"))[::9]:
        a = made["cut"].read_series("cpu", sid)
        b = made["uncut"].read_series("cpu", sid)
        zero = np.zeros(len(a), np.int64)
        same((zero, a), (zero, b))
    assert made["cut"].content_digest() == made["uncut"].content_digest()
    for sh in made.values():
        sh.close()


# -- the text sidecar: once a series a file -----------------------------------


def test_the_text_sidecar_lists_a_series_once_however_cut(tmp_path):
    sh = open_shard(tmp_path / "s")
    body = "\n".join(
        f'log,host=h{s:03d} msg="{"needle" if s % 7 == 0 else "hay"} '
        f'r{r}" {(BASE + r * STEP) * NS}'
        for s in range(64) for r in range(1024)).encode()
    write(sh, body)
    sh.flush()
    r, = sh._files
    assert len(r.chunks("log")) == 2        # 65,536 rows: 4 asked, 2 fit
    assert all(c.packed for c in r.chunks("log"))
    hits = sh.text_match_sids("log", "msg", "needle")
    assert len(hits) == 10
    rec = sh.read_series("log", sorted(hits)[0])
    assert len(rec) == 1024 and rec.columns["msg"].ftype == FieldType.STRING
    assert all("needle" in v for v in rec.columns["msg"].values)
    sh.close()


# -- a file written before this PR --------------------------------------------


def test_an_uncut_file_reopens_and_answers_the_same(shards, tmp_path):
    """No format revision: the chunks of a file written by a writer that
    never cuts (PR 45's bytes) and those of a cut one are the same kind
    of entry in the same meta; both reopen from disk."""
    for layout in ("uncut", "cut"):
        src, = shards[layout]._files
        with open(src.path, "rb") as f:
            assert f.read(8) == tsf.MAGIC2
        r = TSFReader(src.path)
        assert [(c.rows, c.tmin, c.tmax, c.smin, c.smax)
                for c in r.chunks("cpu")] \
            == [(c.rows, c.tmin, c.tmax, c.smin, c.smax)
                for c in src.chunks("cpu")]
        r.close()
    assert os.path.getsize(shards["cut"]._files[0].path) \
        < 1.05 * os.path.getsize(shards["uncut"]._files[0].path)

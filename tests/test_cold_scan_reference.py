"""The deployment whose data does not fit the column cache
(`benchmark/configs/tsbs-devops-cpu-4000-6h.json`, cell
`tsbs_fleet_groupby_cold`), small, on the CPU, through the served /query
path: 512 hosts, six "hours" of 600 s, the cell's own statements from the
benchmark's generator, data and expected answers from the plain reference
`benchmark/configs/tsbs_cpu_only.py` on a seed.

What the cell is defined around is held here.  The store is loaded series
after series (what compaction leaves), and its series are long: the chunk
writer cuts them along time (PR 46), so a statement over one hour decodes
the two or three segments the hour meets, under twice what it keeps, and a
cache smaller than one statement's decode hits only in the segment two
neighbouring hours share.  The writer's constants are scaled down with the
data (360 rows a series here, 2,160 in the cell), so the rule engages as it
does there.  The same files written before PR 46 (`uncut`: every chunk all
six hours long) decode six hours to keep one; the same rows loaded in time
order decode under twice what they keep; a compacted shard keeps the
segments; and the answer is the reference's, bit for bit the same,
whatever the layout and whatever the cache does.  The miss path's spans and
counters (PR 27) are read as the benchmark's metric files read them."""

import json
import os
import sys
import urllib.parse
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import load_module, traffic  # noqa: E402
from harness.metrics import counter  # noqa: E402
from harness.oracle import TOL  # noqa: E402

from opengemini_tpu.server.http import HttpService  # noqa: E402
from opengemini_tpu.storage import colcache, scanpool, tsf  # noqa: E402
from opengemini_tpu.storage.engine import Engine  # noqa: E402
from opengemini_tpu.utils.stats import GLOBAL as STATS  # noqa: E402

HOSTS, HOUR, HOURS, TICKS_AN_HOUR = 512, 600, 6, 60
FILES = HOSTS // 64             # of the series-major load
SEED = 27
# The writer's constants as the series-major store is written, in the
# proportion of the data to the cell's (360 rows a series for 2,160): a
# file of 64 hosts x 360 rows is one buffer, cut into round(360 / 32) = 11
# segments of 32 or 33 rows a series, and an hour's 60 rows meet 2 or 3.
CUT = {"SEGMENT_ROWS": 32, "PACK_ROWS": 8192}
SEGMENTS = 11
LAYOUTS = ("series_major", "uncut", "time_ordered", "compacted")
# One statement decodes 512 hosts x 65-98 rows x (5 fields x 9 B + times +
# sids) = 2-3 MB in 16-24 chunks of 7 columns (11 MB in 8 chunks where the
# files are uncut).  A 1 MB cache holds a third of that: with two workers
# going through the chunks in order, what a statement leaves behind (of its
# last chunks) is mostly evicted when the next one comes to look for it, as
# in the cell.  64 MB hold everything.
REGIMES = {"off": 0, "evicting": 1, "roomy": 64}
MISS_SPANS = ("decode", "pool_wait", "block_read", "codec", "colcache_fill",
              "scan_merge")


def _json(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    cfg = _json("configs", "tsbs-devops-cpu-4000-6h.json")
    cfg.update(hosts=HOSTS, span_s=HOUR * HOURS,
               load_block={"series": 64, "ticks": HOURS * TICKS_AN_HOUR})
    mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                      "reference")
    return mod.Reference(cfg, SEED)


@pytest.fixture(scope="module")
def statements(ref):
    """Two rounds of the six hours, as the cell sends them: the touches,
    then seed-drawn fields, the n-th statement at hour n mod 6."""
    mix = _json("traffic", "fleet_groupby_cold.json")
    mix.update(range_s=HOUR, every_s=50)        # 12 windows a statement
    plan = traffic.build(mix, ref, SEED, 1.0)
    assert plan.cycle == HOURS
    sent = (plan.warm_touch + plan.warm_repeat)[:2 * HOURS]
    assert [(q.stmt["t0"] - ref.start_s) // HOUR for q in sent] \
        == list(range(HOURS)) * 2
    return sent


class Served:
    """One server over one store; `layout` is the order of the load and
    what wrote the files."""

    def __init__(self, path, ref, layout: str):
        self.engine = Engine(str(path))
        self.engine.create_database(ref.db)
        self.svc = HttpService(self.engine, "127.0.0.1", 0)
        self.svc.start()
        bodies = (ref.stream_requests(HOSTS * TICKS_AN_HOUR)
                  if layout == "time_ordered" else ref.load_requests())
        with pytest.MonkeyPatch.context() as mp:
            if layout == "uncut":       # a writer that never cuts: PR 45's
                mp.setattr(tsf, "SEGMENT_ROWS", 10 ** 9)
            elif layout != "time_ordered":
                for name, value in CUT.items():
                    mp.setattr(tsf, name, value)
            for body, _rows in bodies:  # a file a request, as a flush leaves
                assert self.http("POST", "/write", body, db=ref.db)[0] == 204
                self.http("POST", "/debug/ctrl", mod="flush")
            if layout == "compacted":
                for sh in self.engine.all_shards():
                    assert sh.compact() and sh.file_count() == 1

    def http(self, method, path, body=None, **params):
        url = f"http://127.0.0.1:{self.svc.port}{path}"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        req = urllib.request.Request(url, data=body, method=method)
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, r.read()

    def ask(self, req) -> bytes:
        status, body = self.http(req.method, req.path, req.body)
        assert status == 200
        return body

    def vars(self) -> dict:
        return json.loads(self.http("GET", "/debug/vars")[1])

    def chunks(self) -> list:
        return [(r, c) for sh in self.engine.all_shards()
                for r in sh._files for c in r.chunks("cpu")]

    def close(self):
        self.svc.stop()
        self.engine.close()


@pytest.fixture(scope="module")
def stores(tmp_path_factory, ref):
    """The same rows four times: series-major (8 files of 64 hosts, each
    cut into 11 time segments), the same load as PR 45 wrote it (`uncut`:
    every packed chunk all six hours long), in time order (6 files, an
    hour each) and series-major, then compacted into one file."""
    with pytest.MonkeyPatch.context() as mp:
        # a scan pool of two workers, whatever the machine's cores and
        # whatever pool an earlier test of this process left behind
        mp.setattr(scanpool, "WORKERS", 2)
        mp.setattr(scanpool, "_pool", None)
        before = colcache.GLOBAL.config()
        made = {layout: Served(tmp_path_factory.mktemp(layout), ref, layout)
                for layout in LAYOUTS}
        yield made
        for s in made.values():
            s.close()
        colcache.GLOBAL.configure(**before)
        if scanpool._pool is not None:
            scanpool._pool.shutdown(wait=True)


def run(srv: Served, statements, budget_mb: int):
    """Every statement once, from an empty cache of `budget_mb`: the
    bodies, and /debug/vars before the first and after each.  A server of
    the cell is never asked a statement twice; one here is, a regime
    later, and must scan again: its result cache is emptied too."""
    colcache.GLOBAL.configure(budget_mb=budget_mb)
    colcache.GLOBAL.clear()
    srv.svc.executor._inc_cache.clear()
    seen = [srv.vars()]
    bodies = []
    for req in statements:
        bodies.append(srv.ask(req))
        seen.append(srv.vars())
    return bodies, seen


@pytest.fixture(scope="module")
def runs(stores, statements):
    out = {name: run(stores["series_major"], statements, mb)
           for name, mb in REGIMES.items()}
    for layout in LAYOUTS[1:]:
        out[layout] = run(stores[layout], statements, REGIMES["evicting"])
    return out


def delta(seen, path: str, lo: int = 0, hi: int = -1) -> float:
    return counter(seen[hi], path) - counter(seen[lo], path)


# -- the answers --------------------------------------------------------------


@pytest.mark.parametrize("which", [*REGIMES, *LAYOUTS[1:]])
def test_every_answer_is_the_references(ref, statements, runs, which):
    """Window times and group sets exact (`parse` raises otherwise), means
    within the configuration's limit, over both rounds of the hours."""
    bodies, _ = runs[which]
    for req, body in zip(statements, bodies):
        got = ref.parse(req.stmt, json.loads(body))
        assert got.shape == (12, HOSTS, 5)
        (value, limit), = ref.numbers(req.stmt, got).values()
        assert limit == TOL["mean"] == 2e-5
        assert value <= limit, req.stmt["q"]


def test_answers_do_not_depend_on_the_cache_or_the_layout(runs):
    """Files cut into time segments, files written before the cut, the
    rows in time order, a compacted shard: the bytes of the response."""
    want = runs["off"][0]
    for which in ("evicting", "roomy", *LAYOUTS[1:]):
        assert runs[which][0] == want, which


# -- the layout the writer leaves ---------------------------------------------


def met(srv: Served, req) -> list:
    """The chunks whose time range the statement's hour meets: what the
    reader's pruning leaves of the store's."""
    lo, hi = req.stmt["t0"] * 10**9, req.stmt["t1"] * 10**9
    return [(r, c) for r, c in srv.chunks() if c.tmax >= lo and c.tmin < hi]


def test_long_series_are_cut_along_time_into_ordinary_packed_chunks(
        stores, ref):
    """Each file of the series-major load: one sid span, SEGMENTS packed
    chunks in ascending, disjoint time ranges that share the file's rows
    evenly, every series in every one of them; the writer counted it."""
    srv = stores["series_major"]
    chunks = srv.chunks()
    assert len(chunks) == FILES * SEGMENTS and all(c.packed
                                                   for _r, c in chunks)
    for sh in srv.engine.all_shards():
        assert len(sh._files) == FILES
        for r in sh._files:
            segs = r.chunks("cpu")
            assert len(segs) == SEGMENTS == r.packed_count("cpu")
            assert len({(c.smin, c.smax) for c in segs}) == 1
            assert segs[0].smax - segs[0].smin + 1 == 64
            assert sum(c.rows for c in segs) == 64 * HOURS * TICKS_AN_HOUR
            assert {c.rows // 64 for c in segs} == {32, 33}
            for a, b in zip(segs, segs[1:]):
                assert a.tmax < b.tmin
            assert (segs[0].tmin, segs[-1].tmax) == (r.tmin, r.tmax)
            for c in segs:
                sids = r.read_packed_sids(c, cache=False)
                assert (np.unique(sids, return_counts=True)[1]
                        == c.rows // 64).all()
                assert [list(e) for e in c.sparse] == [
                    [int(sids[i]), i] for i in range(0, c.rows, 1024)]
    assert counter(srv.vars(), "tsf/packed_buffers_cut") >= FILES
    assert counter(srv.vars(), "tsf/packed_segments_written") \
        >= FILES * SEGMENTS


def test_a_compacted_shard_keeps_the_time_segments(stores):
    """compact() writes through the same chunk writer: the one file it
    leaves holds each sid span (a buffer of a PACK_ROWS a segment) as
    SEGMENTS chunks, span after span; the few series of the tail are too
    few rows to cut."""
    chunks = [c for _r, c in stores["compacted"].chunks()]
    assert sum(c.rows for c in chunks) == HOSTS * HOURS * TICKS_AN_HOUR
    spans = {}
    for c in chunks:
        spans.setdefault((c.smin, c.smax), []).append(c)
    assert list(spans) == sorted(spans)         # span after span
    *full, last = spans.values()
    assert len(full) >= 2 and all(len(v) == SEGMENTS for v in full)
    for segs in full:
        assert all(a.tmax < b.tmin for a, b in zip(segs, segs[1:]))
        assert all(c.rows >= CUT["PACK_ROWS"] // 4 for c in segs)
    assert len(last) == 1 and last[0].rows < 2 * (CUT["PACK_ROWS"] // 4)


def test_files_written_before_the_cut_and_short_series_hold_whole_chunks(
        stores):
    for layout, files in (("uncut", FILES), ("time_ordered", HOURS)):
        chunks = stores[layout].chunks()
        assert len(chunks) == files and all(c.packed for _r, c in chunks)
        assert all((c.tmin, c.tmax) == (r.tmin, r.tmax) for r, c in chunks)


# -- the regime the cell is defined around ------------------------------------


@pytest.mark.parametrize("which", ["evicting", "compacted"])
def test_an_evicting_cache_hardly_hits_and_a_statement_decodes_under_twice_what_it_keeps(
        runs, stores, statements, which):
    """The pruning reaches the hour: of each file's (each sid span's)
    segments a statement decodes the two or three its hour meets and
    skips the rest by their time range."""
    _, seen = runs[which]
    srv = stores["series_major" if which == "evicting" else which]
    lookups = delta(seen, "colcache/hits") + delta(seen, "colcache/misses")
    assert delta(seen, "colcache/misses") > 0
    assert delta(seen, "colcache/evictions") > 0
    assert delta(seen, "colcache/hits") < 0.05 * lookups
    for n, req in enumerate(statements):        # of every statement alone
        kept = delta(seen, "scan/rows_kept", n, n + 1)
        assert kept == HOSTS * TICKS_AN_HOUR
        decoded = delta(seen, "scan/rows_decoded", n, n + 1)
        assert decoded == sum(c.rows for _r, c in met(srv, req))
        assert kept <= decoded < 2 * kept
        assert delta(seen, "scan/packed_skipped_by_time", n, n + 1) \
            == len(srv.chunks()) - len(met(srv, req)) > 0


def test_files_written_before_the_cut_decode_six_hours_to_keep_one(
        runs, statements):
    """What PR 45 wrote is read as PR 45 read it: every chunk spans the
    six hours, nothing is skipped, and the cache never hits."""
    _, seen = runs["uncut"]
    assert delta(seen, "colcache/hits") == 0
    assert delta(seen, "colcache/misses") > 0
    assert delta(seen, "colcache/evictions") > 0
    assert delta(seen, "scan/packed_skipped_by_time") == 0
    for n in range(len(statements)):
        kept = delta(seen, "scan/rows_kept", n, n + 1)
        assert kept == HOSTS * TICKS_AN_HOUR
        assert delta(seen, "scan/rows_decoded", n, n + 1) == HOURS * kept
        assert delta(seen, "colcache/hits", n, n + 1) == 0


def test_the_same_rows_in_time_order_decode_under_twice_what_they_keep(runs):
    _, seen = runs["time_ordered"]
    kept = delta(seen, "scan/rows_kept")
    assert kept == 2 * HOURS * HOSTS * TICKS_AN_HOUR
    assert 1.0 <= delta(seen, "scan/rows_decoded") / kept < 2.0


def test_a_roomy_cache_holds_the_segments_an_hour_met_and_the_hit_path_adds_nothing(
        runs, stores, statements):
    """A segment holds a stretch of time, so a statement fills the cache
    with what its hour met and no more: the second round of the hours
    finds the fields the first one asked and decodes the others.  A
    statement asked again finds everything; a read that only hits opens
    no span of the miss path and moves none of its counters."""
    _, seen = runs["roomy"]
    assert delta(seen, "colcache/evictions") == 0
    assert delta(seen, "colcache/hits", 0, 1) == 0
    assert 0 < delta(seen, "colcache/misses", HOURS) \
        < delta(seen, "colcache/misses", 0, HOURS)
    assert delta(seen, "colcache/hits", HOURS) \
        > delta(seen, "colcache/hits", 0, HOURS) > 0    # the shared segment
    srv = stores["series_major"]
    _, again = run(srv, statements[1:2], REGIMES["roomy"])
    srv.svc.executor._inc_cache.clear()     # scan again, not a stored answer
    srv.ask(statements[1])
    again.append(srv.vars())
    assert delta(again, "colcache/misses", 0, 1) > 0
    assert delta(again, "colcache/hits", 1) \
        == delta(again, "colcache/misses", 0, 1)
    assert delta(again, "colcache/misses", 1) == 0
    for path in [f"query_stages/{s}_count" for s in MISS_SPANS] + [
            "scan/rows_decoded", "scan/rows_kept", "scan/decoded_bytes",
            "tsf/read_bytes", "tsf/blocks_read", "scanpool/busy_ns"]:
        assert delta(again, path, 0, 1) > 0, path
        assert delta(again, path, 1) == 0, path
    assert delta(again, "scan/merges", 1) == 1
    assert delta(again, "scan/packed_skipped_by_time", 1) \
        == delta(again, "scan/packed_skipped_by_time", 0, 1) > 0


# -- the merge works on what the statement keeps -------------------------------


BRANCHES = ("inorder", "single_sid", "interleaved", "sorted")


@pytest.mark.parametrize("which, branch", [
    ("evicting", "interleaved"), ("compacted", "interleaved"),
    ("uncut", "inorder"), ("time_ordered", "inorder")])
def test_a_cold_merge_works_on_the_rows_it_keeps_not_on_those_decoded(
        runs, statements, which, branch):
    """A chunk decodes whole; every part is trimmed to the hour before
    anything is joined or sorted, so what the merge works on is what the
    statement keeps.  An hour crosses a segment boundary, so the parts of
    one sid span repeat its sids, later in time: their rows are
    interleaved, not sorted.  Whole chunks lie series after series, so
    trimmed they are in order."""
    _, seen = runs[which]
    for n in range(len(statements)):
        kept = delta(seen, "scan/rows_kept", n, n + 1)
        assert kept == HOSTS * TICKS_AN_HOUR
        assert delta(seen, "scan/rows_merged", n, n + 1) == kept
        assert delta(seen, "scan/merges", n, n + 1) == 1
        assert delta(seen, f"scan/merges_{branch}", n, n + 1) == 1
    assert delta(seen, "scan/merges_sorted") == 0


@pytest.mark.parametrize("which", [*REGIMES, *LAYOUTS[1:]])
def test_every_bulk_read_counts_its_merge_and_names_its_branch(
        runs, statements, which):
    """Both call sites: the read that decoded (under `scan_merge`) and the
    one every chunk of which came from the cache (no span of its own)."""
    _, seen = runs[which]
    n = len(statements)
    assert delta(seen, "scan/merges") == n
    assert sum(delta(seen, f"scan/merges_{b}") for b in BRANCHES) == n
    assert delta(seen, "scan/rows_merged") == n * HOSTS * TICKS_AN_HOUR
    # rows_kept counts the reads that decoded
    assert delta(seen, "scan/rows_kept") == HOSTS * TICKS_AN_HOUR * delta(
        seen, "query_stages/decode_count")


def _whole_range_read(srv: Served, fields):
    sh, = srv.engine.all_shards()
    colcache.GLOBAL.configure(budget_mb=REGIMES["off"])
    colcache.GLOBAL.clear()
    before = STATS.counters("scan")
    sids = np.array(sorted(sh.index.series_ids("cpu")), dtype=np.int64)
    sid_arr, rec = sh.read_series_bulk("cpu", sids, None, None, fields)
    after = STATS.counters("scan")
    return sid_arr, rec, {k: after[k] - before.get(k, 0) for k in after}


def test_a_whole_range_read_of_in_order_parts_copies_each_part_once(
        stores, monkeypatch):
    """Not a timing: every array the merge makes is counted.  Joining P
    parts one after the other makes P - 1 ever longer copies a column;
    here the arrays made are the answer's own — sids, times and, a field,
    values and validity — and their bytes are the answer's bytes."""
    from opengemini_tpu import record
    from opengemini_tpu.storage import shard as shard_mod

    fields = ["usage_user", "usage_system", "usage_idle"]
    made: list[np.ndarray] = []
    merges = []
    real_merge, real_cat, real_join = (
        record.merge_bulk_parts, np.concatenate, record._join_plain)

    def counted_cat(arrays, *a, **kw):
        made.append(real_cat(arrays, *a, **kw))
        return made[-1]

    def counted_join(*a):
        col = real_join(*a)
        made.extend([col.values, col.valid])
        return col

    def watched_merge(parts, lo_t, hi_t, told=None):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(np, "concatenate", counted_cat)
            mp.setattr(record, "_join_plain", counted_join)
            out = real_merge(parts, lo_t, hi_t, told)
        merges.append((parts, out))
        return out

    monkeypatch.setattr(shard_mod, "merge_bulk_parts", watched_merge)
    sid_arr, rec, d = _whole_range_read(stores["uncut"], fields)

    rows = HOSTS * HOURS * TICKS_AN_HOUR
    assert len(rec) == rows and list(rec.columns) == fields
    assert d["merges"] == d["merges_inorder"] == 1
    assert d["rows_merged"] == d["rows_kept"] == d["rows_decoded"] == rows
    (parts, out), = merges
    assert len(parts) == FILES and out[1] is rec
    answer = [sid_arr, rec.times] + [
        a for c in rec.columns.values() for a in (c.values, c.valid)]
    assert len(made) == len(answer) == 2 + 2 * len(fields)
    assert {id(a) for a in made} == {id(a) for a in answer}
    assert sum(a.nbytes for a in made) == rows * (8 + 8 + 9 * len(fields))


@pytest.mark.parametrize("layout", ["series_major", "compacted"])
def test_a_whole_range_read_of_time_segments_is_interleaved_not_sorted(
        stores, layout):
    """Every segment of every sid span is a part: the spans repeat their
    sids, later in time.  The rows are those of the uncut files, bit for
    bit, and nothing was skipped or sorted."""
    fields = ["usage_user", "usage_idle"]
    want_sid, want, d0 = _whole_range_read(stores["uncut"], fields)
    sid_arr, rec, d = _whole_range_read(stores[layout], fields)
    assert d0["merges_inorder"] == 1 and d["merges_interleaved"] == 1
    assert d["merges"] == 1 and d.get("merges_sorted", 0) == 0
    assert d["packed_skipped_by_time"] == 0
    assert d["rows_merged"] == d["rows_decoded"] == len(want)
    assert sid_arr.tobytes() == want_sid.tobytes()
    assert rec.times.tobytes() == want.times.tobytes()
    assert list(rec.columns) == fields
    for name in fields:
        assert rec.columns[name].values.tobytes() \
            == want.columns[name].values.tobytes()
        assert rec.columns[name].valid.tobytes() \
            == want.columns[name].valid.tobytes()


# -- the miss path's spans and counters ---------------------------------------


@pytest.mark.parametrize("which", ["evicting", "uncut"])
def test_the_miss_path_reports_its_stages_and_its_bytes(
        stores, runs, statements, which):
    _, seen = runs[which]
    srv = stores["series_major" if which == "evicting" else which]
    n = len(statements)
    chunks = [met(srv, req) for req in statements]
    assert {len(c) for c in chunks} == (
        {2 * FILES, 3 * FILES} if which == "evicting" else {FILES})
    for name in ("decode", "scan_merge"):
        assert delta(seen, f"query_stages/{name}_count") == n
        assert delta(seen, f"query_stages/{name}_ns") > 0
    for name in ("pool_wait", "block_read", "codec", "colcache_fill"):
        assert delta(seen, f"query_stages/{name}_count") \
            == sum(len(c) for c in chunks)
        assert delta(seen, f"query_stages/{name}_ns") > 0
    assert delta(seen, "scanpool/busy_ns") > 0
    # of one statement: the blocks of times, sids and its five fields in
    # every chunk its hour meets, seals included; and what the codecs
    # made of them
    for k, req in enumerate(statements):
        locs = [loc for _r, c in chunks[k]
                for loc in [c.time_loc, c.sid_loc]
                + [c.cols[f][part] for f in req.stmt["fields"]
                   for part in "vm"] if loc]
        assert delta(seen, "tsf/blocks_read", k, k + 1) == len(locs)
        assert delta(seen, "tsf/read_bytes", k, k + 1) \
            == sum(loc[1] for loc in locs)
        rows = sum(c.rows for _r, c in chunks[k])
        assert delta(seen, "scan/decoded_bytes", k, k + 1) \
            == rows * (5 * 9 + 8 + 8)


@pytest.mark.parametrize("pool", [True, False])
def test_decode_self_time_is_never_negative(stores, statements, pool,
                                            monkeypatch):
    """On the pool's threads the stages have no parent frame, so they sum
    beside `decode` (CPU time) and take nothing from its self time; run
    inline (one worker) they are its children on one thread.  Either way
    what `decode` does not spend in a child stage is its self time >= 0,
    and `scan` contains `decode` and `scan_merge`."""
    srv = stores["series_major"]
    if not pool:
        monkeypatch.setattr(scanpool, "WORKERS", 1)
    colcache.GLOBAL.configure(budget_mb=REGIMES["evicting"])
    colcache.GLOBAL.clear()
    srv.svc.executor._inc_cache.clear()
    for req in statements[:HOURS]:
        before = STATS.counters("query_stages")
        srv.ask(req)
        after = STATS.counters("query_stages")
        d = {k: after[k] - before.get(k, 0) for k in after}
        assert d["decode_count"] == 1
        assert d["pool_wait_count"] == (len(met(srv, req)) if pool else 0)
        stages = d["block_read_ns"] + d["codec_ns"] + d["colcache_fill_ns"]
        if pool:    # exact: the frame holds what its children recorded
            assert d["decode_self_ns"] == d["decode_ns"] - d["pool_wait_ns"]
        else:       # and the counted lookups that missed (`colcache`)
            assert d["decode_self_ns"] <= d["decode_ns"] - stages
        assert 0 <= d["decode_self_ns"] <= d["decode_ns"]
        assert d["decode_ns"] + d["scan_merge_ns"] <= d["scan_ns"]
        assert d["scan_self_ns"] >= 0 and d["colcache_fill_self_ns"] >= 0

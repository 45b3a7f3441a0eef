"""Off-lock compaction (ISSUE 19): snapshot -> off-lock merge ->
revalidated swap, racing flush/ingest/quarantine, plus the media-fault
and lockdep legs.

The PR 3 flush discipline applied to background rewrites: the input run
is snapshotted under `_flush_lock` + `_lock` (full merges also reserve
their output seq there), the merge/encode/fsync runs with NO lock held,
and an atomic commit re-validates the run by reader identity before the
file-set splice.  These tests pin the contract edges: a flush published
mid-merge survives the splice (and outranks merged rows by seq), a
vanished input aborts the swap, a faulted output write aborts with the
inputs intact, and the retired lockdep exemptions stay retired."""

import os
import threading
import time

import pytest

from opengemini_tpu.record import FieldType
from opengemini_tpu.storage import diskfault
from opengemini_tpu.storage.shard import Shard
from opengemini_tpu.utils import failpoint, lockdep
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
BASE = 1_700_000_000 * NS


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    failpoint.disable_all()
    diskfault.clear_all()


def _pt(t, v):
    return ("m", (("host", "a"),), t, {"v": (FieldType.FLOAT, v)})


def _mk_shard(tmp_path, n_files=3, rows_per=4):
    sh = Shard(str(tmp_path / "s"), BASE - NS, BASE + 10_000_000 * NS)
    for f in range(n_files):
        sh.write_points_structured(
            [_pt(BASE + (f * rows_per + k) * NS, float(f * rows_per + k))
             for k in range(rows_per)])
        sh.flush()
    return sh


def _series(sh):
    sid = sh.index.get_or_create("m", (("host", "a"),))
    rec = sh.read_series("m", sid)
    return {int((t - BASE) // NS): v
            for t, v in zip(rec.times, rec.columns["v"].values)}


def _park_compact(sh, site="compact-before-replace", event="swap"):
    """Start sh.compact() on a thread, parked at `site` until
    failpoint.set_event(event).  Returns (thread, result dict)."""
    failpoint.enable(site, f"wait:{event}#1")
    out = {}

    def run():
        try:
            out["ok"] = sh.compact()
        except BaseException as e:  # noqa: BLE001 — surfaced by caller
            out["exc"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    for _ in range(2000):
        if failpoint.hits(site):
            break
        time.sleep(0.001)
    assert failpoint.hits(site) == 1, "compaction never reached the swap"
    return th, out


def test_flush_published_mid_merge_survives_the_swap(tmp_path):
    """A flush that publishes while the merge is off-lock must (a) keep
    its file through the revalidated splice and (b) outrank the merged
    rows on a timestamp collision — the reserved-seq rule."""
    sh = _mk_shard(tmp_path, n_files=3)
    th, out = _park_compact(sh)
    # mid-merge flush: a fresh row AND an overwrite of a merged row.
    # The merge snapshot was taken before this existed; if the merged
    # output ranked above the flush by name, t=5 would read 0.5 again.
    sh.write_points_structured([_pt(BASE + 5 * NS, 99.0),
                                _pt(BASE + 1000 * NS, 7.0)])
    sh.flush()
    assert sh.file_count() == 4  # 3 inputs + the mid-merge publish
    failpoint.set_event("swap")
    th.join(30)
    assert not th.is_alive() and out.get("ok") is True
    assert sh.file_count() == 2  # merged(3) + the mid-merge publish
    want = {i: float(i) for i in range(12)}
    want[5] = 99.0
    want[1000] = 7.0
    assert _series(sh) == want
    assert not [f for f in os.listdir(sh.path) if f.endswith(".merge")]
    sh.close()
    # reopen: name order must rank the flush ABOVE the merged output
    sh2 = Shard(str(tmp_path / "s"), BASE - NS, BASE + 10_000_000 * NS)
    assert _series(sh2) == want
    sh2.close()


def test_ingest_never_stalls_behind_a_parked_compaction(tmp_path):
    """The whole point of off-lock: with a compaction parked inside its
    merge window, writes and reads proceed immediately."""
    sh = _mk_shard(tmp_path, n_files=3)
    th, out = _park_compact(sh)
    t0 = time.perf_counter()
    sh.write_points_structured([_pt(BASE + 2000 * NS, 1.0)])
    got = _series(sh)
    elapsed = time.perf_counter() - t0
    assert got[2000] == 1.0 and len(got) == 13
    # generous bound: a write+read pair that had to wait out the merge
    # would block until set_event below, not milliseconds
    assert elapsed < 5.0
    failpoint.set_event("swap")
    th.join(30)
    assert out.get("ok") is True
    sh.close()


def test_quarantined_input_aborts_the_swap(tmp_path):
    """An input pulled from the read set mid-merge (scrub quarantine,
    delete rewrite) fails identity revalidation: the merge output is
    discarded — publishing it could resurrect dropped rows."""
    sh = _mk_shard(tmp_path, n_files=3)
    aborts0 = STATS.snapshot().get("compact", {}).get("swap_aborts", 0)
    th, out = _park_compact(sh)
    victim = sh._files[0].path
    assert sh.quarantine_file(victim, "test: injected")
    failpoint.set_event("swap")
    th.join(30)
    assert not th.is_alive()
    assert out.get("ok") is False  # aborted, not published
    snap = STATS.snapshot().get("compact", {})
    assert snap.get("swap_aborts", 0) == aborts0 + 1
    assert not [f for f in os.listdir(sh.path) if f.endswith(".merge")]
    # survivors unharmed; the quarantined file's rows are gone (that is
    # quarantine's contract, repaired at the cluster tier)
    assert _series(sh) == {i: float(i) for i in range(4, 12)}
    assert sh.compact()  # next tick compacts the surviving set
    assert _series(sh) == {i: float(i) for i in range(4, 12)}
    sh.close()


def test_concurrent_writers_through_a_full_compaction(tmp_path):
    """Unsynchronized ingest racing a real (unparked) compaction loop:
    every acked row readable exactly once afterwards."""
    sh = _mk_shard(tmp_path, n_files=4, rows_per=8)
    acked = {i: float(i) for i in range(32)}
    lock = threading.Lock()
    stop = threading.Event()

    def writer(k):
        for i in range(200):
            if stop.is_set():
                break
            t_idx = 10_000 + k * 1_000 + i
            sh.write_points_structured([_pt(BASE + t_idx * NS,
                                            float(t_idx))])
            with lock:
                acked[t_idx] = float(t_idx)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(6):
            sh.flush()
            sh.compact()
    finally:
        stop.set()
        for t in threads:
            t.join()
    sh.flush()
    sh.compact()
    assert _series(sh) == acked
    sh.close()
    sh2 = Shard(str(tmp_path / "s"), BASE - NS, BASE + 10_000_000 * NS)
    assert _series(sh2) == acked
    sh2.close()


# -- media-fault leg ---------------------------------------------------------


def test_eio_on_merge_output_aborts_with_inputs_intact(tmp_path):
    """EIO while writing the merge output: the compaction fails loudly,
    nothing is published, every input file and row survives."""
    sh = _mk_shard(tmp_path, n_files=3)
    diskfault.set_rule("*.merge*", "eio")
    with pytest.raises(OSError):
        sh.compact()
    diskfault.clear_all()
    assert sh.file_count() == 3
    assert not [f for f in os.listdir(sh.path) if f.endswith(".merge")]
    assert _series(sh) == {i: float(i) for i in range(12)}
    assert sh.compact()  # clean retry once the media behaves
    assert sh.file_count() == 1
    assert _series(sh) == {i: float(i) for i in range(12)}
    sh.close()


def test_torn_write_on_merge_output_aborts_before_the_swap(tmp_path):
    """A torn write on the output is caught by the pre-swap self-verify
    (block CRC walk of the finished file) — the damaged output must
    never replace an input, which an in-place level merge would
    otherwise clobber at os.replace."""
    sh = _mk_shard(tmp_path, n_files=3)
    aborts0 = STATS.snapshot().get("compact", {}).get(
        "output_verify_aborts", 0)
    diskfault.set_rule("*.merge*", "torn-write#1")
    assert sh.compact() is False  # aborted, no exception
    diskfault.clear_all()
    snap = STATS.snapshot().get("compact", {})
    assert snap.get("output_verify_aborts", 0) == aborts0 + 1
    assert sh.file_count() == 3
    assert not [f for f in os.listdir(sh.path) if f.endswith(".merge")]
    assert _series(sh) == {i: float(i) for i in range(12)}
    assert sh.compact()
    assert _series(sh) == {i: float(i) for i in range(12)}
    sh.close()


# -- lockdep leg -------------------------------------------------------------


def test_compaction_exemptions_are_retired():
    """The audited blocking-IO exemptions compaction used to hold are
    gone for good: claiming one is an error in BOTH lockdep modes, so
    the exemption cannot quietly return with a refactor."""
    for reason in sorted(lockdep.RETIRED_EXEMPTIONS):
        with pytest.raises(lockdep.LockdepError, match="retired"):
            with lockdep.allow_blocking(reason):
                pass


def test_compaction_runs_clean_under_armed_lockdep(tmp_path, monkeypatch):
    """With the validator armed, a full flush + all three compaction
    shapes run without a single blocking-IO-under-hot-lock finding (the
    old implementation needed three exemptions to pass this)."""
    if not lockdep.enabled():
        pytest.skip("lockdep not armed in this run (OGT_LOCKDEP=0)")
    sh = _mk_shard(tmp_path, n_files=4)
    v0 = len(lockdep.violations())
    assert sh.compact_level(fanout=2) or True
    sh.write_points_structured([_pt(BASE + 3 * NS, 30.0)])  # overlap
    sh.flush()
    assert sh.compact_out_of_order() or True
    sh.compact()  # may be a no-op if the set already collapsed to one
    assert len(lockdep.violations()) == v0
    sh.close()


# -- compaction of time-segmented files (PR 46) --------------------------------
#
# Long series are written as packed chunks cut along time.  Every kind of
# compaction writes through the same chunk writer, so what it leaves is
# cut the same way, and what it reads may be: a series' rows then come
# from several chunks of one input.  The writer's constants are scaled to
# the data (360 rows a series for the 2,160 of tsbs-devops-cpu-4000-6h).

SEG_SERIES, SEG_ROWS, SEG_CUT = 96, 360, {"SEGMENT_ROWS": 32,
                                          "PACK_ROWS": 4096}


def _seg_write(sh, series, rows, salt=0):
    import numpy as np

    from opengemini_tpu.ingest import native_lp

    out = []
    for s in series:
        v = np.random.default_rng(s * 7919 + salt).normal(
            size=(max(rows) + 1, 2)).round(2)
        out.extend(f"cpu,host=h{s:03d} a={v[r, 0]},b={v[r, 1]} "
                   f"{BASE + r * 10 * NS}" for r in rows)
    body = "\n".join(out).encode()
    sh.write_columnar(native_lp.parse_columnar(body, "ns", 0), None, body,
                      "ns", 0)
    sh.flush()


def _seg_layout(sh):
    """[(file, smin, smax, [(rows, tmin, tmax)])] of the packed chunks
    (a file of under 64 series holds a chunk a series)."""
    out = []
    for at, r in enumerate(sh._files):
        spans = {}
        for c in r.chunks("cpu"):
            if c.packed:
                spans.setdefault((c.smin, c.smax), []).append(
                    (c.rows, c.tmin, c.tmax))
        out.extend((at, lo, hi, segs) for (lo, hi), segs in spans.items())
    return out


def _seg_read(sh, lo, hi):
    import numpy as np

    sids = np.array(sorted(sh.index.series_ids("cpu")), dtype=np.int64)
    before = STATS.counters("scan")
    sid_arr, rec = sh.read_series_bulk("cpu", sids, BASE + lo * 10 * NS,
                                       BASE + hi * 10 * NS)
    after = STATS.counters("scan")
    return sid_arr, rec, {k: after[k] - before.get(k, 0) for k in after}


@pytest.mark.parametrize("how", ["compact", "compact_level",
                                 "compact_out_of_order"])
def test_compaction_of_segmented_files_keeps_rows_digest_and_layout(
        tmp_path, monkeypatch, how):
    from opengemini_tpu.storage import colcache, tsf

    for name, value in SEG_CUT.items():
        monkeypatch.setattr(tsf, name, value)
    before_cc = colcache.GLOBAL.config()
    colcache.GLOBAL.configure(budget_mb=0)
    sh = Shard(str(tmp_path / "s"), BASE - NS, BASE + 10_000_000 * NS)
    series = range(SEG_SERIES)
    # two segmented files, one after the other in time ...
    _seg_write(sh, series, range(SEG_ROWS))
    _seg_write(sh, series, range(SEG_ROWS, 2 * SEG_ROWS))
    if how == "compact_out_of_order":
        # ... and a late one that rewrites a stretch of the first
        _seg_write(sh, range(10, 30), range(100, 160), salt=1)
    else:
        _seg_write(sh, series, range(2 * SEG_ROWS, 2 * SEG_ROWS + 40))
        _seg_write(sh, series, range(2 * SEG_ROWS + 40, 2 * SEG_ROWS + 80))
    for _at, _lo, _hi, segs in _seg_layout(sh)[:2]:
        assert len(segs) == 11 and sum(s[0] for s in segs) \
            == SEG_SERIES * SEG_ROWS
    digest = sh.content_digest()
    rows = digest["cpu"][0]
    assert rows == SEG_SERIES * (2 * SEG_ROWS + (
        0 if how == "compact_out_of_order" else 80))
    want = _seg_read(sh, 90, 150)
    one = sh.read_series("cpu", sorted(sh.index.series_ids("cpu"))[12])

    cut0 = STATS.counters("tsf").get("packed_buffers_cut", 0)
    if how == "compact":
        assert sh.compact() and sh.file_count() == 1
    elif how == "compact_level":
        assert sh.compact_level(fanout=4) and sh.file_count() == 1
    else:
        assert sh.has_time_overlap()
        assert sh.compact_out_of_order(max_files=4)
        assert sh.file_count() == 1 and not sh.has_time_overlap()
    assert STATS.counters("tsf")["packed_buffers_cut"] > cut0

    assert sh.content_digest() == digest            # the rows, bit for bit
    assert sum(s[0] for _f, _l, _h, segs in _seg_layout(sh)
               for s in segs) == rows
    wanted = tsf.packed_segments(rows, SEG_SERIES)
    assert wanted == (rows // SEG_SERIES + 16) // 32 > 11
    spans = _seg_layout(sh)                         # and the layout
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    assert len(spans[0][3]) == wanted               # a full buffer
    for _at, _lo, _hi, segs in spans:
        assert len(segs) == min(wanted, sum(s[0] for s in segs)
                                // (SEG_CUT["PACK_ROWS"] // 4))
        assert all(a[2] < b[1] for a, b in zip(segs, segs[1:]))
    got = _seg_read(sh, 90, 150)
    assert got[0].tobytes() == want[0].tobytes()
    assert got[1].times.tobytes() == want[1].times.tobytes()
    for name, col in want[1].columns.items():
        assert got[1].columns[name].values.tobytes() == col.values.tobytes()
    assert got[2]["rows_kept"] == SEG_SERIES * 60
    assert got[2]["rows_decoded"] < 2 * got[2]["rows_kept"]
    assert got[2]["packed_skipped_by_time"] > 0
    assert got[2].get("merges_sorted", 0) == 0
    again = sh.read_series("cpu", sorted(sh.index.series_ids("cpu"))[12])
    assert again.times.tobytes() == one.times.tobytes()
    assert again.columns["a"].values.tobytes() \
        == one.columns["a"].values.tobytes()
    sh.close()
    # the merged file reopens with its segments
    sh2 = Shard(str(tmp_path / "s"), BASE - NS, BASE + 10_000_000 * NS)
    assert sh2.content_digest() == digest
    sh2.close()
    colcache.GLOBAL.configure(**before_cc)

"""Media-fault tier (ISSUE 9): diskfault injection rules, end-to-end
TSF block checksums, WAL interior-corruption salvage, quarantine, and
the governed scrub service.

The contract: a flipped bit / torn sector / EIO anywhere in the storage
media is DETECTED before any wrong value reaches a query, CONTAINED
(one file quarantined; everything else keeps serving), and — for the
WAL — the acked suffix past the damage is SALVAGED instead of silently
truncated.  With nothing armed, every hook is bit-identical
pass-through."""

from __future__ import annotations

import json
import os
import struct
import urllib.request
import zlib

import numpy as np
import pytest

from opengemini_tpu.record import Column, FieldType
from opengemini_tpu.storage import diskfault
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.storage.shard import FileQuarantined
from opengemini_tpu.storage.tsf import MAGIC, CorruptFile, PreAgg, TSFReader
from opengemini_tpu.storage.wal import WAL, WALCorruption
from opengemini_tpu.utils.stats import GLOBAL as STATS

NS = 1_000_000_000
BASE = 1_700_000_000


@pytest.fixture(autouse=True)
def _clean_rules():
    diskfault.clear_all()
    yield
    diskfault.clear_all()


def _mk_engine(tmp_path, rows=120, flush=True, series=1):
    eng = Engine(str(tmp_path / "d"))
    eng.create_database("db")
    lines = "\n".join(
        f"m,w=w{s} v={i}i {(BASE + i) * NS}"
        for s in range(series) for i in range(rows))
    eng.write_lines("db", lines)
    if flush:
        eng.flush_all()
    return eng


def _flip_byte(path, at, bit=1):
    with open(path, "r+b") as f:
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ bit]))


def _series_values(eng, mst="m"):
    sh = eng.shards_of_db("db")[0]
    out = {}
    for sid in sorted(sh.index.series_ids(mst)):
        rec = sh.read_series(mst, sid)
        col = rec.columns.get("v")
        if col is not None:
            out[sid] = [int(v) for v in col.values]
    return out


# -- diskfault rules ---------------------------------------------------------


class TestDiskfaultRules:
    def test_validate_rejects_garbage(self):
        for bad in ("nope", "bitflip:x", "short-read:-1", "eio#0",
                    "torn-write:abc"):
            with pytest.raises(ValueError):
                diskfault.validate(bad)
        for ok in ("eio", "eio#3", "bitflip", "bitflip:7", "short-read",
                   "short-read:16", "torn-write", "torn-write:4",
                   "fsync-fail"):
            diskfault.validate(ok)

    def test_pass_through_unarmed(self):
        buf = b"hello world"
        assert diskfault.on_read("/x/y.tsf", buf, site="tsf-block-read") is buf
        assert diskfault.on_write("/x/y.tsf", buf, site="tsf-block-write") is buf
        diskfault.on_fsync("/x/y.tsf", site="tsf-fsync")
        assert not diskfault.armed()

    def test_rule_lifecycle_and_hits(self):
        diskfault.set_rule("*.tsf", "bitflip:0")
        assert diskfault.rules() == [{"path": "*.tsf",
                                      "action": "bitflip:0"}]
        out = diskfault.on_read("/a/b.tsf", b"\x00\x00", site="tsf-block-read")
        assert out == b"\x01\x00"
        # a non-matching path and a non-read action pass through
        assert diskfault.on_read("/a/b.wal", b"\x00", site="wal-replay-read") == b"\x00"
        assert diskfault.hits() == {"*.tsf=bitflip:0@tsf-block-read": 1}
        assert diskfault.clear_rule("*.tsf")
        assert not diskfault.rules()

    def test_nth_hit_gating(self):
        diskfault.set_rule("*.log", "eio#3")
        for _ in range(2):
            diskfault.on_read("/w/x.log", b"ok", site="wal-replay-read")
        with pytest.raises(diskfault.DiskFault):
            diskfault.on_read("/w/x.log", b"ok", site="wal-replay-read")
        # after the k-th hit it disarms back to counting
        diskfault.on_read("/w/x.log", b"ok", site="wal-replay-read")

    def test_env_arming(self, monkeypatch):
        monkeypatch.setattr(diskfault, "_rules", [])
        monkeypatch.setenv(
            "OGT_DISKFAULT", "*.tsf=eio; *wal.log=torn-write:3; bad=nope")
        diskfault._load_env()
        assert diskfault.rules() == [
            {"path": "*.tsf", "action": "eio"},
            {"path": "*wal.log", "action": "torn-write:3"},
        ]
        diskfault.clear_all()

    def test_short_read_and_torn_write(self):
        diskfault.set_rule("*short", "short-read:4")
        assert diskfault.on_read("/a/short", b"12345678",
                                 site="tsf-block-read") == b"1234"
        diskfault.set_rule("*torn", "torn-write")
        assert diskfault.on_write("/a/torn", b"12345678",
                                  site="tsf-block-write") == b"1234"


# -- TSF end-to-end block checksums ------------------------------------------


class TestBlockChecksums:
    def test_bitflip_in_data_block_detected_not_decoded(self, tmp_path):
        """Acceptance (a): single-bit corruption is detected before any
        wrong result is served — on the cold decode path AND the
        colcache fill path."""
        eng = _mk_engine(tmp_path)
        sh = eng.shards_of_db("db")[0]
        r = sh._files[0]
        assert r.block_crc
        before = _series_values(eng)
        loc = r.data_locs()[-1]
        eng.close()
        _flip_byte(r.path, loc[0] + loc[1] // 2)
        eng2 = Engine(str(tmp_path / "d"))
        sh2 = eng2.shards_of_db("db")[0]
        sid = sorted(sh2.index.series_ids("m"))[0]
        with pytest.raises(FileQuarantined):
            sh2.read_series("m", sid)
        # acceptance (b): the file is quarantined — later queries skip
        # it and succeed (no files left here, so the series is empty;
        # never a wrong value)
        rec = sh2.read_series("m", sid)
        assert len(rec) == 0
        assert sh2.quarantined()
        eng2.close()
        assert before  # sanity: there was real data to protect

    def test_colcache_fill_path_verifies(self, tmp_path, monkeypatch):
        from opengemini_tpu.storage import colcache

        prior = colcache.GLOBAL.config()
        colcache.GLOBAL.configure(budget_mb=64)
        try:
            eng = _mk_engine(tmp_path)
            sh = eng.shards_of_db("db")[0]
            r = sh._files[0]
            loc = r.data_locs()[0]
            # corrupt ON DISK while nothing is cached yet: the fill
            # path (reader._read under colcache) must verify
            _flip_byte(r.path, loc[0] + 1)
            sid = sorted(sh.index.series_ids("m"))[0]
            with pytest.raises(FileQuarantined):
                sh.read_series("m", sid)
            eng.close()
        finally:
            colcache.GLOBAL.clear()
            colcache.GLOBAL.configure(**prior)

    def test_truncated_file_quarantined_at_open(self, tmp_path):
        eng = _mk_engine(tmp_path)
        sh = eng.shards_of_db("db")[0]
        path = sh._files[0].path
        eng.close()
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - 10)
        # the shard OPENS (old behavior: CorruptFile crashed the whole
        # engine load) with the damaged file quarantined
        eng2 = Engine(str(tmp_path / "d"))
        snap = eng2.quarantine_snapshot()
        assert snap["total"] == 1 and "end magic" in snap["files"][0]["why"]
        # sticky across reopen via the .quar marker
        eng2.close()
        eng3 = Engine(str(tmp_path / "d"))
        assert eng3.quarantine_snapshot()["total"] == 1
        assert eng3.purge_quarantined() == 1
        assert eng3.quarantine_snapshot()["total"] == 0
        eng3.close()

    def test_injected_torn_write_caught_on_read(self, tmp_path):
        """A torn-write fault at flush time publishes a file whose
        damaged block fails its CRC at first decode — the write path
        itself cannot detect a lying disk; the read path must."""
        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        eng.write_lines("db", "\n".join(
            f"m v={i}i {(BASE + i) * NS}" for i in range(50)))
        diskfault.set_rule("*.tsf", "torn-write#1")
        try:
            eng.flush_all()
        finally:
            diskfault.clear_all()
        sh = eng.shards_of_db("db")[0]
        assert len(sh._files) == 1  # published: the writer saw success
        with pytest.raises(FileQuarantined):
            sh.read_series("m", sorted(sh.index.series_ids("m"))[0])
        eng.close()

    def test_eio_fails_flush_loudly(self, tmp_path):
        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        eng.write_lines("db", f"m v=1i {BASE * NS}")
        diskfault.set_rule("*.tsf", "eio")
        with pytest.raises(diskfault.DiskFault):
            eng.flush_all()
        diskfault.clear_all()
        # the failed flush kept its frozen snapshot: retry succeeds
        eng.flush_all()
        sh = eng.shards_of_db("db")[0]
        assert len(sh._files) == 1
        assert not eng.durability_check()
        eng.close()

    def test_legacy_v1_file_still_reads(self, tmp_path):
        """Revision-1 (CRC-less) files stay readable: on-disk
        compatibility across the format bump."""
        from opengemini_tpu.storage import chunkmeta, encoding

        times = np.arange(BASE * NS, (BASE + 10) * NS, NS, dtype=np.int64)
        col = Column(FieldType.INT, np.arange(10, dtype=np.int64),
                     np.ones(10, np.bool_))
        time_buf = encoding.encode_ints(times)
        vbuf, mbuf = encoding.encode_column(col)
        path = str(tmp_path / "legacy.tsf")
        with open(path, "wb") as f:
            f.write(MAGIC)
            off = len(MAGIC)
            tloc = [off, len(time_buf)]
            f.write(time_buf)
            off += len(time_buf)
            vloc = [off, len(vbuf)]
            f.write(vbuf)
            off += len(vbuf)
            mloc = [off, len(mbuf)]
            f.write(mbuf)
            off += len(mbuf)
            meta = {"m": {"schema": {"v": int(FieldType.INT)}, "chunks": [{
                "rows": 10, "time": tloc, "sid": 7,
                "tmin": int(times[0]), "tmax": int(times[-1]),
                "cols": {"v": {"v": vloc, "m": mloc,
                               "pre": PreAgg.of(col).to_json()}},
            }]}}
            meta_buf = b"BM02" + zlib.compress(
                chunkmeta.encode_meta(meta), 1)
            f.write(meta_buf)
            f.write(struct.Struct("<QII").pack(
                off, len(meta_buf), zlib.crc32(meta_buf)))
            f.write(b"OGTSFEND")
        r = TSFReader(path)
        assert not r.block_crc
        rec = r.read_chunk("m", r.chunks("m")[0])
        assert [int(v) for v in rec.columns["v"].values] == list(range(10))
        r.close()


# -- WAL interior corruption --------------------------------------------------


def _wal_frames(path):
    from opengemini_tpu.storage.wal import _HEADER

    data = open(path, "rb").read()
    out, off = [], 0
    while off + _HEADER.size <= len(data):
        length, _crc, _kind = _HEADER.unpack_from(data, off)
        out.append((off, length))
        off += _HEADER.size + length
    return out


class TestWALCorruption:
    def _mk_wal(self, tmp_path, n=5):
        path = str(tmp_path / "wal.log")
        w = WAL(path)
        for i in range(n):
            w.append_lines(f"m v={i}i {(BASE + i) * NS}", "ns", 0)
        w.flush()
        w.close()
        return path

    def test_interior_flip_raises_with_salvage(self, tmp_path):
        """The ISSUE 9 regression: flip one byte in record 2 of 5 —
        replay must NOT return 1 record and exit clean (the old
        truncate-at-first-bad-frame behavior silently discarded the
        acked suffix)."""
        from opengemini_tpu.storage.wal import _HEADER

        path = self._mk_wal(tmp_path, 5)
        frames = _wal_frames(path)
        off, length = frames[1]
        _flip_byte(path, off + _HEADER.size + length // 2)
        got = []
        with pytest.raises(WALCorruption) as ei:
            for entry in WAL.replay(path):
                got.append(entry)
        assert len(got) == 1  # the clean prefix only
        e = ei.value
        assert len(e.clean_frames) == 1
        assert len(e.salvaged_frames) == 3
        vals = [ent[1] for ent in e.salvaged_entries()]
        assert [b"v=2i" in v for v in vals] == [True, False, False]

    def test_torn_tail_still_truncates_silently(self, tmp_path):
        from opengemini_tpu.storage.wal import _HEADER

        path = self._mk_wal(tmp_path, 5)
        off, length = _wal_frames(path)[-1]
        _flip_byte(path, off + _HEADER.size + 1)
        got = list(WAL.replay(path))  # no raise: crash-mid-append shape
        assert len(got) == 4

    def test_shard_salvages_suffix_and_is_idempotent(self, tmp_path):
        from opengemini_tpu.storage.wal import _HEADER

        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        for b in range(5):
            eng.write_lines("db", "\n".join(
                f"m v={b * 10 + i}i {(BASE + b * 10 + i) * NS}"
                for i in range(10)))
        eng.close()
        wal = next(os.path.join(dp, "wal.log")
                   for dp, _d, fs in os.walk(str(tmp_path / "d"))
                   if "wal.log" in fs)
        off, length = _wal_frames(wal)[1]
        _flip_byte(wal, off + _HEADER.size + length // 2)
        before = STATS.counters("wal").get("interior_corruptions", 0)
        eng2 = Engine(str(tmp_path / "d"))
        vals = sorted(v for vs in _series_values(eng2).values() for v in vs)
        # batch 2 (values 10..19) died with its frame; 1, 3, 4, 5 live
        assert vals == [v for v in range(50) if not 10 <= v < 20]
        assert STATS.counters("wal")["interior_corruptions"] == before + 1
        sidecars = [f for dp, _d, fs in os.walk(str(tmp_path / "d"))
                    for f in fs if ".corrupt-" in f]
        assert len(sidecars) == 1
        eng2.close()
        # the rewritten log replays clean: same rows, no new event
        eng3 = Engine(str(tmp_path / "d"))
        vals3 = sorted(v for vs in _series_values(eng3).values() for v in vs)
        assert vals3 == vals
        assert STATS.counters("wal")["interior_corruptions"] == before + 1
        assert not eng3.durability_check()
        eng3.close()


# -- scrub service ------------------------------------------------------------


class TestScrub:
    def test_detects_and_quarantines(self, tmp_path):
        from opengemini_tpu.services.scrub import ScrubService

        eng = _mk_engine(tmp_path, rows=300)
        sh = eng.shards_of_db("db")[0]
        r = sh._files[0]
        loc = r.data_locs()[0]
        _flip_byte(r.path, loc[0] + 3)
        s = ScrubService(eng, 3600.0, mb_per_tick=64)
        s.tick_now()
        assert eng.quarantine_snapshot()["total"] == 1
        assert STATS.counters("scrub").get("corruptions_found_total", 0) >= 1
        eng.close()

    def test_byte_budget_paces_the_sweep(self, tmp_path):
        from opengemini_tpu.services.scrub import ScrubService

        eng = Engine(str(tmp_path / "d"))
        eng.create_database("db")
        eng.write_lines("db", "\n".join(
            f"m,w=w{s_} v={(i * 37) % 1009}i {(BASE + i) * NS}"
            for s_ in range(8) for i in range(4000)))
        eng.flush_all()
        s = ScrubService(eng, 3600.0)
        s.mb_per_tick = 0.001  # ~1KB per tick: pacing observable
        total = sum(loc[1] for sh in eng.all_shards()
                    for r in sh._files for loc in r.data_locs())
        first = s.tick_now()
        assert 0 < first < total  # the budget bounded the sweep
        assert s._cursor  # mid-file resume point retained
        # repeated ticks converge to a full verified pass
        for _ in range(4096):
            if s.passes:
                break
            s.tick_now()
        assert s.passes >= 1
        assert STATS.counters("scrub")["files_verified_total"] >= 1
        eng.close()

    def test_scrub_beside_queries_changes_no_result(self, tmp_path):
        """The sweep only reads and verifies: a query answered while the
        scrub thread ticks continuously is the answer without it, cold
        (colcache emptied, so the read path shares the files) and warm."""
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.services.scrub import ScrubService
        from opengemini_tpu.storage import colcache

        eng = _mk_engine(tmp_path, rows=600, series=8)
        ex = Executor(eng)
        q = (f"SELECT mean(v), max(v), count(v) FROM m WHERE time >= "
             f"{BASE * NS} AND time < {(BASE + 600) * NS} "
             "GROUP BY time(1m), w")

        def run():
            ex._inc_cache.clear()
            return json.dumps(ex.execute(q, db="db"), sort_keys=True)

        quiet = run()
        s = ScrubService(eng, 0.005, mb_per_tick=4)
        bytes0 = STATS.counters("scrub").get("bytes_total", 0)
        s.start()
        try:
            beside = []
            for _ in range(6):
                colcache.GLOBAL.clear()
                beside.append(run())  # cold: decodes the scrubbed files
                beside.append(run())  # warm
        finally:
            s.stop()
        assert set(beside) == {quiet}
        assert STATS.counters("scrub").get("bytes_total", 0) > bytes0
        assert eng.quarantine_snapshot()["total"] == 0
        eng.close()

    def test_disabled_by_env_is_inert(self, tmp_path, monkeypatch):
        from opengemini_tpu.services import scrub as scrub_mod

        monkeypatch.setenv("OGT_SCRUB", "0")
        eng = _mk_engine(tmp_path, rows=50)
        s = scrub_mod.ScrubService(eng, 3600.0)
        assert not s.enabled
        assert s.tick_now() == 0
        eng.close()

    def test_quarantine_metrics_exported_strict(self, tmp_path):
        """ogt_scrub_* / ogt_quarantine_* counters and the scrub-latency
        histogram ride /metrics, and the STRICT Prometheus text parser
        still accepts the whole scrape."""
        from opengemini_tpu.server.http import HttpService
        from opengemini_tpu.services.scrub import ScrubService
        from test_observability import parse_prometheus_strict

        eng = _mk_engine(tmp_path, rows=200)
        sh = eng.shards_of_db("db")[0]
        loc = sh._files[0].data_locs()[0]
        _flip_byte(sh._files[0].path, loc[0] + 2)
        ScrubService(eng, 3600.0).tick_now()
        svc = HttpService(eng, "127.0.0.1", 0)
        svc.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/metrics",
                    timeout=30) as r:
                text = r.read().decode()
            fams = parse_prometheus_strict(text)
            assert "ogt_scrub_corruptions_found_total" in fams
            assert "ogt_scrub_bytes_total" in fams
            assert "ogt_quarantine_tsf_files_total" in fams
            assert "ogt_quarantine_files_current" in fams
            assert fams["ogt_scrub_seconds"]["type"] == "histogram"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.port}/debug/vars",
                    timeout=30) as r:
                vars_ = json.loads(r.read())
            assert vars_["quarantine"]["files_current"] >= 1
        finally:
            svc.stop()
            eng.close()

    def test_ctrl_endpoints_and_body_drain(self, tmp_path):
        """mod=diskfault / mod=scrub ctrl lifecycle, and the new early
        error replies drain the request body first (keep-alive must not
        desync — the PR 5/6 regression class)."""
        import http.client

        from opengemini_tpu.server.http import HttpService

        eng = _mk_engine(tmp_path, rows=30)
        svc = HttpService(eng, "127.0.0.1", 0)
        svc.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                              timeout=30)
            # bad action -> 400 with an UNREAD body on a keep-alive
            # connection; the next request must still parse
            body = b"x" * 4096
            conn.request("POST", "/debug/ctrl?mod=diskfault&path=*&action=bogus",
                         body=body)
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()
            conn.request("POST",
                         "/debug/ctrl?mod=diskfault&path=*.tsf&action=eio",
                         body=body)
            resp = conn.getresponse()
            assert resp.status == 200
            out = json.loads(resp.read())
            assert out["rules"] == [{"path": "*.tsf", "action": "eio"}]
            conn.request("POST", "/debug/ctrl?mod=scrub&op=bogus",
                         body=body)
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()
            conn.request("POST", "/debug/ctrl?mod=scrub&op=tick&mb=2")
            resp = conn.getresponse()
            assert resp.status == 200
            out = json.loads(resp.read())
            assert out["scrub"]["mb_per_tick"] == 2
            assert "verified_bytes" in out
            conn.request("POST", "/debug/ctrl?mod=diskfault&clear=1")
            resp = conn.getresponse()
            assert json.loads(resp.read())["rules"] == []
            conn.close()
        finally:
            svc.stop()
            eng.close()

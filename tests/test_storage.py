"""Storage layer tests: encodings, WAL, TSF files, shard, engine.

Mirrors the reference's engine-against-temp-dirs strategy
(SURVEY.md §4 item 4: engine/shard_test.go writes rows, flushes, compacts,
queries cursors directly)."""

import numpy as np
import pytest

from opengemini_tpu.record import Column, FieldType, Record
from opengemini_tpu.storage import encoding
from opengemini_tpu.storage.engine import Engine, NS
from opengemini_tpu.storage.shard import Shard
from opengemini_tpu.storage.tsf import TSFReader, TSFWriter
from opengemini_tpu.storage.wal import WAL


class TestEncoding:
    def test_int_roundtrip_regular(self):
        v = np.arange(0, 10_000_000_000, 10_000_000, dtype=np.int64)
        buf = encoding.encode_ints(v)
        assert len(buf) < 40  # constant-stride run
        np.testing.assert_array_equal(encoding.decode_ints(buf), v)

    def test_int_roundtrip_irregular(self, rng):
        v = np.cumsum(rng.integers(1, 1000, size=5000)).astype(np.int64)
        buf = encoding.encode_ints(v)
        np.testing.assert_array_equal(encoding.decode_ints(buf), v)

    def test_int_negative_deltas(self):
        v = np.array([100, 50, 200, -5, 7], dtype=np.int64)
        np.testing.assert_array_equal(encoding.decode_ints(encoding.encode_ints(v)), v)

    def test_int_single_and_empty(self):
        for v in ([], [42]):
            arr = np.array(v, dtype=np.int64)
            np.testing.assert_array_equal(encoding.decode_ints(encoding.encode_ints(arr)), arr)

    def test_float_roundtrip(self, rng):
        v = rng.normal(size=1000)
        np.testing.assert_array_equal(encoding.decode_floats(encoding.encode_floats(v)), v)

    def test_bool_roundtrip(self, rng):
        v = rng.random(77) > 0.5
        np.testing.assert_array_equal(encoding.decode_bools(encoding.encode_bools(v)), v)

    def test_string_roundtrip(self):
        v = np.array(["a", "", "héllo", "x" * 100], dtype=object)
        got = encoding.decode_strings(encoding.encode_strings(v))
        assert got.tolist() == v.tolist()

    def test_mask_allvalid_empty(self):
        m = np.ones(10, dtype=bool)
        assert encoding.encode_mask(m) == b""
        np.testing.assert_array_equal(encoding.decode_mask(b"", 10), m)


class TestWAL:
    def test_roundtrip_and_torn_tail(self, tmp_path):
        p = str(tmp_path / "wal.log")
        w = WAL(p)
        w.append_lines("cpu f=1 1", "ns", 100)
        w.append_lines("cpu f=2 2", "s", 200)
        w.flush()
        w.close()
        # corrupt tail: append garbage
        with open(p, "ab") as f:
            f.write(b"\x07\x00\x00\x00garbage")
        entries = list(WAL.replay(p))
        assert len(entries) == 2
        assert entries[0] == ("lines", b"cpu f=1 1", "ns", 100)
        assert entries[1] == ("lines", b"cpu f=2 2", "s", 200)

    def test_truncate(self, tmp_path):
        p = str(tmp_path / "wal.log")
        w = WAL(p)
        w.append_lines("cpu f=1 1", "ns", 1)
        w.truncate()
        w.append_lines("cpu f=2 2", "ns", 2)
        w.flush()
        w.close()
        entries = list(WAL.replay(p))
        assert len(entries) == 1 and entries[0][1] == b"cpu f=2 2"


class TestTSF:
    def _make_record(self, n=100):
        times = np.arange(n, dtype=np.int64) * 1_000_000_000
        vals = np.linspace(0, 1, n)
        valid = np.ones(n, dtype=bool)
        valid[::7] = False
        return Record(
            times,
            {
                "f": Column(FieldType.FLOAT, vals, valid),
                "i": Column.from_values(FieldType.INT, np.arange(n)),
                "s": Column.from_values(
                    FieldType.STRING, np.array([f"v{j}" for j in range(n)], dtype=object)
                ),
            },
        )

    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "0001.tsf")
        rec = self._make_record()
        w = TSFWriter(p)
        w.add_chunk("cpu", 1, rec)
        w.finish()
        r = TSFReader(p)
        assert r.measurements() == ["cpu"]
        chunks = r.chunks("cpu")
        assert len(chunks) == 1
        c = chunks[0]
        assert c.sid == 1 and c.rows == 100
        got = r.read_chunk("cpu", c)
        np.testing.assert_array_equal(got.times, rec.times)
        np.testing.assert_array_equal(got.columns["f"].values[got.columns["f"].valid],
                                      rec.columns["f"].values[rec.columns["f"].valid])
        np.testing.assert_array_equal(got.columns["f"].valid, rec.columns["f"].valid)
        assert got.columns["s"].values.tolist() == rec.columns["s"].values.tolist()
        r.close()

    def test_preagg(self, tmp_path):
        p = str(tmp_path / "0001.tsf")
        rec = self._make_record()
        w = TSFWriter(p)
        w.add_chunk("cpu", 1, rec)
        w.finish()
        r = TSFReader(p)
        pre = r.chunks("cpu")[0].cols["f"]["pre"]
        vals = rec.columns["f"].values[rec.columns["f"].valid]
        assert pre.count == len(vals)
        assert pre.vmin == vals.min() and pre.vmax == vals.max()
        assert np.isclose(pre.vsum, vals.sum())
        r.close()

    def test_chunk_time_pruning(self, tmp_path):
        p = str(tmp_path / "0001.tsf")
        w = TSFWriter(p)
        w.add_chunk("cpu", 1, self._make_record())  # times 0..99s
        w.finish()
        r = TSFReader(p)
        assert r.chunks("cpu", tmin=200 * NS) == []
        assert r.chunks("cpu", tmax=0) == []
        assert len(r.chunks("cpu", tmin=50 * NS, tmax=60 * NS)) == 1
        r.close()

    def test_corrupt_trailer_detected(self, tmp_path):
        from opengemini_tpu.storage.tsf import CorruptFile

        p = str(tmp_path / "0001.tsf")
        w = TSFWriter(p)
        w.add_chunk("cpu", 1, self._make_record())
        w.finish()
        with open(p, "r+b") as f:
            f.seek(-4, 2)
            f.write(b"XXXX")
        with pytest.raises(CorruptFile):
            TSFReader(p)


class TestShard:
    def test_write_flush_read(self, tmp_path):
        import opengemini_tpu.ingest.line_protocol as lp

        sh = Shard(str(tmp_path / "s1"), 0, 10**18)
        lines = "cpu,host=h1 usage=1 1000000000\ncpu,host=h1 usage=2 2000000000"
        pts = lp.parse_lines(lines)
        sh.write_points(pts, lines.encode(), "ns", 0)
        sid = sh.index.get_or_create("cpu", (("host", "h1"),))
        rec = sh.read_series("cpu", sid)
        assert rec.times.tolist() == [10**9, 2 * 10**9]
        sh.flush()
        rec = sh.read_series("cpu", sid)
        assert rec.columns["usage"].values.tolist() == [1.0, 2.0]
        sh.close()

    def test_wal_replay_after_crash(self, tmp_path):
        import opengemini_tpu.ingest.line_protocol as lp

        path = str(tmp_path / "s1")
        sh = Shard(path, 0, 10**18)
        lines = "cpu,host=h1 usage=5 1000000000"
        sh.write_points(lp.parse_lines(lines), lines.encode(), "ns", 0)
        sh.wal.flush()
        # simulate crash: no flush/close
        sh2 = Shard(path, 0, 10**18)
        sid = sh2.index.get_or_create("cpu", (("host", "h1"),))
        rec = sh2.read_series("cpu", sid)
        assert rec.columns["usage"].values.tolist() == [5.0]
        sh2.close()

    def test_dedup_across_memtable_and_file(self, tmp_path):
        import opengemini_tpu.ingest.line_protocol as lp

        sh = Shard(str(tmp_path / "s1"), 0, 10**18)
        l1 = "cpu usage=1 1000000000"
        sh.write_points(lp.parse_lines(l1), l1.encode(), "ns", 0)
        sh.flush()
        l2 = "cpu usage=9 1000000000"  # overwrite same timestamp
        sh.write_points(lp.parse_lines(l2), l2.encode(), "ns", 0)
        sid = sh.index.get_or_create("cpu", ())
        rec = sh.read_series("cpu", sid)
        assert rec.columns["usage"].values.tolist() == [9.0]
        sh.close()

    def test_compact_merges_files(self, tmp_path):
        import opengemini_tpu.ingest.line_protocol as lp

        sh = Shard(str(tmp_path / "s1"), 0, 10**18)
        for i in range(3):
            line = f"cpu usage={i} {i+1}000000000"
            sh.write_points(lp.parse_lines(line), line.encode(), "ns", 0)
            sh.flush()
        assert len(sh._files) == 3
        sh.compact()
        assert len(sh._files) == 1
        sid = sh.index.get_or_create("cpu", ())
        rec = sh.read_series("cpu", sid)
        assert rec.columns["usage"].values.tolist() == [0.0, 1.0, 2.0]
        sh.close()


class TestEngine:
    def test_write_routes_to_shards_and_reopen(self, tmp_path):
        root = str(tmp_path / "e")
        e = Engine(root)
        e.create_database("db")
        week = 7 * 24 * 3600
        # two points in different shard groups
        e.write_lines("db", f"cpu v=1 {1 * NS}\ncpu v=2 {(week + 1) * NS}")
        assert len(e.all_shards()) == 2
        e.flush_all()
        e.close()
        e2 = Engine(root)
        shards = e2.shards_for_range("db", None, 0, 2 * week * NS)
        assert len(shards) == 2
        sid = shards[0].index.get_or_create("cpu", ())
        assert shards[0].read_series("cpu", sid).columns["v"].values.tolist() == [1.0]
        e2.close()

    def test_unknown_database_raises(self, tmp_path):
        from opengemini_tpu.storage.engine import DatabaseNotFound

        e = Engine(str(tmp_path / "e"))
        with pytest.raises(DatabaseNotFound):
            e.write_lines("nope", "cpu v=1 1")
        e.close()

    def test_retention_drops_expired_shards(self, tmp_path):
        e = Engine(str(tmp_path / "e"))
        e.create_database("db")
        e.create_retention_policy("db", "short", duration_ns=2 * 24 * 3600 * NS, default=True)
        e.write_lines("db", f"cpu v=1 {1 * NS}")  # ancient point
        now = 10 * 24 * 3600 * NS
        dropped = e.drop_expired_shards(now_ns=now)
        assert len(dropped) == 1
        assert e.shards_for_range("db", "short", 0, now) == []
        e.close()

    def test_drop_database(self, tmp_path):
        e = Engine(str(tmp_path / "e"))
        e.create_database("db")
        e.write_lines("db", "cpu v=1 1")
        e.drop_database("db")
        assert e.database_names() == []
        e.close()


class TestReviewRegressions:
    """Regressions for confirmed review findings."""

    def test_type_conflict_does_not_poison_wal(self, tmp_path):
        """A rejected batch must not be WAL-logged; shard must reopen."""
        import opengemini_tpu.ingest.line_protocol as lp
        from opengemini_tpu.record import FieldTypeConflict

        path = str(tmp_path / "s1")
        sh = Shard(path, 0, 10**18)
        l1 = "cpu f=1i 1"
        sh.write_points(lp.parse_lines(l1), l1.encode(), "ns", 0)
        l2 = "cpu f=2.5 2"
        with pytest.raises(FieldTypeConflict):
            sh.write_points(lp.parse_lines(l2), l2.encode(), "ns", 0)
        sh.wal.flush()
        sh2 = Shard(path, 0, 10**18)  # must not raise
        sid = sh2.index.get_or_create("cpu", ())
        assert sh2.read_series("cpu", sid).columns["f"].values.tolist() == [1]
        sh2.close()
        sh.close()

    def test_schema_survives_flush(self, tmp_path):
        """Type-changing write after flush must still be rejected."""
        import opengemini_tpu.ingest.line_protocol as lp
        from opengemini_tpu.record import FieldTypeConflict

        sh = Shard(str(tmp_path / "s1"), 0, 10**18)
        l1 = "cpu f=1i 1"
        sh.write_points(lp.parse_lines(l1), l1.encode(), "ns", 0)
        sh.flush()
        with pytest.raises(FieldTypeConflict):
            sh.write_points(lp.parse_lines("cpu f=2.5 2"), b"cpu f=2.5 2", "ns", 0)
        sh.close()

    def test_schema_enforced_after_reopen(self, tmp_path):
        import opengemini_tpu.ingest.line_protocol as lp
        from opengemini_tpu.record import FieldTypeConflict

        path = str(tmp_path / "s1")
        sh = Shard(path, 0, 10**18)
        sh.write_points(lp.parse_lines("cpu f=1i 1"), b"cpu f=1i 1", "ns", 0)
        sh.flush()
        sh.close()
        sh2 = Shard(path, 0, 10**18)
        with pytest.raises(FieldTypeConflict):
            sh2.write_points(lp.parse_lines("cpu f=2.5 2"), b"cpu f=2.5 2", "ns", 0)
        sh2.close()

    def test_weird_tag_values_survive_reopen(self, tmp_path):
        import opengemini_tpu.ingest.line_protocol as lp

        path = str(tmp_path / "s1")
        sh = Shard(path, 0, 10**18)
        line = r"cpu,host=a\,b v=1 1"
        sh.write_points(lp.parse_lines(line), line.encode(), "ns", 0)
        sh.index.flush()
        sh.wal.flush()
        sh2 = Shard(path, 0, 10**18)
        assert sh2.index.tag_values("cpu", "host") == ["a,b"]
        sh2.close()
        sh.close()

    def test_series_key_no_aliasing(self):
        from opengemini_tpu.ingest.line_protocol import series_key

        k1 = series_key("cpu", (("host", "a"), ("x", "1")))
        k2 = series_key("cpu", (("host", "a,x=1"),))
        assert k1 != k2

    def test_out_of_range_timestamp_rejected_at_parse(self):
        import opengemini_tpu.ingest.line_protocol as lp

        with pytest.raises(lp.ParseError):
            lp.parse_lines("cpu v=1 99999999999999999999")
        with pytest.raises(lp.ParseError):
            lp.parse_lines("cpu v=99999999999999999999i 1")
        # precision multiplication overflow too
        with pytest.raises(lp.ParseError):
            lp.parse_lines("cpu v=1 9999999999999999", precision="h")


class TestNativeCodecs:
    """C++ codec library: build, roundtrip vs python fallback parity."""

    @pytest.fixture(scope="class", autouse=True)
    def built(self):
        from opengemini_tpu import native

        assert native.build(), "g++ build of native/codecs.cpp failed"
        yield

    def test_gorilla_roundtrip(self, rng):
        from opengemini_tpu import native

        for vals in (
            rng.normal(size=1000) * 1e6,
            np.repeat(50.0, 500),           # constant: ~1 bit/value
            np.arange(1000) * 0.1 + 3,
            np.array([1.5]),
            np.array([], dtype=np.float64),
            np.array([np.inf, -np.inf, 0.0, -0.0, np.nan]),
        ):
            buf = native.gorilla_encode(vals)
            assert buf is not None
            got_native = native.gorilla_decode_native(buf, len(vals))
            got_py = native.gorilla_decode_py(buf, len(vals))
            np.testing.assert_array_equal(
                got_native.view(np.uint64), vals.view(np.uint64)
            )
            np.testing.assert_array_equal(
                got_py.view(np.uint64), vals.view(np.uint64)
            )

    def test_gorilla_compresses_smooth_series(self, rng):
        from opengemini_tpu import native

        vals = np.repeat(np.arange(100.0), 10)  # slowly-changing
        buf = native.gorilla_encode(vals)
        assert len(buf) < len(vals) * 8 / 4  # at least 4x smaller

    def test_varint_roundtrip(self, rng):
        from opengemini_tpu import native

        for vals in (
            rng.integers(-(2**60), 2**60, size=500),
            np.cumsum(rng.integers(0, 1000, size=1000)),
            np.array([0, -1, 2**62, -(2**62)], dtype=np.int64),
            np.array([], dtype=np.int64),
        ):
            vals = np.asarray(vals, dtype=np.int64)
            buf = native.varint_delta_encode(vals)
            assert buf is not None
            np.testing.assert_array_equal(
                native.varint_delta_decode_native(buf, len(vals)), vals
            )
            np.testing.assert_array_equal(
                native.varint_delta_decode_py(buf, len(vals)), vals
            )

    def test_encoding_uses_native_tags(self, rng):
        # slowly-changing floats: gorilla wins over zlib and is chosen
        vals = np.repeat(np.arange(20.0), 5)
        buf = encoding.encode_floats(vals)
        assert buf[0] == 5  # _T_GORILLA
        np.testing.assert_array_equal(encoding.decode_floats(buf), vals)
        # noisy floats: whichever block wins must still roundtrip
        noisy = rng.normal(size=100)
        np.testing.assert_array_equal(
            encoding.decode_floats(encoding.encode_floats(noisy)), noisy
        )
        ints = np.cumsum(rng.integers(-5, 1000, size=100)).astype(np.int64)
        buf = encoding.encode_ints(ints)
        assert buf[0] == 6  # _T_VARINT
        np.testing.assert_array_equal(encoding.decode_ints(buf), ints)

    def test_varint_extreme_values_py_fallback(self):
        """Deltas overflowing int64 must roundtrip in BOTH decoders."""
        from opengemini_tpu import native

        vals = np.array([-(2**62), 2**62, 0, 2**63 - 1, -(2**63)], dtype=np.int64)
        buf = native.varint_delta_encode(vals)
        np.testing.assert_array_equal(
            native.varint_delta_decode_native(buf, len(vals)), vals
        )
        np.testing.assert_array_equal(
            native.varint_delta_decode_py(buf, len(vals)), vals
        )

    def test_int_encoding_adaptive_repetitive(self):
        """Repetitive deltas: FOR+zlib must win over plain varint."""
        v = np.cumsum(np.tile([0, 1], 5000)).astype(np.int64)
        buf = encoding.encode_ints(v)
        assert buf[0] == 1  # _T_DELTA (zlib path chosen)
        assert len(buf) < 200
        np.testing.assert_array_equal(encoding.decode_ints(buf), v)


class TestLeveledCompaction:
    NS = 10**9
    B = 1_700_000_000

    def _shard_with_files(self, tmp_path, n_files, rows_per=5):
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "lc"))
        e.create_database("db")
        t = self.B
        for f in range(n_files):
            lines = []
            for r in range(rows_per):
                lines.append(f"m,host=h{r % 2} v={f * 100 + r} {t * self.NS}")
                t += 1
            e.write_lines("db", "\n".join(lines))
            e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        return e, sh

    def test_merges_one_run_preserving_data(self, tmp_path):
        e, sh = self._shard_with_files(tmp_path, 6)
        before = len(sh._files)
        assert sh.compact_level(fanout=4)
        assert len(sh._files) == before - 3  # 4 -> 1
        # every row still present, once
        from opengemini_tpu.query.executor import Executor

        out = Executor(e).execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 30
        e.close()

    def test_last_write_wins_across_merge_boundary(self, tmp_path):
        """Rows rewritten in a LATER (unmerged) file must still win over
        the merged output of earlier files."""
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "lw"))
        e.create_database("db")
        T = self.B * self.NS
        for f in range(4):  # four files all writing the SAME point
            e.write_lines("db", f"m v={f} {T}")
            e.flush_all()
        e.write_lines("db", f"m v=99 {T}")  # newest, 5th file
        e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        assert sh.compact_level(fanout=4)  # merges the first four
        out = Executor(e).execute("SELECT v FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 99.0
        e.close()

    def test_no_run_no_merge(self, tmp_path):
        e, sh = self._shard_with_files(tmp_path, 3)
        assert sh.compact_level(fanout=4) is False
        e.close()

    def test_text_sidecar_written_for_merged_file(self, tmp_path):
        import glob

        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "ts"))
        e.create_database("db")
        for f in range(4):
            e.write_lines(
                "db", f'logs msg="event number{f} ok" {(self.B + f) * self.NS}')
            e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        assert sh.compact_level(fanout=4)
        assert len(glob.glob(sh.path + "/*.tidx")) == len(sh._files)
        sids = sh.text_match_sids("logs", "msg", "number2")
        assert sids and len(sids) == 1
        e.close()

    def test_service_drains_all_runs_in_one_tick(self, tmp_path):
        from opengemini_tpu.services.compaction import CompactionService

        e, sh = self._shard_with_files(tmp_path, 10)
        svc = CompactionService(e, interval_s=3600, max_files=4)
        merged = svc.handle()
        assert merged >= 2  # 10 -> 7 -> 4 within ONE tick
        assert sh.file_count() <= 4
        from opengemini_tpu.query.executor import Executor

        out = Executor(e).execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 50
        e.close()

    def test_fanout_one_never_rewrites_in_place(self, tmp_path):
        e, sh = self._shard_with_files(tmp_path, 2)
        path0 = sh._files[0].path
        import os

        mtime = os.path.getmtime(path0)
        assert sh.compact_level(fanout=1)  # floored to 2: merges the pair
        assert sh.file_count() == 1
        e.close()

    def test_crash_leftover_merge_file_swept(self, tmp_path):
        import os

        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.storage.shard import Shard

        e, sh = self._shard_with_files(tmp_path, 2)
        orphan = os.path.join(sh.path, "00000001.tsf.merge")
        with open(orphan, "wb") as f:
            f.write(b"garbage")
        path = sh.path
        e.close()
        sh2 = Shard(path, 0, 2**62)
        assert not os.path.exists(orphan)
        assert len(sh2._files) == 2  # real files untouched
        sh2.close()


class TestStringDictEncoding:
    def test_low_cardinality_dict_round_trip_and_smaller(self):
        import numpy as np

        from opengemini_tpu.storage.encoding import (
            _T_STRDICT, decode_strings, encode_strings,
        )

        vals = np.array(
            [("info", "warn", "error")[i % 3] for i in range(1000)], object)
        buf = encode_strings(vals)
        assert buf[0] == _T_STRDICT
        out = decode_strings(buf)
        assert out.tolist() == vals.tolist()
        # force-plain encoding of the SAME repeated data: the dict block
        # must beat it decisively
        from opengemini_tpu.storage import encoding as enc

        offsets = np.zeros(len(vals) + 1, dtype=np.uint32)
        parts = [v.encode() for v in vals]
        np.cumsum([len(p) for p in parts], out=offsets[1:])
        import struct
        import zlib

        plain_same = struct.pack("<BI", enc._T_STR, len(parts)) + zlib.compress(
            offsets.tobytes() + b"".join(parts), 6)
        assert len(buf) < len(plain_same) / 3  # dict wins big on repeats
        # high cardinality stays plain and round-trips
        hi = np.array([f"unique-{i}" for i in range(1000)], object)
        plain = encode_strings(hi)
        assert plain[0] != _T_STRDICT
        assert decode_strings(plain).tolist() == hi.tolist()

    def test_small_and_edge_columns(self):
        import numpy as np

        from opengemini_tpu.storage.encoding import decode_strings, encode_strings

        for data in ([], ["x"], ["", "", ""], ["a"] * 100,
                     ["日本語", "ascii"] * 50):
            vals = np.array(data, object)
            assert decode_strings(encode_strings(vals)).tolist() == data

    def test_persisted_through_tsf(self, tmp_path):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        NS, B = 10**9, 1_700_000_000
        e = Engine(str(tmp_path / "sd"))
        e.create_database("db")
        e.write_lines("db", "\n".join(
            f'logs level="{("info", "error")[i % 2]}" {(B + i) * NS}'
            for i in range(50)))
        e.flush_all()
        out = Executor(e).execute(
            "SELECT level FROM logs WHERE level = 'error'", db="db")
        assert len(out["results"][0]["series"][0]["values"]) == 25
        e.close()


class TestReadCache:
    def test_decode_happens_once_per_column(self, tmp_path):
        from opengemini_tpu.storage import encoding
        from opengemini_tpu.storage.engine import Engine

        NS, B = 10**9, 1_700_000_000
        e = Engine(str(tmp_path / "rc"))
        e.create_database("db")
        e.write_lines("db", "\n".join(
            f"m v={i} {(B + i) * NS}" for i in range(100)))
        e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        calls = []
        orig = encoding.decode_column
        encoding.decode_column = lambda *a: calls.append(1) or orig(*a)
        try:
            sid = next(iter(sh.index.series_ids("m")))
            r1 = sh.read_series("m", sid)
            v1 = r1.columns["v"].values.tolist()  # materialize
            n1 = len(calls)
            assert n1 >= 1
            r2 = sh.read_series("m", sid)
            v2 = r2.columns["v"].values.tolist()
            # cache hit: zero extra decodes
            assert len(calls) == n1
            assert v1 == v2
        finally:
            encoding.decode_column = orig
        e.close()

    def test_cache_bounded(self, tmp_path):
        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.storage.tsf import TSFReader

        NS, B = 10**9, 1_700_000_000
        e = Engine(str(tmp_path / "rb"))
        e.create_database("db")
        # many series -> many chunks -> cache pressure
        e.write_lines("db", "\n".join(
            f"m,host=h{i} v={i} {(B + i) * NS}" for i in range(700)))
        e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        r = sh._files[0]
        for c in r.chunks("m"):
            r.read_chunk("m", c)
        assert r._cache_bytes <= TSFReader._CACHE_BYTES
        e.close()

    def test_bulk_merge_bypasses_cache(self, tmp_path):
        from opengemini_tpu.storage.engine import Engine

        NS, B = 10**9, 1_700_000_000
        e = Engine(str(tmp_path / "bp"))
        e.create_database("db")
        for f in range(4):
            e.write_lines("db", "\n".join(
                f"m v={f * 10 + i} {(B + f * 10 + i) * NS}" for i in range(5)))
            e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        old = list(sh._files)
        assert sh.compact_level(fanout=4)
        for r in old:
            assert len(r._col_cache) == 0  # merge never populated caches
        e.close()

    def test_concurrent_reads_consistent(self, tmp_path):
        """pread + cache under concurrency: many threads reading the same
        chunks must all see identical, correct data."""
        import threading

        from opengemini_tpu.storage.engine import Engine

        NS, B = 10**9, 1_700_000_000
        e = Engine(str(tmp_path / "cc"))
        e.create_database("db")
        e.write_lines("db", "\n".join(
            f"m,host=h{i % 16} v={i} {(B + i) * NS}" for i in range(2000)))
        e.flush_all()
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        sids = sorted(sh.index.series_ids("m"))
        errs = []

        def worker():
            try:
                for _ in range(10):
                    for sid in sids:
                        rec = sh.read_series("m", sid)
                        v = rec.columns["v"].values
                        h = int(sh.index.tags_of(sid)["host"][1:])
                        assert (v.astype(int) % 16 == h).all()
            except Exception as ex:  # noqa: BLE001
                errs.append(ex)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs
        e.close()


def test_wal_plain_kind_roundtrip(tmp_path):
    """Batches >= 1MiB append UNCOMPRESSED (WAL kind 3) and must replay
    bit-identically after a crash (no flush before close)."""
    from opengemini_tpu.storage.engine import Engine
    from opengemini_tpu.storage.wal import WAL, _KIND_RAW_LINES_PLAIN

    NS = 10**9
    base = 1_700_000_040
    big = "\n".join(
        f"m,host=h{i % 50} v={i} {(base + i) * NS}" for i in range(40_000))
    assert len(big.encode()) >= (1 << 20)
    e = Engine(str(tmp_path), sync_wal=False)
    e.create_database("d")
    e.write_lines("d", big)
    e.write_lines("d", f"m,host=h0 v=-1 {base * NS - NS}")  # small: zlib kind
    sh = list(e._shards.values())[0]
    sh.wal.flush()
    kinds = {entry_kind for entry_kind in _wal_kinds(sh.wal.path)}
    assert _KIND_RAW_LINES_PLAIN in kinds and 1 in kinds, kinds
    # crash (no flush): reopen replays both kinds
    e2 = Engine(str(tmp_path), sync_wal=False)
    sh2 = list(e2._shards.values())[0]
    total = sum(
        len(sh2.read_series("m", sid).times)
        for sid in sh2.index.series_ids("m"))
    assert total == 40_001, total
    e2.close()
    e.close()


def _wal_kinds(path):
    import struct

    with open(path, "rb") as f:
        data = f.read()
    hdr = struct.Struct("<IIB")
    off = 0
    while off + hdr.size <= len(data):
        length, _crc, kind = hdr.unpack_from(data, off)
        yield kind
        off += hdr.size + length

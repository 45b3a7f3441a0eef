"""C++ full-text index + match() filter tests."""

import numpy as np
import pytest

from opengemini_tpu.native import build as build_native
from opengemini_tpu.native.textindex import TextIndex, match_token, tokenize
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine, NS

BASE = 1_700_000_040


@pytest.fixture(scope="module", autouse=True)
def built():
    assert build_native(), "native build failed"


def test_tokenize():
    assert tokenize("GET /api/users?id=42 HTTP/1.1") == [
        "get", "api", "users", "id", "42", "http", "1"
    ][:6] or tokenize("GET /api/users?id=42 HTTP/1.1") == [
        "get", "api", "users", "id", "42", "http", "11"
    ]


def test_index_add_search():
    idx = TextIndex()
    idx.add(1, "error: disk full on /var/log")
    idx.add(2, "user login ok")
    idx.add(3, "Disk warning threshold")
    assert idx.search("disk").tolist() == [1, 3]
    assert idx.search("DISK").tolist() == [1, 3]
    assert idx.search("login").tolist() == [2]
    assert idx.search("missing").tolist() == []
    assert idx.token_count() > 5
    idx.close()


def test_python_fallback_matches_native(monkeypatch):
    import opengemini_tpu.native.textindex as ti

    native_idx = TextIndex()
    monkeypatch.setattr(ti, "_LIB", None)
    monkeypatch.setattr(ti, "_TRIED", True)
    py_idx = TextIndex()
    docs = ["alpha beta", "beta gamma", "Gamma ALPHA delta"]
    for i, d in enumerate(docs):
        native_idx.add(i, d)
        py_idx.add(i, d)
    for tok in ("alpha", "beta", "gamma", "delta", "nope"):
        assert native_idx.search(tok).tolist() == py_idx.search(tok).tolist()
    native_idx.close()


def test_match_filter_in_where(tmp_path):
    e = Engine(str(tmp_path / "d"))
    e.create_database("db")
    lines = "\n".join([
        f'logs msg="error: disk full",level="e" {BASE * NS}',
        f'logs msg="login ok",level="i" {(BASE + 1) * NS}',
        f'logs msg="Disk replaced",level="i" {(BASE + 2) * NS}',
    ])
    e.write_lines("db", lines)
    ex = Executor(e)
    res = ex.execute(
        "SELECT msg FROM logs WHERE match(msg, 'disk')",
        db="db", now_ns=(BASE + 100) * NS,
    )
    vals = [r[1] for r in res["results"][0]["series"][0]["values"]]
    assert vals == ["error: disk full", "Disk replaced"]
    # combined with other conditions
    res = ex.execute(
        "SELECT msg FROM logs WHERE match(msg, 'disk') AND level = 'i'",
        db="db", now_ns=(BASE + 100) * NS,
    )
    vals = [r[1] for r in res["results"][0]["series"][0]["values"]]
    assert vals == ["Disk replaced"]
    e.close()


def test_match_count_aggregate(tmp_path):
    e = Engine(str(tmp_path / "d"))
    e.create_database("db")
    lines = "\n".join(
        f'logs msg="{"error x" if i % 3 == 0 else "ok"}" {(BASE + i) * NS}'
        for i in range(30)
    )
    e.write_lines("db", lines)
    ex = Executor(e)
    res = ex.execute(
        "SELECT count(msg) FROM logs WHERE match(msg, 'error')",
        db="db", now_ns=(BASE + 100) * NS,
    )
    assert res["results"][0]["series"][0]["values"][0][1] == 10
    e.close()


class TestPersistedTextIndex:
    BASE = 1_700_000_000
    NS = 10**9

    def _mk(self, tmp_path):
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "ti"))
        e.create_database("db")
        lines = "\n".join(
            f'logs,src=s{i} msg="{"error disk full" if i == 3 else "all good here"}" {(self.BASE + i) * self.NS}'
            for i in range(8)
        )
        e.write_lines("db", lines)
        return e, Executor(e)

    def test_flush_writes_sidecar_and_lookup(self, tmp_path):
        import glob

        e, ex = self._mk(tmp_path)
        e.flush_all()
        shard = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        assert glob.glob(shard.path + "/*.tidx")
        sids = shard.text_match_sids("logs", "msg", "ERROR")
        assert sids is not None and len(sids) == 1
        assert shard.index.tags_of(next(iter(sids)))["src"] == "s3"
        assert shard.text_match_sids("logs", "msg", "good") is not None
        e.close()

    def test_match_query_prunes_decode_but_stays_exact(self, tmp_path):
        e, ex = self._mk(tmp_path)
        e.flush_all()
        shard = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        calls = []
        orig = shard.read_series
        shard.read_series = lambda *a, **k: calls.append(a) or orig(*a, **k)
        out = ex.execute("SELECT msg FROM logs WHERE match(msg, 'error')",
                         db="db")["results"][0]
        rows = out["series"][0]["values"]
        assert len(rows) == 1 and "error" in rows[0][1]
        assert len(calls) == 1  # 7 non-matching series never decoded
        e.close()

    def test_memtable_rows_survive_pruning(self, tmp_path):
        e, ex = self._mk(tmp_path)
        e.flush_all()
        # new unflushed row with the token, in a NEW series
        e.write_lines("db", f'logs,src=live msg="late error" {(self.BASE + 50) * self.NS}')
        out = ex.execute("SELECT msg FROM logs WHERE match(msg, 'error')",
                         db="db")["results"][0]
        vals = sorted(r[1] for r in out["series"][0]["values"])
        assert vals == ["error disk full", "late error"]
        e.close()

    def test_missing_sidecar_means_no_prune(self, tmp_path):
        import glob
        import os

        e, ex = self._mk(tmp_path)
        e.flush_all()
        shard = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        for p in glob.glob(shard.path + "/*.tidx"):
            os.remove(p)
        shard._tidx_cache = {}
        assert shard.text_match_sids("logs", "msg", "error") is None
        out = ex.execute("SELECT msg FROM logs WHERE match(msg, 'error')",
                         db="db")["results"][0]
        assert len(out["series"][0]["values"]) == 1  # still correct
        e.close()

    def test_compaction_rebuilds_sidecar(self, tmp_path):
        e, ex = self._mk(tmp_path)
        e.flush_all()
        e.write_lines("db", f'logs,src=s9 msg="second error wave" {(self.BASE + 60) * self.NS}')
        e.flush_all()
        shard = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        assert shard.compact(max_files=1) or len(shard._files) == 1
        sids = shard.text_match_sids("logs", "msg", "error")
        assert sids is not None and len(sids) == 2  # s3 + s9 post-merge
        e.close()

    def test_or_match_does_not_prune(self, tmp_path):
        from opengemini_tpu.query import condition as cond
        from opengemini_tpu.sql.parser import Parser

        stmt = Parser("SELECT v FROM m WHERE match(msg, 'a') OR v > 1").parse_select()
        sc = cond.split(stmt.condition, set(), 0)
        assert cond.conjunctive_match_terms(sc.field_expr) == []
        stmt2 = Parser(
            "SELECT v FROM m WHERE match(msg, 'a') AND match(msg, 'b')"
        ).parse_select()
        sc2 = cond.split(stmt2.condition, set(), 0)
        assert cond.conjunctive_match_terms(sc2.field_expr) == [
            ("msg", "a"), ("msg", "b")]

    def test_windowed_fill_series_set_unchanged_by_index(self, tmp_path):
        """GROUP BY time emits fill rows for zero-match series; pruning
        must not change the emitted series set (index on vs off)."""
        import glob
        import os

        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        B, NS = self.BASE, self.NS
        e = Engine(str(tmp_path / "fw"))
        e.create_database("db")
        e.write_lines("db", "\n".join([
            f'logs,src=a msg="has error here",v=1 {B * NS}',
            f'logs,src=b msg="all fine",v=2 {(B + 1) * NS}',
        ]))
        e.flush_all()
        ex = Executor(e)
        sql = (f"SELECT count(v) FROM logs WHERE match(msg, 'error') AND "
               f"time >= {B * NS} AND time < {(B + 4) * NS} "
               "GROUP BY time(2s), src fill(0)")

        def series_set(res):
            return sorted((s["tags"]["src"], len(s["values"]))
                          for s in res.get("series", []))

        with_idx = series_set(ex.execute(sql, db="db")["results"][0])
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        for p in glob.glob(sh.path + "/*.tidx"):
            os.remove(p)
        sh._tidx_cache = {}
        without = series_set(ex.execute(sql, db="db")["results"][0])
        assert with_idx == without
        e.close()

    def test_mem_sids_for_is_cheap_mapping(self, tmp_path):
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "ms"))
        e.create_database("db")
        e.write_lines("db", f'a,t=1 v=1 {self.BASE * self.NS}\n'
                            f'b,t=2 v=2 {self.BASE * self.NS}')
        sh = e.shards_for_range("db", None, -(2**62), 2**62)[0]
        assert len(sh.mem.sids_for("a")) == 1
        assert len(sh.mem.sids_for("b")) == 1
        assert sh.mem.sids_for("zzz") == set()
        e.close()


class TestUtf8Grams:
    """UTF-8/CJK gram tokenization (reference
    SimpleGramTokenizer split-table walk, FullTextIndex.cpp:19-40)."""

    def test_tokenize_mixed(self):
        from opengemini_tpu.native.textindex import tokenize

        assert tokenize("GET /api 错误 x 日志") == [
            "get", "api", "错", "误", "日", "志"]
        assert tokenize("naïve café") == ["na", "ï", "ve", "caf", "é"]
        assert tokenize("") == []

    def test_native_and_python_agree(self):
        from opengemini_tpu.native import textindex as ti

        docs = ["启动 server ok", "error 错误日志", "plain ascii only",
                "mixed 数据 tail"]
        native = ti.TextIndex()
        assert native._lib is not None, "native lib must be built in CI"
        pyidx = ti.TextIndex.__new__(ti.TextIndex)
        pyidx._lib = None
        pyidx._post = {}
        for i, d in enumerate(docs):
            native.add(i, d)
            pyidx.add(i, d)
        for tok in ("启", "错", "误", "数", "error", "server", "plain"):
            assert sorted(native.search(tok)) == sorted(pyidx.search(tok)), tok
        assert native.token_count() == pyidx.token_count()

    def test_match_filter_end_to_end(self, tmp_path):
        """WHERE match() over CJK log lines through the real engine +
        .tidx pruning sidecars."""
        from opengemini_tpu.query.executor import Executor
        from opengemini_tpu.storage.engine import Engine

        NS = 10**9
        B = 1_700_000_040
        e = Engine(str(tmp_path), sync_wal=False)
        e.create_database("d")
        lines = [
            'logs,svc=a msg="启动日志系统完成" 1700000040000000000',
            'logs,svc=b msg="error reading disk" 1700000041000000000',
            'logs,svc=c msg="日志 rotation done" 1700000042000000000',
            'logs,svc=d msg="plain line" 1700000043000000000',
        ]
        e.write_lines("d", "\n".join(lines))
        e.flush_all()  # build the .tidx sidecars
        ex = Executor(e)
        r = ex.execute("SELECT msg FROM logs WHERE match(msg, '日志')",
                       db="d")
        vals = [v[1] for s in r["results"][0]["series"]
                for v in s["values"]]
        assert sorted(vals) == ["启动日志系统完成", "日志 rotation done"], vals
        r2 = ex.execute("SELECT msg FROM logs WHERE match(msg, 'error')",
                        db="d")
        vals2 = [v[1] for s in r2["results"][0]["series"]
                 for v in s["values"]]
        assert vals2 == ["error reading disk"]
        e.close()

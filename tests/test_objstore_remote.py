"""Remote (HTTP/S3-subset) object store: client unit tests and fault
injection on the offload/hydrate paths.

Reference: /root/reference/lib/obs (bucket client) +
engine/immutable/detached_*.go (remote layout). Faults are injected with
the failpoint framework, like the WAL/flush sites.
"""

from __future__ import annotations

import os

import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.services.obstier import ObsTierService
from opengemini_tpu.storage.engine import Engine, WriteError
from opengemini_tpu.storage.objstore import (
    HTTPObjectStore, MiniBucketServer, ObjectStoreError,
)
from opengemini_tpu.utils import failpoint

NS = 1_000_000_000
BASE = 1_700_000_040
WEEK = 7 * 86400


@pytest.fixture
def bucket():
    srv = MiniBucketServer().start()
    yield srv
    srv.stop()


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoint.disable_all()


class TestHTTPClient:
    def test_put_get_list_delete_roundtrip(self, bucket, tmp_path):
        st = HTTPObjectStore(bucket.url)
        src = tmp_path / "x.bin"
        src.write_bytes(b"hello \x00 world" * 1000)
        st.put("a/b/x.bin", str(src))
        st.put("a/b/y.bin", str(src))
        st.put("a/z.bin", str(src))
        assert st.exists("a/b/x.bin")
        assert not st.exists("a/b/missing")
        assert st.list("a/b") == ["a/b/x.bin", "a/b/y.bin"]
        dst = tmp_path / "out.bin"
        st.get("a/b/x.bin", str(dst))
        assert dst.read_bytes() == src.read_bytes()
        assert st.delete_prefix("a/b") == 2
        assert st.list("a/b") == []
        assert st.list("a") == ["a/z.bin"]

    def test_ranged_get(self, bucket, tmp_path):
        st = HTTPObjectStore(bucket.url)
        src = tmp_path / "x.bin"
        src.write_bytes(bytes(range(256)))
        st.put("r.bin", str(src))
        assert st.get_range("r.bin", 10, 5) == bytes(range(10, 15))
        assert st.get_range("r.bin", 250, 100) == bytes(range(250, 256))

    def test_missing_object_fails_loudly(self, bucket, tmp_path):
        st = HTTPObjectStore(bucket.url)
        with pytest.raises(ObjectStoreError, match="not found"):
            st.get("nope", str(tmp_path / "d"))
        assert not (tmp_path / "d").exists()
        assert not (tmp_path / "d.tmp").exists()

    def test_auth_token(self, tmp_path):
        srv = MiniBucketServer(token="sekret").start()
        try:
            src = tmp_path / "x"
            src.write_bytes(b"v")
            good = HTTPObjectStore(srv.url, token="sekret")
            good.put("k", str(src))
            assert good.exists("k")
            bad = HTTPObjectStore(srv.url, token="wrong", retries=1)
            with pytest.raises(ObjectStoreError):
                bad.put("k2", str(src))
        finally:
            srv.stop()

    def test_list_paginates(self, tmp_path):
        """Real S3 truncates ListObjectsV2 at 1000 keys; the client must
        follow continuation tokens or hydrate partial shards."""
        srv = MiniBucketServer(max_keys=7).start()
        try:
            st = HTTPObjectStore(srv.url)
            src = tmp_path / "x"
            src.write_bytes(b"v")
            names = [f"p/{i:04d}" for i in range(23)]
            for n in names:
                st.put(n, str(src))
            assert st.list("p/") == names
            assert st.delete_prefix("p/") == 23
            assert st.list("p/") == []
        finally:
            srv.stop()

    def test_keys_with_spaces(self, bucket, tmp_path):
        st = HTTPObjectStore(bucket.url)
        src = tmp_path / "x"
        src.write_bytes(b"v")
        st.put("dir with space/file name.tsf", str(src))
        assert st.list("dir with space") == ["dir with space/file name.tsf"]
        st.get("dir with space/file name.tsf", str(tmp_path / "o"))
        assert (tmp_path / "o").read_bytes() == b"v"


def _env(tmp_path, bucket):
    e = Engine(str(tmp_path / "data"))
    e.create_database("db")
    e.attach_object_store(HTTPObjectStore(bucket.url))
    lines = "\n".join(
        f"m,host=h{w % 2} v={w} {(BASE + w * WEEK) * NS}" for w in range(4))
    e.write_lines("db", lines)
    e.flush_all()
    return e, Executor(e)


class TestFaultInjection:
    def test_torn_upload_keeps_shard_local(self, tmp_path, bucket):
        """An upload dying mid-offload must leave the shard fully local
        and queryable; a later retry succeeds."""
        e, ex = _env(tmp_path, bucket)
        n_before = len(e._shards)
        failpoint.enable("objstore-put-torn", "error")
        with pytest.raises(failpoint.FailpointError):
            e.offload_shard(*sorted(e._shards)[0])
        assert len(e._shards) == n_before  # nothing moved
        assert not e.obs_shards
        out = ex.execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 4
        failpoint.disable("objstore-put-torn")
        assert e.offload_shard(*sorted(e._shards)[0])
        assert len(e.obs_shards) == 1
        e.close()

    def test_missing_object_on_hydrate_fails_query_loudly(
            self, tmp_path, bucket):
        """404 during hydration must error the query — never silently
        answer without the offloaded shard's rows."""
        e, ex = _env(tmp_path, bucket)
        ObsTierService(e, age_ns=1 * WEEK * NS).handle(
            now_ns=(BASE + 10 * WEEK) * NS)
        assert len(e.obs_shards) == 4
        failpoint.enable("objstore-get-missing", "error")
        out = ex.execute("SELECT count(v) FROM m", db="db")
        assert "could not be hydrated" in out["results"][0]["error"]
        # recovery: clear the fault, the same query hydrates and answers
        failpoint.disable("objstore-get-missing")
        out = ex.execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 4
        e.close()

    def test_torn_download_leaves_no_partial_shard(self, tmp_path, bucket):
        """A download dying mid-hydrate must not leave a partial shard
        dir that a restart would install as live (and then delete the
        bucket copy — data loss)."""
        e, ex = _env(tmp_path, bucket)
        ObsTierService(e, age_ns=1 * WEEK * NS).handle(
            now_ns=(BASE + 10 * WEEK) * NS)
        key = sorted(e.obs_shards)[0]
        failpoint.enable("objstore-get-torn", "error")
        out = ex.execute("SELECT count(v) FROM m", db="db")
        assert "could not be hydrated" in out["results"][0]["error"]
        assert not os.path.exists(e._shard_dir(*key))  # no partial dir
        e.close()
        failpoint.disable("objstore-get-torn")
        # restart: the group is still offloaded, still hydratable
        e2 = Engine(str(tmp_path / "data"))
        e2.attach_object_store(HTTPObjectStore(bucket.url))
        assert key in e2.obs_shards
        out = Executor(e2).execute("SELECT count(v) FROM m", db="db")
        assert out["results"][0]["series"][0]["values"][0][1] == 4
        e2.close()

    def test_vanished_bucket_object_fails_hydrate(self, tmp_path, bucket):
        """Objects deleted behind the engine's back (bucket lifecycle
        policy gone wrong) surface as a hydration error, not a silent
        empty shard."""
        e, ex = _env(tmp_path, bucket)
        ObsTierService(e, age_ns=1 * WEEK * NS).handle(
            now_ns=(BASE + 10 * WEEK) * NS)
        bucket.objects.clear()
        out = ex.execute("SELECT count(v) FROM m", db="db")
        assert "could not be hydrated" in out["results"][0]["error"]
        e.close()

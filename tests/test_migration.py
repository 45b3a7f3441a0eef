"""Shard migration: membership changes rebalance existing data to the
new rendezvous owners with queries correct throughout (reference:
app/ts-meta/meta/migrate_state_machine.go, engine/engine_ha.go)."""

import json
import urllib.parse
import urllib.request

import pytest

from opengemini_tpu.parallel.cluster import (
    DataRouter, RemoteScanError, owners,
)
from opengemini_tpu.server.http import HttpService
from opengemini_tpu.storage.engine import Engine

NS = 10**9
BASE = 1_700_000_000


class FsmStub:
    def __init__(self, addrs):
        self.nodes = {n: {"addr": a, "role": "data"}
                      for n, a in addrs.items()}


class StoreStub:
    token = ""

    def __init__(self, addrs):
        self.fsm = FsmStub(addrs)


def _mk_node(tmp_path, nid, addrs, store):
    e = Engine(str(tmp_path / nid))
    e.create_database("db")
    svc = HttpService(e, "127.0.0.1", 0)
    svc.start()
    addrs[nid] = f"127.0.0.1:{svc.port}"
    return e, svc


def _wire(nodes, addrs, store, rf=1):
    for nid, (e, svc) in nodes.items():
        svc.router = DataRouter(e, store, nid, addrs[nid], rf=rf)
        svc.executor.router = svc.router


def _query_count(addrs, nid):
    url = (f"http://{addrs[nid]}/query?" + urllib.parse.urlencode(
        {"q": "SELECT count(v) FROM cpu", "db": "db", "epoch": "ns"}))
    with urllib.request.urlopen(url, timeout=60) as r:
        res = json.loads(r.read())["results"][0]
    assert "error" not in res, res
    series = res.get("series")
    return series[0]["values"][0][1] if series else 0


def _write(addrs, nid, lines):
    req = urllib.request.Request(
        f"http://{addrs[nid]}/write?db=db", data=lines.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 204


def test_node_join_rebalances_data(tmp_path):
    addrs: dict = {}
    store = StoreStub(addrs)
    nodes = {}
    for nid in ("nA", "nB"):
        nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
    store.fsm = FsmStub(addrs)
    _wire(nodes, addrs, store)

    # 12 weekly points -> many shard groups spread over nA/nB
    lines = "\n".join(
        f"cpu,host=h{w % 3} v={w} {(BASE + w * 7 * 86400) * NS}"
        for w in range(12)
    )
    _write(addrs, "nA", lines)
    assert _query_count(addrs, "nA") == 12

    # nC joins: membership grows, ownership of ~1/3 of groups moves
    nodes["nC"] = _mk_node(tmp_path, "nC", addrs, store)
    store.fsm = FsmStub(addrs)  # all routers share the store object
    _wire(nodes, addrs, store)
    for nid, (e, svc) in nodes.items():
        svc.router.probe_health()

    # queries stay correct BEFORE any migration happens
    assert _query_count(addrs, "nC") == 12

    # old owners push moved groups; nC receives its share
    moved = 0
    for nid in ("nA", "nB"):
        moved += nodes[nid][1].router.migrate_round()
    assert moved > 0

    # data rebalanced: every group lives exactly on its owner
    ids = sorted(addrs)
    for nid, (e, svc) in nodes.items():
        for (db, rp, start) in e._shards:
            assert nid in owners(ids, db, rp, start, 1), (
                f"{nid} still holds group {start}")
    c_groups = len(nodes["nC"][0]._shards)
    assert c_groups > 0, "new node received no shard groups"

    # queries remain correct after rebalancing, from every coordinator
    for nid in addrs:
        assert _query_count(addrs, nid) == 12

    # steady state: nothing more to move
    for nid in addrs:
        assert nodes[nid][1].router.migrate_round() == 0

    for _nid, (e, svc) in nodes.items():
        svc.stop()
        e.close()


def test_migration_waits_for_down_owner(tmp_path):
    addrs: dict = {}
    store = StoreStub(addrs)
    nodes = {}
    for nid in ("nA", "nB"):
        nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
    store.fsm = FsmStub(addrs)
    _wire(nodes, addrs, store)
    lines = "\n".join(
        f"cpu,host=h v={w} {(BASE + w * 7 * 86400) * NS}" for w in range(8))
    _write(addrs, "nA", lines)

    # fake a membership where a dead node owns groups: nC listed but down
    addrs["nC"] = "127.0.0.1:1"  # nothing listens there
    store.fsm = FsmStub(addrs)
    for nid in ("nA", "nB"):
        nodes[nid][1].router.probe_health()
        # groups owned by the unreachable nC must NOT be dropped locally
        before = len(nodes[nid][0]._shards)
        nodes[nid][1].router.migrate_round()
        # any group whose new owner is nC stays; only moves between live
        # nodes happened — and data is never lost
    total = 0
    for nid in ("nA", "nB"):
        for (db, rp, start), sh in nodes[nid][0]._shards.items():
            for sid in sh.index.series_ids("cpu"):
                total += len(sh.read_series("cpu", sid))
    assert total == 8

    for _nid, (e, svc) in nodes.items():
        svc.stop()
        e.close()


class TestTwoPhaseMigration:
    """Pre*/Rollback semantics (reference
    engine/engine_ha.go:33-258 + migrate_state_machine.go)."""

    def _cluster(self, tmp_path, n=2):
        addrs = {}
        nodes = {}
        store = StoreStub(addrs)
        for nid in [f"n{chr(65 + i)}" for i in range(n)]:
            nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
        store.fsm.nodes = FsmStub(addrs).nodes
        _wire(nodes, addrs, store)
        for _e, svc in nodes.values():
            svc.router.probe_health()
        return nodes, addrs, store

    def test_staging_invisible_until_commit(self, tmp_path):
        nodes, addrs, _store = self._cluster(tmp_path)
        eA, _ = nodes["nA"]
        eB, svcB = nodes["nB"]
        t = (BASE // (7 * 86400) + 1) * 7 * 86400  # a clean group start
        _write(addrs, "nB", f"seed v=0 {t * NS}")  # ensures shard exists? no:
        from opengemini_tpu.record import FieldType
        from opengemini_tpu.storage.engine import shard_group_start

        start = shard_group_start(t * NS, 7 * 86400 * NS)
        eB.begin_staging("db", None, start, "mig-x-1")
        eB.write_staging("mig-x-1", [
            ("cpu", (("host", "h1"),), t * NS,
             {"v": (FieldType.FLOAT, 42.0)})])
        # staged rows are INVISIBLE to queries
        assert _query_count(addrs, "nB") == 0
        rows = eB.commit_staging("mig-x-1")
        assert rows == 1
        assert _query_count(addrs, "nB") == 1
        assert not (tmp_path / "nB" / "staging" / "mig-x-1").exists()

    def test_abort_rolls_back_cleanly(self, tmp_path):
        nodes, addrs, _store = self._cluster(tmp_path)
        eB = nodes["nB"][0]
        from opengemini_tpu.record import FieldType

        start = 0
        eB.begin_staging("db", None, start, "mig-x-2")
        eB.write_staging("mig-x-2", [
            ("cpu", (), 1000, {"v": (FieldType.FLOAT, 1.0)})])
        assert eB.abort_staging("mig-x-2")
        assert _query_count(addrs, "nB") == 0
        assert not eB.abort_staging("mig-x-2")  # idempotent

    def test_dead_pusher_staging_expires(self, tmp_path):
        """A pusher that dies mid-stream leaves staging the destination
        TTL-expires; live data never changes (the rollback that survives
        coordinator death)."""
        import os
        import time

        nodes, addrs, _store = self._cluster(tmp_path)
        eB = nodes["nB"][0]
        from opengemini_tpu.record import FieldType

        eB.begin_staging("db", None, 0, "mig-dead-1")
        eB.write_staging("mig-dead-1", [
            ("cpu", (), 1000, {"v": (FieldType.FLOAT, 9.0)})])
        # pusher dies here; the destination's idle clock ages out (a
        # LIVE stream keeps refreshing it, so long migrations survive)
        stage_dir = tmp_path / "nB" / "staging" / "mig-dead-1"
        assert stage_dir.exists()
        assert eB.expire_staging(ttl_s=900) == 0  # fresh: not expired
        eB._staging["mig-dead-1"][4] = time.perf_counter() - 3600
        assert eB.expire_staging(ttl_s=900) == 1
        assert not stage_dir.exists()
        # orphan dir from a pre-restart migration expires by content age
        orphan = tmp_path / "nB" / "staging" / "mig-orphan"
        orphan.mkdir(parents=True)
        (orphan / "wal.log").write_bytes(b"x")
        old = time.time() - 3600  # wall clock: compared against file mtime
        os.utime(orphan / "wal.log", (old, old))
        os.utime(orphan, (old, old))
        assert eB.expire_staging(ttl_s=900) == 1
        assert not orphan.exists()
        assert _query_count(addrs, "nB") == 0
        # a subsequent full retry succeeds end-to-end
        eB.begin_staging("db", None, 0, "mig-dead-2")
        eB.write_staging("mig-dead-2", [
            ("cpu", (), 1000, {"v": (FieldType.FLOAT, 9.0)})])
        assert eB.commit_staging("mig-dead-2") == 1
        assert _query_count(addrs, "nB") == 1

    def test_full_two_phase_flow_over_http(self, tmp_path):
        """migrate_round end-to-end: a new member pulls its share through
        begin/write/commit; no staging is left behind anywhere and the
        cluster still serves every point."""
        nodes, addrs, store = self._cluster(tmp_path, n=2)
        lines = "\n".join(
            f"cpu,host=h{w} v={w} {(BASE + w * 7 * 86400) * NS}"
            for w in range(10))
        _write(addrs, "nA", lines)
        # membership change: nC joins, old owners push moved groups
        nodes["nC"] = _mk_node(tmp_path, "nC", addrs, store)
        store.fsm.nodes = FsmStub(addrs).nodes
        _wire(nodes, addrs, store)
        for _e, svc in nodes.values():
            svc.router.probe_health()
        moved = sum(
            nodes[nid][1].router.migrate_round() for nid in ("nA", "nB"))
        assert moved > 0
        # nC physically received its groups; every point still queryable
        eC = nodes["nC"][0]
        local_c = sum(
            len(sh.read_series("cpu", sid).times)
            for sh in eC.shards_for_range("db", None, -(2**62), 2**62)
            for sid in sh.index.series_ids("cpu"))
        assert local_c == moved > 0
        assert _query_count(addrs, "nC") == 10
        for nid, (e, _svc) in nodes.items():
            assert not e._staging, nid


class TestMigrationPartialFailure:
    """The hairiest distributed edges (ISSUE 6): commit-ack loss,
    destination crash between fold and ack, abort racing an already-
    committed peer, and staging TTL expiry racing a live push — all must
    re-converge by LWW with zero loss and zero duplication."""

    def _cluster(self, tmp_path, nids, rf=1):
        addrs: dict = {}
        store = BalanceStoreStub(addrs)
        nodes = {}
        for nid in nids:
            nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
        store.fsm = FsmStub(addrs)
        store.fsm.placement = {}
        _wire(nodes, addrs, store, rf=rf)
        for _e, svc in nodes.values():
            svc.router.probe_health()
        return nodes, addrs, store

    def _seed_local(self, e, n=6):
        """Rows written ENGINE-level (no routing): data exists only on
        this node, whatever placement says."""
        t0 = (BASE // (7 * 86400) + 2) * 7 * 86400
        e.write_lines("db", "\n".join(
            f"cpu,host=h{i} v={i} {(t0 + i) * NS}" for i in range(n)))
        key = sorted(e._shards)[0]
        return key, n

    def _close(self, nodes):
        for _nid, (e, svc) in nodes.items():
            svc.stop()
            e.close()

    def test_commit_ack_lost_then_retried_is_idempotent(self, tmp_path):
        """The first commit lands but its ACK dies in transit; the
        pusher's retry must hit the committed-marker (ok, no restream)
        and the migration completes with exactly-once rows."""
        nodes, addrs, store = self._cluster(tmp_path, ("nA", "nB"))
        eA, svcA = nodes["nA"]
        eB, _svcB = nodes["nB"]
        routerA = svcA.router
        (db, rp, start), n = self._seed_local(eA)
        store.fsm.placement[f"{db}|{rp}|{start}"] = ["nB"]

        orig = routerA._migrate_rpc
        commits = {"n": 0}

        def lossy(peer, body):
            out = orig(peer, body)
            if body.get("phase") == "commit":
                commits["n"] += 1
                if commits["n"] == 1:  # the server committed; the ack
                    raise RemoteScanError("injected: commit ack lost")
            return out

        routerA._migrate_rpc = lossy
        try:
            assert routerA.migrate_round() == 1
        finally:
            routerA._migrate_rpc = orig
        assert commits["n"] == 2  # retried once, against the marker
        assert (db, rp, start) not in eA._shards  # drop-local happened
        # exactly once, from both coordinators
        for nid in addrs:
            assert _query_count(addrs, nid) == n
        assert not eA._staging and not eB._staging
        # the idempotence marker exists until TTL
        marks = [f for f in (tmp_path / "nB" / "staging").iterdir()
                 if f.name.endswith(".committed")]
        assert len(marks) == 1
        self._close(nodes)

    def test_commit_staging_direct_recommit_returns_ok(self, tmp_path):
        """Engine-level idempotence contract: a re-commit of a folded
        mig_id returns 0 (ok) instead of raising; an unknown mig_id
        without a marker still raises."""
        from opengemini_tpu.record import FieldType
        from opengemini_tpu.storage.engine import Engine, WriteError

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        e.begin_staging("db", None, 0, "mig-idem-1")
        e.write_staging("mig-idem-1", [
            ("cpu", (), 1000, {"v": (FieldType.FLOAT, 1.0)})])
        assert e.commit_staging("mig-idem-1") == 1
        assert e.commit_staging("mig-idem-1") == 0  # marker answers
        with pytest.raises(WriteError):
            e.commit_staging("mig-never-began")
        # markers TTL-expire like staging dirs
        import os
        import time

        mark = e._committed_marker("mig-idem-1")
        assert os.path.exists(mark)
        old = time.time() - 3600  # wall clock: compared against file mtime
        os.utime(mark, (old, old))
        e.expire_staging(ttl_s=900)
        assert not os.path.exists(mark)
        e.close()

    def test_commit_retry_racing_inflight_fold_waits_for_marker(
            self, tmp_path):
        """A retried commit arriving while the FIRST commit is still
        folding (its RPC timed out client-side; the work did not) must
        wait out the fold and answer ok from the marker — not 400
        'unknown migration', which would abort + restream a move that
        is completing."""
        import threading
        import time

        from opengemini_tpu.record import FieldType
        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.utils import failpoint

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        e.begin_staging("db", None, 0, "mig-race-1")
        e.write_staging("mig-race-1", [
            ("cpu", (), 1000, {"v": (FieldType.FLOAT, 1.0)})])
        failpoint.enable("engine-staging-commit-before-marker",
                         "wait:fold-gate")
        first: dict = {}
        second: dict = {}
        try:
            t1 = threading.Thread(
                target=lambda: first.update(
                    rows=e.commit_staging("mig-race-1")))
            t1.start()
            for _ in range(200):  # fold in flight (popped, gated)
                if "mig-race-1" in e._folding:
                    break
                time.sleep(0.01)
            assert "mig-race-1" in e._folding
            t2 = threading.Thread(
                target=lambda: second.update(
                    rows=e.commit_staging("mig-race-1")))
            t2.start()
            time.sleep(0.15)
            assert not second  # the retry WAITS, it does not 400
            failpoint.set_event("fold-gate")
            t1.join(10)
            t2.join(10)
        finally:
            failpoint.disable("engine-staging-commit-before-marker")
        assert first["rows"] == 1
        assert second["rows"] == 0  # answered from the marker
        assert not e._staging and not e._folding
        e.close()

    def test_destination_crash_between_fold_and_ack(self, tmp_path):
        """Kill (error-inject) the destination BETWEEN the staging fold
        and the marker write: rows are live (durable fold), the pusher
        sees a failed commit and aborts, a later full re-push LWW-merges
        without duplicating."""
        from opengemini_tpu.record import FieldType
        from opengemini_tpu.storage.engine import Engine
        from opengemini_tpu.utils import failpoint

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        pts = [("cpu", (("host", "h1"),), 1000 + i,
                {"v": (FieldType.FLOAT, float(i))}) for i in range(5)]
        e.begin_staging("db", None, 0, "mig-crash-1")
        e.write_staging("mig-crash-1", pts)
        failpoint.enable("engine-staging-commit-before-marker", "error")
        try:
            with pytest.raises(failpoint.FailpointError):
                e.commit_staging("mig-crash-1")
        finally:
            failpoint.disable_all()

        def rows():
            return sum(
                len(sh.read_series("cpu", sid))
                for sh in e.shards_of_db("db")
                for sid in sh.index.series_ids("cpu"))

        assert rows() == 5  # the fold IS durable
        # no marker: a retried commit of the dead mig correctly fails,
        # and the pusher's full retry (new mig id) dedups by LWW
        import os

        assert not os.path.exists(e._committed_marker("mig-crash-1"))
        e.begin_staging("db", None, 0, "mig-crash-2")
        e.write_staging("mig-crash-2", pts)
        assert e.commit_staging("mig-crash-2") == 5
        assert rows() == 5  # exactly once
        # the orphaned staging dir from the crash TTL-expires
        import time

        orphan = tmp_path / "d" / "staging" / "mig-crash-1"
        assert orphan.exists()
        old = time.time() - 3600  # wall clock: compared against file mtime
        for f in orphan.iterdir():
            os.utime(f, (old, old))
        os.utime(orphan, (old, old))
        assert e.expire_staging(ttl_s=900) >= 1
        assert not orphan.exists()
        assert not e.durability_check()
        e.close()

    def test_abort_after_partial_commit_reconverges_lww(self, tmp_path):
        """rf=2, owners forced to (nB, nC): commit lands on nB, fails
        persistently on nC -> the pusher aborts everywhere (the abort to
        already-committed nB must NOT undo the fold), keeps its copy,
        and the NEXT round re-pushes both — LWW re-convergence, exactly
        once from every coordinator."""
        nodes, addrs, store = self._cluster(
            tmp_path, ("nA", "nB", "nC"), rf=2)
        eA, svcA = nodes["nA"]
        eB, _ = nodes["nB"]
        eC, _ = nodes["nC"]
        routerA = svcA.router
        (db, rp, start), n = self._seed_local(eA)
        store.fsm.placement[f"{db}|{rp}|{start}"] = ["nB", "nC"]

        orig = routerA._migrate_rpc

        def c_commit_fails(peer, body):
            if peer == "nC" and body.get("phase") == "commit":
                raise RemoteScanError("injected: nC commit always fails")
            return orig(peer, body)

        routerA._migrate_rpc = c_commit_fails
        try:
            assert routerA.migrate_round() == 0  # aborted, nothing moved
        finally:
            routerA._migrate_rpc = orig
        # nA kept its copy; nB holds the committed fold; nC rolled back
        assert (db, rp, start) in eA._shards
        assert not eB._staging and not eC._staging

        def local_rows(e):
            return sum(
                len(sh.read_series("cpu", sid))
                for sh in e.shards_of_db("db")
                for sid in sh.index.series_ids("cpu"))

        assert local_rows(eB) == n and local_rows(eC) == 0
        # reads are correct even in the partial state (primary nB serves,
        # nA's retained copy is rf>1-filtered)
        for nid in addrs:
            assert _query_count(addrs, nid) == n
        # heal: the next round re-pushes to BOTH (LWW into nB's live
        # rows), commits, and drops the local copy
        assert routerA.migrate_round() == 1
        assert (db, rp, start) not in eA._shards
        assert local_rows(eB) == n and local_rows(eC) == n
        for nid in addrs:
            assert _query_count(addrs, nid) == n
        self._close(nodes)

    def test_abort_to_committed_peer_over_http_is_safe(self, tmp_path):
        """The abort RPC against an already-committed mig answers ok
        without undoing the fold (ok semantics the rollback loop relies
        on), and against an unknown mig is a no-op."""
        nodes, addrs, _store = self._cluster(tmp_path, ("nA", "nB"))
        eB, svcB = nodes["nB"]
        from opengemini_tpu.record import FieldType

        eB.begin_staging("db", None, 0, "mig-ab-1")
        eB.write_staging("mig-ab-1", [
            ("cpu", (), 1000, {"v": (FieldType.FLOAT, 7.0)})])
        assert eB.commit_staging("mig-ab-1") == 1
        body = json.dumps({"db": "db", "phase": "abort",
                           "mig_id": "mig-ab-1"}).encode()
        req = urllib.request.Request(
            f"http://{addrs['nB']}/internal/migrate", data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            got = json.loads(r.read())
        assert got["ok"] is True and got["aborted"] is False
        assert _query_count(addrs, "nB") == 1  # the fold survived
        self._close(nodes)

    def test_staging_ttl_expiry_racing_live_push(self, tmp_path):
        """A TTL sweep that fires mid-push (e.g. a pusher stalled past
        the deadline) drops the staging area; the pusher's NEXT write or
        commit fails cleanly (WriteError -> abort path), never folds a
        truncated copy, and a full retry succeeds."""
        import time

        from opengemini_tpu.record import FieldType
        from opengemini_tpu.storage.engine import Engine, WriteError

        e = Engine(str(tmp_path / "d"))
        e.create_database("db")
        pts = [("cpu", (), 1000 + i, {"v": (FieldType.FLOAT, 1.0)})
               for i in range(4)]
        e.begin_staging("db", None, 0, "mig-ttl-1")
        e.write_staging("mig-ttl-1", pts[:2])
        e._staging["mig-ttl-1"][4] = time.perf_counter() - 3600  # stalled pusher
        assert e.expire_staging(ttl_s=900) == 1
        with pytest.raises(WriteError, match="unknown migration"):
            e.write_staging("mig-ttl-1", pts[2:])
        with pytest.raises(WriteError, match="unknown migration"):
            e.commit_staging("mig-ttl-1")

        def rows():
            return sum(
                len(sh.read_series("cpu", sid))
                for sh in e.shards_of_db("db")
                for sid in sh.index.series_ids("cpu"))

        assert rows() == 0  # nothing half-folded
        e.begin_staging("db", None, 0, "mig-ttl-2")
        e.write_staging("mig-ttl-2", pts)
        assert e.commit_staging("mig-ttl-2") == 4
        assert rows() == 4
        e.close()


class BalanceStoreStub(StoreStub):
    """StoreStub + placement dict + synchronous propose (applies the
    placement op directly, standing in for the raft round trip)."""

    def __init__(self, addrs):
        super().__init__(addrs)
        self.fsm.placement = {}

    def is_leader(self):
        return True

    def propose_and_wait(self, cmd, timeout_s=10.0):
        if cmd["op"] == "set_placement":
            self.fsm.placement[cmd["key"]] = list(cmd["owners"])
            return True
        if cmd["op"] == "drop_placement":
            self.fsm.placement.pop(cmd["key"], None)
            return True
        return False


def test_load_balance_moves_heavy_group(tmp_path):
    """Load-aware balancing (reference: balance_manager.go): a byte-size
    skew with stable membership triggers a placement override through
    the meta store, and the heavy node's own migrate_round then streams
    the group to the light node."""
    addrs: dict = {}
    store = BalanceStoreStub(addrs)
    nodes = {}
    for nid in ("nA", "nB"):
        nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
    store.fsm = FsmStub(addrs)
    store.fsm.placement = {}
    _wire(nodes, addrs, store)
    for nid in addrs:
        nodes[nid][1].router.probe_health()

    # many groups; rendezvous spreads them — then skew is FORCED by
    # writing a fat measurement into one specific group
    lines = "\n".join(
        f"cpu,host=h{w % 3} v={w} {(BASE + w * 7 * 86400) * NS}"
        for w in range(8))
    _write(addrs, "nA", lines)
    for nid in addrs:
        nodes[nid][0].flush_all()

    # find a group held by nA and fatten it locally
    heavy_nid = "nA"
    e_heavy = nodes[heavy_nid][0]
    assert e_heavy._shards, "nA holds no groups; rewrite the test data"
    (hdb, hrp, hstart) = sorted(e_heavy._shards)[0]
    fat = "\n".join(
        f"cpu,host=h0 v={i},pad=\"{'x' * 64}\" {hstart + i}"
        for i in range(30_000))
    e_heavy.write_lines("db", fat)
    e_heavy.flush_all()

    router = nodes[heavy_nid][1].router
    loads = router.collect_loads()
    assert set(loads) == {"nA", "nB"}
    move = router.balance_round(min_skew_bytes=1, skew_ratio=1.05)
    assert move is not None, loads
    assert move["from"] == heavy_nid and move["to"] == "nB"
    mdb, mrp, mstart = move["group"].split("|")
    mkey = (mdb, mrp, int(mstart))
    assert mkey in e_heavy._shards  # a group nA actually held
    assert store.fsm.placement[move["group"]] == move["owners"]
    # the chosen group cannot be bigger than 3/4 of the skew — moving
    # the fattened (skew-sized) group would just flip the imbalance
    skew = loads["nA"]["total"] - loads["nB"]["total"]
    assert move["bytes"] <= skew * 0.75

    # the override changes ownership everywhere
    for nid in addrs:
        got = nodes[nid][1].router.group_owners(mdb, mrp, int(mstart))
        assert got == move["owners"]

    # the heavy node sheds the group through the standard machinery
    n_before = _query_count(addrs, "nA")
    moved = router.migrate_round()
    assert moved >= 1
    assert mkey not in e_heavy._shards
    assert mkey in nodes[move["to"]][0]._shards
    # no rows lost, from either coordinator
    for nid in addrs:
        assert _query_count(addrs, nid) == n_before

    # steady state: balanced enough, no further moves
    assert router.balance_round(min_skew_bytes=1 << 40) is None

    for _nid, (e, svc) in nodes.items():
        svc.stop()
        e.close()


def test_placement_override_ignores_vanished_nodes(tmp_path):
    addrs: dict = {}
    store = BalanceStoreStub(addrs)
    nodes = {}
    for nid in ("nA", "nB"):
        nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
    store.fsm = FsmStub(addrs)
    store.fsm.placement = {"db|autogen|0": ["ghost"]}
    _wire(nodes, addrs, store)
    router = nodes["nA"][1].router
    # every listed owner vanished: rendezvous wins, group not black-holed
    got = router.group_owners("db", "autogen", 0)
    assert got and "ghost" not in got
    # partially vanished: surviving override owners win
    store.fsm.placement["db|autogen|0"] = ["ghost", "nB"]
    assert router.group_owners("db", "autogen", 0) == ["nB"]
    for _nid, (e, svc) in nodes.items():
        svc.stop()
        e.close()


def test_balance_override_keeps_a_data_holding_primary(tmp_path):
    """With rf>1 the balance override must keep a retained (data-holding)
    owner FIRST so primary-filtered reads never black-hole the group
    while migration is still pending."""
    addrs: dict = {}
    store = BalanceStoreStub(addrs)
    nodes = {}
    for nid in ("nA", "nB", "nC"):
        nodes[nid] = _mk_node(tmp_path, nid, addrs, store)
    store.fsm = FsmStub(addrs)
    store.fsm.placement = {}
    _wire(nodes, addrs, store, rf=2)
    for nid in addrs:
        nodes[nid][1].router.probe_health()
    lines = "\n".join(
        f"cpu,host=h{w % 3} v={w} {(BASE + w * 7 * 86400) * NS}"
        for w in range(8))
    _write(addrs, "nA", lines)
    for nid in addrs:
        nodes[nid][0].flush_all()
    # fatten several groups on whichever node is heaviest so some group
    # under the 75%-skew cap exists
    router = nodes["nA"][1].router
    loads = router.collect_loads()
    hot = max(loads, key=lambda n: loads[n]["total"])
    e_hot = nodes[hot][0]
    for i, key in enumerate(sorted(e_hot._shards)):
        db, rp, start = key
        fat = "\n".join(
            f"cpu,host=h0 v={j},pad=\"{'y' * 32}\" {start + j}"
            for j in range(4000 * (i % 3 + 1)))
        e_hot.write_lines("db", fat)
    e_hot.flush_all()
    move = nodes[hot][1].router.balance_round(
        min_skew_bytes=1, skew_ratio=1.01)
    if move is None:
        return  # loads happened to balance; nothing to assert
    # primary (first owner) must be a RETAINED owner that holds the
    # data, never the empty destination
    assert move["owners"][0] != move["to"] or len(move["owners"]) == 1
    mdb, mrp, mstart = move["group"].split("|")
    if len(move["owners"]) > 1:
        holder = move["owners"][0]
        assert (mdb, mrp, int(mstart)) in nodes[holder][0]._shards
    for _nid, (e, svc) in nodes.items():
        svc.stop()
        e.close()


def test_invalid_namespace_names_rejected(tmp_path):
    from opengemini_tpu.storage.engine import Engine, WriteError
    import pytest as _pytest

    e = Engine(str(tmp_path / "d"))
    for bad in ("a|b", "a/b", "a\\b", "", ".", "a\nb"):
        with _pytest.raises(WriteError):
            e.create_database(bad)
    e.create_database("ok")
    with _pytest.raises(WriteError):
        e.create_retention_policy("ok", "r|p", 0)
    e.close()

#!/usr/bin/env python
"""Cluster-tier fault-injection torture: zero acked-row loss during
live shard moves, node crashes, and partitions.

The single-node harness (tools/torture.py) proves the storage engine's
acked-write contract under kill -9.  This harness proves the DISTRIBUTED
contract on a real rf>=2 cluster of subprocess nodes (full server
stack: meta raft, data routing, hinted handoff, two-phase migration,
anti-entropy):

    once a client write is ACKED at its consistency level, the row is
    readable — exactly once, with its exact value, from EVERY
    coordinator — after any mix of node kills (failpoint panic at armed
    cluster sites, or SIGKILL), network partitions (netfault drop rules,
    healed), and forced balancer moves, once the cluster re-converges
    (restart + hint replay + anti-entropy).

One round:
  1. (quick: fixed schedule; full: randomized) choose a fault — arm a
     cluster failpoint `panic#k` on a victim via /debug/ctrl, SIGKILL a
     node mid-traffic, partition a node pair with netfault drops, or an
     ELASTIC membership round (join a brand-new node, rebalance onto
     it, decommission an original via drain-then-remove with a
     partition stacked mid-drain) — optionally stacked with a FORCED
     shard move (op=move placement override + migrate rounds) so the
     two-phase migration path is live while the fault fires;
  2. drive tools/loadgen.py traffic against every coordinator (mixed
     consistency levels one+quorum, per-batch fsynced ack journal);
  3. heal: clear netfault rules, disarm surviving failpoints, restart
     dead nodes over their data dirs, force hint-replay + migrate +
     anti-entropy rounds until the cluster is quiet;
  4. verify: every journaled acked batch readable exactly once with
     exact values from every node, per-node durability ledgers clean
     (POST /debug/ctrl?mod=durability), no staging areas left behind.

Usage:
    python tools/cluster_torture.py --quick           # tier-1: fixed
                                                      #  schedule, ~60s
    python tools/cluster_torture.py --rounds 50 --seed 7   # full
                                                      #  randomized run
Exit status 0 = no violation; 1 = acked-row loss/duplication or a dirty
ledger (details on stdout as JSON).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools import loadgen  # noqa: E402

NS = 1_000_000_000
# wider than a weekly shard-group duration (6.048e14 ns): clients land
# in distinct groups, so moves/kills hit several groups at once
TS_SCALE = 10 ** 15
MST = "t"
DB = "load"

# every armed cluster-tier failpoint site (coordinator and replica
# side).  tests/test_torture.py asserts this catalog and the `_fp(...)`
# sites in the code agree both ways — a site added to the code must
# enter this rotation (or the test's exemption set) to be covered.
KILL_SITES = [
    # coordinator: routed-write fan-out + hinted handoff
    "cluster-write-before-forward",
    "cluster-write-before-hint",
    "cluster-hint-before-append",
    "cluster-hint-after-append",
    "cluster-replay-before-forward",
    "cluster-replay-before-requeue",
    # coordinator: two-phase migration push
    "cluster-migrate-before-begin",
    "cluster-migrate-before-push",
    "cluster-migrate-before-commit",
    "cluster-migrate-after-commit",
    "cluster-migrate-before-drop-local",
    "cluster-migrate-before-abort",
    # coordinator: anti-entropy + scan failover
    "cluster-antientropy-before-digest",
    "cluster-antientropy-before-pull",
    "cluster-antientropy-before-merge",
    "cluster-scan-failover",
    # replica: /internal/* handlers
    "internal-write-before-apply",
    "internal-write-before-reply",
    "internal-migrate-begin",
    "internal-migrate-write",
    "internal-migrate-commit",
    "internal-migrate-commit-before-reply",
    "internal-migrate-abort",
    # destination engine: between staging fold and the durable
    # commit-idempotence marker
    "engine-staging-commit-before-marker",
]

# sites that need a shard move in flight to fire
_MIGRATION_SITES = {s for s in KILL_SITES if "migrate" in s or
                    s == "engine-staging-commit-before-marker"}
# sites that need a dead/unreachable peer to fire
_HINT_SITES = {"cluster-write-before-hint", "cluster-hint-before-append",
               "cluster-hint-after-append", "cluster-replay-before-forward",
               "cluster-replay-before-requeue", "cluster-scan-failover"}
# sites that need replica divergence (partition + heal) to fire
_AE_SITES = {s for s in KILL_SITES if "antientropy" in s}


def _free_ports(n: int) -> list[int]:
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class Node:
    """One subprocess ts-server node (full stack) + its HTTP handle."""

    def __init__(self, nid: str, port: int, workdir: str,
                 peer_specs: list[str], rf: int, join: str | None = None):
        self.nid = nid
        self.port = port
        self.addr = f"127.0.0.1:{port}"
        self.workdir = workdir
        self.data_dir = os.path.join(workdir, nid)
        self.log_path = os.path.join(workdir, f"{nid}.log")
        self.cfg_path = os.path.join(workdir, f"{nid}.toml")
        self.proc: subprocess.Popen | None = None
        self._logf = None
        peers_toml = ", ".join(f'"{p}"' for p in peer_specs)
        # an elastic joiner knows only itself + its seed; it enters the
        # meta group via /raft/join and the data roster via the
        # registrar (the path an operator's `op=add` also covers)
        join_toml = f'join = "{join}"\n' if join else ""
        with open(self.cfg_path, "w", encoding="utf-8") as f:
            f.write(f"""\
[data]
dir = "{self.data_dir}"
wal-fsync = true
flush-threshold-mb = 1

[http]
bind-address = "127.0.0.1:{port}"

[meta]
node-id = "{nid}"
peers = [{peers_toml}]
advertise = "{self.addr}"
{join_toml}
[cluster]
data-routing = true
replication-factor = {rf}
write-consistency = "quorum"
hint-interval-s = 0.5
anti-entropy-interval-s = 1.0
migration-interval-s = 1.0
migration-staging-ttl-s = 120
balance-interval-s = 0

[services]
store-monitor = false
compact-interval-s = 2
scrub-interval-s = 3600
retention-interval-s = 3600
downsample-interval-s = 3600
cq-interval-s = 3600
stream-interval-s = 3600
iodetector-interval-s = 3600
sherlock-interval-s = 3600
""")

    def spawn(self, failpoints: str | None = None) -> None:
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "OGT_WAL_GROUP_COMMIT_US": "0",
            # the RPC hardening under test: short probes, one transient
            # retry, a live circuit breaker
            "OGT_PROBE_TIMEOUT_S": "1",
            "OGT_RPC_RETRIES": "1",
            "OGT_RPC_BACKOFF_MS": "25",
            "OGT_CB_THRESHOLD": "4",
            "OGT_CB_COOLDOWN_S": "1",
        })
        for k in ("OGTPU_FAILPOINTS", "OGT_NETFAULT", "OGT_MEM_BUDGET_MB"):
            env.pop(k, None)
        if failpoints:
            env["OGTPU_FAILPOINTS"] = failpoints
        self._logf = open(self.log_path, "a", encoding="utf-8")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "opengemini_tpu.server.app",
             "-config", self.cfg_path],
            cwd=_ROOT, env=env, stdout=self._logf,
            stderr=subprocess.STDOUT)

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def returncode(self):
        return None if self.proc is None else self.proc.poll()

    def kill(self) -> None:
        if self.alive():
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(timeout=30)

    def terminate(self) -> None:
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.kill()
        if self._logf:
            self._logf.close()
            self._logf = None

    # -- HTTP helpers -----------------------------------------------------

    def _url(self, path: str, params: dict | None = None) -> str:
        url = f"http://{self.addr}{path}"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        return url

    def get(self, path: str, params: dict | None = None,
            timeout: float = 10.0) -> dict:
        with urllib.request.urlopen(self._url(path, params),
                                    timeout=timeout) as r:
            body = r.read()
        return json.loads(body) if body.strip() else {}

    def ctrl(self, mod: str, timeout: float = 60.0, **params) -> dict:
        req = urllib.request.Request(
            self._url("/debug/ctrl", dict(params, mod=mod)), method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    def query(self, q: str, timeout: float = 60.0) -> dict:
        req = urllib.request.Request(
            self._url("/query"),
            data=urllib.parse.urlencode({"q": q, "db": DB,
                                         "epoch": "ns"}).encode(),
            headers={"Content-Type":
                     "application/x-www-form-urlencoded"},
            method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    def arm(self, site: str, action: str) -> None:
        self.ctrl("failpoint", name=site, action=action)

    def disarm_all(self) -> None:
        try:
            active = self.ctrl("failpoint").get("active", {})
        except (OSError, ValueError):
            return
        for site in active:
            try:
                self.ctrl("failpoint", name=site, action="off")
            except (OSError, ValueError):
                pass

    def netfault_clear(self) -> None:
        try:
            self.ctrl("netfault", clear="1")
        except (OSError, ValueError):
            pass


class Cluster:
    def __init__(self, workdir: str, n: int = 3, rf: int = 2):
        ports = _free_ports(n)
        nids = [f"n{i + 1}" for i in range(n)]
        specs = [f"{nid}@127.0.0.1:{port}"
                 for nid, port in zip(nids, ports)]
        self.workdir = workdir
        self.rf = rf
        self._next_nid = n + 1
        self.nodes = [Node(nid, port, workdir, specs, rf)
                      for nid, port in zip(nids, ports)]
        self.by_id = {node.nid: node for node in self.nodes}

    def add_elastic_node(self, seed: Node) -> Node:
        """Spawn a brand-new node that JOINS the live cluster via its
        seed (meta /raft/join + data-roster registrar) — the elastic
        grow path, exercised under full traffic."""
        port = _free_ports(1)[0]
        nid = f"n{self._next_nid}"
        self._next_nid += 1
        node = Node(nid, port, self.workdir,
                    [f"{nid}@127.0.0.1:{port}"], self.rf, join=seed.addr)
        self.nodes.append(node)
        self.by_id[nid] = node
        node.spawn()
        return node

    def remove(self, node: Node) -> None:
        """Retire a decommissioned node from the harness roster: its
        process stops and wait_ready/converge/verify stop expecting it."""
        node.terminate()
        if node in self.nodes:
            self.nodes.remove(node)
        self.by_id.pop(node.nid, None)

    def spawn_all(self) -> None:
        for node in self.nodes:
            node.spawn()

    def stop_all(self) -> None:
        for node in self.nodes:
            node.terminate()

    def leader(self, timeout: float = 30.0) -> Node:
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            for node in self.nodes:
                if not node.alive():
                    continue
                try:
                    st = node.get("/raft/status", timeout=3)
                except (OSError, ValueError):
                    continue
                lead = st.get("leader")
                if lead and lead in self.by_id and self.by_id[lead].alive():
                    return self.by_id[lead]
            time.sleep(0.2)
        raise TimeoutError("no meta leader elected")

    def wait_ready(self, timeout: float = 90.0) -> None:
        """Every node serving, every data node registered + healthy in
        the quorum view, the database replicated everywhere."""
        deadline = time.perf_counter() + timeout
        for node in self.nodes:
            while True:
                try:
                    req = urllib.request.Request(node._url("/ping"))
                    with urllib.request.urlopen(req, timeout=2) as r:
                        if r.status in (200, 204):
                            break
                except OSError:
                    pass
                if time.perf_counter() > deadline:
                    raise TimeoutError(f"{node.nid} never served /ping")
                time.sleep(0.2)
        want = {node.nid for node in self.nodes}
        while True:
            try:
                got = self.nodes[0].ctrl("cluster", op="health",
                                         timeout=15).get("health", {})
                if want <= {k for k, v in got.items() if v}:
                    break
            except (OSError, ValueError):
                pass
            if time.perf_counter() > deadline:
                raise TimeoutError(f"cluster never converged: {want}")
            time.sleep(0.3)
        # replicated DDL goes through the meta leader
        while True:
            try:
                res = self.leader().query(f"CREATE DATABASE {DB}")[
                    "results"][0]
                if "error" not in res or "exists" in res["error"]:
                    break
            except (OSError, ValueError, KeyError, TimeoutError):
                pass
            if time.perf_counter() > deadline:
                raise TimeoutError("CREATE DATABASE never committed")
            time.sleep(0.3)
        for node in self.nodes:
            while True:
                try:
                    res = node.query("SHOW DATABASES")["results"][0]
                    vals = [v[0] for s in res.get("series", [])
                            for v in s.get("values", [])]
                    if DB in vals:
                        break
                except (OSError, ValueError, KeyError):
                    pass
                if time.perf_counter() > deadline:
                    raise TimeoutError(f"{node.nid} never saw {DB}")
                time.sleep(0.2)

    # -- fault levers ------------------------------------------------------

    def partition(self, a: Node, b: Node) -> None:
        """Symmetric partition via mirrored client-side drop rules (each
        side drops its OUTBOUND traffic to the other)."""
        a.ctrl("netfault", src="*", dst=b.addr, path="*", action="drop")
        b.ctrl("netfault", src="*", dst=a.addr, path="*", action="drop")

    def heal(self) -> None:
        for node in self.nodes:
            if node.alive():
                node.netfault_clear()
                node.disarm_all()

    def force_move(self) -> dict | None:
        """Propose a placement override through whichever node is meta
        leader and can find a movable group; the shedding node's
        migrate rounds stream the data."""
        for node in self.nodes:
            if not node.alive():
                continue
            try:
                got = self.ctrl_move(node)
            except (OSError, ValueError):
                continue
            if got:
                return got
        return None

    @staticmethod
    def ctrl_move(node: Node) -> dict | None:
        return node.ctrl("cluster", op="move", db=DB).get("move")

    def restart_dead(self) -> list[str]:
        restarted = []
        for node in self.nodes:
            if not node.alive():
                if node._logf:
                    node._logf.close()
                node.spawn()  # over the surviving data dir: WAL replay
                restarted.append(node.nid)
        return restarted

    def converge(self, timeout: float = 60.0) -> list[str]:
        """Heal + force service rounds until the cluster is QUIET: no
        pending hints, no staging areas, migrate/hint/anti-entropy
        rounds all report zero work — twice in a row (one quiet sweep
        can race a round that was already in flight)."""
        problems: list[str] = []
        deadline = time.perf_counter() + timeout
        quiet_sweeps = 0
        while time.perf_counter() < deadline:
            busy = []
            for node in self.nodes:
                if not node.alive():
                    busy.append(f"{node.nid} dead")
                    continue
                try:
                    node.ctrl("cluster", op="health", timeout=20)
                    h = node.ctrl("cluster", op="hints", timeout=30)
                    # short staging TTL here MODELS TIME PASSING: a
                    # killed pusher's abandoned staging areas are
                    # designed to roll back by TTL expiry — the harness
                    # fast-forwards that clock instead of waiting out
                    # the production default (a LIVE push refreshes its
                    # idle stamp every batch, so 15s cannot reap one)
                    m = node.ctrl("cluster", op="migrate",
                                  staging_ttl_s=15, timeout=120)
                    ae = node.ctrl("cluster", op="antientropy",
                                   timeout=120)
                except (OSError, ValueError) as e:
                    busy.append(f"{node.nid} ctrl: {e}")
                    continue
                if h.get("delivered") or m.get("moved") or \
                        ae.get("repaired") or h.get("pending_hints") or \
                        ae.get("staging"):
                    busy.append(
                        f"{node.nid} delivered={h.get('delivered')} "
                        f"moved={m.get('moved')} "
                        f"repaired={ae.get('repaired')} "
                        f"pending={h.get('pending_hints')} "
                        f"staging={ae.get('staging')}")
            if not busy:
                quiet_sweeps += 1
                if quiet_sweeps >= 2:
                    return []
            else:
                quiet_sweeps = 0
            time.sleep(0.3)
        problems.append(f"cluster never quiesced: {busy}")
        return problems


# -- traffic ----------------------------------------------------------------


class Traffic:
    """loadgen in a thread, against every live coordinator."""

    def __init__(self, cluster: Cluster, duration_s: float, clients: int,
                 offset: int, ack_log: str):
        self.out: dict | None = None
        targets = [node.addr for node in cluster.nodes]

        def run():
            self.out = loadgen.run_load(
                "127.0.0.1", cluster.nodes[0].port, DB, clients=clients,
                duration_s=duration_s, write_frac=0.85, batch_rows=25,
                measurement=MST, targets=targets,
                consistency=["one", "quorum"], ack_log=ack_log,
                client_offset=offset, ts_scale=TS_SCALE, timeout_s=15.0)

        self.thread = threading.Thread(target=run, daemon=True,
                                       name="cluster-torture-load")

    def start(self) -> "Traffic":
        self.thread.start()
        return self

    def join(self, timeout: float) -> dict:
        self.thread.join(timeout)
        return self.out or {}


def read_acks(path: str) -> list[dict]:
    out = []
    if not os.path.exists(path):
        return out
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                rec = json.loads(line)
                if isinstance(rec, dict) and "seq" in rec:
                    out.append(rec)
            except ValueError:
                continue
    return out


# -- verification ------------------------------------------------------------


def _read_all_rows(node: Node, deadline: float) -> dict[str, list]:
    """{client-tag: [(t, v), ...]} via a full cluster read from `node`;
    retries while the just-healed cluster still answers with a
    transient fan-out error."""
    last = ""
    while time.perf_counter() < deadline:
        try:
            res = node.query(f"SELECT v FROM {MST} GROUP BY client")[
                "results"][0]
        except (OSError, ValueError, KeyError) as e:
            last = str(e)
            time.sleep(0.5)
            continue
        if "error" in res:
            last = res["error"]
            time.sleep(0.5)
            continue
        out: dict[str, list] = {}
        for s in res.get("series", []):
            tag = s.get("tags", {}).get("client", "?")
            out[tag] = [(row[0], row[1]) for row in s.get("values", [])]
        return out
    raise AssertionError(f"read from {node.nid} kept failing: {last}")


def verify(cluster: Cluster, acked: list[dict],
           timeout: float = 60.0) -> list[str]:
    """The invariant: every journaled acked batch readable exactly once
    with exact values from EVERY coordinator; ledgers clean; no staging
    left anywhere."""
    problems: list[str] = []
    deadline = time.perf_counter() + timeout
    for node in cluster.nodes:
        try:
            rows = _read_all_rows(node, deadline)
        except AssertionError as e:
            problems.append(str(e))
            continue
        by_client: dict[str, dict[int, object]] = {}
        for tag, vals in rows.items():
            seen: dict[int, object] = {}
            for t, v in vals:
                if t in seen:
                    problems.append(
                        f"{node.nid}: duplicate row {tag}@{t}")
                seen[t] = v
            by_client[tag] = seen
        for rec in acked:
            tag = f"c{rec['client']}"
            base = loadgen.client_base_ts(rec["client"], TS_SCALE)
            seen = by_client.get(tag, {})
            for k in range(rec["n"]):
                t = base + rec["seq"] + k
                want = rec["seq"] + k
                got = seen.get(t)
                if got is None:
                    problems.append(
                        f"{node.nid}: LOST acked row {tag} seq="
                        f"{rec['seq'] + k} (level={rec['level']})")
                elif int(got) != want:
                    problems.append(
                        f"{node.nid}: acked row {tag} seq={rec['seq'] + k}"
                        f" wrong value {got} != {want}")
    for node in cluster.nodes:
        try:
            dur = node.ctrl("durability", timeout=30)
        except (OSError, ValueError) as e:
            problems.append(f"{node.nid}: durability check failed: {e}")
            continue
        if dur.get("violations"):
            problems.append(f"{node.nid}: ledger {dur['violations']}")
        try:
            st = node.ctrl("cluster", timeout=30)
        except (OSError, ValueError) as e:
            problems.append(f"{node.nid}: cluster status failed: {e}")
            continue
        if st.get("staging"):
            problems.append(f"{node.nid}: staging left: {st['staging']}")
        if os.environ.get("OGT_LOCKDEP", "") not in ("", "0"):
            # nodes inherit OGT_LOCKDEP (env passthrough at spawn): the
            # lock-order validator's findings surface in /debug/vars —
            # a cycle or blocking-under-hot-lock on any LIVE node is a
            # harness violation like a lost row
            try:
                lv = node.get("/debug/vars").get("lockdep", {})
            except (OSError, ValueError) as e:
                problems.append(f"{node.nid}: lockdep check failed: {e}")
                continue
            if lv.get("violations"):
                problems.append(
                    f"{node.nid}: lockdep violations={lv['violations']} "
                    "(reports on the node's stderr/console log)")
    return problems


# -- rounds ------------------------------------------------------------------


def _scribble_node(victim: Node, rng: random.Random) -> str | None:
    """Flip one bit in a data block of the victim's largest closed TSF
    (DB shards only — never the meta/raft files).  Returns the path, or
    None when the kill landed before any file closed."""
    from opengemini_tpu.storage.tsf import TSFReader

    roots = os.path.join(victim.data_dir, "data", DB)
    candidates = sorted(
        (os.path.join(dp, f)
         for dp, _d, fs in os.walk(roots) for f in fs
         if f.endswith(".tsf")),
        key=os.path.getsize, reverse=True)
    for path in candidates:
        try:
            r = TSFReader(path)
            locs = r.data_locs()
            r.close()
        except Exception:  # noqa: BLE001 — half-written candidate
            continue
        if not locs:
            continue
        loc = locs[rng.randrange(len(locs))]
        at = loc[0] + rng.randrange(loc[1])
        with open(path, "r+b") as f:
            f.seek(at)
            b = f.read(1)
            f.seek(at)
            f.write(bytes([b[0] ^ (1 << rng.randrange(8))]))
        return path
    return None


def _elastic_round(cluster: Cluster, rng: random.Random,
                   traffic: Traffic) -> dict:
    """Membership change under full traffic: JOIN a brand-new node
    (meta raft conf-add + data-roster registration), rebalance a group
    onto it over the two-phase migration, then DECOMMISSION a non-leader
    original (drain-then-remove) with a partition stacked mid-drain.
    The decommission op is idempotent, so the harness re-issues it after
    the heal until it reports done — exactly the operator runbook."""
    detail: dict = {"problems": []}
    seed = next(n for n in cluster.nodes if n.alive())
    new = cluster.add_elastic_node(seed)
    detail["added"] = new.nid
    deadline = time.perf_counter() + 90
    joined = False
    while time.perf_counter() < deadline:
        try:
            st = seed.ctrl("cluster", timeout=15)
            if new.nid in st.get("nodes", []):
                joined = True
                break
        except (OSError, ValueError):
            pass
        time.sleep(0.3)
    if not joined:
        detail["problems"].append(
            f"elastic: {new.nid} never entered the data roster")
        return detail
    # rendezvous already re-homed ~1/N groups when the roster grew; a
    # forced move with an explicit dest makes the migration path onto
    # the joiner deterministic, then migrate rounds stream the data
    for node in cluster.nodes:
        if not node.alive() or node is new:
            continue
        try:
            mv = node.ctrl("cluster", op="move", db=DB, dest=new.nid,
                           timeout=60).get("move")
        except (OSError, ValueError):
            continue
        if mv:
            detail["move"] = mv
            break
    for node in cluster.nodes:
        if node.alive():
            try:
                node.ctrl("cluster", op="migrate", timeout=120)
            except (OSError, ValueError):
                pass
    # decommission a non-leader ORIGINAL while traffic still runs
    try:
        leader_nid = cluster.leader().nid
    except TimeoutError:
        leader_nid = ""
    victim = next((n for n in cluster.nodes
                   if n.alive() and n is not new and n.nid != leader_nid),
                  None)
    if victim is None:  # every non-joiner is dead or the meta leader
        victim = next((n for n in cluster.nodes
                       if n.alive() and n is not new), None)
    if victim is None:
        detail["problems"].append("elastic: no decommission candidate")
        return detail
    detail["decommissioned"] = victim.nid
    out: dict = {}

    def decomm(deadline_s: float, timeout: float) -> None:
        try:
            got = victim.ctrl("cluster", op="decommission",
                              deadline_s=deadline_s, timeout=timeout)
            out.clear()
            out.update(got.get("decommission", {}))
        except (OSError, ValueError) as e:
            out["error"] = str(e)

    # partition FIRST so the drain provably starts degraded (a fast
    # drain would otherwise finish before a stacked fault lands): the
    # blocked/deadline drain must make no false progress claims, and
    # the post-heal re-issue must complete from durable state
    peer = rng.choice([n for n in cluster.nodes
                       if n.alive() and n is not victim])
    cluster.partition(victim, peer)
    detail["mid_drain_partition"] = [victim.nid, peer.nid]
    th = threading.Thread(target=decomm, args=(45.0, 120.0), daemon=True,
                          name="torture-decommission")
    th.start()
    time.sleep(1.5)  # drain passes run against the partitioned pair
    for node in (victim, peer):
        if node.alive():
            node.netfault_clear()
    traffic.join(timeout=90)
    th.join(timeout=150)
    detail["decommission"] = dict(out)
    # a drain that raced the partition returns blocked/deadline WITHOUT
    # removing the node — re-issue until done (resumes from the durable
    # placements/staging/hint state, never re-copies committed groups)
    deadline = time.perf_counter() + 120
    while not out.get("done") and time.perf_counter() < deadline:
        decomm(30.0, 90.0)
        detail["decommission"] = dict(out)
        if not out.get("done"):
            time.sleep(0.5)
    if not out.get("done"):
        detail["problems"].append(
            f"elastic: decommission of {victim.nid} never completed: "
            f"{out}")
        return detail
    # late writes routed THROUGH the removed coordinator may sit in its
    # hint queue: the runbook keeps the process up until a final drain
    # reports clean, then retires it
    try:
        last = victim.ctrl("cluster", op="drain",
                           timeout=120).get("drain", {})
        if last.get("remaining_groups") or last.get("pending_hints"):
            detail["problems"].append(
                f"elastic: removed {victim.nid} still holds work: "
                f"groups={last.get('remaining_groups')} "
                f"hints={last.get('pending_hints')}")
    except (OSError, ValueError) as e:
        detail["problems"].append(
            f"elastic: final drain check on {victim.nid} failed: {e}")
    cluster.remove(victim)
    for node in cluster.nodes:
        if not node.alive():
            continue
        try:
            st = node.ctrl("cluster", timeout=30)
        except (OSError, ValueError) as e:
            detail["problems"].append(
                f"elastic: {node.nid} roster check failed: {e}")
            continue
        if victim.nid in st.get("nodes", []):
            detail["problems"].append(
                f"elastic: {node.nid} roster still lists {victim.nid}")
        if victim.nid in (st.get("pending_hints") or []):
            detail["problems"].append(
                f"elastic: {node.nid} still owes hints to removed "
                f"{victim.nid}")
    return detail


def _apply_round(cluster: Cluster, kind: str, rng: random.Random,
                 traffic: Traffic, site: str | None, nth: int,
                 victim: Node | None, pair: tuple[Node, Node] | None,
                 with_move: bool) -> dict:
    """Drive one fault while `traffic` runs.  Returns round detail."""
    detail: dict = {"kind": kind, "site": site, "nth": nth,
                    "victim": victim.nid if victim else None,
                    "move": None, "killed": []}
    if kind == "site":
        targets = [victim] if victim else [n for n in cluster.nodes
                                           if n.alive()]
        for node in targets:
            try:
                node.arm(site, f"panic#{nth}")
            except (OSError, ValueError):
                pass
        if site in _HINT_SITES or site in _AE_SITES:
            # these edges need an unreachable peer / divergence: drop
            # one direction for a slice of the traffic window
            others = [n for n in cluster.nodes
                      if victim is None or n.nid != victim.nid]
            peer = rng.choice(others)
            src = victim or rng.choice(
                [n for n in cluster.nodes if n.nid != peer.nid])
            try:
                src.ctrl("netfault", src="*", dst=peer.addr, path="*",
                         action="drop")
            except (OSError, ValueError):
                pass
            time.sleep(1.2)
            if src.alive():
                src.netfault_clear()
    elif kind == "sigkill":
        time.sleep(rng.uniform(0.3, 1.2))
        victim.kill()
        detail["killed"].append(victim.nid)
    elif kind == "scribble":
        # media fault: kill the victim mid-traffic, then flip one bit
        # inside a closed TSF data block of its data dir.  On restart
        # the block CRC catches it (scrub tick / first decode), the
        # file quarantines, and anti-entropy re-pulls the lost rows
        # from the rf>1 replica — verify() then demands the FULL acked
        # set from every coordinator, including this one.
        time.sleep(rng.uniform(0.5, 1.2))
        try:
            # flush first so a closed TSF (the corruption target)
            # deterministically exists on the victim
            victim.ctrl("flush", timeout=30)
        except (OSError, ValueError):
            pass
        victim.kill()
        detail["killed"].append(victim.nid)
        detail["scribbled"] = _scribble_node(victim, rng)
    elif kind == "partition":
        a, b = pair
        cluster.partition(a, b)
        detail["pair"] = [a.nid, b.nid]
        time.sleep(rng.uniform(1.0, 2.2))
        for node in (a, b):
            if node.alive():
                node.netfault_clear()
    elif kind == "elastic":
        # membership change under traffic: join a new node, rebalance
        # onto it, decommission an original with a mid-drain partition
        detail.update(_elastic_round(cluster, rng, traffic))
    if with_move:
        try:
            detail["move"] = cluster.force_move()
        except (OSError, ValueError):
            pass
        # pump migrate rounds so migration sites fire inside the window
        for node in cluster.nodes:
            if node.alive():
                try:
                    node.ctrl("cluster", op="migrate", timeout=120)
                except (OSError, ValueError):
                    pass
    # let the remaining traffic window elapse (site kills need hits);
    # loadgen's own worker join bounds this at duration + 4x client
    # timeout, so a longer wait here means a wedged server — surfaced
    # by the verify step rather than hung forever
    traffic.join(timeout=90)
    # anti-entropy sites only fire on a forced round with divergence
    if kind == "site" and site in _AE_SITES:
        for node in cluster.nodes:
            if node.alive():
                try:
                    node.ctrl("cluster", op="antientropy", timeout=120)
                except (OSError, ValueError):
                    pass
    # hint-replay sites: force replay now that the drop rule is healed
    if kind == "site" and site in _HINT_SITES:
        for node in cluster.nodes:
            if node.alive():
                try:
                    node.ctrl("cluster", op="hints", timeout=60)
                except (OSError, ValueError):
                    pass
    for node in cluster.nodes:
        rc = node.returncode()
        if rc is not None and node.nid not in detail["killed"]:
            detail["killed"].append(node.nid)
            detail.setdefault("rc", {})[node.nid] = rc
    return detail


def run_rounds(cluster: Cluster, rounds: list[dict], workdir: str,
               rng: random.Random, clients: int,
               traffic_s: float) -> tuple[list[dict], list[dict]]:
    """Execute the schedule against one live cluster; returns (results,
    all acked records)."""
    results = []
    all_acked: list[dict] = []
    offset = 0
    for i, spec in enumerate(rounds):
        ack_log = os.path.join(workdir, f"acks-{i}.jsonl")
        traffic = Traffic(cluster, spec.get("traffic_s", traffic_s),
                          clients, offset, ack_log).start()
        offset += clients
        time.sleep(0.3)  # let the first batches land
        # resolve by id at round time: elastic rounds mutate membership,
        # so a pre-scheduled victim may no longer exist — reroll it
        live = [n for n in cluster.nodes if n.alive()] or cluster.nodes
        victim = cluster.by_id.get(spec["victim"], rng.choice(live)) \
            if spec.get("victim") else None
        pair = None
        if spec.get("pair"):
            pair = tuple(cluster.by_id[n] for n in spec["pair"]
                         if n in cluster.by_id)
            if len(pair) < 2:
                pair = tuple(rng.sample(live, 2)) if len(live) >= 2 \
                    else None
            if pair is None:
                spec = dict(spec, kind="sigkill", victim=live[0].nid)
                victim = live[0]
        detail = _apply_round(
            cluster, spec["kind"], rng, traffic, spec.get("site"),
            spec.get("nth", 1), victim, pair,
            with_move=spec.get("move", False))
        # heal everything, restart the dead, converge, verify
        cluster.heal()
        detail["restarted"] = cluster.restart_dead()
        try:
            cluster.wait_ready(timeout=90)
        except TimeoutError as e:
            detail["problems"] = [f"cluster never re-formed: {e}"]
            results.append(detail)
            break
        scribble_problems: list[str] = []
        if spec["kind"] == "scribble":
            # force the integrity sweep NOW (instead of waiting out the
            # production scrub interval): detection quarantines the
            # damaged file and converge()'s anti-entropy rounds pull
            # the lost rows back from the healthy replica
            detail["quarantined"] = 0
            for node in cluster.nodes:
                if node.alive():
                    try:
                        got = node.ctrl("scrub", op="tick", timeout=120)
                        detail["quarantined"] += \
                            got.get("quarantine", {}).get("total", 0)
                    except (OSError, ValueError):
                        pass
            if not detail.get("scribbled"):
                scribble_problems.append(
                    "scribble: no closed TSF target on the victim")
            elif detail["quarantined"] < 1:
                scribble_problems.append(
                    "scribble: corruption injected but never detected/"
                    "quarantined")
        problems = detail.pop("problems", [])
        problems += cluster.converge(timeout=90)
        problems += scribble_problems
        acked = read_acks(ack_log)
        all_acked.extend(acked)
        detail["acked_batches"] = len(acked)
        out = traffic.out or {}
        detail["traffic"] = {
            k: out.get(k) for k in ("attempts", "acked_rows", "errors",
                                    "sheds_429", "sheds_503")}
        problems += verify(cluster, all_acked)
        detail["problems"] = problems
        detail["ok"] = not problems
        results.append(detail)
        status = "ok" if not problems else "VIOLATION"
        kills = ",".join(detail["killed"]) or "none"
        print(f"[{i + 1}/{len(rounds)}] {spec['kind']}"
              f"{':' + spec['site'] if spec.get('site') else ''}"
              f" killed={kills} move={bool(detail.get('move'))}: {status}",
              flush=True)
        for p in problems:
            print("   ", p, flush=True)
    return results, all_acked


QUICK_ROUNDS = [
    # replica applies the copy, dies before the ack: the coordinator
    # must classify it unreachable and hint an LWW-safe duplicate
    {"kind": "site", "site": "internal-write-before-reply", "nth": 3,
     "victim": "n3"},
    # forced shard move with the shedding coordinator killed after all
    # commit acks, before drop-local: the re-push must not duplicate
    {"kind": "site", "site": "cluster-migrate-before-drop-local",
     "nth": 1, "move": True},
    # symmetric partition mid-traffic, then heal: hinted copies +
    # anti-entropy must re-converge every acked row
    {"kind": "partition", "pair": ["n1", "n2"]},
    # media fault: kill a replica, flip one bit in a closed TSF data
    # block, restart — block CRC detects, the file quarantines, and
    # anti-entropy repairs from the rf=2 peer until every coordinator
    # again serves the FULL acked set
    {"kind": "scribble", "victim": "n3"},
    # elastic membership under full traffic: join a 4th node (raft
    # conf-add + roster registration), force a group onto it over the
    # two-phase migration, then decommission a non-leader original
    # (drain-then-remove) with a partition stacked mid-drain — every
    # acked row must stay exactly-once readable from every SURVIVOR
    {"kind": "elastic", "traffic_s": 6.0},
]


def _random_schedule(rng: random.Random, n: int,
                     nids: list[str]) -> list[dict]:
    out = []
    for _ in range(n):
        roll = rng.random()
        if roll < 0.5:
            site = rng.choice(KILL_SITES)
            spec = {"kind": "site", "site": site,
                    "nth": rng.randint(1, 6),
                    # migration sites fire on roles the scheduler cannot
                    # predict (shedder vs destination): arm everywhere
                    "victim": None if site in _MIGRATION_SITES
                    else rng.choice(nids),
                    "move": site in _MIGRATION_SITES or rng.random() < 0.3}
        elif roll < 0.65:
            spec = {"kind": "sigkill", "victim": rng.choice(nids),
                    "move": rng.random() < 0.4}
        elif roll < 0.72:
            # membership churn: each elastic round adds one node and
            # decommissions one, so the cluster size stays constant
            # while every round reshuffles which ids exist (victims are
            # re-resolved at round time)
            spec = {"kind": "elastic", "traffic_s": 6.0}
        elif roll < 0.82:
            spec = {"kind": "scribble", "victim": rng.choice(nids)}
        else:
            pair = rng.sample(nids, 2)
            spec = {"kind": "partition", "pair": pair,
                    "move": rng.random() < 0.3}
        out.append(spec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fixed schedule, one cluster, bounded (~60s)")
    ap.add_argument("--rounds", type=int, default=0,
                    help="randomized rounds (full mode)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--rf", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--traffic-s", type=float, default=2.5)
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir even on success")
    args = ap.parse_args(argv)

    rng = random.Random(args.seed)
    workdir = tempfile.mkdtemp(prefix="ogt-cluster-torture-")
    cluster = Cluster(workdir, n=args.nodes, rf=args.rf)
    t0 = time.perf_counter()
    try:
        cluster.spawn_all()
        cluster.wait_ready()
        if args.quick:
            schedule = [dict(s) for s in QUICK_ROUNDS]
        else:
            schedule = _random_schedule(
                rng, args.rounds or 50,
                [node.nid for node in cluster.nodes])
        results, all_acked = run_rounds(
            cluster, schedule, workdir, rng, args.clients, args.traffic_s)
    finally:
        cluster.stop_all()

    bad = [r for r in results if not r.get("ok")]
    summary = {
        "rounds": len(results),
        "killed": sum(1 for r in results if r.get("killed")),
        "acked_batches": sum(r.get("acked_batches", 0) for r in results),
        "acked_rows": sum(rec["n"] for rec in all_acked),
        "violations": len(bad),
        "elapsed_s": round(time.perf_counter() - t0, 1),
    }
    print(json.dumps({"summary": summary, "violations": bad}, indent=2,
                     default=str))
    print("CLUSTER-TORTURE-JSON " + json.dumps({"summary": summary}))
    if bad or not results:
        print(f"workdir kept for triage: {workdir}")
        return 1
    if not args.keep:
        shutil.rmtree(workdir, ignore_errors=True)
    else:
        print(f"workdir: {workdir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

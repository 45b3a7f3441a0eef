"""ts-server: the single-process all-in-one server binary.

Reference: app/ts-server (run/run.go:38) + the app.Command lifecycle
(app/command.go:39-58). `python -m opengemini_tpu.server.app -config x.toml`
or `opengemini_tpu.server.app.main([...])`.

Config (TOML, reference lib/config style):
    [data]
    dir = "/var/lib/opengemini-tpu"
    wal-fsync = false
    flush-threshold-mb = 64
    [http]
    bind-address = "127.0.0.1:8086"
    tls-cert = "/etc/ogt/node.crt"   # serve https (client + peer traffic)
    tls-key = "/etc/ogt/node.key"
    tls-ca = "/etc/ogt/ca.crt"       # peer-client trust (else system CAs)
    tls-insecure-skip-verify = false # self-signed lab clusters
    [device]
    mesh-axes = ["shard", "time"]   # enables the multi-chip aggregate path
    mesh-devices = 0                # 0/absent = every local device
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading

try:
    import tomllib  # py311+
except ModuleNotFoundError:  # pragma: no cover — exercised on py<3.11
    try:
        import tomli as tomllib  # the pre-3.11 backport, same API
    except ModuleNotFoundError:
        tomllib = None  # config loading degrades to defaults-only

from opengemini_tpu.server.http import HttpService
from opengemini_tpu.utils import peers as peernet
from opengemini_tpu.storage.engine import Engine

DEFAULTS = {
    "data": {"dir": "./ogtpu-data", "wal-fsync": False, "flush-threshold-mb": 64},
    "http": {"bind-address": "127.0.0.1:8086"},
}


def load_config(path: str | None) -> dict:
    cfg = {k: dict(v) for k, v in DEFAULTS.items()}
    if path:
        if tomllib is None:
            raise SystemExit(
                "-config requires a TOML parser: Python >= 3.11 "
                "(tomllib) or the tomli package"
            )
        with open(path, "rb") as f:
            user = tomllib.load(f)
        for section, vals in user.items():
            cfg.setdefault(section, {}).update(vals)
    return cfg


_JAX_DISTRIBUTED_UP = False


def _init_jax_distributed(dev_cfg: dict) -> None:
    """[device] coordinator-address + num-processes + process-id ->
    jax.distributed.initialize BEFORE backend init, so jax.devices()
    spans every host of the slice and make_mesh builds a global mesh
    (DCN between hosts, ICI within — SURVEY §7 step 4; the reference's
    analogue is its spdy node mesh). Must run before any jax use;
    idempotent per process."""
    global _JAX_DISTRIBUTED_UP
    coord = dev_cfg.get("coordinator-address")
    if not coord or _JAX_DISTRIBUTED_UP:
        return
    missing = [k for k in ("num-processes", "process-id")
               if dev_cfg.get(k) is None]
    if missing:
        raise SystemExit(
            "[device] coordinator-address requires "
            + " and ".join(missing))
    import jax

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(dev_cfg["num-processes"]),
        process_id=int(dev_cfg["process-id"]),
    )
    _JAX_DISTRIBUTED_UP = True
    print(
        f"jax.distributed up: process {dev_cfg['process-id']}/"
        f"{dev_cfg['num-processes']} via {coord}", flush=True)


def _configure_device_mesh(dev_cfg: dict) -> None:
    """[device] mesh-axes -> a process-wide jax mesh: every dense batch
    (grid / bucketed) and the AggBatch shard_map path then run multi-chip
    (parallel/runtime.set_mesh). The reference's always-on shard fan-out
    analogue is coordinator/shard_mapper.go:61."""
    from opengemini_tpu.parallel import runtime as prt

    # multi-host init is independent of the mesh config: a coordinator
    # address alone must still join the slice (jax.devices() then spans
    # every host even if this node runs without a mesh)
    _init_jax_distributed(dev_cfg)
    axes = dev_cfg.get("mesh-axes")
    if not axes:
        # the mesh is process-global: a config without [device] must not
        # inherit one from an earlier build() in the same process
        prt.set_mesh(None)
        return
    mesh = _build_mesh(dev_cfg)
    prt.set_mesh(mesh)
    print(
        "device mesh: "
        f"{dict(zip(mesh.axis_names, mesh.devices.shape))}", flush=True)


def _build_mesh(dev_cfg: dict):
    """mesh-axes/mesh-devices -> a Mesh (the one [device] parsing shared
    by boot and SIGHUP reload, so both always build the same geometry
    for the same file)."""
    from opengemini_tpu.parallel import distributed as dist

    n = int(dev_cfg.get("mesh-devices", 0)) or None
    return dist.make_mesh(n, tuple(dev_cfg.get("mesh-axes")))


def build(cfg: dict) -> HttpService:
    hint_service = None
    _configure_device_mesh(cfg.get("device", {}))
    data = cfg["data"]
    engine = Engine(
        data["dir"],
        sync_wal=bool(data.get("wal-fsync", False)),
        flush_threshold_bytes=int(data.get("flush-threshold-mb", 64)) << 20,
        tag_arrays=bool(data.get("enable-tag-array", False)),
    )
    host, _, port = cfg["http"]["bind-address"].partition(":")
    http_cfg = cfg["http"]
    tls = None
    if http_cfg.get("tls-cert") and http_cfg.get("tls-key"):
        # [http] tls-cert/tls-key serve the listener over https
        tls = {"certfile": http_cfg["tls-cert"],
               "keyfile": http_cfg["tls-key"]}
    if tls or http_cfg.get("tls-ca") or http_cfg.get(
            "tls-insecure-skip-verify"):
        # peer clients (raft, /internal/*, registrar) speak https whenever
        # ANY tls-* key is set: a node behind a TLS-terminating proxy (no
        # serving cert of its own) still needs https to its peers
        peernet.configure_tls(
            ca_file=http_cfg.get("tls-ca") or None,
            skip_verify=bool(http_cfg.get("tls-insecure-skip-verify",
                                          False)),
        )
    else:
        # process-global, like the device mesh: a config without TLS must
        # not inherit https peer mode from an earlier build()
        peernet.reset()
    svc = HttpService(
        engine, host or "127.0.0.1", int(port or 8086),
        auth_enabled=bool(http_cfg.get("auth-enabled", False)),
        tls=tls,
    )
    meta_cfg = cfg.get("meta")
    if meta_cfg and meta_cfg.get("node-id"):
        # clustered meta plane (reference ts-meta): peers are "id@host:port"
        from opengemini_tpu.meta.service import HttpTransport, MetaStore

        peers = {}
        for p in meta_cfg.get("peers", []):
            pid, sep, addr = p.partition("@")
            if not sep or not pid or ":" not in addr:
                raise ValueError(
                    f"meta.peers entries must be 'id@host:port', got {p!r}"
                )
            peers[pid] = addr
        node_id = meta_cfg["node-id"]
        token = meta_cfg.get("token", "")
        transport = HttpTransport(
            peers, token=token,
            self_addr=meta_cfg.get("advertise", cfg["http"]["bind-address"]),
        )
        svc.meta_store = MetaStore(
            node_id, sorted(set(peers) | {node_id}), transport,
            storage_path=os.path.join(engine.root, "meta.raftlog"),
            compact_threshold=int(meta_cfg.get("compact-threshold", 512)),
        )
        svc.meta_store.token = token
        svc.meta_store.attach_engine(engine)  # replicated DDL -> local engine
        svc.meta_store.attach_users(svc.users)  # replicated user commands
        svc.executor.meta_store = svc.meta_store
        if meta_cfg.get("join"):
            # passive until our conf-add commits: a joiner must never
            # self-elect off its partial seed view
            svc.meta_store.node.learner = True
        svc.meta_store.start()
        if meta_cfg.get("join"):
            # new node: ask the existing cluster's leader to add us, then
            # raft catches us up (snapshot or log) automatically
            _spawn_joiner(
                meta_cfg["join"], node_id,
                meta_cfg.get("advertise", cfg["http"]["bind-address"]), token,
            )
    flight_cfg = cfg.get("flight", {})
    if flight_cfg.get("bind-address"):
        from opengemini_tpu.server.flight import FlightService

        fhost, _, fport = flight_cfg["bind-address"].partition(":")
        svc.flight = FlightService(
            engine, svc.executor, fhost or "127.0.0.1", int(fport or 8087),
            users=svc.users, auth_enabled=bool(cfg["http"].get("auth-enabled", False)),
        )
    cluster_cfg = cfg.get("cluster", {})
    if cluster_cfg.get("data-routing") and svc.meta_store is not None:
        from opengemini_tpu.parallel.cluster import DataRouter

        meta_cfg = cfg.get("meta", {})
        advertise = meta_cfg.get("advertise", cfg["http"]["bind-address"])
        svc.router = DataRouter(
            engine, svc.meta_store, meta_cfg["node-id"], advertise,
            token=meta_cfg.get("token", ""),
            rf=int(cluster_cfg.get("replication-factor", 1)),
            write_consistency=str(
                cluster_cfg.get("write-consistency", "one")),
        )
        svc.executor.router = svc.router
        if str(cluster_cfg.get("ha-policy", "write-available")) == \
                "replication":
            # strict mode: raft-committed writes per replica group
            from opengemini_tpu.parallel.datarep import DataReplication

            svc.router.datarep = DataReplication(
                svc.router, token=meta_cfg.get("token", ""))
        if svc.flight is not None:
            svc.flight.router = svc.router
        _spawn_registrar(svc.meta_store, meta_cfg["node-id"], advertise,
                         meta_cfg.get("token", ""))
        from opengemini_tpu.services.hintreplay import HintReplayService

        # at rf=1 there are never hints to replay, but the same ticker
        # drives member health probes for SHOW CLUSTER
        hint_service = HintReplayService(
            svc.router, float(cluster_cfg.get("hint-interval-s", 30)))
    svc.services = _build_services(cfg, svc)
    if hint_service is not None:
        svc.services.append(hint_service)
    if svc.router is not None and svc.router.rf > 1:
        from opengemini_tpu.services.antientropy import AntiEntropyService

        svc.services.append(AntiEntropyService(
            svc.router,
            float(cluster_cfg.get("anti-entropy-interval-s", 300))))
    if svc.router is not None:
        from opengemini_tpu.services.migration import MigrationService

        svc.services.append(MigrationService(
            svc.router,
            float(cluster_cfg.get("migration-interval-s", 60)),
            staging_ttl_s=float(
                cluster_cfg.get("migration-staging-ttl-s", 900)),
        ))
    if svc.router is not None and svc.meta_store is not None and \
            float(cluster_cfg.get("balance-interval-s", 3600)) > 0:
        from opengemini_tpu.services.balancer import BalanceService

        svc.services.append(BalanceService(
            svc.router, svc.meta_store,
            float(cluster_cfg.get("balance-interval-s", 3600)),
            min_skew_mb=int(cluster_cfg.get("balance-min-skew-mb", 64)),
            skew_ratio=float(cluster_cfg.get("balance-skew-ratio", 1.3)),
        ))
    return svc


def _spawn_registrar(meta_store, node_id: str, addr: str, token: str) -> None:
    """Register this node in the FSM data-node roster (leader-routed,
    retried until the cluster has a leader)."""
    import json as _json
    import urllib.request as _rq

    def run():
        import time as _time

        cmd = {"op": "register_node", "id": node_id, "addr": addr,
               "role": "data"}
        for _ in range(300):
            if meta_store.fsm.nodes.get(node_id, {}).get("addr") == addr:
                return  # already registered (replayed log or prior run)
            if meta_store.is_leader():
                if meta_store.propose_and_wait(cmd):
                    return
            else:
                hint = meta_store.leader_hint()
                laddr = meta_store.meta_members().get(hint or "", "")
                if laddr:
                    try:
                        req = _rq.Request(
                            peernet.url(laddr, "/cluster/register"),
                            data=_json.dumps({
                                "id": node_id, "addr": addr,
                                "role": "data", "token": token,
                            }).encode(),
                            headers={"Content-Type": "application/json"},
                            method="POST",
                        )
                        with peernet.urlopen(req, timeout=3) as r:
                            if r.status == 200:
                                return
                    except OSError:
                        pass
            _time.sleep(1)

    threading.Thread(target=run, daemon=True, name="data-register").start()


def _spawn_joiner(seed: str, node_id: str, addr: str, token: str) -> None:
    import json as _json
    import urllib.request as _rq

    def run():
        import time as _time

        target = seed
        body = {"id": node_id, "addr": addr, "token": token}
        for _ in range(120):
            try:
                req = _rq.Request(
                    peernet.url(target, "/raft/join"),
                    data=_json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"}, method="POST",
                )
                with peernet.urlopen(req, timeout=3) as r:
                    if r.status == 200:
                        print(f"joined meta cluster via {target}", flush=True)
                        return
            except OSError as e:
                # a 409 from a follower carries the leader's address
                if hasattr(e, "read"):
                    try:
                        hint = _json.loads(e.read()).get("leader_addr")
                        if hint:
                            target = hint
                    except Exception:  # noqa: BLE001
                        target = seed
            _time.sleep(1)
        print("meta join failed after retries", flush=True)

    threading.Thread(target=run, daemon=True, name="meta-join").start()


def _build_services(cfg: dict, svc: HttpService) -> list:
    from opengemini_tpu.services.continuous import ContinuousQueryService
    from opengemini_tpu.services.downsample import DownsampleService
    from opengemini_tpu.services.monitor import MonitorService
    from opengemini_tpu.services.retention import RetentionService

    sc = cfg.get("services", {})
    out = [
        RetentionService(svc.engine, float(sc.get("retention-interval-s", 1800))),
        DownsampleService(svc.engine, float(sc.get("downsample-interval-s", 3600))),
        ContinuousQueryService(
            svc.engine, svc.executor, float(sc.get("cq-interval-s", 10)),
            meta_store=svc.meta_store,
        ),
    ]
    if sc.get("store-monitor", True):
        out.append(MonitorService(svc.engine, float(sc.get("monitor-interval-s", 10))))
    from opengemini_tpu.services.compaction import CompactionService
    from opengemini_tpu.services.stream import StreamService

    out.append(StreamService(svc.engine, float(sc.get("stream-interval-s", 5))))
    from opengemini_tpu.services.rollup import RollupService

    # inert (one None check per tick) until a rollup spec is declared
    out.append(RollupService(
        svc.engine, float(sc.get("rollup-interval-s", 5))))
    from opengemini_tpu.promql.rules import enabled_by_env as _rules_on
    from opengemini_tpu.services.rules import RulesService

    if _rules_on():
        from opengemini_tpu.promql.rules import RuleManager

        # constructed eagerly so persisted groups resume ticking after a
        # restart (the durable claim/watermark contract needs the
        # manager live before traffic); OGT_RULES=0 keeps rules_hook
        # None and every write path bit-identical
        svc.rules_manager = RuleManager(svc.engine, prom=svc.prom)
        out.append(RulesService(
            svc.engine, float(sc.get("rules-interval-s", 5)),
            manager=svc.rules_manager, meta_store=svc.meta_store,
            router=svc.router))
    out.append(CompactionService(
        svc.engine, float(sc.get("compact-interval-s", 600)),
        int(sc.get("compact-max-files", 4)),
    ))
    from opengemini_tpu.services.scrub import ScrubService

    # background integrity scrub (block CRC verification feeding
    # quarantine + rf>1 anti-entropy repair); OGT_SCRUB=0 disables.
    # Registered on svc so /debug/ctrl?mod=scrub controls THIS instance.
    svc.scrub_service = ScrubService(
        svc.engine,
        float(sc.get("scrub-interval-s", 0) or 0) or None,
        router=svc.router,
        mb_per_tick=(int(sc["scrub-mb"]) if "scrub-mb" in sc else None),
    )
    out.append(svc.scrub_service)
    from opengemini_tpu.services.subscriber import SubscriberManager

    svc.subscriber = SubscriberManager(svc.engine)
    from opengemini_tpu.services.iodetector import IoDetectorService
    from opengemini_tpu.services.sherlock import SherlockService

    out.append(IoDetectorService(
        svc.engine, float(sc.get("iodetector-interval-s", 30)),
        float(sc.get("iodetector-timeout-s", 10)),
        bool(sc.get("iodetector-fatal", False)),
    ))
    out.append(SherlockService(
        svc.engine, float(sc.get("sherlock-interval-s", 30)),
        float(sc.get("sherlock-mem-mb", 4096)),
        int(sc.get("sherlock-threads", 200)),
        float(sc.get("sherlock-cooldown-s", 600)),
        bool(sc.get("sherlock-tracemalloc", False)),
    ))
    if sc.get("castor-udf-dir"):
        from opengemini_tpu.services.castor import load_udfs

        names = load_udfs(sc["castor-udf-dir"])
        if names:
            print(f"castor udfs loaded: {', '.join(names)}", flush=True)
    if sc.get("obs-dir") or sc.get("obs-url"):
        from opengemini_tpu.services.obstier import ObsTierService

        if sc.get("obs-url"):
            # remote S3-compatible bucket endpoint (reference: lib/obs)
            from opengemini_tpu.storage.objstore import HTTPObjectStore

            store = HTTPObjectStore(
                sc["obs-url"], token=sc.get("obs-token") or None)
        else:
            from opengemini_tpu.storage.objstore import FSObjectStore

            store = FSObjectStore(sc["obs-dir"])
        svc.engine.attach_object_store(store)
        out.append(ObsTierService(
            svc.engine,
            int(float(sc.get("obs-age-days", 90)) * 86400e9),
            float(sc.get("obs-interval-s", 3600)),
        ))
    if sc.get("cold-dir"):
        from opengemini_tpu.services.hierarchical import HierarchicalService

        out.append(HierarchicalService(
            svc.engine, sc["cold-dir"],
            int(float(sc.get("cold-age-days", 30)) * 86400e9),
            float(sc.get("hierarchical-interval-s", 3600)),
        ))
    return out


def _apply_runtime_config(svc: HttpService, cfg: dict) -> list[str]:
    """Hot-apply the reloadable subset of [services] to running services
    (reference: lib/config runtimecfg — SIGHUP re-reads the file; only
    tick intervals and watermark-style knobs change live, topology
    doesn't). Returns a list of 'service.field=value' changes."""
    sc = cfg.get("services", {})
    plans = {
        "retention": {"interval_s": ("retention-interval-s", float)},
        "downsample": {"interval_s": ("downsample-interval-s", float)},
        "continuousquery": {"interval_s": ("cq-interval-s", float)},
        "monitor": {"interval_s": ("monitor-interval-s", float)},
        "stream": {"interval_s": ("stream-interval-s", float)},
        "compaction": {"interval_s": ("compact-interval-s", float),
                       "max_files": ("compact-max-files", int)},
        "hierarchical": {"interval_s": ("hierarchical-interval-s", float)},
        "obstier": {"interval_s": ("obs-interval-s", float)},
        "iodetector": {"interval_s": ("iodetector-interval-s", float),
                       "probe_timeout_s": ("iodetector-timeout-s", float),
                       "fatal": ("iodetector-fatal", bool)},
        "sherlock": {"interval_s": ("sherlock-interval-s", float),
                     "mem_mb_watermark": ("sherlock-mem-mb", float),
                     "thread_watermark": ("sherlock-threads", int),
                     "cooldown_s": ("sherlock-cooldown-s", float)},
        "scrub": {"interval_s": ("scrub-interval-s", float),
                  "mb_per_tick": ("scrub-mb", int)},
    }
    # two-phase: convert EVERYTHING first so a bad value rejects the whole
    # reload instead of leaving a half-applied config behind an error
    staged = []
    for s in svc.services:
        plan = plans.get(s.name)
        if not plan:
            continue
        for attr, (key, conv) in plan.items():
            if key in sc:
                staged.append((s, attr, conv(sc[key])))
    changed = []
    for s, attr, new in staged:
        if getattr(s, attr, None) != new:
            setattr(s, attr, new)
            changed.append(f"{s.name}.{attr}={new}")
    # NOTE: a shortened interval takes effect after the service's current
    # wait expires (the ticker re-reads interval_s each iteration)
    changed.extend(_apply_mesh_config(cfg.get("device", {})))
    return changed


def _apply_mesh_config(dev_cfg: dict) -> list[str]:
    """Hot-apply a changed [device] mesh on SIGHUP. Safe now that every
    sharded-buffer cache rekeys on runtime.mesh_epoch() (models/grid.py,
    models/ragged.py) and the colcache device tier reshards retained
    entries with the stale buffers donated — a live swap reshards, it
    never serves a dead mesh. No-op when the effective mesh geometry is
    unchanged (rebuilding an identical mesh would bump the epoch and
    force every cache to reshard for nothing). Multi-host topology
    (coordinator-address et al.) stays boot-only, like the reference's
    runtimecfg."""
    from opengemini_tpu.parallel import runtime as prt

    axes = tuple(dev_cfg.get("mesh-axes") or ())
    cur = prt.get_mesh()
    if not axes:
        if cur is None:
            return []
        prt.set_mesh(None)
        return ["device.mesh=off"]
    import jax

    n = int(dev_cfg.get("mesh-devices", 0)) or len(jax.devices())
    if cur is not None and tuple(cur.axis_names) == axes and cur.size == n:
        return []
    mesh = _build_mesh(dev_cfg)
    prt.set_mesh(mesh)
    return ["device.mesh="
            + str(dict(zip(mesh.axis_names, mesh.devices.shape)))]


def _bring_up(cfg: dict) -> None:
    """Bring the device backend and the native libraries up before the
    listener opens, and say what came up.  Readiness then means the
    device is ready, and the first request does not pay backend init.
    Nothing here chooses a platform: JAX takes the accelerator it finds,
    ``JAX_PLATFORMS=cpu`` asks for the CPU, and a platform that cannot
    initialise raises out of main() — the process exits non-zero
    instead of serving somewhere else.  Only the CLI entry point does
    this: embedders calling build() bring their own process up."""
    from opengemini_tpu import native
    from opengemini_tpu.utils import backend

    # multi-host slices must join before the backend initialises
    _init_jax_distributed(cfg.get("device", {}))
    dev = backend.init()
    print(
        f"device backend: platform={dev['platform']} "
        f"device_kind={dev['device_kind']!r} count={dev['count']} "
        f"compile_cache={dev['compile_cache_dir']}", flush=True)
    print("native libraries: " + native.report(), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ts-server", description="opengemini-tpu all-in-one server")
    ap.add_argument("-config", default=None, help="TOML config path")
    ap.add_argument("-pidfile", default=None, help="write process id to this file")
    args = ap.parse_args(argv)
    cfg = load_config(args.config)
    _bring_up(cfg)
    svc = build(cfg)
    svc.start()
    if svc.flight is not None:
        svc.flight.start()
    for s in svc.services:
        s.start()
    stop_event = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop_event.set())

    # installed BEFORE the pidfile exists: a supervisor that reads the
    # pidfile and fires an immediate reload must not hit the default
    # SIGHUP disposition (terminate)
    def on_hup(*_):
        try:
            changed = _apply_runtime_config(svc, load_config(args.config))
            print("config reloaded: " + (", ".join(changed) or "no changes"),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — a bad file must not kill us
            print(f"config reload failed: {e}", flush=True)

    signal.signal(signal.SIGHUP, on_hup)
    if args.pidfile:
        with open(args.pidfile, "w", encoding="utf-8") as f:
            f.write(str(os.getpid()))
    scheme = "https" if svc.tls_enabled else "http"
    print(f"opengemini-tpu ts-server listening on {scheme}://:{svc.port}",
          flush=True)
    stop_event.wait()
    print("shutting down", flush=True)
    for s in svc.services:
        s.stop()
    if getattr(svc, "subscriber", None) is not None:
        svc.subscriber.stop()
    if svc.flight is not None:
        svc.flight.stop()
    if svc.meta_store is not None:
        svc.meta_store.stop()
    if getattr(svc.router, "datarep", None) is not None:
        svc.router.datarep.stop()
    if getattr(svc, "rules_manager", None) is not None:
        svc.rules_manager.close()  # final state fsync + hook detach
    svc.stop()
    svc.engine.close()
    if args.pidfile:
        try:
            os.remove(args.pidfile)
        except OSError:
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())

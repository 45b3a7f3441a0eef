"""InfluxDB 1.x-compatible HTTP API.

Reference routes (lib/util/lifted/influx/httpd/handler.go:257-280 and
handler_prom.go:86-312):
  GET/POST /query      InfluxQL, params q/db/epoch/pretty/chunked(ignored)
  POST     /write      line protocol, params db/rp/precision
  POST     /api/v2/write  bucket=db[/rp], precision
  GET/POST /api/v1/query, /api/v1/query_range   PromQL (params db opt.)
  GET      /api/v1/labels, /api/v1/label/<name>/values
  GET      /ping, /health
Auth and TLS are deferred to the cluster round; this is the ts-server
single-node surface.
"""

from __future__ import annotations

import contextlib
import gzip
import json
import re
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from opengemini_tpu import __version__
from opengemini_tpu.ingest.line_protocol import ParseError
from opengemini_tpu.promql.engine import PromEngine, PromError
from opengemini_tpu.promql.parser import PromParseError, parse_duration_s
from opengemini_tpu.utils.querytracker import QueryKilled
from opengemini_tpu.query import condition as cond
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.record import FieldTypeConflict
from opengemini_tpu.storage.shard import FileQuarantined
from opengemini_tpu.storage.engine import (NS, DatabaseNotFound, Engine,
                                           WriteError)
from opengemini_tpu.utils import tracing
from opengemini_tpu.utils.failpoint import inject as _fp
from opengemini_tpu.utils.governor import GOVERNOR, AdmissionRejected
from opengemini_tpu.utils.stats import GLOBAL as STATS
from opengemini_tpu.utils.stats import observe_ns as _observe_ns

_EPOCH_DIV = {"ns": 1, "u": 1_000, "µ": 1_000, "ms": 1_000_000, "s": 1_000_000_000,
              "m": 60_000_000_000, "h": 3_600_000_000_000}

# early-reply keep-alive drain bounds (_send): a rejected request body
# larger than the cap — or one that stalls longer than the timeout —
# closes the connection instead of being read out
_DRAIN_CAP_BYTES = 8 << 20
_DRAIN_TIMEOUT_S = 10.0


def _route_of(path: str) -> str:
    """Coarse route class for the HTTP latency histograms: a FIXED
    vocabulary so /metrics label cardinality stays bounded no matter
    what paths clients probe."""
    if path in ("/query",):
        return "query"
    if path in ("/write", "/api/v2/write"):
        return "write"
    if path in ("/api/v1/prom/write", "/api/v1/otlp/metrics"):
        return "write"
    if path.startswith("/api/v1/"):
        return "prom"
    if path.startswith("/internal/"):
        return "internal"
    if path.startswith("/debug/") or path == "/metrics":
        return "debug"
    if path.startswith("/raft/") or path.startswith("/cluster/"):
        return "cluster"
    if path == "/repo" or path.startswith("/repo/"):
        return "logstore"
    if path in ("/ping", "/health"):
        return "health"
    return "other"


def time_now_s() -> float:
    import time as _t

    # wall clock: PromQL evaluation timestamp, not a duration
    return _t.time()  # ogtlint: disable=OGT040


def _prom_time(s: str | None) -> float:
    """Prom API time param: unix seconds (float) or RFC3339."""
    if s is None:
        raise ValueError("missing time parameter")
    try:
        return float(s)
    except ValueError:
        pass
    return cond.parse_rfc3339(s) / 1e9


def _prom_step(s: str | None) -> float:
    if s is None:
        raise ValueError("missing step parameter")
    try:
        return float(s)
    except ValueError:
        return parse_duration_s(s)


class _TLSThreadingServer(ThreadingHTTPServer):
    """TLS handshake in the worker thread: accept() returns the raw
    connection immediately (do_handshake_on_connect=False on the wrapped
    listener); finish_request — which ThreadingMixIn already runs in the
    per-connection thread — performs the bounded handshake."""

    def finish_request(self, request, client_address):
        import socket
        import ssl

        try:
            request.settimeout(30)
            request.do_handshake()
            request.settimeout(None)
        except (ssl.SSLError, OSError, socket.timeout):
            try:
                request.close()
            except OSError:
                pass
            return
        super().finish_request(request, client_address)


class HttpService:
    """Owns the HTTP listener; one Engine + Executor behind it."""

    def __init__(self, engine: Engine, host: str = "127.0.0.1", port: int = 8086,
                 prom_db: str = "prom", auth_enabled: bool = False,
                 tls: dict | None = None):
        self.engine = engine
        self.auth_enabled = auth_enabled
        self.executor = Executor(engine, auth_enabled=auth_enabled)
        self.users = self.executor.users
        self.prom = PromEngine(engine)
        self.prom_db = prom_db
        self.services: list = []  # populated by server.app.build
        self.meta_store = None  # MetaStore when clustered (server.app.build)
        self.router = None  # DataRouter when [cluster] data-routing is on
        self.flight = None  # FlightService when [flight] is configured
        self.scrub_service = None  # ScrubService (app build or lazy ctrl)
        from opengemini_tpu.server.logstore import LogStoreAPI

        self.logstore = LogStoreAPI(self)  # /repo log-mode surface
        # monitoring: SHOW QUERIES / /debug/queries pair in-flight
        # queries with the live acked-vs-durable ledger (PR 4)
        from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER

        _TRACKER.set_durability_provider(engine.durability_snapshot)
        handler = _make_handler(self)
        if tls:
            # serve every surface — client API, /internal/* data plane,
            # /raft/* — over TLS (reference: the https options of
            # lib/config sql.go applied to the httpd listener). The
            # handshake runs in the per-connection WORKER thread
            # (_TLSThreadingServer), never in the accept loop — one
            # stalled client must not block all new connections.
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(tls["certfile"], tls["keyfile"])
            self.httpd = _TLSThreadingServer((host, port), handler)
            self.httpd.socket = ctx.wrap_socket(
                self.httpd.socket, server_side=True,
                do_handshake_on_connect=False)
        else:
            self.httpd = ThreadingHTTPServer((host, port), handler)
        self.tls_enabled = bool(tls)
        self.port = self.httpd.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        tracing.watch_gc()  # runtime/gc_* from the first request on
        tracing.watch_pulse()   # runtime/pulse_*, stall records
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)


def format_result(result: dict, epoch: str | None) -> dict:
    """Convert internal ns times to the requested epoch, or RFC3339."""
    for res in result.get("results", []):
        for series in res.get("series", []):
            cols = series.get("columns", [])
            if not cols or cols[0] != "time":
                continue
            for row in series.get("values", []):
                t = row[0]
                if not isinstance(t, int):
                    continue
                if epoch:
                    row[0] = t // _EPOCH_DIV.get(epoch, 1)
                else:
                    row[0] = cond.format_rfc3339(t)
    return result


def _dumps(obj, indent: int | None = None) -> str:
    """Strict JSON: a stray non-finite float anywhere in a result must
    not serialize as a bare NaN/Infinity literal (unparseable by standard
    clients).  allow_nan=False makes the common all-finite case zero-cost;
    only offending payloads pay for the sanitize walk."""
    try:
        return json.dumps(obj, indent=indent, allow_nan=False)
    except ValueError:
        return json.dumps(_null_nonfinite(obj), indent=indent)


def _null_nonfinite(obj):
    """Deep-copy with non-finite floats replaced by None (influx marshals
    null). Only runs when a payload actually contains one."""
    import math

    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _null_nonfinite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_null_nonfinite(v) for v in obj]
    return obj


@contextlib.contextmanager
def _admitted():
    """An admission slot for one PromQL read, its wait a stage of the
    request like /query's (executor.execute records its own)."""
    with GOVERNOR.admitted() as token:
        if token.waited_ns:
            tracing.record_stage("admission_wait", token.waited_ns)
        yield


def _make_handler(svc: HttpService):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        server_version = "opengemini-tpu/" + __version__
        # headers and payload flush as separate send()s; with Nagle on,
        # the payload send stalls ~40ms waiting for the client's delayed
        # ACK of the header packet — every keep-alive response paid it
        disable_nagle_algorithm = True

        def log_message(self, fmt, *args):  # quiet; logging layer comes later
            pass

        def handle_one_request(self):
            # during a profiler capture the wait for this connection's
            # next request line is `ogt:conn_idle`: an idle gap of the
            # device then reads "nothing was asked", "the process stood
            # still" (`ogt:pulse`) or a stage.  Annotation only
            ann = tracing.annotated("conn_idle")
            if ann is not None:
                try:
                    self.rfile.peek(1)
                except TimeoutError:
                    self.close_connection = True
                    return
                except OSError:
                    pass            # the read below meets it again
                finally:
                    ann.__exit__(None, None, None)
            super().handle_one_request()

        # -- plumbing -------------------------------------------------------

        def _params(self) -> dict:
            parsed = urllib.parse.urlparse(self.path)
            qs = urllib.parse.parse_qs(parsed.query)
            return {k: v[-1] for k, v in qs.items()}

        def _body(self) -> bytes:
            """Read (and cache) the request body. Caching makes _body()
            idempotent so handlers can drain the socket for keep-alive
            correctness even when they ignore the payload."""
            cached = getattr(self, "_body_cache", None)
            if cached is not None:
                return cached
            length = int(self.headers.get("Content-Length", 0))
            data = self.rfile.read(length) if length else b""
            if self.headers.get("Content-Encoding") == "gzip":
                data = gzip.decompress(data)
            self._body_cache = data
            return data

        def _internal_request(self, svc) -> dict | None:
            """Parse + authorize a peer-to-peer /internal/* request: one
            shared implementation of the cluster-token policy (the data
            plane must not bypass auth without the shared secret vouching
            for the caller). Sends the error response and returns None on
            rejection."""
            try:
                req = json.loads(self._body())
            except ValueError:
                req = None
            if not isinstance(req, dict) or not req.get("db"):
                self._send_json(400, {"error": "db required"})
                return None
            token = getattr(svc.meta_store, "token", "") if svc.meta_store else ""
            if token and req.get("token") != token:
                self._send_json(403, {"error": "bad cluster token"})
                return None
            if not token and svc.auth_enabled:
                self._send_json(403, {"error": "cluster token required"})
                return None
            return req

        @staticmethod
        def _primary_filter(svc, req):
            """rf>1 shard filter: serve only groups this node is PRIMARY
            for among the caller's live set, so each group is counted
            exactly once cluster-wide."""
            live = req.get("live")
            if (int(req.get("rf", 1)) > 1 and live
                    and svc.router is not None):
                return lambda sh: svc.router.is_primary(
                    req["db"], req.get("rp"), sh.tmin, live)
            return None

        def _send(self, code: int, payload: bytes = b"", ctype: str = "application/json"):
            # keep-alive correctness for EVERY early reply (auth failure,
            # bad request, shed) on a request whose body was never read:
            # unread payload left in the socket desyncs the next
            # pipelined request into BrokenPipe/BadStatusLine storms
            # under torture load.  _body() caches, so handlers that
            # already read it pay nothing; draining before the status
            # line keeps the HTTP exchange well-ordered.
            if getattr(self, "_body_cache", None) is None and \
                    self.headers.get("Content-Length"):
                try:
                    # raw socket consumption only: a shed/reject reply
                    # must not pay gzip decompression for a payload it
                    # is refusing to process.  Draining is bounded — an
                    # oversized rejected body costs a connection close,
                    # not reading it all just to preserve keep-alive
                    n = int(self.headers["Content-Length"])
                    if n > _DRAIN_CAP_BYTES:
                        self.close_connection = True
                    else:
                        # bounded wait: a client that declared a length
                        # and stalls must cost a closed connection, not
                        # a pinned handler thread (pre-auth DoS)
                        prev = self.connection.gettimeout()
                        self.connection.settimeout(_DRAIN_TIMEOUT_S)
                        try:
                            while n > 0:
                                got = self.rfile.read(min(n, 1 << 20))
                                if not got:
                                    break
                                n -= len(got)
                        finally:
                            self.connection.settimeout(prev)
                        if n > 0:  # short body: socket is desynced
                            self.close_connection = True
                except (OSError, ValueError):
                    # torn/stalled socket: reply anyway, then close (the
                    # unread remainder makes keep-alive unusable)
                    self.close_connection = True
                self._body_cache = b""
            trace = tracing.active_trace()
            if trace is not None:
                trace.root.add_field("status", code)
                trace.root.add_field("bytes_out", len(payload))
            with tracing.span("send", status=code, bytes=len(payload)):
                self.send_response(code)
                if self.close_connection:
                    self.send_header("Connection", "close")
                if payload:
                    self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                self.send_header("X-Influxdb-Version",
                                 "1.8.0-" + __version__)
                extra = getattr(self, "_extra_headers", None)
                if extra:
                    for k, v in extra.items():
                        self.send_header(k, v)
                    self._extra_headers = None
                self.end_headers()
                if payload:
                    self.wfile.write(payload)

        def _send_err(self, status: int, exc: BaseException,
                      extra: dict | None = None):
            """Error response with the stable errno taxonomy attached:
            X-Ogt-Errno header + errno field (reference lib/errno — the
            code is what fleet log triage greps)."""
            from opengemini_tpu.utils import errno as _errno

            code, mod = _errno.classify(exc)
            body = {"error": str(exc), "errno": code,
                    "module": mod.name.lower()}
            if extra:
                body.update(extra)
            self._send_json(status, body,
                            headers={"X-Ogt-Errno": str(code)})

        def _send_json(self, code: int, obj: dict, pretty: bool = False,
                       headers: dict | None = None):
            self._extra_headers = headers
            indent = 4 if pretty else None
            with tracing.span("serialize"):
                payload = (_dumps(obj, indent) + "\n").encode("utf-8")
            self._send(code, payload)

        def _authenticate(self, params: dict):
            """Basic auth header or u/p params (influx 1.x). Returns the
            user, or None when auth is disabled; sends 401 and returns
            False on failure."""
            if not svc.auth_enabled:
                return None
            if len(svc.users) == 0:
                # bootstrap: with no users yet, requests pass so the first
                # admin can be created (influx 1.x behavior)
                return None
            from opengemini_tpu.meta.users import AuthError
            import base64

            name = params.get("u")
            pw = params.get("p")
            header = self.headers.get("Authorization", "")
            if name is None and header.startswith("Basic "):
                try:
                    raw = base64.b64decode(header[6:]).decode("utf-8")
                    name, _, pw = raw.partition(":")
                except Exception:  # noqa: BLE001
                    name = None
            if name is None:
                self._send_json(401, {"error": "unable to parse authentication credentials"})
                return False
            try:
                return svc.users.authenticate(name, pw or "")
            except AuthError as e:
                self._send_err(401, e)
                return False

        # -- routes ---------------------------------------------------------

        def do_GET(self):
            self._observed("GET", self._do_get)

        def do_POST(self):
            self._observed("POST", self._do_post)

        def do_DELETE(self):
            self._observed("DELETE", self._do_delete)

        def _observed(self, method: str, dispatch) -> None:
            """Endpoint latency histograms (ogt_http_request_seconds,
            labeled by coarse route class + method).  One enabled-flag
            read when histograms are off (OGT_TRACE=0)."""
            from opengemini_tpu.utils.stats import obs_enabled

            if not obs_enabled():
                dispatch()
                return
            import time as _t

            t0 = _t.perf_counter_ns()
            try:
                dispatch()
            finally:
                _observe_ns(
                    "http_request_seconds", _t.perf_counter_ns() - t0,
                    route=_route_of(urllib.parse.urlparse(self.path).path),
                    method=method)

        def _do_get(self):
            self._form_pairs = ()  # reset per request (keep-alive reuse)
            self._body_cache = None
            path = urllib.parse.urlparse(self.path).path
            if path == "/ping":
                self._send(204)
            elif path == "/health":
                self._send_json(200, {"name": "opengemini-tpu", "status": "pass",
                                      "version": __version__})
            elif path == "/query":
                self._handle_query(self._params(), read_only=True)
            elif path == "/api/v1/consume":
                self._handle_consume(self._params())
            elif path == "/repo" or path.startswith("/repo/"):
                self._logstore("GET", path, self._params())
            elif path.startswith("/api/v1/"):
                self._handle_prom(path, self._params())
            elif path == "/raft/status" and svc.meta_store is not None:
                user = self._authenticate(self._params())
                if user is False:
                    return
                self._send_json(200, svc.meta_store.status())
            elif path == "/cluster/health" and svc.router is not None:
                # peer view exchange for the quorum failure view
                # (DataRouter.exchange_health); token-gated like the
                # /internal data plane
                token = getattr(svc.router, "token", "")
                sent = self.headers.get("X-Ogt-Token", "")
                if token and sent != token:
                    self._send_json(403, {"error": "bad cluster token"})
                    return
                if not token and svc.auth_enabled:
                    self._send_json(403, {"error": "cluster token required"})
                    return
                import time as _t

                ts = svc.router.health_ts
                self._send_json(200, {
                    "id": svc.router.self_id,
                    "health": svc.router.health,
                    # RELATIVE age of the probe, not a wall-clock stamp:
                    # the voter's staleness cut must not depend on clocks
                    # agreeing across nodes (NTP skew > the threshold
                    # would silently disqualify a healthy peer's votes)
                    "age_s": (_t.time() - ts) if ts else None,  # ogtlint: disable=OGT040 (health_ts wall pair)
                })
            elif path == "/metrics":
                # Prometheus text-format export (the statisticsPusher
                # analogue): every registry counter/gauge + histogram
                # under ogt_* names, scrapeable by a real Prometheus
                from opengemini_tpu.utils.stats import render_prometheus

                self._send(
                    200, render_prometheus(__version__).encode("utf-8"),
                    ctype="text/plain; version=0.0.4; charset=utf-8")
            elif path == "/debug/vars":
                import time as _t

                snap = {"system": {"uptime_s": round(
                    _t.perf_counter() - STATS.started_pc, 1),
                                   "version": __version__}}
                snap.update(STATS.snapshot())
                # not counters (/metrics and the monitor skip them): the
                # slowest requests since the mark, and the late beats
                snap["tail"] = tracing.tail_doc()
                snap["stalls"] = tracing.stalls_doc()
                self._send_json(200, snap)
            elif path == "/debug/queries":
                from opengemini_tpu.utils.querytracker import (
                    GLOBAL as _TRACKER,
                )

                self._send_json(200, _TRACKER.full_snapshot())
            elif path == "/debug/device":
                # device-runtime telemetry (utils/devobs.py): device
                # table, jit-cache inventory, retained-buffer ledger by
                # owner, bounded recent-compile ring, capability probes —
                # plus the offload planner's model/decision state
                # (query/offload.py; devobs itself stays decoupled)
                from opengemini_tpu.query import offload as _offload
                from opengemini_tpu.utils import devobs as _devobs

                doc = _devobs.debug_doc()
                doc["planner"] = _offload.GLOBAL.debug_doc()
                self._send_json(200, doc)
            elif path == "/debug/trace":
                self._handle_debug_trace(self._params())
            elif path == "/debug/slow":
                from opengemini_tpu.utils.slowlog import GLOBAL as _SLOW

                self._send_json(200, dict(
                    _SLOW.snapshot(), tail=tracing.tail_doc(),
                    stalls=tracing.stalls_doc()))
            else:
                self._send_json(404, {"error": "not found"})

        def _handle_debug_trace(self, params: dict) -> None:
            """?qid= serves one stitched span tree (a RUNNING query's
            live tree, else the finished-trace ring); ?trace_id= looks
            up by trace id; bare = newest-first summaries."""
            from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER

            qid_s = params.get("qid", "")
            if qid_s:
                try:
                    qid = int(qid_s)
                except ValueError:
                    self._send_json(400, {"error": f"bad qid {qid_s!r}"})
                    return
                live = _TRACKER.trace_of(qid)
                if live is not None:
                    self._send_json(200, {
                        "qid": qid, "status": "running",
                        "trace_id": live.trace_id,
                        "trace": live.to_dict()})
                    return
                doc = tracing.get_trace(qid=qid)
                if doc is None:
                    self._send_json(
                        404, {"error": f"no trace for qid {qid} "
                              "(finished long ago, or OGT_TRACE off)"})
                    return
                self._send_json(200, dict(doc, status="finished"))
                return
            tid = params.get("trace_id", "")
            if tid:
                doc = tracing.get_trace(trace_id=tid)
                if doc is None:
                    self._send_json(
                        404, {"error": f"no trace {tid!r}"})
                    return
                self._send_json(200, dict(doc, status="finished"))
                return
            self._send_json(200, {
                "enabled": tracing.trace_enabled(),
                "recent": tracing.recent_traces()})

        def _merge_form_body(self, params: dict) -> None:
            body = self._body().decode("utf-8", errors="replace")
            if body and self.headers.get("Content-Type", "").startswith(
                "application/x-www-form-urlencoded"
            ):
                self._form_pairs = urllib.parse.parse_qsl(body)
                for k, v in urllib.parse.parse_qs(body).items():
                    params.setdefault(k, v[-1])

        def _do_post(self):
            self._form_pairs = ()  # reset per request (keep-alive reuse)
            self._body_cache = None
            path = urllib.parse.urlparse(self.path).path
            params = self._params()
            if path == "/query":
                self._merge_form_body(params)
                self._handle_query(params)
            elif path == "/write":
                self._handle_write(params, db=params.get("db", ""),
                                   rp=params.get("rp") or None)
            elif path == "/api/v2/write":
                bucket = params.get("bucket", "")
                db, _, rp = bucket.partition("/")
                self._handle_write(params, db=db, rp=rp or None)
            elif path == "/api/v1/prom/write":
                self._handle_prom_remote_write(params)
            elif path == "/api/v1/prom/read":
                self._handle_prom_remote_read(params)
            elif path == "/api/v1/otlp/metrics":
                self._handle_otlp_metrics(params)
            elif path == "/repo" or path.startswith("/repo/"):
                self._logstore("POST", path, params)
            elif path.startswith("/api/v1/"):
                self._merge_form_body(params)
                self._handle_prom(path, params)
            elif path == "/raft/msg" and svc.meta_store is not None:
                from opengemini_tpu.meta.raft import RaftNode as _RN

                try:
                    msg = json.loads(self._body())
                except ValueError:
                    msg = None
                if not _RN.valid_message(msg):
                    self._send_json(400, {"error": "bad raft message"})
                    return
                token = getattr(svc.meta_store, "token", "")
                if token and msg.pop("token", None) != token:
                    self._send_json(403, {"error": "bad cluster token"})
                    return
                msg.pop("token", None)
                sender_addr = msg.pop("addr", None)
                if sender_addr:
                    # learn the sender's reachable address (token already
                    # verified): lets a joiner answer a leader it has
                    # never seen in config
                    transport = svc.meta_store.node.transport
                    addr_of = getattr(transport, "addr_of", None)
                    if addr_of is not None:
                        addr_of[msg["from"]] = sender_addr
                svc.meta_store.node.deliver(msg)
                self._send(204)
            elif path == "/internal/write":
                req = self._internal_request(svc)
                if req is None:
                    return
                # replica-side backpressure: the coordinator classifies
                # this 429 as transient and queues the copy as a hint,
                # so shedding here never costs acked durability
                if self._shed_write_if_backpressured():
                    return
                from opengemini_tpu.parallel.cluster import decode_points

                # replica-side child span: a routed write from a traced
                # coordinator executes under it and ships it back in the
                # ack, so the coordinator's tree shows which replica
                # (and which phase) ate the time
                _rtrace = tracing.start_remote(
                    "internal_write", req.get("trace"),
                    node=getattr(svc.router, "self_id", "") or "")
                _fp("internal-write-before-apply")  # replica copy pending
                try:
                    points = decode_points(req.get("points", []))
                    if _rtrace is not None:
                        with tracing.activate(_rtrace), \
                                _rtrace.span("apply") as _sp:
                            n_rows = svc.engine.write_rows(
                                req["db"], points,
                                rp=req.get("rp") or None)
                            _sp.add_field("rows", n_rows)
                    else:
                        svc.engine.write_rows(req["db"], points,
                                              rp=req.get("rp") or None)
                except DatabaseNotFound as e:
                    # a replica lagging meta propagation transiently
                    # lacks the db: 404 keeps the copy hinted until it
                    # appears (the coordinator poisons only on 400)
                    self._send_err(404, e)
                    return
                except (FieldTypeConflict, KeyError, TypeError,
                        ValueError) as e:
                    self._send_json(400, {"error": f"bad points: {e}"})
                    return
                except WriteError as e:
                    # deterministic rejection of THIS payload (unknown
                    # rp, invalid measurement): 400 so the coordinator
                    # classifies it poison instead of hinting a copy
                    # that can never be delivered — 403 stays reserved
                    # for the cluster-token check, whose rotation
                    # window is transient and must not destroy hints
                    self._send_err(400, e)
                    return
                # the hairiest replica edge: the write IS durable but the
                # ack dies here — the coordinator must classify it
                # unreachable and hint a (LWW-idempotent) duplicate copy
                _fp("internal-write-before-reply")
                out = {"ok": True}
                sub = tracing.ship_subtree(_rtrace)
                if sub is not None:
                    out["trace"] = sub
                self._send_json(200, out)
            elif path == "/internal/raftdata":
                # per-replica-group raft traffic (strict replication mode)
                dr = getattr(getattr(svc, "router", None), "datarep", None)
                if dr is None:
                    self._send_json(404, {"error": "replication mode off"})
                    return
                from opengemini_tpu.meta.raft import RaftNode as _RN

                try:
                    msg = json.loads(self._body())
                except ValueError:
                    msg = None
                if not isinstance(msg, dict):
                    self._send_json(400, {"error": "bad raft message"})
                    return
                if dr.token and msg.pop("token", None) != dr.token:
                    self._send_json(403, {"error": "bad cluster token"})
                    return
                if not dr.token and svc.auth_enabled:
                    self._send_json(403, {"error": "cluster token required"})
                    return
                msg.pop("token", None)
                msg.pop("addr", None)
                core = {k: v for k, v in msg.items()
                        if k not in ("group", "owners")}
                if not _RN.valid_message(core):
                    self._send_json(400, {"error": "bad raft message"})
                    return
                dr.deliver(msg)
                self._send(204)
            elif path == "/internal/raftdata_propose":
                dr = getattr(getattr(svc, "router", None), "datarep", None)
                if dr is None:
                    self._send_json(404, {"error": "replication mode off"})
                    return
                try:
                    req = json.loads(self._body())
                except ValueError:
                    req = None
                if not isinstance(req, dict) or not req.get("db"):
                    self._send_json(400, {"error": "db required"})
                    return
                if dr.token and req.pop("token", None) != dr.token:
                    self._send_json(403, {"error": "bad cluster token"})
                    return
                if not dr.token and svc.auth_enabled:
                    self._send_json(403, {"error": "cluster token required"})
                    return
                self._send_json(200, dr.handle_propose(req))
            elif path == "/internal/migrate":
                # two-phase shard-group migration (reference engine_ha.go
                # PreAssign/Assign/Rollback): begin -> staged writes ->
                # commit | abort; staging is invisible to queries and
                # TTL-expired if the pusher dies (MigrationService)
                req = self._internal_request(svc)
                if req is None:
                    return
                from opengemini_tpu.parallel.cluster import decode_points

                op = req.get("phase")
                mig = str(req.get("mig_id", ""))
                try:
                    if op == "begin":
                        _fp("internal-migrate-begin")
                        svc.engine.begin_staging(
                            req["db"], req.get("rp") or None,
                            int(req["group_start"]), mig)
                        out = {"ok": True}
                    elif op == "write":
                        _fp("internal-migrate-write")
                        n = svc.engine.write_staging(
                            mig, decode_points(req.get("points", [])))
                        out = {"ok": True, "rows": n}
                    elif op == "commit":
                        _fp("internal-migrate-commit")  # staged, not live
                        out = {"ok": True,
                               "rows": svc.engine.commit_staging(mig)}
                        # committed (marker durable) but the ack can still
                        # die here — the pusher's retried commit must get
                        # ok from the marker, not a restream
                        _fp("internal-migrate-commit-before-reply")
                    elif op == "abort":
                        _fp("internal-migrate-abort")
                        # always ok: an unknown mig means nothing is
                        # staged (never begun, TTL-expired, or already
                        # committed — where abort must NOT undo the
                        # fold), so the rollback is trivially complete
                        out = {"ok": True,
                               "aborted": svc.engine.abort_staging(mig)}
                    else:
                        self._send_json(400, {"error": f"bad phase {op!r}"})
                        return
                except (KeyError, TypeError, ValueError) as e:
                    self._send_json(400, {"error": f"bad migrate request: {e}"})
                    return
                except WriteError as e:
                    self._send_err(403, e)
                    return
                self._send_json(200, out)
            elif path in ("/internal/select_meta", "/internal/select_partials"):
                req = self._internal_request(svc)
                if req is None:
                    return
                # remote-initiated scans compete for the same memory as
                # local queries: admit them so peer fan-out cannot drive
                # a node past its budget while it sheds its own clients.
                # A 503 here surfaces on the coordinator as a clean
                # query error (PartialsUnavailable), not a node-down.
                try:
                    with GOVERNOR.admitted():
                        if path == "/internal/select_meta":
                            from opengemini_tpu.parallel.cluster import (
                                serialize_select_meta,
                            )

                            self._send_json(200, serialize_select_meta(
                                svc.engine, req["db"], req.get("rp"),
                                req.get("mst", ""),
                                int(req.get("tmin", -(2**62))),
                                int(req.get("tmax", 2**62)),
                                shard_filter=self._primary_filter(svc, req),
                            ))
                            return
                        from opengemini_tpu.query.partials import (
                            compute_partials,
                        )

                        try:
                            body = compute_partials(
                                svc.engine, svc.router, req)
                        except (KeyError, TypeError, ValueError) as e:
                            self._send_json(
                                400,
                                {"error": f"bad partials request: {e}"})
                            return
                except AdmissionRejected as e:
                    self._send_json(
                        503, {"error": str(e)},
                        headers={"Retry-After": str(e.retry_after_s)})
                    return
                self._send(200, body, ctype="application/octet-stream")
            elif path == "/internal/groups":
                # anti-entropy: which shard groups does this node hold?
                req = self._internal_request(svc)
                if req is None:
                    return
                groups = [[db, rp, start]
                          for (db, rp, start) in sorted(svc.engine._shards)]
                self._send_json(200, {"groups": groups})
            elif path == "/internal/load":
                # balancer: this node's shard-group byte footprint
                req = self._internal_request(svc)
                if req is None:
                    return
                self._send_json(200, svc.engine.disk_usage())
            elif path == "/internal/digest":
                # anti-entropy: this node's logical content digest of one
                # shard group (rf>1 replica divergence detection)
                req = self._internal_request(svc)
                if req is None:
                    return
                group = int(req.get("group_start", 0))
                digest: dict = {}
                for sh in svc.engine.shards_for_range(
                        req["db"], req.get("rp"), group, group + 1):
                    if sh.tmin == group:
                        digest = sh.content_digest()
                self._send_json(200, {"digest": digest})
            elif path in ("/internal/scan", "/internal/measurements"):
                from opengemini_tpu.parallel.cluster import serialize_series

                req = self._internal_request(svc)
                if req is None:
                    return
                if path == "/internal/scan":
                    # raw-series exchange materializes full decoded
                    # columns for a peer — the memory-heaviest remote
                    # read, so it takes an admission slot like
                    # select_partials.  The coordinator maps a 503 to
                    # a clean RemoteScanError, not a node-down.
                    try:
                        with GOVERNOR.admitted():
                            shard_filter = self._primary_filter(svc, req)
                            args = (svc.engine, req["db"], req.get("rp"),
                                    req.get("mst", ""),
                                    int(req.get("tmin", -(2**62))),
                                    int(req.get("tmax", 2**62)))
                            tkw = {
                                "trace_ctx": req.get("trace"),
                                "node": getattr(svc.router, "self_id", "")
                                or "",
                            }
                            if req.get("fmt") == "bin":
                                from opengemini_tpu.parallel.cluster import (
                                    serialize_series_binary,
                                )

                                self._send(200, serialize_series_binary(
                                    *args, shard_filter=shard_filter,
                                    **tkw),
                                    ctype="application/octet-stream")
                                return
                            payload = serialize_series(
                                *args, shard_filter=shard_filter, **tkw,
                            )
                    except AdmissionRejected as e:
                        self._send_json(
                            503, {"error": str(e)},
                            headers={"Retry-After": str(e.retry_after_s)})
                        return
                    except FileQuarantined as e:
                        # media damage detected mid-scan: the file is
                        # quarantined; answer a clean 500 so the
                        # coordinator's failover serves these ranges
                        # from a replica this round (a retry here
                        # succeeds without the file)
                        self._send_err(500, e)
                        return
                else:
                    names = set()
                    for sh in svc.engine.shards_for_range(
                            req["db"], req.get("rp"), -(2**62), 2**62):
                        names.update(sh.measurements())
                    payload = {"measurements": sorted(names)}
                self._send_json(200, payload)
            elif path in ("/cluster/register", "/cluster/deregister",
                          "/cluster/placement") and svc.meta_store is not None:
                try:
                    req = json.loads(self._body())
                except ValueError:
                    req = None
                if not isinstance(req, dict):
                    self._send_json(400, {"error": "json body required"})
                    return
                token = getattr(svc.meta_store, "token", "")
                if token and req.get("token") != token:
                    self._send_json(403, {"error": "bad cluster token"})
                    return
                if not token and svc.auth_enabled:
                    # roster/placement writes must not bypass auth without
                    # a shared secret (an attacker-registered node — or an
                    # attacker-placed group — would receive a share of all
                    # writes and feed every query)
                    self._send_json(403, {"error": "cluster token required"})
                    return
                if not svc.meta_store.is_leader():
                    hint = svc.meta_store.leader_hint()
                    self._send_json(
                        409, {"error": "not the meta leader", "leader": hint,
                              "leader_addr": svc.meta_store.meta_members().get(
                                  hint, "")})
                    return
                if path == "/cluster/register":
                    if not req.get("id") or not req.get("addr"):
                        self._send_json(400, {"error": "id and addr required"})
                        return
                    cmd = {"op": "register_node", "id": req["id"],
                           "addr": req["addr"],
                           "role": req.get("role", "data")}
                elif path == "/cluster/deregister":
                    # decommission roster drop, forwarded from the leaving
                    # node (or a survivor forcing out a dead peer)
                    if not req.get("id"):
                        self._send_json(400, {"error": "id required"})
                        return
                    cmd = {"op": "remove_node", "id": req["id"]}
                else:  # /cluster/placement — drain/balance owner override
                    owners_l = req.get("owners")
                    if (not req.get("key") or not isinstance(owners_l, list)
                            or not owners_l
                            or not all(isinstance(o, str) for o in owners_l)):
                        self._send_json(
                            400, {"error": "key and owners[] required"})
                        return
                    cmd = {"op": "set_placement", "key": req["key"],
                           "owners": owners_l}
                ok = svc.meta_store.propose_and_wait(cmd)
                self._send_json(200 if ok else 503,
                                {"ok": True} if ok else {"error": "no quorum"})
            elif path in ("/raft/join", "/raft/remove") and svc.meta_store is not None:
                try:
                    req = json.loads(self._body())
                except ValueError:
                    req = None
                if not isinstance(req, dict) or not req.get("id"):
                    self._send_json(400, {"error": "id required"})
                    return
                token = getattr(svc.meta_store, "token", "")
                if token and req.get("token") != token:
                    self._send_json(403, {"error": "bad cluster token"})
                    return
                if not svc.meta_store.is_leader():
                    hint = svc.meta_store.leader_hint()
                    self._send_json(
                        409, {"error": "not the meta leader", "leader": hint,
                              "leader_addr": svc.meta_store.meta_members().get(
                                  hint, "")})
                    return
                if path == "/raft/join":
                    if not req.get("addr"):
                        self._send_json(400, {"error": "addr required"})
                        return
                    ok = svc.meta_store.propose_conf_change(
                        "add", req["id"], req["addr"])
                else:
                    ok = svc.meta_store.propose_conf_change("remove", req["id"])
                if ok:
                    self._send_json(200, {"ok": True})
                else:
                    self._send_json(503, {"error": "conf change failed"})
            elif path == "/debug/ctrl":
                self._handle_syscontrol(params)
            else:
                self._send_json(404, {"error": "not found"})

        def _do_delete(self):
            self._form_pairs = ()  # reset per request (keep-alive reuse)
            self._body_cache = None
            path = urllib.parse.urlparse(self.path).path
            if path.startswith("/repo/"):
                self._logstore("DELETE", path, self._params())
            else:
                self._send_json(404, {"error": "not found"})

        def _handle_syscontrol(self, params: dict):
            """Runtime admin toggles (reference: lib/syscontrol
            syscontrol.go:42-300, /debug/ctrl?mod=...&switchon=...)."""
            user = self._authenticate(params)
            if user is False:
                return
            if svc.auth_enabled and not (user and user.admin):
                code = 401 if user is None else 403
                self._send_json(code, {"error": "admin required"})
                return
            mod = params.get("mod", "")
            on = params.get("switchon", "").lower() in ("true", "1")
            if mod == "disablewrite":
                svc.engine.write_disabled = on
            elif mod == "disableread":
                svc.engine.read_disabled = on
            elif mod == "readonly":
                svc.engine.write_disabled = on
            elif mod == "flush":
                svc.engine.flush_all()
            elif mod == "durability":
                # online acked-vs-durable invariant check (PR 4): cross-
                # checks every clean shard's ledger live and reports
                # loss/duplication without stopping the engine.  ONE
                # snapshot drives both fields, so the violations always
                # match the ledger state reported next to them.
                snap = svc.engine.durability_snapshot()
                violations = svc.engine.durability_check(snap)
                self._send_json(200, {
                    "status": "ok" if not violations else "violated",
                    "violations": violations,
                    "durability": snap,
                })
                return
            elif mod == "governor":
                # runtime tuning of the resource governor: each knob
                # changes only when passed; no knobs = status query.
                # budget_mb=0 disables (pass-through).
                knobs = {}
                for key in ("budget_mb", "max_concurrent", "queue",
                            "timeout_ms", "hiwat_pct", "lowat_pct",
                            "overdraft_pct", "bg_pause_pct",
                            "bg_max_pause_s", "bp_cache_ms"):
                    if key in params:
                        try:
                            # the anti-starvation bound is a duration —
                            # fractional seconds are meaningful
                            knobs[key] = (float(params[key])
                                          if key == "bg_max_pause_s"
                                          else int(params[key]))
                        except ValueError:
                            self._send_json(
                                400, {"error": f"bad {key}={params[key]!r}"})
                            return
                if knobs:
                    GOVERNOR.configure(**knobs)
                self._send_json(200, {"status": "ok",
                                      "governor": GOVERNOR.describe()})
                return
            elif mod == "netfault":
                # deterministic network-fault rules for THIS node's
                # OUTBOUND peer traffic (parallel/netfault.py): the
                # torture harness's partition lever.  No action =
                # status; action=off clears one rule; clear=1 heals all.
                from opengemini_tpu.parallel import netfault as _nf

                if params.get("clear", "").lower() in ("1", "true", "all"):
                    _nf.clear_all()
                    self._send_json(200, {"status": "ok", "rules": []})
                    return
                action = params.get("action", "")
                if not action:
                    self._send_json(200, {"rules": _nf.rules(),
                                          "hits": _nf.hits()})
                    return
                src = params.get("src", "*")
                dst = params.get("dst", "*")
                pat = params.get("path", "*")
                if action == "off":
                    _nf.clear_rule(src, dst, pat)
                else:
                    try:
                        _nf.set_rule(src, dst, pat, action)
                    except ValueError as e:
                        self._send_json(400, {"error": str(e)})
                        return
                self._send_json(200, {"status": "ok",
                                      "rules": _nf.rules()})
                return
            elif mod == "diskfault":
                # deterministic MEDIA-fault rules for this node's
                # storage IO (storage/diskfault.py): the scribble
                # torture's bit-flip/torn-write/EIO lever.  No action =
                # status; action=off clears one rule; clear=1 heals all.
                from opengemini_tpu.storage import diskfault as _df

                if params.get("clear", "").lower() in ("1", "true", "all"):
                    _df.clear_all()
                    self._send_json(200, {"status": "ok", "rules": []})
                    return
                action = params.get("action", "")
                if not action:
                    self._send_json(200, {"rules": _df.rules(),
                                          "hits": _df.hits()})
                    return
                pat = params.get("path", "*")
                if action == "off":
                    _df.clear_rule(pat)
                else:
                    try:
                        _df.set_rule(pat, action)
                    except ValueError as e:
                        self._send_json(400, {"error": str(e)})
                        return
                self._send_json(200, {"status": "ok",
                                      "rules": _df.rules()})
                return
            elif mod == "scrub":
                # integrity-scrub control (services/scrub.py): status +
                # quarantine inventory, op=tick forces one governed
                # sweep now, op=purge deletes quarantined files from
                # disk, mb=/interval_s= tune the pace live.
                from opengemini_tpu.services.scrub import ScrubService

                scrub = getattr(svc, "scrub_service", None)
                if scrub is None:
                    # no background service wired (embedded/test server):
                    # a ctrl-owned instance still serves manual ticks
                    scrub = svc.scrub_service = ScrubService(
                        svc.engine, 3600.0, router=svc.router)
                if scrub.router is None and svc.router is not None:
                    scrub.router = svc.router
                # two-phase knob apply (like app._apply_runtime_config):
                # a bad second param must reject the WHOLE request, not
                # leave the first knob silently half-applied
                staged = []
                for key, conv, attr in (("mb", int, "mb_per_tick"),
                                        ("interval_s", float,
                                         "interval_s")):
                    if key in params:
                        try:
                            val = conv(params[key])
                            if val <= 0:
                                raise ValueError(f"{key} must be > 0")
                        except ValueError as e:
                            self._send_json(400, {"error": str(e)})
                            return
                        staged.append((attr, val))
                for attr, val in staged:
                    setattr(scrub, attr, val)
                out = {"status": "ok"}
                op = params.get("op", "")
                if op == "tick":
                    out["verified_bytes"] = scrub.tick_now()
                elif op == "purge":
                    out["purged_files"] = svc.engine.purge_quarantined()
                elif op:
                    self._send_json(400, {"error": f"unknown op {op!r}"})
                    return
                out["scrub"] = scrub.status()
                out["quarantine"] = svc.engine.quarantine_snapshot()
                self._send_json(200, out)
                return
            elif mod == "cluster":
                # synchronous cluster-service rounds + RPC-hardening
                # knobs: lets the torture harness (and operators) force
                # a migrate/balance/hint-replay/anti-entropy round NOW
                # instead of waiting out a service interval, and inspect
                # breaker/staging/hint state between faults.
                router = svc.router
                if router is None:
                    self._send_json(400, {"error": "no data router"})
                    return
                for key, conv in (("cb_threshold", int),
                                  ("cb_cooldown_s", float),
                                  ("probe_timeout_s", float),
                                  ("rpc_retries", int)):
                    if key in params:
                        try:
                            val = conv(params[key])
                        except ValueError:
                            self._send_json(
                                400, {"error": f"bad {key}={params[key]!r}"})
                            return
                        # same clamps as the constructor: a negative
                        # retry count would make _post_raw's attempt
                        # loop run zero times and return None
                        if key == "cb_threshold":
                            router.breaker.threshold = val
                        elif key == "cb_cooldown_s":
                            router.breaker.cooldown_s = max(0.0, val)
                        elif key == "rpc_retries":
                            router.rpc_retries = max(0, val)
                        else:  # probe_timeout_s
                            router.probe_timeout_s = max(0.05, val)
                op = params.get("op", "")
                out: dict = {"status": "ok"}
                try:
                    if op == "migrate":
                        out["expired"] = svc.engine.expire_staging(
                            float(params.get("staging_ttl_s", 900)))
                        out["moved"] = router.migrate_round()
                    elif op == "balance":
                        out["move"] = router.balance_round()
                    elif op == "move":
                        out["move"] = router.force_move(
                            params.get("db") or None,
                            dest=params.get("dest") or None)
                    elif op == "hints":
                        out["delivered"] = router.replay_hints()
                    elif op == "antientropy":
                        out["repaired"] = router.anti_entropy_round()
                    elif op == "health":
                        out["health"] = router.exchange_health()
                    elif op == "add":
                        # elastic membership: register a data node in the
                        # roster (a [meta] join node self-registers; this
                        # covers pre-registration + repair)
                        out["add"] = router.add_node(
                            params.get("id", ""), params.get("addr", ""),
                            params.get("role", "data"))
                    elif op == "drain":
                        # one drain pass: disown + migrate + hint replay
                        out["drain"] = router.drain_round()
                    elif op == "decommission":
                        # drain-then-remove this node, or forced removal
                        # of a dead peer via node=<id>
                        out["decommission"] = router.decommission(
                            node=params.get("node") or None,
                            deadline_s=float(
                                params.get("deadline_s", 60.0)))
                    elif op:
                        self._send_json(
                            400, {"error": f"unknown cluster op {op!r}"})
                        return
                except Exception as e:  # noqa: BLE001 — a faulted round
                    # must report, not drop the ctrl connection
                    self._send_json(500, {"error": f"{op} failed: {e}"})
                    return
                out["breaker"] = router.breaker.snapshot()
                out["staging"] = svc.engine.staging_ids()
                out["pending_hints"] = sorted(router.pending_hint_nodes())
                out["nodes"] = sorted(router.data_nodes())
                out["decommission_state"] = router.decommission_state
                self._send_json(200, out)
                return
            elif mod == "rollup":
                # materialized-rollup ops (storage/rollup.py):
                #   (none)/status      per-spec watermark/dirty/backlog
                #   op=flush           run maintenance synchronously NOW
                #   op=invalidate      re-dirty [from,to) (all when unset)
                #   op=declare         declare a spec (db, name,
                #                      measurement, every_s | every_ns,
                #                      [fields, sketch, delay_s, rp])
                #   op=drop            drop a spec (db, name)
                from opengemini_tpu.storage.rollup import (
                    RollupSpec, enabled_by_env)

                op = params.get("op", "")
                mgr = svc.engine.rollup_mgr
                out = {"status": "ok", "enabled": enabled_by_env()}
                try:
                    if op == "declare":
                        every_ns = (
                            int(params["every_ns"]) if "every_ns" in params
                            else int(float(params["every_s"]) * NS))
                        fields = (params["fields"].split(",")
                                  if params.get("fields") else None)
                        delay_ns = (int(float(params["delay_s"]) * NS)
                                    if "delay_s" in params else None)
                        spec = RollupSpec(
                            params["name"], params["measurement"], every_ns,
                            rp=params.get("rp") or None, fields=fields,
                            sketch=params.get("sketch", "1") not in
                            ("0", "false"),
                            delay_ns=delay_ns)
                        svc.engine.create_rollup(params["db"], spec)
                        mgr = svc.engine.rollup_mgr
                    elif op == "drop":
                        svc.engine.drop_rollup(params["db"], params["name"])
                    elif op == "flush":
                        if mgr is not None:
                            out["folded"] = mgr.maintain()
                    elif op == "invalidate":
                        if mgr is not None:
                            out["invalidated"] = mgr.invalidate(
                                params["db"], params.get("name") or None,
                                int(params["from"]) if "from" in params
                                else None,
                                int(params["to"]) if "to" in params
                                else None)
                    elif op and op != "status":
                        self._send_json(
                            400, {"error": f"unknown rollup op {op!r}"})
                        return
                except KeyError as e:
                    self._send_json(
                        400, {"error": f"missing parameter {e.args[0]!r}"})
                    return
                except (ValueError, WriteError) as e:
                    self._send_json(400, {"error": str(e)})
                    return
                out["specs"] = mgr.status() if mgr is not None else {}
                self._send_json(200, out)
                return
            elif mod == "rules":
                # continuous rule engine ops (promql/rules.py):
                #   (none)/status      per-group watermark/alerts/tiles
                #   op=declare         declare a group (db, group,
                #                      [interval_s, lateness_s]) and/or
                #                      one rule (record=<name> |
                #                      alert=<name>, expr, [for_s,
                #                      labels, annotations] — JSON)
                #   op=drop            drop a rule (db, group, name) or
                #                      a whole group (db, group)
                #   op=tick            evaluate due groups NOW
                from opengemini_tpu.promql.rules import (
                    Rule, RuleError, RuleManager, enabled_by_env)

                op = params.get("op", "")
                mgr = svc.engine.rules_hook
                out = {"status": "ok", "enabled": enabled_by_env()}
                try:
                    if op == "declare":
                        if mgr is None and enabled_by_env():
                            # same lazy-construction idiom as rollups:
                            # the manager exists once config does
                            mgr = RuleManager(svc.engine)
                            svc.rules_manager = mgr
                        if mgr is None:
                            self._send_json(
                                400, {"error": "rules disabled (OGT_RULES=0)"})
                            return
                        interval_s = (float(params["interval_s"])
                                      if "interval_s" in params else None)
                        lateness_s = (float(params["lateness_s"])
                                      if "lateness_s" in params else None)
                        if "record" in params or "alert" in params:
                            kind = ("recording" if "record" in params
                                    else "alerting")
                            name = params.get("record") or params["alert"]
                            rule = Rule(
                                name, params["expr"], kind,
                                labels=json.loads(params["labels"])
                                if params.get("labels") else None,
                                for_s=float(params.get("for_s", 0.0)),
                                annotations=json.loads(params["annotations"])
                                if params.get("annotations") else None)
                            mgr.add_rule(params["db"], params["group"],
                                         rule, interval_s, lateness_s)
                        else:
                            mgr.declare_group(params["db"], params["group"],
                                              interval_s, lateness_s)
                    elif op == "drop":
                        if mgr is None:
                            self._send_json(
                                400, {"error": "no rule manager"})
                            return
                        if params.get("name"):
                            mgr.drop_rule(params["db"], params["group"],
                                          params["name"])
                        else:
                            mgr.drop_group(params["db"], params["group"])
                    elif op == "tick":
                        if mgr is not None:
                            out["ticked"] = mgr.tick(
                                int(params["now_ns"]) if "now_ns" in params
                                else None,
                                db=params.get("db") or None)
                    elif op and op != "status":
                        self._send_json(
                            400, {"error": f"unknown rules op {op!r}"})
                        return
                except KeyError as e:
                    self._send_json(
                        400, {"error": f"missing parameter {e.args[0]!r}"})
                    return
                except (RuleError, ValueError, WriteError) as e:
                    self._send_json(400, {"error": str(e)})
                    return
                out["groups"] = mgr.status() if mgr is not None else {}
                self._send_json(200, out)
                return
            elif mod == "obs":
                # observability runtime tuning: trace capture on/off,
                # histogram arming, slow-query threshold + ring bound.
                # No knobs = status query.
                from opengemini_tpu.utils.slowlog import GLOBAL as _SLOW
                from opengemini_tpu.utils.stats import (obs_enabled,
                                                        set_obs_enabled)

                try:
                    if "trace" in params:
                        tracing.set_trace_enabled(
                            params["trace"] in ("1", "true"))
                    if "hist" in params:
                        set_obs_enabled(params["hist"] in ("1", "true"))
                    if "slow_ms" in params:
                        v = params["slow_ms"]
                        # slow_ms= (empty) or slow_ms=off disables
                        _SLOW.configure(
                            slow_ms=None if v in ("", "off", "none")
                            else float(v))
                    if "slow_max" in params:
                        _SLOW.configure(slow_max=max(1, int(params["slow_max"])))
                except ValueError as e:
                    self._send_json(400, {"error": str(e)})
                    return
                if params.get("clear", "") in ("1", "true"):
                    _SLOW.clear()
                    tracing.clear_recent()
                if params.get("mark", "") in ("1", "true"):
                    tracing.mark()      # the tails and the pulse's maximum
                slow = _SLOW.snapshot()
                self._send_json(200, {
                    "status": "ok",
                    "trace": tracing.trace_enabled(),
                    "hist": obs_enabled(),
                    "slow_ms": slow["threshold_ms"],
                    "slow_max": slow["max_records"],
                    "slow_captured": slow["captured"],
                })
                return
            elif mod == "devobs":
                # device-runtime telemetry tuning: arm/disarm, warm-mark
                # the recompile tripwire, clear the compile ring, and
                # on-demand jax.profiler capture (single-capture guard).
                # No knobs = status query.
                from opengemini_tpu.utils import devobs as _devobs

                if "arm" in params:
                    _devobs.set_enabled(params["arm"] in ("1", "true"))
                if params.get("clear", "") in ("1", "true"):
                    _devobs.reset()
                op = params.get("op", "")
                if op == "mark_warm":
                    _devobs.mark_warm()
                elif op == "clear_warm":
                    _devobs.clear_warm()
                elif op == "profile":
                    try:
                        seconds = float(params.get("seconds", "2"))
                    except ValueError:
                        self._send_json(400, {
                            "error": f"bad seconds "
                                     f"{params.get('seconds')!r}"})
                        return
                    try:
                        started = _devobs.start_profile(
                            seconds, logdir=params.get("dir") or None,
                            python=params.get("python") in ("1", "true"))
                    except RuntimeError as e:
                        # capture already active (or backend refused):
                        # 409 so retry loops back off instead of
                        # stacking captures
                        self._send_json(409, {"error": str(e)})
                        return
                    self._send_json(200, {"status": "ok",
                                          "profile": started})
                    return
                elif op:
                    self._send_json(400, {
                        "error": f"unknown devobs op {op!r}"})
                    return
                self._send_json(200, {
                    "status": "ok",
                    "armed": _devobs.enabled(),
                    "compiles_since_warm": _devobs.compiles_since_warm(),
                    "ledger_bytes": _devobs.LEDGER.total_bytes(),
                    "profile": _devobs.profile_status(),
                })
                return
            elif mod == "offload":
                # adaptive offload planner (query/offload.py): arm/clear/
                # freeze the cost model, tune the decision knobs, pin the
                # prom host-kernels override, run a pre-warm sweep.
                # No knobs = status query (the planner debug doc).
                from opengemini_tpu.query import offload as _offload

                if "arm" in params:
                    _offload.set_enabled(params["arm"] in ("1", "true"))
                if "freeze" in params:
                    _offload.GLOBAL.set_frozen(
                        params["freeze"] in ("1", "true"))
                if params.get("clear", "") in ("1", "true"):
                    _offload.GLOBAL.clear()
                if "host_kernels" in params:
                    try:
                        _offload.set_prom_host_kernels_mode(
                            params["host_kernels"])
                    except ValueError as e:
                        self._send_json(400, {"error": str(e)})
                        return
                if "force" in params:
                    v = params["force"]
                    try:
                        _offload.set_force(
                            None if v in ("", "none") else v)
                    except ValueError as e:
                        self._send_json(400, {"error": str(e)})
                        return
                knobs = {}
                for k in ("min_samples", "explore_after"):
                    if k in params:
                        try:
                            knobs[k] = int(params[k])
                        except ValueError:
                            self._send_json(400, {
                                "error": f"bad {k} {params[k]!r}"})
                            return
                for k in ("amortize", "ewma"):
                    if k in params:
                        try:
                            knobs[k] = float(params[k])
                        except ValueError:
                            self._send_json(400, {
                                "error": f"bad {k} {params[k]!r}"})
                            return
                if knobs:
                    _offload.GLOBAL.configure(**knobs)
                op = params.get("op", "")
                if op == "prewarm":
                    ran = _offload.prewarm_once()
                    self._send_json(200, {"status": "ok",
                                          "prewarmed": ran})
                    return
                elif op:
                    self._send_json(400, {
                        "error": f"unknown offload op {op!r}"})
                    return
                doc = _offload.GLOBAL.debug_doc()
                doc["status"] = "ok"
                self._send_json(200, doc)
                return
            elif mod == "failpoint":
                from opengemini_tpu.utils import failpoint as _fpmod

                name = params.get("name", "")
                action = params.get("action", "")
                if not name:
                    self._send_json(200, {"active": _fpmod.active()})
                    return
                if action in ("", "off"):
                    _fpmod.disable(name)
                else:
                    _fpmod.enable(name, action)
                self._send_json(200, {"status": "ok", "failpoint": name,
                                      "action": action or "off"})
                return
            else:
                self._send_json(400, {"error": f"unknown syscontrol mod {mod!r}"})
                return
            self._send_json(200, {"status": "ok", "mod": mod, "switchon": on})

        def _handle_query(self, params: dict, read_only: bool = False):
            with tracing.request("query"):
                self._query(params, read_only)

        def _query(self, params: dict, read_only: bool):
            user = self._authenticate(params)
            if user is False:
                return
            q = params.get("q", "")
            if not q:
                self._send_json(400, {"error": "missing required parameter \"q\""})
                return
            from opengemini_tpu.meta.users import AuthError

            epoch = params.get("epoch")
            pretty = params.get("pretty") in ("true", "1")
            chunked = params.get("chunked") in ("true", "1")
            try:
                # a plain response is written from an aggregate's arrays
                # (_send_results); chunked and pretty ones read the tree
                result = svc.executor.execute(
                    q, db=params.get("db", ""), read_only=read_only,
                    user=user, frames=not (chunked or pretty),
                )
            except AuthError as e:
                self._send_err(403, e)
                return
            except AdmissionRejected as e:
                # admission control shed (resource governor): 503 +
                # Retry-After so well-behaved clients back off instead
                # of retrying into the same overload
                self._send_json(
                    503, {"error": str(e)},
                    headers={"Retry-After": str(e.retry_after_s)})
                return
            with tracing.span("format"):
                result = format_result(result, epoch)
            if chunked:
                try:
                    chunk_size = max(1, int(params.get("chunk_size", 10_000)))
                except ValueError:
                    self._send_json(400, {"error": "bad chunk_size"})
                    return
                with tracing.span("send", chunked=True):
                    self._send_chunked(result, chunk_size)
                return
            if pretty:
                self._send_json(200, result, pretty)
            else:
                self._send_results(result, epoch)

        def _send_results(self, result: dict, epoch: str | None):
            """`_send_json(200, result)`, byte for byte, statement by
            statement: a statement that carries `"frames"` has its
            `"series"` written from their arrays (query/render.py), the
            others are dumped."""
            from opengemini_tpu.query import render as qrender

            div = _EPOCH_DIV.get(epoch, 1) if epoch else None
            with tracing.span("serialize"):
                stmts = []
                for res in result["results"]:
                    members = []
                    for key, val in res.items():
                        if key == "frames":
                            key, val = "series", b"[%s]" % b", ".join(
                                qrender.rows_json(f, div) for f in val)
                        else:
                            val = _dumps(val).encode("utf-8")
                        members.append(
                            b"%s: %s" % (json.dumps(key).encode(), val))
                    stmts.append(b"{%s}" % b", ".join(members))
                payload = b'{"results": [' + b", ".join(stmts) + b"]}\n"
            self._send(200, payload)

        def _send_chunked(self, result: dict, chunk_size: int):
            """Influx chunked responses: newline-delimited JSON documents
            STREAMED via HTTP chunked transfer encoding — each document is
            serialized and written independently, never the whole response
            (handler.go chunked write path)."""
            # drained: /query reads params via _merge_form_body/_body()
            # before execution ever reaches here
            self.send_response(200)  # ogtlint: disable=OGT020
            self.send_header("Content-Type", "application/json")
            self.send_header("Transfer-Encoding", "chunked")
            self.send_header("X-Influxdb-Version", "1.8.0-" + __version__)
            self.end_headers()

            def emit(doc: dict) -> None:
                data = (json.dumps(doc) + "\n").encode("utf-8")
                self.wfile.write(f"{len(data):X}\r\n".encode("ascii"))
                self.wfile.write(data)
                self.wfile.write(b"\r\n")

            for res in result.get("results", []):
                base = {k: v for k, v in res.items() if k != "series"}
                series_list = res.get("series", [])
                if not series_list:
                    emit({"results": [base]})
                    continue
                for series in series_list:
                    values = series.get("values", [])
                    for off in range(0, max(len(values), 1), chunk_size):
                        part = dict(series)
                        part["values"] = values[off : off + chunk_size]
                        if off + chunk_size < len(values):
                            part["partial"] = True
                        emit({"results": [dict(base, series=[part])]})
            self.wfile.write(b"0\r\n\r\n")

        def _handle_prom(self, path: str, params: dict):
            """Prometheus HTTP API v1 (reference: handler_prom.go)."""
            if path in ("/api/v1/query_range", "/api/v1/query"):
                with tracing.request("prom"):
                    self._prom(path, params)
            else:
                self._prom(path, params)

        def _prom(self, path: str, params: dict):
            user = self._authenticate(params)
            if user is False:
                return
            db = params.get("db", svc.prom_db)
            if svc.auth_enabled and not (user and user.can("READ", db)):
                code = 401 if user is None else 403
                self._send_json(code, {"status": "error", "error": "read not authorized"})
                return
            matrix = None
            try:
                if path == "/api/v1/query_range":
                    # PromQL reads scan like any interactive query and must
                    # take an admission slot — otherwise this surface is an
                    # ungoverned side door around the /query sheds.  The
                    # matrix comes back as its JSON text, rendered in bulk
                    # (promql/render.py): json.dumps never sees a point
                    with _admitted():
                        matrix = svc.prom.query_range_json(
                            params.get("query", ""),
                            _prom_time(params.get("start")),
                            _prom_time(params.get("end")),
                            _prom_step(params.get("step")),
                            db,
                        )
                elif path == "/api/v1/query":
                    t = params.get("time")
                    with _admitted():
                        data = svc.prom.query_instant(
                            params.get("query", ""),
                            _prom_time(t) if t else time_now_s(),
                            db,
                        )
                elif path == "/api/v1/labels":
                    data = self._prom_labels(db)
                elif path == "/api/v1/series":
                    data = self._prom_series(db, params)
                elif path.startswith("/api/v1/label/") and path.endswith("/values"):
                    name = path[len("/api/v1/label/") : -len("/values")]
                    data = self._prom_label_values(db, name)
                elif path == "/api/v1/rules":
                    # prometheus rules endpoint (promql/rules.py) —
                    # empty groups, not 404, when no manager is live
                    mgr = svc.engine.rules_hook
                    data = mgr.rules_api() if mgr is not None \
                        else {"groups": []}
                elif path == "/api/v1/alerts":
                    mgr = svc.engine.rules_hook
                    data = mgr.alerts_api() if mgr is not None \
                        else {"alerts": []}
                else:
                    self._send_json(404, {"status": "error", "error": "not found"})
                    return
            except AdmissionRejected as e:
                self._send_json(
                    503,
                    {"status": "error", "errorType": "unavailable",
                     "error": str(e)},
                    headers={"Retry-After": str(e.retry_after_s)})
                return
            except QueryKilled as e:
                # prom queries register with the query tracker now, so
                # KILL QUERY cancels them like any /query statement
                self._send_json(
                    422, {"status": "error", "errorType": "canceled",
                          "error": str(e)})
                return
            except (PromError, PromParseError, ValueError, OverflowError, re.error) as e:
                self._send_json(
                    400, {"status": "error", "errorType": "bad_data", "error": str(e)}
                )
                return
            if matrix is not None:
                with tracing.span("serialize"):
                    payload = b'{"status": "success", "data": ' + matrix + b"}\n"
                self._send(200, payload)
                return
            self._send_json(200, {"status": "success", "data": data})

        def _prom_labels(self, db):
            names = {"__name__"}
            for sh in svc.engine.shards_for_range(db, None, -(2**62), 2**62):
                for mst in sh.measurements():
                    names.update(sh.index.tag_keys(mst))
            return sorted(names)

        def _prom_series(self, db, params):
            """/api/v1/series?match[]=selector — label sets of matching
            series, index-only (reference: prom compat, handler_prom.go).
            match[] may repeat; GET query string and POST form bodies both
            count (promtool/Grafana POST urlencoded bodies)."""
            from opengemini_tpu.promql import parser as prom_parser

            matches = [v for k, v in self._raw_params() if k == "match[]"]
            matches += [v for k, v in getattr(self, "_form_pairs", ())
                        if k == "match[]"]
            if not matches:
                raise ValueError("missing match[] parameter")
            out = []
            seen = set()
            for expr_text in matches:
                expr = prom_parser.parse(expr_text)
                if not isinstance(expr, prom_parser.VectorSelector):
                    raise ValueError("match[] must be a vector selector")
                for labels in svc.prom.series_labels(expr, db):
                    key = tuple(sorted(labels.items()))
                    if key not in seen:
                        seen.add(key)
                        out.append(labels)
            return out

        def _raw_params(self) -> list[tuple[str, str]]:
            parsed = urllib.parse.urlparse(self.path)
            return urllib.parse.parse_qsl(parsed.query)

        def _prom_label_values(self, db, name):
            vals = set()
            for sh in svc.engine.shards_for_range(db, None, -(2**62), 2**62):
                for mst in sh.measurements():
                    if name == "__name__":
                        vals.add(mst)
                    else:
                        vals.update(sh.index.tag_values(mst, name))
            return sorted(vals)

        def _handle_consume(self, params: dict):
            """Kafka-like cursor reads over a measurement (reference:
            services/consume — log-stream consumption with cursors).
            GET /api/v1/consume?db=&measurement=&cursor=&limit=
            cursor is opaque: "t:k" = rows consumed up to time t, k rows
            already taken AT exactly t (exact resume across ns ties)."""
            user = self._authenticate(params)
            if user is False:
                return
            db = params.get("db", "")
            mst = params.get("measurement", "")
            if svc.auth_enabled and not (user and user.can("READ", db)):
                # no bootstrap exemption: with auth on and zero users the
                # only open operation is creating the first admin
                self._send_json(403, {"error": "read not authorized"})
                return
            if getattr(svc.engine, "read_disabled", False):
                self._send_json(403, {"error": "reads are disabled (syscontrol)"})
                return
            if not db or not mst:
                self._send_json(400, {"error": "db and measurement are required"})
                return
            try:
                limit = int(params.get("limit", 1000))
            except ValueError:
                self._send_json(400, {"error": "bad limit"})
                return
            limit = max(1, min(limit, 10_000))
            cursor = params.get("cursor", "")
            from_t, skip_at_t = 0, 0
            if cursor:
                try:
                    a, _, b = cursor.partition(":")
                    from_t, skip_at_t = int(a), int(b)
                except ValueError:
                    self._send_json(400, {"error": "bad cursor"})
                    return
            try:
                with GOVERNOR.admitted():
                    rows, total = self._consume_gather(
                        db, mst, from_t, skip_at_t + limit)
            except AdmissionRejected as e:
                # consume decodes every matched series row >= from_t —
                # an interactive read surface like any other, so it must
                # take an admission slot rather than bypass the governor
                self._send_json(
                    503, {"error": str(e)},
                    headers={"Retry-After": str(e.retry_after_s)})
                return
            pos = 0
            remaining_skip = skip_at_t
            while pos < len(rows) and rows[pos][0] == from_t and remaining_skip > 0:
                pos += 1
                remaining_skip -= 1
            out = rows[pos : pos + limit]
            if out:
                last_t = out[-1][0]
                taken_at_last = sum(1 for r in out if r[0] == last_t)
                if last_t == from_t:
                    taken_at_last += skip_at_t - remaining_skip
                next_cursor = f"{last_t}:{taken_at_last}"
            else:
                next_cursor = cursor or "0:0"
            self._send_json(200, {
                "rows": [
                    {"time": t, "tags": tags, "fields": fields}
                    for t, tags, fields in out
                ],
                "cursor": next_cursor,
                "exhausted": total - (skip_at_t - remaining_skip) - len(out) <= 0,
            })

        def _consume_gather(self, db: str, mst: str, from_t: int,
                            need: int) -> tuple[list, int]:
            """Materialize one consume page: gather per-series arrays and
            bound python-row materialization to the page via the
            `need`-th (= skip + limit, ties included) smallest timestamp.
            Returns (sorted rows, total matched row count)."""
            import numpy as _np

            from opengemini_tpu.query.functions import py_value

            series_recs = []
            all_times = []
            for sh in svc.engine.shards_of_db(db):
                for sid in sorted(sh.index.series_ids(mst)):
                    rec = sh.read_series(mst, sid, from_t, 2**62)
                    if not len(rec):
                        continue
                    series_recs.append((sh.index.tags_of(sid), rec))
                    all_times.append(rec.times)
            total = sum(len(t) for t in all_times)
            if total and need < total:
                merged = _np.concatenate(all_times)
                kth = _np.partition(merged, need - 1)[need - 1]
                page_tmax = int(kth)  # inclusive; ties included below
            else:
                page_tmax = None
            rows = []
            for tags, rec in series_recs:
                sel = (
                    _np.nonzero(rec.times <= page_tmax)[0]
                    if page_tmax is not None
                    else range(len(rec))
                )
                for i in sel:
                    fields = {
                        name: py_value(col.values[i])
                        for name, col in rec.columns.items()
                        if col.valid[i]
                    }
                    rows.append((int(rec.times[i]), tags, fields))
            rows.sort(key=lambda r: r[0])
            return rows, total

        def _logstore(self, method: str, path: str, params: dict) -> None:
            """Dispatch to the /repo log-mode surface with governor shed
            mapping: logstore endpoints execute queries through the same
            admitted executor, so AdmissionRejected must answer 503 +
            Retry-After here too (not a dropped connection)."""
            try:
                handled = svc.logstore.handle(self, method, path, params)
            except AdmissionRejected as e:
                self._body()  # drain any unread body: keep-alive correctness
                self._send_json(
                    503, {"error": str(e)},
                    headers={"Retry-After": str(e.retry_after_s)})
                return
            if not handled:
                self._send_json(404, {"error": "not found"})

        def _shed_write_if_backpressured(self) -> bool:
            """Write-path backpressure (resource governor): when the
            memtable+WAL backlog is over the high watermark, answer 429 +
            Retry-After instead of growing RSS unboundedly.  Returns True
            when the write was shed (response already sent)."""
            retry_after = GOVERNOR.write_backpressure()
            if retry_after is None:
                return False
            self._body()  # drain the unread body: keep-alive correctness
            self._send_json(
                429,
                {"error": "write backpressure: memtable+WAL backlog over "
                          "the high watermark; retry later"},
                headers={"Retry-After": str(retry_after)})
            return True

        def _check_write_auth(self, params: dict, db: str) -> bool:
            user = self._authenticate(params)
            if user is False:
                return False
            if svc.auth_enabled and not (user and user.can("WRITE", db)):
                code = 401 if user is None else 403
                self._send_json(
                    code, {"error": f"write not authorized on {db!r}"})
                return False
            if not db:
                self._send_json(400, {"error": "database is required"})
                return False
            return True

        def _maybe_snappy(self, data: bytes) -> bytes:
            """Remote write/read bodies are snappy block compressed
            (Content-Encoding: snappy); tolerate raw protobuf too."""
            from opengemini_tpu.ingest import protowire as pw

            if self.headers.get("Content-Encoding") == "snappy":
                return pw.snappy_uncompress(data)
            try:
                return pw.snappy_uncompress(data)
            except pw.WireError:
                return data

        def _write_decoded_points(self, db: str, rp, points,
                                  consistency=None) -> bool:
            try:
                router = getattr(svc, "router", None)
                if router is not None:
                    router.routed_write(db, rp, points,
                                        consistency=consistency)
                else:
                    svc.engine.write_rows(db, points, rp=rp)
            except DatabaseNotFound as e:
                self._send_err(404, e)
                return False
            except (FieldTypeConflict, ValueError) as e:
                self._send_err(400, e, extra={"error": f"partial write: {e}"})
                return False
            except WriteError as e:
                self._send_err(403, e)
                return False
            return True

        def _handle_prom_remote_write(self, params: dict) -> None:
            """Prometheus remote write: snappy(protobuf WriteRequest)
            (reference: handler_prom.go:86 servePromWrite)."""
            from opengemini_tpu.ingest import prom_remote
            from opengemini_tpu.ingest.protowire import WireError

            db = params.get("db", "")
            if not self._check_write_auth(params, db):
                return
            if self._shed_write_if_backpressured():
                return
            try:
                body = self._maybe_snappy(self._body())
                points = prom_remote.decode_write_request(body)
            except (WireError, UnicodeDecodeError) as e:
                self._send_json(400, {"error": f"bad remote write body: {e}"})
                return
            if self._write_decoded_points(db, params.get("rp") or None, points):
                self._send(204)

        def _handle_prom_remote_read(self, params: dict) -> None:
            """Prometheus remote read: snappy(ReadRequest) ->
            snappy(ReadResponse) raw samples (reference:
            handler_prom.go servePromRead)."""
            from opengemini_tpu.ingest import prom_remote
            from opengemini_tpu.ingest import protowire as pw

            db = params.get("db", "")
            user = self._authenticate(params)
            if user is False:
                return
            if svc.auth_enabled and not (user and user.can("READ", db)):
                code = 401 if user is None else 403
                self._send_json(code, {"error": f"read not authorized on {db!r}"})
                return
            if not db:
                self._send_json(400, {"error": "database is required"})
                return
            try:
                body = self._maybe_snappy(self._body())
                queries = prom_remote.decode_read_request(body)
            except pw.WireError as e:
                self._send_json(400, {"error": f"bad remote read body: {e}"})
                return
            try:
                with GOVERNOR.admitted():
                    results = self._prom_remote_read_results(db, queries)
            except AdmissionRejected as e:
                # remote read materializes full matched series — it must
                # take an admission slot like every interactive read, not
                # bypass the governor (body already drained above)
                self._send_json(
                    503, {"error": str(e)},
                    headers={"Retry-After": str(e.retry_after_s)})
                return
            payload = prom_remote.encode_read_response(results)
            from opengemini_tpu.ingest.protowire import snappy_compress_literal
            out = snappy_compress_literal(payload)
            # drained: the read request was decoded from _body() above
            self.send_response(200)  # ogtlint: disable=OGT020
            self.send_header("Content-Type", "application/x-protobuf")
            self.send_header("Content-Encoding", "snappy")
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)

        def _prom_remote_read_results(self, db, queries) -> list:
            from opengemini_tpu.ingest import prom_remote
            from opengemini_tpu.promql.engine import _match_sids
            from opengemini_tpu.promql.parser import LabelMatcher

            MS = 1_000_000
            results = []
            for q in queries:
                metric = ""
                matchers = []
                for op, name, value in q["matchers"]:
                    if name == "__name__" and op == "=":
                        metric = value
                    else:
                        matchers.append(LabelMatcher(name, op, value))
                series_out = []
                if metric:
                    tmin = q["start_ms"] * MS
                    tmax = q["end_ms"] * MS + 1
                    per_key: dict = {}
                    for sh in svc.engine.shards_for_range(db, None, tmin, tmax):
                        for sid in sorted(_match_sids(sh, metric, matchers)):
                            rec = sh.read_series(
                                metric, sid, tmin, tmax,
                                fields=[prom_remote.VALUE_FIELD])
                            col = rec.columns.get(prom_remote.VALUE_FIELD)
                            if col is None or not len(rec):
                                continue
                            tags = sh.index.tags_of(sid)
                            key = tuple(sorted(tags.items()))
                            bucket = per_key.setdefault(key, (dict(tags), []))
                            v = col.valid
                            bucket[1].extend(
                                zip((rec.times[v] // MS).tolist(),
                                    col.values[v].tolist()))
                    for key in sorted(per_key):
                        labels, samples = per_key[key]
                        labels["__name__"] = metric
                        series_out.append((labels, sorted(samples)))
                results.append(series_out)
            return results

        def _handle_otlp_metrics(self, params: dict) -> None:
            """OTLP/HTTP metrics export (protobuf body, optional gzip)
            (reference: handler_otlp.go serveOtlpMetricsWrite)."""
            from opengemini_tpu.ingest import otlp
            from opengemini_tpu.ingest.protowire import WireError

            db = params.get("db", "")
            if not self._check_write_auth(params, db):
                return
            if self._shed_write_if_backpressured():
                return
            try:
                points = otlp.decode_metrics_request(self._body())
            except (WireError, UnicodeDecodeError) as e:
                self._send_json(400, {"error": f"bad OTLP body: {e}"})
                return
            if self._write_decoded_points(db, params.get("rp") or None, points):
                # empty ExportMetricsServiceResponse
                # drained: the OTLP payload was decoded from _body() above
                self.send_response(200)  # ogtlint: disable=OGT020
                self.send_header("Content-Type", "application/x-protobuf")
                self.send_header("Content-Length", "0")
                self.end_headers()

        def _handle_write(self, params: dict, db: str, rp):
            internal = bool(self.headers.get("X-Ogt-Internal"))
            if internal:
                # peer-forwarded write: the shared cluster token vouches
                # for it (the coordinator already authenticated the client)
                token = getattr(svc.meta_store, "token", "") if svc.meta_store else ""
                if (token and self.headers.get("X-Ogt-Token") != token) or (
                        not token and svc.auth_enabled):
                    self._send_json(403, {"error": "bad cluster token"})
                    return
            else:
                user = self._authenticate(params)
                if user is False:
                    return
                if svc.auth_enabled and not (user and user.can("WRITE", db)):
                    code = 401 if user is None else 403
                    self._send_json(
                        code, {"error": f"write not authorized on {db!r}"})
                    return
            if not db:
                self._send_json(400, {"error": "database is required"})
                return
            if self._shed_write_if_backpressured():
                return
            precision = params.get("precision", "ns")
            if precision == "n":
                precision = "ns"
            # the write's root span; under OGT_TRACE=1 the coordinator-
            # side tree: routed-write RPC fan-out under it carries wire
            # ctx, replica ack spans graft back, and the stitched tree
            # lands in the /debug/trace ring (no qid — writes are not
            # tracked queries; addressable by trace_id).  A peer-
            # forwarded write's spans belong to the coordinator's tree
            with tracing.request("write", tree=not internal, database=db):
                self._write_dispatch(params, db, rp, precision, internal)

        def _write_dispatch(self, params: dict, db: str, rp,
                            precision: str, internal: bool) -> None:
            try:
                router = getattr(svc, "router", None)
                if router is not None and not internal:
                    self._routed_write(router, db, rp, precision,
                                       consistency=params.get("consistency"))
                    return
                with tracing.span("read_body"):
                    body = self._body()
                svc.engine.write_lines(db, body, precision=precision, rp=rp)
            except DatabaseNotFound as e:
                self._send_err(404, e)
                return
            except (ParseError, FieldTypeConflict, ValueError) as e:
                self._send_err(400, e, extra={"error": f"partial write: {e}"})
                return
            except WriteError as e:
                self._send_err(403, e)
                return
            self._send(204)

        def _routed_write(self, router, db: str, rp, precision: str,
                          consistency=None):
            """Coordinator write: parse, then the shared routed_write
            sequence (split by owner, local structural write, structured
            JSON forwards)."""
            import time as _time

            if consistency is not None and consistency not in (
                    "any", "one", "quorum", "all"):
                # client typo = 400, never a retriable 503
                self._send_json(400, {
                    "error": f"invalid consistency {consistency!r} "
                             "(any, one, quorum, all)"})
                return

            from opengemini_tpu.ingest.line_protocol import parse_lines
            from opengemini_tpu.parallel.cluster import RemoteScanError

            try:
                with tracing.span("read_body"):
                    body = self._body()
                with tracing.span("lp_parse", bytes=len(body)):
                    points = parse_lines(body, precision, _time.time_ns())
                router.routed_write(db, rp, points,
                                    consistency=consistency)
            except RemoteScanError as e:
                self._send_json(503, {"error": f"forward failed: {e}"})
                return
            except DatabaseNotFound as e:
                self._send_err(404, e)
                return
            except (ParseError, FieldTypeConflict, ValueError) as e:
                self._send_err(400, e, extra={"error": f"partial write: {e}"})
                return
            except WriteError as e:
                self._send_err(403, e)
                return
            except OSError as e:
                self._send_json(503, {"error": f"forward failed: {e}"})
                return
            self._send(204)

    return Handler

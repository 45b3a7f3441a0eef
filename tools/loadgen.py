"""Closed-loop multi-client HTTP load generator.

Drives a running opengemini-tpu HTTP endpoint with a mixed write/query
workload from N concurrent closed-loop clients (each sends, waits for
the response, optionally paces to a target QPS, repeats), recording
per-class latency histograms (p50/p95/p99), shed counts (HTTP 429/503
from the resource governor, utils/governor.py), and error counts.

Used two ways:
  - `tests/test_governor.py` overload soak: writers + queries against a
    tiny `OGT_MEM_BUDGET_MB` — no OOM, no deadlock, every acked write
    durable, shed requests carry Retry-After;
  - standalone CLI:
      python tools/loadgen.py --host 127.0.0.1 --port 8086 --db load \
          --clients 32 --duration 10 --write-frac 0.6

Durability accounting: client i writes rows with tag client=c<i> and a
unique per-client timestamp (seq-derived), and records each ACKED batch
(seq range + write-consistency level + coordinator) — so a verifier can
prove every acked row is readable afterwards at its consistency level
(the acked-row contract the torture harnesses check).  Cluster mode:
`targets` spreads clients over multiple coordinators with transport
failover, and `ack_log` journals every acked batch fsynced — the ground
truth tools/cluster_torture.py verifies against.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os as _os
import sys as _sys
import threading
import time

# runnable standalone (`python tools/loadgen.py`): the package lives at
# the repo root, one directory up
_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _ROOT not in _sys.path:
    _sys.path.insert(0, _ROOT)

from opengemini_tpu.utils import lockdep  # noqa: E402 (needs _ROOT)


def percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted list (0 when empty)."""
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1, max(0, int(len(sorted_vals) * q / 100.0)))
    return sorted_vals[k]


def _lat_summary(lat_s: list[float]) -> dict:
    vals = sorted(lat_s)
    return {
        "count": len(vals),
        "p50_ms": round(percentile(vals, 50) * 1000, 3),
        "p95_ms": round(percentile(vals, 95) * 1000, 3),
        "p99_ms": round(percentile(vals, 99) * 1000, 3),
        "max_ms": round((vals[-1] if vals else 0.0) * 1000, 3),
    }


class _AckLog:
    """Fsynced acked-batch journal: the cluster torture harness's ground
    truth.  Each acked write appends one JSON line AFTER the 2xx came
    back, flushed + fsynced before the client proceeds — so the recorded
    set is a subset of what the cluster acked even if the harness itself
    dies (the same discipline as tools/torture.py's ack log)."""

    def __init__(self, path: str):
        import os

        self._f = open(path, "a", encoding="utf-8")
        self._os = os
        self._lock = lockdep.Lock()
        self._closed = False

    def record(self, rec: dict) -> None:
        with self._lock:
            if self._closed:
                return  # a stuck client's late ack after close: the
                # journaled set stays a subset of the cluster's acks
            self._f.write(json.dumps(rec, separators=(",", ":")) + "\n")
            self._f.flush()
            self._os.fsync(self._f.fileno())

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._f.close()


class _ClientState:
    __slots__ = ("idx", "seq", "acked", "write_lat", "query_lat",
                 "sheds_429", "sheds_503", "retry_after_seen", "killed",
                 "errors", "error_samples", "level", "targets", "target_i")

    def __init__(self, idx: int, level: str | None = None,
                 targets: list[str] | None = None):
        self.idx = idx
        self.level = level  # write consistency recorded per acked batch
        self.targets = targets or []  # "host:port" coordinators, failover
        self.target_i = 0
        self.seq = 0
        # acked batches: {"seq": start, "n": rows, "level": consistency,
        # "target": coordinator} — the verifier knows which rows must
        # survive which failure from the level
        self.acked: list[dict] = []
        self.write_lat: list[float] = []
        self.query_lat: list[float] = []
        self.sheds_429 = 0
        self.sheds_503 = 0
        self.retry_after_seen = 0
        self.killed = 0  # overdraft-killed queries (a governor shed)
        self.errors = 0
        self.error_samples: list[str] = []  # first few, for triage

    def note_error(self, what: str) -> None:
        self.errors += 1
        if len(self.error_samples) < 3:
            self.error_samples.append(what)


def client_base_ts(idx: int, ts_scale: int = 10**12) -> int:
    """Per-client disjoint timestamp namespace (ns): rows never collide
    across clients, so acked-row verification is an exact count.
    `ts_scale` spaces the namespaces — the cluster torture passes a
    scale wider than a shard-group duration so clients land in DISTINCT
    shard groups (migration/balance faults need several groups)."""
    return (idx + 1) * ts_scale


class _MetricsPoller:
    """Scrapes GET /metrics from one target on an interval (plus once
    at start and once after the workers join), tracking
    ogt_write_rows_total — the scrape-vs-observed consistency source."""

    METRIC = "ogt_write_rows_total"

    def __init__(self, target: str, interval_s: float,
                 timeout_s: float = 10.0):
        h, _, p = target.partition(":")
        self.host, self.port = h, int(p or 80)
        self.interval_s = max(0.05, interval_s)
        self.timeout_s = timeout_s
        self.scrapes = 0
        self.errors = 0
        self.first: float | None = None
        self.last: float | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def scrape_once(self) -> float | None:
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            body = resp.read().decode("utf-8", errors="replace")
            if resp.status != 200:
                raise OSError(f"/metrics status {resp.status}")
            val = 0.0
            for line in body.splitlines():
                if line.startswith(self.METRIC) and \
                        not line.startswith("#"):
                    # bare family (no labels): "<name> <value>"
                    val = float(line.split()[-1])
                    break
            # a successful scrape with the family absent means the
            # counter has not been created yet (lazy registry) — that IS
            # zero; leaving first=None here would latch the baseline
            # mid-run and misreport a consistency failure
            self.scrapes += 1
            if val is not None:
                if self.first is None:
                    self.first = val
                self.last = val
            return val
        except (OSError, ValueError, http.client.HTTPException):
            self.errors += 1
            return None
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def start(self) -> "_MetricsPoller":
        self.scrape_once()  # baseline BEFORE any load lands

        def run():
            while not self._stop.wait(self.interval_s):
                self.scrape_once()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="loadgen-metrics-poll")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.timeout_s + 1)
        self.scrape_once()  # final value AFTER every worker joined

    def summary(self, acked_rows: int) -> dict:
        delta = (self.last - self.first
                 if self.first is not None and self.last is not None
                 else None)
        return {
            "metric": self.METRIC,
            "scrapes": self.scrapes,
            "scrape_errors": self.errors,
            "first": self.first,
            "last": self.last,
            "metric_delta_rows": delta,
            "observed_acked_rows": acked_rows,
            # exact on a single node (nothing else writes): every acked
            # row is visible in the scraped counter, no phantom rows
            "consistent": (delta is not None
                           and int(delta) == int(acked_rows)),
        }


def run_load(host: str, port: int, db: str, clients: int = 8,
             duration_s: float = 5.0, write_frac: float = 0.5,
             target_qps: float | None = None, batch_rows: int = 50,
             measurement: str = "loadgen", query: str | None = None,
             timeout_s: float = 10.0, targets: list[str] | None = None,
             consistency: str | list[str] | None = None,
             ack_log: str | None = None, client_offset: int = 0,
             ts_scale: int = 10**12,
             metrics_poll_s: float | None = None) -> dict:
    """Run the closed-loop load; returns the aggregate summary dict.
    Shed responses (429 write backpressure / 503 admission) count
    separately from errors — shedding is the governor WORKING.

    Cluster mode: `targets` is a list of "host:port" coordinators —
    clients round-robin across them and FAIL OVER to the next on a
    transport error (a killed node costs its clients one failed request,
    not the rest of the run).  `consistency` sets the /write consistency
    level; a list cycles per client (e.g. ["one", "quorum"]) and the
    level is recorded on every acked batch.  `ack_log` appends each
    acked batch to an fsynced journal.  `client_offset` shifts the
    client tag/timestamp namespace so successive runs against the same
    database stay disjoint."""
    if query is None:
        query = f"SELECT count(v) FROM {measurement}"
    if targets is None:
        targets = [f"{host}:{port}"]
    levels = ([consistency] if isinstance(consistency, str)
              else list(consistency or [None]))
    states = [
        _ClientState(client_offset + i, level=levels[i % len(levels)],
                     targets=targets[i % len(targets):]
                     + targets[: i % len(targets)])
        for i in range(clients)
    ]
    journal = _AckLog(ack_log) if ack_log else None
    poller = (_MetricsPoller(targets[0], metrics_poll_s,
                             timeout_s=timeout_s).start()
              if metrics_poll_s else None)
    stop_at = time.monotonic() + duration_s
    per_client_qps = (target_qps / clients) if target_qps else None

    def _connect(st: _ClientState):
        h, _, p = st.targets[st.target_i % len(st.targets)].partition(":")
        return http.client.HTTPConnection(h, int(p or 80),
                                          timeout=timeout_s)

    def worker(st: _ClientState) -> None:
        conn = _connect(st)
        # deterministic write/query mix per client: no RNG, exact fraction
        acc = 0.0
        next_at = time.monotonic()
        try:
            while time.monotonic() < stop_at:
                if per_client_qps:
                    now = time.monotonic()
                    if now < next_at:
                        time.sleep(min(next_at - now, stop_at - now))
                        if time.monotonic() >= stop_at:
                            break
                    next_at += 1.0 / per_client_qps
                acc += write_frac
                do_write = acc >= 1.0
                if do_write:
                    acc -= 1.0
                t0 = time.monotonic()
                try:
                    if do_write:
                        base = client_base_ts(st.idx, ts_scale) + st.seq
                        body = "".join(
                            f"{measurement},client=c{st.idx} v={st.seq + k}i "
                            f"{base + k}\n"
                            for k in range(batch_rows)
                        ).encode()
                        url = f"/write?db={db}"
                        if st.level:
                            url += f"&consistency={st.level}"
                        conn.request("POST", url, body=body)
                        resp = conn.getresponse()
                        resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 204:
                            rec = {"client": st.idx, "seq": st.seq,
                                   "n": batch_rows, "level": st.level,
                                   "target": st.targets[
                                       st.target_i % len(st.targets)]}
                            if journal is not None:
                                # journal BEFORE counting it acked: a
                                # harness crash must never know of an
                                # acked batch the journal missed
                                journal.record(rec)
                            st.acked.append(rec)
                            st.seq += batch_rows
                            st.write_lat.append(dt)
                        elif resp.status == 429:
                            st.sheds_429 += 1
                            if resp.getheader("Retry-After"):
                                st.retry_after_seen += 1
                        elif resp.status == 503:
                            st.sheds_503 += 1
                            if resp.getheader("Retry-After"):
                                st.retry_after_seen += 1
                        else:
                            st.note_error(f"write status {resp.status}")
                    else:
                        from urllib.parse import quote

                        conn.request(
                            "GET", f"/query?db={db}&q={quote(query)}")
                        resp = conn.getresponse()
                        data = resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 200:
                            doc = json.loads(data)
                            errs = [r["error"]
                                    for r in doc.get("results", [])
                                    if "error" in r]
                            if not errs:
                                st.query_lat.append(dt)
                            elif any("killed" in e for e in errs):
                                # reservation-overdraft kill: the
                                # governor shedding work, not a fault
                                st.killed += 1
                            else:
                                st.note_error("query error: " + errs[0][:120])
                        elif resp.status == 503:
                            st.sheds_503 += 1
                            if resp.getheader("Retry-After"):
                                st.retry_after_seen += 1
                        elif resp.status == 429:
                            st.sheds_429 += 1
                        else:
                            st.note_error(f"query status {resp.status}")
                except (OSError, http.client.HTTPException, ValueError) as e:
                    st.note_error(f"transport: {type(e).__name__}: {e}")
                    try:
                        conn.close()
                    except OSError:
                        pass
                    # fail over to the next coordinator in this client's
                    # rotation (single-target mode reconnects in place)
                    st.target_i += 1
                    conn = _connect(st)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    threads = [threading.Thread(target=worker, args=(st,), daemon=True,
                                name=f"loadgen-{st.idx}") for st in states]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        # generous join bound: a worker past stop_at is finishing ONE
        # request; a longer hang means the server deadlocked (the soak
        # test asserts on leftover alive threads)
        t.join(timeout=duration_s + 4 * timeout_s)
    alive = sum(1 for t in threads if t.is_alive())
    wall_s = time.monotonic() - t_start
    if journal is not None:
        journal.close()
    if poller is not None:
        poller.stop()

    writes_ok = sum(len(st.write_lat) for st in states)
    queries_ok = sum(len(st.query_lat) for st in states)
    sheds = sum(st.sheds_429 + st.sheds_503 for st in states)
    killed = sum(st.killed for st in states)
    errors = sum(st.errors for st in states)
    attempts = writes_ok + queries_ok + sheds + killed + errors
    out = {
        "clients": clients,
        "duration_s": round(wall_s, 3),
        "attempts": attempts,
        "qps": round(attempts / max(wall_s, 1e-9), 1),
        "writes": _lat_summary([v for st in states for v in st.write_lat]),
        "queries": _lat_summary([v for st in states for v in st.query_lat]),
        "acked_rows": sum(r["n"] for st in states for r in st.acked),
        "acked_batches": {st.idx: st.acked for st in states},
        "sheds_429": sum(st.sheds_429 for st in states),
        "sheds_503": sum(st.sheds_503 for st in states),
        "retry_after_seen": sum(st.retry_after_seen for st in states),
        "killed_queries": killed,
        "shed_rate": (round((sheds + killed) / attempts, 4)
                      if attempts else 0.0),
        "errors": errors,
        "error_samples": [s for st in states for s in st.error_samples][:10],
        "stuck_clients": alive,
    }
    if poller is not None:
        out["metrics_poll"] = poller.summary(
            sum(r["n"] for st in states for r in st.acked))
    return out


def zipf_weights(n: int, s: float) -> list[float]:
    """Normalized Zipf weights over ranks 1..n (tenant popularity)."""
    raw = [1.0 / (r ** s) for r in range(1, n + 1)]
    total = sum(raw)
    return [w / total for w in raw]


def run_dashboard_fleet(host: str, port: int, clients: int = 12,
                        tenants: int = 4, zipf_s: float = 1.2,
                        duration_s: float = 6.0, write_frac: float = 0.3,
                        batch_rows: int = 50, window_s: int = 60,
                        range_s: int = 1800, measurement: str = "m",
                        timeout_s: float = 10.0, seed: int = 7) -> dict:
    """Dashboard-fleet scenario: zipf-distributed tenant databases, each
    client pinned to one tenant, issuing REPEATED IDENTICAL ``GROUP BY
    time()`` dashboard queries mixed with live ingest (recent
    timestamps) — the read shape materialized rollups
    (storage/rollup.py) and the incremental result cache exist to make
    cheap.  Reports per-tenant write/query p50/p99, shed counts, and
    error counts, so a hostile tenant's impact on the others' tail is
    measurable.  Declare rollup specs (/debug/ctrl?mod=rollup) before a
    run to A/B the splice."""
    import random

    rng = random.Random(seed)
    weights = zipf_weights(tenants, zipf_s)
    tenant_of = [
        rng.choices(range(tenants), weights=weights)[0]
        for _ in range(clients)
    ]
    # every tenant db exists before traffic (idempotent)
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    from urllib.parse import quote

    for t in range(tenants):
        conn.request(
            "POST", "/query?q=" + quote(f'CREATE DATABASE "tenant_{t}"'))
        conn.getresponse().read()
    conn.close()

    now_ns = time.time_ns()
    lo = (now_ns - range_s * 10 ** 9) // 10 ** 9 * 10 ** 9
    hi = now_ns // 10 ** 9 * 10 ** 9
    query = (f"SELECT mean(v), max(v), count(v) FROM {measurement} "
             f"WHERE time >= {lo} AND time < {hi} "
             f"GROUP BY time({window_s}s)")
    states = [_ClientState(i) for i in range(clients)]
    stop_at = time.monotonic() + duration_s

    def worker(st: _ClientState) -> None:
        tenant = tenant_of[st.idx]
        db = f"tenant_{tenant}"
        conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        acc = 0.0
        try:
            while time.monotonic() < stop_at:
                acc += write_frac
                do_write = acc >= 1.0
                if do_write:
                    acc -= 1.0
                t0 = time.monotonic()
                try:
                    if do_write:
                        # live ingest: recent, in-window timestamps (per
                        # client ns offsets keep series rows distinct)
                        base = time.time_ns() - st.idx
                        body = "".join(
                            f"{measurement},client=c{st.idx} "
                            f"v={st.seq + k}i {base - k * 1000}\n"
                            for k in range(batch_rows)
                        ).encode()
                        conn.request("POST", f"/write?db={db}", body=body)
                        resp = conn.getresponse()
                        resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 204:
                            st.seq += batch_rows
                            st.write_lat.append(dt)
                        elif resp.status in (429, 503):
                            st.sheds_429 += resp.status == 429
                            st.sheds_503 += resp.status == 503
                        else:
                            st.note_error(f"write status {resp.status}")
                    else:
                        conn.request(
                            "GET", f"/query?db={db}&q={quote(query)}")
                        resp = conn.getresponse()
                        data = resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 200:
                            doc = json.loads(data)
                            errs = [r["error"]
                                    for r in doc.get("results", [])
                                    if "error" in r]
                            if not errs:
                                st.query_lat.append(dt)
                            elif any("killed" in e for e in errs):
                                st.killed += 1
                            else:
                                st.note_error(
                                    "query error: " + errs[0][:120])
                        elif resp.status in (429, 503):
                            st.sheds_429 += resp.status == 429
                            st.sheds_503 += resp.status == 503
                        else:
                            st.note_error(f"query status {resp.status}")
                except (OSError, http.client.HTTPException,
                        ValueError) as e:
                    st.note_error(f"transport: {type(e).__name__}: {e}")
                    try:
                        conn.close()
                    except OSError:
                        pass
                    conn = http.client.HTTPConnection(
                        host, port, timeout=timeout_s)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    threads = [threading.Thread(target=worker, args=(st,), daemon=True,
                                name=f"fleet-{st.idx}") for st in states]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 4 * timeout_s)
    wall_s = time.monotonic() - t_start

    per_tenant = {}
    for t in range(tenants):
        members = [st for st in states if tenant_of[st.idx] == t]
        if not members:
            continue
        per_tenant[f"tenant_{t}"] = {
            "clients": len(members),
            "writes": _lat_summary(
                [v for st in members for v in st.write_lat]),
            "queries": _lat_summary(
                [v for st in members for v in st.query_lat]),
            "sheds": sum(st.sheds_429 + st.sheds_503 for st in members),
            "killed": sum(st.killed for st in members),
            "errors": sum(st.errors for st in members),
        }
    attempts = sum(
        len(st.write_lat) + len(st.query_lat) + st.sheds_429
        + st.sheds_503 + st.killed + st.errors for st in states)
    return {
        "scenario": "dashboard",
        "clients": clients,
        "tenants": tenants,
        "zipf_s": zipf_s,
        "duration_s": round(wall_s, 3),
        "attempts": attempts,
        "qps": round(attempts / max(wall_s, 1e-9), 1),
        "per_tenant": per_tenant,
        "stuck_clients": sum(1 for t in threads if t.is_alive()),
        "error_samples": [s for st in states
                          for s in st.error_samples][:10],
    }


def run_mixed_shapes(host: str, port: int, clients: int = 6,
                     duration_s: float = 5.0, tiny_shapes: int = 4,
                     zipf_s: float = 1.2, heavy_every: int = 5,
                     seed_rows: int = 153600, series: int = 64,
                     measurement: str = "mix", db: str = "mixed",
                     base_ns: int = 1_700_000_000 * 10 ** 9,
                     timeout_s: float = 15.0, seed: int = 11,
                     warmup_s: float = 0.0) -> dict:
    """Mixed-shape fleet for the offload planner (query/offload.py):
    a zipf-popular set of TINY recurring dashboard queries (short range,
    coarse window — the geometries that recur thousands of times and
    must never pay a device compile inline) interleaved with HEAVY cold
    scans (full seeded range at fine granularity — the shapes worth the
    device once their compile amortizes).  Deterministic end to end:
    data seeds at fixed absolute timestamps and the read-only query mix
    derives from `seed`, so two runs against identically-seeded engines
    return bit-identical bodies — `fingerprints` (sha256 per distinct
    query, issued once single-threaded after the fleet) is the equality
    contract between runs under different forced routes.  Reports
    per-class (tiny/heavy) p50/p99 and the planner's route/decision
    counter deltas scraped from /debug/device."""
    import hashlib
    import random
    from urllib.parse import quote

    # >= 64 series: the encoded (device-decodable) columns ride the
    # BULK scan, which engages at >= 64 series per shard
    series = max(64, series)
    step_ns = 10 ** 9  # one point per second per series
    span_ns = (seed_rows // max(1, series)) * step_ns
    lo, hi = base_ns, base_ns + span_ns

    # seed: `series` tagged series, one point/second, fixed timestamps
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    conn.request("POST", "/query?q=" + quote(f'CREATE DATABASE "{db}"'))
    conn.getresponse().read()
    n_per = seed_rows // max(1, series)
    for s in range(series):
        body = "".join(
            f"{measurement},series=s{s} v={float((s * 131 + k * 17) % 997)}"
            f" {base_ns + k * step_ns}\n"
            for k in range(n_per)
        ).encode()
        conn.request("POST", f"/write?db={db}", body=body)
        resp = conn.getresponse()
        resp.read()
        if resp.status != 204:
            conn.close()
            raise RuntimeError(f"mixed_shapes seed write: {resp.status}")
    # flush to TSF before the fleet: the scans under test read flushed
    # blocks through the column cache, not a live memtable tail
    conn.request("POST", "/debug/ctrl?mod=flush")
    conn.getresponse().read()
    conn.close()

    # tiny shapes: distinct (range, window) pairs — each is ONE
    # recurring geometry; zipf popularity concentrates repeats on the
    # hot ones exactly like a dashboard fleet does
    tiny = []
    for i in range(tiny_shapes):
        # short ranges: a tiny query touches ~5-8% of the span, the
        # dashboard "last N minutes" shape — cheap on the host, never
        # worth a per-geometry device compile
        r_ns = span_ns // (12 + 3 * i)  # distinct ranges -> shapes
        w_s = 30 + 15 * i
        tiny.append(
            f"SELECT mean(v) FROM {measurement} "
            f"WHERE time >= {hi - r_ns} AND time < {hi} "
            f"GROUP BY time({w_s}s)")
    # heavy scans: a few distinct full-span dashboard panels, each
    # re-issued round-robin.  SAME padded decode geometry across
    # variants (constant width + window count + series set -> one
    # device compile covers all); with OGT_RESULT_CACHE=0 every
    # issue re-executes — on the host route that is a
    # full decode+scatter per repeat, while the device route's decoded
    # grid stays RESIDENT in the colcache device tier and warm repeats
    # skip the decode entirely.  Residency, not raw decode speed, is
    # the device route's structural edge the planner has to find.
    heavy_w_ns = 2 * step_ns
    heavy_variants = max(1, min(4, (span_ns // heavy_w_ns) // 2))
    heavy_width = span_ns - heavy_variants * heavy_w_ns
    heavies = [
        (f"SELECT mean(v), max(v), count(v) FROM {measurement} "
         f"WHERE time >= {lo + j * heavy_w_ns} "
         f"AND time < {lo + j * heavy_w_ns + heavy_width} "
         f"GROUP BY time(2s)")
        for j in range(heavy_variants)
    ]
    weights = zipf_weights(tiny_shapes, zipf_s)

    def planner_counters() -> dict:
        c = http.client.HTTPConnection(host, port, timeout=timeout_s)
        try:
            c.request("GET", "/debug/device")
            doc = json.loads(c.getresponse().read())
            return dict(doc.get("planner", {}).get("counters", {}))
        except (OSError, ValueError, http.client.HTTPException):
            return {}
        finally:
            c.close()

    counters_before = planner_counters()
    states = [_ClientState(i) for i in range(clients)]
    heavy_lat: list[list[float]] = [[] for _ in range(clients)]
    # steady-state window: queries STARTING before warm_at run (they
    # drive the planner's learning + the compile caches) but are not
    # measured — p50/p99 compare the legs' converged behavior, the
    # thing a fleet actually lives with
    warm_at = time.monotonic() + max(0.0, warmup_s)
    stop_at = warm_at + duration_s
    # per-worker deterministic query sequence (seeded off the fleet seed)
    seqs = [random.Random(seed * 1000 + i) for i in range(clients)]

    def worker(st: _ClientState) -> None:
        conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        wrng = seqs[st.idx]
        n = 0
        try:
            while time.monotonic() < stop_at:
                n += 1
                is_heavy = heavy_every > 0 and n % heavy_every == 0
                q = (heavies[(n // heavy_every) % len(heavies)]
                     if is_heavy
                     else wrng.choices(tiny, weights=weights)[0])
                t0 = time.monotonic()
                try:
                    conn.request("GET", f"/query?db={db}&q={quote(q)}")
                    resp = conn.getresponse()
                    data = resp.read()
                    dt = time.monotonic() - t0
                    if resp.status == 200:
                        doc = json.loads(data)
                        errs = [r["error"]
                                for r in doc.get("results", [])
                                if "error" in r]
                        if errs:
                            st.note_error("query error: " + errs[0][:120])
                        elif t0 < warm_at:
                            pass  # warmup: drives learning, unmeasured
                        elif is_heavy:
                            heavy_lat[st.idx].append(dt)
                        else:
                            st.query_lat.append(dt)
                    elif resp.status in (429, 503):
                        st.sheds_429 += resp.status == 429
                        st.sheds_503 += resp.status == 503
                    else:
                        st.note_error(f"query status {resp.status}")
                except (OSError, http.client.HTTPException,
                        ValueError) as e:
                    st.note_error(f"transport: {type(e).__name__}: {e}")
                    try:
                        conn.close()
                    except OSError:
                        pass
                    conn = http.client.HTTPConnection(
                        host, port, timeout=timeout_s)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    threads = [threading.Thread(target=worker, args=(st,), daemon=True,
                                name=f"mixed-{st.idx}") for st in states]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=warmup_s + duration_s + 4 * timeout_s)
    wall_s = time.monotonic() - t_start
    counters_after = planner_counters()

    # the equality contract: every distinct query once, single-threaded,
    # hashed — identical seeding + identical data must hash identically
    # whatever routes the planner picked during the fleet
    fingerprints = {}
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        for name, q in [(f"heavy_{j}", q)
                        for j, q in enumerate(heavies)] + [
                (f"tiny_{i}", q) for i, q in enumerate(tiny)]:
            conn.request("GET", f"/query?db={db}&q={quote(q)}")
            fingerprints[name] = hashlib.sha256(
                conn.getresponse().read()).hexdigest()
    finally:
        conn.close()

    tiny_all = [v for st in states for v in st.query_lat]
    heavy_all = [v for lat in heavy_lat for v in lat]
    route_counts = {
        k: counters_after.get(k, 0) - counters_before.get(k, 0)
        for k in sorted(set(counters_before) | set(counters_after))
    }
    attempts = (len(tiny_all) + len(heavy_all)
                + sum(st.sheds_429 + st.sheds_503 + st.errors
                      for st in states))
    return {
        "scenario": "mixed_shapes",
        "clients": clients,
        "duration_s": round(wall_s, 3),
        "warmup_s": round(warmup_s, 3),
        "attempts": attempts,
        "qps": round(attempts / max(wall_s, 1e-9), 1),
        "tiny": _lat_summary(tiny_all),
        "heavy": _lat_summary(heavy_all),
        "aggregate_p99_ms": _lat_summary(tiny_all + heavy_all)["p99_ms"],
        "planner_routes": route_counts,
        "fingerprints": fingerprints,
        "errors": sum(st.errors for st in states),
        "error_samples": [s for st in states
                          for s in st.error_samples][:10],
        "stuck_clients": sum(1 for t in threads if t.is_alive()),
    }


def run_cardinality_churn(host: str, port: int, clients: int = 6,
                          duration_s: float = 10.0, batch_rows: int = 200,
                          measurement: str = "churn", pods_per_gen: int = 400,
                          churn_every_s: float = 1.0,
                          warmup_s: float = 10.0,
                          write_interval_s: float = 0.1,
                          timeout_s: float = 30.0) -> dict:
    """Cardinality-churn scenario (the label-engine soak): pod-style
    labels churn under live ingest — every write batch advances a pod
    "generation" (new `pod=g<g>-<i>` series, the old generation stops
    receiving rows), so the columnar label tier (index/labels.py) is
    invalidated and lazily rebuilt continuously while reader clients
    run regex + negative selectors over the growing series set.  The
    scenario reports query p99 split into first/second half of the run:
    with generation-keyed snapshots the tail must stay FLAT even as
    total cardinality grows (`p99_flat_ok`; rebuild cost is bounded by
    live series, not by how many generations ever existed)."""
    import random
    from urllib.parse import quote

    db = "churndb"
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    conn.request("POST", "/query?q=" + quote(f'CREATE DATABASE "{db}"'))
    conn.getresponse().read()
    # warmup: seed ~a churn window's worth of generation-0 rows and run
    # each selector twice, so first-execution kernel compiles (and the
    # scan-shape buckets the live run will hit) land before the clock
    # starts — the recorded latencies measure churn behavior, not cold
    # kernels
    now = time.time_ns()
    for b in range(24):
        seed = "".join(
            f"{measurement},job=api-{k % 20},"
            f"pod=g0-{b % 4}-{k % pods_per_gen},"
            f"region=r{k % 5} v={k}i {now - (b * batch_rows + k) * 1000}\n"
            for k in range(batch_rows)).encode()
        conn.request("POST", f"/write?db={db}", body=seed)
        conn.getresponse().read()

    states = [_ClientState(i) for i in range(clients)]
    q_events: list[list[tuple]] = [[] for _ in range(clients)]
    # eq-gated regex + negative selectors over a trailing 2s window:
    # the matcher runs against the FULL ever-growing series set (that
    # is what must stay flat), while the data scan stays bounded to the
    # live generation's rows so selector latency dominates the measure
    def make_queries():
        lo = time.time_ns() - 2_000_000_000
        return [
            f"SELECT count(v) FROM {measurement} "
            f"WHERE job = 'api-7' AND pod =~ /.*-1.0/ AND time >= {lo}",
            f"SELECT count(v) FROM {measurement} "
            f"WHERE job = 'api-13' AND pod !~ /g[02468].*/ "
            f"AND time >= {lo}",
            f"SELECT count(v) FROM {measurement} "
            f"WHERE region = 'r4' AND job =~ /api-1\\d/ AND time >= {lo}",
        ]
    for q in make_queries() * 2:  # unrecorded warmup passes per shape
        conn.request("GET", f"/query?db={db}&q={quote(q)}")
        conn.getresponse().read()
    conn.close()
    # workers run warmup + measured back to back; events stamped before
    # warmup_s are dropped from the latency record (the first seconds
    # carry one-off steady-state costs — offload-planner route
    # exploration pays its device compiles there, flush sizing settles)
    t_start = time.monotonic()
    stop_at = t_start + warmup_s + duration_s

    q_timeouts = [0] * clients

    def worker(st: _ClientState) -> None:
        rng = random.Random(1000 + st.idx)
        is_writer = st.idx % 2 == 0
        # readers truncate at 8s: a one-off server-side stall (e.g. the
        # offload planner's first device exploration paying a compile)
        # must not starve the sampler for the rest of the run — the
        # event is still visible in query_timeouts
        conn_timeout = timeout_s if is_writer else min(8.0, timeout_s)
        conn = http.client.HTTPConnection(host, port,
                                          timeout=conn_timeout)
        try:
            while time.monotonic() < stop_at:
                t0 = time.monotonic()
                try:
                    if is_writer:
                        # pod generation advances on a wall-clock cadence
                        # (a rolling deploy): each churn retires the old
                        # pods and mints pods_per_gen new series, so the
                        # label tier's snapshot is invalidated roughly
                        # once per churn_every_s, not once per batch
                        g = int((t0 - t_start) / churn_every_s)
                        base = time.time_ns() - st.idx
                        body = "".join(
                            f"{measurement},job=api-{k % 20},"
                            f"pod=g{g}-{st.idx}-{k % pods_per_gen},"
                            f"region=r{k % 5} "
                            f"v={st.seq + k}i {base - k * 1000}\n"
                            for k in range(batch_rows)
                        ).encode()
                        conn.request("POST", f"/write?db={db}", body=body)
                        resp = conn.getresponse()
                        resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 204:
                            st.seq += batch_rows
                            st.write_lat.append(dt)
                        elif resp.status in (429, 503):
                            st.sheds_429 += resp.status == 429
                            st.sheds_503 += resp.status == 503
                        else:
                            st.note_error(f"write status {resp.status}")
                        # paced ingest: churn is about label cardinality
                        # turning over, not about saturating the write
                        # path — leave the box headroom so query latency
                        # measures matching, not GIL contention
                        time.sleep(write_interval_s)
                    else:
                        q = rng.choice(make_queries())
                        conn.request(
                            "GET", f"/query?db={db}&q={quote(q)}")
                        resp = conn.getresponse()
                        data = resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 200:
                            doc = json.loads(data)
                            errs = [r["error"]
                                    for r in doc.get("results", [])
                                    if "error" in r]
                            if errs:
                                st.note_error(
                                    "query error: " + errs[0][:120])
                            else:
                                st.query_lat.append(dt)
                                q_events[st.idx].append(
                                    (t0 - t_start, dt))
                        elif resp.status in (429, 503):
                            st.sheds_429 += resp.status == 429
                            st.sheds_503 += resp.status == 503
                        else:
                            st.note_error(f"query status {resp.status}")
                except (OSError, http.client.HTTPException,
                        ValueError) as e:
                    if isinstance(e, TimeoutError) and not is_writer:
                        q_timeouts[st.idx] += 1
                    else:
                        st.note_error(
                            f"transport: {type(e).__name__}: {e}")
                    try:
                        conn.close()
                    except OSError:
                        pass
                    conn = http.client.HTTPConnection(
                        host, port, timeout=conn_timeout)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    threads = [threading.Thread(target=worker, args=(st,), daemon=True,
                                name=f"churn-{st.idx}") for st in states]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=warmup_s + duration_s + 4 * timeout_s)
    wall_s = time.monotonic() - t_start

    events = sorted((ts, dt) for lst in q_events for (ts, dt) in lst
                    if ts >= warmup_s)
    half = warmup_s + (wall_s - warmup_s) / 2.0
    first = [dt for (ts, dt) in events if ts < half]
    second = [dt for (ts, dt) in events if ts >= half]
    p99_first = _lat_summary(first)["p99_ms"]
    p99_second = _lat_summary(second)["p99_ms"]
    # flat: the second half's tail must not outgrow the first half's by
    # more than 2.5x + a 5ms jitter floor, despite the extra generations
    flat_ok = (not second or not first
               or p99_second <= max(p99_first * 2.5, p99_first + 5.0))
    return {
        "scenario": "cardinality_churn",
        "clients": clients,
        "duration_s": round(wall_s, 3),
        "warmup_s": warmup_s,
        "generations": int(wall_s / churn_every_s),
        "writes": _lat_summary(
            [v for st in states for v in st.write_lat]),
        "queries": _lat_summary([dt for (_, dt) in events]),
        "query_p99_first_half_ms": p99_first,
        "query_p99_second_half_ms": p99_second,
        "p99_flat_ok": bool(flat_ok),
        "query_timeouts": sum(q_timeouts),
        "sheds": sum(st.sheds_429 + st.sheds_503 for st in states),
        "errors": sum(st.errors for st in states),
        "error_samples": [s for st in states
                          for s in st.error_samples][:10],
        "stuck_clients": sum(1 for t in threads if t.is_alive()),
    }


def run_rule_fleet(host: str, port: int, clients: int = 6,
                   duration_s: float = 10.0, rules: int = 200,
                   series: int = 60, interval_s: float = 1.0,
                   warmup_s: float = 3.0,
                   write_interval_s: float = 0.25,
                   timeout_s: float = 30.0) -> dict:
    """Rule-fleet scenario (the continuous rule engine soak): a fleet of
    recording + threshold-alert rules (promql/rules.py) ticks over LIVE
    counter ingest while dashboard readers query the recorded series
    through /api/v1/query.  A ticker thread forces group evaluations via
    /debug/ctrl?mod=rules&op=tick and samples each tick's server-side
    duration (status last_tick_ms).  The scenario asserts the per-tick
    p99 stays FLAT first half vs second half of the run
    (`tick_flat_ok`): incremental tile maintenance makes a tick cost
    O(newly dirtied tiles), not O(window) — without it the tick would
    grow with accumulated data.  It also re-evaluates a sample of rule
    expressions on demand at the group's last watermark and checks the
    recorded series agree (`recorded_consistent`).  Run the server with
    OGT_RULES_VERIFY=1 to additionally assert every tick bit-identical
    to a from-scratch evaluation (verify counters land in /metrics)."""
    import random
    from urllib.parse import quote

    db = "rulefleetdb"
    mst = "rf_requests"
    windows_s = (30, 60, 120)
    n_writers = max(1, (clients + 1) // 2)

    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)

    def ctrl(op_params: str) -> dict:
        conn.request("POST", "/debug/ctrl?mod=rules&" + op_params)
        resp = conn.getresponse()
        body = resp.read()
        doc = json.loads(body) if body else {}
        if resp.status != 200:
            raise RuntimeError(
                f"rules ctrl failed ({resp.status}): "
                f"{doc.get('error', body[:120])}")
        return doc

    conn.request("POST", "/query?q=" + quote(f'CREATE DATABASE "{db}"'))
    conn.getresponse().read()

    # seed: a max-window's worth of monotonic counter history per series
    # (1 sample/s), so the first tick's rate() windows are fully covered
    # before the clock starts
    seed_s = max(windows_s) + 30
    now = time.time_ns()
    for lo in range(0, seed_s, 30):
        body = "".join(
            f"{mst},job=api,host=h{k} value={t * 3 + k} "
            f"{now - (seed_s - t) * 1_000_000_000}\n"
            for t in range(lo, min(lo + 30, seed_s))
            for k in range(series)).encode()
        conn.request("POST", f"/write?db={db}", body=body)
        resp = conn.getresponse()
        resp.read()
        if resp.status != 204:
            raise RuntimeError(f"seed write failed ({resp.status})")

    # declare the fleet: one group, alternating recording rules (the
    # dashboard-readable output) and threshold alerts over a mix of
    # rate() windows
    doc = ctrl(f"op=declare&db={db}&group=fleet"
               f"&interval_s={interval_s}")
    if not doc.get("enabled", False):
        raise RuntimeError("rules engine disabled on server (OGT_RULES=0)")
    recordings: list[tuple[str, str]] = []
    for i in range(rules):
        w = windows_s[i % len(windows_s)]
        expr = f"sum by (job) (rate({mst}[{w}s]))"
        if i % 2 == 0:
            name = f"rf_rate_w{w}_{i}"
            ctrl(f"op=declare&db={db}&group=fleet&record={name}"
                 f"&expr={quote(expr)}")
            recordings.append((name, expr))
        else:
            ctrl(f"op=declare&db={db}&group=fleet&alert=RfHot{i}"
                 f"&expr={quote(expr + ' > ' + str(i * 0.05))}")
    # warm: first tick pays recording-measurement creation and the
    # fold/merge paths; two unrecorded reads per queried shape land any
    # first-execution compiles before the clock starts
    ctrl("op=tick")
    for name, _ in recordings[:4] * 2:
        conn.request("GET", f"/api/v1/query?db={db}&query={quote(name)}")
        conn.getresponse().read()
    conn.close()

    states = [_ClientState(i) for i in range(clients)]
    for st in states:
        st.seq = seed_s * 3 + 1000  # counters continue past the seed
    q_events: list[list[tuple]] = [[] for _ in range(clients)]
    tick_events: list[tuple] = []  # (t_rel, server-side tick seconds)
    t_start = time.monotonic()
    stop_at = t_start + warmup_s + duration_s

    def ticker() -> None:
        tconn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        try:
            while time.monotonic() < stop_at:
                t0 = time.monotonic()
                try:
                    tconn.request("POST", "/debug/ctrl?mod=rules&op=tick")
                    resp = tconn.getresponse()
                    doc = json.loads(resp.read())
                    g = doc.get("groups", {}).get(f"{db}.fleet")
                    if doc.get("ticked", 0) >= 1 and g is not None:
                        tick_events.append(
                            (t0 - t_start, g["last_tick_ms"] / 1e3))
                except (OSError, http.client.HTTPException, ValueError):
                    try:
                        tconn.close()
                    except OSError:
                        pass
                    tconn = http.client.HTTPConnection(
                        host, port, timeout=timeout_s)
                time.sleep(interval_s)
        finally:
            try:
                tconn.close()
            except OSError:
                pass

    def worker(st: _ClientState) -> None:
        rng = random.Random(3000 + st.idx)
        is_writer = st.idx % 2 == 0
        wrank = st.idx // 2
        hosts = [k for k in range(series) if k % n_writers == wrank]
        conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        try:
            while time.monotonic() < stop_at:
                t0 = time.monotonic()
                try:
                    if is_writer:
                        base = time.time_ns()
                        body = "".join(
                            f"{mst},job=api,host=h{k} "
                            f"value={st.seq + k} {base - k}\n"
                            for k in hosts).encode()
                        conn.request("POST", f"/write?db={db}", body=body)
                        resp = conn.getresponse()
                        resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 204:
                            st.seq += 7  # monotonic per-host counters
                            st.write_lat.append(dt)
                        elif resp.status in (429, 503):
                            st.sheds_429 += resp.status == 429
                            st.sheds_503 += resp.status == 503
                        else:
                            st.note_error(f"write status {resp.status}")
                        time.sleep(write_interval_s)
                    else:
                        # dashboard reader: recorded series are normal
                        # queryable series — cheap instant lookups, plus
                        # the occasional alerts poll
                        if rng.random() < 0.125:
                            path = f"/api/v1/alerts?db={db}"
                        else:
                            name, _ = rng.choice(recordings)
                            path = (f"/api/v1/query?db={db}"
                                    f"&query={quote(name)}")
                        conn.request("GET", path)
                        resp = conn.getresponse()
                        data = resp.read()
                        dt = time.monotonic() - t0
                        if resp.status == 200:
                            doc = json.loads(data)
                            if doc.get("status", "success") != "success":
                                st.note_error(
                                    "query error: "
                                    + str(doc.get("error"))[:120])
                            else:
                                st.query_lat.append(dt)
                                q_events[st.idx].append((t0 - t_start, dt))
                        elif resp.status in (429, 503):
                            st.sheds_429 += resp.status == 429
                            st.sheds_503 += resp.status == 503
                        else:
                            st.note_error(f"query status {resp.status}")
                except (OSError, http.client.HTTPException,
                        ValueError) as e:
                    st.note_error(f"transport: {type(e).__name__}: {e}")
                    try:
                        conn.close()
                    except OSError:
                        pass
                    conn = http.client.HTTPConnection(
                        host, port, timeout=timeout_s)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    threads = [threading.Thread(target=worker, args=(st,), daemon=True,
                                name=f"rulefleet-{st.idx}")
               for st in states]
    threads.append(threading.Thread(target=ticker, daemon=True,
                                    name="rulefleet-ticker"))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=warmup_s + duration_s + 4 * timeout_s)
    wall_s = time.monotonic() - t_start

    # quiescent closing tick, then recorded-vs-on-demand consistency at
    # the group's watermark: the recorded sample at te must agree with
    # re-evaluating the rule expression over raw samples at te
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    time.sleep(interval_s + 0.05)
    doc = ctrl("op=tick")
    g = doc.get("groups", {}).get(f"{db}.fleet", {})
    te_ns = g.get("last_eval_ns")
    checked = 0
    max_rel_err = 0.0
    consistency_errors: list[str] = []

    def vector_of(query: str) -> dict:
        conn.request("GET", f"/api/v1/query?db={db}&query={quote(query)}"
                            f"&time={te_ns / 1e9}")
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        if resp.status != 200 or doc.get("status") != "success":
            raise RuntimeError(f"consistency query failed: {doc}")
        return {r["metric"].get("job", ""): float(r["value"][1])
                for r in doc["data"]["result"]}

    if te_ns is not None:
        for name, expr in recordings[:3]:
            try:
                rec = vector_of(name)
                ond = vector_of(expr)
            except (RuntimeError, OSError, ValueError,
                    http.client.HTTPException) as e:
                consistency_errors.append(f"{name}: {e}")
                continue
            for job, want in ond.items():
                got = rec.get(job)
                if got is None:
                    consistency_errors.append(f"{name}: missing {job!r}")
                    continue
                rel = abs(got - want) / max(abs(want), 1e-12)
                max_rel_err = max(max_rel_err, rel)
                checked += 1
    try:
        conn.close()
    except OSError:
        pass
    consistent = (checked > 0 and not consistency_errors
                  and max_rel_err <= 1e-3)

    ticks = sorted((ts, dt) for (ts, dt) in tick_events if ts >= warmup_s)
    half = warmup_s + (wall_s - warmup_s) / 2.0
    first = [dt for (ts, dt) in ticks if ts < half]
    second = [dt for (ts, dt) in ticks if ts >= half]
    p99_first = _lat_summary(first)["p99_ms"]
    p99_second = _lat_summary(second)["p99_ms"]
    # flat: per-tick cost must not grow with accumulated data — the
    # second half's p99 stays within 2.5x + a 5ms jitter floor of the
    # first half's (same tolerance as the churn scenario)
    flat_ok = (not second or not first
               or p99_second <= max(p99_first * 2.5, p99_first + 5.0))
    q_all = sorted((ts, dt) for lst in q_events for (ts, dt) in lst
                   if ts >= warmup_s)
    return {
        "scenario": "rule_fleet",
        "clients": clients,
        "duration_s": round(wall_s, 3),
        "warmup_s": warmup_s,
        "rules": rules,
        "series": series,
        "ticks_measured": len(ticks),
        "tick_ms": _lat_summary([dt for (_, dt) in ticks]),
        "tick_p99_first_half_ms": p99_first,
        "tick_p99_second_half_ms": p99_second,
        "tick_flat_ok": bool(flat_ok),
        "recorded_consistent": bool(consistent),
        "recorded_checked": checked,
        "recorded_max_rel_err": max_rel_err,
        "consistency_errors": consistency_errors[:10],
        "alerts_firing": g.get("alerts_firing", 0),
        "writes": _lat_summary(
            [v for st in states for v in st.write_lat]),
        "queries": _lat_summary([dt for (_, dt) in q_all]),
        "sheds": sum(st.sheds_429 + st.sheds_503 for st in states),
        "errors": sum(st.errors for st in states),
        "error_samples": [s for st in states
                          for s in st.error_samples][:10],
        "stuck_clients": sum(1 for t in threads if t.is_alive()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8086)
    ap.add_argument("--db", default="load")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--write-frac", type=float, default=0.5)
    ap.add_argument("--target-qps", type=float, default=None)
    ap.add_argument("--batch-rows", type=int, default=50)
    ap.add_argument("--measurement", default="loadgen")
    ap.add_argument("--targets", default=None,
                    help="comma-separated host:port coordinators "
                         "(multi-node; clients fail over between them)")
    ap.add_argument("--consistency", default=None,
                    help="write consistency level, or a comma-separated "
                         "list cycled per client (recorded per batch)")
    ap.add_argument("--ack-log", default=None,
                    help="append each acked batch to this fsynced journal")
    ap.add_argument("--scenario", default="mixed",
                    choices=("mixed", "dashboard", "mixed_shapes",
                             "cardinality_churn", "rule_fleet"),
                    help="'dashboard' = zipf-tenant dashboard fleet "
                         "(repeated identical GROUP BY time() reads + "
                         "live ingest, per-tenant p50/p99 + sheds); "
                         "'mixed_shapes' = zipf tiny dashboard queries "
                         "+ heavy cold scans, per-class p50/p99 + "
                         "offload-planner route counts; "
                         "'cardinality_churn' = pod-style labels churn "
                         "under live ingest while readers run regex + "
                         "negative selectors; asserts flat query p99 "
                         "(label-tier rebuilds stay bounded); "
                         "'rule_fleet' = recording+alert rule fleet "
                         "ticking over live counter ingest while "
                         "readers query the recorded series; asserts "
                         "flat per-tick p99 and recorded-vs-on-demand "
                         "consistency")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--rules", type=int, default=200,
                    help="rule_fleet scenario: fleet size (half "
                         "recording rules, half threshold alerts)")
    ap.add_argument("--zipf", type=float, default=1.2,
                    help="zipf exponent for tenant popularity")
    ap.add_argument("--metrics-poll", type=float, default=None,
                    metavar="SECONDS",
                    help="scrape GET /metrics from the first target on "
                         "this interval and report acked-rows vs "
                         "ogt_write_rows_total consistency")
    args = ap.parse_args()
    if args.scenario == "rule_fleet":
        out = run_rule_fleet(
            args.host, args.port, clients=args.clients,
            duration_s=args.duration, rules=args.rules)
        print(json.dumps(out, indent=1))
        return
    if args.scenario == "cardinality_churn":
        out = run_cardinality_churn(
            args.host, args.port, clients=args.clients,
            duration_s=args.duration, batch_rows=args.batch_rows,
            measurement=args.measurement)
        print(json.dumps(out, indent=1))
        return
    if args.scenario == "mixed_shapes":
        out = run_mixed_shapes(
            args.host, args.port, clients=args.clients,
            duration_s=args.duration, zipf_s=args.zipf,
            measurement=args.measurement)
        print(json.dumps(out, indent=1))
        return
    if args.scenario == "dashboard":
        out = run_dashboard_fleet(
            args.host, args.port, clients=args.clients,
            tenants=args.tenants, zipf_s=args.zipf,
            duration_s=args.duration, write_frac=args.write_frac,
            batch_rows=args.batch_rows, measurement=args.measurement)
        print(json.dumps(out, indent=1))
        return
    levels = args.consistency.split(",") if args.consistency else None
    out = run_load(args.host, args.port, args.db, clients=args.clients,
                   duration_s=args.duration, write_frac=args.write_frac,
                   target_qps=args.target_qps, batch_rows=args.batch_rows,
                   measurement=args.measurement,
                   targets=args.targets.split(",") if args.targets else None,
                   consistency=(levels[0] if levels and len(levels) == 1
                                else levels),
                   ack_log=args.ack_log,
                   metrics_poll_s=args.metrics_poll)
    out.pop("acked_batches", None)  # CLI summary stays readable
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

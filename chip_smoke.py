#!/usr/bin/env python3
"""The quickest proof that the served path still starts, compiles and
answers correctly on the chip.

    python3 chip_smoke.py                 # one TPU chip
    python3 chip_smoke.py --devices 4     # one four-chip host, [device] mesh
    python3 chip_smoke.py --cpu-dry-run   # tiny sizes, control flow only

The server inherits this environment, so Python warnings become errors
in it with no flag of this script:

    PYTHONWARNINGS='error,ignore:Transparent hugepages:UserWarning' \
        python3 chip_smoke.py

This process stays off JAX (numpy and the standard library only): a chip
belongs to one process, and that process is the server.  It

  1. builds the five native libraries from native/*.cpp (never from a
     .so that happens to lie on disk) and opens each;
  2. starts ONE server through its CLI entry point
     (`python -m opengemini_tpu.server.app -config <generated toml>`),
     reads platform / device_kind / count from it and exits non-zero
     unless the platform is `tpu`;
  3. loads, over `/write` in line protocol, data made from --seed in the
     shapes of the repo's configs #1-#3 (BASELINE.json): TSBS DevOps
     `cpu-only` at scale 4,000 hosts (10 tags + 10 float fields per row,
     10 s interval) and 10,000 Prometheus counter series at a 15 s scrape;
  4. runs each query cold and again warm and compares every answer with
     a one-pass numpy oracle computed here from the same seed (counts
     exact; float aggregates within the tolerances in TOL below — the
     device computes in float32, the oracle in float64).  The server
     runs as a user runs it, incremental result cache on, and that cache
     answers a repeated GROUP BY time() statement with no device work;
     so the warm pass asks each statement of the NEXT field(s) of the
     same rows: the same programs, other data;
  5. asserts from the server's own counters that the device did the
     work: in every pass every query fetched its result from the device,
     every program family the queries were built to drive is in
     `/debug/device` `jit_cache`, the grid layout engaged with no
     fallback where it should and fell back where a TPU makes it, no
     planner decision kept a stage on the host, and the warm pass built
     no new XLA program;
  6. reads back exactly as many rows as `/write` acknowledged, stops the
     server (SIGTERM), starts a second server process on the same data
     directory, reads the same count and the same answer again, and
     checks that it loaded programs from the persistent compile cache
     the first one wrote.

Any failed phase exits non-zero and prints no result line.  The last
line of a passing run is one JSON object with the device as JAX
reported it to the server.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
NATIVE_LIBS = ("codecs", "textindex", "seriesindex", "lineproto", "render")

# -- sizes --------------------------------------------------------------------
# Widths (hosts, tags, fields, series) are the sources' and are never cut;
# durations are scale and every cut is printed.

TSBS_START_S = 1_451_606_400      # 2016-01-01T00:00:00Z, TSBS's default start
TSBS_INTERVAL_S = 10
PROM_SCRAPE_S = 15
PROM_RANGE_S = 300                # rate(metric[5m])
PROM_STEP_S = 60

FULL = {"hosts": 4000, "tsbs_hours": 2, "series": 10_000, "prom_minutes": 60}
SPEC = dict(FULL)                 # what the run uses; a cut edits this
DRY = {"hosts": 24, "tsbs_hours": 2, "series": 48, "prom_minutes": 20}

TSBS_FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
               "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
               "usage_guest", "usage_guest_nice")
PROM_METRIC = "http_requests_total"

# Relative tolerances, device float32 against the float64 oracle.  Values
# cross /write as decimal text, are stored as float64 and narrowed to
# float32 on their way to the device, so a selector answer is one float32
# rounding (2^-24 ~ 6e-8) away from the oracle; sums accumulate that per
# addend.  What each query needed on the chip is printed per run and
# recorded in CHANGES.md (input for ROADMAP S2, the numeric contract).
TOL = {
    "selector": 2e-7,     # first/last/min/max/percentile: one rounding
    "mean": 2e-5,         # float32 partial sums, float64 combine on the host
    # float32 differences of counters the host made monotone and relative
    # to their first sample in float64: every window, the ones at and
    # after a counter reset included
    "rate": 2e-4,
}


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke {time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# -- native libraries ---------------------------------------------------------


def build_native() -> None:
    """make -B: from the sources git tracks, whatever .so is on disk."""
    native_dir = os.path.join(REPO, "native")
    t0 = time.monotonic()
    r = subprocess.run(["make", "-B", "-j4", "-C", native_dir, "all"],
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise SmokeFailure("native build failed:\n" + r.stdout + r.stderr)
    for name in NATIVE_LIBS:
        path = os.path.join(native_dir, f"libogt{name}.so")
        try:
            ctypes.CDLL(path)
        except OSError as e:
            raise SmokeFailure(f"native library {path} does not load: {e}")
    log(f"native: built and opened {', '.join(NATIVE_LIBS)} from source "
        f"in {time.monotonic() - t0:.1f}s")


# -- the server child ---------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One `python -m opengemini_tpu.server.app` process: the only
    process of the smoke that imports jax."""

    def __init__(self, workdir: str, devices: int, dry_run: bool, tag: str):
        self.port = _free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, f"server-{tag}.log")
        cfg = os.path.join(workdir, f"server-{tag}.toml")
        with open(cfg, "w", encoding="utf-8") as f:
            f.write(f'[data]\ndir = "{os.path.join(workdir, "data")}"\n'
                    f'[http]\nbind-address = "127.0.0.1:{self.port}"\n')
            if devices > 1:
                f.write(f'[device]\nmesh-axes = ["shard"]\n'
                        f'mesh-devices = {devices}\n')
        env = dict(os.environ)
        if dry_run:
            env["JAX_PLATFORMS"] = "cpu"
            if devices > 1:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                    f"device_count={devices}").strip()
        cmd = [sys.executable, "-m", "opengemini_tpu.server.app",
               "-config", cfg]
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)
        self.started = time.monotonic()

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            lines = [ln[:400] for ln in f.read().splitlines()]
        return "\n".join(lines[-n:])

    def log_line(self, prefix: str) -> str:
        with open(self.log_path, errors="replace") as f:
            for ln in f:
                if ln.startswith(prefix):
                    return ln.strip()
        raise SmokeFailure(f"server log has no {prefix!r} line:\n"
                           + self.log_tail())

    def wait_ready(self, timeout_s: float = 300.0) -> float:
        while time.monotonic() - self.started < timeout_s:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"server exited with code {rc} before it was ready:\n"
                    + self.log_tail())
            try:
                with urllib.request.urlopen(self.base + "/ping", timeout=2):
                    return time.monotonic() - self.started
            except (urllib.error.URLError, OSError):
                time.sleep(0.2)
        raise SmokeFailure(f"server not ready after {timeout_s:.0f}s:\n"
                           + self.log_tail())

    def stop(self) -> float:
        """SIGTERM and wait for a clean exit; returns the seconds it took."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=300)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not exit within 300s of SIGTERM:\n"
                               + self.log_tail())
        finally:
            self._log.close()
        check(rc == 0, f"server exited with code {rc} on SIGTERM:\n"
              + self.log_tail())
        return time.monotonic() - t0

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self._log.closed:
            self._log.close()

    # -- HTTP -----------------------------------------------------------

    def _open(self, req, timeout: float = 900.0) -> tuple[int, bytes]:
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{req.full_url[:200]} -> HTTP {e.code}: "
                f"{e.read()[:2000]!r}\n" + self.log_tail())
        except (urllib.error.URLError, OSError) as e:
            raise SmokeFailure(f"{req.full_url[:200]} -> {e} (server exit "
                               f"code: {self.proc.poll()})\n" + self.log_tail())

    def get_json(self, path: str, **params) -> dict:
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        return json.loads(self._open(urllib.request.Request(url))[1])

    def post(self, path: str, **params) -> dict:
        url = self.base + path + "?" + urllib.parse.urlencode(params)
        return json.loads(self._open(
            urllib.request.Request(url, data=b"", method="POST"))[1])

    def query(self, q: str, db: str) -> list[dict]:
        """One InfluxQL statement; its series list (epoch in ns)."""
        doc = self.post("/query", q=q, db=db, epoch="ns")
        res = doc["results"][0]
        check("error" not in res, f"query failed: {q!r}: {res.get('error')}")
        return res.get("series", [])

    def write(self, db: str, body: bytes) -> bool:
        """POST /write; True when the server acknowledged with 204."""
        return self._open(urllib.request.Request(
            self.base + "/write?" + urllib.parse.urlencode({"db": db}),
            data=body, method="POST"))[0] == 204

    def device_counters(self) -> dict:
        return self.get_json("/debug/device")["counters"]


# -- data, made from the seed -------------------------------------------------


def _digits(values: np.ndarray, width: int) -> np.ndarray:
    """(n,) non-negative ints -> (n, width) ASCII digits, zero-padded."""
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((values[:, None] // powers) % 10 + 48).astype(np.uint8)


class TickWriter:
    """All series' lines of one timestamp as a byte template whose
    fixed-width numeric slots are overwritten per tick — line protocol at
    the speed of a numpy fancy assignment, not a Python loop per row."""

    def __init__(self, prefixes: list[bytes], fields: tuple, width: int):
        slot = b"0" * width
        tail = b",".join(f.encode() + b"=" + slot for f in fields)
        lines = [p + b" " + tail + b" " + b"0" * 19 + b"\n" for p in prefixes]
        self.buf = np.frombuffer(b"".join(lines), np.uint8).copy()
        starts = np.cumsum([0] + [len(ln) for ln in lines[:-1]])
        plen = np.array([len(p) for p in prefixes]) + 1
        # offset of each field's slot inside the shared tail
        in_tail = np.array([sum(len(g) + 1 + width + 1 for g in fields[:i])
                            + len(f) + 1 for i, f in enumerate(fields)])
        base = (starts + plen)[:, None] + in_tail[None, :]
        self.val_pos = base[:, :, None] + np.arange(width)[None, None, :]
        ts0 = starts + plen + len(tail) + 1
        self.ts_pos = ts0[:, None] + np.arange(19)[None, :]

    def tick(self, slots: np.ndarray, ts_ns: int) -> bytes:
        """slots: (series, fields, width) ASCII bytes for this tick."""
        self.buf[self.val_pos] = slots
        self.buf[self.ts_pos] = _digits(np.array([ts_ns]), 19)
        return self.buf.tobytes()


def tsbs_hosts(rng: np.random.Generator, n: int) -> list[bytes]:
    """Series keys of TSBS cpu-only: measurement `cpu` and its 10 tags."""
    regions = ["us-east-1", "us-west-1", "us-west-2", "eu-west-1",
               "eu-central-1", "ap-southeast-1", "ap-southeast-2",
               "ap-northeast-1", "sa-east-1"]
    oses = ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"]
    envs = ["production", "staging", "test"]
    teams = ["SF", "NYC", "LON", "CHI"]
    out = []
    for i in range(n):
        region = regions[rng.integers(len(regions))]
        out.append((
            f"cpu,hostname=host_{i},region={region},"
            f"datacenter={region}{'abc'[rng.integers(3)]},"
            f"rack={rng.integers(100)},os={oses[rng.integers(3)]},"
            f"arch={'x64' if rng.integers(2) else 'x86'},"
            f"team={teams[rng.integers(4)]},service={rng.integers(20)},"
            f"service_version={rng.integers(2)},"
            f"service_environment={envs[rng.integers(3)]}").encode())
    return out


def tsbs_values(rng: np.random.Generator, ticks: int, hosts: int) -> np.ndarray:
    """(ticks, hosts, 10) int32 hundredths in [0, 10000]: TSBS's clamped
    random walk (uniform start, unit-normal steps) at two decimals, so the
    decimal text, the stored float64 and the oracle's k/100 are one number."""
    nf = len(TSBS_FIELDS)
    out = np.empty((ticks, hosts, nf), np.int32)
    cur = rng.integers(0, 10001, size=(hosts, nf))
    for t in range(ticks):
        out[t] = cur
        cur = np.clip(cur + np.rint(
            rng.standard_normal((hosts, nf)) * 100).astype(np.int64),
            0, 10000)
    return out


def _hundredths_table() -> np.ndarray:
    """k -> the 6 ASCII bytes of k/100, e.g. 9.9900, 99.990, 100.00: every
    value the same width without leading zeros or padding."""
    rows = []
    for k in range(10001):
        v = k / 100
        rows.append(b"%.4f" % v if k < 1000 else
                    b"%.3f" % v if k < 10000 else b"%.2f" % v)
    return np.frombuffer(b"".join(rows), np.uint8).reshape(10001, 6)


def prom_series(n: int) -> list[tuple[str, str, str]]:
    """(instance, handler, code) label sets: n/20 targets x 4 x 5."""
    handlers = ["/api/v1/query", "/api/v1/write", "/metrics", "/healthz"]
    codes = ["200", "204", "400", "404", "500"]
    out = []
    for i in range(n):
        t, rest = divmod(i, 20)
        out.append((f"10.0.{t // 250}.{t % 250}:9100",
                    handlers[rest // 5], codes[rest % 5]))
    return out


def prom_values(rng: np.random.Generator, ticks: int, series: int) -> np.ndarray:
    """(ticks, series) int64 counters: a start up to 1e9, increments up to
    99 per scrape, and a process restart (reset to a small value) in one
    series of fifty."""
    start = rng.integers(0, 10**9, size=series)
    inc = rng.integers(0, 100, size=(ticks, series))
    inc[0] = 0
    vals = start[None, :] + np.cumsum(inc, axis=0)
    for s in range(0, series, 50):
        at = int(rng.integers(ticks // 4, 3 * ticks // 4))
        vals[at:, s] = np.cumsum(inc[at:, s])
    return vals


# -- the oracle ---------------------------------------------------------------


def oracle_rate(vals: np.ndarray, t_s: np.ndarray, ends_s: np.ndarray,
                range_s: float) -> np.ndarray:
    """Prometheus extrapolatedRate for counters, window (end-range, end],
    per series over all steps: (series, steps) float64, NaN where a window
    holds fewer than two samples."""
    v = vals.astype(np.float64)
    out = np.full((v.shape[1], len(ends_s)), np.nan)
    for k, end in enumerate(ends_s):
        inside = np.flatnonzero((t_s > end - range_s) & (t_s <= end))
        if len(inside) < 2:
            continue
        w = v[inside]                          # (n, series)
        t = t_s[inside].astype(np.float64)
        prev, cur = w[:-1], w[1:]
        delta = w[-1] - w[0] + np.where(cur < prev, prev, 0.0).sum(axis=0)
        sampled = t[-1] - t[0]
        avg = sampled / (len(inside) - 1)
        to_start = t[0] - (end - range_s)
        to_end = end - t[-1]
        to_start = np.full_like(delta, avg / 2 if to_start > avg * 1.1
                                else to_start)
        to_end = avg / 2 if to_end > avg * 1.1 else to_end
        with np.errstate(divide="ignore", invalid="ignore"):
            to_zero = np.where((delta > 0) & (w[0] >= 0),
                               sampled * (w[0] / delta), np.inf)
        to_start = np.minimum(to_start, to_zero)
        out[:, k] = delta * ((sampled + to_start + to_end) / sampled) / range_s
    return out


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| / max(|want|, 1): relative to the value, and
    absolute below 1 so a mean near zero cannot blow the ratio up."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite value in an answer")
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1.0)).max())


# -- the run ------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.spec = DRY if args.cpu_dry_run else SPEC
        self.rng = np.random.default_rng(args.seed)
        self.errors: dict[str, float] = {}   # query -> largest rel. error seen
        self.acked = {"tsbs": 0, "prom": 0}

    # -- phases ---------------------------------------------------------

    def identify(self, srv: Server) -> dict:
        """Platform, device kind and count, as JAX reported them to the
        server; fails unless this is the platform the run was asked for."""
        diag = {k: v for k, v in srv.query("SHOW DIAGNOSTICS", "")[0]["values"]}
        devices = srv.get_json("/debug/device")["devices"]
        device = {"platform": devices[0]["platform"],
                  "kind": devices[0]["device_kind"], "count": len(devices)}
        log(f"device: platform={device['platform']} "
            f"device_kind={device['kind']!r} count={device['count']} "
            f"jax={diag['jax']} jaxlib={diag['jaxlib']} "
            f"libtpu={diag['libtpu']} x64={diag['x64']}")
        log(srv.log_line("device backend:"))
        libs = srv.log_line("native libraries:")
        log(libs)
        check(all(f"{n}=loaded" in libs for n in NATIVE_LIBS),
              f"server did not load every native library: {libs}")
        want = "cpu" if self.args.cpu_dry_run else "tpu"
        check(device["platform"] == want,
              f"platform is {device['platform']!r}, not {want!r}: the smoke "
              "runs on a TPU (a CPU control-flow run needs --cpu-dry-run)")
        check(device["count"] >= self.args.devices,
              f"{device['count']} device(s), --devices {self.args.devices}")
        check(diag["x64"] == "false", "the served path runs with x64 off")
        return device

    def load_tsbs(self, srv: Server) -> None:
        hosts, hours = self.spec["hosts"], self.spec["tsbs_hours"]
        ticks = hours * 3600 // TSBS_INTERVAL_S
        self.tsbs = tsbs_values(self.rng, ticks, hosts)
        self.values = self.tsbs / 100.0        # (ticks, hosts, fields) f64
        writer = TickWriter(tsbs_hosts(self.rng, hosts), TSBS_FIELDS, 6)
        table = _hundredths_table()
        srv.query("CREATE DATABASE tsbs", "")
        t0 = time.monotonic()
        nbytes = 0
        for t in range(ticks):
            body = writer.tick(table[self.tsbs[t]],
                               (TSBS_START_S + t * TSBS_INTERVAL_S) * 10**9)
            if srv.write("tsbs", body):
                self.acked["tsbs"] += hosts
            nbytes += len(body)
        wall = time.monotonic() - t0
        log(f"tsbs cpu-only: {hosts} hosts x {ticks} ticks = "
            f"{self.acked['tsbs']} rows acked ({self.acked['tsbs'] * 10} "
            f"field values, {nbytes >> 20} MiB of line protocol) in "
            f"{wall:.1f}s over /write")
        check(self.acked["tsbs"] == hosts * ticks, "a /write was not acked")

    def load_prom(self, srv: Server) -> None:
        n, minutes = self.spec["series"], self.spec["prom_minutes"]
        ticks = minutes * 60 // PROM_SCRAPE_S
        self.prom = prom_values(self.rng, ticks, n)
        self.prom_labels = prom_series(n)
        prefixes = [
            f'{PROM_METRIC},instance={i},handler={h},code={c} value='.encode()
            for i, h, c in self.prom_labels]
        srv.query("CREATE DATABASE prom", "")
        t0 = time.monotonic()
        for t in range(ticks):
            ts = b" %d\n" % ((TSBS_START_S + t * PROM_SCRAPE_S) * 10**9)
            body = b"".join([p + (b"%d" % v) + ts for p, v in
                             zip(prefixes, self.prom[t].tolist())])
            if srv.write("prom", body):
                self.acked["prom"] += n
        log(f"prom counters: {n} series x {ticks} scrapes = "
            f"{self.acked['prom']} samples acked in "
            f"{time.monotonic() - t0:.1f}s over /write")
        check(self.acked["prom"] == n * ticks, "a /write was not acked")

    def read_back(self, srv: Server) -> None:
        """The guarantee: every acknowledged row is read back."""
        for db, mst, field in (("tsbs", "cpu", "usage_user"),
                               ("prom", PROM_METRIC, "value")):
            got = srv.query(f"SELECT count({field}) FROM {mst}", db)
            n = got[0]["values"][0][1] if got else 0
            check(n == self.acked[db],
                  f"{db}: /write acknowledged {self.acked[db]} rows, "
                  f"count() reads back {n}")
        log(f"read back: tsbs {self.acked['tsbs']} rows, prom "
            f"{self.acked['prom']} samples — equal to what /write acked")

    # -- queries: each asks about field number f (or the f-th five) and
    # returns the largest error it saw as a share of its tolerance -------

    def _span(self) -> str:
        end = TSBS_START_S + self.spec["tsbs_hours"] * 3600
        return f"time >= {TSBS_START_S}s AND time < {end}s"

    def q_groupby_time_1m(self, srv: Server, f: int) -> float:
        """Config #1: mean, max, count GROUP BY time(1m) over every host."""
        name = TSBS_FIELDS[f]
        got = srv.query(
            f"SELECT mean({name}), max({name}), count({name}) "
            f"FROM cpu WHERE {self._span()} GROUP BY time(1m)", "tsbs")
        rows = np.array(got[0]["values"], np.float64)
        v = self.values[:, :, f]
        per = 60 // TSBS_INTERVAL_S
        w = v.reshape(-1, per * v.shape[1])
        check(len(rows) == len(w), f"{len(rows)} windows, want {len(w)}")
        check(np.array_equal(
            rows[:, 0], (TSBS_START_S + np.arange(len(w)) * 60) * 1e9),
            "window start times differ")
        check(np.array_equal(rows[:, 3], np.full(len(w), w.shape[1])),
              "count() differs from the oracle (counts are exact)")
        return max(rel_err(rows[:, 1], w.mean(axis=1)) / TOL["mean"],
                   rel_err(rows[:, 2], w.max(axis=1)) / TOL["selector"])

    def q_double_groupby_5(self, srv: Server, f: int, every_s: int) -> float:
        """Config #2, TSBS double-groupby-5: mean of 5 fields GROUP BY
        time(every), hostname."""
        fields = TSBS_FIELDS[5 * f:5 * f + 5]
        got = srv.query(
            "SELECT " + ", ".join(f"mean({name})" for name in fields)
            + f" FROM cpu WHERE {self._span()} "
            f"GROUP BY time({every_s}s), hostname", "tsbs")
        v = self.values[:, :, 5 * f:5 * f + 5]
        windows = self.spec["tsbs_hours"] * 3600 // every_s
        want = v.reshape(windows, -1, v.shape[1], 5).mean(axis=1)
        starts = (TSBS_START_S + np.arange(windows) * every_s) * 1e9
        check(len(got) == v.shape[1], f"{len(got)} series, want {v.shape[1]}")
        rows = np.empty((windows, v.shape[1], 5))
        for s in got:
            host = int(s["tags"]["hostname"].split("_")[1])
            vals = np.array(s["values"], np.float64)
            check(vals.shape == (windows, 6), f"host {host}: {vals.shape}")
            check(np.array_equal(vals[:, 0], starts),
                  f"host {host}: window start times differ")
            rows[:, host, :] = vals[:, 1:]
        return rel_err(rows, want) / TOL["mean"]

    def q_selectors_time(self, srv: Server, f: int) -> float:
        """first/last/min/max GROUP BY time(1m): the grid selector kernel.
        Every host reports at the same instant, and the reference breaks
        an exact-time tie towards the larger value."""
        name = TSBS_FIELDS[f]
        got = srv.query(
            f"SELECT first({name}), last({name}), min({name}), "
            f"max({name}) FROM cpu WHERE {self._span()} "
            "GROUP BY time(1m)", "tsbs")
        rows = np.array(got[0]["values"], np.float64)
        v = self.values[:, :, f]
        per = 60 // TSBS_INTERVAL_S
        w = v.reshape(-1, per, v.shape[1])
        want = np.stack([w[:, 0].max(axis=1), w[:, -1].max(axis=1),
                         w.min(axis=(1, 2)), w.max(axis=(1, 2))], axis=1)
        return rel_err(rows[:, 1:], want) / TOL["selector"]

    def q_selectors_host(self, srv: Server, f: int) -> float:
        """The same four selectors with no GROUP BY time, per host: the
        bucketed layout, whose selector kernel is the Pallas one on a TPU."""
        name = TSBS_FIELDS[f]
        got = srv.query(
            f"SELECT first({name}), last({name}), min({name}), "
            f"max({name}) FROM cpu WHERE {self._span()} "
            "GROUP BY hostname", "tsbs")
        v = self.values[:, :, f]
        want = np.stack([v[0], v[-1], v.min(axis=0), v.max(axis=0)], axis=1)
        check(len(got) == v.shape[1], f"{len(got)} series, want {v.shape[1]}")
        rows = np.empty_like(want)
        for s in got:
            rows[int(s["tags"]["hostname"].split("_")[1])] = s["values"][0][1:]
        return rel_err(rows, want) / TOL["selector"]

    def q_percentile(self, srv: Server, f: int) -> float:
        """percentile(field, 95) GROUP BY time(10m): the sort-based
        AggBatch template.  InfluxQL's is nearest-rank: an actual sample."""
        got = srv.query(
            f"SELECT percentile({TSBS_FIELDS[f]}, 95) FROM cpu "
            f"WHERE {self._span()} GROUP BY time(10m)", "tsbs")
        rows = np.array(got[0]["values"], np.float64)
        v = self.values[:, :, f]
        per = 600 // TSBS_INTERVAL_S
        w = np.sort(v.reshape(-1, per * v.shape[1]), axis=1)
        rank = int(np.floor(w.shape[1] * 0.95 + 0.5)) - 1
        return rel_err(rows[:, 1], w[:, rank]) / TOL["selector"]

    def q_rate(self, srv: Server, f: int) -> float:
        """Config #3: rate(metric[5m]) over every series through
        /api/v1/query_range (one metric: every pass asks the same; the
        result cache does not hold PromQL).  Every window is held to the
        one tolerance, the windows at and after a counter reset too."""
        t_s = TSBS_START_S + np.arange(len(self.prom)) * PROM_SCRAPE_S
        ends = np.arange(TSBS_START_S + PROM_RANGE_S,
                         TSBS_START_S + self.spec["prom_minutes"] * 60 + 1,
                         PROM_STEP_S)
        doc = srv.get_json("/api/v1/query_range",
                           query=f"rate({PROM_METRIC}[5m])",
                           start=int(ends[0]), end=int(ends[-1]),
                           step=PROM_STEP_S, db="prom")
        check(doc["status"] == "success", f"query_range: {doc}")
        want = oracle_rate(self.prom, t_s, ends, float(PROM_RANGE_S))
        check(bool(np.isfinite(want).all()), "oracle window without samples")
        index = {lab: i for i, lab in enumerate(self.prom_labels)}
        result = doc["data"]["result"]
        check(len(result) == len(index),
              f"{len(result)} series, want {len(index)}")
        got = np.empty_like(want)
        for s in result:
            m = s["metric"]
            i = index[(m["instance"], m["handler"], m["code"])]
            vals = np.array(s["values"], np.float64)
            check(np.array_equal(vals[:, 0], ends), f"series {i}: step times")
            got[i] = vals[:, 1]
        reset = np.zeros(want.shape, bool)
        drops = np.diff(self.prom, axis=0) < 0
        for s in np.flatnonzero(drops.any(axis=0)):
            reset[s] = ends >= t_s[1:][drops[:, s]][0]
        check(bool(reset.any()), "no window lies at or after a counter reset")
        log(f"     rate: relative error {rel_err(got[~reset], want[~reset]):.3g}"
            f" in {int((~reset).sum())} windows before any reset, "
            f"{rel_err(got[reset], want[reset]):.3g} in {int(reset.sum())} "
            "windows at or after one")
        return rel_err(got, want) / TOL["rate"]

    # (name, method, further arguments, the layout it has to take).  On a
    # TPU the grid pads its window axis to 128 lanes; 9 to ~22 windows at
    # 4,000 series are more than the 8x waste models/grid.py accepts, and
    # run bucketed.  TSBS's own double-groupby (12 h by 1 h) lies in that
    # band, so at the 2 h cut the 10m statement keeps its 12 windows and
    # takes the path the source's query takes on a TPU, while the 1h one
    # (2 windows) drives the grid with a series axis.
    QUERIES = (
        ("groupby-time-1m mean,max,count", "q_groupby_time_1m", (), "grid"),
        ("double-groupby-5 mean x5 by 1h,hostname", "q_double_groupby_5",
         (3600,), "grid"),
        ("double-groupby-5 mean x5 by 10m,hostname (12 windows)",
         "q_double_groupby_5", (600,), "bucket on a TPU"),
        ("first,last,min,max by time(1m)", "q_selectors_time", (), "grid"),
        ("first,last,min,max by hostname", "q_selectors_host", (), None),
        ("percentile(95) by time(10m)", "q_percentile", (), None),
        ("rate(counter[5m]) query_range", "q_rate", (), None),
    )

    def run_queries(self, srv: Server, label: str, f: int,
                    platform: str) -> None:
        for name, method, more, layout in self.QUERIES:
            t0 = time.monotonic()
            before = srv.device_counters()
            ex0 = srv.get_json("/debug/vars").get("executor", {})
            share = getattr(self, method)(srv, f, *more)
            after = srv.device_counters()
            ex1 = srv.get_json("/debug/vars").get("executor", {})
            grid, fell = (ex1.get(k, 0) - ex0.get(k, 0)
                          for k in ("grid_batches", "grid_fallbacks"))
            built, h2d, d2h = (after.get(k, 0) - before.get(k, 0) for k in (
                "xla_programs_total", "h2d_bytes_total", "d2h_bytes_total"))
            log(f"{label:4s} {name}: {time.monotonic() - t0:6.2f}s, {built} "
                f"XLA programs built, {h2d} B counted to the device and "
                f"{d2h} B back, {grid} grid batch(es), {fell} fallback(s), error at "
                f"{share:.3f} of its tolerance")
            check(share <= 1.0, f"{name}: answer outside its tolerance "
                  f"({share:.3g} x the stated bound)")
            # (bytes in are not asserted per query: one chip's grid and
            # bucket batches enter as jit arguments, which the server
            # does not count — PERF.md, open questions)
            # the CPU backend answers PromQL's tiled kernels in host
            # numpy by design, unless a mesh is configured
            by_design_host = (method == "q_rate" and platform == "cpu"
                              and self.args.devices == 1)
            check(d2h > 0 or by_design_host, f"{label} {name}: no result "
                  "came back from the device, so it did no work")
            if layout == "bucket on a TPU" and platform == "tpu":
                check(grid == 0 and fell > 0, f"{name}: the bucketed "
                      f"fallback was to serve it on a TPU; the grid took it "
                      f"{grid} time(s), {fell} fallback(s)")
            elif layout is not None:
                check(grid > 0 and fell == 0, f"{name}: built to drive the "
                      f"grid layout, took it {grid} time(s) and fell back "
                      f"{fell} time(s)")
            self.errors[name] = max(self.errors.get(name, 0.0), share)

    def check_device_work(self, srv: Server, device: dict) -> None:
        """The device did the work, by the server's own counters."""
        doc = srv.get_json("/debug/device")
        stats = srv.get_json("/debug/vars")
        on_cpu = device["platform"] == "cpu"
        mesh = self.args.devices > 1
        families = ["grid_basic", "grid_selectors", "bucket_basic",
                    "bucket_selectors_xla" if mesh else "bucket_selectors",
                    "agg_batch"]
        if mesh:
            families.append("prom_rate")
        jit = doc["jit_cache"]
        log("jit_cache: " + ", ".join(
            f"{k}={v['compiles']}" for k, v in jit.items()))
        for fam in families:
            check(fam in jit, f"program family {fam!r} never ran: "
                  f"jit_cache has {sorted(jit)}")
        check(stats.get("prom", {}).get("tiled_kernels", 0)
              + stats.get("prom", {}).get("tiled_mesh_kernels", 0) > 0,
              "rate() did not take the tiled kernels")
        # the CPU backend answers the tiled kernels in host numpy by design
        # (promql/engine._backend_is_cpu); anywhere else "host" is a defect
        decisions = doc["planner"]["decisions"]
        routes = sorted({(d["kernel"], d["route"]) for d in decisions})
        log(f"planner routes: {routes}")
        want_route = "mesh" if mesh else "host" if on_cpu else "device"
        check(any(k == "prom_rate" for k, _ in routes),
              "the planner made no decision for prom_rate")
        for kernel, route in routes:
            check(route == want_route, f"planner routed {kernel} to "
                  f"{route!r}, want {want_route!r}")
        c = doc["counters"]
        check(c.get("h2d_bytes_total", 0) > 0, "no bytes went to the device")
        live = [d for d in doc["devices"] if d["memory_stats"]
                and d["memory_stats"].get("peak_bytes_in_use", 0) > 0]
        log(f"h2d {c.get('h2d_bytes_total', 0)} B, d2h "
            f"{c.get('d2h_bytes_total', 0)} B; peak bytes in use per device: "
            + str([d["memory_stats"]["peak_bytes_in_use"] if d["memory_stats"]
                   else None for d in doc["devices"]]))
        if on_cpu:
            log("(the CPU backend reports no memory_stats; not checked)")
        else:
            check(len(live) >= self.args.devices,
                  f"{len(live)} device(s) ever held bytes, want "
                  f"{self.args.devices}")
        if mesh:
            check(c.get("mesh_dense_batches", 0) > 0,
                  "no dense batch was sharded over the mesh")
            check(c.get("mesh_shard_devices") == self.args.devices,
                  f"sharded arrays span {c.get('mesh_shard_devices')} "
                  f"device(s), want {self.args.devices}")
            # "sharded" as the cell tsbs_fleet_groupby_mesh4 counts it
            # (benchmark/metrics/mesh_*.json): rows put under the row
            # sharding, those of them the padding added, and bucket
            # kernels whose matrices were sharded and not kept on the host
            put, pad = c.get("mesh_put_rows", 0), c.get("mesh_pad_rows", 0)
            items = (c.get("mesh_items_sharded", 0),
                     c.get("mesh_items_unsharded", 0))
            log(f"mesh: {put} rows put, {pad} of them padding; bucket "
                f"kernels sharded {items[0]}, kept on the host {items[1]}; "
                f"mesh {doc['mesh'].get('axes')} over devices "
                f"{doc['mesh'].get('device_ids')}")
            check(0 <= pad < put, f"mesh_pad_rows {pad} of mesh_put_rows "
                  f"{put}: nothing was put, or only padding")
            check(items[0] > 0, "no bucket kernel ran on sharded matrices "
                  f"(mesh_items_sharded {items[0]}, unsharded {items[1]})")
            check(doc["mesh"].get("device_ids") is not None
                  and len(doc["mesh"]["device_ids"]) == self.args.devices,
                  f"/debug/device mesh spans {doc['mesh'].get('device_ids')}")
        if not on_cpu and not mesh:
            # models/ragged.py routes unsharded selectors to Pallas on a
            # TPU, and ops/pallas_segment interprets on the CPU only: the
            # family having run here means Mosaic compiled it
            log("bucket_selectors ran as the Mosaic-compiled Pallas kernel")

    def run(self) -> dict:
        a = self.args
        if a.cpu_dry_run:
            log("CPU DRY RUN: tiny sizes, control flow only — not a chip "
                "result, and nothing printed here is a device number")
        for key, full in FULL.items():
            if self.spec[key] != full:
                log(f"cut: {key} = {self.spec[key]} (the full size is {full})")
        build_native()
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
            return self._run_servers(workdir)

    def _run_servers(self, workdir: str) -> dict:
        a = self.args
        srv = Server(workdir, a.devices, a.cpu_dry_run, "1")
        try:
            log(f"server 1 ready {srv.wait_ready():.1f}s after start "
                f"(log: {srv.log_path})")
            device = self.identify(srv)
            self.load_tsbs(srv)
            self.load_prom(srv)
            self.read_back(srv)
            self.run_queries(srv, "cold", 0, device["platform"])
            srv.post("/debug/ctrl", mod="devobs", op="mark_warm")
            before = srv.device_counters()
            self.run_queries(srv, "warm", 1, device["platform"])
            after = srv.device_counters()
            built = (after.get("xla_programs_total", 0)
                     - before.get("xla_programs_total", 0))
            tripped = after.get("recompiles_after_warm_total", 0)
            check(built == 0 and tripped == 0,
                  f"the warm pass built {built} XLA program(s) and tripped "
                  f"the recompile wire {tripped} time(s)")
            log("warm pass: 0 XLA programs built, 0 recompiles after warm")
            self.check_device_work(srv, device)
            first = srv.device_counters()
            log(f"server 1 stopped {srv.stop():.1f}s after SIGTERM, exit 0")

            srv = Server(workdir, a.devices, a.cpu_dry_run, "2")
            log(f"server 2 ready {srv.wait_ready():.1f}s after start, on the "
                "data directory server 1 left")
            check(self.identify(srv) == device, "the device changed")
            self.read_back(srv)
            self.run_queries(srv, "2nd", 0, device["platform"])
            c = srv.device_counters()
            hits = c.get("persistent_cache_hits_total", 0)
            asked = c.get("persistent_cache_requests_total", 0)
            log(f"compile cache: server 1 asked {first.get('persistent_cache_requests_total', 0)} "
                f"times and hit {first.get('persistent_cache_hits_total', 0)}; "
                f"server 2 asked {asked} times and hit {hits} "
                f"({srv.log_line('device backend:').split('compile_cache=')[1]})")
            check(hits > 0, "server 2 loaded nothing from the persistent "
                  "compile cache server 1 wrote")
            log(f"server 2 stopped {srv.stop():.1f}s after SIGTERM, exit 0")
        finally:
            srv.kill()
        log("largest error per query, as a share of its stated tolerance "
            f"(TOL = {TOL}):")
        for name, share in self.errors.items():
            log(f"  {name}: {share:.4g}")
        return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4 runs the [device] mesh over a four-chip host")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU: control flow only")
    args = ap.parse_args()
    try:
        device = Smoke(args).run()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    doc = {"ok": True, "device": device}
    if args.cpu_dry_run:
        doc["cpu_dry_run"] = True
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

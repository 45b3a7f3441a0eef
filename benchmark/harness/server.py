"""One `python -m opengemini_tpu.server.app` child: the system under test,
started through its CLI entry point with its defaults.  Copied from
chip_smoke.py's `Server` (PR 21) and cut to what a cell needs; this process
never imports JAX, so the chip is the server's alone."""

from __future__ import annotations

import ctypes
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.parse

NATIVE_LIBS = ("codecs", "textindex", "seriesindex", "lineproto")


class BenchFailure(Exception):
    """The run cannot produce a result line."""


def build_native(root: str) -> float:
    """`make` the four native libraries (a no-op once they are built in this
    checkout) and open each; returns the seconds it took."""
    t0 = time.monotonic()
    native = os.path.join(root, "native")
    try:
        r = subprocess.run(["make", "-j4", "-C", native, "all"],
                           capture_output=True, text=True, timeout=600)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise BenchFailure(f"native build did not run: {e}")
    if r.returncode != 0:
        raise BenchFailure("native build failed:\n" + r.stdout + r.stderr)
    for name in NATIVE_LIBS:
        path = os.path.join(native, f"libogt{name}.so")
        try:
            ctypes.CDLL(path)
        except OSError as e:
            raise BenchFailure(f"native library {path} does not load: {e}")
    return time.monotonic() - t0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Client:
    """One keep-alive connection; a thread of the generator owns one.
    `meanwhile`, where set, is called once a request has been sent and
    before its answer is read: the caller's work for the time the server
    is busy (an `lp_stream` makes its next batch there)."""

    def __init__(self, port: int, timeout: float = 600.0):
        self.port, self.timeout = port, timeout
        self.conn: http.client.HTTPConnection | None = None
        self.meanwhile = None

    def request(self, method: str, path: str,
                body: bytes | None = None) -> tuple[int, bytes]:
        """(status, body); status 0 with the error text on a transport
        failure.  One reconnect where the server closed an idle socket."""
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body=body)
                if self.meanwhile is not None:
                    self.meanwhile()
                r = self.conn.getresponse()
                return r.status, r.read()
            except (http.client.HTTPException, OSError) as e:
                self.close()
                if attempt:
                    return 0, repr(e).encode()
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Server:
    def __init__(self, root: str, workdir: str, cpu_dry_run: bool):
        self.port = _free_port()
        self.log_path = os.path.join(workdir, "server.log")
        cfg = os.path.join(workdir, "server.toml")
        with open(cfg, "w", encoding="utf-8") as f:
            f.write(f'[data]\ndir = "{os.path.join(workdir, "data")}"\n'
                    f'[http]\nbind-address = "127.0.0.1:{self.port}"\n')
        env = dict(os.environ)
        if cpu_dry_run:
            env["JAX_PLATFORMS"] = "cpu"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "opengemini_tpu.server.app", "-config", cfg],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT)
        self.started = time.monotonic()
        self.ctl = Client(self.port)

    def log_tail(self, n: int = 30) -> str:
        with open(self.log_path, errors="replace") as f:
            lines = [ln[:300] for ln in f.read().splitlines()
                     if "cpu_aot_loader" not in ln]
        return "\n".join(lines[-n:])

    def wait_ready(self, timeout_s: float = 300.0) -> float:
        while time.monotonic() - self.started < timeout_s:
            rc = self.proc.poll()
            if rc is not None:
                raise BenchFailure(f"server exited with code {rc} before it "
                                   "was ready:\n" + self.log_tail())
            if self.ctl.request("GET", "/ping")[0] == 204:
                return time.monotonic() - self.started
            time.sleep(0.1)
        raise BenchFailure(f"server not ready after {timeout_s:.0f}s:\n"
                           + self.log_tail())

    def stop(self) -> None:
        """SIGTERM, wait for the exit, and kill what does not go."""
        self.ctl.close()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if not self._log.closed:
            self._log.close()

    # -- control-plane HTTP (never inside a timed request) ------------------

    def call(self, method: str, path: str, body: bytes | None = None,
             **params) -> tuple[int, bytes]:
        if params:
            path += "?" + urllib.parse.urlencode(params)
        status, data = self.ctl.request(method, path, body)
        if status == 0:
            raise BenchFailure(f"{method} {path[:120]} -> {data[:300]!r} "
                               f"(server exit code: {self.proc.poll()})\n"
                               + self.log_tail())
        return status, data

    def json(self, method: str, path: str, **params) -> dict:
        status, data = self.call(method, path, b"" if method == "POST"
                                 else None, **params)
        if status != 200:
            raise BenchFailure(f"{method} {path} -> HTTP {status}: "
                               f"{data[:300]!r}")
        return json.loads(data)

    def query(self, q: str, db: str = "") -> dict:
        return self.json("POST", "/query", q=q, db=db, epoch="ns")

    def vars(self) -> dict:
        return self.json("GET", "/debug/vars")

    def device(self) -> dict:
        return self.json("GET", "/debug/device")

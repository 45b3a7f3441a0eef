"""One `python -m opengemini_tpu.server.app` child: the system under test,
started through its CLI entry point with its defaults.  Copied from
chip_smoke.py's `Server` (PR 21) and cut to what a cell needs; this process
never imports JAX, so the chip is the server's alone.

The server's life is the run's (PR 41).  It starts in a session of its own,
asks the kernel to kill it when the process that started it dies
(`PR_SET_PDEATHSIG`: the run ended by SIGKILL), and leaves its pid in
`<workdir>/server.pid`, which `Server.refuse_beside_live` reads before the
next run wipes the directory.  `stop` signals the whole group; a run that is
being ended from outside gives the server seconds, not minutes."""

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
PID_FILE = "server.pid"
STOP_S, STOP_ENDED_S = 120.0, 5.0     # SIGTERM's grace: a run's own end, and
#                                       one that is being ended from outside


class BenchFailure(Exception):
    """The run cannot produce a result line."""


def build_native(root: str) -> float:
    """`make` the four native libraries (a no-op once they are built in this
    checkout) and open each; returns the seconds it took."""
    t0 = time.monotonic()
    native = os.path.join(root, "native")
    try:
        r = subprocess.run(["make", "-j4", "-C", native, "all"],
                           capture_output=True, text=True, timeout=600,
                           preexec_fn=die_with_parent)
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


_LIBC = ctypes.CDLL(None)      # opened here: nothing is loaded after a fork


def die_with_parent() -> None:
    """`preexec_fn` of every child: SIGKILL when the thread that started it
    is gone.  Linux's prctl(PR_SET_PDEATHSIG); gVisor honours it (my chip
    run, PR 41)."""
    _LIBC.prctl(1, signal.SIGKILL, 0, 0, 0)


def _toml(value) -> str:
    if isinstance(value, bool):
        return "true" if value else "false"
    if isinstance(value, (int, float)):
        return repr(value)
    if isinstance(value, str):
        return json.dumps(value)
    if isinstance(value, list):
        return "[" + ", ".join(_toml(v) for v in value) + "]"
    raise BenchFailure(f"a configuration's `server` member holds {value!r}: "
                       "a section's values are numbers, strings, booleans "
                       "and lists of them")


def server_toml(workdir: str, port: int, sections: dict | None) -> str:
    """The server's configuration: where its data lies and where it
    listens, then the sections a configuration file carries under `server`
    (`{"device": {"mesh-axes": [...]}}`), which may not restate those two."""
    text = (f'[data]\ndir = "{os.path.join(workdir, "data")}"\n'
            f'[http]\nbind-address = "127.0.0.1:{port}"\n')
    for name, members in (sections or {}).items():
        if name in ("data", "http") or not isinstance(members, dict):
            raise BenchFailure(f"a configuration's `server` member may add "
                               f"sections of its own, not {name!r}")
        text += f"[{name}]\n" + "".join(
            f"{k} = {_toml(v)}\n" for k, v in members.items())
    return text


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


def _is_server(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return b"opengemini_tpu.server.app" in f.read()
    except OSError:
        return False


class Server:
    def __init__(self, root: str, workdir: str, cpu_dry_run: bool,
                 sections: dict | None = None):
        self.port = _free_port()
        self.log_path = os.path.join(workdir, "server.log")
        self.pid_path = os.path.join(workdir, PID_FILE)
        cfg = os.path.join(workdir, "server.toml")
        with open(cfg, "w", encoding="utf-8") as f:
            f.write(server_toml(workdir, self.port, sections))
        env = dict(os.environ)
        if cpu_dry_run:
            env["JAX_PLATFORMS"] = "cpu"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "opengemini_tpu.server.app", "-config", cfg],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True, preexec_fn=die_with_parent)
        with open(self.pid_path, "w", encoding="utf-8") as f:
            f.write(f"{self.proc.pid}\n")
        self.started = time.monotonic()
        self.ctl = Client(self.port)

    @staticmethod
    def refuse_beside_live(workdir: str) -> None:
        """Before a run wipes `workdir`: the server an earlier run left its
        pid of has to be gone.  One that lives holds the chip and the data
        directory, and a second beside it measures neither."""
        try:
            with open(os.path.join(workdir, PID_FILE), encoding="utf-8") as f:
                pid = int(f.read().split()[0])
        except (OSError, ValueError, IndexError):
            return
        if _is_server(pid):
            raise BenchFailure(
                f"the server of an earlier run in this checkout (pid {pid}, "
                f"{os.path.join(workdir, PID_FILE)}) is still alive: it "
                "holds the chip and the work directory; end it first")

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

    def _signal_group(self, sig: int) -> None:
        """The server leads a session of its own, so its group is its pid."""
        try:
            os.killpg(self.proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass

    def stop(self, grace_s: float = STOP_S) -> None:
        """SIGTERM to the server's whole group, `grace_s` for the exit, then
        SIGKILL to whatever of the group is left."""
        self.ctl.close()
        if self.proc.poll() is None:
            self._signal_group(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                pass
        self._signal_group(signal.SIGKILL)
        self.proc.wait()
        if not self._log.closed:
            self._log.close()
        try:
            os.unlink(self.pid_path)
        except OSError:
            pass

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

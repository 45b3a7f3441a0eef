"""ctypes bindings for the C++ libraries under native/, and the one
place that builds and opens them.

The reference uses cgo for its native pieces (textindex, lz4, rocksdb);
pybind11 isn't in this image, so the bridge is a plain C ABI + ctypes
(SURVEY.md environment notes).  Five libraries: codecs (bound here),
textindex (native/textindex.py), seriesindex (index/mergeset.py),
lineproto (ingest/native_lp.py), render (promql/render.py, which also binds
the row writer query/render.py calls).
`.gitignore` excludes the built `.so` files, so a clean checkout has
none: `open_library` runs the library's make target when the file is
missing.  A library that still cannot be
built or opened leaves its callers on their pure-Python paths (every
file stays readable), and the reason is kept for `report()` — the
server prints it at start-up (chip_smoke.py fails on it) and in SHOW
DIAGNOSTICS, instead of running slow in silence.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native"))
LIBRARIES = ("codecs", "textindex", "seriesindex", "lineproto", "render")

# library -> "" once loaded, else why it did not load
_status: dict[str, str] = {}


def lib_path(name: str) -> str:
    return os.path.join(NATIVE_DIR, f"libogt{name}.so")


def _make(name: str, force: bool = False) -> str:
    """Run one library's make target; "" on success, else what failed."""
    cmd = ["make", "-C", NATIVE_DIR, f"libogt{name}.so"]
    if force:
        cmd.insert(1, "-B")
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"make: {type(e).__name__}: {e}"
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-3:]
        return f"make exited {r.returncode}: " + " | ".join(tail)
    return ""


def open_library(name: str, bind):
    """The loaded library, or None with the reason recorded.

    Builds `native/libogt<name>.so` from its source first when the file
    is missing, opens it, and lets ``bind(lib)`` declare the signatures.
    A library that lacks a symbol `bind` asks for is a stale build from
    before the symbol existed: it is rebuilt once and opened again."""
    path = lib_path(name)
    why = "" if os.path.exists(path) else _make(name)
    for rebuilt in (False, True):
        if why:
            break
        try:
            lib = ctypes.CDLL(path)
            bind(lib)
        except OSError as e:
            why = f"dlopen {path}: {e}"
        except AttributeError as e:
            why = f"{path}: {e}"
            if not rebuilt:
                why = _make(name, force=True)
        else:
            _status[name] = ""
            return lib
    _status[name] = why
    return None


def load_all() -> dict[str, str]:
    """Open all five libraries (building any that is missing) and return
    {library: "" if loaded else why not}."""
    from opengemini_tpu.index import mergeset
    from opengemini_tpu.ingest import native_lp
    from opengemini_tpu.native import textindex
    from opengemini_tpu.promql import render

    load()
    textindex._load()
    mergeset.load()
    native_lp.load()
    render.load()
    return {name: _status[name] for name in LIBRARIES}


def report() -> str:
    """load_all() as one line: `codecs=loaded textindex=NOT LOADED (why)`."""
    return " ".join(
        f"{name}={'loaded' if not why else 'NOT LOADED (' + why + ')'}"
        for name, why in load_all().items())


_LIB = None
_TRIED = False


def _bind(lib) -> None:
    sig = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
    for name in ("ogt_gorilla_encode", "ogt_gorilla_decode",
                 "ogt_varint_delta_encode", "ogt_varint_delta_decode"):
        fn = getattr(lib, name)
        fn.restype = ctypes.c_int64
        fn.argtypes = sig


def load():
    """The codec library or None (see open_library)."""
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        _LIB = open_library("codecs", _bind)
    return _LIB


def build() -> bool:
    """Rebuild the codec library from source and reload it (tests)."""
    global _TRIED
    if _make("codecs", force=True):
        return False
    _TRIED = False
    return load() is not None


# -- native-backed codecs ----------------------------------------------------


def gorilla_encode(values: np.ndarray) -> bytes | None:
    lib = load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(values, dtype=np.float64).view(np.uint64)
    cap = len(vals) * 10 + 16
    out = np.zeros(cap, dtype=np.uint8)
    n = lib.ogt_gorilla_encode(
        vals.ctypes.data, len(vals), out.ctypes.data, cap
    )
    if n < 0:
        return None
    return out[:n].tobytes()


def gorilla_decode_native(buf: bytes, n: int) -> np.ndarray | None:
    lib = load()
    if lib is None:
        return None
    inp = np.frombuffer(buf, dtype=np.uint8)
    out = np.empty(n, dtype=np.uint64)
    got = lib.ogt_gorilla_decode(inp.ctypes.data, len(inp), out.ctypes.data, n)
    if got != n:
        raise ValueError("corrupt gorilla block")
    return out.view(np.float64)


def varint_delta_encode(values: np.ndarray) -> bytes | None:
    lib = load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(values, dtype=np.int64)
    cap = len(vals) * 10 + 16
    out = np.zeros(cap, dtype=np.uint8)
    n = lib.ogt_varint_delta_encode(vals.ctypes.data, len(vals), out.ctypes.data, cap)
    if n < 0:
        return None
    return out[:n].tobytes()


def varint_delta_decode_native(buf: bytes, n: int) -> np.ndarray | None:
    lib = load()
    if lib is None:
        return None
    inp = np.frombuffer(buf, dtype=np.uint8)
    out = np.empty(n, dtype=np.int64)
    got = lib.ogt_varint_delta_decode(inp.ctypes.data, len(inp), out.ctypes.data, n)
    if got != n:
        raise ValueError("corrupt varint block")
    return out


# -- pure-python decode fallbacks (files stay readable without the lib) ------


def gorilla_decode_py(buf: bytes, n: int) -> np.ndarray:
    out = np.empty(n, dtype=np.uint64)
    if n == 0:
        return out.view(np.float64)
    bits = _Bits(buf)
    prev = bits.read(64)
    out[0] = prev
    lz = tz = 0
    for i in range(1, n):
        if bits.read(1) == 0:
            out[i] = prev
            continue
        if bits.read(1) == 1:
            lz = bits.read(5)
            mbits = bits.read(6) + 1
            tz = 64 - lz - mbits
            if tz < 0:
                raise ValueError("corrupt gorilla block")
        mbits = 64 - lz - tz
        x = bits.read(mbits) << tz
        prev ^= x
        out[i] = prev & 0xFFFFFFFFFFFFFFFF
    return out.view(np.float64)


def varint_delta_decode_py(buf: bytes, n: int) -> np.ndarray:
    out = np.empty(n, dtype=np.int64)
    pos = 0
    prev = 0
    for i in range(n):
        u = 0
        shift = 0
        while True:
            if pos >= len(buf):
                raise ValueError("corrupt varint block")
            b = buf[pos]
            pos += 1
            u |= (b & 0x7F) << shift
            if not (b & 0x80):
                break
            shift += 7
        delta = (u >> 1) ^ -(u & 1)
        # int64 wraparound semantics must match the native codec: deltas
        # may overflow int64 by design (encoded mod 2^64)
        prev = (prev + delta) & 0xFFFFFFFFFFFFFFFF
        out[i] = prev - (1 << 64) if prev >= (1 << 63) else prev
        prev = int(out[i])
    return out


class _Bits:
    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes):
        self.buf = buf
        self.pos = 0

    def read(self, nbits: int) -> int:
        v = 0
        for _ in range(nbits):
            byte_i = self.pos >> 3
            if byte_i >= len(self.buf):
                raise ValueError("truncated bit stream")
            bit = (self.buf[byte_i] >> (7 - (self.pos & 7))) & 1
            v = (v << 1) | bit
            self.pos += 1
        return v


def gorilla_decode(buf: bytes, n: int) -> np.ndarray:
    got = gorilla_decode_native(buf, n)
    return got if got is not None else gorilla_decode_py(buf, n)


def varint_delta_decode(buf: bytes, n: int) -> np.ndarray:
    got = varint_delta_decode_native(buf, n)
    return got if got is not None else varint_delta_decode_py(buf, n)

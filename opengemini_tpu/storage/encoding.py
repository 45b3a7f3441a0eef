"""Per-type column block encodings.

numpy-vectorized analogues of the reference's lib/encoding per-type codecs
(gorilla floats float.go:27, delta+simple8b ints int.go:21, RLE timestamps):
  - int64/time: frame-of-reference delta + minimal fixed width + zlib
  - float64: raw LE + zlib (XOR-compress candidate for the C++ codec lib)
  - bool: bit-packed
  - string: offsets + utf8 blob + zlib
Every codec returns a self-describing block: [tag u8][payload] so readers
don't need schema-side encoding info.
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

from opengemini_tpu import native
from opengemini_tpu.record import Column, FieldType

# block tags
_T_RAW64 = 0  # raw little-endian 8-byte values (+zlib)
_T_DELTA = 1  # int64: first value + deltas packed at minimal width (+zlib)
_T_BOOL = 2  # packed bits
_T_STR = 3  # uint32 offsets + utf8 blob (+zlib)
_T_CONST = 4  # int64 constant run: value + count (RLE timestamps fast path)
_T_GORILLA = 5  # float64 XOR-compressed (native C++ codec, py-decodable)
_T_VARINT = 6  # int64 delta+zigzag varint (native C++ codec, py-decodable)
_T_STRDICT = 7  # dictionary-coded strings: uniq table + min-width indices

# flag bit on the tag byte of _T_DELTA and _T_RAW64: the payload is in
# its raw envelope (no zlib).  No writer in the tree sets it; files
# written under an earlier writer profile carry it, and the decoders read
# flagged blocks unconditionally so that those files stay readable
# (tests/test_golden_blocks.py holds their bytes).
_DEV_FLAG = 0x80

_ZLEVEL = 1

_DELTA_HEAD = struct.calcsize("<BIqqB")


def encode_ints(values: np.ndarray) -> bytes:
    """int64 via constant-stride RLE, native varint-delta (C++), or
    frame-of-reference deltas at minimal byte width."""
    values = np.ascontiguousarray(values, dtype=np.int64)
    n = len(values)
    if n == 0:
        return struct.pack("<BI", _T_DELTA, 0)
    deltas = np.diff(values)
    if n > 1 and (deltas == deltas[0]).all():
        # constant-stride run (regular timestamps): 18-byte block
        return struct.pack("<BIqq", _T_CONST, n, int(values[0]), int(deltas[0]))
    if n == 1:
        return struct.pack("<BIqq", _T_CONST, 1, int(values[0]), 0)
    dmin = deltas.min()
    shifted = (deltas - dmin).astype(np.uint64)
    width = _min_width(int(shifted.max()))
    packed = shifted.astype({1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[width])
    payload = zlib.compress(packed.tobytes(), _ZLEVEL)
    head = struct.pack("<BIqqB", _T_DELTA, n, int(values[0]), int(dmin), width)
    for_block = head + payload
    # adaptive: native varint vs FOR+zlib — keep the smaller block
    # (repetitive delta sequences compress far better under zlib)
    nv = native.varint_delta_encode(values)
    if nv is not None and 5 + len(nv) < len(for_block):
        return struct.pack("<BI", _T_VARINT, n) + nv
    return for_block


def decode_ints(buf: bytes) -> np.ndarray:
    tag = buf[0]
    if tag == _T_VARINT:
        (n,) = struct.unpack_from("<I", buf, 1)
        return native.varint_delta_decode(buf[5:], n)
    if tag == _T_CONST:
        _, n, first, stride = struct.unpack_from("<BIqq", buf)
        return (first + stride * np.arange(n, dtype=np.int64)).astype(np.int64)
    if tag & ~_DEV_FLAG == _T_DELTA:
        (n,) = struct.unpack_from("<I", buf, 1)
        if n == 0:
            return np.empty(0, dtype=np.int64)
        first, dmin, width = struct.unpack_from("<qqB", buf, 5)
        raw = buf[_DELTA_HEAD:]
        payload = raw if tag & _DEV_FLAG else zlib.decompress(raw)
        dt = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[width]
        shifted = np.frombuffer(payload, dtype=dt).astype(np.int64)
        out = np.empty(n, dtype=np.int64)
        out[0] = first
        if n > 1:
            np.cumsum(shifted + dmin, out=out[1:])
            out[1:] += first
        return out
    raise ValueError(f"bad int block tag {tag}")


def encode_floats(values: np.ndarray) -> bytes:
    """Adaptive: gorilla XOR (native) vs zlib — keep the smaller block
    (the reference's lib/encoding float.go also chooses per block)."""
    values = np.ascontiguousarray(values, dtype=np.float64)
    z = zlib.compress(values.tobytes(), _ZLEVEL)
    g = native.gorilla_encode(values)
    if g is not None and len(g) < len(z):
        return struct.pack("<BI", _T_GORILLA, len(values)) + g
    return struct.pack("<BI", _T_RAW64, len(values)) + z


def decode_floats(buf: bytes) -> np.ndarray:
    tag = buf[0]
    if tag == _T_GORILLA:
        (n,) = struct.unpack_from("<I", buf, 1)
        return native.gorilla_decode(buf[5:], n)
    if tag & ~_DEV_FLAG != _T_RAW64:
        raise ValueError(f"bad float block tag {tag}")
    (n,) = struct.unpack_from("<I", buf, 1)
    raw = buf[5:]
    payload = raw if tag & _DEV_FLAG else zlib.decompress(raw)
    return np.frombuffer(payload, dtype=np.float64).copy()


def encode_bools(values: np.ndarray) -> bytes:
    values = np.ascontiguousarray(values, dtype=np.bool_)
    packed = np.packbits(values)
    return struct.pack("<BI", _T_BOOL, len(values)) + packed.tobytes()


def decode_bools(buf: bytes) -> np.ndarray:
    tag = buf[0]
    if tag != _T_BOOL:
        raise ValueError(f"bad bool block tag {tag}")
    (n,) = struct.unpack_from("<I", buf, 1)
    bits = np.frombuffer(buf[5:], dtype=np.uint8)
    return np.unpackbits(bits, count=n).astype(np.bool_)


def encode_strings(values: np.ndarray) -> bytes:
    """Adaptive: low-cardinality columns (log levels, statuses, hostnames)
    dictionary-encode — unique table + minimal-width indices (reference:
    lib/compress dictionary coding); high-cardinality columns keep the
    plain offsets+blob layout."""
    parts = [(v if isinstance(v, str) else "").encode("utf-8") for v in values]
    n = len(parts)
    uniq_set = set(parts)
    if n >= 8 and len(uniq_set) <= max(16, n // 4):
        uniq = sorted(uniq_set)  # sort only when the dict branch is taken
        idx_of = {u: i for i, u in enumerate(uniq)}
        width = _min_width(max(1, len(uniq) - 1))
        dt = _WIDTH_DT[width]
        indices = np.fromiter((idx_of[p] for p in parts), dt, count=n)
        uoff = np.zeros(len(uniq) + 1, dtype=np.uint32)
        np.cumsum([len(u) for u in uniq], out=uoff[1:])
        payload = zlib.compress(
            uoff.tobytes() + b"".join(uniq) + indices.tobytes(), _ZLEVEL
        )
        return struct.pack("<BIIB", _T_STRDICT, n, len(uniq), width) + payload
    offsets = np.zeros(n + 1, dtype=np.uint32)
    if parts:
        np.cumsum([len(p) for p in parts], out=offsets[1:])
    blob = b"".join(parts)
    payload = zlib.compress(offsets.tobytes() + blob, _ZLEVEL)
    return struct.pack("<BI", _T_STR, n) + payload


def decode_strings(buf: bytes) -> np.ndarray:
    tag = buf[0]
    if tag == _T_STRDICT:
        n, k, width = struct.unpack_from("<IIB", buf, 1)
        payload = zlib.decompress(buf[10:])
        uoff = np.frombuffer(payload[: 4 * (k + 1)], dtype=np.uint32)
        blob_end = 4 * (k + 1) + int(uoff[-1])
        blob = payload[4 * (k + 1) : blob_end]
        dt = _WIDTH_DT[width]
        indices = np.frombuffer(payload[blob_end:], dtype=dt)[:n]
        table = np.empty(k, dtype=object)
        for i in range(k):
            table[i] = blob[uoff[i] : uoff[i + 1]].decode("utf-8")
        return table[indices]
    if tag != _T_STR:
        raise ValueError(f"bad string block tag {tag}")
    (n,) = struct.unpack_from("<I", buf, 1)
    payload = zlib.decompress(buf[5:])
    offsets = np.frombuffer(payload[: 4 * (n + 1)], dtype=np.uint32)
    blob = payload[4 * (n + 1) :]
    out = np.empty(n, dtype=object)
    for i in range(n):
        out[i] = blob[offsets[i] : offsets[i + 1]].decode("utf-8")
    return out


def encode_mask(valid: np.ndarray) -> bytes:
    """Validity bitmap; b'' means all-valid (the common case)."""
    if valid.all():
        return b""
    return encode_bools(valid)


def decode_mask(buf: bytes, n: int) -> np.ndarray:
    if not buf:
        return np.ones(n, dtype=np.bool_)
    return decode_bools(buf)


_ENCODERS = {
    FieldType.FLOAT: encode_floats,
    FieldType.INT: encode_ints,
    FieldType.BOOL: encode_bools,
    FieldType.STRING: encode_strings,
}
_DECODERS = {
    FieldType.FLOAT: decode_floats,
    FieldType.INT: decode_ints,
    FieldType.BOOL: decode_bools,
    FieldType.STRING: decode_strings,
}


def encode_column(col: Column) -> tuple[bytes, bytes]:
    """-> (values block, mask block)."""
    return _ENCODERS[col.ftype](col.values), encode_mask(col.valid)


def decode_column(ftype: FieldType, vbuf: bytes, mbuf: bytes) -> Column:
    values = _DECODERS[ftype](vbuf)
    return Column(ftype, values, decode_mask(mbuf, len(values)))


_WIDTH_DT = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _min_width(vmax: int) -> int:
    if vmax < 1 << 8:
        return 1
    if vmax < 1 << 16:
        return 2
    if vmax < 1 << 32:
        return 4
    return 8

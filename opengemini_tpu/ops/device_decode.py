"""Device-side decode of TSF device-profile blocks, fused into the grid
aggregation data path.

Cold scans used to pay CPU decode (zlib + delta reconstruction) and then
a FULL-WIDTH host->device transfer of the padded grid — 8-byte values
plus a mask byte for every padded cell.  This module moves the decode
onto the accelerator for the block shapes that allow it ("GPU
Acceleration of SQL Analytics on Compressed Data", arXiv:2506.10092;
"Data Path Fusion", arXiv:2605.10511): the writer's device profile
(storage/encoding.py, OGT_DEVICE_PROFILE=1) keeps int/float payloads in
a raw envelope, the cold scan ships those encoded bytes (plus int32
scatter slots and packed mask bits) to the device, and ONE jit program
decodes, scatters into the (S_pad, k, W_pad) grid, and runs the basic
window reduce — compressed-bytes -> decode -> group -> reduce with no
decoded column ever materializing on the host.

Decodable block kinds (encoding.DeviceBlock):

  const    first + step * iota — pure header, zero payload bytes
  delta    frame-of-reference deltas at fixed byte width: widen, +step,
           int64 cumsum, +first (exactly the host decode_ints
           arithmetic, so results are bit-identical)
  raw64    little-endian float64 values: an 8-byte bitcast
  gorilla  XOR-compressed float64: a host structural scan walks the
           control bits once per block (cached) and emits per-value
           (bitpos, mbits, shift) aux vectors; the device unpacks the
           payload to bits (jnp shift/mask), gathers each value's
           meaningful-bit
           window, and reconstructs with a parallel XOR prefix scan —
           bit-identical to the host decoder including NaN/±0.0
  varint   delta+zigzag LEB128 int64: fully data-parallel — terminator
           bits mark value ids, a segmented shift/or rebuilds each
           varint, zigzag + wrapping int64 cumsum match the host's
           mod-2^64 arithmetic exactly
  strdict  dictionary-coded strings: the min-width index array decodes
           on device (widen); the uniq table stays host-side for label
           work (encoding.DeviceBlock.table)

Everything else (zlib envelopes, bool/plain-string blocks) keeps the
host decode — EncodedColumn.values decodes lazily and the existing path
runs unchanged.  `OGT_DEVICE_DECODE=0` disables this module entirely
(bit-identical host path); `OGT_DEVICE_DECODE_CODECS` restricts the
device family to a comma list of the kinds above (default: all); x64 is
required for bit-identity (int64 cumsum, f64 bitcast), so a process
without x64 — a server, today (ROADMAP S2) — answers inactive and
decodes on the host.

Mesh sharding: under a configured device mesh, build_mesh_grid_plan
splits one grid plan into per-output-row-shard sub-plans (series runs
never straddle a shard boundary because the scatter row ids are
non-decreasing), ships each shard's encoded bytes to its own device,
runs the same fused per-shard programs, and assembles vt/mt/stats as
NamedSharding global arrays partitioned on the row axis — zero
collectives, and the sharded colcache device tier retains the result
for warm repeats.

Program caching: one jitted program per static geometry (block
signature, row count, grid shape, dtype, mask presence), registered
with the devobs compile inventory — a warm loop repeating the same scan
reuses the program, so the recompile tripwire stays clean.

Counters (module `device`, /metrics `ogt_device_decode_*`):
decode_blocks_total, decode_payload_bytes_total, decode_rows_total,
decode_fallbacks_total, plus the per-codec split
decode_blocks_<codec>_total / decode_payload_bytes_<codec>_total for
codec in const/delta/raw64/gorilla/varint/strdict — /debug/device shows
which codecs actually ship encoded.  Transfers land on the
`device-decode` site of the `ogt_device_h2d_*` histograms via
devobs.note_transfer; mesh-sharded transfers carry a `mesh="on"` label
on the same site.
"""

from __future__ import annotations

import functools
import os
import struct
import time

import numpy as np

from opengemini_tpu.query import offload
from opengemini_tpu.storage import encoding
from opengemini_tpu.utils import devobs
from opengemini_tpu.utils.stats import GLOBAL as _STATS

# past this many blocks the unrolled decode program's compile time would
# dominate what it saves; the host pool decode handles the long tail
_MAX_BLOCKS = 256

_XFER_SITE = "device-decode"


def enabled() -> bool:
    """The OGT_DEVICE_DECODE knob alone (README "Decode on device")."""
    return os.environ.get("OGT_DEVICE_DECODE", "1") not in ("", "0")


_ALL_CODECS = ("const", "delta", "raw64", "gorilla", "varint", "strdict")


def codecs_enabled() -> frozenset:
    """The device codec family (OGT_DEVICE_DECODE_CODECS, README "Decode
    on device"): a comma list of block kinds allowed to decode on the
    accelerator; unset/empty means all of them.  Read fresh every plan —
    it is a triage knob (pin a suspect codec to the host path live)."""
    raw = os.environ.get("OGT_DEVICE_DECODE_CODECS", "")
    if not raw.strip():
        return frozenset(_ALL_CODECS)
    return frozenset(t.strip().lower() for t in raw.split(",") if t.strip())


def _x64_on() -> bool:
    """Read the x64 flag FRESH every time — it is runtime-togglable,
    and a stale cached True would run the int64 cumsum / f64 bitcast in
    32-bit and silently diverge from the host path."""
    import jax

    return bool(jax.config.jax_enable_x64)


def active() -> bool:
    """Device decode usable in this process (knob + x64).  x64 is what
    makes the int64 cumsum and f64 bitcast bit-identical to the host
    decoders."""
    return enabled() and _x64_on()


@functools.lru_cache(maxsize=1024)
def _gorilla_scan(payload: bytes, n: int):
    """Host structural scan of one gorilla XOR stream: the control bits
    are inherently sequential, so the host walks them ONCE per block
    (cached on the payload bytes the EncodedColumn retains anyway) and
    emits the per-value aux vectors the data-parallel device decode
    needs — bitpos (where each value's meaningful-bit window starts),
    mbits (its length; 0 marks a repeat), shift (its trailing-zero
    shift).  Value 0 is the raw 64-bit first value (mbits=64, shift=0).
    Returns (bitpos int32, mbits uint8, shift uint8, vals uint64) where
    vals[i] is the decoded bit pattern of value i (the cumulative XOR) —
    mesh shards slice mid-stream and seed the device XOR-scan with
    vals[lo-1].  Returns None when the stream is malformed (the caller
    falls back to the host decoder's error handling)."""
    nbits = len(payload) * 8

    def read(pos, k):
        b = payload[pos >> 3:(pos + k + 7) >> 3]
        v = int.from_bytes(b, "big")
        return (v >> (len(b) * 8 - (pos & 7) - k)) & ((1 << k) - 1)

    bitpos = np.zeros(n, np.int32)
    mbits = np.zeros(n, np.uint8)
    shift = np.zeros(n, np.uint8)
    vals = np.zeros(n, np.uint64)
    if n == 0:
        return bitpos, mbits, shift, vals
    if nbits < 64:
        return None
    mbits[0] = 64
    acc = read(0, 64)
    vals[0] = acc
    pos = 64
    lz = tz = 0
    for i in range(1, n):
        if pos + 1 > nbits:
            return None
        c = read(pos, 1)
        pos += 1
        if not c:
            vals[i] = acc
            continue  # repeat of prev: xor = 0, mbits stays 0
        if pos + 1 > nbits:
            return None
        f = read(pos, 1)
        pos += 1
        if f:
            if pos + 11 > nbits:
                return None
            lz = read(pos, 5)
            pos += 5
            mb = read(pos, 6) + 1
            pos += 6
            tz = 64 - lz - mb
            if tz < 0:
                return None
        mb = 64 - lz - tz
        if mb <= 0 or pos + mb > nbits:
            return None
        bitpos[i] = pos
        mbits[i] = mb
        shift[i] = tz
        acc ^= read(pos, mb) << tz
        vals[i] = acc
        pos += mb
    return bitpos, mbits, shift, vals


def _varint_ok(payload: bytes, n: int) -> bool:
    """Shape-validate a varint stream on the host (vectorized): exactly
    n terminator bytes, stream ends on one, and every varint is at most
    10 bytes (canonical uint64) so the device's 7*offset shifts stay in
    range."""
    b = np.frombuffer(payload, np.uint8)
    ends = np.flatnonzero((b & 0x80) == 0)
    if len(ends) != n or (n and ends[-1] != len(b) - 1):
        return False
    if n == 0:
        return len(b) == 0
    lens = np.diff(np.concatenate(([np.int64(-1)], ends)))
    return bool((lens <= 10).all())


def classify(blocks) -> list | None:
    """DeviceBlock views of every raw block buffer, or None when any
    block (or the block count) is not device-decodable — including
    kinds excluded by OGT_DEVICE_DECODE_CODECS and streams whose host
    structural validation fails."""
    if len(blocks) > _MAX_BLOCKS:
        return None
    allowed = codecs_enabled()
    out = []
    for buf in blocks:
        if isinstance(buf, encoding.DeviceBlock):
            db = buf  # pre-sliced mesh-shard block; knob still applies
        else:
            db = encoding.device_block(buf)
        if db is None or db.kind not in allowed:
            return None
        if db.kind == "gorilla":
            # sliced blocks carry their scan (aux); whole blocks scan here
            if db.aux is None and \
                    _gorilla_scan(bytes(db.payload), db.n) is None:
                return None
        elif db.kind == "varint":
            if not _varint_ok(bytes(db.payload), db.n):
                return None
        elif db.kind == "strdict" and len(db.payload) != db.n * db.width:
            return None
        out.append(db)
    return out


def _pack_blocks(dbs):
    """(sig, payload, scalars, aux32, aux8) of classified DeviceBlocks —
    THE block assembly every program entry point shares, so the jit
    cache key (sig) can never desynchronize from the shipped bytes.
    aux32/aux8 carry the gorilla structural-scan vectors (bitpos;
    interleaved mbits,shift) and are None when no block needs them."""
    sig = tuple((b.kind, b.n, b.width) for b in dbs)
    payload = np.frombuffer(
        b"".join(bytes(b.payload) for b in dbs), np.uint8)
    scalars = np.array([[b.first, b.step] for b in dbs],
                       np.int64).reshape(len(dbs), 2)
    aux32 = aux8 = None
    if any(b.kind == "gorilla" for b in dbs):
        p32, p8 = [], []
        for b in dbs:
            if b.kind != "gorilla":
                continue
            if b.aux is not None:
                bitpos, mbits, shift = b.aux
            else:
                bitpos, mbits, shift, _ = _gorilla_scan(
                    bytes(b.payload), b.n)
            p32.append(bitpos)
            p8.append(np.stack([mbits, shift], axis=1).reshape(-1))
        aux32 = np.concatenate(p32) if p32 else np.zeros(0, np.int32)
        aux8 = np.concatenate(p8) if p8 else np.zeros(0, np.uint8)
    return sig, payload, scalars, aux32, aux8


def _sig_has_aux(sig) -> bool:
    return any(kind == "gorilla" for kind, _, _ in sig)


def note_fallback(n: int = 1) -> None:
    """Count an eligible-looking encoded scan that ended up on the host
    decode path anyway (ineligible blocks, codec excluded by the knob,
    cost gate, knob off at freeze time) — the triage counter for "why
    didn't H2D drop"."""
    _STATS.incr("device", "decode_fallbacks_total", n)


# per-codec counter spellings (the label-free registry renders each as
# its own ogt_device_decode_*_total family; README documents the set)
_CODEC_KEYS = {
    "const": ("decode_blocks_const_total",
              "decode_payload_bytes_const_total"),
    "delta": ("decode_blocks_delta_total",
              "decode_payload_bytes_delta_total"),
    "raw64": ("decode_blocks_raw64_total",
              "decode_payload_bytes_raw64_total"),
    "gorilla": ("decode_blocks_gorilla_total",
                "decode_payload_bytes_gorilla_total"),
    "varint": ("decode_blocks_varint_total",
               "decode_payload_bytes_varint_total"),
    "strdict": ("decode_blocks_strdict_total",
                "decode_payload_bytes_strdict_total"),
}


def _payload_nbytes(kind: str, n: int, width: int) -> int:
    if kind == "const":
        return 0
    if kind == "delta":
        return (n - 1) * width if n else 0
    if kind == "raw64":
        return 8 * n
    if kind == "strdict":
        return n * width
    return width  # gorilla/varint: width IS the payload byte length


def _note_decode_stats(sig, rows: int) -> None:
    """The decode counters, split per codec so /debug/device shows which
    codecs actually ship encoded (the aggregates keep their pre-split
    spellings)."""
    _STATS.incr("device", "decode_blocks_total", len(sig))
    total = 0
    for kind, bn, width in sig:
        nb = _payload_nbytes(kind, bn, width)
        total += nb
        bkey, pkey = _CODEC_KEYS[kind]
        _STATS.incr("device", bkey)
        _STATS.incr("device", pkey, nb)
    _STATS.incr("device", "decode_payload_bytes_total", total)
    _STATS.incr("device", "decode_rows_total", rows)


class GridPlan:
    """Host-side inputs + static geometry of one fused decode->scatter->
    reduce program invocation.  The scatter slots travel either as an
    explicit int32 `flat` array (4 bytes/row) or — when every series run
    is constant-stride and the window arithmetic verifies on the host —
    as `runmeta` (rel0, stride, start_row) int64 triples plus one phase
    scalar (~24 bytes/RUN), reconstructed on device."""

    __slots__ = ("geom", "payload", "scalars", "aux32", "aux8",
                 "viewruns", "flat", "runmeta", "consts", "maskbits", "n")

    def __init__(self, geom, payload, scalars, aux32, aux8, viewruns,
                 flat, runmeta, consts, maskbits, n):
        self.geom = geom
        self.payload = payload
        self.scalars = scalars
        self.aux32 = aux32
        self.aux8 = aux8
        self.viewruns = viewruns
        self.flat = flat
        self.runmeta = runmeta
        self.consts = consts
        self.maskbits = maskbits
        self.n = n

    def transfer_nbytes(self) -> int:
        nb = int(self.payload.nbytes) + int(self.scalars.nbytes)
        for a in (self.aux32, self.aux8, self.viewruns, self.flat,
                  self.runmeta, self.consts, self.maskbits):
            if a is not None:
                nb += int(a.nbytes)
        return nb


def _affine_scatter(flat, rel, starts, every_ns, dt, k, w_pad):
    """(runmeta, consts) when the scatter slots are reconstructible
    on device from per-run scalars, else None.

    Requirements, each VERIFIED on the host against the actual arrays
    (vectorized int compares — far cheaper than the transfer they save):
    every run's times are affine (rel0 + j*stride), and the window
    ordinal follows one global phase: w == (rel - woff) // every.  Then
    the device recomputes flat = (rid*k + (rel - w*every)//dt)*w_pad + w
    exactly — any offset/edge subtlety just fails verification and the
    plan ships the explicit flat array instead."""
    n = len(rel)
    runs = len(starts)
    if n == 0 or runs == 0 or every_ns is None or not every_ns or not dt:
        return None
    lens = np.diff(np.append(starts, n))
    rel0 = rel[starts]
    stride = np.zeros(runs, np.int64)
    multi = lens > 1
    if multi.any():
        d = np.diff(rel)
        stride[multi] = d[starts[multi]]
    rid = np.repeat(np.arange(runs, dtype=np.int64), lens)
    j = np.arange(n, dtype=np.int64) - np.repeat(starts, lens)
    if not np.array_equal(rel0[rid] + j * stride[rid], rel):
        return None  # gaps / irregular spacing inside a run
    w = flat % w_pad
    # window phase: any valid woff satisfies woff + w*every <= rel <
    # woff + (w+1)*every for EVERY row; the supremum of that interval,
    # min(rel - w*every), is valid whenever any woff is — and the full
    # verification below rejects the rest
    woff = int((rel - w * every_ns).min())
    if not np.array_equal((rel - woff) // every_ns, w):
        return None
    r = (rel - w * every_ns) // dt
    if not np.array_equal((rid * k + r) * w_pad + w, flat):
        return None
    # (rel0, stride, start_row) per run — all DYNAMIC program inputs
    # (~24 bytes/run): baking row offsets in as program constants would
    # make every distinct series count a fresh multi-second compile
    runmeta = np.stack([rel0, stride, starts.astype(np.int64)], axis=1)
    return runmeta, np.array([woff], np.int64)


def combine_views(views):
    """Flatten per-column (blocks, segments, n_full) views into one
    block list plus the absolute row runs of the combined view over the
    combined decode (adjacent runs merged; None = identity).  Returns
    (blocks, runs|None, n_view, n_full)."""
    blocks: list = []
    runs = []
    base = 0
    n_view = 0
    for vb, segs, n_full in views:
        blocks.extend(vb)
        for a, b in np.asarray(segs, np.int64):
            a, b = int(a) + base, int(b) + base
            n_view += b - a
            if runs and runs[-1][1] == a:
                runs[-1][1] = b  # adjacent runs merge
            else:
                runs.append([a, b])
        base += int(n_full)
    if not runs or (len(runs) == 1 and runs[0] == [0, base]):
        return blocks, None, n_view, base  # identity (or empty) view
    return blocks, np.asarray(runs, np.int64), n_view, base


def build_grid_plan(views, flat, mask, shape, dtype, rel=None,
                    starts=None, every_ns=None, dt=None) -> GridPlan | None:
    """Plan the fused program for one frozen grid: `views` are the
    still-encoded value columns' (blocks, segments, n_full) triples in
    row order, `flat` the host-computed scatter slots (injective,
    < prod(shape)), `mask` the row validity.  `rel`/`starts`/
    `every_ns`/`dt` (the freeze's run layout) enable the per-run scatter
    reconstruction.  Returns None when the blocks are not
    device-decodable or the transfer would not beat the decoded grid —
    the caller host-decodes exactly as before."""
    if not active():
        return None
    blocks, viewruns, n_view, n_full = combine_views(views)
    dbs = classify(blocks)
    if dbs is None:
        note_fallback()
        return None
    if sum(b.n for b in dbs) != n_full or n_view != len(flat):
        note_fallback()
        return None  # defensive: blocks must cover the view exactly
    n = n_view
    sig, payload, scalars, aux32, aux8 = _pack_blocks(dbs)
    maskbits = None
    if mask is not None and not mask.all():
        maskbits = np.packbits(np.asarray(mask, np.bool_))
    affine = None
    if rel is not None and starts is not None:
        affine = _affine_scatter(flat, rel, np.asarray(starts),
                                 every_ns, dt, shape[1], shape[2])
    if affine is not None:
        runmeta, consts = affine
        flat32 = None
        nruns_affine = len(runmeta)
    else:
        runmeta, consts, nruns_affine = None, None, None
        flat32 = np.ascontiguousarray(flat, np.int32)
    geom = (sig, n, tuple(shape), np.dtype(dtype).str,
            maskbits is not None, nruns_affine,
            every_ns if nruns_affine else None,
            dt if nruns_affine else None,
            None if viewruns is None else len(viewruns))
    plan = GridPlan(geom, payload, scalars, aux32, aux8, viewruns,
                    flat32, runmeta, consts, maskbits, n)
    # cost gate, now the offload planner's zero-sample prior: with no
    # measured device samples this is the exact byte inequality (the
    # fused path must shrink the transfer below the decoded grid it
    # replaces — values + mask bytes per padded cell); once the planner
    # holds real wall samples for this geometry its decide() owns the
    # choice and the byte rule stands down
    if not offload.GLOBAL.gate_prior(
            "grid_decode", geom, plan.transfer_nbytes(),
            int(np.prod(shape)) * 9):
        note_fallback()
        return None
    return plan


def _plan_inputs(plan: GridPlan) -> list:
    """The program's positional inputs in the ONE canonical order shared
    with _grid_program: payload, scalars, [aux32, aux8], [viewruns],
    [flat | runmeta+consts], [maskbits]."""
    inputs = [plan.payload, plan.scalars]
    if plan.aux32 is not None:
        inputs.extend((plan.aux32, plan.aux8))
    if plan.viewruns is not None:
        inputs.append(plan.viewruns)
    if plan.flat is not None:
        inputs.append(plan.flat)
    else:
        inputs.extend((plan.runmeta, plan.consts))
    if plan.maskbits is not None:
        inputs.append(plan.maskbits)
    return inputs


def run_grid_plan(plan: GridPlan):
    """Execute the fused program: one H2D of the encoded inputs (site
    `device-decode`), then decode+scatter+reduce in a single jit program.
    Returns ({count,sum,mean,min,max} device arrays, vt, mt, flat) —
    vt/mt are the decoded grid buffers, ready for colcache device-tier
    retention and the ssd/selector kernels; flat is the device-resident
    scatter-slot vector (imat_from_flat builds the selector index grid
    from it without a host round-trip)."""
    import jax

    t0 = time.perf_counter_ns()
    inputs = _plan_inputs(plan)
    dev = [jax.device_put(a) for a in inputs]
    devobs.note_transfer("h2d", _XFER_SITE, plan.transfer_nbytes(),
                         (time.perf_counter_ns() - t0) / 1e9)
    _note_decode_stats(plan.geom[0], plan.n)
    geom = plan.geom
    pw_geo = (len(geom[0]), geom[1], geom[2], geom[3],
              geom[5] is not None)
    devobs.note_use("grid_decode_fused", pw_geo)
    offload.register_builder("grid_decode_fused", pw_geo,
                             lambda g=geom: _grid_program(g))
    fn = _grid_program(plan.geom)
    return devobs.launch(fn, dev, program="grid_decode_fused",
                         xfer_site=_XFER_SITE)


class MeshGridPlan:
    """One fused-decode plan per mesh shard, plus the global geometry
    the assembly step needs.  Each shard's GridPlan is self-contained
    (its own blocks, scatter slots rebased to the shard's row origin,
    per-shard affine runs), so the per-shard programs are exactly the
    single-device fused program — sharding is pure input partitioning,
    zero collectives."""

    __slots__ = ("mesh", "shards", "shape", "dtype_str", "n")

    def __init__(self, mesh, shards, shape, dtype_str, n):
        self.mesh = mesh
        self.shards = shards
        self.shape = shape
        self.dtype_str = dtype_str
        self.n = n

    def transfer_nbytes(self) -> int:
        return sum(p.transfer_nbytes() for p in self.shards)


@functools.lru_cache(maxsize=1024)
def _varint_scan(payload: bytes, n: int):
    """Host byte-structure + values of one varint block (cached like
    the gorilla scan): (ends, vals) where ends[i] is the byte index of
    value i's terminator byte and vals[i] its decoded int64 — mesh
    shards slice the byte stream at ends and seed the device cumsum
    with vals[lo-1]."""
    b = np.frombuffer(payload, np.uint8)
    ends = np.flatnonzero((b & 0x80) == 0).astype(np.int64)
    vals = encoding.decode_ints(
        struct.pack("<BI", encoding._T_VARINT, n) + payload)
    return ends, np.asarray(vals, np.int64)


@functools.lru_cache(maxsize=1024)
def _delta_vals(payload: bytes, n: int, first: int, step: int,
                width: int):
    """Host-decoded int64 values of one FOR-delta block (the exact
    decode_ints arithmetic: zero-extend widen, +step, wrapping cumsum,
    +first) — mesh shards reseed a slice's `first` from vals[lo]."""
    dt = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[width]
    d = np.frombuffer(payload[:(n - 1) * width], dtype=dt).astype(
        np.int64)
    out = np.empty(n, np.int64)
    out[0] = first
    if n > 1:
        np.cumsum(d + step, out=out[1:])
        out[1:] += first
    return out


def _wrap_i64(v) -> int:
    v = int(v) & 0xFFFFFFFFFFFFFFFF
    return v - (1 << 64) if v >= (1 << 63) else v


def _slice_block(db, lo: int, hi: int):
    """A DeviceBlock covering values [lo, hi) of `db`, shipping ONLY the
    payload bytes those values need — what lets a mesh shard whose span
    ends mid-block avoid duplicating the whole stream.  Stateful codecs
    get their seed carried in `first` (gorilla: the decoded bit pattern
    of value lo-1, XORed into the device scan; varint: the int64 value
    of lo-1, added to the device cumsum) and gorilla slices attach their
    precomputed structural scan as `aux` (the control bits are stateful,
    so a mid-stream payload cannot be re-scanned).  Returns None when
    the codec cannot slice (the caller falls back)."""
    n = hi - lo
    if lo == 0 and hi == db.n:
        return db
    if db.kind == "const":
        return encoding.DeviceBlock(
            "const", n, _wrap_i64(db.first + db.step * lo), db.step)
    if db.kind == "raw64":
        return encoding.DeviceBlock(
            "raw64", n, payload=db.payload[8 * lo:8 * hi])
    if db.kind == "strdict":
        w = db.width
        return encoding.DeviceBlock(
            "strdict", n, width=w, payload=db.payload[w * lo:w * hi],
            table=db.table)
    if db.kind == "delta":
        vals = _delta_vals(bytes(db.payload), db.n, db.first, db.step,
                           db.width)
        # payload keeps deltas for slice indices 1..n-1 = global
        # lo+1..hi-1; delta j lives at payload[(j-1)*width:]
        return encoding.DeviceBlock(
            "delta", n, int(vals[lo]), db.step, db.width,
            db.payload[lo * db.width:(hi - 1) * db.width])
    if db.kind == "varint":
        ends, vals = _varint_scan(bytes(db.payload), db.n)
        b0 = 0 if lo == 0 else int(ends[lo - 1]) + 1
        sub = db.payload[b0:int(ends[hi - 1]) + 1]
        seed = 0 if lo == 0 else int(vals[lo - 1])
        return encoding.DeviceBlock(
            "varint", n, seed, width=len(sub), payload=sub)
    if db.kind == "gorilla":
        scan = _gorilla_scan(bytes(db.payload), db.n)
        if scan is None:
            return None
        bitpos, mbits, shift, vals = scan
        mb = mbits[lo:hi].astype(np.int32)
        sel = mb > 0
        if sel.any():
            bp = bitpos[lo:hi].astype(np.int64)
            b0 = int(bp[sel].min()) >> 3
            b1 = (int((bp[sel] + mb[sel]).max()) + 7) >> 3
            sub = db.payload[b0:b1]
            bp = np.where(sel, bp - 8 * b0, 0).astype(np.int32)
        else:  # pure repeat run: every value IS the seed
            sub = b""
            bp = np.zeros(n, np.int32)
        seed = 0 if lo == 0 else _wrap_i64(vals[lo - 1])
        return encoding.DeviceBlock(
            "gorilla", n, seed, width=len(sub), payload=sub,
            aux=(bp, mbits[lo:hi].copy(), shift[lo:hi].copy()))
    return None


def build_mesh_grid_plan(views, flat, mask, shape, dtype, mesh,
                         rel=None, starts=None, every_ns=None,
                         dt=None) -> MeshGridPlan | None:
    """Partition one fused grid-decode plan by output row shard.  The
    scatter row ids (flat // (k*W_pad)) are non-decreasing — series runs
    are emitted in row order — so each mesh shard owns one CONTIGUOUS
    span of data rows, and that span maps to a contiguous span of view
    rows, blocks, and payload bytes: every per-shard input is a slice +
    rebase of the global plan's, built through the same build_grid_plan
    (same verification, same per-shard cost gate).  Returns None when
    the rows cannot split cleanly or any shard refuses — the caller
    falls back to the host scatter + shard_leading_axis exactly as
    before."""
    if not active():
        return None
    S_pad, k, w_pad = shape
    nsh = int(mesh.size)
    if S_pad % nsh:
        return None
    rows_per = S_pad // nsh
    blocks, viewruns, n_view, n_full = combine_views(views)
    dbs = classify(blocks)
    if dbs is None or sum(b.n for b in dbs) != n_full \
            or n_view != len(flat):
        note_fallback()
        return None
    flat = np.asarray(flat, np.int64)
    row_of = flat // (k * w_pad)
    if len(row_of) and (np.diff(row_of) < 0).any():
        note_fallback()
        return None  # rows out of order: no contiguous shard spans
    cuts = np.concatenate((
        [0], np.searchsorted(row_of, np.arange(1, nsh) * rows_per),
        [n_view])).astype(np.int64)
    mask = None if mask is None else np.asarray(mask, bool)
    rel = None if rel is None else np.asarray(rel, np.int64)
    starts = None if starts is None else np.asarray(starts, np.int64)
    # block offsets in FULL (concatenated-decode) coordinates, and the
    # view runs as explicit [lo, hi) full-coordinate spans
    boffs = np.cumsum([0] + [b.n for b in dbs]).astype(np.int64)
    vruns = (np.array([[0, n_full]], np.int64) if viewruns is None
             else np.asarray(viewruns, np.int64))
    run_len = vruns[:, 1] - vruns[:, 0]
    run_end_v = np.cumsum(run_len)       # view-coordinate run ends
    run_start_v = run_end_v - run_len
    shards = []
    for s in range(nsh):
        a, b = int(cuts[s]), int(cuts[s + 1])
        sub_views: list = []
        if a < b:
            i0 = int(np.searchsorted(run_end_v, a, side="right"))
            i1 = int(np.searchsorted(run_start_v, b, side="left"))
            lo_f = vruns[i0:i1, 0] + np.maximum(a - run_start_v[i0:i1], 0)
            hi_f = vruns[i0:i1, 0] + np.minimum(b - run_start_v[i0:i1],
                                                run_len[i0:i1])
            span_lo, span_hi = int(lo_f[0]), int(hi_f[-1])
            jmin = int(np.searchsorted(boffs, span_lo,
                                       side="right")) - 1
            jmax = int(np.searchsorted(boffs, span_hi - 1,
                                       side="right")) - 1
            # slice boundary blocks at VALUE granularity — a block
            # spanning several shards must not ship whole to each (the
            # duplicated payload+aux would trip every shard's cost
            # gate); _slice_block reseeds the stateful codecs
            sub_blocks = []
            for j in range(jmin, jmax + 1):
                o = int(boffs[j])
                sb = _slice_block(dbs[j], max(span_lo - o, 0),
                                  min(span_hi, int(boffs[j + 1])) - o)
                if sb is None:
                    note_fallback()
                    return None
                sub_blocks.append(sb)
            segs = np.stack([lo_f - span_lo, hi_f - span_lo], axis=1)
            sub_views = [(sub_blocks, segs, span_hi - span_lo)]
        plan = build_grid_plan(
            sub_views, flat[a:b] - s * rows_per * k * w_pad,
            None if mask is None else mask[a:b],
            (rows_per, k, w_pad), dtype,
            rel=None if rel is None else rel[a:b],
            starts=None if starts is None else
            starts[(starts >= a) & (starts < b)] - a,
            every_ns=every_ns, dt=dt)
        if plan is None:
            note_fallback()
            return None
        shards.append(plan)
    return MeshGridPlan(mesh, shards, tuple(shape), np.dtype(dtype).str,
                        n_view)


def run_mesh_grid_plan(mplan: MeshGridPlan):
    """Execute the per-shard fused programs and assemble the results as
    NamedSharding global arrays partitioned on the row axis.  One
    explicit device_put per input per shard (each shard's encoded bytes
    land only on its own device — the explicit per-shard form of the
    row-sharded layout, no replicated intermediate), then the SAME
    cached per-geometry programs as the single-device path, then a
    zero-copy global-array assembly.  Returns (stats, vt, mt, None) —
    vt/mt ready for the mesh-aware colcache device tier and the GSPMD
    ssd/selector kernels."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = mplan.mesh
    devices = list(mesh.devices.flat)
    t0 = time.perf_counter_ns()
    nbytes = 0
    shard_in = []
    for plan, dev in zip(mplan.shards, devices):
        shard_in.append([jax.device_put(a, dev)
                         for a in _plan_inputs(plan)])
        nbytes += plan.transfer_nbytes()
    # every byte here is mesh-cold H2D a warm repeat must NOT pay (the
    # sharded colcache tier retains vt/mt) — the same warm-flat contract
    # shard_leading_axis's counter carries for the dense path
    _STATS.incr("device", "mesh_h2d_bytes", nbytes)
    devobs.note_transfer("h2d", _XFER_SITE, nbytes,
                         (time.perf_counter_ns() - t0) / 1e9, mesh=True)
    outs = []
    for plan, ins in zip(mplan.shards, shard_in):
        _note_decode_stats(plan.geom[0], plan.n)
        outs.append(devobs.launch(_grid_program(plan.geom), ins,
                                  program="grid_decode_fused",
                                  xfer_site=_XFER_SITE))
    ax = tuple(mesh.axis_names)

    def assemble(pieces):
        gshape = (mplan.shape[0],) + tuple(pieces[0].shape[1:])
        spec = PartitionSpec(ax, *([None] * (pieces[0].ndim - 1)))
        return jax.make_array_from_single_device_arrays(
            gshape, NamedSharding(mesh, spec), list(pieces))

    vt = assemble([o[1] for o in outs])
    mt = assemble([o[2] for o in outs])
    stats = {key: assemble([o[0][key] for o in outs])
             for key in outs[0][0]}
    return stats, vt, mt, None


def imat_from_flat(flat_dev, shape):
    """Selector index grid (sample ordinal per grid slot) from the
    device-resident scatter slots a fused decode left behind — replaces
    the host imat build + its full-grid transfer on the cold selector
    path."""
    return _imat_program(int(flat_dev.shape[0]), tuple(shape))(flat_dev)


@functools.lru_cache(maxsize=256)
def _imat_program(n: int, shape):
    import jax
    import jax.numpy as jnp

    devobs.note_compile("grid_decode_imat", (n, shape))
    cells = int(np.prod(shape))

    def run(flat):
        return jnp.zeros(cells, jnp.int32).at[flat].set(
            jnp.arange(n, dtype=jnp.int32),
            unique_indices=True).reshape(shape)

    return jax.jit(run)


def decode_to_device(blocks, dtype=None):
    """Standalone device decode of raw block buffers -> one device value
    vector (int64/float64, or `dtype` when given).  The non-fused entry
    point: tests assert bit-identity against the host decoders with it,
    and column-shaped consumers can device_put encoded bytes directly."""
    import jax

    dbs = classify(blocks)
    if dbs is None:
        raise ValueError("blocks are not device-decodable")
    out_dtype = np.dtype(dtype) if dtype is not None else (
        np.dtype(np.float64)
        if any(b.kind in ("raw64", "gorilla") for b in dbs)
        else np.dtype(np.int64))
    sig, payload, scalars, aux32, aux8 = _pack_blocks(dbs)
    host_in = [payload, scalars]
    if aux32 is not None:
        host_in.extend((aux32, aux8))
    t0 = time.perf_counter_ns()
    dev = [jax.device_put(a) for a in host_in]
    devobs.note_transfer(
        "h2d", _XFER_SITE, sum(int(a.nbytes) for a in host_in),
        (time.perf_counter_ns() - t0) / 1e9)
    return _decode_program(sig, out_dtype.str)(*dev)


def materialize_enc(enc) -> np.ndarray:
    """Host materialization of a (ftype, blocks, segments, slices)
    encoded-column descriptor into the concatenated f64 sample vector —
    the bit-identical fallback for consumers that need host values
    (dense prom kernels, mesh sharding)."""
    ftype, blocks, segments, slices = enc
    d = encoding.decode_value_blocks(ftype, list(blocks)).astype(
        np.float64)
    if segments is not None:
        d = (np.concatenate([d[a:b] for a, b in segments])
             if len(segments) else d[:0])
    if not slices:
        return np.empty(0, np.float64)
    if len(slices) == 1:
        lo, hi = slices[0]
        return d[lo:hi]
    return np.concatenate([d[lo:hi] for lo, hi in slices])


def decode_rows_matrix(enc, shape, dtype):
    """Decode raw blocks ON device and lay the per-series sample slices
    into a zero-padded (S, N) row matrix — the PromQL tiled kernels'
    value matrix without the padded-f64 H2D (the transfer is the raw
    payload + two ints per series).  `enc` is the (ftype, blocks,
    segments, slices) descriptor (slices in VIEW coordinates).  Returns
    the device matrix, or None when the blocks are not device-decodable
    (caller host-materializes, bit-identically)."""
    import jax

    if not active():
        return None
    ftype, blocks, segments, slices = enc
    dbs = classify(list(blocks))
    if dbs is None:
        note_fallback()
        return None
    n_full = sum(b.n for b in dbs)
    if segments is None:
        viewruns, n_view = None, n_full
    else:
        segments = np.asarray(segments, np.int64).reshape(-1, 2)
        viewruns = segments
        n_view = int((segments[:, 1] - segments[:, 0]).sum())
        if len(segments) and (segments[:, 0] < 0).any() \
                or len(segments) and (segments[:, 1] > n_full).any():
            note_fallback()
            return None
    S, N = shape
    lo = np.array([s[0] for s in slices], np.int64)
    ln = np.array([s[1] - s[0] for s in slices], np.int64)
    if len(slices) != S or (ln > N).any() or (lo < 0).any() \
            or (lo + ln > n_view).any():
        note_fallback()
        return None
    sig, payload, scalars, aux32, aux8 = _pack_blocks(dbs)
    host_in = [payload, scalars]
    if aux32 is not None:
        host_in.extend((aux32, aux8))
    host_in.extend((lo, ln))
    if viewruns is not None:
        host_in.append(viewruns)
    # cost gate (the encoded transfer must beat the padded value matrix
    # it replaces — whole-block payloads can exceed a heavily trimmed
    # view; raw64 floats have no width compression to amortize it),
    # serving as the offload planner's zero-sample prior: measured
    # device samples for this geometry retire the byte rule
    rows_geo = (len(sig), n_view, (S, N))
    if not offload.GLOBAL.gate_prior(
            "prom_decode_rows", rows_geo,
            sum(int(a.nbytes) for a in host_in),
            S * N * np.dtype(dtype).itemsize):
        note_fallback()
        return None
    t0 = time.perf_counter_ns()
    dev = [jax.device_put(a) for a in host_in]
    devobs.note_transfer(
        "h2d", _XFER_SITE, sum(int(a.nbytes) for a in host_in),
        (time.perf_counter_ns() - t0) / 1e9)
    _note_decode_stats(sig, n_view)
    devobs.note_use("prom_decode_rows", rows_geo)
    pw = (sig, n_view, (S, N), np.dtype(dtype).str,
          None if viewruns is None else len(viewruns))
    offload.register_builder("prom_decode_rows", rows_geo,
                             lambda a=pw: _rows_program(*a))
    fn = _rows_program(sig, n_view, (S, N), np.dtype(dtype).str,
                       None if viewruns is None else len(viewruns))
    return devobs.launch(fn, dev, program="prom_decode_rows",
                         xfer_site=_XFER_SITE)


@functools.lru_cache(maxsize=256)
def _rows_program(sig, n: int, shape, dtype_str, nruns):
    import jax
    import jax.numpy as jnp

    devobs.note_compile("prom_decode_rows", (len(sig), n, shape))
    S, N = shape
    out_dt = jnp.dtype(dtype_str)
    decode = _decode_expr(sig, dtype_str)
    has_aux = _sig_has_aux(sig)

    def run(payload, scalars, *rest):
        if n == 0:
            return jnp.zeros((S, N), out_dt)
        if has_aux:
            aux32, aux8 = rest[0], rest[1]
            rest = rest[2:]
        else:
            aux32 = aux8 = None
        lo, ln = rest[0], rest[1]
        viewruns = rest[2] if len(rest) > 2 else None
        vals = decode(payload, scalars, aux32, aux8)
        if nruns is not None:
            vals = _view_gather(vals, viewruns, n)
        col = jnp.arange(N, dtype=jnp.int64)[None, :]
        idx = jnp.clip(lo[:, None] + col, 0, n - 1)
        m = col < ln[:, None]
        return jnp.where(m, vals[idx], jnp.zeros((), out_dt))

    return jax.jit(run)


# -- jit program construction -------------------------------------------------


def _view_gather(vals_full, viewruns, n_view: int):
    """Gather a column VIEW (absolute [lo, hi) row runs) out of the
    fully-decoded block concatenation, on device.  `viewruns` is the
    dynamic (k, 2) run array; `n_view` is static."""
    import jax.numpy as jnp

    run_len = viewruns[:, 1] - viewruns[:, 0]
    ends = jnp.cumsum(run_len)
    pos = jnp.arange(n_view, dtype=jnp.int64)
    rid = jnp.searchsorted(ends, pos, side="right")
    start_out = ends - run_len
    return vals_full[viewruns[rid, 0] + pos - start_out[rid]]


def _widen(raw, width: int, cnt: int):
    """(cnt*width,) LE bytes -> (cnt,) int64, matching the host
    frombuffer(...).astype(int64) exactly (zero-extend below 8 bytes,
    bit-reinterpretation at 8)."""
    import jax
    import jax.numpy as jnp

    if width == 1:
        return raw.astype(jnp.int64)
    if width == 8:
        # bitcast, not convert: uint64 values >= 2^63 must wrap to
        # negative int64 exactly like numpy's astype
        return jax.lax.bitcast_convert_type(
            raw.reshape(cnt, 8), jnp.int64)
    dt = {2: jnp.uint16, 4: jnp.uint32}[width]
    return jax.lax.bitcast_convert_type(
        raw.reshape(cnt, width), dt).astype(jnp.int64)


def _unpack_bits(raw, nbytes: int):
    """(nbytes,) uint8 -> (nbytes*8,) int32 bits, MSB-first per byte
    (matches np.unpackbits exactly)."""
    import jax.numpy as jnp

    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    return ((raw[:, None] >> shifts) & jnp.uint8(1)).astype(
        jnp.int32).reshape(nbytes * 8)


def _gorilla_piece(raw, m: int, bitpos, mb_sh, bn: int, seed):
    """Data-parallel gorilla reconstruction from the payload bytes plus
    the host structural scan's aux vectors.  Each value's 64-bit window
    starting at bitpos is gathered from the unpacked bit vector; the top
    mbits of the window, shifted left by its trailing-zero count, is the
    value's XOR delta (repeats have mbits=0 -> delta 0; value 0 has
    mbits=64 -> its raw bits).  An associative XOR prefix scan, XORed
    with `seed` (the running value BEFORE this slice: 0 for whole
    blocks, vals[lo-1] for mesh-shard slices), then yields every decoded
    word in parallel — bit-identical to the host's sequential prev^delta
    walk, NaN/±0.0 included, because XOR carries no arithmetic."""
    import jax
    import jax.numpy as jnp

    if m == 0:
        # all-repeat slice: no meaningful bits shipped; every value is
        # the seed (the gather below reads only masked-out zeros)
        bits = jnp.zeros(64, jnp.int32)
    else:
        bits = jnp.concatenate(
            [_unpack_bits(raw, m), jnp.zeros(64, jnp.int32)])
    g = bitpos[:, None].astype(jnp.int32) + jnp.arange(
        64, dtype=jnp.int32)
    bv = bits[g].astype(jnp.uint64)  # (bn, 64)
    w64 = jnp.sum(bv << jnp.arange(63, -1, -1, dtype=jnp.uint64),
                  axis=1, dtype=jnp.uint64)
    pair = mb_sh.reshape(bn, 2)
    mb = pair[:, 0].astype(jnp.uint64)
    sh = pair[:, 1].astype(jnp.uint64)
    nz = mb > 0
    s1 = jnp.where(nz, jnp.uint64(64) - mb, jnp.uint64(0))
    xor = jnp.where(nz, (w64 >> s1) << sh, jnp.uint64(0))
    acc = jax.lax.associative_scan(jnp.bitwise_xor, xor)
    return jax.lax.bitcast_convert_type(acc ^ seed, jnp.float64)


def _varint_piece(raw, m: int, bn: int):
    """Data-parallel LEB128 delta+zigzag decode: terminator bytes (high
    bit clear) close each varint, so a cumulative count assigns every
    byte its value id; a segmented shift/or (the 7-bit groups occupy
    disjoint bit ranges, so scatter-add IS or) rebuilds each unsigned
    word; zigzag then a wrapping int64 cumsum reproduce the host's
    mod-2^64 arithmetic exactly (the first value is a delta from 0)."""
    import jax.numpy as jnp

    ends = (raw & jnp.uint8(0x80)) == 0
    e64 = ends.astype(jnp.int64)
    vid = jnp.cumsum(e64) - e64
    pos = jnp.arange(m, dtype=jnp.int64)
    is_start = jnp.concatenate([jnp.ones(1, bool), ends[:-1]])
    starts = jnp.zeros(bn, jnp.int64).at[vid].add(
        jnp.where(is_start, pos, 0), unique_indices=False)
    off7 = ((pos - starts[vid]) * 7).astype(jnp.uint64)
    groups = (raw.astype(jnp.uint64) & jnp.uint64(0x7F)) << off7
    u = jnp.zeros(bn, jnp.uint64).at[vid].add(groups)
    d = (u >> jnp.uint64(1)).astype(jnp.int64) \
        ^ -((u & jnp.uint64(1)).astype(jnp.int64))
    return jnp.cumsum(d)


def _decode_expr(sig, dtype_str):
    """The unrolled per-block decode, shared by the standalone and fused
    programs.  Returns a traced fn (payload, scalars, aux32, aux8) ->
    (n,) values in `dtype_str` (aux args are None unless the signature
    has gorilla blocks).  Offsets are static (they come from the
    signature), so every slice lowers to a static-slice."""
    import jax
    import jax.numpy as jnp

    out_dt = jnp.dtype(dtype_str)

    def decode(payload, scalars, aux32=None, aux8=None):
        pieces = []
        off = 0
        aoff = 0
        for i, (kind, bn, width) in enumerate(sig):
            if bn == 0:
                continue
            first = scalars[i, 0]
            step = scalars[i, 1]
            if kind == "const":
                piece = first + step * jnp.arange(bn, dtype=jnp.int64)
            elif kind == "delta":
                m = (bn - 1) * width
                raw = jax.lax.slice(payload, (off,), (off + m,))
                off += m
                d = _widen(raw, width, bn - 1) + step
                piece = jnp.concatenate(
                    [first[None], first + jnp.cumsum(d)])
            elif kind == "raw64":
                m = 8 * bn
                raw = jax.lax.slice(payload, (off,), (off + m,))
                off += m
                piece = jax.lax.bitcast_convert_type(
                    raw.reshape(bn, 8), jnp.float64)
            elif kind == "gorilla":
                m = width  # payload byte length rides in the signature
                raw = jax.lax.slice(payload, (off,), (off + m,))
                off += m
                bitpos = jax.lax.slice(aux32, (aoff,), (aoff + bn,))
                mb_sh = jax.lax.slice(
                    aux8, (2 * aoff,), (2 * (aoff + bn),))
                aoff += bn
                # scalar 0 carries the slice seed (decoded bit pattern
                # of the value preceding the slice; 0 for whole blocks)
                seed = jax.lax.bitcast_convert_type(first, jnp.uint64)
                piece = _gorilla_piece(raw, m, bitpos, mb_sh, bn, seed)
            elif kind == "varint":
                m = width
                raw = jax.lax.slice(payload, (off,), (off + m,))
                off += m
                # `first` seeds mid-stream slices (wrapping int64 add,
                # like the host's mod-2^64 walk); 0 for whole blocks
                piece = first + _varint_piece(raw, m, bn)
            else:  # strdict: min-width indices, table stays host-side
                m = bn * width
                raw = jax.lax.slice(payload, (off,), (off + m,))
                off += m
                piece = _widen(raw, width, bn)
            pieces.append(piece.astype(out_dt))
        if not pieces:
            return jnp.zeros((0,), out_dt)
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    return decode


@functools.lru_cache(maxsize=256)
def _decode_program(sig, dtype_str):
    import jax

    devobs.note_compile("device_decode",
                        (len(sig), sum(b[1] for b in sig), dtype_str))
    return jax.jit(_decode_expr(sig, dtype_str))


@functools.lru_cache(maxsize=256)
def _grid_program(geom):
    """One fused program per static geometry: decode the blocks, scatter
    values+mask into the padded grid, and reduce the basic window stats
    — the compressed-bytes->decode->group->reduce pipeline of the
    data-path-fusion literature as a single XLA program."""
    import jax
    import jax.numpy as jnp

    (sig, n, shape, dtype_str, has_mask, nruns_affine, every_ns, dt,
     nruns) = geom
    devobs.note_compile("grid_decode_fused",
                        (len(sig), n, shape, dtype_str,
                         nruns_affine is not None))
    out_dt = jnp.dtype(dtype_str)
    cells = int(np.prod(shape))
    k, w_pad = shape[1], shape[2]
    decode = _decode_expr(sig, dtype_str)
    has_aux = _sig_has_aux(sig)

    def scatter_slots(args):
        if nruns_affine is None:
            return args[0], args[1:]  # explicit flat
        # runmeta rows: (rel0, stride, start_row) — all dynamic, so the
        # program is free of run-count-sized constants
        runmeta, consts = args[0], args[1]
        starts_c = runmeta[:, 2]
        ar = jnp.arange(n, dtype=jnp.int64)
        rid = jnp.searchsorted(starts_c, ar, side="right") - 1
        j = ar - starts_c[rid]
        rel = runmeta[:, 0][rid] + j * runmeta[:, 1][rid]
        w = (rel - consts[0]) // every_ns
        r = (rel - w * every_ns) // dt
        return ((rid * k + r) * w_pad + w).astype(jnp.int32), args[2:]

    def run(payload, scalars, *rest):
        from opengemini_tpu.ops import segment as seg

        if has_aux:
            aux32, aux8 = rest[0], rest[1]
            rest = rest[2:]
        else:
            aux32 = aux8 = None
        vals = decode(payload, scalars, aux32, aux8)
        if nruns is not None:
            vals = _view_gather(vals, rest[0], n)
            rest = rest[1:]
        flat, rest2 = scatter_slots(rest)
        vt = jnp.zeros(cells, out_dt).at[flat].set(
            vals, unique_indices=True).reshape(shape)
        if has_mask:
            shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
            bits = (rest2[0][:, None] >> shifts) & jnp.uint8(1)
            mrow = bits.reshape(-1)[:n].astype(bool)
        else:
            mrow = jnp.ones((n,), bool)
        mt = jnp.zeros(cells, bool).at[flat].set(
            mrow, unique_indices=True).reshape(shape)
        stats = seg.grid_window_agg_t(vt, mt)
        return stats, vt, mt, flat

    return jax.jit(run)

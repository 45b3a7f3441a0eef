"""Decode on device (ISSUE 15): device-profile encodings, the lazy
EncodedColumn view algebra, the fused device decoder, and end-to-end
cold-scan bit-identity between `OGT_DEVICE_DECODE=0` (host path) and
`=1` (compressed bytes -> device -> decode -> reduce).

Everything here runs on the CPU backend with x64 on (tests/conftest.py),
which is exactly the regime the device decoder requires for
bit-identity — equality assertions are exact, never approximate.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from opengemini_tpu.ops import device_decode as dd  # noqa: E402
from opengemini_tpu.record import EncodedColumn, FieldType  # noqa: E402
from opengemini_tpu.storage import encoding as enc  # noqa: E402

NS = 1_000_000_000
BASE = 1_700_000_000


@pytest.fixture
def profile_on(monkeypatch):
    monkeypatch.setenv("OGT_DEVICE_PROFILE", "1")


# -- encoding round-trip fuzz -------------------------------------------------


def _int_cases(rng):
    """Int columns straddling every adaptive boundary: constant stride
    (_T_CONST), repetitive deltas (varint+zlib wins), wide random deltas
    (FOR wins), each delta width, singletons, empties."""
    yield np.empty(0, np.int64)
    yield np.array([42], np.int64)
    yield np.arange(0, 5000, 7, dtype=np.int64)              # const stride
    yield np.cumsum(rng.integers(0, 3, 400)).astype(np.int64)    # repetitive
    for scale in (200, 40_000, 2**20, 2**44):                # widths 1,2,4,8
        yield np.cumsum(rng.integers(0, scale, 300)).astype(np.int64)
    yield rng.integers(-2**62, 2**62, 257).astype(np.int64)  # wide/wrap
    yield np.array([5, 5, 5, 5, 9], np.int64)                # dup then break


def _float_cases(rng):
    """Float columns straddling gorilla-vs-zlib: smooth series (gorilla
    wins), constant (zlib wins), random, NaN/inf payloads, empties."""
    yield np.empty(0, np.float64)
    yield np.repeat(3.25, 300)
    yield np.cumsum(rng.standard_normal(400)) + 50.0
    yield rng.standard_normal(513) * 1e18
    v = rng.standard_normal(64)
    v[::7] = np.nan
    v[3] = np.inf
    yield v


@pytest.mark.parametrize("profile", ["0", "1"])
def test_encoding_roundtrip_fuzz(monkeypatch, profile, rng):
    monkeypatch.setenv("OGT_DEVICE_PROFILE", profile)
    for v in _int_cases(rng):
        buf = enc.encode_ints(v)
        np.testing.assert_array_equal(enc.decode_ints(buf), v)
    for v in _float_cases(rng):
        buf = enc.encode_floats(v)
        got = enc.decode_floats(buf)
        np.testing.assert_array_equal(
            got.view(np.uint64), v.view(np.uint64))  # NaN-exact


def test_profile_blocks_cross_readable(monkeypatch, rng):
    """Profile-written blocks decode with the profile off (old reader,
    new file) and plain blocks decode with it on (new reader, old
    file) — the format change is reader-transparent."""
    v_i = np.cumsum(rng.integers(0, 999, 500)).astype(np.int64)
    v_f = rng.standard_normal(500)
    monkeypatch.setenv("OGT_DEVICE_PROFILE", "1")
    bi, bf = enc.encode_ints(v_i), enc.encode_floats(v_f)
    assert enc.device_block(bi) is not None
    assert enc.device_block(bf) is not None
    monkeypatch.setenv("OGT_DEVICE_PROFILE", "0")
    np.testing.assert_array_equal(enc.decode_ints(bi), v_i)
    np.testing.assert_array_equal(enc.decode_floats(bf), v_f)
    bi2, bf2 = enc.encode_ints(v_i), enc.encode_floats(v_f)
    assert enc.device_block(bf2) is None  # zlib/gorilla: host-only
    monkeypatch.setenv("OGT_DEVICE_PROFILE", "1")
    np.testing.assert_array_equal(enc.decode_ints(bi2), v_i)
    np.testing.assert_array_equal(enc.decode_floats(bf2), v_f)


def test_device_block_classification(profile_on, rng):
    assert enc.device_block(
        enc.encode_ints(np.arange(100, dtype=np.int64))).kind == "const"
    db = enc.device_block(enc.encode_ints(
        np.cumsum(rng.integers(0, 200, 64)).astype(np.int64)))
    assert db.kind == "delta" and db.width == 1
    assert enc.device_block(
        enc.encode_floats(rng.standard_normal(32))).kind == "raw64"
    # bool/string blocks never classify
    assert enc.device_block(
        enc.encode_bools(np.ones(8, np.bool_))) is None


# -- device decoder vs host oracle -------------------------------------------


def test_decode_to_device_bit_identical(profile_on, rng):
    blocks, want = [], []
    for scale in (100, 50_000, 2**21, 2**45):
        v = np.cumsum(rng.integers(0, scale, 300)).astype(np.int64)
        b = enc.encode_ints(v)
        blocks.append(b)
        want.append(enc.decode_ints(b))
    blocks.append(enc.encode_ints(np.arange(0, 900, 9, dtype=np.int64)))
    want.append(np.arange(0, 900, 9, dtype=np.int64))
    got = np.asarray(dd.decode_to_device(blocks))
    np.testing.assert_array_equal(got, np.concatenate(want))
    fb = [enc.encode_floats(rng.standard_normal(257))]
    np.testing.assert_array_equal(
        np.asarray(dd.decode_to_device(fb)),
        enc.decode_floats(fb[0]))


# -- EncodedColumn view algebra ----------------------------------------------


def _enc_col(rng, n=500, scale=1000):
    v = np.cumsum(rng.integers(0, scale, n)).astype(np.int64)
    buf = enc.encode_ints(v)
    col = EncodedColumn(FieldType.INT, [buf], np.ones(n, np.bool_),
                        enc.decode_value_blocks)
    return col, v


def test_encoded_column_lazy_and_take(profile_on, rng):
    col, v = _enc_col(rng)
    assert not col.is_decoded
    # strictly-increasing takes stay encoded and compose
    idx = np.flatnonzero(rng.random(len(v)) < 0.5)
    t1 = col.take(idx)
    assert isinstance(t1, EncodedColumn) and not t1.is_decoded
    sub = np.arange(3, len(idx) - 2)
    t2 = t1.take(sub)
    assert isinstance(t2, EncodedColumn) and not t2.is_decoded
    # composing views alone never decodes anything
    assert not col.is_decoded
    np.testing.assert_array_equal(t2.values, v[idx][sub])
    # materializing a view decodes ONCE through the shared root (the
    # cache-resident source column): every later view of the same
    # blocks slices the memoized decode instead of re-decoding
    assert col.is_decoded
    # non-monotone takes decode (bit-identically) — via the source
    t3 = col.take(idx[::-1])
    np.testing.assert_array_equal(t3.values, v[idx[::-1]])
    np.testing.assert_array_equal(col.values, v)
    # a take of a DECODED source keeps the blocks attached (the device
    # route stays available on warm repeats) and carries the row subset
    t4 = col.take(idx)
    assert isinstance(t4, EncodedColumn) and t4.is_decoded and t4.blocks
    np.testing.assert_array_equal(t4.values, v[idx])


def test_encoded_column_concat_views(profile_on, rng):
    a, va = _enc_col(rng, 300)
    b, vb = _enc_col(rng, 200)
    a2 = a.take(np.arange(50, 250))
    c = a2.concat(b)
    assert isinstance(c, EncodedColumn) and not c.is_decoded
    np.testing.assert_array_equal(
        c.values, np.concatenate([va[50:250], vb]))


def test_affine_scatter_rejects_irregular(profile_on, rng):
    every, dt, k, w_pad = 60 * NS, 10 * NS, 6, 24
    rel = np.tile(np.arange(100) * dt, 3)
    starts = np.arange(3) * 100
    rid = np.repeat(np.arange(3), 100)
    w = rel // every
    flat = (rid * k + (rel - w * every) // dt) * w_pad + w
    assert dd._affine_scatter(flat, rel, starts, every, dt, k, w_pad) \
        is not None
    rel2 = rel.copy()
    rel2[57] += 1  # one irregular sample: must fall back to explicit flat
    assert dd._affine_scatter(flat, rel2, starts, every, dt, k, w_pad) \
        is None


# -- end-to-end cold-scan bit-identity ---------------------------------------


@pytest.fixture
def env(tmp_path, profile_on):
    from opengemini_tpu.query.executor import Executor
    from opengemini_tpu.storage.engine import Engine

    e = Engine(str(tmp_path / "data"), sync_wal=False)
    e.create_database("db")
    yield e, Executor(e)
    e.close()


def _write_random_shard(e, rng, hosts=70, points=120):
    """Randomized shard contents: regular int and float fields, a
    sparse field (validity masks), and a handful of irregular rows so
    some series refuse the grid."""
    lines = []
    for h in range(hosts):
        step = int(rng.choice([10, 10, 10, 20]))
        for p in range(points):
            t = (BASE + p * step) * NS
            f = f"cpu,host=h{h} vi={int(rng.integers(0, 250))}i," \
                f"vf={float(rng.standard_normal()):.6f}"
            if rng.random() < 0.3:
                f += f",sparse={float(rng.random()):.4f}"
            lines.append(f"{f} {t}")
    e.write_lines("db", "\n".join(lines))
    e.flush_all()


QUERIES = [
    "SELECT count(vi), min(vi), max(vi) FROM cpu WHERE time >= {lo} AND "
    "time < {hi} GROUP BY time(1m)",
    "SELECT mean(vf), sum(vf), stddev(vf), first(vf), last(vf) FROM cpu "
    "WHERE time >= {lo} AND time < {hi} GROUP BY time(90s), host",
    "SELECT count(sparse), max(sparse) FROM cpu WHERE time >= {lo} AND "
    "time < {hi} GROUP BY time(2m)",
    # partial range: exercises the encoded-view time trim
    "SELECT mean(vf), count(vi) FROM cpu WHERE time >= {plo} AND "
    "time < {phi} GROUP BY time(1m)",
]


def test_cold_scan_bit_identity_device_vs_host(env, monkeypatch, rng):
    from opengemini_tpu.storage import colcache

    e, ex = env
    _write_random_shard(e, rng)
    lo, hi = BASE * NS, (BASE + 120 * 20 + 60) * NS
    plo, phi = (BASE + 300) * NS, (BASE + 1500) * NS
    for q in QUERIES:
        qq = q.format(lo=lo, hi=hi, plo=plo, phi=phi)
        out = {}
        for dec in ("0", "1"):
            monkeypatch.setenv("OGT_DEVICE_DECODE", dec)
            colcache.GLOBAL.clear()
            ex._inc_cache.clear()
            out[dec] = ex.execute(qq, db="db")
        assert json.dumps(out["0"], sort_keys=True) == \
            json.dumps(out["1"], sort_keys=True), qq


def test_cold_scan_engages_device_decode(env, monkeypatch, rng):
    """The int-field cold scan must actually take the fused path (not
    silently fall back) and transfer fewer H2D bytes than the host
    path's decoded grid."""
    from opengemini_tpu.storage import colcache
    from opengemini_tpu.utils import devobs
    from opengemini_tpu.utils.stats import GLOBAL as STATS

    e, ex = env
    _write_random_shard(e, rng, hosts=70, points=100)
    monkeypatch.setenv("OGT_COLCACHE_DEVICE", "1")
    colcache.GLOBAL.configure(device=True)
    q = ("SELECT count(vi), min(vi), max(vi) FROM cpu WHERE time >= %d "
         "AND time < %d GROUP BY time(1m)" % (BASE * NS,
                                              (BASE + 4000) * NS))

    def h2d():
        return STATS.counters("device").get("h2d_bytes_total", 0)

    def run(dec):
        monkeypatch.setenv("OGT_DEVICE_DECODE", dec)
        colcache.GLOBAL.clear()
        ex._inc_cache.clear()
        before, fused = h2d(), STATS.counters("executor").get(
            "grid_decode_fused", 0)
        out = ex.execute(q, db="db")
        return out, h2d() - before, STATS.counters("executor").get(
            "grid_decode_fused", 0) - fused

    out_host, bytes_host, _ = run("0")
    out_dev, bytes_dev, fused = run("1")
    assert json.dumps(out_host) == json.dumps(out_dev)
    assert fused >= 1, "fused decode path did not engage"
    assert 0 < bytes_dev < bytes_host, (bytes_dev, bytes_host)
    # warm repeats reuse every program: the recompile tripwire stays 0
    devobs.mark_warm()
    try:
        for _ in range(3):
            ex._inc_cache.clear()
            assert json.dumps(ex.execute(q, db="db")) == json.dumps(out_dev)
        assert devobs.compiles_since_warm() == 0
    finally:
        devobs.clear_warm()
    colcache.GLOBAL.configure(device=False)


def test_prom_tiled_device_decode_identity(env, monkeypatch, rng):
    """PromQL tiled path: forced traced kernels with device decode on
    vs host kernels — identical JSON output."""
    from opengemini_tpu.promql.engine import PromEngine
    from opengemini_tpu.storage import colcache

    e, _ex = env
    lines = []
    for h in range(70):
        for p in range(150):
            lines.append(
                f"req_total,host=h{h} value={h * 997 + p * 3}i "
                f"{(BASE + p * 10) * NS}")
    e.write_lines("db", "\n".join(lines))
    e.flush_all()
    pe = PromEngine(e)

    def q():
        colcache.GLOBAL.clear()
        return pe.query_range("rate(req_total[5m])", BASE + 600,
                              BASE + 1400, 30, db="db")

    monkeypatch.setenv("OGT_PROM_HOST_KERNELS", "1")
    want = q()
    monkeypatch.setenv("OGT_PROM_HOST_KERNELS", "0")
    monkeypatch.setenv("OGT_DEVICE_DECODE", "1")
    got = q()
    monkeypatch.setenv("OGT_DEVICE_DECODE", "0")
    got_host = q()
    assert json.dumps(got, sort_keys=True) == \
        json.dumps(got_host, sort_keys=True)
    assert json.dumps(got, sort_keys=True) == \
        json.dumps(want, sort_keys=True)


# -- full codec family: gorilla / varint / strdict (ISSUE 16) ----------------


def _gorilla_cases(rng):
    """Compressible float streams the profile writer sends to the native
    gorilla codec: quantized values, long repeats, NaN/±0.0/inf payloads
    — XOR carries no arithmetic, so device decode must be NaN-exact."""
    yield np.round(np.cumsum(rng.standard_normal(300)), 1)
    yield np.repeat(rng.standard_normal(12), 40)
    v = np.round(np.cumsum(rng.standard_normal(256)), 2)
    v[::11] = np.nan
    v[5] = np.inf
    v[6] = -np.inf
    v[7:9] = [0.0, -0.0]
    yield v
    yield np.zeros(200)
    yield np.array([3.5])


def _varint_cases(rng):
    """Int streams the profile writer sends to the native varint-delta
    codec: small deltas with occasional wide outliers, sign flips,
    int64-boundary values (zigzag + mod-2^64 cumsum on device)."""
    v = np.cumsum(rng.integers(-3, 4, 400)).astype(np.int64)
    v[::97] += 2**40
    yield v
    yield rng.integers(-5, 6, 513).astype(np.int64).cumsum()
    yield np.array([2**62, -2**62, 0, -1, 1], np.int64)
    yield np.array([-7], np.int64)


def test_gorilla_device_decode_fuzz(profile_on, rng):
    for v in _gorilla_cases(rng):
        buf = enc.encode_floats(v)
        db = enc.device_block(buf)
        if db is None or db.kind != "gorilla":
            continue  # writer chose raw64 (incompressible) — fine
        got = np.asarray(dd.decode_to_device([buf]))
        np.testing.assert_array_equal(
            got.view(np.uint64), enc.decode_floats(buf).view(np.uint64))


def test_varint_device_decode_fuzz(profile_on, rng):
    hit = 0
    for v in _varint_cases(rng):
        buf = enc.encode_ints(v)
        db = enc.device_block(buf)
        if db is None or db.kind != "varint":
            continue
        hit += 1
        got = np.asarray(dd.decode_to_device([buf]))
        np.testing.assert_array_equal(got, enc.decode_ints(buf))
    assert hit >= 2, "varint cases unexpectedly all fell to FOR/const"


def test_strdict_device_decode_indices(profile_on, rng):
    """strdict ships the min-width index array; the uniq table stays on
    the host — device indices gathered through the table must equal the
    host string decode."""
    vals = rng.choice(["info", "warn", "error", "debug"], 300)
    buf = enc.encode_strings(vals)
    db = enc.device_block(buf)
    assert db is not None and db.kind == "strdict"
    assert db.table is not None and len(db.table) <= 4
    idx = np.asarray(dd.decode_to_device([buf], dtype=np.int64))
    got = np.asarray([db.table[i] for i in idx])
    np.testing.assert_array_equal(got, enc.decode_strings(buf))


def test_mixed_codec_signature(profile_on, rng):
    """One program over const+delta+raw64+gorilla+varint blocks: the
    packed payload offsets and aux vectors must line up per block."""
    blocks, want = [], []
    v1 = np.arange(0, 500, 5, dtype=np.int64)
    v2 = np.cumsum(rng.integers(-2, 3, 300)).astype(np.int64)
    v3 = rng.standard_normal(200)
    v4 = np.repeat(np.round(rng.standard_normal(8), 1), 25)
    for v, encode in ((v1, enc.encode_ints), (v2, enc.encode_ints),
                      (v3, enc.encode_floats), (v4, enc.encode_floats)):
        buf = encode(v)
        blocks.append(buf)
        want.append(np.asarray(v, np.float64))
    kinds = [enc.device_block(b).kind for b in blocks]
    assert "varint" in kinds and "gorilla" in kinds
    got = np.asarray(dd.decode_to_device(blocks, dtype=np.float64))
    np.testing.assert_array_equal(
        got.view(np.uint64), np.concatenate(want).view(np.uint64))


def test_codec_knob_excludes(profile_on, monkeypatch, rng):
    """OGT_DEVICE_DECODE_CODECS narrows the device family: an excluded
    codec fails classification (-> host fallback), the others keep
    working, and the default is everything."""
    g = enc.encode_floats(np.repeat(np.round(rng.standard_normal(8), 1),
                                    30))
    assert enc.device_block(g).kind == "gorilla"
    assert dd.classify([g]) is not None
    monkeypatch.setenv("OGT_DEVICE_DECODE_CODECS", "const,delta,raw64")
    assert dd.classify([g]) is None
    r = enc.encode_floats(rng.standard_normal(64))
    assert dd.classify([r]) is not None  # raw64 still allowed
    monkeypatch.delenv("OGT_DEVICE_DECODE_CODECS")
    assert dd.classify([g]) is not None


def test_cost_gate_keeps_incompressible_on_host(profile_on, rng):
    """Two gates: the WRITER refuses gorilla when the stream does not
    shrink (random mantissas -> raw64 envelope), and the PLANNER refuses
    a fused plan whose encoded transfer would not beat the decoded grid
    it replaces."""
    incompressible = rng.standard_normal(256) * 1e17
    buf = enc.encode_floats(incompressible)
    assert enc.device_block(buf).kind == "raw64"  # writer gate

    # planner gate: a tight grid (cells == n) with full-width raw64
    # payload + explicit int32 slots transfers MORE than the grid
    S_pad, k, w_pad = 8, 1, 128
    n = S_pad * k * w_pad
    v = rng.standard_normal(n) * 1e17
    blocks = [enc.encode_floats(v)]
    assert enc.device_block(blocks[0]).kind == "raw64"
    views = [(blocks, np.array([[0, n]], np.int64), n)]
    flat = rng.permutation(n).astype(np.int64)
    before = dd._STATS.snapshot().get("device", {}).get(
        "decode_fallbacks_total", 0)
    plan = dd.build_grid_plan(views, flat, np.ones(n, bool),
                              (S_pad, k, w_pad), np.float64)
    assert plan is None, "cost gate must refuse a transfer-losing plan"
    assert dd._STATS.snapshot().get("device", {}).get(
        "decode_fallbacks_total", 0) > before


def test_per_codec_decode_counters(profile_on, rng):
    """/debug/device contract: each decoded block increments its codec's
    decode_blocks_/decode_payload_bytes_ family alongside aggregates."""
    from opengemini_tpu.utils.stats import GLOBAL as STATS

    def counters():
        c = STATS.snapshot().get("device", {})
        return {k: v for k, v in c.items() if k.startswith("decode_")}

    v = np.cumsum(rng.integers(-2, 3, 300)).astype(np.int64)
    buf = enc.encode_ints(v)
    assert enc.device_block(buf).kind == "varint"
    sig, payload, _s, _a, _b = dd._pack_blocks(dd.classify([buf]))
    before = counters()
    dd._note_decode_stats(sig, 300)
    after = counters()
    assert after.get("decode_blocks_varint_total", 0) == \
        before.get("decode_blocks_varint_total", 0) + 1
    assert after.get("decode_payload_bytes_varint_total", 0) == \
        before.get("decode_payload_bytes_varint_total", 0) + len(payload)
    assert after["decode_blocks_total"] == \
        before.get("decode_blocks_total", 0) + 1

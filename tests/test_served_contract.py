"""The served numeric contract, in tier-1: what a server on the chip
computes in (x64 off: float32 on the device, float64 on the host) over the
read path it runs — blocks decoded on the host where they are read, cached
decoded, merged as plain `Column`s, laid out, reduced.

One store a codec, the data deciding the codec (the value blocks' tags are
read back from the files): 64 hosts, 840 ticks at 10 s across a shard
boundary, flushed with `tsf.PACK_ROWS` lowered so that each shard's file is
cut into time segments at this size (the writer's real constants are
`tests/test_time_segments.py`'s).  Then, against float64 numpy over the
arrays the store was written from, at the limits and by the error measure
of `benchmark/harness/oracle.py` (`TOL`: 2e-5 mean, 2e-7 selector, 2e-4
rate):

- a bulk read of every shard gives the rows back bit for bit;
- the fleet statement (`mean ... GROUP BY time(5m), hostname`) over both
  shards, its range starting inside a segment: PR 43's trim and PR 46's
  `interleaved` merge both run;
- a panel (`max` of 8 hosts by `time(1m)`) through the grid layout;
- `rate()` over the counters, resets included, through
  `PromEngine.query_range` on the device route."""

import os
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.oracle import TOL, oracle_rate, rel_err  # noqa: E402

from opengemini_tpu.promql.engine import PromEngine  # noqa: E402
from opengemini_tpu.query import offload  # noqa: E402
from opengemini_tpu.query.executor import Executor  # noqa: E402
from opengemini_tpu.storage import colcache, encoding, tsf  # noqa: E402
from opengemini_tpu.storage.engine import (  # noqa: E402
    DEFAULT_SHARD_DURATION, Engine, shard_group_start)
from opengemini_tpu.utils.stats import GLOBAL as STATS  # noqa: E402

assert TOL == {"selector": 2e-7, "mean": 2e-5, "rate": 2e-4}

NS, STEP = 10**9, 10
# where two shard groups of the default retention policy meet
BOUNDARY = (shard_group_start(1_700_000_000 * NS, DEFAULT_SHARD_DURATION)
            + DEFAULT_SHARD_DURATION) // NS
HALF = 420                      # ticks a shard: 14 windows of 5 m
TICKS, HOSTS = 2 * HALF, 64
BASE = BOUNDARY - HALF * STEP
T_S = BASE + STEP * np.arange(TICKS)


def _resets(vals: np.ndarray, rng) -> np.ndarray:
    """Each series restarts from zero at two ticks of its own."""
    out = vals.copy()
    for h in range(out.shape[1]):
        for at in sorted(rng.integers(50, TICKS - 50, 2)):
            out[at:, h] -= out[at, h]
    return out


def _data(codec: str) -> np.ndarray:
    """(ticks, hosts) values whose packed column takes `codec`: counters,
    but for gorilla's walk and raw's noise, which rate() reads as counters
    that restart at every step down."""
    rng = np.random.default_rng(47)
    if codec == "gorilla":      # float, whole numbers that move little
        return np.floor(50 + np.cumsum(rng.normal(0, 0.5, (TICKS, HOSTS)),
                                       axis=0))
    if codec == "raw":          # float, sign and every mantissa bit in use
        return rng.normal(0, 100, (TICKS, HOSTS))
    if codec == "varint":       # int: a restart is one long delta
        return _resets(np.cumsum(rng.integers(0, 60, (TICKS, HOSTS)),
                                 axis=0), rng)
    if codec == "delta":        # int, deltas zlib packs under a byte
        return np.cumsum(rng.integers(0, 4, (TICKS, HOSTS)), axis=0)
    assert codec == "const"     # int, stride 0
    return np.full((TICKS, HOSTS), 7)


TAGS = {"gorilla": encoding._T_GORILLA, "raw": encoding._T_RAW64,
        "varint": encoding._T_VARINT, "delta": encoding._T_DELTA,
        "const": encoding._T_CONST}


class Store:
    def __init__(self, path, codec: str):
        self.codec = codec
        self.vals = _data(codec)
        lit = (repr if self.vals.dtype.kind == "f" else "{}i".format)
        self.engine = Engine(str(path))
        self.engine.create_database("db")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(tsf, "PACK_ROWS", 4096)
            self.engine.write_lines("db", "\n".join(
                f"cpu,hostname=host_{h:02d} value={lit(v)} {t * NS}"
                for h in range(HOSTS)
                for t, v in zip(T_S.tolist(), self.vals[:, h].tolist())))
            self.engine.flush_all()
        self.shards = self.engine.shards_for_range(
            "db", None, BASE * NS, (BASE + TICKS * STEP) * NS)

    def close(self):
        self.engine.close()


@pytest.fixture(scope="module", params=list(TAGS))
def store(request, tmp_path_factory):
    """A store of one codec, served as a server serves: x64 off, the
    PromQL tiled kernels on the device route (jax.numpy, float32)."""
    cache = colcache.GLOBAL.config()
    mode, x64 = offload.prom_host_kernels_mode(), jax.config.jax_enable_x64
    st = Store(tmp_path_factory.mktemp(request.param), request.param)
    jax.config.update("jax_enable_x64", False)
    offload.set_prom_host_kernels_mode("0")
    yield st
    offload.set_prom_host_kernels_mode(mode)
    jax.config.update("jax_enable_x64", x64)
    st.close()
    colcache.GLOBAL.configure(**cache)
    colcache.GLOBAL.clear()


def moved(group: str, before: dict) -> dict:
    after = STATS.counters(group)
    return {k: after[k] - before.get(k, 0) for k in after}


def test_a_flushed_shard_reads_back_bit_for_bit(store):
    assert len(store.shards) == 2
    for half, sh in enumerate(store.shards):
        chunks = [(r, c) for r in sh._files for c in r.chunks("cpu")]
        # three sid spans of 20 hosts in two time segments each, and the
        # last four hosts whole
        assert len(chunks) == 7 and all(c.packed for _r, c in chunks)
        assert {r._read(c.cols["value"]["v"])[0] for r, c in chunks} \
            == {TAGS[store.codec]}
        sids = np.array(sorted(sh.index.series_ids("cpu")), dtype=np.int64)
        colcache.GLOBAL.clear()
        before = STATS.counters("scan")
        sid_arr, rec = sh.read_series_bulk("cpu", sids, None, None,
                                           ["value"])
        assert moved("scan", before)["merges_interleaved"] == 1
        col = rec.columns["value"]
        assert col.valid.all() and len(rec) == HOSTS * HALF
        want = store.vals[half * HALF:(half + 1) * HALF]
        assert col.values.dtype == want.dtype
        hosts = [int(sh.index.tags_of(int(s))["hostname"][5:])
                 for s in sid_arr[::HALF]]
        got = col.values.reshape(HOSTS, HALF)
        assert got.tobytes() == want.T[hosts].tobytes()
        assert rec.times.reshape(HOSTS, HALF)[0].tolist() \
            == (T_S[half * HALF:(half + 1) * HALF] * NS).tolist()


def test_the_fleet_statement_is_the_float64_mean(store):
    lo, hi = BASE + 600, BASE + TICKS * STEP - 600      # inside a segment
    ex = Executor(store.engine)
    colcache.GLOBAL.clear()
    before = STATS.counters("scan")
    doc = ex.execute(
        f"SELECT mean(value) FROM cpu WHERE time >= {lo * NS} AND "
        f"time < {hi * NS} GROUP BY time(5m), hostname", db="db")
    scan = moved("scan", before)
    assert scan["merges_interleaved"] == 2          # one a shard
    assert scan["rows_kept"] == HOSTS * (hi - lo) // STEP \
        < scan["rows_decoded"]
    series = doc["results"][0]["series"]
    assert [s["tags"]["hostname"] for s in series] \
        == [f"host_{h:02d}" for h in range(HOSTS)]
    k0, k1 = (lo - BASE) // STEP, (hi - BASE) // STEP
    want = store.vals[k0:k1].astype(np.float64).reshape(
        -1, 30, HOSTS).mean(axis=1).T
    for s in series:
        assert [row[0] for row in s["values"]] \
            == list(range(lo * NS, hi * NS, 300 * NS))
    got = np.array([[row[1] for row in s["values"]] for s in series])
    assert rel_err(got, want) <= TOL["mean"]


def test_a_panel_is_the_float64_max_through_the_grid(store):
    lo = BASE + HALF * STEP - 1800                  # an hour over the seam
    hosts = [3, 11, 19, 27, 35, 43, 51, 59]
    names = " OR ".join(f"hostname = 'host_{h:02d}'" for h in hosts)
    ex = Executor(store.engine)
    before = STATS.counters("executor")
    doc = ex.execute(
        f"SELECT max(value) FROM cpu WHERE ({names}) AND time >= {lo * NS} "
        f"AND time < {(lo + 3600) * NS} GROUP BY time(1m)", db="db")
    assert moved("executor", before)["grid_batches"] >= 1
    (series,) = doc["results"][0]["series"]
    k0 = (lo - BASE) // STEP
    want = store.vals[k0:k0 + 360][:, hosts].astype(np.float64).reshape(
        60, 6 * len(hosts)).max(axis=1)
    assert [row[0] for row in series["values"]] \
        == list(range(lo * NS, (lo + 3600) * NS, 60 * NS))
    assert rel_err([row[1] for row in series["values"]], want) \
        <= TOL["selector"]


def test_rate_over_the_counters_is_the_float64_rate(store):
    start, end = BASE + 600, BASE + TICKS * STEP - 10
    ends = np.arange(start, end + 1, 60)
    before = STATS.counters("prom")
    doc = PromEngine(store.engine).query_range(
        "rate(cpu[5m])", float(start), float(end), 60.0, "db")
    assert moved("prom", before)["tiled_kernels"] == 1
    result = doc["result"]
    assert [r["metric"]["hostname"] for r in result] \
        == [f"host_{h:02d}" for h in range(HOSTS)]
    for r in result:
        assert [p[0] for p in r["values"]] == ends.tolist()
    got = np.array([[float(p[1]) for p in r["values"]] for r in result])
    want = oracle_rate(store.vals, T_S, ends, 300.0)
    assert rel_err(got, want) <= TOL["rate"]

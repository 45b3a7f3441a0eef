"""The benchmark's copies of the data generators and the oracle give what
chip_smoke.py's originals give on one seed (while the original exists), and
the fast load carries the same rows as TSBS's loader shape."""

import json
import os

import numpy as np
import pytest

from harness import load_module
from harness.oracle import TOL, oracle_rate, rel_err, to_bf16

from conftest import BENCH, ROOT


def reference(name, **over):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    cfg.update(cfg["dry_run"])
    cfg.update(over)
    return load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                       "ref_" + name), cfg


@pytest.fixture(scope="module")
def smoke():
    path = os.path.join(ROOT, "chip_smoke.py")
    if not os.path.isfile(path):
        pytest.skip("chip_smoke.py is gone; the copies stand alone")
    return load_module(path, "chip_smoke")


def test_tsbs_copy_is_the_original(smoke):
    mod, _ = reference("tsbs-devops-cpu-4000")
    a, b = np.random.default_rng(7), np.random.default_rng(7)
    assert np.array_equal(mod.walk(a, 50, 24), smoke.tsbs_values(b, 50, 24))
    assert mod.hosts_keys(a, 24) == smoke.tsbs_hosts(b, 24)
    assert np.array_equal(mod.hundredths_table(), smoke._hundredths_table())
    assert tuple(mod.FIELDS) == tuple(smoke.TSBS_FIELDS)


def test_prom_copy_is_the_original(smoke):
    mod, _ = reference("prom-counters-10k")
    a, b = np.random.default_rng(7), np.random.default_rng(7)
    va, vb = mod.counters(a, 80, 100), smoke.prom_values(b, 80, 100)
    assert np.array_equal(va, vb)
    assert mod.label_sets(100) == smoke.prom_series(100)
    t = 1000 + np.arange(80) * 15
    ends = np.arange(1300, 1000 + 80 * 15, 60)
    assert np.array_equal(oracle_rate(va, t, ends, 300.0),
                          smoke.oracle_rate(vb, t, ends, 300.0))
    assert TOL == smoke.TOL


def test_line_writer_copy_is_the_original(smoke):
    from harness.lineproto import LineTemplate

    mod, _ = reference("tsbs-devops-cpu-4000")
    rng = np.random.default_rng(3)
    keys = mod.hosts_keys(rng, 5)
    vals = rng.integers(0, 10001, size=(5, 10))
    slots = mod.hundredths_table()[vals]
    ts = 1451606400 * 10**9
    ours = LineTemplate(keys, mod.FIELDS, 6).fill(slots, np.full(5, ts))
    assert ours == smoke.TickWriter(keys, smoke.TSBS_FIELDS, 6).tick(slots, ts)


def _rows(bodies):
    return sorted(ln for body, _ in bodies for ln in body.split(b"\n") if ln)


def test_fast_load_carries_the_loader_rows():
    mod, cfg = reference("tsbs-devops-cpu-4000", span_s=300)
    ref = mod.Reference(cfg, 11)
    fast = list(ref.load_requests())
    slow = list(ref.stream_requests(60))
    assert sum(n for _, n in fast) == sum(n for _, n in slow) == ref.rows
    assert _rows(fast) == _rows(slow)
    # series-major in the fast shape: a host's lines are consecutive
    first = fast[0][0].split(b"\n")
    assert first[0].split(b" ")[0] == first[1].split(b" ")[0]
    # time order in the loader's shape
    stamps = [int(ln.rsplit(b" ", 1)[1]) for body, _ in slow
              for ln in body.split(b"\n") if ln]
    assert stamps == sorted(stamps)


def test_bf16_rounding():
    x = np.array([1.0, 1.00390625, 100.0, 99.99, 3.0e9])
    got = to_bf16(x)
    assert got[0] == 1.0 and got[2] == 100.0
    assert got[1] in (1.0, 1.0078125)           # ties to even: 1.0
    assert abs(got[3] - 99.99) / 99.99 < 2**-8
    assert rel_err(got, x) > 1e-4

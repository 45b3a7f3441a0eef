"""The stored cells' generator gives, for a seed, byte for byte what PR 23's
gave: the data, the set-up load's bodies, the warm touches, the warm
repeats, the request list and the `keep` mask, at the cells' own sizes.

`recorded/generator_hashes.json` was written from the tree before PR 26
touched the generator (`python3 benchmark/selftest/test_generator_unchanged.py
--record` there).  A PR that means to change what a stored cell sends
records anew and says so: the cell's ledger lines then stand no longer."""

import functools
import hashlib
import json
import os
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

from harness import load_module, traffic

RECORDED = os.path.join(BENCH, "selftest", "recorded", "generator_hashes.json")
CELLS = ("tsbs_fleet_groupby", "prom_rate_range", "tsbs_host_panels")
SEEDS = (7, 2147483659)
SECONDS = (51.0, 59.0)          # a --trace 0 run's list, and a --trace 1 run's


def _sha(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        if isinstance(p, np.ndarray):
            p = np.ascontiguousarray(p).tobytes()
        h.update(p if isinstance(p, bytes) else repr(p).encode())
        h.update(b"\0")
    return h.hexdigest()


def _requests(reqs) -> str:
    return _sha(*[(r.method, r.path, r.body, sorted(r.stmt.items()), r.units)
                  for r in reqs])


@functools.lru_cache(maxsize=2)
def _data(config_file: str, seed: int):
    """(reference, hashes of its data and of its set-up load's bodies);
    two cells share the TSBS deployment."""
    with open(os.path.join(ROOT, config_file)) as f:
        cfg = json.load(f)
    mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                      "ref_" + cfg["name"])
    ref = mod.Reference(cfg, seed)
    data = ref.hundredths if hasattr(ref, "hundredths") else ref.vals
    names = ref.keys if hasattr(ref, "keys") else ref.labels
    return ref, {"data": _sha(data, ref.rows, names),
                 "load": _sha(*[x for body, n in ref.load_requests()
                                for x in (body, n)])}


def hashes(workload: str, seed: int) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    ref, out = _data(conf["file"], seed)
    out = dict(out)
    for seconds in SECONDS:
        plan = traffic.build(mix, ref, seed, seconds)
        out[f"plan_{seconds:g}s"] = {
            "warm_touch": _requests(plan.warm_touch),
            "warm_repeat": _requests(plan.warm_repeat),
            "requests": _requests(plan.requests),
            "n_requests": len(plan.requests),
            "keep": _sha(np.asarray(plan.keep, bool))}
    return out


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_stored_cells_send_what_they_sent(workload, seed, recorded):
    assert hashes(workload, seed) == recorded[workload][str(seed)]


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit("usage: test_generator_unchanged.py --record")
    doc = {w: {} for w in CELLS}
    for s in SEEDS:
        for w in CELLS:
            doc[w][str(s)] = hashes(w, s)
    with open(RECORDED, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"recorded {RECORDED}")

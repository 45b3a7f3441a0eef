"""Compile every Pallas kernel with Mosaic on the chip and compare each
with its XLA twin, at the geometries production launches them with.

    python tools/pallas_chip_check.py          # needs a TPU; exits 1 on any
                                               # refusal or mismatch

tests/test_pallas.py checks the same kernels in interpret mode on the
CPU; interpret mode says nothing about what Mosaic accepts (block
shapes, int8 tiling, scoped-VMEM use), so a kernel counts as working
only after this script has passed on a chip.  One JSON line per
(kernel, geometry) and a final summary line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bucket_inputs(g: int, w: int, seed: int):
    rng = np.random.default_rng(seed)
    v = (rng.standard_normal((g, w)) * 10).astype(np.float32)
    m = rng.random((g, w)) < 0.7
    m[:: max(g // 4, 1)] = False  # fully-empty segments
    rel = rng.integers(0, 2**40, size=(g, w)).astype(np.int64)
    hi = (rel >> 30).astype(np.int32)
    lo = (rel & ((1 << 30) - 1)).astype(np.int32)
    idx = np.arange(g * w, dtype=np.int32).reshape(g, w)
    v[0, : w // 2] = 7.5  # value ties inside one row
    return v, hi, lo, idx, m


def _compare(got: dict, want: dict, exact: tuple) -> str | None:
    for k, w in want.items():
        g = np.asarray(got[k])
        w = np.asarray(w)
        if k in exact:
            if not np.array_equal(g, w):
                return f"{k}: {int((g != w).sum())} cells differ"
        elif not np.allclose(g, w, rtol=1e-5, atol=1e-4, equal_nan=True):
            return f"{k}: max abs diff {float(np.nanmax(np.abs(g - w)))}"
    return None


def main() -> int:
    from opengemini_tpu.utils import backend

    dev = backend.init()
    print(json.dumps(dev), flush=True)
    if dev["platform"] != "tpu":
        print(f"pallas_chip_check needs a TPU, found {dev['platform']!r}",
              file=sys.stderr)
        return 1
    import jax

    from opengemini_tpu.models import ragged
    from opengemini_tpu.ops import pallas_segment as ps
    from opengemini_tpu.ops import segment as seg
    from opengemini_tpu.utils import devobs

    # the capability probe's own kernel goes through Mosaic too; on a
    # TPU a refusal raises out of here (utils/devobs._probe_pallas)
    devobs.pallas_supported()
    print(json.dumps({"kernel": "devobs_probe", "mosaic": "compiled"}),
          flush=True)

    cases = []
    # bucket kernels: every width of the ragged ladder, at the row cap of
    # _tile_g and at a multi-step grid (models/ragged.py pads G to pow2)
    for w in ragged.WIDTHS:
        for g in (8, 1024, 16384):
            cases.append(("bucket_basic", (g, w)))
            cases.append(("bucket_selectors", (g, w)))
    # grid kernel: (S_pad, k, W_pad) as models/grid.py pads them on TPU
    # (rows to 8-multiples, lanes to 128-multiples); k=6 is 10 s data in
    # 1 m windows, k=360 the 1 h window of double-groupby
    for shape in ((8, 6, 128), (4096, 6, 128), (4000, 360, 128),
                  (512, 60, 1792)):
        cases.append(("grid", shape))

    failures = 0
    for name, shape in cases:
        rec = {"kernel": name, "shape": list(shape)}
        t0 = time.perf_counter()
        try:
            if name.startswith("bucket"):
                args = _bucket_inputs(*shape, seed=sum(shape))
                if name == "bucket_basic":
                    got = ps.bucket_stats_basic(*args)
                    want = jax.jit(ragged._stats_fn("basic"))(
                        args[0], args[4])
                    exact = ("count",)
                else:
                    got = ps.bucket_stats_selectors(*args)
                    want = jax.jit(ragged._stats_fn("selectors_xla"))(*args)
                    exact = ("sel_first", "sel_last", "sel_min", "sel_max")
            else:
                rng = np.random.default_rng(sum(shape))
                v = (rng.standard_normal(shape) * 10).astype(np.float32)
                m = rng.random(shape) < 0.8
                got = ps.grid_window_agg_t(v, m)
                want = seg.grid_window_agg_t(v, m)
                exact = ("count",)
            jax.block_until_ready(got)
            err = _compare(got, want, exact)
            rec["mosaic"] = "compiled"
            rec["matches_xla"] = err is None
            if err:
                rec["error"] = err
        except Exception as e:  # noqa: BLE001 — report every kernel, then fail
            rec["mosaic"] = "refused"
            rec["error"] = f"{type(e).__name__}: {str(e)[:1500]}"
        rec["wall_s"] = round(time.perf_counter() - t0, 3)
        if rec.get("error"):
            failures += 1
        print(json.dumps(rec), flush=True)
    print(json.dumps({"ok": failures == 0, "cases": len(cases),
                      "failures": failures}), flush=True)
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())

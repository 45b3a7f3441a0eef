"""Both forms of PromQL's instant selection (`ops/prom.py` `instant_values`:
by comparison up to `INSTANT_COMPARE_MAX_SAMPLES` samples a row, by binary
search and a row gather past it), compiled and run on the device that is
there, at the geometries given — what the constant is set from.

    python tools/instant_forms_check.py 1000000x64x5 1000000x128x5
                                        # series x samples x steps; one JSON
                                        # line a (geometry, form), the same
                                        # lines in chiprun_out/instant_forms.jsonl

A line holds the seconds the compiler took, the bytes it planned (code,
temporaries), the median milliseconds of five runs, and whether the two
forms of a geometry agree cell for cell.  On a CPU the lines say so
(`platform`) and are no device numbers.  Exits 1 where the forms disagree.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
DEFAULT = ["1000000x8x5", "1000000x64x5", "1000000x128x5", "1000000x256x5"]


def inputs(series: int, samples: int, steps: int):
    """A 15 s scrape grid with a jitter a series, float32, and steps over
    the last quarter of it: every row full, as the cell's are."""
    rng = np.random.default_rng(series + samples)
    times = (15.0 * np.arange(samples, dtype=np.float32)[None, :]
             + rng.uniform(0, 5, size=(series, 1)).astype(np.float32))
    values = rng.uniform(64 << 20, 8 << 30, size=times.shape).astype(np.float32)
    last = 15.0 * samples
    at = np.linspace(0.75 * last, last, steps, dtype=np.float32)
    return times, values, np.full(series, samples, np.int32), at, np.float32(300)


def measure(form: str, args) -> tuple[dict, tuple]:
    import jax

    from opengemini_tpu.ops import prom as promops

    samples = args[0].shape[1]
    promops.INSTANT_COMPARE_MAX_SAMPLES = samples if form == "compare" else 0

    def prom_instant(*a):   # a function of its own: jit keeps traces by it
        return promops.instant_values(*a)

    t0 = time.monotonic()
    compiled = jax.jit(prom_instant).lower(*args).compile()
    compile_s = time.monotonic() - t0
    mem = compiled.memory_analysis()
    on_device = jax.device_put(args)
    jax.block_until_ready(compiled(*on_device))
    took = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*on_device))
        took.append(1e3 * (time.perf_counter() - t0))
    vals, valid = (np.asarray(x) for x in out)
    return ({"form": form, "compile_s": round(compile_s, 2),
             "code_bytes": mem.generated_code_size_in_bytes,
             "temp_bytes": mem.temp_size_in_bytes,
             "run_ms": round(statistics.median(took), 3)},
            (np.where(valid, vals, 0), valid))


def main(argv: list[str]) -> int:
    from opengemini_tpu.utils import backend

    dev = backend.init()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    bad = 0
    with open(os.path.join(out_dir, "instant_forms.jsonl"), "a") as sink:
        for geometry in argv or DEFAULT:
            series, samples, steps = map(int, geometry.split("x"))
            args = inputs(series, samples, steps)
            got = {}
            for form in ("compare", "search"):
                line, got[form] = measure(form, args)
                line = {"platform": dev["platform"], "geometry": geometry,
                        **line}
                if form == "search":
                    line["agree"] = all(
                        np.array_equal(a, b) for a, b in zip(*got.values()))
                    bad += not line["agree"]
                print(json.dumps(line), flush=True)
                sink.write(json.dumps(line) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""The control comes out as not correct: the plain reference computed in
bfloat16, the precision below the served path's float32, put in the
program's place, fails the limit of every float comparison — at a size a
test can hold.  (On the chip at the cells' own sizes: PERF.md §2.)"""

import numpy as np
import pytest

from harness.oracle import to_bf16

from test_oracles import reference

SEEDS = (1, 2, 3000000007)


def stmt_influx(ref, agg, fields, every_s, hosts, by_host):
    return {"agg": agg, "fields": [ref.field_names[i] for i in fields],
            "every_s": every_s, "t0": ref.start_s,
            "t1": ref.start_s + ref.cfg["span_s"], "hosts": hosts,
            "group_by_host": by_host}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", ["fleet_mean", "panel_max"])
def test_tsbs_control_fails(seed, shape):
    mod, cfg = reference("tsbs-devops-cpu-4000")
    ref = mod.Reference(cfg, seed)
    stmt = (stmt_influx(ref, "mean", [4, 1, 7, 0, 9], 300, None, True)
            if shape == "fleet_mean" else
            stmt_influx(ref, "max", [0, 1, 2, 3, 4], 60, [1, 3, 5, 7, 9, 11,
                                                          13, 15], False))
    sound = ref.numbers(stmt, ref.want(stmt))
    control = ref.numbers(stmt, ref.want(stmt, narrow=to_bf16))
    for name, (value, limit) in sound.items():
        assert value <= limit
        assert control[name][0] > 3 * limit, (name, control[name])


@pytest.mark.parametrize("seed", SEEDS)
def test_prom_control_fails(seed):
    mod, cfg = reference("prom-counters-10k")
    ref = mod.Reference(cfg, seed)
    stmt = {"range_s": 300, "step_s": 60, "start": ref.start_s + 300,
            "end": ref.start_s + cfg["span_s"]}
    (value, limit), = ref.numbers(stmt, ref.want(stmt, to_bf16)).values()
    assert value > 3 * limit
    # and float32 of the reset-corrected, first-sample-relative values,
    # which is what the served path ships, passes
    f32 = lambda x: np.asarray(x, np.float32).astype(np.float64)  # noqa: E731
    (value, limit), = ref.numbers(stmt, ref.want(stmt, f32)).values()
    assert value <= limit

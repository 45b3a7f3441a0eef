"""`range_s` and `range_walk` of an `influxql_template`: the part of the
span a statement covers is the traffic file's to set, a `cycle` goes round
the span's offsets in the order statements are sent, across the seam
between warm-up and window too, and the oracle answers a sub-range as a
brute-force mean over the same rows does."""

import json
import os

import numpy as np
import pytest

from harness import traffic

from conftest import BENCH
from test_oracles import reference

SPAN, RANGE, EVERY = 3600, 600, 300         # k = 6 offsets, 2 windows each


def mix(**over):
    with open(os.path.join(BENCH, "traffic", "fleet_groupby.json")) as f:
        t = json.load(f)
    t.update(range_s=RANGE, range_walk="cycle", every_s=EVERY)
    t.update(over)
    return t


@pytest.fixture(scope="module")
def ref():
    mod, cfg = reference("tsbs-devops-cpu-4000", span_s=SPAN)
    return mod.Reference(cfg, 5)


def offsets(ref, reqs):
    for q in reqs:
        assert q.stmt["t1"] - q.stmt["t0"] == RANGE
    return [(q.stmt["t0"] - ref.start_s) // RANGE for q in reqs]


def test_offsets_go_round_in_the_order_sent(ref):
    plan = traffic.build(mix(), ref, 3, 2.0)
    k = SPAN // RANGE
    assert plan.cycle == k
    sent = plan.warm_touch + plan.warm_repeat + plan.requests
    assert offsets(ref, sent) == [n % k for n in range(len(sent))]
    for q in sent:
        s = q.stmt
        assert s["windows"] == RANGE // EVERY
        assert s["groups"] == s["windows"] * ref.hosts * 5
        assert q.units == 5 * ref.hosts * RANGE // ref.interval_s
        assert f"time >= {s['t0']}s AND time < {s['t1']}s" in s["q"]
    assert len({q.path for q in sent}) == len(sent)      # none asked twice


@pytest.mark.parametrize("warm_sent", [7, 8, 9, 13, 30])
def test_no_range_again_within_k_across_the_seam(ref, warm_sent):
    """The warm-up stops when the server stands still, after any number of
    its repeats; the window goes on where it stopped."""
    plan = traffic.build(mix(), ref, 3, 2.0)
    n, keep = len(plan.requests), plan.keep.copy()
    traffic.join_walk(plan, warm_sent)
    skipped = n - len(plan.requests)
    assert 0 <= skipped < plan.cycle
    assert np.array_equal(plan.keep, keep[skipped:])
    sent = offsets(ref, plan.warm_touch + plan.warm_repeat[:warm_sent]
                   + plan.requests)
    for i, off in enumerate(sent):
        assert off not in sent[max(0, i - plan.cycle + 1):i], (i, sent[:i + 1])
    # and the traced phase goes on after the window's last
    plan.results = [None] * 5
    more = offsets(ref, plan.requests[4:5] + traffic.rest(plan).requests[:1])
    assert more[1] == (more[0] + 1) % plan.cycle


def test_a_range_without_a_walk_stays_at_the_span_s_start(ref):
    t = mix()
    del t["range_walk"]
    plan = traffic.build(t, ref, 3, 2.0)
    assert plan.cycle == 1
    assert set(offsets(ref, plan.warm_touch + plan.requests)) == {0}
    before = list(plan.requests)
    traffic.join_walk(plan, 9)                         # nothing to join
    assert plan.requests == before


@pytest.mark.parametrize("over, says", [
    ({"range_s": 700}, "divide span_s"),               # 3600 % 700
    ({"range_s": 450}, "multiple of every_s"),         # 450 % 300
    ({"range_s": 0}, "divide span_s"),
    ({"range_walk": "random"}, "unknown range_walk"),
])
def test_a_range_that_does_not_fit_is_refused(ref, over, says):
    with pytest.raises(ValueError, match=says):
        traffic.check(mix(**over), ref.cfg)
    with pytest.raises(ValueError, match=says):
        traffic.build(mix(**over), ref, 3, 2.0)


def test_a_walk_without_a_range_is_refused(ref):
    t = mix()
    del t["range_s"]
    with pytest.raises(ValueError, match="range_walk without range_s"):
        traffic.check(t, ref.cfg)


def test_a_touch_s_own_windows_have_to_fit_too(ref):
    t = mix(range_s=900)                               # 900 % 300 == 0
    t["warm"]["touch"] = [{"fields": [0, 1, 2, 3, 4], "every_s": 600}]
    with pytest.raises(ValueError, match="multiple of every_s"):
        traffic.check(t, ref.cfg)


def test_the_oracle_answers_a_sub_range_as_brute_force_does(ref):
    plan = traffic.build(mix(), ref, 3, 2.0)
    for q in plan.requests[:plan.cycle]:
        s = q.stmt
        want = ref.want(s)
        assert want.shape == (s["windows"], ref.hosts, 5)
        cols = [ref.field_names.index(f) for f in s["fields"]]
        per = EVERY // ref.interval_s
        for w in range(s["windows"]):
            first = (s["t0"] - ref.start_s) // ref.interval_s + w * per
            for h in (0, ref.hosts - 1):
                for j, c in enumerate(cols):
                    rows = [ref.hundredths[t, h, c] / 100.0
                            for t in range(first, first + per)]
                    assert want[w, h, j] == pytest.approx(
                        sum(rows) / per, rel=1e-12)
        # and what parse() expects of the served answer are this range's
        # window times
        doc = {"results": [{"series": [
            {"tags": {"hostname": f"host_{h}"},
             "values": [[(s["t0"] + w * EVERY) * 10**9]
                        + list(want[w, h]) for w in range(s["windows"])]}
            for h in range(ref.hosts)]}]}
        assert np.array_equal(ref.parse(s, doc), want)

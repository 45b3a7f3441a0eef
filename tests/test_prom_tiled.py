"""Parity suite for the time-centric tiled range-vector engine
(ops/prom.py TiledPrepared).

Every tiled kernel is pitted against a pure-numpy per-sample Prometheus
reference (f64, sample loops — promql/functions.go semantics) over
ragged/irregular series: counter resets, empty windows, <2-sample
windows, offsets, and the left-open/right-closed window boundary.  A
second pass asserts ulp-bounded equality against the old dense kernels
on randomized shapes (the dense path runs f32 under jax, so the bound is
f32-scale), and the engine-level tests pin OGT_PROM_TILED=0/1
bit-compatibility plus the stage/slow-log wiring."""

import math

import numpy as np
import pytest

from opengemini_tpu.ops import prom as promops
from opengemini_tpu.promql.engine import PromEngine
from opengemini_tpu.storage.engine import Engine, NS

BASE = 1_700_000_000
BASE_MS = BASE * 1000


# -- pure-numpy per-sample Prometheus reference -----------------------------


def _window(t_ms, v, s_s, e_s):
    """Samples in the left-open/right-closed window (s, e]."""
    s_ms = int(round(s_s * 1000))
    e_ms = int(round(e_s * 1000))
    m = (t_ms > s_ms) & (t_ms <= e_ms)
    return t_ms[m], v[m]


def ref_rate(t_ms, v, base_ms, s_s, e_s, w, is_counter, is_rate):
    tt, vv = _window(t_ms, v, s_s, e_s)
    if len(tt) < 2:
        return None
    ts = (tt - base_ms) / 1000.0
    delta = vv[-1] - vv[0]
    if is_counter:
        for i in range(1, len(vv)):
            if vv[i] < vv[i - 1]:
                delta += vv[i - 1]
    sampled = ts[-1] - ts[0]
    if sampled <= 0:
        sampled = 1.0
    avg_iv = sampled / max(len(tt) - 1, 1)
    rel_s = s_s - base_ms / 1000.0
    rel_e = e_s - base_ms / 1000.0
    dur_start = ts[0] - rel_s
    dur_end = rel_e - ts[-1]
    thresh = avg_iv * 1.1
    if dur_start > thresh:
        dur_start = avg_iv / 2
    if dur_end > thresh:
        dur_end = avg_iv / 2
    if is_counter and delta > 0 and vv[0] >= 0:
        dur_zero = sampled * (vv[0] / max(delta, 1e-30))
        dur_start = min(dur_start, dur_zero)
    out = delta * ((sampled + dur_start + dur_end) / sampled)
    return out / w if is_rate else out


def ref_over_time(t_ms, v, s_s, e_s, func):
    _tt, vv = _window(t_ms, v, s_s, e_s)
    if len(vv) == 0:
        return None
    if func == "sum":
        return vv.sum()
    if func == "count":
        return float(len(vv))
    if func == "avg":
        return vv.mean()
    if func == "min":
        return vv.min()
    if func == "max":
        return vv.max()
    if func == "last":
        return vv[-1]
    if func == "present":
        return 1.0
    if func in ("stddev", "stdvar"):
        var = ((vv - vv.mean()) ** 2).mean()
        return var if func == "stdvar" else math.sqrt(var)
    raise AssertionError(func)


def ref_changes_resets(t_ms, v, s_s, e_s, kind):
    _tt, vv = _window(t_ms, v, s_s, e_s)
    if len(vv) == 0:
        return None
    n = 0
    for i in range(1, len(vv)):
        if kind == "changes" and vv[i] != vv[i - 1]:
            n += 1
        if kind == "resets" and vv[i] < vv[i - 1]:
            n += 1
    return float(n)


def ref_instant_rate(t_ms, v, base_ms, s_s, e_s, per_second):
    tt, vv = _window(t_ms, v, s_s, e_s)
    if len(tt) < 2:
        return None
    dv = vv[-1] - vv[-2]
    if per_second:
        if dv < 0:
            dv = vv[-1]
        dt = max((tt[-1] - tt[-2]) / 1000.0, 1e-9)
        return dv / dt
    return dv


def ref_linreg(t_ms, v, base_ms, s_s, e_s):
    tt, vv = _window(t_ms, v, s_s, e_s)
    if len(tt) < 2 or tt[-1] == tt[0]:
        return None
    rel_e = e_s - base_ms / 1000.0
    x = (tt - base_ms) / 1000.0 - rel_e
    n = len(x)
    cov = (x * vv).sum() - x.sum() * vv.sum() / n
    var = (x * x).sum() - x.sum() ** 2 / n
    slope = 0.0 if var == 0 else cov / var
    intercept = vv.mean() - slope * x.mean()
    return slope, intercept


# -- generators --------------------------------------------------------------


def gen_series(rng, S, max_n=120, irregular=True, resets=True):
    """Run-encoded ragged series on (or off) a regular grid."""
    t_parts, v_parts, lens = [], [], []
    for _ in range(S):
        n = int(rng.integers(0, max_n))
        if n == 0:
            lens.append(0)
            continue
        if irregular:
            t = np.sort(rng.choice(
                np.arange(0, 3_600_000, 500), size=n, replace=False))
        else:
            t = np.arange(n, dtype=np.int64) * 15_000
        v = np.cumsum(rng.random(n))
        if resets:
            rmask = rng.random(n) < 0.06
            off = np.maximum.accumulate(
                np.where(rmask, v * rng.random(n), 0.0))
            v = v - off
        t_parts.append(BASE_MS + t.astype(np.int64))
        v_parts.append(v)
        lens.append(n)
    t_all = (np.concatenate(t_parts) if t_parts else np.empty(0, np.int64))
    v_all = (np.concatenate(v_parts) if v_parts else np.empty(0, np.float64))
    return t_all, v_all, np.asarray(lens, np.int64)


def make_prep(t_all, v_all, lens, starts, ends, **kw):
    tmin = int(t_all.min()) if len(t_all) else BASE_MS
    tmax = int(t_all.max()) if len(t_all) else BASE_MS
    plan = promops.plan_tiles(starts, ends, tmin, tmax,
                              max_tiles=kw.pop("max_tiles", 500_000))
    assert plan is not None
    return promops.prepare_tiled(plan, t_all, v_all, lens,
                                 dtype=np.float64,
                                 max_gather_cols=kw.pop("max_gather_cols",
                                                        10**7), **kw)


def series_view(t_all, v_all, lens, i):
    off = int(np.cumsum(lens)[i] - lens[i])
    return t_all[off:off + lens[i]], v_all[off:off + lens[i]]


# -- per-sample reference parity ---------------------------------------------


class TestTiledVsReference:
    @pytest.fixture
    def data(self):
        rng = np.random.default_rng(11)
        cases = []
        for trial in range(4):
            S = int(rng.integers(1, 24))
            t_all, v_all, lens = gen_series(
                rng, S, irregular=bool(trial % 2), resets=True)
            w = float(rng.choice([60, 120, 300, 307]))
            step = float(rng.choice([30, 60, 299, 300, 600]))
            K = int(rng.integers(1, 24))
            start0 = BASE + float(rng.integers(-400, 3000))
            ends = start0 + np.arange(K) * step
            cases.append((t_all, v_all, lens, ends - w, ends, w))
        return cases

    def _check_cells(self, prep, out, valid, t_all, v_all, lens, starts,
                     ends, ref_fn, rtol=1e-9, atol=1e-9):
        out = np.asarray(out)[:, :prep.k_real]
        valid = np.asarray(valid)[:, :prep.k_real]
        for i in range(len(lens)):
            tt, vv = series_view(t_all, v_all, lens, i)
            for k in range(len(ends)):
                ref = ref_fn(tt, vv, starts[k], ends[k])
                if ref is None:
                    assert not valid[i, k], (i, k)
                else:
                    assert valid[i, k], (i, k)
                    assert abs(out[i, k] - ref) <= atol + rtol * abs(ref), (
                        i, k, out[i, k], ref)

    def test_rate_family(self, data):
        for t_all, v_all, lens, starts, ends, w in data:
            prep = make_prep(t_all, v_all, lens, starts, ends)
            for ic, ir in [(True, True), (True, False), (False, False)]:
                out, valid = prep.rate(np, is_counter=ic, is_rate=ir)
                self._check_cells(
                    prep, out, valid, t_all, v_all, lens, starts, ends,
                    lambda tt, vv, s, e: ref_rate(
                        tt, vv, prep.base_ms, s, e, w, ic, ir))

    def test_over_time_family(self, data):
        for t_all, v_all, lens, starts, ends, _w in data:
            prep = make_prep(t_all, v_all, lens, starts, ends)
            for func in ("sum", "count", "avg", "min", "max", "last",
                         "present", "stddev", "stdvar"):
                out, valid = prep.over_time(np, func=func)
                self._check_cells(
                    prep, out, valid, t_all, v_all, lens, starts, ends,
                    lambda tt, vv, s, e: ref_over_time(tt, vv, s, e, func),
                    rtol=1e-7, atol=1e-7)

    def test_changes_resets(self, data):
        for t_all, v_all, lens, starts, ends, _w in data:
            prep = make_prep(t_all, v_all, lens, starts, ends)
            for kind in ("changes", "resets"):
                out, valid = prep.changes_resets(np, kind=kind)
                self._check_cells(
                    prep, out, valid, t_all, v_all, lens, starts, ends,
                    lambda tt, vv, s, e: ref_changes_resets(tt, vv, s, e,
                                                            kind))

    def test_instant_rate(self, data):
        for t_all, v_all, lens, starts, ends, _w in data:
            prep = make_prep(t_all, v_all, lens, starts, ends)
            for ps in (True, False):
                out, valid = prep.instant_rate(np, per_second=ps)
                self._check_cells(
                    prep, out, valid, t_all, v_all, lens, starts, ends,
                    lambda tt, vv, s, e: ref_instant_rate(
                        tt, vv, prep.base_ms, s, e, ps))

    def test_linear_regression(self, data):
        for t_all, v_all, lens, starts, ends, _w in data:
            prep = make_prep(t_all, v_all, lens, starts, ends)
            slope, icept, valid = prep.linear_regression(np)
            self._check_cells(
                prep, slope, valid, t_all, v_all, lens, starts, ends,
                lambda tt, vv, s, e: (
                    None if ref_linreg(tt, vv, prep.base_ms, s, e) is None
                    else ref_linreg(tt, vv, prep.base_ms, s, e)[0]),
                rtol=1e-6, atol=1e-8)
            self._check_cells(
                prep, icept, valid, t_all, v_all, lens, starts, ends,
                lambda tt, vv, s, e: (
                    None if ref_linreg(tt, vv, prep.base_ms, s, e) is None
                    else ref_linreg(tt, vv, prep.base_ms, s, e)[1]),
                rtol=1e-6, atol=1e-8)


class TestDeviceWithoutX64:
    """A server runs with x64 off: the device computes in float32, where
    a counter near 1e9 keeps its value only to 64.  The device paths
    narrow on the host, after the float64 arithmetic float32 cannot do
    (ops/prom.py TiledPrepared._narrowed), and every kernel must agree
    with the float64 host path to float32 scale.

    One series in three restarts (one twice, one to exactly 0).  What
    Prometheus defines over a counter with resets — rate, increase,
    irate — and what selects a sample — min, max, last — must be right
    in EVERY window, the ones at and after a reset included.  The other
    kernels read values relative to the series' first sample and are
    held to float32 scale only where the series stays near it: the
    series without a reset (the rest is ROADMAP S2's contract)."""

    RESET_PROOF = ("rate", "increase", "irate", "min", "max", "last")

    @pytest.fixture
    def case(self):
        rng = np.random.default_rng(5)
        S, n = 19, 160
        t = BASE_MS + np.arange(n, dtype=np.int64) * 15_000
        start = rng.integers(10**8, 10**9, size=S).astype(np.float64)
        inc = rng.integers(0, 100, size=(S, n)).astype(np.float64)
        v = start[:, None] + np.cumsum(inc, axis=1)
        resets = np.zeros(S, bool)
        for s in range(0, S, 3):
            for at in rng.integers(n // 4, 3 * n // 4, size=2 if s == 3 else 1):
                v[s, at:] = np.cumsum(inc[s, at:]) - (inc[s, at] if s == 6
                                                      else 0.0)
            resets[s] = True
        assert (np.diff(v[resets], axis=1) < 0).any(axis=1).all()
        assert (v[6] == 0).any()
        lens = np.full(S, n, np.int64)
        ends = BASE + 300.0 + np.arange(30) * 60.0
        prep = make_prep(np.tile(t, S), v.reshape(-1), lens,
                         ends - 300.0, ends)
        return prep, resets

    def _kernels(self, run):
        out = {"rate": run("rate", is_counter=True, is_rate=True),
               "increase": run("rate", is_counter=True, is_rate=False),
               "delta": run("rate", is_counter=False, is_rate=False),
               "irate": run("instant_rate", per_second=True),
               "idelta": run("instant_rate", per_second=False),
               "changes": run("changes_resets", kind="changes"),
               "resets": run("changes_resets", kind="resets"),
               "linreg": run("linear_regression")}
        for func in ("sum", "avg", "min", "max", "last", "stddev"):
            out[func] = run("over_time", func=func)
        return out

    def _assert_close(self, got, want, prep, resets):
        for name, w in want.items():
            rows = (slice(None) if name in self.RESET_PROOF
                    else np.flatnonzero(~resets))
            for g_arr, w_arr in zip(got[name][:-1], w[:-1]):
                g_arr = np.asarray(g_arr)[:prep.S, :prep.k_real][rows]
                w_arr = np.asarray(w_arr)[:, :prep.k_real][rows]
                assert g_arr.dtype == np.float32, name
                # the regression and the variance sum products in
                # float32 and then cancel them: digits fewer than a sum
                cancels = name in ("linreg", "stddev")
                np.testing.assert_allclose(
                    g_arr, w_arr, rtol=2e-3 if cancels else 2e-5,
                    atol=1e-6, err_msg=name)

    def test_single_device_kernels_match_host_f64(self, case):
        import jax
        import jax.numpy as jnp

        prep, resets = case
        want = self._kernels(lambda k, **o: getattr(prep, k)(np, **o))
        with jax.enable_x64(False):
            got = self._kernels(lambda k, **o: getattr(prep, k)(jnp, **o))
        self._assert_close(got, want, prep, resets)

    def test_mesh_kernels_match_host_f64(self, case):
        import jax

        from opengemini_tpu.parallel import distributed as dist

        prep, resets = case
        want = self._kernels(lambda k, **o: getattr(prep, k)(np, **o))
        with jax.enable_x64(False):
            sh = prep.sharded(dist.make_mesh(4, ("shard",)))
            got = self._kernels(lambda k, **o: getattr(sh, k)(**o))
        self._assert_close(got, want, prep, resets)


# -- what only some kernels read is built by its first reader ----------------

_COUNTER = {"kind": "rate", "is_counter": True}
LAZY_KINDS = {
    "rate": dict(_COUNTER, is_rate=True),
    "increase": dict(_COUNTER, is_rate=False),
    "rate_gauge": {"kind": "rate", "is_counter": False, "is_rate": True},
    "delta": {"kind": "rate", "is_counter": False, "is_rate": False},
    "irate": {"kind": "instant_rate", "per_second": True},
    "idelta": {"kind": "instant_rate", "per_second": False},
    "changes": {"kind": "changes_resets", "which": "changes"},
    "resets": {"kind": "changes_resets", "which": "resets"},
    "deriv": {"kind": "deriv"},
    "predict_linear": {"kind": "predict", "dur": 600.0},
    **{f + "_over_time": {"kind": "over_time", "func": f}
       for f in sorted(PromEngine._TILED_OVER_TIME)},
}
# the kernels that gather covered tiles, and the one that gathers times
_READS_LAYOUT = {"changes", "resets", "deriv", "predict_linear",
                 *(f + "_over_time" for f in
                   ("sum", "avg", "stddev", "stdvar", "min", "max"))}
_READS_TIMES = {"deriv", "predict_linear"}


def lazy_case():
    """Ragged series on an irregular grid, resets inside: an empty series
    in the middle and one at the end, a series of a single sample."""
    rng = np.random.default_rng(45)
    t_parts, v_parts, lens = [], [], [37, 1, 90, 0, 64, 12, 0]
    for n in lens:
        t = np.sort(rng.choice(np.arange(0, 3_600_000, 500), size=n,
                               replace=False))
        v = np.cumsum(rng.random(n) * 50)
        if n > 1:
            v[n // 2:] -= v[n // 2] * 0.75      # a counter reset
        t_parts.append(BASE_MS + t.astype(np.int64))
        v_parts.append(v)
    ends = BASE + 120.0 + np.arange(40) * 60.0
    return (np.concatenate(t_parts), np.concatenate(v_parts),
            np.asarray(lens, np.int64), ends - 300.0, ends)


class TestBuiltOnFirstRead:
    """The covered-tile gather layout and the (S, N) times matrix are not
    the prepare's to build: a kernel that reads one gets it, bit for bit
    what a prepare that built both at construction answers from, and one
    that reads neither never pays for it (ISSUE 45)."""

    @staticmethod
    def _answer(spec, prep, on):
        import jax
        import jax.numpy as jnp

        if on == "host":
            out, valid = PromEngine._tiled_dispatch(spec, spec["kind"],
                                                    prep, np)
        else:
            with jax.enable_x64(False):   # a server: the device narrows
                assert prep._narrows(jnp, None)
                out, valid = PromEngine._tiled_dispatch(
                    spec, spec["kind"], prep, jnp)
        return np.asarray(out), np.asarray(valid)

    @pytest.mark.parametrize("on", ["host", "device"])
    @pytest.mark.parametrize("name", sorted(LAZY_KINDS))
    def test_a_kernel_builds_what_it_reads_and_answers_the_same(self, name,
                                                                on):
        spec = LAZY_KINDS[name]
        forced = make_prep(*lazy_case(), lane_quantum=8)
        assert forced.unbuilt() == ("layout", "times")
        assert forced.times.shape == (forced.S, forced.N)
        assert forced.gidx.shape == forced.gmask.shape
        assert forced.unbuilt() == ()
        lazy = make_prep(*lazy_case(), lane_quantum=8)
        with np.errstate(all="ignore"):
            want = self._answer(spec, forced, on)
            got = self._answer(spec, lazy, on)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes()
        # rate() of a counter corrects its resets from the gathered tiles
        # only where the device has not had them folded in on the host
        reads_layout = name in _READS_LAYOUT or (
            name in ("rate", "increase") and on == "host")
        left = tuple(n for n, read in (("layout", reads_layout),
                                       ("times", name in _READS_TIMES))
                     if not read)
        assert lazy.unbuilt() == left

    def test_the_edge_times_are_the_matrix_s(self):
        """t_first, t_last and t_lm1 are gathered from the run-encoded
        times; the matrix, once built, holds the same bits there — and
        +inf in an empty series' row."""
        prep = make_prep(*lazy_case())
        for got, idx in ((prep.t_first, prep.safe_f),
                         (prep.t_last, prep.safe_l),
                         (prep.t_lm1, prep.safe_lm1)):
            want = np.take_along_axis(prep.times, idx, axis=1)
            assert got.tobytes() == want.astype(prep.dtype).tobytes()
        empty = np.flatnonzero(np.asarray(prep.counts) == 0)
        assert list(empty) == [3, 6]
        assert np.isinf(prep.t_first[empty]).all()
        assert not prep.has1[empty].any()
        # what the dense path prepares from the same samples
        times, values, counts, base_ms = promops.prepare_matrix_runs(
            *lazy_case()[:3], dtype=np.float64)
        assert base_ms == prep.base_ms
        assert times.tobytes() == prep.times.tobytes()
        assert values.tobytes() == prep.values.tobytes()
        assert counts.tobytes() == prep.counts.tobytes()

    def test_the_budget_is_decided_by_the_constructor_with_no_layout(
            self, monkeypatch):
        """Tiled against dense is decided before the route is: the
        constructor raises from the layout's size alone."""
        def built(self):
            raise AssertionError("the gather layout was built")

        monkeypatch.setattr(promops.TiledPrepared, "_gather_layout",
                            property(built))
        t_all = BASE_MS + np.arange(200, dtype=np.int64)
        v_all = np.arange(200, dtype=np.float64)
        lens = np.asarray([200], np.int64)
        plan = promops.plan_tiles(np.asarray([BASE - 60.0]),
                                  np.asarray([BASE + 60.0]),
                                  int(t_all.min()), int(t_all.max()), 10_000)
        with pytest.raises(promops.TileBudgetExceeded):
            promops.TiledPrepared(plan, t_all, v_all, lens,
                                  max_gather_cols=8)
        assert promops.prepare_tiled(plan, t_all, v_all, lens,
                                     max_gather_cols=8) is None
        # inside the budget the same constructor builds no layout either
        prep = promops.TiledPrepared(plan, t_all, v_all, lens)
        assert (prep.C, prep.pmax) == (1, 200)
        assert prep.unbuilt() == ("layout", "times")


class TestBoundaries:
    """Left-open/right-closed edges, empty and 1-sample windows."""

    def _one(self, t_s_list, v_list, starts, ends):
        t_all = (np.asarray(t_s_list, np.int64) * 1000) + BASE_MS
        v_all = np.asarray(v_list, np.float64)
        lens = np.asarray([len(t_all)], np.int64)
        return t_all, v_all, lens, make_prep(
            t_all, v_all, lens, np.asarray(starts, float) + BASE,
            np.asarray(ends, float) + BASE)

    def test_sample_at_window_start_excluded(self):
        _t, _v, _l, prep = self._one([100, 200, 400], [1, 2, 3],
                                     [100], [400])
        out, valid = prep.over_time(np, func="count")
        # (100, 400]: sample at t=100 is OUT, t=400 is IN
        assert valid[0, 0] and out[0, 0] == 2

    def test_sample_at_window_end_included(self):
        _t, _v, _l, prep = self._one([400], [7.0], [100], [400])
        out, valid = prep.over_time(np, func="last")
        assert valid[0, 0] and out[0, 0] == 7.0

    def test_empty_window_invalid(self):
        _t, _v, _l, prep = self._one([50, 500], [1, 2], [100], [400])
        for func in ("sum", "min", "last"):
            _out, valid = prep.over_time(np, func=func)
            assert not valid[0, 0]
        _out, valid = prep.rate(np, is_counter=True, is_rate=True)
        assert not valid[0, 0]

    def test_single_sample_window(self):
        _t, _v, _l, prep = self._one([250], [5.0], [100], [400])
        out, valid = prep.over_time(np, func="stddev")
        assert valid[0, 0] and out[0, 0] == 0.0
        _out, rvalid = prep.rate(np, is_counter=True, is_rate=True)
        assert not rvalid[0, 0]  # rate needs >= 2 samples
        _out, ivalid = prep.instant_rate(np, per_second=True)
        assert not ivalid[0, 0]

    def test_reset_pair_straddling_window_start(self):
        # pair (t=90 v=10, t=150 v=2) is a reset, but t=90 is OUTSIDE the
        # window (100, 400] — the boundary refinement must NOT count it,
        # while the in-window reset (300: 8 -> 400: 1) must count
        _t, _v, _l, prep = self._one(
            [90, 150, 300, 400], [10, 2, 8, 1], [100], [400])
        out, valid = prep.changes_resets(np, kind="resets")
        assert valid[0, 0] and out[0, 0] == 1
        inc, _iv = prep.rate(np, is_counter=True, is_rate=False)
        # increase correction: only the in-window reset (+8), not (+10)
        ref = ref_rate(_t, _v, prep.base_ms, BASE + 100, BASE + 400,
                       300.0, True, False)
        assert abs(inc[0, 0] - ref) < 1e-9


class TestTiledVsOldKernels:
    """ulp-bounded equality against the dense kernels on randomized
    shapes (the dense path computes in f32 under jax, so bounds are
    f32-scale; `valid` must match exactly)."""

    def _cmp(self, name, new, valid_new, old, valid_old, k_real,
             rtol=2e-3, atol=None, scale=1.0):
        valid_new = np.asarray(valid_new)[:, :k_real]
        valid_old = np.asarray(valid_old)
        assert (valid_new == valid_old).all(), name
        a = np.asarray(new)[:, :k_real][valid_old]
        b = np.asarray(old)[valid_old]
        if atol is None:
            atol = 1e-5 * scale
        if len(a):
            err = np.abs(a - b) - (atol + rtol * np.abs(b))
            assert err.max() <= 0, (name, float(err.max()))

    def test_randomized(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(23)
        for trial in range(3):
            S = int(rng.integers(1, 24))
            t_all, v_all, lens = gen_series(rng, S,
                                            irregular=bool(trial % 2))
            w = float(rng.choice([60, 300]))
            step = float(rng.choice([60, 450]))
            K = int(rng.integers(1, 20))
            ends = BASE + float(rng.integers(0, 2000)) + np.arange(K) * step
            starts = ends - w
            prep = make_prep(t_all, v_all, lens, starts, ends)
            times, values, counts, base_ms = promops.prepare_matrix_runs(
                t_all, v_all, lens, dtype=np.float64)
            e_rel = jnp.asarray(ends - base_ms / 1000.0)
            s_rel = jnp.asarray(starts - base_ms / 1000.0)
            tj, vj, cj = (jnp.asarray(times), jnp.asarray(values),
                          jnp.asarray(counts))
            scale = float(np.abs(v_all).max()) if len(v_all) else 1.0
            o, ov = promops.extrapolated_rate(tj, vj, cj, s_rel, e_rel, w,
                                              True, True)
            n, nv = prep.rate(np, is_counter=True, is_rate=True)
            self._cmp("rate", n, nv, o, ov, prep.k_real, scale=scale)
            # the jnp path must agree with the numpy path on the same prep
            n2, nv2 = prep.rate(jnp, is_counter=True, is_rate=True)
            self._cmp("rate-jnp-vs-old", n2, nv2, o, ov, prep.k_real,
                      scale=scale)
            for func in ("sum", "min", "max", "avg", "stddev"):
                o, ov = promops.over_time(tj, vj, cj, s_rel, e_rel, func)
                n, nv = prep.over_time(np, func=func)
                # old stddev on 1-sample windows carries f32 cancellation
                # noise ~|v|*sqrt(eps); bound accordingly
                at = scale * 5e-3 if func in ("stddev", "stdvar") else None
                self._cmp(func, n, nv, o, ov, prep.k_real, atol=at,
                          scale=scale)
            o, ov = promops.instant_rate(tj, vj, cj, s_rel, e_rel, True)
            n, nv = prep.instant_rate(np, per_second=True)
            self._cmp("irate", n, nv, o, ov, prep.k_real, scale=scale)
            o, ov = promops.changes_resets(tj, vj, cj, s_rel, e_rel,
                                           "changes")
            n, nv = prep.changes_resets(np, kind="changes")
            self._cmp("changes", n, nv, o, ov, prep.k_real, scale=scale)


class TestPlanEligibility:
    def test_sub_ms_edges_fall_back(self):
        ends = BASE + np.arange(4) * 0.0001  # 0.1ms step: off the lattice
        assert promops.plan_tiles(ends - 60, ends, BASE_MS, BASE_MS + 10,
                                  max_tiles=10_000) is None

    def test_tile_cap_falls_back(self):
        ends = BASE + np.arange(4) * 1.0
        # one-second lattice over a huge span -> too many tiles
        assert promops.plan_tiles(ends - 1, ends, BASE_MS,
                                  BASE_MS + 10**10, max_tiles=1000) is None

    def test_gather_budget_falls_back(self):
        # everything in one tile -> occupancy == n, over a tiny budget
        # (the budget floor is 64 gather columns)
        t_all = BASE_MS + np.arange(200, dtype=np.int64)
        v_all = np.arange(200, dtype=np.float64)
        lens = np.asarray([200], np.int64)
        plan = promops.plan_tiles(np.asarray([BASE - 60.0]),
                                  np.asarray([BASE + 60.0]),
                                  int(t_all.min()), int(t_all.max()), 10_000)
        assert plan is not None
        assert promops.prepare_tiled(plan, t_all, v_all, lens,
                                     max_gather_cols=8) is None

    def test_plan_single_instant_window(self):
        plan = promops.plan_tiles(np.asarray([BASE - 300.0]),
                                  np.asarray([BASE + 0.0]),
                                  BASE_MS - 200_000, BASE_MS, 10_000)
        assert plan is not None and plan.win_tiles >= 1


# -- engine level -------------------------------------------------------------


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("prom")
    yield e, PromEngine(e)
    e.close()


def _write(e, name, series, start=BASE, step=15):
    lines = []
    for inst, vals in series.items():
        for i, v in enumerate(vals):
            lines.append(
                f"{name},instance={inst} value={v} {(start + i * step) * NS}")
    e.write_lines("prom", "\n".join(lines))


def _values_of(data):
    out = {}
    for row in data["result"]:
        key = tuple(sorted(row["metric"].items()))
        pts = row.get("values") or [row["value"]]
        out[key] = [(t, float(v)) for t, v in pts]
    return out


def _assert_results_close(a, b, rtol=2e-3, atol=1e-4):
    va, vb = _values_of(a), _values_of(b)
    assert va.keys() == vb.keys()
    for key in va:
        assert len(va[key]) == len(vb[key]), key
        for (t1, x1), (t2, x2) in zip(va[key], vb[key]):
            assert t1 == t2
            if math.isnan(x1) or math.isnan(x2):
                assert math.isnan(x1) and math.isnan(x2)
            else:
                assert abs(x1 - x2) <= atol + rtol * abs(x2), (key, x1, x2)


class TestEngineTiled:
    QUERIES = [
        "rate(m[2m])",
        "increase(m[2m])",
        "delta(m[2m])",
        "irate(m[2m])",
        "idelta(m[2m])",
        "sum_over_time(m[3m])",
        "min_over_time(m[3m])",
        "max_over_time(m[3m])",
        "avg_over_time(m[3m])",
        "count_over_time(m[3m])",
        "last_over_time(m[3m])",
        "stddev_over_time(m[3m])",
        "changes(m[5m])",
        "resets(m[5m])",
        "deriv(m[4m])",
        "predict_linear(m[4m], 600)",
        "rate(m[2m] offset 1m)",
        "max_over_time(rate(m[1m])[5m:30s])",
    ]

    def test_tiled_matches_dense_e2e(self, env, monkeypatch):
        e, pe = env
        rng = np.random.default_rng(5)
        series = {}
        for i in range(6):
            v = np.cumsum(rng.random(80) * 4)
            v[40 + i:] -= v[40 + i]  # a mid-series counter reset
            series[f"i{i}"] = np.round(v, 3)
        _write(e, "m", series)
        t0, t1 = BASE + 240, BASE + 1100
        for q in self.QUERIES:
            tiled = pe.query_range(q, t0, t1, 60, "prom")
            monkeypatch.setenv("OGT_PROM_TILED", "0")
            dense = pe.query_range(q, t0, t1, 60, "prom")
            monkeypatch.delenv("OGT_PROM_TILED")
            _assert_results_close(tiled, dense)

    def test_tiled_engages(self, env):
        from opengemini_tpu.utils.stats import GLOBAL as STATS

        e, pe = env
        _write(e, "m", {"a": np.arange(50.0)})
        before = STATS.snapshot().get("prom", {}).get("tiled_kernels", 0)
        pe.query_range("rate(m[2m])", BASE + 120, BASE + 600, 60, "prom")
        after = STATS.snapshot().get("prom", {}).get("tiled_kernels", 0)
        assert after == before + 1

    def test_non_lattice_step_still_answers(self, env):
        e, pe = env
        _write(e, "m", {"a": np.arange(50.0)})
        # 0.0001s step: ineligible for tiling, dense path must serve it
        r = pe.query_range("rate(m[2m])", BASE + 300, BASE + 300.001,
                           0.0005, "prom")
        assert r["resultType"] == "matrix"

    def test_stage_attribution_and_slowlog(self, env, monkeypatch):
        from opengemini_tpu.utils.slowlog import GLOBAL as SLOWLOG
        from opengemini_tpu.utils.stats import GLOBAL as STATS

        e, pe = env
        _write(e, "m", {"a": np.arange(50.0)})
        monkeypatch.setattr(SLOWLOG, "threshold_ms", 0.0)
        pe.query_range("rate(m[2m])", BASE + 120, BASE + 600, 60, "prom")
        snap = STATS.snapshot().get("query_stages", {})
        for st in ("prom_collect", "prom_prepare", "prom_kernel"):
            assert snap.get(f"{st}_count", 0) >= 1, st
        rec = SLOWLOG.snapshot()["records"][-1]
        assert rec["kind"] == "promql"
        assert rec["statement"] == "rate(m[2m])"
        assert any(k.startswith("prom_") for k in rec["stages_ms"])

    def test_bulk_read_default_and_knob(self, env, monkeypatch):
        e, pe = env
        _write(e, "m", {f"i{i}": np.arange(10.0) for i in range(3)})
        e.flush_all()
        calls = {"bulk": 0, "single": 0}
        shards = e.shards_for_range("prom", None, -(2**62), 2**62)
        for sh in shards:
            orig_bulk = sh.read_series_bulk
            orig_one = sh.read_series

            def bulk(*a, _o=orig_bulk, **kw):
                calls["bulk"] += 1
                return _o(*a, **kw)

            def one(*a, _o=orig_one, **kw):
                calls["single"] += 1
                return _o(*a, **kw)

            monkeypatch.setattr(sh, "read_series_bulk", bulk)
            monkeypatch.setattr(sh, "read_series", one)
        # default OGT_PROM_BULK_SIDS=1: bulk decode even for 3 series
        pe.query_range("rate(m[2m])", BASE + 120, BASE + 300, 60, "prom")
        assert calls["bulk"] >= 1 and calls["single"] == 0
        # raising the knob reverts small matches to the per-sid loop
        calls.update(bulk=0, single=0)
        monkeypatch.setenv("OGT_PROM_BULK_SIDS", "64")
        pe.query_range("rate(m[2m])", BASE + 120, BASE + 300, 60, "prom")
        assert calls["bulk"] == 0 and calls["single"] >= 1

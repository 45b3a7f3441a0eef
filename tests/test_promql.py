"""PromQL tests: parser, rate semantics vs a pure-python Prometheus oracle,
engine end-to-end over the storage engine."""

import math

import numpy as np
import pytest

from opengemini_tpu.ops import prom as promops
from opengemini_tpu.promql import parser as pp
from opengemini_tpu.promql.engine import PromEngine
from opengemini_tpu.storage.engine import Engine, NS

BASE = 1_700_000_000


# -- oracle: prometheus promql/functions.go extrapolatedRate ----------------


def prom_rate_oracle(times_s, values, t_end, window, is_counter=True, is_rate=True):
    sel = [(t, v) for t, v in zip(times_s, values) if t_end - window < t <= t_end]
    if len(sel) < 2:
        return None
    ts = [t for t, _ in sel]
    vs = [v for _, v in sel]
    delta = vs[-1] - vs[0]
    if is_counter:
        for i in range(1, len(vs)):
            if vs[i] < vs[i - 1]:
                delta += vs[i - 1]
    sampled = ts[-1] - ts[0]
    avg_iv = sampled / (len(sel) - 1)
    dur_start = ts[0] - (t_end - window)
    dur_end = t_end - ts[-1]
    thresh = avg_iv * 1.1
    if dur_start > thresh:
        dur_start = avg_iv / 2
    if dur_end > thresh:
        dur_end = avg_iv / 2
    if is_counter and delta > 0 and vs[0] >= 0:
        dur_zero = sampled * (vs[0] / delta)
        if dur_zero < dur_start:
            dur_start = dur_zero
    factor = (sampled + dur_start + dur_end) / sampled
    out = delta * factor
    if is_rate:
        out /= window
    return out


class TestParser:
    def test_selector_with_matchers(self):
        e = pp.parse('http_requests_total{job="api", code=~"5.."}')
        assert isinstance(e, pp.VectorSelector)
        assert e.metric == "http_requests_total"
        assert e.matchers[0] == pp.LabelMatcher("job", "=", "api")
        assert e.matchers[1].op == "=~"

    def test_rate_range(self):
        e = pp.parse("rate(http_requests_total[5m])")
        assert isinstance(e, pp.FunctionCall) and e.name == "rate"
        assert isinstance(e.args[0], pp.MatrixSelector)
        assert e.args[0].range_s == 300.0

    def test_aggregation_by(self):
        e = pp.parse("sum by (job) (rate(m[1m]))")
        assert isinstance(e, pp.Aggregation)
        assert e.op == "sum" and e.grouping == ["job"]
        e2 = pp.parse("sum(rate(m[1m])) by (job)")
        assert e2.grouping == ["job"]

    def test_binary_and_precedence(self):
        e = pp.parse("a + b * 2")
        assert isinstance(e, pp.BinaryOp) and e.op == "+"
        assert isinstance(e.rhs, pp.BinaryOp) and e.rhs.op == "*"

    def test_topk(self):
        e = pp.parse("topk(3, rate(m[5m]))")
        assert e.op == "topk" and isinstance(e.param, pp.NumberLit)

    def test_durations(self):
        assert pp.parse_duration_s("1h30m") == 5400.0
        assert pp.parse_duration_s("500ms") == 0.5

    def test_offset(self):
        e = pp.parse('m{a="b"} offset 5m')
        assert e.offset_s == 300.0

    @pytest.mark.parametrize("bad", ["rate(", "m{a=}", "sum by (", "m[xyz]"])
    def test_errors(self, bad):
        with pytest.raises(pp.PromParseError):
            pp.parse(bad)


class TestRateKernel:
    @pytest.mark.parametrize("is_counter,is_rate", [(True, True), (True, False), (False, False)])
    def test_extrapolated_rate_matches_oracle(self, rng, is_counter, is_rate):
        # irregular scrape times + counter resets
        n = 50
        times_s = np.sort(rng.uniform(0, 600, n))
        if is_counter:
            vals = np.cumsum(rng.uniform(0, 10, n))
            vals[30:] -= vals[30] * 0.9  # reset
        else:
            vals = rng.normal(size=n) * 10
        window = 120.0
        step_ends = np.arange(150.0, 600.0, 60.0)
        samples = [(np.asarray(times_s * 1000, dtype=np.int64), vals)]
        t, v, c, base_ms = promops.prepare_matrix(samples, dtype=np.float64)
        # oracle uses ms-truncated times like the kernel input
        times_trunc = np.asarray(times_s * 1000, dtype=np.int64) / 1000.0
        out, valid = promops.extrapolated_rate(
            t, v, c, step_ends - window - base_ms / 1000, step_ends - base_ms / 1000,
            window, is_counter, is_rate,
        )
        out, valid = np.asarray(out), np.asarray(valid)
        # the tiled production path must satisfy the same oracle
        t_ms = np.asarray(times_s * 1000, dtype=np.int64)
        plan = promops.plan_tiles(step_ends - window, step_ends,
                                  int(t_ms.min()), int(t_ms.max()), 100_000)
        assert plan is not None
        prep = promops.prepare_tiled(plan, t_ms, vals, np.asarray([n]),
                                     dtype=np.float64,
                                     max_gather_cols=10**6)
        t_out, t_valid = prep.rate(np, is_counter=is_counter,
                                   is_rate=is_rate)
        for k, te in enumerate(step_ends):
            ref = prom_rate_oracle(times_trunc, vals, te, window, is_counter, is_rate)
            if ref is None:
                assert not valid[0, k]
                assert not t_valid[0, k]
            else:
                assert valid[0, k]
                assert out[0, k] == pytest.approx(ref, rel=1e-9)
                assert t_valid[0, k]
                assert t_out[0, k] == pytest.approx(ref, rel=1e-9)

    def test_over_time(self, rng):
        times_s = np.arange(0, 300, 10.0)
        vals = rng.normal(size=len(times_s))
        samples = [(np.asarray(times_s * 1000, np.int64), vals)]
        t, v, c, base = promops.prepare_matrix(samples, dtype=np.float64)
        ends = np.array([100.0, 200.0])
        starts = ends - 60.0
        for func, ref_fn in (
            ("avg", np.mean), ("min", np.min), ("max", np.max), ("sum", np.sum),
        ):
            out, valid = promops.over_time(t, v, c, starts, ends, func)
            for k, te in enumerate(ends):
                sel = vals[(times_s > te - 60) & (times_s <= te)]
                assert np.asarray(out)[0, k] == pytest.approx(ref_fn(sel))


@pytest.fixture
def prom_env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("prom")
    yield e, PromEngine(e)
    e.close()


def write_counter(e, series: dict[str, list], start=BASE, step=15):
    """series: label-value -> list of counter values."""
    lines = []
    for inst, vals in series.items():
        for i, v in enumerate(vals):
            lines.append(
                f"http_requests_total,instance={inst},job=api value={v} "
                f"{(start + i * step) * NS}"
            )
    e.write_lines("prom", "\n".join(lines))


class TestEngine:
    def test_instant_vector(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [1, 2, 3], "b": [10, 20, 30]})
        data = pe.query_instant('http_requests_total{instance="a"}', BASE + 31, "prom")
        assert data["resultType"] == "vector"
        [r] = data["result"]
        assert r["metric"]["instance"] == "a"
        assert r["value"][1] == "3.0"

    def test_rate_range_query(self, prom_env):
        e, pe = prom_env
        # steady 2/sec counter, 15s scrapes over 10 min
        n = 40
        write_counter(e, {"a": [i * 30 for i in range(n)]})
        data = pe.query_range(
            "rate(http_requests_total[2m])", BASE + 300, BASE + 480, 60, "prom"
        )
        assert data["resultType"] == "matrix"
        [r] = data["result"]
        for t, v in r["values"]:
            assert float(v) == pytest.approx(2.0, rel=1e-6)

    def test_sum_by_job(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [0, 60], "b": [0, 120]})
        data = pe.query_range(
            "sum by (job) (rate(http_requests_total[2m]))",
            BASE + 15, BASE + 15, 60, "prom",
        )
        [r] = data["result"]
        assert r["metric"] == {"job": "api"}
        # prom rate divides the (non-extrapolatable, zero-start-clamped)
        # increase by the full 120s window: a=60/120, b=120/120
        assert float(r["values"][0][1]) == pytest.approx(1.5, rel=1e-9)

    def test_scalar_arith_and_comparison(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [5, 5, 5], "b": [1, 1, 1]})
        data = pe.query_instant("http_requests_total * 2", BASE + 31, "prom")
        vals = {r["metric"]["instance"]: float(r["value"][1]) for r in data["result"]}
        assert vals == {"a": 10.0, "b": 2.0}
        data = pe.query_instant("http_requests_total > 3", BASE + 31, "prom")
        assert [r["metric"]["instance"] for r in data["result"]] == ["a"]

    def test_vector_vector_binop(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [4], "b": [8]})
        lines = [
            f"errors_total,instance={i},job=api value={v} {BASE * NS}"
            for i, v in (("a", 1), ("b", 2))
        ]
        e.write_lines("prom", "\n".join(lines))
        data = pe.query_instant(
            "errors_total / http_requests_total", BASE + 10, "prom"
        )
        vals = {r["metric"]["instance"]: float(r["value"][1]) for r in data["result"]}
        assert vals == {"a": 0.25, "b": 0.25}

    def test_topk(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [1], "b": [9], "c": [5]})
        data = pe.query_instant("topk(2, http_requests_total)", BASE + 10, "prom")
        insts = sorted(r["metric"]["instance"] for r in data["result"])
        assert insts == ["b", "c"]

    def test_regex_matcher(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"web1": [1], "web2": [2], "db1": [3]})
        data = pe.query_instant(
            'http_requests_total{instance=~"web.*"}', BASE + 10, "prom"
        )
        assert len(data["result"]) == 2

    def test_stale_series_excluded(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [1]})  # single sample at BASE
        data = pe.query_instant("http_requests_total", BASE + 400, "prom")
        assert data["result"] == []  # beyond 5m lookback


class TestReviewRegressions:
    def test_anchored_regex_matcher(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"web1": [1], "web10": [2]})
        data = pe.query_instant(
            'http_requests_total{instance=~"web1"}', BASE + 10, "prom"
        )
        assert [r["metric"]["instance"] for r in data["result"]] == ["web1"]

    def test_invalid_regex_is_prom_error(self, prom_env):
        from opengemini_tpu.promql.engine import PromError

        e, pe = prom_env
        write_counter(e, {"a": [1]})
        with pytest.raises(PromError):
            pe.query_instant('http_requests_total{instance=~"["}', BASE + 10, "prom")

    def test_infinite_range_is_prom_error(self, prom_env):
        from opengemini_tpu.promql.engine import PromError

        e, pe = prom_env
        with pytest.raises(PromError):
            pe.query_range("up", float("inf"), float("inf"), 60, "prom")

    def test_power_right_associative_and_unary_minus(self, prom_env):
        e, pe = prom_env
        data = pe.query_instant("2^3^2", BASE, "prom")
        assert float(data["result"][1]) == 512.0
        data = pe.query_instant("-2^2", BASE, "prom")
        assert float(data["result"][1]) == -4.0

    def test_scalar_invalid_steps_are_nan(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [7]})  # one sample at BASE
        data = pe.query_range("scalar(http_requests_total)", BASE + 600, BASE + 600, 60, "prom")
        # beyond lookback: scalar must be NaN, not the stale sample;
        # NaN points still render (prom scalar always yields a value)
        [r] = data["result"]
        assert r["values"][0][1] == "NaN"

    def test_counter_negative_first_value_no_clamp(self, rng):
        # negative v_first with delta > 0: prom skips the zero-crossing clamp
        times_s = np.array([10.0, 20.0, 30.0])
        vals = np.array([-5.0, 0.0, 5.0])
        samples = [(np.asarray(times_s * 1000, np.int64), vals)]
        t, v, c, base = promops.prepare_matrix(samples, dtype=np.float64)
        ends = np.array([40.0]) - base / 1000  # kernel times are base-relative
        out, valid = promops.extrapolated_rate(t, v, c, ends - 60, ends, 60.0, True, False)
        ref = prom_rate_oracle(times_s, vals, 40.0, 60.0, True, False)
        assert np.asarray(out)[0, 0] == pytest.approx(ref, rel=1e-12)

    def test_over_time_prefix_path_with_nulls(self, rng):
        # irregular counts across series exercise the cumsum/gather path
        s1 = (np.array([1000, 3000, 5000], np.int64), np.array([1.0, 2.0, 3.0]))
        s2 = (np.array([2000], np.int64), np.array([10.0]))
        t, v, c, base = promops.prepare_matrix([s1, s2], dtype=np.float64)
        ends = np.array([6.0]) - base / 1000
        starts = ends - 10.0
        out, valid = promops.over_time(t, v, c, starts, ends, "sum")
        assert np.asarray(out)[0, 0] == 6.0
        assert np.asarray(out)[1, 0] == 10.0
        out, valid = promops.over_time(t, v, c, starts, ends, "count")
        assert np.asarray(out)[0, 0] == 3 and np.asarray(out)[1, 0] == 1


class TestNewFunctions:
    def test_changes_and_resets(self, prom_env):
        e, pe = prom_env
        # values: 1,1,2,2,1 -> changes 2 (1->2, 2->1); resets 1 (2->1)
        vals = [1, 1, 2, 2, 1]
        lines = "\n".join(
            f"m value={v} {(BASE + i * 15) * NS}" for i, v in enumerate(vals)
        )
        e.write_lines("prom", lines)
        data = pe.query_instant("changes(m[2m])", BASE + 61, "prom")
        assert float(data["result"][0]["value"][1]) == 2.0
        data = pe.query_instant("resets(m[2m])", BASE + 61, "prom")
        assert float(data["result"][0]["value"][1]) == 1.0

    def test_absent(self, prom_env):
        e, pe = prom_env
        write_counter(e, {"a": [1]})
        data = pe.query_instant("absent(http_requests_total)", BASE + 10, "prom")
        assert data["result"] == []  # present -> empty vector
        data = pe.query_instant("absent(nothing_here)", BASE + 10, "prom")
        assert data["result"][0]["value"][1] == "1.0"

    def test_histogram_quantile(self, prom_env):
        e, pe = prom_env
        buckets = [("0.1", 10), ("0.5", 50), ("1", 90), ("+Inf", 100)]
        lines = "\n".join(
            f'http_req_bucket,le={le},job=api value={c} {BASE * NS}'
            for le, c in buckets
        )
        e.write_lines("prom", lines)
        data = pe.query_instant(
            "histogram_quantile(0.5, http_req_bucket)", BASE + 10, "prom"
        )
        [r] = data["result"]
        assert r["metric"] == {"job": "api"}
        assert float(r["value"][1]) == pytest.approx(0.5)
        data = pe.query_instant(
            "histogram_quantile(0.9, http_req_bucket)", BASE + 10, "prom"
        )
        # rank 90 falls exactly at le=1 bucket boundary
        assert float(data["result"][0]["value"][1]) == pytest.approx(1.0)


class TestReviewRegressions2:
    def test_absent_carries_equality_matcher_labels(self, prom_env):
        e, pe = prom_env
        data = pe.query_instant(
            'absent(ghost{job="api", code=~"5.."})', BASE + 10, "prom"
        )
        [r] = data["result"]
        assert r["metric"] == {"job": "api"}  # eq matchers only

    def test_histogram_quantile_edge_q(self, prom_env):
        e, pe = prom_env
        lines = "\n".join(
            f'b_bucket,le={le} value={c} {BASE * NS}'
            for le, c in (("1", 50), ("+Inf", 100))
        )
        e.write_lines("prom", lines)
        data = pe.query_instant("histogram_quantile(1.5, b_bucket)", BASE + 5, "prom")
        assert data["result"][0]["value"][1] == "+Inf"
        data = pe.query_instant("histogram_quantile(-1, b_bucket)", BASE + 5, "prom")
        assert data["result"][0]["value"][1] == "-Inf"
        # rank beyond le=1 -> +Inf bucket wins -> previous bound
        data = pe.query_instant("histogram_quantile(0.99, b_bucket)", BASE + 5, "prom")
        assert float(data["result"][0]["value"][1]) == 1.0

    def test_histogram_quantile_negative_first_bucket(self, prom_env):
        e, pe = prom_env
        lines = "\n".join(
            f'nb_bucket,le={le} value={c} {BASE * NS}'
            for le, c in (("-1", 30), ("0.5", 60), ("+Inf", 100))
        )
        e.write_lines("prom", lines)
        data = pe.query_instant("histogram_quantile(0.1, nb_bucket)", BASE + 5, "prom")
        assert float(data["result"][0]["value"][1]) == -1.0  # bound, not interp


class TestSubqueries:
    """expr[range:step] — reference: promql subquery support in the
    lifted prometheus engine."""

    def _env(self, tmp_path):
        from opengemini_tpu.promql.engine import PromEngine
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path / "sq"))
        e.create_database("db")
        return e, PromEngine(e)

    def test_parse_shapes(self):
        from opengemini_tpu.promql import parser as pp

        sq = pp.parse("rate(m[1m])[10m:1m]")
        assert isinstance(sq, pp.Subquery)
        assert sq.range_s == 600 and sq.step_s == 60
        sq2 = pp.parse("sum(m)[5m:]")
        assert isinstance(sq2, pp.Subquery) and sq2.step_s is None
        sq3 = pp.parse("m[10m:30s] offset 2m")
        assert sq3.offset_s == 120

    def test_max_over_time_of_rate_subquery(self, tmp_path):
        """The canonical use: max_over_time(rate(m[1m])[10m:1m])."""
        e, pe = self._env(tmp_path)
        B = 1_700_000_000
        # counter rising 1/s for 5 min, then 11/s for 5 min
        lines = []
        total = 0
        for i in range(0, 600, 15):
            total += 15 * (1 if i < 300 else 11)
            lines.append(f"reqs value={total} {(B + i) * 10**9}")
        e.write_lines("db", "\n".join(lines))
        res = pe.query_range(
            "max_over_time(rate(reqs[1m])[5m:30s])",
            B + 600, B + 600, 30, db="db")
        v = float(res["result"][0]["values"][0][1])
        assert 10.0 <= v <= 12.0, v  # max rate ~11/s
        # and the plain avg is between the two regimes
        res = pe.query_range(
            "avg_over_time(rate(reqs[1m])[9m:30s])",
            B + 600, B + 600, 30, db="db")
        v = float(res["result"][0]["values"][0][1])
        assert 2.0 < v < 11.0, v

    def test_subquery_over_aggregation(self, tmp_path):
        e, pe = self._env(tmp_path)
        B = 1_700_000_000
        lines = []
        for i in range(0, 300, 30):
            lines.append(f"g,host=a value={i} {(B + i) * 10**9}")
            lines.append(f"g,host=b value={2 * i} {(B + i) * 10**9}")
        e.write_lines("db", "\n".join(lines))
        res = pe.query_range(
            "max_over_time(sum(g)[5m:30s])", B + 300, B + 300, 30, db="db")
        v = float(res["result"][0]["values"][0][1])
        assert v == 270 * 3  # max of sum = 270 + 540
        e.close()

    def test_unwrapped_subquery_rejected(self, tmp_path):
        e, pe = self._env(tmp_path)
        import pytest as _p

        from opengemini_tpu.promql.engine import PromError

        with _p.raises(PromError, match="wrapped"):
            pe.query_range("m[5m:1m]", 0, 0, 30, db="db")
        e.close()

    def test_zero_step_rejected(self, tmp_path):
        e, pe = self._env(tmp_path)
        import pytest as _p

        from opengemini_tpu.promql.engine import PromError

        with _p.raises(PromError, match="positive"):
            pe.query_range("max_over_time(m[5m:0s])", 0, 0, 30, db="db")
        e.close()

    def test_scalar_subquery_rejected(self, tmp_path):
        e, pe = self._env(tmp_path)
        import pytest as _p

        from opengemini_tpu.promql.engine import PromError

        with _p.raises(PromError, match="instant vector"):
            pe.query_range("max_over_time((2)[5m:1m])", 0, 0, 30, db="db")
        e.close()

    def test_nested_subquery_parses_and_runs(self, tmp_path):
        from opengemini_tpu.promql import parser as pp

        sq = pp.parse("max_over_time(m[5m:1m][10m:1m])")
        inner = sq.args[0]
        assert isinstance(inner, pp.Subquery)
        assert isinstance(inner.expr, pp.Subquery)
        # and it evaluates end to end (unwrapped inner subquery errors
        # inside _eval — wrap the nested one in a range fn instead)
        e, pe = self._env(tmp_path)
        B = 1_700_000_000
        e.write_lines("db", "\n".join(
            f"m value={i} {(B + i * 30) * 10**9}" for i in range(20)))
        res = pe.query_range(
            "max_over_time(max_over_time(m[2m:30s])[5m:1m])",
            B + 600, B + 600, 30, db="db")
        assert res["result"], res
        e.close()


class TestCountValuesAndRank:
    """count_values + vectorized topk/bottomk/quantile (config #5 surface).
    Oracle: hand-computed Prometheus semantics."""

    def _write(self, e, series):
        lines = []
        for inst, vals in series.items():
            for i, v in enumerate(vals):
                lines.append(
                    f"gauge_metric,instance={inst} value={v} "
                    f"{(BASE + i * 15) * NS}")
        e.write_lines("prom", "\n".join(lines))

    def test_count_values(self, prom_env):
        e, pe = prom_env
        self._write(e, {"a": [2, 2], "b": [2, 3], "c": [5, 3]})
        data = pe.query_instant('count_values("v", gauge_metric)',
                                BASE + 16, "prom")
        got = {r["metric"]["v"]: float(r["value"][1]) for r in data["result"]}
        # at t=BASE+16 the latest samples are a=2, b=3, c=3
        assert got == {"2.0": 1.0, "3.0": 2.0}

    def test_count_values_by_group(self, prom_env):
        e, pe = prom_env
        lines = []
        for inst, dc, v in [("a", "e", 1), ("b", "e", 1), ("c", "w", 1),
                            ("d", "w", 7)]:
            lines.append(f"m2,instance={inst},dc={dc} value={v} {BASE * NS}")
        e.write_lines("prom", "\n".join(lines))
        data = pe.query_instant('count_values by (dc) ("val", m2)',
                                BASE + 1, "prom")
        got = {(r["metric"]["dc"], r["metric"]["val"]): float(r["value"][1])
               for r in data["result"]}
        assert got == {("e", "1.0"): 2.0, ("w", "1.0"): 1.0,
                       ("w", "7.0"): 1.0}

    def test_topk_bottomk_values(self, prom_env):
        e, pe = prom_env
        self._write(e, {f"i{j}": [j] for j in range(10)})
        data = pe.query_instant("topk(3, gauge_metric)", BASE + 1, "prom")
        vals = sorted(float(r["value"][1]) for r in data["result"])
        assert vals == [7.0, 8.0, 9.0]
        data = pe.query_instant("bottomk(2, gauge_metric)", BASE + 1, "prom")
        vals = sorted(float(r["value"][1]) for r in data["result"])
        assert vals == [0.0, 1.0]

    def test_quantile_matches_scalar_oracle(self, prom_env):
        from opengemini_tpu.promql.engine import _prom_quantile

        e, pe = prom_env
        vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
        self._write(e, {f"i{j}": [v] for j, v in enumerate(vals)})
        for q in (0.0, 0.25, 0.5, 0.9, 1.0):
            data = pe.query_instant(f"quantile({q}, gauge_metric)",
                                    BASE + 1, "prom")
            [r] = data["result"]
            assert float(r["value"][1]) == pytest.approx(
                _prom_quantile(q, vals))

    def test_topk_partition_path_matches_argsort(self):
        """The O(R) partition keep-mask must agree with a full argsort
        oracle, including boundary ties and invalid cells."""
        import numpy as np

        from opengemini_tpu.promql.engine import _topk_keep

        rng = np.random.default_rng(3)
        for trial in range(30):
            R, K = rng.integers(2, 40), rng.integers(1, 6)
            # small value alphabet -> many exact ties
            vals = rng.integers(0, 5, size=(R, K)).astype(np.float64)
            valid = rng.random((R, K)) > 0.3
            n = int(rng.integers(1, R + 1))
            for desc in (True, False):
                got = _topk_keep(vals, valid, n, desc)
                # oracle: stable argsort of (key, row) per column
                for col in range(K):
                    cand = [(vals[r, col], r) for r in range(R)
                            if valid[r, col]]
                    cand.sort(key=lambda t: (-t[0] if desc else t[0], t[1]))
                    want = {r for _v, r in cand[:n]}
                    assert {r for r in range(R) if got[r, col]} == want, (
                        trial, col, n, desc)

    def test_topk_edge_cases(self, prom_env):
        import numpy as np

        from opengemini_tpu.promql.engine import _topk_keep

        # valid -Inf must beat invalid cells
        vals = np.array([[0.0], [-np.inf], [1.0]])
        valid = np.array([[False], [True], [True]])
        got = _topk_keep(vals, valid, 2, descending=True)
        assert got[:, 0].tolist() == [False, True, True]
        # negative n via the engine: empty result
        e, pe = prom_env
        self._write(e, {"a": [1], "b": [2]})
        data = pe.query_instant("topk(-1, gauge_metric)", BASE + 1, "prom")
        assert data["result"] == []

    def test_count_values_many_distinct_one_pass(self, prom_env):
        """Mostly-distinct values (the config-#5 shape) stay fast and
        correct: one unique+bincount pass, never distinct x cells."""
        e, pe = prom_env
        n = 3000
        self._write(e, {f"i{j:05d}": [j * 0.5] for j in range(n)})
        import time
        t0 = time.perf_counter()
        data = pe.query_instant('count_values("v", gauge_metric)',
                                BASE + 1, "prom")
        dt = time.perf_counter() - t0
        assert len(data["result"]) == n
        assert all(float(r["value"][1]) == 1.0 for r in data["result"])
        assert dt < 5.0, dt

    def test_topk_quantile_nan_inf_params(self, prom_env):
        """Folded NaN/Inf parameters must fail cleanly (PromError), not
        leak IndexError/OverflowError; NaN phi yields NaN results."""
        from opengemini_tpu.promql.engine import PromError
        e, pe = prom_env
        self._write(e, {"a": [1], "b": [2]})
        for q in ("topk(1/0, gauge_metric)", "topk(0/0, gauge_metric)",
                  "bottomk(-1/0, gauge_metric)"):
            with pytest.raises(PromError):
                pe.query_instant(q, BASE + 1, "prom")
        # quantile with NaN phi: every group is NaN, no crash
        data = pe.query_instant("quantile(0/0, gauge_metric)", BASE + 1, "prom")
        assert all(r["value"][1] == "NaN" for r in data["result"])

    def test_topk_keeps_nan_samples_when_room(self, prom_env):
        """Prometheus pushes NaN samples while the heap has room: topk(3)
        over [1, NaN] returns both series; topk(1) prefers the number."""
        e, pe = prom_env
        self._write(e, {"a": [1], "b": ["NaN"]})
        data = pe.query_instant("topk(3, gauge_metric)", BASE + 1, "prom")
        assert sorted(r["metric"]["instance"] for r in data["result"]) == ["a", "b"]
        data = pe.query_instant("topk(1, gauge_metric)", BASE + 1, "prom")
        assert [r["metric"]["instance"] for r in data["result"]] == ["a"]

    def test_quantile_nan_sample_poisons_group(self, prom_env):
        """A valid NaN sample in a group yields NaN (the +Inf invalid-cell
        padding must not surface as the quantile)."""
        e, pe = prom_env
        self._write(e, {"a": [1], "b": [3], "c": ["NaN"]})
        data = pe.query_instant("quantile(0.9, gauge_metric)", BASE + 1, "prom")
        assert [r["value"][1] for r in data["result"]] == ["NaN"]


class TestLazyAggFastPath:
    """topk/bottomk/count_values over high-cardinality selectors resolve
    labels AFTER selection (config #5); results must equal the eager
    path bit-for-bit."""

    @pytest.fixture()
    def hc(self, tmp_path):
        from opengemini_tpu.storage.engine import Engine

        e = Engine(str(tmp_path), sync_wal=False)
        e.create_database("hc")
        base = 1_700_000_000
        lines = "\n".join(
            f"m,sid=s{i},grp=g{i % 13} value={i * 7 % 4999} {base * NS}"
            for i in range(5000))
        e.write_lines("hc", lines)
        e.flush_all()
        from opengemini_tpu.promql.engine import PromEngine

        yield PromEngine(e), base
        e.close()

    @pytest.mark.parametrize("q", [
        "topk(5, m)", "bottomk(3, m)", 'count_values("v", m)',
        "topk(2, m{grp=\"g3\"})",
    ])
    def test_fast_matches_eager(self, hc, q, monkeypatch):
        pe, base = hc
        fast = pe.query_instant(q, base + 10, db="hc")
        monkeypatch.setattr(
            type(pe), "_collect_runs", lambda self, *a, **k: "few_series")
        eager = pe.query_instant(q, base + 10, db="hc")
        assert fast == eager, q


# -- vector matching: on/ignoring, group_left/right, set ops, bool --------
# Mirrors Prometheus' promql/testdata/operators.test fixture (the
# method/code error-rate join) — reference surface:
# lib/util/lifted/promql2influxql/binary_expr.go:308 (On/MatchKeys/
# MatchCard/IncludeKeys).

@pytest.fixture
def match_env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("prom")
    lines = []
    for method, code, v in (
        ("get", "500", 24), ("get", "404", 30), ("put", "501", 3),
        ("post", "500", 6), ("post", "404", 21),
    ):
        lines.append(
            f"http_errors,method={method},code={code} value={v} {BASE * NS}")
    for method, v in (("get", 600), ("del", 34), ("post", 120)):
        lines.append(f"http_requests,method={method} value={v} {BASE * NS}")
    e.write_lines("prom", "\n".join(lines))
    yield e, PromEngine(e)
    e.close()


def _vals(data):
    """result -> {frozenset(non-name labels): value}"""
    out = {}
    for r in data["result"]:
        key = frozenset(
            (k, v) for k, v in r["metric"].items() if k != "__name__")
        out[key] = float(r["value"][1])
    return out


class TestVectorMatching:
    def test_group_left_ignoring(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_errors / ignoring(code) group_left http_requests",
            BASE + 10, "prom")
        vals = _vals(data)
        assert vals == {
            frozenset({("method", "get"), ("code", "500")}): pytest.approx(24 / 600),
            frozenset({("method", "get"), ("code", "404")}): pytest.approx(30 / 600),
            frozenset({("method", "post"), ("code", "500")}): pytest.approx(6 / 120),
            frozenset({("method", "post"), ("code", "404")}): pytest.approx(21 / 120),
        }
        # no result carries a metric name after arithmetic
        assert all("__name__" not in r["metric"] for r in data["result"])

    def test_group_left_on(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_errors / on(method) group_left http_requests",
            BASE + 10, "prom")
        assert len(data["result"]) == 4

    def test_group_right_mirror(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_requests / on(method) group_right http_errors",
            BASE + 10, "prom")
        vals = _vals(data)
        # many side is now http_errors (rhs): same label sets, inverted values
        assert vals[frozenset({("method", "get"), ("code", "500")})] == \
            pytest.approx(600 / 24)
        assert len(vals) == 4

    def test_many_to_one_requires_group_left(self, match_env):
        e, pe = match_env
        with pytest.raises(ValueError, match="group_left"):
            pe.query_instant(
                "http_errors / ignoring(code) http_requests",
                BASE + 10, "prom")

    def test_duplicate_one_side_errors(self, match_env):
        e, pe = match_env
        # group_right makes the LHS the one side: http_errors has two
        # series per method after ignoring code -> duplicate-signature error
        with pytest.raises(ValueError, match="duplicate series"):
            pe.query_instant(
                "http_errors / ignoring(code) group_right http_requests",
                BASE + 10, "prom")

    def test_group_left_include_labels(self, match_env):
        e, pe = match_env
        # graft the one side's mode label onto the result
        e.write_lines("prom", f"capacity,method=get,mode=turbo value=2 {BASE * NS}")
        data = pe.query_instant(
            "http_errors * on(method) group_left(mode) capacity",
            BASE + 10, "prom")
        vals = _vals(data)
        assert vals == {
            frozenset({("method", "get"), ("code", "500"), ("mode", "turbo")}):
                pytest.approx(48.0),
            frozenset({("method", "get"), ("code", "404"), ("mode", "turbo")}):
                pytest.approx(60.0),
        }

    def test_one_to_one_on(self, match_env):
        e, pe = match_env
        # one-to-one with on(): output keeps only the on labels
        data = pe.query_instant(
            'http_errors{code="500"} / on(method) http_requests',
            BASE + 10, "prom")
        vals = _vals(data)
        assert vals == {
            frozenset({("method", "get")}): pytest.approx(24 / 600),
            frozenset({("method", "post")}): pytest.approx(6 / 120),
        }

    def test_one_to_one_ignoring_drops_label(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            'http_errors{code="500"} / ignoring(code) http_requests',
            BASE + 10, "prom")
        vals = _vals(data)
        assert frozenset({("method", "get")}) in vals

    def test_and(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_errors and on(method) http_requests", BASE + 10, "prom")
        vals = _vals(data)
        # put has no http_requests series -> dropped; labels + name kept
        assert len(vals) == 4
        assert frozenset({("method", "put"), ("code", "501")}) not in vals
        assert all("__name__" in r["metric"] for r in data["result"])
        assert vals[frozenset({("method", "get"), ("code", "500")})] == 24

    def test_unless(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_errors unless on(method) http_requests", BASE + 10, "prom")
        vals = _vals(data)
        assert list(vals) == [frozenset({("method", "put"), ("code", "501")})]

    def test_or(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_requests or on(method) http_errors", BASE + 10, "prom")
        vals = _vals(data)
        # all 3 lhs series, plus the rhs series whose method has no lhs
        # match: put (501) only
        assert len(vals) == 4
        assert vals[frozenset({("method", "put"), ("code", "501")})] == 3

    def test_or_full_label_match(self, match_env):
        e, pe = match_env
        # default many-to-many or: full label-set signature
        data = pe.query_instant(
            "http_requests or http_errors", BASE + 10, "prom")
        # nothing collides (different label sets) -> union of all 8
        assert len(data["result"]) == 8

    def test_bool_vector_scalar(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            "http_requests > bool 100", BASE + 10, "prom")
        vals = _vals(data)
        assert vals == {
            frozenset({("method", "get")}): 1.0,
            frozenset({("method", "del")}): 0.0,
            frozenset({("method", "post")}): 1.0,
        }
        assert all("__name__" not in r["metric"] for r in data["result"])

    def test_bool_vector_vector(self, match_env):
        e, pe = match_env
        data = pe.query_instant(
            'http_errors{code="500"} > bool on(method) http_requests',
            BASE + 10, "prom")
        vals = _vals(data)
        assert vals == {
            frozenset({("method", "get")}): 0.0,
            frozenset({("method", "post")}): 0.0,
        }

    def test_scalar_scalar_comparison_requires_bool(self, match_env):
        e, pe = match_env
        with pytest.raises(ValueError, match="BOOL"):
            pe.query_instant("1 > 2", BASE + 10, "prom")
        data = pe.query_instant("1 > bool 2", BASE + 10, "prom")
        assert data["result"][1] == "0.0"

    def test_filter_comparison_keeps_name(self, match_env):
        e, pe = match_env
        data = pe.query_instant("http_requests > 100", BASE + 10, "prom")
        assert sorted(r["metric"]["method"] for r in data["result"]) == \
            ["get", "post"]
        assert all(r["metric"]["__name__"] == "http_requests"
                   for r in data["result"])

    def test_atan2(self, match_env):
        e, pe = match_env
        import math as _m

        data = pe.query_instant(
            "http_requests atan2 http_requests", BASE + 10, "prom")
        for r in data["result"]:
            assert float(r["value"][1]) == pytest.approx(_m.atan2(1, 1) * 1)
        with pytest.raises(pp.PromParseError, match="bool"):
            pp.parse("a atan2 bool b")  # bool only on comparisons


class TestVectorMatchingParse:
    def test_parse_modifiers(self):
        e = pp.parse("a / on(job, instance) group_left(mode) b")
        assert e.matching.on is True
        assert e.matching.labels == ["job", "instance"]
        assert e.matching.card == "many-to-one"
        assert e.matching.include == ["mode"]
        e = pp.parse("a + ignoring(code) b")
        assert e.matching.on is False and e.matching.card == "one-to-one"
        e = pp.parse("a > bool b")
        assert e.bool_mod is True and e.matching is None
        e = pp.parse("a and b")
        assert e.matching.card == "many-to-many"

    def test_parse_errors(self):
        with pytest.raises(pp.PromParseError, match="bool"):
            pp.parse("a + bool b")
        with pytest.raises(pp.PromParseError, match="grouping"):
            pp.parse("a and on(x) group_left b")
        with pytest.raises(pp.PromParseError, match="ON and GROUP"):
            pp.parse("a / on(x) group_left(x) b")
        with pytest.raises(pp.PromParseError):
            pp.parse("a / group_left b")

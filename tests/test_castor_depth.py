"""Castor algorithm depth: the STL-style sudden-change
pipeline, fit/detect with persisted seasonal artifacts, and the stream
entry point. Reference: python/ts-udf/server/fit_detect.py:32
(FitDetectorUDF) + server/udf/sudden_increase_STL3.py; the
decomposition here is an original numpy implementation.
"""

from __future__ import annotations

import numpy as np
import pytest

from opengemini_tpu.query.executor import Executor
from opengemini_tpu.services import castor
from opengemini_tpu.storage.engine import Engine, NS

BASE = 1_700_000_040


@pytest.fixture
def env(tmp_path):
    e = Engine(str(tmp_path / "data"))
    e.create_database("db")
    yield e, Executor(e)
    e.close()


def _seasonal_series(n=240, period=3, noise=0.05, seed=3):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    prof = np.array([0.0, 2.0, -2.0])[t % period]
    return 10.0 + prof + rng.normal(0, noise, n)


class TestRobustDecompose:
    def test_recovers_seasonal_profile(self):
        v = _seasonal_series()
        trend, seasonal, resid, prof = castor.robust_decompose(v, period=3)
        # profile is centered and close to [0, 2, -2]
        assert abs(prof.mean()) < 1e-9
        assert prof[1] == pytest.approx(2.0, abs=0.3)
        assert prof[2] == pytest.approx(-2.0, abs=0.3)
        assert resid.std() < 0.5

    def test_outliers_do_not_drag_trend(self):
        v = _seasonal_series()
        v[100] += 500.0  # massive spike
        trend, _s, _r, _p = castor.robust_decompose(v, period=3)
        assert abs(trend[100] - 10.0) < 2.0  # median trend unmoved


class TestSuddenChange:
    def test_flags_sudden_increase(self):
        v = _seasonal_series()
        v[200] += 8.0
        mask = castor.stl_sudden_change(v)
        assert mask[200]
        assert mask.sum() <= 3  # no mass false positives

    def test_flags_sudden_decrease(self):
        v = _seasonal_series()
        v[190] -= 8.0
        mask = castor.stl_sudden_change(v)
        assert mask[190]

    def test_quiet_series_is_clean(self):
        v = _seasonal_series()
        mask = castor.stl_sudden_change(v)
        assert mask.sum() == 0

    def test_detect_sql_surface(self, env):
        e, ex = env
        v = _seasonal_series(120)
        v[100] += 9.0
        lines = "\n".join(
            f"m value={x} {(BASE + i) * NS}" for i, x in enumerate(v))
        e.write_lines("db", lines)
        res = ex.execute("SELECT detect(value, 'stl') FROM m", db="db")
        rows = res["results"][0]["series"][0]["values"]
        flagged_times = {r[0] for r in rows}
        assert len(rows) >= 1
        # the spike's timestamp is among the flagged rows
        assert (BASE + 100) * NS in flagged_times


class TestFitDetectPipeline:
    def test_fit_persists_seasonal_artifact(self):
        v = _seasonal_series()
        model = castor.fit("stl", v)
        assert model["algorithm"] == "stl"
        assert len(model["params"]["seasonal"]) == model["params"]["period"]
        assert model["params"]["resid_std"] > 0
        # scoring NEW data against the trained profile: in-profile points
        # pass, a level break is flagged at every broken point
        fresh = _seasonal_series(seed=99)
        assert castor.detect_fitted(model, fresh).sum() == 0
        broken = fresh + 6.0
        assert castor.detect_fitted(model, broken).all()

    def test_create_model_sql_roundtrip(self, env):
        e, ex = env
        v = _seasonal_series(120)
        lines = "\n".join(
            f"m value={x} {(BASE + i) * NS}" for i, x in enumerate(v))
        e.write_lines("db", lines)
        res = ex.execute(
            "CREATE MODEL seasonal1 WITH ALGORITHM 'stl' FROM "
            "(SELECT value FROM m)", db="db")
        assert "error" not in res["results"][0], res
        res = ex.execute("SHOW MODELS", db="db")
        names = [r[0] for r in res["results"][0]["series"][0]["values"]]
        assert "seasonal1" in names
        # new data breaking the profile scores against the ARTIFACT
        lines = "\n".join(
            f"m2 value={x + 7.0} {(BASE + i) * NS}"
            for i, x in enumerate(_seasonal_series(30, seed=5)))
        e.write_lines("db", lines)
        res = ex.execute("SELECT detect(value, 'seasonal1') FROM m2",
                         db="db")
        rows = res["results"][0]["series"][0]["values"]
        assert len(rows) == 30  # every shifted point flagged


class TestStreamEntryPoint:
    def test_incremental_scoring_matches_batch_tail(self):
        v = _seasonal_series()
        v[220] += 9.0
        sd = castor.StreamDetector("sigma", history=1024)
        out = []
        for lo in range(0, len(v), 40):  # arrive in ingest-sized batches
            out.append(sd.push(v[lo:lo + 40]))
        mask = np.concatenate(out)
        assert mask[220]
        assert mask.shape == v.shape

    def test_stream_with_fitted_model(self):
        model = castor.fit("stl", _seasonal_series())
        sd = castor.StreamDetector("stl", model=model)
        clean = sd.push(_seasonal_series(30, seed=11))
        assert clean.sum() == 0
        assert sd.push(_seasonal_series(30, seed=11) + 6.0).all()

    def test_history_ring_is_bounded(self):
        sd = castor.StreamDetector("mad", history=64)
        for _ in range(100):
            sd.push(np.ones(10))
        assert len(sd._ring) == 64

    def test_unknown_algorithm_rejected(self):
        with pytest.raises(ValueError, match="unknown"):
            castor.StreamDetector("nope")


class TestReviewRegressions:
    def test_fitted_stl_phase_alignment(self):
        """A scored window starting mid-cycle must NOT produce systematic
        false anomalies: the fitted scorer aligns the seasonal profile by
        best fit."""
        v = _seasonal_series()
        model = castor.fit("stl", v)
        fresh = _seasonal_series(90, seed=42)
        for shift in (1, 2):
            assert castor.detect_fitted(model, fresh[shift:]).sum() == 0

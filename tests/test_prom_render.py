"""A PromQL matrix answer rendered in bulk (`promql/render.py`,
`native/render.cpp`) is, byte for byte, what `json.dumps` makes of the tree
`PromEngine.query_range` returns: over value sets that cover every branch of
`repr(float)`, over masks, with the native core and with it forced off, for
labels that need escaping, in the dict path's series order, and through the
served `/api/v1/query_range` on the benchmark's own counter fleet, small."""

import json
import os
import sys
import urllib.parse
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import load_module  # noqa: E402
from harness.oracle import TOL  # noqa: E402

from opengemini_tpu.promql import render  # noqa: E402
from opengemini_tpu.promql.engine import Frame  # noqa: E402
from opengemini_tpu.server.http import HttpService  # noqa: E402
from opengemini_tpu.storage.engine import Engine  # noqa: E402
from opengemini_tpu.utils.stats import GLOBAL as STATS  # noqa: E402

S, K = 12, 9
T0 = 1_451_606_400


def _rng():
    return np.random.default_rng(28)


def _bits(n):
    v = _rng().integers(0, 2**64, size=4 * n, dtype=np.uint64).view(np.float64)
    return v[np.isfinite(v)][:n]


def _fill(pool):
    return np.resize(np.asarray(pool), S * K).reshape(S, K)


VALUES = {
    "random_f64": lambda: _rng().random((S, K)) * 1e3,
    "random_bits": lambda: _fill(_bits(S * K)),
    "float32_frame": lambda: (_rng().random((S, K)) * 1e3).astype(np.float32),
    "float32_tiny": lambda: _fill(np.float32([1e-45, 1.1754944e-38, 3.4028235e38,
                                              0.1, 1 / 3, 16777216.0])),
    "integers": lambda: _fill(np.arange(-40, 68) * 1.0),
    "int64_frame": lambda: _fill(np.arange(S * K, dtype=np.int64) * 10**9),
    "zeros": lambda: _fill([0.0, -0.0]),
    "nonfinite": lambda: _fill([np.nan, np.inf, -np.inf, 1.5, -np.nan]),
    "subnormal": lambda: _fill([5e-324, 1e-323, 2.2250738585072014e-308,
                                2.225073858507201e-308, -5e-324]),
    "around_1e-4": lambda: _fill([1e-4, 9.999999999999999e-5, 1e-5, 0.00012345,
                                  1.0000000000000002e-4, -1e-4, -9.9e-5]),
    "around_1e16": lambda: _fill([1e16, 9999999999999998.0, 1e15, 1e17,
                                  1.2345678901234568e17, 123456789012345.6,
                                  -1e16, 2.0**53, 2.0**63, 1e22, 1e23]),
    "largest": lambda: _fill([1.7976931348623157e308, -1.7976931348623157e308,
                              8.98846567431158e307]),
}

MASKS = {
    "all_valid": lambda: np.ones((S, K), bool),
    "leading_gap": lambda: np.arange(K)[None, :].repeat(S, 0) >= 3,
    "ragged": lambda: _rng().random((S, K)) < 0.6,
    "one_series_empty": lambda: np.arange(S)[:, None].repeat(K, 1) != 4,
    "nothing_valid": lambda: np.zeros((S, K), bool),
}

STEPS = {
    "integral": T0 + np.arange(K) * 60.0,
    "fractional": T0 + 0.25 + np.arange(K) * 0.1,
}


def _labels(n):
    return [{"__name__": "m", "instance": f"10.0.0.{(7 * i) % n}:9100",
             "code": str(200 + i % 3)} for i in range(n)]


def _parent_body(frame, steps) -> bytes:
    """What the server sent before the bulk renderer: the dict, dumped."""
    doc = {"status": "success", "data": render.matrix_dict(frame, steps)}
    return (json.dumps(doc, allow_nan=False) + "\n").encode("utf-8")


def _body(frame, steps) -> bytes:
    return b'{"status": "success", "data": ' \
        + render.matrix_json(frame, steps) + b"}\n"


@pytest.fixture(params=["native", "python"])
def core(request, monkeypatch):
    """Both cores behind `matrix_json`: the library, and the bulk Python
    path a machine without it runs."""
    if request.param == "python":
        monkeypatch.setattr(render, "load", lambda: None)
    elif render.load() is None:
        pytest.fail("native/render.cpp did not build or load")
    return request.param


def _points():
    c = STATS.counters("prom")
    return c.get("render_points", 0), c.get("render_native_points", 0)


@pytest.mark.parametrize("values", sorted(VALUES))
def test_value_sets_are_byte_equal(core, values):
    for mask in ("all_valid", "ragged"):
        frame = Frame(_labels(S), VALUES[values](), MASKS[mask]())
        for steps in STEPS.values():
            assert _body(frame, steps) == _parent_body(frame, steps), mask


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_masks_are_byte_equal_and_counted(core, mask):
    frame = Frame(_labels(S), VALUES["random_f64"](), MASKS[mask]())
    before = _points()
    assert _body(frame, STEPS["integral"]) \
        == _parent_body(frame, STEPS["integral"])
    n = int(frame.valid.sum())
    after = _points()
    assert after[0] - before[0] == n
    assert after[1] - before[1] == (n if core == "native" else 0)


def test_an_empty_frame_and_a_scalar(core):
    empty = Frame([], np.zeros((0, K)), np.zeros((0, K), bool))
    assert _body(empty, STEPS["integral"]) == _parent_body(empty, STEPS["integral"])
    assert render.matrix_json(empty, STEPS["integral"]) \
        == b'{"resultType": "matrix", "result": []}'
    scalar = Frame.scalar(0.1 + 0.2, K)
    assert _body(scalar, STEPS["fractional"]) \
        == _parent_body(scalar, STEPS["fractional"])


def test_labels_that_need_escaping(core):
    labels = [
        {"__name__": "m", "path": 'say "hi"\\now', "host": "zürich-ü"},
        {"__name__": "m", "path": '"metric": {"values": [[1, "2"]]}', "host": "a"},
        {"__name__": "m", "path": "日本語\t\n\x01", "host": "%s %d %%"},
        {},
    ]
    n = len(labels)
    frame = Frame(labels, _rng().random((n, K)), np.ones((n, K), bool))
    body = _body(frame, STEPS["integral"])
    assert body == _parent_body(frame, STEPS["integral"])
    got = json.loads(body)["data"]["result"]
    assert sorted(map(json.dumps, (s["metric"] for s in got))) \
        == sorted(map(json.dumps, labels))


def test_series_order_is_the_dict_paths(core):
    rng = _rng()
    n = 200
    # label sets that differ in which keys they have, duplicates included:
    # the order is by sorted items, stable for equal sets
    labels = [{k: str(int(rng.integers(0, 3)))
               for k in ("a", "b", "c")[: int(rng.integers(0, 4))]}
              for _ in range(n)]
    values = np.arange(n * K, dtype=float).reshape(n, K)
    frame = Frame(labels, values, rng.random((n, K)) < 0.5)
    want = render.matrix_dict(frame, STEPS["integral"])["result"]
    got = json.loads(render.matrix_json(frame, STEPS["integral"]))["result"]
    assert got == want
    assert [s["metric"] for s in got] \
        == sorted((s["metric"] for s in got), key=lambda m: sorted(m.items()))


def test_the_formatter_is_reprs_over_random_bit_patterns():
    if render.load() is None:
        pytest.fail("native/render.cpp did not build or load")
    v = np.concatenate([_bits(200_000), _rng().random(50_000) * 1e-3,
                        np.round(_rng().random(50_000) * 1e17),
                        np.float32(_rng().random(50_000)).astype(np.float64),
                        [np.nan, np.inf, -np.inf, 0.0, -0.0]])
    assert render.repr_floats(v) == list(map(repr, v.tolist()))


# -- through the server -------------------------------------------------------


def _http(port, method, path, body=None, **params):
    url = f"http://127.0.0.1:{port}{path}?" + urllib.parse.urlencode(params)
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, r.read()


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """The benchmark's `prom-counters` deployment at 60 series, loaded over
    /write, with its reference."""
    with open(os.path.join(BENCH, "configs", "prom-counters-10k.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(cfg["dry_run"])
    mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                      "prom_reference")
    ref = mod.Reference(cfg, 28)
    engine = Engine(str(tmp_path_factory.mktemp("prom") / "data"))
    engine.create_database(ref.db)
    svc = HttpService(engine, "127.0.0.1", 0)
    svc.start()
    for body, _ in ref.load_requests():
        assert _http(svc.port, "POST", "/write", bytes(body), db=ref.db)[0] == 204
    yield svc, ref
    svc.stop()
    engine.close()


def test_the_served_body_is_the_dict_paths_dump_and_the_oracles(fleet):
    svc, ref = fleet
    stmt = {"start": ref.start_s + 300, "end": ref.start_s + 3540,
            "step_s": 60, "range_s": 300}
    q = "rate(http_requests_total[5m])"
    status, body = _http(svc.port, "GET", "/api/v1/query_range", db=ref.db,
                         query=q, start=stmt["start"], end=stmt["end"],
                         step=stmt["step_s"])
    assert status == 200
    tree = svc.prom.query_range(q, float(stmt["start"]), float(stmt["end"]),
                                float(stmt["step_s"]), ref.db)
    want = json.dumps({"status": "success", "data": tree}, allow_nan=False) + "\n"
    assert body == want.encode("utf-8")
    assert body.count(b'"metric"') == ref.series
    err, limit = ref.numbers(stmt, ref.parse(stmt, json.loads(body)))["rate_rel_err"]
    assert limit == TOL["rate"] and err <= limit

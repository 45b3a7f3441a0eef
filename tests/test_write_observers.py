"""Write observers are handed a lazy view of the write (WrittenPoints):
rows become point tuples only when an observer that has work reads them,
once for all of them.  With no stream and no subscription on the written
database nothing is built; with one, the observer reads exactly the rows
ColumnarBatch.to_points gives, in body order, after the commit."""

from __future__ import annotations

import time

import pytest

from opengemini_tpu.ingest import native_lp
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.record import FieldType
from opengemini_tpu.services.stream import StreamService
from opengemini_tpu.services.subscriber import SubscriberManager, points_to_lines
from opengemini_tpu.storage import engine as engmod
from opengemini_tpu.storage.engine import Engine, WrittenPoints
from opengemini_tpu.utils.stats import GLOBAL as STATS

from test_subscriber_chunked import _Sink

NS = 1_000_000_000
BASE = 1_700_000_040

pytestmark = pytest.mark.skipif(native_lp.load() is None,
                                reason="native line-protocol parser not built")


def _counts() -> tuple[int, int]:
    w = STATS.snapshot().get("write", {})
    return (w.get("observer_rows_offered", 0), w.get("observer_rows_built", 0))


def _delta(before) -> tuple[int, int]:
    offered, built = _counts()
    return offered - before[0], built - before[1]


def _cpu_body(n: int, t0: int = BASE, mst: str = "cpu") -> str:
    return "\n".join(
        f"{mst},host=h{i % 40},region=r{i % 3} usage_user={i * 0.5},"
        f"usage_system={i % 7}i {(t0 + i) * NS}" for i in range(n))


@pytest.fixture(params=["segmented", "inline"])
def cuts(request, monkeypatch):
    """Both ways a natively parsed body is applied, on any host: bodies
    of 8 KiB and more take _write_segmented, or none does.  The list
    records how many segments each body was cut into."""
    monkeypatch.setattr(engmod, "_INGEST_WORKERS", 4)
    monkeypatch.setattr(engmod, "_ingest_pool_obj", None)
    monkeypatch.setattr(engmod, "_INGEST_SEGMENT_BYTES",
                        4096 if request.param == "segmented" else 1 << 40)
    cuts = []
    split = engmod._split_lp_segments

    def spy(raw, n):
        segs = split(raw, n)
        cuts.append(len(segs))
        return segs
    monkeypatch.setattr(engmod, "_split_lp_segments", spy)
    yield cuts
    monkeypatch.setattr(engmod, "_ingest_pool_obj", None)


@pytest.fixture
def served(tmp_path):
    """An engine with both of a server's observers registered, as
    server/app.py does at start-up."""
    e = Engine(str(tmp_path / "data"))
    e.create_database("db")
    streams = StreamService(e, interval_s=3600)
    subs = SubscriberManager(e)
    yield e, Executor(e), streams
    subs.stop()
    e.close()


def _q(ex, text):
    res = ex.execute(text, db="db", now_ns=(BASE + 100_000) * NS)
    assert "error" not in res["results"][0], res
    return res


# -- (a) nobody subscribed: nothing is built ------------------------------


def _segmented(cuts, request) -> None:
    """The last body took the path the case names."""
    took = bool(cuts) and cuts[-1] > 1
    assert took == ("segmented" in request.node.name), cuts


def test_no_stream_no_subscription_builds_nothing(served, cuts, request,
                                                  monkeypatch):
    e, _ex, _streams = served
    rows = 10_000

    def never(self):
        raise AssertionError("to_points called with nobody subscribed")
    monkeypatch.setattr(native_lp.ColumnarBatch, "to_points", never)
    before = _counts()
    assert e.write_lines("db", _cpu_body(rows)) == rows
    _segmented(cuts, request)
    assert _delta(before) == (rows, 0)


def test_a_write_that_holds_its_points_is_wrapped_not_rebuilt(served):
    e, _ex, _streams = served
    seen = []
    e.add_write_observer(lambda db, rp, points: seen.append(points))
    before = _counts()
    # an escaped space needs the exact Python parser: it has the points
    e.write_lines("db", f"m,host=a\\ b v=1.5 {BASE * NS}\nm,host=c v=2 "
                        f"{(BASE + 1) * NS}")
    rows = [("m", (("host", "x"),), (BASE + 2) * NS,
             {"v": (FieldType.FLOAT, 3.0)})]
    e.write_rows("db", rows)
    assert [len(v) for v in seen] == [2, 1]
    assert seen[0][0][1] == (("host", "a b"),)
    assert list(seen[1]) == rows and seen[1][0] is rows[0]
    assert _delta(before) == (3, 0)


# -- (b) the view against to_points ---------------------------------------


def _ts(i: int) -> int:
    return (BASE + i) * NS


BODIES = {
    "float": "\n".join(f"m,host=h{i % 5} v={i * 0.25} {_ts(i)}"
                       for i in range(300)),
    "int": "\n".join(f"m,host=h{i % 5} v={i - 150}i {_ts(i)}"
                     for i in range(300)),
    "bool": "\n".join(f"m,host=h{i % 5} v={'true' if i % 3 else 'false'} "
                      f"{_ts(i)}" for i in range(300)),
    "string": "\n".join(f'm,host=h{i % 5} v="s {i}" {_ts(i)}'
                        for i in range(300)),
    "two_measurements": "\n".join(
        (f"cpu,host=h{i % 5} user={i}i,idle={i * 0.5} {_ts(i)}" if i % 2
         else f'disk,dev=d{i % 3} free={i}i,label="x{i}" {_ts(i)}')
        for i in range(300)),
    "sparse": "\n".join(
        f"m,host=h{i % 5} " + ",".join(
            f"f{k}={i + k}" for k in range(4) if (i + k) % 3) + f" {_ts(i)}"
        for i in range(300)),
}


def _cut(body: str, parts: int) -> list[bytes]:
    lines = body.split("\n")
    step = -(-len(lines) // parts)
    return [("\n".join(lines[i:i + step]) + "\n").encode()
            for i in range(0, len(lines), step)]


@pytest.mark.parametrize("parts", [1, 3], ids=["one_batch", "three_segments"])
@pytest.mark.parametrize("shape", sorted(BODIES))
def test_view_reads_as_to_points(shape, parts):
    batches = [native_lp.parse_columnar(seg, "ns", 0)
               for seg in _cut(BODIES[shape], parts)]
    want = [p for b in batches for p in b.to_points()]
    assert len(want) == 300
    calls = []
    real = native_lp.ColumnarBatch.to_points

    class Counted(native_lp.ColumnarBatch):
        __slots__ = ()

        def to_points(self):
            calls.append(len(self))
            return real(self)
    for b in batches:
        b.__class__ = Counted
    before = _counts()
    view = WrittenPoints(batches)
    assert len(view) == 300 and not calls
    first = list(view)                       # one reader ...
    second = [p for p in view]               # ... and another
    assert first == want == second
    assert all(a is b for a, b in zip(first, second))
    assert view[0] == want[0] and view[-1] == want[-1] and view[7:9] == want[7:9]
    assert len(view) == 300
    assert len(calls) == parts and sum(calls) == 300
    assert _delta(before) == (0, 300)
    for (_m, _tags, _t, fields), (_, _, _, wf) in zip(first, want):
        for name, (ftype, v) in fields.items():
            assert type(v) is type(wf[name][1]) and ftype == wf[name][0]


# -- (c) a stream created between two writes ------------------------------


def test_create_stream_between_writes(served, cuts, request):
    e, ex, streams = served
    rows = 2_000
    before = _counts()
    e.write_lines("db", _cpu_body(rows, BASE))                 # write N
    assert _delta(before) == (rows, 0)
    _q(ex, "CREATE STREAM s1 ON SELECT sum(usage_user), count(usage_user) "
           "INTO cpu_1h FROM cpu GROUP BY time(1h), region")
    t1 = BASE - BASE % 3600 + 7200                             # one window
    body = _cpu_body(rows, t1) + "\n" + _cpu_body(5, t1, mst="other")
    e.write_lines("db", body)                                  # write N+1
    _segmented(cuts, request)
    assert _delta(before) == (2 * rows + 5, rows + 5)
    assert streams.handle(now_ns=(t1 + 2 * 3600) * NS) == 3
    out = _q(ex, "SELECT sum, count FROM cpu_1h GROUP BY region")
    got = {s["tags"]["region"]: s["values"][0][1:]
           for s in out["results"][0]["series"]}
    assert got == {f"r{r}": [sum(i * 0.5 for i in range(r, rows, 3)),
                             len(range(r, rows, 3))] for r in range(3)}


# -- (d) a subscription created between two writes ------------------------


def test_create_subscription_between_writes(served, cuts, request):
    e, ex, _streams = served
    rows = 2_000
    sink = _Sink()
    try:
        before = _counts()
        e.write_lines("db", _cpu_body(rows, BASE))             # write N
        assert _delta(before) == (rows, 0)
        _q(ex, f"CREATE SUBSCRIPTION sub ON db DESTINATIONS ALL "
               f"'http://127.0.0.1:{sink.port}'")
        _q(ex, "CREATE STREAM s1 ON SELECT max(usage_user) INTO cpu_max "
               "FROM cpu GROUP BY time(1h)")
        body = BODIES["two_measurements"] + "\n" + _cpu_body(rows, BASE + 500)
        n = body.count("\n") + 1
        e.write_lines("db", body)                              # write N+1
        _segmented(cuts, request)
        # the stream and the subscription both read it: one build
        assert _delta(before) == (rows + n, n)
        deadline = time.time() + 10
        while not sink.bodies and time.time() < deadline:
            time.sleep(0.02)
        # what the parent commit forwards: its observers were handed
        # to_points() of the parsed body
        parent = points_to_lines(
            native_lp.parse_columnar(body.encode(), "ns", 0).to_points())
        assert sink.bodies == [parent]
        assert parent.count("\n") + 1 == n
    finally:
        sink.stop()


# -- (e) a failing observer -----------------------------------------------


def test_an_observer_that_raises_fails_nothing(tmp_path):
    e = Engine(str(tmp_path / "data"))
    try:
        e.create_database("db")
        got = []

        def bad(db, rp, points):
            raise RuntimeError("observer bug")
        e.add_write_observer(bad)
        e.add_write_observer(lambda db, rp, points: got.append(
            (db, rp, type(points), len(points), points[2][0])))
        before = _counts()
        assert e.write_lines("db", BODIES["int"]) == 300
        assert got == [("db", "autogen", WrittenPoints, 300, "m")]
        assert _delta(before) == (300, 300)
        res = Executor(e).execute("SELECT count(v) FROM m", db="db",
                                  now_ns=(BASE + 100_000) * NS)
        assert res["results"][0]["series"][0]["values"][0][1] == 300
    finally:
        e.close()

"""Black-box HTTP API tests: a live server, line-protocol writes, InfluxQL
queries over the wire (reference: tests/ black-box suite, SURVEY.md §4.5)."""

import gzip
import json
import urllib.parse
import urllib.request

import pytest

from opengemini_tpu.server.http import HttpService
from opengemini_tpu.storage.engine import Engine, NS

BASE = 1_700_000_040


@pytest.fixture
def server(tmp_path):
    engine = Engine(str(tmp_path / "data"))
    engine.create_database("db")
    svc = HttpService(engine, "127.0.0.1", 0)  # ephemeral port
    svc.start()
    yield svc
    svc.stop()
    engine.close()


def _url(svc, path, **params):
    return f"http://127.0.0.1:{svc.port}{path}?" + urllib.parse.urlencode(params)


def post(svc, path, body=b"", headers=None, **params):
    req = urllib.request.Request(
        _url(svc, path, **params), data=body, headers=headers or {}, method="POST"
    )
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def post_full(svc, path, body=b"", headers=None, **params):
    req = urllib.request.Request(
        _url(svc, path, **params), data=body, headers=headers or {},
        method="POST")
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def get(svc, path, **params):
    try:
        with urllib.request.urlopen(_url(svc, path, **params)) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_ping(server):
    status, _ = get(server, "/ping")
    assert status == 204


def test_health(server):
    status, body = get(server, "/health")
    assert status == 200
    assert json.loads(body)["status"] == "pass"


def test_write_and_query_roundtrip(server):
    lines = f"cpu,host=h1 usage=0.5 {BASE * NS}\ncpu,host=h1 usage=1.5 {(BASE + 60) * NS}"
    status, _ = post(server, "/write", lines.encode(), db="db")
    assert status == 204
    status, body = get(server, "/query", db="db", q="SELECT mean(usage) FROM cpu", epoch="ns")
    assert status == 200
    res = json.loads(body)
    s = res["results"][0]["series"][0]
    assert s["values"][0][1] == 1.0


def test_rfc3339_time_format_default(server):
    post(server, "/write", f"m v=1 {BASE * NS}".encode(), db="db")
    _, body = get(server, "/query", db="db", q="SELECT v FROM m")
    s = json.loads(body)["results"][0]["series"][0]
    assert s["values"][0][0] == "2023-11-14T22:14:00Z"


def test_epoch_seconds(server):
    post(server, "/write", f"m v=1 {BASE * NS}".encode(), db="db")
    _, body = get(server, "/query", db="db", q="SELECT v FROM m", epoch="s")
    s = json.loads(body)["results"][0]["series"][0]
    assert s["values"][0][0] == BASE


def test_write_precision_seconds(server):
    post(server, "/write", f"m v=7 {BASE}".encode(), db="db", precision="s")
    _, body = get(server, "/query", db="db", q="SELECT v FROM m", epoch="ns")
    s = json.loads(body)["results"][0]["series"][0]
    assert s["values"][0][0] == BASE * NS


def test_gzip_write(server):
    body = gzip.compress(f"m v=3 {BASE * NS}".encode())
    status, _ = post(server, "/write", body, headers={"Content-Encoding": "gzip"}, db="db")
    assert status == 204
    _, out = get(server, "/query", db="db", q="SELECT v FROM m", epoch="ns")
    assert json.loads(out)["results"][0]["series"][0]["values"][0][1] == 3.0


def test_write_missing_db_404(server):
    status, body = post(server, "/write", b"m v=1 1", db="nope")
    assert status == 404
    assert "not found" in json.loads(body)["error"]


def test_write_bad_line_400(server):
    status, body = post(server, "/write", b"garbage without fields", db="db")
    assert status == 400


def test_query_via_post_form(server):
    post(server, "/write", f"m v=1 {BASE * NS}".encode(), db="db")
    body = urllib.parse.urlencode({"q": "SELECT v FROM m", "db": "db"}).encode()
    status, out = post(
        server, "/query", body,
        headers={"Content-Type": "application/x-www-form-urlencoded"}, epoch="ns",
    )
    assert status == 200
    assert json.loads(out)["results"][0]["series"][0]["values"][0][1] == 1.0


def test_api_v2_write(server):
    status, _ = post(server, "/api/v2/write", f"m v=9 {BASE * NS}".encode(), bucket="db/autogen")
    assert status == 204
    _, out = get(server, "/query", db="db", q="SELECT v FROM m", epoch="ns")
    assert json.loads(out)["results"][0]["series"][0]["values"][0][1] == 9.0


def test_ddl_over_http_post_only(server):
    # GET must reject mutating statements (influx 1.x POST requirement)
    status, body = get(server, "/query", q="CREATE DATABASE http_db")
    assert "must be sent via POST" in json.loads(body)["results"][0]["error"]
    status, _ = post(server, "/query", b"", q="CREATE DATABASE http_db")
    assert status == 200
    _, body = get(server, "/query", q="SHOW DATABASES")
    vals = json.loads(body)["results"][0]["series"][0]["values"]
    assert ["http_db"] in vals


def test_missing_q_param(server):
    status, body = get(server, "/query", db="db")
    assert status == 400


def test_prom_query_range_over_http(server):
    server.engine.create_database("prom")
    lines = "\n".join(
        f"http_requests_total,instance=a value={i*30} {(BASE + i*15) * NS}"
        for i in range(40)
    )
    post(server, "/write", lines.encode(), db="prom")
    status, body = get(
        server, "/api/v1/query_range",
        query="rate(http_requests_total[2m])",
        start=str(BASE + 300), end=str(BASE + 480), step="60",
    )
    assert status == 200
    data = json.loads(body)
    assert data["status"] == "success"
    [r] = data["data"]["result"]
    assert r["metric"]["instance"] == "a"
    assert float(r["values"][0][1]) == pytest.approx(2.0, rel=1e-6)


def test_prom_instant_and_labels(server):
    server.engine.create_database("prom")
    post(server, "/write", f"up,job=api value=1 {BASE * NS}".encode(), db="prom")
    status, body = get(server, "/api/v1/query", query="up", time=str(BASE + 10))
    data = json.loads(body)
    assert data["data"]["result"][0]["value"][1] == "1.0"
    _, body = get(server, "/api/v1/labels")
    assert "job" in json.loads(body)["data"]
    _, body = get(server, "/api/v1/label/__name__/values")
    assert "up" in json.loads(body)["data"]


def test_prom_bad_query_400(server):
    status, body = get(server, "/api/v1/query", query="rate(", time="0")
    assert status == 400
    assert json.loads(body)["status"] == "error"


def test_explain_and_explain_analyze(server):
    post(server, "/write", f"cpu v=1 {BASE*NS}\ncpu v=3 {(BASE+60)*NS}".encode(), db="db")
    _, body = get(server, "/query", db="db", q="EXPLAIN SELECT mean(v) FROM cpu")
    s = json.loads(body)["results"][0]["series"][0]
    text = "\n".join(r[0] for r in s["values"])
    assert "DEVICE SEGMENTED REDUCTION" in text and "series: 1" in text
    _, body = get(server, "/query", db="db", q="EXPLAIN ANALYZE SELECT mean(v) FROM cpu")
    s = json.loads(body)["results"][0]["series"][0]
    text = "\n".join(r[0] for r in s["values"])
    assert "device_compute" in text and "rows: 2" in text


def test_debug_vars_and_syscontrol(server):
    post(server, "/write", f"m v=1 {BASE*NS}".encode(), db="db")
    get(server, "/query", db="db", q="SELECT v FROM m")
    _, body = get(server, "/debug/vars")
    snap = json.loads(body)
    assert snap["write"]["points"] >= 1
    assert snap["executor"]["queries"] >= 1
    # disable writes
    status, _ = post(server, "/debug/ctrl", mod="disablewrite", switchon="true")
    assert status == 200
    status, body = post(server, "/write", b"m v=2 1", db="db")
    assert status == 403
    post(server, "/debug/ctrl", mod="disablewrite", switchon="false")
    status, _ = post(server, "/write", f"m v=2 {BASE*NS}".encode(), db="db")
    assert status == 204
    # disable reads
    post(server, "/debug/ctrl", mod="disableread", switchon="true")
    _, body = get(server, "/query", db="db", q="SELECT v FROM m")
    assert "disabled" in json.loads(body)["results"][0]["error"]
    post(server, "/debug/ctrl", mod="disableread", switchon="false")


def test_explain_validates_like_select(server):
    # missing db
    _, body = get(server, "/query", q="EXPLAIN SELECT v FROM cpu")
    assert "database name required" in json.loads(body)["results"][0]["error"]
    # missing database
    _, body = get(server, "/query", db="nope", q="EXPLAIN SELECT v FROM cpu")
    assert "database not found" in json.loads(body)["results"][0]["error"]
    # subquery guard
    _, body = get(server, "/query", db="db", q="EXPLAIN SELECT v FROM (SELECT v FROM cpu)")
    assert "subqueries" in json.loads(body)["results"][0]["error"]


def test_disableread_blocks_promql_too(server):
    server.engine.create_database("prom")
    post(server, "/write", f"up value=1 {BASE*NS}".encode(), db="prom")
    post(server, "/debug/ctrl", mod="disableread", switchon="true")
    status, body = get(server, "/api/v1/query", query="up", time=str(BASE))
    assert status == 400
    assert "disabled" in json.loads(body)["error"]
    post(server, "/debug/ctrl", mod="disableread", switchon="false")


def test_consume_api_cursor_pagination(server):
    lines = "\n".join(
        f'logs,host=h{i%2} msg="line {i}" {(BASE + i) * NS}' for i in range(10)
    )
    post(server, "/write", lines.encode(), db="db")
    # duplicate-timestamp rows across series must paginate exactly
    post(server, "/write", f'logs,host=h0 extra=1 {(BASE + 3) * NS}'.encode(), db="db")
    seen = []
    cursor = ""
    for _ in range(10):
        status, body = get(server, "/api/v1/consume", db="db",
                           measurement="logs", limit="3",
                           **({"cursor": cursor} if cursor else {}))
        assert status == 200
        data = json.loads(body)
        seen.extend(data["rows"])
        cursor = data["cursor"]
        if data["exhausted"]:
            break
    assert len(seen) == 11
    times = [r["time"] for r in seen]
    assert times == sorted(times)
    assert seen[0]["tags"] == {"host": "h0"}
    assert seen[0]["fields"]["msg"] == "line 0"


def test_consume_requires_params(server):
    status, _ = get(server, "/api/v1/consume", db="db")
    assert status == 400


def test_detect_anomaly_function(server):
    vals = [10.0] * 20 + [500.0] + [10.0] * 5
    lines = "\n".join(f"m v={v} {(BASE + i) * NS}" for i, v in enumerate(vals))
    post(server, "/write", lines.encode(), db="db")
    _, body = get(server, "/query", db="db", epoch="ns",
                  q="SELECT detect(v, 'mad') FROM m")
    s = json.loads(body)["results"][0]["series"][0]
    assert s["values"] == [[(BASE + 20) * NS, 500.0]]
    # sigma with custom threshold
    _, body = get(server, "/query", db="db", epoch="ns",
                  q="SELECT detect(v, 'sigma', 2) FROM m")
    s = json.loads(body)["results"][0]["series"][0]
    assert [r[1] for r in s["values"]] == [500.0]
    # unknown algorithm -> statement error
    _, body = get(server, "/query", db="db", q="SELECT detect(v, 'bogus') FROM m")
    assert "unknown detect algorithm" in json.loads(body)["results"][0]["error"]


def test_consume_review_regressions(server):
    post(server, "/write", f"logs v=1 {BASE*NS}".encode(), db="db")
    # bad limit -> 400
    status, _ = get(server, "/api/v1/consume", db="db", measurement="logs", limit="abc")
    assert status == 400
    # limit <= 0 clamps to 1, still terminates
    status, body = get(server, "/api/v1/consume", db="db", measurement="logs", limit="0")
    assert status == 200 and len(json.loads(body)["rows"]) == 1
    # empty cursor param behaves like no cursor
    status, body = get(server, "/api/v1/consume", db="db", measurement="logs", cursor="")
    assert status == 200 and json.loads(body)["exhausted"]
    # disableread blocks consume too
    post(server, "/debug/ctrl", mod="disableread", switchon="true")
    status, _ = get(server, "/api/v1/consume", db="db", measurement="logs")
    assert status == 403
    post(server, "/debug/ctrl", mod="disableread", switchon="false")


def test_top_string_param_rejected_at_plan_time(server):
    post(server, "/write", f"m v=1 {BASE*NS}".encode(), db="db")
    _, body = get(server, "/query", db="db", q="SELECT top(v, 'abc') FROM m")
    assert "number or duration" in json.loads(body)["results"][0]["error"]
    _, body = get(server, "/query", db="db", q="SELECT detect(v, 'mad', 'x') FROM m")
    assert "number or duration" in json.loads(body)["results"][0]["error"]


def test_prom_series_endpoint(server):
    server.engine.create_database("prom")
    post(server, "/write", "\n".join([
        f"up,job=api,instance=a value=1 {BASE*NS}",
        f"up,job=api,instance=b value=1 {BASE*NS}",
        f"down,job=x value=1 {BASE*NS}",
    ]).encode(), db="prom")
    url = (f"http://127.0.0.1:{server.port}/api/v1/series?" +
           urllib.parse.urlencode([("match[]", 'up{job="api"}')]))
    with urllib.request.urlopen(url) as r:
        data = json.loads(r.read())
    assert data["status"] == "success"
    insts = sorted(s["instance"] for s in data["data"])
    assert insts == ["a", "b"]
    # missing match[] -> 400
    status, _ = get(server, "/api/v1/series")
    assert status == 400


def test_show_shards_stats_diagnostics(server):
    post(server, "/write", f"m v=1 {BASE*NS}".encode(), db="db")
    _, body = get(server, "/query", db="db", q="SHOW SHARDS")
    s = json.loads(body)["results"][0]["series"][0]
    assert s["columns"][0] == "database"
    assert s["values"][0][0] == "db" and s["values"][0][6] == "hot"
    _, body = get(server, "/query", q="SHOW STATS")
    assert "series" in json.loads(body)["results"][0]
    _, body = get(server, "/query", q="SHOW DIAGNOSTICS")
    rows = dict(json.loads(body)["results"][0]["series"][0]["values"])
    assert "jax" in rows and rows["backend"] in ("cpu", "tpu")


def test_prom_series_post_form_body(server):
    server.engine.create_database("prom")
    post(server, "/write", f"up,job=api value=1 {BASE*NS}".encode(), db="prom")
    body = urllib.parse.urlencode([("match[]", "up")]).encode()
    status, out = post(
        server, "/api/v1/series", body,
        headers={"Content-Type": "application/x-www-form-urlencoded"},
    )
    assert status == 200
    data = json.loads(out)["data"]
    assert data and data[0]["job"] == "api"


# -- prometheus remote write/read + OTLP ingest ------------------------------


def _varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _pb_len(fnum, payload):
    return _varint((fnum << 3) | 2) + _varint(len(payload)) + payload


def _pb_sample(value, t_ms):
    import struct
    return (_varint((1 << 3) | 1) + struct.pack("<d", value)
            + _varint((2 << 3) | 0) + _varint(t_ms & ((1 << 64) - 1)))


def _pb_label(name, value):
    return _pb_len(1, name.encode()) + _pb_len(2, value.encode())


def _write_request(series):
    """series: [(labels_dict, [(t_ms, v)])] -> WriteRequest bytes."""
    out = b""
    for labels, samples in series:
        ts = b""
        for n, v in labels.items():
            ts += _pb_len(1, _pb_label(n, v))
        for t_ms, val in samples:
            ts += _pb_len(2, _pb_sample(val, t_ms))
        out += _pb_len(1, ts)
    return out


def test_prom_remote_write_and_query(server):
    from opengemini_tpu.ingest.protowire import snappy_compress_literal

    body = snappy_compress_literal(_write_request([
        ({"__name__": "http_requests_total", "job": "api", "instance": "a"},
         [(BASE * 1000, 1.0), ((BASE + 15) * 1000, 5.0)]),
        ({"__name__": "http_requests_total", "job": "api", "instance": "b"},
         [(BASE * 1000, 2.0)]),
    ]))
    status, resp = post(server, "/api/v1/prom/write", body,
                        headers={"Content-Encoding": "snappy"}, db="db")
    assert status == 204, resp
    # readable through InfluxQL...
    status, resp = get(server, "/query", db="db",
                       q="SELECT count(value) FROM http_requests_total")
    s = json.loads(resp)["results"][0]["series"][0]
    assert s["values"][0][1] == 3
    # ...and through the Prom HTTP API
    status, resp = get(server, "/api/v1/query", db="db",
                       query='http_requests_total{instance="a"}',
                       time=str(BASE + 20))
    data = json.loads(resp)["data"]["result"]
    assert len(data) == 1 and float(data[0]["value"][1]) == 5.0


def test_prom_remote_read(server):
    from opengemini_tpu.ingest import prom_remote
    from opengemini_tpu.ingest.protowire import (
        snappy_compress_literal, snappy_uncompress)

    post(server, "/api/v1/prom/write", snappy_compress_literal(_write_request([
        ({"__name__": "m1", "host": "x"}, [(BASE * 1000, 7.0)]),
    ])), headers={"Content-Encoding": "snappy"}, db="db")
    # ReadRequest: one query, matcher __name__ = m1
    matcher = (_varint((1 << 3) | 0) + _varint(0)
               + _pb_len(2, b"__name__") + _pb_len(3, b"m1"))
    q = (_varint((1 << 3) | 0) + _varint((BASE - 10) * 1000)
         + _varint((2 << 3) | 0) + _varint((BASE + 10) * 1000)
         + _pb_len(3, matcher))
    req = _pb_len(1, q)
    status, resp = post(server, "/api/v1/prom/read",
                        snappy_compress_literal(req),
                        headers={"Content-Encoding": "snappy"}, db="db")
    assert status == 200, resp
    payload = snappy_uncompress(resp)
    from opengemini_tpu.ingest import protowire as pw
    results = [v for f, _w, v in pw.fields(payload) if f == 1]
    assert len(results) == 1
    ts_bufs = [v for f, _w, v in pw.fields(results[0]) if f == 1]
    assert len(ts_bufs) == 1
    labels = {}
    samples = []
    for f, w, v in pw.fields(ts_bufs[0]):
        if f == 1:
            kv = dict()
            for f2, _w2, v2 in pw.fields(v):
                kv[f2] = v2.decode()
            labels[kv[1]] = kv[2]
        elif f == 2:
            vals = {f3: (w3, v3) for f3, w3, v3 in pw.fields(v)}
            samples.append((pw.as_double(*vals[1]), vals[2][1]))
    assert labels["__name__"] == "m1" and labels["host"] == "x"
    assert samples == [(7.0, BASE * 1000)]


def test_otlp_metrics_ingest(server):
    import struct

    def kv(key, val_any):
        return _pb_len(1, key.encode()) + _pb_len(2, val_any)

    t_ns = BASE * 10**9
    # NumberDataPoint: attrs(7), time(3 fixed64), as_double(4)
    dp = (_pb_len(7, kv("host", _pb_len(1, b"h1")))
          + _varint((3 << 3) | 1) + struct.pack("<Q", t_ns)
          + _varint((4 << 3) | 1) + struct.pack("<d", 42.5))
    gauge = _pb_len(1, dp)
    metric = _pb_len(1, b"cpu_temp") + _pb_len(5, gauge)
    scope = _pb_len(2, metric)
    resource = _pb_len(1, kv("service", _pb_len(1, b"svc1")))
    rm = _pb_len(1, resource) + _pb_len(2, scope)
    req = _pb_len(1, rm)
    status, resp = post(server, "/api/v1/otlp/metrics", req, db="db")
    assert status == 200, resp
    status, resp = get(server, "/query", db="db",
                       q="SELECT gauge FROM cpu_temp GROUP BY *", epoch="ns")
    s = json.loads(resp)["results"][0]["series"][0]
    assert s["tags"] == {"host": "h1", "service": "svc1"}
    assert s["values"][0] == [t_ns, 42.5]


class TestErrnoTaxonomy:
    """Stable error codes on the wire (reference lib/errno code taxonomy:
    fleet log triage greps codes, not message text)."""

    def test_classify_stability(self):
        from opengemini_tpu.ingest.line_protocol import ParseError
        from opengemini_tpu.meta.users import AuthError
        from opengemini_tpu.query.qhelpers import QueryError
        from opengemini_tpu.record import FieldType, FieldTypeConflict
        from opengemini_tpu.storage.engine import DatabaseNotFound
        from opengemini_tpu.utils import errno

        cases = [
            (ParseError(1, "bad"), errno.WRITE_PARSE, "write"),
            (FieldTypeConflict("f", FieldType.FLOAT, FieldType.INT),
             errno.WRITE_FIELD_CONFLICT, "write"),
            (DatabaseNotFound("x"), errno.WRITE_DB_NOT_FOUND, "write"),
            (AuthError("denied"), errno.AUTH_DENIED, "auth"),
            (QueryError("measurement not found"), errno.QUERY_MEASUREMENT_NOT_FOUND, "query"),
            (QueryError("xyz() is not supported"), errno.QUERY_UNSUPPORTED, "query"),
        ]
        for exc, want_code, want_mod in cases:
            code, mod = errno.classify(exc)
            assert code == want_code and mod.name.lower() == want_mod, exc
        # explicit pin wins
        e = QueryError("whatever")
        e.og_errno = errno.META_NO_QUORUM
        assert errno.classify(e)[0] == errno.META_NO_QUORUM
        # OSError's built-in errno must NOT hijack classification
        ce = ConnectionRefusedError(111, "refused")
        assert errno.classify(ce)[0] == errno.NET_NODE_UNREACHABLE
        assert "errno=" in errno.tag(QueryError("zz"))

    def test_wire_surface(self, server):
        from opengemini_tpu.utils import errno

        # auth-less write to a missing database: stable code + header
        status, headers, body = post_full(
            server, "/write", b"m v=1", db="missing_db")
        assert status == 404
        assert headers.get("X-Ogt-Errno") == str(errno.WRITE_DB_NOT_FOUND)
        doc = json.loads(body)
        assert doc["errno"] == errno.WRITE_DB_NOT_FOUND
        assert doc["module"] == "write"


# -- an aggregate answer written from its arrays (query/render.py) -----------

_FLEET = ("SELECT mean(a), mean(b), max(n), count(up), first(up) FROM cpu "
          f"WHERE time >= {BASE}s AND time < {BASE + 3600}s "
          "GROUP BY time(5m), hostname")
_BODIES = [
    _FLEET,
    _FLEET + " fill(none) ORDER BY time DESC LIMIT 5",
    _FLEET.replace("mean(a)", 'mean(a) / mean(b) AS "r\\"atio"') + " fill(0)",
    f"SELECT mean(a), sum(n) FROM cpu WHERE time >= {BASE}s GROUP BY dc",
    _FLEET + "; SHOW MEASUREMENTS; SELECT a FROM cpu LIMIT 2; "
    "SELECT mean(a) FROM nothing GROUP BY time(1m); SELECT nope(a) FROM cpu",
    f"SELECT max(a) FROM cpu WHERE time >= {BASE}s GROUP BY hostname",
]


@pytest.fixture
def fleet(server):
    lines = "\n".join(
        f'cpu,hostname=host_{h},dc=d\\ é{h % 3} a={(h * 31 + k) % 17 / 7},'
        f"b={(h + k) % 5},n={h * 1000 + k}i,up={'tf'[(h + k) % 2]} "
        f"{(BASE + k * 47) * NS}"
        for h in range(9) for k in range(70) if not (h == 4 and 20 < k < 50))
    assert post(server, "/write", lines.encode(), db="db")[0] == 204
    return server


@pytest.mark.parametrize("epoch", [None, "ns", "ms", "s"])
@pytest.mark.parametrize("q", _BODIES, ids=[
    "fleet", "none-desc-limit", "ratio-fill0", "no-group-time", "statements",
    "single-selector"])
def test_query_body_is_what_dumping_the_tree_writes(fleet, q, epoch):
    """A plain /query response is assembled from frames; it is byte for
    byte `json.dumps` of the tree every other reader gets."""
    from opengemini_tpu.server.http import format_result

    params = {"epoch": epoch} if epoch else {}
    status, body = get(fleet, "/query", db="db", q=q, **params)
    assert status == 200
    tree = fleet.executor.execute(q, db="db", read_only=True)
    assert body == (json.dumps(format_result(tree, epoch),
                               allow_nan=False) + "\n").encode()
    n_series = len(json.loads(body)["results"][0].get("series", []))
    assert n_series >= 3


def test_chunked_and_pretty_answer_from_the_tree(fleet):
    from opengemini_tpu.server.http import format_result
    from opengemini_tpu.utils.stats import GLOBAL as STATS

    tree = format_result(fleet.executor.execute(_FLEET, db="db"), "ns")
    before = STATS.counters("query")
    status, body = get(fleet, "/query", db="db", q=_FLEET, epoch="ns",
                       pretty="true")
    assert status == 200 and body == (json.dumps(tree, indent=4) + "\n").encode()
    status, body = get(fleet, "/query", db="db", q=_FLEET, epoch="ns",
                       chunked="true", chunk_size=7)
    docs = [json.loads(line) for line in body.splitlines()]
    rows = {}
    for doc in docs:
        for s in doc["results"][0]["series"]:
            rows.setdefault(s["tags"]["hostname"], []).extend(s["values"])
    assert status == 200 and len(docs) > 9
    assert rows == {s["tags"]["hostname"]: s["values"]
                    for s in tree["results"][0]["series"]}
    now = STATS.counters("query")
    # both were evaluated as arrays; neither was written from them
    cells = 5 * sum(len(s["values"]) for s in tree["results"][0]["series"])
    assert now["render_bulk_cells"] - before["render_bulk_cells"] == \
        now["render_cells"] - before["render_cells"] == 2 * cells > 0
    assert now.get("render_native_cells", 0) == \
        before.get("render_native_cells", 0)

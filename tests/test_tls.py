"""TLS for the HTTP listener and every peer transport (reference: the
https options of lib/config applied to httpd and inter-node traffic)."""

import json
import ssl
import subprocess
import urllib.error
import urllib.parse
import urllib.request

import pytest

from opengemini_tpu.storage.engine import Engine, NS
from opengemini_tpu.utils import peers

BASE = 1_700_000_040


@pytest.fixture(scope="module")
def certpair(tmp_path_factory):
    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "node.crt"), str(d / "node.key")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "2",
         "-subj", "/CN=127.0.0.1",
         "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True)
    return cert, key


@pytest.fixture(autouse=True)
def _reset_peers():
    yield
    peers.reset()


def _client_ctx(cert):
    ctx = ssl.create_default_context(cafile=cert)
    ctx.check_hostname = False
    return ctx


def test_https_listener_serves_and_plain_http_fails(tmp_path, certpair):
    from opengemini_tpu.server.http import HttpService

    cert, key = certpair
    e = Engine(str(tmp_path), sync_wal=False)
    e.create_database("d")
    e.write_lines("d", f"m v=7 {BASE * NS}")
    svc = HttpService(e, "127.0.0.1", 0,
                      tls={"certfile": cert, "keyfile": key})
    svc.start()
    try:
        url = (f"https://127.0.0.1:{svc.port}/query?" +
               urllib.parse.urlencode({"q": "SELECT v FROM m", "db": "d"}))
        with urllib.request.urlopen(url, context=_client_ctx(cert),
                                    timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["results"][0]["series"][0]["values"][0][1] == 7.0
        # plain http against the TLS socket must not succeed
        with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
            urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/ping", timeout=5).read()
    finally:
        svc.stop()
        e.close()


def test_cluster_peer_traffic_over_tls(tmp_path, certpair):
    """Routed writes + remote scans + health probes all ride https when
    [http] TLS is on (peers.configure_tls flips every call site)."""
    from opengemini_tpu.parallel.cluster import DataRouter
    from opengemini_tpu.server.http import HttpService

    cert, key = certpair
    peers.configure_tls(ca_file=cert, skip_verify=True)

    nodes, addrs = {}, {}
    for nid in ("nA", "nB", "nC"):
        e = Engine(str(tmp_path / nid), sync_wal=False)
        e.create_database("db")
        svc = HttpService(e, "127.0.0.1", 0,
                          tls={"certfile": cert, "keyfile": key})
        svc.start()
        addrs[nid] = f"127.0.0.1:{svc.port}"
        nodes[nid] = (e, svc)

    class FsmStub:
        def __init__(self):
            self.nodes = {n: {"addr": a, "role": "data"}
                          for n, a in addrs.items()}

    class StoreStub:
        fsm = FsmStub()
        token = ""

    for nid, (e, svc) in nodes.items():
        svc.router = DataRouter(e, StoreStub(), nid, addrs[nid], rf=1)
        svc.executor.router = svc.router
    try:
        week = 7 * 86400
        lines = "\n".join(
            f"m v={w} {(BASE + w * week) * NS}" for w in range(9))
        req = urllib.request.Request(
            f"https://{addrs['nA']}/write?db=db", data=lines.encode(),
            method="POST")
        urllib.request.urlopen(req, context=_client_ctx(cert),
                               timeout=30).read()
        # points spread over 9 weekly groups across all three nodes
        def rows_on(nid):
            e = nodes[nid][0]
            return sum(
                len(sh.read_series("m", sid).times)
                for sh in e.shards_for_range("db", None, -(2**62), 2**62)
                for sid in sh.index.series_ids("m"))

        per_node = {n: rows_on(n) for n in nodes}
        assert sum(per_node.values()) == 9
        assert sum(1 for v in per_node.values() if v) >= 2, per_node
        # distributed query from every node sees every point (remote
        # scans go over https peer calls)
        for nid in nodes:
            url = (f"https://{addrs[nid]}/query?" + urllib.parse.urlencode(
                {"q": "SELECT count(v) FROM m", "db": "db"}))
            with urllib.request.urlopen(url, context=_client_ctx(cert),
                                        timeout=60) as r:
                doc = json.loads(r.read())
            assert doc["results"][0]["series"][0]["values"][0][1] == 9, nid
    finally:
        for e, svc in nodes.values():
            svc.stop()
            e.close()

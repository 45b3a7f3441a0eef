"""The fleet statement on a device mesh
(`benchmark/configs/tsbs-devops-cpu-4000-mesh4.json`, cell
`tsbs_fleet_groupby_mesh4`), small, on the CPU, through the served /write and
/query paths: 16 hosts, one hour stored and flushed, then the cell's own
statements (`benchmark/traffic/fleet_groupby_mesh4.json` through the
benchmark's generator) asked of a process whose mesh spans four of the eight
forced host devices — what `[device] mesh-axes = ["shard"], mesh-devices = 4`
configures.  Data and expected answers come from the plain reference
`benchmark/configs/tsbs_cpu_only.py` on a seed.

A server computes in float32 (x64 off), and on the chip the grid refuses
twelve windows (128 lanes would be 8x waste) so every field freezes into
buckets (`grid_fallback_share` 100): both are set up here for the module's
life, the second by leaving the grid no cell to allocate.

What the configuration guarantees is held here: an answer computed over the
mesh is within the one-chip limits of the float64 oracle (window times and
group sets exact: `parse` raises otherwise) and is the answer computed with
no mesh — to the last bit, the whole response body: a bucket's sub-rows are
reduced each alone along axis 1, so how many of them a device holds cannot
move a number — whether the rows divide by the mesh, are padded to it, or
are fewer than its devices.  The span `mesh_shard` and the counters beside
it are read as the benchmark's metric files read them."""

import json
import os
import sys
import urllib.parse
import urllib.request

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import load_module, metrics, traffic  # noqa: E402
from harness.oracle import TOL, rel_err  # noqa: E402

from opengemini_tpu.models import grid, launch  # noqa: E402
from opengemini_tpu.parallel import distributed as dist  # noqa: E402
from opengemini_tpu.parallel import runtime as prt  # noqa: E402
from opengemini_tpu.server.http import HttpService  # noqa: E402
from opengemini_tpu.storage.engine import Engine  # noqa: E402
from opengemini_tpu.utils import devobs  # noqa: E402

HOSTS, SEED, FIELDS = 16, 44, 5
# 12 windows x 16 hosts = 192 segments of 30 points: one bucket of width 64,
# 192 sub-rows in a matrix of 256 rows, values and mask a field
G, ROWS = 192, 256
MESH_COUNTERS = ("mesh_dense_batches", "mesh_h2d_bytes", "mesh_put_rows",
                 "mesh_pad_rows", "mesh_items_sharded", "mesh_items_unsharded")


def _json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def cell_files() -> tuple[dict, dict]:
    """The cell's configuration and traffic files, found as run.py finds
    them."""
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "tsbs_fleet_groupby_mesh4")
    assert cell["chips"] == 4
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (_json(ROOT, conf["file"]),
            _json(BENCH, "traffic", cell["traffic"] + ".json"))


class Served:
    """One server over one store with the hour loaded and flushed, and the
    reference that made it."""

    def __init__(self, path):
        cfg, self.mix = cell_files()
        assert cfg["server"] == {"device": {"mesh-axes": ["shard"],
                                            "mesh-devices": 4}}
        assert "sharded_equals_unsharded" in cfg["guarantees"]
        cfg.update(hosts=HOSTS, load_block={"series": 8, "ticks": 360})
        mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                          "reference")
        self.ref = mod.Reference(cfg, SEED)
        self.engine = Engine(str(path))
        self.engine.create_database(self.ref.db)
        self.svc = HttpService(self.engine, "127.0.0.1", 0)
        self.svc.start()
        for body, _rows in self.ref.load_requests():
            assert self.http("POST", "/write", body, db=self.ref.db)[0] == 204
        self.http("POST", "/debug/ctrl", mod="flush")

    def http(self, method, path, body=None, **params):
        url = f"http://127.0.0.1:{self.svc.port}{path}"
        if params:
            url += ("&" if "?" in path else "?") + urllib.parse.urlencode(
                params)
        req = urllib.request.Request(url, data=body, method=method)
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, r.read()

    def doc(self, path: str) -> dict:
        return json.loads(self.http("GET", path)[1])

    def statements(self, n: int, **over) -> list:
        """The cell's first `n` statements of the window, as the generator
        draws them from the seed; `over` changes members of the traffic
        file."""
        plan = traffic.build({**self.mix, **over}, self.ref, SEED, 1.0)
        return plan.requests[:n]

    def ask(self, req) -> bytes:
        """The response body, held to the reference at the configuration's
        limit; every field froze into buckets, none into a grid."""
        self.svc.executor._inc_cache.clear()    # asked again: computed again
        status, body = self.http(req.method, req.path, req.body)
        assert status == 200
        got = self.ref.parse(req.stmt, json.loads(body))
        (value, limit), = self.ref.numbers(req.stmt, got).values()
        assert limit == TOL["selector" if req.stmt["agg"] == "max" else "mean"]
        assert value <= limit, req.stmt["q"]
        return body

    def window(self, reqs, mesh=None) -> dict:
        """Ask `reqs` one at a time under `mesh`: the `ctx` a traced run
        hands the metric files, the answers, and the deltas of the spans and
        of the `device` and `executor` counters beside it."""
        prt.set_mesh(mesh)
        try:
            vars0 = self.doc("/debug/vars")
            bodies = [self.ask(q) for q in reqs]
            vars1 = self.doc("/debug/vars")
        finally:
            prt.set_mesh(None)
        vars1["client"] = {"completed": len(reqs)}

        def moved(group):
            return {k: v - vars0.get(group, {}).get(k, 0)
                    for k, v in vars1.get(group, {}).items()
                    if isinstance(v, (int, float))}

        return {"vars0": vars0, "vars1": vars1, "bodies": bodies,
                "stages": moved("query_stages"), "device": moved("device"),
                "executor": moved("executor")}

    def close(self):
        self.svc.stop()
        self.engine.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    patch = pytest.MonkeyPatch()
    patch.setattr(grid, "_MAX_GRID_CELLS", 0)   # as on the chip: buckets
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # as a server: float32
    s = Served(tmp_path_factory.mktemp("mesh_fleet"))
    try:
        yield s
    finally:
        prt.set_mesh(None)
        s.close()
        jax.config.update("jax_enable_x64", x64)
        patch.undo()


def read(ctx: dict, name: str):
    entry = next(m for m in _json(ROOT, "BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == ["tsbs_fleet_groupby_mesh4"]
    fn, params = metrics.load(name, entry)
    return fn(ctx, params)


def bucketed(ctx: dict, statements: int) -> None:
    assert ctx["executor"].get("grid_batches", 0) == 0
    assert ctx["executor"]["grid_fallbacks"] == FIELDS * statements


@pytest.mark.parametrize("agg", ["mean", "max"])
@pytest.mark.parametrize("devices, pad", [(4, 0), (3, 2)],
                         ids=["rows_divide_by_four", "rows_padded_to_three"])
def test_the_sharded_answer_is_the_oracle_s_and_the_unsharded_one(
        served, devices, pad, agg):
    """256 bucket rows over four devices, and over three, where two rows of
    padding a matrix make 258: the padded rows yield no group (`parse`) and
    move no aggregate (the body is the unsharded one, byte for byte).
    `max` under GROUP BY time() is the basic kernel's, as `mean` is."""
    (req,) = served.statements(1, agg=agg)
    assert req.stmt["windows"] * HOSTS == G
    solo = served.window([req])
    meshed = served.window([req], dist.make_mesh(devices))
    assert meshed["bodies"] == solo["bodies"]
    bucketed(solo, 1), bucketed(meshed, 1)
    dev, mats = meshed["device"], 2 * FIELDS    # values and mask a field
    assert dev["mesh_dense_batches"] == FIELDS
    assert dev["mesh_put_rows"] == (ROWS + pad) * mats
    assert dev["mesh_pad_rows"] == pad * mats
    assert (dev["mesh_items_sharded"], dev.get("mesh_items_unsharded", 0)) \
        == (FIELDS, 0)
    assert meshed["vars1"]["device"]["mesh_shard_devices"] == devices
    # every byte that crossed went through the sharded put: on a mesh the
    # cell's `h2d_bytes_per_q` is `device/mesh_h2d_bytes` a query
    assert dev["mesh_h2d_bytes"] == dev["h2d_bytes_total"] \
        == (ROWS + pad) * 64 * (4 + 1) * FIELDS
    # one launch group a statement, as with no mesh: the placements of
    # five fields' matrices compare equal
    assert meshed["stages"]["device_launch_count"] \
        == solo["stages"]["device_launch_count"] == 1
    assert meshed["stages"]["mesh_shard_count"] == FIELDS
    assert read(meshed, "mesh_pad_share") == pytest.approx(
        100.0 * pad / (ROWS + pad))
    assert read(meshed, "mesh_unsharded_item_share") == 0.0
    assert 0 < meshed["stages"]["mesh_shard_ns"] * 1e-6 == pytest.approx(
        read(meshed, "mesh_shard_ms_per_q"))


def test_with_no_mesh_no_new_span_opens_and_no_new_counter_moves(served):
    ctx = served.window(served.statements(2))
    bucketed(ctx, 2)
    assert not [k for k, v in ctx["stages"].items()
                if k.startswith("mesh_shard") and v]
    assert not [k for k in MESH_COUNTERS if ctx["device"].get(k, 0)]
    assert read(ctx, "mesh_shard_ms_per_q") == 0.0
    assert read(ctx, "mesh_pad_share") is None
    assert read(ctx, "mesh_unsharded_item_share") is None
    assert served.doc("/debug/device")["mesh"] == {
        "configured": False, "size": None, "epoch": prt.mesh_epoch(),
        "axes": None, "device_ids": None}


def test_fewer_rows_than_devices_keep_the_host_matrices(served):
    """One host under three windows of 20 m: three sub-rows, fewer than
    the mesh's four devices, so every field's bucket keeps its host
    matrices (`_Bucket._args`) and the five launch in one group as they do
    with no mesh; nothing is put, padded or sharded."""
    (req,) = served.statements(1, hosts=1, every_s=1200)
    assert req.stmt["windows"] == 3 and len(req.stmt["hosts"]) == 1
    solo = served.window([req])
    meshed = served.window([req], dist.make_mesh(4))
    assert meshed["bodies"] == solo["bodies"]
    bucketed(meshed, 1)
    dev = meshed["device"]
    assert (dev.get("mesh_items_sharded", 0), dev["mesh_items_unsharded"]) \
        == (0, FIELDS)
    assert not [k for k in MESH_COUNTERS[:4] if dev.get(k, 0)]
    assert meshed["stages"].get("mesh_shard_count", 0) == 0
    assert meshed["stages"]["device_launch_count"] == 1
    assert dev["h2d_bytes_total"] == solo["device"]["h2d_bytes_total"] > 0
    assert read(meshed, "mesh_unsharded_item_share") == 100.0
    assert read(meshed, "mesh_pad_share") is None


def test_a_bare_selector_runs_the_xla_selectors_over_the_mesh(served):
    """`max(field) GROUP BY hostname` with no time(): the row of the
    maximum is asked for, so the selector kernel runs — on a mesh its plain
    XLA form, which GSPMD partitions (`pallas_call` does not) — over the
    values, the mask and the three time and index matrices.  Sixteen
    segments of 360 points: one bucket of width 1,024 in 16 rows."""
    ref = served.ref
    field = ref.field_names[3]
    t0, t1 = ref.start_s, ref.start_s + int(ref.cfg["span_s"])
    q = (f"SELECT max({field}) FROM cpu WHERE time >= {t0}s AND time < "
         f"{t1}s GROUP BY hostname")
    path = "/query?" + urllib.parse.urlencode(
        {"q": q, "db": ref.db, "epoch": "ns"})
    stmt = {"agg": "max", "fields": [field], "every_s": t1 - t0, "t0": t0,
            "t1": t1, "hosts": None, "group_by_host": True}
    want = ref.want(stmt)[0, :, 0]              # (hosts,)

    def launched():
        """Dispatches of the XLA selector program (launch.dispatch notes
        one a launch group, whether or not it had to compile)."""
        kernel = devobs.inventory().get("bucket_selectors_xla")
        return sum(g["hits"] for g in kernel["geometries"]) if kernel else 0

    def ask(mesh):
        served.svc.executor._inc_cache.clear()
        prt.set_mesh(mesh)
        try:
            n0 = launched()
            d0 = served.doc("/debug/vars")["device"]
            body = served.http("POST", path, b"")[1]
            d1 = served.doc("/debug/vars")["device"]
        finally:
            prt.set_mesh(None)
        return body, {k: d1.get(k, 0) - d0.get(k, 0)
                      for k in MESH_COUNTERS}, launched() - n0

    solo, _, solo_xla = ask(None)
    meshed, dev, meshed_xla = ask(dist.make_mesh(4))
    assert meshed == solo                       # values and point times
    series = json.loads(meshed)["results"][0]["series"]
    got = {s["tags"]["hostname"]: s["values"] for s in series}
    assert sorted(got) == sorted(f"host_{h}" for h in range(HOSTS))
    assert all(len(v) == 1 and t0 * 1e9 <= v[0][0] < t1 * 1e9
               for v in got.values())
    assert rel_err([got[f"host_{h}"][0][1] for h in range(HOSTS)],
                   want) <= TOL["selector"]
    assert (solo_xla, meshed_xla) == (0, 1)
    # values and mask for `basic`, then the three selector matrices
    assert dev["mesh_dense_batches"] == 2
    assert dev["mesh_put_rows"] == 16 * 5 and dev["mesh_pad_rows"] == 0
    assert (dev["mesh_items_sharded"], dev["mesh_items_unsharded"]) == (2, 0)


def test_two_statements_in_a_row_are_two_launches_of_five_puts_each(served):
    """The second statement draws other fields: nothing of the first is
    reused, each is one launch group of five sharded buckets, and no
    program is built for the second."""
    reqs = served.statements(2)
    assert reqs[0].stmt["fields"] != reqs[1].stmt["fields"]
    mesh = dist.make_mesh(4)
    served.window(reqs[:1], mesh)               # builds the program
    programs = launch._program.cache_info().misses
    ctx = served.window(reqs, mesh)
    assert launch._program.cache_info().misses == programs
    assert ctx["device"]["mesh_dense_batches"] == 2 * FIELDS
    assert ctx["device"]["mesh_items_sharded"] == 2 * FIELDS
    assert ctx["device"]["mesh_put_rows"] == 2 * ROWS * 2 * FIELDS
    assert ctx["stages"]["device_launch_count"] == 2
    assert ctx["stages"]["mesh_shard_count"] == 2 * FIELDS
    assert ctx["stages"]["device_fetch_count"] == 2
    # the span lies under `device_compute`, beside the launch: its time is
    # a part of the statement's device-facing stage, not of its self time
    st = ctx["stages"]
    assert st["device_compute_self_ns"] <= st["device_compute_ns"] \
        - st["mesh_shard_ns"] - st["device_launch_ns"] - st["device_fetch_ns"]


def test_a_mesh_set_and_then_cleared_leaves_one_chip_answers(served):
    """`runtime.set_mesh` and back: the epoch moves with each assignment,
    `/debug/device` says which devices the mesh spans, and a statement asked
    after the mesh is gone runs as if there had never been one."""
    (req,) = served.statements(1)
    epoch = prt.mesh_epoch()
    mesh = dist.make_mesh(4)
    prt.set_mesh(mesh)
    try:
        doc = served.doc("/debug/device")["mesh"]
    finally:
        prt.set_mesh(None)
    assert doc == {"configured": True, "size": 4, "epoch": epoch + 1,
                   "axes": {"shard": 4},
                   "device_ids": [d.id for d in jax.devices()[:4]]}
    assert prt.mesh_epoch() == epoch + 2
    meshed = served.window([req], mesh)
    after = served.window([req])
    again = served.window([req], mesh)
    assert prt.mesh_epoch() == epoch + 6        # each window set and cleared,
    #                                             but None over None is no change
    assert meshed["bodies"] == after["bodies"] == again["bodies"]
    assert not [k for k in MESH_COUNTERS if after["device"].get(k, 0)]
    assert after["stages"].get("mesh_shard_count", 0) == 0
    assert again["device"]["mesh_dense_batches"] == FIELDS

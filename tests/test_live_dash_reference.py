"""The deployment that is read while it is written
(`benchmark/configs/tsbs-devops-cpu-4000-live.json`, cell
`tsbs_dash_refresh`), small, on the CPU, through the served /write and
/query paths: 64 hosts, one hour stored and flushed, then the fleet's next
ticks written while the cell's own statements (`benchmark/traffic/
dash_refresh.json` through the benchmark's generator: fixed panels over the
hour that ends with the newest tick acknowledged in full) are asked.  Data
and expected answers come from the plain reference
`benchmark/configs/tsbs_cpu_only.py` on a seed, which grows by every tick
sent.

What the configuration guarantees is held here: a statement made after a
batch's 204 covers that batch (freshness), and an answer assembled from
cached windows is the answer computed whole (cached = computed), tick by
tick, across a flush, after a late row, past the mutation log's end, after
an eviction and under a racing writer.  The result cache's reasons for
recomputing a window and the `mem_read` span are read as the benchmark's
metric files read them."""

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

from harness import load_module, metrics, traffic  # noqa: E402
from harness.oracle import TOL, read_count  # noqa: E402

from opengemini_tpu.query import resultcache  # noqa: E402
from opengemini_tpu.server.http import HttpService  # noqa: E402
from opengemini_tpu.storage import colcache  # noqa: E402
from opengemini_tpu.storage import shard as shard_mod  # noqa: E402
from opengemini_tpu.storage.engine import Engine  # noqa: E402
from opengemini_tpu.utils import failpoint  # noqa: E402
from opengemini_tpu.utils.stats import GLOBAL as STATS  # noqa: E402

HOSTS, STORED_TICKS, SEED, EVERY = 64, 360, 32, 60
WHY = ("asked", "reused", "cut", "absent", "touched")
NEW = ("mem_read_ms_per_q.live", "mem_rows_per_q.live",
       "resultcache_touched_windows_per_q.live",
       "resultcache_cut_windows_per_q.live",
       "resultcache_evictions_in_window.live")


def _json(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def mix(**over):
    """The cell's traffic file at its dry-run sizes; a batch is one tick of
    the 64 hosts, as the cell's is one tick of its 4,000."""
    t = _json("traffic", "dash_refresh.json")
    t.update(t.pop("dry_run"))
    t["ingest"] = {"batch_rows": HOSTS, "rows_per_s": 50 * HOSTS, "clients": 1}
    t.update(over)
    return t


class Live:
    """One server over one store with the hour loaded and flushed, the
    reference that made it, and the plan that draws the cell's panels."""

    def __init__(self, path, **over):
        cfg = _json("configs", "tsbs-devops-cpu-4000-live.json")
        assert {"freshness", "cached_equals_computed"} <= set(
            cfg["guarantees"])
        cfg.update(hosts=HOSTS, load_block={"series": HOSTS,
                                            "ticks": STORED_TICKS})
        mod = load_module(os.path.join(BENCH, "configs", cfg["reference"]),
                          "reference")
        self.ref = mod.Reference(cfg, SEED)
        self.plan = traffic.build(mix(**over), self.ref, SEED, 4.0)
        self.engine = Engine(str(path))
        self.engine.create_database(self.ref.db)
        self.svc = HttpService(self.engine, "127.0.0.1", 0)
        self.svc.start()
        for body, _rows in self.ref.load_requests():
            assert self.http("POST", "/write", body, db=self.ref.db)[0] == 204
        self.flush()
        self.stream = self.ref.stream_requests(HOSTS, live=True)
        self.model = CacheModel()

    def http(self, method, path, body=None, **params):
        url = f"http://127.0.0.1:{self.svc.port}{path}"
        if params:
            url += ("&" if "?" in path else "?") + urllib.parse.urlencode(
                params)
        req = urllib.request.Request(url, data=body, method=method)
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, r.read()

    def flush(self):
        self.http("POST", "/debug/ctrl", mod="flush")

    def vars(self) -> dict:
        return json.loads(self.http("GET", "/debug/vars")[1])

    def write_tick(self) -> int:
        """The fleet's next tick, one batch; its time in seconds.  Once it
        has its 204 a trailing range ends after it."""
        ref = self.ref
        at = ref.start_s + ref.acked_ticks * ref.interval_s
        body, rows = next(self.stream)
        assert rows == HOSTS
        assert self.http("POST", "/write", body, db=ref.db)[0] == 204
        ref.acked_ticks += 1
        self.model.wrote(at, at + 1)
        return at

    def bind(self, panel: int):
        """Panel `panel`'s statement, made now: it ends with the newest
        tick acknowledged in full."""
        return traffic.bound(self.plan, self.plan.requests[panel])

    def ask(self, req):
        """(body, why the cache recomputed what it did); the answer held to
        the reference at the configuration's limits, window times and
        group set exact (`parse` raises otherwise)."""
        before = STATS.counters("executor")
        status, body = self.http(req.method, req.path, req.body)
        after = STATS.counters("executor")
        assert status == 200
        got = self.ref.parse(req.stmt, json.loads(body))
        (value, limit), = self.ref.numbers(req.stmt, got).values()
        assert limit == (TOL["selector"] if req.stmt["agg"] == "max"
                         else TOL["mean"])
        assert value <= limit, req.stmt["q"]
        why = {k: after.get(f"inc_cache_windows_{k}", 0)
               - before.get(f"inc_cache_windows_{k}", 0) for k in WHY}
        assert why["asked"] == req.stmt["windows"] == sum(
            why[k] for k in WHY[1:])
        return body, why

    def clear_cache(self):
        self.svc.executor._inc_cache.clear()
        self.model.held.clear()

    def close(self):
        self.svc.stop()
        self.engine.close()


class CacheModel:
    """What the result cache holds, kept from the test's side: for each
    panel the whole windows it has been answered, and whether a write has
    fallen into one since."""

    def __init__(self):
        self.held: dict[int, dict[int, bool]] = {}

    def wrote(self, lo_s: int, hi_s: int, everywhere: bool = False) -> None:
        for windows in self.held.values():
            for ws in windows:
                if everywhere or (ws < hi_s and ws + EVERY > lo_s):
                    windows[ws] = True

    def ask(self, stmt: dict) -> dict:
        """Why each window of the statement is computed or reused; then the
        panel holds its whole windows, clean."""
        held = self.held.setdefault(stmt["panel"], {})
        why = dict.fromkeys(WHY, 0)
        for ws in range(stmt["t0"] // EVERY * EVERY, stmt["t1"], EVERY):
            why["asked"] += 1
            if ws < stmt["t0"] or ws + EVERY > stmt["t1"]:
                why["cut"] += 1
                continue
            why["absent" if ws not in held else
                "touched" if held[ws] else "reused"] += 1
            held[ws] = False
        return why


@pytest.fixture
def live(tmp_path):
    srv = Live(tmp_path)
    yield srv
    srv.close()


# -- 1. tick by tick -----------------------------------------------------------


@pytest.mark.parametrize("ask_every", [1, 3])
def test_tick_by_tick_every_answer_is_the_references_cached_or_computed(
        live, ask_every):
    """Write a tick, ask every panel: each answer is the reference's over
    the rows acknowledged so far, and byte for byte the answer of the same
    statement computed whole; the cache recomputes the two windows the
    range cuts and those that became whole since the panel's last ask, and
    reuses the rest.  In-order ticks fall past every cached window, so none
    reads as touched."""
    panels = range(len({q.stmt["panel"] for q in live.plan.requests}))
    assert len(panels) == 6
    for p in panels:                    # a dashboard that was open before
        live.ask(live.bind(p))
        live.model.ask(live.bind(p).stmt)
    seen = dict.fromkeys(WHY, 0)
    for tick in range(1, 15):           # 140 s: over two minute boundaries
        live.write_tick()
        if tick % ask_every:
            continue
        asked = [live.bind(p) for p in panels]
        cached = []
        for req in asked:
            assert req.stmt["t1"] == live.ref.start_s + 3600 + 10 * tick
            body, why = live.ask(req)
            assert why == live.model.ask(req.stmt)
            assert why["cut"] == (0 if tick % 6 == 0 else 2)
            assert why["touched"] == 0 and why["reused"] >= 57
            cached.append(body)
            seen = {k: seen[k] + why[k] for k in WHY}
        live.clear_cache()
        for req, body in zip(asked, cached):
            whole, why = live.ask(req)
            assert whole == body        # cached = computed
            assert why == live.model.ask(req.stmt)
            assert why["reused"] == why["touched"] == 0
    assert seen["absent"] > 0 and seen["cut"] > 0
    assert seen["asked"] == sum(seen[k] for k in WHY[1:])


# -- 2. a flush between two asks ----------------------------------------------


def test_a_flush_between_two_asks_changes_no_answer_and_costs_no_reuse(live):
    """Rows move live memtable -> frozen snapshot -> file; a panel asked in
    each state is answered the same, and re-asked after the flush it reuses
    every whole window: a flush bumps no `data_version`."""
    for _ in range(4):
        live.write_tick()
    reqs = [live.bind(p) for p in range(3)]
    unflushed = [live.ask(q)[0] for q in reqs]
    frozen = []

    def while_frozen():
        """On the flushing thread, off the shard lock, the rows in a frozen
        snapshot and the live memtable empty."""
        failpoint.disable("shard-flush-before-publish")
        sh, = live.engine.all_shards()
        assert len(sh._frozen) == 1 and len(sh.mem) == 0
        before = STATS.counters("scan")
        live.svc.executor._inc_cache.clear()    # each computed whole
        frozen.extend(live.ask(q)[0] for q in reqs)
        after = STATS.counters("scan")
        assert after["mem_rows"] - before.get("mem_rows", 0) == 3 * 8 * 4

    failpoint.enable("shard-flush-before-publish", while_frozen)
    try:
        live.flush()
    finally:
        failpoint.disable("shard-flush-before-publish")
    assert frozen == unflushed
    sh, = live.engine.all_shards()
    assert not sh._frozen and len(sh.mem) == 0 and len(sh._files) == 2
    for q, body in zip(reqs, unflushed):
        again, why = live.ask(q)
        assert again == body
        assert why["reused"] == why["asked"] - why["cut"]
    live.clear_cache()
    before = STATS.counters("query_stages").get("mem_read_count", 0)
    assert [live.ask(q)[0] for q in reqs] == unflushed      # from the files
    assert STATS.counters("query_stages").get("mem_read_count", 0) == before


# -- 3. a late row into an old window -----------------------------------------


def test_a_late_row_recomputes_its_window_alone_and_moves_the_answer(live):
    for _ in range(3):
        live.write_tick()
    req = live.bind(0)
    old, _ = live.ask(req)
    live.model.ask(req.stmt)
    # one of the panel's hosts reports tick 100 again, every field at 100
    ref, tick = live.ref, 100
    host = req.stmt["hosts"][0]
    at = ref.start_s + tick * ref.interval_s
    line = ref.keys[host] + b" " + b",".join(
        f.encode() + b"=100.00" for f in ref.field_names) + b" %d\n" % (
            at * 10**9)
    assert live.http("POST", "/write", line, db=ref.db)[0] == 204
    live.model.wrote(at, at + 1)
    ref.want(req.stmt)                  # joins the live ticks
    ref.hundredths[tick, host] = 10000
    ref.values[tick, host] = 100.0
    new, why = live.ask(req)            # held to the reference as it is now
    assert why == live.model.ask(req.stmt)
    assert why["touched"] == 1 and why["absent"] == 0
    assert new != old
    w = (at - req.stmt["t0"] // EVERY * EVERY) // EVERY
    got_old = ref.parse(req.stmt, json.loads(old))
    got_new = ref.parse(req.stmt, json.loads(new))
    assert (got_new[w] == 100.0).all()
    assert np.array_equal(np.delete(got_new, w, 0), np.delete(got_old, w, 0))
    live.clear_cache()
    assert live.ask(req)[0] == new      # cached = computed


# -- 4. past the end of the mutation log --------------------------------------


def test_truncated_mutation_history_reads_as_touched_and_answers_right(
        tmp_path):
    """More write batches between two asks of a panel than the shard's
    mutation log keeps: every cached window reads as touched (history
    unknown), the answer is still the reference's, and the next ask reuses
    again."""
    live = Live(tmp_path)
    try:
        req = live.bind(0)
        live.ask(req)
        live.model.ask(req.stmt)
        sh, = live.engine.all_shards()
        asked_at = sh.data_version
        quarter = live.ref.stream_requests(HOSTS // 4, live=True)
        batches = shard_mod._MUT_LOG_MAX + 8
        assert batches % 4 == 0
        for n in range(batches):        # four batches a tick
            body, rows = next(quarter)
            assert live.http("POST", "/write", body,
                             db=live.ref.db)[0] == 204
        live.ref.acked_ticks += batches // 4
        assert len(sh._mutations) <= shard_mod._MUT_LOG_MAX
        assert sh._mut_floor > asked_at     # the log's end is past the cells
        live.model.wrote(0, 0, everywhere=True)
        req = live.bind(0)
        assert req.stmt["t1"] - live.ref.start_s - 3600 == 10 * batches // 4
        _, why = live.ask(req)
        assert why == live.model.ask(req.stmt)
        moved = batches // 4 * 10 // EVERY        # whole minutes of new rows
        assert why["touched"] == why["asked"] - why["cut"] - why["absent"] > 0
        assert why["reused"] == 0 and moved <= why["absent"] <= moved + 1
        _, why = live.ask(req)
        assert why["reused"] == why["asked"] - why["cut"]
        got = read_count(json.loads(live.http(
            "GET", "/query", q=live.ref.count_q, db=live.ref.db)[1]))
        assert got == HOSTS * (STORED_TICKS + batches // 4)
    finally:
        live.close()


# -- 5. a 65th panel ----------------------------------------------------------


def test_a_65th_fingerprint_evicts_the_oldest_panel(tmp_path):
    held = resultcache._MAX_QUERIES
    live = Live(tmp_path, panels={"count": held + 1, "refresh_s": 1.0})
    try:
        live.write_tick()
        reqs = [live.bind(p) for p in range(held + 1)]
        assert len({json.dumps(q.stmt["hosts"]) for q in reqs}) == held + 1

        def evicted():
            return STATS.counters("executor").get("inc_cache_evictions", 0)

        n0 = evicted()
        for q in reqs[:held]:
            live.ask(q)
        assert evicted() == n0
        _, why = live.ask(reqs[1])      # held: answered from the cache
        assert why["reused"] == why["asked"] - why["cut"]
        live.ask(reqs[held])            # the 65th: panel 0, the oldest, goes
        assert evicted() == n0 + 1
        _, why = live.ask(reqs[0])      # computed whole, and correct
        assert why["reused"] == 0 and why["absent"] == \
            why["asked"] - why["cut"]
        assert evicted() == n0 + 2      # which cost panel 2 its entry
        _, why = live.ask(reqs[1])
        assert why["reused"] == why["asked"] - why["cut"]
    finally:
        live.close()


# -- 6. freshness under a race ------------------------------------------------


def test_under_a_racing_writer_every_answer_covers_what_was_acknowledged(
        tmp_path):
    """The cell's own two senders for three seconds: the ingest posts a
    tick every 20 ms, four workers ask the six panels at 24 q/s, each
    statement made when it is sent and ending with the newest tick
    acknowledged in full.  Every answer is the reference's over exactly
    those rows, with no tolerance for timing."""
    live = Live(tmp_path, loop={"kind": "open", "workers": 4},
                panels={"count": 6, "refresh_s": 0.25})
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        plan, ref = live.plan, live.ref
        plan.keep[:] = True
        ingest = traffic.Ingest(mix()["ingest"], ref)
        ingest.start(live.svc.port)
        try:
            traffic.run_open(plan, live.svc.port, 3.0)
        finally:
            ingest.stop()
        assert not ingest.thread.is_alive()
        assert len(plan.results) == 72 and all(r.ok for r in plan.results)
        wrote = ingest.sent()
        assert len(wrote) == len(ingest.results) >= 20
        assert ref.acked_ticks == STORED_TICKS + len(wrote)
        ends = set()
        for r in plan.results:
            req = plan.requests[r.index]
            got = ref.parse(req.stmt, json.loads(r.body))
            (value, limit), = ref.numbers(req.stmt, got).values()
            assert value <= limit == 2e-7, req.stmt["q"]
            ends.add(req.stmt["t1"])
        assert len(ends) > 5             # the range moved under the readers
        got = read_count(json.loads(live.http(
            "GET", "/query", q=ref.count_q, db=ref.db)[1]))
        assert got == ref.rows + ingest.rows(wrote)
        moved = STATS.counters("executor")
        assert moved["inc_cache_windows_asked"] == sum(
            moved.get(f"inc_cache_windows_{k}", 0) for k in WHY[1:])
    finally:
        sys.setswitchinterval(old)
        live.close()


# -- 7. the span and the counters, as the metric files read them --------------


def window(live, reqs) -> dict:
    """Ask `reqs` one at a time: the `ctx` a traced run hands the metric
    files, and the spans' deltas beside it."""
    vars0 = live.vars()
    for q in reqs:
        live.ask(q)
    vars1 = live.vars()
    vars1["client"] = {"completed": len(reqs)}
    stages = {k: v - vars0["query_stages"].get(k, 0)
              for k, v in vars1["query_stages"].items()}
    return {"vars0": vars0, "vars1": vars1, "stages": stages}


def read(ctx: dict, name: str):
    entry = next(m for m in _json("..", "BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    fn, params = metrics.load(name, entry)
    return fn(ctx, params)


@pytest.mark.parametrize("want", [[3], [0, 63], [5, 5, 9], [7, 200, 64],
                                  list(range(64)), [100], []])
def test_a_memtable_s_bulk_read_of_some_series_is_the_mask_over_all_rows(want):
    """`MemTable.bulk_parts` finds a wanted sid's run by bisection; what it
    gives is what a membership mask over every row gives, for sids asked
    twice, sids that are absent, all of them and none."""
    from opengemini_tpu.record import FieldType
    from opengemini_tpu.storage.memtable import MemTable

    rng = np.random.default_rng(7)
    mem = MemTable()
    for tick in range(5):               # slabs in host order, then shuffled
        sids = rng.permutation(64).astype(np.int64)
        mem.write_columnar("cpu", sids, np.full(64, tick * 10, np.int64), {
            "v": (FieldType.FLOAT, sids + tick / 10.0, np.ones(64, bool))})
    all_sids, whole = mem.bulk_parts("cpu")[0]
    assert (np.diff(all_sids) >= 0).all() and len(whole) == 320
    keep = np.flatnonzero(np.isin(all_sids, want))
    parts = mem.bulk_parts("cpu", np.asarray(sorted(want), np.int64))
    if not len(keep):
        assert parts == []
        return
    (sid_arr, rec), = parts
    assert np.array_equal(sid_arr, all_sids[keep])
    assert np.array_equal(rec.times, whole.times[keep])
    assert np.array_equal(rec.columns["v"].values,
                          whole.columns["v"].values[keep])


@pytest.fixture
def roomy_colcache():
    """The files' parts come from the column cache once it is warm, however
    an earlier test of this process left it."""
    before = colcache.GLOBAL.config()
    colcache.GLOBAL.configure(budget_mb=64)
    yield
    colcache.GLOBAL.configure(**before)


def test_mem_read_is_one_span_a_shard_read_under_scan_and_counts_its_rows(
        live, roomy_colcache):
    reqs = [live.bind(p) for p in range(6)]
    assert all(len(q.stmt["hosts"]) == 8 for q in reqs)
    # everything flushed: no in-memory part holds a row of `cpu`
    live.clear_cache()
    ctx = window(live, reqs)
    assert ctx["stages"]["scan_count"] == 6
    assert ctx["stages"].get("mem_read_count", 0) == 0
    assert [read(ctx, n) for n in NEW] == [0.0] * 5
    # five ticks not yet flushed, every panel computed whole: one span a
    # statement (one shard), 8 hosts x 5 ticks taken, in one part
    for _ in range(5):
        live.write_tick()
    unflushed = live.ref.acked_ticks - STORED_TICKS
    reqs = [live.bind(p) for p in range(6)]
    live.clear_cache()
    ctx = window(live, reqs)
    st = ctx["stages"]
    assert st["scan_count"] == st["mem_read_count"] == 6
    assert st.get("decode_count", 0) == 0       # the files' parts: cache hits
    # `scan`'s frame holds what its children recorded: `mem_read`, and the
    # column cache's lookups (which the executor's `colcache` span repeats)
    assert st["scan_ns"] - st["mem_read_ns"] - st["colcache_ns"] \
        <= st["scan_self_ns"] <= st["scan_ns"] - st["mem_read_ns"]
    assert 0 < st["mem_read_self_ns"] == st["mem_read_ns"]
    assert read(ctx, "mem_rows_per_q.live") == 8 * unflushed == 40
    assert read(ctx, "mem_read_ms_per_q.live") == pytest.approx(
        st["mem_read_ns"] / 6 * 1e-6)
    assert read(ctx, "resultcache_cut_windows_per_q.live") == 2
    assert read(ctx, "resultcache_touched_windows_per_q.live") == 0
    assert read(ctx, "resultcache_evictions_in_window.live") == 0
    parts = ctx["vars1"]["scan"]["mem_parts"] - ctx["vars0"]["scan"].get(
        "mem_parts", 0)
    assert parts == 6
    # re-asked a tick later, the range ending on the minute: the one window
    # that became whole is scanned alone, under one span still, and the
    # rows taken are counted before the range cut
    live.write_tick()
    ctx = window(live, [live.bind(p) for p in range(6)])
    assert ctx["stages"]["mem_read_count"] == 6
    assert read(ctx, "resultcache_cut_windows_per_q.live") == 0
    assert read(ctx, "mem_rows_per_q.live") == 8 * (unflushed + 1)
    # the fleet-wide touch reads its 64 hosts in bulk: one span as well
    live.clear_cache()
    touch = traffic.bound(live.plan, live.plan.warm_touch[0])
    vars0 = live.vars()
    live.ask(touch)
    vars1 = live.vars()
    assert vars1["query_stages"]["mem_read_count"] \
        - vars0["query_stages"]["mem_read_count"] == 1
    assert vars1["scan"]["mem_rows"] - vars0["scan"]["mem_rows"] \
        == HOSTS * (unflushed + 1)

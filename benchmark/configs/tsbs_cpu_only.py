"""Plain reference for TSBS DevOps `cpu-only`: the data, made from the seed,
the line protocol that carries it, and a float64 numpy oracle for the
statements the traffic files ask.  Imports nothing of the program.

Data generator copied from chip_smoke.py (`tsbs_hosts`, `tsbs_values`,
`_hundredths_table`, PR 21): TSBS's clamped random walk at two decimals, so
that the decimal text, the stored float64 and the oracle's k/100 are one
number.  `Walk` makes it in blocks (PR 26), value for value the original."""

from __future__ import annotations

import numpy as np

from harness.lineproto import LineTemplate
from harness.oracle import TOL, Mismatch, rel_err

FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice")
MEASUREMENT = "cpu"
WIDTH = 6       # every value is 6 ASCII bytes: 9.9900, 99.990, 100.00


def hosts_keys(rng: np.random.Generator, n: int) -> list[bytes]:
    """Series keys of TSBS cpu-only: measurement `cpu` and its 10 tags."""
    regions = ["us-east-1", "us-west-1", "us-west-2", "eu-west-1",
               "eu-central-1", "ap-southeast-1", "ap-southeast-2",
               "ap-northeast-1", "sa-east-1"]
    oses = ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"]
    envs = ["production", "staging", "test"]
    teams = ["SF", "NYC", "LON", "CHI"]
    out = []
    for i in range(n):
        region = regions[rng.integers(len(regions))]
        out.append((
            f"cpu,hostname=host_{i},region={region},"
            f"datacenter={region}{'abc'[rng.integers(3)]},"
            f"rack={rng.integers(100)},os={oses[rng.integers(3)]},"
            f"arch={'x64' if rng.integers(2) else 'x86'},"
            f"team={teams[rng.integers(4)]},service={rng.integers(20)},"
            f"service_version={rng.integers(2)},"
            f"service_environment={envs[rng.integers(3)]}").encode())
    return out


class Walk:
    """TSBS's clamped random walk, a block of ticks at a time: uniform start,
    unit-normal steps, clamped to [0, 10000] hundredths.  It carries its
    state (`cur` and the generator), so any split into blocks gives the
    values, and draws from the generator, that one block gives."""

    def __init__(self, rng: np.random.Generator, hosts: int):
        self.rng = rng
        self.cur = rng.integers(0, 10001, size=(hosts, len(FIELDS)))

    def take(self, ticks: int) -> np.ndarray:
        """The next `ticks` ticks: (ticks, hosts, 10) int32."""
        out = np.empty((ticks,) + self.cur.shape, np.int32)
        for t in range(ticks):
            out[t] = self.cur
            self.cur = np.clip(self.cur + np.rint(
                self.rng.standard_normal(self.cur.shape) * 100
            ).astype(np.int64), 0, 10000)
        return out


def walk(rng: np.random.Generator, ticks: int, hosts: int) -> np.ndarray:
    """(ticks, hosts, 10) int32 hundredths in [0, 10000]."""
    return Walk(rng, hosts).take(ticks)


def hundredths_table() -> np.ndarray:
    """k -> the 6 ASCII bytes of k/100, no leading zeros, no padding."""
    rows = []
    for k in range(10001):
        v = k / 100
        rows.append(b"%.4f" % v if k < 1000 else
                    b"%.3f" % v if k < 10000 else b"%.2f" % v)
    return np.frombuffer(b"".join(rows), np.uint8).reshape(10001, WIDTH)


class Reference:
    """`stored`: the deployment's `span_s` of data, held whole (what the read
    cells load, and the oracle's values).  Not stored: TSBS's loader, a
    stream with no end whose rows are made as they are sent and kept
    nowhere; what checks a write is a count and the durability ledger."""

    def __init__(self, cfg: dict, seed: int, stored: bool = True):
        self.cfg = cfg
        self.seed = seed
        self.field_names = FIELDS
        self.db = cfg["db"]
        self.hosts = int(cfg["hosts"])
        self.start_s = int(cfg["start_s"])
        self.interval_s = int(cfg["interval_s"])
        self.ticks = int(cfg["span_s"]) // self.interval_s if stored else 0
        rng = np.random.default_rng(seed)
        if stored:
            self.hundredths = walk(rng, self.ticks, self.hosts)
            self.values = self.hundredths / 100.0     # (ticks, hosts, fields)
        else:
            self.hundredths = self.values = None
        self.keys = hosts_keys(rng, self.hosts)
        self.rows = self.ticks * self.hosts
        self.count_q = f"SELECT count({FIELDS[0]}) FROM {MEASUREMENT}"
        self._table = hundredths_table()

    # -- line protocol ------------------------------------------------------

    def _ts(self, ticks: np.ndarray) -> np.ndarray:
        return (self.start_s + ticks * self.interval_s) * 10**9

    def load_requests(self):
        """The set-up load: each request carries `ticks` consecutive ticks
        of a block of `series` hosts, series-major, blocks of time in time
        order.  The same rows as `stream_requests`, in another order."""
        bs = int(self.cfg["load_block"]["series"])
        bt = int(self.cfg["load_block"]["ticks"])
        for lo in range(0, self.hosts, bs):
            hi = min(lo + bs, self.hosts)
            tpl = None
            for t0 in range(0, self.ticks, bt):
                t1 = min(t0 + bt, self.ticks)
                if tpl is None or tpl.lines != (hi - lo) * (t1 - t0):
                    tpl = LineTemplate([k for k in self.keys[lo:hi]
                                        for _ in range(t1 - t0)], FIELDS, WIDTH)
                h = self.hundredths[t0:t1, lo:hi].transpose(1, 0, 2)
                ts = np.tile(self._ts(np.arange(t0, t1)), hi - lo)
                yield (tpl.fill(self._table[h.reshape(-1, len(FIELDS))], ts),
                       tpl.lines)

    def stream_walk(self) -> Walk:
        """The walk a stream with no end sends, from its first tick: a
        generator of its own, so that every call begins the same stream."""
        return Walk(np.random.default_rng([self.seed, 1]), self.hosts)

    def _tick_blocks(self, ticks: int):
        if self.hundredths is not None:
            yield self.hundredths
            return
        w = self.stream_walk()
        while True:
            yield w.take(ticks)

    def stream_requests(self, batch_rows: int):
        """TSBS's loader: rows in time order, host-major within a tick, in
        batches of `batch_rows`.  Of a stored reference, its rows and then
        no more; else without end, the walk made a batch's ticks at a time."""
        nf = len(FIELDS)
        templates: dict[tuple[int, int], LineTemplate] = {}

        def batch(lo: int, rows: np.ndarray):
            key = (lo % self.hosts, len(rows))
            if key not in templates:
                templates[key] = LineTemplate(
                    [self.keys[r % self.hosts]
                     for r in range(lo, lo + len(rows))], FIELDS, WIDTH)
            at = np.arange(lo, lo + len(rows)) // self.hosts
            return templates[key].fill(self._table[rows], self._ts(at)), \
                len(rows)

        lo, have = 0, np.empty((0, nf), np.int32)
        for block in self._tick_blocks(-(-batch_rows // self.hosts)):
            have = np.concatenate((have, block.reshape(-1, nf)))
            while len(have) >= batch_rows:
                yield batch(lo, have[:batch_rows])
                lo, have = lo + batch_rows, have[batch_rows:]
        if len(have):
            yield batch(lo, have)

    # -- the oracle ---------------------------------------------------------

    def points(self, stmt: dict) -> int:
        hosts = len(stmt["hosts"]) if stmt["hosts"] is not None else self.hosts
        return len(stmt["fields"]) * hosts * (stmt["t1"] - stmt["t0"]) \
            // self.interval_s

    def want(self, stmt: dict, narrow=None) -> np.ndarray:
        """(windows, groups, fields): `agg` of each field per window of
        `every_s`, per host where the statement groups by hostname, over
        all its hosts otherwise.  `narrow` rounds inputs and result to a
        lower precision (the control)."""
        cols = [FIELDS.index(f) for f in stmt["fields"]]
        a = (stmt["t0"] - self.start_s) // self.interval_s
        b = (stmt["t1"] - self.start_s) // self.interval_s
        v = self.values[a:b][:, :, cols]
        if stmt["hosts"] is not None:
            v = v[:, stmt["hosts"], :]
        if narrow is not None:
            v = narrow(v)
        per = stmt["every_s"] // self.interval_s
        w = v.reshape(-1, per, v.shape[1], len(cols))
        if stmt["group_by_host"]:
            out = w.mean(axis=1) if stmt["agg"] == "mean" else w.max(axis=1)
        else:
            w = w.reshape(w.shape[0], -1, len(cols))
            out = (w.mean(axis=1) if stmt["agg"] == "mean"
                   else w.max(axis=1))[:, None, :]
        return narrow(out) if narrow is not None else out

    def parse(self, stmt: dict, doc: dict) -> np.ndarray:
        """The served answer in the oracle's shape; Mismatch where series,
        windows or window times are not the oracle's."""
        res = doc["results"][0]
        if "error" in res:
            raise Mismatch(f"query failed: {res['error']}")
        series = res.get("series", [])
        windows = (stmt["t1"] - stmt["t0"]) // stmt["every_s"]
        starts = (stmt["t0"] + np.arange(windows) * stmt["every_s"]) * 1e9
        nf = len(stmt["fields"])
        if stmt["group_by_host"]:
            order = (stmt["hosts"] if stmt["hosts"] is not None
                     else range(self.hosts))
            index = {f"host_{h}": i for i, h in enumerate(order)}
        else:
            index = None
        groups = len(index) if index is not None else 1
        if len(series) != groups:
            raise Mismatch(f"{len(series)} series, want {groups}")
        got = np.full((windows, groups, nf), np.nan)
        for s in series:
            g = index[s["tags"]["hostname"]] if index is not None else 0
            vals = np.array(s["values"], np.float64)
            if vals.shape != (windows, nf + 1):
                raise Mismatch(f"group {g}: shape {vals.shape}")
            if not np.array_equal(vals[:, 0], starts):
                raise Mismatch(f"group {g}: window start times differ")
            got[:, g, :] = vals[:, 1:]
        return got

    def numbers(self, stmt: dict, got: np.ndarray) -> dict:
        """{name: (value, limit)} for one answer."""
        kind = "mean" if stmt["agg"] == "mean" else "selector"
        return {f"{kind}_rel_err": (rel_err(got, self.want(stmt)), TOL[kind])}

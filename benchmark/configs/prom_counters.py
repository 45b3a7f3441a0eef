"""Plain reference for the Prometheus counter fleet (`http_requests_total`,
targets x 4 handlers x 5 codes): the counters, made from the seed, their
line protocol, and the float64 extrapolated-rate oracle.  Imports nothing
of the program.  Data generator copied from chip_smoke.py (`prom_series`,
`prom_values`, PR 21)."""

from __future__ import annotations

import numpy as np

from harness.lineproto import LineTemplate, digits
from harness.oracle import TOL, Mismatch, oracle_rate, rel_err

METRIC = "http_requests_total"
WIDTH = 10      # counters stay under 1e9 + 99 a scrape: ten digits


def label_sets(n: int) -> list[tuple[str, str, str]]:
    """(instance, handler, code): n/20 targets x 4 handlers x 5 codes."""
    handlers = ["/api/v1/query", "/api/v1/write", "/metrics", "/healthz"]
    codes = ["200", "204", "400", "404", "500"]
    out = []
    for i in range(n):
        t, rest = divmod(i, 20)
        out.append((f"10.0.{t // 250}.{t % 250}:9100",
                    handlers[rest // 5], codes[rest % 5]))
    return out


def counters(rng: np.random.Generator, ticks: int, series: int) -> np.ndarray:
    """(ticks, series) int64: a start up to 1e9, increments up to 99 a
    scrape, and a restart (reset to a small value) in one series of fifty."""
    start = rng.integers(0, 10**9, size=series)
    inc = rng.integers(0, 100, size=(ticks, series))
    inc[0] = 0
    vals = start[None, :] + np.cumsum(inc, axis=0)
    for s in range(0, series, 50):
        at = int(rng.integers(ticks // 4, 3 * ticks // 4))
        vals[at:, s] = np.cumsum(inc[at:, s])
    return vals


class Reference:
    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.db = cfg["db"]
        self.series = int(cfg["series"])
        self.start_s = int(cfg["start_s"])
        self.scrape_s = int(cfg["scrape_s"])
        self.ticks = int(cfg["span_s"]) // self.scrape_s
        rng = np.random.default_rng(seed)
        self.vals = counters(rng, self.ticks, self.series)
        self.labels = label_sets(self.series)
        self.t_s = self.start_s + np.arange(self.ticks) * self.scrape_s
        self.rows = self.ticks * self.series
        self.count_q = f"SELECT count(value) FROM {METRIC}"

    def load_requests(self):
        """Each request carries every scrape of a block of series,
        series-major; zero-padded decimal text is the same number."""
        bs = int(self.cfg["load_block"]["series"])
        bt = int(self.cfg["load_block"]["ticks"])
        keys = [f"{METRIC},instance={i},handler={h},code={c}".encode()
                for i, h, c in self.labels]
        for lo in range(0, self.series, bs):
            hi = min(lo + bs, self.series)
            for t0 in range(0, self.ticks, bt):
                t1 = min(t0 + bt, self.ticks)
                tpl = LineTemplate([k for k in keys[lo:hi]
                                    for _ in range(t1 - t0)], ("value",), WIDTH)
                v = self.vals[t0:t1, lo:hi].T.reshape(-1)
                ts = np.tile(self.t_s[t0:t1] * 10**9, hi - lo)
                yield tpl.fill(digits(v, WIDTH)[:, None, :], ts), tpl.lines

    def points(self, stmt: dict) -> int:
        """Samples a range query's windows cover: every sample after
        start - range up to end, of every series."""
        inside = (self.t_s > stmt["start"] - stmt["range_s"]) \
            & (self.t_s <= stmt["end"])
        return int(inside.sum()) * self.series

    def _ends(self, stmt: dict) -> np.ndarray:
        return np.arange(stmt["start"], stmt["end"] + 1, stmt["step_s"])

    def want(self, stmt: dict, narrow=None) -> np.ndarray:
        """(series, steps) float64."""
        out = oracle_rate(self.vals, self.t_s, self._ends(stmt),
                          float(stmt["range_s"]), narrow)
        if not np.isfinite(out).all():
            raise Mismatch("oracle window without samples")
        return out

    def parse(self, stmt: dict, doc: dict) -> np.ndarray:
        if doc.get("status") != "success":
            raise Mismatch(f"query_range: {str(doc)[:300]}")
        ends = self._ends(stmt)
        times = [float(t) for t in ends]
        index = {lab: i for i, lab in enumerate(self.labels)}
        result = doc["data"]["result"]
        if len(result) != len(index):
            raise Mismatch(f"{len(result)} series, want {len(index)}")
        got = np.full((len(index), len(ends)), np.nan)
        for s in result:
            m = s["metric"]
            i = index[(m["instance"], m["handler"], m["code"])]
            if [v[0] for v in s["values"]] != times:
                raise Mismatch(f"series {i}: step times differ")
            got[i] = [float(v[1]) for v in s["values"]]
        return got

    def numbers(self, stmt: dict, got: np.ndarray) -> dict:
        return {"rate_rel_err": (rel_err(got, self.want(stmt)), TOL["rate"])}

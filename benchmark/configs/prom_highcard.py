"""Plain reference for the high-cardinality tenant (`BASELINE.json` config
#5): one gauge, cAdvisor's
`container_memory_working_set_bytes{namespace,pod,container,node}`, over
nodes x pods x containers series — the label sets, the gauges made from the
seed, their line protocol, and float64 oracles written from Prometheus's
definitions: instant selection, `topk`/`bottomk`, `count_values`.  Imports
nothing of the program (`require_program` searches its sources, as text, for
the name of the one device program the cell times, and says why).

Departures from the published semantics, the reference's and the program's:

- Staleness markers: none.  The influx data model carries none
  (`ops/prom.py` `instant_values`), so a series is selected at a step
  whenever it has a sample in the lookback; with every series scraped on one
  grid and a lookback longer than the span, that is every series at every
  step from the first scrape on.
- The lookback is left-open, (t - lookback, t], as Prometheus 3 has it; the
  program's is closed.  No sample of these deployments lies on that edge.
- Ties at the k-th place are arbitrary in Prometheus.  The generator leaves
  none (`Reference.settle`), so the comparison of the series set is exact.
- `count_values` labels its output with `strconv.FormatFloat(v, 'f', -1,
  64)` (`prom_float`: "3", never "3.0").  The program writes Python's repr
  digits ("3.0"): the same number in another spelling, so `parse` matches a
  label by its number, and the spelling is the program's known departure
  (PERF.md section 7)."""

from __future__ import annotations

import math
import os
import re
from decimal import Decimal

import numpy as np

from harness.lineproto import LineTemplate, digits
from harness.oracle import TOL, Mismatch

METRIC = "container_memory_working_set_bytes"
LABELS = ("container", "namespace", "node", "pod")    # as the keys sort
CONTAINERS = ("app", "istio-proxy", "log-agent", "init-config")
WIDTH = 10            # whole bytes under 1e10: ten digits
LEVEL_LO, LEVEL_HI = 64 << 20, 8 << 30
STEP_SHARE = 0.01     # a scrape moves a gauge by a normal step of 1 % of its level
TIE_GAP = 10 * TOL["selector"]    # 2e-6: the k-th and the next, relative
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def require_program(need: dict, root: str = ROOT) -> None:
    """Refuse, at once and with the reason, a checkout whose program names
    no `need["jit"]` anywhere under `opengemini_tpu/`: the name the cell's
    traffic file already gives the harness (`device_work.launch_program`,
    less its `jit_`), searched as a word in the sources and never imported.

    Why a reference looks at the program at all: the driver tries a new
    cell on the parent's program under this PR's benchmark files, and
    refuses the PR if that run hangs or is killed.  A program from before
    `prom_instant` CAN answer the statement (657 s, correct: PERF.md
    section 6, PR 49), but its set-up alone, 593 s, passes the 360 s a run
    may take, so it would be killed there, not compared.  The harness has
    the like of this guard for a planner's route (`run.py` `pin_planner`);
    one for `device_work.launch_program` belongs there too, and only a
    `benchmark` PR may put it there (ROADMAP R-A9.18)."""
    word = re.compile(rf"\b{re.escape(need['jit'])}\b")
    for folder, _dirs, files in os.walk(os.path.join(root, "opengemini_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name), encoding="utf-8") as f:
                    if word.search(f.read()):
                        return
    raise ValueError(
        f"no source under opengemini_tpu/ names `{need['jit']}`: this "
        f"checkout's program has no jit_{need['jit']} for the cell to time "
        f"({need['why']})")


def label_sets(cfg: dict, rng: np.random.Generator) -> list[tuple]:
    """(container, namespace, node, pod) a series, node-major: series i is
    container i mod C of pod i div C, which runs on node i div (P x C).  A
    pod's namespace is drawn once."""
    per_pod = int(cfg["containers_per_pod"])
    per_node = int(cfg["pods_per_node"]) * per_pod
    n = int(cfg["nodes"]) * per_node
    ns_of_pod = rng.integers(0, int(cfg["namespaces"]), size=n // per_pod)
    if per_pod > len(CONTAINERS):
        raise ValueError(f"at most {len(CONTAINERS)} containers a pod")
    return [(CONTAINERS[i % per_pod], f"ns-{ns_of_pod[i // per_pod]:03d}",
             f"node-{i // per_node:04d}", f"pod-{i // per_pod:07d}")
            for i in range(n)]


def gauges(rng: np.random.Generator, ticks: int, series: int) -> np.ndarray:
    """(ticks, series) int64 whole bytes: a level uniform in [64 MiB,
    8 GiB), then a random walk whose step is normal with a deviation of 1 %
    of the level."""
    level = rng.uniform(LEVEL_LO, LEVEL_HI, size=series)
    walk = rng.normal(0.0, 1.0, size=(ticks, series)) * (STEP_SHARE * level)
    walk[0] = 0.0
    vals = np.rint(level[None, :] + np.cumsum(walk, axis=0))
    return np.clip(vals, 1, 10**WIDTH - 1).astype(np.int64)


def prom_float(v: float) -> str:
    """Go's strconv.FormatFloat(v, 'f', -1, 64): the shortest digits that
    read back as v, never an exponent."""
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    text = format(Decimal(repr(float(v))), "f")
    if "." in text:
        text = text.rstrip("0").rstrip(".")
    return "0" if text in ("-0", "") else text


def select_instant(vals: np.ndarray, t_s: np.ndarray, steps: np.ndarray,
                   lookback_s: float) -> tuple[np.ndarray, np.ndarray]:
    """Instant vector selection on one scrape grid: at step t the newest
    sample in (t - lookback, t].  -> ((series, steps) float64, (steps,)
    bool: whether the step selects anything)."""
    idx = np.searchsorted(t_s, steps, side="right") - 1
    newest = np.clip(idx, 0, None)
    has = (idx >= 0) & (t_s[newest] > steps - lookback_s)
    return vals[newest].T.astype(np.float64), has


def oracle_topk(sel: np.ndarray, has: np.ndarray, k: int,
                descending: bool) -> np.ndarray:
    """(series, steps) bool: the k largest (smallest) values of each step
    that selects anything."""
    keep = np.zeros(sel.shape, bool)
    k = min(k, sel.shape[0])
    for j in np.flatnonzero(has):
        col = -sel[:, j] if descending else sel[:, j]
        keep[np.argpartition(col, k - 1)[:k], j] = True
    return keep


def oracle_count_values(sel: np.ndarray, has: np.ndarray) -> dict:
    """{distinct value: (steps,) float64 counts}; a value is an output
    series at the steps where it counts at least one."""
    out: dict[float, np.ndarray] = {}
    for j in np.flatnonzero(has):
        vals, counts = np.unique(sel[:, j], return_counts=True)
        for v, c in zip(vals.tolist(), counts.tolist()):
            out.setdefault(v, np.zeros(sel.shape[1]))[j] = c
    return out


class Reference:
    def __init__(self, cfg: dict, seed: int):
        require_program(cfg["timed_program"])
        self.cfg = cfg
        self.db = cfg["db"]
        self.start_s = int(cfg["start_s"])
        self.scrape_s = int(cfg["scrape_s"])
        self.lookback_s = float(cfg["lookback_s"])
        self.ticks = int(cfg["span_s"]) // self.scrape_s
        rng = np.random.default_rng(seed)
        self.labels = label_sets(cfg, rng)
        self.stored_series = len(self.labels)
        if self.stored_series != int(cfg["stored_series"]):
            raise ValueError(f"{self.stored_series} label sets, the file "
                             f"states {cfg['stored_series']}")
        self.vals = gauges(rng, self.ticks, self.stored_series)
        self.t_s = self.start_s + np.arange(self.ticks) * self.scrape_s
        self.rows = self.ticks * self.stored_series
        self.count_q = f"SELECT count(value) FROM {METRIC}"
        stmt = self._grid(cfg["statement"])
        op, k, _ = self._parsed(stmt)
        self.redrawn = 0
        if op != "count_values":
            self.redrawn = self.settle(k, op == "topk", self._matched(stmt),
                                       seed)
        self.series = self.answer_series()

    def answer_series(self) -> int:
        """What the harness counts `"metric"` in a body against: the series
        of the ANSWER to the configuration's statement (the union over its
        steps of each step's k), not the stored ones."""
        return len(self.want(self._grid(self.cfg["statement"])))

    # -- data -----------------------------------------------------------------

    def near_ties(self, k: int, descending: bool,
                  rows: np.ndarray) -> list[tuple[int, int]]:
        """(scrape, series at place k + 1) wherever, among the series
        `rows`, the k-th and the next value of a scrape lie closer than
        TIE_GAP, relative."""
        out = []
        if k >= len(rows):
            return out
        for j in range(self.ticks):
            col = self.vals[j, rows]
            col = -col if descending else col
            top = np.argpartition(col, k)[:k + 1]
            top = top[np.argsort(col[top], kind="stable")]
            a, b = (float(abs(col[top[i]])) for i in (k - 1, k))
            if abs(a - b) < TIE_GAP * max(a, b, 1.0):
                out.append((j, int(rows[top[k]])))
        return out

    def settle(self, k: int, descending: bool, rows: np.ndarray,
               seed: int) -> int:
        """Re-draw, from a generator of its own (seed, series, attempt), the
        series just outside the k of the first scrape that has a near tie,
        until no scrape has one; then assert it.  A uniform re-draw lands
        among the k + 1 with a chance of (k + 1) / series."""
        redrawn = 0
        while True:
            ties = self.near_ties(k, descending, rows)
            if not ties:
                break
            _, s = ties[0]
            redrawn += 1
            if redrawn > 1000:
                raise ValueError("the gauges do not settle: near ties remain "
                                 "after 1000 re-draws")
            sub = np.random.default_rng([seed, 0x7071E, s, redrawn])
            self.vals[:, s] = gauges(sub, self.ticks, 1)[:, 0]
        assert not self.near_ties(k, descending, rows)
        return redrawn

    def load_requests(self):
        """Each request carries every scrape of a block of series,
        series-major; zero-padded decimal text is the same number."""
        bs = int(self.cfg["load_block"]["series"])
        per = self.ticks
        for lo in range(0, self.stored_series, bs):
            hi = min(lo + bs, self.stored_series)
            keys = [(METRIC + "".join(f",{n}={v}" for n, v in zip(LABELS, lab))
                     ).encode() for lab in self.labels[lo:hi]]
            tpl = LineTemplate([k for k in keys for _ in range(per)],
                               ("value",), WIDTH)
            v = self.vals[:, lo:hi].T.reshape(-1)
            ts = np.tile(self.t_s * 10**9, hi - lo)
            yield tpl.fill(digits(v, WIDTH)[:, None, :], ts), tpl.lines

    # -- statements -----------------------------------------------------------

    def _grid(self, st: dict) -> dict:
        """The statement the configuration states, as the generator makes
        it from a traffic file (`traffic._promql_statements`)."""
        return {"q": st["query"], "range_s": int(st["range_s"]),
                "step_s": int(st["step_s"]),
                "start": self.start_s + int(st["range_s"]),
                "end": self.start_s + int(self.cfg["span_s"])}

    def _steps(self, stmt: dict) -> np.ndarray:
        return np.arange(stmt["start"], stmt["end"] + 1, stmt["step_s"])

    def points(self, stmt: dict) -> int:
        """Stored samples a query reads: every sample of every matched
        series inside (first step - lookback, last step]."""
        if "marker_count" in stmt:      # the generator's: the file's own grid
            want = self._grid(self.cfg["statement"])
            got = {k: stmt[k] for k in want}
            if got != want:
                raise ValueError(f"the traffic file's statement {got} is not "
                                 f"the configuration's {want}")
        steps = self._steps(stmt)
        inside = (self.t_s > steps[0] - self.lookback_s) \
            & (self.t_s <= steps[-1])
        return int(inside.sum()) * len(self._matched(stmt))

    def _parsed(self, stmt: dict) -> tuple[str, object, dict]:
        """(`topk` | `bottomk` | `count_values`, its parameter, equality
        [(label, `=` | `!=`, value)]) of the statements this deployment is
        asked: `op(param, metric)` or `op(param, metric{label="value",..})`."""
        q = stmt["q"].strip()
        op, _, rest = q.partition("(")
        param, _, sel = rest.rpartition(")")[0].partition(",")
        sel, matchers = sel.strip(), []
        if "{" in sel:
            sel, _, inner = sel.partition("{")
            for pair in inner.rstrip("}").split(","):
                name, eq, value = re.fullmatch(
                    r'\s*(\w+)\s*(!?=)\s*"([^"]*)"\s*', pair).groups()
                matchers.append((name, eq, value))
        if op not in ("topk", "bottomk", "count_values") or sel != METRIC:
            raise ValueError(f"no oracle for {q!r}")
        param = param.strip()
        return op, (param.strip('"') if op == "count_values"
                    else int(param)), matchers

    def _matched(self, stmt: dict) -> np.ndarray:
        _, _, matchers = self._parsed(stmt)
        rows = np.arange(self.stored_series)
        for name, eq, value in matchers:
            at = LABELS.index(name)
            rows = rows[np.fromiter(
                ((self.labels[i][at] == value) == (eq == "=") for i in rows),
                bool, len(rows))]
        return rows

    def _selected(self, stmt: dict):
        rows = self._matched(stmt)
        sel, has = select_instant(self.vals[:, rows], self.t_s,
                                  self._steps(stmt), self.lookback_s)
        return rows, sel, has

    def want(self, stmt: dict, narrow=None) -> dict:
        """{key: {step time: value}}: a key is a series' label tuple, or of
        `count_values` the distinct value as Prometheus spells it.  `narrow`
        (the control) rounds the selected values as a path that computed
        below float32 would; which series a step keeps is decided on the
        float64 values either way."""
        op, k, _ = self._parsed(stmt)
        steps = self._steps(stmt)
        rows, exact, has = self._selected(stmt)
        sel = narrow(exact) if narrow is not None else exact
        if op == "count_values":
            return {prom_float(v): {float(steps[j]): c
                                    for j, c in enumerate(counts) if c > 0}
                    for v, counts in oracle_count_values(sel, has).items()}
        keep = oracle_topk(exact, has, k, op == "topk")
        return {self.labels[rows[i]]: {float(steps[j]): float(sel[i, j])
                                       for j in np.flatnonzero(keep[i])}
                for i in np.flatnonzero(keep.any(axis=1))}

    def parse(self, stmt: dict, doc: dict) -> dict:
        """The answer in `want`'s form; raises where a series is named
        twice or carries labels this deployment does not have."""
        if doc.get("status") != "success":
            raise Mismatch(f"query_range: {str(doc)[:300]}")
        op, param, _ = self._parsed(stmt)
        got: dict = {}
        for s in doc["data"]["result"]:
            m = s["metric"]
            try:
                key = prom_float(float(m[param])) if op == "count_values" \
                    else tuple(m[n] for n in LABELS)
            except (KeyError, ValueError):
                raise Mismatch(f"series with labels {m}")
            if key in got:
                raise Mismatch(f"series {key} twice")
            got[key] = {float(t): float(v) for t, v in s["values"]}
        return got

    def numbers(self, stmt: dict, got: dict, narrow=None) -> dict:
        """Series set and step membership exact (a difference is a
        Mismatch, not a number), each value within one float32 rounding of
        the float64 oracle."""
        want = self.want(stmt, narrow)
        if got.keys() != want.keys():
            raise Mismatch(
                f"{len(got.keys() - want.keys())} series of the answer's "
                f"{len(got)} are not among the oracle's {len(want)}, "
                f"{len(want.keys() - got.keys())} of the oracle's are missing")
        differ = [k for k in want if got[k].keys() != want[k].keys()]
        if differ:
            raise Mismatch(f"{len(differ)} series hold a value at other steps "
                           f"than the oracle's, the first {differ[0]}: "
                           f"{sorted(got[differ[0]])} for "
                           f"{sorted(want[differ[0]])}")
        err = max((abs(got[k][t] - v) / max(abs(v), 1.0)
                   for k, pts in want.items() for t, v in pts.items()),
                  default=0.0)
        return {"value_rel_err": (err, TOL["selector"])}

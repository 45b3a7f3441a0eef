"""One span primitive, three sinks; hierarchical traces with cross-node
span propagation.

Reference: lib/tracing — Trace/Span (span.go:31) with StartPP/EndPP
wall-time measurement and fields, serialized back to the client by
EXPLAIN ANALYZE (statement_executor.go:943); the reference additionally
ships spans across the MPP executor's RPC boundary so the coordinator
renders one tree spanning every store node.

`span(name, **fields)` is the one way to time a stage.  Every layer
boundary of the served paths (/query, /api/v1/query[_range], /write)
opens one, under a `request(route)` root the HTTP front end opens, and a
closing span writes to:

  1. the statistics registry, always: `<group>/<name>_ns`, `_count` and
     `_self_ns` (elapsed minus what its child spans on the same thread
     covered), group `query_stages`, or `write_stages` under a /write
     root; the `query_stage_seconds` histogram; and its request's
     account, the one stage map a request has (below).  The root adds
     `http/<route>_ns`, `_cpu_ns` (its thread's CPU time), `_offcpu_ns`
     (wall minus CPU: the GIL, locks, the socket), `_self_ns`, `_count`;
  2. the request's Trace, under OGT_TRACE=1: a Span with name, wall
     start, elapsed, parent and the request's trace id
     (`/debug/trace`, slow-log capture, EXPLAIN ANALYZE);
  3. the profiler capture, while one is active (utils/devobs.py): a
     `jax.profiler.TraceAnnotation("ogt:<name>")` on the capture's own
     clock, beside the device's operations.

`record_stage(name, ns)` is the same primitive for time that was
measured elsewhere (the governor's admission wait, the column cache's
per-lookup time, XLA's compile events): sink 1 only.

A request's account.  The root owns an `Account`: stage -> [ns, self_ns,
count] of every span and `record_stage` closed under it, on its own
thread with no lock and from scan-pool workers through `handoff()` /
`adopt()` (a worker fills a map of its own and folds it in once a
task).  The query tracker's per-statement stages (/debug/queries, EXPLAIN
ANALYZE's siblings, the slow log's `stages_ms`) are a view of it; a
statement with no root above it (Flight, a rules tick, a library caller)
gets a bare account for as long as it is registered.  When the root
closes, its time is offered to the route's tail, the 16 slowest requests
since `mark()`: one comparison, and a request record (plain ints and one
small dict: `request.record`) built only for one that enters.  /debug/vars
serves `tail` and `stalls`, /debug/slow both beside its ring.

A pulse (`watch_pulse`, started beside `watch_gc`): one daemon thread on
an absolute 20 ms schedule that measures how late it wakes.  It is late
exactly when the interpreter could not run it: one long C call held the
GIL, a collection ran, or the host took the CPU away.  `runtime/pulse_*`
count it, a request's `stalled_ns` is the lateness that accrued while it
was open, and a beat over 100 ms late leaves a stall record saying what
grew across it (CPU, collector, run-queue delay, faults, switches) and
which stage every open request stood in.

Here a Trace is a tree of Spans, each carrying (trace_id, span_id,
parent_id, node, start wall-ns, elapsed perf-ns).  The coordinator
attaches `ctx()` — {trace_id, span_id} of its innermost open span — to
/internal/* RPC bodies; the replica executes under a child Trace built
by `start_remote()` and returns `to_dict()` in its response payload;
the coordinator `graft()`s the subtree back under the span that issued
the RPC, yielding one stitched tree with correct cross-node parentage.
A helper thread (scan pool) attaches its spans under the span that
dispatched it: `handoff()` there, `adopt()` here.

Cost model: with OGT_TRACE unset/0 and no capture a span is two
perf_counter reads, a thread-local frame, one registry lock for its
three counters, one histogram observe (its lock) and an unlocked add to
the request's account — no Span objects, no ids, and no record unless
the request enters the tail or the armed slow log.  OGT_TRACE=1 arms
per-request trees; the arming check is one thread-local read per span.
"""

from __future__ import annotations

import gc
import os
import random
import resource
import threading
import time
from collections import deque

from opengemini_tpu.utils import lockdep
from opengemini_tpu.utils import stats as _stats
# devobs never imports this module at import time (it calls in lazily)
from opengemini_tpu.utils.devobs import _profile as _CAPTURE
from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER
from opengemini_tpu.utils.slowlog import GLOBAL as _SLOW

_STATS = _stats.GLOBAL

# per-request span-tree capture (OGT_TRACE=1).  Mutable at runtime via
# /debug/ctrl?mod=obs — read through trace_enabled(), never directly.
_TRACE_ON = os.environ.get("OGT_TRACE", "") in ("1", "true")

# finished traces kept for /debug/trace?qid= (bounded; newest wins)
_RECENT_MAX = 256
_RECENT: dict[object, dict] = {}
_RECENT_LOCK = lockdep.Lock()

# per thread: `top`, the innermost open span (the self-time frame);
# `acct`, the account its closing spans add to; `trace` and `node`, the
# active Trace and its innermost open tree node
_tls = threading.local()

# children of one tree node are appended from the request's thread and
# from scan-pool workers
_TREE_LOCK = lockdep.Lock()

_Annotation = None      # jax.profiler.TraceAnnotation, first capture on


def trace_enabled() -> bool:
    return _TRACE_ON


def set_trace_enabled(on: bool) -> None:
    global _TRACE_ON
    _TRACE_ON = bool(on)


def _new_id() -> str:
    # span/trace ids need uniqueness across NODES (replica subtrees are
    # grafted into coordinator trees), so a per-process counter is not
    # enough; 64 random bits at ~100ns/span only when tracing is armed
    return f"{random.getrandbits(64):016x}"


class Span:
    __slots__ = ("name", "span_id", "parent_id", "node", "fields",
                 "children", "start_ns", "elapsed_ns", "_t0")

    def __init__(self, name: str, span_id: str, parent_id: str,
                 node: str = ""):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.node = node
        self.fields: list[tuple[str, object]] = []
        self.children: list[Span] = []
        self.start_ns = time.time_ns()  # wall: cross-node alignment
        self._t0 = time.perf_counter_ns()
        self.elapsed_ns = 0

    def add_field(self, key: str, value) -> None:
        self.fields.append((key, value))

    def finish(self) -> None:
        self.elapsed_ns = time.perf_counter_ns() - self._t0

    def to_dict(self) -> dict:
        elapsed = self.elapsed_ns
        if not elapsed and self._t0:
            # still open (a live /debug/trace, the slow log's capture
            # inside the request's root): the time so far
            elapsed = time.perf_counter_ns() - self._t0
        return {
            "name": self.name, "span_id": self.span_id,
            "parent_id": self.parent_id, "node": self.node,
            "start_ns": self.start_ns, "elapsed_ns": elapsed,
            "fields": [[k, v] for k, v in self.fields],
            "children": [c.to_dict() for c in list(self.children)],
        }

    @classmethod
    def from_dict(cls, doc: dict) -> "Span":
        s = cls.__new__(cls)
        s.name = str(doc.get("name", ""))
        s.span_id = str(doc.get("span_id", ""))
        s.parent_id = str(doc.get("parent_id", ""))
        s.node = str(doc.get("node", ""))
        s.fields = [(k, v) for k, v in doc.get("fields", ())]
        s.start_ns = int(doc.get("start_ns", 0))
        s._t0 = 0
        s.elapsed_ns = int(doc.get("elapsed_ns", 0))
        s.children = [cls.from_dict(c) for c in doc.get("children", ())]
        return s


class Trace:
    """One request's span tree.  Spans attach through the module-level
    `span()` on a thread where the trace is active (`activate`, a
    `request` root, `adopt`); `span`/`add_field`/`ctx`/`graft` here act
    on the calling thread's innermost open span of this trace."""

    def __init__(self, name: str, trace_id: str | None = None,
                 parent_span_id: str = "", node: str = ""):
        self.trace_id = trace_id or _new_id()
        self.node = node
        self.root = Span(name, _new_id(), parent_span_id, node)
        self.qid = None     # set by whoever registers the query

    def _open(self) -> Span:
        if getattr(_tls, "trace", None) is self:
            return _tls.node
        return self.root

    def span(self, name: str, **fields):
        return span(name, **fields)

    def add_field(self, key: str, value) -> None:
        self._open().add_field(key, value)

    def ctx(self) -> dict:
        """Wire context of the innermost open span — attached to
        /internal/* RPC bodies so the replica's subtree parents here."""
        return {"trace_id": self.trace_id,
                "span_id": self._open().span_id}

    def graft(self, subtree: dict | None) -> None:
        """Attach a remote subtree (a Trace.to_dict() from a replica's
        response payload) under the innermost open span.  The subtree
        root's recorded parent_id is the ctx span the coordinator sent;
        a mismatched or trace-less payload is ignored, never an error —
        stitching is best-effort observability."""
        if not subtree or not isinstance(subtree, dict):
            return
        root = subtree.get("root")
        if not isinstance(root, dict):
            return
        try:
            child = Span.from_dict(root)
        except (TypeError, ValueError):
            return
        with _TREE_LOCK:
            self._open().children.append(child)

    def finish(self) -> None:
        self.root.finish()

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "node": self.node,
                "root": self.root.to_dict()}

    def render(self) -> list[str]:
        """Indented tree lines (the EXPLAIN ANALYZE payload)."""
        lines: list[str] = []

        def walk(span: Span, depth: int):
            pad = "    " * depth
            where = f" [{span.node}]" if span.node else ""
            lines.append(
                f"{pad}{span.name}{where}: {_fmt_ns(span.elapsed_ns)}")
            for k, v in span.fields:
                lines.append(f"{pad}    {k}: {v}")
            for c in span.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        return lines


def start_remote(name: str, ctx: dict | None, node: str = "") -> Trace | None:
    """Replica side: a child Trace parented at the coordinator's wire
    ctx.  None when the ctx is absent/malformed (untraced caller)."""
    if not isinstance(ctx, dict):
        return None
    tid, sid = ctx.get("trace_id"), ctx.get("span_id")
    if not tid or not sid:
        return None
    return Trace(name, trace_id=str(tid), parent_span_id=str(sid),
                 node=node)


def start_remote_activated(name: str, ctx: dict | None, node: str = ""):
    """The whole replica-side entry protocol in one call: (trace | None,
    activation context manager) — a nullcontext when the caller is
    untraced, so handlers write `t, cm = ...; with cm: work()`
    unconditionally.  Pair with ship_subtree(t) on the way out."""
    import contextlib

    t = start_remote(name, ctx, node=node)
    return t, (activate(t) if t is not None else contextlib.nullcontext())


def ship_subtree(trace: Trace | None) -> dict | None:
    """Replica-side exit protocol: finish the child trace and hand back
    the wire subtree for the response payload (None when untraced).
    The obs-before-span-ship failpoint arms the computed-but-unshipped
    window here for every shipping site."""
    if trace is None:
        return None
    from opengemini_tpu.utils.failpoint import inject as _fp

    _fp("obs-before-span-ship")
    trace.finish()
    return trace.to_dict()


# -- thread-local activation -------------------------------------------------
# A request's root binds its Trace here so deep callees (cluster RPC
# fan-out, the partials serializer) reach it without threading a trace
# parameter through every signature.  Worker threads (scan pool, RPC
# fan-out) never inherit the binding: the dispatching thread captures a
# handoff() (or its wire ctx) before dispatch.


class activate:
    """Make `trace` the calling thread's active trace, its spans
    attaching under `node` (the trace's root by default), for a `with`
    block.  `trace` None: no tree on this thread for the block."""

    __slots__ = ("_trace", "_node", "_prev")

    def __init__(self, trace, node: Span | None = None):
        self._trace = trace
        self._node = node if node is not None or trace is None \
            else trace.root

    def __enter__(self):
        self._prev = (getattr(_tls, "trace", None),
                      getattr(_tls, "node", None))
        _tls.trace, _tls.node = self._trace, self._node
        return self._trace

    def __exit__(self, *exc):
        _tls.trace, _tls.node = self._prev
        return False


def handoff():
    """What a helper thread needs to work for the calling thread's
    request: its account, and under a trace the innermost open span to
    parent its own under.  Pass the result to adopt() there.  None (and
    adopt(None) a no-op) where neither is active here."""
    acct = getattr(_tls, "acct", None)
    trace = getattr(_tls, "trace", None)
    if acct is None and trace is None:
        return None
    return acct, trace, _tls.node if trace is not None else None


class adopt:
    """The helper-thread side of handoff(): a `with` block whose closing
    stages count for the dispatching request — into a map of this
    thread's own, folded into the request's account under one lock when
    the block ends — and whose spans, under a trace, parent under the
    dispatching span."""

    __slots__ = ("_acct", "_sub", "_prev", "_tree")

    def __init__(self, handed):
        self._acct, trace, node = handed or (None, None, None)
        self._tree = activate(trace, node)

    def __enter__(self):
        self._prev = prev = getattr(_tls, "acct", None)
        acct, self._sub = self._acct, None
        if acct is not None and acct is not prev:
            self._sub = _tls.acct = Account()
        return self._tree.__enter__()

    def __exit__(self, *exc):
        sub = self._sub
        if sub is not None:
            _tls.acct = self._prev
            with _POOL_LOCK:
                acct = self._acct
                if acct.pool is None:
                    acct.pool = Account()
                acct.pool.fold(sub)
        return self._tree.__exit__(*exc)


def active_trace() -> Trace | None:
    """The calling thread's active Trace, if any (OGT_TRACE=1 under a
    request root, an EXPLAIN ANALYZE, a replica's child trace)."""
    return getattr(_tls, "trace", None)


def current():
    """The calling thread's active Trace, or NOOP."""
    t = getattr(_tls, "trace", None)
    return t if t is not None else NOOP


def current_ctx() -> dict | None:
    """Wire ctx of the active trace (None when untraced) — what RPC
    bodies carry."""
    t = getattr(_tls, "trace", None)
    return t.ctx() if t is not None else None


# -- finished-trace ring (/debug/trace) --------------------------------------


def note_finished(qid, trace: Trace, meta: dict | None = None) -> None:
    """Retain a finished trace for /debug/trace?qid= (bounded ring,
    oldest evicted).  `qid` may be None (e.g. writes) — the entry is
    then addressable by trace_id only."""
    doc = {"qid": qid, "trace_id": trace.trace_id,
           "name": trace.root.name,
           "elapsed_ms": round(trace.root.elapsed_ns / 1e6, 3),
           "trace": trace.to_dict()}
    if meta:
        doc.update(meta)
    key = qid if qid is not None else trace.trace_id
    with _RECENT_LOCK:
        _RECENT.pop(key, None)
        _RECENT[key] = doc
        while len(_RECENT) > _RECENT_MAX:
            _RECENT.pop(next(iter(_RECENT)))


def recent_traces() -> list[dict]:
    """Newest-first summaries (no tree) of the retained traces."""
    with _RECENT_LOCK:
        docs = list(_RECENT.values())
    return [
        {k: v for k, v in d.items() if k != "trace"}
        for d in reversed(docs)
    ]


def get_trace(qid=None, trace_id: str | None = None) -> dict | None:
    with _RECENT_LOCK:
        if qid is not None:
            return _RECENT.get(qid)
        if trace_id is not None:
            for d in _RECENT.values():
                if d["trace_id"] == trace_id:
                    return d
    return None


def clear_recent() -> None:
    with _RECENT_LOCK:
        _RECENT.clear()


# -- a request's account ----------------------------------------------------


class Account:
    """What one request spent, by stage: name -> [ns, self_ns, count].
    `stages` is written by the owning thread alone, with no lock; `pool`
    (an Account of its own, made on first use) holds what helper threads
    folded in under `_POOL_LOCK`.  Readers on other threads (the query
    tracker's view, the pulse) take `totals()`."""

    __slots__ = ("stages", "pool", "qids", "d2h_bytes")

    def __init__(self):
        self.stages: dict[str, list] = {}
        self.pool: Account | None = None
        self.qids: list[int] = []       # statements registered under it
        self.d2h_bytes = 0

    def fold(self, other: "Account") -> None:
        mine = self.stages
        # list(): the owner may be adding a stage right now
        for name, (ns, self_ns, count) in list(other.stages.items()):
            m = mine.get(name)
            if m is None:
                mine[name] = [ns, self_ns, count]
            else:
                m[0] += ns
                m[1] += self_ns
                m[2] += count
        self.d2h_bytes += other.d2h_bytes

    def totals(self) -> "Account":
        """A copy with the helpers' stages folded in."""
        out = Account()
        out.fold(self)
        if self.pool is not None:
            with _POOL_LOCK:
                out.fold(self.pool)
        return out

    def stage_ns(self) -> dict[str, int]:
        return {name: m[0] for name, m in self.totals().stages.items()}


_POOL_LOCK = lockdep.Lock()


def statement_account():
    """(account, made here) for a statement the tracker registers on
    this thread: its request's, or — with no root above it — a bare one
    bound to the thread until `release_account`."""
    acct = getattr(_tls, "acct", None)
    made = acct is None
    if made:
        acct = _tls.acct = Account()
    return acct, made


def release_account(acct: Account) -> None:
    if getattr(_tls, "acct", None) is acct:
        _tls.acct = None


def note_d2h(nbytes: int) -> None:
    """Bytes a fetch under this thread's request brought back."""
    acct = getattr(_tls, "acct", None)
    if acct is not None:
        acct.d2h_bytes += nbytes


# open roots by id: the pulse reads what each stands in, a root counts
# the others when it opens
_OPEN: dict[int, "request"] = {}

# per route, the slowest requests since the last mark.  `floor` is the
# fastest member's time once the tail is full (read unlocked: a stale
# one builds a record too many or drops a marginal one)
_TAIL_MAX = 16
_STAGES_MAX = 16            # of a served record; the rest sum to "other"
_TAIL_LOCK = lockdep.Lock()


class _Tail:
    __slots__ = ("recs", "floor")

    def __init__(self):
        self.recs: list[dict] = []
        self.floor = -1


_TAILS: dict[str, _Tail] = {}


def _offer(route: str, rec: dict) -> None:
    with _TAIL_LOCK:
        tail = _TAILS.get(route)
        if tail is None:
            tail = _TAILS[route] = _Tail()
        recs = tail.recs                # kept slowest first
        recs.append(rec)
        recs.sort(key=lambda r: -r["ns"])
        del recs[_TAIL_MAX:]
        if len(recs) == _TAIL_MAX:
            tail.floor = recs[-1]["ns"]


def mark() -> None:
    """A new epoch: the tails forget, `pulse_late_max_ns` starts again.
    devobs.mark_warm() calls it (a benchmark window begins just after),
    an operator /debug/ctrl?mod=obs&mark=1."""
    with _TAIL_LOCK:
        _TAILS.clear()
    _pulse["late_max_ns"] = 0


def tail_doc() -> dict:
    """/debug/vars `tail`: route -> its records, slowest first."""
    with _TAIL_LOCK:
        return {route: list(t.recs) for route, t in _TAILS.items()}


def _served_stages(stages: dict) -> dict:
    """The largest `_STAGES_MAX` stages of a record and the rest summed
    under `other`: a bound on what /debug/vars carries a request."""
    if len(stages) <= _STAGES_MAX:
        return stages
    names = sorted(stages, key=lambda n: -stages[n][0])
    out = {n: stages[n] for n in names[:_STAGES_MAX]}
    out["other"] = [sum(stages[n][i] for n in names[_STAGES_MAX:])
                    for i in range(3)]
    return out


# -- the span primitive ------------------------------------------------------

_QUERY, _WRITE = "query_stages", "write_stages"

# per stage name: its three counter names and its histogram's key, built
# once.  A name with a space is dynamic ("select: <mst>"): no histogram,
# or label cardinality would leak into /metrics
_KEYS: dict[str, tuple] = {}


def _keys(name: str) -> tuple:
    k = _KEYS.get(name)
    if k is None:
        k = _KEYS[name] = (
            name + "_ns", name + "_count", name + "_self_ns",
            None if " " in name
            else _stats.histogram_key("query_stage_seconds", stage=name))
    return k


def _record(group: str, name: str, ns: int, self_ns: int) -> None:
    """Sink 1 of a closed stage."""
    k_ns, k_count, k_self, k_hist = _keys(name)
    _STATS.add(group, ((k_ns, ns), (k_count, 1), (k_self, self_ns)))
    if k_hist is not None and _stats.obs_enabled():
        _stats.histogram_at(k_hist).observe_ns(ns)
    acct = getattr(_tls, "acct", None)
    if acct is not None:
        stages = acct.stages
        m = stages.get(name)
        if m is None:
            stages[name] = [ns, self_ns, 1]
        else:
            m[0] += ns
            m[1] += self_ns
            m[2] += 1


def record_stage(name: str, elapsed_ns: int) -> None:
    """A stage whose time was measured elsewhere, closed now: counters,
    histogram and the request's account, like a span's; the calling
    thread's open span counts it among its children."""
    top = getattr(_tls, "top", None)
    if top is None:
        _record(_QUERY, name, elapsed_ns, elapsed_ns)
    else:
        top._child_ns += elapsed_ns
        _record(top.group, name, elapsed_ns, elapsed_ns)


def _annotate(name: str, fields: dict):
    """Sink 3: an entered TraceAnnotation on the capture's clock."""
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation as _Annotation
    qid = _TRACKER.current_qid()
    if qid is not None:
        fields = dict(fields, qid=qid)
    ann = _Annotation("ogt:" + name, **fields)
    ann.__enter__()
    return ann


def annotated(name: str):
    """An entered `ogt:<name>` annotation while a capture is active, for
    time that is no stage of any request (a connection idle between two
    of them); None, and nothing at all, otherwise.  The caller exits it."""
    return _annotate(name, {}) if _CAPTURE["active"] else None


class span:
    """`with span("scan", rows=n) as sp:` — one stage of a request, at
    stage granularity only: never inside a per-series or per-row loop,
    and per chunk only on the column cache's miss path, where a chunk
    is read, decoded and cached in three stages (storage/tsf.py
    `_load_columns`).  `sp.add_field` adds to the tree's span (a no-op
    without a tree); fields given here also ride the annotation."""

    __slots__ = ("name", "group", "_fields", "_t0", "_child_ns", "_prev",
                 "_node", "_parent", "_ann")

    def __init__(self, name: str, **fields):
        self.name = name
        self._fields = fields

    def __enter__(self):
        prev = getattr(_tls, "top", None)
        # a span with no root above it is a query's (the Flight path)
        self.group = prev.group if prev is not None else _QUERY
        self._node = None
        trace = getattr(_tls, "trace", None)
        if trace is not None:
            parent = self._parent = _tls.node
            node = self._node = Span(self.name, _new_id(), parent.span_id,
                                     trace.node)
            with _TREE_LOCK:
                parent.children.append(node)
            _tls.node = node
        return self._start(prev)

    def _start(self, prev):
        """Open the frame and the annotation; the clock starts last."""
        self._prev = prev
        self._child_ns = 0
        if self._node is not None:
            self._node.fields.extend(self._fields.items())
        self._ann = (_annotate(self.name, self._fields)
                     if _CAPTURE["active"] else None)
        _tls.top = self
        self._t0 = time.perf_counter_ns()
        return self

    def _stop(self, exc) -> int:
        """Stop the clock, close the frame and the annotation; elapsed."""
        ns = time.perf_counter_ns() - self._t0
        prev = _tls.top = self._prev
        if prev is not None:
            prev._child_ns += ns
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._node is not None:
            self._node.elapsed_ns = ns
        return ns

    def add_field(self, key: str, value) -> None:
        if self._node is not None:
            self._node.add_field(key, value)

    def __exit__(self, *exc):
        ns = self._stop(exc)
        if self._node is not None:
            _tls.node = self._parent
        _record(self.group, self.name, ns, ns - self._child_ns)
        return False


class request(span):
    """The root span of one served request, opened by the HTTP front
    end: `http_<route>` for route `query`, `prom` or `write`.  It owns
    the request's account (`acct`) and, when it closes, offers its time
    to the route's tail and to the armed slow log.  Under OGT_TRACE=1
    (and `tree`) it also owns the request's Trace, whose root span it is,
    and retains it for /debug/trace when it closes, under the qid the
    executor or the PromQL engine set on it."""

    __slots__ = ("route", "trace", "acct", "inflight", "_cpu0", "_active",
                 "_prev_acct", "_gc0", "_gen2_0", "_frames")

    def __init__(self, route: str, tree: bool = True, **fields):
        span.__init__(self, "http_" + route, **fields)
        self.route = route
        self.group = _WRITE if route == "write" else _QUERY
        self.trace = Trace(self.name) if _TRACE_ON and tree else None

    def __enter__(self):
        self._node = None
        if self.trace is not None:
            self._node = self.trace.root
            self._active = activate(self.trace)
            self._active.__enter__()
        self._prev_acct = getattr(_tls, "acct", None)
        self.acct = _tls.acct = Account()
        # this thread's frames, for the pulse: `top` there is the
        # innermost span this request has open
        self._frames = _tls.__dict__
        self.inflight = len(_OPEN)
        _OPEN[id(self)] = self
        self._gc0 = _gc["pause_ns"]
        self._gen2_0 = _gc["gen2_collections"]
        self._cpu0 = time.thread_time_ns()
        return self._start(getattr(_tls, "top", None))

    def __exit__(self, *exc):
        ns = self._stop(exc)
        cpu = time.thread_time_ns() - self._cpu0
        r = self.route
        _STATS.add("http", (
            (r + "_ns", ns), (r + "_cpu_ns", cpu),
            (r + "_offcpu_ns", max(ns - cpu, 0)),
            (r + "_self_ns", ns - self._child_ns), (r + "_count", 1)))
        _OPEN.pop(id(self), None)
        rec = None
        tail = _TAILS.get(r)
        if tail is None or ns > tail.floor:
            rec = self.record(ns, cpu)
            _offer(r, rec)
        slow_ms = _SLOW.threshold_ms
        if slow_ms is not None and not self.acct.qids \
                and ns >= slow_ms * 1e6:
            # a request no statement spoke for (/write): the root does
            _SLOW.note(None, self.name, self._fields.get("database", ""),
                       ns / 1e6, trace=self.trace, extra={"kind": r},
                       request=rec or self.record(ns, cpu))
        _tls.acct = self._prev_acct
        if self.trace is not None:
            self._active.__exit__(*exc)
            note_finished(self.trace.qid, self.trace)
        return False

    def record(self, ns: int | None = None, cpu: int | None = None) -> dict:
        """The request record: plain ints on `time.perf_counter_ns`, the
        clock every span, `_on_gc` and the pulse use, and the stage map.
        At the close, or (no arguments) of the request so far."""
        if ns is None:
            ns = time.perf_counter_ns() - self._t0
            cpu = time.thread_time_ns() - self._cpu0
        acct = self.acct.totals()
        stages = acct.stages
        return {
            "route": self.route,
            # the route's `_count` at the close: past a window's first
            # `http/<route>_count`, the request closed inside the window
            "seq": _STATS.counters("http").get(self.route + "_count", 0),
            "qids": list(self.acct.qids),
            "t0_ns": self._t0, "ns": ns, "cpu_ns": cpu,
            "offcpu_ns": max(ns - cpu, 0),
            "self_ns": ns - self._child_ns,
            "stages": _served_stages(stages),
            # a collection holds the GIL: every open request's pause
            "gc_ns": _gc["pause_ns"] - self._gc0,
            "gc_gen2": _gc["gen2_collections"] - self._gen2_0,
            "stalled_ns": _late_within(self._t0, self._t0 + ns),
            "inflight": self.inflight,
            "launches": stages.get("device_launch", (0, 0, 0))[2],
            "d2h_bytes": acct.d2h_bytes,
        }


def current_record() -> dict | None:
    """The record so far of the request this thread works for (the slow
    log's, taken inside the root), else what a bare account holds."""
    top = getattr(_tls, "top", None)
    while top is not None and not isinstance(top, request):
        top = top._prev
    if top is not None:
        return top.record()
    acct = getattr(_tls, "acct", None)
    if acct is None:
        return None
    acct = acct.totals()
    return {"stages": _served_stages(acct.stages),
            "d2h_bytes": acct.d2h_bytes}


# -- garbage collection ------------------------------------------------------
# Plain ints, not registry counters: a collection can start inside the
# registry's own lock, and the callback must take none.  One collection
# runs at a time, on whichever thread allocated last.

_gc = {"t0": 0, "pause_ns": 0, "collections": 0,
       "gen2_pause_ns": 0, "gen2_collections": 0, "ann": None}


def _on_gc(phase: str, info: dict) -> None:
    gen2 = info.get("generation") == 2
    if phase == "start":
        _gc["t0"] = time.perf_counter_ns()
        if gen2 and _CAPTURE["active"]:
            _gc["ann"] = _annotate("gc", {"generation": 2})
        return
    ns = time.perf_counter_ns() - _gc["t0"]
    _gc["pause_ns"] += ns
    _gc["t0"] = 0                   # counted: none is open (the pulse asks)
    _gc["collections"] += 1
    if not gen2:
        return
    _gc["gen2_pause_ns"] += ns
    _gc["gen2_collections"] += 1
    ann, _gc["ann"] = _gc["ann"], None
    if ann is not None:
        ann.__exit__(None, None, None)
    trace = getattr(_tls, "trace", None)
    if trace is not None:
        # a full collection inside a traced request: a span of its own
        # under whatever stage it interrupted
        parent = _tls.node
        node = Span("gc", _new_id(), parent.span_id, trace.node)
        node.start_ns -= ns
        node.elapsed_ns = ns
        node.add_field("collected", info.get("collected", 0))
        parent.children.append(node)


# a kernel that keeps none (gVisor) is asked once, not once a task a second
_HAS_SCHEDSTAT = os.path.exists("/proc/self/schedstat")


def _schedstat() -> tuple[int, int] | None:
    """(on-CPU ns, run-queue delay ns) summed over this process's tasks:
    the first two fields of /proc/self/task/*/schedstat.  The second is
    time runnable and not given a CPU — the host's doing.  None where
    there is no /proc, or no such file in it."""
    if not _HAS_SCHEDSTAT:
        return None
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return None
    ran = delay = read = 0
    for tid in tids:
        try:
            with open(f"/proc/self/task/{tid}/schedstat", "rb") as f:
                a, b, _ = f.read().split()
        except (OSError, ValueError):
            continue        # the task ended between the list and the read
        ran += int(a)
        delay += int(b)
        read += 1
    return (ran, delay) if read else None   # a kernel that keeps none


def _runtime_gauges() -> dict:
    out = {"gc_pause_ns": _gc["pause_ns"],
           "gc_collections": _gc["collections"],
           "gc_gen2_pause_ns": _gc["gen2_pause_ns"],
           "gc_gen2_collections": _gc["gen2_collections"],
           "pulse_beats": _pulse["beats"],
           "pulse_late_ns": _pulse["late_ns"],
           "pulse_late_max_ns": _pulse["late_max_ns"]}
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out.update(majflt=ru.ru_majflt, nivcsw=ru.ru_nivcsw)
    sched = _schedstat()
    if sched is not None:       # absent where the kernel keeps none
        out["run_delay_ns"] = sched[1]
    return out


_provided = False


def _provide() -> None:
    global _provided
    if not _provided:
        _provided = True
        _STATS.register_provider("runtime", _runtime_gauges)


def watch_gc() -> None:
    """Count the interpreter's garbage collections from here on (the
    server's start-up; idempotent): `runtime/gc_*` in /debug/vars, and a
    `gc` span and annotation for each full (generation 2) collection."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    _provide()


# -- the pulse ---------------------------------------------------------------
# Plain ints like `_gc`: the pulse thread alone adds to them (`mark()`
# starts the maximum again).

_PULSE_NS = 20_000_000          # the schedule
_LATE_NS = 50_000_000           # a beat later than this counts as lateness
_STALL_NS = 100_000_000         # ... than this leaves a stall record
_SCHED_EVERY = 50               # beats between baselines (1 s)

_pulse = {"beats": 0, "late_ns": 0, "late_max_ns": 0, "due": 0}
_LATE: deque = deque(maxlen=64)     # (due, woke) of the last late beats
_STALLS: deque = deque(maxlen=16)


def _late_within(t0: int, t1: int) -> int:
    """How much of [t0, t1) the pulse was late: a request's `stalled_ns`,
    the growth of `pulse_late_ns` while it was open, cut to the part of
    each late beat that lies inside the request.  A beat the pulse has
    not reported yet (it wakes after the stall as every thread does, and
    may get the GIL last) counts from its due time.  A beat counts only
    for a request that opened in its first half: one that opened later
    came in when the stall was over (the requests that queued up behind
    it, all let in at once) and only shares the pulse's wait for the GIL."""
    late = [*_LATE]
    due = _pulse["due"]
    if due and t1 - due > _LATE_NS and not (late and late[-1][0] == due):
        late.append((due, t1))
    return sum(min(t1, b) - max(t0, a) for a, b in late
               if b > t0 and a < t1 and t0 < (a + b) // 2)


def _thread_cpu() -> dict[str, int]:
    """CPU ns of every Python thread, by name (the runtime's own threads
    never hold the GIL and are not asked)."""
    out: dict[str, int] = {}
    for th in threading.enumerate():
        try:
            ns = time.clock_gettime_ns(time.pthread_getcpuclockid(th.ident))
        except (OSError, TypeError, AttributeError, OverflowError):
            continue        # it ended, or the platform has no such clock
        out[th.name] = out.get(th.name, 0) + ns
    return out


class _Baseline:
    """What the pulse compares a late beat with: read every `_SCHED_EVERY`
    beats and after a late one, being too dear to read at each."""

    __slots__ = ("at", "delay", "threads")

    def __init__(self):
        self.at = time.perf_counter_ns()
        sched = _schedstat()
        self.delay = None if sched is None else sched[1]
        self.threads = _thread_cpu()


def _sample() -> tuple:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return (time.process_time_ns(), _gc["pause_ns"], ru.ru_majflt,
            ru.ru_nivcsw)


def _standing(before: int) -> list[str]:
    """The innermost open span of each root opened before `before`, read
    from the frames its thread keeps (`_tls.top` there)."""
    out = []
    for root in list(_OPEN.values()):
        if getattr(root, "_t0", before) >= before:
            continue
        top = root._frames.get("top")
        out.append((top.name if top is not None else root.name)[:32])
    return out


def _beat(due: int, before: tuple, base: _Baseline) -> None:
    """One wake-up, `due` being when it was meant to happen; `before`
    the sample taken before the sleep."""
    now = time.perf_counter_ns()
    late = now - due
    _pulse["beats"] += 1
    if late <= _LATE_NS:
        return
    _LATE.append((due, now))
    _pulse["late_ns"] += late
    if late > _pulse["late_max_ns"]:
        _pulse["late_max_ns"] = late
    if late <= _STALL_NS:
        return
    # the cheap reads first: whatever queued up behind the stall runs now
    cpu, gc_ns, majflt, nivcsw = (a - b for a, b in zip(_sample(), before))
    if _gc["t0"]:
        # a collection that has not been counted yet: this thread is let
        # in at the first bytecode of its `stop` callback, before the sum
        gc_ns += now - _gc["t0"]
    # open through most of it: those that opened in its second half came
    # in when it was over, before this thread was given the GIL
    standing = _standing(due + late // 2)
    threads = _thread_cpu()
    grown = max(((ns - base.threads.get(name, 0), name)
                 for name, ns in threads.items()), default=(0, ""))
    rec = {
        "t_ns": now, "late_ns": late,
        # about late_ns: a thread of ours ran through it and held the
        # GIL; about 0: the process was not running at all
        "cpu_ns": cpu, "gc_ns": gc_ns, "majflt": majflt, "nivcsw": nivcsw,
        # the requests open across it, and the stage each stood in
        "roots_open": len(standing), "standing": standing[:16],
        # since a baseline `baseline_age_ns` older than the beat: the
        # Python thread whose CPU time grew most (the one that held the
        # GIL, where `cpu_ns` says one did) ...
        "busiest": grown[1][:48], "busiest_cpu_ns": grown[0],
        "baseline_age_ns": due - _PULSE_NS - base.at}
    sched = _schedstat() if base.delay is not None else None
    if sched is not None:
        # ... and the time our threads were runnable and given no CPU
        rec["run_delay_ns"] = max(sched[1] - base.delay, 0)
    _STALLS.append(rec)


def _pulse_loop() -> None:
    base, n = _Baseline(), 0
    due = _pulse["due"] = time.perf_counter_ns() + _PULSE_NS
    while True:
        before = _sample()
        t0 = time.perf_counter_ns()
        if due > t0:
            if _CAPTURE["active"]:
                # on the capture's clock a process that stood still is
                # one long `ogt:pulse` beside an empty device plane
                ann = _annotate("pulse", {})
                time.sleep((due - t0) / 1e9)
                ann.__exit__(None, None, None)
            else:
                time.sleep((due - t0) / 1e9)
        _beat(due, before, base)
        n += 1
        now = time.perf_counter_ns()
        late = now - due > _LATE_NS
        # an absolute schedule; beats a stall swallowed are not made up.
        # Set before the baseline is read, so that what holds this thread
        # up in there makes the next beat late and is not lost
        due = _pulse["due"] = max(due + _PULSE_NS, now)
        if n % _SCHED_EVERY == 0 or late:
            base = _Baseline()


_pulse_thread: threading.Thread | None = None


def watch_pulse() -> None:
    """Start the pulse (the server's start-up, beside watch_gc();
    idempotent): `runtime/pulse_*`, and with them `runtime/run_delay_ns`,
    `majflt`, `nivcsw`, read at each scrape."""
    global _pulse_thread
    if _pulse_thread is None or not _pulse_thread.is_alive():
        _pulse_thread = threading.Thread(target=_pulse_loop, name="ogt-pulse",
                                         daemon=True)
        _pulse_thread.start()
    _provide()


def stalls_doc() -> list[dict]:
    """/debug/vars `stalls`: the last 16 beats over 100 ms late."""
    return list(_STALLS)


class NoopTrace:
    """What current() answers when no trace is active: the executor
    calls trace methods unconditionally.  Its span is the one primitive
    (counters always; there is just no tree to add to)."""

    def span(self, name: str, **fields):
        return span(name, **fields)

    def add_field(self, key: str, value) -> None:
        pass

    def ctx(self) -> None:
        return None

    def graft(self, subtree) -> None:
        pass

    def finish(self) -> None:
        pass


NOOP = NoopTrace()


def _fmt_ns(ns: int) -> str:
    if ns >= 1_000_000_000:
        return f"{ns / 1e9:.3f}s"
    if ns >= 1_000_000:
        return f"{ns / 1e6:.3f}ms"
    if ns >= 1_000:
        return f"{ns / 1e3:.1f}µs"
    return f"{ns}ns"

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
     root; the `query_stage_seconds` histogram; and the bound query's
     stage map (/debug/queries, the slow log).  The root adds
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
three counters, one histogram observe and the tracker's stage add — no
Span objects, no ids.  OGT_TRACE=1 arms per-request trees; the arming
check is one thread-local read per span.
"""

from __future__ import annotations

import gc
import os
import random
import threading
import time

from opengemini_tpu.utils import lockdep
from opengemini_tpu.utils import stats as _stats
# devobs never imports this module at import time (it calls in lazily)
from opengemini_tpu.utils.devobs import _profile as _CAPTURE
from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER

_STATS = _stats.GLOBAL

# per-request span-tree capture (OGT_TRACE=1).  Mutable at runtime via
# /debug/ctrl?mod=obs — read through trace_enabled(), never directly.
_TRACE_ON = os.environ.get("OGT_TRACE", "") in ("1", "true")

# finished traces kept for /debug/trace?qid= (bounded; newest wins)
_RECENT_MAX = 256
_RECENT: dict[object, dict] = {}
_RECENT_LOCK = lockdep.Lock()

# per thread: `top`, the innermost open span (the self-time frame);
# `trace` and `node`, the active Trace and its innermost open tree node
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
    """What a helper thread needs to attach its spans under the calling
    thread's innermost open span: pass the result to adopt() there.
    None (and adopt(None) a no-op) when no trace is active here."""
    trace = getattr(_tls, "trace", None)
    return None if trace is None else (trace, _tls.node)


def adopt(handed):
    """The helper-thread side of handoff(): a `with` block whose spans
    parent under the dispatching span."""
    return activate(*handed) if handed is not None else activate(None)


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
    qid = _TRACKER.current_qid()
    if qid is not None:
        _TRACKER.add_stage_ns(qid, name, ns)


def record_stage(name: str, elapsed_ns: int) -> None:
    """A stage whose time was measured elsewhere, closed now: counters,
    histogram and the bound query's stage map, like a span's; the
    calling thread's open span counts it among its children."""
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
    end: `http_<route>` for route `query`, `prom` or `write`.  Under
    OGT_TRACE=1 (and `tree`) it owns the request's Trace, whose root
    span it is, and retains it for /debug/trace when it closes, under
    the qid the executor or the PromQL engine set on it."""

    __slots__ = ("route", "trace", "_cpu0", "_active")

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
        if self.trace is not None:
            self._active.__exit__(*exc)
            note_finished(self.trace.qid, self.trace)
        return False


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


def _gc_gauges() -> dict:
    return {"gc_pause_ns": _gc["pause_ns"],
            "gc_collections": _gc["collections"],
            "gc_gen2_pause_ns": _gc["gen2_pause_ns"],
            "gc_gen2_collections": _gc["gen2_collections"]}


def watch_gc() -> None:
    """Count the interpreter's garbage collections from here on (the
    server's start-up; idempotent): `runtime/gc_*` in /debug/vars, and a
    `gc` span and annotation for each full (generation 2) collection."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
        _STATS.register_provider("runtime", _gc_gauges)


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

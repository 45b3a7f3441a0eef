"""Compiled aggregate templates: pad -> jit -> run -> slice.

The executor hands numpy batches here; this module owns padding (shape
bucketing so the XLA compile cache stays small), jit caching, and device
round-trips. Padding rows are masked out; padded segments are sliced off
after the device call.

This is the plan-template cache of the reference
(engine/executor/select.go:121 buildPlanByCache) applied to XLA programs:
queries with the same (aggregate, padded shape, padded segment count,
dtype) reuse one compiled device program.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from opengemini_tpu.ops import window as winmod
from opengemini_tpu.ops.aggregates import AggSpec
from opengemini_tpu.utils import devobs
from opengemini_tpu.utils.stats import GLOBAL as _STATS

_REL_LO_BITS = 30
_REL_LO_MASK = (1 << _REL_LO_BITS) - 1


def compute_dtype() -> np.dtype:
    """float64 when x64 is enabled (CPU parity tests), else float32 (TPU)."""
    return np.dtype(np.float64) if jax.config.jax_enable_x64 else np.dtype(np.float32)


@functools.lru_cache(maxsize=512)
def _jitted_build(fn, num_segments: int, params: tuple):
    devobs.note_compile("agg_batch",
                        (fn.__name__, num_segments, params))

    @jax.jit
    def run(values, rel_hi, rel_lo, seg_ids, mask):
        return fn(values, rel_hi, rel_lo, seg_ids, num_segments, mask, *params)

    return run


def _jitted(fn, num_segments: int, params: tuple):
    _STATS.incr("device", "jit_lookups")  # hits = lookups - misses
    return _jitted_build(fn, num_segments, params)


def _count_fn(values, rel_hi, rel_lo, seg_ids, num_segments, mask):
    from opengemini_tpu.ops import segment as seg

    return seg.seg_count(seg_ids, num_segments, mask), None


def split_rel_ns(rel_ns: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Exact int64 ns offset -> lexicographic int32 (hi, lo) pair for
    device-side time ordering without int64."""
    hi = (rel_ns >> _REL_LO_BITS).astype(np.int32)
    lo = (rel_ns & _REL_LO_MASK).astype(np.int32)
    return hi, lo


class AggBatch:
    """A device-ready batch for one field: values, (hi, lo) relative times,
    segment ids, validity mask — plus a host-only int64 ns time array for
    exact selector timestamps. Accumulated across shards/series."""

    def __init__(self, dtype=None):
        self.dtype = dtype or compute_dtype()
        self.values: list[np.ndarray] = []
        self.rel_hi: list[np.ndarray] = []
        self.rel_lo: list[np.ndarray] = []
        self.seg_ids: list[np.ndarray] = []
        self.mask: list[np.ndarray] = []
        self.times_ns: list[np.ndarray] = []  # host-side only
        self.n = 0
        self._padded = None
        self._counts_cache: dict[int, np.ndarray] = {}
        self._mesh_outs: dict[int, dict] = {}

    def add(self, values, rel_ns, seg_ids, mask, times_ns, sids=None):
        self.values.append(np.asarray(values, dtype=self.dtype))
        hi, lo = split_rel_ns(np.asarray(rel_ns, dtype=np.int64))
        self.rel_hi.append(hi)
        self.rel_lo.append(lo)
        self.seg_ids.append(np.asarray(seg_ids, dtype=np.int32))
        self.mask.append(np.asarray(mask, dtype=np.bool_))
        self.times_ns.append(np.asarray(times_ns, dtype=np.int64))
        self.n += len(values)

    def _concat_padded(self):
        if self._padded is not None:
            return self._padded
        npad = winmod.pad_to(max(self.n, 1))
        values = np.zeros(npad, dtype=self.dtype)
        rel_hi = np.zeros(npad, dtype=np.int32)
        rel_lo = np.zeros(npad, dtype=np.int32)
        seg_ids = np.zeros(npad, dtype=np.int32)
        mask = np.zeros(npad, dtype=np.bool_)
        off = 0
        for v, h, l, s, m in zip(self.values, self.rel_hi, self.rel_lo, self.seg_ids, self.mask):
            k = len(v)
            values[off : off + k] = v
            rel_hi[off : off + k] = h
            rel_lo[off : off + k] = l
            seg_ids[off : off + k] = s
            mask[off : off + k] = m
            off += k
        self._padded = (values, rel_hi, rel_lo, seg_ids, mask)
        # the padded batch crosses to the device on the next kernel call
        devobs.note_transfer("h2d", "agg-batch",
                             sum(a.nbytes for a in self._padded))
        return self._padded

    def layout_name(self) -> str:
        """Trace label for EXPLAIN ANALYZE (each batch class owns its
        own name; executor never inspects internals)."""
        return "scatter"

    def host_times(self) -> np.ndarray:
        return (
            np.concatenate(self.times_ns) if self.times_ns else np.empty(0, np.int64)
        )

    def host_value_multiset(self, num_segments: int):
        """Per-segment (value, count) multiset of the batch's masked rows:
        (values f64, counts i64, offsets i64[num_segments+1]), values
        sorted ascending within each segment. EXACTLY mergeable across
        nodes — rank-based aggregates (percentile/median/count_distinct)
        recompute losslessly from merged multisets, so distributed
        pushdown ships O(groups x distinct) instead of raw columns
        (reference: the hash-exchange distribution of rank aggs,
        engine/executor agg transforms)."""
        if not self.values:
            return (np.empty(0, np.float64), np.empty(0, np.int64),
                    np.zeros(num_segments + 1, np.int64))
        v = np.concatenate(
            [np.asarray(x, np.float64) for x in self.values])
        s = np.concatenate(
            [np.asarray(x, np.int64) for x in self.seg_ids])
        m = np.concatenate([x for x in self.mask])
        keep = m & (s >= 0) & (s < num_segments)
        v, s = v[keep], s[keep]
        if len(v) == 0:
            return (v, np.empty(0, np.int64),
                    np.zeros(num_segments + 1, np.int64))
        order = np.lexsort((v, s))
        v, s = v[order], s[order]
        new = np.empty(len(v), np.bool_)
        new[0] = True
        new[1:] = (s[1:] != s[:-1]) | (v[1:] != v[:-1])
        starts = np.flatnonzero(new)
        counts = np.diff(np.append(starts, len(v)))
        v_u, s_u = v[starts], s[starts]
        offs = np.searchsorted(s_u, np.arange(num_segments + 1))
        return v_u, counts.astype(np.int64), offs.astype(np.int64)

    def counts(self, num_segments: int) -> np.ndarray:
        """Per-segment valid-row counts (cached per batch — every aggregate
        needs them for null rendering, compute once)."""
        got = self._counts_cache.get(num_segments)
        if got is None:
            seg_pad = winmod.pad_to(max(num_segments, 1), 256)
            arrays = self._concat_padded()
            counts, _ = devobs.launch(
                _jitted(_count_fn, seg_pad, ()), arrays,
                program="agg_count", xfer_site="agg-launch")
            got = devobs.fetch_np(counts)[:num_segments]
            self._counts_cache[num_segments] = got
        return got

    def run(self, spec: AggSpec, num_segments: int, params: tuple = ()):
        """Execute one aggregate; returns (values[num_segments],
        sel_idx[num_segments] | None, counts[num_segments]).

        With a configured device mesh (parallel/runtime.py) the mesh-
        servable aggregates run as ONE shard_map program over all devices
        (rows sharded, collective merges) — the executor's actual
        multi-chip path; the sel contract is identical (global row
        indices), so selector time resolution is unchanged."""
        from opengemini_tpu.parallel import runtime as prt

        mesh = prt.get_mesh()
        if mesh is not None and not params:
            got = self._run_mesh(mesh, spec, num_segments)
            if got is not None:
                return got
        seg_pad = winmod.pad_to(max(num_segments, 1), 256)
        arrays = self._concat_padded()
        fn = _jitted(spec.fn, seg_pad, tuple(params))
        _STATS.incr("device", "kernel_launches")
        out, sel = devobs.launch(fn, arrays, program="agg_" + spec.name,
                                 xfer_site="agg-launch")
        out_np = devobs.fetch_np(out)[:num_segments]
        sel_np = (devobs.fetch_np(sel)[:num_segments]
                  if sel is not None else None)
        return out_np, sel_np, self.counts(num_segments)

    def _run_mesh(self, mesh, spec, num_segments: int):
        from opengemini_tpu.parallel import distributed as dist

        if spec.name not in dist.MESH_AGGS:
            return None
        seg_pad = winmod.pad_to(max(num_segments, 1), 256)
        # winner-merge machinery is only compiled for the selector this
        # spec actually needs; value-only aggregates share one program
        sel = (spec.name,) if spec.name in ("min", "max", "first", "last") else ()
        cache_key = (seg_pad, sel)
        outs = self._mesh_outs.get(cache_key)
        if outs is None:
            values, rel_hi, rel_lo, seg_ids, mask = self._concat_padded()
            gidx = np.arange(len(values), dtype=np.int32)
            fn = dist.batch_agg_jit(mesh, seg_pad, sel)
            sharded = dist.shard_rows(
                mesh, values, rel_hi, rel_lo, seg_ids, mask, gidx
            )
            got = devobs.launch(fn, sharded, program="agg_mesh",
                                xfer_site="agg-launch")
            outs = devobs.fetch_tree(got)
            self._mesh_outs[cache_key] = outs
        out = outs[spec.name][:num_segments]
        sel = outs.get(spec.name + "_sel")
        if sel is not None:
            sel = sel[:num_segments]
        counts = outs["count"][:num_segments]
        return out, sel, counts

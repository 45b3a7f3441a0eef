"""Ragged-to-dense segment batching: the TPU answer to variable group sizes.

Scatter-based segment reduction serialises on a TPU while dense axis
reductions stream (how far apart the two are: not measured on the
present code). So the general aggregation path converts ragged (segment
id per row) batches into SIZE-BUCKETED DENSE matrices on the host and
every aggregate becomes a dense axis-1 reduction. Design constraints:

  - CANONICAL SHAPES: the WIDTHS ladder (16/64/256/1024, <=4x padding
    waste) and pow2-padded row counts keep the XLA compile cache tiny
    (arbitrary (g, w) shapes cost seconds of re-compile per query).
  - Segments wider than the top width SPLIT into consecutive sub-rows;
    combine on the host with reduceat (exact k-way variance combination
    for stddev: SSD = sum_i [ssd_i + c_i (mu_i - mu)^2]).
  - Offsets within segments come from RUN analysis (rows arrive as
    consecutive same-segment runs per series chunk), not a global
    argsort — freeze is O(N) + O(runs log runs).

This is SURVEY.md §7's 'ragged group sizes' hard part. Segments live in
exactly one bucket; per-bucket results scatter back into (num_segments,)
outputs host-side.
"""

from __future__ import annotations

import functools

import numpy as np

from opengemini_tpu.models import launch, layoutplan, templates
from opengemini_tpu.utils import devobs, tracing
from opengemini_tpu.utils.stats import GLOBAL as STATS

_REL_LO_BITS = 30
_REL_LO_MASK = (1 << _REL_LO_BITS) - 1

WIDTHS = (16, 64, 256, 1024)  # ~4x max padding waste, 4 canonical shapes
_MIN_G = 8

# aggregates the dense path supports (others use the scatter/lexsort path)
DENSE_AGGS = {"sum", "count", "mean", "min", "max", "first", "last",
              "spread", "stddev"}


def needs_selectors(agg_names, want_sel: bool) -> bool:
    """Whether these aggregates read the selector kernel's statistics:
    first/last always (their VALUES come from it), min/max only where
    the caller consults sel (never under GROUP BY time())."""
    names = set(agg_names)
    return bool(names & {"first", "last"}) or (
        want_sel and bool(names & {"min", "max"}))


# aggregates the host-exact int64 path supports (INT fields: float compute
# dtype would corrupt values beyond its mantissa — 2^24 in f32 on TPU).
# Selector aggs (min/max/first/last) stay on-device for row selection.
INT_EXACT_AGGS = {"sum", "count", "mean"}


class IntExactBatch:
    """Host-side exact int64 aggregation for INT fields (same add/run
    contract as AggBatch/BucketedBatch, minus selector support — the
    routing predicate never sends selectors here). numpy ufunc.at is
    slower than the device, but integer exactness wins for int columns —
    the same tradeoff storage/downsample.py makes for destructive
    rewrites. No timestamps are retained (no selectors -> no consumer)."""

    def __init__(self):
        self._vals: list[np.ndarray] = []
        self._seg: list[np.ndarray] = []
        self._mask: list[np.ndarray] = []
        self.n = 0
        self._acc = None

    def add(self, values, rel_ns, seg_ids, mask, times_ns, sids=None):
        self._vals.append(np.asarray(values))
        self._seg.append(np.asarray(seg_ids, dtype=np.int64))
        self._mask.append(np.asarray(mask, dtype=np.bool_))
        self.n += len(values)

    def layout_name(self) -> str:
        return "int-exact"

    def host_times(self) -> np.ndarray:
        return np.empty(0, np.int64)  # interface parity; never consumed

    def _accumulate(self, num_segments: int):
        if self._acc is not None:
            return self._acc
        s = np.zeros(num_segments, dtype=np.int64)
        c = np.zeros(num_segments, dtype=np.int64)
        for vals, seg, mask in zip(self._vals, self._seg, self._mask):
            idx = np.flatnonzero(mask)
            if not len(idx):
                continue
            v = vals[idx].astype(np.int64)
            g = seg[idx]
            np.add.at(s, g, v)
            np.add.at(c, g, 1)
        self._acc = (s, c)
        self._vals = self._seg = self._mask = []  # free the raw rows
        return self._acc

    def run(self, spec, num_segments: int, params: tuple = ()):
        s, c = self._accumulate(num_segments)
        if spec.name == "sum":
            out = s  # int64 end-to-end; renderer keeps integers exact
        elif spec.name == "count":
            out = c
        elif spec.name == "mean":
            out = s / np.maximum(c, 1)
        else:
            raise ValueError(f"int-exact path does not support {spec.name!r}")
        return np.asarray(out), None, c


class BucketedBatch:
    """Drop-in alternative to templates.AggBatch for dense-capable
    aggregates. add() accumulates ragged chunks; the first run() freezes
    the batch into dense buckets.  `plans` is the statement's
    layoutplan.Plans: the batches fed the same rows share one bucket
    plan, and each scatters only its own values and mask."""

    def __init__(self, dtype=None, plans=None):
        self.dtype = dtype or templates.compute_dtype()
        self._plans = plans or layoutplan.Plans()
        self._vals: list[np.ndarray] = []
        self._rel: list[np.ndarray] = []
        self._seg: list[np.ndarray] = []
        self._mask: list[np.ndarray] = []
        self._times: list[np.ndarray] = []
        self.n = 0
        self._frozen = None

    def add(self, values, rel_ns, seg_ids, mask, times_ns, sids=None):
        self._vals.append(np.asarray(values, dtype=self.dtype))
        self._rel.append(np.asarray(rel_ns, dtype=np.int64))
        # as handed (the plan widens it, once for all the fields): a
        # private int64 copy a field would hide that they share their rows
        self._seg.append(np.asarray(seg_ids))
        self._mask.append(np.asarray(mask, dtype=np.bool_))
        self._times.append(np.asarray(times_ns, dtype=np.int64))
        self.n += len(values)

    def layout_name(self) -> str:
        return "bucketed"

    def host_times(self) -> np.ndarray:
        return np.concatenate(self._times) if self._times else np.empty(0, np.int64)

    # -- freeze: ragged -> dense buckets --------------------------------

    def _freeze(self, num_segments: int):
        if self._frozen is None:
            if self.n == 0:
                self._frozen = []
            else:
                with tracing.span("layout_build", rows=self.n):
                    shared = self.build(num_segments)
                if shared:
                    STATS.incr("executor", "layout_plans_shared")
        return self._frozen

    def build(self, num_segments: int) -> bool:
        """The freeze itself, under the caller's span and count (a grid
        that refused freezes its fallback inside its own): the bucket
        plan of these rows — built here, or taken from the batch of the
        statement that built it: True — and this field's fill."""
        plan, shared = self._plans.get(
            ("buckets", num_segments), self._rel + self._seg,
            lambda: _plan_buckets(self._rel, self._seg, num_segments))
        vals = layoutplan.cat(self._vals)
        mask = layoutplan.cat(self._mask)
        self._frozen = [_Bucket(bp, self.dtype, vals, mask) for bp in plan]
        return shared

    # -- execution -------------------------------------------------------

    supports_want_sel = True

    def launch_items(self, num_segments: int, agg_names,
                     want_sel: bool = True) -> list:
        """The launch.Items these aggregates still need, one a bucket and
        kernel: the caller dispatches them with those of the statement's
        other batches (models/launch.py), and run() then only combines."""
        need_sel = needs_selectors(agg_names, want_sel)
        return [it for b in self._freeze(num_segments)
                for it in b.launch_items(need_sel)]

    def run(self, spec, num_segments: int, params: tuple = (),
            want_sel: bool = True):
        """Same contract as AggBatch.run: (values, sel|None, counts).
        want_sel=False skips the selector lex-scan kernels for min/max
        (their values come from the basic pass) — GROUP BY time() scans
        never consult sel. first/last still need the selector kernel for
        their VALUES.  Statistics no launch group brought yet are
        launched here, as a group of one."""
        buckets = self._freeze(num_segments)
        out = np.zeros(num_segments, dtype=np.float64)
        sel = np.zeros(num_segments, dtype=np.int64)
        counts = np.zeros(num_segments, dtype=np.int64)
        is_selector = spec.name in ("min", "max", "first", "last")
        need_sel = needs_selectors((spec.name,), want_sel)
        for b in buckets:
            st = b.combined(need_selectors=need_sel)
            counts[b.segs] = st["count"]
            if spec.name == "spread":
                out[b.segs] = st["max"] - st["min"]
            elif spec.name == "stddev":
                c = np.maximum(st["count"], 1)
                out[b.segs] = np.sqrt(np.maximum(st["ssd"] / np.maximum(c - 1, 1), 0))
            else:
                out[b.segs] = st[spec.name]
            if is_selector and need_sel:
                sel[b.segs] = st["sel_" + spec.name]
        return out, (sel if (is_selector and need_sel) else None), counts


def _plan_buckets(rel_parts, seg_parts, num_segments: int) -> list:
    """The bucket plan of a row set: one _BucketPlan a width in use.
    Nothing here reads a value or a mask."""
    seg = layoutplan.cat(seg_parts, np.int64)
    n = len(seg)
    counts = np.bincount(seg, minlength=num_segments)

    # within-segment arrival offsets via run analysis (no global sort)
    run_starts = np.concatenate([[0], np.flatnonzero(seg[1:] != seg[:-1]) + 1])
    run_segs = seg[run_starts]
    run_lens = np.diff(np.concatenate([run_starts, [n]]))
    order = np.argsort(run_segs, kind="stable")  # runs, not rows
    cum = np.zeros(len(run_starts), dtype=np.int64)
    lens_sorted = run_lens[order]
    segs_sorted = run_segs[order]
    csum = np.cumsum(lens_sorted) - lens_sorted
    first_run_of_seg = np.searchsorted(segs_sorted, segs_sorted)
    base_sorted = csum - csum[first_run_of_seg]
    cum[order] = base_sorted
    offsets = (
        np.arange(n, dtype=np.int64)
        - np.repeat(run_starts, run_lens)
        + np.repeat(cum, run_lens)
    )

    segs_of = []  # per bucket, the segments it holds
    bucket_of = np.full(num_segments, -1, dtype=np.int8)
    for bi, w in enumerate(WIDTHS):
        lo = WIDTHS[bi - 1] if bi else 0
        if w == WIDTHS[-1]:
            here = counts > lo  # larger segments split into sub-rows
        else:
            here = (counts > lo) & (counts <= w)
        segs_here = np.nonzero(here)[0]
        if len(segs_here) == 0:
            continue
        bucket_of[segs_here] = len(segs_of)
        segs_of.append((w, segs_here))

    # the rows' relative times as one array, made when a selector asks
    # (the time matrices, the host combine of split selectors)
    rel = functools.cache(lambda: layoutplan.cat(rel_parts))
    plan = []
    for bi, (w, segs) in enumerate(segs_of):
        seg_counts = counts[segs]
        # sub-row layout: segment k gets ceil(count/w) consecutive rows
        n_sub = np.maximum((seg_counts + w - 1) // w, 1)
        sub_base = np.cumsum(n_sub) - n_sub  # first sub-row per segment
        slot_of = np.zeros(num_segments, dtype=np.int64)
        slot_of[segs] = sub_base
        if len(segs_of) == 1:
            rows, seg_here, off = None, seg, offsets  # every row, in order
        else:
            rows = np.nonzero(bucket_of[seg] == bi)[0]
            seg_here, off = seg[rows], offsets[rows]
        flat = (slot_of[seg_here] + off // w) * w + off % w
        plan.append(_BucketPlan(w, segs, n_sub, sub_base, rows, flat, rel))
    return plan


class _BucketPlan:
    """One width's bucket of a row set: its segments, their sub-rows and
    the slot of every row — the half of a bucket every field of the
    statement shares."""

    def __init__(self, width, segs, n_sub, sub_base, rows, flat, rel):
        self.width = width
        self.segs = segs
        self.n_sub = n_sub
        self.sub_base = sub_base
        self.rows = rows  # None: every row of the batch, in arrival order
        self.flat = flat
        self.rel = rel
        self.g = int(n_sub.sum())
        self.shape = (_pow2_at_least(self.g, _MIN_G), width)
        self._selector_mats = None

    def _own(self, column: np.ndarray) -> np.ndarray:
        return column if self.rows is None else column[self.rows]

    def _mat(self, own: np.ndarray, dtype) -> np.ndarray:
        mat = np.zeros(self.shape, dtype=dtype)
        mat.reshape(-1)[self.flat] = own
        return mat

    def scatter(self, column: np.ndarray, dtype) -> np.ndarray:
        """One per-row column of the batch as this bucket's padded
        matrix."""
        return self._mat(self._own(column), dtype)

    def selector_mats(self) -> tuple:
        """(hmat, lmat, imat): every slot's split relative time and row
        index.  Only the selector kernels read them, so they are built
        when one is about to be launched — never for a `mean` under
        GROUP BY time() — and, depending on the rows alone, once for all
        the fields."""
        if self._selector_mats is None:
            with tracing.span("layout_build", rows=len(self.flat)):
                r = self._own(self.rel())
                idx = np.arange(len(r)) if self.rows is None else self.rows
                self._selector_mats = (
                    self._mat(r >> _REL_LO_BITS, np.int32),
                    self._mat(r & _REL_LO_MASK, np.int32),
                    self._mat(idx, np.int32))
        return self._selector_mats


class _Bucket:
    """One field's bucket: its values and mask scattered through the
    shared plan, and the statistics the kernels give of them."""

    def __init__(self, plan: _BucketPlan, dtype, vals, mask):
        self.plan = plan
        self.width = plan.width
        self.segs = plan.segs
        self.g = plan.g
        self.sub_base = plan.sub_base
        self.n_sub = plan.n_sub
        self.values = plan.scatter(vals, dtype)
        self.mask = plan.scatter(mask, np.bool_)
        self._raw: dict = {}
        self._items: dict = {}  # kernel family -> its launch.Item
        self._combined: dict = {}
        self._mesh_vm = None  # values and mask, row-sharded
        self._mesh_times = None  # the selector matrices, row-sharded
        self._mesh_epoch = None
        self._ledger = None

    def _args(self, mesh, family: str) -> tuple:
        """What the family's kernel is passed: `basic` the values and the
        mask, the selectors the plan's three time and index matrices
        between them.  With a configured mesh, row-sharded device arrays
        (bucket rows are independent — GSPMD partitions the dense
        reduces with zero collectives, parallel/distributed.py
        shard_leading_axis); otherwise the host matrices as-is. The
        sharded copies are keyed by mesh EPOCH so a hot config reload
        (runtime.set_mesh) reshards instead of serving a dead mesh."""
        vm = (self.values, self.mask)
        times = () if family == "basic" else self.plan.selector_mats()
        if mesh is not None and self.g >= mesh.size:
            from opengemini_tpu.parallel import distributed as _dist
            from opengemini_tpu.parallel import runtime as _prt

            epoch = _prt.mesh_epoch()
            if self._mesh_epoch != epoch:
                devobs.LEDGER.drop(self._ledger)
                self._mesh_vm = self._mesh_times = self._ledger = None
                self._mesh_epoch = epoch
            if self._mesh_vm is None:
                self._mesh_vm = _dist.shard_leading_axis(
                    mesh, *vm, xfer_site="bucket-shard")
                self._ledger = devobs.LEDGER.register(
                    "bucket_mesh", sum(int(a.nbytes) for a in self._mesh_vm),
                    mesh_epoch=epoch, label="bucket", anchor=self)
            if times and self._mesh_times is None:
                self._mesh_times = _dist.shard_leading_axis(
                    mesh, *times, xfer_site="bucket-shard")
                devobs.LEDGER.update(self._ledger, sum(
                    int(a.nbytes)
                    for a in self._mesh_vm + self._mesh_times))
            vm = self._mesh_vm
            times = self._mesh_times if times else ()
        return (vm[0], *times, vm[1])

    def launch_items(self, need_selectors: bool) -> list:
        """Items for the kernel families whose statistics are neither
        here nor in flight: `basic` always, the selector lex scans (4
        extra matrix passes) only for selector queries.  Each is passed
        what its kernel reads: `basic` the values and the mask, not the
        three time and index matrices beside them."""
        return launch.pending(self._items, _families(need_selectors),
                              self._item)

    def _item(self, family: str):
        from opengemini_tpu.parallel import runtime as _prt

        mesh = _prt.get_mesh()
        args = self._args(mesh, family)
        sharded = args[0] is not self.values
        if mesh is not None:
            # a bucket of fewer sub-rows than the mesh has devices keeps
            # its host matrices and so launches in a group of its own
            # (launch.Item.key holds the placement): counted, so that a
            # statement's launches on a mesh can be told from counters
            STATS.incr("device", "mesh_items_sharded" if sharded
                       else "mesh_items_unsharded")
        kind = family
        if family == "selectors" and sharded:
            # force the XLA selector form only when the inputs really are
            # mesh-sharded (pallas_call does not auto-partition);
            # unsharded buckets keep the fused Pallas kernel on TPU
            kind = "selectors_xla"
        return launch.Item("bucket_" + kind, _stats_fn(kind), args,
                           self._take)

    def _take(self, stats: dict) -> None:
        self._raw.update({k: a[: self.g] for k, a in stats.items()})

    def _raw_stats(self, need_selectors: bool) -> dict:
        """Per-sub-row device stats (launch.settle: launched here, alone,
        where no launch group brought them)."""
        launch.settle(self._items, _families(need_selectors), self._item)
        return self._raw

    def combined(self, need_selectors: bool) -> dict:
        """Per-segment stats: raw sub-row stats + host k-way combine."""
        if "count" in self._combined and (
            not need_selectors or "sel_first" in self._combined
        ):
            return self._combined
        raw = self._raw_stats(need_selectors)
        with tracing.span("host_combine"):
            return self._combine(raw, need_selectors)

    def _combine(self, raw: dict, need_selectors: bool) -> dict:
        if (self.n_sub == 1).all():
            self._combined = dict(raw)
            cnt = raw["count"].astype(np.int64)
            self._combined["count"] = cnt
            # mean recomputed host-side as f64(sum)/count — the SAME
            # arithmetic as the k-way combine branch below and the grid
            # layout (models/grid.py run()), so a query answers
            # identically whichever layout or slice width the planner
            # picked (the device f32 mean differs in the last ulp)
            self._combined["mean"] = raw["sum"] / np.maximum(cnt, 1)
            return self._combined
        starts = self.sub_base
        out = self._combined
        if "count" not in out:
            cnt = np.add.reduceat(raw["count"], starts).astype(np.int64)
            s = np.add.reduceat(raw["sum"], starts)
            mean = s / np.maximum(cnt, 1)
            # exact k-way variance combination:
            # SSD = sum_i [ssd_i + c_i (mu_i - mu)^2]
            mean_rep = np.repeat(mean, self.n_sub)
            extra = raw["count"] * (raw["mean"] - mean_rep) ** 2
            out.update(
                count=cnt,
                sum=s,
                mean=mean,
                min=np.minimum.reduceat(raw["min"], starts),
                max=np.maximum.reduceat(raw["max"], starts),
                ssd=np.add.reduceat(raw["ssd"] + extra, starts),
            )
        if need_selectors and "sel_first" not in out:
            rel = self.plan.rel()
            i64max = np.iinfo(np.int64).max
            i64min = np.iinfo(np.int64).min
            for name, latest in (("first", False), ("last", True)):
                sel_sub = raw["sel_" + name]
                r = np.where(
                    raw["count"] > 0, rel[sel_sub], i64max if not latest else i64min
                )
                red = np.maximum if latest else np.minimum
                best_rep = np.repeat(red.reduceat(r, starts), self.n_sub)
                hit = (r == best_rep) & (raw["count"] > 0)
                # exact-time ties across sub-rows: larger value wins
                # (reference FirstReduce/LastReduce tie rule)
                v_best = np.repeat(np.maximum.reduceat(
                    np.where(hit, raw[name], -np.inf), starts), self.n_sub)
                hit &= raw[name] == v_best
                idx_sub = np.where(hit, np.arange(len(r)), len(r))
                pick = np.clip(np.minimum.reduceat(idx_sub, starts), 0, len(r) - 1)
                out[name] = raw[name][pick]
                out["sel_" + name] = sel_sub[pick]
            for name in ("min", "max"):
                sel_sub = raw["sel_" + name]
                ext_rep = np.repeat(out[name], self.n_sub)
                hit = (raw[name] == ext_rep) & (raw["count"] > 0)
                r = np.where(hit, rel[sel_sub], i64max)
                best_rep = np.repeat(np.minimum.reduceat(r, starts), self.n_sub)
                hit &= r == best_rep
                idx_sub = np.where(hit, np.arange(len(r)), len(r))
                pick = np.clip(np.minimum.reduceat(idx_sub, starts), 0, len(r) - 1)
                out["sel_" + name] = sel_sub[pick]
        return out


def _families(need_selectors: bool) -> tuple:
    return ("basic", "selectors") if need_selectors else ("basic",)


def _pow2_at_least(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


_BIG_I32 = 2**31 - 1


def _stats_fn(kind: str):
    """The per-sub-row stat kernel of one bucket matrix set, as a
    traceable function a launch group's program runs once a field
    (models/launch.py): 'basic' (v, m) — one fused pass for
    count/sum/mean/min/max/ssd — and 'selectors' (v, hi, lo, idx, m) —
    the four lexicographic (hi, lo, col) scans for first/last/min/max
    row selection.  'selectors_xla' forces the XLA form — used with a
    device mesh, where GSPMD partitions the plain XLA kernels over
    row-sharded inputs but pallas_call does not auto-partition.

    On a TPU backend 'selectors' routes to the fused Pallas tile kernel
    (ops/pallas_segment.py) — one HBM pass feeds every statistic; the
    XLA expressions below serve CPU runs and remain the semantics
    oracle the Pallas kernels are tested against.  The compile inventory
    counts a kind (`bucket_<kind>`) when a program over it is built, so
    /debug/device shows which of them a workload really ran."""
    from opengemini_tpu.ops import pallas_segment

    if kind == "selectors" and pallas_segment.use_pallas():
        return pallas_segment.bucket_stats_selectors
    if kind == "basic":
        return _xla_stats_fns()[0]
    if kind in ("selectors", "selectors_xla"):
        return _xla_stats_fns()[1]
    raise KeyError(kind)  # unknown kinds must raise, not silently alias


@functools.lru_cache(maxsize=1)
def _xla_stats_fns():
    """(basic, selectors) as traceable XLA functions."""
    import jax.numpy as jnp

    def _take(mat, col_sel):
        return jnp.take_along_axis(mat, col_sel[:, None], axis=1)[:, 0]

    def _lex_col(hi, lo, cand, latest):
        """Column of the lexicographically (hi, lo) extreme candidate;
        ties by column order. int32-only — exact without x64 (TPU)."""
        big = _BIG_I32
        col = jnp.arange(hi.shape[1], dtype=jnp.int32)[None, :]
        if latest:
            hi_ext = jnp.where(cand, hi, -big).max(axis=1)
            c2 = cand & (hi == hi_ext[:, None])
            lo_ext = jnp.where(c2, lo, -big).max(axis=1)
            c3 = c2 & (lo == lo_ext[:, None])
            return jnp.where(c3, col, -big).max(axis=1)
        hi_ext = jnp.where(cand, hi, big).min(axis=1)
        c2 = cand & (hi == hi_ext[:, None])
        lo_ext = jnp.where(c2, lo, big).min(axis=1)
        c3 = c2 & (lo == lo_ext[:, None])
        return jnp.where(c3, col, big).min(axis=1)

    def basic(v, m):
        zero = jnp.zeros((), v.dtype)
        vz = jnp.where(m, v, zero)
        cnt = m.sum(axis=1)
        s = vz.sum(axis=1)
        big = jnp.array(jnp.inf, v.dtype)
        mn = jnp.where(m, v, big).min(axis=1)
        mx = jnp.where(m, v, -big).max(axis=1)
        mean = s / jnp.maximum(cnt, 1).astype(v.dtype)
        dev = jnp.where(m, v - mean[:, None], zero)
        ssd = (dev * dev).sum(axis=1)
        return {"count": cnt, "sum": s, "ssd": ssd, "min": mn, "max": mx,
                "mean": mean}

    def _first_last_col(v, hi, lo, cand, latest):
        """Extreme (hi, lo) time; exact-time ties take the LARGER VALUE
        (reference agg_func.go FirstReduce/LastReduce), then column
        order."""
        big = _BIG_I32
        col = jnp.arange(hi.shape[1], dtype=jnp.int32)[None, :]
        if latest:
            hi_ext = jnp.where(cand, hi, -big).max(axis=1)
            c2 = cand & (hi == hi_ext[:, None])
            lo_ext = jnp.where(c2, lo, -big).max(axis=1)
            c3 = c2 & (lo == lo_ext[:, None])
        else:
            hi_ext = jnp.where(cand, hi, big).min(axis=1)
            c2 = cand & (hi == hi_ext[:, None])
            lo_ext = jnp.where(c2, lo, big).min(axis=1)
            c3 = c2 & (lo == lo_ext[:, None])
        fbig = jnp.array(jnp.inf, v.dtype)
        v_ext = jnp.where(c3, v, -fbig).max(axis=1)
        c4 = c3 & (v == v_ext[:, None])
        return jnp.where(c4, col, big).min(axis=1)

    def selectors(v, hi, lo, idx, m):
        big = jnp.array(jnp.inf, v.dtype)
        mn = jnp.where(m, v, big).min(axis=1)
        mx = jnp.where(m, v, -big).max(axis=1)
        clip = lambda c: jnp.clip(c, 0, v.shape[1] - 1)  # noqa: E731
        cf = clip(_first_last_col(v, hi, lo, m, latest=False))
        cl = clip(_first_last_col(v, hi, lo, m, latest=True))
        cmin = clip(_lex_col(hi, lo, m & (v == mn[:, None]), latest=False))
        cmax = clip(_lex_col(hi, lo, m & (v == mx[:, None]), latest=False))
        return {
            "first": _take(v, cf), "last": _take(v, cl),
            "sel_first": _take(idx, cf), "sel_last": _take(idx, cl),
            "sel_min": _take(idx, cmin), "sel_max": _take(idx, cmax),
        }

    return basic, selectors

"""Regular-grid dense batch: production wiring for the windows-on-lanes
fast path (ops/segment.py grid_window_agg_t).

TSBS-shaped data — every series sampled on a constant stride — lets
windowed aggregation skip segment machinery entirely: place samples into
a dense (series_run, samples_per_window, num_windows) grid and every
per-window statistic is one sublane-axis reduce (its speed against the
bucketed layout: not measured on the present code).
The reference reaches its regular fast path through pre-aggregation
metadata + the interval cursor (engine/immutable/pre_aggregation.go:40,
engine/aggregate_cursor.go:343); here regularity is detected per scan and
the grid is assembled directly from the scanned chunks.

GridBatch is SPECULATIVE: add() accumulates raw rows exactly like
BucketedBatch; the first launch_items() or run() checks regularity (one global stride that
divides the window, per-series-run constant spacing, bounded density
waste) and either assembles the grid or silently delegates to a
BucketedBatch built from the same rows. Wrong results are impossible —
only the layout changes. The executor's stats counters record which path
engaged (executor/grid_batches vs executor/grid_fallbacks).

Contract is the AggBatch/BucketedBatch contract: add(values, rel_ns,
seg_ids, mask, times_ns, sids=...) + run(spec, num_segments, params) ->
(values, sel|None, counts), where sel indexes the batch's host_times()
row order (selector timestamp resolution is unchanged).
"""

from __future__ import annotations

import functools

import numpy as np

from opengemini_tpu.models import launch, layoutplan, ragged, templates
from opengemini_tpu.utils import devobs, tracing
from opengemini_tpu.utils.stats import GLOBAL as STATS

# aggregates the grid path serves; others never get routed here
GRID_AGGS = {"count", "sum", "mean", "min", "max", "spread", "stddev",
             "first", "last"}

_MIN_S = 8
_MIN_W = 8
# hard cap on grid slots (~0.9 GB f64+mask+idx at 2^26) and max slots per
# scanned row (sparse series would explode the dense grid)
_MAX_GRID_CELLS = 1 << 26
_MAX_EXPANSION = 8
# samples-per-window above this would make (S, k, W) degenerate (one
# giant sublane axis); bucketed split rows handle it better
_MAX_K = 8192


class GridBatch:
    accepts_boundaries = True  # coalesced adds forward record breaks

    def __init__(self, dtype, W: int, every_ns: int, plans=None):
        self.dtype = dtype or templates.compute_dtype()
        self.W = int(W)
        self.every_ns = int(every_ns)
        # the statement's layout plans: the batches fed the same rows
        # share the grid's plan (or its refusal, and then the buckets')
        self._plans = plans or layoutplan.Plans()
        self._vals: list[np.ndarray] = []
        self._rel: list[np.ndarray] = []
        self._seg: list[np.ndarray] = []
        self._mask: list[np.ndarray] = []
        self._times: list[np.ndarray] = []
        self._sids: list[np.ndarray | None] = []
        self._bnds: list[np.ndarray | None] = []
        self.n = 0
        self._state = None  # grid state dict after a successful freeze
        self._fallback = None  # BucketedBatch when the grid refuses
        self._raw: dict = {}  # lazy per-(row, window) device stats
        self._items: dict = {}  # kernel kind -> its launch.Item
        # scan signature for the decoded-column cache's DEVICE tier
        # (storage/colcache.py): when the executor proves the scan
        # deterministic (local shards) it stamps a token here and the
        # padded device_put grid buffers — MESH-SHARDED when a device
        # mesh is configured — are retained/reused across identical
        # scans: a warm repeat skips the H2D transfer, the per-query
        # reshard, and (on a hit) the host-side grid scatter too
        self.device_cache_token = None

    def add(self, values, rel_ns, seg_ids, mask, times_ns, sids=None,
            boundaries=None):
        """`boundaries` (optional sorted row offsets within this add)
        marks run breaks inside a coalesced add — per-shard sid numbering
        is independent, so a stager that concatenates records from
        different shards must keep equal sid values from fusing into one
        stride run."""
        vals = np.asarray(values, dtype=self.dtype)
        self._vals.append(vals)
        self._rel.append(np.asarray(rel_ns, dtype=np.int64))
        # segment and series ids stay as handed (the plan widens and
        # expands them, once for all the fields): a private copy a field
        # would hide that the fields share their rows
        self._seg.append(np.asarray(seg_ids))
        self._mask.append(np.asarray(mask, dtype=np.bool_))
        self._times.append(np.asarray(times_ns, dtype=np.int64))
        self._sids.append(
            sids if sids is None or np.isscalar(sids) else np.asarray(sids))
        self._bnds.append(
            None if boundaries is None else np.asarray(boundaries))
        self.n += len(vals)

    def layout_name(self) -> str:
        if self._state is not None:
            return "grid"
        if self._fallback is not None:
            return "grid->bucketed"
        return "grid (not executed)"  # e.g. full result-cache hit

    def host_times(self) -> np.ndarray:
        return (np.concatenate(self._times) if self._times
                else np.empty(0, np.int64))

    def host_value_multiset(self, num_segments: int):
        """Rank-aggregate multisets never route to the grid path locally,
        but the distributed merge may ask any batch for them."""
        self._ensure_fallback()
        return self._fallback.host_value_multiset(num_segments)

    # -- freeze ----------------------------------------------------------

    def _ensure_fallback(self):
        if self._fallback is None:
            if self._vals is None:
                raise RuntimeError(
                    "bucketed fallback requested after prefetch() dropped "
                    "the raw rows — prefetch callers must keep aggs "
                    "within GRID_AGGS")
            # the same row arrays and the same plans: the fallbacks of a
            # statement's fields share one bucket plan
            fb = ragged.BucketedBatch(self.dtype, self._plans)
            for v, r, s, m, t in zip(self._vals, self._rel, self._seg,
                                     self._mask, self._times):
                fb.add(v, r, s, m, t)
            self._fallback = fb

    def _freeze(self, num_segments: int):
        """Returns the grid state dict, or None (delegate to bucketed).
        The plan — or the refusal — is built by the first batch of the
        statement that was fed these rows and taken by the others; what
        a batch does for itself is the fill."""
        if self._state is not None or self._fallback is not None:
            return self._state
        with tracing.span("layout_build", rows=self.n):
            plan, shared = self._plans.get(
                ("grid", self.W, self.every_ns, num_segments),
                self._rel + self._seg + self._sids + self._bnds,
                lambda: _plan_grid(
                    self._rel, self._seg, self._sids, self._bnds, self.W,
                    self.every_ns, num_segments))
            if plan is None:
                # the fallback freezes inside this span and count; its
                # bucket plan is shared wherever the refusal was
                self._ensure_fallback()
                if self.n:
                    self._fallback.build(num_segments)
            else:
                self._state = self._fill(plan)
        STATS.add("executor", (
            ("grid_fallbacks" if plan is None else "grid_batches", 1),
            ("layout_plans_shared", int(shared))))
        return self._state

    def _fill(self, plan: dict) -> dict:
        """This field's half of a freeze, as the state dict: its grids
        from the device tier, or scattered here through the plan's
        index."""
        shape, flat, mesh = plan["shape"], plan["flat"], plan["mesh"]
        # device tier consult: an identically-signed earlier scan already
        # holds the padded grid on device — skip the host scatter AND the
        # H2D transfer (the signature embeds every shard's data_version,
        # so content equality is the same guarantee the incremental
        # result cache relies on)
        dev_entry = None
        if self.device_cache_token is not None:
            from opengemini_tpu.storage import colcache

            dev_entry = colcache.GLOBAL.device_get(
                self.device_cache_token,
                shape=shape, dtype=str(self.dtype), mesh=mesh)
        return {
            **plan,
            "arrays": (self._scatter_grid(shape, flat)
                       if dev_entry is None else None),
            "device_entry": dev_entry,
            # imat (sample-index grid for the selector kernels) builds
            # lazily from `flat` — count/sum/mean scans never pay for it
            "imat": None,
        }

    # -- execution -------------------------------------------------------

    def launch_items(self, num_segments: int, agg_names,
                     want_sel: bool = True) -> list:
        """The launch.Items these aggregates still need — the grid's
        kernels, or the bucketed fallback's where the grid refused or an
        aggregate is not the grid's: the caller dispatches them with
        those of the statement's other batches (models/launch.py), and
        run() then only combines."""
        names = set(agg_names)
        mine = names & GRID_AGGS \
            if self._freeze(num_segments) is not None else set()
        out = launch.pending(self._items, _kinds(mine, want_sel),
                             self._item) if mine else []
        if names - mine:
            self._ensure_fallback()
            out += self._fallback.launch_items(num_segments, names - mine,
                                               want_sel)
        return out

    def run(self, spec, num_segments: int, params: tuple = (),
            want_sel: bool = True):
        """want_sel=False skips the selector index machinery for min/max
        (their values come from the basic kernel) — the sliced scan path
        never consults sel (selector timestamps only matter without
        GROUP BY time()).  Statistics no launch group brought yet are
        launched here, as a group of one."""
        st = self._freeze(num_segments)
        if st is None:
            return self._fallback.run(spec, num_segments, params,
                                      want_sel=want_sel)
        name = spec.name
        if name not in GRID_AGGS:
            self._ensure_fallback()
            return self._fallback.run(spec, num_segments, params,
                                      want_sel=want_sel)
        launch.settle(self._items, _kinds((name,), want_sel), self._item)
        with tracing.span("host_combine"):
            return self._combine(st, self._raw, name, num_segments, want_sel)

    def _combine(self, st, raw, name: str, num_segments: int,
                 want_sel: bool):
        """The host half of run(): per-row device stats reduced to the
        (group, window) segments."""
        G = num_segments // self.W
        order, starts = st["row_order"], st["gid_starts"]
        gids, W = st["gids_present"], self.W

        cnt_rows = raw["count"][order].astype(np.int64)
        cnt_g = np.add.reduceat(cnt_rows, starts, axis=0)
        counts = np.zeros(num_segments, dtype=np.int64)
        counts.reshape(G, W)[gids] = cnt_g

        out = np.zeros(num_segments, dtype=np.float64)
        out2d = out.reshape(G, W)
        sel = None
        if name == "count":
            out2d[gids] = cnt_g
        elif name == "sum":
            out2d[gids] = np.add.reduceat(raw["sum"][order], starts, axis=0)
        elif name == "mean":
            s = np.add.reduceat(raw["sum"][order], starts, axis=0)
            out2d[gids] = s / np.maximum(cnt_g, 1)
        elif name == "min":
            out2d[gids] = np.minimum.reduceat(raw["min"][order], starts, axis=0)
            if want_sel:
                sel = self._combine_value_selector(st, raw, "min", num_segments)
        elif name == "max":
            out2d[gids] = np.maximum.reduceat(raw["max"][order], starts, axis=0)
            if want_sel:
                sel = self._combine_value_selector(st, raw, "max", num_segments)
        elif name == "spread":
            mn = np.minimum.reduceat(raw["min"][order], starts, axis=0)
            mx = np.maximum.reduceat(raw["max"][order], starts, axis=0)
            out2d[gids] = mx - mn
        elif name == "stddev":
            s = np.add.reduceat(raw["sum"][order], starts, axis=0)
            mean_g = s / np.maximum(cnt_g, 1)
            # exact k-way variance combine across the gid's series rows:
            # SSD = sum_i [ssd_i + c_i (mu_i - mu)^2]
            mean_rep = np.repeat(mean_g, st["rows_per_gid"], axis=0)
            extra = cnt_rows * (raw["mean"][order] - mean_rep) ** 2
            ssd = np.add.reduceat(raw["ssd"][order] + extra, starts, axis=0)
            out2d[gids] = np.sqrt(
                np.maximum(ssd / np.maximum(cnt_g - 1, 1), 0))
        elif name in ("first", "last"):
            vals2d, sel = self._combine_time_selector(st, raw, name,
                                                      num_segments)
            out2d[gids] = vals2d
        return out, sel, counts

    def _scatter_grid(self, shape, flat):
        """Scatter the raw rows into the padded (S_pad, k, W_pad) grid:
        the ONE scatter shared by freeze and the entry-lost rebuild, so
        the rare rebuild branch can never diverge from the hot path."""
        vt = np.zeros(shape, dtype=self.dtype)
        mt = np.zeros(shape, dtype=np.bool_)
        vt.reshape(-1)[flat] = layoutplan.cat(self._vals)
        mt.reshape(-1)[flat] = layoutplan.cat(self._mask)
        return vt, mt

    def _build_imat_np(self):
        st = self._state
        if st["flat"] is None:
            raise RuntimeError(
                "selector index grid needed after prefetch dropped the "
                "host rows — prefetch callers must declare selector aggs")
        imat = np.zeros(st["shape"], dtype=np.int32)
        imat.reshape(-1)[st["flat"]] = np.arange(st["n"], dtype=np.int32)
        return imat

    @staticmethod
    def _mesh_for_rows(rows: int):
        """The configured device mesh when ``rows`` grid rows can shard
        over it, else None (replicated single-device exactly as before)."""
        from opengemini_tpu.parallel import runtime as _prt

        mesh = _prt.get_mesh()
        if mesh is None or rows < mesh.size:
            return None
        return mesh

    def _device_put(self, mesh, *arrays_np, xfer_site: str = "grid-shard"):
        """One explicit device_put per array, straight into the final
        layout: row-sharded over the mesh when configured (NamedSharding,
        parallel/distributed.py), plain single-device otherwise — never a
        replicated intermediate that a later reshard would re-copy."""
        import time as _time

        import jax

        if mesh is not None:
            from opengemini_tpu.parallel import distributed as _dist

            return _dist.shard_leading_axis(mesh, *arrays_np,
                                            xfer_site=xfer_site)
        t0 = _time.perf_counter_ns()
        out = tuple(jax.device_put(a) for a in arrays_np)
        devobs.note_transfer(
            "h2d", xfer_site, sum(int(a.nbytes) for a in arrays_np),
            (_time.perf_counter_ns() - t0) / 1e9)
        return out

    def _device_arrays(self, with_imat: bool):
        st = self._state
        mesh = self._mesh_for_rows(st["shape"][0])
        ent = st.get("device_entry")
        if ent is not None and ent.get("mesh") is not mesh:
            # mesh changed since the entry was consulted/stored (hot
            # config reload): re-get — the cache reshards the retained
            # buffers onto the new mesh, donating the stale layout
            from opengemini_tpu.storage import colcache

            ent = colcache.GLOBAL.device_get(
                self.device_cache_token, shape=st["shape"],
                dtype=str(self.dtype), mesh=mesh)
            st["device_entry"] = ent
        if (ent is None and self.device_cache_token is not None
                and st["arrays"] is not None):
            # cold scan with the device tier on: one transfer into the
            # final (sharded) layout, retained in the cache — later
            # kernel kinds of THIS scan and identically-signed future
            # scans all skip the transfer
            from opengemini_tpu.storage import colcache

            vt_np, mt_np = st["arrays"]
            vt_d, mt_d = self._device_put(mesh, vt_np, mt_np,
                                          xfer_site="colcache-fill")
            ent = colcache.GLOBAL.device_put_grid(
                self.device_cache_token, vt_d, mt_d,
                shape=vt_np.shape, dtype=str(vt_np.dtype), mesh=mesh)
            st["device_entry"] = ent
        if ent is not None:
            imat = None
            if with_imat:
                imat = ent.get("imat")
                if imat is None:
                    from opengemini_tpu.storage import colcache

                    ent_mesh = ent.get("mesh")
                    (imat_d,) = self._device_put(
                        ent_mesh, self._build_imat_np(),
                        xfer_site="colcache-fill")
                    imat = colcache.GLOBAL.device_add_imat(
                        self.device_cache_token, ent, imat_d,
                        mesh=ent_mesh)
                    if ent.get("mesh") is not ent_mesh:
                        # a concurrent reshard moved the entry while the
                        # imat was building: one more pass picks up the
                        # new layout end to end (bounded by mesh swaps,
                        # which are rare admin events)
                        return self._device_arrays(with_imat)
            return ent["vt"], ent["mt"], imat
        if st["arrays"] is None:
            # the freeze-time device-cache hit skipped the host scatter,
            # then the entry vanished (mesh swap dropped an indivisible
            # geometry, or LRU eviction): rebuild the grid from the raw
            # rows — unless prefetch() already dropped them
            if self._vals is None or st["flat"] is None:
                raise RuntimeError(
                    "grid device entry lost after prefetch dropped the "
                    "host rows (device mesh changed mid-query?)")
            st["arrays"] = self._scatter_grid(st["shape"], st["flat"])
        vt, mt = st["arrays"]
        imat = None
        if with_imat:
            imat = st["imat"]
            if imat is None:
                imat = self._build_imat_np()
                st["imat"] = imat
        if mesh is not None:
            # multi-chip: series-run rows are independent — shard the S
            # axis, GSPMD partitions the sublane reduces, no collectives.
            # Keyed by mesh EPOCH: a hot config reload (runtime.set_mesh)
            # must never serve shards laid out for a dead mesh.
            from opengemini_tpu.parallel import distributed as _dist
            from opengemini_tpu.parallel import runtime as _prt

            epoch = _prt.mesh_epoch()
            if st.get("mesh_epoch") != epoch:
                st.pop("mesh_arrays", None)
                st.pop("mesh_imat", None)
                devobs.LEDGER.drop(st.pop("ledger", None))
                st["mesh_epoch"] = epoch
            if "mesh_arrays" not in st:
                st["mesh_arrays"] = _dist.shard_leading_axis(
                    mesh, vt, mt, xfer_site="grid-shard")
                st["ledger"] = devobs.LEDGER.register(
                    "grid_mesh", sum(int(a.nbytes)
                                     for a in st["mesh_arrays"]),
                    mesh_epoch=epoch, label="grid", anchor=self)
            vt, mt = st["mesh_arrays"]
            if with_imat:
                if "mesh_imat" not in st:
                    (st["mesh_imat"],) = _dist.shard_leading_axis(
                        mesh, imat, xfer_site="grid-shard")
                    devobs.LEDGER.update(
                        st.get("ledger"),
                        sum(int(a.nbytes) for a in st["mesh_arrays"])
                        + int(st["mesh_imat"].nbytes))
                imat = st["mesh_imat"]
        return vt, mt, imat

    def _item(self, kind: str):
        """One kernel over this grid as a launch.Item, to be dispatched
        with the same kernel over the statement's other grids of this
        shape (JAX dispatch is async — the host is free to keep decoding
        while the device reduces)."""
        st = self._state
        if st["arrays"] is None and st.get("device_entry") is None:
            raise RuntimeError(
                f"grid kernel {kind!r} needed after prefetch dropped the "
                "host arrays")
        vt, mt, imat = self._device_arrays(with_imat=(kind == "selectors"))
        return launch.Item(
            "grid_" + kind, _KERNELS[kind],
            (vt, mt, imat) if kind == "selectors" else (vt, mt),
            functools.partial(self._take, st["S"]))

    def _take(self, S: int, stats: dict) -> None:
        self._raw.update({k: a[:S, : self.W] for k, a in stats.items()})

    supports_want_sel = True

    def prefetch(self, num_segments: int, agg_names,
                 want_sel: bool = False) -> None:
        """Sliced-scan overlap hook: freeze the grid and dispatch every
        kernel this batch's aggregates will need (a launch group of this
        one batch), then drop the host-side row lists and grid arrays —
        run() lands the in-flight device results later. No-op when the
        grid refuses (bucketed fallback keeps its rows) or an agg
        outside GRID_AGGS is coming."""
        names = set(agg_names)
        if not names or not names <= GRID_AGGS:
            return
        st = self._freeze(num_segments)
        if st is None:
            return
        launch.dispatch(launch.pending(
            self._items, _kinds(names, want_sel), self._item))
        # inputs are on device now; free the host copies
        st["arrays"] = None
        st["imat"] = None
        st["flat"] = None
        st.pop("mesh_arrays", None)
        st.pop("mesh_imat", None)
        devobs.LEDGER.drop(st.pop("ledger", None))
        self._vals = self._rel = self._seg = self._mask = self._sids = None
        self._bnds = self._plans = None  # the plan is its siblings' to keep

    def _combine_value_selector(self, st, raw, name, num_segments):
        """Per-segment row index of the selected min/max point. Value ties
        break by earliest timestamp then row order — the BucketedBatch /
        ops/segment.py rule."""
        order, starts = st["row_order"], st["gid_starts"]
        gids = st["gids_present"]
        G = num_segments // self.W
        rel = st["rel"]
        S = st["S"]
        v = raw[name][order]
        red = np.minimum if name == "min" else np.maximum
        ext = red.reduceat(v, starts, axis=0)
        ext_rep = np.repeat(ext, st["rows_per_gid"], axis=0)
        cnt = raw["count"][order]
        sel_sub = raw["sel_" + name][order]
        hit = (v == ext_rep) & (cnt > 0)
        t = np.where(hit, rel[sel_sub], np.iinfo(np.int64).max)
        tbest = np.repeat(np.minimum.reduceat(t, starts, axis=0),
                          st["rows_per_gid"], axis=0)
        hit &= t == tbest
        rows = np.arange(S, dtype=np.int64)[:, None]
        idx = np.where(hit, rows, S)
        pick = np.clip(np.minimum.reduceat(idx, starts, axis=0), 0, S - 1)
        sel = np.zeros(num_segments, dtype=np.int64)
        # result[g, w] = sel_sub[pick[g, w], w] — rows align with gids order
        sel.reshape(G, self.W)[gids] = np.take_along_axis(sel_sub, pick, axis=0)
        return sel

    def _combine_time_selector(self, st, raw, name, num_segments):
        """first/last across a gid's series rows: pick by extreme exact
        timestamp (ties by row order). Returns (values for present gids,
        sel array)."""
        order, starts = st["row_order"], st["gid_starts"]
        gids = st["gids_present"]
        G = num_segments // self.W
        rel = st["rel"]
        S = st["S"]
        cnt = raw["count"][order]
        sel_sub = raw["sel_" + name][order]
        vals_sub = raw[name][order]
        latest = name == "last"
        bad = np.iinfo(np.int64).min if latest else np.iinfo(np.int64).max
        t = np.where(cnt > 0, rel[sel_sub], bad)
        red = np.maximum if latest else np.minimum
        tbest = np.repeat(red.reduceat(t, starts, axis=0),
                          st["rows_per_gid"], axis=0)
        hit = (cnt > 0) & (t == tbest)
        # exact-time ties across series rows: larger value wins
        # (reference FirstReduce/LastReduce tie rule)
        v_best = np.repeat(np.maximum.reduceat(
            np.where(hit, vals_sub, -np.inf), starts, axis=0),
            st["rows_per_gid"], axis=0)
        hit &= vals_sub == v_best
        rows = np.arange(S, dtype=np.int64)[:, None]
        if latest:
            # time ties pick the LATEST row in scan order — the
            # ops/segment.py `smax(idx)` rule for last()
            idx = np.where(hit, rows, -1)
            pick = np.clip(np.maximum.reduceat(idx, starts, axis=0), 0, S - 1)
        else:
            idx = np.where(hit, rows, S)
            pick = np.clip(np.minimum.reduceat(idx, starts, axis=0), 0, S - 1)
        vals2d = np.take_along_axis(vals_sub, pick, axis=0)
        sel = np.zeros(num_segments, dtype=np.int64)
        sel.reshape(G, self.W)[gids] = np.take_along_axis(sel_sub, pick, axis=0)
        return vals2d, sel


def _plan_grid(rel_parts, seg_parts, sid_parts, bnd_parts, W: int,
               every_ns: int, num_segments: int):
    """The grid plan of a row set, or None (the grid refuses): the stride
    analysis, the padded shape, every row's slot (`flat`) and the combine
    index of the series rows.  Nothing here reads a value or a mask, so
    the fields of a statement share it (models/layoutplan.py)."""
    n = sum(len(r) for r in rel_parts)
    if n == 0 or W < 1 or num_segments % W:
        return None
    if any(s is None for s in sid_parts):
        return None  # no series identity: cannot prove no slot clash
    rel = layoutplan.cat(rel_parts)
    seg = layoutplan.cat(seg_parts, np.int64)
    sid = layoutplan.cat(
        [np.full(len(r), s, dtype=np.int64) if np.isscalar(s) else s
         for r, s in zip(rel_parts, sid_parts)], np.int64)
    # series runs: sid change or chunk boundary (the same series split
    # across shards/chunks gets separate rows — a run is only required
    # to be internally constant-stride)
    boundary = np.zeros(n, dtype=np.bool_)
    boundary[0] = True
    boundary[1:] = sid[1:] != sid[:-1]
    off = 0
    for r, b in zip(rel_parts, bnd_parts):
        if b is not None and len(b):
            boundary[off + b] = True  # coalesced-add record breaks
        off += len(r)
        if off < n:
            boundary[off] = True
    d = np.diff(rel)
    inner = ~boundary[1:]
    dd = d[inner]
    if len(dd) and int(dd.min()) <= 0:
        return None  # duplicate/unsorted times within a run
    # dt = gcd(all within-run diffs, window) — every within-run diff is
    # then a positive multiple of dt and every run's times share one
    # residue class mod dt, so (window, (rel - w*every)//dt) is
    # injective per run: gaps and per-series phase shifts grid fine,
    # they just leave masked-off slots. All-singleton runs (one sample
    # per series) degenerate to k=1.
    dt = _stride_gcd(dd, every_ns) if len(dd) else every_ns
    if dt <= 0 or every_ns % dt:
        return None
    k = every_ns // dt
    if k > _MAX_K:
        return None
    run_starts = np.flatnonzero(boundary)
    S = len(run_starts)
    S_pad = _pad_rows(S, _MIN_S)
    W_pad = _pad_lanes(W, _MIN_W)
    mesh = GridBatch._mesh_for_rows(S_pad)
    if mesh is not None and S_pad % mesh.size:
        # multi-chip: pad the row axis to a mesh multiple up front so
        # the grid scatters straight into the shardable shape (no
        # second padding copy at device_put time) and the device-tier
        # signature shape is stable across cold/warm scans
        S_pad += mesh.size - S_pad % mesh.size
    cells = S_pad * k * W_pad  # padded = what actually allocates
    if cells > _MAX_GRID_CELLS or cells > max(_MAX_EXPANSION * n, 1 << 20):
        return None
    w = seg % W
    r = (rel - w * every_ns) // dt
    if (r < 0).any() or (r >= k).any():
        return None  # window grid misaligned with the stride grid
    rid = np.cumsum(boundary) - 1
    flat = (rid * k + r) * W_pad + w
    run_gid = (seg[run_starts] // W).astype(np.int64)
    order = np.argsort(run_gid, kind="stable")
    sg = run_gid[order]
    gb = np.empty(S, dtype=np.bool_)
    gb[0] = True
    gb[1:] = sg[1:] != sg[:-1]
    starts = np.flatnonzero(gb)
    return {
        "k": k, "S": S, "W_pad": W_pad, "shape": (S_pad, k, W_pad),
        "flat": flat, "n": n, "rel": rel, "mesh": mesh,
        "row_order": order,  # grid rows sorted by gid
        "gid_starts": starts,  # reduceat starts in row_order
        "gids_present": sg[starts],
        "rows_per_gid": np.diff(np.append(starts, S)),
    }


def _stride_gcd(dd: np.ndarray, every_ns: int) -> int:
    """gcd of every within-run time diff and the window length.
    np.gcd.reduce is per-element microcode (~200ns/elt — 4s on a 20M-row
    scan); constant-stride data (the common TSBS shape) exits via one
    vectorized modulo pass instead."""
    m = int(dd.min())
    if m <= 0:
        return 0
    if not (dd % m).any():  # every diff is a multiple of the smallest
        return int(np.gcd(m, every_ns))
    return int(np.gcd(np.gcd.reduce(np.unique(dd)), every_ns))


def _pow2_at_least(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=1)
def _lane_quantum() -> int:
    """Lane-axis padding quantum: 128 on TPU (the native lane tile —
    anything less re-pads on device), 8 on CPU/GPU backends where a
    128-wide floor at W=20 meant computing 6.4x the cells for nothing)."""
    import jax

    return 128 if jax.default_backend() == "tpu" else 8


def lane_quantum() -> int:
    """Public backend lane quantum — the PromQL tiled kernels pad their
    window (lane) axis with the same rule as the grid W axis."""
    return _lane_quantum()


def _pad_lanes(n: int, floor: int) -> int:
    """Pad the lane (W) axis to a multiple of the backend quantum
    instead of a power of two: at W=1667 that is 1792 rather than 2048
    on TPU (-12% cells). Shape count stays bounded for the compile
    cache: the fine non-TPU quantum applies only below 256 lanes
    (<= 32 small shapes), then 128-multiples to 2048, pow2 above."""
    q = _lane_quantum()
    if n <= floor:
        return floor
    if n <= 256:
        return (n + q - 1) // q * q
    if n <= 2048:
        return (n + 127) // 128 * 128
    return _pow2_at_least(n, 2048)


def _pad_rows(n: int, floor: int) -> int:
    """Pad the row (S) axis in 1.5x steps instead of 2x: the padded rows
    are pure zeros the kernels still reduce over."""
    p = floor
    while p < n:
        p = (p * 3 + 1) // 2
        p = (p + 7) // 8 * 8
    return p


def _kinds(agg_names, want_sel: bool) -> list[str]:
    """The grid kernels these aggregates read: `basic` always (every
    aggregate needs the counts), `ssd` for stddev, `selectors` per
    ragged.needs_selectors."""
    kinds = ["basic"]
    if "stddev" in agg_names:
        kinds.append("ssd")
    if ragged.needs_selectors(agg_names, want_sel):
        kinds.append("selectors")
    return kinds


# The (S_pad, k, W_pad) grid kernels, as traceable functions a launch
# group's program runs once a field (models/launch.py; compiled and
# counted per (fields, shape, dtype) as `grid_<kind>`).  'basic' = one
# fused pass for count/sum/mean/min/max; 'ssd' = two-pass squared
# deviations (the one-pass formula cancels catastrophically);
# 'selectors' = within-row argmin/argmax sample selection for
# min/max/first/last.


def _basic(v, m):
    # XLA, not the Pallas grid kernel (ops/pallas_segment.py): the plain
    # reduce is what GSPMD can row-shard under a device mesh (pallas_call
    # does not auto-partition).  Which of the two is faster on one chip:
    # not measured on the present code.
    from opengemini_tpu.ops import segment as seg

    return seg.grid_window_agg_t(v, m)


def _ssd(v, m):
    import jax.numpy as jnp

    zero = jnp.zeros((), v.dtype)
    vz = jnp.where(m, v, zero)
    cnt = m.sum(axis=1)
    mean = vz.sum(axis=1) / jnp.maximum(cnt, 1).astype(v.dtype)
    dev = jnp.where(m, v - mean[:, None, :], zero)
    return {"ssd": (dev * dev).sum(axis=1)}


def _selectors(v, m, imat):
    import jax.numpy as jnp

    big = jnp.array(jnp.inf, v.dtype)
    k = v.shape[1]
    # argmin/argmax tie -> lowest k index = earliest in-row timestamp
    r_min = jnp.argmin(jnp.where(m, v, big), axis=1)
    r_max = jnp.argmin(jnp.where(m, -v, big), axis=1)
    r_first = jnp.argmax(m, axis=1)
    r_last = (k - 1) - jnp.argmax(m[:, ::-1, :], axis=1)

    def take(mat, ridx):
        return jnp.take_along_axis(mat, ridx[:, None, :], axis=1)[:, 0, :]

    return {
        "sel_min": take(imat, r_min), "sel_max": take(imat, r_max),
        "sel_first": take(imat, r_first), "sel_last": take(imat, r_last),
        "first": take(v, r_first), "last": take(v, r_last),
    }


_KERNELS = {"basic": _basic, "ssd": _ssd, "selectors": _selectors}

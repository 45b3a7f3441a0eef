"""Incremental query result cache for GROUP BY time() aggregates.

The reference serves repeated dashboard queries from cached partials with
incremental append (engine/executor/inc_agg_transform.go,
inc_hash_agg_transform.go, lib/resultcache/). Here the unit of caching is
one window's columns: with GROUP BY time() the renderer never needs
selector row identities (output times are window starts), so a cached
window is the tuple of group keys it was computed over and, per
aggregate, one array of values in that aggregate's own dtype beside an
int64 array of counts — losslessly re-renderable under any
fill/limit/order, including fill(previous)/linear which the renderer
applies over the merged window sequence. The key tuple is one object
shared by every window a statement stores (and by the windows a re-asked
panel stores later, while its groups stay the same), so neither storing
nor reading a window builds a Python object a (group, window) cell.

Validity is tracked per window by the (path, data_version) signature of
every shard overlapping it (storage/shard.py data_version: bumped by
writes/deletes/rewrites, not by flush/compact). Appending new points
bumps only the owning shard, so a re-executed dashboard query recomputes
only the trailing (or otherwise touched) windows and re-reads nothing
else; an untouched query answers entirely from cache with no scan and no
device work.

Keys are a time-less statement fingerprint — db/rp/measurement, the
non-time WHERE trees, the window grid (every, offset), grouping, and the
ordered aggregate list — so the same dashboard panel re-queried over a
moving range keeps hitting the same entry (windows are keyed by absolute
start time).
"""

from __future__ import annotations

import json
import threading
from opengemini_tpu.utils import lockdep
from collections import OrderedDict

import numpy as np

from opengemini_tpu.utils.stats import GLOBAL as STATS

# bounds: fingerprints (distinct dashboard panels) and windows per panel
_MAX_QUERIES = 64
_MAX_WINDOWS = 16384


class IncrementalCache:
    def __init__(self, max_queries: int = _MAX_QUERIES,
                 max_windows: int = _MAX_WINDOWS):
        self._store: OrderedDict[str, dict] = OrderedDict()
        self._lock = lockdep.Lock()
        self.max_queries = max_queries
        self.max_windows = max_windows

    def lookup(self, fp: str) -> dict:
        """-> {window_start: (sig, keys, idx, values, counts)}: `keys` the
        tuple of group keys the window was computed over (one object
        shared between windows), `idx` the positions in it of the groups
        with data in this window (None: all of them), `values` one 1-D
        array an aggregate in its own dtype and `counts` one
        (aggregates, groups-with-data) int64 array, both aligned to
        `idx`; nothing in an entry is ever written again.
        Returns a shallow COPY — update() mutates/evicts the live entry
        concurrently and a plan must keep seeing the windows it
        validated."""
        with self._lock:
            got = self._store.get(fp)
            if got is None:
                return {}
            self._store.move_to_end(fp)
            return dict(got)

    def update(self, fp: str, windows: dict) -> None:
        """Merge freshly-computed windows into the fingerprint's entry."""
        evicted = 0     # windows trimmed + fingerprints dropped whole
        with self._lock:
            entry = self._store.get(fp)
            if entry is None:
                entry = self._store[fp] = {}
            entry.update(windows)
            self._store.move_to_end(fp)
            if len(entry) > self.max_windows:
                for ws in sorted(entry)[: len(entry) - self.max_windows]:
                    del entry[ws]
                    evicted += 1
            while len(self._store) > self.max_queries:
                self._store.popitem(last=False)
                evicted += 1
        if evicted:
            STATS.incr("executor", "inc_cache_evictions", evicted)

    def clear(self) -> None:
        with self._lock:
            self._store.clear()


def fingerprint(db, rp, mst, sc, group_time, group_tags, all_tags,
                agg_specs) -> str:
    from opengemini_tpu.sql import astjson

    return json.dumps(
        [
            db, rp or "", mst,
            astjson.to_json(sc.tag_expr),
            astjson.to_json(sc.field_expr),
            astjson.to_json(sc.mixed_expr),
            bool(sc.mixed_series_level),
            group_time.every_ns, group_time.offset_ns,
            list(group_tags), bool(all_tags),
            [[name, list(params), fname] for name, params, fname in agg_specs],
        ],
        separators=(",", ":"),
    )


def window_signature(shards, ws: int, we: int) -> tuple:
    """(path, data_version) of every shard overlapping [ws, we)."""
    return tuple(sorted(
        (sh.path, sh.data_version)
        for sh in shards
        if sh.tmax > ws and sh.tmin < we
    ))


def window_fresh(cached_sig, by_path: dict, ws: int, we: int) -> bool:
    """Is a cached window still valid? The shard SET must be unchanged and
    no shard may have a mutation newer than its cached version touching
    [ws, we) — sub-shard granularity via Shard.changed_since, so a write
    into one window leaves the rest of a 7d shard's windows cached."""
    cur = {sh.path for sh in by_path.values()
           if sh.tmax > ws and sh.tmin < we}
    if {p for p, _v in cached_sig} != cur:
        return False
    for p, v in cached_sig:
        if by_path[p].changed_since(v, ws, we):
            return False
    return True


class CachePlan:
    """Per-execution cache bookkeeping for the executor's aggregate path.

    Built after the scan context; tells the executor which window range
    must actually be scanned (the stale hull) and merges cached cells with
    the fresh compute before rendering.
    """

    def __init__(self, cache: IncrementalCache, fp: str, shards, aligned: int,
                 every_ns: int, W: int, n_aggs: int, tmin: int, tmax: int):
        self.cache = cache
        self.fp = fp
        self.aligned = aligned
        self.every = every_ns
        self.W = W
        self.n_aggs = n_aggs
        self.wstarts = [aligned + w * every_ns for w in range(W)]
        self.sigs = [
            window_signature(shards, ws, ws + every_ns) for ws in self.wstarts
        ]
        # PARTIAL windows — cut by the query's time bounds — cover only a
        # slice of their range: never cached, never served (a different
        # cutoff shares the same fingerprint and window key,
        # TestServer_Query_GroupByTimeCutoffs)
        self.partial = {
            w for w in range(W)
            if self.wstarts[w] < tmin or self.wstarts[w] + every_ns > tmax
        }
        held = cache.lookup(fp)
        self.cached = held
        by_path = {sh.path: sh for sh in shards}
        # why a window is recomputed: cut by the range, never cached (or
        # evicted), or touched (a mutation newer than the cached version
        # overlaps it, the shard set changed, or the mutation log was
        # truncated past it); asked = reused + cut + absent + touched
        cut = absent = touched = 0
        stale = []
        for w in range(W):
            got = held.get(self.wstarts[w])
            if w in self.partial:
                cut += 1
            elif got is None:
                absent += 1
            elif not window_fresh(got[0], by_path, self.wstarts[w],
                                  self.wstarts[w] + every_ns):
                touched += 1
            else:
                continue
            stale.append(w)
        self.stale = set(stale)
        STATS.add("executor", (
            ("inc_cache_windows_asked", W),
            ("inc_cache_windows_reused", W - len(stale)),
            ("inc_cache_windows_cut", cut),
            ("inc_cache_windows_absent", absent),
            ("inc_cache_windows_touched", touched),
            ("inc_cache_full_hits", int(not stale))))

    @property
    def scan_ranges(self):
        """Disjoint [lo, hi) scan ranges covering exactly the stale
        windows, or [] when everything is cached. Kept as runs (not one
        hull) so a now()-relative dashboard query — whose partial edge
        windows are always stale — still skips the cached middle."""
        if not self.stale:
            return []
        runs = []
        for w in sorted(self.stale):
            ws, we = self.wstarts[w], self.wstarts[w] + self.every
            if runs and runs[-1][1] == ws:
                runs[-1][1] = we
            else:
                runs.append([ws, we])
        return [tuple(r) for r in runs]

    def merge(self, agg_results, aggs, group_keys):
        """Overwrite cached windows into the computed arrays (extending
        group_keys with cache-only groups), then persist the freshly
        computed hull windows. agg_results maps id(call) -> (out, sel,
        counts, spec, fname, times_abs); with GROUP BY time the renderer
        consumes only (out, counts, spec, fname)."""
        W = self.W
        hull = self.stale
        reused = [(w, self.cached[self.wstarts[w]])
                  for w in range(W) if w not in hull]
        # group key -> gid once a distinct key tuple (by identity: the
        # windows of one statement share theirs), not once a window; a
        # cache-only group extends group_keys where a reused window has
        # data for it, in the order of the tuples met and of their keys
        # (both renderers sort the groups, so the order shows nowhere)
        keysets: dict[int, tuple] = {}
        for _w, (_sig, keys, idx, _vals, _cnts) in reused:
            ks = keysets.get(id(keys))
            if ks is None:
                ks = keysets[id(keys)] = (keys, np.zeros(len(keys), bool))
            ks[1][slice(None) if idx is None else idx] = True
        gid_of = {k: i for i, k in enumerate(group_keys)} if keysets else {}
        gids_of: dict[int, np.ndarray] = {}
        for keys, used in keysets.values():
            gids = np.fromiter((gid_of.get(k, -1) for k in keys),
                               np.int64, len(keys))
            for p in np.flatnonzero(used & (gids < 0)).tolist():
                gids[p] = gid_of[keys[p]] = len(group_keys)
                group_keys.append(keys[p])
            gids_of[id(keys)] = gids
        G = len(group_keys)

        outs2d, cnts2d = [], []
        for call, _s, _p, _f in aggs:
            out, _sel, counts, spec_, fname_, _times = agg_results[id(call)]
            out = np.asarray(out)
            new_out = np.zeros((G, W), dtype=out.dtype)
            new_cnt = np.zeros((G, W), dtype=np.int64)
            old_G = len(out) // W if W else 0
            if len(out):
                new_out[:old_G] = out.reshape(old_G, W)
                new_cnt[:old_G] = np.asarray(counts).reshape(old_G, W)
            outs2d.append(new_out)
            cnts2d.append(new_cnt)
            agg_results[id(call)] = (new_out.reshape(-1), None,
                                     new_cnt.reshape(-1), spec_, fname_, None)
        # one fancy-index assignment a (window, aggregate), each column
        # in the dtype it was computed in (an int-exact sum stays int64)
        for w, (_sig, keys, idx, vals, cnts) in reused:
            gids = gids_of[id(keys)]
            if idx is not None:
                gids = gids[idx]
            for ai in range(len(aggs)):
                outs2d[ai][gids, w] = vals[ai]
                cnts2d[ai][gids, w] = cnts[ai]

        # persist the recomputed windows (never the partial edge windows;
        # only groups with data — zero cells rebuild as zeros on read, so
        # sparse windows stay cheap at high group cardinality): one `has`
        # matrix a statement, then column copies a window
        store = [w for w in sorted(hull) if w not in self.partial]
        if not store:
            return group_keys
        # the statement's one key tuple, or the equal one its reused
        # windows hold already (a panel re-asked over a moving range)
        keys = tuple(group_keys)
        held = next((k for k, _u in keysets.values() if k == keys), None)
        if held is not None:
            keys = held
        cnt = np.stack([c[:, store].T for c in cnts2d], axis=1)  # (S, A, G)
        has = (cnt > 0).any(axis=1)                               # (S, G)
        whole = has.all(axis=1).tolist()
        fresh: dict[int, tuple] = {}
        for j, w in enumerate(store):
            if whole[j]:
                idx = None
                vals = tuple(o[:, w].copy() for o in outs2d)
                cnts = cnt[j].copy()
            else:
                idx = np.flatnonzero(has[j])
                vals = tuple(o[idx, w] for o in outs2d)
                cnts = cnt[j][:, idx]
            fresh[self.wstarts[w]] = (self.sigs[w], keys, idx, vals, cnts)
        self.cache.update(self.fp, fresh)
        # a window stored alone under a tuple built for it shares nothing
        shared = len(fresh) if held is not None or len(fresh) > 1 else 0
        STATS.add("executor", (("inc_cache_windows_stored", len(fresh)),
                               ("inc_cache_keysets_shared", shared)))
        return group_keys

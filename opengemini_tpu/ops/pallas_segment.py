"""Pallas TPU kernels for the aggregation hot loop.

The XLA paths in ``ops/segment.py`` / ``models/ragged.py`` express each
aggregate as separate masked reduces and rely on XLA fusion to keep the
batch in registers/VMEM. These Pallas kernels make that guarantee
explicit: one tile load from HBM into VMEM feeds EVERY statistic (count,
sum, mean, min, max, ssd — and for the selector variant the four
lexicographic (hi, lo) scans), so the batch crosses HBM exactly once per
kernel regardless of how many aggregates the query asked for.

This is the TPU replacement for the reference's generated per-(type, agg)
scalar reduce loops (engine/series_agg_func.gen.go:47 floatSumReduce and
the 45 sibling fns; series_agg_reducer.gen.go) — there the fusion is
hand-written per combination, here it is one kernel per *shape family*:

  - ``bucket_stats_basic``     — (G, W) dense bucket rows (models/ragged.py)
  - ``bucket_stats_selectors`` — same tiles, first/last/min/max row selection
  - ``grid_window_agg_t``      — (S, SPW, W) regular-grid window layout
                                 (ops/segment.grid_window_agg_t)

Routing (models/ragged.py): on a TPU the SELECTOR kernel serves unsharded
buckets — one tile residency feeds all four lexicographic scans — and
the pure reductions (basic/grid) stay with XLA's own fusion, which also
keeps GSPMD row sharding working under a device mesh (pallas_call does
not auto-partition).  The basic and grid kernels are retained, tested
and directly callable as the explicit-fusion alternates.  Speed of
either side: not measured on the present code.

All three are compiled by Mosaic and compared with their XLA twins on
the chip by tools/pallas_chip_check.py; tests/test_pallas.py asserts
the same equality in interpret mode on the CPU (same empty-segment
identities: count 0, sum 0, min +inf, max -inf, ssd 0).  Interpret mode
is for the CPU only: on a TPU a kernel Mosaic refuses raises.

Mask convention: callers pass bool masks; ``_as_i8`` widens to int8 at
the call boundary (TPU VMEM has no packed bool tiling) and kernels
compare ``!= 0``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

_BIG_I32 = 2**31 - 1


# -- routing -----------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def use_pallas() -> bool:
    """True when the Pallas kernels should serve the hot path: a TPU
    backend and not explicitly disabled. OGTPU_PALLAS=1 forces them on
    (interpret mode on the CPU is far slower than XLA — test-only), =0
    off."""
    flag = os.environ.get("OGTPU_PALLAS")
    if flag is not None:
        return flag.strip().lower() not in ("0", "false", "off", "no", "")
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode on the CPU only (tests, forced-on CPU runs); on an
    accelerator the kernels compile or raise."""
    return jax.default_backend() == "cpu"


def _as_i8(mask) -> jax.Array:
    return jnp.asarray(mask).astype(jnp.int8)


def _tile_g(g: int, w: int) -> int:
    """Rows-per-block: amortize per-grid-step overhead while bounding the
    VMEM footprint (~4 MB of input tiles per step at the cap). G is pow2
    >= 8 (models/ragged.py _pow2_at_least) so any pow2 tile divides it."""
    cap = max(512 * 256 // max(w, 128), 128)
    return min(g, cap)


# -- (G, W) bucket stats: basic ---------------------------------------------


def _basic_kernel(v_ref, m_ref, cnt_ref, sum_ref, mean_ref, min_ref, max_ref, ssd_ref):
    v = v_ref[...]
    m = m_ref[...] != 0
    zero = jnp.zeros((), v.dtype)
    big = jnp.array(jnp.inf, v.dtype)
    vz = jnp.where(m, v, zero)
    # explicit int32 result: under x64 the interpret-mode lowering widens
    # integer reduces to int64, which an int32 out ref rejects ("Invalid
    # dtype for swap") — the breakage devobs.backend_capabilities probes
    cnt = jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True).astype(jnp.int32)
    s = jnp.sum(vz, axis=1, keepdims=True)
    mean = s / jnp.maximum(cnt, 1).astype(v.dtype)
    dev = jnp.where(m, v - mean, zero)
    cnt_ref[...] = cnt
    sum_ref[...] = s
    mean_ref[...] = mean
    min_ref[...] = jnp.min(jnp.where(m, v, big), axis=1, keepdims=True)
    max_ref[...] = jnp.max(jnp.where(m, v, -big), axis=1, keepdims=True)
    ssd_ref[...] = jnp.sum(dev * dev, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bucket_basic_call(v, m_i8, *, interpret: bool):
    from jax.experimental import pallas as pl

    g, w = v.shape
    tg = _tile_g(g, w)
    if g % tg:  # trailing rows would be silently skipped by the floor grid
        raise ValueError(f"row count {g} must be a multiple of the tile {tg}")
    col = lambda dt: jax.ShapeDtypeStruct((g, 1), dt)  # noqa: E731
    in_spec = pl.BlockSpec((tg, w), lambda i: (i, 0))
    out_spec = pl.BlockSpec((tg, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        _basic_kernel,
        grid=(g // tg,),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec] * 6,
        out_shape=[
            col(jnp.int32), col(v.dtype), col(v.dtype),
            col(v.dtype), col(v.dtype), col(v.dtype),
        ],
        interpret=interpret,
    )(v, m_i8)
    names = ("count", "sum", "mean", "min", "max", "ssd")
    return {k: o[:, 0] for k, o in zip(names, outs)}


def bucket_stats_basic(v, hi, lo, idx, m):
    """Pallas twin of models/ragged._stats_fn('basic'): fused single-pass
    count/sum/mean/min/max/ssd over (G, W) bucket rows. hi/lo/idx are
    accepted (the selectors twin's signature) and unused."""
    return _bucket_basic_call(jnp.asarray(v), _as_i8(m), interpret=_interpret())


# -- (G, W) bucket stats: selectors ------------------------------------------


def _masked(vals, cand_i32, fill):
    """where(cand, vals, fill) in pure i32 arithmetic — Mosaic (the Pallas
    TPU compiler) rejects relayouts of combined i1 mask vectors
    ("non-singleton dimension replicated"), so candidate masks stay i32
    0/1 end-to-end and never materialize as vector<i1>."""
    return vals * cand_i32 + fill * (1 - cand_i32)


def _lex_col(hi, lo, cand, latest):
    """Column index of the lexicographically (hi, lo) extreme candidate per
    row; ties break by column order. Mirrors models/ragged._lex_col.
    ``cand`` is i32 0/1; returns i32 columns (big-valued rows = no
    candidate)."""
    big = _BIG_I32
    col = jax.lax.broadcasted_iota(jnp.int32, hi.shape, dimension=1)
    bcast = lambda x: jnp.broadcast_to(x, hi.shape)  # noqa: E731
    if latest:
        hi_ext = jnp.max(_masked(hi, cand, -big), axis=1, keepdims=True)
        c2 = cand * (hi == bcast(hi_ext)).astype(jnp.int32)
        lo_ext = jnp.max(_masked(lo, c2, -big), axis=1, keepdims=True)
        c3 = c2 * (lo == bcast(lo_ext)).astype(jnp.int32)
        return jnp.max(_masked(col, c3, -big), axis=1)
    hi_ext = jnp.min(_masked(hi, cand, big), axis=1, keepdims=True)
    c2 = cand * (hi == bcast(hi_ext)).astype(jnp.int32)
    lo_ext = jnp.min(_masked(lo, c2, big), axis=1, keepdims=True)
    c3 = c2 * (lo == bcast(lo_ext)).astype(jnp.int32)
    return jnp.min(_masked(col, c3, big), axis=1)


def _first_last_col(v, hi, lo, cand, latest):
    """first/last column pick: extreme (hi, lo) time, then exact-time ties
    take the LARGER VALUE (reference agg_func.go FirstReduce/LastReduce),
    then column order."""
    big = _BIG_I32
    col = jax.lax.broadcasted_iota(jnp.int32, hi.shape, dimension=1)
    bcast = lambda x: jnp.broadcast_to(x, hi.shape)  # noqa: E731
    if latest:
        hi_ext = jnp.max(_masked(hi, cand, -big), axis=1, keepdims=True)
        c2 = cand * (hi == bcast(hi_ext)).astype(jnp.int32)
        lo_ext = jnp.max(_masked(lo, c2, -big), axis=1, keepdims=True)
        c3 = c2 * (lo == bcast(lo_ext)).astype(jnp.int32)
    else:
        hi_ext = jnp.min(_masked(hi, cand, big), axis=1, keepdims=True)
        c2 = cand * (hi == bcast(hi_ext)).astype(jnp.int32)
        lo_ext = jnp.min(_masked(lo, c2, big), axis=1, keepdims=True)
        c3 = c2 * (lo == bcast(lo_ext)).astype(jnp.int32)
    fbig = jnp.array(jnp.inf, v.dtype)
    v_ext = jnp.max(jnp.where(c3 != 0, v, -fbig), axis=1, keepdims=True)
    c4 = c3 * (v == bcast(v_ext)).astype(jnp.int32)
    return jnp.min(_masked(col, c4, big), axis=1)


def _sel_kernel(v_ref, hi_ref, lo_ref, idx_ref, m_ref,
                first_ref, last_ref, sf_ref, sl_ref, smin_ref, smax_ref):
    v = v_ref[...]
    hi = hi_ref[...]
    lo = lo_ref[...]
    idx = idx_ref[...]
    m = m_ref[...] != 0  # direct load-compare i1 is fine; combining isn't
    m32 = m_ref[...].astype(jnp.int32)
    big = jnp.array(jnp.inf, v.dtype)
    mn = jnp.broadcast_to(
        jnp.min(jnp.where(m, v, big), axis=1, keepdims=True), v.shape
    )
    mx = jnp.broadcast_to(
        jnp.max(jnp.where(m, v, -big), axis=1, keepdims=True), v.shape
    )
    wlim = v.shape[1] - 1
    clip = lambda c: jnp.clip(c, 0, wlim)  # noqa: E731
    cf = clip(_first_last_col(v, hi, lo, m32, latest=False))
    cl = clip(_first_last_col(v, hi, lo, m32, latest=True))
    cmin = clip(_lex_col(hi, lo, m32 * (v == mn).astype(jnp.int32), latest=False))
    cmax = clip(_lex_col(hi, lo, m32 * (v == mx).astype(jnp.int32), latest=False))

    def take(mat, cols):
        # one-hot lane select: (TG, W) -> (TG, 1) without gather (TPU-
        # friendly; W <= 1024 so the one-hot mask is one VREG row set).
        # where (not multiply): a NaN value off-lane must not leak into
        # the sum; the fresh same-shape compare is a layout-safe i1.
        oh = jax.lax.broadcasted_iota(jnp.int32, mat.shape, 1) == jnp.broadcast_to(
            cols[:, None], mat.shape
        )
        # keep the reduce at the ref dtype: x64 interpret mode widens
        # integer sums to int64, which the int32 out refs reject
        return jnp.sum(jnp.where(oh, mat, jnp.zeros((), mat.dtype)),
                       axis=1, keepdims=True).astype(mat.dtype)

    first_ref[...] = take(v, cf)
    last_ref[...] = take(v, cl)
    sf_ref[...] = take(idx, cf)
    sl_ref[...] = take(idx, cl)
    smin_ref[...] = take(idx, cmin)
    smax_ref[...] = take(idx, cmax)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bucket_sel_call(v, hi, lo, idx, m_i8, *, interpret: bool):
    from jax.experimental import pallas as pl

    g, w = v.shape
    tg = _tile_g(g, w)
    if g % tg:  # trailing rows would be silently skipped by the floor grid
        raise ValueError(f"row count {g} must be a multiple of the tile {tg}")
    col = lambda dt: jax.ShapeDtypeStruct((g, 1), dt)  # noqa: E731
    in_spec = pl.BlockSpec((tg, w), lambda i: (i, 0))
    out_spec = pl.BlockSpec((tg, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        _sel_kernel,
        grid=(g // tg,),
        in_specs=[in_spec] * 5,
        out_specs=[out_spec] * 6,
        out_shape=[
            col(v.dtype), col(v.dtype), col(jnp.int32),
            col(jnp.int32), col(jnp.int32), col(jnp.int32),
        ],
        interpret=interpret,
    )(v, hi, lo, idx, m_i8)
    names = ("first", "last", "sel_first", "sel_last", "sel_min", "sel_max")
    return {k: o[:, 0] for k, o in zip(names, outs)}


def bucket_stats_selectors(v, hi, lo, idx, m):
    """Drop-in for models/ragged._stats_fn('selectors'): fused first/last
    values + first/last/min/max row-index selection in one tile pass."""
    return _bucket_sel_call(
        jnp.asarray(v), jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(idx),
        _as_i8(m), interpret=_interpret(),
    )


# -- (S, SPW, W) regular-grid window aggregation -----------------------------


def _grid_kernel(v_ref, m_ref, cnt_ref, sum_ref, mean_ref, min_ref, max_ref):
    v = v_ref[...]  # (TS, SPW, TW)
    m = m_ref[...] != 0
    zero = jnp.zeros((), v.dtype)
    big = jnp.array(jnp.inf, v.dtype)
    vz = jnp.where(m, v, zero)
    # int32 ref store under x64 interpret mode needs the explicit cast
    cnt = jnp.sum(m.astype(jnp.int32), axis=1).astype(jnp.int32)
    s = jnp.sum(vz, axis=1)
    cnt_ref[...] = cnt
    sum_ref[...] = s
    mean_ref[...] = s / jnp.maximum(cnt, 1).astype(v.dtype)
    min_ref[...] = jnp.min(jnp.where(m, v, big), axis=1)
    max_ref[...] = jnp.max(jnp.where(m, v, -big), axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grid_call(v_t, m_i8, *, interpret: bool):
    from jax.experimental import pallas as pl

    s_dim, spw, w = v_t.shape
    ts = 8 if s_dim % 8 == 0 else 1
    tw = 512 if w % 512 == 0 else w
    grid = (s_dim // ts, w // tw)
    in_spec = pl.BlockSpec((ts, spw, tw), lambda i, j: (i, 0, j))
    out_spec = pl.BlockSpec((ts, tw), lambda i, j: (i, j))
    mat = lambda dt: jax.ShapeDtypeStruct((s_dim, w), dt)  # noqa: E731
    outs = pl.pallas_call(
        _grid_kernel,
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec] * 5,
        out_shape=[
            mat(jnp.int32), mat(v_t.dtype), mat(v_t.dtype),
            mat(v_t.dtype), mat(v_t.dtype),
        ],
        interpret=interpret,
    )(v_t, m_i8)
    names = ("count", "sum", "mean", "min", "max")
    return dict(zip(names, outs))


def grid_window_agg_t(values_t, mask_t):
    """Pallas variant of ops/segment.grid_window_agg_t: same (S, SPW, W)
    windows-on-lanes layout, all five stats from one VMEM residency."""
    return _grid_call(jnp.asarray(values_t), _as_i8(mask_t), interpret=_interpret())

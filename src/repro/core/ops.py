"""Vectorized mining primitives the compiler lowers stages onto.

TPU adaptation of the paper's warp-cooperative kernels:

* ``lower_bound`` — branch-free fixed-iteration binary search, vectorized
  over arbitrary query shapes (the "early exit on temporal violation"
  becomes a closed-form rank difference).
* ``count_id_in_window`` — two-level search: locate the id run inside an
  id-sorted CSR row, then rank the time window inside that run (rows are
  sorted by (id, t), so the run is time-sorted).  This replaces the int64
  composite-key search with pure int32 ops (TPU-friendly).
* ``count_window`` — windowed degree on the time-sorted row copy.
* ``count_window_tiled`` — the same count through a tiled search of the
  whole time-sorted flat (:func:`repro.graph.csr.time_index`): one
  broadcast compare and one 128-lane row gather per level, no loop.
  Only the fused seed-local kernel uses it; its queries are ``(B,)``.
* ``expand`` — padded neighborhood materialization for ``for_all`` stages
  (the only primitive that materializes; intersections never do).

All primitives broadcast elementwise, so higher stage arity is just query
shape: seeds ``(B,)``, one expansion ``(B, D1)``, two ``(B, D1, D2)``.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "lower_bound",
    "count_t_in",
    "count_t_in_pos",
    "count_id_in_window",
    "count_id_in_window_pos",
    "count_window",
    "count_window_pos",
    "count_window_tiled",
    "tiled_lower_bound",
    "expand",
    "expand_pos",
    "dedup_ids",
    "n_iters_for",
]


def n_iters_for(max_len: int) -> int:
    return max(1, int(max_len).bit_length())


def lower_bound(flat, lo, hi, q, n_iters: int):
    """# of elements in flat[lo:hi) strictly less than q (elementwise)."""
    q = jnp.asarray(q)
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    shape = jnp.broadcast_shapes(q.shape, lo.shape, hi.shape)
    q = jnp.broadcast_to(q, shape)
    lo = jnp.broadcast_to(lo, shape)
    hi = jnp.broadcast_to(hi, shape)
    cap = flat.shape[0] - 1

    def body(_, carry):
        clo, chi = carry
        mid = (clo + chi) >> 1
        v = flat[jnp.clip(mid, 0, cap)]
        active = clo < chi
        less = v < q
        nlo = jnp.where(active & less, mid + 1, clo)
        nhi = jnp.where(active & ~less, mid, chi)
        return nlo, nhi

    lo_f, _ = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    return lo_f


def count_t_in(t_flat, start, end, after, until, n_iters: int):
    """# of times in t_flat[start:end) with  after < t <= until.

    Clamped at 0: callers clamp per-branch windows (e.g. the `ordered`
    intersect lowers to until=min(u, t2-1)), which can invert the window
    (until < after); the rank difference would then go negative by the
    number of edges inside the inverted range.
    """
    a = lower_bound(t_flat, start, end, jnp.asarray(after, jnp.int32) + 1, n_iters)
    b = lower_bound(t_flat, start, end, jnp.asarray(until, jnp.int32) + 1, n_iters)
    return jnp.maximum(b - a, 0)


def count_t_in_pos(t_flat, start, end, after, until, n_iters: int):
    """Like :func:`count_t_in`, but also returns the absolute flat rank of
    the first in-window element.  The j-th in-window element of the run
    (j < count) sits at flat position ``start_pos + j`` — counting
    primitives never materialize their runs, so this is all a witness
    extraction needs to address individual matched edges."""
    a = lower_bound(t_flat, start, end, jnp.asarray(after, jnp.int32) + 1, n_iters)
    b = lower_bound(t_flat, start, end, jnp.asarray(until, jnp.int32) + 1, n_iters)
    return jnp.maximum(b - a, 0), a


def count_id_in_window(
    nbr_flat,
    t_flat,
    indptr,
    node,
    x,
    after,
    until,
    n_iters: int,
):
    """Multiplicity of edges node->x (id-sorted row) with t in (after, until].

    Row layout is sorted by (id, t): the id run [lb, ub) found in level 1 is
    itself time-sorted, so level 2 ranks the window inside the run.
    Invalid nodes (node < 0) contribute 0.
    """
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe]
    end = indptr[safe + 1]
    x = jnp.asarray(x, jnp.int32)
    lb = lower_bound(nbr_flat, start, end, x, n_iters)
    ub = lower_bound(nbr_flat, start, end, x + 1, n_iters)
    cnt = count_t_in(t_flat, lb, ub, after, until, n_iters)
    return jnp.where((node >= 0) & (x >= 0), cnt, 0)


def count_id_in_window_pos(
    nbr_flat,
    t_flat,
    indptr,
    node,
    x,
    after,
    until,
    n_iters: int,
):
    """(count, run start) variant of :func:`count_id_in_window`: the id
    run [lb, ub) is time-sorted, so the j-th matched edge of the window
    sits at flat position ``start + j`` of the id-sorted row arrays."""
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe]
    end = indptr[safe + 1]
    x = jnp.asarray(x, jnp.int32)
    lb = lower_bound(nbr_flat, start, end, x, n_iters)
    ub = lower_bound(nbr_flat, start, end, x + 1, n_iters)
    cnt, pos = count_t_in_pos(t_flat, lb, ub, after, until, n_iters)
    return jnp.where((node >= 0) & (x >= 0), cnt, 0), pos


def count_window(t_sorted_flat, indptr, node, after, until, n_iters: int):
    """Windowed degree of `node` on the time-sorted row copy."""
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe]
    end = indptr[safe + 1]
    cnt = count_t_in(t_sorted_flat, start, end, after, until, n_iters)
    return jnp.where(node >= 0, cnt, 0)


def count_window_pos(t_sorted_flat, indptr, node, after, until, n_iters: int):
    """(count, run start) variant of :func:`count_window`: the j-th
    in-window edge sits at flat position ``start + j`` of the time-sorted
    row arrays."""
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe]
    end = indptr[safe + 1]
    cnt, pos = count_t_in_pos(t_sorted_flat, start, end, after, until, n_iters)
    return jnp.where(node >= 0, cnt, 0), pos


def tiled_lower_bound(index, start, end, q):
    """Lower bound of q in the time-sorted run flat[start:end), as an
    absolute flat position, through the tiled index of the whole flat.

    ``index`` is :func:`repro.graph.csr.time_index`'s ``(top, ...,
    leaf)``: level ``j`` from the leaf up samples every ``tile**j``-th
    element of the flat, in rows of ``tile``; ``top`` is the coarsest
    sample, 1-D.  The predicate ``p < start or (p < end and flat[p] <
    q)`` over flat positions ``p`` is monotone (true exactly below the
    run's lower bound), so its count of true positions IS the lower
    bound: the top samples are compared all at once, then each level
    gathers the one row of ``tile`` samples that holds the last true
    position and counts inside it.  Positions past ``end`` never count,
    so the index's padding is never read as data.  Dependent gather
    rounds: ``len(index) - 1``."""
    start, end, q = jnp.broadcast_arrays(
        jnp.asarray(start, jnp.int32),
        jnp.asarray(end, jnp.int32),
        jnp.asarray(q, jnp.int32),
    )
    start, end, q = start[..., None], end[..., None], q[..., None]

    def n_true(pos, vals):
        hit = (pos < start) | ((pos < end) & (vals < q))
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    top, rows = index[0], index[1:]
    tile = rows[-1].shape[1]
    stride = tile ** len(rows)
    lane = jnp.arange(tile, dtype=jnp.int32)
    lb = n_true(jnp.arange(top.shape[0], dtype=jnp.int32) * stride, top)
    for level in rows:  # coarse to fine: one row of `tile` samples each
        blk = jnp.maximum(lb - 1, 0)  # the block holding the last true p
        stride //= tile
        pos = (blk * (stride * tile))[..., None] + lane * stride
        lb = blk * tile + n_true(pos, level[blk])
    return lb


def count_window_tiled(index, indptr, node, after, until):
    """:func:`count_window` through :func:`tiled_lower_bound`: the same
    windowed degree (clamped at 0, 0 for ``node < 0``) on the index of
    the time-sorted row copy."""
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe]
    end = indptr[safe + 1]
    a = tiled_lower_bound(index, start, end, jnp.asarray(after, jnp.int32) + 1)
    b = tiled_lower_bound(index, start, end, jnp.asarray(until, jnp.int32) + 1)
    return jnp.where(node >= 0, jnp.maximum(b - a, 0), 0)


def dedup_ids(ids, ts, mask, invalid):
    """Keep one representative per id along the last axis (node-set dedup).

    Sorts masked-out slots to the end (as `invalid`), compares neighbors,
    and returns (ids, ts, mask) with duplicates masked off.  Filter the
    mask *before* calling so each id's surviving representative satisfies
    the window — union ``for_all`` frontiers lower onto this.
    """
    key = jnp.where(mask, ids, invalid)
    order = jnp.argsort(key, axis=-1)
    ids = jnp.take_along_axis(key, order, axis=-1)
    ts = jnp.take_along_axis(ts, order, axis=-1)
    prev = jnp.concatenate(
        [jnp.full_like(ids[..., :1], -1), ids[..., :-1]], axis=-1
    )
    mask = (ids != invalid) & (ids != prev)
    return ids, ts, mask


def expand(
    indptr,
    flats: Tuple,
    node,
    d: int,
    offset=0,
):
    """Materialize up to `d` row elements per node (padded).

    Returns (mask, gathered...) each of shape node.shape + (d,).  `offset`
    (broadcastable to node.shape) slides the window along the row — the
    hub-tail chunking path uses it to sweep rows longer than `d`.
    """
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe] + jnp.asarray(offset, jnp.int32)
    end = indptr[safe + 1]
    idx = start[..., None] + jnp.arange(d, dtype=jnp.int32)
    mask = (idx < end[..., None]) & (node >= 0)[..., None]
    cap = flats[0].shape[0] - 1
    cidx = jnp.clip(idx, 0, cap)
    outs = tuple(f[cidx] for f in flats)
    return (mask,) + outs


def expand_pos(
    indptr,
    flats: Tuple,
    node,
    d: int,
    offset=0,
):
    """:func:`expand` that also returns the (clipped) flat row positions
    of the gathered elements — witness extraction converts them to edge
    ids via the row-order eid arrays.  Positions at masked slots are
    clipped garbage; callers only read them where the mask holds."""
    node = jnp.asarray(node, jnp.int32)
    safe = jnp.maximum(node, 0)
    start = indptr[safe] + jnp.asarray(offset, jnp.int32)
    end = indptr[safe + 1]
    idx = start[..., None] + jnp.arange(d, dtype=jnp.int32)
    mask = (idx < end[..., None]) & (node >= 0)[..., None]
    cap = flats[0].shape[0] - 1
    cidx = jnp.clip(idx, 0, cap)
    outs = tuple(f[cidx] for f in flats)
    return (mask, cidx) + outs

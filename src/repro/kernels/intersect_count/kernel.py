"""Pallas TPU kernel: padded-tile weighted temporal intersection count.

TPU adaptation of BlazingAML's warp-cooperative sorted-set intersection:
instead of per-lane binary search (GPU) both padded neighbor tiles are
staged in VMEM and compared branch-free over the pair grid — pure VPU
work, no gathers, no data-dependent control flow.  This mirrors the
compiler's ``pw`` strategy (`repro.core.compiler`), which is what
low-degree buckets (the bulk of a power-law transaction graph) lower to.

Layout is lane-dense: kernel rows (one per query) run along the 128-wide
lane axis and the neighbor slots along sublanes, so every vector register
is full even when a bucket's widths are 4.  Inputs (``ops.py`` folds the
window predicates into the ids and transposes):

  x_ids (Dx, R) int32   tile side: one column per row   (Dx % 8 == 0)
  y_ids (Dy, R) int32   loop side                        (Dy % 8 == 0)
  x_t, y_t             edge times, passed only when ``order != 0``
Output:
  counts (1, R) int32 — # pairs (i, j) with x_ids[i, r] == y_ids[j, r]
  (and y_t[j, r] > x_t[i, r] for ``order=+1``, < for ``order=-1``).

Invalid slots carry distinct negative sentinels per side, so equality
alone decides a match.  Each grid step holds a ``(Dx, bn)`` tile and
walks the loop side eight sublanes at a time: a row of ``y`` broadcasts
down the sublanes of the ``x`` tile, and the hit tile folds into an
``(8, bn)`` accumulator with vreg adds (no cross-lane reduction until
the single final one).  No (bm, Da, Db) cube is ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["intersect_count_pallas"]

_SUB = 8  # sublanes per vreg


def _kernel(order: int, *refs):
    if order:
        x_ids, x_t, y_ids, y_t, out = refs
        xt = x_t[...]
    else:
        x_ids, y_ids, out = refs
    xi = x_ids[...]  # (Dx, bn)
    dx, bn = xi.shape

    def body(g, acc):
        r0 = pl.multiple_of(g * _SUB, _SUB)
        yi8 = y_ids[pl.ds(r0, _SUB), :]  # (8, bn)
        if order:
            yt8 = y_t[pl.ds(r0, _SUB), :]
        for r in range(_SUB):
            hit = xi == yi8[r : r + 1, :]
            if order > 0:
                hit = hit & (yt8[r : r + 1, :] > xt)
            elif order < 0:
                hit = hit & (yt8[r : r + 1, :] < xt)
            acc = acc + jnp.sum(
                hit.astype(jnp.int32).reshape(dx // _SUB, _SUB, bn), axis=0
            )
        return acc

    n_groups = y_ids.shape[0] // _SUB
    acc = jax.lax.fori_loop(
        0, n_groups, body, jnp.zeros((_SUB, bn), jnp.int32)
    )
    out[...] = jnp.sum(acc, axis=0, keepdims=True)


def intersect_count_pallas(
    x_ids,
    y_ids,
    x_t=None,
    y_t=None,
    *,
    order: int = 0,
    block_cols: int = 128,
    interpret: bool = True,
):
    dx, r = x_ids.shape
    dy = y_ids.shape[0]
    assert dx % _SUB == 0 and dy % _SUB == 0, "pad slot axes to 8"
    assert r % block_cols == 0, "pad rows to a multiple of block_cols"
    spec = lambda d: pl.BlockSpec((d, block_cols), lambda i: (0, i))
    if order:
        args = (x_ids, x_t, y_ids, y_t)
        in_specs = [spec(dx), spec(dx), spec(dy), spec(dy)]
    else:
        args = (x_ids, y_ids)
        in_specs = [spec(dx), spec(dy)]
    return pl.pallas_call(
        functools.partial(_kernel, order),
        grid=(r // block_cols,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, r), jnp.int32),
        interpret=interpret,
        name="intersect_count",
    )(*args)

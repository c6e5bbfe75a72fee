"""Jitted wrapper for the intersect_count Pallas kernel.

Takes the row-major query layout the compiler builds — ``(B, Da)`` /
``(B, Db)`` neighbor tiles plus per-row windows — folds each side's
window predicate into its ids (a slot outside its window becomes a
side-specific negative sentinel, so the kernel tests equality only),
puts the wider side on the kernel's tile and the narrower one on its
loop, pads both slot axes to 8 and the rows to the lane block, and
transposes to the kernel's lane-dense ``(D, rows)`` layout.  On TPU the
kernel compiles to a Mosaic custom call; on other backends it runs in
interpret mode (the correctness path).

Called under ``jit`` (the compiled mining path routes every
``pw``-strategy bucket through it when ``backend="pallas"``) the batch
and tile dims are static bucket-ladder widths, so :func:`_block_cols`
resolves the tiling at trace time and the fold/pad/transpose fuse into
the surrounding program.  Every trace bumps
``repro_kernel_traces_total{kernel="intersect_count",mode=...}`` so a
caller can check which lowering its mine took.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.intersect_count.kernel import intersect_count_pallas
from repro.obs import metrics as obs_metrics

__all__ = ["intersect_count"]

_LANES = 128
_SUB = 8
_TILE_ELEMS = 1 << 16  # (Dx, bn) elements compared per loop step
_MAX_COLS = 2048
# sentinels of invalid slots, distinct so no two ever match: out-of-window
# slots of side a / side b, then slot padding of the tile / loop side
_A_OFF, _B_OFF, _X_PAD, _Y_PAD = -1, -2, -3, -4


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _block_cols(dx: int, rows: int) -> int:
    """Rows (lanes) per grid step: about ``_TILE_ELEMS`` compares per
    step over a ``dx``-slot tile, a power-of-two multiple of 128, no
    wider than the padded row count needs."""
    bn = _LANES
    while bn < _MAX_COLS and 2 * bn * dx <= _TILE_ELEMS:
        bn *= 2
    while bn > _LANES and bn // 2 >= rows:
        bn //= 2
    return bn


def intersect_count(
    a_ids,
    a_t,
    b_ids,
    b_t,
    a_lo,
    a_hi,
    b_lo,
    b_hi,
    *,
    ordered: bool = False,
    interpret: bool | None = None,
):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    obs_metrics.get_registry().counter(
        "repro_kernel_traces_total",
        help="Pallas kernel traces, by lowering (mosaic | interpret)",
        labels={
            "kernel": "intersect_count",
            "mode": "interpret" if interpret else "mosaic",
        },
    ).inc()
    b, da = a_ids.shape
    db = b_ids.shape[1]
    a_ok = (a_ids >= 0) & (a_t > a_lo[:, None]) & (a_t <= a_hi[:, None])
    b_ok = (b_ids >= 0) & (b_t > b_lo[:, None]) & (b_t <= b_hi[:, None])
    a_ids = jnp.where(a_ok, a_ids, _A_OFF)
    b_ids = jnp.where(b_ok, b_ids, _B_OFF)
    # the wider side is the tile, the narrower one the loop; ordered
    # means b_t > a_t, i.e. loop_t > tile_t unless the sides swapped
    if da >= db:
        x_ids, x_t, y_ids, y_t, order = a_ids, a_t, b_ids, b_t, 1
    else:
        x_ids, x_t, y_ids, y_t, order = b_ids, b_t, a_ids, a_t, -1
    dx, dy = x_ids.shape[1], y_ids.shape[1]
    dxp, dyp = _round_up(dx, _SUB), _round_up(dy, _SUB)
    bn = _block_cols(dxp, _round_up(b, _LANES))
    rows = _round_up(b, bn)

    def lane_dense(a, d, pad):
        a = jnp.pad(
            a, ((0, rows - b), (0, d - a.shape[1])), constant_values=pad
        )
        return a.T

    args = [lane_dense(x_ids, dxp, _X_PAD), lane_dense(y_ids, dyp, _Y_PAD)]
    if ordered:
        args += [lane_dense(x_t, dxp, 0), lane_dense(y_t, dyp, 0)]
    out = intersect_count_pallas(
        *args,
        order=order if ordered else 0,
        block_cols=bn,
        interpret=interpret,
    )
    return out[0, :b]

"""Temporal CSR/CSC graph substrate.

The mining compiler (repro.core.compiler) consumes a :class:`TemporalGraph`,
which stores every adjacency row in TWO orders:

* id-sorted (``nbr`` ascending, ties by timestamp) — enables O(log d)
  binary-search set membership / weighted intersection, including temporal
  windows, via a composite ``key = nbr * (t_max+2) + (t+1)`` that is
  lexicographic in (nbr, t).  This is the TPU-adapted analogue of the
  paper's warp-cooperative sorted-set intersection.
* time-sorted (``t`` ascending) — turns the paper's "break on time-window
  overflow" early-exit into a closed-form ``searchsorted`` slice
  (fan/degree-in-window counting without data-dependent control flow).

Multi-edges (parallel transactions between the same account pair) are
first-class: duplicate neighbor ids are kept, so a binary-search range
``[lower_bound, upper_bound)`` *is* the edge multiplicity.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

__all__ = [
    "TemporalGraph",
    "DeviceGraph",
    "build_temporal_graph",
    "csr_row_offsets",
    "time_index",
    "time_index_shapes",
]

# Lane width: the tiled time search (repro.core.ops.tiled_lower_bound)
# probes one row of TILE samples per level.
TILE = 128
# The top level of a time index is compared whole, so it holds at most
# this many rows' worth of samples (4,096 at TILE; HI-Medium's 32M
# transfers need 1,950).
_TOP_ROWS = 32
_INT32_MAX = np.iinfo(np.int32).max


def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def csr_row_offsets(indptr: np.ndarray, nodes: np.ndarray):
    """Flat CSR positions of the adjacency rows of `nodes`, concatenated
    in node order, plus per-node row lengths (so callers can map entries
    back to their source node with ``np.repeat(..., lens)``)."""
    starts = indptr[nodes].astype(np.int64)
    lens = (indptr[nodes + 1] - indptr[nodes]).astype(np.int64)
    tot = int(lens.sum())
    first = np.repeat(np.cumsum(lens) - lens, lens)
    offs = np.repeat(starts, lens) + (np.arange(tot, dtype=np.int64) - first)
    return offs, lens


def _index_levels(n: int, tile: int) -> int:
    """Row levels of a time index over ``n`` elements: at least two (one
    search shape from a stream tick up to HI-Medium), more only where
    the top level would pass ``_TOP_ROWS`` rows."""
    levels = 2
    while -(-n // tile**levels) > _TOP_ROWS * tile:
        levels += 1
    return levels


def time_index_shapes(n: int, tile: int = TILE):
    """Shapes of :func:`time_index`'s arrays for a flat of ``n``."""
    levels = _index_levels(n, tile)
    n_pad = max(1, -(-n // tile**levels)) * tile**levels
    return [(n_pad // tile**levels,)] + [
        (n_pad // tile ** (j + 1), tile) for j in range(levels - 1, -1, -1)
    ]


def time_index(flat: np.ndarray, tile: int = TILE):
    """Tiled search index of a time-sorted CSR flat, coarse to fine: the
    1-D top sample, then each level's samples in rows of ``tile``, the
    last being the flat itself as ``(n_pad / tile, tile)``.  The flat is
    padded with INT32_MAX (above every time) to a multiple of the top
    stride; levels are strided slices of it, so nothing is searched on
    the host.  Kept 2-D so the kernel gathers whole lane rows."""
    shapes = time_index_shapes(len(flat), tile)
    leaf = np.full(shapes[-1][0] * tile, _INT32_MAX, np.int32)
    leaf[: len(flat)] = flat
    stride = leaf.size // shapes[0][0]
    out = [leaf[::stride]]
    for shape in shapes[1:]:
        stride //= tile
        out.append(np.ascontiguousarray(leaf[::stride]).reshape(shape))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class TemporalGraph:
    """Host-side (numpy) temporal multigraph in dual-order CSR/CSC form."""

    n_nodes: int
    n_edges: int
    # edge list in input (edge-id) order
    src: np.ndarray  # (E,) int32
    dst: np.ndarray  # (E,) int32
    t: np.ndarray  # (E,) int64
    amount: np.ndarray  # (E,) float32
    # out-CSR, id-sorted within row
    out_indptr: np.ndarray  # (N+1,) int64
    out_nbr: np.ndarray  # (E,) int32 — dst, sorted by (src, dst, t)
    out_key: np.ndarray  # (E,) int64 — composite (nbr, t) key
    out_t: np.ndarray  # (E,) int64
    out_eid: np.ndarray  # (E,) int32 — original edge id
    # out-CSR, time-sorted within row
    out_t_sorted: np.ndarray  # (E,) int64 — t sorted by (src, t)
    out_eid_t: np.ndarray  # (E,) int32
    # in-CSC, id-sorted within row
    in_indptr: np.ndarray
    in_nbr: np.ndarray  # src, sorted by (dst, src, t)
    in_key: np.ndarray
    in_t: np.ndarray
    in_eid: np.ndarray
    # in-CSC, time-sorted within row
    in_t_sorted: np.ndarray
    in_eid_t: np.ndarray
    # composite-key scale: key = nbr * key_scale + (t + 1); 0 reserved
    key_scale: int
    t_max: int

    # ---- degree helpers -------------------------------------------------
    @property
    def out_deg(self) -> np.ndarray:
        return np.diff(self.out_indptr).astype(np.int32)

    @property
    def in_deg(self) -> np.ndarray:
        return np.diff(self.in_indptr).astype(np.int32)

    def max_out_deg(self) -> int:
        return int(self.out_deg.max(initial=0))

    def max_in_deg(self) -> int:
        return int(self.in_deg.max(initial=0))

    def degree_stats(self) -> dict:
        od, idg = self.out_deg, self.in_deg
        return {
            "n_nodes": self.n_nodes,
            "n_edges": self.n_edges,
            "out_deg_mean": float(od.mean()) if od.size else 0.0,
            "out_deg_max": int(od.max(initial=0)),
            "out_deg_p99": float(np.percentile(od, 99)) if od.size else 0.0,
            "in_deg_mean": float(idg.mean()) if idg.size else 0.0,
            "in_deg_max": int(idg.max(initial=0)),
            "in_deg_p99": float(np.percentile(idg, 99)) if idg.size else 0.0,
        }

    def to_device(
        self,
        pad: bool = False,
        *,
        floor_nodes: int = 1,
        floor_edges: int = 1,
        floor_deg: int = 1,
    ) -> "DeviceGraph":
        """jnp mirror.  Device arrays are int32 (JAX x64 stays off): instead
        of the int64 composite key, compiled plans do a two-level int32
        binary search (id range, then time range within it).

        ``pad=True`` rounds every dimension that lands in a kernel trace
        key up to a power of two: edge-length arrays are padded (the tail
        is unreachable — binary searches and expansions only address CSR
        ranges below the real ``indptr`` values), ``indptr`` gains empty
        rows up to a pow2 node count, and the static ``max_deg`` is
        pow2-ceiled so the derived binary-search iteration count lands on
        a ladder.  A stream of per-tick graph views then presents
        logarithmically many distinct device shapes, and jitted mining
        kernels cached across ticks replay instead of re-tracing.

        ``floor_nodes``/``floor_edges``/``floor_deg`` (pad mode only) set
        lower bounds on the padded dimensions.  A streaming caller keeps
        monotone high-water floors across ticks so a mirror's static
        shapes — and the ``max_deg``-derived binary-search iteration
        count baked into every kernel trace — never shrink and reopen a
        trace family a later, bigger tick would have to remint.
        Oversizing is exact: padded CSR tails sit above every real
        ``indptr`` value and extra bisection iterations converge
        harmlessly.

        Each time-sorted flat also gets its :func:`time_index`, built from
        the flat as mirrored, so its shapes follow ``e_pad`` in pad mode."""
        import jax.numpy as jnp

        def pad_edges(a: np.ndarray, fill: int, e_pad: int) -> np.ndarray:
            if len(a) == e_pad:
                return a
            out = np.full(e_pad, fill, dtype=a.dtype)
            out[: len(a)] = a
            return out

        if pad:
            e_pad = _pow2ceil(max(1, int(floor_edges), self.n_edges))
            n_pad = _pow2ceil(max(1, int(floor_nodes), self.n_nodes))
            ep = lambda a, fill=-1: pad_edges(np.asarray(a), fill, e_pad)
            ip = lambda a: pad_edges(np.asarray(a), int(a[-1]), n_pad + 1)
            n_nodes, n_edges = n_pad, e_pad
            max_deg = _pow2ceil(
                max(1, int(floor_deg), self.max_out_deg(), self.max_in_deg())
            )
        else:
            ep = lambda a, fill=-1: a
            ip = lambda a: a
            n_nodes, n_edges = self.n_nodes, self.n_edges
            max_deg = max(1, self.max_out_deg(), self.max_in_deg())

        i32 = lambda a: jnp.asarray(a, dtype=jnp.int32)
        index = lambda a: tuple(jnp.asarray(x) for x in time_index(ep(a, 0)))
        return DeviceGraph(
            n_nodes=n_nodes,
            n_edges=n_edges,
            max_deg=max_deg,
            src=i32(ep(self.src)),
            dst=i32(ep(self.dst)),
            t=i32(ep(self.t, 0)),
            amount=jnp.asarray(ep(self.amount, 0)),
            out_indptr=i32(ip(self.out_indptr)),
            out_nbr=i32(ep(self.out_nbr)),
            out_t=i32(ep(self.out_t, 0)),
            out_eid=i32(ep(self.out_eid, -1)),
            out_t_sorted=i32(ep(self.out_t_sorted, 0)),
            out_eid_t=i32(ep(self.out_eid_t, -1)),
            in_indptr=i32(ip(self.in_indptr)),
            in_nbr=i32(ep(self.in_nbr)),
            in_t=i32(ep(self.in_t, 0)),
            in_eid=i32(ep(self.in_eid, -1)),
            in_t_sorted=i32(ep(self.in_t_sorted, 0)),
            in_eid_t=i32(ep(self.in_eid_t, -1)),
            out_t_index=index(self.out_t_sorted),
            in_t_index=index(self.in_t_sorted),
        )


@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """jnp mirror of TemporalGraph (fields used by compiled mining plans)."""

    n_nodes: int
    n_edges: int
    max_deg: int
    src: "object"
    dst: "object"
    t: "object"
    amount: "object"
    out_indptr: "object"
    out_nbr: "object"
    out_t: "object"
    out_eid: "object"
    out_t_sorted: "object"
    out_eid_t: "object"
    in_indptr: "object"
    in_nbr: "object"
    in_t: "object"
    in_eid: "object"
    in_t_sorted: "object"
    in_eid_t: "object"
    # time_index of out_t_sorted / in_t_sorted (fused seed-local kernel)
    out_t_index: tuple
    in_t_index: tuple

    def arrays(self) -> dict:
        """The 1-D device arrays by field name (the indexes left out)."""
        return {
            f.name: v
            for f in dataclasses.fields(self)
            if not isinstance(v := getattr(self, f.name), (int, tuple))
        }


def _register_devicegraph_pytree() -> None:
    import jax

    static = ("n_nodes", "n_edges", "max_deg")
    dyn = [f.name for f in dataclasses.fields(DeviceGraph) if f.name not in static]

    def flatten(g):
        return tuple(getattr(g, k) for k in dyn), tuple(getattr(g, k) for k in static)

    def unflatten(aux, children):
        kwargs = dict(zip(dyn, children))
        kwargs.update(dict(zip(static, aux)))
        return DeviceGraph(**kwargs)

    jax.tree_util.register_pytree_node(DeviceGraph, flatten, unflatten)


_register_devicegraph_pytree()


def _csr_from_edges(
    key_major: np.ndarray,
    minor: np.ndarray,
    t: np.ndarray,
    n_nodes: int,
    key_scale: int,
):
    """Build one CSR: rows keyed by key_major, id-sorted + time-sorted copies."""
    e = key_major.shape[0]
    eid = np.arange(e, dtype=np.int32)
    # id-sorted: (major, minor, t)
    order = np.lexsort((t, minor, key_major))
    nbr = minor[order].astype(np.int32)
    tt = t[order].astype(np.int64)
    keys = nbr.astype(np.int64) * key_scale + (tt + 1)
    # time-sorted: (major, t)
    torder = np.lexsort((t, key_major))
    t_sorted = t[torder].astype(np.int64)
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.add.at(indptr, key_major.astype(np.int64) + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, nbr, keys, tt, eid[order], t_sorted, eid[torder]


def build_temporal_graph(
    src: np.ndarray,
    dst: np.ndarray,
    t: np.ndarray,
    amount: Optional[np.ndarray] = None,
    n_nodes: Optional[int] = None,
) -> TemporalGraph:
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    t = np.asarray(t, dtype=np.int64)
    if t.size and t.min() < 0:
        raise ValueError("timestamps must be non-negative")
    if amount is None:
        amount = np.ones_like(src, dtype=np.float32)
    amount = np.asarray(amount, dtype=np.float32)
    e = src.shape[0]
    if n_nodes is None:
        n_nodes = int(max(src.max(initial=-1), dst.max(initial=-1))) + 1
    t_max = int(t.max(initial=0))
    key_scale = t_max + 2  # key = nbr*key_scale + (t+1); t+1 in [1, t_max+1]
    if n_nodes * key_scale >= 2**62:
        raise ValueError("composite key overflow; rescale timestamps")

    (o_indptr, o_nbr, o_key, o_t, o_eid, o_ts, o_eid_t) = _csr_from_edges(
        src, dst, t, n_nodes, key_scale
    )
    (i_indptr, i_nbr, i_key, i_t, i_eid, i_ts, i_eid_t) = _csr_from_edges(
        dst, src, t, n_nodes, key_scale
    )
    return TemporalGraph(
        n_nodes=n_nodes,
        n_edges=e,
        src=src,
        dst=dst,
        t=t,
        amount=amount,
        out_indptr=o_indptr,
        out_nbr=o_nbr,
        out_key=o_key,
        out_t=o_t,
        out_eid=o_eid,
        out_t_sorted=o_ts,
        out_eid_t=o_eid_t,
        in_indptr=i_indptr,
        in_nbr=i_nbr,
        in_key=i_key,
        in_t=i_t,
        in_eid=i_eid,
        in_t_sorted=i_ts,
        in_eid_t=i_eid_t,
        key_scale=key_scale,
        t_max=t_max,
    )

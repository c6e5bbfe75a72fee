# Copied from src/repro/graph/csr.py at commit 458a8805667351adad47ab46b7ecb5788edf5b20 so that the
# yardstick cannot move with the program: the host-side graph only
# (TemporalGraph without its device mirror, and build_temporal_graph).
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

__all__ = ["TemporalGraph", "build_temporal_graph"]


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

"""The parts of the comparison that decides ``correct`` which every
traffic kind shares: the reference's graph of the transfers, the
planted typology edges, the seeded sample, and the count comparison.
Each kind's ``check`` (``<bench>/traffic/<kind>.py``) says what it
compares; the reference (:mod:`chipbench.ref`) imports nothing of the
program and takes nothing it made.

``produce`` stands in for the reference on the program's side; the
control (``chipbench/control.py``) passes a reference with a broken
guarantee there.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

from chipbench.ref.csr import build_temporal_graph
from chipbench.ref.oracle import GFPReference
from chipbench.ref.patterns import build_pattern

__all__ = [
    "DEEP_KINDS",
    "Produce",
    "compare_counts",
    "planted_edges",
    "ref_graph",
    "reference",
    "sample",
]

DEEP_KINDS = ("cycle", "scatter_gather", "stack")

Produce = Callable[[str, object, np.ndarray], np.ndarray]


def planted_edges(data: dict) -> np.ndarray:
    """Edge ids of every planted instance whose kind only the deep
    patterns see."""
    eids = [d["eids"] for d in data["instances"] if d["kind"] in DEEP_KINDS]
    return np.unique(np.concatenate(eids)) if eids else np.zeros(0, np.int64)


def ref_graph(data: dict, order: Optional[np.ndarray] = None):
    """The reference's graph of the transfers, in ``order`` if given
    (edge id k is transfer ``order[k]``)."""
    cols = [data[k] for k in ("src", "dst", "t", "amount")]
    if order is not None:
        cols = [c[order] for c in cols]
    return build_temporal_graph(*cols, n_nodes=data["n_nodes"])


def sample(pool: np.ndarray, planted: np.ndarray, n: int, rng) -> np.ndarray:
    """Positions into ``pool``: its planted ids (at most half of ``n``,
    drawn) and a uniform draw for the rest."""
    pos_planted = np.nonzero(np.isin(pool, planted))[0]
    k = min(len(pos_planted), n // 2)
    pick = rng.choice(pos_planted, size=k, replace=False) if k else pos_planted[:0]
    rest = np.setdiff1d(np.arange(len(pool)), pick)
    extra = rng.choice(rest, size=min(len(rest), n - k), replace=False)
    return np.sort(np.concatenate([pick, extra]).astype(np.int64))


def reference(name: str, g, eids: np.ndarray, window: int) -> np.ndarray:
    """The reference's count of pattern ``name`` at each seed edge."""
    return GFPReference(build_pattern(name, window), g).mine(eids)


def compare_counts(names, g, eids, got: Dict[str, np.ndarray], window: int,
                   produce: Optional[Produce]) -> Tuple[int, Dict[str, int], Dict[str, np.ndarray]]:
    """Mismatched (seed, pattern) counts of ``got`` (or of ``produce``)
    against the reference on ``g``; per pattern how many of the seeds
    the reference matches; and the reference's counts."""
    bad = 0
    matched = {}
    want_all = {}
    for name in names:
        want = reference(name, g, eids, window)
        have = got[name] if produce is None else produce(name, g, eids)
        bad += int((np.asarray(have) != want).sum())
        matched[name] = int((want > 0).sum())
        want_all[name] = want
    return bad, matched, want_all

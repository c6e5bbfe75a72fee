"""Traffic kind ``batch``: nightly scoring through ``MiningSession.mine``
on a compiled session, closed loop with one mine in flight.

Plan: each mine takes ``seeds_per_mine`` transfers, the next block of a
seeded permutation of all transfers, sorted by time, so every mine
draws evenly from the whole graph.  Blocks wrap at the end of the
permutation; the block size does not divide the transfer count, so a
window long enough to pass the end mines the same transfers again in
other blocks.  ``warmup_mines`` blocks just before the start are mined
in set-up.  Optional keys: ``patterns`` (a subset of the
configuration's portfolio) and ``check_seeds``.

Set-up builds the program's graph from the deployment's transfers,
compiles the session and mines the warm-up blocks.  The window then
issues mines back to back while it is open; the mine in flight when it
closes runs to its end, and the window ends with it, so the rate is all
the seeds mined over all the time taken.  Of each mine's answers the
run keeps the rows the check may draw from (:func:`keep_rows`).

Check: the per-pattern counts of a sample of the seeds the window
mined, drawn from ``--seed``: every planted typology edge among them
(cycles, scatter-gather and stack instances; fans match every seed), up
to half the sample, and a uniform draw for the rest; exact, limit 0.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench.check import Produce, compare_counts, planted_edges, ref_graph, sample
from chipbench.generator import rng_for

__all__ = ["BatchPlan", "batch_plan", "check", "control", "keep_rows", "run"]


@dataclasses.dataclass
class BatchPlan:
    """Seed blocks: ``mine(i)`` for i >= 0 are the window's mines,
    ``warmup`` the blocks before the start."""

    order: np.ndarray  # edge ids in the order blocks are cut from
    t: np.ndarray  # every edge's time
    start: int
    per: int
    warmup: List[np.ndarray]

    def mine(self, i: int) -> np.ndarray:
        idx = (self.start + i * self.per + np.arange(self.per)) % len(self.order)
        ids = self.order[idx]
        return ids[np.argsort(self.t[ids], kind="stable")].astype(np.int32)


def batch_plan(mix: dict, t: np.ndarray, seed: int) -> BatchPlan:
    per = int(mix["seeds_per_mine"])
    if per > len(t):
        raise ValueError(f"seeds_per_mine {per} exceeds the {len(t)} transfers")
    rng = rng_for(seed, 1)
    order = rng.permutation(len(t))
    start = int(rng.integers(0, len(t)))
    plan = BatchPlan(order=order, t=t, start=start, per=per, warmup=[])
    plan.warmup = [plan.mine(-k) for k in range(int(mix.get("warmup_mines", 1)), 0, -1)]
    return plan


def keep_rows(mix: dict, data: dict, seed: int) -> Callable[[int, np.ndarray], np.ndarray]:
    """Rows of mine ``i`` a run keeps for the check: its planted edges and
    ``check_seeds`` positions drawn once from the seed (each mine's seeds
    are a different block), so the check's sample is drawn from every
    mine of the window without the run holding every answer, at a few
    microseconds a mine."""
    is_planted = np.zeros(len(data["t"]), dtype=bool)
    is_planted[planted_edges(data)] = True
    per = int(mix["seeds_per_mine"])
    n = min(int(mix.get("check_seeds", 256)), per)
    drawn = np.sort(rng_for(seed, 5).choice(per, size=n, replace=False))

    def keep(i: int, seeds: np.ndarray) -> np.ndarray:
        return np.union1d(np.nonzero(is_planted[seeds])[0], drawn[drawn < len(seeds)])

    return keep


def run(cfg: dict, mix: dict, data: dict, seed: int, seconds: float, recorder,
        clock_start: float, mine_hook: Optional[Callable] = None) -> dict:
    """Drive one batch run; returns the run's record.  ``mine_hook``, for
    tests, wraps the session's ``mine`` to plant a fault in the timed
    path."""
    from repro.api import MiningSession
    from repro.graph.csr import build_temporal_graph

    patterns = list(mix.get("patterns") or cfg["portfolio"])
    g = build_temporal_graph(
        data["src"], data["dst"], data["t"], data["amount"], n_nodes=data["n_nodes"]
    )
    session = MiningSession(g, window=int(cfg["window"]))
    session.register(*patterns).compile()
    mine = session.mine if mine_hook is None else mine_hook(session.mine)
    plan = batch_plan(mix, data["t"], seed)
    for seeds in plan.warmup:
        mine(seeds=seeds)
    setup_s = time.perf_counter() - clock_start

    keep = keep_rows(mix, data, seed)
    kept = []
    stats = {}
    n_seeds = 0
    with recorder.window():
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            seeds = plan.mine(i)
            res = mine(seeds=seeds)
            rows = keep(i, seeds)
            kept.append((seeds[rows], np.asarray(res.counts)[rows]))
            for k, v in res.stats.items():
                stats[k] = stats.get(k, 0) + int(v)
            n_seeds += len(seeds)
            i += 1
        window_s = time.perf_counter() - t0
    return {
        "mode": "batch",
        "patterns": patterns,
        "setup_s": setup_s,
        "window_s": window_s,
        "mines": i,
        "seeds": n_seeds,
        "stats": stats,
        "attempted": i,
        "failed": 0,
        "outputs": kept,
    }


def check(cfg: dict, mix: dict, data: dict, rec: dict, seed: int,
          produce: Optional[Produce] = None) -> Tuple[Dict[str, dict], List[str]]:
    t0 = time.perf_counter()
    window = int(cfg["window"])
    names = rec["patterns"]
    seeds = np.concatenate([s for s, _ in rec["outputs"]])
    counts = np.concatenate([c for _, c in rec["outputs"]])
    planted = planted_edges(data)
    pos = sample(seeds, planted, int(mix.get("check_seeds", 256)), rng_for(seed, 3))
    eids = seeds[pos]
    got = {n: counts[pos, j] for j, n in enumerate(names)}
    bad, matched, _ = compare_counts(names, ref_graph(data), eids, got, window, produce)
    info = [
        f"batch: {len(eids)} of {rec['seeds']} mined seeds compared x {len(names)} patterns, "
        f"{int(np.isin(eids, planted).sum())} of them planted",
        f"batch: sampled seeds with a match, per pattern {matched}",
        f"batch: reference took {time.perf_counter() - t0:.3f} s",
    ]
    return {"count_mismatches": {"value": bad, "limit": 0}}, info


def control(cfg: dict, mix: dict, data: dict, seed: int, produce: Produce,
            seconds: float, size: int) -> Tuple[Dict[str, dict], List[str]]:
    """The control scored on the seeds of the plan's first ``size`` mines,
    sampled as :func:`check` samples a run's."""
    patterns = list(mix.get("patterns") or cfg["portfolio"])
    plan = batch_plan(mix, data["t"], seed)
    keep = keep_rows(mix, data, seed)
    zero = np.zeros((plan.per, len(patterns)), dtype=np.int64)
    outputs = []
    for i in range(size):
        rows = keep(i, plan.mine(i))
        outputs.append((plan.mine(i)[rows], zero[rows]))
    rec = {"patterns": patterns, "outputs": outputs, "seeds": size * plan.per}
    return check(cfg, mix, data, rec, seed, produce=produce)

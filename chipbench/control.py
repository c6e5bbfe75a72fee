#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the copied
reference put in the program's place with one stated guarantee broken,
which the comparison has to catch.

The guarantee broken is ``exact``: no degree cap.  The control caps
every account's rows at ``--cap`` transfers (1,024 by default, the
widest bucket of the program's mining ladder), keeping each account's
earliest, as a mine that truncated hub rows would.  It is scored on the
same seeds, drawn the same way, as the check of a run of the cell that
did ``--size`` units of work draws them (each traffic kind's
``control``): for a batch cell the seeds of the plan's first ``--size``
mines, for a stream cell the history and the window's first ``--size``
events, ingested in order.  It needs no chip; the benchmark's own runs
never run it.

  python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --size <n> [--cap 1024]

Prints one JSON line per seed with the mismatches the comparison found.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.ref.csr import build_temporal_graph  # noqa: E402

__all__ = ["capped_graph", "control_counts", "main"]


def _ranks(indptr: np.ndarray, eid_t: np.ndarray) -> np.ndarray:
    """Each edge's position in its row, in time order."""
    rank = np.empty(len(eid_t), dtype=np.int64)
    lens = np.diff(indptr)
    rank[eid_t] = np.arange(len(eid_t)) - np.repeat(indptr[:-1], lens)
    return rank


def capped_graph(g, cap: int):
    """``g`` with every account's out-row and in-row cut to its first
    ``cap`` transfers in time; edge ids and the edge list are kept, so
    seeds still resolve."""
    keep = (_ranks(g.out_indptr, g.out_eid_t) < cap) & (_ranks(g.in_indptr, g.in_eid_t) < cap)
    kept = np.nonzero(keep)[0].astype(np.int32)
    sub = build_temporal_graph(g.src[kept], g.dst[kept], g.t[kept], g.amount[kept], n_nodes=g.n_nodes)
    return dataclasses.replace(
        sub,
        n_edges=g.n_edges,
        src=g.src,
        dst=g.dst,
        t=g.t,
        amount=g.amount,
        out_eid=kept[sub.out_eid],
        out_eid_t=kept[sub.out_eid_t],
        in_eid=kept[sub.in_eid],
        in_eid_t=kept[sub.in_eid_t],
    )


def control_counts(cap: int, window: int):
    """A ``produce`` for :mod:`chipbench.check`: the capped reference."""
    from chipbench.ref.oracle import GFPReference
    from chipbench.ref.patterns import build_pattern

    cache = {}

    def produce(name, g, eids):
        if id(g) not in cache:
            cache.clear()
            cache[id(g)] = capped_graph(g, cap)
        return GFPReference(build_pattern(name, window), cache[id(g)]).mine(eids)

    return produce


def run_control(root: str, cell: str, seed: int, cap: int, size: int,
                seconds: float) -> dict:
    from chipbench import harness

    bench = harness.Benchmark(root)
    wl = bench.workload(cell)
    cfg = bench.config(wl["config"])
    mix = bench.mix(wl["traffic"])
    t0 = time.perf_counter()
    data = harness.generate_data(cfg, seed)
    produce = control_counts(cap, int(cfg["window"]))
    checks, info = bench.kind(mix["mode"]).control(cfg, mix, data, seed, produce, seconds, size)
    return {"cell": cell, "seed": seed, "cap": cap, "checks": checks,
            "correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "info": info, "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cap", type=int, default=1024)
    ap.add_argument("--size", type=int, required=True,
                    help="work a run did: mines (batch) or window events ingested (stream)")
    ap.add_argument("--seconds", type=float, default=50.0, help="the run's window length")
    args = ap.parse_args(argv)
    for s in args.seeds.split(","):
        out = run_control(ROOT, args.workload, int(s), args.cap, args.size, args.seconds)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

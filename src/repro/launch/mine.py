"""Distributed mining launcher: degree-balanced edge partitions
dispatched across the device set (the paper's mining scaled across
parallel hardware).

Per-partition counts are independent (pattern counts are per-seed-edge),
so the only cross-device communication is the final gather of finished
per-shard counts — mining is embarrassingly data-parallel once the
partitioner has balanced expected cost (``graph/partition.py``).  The
default ``--backend sharded`` path runs the real multi-device executor
(:mod:`repro.core.shard`): every partition's chunk launches land on its
own device with a per-device resident accumulator and exactly one
blocking host sync per mine.  On the CPU platform the launcher
requests ``--devices`` virtual devices in-process via
``repro.launch.mesh.ensure_host_devices`` (the
``--xla_force_host_platform_device_count`` flag) before first jax
backend init; on an accelerator, asking for more devices than the host
has is an error.  ``--backend partitioned`` keeps the sequential
single-device loop for comparison.

Mining goes through a portfolio :class:`repro.api.MiningSession`, so
every partition reuses one compiled plan set (shared JIT cache, device
graph replicas, and requirement cache).

Usage:
  PYTHONPATH=src python -m repro.launch.mine --dataset HI-Small \
      --pattern scatter_gather --window 4096 --parts 4 --devices 4
"""
from __future__ import annotations

import argparse
import time

import numpy as np

__all__ = ["mine_partitioned"]


def mine_partitioned(
    graph, spec_name: str, window: int, n_parts: int, backend: str = "sharded"
):
    """Partition edges by cost, mine each partition, reassemble.

    ``backend="sharded"`` dispatches each partition to its own device
    (round-robin when ``n_parts`` exceeds the device count) and reports
    per-shard dispatch walls, devices, and the predicted-vs-achieved
    load balance; ``backend="partitioned"`` runs the partitions
    sequentially on one device and reports per-partition wall times.

    Returns ``(counts, plan, timing)`` where ``timing`` holds the
    per-partition/per-shard steady-state measurements plus the one-off
    warm-up (compile + first run) time.  The warm-up mine runs BEFORE
    the timed loop: without it the first partition's wall time absorbed
    the whole JIT compilation, corrupting the reported cost-skew metric.
    """
    from repro.api import MiningSession

    session = MiningSession(graph, window=window).register(spec_name)
    t0 = time.perf_counter()
    session.mine([spec_name])  # warm-up: compiles every bucket kernel
    warmup_s = time.perf_counter() - t0
    res = session.mine([spec_name], backend=backend, n_parts=n_parts)
    counts = np.asarray(res.column(spec_name), dtype=np.int64)
    if backend == "sharded":
        timing = {
            # per-shard walls run on CONCURRENT dispatch threads: they
            # overlap and do not sum to the mine wall — dispatch_wall_s
            # is the true window, overlap_ratio = sum(per_part)/window
            "per_part": res.per_shard_seconds,
            "dispatch_wall_s": res.dispatch_wall_s,
            "overlap_ratio": res.dispatch_overlap_ratio(),
            "gather_mode": res.gather_mode,
            "warmup_s": warmup_s,
            "devices": list(res.shard_devices),
            "balance": res.shard_balance(),
            "host_syncs": res.stats["host_syncs"],
        }
    else:
        timing = {"per_part": res.per_part_seconds, "warmup_s": warmup_s}
    return counts, res.partition_plan, timing


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="HI-Small")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--pattern", default="scatter_gather")
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument(
        "--backend", default="sharded", choices=("sharded", "partitioned")
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=0,
        help="virtual host devices to request (0 = --parts for sharded); "
        "must take effect before jax backend init",
    )
    args = ap.parse_args()

    # request virtual devices BEFORE anything initializes a jax backend
    # (dataset loading and session compilation both touch jax)
    if args.backend == "sharded":
        from repro.launch.mesh import ensure_host_devices

        want = args.devices or args.parts
        got = ensure_host_devices(want)  # raises on a short accelerator
        if got < want:
            print(f"# requested {want} CPU devices, got {got} (degrading)")

    from repro.launch.jax_cache import enable_compile_cache

    enable_compile_cache()

    from repro.core.patterns import PATTERN_NAMES
    from repro.data.synth_aml import load_dataset

    if args.pattern not in PATTERN_NAMES:
        ap.error(f"unknown pattern {args.pattern!r}; options: {PATTERN_NAMES}")

    ds = load_dataset(args.dataset, scale=args.scale)
    counts, plan, timing = mine_partitioned(
        ds.graph, args.pattern, args.window, args.parts, backend=args.backend
    )
    line = (
        f"{args.pattern} on {ds.name} [{args.backend}]: {counts.sum()} "
        f"instances over {ds.graph.n_edges} edges; partition cost skew "
        f"{plan.skew:.3f}; compile+warmup {timing['warmup_s']:.2f}s"
    )
    if args.backend == "sharded":
        # per-shard walls overlap on concurrent dispatch threads — report
        # the true window + overlap, never a per-part "sum"
        bal = timing["balance"]
        line += (
            f"; dispatch window {timing['dispatch_wall_s']:.2f}s "
            f"(overlap {timing['overlap_ratio']:.2f}x across "
            f"{len(timing['per_part'])} shards; per-shard walls "
            f"{[f'{t:.2f}s' for t in timing['per_part']]} are concurrent, "
            f"not additive); gather {timing['gather_mode']}; "
            f"devices {timing['devices']}; host_syncs {timing['host_syncs']}; "
            f"achieved kernel-call skew {bal['kernel_call_skew']:.3f} "
            f"(predicted {bal['predicted_cost_skew']:.3f})"
        )
    else:
        line += (
            f"; steady wall per part: "
            f"{[f'{t:.2f}s' for t in timing['per_part']]}"
        )
    print(line)


if __name__ == "__main__":
    main()

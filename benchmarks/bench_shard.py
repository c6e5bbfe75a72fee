"""Sharded mining: the multi-device executor's scaling curve + load
balance (the paper's near-linear-scaling claim, exercised over virtual
host devices).

For each shard count in ``--parts-list`` (default 1/2/4/8) the bench
runs ``session.mine(backend="sharded", n_parts=P)`` over the whole
library pattern portfolio and records steady-state wall time, per-shard
dispatch walls, per-shard executor counters, and the achieved
kernel-call / padded-element skew next to the partitioner's predicted
cost skew.  Hard asserts (CI smoke runs these at tiny scale):

* sharded counts are **bit-exact** vs ``backend="compiled"`` for every
  library pattern at every shard count;
* ``stats["host_syncs"] == 1`` per sharded mine (the single final
  gather — host-side or device-collective — per-device accumulators
  never sync early);
* achieved kernel-call balance stays within the partitioner's predicted
  cost skew (plus slack for bucket-granularity rounding);
* with ``--monotone-slack`` set, the speedup curve is monotone
  nondecreasing in shard count (up to the given relative slack) —
  the regression guard for the pre-overlap executor, whose curve
  COLLAPSED past 2 shards (0.76x at 8; see ``pre_overlap_baseline``
  embedded in the report).  Steps past the host's core count are
  reported but not asserted: with shards time-sharing cores every
  extra shard is pure overhead and the decline is physics, not a
  regression (on this repo's 1-CPU container even the pre-overlap
  executor's curve falls the same way).

Per shard count the report also records ``dispatch_wall_s`` (the true
overlapped dispatch window) and ``dispatch_overlap_ratio`` (sum of
per-shard dispatch walls / window: 1.0 = fully serialized dispatch,
``n_shards`` = perfect overlap), plus ``gather_mode`` — collective when
partitions map 1:1 onto devices, host fallback otherwise.  ``host_cpus``
pins the curve to the machine: on a single-core container threads
time-share one CPU and real speedup is physically capped regardless of
dispatch overlap.

Run standalone it requests ``--devices`` (default 8) devices BEFORE jax
backend init: virtual ones on CPU, while on an accelerator a host with
fewer chips is an error (pass ``--devices`` to match it).  Under
``benchmarks/run.py`` on CPU it is spawned as a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for the same
reason; on a chip ``run.py`` calls :func:`run` in its own process, which
already holds the chips.  With fewer devices than shards the executor
round-robins (the curve flattens but every assert still holds).

By default each curve point runs in its OWN subprocess
(``--no-isolate-points`` to disable): XLA's LLVM JIT pins ~dozens of
memory mappings per compiled executable and executables specialize per
device, so one process accumulating every point's kernels x devices
walks into ``vm.max_map_count`` (LLVM "Cannot allocate memory" at the
8-shard point at full scale under the default 65530 limit).  Isolation
also makes points comparable: each measures its own in-process compiled
baseline instead of inheriting the previous point's warmed JIT state.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_shard
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

ROOT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_shard.json")
# headroom over the predicted cost skew: bucket-granularity rounding means
# kernel calls track cost only to within a ladder class
SKEW_SLACK = 1.0

# speedup_vs_compiled of the PRE-overlap sequential-dispatch executor
# (host-side gather, one Python thread building and dispatching every
# shard in turn), measured at the default full-scale settings on a
# multi-core host before this change landed.  Embedded so the dispatch
# rework's win stays visible PR-over-PR in BENCH_shard.json.
PRE_OVERLAP_BASELINE = {"1": 0.9895, "2": 1.794, "4": 1.3586, "8": 0.7607}


def run(
    dataset="HI-Small",
    scale=0.5,
    window=4096,
    n_seeds=4000,
    parts_list=(1, 2, 4, 8),
    out_path=ROOT_OUT,
    monotone_slack=None,
):
    import jax

    from benchmarks.common import emit
    from repro.api import MiningSession
    from repro.core.patterns import PATTERN_NAMES

    from repro.data.synth_aml import load_dataset

    devices = jax.devices()
    ds = load_dataset(dataset, scale=scale)
    g = ds.graph
    rng = np.random.default_rng(0)
    seeds = rng.choice(
        g.n_edges, size=min(n_seeds, g.n_edges), replace=False
    ).astype(np.int32)
    names = list(PATTERN_NAMES)

    session = MiningSession(g, window=window).register(*names)
    session.mine(names, seeds=seeds)  # compile + warm-up
    t0 = time.perf_counter()
    base = session.mine(names, seeds=seeds)
    base_s = time.perf_counter() - t0

    report = {
        "dataset": ds.name,
        "scale": scale,
        "window": window,
        "n_seeds": int(len(seeds)),
        "n_devices": len(devices),
        # virtual devices time-share the physical cores: on host_cpus=1
        # the dispatch overlap is real but wall-clock speedup is capped
        "host_cpus": len(os.sched_getaffinity(0)),
        "patterns": names,
        "compiled_wall_s": base_s,
        "pre_overlap_baseline": dict(PRE_OVERLAP_BASELINE),
        "shards": {},
    }
    for n_parts in parts_list:
        session.mine(names, seeds=seeds, backend="sharded", n_parts=n_parts)
        t0 = time.perf_counter()
        res = session.mine(
            names, seeds=seeds, backend="sharded", n_parts=n_parts
        )
        wall = time.perf_counter() - t0
        assert np.array_equal(res.counts, base.counts), (
            f"sharded n_parts={n_parts} diverged from compiled counts"
        )
        assert res.stats["host_syncs"] == 1, (
            f"sharded mine must sync exactly once, saw "
            f"{res.stats['host_syncs']}"
        )
        bal = res.shard_balance()
        n_used = len(set(res.shard_devices))
        if n_parts > 1:
            assert bal["kernel_call_skew"] <= (
                bal["predicted_cost_skew"] + SKEW_SLACK
            ), f"kernel-call balance blew past the predicted skew: {bal}"
        report["shards"][str(n_parts)] = {
            "wall_s": wall,
            "speedup_vs_compiled": base_s / wall if wall > 0 else float("inf"),
            "devices_used": n_used,
            "shard_devices": list(res.shard_devices),
            "per_shard_dispatch_s": res.per_shard_seconds,
            "dispatch_wall_s": res.dispatch_wall_s,
            "dispatch_overlap_ratio": res.dispatch_overlap_ratio(),
            "gather_mode": res.gather_mode,
            "per_shard_kernel_calls": [
                s["kernel_calls"] for s in res.shard_stats
            ],
            "per_shard_padded_elements": [
                s["padded_elements"] for s in res.shard_stats
            ],
            "balance": bal,
            "host_syncs": res.stats["host_syncs"],
            "counts_match_compiled": True,
            **{k: res.stats[k] for k in ("kernel_calls", "padded_elements",
                                         "bytes_h2d", "bytes_d2h")},
        }
        emit(
            f"shard/parts{n_parts}",
            wall / max(1, len(seeds)) * 1e6,
            f"wall_s={wall:.3f};devices={n_used};"
            f"speedup_vs_compiled={base_s / max(wall, 1e-9):.2f}x;"
            f"overlap={res.dispatch_overlap_ratio():.2f}x;"
            f"gather={res.gather_mode};"
            f"kernel_call_skew={bal['kernel_call_skew']:.3f};"
            f"predicted_skew={bal['predicted_cost_skew']:.3f};"
            f"host_syncs={res.stats['host_syncs']};exact=True",
        )
    if monotone_slack is not None:
        # the 0.76x-at-8-shards regression guard: the speedup curve must
        # be monotone nondecreasing in shard count (relative slack covers
        # timer noise at smoke scale).  Only steps that stay within the
        # host's core budget are asserted: once shard count exceeds
        # host_cpus the virtual devices time-share cores and every extra
        # shard is pure dispatch overhead — the curve declines on ANY
        # executor (the pre-overlap one included), so a decline there
        # carries no regression signal.  Skipped steps are printed, never
        # silently dropped.
        host_cpus = report["host_cpus"]
        curve = [
            (p, report["shards"][str(p)]["speedup_vs_compiled"])
            for p in parts_list
        ]
        for (p0, s0), (p1, s1) in zip(curve, curve[1:]):
            if p0 >= host_cpus:
                print(
                    f"# monotone step {p0}->{p1} skipped: {p0} shards "
                    f"already saturate host_cpus={host_cpus}"
                )
                continue
            assert s1 >= s0 * (1.0 - monotone_slack), (
                f"scaling curve regressed: speedup fell from {s0:.3f}x at "
                f"{p0} shards to {s1:.3f}x at {p1} shards "
                f"(slack {monotone_slack}, host_cpus {host_cpus}); "
                f"full curve: {[(p, round(s, 3)) for p, s in curve]}"
            )
    out_path = os.path.abspath(out_path)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}")
    return report


def _run_isolated(args, parts_list):
    """One subprocess per curve point (fresh XLA JIT state each), merged
    into a single report with the monotone guard applied at the end.

    The parent never initializes a jax backend (a chip belongs to one
    process at a time, and each child needs it).  Each child is this
    module with a single-element ``--parts-list`` and
    ``--no-isolate-points``; its emit lines are passed through (header
    dropped) and its report's shard entry is merged.  The per-point
    speedup is the child's own in-process compiled-vs-sharded ratio.
    """
    import subprocess
    import sys
    import tempfile

    merged = None
    walls = {}
    for n_parts in parts_list:
        with tempfile.NamedTemporaryFile(
            suffix=f".parts{n_parts}.json", delete=False
        ) as tf:
            child_out = tf.name
        cmd = [
            sys.executable, "-m", "benchmarks.bench_shard",
            "--dataset", args.dataset,
            "--scale", str(args.scale),
            "--window", str(args.window),
            "--seeds", str(args.seeds),
            "--parts-list", str(n_parts),
            "--devices", str(args.devices),
            "--out", child_out,
            "--no-isolate-points",
        ]
        if getattr(args, "trace_dir", None):
            # one trace per isolated curve point (its own process owns
            # the devices and the spans)
            cmd += ["--trace-dir", args.trace_dir]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        for line in proc.stdout.splitlines():
            if line.startswith("shard/") or line.startswith("# "):
                print(line)
        if proc.returncode != 0:
            raise RuntimeError(
                f"isolated point n_parts={n_parts} failed "
                f"(exit {proc.returncode}):\n{proc.stdout}\n{proc.stderr}"
            )
        with open(child_out) as f:
            point = json.load(f)
        os.unlink(child_out)
        walls[str(n_parts)] = point["compiled_wall_s"]
        if merged is None:
            merged = point
        else:
            merged["shards"].update(point["shards"])
    merged["isolated_points"] = True
    # per-point in-process baselines (speedups already use these); the
    # top-level compiled_wall_s is their median
    merged["compiled_wall_s_per_point"] = walls
    merged["compiled_wall_s"] = float(np.median(list(walls.values())))
    if args.monotone_slack is not None:
        host_cpus = merged["host_cpus"]
        curve = [
            (p, merged["shards"][str(p)]["speedup_vs_compiled"])
            for p in parts_list
        ]
        for (p0, s0), (p1, s1) in zip(curve, curve[1:]):
            if p0 >= host_cpus:
                print(
                    f"# monotone step {p0}->{p1} skipped: {p0} shards "
                    f"already saturate host_cpus={host_cpus}"
                )
                continue
            assert s1 >= s0 * (1.0 - args.monotone_slack), (
                f"scaling curve regressed: speedup fell from {s0:.3f}x at "
                f"{p0} shards to {s1:.3f}x at {p1} shards "
                f"(slack {args.monotone_slack}, host_cpus {host_cpus}); "
                f"full curve: {[(p, round(s, 3)) for p, s in curve]}"
            )
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=2)
    print(f"# wrote {out_path}")
    return merged


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="HI-Small")
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--seeds", type=int, default=4000)
    ap.add_argument("--parts-list", default="1,2,4,8")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default=ROOT_OUT)
    ap.add_argument(
        "--monotone-slack",
        type=float,
        default=None,
        help="assert speedup[i+1] >= speedup[i] * (1 - slack) across the "
        "parts list (omit to skip the scaling-curve assert)",
    )
    ap.add_argument(
        "--no-isolate-points",
        dest="isolate_points",
        action="store_false",
        help="run every curve point in THIS process instead of one "
        "subprocess per point (risks vm.max_map_count exhaustion from "
        "accumulated per-device JIT executables at large scale)",
    )
    ap.add_argument(
        "--trace-dir",
        default=None,
        help="capture a repro.obs Chrome trace (per-shard dispatch "
        "spans) + metrics snapshot of the bench run",
    )
    args = ap.parse_args()
    parts_list = tuple(int(p) for p in args.parts_list.split(","))

    if args.isolate_points and len(parts_list) > 1:
        print("name,us_per_call,derived")
        _run_isolated(args, parts_list)
        return

    # request virtual devices BEFORE anything initializes a jax backend
    from repro.launch.mesh import ensure_host_devices

    got = ensure_host_devices(args.devices)  # raises on a short accelerator
    if got < args.devices:
        print(f"# requested {args.devices} CPU devices, got {got} (degrading)")

    print("name,us_per_call,derived")
    from benchmarks.common import traced

    trace_name = (
        f"shard_parts{parts_list[0]}" if len(parts_list) == 1 else "shard"
    )
    with traced(args.trace_dir, trace_name):
        run(
            dataset=args.dataset,
            scale=args.scale,
            window=args.window,
            n_seeds=args.seeds,
            parts_list=parts_list,
            out_path=args.out,
            monotone_slack=args.monotone_slack,
        )


if __name__ == "__main__":
    main()

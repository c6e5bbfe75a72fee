"""Benchmark driver — one function per paper table/figure.

Prints `name,us_per_call,derived` CSV rows.  Full sweep:

  PYTHONPATH=src python -m benchmarks.run            # all benches
  PYTHONPATH=src python -m benchmarks.run --only mining,f1
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
import traceback


def _run_shard(trace_dir=None) -> None:
    """On an accelerator this process already holds the chips (a chip
    belongs to one process at a time), so bench_shard runs in-process
    over them.  On CPU it needs --xla_force_host_platform_device_count
    before jax backend init; by the time the suite reaches it this
    process has long been initialized with one CPU device, so the shard
    bench runs in a subprocess with the flag in its environment."""
    import jax

    from benchmarks import bench_shard
    from benchmarks.common import traced

    if jax.default_backend() != "cpu":
        with traced(trace_dir, "shard"):
            bench_shard.run(out_path=bench_shard.ROOT_OUT)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    cmd = [sys.executable, "-m", "benchmarks.bench_shard",
           "--out", os.path.abspath(bench_shard.ROOT_OUT)]
    if trace_dir:
        # the trace must come from the subprocess that owns the devices
        cmd += ["--trace-dir", os.path.abspath(trace_dir)]
    subprocess.run(cmd, check=True, env=env)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default="kernels,mining,portfolio,streaming,resilience,shard,witness,"
        "scaling,f1,fraudgt,roofline",
        help="comma list: kernels,mining,portfolio,streaming,resilience,"
        "shard,witness,scaling,f1,fraudgt,roofline",
    )
    ap.add_argument(
        "--trace-dir",
        default=None,
        help="enable repro.obs tracing and write one Chrome trace JSON "
        "(Perfetto-loadable) + metrics snapshot per bench job",
    )
    args = ap.parse_args()
    only = set(args.only.split(","))

    from repro.launch.jax_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    jobs = []
    if "kernels" in only:
        from benchmarks import bench_kernels

        jobs.append(("kernels", bench_kernels.run))
    if "mining" in only:
        from benchmarks import bench_mining

        # the mining bench is the perf trajectory: always emit its
        # BENCH_mining.json (counters + baseline deltas) at the repo root
        jobs.append(
            ("mining", lambda: bench_mining.run(out_path=bench_mining.ROOT_OUT))
        )
    if "portfolio" in only:
        from benchmarks import bench_portfolio

        jobs.append(("portfolio", bench_portfolio.run))
    if "streaming" in only:
        from benchmarks import bench_streaming

        # the streaming bench is the locality trajectory: always emit its
        # BENCH_streaming.json (dirty fractions + maintenance + exactness)
        # at the repo root
        jobs.append(
            (
                "streaming",
                lambda: bench_streaming.run(out_path=bench_streaming.ROOT_OUT),
            )
        )
    if "resilience" in only:
        from benchmarks import bench_resilience

        # the resilience bench is the fault-tolerance trajectory: always
        # emit its BENCH_resilience.json (WAL/validation overhead on tick
        # p50/p99, recovery wall, post-recovery exactness asserts) at the
        # repo root
        jobs.append(
            (
                "resilience",
                lambda: bench_resilience.run(
                    out_path=bench_resilience.ROOT_OUT
                ),
            )
        )
    if "shard" in only:
        # the shard bench is the multi-device scaling trajectory: always
        # emit its BENCH_shard.json (scaling curve + balance + exactness)
        # at the repo root
        jobs.append(
            ("shard", lambda: _run_shard(args.trace_dir))
        )
    if "witness" in only:
        from benchmarks import bench_witness

        # the witness bench is the evidence trajectory: always emit its
        # BENCH_witness.json (overhead vs count-only, top-k scaling,
        # triage throughput, oracle-exactness asserts) at the repo root
        jobs.append(
            ("witness", lambda: bench_witness.run(out_path=bench_witness.ROOT_OUT))
        )
    if "scaling" in only:
        from benchmarks import bench_scaling

        jobs.append(("scaling", bench_scaling.run))
    if "f1" in only:
        from benchmarks import bench_f1_features

        jobs.append(("f1", bench_f1_features.run))
    if "fraudgt" in only:
        from benchmarks import bench_fraudgt

        jobs.append(("fraudgt", bench_fraudgt.run))
    if "roofline" in only:
        from benchmarks import bench_roofline

        jobs.append(("roofline", bench_roofline.run))

    from benchmarks.common import traced

    failures = []
    for name, fn in jobs:
        try:
            # the shard job traces where its devices live (a CPU
            # subprocess, or this process on a chip)
            with traced(None if name == "shard" else args.trace_dir, name):
                fn()
        except Exception as e:  # keep the suite going, report at the end
            failures.append((name, e))
            traceback.print_exc()
    print(f"# total {time.time()-t0:.0f}s; failures: {[n for n, _ in failures]}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

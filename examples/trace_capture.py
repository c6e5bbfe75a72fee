"""Trace capture: record a `repro.obs` Chrome trace of one sharded
8-device mine and a short streaming run, ready to open in Perfetto.

  PYTHONPATH=src python examples/trace_capture.py
  PYTHONPATH=src python examples/trace_capture.py --scale 0.1 --out-dir /tmp/traces

Open the resulting ``*.trace.json`` at https://ui.perfetto.dev (or
``chrome://tracing``): pid/tid lanes show the dispatch pool's overlap,
``dispatch:shard{k}`` spans carry per-shard counter deltas in their
args, and the streaming file nests ``tick:ingest/plan/mine/score``
under each ``tick``.
"""
import argparse
import os

# 8 virtual CPU devices for the sharded mine — must land before jax
# initializes its backend (i.e. before any repro import)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

from repro.api import MiningSession
from repro.data import generate_aml_dataset
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.stream import DetectionService

ap = argparse.ArgumentParser()
ap.add_argument("--scale", type=float, default=0.2, help="dataset scale factor")
ap.add_argument("--out-dir", default="traces", help="where the trace JSONs land")
args = ap.parse_args()
os.makedirs(args.out_dir, exist_ok=True)

W = 4096
ds = generate_aml_dataset("HI-Small", seed=0, scale=args.scale)
tracer = obs_trace.get_tracer()

# 1. one sharded mine across all 8 virtual devices ---------------------------
# spans: mine -> schedule -> schedule/stage/dispatch per shard under
# dispatch:shard{k}, compile on first-call jit misses, then the single
# blocking fetch (its wait child) and the assembling fetch
session = MiningSession(ds.graph, window=W)
session.register("scatter_gather", "fan_in", "fan_out", "cycle3")
session.mine()  # warm untraced so the traced mine shows steady state
obs_trace.enable()
res = session.mine(backend="sharded", n_parts=8)
obs_trace.disable()
path = os.path.join(args.out_dir, "sharded_mine.trace.json")
tracer.export_chrome(path)
print(f"sharded mine: {res.stats['kernel_calls']} kernel calls, "
      f"host_syncs={res.stats['host_syncs']}, "
      f"{len(tracer.spans())} spans -> {path}")
print(tracer.summary())
tracer.reset()

# 2. a few streaming ticks ---------------------------------------------------
# spans: tick -> tick:ingest / tick:plan / tick:mine / tick:score, with
# executor-counter deltas attributed to the mine span of each tick
svc = DetectionService(["fan_in", "cycle3"], window=W)
g, order = ds.graph, np.argsort(ds.graph.t, kind="stable")
obs_trace.enable()
for ch in np.array_split(order, 6):
    batch = svc.submit(g.src[ch], g.dst[ch], g.t[ch], g.amount[ch])
    r = batch.report
    print(f"tick {r.tick}: path={r.path} span_id={r.span_id} "
          f"trace_misses={r.trace_misses} {r.seconds*1e3:.0f}ms")
obs_trace.disable()
path = os.path.join(args.out_dir, "streaming.trace.json")
tracer.export_chrome(path)
print(f"streaming: {len(tracer.spans())} spans -> {path}")
tracer.reset()

# the same run also populated the metrics registry (tick latency
# histogram, executor/store counters) — Prometheus-style text:
print(obs_metrics.get_registry().exposition())

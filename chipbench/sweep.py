#!/usr/bin/env python3
"""Rate sweep of a stream cell: find the highest offered rate the server
sustains with no growing backlog, once, on the chip.  The cell's mix
then fixes its rate against it (below it, where the tails are what a
cell measures; above it, where the completed rate is); runs never
search for a rate.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --rates 200,400,800 --seconds 20

One process: set-up as a run makes it (with no rings planted), then
each rate in turn offers the next ``rate x seconds`` transfers in time
order, open loop, and drains them as a run does.  A
rate is sustained when the median latency of the segment's second half
is no more than one median tick above its first half's (the backlog
does not grow) and the drain after the close takes at most three median
ticks.  Prints one JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="events/s, comma-separated, ascending")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

    from chipbench import harness
    from repro.launch.jax_cache import enable_compile_cache

    bench = harness.Benchmark(ROOT)
    wl = bench.workload(args.workload)
    cfg, mix = bench.config(wl["config"]), bench.mix(wl["traffic"])
    stream = bench.kind(mix["mode"])
    harness.device_info(int(wl["chips"]))
    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    data = harness.generate_data(cfg, args.seed)
    budget = sum(rates) * args.seconds
    plan, data = stream.stream_plan(dict(mix, rate_per_s=budget / args.seconds, rings={}),
                                    data, args.seed, cfg, args.seconds)
    _, server, _ = stream.build_server(cfg, mix)
    cols = (data["src"], data["dst"], data["t"], data["amount"])
    t0 = time.perf_counter()
    stream.submit_history(server.submit, cols, plan.history, mix)
    print(json.dumps({"history_events": len(plan.history),
                      "history_s": time.perf_counter() - t0}), flush=True)
    first = 0
    for rate in rates:
        n = int(np.ceil(rate * args.seconds))
        seg = stream.StreamPlan(history=plan.history[:0], window=plan.window[first : first + n],
                                rate=rate, rings=plan.rings)
        first += n
        at, ticks, errors, lag = stream.serve_window(
            server.submit, cols, seg, args.seconds, int(mix["max_batch"])
        )
        due = np.arange(len(seg.window)) / rate
        lat = at - due
        ok = ~np.isnan(lat)
        half = len(lat) // 2
        tick_p50 = float(np.median([t["submit_s"] for t in ticks])) if ticks else None
        drain = float(np.nanmax(at)) - args.seconds if ok.any() else None
        climb = float(np.nanmedian(lat[half:]) - np.nanmedian(lat[:half])) if ok.any() else None
        print(json.dumps({
            "rate": rate,
            "events": len(seg.window),
            "delivered": int(ok.sum()),
            "submit_errors": errors,
            "ticks": len(ticks),
            "tick_p50_s": tick_p50,
            "full_share": sum(t["path"] == "full" for t in ticks) / max(1, len(ticks)),
            "p50_s": float(np.nanmedian(lat)) if ok.any() else None,
            "p95_s": float(np.nanpercentile(lat, 95)) if ok.any() else None,
            "drain_s": drain,
            "latency_climb_s": climb,
            "generator_lag_max_s": lag,
            "sustained": bool(ok.all() and tick_p50 is not None and climb <= tick_p50
                              and drain <= 3 * tick_p50),
        }), flush=True)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

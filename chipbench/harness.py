"""The harness: finds a cell's files by name, runs it, checks it, and
prints the result line.

Everything is found from ``BENCHMARK.json`` at the root, so a later cell,
configuration, traffic mix or metric is new files and new entries:

* a cell (``workloads``) names a configuration and a traffic mix;
* a configuration's ``file`` holds the deployment (sizes, window,
  portfolio, guarantees);
* a traffic mix is ``<bench>/traffic/<traffic>.json``; its ``mode``
  names the traffic kind that plans, drives and checks it,
  ``<bench>/traffic/<mode>.py`` (:mod:`chipbench.generator`);
* a metric, end to end or per layer, is read by
  ``<bench>/metrics/<name>.py``, whose ``read(record)`` returns the
  value or ``None`` where the run has nothing to read.

``<bench>`` is the first entry of ``paths``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "Benchmark",
    "NoChip",
    "device_info",
    "run",
]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Benchmark:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.dir = os.path.join(self.root, self.spec["paths"][0])

    def workload(self, name: str) -> dict:
        for wl in self.spec["workloads"]:
            if wl["name"] == name:
                return wl
        raise KeyError(f"no workload {name!r}; have {[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for entry in self.spec["configs"]:
            if entry["name"] == name:
                with open(os.path.join(self.root, entry["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r}")

    def mix(self, name: str) -> dict:
        from chipbench.generator import load_mix

        return load_mix(self.dir, name)

    def kind(self, mode: str):
        from chipbench.generator import load_kind

        return load_kind(self.dir, mode)

    def metrics_for(self, cell: str, per_layer: bool) -> List[dict]:
        """The metrics a run of ``cell`` reports: the end-to-end ones
        without ``--trace``, the per-layer ones with it."""
        kind = "per_layer" if per_layer else "end_to_end"
        return [m for m in self.spec[kind] if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        path = os.path.join(self.dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def device_info(chips: int, require_chip: bool = True) -> dict:
    """Platform, kind and count of JAX's devices.  Raises
    :class:`NoChip` when there is no accelerator or too few of them,
    unless ``require_chip`` is off (tests drive the rest of a run on the
    CPU that way)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if require_chip:
        if info["platform"] == "cpu":
            raise NoChip("JAX found no accelerator (platform cpu)")
        if info["count"] < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {info['count']}")
        from chipbench.peaks import peaks_for

        peaks_for(info["kind"])  # an unknown chip is an error, not a default
    return info


def _peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def generate_data(cfg: dict, seed: int) -> dict:
    from chipbench.data.synth_aml import published_transfers

    return published_transfers(
        cfg["dataset"],
        seed,
        n_accounts=int(cfg["accounts"]),
        n_transactions=int(cfg["transactions"]),
        illicit_ratio=float(cfg["illicit_ratio"]),
        window=int(cfg["window"]),
    )


def run(root: str, cell: str, seed: int, seconds: float, trace: bool, *,
        clock_start: Optional[float] = None, require_chip: bool = True,
        hook: Optional[Callable] = None) -> Tuple[dict, List[str]]:
    """Run ``cell`` once; returns the result line and the lines that
    show each compared number beside its limit.  ``hook`` plants a fault
    in the timed path (tests only): the traffic kind wraps its entry in
    it (``MiningSession.mine`` for ``batch``, ``TriageServer.submit`` for
    ``stream``)."""
    clock_start = time.perf_counter() if clock_start is None else clock_start
    if seconds <= 0:
        raise ValueError("--seconds must be positive")
    bench = Benchmark(root)
    wl = bench.workload(cell)
    cfg = bench.config(wl["config"])
    mix = bench.mix(wl["traffic"])
    kind = bench.kind(mix["mode"])
    chips = int(wl["chips"])
    device = device_info(chips, require_chip)

    from repro.launch.jax_cache import enable_compile_cache

    from chipbench.tracing import Recorder

    enable_compile_cache()
    data = generate_data(cfg, seed)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    recorder = Recorder(trace_dir)
    try:
        rec = kind.run(cfg, mix, data, seed, seconds, recorder, clock_start, hook)
        rec["compiles_in_window"] = recorder.compiles_in_window()
        rec["trace"] = recorder.reduce()
    finally:
        recorder.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = _peak_bytes(chips)
    gc.collect()  # the program's state is gone before the reference runs

    checks, info = kind.check(cfg, mix, data, rec, seed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics: Dict[str, dict] = {}
    for m in bench.metrics_for(cell, per_layer=trace):
        value = bench.reader(m["name"])(rec)
        if value is None or not math.isfinite(value):
            info.append(f"metric {m['name']}: nothing to read ({value})")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": bool(correct),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if rec["trace"] is not None:
        tr = rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    notes = info + [
        f"run: setup_s={rec['setup_s']:.3f} window_s={rec['window_s']:.3f} "
        f"compiles_in_window={rec['compiles_in_window']}",
    ]
    if rec["trace"] is not None:
        notes.append(f"trace: idle_s_by_label={rec['trace']['idle_s_by_label']} "
                     f"programs={len(rec['trace']['program_s'])}")
    notes += [f"check {k}={v['value']} limit={v['limit']}" for k, v in checks.items()]
    return line, notes


def main(argv: Optional[List[str]] = None, clock_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        line, notes = run(root, args.workload, args.seed, args.seconds, bool(args.trace),
                          clock_start=clock_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for n in notes:
        print(n, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0

"""A tiny benchmark tree for the tests: the real traffic kinds, mixes
and metric readers with the real cells, on configurations cut to a size
a CPU test holds.  Only the sizes change; every other key is the real
configuration's and mix's.

The tree also holds the stream cell, which ``BENCHMARK.json`` leaves out
until the program delivers its traffic in time (``PERF.md``, Open
questions): its configuration, mix and traffic kind are kept and tested
here, so that admitting it is one set of entries."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

SIZES = {
    "hi_small": {"accounts": 400, "transactions": 6000, "illicit_ratio": 0.05},
    "li_small": {"accounts": 400, "transactions": 8000, "illicit_ratio": 0.05},
}
TRAFFIC = {
    "batch_local": {"seeds_per_mine": 256, "warmup_mines": 1, "check_seeds": 64},
    "triage_stream": {"rate_per_s": 400, "max_batch": 256, "warmup_batch": 256,
                      "warmup_tail_ticks": 2, "warmup_tail_batch": 64,
                      "rings": {"cycle2": 2, "cycle3": 2, "scatter_gather": 1},
                      "rings_within": 32, "check_seeds": 64, "check_alerts": 25},
}


STREAM = {
    "configs": [{"name": "li_small", "source": "https://arxiv.org/abs/2306.16424",
                 "file": "chipbench/configs/li_small.json", "reduced": [],
                 "why": "the published LI-Small graph"}],
    "workloads": [{"name": "li_small.triage_stream", "config": "li_small",
                   "traffic": "triage_stream", "chips": 1, "why": "open-loop triage stream"}],
    "end_to_end": [{"name": n, "unit": u, "better": b, "bound": 0.25, "source": "host_clock",
                    "workloads": ["li_small.triage_stream"]}
                   for n, u, b in (("alert_p50_ms", "ms", "lower"),
                                   ("stream_events_per_s", "events/s", "higher"))],
}


def make_tree(dst: str) -> str:
    """Write the tiny tree under ``dst``; returns its root."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for key, entries in STREAM.items():
        spec[key] = spec[key] + entries
    bench = os.path.join(dst, spec["paths"][0])
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(bench, "metrics"))
    shutil.copytree(os.path.join(BENCH, "traffic"), os.path.join(bench, "traffic"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.json"))
    os.makedirs(os.path.join(bench, "configs"))
    for entry in spec["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        cfg.update(SIZES[entry["name"]])
        with open(os.path.join(dst, entry["file"]), "w") as f:
            json.dump(cfg, f)
    for name in {w["traffic"] for w in spec["workloads"]}:
        with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
            mix = json.load(f)
        mix.update(TRAFFIC.get(name, {}))
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dst

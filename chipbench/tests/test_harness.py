"""The harness: discovery by name, the metric arithmetic, and the refusal
to run without a chip."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, stats
from chipbench.tests.tiny import ROOT

RUN = os.path.join(ROOT, "chipbench", "run.py")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- discovery ------------------------------------------------------------
def test_every_named_file_is_found():
    bench = harness.Benchmark(ROOT)
    spec = bench.spec
    for wl in spec["workloads"]:
        cfg = bench.config(wl["config"])
        assert cfg["name"] == wl["config"]
        kind = bench.kind(bench.mix(wl["traffic"])["mode"])
        assert all(callable(getattr(kind, f)) for f in ("run", "check", "control"))
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert callable(bench.reader(m["name"]))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    bench = harness.Benchmark(ROOT)
    for wl in bench.spec["workloads"]:
        e2e = {m["name"] for m in bench.metrics_for(wl["name"], per_layer=False)}
        assert "setup_s" in e2e and len(e2e) >= 2, wl["name"]
        assert bench.metrics_for(wl["name"], per_layer=True), wl["name"]


DUMMY_KIND = """\
\"\"\"A dummy traffic kind: the same first block of transfers mined over
and over, its counts compared with the reference.\"\"\"
import time

import numpy as np

from chipbench.check import compare_counts, ref_graph


def run(cfg, mix, data, seed, seconds, recorder, clock_start, hook=None):
    from repro.api import MiningSession
    from repro.graph.csr import build_temporal_graph

    g = build_temporal_graph(data["src"], data["dst"], data["t"], data["amount"],
                             n_nodes=data["n_nodes"])
    session = MiningSession(g, window=int(cfg["window"]))
    session.register(*mix["patterns"]).compile()
    seeds = np.sort(np.argsort(data["t"], kind="stable")[: int(mix["block"])]).astype(np.int32)
    res = session.mine(seeds=seeds)
    setup_s = time.perf_counter() - clock_start
    n = 0
    with recorder.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            res = session.mine(seeds=seeds)
            n += 1
        window_s = time.perf_counter() - t0
    return {"mode": "dummy_kind", "patterns": list(mix["patterns"]), "setup_s": setup_s,
            "window_s": window_s, "mines": n, "attempted": n, "failed": 0,
            "outputs": (seeds, np.asarray(res.counts))}


def check(cfg, mix, data, rec, seed, produce=None):
    seeds, counts = rec["outputs"]
    got = {p: counts[:, j] for j, p in enumerate(rec["patterns"])}
    bad, _, _ = compare_counts(rec["patterns"], ref_graph(data), seeds, got,
                               int(cfg["window"]), produce)
    return {"count_mismatches": {"value": bad, "limit": 0}}, []


def control(cfg, mix, data, seed, produce, seconds, size):
    raise NotImplementedError
"""


def test_a_new_cell_config_mix_and_metric_need_only_new_files(tmp_path, no_compile_cache):
    """A dummy of each kind of file (configuration, mix of an existing
    traffic kind, a new traffic kind with its mix, metric), added as
    files and entries in a copy of the tree, is found and run with no
    edit to the harness."""
    from chipbench.tests.tiny import make_tree

    root = make_tree(str(tmp_path))
    bench_dir = os.path.join(root, "chipbench")
    with open(os.path.join(bench_dir, "configs", "hi_small.json")) as f:
        cfg = json.load(f)
    cfg.update(name="dummy_cfg", accounts=300, transactions=3000)
    with open(os.path.join(bench_dir, "configs", "dummy_cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench_dir, "traffic", "dummy_mix.json"), "w") as f:
        json.dump({"mode": "batch", "patterns": ["fan_in"], "seeds_per_mine": 64,
                   "warmup_mines": 1, "check_seeds": 16}, f)
    with open(os.path.join(bench_dir, "traffic", "dummy_kind.py"), "w") as f:
        f.write(DUMMY_KIND)
    with open(os.path.join(bench_dir, "traffic", "dummy_kind_mix.json"), "w") as f:
        json.dump({"mode": "dummy_kind", "patterns": ["fan_out", "cycle2"], "block": 48}, f)
    with open(os.path.join(bench_dir, "metrics", "dummy_metric.py"), "w") as f:
        f.write("def read(rec):\n    return float(rec['mines'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = ["dummy_cfg.dummy_mix", "dummy_cfg.dummy_kind_mix"]
    spec["configs"].append({"name": "dummy_cfg", "source": "test", "file": "chipbench/configs/dummy_cfg.json",
                            "reduced": [], "why": "test"})
    for cell in cells:
        spec["workloads"].append({"name": cell, "config": "dummy_cfg",
                                  "traffic": cell.split(".")[1], "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "dummy_metric", "unit": "mines", "better": "higher",
                               "bound": 0.1, "source": "host_clock", "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    for cell in cells:
        line, notes = harness.run(root, cell, 5, 0.5, False, require_chip=False)
        assert line["correct"] is True, (cell, notes)
        assert line["metrics"]["dummy_metric"]["value"] >= 1
        assert set(line["metrics"]) == {"dummy_metric", "setup_s"}
        assert list(line)[-1] == "checks"
        assert notes[-1].startswith("check count_mismatches=0 limit=0")


# -- metric arithmetic ----------------------------------------------------
def test_p95_is_over_every_event_and_failed_ones_rank_last():
    lat = list(range(1, 101))  # 1..100
    assert stats.percentile(lat, 95) == 95
    assert stats.percentile(lat, 95, n_failed=4) == 99  # 104 events, rank 99
    assert stats.percentile(lat, 95, n_failed=6) == math.inf  # rank 101 is a failure
    assert stats.percentile([], 95) is None
    assert stats.percentile([], 95, n_failed=1) == math.inf


def test_rate_is_over_the_whole_window():
    assert stats.rate(8192 * 3, 12.0) == 2048.0
    assert stats.rate(10, 0.0) is None


def _reader(name):
    return harness.Benchmark(ROOT).reader(name)


def test_stream_readers_count_undelivered_events_as_failed():
    rec = {"mode": "stream", "latencies_s": [0.1] * 90, "failed": 10, "delivered": 90,
           "last_delivery_s": 9.0, "ticks": [{"path": "full", "submit_s": 0.2},
                                             {"path": "local", "submit_s": 0.4},
                                             {"path": "full", "submit_s": 0.3}]}
    assert _reader("alert_p95_ms.stream")(rec) == math.inf
    assert _reader("alert_p50_ms")(rec) == pytest.approx(100.0)
    rec["failed"] = 91  # rank 91 of 181 is a failure
    assert _reader("alert_p50_ms")(rec) == math.inf
    rec["failed"] = 0
    assert _reader("alert_p95_ms.stream")(rec) == pytest.approx(100.0)
    assert _reader("stream_events_per_s")(rec) == pytest.approx(10.0)
    assert _reader("full_path_share.stream")(rec) == pytest.approx(200 / 3)
    assert _reader("tick_p50_ms.stream")(rec) == pytest.approx(300.0)
    assert _reader("batch_seeds_per_s")(rec) is None


def test_batch_readers():
    rec = {"mode": "batch", "seeds": 16384, "window_s": 8.0, "compiles_in_window": 3,
           "stats": {"kernel_calls": 230, "padded_elements": 16384 * 50},
           "trace": {"program_s": {"a": 0.5, "b": 0.3}, "idle_share": 0.9, "n_devices": 1}}
    assert _reader("batch_seeds_per_s")(rec) == 2048.0
    assert _reader("launches_per_kseed.batch")(rec) == pytest.approx(230 / 16.384)
    assert _reader("padded_elements_per_seed.batch")(rec) == 50.0
    assert _reader("compiles_in_window.batch")(rec) == 3
    assert _reader("mine_device_ms_per_kseed.batch")(rec) == pytest.approx(800 / 16.384)
    assert _reader("device_idle_share.batch")(rec) == pytest.approx(90.0)
    assert _reader("device_idle_share.stream")(rec) is None
    rec["trace"] = None
    assert _reader("mine_device_ms_per_kseed.batch")(rec) is None


# -- no chip, no result ---------------------------------------------------
def test_the_harness_refuses_a_cpu():
    with pytest.raises(harness.NoChip):
        harness.run(ROOT, "hi_small.batch_local", 1, 1.0, False)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"), "--workload",
         "hi_small.batch_local", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_prints_no_result_on_a_cpu():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""

"""The readers of the program's phase counters: ``mine_host_ms_per_kseed.batch``
and ``stage_ms_per_kseed.batch``, on hand-made records and on a real
tiny batch run, whose summed ``MiningResult.stats`` carry the counters."""
import math
import time

import pytest

from chipbench import harness
from chipbench.tests.tiny import ROOT
from chipbench.tracing import Recorder

READERS = ("mine_host_ms_per_kseed.batch", "stage_ms_per_kseed.batch")


def _reader(name, root=ROOT):
    return harness.Benchmark(root).reader(name)


def _batch(**stats):
    return {"mode": "batch", "seeds": 16384, "window_s": 8.0, "stats": stats}


def test_values_on_a_hand_made_record():
    rec = _batch(kernel_calls=2, mine_ns=52_000_000, wait_ns=36_000_000,
                 stage_ns=4_096_000)
    assert _reader("mine_host_ms_per_kseed.batch")(rec) == pytest.approx(16.0 / 16.384)
    assert _reader("stage_ms_per_kseed.batch")(rec) == pytest.approx(4.096 / 16.384)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_without_the_counters(name):
    read = _reader(name)
    assert read(_batch(kernel_calls=2, padded_elements=64)) is None  # a program without them
    assert read({**_batch(mine_ns=1, wait_ns=0, stage_ns=1), "seeds": 0}) is None
    stream = {"mode": "stream", "ticks": [], "delivered": 0, "failed": 0,
              "stats": {"mine_ns": 1, "wait_ns": 0, "stage_ns": 1}}
    assert read(stream) is None


def test_a_tiny_batch_run_carries_the_counters(tiny_root, no_compile_cache):
    bench = harness.Benchmark(tiny_root)
    wl = bench.workload("hi_small.batch_local")
    cfg, mix = bench.config(wl["config"]), bench.mix(wl["traffic"])
    seed = 2**31 + 23
    recorder = Recorder(None)  # a --trace 0 run
    try:
        rec = bench.kind(mix["mode"]).run(cfg, mix, harness.generate_data(cfg, seed), seed,
                                          0.5, recorder, time.perf_counter())
    finally:
        recorder.close()
    stats = rec["stats"]
    assert rec["mines"] > 0 and stats["host_syncs"] == rec["mines"]
    for key in ("schedule_ns", "stage_ns", "dispatch_ns", "fetch_ns", "wait_ns", "mine_ns"):
        assert stats[key] > 0, key
    assert stats["mine_ns"] >= stats["stage_ns"] + stats["wait_ns"]
    window_ms_per_kseed = rec["window_s"] * 1e3 / rec["seeds"] * 1e3
    values = {name: _reader(name, tiny_root)(rec) for name in READERS}
    for name, value in values.items():
        assert value is not None and math.isfinite(value) and value > 0, name
    wait_ms_per_kseed = stats["wait_ns"] / 1e6 / rec["seeds"] * 1e3
    assert values["mine_host_ms_per_kseed.batch"] + wait_ms_per_kseed <= window_ms_per_kseed
    assert values["stage_ms_per_kseed.batch"] <= values["mine_host_ms_per_kseed.batch"]

"""The mine path's phases on the profiler's clock, and their counters.

* A fused mine (``fan_in``) and a compiled mine (``cycle3``) run under
  ``jax.profiler`` with the in-memory tracer off put host events named
  exactly ``mine``, ``schedule``, ``stage``, ``dispatch``, ``fetch`` and
  ``wait`` on ``/host:CPU``, each nested in time under ``mine``, read
  with the benchmark's own trace loader.
* With no profiler session and the tracer off, ``span()`` is still the
  shared no-op.
* The phase counters: every one > 0, ``mine_ns >= stage_ns + wait_ns``,
  and a sharded mine's launch-side phase counters still sum over its
  shards.
* An empty mine launches nothing and reads nothing back.
* The device programs carry stable names.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import MiningSession
from repro.core.compiler import STRATEGY_NAMES
from repro.obs import trace as obs_trace
from tests.conftest import random_temporal_graph

W = 64
PHASES = ("mine", "schedule", "stage", "dispatch", "fetch", "wait")
COUNTERS = tuple(f"{p}_ns" for p in PHASES)


@pytest.fixture(scope="module")
def graph():
    return random_temporal_graph(
        np.random.default_rng(21), n_nodes=30, n_edges=300, t_max=256
    )


@pytest.fixture(scope="module")
def seeds():
    return np.arange(120, dtype=np.int32)


def _host_events(trace_dir, names):
    from chipbench import trace_reduce

    pd = trace_reduce.load(str(trace_dir))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("pattern", ["fan_in", "cycle3"])
def test_mine_phases_land_on_the_profiler_clock(graph, seeds, pattern, tmp_path):
    assert not obs_trace.is_enabled()  # the profiler alone records them
    session = MiningSession(graph, window=W).register(pattern)
    session.mine(seeds=seeds)  # compile outside the traced mine
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = session.mine(seeds=seeds)
    finally:
        jax.profiler.stop_trace()
    assert res.stats["host_syncs"] == 1
    events = _host_events(tmp_path, set(PHASES))
    assert {name for name, *_ in events} == set(PHASES)
    (mine,) = [e for e in events if e[0] == "mine"]
    assert mine[3]["backend"] == "compiled" and mine[3]["n_seeds"] == len(seeds)
    for name, t0, t1, _ in events:
        assert mine[1] <= t0 <= t1 <= mine[2], name
    # the blocking read-back nests in a fetch
    fetches = [e for e in events if e[0] == "fetch"]
    for _, t0, t1, _ in (e for e in events if e[0] == "wait"):
        assert any(f0 <= t0 <= t1 <= f1 for _, f0, f1, _ in fetches)
    assert {e[3].get("mode") for e in fetches} >= {"result"}


def test_span_is_the_shared_noop_without_a_profiler_session():
    tr = obs_trace.Tracer(enabled=False)
    assert tr.span("stage", strat="fused") is tr.span("fetch")
    assert obs_trace.span("mine") is obs_trace.span("wait")  # global, off


def test_phase_counts_with_everything_off():
    stats = {}
    with obs_trace.phase("stage", stats, strat="fused") as sp:
        assert sp is obs_trace.span("stage")  # the shared no-op
    with obs_trace.phase("stage", stats):
        pass
    assert stats["stage_ns"] > 0 and set(stats) == {"stage_ns"}


def test_phase_attributes_reach_the_profiler(tmp_path):
    stats = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.phase("stage", stats, strat="fused", n_seeds=7) as sp:
            sp.set(bytes_staged=96)
    finally:
        jax.profiler.stop_trace()
    ((name, t0, t1, attrs),) = _host_events(tmp_path, {"stage"})
    assert attrs == {"strat": "fused", "n_seeds": 7, "bytes_staged": 96}
    assert stats["stage_ns"] > 0


@pytest.mark.parametrize("pattern", ["fan_in", "cycle3"])
def test_phase_counters_cover_the_mine(graph, seeds, pattern):
    session = MiningSession(graph, window=W).register(pattern)
    for _ in range(2):  # cold, then warm: both count every phase
        res = session.mine(seeds=seeds)
        assert all(res.stats[k] > 0 for k in COUNTERS), res.stats
        st = res.stats
        assert st["mine_ns"] >= st["stage_ns"] + st["wait_ns"]
        assert st["fetch_ns"] >= st["wait_ns"]
        assert st["mine_ns"] >= (
            st["schedule_ns"] + st["stage_ns"] + st["dispatch_ns"] + st["fetch_ns"]
        )
    # lifetime counters take the mine-level phases too
    assert session.stats["mine_ns"] >= res.stats["mine_ns"] > 0


@pytest.mark.parametrize("n_parts, mode", [(1, "collective"), (3, "host")])
def test_sharded_phase_counters_sum_over_shards(graph, seeds, n_parts, mode):
    session = MiningSession(graph, window=W).register("fan_in", "cycle3")
    res = session.mine(seeds=seeds, backend="sharded", n_parts=n_parts)
    assert res.gather_mode == mode
    for key in ("stage_ns", "dispatch_ns"):
        assert res.stats[key] == sum(st[key] for st in res.shard_stats) > 0, key
    # the mine adds its own preamble to the shards' schedules
    assert res.stats["schedule_ns"] > sum(st["schedule_ns"] for st in res.shard_stats)
    # the read-back is the mine's alone
    assert all(st["fetch_ns"] == st["wait_ns"] == 0 for st in res.shard_stats)
    assert res.stats["fetch_ns"] >= res.stats["wait_ns"] > 0
    assert res.stats["mine_ns"] >= res.stats["fetch_ns"]


def test_device_programs_have_stable_names(graph, seeds):
    session = MiningSession(graph, window=W).register("fan_in", "cycle3")
    session.mine(seeds=seeds)
    session.mine(seeds=seeds, witnesses=1)
    fused = session._fused
    (fn,) = fused._jitted.values()
    s = jnp.zeros(32, jnp.int32)
    assert "module @jit_fused_seed_local" in fn.lower(session._dg, s, s, s).as_text()
    kernels = session._compiled_for(session._canon_of["cycle3"])._kernels
    names = {False: set(), True: set()}  # counting, witness
    for key, k in kernels.items():
        names[key[1] == "wit"].add(k.__name__)
    assert names[False] and names[False] <= {f"mine_{s}" for s in STRATEGY_NAMES}
    assert names[True] and names[True] <= {f"witness_{s}" for s in STRATEGY_NAMES}


@pytest.mark.parametrize("backend", ["compiled", "partitioned"])
@pytest.mark.parametrize("patterns", [("fan_in",), ("fan_in", "cycle3")])
def test_empty_mine_launches_and_syncs_nothing(graph, backend, patterns):
    session = MiningSession(graph, window=W).register(*patterns)
    kw = {"n_parts": 2} if backend == "partitioned" else {}
    res = session.mine(seeds=np.zeros(0, np.int32), backend=backend, **kw)
    assert res.counts.shape == (0, len(patterns))
    st = res.stats
    assert st["host_syncs"] == st["kernel_calls"] == st["bytes_h2d"] == 0
    assert st["wait_ns"] == st["stage_ns"] == 0 and st["mine_ns"] > 0

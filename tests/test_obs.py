"""`repro.obs` contracts (ISSUE 9): span tracer, metrics registry,
flight recorder, and the instrumentation threaded through the executor,
the sharded dispatch pool, and the streaming service.

* Chrome trace-event export schema: ``traceEvents`` of ``"ph": "X"``
  complete events with microsecond ``ts``/``dur``, parent/span ids in
  ``args``, thread-id lanes — loadable by chrome://tracing / Perfetto;
* span nesting + counter-delta attribution (``stats=`` snapshots);
* disabled-tracer overhead: one branch + a shared no-op manager — the
  per-call cost is bounded in a microbench-style test;
* histogram quantiles match ``np.percentile`` exactly below the
  reservoir cap; count/sum stay exact past it;
* thread hammer: concurrent counter/histogram mutation is bit-exact;
* Prometheus text exposition shape;
* a 20-tick streaming run produces the per-stage tick span breakdown
  (tick -> ingest/plan/mine/score), ``TickReport.trace_misses`` decays
  to zero as the JIT cache warms (with a warning log on warm-tick
  misses), the flight recorder rings the reports, and a postmortem
  bundle dumps on demand;
* the real sharded path (8 virtual devices, subprocess) emits one
  ``dispatch:shard{k}`` span per shard with per-shard counter deltas
  while ``host_syncs`` stays 1.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.obs import flight as obs_flight
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@pytest.fixture()
def tracer():
    """A private enabled tracer installed as the global one (restored
    after the test) — instrumented library code sees it."""
    prev = obs_trace.set_tracer(obs_trace.Tracer(enabled=True))
    try:
        yield obs_trace.get_tracer()
    finally:
        obs_trace.set_tracer(prev)


@pytest.fixture()
def registry():
    prev = obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    try:
        yield obs_metrics.get_registry()
    finally:
        obs_metrics.set_registry(prev)


# ----------------------------------------------------------------------
# tracer
# ----------------------------------------------------------------------
def test_span_nesting_and_chrome_schema(tracer, tmp_path):
    with tracer.span("outer", label="root"):
        with tracer.span("inner:a"):
            pass
        with tracer.span("inner:b"):
            tracer.instant("marker", note="x")
    spans = tracer.spans()
    by_name = {ev["name"]: ev for ev in spans}
    assert set(by_name) == {"outer", "inner:a", "inner:b", "marker"}
    # children closed before the parent and link to it
    outer = by_name["outer"]
    for child in ("inner:a", "inner:b"):
        assert by_name[child]["parent"] == outer["id"]
    assert by_name["marker"]["parent"] == by_name["inner:b"]["id"]
    assert outer["parent"] is None
    assert all(ev["dur_ns"] >= 0 for ev in spans)

    path = tmp_path / "trace.json"
    out = tracer.export_chrome(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(out))
    assert isinstance(loaded["traceEvents"], list)
    assert loaded["displayTimeUnit"] == "ms"
    complete = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    instants = [e for e in loaded["traceEvents"] if e["ph"] == "i"]
    assert len(complete) == 3 and len(instants) == 1
    for e in loaded["traceEvents"]:
        assert set(("name", "cat", "pid", "tid", "ts", "args")) <= set(e)
        assert isinstance(e["ts"], float)
        assert "span_id" in e["args"]
    # parent links survive into args, ts/dur are microseconds
    inner = next(e for e in complete if e["name"] == "inner:a")
    root = next(e for e in complete if e["name"] == "outer")
    assert inner["args"]["parent_span_id"] == root["args"]["span_id"]
    assert root["dur"] >= inner["dur"] >= 0
    assert root["ts"] <= inner["ts"]


def test_span_stats_delta_attribution(tracer):
    stats = {"kernel_calls": 3, "bytes_h2d": 100, "name": "not-numeric"}
    with tracer.span("work", stats=stats, strat="bulk"):
        stats["kernel_calls"] += 4
        stats["bytes_h2d"] += 256
    (ev,) = tracer.spans()
    assert ev["attrs"]["kernel_calls"] == 4
    assert ev["attrs"]["bytes_h2d"] == 256
    assert ev["attrs"]["strat"] == "bulk"
    assert "name" not in ev["attrs"]  # non-numeric keys are not diffed


def test_span_records_exception_and_unwinds_stack(tracer):
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("x")
    (ev,) = tracer.spans()
    assert ev["attrs"]["error"] == "ValueError"
    assert tracer.current_span_id() is None  # stack unwound


def test_disabled_tracer_is_noop_singleton_and_cheap():
    tr = obs_trace.Tracer(enabled=False)
    a = tr.span("x", stats={"k": 1}, attr=1)
    b = tr.span("y")
    assert a is b  # shared no-op: no allocation on the disabled path
    with a as sp:
        assert sp.span_id is None
        sp.set(ignored=True)
    assert tr.spans() == []
    assert tr.current_span_id() is None

    # microbench bound: the disabled call is one branch + a constant —
    # budget 5 us/call, ~50x slack over the measured cost, so the bound
    # holds on a loaded single-core CI runner
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"disabled span cost {per_call * 1e9:.0f}ns"


def test_tracer_capacity_drops_oldest(tracer):
    tracer.capacity = 10
    for i in range(25):
        with tracer.span(f"s{i}"):
            pass
    spans = tracer.spans()
    assert len(spans) == 10
    assert tracer.dropped == 15
    assert [ev["name"] for ev in spans] == [f"s{i}" for i in range(15, 25)]
    assert "dropped" in tracer.summary()


def test_tracer_thread_lanes(tracer):
    # all four workers stay alive until each has opened its span: a
    # worker that exits early frees its OS thread id for the next one
    together = threading.Barrier(4)

    def worker(k):
        with tracer.span(f"w{k}"):
            together.wait(timeout=30)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    spans = tracer.spans()
    assert len(spans) == 4
    assert all(ev["parent"] is None for ev in spans)  # per-thread stacks
    assert len({ev["tid"] for ev in spans}) == 4


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
def test_histogram_quantiles_match_numpy(registry):
    rng = np.random.default_rng(7)
    vals = rng.exponential(scale=3.0, size=2000)
    h = registry.histogram("lat", help="latency")
    for v in vals:
        h.observe(float(v))
    # below the reservoir cap every observation is kept: quantiles are
    # np.percentile bit-for-bit
    for q in (0.5, 0.9, 0.99):
        assert h.quantile(q) == float(np.percentile(vals, q * 100.0))
    assert h.count == len(vals)
    assert h.sum == pytest.approx(vals.sum())


def test_histogram_reservoir_bounds_memory_keeps_exact_count(registry):
    h = registry.histogram("big", reservoir=64)
    for i in range(1000):
        h.observe(float(i))
    assert h.count == 1000
    assert h.sum == sum(range(1000))
    assert len(h._samples) == 64  # bounded
    q50 = h.quantile(0.5)
    assert 0.0 <= q50 <= 999.0


def test_registry_threaded_hammer_bit_exact(registry):
    c = registry.counter("hits")
    h = registry.histogram("obs")
    g = registry.gauge("hw")
    n_threads, per = 8, 5000

    def worker(k):
        for i in range(per):
            c.inc()
            h.observe(1.0)
            g.max_set(k * per + i)

    threads = [
        threading.Thread(target=worker, args=(k,)) for k in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per  # no dropped increments
    assert h.count == n_threads * per
    assert h.sum == float(n_threads * per)
    assert g.value == n_threads * per - 1


def test_exposition_and_snapshot_shape(registry):
    registry.counter("reqs", help="requests").inc(3)
    registry.gauge("level").set(2)
    registry.counter(
        "beats", labels={"device": "cpu:0"}
    ).inc(5)
    h = registry.histogram("lat", help="latency seconds")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    text = registry.exposition()
    assert "# HELP reqs requests" in text
    assert "# TYPE reqs counter" in text
    assert "reqs 3" in text
    assert "# TYPE level gauge" in text
    assert 'beats{device="cpu:0"} 5' in text
    assert "# TYPE lat summary" in text
    assert 'lat{quantile="0.5"}' in text
    assert "lat_count 4" in text
    assert "lat_sum 10.0" in text

    snap = registry.snapshot()
    assert snap["reqs"] == 3
    assert snap['beats{device="cpu:0"}'] == 5
    assert snap["lat_count"] == 4
    assert snap['lat{quantile="0.5"}'] == 2.5
    json.dumps(snap)  # JSON-friendly end to end


def test_registry_kind_collision_raises(registry):
    registry.counter("x")
    with pytest.raises(TypeError):
        registry.gauge("x")


def test_observe_stats_counters_and_gauges(registry):
    obs_metrics.observe_stats(
        {"kernel_calls": 3, "jit_cache_entries": 5}, "ex", registry=registry
    )
    obs_metrics.observe_stats(
        {"kernel_calls": 2, "jit_cache_entries": 4}, "ex", registry=registry
    )
    snap = registry.snapshot()
    assert snap["ex_kernel_calls"] == 5  # counter: deltas sum
    assert snap["ex_jit_cache_entries"] == 5  # gauge: high-water mark


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------
def test_flight_recorder_ring_and_dump(tracer, tmp_path):
    fr = obs_flight.FlightRecorder(capacity=3)
    for i in range(5):
        with tracer.span("tick", tick=i) as sp:
            with tracer.span("tick:mine"):
                pass
        fr.record({"tick": i, "arr": np.int64(i)}, span_id=sp.span_id)
    assert len(fr) == 3  # ring bound
    assert fr.n_recorded == 5
    last = fr.last()
    assert last["report"]["tick"] == 4
    assert last["report"]["arr"] == 4  # numpy scalar -> plain int
    # the span tree of the tick rode along (tick + its mine child)
    names = sorted(s["name"] for s in last["spans"])
    assert names == ["tick", "tick:mine"]

    path = tmp_path / "post" / "bundle.jsonl"
    fr.dump(str(path), reason="test", failure={"type": "Boom"})
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    header, entries = lines[0], lines[1:]
    assert header["postmortem"] and header["reason"] == "test"
    assert header["failure"]["type"] == "Boom"
    assert header["ticks_in_ring"] == 3 and header["ticks_recorded"] == 5
    assert [e["report"]["tick"] for e in entries] == [2, 3, 4]  # oldest first


def test_flight_recorder_skips_spans_when_disabled():
    fr = obs_flight.FlightRecorder()
    prev = obs_trace.set_tracer(obs_trace.Tracer(enabled=False))
    try:
        fr.record({"tick": 1}, span_id=7)
    finally:
        obs_trace.set_tracer(prev)
    assert fr.last()["spans"] is None


# ----------------------------------------------------------------------
# streaming instrumentation (20 ticks, per-stage breakdown)
# ----------------------------------------------------------------------
def _feed(rng, n, lo):
    src = rng.integers(0, 40, n).astype(np.int32)
    dst = rng.integers(0, 40, n).astype(np.int32)
    t = (np.arange(n) + lo).astype(np.int64)
    amt = rng.random(n).astype(np.float32)
    return src, dst, t, amt


def test_streaming_20_ticks_trace_and_flight(tracer, registry, tmp_path, caplog):
    from repro.stream.service import DetectionService

    svc = DetectionService(
        ["fan_in", "cycle2"],
        window=128,
        thresholds={"fan_in": 2, "cycle2": 1},
    )
    rng = np.random.default_rng(3)
    reports = []
    with caplog.at_level("WARNING", logger="repro.stream"):
        for k in range(20):
            batch = svc.submit(*_feed(rng, 30, 30 * k))
            reports.append(batch.report)

    # every report joins its span tree and counts its fresh traces
    assert all(r.span_id is not None for r in reports)
    assert len({r.span_id for r in reports}) == 20
    assert reports[0].trace_misses > 0  # cold tick compiles
    assert reports[-1].trace_misses == 0  # warm cache replays
    # a warm tick that minted a trace logged the latency-smell warning
    warm_missed = [
        r for r in reports if r.path in ("local", "full") and r.trace_misses
    ]
    warned = [rec for rec in caplog.records if "fresh JIT trace" in rec.message]
    assert len(warned) == len(warm_missed)

    # per-stage breakdown: each tick span parents ingest/plan/mine, the
    # scored ticks parent a score span
    spans = tracer.spans()
    by_id = {ev["id"]: ev for ev in spans}
    ticks = [ev for ev in spans if ev["name"] == "tick"]
    assert len(ticks) == 20
    for r in reports:
        kids = {
            ev["name"] for ev in spans if ev["parent"] == r.span_id
        }
        assert {"tick:ingest", "tick:plan", "tick:mine"} <= kids
    assert any(ev["name"] == "tick:score" for ev in spans)
    # stage spans nest under the tick:mine stage, carrying counter deltas
    mines = [ev for ev in spans if ev["name"] == "tick:mine"]
    assert any(ev["attrs"].get("kernel_calls", 0) > 0 for ev in mines)
    launches = [ev for ev in spans if ev["name"] == "dispatch"]
    assert launches and all(
        by_id[ev["parent"]]["name"] in ("tick:mine", "tick:witness")
        or by_id[by_id[ev["parent"]]["parent"]]["name"]
        in ("tick:mine", "tick:witness")
        for ev in launches
        if ev["parent"] is not None
    )

    # chrome export round-trips and carries every tick lane
    out = tracer.export_chrome(str(tmp_path / "stream.json"))
    names = {e["name"] for e in out["traceEvents"]}
    assert {"tick", "tick:ingest", "tick:plan", "tick:mine"} <= names

    # the flight recorder rang every tick with its span tree
    assert len(svc.flight) == 20
    last = svc.flight.last()
    assert last["report"]["tick"] == 20
    assert {"tick", "tick:ingest"} <= {s["name"] for s in last["spans"]}
    dump = svc.flight.dump(str(tmp_path / "bundle.jsonl"))
    assert os.path.exists(dump)

    # tick latency histogram + executor counters landed in the registry
    snap = registry.snapshot()
    assert snap["repro_stream_tick_seconds_count"] == 20
    assert snap["repro_executor_kernel_calls"] > 0
    assert snap["repro_stream_trace_misses_total"] == sum(
        r.trace_misses for r in reports
    )


def test_streaming_tick_report_span_id_none_when_disabled(registry):
    from repro.stream.service import DetectionService

    svc = DetectionService(["fan_in"], window=64, thresholds={"fan_in": 2})
    rng = np.random.default_rng(5)
    batch = svc.submit(*_feed(rng, 20, 0))
    assert batch.report.span_id is None
    assert batch.report.trace_misses > 0  # counted even without tracing
    assert len(svc.flight) == 1
    assert svc.flight.last()["spans"] is None


def test_resilient_postmortem_bundle_on_exhausted_retries(tmp_path, registry):
    from repro.stream.chaos import FaultInjector, TransientFault
    from repro.stream.resilience import (
        ResilienceConfig,
        ResilientDetectionService,
    )

    chaos = FaultInjector()
    chaos.arm("mine", tick=2, times=-1)  # tick 2 fails every attempt
    svc = ResilientDetectionService(
        ["fan_in"],
        window=64,
        thresholds={"fan_in": 2},
        chaos=chaos,
        resilience=ResilienceConfig(
            postmortem_dir=str(tmp_path / "post"),
            max_retries=1,
            backoff_s=0.0,
        ),
    )
    rng = np.random.default_rng(9)
    svc.submit(*_feed(rng, 25, 0))  # tick 1 commits
    with pytest.raises(TransientFault):
        svc.submit(*_feed(rng, 25, 25))  # tick 2 exhausts retries
    bundles = list((tmp_path / "post").glob("postmortem_tick_*.jsonl"))
    assert len(bundles) == 1
    lines = [json.loads(l) for l in bundles[0].read_text().splitlines()]
    assert lines[0]["postmortem"] and lines[0]["reason"] == "tick_failed"
    assert lines[0]["failure"]["type"] == "TransientFault"
    # the ring preserved the COMMITTED tick leading up to the crash
    assert [e["report"]["tick"] for e in lines[1:]] == [1]
    snap = registry.snapshot()
    assert snap["repro_resilience_retries_total"] == 1


def test_triage_server_metrics_endpoint_and_audit_span_ids(
    tracer, registry, tmp_path
):
    from repro.launch.serve import TriageServer
    from repro.stream.service import DetectionService

    audit = tmp_path / "audit.jsonl"
    svc = DetectionService(["fan_in"], window=64, thresholds={"fan_in": 1})
    server = TriageServer(svc, audit_path=str(audit))
    rng = np.random.default_rng(11)
    for k in range(3):
        server.submit(*_feed(rng, 25, 25 * k))
    snap = server.metrics()
    assert snap["repro_triage_submit_seconds_count"] == 3
    assert "repro_triage_submit_seconds" in server.metrics("prometheus")
    with pytest.raises(ValueError):
        server.metrics("xml")
    server.close()
    lines = [json.loads(l) for l in audit.read_text().splitlines()]
    alerts = [l for l in lines if "eid" in l and not l.get("dedup")]
    assert alerts, "portfolio with threshold 1 must alert"
    # audit lines join the tick's span tree
    tick_span_ids = {ev["id"] for ev in tracer.spans() if ev["name"] == "tick"}
    assert all(l["span_id"] in tick_span_ids for l in alerts)
    # close() flushed the final metrics snapshot into the audit stream
    metric_lines = [l for l in lines if l.get("metrics")]
    assert len(metric_lines) == 1
    assert (
        metric_lines[0]["snapshot"]["repro_triage_submit_seconds_count"] == 3
    )


# ----------------------------------------------------------------------
# sharded instrumentation (real multi-device path, subprocess)
# ----------------------------------------------------------------------
_SHARDED_TRACE_SCRIPT = r"""
import json
import numpy as np
from repro import obs
obs.trace.enable()
from repro.api import MiningSession
from tests.conftest import random_temporal_graph

rng = np.random.default_rng(13)
g = random_temporal_graph(rng, n_nodes=18, n_edges=140, t_max=256)
session = MiningSession(g, window=96).register("fan_in", "cycle3")
res = session.mine(backend="sharded", n_parts=8)
out = obs.trace.get_tracer().export_chrome("%(path)s")
evs = out["traceEvents"]
disp = [e for e in evs if e["name"].startswith("dispatch:shard")]
print(json.dumps({
    "gather_mode": res.gather_mode,
    "host_syncs": int(res.stats["host_syncs"]),
    "dispatch_spans": sorted(e["name"] for e in disp),
    "shard_kernel_calls": sum(
        int(e["args"].get("kernel_calls", 0)) for e in disp
    ),
    "mine_kernel_calls": int(res.stats["kernel_calls"]),
    "gather_modes": sorted(
        e["args"].get("mode", "") for e in evs if e["name"] == "fetch"
    ),
    "beat_metrics": sum(
        1
        for k in obs.metrics.get_registry().snapshot()
        if k.startswith("repro_shard_worker_beats")
    ),
}))
"""


def test_sharded_trace_multi_device_subprocess(tmp_path):
    """8 virtual devices: every shard dispatch emits its own span whose
    counter deltas sum to the mine totals, the collective gather emits
    one gather span, the trace is valid Chrome JSON, and instrumentation
    did not add a host sync."""
    trace_path = str(tmp_path / "mine.trace.json")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_TRACE_SCRIPT % {"path": trace_path}],
        capture_output=True,
        text=True,
        env=env,
        cwd=root,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["gather_mode"] == "collective"
    assert got["host_syncs"] == 1  # tracing adds no syncs
    assert got["dispatch_spans"] == [f"dispatch:shard{k}" for k in range(8)]
    # per-shard span counter deltas reassemble the mine-level total
    assert got["shard_kernel_calls"] == got["mine_kernel_calls"]
    # one collective read-back, then the host assembles the result
    assert got["gather_modes"] == ["assemble", "collective"]
    assert got["beat_metrics"] == 8  # one liveness gauge per device

    with open(trace_path) as f:
        trace = json.load(f)
    assert {e["name"] for e in trace["traceEvents"]} >= {
        "dispatch:shard0",
        "fetch",
        "stage",
        "dispatch",
    }

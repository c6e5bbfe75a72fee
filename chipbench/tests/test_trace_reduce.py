"""The trace reduction, on a small trace recorded on one TPU v5e by
``make_trace.py`` (three rounds of one jitted program, a 20 ms
``schedule`` span and a 10 ms unmarked sleep after each), and on
synthetic spans for the labelling rule."""
import os

import numpy as np
import pytest

from chipbench import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_v5e.xplane.pb")


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(TRACE)


def _events(pd, plane_prefix, line_name):
    for plane in pd.planes:
        if plane.name.startswith(plane_prefix):
            for line in plane.lines:
                if line.name == line_name:
                    return list(line.events)
    return []


def _window(pd):
    (win,) = [e for e in _events(pd, "/host:CPU", "python3") if e.name == "window"]
    return win.start_ns, win.start_ns + win.duration_ns


def _sweep_union(iv):
    """Union length by an endpoint sweep (a second method)."""
    pts = sorted([(s, 1) for s, _ in iv] + [(e, -1) for _, e in iv])
    depth, last, total = 0, None, 0.0
    for x, d in pts:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


def test_busy_is_the_union_of_device_ops_in_the_window(pd):
    w0, w1 = _window(pd)
    ops = _events(pd, "/device:TPU:0", "XLA Ops")
    iv = [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)) for e in ops]
    iv = [(a, b) for a, b in iv if b > a]
    out = trace_reduce.reduce_trace(pd)
    assert out["n_devices"] == 1
    assert out["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert out["busy_s"] == pytest.approx(_sweep_union(iv) / 1e9)
    assert out["busy_s"] > 0
    assert out["idle_share"] == pytest.approx(1 - out["busy_s"] / out["window_s"])


def test_program_time_sums_the_modules_in_the_window(pd):
    w0, w1 = _window(pd)
    mods = [e for e in _events(pd, "/device:TPU:0", "XLA Modules")
            if e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
    out = trace_reduce.reduce_trace(pd)
    assert set(out["program_s"]) == {"jit_probe"}
    assert out["program_s"]["jit_probe"] == pytest.approx(sum(e.duration_ns for e in mods) / 1e9)
    # the program is the only thing on the device: its ops fill its time
    assert out["busy_s"] <= out["program_s"]["jit_probe"] * 1.001
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])


def test_idle_gaps_are_labelled_by_the_host_span_open_in_them(pd):
    out = trace_reduce.reduce_trace(pd)
    labels = [g[0] for g in out["idle_gaps"]]
    secs = [g[1] for g in out["idle_gaps"]]
    # three sleeps of 20 ms in "schedule" (plus 10 ms unmarked) are the
    # three longest gaps; the device ran for microseconds in between
    assert labels[:3] == ["schedule"] * 3
    assert all(0.025 < s < 0.045 for s in secs[:3])
    assert secs == sorted(secs, reverse=True)
    total_idle = sum(out["idle_s_by_label"].values())
    assert total_idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)


def test_compile_spans_from_the_caller_label_gaps(pd):
    """A compile that JAX reports inside each ``schedule`` sleep is the
    innermost span there, so it names the gap."""
    sched = [e for e in _events(pd, "/host:CPU", "python3") if e.name == "schedule"]
    extra = [("compile", e.start_ns + 1, e.start_ns + e.duration_ns) for e in sched]
    out = trace_reduce.reduce_trace(pd, extra_spans=extra)
    assert [g[0] for g in out["idle_gaps"][:3]] == ["compile"] * 3
    assert "schedule" not in out["idle_s_by_label"]


def test_the_innermost_span_wins_and_uncovered_is_other():
    spans = np.asarray([[0, 100], [10, 60], [20, 30]], dtype=float)
    names = ["submit", "schedule", "fetch"]
    assert trace_reduce._label_gap(12, 58, spans, names) == "schedule"
    assert trace_reduce._label_gap(21, 29, spans, names) == "fetch"
    assert trace_reduce._label_gap(61, 99, spans, names) == "submit"
    assert trace_reduce._label_gap(150, 160, spans, names) == "other"
    # mostly uncovered
    assert trace_reduce._label_gap(90, 200, spans, names) == "other"


def test_union_length_merges_overlaps():
    iv = np.asarray([[0, 10], [5, 20], [30, 40], [40, 45]], dtype=float)
    assert trace_reduce.union_length(iv) == 35.0
    assert trace_reduce.union_length(np.zeros((0, 2))) == 0.0

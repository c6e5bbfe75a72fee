"""The stream kind's plan: the same seed gives the same plan, other seeds
the same sizes elsewhere in the data, and every planted ring is a match
of its pattern by the reference, at or above the alert threshold."""
import numpy as np
import pytest

from chipbench import check, harness
from chipbench.generator import load_kind

CELL = "li_small.triage_stream"


def _setup(root, seed, seconds=2.0):
    bench = harness.Benchmark(root)
    wl = bench.workload(CELL)
    cfg, mix = bench.config(wl["config"]), bench.mix(wl["traffic"])
    stream = load_kind(bench.dir, mix["mode"])
    data = harness.generate_data(cfg, seed)
    plan, planted = stream.stream_plan(mix, data, seed, cfg, seconds)
    return cfg, mix, data, plan, planted


def test_the_same_seed_gives_the_same_plan(tiny_root):
    _, mix, _, a, da = _setup(tiny_root, 2**33 + 5)
    _, _, _, b, db = _setup(tiny_root, 2**33 + 5)
    _, _, _, c, _ = _setup(tiny_root, 2**33 + 6)
    assert np.array_equal(a.window, b.window) and np.array_equal(a.history, b.history)
    assert np.array_equal(da["t"], db["t"])
    assert len(c.window) == len(a.window)
    n_rings = sum(mix["rings"].values())
    assert len(a.rings) == 2 * mix["rings"]["cycle2"] + 3 * mix["rings"]["cycle3"] \
        + 2 * 7 * mix["rings"]["scatter_gather"]  # threshold 6: 7 mules
    assert n_rings > 0 and np.isin(a.rings, a.window).all()


def test_events_fall_due_evenly_from_the_opening(tiny_root):
    _, mix, _, plan, _ = _setup(tiny_root, 2**33 + 5)
    assert plan.n_due(0.0) == 1
    assert plan.due(int(plan.rate)) == 1.0
    assert plan.n_due(1.0) == 1 + int(plan.rate)
    assert plan.n_due(1e9) == len(plan.window)


@pytest.mark.parametrize("seed", [2**33 + 5, 2**31 + 77])
def test_every_ring_is_a_match_at_its_threshold(tiny_root, seed):
    cfg, mix, _, plan, data = _setup(tiny_root, seed)
    order = np.concatenate([plan.history, plan.window])
    g = check.ref_graph(data, order)
    pos = {int(e): i for i, e in enumerate(order)}
    thr = cfg["portfolio"]
    rings = [d for d in data["instances"] if d["eids"][0] >= plan.rings[0]]
    kinds = {"cycle": 0, "scatter_gather": 0}
    for inst in rings:
        eids = np.asarray([pos[int(e)] for e in inst["eids"]])
        if inst["kind"] == "cycle":
            name = "cycle2" if len(eids) == 2 else "cycle3"
            got = check.reference(name, g, eids[:1], int(cfg["window"]))
        else:
            name = "scatter_gather"
            got = check.reference(name, g, eids[len(eids) // 2:], int(cfg["window"]))
        assert (got >= thr[name]).all(), (name, got)
        kinds[inst["kind"]] += 1
    assert kinds["cycle"] == mix["rings"]["cycle2"] + mix["rings"]["cycle3"]
    assert kinds["scatter_gather"] == mix["rings"]["scatter_gather"]
    early = [d for d in data["instances"][-2 * len(rings):-len(rings)]]
    assert len(early) == len(rings)
    assert all(np.isin(d["eids"], plan.history).all() for d in early)

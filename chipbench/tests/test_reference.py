"""The copied reference against the program's original oracle, on random
temporal graphs: the copy's window masks change its speed, never its
counts or the order of its witnesses.  (The test reads the program; the
benchmark's reference does not.)"""
import numpy as np
import pytest

from chipbench.ref import csr as ref_csr
from chipbench.ref import oracle as ref_oracle
from chipbench.ref import patterns as ref_patterns


def _graphs(seed, n_nodes, n_edges, t_max):
    from repro.graph.csr import build_temporal_graph

    rng = np.random.default_rng(seed)
    # a few hubs: a quarter of the endpoints land on 3 nodes
    def ends():
        x = rng.integers(0, n_nodes, n_edges)
        hub = rng.random(n_edges) < 0.25
        x[hub] = rng.integers(0, 3, hub.sum())
        return x.astype(np.int32)

    src, dst = ends(), ends()
    fix = src == dst
    dst[fix] = (dst[fix] + 1) % n_nodes
    t = rng.integers(0, t_max, n_edges).astype(np.int64)
    return (
        build_temporal_graph(src, dst, t, n_nodes=n_nodes),
        ref_csr.build_temporal_graph(src, dst, t, n_nodes=n_nodes),
    )


@pytest.mark.parametrize("seed,n_nodes,n_edges,t_max,window", [
    (0, 24, 200, 256, 64),
    (1, 12, 240, 512, 128),
    (2, 40, 300, 128, 32),
])
def test_counts_and_witnesses_equal_the_original(seed, n_nodes, n_edges, t_max, window):
    from repro.core.oracle import GFPReference
    from repro.core.patterns import PATTERN_NAMES, build_pattern

    g_prog, g_ref = _graphs(seed, n_nodes, n_edges, t_max)
    seeds = np.arange(g_ref.n_edges, dtype=np.int32)
    assert tuple(ref_patterns.PATTERN_NAMES) == tuple(PATTERN_NAMES)
    for name in PATTERN_NAMES:
        want = GFPReference(build_pattern(name, window), g_prog)
        have = ref_oracle.GFPReference(ref_patterns.build_pattern(name, window), g_ref)
        assert np.array_equal(have.mine(seeds), want.mine(seeds)), name
        wc, ww = want.mine_witnesses(seeds[:60])
        hc, hw = have.mine_witnesses(seeds[:60])
        assert np.array_equal(hc, wc), name
        assert hw == ww, name

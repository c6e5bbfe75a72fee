"""Portfolio MiningSession contracts: session == per-pattern loop ==
oracle (exactness), strictly fewer kernel invocations than the loop on
the "full" group (the fusion win), canonical-plan dedup, every backend,
and the deprecation shims."""
import numpy as np
import pytest

from repro.api import (
    MiningSession,
    canonical_key,
    featurize,
    mine_features,
    pattern,
    seed,
    var,
)
from repro.core.compiler import CompiledPattern
from repro.core.oracle import GFPReference
from repro.core.patterns import build_pattern, feature_pattern_set
from tests.conftest import random_temporal_graph

W = 96


@pytest.fixture(scope="module")
def dense_graph():
    rng = np.random.default_rng(11)
    return random_temporal_graph(rng, n_nodes=18, n_edges=140, t_max=256)


def test_full_group_fewer_kernel_calls_and_oracle_exact(small_graph):
    """Acceptance: the session mines the "full" feature group with
    STRICTLY fewer kernel invocations than the per-pattern
    CompiledPattern loop, with oracle-identical counts."""
    patterns = feature_pattern_set("full")
    rng = np.random.default_rng(0)
    seeds = rng.choice(small_graph.n_edges, size=200, replace=False).astype(np.int32)

    session = MiningSession(small_graph, window=4096).register(*patterns)
    res = session.mine(seeds=seeds)

    loop_calls = 0
    for j, name in enumerate(patterns):
        cp = CompiledPattern(build_pattern(name, 4096), small_graph)
        np.testing.assert_array_equal(res.counts[:, j], cp.mine(seeds))
        loop_calls += cp.stats["kernel_calls"]
    assert res.stats["kernel_calls"] < loop_calls

    # the seed-local windowed-degree family went through the fused kernel
    assert set(res.fused) == {"fan_in", "fan_out", "deg_in", "deg_out",
                              "cycle2", "stack"}
    for j, name in enumerate(patterns):
        ref = GFPReference(build_pattern(name, 4096), small_graph).mine(seeds)
        np.testing.assert_array_equal(res.counts[:, j], ref)


def test_full_deep_session_vs_loop_vs_oracle(dense_graph):
    """Session exactness on the full_deep group (chained-frontier depth-3+
    patterns included) against both the loop and the enumerator."""
    patterns = feature_pattern_set("full_deep")
    session = MiningSession(dense_graph, window=W).register(*patterns)
    res = session.mine()
    orc = session.mine(backend="oracle")
    np.testing.assert_array_equal(res.counts, orc.counts)
    assert res.columns == orc.columns == tuple(patterns)
    for j, name in enumerate(patterns):
        cp = CompiledPattern(build_pattern(name, W), dense_graph)
        np.testing.assert_array_equal(res.counts[:, j], cp.mine())


def test_backends_agree(dense_graph):
    names = ["fan_in", "cycle3", "scatter_gather", "stack"]
    session = MiningSession(dense_graph, window=W).register(*names)
    base = session.mine()
    for backend in ("oracle", "streaming", "partitioned"):
        got = session.mine(backend=backend, n_parts=3)
        np.testing.assert_array_equal(got.counts, base.counts, err_msg=backend)
    part = session.mine(backend="partitioned", n_parts=3)
    assert part.partition_plan is not None
    assert len(part.per_part_seconds) == 3
    with pytest.raises(ValueError, match="unknown backend"):
        session.mine(backend="nope")


def test_fused_mine_hub_rows_past_top_stride_oracle_exact():
    """The fused seed-local kernel's tiled search on rows of more than
    16,384 transfers (an in-hub and an out-hub), so the index's top
    level picks the block: counts equal the oracle's, and every window
    search takes two row probes."""
    from repro.graph.csr import build_temporal_graph

    rng = np.random.default_rng(5)
    hub = 17_000
    src = np.concatenate(
        [rng.integers(2, 64, hub), np.ones(hub, np.int64), rng.integers(0, 64, 3000)]
    )
    dst = np.concatenate(
        [np.zeros(hub, np.int64), rng.integers(2, 64, hub), rng.integers(0, 64, 3000)]
    )
    dst[src == dst] = (dst[src == dst] + 1) % 64
    t = rng.integers(0, 20_000, len(src))
    g = build_temporal_graph(src, dst, t, n_nodes=64)
    names = ["fan_in", "fan_out", "deg_in", "deg_out"]
    session = MiningSession(g, window=256).register(*names)
    seeds = np.concatenate(
        [
            rng.choice(hub, 24, replace=False),  # into the in-hub
            hub + rng.choice(hub, 24, replace=False),  # out of the out-hub
            np.flatnonzero((src[2 * hub :] <= 1) | (dst[2 * hub :] <= 1))[:24]
            + 2 * hub,  # the hubs on the other side
            2 * hub + rng.choice(3000, 24, replace=False),
        ]
    ).astype(np.int32)
    res = session.mine(seeds=seeds)
    assert set(res.fused) == set(names)
    orc = session.mine(seeds=seeds, backend="oracle")
    np.testing.assert_array_equal(res.counts, orc.counts)
    assert res.counts[:24, 0].min() > 100 and res.counts[24:48, 1].min() > 100
    n_searches = 2 * session._fused.n_units * res.stats["kernel_calls"]
    assert res.stats["search_rounds"] == 2 * n_searches


def test_seed_subset_and_result_accessors(dense_graph):
    session = MiningSession(dense_graph, window=W).register("fan_in", "cycle3")
    seeds = np.array([3, 0, 17, 5], dtype=np.int32)
    res = session.mine(seeds=seeds)
    assert res.counts.shape == (4, 2) and res.n_seeds == 4
    full = session.mine()
    np.testing.assert_array_equal(res.column("cycle3"), full.column("cycle3")[seeds])
    feats = res.as_features()
    assert feats.dtype == np.float32 and feats.shape == (4, 2)
    assert res.totals()["fan_in"] == int(res.column("fan_in").sum())
    assert "cycle3" in res.seconds and "fan_in" in res.seconds


def test_canonical_dedup_shares_one_plan(dense_graph):
    """Two structurally identical patterns (different authoring names)
    canonicalize to one key, compile once, and mine once."""
    clone = (
        pattern("cycle3_alias")
        .for_all("hop", seed.dst.out, skip=[seed.dst, seed.src], after_seed=W)
        .count_edges("back", "hop", seed.src, after_stage="hop", until_seed=W)
        .emit("back")
        .build()
    )
    assert canonical_key(clone) == canonical_key(build_pattern("cycle3", W))
    session = MiningSession(dense_graph, window=W).register("cycle3", clone)
    session.compile()
    assert len(session._compiled) == 1  # one shared compiled plan
    res = session.mine()
    np.testing.assert_array_equal(res.column("cycle3"), res.column("cycle3_alias"))
    # a second mine with only the alias reuses the same plan (no growth)
    session.mine(["cycle3_alias"])
    assert len(session._compiled) == 1


def test_register_name_conflict_rejected(dense_graph):
    session = MiningSession(dense_graph, window=W).register("cycle3")
    session.register("cycle3")  # identical re-registration is a no-op
    other = (
        pattern("cycle3").count_window("cnt", seed.dst.in_, around_seed=W, emit=True)
    )
    with pytest.raises(ValueError, match="different structure"):
        session.register(other)


def test_mine_accepts_builders_and_specs(dense_graph):
    rt3 = (
        pattern("roundtrip3")
        .for_all("w", seed.dst.out, after_seed=W, skip=[seed.src, seed.dst])
        .count_edges("close", "w", seed.src, after_stage="w")
        .emit("close")
    )
    session = MiningSession(dense_graph, window=W)
    res = session.mine([rt3, "fan_in"])
    ref = GFPReference(rt3.build(), dense_graph).mine()
    np.testing.assert_array_equal(res.column("roundtrip3"), ref)


def test_vals_cache_shared_across_patterns(dense_graph):
    """The session-level host requirement cache is one dict reused by all
    compiled plans (windowed-degree arrays computed once per graph)."""
    session = MiningSession(dense_graph, window=W).register(
        "cycle3", "cycle4", "peel_chain"
    )
    session.compile()
    caches = [id(cp._vals_cache) for cp in session._compiled.values()]
    assert len(set(caches)) == 1 and caches[0] == id(session._vals_cache)
    session.mine()
    assert len(session._vals_cache) > 0


def test_graphless_session_streams_but_cannot_mine():
    session = MiningSession(window=W).register("fan_in", "cycle3")
    with pytest.raises(ValueError, match="no graph"):
        session.mine()
    sm = session.streaming()
    assert sm.pattern_names == ("fan_in", "cycle3")
    rng = np.random.default_rng(5)
    g = random_temporal_graph(rng, n_nodes=12, n_edges=60, t_max=200)
    sm.ingest(g.src, g.dst, g.t)
    want = CompiledPattern(build_pattern("cycle3", W), sm.graph).mine()
    np.testing.assert_array_equal(sm.counts["cycle3"], want)


def test_deprecation_shims_warn_and_match(dense_graph):
    """Old repro.core.features entry points warn but return identical
    results to the session-backed repro.api successors."""
    from repro.core.features import featurize as old_featurize
    from repro.core.features import mine_features as old_mine_features

    names = ["fan_in", "cycle3"]
    with pytest.warns(DeprecationWarning, match="mine_features is deprecated"):
        old = old_mine_features(dense_graph, W, names)
    new = mine_features(dense_graph, W, names)
    np.testing.assert_array_equal(old, new)
    for j, name in enumerate(names):
        ref = GFPReference(build_pattern(name, W), dense_graph).mine()
        np.testing.assert_array_equal(old[:, j].astype(np.int64), ref)

    with pytest.warns(DeprecationWarning, match="featurize is deprecated"):
        old_x, old_cols = old_featurize(dense_graph, W, names)
    new_x, new_cols = featurize(dense_graph, W, names)
    assert old_cols == new_cols == ("src", "dst", "amount", "fan_in", "cycle3")
    np.testing.assert_array_equal(old_x, new_x)


def test_featurize_group_name(dense_graph):
    x, cols = featurize(dense_graph, W, "fan")
    assert cols == ("src", "dst", "amount", "fan_in", "fan_out")
    assert x.shape == (dense_graph.n_edges, 5)


def test_subset_mine_charges_only_requested_units(dense_graph):
    """Mining one fused pattern must not compute (or get charged for)
    the other registered seed-local patterns' count units."""
    session = MiningSession(dense_graph, window=W).register(
        "fan_in", "fan_out", "deg_in", "deg_out", "cycle2", "stack"
    )
    one = session.mine(["fan_in"])  # fan_in needs exactly 1 count unit
    all_ = session.mine()  # the six patterns span 7 deduped units
    assert one.stats["padded_elements"] * 7 == all_.stats["padded_elements"]
    np.testing.assert_array_equal(one.column("fan_in"), all_.column("fan_in"))


def test_single_host_sync_per_backend_invocation(dense_graph):
    """The async executor regime, locked in: a full-portfolio mine blocks
    on the device exactly once per backend invocation — once for the
    fused seed-local pass and once per unique compiled plan — never once
    per kernel call, chunk, or sweep step."""
    patterns = feature_pattern_set("full_deep")
    session = MiningSession(dense_graph, window=W).register(*patterns)
    res = session.mine()
    n_invocations = len(session._compiled) + (1 if res.fused else 0)
    assert res.stats["host_syncs"] == n_invocations
    assert res.stats["kernel_calls"] > n_invocations  # syncs ≪ launches
    # repeated mines replay cached bucket schedules (no numpy regrouping)
    res2 = session.mine()
    assert res2.stats["host_syncs"] == n_invocations
    assert res2.stats["schedule_hits"] == len(session._compiled)
    np.testing.assert_array_equal(res.counts, res2.counts)


def test_session_kernel_backend_pallas(dense_graph):
    """kernel_backend="pallas" routes pw compare cubes through the Pallas
    intersect op (interpret mode on CPU) with identical counts."""
    names = ["cycle3", "cycle4", "scatter_gather", "peel_chain"]
    base = MiningSession(dense_graph, window=W).register(*names).mine()
    got = (
        MiningSession(dense_graph, window=W, kernel_backend="pallas")
        .register(*names)
        .mine()
    )
    np.testing.assert_array_equal(got.counts, base.counts)
    with pytest.raises(ValueError, match="kernel backend"):
        MiningSession(dense_graph, window=W, kernel_backend="cuda").register(
            "cycle3"
        ).compile()


def test_plan_text_shows_fusion_and_sharing(small_graph):
    session = MiningSession(small_graph, window=4096).register(
        *feature_pattern_set("full")
    )
    txt = session.plan_text()
    assert "fused seed-local kernel" in txt
    assert "fan_in" in txt and "compiled cycle3" in txt

#!/usr/bin/env python3
"""Chip smoke test: BlazingAML's three user paths on one TPU at the
published size of IBM AML HI-Small (~5.08M transactions).

Phases, in one process (a chip belongs to one process at a time):

1. device  — require a TPU; print its kind and count.  No CPU fallback.
2. load    — generate HI-Small from ``--seed`` at the published scale,
             build the session's device graph mirror, print peak bytes.
3. batch   — mine the ``full_deep`` portfolio over a seeded sample of
             8,192 seeds with the XLA and the Pallas (Mosaic) lowering:
             counts identical, and equal to the oracle on a 128-seed
             subsample.  Both samples hold the edges of a few planted
             instances of each typology, so every pattern has matching
             oracle seeds.
4. witness — ``mine(seeds, witnesses=2)`` on the oracle subsample ==
             the oracle's top-2 tuples.
5. triage  — 8 time-ordered microbatches of 4,096 transactions through
             a ``TriageServer``: no submit error, incremental == batch.

``--chips 4`` runs only the sharded mine over four chips, compared
bit-for-bit with the one-device mine.  Any failed check raises; the last
line of stdout is the JSON device record, printed only on success.

  python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# IBM AML HI-Small: 515,088 accounts, 5,078,345 transactions.  The
# preset is 1,600 accounts x 18,000 background transfers (+1.2% illicit),
# so scale 282 gives 451,200 accounts and ~5.14M transactions.
HI_SMALL_SCALE = 282.0
WINDOW = 4096
N_SEEDS = 8192
N_ORACLE = 128  # the oracle is exponential on deep hub seeds
PLANTED_PER_GROUP = 2  # planted instances per typology group, in both samples
MIN_MATCHED = 2  # oracle seeds with a nonzero count, per pattern
WITNESS_K = 2
TRIAGE_TICKS = 8
TRIAGE_BATCH = 4096
SHARD_CHIPS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check raises (never an ``assert``: ``-O`` strips those)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform {info['platform']!r}"
        )
    if info["count"] < chips:
        raise SystemExit(
            f"chip_smoke: needs {chips} chips, found {info['count']}"
        )


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def planted_seeds(ds, per_group: int, rng) -> np.ndarray:
    """Edge ids of a seeded pick of ``per_group`` planted typology
    instances per group, all of each picked instance's edges.  Random
    seeds rarely hit the deep typologies, so these give every pattern of
    the portfolio matching seeds.  Cycles are grouped by length, and only
    those whose hops are strictly time-ordered (the planter shuffles some
    as camouflage) qualify: the cycle patterns count ordered cycles.
    Fans are left out: every seed matches the fan and degree patterns."""
    from repro.data.synth_aml import planted_instances

    t = ds.graph.t
    groups: dict = {}
    for inst in planted_instances(ds):
        kind, eids = inst["kind"], inst["eids"]
        if kind in ("fan_in", "fan_out"):
            continue
        if kind == "cycle":
            if not np.all(np.diff(t[eids]) > 0):
                continue
            kind = f"cycle{len(eids)}"
        groups.setdefault(kind, []).append(eids)
    picks = []
    for kind in sorted(groups):
        insts = groups[kind]
        for i in rng.choice(len(insts), size=min(per_group, len(insts)), replace=False):
            picks.append(insts[i])
    return np.unique(np.concatenate(picks)).astype(np.int32)


def _fill(rng, pool: np.ndarray, keep: np.ndarray, size: int) -> np.ndarray:
    """``keep`` plus a seeded draw from the rest of ``pool``, ``size`` in all."""
    rest = np.setdiff1d(pool, keep)
    extra = rng.choice(rest, size=max(0, min(size, len(pool)) - len(keep)), replace=False)
    return np.sort(np.concatenate([keep, extra]).astype(np.int32))


def phase_load(seed: int, scale: float, n_seeds: int, n_oracle: int):
    """Generate the graph and the seeded seed sample + oracle subsample;
    both hold the same planted-instance edges."""
    from repro.data.synth_aml import generate_aml_dataset

    t0 = time.perf_counter()
    ds = generate_aml_dataset("HI-Small", seed=seed, scale=scale, window=WINDOW)
    g = ds.graph
    log(
        f"load: HI-Small scale={scale} accounts={g.n_nodes} "
        f"edges={g.n_edges} generate_s={time.perf_counter() - t0:.3f}"
    )
    rng = np.random.default_rng(seed)
    planted = planted_seeds(ds, PLANTED_PER_GROUP, rng)
    check(len(planted) <= n_oracle, f"{len(planted)} planted edges > {n_oracle}")
    all_eids = np.arange(g.n_edges, dtype=np.int32)
    seeds = _fill(rng, all_eids, planted, n_seeds)
    oracle_seeds = _fill(rng, seeds, planted, n_oracle)
    log(f"load: {len(planted)} planted-instance edges in both samples")
    return g, seeds, oracle_seeds


def portfolio():
    from repro.core.patterns import feature_pattern_set

    return feature_pattern_set("full_deep")


def oracle_reference(g, names, seeds, k: int) -> dict:
    """Per pattern, the oracle's counts and first-k witness tuples."""
    from repro.core.oracle import GFPReference
    from repro.core.patterns import build_pattern

    t0 = time.perf_counter()
    ref = {
        n: GFPReference(build_pattern(n, WINDOW), g).mine_witnesses(seeds, k=k)
        for n in names
    }
    log(f"oracle: {len(seeds)} seeds x {len(names)} patterns "
        f"host_s={time.perf_counter() - t0:.3f}")
    return ref


def new_session(g, names, kernel_backend: str):
    """A portfolio session with its device graph mirror built."""
    from repro.api import MiningSession

    session = MiningSession(g, window=WINDOW, kernel_backend=kernel_backend)
    return session.register(*names).compile()


def _kernel_traces() -> dict:
    from repro.obs import metrics as obs_metrics

    reg = obs_metrics.get_registry()
    return {
        mode: reg.counter(
            "repro_kernel_traces_total",
            labels={"kernel": "intersect_count", "mode": mode},
        ).value
        for mode in ("mosaic", "interpret")
    }


def check_mosaic_lowering() -> None:
    """The Pallas op, jitted on the default device at the widest ladder
    pair, compiles to a Mosaic custom call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.intersect_count import intersect_count

    i = jax.ShapeDtypeStruct((256, 1024), jnp.int32)
    r = jax.ShapeDtypeStruct((256,), jnp.int32)
    text = (
        jax.jit(lambda *a: intersect_count(*a, ordered=True))
        .lower(i, i, i, i, r, r, r, r)
        .compile()
        .as_text()
    )
    check("tpu_custom_call" in text, "intersect_count did not lower to Mosaic")


def _timed_mine(session, seeds, label: str):
    t0 = time.perf_counter()
    cold = session.mine(seeds=seeds)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = session.mine(seeds=seeds)
    warm_s = time.perf_counter() - t0
    check(np.array_equal(warm.counts, cold.counts), f"{label}: warm != cold")
    log(f"batch[{label}]: cold_s={cold_s:.3f} (compile + first mine)")
    log(f"batch[{label}]: warm_s={warm_s:.3f} seeds={len(seeds)} "
        f"kernel_calls={warm.stats['kernel_calls']} "
        f"host_syncs={warm.stats['host_syncs']} "
        f"jit_cache_entries={cold.stats['jit_cache_entries']}")
    return cold


def phase_batch(session, g, names, seeds, oracle_seeds, ref, lowering: str):
    """XLA mine == Pallas mine == oracle.  ``lowering`` is the mode the
    Pallas op must have been traced in: ``mosaic`` on a TPU."""
    xla = _timed_mine(session, seeds, "xla")
    before = _kernel_traces()
    t0 = time.perf_counter()
    pallas_session = new_session(g, names, "pallas")
    log(f"batch[pallas]: session_s={time.perf_counter() - t0:.3f}")
    pallas = _timed_mine(pallas_session, seeds, "pallas")
    traced = {m: v - before[m] for m, v in _kernel_traces().items()}
    log(f"batch[pallas]: intersect_count traces {traced}")
    other = "interpret" if lowering == "mosaic" else "mosaic"
    check(traced[lowering] > 0 and traced[other] == 0, f"lowering {traced}")
    if lowering == "mosaic":
        check_mosaic_lowering()
    check(np.array_equal(xla.counts, pallas.counts), "xla != pallas counts")
    rows = np.searchsorted(seeds, oracle_seeds)
    matched = {}
    for j, n in enumerate(names):
        want = ref[n][0]
        got = xla.counts[rows, j]
        check(np.array_equal(got, want), f"{n}: compiled != oracle")
        matched[n] = int((want > 0).sum())
    log(f"batch: xla == pallas on {len(seeds)} seeds; == oracle on "
        f"{len(oracle_seeds)} seeds x {len(names)} patterns")
    log(f"batch: oracle seeds with a match, per pattern {matched}")
    log(f"batch: seeds with a match, per pattern "
        f"{dict(zip(names, (xla.counts > 0).sum(axis=0).tolist()))}")
    short = {n: c for n, c in matched.items() if c < MIN_MATCHED}
    check(not short, f"patterns with under {MIN_MATCHED} matched oracle seeds: {short}")


def phase_witnesses(session, names, oracle_seeds, ref, k: int) -> int:
    """Witness-mine the oracle seeds, pattern by pattern, against the
    oracle's first-k tuples; returns the hub branch items mined."""
    items = 0
    for name in names:
        t0 = time.perf_counter()
        res = session.mine([name], seeds=oracle_seeds, witnesses=k)
        dt = time.perf_counter() - t0
        counts, wits = ref[name]
        check(np.array_equal(res.counts[:, 0], counts), f"{name}: witness counts")
        w = res.witnesses[name]
        for i in range(len(oracle_seeds)):
            check(w.tuples(i) == wits[i][:k], f"{name}: witness tuples, seed {i}")
        items += res.stats["branch_items"]
        log(f"witness[{name}]: mine_s={dt:.3f} "
            f"padded_elements={res.stats['padded_elements']} "
            f"branch_items={res.stats['branch_items']} "
            f"host_syncs={res.stats['host_syncs']}")
    log(f"witness: k={k} on {len(oracle_seeds)} seeds x {len(names)} "
        f"patterns == oracle; hub branch items {items}")
    return items


def phase_triage(g, ticks: int, batch: int, k: int):
    """Time-ordered microbatches through a TriageServer; no submit may
    fail, and incremental counts must equal a batch recompute."""
    from repro.core.compiler import CompiledPattern
    from repro.core.patterns import build_pattern
    from repro.graph.csr import build_temporal_graph
    from repro.launch.serve import (
        DEFAULT_PORTFOLIO,
        SubmitError,
        TriageServer,
        make_feed,
    )
    from repro.stream.service import DetectionService

    svc = DetectionService(
        list(DEFAULT_PORTFOLIO),
        window=WINDOW,
        thresholds=dict(DEFAULT_PORTFOLIO),
        witnesses=k,
    )
    server = TriageServer(svc)
    feed = make_feed(g, batch)[:ticks]
    walls = []
    for i, mb in enumerate(feed):
        t0 = time.perf_counter()
        out = server.submit(*mb)
        walls.append(time.perf_counter() - t0)
        if isinstance(out, SubmitError):
            raise RuntimeError(f"triage tick {i} failed: {out}")
    summ = server.summary()
    server.close()
    check(summ["errors"] == 0 and summ["ticks"] == len(feed), f"triage {summ}")
    log(f"triage: cold_s={walls[0]:.3f} (first tick, compiles)")
    log(f"triage: warm_s={sum(walls[1:]):.3f} over {len(walls) - 1} ticks; "
        f"alerts={summ['alerts']} evidence_hops={summ['evidence_hop_tuples']} "
        f"errors={summ['errors']}")
    cat = [np.concatenate([mb[c] for mb in feed]) for c in range(4)]
    full = build_temporal_graph(*cat)
    for name in DEFAULT_PORTFOLIO:
        want = CompiledPattern(build_pattern(name, WINDOW), full).mine()
        got = svc.pattern_counts(name)
        check(np.array_equal(got, want), f"{name}: incremental != batch")
    log(f"triage: incremental == batch recompute over {full.n_edges} edges "
        f"x {len(DEFAULT_PORTFOLIO)} patterns")


def phase_sharded(session, seeds, n_parts: int):
    """Sharded mine over ``n_parts`` devices == the one-device mine."""
    import jax
    t0 = time.perf_counter()
    one = session.mine(seeds=seeds)
    log(f"sharded: one-device mine_s={time.perf_counter() - t0:.3f}")
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        res = session.mine(seeds=seeds, backend="sharded", n_parts=n_parts)
        log(f"sharded[{label}]: mine_s={time.perf_counter() - t0:.3f} "
            f"gather={res.gather_mode} host_syncs={res.stats['host_syncs']} "
            f"devices={list(res.shard_devices)}")
    check(np.array_equal(res.counts, one.counts), "sharded != one-device")
    want = {str(d) for d in jax.devices()[:n_parts]}
    check(
        set(res.shard_devices) == want and len(want) == n_parts,
        f"shard devices {res.shard_devices}",
    )
    check(res.gather_mode == "collective", f"gather {res.gather_mode}")
    check(res.stats["host_syncs"] == 1, f"host_syncs {res.stats['host_syncs']}")
    log(f"sharded: {n_parts} devices bit-exact vs one-device on "
        f"{len(seeds)} seeds")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, SHARD_CHIPS),
        help=f"{SHARD_CHIPS}: run only the sharded mine over {SHARD_CHIPS} chips",
    )
    args = ap.parse_args(argv)

    info = device_info()
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    require_tpu(info, args.chips)

    from repro.launch.jax_cache import enable_compile_cache

    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} ({n_cached} entries at start)")
    names = portfolio()
    g, seeds, oracle_seeds = phase_load(
        args.seed, HI_SMALL_SCALE, N_SEEDS, N_ORACLE
    )
    t0 = time.perf_counter()
    session = new_session(g, names, "xla")
    log(f"load: device graph mirror built in {time.perf_counter() - t0:.3f}s; "
        f"peak_bytes_in_use={_peak_bytes()}")

    if args.chips == SHARD_CHIPS:
        phase_sharded(session, seeds, SHARD_CHIPS)
    else:
        ref = oracle_reference(g, names, oracle_seeds, WITNESS_K)
        phase_batch(session, g, names, seeds, oracle_seeds, ref, "mosaic")
        phase_witnesses(session, names, oracle_seeds, ref, WITNESS_K)
        phase_triage(g, TRIAGE_TICKS, TRIAGE_BATCH, WITNESS_K)
        log(f"peak_bytes_in_use={_peak_bytes()}")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

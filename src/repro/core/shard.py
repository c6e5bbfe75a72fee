"""Multi-device sharded mining executor (the paper's near-linear
scaling claim, realized over a JAX device set).

Pattern counts are per-seed-edge, so mining is embarrassingly
data-parallel once the partitioner (:mod:`repro.graph.partition`) has
balanced expected cost: each partition of the dense ``(P, L)`` edge-id
matrix is an independent mine.  This module turns that independence into
actual multi-device execution with **explicit device placement** (the
``device_put(x, device)`` layout — per-partition bucket schedules are
ragged, so a ``shard_map`` over uniform per-device shapes would force
worst-case padding on every shard; committed inputs give the same
device-parallel dispatch without it):

* **One graph replica per device** (:class:`ShardContext`) — the
  :class:`~repro.graph.csr.DeviceGraph` pytree is ``device_put`` onto
  each mining device once and cached for the session's lifetime;
  partitions are assigned round-robin, so ``n_parts`` may exceed the
  device count (extra partitions time-share a device) and on a single
  device the executor degrades to exactly the resident async behavior.
* **Overlapped dispatch, one thread per device** — :func:`run_sharded`
  fans partitions out to a per-device dispatch pool: shard ``k``'s
  host-side schedule build (``CompiledPattern.schedule_for``) and
  staging overlap with device execution on already-dispatched shards,
  instead of the old sequential loop where every shard's Python-side
  work serialized in front of every later shard's launches.  The
  shared schedule LRU, requirement cache, and jit kernel caches are
  lock-protected for exactly this concurrency (see
  ``CompiledPattern``); per-device launch counts are cut further by
  chunk coalescing (:func:`repro.core.executor.coalesce_groups`).
* **Device-collective gather, ONE host sync** — every partition's chunk
  launches scatter-add into an accumulator resident on its own device.
  When the partitions map 1:1 onto distinct devices, each shard's
  ragged outputs are scattered device-side into full-length rows
  (:func:`_place_rows` via the partition plan's ``positions``), the
  per-device rows are assembled into ONE mesh-sharded global array, and
  a jitted axis-0 sum reduces them with a device collective — the one
  blocking transfer of the whole mine is the fetch of the
  *already-reduced* result.  Time-shared runs (``n_parts`` exceeding
  the device count) fall back to the host-side :func:`gather`, which is
  still a single ``device_get`` (``stats["host_syncs"] == 1`` either
  way, fused seed-local pass included).

Per-shard observability: :func:`run_sharded` returns a
:class:`ShardRun` carrying one executor stat dict, dispatch wall time,
and device name per shard, plus ``dispatch_wall_s`` — the true
overlapped dispatch window.  Per-shard walls are measured on concurrent
threads, so they do NOT sum to the mine wall; their sum divided by
``dispatch_wall_s`` is the dispatch overlap ratio reported by
``benchmarks/bench_shard.py``.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import executor
from repro.distributed.fault_tolerance import Heartbeat, StragglerMonitor
from repro.graph.partition import PartitionPlan
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

__all__ = [
    "ShardContext",
    "ShardRun",
    "mining_devices",
    "run_sharded",
    "gather",
    "collective_gather",
]


def mining_devices(n: Optional[int] = None) -> List:
    """The devices a sharded mine runs over: the first ``n`` JAX devices
    (all of them when ``n`` is None or exceeds the platform count).
    Under ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` the CPU
    platform presents K virtual devices, which is how the multi-device
    path is exercised on a single-CPU container."""
    devs = jax.devices()
    if n is None or n >= len(devs):
        return list(devs)
    return list(devs[: max(1, n)])


class ShardContext:
    """Per-device graph replicas + dispatch pool for one resident
    :class:`DeviceGraph`.

    Replication is lazy and cached: a device's replica is built on its
    first partition and reused for every later mine, so steady-state
    sharded mines move only staging buffers.  On the device that already
    holds the source mirror, ``device_put`` is a no-op aliasing the
    existing buffers.  The dispatch pool (one worker per device) is
    lazy too and lives for the context's lifetime — concurrent
    ``replica`` misses from those workers are double-check locked.
    """

    def __init__(
        self,
        dg,
        devices: Optional[Sequence] = None,
        heartbeat_dir: Optional[str] = None,
    ):
        self.dg = dg
        self.devices = (
            list(devices) if devices is not None else mining_devices()
        )
        if not self.devices:
            raise ValueError("no devices available for sharded mining")
        self._replicas: Dict = {}
        self._lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        # per-device worker liveness: every dispatch beats in-memory
        # (last_beat) and — when heartbeat_dir is set — through the
        # file-backed distributed.fault_tolerance.Heartbeat tracker, the
        # same liveness surface the training launcher uses
        self.heartbeat_dir = heartbeat_dir
        self.last_beat: Dict[str, float] = {}
        self.beat_steps: Dict[str, int] = {}
        self._heartbeats: Dict = {}
        self.stragglers = StragglerMonitor()

    def beat(self, device, shard: int) -> None:
        """Record liveness of ``device``'s dispatch worker at ``shard``.
        Every beat also lands as a pair of `repro.obs` gauge samples
        (last-beat instant + cumulative beats, labeled by device), so a
        scrape of the metrics registry sees worker liveness without
        touching ``MiningResult.worker_liveness``."""
        key = str(device)
        self.last_beat[key] = time.time()
        self.beat_steps[key] = self.beat_steps.get(key, 0) + 1
        reg = obs_metrics.get_registry()
        reg.gauge(
            "repro_shard_worker_last_beat_seconds",
            help="unix time of the device dispatch worker's last beat",
            labels={"device": key},
        ).set(self.last_beat[key])
        reg.gauge(
            "repro_shard_worker_beats",
            help="cumulative dispatch-worker liveness beats",
            labels={"device": key},
        ).set(self.beat_steps[key])
        if self.heartbeat_dir is not None:
            hb = self._heartbeats.get(key)
            if hb is None:
                with self._lock:
                    hb = self._heartbeats.get(key)
                    if hb is None:
                        hb = Heartbeat(self.heartbeat_dir, key)
                        self._heartbeats[key] = hb
            hb.beat(shard)

    def alive_devices(self) -> Optional[List[str]]:
        """File-backed liveness view (None without a heartbeat_dir)."""
        if self.heartbeat_dir is None or not self._heartbeats:
            return None
        return next(iter(self._heartbeats.values())).alive_hosts()

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def device_for(self, p: int):
        """Round-robin partition -> device assignment."""
        return self.devices[p % len(self.devices)]

    def replica(self, device):
        """The graph replica resident on ``device`` (built on first use;
        safe to race from concurrent dispatch workers)."""
        r = self._replicas.get(device)
        if r is None:
            with self._lock:
                r = self._replicas.get(device)
                if r is None:
                    r = jax.device_put(self.dg, device)
                    self._replicas[device] = r
        return r

    def pool(self) -> ThreadPoolExecutor:
        """The dispatch pool (lazy): one worker per device, capped at the
        host CPU count — schedule build + staging is CPU-bound Python, so
        workers beyond the physical cores only add GIL contention (on a
        single-core host dispatch degrades to serialized, contention-free
        submission; device execution still overlaps via async dispatch)."""
        if self._pool is None:
            with self._lock:
                if self._pool is None:
                    try:
                        n_cpus = len(os.sched_getaffinity(0))
                    except AttributeError:  # non-Linux
                        n_cpus = os.cpu_count() or 1
                    self._pool = ThreadPoolExecutor(
                        max_workers=max(1, min(len(self.devices), n_cpus)),
                        thread_name_prefix="shard-dispatch",
                    )
        return self._pool


@dataclasses.dataclass
class ShardRun:
    """One sharded dispatch+gather, with per-shard observability.

    ``host_outs`` is gather-mode dependent: the per-shard list of host
    output pytrees under ``gather_mode == "host"``, or the single
    already-reduced output pytree (full-length rows, every shard summed
    in) under ``gather_mode == "collective"``.  ``shard_walls`` are
    per-shard dispatch walls measured on concurrent worker threads —
    they overlap and do NOT sum to ``dispatch_wall_s``, the true
    wall-clock window of the whole overlapped dispatch phase.
    """

    host_outs: object
    shard_stats: List[Dict[str, int]]
    shard_walls: List[float]
    shard_devices: List[str]
    dispatch_wall_s: float
    gather_mode: str  # "collective" | "host"
    # per-device worker liveness for this run: last heartbeat instant,
    # cumulative beats, per-device wall medians, and the devices the
    # StragglerMonitor flags slower than threshold x median
    worker_liveness: Optional[dict] = None


def _place_rows_impl(vec, rows, n_total):
    # scatter one shard's ragged per-seed outputs into full-length rows:
    # slot i of the shard holds input position rows[i].  Positions are a
    # bijection over input indices (duplicated seed *ids* occupy distinct
    # positions), so rows never collide within or across shards and the
    # cross-shard axis-0 sum of placed rows is exact reassembly.  vec may
    # carry ladder padding past len(rows) (the fused unit matrix); the
    # leading slice drops it.
    out = jnp.zeros((n_total,) + vec.shape[1:], vec.dtype)
    return out.at[rows].add(vec[: rows.shape[0]], mode="drop")


_place_rows = jax.jit(_place_rows_impl, static_argnums=2)


def _sum_shards(x):
    return x.sum(axis=0)


_sum_shards_jit = jax.jit(_sum_shards)


def _flatten_outs(leaves):
    # one shard's output leaves raveled into a single (1, L) row so the
    # whole cross-shard reduction is ONE collective over ONE global
    # array, not one per output key (per-key make_array + reduce
    # dispatch overhead dominates small mines)
    return jnp.concatenate([x.reshape(-1) for x in leaves])[None]


_flatten_outs_jit = jax.jit(_flatten_outs)


def gather(outs, stats: Dict[str, int], mode: str = "host"):
    """One blocking ``device_get`` over a whole pytree of finished device
    outputs — the single host sync of whatever dispatched them.

    Used as the host-side gather fallback of a sharded mine (time-shared
    ``n_parts > n_devices``; the pytree then spans all mining devices)
    and by the streaming service's portfolio tick, which fetches EVERY
    pattern's device-resident count vector in this one call
    (``mode="portfolio"`` tags the span so trace tooling can tell the
    two apart)."""
    with obs_trace.phase("fetch", stats, mode=mode):
        with obs_trace.phase("wait", stats):
            host = jax.device_get(outs)
        stats["host_syncs"] += 1
        stats["bytes_d2h"] += int(
            sum(a.nbytes for a in jax.tree_util.tree_leaves(host))
        )
    return host


def collective_gather(placed, devices, stats: Dict[str, int]):
    """Device-collective gather: reduce per-shard placed rows on device,
    then fetch the finished result with ONE blocking transfer.

    ``placed[p]`` is shard ``p``'s output dict with every leaf already
    scattered into full-length rows on ``devices[p]`` (disjoint rows per
    shard).  Each shard's leaves are raveled device-side into one flat
    row, the per-device rows become ONE mesh-sharded global array
    (:func:`jax.make_array_from_single_device_arrays` over the 1-D
    shard mesh), and a single jitted axis-0 sum reduces every output of
    every pattern at once (a device collective — AllReduce — on a real
    mesh).  The single ``device_get`` of the reduced flat vector is the
    mine's one host sync — ``bytes_d2h`` counts only the reduced
    result, not per-shard copies — and the host-side split/reshape into
    the output dict is pure numpy views.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_shard_mesh  # lazy: no import cycle

    with obs_trace.phase(
        "fetch", stats, mode="collective", n_shards=len(placed)
    ):
        keys = list(placed[0])
        shapes = [placed[0][k].shape for k in keys]
        dtypes = [placed[0][k].dtype for k in keys]
        flat = [
            _flatten_outs_jit([p_out[k] for k in keys]) for p_out in placed
        ]  # one (1, L) row per shard, resident on that shard's device
        mesh = make_shard_mesh(devices)
        sharding = NamedSharding(mesh, PartitionSpec("shard"))
        arr = jax.make_array_from_single_device_arrays(
            (len(placed),) + flat[0].shape[1:], sharding, flat
        )
        reduced = _sum_shards_jit(arr)
        with obs_trace.phase("wait", stats):
            host_flat = jax.device_get(reduced)  # THE host sync
        stats["host_syncs"] += 1
        stats["bytes_d2h"] += int(host_flat.nbytes)
    host = {}
    off = 0
    for k, shape, dtype in zip(keys, shapes, dtypes):
        n = int(np.prod(shape))
        host[k] = host_flat[off : off + n].reshape(shape).astype(dtype, copy=False)
        off += n
    return host


def run_sharded(
    plan: PartitionPlan,
    launch: Callable,
    ctx: ShardContext,
    stats: Dict[str, int],
    collective: Optional[bool] = None,
) -> ShardRun:
    """Dispatch every partition of ``plan`` concurrently and gather once.

    ``launch(p, ids, dg, device, shard_stats)`` must dispatch partition
    ``p``'s work (seed edge ids ``ids``) onto ``device`` using the graph
    replica ``dg`` and return a dict of **device-resident** arrays — it
    must not block on the device (no ``np.asarray`` / ``device_get``;
    use ``CompiledPattern.mine_async`` and friends).  It runs on a
    dispatch-pool worker thread, so everything it touches that is shared
    across shards (schedule LRU, requirement cache, jit caches) must be
    thread-safe — the compiled-plan side already is.

    Dispatch is one worker per *device*: partition ``p`` goes to device
    ``p % n_devices``, and each device's partitions run in submission
    order on its worker (they time-share that device's queue anyway),
    while different devices' schedule builds and launches overlap.  A
    single in-use device skips the pool entirely (inline dispatch,
    exactly the resident async behavior).

    Gather: device-collective when every partition has its own device
    (``n_parts <= n_devices``; per-shard outputs are scattered into
    full-length rows on-device first — see :func:`collective_gather`),
    host-side :func:`gather` otherwise.  ``collective`` forces the
    choice (tests); both charge exactly ONE ``host_syncs``.

    Aggregates every shard's counters into ``stats`` and returns a
    :class:`ShardRun` (gather-mode-dependent ``host_outs``, per-shard
    stats/walls/devices, and the overlapped ``dispatch_wall_s``).
    """
    n_parts = plan.n_parts
    n_total = int(plan.valid.sum())
    if collective is None:
        # the collective path needs a 1:1 partition->device map (the mesh
        # places one shard's rows per device); empty mines skip straight
        # to the trivial host gather
        collective = n_parts <= ctx.n_devices and n_total > 0
    shard_stats = [executor.new_stats() for _ in range(n_parts)]
    shard_walls = [0.0] * n_parts
    shard_devices = [""] * n_parts
    outs: List = [None] * n_parts

    def dispatch_one(p: int) -> None:
        ids = plan.edge_ids[p][plan.valid[p]]
        device = ctx.device_for(p)
        st = shard_stats[p]
        ctx.beat(device, p)  # liveness: worker picked up shard p
        t0 = time.perf_counter()
        # the span runs ON the worker thread: each device's lane in the
        # exported trace shows its shards back to back, and cross-device
        # overlap is the horizontal overlap of the lanes.  It times
        # DISPATCH (schedule build + staging + async launches), not
        # device completion — see the repro.obs.trace asynchrony caveat.
        with obs_trace.span(
            f"dispatch:shard{p}",
            stats=st,
            device=str(device),
            n_seeds=len(ids),
        ):
            out = launch(p, ids, ctx.replica(device), device, st)
        if collective:
            # scatter this shard's ragged outputs into full-length rows
            # on its own device, still without blocking — the reduction
            # consumes them in place
            rows = np.ascontiguousarray(plan.positions[p][plan.valid[p]])
            if rows.size:
                rows_dev = jax.device_put(rows, device)
                st["bytes_h2d"] += int(rows.nbytes)
                out = {
                    k: _place_rows(v, rows_dev, n_total)
                    for k, v in out.items()
                }
            else:
                # empty shard: build the zero rows with an explicit
                # device_put — jit output placement ignores zero-sized
                # committed inputs and would land these on device 0,
                # breaking the mesh's one-array-per-device requirement
                out = {
                    k: jax.device_put(
                        jnp.zeros((n_total,) + v.shape[1:], v.dtype), device
                    )
                    for k, v in out.items()
                }
        outs[p] = out
        shard_walls[p] = time.perf_counter() - t0
        shard_devices[p] = str(device)
        ctx.beat(device, p)  # liveness: shard p dispatched
        ctx.stragglers.record(str(device), shard_walls[p])

    n_used = min(n_parts, ctx.n_devices)
    t0 = time.perf_counter()
    if n_used <= 1:
        for p in range(n_parts):
            dispatch_one(p)
    else:

        def worker(d: int) -> None:
            for p in range(d, n_parts, ctx.n_devices):
                dispatch_one(p)

        pool = ctx.pool()
        futures = [pool.submit(worker, d) for d in range(n_used)]
        for f in futures:
            f.result()  # propagate worker exceptions
    dispatch_wall = time.perf_counter() - t0

    if collective:
        devices = [ctx.device_for(p) for p in range(n_parts)]
        host_outs = collective_gather(outs, devices, stats)
        mode = "collective"
    else:
        host_outs = gather(outs, stats)
        mode = "host"
    for st in shard_stats:
        for k in executor.STAT_KEYS:
            if k in ("host_syncs", "bytes_d2h"):
                continue  # per-shard launches never sync; the gather paid
            stats[k] += st[k]  # all deltas (jit_cache_entries included)
    used = sorted({d for d in shard_devices if d})
    liveness = {
        "last_beat": {d: ctx.last_beat.get(d) for d in used},
        "beats": {d: ctx.beat_steps.get(d, 0) for d in used},
        "wall_medians": {
            d: m for d, m in ctx.stragglers.medians().items() if d in used
        },
        "stragglers": [d for d in ctx.stragglers.stragglers() if d in used],
        "alive": ctx.alive_devices(),
    }
    return ShardRun(
        host_outs=host_outs,
        shard_stats=shard_stats,
        shard_walls=shard_walls,
        shard_devices=shard_devices,
        dispatch_wall_s=dispatch_wall,
        gather_mode=mode,
        worker_liveness=liveness,
    )

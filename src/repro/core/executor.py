"""Device-resident async bucket executor (the paper's "runs as fast as
the hardware allows" regime).

The compiler's bucket schedule used to round-trip to the host on every
kernel call: each sweep step was its own launch, results were pulled back
with ``np.asarray`` (a blocking device sync) and accumulated in numpy.
This module is the shared execution engine that keeps the whole bucket
schedule on-device:

* **Staging once per bucket group** — the padded ``src``/``dst``/``ts``/
  frontier staging arrays for a group are built in ONE padded host buffer
  (padding only ever lands in the tail chunk) and moved with a single
  :func:`jax.device_put`; per-chunk inputs are device-side slices, so the
  inner loop never allocates or transfers.
* **Async dispatch + device accumulation** — every kernel launch returns
  a device array that is scatter-added (``at[].add`` with out-of-bounds
  drop semantics, replacing the old ``np.add.at``) into a device-resident
  per-seed output vector.  Nothing blocks: dispatch runs ahead of the
  device and the ONLY host sync of a mine call is the final
  :func:`fetch` of the finished counts.
* **Bounded JIT shapes** — chunk widths come from a power-of-two ladder
  (:func:`chunk_widths`): the full-chunk width is rounded *down* to a
  power of two and tails are rounded *up* with a floor of
  ``MIN_CHUNK``, so a bucket group can only ever trace
  ``log2(bchunk / MIN_CHUNK) + 1`` distinct batch widths instead of one
  per distinct tail length.

Observability counters (reported through ``CompiledPattern.stats`` /
``MiningSession.stats`` and the mining benchmarks):

``kernel_calls``      device launches (sweep grids count as ONE — the
                      sweep loop is fused into the kernel)
``padded_elements``   padded query-shape elements materialized, sweep
                      iterations included (comparable across executors)
``branch_items``      host-decomposed hub branch items
``host_syncs``        blocking device→host transfers (1 per mine call)
``bytes_h2d``         staging bytes shipped host→device
``bytes_d2h``         result bytes shipped device→host
``jit_cache_entries`` distinct (strategy, dims, sweeps, batch) kernel
                      traces compiled so far (a gauge, proves the chunk
                      ladder bounds cache growth)
``schedule_hits``     bucket schedules served from the schedule cache
                      (repeated ``mine()`` calls skip the host-side
                      numpy grouping entirely)

Accumulation width: device arrays are int32 across the system (JAX x64
stays off — see ``TemporalGraph.to_device``), so the device-resident
accumulator is int32 as well.  Per-seed pattern counts are exact up to
2^31-1.  (The previous host-accumulating engine summed int32 kernel
partials into int64 numpy, so totals past 2^31 were representable at the
cost of a host sync per launch; in this regime such a count would wrap.
No realistic per-edge typology count approaches 2^31 — revisit with an
int32 hi/lo pair if one ever does.)

Tracing (`repro.obs.trace`, off by default): when the global tracer is
enabled, each bucket group contributes a ``stage`` span (the staging
``device_put``, with its ``bytes_h2d`` delta attached) and a ``launch``
span (the chunk dispatch loop, with ``kernel_calls`` /
``padded_elements`` deltas), and :func:`fetch` contributes a ``gather``
span.  Spans time *dispatch*, not device completion — launches are
asynchronous, so a closed ``launch`` span means work was submitted, and
only the blocking ``gather`` span covers real device execution.  The
tracer never adds a host sync; disabled, each span site is one branch.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace

__all__ = [
    "STAT_KEYS",
    "MIN_CHUNK",
    "new_stats",
    "pow2ceil",
    "chunk_widths",
    "coalesce_widths",
    "coalesce_groups",
    "BucketGroup",
    "Schedule",
    "build_staging",
    "execute",
    "fetch",
]

STAT_KEYS = (
    "kernel_calls",
    "padded_elements",
    "branch_items",
    "host_syncs",
    "bytes_h2d",
    "bytes_d2h",
    "jit_cache_entries",
    "schedule_hits",
)

MIN_CHUNK = 32  # smallest padded batch width (floor of the chunk ladder)


def new_stats() -> Dict[str, int]:
    return {k: 0 for k in STAT_KEYS}


def pow2ceil(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def chunk_widths(
    n_rows: int,
    batch_elem_cap: int,
    per_row: int,
    pad_rows_pow2: bool = False,
) -> List[int]:
    """Padded batch widths of a bucket group's chunks.

    Full chunks share one power-of-two width ``bchunk`` sized so a launch
    stays under ``batch_elem_cap`` padded elements; the tail is rounded up
    to the next power of two with a ``MIN_CHUNK`` floor.  Every width is a
    power of two in ``[MIN_CHUNK, bchunk]`` (or the single ``pow2ceil``
    width of a tiny group), so the set of batch shapes a (strategy, dims)
    kernel can be traced at is logarithmic, not linear, in group size.

    ``pad_rows_pow2=True`` sizes the widths for ``pow2ceil(n_rows)`` rows
    instead, with a ``MIN_CHUNK`` floor on the row class: the widths LIST
    itself (not just each width) is then canonical per pow2 row-count
    class, so shape-keyed schedule reuse can treat it as part of a stable
    launch profile — and tiny groups (streaming hub branches routinely
    have 1-16 rows) collapse onto ONE width class instead of minting a
    kernel trace per pow2 size below the floor.  The surplus rows are
    staged as padding (:func:`build_staging` points their scatter targets
    at the drop sentinel), so results are unchanged.
    """
    if pad_rows_pow2:
        n_rows = max(MIN_CHUNK, pow2ceil(max(1, n_rows)))
    bchunk = max(MIN_CHUNK, batch_elem_cap // max(1, per_row))
    bchunk = 1 << (bchunk.bit_length() - 1)  # round DOWN: ladder anchor
    bchunk = min(bchunk, pow2ceil(n_rows))
    widths = [bchunk] * (n_rows // bchunk)
    tail = n_rows - bchunk * len(widths)
    if tail:
        widths.append(min(bchunk, max(MIN_CHUNK, pow2ceil(tail))))
    return widths


def coalesce_widths(widths: Sequence[int], factor: int) -> List[int]:
    """Merge runs of equal-width chunks into fewer, fatter launches.

    Chunks of a bucket group are consecutive slices of ONE staging buffer,
    so ``k`` adjacent equal-width chunks can be launched as a single
    ``k*w``-wide kernel call just by slicing fatter — no restaging.  Merges
    happen in power-of-two counts up to ``factor`` (pow2-floored), so every
    produced width stays on the power-of-two trace ladder and the set of
    distinct batch widths grows by at most ``log2(factor)`` entries.

    Dispatch-bound callers use this (the sharded executor batches each
    device's launches before dispatching); the total padded element count
    is unchanged — only the launch count drops.
    """
    if factor <= 1 or len(widths) <= 1:
        return list(widths)
    fmax = 1 << (int(factor).bit_length() - 1)  # pow2 floor of factor
    out: List[int] = []
    i = 0
    n = len(widths)
    while i < n:
        w = widths[i]
        run = 1
        while i + run < n and widths[i + run] == w:
            run += 1
        i += run
        while run > 0:
            take = min(fmax, 1 << (run.bit_length() - 1))
            out.append(w * take)
            run -= take
    return out


def coalesce_groups(
    groups: Sequence["BucketGroup"], factor: int
) -> List["BucketGroup"]:
    """A schedule's groups with per-group chunk widths coalesced (the
    staging buffers are shared with the input groups — widths are just a
    different slicing of the same padded host buffer)."""
    if factor <= 1:
        return list(groups)
    return [
        dataclasses.replace(g, widths=coalesce_widths(g.widths, factor))
        for g in groups
    ]


@dataclasses.dataclass
class BucketGroup:
    """One (strategy, bucket-dims) group of the schedule, staged and ready
    to launch: padded host staging buffers plus the chunk widths that
    slice them."""

    strat: int
    dims: Tuple[int, ...]
    sweeps: Tuple[int, ...]
    branch: bool
    widths: List[int]
    # padded host staging: (src, dst, ts, frontier, frontier_t, seg)
    staging: Tuple[np.ndarray, ...]
    per_row: int
    n_sweep: int


@dataclasses.dataclass
class Schedule:
    """A fully grouped, staged bucket schedule for one (plan, seed set).

    Pure in (plan, graph degree requirements, seed ids) — cacheable, so a
    repeated ``mine()`` over the same seeds replays the launches without
    re-running any host-side numpy grouping."""

    groups: List[BucketGroup]
    branch_items: int
    n_out: int
    # witness schedules only: branch rows scatter into one slot per hub
    # branch item; per item, its seed's index and its level-1 edge id
    item_seed: Optional[np.ndarray] = None
    item_eid: Optional[np.ndarray] = None


def build_staging(
    widths: Sequence[int],
    n_out: int,
    sel: np.ndarray,
    src: np.ndarray,
    dst: np.ndarray,
    ts: np.ndarray,
    seg_vals: np.ndarray,
    fr: Optional[np.ndarray] = None,
    frt: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, ...]:
    """One padded staging buffer per kernel input for a whole group.

    Chunks are consecutive slices and only the final tail chunk carries
    padding, so a single ``np.full`` + prefix fill per field replaces the
    old per-chunk ``neg``/``zero``/``concatenate`` allocations.  ``seg``
    holds the scatter target of every row; pad rows point at ``n_out``,
    which the drop-mode scatter discards.
    """
    total = int(sum(widths))
    n = len(sel)
    ss = np.full(total, -1, np.int32)
    dd = np.full(total, -1, np.int32)
    tt = np.zeros(total, np.int32)
    ff = np.full(total, -1, np.int32)
    fft = np.zeros(total, np.int32)
    seg = np.full(total, n_out, np.int32)
    ss[:n] = src[sel]
    dd[:n] = dst[sel]
    tt[:n] = ts[sel]
    if fr is not None:
        ff[:n] = fr[sel]
        fft[:n] = frt[sel]
    seg[:n] = seg_vals
    return ss, dd, tt, ff, fft, seg


def _scatter_add_impl(out, seg, val):
    # pad rows carry seg == n_out (out of bounds) and are dropped; valid
    # rows are disjoint across groups, so add-into-zeros == assignment on
    # the bulk path and segment-sum on the branch path
    return out.at[seg].add(val, mode="drop")


_scatter_add_jit = None
_scatter_add_lock = threading.Lock()


def _scatter_add(out, seg, val):
    # donate the accumulator where the backend supports in-place donation
    # (CPU does not and would warn); lazy so importing this module never
    # forces backend initialization.  Locked: sharded dispatch threads may
    # race the first call, and the donation probe must run exactly once.
    global _scatter_add_jit
    if _scatter_add_jit is None:
        with _scatter_add_lock:
            if _scatter_add_jit is None:
                donate = (0,) if jax.default_backend() != "cpu" else ()
                _scatter_add_jit = jax.jit(
                    _scatter_add_impl, donate_argnums=donate
                )
    return _scatter_add_jit(out, seg, val)


def execute(
    groups: Sequence[BucketGroup],
    n_out: int,
    kernel_for: Callable[[int, Tuple[int, ...], Tuple[int, ...], bool], Callable],
    dg,
    stats: Dict[str, int],
    trace_keys: set,
    trace_tag: Tuple = (),
    device=None,
):
    """Launch every group chunk asynchronously, accumulating on device.

    Returns the device-resident per-seed count vector; nothing here
    blocks on the device — call :func:`fetch` for the one host sync.

    ``device`` pins the whole launch sequence (staging transfers, kernel
    dispatch, and the accumulator) to one explicit device — the sharded
    executor (:mod:`repro.core.shard`) passes each partition's device
    together with that device's graph replica as ``dg``, so jit dispatch
    follows the committed inputs and nothing lands on device 0 by
    accident.  ``device=None`` keeps the single-device default placement
    (``jax.device_put(x, None)`` and ``jax.default_device(None)`` are
    no-op identities).
    """
    with jax.default_device(device):  # allocate the accumulator in place
        out = jnp.zeros(n_out, jnp.int32)
    for grp in groups:
        with obs_trace.span(
            "stage", stats=stats, strat=grp.strat, dims=str(grp.dims)
        ):
            dev = jax.device_put(grp.staging, device)
            stats["bytes_h2d"] += sum(int(a.nbytes) for a in grp.staging)
        fn = kernel_for(grp.strat, grp.dims, grp.sweeps, grp.branch)
        with obs_trace.span(
            "launch", stats=stats, strat=grp.strat, dims=str(grp.dims)
        ):
            s0 = 0
            for w in grp.widths:
                sl = slice(s0, s0 + w)
                ss, dd, tt, ff, fft, seg = (a[sl] for a in dev)
                res = fn(dg, ss, dd, tt, ff, fft)
                out = _scatter_add(out, seg, res)
                # trace_tag carries caller-side trace-key components (the
                # compiled plan's n_iters) so cross-tick gauges don't collide
                trace_keys.add(trace_tag + (grp.strat, grp.dims, grp.sweeps, grp.branch, w))
                stats["kernel_calls"] += 1
                stats["padded_elements"] += w * grp.per_row * grp.n_sweep
                s0 += w
    return out


def fetch(out_dev, stats: Dict[str, int]) -> np.ndarray:
    """THE host sync: one blocking transfer of the finished counts."""
    with obs_trace.span("gather", stats=stats, mode="fetch"):
        host = np.asarray(out_dev)
        stats["host_syncs"] += 1
        stats["bytes_d2h"] += int(host.nbytes)
    return host

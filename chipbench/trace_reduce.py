"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

* **Busy** is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane, or ``XLA
  Modules`` where a plane has no op line), clipped to the traced window
  and averaged over the devices that ran anything.
* **Per-program device time** sums the ``XLA Modules`` events by program
  name (the trailing ``(id)`` dropped), and **device ops** the ``XLA
  Ops`` events by op name.
* **Idle gaps** are the stretches of the window in which the first
  device ran nothing.  Each gap is labelled by the benchmark-side host
  span open during most of it, the innermost where spans nest: the
  ``jax.profiler.TraceAnnotation`` names in :data:`LABELS`, plus any
  spans handed in by the caller (compiles, which JAX reports to
  ``jax.monitoring`` rather than to the trace).  A gap no span covers is
  ``other``.

The window is the host annotation named ``window`` when the trace has
one, else the whole trace.  Times are seconds.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["LABELS", "WINDOW", "load", "reduce_trace", "union_length", "window_of"]

LABELS = ("generate", "schedule", "stage", "dispatch", "fetch", "submit", "compile")
WINDOW = "window"
TOP = 10  # entries kept in each breakdown list
LABEL_MIN_NS = 100_000  # gaps shorter than this are summed as one "short" row

Span = Tuple[str, float, float]  # (label, start_ns, end_ns)


def load(path: str):
    """The ``ProfileData`` of ``path``: an ``.xplane.pb`` file, or a
    profiler log directory (its newest ``.xplane.pb`` is read)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(
            glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime,
        )
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return ProfileData.from_file(path)


def _intervals(events) -> np.ndarray:
    out = [(e.start_ns, e.start_ns + e.duration_ns) for e in events]
    return np.asarray(out, dtype=np.float64).reshape(-1, 2)


def _merge(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of ``(start, end)`` rows."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def union_length(iv: np.ndarray) -> float:
    m = _merge(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
    return float((m[:, 1] - m[:, 0]).sum()) if len(m) else 0.0


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _label_gap(a: float, b: float, spans: np.ndarray, names: Sequence[str]) -> str:
    """The label covering most of ``[a, b)``: per elementary piece, the
    innermost open span (latest start, then shortest)."""
    hit = np.nonzero((spans[:, 0] < b) & (spans[:, 1] > a))[0]
    if len(hit) == 0:
        return "other"
    cuts = np.unique(np.clip(np.concatenate([[a, b], spans[hit].ravel()]), a, b))
    share: Dict[str, float] = {}
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        mid = 0.5 * (lo + hi)
        open_ = [i for i in hit if spans[i, 0] <= mid < spans[i, 1]]
        if open_:
            i = max(open_, key=lambda j: (spans[j, 0], -(spans[j, 1] - spans[j, 0])))
            label = names[i]
        else:
            label = "other"
        share[label] = share.get(label, 0.0) + (hi - lo)
    return max(sorted(share), key=lambda k: share[k])


def _host_spans(pd, labels: Iterable[str]) -> List[Span]:
    want = set(labels)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in want:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def window_of(pd) -> Tuple[float, float]:
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    return e.start_ns, e.start_ns + e.duration_ns
    lo, hi = np.inf, -np.inf
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                lo, hi = min(lo, e.start_ns), max(hi, e.start_ns + e.duration_ns)
    return lo, hi


def reduce_trace(pd, extra_spans: Sequence[Span] = ()) -> dict:
    """Device busy and idle, per-program and per-op device time, and the
    longest labelled idle gaps of the traced window of ``pd`` (a
    ``ProfileData`` or a path :func:`load` accepts).  ``extra_spans`` are
    ``(label, start_ns, end_ns)`` host spans on the trace's clock."""
    if isinstance(pd, (str, os.PathLike)):
        pd = load(str(pd))
    w0, w1 = window_of(pd)
    window_ns = max(0.0, w1 - w0)
    busy: List[float] = []
    first_busy: Optional[np.ndarray] = None
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if op_line is None:
            continue
        evs = [e for e in op_line.events if e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
        if not evs:
            continue
        iv = _merge(_clip(_intervals(evs), w0, w1))
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()))
        if first_busy is None:
            first_busy = iv
        if "XLA Ops" in lines:
            for e in evs:
                ops[e.name] = ops.get(e.name, 0.0) + e.duration_ns
        for e in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            if e.start_ns < w1 and e.start_ns + e.duration_ns > w0:
                k = _program_name(e.name)
                programs[k] = programs.get(k, 0.0) + e.duration_ns
    busy_ns = float(np.mean(busy)) if busy else 0.0

    gaps: List[Tuple[str, float]] = []
    gap_by_label: Dict[str, float] = {}
    if first_busy is not None and window_ns > 0:
        edges = np.concatenate([[w0], first_busy.ravel(), [w1]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        spans = list(_host_spans(pd, LABELS)) + list(extra_spans)
        names = [s[0] for s in spans]
        arr = np.asarray([s[1:] for s in spans], dtype=np.float64).reshape(-1, 2)
        for a, b in edges:
            if b - a < LABEL_MIN_NS:
                label = "short"
            else:
                label = _label_gap(a, b, arr, names)
            gap_by_label[label] = gap_by_label.get(label, 0.0) + float(b - a) / 1e9
            if label != "short":
                gaps.append((label, float(b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": (1.0 - busy_ns / window_ns) if busy and window_ns > 0 else None,
        "n_devices": len(busy),
        "program_s": {k: v / 1e9 for k, v in sorted(programs.items())},
        "device_ops": [[k, v / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in gaps[:TOP]],
        "idle_s_by_label": dict(sorted(gap_by_label.items())),
    }

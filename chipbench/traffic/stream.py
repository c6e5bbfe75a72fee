"""Traffic kind ``stream``: an open-loop transfer stream into
``TriageServer``, built as ``repro.launch.serve`` builds it, with the
configuration's portfolio, thresholds and the mix's ``witnesses``.

Plan: transfers replayed in time order from a seeded start at
``rate_per_s`` events a second, evenly spaced, each stamped with its
due time.  Set-up submits the ``history_windows`` x window span of
transfers before the start in microbatches of ``warmup_batch``, the
last ``warmup_tail_ticks`` of them of ``warmup_tail_batch`` (the size of
a window tick, so its shapes are compiled before the window).  Into the
time span of the window's first ``rings_within`` events the plan plants
laundering rings, drawn from the seed between random accounts: for each
pattern named in ``rings``, that many instances of it (a round trip, a
three-hop cycle, or a scatter-gather through one mule more than its
alert threshold), each within an eighth of the window.  The
deployment's own typologies are as rare as its published laundering
ratio, so without the rings a window holds almost no cycle; with them
every run's check covers every pattern of the portfolio.  As many rings
again go into the history's last ``rings_within`` events, so that
set-up builds what the deep patterns' alerts and witnesses need.  Optional keys: ``check_seeds``,
``check_alerts``.

Run: a generator thread publishes each event when it falls due, on a
schedule that does not slow when the server does; the submit loop takes
every published event it has not yet submitted, up to ``max_batch``, and
submits them as one microbatch.  An event's latency runs from its due
time to the return of the tick that delivered its alerts and witnesses.
The generator stops when the window closes; the loop then drains what
is still due, for at most ``DRAIN_S`` seconds, and an event still not
delivered after that, or whose tick failed, has failed.

Check, exact with every limit 0: (1) the final per-pattern counts of
every ring edge the server ingested and of a sample of the other events
ingested in the window (planted typology edges, up to half, then a
uniform draw), against the reference over every edge ingested; (2) of
those, every (event, pattern) whose reference count reaches the
pattern's threshold has been raised as an alert by some tick; (3) for
up to ``check_alerts`` alert-pattern pairs drawn evenly across the
patterns, the alert's count and its witness tuples against the
reference over the edges ingested up to that tick; (4) every event due in the window delivered.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.check import Produce, compare_counts, planted_edges, ref_graph, sample
from chipbench.data.synth_aml import _illicit_amounts
from chipbench.generator import rng_for

__all__ = [
    "StreamPlan",
    "build_server",
    "check",
    "control",
    "run",
    "serve_window",
    "stream_plan",
    "submit_history",
]

DRAIN_S = 60.0
PUBLISH_S = 0.002  # generator cadence
RING_KINDS = ("cycle2", "cycle3", "scatter_gather")


@dataclasses.dataclass
class StreamPlan:
    """Replay order of a stream run: ``history`` (edge ids submitted in
    set-up) then ``window`` (edge ids offered open loop, event i due
    ``i / rate`` seconds after the window opens); ``rings`` are the ids
    of the planted ring edges, all of them in ``window``."""

    history: np.ndarray
    window: np.ndarray
    rate: float
    rings: np.ndarray

    def due(self, i):
        return i / self.rate

    def n_due(self, elapsed: float) -> int:
        """Events due within ``elapsed`` seconds of the window opening."""
        return min(len(self.window), int(math.floor(elapsed * self.rate)) + 1)


def _ring(kind: str, rng, n_nodes: int, t0: int, span: int, width: int):
    """One ring of ``kind`` starting at ``t0``: its (src, dst, t)."""
    if kind == "cycle2" or kind == "cycle3":
        k = 2 if kind == "cycle2" else 3
        nodes = rng.choice(n_nodes, size=k, replace=False)
        ts = t0 + np.sort(rng.choice(span, size=k, replace=False))
        return nodes, np.roll(nodes, -1), ts
    if kind == "scatter_gather":
        nodes = rng.choice(n_nodes, size=width + 2, replace=False)
        s, v, mids = nodes[0], nodes[1], nodes[2:]
        t_sc = t0 + rng.integers(0, span // 2, width)
        t_ga = t_sc + 1 + rng.integers(0, span // 2, width)
        return (np.concatenate([np.full(width, s), mids]),
                np.concatenate([mids, np.full(width, v)]),
                np.concatenate([t_sc, t_ga]))
    raise ValueError(f"no ring of kind {kind!r}; have {RING_KINDS}")


def plant_rings(mix: dict, data: dict, span_ids: np.ndarray, rng,
                cfg: dict) -> Tuple[dict, np.ndarray]:
    """``data`` with the mix's rings appended as new transfers (and as
    planted instances) inside the time span of ``span_ids``, and the
    rings' edge ids."""
    rings = mix.get("rings") or {}
    if not rings or not len(span_ids):
        return data, np.zeros(0, np.int64)
    window = int(cfg["window"])
    width = int(cfg["portfolio"].get("scatter_gather", 1)) + 1
    ts = data["t"][span_ids]
    span = max(2, window // 8)
    lo, hi = int(ts[0]), max(int(ts[0]) + 1, int(ts[-1]) - span)
    n0 = len(data["t"])
    parts, instances = [], []
    n = n0
    for kind in sorted(rings):
        for _ in range(int(rings[kind])):
            s, d, t = _ring(kind, rng, int(data["n_nodes"]), int(rng.integers(lo, hi)), span,
                            width)
            parts.append((s, d, t))
            instances.append({"kind": "cycle" if kind.startswith("cycle") else kind,
                              "eids": np.arange(n, n + len(s), dtype=np.int64)})
            n += len(s)
    src, dst, t = (np.concatenate([p[i] for p in parts]) for i in range(3))
    out = dict(data)
    out["src"] = np.concatenate([data["src"], src.astype(data["src"].dtype)])
    out["dst"] = np.concatenate([data["dst"], dst.astype(data["dst"].dtype)])
    out["t"] = np.concatenate([data["t"], t.astype(data["t"].dtype)])
    out["amount"] = np.concatenate([data["amount"], _illicit_amounts(rng, len(src))])
    out["labels"] = np.concatenate([data["labels"], np.ones(len(src), data["labels"].dtype)])
    out["instances"] = list(data["instances"]) + instances
    return out, np.arange(n0, n, dtype=np.int64)


def stream_plan(mix: dict, data: dict, seed: int, cfg: dict,
                seconds: float) -> Tuple[StreamPlan, dict]:
    """The run's plan, and the transfers with its rings planted."""
    rate = float(mix["rate_per_s"])
    t = data["t"]
    span = int(mix["history_windows"]) * int(cfg["window"])
    order = np.argsort(t, kind="stable")
    ts = t[order]
    n_win = max(1, int(math.ceil(rate * seconds)))
    lo = int(np.searchsorted(ts, ts[0] + span))
    hi = len(order) - n_win
    if hi <= lo:
        raise ValueError(f"{n_win} window events and {span} of history exceed the data")
    s0 = int(rng_for(seed, 2).integers(lo, hi))
    h0 = int(np.searchsorted(ts, ts[s0] - span))
    hist, win = order[h0:s0], order[s0 : s0 + n_win]
    within = int(mix.get("rings_within", n_win))
    data, early = plant_rings(mix, data, hist[-within:], rng_for(seed, 7), cfg)
    data, rings = plant_rings(mix, data, win[:within], rng_for(seed, 6), cfg)
    hist = np.concatenate([hist, early])
    hist = hist[np.argsort(data["t"][hist], kind="stable")]
    win = np.concatenate([win, rings])
    win = win[np.argsort(data["t"][win], kind="stable")][:n_win]  # rings lie early: none cut
    return StreamPlan(history=hist, window=win, rate=rate, rings=rings), data


class _Generator(threading.Thread):
    """Publishes the count of due events; records how late it ran."""

    def __init__(self, plan, t0: float, seconds: float):
        super().__init__(daemon=True)
        self.plan, self.t0, self.seconds = plan, t0, seconds
        self.published = 0
        self.lag_max_s = 0.0
        self.cond = threading.Condition()
        self.stop = threading.Event()

    def run(self) -> None:
        import jax

        n_total = len(self.plan.window)
        while not self.stop.is_set():
            now = time.perf_counter()
            with jax.profiler.TraceAnnotation("generate"):
                n = self.plan.n_due(now - self.t0)
                with self.cond:
                    if n > self.published:
                        self.lag_max_s = max(
                            self.lag_max_s, now - self.t0 - self.plan.due(n - 1)
                        )
                        self.published = n
                        self.cond.notify()
            if n >= n_total or now - self.t0 >= self.seconds:
                return
            self.stop.wait(PUBLISH_S)


def build_server(cfg: dict, mix: dict):
    """The service and server as ``repro.launch.serve`` builds them, with
    the configuration's portfolio and thresholds."""
    from repro.launch.serve import TriageServer
    from repro.stream.service import DetectionService

    portfolio = dict(cfg["portfolio"])
    svc = DetectionService(
        list(portfolio),
        window=int(cfg["window"]),
        thresholds=portfolio,
        witnesses=int(mix.get("witnesses", 0)),
    )
    return svc, TriageServer(svc), portfolio


def submit_history(submit, cols, history, mix: dict) -> None:
    """Set-up: the history before the window, in microbatches of
    ``warmup_batch``, the last ``warmup_tail_ticks`` of
    ``warmup_tail_batch``."""
    from repro.launch.serve import SubmitError

    tail_n = int(mix.get("warmup_tail_ticks", 0))
    tail_b = int(mix.get("warmup_tail_batch", 0))
    cut = max(0, len(history) - tail_n * tail_b)
    bulk = int(mix.get("warmup_batch", mix["max_batch"]))
    bounds = list(range(0, cut, bulk)) + list(range(cut, len(history), max(1, tail_b)))
    for lo, hi in zip(bounds, bounds[1:] + [len(history)]):
        ids = history[lo:hi]
        out = submit(*(c[ids] for c in cols))
        if isinstance(out, SubmitError):
            raise RuntimeError(f"history submit failed: {out.error}: {out.detail}")


def serve_window(submit, cols, plan, seconds: float, cap: int):
    """Offer ``plan.window`` open loop for ``seconds`` and drain it.
    Returns each event's delivery time (seconds after the window opened;
    NaN if never delivered), the ticks, the failed submits and the
    generator's worst lateness."""
    import jax

    from repro.launch.serve import SubmitError

    n_total = len(plan.window)
    delivered_at = np.full(n_total, np.nan)
    ticks = []
    t0 = time.perf_counter()
    gen = _Generator(plan, t0, seconds)
    gen.start()
    done = errors = 0
    try:
        while done < n_total:
            with gen.cond:
                while gen.published <= done and gen.is_alive():
                    gen.cond.wait(0.05)
                avail = gen.published
            if avail <= done or time.perf_counter() - t0 > seconds + DRAIN_S:
                break
            take = min(avail - done, cap)
            ids = plan.window[done : done + take]
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("submit"):
                batch = submit(*(c[ids] for c in cols))
            te = time.perf_counter()
            if isinstance(batch, SubmitError):
                errors += 1  # rolled back: these events are never delivered
            else:
                delivered_at[done : done + take] = te - t0
                ticks.append({
                    "n": take,
                    "submit_s": te - ts,
                    "end_s": te - t0,
                    "path": batch.report.path,
                    "n_live": batch.report.n_live,
                    "first": done,
                    "batch": batch,
                })
            done += take
    finally:
        gen.stop.set()
        gen.join(timeout=10)
    return delivered_at, ticks, errors, gen.lag_max_s


def run(cfg: dict, mix: dict, data: dict, seed: int, seconds: float, recorder,
        clock_start: float, submit_hook: Optional[Callable] = None) -> dict:
    """Drive one stream run; returns the run's record.  ``submit_hook``,
    for tests, wraps the server's ``submit`` to plant a fault."""
    svc, server, portfolio = build_server(cfg, mix)
    submit = server.submit if submit_hook is None else submit_hook(server.submit)
    plan, data = stream_plan(mix, data, seed, cfg, seconds)
    cols = (data["src"], data["dst"], data["t"], data["amount"])
    submit_history(submit, cols, plan.history, mix)
    setup_s = time.perf_counter() - clock_start

    with recorder.window():
        t_open = time.perf_counter()
        delivered_at, ticks, errors, lag = serve_window(
            submit, cols, plan, seconds, int(mix["max_batch"])
        )
        window_s = time.perf_counter() - t_open
    server.close()
    n_total = len(plan.window)
    due = plan.due(np.arange(n_total))
    ok = ~np.isnan(delivered_at)
    return {
        "mode": "stream",
        "patterns": list(portfolio),
        "thresholds": portfolio,
        "setup_s": setup_s,
        "window_s": window_s,
        "seconds": float(seconds),
        "events": n_total,
        "delivered": int(ok.sum()),
        "latencies_s": (delivered_at[ok] - due[ok]).tolist(),
        "last_delivery_s": float(np.nanmax(delivered_at)) if ok.any() else None,
        "generator_lag_max_s": lag,
        "ticks": [{k: v for k, v in tk.items() if k != "batch"} for tk in ticks],
        "attempted": n_total,
        "failed": int(n_total - ok.sum()),
        "submit_errors": errors,
        "n_history": len(plan.history),
        "outputs": {"plan": plan, "ticks": ticks, "data": data, "counts": {
            n: np.asarray(svc.pattern_counts(n)).copy() for n in portfolio
        }},
    }


def _at(counts: np.ndarray, eids: np.ndarray) -> np.ndarray:
    """``counts[eids]``, with -1 for an edge the server never ingested."""
    out = np.full(len(eids), -1, dtype=np.int64)
    ok = eids < len(counts)
    out[ok] = counts[eids[ok]]
    return out


def _ingested(plan, ticks: Sequence[dict]) -> Tuple[np.ndarray, List[int]]:
    """Transfer ids in the order the server ingested them, and the
    ingested length at the end of each tick."""
    parts = [plan.history]
    ends = []
    n = len(plan.history)
    for tk in ticks:
        parts.append(plan.window[tk["first"] : tk["first"] + tk["n"]])
        n += tk["n"]
        ends.append(n)
    return np.concatenate(parts), ends


def window_sample(mix: dict, data: dict, plan, order: np.ndarray, rng) -> Tuple[np.ndarray, int]:
    """Positions in ingest ``order`` of the events ingested after the
    history whose final counts are compared: every ring edge, then
    ``check_seeds`` more drawn by :func:`chipbench.check.sample`; and how
    many events the window ingested."""
    window_pos = np.arange(len(plan.history), len(order))
    is_ring = np.isin(order[window_pos], plan.rings)
    rings, rest = window_pos[is_ring], window_pos[~is_ring]
    pos = sample(order[rest], planted_edges(data), int(mix.get("check_seeds", 256)), rng)
    return np.sort(np.concatenate([rings, rest[pos]])), len(window_pos)


def _raised(ticks, names) -> Dict[str, np.ndarray]:
    """Per pattern, the edges (ingest positions) some tick raised an
    alert for."""
    out = {}
    for name in names:
        ids = [tk["batch"].eids[tk["batch"].triggered[:, tk["batch"].columns.index(name)]]
               for tk in ticks]
        out[name] = np.unique(np.concatenate(ids)) if ids else np.zeros(0, np.int64)
    return out


def _alert_pairs(ticks, names, rng, per: int):
    """(tick index, row, pattern) triples with witnesses: up to ``per``
    drawn for each pattern from every tick's alerts."""
    cand: Dict[str, list] = {n: [] for n in names}
    for i, tk in enumerate(ticks):
        for row, ev in enumerate(tk["batch"].evidence or ()):
            for name in ev:
                if name in cand:
                    cand[name].append((i, row))
    out = []
    for name in names:
        c = cand[name]
        for k in rng.choice(len(c), size=min(per, len(c)), replace=False):
            out.append((*c[int(k)], name))
    return sorted(out)


def check(cfg: dict, mix: dict, data: dict, rec: dict, seed: int,
          produce: Optional[Produce] = None) -> Tuple[Dict[str, dict], List[str]]:
    from chipbench.ref.oracle import GFPReference
    from chipbench.ref.patterns import build_pattern

    t0 = time.perf_counter()
    window = int(cfg["window"])
    k = int(mix.get("witnesses", 0))
    names, thresholds = rec["patterns"], rec["thresholds"]
    out = rec["outputs"]
    plan, ticks, data = out["plan"], out["ticks"], out["data"]
    order, ends = _ingested(plan, ticks)
    rng = rng_for(seed, 4)
    # (1) final counts of events ingested in the window
    eids, n_window = window_sample(mix, data, plan, order, rng)
    g = ref_graph(data, order)
    got = {n: _at(out["counts"][n], eids) for n in names}
    bad_counts, matched, want = compare_counts(names, g, eids, got, window, produce)
    # (2) each of them that reaches a threshold was raised by some tick
    raised = _raised(ticks, names)
    missed = {n: int((~np.isin(eids[want[n] >= thresholds[n]], raised[n])).sum())
              for n in names if n in thresholds}
    # (3) alerts: count and witness tuples as of their tick
    pairs = _alert_pairs(ticks, names, rng, int(mix.get("check_alerts", 96)) // len(names))
    bad_alerts = 0
    graphs = {}
    for i, row, name in pairs:
        if i not in graphs:
            graphs.clear()  # pairs are sorted by tick: one graph at a time
            graphs[i] = ref_graph(data, order[: ends[i]])
        b = ticks[i]["batch"]
        ref = GFPReference(build_pattern(name, window), graphs[i])
        want_n, want_w = ref.mine_witnesses(np.asarray([int(b.eids[row])]), k=k)
        have_n = int(b.counts[row, b.columns.index(name)])
        have_w = [tuple(h["eid"] for h in wit) for wit in b.evidence[row][name]]
        if have_n != int(want_n[0]) or have_w != list(want_w[0][:k]):
            bad_alerts += 1
    checks = {
        "count_mismatches": {"value": bad_counts, "limit": 0},
        "missed_alerts": {"value": sum(missed.values()), "limit": 0},
        "alert_mismatches": {"value": bad_alerts, "limit": 0},
        "undelivered": {"value": int(rec["failed"]), "limit": 0},
    }
    per_pattern = {n: sum(p[2] == n for p in pairs) for n in names}
    info = [
        f"stream: {len(eids)} of {n_window} window events compared x {len(names)} patterns "
        f"over {len(order)} ingested edges, {int(np.isin(order[eids], plan.rings).sum())} "
        f"ring edges, {int(np.isin(order[eids], planted_edges(data)).sum())} planted in all",
        f"stream: compared events with a match, per pattern {matched}",
        f"stream: alerts missed, per pattern {missed}",
        f"stream: alert-pattern pairs compared (k={k}), per pattern {per_pattern}, "
        f"from {len({p[0] for p in pairs})} ticks",
        f"stream: {rec['delivered']} of {rec['attempted']} due events delivered in "
        f"{len(ticks)} ticks, the last {rec['last_delivery_s']} s after the opening",
        f"stream: reference took {time.perf_counter() - t0:.3f} s",
    ]
    return checks, info


def control(cfg: dict, mix: dict, data: dict, seed: int, produce: Produce,
            seconds: float, size: int) -> Tuple[Dict[str, dict], List[str]]:
    """The control's counts for the history and the first ``size`` events
    of a ``seconds`` run's window, ingested in order, on the events a
    run's check would compare."""
    plan, data = stream_plan(mix, data, seed, cfg, seconds)
    order = np.concatenate([plan.history, plan.window[:size]])
    names = list(cfg["portfolio"])
    eids, _ = window_sample(mix, data, plan, order, rng_for(seed, 4))
    bad, matched, _ = compare_counts(names, ref_graph(data, order), eids, None,
                                     int(cfg["window"]), produce)
    info = [f"stream: {len(eids)} window events x {len(names)} patterns, "
            f"matched per pattern {matched}"]
    return {"count_mismatches": {"value": bad, "limit": 0}}, info

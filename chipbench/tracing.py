"""What a run records besides its results: every compile JAX reports,
and, in a ``--trace 1`` run, a profiler trace of the measured window
with the host's phases marked in it.

Compiles are counted from ``jax.monitoring``: one
``backend_compile_duration`` event per executable JAX produces, whether
compiled or loaded from the persistent cache.  Their time spans (and
those of tracing and lowering a new specialisation) become ``compile``
spans on the trace's clock.

The host phases are marked from the benchmark's side: for the traced
run only, the program functions in :data:`PHASES` are wrapped in a
``jax.profiler.TraceAnnotation`` named after their phase, and unwrapped
when the run ends.  A function that a later version of the program
renames or removes is skipped and reported; its gaps then read
``other``.
"""
from __future__ import annotations

import contextlib
import importlib
import sys
import time
from typing import Iterator, List, Optional, Tuple

__all__ = ["Recorder", "PHASES"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_SPANS = (
    COMPILE_EVENT,
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)

# (module, class or None, attribute, phase label)
PHASES = (
    ("repro.core.compiler", "CompiledPattern", "schedule_for", "schedule"),
    ("repro.stream.delta", "DeltaScheduler", "plan", "schedule"),
    ("repro.core.executor", None, "build_staging", "stage"),
    ("repro.stream.store", "TemporalGraphStore", "ingest", "stage"),
    ("repro.core.executor", None, "execute", "dispatch"),
    ("repro.api.session", "_FusedSeedPlan", "launch_units", "dispatch"),
    ("repro.core.executor", None, "fetch", "fetch"),
    ("repro.core.shard", None, "gather", "fetch"),
)


def _annotated(fn, label: str):
    import jax

    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation(label):
            return fn(*args, **kwargs)

    wrapper.__wrapped__ = fn
    return wrapper


@contextlib.contextmanager
def phase_annotations() -> Iterator[None]:
    """Wrap :data:`PHASES` for the duration of the block."""
    undo: List[Tuple[object, str, object]] = []
    try:
        for mod_name, cls_name, attr, label in PHASES:
            try:
                owner = importlib.import_module(mod_name)
                if cls_name is not None:
                    owner = getattr(owner, cls_name)
                fn = getattr(owner, attr)
            except (ImportError, AttributeError):
                print(f"trace: no {mod_name}.{cls_name or ''}.{attr} to mark", file=sys.stderr)
                continue
            setattr(owner, attr, _annotated(fn, label))
            undo.append((owner, attr, fn))
        yield
    finally:
        for owner, attr, fn in reversed(undo):
            setattr(owner, attr, fn)


class Recorder:
    """Compile events of the whole run, and the profiler around the
    window when ``trace_dir`` is given."""

    def __init__(self, trace_dir: Optional[str] = None):
        import jax

        self.trace_dir = trace_dir
        self.spans: List[Tuple[str, float, float]] = []  # (event, start, end), wall s
        self.window_wall = (0.0, 0.0)
        self._jax = jax
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event: str, start: float, end: float, **_kw) -> None:
        if event in COMPILE_SPANS:
            self.spans.append((event, start, end))

    def close(self) -> None:
        self._jax.monitoring.unregister_event_time_span_listener(self._on_span)

    def compiles_in_window(self) -> int:
        lo, hi = self.window_wall
        return sum(1 for ev, s, _ in self.spans if ev == COMPILE_EVENT and lo <= s < hi)

    @contextlib.contextmanager
    def window(self) -> Iterator[None]:
        """The measured window: profiled, with the host phases marked,
        when tracing."""
        jax = self._jax
        stack = contextlib.ExitStack()
        with stack:
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                stack.callback(jax.profiler.stop_trace)
                stack.enter_context(phase_annotations())
            t0 = time.time()
            try:
                with jax.profiler.TraceAnnotation("window"):
                    yield
            finally:
                self.window_wall = (t0, time.time())

    def reduce(self) -> Optional[dict]:
        """The reduced trace of the window, with the compiles of the
        window as ``compile`` spans; ``None`` when not tracing."""
        if not self.trace_dir:
            return None
        from chipbench import trace_reduce

        pd = trace_reduce.load(self.trace_dir)
        w0_ns, _ = trace_reduce.window_of(pd)
        offset = w0_ns - self.window_wall[0] * 1e9
        lo, hi = self.window_wall
        extra = [
            ("compile", s * 1e9 + offset, e * 1e9 + offset)
            for _, s, e in self.spans
            if e > lo and s < hi
        ]
        return trace_reduce.reduce_trace(pd, extra)

#!/usr/bin/env python3
"""Record the small trace ``test_trace_reduce.py`` reads, on the chip.

  python3 chipbench/tests/make_trace.py <out_dir>

Inside a ``window`` annotation, three rounds of: dispatch one jitted
program (``dispatch``), wait for it (``fetch``), then leave the device
idle for 20 ms inside a ``schedule`` annotation and 10 ms outside any.
The program is compiled before the trace starts.  Prints the trace's
planes and lines.
"""
import os
import sys
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    def probe(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) + 1.0
        return x.sum()

    f = jax.jit(probe)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("fetch"):
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("schedule"):
                time.sleep(0.02)
            time.sleep(0.01)
    jax.profiler.stop_trace()

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    from chipbench import trace_reduce

    pd = trace_reduce.load(out)
    for plane in pd.planes:
        lines = {ln.name: sum(1 for _ in ln.events) for ln in plane.lines}
        print(plane.name, lines)
    print(trace_reduce.reduce_trace(pd))


if __name__ == "__main__":
    main(sys.argv[1])

#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and ``checks`` last); the last lines of standard
error give each compared number beside its limit.  With no accelerator,
or fewer chips than the cell asks for, it prints no result and exits 3.
JAX's persistent compilation cache is ``<checkout>/.jax_cache``.
"""
import time

CLOCK_START = time.perf_counter()  # set-up counts from here: imports included

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the program keeps its compile cache where this variable says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from chipbench.harness import main as harness_main

    return harness_main(sys.argv[1:], clock_start=CLOCK_START)


if __name__ == "__main__":
    sys.exit(main())

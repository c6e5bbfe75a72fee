"""A batch run with the timed path broken underneath comes out not
correct: once for each fault a batch cell can have.  The harness's look
for a chip is skipped; everything else is a real run at a tiny size."""
import dataclasses

import numpy as np
import pytest

from chipbench import harness

CELL = "hi_small.batch_local"


def altered(mine):
    """Every seed's answer for the first pattern altered where produced."""
    def f(*a, **k):
        res = mine(*a, **k)
        res.counts[:, 0] += 1
        return res
    return f


def half_left_out(mine):
    """Half of each mine's seeds never mined; their rows left at 0."""
    def f(*a, seeds, **k):
        res = mine(*a, seeds=seeds[: len(seeds) // 2], **k)
        counts = np.zeros((len(seeds), res.counts.shape[1]), dtype=res.counts.dtype)
        counts[: len(seeds) // 2] = res.counts
        return dataclasses.replace(res, counts=counts, n_seeds=len(seeds))
    return f


def unchanged(mine):
    """Each mine returns the previous mine's result."""
    last = {}

    def f(*a, **k):
        res = mine(*a, **k)
        prev, last["res"] = last.get("res"), res
        return res if prev is None else prev
    return f


def test_a_sound_run_is_correct(tiny_root, no_compile_cache):
    line, notes = harness.run(tiny_root, CELL, 2**31 + 11, 0.5, False, require_chip=False)
    assert line["correct"] is True, notes
    assert line["checks"]["count_mismatches"]["value"] == 0


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
def test_a_fault_is_caught(tiny_root, no_compile_cache, fault):
    line, notes = harness.run(tiny_root, CELL, 2**31 + 11, 0.5, False,
                              require_chip=False, hook=fault)
    assert line["correct"] is False, notes
    assert line["checks"]["count_mismatches"]["value"] > 0
    assert notes[-1].startswith("check count_mismatches=")

"""A stream run with the timed path broken underneath comes out not
correct: once for each fault a stream cell can have, for a deep pattern
that no alert reports, and for an event that is never delivered.  The
harness's look for a chip is skipped; everything else is a real run at
a tiny size."""
import pytest

from chipbench import harness

CELL = "li_small.triage_stream"


def unchanged(submit):
    """Ticks that ingest nothing: the store's state never changes."""
    def f(src, dst, t, amount):
        return submit(src[:0], dst[:0], t[:0], amount[:0])
    return f


def half_left_out(submit):
    """Each microbatch loses its second half."""
    def f(src, dst, t, amount):
        h = (len(src) + 1) // 2
        return submit(src[:h], dst[:h], t[:h], amount[:h])
    return f


def altered(submit):
    """Every alert's counts altered where produced."""
    def f(*a):
        out = submit(*a)
        if hasattr(out, "counts"):
            out.counts += 1
        return out
    return f


def cycle2_zeroed(submit):
    """No alert ever reports the round-trip pattern: its column zeroed
    and its witnesses dropped where each tick's alerts are produced."""
    def f(*a):
        out = submit(*a)
        if hasattr(out, "counts"):
            j = out.columns.index("cycle2")
            out.counts[:, j] = 0
            out.triggered[:, j] = False
            for ev in out.evidence or ():
                ev.pop("cycle2", None)
        return out
    return f


def undelivered(submit):
    """The first tick of the window fails and is rolled back."""
    import inspect

    from repro.launch.serve import SubmitError

    calls = {"n": 0}

    def f(*a):
        if any(fr.function == "serve_window" for fr in inspect.stack()[1:4]):
            calls["n"] += 1
            if calls["n"] == 1:
                return SubmitError(error="Fault", detail="planted", tick=0)
        return submit(*a)
    return f


def test_a_sound_run_is_correct(tiny_root, no_compile_cache):
    line, notes = harness.run(tiny_root, CELL, 2**31 + 13, 1.0, False, require_chip=False)
    assert line["correct"] is True, notes
    assert line["failed"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())


@pytest.mark.parametrize("fault,number", [
    (unchanged, "count_mismatches"),
    (half_left_out, "count_mismatches"),
    (altered, "alert_mismatches"),
    (cycle2_zeroed, "missed_alerts"),
    (undelivered, "undelivered"),
])
def test_a_fault_is_caught(tiny_root, no_compile_cache, fault, number):
    line, notes = harness.run(tiny_root, CELL, 2**31 + 13, 1.0, False,
                              require_chip=False, hook=fault)
    assert line["correct"] is False, notes
    assert line["checks"][number]["value"] > 0
    if fault is undelivered:
        assert line["failed"] > 0

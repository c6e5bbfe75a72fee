"""The control: the reference with a degree cap in the program's place
fails the comparison, and with no cap it passes (the cap, not the
plumbing, is what fails).  At the test's size accounts hold tens of
transfers, so the cap is cut to match; on the chip it is 1,024."""
import pytest

from chipbench.control import run_control


SIZE = {"hi_small.batch_local": 3, "li_small.triage_stream": 800}  # mines; window events


@pytest.mark.parametrize("cell", ["hi_small.batch_local", "li_small.triage_stream"])
def test_the_control_is_caught(tiny_root, cell):
    out = run_control(tiny_root, cell, 21, cap=4, size=SIZE[cell], seconds=2.0)
    assert out["correct"] is False
    assert out["checks"]["count_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", ["hi_small.batch_local", "li_small.triage_stream"])
def test_without_the_cap_the_control_agrees(tiny_root, cell):
    out = run_control(tiny_root, cell, 21, cap=10**9, size=SIZE[cell], seconds=2.0)
    assert out["correct"] is True

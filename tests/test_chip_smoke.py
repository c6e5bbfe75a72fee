"""chip_smoke.py's phases on CPU at a tiny scale, with the chip run's
exactness asserts: xla == pallas == oracle counts with every pattern
matched on a few oracle seeds, witnesses == oracle top-k on every oracle
seed, triage ticks with no submit error and incremental == batch, and
the sharded mine over four virtual devices == the one-device mine.  The
script's own ``main`` refuses a CPU device; here the device check is
steered by calling the phases directly (Pallas runs in interpret mode)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke as cs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALE = 4.0  # enough planted instances for two of each typology group
N_SEEDS = 512
N_ORACLE = 96


@pytest.fixture(scope="module")
def loaded():
    names = cs.portfolio()
    g, seeds, oracle_seeds = cs.phase_load(0, SCALE, N_SEEDS, N_ORACLE)
    session = cs.new_session(g, names, "xla")
    ref = cs.oracle_reference(g, names, oracle_seeds, cs.WITNESS_K)
    return names, g, seeds, oracle_seeds, session, ref


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_require_tpu_rejects_short_host():
    with pytest.raises(SystemExit):
        cs.require_tpu({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4)
    cs.require_tpu({"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, 4)


def test_phase_load_sizes(loaded):
    names, g, seeds, oracle_seeds, _, _ = loaded
    assert len(names) == 12
    assert len(seeds) == N_SEEDS and len(set(seeds.tolist())) == N_SEEDS
    assert set(oracle_seeds.tolist()) <= set(seeds.tolist())
    assert len(oracle_seeds) == N_ORACLE
    # the planted-instance edges are in both samples
    from repro.data.synth_aml import generate_aml_dataset

    ds = generate_aml_dataset("HI-Small", seed=0, scale=SCALE, window=cs.WINDOW)
    planted = cs.planted_seeds(ds, cs.PLANTED_PER_GROUP, np.random.default_rng(0))
    assert set(planted.tolist()) <= set(oracle_seeds.tolist())


def test_oracle_seeds_match_every_pattern(loaded):
    names, _, _, _, _, ref = loaded
    for n in names:
        assert int((ref[n][0] > 0).sum()) >= cs.MIN_MATCHED, n


def test_phase_batch(loaded):
    names, g, seeds, oracle_seeds, session, ref = loaded
    cs.phase_batch(session, g, names, seeds, oracle_seeds, ref, "interpret")


def test_phase_witnesses(loaded):
    names, _, _, oracle_seeds, session, ref = loaded
    items = cs.phase_witnesses(session, names, oracle_seeds, ref, cs.WITNESS_K)
    assert items > 0  # the hub-branch witness path ran


def test_phase_triage(loaded):
    _, g, _, _, _, _ = loaded
    cs.phase_triage(g, ticks=3, batch=256, k=cs.WITNESS_K)


SHARDED = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
import chip_smoke as cs
g, seeds, _ = cs.phase_load(0, {scale}, {n_seeds}, {n_oracle})
session = cs.new_session(g, cs.portfolio(), "xla")
cs.phase_sharded(session, seeds, 4)
print("RESULT " + json.dumps(cs.device_info()))
"""


def test_phase_sharded_four_virtual_devices():
    code = SHARDED.format(
        root=ROOT, scale=SCALE, n_seeds=N_SEEDS, n_oracle=N_ORACLE
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")][-1]
    assert json.loads(line[len("RESULT "):])["count"] == 4

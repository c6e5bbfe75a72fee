"""Multi-device tests: run in a subprocess with
--xla_force_host_platform_device_count=8 so the main test process keeps
seeing 1 device (per the dry-run contract).

Covers: sharded train step == unsharded train step (bit-level tolerance),
sharding rule divisibility fallback, elastic checkpoint restore onto a
different mesh, and degree-partitioned mining == direct mining.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.registry import smoke_config
from repro.distributed.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.distributed.sharding import (
    batch_sharding, param_sharding, mesh_axes, zero1_sharding,
)
from repro.distributed.checkpoint import save_checkpoint, restore_checkpoint
from repro.models.model import init_params, loss_fn, param_specs

out = {}
assert jax.device_count() == 8
cfg = smoke_config("qwen2-1.5b")
params = init_params(cfg, jax.random.key(0))
opt = adamw_init(params)
ocfg = AdamWConfig(lr=1e-3)
rng = np.random.default_rng(0)
batch = {
    "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
    "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
}

def train_step(params, opt, batch):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
    p2, o2, gn = adamw_update(params, grads, opt, ocfg)
    return p2, o2, loss

# unsharded reference
p_ref, o_ref, loss_ref = jax.jit(train_step)(params, opt, batch)
out["loss_ref"] = float(loss_ref)

# sharded: 2-way data x 4-way model
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
p_specs = param_specs(cfg)
p_sh = param_sharding(mesh, p_specs)
b_sh = batch_sharding(mesh, jax.eval_shape(lambda: batch))
o_specs = jax.eval_shape(lambda p: adamw_init(p), p_specs)
o_sh = {
    "m": zero1_sharding(mesh, p_specs, p_sh),
    "v": zero1_sharding(mesh, p_specs, p_sh),
    "step": NamedSharding(mesh, P()),
}
params_s = jax.tree_util.tree_map(jax.device_put, params, p_sh)
opt_s = jax.tree_util.tree_map(jax.device_put, opt, o_sh)
batch_s = jax.tree_util.tree_map(jax.device_put, batch, b_sh)
p_shd, o_shd, loss_shd = jax.jit(
    train_step, in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None)
)(params_s, opt_s, batch_s)
out["loss_sharded"] = float(loss_shd)

diffs = jax.tree_util.tree_map(
    lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
    p_ref, jax.device_get(p_shd),
)
out["max_param_diff"] = max(jax.tree_util.tree_leaves(diffs))

# elastic checkpoint: save from the (2,4) mesh, restore onto (4,2)
ck = os.environ["CK_DIR"]
save_checkpoint(ck, 1, p_shd)
mesh2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
p_sh2 = param_sharding(mesh2, p_specs)
restored, step, _ = restore_checkpoint(ck, params, shardings=p_sh2)
rd = jax.tree_util.tree_map(
    lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
    jax.device_get(p_shd), jax.device_get(restored),
)
out["restore_diff"] = max(jax.tree_util.tree_leaves(rd))
out["restore_step"] = step

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_result(tmp_path_factory):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["CK_DIR"] = str(tmp_path_factory.mktemp("ck"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_training_matches_unsharded(subproc_result):
    r = subproc_result
    assert abs(r["loss_ref"] - r["loss_sharded"]) < 1e-4
    assert r["max_param_diff"] < 5e-3  # bf16 params, reduction-order noise


def test_elastic_checkpoint_restore(subproc_result):
    assert subproc_result["restore_diff"] == 0.0
    assert subproc_result["restore_step"] == 1


@pytest.mark.parametrize("backend", ["partitioned", "sharded"])
def test_partitioned_mining_matches_direct(small_ds, backend):
    from repro.launch.mine import mine_partitioned
    from repro.core.compiler import CompiledPattern
    from repro.core.patterns import build_pattern

    g = small_ds.graph
    counts, plan, timing = mine_partitioned(
        g, "cycle3", 4096, n_parts=4, backend=backend
    )
    direct = CompiledPattern(build_pattern("cycle3", 4096), g).mine()
    np.testing.assert_array_equal(counts, direct)
    assert plan.skew < 1.3
    assert len(timing["per_part"]) == 4
    if backend == "sharded":
        assert timing["host_syncs"] == 1

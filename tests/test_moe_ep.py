"""shard_map expert-parallel MoE == plain (meshless) MoE, 8 fake devices."""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs.registry import smoke_config
from repro.distributed import ctx
from repro.models.layers import moe_apply, moe_apply_shard_map, moe_init

cfg = smoke_config("mixtral-8x7b")
cfg = dataclasses.replace(cfg, dtype="float32")
# no drops: capacity is per-data-shard in EP mode, so oversize it
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=32.0))
p = moe_init(jax.random.key(0), cfg)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(32, cfg.d_model)).astype(np.float32))

y_ref, aux_ref = moe_apply(p, x, cfg)

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
ctx.set_axes(mesh, ("data",), ("model",))
y_ep, aux_ep = jax.jit(lambda p, x: moe_apply_shard_map(p, x, cfg))(p, x)

# expert-TP path: 2 experts cannot shard over the 4-way model axis
cfg2 = dataclasses.replace(
    cfg, moe=dataclasses.replace(cfg.moe, n_experts=2, top_k=1)
)
p2 = moe_init(jax.random.key(1), cfg2)
y2_ref, _ = moe_apply(p2, x, cfg2)
y2_ep, _ = jax.jit(lambda p, x: moe_apply_shard_map(p, x, cfg2))(p2, x)
ctx.clear()

err = float(jnp.max(jnp.abs(y_ref - y_ep)))
aerr = abs(float(aux_ref) - float(aux_ep))
err_tp = float(jnp.max(jnp.abs(y2_ref - y2_ep)))
print("RESULT " + json.dumps({"err": err, "aux_err": aerr, "err_tp": err_tp}))
"""


def test_shard_map_moe_matches_plain():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_OPTS", None)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["err"] < 2e-4, out
    # expert-TP reorders the FFN partial sums across the psum: ~1e-4 noise
    assert out["err_tp"] < 1e-3, out
    # aux is a per-shard load-balance estimate under EP (E[m_r c_r] vs
    # m c globally) — close but not identical
    assert out["aux_err"] < 5e-3, out

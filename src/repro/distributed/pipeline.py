"""GPipe-style pipeline parallelism over shard_map + collective_permute.

For the deep dense architectures (deepseek-coder-33b: 62 layers) a third
parallelism axis beyond DP x TP can pay off at pod scale.  This module
implements synchronous GPipe: the layer stack is split into S stages laid
out along a ``pipe`` mesh axis; microbatches stream through stages with
``jax.lax.ppermute`` moving activations stage-to-stage.  The classic
schedule runs M + S - 1 ticks for M microbatches (bubble fraction
(S-1)/(M+S-1)).

Forward-only is implemented explicitly (serving / evaluating); training
composes this with jax.grad through shard_map.  The unit-scan body reuses
the model-zoo blocks, so any homogeneous-unit arch can be piped.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward", "pipeline_spec"]


def pipeline_spec(n_stages: int, n_micro: int):
    assert n_micro >= n_stages, "GPipe wants microbatches >= stages"
    return {"n_stages": n_stages, "n_micro": n_micro}


def pipeline_forward(
    mesh: Mesh,
    stage_fn: Callable,  # (stage_params, x) -> x
    stage_params,  # pytree with leading dim = n_stages (sharded on "pipe")
    x,  # (n_micro, micro_batch, ...) activations
    axis: str = "pipe",
):
    """Run x through all stages; returns activations after the last stage.

    Each device along `axis` holds ONE stage's params. Tick t: device s
    processes microbatch (t - s) if 0 <= t - s < M, then activations
    ppermute to s+1.  After M + S - 1 ticks every microbatch passed every
    stage; results are gathered back to the (n_micro, ...) layout.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]

    def body(params, xs):
        # params: this stage's slice (leading dim 1); xs: (M, mb, ...)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        s = jax.lax.axis_index(axis)
        total = m + n_stages - 1
        buf = jnp.zeros_like(xs)  # outputs of the LAST stage per microbatch
        carry = jnp.zeros_like(xs[0])  # activation arriving at this stage

        def tick(t, state):
            carry, buf = state
            mb_idx = t - s  # microbatch this stage works on at tick t
            active = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 ingests fresh microbatches; others take the carry
            inp = jnp.where(
                s == 0, xs[jnp.clip(t, 0, m - 1)], carry
            )
            out = stage_fn(params, inp)
            out = jnp.where(active, out, carry)
            # the last stage banks its result
            buf = jnp.where(
                (s == n_stages - 1) & active,
                buf.at[jnp.clip(mb_idx, 0, m - 1)].set(out),
                buf,
            )
            # everyone forwards to the next stage (ring; last->0 ignored)
            nxt = jax.lax.ppermute(
                out,
                axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return nxt, buf

        carry, buf = jax.lax.fori_loop(0, total, tick, (carry, buf))
        # only the last stage holds real outputs; broadcast them
        buf = jax.lax.psum(
            jnp.where(s == n_stages - 1, buf, jnp.zeros_like(buf)), axis
        )
        return buf

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),
    )
    try:  # jax>=0.8 renamed check_rep -> check_vma
        fn = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
        )
    except TypeError:  # pragma: no cover
        fn = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=P(), check_rep=False
        )
    return fn(stage_params, x)

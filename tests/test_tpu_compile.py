"""Main-path kernels compiled for a described TPU v5e (no chip needed).

The TPU compiler is installed even where no chip is attached: it
compiles for a ``v5e:2x2`` topology described here, and refuses what
the chip's compiler would refuse (unaligned blocks, too much VMEM, a
relayout Mosaic cannot do) — which interpret mode never shows.  The
topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.compiler import BUCKET_LADDER, CompiledPattern
from repro.core.patterns import build_pattern
from repro.graph.csr import DeviceGraph
from repro.kernels.intersect_count import intersect_count
from tests.conftest import random_temporal_graph


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("ordered", [False, True])
@pytest.mark.parametrize(
    "da,db", [(BUCKET_LADDER[-1], BUCKET_LADDER[-1]), (BUCKET_LADDER[0], 64), (1, 1024)]
)
def test_intersect_count_lowers_to_mosaic(one_chip, no_persistent_cache, da, db, ordered):
    b = 4096
    fn = jax.jit(
        functools.partial(intersect_count, ordered=ordered, interpret=False)
    )
    a2, b2, r = _sds((b, da), one_chip), _sds((b, db), one_chip), _sds((b,), one_chip)
    compiled = fn.lower(a2, a2, b2, b2, r, r, r, r).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "name,strat,dims",
    [("scatter_gather", 2, (1, 4, 1024)), ("cycle4", 1, (1, 1, 1024))],
    ids=["pw", "bs2"],
)
def test_xla_mining_kernel_compiles(one_chip, no_persistent_cache, name, strat, dims):
    """One bucket kernel of the XLA lowering at the widest ladder width,
    against a device graph of HI-Small's published size (8M-edge pow2
    arrays), at the chunk width the executor's element cap allows."""
    rng = np.random.default_rng(0)
    g = random_temporal_graph(rng, n_nodes=32, n_edges=256, t_max=4096)
    cp = CompiledPattern(build_pattern(name, 4096), g)
    kernel = jax.jit(cp._build_kernel(strat, dims, (1,) * len(dims), False))
    _compile_at_hi_small(kernel, g, dims, one_chip)


def test_witness_hub_branch_kernel_compiles(one_chip, no_persistent_cache):
    """A hub-branch witness kernel (one level-1 item per row, the item's
    scatter-source row swept in-kernel) at the widest ladder width."""
    from repro.witness.extract import _build_witness_kernel

    rng = np.random.default_rng(0)
    g = random_temporal_graph(rng, n_nodes=32, n_edges=256, t_max=4096)
    cp = CompiledPattern(build_pattern("scatter_gather", 4096), g)
    dims, sweeps = (1, BUCKET_LADDER[-1], 1), (1, 4, 1)
    kernel = jax.jit(
        _build_witness_kernel(cp.ir, cp.n_iters, 0, dims, sweeps, 2, True)
    )
    _compile_at_hi_small(kernel, g, dims, one_chip)


def _compile_at_hi_small(kernel, g, dims, one_chip):
    n_nodes, n_edges = 1 << 19, 1 << 23
    small = g.to_device()
    dg = DeviceGraph(
        n_nodes=n_nodes,
        n_edges=n_edges,
        max_deg=small.max_deg,
        **{
            k: _sds(
                (n_nodes + 1,) if k.endswith("indptr") else (n_edges,),
                one_chip,
                v.dtype,
            )
            for k, v in small.arrays().items()
        },
    )
    b = (1 << 22) // int(np.prod(dims))
    s = _sds((b,), one_chip)
    compiled = kernel.lower(dg, s, s, s, s, s).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def test_collective_gather_reduction_compiles(topo, no_persistent_cache):
    """The sharded mine's cross-shard sum over a 4-chip mesh lowers to
    an all-reduce."""
    from repro.core.shard import _sum_shards_jit
    from repro.launch.mesh import make_shard_mesh

    mesh = make_shard_mesh(topo.devices[:4])
    x = _sds((4, 1 << 16), NamedSharding(mesh, PartitionSpec("shard")))
    compiled = _sum_shards_jit.lower(x).compile()
    assert "all-reduce" in compiled.as_text()

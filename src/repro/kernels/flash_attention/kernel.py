"""Pallas TPU kernel: fused causal attention (FlashAttention-style fwd).

Why it exists here: the dry-run roofline shows every train/prefill cell
memory-bound, dominated by materialized (B,H,Tq,S) score/softmax traffic
(~8 HBM passes per chunk in the unfused XLA lowering).  The fused kernel
streams K/V blocks through VMEM with an online-softmax accumulator, so
score tiles never touch HBM — traffic drops from O(H·T·S) to O(T·d).

Tiling: grid (B·H, T/bq).  Each step holds one (bq, hd) query tile plus
the full (S, hd) K and V rows for that head in VMEM and walks S in bk
chunks with a fori_loop carrying (m, l, acc) — the standard online
softmax.  VMEM budget = 2·S·hd + O(bq·hd); fine for S <= 8k at hd=128
(the train_4k/SSD-chunk regime).  For 32k+ sequences the production
variant adds a third grid axis over S with an HBM accumulator; that
variant is TPU-only and not exercised in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]

NEG = -1e30


def _kernel(bq: int, bk: int, causal: bool, scale: float, q_ref, k_ref, v_ref, o_ref):
    qi = pl.program_id(1)  # query tile index
    q = q_ref[...].astype(jnp.float32) * scale  # (bq, hd)
    s_len = k_ref.shape[0]
    nk = s_len // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T  # (bq, bk)
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # fully-masked blocks leave m_new at NEG: exp(NEG-NEG)=1 would
        # poison l/acc, so zero masked probabilities explicitly
        p = jnp.where(s > 0.5 * NEG, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q_ref.shape[1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q, k, v, *, causal: bool = True, block_q: int = 128, block_k: int = 128,
    interpret: bool = True,
):
    """q (BH, T, hd), k/v (BH, S, hd) -> (BH, T, hd)."""
    bh, t, hd = q.shape
    s = k.shape[1]
    assert t % block_q == 0 and s % block_k == 0
    scale = 1.0 / math.sqrt(hd)
    grid = (bh, t // block_q)
    return pl.pallas_call(
        functools.partial(_kernel, block_q, block_k, causal, scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)

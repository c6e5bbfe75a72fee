"""Transformer layers: norms, RoPE, GQA attention (full/SWA, train/decode),
SwiGLU MLP, and sort-based top-k MoE with static capacity (EP-shardable).

Pure-functional: ``*_init`` builds a param pytree, ``*_apply`` consumes it.
All inits are wrapped in ``jax.eval_shape`` at dry-run time, so full-size
params never materialize on CPU.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import ctx, opts

__all__ = [
    "rms_norm",
    "rms_norm_init",
    "rope",
    "attn_init",
    "attn_apply",
    "attn_decode",
    "mlp_init",
    "mlp_apply",
    "moe_init",
    "moe_apply",
]

NEG = -1e30


def _dense(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * scale


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------
def rms_norm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}

def rms_norm(p, x, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def rope(x, positions, theta: float):
    """x (..., T, H, hd); positions (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense(ks[0], (d, h * hd)),
        "wk": _dense(ks[1], (d, kv * hd)),
        "wv": _dense(ks[2], (d, kv * hd)),
        "wo": _dense(ks[3], (h * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((kv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd)
        p["k_norm"] = rms_norm_init(hd)
    return p


def _qkv(p, x, cfg: ModelConfig, positions):
    b, t, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, t, h, hd)
    k = k.reshape(b, t, kv, hd)
    v = v.reshape(b, t, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask):
    """q (B,T,K,G,hd), k/v (B,S,K,hd), mask (T,S) or (B,T,S)."""
    hd = q.shape[-1]
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k) / math.sqrt(hd)
    if not opts.enabled("bf16_scores"):
        scores = scores.astype(jnp.float32)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, None, None]
    scores = jnp.where(mask, scores, jnp.asarray(NEG, scores.dtype))
    # softmax reduces in f32 regardless of the score storage dtype
    m = jnp.max(scores.astype(jnp.float32), axis=-1, keepdims=True)
    e = jnp.exp(scores.astype(jnp.float32) - m)
    w = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(q.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v)
    return out


Q_CHUNK = 1024  # query-chunked attention above this T (bounds score temps)


def attn_apply(p, x, cfg: ModelConfig, positions=None):
    """Training/prefill: full-sequence causal (optionally sliding-window).

    For T > Q_CHUNK the query axis is processed in chunks via lax.scan so
    the score temporary is (B, H, Q_CHUNK, T) instead of (B, H, T, T) —
    the pure-XLA stand-in for a fused flash kernel (see DESIGN.md; on TPU
    the same contraction pattern is the flash-attention Pallas kernel's
    job, but the dry-run lowers the XLA path).
    """
    b, t, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)[None, :].repeat(b, 0)
    q, k, v = _qkv(p, x, cfg, positions)
    q = q.reshape(b, t, kv, g, hd)
    j = jnp.arange(t, dtype=jnp.int32)[None, :]

    if t <= Q_CHUNK:
        i = jnp.arange(t, dtype=jnp.int32)[:, None]
        mask = j <= i
        if cfg.attn_window is not None:
            mask = mask & (i - j < cfg.attn_window)
        out = _sdpa(q, k, v, mask)
    else:
        assert t % Q_CHUNK == 0, "pad sequence to the attention chunk"
        nq = t // Q_CHUNK
        qc = q.reshape(b, nq, Q_CHUNK, kv, g, hd).swapaxes(0, 1)

        def chunk_fn(_, inp):
            qi, idx = inp
            i = (idx * Q_CHUNK + jnp.arange(Q_CHUNK, dtype=jnp.int32))[:, None]
            mask = j <= i
            if cfg.attn_window is not None:
                mask = mask & (i - j < cfg.attn_window)
            return None, _sdpa(qi, k, v, mask)

        # remat the chunk body: otherwise the scan stores every chunk's
        # (B,H,Q_CHUNK,T) softmax weights for backward = the full T x T
        # attention matrix in HBM (23 GB/chip at qwen2 train_4k)
        _, oc = jax.lax.scan(
            jax.checkpoint(chunk_fn),
            None,
            (qc, jnp.arange(nq, dtype=jnp.int32)),
            unroll=True if cfg.unroll_stack else 1,
        )  # (nq, B, Q_CHUNK, kv, g, hd)
        out = oc.swapaxes(0, 1).reshape(b, t, kv, g, hd)
    out = out.reshape(b, t, h * hd)
    return out @ p["wo"].astype(x.dtype)


def attn_decode(p, x, cfg: ModelConfig, cache: dict):
    """One-token decode against a KV cache.

    cache: {"k": (B,S,kv,hd), "v": (B,S,kv,hd), "pos": (B,) int32}.
    S is the cache *capacity*: full seq_len for full attention, or the
    window size for sliding-window attention, in which case the cache is a
    ring buffer (slot = pos % S) — RoPE is applied at absolute positions
    when keys are written, so slots need no re-rotation.
    """
    b, t, d = x.shape
    assert t == 1
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    pos = cache["pos"]  # (B,)
    q, k, v = _qkv(p, x, cfg, pos[:, None])
    s = cache["k"].shape[1]
    slot = pos % s
    ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))(
        cache["k"], k, slot
    )
    cv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))(
        cache["v"], v, slot
    )
    j = jnp.arange(s, dtype=jnp.int32)[None, :]  # (1,S)
    # ring semantics: before wrap only slots <= pos are live; after wrap all
    mask = (j <= pos[:, None]) | (pos[:, None] >= s)
    q = q.reshape(b, 1, kv, g, hd)
    if opts.enabled("decode_hint"):
        # pin the attention operands to the CACHE layout so the partitioner
        # doesn't bounce the 32k-token cache between shardings per op
        if opts.enabled("kv_seq_model"):
            tpl = ("data", "model", None, None)
        else:
            tpl = ("data", None, None, "model")
        ck = ctx.hint(ck, tpl)
        cv = ctx.hint(cv, tpl)
    out = _sdpa(q, ck, cv, mask[:, None, :])  # (B,1,S) mask
    out = out.reshape(b, 1, h * hd) @ p["wo"].astype(x.dtype)
    new_cache = {"k": ck, "v": cv, "pos": pos + 1}
    return out, new_cache


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------
def mlp_init(key, d: int, d_ff: int):
    ks = jax.random.split(key, 3)
    return {
        "w1": _dense(ks[0], (d, d_ff)),
        "w3": _dense(ks[1], (d, d_ff)),
        "w2": _dense(ks[2], (d_ff, d)),
    }


def mlp_apply(p, x):
    h = jax.nn.silu(x @ p["w1"].astype(x.dtype)) * (x @ p["w3"].astype(x.dtype))
    return h @ p["w2"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (top-k, static capacity, sort-based dispatch; EP over the expert dim)
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig):
    m = cfg.moe
    d, e, f = cfg.d_model, m.n_experts, m.d_expert_ff
    ks = jax.random.split(key, 4)
    return {
        "router": _dense(ks[0], (d, e)),
        "w1": _dense(ks[1], (e, d, f)),
        "w3": _dense(ks[2], (e, d, f)),
        "w2": _dense(ks[3], (e, f, d)),
    }


def moe_apply_shard_map(p, x, cfg: ModelConfig):
    """Explicit-EP MoE: shard_map over (data, model).

    Insight (EXPERIMENTS.md §Perf P8): activations are replicated across
    the model axis between TP blocks, so expert parallelism needs NO token
    exchange at all — every (data, model) rank dispatches its local tokens
    against its LOCAL expert slice and the per-token expert outputs are
    summed with one (T_local, d) psum over the model axis: the exact
    communication pattern of a dense Megatron FFN.  The pjit hint-based
    lowering (P7) was refuted — the partitioner all-gathered the token
    buffer; shard_map makes the locality explicit.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, data_axes, model_axes = ctx.mesh_and_axes()
    m = cfg.moe
    t, d = x.shape
    e = m.n_experts
    msize = ctx.model_size()
    dsize = ctx.data_size()
    f = m.d_expert_ff
    # expert-dim EP when experts divide the model axis; otherwise
    # expert-TP (shard the FFN dim, all experts on every rank) — mixtral's
    # 8 experts on a 16-way model axis take this path
    expert_ep = e % max(msize, 1) == 0
    if (
        mesh is None
        or t % max(dsize, 1) != 0
        or (not expert_ep and f % max(msize, 1) != 0)
        or (dsize == 1 and msize == 1)
    ):
        return moe_apply(p, x, cfg)

    e_local = e // msize if expert_ep else e
    tl = t // dsize
    cap = int(max(1, math.ceil(tl * m.top_k / e * m.capacity_factor)))

    def body(pl_, xl):
        # local dispatch of tl tokens over ALL experts (replicated math)
        logits = (xl @ pl_["router"].astype(xl.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, m.top_k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        fe = idx.reshape(-1)
        ft = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), m.top_k)
        fg = gates.reshape(-1)
        order = jnp.argsort(fe)
        se, st_, sg = fe[order], ft[order], fg[order]
        starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
        pos = jnp.arange(tl * m.top_k, dtype=jnp.int32) - starts[se]
        keep = pos < cap
        slot = jnp.where(keep, se * cap + pos, e * cap)
        buf = (
            jnp.zeros((e * cap + 1, d), xl.dtype)
            .at[slot]
            .set(xl[st_], mode="drop")[: e * cap]
            .reshape(e, cap, d)
        )
        r = jax.lax.axis_index(model_axes[0]) if model_axes else 0
        if expert_ep:
            # this rank's expert slice (weights already local (e_local,...))
            local = jax.lax.dynamic_slice(
                buf, (r * e_local, 0, 0), (e_local, cap, d)
            )
        else:  # expert-TP: all experts, FFN dim sharded (weights local f/m)
            local = buf
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", local, pl_["w1"].astype(xl.dtype))
        )
        h = h * jnp.einsum("ecd,edf->ecf", local, pl_["w3"].astype(xl.dtype))
        yb = jnp.einsum("ecf,efd->ecd", h, pl_["w2"].astype(xl.dtype))
        # place local expert outputs into the global buffer layout; the
        # psum over model sums expert slices (EP) or partial FFN sums (TP)
        if expert_ep:
            ybuf = jax.lax.dynamic_update_slice(
                jnp.zeros((e * cap, d), xl.dtype),
                yb.reshape(e_local * cap, d),
                (r * e_local * cap, jnp.int32(0)),
            )
        else:
            ybuf = yb.reshape(e * cap, d)
        contrib = ybuf[jnp.minimum(slot, e * cap - 1)] * sg[:, None].astype(
            xl.dtype
        )
        contrib = jnp.where(keep[:, None], contrib, 0)
        y = jax.ops.segment_sum(contrib, st_, num_segments=tl)
        if model_axes:
            y = jax.lax.psum(y, model_axes)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
        aux = m.router_aux_weight * e * jnp.sum(me * ce)
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        if model_axes:
            aux = jax.lax.pmean(aux, model_axes)
        return y, aux

    ma = model_axes or None
    if expert_ep:
        pspec = {
            "router": P(),
            "w1": P(ma, None, None),
            "w3": P(ma, None, None),
            "w2": P(ma, None, None),
        }
    else:  # expert-TP: column-shard w1/w3, row-shard w2
        pspec = {
            "router": P(),
            "w1": P(None, None, ma),
            "w3": P(None, None, ma),
            "w2": P(None, ma, None),
        }
    kw = dict(
        mesh=mesh,
        in_specs=(pspec, P(data_axes or None, None)),
        out_specs=(P(data_axes or None, None), P()),
    )
    try:  # jax>=0.8 renamed check_rep -> check_vma
        fn = shard_map(body, check_vma=False, **kw)
    except TypeError:  # pragma: no cover
        fn = shard_map(body, check_rep=False, **kw)
    return fn(p, x)


def moe_apply(p, x, cfg: ModelConfig):
    """x (T, d) -> (y (T, d), aux_loss).  Static capacity C per expert;
    overflow tokens are dropped (standard GShard/Switch semantics).

    Locality-aware two-stage EP dispatch: tokens are viewed as
    (R, T/R, d) with R = data-parallel group size; routing, sort and the
    capacity scatter happen *within* each row (local to its data shard),
    and only the compact (R, E, C_local, d) expert buffer crosses the
    mesh — the sharding hint flips it from row(data)-sharded to
    expert(model)-sharded, which XLA lowers to the canonical MoE
    all-to-all.  Without this, the partitioner all-gathers the full token
    buffer per layer (measured 300 s/step collective term on
    moonshot-16B train_4k — EXPERIMENTS.md §Perf P7).  With R = 1
    (meshless smoke tests) the semantics reduce to plain global dispatch.
    """
    m = cfg.moe
    t, d = x.shape
    e, k = m.n_experts, m.top_k
    r = ctx.data_size()
    if t % max(r, 1) != 0:
        r = 1
    tl = t // r  # tokens per local row

    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # (T,k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    cap = int(max(1, math.ceil(tl * k / e * m.capacity_factor)))
    fe = idx.reshape(r, tl * k)  # per-row flat expert ids
    ft = jnp.tile(
        jnp.repeat(jnp.arange(tl, dtype=jnp.int32), k)[None], (r, 1)
    )
    fg = gates.reshape(r, tl * k)
    order = jnp.argsort(fe, axis=-1)
    se = jnp.take_along_axis(fe, order, axis=-1)
    st_ = jnp.take_along_axis(ft, order, axis=-1)
    sg = jnp.take_along_axis(fg, order, axis=-1)
    starts = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(e, dtype=row.dtype))
    )(se)  # (R, E)
    pos = jnp.arange(tl * k, dtype=jnp.int32)[None] - jnp.take_along_axis(
        starts, se, axis=-1
    )
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, e * cap)  # (R, TLk) in [0, E*cap]

    xd = ctx.hint(x.reshape(r, tl, d), ("data", None, None))
    flat_slot = (
        jnp.arange(r, dtype=jnp.int32)[:, None] * (e * cap + 1) + slot
    ).reshape(-1)
    flat_src = (
        jnp.arange(r, dtype=jnp.int32)[:, None] * tl + st_
    ).reshape(-1)
    buf = (
        jnp.zeros((r * (e * cap + 1), d), x.dtype)
        .at[flat_slot]
        .set(xd.reshape(r * tl, d)[flat_src], mode="drop")
    )
    hbuf = buf.reshape(r, e * cap + 1, d)[:, : e * cap].reshape(r, e, cap, d)
    # the all-to-all boundary: rows(data) -> experts(model)
    hbuf = ctx.hint(hbuf, (None, "model", None, None))
    h = jax.nn.silu(jnp.einsum("recd,edf->recf", hbuf, p["w1"].astype(x.dtype)))
    h = h * jnp.einsum("recd,edf->recf", hbuf, p["w3"].astype(x.dtype))
    yb = jnp.einsum("recf,efd->recd", h, p["w2"].astype(x.dtype))
    # back: experts(model) -> rows(data)
    yb = ctx.hint(yb, ("data", None, None, None))
    ybuf = yb.reshape(r, e * cap, d)

    gslot = jnp.minimum(slot, e * cap - 1)
    contrib = jnp.take_along_axis(
        ybuf, gslot[..., None].astype(jnp.int32), axis=1
    ) * sg[..., None].astype(x.dtype)
    contrib = jnp.where(keep[..., None], contrib, 0)
    seg = (jnp.arange(r, dtype=jnp.int32)[:, None] * tl + st_).reshape(-1)
    y = jax.ops.segment_sum(
        contrib.reshape(r * tl * k, d), seg, num_segments=r * tl
    )
    y = ctx.hint(y.reshape(r, tl, d), ("data", None, None)).reshape(t, d)

    # GShard load-balancing aux loss
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0
    )  # top-1 dispatch fraction
    aux = m.router_aux_weight * e * jnp.sum(me * ce)
    return y.astype(x.dtype), aux

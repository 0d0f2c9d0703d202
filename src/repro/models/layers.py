"""Building-block layers: norms, RoPE, GQA attention, MLP, MoE.

Pure-functional style: every block is ``init_*(key, cfg) -> params`` plus an
apply function.  Tensor-parallel sharding is expressed with *constraints on
the 'model' mesh axis only* (the DP axes are manual inside the training
shard_map and must never appear here); the :func:`shard` helper silently
no-ops when there is no mesh (CPU unit tests) or the named axis is absent
or manual.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig, MoEConfig


# ---------------------------------------------------------------------------
# sharding helper
# ---------------------------------------------------------------------------

def shard(x: jax.Array, *entries):
    """with_sharding_constraint that tolerates missing/manual mesh axes."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = set(mesh.axis_names) - set(mesh.manual_axes)

    def ok(axis) -> bool:
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        return all(a in auto for a in axes)

    cleaned = tuple(a if a is not None and ok(a) else None for a in entries)
    if all(a is None for a in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"]).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> dict:
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    dt = _dtype(cfg)
    p = {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * std).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, k * hd)) * std).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, k * hd)) * std).astype(dt),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * std).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((k * hd,), dt)
        p["bv"] = jnp.zeros((k * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def attention_pspecs(cfg: ModelConfig) -> dict:
    """TP PartitionSpecs: Q/O sharded over heads, KV replicated (GQA-safe)."""
    p = {"wq": P(None, "model"), "wk": P(), "wv": P(),
         "wo": P("model", None)}
    if cfg.qkv_bias:
        p.update({"bq": P("model"), "bk": P(), "bv": P()})
    if cfg.qk_norm:
        p.update({"q_norm": {"scale": P()}, "k_norm": {"scale": P()}})
    return p


def _qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions):
    b, s, d = x.shape
    h, k, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = x @ p["wq"]
    kk = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q = shard(q.reshape(b, s, h, hd), None, None, "model", None)
    kk = kk.reshape(b, s, k, hd)
    v = v.reshape(b, s, k, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        kk = rmsnorm(p["k_norm"], kk, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    kk = rope(kk, positions, cfg.rope_theta)
    return q, kk, v


def _gqa_scores(q: jax.Array, k: jax.Array, cfg: ModelConfig) -> jax.Array:
    """q: (B,S,H,hd), k: (B,T,K,hd) -> scores (B,K,G,S,T); H = K*G."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    q = q.reshape(b, s, kv, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32)
    return scores / math.sqrt(hd)


def _gqa_out(scores: jax.Array, v: jax.Array, cfg: ModelConfig) -> jax.Array:
    """scores (B,K,G,S,T) x v (B,T,K,hd) -> (B,S,H*hd)."""
    b, kv, g, s, t = scores.shape
    out = jnp.einsum("bkgst,btkh->bskgh", scores.astype(v.dtype), v)
    return out.reshape(b, s, kv * g * v.shape[-1])


#: above this sequence length, attention runs double-blocked (flash-style)
FLASH_SEQ_THRESHOLD = 2048
FLASH_BLOCK_Q = 1024
FLASH_BLOCK_K = 1024


def _mask_block(iq: jax.Array, jk: jax.Array, *, causal: bool,
                window, is_global) -> jax.Array:
    """(bq, bk) bool mask from absolute query/key positions."""
    i = iq[:, None]
    j = jk[None, :]
    m = (j <= i) if causal else jnp.ones((iq.shape[0], jk.shape[0]), bool)
    if window is not None:
        local = m & (i - j < window)
        m = jnp.where(jnp.asarray(is_global), m, local)
    return m


def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, window, is_global, scale: float,
                     block_q: int = FLASH_BLOCK_Q,
                     block_k: int = FLASH_BLOCK_K) -> jax.Array:
    """Double-blocked online-softmax attention (memory O(S * block)).

    q: (B,S,KV,G,hd); k, v: (B,T,KV,hd).  Returns (B,S,KV,G,hd).
    Blockwise numerically-stable softmax: per query block, scan key blocks
    carrying (running max, denominator, weighted accumulator).  This keeps
    the 32k/500k-token cells compilable without a quadratic score buffer —
    the flash-attention recurrence expressed in pure lax (XLA fuses it per
    block; a Pallas attention kernel is an orthogonal optimization to the
    paper's contribution and intentionally out of scope, see DESIGN.md).
    """
    b, s, kv, g, hd = q.shape
    t = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, t)
    pad_q = (-s) % bq
    pad_k = (-t) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (s + pad_q) // bq, (t + pad_k) // bk
    qb = q.reshape(b, nq, bq, kv, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nk, bk, kv, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, kv, hd).transpose(1, 0, 3, 2, 4)
    # qb: (nq, B, KV, G, bq, hd); kb/vb: (nk, B, KV, bk, hd)

    def q_block(carry, qi):
        qblk, iq0 = qi                      # (B,KV,G,bq,hd), scalar

        def k_block(state, ki):
            kblk, vblk, jk0 = ki
            m_run, l_run, acc = state
            sc = jnp.einsum("bkgqh,bkth->bkgqt", qblk, kblk,
                            preferred_element_type=jnp.float32) * scale
            iq = iq0 + jnp.arange(bq)
            jk = jk0 + jnp.arange(bk)
            mask = _mask_block(iq, jk, causal=causal, window=window,
                               is_global=is_global)
            sc = jnp.where(mask[None, None, None], sc, -1e30)
            m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bkth->bkgqh", p.astype(vblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kv, g, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kv, g, bq, hd), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0),
            (kb, vb, jnp.arange(nk) * bk))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return carry, out

    _, outs = jax.lax.scan(q_block, None, (qb, jnp.arange(nq) * bq))
    # outs: (nq, B, KV, G, bq, hd) -> (B, S, KV, G, hd)
    outs = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * bq, kv, g, hd)
    return outs[:, :s].astype(q.dtype)


def attn_forward(p: dict, x: jax.Array, cfg: ModelConfig, *,
                 is_global=True, positions=None, causal: bool = True
                 ) -> jax.Array:
    """Full-sequence attention (training / prefill).

    ``is_global`` may be a traced bool (scan-over-layers with a per-layer
    local/global pattern): both masks are cheap, only one set of einsums.
    Long sequences take the blockwise flash path automatically.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    kv, hd = cfg.num_kv_heads, cfg.hd
    g = cfg.num_heads // kv

    if s > FLASH_SEQ_THRESHOLD:
        q5 = q.reshape(b, s, kv, g, hd)
        out = _flash_attention(q5, k, v, causal=causal,
                               window=cfg.sliding_window,
                               is_global=is_global,
                               scale=1.0 / math.sqrt(hd))
        out = out.reshape(b, s, kv * g * hd)
        out = shard(out, None, None, "model")
        return out @ p["wo"]

    scores = _gqa_scores(q, k, cfg)                     # (B,K,G,S,T)
    i = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    mask = (j <= i) if causal else jnp.ones((s, s), bool)
    if cfg.sliding_window is not None:
        local = mask & (i - j < cfg.sliding_window)
        glob = jnp.asarray(is_global)
        mask = jnp.where(glob, mask, local)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v, cfg)
    out = shard(out, None, None, "model")
    return out @ p["wo"]


def attn_decode(p: dict, x: jax.Array, cfg: ModelConfig, cache_k, cache_v,
                position, *, is_global=True):
    """One-token decode against a KV cache.

    x: (B, 1, d); cache_k/v: (B, S_max, K, hd); position: scalar int32,
    or (B,) int32 for continuous batching — per-row write positions and
    per-row causal masks, so requests at different depths share one step.
    Returns (out (B,1,d), new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    t = cache_k.shape[1]
    jidx = jnp.arange(t)
    if jnp.ndim(position) == 0:
        # scalar path: all rows at the same depth (training-style decode)
        pos = jnp.full((b, 1), position, jnp.int32)
        q, k_new, v_new = _qkv(p, x, cfg, pos)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k_new.astype(cache_k.dtype), (0, position, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v_new.astype(cache_v.dtype), (0, position, 0, 0))
        valid = jidx <= position
        if cfg.sliding_window is not None:
            local = valid & (position - jidx < cfg.sliding_window)
            valid = jnp.where(jnp.asarray(is_global), valid, local)
        mask = valid[None, None, None, None]            # (1,1,1,1,T)
    else:
        # vector path: row i writes/reads at its own position[i]
        pos = jnp.asarray(position, jnp.int32).reshape(b, 1)
        q, k_new, v_new = _qkv(p, x, cfg, pos)
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, pos[:, 0]].set(
            k_new[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos[:, 0]].set(
            v_new[:, 0].astype(cache_v.dtype))
        valid = jidx[None, :] <= pos                    # (B,T)
        if cfg.sliding_window is not None:
            local = valid & (pos - jidx[None, :] < cfg.sliding_window)
            valid = jnp.where(jnp.asarray(is_global), valid, local)
        mask = valid[:, None, None, None, :]            # (B,1,1,1,T)
    scores = _gqa_scores(q, cache_k, cfg)               # (B,K,G,1,T)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, cache_v, cfg)
    out = shard(out, None, None, "model")
    return out @ p["wo"], cache_k, cache_v


def init_cross_attention(key, cfg: ModelConfig) -> dict:
    return init_attention(key, cfg)


def cross_attn_forward(p: dict, x: jax.Array, cfg: ModelConfig,
                       enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V (whisper).

    x: (B,S,d); enc_k/enc_v: (B,T_enc,K,hd) already projected+normalized.
    """
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.hd
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = shard(q.reshape(b, s, h, hd), None, None, "model", None)
    scores = _gqa_scores(q, enc_k, cfg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, enc_v, cfg)
    out = shard(out, None, None, "model")
    return out @ p["wo"]


def cross_kv(p: dict, enc_out: jax.Array, cfg: ModelConfig):
    """Project encoder output to cross-attention K/V once per sequence."""
    b, t, _ = enc_out.shape
    k, hd = cfg.num_kv_heads, cfg.hd
    kk = (enc_out @ p["wk"]).reshape(b, t, k, hd)
    v = (enc_out @ p["wv"]).reshape(b, t, k, hd)
    if cfg.qkv_bias:
        kk = kk + p["bk"].reshape(k, hd)
        v = v + p["bv"].reshape(k, hd)
    return kk, v


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = _dtype(cfg)
    std = 1.0 / math.sqrt(d)
    ks = jax.random.split(key, 3)
    if cfg.mlp_variant == "swiglu":
        return {
            "w_gate": (jax.random.normal(ks[0], (d, f)) * std).astype(dt),
            "w_up": (jax.random.normal(ks[1], (d, f)) * std).astype(dt),
            "w_down": (jax.random.normal(ks[2], (f, d)) / math.sqrt(f)).astype(dt),
        }
    return {
        "w_up": (jax.random.normal(ks[0], (d, f)) * std).astype(dt),
        "w_down": (jax.random.normal(ks[1], (f, d)) / math.sqrt(f)).astype(dt),
    }


def mlp_pspecs(cfg: ModelConfig) -> dict:
    if cfg.mlp_variant == "swiglu":
        return {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                "w_down": P("model", None)}
    return {"w_up": P(None, "model"), "w_down": P("model", None)}


def mlp_forward(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    h = shard(h, None, None, "model")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style grouped capacity dispatch)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, de, e = cfg.d_model, m.d_expert, m.num_experts
    dt = _dtype(cfg)
    std = 1.0 / math.sqrt(d)
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * std).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, de)) * std).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, de)) * std).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, de, d)) / math.sqrt(de)).astype(dt),
    }
    if m.num_shared:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=m.d_expert * m.num_shared)
    return p


def moe_pspecs(cfg: ModelConfig) -> dict:
    p = {"router": P(),
         "w_gate": P("model", None, None),
         "w_up": P("model", None, None),
         "w_down": P("model", None, None)}
    if cfg.moe.num_shared:
        p["shared"] = mlp_pspecs(cfg)
    return p


def moe_forward(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Top-k capacity-based MoE over grouped tokens; experts sharded (EP)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    gs = min(m.group_size, t)
    pad = (-t) % gs
    xt = x.reshape(t, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    g = (t + pad) // gs
    xg = xt.reshape(g, gs, d)
    e, k = m.num_experts, m.top_k
    cap = max(4, int(gs * k / e * m.capacity_factor))
    cap = min(cap, gs)

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)                     # (g, gs, k)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
    topv = topv.astype(_dtype(cfg))

    # capacity assignment: sequential priority over the k choices
    combine = jnp.zeros((g, gs, e, cap), _dtype(cfg))
    counts = jnp.zeros((g, e), jnp.int32)
    for i in range(k):
        onehot = jax.nn.one_hot(topi[..., i], e, dtype=jnp.int32)   # (g,gs,e)
        pos = jnp.cumsum(onehot, axis=1) - 1 + counts[:, None, :]
        counts = counts + jnp.sum(onehot, axis=1)
        keep = (pos < cap) & (onehot > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap,
                                dtype=_dtype(cfg))                  # (g,gs,e,cap)
        combine = combine + pos_oh * (topv[..., i, None, None]
                                      * onehot[..., None].astype(_dtype(cfg)))
    dispatch = (combine > 0).astype(_dtype(cfg))

    expert_in = jnp.einsum("gsec,gsd->gecd", dispatch, xg)
    expert_in = shard(expert_in, None, "model", None, None)
    if "w_gate" in p:
        h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"]))
             * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"]))
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"]))
    h = shard(h, None, "model", None, None)
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    y = jnp.einsum("gsec,gecd->gsd", combine, expert_out)
    y = y.reshape(t + pad, d)[:t].reshape(b, s, d)
    if "shared" in p:
        y = y + mlp_forward(p["shared"], x, cfg)
    return y

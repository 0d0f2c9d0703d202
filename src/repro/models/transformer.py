"""Composable LM assembly: decoder-only, MoE, hybrid, SSM, enc-dec, VLM.

One code path covers all ten assigned architectures.  Homogeneous layer
bodies are *scanned* (``lax.scan`` over stacked parameters — one layer
compiled regardless of depth, essential for the 62-layer dry-runs); per-
layer heterogeneity is expressed as scan-time flag arrays (local/global
attention) or as a small unrolled prefix (DeepSeekMoE's dense first layer).
The 12-block xLSTM stack alternates two parameter shapes and is unrolled.

API surface used by the runtime and launcher:

    init_params(key, cfg)          -> params pytree
    param_pspecs(cfg)              -> PartitionSpec pytree (TP over 'model')
    forward(params, cfg, batch)    -> logits            (train / prefill)
    loss_fn(params, cfg, batch)    -> scalar CE loss
    init_cache(cfg, batch, seq)    -> decode cache pytree
    decode_step(params, cfg, token, cache, position) -> (logits, cache)
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import PartitionSpec as P

from . import layers as L
from . import ssm as S
from .config import ModelConfig


# ---------------------------------------------------------------------------
# per-layer flags
# ---------------------------------------------------------------------------

def global_attention_flags(cfg: ModelConfig):
    """(L,) host bool array: global vs sliding-window attention per layer.

    Host-side numpy so unrolled prefix layers can branch statically; the
    scanned body consumes it as a traced per-layer xs input.
    """
    import numpy as np
    n = cfg.num_layers
    if cfg.sliding_window is None:
        return np.ones((n,), bool)
    if cfg.global_every:                       # gemma3: every Nth is global
        return np.asarray([(i % cfg.global_every) == cfg.global_every - 1
                           for i in range(n)])
    if cfg.family == "hybrid":                 # hymba: first / middle / last
        keep = {0, n // 2, n - 1}
        return np.asarray([i in keep for i in range(n)])
    return np.zeros((n,), bool)               # pure sliding-window


def _is_slstm_block(cfg: ModelConfig, i: int) -> bool:
    e = cfg.ssm.slstm_every if cfg.ssm else 0
    return bool(e) and (i % e == e - 1)


# ---------------------------------------------------------------------------
# single decoder layer (attention family)
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, dense_ffn: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "norm1": L.init_rmsnorm(cfg.d_model),
        "attn": L.init_attention(ks[0], cfg),
        "norm2": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.hybrid_parallel:
        p["ssm_head"] = S.init_mamba_head(ks[2], cfg)
    if cfg.moe is not None and not dense_ffn:
        p["moe"] = L.init_moe(ks[1], cfg)
    elif cfg.d_ff or dense_ffn:
        d_ff = cfg.d_ff
        if dense_ffn and cfg.moe is not None:
            d_ff = cfg.moe.d_expert * (cfg.moe.top_k + cfg.moe.num_shared)
        p["mlp"] = L.init_mlp(ks[1], cfg, d_ff=d_ff)
    return p


def _layer_pspecs(cfg: ModelConfig, dense_ffn: bool = False) -> dict:
    p = {"norm1": {"scale": P()}, "attn": L.attention_pspecs(cfg),
         "norm2": {"scale": P()}}
    if cfg.hybrid_parallel:
        p["ssm_head"] = {
            "w_in": P(None, "model"), "w_dt": P(), "dt_bias": P(),
            "w_bc": P(), "a_log": P("model", None), "skip_scale": P("model"),
            "w_out": P("model", None),
        }
    if cfg.moe is not None and not dense_ffn:
        p["moe"] = L.moe_pspecs(cfg)
    elif cfg.d_ff or dense_ffn:
        p["mlp"] = L.mlp_pspecs(cfg)
    return p


def _layer_forward(p: dict, x: jax.Array, cfg: ModelConfig, is_global,
                   positions) -> jax.Array:
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    a = L.attn_forward(p["attn"], h, cfg, is_global=is_global,
                       positions=positions)
    if cfg.hybrid_parallel:
        m = S.mamba_forward(p["ssm_head"], h, cfg)
        a = 0.5 * (a + m)
    x = x + a
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    if "moe" in p:
        x = x + L.moe_forward(p["moe"], h, cfg)
    elif "mlp" in p:
        x = x + L.mlp_forward(p["mlp"], h, cfg)
    return x


def _layer_decode(p: dict, x: jax.Array, cfg: ModelConfig, cache: dict,
                  position, is_global):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    a, ck, cv = L.attn_decode(p["attn"], h, cfg, cache["k"], cache["v"],
                              position, is_global=is_global)
    new_cache = {"k": ck, "v": cv}
    if cfg.hybrid_parallel:
        m, st = S.mamba_decode(p["ssm_head"], h, cfg,
                               cache["ssm"])
        a = 0.5 * (a + m)
        new_cache["ssm"] = st
    x = x + a
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    if "moe" in p:
        x = x + L.moe_forward(p["moe"], h, cfg)
    elif "mlp" in p:
        x = x + L.mlp_forward(p["mlp"], h, cfg)
    return x, new_cache


# ---------------------------------------------------------------------------
# parameter init / pspecs for the whole model
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab_size
    params: dict[str, Any] = {
        "embed": {"tok": (jax.random.normal(ks[0], (v, d)) * 0.02).astype(dt)},
        "final_norm": L.init_rmsnorm(d),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": (jax.random.normal(ks[1], (d, v))
                                * (1.0 / math.sqrt(d))).astype(dt)}
    if cfg.vision_patches:
        params["embed"]["patch_proj"] = {
            "w": (jax.random.normal(ks[2], (cfg.vision_feat_dim, d))
                  * (1.0 / math.sqrt(cfg.vision_feat_dim))).astype(dt)}

    if cfg.family == "ssm":      # xLSTM: unrolled heterogeneous blocks
        blocks = []
        bkeys = jax.random.split(ks[3], cfg.num_layers)
        for i in range(cfg.num_layers):
            if _is_slstm_block(cfg, i):
                blocks.append({"norm1": L.init_rmsnorm(d),
                               "slstm": S.init_slstm(bkeys[i], cfg)})
            else:
                blocks.append({"norm1": L.init_rmsnorm(d),
                               "mlstm": S.init_mlstm(bkeys[i], cfg)})
        params["blocks"] = blocks
        return params

    if cfg.family == "encdec":   # whisper: encoder + decoder stacks
        params["embed"]["frame_proj"] = {
            "w": (jax.random.normal(ks[2], (d, d)) * (1 / math.sqrt(d))).astype(dt)}
        enc_keys = jax.random.split(ks[4], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: _init_encoder_layer(k, cfg))(enc_keys)
        dec_keys = jax.random.split(ks[5], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_decoder_xlayer(k, cfg))(dec_keys)
        return params

    # decoder-only families (dense / moe / hybrid / vlm)
    n_prefix = cfg.moe.first_dense if cfg.moe else 0
    if n_prefix:
        pkeys = jax.random.split(ks[6], n_prefix)
        params["prefix"] = [_init_layer(pk, cfg, dense_ffn=True)
                            for pk in pkeys]
    body = cfg.num_layers - n_prefix
    lkeys = jax.random.split(ks[7], body)
    params["layers"] = jax.vmap(lambda k: _init_layer(k, cfg))(lkeys)
    return params


def param_pspecs(cfg: ModelConfig) -> dict:
    """PartitionSpec tree matching init_params (TP over 'model' axis)."""
    specs: dict[str, Any] = {
        "embed": {"tok": P("model", None)},
        "final_norm": {"scale": P()},
    }
    if not cfg.tie_embeddings:
        specs["head"] = {"w": P(None, "model")}
    if cfg.vision_patches:
        specs["embed"]["patch_proj"] = {"w": P(None, "model")}

    if cfg.family == "ssm":
        blocks = []
        for i in range(cfg.num_layers):
            if _is_slstm_block(cfg, i):
                blocks.append({"norm1": {"scale": P()}, "slstm": {
                    "w_in": P(None, "model"), "r": P("model", None, None),
                    "bias": P("model"), "w_down": P("model", None)}})
            else:
                blocks.append({"norm1": {"scale": P()}, "mlstm": {
                    "w_up": P(None, "model"), "w_q": P("model", None),
                    "w_k": P("model", None), "w_v": P("model", None),
                    "w_ogate": P(None, "model"), "w_if": P("model", None),
                    "if_bias": P(), "w_down": P("model", None)}})
        specs["blocks"] = blocks
        return specs

    def stack(tree):
        return jax.tree.map(
            lambda s: P(None, *s) if isinstance(s, P) else s, tree,
            is_leaf=lambda x: isinstance(x, P))

    if cfg.family == "encdec":
        specs["embed"]["frame_proj"] = {"w": P(None, "model")}
        specs["encoder"] = stack(_encoder_layer_pspecs(cfg))
        specs["layers"] = stack(_decoder_xlayer_pspecs(cfg))
        return specs

    n_prefix = cfg.moe.first_dense if cfg.moe else 0
    if n_prefix:
        specs["prefix"] = [_layer_pspecs(cfg, dense_ffn=True)
                           for _ in range(n_prefix)]
    specs["layers"] = stack(_layer_pspecs(cfg))
    return specs


# ---------------------------------------------------------------------------
# encoder-decoder layers (whisper)
# ---------------------------------------------------------------------------

def _init_encoder_layer(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 2)
    return {"norm1": L.init_rmsnorm(cfg.d_model),
            "attn": L.init_attention(ks[0], cfg),
            "norm2": L.init_rmsnorm(cfg.d_model),
            "mlp": L.init_mlp(ks[1], cfg)}


def _encoder_layer_pspecs(cfg: ModelConfig) -> dict:
    return {"norm1": {"scale": P()}, "attn": L.attention_pspecs(cfg),
            "norm2": {"scale": P()}, "mlp": L.mlp_pspecs(cfg)}


def _encoder_layer_forward(p: dict, x: jax.Array, cfg: ModelConfig):
    """Bidirectional self-attention encoder layer."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    x = x + L.attn_forward(p["attn"], h, cfg, causal=False)
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    return x + L.mlp_forward(p["mlp"], h, cfg)


def _init_decoder_xlayer(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 3)
    return {"norm1": L.init_rmsnorm(cfg.d_model),
            "attn": L.init_attention(ks[0], cfg),
            "norm_x": L.init_rmsnorm(cfg.d_model),
            "cross": L.init_cross_attention(ks[1], cfg),
            "norm2": L.init_rmsnorm(cfg.d_model),
            "mlp": L.init_mlp(ks[2], cfg)}


def _decoder_xlayer_pspecs(cfg: ModelConfig) -> dict:
    return {"norm1": {"scale": P()}, "attn": L.attention_pspecs(cfg),
            "norm_x": {"scale": P()}, "cross": L.attention_pspecs(cfg),
            "norm2": {"scale": P()}, "mlp": L.mlp_pspecs(cfg)}


def _decoder_xlayer_forward(p: dict, x: jax.Array, cfg: ModelConfig,
                            enc_k, enc_v, positions):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    x = x + L.attn_forward(p["attn"], h, cfg, positions=positions)
    h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
    x = x + L.cross_attn_forward(p["cross"], h, cfg, enc_k, enc_v)
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    return x + L.mlp_forward(p["mlp"], h, cfg)


def _decoder_xlayer_decode(p: dict, x: jax.Array, cfg: ModelConfig,
                           cache: dict, position):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    a, ck, cv = L.attn_decode(p["attn"], h, cfg, cache["k"], cache["v"],
                              position)
    x = x + a
    h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
    x = x + L.cross_attn_forward(p["cross"], h, cfg, cache["cross_k"],
                                 cache["cross_v"])
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    x = x + L.mlp_forward(p["mlp"], h, cfg)
    return x, {"k": ck, "v": cv, "cross_k": cache["cross_k"],
               "cross_v": cache["cross_v"]}


# ---------------------------------------------------------------------------
# embeddings and head
# ---------------------------------------------------------------------------

def _embed_tokens(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  patch_feats: Optional[jax.Array] = None) -> jax.Array:
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if cfg.vision_patches and patch_feats is not None:
        proj = patch_feats.astype(x.dtype) @ params["embed"]["patch_proj"]["w"]
        x = jnp.concatenate([proj, x], axis=1)      # prepend image patches
    return L.shard(x, None, None, None)


def _logits(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].T
    else:
        w = params["head"]["w"]
    logits = x @ w
    return L.shard(logits, None, None, "model")


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------

#: Share of the first device's ``bytes_limit`` that the scanned layers' saved
#: dot outputs may take under ``remat_policy="auto"``.  The step's arguments
#: (weights, optimizer moments) and its other temporaries (gradients, the
#: head's logits, each layer's recomputed attention scores) need the rest: on
#: a 16 GB v5e a quarter (4.2 GB) admits qwen3-0.6b at 8 x 512 tokens (2.6 GB
#: of dots; its compiled step then holds 12.6 GB in all) and rejects it at
#: 2 x 4096 tokens (5.2 GB) and the 48-layer qwen2.5-14b (15.7 GB).
SAVED_DOTS_SHARE = 0.25

_SAVE_DOTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def _device_bytes_limit() -> Optional[int]:
    """Memory limit of the first addressable device; None where the backend
    reports none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def _batch_shards() -> int:
    """Shards of the activations' batch axis that the traced shapes do not
    already show: the data-parallel (non-``model``) axes of the current
    mesh that are still automatic.  Inside the train step's ``shard_map``
    those axes are manual and the shapes are per device already."""
    mesh = jax.sharding.get_abstract_mesh()
    return math.prod(mesh.shape[a] for a in mesh.axis_names
                     if a != "model" and a not in mesh.manual_axes)


def saved_dot_bytes(fn, carry, xs) -> int:
    """Bytes per device that ``dots_with_no_batch_dims_saveable`` keeps for
    the backward pass of one call ``fn(carry, xs)``, found by tracing it:
    the un-batched dot outputs the backward reads, beyond the arguments and
    constants that full remat keeps too."""
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    res = saved_residuals(jax.checkpoint(fn, policy=_SAVE_DOTS),
                          *jax.tree.map(spec, (carry, xs)))
    n = sum(aval.size * aval.dtype.itemsize for aval, src in res
            if not src.startswith("from "))
    return n // _batch_shards()


def _remat_policy(fn, cfg: ModelConfig, carry, xs):
    """The ``jax.checkpoint`` policy of a body scanned over ``xs`` (stacked
    layers); None recomputes everything."""
    if cfg.remat_policy == "full":
        return None
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if cfg.remat_policy != "auto":
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}: "
                         "expected 'auto', 'full' or 'dots'")
    limit = _device_bytes_limit()
    if limit is None:
        return None
    n_layers = jax.tree.leaves(xs)[0].shape[0]
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         xs)
    total = n_layers * saved_dot_bytes(fn, carry, layer)
    return _SAVE_DOTS if total <= SAVED_DOTS_SHARE * limit else None


def _ckpt(fn, cfg: ModelConfig, carry, xs):
    """Per-layer remat of a body scanned as ``lax.scan(fn, carry, xs)``.

    'full' recomputes everything in backward (min memory, but re-runs the
    layer's TP collectives); 'dots' saves every dot output — the tensors
    the SPMD partitioner all-reduces — trading activation memory for a ~1/3
    cut of the per-layer collective traffic (no recomputed ARs).  'auto'
    saves the un-batched dot outputs (the projections and MLP matmuls, not
    the attention or expert einsums) when all layers' worth of them, per
    device, fit :data:`SAVED_DOTS_SHARE` of the device's memory, and
    recomputes everything otherwise or where the device reports no limit.
    The count is of the shapes the body is traced at: under ``vmap``
    virtual workers (``ElasticTrainer``) it is one worker's, W times too
    small.
    """
    if not cfg.remat:
        return fn
    return jax.checkpoint(fn, policy=_remat_policy(fn, cfg, carry, xs))


def forward(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """batch: {'tokens': (B,S)[, 'patch_feats': (B,P,F)][, 'frames': (B,T,d)]}.

    Returns logits (B, S_total, V).
    """
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens, batch.get("patch_feats"))
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]

    if cfg.family == "ssm":
        for i, blk in enumerate(params["blocks"]):
            h = L.rmsnorm(blk["norm1"], x, cfg.norm_eps)
            if "slstm" in blk:
                x = x + S.slstm_forward(blk["slstm"], h, cfg)
            else:
                x = x + S.mlstm_forward(blk["mlstm"], h, cfg)
        return _logits(params, cfg, x)

    if cfg.family == "encdec":
        frames = batch["frames"]
        enc = frames.astype(x.dtype) @ params["embed"]["frame_proj"]["w"]

        def enc_body(h, lp):
            return _encoder_layer_forward(lp, h, cfg), None
        enc_body = _ckpt(enc_body, cfg, enc, params["encoder"])
        enc, _ = jax.lax.scan(enc_body, enc, params["encoder"])

        def dec_body(h, lp):
            ek, ev = L.cross_kv(lp["cross"], enc, cfg)
            return _decoder_xlayer_forward(lp, h, cfg, ek, ev, positions), None
        dec_body = _ckpt(dec_body, cfg, x, params["layers"])
        x, _ = jax.lax.scan(dec_body, x, params["layers"])
        return _logits(params, cfg, x)

    flags = global_attention_flags(cfg)
    n_prefix = cfg.moe.first_dense if cfg.moe else 0
    for i, lp in enumerate(params.get("prefix", [])):
        x = _layer_forward(lp, x, cfg, bool(flags[i]), positions)

    def body(h, xs):
        lp, is_global = xs
        return _layer_forward(lp, h, cfg, is_global, positions), None
    xs = (params["layers"], jnp.asarray(flags[n_prefix:]))
    x, _ = jax.lax.scan(_ckpt(body, cfg, x, xs), x, xs)
    return _logits(params, cfg, x)


def loss_fn(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Mean next-token cross entropy in fp32 (vocab-sharded safe)."""
    logits = forward(params, cfg, batch).astype(jnp.float32)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:       # VLM: drop patch positions
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    ce = logz - gold
    if mask is not None:
        return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(ce)


# ---------------------------------------------------------------------------
# decode path (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               enc_out: Optional[jax.Array] = None,
               dtype=jnp.bfloat16) -> dict:
    """Decode cache pytree sized for ``max_seq`` past tokens."""
    kv = cfg.num_kv_heads
    hd = cfg.hd
    n = cfg.num_layers

    if cfg.family == "ssm":
        blocks = []
        for i in range(n):
            if _is_slstm_block(cfg, i):
                blocks.append({"slstm": S.slstm_init_state(cfg, batch)})
            else:
                blocks.append({"mlstm": S.mlstm_init_state(cfg, batch)})
        return {"blocks": blocks}

    if cfg.family == "encdec":
        cache = {
            "k": jnp.zeros((n, batch, max_seq, kv, hd), dtype),
            "v": jnp.zeros((n, batch, max_seq, kv, hd), dtype),
        }
        t_enc = enc_out.shape[1] if enc_out is not None else cfg.encoder_seq
        cache["cross_k"] = jnp.zeros((n, batch, t_enc, kv, hd), dtype)
        cache["cross_v"] = jnp.zeros((n, batch, t_enc, kv, hd), dtype)
        return cache

    # window-bounded cache for pure sliding-window layers keeps long_500k
    # decode sub-quadratic AND sub-linear in memory for local layers; the
    # (few) global layers keep the full horizon.
    cache = {
        "k": jnp.zeros((n, batch, max_seq, kv, hd), dtype),
        "v": jnp.zeros((n, batch, max_seq, kv, hd), dtype),
    }
    if cfg.hybrid_parallel:
        cache["ssm"] = jnp.zeros((n, batch, cfg.d_model,
                                  cfg.ssm.state_size), jnp.float32)
    return cache


def cache_pspecs(cfg: ModelConfig, *, shard_seq: bool = False,
                 dp_axes=("pod", "data")) -> dict:
    """PartitionSpecs for the decode cache.

    Default: batch over DP, KV heads over model.  ``shard_seq=True`` is the
    long-context (batch=1) layout: the *sequence* dim of the KV cache is
    sharded over the DP axes instead (flash-decode style SP), which GSPMD
    resolves into partial-softmax + combine collectives.
    """
    dp = tuple(dp_axes)
    if cfg.family == "ssm":
        bspec = P() if shard_seq else P(dp)     # batch-dim sharding
        blocks = []
        for i in range(cfg.num_layers):
            key = "slstm" if _is_slstm_block(cfg, i) else "mlstm"
            blocks.append({key: jax.tree.map(
                lambda _: bspec, {"c": 0, "n": 0, "h": 0, "m": 0}
                if key == "slstm" else {"C": 0, "n": 0, "m": 0})})
        return {"blocks": blocks}
    if shard_seq:
        # long-context batch=1: sequence sharded over every mesh axis
        # (flash-decode / sequence parallelism; GSPMD emits the
        # partial-softmax combine collectives)
        kv_spec = P(None, None, dp + ("model",), None, None)
    else:
        # batched decode: batch over DP, cache sequence over 'model'
        kv_spec = P(None, dp, "model", None, None)
    cache = {"k": kv_spec, "v": kv_spec}
    if cfg.family == "encdec":
        cache["cross_k"] = kv_spec
        cache["cross_v"] = kv_spec
        return cache
    if cfg.hybrid_parallel:
        cache["ssm"] = P(None, dp if not shard_seq else None, "model", None)
    return cache


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                cache: dict, position) -> tuple[jax.Array, dict]:
    """One-token decode.  token: (B, 1) int32; position: scalar int32 or
    (B,) int32 for continuous batching (per-row depths, see attn_decode)."""
    x = jnp.take(params["embed"]["tok"], token, axis=0)

    if cfg.family == "ssm":
        new_blocks = []
        for i, (blk, cb) in enumerate(zip(params["blocks"], cache["blocks"])):
            h = L.rmsnorm(blk["norm1"], x, cfg.norm_eps)
            if "slstm" in blk:
                y, st = S.slstm_decode(blk["slstm"], h, cfg, cb["slstm"])
                new_blocks.append({"slstm": st})
            else:
                y, st = S.mlstm_decode(blk["mlstm"], h, cfg,
                                       cb["mlstm"])
                new_blocks.append({"mlstm": st})
            x = x + y
        return _logits(params, cfg, x)[:, 0], {"blocks": new_blocks}

    if cfg.family == "encdec":
        def body(h, xs):
            lp, ck, cv, xk, xv = xs
            h, nc = _decoder_xlayer_decode(
                lp, h, cfg, {"k": ck, "v": cv, "cross_k": xk, "cross_v": xv},
                position)
            return h, (nc["k"], nc["v"])
        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, k=nk, v=nv)
        return _logits(params, cfg, x)[:, 0], new_cache

    flags = global_attention_flags(cfg)
    n_prefix = cfg.moe.first_dense if cfg.moe else 0
    new_cache = dict(cache)
    # unrolled prefix layers use the leading slices of the stacked cache
    for i, lp in enumerate(params.get("prefix", [])):
        sub = {"k": cache["k"][i], "v": cache["v"][i]}
        if cfg.hybrid_parallel:
            sub["ssm"] = cache["ssm"][i]
        x, nc = _layer_decode(lp, x, cfg, sub, position, bool(flags[i]))
        new_cache["k"] = new_cache["k"].at[i].set(nc["k"])
        new_cache["v"] = new_cache["v"].at[i].set(nc["v"])

    if cfg.hybrid_parallel:
        def body(h, xs):
            lp, is_global, ck, cv, cs = xs
            h, nc = _layer_decode(lp, h, cfg, {"k": ck, "v": cv, "ssm": cs},
                                  position, is_global)
            return h, (nc["k"], nc["v"], nc["ssm"])
        x, (nk, nv, ns) = jax.lax.scan(
            body, x, (params["layers"], jnp.asarray(flags[n_prefix:]),
                      cache["k"][n_prefix:], cache["v"][n_prefix:],
                      cache["ssm"][n_prefix:]))
        new_cache["k"] = jnp.concatenate([new_cache["k"][:n_prefix], nk]) \
            if n_prefix else nk
        new_cache["v"] = jnp.concatenate([new_cache["v"][:n_prefix], nv]) \
            if n_prefix else nv
        new_cache["ssm"] = jnp.concatenate([cache["ssm"][:n_prefix], ns]) \
            if n_prefix else ns
    else:
        def body(h, xs):
            lp, is_global, ck, cv = xs
            h, nc = _layer_decode(lp, h, cfg, {"k": ck, "v": cv},
                                  position, is_global)
            return h, (nc["k"], nc["v"])
        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], jnp.asarray(flags[n_prefix:]),
                      cache["k"][n_prefix:], cache["v"][n_prefix:]))
        if n_prefix:
            nk = jnp.concatenate([new_cache["k"][:n_prefix], nk])
            nv = jnp.concatenate([new_cache["v"][:n_prefix], nv])
        new_cache["k"], new_cache["v"] = nk, nv

    return _logits(params, cfg, x)[:, 0], new_cache


def count_params(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))

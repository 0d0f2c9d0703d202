"""Plain float32 reference of the first training steps of a dense decoder.

What it computes, from the seed and the batches alone:

* the weights, drawn with JAX's PRNG by the initialisation the model
  states: embedding N(0, 0.02^2); head, Q/K/V/O, gate and up N(0, 1/d);
  down N(0, 1/d_ff); norm scales 1; biases 0.  Matrices and biases are
  stored in the configuration's dtype (bfloat16), norm scales in float32;
* a pre-norm GQA decoder with RoPE, optional QK RMSNorm and QKV bias, a
  SwiGLU MLP and a tied or untied head: loss and gradients in float32,
  every matmul at ``precision="highest"``.  Layers are scanned with their
  activations recomputed, and the loss is taken over blocks of rows, so
  that the published widths fit on one chip.

``matmul="fp8"`` computes each matmul on operands rounded to float8 e4m3
with one scale per tensor: the lower-precision control that must fail.

This is the reference a configuration gets when it names none.  It
provides the interface :mod:`reference.common` describes, without counts
of its own: ``harness.flops`` counts it by the dense formulas.  The
aggregation over the workers, AdamW and the loop over the checked steps
are :mod:`reference.common`'s, and the names of it that callers used to
take from here are imported here.

Nothing here imports the program under test.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from reference.common import (  # noqa: F401  (re-exported)
    AdamW, change_readings, einsum_for, leaf_norm, leaf_paths, path_of,
    vote_bits, voted_rule)


@dataclasses.dataclass(frozen=True)
class Spec:
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float
    tied: bool
    qk_norm: bool
    qkv_bias: bool
    dtype: str


def spec_of(config: dict) -> Spec:
    """Sizes from a configuration file (Hugging Face ``config.json`` keys)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    prog = config.get("program", {})
    return Spec(d=d, heads=h, kv=config["num_key_value_heads"],
                hd=config.get("head_dim") or d // h,
                ff=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"],
                theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]),
                tied=bool(config["tie_word_embeddings"]),
                qk_norm=bool(prog.get("qk_norm", False)),
                qkv_bias=bool(prog.get("qkv_bias", False)),
                dtype=config["torch_dtype"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _normal(key, shape):
    return jax.random.normal(key, shape)


def _attn_weights(key, s: Spec) -> dict:
    dt = jnp.dtype(s.dtype)
    d, h, kv, hd = s.d, s.heads, s.kv, s.hd
    ka = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    attn = {"wq": (_normal(ka[0], (d, h * hd)) * std).astype(dt),
            "wk": (_normal(ka[1], (d, kv * hd)) * std).astype(dt),
            "wv": (_normal(ka[2], (d, kv * hd)) * std).astype(dt),
            "wo": (_normal(ka[3], (h * hd, d)) * std).astype(dt)}
    if s.qkv_bias:
        attn["bq"] = jnp.zeros((h * hd,), dt)
        attn["bk"] = jnp.zeros((kv * hd,), dt)
        attn["bv"] = jnp.zeros((kv * hd,), dt)
    if s.qk_norm:
        attn["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        attn["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return attn


def _mlp_weights(key, d: int, f: int, dt) -> dict:
    km = jax.random.split(key, 3)
    std = 1.0 / math.sqrt(d)
    return {"w_gate": (_normal(km[0], (d, f)) * std).astype(dt),
            "w_up": (_normal(km[1], (d, f)) * std).astype(dt),
            "w_down": (_normal(km[2], (f, d)) / math.sqrt(f)).astype(dt)}


def _layer_weights(key, s: Spec) -> dict:
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    return {"norm1": {"scale": jnp.ones((s.d,), jnp.float32)},
            "attn": _attn_weights(k_attn, s),
            "norm2": {"scale": jnp.ones((s.d,), jnp.float32)},
            "mlp": _mlp_weights(k_mlp, s.d, s.ff, jnp.dtype(s.dtype))}


def init_weights(seed, s: Spec) -> dict:
    """The stated initialisation from ``seed`` (an int or a PRNG key), in
    the storage dtypes."""
    dt = jnp.dtype(s.dtype)
    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed
    ks = jax.random.split(key, 8)
    w = {"embed": {"tok": (_normal(ks[0], (s.vocab, s.d)) * 0.02).astype(dt)},
         "final_norm": {"scale": jnp.ones((s.d,), jnp.float32)}}
    if not s.tied:
        w["head"] = {"w": (_normal(ks[1], (s.d, s.vocab))
                           * (1.0 / math.sqrt(s.d))).astype(dt)}
    keys = jax.random.split(ks[7], s.layers)
    w["layers"] = jax.vmap(lambda k: _layer_weights(k, s))(keys)
    return w


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, lw, s: Spec, ein):
    """The attention half of a layer, its residual included."""
    b, t, _ = x.shape
    g = s.heads // s.kv
    a = lw["attn"]
    h = _rms(x, lw["norm1"]["scale"], s.eps)
    q = ein("btd,de->bte", h, a["wq"])
    k = ein("btd,de->bte", h, a["wk"])
    v = ein("btd,de->bte", h, a["wv"])
    if s.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = q.reshape(b, t, s.heads, s.hd)
    k = k.reshape(b, t, s.kv, s.hd)
    v = v.reshape(b, t, s.kv, s.hd)
    if s.qk_norm:
        q = _rms(q, a["q_norm"]["scale"], s.eps)
        k = _rms(k, a["k_norm"]["scale"], s.eps)
    q, k = _rope(q, s.theta), _rope(k, s.theta)
    # query head i reads key/value head i // g
    q = q.reshape(b, t, s.kv, g, s.hd)
    scores = ein("bskgh,btkh->bkgst", q, k) / math.sqrt(s.hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = ein("bkgst,btkh->bskgh", probs, v).reshape(b, t, s.heads * s.hd)
    return x + ein("bte,ed->btd", o, a["wo"])


def _swiglu(h, m, ein):
    u = jax.nn.silu(ein("btd,df->btf", h, m["w_gate"])) \
        * ein("btd,df->btf", h, m["w_up"])
    return ein("btf,fd->btd", u, m["w_down"])


def _layer(x, lw, s: Spec, ein):
    x = _attention(x, lw, s, ein)
    h = _rms(x, lw["norm2"]["scale"], s.eps)
    return x + _swiglu(h, lw["mlp"], ein)


def loss(w32: dict, tokens, labels, s: Spec, *, matmul: str = "highest",
         row_block: int = 512, layer=_layer):
    """Mean next-token cross entropy of one worker's rows, in float32.

    ``layer(x, lw, s, ein) -> x`` is one scanned layer; a reference whose
    layers differ passes its own."""
    ein = einsum_for(matmul)
    x = jnp.take(w32["embed"]["tok"], tokens, axis=0)

    @jax.checkpoint
    def body(x, lw):
        return layer(x, lw, s, ein), None

    x, _ = jax.lax.scan(body, x, w32["layers"])
    x = _rms(x, w32["final_norm"]["scale"], s.eps)
    head = (w32["embed"]["tok"].T if s.tied else w32["head"]["w"])
    rows = x.reshape(-1, s.d)
    gold = labels.reshape(-1)
    n = rows.shape[0]
    blk = min(row_block, n)
    assert n % blk == 0, (n, blk)

    @jax.checkpoint
    def block_ce(args):
        r, y = args
        logits = ein("rd,dv->rv", r, head)
        lz = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(lz - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])

    ce = jax.lax.map(block_ce, (rows.reshape(n // blk, blk, s.d),
                                gold.reshape(n // blk, blk)))
    return jnp.sum(ce) / n

"""Operations and bytes the algorithm needs, computed from shapes.

Model FLOPs (the numerator of ``mfu.train``): 6 x the matmul parameters a
token touches (the LM head included, the embedding lookup excluded, no
recompute), plus causal attention counted as the half of the score and
value matmuls that the mask keeps.

Aggregation bytes (the numerator of ``agg_kernel_roofline``): per chip,
read each admitted gradient element once, write each decoded update once,
and write and read the packed sign words (1 bit per element) once.  That
holds on any number of chips: each chip encodes and decodes every element,
and votes on its share of the words.

A configuration's counts live with its plain reference: where the module
it names (``harness.spec.reference_module``) defines
``model_flops_per_token`` or ``admitted_elements``, those count it;
otherwise the dense decoder's formulas below do.  ``root`` is the
checkout whose ``bench/reference`` holds the module.
"""
from __future__ import annotations

from . import spec


def _own(config: dict, name: str, root):
    """The reference module's own count ``name``, or None."""
    return getattr(spec.reference_module(config, root), name, None)


def _dims(config: dict):
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    hd = config.get("head_dim") or d // h
    return d, h, kv, hd, config["intermediate_size"], config["vocab_size"]


def layer_matmul_params(config: dict) -> int:
    """Weights of one decoder layer's matmuls (Q, K, V, O, gate, up, down)."""
    d, h, kv, hd, f, _ = _dims(config)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params_per_token(config: dict) -> int:
    """Matmul weights one token touches: every layer plus the LM head."""
    d, *_, v = _dims(config)
    return config["num_hidden_layers"] * layer_matmul_params(config) + d * v


def model_flops_per_token(config: dict, seq_len: int,
                          root=spec.ROOT) -> float:
    """Training FLOPs per token: forward + backward, no recompute."""
    own = _own(config, "model_flops_per_token", root)
    if own is not None:
        return float(own(config, seq_len))
    _, h, _, hd, _, _ = _dims(config)
    dense = 6 * matmul_params_per_token(config)
    # QK^T and PV: 2 * 2 * S * H * hd per token forward, half kept by the
    # causal mask, times 3 for forward plus backward
    attn = 3 * 2 * seq_len * h * hd * config["num_hidden_layers"]
    return float(dense + attn)


def admitted_elements(config: dict, root=spec.ROOT) -> int:
    """Elements the low-bit backbone votes on: every layer weight and bias
    that is not a norm (the LM head and embedding stay on FP32)."""
    own = _own(config, "admitted_elements", root)
    if own is not None:
        return int(own(config))
    d, h, kv, hd, _, _ = _dims(config)
    biases = (h * hd + 2 * kv * hd) if config.get("program", {}).get(
        "qkv_bias") else 0
    return config["num_hidden_layers"] * (layer_matmul_params(config)
                                          + biases)


def agg_bytes_per_chip(config: dict, *, grad_bytes: int = 2,
                       update_bytes: int = 2, root=spec.ROOT) -> float:
    """Algorithmic HBM bytes of one step's vote datapath on one chip."""
    n = admitted_elements(config, root)
    return n * (grad_bytes + update_bytes) + 2 * n / 8

"""The plain reference against the program (repro.models, repro.fabric,
repro.optim) at a small size on the CPU."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_CONFIG, TINY_TRAFFIC, write_root
from harness import cell as C
from harness import spec, traffic
from reference import common
from reference import model as R

QWEN2_LIKE = dict(copy.deepcopy(TINY_CONFIG), name="tiny-bias",
                  tie_word_embeddings=False,
                  program={"qk_norm": False, "qkv_bias": True, "remat": True})


@pytest.mark.parametrize("config", [TINY_CONFIG, QWEN2_LIKE],
                         ids=["qk_norm_tied", "qkv_bias_untied"])
def test_init_is_the_programs(config):
    from repro.models import init_params
    seed = 2**31 + 11
    ours = R.init_weights(seed, R.spec_of(config))
    theirs = init_params(jax.random.PRNGKey(seed), spec.model_config(config))
    assert R.leaf_paths(ours) == R.leaf_paths(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("config", [TINY_CONFIG, QWEN2_LIKE],
                         ids=["qk_norm_tied", "qkv_bias_untied"])
def test_loss_and_grads_match_the_model(config):
    from repro.models import loss_fn
    config = dict(config, torch_dtype="float32")
    s = R.spec_of(config)
    w = R.init_weights(3, s)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, s.vocab, (2, 16)).astype(np.int32)
    lab = rng.integers(0, s.vocab, (2, 16)).astype(np.int32)
    cfg = spec.model_config(config)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, {"tokens": tok, "labels": lab}))(w)
        lr, gr = jax.value_and_grad(
            lambda p: R.loss(p, tok, lab, s, row_block=16))(w)
    np.testing.assert_allclose(float(lr), float(lp), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-6)


def test_vote_rules():
    voted = R.voted_rule("gbin_packed")
    assert all(voted(p) for p in ["layers/mlp/w_up", "layers/attn/bq"])
    assert not any(voted(p) for p in ["embed/tok", "head/w",
                                      "layers/attn/q_norm/scale",
                                      "final_norm/scale"])
    assert not R.voted_rule("fp32")("layers/mlp/w_up")
    with pytest.raises(ValueError):
        R.voted_rule("int4_backbone")


def test_voted_leaves_carry_a_direction():
    """A voted leaf's first aggregate is +-1: its norm is sqrt(size)."""
    config = dict(TINY_CONFIG, torch_dtype="float32")
    ref = common.Reference(R, config, R.AdamW(3e-4, 100, 1000),
                           voted=R.voted_rule("gbin_packed"))
    w0 = ref.init(5)
    rng = np.random.default_rng(1)
    batch = {"tokens": rng.integers(0, 512, (2, 16)).astype(np.int32),
             "labels": rng.integers(0, 512, (2, 16)).astype(np.int32)}
    out = ref.steps(5, [batch])
    paths = out["paths"]
    for p, x, n in zip(paths, jax.tree.leaves(w0), out["agg_norm"]):
        if ref.voted(p):
            assert n == pytest.approx(np.sqrt(x.size), rel=1e-6)


def _program_against_reference(root, seed: int) -> dict:
    """The checked numbers of cell ``c`` of ``root``: the program's three
    checked steps against the reference's, on one CPU device."""
    cell = spec.find_cell("c", root)
    pool = traffic.pool_for(cell, seed)
    trainer = C.build(cell, seed, jax.devices()[:1])
    trainer.data = pool
    voted = C.voted_rule(cell)
    prog = C.program_readings(trainer, pool, 3, 0.9, voted)
    ref = C.reference_readings(cell, seed, pool, 3)
    return C.compare(prog, ref, voted)


def _assert_rounding(vals):
    assert vals["loss_gap"] < 1e-5
    assert vals["grad_norm_gap"] < 1e-4
    assert vals["vote_mismatch"] < 2e-3
    assert vals["update_norm_gap"] < 2e-3
    assert vals["update_mismatch"] < 2e-3


@pytest.mark.parametrize("plan", ["gbin_packed", "fp32"])
def test_program_steps_match_the_reference(tmp_path, plan):
    """At float32 the program's checked steps and the reference's agree to
    rounding: the same model, aggregation and optimizer."""
    config = dict(TINY_CONFIG, torch_dtype="float32")
    root = write_root(tmp_path, {"tiny": config},
                      {"t": dict(TINY_TRAFFIC, plan=plan)},
                      [{"name": "c", "config": "tiny", "traffic": "t",
                        "chips": 1, "why": "t"}])
    _assert_rounding(_program_against_reference(root, 7))


#: a reference of the program's expert-share layer, as a configuration of
#: that family brings one: attention as reference.model's, then a float32
#: softmax router, top-k renormalised, SwiGLU experts and a shared MLP
MOE_REFERENCE = '''
import dataclasses
import math

import jax
import jax.numpy as jnp

from reference import model as R
from reference.common import einsum_for


@dataclasses.dataclass(frozen=True)
class Spec(R.Spec):
    experts: int
    top_k: int
    d_expert: int
    shared: int


def spec_of(config):
    m = config["program"]["moe"]
    assert m["first_dense"] == 0, "no dense leading layers here"
    return Spec(**dataclasses.asdict(R.spec_of(config)),
                experts=m["num_experts"], top_k=m["top_k"],
                d_expert=m["d_expert"], shared=m["num_shared"])


def _layer_weights(key, s):
    dt = jnp.dtype(s.dtype)
    d, e, f = s.d, s.experts, s.d_expert
    k_attn, k_moe, _, _ = jax.random.split(key, 4)
    km = jax.random.split(k_moe, 5)
    std = 1.0 / math.sqrt(d)
    moe = {"router": (jax.random.normal(km[0], (d, e)) * std)
                     .astype(jnp.float32),
           "w_gate": (jax.random.normal(km[1], (e, d, f)) * std).astype(dt),
           "w_up": (jax.random.normal(km[2], (e, d, f)) * std).astype(dt),
           "w_down": (jax.random.normal(km[3], (e, f, d))
                      / math.sqrt(f)).astype(dt)}
    if s.shared:
        moe["shared"] = R._mlp_weights(km[4], d, f * s.shared, dt)
    return {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": R._attn_weights(k_attn, s),
            "norm2": {"scale": jnp.ones((d,), jnp.float32)}, "moe": moe}


def init_weights(seed, s):
    dt = jnp.dtype(s.dtype)
    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed
    ks = jax.random.split(key, 8)
    w = {"embed": {"tok": (jax.random.normal(ks[0], (s.vocab, s.d))
                           * 0.02).astype(dt)},
         "final_norm": {"scale": jnp.ones((s.d,), jnp.float32)}}
    if not s.tied:
        w["head"] = {"w": (jax.random.normal(ks[1], (s.d, s.vocab))
                           / math.sqrt(s.d)).astype(dt)}
    keys = jax.random.split(ks[7], s.layers)
    w["layers"] = jax.vmap(lambda k: _layer_weights(k, s))(keys)
    return w


def _moe(h, m, s, ein):
    """Every token through its top-k experts, weighted by its renormalised
    gates, plus the shared experts."""
    gates = jax.nn.softmax(ein("btd,de->bte", h, m["router"]), axis=-1)
    topv, topi = jax.lax.top_k(gates, s.top_k)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
    weight = jnp.sum(jax.nn.one_hot(topi, s.experts) * topv[..., None], -2)
    u = jax.nn.silu(ein("btd,edf->btef", h, m["w_gate"])) \\
        * ein("btd,edf->btef", h, m["w_up"])
    y = ein("bte,bted->btd", weight, ein("btef,efd->bted", u, m["w_down"]))
    if "shared" in m:
        y = y + R._swiglu(h, m["shared"], ein)
    return y


def _layer(x, lw, s, ein):
    x = R._attention(x, lw, s, ein)
    return x + _moe(R._rms(x, lw["norm2"]["scale"], s.eps), lw["moe"], s, ein)


def loss(w32, tokens, labels, s, *, matmul="highest"):
    return R.loss(w32, tokens, labels, s, matmul=matmul, layer=_layer)
'''

#: an expert-share configuration whose dispatch drops no token: one group
#: of all the step's 2 x 16 tokens, each expert's capacity the whole group
#: (int(32 * 2 / 4 * 2.0) = 32)
MOE_CONFIG = {
    "name": "tiny-moe", "source": "a small expert-share stand-in",
    "hidden_size": 32, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "reduced": [], "assumed": {}, "reference": "tiny_moe",
    "program": {"family": "moe", "remat": True,
                "moe": {"num_experts": 4, "top_k": 2, "d_expert": 16,
                        "num_shared": 1, "first_dense": 0,
                        "capacity_factor": 2.0, "group_size": 32}},
}


def _moe_root(tmp_path, plan):
    root = write_root(tmp_path, {"tiny-moe": MOE_CONFIG},
                      {"t": dict(TINY_TRAFFIC, plan=plan)},
                      [{"name": "c", "config": "tiny-moe", "traffic": "t",
                        "chips": 1, "why": "t"}])
    (root / "bench" / "reference" / "tiny_moe.py").write_text(MOE_REFERENCE)
    return root


def test_moe_init_is_the_programs(tmp_path):
    from repro.models import init_params
    cell = spec.find_cell("c", _moe_root(tmp_path, "gbin_packed"))
    arch, seed = cell.reference, 2**31 + 11
    ours = arch.init_weights(seed, arch.spec_of(cell.config))
    theirs = init_params(jax.random.PRNGKey(seed),
                         spec.model_config(cell.config))
    assert "layers/moe/router" in R.leaf_paths(ours)
    assert R.leaf_paths(ours) == R.leaf_paths(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("plan", ["gbin_packed", "fp32"])
def test_moe_program_steps_match_the_reference(tmp_path, plan):
    """An expert-share model's checked steps agree with its reference to
    rounding, the router on the FP32 mean in both."""
    _assert_rounding(_program_against_reference(_moe_root(tmp_path, plan),
                                                 7))


def test_update_rounds_to_the_storage_dtype():
    """The reference keeps a bfloat16 weight in bfloat16: a step smaller
    than half its rounding step leaves it where it was, as it leaves the
    program's weight with no float32 master copy."""
    config = dict(TINY_CONFIG)
    ref = common.Reference(R, config, R.AdamW(3e-4, 100, 1000),
                           voted=R.voted_rule("gbin_packed"))
    w = jax.tree.leaves(ref.init(5))
    w0 = [np.asarray(x) for x in w]
    zeros = [jnp.zeros(x.shape, jnp.float32) for x in w]
    agg = [jnp.ones(x.shape, jnp.float32) for x in w]
    out, _, _ = ref.update_fn()(w, zeros, [z + 0 for z in zeros], agg,
                                 jnp.float32(1))
    for x, x0 in zip(out, w0):
        assert x.dtype == x0.dtype
        want = (x0.astype(np.float32) - 3e-6 / (1 + 1e-8)).astype(x0.dtype)
        np.testing.assert_array_equal(np.asarray(x), want)
    moved = sum(int(np.sum(np.asarray(x) != x0)) for x, x0 in zip(out, w0)
                if x0.dtype != np.float32)
    assert 0 < moved < sum(x.size for x in w0 if x.dtype != np.float32) // 4

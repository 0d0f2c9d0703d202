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


@pytest.mark.parametrize("plan", ["gbin_packed", "fp32"])
def test_program_steps_match_the_reference(tmp_path, plan):
    """At float32 the program's checked steps and the reference's agree to
    rounding: the same model, aggregation and optimizer."""
    config = dict(TINY_CONFIG, torch_dtype="float32")
    root = write_root(tmp_path, {"tiny": config},
                      {"t": dict(TINY_TRAFFIC, plan=plan)},
                      [{"name": "c", "config": "tiny", "traffic": "t",
                        "chips": 1, "why": "t"}])
    cell = spec.find_cell("c", root)
    devices = jax.devices()[:1]
    pool = traffic.pool_for(cell, 7)
    trainer = C.build(cell, 7, devices)
    trainer.data = pool
    voted = C.voted_rule(cell)
    prog = C.program_readings(trainer, pool, 3, 0.9, voted)
    ref = C.reference_readings(cell, 7, pool, 3)
    vals = C.compare(prog, ref, voted)
    assert vals["loss_gap"] < 1e-5
    assert vals["grad_norm_gap"] < 1e-4
    assert vals["vote_mismatch"] < 2e-3
    assert vals["update_norm_gap"] < 2e-3
    assert vals["update_mismatch"] < 2e-3



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

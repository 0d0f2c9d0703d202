"""The fused Pallas attention kernel of ``attn_forward`` (JAX's bundled
splash kernel): its outputs and gradients against the dense branch (the
kernel in Pallas interpret mode on the CPU), and which inputs the dispatch
sends to it.

On the CPU ``attn_forward`` never takes the kernel; the dispatch tests
steer it by standing in the TPU's answer for ``kernels.ops.on_tpu``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kernel_ops
from repro.models import layers as L
from repro.models.config import ModelConfig

B, S, HD = 2, 256, 128


def _cfg(**kw) -> ModelConfig:
    base = dict(name="attn", family="dense", num_layers=1, d_model=256,
                num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=64,
                head_dim=HD, dtype="bfloat16")
    return ModelConfig(**{**base, **kw})


def _qkv(h, kv, s=S, hd=HD, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, s, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, s, kv, hd), jnp.bfloat16)
    return q, k, v


def _out_and_grads(fn, q, k, v):
    out, vjp = jax.vjp(jax.jit(fn), q, k, v)
    ct = jax.random.normal(jax.random.PRNGKey(9), out.shape, out.dtype)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    return f32(out), [f32(g) for g in vjp(ct)]


@pytest.mark.parametrize("heads,kv_heads,block", [
    (4, 2, S), (10, 2, S), (4, 2, 128), (10, 2, 128)])
def test_kernel_matches_dense_branch(heads, kv_heads, block):
    # at 128 the sequence is two blocks: the block above the diagonal is
    # skipped, the two on it are masked
    cfg = _cfg(num_heads=heads, num_kv_heads=kv_heads)
    q, k, v = _qkv(heads, kv_heads)
    dense = lambda q, k, v: L._dense_attention(q, k, v, cfg, causal=True,
                                               is_global=True)
    # the kernel takes q scaled by 1/sqrt(hd), as ``attn_forward`` hands
    # it over (q here is bf16 already: one rounding of the scaled value)
    scale = lambda q: (q.astype(jnp.float32) / np.sqrt(HD)).astype(q.dtype)
    fused = lambda q, k, v: L._fused_causal_attention(
        scale(q), k, v, block=block, interpret=True)
    out_d, grads_d = _out_and_grads(dense, q, k, v)
    out_f, grads_f = _out_and_grads(fused, q, k, v)
    assert out_f.shape == out_d.shape == (B, S, heads * HD)
    # bf16 outputs of softmax-weighted sums of N(0, 1) values: a few bf16
    # ulps at the largest magnitudes (~3)
    np.testing.assert_allclose(out_f, out_d, atol=3e-2, rtol=0)
    for name, gf, gd in zip("qkv", grads_f, grads_d):
        rel = np.linalg.norm(gf - gd) / np.linalg.norm(gd)
        assert rel < 1e-2, (name, rel)


def test_rope_rounds_the_scaled_query_once():
    # the kernel branch passes 1/sqrt(hd) to rope: q is rounded to bf16
    # once, from the float32 product, where scaling rope's bf16 output
    # would round it twice
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, 4, HD), jnp.bfloat16)
    pos = jnp.arange(S)[None, :]
    scale = 1.0 / np.sqrt(HD)
    exact = np.asarray(L.rope(x.astype(jnp.float32), pos, 1e6)) * scale
    once = np.asarray(L.rope(x, pos, 1e6, scale).astype(jnp.float32))
    twice = np.asarray((L.rope(x, pos, 1e6).astype(jnp.float32) * scale)
                       .astype(jnp.bfloat16).astype(jnp.float32))
    # one rounding: within half a bf16 ulp (2**-8 relative) of the
    # float32 value, bar float32 cancellation in the fold near zero
    assert np.all(np.abs(once - exact) <= 2.0 ** -8 * np.abs(exact) + 1e-6)
    assert np.mean(np.abs(once - exact)) < 0.85 * np.mean(np.abs(twice - exact))


#: the train step's mesh with no tensor parallelism
STEP_MESH = ((1, 1), ("data", "model"))


def _takes_kernel(monkeypatch, cfg, *, s=S, causal=True, mesh=STEP_MESH,
                  manual=("data",)) -> bool:
    """Whether ``attn_forward`` traces a Pallas call for these inputs on the
    TPU, inside a shard_map manual over the ``manual`` axes of ``mesh``
    as the train step runs it (no shard_map where ``manual`` is empty)."""
    monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)
    p = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16)
    fn = lambda p, x: L.attn_forward(p, x, cfg, causal=causal)
    mesh = AbstractMesh(*mesh)
    if manual:
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(), P()),
                           out_specs=P(), axis_names=frozenset(manual),
                           check_vma=False)
    with jax.sharding.use_abstract_mesh(mesh):
        jaxpr = jax.make_jaxpr(fn)(p, x)
    return "pallas_call" in str(jaxpr)


def test_dispatch_takes_kernel_for_causal_gqa(monkeypatch):
    assert _takes_kernel(monkeypatch, _cfg())
    assert _takes_kernel(monkeypatch, _cfg(), s=2048)
    assert _takes_kernel(monkeypatch, _cfg(), s=128)
    # four data-parallel workers, each with its own batch rows
    assert _takes_kernel(monkeypatch, _cfg(), mesh=((4, 1), ("data", "model")))


EXCLUDED = {
    "non_causal": dict(causal=False),
    "windowed": dict(cfg=dict(sliding_window=64)),
    "not_a_block_multiple": dict(s=S + 64),
    "not_a_multiple_of_the_largest_block": dict(s=640),
    "short_odd_length": dict(s=100),
    "head_dim_64": dict(cfg=dict(head_dim=64)),
    "over_threshold": dict(s=2 * L.FLASH_SEQ_THRESHOLD),
    "tensor_parallel": dict(mesh=((1, 2), ("data", "model"))),
    # a jit over NamedShardings, as serving runs it: no manual region
    "outside_a_manual_region": dict(manual=()),
}


@pytest.mark.parametrize("case", sorted(EXCLUDED))
def test_excluded_inputs_take_todays_branch(monkeypatch, case):
    kw = dict(EXCLUDED[case])
    cfg = _cfg(**kw.pop("cfg", {}))
    assert not _takes_kernel(monkeypatch, cfg, **kw)


def test_cpu_never_takes_kernel():
    # without the stand-in the CPU keeps the dense branch
    p = L.init_attention(jax.random.PRNGKey(0), _cfg())
    x = jax.ShapeDtypeStruct((1, S, 256), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x: L.attn_forward(p, x, _cfg()))(p, x)
    assert "pallas_call" not in str(jaxpr)


def test_attention_core_is_scoped():
    # forward and backward ops of the core carry the ``attention`` scope
    # inside the train step's ``model`` scope, where the benchmark's
    # ``attn_ms.train`` finds them
    cfg = _cfg()
    p = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 256), jnp.bfloat16)

    def loss(p):
        with jax.named_scope("model"):
            return jnp.sum(L.attn_forward(p, x, cfg).astype(jnp.float32))

    hlo = jax.jit(jax.grad(loss)).lower(p).compile().as_text()
    names = [n.split("/") for n in re.findall(r'op_name="([^"]*)"', hlo)]
    scoped = [n for n in names if "attention" in n]
    assert any("jvp(model)" in n for n in scoped)
    assert any("transpose(jvp(model))" in n for n in scoped)

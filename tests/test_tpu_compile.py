"""Mosaic compiles of the Pallas datapath for a described TPU v5e.

Interpret mode runs the kernel bodies element for element on the CPU;
it never asks Mosaic whether the kernels lower.  These tests compile
every kernel entry point with ``interpret=False`` for a v5e that is
described, not attached: at a full 32 MiB bucket (8,388,608 float32
values), at a ragged bucket of 13 word rows (padded to whole tiles, as
the aggregation path pads it, and as a raw 13-row plane), at W=1 and
W=4, and one ``fused_packed_vote`` bucket under a shard_map over four
described chips.  Nothing runs; the compile raises what the chip's
compiler would raise.

The topology is described inside a module-scoped fixture only: the
TPU library may be loaded by one process at a time, so it is never
touched while a module is imported or tests are collected.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import kernels as K
from repro.kernels import fused

#: value counts of the buckets compiled: a full 32 MiB bucket, and a
#: ragged one of 13 word rows (to_plane pads it to 16)
FULL_BUCKET = 8_388_608
RAGGED_BUCKET = 13 * 4096 - 5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without one: keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _plane_rows(size: str) -> int:
    """Value-plane rows for a bucket size name."""
    if size == "rows13":
        return 13 * K.PACK
    n = FULL_BUCKET if size == "full32MiB" else RAGGED_BUCKET
    return K.padded_len(n) // K.LANE


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _cases():
    """(kernel, dtype, W) cases; W=0 marks a kernel without workers."""
    out = []
    for dt in ("float32", "bfloat16"):
        out += [("pack_signs", dt, 0), ("encode_pack_ef", dt, 0),
                ("ef_residual_plane", dt, 0),
                ("threshold_mask_plane", dt, 0), ("vote_pipeline", dt, 1),
                ("vote_pipeline", dt, 4)]
    out += [("unpack_ternary", "float32", 0),
            ("int4_quant_plane", "float32", 0)]
    for w in (1, 4):
        out += [("vote_combine", "uint32", w)]
    return out


@pytest.mark.parametrize("size", ["full32MiB", "ragged13", "rows13"])
@pytest.mark.parametrize("kernel,dtype,w", _cases())
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype, w, size):
    m = _plane_rows(size)
    r = m // K.PACK
    dt = jnp.dtype(dtype)

    def s(shape, d=dt):
        return jax.ShapeDtypeStruct(shape, d, sharding=one_chip)

    plane, words = s((m, K.LANE)), s((r, K.LANE), jnp.uint32)
    scalar = s((), dt)
    calls = {
        "pack_signs": (functools.partial(K.pack_signs, interpret=False),
                       plane),
        "encode_pack_ef": (functools.partial(fused.encode_pack_ef,
                                             interpret=False), plane, plane),
        "ef_residual_plane": (functools.partial(fused.ef_residual_plane,
                                                interpret=False),
                              plane, scalar),
        "threshold_mask_plane": (functools.partial(
            fused.threshold_mask_plane, interpret=False), plane, scalar),
        "vote_pipeline": (functools.partial(fused.vote_pipeline,
                                            num_workers=w, interpret=False),
                          s((w, m, K.LANE)), words),
        "unpack_ternary": (functools.partial(K.unpack_ternary,
                                             interpret=False), words, words),
        "int4_quant_plane": (functools.partial(fused.int4_quant_plane,
                                               interpret=False), plane),
        "vote_combine": (functools.partial(fused.vote_combine,
                                           num_workers=w, interpret=False),
                         s((w, r, K.LANE)), words),
    }
    fn, *shapes = calls[kernel]
    _compile(fn, *shapes)


@pytest.mark.parametrize("ternary,ef", [(False, False), (True, True)])
@pytest.mark.parametrize("n", [FULL_BUCKET, RAGGED_BUCKET])
def test_fused_packed_vote_compiles_on_four_chips(topo, n, ternary, ef):
    """One packed-vote bucket per chip at W=4: encode, all_to_all of the
    packed words, vote_combine, all_gather, decode — Mosaic kernels
    inside a shard_map manual over the mesh."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    spec = P("data")
    bucket = jax.ShapeDtypeStruct((4 * n,), jnp.bfloat16,
                                  sharding=NamedSharding(mesh, spec))

    def vote(g, e):
        u, new_e = fused.fused_packed_vote(g, ("data",), 4, ternary=ternary,
                                           ef=e, interpret=False)
        return u, (g if new_e is None else new_e)

    fn = jax.shard_map(lambda g: vote(g, g if ef else None), mesh=mesh,
                       in_specs=spec, out_specs=(spec, spec),
                       check_vma=False)
    compiled = jax.jit(fn).lower(bucket).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text


def _one_layer_qwen3():
    import dataclasses

    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen3_0p6b"), num_layers=1,
                               vocab_size=1024)


def _mosaic_op_names(hlo_text: str) -> list:
    """The ``op_name`` of every Mosaic call in ``hlo_text``.  A kernel
    that passes metadata prints it as indented JSON, so its instruction
    runs on to lines that start with neither an indent nor ``%``; they
    are joined to it first."""
    import re
    instrs = []
    for line in hlo_text.splitlines():
        if line.startswith((" ", "\t")) or not instrs:
            instrs.append(line)
        elif not re.match(r"(ENTRY\s+)?%|\}$", line):
            instrs[-1] += "\n" + line
    return [re.search(r'(?<![\w"])metadata=\{op_name="([^"]*)"', i).group(1)
            for i in instrs if 'custom_call_target="tpu_custom_call"' in i]


def test_decoder_layer_attention_kernel_compiles(topo, monkeypatch):
    """One qwen3-width decoder layer's loss and gradients under the
    ``"auto"`` remat policy, inside a shard_map manual over the data axis
    of a (1, 1) mesh as the train step runs it: the attention core lowers
    to the splash flash kernels, which carry the ``attention`` scope in
    the forward pass and under ``transpose(jvp(`` in the backward pass."""
    from repro.kernels import ops as kernel_ops
    from repro.models import transformer as T

    # the TPU's answers, which the CPU process lacks
    monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(T, "_device_bytes_limit", lambda: 16 << 30)
    cfg = _one_layer_qwen3()
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((8, 512), jnp.int32,
                                sharding=NamedSharding(mesh, P("data")))

    def grads(p, t):
        def loss(p):
            with jax.named_scope("model"):
                return T.loss_fn(p, cfg, {"tokens": t, "labels": t})
        return jax.value_and_grad(loss)(p)

    fn = jax.shard_map(grads, mesh=mesh, in_specs=(P(), P("data")),
                       out_specs=(P(), P()), axis_names=frozenset({"data"}),
                       check_vma=False)
    text = jax.jit(fn).lower(params, toks).compile().as_text()
    calls = _mosaic_op_names(text)
    assert calls and all("attention" in c.split("/") for c in calls)
    backward = [c for c in calls if "transpose(jvp(" in c]
    assert backward and len(backward) < len(calls)


def test_prefill_over_tensor_parallel_mesh_keeps_dense_attention(
        topo, monkeypatch):
    """Serving prefill is a jit over NamedShardings with the heads sharded
    over the ``model`` axis, and no mesh its trace can see: attention
    keeps the dense branch there, so no Mosaic call sits among the
    partitioned ops."""
    from repro.kernels import ops as kernel_ops
    from repro.models import transformer as T
    from repro.runtime.serve import build_prefill

    monkeypatch.setattr(kernel_ops, "on_tpu", lambda: True)
    cfg = _one_layer_qwen3()
    mesh = Mesh(np.array(topo.devices[:2]).reshape(1, 2), ("data", "model"))
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 512), jnp.int32)}
    text = build_prefill(cfg, mesh).lower(params, batch).compile().as_text()
    assert not _mosaic_op_names(text)

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
                ("apply_sign_update", dt, 0), ("ef_residual_plane", dt, 0),
                ("threshold_mask_plane", dt, 0), ("vote_pipeline", dt, 1),
                ("vote_pipeline", dt, 4)]
    out += [("unpack_ternary", "float32", 0),
            ("int4_quant_plane", "float32", 0)]
    for w in (1, 4):
        out += [("popcount_stack", "int32", w),
                ("majority_decode", "int32", w),
                ("vote_combine", "uint32", w)]
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
        "apply_sign_update": (
            lambda p, sw, mw: K.apply_sign_update(p, sw, mw, 0.01,
                                                  interpret=False),
            plane, words, words),
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
        "popcount_stack": (functools.partial(K.popcount_stack,
                                             interpret=False),
                           s((w, r, K.LANE), jnp.uint32)),
        "majority_decode": (
            lambda c, g: K.majority_decode(c, num_workers=w, gate_words=g,
                                           interpret=False),
            plane, words),
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

"""Pallas TPU kernels: decode packed aggregates and apply parameter updates.

``unpack_ternary`` decodes a ternary packed pair back to {-1, 0, +1} values
(the read-response payload the requester sees in the paper).

``apply_sign_update`` is a beyond-paper fusion: instead of materializing the
decoded aggregate in HBM and then running the optimizer update, it reads the
parameter plane once, decodes the packed aggregate in VMEM (1/32 the bytes
of a dense gradient), applies ``p - scale * u`` and writes the plane back.
For the sign-SGD update step this turns an HBM-bound 3-pass update
(read grad + read param + write param = 12 bytes/element fp32) into
~8.25 bytes/element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import LANE, PACK
from .sign_pack import pick_word_block, unpack_rows


def decode_ternary(sign_words: jax.Array, mask_words: jax.Array) -> jax.Array:
    """(RB, LANE) ternary packed pair -> (32 RB, LANE) int32 in {-1, 0, +1}."""
    return (2 * unpack_rows(sign_words) - 1) * unpack_rows(mask_words)


def _unpack_ternary_kernel(sign_ref, mask_ref, out_ref):
    u = decode_ternary(sign_ref[...], mask_ref[...])
    out_ref[...] = u.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def unpack_ternary(sign_words: jax.Array, mask_words: jax.Array, *,
                   dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """Ternary packed pair (R, LANE) -> value plane (32 R, LANE) of {-1,0,+1}."""
    r, lane = sign_words.shape
    assert lane == LANE and mask_words.shape == (r, lane)
    wb = pick_word_block(r, max_words=8)
    grid = (r // wb,)
    return pl.pallas_call(
        _unpack_ternary_kernel,
        out_shape=jax.ShapeDtypeStruct((r * PACK, LANE), dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((wb, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
        interpret=interpret,
        name="fabric_unpack_ternary",
    )(sign_words, mask_words)


def _apply_sign_update_kernel(param_ref, sign_ref, mask_ref, scale_ref,
                              out_ref):
    u = decode_ternary(sign_ref[...], mask_ref[...]).astype(jnp.float32)
    p = param_ref[...].astype(jnp.float32)
    out_ref[...] = (p - scale_ref[0, 0] * u).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_sign_update(param_plane: jax.Array, sign_words: jax.Array,
                      mask_words: jax.Array, scale: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """Fused ``param - scale * decode(sign, mask)`` over a value plane."""
    m, lane = param_plane.shape
    assert lane == LANE and m % PACK == 0
    num_words = m // PACK
    assert sign_words.shape == (num_words, LANE)
    assert mask_words.shape == (num_words, LANE)
    wb = pick_word_block(num_words, max_words=8)
    grid = (num_words // wb,)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _apply_sign_update_kernel,
        out_shape=jax.ShapeDtypeStruct((m, LANE), param_plane.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
        interpret=interpret,
        name="fabric_apply_sign_update",
    )(param_plane, sign_words, mask_words, scale_arr)

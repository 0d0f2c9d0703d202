"""Pallas TPU kernel: decode packed aggregates.

``unpack_ternary`` decodes a ternary packed pair back to {-1, 0, +1} values
(the read-response payload the requester sees in the paper); the packed
vote's decode stage (:func:`repro.kernels.fused.fused_packed_vote`).
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

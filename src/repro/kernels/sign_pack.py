"""Pallas TPU kernel: pack gradient signs into uint32 word planes.

This is the write-side payload materialization of the paper (Section 3,
"Write-side payload materialization"): the runtime derives a packed sign
payload from ordinary FP32/BF16 gradients *before* the communication step.

Layout contract (shared with ref.py): value plane (M, 128) -> word plane
(M // 32, 128) uint32, bit b of word [r, l] = sign of value [32 r + b, l].

TPU mapping: each block holds ``32 * RB`` value rows in VMEM and forms its
RB word rows at once: the block is viewed as (RB, 32, 128) and each word
is the sublane reduction of ``bit << row_index`` — the direct analogue of
the paper's 512-bit sign-packing stage.  Mosaic reduces no unsigned
integers, so words are summed as int32 (the bits are disjoint, so the sum
is the bitwise OR, bit 31 included) and bitcast to uint32.

Block shapes: a word-plane block is a multiple of 8 rows (one (8, 128)
int32 VREG tile) or the whole plane; :func:`repro.kernels.ref.padded_len`
pads every bucket to whole tiles, so real buckets always tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import LANE, PACK, SUBLANE


def pack_rows(bits: jax.Array) -> jax.Array:
    """(32 RB, LANE) int32 bits in {0, 1} -> (RB, LANE) uint32 words.

    Bit b of word r is ``bits[32 r + b]``.
    """
    rb = bits.shape[0] // PACK
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, PACK, LANE), 1)
    words = jnp.sum(bits.reshape(rb, PACK, LANE) << shifts, axis=1)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def unpack_rows(words: jax.Array) -> jax.Array:
    """(RB, LANE) uint32 words -> (32 RB, LANE) int32 bits in {0, 1}."""
    rb = words.shape[0]
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, PACK, LANE), 1)
    bits = (words[:, None, :] >> shifts) & jnp.uint32(1)
    return bits.astype(jnp.int32).reshape(rb * PACK, LANE)


def positive(x: jax.Array) -> jax.Array:
    """int32 {0, 1}: x > 0, compared in float32 (exact for every float
    input; the v5e vector unit compares no bfloat16)."""
    return (x.astype(jnp.float32) > 0).astype(jnp.int32)


def _sign_pack_kernel(x_ref, out_ref):
    out_ref[...] = pack_rows(positive(x_ref[...]))


def pick_word_block(num_words: int, max_words: int = 16) -> int:
    """Word rows per block: the largest multiple of 8 up to ``max_words``
    that divides ``num_words``, or all of them when 8 does not divide."""
    if num_words % SUBLANE:
        return num_words
    for wb in range(max(SUBLANE, max_words // SUBLANE * SUBLANE), 0,
                    -SUBLANE):
        if num_words % wb == 0:
            return wb
    return num_words


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_pack(plane: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Value plane (M, LANE) -> packed sign word plane (M // 32, LANE) uint32."""
    m, lane = plane.shape
    assert lane == LANE, f"lane dim must be {LANE}, got {lane}"
    assert m % PACK == 0, f"rows {m} must be a multiple of {PACK}"
    num_words = m // PACK
    wb = pick_word_block(num_words)
    grid = (num_words // wb,)
    return pl.pallas_call(
        _sign_pack_kernel,
        out_shape=jax.ShapeDtypeStruct((num_words, LANE), jnp.uint32),
        grid=grid,
        in_specs=[pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(plane)

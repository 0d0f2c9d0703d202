"""Pallas TPU kernels: the NEURON-Fabric "controller datapath".

Two stages mirror the paper's five-cycle 512-bit aggregation pipeline
(Section 3, "Datapath"):

  * ``popcount_stack``  — sign unpacking/alignment + per-element PopCount
    across W workers' packed payloads (the XNOR/PopCount tree).
  * ``majority_decode`` — vote margin a = 2c - W, majority / ternary gating,
    and re-packing of the returned aggregate as a ternary packed pair
    (sign_words, mask_words).

The zero gate is an explicit packed operand so the same kernel serves
G-Binary (gate = all ones; zeros only on vote ties) and G-Ternary
(gate = the fixed 2-of-3 pattern from Section 2, or any policy mask).

TPU mapping notes: counts are int32, so any worker-group width W fits
(the int8 accumulator the datapath originally used capped groups at
W <= 127 and silently wrapped beyond); word-plane blocks are (8k, 128)
VREG-aligned (see :mod:`.sign_pack`); the word <-> value fan-out of 32 is
a sublane reduction / broadcast (:func:`~.sign_pack.pack_rows` /
:func:`~.sign_pack.unpack_rows`), so no reshape crosses the lane dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import LANE, PACK
from .sign_pack import pack_rows, pick_word_block, unpack_rows


# ---------------------------------------------------------------------------
# popcount across workers
# ---------------------------------------------------------------------------

def count_votes(packed_ref) -> jax.Array:
    """(W, RB, LANE) packed sign words -> (32 RB, LANE) int32 vote counts,
    one worker's word block at a time."""
    def add(w, acc):
        return acc + unpack_rows(packed_ref[w])
    _, rb, lane = packed_ref.shape
    return jax.lax.fori_loop(0, packed_ref.shape[0], add,
                             jnp.zeros((rb * PACK, lane), jnp.int32))


def _popcount_stack_kernel(packed_ref, out_ref):
    out_ref[...] = count_votes(packed_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def popcount_stack(packed: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(W, R, LANE) uint32 packed sign words -> (32 R, LANE) int32 vote counts."""
    w, r, lane = packed.shape
    assert lane == LANE
    wb = pick_word_block(r, max_words=8)
    grid = (r // wb,)
    return pl.pallas_call(
        _popcount_stack_kernel,
        out_shape=jax.ShapeDtypeStruct((r * PACK, LANE), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((w, wb, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(packed)


# ---------------------------------------------------------------------------
# majority / ternary decode of vote counts
# ---------------------------------------------------------------------------

def decode_margin(counts: jax.Array, gate: jax.Array, num_workers: int):
    """(32 RB, LANE) int32 counts + (RB, LANE) gate -> (sign, mask) words."""
    a = 2 * counts - num_workers                                    # vote margin
    sign = pack_rows((a > 0).astype(jnp.int32))
    mask = pack_rows((a != 0).astype(jnp.int32))
    return sign, mask & gate


def _majority_decode_kernel(counts_ref, gate_ref, sign_ref, mask_ref, *,
                            num_workers: int):
    sign_ref[...], mask_ref[...] = decode_margin(
        counts_ref[...].astype(jnp.int32), gate_ref[...], num_workers)


@functools.partial(jax.jit, static_argnames=("num_workers", "interpret"))
def majority_decode(counts: jax.Array, gate_words: jax.Array, *,
                    num_workers: int, interpret: bool = False):
    """Vote counts (M, LANE) + packed gate (M//32, LANE) -> ternary packed pair.

    Returns (sign_words, mask_words), each (M // 32, LANE) uint32.
    mask bit = (2c != W) AND gate bit; sign bit = (2c > W).
    """
    m, lane = counts.shape
    assert lane == LANE and m % PACK == 0
    num_words = m // PACK
    assert gate_words.shape == (num_words, LANE)
    wb = pick_word_block(num_words, max_words=8)
    grid = (num_words // wb,)
    out_shape = (jax.ShapeDtypeStruct((num_words, LANE), jnp.uint32),
                 jax.ShapeDtypeStruct((num_words, LANE), jnp.uint32))
    return pl.pallas_call(
        functools.partial(_majority_decode_kernel, num_workers=num_workers),
        out_shape=out_shape,
        grid=grid,
        in_specs=[pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((wb, LANE), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((wb, LANE), lambda i: (i, 0))),
        interpret=interpret,
    )(counts, gate_words)

"""Public jit'd wrappers for the NEURON-Fabric controller-datapath kernels.

On TPU the Pallas kernels lower to Mosaic; on CPU (this container, and any
unit-test environment) they execute in ``interpret=True`` mode, which runs
the kernel body element-for-element and therefore validates the exact packed
semantics the hardware path would produce.

The wrappers also own the *canonical bucket layout* plumbing: arbitrary
flat buckets are zero-padded and reshaped to (M, 128) value planes before
the kernels see them (see kernels/ref.py for the layout contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .ref import LANE, PACK, padded_len, shard_rows, to_plane, from_plane
from .sign_pack import sign_pack as _sign_pack_pallas
from .popcount_majority import (popcount_stack as _popcount_pallas,
                                majority_decode as _majority_pallas)
from .apply_update import (unpack_ternary as _unpack_pallas,
                           apply_sign_update as _apply_pallas)

__all__ = [
    "interpret_default", "pack_signs", "popcount_stack", "majority_decode",
    "unpack_ternary", "apply_sign_update", "ternary_gate_words",
    "gate_words_from_mask", "to_plane", "from_plane", "padded_len",
    "shard_rows", "LANE", "PACK",
]


@functools.cache
def interpret_default() -> bool:
    """Pallas interpret mode: True off-TPU (kernels are TPU-targeted)."""
    return jax.default_backend() != "tpu"


def _mode(interpret) -> str:
    """Dispatch: 'pallas' (TPU / interpret=False), 'interp' (interpret=True),
    'ref' (interpret=None off-TPU — pure-jnp oracle, identical bits, clean
    HLO for the dry-run analyses)."""
    if interpret is True:
        return "interp"
    if interpret is False:
        return "pallas"
    return "pallas" if not interpret_default() else "ref"


def pack_signs(plane: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Value plane (M, LANE) -> packed sign words (M // 32, LANE) uint32."""
    m = _mode(interpret)
    if m == "ref":
        return ref.sign_pack(plane)
    return _sign_pack_pallas(plane, interpret=(m == "interp"))


def popcount_stack(packed: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """(W, R, LANE) packed sign words -> (32 R, LANE) int32 vote counts."""
    m = _mode(interpret)
    if m == "ref":
        return ref.popcount_stack(packed)
    return _popcount_pallas(packed, interpret=(m == "interp"))


def majority_decode(counts: jax.Array, *, num_workers: int,
                    gate_words: jax.Array | None = None,
                    interpret: bool | None = None):
    """Vote counts -> ternary packed (sign_words, mask_words)."""
    if gate_words is None:
        r = counts.shape[0] // PACK
        gate_words = jnp.full((r, LANE), 0xFFFFFFFF, jnp.uint32)
    m = _mode(interpret)
    if m == "ref":
        return ref.majority_decode(counts, num_workers, gate_words)
    return _majority_pallas(counts, gate_words, num_workers=num_workers,
                            interpret=(m == "interp"))


def unpack_ternary(sign_words: jax.Array, mask_words: jax.Array, *,
                   dtype=jnp.float32, interpret: bool | None = None) -> jax.Array:
    """Ternary packed pair -> {-1, 0, +1} value plane."""
    m = _mode(interpret)
    if m == "ref":
        return ref.unpack_ternary(sign_words, mask_words, dtype=dtype)
    return _unpack_pallas(sign_words, mask_words, dtype=dtype,
                          interpret=(m == "interp"))


def apply_sign_update(param_plane: jax.Array, sign_words: jax.Array,
                      mask_words: jax.Array, scale, *,
                      interpret: bool | None = None) -> jax.Array:
    """Fused ``param - scale * decode(sign, mask)``."""
    m = _mode(interpret)
    if m == "ref":
        return ref.apply_sign_update(param_plane, sign_words, mask_words,
                                     scale)
    return _apply_pallas(param_plane, sign_words, mask_words,
                         jnp.asarray(scale, jnp.float32),
                         interpret=(m == "interp"))


def ternary_gate_words(num_rows: int, phase: int = 0) -> jax.Array:
    """Packed fixed 2-of-3 zero-gate pattern (Section 2 of the paper)."""
    return ref.ternary_gate_words(num_rows, phase)


def gate_words_from_mask(keep, pad_words: int | None = None) -> jax.Array:
    """Arbitrary flat keep mask -> packed gate word plane (host-side)."""
    return ref.gate_words_from_mask(keep, pad_words=pad_words)

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
from jax.sharding import PartitionSpec as P

from . import ref
from .ref import LANE, PACK, padded_len, shard_rows, to_plane, from_plane
from .sign_pack import sign_pack as _sign_pack_pallas
from .apply_update import unpack_ternary as _unpack_pallas

__all__ = [
    "interpret_default", "on_tpu", "fully_manual", "pack_signs",
    "unpack_ternary", "ternary_gate_words",
    "gate_words_from_mask", "to_plane", "from_plane", "padded_len",
    "shard_rows", "LANE", "PACK",
]


@functools.cache
def interpret_default() -> bool:
    """Pallas interpret mode: True off-TPU (kernels are TPU-targeted)."""
    return jax.default_backend() != "tpu"


def on_tpu() -> bool:
    """Whether kernels left at their default compile with Mosaic for the
    TPU (not the interpreter or the CPU oracle)."""
    return not interpret_default()


def fully_manual(fn, in_specs=P(), out_specs=P()):
    """``fn`` under a shard_map manual over every mesh axis still automatic.

    Mosaic kernels cannot be partitioned automatically, so every Pallas
    call in a train step runs where no mesh axis is left to the
    partitioner.  The train step's shard_map is manual over the DP axes
    only; this adds the remaining ones (``'model'``) for the span of
    ``fn``.  Specs default to replicated, so a leaf sharded over an auto
    axis is gathered first — the semantics of the un-sharded leaf.
    Outside any mesh (host-local sessions, vmapped virtual workers)
    ``fn`` is returned as is.
    """
    mesh = jax.sharding.get_abstract_mesh()
    rest = frozenset(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not rest:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=rest, check_vma=False)


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


def unpack_ternary(sign_words: jax.Array, mask_words: jax.Array, *,
                   dtype=jnp.float32, interpret: bool | None = None) -> jax.Array:
    """Ternary packed pair -> {-1, 0, +1} value plane."""
    m = _mode(interpret)
    if m == "ref":
        return ref.unpack_ternary(sign_words, mask_words, dtype=dtype)
    return _unpack_pallas(sign_words, mask_words, dtype=dtype,
                          interpret=(m == "interp"))


def ternary_gate_words(num_rows: int, phase: int = 0) -> jax.Array:
    """Packed fixed 2-of-3 zero-gate pattern (Section 2 of the paper)."""
    return ref.ternary_gate_words(num_rows, phase)


def gate_words_from_mask(keep, pad_words: int | None = None) -> jax.Array:
    """Arbitrary flat keep mask -> packed gate word plane (host-side)."""
    return ref.gate_words_from_mask(keep, pad_words=pad_words)

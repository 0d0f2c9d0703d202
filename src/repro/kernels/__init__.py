"""NEURON-Fabric controller-datapath kernels (Pallas TPU + pure-jnp oracles).

The paper's 512-bit five-cycle CXL aggregation datapath maps here to three
VREG-aligned Pallas stages (sign packing, worker PopCount, majority/ternary
decode) plus a beyond-paper fused packed-update kernel.  ``ref`` holds the
bit-exact pure-jnp oracles used by the functional tests (paper Section 6).
``fused`` is the codec-owned kernel-fusion subsystem: :class:`KernelSet`
capabilities a codec exposes through its ``pallas_kernels()`` hook, plus
the one-kernel-per-bucket drivers for the vote chain and the extension
codec quantizers.
"""
from . import ref
from .ops import (LANE, PACK, apply_sign_update, from_plane, fully_manual,
                  gate_words_from_mask, interpret_default, majority_decode,
                  on_tpu, pack_signs, padded_len, popcount_stack, shard_rows,
                  ternary_gate_words, to_plane, unpack_ternary)
from .fused import (Int4KernelSet, KernelSet, TopKKernelSet, VoteKernelSet,
                    fused_packed_vote, vote_kernel_set)

__all__ = [
    "ref", "LANE", "PACK", "apply_sign_update", "from_plane", "fully_manual",
    "gate_words_from_mask", "interpret_default", "majority_decode", "on_tpu",
    "pack_signs", "padded_len", "popcount_stack", "shard_rows",
    "ternary_gate_words",
    "to_plane", "unpack_ternary",
    "KernelSet", "VoteKernelSet", "Int4KernelSet", "TopKKernelSet",
    "fused_packed_vote", "vote_kernel_set",
]

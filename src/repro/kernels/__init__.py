"""NEURON-Fabric controller-datapath kernels (Pallas TPU + pure-jnp oracles).

The paper's 512-bit five-cycle CXL aggregation datapath maps here to one
vote chain of VREG-aligned Pallas kernels (:mod:`fused`): sign packing
(fused with the error-feedback inject when EF is on), one PopCount +
majority/ternary-gate kernel per routed shard, and the ternary decode —
or, for a host-local payload, the whole chain as one kernel.  ``fused``
is also the codec-owned kernel subsystem: :class:`KernelSet`
capabilities a codec exposes through its ``pallas_kernels()`` hook, and
the extension codecs' quantizers.  ``ref`` holds the bit-exact pure-jnp
oracles used by the functional tests (paper Section 6) and, off the TPU
with ``interpret=None``, by the program itself.
"""
from . import ref
from .ops import (LANE, PACK, from_plane, fully_manual, gate_words_from_mask,
                  interpret_default, on_tpu, pack_signs, padded_len,
                  shard_rows, ternary_gate_words, to_plane, unpack_ternary)
from .fused import (Int4KernelSet, KernelSet, TopKKernelSet, VoteKernelSet,
                    fused_packed_vote, vote_kernel_set)

__all__ = [
    "ref", "LANE", "PACK", "from_plane", "fully_manual",
    "gate_words_from_mask", "interpret_default", "on_tpu",
    "pack_signs", "padded_len", "shard_rows", "ternary_gate_words",
    "to_plane", "unpack_ternary",
    "KernelSet", "VoteKernelSet", "Int4KernelSet", "TopKKernelSet",
    "fused_packed_vote", "vote_kernel_set",
]

"""Pure-jnp reference oracles for the NEURON-Fabric controller datapath.

These mirror, bit-for-bit, the packed payload layout used by the Pallas
kernels.  All kernels operate on the *canonical bucket layout*:

    flat gradient bucket of N elements
      -> zero-padded to a multiple of LANE * 32 * 8
      -> reshaped to (M, LANE) with M a multiple of 256    ("value plane")
      -> sign words of shape (M // 32, LANE), uint32        ("word plane")

The word plane therefore has a multiple of 8 rows: whole (8, 128) VREG
tiles, which is what Mosaic needs of every word-plane block.

Bit ``b`` of word ``w[r, l]`` holds the sign of value ``v[32 * r + b, l]``
(1 = strictly positive, 0 = non-positive).  This is the TPU adaptation of
the paper's 512-bit CXL cache-line payload: one (8, 128) VREG row of uint32
words covers 8 * 128 * 32 = 32768 sign bits.

The paper's aggregation semantics (Section 2):

    b_{k,i} = 1{ sgn(g_{k,i}) > 0 }
    c_i     = PopCount(b_{0,i}, ..., b_{W-1,i})
    a_i     = 2 * c_i - W
    u_bin   = sgn(a_i)                  in {-1, 0, +1}
    u_ter   = m_i * u_bin               with zero gate m_i in {0, 1}

The returned aggregate is represented as a *ternary packed pair*
``(sign_words, mask_words)``: ``mask`` bit 0 means the element decodes to 0
(vote tie, or gated off); otherwise the ``sign`` bit selects +1 / -1.
G-Binary is the special case where the only zeros are vote ties.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128          # TPU vector lane count; canonical last dim
PACK = 32           # sign bits per uint32 word
SUBLANE = 8         # word rows per (8, 128) VREG tile
TILE = LANE * PACK  # elements covered by one word row


def padded_len(n: int) -> int:
    """Canonical padded length for an N-element bucket: whole word tiles."""
    step = TILE * SUBLANE
    return ((n + step - 1) // step) * step


def shard_rows(num_words: int, num_workers: int) -> int:
    """Word rows each of W owners receives from the packed all_to_all:
    the word plane right-padded so every shard is whole tiles."""
    step = SUBLANE * num_workers
    return (num_words + step - 1) // step * SUBLANE


def to_plane(flat: jax.Array) -> jax.Array:
    """Flat (N,) -> canonical value plane (M, LANE), zero padded."""
    n = flat.shape[0]
    p = padded_len(n)
    if p != n:
        flat = jnp.pad(flat, (0, p - n))
    return flat.reshape(p // LANE, LANE)


def from_plane(plane: jax.Array, n: int) -> jax.Array:
    """Canonical value plane -> flat (N,), dropping padding."""
    return plane.reshape(-1)[:n]


def _shifts32(dtype=jnp.uint32) -> jax.Array:
    return jnp.arange(PACK, dtype=dtype)


# ---------------------------------------------------------------------------
# sign packing
# ---------------------------------------------------------------------------

def sign_pack(plane: jax.Array) -> jax.Array:
    """Value plane (M, LANE) -> sign word plane (M//32, LANE) uint32.

    Bit b of word [r, l] = 1 iff plane[32*r + b, l] > 0.
    """
    m, lane = plane.shape
    assert m % PACK == 0, f"rows {m} not a multiple of {PACK}"
    bits = (plane > 0).astype(jnp.uint32).reshape(m // PACK, PACK, lane)
    return jnp.sum(bits << _shifts32().reshape(1, PACK, 1), axis=1).astype(jnp.uint32)


def unpack_bits(words: jax.Array) -> jax.Array:
    """Sign word plane (R, LANE) -> bit plane (32R, LANE) uint32 in {0,1}."""
    r, lane = words.shape
    bits = (words[:, None, :] >> _shifts32().reshape(1, PACK, 1)) & jnp.uint32(1)
    return bits.reshape(r * PACK, lane)


# ---------------------------------------------------------------------------
# popcount across workers ("the controller's PopCount tree")
# ---------------------------------------------------------------------------

def popcount_stack(packed: jax.Array) -> jax.Array:
    """(W, R, LANE) packed sign words -> per-element vote counts (32R, LANE) int32.

    counts[i] = c_i = PopCount over the W workers' sign bits.
    """
    w, r, lane = packed.shape
    bits = (packed[:, :, None, :] >> _shifts32().reshape(1, 1, PACK, 1)) & jnp.uint32(1)
    counts = jnp.sum(bits.astype(jnp.int32), axis=0)          # (R, 32, LANE)
    return counts.reshape(r * PACK, lane)


# ---------------------------------------------------------------------------
# majority decode (vote margin -> ternary packed aggregate)
# ---------------------------------------------------------------------------

def majority_decode(counts: jax.Array, num_workers: int,
                    gate_words: jax.Array | None = None):
    """Vote counts (M, LANE) -> ternary packed pair ((R, LANE) u32, (R, LANE) u32).

    a_i = 2 * c_i - W; sign bit = a_i > 0; mask bit = a_i != 0.
    If ``gate_words`` is given (packed zero-gate), mask &= gate.
    """
    m, lane = counts.shape
    a = 2 * counts.astype(jnp.int32) - num_workers
    sign_words = sign_pack(a.astype(jnp.float32))
    nz = (a != 0).astype(jnp.uint32).reshape(m // PACK, PACK, lane)
    mask_words = jnp.sum(nz << _shifts32().reshape(1, PACK, 1), axis=1).astype(jnp.uint32)
    if gate_words is not None:
        mask_words = mask_words & gate_words
    return sign_words, mask_words


# ---------------------------------------------------------------------------
# ternary zero gate (paper: fixed 2-of-3 pattern over flattened elements)
# ---------------------------------------------------------------------------

def ternary_gate_words(num_rows: int, phase: int = 0) -> jax.Array:
    """Packed 2-of-3 zero-gate pattern for a (num_rows, LANE) value plane.

    Element index i (row-major over the value plane) is gated to zero when
    (i + phase) % 3 == 2 — i.e. two consecutive elements keep the G-Binary
    update and the third returns zero, per Section 2 of the paper.
    """
    assert num_rows % PACK == 0
    keep = ((np.arange(num_rows * LANE, dtype=np.int64) + phase) % 3) != 2
    return gate_words_from_mask(keep)[:num_rows // PACK]


def gate_words_from_mask(keep: np.ndarray,
                         pad_words: int | None = None) -> jax.Array:
    """Arbitrary flat keep mask (N,) -> packed gate word plane.

    Generalizes :func:`ternary_gate_words` to any host-side boolean
    pattern — the fused bucket path uses it to pack the concatenation of
    per-leaf 2-of-3 gates into one bucket-wide gate.  Elements beyond N
    (canonical padding) keep = 1; ``pad_words`` optionally right-pads the
    word plane with all-ones rows to a given row count (the all_to_all
    row padding of the packed schedule — padding is dropped on unpack,
    so its gate value is irrelevant).
    """
    keep = np.asarray(keep, bool).reshape(-1)
    n = keep.shape[0]
    full = np.ones(padded_len(n), np.uint32)
    full[:n] = keep.astype(np.uint32)
    rows = full.shape[0] // LANE
    full = full.reshape(rows, LANE).reshape(rows // PACK, PACK, LANE)
    words = np.sum(full << np.arange(PACK, dtype=np.uint32).reshape(1, PACK, 1),
                   axis=1, dtype=np.uint64).astype(np.uint32)
    if pad_words is not None and pad_words > words.shape[0]:
        pad = np.full((pad_words - words.shape[0], LANE), 0xFFFFFFFF,
                      np.uint32)
        words = np.concatenate([words, pad], axis=0)
    return jnp.asarray(words)


# ---------------------------------------------------------------------------
# unpack ternary aggregate to values
# ---------------------------------------------------------------------------

def unpack_ternary(sign_words: jax.Array, mask_words: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    """Ternary packed pair -> value plane (M, LANE) of {-1, 0, +1}."""
    s = unpack_bits(sign_words).astype(jnp.int32)   # {0, 1}
    m = unpack_bits(mask_words).astype(jnp.int32)   # {0, 1}
    return ((2 * s - 1) * m).astype(dtype)


# ---------------------------------------------------------------------------
# fused apply: param update from packed aggregate
# ---------------------------------------------------------------------------

def apply_sign_update(param_plane: jax.Array, sign_words: jax.Array,
                      mask_words: jax.Array, scale) -> jax.Array:
    """param - scale * u, with u decoded from the ternary packed pair."""
    u = unpack_ternary(sign_words, mask_words, dtype=jnp.float32)
    out = param_plane.astype(jnp.float32) - jnp.asarray(scale, jnp.float32) * u
    return out.astype(param_plane.dtype)


# ---------------------------------------------------------------------------
# fused-stage references (oracles for repro.kernels.fused)
# ---------------------------------------------------------------------------
#
# Each function below is the pure-jnp composition the corresponding fused
# Pallas kernel must reproduce bit-for-bit.  They are deliberately written
# as compositions of the staged references above wherever one exists, so
# "fused == ref" transitively proves "fused == staged pipeline".

def encode_pack_ef(g_plane: jax.Array, e_plane: jax.Array):
    """EF inject + sign pack in one step: (words, g_eff plane).

    g_eff = g + e (the error-feedback inject); the words are the packed
    signs of g_eff.  Reference for the fused encode kernel.
    """
    g_eff = g_plane + e_plane
    return sign_pack(g_eff), g_eff


def vote_combine(routed: jax.Array, num_workers: int,
                 gate_words: jax.Array):
    """(W, R, LANE) routed sign words -> ternary packed pair, one step.

    Composition of :func:`popcount_stack` and :func:`majority_decode` —
    the fused combine kernel skips the (M, LANE) int32 counts
    materialization between them.
    """
    counts = popcount_stack(routed)
    return majority_decode(counts, num_workers, gate_words=gate_words)


def vote_pipeline_dense(stack: jax.Array, num_workers: int,
                        gate_words: jax.Array) -> jax.Array:
    """(W, M, LANE) value planes -> decoded ternary value plane (M, LANE).

    The whole local (no-collective) vote datapath in one step:
    encode -> popcount -> majority -> decode, never leaving registers in
    the fused kernel.  Reference composition of the staged kernels.
    """
    packed = jnp.stack([sign_pack(stack[w]) for w in range(stack.shape[0])])
    sw, mw = vote_combine(packed, num_workers, gate_words)
    return unpack_ternary(sw, mw, dtype=jnp.float32)


def ef_residual(plane: jax.Array, beta) -> jax.Array:
    """EF residual update on a value plane: x - beta * sgn(x).

    Reference for the fused residual kernel; elementwise-identical to
    the unfused ``g_eff - beta * jnp.sign(g_eff)`` on the leaf shape.
    """
    b = jnp.asarray(beta, plane.dtype)
    return plane - b * jnp.sign(plane)


def int4_quant_plane(plane: jax.Array, levels: float = 7.0) -> jax.Array:
    """Absmax-scaled int4 fake-quant of a float32 value plane.

    Same math as ``Int4Codec.encode``: one global absmax scale over the
    plane, round-to-nearest into [-levels, levels], dequantize.  The
    canonical zero padding never changes the absmax, so quantizing the
    padded plane is bit-identical to quantizing the flat bucket.
    """
    scale = jnp.max(jnp.abs(plane)) / levels
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(plane / safe), -levels, levels)
    return q * safe


def threshold_mask_plane(plane: jax.Array, thresh) -> jax.Array:
    """Magnitude sparsification: keep x where |x| >= thresh, else 0.

    Same comparison as ``TopKCodec.encode`` (threshold precomputed from
    the top-k magnitude); reference for the fused top-k mask kernel.
    """
    t = jnp.asarray(thresh, plane.dtype)
    return jnp.where(jnp.abs(plane) >= t, plane, jnp.zeros((), plane.dtype))


# ---------------------------------------------------------------------------
# end-to-end oracle (paper Section 2, all workers -> aggregate values)
# ---------------------------------------------------------------------------

def gbinary_aggregate_dense(grads: jax.Array) -> jax.Array:
    """(W, N) worker gradients -> (N,) G-Binary aggregate in {-1, 0, +1}.

    Direct (unpacked) evaluation of the Section 2 equations; used as the
    semantic oracle for the whole packed pipeline.
    """
    w = grads.shape[0]
    b = (grads > 0).astype(jnp.int32)
    c = jnp.sum(b, axis=0)
    a = 2 * c - w
    return jnp.sign(a).astype(jnp.float32)


def gternary_aggregate_dense(grads: jax.Array, phase: int = 0) -> jax.Array:
    """(W, N) worker gradients -> (N,) G-Ternary aggregate (2-of-3 gate)."""
    u = gbinary_aggregate_dense(grads)
    n = grads.shape[1]
    gate = (((jnp.arange(n) + phase) % 3) != 2).astype(jnp.float32)
    return u * gate

"""Codec-owned fused Pallas kernels: one kernel per bucket stage chain.

The staged controller datapath (``sign_pack`` -> ``popcount_stack`` ->
``majority_decode`` -> ``unpack_ternary``) pays an HBM round-trip between
every stage — exactly the overhead the paper's five-cycle near-memory
pipeline exists to avoid.  This module makes fused kernels a *codec
capability*:

  * :class:`KernelSet` — the protocol a codec's ``pallas_kernels()`` hook
    returns: ``encode_flat`` / ``combine`` / ``decode_apply`` entry points
    plus an optional fused error-feedback residual update, and modeled
    launch/HBM accounting so benchmarks price fused vs unfused uniformly.
  * :class:`VoteKernelSet` — the sign-vote chain shared by ``gbinary`` and
    ``gternary``: fused EF-inject+pack encode, a single popcount+majority
    combine (the staged pipeline's (M, LANE) int32 counts plane never
    touches HBM), and — when no collective separates the stages — the
    whole encode -> vote -> decode chain as ONE kernel
    (:func:`vote_pipeline`).
  * :class:`Int4KernelSet` / :class:`TopKKernelSet` — real Pallas kernels
    for the extension codecs (absmax fake-quant as a single two-phase
    kernel; magnitude-threshold sparsify), registered purely through the
    public ``Codec.pallas_kernels`` seam.
  * :func:`fused_packed_vote` — the bucket-level fusion driver: the
    ``packed_a2a`` schedule realized with the fused kernels (3 launches
    distributed, 1 launch when the payload is host-local).

Bit-identity contract: every fused kernel reproduces, bit-for-bit, the
pure-jnp reference composition in :mod:`repro.kernels.ref`
(``vote_combine`` / ``vote_pipeline_dense`` / ``encode_pack_ef`` /
``ef_residual`` / ``int4_quant_plane`` / ``threshold_mask_plane``), which
are themselves compositions of the staged references — so fused == ref
transitively proves fused == the unfused pipeline wherever both run.
The same three-way dispatch as :mod:`repro.kernels.ops` applies:
``interpret=True`` runs the kernel bodies on CPU, ``interpret=None``
off-TPU takes the reference path (identical bits, clean HLO).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .ref import LANE, PACK
from .apply_update import decode_ternary
from .popcount_majority import count_votes, decode_margin
from .sign_pack import pack_rows, pick_word_block, positive
from .ops import _mode, pack_signs, unpack_ternary


# ---------------------------------------------------------------------------
# gate-word helpers (shared by the fused AND unfused packed paths, so the
# two pipelines consume byte-identical zero gates by construction)
# ---------------------------------------------------------------------------

def local_gate_words(num_words: int, *, ternary: bool, gate_phase: int = 0,
                     gate_mask=None) -> jax.Array:
    """Packed zero gate for an un-routed (num_words, LANE) word plane."""
    if gate_mask is not None:
        return ref.gate_words_from_mask(gate_mask, pad_words=num_words)
    if ternary:
        return ref.ternary_gate_words(num_words * PACK, phase=gate_phase)
    return jnp.full((num_words, LANE), 0xFFFFFFFF, jnp.uint32)


def shard_gate_words(dp_axes, rows_per_shard: int, *, ternary: bool,
                     gate_phase: int = 0, gate_mask=None,
                     total_rows: int | None = None) -> jax.Array:
    """Packed zero gate for this shard's routed segment of a packed a2a.

    The gate is indexed by the element range this worker owns after the
    all_to_all (``axis_index * rows_per_shard`` word rows into the plane).
    ``gate_mask`` (host-side flat keep vector) overrides the uniform
    flat-index 2-of-3 pattern; ``total_rows`` right-pads the packed mask
    to the collective's row padding (dropped on unpack, gate irrelevant).
    """
    rw = rows_per_shard
    if not ternary:
        return jnp.full((rw, LANE), 0xFFFFFFFF, jnp.uint32)
    my = jax.lax.axis_index(dp_axes)
    if gate_mask is not None:
        full = ref.gate_words_from_mask(gate_mask, pad_words=total_rows)
        return jax.lax.dynamic_slice_in_dim(full, my * rw, rw, axis=0)
    # the 2-of-3 pattern repeats every 3 elements: precompute the three
    # phase rotations and select by this shard's flat element offset
    base = (my * rw * PACK * LANE + gate_phase) % 3
    gates = jnp.stack([ref.ternary_gate_words(rw * PACK, phase=p)
                       for p in range(3)])
    return gates[base]


# ---------------------------------------------------------------------------
# fused kernel bodies
# ---------------------------------------------------------------------------

def _encode_pack_ef_kernel(g_ref, e_ref, words_ref, geff_ref):
    """EF inject + sign pack fused: the g_eff = g + e plane is packed the
    moment it is formed, so the unfused path's inject-pass write/re-read
    of g_eff before packing never happens."""
    x = g_ref[...] + e_ref[...]
    geff_ref[...] = x
    words_ref[...] = pack_rows(positive(x))


def _vote_combine_kernel(routed_ref, gate_ref, sign_ref, mask_ref, *,
                         num_workers: int):
    """PopCount + majority/ternary decode in one kernel: the (M, LANE)
    int32 counts plane lives only in VMEM."""
    sign_ref[...], mask_ref[...] = decode_margin(
        count_votes(routed_ref), gate_ref[...], num_workers)


def _vote_pipeline_kernel(stack_ref, gate_ref, out_ref, *, num_workers: int):
    """The whole local vote datapath — encode, popcount, majority, ternary
    gate, decode — as ONE kernel over stacked (W, M, LANE) value planes.
    No packed words, counts, or ternary pair ever reach HBM; counting
    (v > 0) directly is bit-identical to packing the sign bits first."""
    def add(w, acc):
        return acc + positive(stack_ref[w])
    counts = jax.lax.fori_loop(0, stack_ref.shape[0], add,
                               jnp.zeros(out_ref.shape, jnp.int32))
    sign, mask = decode_margin(counts, gate_ref[...], num_workers)
    out_ref[...] = decode_ternary(sign, mask).astype(out_ref.dtype)


def _ef_residual_kernel(x_ref, beta_ref, out_ref):
    """EF-signSGD residual e' = x - beta * sgn(x) (beta precomputed).

    Scalar operands are float32 (Mosaic extracts no narrower scalar) and
    so is the arithmetic: beta * sgn(x) is exact, so the one rounding
    back to the plane's dtype is the reference's own."""
    beta = beta_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = (x - beta * jnp.sign(x)).astype(out_ref.dtype)


def _int4_quant_kernel(x_ref, out_ref, acc_ref, *, levels: float):
    """Two-phase absmax fake-quant: grid (2, nblocks); phase 0 streams the
    plane once accumulating the global absmax in SMEM, phase 1 re-streams
    it quantizing with the now-complete scale.  One launch replaces the
    staged absmax-reduce + quantize-pass pair; the running max visits
    blocks in a fixed order, and max() is order-independent, so the scale
    is bit-identical to ``jnp.max(jnp.abs(plane))``."""
    phase = pl.program_id(0)
    block = pl.program_id(1)

    @pl.when((phase == 0) & (block == 0))
    def _init():
        acc_ref[0, 0] = jnp.float32(0.0)

    @pl.when(phase == 0)
    def _scan():
        acc_ref[0, 0] = jnp.maximum(acc_ref[0, 0],
                                    jnp.max(jnp.abs(x_ref[...])))

    @pl.when(phase == 1)
    def _quant():
        scale = acc_ref[0, 0] / levels
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(x_ref[...] / safe), -levels, levels)
        out_ref[...] = q * safe


def _threshold_mask_kernel(x_ref, t_ref, out_ref):
    """Magnitude sparsify: keep x where |x| >= t (t = k-th magnitude)."""
    t = t_ref[0, 0]
    x = x_ref[...]
    keep = jnp.abs(x.astype(jnp.float32)) >= t
    out_ref[...] = jnp.where(keep, x, jnp.zeros((), x.dtype))


# ---------------------------------------------------------------------------
# jit'd entry points (same 3-way interpret dispatch as kernels.ops)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def _encode_pack_ef_call(g_plane, e_plane, *, interpret: bool):
    m, lane = g_plane.shape
    num_words = m // PACK
    wb = pick_word_block(num_words, max_words=8)
    out_shape = (jax.ShapeDtypeStruct((num_words, LANE), jnp.uint32),
                 jax.ShapeDtypeStruct((m, LANE), g_plane.dtype))
    return pl.pallas_call(
        _encode_pack_ef_kernel,
        out_shape=out_shape,
        grid=(num_words // wb,),
        in_specs=[pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0))),
        interpret=interpret,
    )(g_plane, e_plane)


def encode_pack_ef(g_plane: jax.Array, e_plane: jax.Array, *,
                   interpret: bool | None = None):
    """Fused EF inject + sign pack: -> (sign words, g_eff value plane)."""
    m = _mode(interpret)
    if m == "ref":
        return ref.encode_pack_ef(g_plane, e_plane)
    return _encode_pack_ef_call(g_plane, e_plane, interpret=(m == "interp"))


@functools.partial(jax.jit, static_argnames=("num_workers", "interpret"))
def _vote_combine_call(routed, gate_words, *, num_workers: int,
                       interpret: bool):
    w, r, lane = routed.shape
    wb = pick_word_block(r, max_words=8)
    out_shape = (jax.ShapeDtypeStruct((r, LANE), jnp.uint32),
                 jax.ShapeDtypeStruct((r, LANE), jnp.uint32))
    return pl.pallas_call(
        functools.partial(_vote_combine_kernel, num_workers=w),
        out_shape=out_shape,
        grid=(r // wb,),
        in_specs=[pl.BlockSpec((w, wb, LANE), lambda i: (0, i, 0)),
                  pl.BlockSpec((wb, LANE), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((wb, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((wb, LANE), lambda i: (i, 0))),
        interpret=interpret,
    )(routed, gate_words)


def vote_combine(routed: jax.Array, gate_words: jax.Array, *,
                 num_workers: int, interpret: bool | None = None):
    """(W, R, LANE) routed sign words + packed gate -> ternary packed pair.

    One kernel for popcount_stack + majority_decode; the int32 counts
    plane (8x the packed payload) never reaches HBM.
    """
    m = _mode(interpret)
    if m == "ref":
        return ref.vote_combine(routed, num_workers, gate_words)
    return _vote_combine_call(routed, gate_words, num_workers=num_workers,
                              interpret=(m == "interp"))


@functools.partial(jax.jit, static_argnames=("num_workers", "dtype",
                                             "interpret"))
def _vote_pipeline_call(stack, gate_words, *, num_workers: int, dtype,
                        interpret: bool):
    w, m, lane = stack.shape
    num_words = m // PACK
    wb = pick_word_block(num_words, max_words=8)
    return pl.pallas_call(
        functools.partial(_vote_pipeline_kernel, num_workers=w),
        out_shape=jax.ShapeDtypeStruct((m, LANE), dtype),
        grid=(num_words // wb,),
        in_specs=[pl.BlockSpec((w, wb * PACK, LANE), lambda i: (0, i, 0)),
                  pl.BlockSpec((wb, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((wb * PACK, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(stack, gate_words)


def vote_pipeline(stack: jax.Array, gate_words: jax.Array, *,
                  num_workers: int, dtype=jnp.float32,
                  interpret: bool | None = None) -> jax.Array:
    """(W, M, LANE) stacked value planes -> decoded {-1,0,+1} plane.

    The whole encode -> vote -> decode chain as ONE kernel (the paper's
    single streaming datapath stage) — usable whenever no collective
    separates the stages (host-local payloads, or post-routing stacks).
    """
    m = _mode(interpret)
    if m == "ref":
        return ref.vote_pipeline_dense(stack, num_workers,
                                       gate_words).astype(dtype)
    return _vote_pipeline_call(stack, gate_words, num_workers=num_workers,
                               dtype=dtype, interpret=(m == "interp"))


def _scalar_operand(x, dtype) -> jax.Array:
    """A scalar rounded to the plane's dtype, carried as (1, 1) float32."""
    return jnp.asarray(x, dtype).astype(jnp.float32).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ef_residual_call(plane, beta, *, interpret: bool):
    m, lane = plane.shape
    rb = pick_word_block(m // PACK, max_words=8) * PACK
    return pl.pallas_call(
        _ef_residual_kernel,
        out_shape=jax.ShapeDtypeStruct((m, LANE), plane.dtype),
        grid=(m // rb,),
        in_specs=[pl.BlockSpec((rb, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(plane, _scalar_operand(beta, plane.dtype))


def ef_residual_plane(plane: jax.Array, beta, *,
                      interpret: bool | None = None) -> jax.Array:
    """EF residual e' = x - beta * sgn(x) on a value plane."""
    m = _mode(interpret)
    if m == "ref":
        return ref.ef_residual(plane, beta)
    return _ef_residual_call(plane, beta, interpret=(m == "interp"))


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def _int4_quant_call(plane, *, levels: float, interpret: bool):
    m, lane = plane.shape
    rb = pick_word_block(m // PACK, max_words=8) * PACK
    nblocks = m // rb
    return pl.pallas_call(
        functools.partial(_int4_quant_kernel, levels=levels),
        out_shape=jax.ShapeDtypeStruct((m, LANE), plane.dtype),
        grid=(2, nblocks),
        in_specs=[pl.BlockSpec((rb, LANE), lambda p, i: (i, 0))],
        out_specs=pl.BlockSpec((rb, LANE), lambda p, i: (i, 0)),
        scratch_shapes=[_smem_scratch()],
        interpret=interpret,
    )(plane)


def _smem_scratch():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.SMEM((1, 1), jnp.float32)


def int4_quant_plane(plane: jax.Array, *, levels: float = 7.0,
                     interpret: bool | None = None) -> jax.Array:
    """Absmax int4 fake-quant of a float32 value plane, one launch."""
    m = _mode(interpret)
    if m == "ref":
        return ref.int4_quant_plane(plane, levels=levels)
    return _int4_quant_call(plane, levels=levels, interpret=(m == "interp"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _threshold_mask_call(plane, thresh, *, interpret: bool):
    m, lane = plane.shape
    rb = pick_word_block(m // PACK, max_words=8) * PACK
    return pl.pallas_call(
        _threshold_mask_kernel,
        out_shape=jax.ShapeDtypeStruct((m, LANE), plane.dtype),
        grid=(m // rb,),
        in_specs=[pl.BlockSpec((rb, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(plane, _scalar_operand(thresh, plane.dtype))


def threshold_mask_plane(plane: jax.Array, thresh, *,
                         interpret: bool | None = None) -> jax.Array:
    """Magnitude-threshold sparsify of a value plane, one launch."""
    m = _mode(interpret)
    if m == "ref":
        return ref.threshold_mask_plane(plane, thresh)
    return _threshold_mask_call(plane, thresh, interpret=(m == "interp"))


def ef_update_fused(g_eff: jax.Array, ef: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """Fused-kernel EF residual update, bit-identical to ``_ef_update``.

    beta is the mean |g_eff| over the *leaf-shaped* array (identical to
    the unfused reduction); the elementwise residual runs as one kernel
    on the canonical plane — same per-element ops, so identical bits.
    """
    beta = jnp.mean(jnp.abs(g_eff))
    plane = ref.to_plane(g_eff.reshape(-1))
    resid = ef_residual_plane(plane, beta, interpret=interpret)
    return ref.from_plane(resid, g_eff.size).reshape(g_eff.shape).astype(ef.dtype)


# ---------------------------------------------------------------------------
# bucket-level fusion driver: packed_a2a on the fused kernels
# ---------------------------------------------------------------------------

def fused_packed_vote(g: jax.Array, dp_axes, num_workers: int, *,
                      ternary: bool = False, gate_phase: int = 0,
                      ef: jax.Array | None = None,
                      interpret: bool | None = None, gate_mask=None):
    """The ``packed_a2a`` vote schedule realized with fused kernels.

    Distributed (3 launches vs the staged pipeline's 4): fused
    EF-inject+pack encode -> all_to_all -> fused popcount+majority
    combine -> all_gather -> decode.  Host-local (``dp_axes`` empty, a
    configuration the staged path cannot run at all): the entire chain
    is ONE :func:`vote_pipeline` launch.  Bit-identical to
    ``core.lowbit._packed_a2a_local`` wherever that path runs.

    Returns ``(u, new_ef)`` exactly like the unfused collective.
    """
    w = num_workers
    n = g.size
    if not dp_axes:
        # no collective separates the stages: one kernel per bucket
        g_eff = g if ef is None else g + ef.astype(g.dtype)
        plane = ref.to_plane(g_eff.reshape(-1))
        gate = local_gate_words(plane.shape[0] // PACK, ternary=ternary,
                                gate_phase=gate_phase, gate_mask=gate_mask)
        u_plane = vote_pipeline(plane[None], gate, num_workers=w,
                                dtype=jnp.float32, interpret=interpret)
        u = ref.from_plane(u_plane, n).reshape(g.shape).astype(g.dtype)
        new_ef = None if ef is None else \
            ef_update_fused(g_eff, ef, interpret=interpret)
        return u, new_ef

    if ef is None:
        plane = ref.to_plane(g.reshape(-1))
        words = pack_signs(plane, interpret=interpret)
        g_eff = None
    else:
        g_plane = ref.to_plane(g.reshape(-1))
        e_plane = ref.to_plane(ef.astype(g.dtype).reshape(-1))
        words, geff_plane = encode_pack_ef(g_plane, e_plane,
                                           interpret=interpret)
        g_eff = ref.from_plane(geff_plane, n).reshape(g.shape)
    r = words.shape[0]
    rw = ref.shard_rows(r, w)
    pad_r = rw * w - r
    if pad_r:
        words = jnp.pad(words, ((0, pad_r), (0, 0)))
    routed = jax.lax.all_to_all(words.reshape(w, rw, LANE), dp_axes,
                                split_axis=0, concat_axis=0, tiled=False)
    gate = shard_gate_words(dp_axes, rw, ternary=ternary,
                            gate_phase=gate_phase, gate_mask=gate_mask,
                            total_rows=r + pad_r)
    sw, mw = vote_combine(routed, gate, num_workers=w, interpret=interpret)
    sw_all = jax.lax.all_gather(sw, dp_axes, axis=0, tiled=True)[:r]
    mw_all = jax.lax.all_gather(mw, dp_axes, axis=0, tiled=True)[:r]
    u_plane = unpack_ternary(sw_all, mw_all, dtype=jnp.float32,
                             interpret=interpret)
    u = ref.from_plane(u_plane, n).reshape(g.shape).astype(g.dtype)
    new_ef = None if ef is None else \
        ef_update_fused(g_eff, ef, interpret=interpret)
    return u, new_ef


# ---------------------------------------------------------------------------
# KernelSet protocol + built-in sets
# ---------------------------------------------------------------------------

# modeled HBM bytes per element of a bucket, by representation
_F32 = 4.0          # one float32
_WORDS = 1 / 8.0    # packed sign bits
_PAIR = 1 / 4.0     # ternary packed (sign, mask) pair
_COUNTS = 4.0       # int32 vote counts


class KernelSet:
    """Protocol for a codec's fused Pallas kernels.

    A codec's ``pallas_kernels()`` hook returns one of these (or None to
    keep the reference-jnp path).  Two capability axes:

      * ``votes`` — the set realizes the packed sign-vote chain; the
        ``packed_a2a`` backend calls :meth:`packed_vote` for the whole
        bucket.
      * ``means`` — the set realizes encode/decode around a mean
        collective; the psum backend calls :meth:`encode_flat` /
        :meth:`decode_apply` on the flat payload.

    ``launches`` / ``hbm_bytes`` are the *modeled* accounting (kernel
    launch count, HBM bytes streamed per bucket) that benchmarks and the
    nightly fused-vs-unfused gate consume; they price the algorithmic
    reads/writes each pipeline must perform, not transient compiler
    spills.  ``signature()`` feeds the session step-cache key so swapping
    a codec's kernels invalidates compiled steps.
    """
    name = "kernelset"
    votes = False
    means = False

    def signature(self) -> str:
        return self.name

    def launches(self, *, fused: bool, distributed: bool = True,
                 ef: bool = False) -> int:
        raise NotImplementedError

    def hbm_bytes(self, n: int, *, num_workers: int, fused: bool,
                  distributed: bool = True, ef: bool = False) -> float:
        raise NotImplementedError

    # --- mean-reduction entry points (means=True sets) ---
    def encode_flat(self, flat: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
        raise NotImplementedError

    def decode_apply(self, payload: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
        return payload

    # --- vote-reduction entry point (votes=True sets) ---
    def packed_vote(self, g, dp_axes, num_workers, *, ternary, gate_phase,
                    ef, interpret, gate_mask=None):
        raise NotImplementedError


class VoteKernelSet(KernelSet):
    """Fused sign-vote chain for ``gbinary`` / ``gternary``."""
    name = "vote"
    votes = True

    def signature(self) -> str:
        return "vote:v1"

    def packed_vote(self, g, dp_axes, num_workers, *, ternary, gate_phase,
                    ef, interpret, gate_mask=None):
        return fused_packed_vote(g, dp_axes, num_workers, ternary=ternary,
                                 gate_phase=gate_phase, ef=ef,
                                 interpret=interpret, gate_mask=gate_mask)

    def launches(self, *, fused: bool, distributed: bool = True,
                 ef: bool = False) -> int:
        # staged: pack, popcount, majority, decode (EF inject/residual are
        # XLA elementwise passes either way — not Pallas launches)
        if not fused:
            return 4
        # fused: encode / combine / decode around the collectives —
        # or the whole chain as one kernel when nothing separates stages
        return 3 if distributed else 1

    def hbm_bytes(self, n: int, *, num_workers: int, fused: bool,
                  distributed: bool = True, ef: bool = False) -> float:
        w = num_workers
        if distributed:
            # per worker; the routed segment it owns covers n/W elements,
            # scaled back up here so fused/unfused compare on equal terms
            enc = n * (_F32 + _WORDS)                       # read g, write words
            if ef:
                # unfused: inject pass (read g+e, write g_eff) then pack
                # re-reads g_eff; fused packs g_eff as it is formed
                enc += n * (2 * _F32 + _F32) if not fused else n * (2 * _F32)
            dec = n * (_PAIR + _F32)                        # read pair, write u
            if fused:
                comb = n * (w * _WORDS + _WORDS + _PAIR)    # stack+gate -> pair
                return enc + comb + dec
            pop = n * (w * _WORDS + _COUNTS)                # stack -> counts
            maj = n * (_COUNTS + _WORDS + _PAIR)            # counts+gate -> pair
            return enc + pop + maj + dec
        # host-local: all W planes resident, no collective
        if fused:
            return n * (w * _F32 + _WORDS + _F32)           # stacks+gate -> u
        pack = w * n * (_F32 + _WORDS)
        pop = n * (w * _WORDS + _COUNTS)
        maj = n * (_COUNTS + _WORDS + _PAIR)
        dec = n * (_PAIR + _F32)
        return pack + pop + maj + dec


class Int4KernelSet(KernelSet):
    """Single-launch absmax int4 fake-quant for the ``int4`` codec."""
    name = "int4"
    means = True

    def __init__(self, levels: float = 7.0):
        self.levels = float(levels)

    def signature(self) -> str:
        return f"int4:v1:levels={self.levels:g}"

    def encode_flat(self, flat: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
        n = flat.shape[0]
        plane = ref.to_plane(flat.astype(jnp.float32))
        out = int4_quant_plane(plane, levels=self.levels, interpret=interpret)
        return ref.from_plane(out, n).astype(flat.dtype)

    def launches(self, *, fused: bool, distributed: bool = True,
                 ef: bool = False) -> int:
        # staged: absmax reduce pass + quantize pass; fused: one two-phase
        # kernel carrying the scale across phases in SMEM
        return 1 if fused else 2

    def hbm_bytes(self, n: int, *, num_workers: int, fused: bool,
                  distributed: bool = True, ef: bool = False) -> float:
        # both stream the plane twice (scan + quant) and write it once;
        # fusion folds the launches, not the reads: 12n either way
        return n * (2 * _F32 + _F32)


class TopKKernelSet(KernelSet):
    """Magnitude-threshold sparsify kernel for the ``topk`` codec."""
    name = "topk"
    means = True

    def __init__(self, fraction: float):
        self.fraction = float(fraction)

    def signature(self) -> str:
        return f"topk:v1:f={self.fraction:g}"

    def encode_flat(self, flat: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
        f = jnp.abs(flat.astype(jnp.float32)).reshape(-1)
        k = max(1, int(f.shape[0] * self.fraction))
        thresh = jax.lax.top_k(f, k)[0][-1]
        plane = ref.to_plane(flat)
        out = threshold_mask_plane(plane, thresh.astype(flat.dtype),
                                   interpret=interpret)
        return ref.from_plane(out, flat.shape[0])

    def launches(self, *, fused: bool, distributed: bool = True,
                 ef: bool = False) -> int:
        # staged: |x| materialization, top-k select, masking pass; fused:
        # top-k reads |x| on the fly + one mask kernel
        return 2 if fused else 3

    def hbm_bytes(self, n: int, *, num_workers: int, fused: bool,
                  distributed: bool = True, ef: bool = False) -> float:
        select = n * _F32                                   # top-k scan
        mask = n * (2 * _F32)                               # read x, write out
        if fused:
            return select + mask
        return n * (2 * _F32) + select + mask               # + |x| round trip


@functools.cache
def vote_kernel_set() -> VoteKernelSet:
    """Shared singleton: gbinary/gternary differ only in the gate operand."""
    return VoteKernelSet()

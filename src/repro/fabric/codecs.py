"""Gradient-codec registry: *what bits go on the wire*.

The fabric has two orthogonal axes, and this module owns the first:

  * **Codec** — the communicated gradient *representation* and its
    cross-worker reduction semantics: FP32 mean, packed G-Binary
    sign-count, gated G-Ternary, a quantized int4 mean, a top-k
    sparsifier, ...  A codec owns the payload contract end to end:
    per-worker encode, reduction kind, post-reduction decode, the
    ternary-gate and error-feedback capability flags, bits/element wire
    accounting, and the sim datapath lane descriptor.
  * **Schedule backend** (:mod:`repro.fabric.registry`) — the transport:
    how the encoded bytes actually move on the mesh (psum ring, dense
    int8 votes, packed ``all_to_all``, ...).

Codecs register under a string name — the same extension idiom as
schedules (PR 1), controllers (PR 3), and sim topologies (PR 4) — and
plans simply *name* them: ``GroupPolicy(mode="int4")`` works exactly
like ``GroupPolicy(mode=AggregationMode.G_BINARY)`` (the legacy enum's
values are the built-in codec names).  Schedule backends are
codec-parametric: they ask the codec for encode/decode/gate behaviour
instead of branching on a closed mode enum, so a new representation
plugs into every transport, the traffic model, and the simulator
without editing any of them::

    from repro.fabric import GradientCodec, register_codec

    @register_codec("int2")
    class Int2(GradientCodec):
        name = "int2"
        bits_per_element = 2.0
        def encode(self, ctx, g):            # per-worker wire payload
            s = jnp.max(jnp.abs(g))
            return jnp.round(g / jnp.where(s > 0, s, 1.0)) * s

    plan = AdmissionPlan.lowbit_backbone("int2")     # name it like a mode

Reduction kinds
---------------
``reduction = "mean"`` declares an elementwise-summable payload: the
transport averages the encoded per-worker payloads (``psum`` /
``sign_of_mean`` style backends), then :meth:`~GradientCodec.decode`
runs on the mean.  ``reduction = "vote"`` declares the paper's
sign-vote contract: workers contribute sign bits, the transport
popcounts them, and the majority (plus the codec's zero gate when
``gated``) decides — the G-Binary / G-Ternary pipeline of Section 2.
:func:`repro.core.modes.wire_schedule` uses the reduction kind to keep
codecs off transports that cannot realize them (a mean codec nominally
on ``vote_psum`` rides ``psum``; a vote codec on ``psum`` rides
``vote_psum`` — the historical bypass semantics, generalized).

Encode granularity is the collective payload: the leaf on the per-leaf
path, the fused flat bucket on the bucketed path (the paper's
controller is bucket-granular, Section 5.2).  Bucket-statistic codecs
(e.g. an absmax-scaled quantizer) therefore see per-bucket statistics
when fused; the four built-ins are statistic-free, which is why they
are bit-identical on both paths.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Protocol, runtime_checkable

import numpy as np

from ..core.modes import AggregationMode, codec_name
from ..core.registry import Registry

__all__ = [
    "Codec", "CodecLane", "GradientCodec", "MaskGate", "available_codecs",
    "get_codec", "register_codec", "resolve_leaf_gate_mask",
    "ring_wire_bytes", "unregister_codec",
]


@dataclasses.dataclass(frozen=True)
class CodecLane:
    """Sim-datapath lane descriptor for one codec.

    Field-compatible with :class:`repro.sim.datapath.LaneSpec`; the
    :class:`~repro.sim.datapath.FlitPipeline` resolves a launch's lane
    from its codec, so a registered codec times correctly in the
    simulator without touching the built-in lane table.
    """
    name: str
    #: flits issued per initiation interval slot (usually 1).
    initiation_interval: float = 1.0
    #: extra stall cycles charged per flit (gate fetch, bypass hazards).
    stall_cycles_per_flit: float = 0.0
    #: the lane's stages run as one fused pipeline (the paper's single
    #: streaming datapath stage); unfused lanes re-fill the pipeline once
    #: per staged pass (:attr:`repro.sim.datapath.FlitPipeline.unfused_passes`).
    fused: bool = False


@dataclasses.dataclass(frozen=True)
class MaskGate:
    """Bucket zero gate carrying an explicit host keep mask.

    The gate representation for codecs with arbitrary (non-2-of-3)
    keep patterns — the default :meth:`GradientCodec.bucket_gate`
    builds one from per-leaf ``leaf_gate_mask`` patterns.  Unlike
    :class:`repro.core.buckets.BucketGate` the device vector is a
    materialized constant (an arbitrary mask has no iota shortcut).
    """
    keep: Any                   # host-side boolean (N,) array

    def mask(self) -> np.ndarray:
        return np.asarray(self.keep, bool)

    def vector(self, dtype) -> Any:
        import jax.numpy as jnp
        return jnp.asarray(self.mask(), dtype)


_UNGATED_MASK_ERROR = (
    "codec {0!r} returned a leaf gate mask but declares gated=False; the "
    "vote transports only apply gates of gated codecs — set gated = True "
    "on the codec so the declared keep pattern actually takes effect")


def resolve_leaf_gate_mask(codec: "Codec", shape: Any, gate_phase: int):
    """A codec's per-leaf keep mask, validated against its ``gated`` flag.

    The single accessor the vote transports use: returns
    ``codec.leaf_gate_mask(...)`` and raises — instead of silently
    dropping the mask — when an ungated codec supplies one.
    """
    mask = codec.leaf_gate_mask(shape, gate_phase)
    if mask is not None and not getattr(codec, "gated", False):
        raise ValueError(_UNGATED_MASK_ERROR.format(codec.name))
    return mask


def ring_wire_bytes(payload_bytes: float, num_workers: int,
                    trips: float = 2.0) -> float:
    """Ring-collective bytes/device for a given payload size.

    ``trips = 2`` is the reduce-scatter + all-gather round trip of a
    ring all-reduce; the shared helper replaces the per-backend copies
    of the ``2 (W-1)/W * payload`` formula.
    """
    if num_workers <= 1:
        return 0.0
    f = (num_workers - 1) / num_workers
    return trips * f * payload_bytes


# ---------------------------------------------------------------------------
# the protocol + base class
# ---------------------------------------------------------------------------

@runtime_checkable
class Codec(Protocol):
    """Structural protocol every registered codec satisfies.

    Required attributes: ``name`` and ``bits_per_element``.  Everything
    else has paper-faithful defaults on :class:`GradientCodec`, which
    extension codecs should subclass.
    """

    name: str
    bits_per_element: float


class GradientCodec:
    """Base codec: FP32-bypass defaults, hooks for every contract axis.

    Subclasses override only what differs from a transparent mean codec:

    ``reduction``        — ``"mean"`` (encoded payloads are averaged) or
                           ``"vote"`` (sign votes + majority decode).
    ``gated``            — the codec zero-gates the majority output
                           (G-Ternary's 2-of-3 gate); drives gate-word
                           packing on the fused path and the ``ternary``
                           leg of the vote collectives.
    ``threads_ef``       — the codec consumes error-feedback residuals
                           (injected/updated per leaf by the bucket
                           layer on EF-capable transports).
    ``lane``             — :class:`CodecLane` timing descriptor for the
                           sim's flit pipeline.
    ``default_schedule`` — transport used when a plan names no schedule.
    ``pallas_kernels``   — optional fused Pallas :class:`~repro.kernels.
                           fused.KernelSet`; the psum transport runs a
                           mean-capable set around its collective.
    """

    name: str = "identity"
    bits_per_element: float = 32.0
    reduction: str = "mean"
    gated: bool = False
    threads_ef: bool = False
    lane: CodecLane = CodecLane("fp32_bypass", fused=True)
    default_schedule: str = "psum"

    # -- mean-reduction hooks (psum-style transports) --------------------
    def encode(self, ctx: Any, g: Any) -> Any:
        """Per-worker wire representation of the gradient payload."""
        return g

    def decode(self, ctx: Any, u: Any) -> Any:
        """Post-reduction decode of the averaged payload."""
        return u

    # -- vote-reduction hooks --------------------------------------------
    def bucket_gate(self, bucket: Any):
        """Zero gate for a fused bucket (``None`` when ungated).

        The default derives the fused gate from the codec's own
        declaration, so per-leaf and fused paths always zero the same
        elements: ungated codecs return None; gated codecs concatenate
        per-leaf :meth:`leaf_gate_mask` patterns (falling back, per
        leaf, to the built-in 2-of-3 flat-index gate at the bucket's
        phase — each leaf restarting at its own flat index 0, paper
        Section 2).  Override only for gate structure this composition
        cannot express; the returned object must expose
        ``mask() -> np.ndarray`` and ``vector(dtype) -> jax.Array``
        over the bucket's flat payload (see
        :class:`repro.core.buckets.BucketGate`).
        """
        from ..core.buckets import BucketGate
        phase = bucket.key.gate_phase
        masks = [self.leaf_gate_mask(s.shape, phase) for s in bucket.slots]
        if not self.gated:
            if any(m is not None for m in masks):
                raise ValueError(_UNGATED_MASK_ERROR.format(self.name))
            return None
        if all(m is None for m in masks):
            # pure 2-of-3 per-leaf segments: the device-built BucketGate
            # avoids a bucket-sized host constant in the compiled step
            return BucketGate(segments=tuple((s.size, phase)
                                             for s in bucket.slots))
        parts = []
        for slot, m in zip(bucket.slots, masks):
            if m is None:
                # per-leaf 2-of-3 fallback from the one canonical source
                m = BucketGate(segments=((slot.size, phase),)).mask()
            parts.append(np.asarray(m, bool).reshape(-1))
        return MaskGate(np.concatenate(parts))

    def leaf_gate_mask(self, shape: Any, gate_phase: int):
        """Explicit keep mask for one leaf on the per-leaf vote paths.

        ``None`` (the default) lets the collective build the built-in
        2-of-3 flat-index gate from ``gate_phase``; codecs with custom
        gate patterns return a host-side boolean ``(N,)`` array (flat
        over the leaf) here — ``vote_psum`` applies it as a device keep
        vector and ``packed_a2a`` packs it into gate words, so both
        transports zero the same elements.  (Packed gate masks require
        a fully local leaf — TP-sharded leaves must stay on
        ``vote_psum``.)
        """
        return None

    # -- fused Pallas kernels (the codec-owned kernel capability) --------
    def pallas_kernels(self):
        """The codec's fused :class:`~repro.kernels.fused.KernelSet`.

        ``None`` (the default) keeps the codec's own jnp
        :meth:`encode`/:meth:`decode`.  Mean codecs return a
        mean-capable set (the psum transport runs its
        ``encode_flat``/``decode_apply`` around the collective), which
        must be bit-identical to the codec's :meth:`encode`/:meth:`decode`.
        Vote codecs may return a vote-capable set for the modeled
        launch/HBM accounting; the ``packed_a2a`` transport runs the one
        fused vote chain for every vote codec either way.  Sessions key
        compiled steps on :meth:`kernel_signature`, not object identity,
        so return a stable (cached) instance.
        """
        return None

    def kernel_signature(self) -> str | None:
        """Step-cache key component for the codec's kernel set (or None)."""
        ks = self.pallas_kernels()
        return None if ks is None else ks.signature()

    # -- accounting ------------------------------------------------------
    def payload_bytes(self, n_elements: int) -> float:
        """Wire payload bytes for ``n_elements`` under this codec."""
        return n_elements * self.bits_per_element / 8.0

    # -- KV-cache capability (serving) -----------------------------------
    #: the codec can represent KV-cache blocks (not just gradients).
    #: Sign-vote codecs stay False — a {-1, 0, +1} alphabet cannot carry
    #: key/value activations; mean-family codecs (FP32 bypass,
    #: quantizers) opt in and the serving engine routes every cache
    #: block through ``kv_encode``/``kv_decode``.
    kv_cache: bool = False

    def kv_encode(self, block: Any) -> Any:
        """Stored representation of one host-side KV-cache block.

        Mirrors :meth:`encode`'s functional convention: lossy codecs
        return the dequantized values their wire codes decode to (the
        int4 block carries 4-bit codes plus a scale on the wire; the
        functional path stores the values those codes reproduce), so
        byte accounting uses :meth:`kv_bytes` while the compute path
        sees exactly what a bit-true decoder would.  Blocks are host
        ``numpy`` arrays — encoding happens off the jitted step.
        """
        return block

    def kv_decode(self, block: Any) -> Any:
        """Inverse of :meth:`kv_encode` (identity for functional codecs)."""
        return block

    def kv_bytes(self, n_elements: int) -> float:
        """Resident/transferred bytes for ``n_elements`` KV-cache values."""
        return self.payload_bytes(n_elements)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"{type(self).__name__}(name={self.name!r}, "
                f"bits={self.bits_per_element:.3g}, {self.reduction})")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _prepare_codec(obj: Any, keys) -> Codec:
    codec = obj() if isinstance(obj, type) else obj
    if not isinstance(codec, Codec):
        raise TypeError(
            f"codec {keys[0]!r} must define 'name' and "
            f"'bits_per_element' (subclass GradientCodec)")
    return codec


#: the shared :class:`repro.core.registry.Registry` instance — the same
#: generic helper backs schedules, controllers, sim topologies, and the
#: serve scheduler policies, so the override/unregister alias sweep is
#: implemented exactly once.
_REGISTRY = Registry("codec", key_fn=codec_name, prepare=_prepare_codec,
                     register_hint="@register_codec({key!r})")


def register_codec(name: Any, *aliases: Any, override: bool = False):
    """Class/instance decorator registering a codec under ``name``.

    Accepts a codec class (instantiated with no arguments) or a ready
    instance (for parameterized codecs).  ``aliases`` register the same
    codec under extra names; re-registering raises unless
    ``override=True``, which replaces the named keys *and* removes any
    other aliases still bound to the replaced instances (a plan naming
    a stale alias must never silently resolve the old codec).
    """
    return _REGISTRY.register(name, *aliases, override=override)


def unregister_codec(name: Any) -> None:
    """Remove a codec and every alias bound to the same instance
    (primarily for tests tearing down toy codecs)."""
    _REGISTRY.unregister(name)


def get_codec(name: Any) -> Codec:
    """Resolve a codec name (str or AggregationMode enum) to its codec."""
    return _REGISTRY.get(name)


def available_codecs() -> tuple[str, ...]:
    return _REGISTRY.available()


# ---------------------------------------------------------------------------
# built-in codecs (the paper's Table 2 representations)
# ---------------------------------------------------------------------------

@register_codec(AggregationMode.FP32)
class Fp32Codec(GradientCodec):
    """Full-precision mean — warm-up / calibration / recovery bypass."""
    name = "fp32"
    bits_per_element = 32.0
    kv_cache = True           # serving: full-precision KV blocks


@register_codec(AggregationMode.IDENTITY)
class IdentityCodec(GradientCodec):
    """Original bytes (functional read-back checks only); FP32 accounting."""
    name = "identity"
    bits_per_element = 32.0
    kv_cache = True           # serving: passthrough KV blocks


@register_codec(AggregationMode.G_BINARY)
class GBinaryCodec(GradientCodec):
    """Majority sign aggregate, u = sgn(2c - W); 1 wire bit/element."""
    name = "gbinary"
    bits_per_element = 1.0
    reduction = "vote"
    threads_ef = True
    lane = CodecLane("sign_count", fused=True)
    default_schedule = "vote_psum"

    def pallas_kernels(self):
        from ..kernels.fused import vote_kernel_set
        return vote_kernel_set()


@register_codec(AggregationMode.G_TERNARY)
class GTernaryCodec(GradientCodec):
    """Gated ternary aggregate, u = m * sgn(2c - W), 2-of-3 zero gate.

    Counted at log2(3) bits/element, which reproduces the paper's
    0.0494 full-path traffic ratio (Table 6).
    """
    name = "gternary"
    bits_per_element = math.log2(3.0)
    reduction = "vote"
    gated = True
    threads_ef = True
    lane = CodecLane("ternary_gated", stall_cycles_per_flit=1.0, fused=True)
    default_schedule = "vote_psum"
    # bucket_gate: the base-class default already yields the per-leaf
    # 2-of-3 BucketGate segments (leaf_gate_mask is None everywhere)

    def pallas_kernels(self):
        # the vote chain is gate-parametric: gbinary and gternary share
        # one kernel set and differ only in the packed gate operand
        from ..kernels.fused import vote_kernel_set
        return vote_kernel_set()

"""Built-in schedule backends: the paper's collectives behind the registry.

Each backend wraps one of the core collectives (:mod:`repro.core.lowbit`)
in the uniform ``aggregate(ctx, g, policy, ef)`` signature.  The Section-9
baselines (MajoritySignSGD, SignOfMean) are registered too, so experiment
plans can select them by name exactly like the production schedules.

Backends are *codec-parametric*: the transport never branches on a mode
enum — it resolves the policy's codec (:mod:`repro.fabric.codecs`) and
asks it for encode/decode (mean transports), the zero gate (vote
transports), and the payload bytes (wire accounting).  A registered
codec therefore rides every compatible transport without any edit here.

All built-ins are **fusable**: they additionally implement
``aggregate_flat(ctx, flat, codec, gate=...)`` over a 1-D bucket
payload, which is what the bucketed aggregation path
(:func:`repro.fabric.session.aggregate_tree_bucketed`) calls — one
collective launch per 32 MiB bucket instead of one per gradient leaf.
``threads_ef`` marks the transports able to carry error feedback (the
codec's own ``threads_ef`` flag must agree); the bucket layer
injects/updates EF residuals per leaf around the fused collective so EF
semantics stay bit-identical to the per-leaf path.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..core.lowbit import (LeafPolicy, _ef_inject, _ef_update,
                           fp32_allreduce, lowbit_packed_a2a,
                           lowbit_vote_psum, sign_of_mean)
from ..core.modes import Schedule, wire_schedule
from ..kernels.ops import fully_manual
from .codecs import get_codec, resolve_leaf_gate_mask, ring_wire_bytes
from .registry import AggregationContext, get_schedule, register_schedule


def _codec_kernels(codec):
    """The codec's fused kernel set, None when it brings none."""
    hook = getattr(codec, "pallas_kernels", None)
    return None if hook is None else hook()


def _mean_kernel_path(ctx: AggregationContext, ks, flat):
    """A mean codec's kernels around the psum, on a flat payload.  The
    kernels run under :func:`fully_manual` (Mosaic cannot be partitioned
    over the step's auto axes)."""
    def run(x):
        enc = ks.encode_flat(x, interpret=ctx.interpret)
        return ks.decode_apply(fp32_allreduce(enc, ctx.dp_axes),
                               interpret=ctx.interpret)
    return fully_manual(run)(flat)


@register_schedule(Schedule.PSUM, "fp32")
class Fp32AllreduceBackend:
    """Mean transport via XLA psum — the paper's bypass / calibration path.

    Mean-reduction codecs plug in around the collective: the per-worker
    payload is ``codec.encode(g)``, the psum averages it, and
    ``codec.decode`` runs on the mean (both identity for the FP32 and
    IDENTITY codecs, hence bit-identical to the pre-codec path).
    """

    name = "psum"
    fusable = True
    threads_ef = False

    def aggregate(self, ctx: AggregationContext, g, policy, ef=None):
        codec = get_codec(policy.mode)
        ks = _codec_kernels(codec)
        if ks is not None and ks.means:
            # fused encode kernel on the flat payload (bit-identical to
            # codec.encode — the KernelSet contract), decode on the mean
            u = _mean_kernel_path(ctx, ks, g.reshape(-1))
            return codec.decode(ctx, u.reshape(g.shape)), ef
        u = codec.decode(ctx, fp32_allreduce(codec.encode(ctx, g),
                                             ctx.dp_axes))
        return u, ef

    def aggregate_flat(self, ctx: AggregationContext, flat, codec, *,
                       gate=None):
        ks = _codec_kernels(codec)
        if ks is not None and ks.means:
            return codec.decode(ctx, _mean_kernel_path(ctx, ks, flat))
        return codec.decode(ctx, fp32_allreduce(codec.encode(ctx, flat),
                                                ctx.dp_axes))

    def wire_bytes_per_device(self, n_elements: int, mode, num_workers: int,
                              dtype_bytes: int = 4) -> float:
        # ring reduce-scatter + all-gather of the codec's wire payload
        return ring_wire_bytes(get_codec(mode).payload_bytes(n_elements),
                               num_workers)


@register_schedule(Schedule.VOTE_PSUM, "majority_sign_sgd")
class VotePsumBackend:
    """Dense int8 sign votes + one psum (works on any sharding).

    Registered under ``majority_sign_sgd`` too: the software baseline is
    update-rule-identical to G-Binary on this schedule (paper Section 9).
    The codec contributes the majority-stage gate: ``codec.gated``
    selects the ternary leg, and ``codec.leaf_gate_mask`` may supply an
    explicit keep pattern overriding the built-in 2-of-3 one.

    This transport deliberately ignores codec kernel sets: its dense
    int8 votes have no packed word-plane representation to fuse — the
    psum *is* the combine, and XLA already fuses the elementwise
    vote/majority stages around it.
    """

    name = "vote_psum"
    fusable = True
    threads_ef = True

    def aggregate(self, ctx: AggregationContext, g, policy, ef=None):
        codec = get_codec(policy.mode)
        mask = resolve_leaf_gate_mask(codec, g.shape, policy.gate_phase)
        gate = None if mask is None else \
            jnp.asarray(mask, g.dtype).reshape(g.shape)
        return lowbit_vote_psum(
            g, ctx.dp_axes, ctx.num_workers, ternary=codec.gated,
            gate_phase=policy.gate_phase, gate=gate, ef=ef)

    def aggregate_flat(self, ctx: AggregationContext, flat, codec, *,
                       gate=None):
        # gate.vector builds the concatenated per-leaf pattern on device
        # (iota + mod), avoiding a bucket-sized host constant per step
        gv = None if gate is None else gate.vector(jnp.float32)
        u, _ = lowbit_vote_psum(flat, ctx.dp_axes, ctx.num_workers,
                                ternary=codec.gated, gate=gv)
        return u

    def wire_bytes_per_device(self, n_elements: int, mode, num_workers: int,
                              dtype_bytes: int = 4) -> float:
        """Models the paper's logical 1-byte vote payload.

        The XLA realization widens the psum operand to int32 to keep the
        vote margin exact for W >= 128 (see ``lowbit_vote_psum``), so
        bytes actually crossing ICI under this software schedule are
        4x this figure; a controller-side popcount (or a staged int8
        reduce) moves the modeled amount.
        """
        return ring_wire_bytes(1.0 * n_elements, num_workers)


@register_schedule(Schedule.PACKED_A2A)
class PackedA2ABackend:
    """The controller schedule: pack -> all_to_all -> PopCount -> gather.

    Every vote codec runs the same fused kernel chain
    (:func:`repro.kernels.fused.fused_packed_vote`): codecs differ only
    in their gate words, which are a kernel operand.
    """

    name = "packed_a2a"
    fusable = True
    threads_ef = True

    def aggregate(self, ctx: AggregationContext, g, policy, ef=None):
        codec = get_codec(policy.mode)
        # a custom leaf gate packs into gate words exactly like the bucket
        # path, so both vote transports zero the same elements (the
        # packed path needs a fully local payload for gate masks)
        return lowbit_packed_a2a(
            g, ctx.dp_axes, ctx.num_workers,
            model_spec=getattr(policy, "model_spec", None),
            ternary=codec.gated, gate_phase=policy.gate_phase,
            gate_mask=resolve_leaf_gate_mask(codec, g.shape,
                                             policy.gate_phase),
            ef=ef, interpret=ctx.interpret)

    def aggregate_flat(self, ctx: AggregationContext, flat, codec, *,
                       gate=None):
        # the packed schedule needs the host mask to pack gate words
        # (1 bit/element once packed — see gate_words_from_mask)
        mask = None if gate is None else gate.mask()
        u, _ = lowbit_packed_a2a(flat, ctx.dp_axes, ctx.num_workers,
                                 ternary=codec.gated, gate_mask=mask,
                                 interpret=ctx.interpret)
        return u

    def wire_bytes_per_device(self, n_elements: int, mode, num_workers: int,
                              dtype_bytes: int = 4) -> float:
        # all_to_all of packed signs + all-gather of sign+mask words
        return (ring_wire_bytes(n_elements / 8.0, num_workers, trips=1.0)
                + ring_wire_bytes(n_elements / 4.0, num_workers, trips=1.0))


@register_schedule("sign_of_mean")
class SignOfMeanBackend:
    """Sign *after* the FP32 mean — optimizer reference, FP32 wire cost."""

    name = "sign_of_mean"
    fusable = True
    threads_ef = False

    def aggregate(self, ctx: AggregationContext, g, policy, ef=None):
        return sign_of_mean(g, ctx.dp_axes), ef

    def aggregate_flat(self, ctx: AggregationContext, flat, codec, *,
                       gate=None):
        return sign_of_mean(flat, ctx.dp_axes)

    def wire_bytes_per_device(self, n_elements: int, mode, num_workers: int,
                              dtype_bytes: int = 4) -> float:
        # the full-precision reduction has already happened: FP32 wire
        # cost regardless of the nominal codec (paper Section 9) —
        # priced like the psum transport's fp32 payload, ignoring the
        # legacy dtype_bytes knob for the same reason it does
        return ring_wire_bytes(get_codec("fp32").payload_bytes(n_elements),
                               num_workers)


def _resolve_hop(hop):
    """(backend, codec, wire-schedule name) for one HopSpec leg."""
    hcodec = get_codec(hop.codec)
    sched = wire_schedule(hop.codec,
                          hop.schedule or hcodec.default_schedule)
    return get_schedule(sched), hcodec, sched


@register_schedule("hierarchical")
class HierarchicalBackend:
    """Per-hop-recompressing route: compose the hops' own transports.

    The policy's codec must be a :class:`~repro.fabric.hierarchy.
    HierarchicalCodec` (it carries the :class:`HopPlan`); each hop leg
    dispatches to the hop codec's registered transport over that hop's
    worker group only, so the gradient is re-encoded at every hop —
    intra-node FP32 mean first, then the compressed inter-node vote on
    the already-averaged values (DynamiQ's per-hop recompression shape).

    Hop 0 runs over the *innermost* worker group.  With one
    data-parallel axis, only 1-hop plans are runnable and the backend is
    bit-identical to the flat backend of the plan's single codec; with
    one axis per hop (``dp_axes=("outer", "inner")``), hop ``i`` reduces
    over axis ``-1 - i``.

    EF is threaded *around* the whole route (inject before hop 0, update
    the residual from the injected gradient after the last hop) — the
    exact external pattern the bucket layer uses, so per-leaf, fused,
    and flat-backend EF all stay bit-identical.

    Fused kernels resolve *per hop*: each leg dispatches through its hop
    codec's own transport, so e.g. a packed gbinary backbone hop runs the
    fused vote chain while the intra-node fp32 hop stays on plain psum —
    no extra wiring here.
    """

    name = "hierarchical"
    fusable = True
    threads_ef = True

    @staticmethod
    def _plan_of(codec):
        plan = getattr(codec, "plan", None)
        if plan is None:
            raise TypeError(
                f"codec {codec.name!r} rides the hierarchical schedule but "
                f"carries no HopPlan; register it via "
                f"repro.fabric.register_hop_plan")
        return plan

    @staticmethod
    def _hop_contexts(ctx: AggregationContext, plan):
        sizes = plan.group_sizes(ctx.num_workers)
        h = len(plan.hops)
        if not ctx.dp_axes:
            axes = [()] * h
        elif h == 1:
            axes = [tuple(ctx.dp_axes)]
        elif h == len(ctx.dp_axes):
            # hop 0 = innermost (last) mesh axis, hop i = axis -1 - i
            axes = [(ctx.dp_axes[-1 - i],) for i in range(h)]
        else:
            raise ValueError(
                f"hop plan {plan.name!r} has {h} hops but the session has "
                f"{len(ctx.dp_axes)} data-parallel axes "
                f"({ctx.dp_axes!r}); map one axis per hop (innermost "
                f"axis = hop 0) or use a 1-hop plan")
        return [dataclasses.replace(ctx, dp_axes=a, num_workers=s)
                for a, s in zip(axes, sizes)]

    def aggregate(self, ctx: AggregationContext, g, policy, ef=None):
        codec = get_codec(policy.mode)
        plan = self._plan_of(codec)
        use_ef = (ef is not None and policy.error_feedback
                  and codec.threads_ef)
        g_eff, _ = _ef_inject(g, ef if use_ef else None)
        u = g_eff
        for hop, hop_ctx in zip(plan.hops, self._hop_contexts(ctx, plan)):
            backend, _, sched = _resolve_hop(hop)
            hop_policy = LeafPolicy(
                mode=hop.codec, schedule=sched,
                model_spec=getattr(policy, "model_spec", None),
                gate_phase=policy.gate_phase, error_feedback=False)
            u, _ = backend.aggregate(hop_ctx, u, hop_policy, None)
        new_ef = _ef_update(g_eff, ef) if use_ef else ef
        return u, new_ef

    def aggregate_flat(self, ctx: AggregationContext, flat, codec, *,
                       gate=None):
        plan = self._plan_of(codec)
        for hop, hop_ctx in zip(plan.hops, self._hop_contexts(ctx, plan)):
            backend, hcodec, _ = _resolve_hop(hop)
            # the zero gate belongs to the gated hop's majority stage;
            # ungated hops (e.g. the intra-node fp32 mean) never see it
            flat = backend.aggregate_flat(
                hop_ctx, flat, hcodec,
                gate=gate if hcodec.gated else None)
        return flat

    def hop_wire_bytes_per_device(self, n_elements: int, mode,
                                  num_workers: int,
                                  dtype_bytes: int = 4) -> tuple:
        """Per-leg wire bytes: each hop's own model at its group size."""
        codec = get_codec(mode)
        plan = self._plan_of(codec)
        legs = []
        for hop, size in zip(plan.hops, plan.group_sizes(num_workers)):
            backend, _, _ = _resolve_hop(hop)
            legs.append(backend.wire_bytes_per_device(
                n_elements, hop.codec, size, dtype_bytes=dtype_bytes))
        return tuple(legs)

    def wire_bytes_per_device(self, n_elements: int, mode, num_workers: int,
                              dtype_bytes: int = 4) -> float:
        return float(sum(self.hop_wire_bytes_per_device(
            n_elements, mode, num_workers, dtype_bytes=dtype_bytes)))

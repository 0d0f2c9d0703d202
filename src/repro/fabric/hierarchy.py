"""Hop plans: hierarchical, per-hop-recompressing collective routes.

Everything below the controller used to assume a single flat hop: one
codec, one transport, one worker group.  A :class:`HopPlan` makes the
*route* first-class — an ordered tuple of :class:`HopSpec` legs, each
naming a codec from the registry, a worker-group size, and (optionally)
a transport — so the paper's traffic win survives an oversubscribed
inter-node fabric the way DynamiQ (PAPERS.md) does: re-compress at
every hop instead of end-to-end.  The canonical shape is intra-node
FP32 ``psum`` followed by an inter-node low-bit vote::

    plan  = HopPlan("hier_fp32_gbinary",
                    (HopSpec("fp32", workers=8),    # hop 0: intra-node
                     HopSpec("gbinary")))           # hop 1: the rest
    codec = register_hop_plan(plan)

Registration puts a :class:`HierarchicalCodec` carrying the plan into
the **codec registry** under the plan's name, so a hierarchical route
is addressed exactly like any other representation — ``GroupPolicy(
mode="hier_fp32_gbinary")``, ``AdmissionPlan.lowbit_backbone(
"hier_fp32_gbinary")``, a :func:`~repro.fabric.control.plan_presets`
entry, or a ``Commander(binary_mode="hier_fp32_gbinary")`` admission
ladder — with zero changes to the policy schema.  The codec's
``default_schedule`` is the ``hierarchical`` backend
(:mod:`repro.fabric.backends`), which composes the per-hop
encode -> reduce -> decode chain by dispatching each leg to that hop
codec's own registered transport.

Worker groups and axes
----------------------
``HopSpec.workers`` is the hop's group size: a fixed count (clamped to
the session's worker total when smaller, so an 8-wide intra-node hop
degrades gracefully on a 4-worker test mesh) or ``None`` for "the
remaining workers" (at most one hop per plan).  Hop 0 is the
*innermost* group: on a session with one data-parallel axis per hop
(``Fabric(dp_axes=("outer", "inner"))`` for a 2-hop plan), hop 0 runs
over the last axis and hop ``h`` over the axis ``h`` from the end; a
1-hop plan runs over all axes at once and is bit-identical to the flat
backend of its single codec.

Accounting
----------
``bits_per_element`` (hence the paper-style payload ratio) counts the
*backbone* — the last hop's representation, the bits that cross the
scarce inter-node links; per-leg wire bytes come from
:func:`repro.core.traffic.hop_wire_bytes_per_device`, which sums each
hop backend's own ring model at that hop's group size.
"""
from __future__ import annotations

import dataclasses

from .codecs import GradientCodec, get_codec, register_codec, \
    unregister_codec

__all__ = [
    "HierarchicalCodec", "HopPlan", "HopSpec", "INTRA_NODE_WORKERS",
    "register_hop_plan", "unregister_hop_plan",
]

#: default intra-node group size for the built-in plans (one v5e-like
#: host's worth of chips); clamped to the session's worker count.
INTRA_NODE_WORKERS = 8


@dataclasses.dataclass(frozen=True)
class HopSpec:
    """One leg of a hierarchical route.

    ``codec`` names the hop's gradient representation (codec-registry
    key); ``workers`` is the hop's group size — a fixed count or None
    for "the remaining workers"; ``schedule`` optionally pins the hop's
    transport (default: the hop codec's ``default_schedule``, with the
    usual :func:`~repro.core.modes.wire_schedule` normalization).
    """
    codec: str
    workers: int | None = None
    schedule: str | None = None

    def __post_init__(self):
        if self.workers is not None and int(self.workers) < 1:
            raise ValueError(
                f"hop group size must be >= 1, got {self.workers}")


@dataclasses.dataclass(frozen=True)
class HopPlan:
    """An ordered route of hops; hop 0 is the innermost worker group."""
    name: str
    hops: tuple

    def __post_init__(self):
        object.__setattr__(self, "hops", tuple(self.hops))
        if not self.hops:
            raise ValueError(f"hop plan {self.name!r} needs at least one hop")
        if sum(1 for h in self.hops if h.workers is None) > 1:
            raise ValueError(
                f"hop plan {self.name!r} has more than one remainder hop "
                f"(workers=None); at most one hop may absorb the leftover "
                f"workers")

    def signature(self) -> str:
        """Stable route identity (folded into the bucket fusion key)."""
        legs = ">".join(
            f"{h.codec}:{'*' if h.workers is None else int(h.workers)}"
            + (f"@{h.schedule}" if h.schedule else "")
            for h in self.hops)
        return f"{self.name}[{legs}]"

    def group_sizes(self, num_workers: int) -> tuple:
        """Per-hop worker-group sizes for a ``num_workers`` session.

        Fixed hops are clamped to the workers still unassigned (so the
        built-in 8-wide intra-node hop runs as 4-wide on a 4-worker test
        mesh) and must divide them; the remainder hop absorbs whatever
        is left.  The product of the returned sizes always equals
        ``max(1, num_workers)``.
        """
        remaining = max(1, int(num_workers))
        sizes: list = [None] * len(self.hops)
        rem_idx = None
        for i, hop in enumerate(self.hops):
            if hop.workers is None:
                rem_idx = i
                continue
            s = min(int(hop.workers), remaining)
            if remaining % s:
                raise ValueError(
                    f"hop plan {self.name!r}: hop {i} group size {s} does "
                    f"not divide the {remaining} unassigned workers "
                    f"(session has {num_workers})")
            sizes[i] = s
            remaining //= s
        if rem_idx is not None:
            sizes[rem_idx] = remaining
            remaining = 1
        if remaining != 1:
            raise ValueError(
                f"hop plan {self.name!r} covers only "
                f"{max(1, int(num_workers)) // remaining} of {num_workers} "
                f"workers; add a remainder hop (workers=None) or size the "
                f"fixed hops to the session")
        return tuple(sizes)


class HierarchicalCodec(GradientCodec):
    """A registered codec carrying a :class:`HopPlan`.

    ``reduction = "hierarchical"`` routes every built-in flat schedule
    to the ``hierarchical`` backend (see
    :func:`~repro.core.modes.wire_schedule`); the remaining contract
    attributes delegate to the hop codecs — ``bits_per_element`` and the
    sim ``lane`` to the *backbone* (last) hop, ``gated``/``threads_ef``
    to any hop declaring them, the bucket zero gate to the first gated
    hop.  ``hop_signature`` is folded into
    :class:`~repro.core.buckets.BucketKey` so buckets never mix routes.
    """

    reduction = "hierarchical"
    default_schedule = "hierarchical"
    kv_cache = False

    def __init__(self, plan: HopPlan):
        self.plan = plan
        self.name = plan.name
        self.hop_signature = plan.signature()
        hop_codecs = [get_codec(h.codec) for h in plan.hops]
        for c in hop_codecs:
            if getattr(c, "reduction", "") == "hierarchical":
                raise ValueError(
                    f"hop plan {plan.name!r}: hop codec {c.name!r} is "
                    f"itself hierarchical — hop plans do not nest")
        backbone = hop_codecs[-1]
        self.bits_per_element = backbone.bits_per_element
        self.lane = backbone.lane
        self.gated = any(c.gated for c in hop_codecs)
        self.threads_ef = any(c.threads_ef for c in hop_codecs)

    def bucket_gate(self, bucket):
        """Delegate the fused zero gate to the first gated hop codec."""
        for hop in self.plan.hops:
            c = get_codec(hop.codec)
            if c.gated:
                return c.bucket_gate(bucket)
        return None

    # -- fused kernels: resolved per hop, not for the route -------------
    def pallas_kernels(self):
        """None: a multi-hop route has no single kernel set — each hop
        leg resolves its own codec's kernels inside the hierarchical
        backend."""
        return None

    def kernel_signature(self) -> str | None:
        """Composed per-hop kernel signatures for the step-cache key.

        ``None`` when no hop brings kernels; otherwise one string over
        the route so swapping any hop codec's kernel set invalidates
        compiled steps exactly like a flat codec swap would.
        """
        sigs = []
        for hop in self.plan.hops:
            c = get_codec(hop.codec)
            hook = getattr(c, "kernel_signature", None)
            sigs.append(hook() if hook is not None else None)
        if not any(s is not None for s in sigs):
            return None
        return ">".join("-" if s is None else s for s in sigs)


def register_hop_plan(plan: HopPlan, *aliases: str,
                      override: bool = False) -> HierarchicalCodec:
    """Build a :class:`HierarchicalCodec` for ``plan`` and register it
    in the codec registry under ``plan.name`` (+ ``aliases``).

    The returned codec is what plans, presets, buckets, the traffic
    model, and the simulator resolve by name; tear toys down with
    :func:`unregister_hop_plan`.
    """
    codec = HierarchicalCodec(plan)
    register_codec(plan.name, *aliases, override=override)(codec)
    return codec


def unregister_hop_plan(name: str) -> None:
    """Remove a registered hop-plan codec and its aliases."""
    unregister_codec(name)


# ---------------------------------------------------------------------------
# built-in hop plans (intra-node FP32 psum -> inter-node low-bit)
# ---------------------------------------------------------------------------

register_hop_plan(HopPlan("hier_fp32_gbinary", (
    HopSpec("fp32", workers=INTRA_NODE_WORKERS),
    HopSpec("gbinary"))))

register_hop_plan(HopPlan("hier_fp32_gternary", (
    HopSpec("fp32", workers=INTRA_NODE_WORKERS),
    HopSpec("gternary"))))

register_hop_plan(HopPlan("hier_fp32_int4", (
    HopSpec("fp32", workers=INTRA_NODE_WORKERS),
    HopSpec("int4"))))

"""Schedule-backend registry: the Fabric extension seam.

A *schedule backend* owns the wire-level algorithm that realizes an
aggregation mode on the mesh (how bytes move: psum, packed all_to_all,
a future DynamiQ-style multi-hop compressed all-reduce, a CXL-CCL-style
pooled-memory collective, ...).  Backends register under a string name
and are resolved by :func:`get_schedule`; core dispatch never hardcodes
a schedule, so new collectives plug in without editing core files:

    from repro.fabric import register_schedule

    @register_schedule("my_sched")
    class MySched:
        name = "my_sched"
        def aggregate(self, ctx, g, policy, ef=None):
            return my_collective(g, ctx.dp_axes), ef

    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                         schedule="my_sched")

Every backend sees one uniform signature: ``aggregate(ctx, g, policy,
ef)`` where ``ctx`` (:class:`AggregationContext`) carries the session
facts (dp_axes / num_workers / interpret) that the old free functions
each re-threaded by hand.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, Sequence, runtime_checkable

from ..core.modes import schedule_name
from ..core.registry import Registry

Axes = Sequence[str] | str


@dataclasses.dataclass(frozen=True)
class AggregationContext:
    """Session facts a backend needs to run its collective.

    ``dp_axes``     — manual mesh axes the aggregation reduces over;
    ``num_workers`` — product of the dp-axis sizes (the paper's W);
    ``interpret``   — Pallas interpret-mode override for kernel backends;
    ``mesh``        — the owning mesh, when a backend needs topology
                      (None for host-local / virtual-worker use).
    """
    dp_axes: Any = ()
    num_workers: int = 1
    interpret: bool | None = None
    mesh: Any = None


@runtime_checkable
class ScheduleBackend(Protocol):
    """Protocol every registered schedule backend implements.

    ``aggregate`` runs *inside* a shard_map whose manual axes are
    ``ctx.dp_axes`` and returns ``(aggregate, new_ef)``; backends that do
    not thread error feedback return ``ef`` unchanged.  Backends may
    additionally expose ``wire_bytes_per_device(n_elements, mode,
    num_workers, dtype_bytes)`` to participate in the traffic model.

    **Codecs.**  Backends are transport-only: the payload contract
    (encode/decode, reduction kind, gate, bits/element) lives on the
    policy's *codec* (:mod:`repro.fabric.codecs`).  Resolve it with
    ``get_codec(policy.mode)`` and consult its hooks instead of
    branching on a mode enum.

    **Bucket fusion (opt-in).**  A backend that sets ``fusable = True``
    must also implement

        aggregate_flat(ctx, flat, codec, *, gate=None)

    over a 1-D bucket payload (the concatenation of compatible leaves)
    and return the 1-D aggregate.  ``codec`` is the bucket's resolved
    :class:`~repro.fabric.codecs.Codec`; ``gate`` is the codec's bucket
    zero gate (e.g. :class:`~repro.core.buckets.BucketGate` carrying
    the concatenated per-leaf ternary gates; None for ungated codecs) —
    call ``gate.vector(dtype)`` for an on-device keep vector or
    ``gate.mask()`` for the host boolean array (packed-word schedules).
    ``threads_ef = True`` declares that the per-leaf ``aggregate``
    consumes error feedback; the bucket layer then injects/updates EF
    residuals per leaf around the fused collective — but only for
    codecs whose own ``threads_ef`` flag agrees (the backend's
    ``aggregate_flat`` never sees EF).  Backends without ``fusable``
    simply stay on the per-leaf path.
    """

    name: str

    def aggregate(self, ctx: AggregationContext, g: Any, policy: Any,
                  ef: Any | None = None) -> tuple[Any, Any | None]: ...


def _prepare_schedule(obj: Any, keys) -> ScheduleBackend:
    return obj() if isinstance(obj, type) else obj


#: backed by the shared generic :class:`repro.core.registry.Registry`
#: (one implementation of keys / duplicate check / override alias sweep
#: for every extension seam).
_REGISTRY = Registry("schedule backend", key_fn=schedule_name,
                     prepare=_prepare_schedule,
                     register_hint="@register_schedule({key!r})")


def register_schedule(name: Any, *aliases: Any, override: bool = False):
    """Class/instance decorator registering a backend under ``name``.

    Accepts a backend class (instantiated with no arguments) or a ready
    instance.  ``aliases`` register the same backend under extra names;
    re-registering an existing name raises unless ``override=True``,
    which replaces the named keys *and* removes any other aliases still
    bound to the replaced instances (a plan naming a stale alias must
    never silently resolve the old backend).
    """
    return _REGISTRY.register(name, *aliases, override=override)


def unregister_schedule(name: Any) -> None:
    """Remove a backend and every alias bound to the same instance
    (primarily for tests tearing down toy schedules)."""
    _REGISTRY.unregister(name)


def get_schedule(name: Any) -> ScheduleBackend:
    """Resolve a schedule name (str or Schedule enum) to its backend."""
    return _REGISTRY.get(name)


def available_schedules() -> tuple[str, ...]:
    return _REGISTRY.available()

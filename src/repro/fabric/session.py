"""The Fabric session: one control surface over the aggregation fabric.

A :class:`Fabric` is constructed once from ``(mesh, dp_axes, rules,
interpret)`` and owns everything the old free-function API made every
caller re-thread by hand: the worker count, group assignment, policy
resolution, error-feedback state init/specs, schedule dispatch (via the
backend registry, through 32 MiB *buckets*: one collective per bucket
instead of one per leaf; see :func:`aggregate_tree_bucketed`), and the
per-plan-signature jit cache
for compiled train steps.  It is the seam later scaling work (new
collectives, async overlap, multi-backend) plugs into — swap or add a
registered :class:`~repro.fabric.registry.ScheduleBackend` and every
layer above (Trainer, dry-run, benchmarks) picks it up.

Layering: ``fabric`` sits above ``core`` (math + policy vocabulary) and
below ``runtime`` (Trainer control loop); model/optimizer specifics are
imported lazily inside :meth:`Fabric.build_step` so the session stays
usable for host-local aggregation without the full model stack.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.aggregate import init_ef_states
from ..core.buckets import (AdmissionPlan, BucketLayout,
                            DEFAULT_BUCKET_BYTES, GroupRules, assign_groups,
                            group_sizes, plan_buckets, resolve_policies)
from ..core.lowbit import _ef_update
from ..core.modes import codec_name, wire_schedule
from .codecs import get_codec
from .registry import AggregationContext, get_schedule

Axes = Sequence[str] | str

_is_policy = lambda x: hasattr(x, "mode") and hasattr(x, "schedule")


# ---------------------------------------------------------------------------
# leaf- and tree-level aggregation (registry-dispatched)
# ---------------------------------------------------------------------------

def aggregate_leaf(ctx: AggregationContext, g: jax.Array, policy,
                   ef: jax.Array | None = None):
    """Aggregate one gradient leaf under its admitted policy.

    Pure registry dispatch: the wire schedule (FP32/IDENTITY always ride
    psum) names the backend; the backend interprets the rest of the
    policy.  Returns ``(aggregate, new_ef)``.
    """
    backend = get_schedule(wire_schedule(policy.mode, policy.schedule))
    return backend.aggregate(ctx, g, policy, ef)


def _leaf_uses_ef(pol, e) -> bool:
    """Does this leaf thread error feedback through its collective?

    Requires the policy flag, a real residual leaf (not the scalar
    sentinel), *and* a codec that consumes EF — the same gate the fused
    path applies, so fused and per-leaf EF semantics agree for custom
    codecs too (a ``threads_ef=False`` codec never injects/updates).
    """
    return (pol.error_feedback and e is not None and e.ndim > 0
            and get_codec(pol.mode).threads_ef)


def aggregate_tree(ctx: AggregationContext, grads: Any, policies: Any,
                   ef_states: Any | None = None):
    """Aggregate a gradient pytree leaf-by-leaf under resolved policies.

    The per-leaf reference that :func:`aggregate_tree_bucketed` must
    match bit for bit; no production path calls it (the session always
    aggregates through its bucket layout, whose per-leaf fallback is
    :func:`aggregate_leaf`).  Runs inside a shard_map whose manual axes
    are ``ctx.dp_axes``.
    Error-feedback leaves hold a ``(1, *shape)`` local residual (globally
    ``(W, *shape)`` sharded over the DP axes); disabled leaves hold a
    scalar sentinel so the tree structure stays static across plans.
    Returns ``(aggregates, new_ef_states)`` mirroring the sentinel
    structure.
    """
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = treedef.flatten_up_to(policies)
    if ef_states is None:
        e_leaves = [None] * len(g_leaves)
    else:
        e_leaves = treedef.flatten_up_to(ef_states)

    agg, new_ef = [], []
    for g, pol, e in zip(g_leaves, p_leaves, e_leaves):
        use_ef = _leaf_uses_ef(pol, e)
        ef_in = e[0] if use_ef else None
        u, ef_out = aggregate_leaf(ctx, g, pol, ef=ef_in)
        agg.append(u)
        if e is None:
            new_ef.append(None)
        elif use_ef:
            new_ef.append(ef_out[None])
        else:
            new_ef.append(e)
    aggregates = jax.tree_util.tree_unflatten(treedef, agg)
    if ef_states is None:
        return aggregates, None
    return aggregates, jax.tree_util.tree_unflatten(treedef, new_ef)


# ---------------------------------------------------------------------------
# bucketed (fused) tree aggregation
# ---------------------------------------------------------------------------

def _registry_fusable(schedule: str) -> bool:
    """Layout-planner predicate: does this wire schedule's backend fuse?"""
    try:
        return bool(getattr(get_schedule(schedule), "fusable", False))
    except KeyError:
        return False        # unknown name: per-leaf path raises the
                            # canonical registry error at dispatch time


def _codec_kernel_sig(mode) -> str | None:
    """A mode's fused-kernel signature, None when it brings no kernels
    (or is not registered — the dispatch layer raises the real error)."""
    try:
        codec = get_codec(mode)
    except KeyError:
        return None
    hook = getattr(codec, "kernel_signature", None)
    return hook() if hook is not None else None


def plan_modes(plan: AdmissionPlan) -> set:
    """Every codec mode an admission plan can route a leaf to."""
    return {pol.mode for _, pol in plan.policies} | {plan.default.mode}


def layout_kernel_stats(layout: BucketLayout, num_workers: int) -> dict:
    """Modeled Pallas-launch and HBM-byte accounting for one layout.

    Sums, over every collective launch in ``layout``, the launch count
    and modeled HBM traffic of the launch codec's
    :class:`~repro.kernels.fused.KernelSet` under both datapaths —
    ``fused`` (codec-owned single/merged kernels) and ``unfused`` (the
    staged reference chain).  Hierarchical routes decompose per hop at
    that hop's group size.  Launches whose codec brings no kernel set
    (fp32 psum, custom codecs) count once under ``collectives`` but do
    not contribute kernel stats — the two paths are identical there.

    Returns ``{"launches_fused", "launches_unfused", "hbm_bytes_fused",
    "hbm_bytes_unfused", "collectives", "unkernelized"}``.
    """
    stats = {"launches_fused": 0, "launches_unfused": 0,
             "hbm_bytes_fused": 0.0, "hbm_bytes_unfused": 0.0,
             "collectives": 0, "unkernelized": 0}

    def add(ks, schedule, n, w, ef):
        if ks is None:
            stats["unkernelized"] += 1
            return
        dist = w > 1
        if ks.votes and schedule == "packed_a2a":
            pass
        elif ks.means and schedule == "psum":
            ef = False          # mean sets never thread EF in-kernel
        else:
            stats["unkernelized"] += 1
            return
        for path, fused in (("fused", True), ("unfused", False)):
            stats[f"launches_{path}"] += ks.launches(
                fused=fused, distributed=dist, ef=ef)
            stats[f"hbm_bytes_{path}"] += ks.hbm_bytes(
                n, num_workers=w, fused=fused, distributed=dist, ef=ef)

    for key, n in layout.launches():
        stats["collectives"] += 1
        try:
            codec = get_codec(key.mode)
        except KeyError:
            stats["unkernelized"] += 1
            continue
        if getattr(codec, "reduction", "") == "hierarchical":
            sizes = codec.plan.group_sizes(num_workers)
            for hop, w in zip(codec.plan.hops, sizes):
                c = get_codec(hop.codec)
                hook = getattr(c, "pallas_kernels", None)
                sched = hop.schedule or c.default_schedule
                add(hook() if hook is not None else None,
                    wire_schedule(hop.codec, sched), n, w,
                    key.error_feedback and c.threads_ef)
        else:
            hook = getattr(codec, "pallas_kernels", None)
            add(hook() if hook is not None else None, key.schedule, n,
                num_workers, key.error_feedback and codec.threads_ef)
    return stats


def aggregate_tree_bucketed(ctx: AggregationContext, grads: Any,
                            policies: Any, ef_states: Any | None = None, *,
                            layout: BucketLayout | None = None,
                            bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """Aggregate a gradient pytree through fused flat buckets.

    Semantically identical to :func:`aggregate_tree` (bit-for-bit for
    every built-in schedule, EF states included) but launches **one**
    collective per bucket instead of one per leaf: compatible leaves
    (same :class:`~repro.core.buckets.BucketKey`) are flattened and
    concatenated, the backend's ``aggregate_flat`` runs on the fused
    payload, and results are scattered back to the original leaf shapes.

    Error feedback is handled per leaf *around* the fused collective —
    injection ``g + e`` before concatenation and the EF-signSGD residual
    update (whose ``beta = mean|g_eff|`` is a per-leaf statistic) after
    the scatter — which is exactly what keeps EF semantics identical to
    the per-leaf path.  TP-sharded leaves and non-fusable backends stay
    on the per-leaf path (``layout.unfused``).

    ``layout`` may be precomputed (and cached — it is stable across
    steps); otherwise it is planned here from the grads' shapes.
    """
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = treedef.flatten_up_to(policies)
    if ef_states is None:
        e_leaves = [None] * len(g_leaves)
    else:
        e_leaves = treedef.flatten_up_to(ef_states)
    if layout is None:
        layout = plan_buckets(grads, policies, bucket_bytes=bucket_bytes,
                              fusable=_registry_fusable)
    assert layout.num_leaves == len(g_leaves), (
        f"bucket layout planned for {layout.num_leaves} leaves applied to "
        f"a {len(g_leaves)}-leaf gradient tree")

    agg: list = [None] * len(g_leaves)
    new_ef = list(e_leaves)

    # per-leaf fallback — same dispatch (and same EF gate) as aggregate_tree
    for uf in layout.unfused:
        g, pol, e = g_leaves[uf.leaf], p_leaves[uf.leaf], e_leaves[uf.leaf]
        use_ef = _leaf_uses_ef(pol, e)
        u, ef_out = aggregate_leaf(ctx, g, pol, ef=e[0] if use_ef else None)
        agg[uf.leaf] = u
        if use_ef:
            new_ef[uf.leaf] = ef_out[None]

    for bucket in layout.buckets:
        backend = get_schedule(bucket.key.schedule)
        codec = get_codec(bucket.key.mode)
        # EF rides the fused collective only when both axes can carry it
        threads_ef = getattr(backend, "threads_ef", False) and codec.threads_ef
        flats, g_effs = [], {}
        with jax.named_scope("bucket.pack"):
            for slot in bucket.slots:
                g = g_leaves[slot.leaf].reshape(-1)
                e, pol = e_leaves[slot.leaf], p_leaves[slot.leaf]
                if (threads_ef and pol.error_feedback and e is not None
                        and e.ndim > 0):
                    g = g + e[0].reshape(-1).astype(g.dtype)
                    g_effs[slot.leaf] = g
                flats.append(g)
            flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        u_flat = backend.aggregate_flat(ctx, flat, codec,
                                        gate=bucket.gate())
        with jax.named_scope("bucket.scatter"):
            for slot in bucket.slots:
                u = u_flat[slot.offset:slot.offset + slot.size]
                agg[slot.leaf] = u.reshape(slot.shape)
                if slot.leaf in g_effs:
                    e = e_leaves[slot.leaf]
                    g_eff = g_effs[slot.leaf].reshape(slot.shape)
                    new_ef[slot.leaf] = _ef_update(g_eff, e[0])[None]

    aggregates = jax.tree_util.tree_unflatten(treedef, agg)
    if ef_states is None:
        return aggregates, None
    return aggregates, jax.tree_util.tree_unflatten(treedef, new_ef)


# ---------------------------------------------------------------------------
# train-step state (owned here; re-exported by repro.runtime)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: Any
    ef: Any                    # error-feedback residuals (sentinel tree)
    step: jax.Array


class CompiledStep(NamedTuple):
    """One compiled train step and its I/O contracts: unpacks as
    ``(jitted, state_shardings, batch_sharding, aux)``."""
    step_fn: Callable
    state_shardings: Any
    batch_sharding: Any
    aux: dict

    def __call__(self, state, batch):
        return self.step_fn(state, batch)


def dp_num_workers(mesh, dp_axes: Axes) -> int:
    axes = (dp_axes,) if isinstance(dp_axes, str) else dp_axes
    return int(np.prod([mesh.shape[a] for a in axes]))


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        spec_tree, is_leaf=lambda x: isinstance(x, P) or x is None)


def _optimizer_has_nu(optimizer) -> bool:
    """Does this optimizer's state carry a second moment (nu)?

    Prefers the optimizer's own ``has_nu`` hook (see
    :class:`repro.optim.optimizers.Optimizer`), falling back to probing
    the actual init state for duck-typed optimizers — never the class
    name, which breaks for subclasses and new adaptive optimizers.
    """
    flag = getattr(optimizer, "has_nu", None)
    if flag is not None:
        return bool(flag)
    from ..optim.optimizers import state_has_nu
    return state_has_nu(optimizer)


def _opt_shardings(optimizer, mu_sh, mesh):
    """OptState(step, mu, nu) sharding tree matching optimizer kind."""
    from ..optim.optimizers import OptState
    scalar = NamedSharding(mesh, P())
    return OptState(step=scalar, mu=mu_sh,
                    nu=mu_sh if _optimizer_has_nu(optimizer) else None)


def _split_microbatches(batch: Any, grad_accum: int) -> Any:
    """Reshape each batch leaf to ``(grad_accum, B // grad_accum, ...)``.

    Raises at trace time when the per-device batch is not divisible —
    the old silent ``x.shape[0] // grad_accum`` reshape dropped trailing
    samples.
    """
    def split(x):
        if x.shape[0] % grad_accum:
            raise ValueError(
                f"grad_accum={grad_accum} must divide the per-device batch "
                f"size, but got a batch leaf of shape {tuple(x.shape)} "
                f"({x.shape[0]} % {grad_accum} = {x.shape[0] % grad_accum}); "
                f"trailing samples would be silently dropped")
        return x.reshape((grad_accum, x.shape[0] // grad_accum)
                         + x.shape[1:])
    return jax.tree.map(split, batch)


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class Fabric:
    """Aggregation-fabric session bound to one mesh and DP axis set.

    ``mesh=None`` gives a host-local session (virtual workers /
    single-process experiments); ``num_workers`` then defaults to 1 or
    may be forced (e.g. for abstract spec construction).
    """

    def __init__(self, mesh=None, dp_axes: Axes | None = None, *,
                 rules: GroupRules | None = None,
                 interpret: bool | None = None,
                 num_workers: int | None = None,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES):
        self.mesh = mesh
        if dp_axes is None:
            dp_axes = ("data",) if mesh is not None else ()
        self.dp_axes = ((dp_axes,) if isinstance(dp_axes, str)
                        else tuple(dp_axes))
        self.rules = rules or GroupRules()
        self.interpret = interpret
        if num_workers is not None:
            self.num_workers = int(num_workers)
        elif mesh is not None:
            self.num_workers = dp_num_workers(mesh, self.dp_axes)
        else:
            self.num_workers = 1
        self.bucket_bytes = int(bucket_bytes)
        self.membership_epoch = 0        # bumped by bind_membership
        self.controller = None           # attached admission controller
        self._compiled: dict[tuple, CompiledStep] = {}
        self._layouts: dict[tuple, BucketLayout] = {}

    # -- elastic membership ---------------------------------------------

    def bind_membership(self, view) -> None:
        """Bind this session to an epoch-numbered worker view.

        ``view`` is any object with ``num_workers`` and ``epoch``
        attributes (:class:`repro.elastic.WorkerView`).  Re-binding
        updates ``num_workers`` and stamps the membership epoch into the
        compiled-step cache key, so a jitted step built for an earlier
        view can never be served after a re-plan.  Only mesh-free
        (virtual-worker) sessions may change worker count — a mesh fixes
        the DP extent at construction.
        """
        w, epoch = int(view.num_workers), int(view.epoch)
        if self.mesh is not None and w != dp_num_workers(self.mesh,
                                                         self.dp_axes):
            raise ValueError(
                f"cannot bind a {w}-worker view: mesh fixes the DP extent "
                f"at {dp_num_workers(self.mesh, self.dp_axes)}")
        self.num_workers = w
        self.membership_epoch = epoch

    # -- admission controller -------------------------------------------

    def attach_controller(self, controller, **kwargs):
        """Attach an admission controller to this session.

        ``controller`` is either a :class:`repro.fabric.control.Controller`
        instance or a name registered via ``@register_controller``
        (``kwargs`` then go to the factory, e.g.
        ``fabric.attach_controller("paper", warmup_steps=50)``).  The
        session is the natural owner: the controller's mode latch and the
        per-plan-signature jit cache (the XLA analogue of that latch)
        then live in one object, and a Trainer built on this session
        picks the controller up automatically.  Returns the controller.
        """
        from .control import make_controller
        if isinstance(controller, str):
            controller = make_controller(controller, **kwargs)
        elif kwargs:
            raise TypeError("factory kwargs are only valid when attaching "
                            "a controller by registered name")
        self.controller = controller
        return controller

    # -- context / policy resolution ------------------------------------

    @property
    def context(self) -> AggregationContext:
        return AggregationContext(dp_axes=self.dp_axes,
                                  num_workers=self.num_workers,
                                  interpret=self.interpret, mesh=self.mesh)

    def resolve(self, params_like: Any, plan: AdmissionPlan,
                pspecs: Any | None = None) -> Any:
        """Params (+ optional PartitionSpec tree) -> LeafPolicy pytree."""
        return resolve_policies(params_like, plan, pspecs=pspecs,
                                rules=self.rules)

    def groups(self, params_like: Any) -> Any:
        return assign_groups(params_like, self.rules)

    def group_sizes(self, params_like: Any) -> dict[str, int]:
        return group_sizes(params_like, self.rules)

    # -- error-feedback state -------------------------------------------

    def init_ef(self, params: Any, policies: Any, dtype=jnp.float32) -> Any:
        """Global EF tree: ``(W, *shape)`` zeros where EF is on, scalar 0
        sentinel elsewhere (W = this session's worker count)."""
        local = init_ef_states(params, policies, dtype)
        w = self.num_workers
        return jax.tree.map(
            lambda e: (jnp.broadcast_to(e, (w,) + e.shape[1:])
                       if e.ndim > 0 else e), local)

    def ef_specs(self, policies: Any, pspecs: Any) -> Any:
        """PartitionSpecs for the EF tree (leading dim sharded over DP).

        The single implementation — both the step builder and external
        spec construction (launch/specs) derive EF shardings here.
        """
        pol_leaves, pol_def = jax.tree_util.tree_flatten(
            policies, is_leaf=_is_policy)
        spec_leaves = pol_def.flatten_up_to(pspecs)
        leaves = [
            P(self.dp_axes, *tuple(sp or P())) if pol.error_feedback else P()
            for pol, sp in zip(pol_leaves, spec_leaves)]
        return jax.tree_util.tree_unflatten(pol_def, leaves)

    # -- aggregation ----------------------------------------------------

    def layout_for(self, params_like: Any, plan: AdmissionPlan | Any,
                   pspecs: Any | None = None) -> BucketLayout:
        """Bucket layout for a (tree, plan) pair — cached per signature.

        The layout is a pure function of leaf order/shapes/dtypes, the
        resolved policies, and this session's ``bucket_bytes``, so it is
        stable across steps and shared with the compiled-step cache.
        """
        if isinstance(plan, AdmissionPlan):
            policies = self.resolve(params_like, plan, pspecs=pspecs)
        else:
            policies = plan
        leaves, treedef = jax.tree_util.tree_flatten(params_like)
        pol_leaves = tuple(jax.tree_util.tree_flatten(
            policies, is_leaf=_is_policy)[0])
        # the layout also depends on which backends currently fuse and on
        # the codecs' layout-relevant attributes (reduction drives the
        # wire schedule, gated drives gate-phase normalization), so a
        # backend *or codec* swapped under the same name
        # (register/unregister) must not hit a stale cached layout
        wires = {wire_schedule(p.mode, p.schedule) for p in pol_leaves}
        fus_sig = tuple(sorted((w, _registry_fusable(w)) for w in wires))
        modes = {codec_name(p.mode) for p in pol_leaves}
        codec_sig = tuple(sorted(
            (m, get_codec(m).reduction, bool(get_codec(m).gated),
             getattr(get_codec(m), "hop_signature", None),
             _codec_kernel_sig(m))
            for m in modes))
        key = (treedef,
               tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
               pol_leaves, self.bucket_bytes, fus_sig, codec_sig)
        if key not in self._layouts:
            self._layouts[key] = plan_buckets(
                params_like, policies, bucket_bytes=self.bucket_bytes,
                fusable=_registry_fusable)
        return self._layouts[key]

    def aggregate(self, grads: Any, plan: AdmissionPlan | Any,
                  ef: Any | None = None, *, pspecs: Any | None = None):
        """Aggregate a gradient pytree under a plan (or resolved policies).

        Runs inside a shard_map whose manual axes are this session's
        ``dp_axes`` (the train step's gradient context); with
        ``dp_axes=()`` it is the host-local/virtual-worker path.  ``plan``
        may be an :class:`AdmissionPlan` (resolved against ``grads`` with
        this session's rules) or an already-resolved LeafPolicy pytree.

        Compatible leaves are fused into flat ``bucket_bytes`` buckets
        and aggregated by one collective per bucket — bit-identical to
        the per-leaf reference :func:`aggregate_tree` for every built-in
        schedule.  Returns ``(aggregates, new_ef)``.
        """
        if isinstance(plan, AdmissionPlan):
            policies = self.resolve(grads, plan, pspecs=pspecs)
        else:
            policies = plan
        layout = self.layout_for(grads, policies)
        return aggregate_tree_bucketed(self.context, grads, policies,
                                       ef_states=ef, layout=layout)

    # -- simulation -----------------------------------------------------

    def simulate(self, params_like: Any, plan: AdmissionPlan | Any, *,
                 pspecs: Any | None = None, topology: Any = "ici_ring",
                 datapath: Any | None = None,
                 overlap_fraction: float = 1.0,
                 compute_time_s: float = 0.0,
                 ready_times: Sequence[float] | None = None,
                 **topology_kwargs):
        """Simulate one aggregation pass of this session's layout.

        Replays the (cached) bucket layout for ``(params_like, plan)``
        through the :mod:`repro.sim` discrete-event simulator on any
        registered topology (``"cxl_direct"``, ``"cxl_switched"``,
        ``"ici_ring"``, ``"multihop"``, or a custom
        ``@register_topology`` entry).  ``compute_time_s`` is the
        backward-pass wall time the collective timeline overlaps with;
        ``datapath`` defaults to the paper's 5-stage 512-bit
        :class:`~repro.sim.FlitPipeline`.  Returns a
        :class:`~repro.sim.SimReport` — per-bucket start/end times,
        exposed-vs-hidden datapath time, link utilization, and the
        critical path; ``report.telemetry(step, loss)`` adapts the
        simulated step time into the controller Telemetry channel.
        """
        from ..sim import simulate_layout
        layout = self.layout_for(params_like, plan, pspecs=pspecs)
        return simulate_layout(layout, self.num_workers, topology=topology,
                               datapath=datapath,
                               overlap_fraction=overlap_fraction,
                               compute_time_s=compute_time_s,
                               ready_times=ready_times, **topology_kwargs)

    # -- plan autotuning ------------------------------------------------

    def autotune(self, params_like: Any, space: Any | None = None, *,
                 topology: str = "ici_ring", strategy: Any = "grid",
                 shortlist: int = 8, objective: Any | None = None,
                 compute_time_s: float = 0.0,
                 overlap_fraction: float = 1.0,
                 pspecs: Any | None = None, name: str | None = None,
                 error_feedback: bool = False, **topology_kwargs):
        """Search a plan space for this session's best configuration.

        Thin session entry point over :func:`repro.tune.autotune` (the
        tune package is imported lazily — fabric does not depend on it
        at module load).  ``params_like`` may be abstract
        ShapeDtypeStructs; ``space`` defaults to
        :func:`repro.tune.default_space` (all presets + generated
        low-bit axes, classifier head pinned to FP32).  Returns a
        :class:`repro.tune.TunedPlan`; ``tuned.apply(self)`` adopts its
        bucket budget and ``tuned.install()`` registers it as a named
        preset.
        """
        from ..tune import autotune as _autotune
        return _autotune(self, params_like, space, topology=topology,
                         strategy=strategy, shortlist=shortlist,
                         objective=objective,
                         compute_time_s=compute_time_s,
                         overlap_fraction=overlap_fraction, pspecs=pspecs,
                         name=name, error_feedback=error_feedback,
                         **topology_kwargs)

    # -- step builder ---------------------------------------------------

    def build_step(self, cfg, optimizer, plan: AdmissionPlan,
                   params_like: Any, *,
                   with_diagnostics: bool = False,
                   loss: Callable | None = None,
                   zero1: bool = True,
                   grad_accum: int = 1,
                   donate: bool = True) -> CompiledStep:
        """Compile one train step for a given admission plan.

        ``params_like``: a concrete or abstract (ShapeDtypeStruct) params
        tree — used only for structure/paths.  ``grad_accum`` splits the
        per-device batch into that many sequentially-scanned microbatches
        (activation memory / grad_accum, one aggregation per step —
        communication volume unchanged, overlap-friendly).  Aggregation
        runs through the bucket layout — one collective per 32 MiB
        bucket; the layout is planned here once and cached with the
        compiled step.
        """
        if self.mesh is None:
            raise ValueError("Fabric.build_step needs a mesh-bound session "
                             "(construct Fabric(mesh, dp_axes))")
        from ..models import loss_fn as model_loss_fn, param_pspecs
        from ..optim import optimizer_state_pspecs
        from ..runtime.shardings import sanitize_pspecs
        from ..core.diagnostics import group_cosines_from_mean

        mesh, dp, w = self.mesh, self.dp_axes, self.num_workers
        ctx = self.context
        pspecs = sanitize_pspecs(param_pspecs(cfg), params_like, mesh)
        policies = self.resolve(params_like, plan, pspecs=pspecs)
        groups = self.groups(params_like)
        ef_specs = self.ef_specs(policies, pspecs)
        base_loss = loss or (lambda p, b: model_loss_fn(p, cfg, b))

        def lf(p, b):
            # forward ops carry ``jvp(model)`` in their HLO op_name, backward
            # ops (the remat recompute included) ``transpose(jvp(model))``
            with jax.named_scope("model"):
                return base_loss(p, b)

        layout = self.layout_for(params_like, policies)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(dp), ef_specs),
            out_specs=(P(), P(), ef_specs),
            axis_names=frozenset(dp), check_vma=False)
        def _grad_agg(params, batch, ef):
            if grad_accum > 1:
                micro = _split_microbatches(batch, grad_accum)
                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def body(carry, mb):
                    lacc, gacc = carry
                    l, g = jax.value_and_grad(lf)(params, mb)
                    gacc = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), gacc, g)
                    return (lacc + l, gacc), None

                (lval, grads), _ = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), g0), micro)
                lval = lval / grad_accum
                grads = jax.tree.map(lambda x: x / grad_accum, grads)
            else:
                lval, grads = jax.value_and_grad(lf)(params, batch)
            with jax.named_scope("fabric.aggregate"):
                agg, new_ef = aggregate_tree_bucketed(
                    ctx, grads, policies, ef_states=ef, layout=layout)
            lval = jax.lax.pmean(lval, dp)
            return lval, agg, new_ef

        def step_fn(state: TrainState, batch):
            lval, agg, new_ef = _grad_agg(state.params, batch, state.ef)
            metrics = {"loss": lval}
            if with_diagnostics:
                cos = group_cosines_from_mean(agg, groups)
                for g, d in sorted(cos.items()):
                    metrics[f"cos/{g}/gbinary"] = d["gbinary"]
                    metrics[f"cos/{g}/gternary"] = d["gternary"]
            gn = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                              for x in jax.tree.leaves(agg)))
            metrics["agg_norm"] = gn
            with jax.named_scope("optimizer.apply"):
                new_params, new_opt = optimizer.apply(state.params, agg,
                                                      state.opt)
            return (TrainState(params=new_params, opt=new_opt, ef=new_ef,
                               step=state.step + 1), metrics)

        # shardings for explicit jit I/O (also consumed by the dry-run)
        param_sh = _named(mesh, pspecs)
        opt_specs = optimizer_state_pspecs(pspecs, params_like, dp_axes=dp,
                                           dp_size=w, zero1=zero1)
        mu_sh = _named(mesh, opt_specs)
        state_shardings = TrainState(
            params=param_sh,
            opt=_opt_shardings(optimizer, mu_sh, mesh),
            ef=_named(mesh, ef_specs),
            step=NamedSharding(mesh, P()))
        batch_sharding = NamedSharding(mesh, P(dp))

        jitted = jax.jit(
            step_fn,
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else ())
        aux = {"policies": policies, "groups": groups, "num_workers": w,
               "ef_specs": ef_specs, "pspecs": pspecs, "layout": layout,
               "num_launches": layout.num_launches}
        return CompiledStep(jitted, state_shardings, batch_sharding, aux)

    # -- per-plan-signature jit cache -----------------------------------

    def step_for(self, cfg, optimizer, plan: AdmissionPlan,
                 params_like: Any, *,
                 with_diagnostics: bool = False,
                 loss: Callable | None = None,
                 zero1: bool = True,
                 grad_accum: int = 1) -> CompiledStep:
        """Cached :meth:`build_step` — one compiled step per plan
        signature (the XLA analogue of the controller mode latch).

        The key also covers ``cfg``/``optimizer``/``loss`` (hashable
        frozen dataclasses / callables), so several Trainers may safely
        share one session without cross-model cache hits.
        """
        # num_workers + membership epoch: a step compiled for one worker
        # view must never be served after an elastic re-plan, even when
        # the rejoined view happens to have the same worker count
        # the plan modes' kernel signatures: a step compiled against one
        # kernel set must never be served after a codec (or its kernels)
        # is swapped under the same name
        kern_sig = tuple(sorted(
            (codec_name(m), _codec_kernel_sig(m)) for m in plan_modes(plan)))
        key = (plan.signature(), with_diagnostics, zero1, grad_accum,
               cfg, optimizer, loss, kern_sig, self.num_workers,
               self.membership_epoch)
        if key not in self._compiled:
            # a miss inside a training run shows in its trace at that step
            with jax.profiler.TraceAnnotation("fabric.build_step"):
                self._compiled[key] = self.build_step(
                    cfg, optimizer, plan, params_like,
                    with_diagnostics=with_diagnostics, loss=loss,
                    zero1=zero1, grad_accum=grad_accum)
        return self._compiled[key]

    def clear_cache(self) -> None:
        self._compiled.clear()
        self._layouts.clear()

    def __repr__(self) -> str:
        return (f"Fabric(dp_axes={self.dp_axes}, "
                f"num_workers={self.num_workers}, "
                f"mesh={'set' if self.mesh is not None else None})")

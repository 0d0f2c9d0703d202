"""ElasticTrainer: the Trainer control loop over a dynamic worker fleet.

Virtual workers via ``jax.vmap(..., axis_name="w")`` over a host-local
``Fabric(dp_axes=("w",))`` session — the same collectives the mesh path
runs under shard_map resolve against the vmapped axis, so per-worker
gradients, EF residuals, and votes behave exactly as on hardware while
the worker count is free to change between steps.

Lifecycle on a membership change (DESIGN.md §10):

  * graceful ``join``/``leave`` — step-boundary re-plan: the fleet's
    :class:`~repro.elastic.membership.WorkerView` epoch bumps, the
    session re-binds (``Fabric.bind_membership``), EF residuals are
    re-seated by worker id (survivors keep theirs, joiners start at
    zero), and the next step compiles fresh under the new
    ``(num_workers, epoch)`` cache key — a stale jitted step or
    ``BucketLayout`` can never be served;
  * ``crash`` — involuntary: same view change, then rollback to the
    last durable checkpoint and deterministic replay under the shrunken
    fleet.  Controller state (CUSUM, cooldown, the admitted plan) rides
    the checkpoint via the ``controller=`` threading, so recovery never
    resets the control plane to warm-up.  EF residuals are worker-local
    state and do not survive a crash (documented loss, like the paper's
    fabric-resident accumulators).

Per-worker step times (wall-clock, or a deterministic nominal time
scaled by the active fault models) feed the
:class:`~repro.elastic.detector.StragglerDetector`, whose statistics
ride into :class:`~repro.fabric.control.Telemetry` for any controller
to act on.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..core import AdmissionPlan, GroupRules, plan_traffic_ratio
from ..core.diagnostics import group_cosines_from_mean
from ..fabric import Fabric, TrainState
from ..fabric.control import Telemetry, make_controller
from ..fabric.session import aggregate_tree_bucketed
from ..models import ModelConfig, init_params
from ..models import loss_fn as model_loss_fn
from ..optim import Optimizer
from ..runtime.fault import StepTimer
from .detector import StragglerDetector
from .faults import (FaultModel, combined_step_time_scale, resolve_faults)
from .membership import Membership, MembershipEvent

log = logging.getLogger("repro.elastic")

__all__ = ["ElasticConfig", "ElasticFailure", "ElasticTrainer"]


class ElasticFailure(RuntimeError):
    """A worker crashed: roll back to the last durable checkpoint."""

    def __init__(self, event: MembershipEvent):
        super().__init__(f"worker {event.worker} crashed at step "
                         f"{event.step}")
        self.event = event


@dataclasses.dataclass
class ElasticConfig:
    checkpoint_interval: int = 10
    checkpoint_keep: int = 3
    log_interval: int = 50
    max_restarts: int = 10
    #: None -> measure wall time per step; a float makes per-worker step
    #: times fully deterministic (nominal seconds scaled by the active
    #: fault models) for reproducible detector/controller runs.
    synthetic_step_time_s: float | None = None


def _worker_stream(data: Any, worker: int):
    """Per-worker deterministic stream from one stream template.

    ``data`` is either a factory ``worker_id -> stream`` or a
    dataclass stream with a ``host_index`` field (SyntheticLMStream):
    each worker draws from its own host slot, so the *effective batch of
    a step depends only on the live worker set*, never on fleet history.
    """
    if callable(data) and not hasattr(data, "batch_at"):
        return data(worker)
    if dataclasses.is_dataclass(data) and hasattr(data, "host_index"):
        return dataclasses.replace(data, host_index=worker)
    raise TypeError(
        "data must be a worker_id -> stream factory or a dataclass "
        "stream with a host_index field (e.g. SyntheticLMStream)")


def _resize_ef(ef: Any, old_workers: Sequence[int],
               new_workers: Sequence[int]) -> Any:
    """Re-seat per-worker EF rows across a view change, keyed by id."""
    slot = {w: i for i, w in enumerate(old_workers)}

    def leaf(e):
        rows = [e[slot[w]] if w in slot else jnp.zeros_like(e[0])
                for w in new_workers]
        return jnp.stack(rows)

    return jax.tree.map(leaf, ef)


class ElasticTrainer:
    """Host control loop over an elastic virtual-worker fleet.

    ``membership`` is a :class:`Membership` ledger (or an int for a
    fixed initial fleet); graceful events come from its deterministic
    schedule, involuntary ones from the ``faults`` models
    (:func:`repro.elastic.resolve_faults` shapes accepted).  Controller
    resolution mirrors :class:`repro.runtime.Trainer`: ``controller=``
    (instance or registered name) for adaptive plans, ``plan=`` for the
    static fast path.
    """

    def __init__(self, cfg: ModelConfig, optimizer: Optimizer, data: Any,
                 membership: Membership | int, *,
                 faults: Sequence = (),
                 controller=None,
                 plan: AdmissionPlan | None = None,
                 rules: GroupRules | None = None,
                 ecfg: ElasticConfig | None = None,
                 ckpt_dir: str | None = None,
                 detector: StragglerDetector | None = None,
                 loss: Callable | None = None,
                 seed: int = 0):
        self.cfg, self.optimizer, self.data = cfg, optimizer, data
        self.membership = (membership if isinstance(membership, Membership)
                           else Membership(membership))
        self.faults: tuple[FaultModel, ...] = resolve_faults(faults)
        if isinstance(controller, str):
            controller = make_controller(controller)
        self.controller = controller
        self.static_plan = plan
        self.ecfg = ecfg or ElasticConfig()
        self.loss = loss
        self.seed = seed
        self.fabric = Fabric(dp_axes=("w",),
                             num_workers=self.membership.view.num_workers,
                             rules=rules)
        self.fabric.bind_membership(self.membership.view)
        if controller is not None:
            self.fabric.attach_controller(controller)
        self.detector = detector or StragglerDetector()
        self.ckpt = (CheckpointManager(
            ckpt_dir, interval=self.ecfg.checkpoint_interval,
            keep=self.ecfg.checkpoint_keep) if ckpt_dir else None)
        self.state: TrainState | None = None
        self.history: list[dict] = []
        self.recoveries: list[dict] = []
        self.restarts = 0
        self.executed_steps = 0
        self.replayed_steps = 0
        self.total_traffic = 0.0
        self.unique_traffic = 0.0
        self._high_water = 0
        self._sizes = None
        self._streams: dict[int, Any] = {}
        self._compiled: dict[tuple, Any] = {}
        self._just_restarted = False

    # -- state ----------------------------------------------------------

    def init_state(self) -> TrainState:
        params = init_params(jax.random.PRNGKey(self.seed), self.cfg)
        opt = self.optimizer.init(params)
        self.state = TrainState(params=params, opt=opt,
                                ef=self._fresh_ef(params),
                                step=jnp.zeros((), jnp.int32))
        self._sizes = self.fabric.group_sizes(params)
        return self.state

    def _fresh_ef(self, params: Any) -> Any:
        """Full per-worker residual tree, ``(W, 1, *shape)`` per leaf.

        Capacity for *any* plan the controller may latch later (the EF
        gate in aggregation is per-policy, so non-EF plans simply pass
        the rows through untouched) — unlike the mesh Trainer, elastic
        plans change too often to size EF off the initial plan.
        """
        w = self.membership.view.num_workers
        return jax.tree.map(
            lambda p: jnp.zeros((w, 1) + tuple(p.shape), jnp.float32),
            params)

    def _current_plan(self) -> AdmissionPlan:
        if self.controller is not None:
            return self.controller.plan
        return self.static_plan or AdmissionPlan.fp32_all()

    def _stream(self, worker: int):
        if worker not in self._streams:
            self._streams[worker] = _worker_stream(self.data, worker)
        return self._streams[worker]

    def _batch(self, step: int):
        """Stacked per-worker batch, leading axis = live workers."""
        parts = [self._stream(w).batch_at(step)
                 for w in self.membership.view.workers]
        return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x)
                                                   for x in xs]), *parts)

    # -- step compilation ------------------------------------------------

    def _get_step(self, plan: AdmissionPlan, diagnostics: bool):
        # num_workers + membership epoch in the key: a step compiled for
        # one view is never served after a re-plan (same fix as
        # Fabric.step_for)
        key = (plan.signature(), diagnostics,
               self.membership.view.num_workers,
               self.fabric.membership_epoch)
        if key not in self._compiled:
            self._compiled[key] = self._build_step(plan, diagnostics)
        return self._compiled[key]

    def _build_step(self, plan: AdmissionPlan, diagnostics: bool):
        fabric, cfg, optimizer = self.fabric, self.cfg, self.optimizer
        params_like = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(self.seed), cfg))
        policies = fabric.resolve(params_like, plan)
        groups = fabric.groups(params_like)
        ctx = fabric.context
        layout = fabric.layout_for(params_like, policies)
        lf = self.loss or (lambda p, b: model_loss_fn(p, cfg, b))

        def per_worker(params, batch, ef):
            lval, grads = jax.value_and_grad(lf)(params, batch)
            agg, new_ef = aggregate_tree_bucketed(
                ctx, grads, policies, ef_states=ef, layout=layout)
            return jax.lax.pmean(lval, "w"), agg, new_ef

        def step_fn(state: TrainState, batch):
            lval, agg, new_ef = jax.vmap(
                per_worker, in_axes=(None, 0, 0),
                axis_name="w")(state.params, batch, state.ef)
            # post-collective values are replicated over w; take slot 0
            loss0 = lval[0]
            agg0 = jax.tree.map(lambda a: a[0], agg)
            metrics = {"loss": loss0}
            if diagnostics:
                cos = group_cosines_from_mean(agg0, groups)
                for g, d in sorted(cos.items()):
                    metrics[f"cos/{g}/gbinary"] = d["gbinary"]
                    metrics[f"cos/{g}/gternary"] = d["gternary"]
            new_params, new_opt = optimizer.apply(state.params, agg0,
                                                  state.opt)
            return (TrainState(params=new_params, opt=new_opt, ef=new_ef,
                               step=state.step + 1), metrics)

        return jax.jit(step_fn)

    # -- membership ------------------------------------------------------

    def _apply_events(self, step: int) -> MembershipEvent | None:
        """Apply all events due at ``step``; returns a crash, if any.

        Graceful scheduled events apply first, then fault-driven ones;
        every view change re-binds the session (epoch into the jit-cache
        key) and re-seats EF rows by worker id.
        """
        events = list(self.membership.step_events(step))
        for f in self.faults:
            events.extend(f.membership_events(step))
        if not events:
            return None
        old = self.membership.view
        crash = None
        for ev in events:
            self.membership.apply(ev)
            if ev.kind == "crash":
                crash = ev
        new = self.membership.view
        self.fabric.bind_membership(new)
        if self.state is not None:
            self.state = TrainState(
                params=self.state.params, opt=self.state.opt,
                ef=_resize_ef(self.state.ef, old.workers, new.workers),
                step=self.state.step)
        log.info("membership epoch %d -> %d: %s (W=%d)", old.epoch,
                 new.epoch, [e.to_jsonable() for e in events],
                 new.num_workers)
        return crash

    # -- checkpointing ---------------------------------------------------

    def _ckpt_tree(self) -> dict:
        """Durable state: params/opt/step only — EF rows are worker-local
        (their shapes change with the fleet; a crash loses them)."""
        return {"params": self.state.params, "opt": self.state.opt,
                "step": self.state.step}

    def _restore(self) -> bool:
        try:
            restored = self.ckpt.restore(self._ckpt_tree(),
                                         controller=self.controller)
        except FileNotFoundError:
            return False
        if restored is None:
            return False
        _, tree, _ = restored
        self.state = TrainState(
            params=tree["params"], opt=tree["opt"],
            ef=self._fresh_ef(tree["params"]),
            step=jnp.asarray(tree["step"], jnp.int32))
        self._just_restarted = True
        return True

    def _recover(self, failure: ElasticFailure) -> None:
        crash_step = failure.event.step
        if self.ckpt is None or not self._restore():
            # no durable checkpoint yet: deterministic re-init from step 0
            self.init_state()
            self._just_restarted = True
        restored_step = int(self.state.step)
        self.recoveries.append({
            "crash_step": crash_step,
            "restored_step": restored_step,
            "steps_to_recover": crash_step - restored_step,
            "epoch": self.membership.view.epoch,
            "num_workers": self.membership.view.num_workers,
        })
        log.warning("recovered from %s: rolled back %d steps (restart %d)",
                    failure, crash_step - restored_step, self.restarts)

    # -- loop ------------------------------------------------------------

    def run(self, num_steps: int) -> list[dict]:
        if self.state is None:
            self.init_state()
            if self.ckpt is not None and self._restore():
                log.info("restored checkpoint at step %d",
                         int(self.state.step))
        while int(self.state.step) < num_steps:
            try:
                self._run_until(num_steps)
            except ElasticFailure as e:
                self.restarts += 1
                if self.restarts > self.ecfg.max_restarts:
                    raise
                self._recover(e)
        if self.ckpt is not None:
            self.ckpt.maybe_save(int(self.state.step), self._ckpt_tree(),
                                 force=True, controller=self.controller)
            self.ckpt.wait()
        return self.history

    def _run_until(self, num_steps: int) -> None:
        while int(self.state.step) < num_steps:
            step = int(self.state.step)
            crash = self._apply_events(step)
            if crash is not None:
                raise ElasticFailure(crash)
            view = self.membership.view

            plan = self._current_plan()
            calibrating = bool(self.controller is not None and getattr(
                self.controller, "wants_diagnostics", False))
            jitted = self._get_step(plan, calibrating)
            batch = self._batch(step)

            with StepTimer() as t:
                self.state, metrics = jitted(self.state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}

            self.executed_steps += 1
            replay = step < self._high_water
            if replay:
                self.replayed_steps += 1
            self._high_water = max(self._high_water, step + 1)

            base = (self.ecfg.synthetic_step_time_s
                    if self.ecfg.synthetic_step_time_s is not None
                    else t.duration)
            times = {w: base * combined_step_time_scale(self.faults, step, w)
                     for w in view.workers}
            stats = self.detector.observe(step, times)

            ratio = plan_traffic_ratio(self._sizes, plan)
            self.total_traffic += ratio
            if not replay:
                self.unique_traffic += ratio
            metrics.update(step=step, plan=plan.signature(),
                           traffic_ratio=ratio,
                           step_time_s=max(times.values()),
                           num_workers=view.num_workers,
                           membership_epoch=view.epoch,
                           stragglers=stats.stragglers)
            self.history.append(metrics)

            if self.controller is not None:
                telemetry = dataclasses.replace(
                    Telemetry.from_metrics(step, metrics,
                                           step_time_s=max(times.values()),
                                           restart=self._just_restarted),
                    worker_step_times=times, stragglers=stats.stragglers,
                    membership_epoch=view.epoch)
                self._just_restarted = False
                self.controller.observe(telemetry)

            if self.ckpt is not None:
                self.ckpt.maybe_save(
                    step + 1, self._ckpt_tree(),
                    extra={"plan": plan.signature(),
                           "membership": view.to_jsonable()},
                    controller=self.controller)
            if step % self.ecfg.log_interval == 0:
                log.info("step %d loss %.4f W=%d epoch=%d plan=%s", step,
                         metrics["loss"], view.num_workers, view.epoch,
                         plan.signature()[:48])

    # -- reporting -------------------------------------------------------

    @property
    def traffic_overhead(self) -> float:
        """Executed over ideal gradient traffic (1.0 = no replay waste)."""
        return (self.total_traffic / self.unique_traffic
                if self.unique_traffic > 0 else 1.0)

    def report(self) -> dict:
        return {
            "steps": self._high_water,
            "executed_steps": self.executed_steps,
            "replayed_steps": self.replayed_steps,
            "traffic_overhead": self.traffic_overhead,
            "restarts": self.restarts,
            "recoveries": list(self.recoveries),
            "final_view": self.membership.view.to_jsonable(),
            "compiled_steps": len(self._compiled),
        }

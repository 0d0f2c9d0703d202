"""Distributed training runtime: the NEURON-Fabric train step and Trainer.

The train step is the integration point of the whole system (DESIGN.md §4):

  * gradients are computed inside a *partial-manual* ``jax.shard_map`` —
    manual over the DP axes (``('pod','data')``), auto over ``'model'`` —
    so per-worker gradients are visible to the aggregation layer exactly
    like per-worker payloads are visible to the paper's controller;
  * each bucket is aggregated under its admitted mode through the
    :class:`repro.fabric.Fabric` session: FP32 buckets via psum, low-bit
    buckets via whichever registered schedule backend the plan names;
  * the optimizer runs *outside* the shard_map in auto-SPMD land, so
    ZeRO-1 optimizer-state sharding is pure GSPMD;
  * one compiled step per AdmissionPlan signature, cached inside the
    Fabric — the XLA analogue of the paper's controller mode latch.

The Trainer owns the host-side control loop: checkpointing, failure
recovery, the straggler watchdog, and — via an attached
:class:`repro.fabric.control.Controller` — the admission-control plane.
Each step it emits one typed :class:`~repro.fabric.control.Telemetry`
record (built from the Fabric-compiled step's metrics) to the
controller, which owns warm-up/calibration/admission/recovery policy and
the mode latch.  Step compilation and aggregation policy live in the
Fabric session it drives.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from ..core import AdmissionPlan, GroupRules, plan_traffic_ratio
from ..checkpoint import CheckpointManager
from ..fabric import CompiledStep, Fabric, TrainState, dp_num_workers
from ..fabric.control import Telemetry, make_controller
from ..fabric.session import _named
from ..models import ModelConfig, init_params, param_pspecs
from ..optim import Optimizer
from .fault import (FailureInjector, SimulatedFailure, StepTimer,
                    StragglerWatchdog)

log = logging.getLogger("repro.train")

__all__ = ["TrainState", "Trainer", "TrainerConfig", "build_train_step",
           "dp_num_workers"]


# ---------------------------------------------------------------------------
# step builder (legacy shim)
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, mesh, optimizer: Optimizer,
                     plan: AdmissionPlan, params_like: Any, *,
                     dp_axes=("data",), rules: GroupRules | None = None,
                     with_diagnostics: bool = False,
                     loss: Callable | None = None,
                     zero1: bool = True,
                     grad_accum: int = 1,
                     donate: bool = True) -> CompiledStep:
    """Deprecated free-function shim — use ``Fabric(...).build_step``.

    Constructs a throwaway session and compiles one step; returns the
    legacy 4-tuple-compatible :class:`CompiledStep`
    ``(jitted, state_shardings, batch_sharding, aux)``.
    """
    fabric = Fabric(mesh, dp_axes, rules=rules)
    return fabric.build_step(cfg, optimizer, plan, params_like,
                             with_diagnostics=with_diagnostics, loss=loss,
                             zero1=zero1, grad_accum=grad_accum,
                             donate=donate)


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainerConfig:
    dp_axes: tuple = ("data",)
    checkpoint_interval: int = 100
    checkpoint_keep: int = 3
    log_interval: int = 10
    max_restarts: int = 10
    zero1: bool = True


class Trainer:
    """Host control loop with admission control and fault tolerance.

    Runs on a :class:`repro.fabric.Fabric` session — pass one via
    ``fabric=`` to share schedule backends / compiled-step caches across
    components, or let the Trainer construct its own from ``mesh`` and
    ``tcfg.dp_axes``.

    Admission control is a pluggable controller: pass ``controller=``
    (an instance or a ``@register_controller`` name) or attach one to
    the session beforehand (``fabric.attach_controller(...)``) — both
    drive the same telemetry -> observe -> latch path.  ``plan=``
    without a controller is the static fast path (bit-identical to
    pre-controller behaviour).
    """

    def __init__(self, cfg: ModelConfig, mesh, optimizer: Optimizer,
                 data: Iterator[dict], *,
                 tcfg: TrainerConfig | None = None,
                 controller=None,
                 control=None,
                 plan: AdmissionPlan | None = None,
                 rules: GroupRules | None = None,
                 fabric: Fabric | None = None,
                 ckpt_dir: str | None = None,
                 failure_injector: FailureInjector | None = None,
                 loss: Callable | None = None,
                 seed: int = 0):
        if tcfg is None:
            # fresh per-Trainer config (a dataclass default instance would
            # be shared across every Trainer constructed without one)
            tcfg = TrainerConfig(dp_axes=(fabric.dp_axes if fabric is not None
                                          else ("data",)))
        if fabric is None:
            fabric = Fabric(mesh, tcfg.dp_axes, rules=rules)
        else:
            # an explicit fabric owns mesh + dp_axes + rules; conflicting
            # direct arguments would otherwise be silently ignored
            if mesh is not None and mesh != fabric.mesh:
                raise ValueError("mesh argument conflicts with fabric.mesh; "
                                 "pass one or the other")
            if tuple(tcfg.dp_axes) != fabric.dp_axes:
                raise ValueError(
                    f"tcfg.dp_axes {tuple(tcfg.dp_axes)} conflicts with "
                    f"fabric.dp_axes {fabric.dp_axes}; construct the Fabric "
                    f"with these axes")
            if rules is not None and rules is not fabric.rules:
                raise ValueError("rules argument conflicts with fabric.rules"
                                 "; construct the Fabric with these rules")
        self.fabric = fabric
        self.cfg, self.mesh, self.optimizer = cfg, fabric.mesh, optimizer
        self.tcfg = tcfg
        self.rules = fabric.rules
        # controller resolution: explicit argument (new `controller=` or
        # legacy `control=`) > the session's attached controller
        if controller is not None and control is not None:
            raise ValueError("pass either controller= or the deprecated "
                             "control=, not both")
        controller = controller if controller is not None else control
        if isinstance(controller, str):
            controller = make_controller(controller)
        if controller is None:
            controller = fabric.controller
        elif fabric.controller is not None \
                and fabric.controller is not controller:
            raise ValueError("controller argument conflicts with the "
                             "controller already attached to this fabric")
        else:
            fabric.attach_controller(controller)
        self.controller = controller
        self.control = controller          # backward-compatible alias
        self.static_plan = plan
        self.data = data
        self.loss = loss
        self.seed = seed
        self.failure_injector = failure_injector
        self.watchdog = StragglerWatchdog()
        self.ckpt = (CheckpointManager(ckpt_dir,
                                       interval=tcfg.checkpoint_interval,
                                       keep=tcfg.checkpoint_keep)
                     if ckpt_dir else None)
        self.state: TrainState | None = None
        self.history: list[dict] = []
        self.restarts = 0
        self.traffic_log: list[float] = []
        self._sizes = None
        self._just_restarted = False

    # -- state ----------------------------------------------------------
    def init_state(self) -> TrainState:
        key = jax.random.PRNGKey(self.seed)
        with jax.set_mesh(self.mesh):
            params = init_params(key, self.cfg)
        pspecs = param_pspecs(self.cfg)
        params = jax.device_put(params, _named(self.mesh, pspecs))
        opt = self.optimizer.init(params)
        plan = self._current_plan()
        policies = self.fabric.resolve(params, plan, pspecs=pspecs)
        ef = self.fabric.init_ef(params, policies)
        self.state = TrainState(params=params, opt=opt, ef=ef,
                                step=jnp.zeros((), jnp.int32))
        # placed as the step places its output, so the step compiled for
        # this state serves every later step too
        self.state = jax.device_put(
            self.state, self.compiled_step(plan).state_shardings)
        self._sizes = self.fabric.group_sizes(params)
        return self.state

    def _current_plan(self) -> AdmissionPlan:
        if self.controller is not None:
            return self.controller.plan
        return self.static_plan or AdmissionPlan.fp32_all()

    def compiled_step(self, plan: AdmissionPlan | None = None,
                      diagnostics: bool = False) -> CompiledStep:
        """The session's step for ``plan`` (default: the current plan) —
        the one :meth:`run` executes.  Needs :meth:`init_state` first."""
        return self.fabric.step_for(
            self.cfg, self.optimizer, plan or self._current_plan(),
            self.state.params, with_diagnostics=diagnostics,
            loss=self.loss, zero1=self.tcfg.zero1)

    def _get_step(self, plan: AdmissionPlan, diagnostics: bool):
        step = self.compiled_step(plan, diagnostics)
        return step, step.batch_sharding

    # -- loop -----------------------------------------------------------
    def run(self, num_steps: int) -> list[dict]:
        if self.state is None:
            if self.ckpt is not None:
                restored = None
                try:
                    self.init_state()
                    restored = self.ckpt.restore(self.state,
                                                 controller=self.controller)
                except FileNotFoundError:
                    restored = None
                if restored is not None:
                    step, tree, _ = restored
                    self.state = tree
                    self._just_restarted = True
                    log.info("restored checkpoint at step %d", step)
            else:
                self.init_state()

        it = iter(self.data)
        done = int(self.state.step)
        while done < num_steps:
            try:
                done = self._run_until(num_steps, it)
            except SimulatedFailure as e:
                self.restarts += 1
                if self.restarts > self.tcfg.max_restarts:
                    raise
                log.warning("%s -> restart %d (restore + replay)",
                            e, self.restarts)
                self._recover()
                done = int(self.state.step)
        if self.ckpt is not None:
            self.ckpt.maybe_save(int(self.state.step), self.state, force=True,
                                 controller=self.controller)
            self.ckpt.wait()
        return self.history

    def _recover(self):
        """Node-failure recovery: restore last durable checkpoint.

        The controller is restored alongside the model state, so CUSUM
        statistics, the Supervisor cooldown, and the admitted plan pick
        up where the checkpoint left them instead of resetting the
        control plane to warm-up.
        """
        if self.ckpt is None:
            raise RuntimeError("failure without checkpointing enabled")
        restored = self.ckpt.restore(self.state, controller=self.controller)
        if restored is None:
            self.init_state()
        else:
            _, self.state, _ = restored
        self._just_restarted = True

    def _run_until(self, num_steps: int, it: Iterator[dict]) -> int:
        while int(self.state.step) < num_steps:
            step = int(self.state.step)
            if self.failure_injector is not None:
                self.failure_injector.check(step)

            plan = self._current_plan()
            # the controller owns the calibration window (single source of
            # truth for warm-up length): compile with diagnostics while it
            # asks for them, so admission can retry until cosines land
            calibrating = bool(self.controller is not None and getattr(
                self.controller, "wants_diagnostics", False))
            jitted, b_sh = self._get_step(plan, calibrating)
            if hasattr(self.data, "batch_at"):   # deterministic replay
                batch = self.data.batch_at(step)
            else:
                batch = next(it)
            batch = jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), b_sh), batch)

            with StepTimer() as t:
                self.state, metrics = jitted(self.state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}
            self.watchdog.observe(step, t.duration)

            metrics["step"] = step
            metrics["plan"] = plan.signature()
            metrics["traffic_ratio"] = plan_traffic_ratio(self._sizes, plan)
            self.traffic_log.append(metrics["traffic_ratio"])
            self.history.append(metrics)

            if self.controller is not None:
                telemetry = Telemetry.from_metrics(
                    step, metrics, step_time_s=t.duration,
                    restart=self._just_restarted)
                self._just_restarted = False
                self.controller.observe(telemetry)

            if self.ckpt is not None:
                self.ckpt.maybe_save(step + 1, self.state,
                                     extra={"plan": plan.signature()},
                                     controller=self.controller)
            if step % self.tcfg.log_interval == 0:
                log.info("step %d loss %.4f traffic %.4f plan=%s", step,
                         metrics["loss"], metrics["traffic_ratio"],
                         plan.signature()[:48])
        return int(self.state.step)

"""repro.elastic: membership, faults, detector, ElasticTrainer, replay.

Acceptance criteria exercised here (ISSUE 8):

  * a scripted crash→rejoin schedule runs end-to-end through
    ``ElasticTrainer``, bit-identical to a fixed-membership run on the
    same effective batch when no faults fire;
  * under a ``straggler`` fault the detector emits Telemetry that flips
    the admission ladder;
  * the same schedule replays through ``repro.sim`` with per-phase
    exposed-time reporting;
  * checkpoint/restore across a membership change re-plans buckets for
    the new worker count and does not reset the controller to warm-up;
  * step-cache keys include the membership epoch (Fabric + elastic).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdmissionPlan, AggregationMode, Commander,
                        CusumGuard, Schedule, Supervisor)
from repro.data import SyntheticLMStream
from repro.elastic import (Crash, ElasticConfig, ElasticTrainer,
                           FaultModel, LocalSgdController, Membership,
                           MembershipEvent, StragglerAwareController,
                           StragglerDetector, WorkerView, available_faults,
                           make_fault, register_fault, replay_schedule,
                           resolve_faults, unregister_fault)
from repro.models import ModelConfig, init_params
from repro.optim import SgdMomentum


def _cfg():
    return ModelConfig(name="el", family="dense", num_layers=2, d_model=32,
                       num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128,
                       dtype="float32", remat=False)


def _data(seed=0):
    return SyntheticLMStream(vocab=128, seq_len=16, batch=4, seed=seed)


def _ecfg(**kw):
    kw.setdefault("synthetic_step_time_s", 1e-3)
    kw.setdefault("log_interval", 10_000)
    return ElasticConfig(**kw)


_PLAN = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                      schedule=Schedule.VOTE_PSUM,
                                      error_feedback=True)


# ---------------------------------------------------------------------------
# membership ledger
# ---------------------------------------------------------------------------

def test_membership_ledger_epochs_and_validation():
    m = Membership(4, schedule=[MembershipEvent(3, "leave", 2),
                                MembershipEvent(7, "join", 2)])
    assert m.view == WorkerView(0, (0, 1, 2, 3))
    assert m.step_events(2) == ()
    (ev,) = m.step_events(3)
    assert m.apply(ev) == WorkerView(1, (0, 1, 3))
    # re-removing an absent worker / re-joining a live one are bugs
    with pytest.raises(ValueError):
        m.apply(MembershipEvent(4, "leave", 2))
    with pytest.raises(ValueError):
        m.apply(MembershipEvent(4, "join", 0))
    (ev,) = m.step_events(7)
    assert m.apply(ev) == WorkerView(2, (0, 1, 2, 3))
    assert [e.kind for e, _ in m.log] == ["leave", "join"]
    # events scheduled in a rolled-past window still fire exactly once
    m2 = Membership(2, schedule=[MembershipEvent(1, "join", 5)])
    assert [e.worker for e in m2.step_events(4)] == [5]
    assert m2.step_events(4) == ()


def test_membership_never_empties():
    m = Membership([7])
    with pytest.raises(ValueError):
        m.apply(MembershipEvent(0, "crash", 7))


# ---------------------------------------------------------------------------
# fault-model registry
# ---------------------------------------------------------------------------

def test_fault_registry_builtins_and_custom():
    assert {"crash", "straggler", "link_degrade"} <= set(available_faults())
    crash = make_fault("crash", worker=3, step=8, rejoin_step=14)
    kinds = [e.kind for e in crash.scheduled_events()]
    assert kinds == ["crash", "join"]
    # live path fires each event exactly once, even when steps replay
    assert [e.kind for e in crash.membership_events(8)] == ["crash"]
    assert crash.membership_events(8) == ()

    @register_fault("toy_blip")
    class Blip(FaultModel):
        name = "toy_blip"

        def __init__(self, step=0):
            super().__init__()
            self.step = step

        def bandwidth_scale(self, step):
            return 0.5 if step == self.step else 1.0

    try:
        specs = resolve_faults([("toy_blip", {"step": 2}),
                                {"name": "straggler", "worker": 0,
                                 "start": 0, "stop": 4},
                                Crash(worker=1, step=9)])
        assert [type(f).__name__ for f in specs] == ["Blip", "Straggler",
                                                     "Crash"]
        assert specs[0].bandwidth_scale(2) == 0.5
    finally:
        unregister_fault("toy_blip")
    with pytest.raises(KeyError):
        make_fault("toy_blip")


def test_fault_parameter_validation():
    with pytest.raises(ValueError):
        make_fault("crash", worker=0, step=5, rejoin_step=5)
    with pytest.raises(ValueError):
        make_fault("straggler", worker=0, start=0, stop=4, factor=0.5)
    with pytest.raises(ValueError):
        make_fault("link_degrade", start=0, stop=4, factor=0.0)


# ---------------------------------------------------------------------------
# straggler detector
# ---------------------------------------------------------------------------

def test_detector_flags_sustained_straggler_only():
    det = StragglerDetector(threshold=2.0, alpha=0.3, warmup=1)
    base = {0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0}
    assert det.observe(0, base).stragglers == ()      # warmup
    assert det.observe(1, base).stragglers == ()
    # one-off spike is absorbed by the EWMA
    spike = {**base, 2: 3.5}
    assert det.observe(2, spike).stragglers == ()
    # sustained slowdown is flagged, with the right slowdown ratio
    stats = det.observe(3, spike)
    assert stats.stragglers == (2,)
    assert stats.slowdown > 2.0
    # departed workers drop out of the fleet statistics
    stats = det.observe(4, {0: 1.0, 1: 1.0, 3: 1.0})
    assert stats.stragglers == ()
    assert set(stats.times) == {0, 1, 3}


# ---------------------------------------------------------------------------
# ElasticTrainer: bit-identity, crash→rejoin, epoch-keyed jit cache
# ---------------------------------------------------------------------------

def test_no_fault_run_bit_identical_to_fixed_membership():
    """Armed-but-never-firing faults must not perturb a single bit."""
    def run(faults):
        tr = ElasticTrainer(_cfg(), SgdMomentum(peak_lr=0.2, total_steps=40),
                            _data(), 4, plan=_PLAN, faults=faults,
                            ecfg=_ecfg())
        return [h["loss"] for h in tr.run(8)]

    fixed = run(())
    armed = run([("crash", dict(worker=3, step=100)),
                 ("straggler", dict(worker=1, start=50, stop=60))])
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(armed))
    assert fixed[-1] < fixed[0]


def test_crash_rejoin_end_to_end(tmp_path):
    tr = ElasticTrainer(
        _cfg(), SgdMomentum(peak_lr=0.2, total_steps=60), _data(), 4,
        plan=_PLAN, ckpt_dir=str(tmp_path),
        faults=[("crash", dict(worker=3, step=9, rejoin_step=14))],
        ecfg=_ecfg(checkpoint_interval=4))
    hist = tr.run(20)
    rep = tr.report()
    # crash at 9, last durable checkpoint at 8 -> one replayed step
    assert rep["restarts"] == 1
    assert rep["recoveries"][0]["steps_to_recover"] == 1
    assert rep["replayed_steps"] == 1
    assert rep["traffic_overhead"] > 1.0
    # fleet trajectory: 4 -> 3 (crash) -> 4 (rejoin), epochs 0/1/2;
    # step 8 executes twice (original at W=4, replayed at W=3)
    eights = [h for h in hist if h["step"] == 8]
    assert [h["num_workers"] for h in eights] == [4, 3]
    by_step = {h["step"]: h for h in hist}
    assert by_step[10]["num_workers"] == 3
    assert by_step[15]["num_workers"] == 4
    assert rep["final_view"] == {"epoch": 2, "workers": [0, 1, 2, 3]}
    # one compiled step per (plan, W, epoch) - the rejoined view has the
    # same W as epoch 0 but must not be served the stale step
    assert rep["compiled_steps"] == 3
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_restore_across_membership_change_replans_and_keeps_phase(tmp_path):
    """Satellite 3: restore into a different worker count re-plans
    buckets for the live view, and the controller resumes in its
    checkpointed phase instead of warm-up."""
    from repro.fabric.control import PaperController

    def controller():
        return PaperController(commander=Commander(tau_binary=-1.0),
                               supervisor=Supervisor(guard=CusumGuard(h=1e9)),
                               warmup_steps=3)

    ctrl_a = controller()
    tr_a = ElasticTrainer(_cfg(), SgdMomentum(peak_lr=0.1, total_steps=60),
                          _data(), 4, controller=ctrl_a,
                          ckpt_dir=str(tmp_path),
                          ecfg=_ecfg(checkpoint_interval=2))
    tr_a.run(10)
    assert ctrl_a.program.phase == "admitted"

    # new process, new fleet size: 3 workers instead of 4
    ctrl_b = controller()
    tr_b = ElasticTrainer(_cfg(), SgdMomentum(peak_lr=0.1, total_steps=60),
                          _data(), 3, controller=ctrl_b,
                          ckpt_dir=str(tmp_path),
                          ecfg=_ecfg(checkpoint_interval=2))
    hist = tr_b.run(12)
    # restored at the checkpointed step, not from scratch
    assert hist[0]["step"] == 10
    # controller phase survived the worker-count change
    assert ctrl_b.program.phase == "admitted"
    assert "gbinary" in hist[0]["plan"]
    # the step ran under the live 3-worker view (fresh plan/bucket
    # build), not a resurrected 4-worker artifact
    assert hist[0]["num_workers"] == 3
    assert tr_b.fabric.num_workers == 3
    assert all(w == 3 for (_, _, w, _) in tr_b._compiled)


def test_fabric_step_cache_keys_include_membership_epoch():
    """Satellite 6 at the session level: re-binding an epoch-bumped view
    must miss the jit cache even at the same worker count."""
    from jax.sharding import AxisType
    from repro.fabric import Fabric
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    fabric = Fabric(mesh, ("data",))
    cfg = _cfg()
    opt = SgdMomentum(peak_lr=0.1, total_steps=10)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    plan = AdmissionPlan.fp32_all()
    with jax.set_mesh(mesh):
        fabric.step_for(cfg, opt, plan, params)
        fabric.step_for(cfg, opt, plan, params)
        assert len(fabric._compiled) == 1
        fabric.bind_membership(WorkerView(epoch=1, workers=(0,)))
        fabric.step_for(cfg, opt, plan, params)
    assert len(fabric._compiled) == 2
    # a mesh-bound session cannot change worker count
    with pytest.raises(ValueError):
        fabric.bind_membership(WorkerView(epoch=2, workers=(0, 1)))


# ---------------------------------------------------------------------------
# detector -> Telemetry -> admission ladder (acceptance criterion)
# ---------------------------------------------------------------------------

def test_straggler_fault_flips_admission_ladder():
    ctrl = StragglerAwareController(demote_after=2, recover_after=6)
    tr = ElasticTrainer(
        _cfg(), SgdMomentum(peak_lr=0.1, total_steps=60), _data(), 4,
        controller=ctrl,
        faults=[("straggler", dict(worker=1, start=3, stop=12, factor=6.0))],
        ecfg=_ecfg())
    hist = tr.run(24)
    # the detector surfaced the slow worker in telemetry
    assert any(h["stragglers"] == (1,) for h in hist)
    # ... which demoted the ladder to low-bit, then recovered to FP32
    kinds = [e.kind for e in ctrl.events]
    assert kinds == ["demoted", "recovered"]
    plans = [h["plan"] for h in hist]
    assert "gbinary" not in plans[0] and any("gbinary" in p for p in plans)
    assert "gbinary" not in plans[-1]
    # controller state round-trips
    blob = ctrl.state_dict()
    fresh = StragglerAwareController()
    fresh.load_state_dict(blob)
    assert fresh.phase == ctrl.phase
    assert fresh.plan.signature() == ctrl.plan.signature()


def test_graceful_leave_and_join_without_rollback():
    m = Membership(4, schedule=[MembershipEvent(3, "leave", 0),
                                MembershipEvent(6, "join", 0)])
    tr = ElasticTrainer(_cfg(), SgdMomentum(peak_lr=0.2, total_steps=40),
                        _data(), m, plan=_PLAN, ecfg=_ecfg())
    hist = tr.run(9)
    rep = tr.report()
    assert rep["restarts"] == 0 and rep["replayed_steps"] == 0
    assert [h["num_workers"] for h in hist] == [4, 4, 4, 3, 3, 3, 4, 4, 4]
    assert [h["membership_epoch"] for h in hist] == [0] * 3 + [1] * 3 + [2] * 3
    assert all(np.isfinite(h["loss"]) for h in hist)


# ---------------------------------------------------------------------------
# local-SGD strategy through the public seams
# ---------------------------------------------------------------------------

def test_local_sgd_strategy_traffic_and_training():
    tr = ElasticTrainer(_cfg(), SgdMomentum(peak_lr=0.3, total_steps=40),
                        _data(), 4,
                        controller=LocalSgdController(sync_every=4),
                        ecfg=_ecfg())
    hist = tr.run(16)
    traffic = [h["traffic_ratio"] for h in hist]
    # H-1 zero-wire local steps, then one low-bit sync step
    assert traffic[:4] == [0.0, 0.0, 0.0, traffic[3]]
    assert traffic[3] > 0.0
    for i, t in enumerate(traffic):
        assert (t > 0.0) == (i % 4 == 3), (i, t)
    # the banked gradients actually train the model at sync steps
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_local_accum_requires_error_feedback():
    from repro.elastic import LocalAccumBackend
    from repro.fabric.registry import AggregationContext
    backend = LocalAccumBackend()
    ctx = AggregationContext(dp_axes=(), num_workers=1)
    g = jnp.ones((4,))
    agg, ef = backend.aggregate(ctx, g, None, ef=jnp.zeros((4,)))
    np.testing.assert_array_equal(np.asarray(agg), np.zeros(4))
    np.testing.assert_array_equal(np.asarray(ef), np.ones(4))
    with pytest.raises(ValueError):
        backend.aggregate(ctx, g, None, ef=None)


def test_local_codec_canonicalizes_onto_local_accum():
    """A 0-bit payload must never ride a real collective: any built-in
    schedule a policy nominally names travels on local_accum (same
    normalization precedent as hierarchical routes)."""
    from repro.core.modes import wire_schedule
    for nominal in ("psum", "vote_psum", "packed_a2a", "local_accum"):
        assert wire_schedule("local", nominal) == "local_accum"


# ---------------------------------------------------------------------------
# sim replay (acceptance criterion: per-phase exposed-time reporting)
# ---------------------------------------------------------------------------

def test_replay_schedule_reports_per_phase_exposure():
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), _cfg()))
    faults = [("crash", dict(worker=3, step=8, rejoin_step=14)),
              ("straggler", dict(worker=1, start=4, stop=8, factor=5.0)),
              ("link_degrade", dict(start=16, stop=20, factor=0.25))]
    rep = replay_schedule(params, _PLAN, 4, 24, faults=faults,
                          topology="cxl_direct", compute_time_s=1e-4)
    assert rep.num_steps == 24
    spans = [(p.start, p.stop, p.num_workers, p.straggler_scale,
              p.bandwidth_scale) for p in rep.phases]
    assert spans == [(0, 4, 4, 1.0, 1.0), (4, 8, 4, 5.0, 1.0),
                     (8, 14, 3, 1.0, 1.0), (14, 16, 4, 1.0, 1.0),
                     (16, 20, 4, 1.0, 0.25), (20, 24, 4, 1.0, 1.0)]
    # straggler phases are slower; the report prices the whole scenario
    slow = next(p for p in rep.phases if p.straggler_scale > 1)
    assert slow.step_time_s > rep.phases[0].step_time_s
    assert rep.total_time_s > 0
    assert rep.summary()["num_phases"] == 6
    # a degraded link exposes at least as much communication
    degraded = next(p for p in rep.phases if p.bandwidth_scale < 1)
    assert degraded.exposed_s >= rep.phases[0].exposed_s
    # fault-free replay of the same plan is strictly cheaper
    clean = replay_schedule(params, _PLAN, 4, 24, topology="cxl_direct",
                            compute_time_s=1e-4)
    assert len(clean.phases) == 1
    assert clean.total_time_s < rep.total_time_s

"""repro.fabric.control: controller registry, phase programs, persistence.

Covers the registry contract (round-trip, unknown-name error, duplicate
protection), the typed Telemetry record, the PolicyProgram phase machine
(staged user phases, state round-trip), the ``"paper"`` controller's
event sequence ``warmup_end -> admitted -> recovery -> readmitted`` on a
scripted loss curve (including the admission *retry* while calibration
cosines are pending — the old one-shot-window bug), CusumGuard
properties under hypothesis, controller state threading through the
CheckpointManager, a failure-replay regression (restored runs keep the
Supervisor cooldown and the admitted plan instead of resetting to
warm-up), and — on a capable jax — the acceptance path: paper / static /
custom controllers all driving the Trainer through
``fabric.attach_controller``, bit-identical to the legacy static-plan
Trainer.
"""
import json
import math

import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import (AdmissionPlan, AggregationMode, Commander,
                        CusumGuard, Schedule, Supervisor)
from repro.fabric import Fabric
from repro.fabric.control import (Controller, FP32Controller,
                                  PaperController, Phase, PolicyProgram,
                                  StaticController, Telemetry,
                                  available_controllers, get_controller,
                                  make_controller, plan_from_jsonable,
                                  plan_presets, plan_to_jsonable,
                                  register_controller,
                                  unregister_controller)
from repro.runtime.fault import FailureInjector, SimulatedFailure

COS = {"backbone": {"gbinary": 0.8, "gternary": 0.7},
       "head": {"gbinary": 0.1, "gternary": 0.1}}


def _t(step, loss, cosines=None, **kw):
    return Telemetry(step=step, loss=loss, cosines=cosines, **kw)


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

def test_builtin_controllers_registered():
    names = available_controllers()
    for expected in ("paper", "adaptive", "static", "fp32"):
        assert expected in names
    assert get_controller("adaptive") is get_controller("paper")
    assert isinstance(make_controller("paper", warmup_steps=3),
                      PaperController)
    assert isinstance(make_controller("fp32"), FP32Controller)
    static = make_controller("static", plan="gbin_packed")
    assert static.plan.signature() == plan_presets()["gbin_packed"].signature()


def test_register_controller_roundtrip():
    @register_controller("toy_ctrl")
    class ToyController:
        name = "toy_ctrl"

        def __init__(self, plan=None):
            self.plan = plan or AdmissionPlan.fp32_all()

        def observe(self, telemetry):
            return self.plan

    try:
        c = make_controller("toy_ctrl")
        assert isinstance(c, ToyController)
        assert isinstance(c, Controller)      # protocol satisfied
        assert "toy_ctrl" in available_controllers()
    finally:
        unregister_controller("toy_ctrl")
    assert "toy_ctrl" not in available_controllers()


def test_unknown_controller_raises_clear_error():
    with pytest.raises(KeyError, match="unknown controller 'nope'"):
        get_controller("nope")
    with pytest.raises(KeyError, match="register_controller"):
        make_controller("nope")


def test_duplicate_controller_registration_raises_unless_override():
    with pytest.raises(ValueError, match="already registered"):
        @register_controller("paper")
        class Clash:
            name = "paper"

            def observe(self, telemetry):
                return AdmissionPlan.fp32_all()

    original = get_controller("static")

    @register_controller("static", override=True)
    class Replacement(StaticController):
        pass

    try:
        assert get_controller("static") is Replacement
    finally:
        register_controller("static", override=True)(original)
    assert get_controller("static") is original


def test_unregister_controller_removes_aliases_too():
    @register_controller("toy_main", "toy_alias")
    class Toy:
        name = "toy_main"

        def observe(self, telemetry):
            return AdmissionPlan.fp32_all()

    unregister_controller("toy_main")
    assert "toy_alias" not in available_controllers()
    # the same (name, *aliases) registration is repeatable after teardown
    register_controller("toy_main", "toy_alias")(Toy)
    unregister_controller("toy_alias")       # either key clears both
    assert "toy_main" not in available_controllers()


def test_builtin_controllers_satisfy_protocol():
    assert isinstance(make_controller("paper"), Controller)
    assert isinstance(make_controller("static"), Controller)


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def test_telemetry_from_metrics_parses_cosine_keys():
    metrics = {"loss": 1.25, "agg_norm": 3.0, "traffic_ratio": 0.25,
               "plan": "sig", "cos/backbone/gbinary": 0.8,
               "cos/backbone/gternary": 0.7, "cos/head/gbinary": 0.1}
    t = Telemetry.from_metrics(7, metrics, step_time_s=0.5, restart=True)
    assert t.step == 7 and t.loss == 1.25 and t.restart
    assert t.traffic_ratio == 0.25 and t.step_time_s == 0.5
    assert t.plan_signature == "sig"
    assert t.cosines == {"backbone": {"gbinary": 0.8, "gternary": 0.7},
                         "head": {"gbinary": 0.1}}
    # no cos/ keys -> cosines is None (calibration window over)
    assert Telemetry.from_metrics(8, {"loss": 1.0}).cosines is None


# ---------------------------------------------------------------------------
# plan (de)serialization
# ---------------------------------------------------------------------------

def test_plan_jsonable_roundtrip_preserves_signature():
    plans = list(plan_presets(error_feedback=True).values())
    plans += [AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                            schedule="my_custom_sched"),
              AdmissionPlan.fp32_all()]
    for plan in plans:
        blob = json.dumps(plan_to_jsonable(plan))          # JSON-safe
        back = plan_from_jsonable(json.loads(blob))
        assert back.signature() == plan.signature()
        assert back == plan


def test_plan_presets_match_launcher_vocabulary():
    presets = plan_presets()
    assert presets["gbin_vote"].signature() == \
        AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                      schedule=Schedule.VOTE_PSUM).signature()
    assert presets["fp32"].signature() == AdmissionPlan.fp32_all().signature()
    ef = plan_presets(error_feedback=True)
    assert ef["gbin_backbone"].policy_for("backbone").error_feedback
    assert not presets["gbin_backbone"].policy_for("backbone").error_feedback


def test_every_preset_json_roundtrip_is_canonical():
    """Every plan_presets entry — including hier_* hop plans and the
    int4/topk extension codecs — resolves its leaf policies, survives
    JSON, and re-resolves to the same canonical codec/schedule names."""
    import jax

    from repro.core.modes import codec_name, schedule_name
    from repro.fabric import get_codec

    sds = jax.ShapeDtypeStruct
    tree = {"wte": sds((512, 64), "float32"),
            "h00": {"qkv": sds((64, 192), "float32"),
                    "ln1_scale": sds((64,), "float32")},
            "head_w": sds((64, 512), "float32")}
    fab = Fabric(num_workers=4)
    for name, plan in plan_presets(error_feedback=True).items():
        # resolves: every leaf policy's codec is registered and its
        # wire schedule has a name
        policies = fab.resolve(tree, plan)
        for pol in jax.tree.leaves(
                policies, is_leaf=lambda x: hasattr(x, "mode")):
            get_codec(pol.mode)
        back = plan_from_jsonable(json.loads(
            json.dumps(plan_to_jsonable(plan))))
        assert back.signature() == plan.signature(), name
        for group in ("backbone", "head", "norms", "embed"):
            a, b = plan.policy_for(group), back.policy_for(group)
            assert codec_name(a.mode) == codec_name(b.mode), (name, group)
            assert schedule_name(a.resolved_schedule()) == \
                schedule_name(b.resolved_schedule()), (name, group)
            assert a.error_feedback == b.error_feedback, (name, group)


def test_register_plan_preset_roundtrip_and_builtin_guard():
    from repro.fabric.control import (register_plan_preset,
                                      unregister_plan_preset)

    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_TERNARY)
    register_plan_preset("my_tuned", plan)
    try:
        assert plan_presets()["my_tuned"].signature() == plan.signature()
        # duplicate registration raises unless override
        with pytest.raises(ValueError, match="already registered"):
            register_plan_preset("my_tuned", AdmissionPlan.fp32_all())
        register_plan_preset("my_tuned", AdmissionPlan.fp32_all(),
                             override=True)
        assert plan_presets()["my_tuned"].signature() == \
            AdmissionPlan.fp32_all().signature()
    finally:
        unregister_plan_preset("my_tuned")
    assert "my_tuned" not in plan_presets()
    # built-ins are never shadowable or removable
    with pytest.raises(ValueError, match="built-in"):
        register_plan_preset("fp32", plan, override=True)
    with pytest.raises(ValueError, match="built-in"):
        unregister_plan_preset("fp32")
    with pytest.raises(KeyError):
        unregister_plan_preset("never_registered")


# ---------------------------------------------------------------------------
# the paper controller's event sequence on a scripted loss curve
# ---------------------------------------------------------------------------

def test_paper_event_sequence_on_scripted_losses():
    c = PaperController(warmup_steps=5,
                        supervisor=Supervisor(
                            guard=CusumGuard(kappa=0.0, h=0.3),
                            cooldown_steps=5))
    fp32_sig = AdmissionPlan.fp32_all().signature()

    # warm-up: FP32, controller keeps asking for diagnostics
    for i in range(4):
        plan = c.observe(_t(i, 1.0 - 0.01 * i))
        assert plan.signature() == fp32_sig
        assert c.wants_diagnostics

    # cosines pending past the warm-up boundary: admission must RETRY,
    # not silently expire (the old one-shot `_step == warmup_steps` bug)
    for i in range(4, 7):
        plan = c.observe(_t(i, 0.95))
        assert plan.signature() == fp32_sig
        assert c.wants_diagnostics, "must keep calibrating until cosines land"
    assert [e.kind for e in c.events] == ["warmup_end"]

    # cosines finally arrive -> admitted
    plan = c.observe(_t(7, 0.9, cosines=COS))
    assert plan.policy_for("backbone").mode == AggregationMode.G_BINARY
    assert plan.policy_for("head").mode == AggregationMode.FP32
    assert not c.wants_diagnostics

    # sustained loss growth -> CUSUM recovery to FP32
    step = 8
    while c.program.phase != "recovery":
        assert step < 30, "guard never fired"
        c.observe(_t(step, 0.9 + 0.2 * (step - 7)))
        step += 1
    assert c.plan.signature() == fp32_sig
    assert c.supervisor.in_cooldown

    # healthy again -> re-admitted (stored plan; no cosines needed)
    while c.program.phase != "readmitted":
        assert step < 60, "never re-admitted"
        c.observe(_t(step, 0.5))
        step += 1
    assert c.plan.signature() == plan.signature()
    assert [e.kind for e in c.events] == \
        ["warmup_end", "admitted", "recovery", "readmitted"]


def test_paper_warmup_end_and_admission_can_share_a_step():
    """When cosines are already there as warm-up ends, the program chains
    warmup -> calibrate -> admitted on a single observe."""
    c = PaperController(warmup_steps=3, supervisor=Supervisor(
        guard=CusumGuard(h=1e9)))
    for i in range(2):
        c.observe(_t(i, 1.0, cosines=COS))
        assert [e.kind for e in c.events] == []
    plan = c.observe(_t(2, 1.0, cosines=COS))
    assert [e.kind for e in c.events] == ["warmup_end", "admitted"]
    assert plan.policy_for("backbone").mode == AggregationMode.G_BINARY


def test_supervisor_trigger_during_warmup_does_not_emit_recovery():
    """On the FP32 path already -> nothing to recover (legacy semantics)."""
    c = PaperController(warmup_steps=50, supervisor=Supervisor(
        guard=CusumGuard(kappa=0.0, h=0.01), cooldown_steps=5))
    for i in range(20):
        c.observe(_t(i, 1.0 + 0.5 * i))    # exploding loss during warm-up
    assert [e.kind for e in c.events] == []
    assert c.plan.signature() == AdmissionPlan.fp32_all().signature()


# ---------------------------------------------------------------------------
# CusumGuard properties (hypothesis)
# ---------------------------------------------------------------------------

def test_cusum_nonfinite_loss_always_triggers():
    pytest.importorskip("hypothesis",
                        reason="optional test dependency (pip install .[test])")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(prefix=st.lists(st.floats(0.1, 10.0), max_size=20),
           bad=st.sampled_from([math.nan, math.inf, -math.inf]))
    def check(prefix, bad):
        g = CusumGuard()
        for x in prefix:
            g.update(x)
        assert g.update(bad) is True

    check()


def test_cusum_bounded_noise_never_triggers():
    pytest.importorskip("hypothesis",
                        reason="optional test dependency (pip install .[test])")
    from hypothesis import given, settings, strategies as st

    kappa = 0.05

    @settings(max_examples=60, deadline=None)
    @given(base=st.floats(0.5, 5.0),
           noise=st.lists(st.floats(-kappa / 2, kappa / 2),
                          min_size=1, max_size=200))
    def check(base, noise):
        # |loss - base| <= kappa/2 keeps loss - mu <= kappa: the EWMA mu
        # stays inside the noise band, so the CUSUM statistic never grows
        g = CusumGuard(kappa=kappa, h=0.25)
        assert not any(g.update(base + n) for n in noise)

    check()


def test_cusum_sustained_drift_eventually_triggers():
    pytest.importorskip("hypothesis",
                        reason="optional test dependency (pip install .[test])")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(base=st.floats(0.5, 5.0), rate=st.floats(0.05, 0.5))
    def check(base, rate):
        g = CusumGuard(kappa=0.01, h=0.25)
        assert any(g.update(base + rate * i) for i in range(400)), \
            f"drift {rate}/step never triggered"

    check()


# ---------------------------------------------------------------------------
# PolicyProgram
# ---------------------------------------------------------------------------

def test_policy_program_staged_user_phases():
    """'Head on FP32 after step N' as a declarative program."""
    prog = PolicyProgram.staged([
        ("warmup", ("fp32", "fp32"), 3),
        ("all_lowbit", ("gbinary", "gbinary"), 6),
        ("head_fp32", ("gbinary", "fp32"), None)])
    latched = [prog.advance(_t(i, 1.0)) for i in range(9)]
    assert latched[:3] == [("fp32", "fp32")] * 3
    assert latched[3:6] == [("gbinary", "gbinary")] * 3
    assert latched[6:] == [("gbinary", "fp32")] * 3
    assert [e.kind for e in prog.events] == ["all_lowbit", "head_fp32"]


def test_policy_program_latch_vs_live_plans():
    calls = {"latched": 0, "live": 0}

    def latched_plan(t, p):
        calls["latched"] += 1
        return "L"

    def live_plan(t, p):
        calls["live"] += 1
        return "V"

    prog = PolicyProgram([
        Phase("a", plan=latched_plan,
              transition=lambda t, p: "b" if t.step >= 2 else None),
        Phase("b", plan=live_plan, latch=False),
    ], plan="init")
    # a latched callable on the start phase defers to the first advance
    # (it needs telemetry); until then the constructor fallback holds
    assert prog.plan == "init"
    assert prog.advance(_t(0, 1.0)) == "L"
    for i in range(1, 5):
        prog.advance(_t(i, 1.0))
    # "a" latches exactly once; "b" (live) evaluates on entry at step 2
    # + every subsequent advance
    assert calls == {"latched": 1, "live": 3}
    assert prog.plan == "V"


def test_policy_program_single_phase_latched_callable():
    """Regression: a one-phase program whose only plan is a latched
    callable must evaluate it on the first advance, not return None."""
    prog = PolicyProgram([Phase("go", plan=lambda t, p: ("gbinary", "fp32"))])
    assert prog.advance(_t(0, 1.0)) == ("gbinary", "fp32")
    assert prog.advance(_t(1, 1.0)) == ("gbinary", "fp32")


def test_policy_program_state_roundtrip_with_plan_payload():
    prog = PolicyProgram.staged([
        ("warmup", AdmissionPlan.fp32_all(), 2),
        ("admit", plan_presets()["gbin_packed"], None)])
    for i in range(4):
        prog.advance(_t(i, 1.0))
    blob = json.dumps(prog.state_dict())

    fresh = PolicyProgram.staged([
        ("warmup", AdmissionPlan.fp32_all(), 2),
        ("admit", plan_presets()["gbin_packed"], None)])
    fresh.load_state_dict(json.loads(blob))
    assert fresh.phase == "admit"
    assert fresh.plan.signature() == plan_presets()["gbin_packed"].signature()
    assert [e.kind for e in fresh.events] == ["admit"]

    with pytest.raises(ValueError, match="not in this program"):
        PolicyProgram([Phase("only")]).load_state_dict(json.loads(blob))


def test_run_training_labels_user_program_result():
    """RunResult.policy must name what the program actually latched, not
    the (ignored) policy arguments."""
    from repro.core.experiments import easy_task, run_training
    r = run_training(easy_task(), policy="fp32", steps=4, batch=16,
                     hidden=16,
                     program=PolicyProgram.staged(
                         [("all", ("gternary", "gternary"), None)]))
    assert r.policy == "gternary+gternaryhead"


def test_policy_program_rejects_bad_shapes():
    with pytest.raises(ValueError, match="at least one phase"):
        PolicyProgram([])
    with pytest.raises(ValueError, match="duplicate phase"):
        PolicyProgram([Phase("a"), Phase("a")])
    with pytest.raises(KeyError, match="unknown phase"):
        PolicyProgram([Phase("a")]).enter("nope")
    # a callable plan cannot be computed without telemetry — clear error
    # instead of an AttributeError deep inside the plan function
    c = PaperController(warmup_steps=2)
    with pytest.raises(ValueError, match="requires telemetry"):
        c.program.enter("admitted")
    c.program.enter("recovery")              # static plan: fine without
    assert c.program.events[-1].kind == "recovery"


# ---------------------------------------------------------------------------
# controller persistence: state_dict / CheckpointManager threading
# ---------------------------------------------------------------------------

def _drive_to_mid_cooldown(c, cooldown=20):
    """Warm-up, admit, trigger recovery, then burn a few cooldown steps."""
    step = 0
    for _ in range(2):
        c.observe(_t(step, 1.0, cosines=COS))
        step += 1
    assert c.program.phase == "admitted"
    while c.program.phase != "recovery":
        c.observe(_t(step, 1.0 + 0.5 * step))
        step += 1
    for _ in range(3):                      # partially spend the cooldown
        c.observe(_t(step, 0.5))
        step += 1
    assert c.supervisor.in_cooldown
    return step


def _paper(cooldown=20):
    return PaperController(
        warmup_steps=2,
        commander=Commander(tau_binary=-1.0),
        supervisor=Supervisor(guard=CusumGuard(kappa=0.0, h=0.3),
                              cooldown_steps=cooldown))


def test_paper_state_dict_roundtrip_mid_cooldown():
    c = _paper()
    step = _drive_to_mid_cooldown(c)
    blob = json.dumps(c.state_dict())          # must be JSON-serializable

    fresh = _paper()
    fresh.warmup_steps = 99                 # restart with a different knob
    fresh.load_state_dict(json.loads(blob))
    assert fresh.warmup_steps == c.warmup_steps, \
        "the checkpointed calibration window must win over the constructor"
    assert fresh.program.phase == "recovery"
    assert fresh.supervisor.in_cooldown
    assert fresh.supervisor._cooldown_left == c.supervisor._cooldown_left
    assert fresh._admitted_plan.signature() == c._admitted_plan.signature()
    assert [e.kind for e in fresh.events] == [e.kind for e in c.events]

    # the restored twin re-admits in lockstep with the original
    for twin in (c, fresh):
        while twin.program.phase != "readmitted":
            twin.observe(_t(step, 0.5))
    assert c.events[-1].kind == fresh.events[-1].kind == "readmitted"
    assert c.plan.signature() == fresh.plan.signature()


def test_checkpoint_manager_threads_controller_state(tmp_path):
    import jax.numpy as jnp
    tree = {"w": jnp.zeros((4,))}
    c = _paper()
    _drive_to_mid_cooldown(c)

    m = CheckpointManager(str(tmp_path), interval=1, keep=2)
    m.maybe_save(5, tree, extra={"plan": c.plan.signature()}, controller=c)
    m.wait()

    fresh = _paper()
    step, _, extra = m.restore(tree, controller=fresh)
    assert step == 5 and "controller" in extra
    assert fresh.program.phase == "recovery"
    assert fresh.supervisor.in_cooldown
    assert fresh._admitted_plan.signature() == c._admitted_plan.signature()

    # controller-free callers are untouched by the threading
    m2 = CheckpointManager(str(tmp_path), interval=1)
    assert m2.restore(tree)[0] == 5

    # resuming under a DIFFERENT controller kind must not feed it a
    # foreign state dict (warn + keep the fresh controller)
    other = StaticController(plan_presets()["gbin_vote"])
    m.restore(tree, controller=other)
    assert other.plan.signature() == plan_presets()["gbin_vote"].signature()


def test_failure_replay_keeps_cooldown_and_admitted_plan(tmp_path):
    """Regression for tentpole item 4, mesh-free: a SimulatedFailure lands
    mid-cooldown; the restarted control loop (fresh controller restored
    from the checkpoint, Trainer `_recover` style) must keep the
    Supervisor cooldown and the admitted plan instead of resetting the
    control plane to warm-up."""
    cooldown = 12
    losses = ([1.0, 1.0]                     # warm-up (admits at step 1)
              + [1.0 + 0.5 * i for i in range(6)]   # drift -> recovery
              + [0.5] * 30)                  # healthy tail
    injector = FailureInjector(at_steps=(9,))
    ckpt = CheckpointManager(str(tmp_path), interval=1, keep=3,
                             async_save=False)
    import jax.numpy as jnp
    tree = {"w": jnp.zeros(())}              # stand-in model state

    c = _paper(cooldown=cooldown)
    step, restarts = 0, 0
    while step < 28:
        try:
            injector.check(step)
        except SimulatedFailure:
            restarts += 1
            c = _paper(cooldown=cooldown)    # process restart: fresh plane
            restored = ckpt.restore(tree, controller=c)
            step = restored[0]
            assert c.program.phase == "recovery", \
                "restore must land back mid-recovery, not in warm-up"
            assert c.supervisor.in_cooldown, "cooldown must survive restore"
            continue
        cos = COS if c.wants_diagnostics else None
        c.observe(_t(step, losses[step], cosines=cos))
        ckpt.maybe_save(step + 1, tree, controller=c)
        step += 1

    assert restarts == 1
    kinds = [e.kind for e in c.events]
    # one admission, one recovery, one re-admission: the restart neither
    # replayed warm-up nor re-fired admission
    assert kinds == ["warmup_end", "admitted", "recovery", "readmitted"]
    assert c.plan.signature() == c._admitted_plan.signature()
    readmit_step = c.events[-1].step
    recovery_step = c.events[-2].step
    assert readmit_step - recovery_step >= cooldown, \
        "re-admission must wait out the full (restored) cooldown"


# ---------------------------------------------------------------------------
# Fabric.attach_controller surface (mesh-free checks)
# ---------------------------------------------------------------------------

def test_attach_controller_by_name_and_instance():
    fabric = Fabric()
    c = fabric.attach_controller("paper", warmup_steps=7)
    assert fabric.controller is c and c.warmup_steps == 7

    fabric2 = Fabric()
    mine = StaticController(plan_presets()["gbin_vote"])
    assert fabric2.attach_controller(mine) is mine
    with pytest.raises(TypeError, match="registered name"):
        Fabric().attach_controller(mine, warmup_steps=3)


# ---------------------------------------------------------------------------
# full-stack acceptance: all controllers drive the Trainer through the
# same attach_controller path (jax >= 0.7 runtime required)
# ---------------------------------------------------------------------------

def _trainer_bits():
    import jax
    from jax.sharding import AxisType
    from repro.data import SyntheticLMStream
    from repro.models import ModelConfig
    from repro.optim import SgdMomentum
    from repro.runtime import Trainer, TrainerConfig

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = ModelConfig(name="ctl", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", remat=False)
    return mesh, cfg, SyntheticLMStream, SgdMomentum, Trainer, TrainerConfig


def test_static_controller_bit_identical_to_legacy_plan_path():
    mesh, cfg, Stream, Sgd, Trainer, TrainerConfig = _trainer_bits()
    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                         schedule=Schedule.PACKED_A2A)

    h_legacy = Trainer(cfg, mesh, Sgd(peak_lr=0.2, total_steps=60),
                       Stream(vocab=256, seq_len=32, batch=8, seed=0),
                       plan=plan,
                       tcfg=TrainerConfig(dp_axes=("data",),
                                          log_interval=1000)).run(6)

    fabric = Fabric(mesh, ("data",))
    fabric.attach_controller("static", plan=plan)
    h_ctrl = Trainer(cfg, mesh, Sgd(peak_lr=0.2, total_steps=60),
                     Stream(vocab=256, seq_len=32, batch=8, seed=0),
                     fabric=fabric).run(6)

    assert [h["plan"] for h in h_ctrl] == [h["plan"] for h in h_legacy]
    np.testing.assert_array_equal(
        np.asarray([h["loss"] for h in h_ctrl]),
        np.asarray([h["loss"] for h in h_legacy]))
    np.testing.assert_array_equal(
        np.asarray([h["agg_norm"] for h in h_ctrl]),
        np.asarray([h["agg_norm"] for h in h_legacy]))


def test_custom_registered_controller_drives_trainer():
    """A test-registered controller flips the plan mid-run, selected
    purely by name through attach_controller — no core edits."""
    mesh, cfg, Stream, Sgd, Trainer, TrainerConfig = _trainer_bits()

    @register_controller("toy_flip")
    class FlipController:
        name = "toy_flip"
        wants_diagnostics = False

        def __init__(self, at=3):
            self.at = at
            self.plan = AdmissionPlan.fp32_all()

        def observe(self, telemetry):
            if telemetry.step + 1 >= self.at:
                self.plan = AdmissionPlan.lowbit_backbone(
                    AggregationMode.G_BINARY)
            return self.plan

    try:
        fabric = Fabric(mesh, ("data",))
        fabric.attach_controller("toy_flip", at=3)
        tr = Trainer(cfg, mesh, Sgd(peak_lr=0.1, total_steps=40),
                     Stream(vocab=256, seq_len=32, batch=8, seed=2),
                     fabric=fabric,
                     tcfg=TrainerConfig(dp_axes=("data",),
                                        log_interval=1000))
        hist = tr.run(6)
        plans = [h["plan"] for h in hist]
        assert "gbinary" not in plans[0]
        assert all("gbinary" in p for p in plans[3:])
        assert len(fabric._compiled) == 2      # one jit per plan signature
    finally:
        unregister_controller("toy_flip")


def test_trainer_controller_state_survives_failure_injector(tmp_path):
    """Satellite regression: SimulatedFailure mid-cooldown; the restored
    run must keep the Supervisor cooldown and the admitted plan."""
    mesh, cfg, Stream, Sgd, Trainer, TrainerConfig = _trainer_bits()

    class ScriptedSupervisor(Supervisor):
        """Deterministic guard: trigger at the Nth observe (telemetry is
        real training loss, which is not scriptable)."""

        def __init__(self, trigger_at, cooldown_steps):
            super().__init__(guard=CusumGuard(h=1e9),
                             cooldown_steps=cooldown_steps)
            self.trigger_at = int(trigger_at)
            self._n = 0

        def observe(self, loss):
            self._n += 1
            if self._cooldown_left > 0:
                self._cooldown_left -= 1
                return False
            if self._n == self.trigger_at:
                self._cooldown_left = self.cooldown_steps
                return True
            return False

        def state_dict(self):
            return dict(super().state_dict(), n=self._n)

        def load_state_dict(self, state):
            super().load_state_dict(state)
            self._n = int(state["n"])

    def controller():
        return PaperController(
            warmup_steps=2, commander=Commander(tau_binary=-1.0),
            supervisor=ScriptedSupervisor(trigger_at=5, cooldown_steps=8))

    def trainer(ctrl, injector=None):
        fabric = Fabric(mesh, ("data",))
        fabric.attach_controller(ctrl)
        return Trainer(cfg, mesh, Sgd(peak_lr=0.05, total_steps=100),
                       Stream(vocab=256, seq_len=32, batch=8, seed=3),
                       fabric=fabric, ckpt_dir=str(tmp_path),
                       failure_injector=injector,
                       tcfg=TrainerConfig(dp_axes=("data",),
                                          checkpoint_interval=1,
                                          log_interval=1000))

    # in-process restart path (Trainer._recover): failure at step 7, two
    # steps into the 8-step cooldown that started at step 4
    c1 = controller()
    tr = trainer(c1, injector=FailureInjector(at_steps=(7,)))
    tr.run(16)
    assert tr.restarts == 1
    kinds = [e.kind for e in c1.events]
    assert kinds == ["warmup_end", "admitted", "recovery", "readmitted"], \
        f"restart corrupted the control sequence: {kinds}"

    # process-restart path: a FRESH controller + Trainer on the same
    # checkpoint dir resumes mid-stream instead of re-warming up
    c2 = controller()
    tr2 = trainer(c2)
    tr2.run(20)
    kinds2 = [e.kind for e in c2.events]
    assert kinds2 == ["warmup_end", "admitted", "recovery", "readmitted"]
    # restored log, not re-fired: admission predates the checkpoint
    assert c2.events[1].step < 16 and c2.events[1].step == c1.events[1].step
    assert c2.plan.signature() == c1.plan.signature()
    assert "gbinary" in c2.plan.signature()

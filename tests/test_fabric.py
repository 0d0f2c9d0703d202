"""Fabric session + schedule-backend registry.

Covers the registry contract (round-trip, unknown-name error, duplicate
protection), bit-for-bit equivalence of ``Fabric.aggregate`` with the
per-leaf reference ``aggregate_tree`` on a mixed plan, EF spec
construction, wire-byte accounting through backends, and — the extension
seam the registry exists for — training with a custom schedule that was
registered without modifying any core file.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdmissionPlan, AggregationMode, GroupPolicy,
                        Schedule, init_ef_states, resolve_policies,
                        wire_bytes_per_device)
from repro.fabric import (AggregationContext, Fabric, ScheduleBackend,
                          aggregate_tree, available_schedules, get_schedule,
                          register_schedule, unregister_schedule)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

def test_builtin_schedules_registered():
    names = available_schedules()
    for expected in ("psum", "fp32", "vote_psum", "packed_a2a",
                     "majority_sign_sgd", "sign_of_mean"):
        assert expected in names
    # enum and string keys resolve to the same backend
    assert get_schedule(Schedule.VOTE_PSUM) is get_schedule("vote_psum")
    assert get_schedule("fp32") is get_schedule(Schedule.PSUM)
    assert isinstance(get_schedule("packed_a2a"), ScheduleBackend)


def test_register_schedule_roundtrip():
    @register_schedule("toy_roundtrip")
    class ToyBackend:
        name = "toy_roundtrip"

        def aggregate(self, ctx, g, policy, ef=None):
            return g, ef

    try:
        backend = get_schedule("toy_roundtrip")
        assert isinstance(backend, ToyBackend)
        assert "toy_roundtrip" in available_schedules()
    finally:
        unregister_schedule("toy_roundtrip")
    assert "toy_roundtrip" not in available_schedules()


def test_unknown_schedule_raises_clear_error():
    with pytest.raises(KeyError, match="unknown schedule backend 'nope'"):
        get_schedule("nope")
    # the error names the registration hook
    with pytest.raises(KeyError, match="register_schedule"):
        get_schedule("nope")


def test_duplicate_registration_raises_unless_override():
    with pytest.raises(ValueError, match="already registered"):
        @register_schedule("vote_psum")
        class Clash:
            name = "vote_psum"

            def aggregate(self, ctx, g, policy, ef=None):
                return g, ef

    # override=True replaces and can be restored
    original = get_schedule("sign_of_mean")

    @register_schedule("sign_of_mean", override=True)
    class Replacement:
        name = "sign_of_mean"

        def aggregate(self, ctx, g, policy, ef=None):
            return g, ef

    try:
        assert isinstance(get_schedule("sign_of_mean"), Replacement)
    finally:
        register_schedule("sign_of_mean", override=True)(original)
    assert get_schedule("sign_of_mean") is original


# ---------------------------------------------------------------------------
# Fabric.aggregate equivalence with the legacy free functions
# ---------------------------------------------------------------------------

def _mixed_plan(error_feedback: bool = False) -> AdmissionPlan:
    return AdmissionPlan.from_dict(
        {"backbone": GroupPolicy(AggregationMode.G_BINARY,
                                 error_feedback=error_feedback),
         "embed": GroupPolicy(AggregationMode.G_TERNARY)},
        default=GroupPolicy(AggregationMode.FP32))


def _params(rng):
    return {"backbone": {"w1": jnp.asarray(rng.randn(64, 64), jnp.float32),
                         "w2": jnp.asarray(rng.randn(64, 32), jnp.float32)},
            "embed": {"table": jnp.asarray(rng.randn(128, 16), jnp.float32)},
            "head": {"w": jnp.asarray(rng.randn(32, 8), jnp.float32)}}


def test_fabric_aggregate_matches_legacy_bitwise(rng):
    grads = _params(rng)
    plan = _mixed_plan()
    policies = resolve_policies(grads, plan)

    want, want_ef = aggregate_tree(AggregationContext(), grads, policies)
    got, got_ef = Fabric().aggregate(grads, plan)

    for path in (("backbone", "w1"), ("backbone", "w2"), ("embed", "table"),
                 ("head", "w")):
        w, g = want[path[0]][path[1]], got[path[0]][path[1]]
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    assert want_ef is None and got_ef is None
    # sanity: the three modes actually produced three behaviours
    assert set(np.unique(np.asarray(got["backbone"]["w1"]))) <= {-1.0, 1.0}
    assert 0.0 in np.unique(np.asarray(got["embed"]["table"]))
    np.testing.assert_array_equal(np.asarray(got["head"]["w"]),
                                  np.asarray(grads["head"]["w"]))


def test_fabric_aggregate_matches_legacy_with_error_feedback(rng):
    grads = _params(rng)
    plan = _mixed_plan(error_feedback=True)
    policies = resolve_policies(grads, plan)
    ef = init_ef_states(grads, policies)

    want, want_ef = aggregate_tree(AggregationContext(), grads, policies,
                                   ef_states=ef)
    got, got_ef = Fabric().aggregate(grads, plan, ef=ef)

    np.testing.assert_array_equal(np.asarray(want["backbone"]["w1"]),
                                  np.asarray(got["backbone"]["w1"]))
    np.testing.assert_array_equal(np.asarray(want_ef["backbone"]["w1"]),
                                  np.asarray(got_ef["backbone"]["w1"]))
    assert got_ef["backbone"]["w1"].shape == (1, 64, 64)
    assert got_ef["head"]["w"].shape == ()           # sentinel untouched
    assert float(jnp.sum(jnp.abs(got_ef["backbone"]["w1"]))) > 0


def test_fabric_resolve_and_aggregate_accept_policy_tree(rng):
    grads = _params(rng)
    fabric = Fabric()
    policies = fabric.resolve(grads, _mixed_plan())
    via_plan, _ = fabric.aggregate(grads, _mixed_plan())
    via_tree, _ = fabric.aggregate(grads, policies)
    np.testing.assert_array_equal(np.asarray(via_plan["backbone"]["w1"]),
                                  np.asarray(via_tree["backbone"]["w1"]))


def test_fabric_ef_specs_single_implementation(rng):
    from jax.sharding import PartitionSpec as P
    params = _params(rng)
    fabric = Fabric(dp_axes=("pod", "data"), num_workers=4)
    policies = fabric.resolve(params, _mixed_plan(error_feedback=True))
    pspecs = jax.tree.map(lambda _: None, params)
    specs = fabric.ef_specs(policies, pspecs)
    assert specs["backbone"]["w1"] == P(("pod", "data"))   # EF on: DP-sharded
    assert specs["head"]["w"] == P()                       # EF off: sentinel
    ef = fabric.init_ef(params, policies)
    assert ef["backbone"]["w1"].shape == (4, 64, 64)       # leading W dim
    assert ef["head"]["w"].shape == ()


def test_wire_schedule_bypass_only_for_lowbit_only_schedules(rng):
    """FP32 buckets on vote_psum/packed_a2a ride psum; FP32 buckets on a
    named backend (e.g. the sign_of_mean baseline) dispatch as named."""
    from repro.core import wire_schedule
    assert wire_schedule(AggregationMode.FP32, Schedule.PACKED_A2A) \
        == Schedule.PSUM
    assert wire_schedule(AggregationMode.FP32, Schedule.VOTE_PSUM) \
        == Schedule.PSUM
    assert wire_schedule(AggregationMode.FP32, "sign_of_mean") \
        == "sign_of_mean"
    assert wire_schedule(AggregationMode.G_BINARY, Schedule.PACKED_A2A) \
        == Schedule.PACKED_A2A

    # a low-bit mode nominally on psum rides the dense vote path, exactly
    # as the pre-registry dispatch did — never the FP32 mean
    assert wire_schedule(AggregationMode.G_BINARY, Schedule.PSUM) \
        == Schedule.VOTE_PSUM

    g = {"backbone": {"w": jnp.asarray(rng.randn(64), jnp.float32)}}
    plan = AdmissionPlan.lowbit_all(AggregationMode.FP32,
                                    schedule="sign_of_mean")
    agg, _ = Fabric().aggregate(g, plan)
    np.testing.assert_array_equal(np.asarray(agg["backbone"]["w"]),
                                  np.sign(np.asarray(g["backbone"]["w"])))

    lb_plan = AdmissionPlan.lowbit_all(AggregationMode.G_BINARY,
                                       schedule=Schedule.PSUM)
    lb_agg, _ = Fabric().aggregate(g, lb_plan)
    np.testing.assert_array_equal(np.asarray(lb_agg["backbone"]["w"]),
                                  np.sign(np.asarray(g["backbone"]["w"])))


def test_alias_clash_leaves_registry_unchanged():
    """A clash on any alias must not half-register the earlier names."""
    with pytest.raises(ValueError, match="already registered"):
        @register_schedule("toy_fresh_name", "vote_psum")
        class Clash:
            name = "toy_fresh_name"

            def aggregate(self, ctx, g, policy, ef=None):
                return g, ef

    assert "toy_fresh_name" not in available_schedules()


# ---------------------------------------------------------------------------
# wire-byte accounting through backends
# ---------------------------------------------------------------------------

def test_wire_bytes_resolve_through_registry():
    n, w = 1 << 20, 8
    assert (wire_bytes_per_device(n, AggregationMode.G_BINARY,
                                  "majority_sign_sgd", w)
            == wire_bytes_per_device(n, AggregationMode.G_BINARY,
                                     Schedule.VOTE_PSUM, w))

    @register_schedule("toy_no_wire_model")
    class NoWire:
        name = "toy_no_wire_model"

        def aggregate(self, ctx, g, policy, ef=None):
            return g, ef

    try:
        with pytest.raises(ValueError, match="wire-byte model"):
            wire_bytes_per_device(n, AggregationMode.G_BINARY,
                                  "toy_no_wire_model", w)
    finally:
        unregister_schedule("toy_no_wire_model")


# ---------------------------------------------------------------------------
# step-builder fixes: grad_accum divisibility + optimizer introspection
# ---------------------------------------------------------------------------

def test_split_microbatches_raises_on_indivisible_batch():
    """The old reshape silently dropped trailing samples when grad_accum
    did not divide the per-device batch; it must raise at trace time."""
    from repro.fabric.session import _split_microbatches

    batch = {"x": jnp.zeros((8, 4)), "y": jnp.zeros((8,))}
    micro = _split_microbatches(batch, 4)
    assert micro["x"].shape == (4, 2, 4) and micro["y"].shape == (4, 2)

    bad = {"x": jnp.zeros((10, 4)), "y": jnp.zeros((10,))}
    with pytest.raises(ValueError, match=r"grad_accum=4 must divide"):
        _split_microbatches(bad, 4)
    # the error names the offending shape
    with pytest.raises(ValueError, match=r"\(10, 4\)"):
        _split_microbatches(bad, 4)


def test_opt_shardings_detect_nu_by_state_not_class_name():
    """AdamW subclasses / custom adaptive optimizers must get a nu
    sharding tree; SGD-family must not — detected from the actual init
    state, never the class name."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.fabric.session import _opt_shardings, _optimizer_has_nu
    from repro.optim import AdamW, SgdMomentum
    from repro.optim.optimizers import OptState

    class RenamedAdamW(AdamW):          # name check would miss this
        pass

    class DuckAdaptive:                 # no Optimizer base at all
        def init(self, params):
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape), params)
            return OptState(step=jnp.zeros((), jnp.int32), mu=zeros,
                            nu=zeros)

    assert AdamW().has_nu and RenamedAdamW().has_nu
    assert not SgdMomentum().has_nu
    assert _optimizer_has_nu(DuckAdaptive())

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    mu_sh = {"w": NamedSharding(mesh, P())}
    assert _opt_shardings(RenamedAdamW(), mu_sh, mesh).nu is mu_sh
    assert _opt_shardings(SgdMomentum(), mu_sh, mesh).nu is None


# ---------------------------------------------------------------------------
# the extension seam: custom schedules train without touching core files
# ---------------------------------------------------------------------------

def test_custom_schedule_trains_one_step(rng):
    """A toy registered schedule drives one full training step.

    The backend scales the mean gradient — distinguishable bit-for-bit
    from every built-in — and is selected purely by name through the
    plan, proving admission -> policy -> registry dispatch needs no core
    edits.
    """
    @register_schedule("toy_halfmean")
    class HalfMean:
        name = "toy_halfmean"

        def aggregate(self, ctx, g, policy, ef=None):
            return 0.5 * jax.lax.pmean(g.astype(jnp.float32),
                                       ctx.dp_axes).astype(g.dtype), ef

    try:
        fabric = Fabric()
        plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                             schedule="toy_halfmean")
        assert "toy_halfmean" in plan.signature()

        params = {"backbone": {"w": jnp.asarray(rng.randn(16, 4),
                                                jnp.float32)},
                  "head": {"w": jnp.asarray(rng.randn(4, 2), jnp.float32)}}
        x = jnp.asarray(rng.randn(32, 16), jnp.float32)

        def loss_fn(p):
            h = jnp.tanh(x @ p["backbone"]["w"])
            return jnp.mean((h @ p["head"]["w"]) ** 2)

        loss0, grads = jax.value_and_grad(loss_fn)(params)
        agg, _ = fabric.aggregate(grads, plan)
        # custom backend applied to the backbone, FP32 psum to the head
        np.testing.assert_array_equal(np.asarray(agg["backbone"]["w"]),
                                      0.5 * np.asarray(grads["backbone"]["w"]))
        np.testing.assert_array_equal(np.asarray(agg["head"]["w"]),
                                      np.asarray(grads["head"]["w"]))
        new_params = jax.tree.map(lambda p, a: p - 0.1 * a, params, agg)
        assert float(loss_fn(new_params)) < float(loss0)
    finally:
        unregister_schedule("toy_halfmean")


@pytest.mark.slow
def test_custom_schedule_trains_via_trainer_on_mesh():
    """Full stack: a registered toy schedule drives the Trainer on a real
    (simulated-device) mesh, selected only by its plan name."""
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {SRC!r})

    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.core import AdmissionPlan, AggregationMode
    from repro.data import SyntheticLMStream
    from repro.fabric import Fabric, register_schedule
    from repro.models import ModelConfig
    from repro.optim import SgdMomentum
    from repro.runtime import Trainer, TrainerConfig

    @register_schedule("toy_signmean")
    class SignMean:
        name = "toy_signmean"
        def aggregate(self, ctx, g, policy, ef=None):
            return jnp.sign(jax.lax.pmean(g, ctx.dp_axes)), ef

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", remat=False)
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=16, seed=0)
    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                         schedule="toy_signmean")
    tr = Trainer(cfg, mesh, SgdMomentum(peak_lr=1e-3), data, plan=plan,
                 fabric=Fabric(mesh, dp_axes=("data",)),
                 tcfg=TrainerConfig(dp_axes=("data",), log_interval=1000))
    h = tr.run(2)
    assert len(h) == 2 and "toy_signmean" in h[-1]["plan"]
    print("CUSTOM_SCHEDULE_TRAINED", h[0]["loss"], h[-1]["loss"])
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "CUSTOM_SCHEDULE_TRAINED" in r.stdout

"""Hierarchical hop-plan collectives: the hop-aware aggregation stack.

The hierarchy contract: a 1-hop :class:`HopPlan` is *bit-identical* to
the flat backend of its single codec (per-leaf and fused, EF on and
off); a multi-hop plan composes each hop's encode -> reduce -> decode
over its own worker group (validated against a nested-vmap oracle); the
per-hop wire legs from ``hop_wire_bytes_per_device`` sum to the route
total and each leg is priced by the hop backend's own ring model; and
the sim's ``multihop`` topology replays hierarchical launches leg by
leg, agreeing with the analytic :class:`MultiHopModel` within 1% on
degenerate single-launch cases.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdmissionPlan, Commander, IciModel, MultiHopModel,
                        codec_name, hop_wire_bytes_per_device,
                        init_ef_states, modeled_layout_comm_time,
                        modeled_layout_multihop_time, plan_buckets,
                        resolve_policies, schedule_name,
                        wire_bytes_per_device, wire_schedule)
from repro.fabric import (Fabric, HopPlan, HopSpec, get_codec,
                          plan_presets, register_hop_plan,
                          unregister_hop_plan)
from repro.sim import LaunchSpec, layout_launch_specs, simulate_launches

#: sim-vs-analytic tolerance, same contract as tests/test_sim.py
REL_TOL = 0.01

#: the built-in flat codecs every 1-hop plan must be bit-identical to
FLAT_CODECS = ["fp32", "gbinary", "gternary", "int4"]


def _tree_equal(a, b):
    flags = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))), a, b)
    return all(jax.tree.leaves(flags))


def _grads(rng, w=None):
    mk = (lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)) if w is None \
        else (lambda *s: jnp.asarray(rng.randn(w, *s), jnp.float32))
    return {"backbone": {"w1": mk(40, 33), "w2": mk(257), "w3": mk(64, 8)},
            "embed": {"table": mk(130, 7)},
            "head": {"w": mk(17)},
            "norms": {"scale": mk(33)}}


def _default_wire_schedule(mode):
    return wire_schedule(mode, get_codec(mode).default_schedule)


# ---------------------------------------------------------------------------
# plan construction + group sizing
# ---------------------------------------------------------------------------

def test_hop_plan_validation():
    with pytest.raises(ValueError):
        HopPlan("bad_empty", ())
    with pytest.raises(ValueError):            # two remainder hops
        HopPlan("bad_two_rem", (HopSpec("fp32"), HopSpec("gbinary")))
    with pytest.raises(ValueError):
        HopSpec("fp32", workers=0)
    with pytest.raises(ValueError):            # hop plans do not nest
        register_hop_plan(HopPlan("bad_nested",
                                  (HopSpec("hier_fp32_gbinary"),)))


def test_group_sizes_clamp_divide_and_remainder():
    builtin = get_codec("hier_fp32_gbinary").plan
    assert builtin.group_sizes(32) == (8, 4)
    assert builtin.group_sizes(4) == (4, 1)    # intra hop clamps to W
    assert builtin.group_sizes(1) == (1, 1)
    odd = HopPlan("odd", (HopSpec("fp32", workers=3), HopSpec("gbinary")))
    with pytest.raises(ValueError):            # 3 does not divide 8
        odd.group_sizes(8)
    short = HopPlan("short", (HopSpec("fp32", workers=2),))
    with pytest.raises(ValueError):            # no remainder hop for the rest
        short.group_sizes(8)


def test_signature_is_stable_route_identity():
    plan = HopPlan("x", (HopSpec("fp32", workers=8),
                         HopSpec("gbinary", schedule="vote_psum")))
    assert plan.signature() == "x[fp32:8>gbinary:*@vote_psum]"
    assert get_codec("hier_fp32_gbinary").hop_signature == \
        "hier_fp32_gbinary[fp32:8>gbinary:*]"


def test_hier_codec_contract_delegates_to_hops():
    c = get_codec("hier_fp32_gternary")
    assert c.reduction == "hierarchical"
    assert schedule_name(c.default_schedule) == "hierarchical"
    assert c.bits_per_element == get_codec("gternary").bits_per_element
    assert c.lane == get_codec("gternary").lane
    assert c.gated == get_codec("gternary").gated
    assert c.threads_ef
    # every flat schedule a policy could name routes to the hier backend
    for sched in ("psum", "vote_psum", "packed_a2a"):
        assert wire_schedule("hier_fp32_gternary", sched) == "hierarchical"


# ---------------------------------------------------------------------------
# 1-hop plan == flat backend (bit-identical, per-leaf and fused)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", FLAT_CODECS)
@pytest.mark.parametrize("fused", [False, True])
def test_one_hop_plan_matches_flat_backend(rng, aggregate, mode, fused):
    w, name = 4, f"hier1_{mode}"
    gs = _grads(rng, w=w)
    register_hop_plan(HopPlan(name, (HopSpec(mode),)))
    try:
        fabric = Fabric(dp_axes=("w",), num_workers=w)

        def run(plan):
            def one(g):
                return aggregate(fabric, g, plan, fused=fused)[0]
            return jax.vmap(one, axis_name="w")(gs)

        flat = run(AdmissionPlan.lowbit_all(mode))
        hier = run(AdmissionPlan.lowbit_all(name))
        assert _tree_equal(flat, hier)
    finally:
        unregister_hop_plan(name)


@pytest.mark.parametrize("mode", ["gbinary", "gternary"])
@pytest.mark.parametrize("fused", [False, True])
def test_one_hop_plan_matches_flat_backend_with_ef(rng, aggregate, mode,
                                                   fused):
    w, name = 4, f"hier1ef_{mode}"
    gs = _grads(rng, w=w)
    register_hop_plan(HopPlan(name, (HopSpec(mode),)))
    try:
        fabric = Fabric(dp_axes=("w",), num_workers=w)
        g0 = jax.tree.map(lambda x: x[0], gs)
        flat_plan = AdmissionPlan.lowbit_all(mode, error_feedback=True)
        hier_plan = AdmissionPlan.lowbit_all(name, error_feedback=True)
        ef0 = init_ef_states(g0, resolve_policies(g0, flat_plan))
        efs = jax.tree.map(
            lambda e: jnp.asarray(rng.randn(w, *e.shape), e.dtype), ef0)

        def run(plan):
            def one(g, e):
                return aggregate(fabric, g, plan, ef=e, fused=fused)
            return jax.vmap(one, axis_name="w")(gs, efs)

        flat, flat_ef = run(flat_plan)
        hier, hier_ef = run(hier_plan)
        assert _tree_equal(flat, hier)
        assert _tree_equal(flat_ef, hier_ef)   # EF residuals identical too
    finally:
        unregister_hop_plan(name)


# ---------------------------------------------------------------------------
# multi-hop semantics (nested virtual-worker mesh)
# ---------------------------------------------------------------------------

def _hier_2x2_plan():
    # intra group sized to the inner axis of the 2x2 test mesh
    return HopPlan("hier_test_2x2", (HopSpec("fp32", workers=2),
                                     HopSpec("gbinary")))


def test_two_hop_plan_matches_nested_vmap_oracle(rng, aggregate):
    """Hop 0 = fp32 mean over the *inner* axis, hop 1 = gbinary vote
    over the outer axis: exactly sign(sum_outer(sign(mean_inner(g))))."""
    outer, inner = 2, 2
    gs = jnp.asarray(rng.randn(outer, inner, 64), jnp.float32)
    register_hop_plan(_hier_2x2_plan())
    try:
        fabric = Fabric(dp_axes=("outer", "inner"),
                        num_workers=outer * inner)
        plan = AdmissionPlan.lowbit_all("hier_test_2x2")

        def one(g):
            return aggregate(fabric, {"p": g}, plan, fused=False)[0]["p"]
        got = jax.vmap(jax.vmap(one, axis_name="inner"),
                       axis_name="outer")(gs)
        want = jnp.sign(jnp.sign(jnp.mean(gs, axis=1)).sum(axis=0))
        assert _tree_equal(got[0, 0], want)
        # every worker sees the same aggregate
        assert _tree_equal(got, jnp.broadcast_to(want, got.shape))
    finally:
        unregister_hop_plan("hier_test_2x2")


@pytest.mark.parametrize("error_feedback", [False, True])
def test_two_hop_fused_matches_per_leaf(rng, aggregate, error_feedback):
    outer, inner = 2, 2
    w = outer * inner
    gs = jax.tree.map(
        lambda x: jnp.reshape(x, (outer, inner) + x.shape[1:]),
        _grads(rng, w=w))
    register_hop_plan(_hier_2x2_plan())
    try:
        fabric = Fabric(dp_axes=("outer", "inner"), num_workers=w)
        plan = AdmissionPlan.lowbit_all("hier_test_2x2",
                                        error_feedback=error_feedback)
        g0 = jax.tree.map(lambda x: x[0, 0], gs)
        ef0 = init_ef_states(g0, resolve_policies(g0, plan))
        efs = jax.tree.map(
            lambda e: jnp.asarray(rng.randn(outer, inner, *e.shape),
                                  e.dtype), ef0)

        def run(fused):
            def one(g, e):
                return aggregate(fabric, g, plan,
                                 ef=(e if error_feedback else None),
                                 fused=fused)
            return jax.vmap(jax.vmap(one, axis_name="inner"),
                            axis_name="outer")(gs, efs)

        want, want_ef = run(False)
        got, got_ef = run(True)
        assert _tree_equal(want, got)
        assert _tree_equal(want_ef, got_ef)
    finally:
        unregister_hop_plan("hier_test_2x2")


def test_multi_hop_plan_requires_matching_axes(rng):
    """A 2-hop plan on a multi-worker session with one dp axis cannot
    place its hops; the backend must refuse, not silently mis-group."""
    w = 4
    gs = _grads(rng, w=w)
    fabric = Fabric(dp_axes=("w",), num_workers=w)
    plan = AdmissionPlan.lowbit_all("hier_fp32_gbinary")
    with pytest.raises(ValueError):
        jax.vmap(lambda g: fabric.aggregate(g, plan)[0],
                 axis_name="w")(gs)


def test_host_local_hier_matches_flat_backbone(rng):
    """With no dp axes every hop degenerates to its local encode/decode
    round-trip, so the route equals its backbone codec alone."""
    grads = _grads(rng)
    fabric = Fabric()
    a, _ = fabric.aggregate(grads,
                            AdmissionPlan.lowbit_all("hier_fp32_gbinary"))
    b, _ = fabric.aggregate(grads, AdmissionPlan.lowbit_all("gbinary"))
    assert _tree_equal(a, b)


# ---------------------------------------------------------------------------
# per-hop traffic accounting
# ---------------------------------------------------------------------------

def test_flat_codecs_report_a_single_leg():
    n = 1 << 16
    for mode in FLAT_CODECS:
        sched = _default_wire_schedule(mode)
        for w in (1, 4, 32):
            legs = hop_wire_bytes_per_device(n, mode, sched, w)
            assert len(legs) == 1
            assert legs[0] == wire_bytes_per_device(n, mode, sched, w)


def test_hier_legs_priced_by_each_hop_backend():
    n, w = 1000, 32
    legs = hop_wire_bytes_per_device(n, "hier_fp32_gbinary",
                                     "hierarchical", w)
    assert legs == (wire_bytes_per_device(n, "fp32",
                                          _default_wire_schedule("fp32"), 8),
                    wire_bytes_per_device(n, "gbinary",
                                          _default_wire_schedule("gbinary"),
                                          4))
    # the route total IS the sum of its legs
    assert sum(legs) == wire_bytes_per_device(n, "hier_fp32_gbinary",
                                              "hierarchical", w)


def test_hier_backbone_leg_beats_flat_backbone_total():
    """The paper-style win: after the intra-node FP32 stage only 1/8 of
    the workers vote across the backbone, so the inter-node leg carries
    fewer bytes than the flat single-codec collective at full width."""
    n, w = 1 << 20, 32
    legs = hop_wire_bytes_per_device(n, "hier_fp32_gbinary",
                                     "hierarchical", w)
    flat = wire_bytes_per_device(n, "gbinary",
                                 _default_wire_schedule("gbinary"), w)
    assert legs[-1] < flat


def test_hop_wire_bytes_property_for_every_codec_pair():
    pytest.importorskip("hypothesis",
                        reason="optional test dependency (pip install .[test])")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=60, deadline=None)
    @given(intra=st.sampled_from(FLAT_CODECS),
           backbone=st.sampled_from(FLAT_CODECS),
           intra_w=st.sampled_from([2, 4, 8]),
           w=st.sampled_from([2, 4, 8, 16, 32]),
           n=st.integers(min_value=1, max_value=1 << 16))
    def per_hop_legs_sum_to_route_total(intra, backbone, intra_w, w, n):
        plan = HopPlan("hier_prop_tmp",
                       (HopSpec(intra, workers=intra_w), HopSpec(backbone)))
        register_hop_plan(plan, override=True)
        try:
            legs = hop_wire_bytes_per_device(n, "hier_prop_tmp",
                                             "hierarchical", w)
            sizes = plan.group_sizes(w)
            assert len(legs) == len(plan.hops)
            for leg, hop, s in zip(legs, plan.hops, sizes):
                assert leg == wire_bytes_per_device(
                    n, hop.codec, _default_wire_schedule(hop.codec), s)
            assert sum(legs) == wire_bytes_per_device(
                n, "hier_prop_tmp", "hierarchical", w)
        finally:
            unregister_hop_plan("hier_prop_tmp")

    per_hop_legs_sum_to_route_total()


def test_layout_comm_time_sums_per_hop_legs():
    w = 32
    params = {"p": jax.ShapeDtypeStruct((1 << 16,), "float32")}
    plan = AdmissionPlan.lowbit_all("hier_fp32_gbinary")
    layout = plan_buckets(params, resolve_policies(params, plan))
    assert layout.num_launches == 1
    legs = hop_wire_bytes_per_device(1 << 16, "hier_fp32_gbinary",
                                     "hierarchical", w)
    ici = IciModel()
    assert modeled_layout_comm_time(layout, w, ici) == pytest.approx(
        ici.collective_time(sum(legs), w, num_launches=1))


# ---------------------------------------------------------------------------
# bucket identity: routes never mix
# ---------------------------------------------------------------------------

def test_bucket_key_carries_hop_signature(rng):
    grads = _grads(rng)
    fabric = Fabric()
    plan = AdmissionPlan.lowbit_backbone("hier_fp32_gbinary")
    layout = fabric.layout_for(grads, plan)
    hops = {b.key.mode: b.key.hops for b in layout.buckets}
    assert hops["hier_fp32_gbinary"] == \
        "hier_fp32_gbinary[fp32:8>gbinary:*]"
    assert hops["fp32"] is None               # flat codecs carry no route


def test_layout_cache_invalidated_when_hop_plan_swapped(rng):
    grads = _grads(rng)
    fabric = Fabric()
    plan = AdmissionPlan.lowbit_all("hier_swap")
    register_hop_plan(HopPlan("hier_swap", (HopSpec("gbinary"),)))
    try:
        lay1 = fabric.layout_for(grads, plan)
        assert lay1.buckets[0].key.hops == "hier_swap[gbinary:*]"
        register_hop_plan(HopPlan("hier_swap", (HopSpec("fp32", workers=2),
                                                HopSpec("gbinary"))),
                          override=True)
        lay2 = fabric.layout_for(grads, plan)
        assert lay2.buckets[0].key.hops == \
            "hier_swap[fp32:2>gbinary:*]"
    finally:
        unregister_hop_plan("hier_swap")


# ---------------------------------------------------------------------------
# control surface: presets + admission ladder
# ---------------------------------------------------------------------------

def test_hier_presets_registered():
    presets = plan_presets(error_feedback=True)
    for name in ("hier_fp32_gbinary", "hier_fp32_gternary",
                 "hier_fp32_int4"):
        pol = presets[name].policy_for("backbone")
        assert codec_name(pol.mode) == name
        assert schedule_name(pol.resolved_schedule()) == "hierarchical"
        # head stays FP32 — hier presets are backbone plans
        assert codec_name(presets[name].policy_for("head").mode) == "fp32"
    assert presets["hier_fp32_gbinary"].policy_for("backbone").error_feedback
    # int4 backbone pins EF off, like the flat int4_backbone preset
    assert not presets["hier_fp32_int4"].policy_for("backbone").error_feedback


def test_commander_ladder_admits_hier_modes():
    cmd = Commander(binary_mode="hier_fp32_gbinary",
                    ternary_mode="hier_fp32_gternary",
                    tau_binary=0.5, tau_ternary=0.2)
    plan = cmd.propose({"backbone": {"gbinary": 0.9},
                        "embed": {"gbinary": 0.3, "gternary": 0.4},
                        "norms": {"gbinary": 0.9}})
    assert codec_name(plan.policy_for("backbone").mode) == \
        "hier_fp32_gbinary"
    assert codec_name(plan.policy_for("embed").mode) == \
        "hier_fp32_gternary"
    assert codec_name(plan.policy_for("norms").mode) == "fp32"


# ---------------------------------------------------------------------------
# sim: multihop replays hierarchical routes leg by leg
# ---------------------------------------------------------------------------

def test_multihop_sim_matches_analytic_model_single_launch():
    """Degenerate single-launch, queue-free replay must agree with
    MultiHopModel.route_time within the 1% sim-validation tolerance."""
    n, w = 1 << 20, 32
    legs = hop_wire_bytes_per_device(n, "hier_fp32_gbinary",
                                     "hierarchical", w)
    spec = LaunchSpec("b", "hier_fp32_gbinary", "hierarchical", n,
                      float(sum(legs)), hop_bytes=tuple(legs))
    rep = simulate_launches([spec], w, topology="multihop", datapath=None)
    launch = rep.launches[0]
    assert launch.links == ("hop0", "hop1")
    ref = MultiHopModel().route_time(legs, num_launches=1)
    assert launch.collective_s == pytest.approx(ref, rel=REL_TOL)


def test_layout_specs_carry_hop_bytes_and_match_layout_model():
    w = 32
    params = {"p": jax.ShapeDtypeStruct((1 << 16,), "float32")}
    plan = AdmissionPlan.lowbit_all("hier_fp32_gbinary")
    layout = plan_buckets(params, resolve_policies(params, plan))
    specs = layout_launch_specs(layout, w)
    assert len(specs) == 1
    legs = hop_wire_bytes_per_device(1 << 16, "hier_fp32_gbinary",
                                     "hierarchical", w)
    assert specs[0].hop_bytes == tuple(legs)
    assert specs[0].wire_bytes == pytest.approx(sum(legs))
    rep = simulate_launches(specs, w, topology="multihop", datapath=None)
    ref = modeled_layout_multihop_time(layout, w)
    assert rep.launches[0].collective_s == pytest.approx(ref, rel=REL_TOL)


def test_flat_launch_specs_do_not_grow_hop_bytes(rng):
    """Flat codecs keep hop_bytes=None so the multihop topology applies
    its own per-stage payload profile exactly as before this refactor."""
    grads = _grads(rng)
    plan = AdmissionPlan.lowbit_all("gbinary")
    layout = plan_buckets(grads, resolve_policies(grads, plan))
    for spec in layout_launch_specs(layout, 8):
        assert spec.hop_bytes is None


def test_fabric_simulate_multihop_reports_per_hop_links():
    fabric = Fabric(dp_axes=("w",), num_workers=32)
    params = {"backbone": {"w1": jax.ShapeDtypeStruct((4096,), "float32")}}
    plan = AdmissionPlan.lowbit_all("hier_fp32_gbinary")
    rep = fabric.simulate(params, plan, topology="multihop")
    assert {"hop0", "hop1"} <= set(rep.link_utilization)

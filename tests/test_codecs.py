"""Gradient-codec registry: the representation axis behind the fabric.

Covers the registry contract (round-trip, duplicate protection, clear
unknown-name error, parameterized instances), bit-for-bit equivalence of
the codec-dispatched built-ins with the direct core collectives (the
pre-redesign paths) on per-leaf and fused routes, the normalized
``wire_schedule`` over the full codec x schedule grid, the
``AggregationMode`` deprecation shims, and — the seam this PR exists
for — a codec registered *outside* ``repro.fabric.codecs`` flowing
through the fused bucket path, the traffic model, the simulator, and a
compiled train step with zero edits to schedule backends or sim lanes.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdmissionPlan, AggregationMode, GroupPolicy,
                        Schedule, bits_per_element, codec_name,
                        canonical_mode, group_sizes, plan_traffic_ratio,
                        resolve_policies, wire_bytes_per_device,
                        wire_schedule)
from repro.core.lowbit import fp32_allreduce
from repro.fabric import (Codec, Fabric, GradientCodec, available_codecs,
                          get_codec, plan_presets, register_codec,
                          unregister_codec)
from repro.fabric.extra_codecs import Int4Codec, TopKCodec


def _tree_equal(a, b):
    flags = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))), a, b)
    return all(jax.tree.leaves(flags))


def _grads(rng):
    return {"backbone": {"w1": jnp.asarray(rng.randn(40, 33), jnp.float32),
                         "w2": jnp.asarray(rng.randn(257), jnp.float32)},
            "embed": {"table": jnp.asarray(rng.randn(130, 7), jnp.float32)},
            "head": {"w": jnp.asarray(rng.randn(17), jnp.float32)}}


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

def test_builtin_codecs_registered():
    names = available_codecs()
    for expected in ("identity", "fp32", "gbinary", "gternary",
                     "int4", "topk"):
        assert expected in names
    # enum and string keys resolve to the same codec
    assert get_codec(AggregationMode.G_BINARY) is get_codec("gbinary")
    assert isinstance(get_codec("gternary"), Codec)
    # the paper's Table 2 payload figures live on the codecs
    assert get_codec("gbinary").bits_per_element == 1.0
    assert get_codec("gternary").bits_per_element == pytest.approx(
        np.log2(3.0))
    assert get_codec("fp32").bits_per_element == 32.0
    assert get_codec("int4").bits_per_element == 4.0


def test_register_codec_roundtrip_and_duplicate():
    @register_codec("toy_codec")
    class Toy(GradientCodec):
        name = "toy_codec"
        bits_per_element = 8.0

    try:
        assert isinstance(get_codec("toy_codec"), Toy)
        with pytest.raises(ValueError, match="already registered"):
            register_codec("toy_codec")(Toy)
        # a clash on any alias must not half-register the fresh name
        with pytest.raises(ValueError, match="already registered"):
            register_codec("toy_fresh", "toy_codec")(Toy)
        assert "toy_fresh" not in available_codecs()
    finally:
        unregister_codec("toy_codec")
    assert "toy_codec" not in available_codecs()


def test_override_registration_sweeps_stale_aliases():
    """Overriding a name must not leave other aliases resolving the
    replaced instance — a plan naming the alias would silently use the
    old codec."""
    @register_codec("ov_main", "ov_alias")
    class A(GradientCodec):
        name = "ov_main"
        bits_per_element = 8.0

    try:
        @register_codec("ov_main", override=True)
        class B(GradientCodec):
            name = "ov_main"
            bits_per_element = 4.0

        assert get_codec("ov_main").bits_per_element == 4.0
        assert "ov_alias" not in available_codecs()   # stale alias swept
    finally:
        unregister_codec("ov_main")
        unregister_codec("ov_alias")


def test_unregister_codec_tears_down_aliases():
    @register_codec("toy_main", "toy_alias")
    class Toy(GradientCodec):
        name = "toy_main"
        bits_per_element = 8.0

    unregister_codec("toy_main")
    assert "toy_main" not in available_codecs()
    assert "toy_alias" not in available_codecs()   # alias removed too
    # re-registering the alias name must not clash with a stale entry
    register_codec("toy_alias")(Toy)
    unregister_codec("toy_alias")


def test_unknown_codec_raises_clear_error():
    with pytest.raises(KeyError, match="unknown codec 'nope'"):
        get_codec("nope")
    with pytest.raises(KeyError, match="register_codec"):
        get_codec("nope")


def test_parameterized_codec_instance_registration():
    dense = TopKCodec(fraction=1.0)
    register_codec("topall")(dense)
    try:
        assert get_codec("topall") is dense
        assert get_codec("topall").bits_per_element == 64.0
        # fraction=1 keeps everything: encode is the identity
        g = jnp.asarray([1.0, -2.0, 0.5], jnp.float32)
        np.testing.assert_array_equal(np.asarray(dense.encode(None, g)),
                                      np.asarray(g))
    finally:
        unregister_codec("topall")
    with pytest.raises(ValueError, match="fraction"):
        TopKCodec(fraction=0.0)


def test_register_codec_rejects_incomplete_objects():
    with pytest.raises(TypeError, match="bits_per_element"):
        @register_codec("toy_bad")
        class Bad:                       # no name / bits_per_element
            pass


# ---------------------------------------------------------------------------
# wire_schedule: normalized returns over the codec x schedule grid
# ---------------------------------------------------------------------------

def test_wire_schedule_always_returns_canonical_string():
    """Old behavior leaked a Schedule enum on one branch and the caller's
    enum-or-string otherwise; the return is now always the registry key
    string — exhaustively over every built-in codec x schedule pairing
    (enum and string spellings) plus custom names on both axes."""
    schedules = [Schedule.PSUM, Schedule.VOTE_PSUM, Schedule.PACKED_A2A,
                 "psum", "vote_psum", "packed_a2a", "sign_of_mean",
                 "my_custom_sched"]
    for mode in list(AggregationMode) + [m.value for m in AggregationMode] \
            + ["int4", "topk"]:
        votes = get_codec(mode).reduction == "vote"
        for sched in schedules:
            got = wire_schedule(mode, sched)
            assert type(got) is str, (mode, sched, got)
            name = sched.value if isinstance(sched, Schedule) else sched
            if not votes and name in ("vote_psum", "packed_a2a"):
                assert got == "psum"            # mean codecs ride the bypass
            elif votes and name == "psum":
                assert got == "vote_psum"       # votes have no mean path
            else:
                assert got == name              # everything else: as named


def test_wire_schedule_mean_codec_never_on_vote_transport():
    # the int4 mean codec nominally on the vote transports rides psum,
    # exactly like FP32 — the generalized bypass semantics
    assert wire_schedule("int4", Schedule.VOTE_PSUM) == "psum"
    assert wire_schedule("int4", Schedule.PACKED_A2A) == "psum"
    assert wire_schedule("int4", "sign_of_mean") == "sign_of_mean"


# ---------------------------------------------------------------------------
# built-ins: bit-identical to the pre-redesign direct collectives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", [None, Schedule.VOTE_PSUM])
@pytest.mark.parametrize("fused", [False, True])
def test_builtin_codecs_bit_identical_string_vs_enum(rng, aggregate, schedule,
                                                    fused):
    """Naming built-in codecs by string is bit-for-bit the enum path —
    per-leaf and fused (packed_a2a needs real/virtual workers and is
    covered by test_string_named_packed_a2a_virtual_workers)."""
    grads = _grads(rng)
    fabric = Fabric()

    def plan(modes):
        backbone, embed = modes
        return AdmissionPlan.from_dict(
            {"backbone": GroupPolicy(backbone, schedule),
             "embed": GroupPolicy(embed, schedule)},
            default=GroupPolicy(AggregationMode.FP32))

    want, _ = aggregate(
        fabric, grads,
        plan((AggregationMode.G_BINARY, AggregationMode.G_TERNARY)),
        fused=fused)
    got, _ = aggregate(fabric, grads, plan(("gbinary", "gternary")),
                       fused=fused)
    assert _tree_equal(want, got)


def test_fp32_codec_is_exact_pmean(rng, aggregate):
    grads = _grads(rng)
    for fused in (False, True):
        agg, _ = aggregate(Fabric(), grads, AdmissionPlan.fp32_all(),
                           fused=fused)
        ref = jax.tree.map(lambda g: fp32_allreduce(g, ()), grads)
        assert _tree_equal(agg, ref)


def test_vote_codecs_match_dense_oracle_multiworker(rng):
    """W=4 virtual workers: codec-dispatched gbinary/gternary equal the
    dense Section-2 oracle, with and without error feedback threading."""
    from repro.kernels import ref
    w = 4
    gs = jnp.asarray(rng.randn(w, 64, 5), jnp.float32)
    fabric = Fabric(dp_axes=("w",), num_workers=w)

    for mode, oracle in (("gbinary", ref.gbinary_aggregate_dense),
                         ("gternary", ref.gternary_aggregate_dense)):
        plan = AdmissionPlan.lowbit_all(mode)

        def one(g):
            agg, _ = fabric.aggregate({"g": g}, plan)
            return agg["g"]

        got = jax.vmap(one, axis_name="w")(gs)
        want = np.asarray(oracle(gs.reshape(w, -1))).reshape(64, 5)
        np.testing.assert_array_equal(np.asarray(got[0]), want)


def test_string_named_packed_a2a_virtual_workers(rng, aggregate):
    """String-named codecs on the packed controller schedule (W=4 vmap)
    are bit-identical to the enum-named path, fused and per-leaf."""
    w = 4
    gs = {"backbone": jnp.asarray(rng.randn(w, 40, 33), jnp.float32),
          "embed": jnp.asarray(rng.randn(w, 130), jnp.float32)}
    fabric = Fabric(dp_axes=("w",), num_workers=w)

    def plan(modes):
        backbone, embed = modes
        return AdmissionPlan.from_dict(
            {"backbone": GroupPolicy(backbone, Schedule.PACKED_A2A),
             "embed": GroupPolicy(embed, Schedule.PACKED_A2A)},
            default=GroupPolicy(AggregationMode.FP32))

    for fused in (False, True):
        def run(p, fused=fused):
            def one(g):
                agg, _ = aggregate(fabric, g, p, fused=fused)
                return agg
            return jax.vmap(one, axis_name="w")(gs)

        want = run(plan((AggregationMode.G_BINARY,
                         AggregationMode.G_TERNARY)))
        got = run(plan(("gbinary", "gternary")))
        assert _tree_equal(want, got)


def test_codec_threads_ef_flag_gates_fused_ef(rng):
    """EF rides the fused collective only when the codec allows it: a
    mean codec with threads_ef=False on an EF-enabled plan leaves the
    residuals untouched (exactly the per-leaf psum behavior)."""
    from repro.core import init_ef_states
    grads = _grads(rng)
    plan = AdmissionPlan.lowbit_all("int4", error_feedback=True)
    fabric = Fabric()
    policies = fabric.resolve(grads, plan)
    ef = init_ef_states(grads, policies)
    _, new_ef = fabric.aggregate(grads, plan, ef=ef)
    assert _tree_equal(ef, new_ef)       # int4 declares threads_ef=False


# ---------------------------------------------------------------------------
# AggregationMode deprecation shim
# ---------------------------------------------------------------------------

def test_legacy_mode_tables_warn_and_match_registry():
    from repro.core import modes
    with pytest.warns(DeprecationWarning, match="BITS_PER_ELEMENT"):
        table = modes.BITS_PER_ELEMENT
    assert table == {m: get_codec(m).bits_per_element
                     for m in AggregationMode}
    with pytest.warns(DeprecationWarning, match="DEFAULT_SCHEDULE"):
        defaults = modes.DEFAULT_SCHEDULE
    assert defaults == {m: Schedule(get_codec(m).default_schedule)
                        for m in AggregationMode}
    with pytest.warns(DeprecationWarning, match="is_lowbit"):
        assert AggregationMode.G_BINARY.is_lowbit
    with pytest.raises(AttributeError):
        modes.NOT_A_TABLE


def test_shimmed_enum_reproduces_pilot_decisions():
    """The Fig-6 pilot's Commander ladder still emits the same plans
    through the shim (its values are the built-in codec names)."""
    from repro.core import Commander
    cmd = Commander(tau_binary=0.35, tau_ternary=0.30)
    plan = cmd.propose({"backbone": {"gbinary": 0.5},
                        "embed": {"gbinary": 0.1, "gternary": 0.4},
                        "head": {"gbinary": 0.0, "gternary": 0.0}})
    assert plan.policy_for("backbone").mode == AggregationMode.G_BINARY
    assert plan.policy_for("embed").mode == AggregationMode.G_TERNARY
    assert plan.policy_for("head").mode == AggregationMode.FP32
    assert plan.signature() == ("backbone:gbinary:vote_psum:0"
                                "|embed:gternary:vote_psum:0"
                                "|head:fp32:psum:0|*:fp32:psum:0")


def test_canonical_mode_and_plan_json_roundtrip():
    assert canonical_mode("gbinary") is AggregationMode.G_BINARY
    assert canonical_mode(AggregationMode.FP32) is AggregationMode.FP32
    assert canonical_mode("int4") == "int4" and codec_name("int4") == "int4"

    from repro.fabric import plan_from_jsonable, plan_to_jsonable
    plan = AdmissionPlan.from_dict(
        {"backbone": GroupPolicy("int4"),
         "embed": GroupPolicy(AggregationMode.G_TERNARY)},
        default=GroupPolicy(AggregationMode.FP32))
    back = plan_from_jsonable(plan_to_jsonable(plan))
    assert back.signature() == plan.signature()
    assert back.policy_for("backbone").mode == "int4"
    assert back.policy_for("embed").mode is AggregationMode.G_TERNARY


# ---------------------------------------------------------------------------
# the extension seam: a codec registered outside repro.fabric.codecs
# flows through buckets + traffic + sim + build_step with no backend edits
# ---------------------------------------------------------------------------

def test_extra_codec_int4_fused_aggregate_and_traffic(rng):
    grads = _grads(rng)
    plan = plan_presets()["int4_backbone"]
    fabric = Fabric()
    layout = fabric.layout_for(grads, plan)
    # the int4 leaves fuse on the psum wire schedule (mean transport)
    int4_buckets = [b for b in layout.buckets if b.key.mode == "int4"]
    assert len(int4_buckets) == 1
    assert int4_buckets[0].key.schedule == "psum"
    agg, _ = fabric.aggregate(grads, plan)
    # W=1 psum mean of the quantized payload == the quantized payload;
    # per-bucket absmax scale, so quantize the fused flat payload
    codec = Int4Codec()
    flat = jnp.concatenate([grads["backbone"]["w1"].reshape(-1),
                            grads["backbone"]["w2"].reshape(-1)])
    q = np.asarray(codec.encode(None, flat))
    np.testing.assert_array_equal(
        np.asarray(agg["backbone"]["w1"]).reshape(-1), q[:40 * 33])
    # quantization actually happened (few distinct magnitudes) but kept
    # the direction
    assert len(np.unique(np.abs(q))) <= 8
    np.testing.assert_array_equal(np.sign(q[q != 0]),
                                  np.sign(np.asarray(flat)[q != 0]))
    # head stays exact FP32
    np.testing.assert_array_equal(np.asarray(agg["head"]["w"]),
                                  np.asarray(grads["head"]["w"]))

    # traffic accounting picks the codec up by name
    sizes = group_sizes(grads)
    ratio = plan_traffic_ratio(sizes, plan)
    nb = sizes["backbone"]
    total = sum(sizes.values())
    assert ratio == pytest.approx((nb * 4.0 + (total - nb) * 32.0)
                                  / (32.0 * total))
    w = 8
    f = (w - 1) / w
    assert wire_bytes_per_device(1000, "int4", "psum", w) == pytest.approx(
        2 * f * 1000 * 0.5)


@pytest.mark.parametrize("topology", ["ici_ring", "cxl_direct"])
def test_extra_codec_simulates_on_topologies(rng, topology):
    """The int4 codec's layout simulates on >= 2 topologies, timed by its
    own lane descriptor — no edits to sim/datapath built-in lanes."""
    grads = _grads(rng)
    fabric = Fabric(num_workers=8)
    rep = fabric.simulate(grads, plan_presets()["int4_backbone"],
                          topology=topology, compute_time_s=1e-4)
    assert rep.topology == topology
    assert rep.step_time_s > 0 and rep.num_launches >= 2
    int4 = [l for l in rep.launches if l.mode == "int4"]
    assert len(int4) == 1 and int4[0].wire_bytes > 0
    # the codec's 4-bit payload moves 8x fewer wire bytes than its FP32
    # sibling of the same element count would
    fp32 = [l for l in rep.launches if l.mode == "fp32"]
    assert all(l.wire_bytes > 0 for l in fp32)
    from repro.sim import FlitPipeline
    pipe = FlitPipeline()
    assert pipe.lane("int4").name == "int4_dense"
    assert pipe.flits(1 << 20, "int4") == (1 << 20) * 4 // 512


def test_custom_codec_registered_in_test_runs_everywhere(rng):
    """A codec defined *here* (outside the repo's codec modules): scaled
    mean with custom bits — proof the representation axis is open."""
    @register_codec("halfmean")
    class HalfMean(GradientCodec):
        name = "halfmean"
        bits_per_element = 16.0

        def decode(self, ctx, u):
            return 0.5 * u

    try:
        grads = _grads(rng)
        plan = AdmissionPlan.lowbit_backbone("halfmean")
        fabric = Fabric()
        # fused path: one bucket on the psum transport, halved mean
        layout = fabric.layout_for(grads, plan)
        assert any(b.key.mode == "halfmean" for b in layout.buckets)
        agg, _ = fabric.aggregate(grads, plan)
        np.testing.assert_allclose(
            np.asarray(agg["backbone"]["w1"]),
            0.5 * np.asarray(grads["backbone"]["w1"]), rtol=1e-6)
        # traffic + sim, by name only
        assert bits_per_element("halfmean") == 16.0
        rep = fabric.simulate(grads, plan, topology="cxl_switched")
        assert any(l.mode == "halfmean" for l in rep.launches)
    finally:
        unregister_codec("halfmean")


def test_custom_vote_codec_without_ef_consistent_across_paths(rng, aggregate):
    """A vote codec with threads_ef=False: the per-leaf path must apply
    the same EF gate as the fused path — no injection, residuals
    untouched, aggregates identical on both routes."""
    @register_codec("vote_noef")
    class VoteNoEf(GradientCodec):
        name = "vote_noef"
        bits_per_element = 1.0
        reduction = "vote"
        threads_ef = False
        default_schedule = "vote_psum"

    try:
        grads = {"a": jnp.asarray(rng.randn(33, 5), jnp.float32)}
        plan = AdmissionPlan.lowbit_all("vote_noef", error_feedback=True)
        fabric = Fabric()
        ef = {"a": jnp.asarray(rng.randn(1, 33, 5), jnp.float32)}
        a1, e1 = aggregate(fabric, grads, plan, ef=ef)
        a2, e2 = aggregate(fabric, grads, plan, ef=ef, fused=False)
        assert _tree_equal(a1, a2)
        # the residual is neither injected (W=1 vote == sign(g), not
        # sign(g + e)) nor updated, on either path
        np.testing.assert_array_equal(np.asarray(a1["a"]),
                                      np.sign(np.asarray(grads["a"])))
        assert _tree_equal(e1, ef) and _tree_equal(e2, ef)
    finally:
        unregister_codec("vote_noef")


def test_custom_leaf_gate_mask_same_zeros_on_both_vote_transports(rng, aggregate):
    """A gated codec with a custom keep pattern zeroes the same elements
    on vote_psum and packed_a2a, per-leaf and fused (W=4 vmap)."""
    def even_mask(n):
        return (np.arange(n) % 2) == 0

    @register_codec("even_keep")
    class EvenKeep(GradientCodec):
        name = "even_keep"
        bits_per_element = 1.0
        reduction = "vote"
        gated = True
        threads_ef = True
        default_schedule = "vote_psum"

        # bucket_gate deliberately NOT overridden: the base-class
        # default must compose the fused gate from leaf_gate_mask so
        # fused and per-leaf paths zero the same elements
        def leaf_gate_mask(self, shape, gate_phase):
            return even_mask(int(np.prod(shape)))

    try:
        from repro.kernels import ref
        w = 4
        gs = {"g": jnp.asarray(rng.randn(w, 64, 6), jnp.float32)}
        fabric = Fabric(dp_axes=("w",), num_workers=w)
        want = (np.asarray(ref.gbinary_aggregate_dense(
            gs["g"].reshape(w, -1))) * even_mask(64 * 6)).reshape(64, 6)
        for schedule in (Schedule.VOTE_PSUM, Schedule.PACKED_A2A):
            plan = AdmissionPlan.lowbit_all("even_keep", schedule=schedule)
            for fused in (False, True):
                def one(g, plan=plan, fused=fused):
                    agg, _ = aggregate(fabric, g, plan, fused=fused)
                    return agg
                got = jax.vmap(one, axis_name="w")(gs)
                np.testing.assert_array_equal(
                    np.asarray(got["g"][0]), want,
                    err_msg=f"schedule={schedule} fused={fused}")
    finally:
        unregister_codec("even_keep")


def test_layout_cache_invalidated_when_codec_swapped(rng):
    """Swapping a codec under the same name (override/unregister) must
    not serve a stale layout: gate-phase normalization depends on the
    codec's gated flag, exactly like fusability depends on the backend."""
    from repro.core.lowbit import LeafPolicy
    grads = {"a": jnp.asarray(rng.randn(9), jnp.float32),
             "b": jnp.asarray(rng.randn(9), jnp.float32)}
    policies = {
        "a": LeafPolicy("toy_swap_codec", Schedule.VOTE_PSUM, gate_phase=0),
        "b": LeafPolicy("toy_swap_codec", Schedule.VOTE_PSUM, gate_phase=1)}
    fabric = Fabric()

    @register_codec("toy_swap_codec")
    class Ungated(GradientCodec):
        name = "toy_swap_codec"
        bits_per_element = 1.0
        reduction = "vote"
        default_schedule = "vote_psum"

    try:
        # ungated: gate_phase normalizes to 0, both leaves share a bucket
        assert len(fabric.layout_for(grads, policies).buckets) == 1
        unregister_codec("toy_swap_codec")

        @register_codec("toy_swap_codec")
        class Gated(Ungated):
            gated = True

        layout = fabric.layout_for(grads, policies)
        # gated: distinct gate phases must split the bucket (stale cache
        # would still fuse them under one phase-0 gate)
        assert len(layout.buckets) == 2
        assert {b.key.gate_phase for b in layout.buckets} == {0, 1}
    finally:
        unregister_codec("toy_swap_codec")


def test_parameterized_codec_carries_registration_name():
    codec = TopKCodec(0.25, name="top25pct")
    register_codec("top25pct")(codec)
    try:
        from repro.fabric import get_codec
        assert get_codec("top25pct").name == "top25pct"
        assert get_codec("top25pct").bits_per_element == 16.0
    finally:
        unregister_codec("top25pct")


def test_ungated_codec_with_leaf_gate_mask_raises(rng, aggregate):
    """A codec supplying a keep mask while declaring gated=False is a
    contract violation — it must fail loudly on both paths, never
    silently drop the gate."""
    @register_codec("bad_gate")
    class BadGate(GradientCodec):
        name = "bad_gate"
        bits_per_element = 1.0
        reduction = "vote"
        gated = False               # inconsistent with the mask below
        default_schedule = "vote_psum"

        def leaf_gate_mask(self, shape, gate_phase):
            return np.ones(int(np.prod(shape)), bool)

    try:
        grads = {"a": jnp.asarray(rng.randn(8), jnp.float32)}
        plan = AdmissionPlan.lowbit_all("bad_gate")
        for fused in (True, False):
            with pytest.raises(ValueError, match="gated=False"):
                aggregate(Fabric(), grads, plan, fused=fused)
    finally:
        unregister_codec("bad_gate")


def test_topk_codec_sparsifies_and_aggregates(rng):
    g = jnp.asarray(rng.randn(1024), jnp.float32)
    codec = TopKCodec(fraction=1 / 16)
    enc = np.asarray(codec.encode(None, g))
    kept = np.count_nonzero(enc)
    assert 64 <= kept <= 80                      # ties may keep a few extra
    # the kept entries are the largest magnitudes, passed through exactly
    assert np.min(np.abs(enc[enc != 0])) >= np.sort(np.abs(np.asarray(g)))[-80]
    np.testing.assert_array_equal(enc[enc != 0], np.asarray(g)[enc != 0])

    agg, _ = Fabric().aggregate({"backbone": {"w": g}},
                                plan_presets()["topk_backbone"])
    assert 0 < np.count_nonzero(np.asarray(agg["backbone"]["w"])) < g.size


def test_extra_codec_trains_through_build_step(rng):
    """Acceptance: the int4 codec trains through Fabric.build_step —
    resolved purely by plan name, fused by default, loss decreases."""
    from jax.sharding import AxisType
    from repro.models import ModelConfig, init_params
    from repro.optim import SgdMomentum
    from repro.fabric import TrainState

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = ModelConfig(name="codec_t", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, dtype="float32", remat=False)
    fabric = Fabric(mesh, dp_axes=("data",))
    plan = plan_presets()["int4_backbone"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = SgdMomentum(peak_lr=0.2, total_steps=20)
    step = fabric.build_step(cfg, opt, plan, params)
    assert "int4" in plan.signature()
    assert step.aux["layout"] is not None
    assert any(b.key.mode == "int4" for b in step.aux["layout"].buckets)

    policies = step.aux["policies"]
    state = TrainState(params=params, opt=opt.init(params),
                       ef=fabric.init_ef(params, policies),
                       step=jnp.zeros((), jnp.int32))
    tokens = jnp.asarray(rng.randint(0, 256, size=(8, 33)), jnp.int32)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()

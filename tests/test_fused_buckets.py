"""Bucketed (fused) aggregation: layout planner + bit-for-bit equivalence.

The fusion contract: `aggregate_tree_bucketed` / `Fabric.aggregate` must
be *bit-identical* — aggregates and EF states — to the per-leaf reference
`aggregate_tree` for every built-in schedule, in every mode, with and
without error feedback, for any gate phase.  Multi-worker semantics are
exercised with virtual workers via ``jax.vmap(..., axis_name='w')``
(psum/all_to_all/all_gather resolve against the vmapped axis exactly as
on a mesh).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdmissionPlan, AggregationMode, GroupPolicy,
                        IciModel, Schedule, init_ef_states,
                        modeled_layout_comm_time, plan_buckets,
                        resolve_policies)
from repro.core.buckets import DEFAULT_BUCKET_BYTES
from repro.core.lowbit import LeafPolicy
from repro.fabric import (Fabric, aggregate_tree, aggregate_tree_bucketed,
                          register_schedule, unregister_schedule)
from jax.sharding import PartitionSpec as P


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


def _plan(schedule=None, error_feedback=False,
          mode=AggregationMode.G_BINARY):
    return AdmissionPlan.from_dict(
        {"backbone": GroupPolicy(mode, schedule,
                                 error_feedback=error_feedback),
         "embed": GroupPolicy(AggregationMode.G_TERNARY, schedule)},
        default=GroupPolicy(AggregationMode.FP32))


# ---------------------------------------------------------------------------
# layout planner
# ---------------------------------------------------------------------------

def test_layout_groups_by_compatibility_key(rng):
    grads = _grads(rng)
    layout = plan_buckets(grads, resolve_policies(grads, _plan()))
    # three distinct keys -> three buckets (backbone / embed / fp32 rest)
    assert len(layout.buckets) == 3 and not layout.unfused
    assert layout.num_leaves == 6 and layout.num_launches == 3
    by_mode = {b.key.mode: b for b in layout.buckets}
    backbone = by_mode[AggregationMode.G_BINARY]
    assert [s.name for s in backbone.slots] == ["backbone/w1", "backbone/w2",
                                                "backbone/w3"]
    # offsets are a running sum of sizes; bucket size is the total
    assert [s.offset for s in backbone.slots] == [0, 40 * 33, 40 * 33 + 257]
    assert backbone.size == 40 * 33 + 257 + 64 * 8
    # fp32 leaves from different groups fuse (same wire schedule + mode)
    fp32 = by_mode[AggregationMode.FP32]
    assert {s.name for s in fp32.slots} == {"head/w", "norms/scale"}


def test_layout_respects_bucket_byte_budget(rng):
    grads = _grads(rng)
    policies = resolve_policies(grads, _plan())
    # 1 KiB budget = 256 f32 elements: backbone leaves can't share buckets
    layout = plan_buckets(grads, policies, bucket_bytes=1024)
    backbone = [b for b in layout.buckets
                if b.key.mode == AggregationMode.G_BINARY]
    assert len(backbone) == 3          # every leaf overflows the budget
    for b in backbone:                 # oversize leaves bucket alone
        assert len(b.slots) == 1 and b.slots[0].offset == 0


def test_layout_per_leaf_degenerate_and_stability(rng):
    grads = _grads(rng)
    policies = resolve_policies(grads, _plan())
    per_leaf = plan_buckets(grads, policies, bucket_bytes=1)
    assert per_leaf.num_launches == per_leaf.num_leaves == 6
    # deterministic: same inputs -> identical layout (jit-cache safe)
    a = plan_buckets(grads, policies)
    b = plan_buckets(grads, policies)
    assert a == b
    assert list(a.launches()) == list(b.launches())


def test_layout_tp_sharded_and_nonfusable_leaves_stay_per_leaf(rng):
    grads = {"a": jnp.asarray(rng.randn(8, 4), jnp.float32),
             "b": jnp.asarray(rng.randn(8, 4), jnp.float32)}
    policies = {
        "a": LeafPolicy(AggregationMode.G_BINARY, Schedule.PACKED_A2A,
                        model_spec=P(None, "model")),
        "b": LeafPolicy(AggregationMode.G_BINARY, Schedule.PACKED_A2A)}
    layout = plan_buckets(grads, policies)
    assert [u.name for u in layout.unfused] == ["a"]     # TP-sharded
    assert len(layout.buckets) == 1
    # a predicate rejecting the schedule forces per-leaf for both
    layout2 = plan_buckets(grads, policies, fusable=lambda s: False)
    assert len(layout2.unfused) == 2 and not layout2.buckets


def test_layout_key_uses_wire_schedule(rng):
    """FP32 leaves nominally on packed_a2a fuse with plain psum leaves."""
    grads = {"a": jnp.asarray(rng.randn(8), jnp.float32),
             "b": jnp.asarray(rng.randn(8), jnp.float32)}
    policies = {
        "a": LeafPolicy(AggregationMode.FP32, Schedule.PACKED_A2A),
        "b": LeafPolicy(AggregationMode.FP32, Schedule.PSUM)}
    layout = plan_buckets(grads, policies)
    assert len(layout.buckets) == 1
    assert layout.buckets[0].key.schedule == "psum"


def test_ternary_gate_mask_is_per_leaf_indexed():
    sds = jax.ShapeDtypeStruct
    grads = {"a": sds((5,), jnp.float32), "b": sds((4,), jnp.float32)}
    pol = LeafPolicy(AggregationMode.G_TERNARY, Schedule.VOTE_PSUM,
                     gate_phase=1)
    layout = plan_buckets(grads, {"a": pol, "b": pol})
    (bucket,) = layout.buckets
    # each leaf restarts the 2-of-3 pattern at its own flat index 0
    leaf = (((np.arange(5) + 1) % 3) != 2)
    want = np.concatenate([leaf, leaf[:4]])
    gate = bucket.gate()
    np.testing.assert_array_equal(gate.mask(), want)
    # the on-device representation matches the host mask bit for bit
    np.testing.assert_array_equal(np.asarray(gate.vector(jnp.float32)),
                                  want.astype(np.float32))


def test_gate_phase_normalized_for_non_ternary_modes(rng):
    """gate_phase only affects G-Ternary; binary leaves differing only in
    phase must still share a bucket."""
    grads = {"a": jnp.asarray(rng.randn(8), jnp.float32),
             "b": jnp.asarray(rng.randn(8), jnp.float32)}
    policies = {
        "a": LeafPolicy(AggregationMode.G_BINARY, Schedule.VOTE_PSUM,
                        gate_phase=0),
        "b": LeafPolicy(AggregationMode.G_BINARY, Schedule.VOTE_PSUM,
                        gate_phase=1)}
    layout = plan_buckets(grads, policies)
    assert len(layout.buckets) == 1 and not layout.unfused


# ---------------------------------------------------------------------------
# bit-for-bit equivalence: fused vs per-leaf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", [None, Schedule.VOTE_PSUM])
@pytest.mark.parametrize("error_feedback", [False, True])
def test_fused_matches_per_leaf_host_local(rng, aggregate, schedule,
                                           error_feedback):
    grads = _grads(rng)
    plan = _plan(schedule=schedule, error_feedback=error_feedback)
    fabric = Fabric()
    policies = fabric.resolve(grads, plan)
    ef = init_ef_states(grads, policies) if error_feedback else None
    want, want_ef = aggregate(fabric, grads, plan, ef=ef, fused=False)
    got, got_ef = aggregate(fabric, grads, plan, ef=ef)
    assert _tree_equal(want, got)
    if error_feedback:
        assert _tree_equal(want_ef, got_ef)
        # EF actually produced a nonzero residual somewhere
        assert float(jnp.sum(jnp.abs(got_ef["backbone"]["w1"]))) > 0
    else:
        assert want_ef is None and got_ef is None


@pytest.mark.parametrize("mode", [AggregationMode.G_BINARY,
                                  AggregationMode.G_TERNARY])
@pytest.mark.parametrize("gate_phase", [0, 1, 2])
def test_fused_matches_per_leaf_all_gate_phases(rng, mode, gate_phase):
    grads = _grads(rng)
    pol = lambda _: LeafPolicy(mode, Schedule.VOTE_PSUM,
                               gate_phase=gate_phase)
    policies = jax.tree.map(pol, grads)
    ctx = Fabric().context
    want, _ = aggregate_tree(ctx, grads, policies)
    got, _ = aggregate_tree_bucketed(ctx, grads, policies)
    assert _tree_equal(want, got)


@pytest.mark.parametrize("schedule", [Schedule.VOTE_PSUM,
                                      Schedule.PACKED_A2A])
@pytest.mark.parametrize("error_feedback", [False, True])
def test_fused_matches_per_leaf_virtual_workers(rng, aggregate, schedule,
                                                error_feedback):
    """W=4 virtual workers via vmap: binary + ternary + FP32 mixed plan.

    Covers the fused packed_a2a datapath end to end — pack, all_to_all,
    PopCount/majority with the bucket-wide gate words, all_gather — and
    its per-bucket EF handling, against the per-leaf reference.
    """
    w = 4
    gs = _grads(rng, w=w)
    plan = _plan(schedule=schedule, error_feedback=error_feedback)
    fabric = Fabric(dp_axes=("w",), num_workers=w)
    g0 = jax.tree.map(lambda x: x[0], gs)
    policies = fabric.resolve(g0, plan)
    if error_feedback:
        ef0 = init_ef_states(g0, policies)
        # nonzero per-worker residuals so injection has a real effect
        efs = jax.tree.map(
            lambda e: (jnp.asarray(rng.randn(w, *e.shape), jnp.float32)
                       if e.ndim > 0 else jnp.zeros((w,) + e.shape)), ef0)
    else:
        efs = jax.tree.map(lambda x: jnp.zeros((x.shape[0],)), gs)  # unused

    def run(fused):
        def one(g, e):
            return aggregate(fabric, g, plan,
                             ef=(e if error_feedback else None), fused=fused)
        return jax.vmap(one, axis_name="w")(gs, efs)

    want, want_ef = run(False)
    got, got_ef = run(True)
    assert _tree_equal(want, got)
    if error_feedback:
        assert _tree_equal(want_ef, got_ef)
    # semantic oracle for the ternary group (dense Section-2 reduction)
    from repro.kernels import ref
    table = gs["embed"]["table"]
    want_ter = np.asarray(ref.gternary_aggregate_dense(
        table.reshape(w, -1))).reshape(table.shape[1:])
    np.testing.assert_array_equal(np.asarray(got["embed"]["table"][0]),
                                  want_ter)


def test_fused_is_the_default_aggregate_path(rng, aggregate):
    grads = _grads(rng)
    fabric = Fabric()
    got, _ = fabric.aggregate(grads, _plan())           # the only route
    want, _ = aggregate(fabric, grads, _plan(), fused=False)
    assert _tree_equal(want, got)
    # the layout is planned once and cached per (tree, policies) signature
    lay = fabric.layout_for(grads, _plan())
    assert lay is fabric.layout_for(grads, _plan())
    assert lay.num_launches < lay.num_leaves


def test_non_fusable_custom_backend_routes_per_leaf(rng):
    """A registered backend without `fusable` still works under the
    default fused path — its leaves ride the per-leaf fallback."""
    @register_schedule("toy_unfused_mean")
    class ToyMean:
        name = "toy_unfused_mean"

        def aggregate(self, ctx, g, policy, ef=None):
            return 2.0 * g, ef

    try:
        grads = {"a": jnp.asarray(np.arange(6.0), jnp.float32),
                 "b": jnp.asarray(np.arange(4.0), jnp.float32)}
        plan = AdmissionPlan.lowbit_all(AggregationMode.G_BINARY,
                                        schedule="toy_unfused_mean")
        fabric = Fabric()
        layout = fabric.layout_for(grads, plan)
        assert len(layout.unfused) == 2 and not layout.buckets
        got, _ = fabric.aggregate(grads, plan)
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      2.0 * np.arange(6.0))
    finally:
        unregister_schedule("toy_unfused_mean")


def test_layout_cache_invalidated_when_backend_swapped(rng):
    """Swapping a schedule backend under the same name (the documented
    extension workflow) must not leave a stale fused layout routing
    leaves to a backend that no longer implements aggregate_flat."""
    grads = {"a": jnp.asarray(rng.randn(8), jnp.float32)}
    plan = AdmissionPlan.lowbit_all(AggregationMode.G_BINARY,
                                    schedule="toy_swappable")
    fabric = Fabric()

    @register_schedule("toy_swappable")
    class FusableToy:
        name = "toy_swappable"
        fusable = True

        def aggregate(self, ctx, g, policy, ef=None):
            return g, ef

        def aggregate_flat(self, ctx, flat, codec, *, gate=None):
            return flat

    try:
        assert len(fabric.layout_for(grads, plan).buckets) == 1
        fabric.aggregate(grads, plan)
        unregister_schedule("toy_swappable")

        @register_schedule("toy_swappable")
        class PerLeafToy:
            name = "toy_swappable"       # no fusable / aggregate_flat

            def aggregate(self, ctx, g, policy, ef=None):
                return 3.0 * g, ef

        layout = fabric.layout_for(grads, plan)
        assert not layout.buckets and len(layout.unfused) == 1
        got, _ = fabric.aggregate(grads, plan)
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      3.0 * np.asarray(grads["a"]))
    finally:
        unregister_schedule("toy_swappable")


def test_mixed_dtypes_never_share_a_bucket(rng, aggregate):
    grads = {"a": jnp.asarray(rng.randn(8), jnp.float32),
             "b": jnp.asarray(rng.randn(8), jnp.bfloat16)}
    plan = AdmissionPlan.lowbit_all(AggregationMode.G_BINARY)
    fabric = Fabric()
    layout = fabric.layout_for(grads, plan)
    assert len(layout.buckets) == 2
    want, _ = aggregate(fabric, grads, plan, fused=False)
    got, _ = aggregate(fabric, grads, plan)
    assert _tree_equal(want, got)
    assert got["b"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# traffic model: the per-launch term explains the fusion win
# ---------------------------------------------------------------------------

def test_layout_comm_time_rewards_fusion(rng):
    grads = _grads(rng)
    policies = resolve_policies(grads, _plan())
    fused = plan_buckets(grads, policies)
    per_leaf = plan_buckets(grads, policies, bucket_bytes=1)
    w = 32
    t_fused = modeled_layout_comm_time(fused, w)
    t_leaf = modeled_layout_comm_time(per_leaf, w)
    assert t_fused < t_leaf
    ici = IciModel()
    # identical bytes: the whole gap is launches * per-launch latency
    per_launch = (2 * (w - 1)) * ici.hop_latency_s + ici.launch_overhead_s
    gap = (per_leaf.num_launches - fused.num_launches) * per_launch
    assert t_leaf - t_fused == pytest.approx(gap)


def test_collective_time_launch_term_monotonic():
    ici = IciModel()
    one = ici.collective_time(2 ** 20, 8, num_launches=1)
    many = ici.collective_time(2 ** 20, 8, num_launches=10)
    assert many > one
    assert many - one == pytest.approx(
        9 * (14 * ici.hop_latency_s + ici.launch_overhead_s))

"""Codec-owned fused Pallas kernels (repro.kernels.fused).

The contract under test: every fused kernel is **bit-identical** to its
pure-jnp reference composition in :mod:`repro.kernels.ref` — per stage
(ragged sizes, W in {2, 3, 8, 16, 32} and {3, 31, 128, 256}), for the
distributed packed vote under vmapped virtual workers, and end-to-end
through the Fabric session against the dense Section-2 oracle (EF on/off,
one bucket per leaf or the default budget, flat and hierarchical routes)
or, for the mean codecs, the codec's own jnp encode/decode.  Comparisons
against the jnp reference jit the reference side: XLA CPU rounds an eager
scalar division differently from the jitted program the kernels (and
every production step) run in, and bit-identity is a claim about
compiled programs (DESIGN.md section 12).

Also covered: the KernelSet launch/HBM accounting invariants the
nightly benchmark gate relies on, the ``layout_kernel_stats`` roll-up,
the sim lane pricing (``CodecLane.fused``), the step/layout cache keys,
and the import-hygiene rule that only :mod:`repro.kernels` touches
``kernels.ref`` directly.
"""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DEFAULT_BUCKET_BYTES, AdmissionPlan, init_ef_states,
                        resolve_policies)
from repro.fabric import (Fabric, HopPlan, HopSpec, get_codec,
                          layout_kernel_stats, register_hop_plan,
                          unregister_hop_plan)
from repro.kernels import (Int4KernelSet, TopKKernelSet, VoteKernelSet,
                           fused, ref, vote_kernel_set)

#: the satellite's worker-count sweep (odd, large, power-of-two, > ports)
W_SWEEP = [3, 31, 128, 256]


def _tree_equal(a, b):
    flags = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))), a, b)
    return all(jax.tree.leaves(flags))


def _grads(rng, w=None):
    mk = (lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)) if w is None \
        else (lambda *s: jnp.asarray(rng.randn(w, *s), jnp.float32))
    return {"backbone": {"w1": mk(40, 33), "w2": mk(257), "w3": mk(64, 8)},
            "head": {"w": mk(17)},
            "norms": {"scale": mk(33)}}


def _stack_planes(rng, w, n):
    """(W, n) random values -> (W, M, LANE) canonical value planes."""
    vals = jnp.asarray(rng.randn(w, n), jnp.float32)
    return jnp.stack([ref.to_plane(vals[i]) for i in range(w)])


def _dense_vote(x, ternary, phase=0):
    """(W, ...) worker values -> the Section-2 aggregate of their signs."""
    w = x.shape[0]
    flat = jnp.asarray(x, jnp.float32).reshape(w, -1)
    if ternary:
        u = jax.jit(ref.gternary_aggregate_dense,
                    static_argnums=1)(flat, phase)
    else:
        u = jax.jit(ref.gbinary_aggregate_dense)(flat)
    return np.asarray(u).reshape(x.shape[1:])


def _ef_signsgd_residual(x):
    """EF-signSGD residual x - mean|x| * sgn(x), per worker, in numpy."""
    x = np.asarray(x, np.float32)
    axes = tuple(range(1, x.ndim))
    beta = np.mean(np.abs(x), axis=axes, keepdims=True, dtype=np.float64)
    return x - beta.astype(np.float32) * np.sign(x)


# ---------------------------------------------------------------------------
# per-stage bit-identity: fused kernel vs jitted reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", W_SWEEP)
@pytest.mark.parametrize("ternary", [False, True])
def test_vote_pipeline_matches_ref_w_sweep(rng, w, ternary):
    n = 5000                                    # ragged: pads to 2 tiles
    stack = _stack_planes(rng, w, n)
    num_words = stack.shape[1] // ref.PACK
    gate = fused.local_gate_words(num_words, ternary=ternary)
    want = jax.jit(ref.vote_pipeline_dense, static_argnums=1)(
        stack, w, gate).astype(jnp.float32)
    got = fused.vote_pipeline(stack, gate, num_workers=w, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("rows", [32, 64, 256, 1024])
@pytest.mark.parametrize("w", [2, 3, 8, 16, 32])
@pytest.mark.parametrize("gated", [False, True])
def test_vote_combine_matches_ref(rng, rows, w, gated):
    """PopCount + majority in one kernel, over (rows, LANE) value planes
    of W workers, with and without the 2-of-3 gate."""
    planes = jnp.asarray(rng.randn(w, rows, ref.LANE), jnp.float32)
    routed = jnp.stack([ref.sign_pack(planes[i]) for i in range(w)])
    gate = (ref.ternary_gate_words(rows) if gated else
            fused.local_gate_words(rows // ref.PACK, ternary=False))
    want_s, want_m = jax.jit(ref.vote_combine, static_argnums=1)(
        routed, w, gate)
    got_s, got_m = fused.vote_combine(routed, gate, num_workers=w,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(want_s), np.asarray(got_s))
    np.testing.assert_array_equal(np.asarray(want_m), np.asarray(got_m))


@pytest.mark.parametrize("w", W_SWEEP)
def test_vote_combine_matches_ref_w_sweep(rng, w):
    n = 4096 * 3
    stack = _stack_planes(rng, w, n)
    routed = jnp.stack([ref.sign_pack(stack[i]) for i in range(w)])
    gate = fused.local_gate_words(routed.shape[1], ternary=True, gate_phase=1)
    want_s, want_m = jax.jit(ref.vote_combine, static_argnums=1)(
        routed, w, gate)
    got_s, got_m = fused.vote_combine(routed, gate, num_workers=w,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(want_s), np.asarray(got_s))
    np.testing.assert_array_equal(np.asarray(want_m), np.asarray(got_m))


def test_encode_pack_ef_matches_ref(rng):
    g = ref.to_plane(jnp.asarray(rng.randn(7000), jnp.float32))
    e = ref.to_plane(jnp.asarray(rng.randn(7000), jnp.float32))
    want_w, want_g = jax.jit(ref.encode_pack_ef)(g, e)
    got_w, got_g = fused.encode_pack_ef(g, e, interpret=True)
    np.testing.assert_array_equal(np.asarray(want_w), np.asarray(got_w))
    np.testing.assert_array_equal(np.asarray(want_g), np.asarray(got_g))


def test_ef_residual_matches_ref(rng):
    plane = ref.to_plane(jnp.asarray(rng.randn(9000), jnp.float32))
    beta = jnp.float32(0.7315)
    want = jax.jit(ref.ef_residual)(plane, beta)
    got = fused.ef_residual_plane(plane, beta, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_int4_quant_matches_jitted_ref(rng):
    plane = ref.to_plane(jnp.asarray(rng.randn(5 * 4096), jnp.float32))
    want = jax.jit(ref.int4_quant_plane)(plane)
    got = fused.int4_quant_plane(plane, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_threshold_mask_matches_ref(rng):
    flat = jnp.asarray(rng.randn(6000), jnp.float32)
    plane = ref.to_plane(flat)
    t = jax.lax.top_k(jnp.abs(flat), 600)[0][-1]
    want = jax.jit(ref.threshold_mask_plane)(plane, t)
    got = fused.threshold_mask_plane(plane, t, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# ---------------------------------------------------------------------------
# the distributed packed vote under vmapped virtual workers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [2, 3, 4, 8])
@pytest.mark.parametrize("ternary", [False, True])
@pytest.mark.parametrize("ef", [False, True])
@pytest.mark.parametrize("n", [5000, 3 * 4096])
def test_fused_packed_vote_distributed_matches_dense_oracle(rng, w, ternary,
                                                            ef, n):
    """Encode, all_to_all of the packed words, vote_combine, all_gather,
    decode over W virtual workers == the dense vote of g + e; the
    residual is EF-signSGD's."""
    g = jnp.asarray(rng.randn(w, n), jnp.float32)
    e = jnp.asarray(rng.randn(w, n), jnp.float32) if ef else None

    def one(g, e):
        return fused.fused_packed_vote(g, ("w",), w, ternary=ternary, ef=e,
                                       interpret=True)

    if ef:
        u, new_e = jax.vmap(one, axis_name="w")(g, e)
    else:
        u, new_e = jax.vmap(lambda x: one(x, None), axis_name="w")(g)
    x = np.asarray(g) + (np.asarray(e) if ef else 0.0)
    want = _dense_vote(x, ternary)
    for k in range(w):
        np.testing.assert_array_equal(np.asarray(u[k]), want)
    if ef:
        np.testing.assert_allclose(np.asarray(new_e), _ef_signsgd_residual(x),
                                   rtol=1e-6, atol=1e-6)
    else:
        assert new_e is None


# ---------------------------------------------------------------------------
# Fabric end-to-end against the dense oracle
# ---------------------------------------------------------------------------

def _check_against_oracle(policies, gs, efs, got, got_ef):
    """Each worker's aggregate of a W-stacked tree == the dense vote of
    g + e on voted leaves and the float32 mean on FP32 leaves; EF
    residuals are EF-signSGD's."""
    flat = lambda t: jax.tree_util.tree_flatten(
        t, is_leaf=lambda x: hasattr(x, "mode"))[0]
    pols, g_l, u_l = flat(policies), flat(gs), flat(got)
    e_l = flat(efs) if efs is not None else [None] * len(g_l)
    ne_l = flat(got_ef) if got_ef is not None else [None] * len(g_l)
    for pol, g, u, e, ne in zip(pols, g_l, u_l, e_l, ne_l):
        codec = get_codec(pol.mode)
        if codec.reduction != "vote":
            want = np.asarray(jnp.mean(g, axis=0))
            for k in range(g.shape[0]):
                np.testing.assert_array_equal(np.asarray(u[k]), want)
            continue
        use_ef = pol.error_feedback and e is not None and e.ndim > 1
        x = np.asarray(g) + (np.asarray(e)[:, 0] if use_ef else 0.0)
        want = _dense_vote(x, codec.gated, pol.gate_phase)
        for k in range(g.shape[0]):
            np.testing.assert_array_equal(np.asarray(u[k]), want)
        if use_ef:
            np.testing.assert_allclose(np.asarray(ne)[:, 0],
                                       _ef_signsgd_residual(x),
                                       rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("interpret", [None, True])
@pytest.mark.parametrize("mode", ["gbinary", "gternary"])
@pytest.mark.parametrize("error_feedback", [False, True])
@pytest.mark.parametrize("bucket_bytes", [DEFAULT_BUCKET_BYTES, 4],
                         ids=["default", "leaf_per_bucket"])
def test_fabric_fused_kernels_bit_identical_packed(rng, interpret, mode,
                                                   error_feedback,
                                                   bucket_bytes):
    """W=4 virtual workers on packed_a2a: the default bucket budget, and
    one below the smallest leaf so every leaf gets its own bucket."""
    w = 4
    gs = _grads(rng, w=w)
    plan = AdmissionPlan.lowbit_backbone(mode, schedule="packed_a2a",
                                         error_feedback=error_feedback)
    f = Fabric(dp_axes=("w",), num_workers=w, interpret=interpret,
               bucket_bytes=bucket_bytes)
    g0 = jax.tree.map(lambda x: x[0], gs)
    policies = f.resolve(g0, plan)
    layout = f.layout_for(g0, policies)
    if bucket_bytes == 4:
        assert layout.num_launches == layout.num_leaves
    else:
        assert layout.num_launches < layout.num_leaves
    if error_feedback:
        ef0 = init_ef_states(g0, policies)
        efs = jax.tree.map(
            lambda e: (jnp.asarray(rng.randn(w, *e.shape), jnp.float32)
                       if e.ndim > 0 else jnp.zeros((w,) + e.shape)), ef0)
        got, got_ef = jax.vmap(lambda g, e: f.aggregate(g, plan, ef=e),
                               axis_name="w")(gs, efs)
    else:
        efs = got_ef = None
        got, _ = jax.vmap(lambda g: f.aggregate(g, plan),
                          axis_name="w")(gs)
    _check_against_oracle(policies, gs, efs, got, got_ef)


@pytest.mark.parametrize("mode", ["int4", "topk"])
def test_fabric_fused_kernels_bit_identical_means(rng, mode):
    """Mean codecs: the kernel encode == the codec's own jnp
    encode/decode over each bucket's flat payload, in one jit program."""
    gs = _grads(rng)
    plan = AdmissionPlan.lowbit_backbone(mode)
    f = Fabric(interpret=True)
    pol = f.resolve(gs, plan)
    layout = f.layout_for(gs, pol)
    assert any(get_codec(b.key.mode).pallas_kernels() is not None
               for b in layout.buckets)

    def oracle(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out = [None] * len(leaves)
        for b in layout.buckets:
            codec = get_codec(b.key.mode)
            flat = jnp.concatenate([leaves[s.leaf].reshape(-1)
                                    for s in b.slots])
            u = codec.decode(f.context, codec.encode(f.context, flat))
            for s in b.slots:
                out[s.leaf] = u[s.offset:s.offset + s.size].reshape(s.shape)
        return jax.tree_util.tree_unflatten(treedef, out)

    want = jax.jit(oracle)(gs)
    got = jax.jit(lambda g: f.aggregate(g, pol)[0])(gs)
    assert _tree_equal(want, got)


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_names(sub)
    return out


@pytest.mark.parametrize("mode", ["gbinary", "gternary"])
def test_fabric_host_local_single_launch_pipeline(rng, mode):
    """Host-local packed vote: the whole chain is ONE vote_pipeline
    kernel per bucket, equal to the one-worker dense oracle."""
    gs = _grads(rng)
    plan = AdmissionPlan.lowbit_backbone(mode, schedule="packed_a2a")
    f = Fabric(interpret=True)
    pol = f.resolve(gs, plan)
    run = jax.jit(lambda g: f.aggregate(g, pol)[0])
    names = _pallas_names(jax.make_jaxpr(run)(gs).jaxpr)
    votes = [b for b in f.layout_for(gs, pol).buckets
             if get_codec(b.key.mode).reduction == "vote"]
    assert names == ["fabric_vote_pipeline"] * len(votes)
    got = jax.tree.map(lambda x: x[None], run(gs))
    _check_against_oracle(pol, jax.tree.map(lambda x: x[None], gs), None,
                          got, None)


@pytest.mark.parametrize("error_feedback", [False, True])
def test_fabric_hierarchical_hop_kernels_bit_identical(rng, error_feedback):
    """Per-hop kernel resolution: a 2-hop fp32 -> gbinary/packed_a2a route
    == the vote over the outer axis of the inner axis' mean of g + e."""
    outer, inner = 2, 2
    w = outer * inner
    gs = jax.tree.map(
        lambda x: jnp.reshape(x, (outer, inner) + x.shape[1:]),
        _grads(rng, w=w))
    register_hop_plan(HopPlan("fk_hier", (
        HopSpec("fp32", workers=inner),
        HopSpec("gbinary", schedule="packed_a2a"))))
    try:
        plan = AdmissionPlan.lowbit_all("fk_hier",
                                        error_feedback=error_feedback)
        g0 = jax.tree.map(lambda x: x[0, 0], gs)
        ef0 = init_ef_states(g0, resolve_policies(g0, plan))
        efs = jax.tree.map(
            lambda e: (jnp.asarray(rng.randn(outer, inner, *e.shape),
                                   e.dtype) if e.ndim > 0
                       else jnp.zeros((outer, inner) + e.shape)), ef0)
        f = Fabric(dp_axes=("outer", "inner"), num_workers=w)

        def one(g, e):
            return f.aggregate(g, plan, ef=(e if error_feedback else None))
        got, got_ef = jax.vmap(jax.vmap(one, axis_name="inner"),
                               axis_name="outer")(gs, efs)
        for path in (("backbone", "w1"), ("backbone", "w2"),
                     ("head", "w"), ("norms", "scale")):
            g, e = gs[path[0]][path[1]], efs[path[0]][path[1]]
            x = np.asarray(g) + (np.asarray(e)[:, :, 0] if error_feedback
                                 else 0.0)
            want = _dense_vote(jnp.mean(jnp.asarray(x), axis=1), False)
            u = np.asarray(got[path[0]][path[1]])
            for i in range(outer):
                for j in range(inner):
                    np.testing.assert_array_equal(u[i, j], want)
            if error_feedback:
                ne = np.asarray(got_ef[path[0]][path[1]])[:, :, 0]
                resid = _ef_signsgd_residual(x.reshape((w,) + x.shape[2:]))
                np.testing.assert_allclose(ne.reshape(resid.shape), resid,
                                           rtol=1e-6, atol=1e-6)
    finally:
        unregister_hop_plan("fk_hier")


def test_fused_local_packed_matches_vote_psum_semantics(rng):
    """W=1 host-local: the single-kernel pipeline degenerates to
    sign-with-zero-gate of the lone worker — the dense oracle."""
    g = jnp.asarray(rng.randn(517), jnp.float32)
    u, _ = fused.fused_packed_vote(g, (), 1, ternary=True, interpret=True)
    want = np.asarray(ref.gternary_aggregate_dense(g[None].reshape(1, -1)))
    np.testing.assert_array_equal(np.asarray(u).reshape(-1), want.reshape(-1))


# ---------------------------------------------------------------------------
# accounting invariants (the nightly gate's contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ks", [VoteKernelSet(), Int4KernelSet(),
                                TopKKernelSet(1 / 16)],
                         ids=["vote", "int4", "topk"])
@pytest.mark.parametrize("distributed", [True, False])
@pytest.mark.parametrize("ef", [False, True])
def test_kernel_set_accounting_invariants(ks, distributed, ef):
    n, w = 1 << 20, 32
    lf = ks.launches(fused=True, distributed=distributed, ef=ef)
    lu = ks.launches(fused=False, distributed=distributed, ef=ef)
    bf = ks.hbm_bytes(n, num_workers=w, fused=True,
                      distributed=distributed, ef=ef)
    bu = ks.hbm_bytes(n, num_workers=w, fused=False,
                      distributed=distributed, ef=ef)
    assert lf < lu, "fused must launch strictly fewer kernels"
    assert bf <= bu, "fused must model no more HBM traffic"
    assert lf >= 1 and bf > 0


def test_vote_kernel_set_is_shared_singleton():
    assert vote_kernel_set() is vote_kernel_set()
    assert get_codec("gbinary").pallas_kernels() is \
        get_codec("gternary").pallas_kernels()


def test_layout_kernel_stats_rollup(rng):
    gs = _grads(rng)
    plan = AdmissionPlan.lowbit_backbone("gbinary", schedule="packed_a2a")
    f = Fabric(num_workers=32)
    stats = layout_kernel_stats(f.layout_for(gs, plan), 32)
    assert stats["collectives"] == f.layout_for(gs, plan).num_launches
    assert stats["launches_fused"] < stats["launches_unfused"]
    assert stats["hbm_bytes_fused"] <= stats["hbm_bytes_unfused"]
    assert stats["unkernelized"] >= 1            # the fp32 head bucket
    # hierarchical: per-hop decomposition (fp32 hop unkernelized,
    # backbone vote hop priced at its own group size)
    register_hop_plan(HopPlan("fk_stats", (
        HopSpec("fp32", workers=8),
        HopSpec("gbinary", schedule="packed_a2a"))))
    try:
        hplan = AdmissionPlan.lowbit_backbone("fk_stats")
        hstats = layout_kernel_stats(f.layout_for(gs, hplan), 32)
        assert hstats["launches_fused"] < hstats["launches_unfused"]
    finally:
        unregister_hop_plan("fk_stats")


# ---------------------------------------------------------------------------
# session integration: cache keys + signatures
# ---------------------------------------------------------------------------

def test_kernel_signatures():
    assert get_codec("gbinary").kernel_signature() == "vote:v1"
    assert get_codec("gternary").kernel_signature() == "vote:v1"
    assert get_codec("fp32").kernel_signature() is None
    assert "levels=7" in get_codec("int4").kernel_signature()
    # hierarchical: composed over hops, '-' for kernel-less legs
    sig = get_codec("hier_fp32_gbinary").kernel_signature()
    assert sig == "->vote:v1"


def test_layout_cache_distinguishes_kernel_signatures(rng):
    """Swapping a codec's kernels under the same name must miss the
    layout cache (the signature participates in the key)."""
    gs = _grads(rng)
    plan = AdmissionPlan.lowbit_backbone("int4")
    f = Fabric(num_workers=4)
    l1 = f.layout_for(gs, plan)
    codec = get_codec("int4")
    orig = codec.pallas_kernels
    try:
        Int4Codec = type(codec)
        Int4Codec.pallas_kernels = lambda self: Int4KernelSet(levels=3.0)
        l2 = f.layout_for(gs, plan)
    finally:
        type(codec).pallas_kernels = orig
    assert len(f._layouts) == 2
    assert l1 is not l2


# ---------------------------------------------------------------------------
# sim lane pricing (CodecLane.fused -> FlitPipeline.unfused_passes)
# ---------------------------------------------------------------------------

def test_builtin_lanes_all_fused_and_pricing_unchanged():
    from repro.fabric import available_codecs
    from repro.sim import FlitPipeline
    pipe = FlitPipeline()
    for name in available_codecs():
        lane = get_codec(name).lane
        assert lane.fused, f"built-in lane {name!r} must be fused"
        c = pipe.cycles(1 << 20, 32, name)
        assert c["fill_cycles"] == float(pipe.stages)


def test_unfused_lane_pays_staged_fills_within_one_percent(rng):
    """A deliberately-unfused lane re-fills the pipeline per staged pass;
    at realistic sizes the fill is < 1% of the launch (degenerate
    unfused configs effectively unchanged)."""
    from repro.fabric import CodecLane, register_codec, unregister_codec
    from repro.fabric.codecs import GradientCodec
    from repro.sim import FlitPipeline

    @register_codec("fk_staged")
    class _Staged(GradientCodec):
        name = "fk_staged"
        bits_per_element = 1.0
        reduction = "vote"
        lane = CodecLane("sign_count")          # fused defaults to False

    try:
        pipe = FlitPipeline()
        c = pipe.cycles(1 << 20, 32, "fk_staged")
        assert c["fill_cycles"] == float(pipe.stages * pipe.unfused_passes)
        t_staged = pipe.t_agg(1 << 20, 32, "fk_staged")
        t_fused = pipe.t_agg(1 << 20, 32, "gbinary")
        assert t_staged > t_fused
        assert (t_staged - t_fused) / t_fused < 0.01
    finally:
        unregister_codec("fk_staged")


# ---------------------------------------------------------------------------
# import hygiene: kernels.ref is internal to the kernels package
# ---------------------------------------------------------------------------

def test_no_direct_kernels_ref_imports_outside_kernels_package():
    """Non-kernel modules consume the staged ops through kernels.ops (the
    interpret-dispatch seam) or the fused entry points — never the raw
    reference module (mirrors the CI grep gate)."""
    src = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
    pat = re.compile(r"(from\s+[\w.]*kernels\s+import\s+[\w,\s]*\bref\b"
                     r"|[\w.]*kernels\.ref\b)")
    offenders = []
    for py in src.rglob("*.py"):
        if "kernels" in py.parts:
            continue
        for i, line in enumerate(py.read_text().splitlines(), 1):
            if pat.search(line) and not line.lstrip().startswith("#"):
                offenders.append(f"{py.relative_to(src)}:{i}: {line.strip()}")
    assert not offenders, "\n".join(offenders)

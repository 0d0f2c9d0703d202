"""Hypothesis property tests on the system's invariants.

Properties proved over randomized inputs:
  * pack/unpack roundtrip preserves signs exactly;
  * the packed majority equals the dense Section-2 equations for any W;
  * majority is permutation-invariant in the worker axis;
  * unanimous workers always win the vote; flipping all signs negates u;
  * traffic accounting is a convex combination of per-mode ratios;
  * the CUSUM guard triggers on sustained growth and stays quiet on
    decreasing loss.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional test dependency (pip install .[test])")
from hypothesis import given, settings, strategies as st

from repro import kernels as K
from repro.kernels import fused, ref
from repro.core import (AdmissionPlan, AggregationMode, CusumGuard,
                        GroupPolicy, bits_per_element, plan_traffic_ratio)

wstrat = st.integers(min_value=1, max_value=16)
rows = st.sampled_from([32, 64, 96])


@settings(max_examples=25, deadline=None)
@given(m=rows, seed=st.integers(0, 2**31 - 1))
def test_pack_unpack_roundtrip(m, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(m, 128), jnp.float32)
    words = K.pack_signs(x)
    bits = ref.unpack_bits(words)
    np.testing.assert_array_equal(np.asarray(bits),
                                  (np.asarray(x) > 0).astype(np.uint32))


@settings(max_examples=25, deadline=None)
@given(w=wstrat, seed=st.integers(0, 2**31 - 1))
def test_packed_majority_equals_dense(w, seed):
    rng = np.random.RandomState(seed)
    n = 32 * 128
    grads = rng.randn(w, n).astype(np.float32)
    stack = jnp.stack([K.pack_signs(ref.to_plane(jnp.asarray(g)))
                       for g in grads])
    gate = fused.local_gate_words(stack.shape[1], ternary=False)
    sw, mw = fused.vote_combine(stack, gate, num_workers=w)
    u = ref.from_plane(K.unpack_ternary(sw, mw), n)
    want = ref.gbinary_aggregate_dense(jnp.asarray(grads))
    np.testing.assert_array_equal(np.asarray(u), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(w=st.integers(2, 8), seed=st.integers(0, 2**31 - 1),
       perm_seed=st.integers(0, 2**31 - 1))
def test_majority_permutation_invariant(w, seed, perm_seed):
    rng = np.random.RandomState(seed)
    grads = rng.randn(w, 32 * 128).astype(np.float32)
    perm = np.random.RandomState(perm_seed).permutation(w)
    a = ref.gbinary_aggregate_dense(jnp.asarray(grads))
    b = ref.gbinary_aggregate_dense(jnp.asarray(grads[perm]))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=15, deadline=None)
@given(w=wstrat, seed=st.integers(0, 2**31 - 1))
def test_unanimous_vote_and_sign_flip(w, seed):
    rng = np.random.RandomState(seed)
    base = np.abs(rng.randn(32 * 128)).astype(np.float32) + 1e-3
    grads = np.tile(base, (w, 1))
    u = np.asarray(ref.gbinary_aggregate_dense(jnp.asarray(grads)))
    assert np.all(u == 1.0)
    u_neg = np.asarray(ref.gbinary_aggregate_dense(jnp.asarray(-grads)))
    np.testing.assert_array_equal(u_neg, -u)


@settings(max_examples=30, deadline=None)
@given(nb=st.integers(1, 10**9), nh=st.integers(1, 10**7),
       mode=st.sampled_from([AggregationMode.G_BINARY,
                             AggregationMode.G_TERNARY]))
def test_traffic_ratio_convex_combination(nb, nh, mode):
    sizes = {"backbone": nb, "head": nh}
    plan = AdmissionPlan.from_dict(
        {"backbone": GroupPolicy(mode)},
        default=GroupPolicy(AggregationMode.FP32))
    r = plan_traffic_ratio(sizes, plan)
    fb = nb / (nb + nh)
    expect = fb * bits_per_element(mode) / 32.0 + (1 - fb) * 1.0
    assert math.isclose(r, expect, rel_tol=1e-12)
    assert bits_per_element(mode) / 32.0 <= r <= 1.0


@settings(max_examples=20, deadline=None)
@given(start=st.floats(0.5, 5.0), slope=st.floats(0.01, 0.2))
def test_cusum_triggers_on_sustained_growth(start, slope):
    g = CusumGuard(kappa=0.005, h=0.2)
    triggered = False
    for i in range(200):
        if g.update(start + slope * i):
            triggered = True
            break
    assert triggered


@settings(max_examples=20, deadline=None)
@given(start=st.floats(0.5, 5.0), decay=st.floats(0.9, 0.999),
       noise_seed=st.integers(0, 2**31 - 1))
def test_cusum_quiet_on_decreasing_loss(start, decay, noise_seed):
    rng = np.random.RandomState(noise_seed)
    g = CusumGuard(kappa=0.01, h=0.25)
    loss = start
    for _ in range(200):
        loss *= decay
        assert not g.update(loss + abs(rng.randn()) * 1e-4)


@settings(max_examples=10, deadline=None)
@given(m=rows, phase=st.integers(0, 2))
def test_ternary_gate_keeps_two_of_three(m, phase):
    words = ref.ternary_gate_words(m, phase=phase)
    bits = np.asarray(ref.unpack_bits(words)).reshape(-1)
    idx = np.arange(bits.size)
    np.testing.assert_array_equal(bits, ((idx + phase) % 3 != 2))
    kept = bits.mean()
    assert abs(kept - 2 / 3) < 1e-3


# ---------------------------------------------------------------------------
# pack/unpack plane layout on ragged sizes (padding/truncation edges the
# fused bucket path relies on)
# ---------------------------------------------------------------------------

#: deliberately awkward sizes: 1, sub-tile, off-by-one around the
#: LANE*32 tile boundary, and multi-tile ragged tails
ragged_n = st.one_of(
    st.integers(1, 2 * ref.TILE + 1),
    st.sampled_from([ref.TILE - 1, ref.TILE, ref.TILE + 1,
                     2 * ref.TILE - 1, 3 * ref.TILE + 17, ref.LANE + 3]))


@settings(max_examples=40, deadline=None)
@given(n=ragged_n, seed=st.integers(0, 2**31 - 1))
def test_plane_roundtrip_any_size(n, seed):
    """to_plane zero-pads to the canonical tile; from_plane drops exactly
    the padding — a lossless round trip for every ragged size."""
    rng = np.random.RandomState(seed)
    flat = jnp.asarray(rng.randn(n), jnp.float32)
    plane = ref.to_plane(flat)
    assert plane.shape == (ref.padded_len(n) // ref.LANE, ref.LANE)
    assert plane.shape[0] % ref.PACK == 0          # word-plane compatible
    back = ref.from_plane(plane, n)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(flat))
    # the padding region is exactly zero (sign bit 0 = non-positive)
    pad = np.asarray(plane).reshape(-1)[n:]
    assert not pad.size or not pad.any()


@settings(max_examples=40, deadline=None)
@given(n=ragged_n, seed=st.integers(0, 2**31 - 1))
def test_sign_pack_roundtrip_ragged(n, seed):
    """pack_signs on a ragged payload: the first n bits are the signs,
    every padding bit is 0 (zero padding is non-positive)."""
    rng = np.random.RandomState(seed)
    flat = jnp.asarray(rng.randn(n), jnp.float32)
    words = ref.sign_pack(ref.to_plane(flat))
    bits = np.asarray(ref.unpack_bits(words)).reshape(-1)
    np.testing.assert_array_equal(bits[:n],
                                  (np.asarray(flat) > 0).astype(np.uint32))
    assert not bits[n:].any()


@settings(max_examples=40, deadline=None)
@given(n=ragged_n, seed=st.integers(0, 2**31 - 1),
       extra_rows=st.integers(0, 3))
def test_gate_words_from_mask_roundtrip_ragged(n, seed, extra_rows):
    """gate_words_from_mask on sizes not a multiple of the word-plane
    tile: bits [0, n) reproduce the mask, canonical padding keeps = 1,
    and pad_words right-pads with all-ones rows (the all_to_all row
    padding of the fused packed schedule)."""
    rng = np.random.RandomState(seed)
    keep = rng.rand(n) < 0.5
    base_rows = ref.padded_len(n) // ref.LANE // ref.PACK
    pad_words = base_rows + extra_rows
    words = ref.gate_words_from_mask(keep, pad_words=pad_words)
    assert words.shape == (pad_words, ref.LANE)
    bits = np.asarray(ref.unpack_bits(words)).reshape(-1)
    np.testing.assert_array_equal(bits[:n], keep.astype(np.uint32))
    # canonical padding and pad_words rows all keep (gate never zeroes
    # out-of-payload elements — unpack drops them, value irrelevant)
    assert bits[n:].all()


@settings(max_examples=25, deadline=None)
@given(n=ragged_n, phase=st.integers(0, 2), seed=st.integers(0, 2**31 - 1))
def test_gate_words_match_bucket_gate_mask(n, phase, seed):
    """The packed gate words and the BucketGate host mask/device vector
    agree bit-for-bit on ragged per-leaf segments — the invariant that
    keeps the fused ternary path identical across schedules."""
    from repro.core.buckets import BucketGate
    n2 = max(1, n // 2)
    gate = BucketGate(segments=((n, phase), (n2, phase)))
    mask = gate.mask()
    assert mask.shape == (n + n2,)
    words = ref.gate_words_from_mask(mask)
    bits = np.asarray(ref.unpack_bits(words)).reshape(-1)
    np.testing.assert_array_equal(bits[:n + n2], mask.astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(gate.vector(jnp.float32)), mask.astype(np.float32))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), perm_seed=st.integers(0, 2**31 - 1),
       bucket_bytes=st.sampled_from([1, 4096, 256 * 1024]))
def test_bucket_layout_insertion_order_invariant(seed, perm_seed,
                                                 bucket_bytes):
    """plan_buckets is a pure function of the *canonical* tree, not of
    dict insertion order: permuting the order keys were inserted in
    yields a bit-identical BucketLayout (pytree flattening sorts dict
    keys, and the planner adds no ordering of its own)."""
    import jax

    from repro.core import plan_buckets, resolve_policies

    rng = np.random.RandomState(seed)
    sds = jax.ShapeDtypeStruct
    names = ["wte", "head_w", "ln_scale", "h00/qkv", "h00/proj",
             "h01/fc_in", "h01/fc_out", "bias"]
    shapes = [(rng.randint(1, 64), rng.randint(1, 64)) for _ in names]
    tree = {n: sds(s, "float32") for n, s in zip(names, shapes)}

    perm = np.random.RandomState(perm_seed).permutation(len(names))
    permuted = {}
    for i in perm:
        permuted[names[i]] = tree[names[i]]
    assert list(permuted) != list(tree) or (perm == np.arange(
        len(names))).all()

    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY)
    layouts = []
    for t in (tree, permuted):
        policies = resolve_policies(t, plan)
        layouts.append(plan_buckets(t, policies,
                                    bucket_bytes=bucket_bytes))
    assert layouts[0] == layouts[1]

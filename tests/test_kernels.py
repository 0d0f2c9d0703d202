"""Per-kernel sweeps against the pure-jnp oracles (paper Section 6).

The paper's functional-correctness protocol: mode-specific expected values —
identity uses byte-exact read-back, G-Binary/G-Ternary use a
transformation-aware oracle computing the Section 2 reduction.  Here the
vote chain's Pallas kernels (interpret mode on CPU) — ``pack_signs`` ->
``vote_combine`` -> ``unpack_ternary`` — are swept over shapes/dtypes and
compared bit-exactly against kernels/ref.py; ``vote_combine`` and the
fused entry points have their own sweeps in tests/test_fused_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.kernels import fused, ref

SHAPES = [(32, 128), (64, 128), (256, 128), (1024, 128), (4096, 128)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


@pytest.mark.parametrize("m,lane", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sign_pack_matches_ref(rng, m, lane, dtype):
    x = jnp.asarray(rng.randn(m, lane), dtype)
    got = K.pack_signs(x, interpret=True)
    want = ref.sign_pack(x)
    assert got.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [32, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_unpack_ternary_matches_ref(rng, m, dtype):
    counts = jnp.asarray(rng.randint(0, 9, (m, 128)), jnp.int32)
    sw, mw = ref.majority_decode(counts, 8)
    got = K.unpack_ternary(sw, mw, dtype=dtype, interpret=True)
    want = ref.unpack_ternary(sw, mw, dtype=dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    vals = set(np.unique(np.asarray(got, np.float32)))
    assert vals <= {-1.0, 0.0, 1.0}


def _all_ones_gate(num_words):
    return fused.local_gate_words(num_words, ternary=False)


def test_end_to_end_packed_pipeline_equals_dense_oracle(rng):
    """pack -> vote_combine -> unpack == the Section 2 equations."""
    w, n = 8, 32 * 128 * 3
    grads = rng.randn(w, n).astype(np.float32)
    planes = [ref.to_plane(jnp.asarray(grads[i])) for i in range(w)]
    stack = jnp.stack([K.pack_signs(p, interpret=True) for p in planes])
    # G-Binary
    sw, mw = fused.vote_combine(stack, _all_ones_gate(stack.shape[1]),
                                num_workers=w, interpret=True)
    u = ref.from_plane(K.unpack_ternary(sw, mw, interpret=True), n)
    want = ref.gbinary_aggregate_dense(jnp.asarray(grads))
    np.testing.assert_array_equal(np.asarray(u), np.asarray(want))
    # G-Ternary (2-of-3 gate)
    sw, mw = fused.vote_combine(
        stack, K.ternary_gate_words(planes[0].shape[0]), num_workers=w,
        interpret=True)
    u = ref.from_plane(K.unpack_ternary(sw, mw, interpret=True), n)
    want = ref.gternary_aggregate_dense(jnp.asarray(grads))
    np.testing.assert_array_equal(np.asarray(u), np.asarray(want))


def test_identity_readback_byte_exact(rng):
    """Identity mode: packed payload written and read back byte-for-byte."""
    x = jnp.asarray(rng.randn(256, 128), jnp.float32)
    words = K.pack_signs(x, interpret=True)
    roundtrip = jnp.asarray(np.asarray(words))   # host write + read back
    np.testing.assert_array_equal(np.asarray(words), np.asarray(roundtrip))


@pytest.mark.parametrize("w", [128, 256])
def test_popcount_counts_survive_wide_worker_groups(rng, w):
    """Regression: the int8 count accumulator wrapped for W > 127 (256
    unanimous positive votes counted as 0, flipping the majority)."""
    plane = jnp.ones((32, 128), jnp.float32)
    stack = jnp.stack([K.pack_signs(plane, interpret=True)] * w)
    sw, mw = fused.vote_combine(stack, _all_ones_gate(stack.shape[1]),
                                num_workers=w, interpret=True)
    # unanimous +1: every sign and every mask bit set
    assert np.all(np.asarray(sw) == 0xFFFFFFFF)
    assert np.all(np.asarray(mw) == 0xFFFFFFFF)
    want_s, want_m = ref.vote_combine(stack, w, _all_ones_gate(stack.shape[1]))
    np.testing.assert_array_equal(np.asarray(sw), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(mw), np.asarray(want_m))
    u = K.unpack_ternary(sw, mw, interpret=True)
    assert np.all(np.asarray(u) == 1.0)


def test_vote_tie_decodes_to_zero():
    """Even worker count, exact tie -> a = 0 -> u = 0 (paper Section 2)."""
    w = 8
    grads = np.ones((w, 32 * 128), np.float32)
    grads[: w // 2] *= -1.0
    planes = [ref.to_plane(jnp.asarray(g)) for g in grads]
    stack = jnp.stack([K.pack_signs(p, interpret=True) for p in planes])
    sw, mw = fused.vote_combine(stack, _all_ones_gate(stack.shape[1]),
                                num_workers=w, interpret=True)
    u = ref.from_plane(K.unpack_ternary(sw, mw, interpret=True),
                       grads.shape[1])
    assert np.all(np.asarray(u) == 0.0)

"""Error-feedback (EF-signSGD) path: residual math + end-to-end benefit.

Beyond-paper option (DESIGN.md): votes taken on g + e with residual
e' = x - mean|x| * sign(x).  Properties: the residual shrinks what the
compressor discarded, and EF strictly reduces long-run compression error
on a fixed gradient (classic EF contraction).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lowbit import _ef_inject, _ef_update, lowbit_vote_psum
from repro.core import init_ef_states, resolve_policies
from repro.core import AdmissionPlan, AggregationMode, GroupPolicy
from repro.fabric import Fabric


def test_residual_update_formula(rng):
    g = jnp.asarray(rng.randn(1024), jnp.float32)
    ef = jnp.zeros_like(g)
    g_eff, ef_in = _ef_inject(g, ef)
    np.testing.assert_array_equal(np.asarray(g_eff), np.asarray(g))
    new_ef = _ef_update(g_eff, ef_in)
    beta = float(jnp.mean(jnp.abs(g)))
    want = np.asarray(g) - beta * np.sign(np.asarray(g))
    np.testing.assert_allclose(np.asarray(new_ef), want, rtol=1e-6)


def test_ef_accumulates_what_compression_discards(rng):
    """On a constant gradient, sum of sent signals converges toward g."""
    g = jnp.asarray(rng.randn(4096) * 0.5, jnp.float32)
    ef = jnp.zeros_like(g)
    sent_total = np.zeros(4096, np.float32)
    for _ in range(50):
        x = g + ef
        beta = jnp.mean(jnp.abs(x))
        sent = beta * jnp.sign(x)
        sent_total += np.asarray(sent)
        ef = x - sent
    avg_sent = sent_total / 50
    err = np.linalg.norm(avg_sent - np.asarray(g)) / np.linalg.norm(np.asarray(g))
    assert err < 0.15, err     # EF closes most of the compression error


def test_ef_states_threaded_through_aggregation(rng):
    """Fabric.aggregate round-trips EF sentinels and residuals."""
    params = {"backbone": {"w": jnp.zeros((64, 64))},
              "head": {"w": jnp.zeros((64, 8))}}
    plan = AdmissionPlan.from_dict(
        {"backbone": GroupPolicy(AggregationMode.G_BINARY,
                                 error_feedback=True)},
        default=GroupPolicy(AggregationMode.FP32))
    policies = resolve_policies(params, plan)
    ef = init_ef_states(params, policies)
    assert ef["backbone"]["w"].shape == (1, 64, 64)   # enabled: (W,*shape)
    assert ef["head"]["w"].shape == ()                # sentinel

    grads = jax.tree.map(lambda p: jnp.asarray(
        rng.randn(*p.shape), jnp.float32), params)
    agg, new_ef = Fabric().aggregate(grads, policies, ef=ef)
    # W=1: aggregate is sign(g); residual is g - mean|g|*sign(g)
    np.testing.assert_array_equal(np.asarray(agg["backbone"]["w"]),
                                  np.sign(np.asarray(grads["backbone"]["w"])))
    assert new_ef["backbone"]["w"].shape == (1, 64, 64)
    assert float(jnp.sum(jnp.abs(new_ef["backbone"]["w"]))) > 0
    assert new_ef["head"]["w"].shape == ()            # sentinel untouched
    np.testing.assert_allclose(np.asarray(agg["head"]["w"]),
                               np.asarray(grads["head"]["w"]), rtol=1e-6)

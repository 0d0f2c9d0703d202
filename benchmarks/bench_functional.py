"""Paper Section 6: functional correctness with mode-specific oracles.

The packed-sign validation: write sign packets for eight virtual workers,
read back under identity / G-Binary / G-Ternary, compare each against its
transformation-aware oracle (identity: byte-exact; low-bit: the Section 2
reduction).  Reported value is the end-to-end pipeline latency on the
functional path; `derived` records the exact-match verdicts.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels as K
from repro.core import AdmissionPlan, AggregationMode, GroupPolicy
from repro.fabric import Fabric, aggregate_tree
from repro.kernels import fused as KF, ref


def _fabric_session_row():
    """End-to-end session check: Fabric.aggregate under a mixed plan.

    Host-local session (one worker): the G-Binary backbone reduces to
    sign(g), the FP32 head to g itself — mode-specific oracles through
    the full registry-dispatch path.
    """
    rng = np.random.RandomState(11)
    grads = {"backbone": {"w": jnp.asarray(rng.randn(256, 128), jnp.float32)},
             "head": {"w": jnp.asarray(rng.randn(128, 16), jnp.float32)}}
    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                         error_feedback=False)
    fabric = Fabric()                       # mesh-less: 1 virtual worker
    t0 = time.perf_counter()
    agg, _ = fabric.aggregate(grads, plan)
    jax.block_until_ready(agg)
    t_us = (time.perf_counter() - t0) * 1e6
    ok = (np.array_equal(np.asarray(agg["backbone"]["w"]),
                         np.sign(np.asarray(grads["backbone"]["w"])))
          and np.allclose(np.asarray(agg["head"]["w"]),
                          np.asarray(grads["head"]["w"])))
    return ("functional/fabric_session_mixed_plan", t_us, f"oracle_exact={ok}")


def _fused_bucketing_rows():
    """Bucketed vs per-leaf aggregation on the quickstart model.

    Plans the bucket layout over the real quickstart param tree
    (qwen3_0p6b smoke) under the paper's recovered operating point, and
    reports the collective-launch reduction — O(leaves) per-leaf vs
    O(buckets) fused — plus measured host-local dispatch latency and a
    bit-for-bit cross-check against the per-leaf reference
    (``aggregate_tree``).
    """
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config("qwen3_0p6b", smoke=True)
    params = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY)
    fabric = Fabric()                        # host-local session
    layout = fabric.layout_for(params, plan)
    n_leaves, n_launches = layout.num_leaves, layout.num_launches

    # concrete grads with the same structure: time both paths end to end
    rng = np.random.RandomState(3)
    grads = jax.tree.map(
        lambda s: jnp.asarray(rng.randn(*s.shape), jnp.float32), params)

    policies = fabric.resolve(grads, plan)

    def timed(run):
        agg, _ = run()                                     # warm caches
        jax.block_until_ready(agg)
        t0 = time.perf_counter()
        agg, _ = run()
        jax.block_until_ready(agg)
        return agg, (time.perf_counter() - t0) * 1e6

    per_leaf, t_leaf = timed(
        lambda: aggregate_tree(fabric.context, grads, policies))
    fused, t_fused = timed(lambda: fabric.aggregate(grads, plan))
    exact = all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        per_leaf, fused)))
    return [
        ("functional/fused_launch_count", 0.0,
         f"leaves={n_leaves} launches={n_launches} "
         f"buckets={len(layout.buckets)}"),
        ("functional/per_leaf_aggregate", t_leaf, f"launches={n_leaves}"),
        ("functional/fused_aggregate", t_fused,
         f"launches={n_launches} bitwise_equal={exact}"),
    ]


def rows():
    rng = np.random.RandomState(7)
    w, n = 8, 64 * 128 * 32                      # 64 word rows
    grads = rng.randn(w, n).astype(np.float32)
    planes = [ref.to_plane(jnp.asarray(g)) for g in grads]

    # identity: byte-for-byte read-back of the packed payload
    words = [K.pack_signs(p) for p in planes]
    ident_ok = all(np.array_equal(np.asarray(x), np.asarray(ref.sign_pack(p)))
                   for x, p in zip(words, planes))

    t0 = time.perf_counter()
    stack = jnp.stack(words)
    ones = KF.local_gate_words(stack.shape[1], ternary=False)
    sw_b, mw_b = KF.vote_combine(stack, ones, num_workers=w)
    u_bin = ref.from_plane(K.unpack_ternary(sw_b, mw_b), n)
    jax.block_until_ready(u_bin)
    t_bin = (time.perf_counter() - t0) * 1e6

    bin_ok = np.array_equal(np.asarray(u_bin),
                            np.asarray(ref.gbinary_aggregate_dense(
                                jnp.asarray(grads))))

    gate = K.ternary_gate_words(planes[0].shape[0])
    sw_t, mw_t = KF.vote_combine(stack, gate, num_workers=w)
    u_ter = ref.from_plane(K.unpack_ternary(sw_t, mw_t), n)
    ter_ok = np.array_equal(np.asarray(u_ter),
                            np.asarray(ref.gternary_aggregate_dense(
                                jnp.asarray(grads))))

    return [
        ("functional/identity_readback", 0.0, f"byte_exact={ident_ok}"),
        ("functional/gbinary_pipeline", t_bin, f"oracle_exact={bin_ok}"),
        ("functional/gternary_pipeline", t_bin, f"oracle_exact={ter_ok}"),
        _fabric_session_row(),
        *_fused_bucketing_rows(),
    ]

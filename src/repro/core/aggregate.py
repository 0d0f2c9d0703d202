"""Error-feedback state and policy trees for tree-level aggregation.

Aggregation itself lives in :mod:`repro.fabric` — a
:class:`~repro.fabric.Fabric` session owns dispatch (via the
schedule-backend registry), the aggregation context, and EF-state
handling.  This module keeps :func:`init_ef_states`, the worker-local EF
initializer the session builds on, and :func:`make_policy_tree`.

Error-feedback residual state (beyond paper, optional) is carried as a
pytree matching the params: EF-enabled leaves hold a ``(1, *shape)`` local
residual (globally ``(W, *shape)`` sharded over the DP axes); disabled
leaves hold a scalar sentinel so the tree structure stays static across
plans (one jit cache entry per plan signature, not per step).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .buckets import AdmissionPlan, GroupRules, resolve_policies


def init_ef_states(params: Any, policies: Any, dtype=jnp.float32) -> Any:
    """Residual tree: zeros like (1, *shape) where EF is on, scalar 0 else."""
    def make(p, pol):
        if pol.error_feedback:
            return jnp.zeros((1,) + tuple(p.shape), dtype)
        return jnp.zeros((), dtype)
    return jax.tree.map(make, params, policies, is_leaf=None)


def make_policy_tree(params: Any, plan: AdmissionPlan,
                     pspecs: Any | None = None,
                     rules: GroupRules | None = None) -> Any:
    """Convenience re-export: params + plan (+ specs) -> LeafPolicy tree."""
    return resolve_policies(params, plan, pspecs=pspecs, rules=rules)

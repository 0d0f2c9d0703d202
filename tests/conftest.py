"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches see
the real single CPU device; multi-device tests spawn subprocesses or use
their own flags via module isolation (tests/test_distributed.py)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(0)



@pytest.fixture
def aggregate():
    """``aggregate(fabric, grads, plan, ef=None, fused=True)``: the
    session's bucketed ``Fabric.aggregate`` or, with ``fused=False``, the
    per-leaf reference ``aggregate_tree`` under the same policies."""
    from repro.core import AdmissionPlan
    from repro.fabric import aggregate_tree

    def run(fabric, grads, plan, ef=None, *, fused=True):
        if fused:
            return fabric.aggregate(grads, plan, ef=ef)
        policies = (fabric.resolve(grads, plan)
                    if isinstance(plan, AdmissionPlan) else plan)
        return aggregate_tree(fabric.context, grads, policies, ef_states=ef)
    return run

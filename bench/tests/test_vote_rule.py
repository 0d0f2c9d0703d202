"""The reference votes exactly the leaves the program votes.

The reference states its vote rule on its own (``reference.common``); the
program admits a leaf by ``repro.core.buckets.GroupRules`` and the plan's
policy for the leaf's group.  Over every architecture the program knows,
at its smoke and its full shapes, and over the benchmark's
configurations, the two agree leaf for leaf under both plans.
"""
import json

import jax
import pytest

from conftest import BENCH
from harness import spec
from reference.common import path_of, voted_rule

PLANS = ("gbin_packed", "fp32")


def _arch_cases():
    from repro.configs import ARCH_IDS
    return [(a, smoke) for a in ARCH_IDS for smoke in (True, False)]


def _bench_configs():
    """The configuration files BENCHMARK.json names."""
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return sorted(c["file"] for c in bm["configs"])


def _leaves(cfg):
    from repro.models import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return [kp for kp, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def _disagreements(cfg, plan) -> list:
    """The leaves whose vote the program and the reference decide apart,
    with a list index printed either way."""
    from repro.core.buckets import GroupRules, path_name
    from repro.core.modes import AggregationMode, canonical_mode
    from repro.fabric.control import plan_presets
    policy, rules, ref = plan_presets()[plan], GroupRules(), voted_rule(plan)
    out = []
    for kp in _leaves(cfg):
        name = path_name(kp)
        mode = canonical_mode(policy.policy_for(rules.group_of(name)).mode)
        program = mode != AggregationMode.FP32
        for p in {name, path_of(kp)}:
            if ref(p) != program:
                out.append((p, program))
    return out


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("arch, smoke", _arch_cases())
def test_vote_rule_is_the_programs_for_every_architecture(arch, smoke, plan):
    from repro.configs import get_config
    assert _disagreements(get_config(arch, smoke=smoke), plan) == []


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("name", _bench_configs())
def test_vote_rule_is_the_programs_for_the_bench_configs(name, plan):
    config = spec.load_json(BENCH.parent / name)
    assert _disagreements(spec.model_config(config), plan) == []


def _old_rule(path):
    """The rule the benchmark's limits and recorded readings were set
    under: every leaf but the embedding, head and norms."""
    return not any(w in path for w in ("embed", "head", "norm"))


@pytest.mark.parametrize("name", _bench_configs())
def test_bench_configs_vote_the_leaves_they_voted(name):
    """The configurations of the accepted cells vote the same leaves as
    under the older rule, so their limits still hold."""
    config = spec.load_json(BENCH.parent / name)
    paths = [path_of(kp) for kp in _leaves(spec.model_config(config))]
    rule = voted_rule("gbin_packed")
    assert [p for p in paths if rule(p)] == [p for p in paths if _old_rule(p)]
    assert any(rule(p) for p in paths)


@pytest.mark.parametrize("path, voted", [
    ("layers/attn/wq", True),
    ("layers/mlp/w_up", True),
    ("layers/attn/bq", True),                  # named with no "bias"
    ("layers/attn/q_bias", False),
    ("layers/moe/router", False),              # a router
    ("layers/3/moe/router", False),
    ("layers/[3]/moe/router", False),
    ("layers/moe/gate_weights", False),
    ("layers/moe/routerless", True),           # a segment, not a prefix
    ("head/w", False),
    ("layers/ssm_head/w_in", True),            # "head" only as a segment
    ("blocks/0/mlstm/w_up", True),
    ("blocks/[0]/mlstm/b_gates_bias", False),
    ("layers/ssm_head/dt_bias", False),
    ("layers/ssm_head/skip_scale", False),
    ("final_norm/scale", False),
    ("embed/patch_proj/w", False),
    ("encoder/ln_1/w", False),
])
def test_vote_rule_cases(path, voted):
    assert voted_rule("gbin_packed")(path) is voted
    assert voted_rule("fp32")(path) is False


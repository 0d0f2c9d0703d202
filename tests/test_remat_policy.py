"""The scanned layers' remat policy: ``remat_policy="auto"`` saves the
un-batched dot outputs (projections and MLP matmuls) when they fit a share
of the device's memory, and compiles the same step as ``"full"`` when they
do not or the device reports no memory limit (the CPU).

The memory limit is injected through ``transformer._device_bytes_limit``.
"""
import dataclasses
import functools
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

B, S = 2, 16
BIG = 16 << 30          # admits every tiny config's residuals
SMALL = 4096            # admits none of them


def _cfg(arch: str, policy: str):
    return dataclasses.replace(get_config(arch, smoke=True), remat=True,
                               dtype="bfloat16", remat_policy=policy)


def _batch(cfg):
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_seq, cfg.d_model))
    return batch


def _grad_and_hlo(arch, policy):
    cfg = _cfg(arch, policy)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    step = jax.jit(jax.grad(lambda p: T.loss_fn(p, cfg, batch)))
    compiled = step.lower(params).compile()
    return compiled(params), compiled.as_text()


@functools.lru_cache(maxsize=None)
def _full(arch):
    """``"full"``'s gradients and HLO, which never read the limit."""
    return _grad_and_hlo(arch, "full")


def _remat_dots(hlo: str) -> int:
    """dot ops of the backward's recompute."""
    return sum(1 for line in hlo.splitlines()
               if re.search(r"\sdot\(", line)
               and "rematted_computation" in line)


def _program(hlo: str) -> str:
    """The compiled module without source locations: instructions, shapes
    and op names only."""
    keep = [line for line in hlo.splitlines()
            if line.startswith((" ", "%", "ENTRY", "}", "HloModule"))]
    return re.sub(r' (source_\w+|stack_frame_id)=("[^"]*"|\d+)', "",
                  "\n".join(keep))


ARCHS = ["qwen3_0p6b", "deepseek_moe_16b", "whisper_tiny"]


@pytest.mark.parametrize("arch", ARCHS)
def test_admitted_auto_saves_dots_with_equal_gradients(monkeypatch, arch):
    g_full, h_full = _full(arch)
    monkeypatch.setattr(T, "_device_bytes_limit", lambda: BIG)
    g_auto, h_auto = _grad_and_hlo(arch, "auto")
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_auto)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert 0 < _remat_dots(h_auto) < _remat_dots(h_full)


@pytest.mark.parametrize("limit", [None, SMALL], ids=["no_limit", "over"])
@pytest.mark.parametrize("arch", ARCHS)
def test_rejected_auto_compiles_the_full_step(monkeypatch, arch, limit):
    _, h_full = _full(arch)
    monkeypatch.setattr(T, "_device_bytes_limit", lambda: limit)
    _, h_auto = _grad_and_hlo(arch, "auto")
    assert _program(h_auto) == _program(h_full)


def _dense_layer(cfg):
    """A dense layer's scan body, its carry's shape, the stacked layers and
    one layer's slice of them."""
    xs = T.init_params(jax.random.PRNGKey(0), cfg)["layers"]
    x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         xs)
    positions = jnp.arange(S)[None, :]

    def body(h, lp):
        return T._layer_forward(lp, h, cfg, True, positions), None
    return body, x, xs, layer


def test_saved_bytes_match_the_hand_count():
    """q, k, v, the ``wo`` output, gate and up; the ``w_down`` output is
    read by nothing in the backward pass."""
    cfg = _cfg("qwen3_0p6b", "auto")
    h, kv, hd, d, ff = (cfg.num_heads, cfg.num_kv_heads, cfg.hd,
                        cfg.d_model, cfg.d_ff)
    hand = B * S * (h * hd + 2 * kv * hd + d + 2 * ff) * 2
    body, x, _, layer = _dense_layer(cfg)
    assert T.saved_dot_bytes(body, x, layer) == hand


def test_budget_is_a_share_of_the_device_limit(monkeypatch):
    cfg = _cfg("qwen3_0p6b", "auto")
    body, x, xs, layer = _dense_layer(cfg)
    total = cfg.num_layers * T.saved_dot_bytes(body, x, layer)
    at = int(total / T.SAVED_DOTS_SHARE)
    for limit, policy in [(at, T._SAVE_DOTS), (at - 8, None), (None, None)]:
        monkeypatch.setattr(T, "_device_bytes_limit", lambda: limit)
        assert T._remat_policy(body, cfg, x, xs) is policy


def test_named_policies_and_unknown_name(monkeypatch):
    monkeypatch.setattr(T, "_device_bytes_limit", lambda: BIG)
    body, x, xs, _ = _dense_layer(_cfg("qwen3_0p6b", "auto"))
    assert get_config("qwen3_0p6b").remat_policy == "auto"
    assert T._remat_policy(body, _cfg("qwen3_0p6b", "full"), x, xs) is None
    assert (T._remat_policy(body, _cfg("qwen3_0p6b", "dots"), x, xs)
            is jax.checkpoint_policies.checkpoint_dots)
    with pytest.raises(ValueError, match="remat_policy"):
        T._remat_policy(body, _cfg("qwen3_0p6b", "fast"), x, xs)


def test_per_device_count_halves_on_a_2x1_mesh():
    """On a (2, 1) mesh each device holds half the batch: the count halves
    both where the batch axis is an automatic mesh axis (global shapes) and
    inside the train step's ``shard_map`` (per-device shapes), which must
    not divide twice."""
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, {SRC!r})
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import SyntheticLMStream
    from repro.fabric import Fabric
    from repro.fabric.control import plan_presets
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.optim import AdamW
    from repro.runtime import Trainer, TrainerConfig

    cfg = dataclasses.replace(get_config("qwen3_0p6b", smoke=True),
                              remat=True, dtype="bfloat16")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    positions = jnp.arange(16)[None, :]
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         params["layers"])
    def body(h, lp):
        return T._layer_forward(lp, h, cfg, True, positions), None
    x = jax.ShapeDtypeStruct((4, 16, cfg.d_model), jnp.bfloat16)
    one = T.saved_dot_bytes(body, x, layer)
    with jax.set_mesh(make_mesh((2, 1))):
        auto_axis = T.saved_dot_bytes(body, x, layer)

    seen = []
    count = T.saved_dot_bytes
    T.saved_dot_bytes = lambda *a: seen.append(count(*a)) or seen[-1]
    T._device_bytes_limit = lambda: 16 << 30
    for shape in ((1, 1), (2, 1)):
        mesh = make_mesh(shape)
        opt, plan = AdamW(peak_lr=1e-3, total_steps=10), plan_presets()["fp32"]
        data = SyntheticLMStream(vocab=cfg.vocab_size, seq_len=16, batch=4,
                                 seed=0)
        fab = Fabric(mesh, ("data",))
        tr = Trainer(cfg, mesh, opt, data, plan=plan, fabric=fab,
                     tcfg=TrainerConfig(dp_axes=("data",)))
        state = tr.init_state()
        step = fab.build_step(cfg, opt, plan, jax.eval_shape(lambda: state.params))
        step.step_fn.lower(state, data.batch_at(0))
    print(json.dumps([one, auto_axis] + seen))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    one, auto_axis, step_1x1, step_2x1 = json.loads(r.stdout.splitlines()[-1])
    assert auto_axis * 2 == one
    assert step_1x1 == one and step_2x1 * 2 == one

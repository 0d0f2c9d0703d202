"""The program's own spans and scopes, as a profiler trace and the compiled
HLO show them.

The training loop writes host spans (``trainer.*``) and the train step
names its stages (``model``, ``fabric.aggregate``, ``optimizer.apply``)
so that a trace says which layer a device op or a host stall belongs
to; every Pallas kernel carries a ``fabric_*`` name.  These tests run on
the CPU at a tiny size.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType

from repro import kernels as K
from repro.data import SyntheticLMStream
from repro.fabric import Fabric
from repro.fabric.control import plan_presets
from repro.kernels import fused
from repro.models import ModelConfig, init_params
from repro.optim import AdamW
from repro.runtime import Trainer, TrainerConfig


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _cfg():
    return ModelConfig(name="trace", family="dense", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=256, dtype="float32", remat=True)


def _data():
    return SyntheticLMStream(vocab=256, seq_len=16, batch=4, seed=0)


def _host_spans(logdir):
    """(name, start_ns, end_ns, stats) of every host event in the trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)) for ev in line.events]
    return out


def test_trainer_spans_each_step(tmp_path):
    tr = Trainer(_cfg(), _mesh(), AdamW(peak_lr=1e-3, total_steps=10),
                 _data(), plan=plan_presets()["gbin_packed"],
                 tcfg=TrainerConfig(dp_axes=("data",), log_interval=1000))
    tr.run(1)                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.run(3)
    spans = _host_spans(tmp_path)
    steps = [s for s in spans if s[0] == "trainer.step"]
    assert sorted(s[3]["step_num"] for s in steps) == [1, 2]
    for name in ("trainer.lookup", "trainer.data", "trainer.dispatch",
                 "trainer.sync", "trainer.bookkeeping"):
        for _, a, b, _ in steps:
            inside = [s for s in spans
                      if s[0] == name and a <= s[1] and s[2] <= b]
            assert len(inside) == 1, (name, len(inside))
    # the loop's step reads are synced too, between the steps
    assert len([s for s in spans if s[0] == "trainer.sync"]) > len(steps) * 2


def _op_names(hlo: str) -> list:
    return re.findall(r'op_name="([^"]+)"', hlo)


def test_train_step_scopes_in_compiled_hlo():
    cfg, mesh = _cfg(), _mesh()
    fab = Fabric(mesh, ("data",))
    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
    opt = AdamW(peak_lr=1e-3, total_steps=10)
    step = fab.build_step(cfg, opt, plan_presets()["gbin_packed"],
                          jax.eval_shape(lambda: params))
    tr = Trainer(cfg, mesh, opt, _data(), plan=plan_presets()["gbin_packed"],
                 fabric=fab, tcfg=TrainerConfig(dp_axes=("data",)))
    state = tr.init_state()
    batch = _data().batch_at(0)
    names = _op_names(step.step_fn.lower(state, batch).compile().as_text())
    fwd = [n for n in names if "jvp(model" in n and "transpose(" not in n]
    bwd = [n for n in names if "transpose(jvp(model" in n]
    agg = [n for n in names if "fabric.aggregate" in n]
    opt_ops = [n for n in names if "optimizer.apply" in n]
    assert fwd and bwd and agg and opt_ops
    # the scanned layers' recompute runs inside the backward pass
    assert any("rematted_computation" in n for n in bwd)
    for stage in ("bucket.pack", "encode", "vote", "decode",
                  "bucket.scatter"):
        assert any("fabric.aggregate/" in n and f"/{stage}/" in n
                   for n in agg), stage


KERNELS = ("fabric_sign_pack", "fabric_vote_pipeline",
           "fabric_vote_combine", "fabric_encode_pack_ef",
           "fabric_ef_residual", "fabric_int4_quant", "fabric_threshold_mask",
           "fabric_unpack_ternary")


def _kernel_calls():
    """One call of each kernel entry point, at a small shape."""
    m, r, w = 8 * K.PACK, 8, 2
    plane = jnp.ones((m, K.LANE), jnp.float32)
    words = jnp.ones((r, K.LANE), jnp.uint32)
    kw = {"interpret": True}
    return {
        "fabric_sign_pack": lambda: K.pack_signs(plane, **kw),
        "fabric_unpack_ternary": lambda: K.unpack_ternary(words, words, **kw),
        "fabric_encode_pack_ef": lambda: fused.encode_pack_ef(
            plane, plane, **kw),
        "fabric_vote_combine": lambda: fused.vote_combine(
            jnp.ones((w, r, K.LANE), jnp.uint32), words, num_workers=w,
            **kw),
        "fabric_vote_pipeline": lambda: fused.vote_pipeline(
            jnp.ones((w, m, K.LANE), jnp.float32), words, num_workers=w,
            **kw),
        "fabric_ef_residual": lambda: fused.ef_residual_plane(
            plane, jnp.float32(0.5), **kw),
        "fabric_int4_quant": lambda: fused.int4_quant_plane(plane, **kw),
        "fabric_threshold_mask": lambda: fused.threshold_mask_plane(
            plane, jnp.float32(0.5), **kw),
    }


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_names(sub)
    return out


@pytest.mark.parametrize("name", KERNELS)
def test_every_kernel_has_a_fabric_name(name):
    jaxpr = jax.make_jaxpr(_kernel_calls()[name])()
    names = _pallas_names(jaxpr.jaxpr)
    assert names == [name]

"""End-to-end system behaviour on the default (single-device) backend.

The full stack — config → model → admission plan → partial-manual
shard_map train step → optimizer → control plane — on a 1x1 mesh, where
W=1 majority voting degenerates to sign(g) (checked), plus the adaptive
control plane driving a live Trainer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import (AdmissionPlan, AggregationMode, Commander,
                        CusumGuard, Schedule, Supervisor)
from repro.data import SyntheticLMStream
from repro.fabric import make_controller
from repro.models import ModelConfig
from repro.optim import SgdMomentum
from repro.runtime import Trainer, TrainerConfig


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _cfg():
    return ModelConfig(name="sys", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                       dtype="float32", remat=False)


def test_full_stack_trains_and_tracks_traffic():
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=8, seed=0)
    tr = Trainer(_cfg(), _mesh(), SgdMomentum(peak_lr=0.2, total_steps=60),
                 data,
                 plan=AdmissionPlan.lowbit_backbone(
                     AggregationMode.G_BINARY, schedule=Schedule.PACKED_A2A),
                 tcfg=TrainerConfig(dp_axes=("data",), log_interval=1000))
    hist = tr.run(40)
    assert hist[-1]["loss"] < hist[0]["loss"]
    # mixed-plan traffic: low-bit backbone + FP32 everything else
    assert 0.0 < hist[-1]["traffic_ratio"] < 1.0
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_w1_majority_equals_sign():
    """With a single worker the Section-2 vote degenerates to sign(g)."""
    from repro.kernels import ref
    g = jnp.asarray(np.random.RandomState(0).randn(1, 4096), jnp.float32)
    u = ref.gbinary_aggregate_dense(g)
    np.testing.assert_array_equal(np.asarray(u), np.sign(np.asarray(g[0])))


def test_adaptive_control_plane_drives_trainer():
    """Warm-up on FP32, then the Commander admits from live diagnostics."""
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=8, seed=1)
    control = make_controller(
        "paper",
        commander=Commander(tau_binary=-1.0),   # always-admitting ladder
        supervisor=Supervisor(guard=CusumGuard(h=1e9)),
        warmup_steps=5)
    tr = Trainer(_cfg(), _mesh(), SgdMomentum(peak_lr=0.1, total_steps=40),
                 data, controller=control,
                 tcfg=TrainerConfig(dp_axes=("data",), log_interval=1000))
    hist = tr.run(12)
    plans = [h["plan"] for h in hist]
    assert "gbinary" not in plans[0], "must warm up on FP32"
    assert any("gbinary" in p for p in plans[6:]), "never admitted"
    assert "admitted" in [e.kind for e in control.events]
    # diagnostics were recorded during calibration steps
    assert any(k.startswith("cos/") for k in hist[0])


def test_plan_change_uses_compile_cache():
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=8, seed=2)
    tr = Trainer(_cfg(), _mesh(), SgdMomentum(peak_lr=0.1, total_steps=40),
                 data, plan=AdmissionPlan.fp32_all(),
                 tcfg=TrainerConfig(dp_axes=("data",), log_interval=1000))
    tr.run(3)
    tr.static_plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY)
    tr.run(6)
    tr.static_plan = AdmissionPlan.fp32_all()
    tr.run(9)
    # two distinct plan signatures -> exactly two cached compilations
    # (the per-plan jit cache lives in the Fabric session)
    assert len(tr.fabric._compiled) == 2

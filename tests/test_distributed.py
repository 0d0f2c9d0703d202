"""Distributed integration tests (subprocess-isolated: forced device count).

Each test runs a small script in a fresh interpreter with
``--xla_force_host_platform_device_count`` so the main pytest process keeps
its single real CPU device.  Covered:

  * aggregation schedules on a real mesh match the dense Section-2 oracle;
  * Trainer end-to-end: convergence + failure injection + deterministic
    restart (losses bitwise-equal with and without a mid-run crash);
  * elastic restart: checkpoint written on a (4,2) mesh restores onto a
    (2,4) mesh (reshard-on-load);
  * the production dry-run entry point succeeds for a full-size cell.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_script(body: str, devices: int = 8, timeout: int = 900) -> str:
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    import sys
    sys.path.insert(0, {SRC!r})
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_aggregation_schedules_match_dense_oracle():
    out = run_script("""
    import functools, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding, AxisType
    from repro.core import lowbit_vote_psum, lowbit_packed_a2a, sign_of_mean
    from repro.kernels import ref

    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
    W = 4
    n = 32 * 128 * 2 + 77           # deliberately unaligned
    rng = np.random.RandomState(0)
    gs = rng.randn(W, n).astype(np.float32)

    def agg(fn):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("data",)), out_specs=P(),
                           axis_names=frozenset({"data"}), check_vma=False)
        def run(stacked):
            return fn(stacked[0])
        return np.asarray(jax.jit(run)(jnp.asarray(gs)))

    want_bin = np.asarray(ref.gbinary_aggregate_dense(jnp.asarray(gs)))
    got_vote = agg(lambda g: lowbit_vote_psum(g, ("data",), W)[0])
    np.testing.assert_array_equal(got_vote, want_bin)
    got_packed = agg(lambda g: lowbit_packed_a2a(g, ("data",), W)[0])
    np.testing.assert_array_equal(got_packed, want_bin)
    got_ter = agg(lambda g: lowbit_vote_psum(g, ("data",), W, ternary=True)[0])
    want_ter = np.asarray(ref.gternary_aggregate_dense(jnp.asarray(gs)))
    np.testing.assert_array_equal(got_ter, want_ter)
    som = agg(lambda g: sign_of_mean(g, ("data",)))
    np.testing.assert_array_equal(som, np.sign(gs.mean(0)))
    print("SCHEDULES_MATCH")
    """)
    assert "SCHEDULES_MATCH" in out


@pytest.mark.slow
def test_trainer_failure_recovery_is_deterministic():
    out = run_script("""
    import jax, tempfile, shutil
    from jax.sharding import AxisType
    from repro.models import ModelConfig
    from repro.optim import AdamW
    from repro.core import AdmissionPlan, AggregationMode, Schedule
    from repro.runtime import Trainer, TrainerConfig, FailureInjector
    from repro.data import SyntheticLMStream

    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", remat=False)
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=16, seed=0)
    opt = AdamW(peak_lr=3e-3, warmup_steps=5, total_steps=100)
    plan = AdmissionPlan.lowbit_backbone(AggregationMode.G_BINARY,
                                         schedule=Schedule.PACKED_A2A)
    def run(fail):
        ck = tempfile.mkdtemp()
        tr = Trainer(cfg, mesh, opt, data, plan=plan,
                     tcfg=TrainerConfig(dp_axes=("data",),
                                        checkpoint_interval=5,
                                        log_interval=1000),
                     ckpt_dir=ck,
                     failure_injector=FailureInjector(at_steps=[12]) if fail
                     else None)
        h = tr.run(18)
        shutil.rmtree(ck)
        return [x["loss"] for x in h], tr.restarts

    a, r0 = run(False)
    b, r1 = run(True)
    assert r0 == 0 and r1 == 1
    assert a[-1] == b[-1], (a[-1], b[-1])
    assert a[-1] < a[0]
    print("RECOVERY_DETERMINISTIC", a[0], "->", a[-1])
    """)
    assert "RECOVERY_DETERMINISTIC" in out


@pytest.mark.slow
def test_elastic_restart_across_mesh_shapes():
    out = run_script("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import AxisType
    from repro.models import ModelConfig
    from repro.optim import SgdMomentum
    from repro.core import AdmissionPlan
    from repro.runtime import Trainer, TrainerConfig
    from repro.data import SyntheticLMStream

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", remat=False)
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=16, seed=0)
    opt = SgdMomentum(peak_lr=1e-2)
    ck = tempfile.mkdtemp()

    mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
    tr = Trainer(cfg, mesh_a, opt, data, plan=AdmissionPlan.fp32_all(),
                 tcfg=TrainerConfig(dp_axes=("data",), checkpoint_interval=5,
                                    log_interval=1000), ckpt_dir=ck)
    tr.run(10)
    w_before = np.asarray(tr.state.params["layers"]["attn"]["wq"])

    # "elastic rescale": restart on a different mesh shape
    mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
    tr2 = Trainer(cfg, mesh_b, opt, data, plan=AdmissionPlan.fp32_all(),
                  tcfg=TrainerConfig(dp_axes=("data",), checkpoint_interval=5,
                                     log_interval=1000), ckpt_dir=ck)
    tr2.run(10)   # restores step 10 checkpoint; no extra steps needed
    w_after = np.asarray(tr2.state.params["layers"]["attn"]["wq"])
    np.testing.assert_allclose(w_before, w_after, rtol=1e-6)
    assert int(tr2.state.step) == 10
    print("ELASTIC_RESTART_OK")
    """)
    assert "ELASTIC_RESTART_OK" in out


@pytest.mark.slow
def test_dryrun_entrypoint_full_size_cell(tmp_path):
    """The production dry-run proves (e): lower+compile on the 16x16 mesh."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3_0p6b",
         "--shape", "decode_32k", "--mesh", "single", "--out", str(tmp_path),
         "--force"],
        capture_output=True, text=True, timeout=1200, env=env)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    data = json.load(open(tmp_path / "pod_16x16" / "qwen3_0p6b"
                          / "decode_32k.decode.json"))
    assert data["num_devices"] == 256
    assert data["flops_per_device"] > 0
    assert data["roofline"]["dominant"] in ("compute", "memory", "collective")


def test_straggler_watchdog():
    from repro.runtime import StragglerWatchdog
    wd = StragglerWatchdog(threshold=2.0, warmup=2)
    flags = [wd.observe(i, d) for i, d in
             enumerate([1.0, 1.0, 1.0, 1.05, 5.0, 1.0])]
    assert flags == [False, False, False, False, True, False]
    assert len(wd.events) == 1 and wd.events[0].step == 4
    # EWMA must not be polluted by the straggler sample
    assert wd.ewma < 1.2


@pytest.mark.slow
def test_grad_accumulation_equivalence():
    """grad_accum=4 reproduces grad_accum=1 (linear FP32 aggregation)."""
    out = run_script("""
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.models import ModelConfig
    from repro.optim import AdamW
    from repro.core import AdmissionPlan
    from repro.runtime import Trainer, TrainerConfig
    from repro.fabric import Fabric
    from repro.data import SyntheticLMStream

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,)*2)
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", remat=False)
    data = SyntheticLMStream(vocab=256, seq_len=32, batch=16, seed=0)
    opt = AdamW(peak_lr=3e-3, warmup_steps=5, total_steps=50)
    plan = AdmissionPlan.fp32_all()
    losses = {}
    for ga in (1, 4):
        tr = Trainer(cfg, mesh, opt, data, plan=plan,
                     tcfg=TrainerConfig(dp_axes=("data",), log_interval=1000))
        tr.init_state()
        jitted, _, b_sh, _ = Fabric(mesh, ("data",)).build_step(
            cfg, opt, plan, tr.state.params, grad_accum=ga, donate=False)
        st = tr.state
        for step in range(6):
            b = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), b_sh),
                             data.batch_at(step))
            st, m = jitted(st, b)
        losses[ga] = float(m["loss"])
    assert abs(losses[1] - losses[4]) < 2e-4, losses
    print("GRAD_ACCUM_OK")
    """)
    assert "GRAD_ACCUM_OK" in out

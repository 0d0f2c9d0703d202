"""Production training launcher.

Single-host entry point (multi-host: same binary under your cluster
scheduler with jax.distributed.initialize — the Trainer, checkpoint, and
data layers are already host-indexed).  Examples:

  # 8 simulated devices, qwen3 smoke config, G-Binary backbone:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_0p6b --smoke \\
      --mesh 4,2 --steps 100 --plan gbin_backbone

  # adaptive control plane (warm-up -> calibrate -> admit -> guarded):
  ... --plan adaptive          # equivalent: --controller paper

Plan names resolve through ``repro.fabric.control.plan_presets`` (the
same table the dry-run uses); ``--controller`` accepts any name in the
``@register_controller`` registry.
"""
import argparse
import logging
import os
import pathlib

#: preset names, hardcoded so --help works without importing jax;
#: validated against plan_presets() at startup
_PLAN_CHOICES = ["fp32", "gbin_backbone", "gbin_vote", "gbin_packed",
                 "gter_backbone", "gter_vote", "lowbit_all",
                 "gbin_packed_all", "gbin_packed_embed",
                 "int4_backbone", "topk_backbone",
                 "hier_fp32_gbinary", "hier_fp32_gternary",
                 "hier_fp32_int4", "adaptive"]

#: the checkout root (src/repro/launch/train.py -> three levels up)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself, so nothing is set here).  Otherwise the cache is the
    fixed, gitignored ``.jax_cache/`` of the checkout: the path is part
    of the cache key, so it never depends on a temp name, pid or time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_trainer(cfg, mesh, dp_axes, *, plan=None, fabric=None,
                  global_batch: int, seq_len: int, steps: int,
                  optimizer: str = "adamw", lr: float = 3e-4, seed: int = 0,
                  ckpt_dir=None, ckpt_interval: int = 100):
    """The launcher's Trainer: synthetic LM stream, optimizer, session.

    ``plan`` is a static admission plan; leave it None and pass a
    ``fabric`` with a controller attached for an adaptive run.
    """
    from ..data import SyntheticLMStream
    from ..fabric import Fabric
    from ..optim import AdamW, SgdMomentum
    from ..runtime import Trainer, TrainerConfig

    data = SyntheticLMStream(vocab=cfg.vocab_size, seq_len=seq_len,
                             batch=global_batch, seed=seed)
    opt_cls = AdamW if optimizer == "adamw" else SgdMomentum
    return Trainer(
        cfg, mesh, opt_cls(peak_lr=lr, total_steps=steps), data, plan=plan,
        fabric=fabric if fabric is not None else Fabric(mesh, dp_axes),
        tcfg=TrainerConfig(dp_axes=dp_axes,
                           checkpoint_interval=ckpt_interval),
        ckpt_dir=ckpt_dir, seed=seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--mesh", default="1,1",
                    help="data,model (or pod,data,model) mesh shape")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--plan", default="gbin_backbone", choices=_PLAN_CHOICES)
    ap.add_argument("--controller", default=None,
                    help="registered admission controller driving the run "
                         "(e.g. paper, static, fp32); overrides --plan")
    ap.add_argument("--autotune", action="store_true",
                    help="search plan_presets + generated low-bit plans "
                         "offline (repro.tune) and train on the winner; "
                         "overrides --plan / --controller")
    ap.add_argument("--autotune-topology", default="ici_ring",
                    help="sim topology the autotuner certifies against")
    ap.add_argument("--autotune-strategy", default="grid",
                    help="registered search strategy (grid, random, "
                         "successive_halving)")
    ap.add_argument("--autotune-out", default=None,
                    help="write the TunedPlan artifact JSON here")
    ap.add_argument("--warmup-steps", type=int, default=20,
                    help="FP32 calibration window of the paper controller")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgdm"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=100)
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--device-count", type=int, default=0,
                    help="force host platform device count (CPU sim)")
    args = ap.parse_args()

    if args.device_count:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{args.device_count}").strip()

    import jax

    from ..configs import get_config
    from ..fabric import Fabric
    from ..fabric.control import plan_presets
    from .mesh import dp_axes_of, make_mesh

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    use_compile_cache()

    mesh = make_mesh(tuple(int(x) for x in args.mesh.split(",")))
    dp_axes = dp_axes_of(mesh)
    cfg = get_config(args.arch, smoke=args.smoke)

    plans = plan_presets(error_feedback=args.error_feedback)
    # built-in choices must all resolve; plan_presets may additionally
    # carry runtime-registered extras (register_plan_preset), which the
    # static --help choices deliberately don't enumerate
    assert set(_PLAN_CHOICES) - {"adaptive"} <= set(plans), \
        "launcher plan choices drifted from plan_presets()"

    fabric = Fabric(mesh, dp_axes)
    plan = None
    controller_name = args.controller or (
        "paper" if args.plan == "adaptive" else None)
    if args.autotune:
        from ..models import init_params
        params_like = jax.eval_shape(
            lambda: init_params(jax.random.key(args.seed), cfg))
        tuned = fabric.autotune(params_like,
                                topology=args.autotune_topology,
                                strategy=args.autotune_strategy,
                                error_feedback=args.error_feedback)
        if args.autotune_out:
            tuned.save(args.autotune_out)
        logging.getLogger("repro.launch").info(
            "autotuned plan %s (%s): step=%.1fus, %d runners-up",
            tuned.name, tuned.plan.signature(),
            tuned.score.step_time_s * 1e6, len(tuned.runners_up))
        tuned.apply(fabric)     # adopt the tuned bucket budget
        # the "tuned" controller latches the winner and re-ranks the
        # sim-certified shortlist from live step times
        fabric.attach_controller("tuned", tuned=tuned)
    elif controller_name in ("paper", "adaptive"):
        fabric.attach_controller(controller_name,
                                 warmup_steps=args.warmup_steps)
    elif controller_name == "static":
        if args.plan == "adaptive":
            ap.error("--controller static needs a concrete --plan preset")
        fabric.attach_controller("static", plan=plans[args.plan])
    elif controller_name is not None:
        fabric.attach_controller(controller_name)
    else:
        plan = plans[args.plan]

    trainer = build_trainer(
        cfg, mesh, dp_axes, plan=plan, fabric=fabric,
        global_batch=args.global_batch, seq_len=args.seq_len,
        steps=args.steps, optimizer=args.optimizer, lr=args.lr,
        seed=args.seed, ckpt_dir=args.ckpt_dir,
        ckpt_interval=args.ckpt_interval)
    history = trainer.run(args.steps)
    last = history[-1]
    print(f"final: step={last['step']} loss={last['loss']:.4f} "
          f"traffic={last['traffic_ratio']:.4f} "
          f"restarts={trainer.restarts} "
          f"stragglers={len(trainer.watchdog.events)}")


if __name__ == "__main__":
    main()

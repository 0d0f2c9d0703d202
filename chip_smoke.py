"""On-chip smoke run of the training main path at full width.

Trains full-width qwen3-0.6b (28 layers, d_model 1024, vocab 151,936,
bf16, remat; random weights from a seed) through the objects the
training launcher builds (``repro.launch.train``): a few steps under the
packed low-bit plan ``gbin_packed``, whose aggregation runs the fused
Pallas vote datapath compiled by Mosaic, and under ``fp32``.  Checks:

  * every loss is finite, and the first is within 1.0 of ln(vocab);
  * the compiled ``gbin_packed`` step holds ``tpu_custom_call`` (the
    kernels lowered to Mosaic);
  * from one initial state and batch, the params after one
    ``gbin_packed`` step are bit-equal to those after one ``gbin_vote``
    step (the kernel-free dense vote; both are exact integer majorities).

Usage (on a TPU host; there is no CPU path)::

    python chip_smoke.py              # one chip: mesh (1, 1), batch 8 x 512
    python chip_smoke.py --chips 4    # four chips: mesh (4, 1), batch 32 x 512

Earlier output lines are informational; the last line is one JSON object
``{"ok": true, "device": {...}}``.  Any failed check exits non-zero and
prints no such line.  The compile cache follows the launcher's rule
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/``).
"""
import argparse
import json
import math
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen3_0p6b"
SEQ_LEN = 512
BATCH_PER_CHIP = 8
STEPS = 5
SEED = 0


def run_plan(cfg, mesh, dp_axes, plan_name, *, global_batch, steps):
    """Train ``steps`` steps under one plan preset from the seeded state.

    Returns compile seconds, per-step seconds (each ends in
    ``block_until_ready``), the losses, the host params after step 1,
    and whether the compiled step holds a Mosaic kernel.
    """
    import jax
    import jax.numpy as jnp
    from repro.fabric.control import plan_presets
    from repro.launch.train import build_trainer

    trainer = build_trainer(cfg, mesh, dp_axes,
                            plan=plan_presets()[plan_name],
                            global_batch=global_batch, seq_len=SEQ_LEN,
                            steps=steps, seed=SEED)
    trainer.init_state()
    step = trainer.compiled_step()
    batch = jax.tree.map(
        lambda a: jax.device_put(jnp.asarray(a), step.batch_sharding),
        trainer.data.batch_at(0))
    t0 = time.perf_counter()
    compiled = step.step_fn.lower(trainer.state, batch).compile()
    compile_s = time.perf_counter() - t0
    times, after_first = [], None
    for k in range(1, steps + 1):
        t0 = time.perf_counter()
        trainer.run(k)
        jax.block_until_ready(trainer.state)
        times.append(time.perf_counter() - t0)
        if k == 1:
            after_first = jax.device_get(trainer.state.params)
    return {"compile_s": compile_s, "step_s": times,
            "losses": [h["loss"] for h in trainer.history],
            "params_after_1": after_first,
            "kernels": "tpu_custom_call" in compiled.as_text()}


def bit_diff(a, b):
    """(differing leaves, differing elements, total elements) of two
    host param trees, compared bit for bit."""
    import jax
    import numpy as np
    leaves, elems, total = 0, 0, 0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        uint = np.dtype(f"u{x.dtype.itemsize}")
        n = int(np.sum(x.view(uint) != y.view(uint)))
        leaves += n > 0
        elems += n
        total += x.size
    return leaves, elems, total


def peak_bytes(devices):
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def smoke(cfg, chips: int) -> list[str]:
    """Run the phases; returns the failed checks (empty when all pass)."""
    import jax
    from repro.launch.mesh import dp_axes_of, make_mesh

    mesh = make_mesh((chips, 1))
    dp_axes = dp_axes_of(mesh)
    batch = BATCH_PER_CHIP * chips
    devices = jax.devices()
    failures = []
    results = {}
    for name, steps in (("gbin_packed", STEPS), ("gbin_vote", 1),
                        ("fp32", STEPS)):
        r = run_plan(cfg, mesh, dp_axes, name, global_batch=batch,
                     steps=steps)
        results[name] = r
        median = statistics.median(r["step_s"][1:]) if steps > 1 else None
        print(f"{name}: W={chips} batch={batch}x{SEQ_LEN} "
              f"compile_s={r['compile_s']!r} step_s={r['step_s']!r} "
              f"median_step_s_2_to_{steps}={median!r} "
              f"peak_bytes_in_use={peak_bytes(devices)} "
              f"tpu_custom_call={r['kernels']} losses={r['losses']!r}",
              flush=True)
        if not all(math.isfinite(x) for x in r["losses"]):
            failures.append(f"{name}: non-finite loss")

    packed = results["gbin_packed"]
    ln_vocab = math.log(cfg.vocab_size)
    if abs(packed["losses"][0] - ln_vocab) > 1.0:
        failures.append(f"first loss {packed['losses'][0]!r} not within "
                        f"1.0 of ln(vocab) = {ln_vocab!r}")
    if not packed["kernels"]:
        failures.append("gbin_packed step holds no tpu_custom_call")
    leaves, elems, total = bit_diff(packed["params_after_1"],
                                    results["gbin_vote"]["params_after_1"])
    print(f"bit_equal gbin_packed vs gbin_vote after 1 step (W={chips}): "
          f"{elems == 0} ({elems} of {total} elements differ, "
          f"in {leaves} leaves)", flush=True)
    if elems:
        failures.append("gbin_packed params differ from gbin_vote")
    return failures


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip run; 4: the data-parallel "
                         "four-chip phase only")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU devices, JAX found {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.launch.train import use_compile_cache

    cache = use_compile_cache()
    cfg = get_config(ARCH)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    print(f"config: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"remat={cfg.remat}", flush=True)

    failures = smoke(cfg, args.chips)
    for f in failures:
        print(f"FAIL: {f}", flush=True)
    if failures:
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
# cell against 512 placeholder host devices, record memory_analysis(),
# cost_analysis(), and the parsed collective inventory for the roofline.
# The two lines above MUST run before any other import (JAX locks the device
# count at first init).  Usage:
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
#       --mesh both --plans fp32,gbin_vote --out results/dryrun
#
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..fabric.control import plan_presets
from ..models import SHAPES, SHAPES_BY_NAME, init_cache
from ..optim import AdamW
from .hlo_analysis import (parse_collectives, roofline_terms,
                           summarize_collectives)
from .hlo_walk import walk
from .mesh import dp_axes_of, make_production_mesh
from .specs import input_specs, state_specs, train_batch_specs

#: one source of named plans for every launcher (repro.fabric.control);
#: the dry-run compiles any subset of them per (arch x shape x mesh) cell
PLANS = plan_presets()


def cell_skipped(cfg, cell) -> str | None:
    """Assignment skip rules (documented in DESIGN.md §Arch-applicability)."""
    if cell.name == "long_500k" and not cfg.is_subquadratic:
        return "long_500k skipped: pure full-attention architecture"
    return None


#: crude per-device compute estimate for the simulated timeline: v5e-ish
#: bf16 peak, derated to a realistic MFU.
V5E_PEAK_FLOPS = 197e12
SIM_MFU = 0.4
#: topologies the dry-run's simulated-timeline section replays per cell
SIM_TOPOLOGIES = ("ici_ring", "cxl_switched", "multihop")


def run_train_cell(cfg, cell, mesh, plan_name: str,
                   grad_accum: int = 1) -> dict:
    from ..fabric import Fabric
    plan = PLANS[plan_name]
    fabric = Fabric(mesh, dp_axes_of(mesh))
    optimizer = AdamW(peak_lr=1e-4)
    state = state_specs(cfg, optimizer, plan, dp_size=fabric.num_workers)
    batch = train_batch_specs(cfg, cell)
    step = fabric.build_step(cfg, optimizer, plan, state.params,
                             grad_accum=grad_accum, donate=False)
    t0 = time.time()
    lowered = step.step_fn.lower(state, batch)
    compiled = lowered.compile()
    result = analyze(compiled, mesh, t0, cfg, cell, extra={
        "plan": plan_name, "num_workers": step.aux["num_workers"]})
    # simulated collective timeline (repro.sim): the cell's bucket layout
    # replayed per topology against an MFU-derated compute estimate
    compute_s = (model_flops_per_device(cfg, cell, mesh.devices.size)
                 / (V5E_PEAK_FLOPS * SIM_MFU))
    result["sim"] = {
        topo: fabric.simulate(state.params, plan, topology=topo,
                              compute_time_s=compute_s).summary()
        for topo in SIM_TOPOLOGIES}
    return result


def run_decode_cell(cfg, cell, mesh) -> dict:
    from ..runtime.serve import build_serve_step
    dp = dp_axes_of(mesh)
    spec = input_specs(cfg, cell)
    jitted, sh = build_serve_step(cfg, mesh, batch=cell.global_batch,
                                  max_seq=cell.seq_len, dp_axes=dp,
                                  donate=False)
    from ..models import init_params
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    t0 = time.time()
    lowered = jitted.lower(params, spec["token"], spec["cache"],
                           spec["position"])
    compiled = lowered.compile()
    return analyze(compiled, mesh, t0, cfg, cell, extra={
        "plan": "serve", "shard_seq": bool(sh["shard_seq"])})


def run_prefill_cell(cfg, cell, mesh) -> dict:
    from ..runtime.serve import build_prefill
    dp = dp_axes_of(mesh)
    batch = train_batch_specs(cfg, cell)
    batch.pop("labels")
    jitted = build_prefill(cfg, mesh, dp_axes=dp)
    from ..models import init_params
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    t0 = time.time()
    lowered = jitted.lower(params, batch)
    compiled = lowered.compile()
    return analyze(compiled, mesh, t0, cfg, cell, extra={"plan": "prefill"})


def model_flops_per_device(cfg, cell, num_devices: int) -> float:
    """MODEL_FLOPS: 6*N_active*D (train) / 2*N_active*tokens (inference)."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens / num_devices
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens / num_devices
    return 2.0 * n * cell.global_batch / num_devices   # decode: 1 new token


def analyze(compiled, mesh, t0: float, cfg, cell, extra: dict) -> dict:
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    num_devices = mesh.devices.size
    pod_size = (num_devices // mesh.shape["pod"]
                if "pod" in mesh.axis_names else 0)
    # while-aware walk: correct flops/bytes/wire for scanned layer stacks
    wk = walk(hlo, pod_size=pod_size)
    colls = parse_collectives(hlo, pod_size=pod_size)   # static inventory
    csum = summarize_collectives(colls)
    csum["total_wire_bytes"] = wk["wire_bytes"]         # loop-corrected
    csum["pod_crossing_wire_bytes"] = wk["pod_wire_bytes"]
    csum["wire_breakdown_top"] = dict(
        list(wk["wire_breakdown"].items())[:10])
    flops = wk["flops"]
    hbm_bytes = wk["hbm_bytes"]
    roof = roofline_terms(flops, hbm_bytes, wk["wire_bytes"])
    mflops = model_flops_per_device(cfg, cell, num_devices)
    roof["model_flops_per_device"] = mflops
    roof["useful_flop_ratio"] = mflops / flops if flops else 0.0
    return {
        **extra,
        "mesh": dict(zip(mesh.axis_names, [int(mesh.shape[a])
                                           for a in mesh.axis_names])),
        "num_devices": int(num_devices),
        "compile_s": compile_s,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.temp_size_in_bytes),
        },
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm_bytes,
        "cost_analysis_raw": {"flops": float(cost.get("flops", 0.0)),
                              "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "loop_trip_counts": wk["loops"],
        "collectives": csum,
        "roofline": roof,
    }


def run_cell(arch: str, shape: str, multi_pod: bool, plan: str,
             out_dir: str, force: bool = False,
             grad_accum: int = 1, tag_suffix: str = "",
             moe_cf: float = 0.0, remat_policy: str = "") -> dict | None:
    import dataclasses
    cfg = get_config(arch)
    if moe_cf and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=moe_cf))
    if remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    cell = SHAPES_BY_NAME[shape]
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    tag = (plan if cell.is_train else cell.kind) + tag_suffix
    path = os.path.join(out_dir, mesh_name, arch, f"{shape}.{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if os.path.exists(path) and not force:
        print(f"[skip-cached] {mesh_name}/{arch}/{shape}.{tag}")
        with open(path) as f:
            return json.load(f)

    skip = cell_skipped(cfg, cell)
    if skip:
        result = {"skipped": skip}
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        try:
            with jax.set_mesh(mesh):
                if cell.kind == "train":
                    result = run_train_cell(cfg, cell, mesh, plan,
                                            grad_accum=grad_accum)
                elif cell.kind == "prefill":
                    result = run_prefill_cell(cfg, cell, mesh)
                else:
                    result = run_decode_cell(cfg, cell, mesh)
        except Exception as e:  # record failures; they are bugs to fix
            result = {"error": f"{type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()[-4000:]}
    result.update({"arch": arch, "shape": shape, "mesh_name": mesh_name})
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    status = ("SKIP" if "skipped" in result
              else "FAIL" if "error" in result else
              f"ok {result['compile_s']:.0f}s dom={result['roofline']['dominant']}")
    print(f"[{mesh_name}] {arch} {shape} ({tag}): {status}", flush=True)
    if "error" in result:
        print(result["error"], flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--plans", default="gbin_vote",
                    help="comma-separated train plans (fp32,gbin_vote,...)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--moe-cf", type=float, default=0.0)
    ap.add_argument("--remat-policy", default="",
                    help="override the config's remat_policy: auto (save "
                         "the layers' un-batched dot outputs when they fit "
                         "a quarter of the device's memory, else full; the "
                         "placeholder host devices report no limit, so they "
                         "take full), full (recompute each layer) or dots "
                         "(save every dot output)")
    ap.add_argument("--tag-suffix", default="")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = [s.name for s in SHAPES] if args.shape == "all" \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    plans = args.plans.split(",")

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                cell = SHAPES_BY_NAME[shape]
                cell_plans = plans if cell.is_train else ["serve"]
                for plan in cell_plans:
                    r = run_cell(arch, shape, mp, plan, args.out,
                                 force=args.force,
                                 grad_accum=args.grad_accum,
                                 moe_cf=args.moe_cf,
                                 remat_policy=args.remat_policy,
                                 tag_suffix=args.tag_suffix)
                    if r and "error" in r:
                        failures += 1
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

"""One run of one cell: set-up, checked steps, timed window, trace, check.

The timed path is the program's own: a ``repro.runtime.Trainer`` built by
``repro.launch.train.build_trainer`` with a static plan from
``repro.fabric.control.plan_presets()``, driven through ``Trainer.run``.
Set-up builds that one Trainer, drives it from the seed through the
checked steps (which also warm it up) and hands the same object to the
window.  The reference the configuration names follows the checked steps
once the window has closed and the program's state is freed, over as many
data-parallel workers as the program runs.
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import (checks, faults, hlo_ops, readers, spec, step_scopes,
               trace_reduce, traffic)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def peaks_for(kind: str, bench=spec.BENCH) -> dict:
    table = spec.load_json(bench / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


class CompileCounter:
    """Counts programs lowered or compiled while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.active and event in self.EVENTS:
            self.count += 1


def _mesh(devices, shape):
    import jax
    from jax.sharding import AxisType, Mesh
    n = shape[0] * shape[1]
    return Mesh(np.asarray(devices[:n]).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def build(cell, seed: int, devices):
    """The Trainer the window drives, fed from the seeded batch pool."""
    from repro.fabric.control import plan_presets
    from repro.launch.train import build_trainer
    t = cell.traffic
    trainer = build_trainer(
        spec.model_config(cell.config), _mesh(devices, t["mesh"]),
        ("data",), plan=plan_presets()[t["plan"]],
        global_batch=cell.global_batch, seq_len=t["seq_len"],
        steps=t["total_steps"], lr=t["peak_lr"], seed=seed)
    opt = trainer.optimizer
    stated = t["optimizer"]
    for k in ("warmup_steps", "b1", "b2", "eps", "weight_decay", "grad_clip"):
        if getattr(opt, k) != stated[k]:
            raise ValueError(f"optimizer {k}={getattr(opt, k)!r}, the "
                             f"traffic file states {stated[k]!r}")
    return trainer


def program_readings(trainer, pool, checked: int, b1: float, voted) -> dict:
    """Drive the Trainer through the checked steps; read what it made.

    Returns the losses, the per-leaf norms of the first aggregated
    gradient as the optimizer got it (AdamW's first moment after one
    step is (1 - b1) times it), the first step's vote bits, and per leaf
    the norm and the direction bits of the parameters' change over the
    checked steps.
    """
    import jax
    from reference.common import change_readings, leaf_norm, leaf_paths, \
        vote_bits
    marks = {"init_start": time.time()}
    trainer.init_state()
    marks["init_state"] = time.time()
    paths = leaf_paths(trainer.state.params)
    p0 = jax.device_get(trainer.state.params)
    marks["p0_to_host"] = time.time()
    trainer.run(1)
    marks["step1"] = time.time()
    mu = jax.tree.leaves(trainer.state.opt.mu)
    scale = np.float32(1.0 - b1)
    agg_norm = [float(x) for x in jax.jit(
        lambda ms: [leaf_norm(m / scale) for m in ms])(mu)]
    bits = jax.jit(lambda ms: [vote_bits(m) for p, m in zip(paths, ms)
                               if voted(p)])(mu)
    bits = [(np.asarray(a), np.asarray(b)) for a, b in bits]
    del mu
    marks["step1_readings"] = time.time()
    trainer.run(checked)
    marks[f"steps2_{checked}"] = time.time()
    one = jax.jit(lambda a, b: change_readings([a], [b]))
    change, change_bits = [], []
    for x, x0 in zip(jax.tree.leaves(trainer.state.params),
                     jax.tree.leaves(p0)):
        (n,), ((pos, neg),) = one(x, jax.device_put(x0, x.sharding))
        change.append(float(n))
        change_bits.append((np.asarray(pos), np.asarray(neg)))
    marks["change_readings"] = time.time()
    losses = [h["loss"] for h in trainer.history[:checked]]
    return {"loss": losses, "agg_norm": agg_norm, "bits": bits,
            "change_norm": change, "change_bits": change_bits,
            "paths": paths, "marks": marks}


def reference_readings(cell, seed: int, pool, checked: int,
                       matmul: str = "highest") -> dict:
    """The reference's checked steps: W = the mesh's ``data`` size
    workers, each on its rows of every global batch, one to a chip."""
    import jax
    from reference.common import AdamW, Reference, leaf_paths
    arch = cell.reference
    workers = cell.traffic["mesh"][0]
    o = cell.traffic["optimizer"]
    ref = Reference(arch, cell.config,
                    AdamW(peak_lr=cell.traffic["peak_lr"],
                          warmup_steps=o["warmup_steps"],
                          total_steps=cell.traffic["total_steps"],
                          b1=o["b1"], b2=o["b2"], eps=o["eps"]),
                    voted=voted_rule(cell), matmul=matmul,
                    devices=jax.devices()[:workers] if workers > 1 else None)
    shapes = jax.eval_shape(lambda: arch.init_weights(0, ref.s))
    voted = [int(x.size) for p, x in zip(leaf_paths(shapes),
                                         jax.tree.leaves(shapes))
             if ref.voted(p)]
    r = ref.steps(seed, [pool.batch_at(i) for i in range(checked)])
    r["voted_sizes"] = voted
    return r


def voted_rule(cell):
    from reference.common import voted_rule as rule
    return rule(cell.traffic["plan"])


def compare(prog: dict, ref: dict, voted) -> dict:
    if prog["paths"] != ref["paths"]:
        raise ValueError("program and reference leaves differ: "
                         f"{prog['paths']} vs {ref['paths']}")
    return checks.numbers(prog, ref, voted)


def free_device_memory():
    import gc
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def run(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, peaks: dict | None = None,
        fault: str | None = None) -> dict:
    """One run; returns the result line's object.

    ``fault`` names a fault of :mod:`harness.faults` to plant under the
    program for the whole run; the benchmark's own runs plant none.
    """
    with faults.FAULTS[fault]() if fault else contextlib.nullcontext():
        return _run(cell, seed, seconds, trace, t_start=t_start,
                    require_tpu=require_tpu, peaks=peaks)


def _run(cell, seed, seconds, trace, *, t_start, require_tpu, peaks):
    if not cell.limits.get("limits"):
        raise ValueError(f"{cell.name} has no bench/limits/{cell.name}.json")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found {dev.platform!r} devices, no TPU")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    devices = devices[:cell.chips]
    peaks = peaks or peaks_for(dev.device_kind, cell.root / "bench")
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} cell={cell.name} seed={seed}")
    counter = CompileCounter()
    t_jax = time.time()
    t = cell.traffic
    checked = t["checked_steps"]
    pool = traffic.pool_for(cell, seed)
    t_pool = time.time()
    trainer = build(cell, seed, devices)
    t_build = time.time()
    trainer.data = pool
    prog = program_readings(trainer, pool, checked, t["optimizer"]["b1"],
                            voted_rule(cell))
    log(f"checked steps: losses={prog['loss']!r}")
    marks = {"imports": t_jax, "pool": t_pool, "build_trainer": t_build,
             **prog["marks"]}
    last, phases = t_start, []
    for k, v in marks.items():
        phases.append(f"{k} {v - last:.3f}")
        last = v
    log("setup phases (s): " + ", ".join(phases))

    # -- the timed window ----------------------------------------------
    step = checked
    counter.active = True
    t0 = time.perf_counter()
    setup_s = time.time() - t_start
    while True:
        trainer.run(step + 1)
        step += 1
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(trainer.state)
    window_s = time.perf_counter() - t0
    counter.active = False
    window_steps = step - checked
    losses = [h["loss"] for h in trainer.history[checked:]]
    nonfinite = sum(not math.isfinite(x) for x in losses)
    tokens_per_s = window_steps * cell.tokens_per_step / window_s
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"memory_stats: {devices[0].memory_stats()}")
    log(f"window: {window_steps} steps in {window_s!r} s, "
        f"{tokens_per_s!r} tokens/s, setup {setup_s!r} s, "
        f"peak_bytes_in_use {peak}, compiles in window {counter.count}")

    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": int(peak)}}
    metrics = {}
    if trace:
        per_layer = _traced(cell, trainer, pool, step, tokens_per_s, peaks,
                            result)
        for m in cell.per_layer:
            if m["name"] in per_layer:
                metrics[m["name"]] = {"value": per_layer[m["name"]],
                                      "unit": m["unit"]}
    else:
        e2e = {"train_tokens_per_s": tokens_per_s,
               "peak_hbm_gib": peak / 2**30, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    del trainer
    free_device_memory()

    t1 = time.perf_counter()
    ref = reference_readings(cell, seed, pool, checked)
    log(f"reference: {time.perf_counter() - t1!r} s, losses={ref['loss']!r}")
    values = compare(prog, ref, voted_rule(cell))
    values["window_compiles"] = float(counter.count)
    values["window_nonfinite_losses"] = float(nonfinite)
    limits = dict(cell.limits["limits"])
    limits.update(window_compiles=0.0, window_nonfinite_losses=0.0)
    rows = checks.judge(values, limits)
    correct = all(r["ok"] for r in rows)
    result.update(correct=correct, attempted=window_steps, failed=nonfinite,
                  metrics=metrics)
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    for r in rows:
        verdict = ("not compared" if r["limit"] is None
                   else "ok" if r["ok"] else "FAIL")
        log(f"check {r['name']} {r['value']!r} limit {r['limit']!r} "
            f"{verdict}")
    # readers of the result line take the keys they know; the checks come last
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    return {k: result[k] for k in order if k in result}


def _traced(cell, trainer, pool, step, tokens_per_s, peaks, result) -> dict:
    """Trace a few more steps; reduce the trace; read the per-layer metrics."""
    import jax
    n = cell.traffic["trace_steps"]
    cstep = trainer.compiled_step()
    batch = jax.tree.map(lambda a: jax.device_put(a, cstep.batch_sharding),
                         pool.batch_at(step))
    compiled = cstep.step_fn.lower(trainer.state, batch).compile()
    del batch
    log(f"memory_analysis: {compiled.memory_analysis()}")
    hlo = compiled.as_text()
    if os.environ.get("BENCH_KEEP_HLO"):
        with open(os.environ["BENCH_KEEP_HLO"], "w") as f:
            f.write(hlo)
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(n):
                with jax.profiler.TraceAnnotation("Trainer.run"):
                    trainer.run(step + 1)
                step += 1
            jax.block_until_ready(trainer.state)
        jax.profiler.stop_trace()
        path = trace_reduce.find_trace(logdir)
        if os.environ.get("BENCH_KEEP_TRACE"):
            shutil.copy(path, os.environ["BENCH_KEEP_TRACE"])
        events = trace_reduce.events_of(path)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    red = trace_reduce.reduce_events(events)
    chip_ops = trace_reduce.chip_ops(events)
    del events
    op_class = hlo_ops.classify(red["op_s"], hlo)
    ctx = {"cell": cell, "trace": red, "chip_ops": chip_ops,
           "op_class": op_class, "steps_traced": n, "hlo": hlo,
           "peaks": peaks, "tokens_per_s": tokens_per_s}
    values = readers.read_all([m["name"] for m in cell.per_layer], ctx)
    result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
    top = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    result["breakdown"] = {
        "device_ops": [[f"{k} ({op_class.get(k, 'other')})", v]
                       for k, v in top],
        "idle_gaps": red["idle_gaps"][:10]}
    kernels = step_scopes.kernel_seconds(red["op_s"], op_class, hlo)
    log("trace: " + json.dumps({
        "window_s": red["window_s"], "busy_s": red["busy_s"], **values,
        "kernel_ms_by_scope": {k or "(none)": 1e3 * v / n
                               for k, v in sorted(kernels.items())}}))
    return values

"""Paper Section 5 (Table 4, Fig 2-3): datapath timing exposure, TPU-adapted.

Three evidence sources:
  * measured wall-time of the controller-datapath kernels on the functional
    (interpret) path — the byte-exact reference implementation — the
    distributed vote chain's three kernels vs the one-kernel local
    pipeline (repro.kernels.fused);
  * modeled per-bucket kernel-launch counts and HBM bytes of the fused vs
    unfused pipelines from each codec's KernelSet accounting (merged into
    BENCH_codecs.json for the nightly fused-vs-unfused gate);
  * the analytic exposure model with v5e constants:
    T_exposed = max(0, T_agg - T_overlap), swept over link bandwidth,
    datapath depth, admitted fraction, and telemetry staleness (Fig 3
    panels a-d).
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels as K
from repro.kernels import fused as KF
from repro.core.exposure import ExposureModel, TpuDatapathModel, envelope_sweep
from repro.core.traffic import wire_bytes_per_device
from repro.core.modes import AggregationMode, Schedule

#: same file bench_comm_model writes — both writers read-modify-write so
#: module order within a run (and partial runs) cannot drop keys
BENCH_CODECS_JSON = os.environ.get("BENCH_CODECS_JSON", "BENCH_codecs.json")


def merge_bench_json(path, updates):
    """Read-modify-write merge of per-codec dicts into a bench JSON."""
    bench = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                bench = json.load(f)
        except (OSError, ValueError):
            bench = {}
    for name, d in updates.items():
        bench.setdefault(name, {}).update(d)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
    return bench


def _time(fn, *args, reps=3):
    fn(*args)                      # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6   # us


def rows():
    out = []
    rng = np.random.RandomState(0)
    w, m = 8, 2048                      # 8 workers, 2048x128 plane (256 KiB)
    plane = jnp.asarray(rng.randn(m, 128), jnp.float32)
    stack = jnp.stack([K.pack_signs(jnp.asarray(rng.randn(m, 128),
                                                jnp.float32))
                       for _ in range(w)])
    gate = K.ternary_gate_words(m)
    sw, mw = KF.vote_combine(stack, gate, num_workers=w)

    out.append(("datapath/pack_signs_256KiB", _time(K.pack_signs, plane),
                f"elements={m*128}"))
    out.append(("datapath/vote_combine_w8",
                _time(lambda s: KF.vote_combine(s, gate, num_workers=w),
                      stack),
                "W=8 ternary-gated"))
    out.append(("datapath/unpack_ternary", _time(K.unpack_ternary, sw, mw),
                ""))

    # Table 4 analogue: modeled exposure at the production operating point
    n = 8 << 20                      # 8M-element bucket
    model = ExposureModel()
    for sched, tag in ((Schedule.VOTE_PSUM, "vote_psum"),
                       (Schedule.PACKED_A2A, "packed_a2a")):
        wb = wire_bytes_per_device(n, AggregationMode.G_BINARY, sched, 32)
        r = model.exposed(n, 32, wb)
        out.append((f"exposure/{tag}", r["t_agg_s"] * 1e6,
                    f"exposed_pct={r['exposed_pct']:.2f} hidden={r['hidden']}"))

    # per-codec exposure + flit-pipeline timing: every registered codec
    # priced through its own wire model and sim lane descriptor
    from repro.fabric import available_codecs, get_codec
    from repro.sim import FlitPipeline
    pipe = FlitPipeline()
    for name in available_codecs():
        codec = get_codec(name)
        wire = codec.default_schedule if codec.reduction == "mean" \
            else Schedule.PACKED_A2A
        r = model.exposed_launch(n, 32, name, wire)
        t_pipe = pipe.t_agg(n, 32, name)
        out.append((f"exposure/codec/{name}", r["t_agg_s"] * 1e6,
                    f"wire={wire if isinstance(wire, str) else wire.value} "
                    f"exposed_pct={r['exposed_pct']:.2f} "
                    f"hidden={r['hidden']} "
                    f"flit_pipeline_us={t_pipe * 1e6:.1f} "
                    f"lane={pipe.lane(name).name}"))

    out.extend(fused_rows())

    # Fig 3 envelope sweep
    sweep = envelope_sweep()
    worst_a = max(sweep["a"], key=lambda r: r["exposed_pct"])
    out.append(("exposure/envelope_worst_a", worst_a["t_exposed_s"] * 1e6,
                f"link={worst_a['link_GBps']}GBps depth={worst_a['depth_mult']}x "
                f"exposed={worst_a['exposed_pct']:.2f}pct"))
    hidden_frac = np.mean([r["hidden"] for r in sweep["a"]])
    out.append(("exposure/envelope_hidden_fraction", 0.0,
                f"{hidden_frac:.2f} of (bw x depth) grid fully hidden"))
    d10 = [r for r in sweep["d"] if r["stale_steps"] == 10][0]
    out.append(("exposure/telemetry_staleness_10steps", 0.0,
                f"amortized_cost={d10['amortized_step_cost_pct']:.3f}pct"))
    out.extend(sim_rows())
    return out


def fused_rows():
    """Fused vs unfused datapath: measured wall time + modeled accounting.

    Wall time compares the distributed chain's kernels in interpret mode
    (pack -> vote_combine -> unpack) against the single fused
    ``vote_pipeline`` kernel on the same payload, and the jitted int4
    reference against the one-launch two-phase quant kernel.  The modeled section prices
    every registered codec that brings a :class:`KernelSet` — launch
    count and HBM bytes per 8M-element bucket at W=32, fused vs unfused
    — and merges the numbers into ``BENCH_codecs.json`` under each
    codec's ``fused_datapath`` key (the nightly gate asserts
    fused launches < unfused and fused HBM <= unfused there).
    """
    from repro.fabric import available_codecs, get_codec
    from repro.kernels import fused, ref

    out = []
    rng = np.random.RandomState(1)
    w, m = 8, 2048
    stack = jnp.asarray(rng.randn(w, m, 128), jnp.float32)
    gate = fused.local_gate_words(m // ref.PACK, ternary=True)

    def chain(s):
        words = jnp.stack([K.pack_signs(s[i], interpret=True)
                           for i in range(w)])
        sw, mw = fused.vote_combine(words, gate, num_workers=w,
                                    interpret=True)
        return K.unpack_ternary(sw, mw, interpret=True)

    t_chain = _time(chain, stack)
    t_fused = _time(lambda s: fused.vote_pipeline(
        s, gate, num_workers=w, interpret=True), stack)
    out.append(("datapath/fused/vote_chain_3op", t_chain, "W=8 interpret"))
    out.append(("datapath/fused/vote_pipeline_1op", t_fused,
                f"W=8 interpret vs_chain={t_chain / t_fused:.2f}x "
                "(interpret-mode wall; the modeled rows are the perf claim)"))

    plane = jnp.asarray(rng.randn(m, 128), jnp.float32)
    t_ref = _time(jax.jit(ref.int4_quant_plane), plane)
    t_k = _time(lambda p: fused.int4_quant_plane(p, interpret=True), plane)
    out.append(("datapath/fused/int4_staged", t_ref, "jit ref"))
    out.append(("datapath/fused/int4_kernel_1op", t_k, "interpret"))

    # modeled per-bucket accounting, per codec kernel set
    n, W = 8 << 20, 32
    updates = {}
    for name in available_codecs():
        codec = get_codec(name)
        hook = getattr(codec, "pallas_kernels", None)
        ks = hook() if hook is not None else None
        if ks is None:
            continue
        ef = bool(codec.threads_ef)
        row = {"kernel_signature": ks.signature()}
        for path, is_fused in (("fused", True), ("unfused", False)):
            row[f"launches_{path}"] = ks.launches(fused=is_fused,
                                                  distributed=True, ef=ef)
            row[f"hbm_bytes_{path}"] = ks.hbm_bytes(
                n, num_workers=W, fused=is_fused, distributed=True, ef=ef)
        updates[name] = {"fused_datapath": row}
        out.append((f"datapath/fused/modeled/{name}",
                    float(row["launches_fused"]),
                    f"launches {row['launches_fused']}f vs "
                    f"{row['launches_unfused']}u, HBM/bucket "
                    f"{row['hbm_bytes_fused'] / 2**20:.1f}MiBf vs "
                    f"{row['hbm_bytes_unfused'] / 2**20:.1f}MiBu "
                    f"(n=8M W={W})"))
    merge_bench_json(BENCH_CODECS_JSON, updates)
    out.append(("datapath/fused/bench_json", 0.0,
                f"merged fused_datapath for {len(updates)} codecs into "
                f"{BENCH_CODECS_JSON}"))
    return out


def sim_rows():
    """Cycle-level simulator cross-check of the analytic exposure model.

    (The paper's operating-point scenarios live in ``bench_sim`` — not
    duplicated here, so every ``sim/*`` metric name is emitted once per
    full run.)
    """
    from repro.core.traffic import IciModel
    from repro.sim import LaunchSpec, simulate_launches

    # degenerate single-launch agreement: sim vs closed-form exposure
    n, w, wb = 8 << 20, 32, 1024.0        # cheap collective -> exposed
    model = ExposureModel()
    ref = model.exposed(n, w, wb)
    spec = LaunchSpec("agree", AggregationMode.G_BINARY, "vote_psum", n, wb)
    rep = simulate_launches(
        [spec], w, topology="ici_ring", datapath=model.datapath,
        ici=IciModel(link_bytes_per_s=model.link_bw, hop_latency_s=0.0,
                     launch_overhead_s=0.0))
    delta = abs(rep.launches[0].exposed_s - ref["t_exposed_s"])
    rel = delta / ref["t_exposed_s"] if ref["t_exposed_s"] else 0.0
    return [("sim/analytic_agreement", rep.launches[0].exposed_s * 1e6,
             f"rel_delta={rel:.2e} (tolerance 1e-2)")]

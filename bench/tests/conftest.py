"""Shared fixtures: a tiny cell in a checkout of its own.

The tests run on the CPU (``JAX_PLATFORMS=cpu``) at a size a test run can
hold, with four CPU devices for the cells of four workers; ``tiny_root``
writes a BENCHMARK.json with one small configuration and traffic mix
beside copies of the metric readers and the references, so the harness
finds them by name exactly as it finds the real cells.
"""
import json
import os
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

#: the cells of four workers run one to a device, as on four chips
FOUR_DEVICES = "--xla_force_host_platform_device_count=4"
if FOUR_DEVICES not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), FOUR_DEVICES) if f)

TINY_CONFIG = {
    "name": "tiny", "source": "a small same-family stand-in for the CPU",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "reduced": [], "assumed": {},
    "program": {"qk_norm": True, "qkv_bias": False, "remat": True},
}

TINY_TRAFFIC = {
    "name": "tiny-2x16", "plan": "gbin_packed", "batch_per_chip": 2,
    "seq_len": 16, "mesh": [1, 1], "pool_batches": 4,
    "markov": {"concentration": 0.5, "topk": 16},
    "checked_steps": 3, "trace_steps": 2, "total_steps": 1000,
    "peak_lr": 0.0003,
    "optimizer": {"name": "adamw", "warmup_steps": 100, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-08, "weight_decay": 0.0,
                  "grad_clip": 0.0},
}

#: limits for the tiny cells, set from CPU readings of the program (largest
#: over seeds 11-13 and 2**31 + 5: loss_gap 4.9e-4, grad_norm_gap 4.2e-3,
#: vote_mismatch 4.9e-3, update_norm_gap 1.2e-2, update_mismatch 1.0e-2)
#: and of the float8 control (smallest: 1.3e-3, 2.0e-2, 5.8e-2, 3.6e-2,
#: 9.5e-2)
TINY_LIMITS = {"loss_gap": 2e-3, "grad_norm_gap": 1e-2, "vote_mismatch": 4e-2,
               "update_norm_gap": 3e-2, "update_mismatch": 4e-2}

#: limits for the tiny cells of four workers, set from CPU readings of the
#: program (largest over seeds 11-13, 21, 22 and 2**31 + 5: loss_gap 3.2e-4,
#: grad_norm_gap 3.2e-3, vote_mismatch 1.4e-2, update_norm_gap 2.1e-2,
#: update_mismatch 1.5e-2), of the float8 control (smallest: 4.0e-4,
#: 2.1e-2, 0.16, 3.6e-2, 0.12) and of the faults (half_batch's loss_gap
#: 1.2e-2 at the least; no_exchange's vote_mismatch 0.50), as
#: lower^(1/3) * upper^(2/3), rounded down; update_norm_gap, which only a
#: state left unchanged separates, is not compared
TINY4_LIMITS = {"loss_gap": 3e-3, "grad_norm_gap": 1e-2, "vote_mismatch": 7e-2,
                "update_mismatch": 6e-2}

#: the tiny traffic on four workers, one to a device
TINY4_TRAFFIC = dict(TINY_TRAFFIC, name="tiny-4x2x16", mesh=[4, 1])

CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def write_root(root: pathlib.Path, configs: dict, traffics: dict,
               cells: list, limits: dict | None = None) -> pathlib.Path:
    """A checkout holding BENCHMARK.json and the named files."""
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "reference"):
        shutil.copytree(BENCH / sub, root / "bench" / sub,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", root / "bench" / "peaks.json")
    for name, c in configs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(c))
    for name, t in traffics.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for name, lim in (limits or {}).items():
        (root / "bench" / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": lim}))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm = dict(real)
    bm["configs"] = [{"name": n, "source": c["source"],
                      "file": f"bench/configs/{n}.json",
                      "reduced": c["reduced"], "why": "test"}
                     for n, c in configs.items()]
    bm["workloads"] = cells
    bm["per_layer"] = [dict(m, workloads=[w["name"] for w in cells])
                       for m in real["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_root(
        tmp_path, {"tiny": TINY_CONFIG}, {"tiny-2x16": TINY_TRAFFIC},
        [{"name": "tiny.gbin_packed", "config": "tiny",
          "traffic": "tiny-2x16", "chips": 1, "why": "test"}],
        limits={"tiny.gbin_packed": TINY_LIMITS})


@pytest.fixture
def four_root(tmp_path):
    return write_root(
        tmp_path, {"tiny": TINY_CONFIG}, {"tiny-4x2x16": TINY4_TRAFFIC},
        [{"name": "tiny.gbin_packed.4", "config": "tiny",
          "traffic": "tiny-4x2x16", "chips": 4, "why": "test"}],
        limits={"tiny.gbin_packed.4": TINY4_LIMITS})


@pytest.fixture(autouse=True, scope="session")
def _cpu():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    yield

"""Cells, configurations, traffic mixes and metrics are found by name:
adding files and an entry adds a cell, with no edit to any file there."""
import copy
import json

import pytest

from conftest import BENCH, ROOT, TINY_CONFIG, TINY_TRAFFIC, write_root
from harness import readers, spec


def test_every_real_cell_resolves():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = spec.find_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.limits.get("limits"), f"{w['name']} has no limits file"
        cfg = spec.model_config(cell.config)
        assert cfg.num_layers == cell.config["num_hidden_layers"]
        for m in bm["per_layer"]:
            assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_new_files_add_a_cell(tmp_path):
    other = dict(copy.deepcopy(TINY_CONFIG), name="tiny-wide",
                 intermediate_size=256)
    root = write_root(
        tmp_path, {"tiny": TINY_CONFIG, "tiny-wide": other},
        {"tiny-2x16": TINY_TRAFFIC,
         "tiny-4x16": dict(TINY_TRAFFIC, name="tiny-4x16", batch_per_chip=4)},
        [{"name": "a", "config": "tiny", "traffic": "tiny-2x16", "chips": 1,
          "why": "t"},
         {"name": "b", "config": "tiny-wide", "traffic": "tiny-4x16",
          "chips": 1, "why": "t"}])
    b = spec.find_cell("b", root)
    assert b.config["intermediate_size"] == 256
    assert b.global_batch == 4 and b.tokens_per_step == 64
    assert spec.model_config(b.config).d_ff == 256

    # a new per-layer metric: a reader file and an entry
    (root / "bench" / "metrics" / "tokens_per_step.py").write_text(
        "def read(ctx):\n    return ctx['cell'].tokens_per_step\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "tokens_per_step", "unit": "tokens",
                            "better": "higher", "source": "program_counter",
                            "layer": "data", "moves": "train_tokens_per_s",
                            "workloads": ["b"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    b = spec.find_cell("b", root)
    assert "tokens_per_step" in [m["name"] for m in b.per_layer]
    assert "tokens_per_step" not in [
        m["name"] for m in spec.find_cell("a", root).per_layer]
    got = readers.read_all(["tokens_per_step", "agg_kernel_ms"],
                           {"cell": b, "trace": None})
    assert got == {"tokens_per_step": 64.0}     # a silent reader is left out


def test_unknown_cell_names_the_known(tmp_path):
    root = write_root(tmp_path, {"tiny": TINY_CONFIG},
                      {"tiny-2x16": TINY_TRAFFIC},
                      [{"name": "a", "config": "tiny", "traffic": "tiny-2x16",
                        "chips": 1, "why": "t"}])
    try:
        spec.find_cell("zzz", root)
    except KeyError as e:
        assert "'a'" in str(e)
    else:
        raise AssertionError("no error for an unknown cell")


#: a tiny reference of another family, as a later configuration brings one:
#: its weights, sizes, loss and counts in one file, the rest from
#: reference.common
TINY_MOE_REFERENCE = '''
import dataclasses

import jax
import jax.numpy as jnp

from reference.common import einsum_for


@dataclasses.dataclass(frozen=True)
class Spec:
    d: int
    vocab: int
    experts: int
    dtype: str


def spec_of(config):
    return Spec(d=config["hidden_size"], vocab=config["vocab_size"],
                experts=config["program"]["moe"]["num_experts"],
                dtype=config["torch_dtype"])


def init_weights(seed, s):
    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed
    k = jax.random.split(key, 3)
    dt = jnp.dtype(s.dtype)
    return {"embed": {"tok": (jax.random.normal(k[0], (s.vocab, s.d))
                              * 0.02).astype(dt)},
            "layers": {"router": jax.random.normal(k[1], (s.d, s.experts))
                       .astype(dt),
                       "experts": (jax.random.normal(k[2],
                                                     (s.experts, s.d, s.d))
                                   / s.d ** 0.5).astype(dt)}}


def loss(w, tokens, labels, s, *, matmul="highest"):
    ein = einsum_for(matmul)
    x = jnp.take(w["embed"]["tok"], tokens, axis=0)
    gate = jax.nn.softmax(ein("btd,de->bte", x, w["layers"]["router"]), -1)
    y = ein("bte,bted->btd", gate,
            ein("btd,edf->btef", x, w["layers"]["experts"]))
    logits = ein("btd,vd->btv", x + y, w["embed"]["tok"])
    lz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lz - gold)


def model_flops_per_token(config, seq_len):
    return 12345.0


def admitted_elements(config):
    return 678
'''

TINY_MOE_CONFIG = {
    "name": "tiny-moe", "source": "a small expert-share stand-in",
    "hidden_size": 32, "intermediate_size": 96, "num_hidden_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "vocab_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "reduced": [], "assumed": {}, "reference": "tiny_moe",
    "program": {"family": "moe", "remat": False,
                "moe": {"num_experts": 4, "top_k": 2, "d_expert": 16,
                        "num_shared": 1, "first_dense": 1}},
}


def test_a_new_family_is_new_files_and_an_entry(tmp_path):
    """A configuration of another family names its model fields and its
    own reference, whose counts the FLOP and byte functions use: files and
    an entry, no edit to the harness or to reference/model.py."""
    from harness import cell as C
    from harness import flops, traffic
    from repro.models import MoEConfig

    root = write_root(tmp_path, {"tiny-moe": TINY_MOE_CONFIG},
                      {"tiny-2x16": TINY_TRAFFIC},
                      [{"name": "m", "config": "tiny-moe",
                        "traffic": "tiny-2x16", "chips": 1, "why": "t"}])
    (root / "bench" / "reference" / "tiny_moe.py").write_text(
        TINY_MOE_REFERENCE)
    cell = spec.find_cell("m", root)

    cfg = spec.model_config(cell.config)
    assert cfg.family == "moe"
    assert cfg.moe == MoEConfig(num_experts=4, top_k=2, d_expert=16,
                                num_shared=1, first_dense=1)
    assert cfg.d_ff == 96 and cfg.remat is False

    assert cell.reference.admitted_elements(cell.config) == 678
    assert flops.model_flops_per_token(cell.config, 16, root=root) == 12345.0
    assert flops.admitted_elements(cell.config, root=root) == 678
    assert flops.agg_bytes_per_chip(cell.config, root=root) == 678 * 4.25
    mfu = readers.read_all(
        ["mfu.train"], {"cell": cell, "tokens_per_s": 1e6,
                        "peaks": {"bf16_flops": 1e12}})["mfu.train"]
    assert mfu == 100.0 * 12345.0 * 1e6 / 1e12

    pool = traffic.pool_for(cell, 3)
    ref = C.reference_readings(cell, 3, pool, 2)
    assert ref["paths"] == ["embed/tok", "layers/experts", "layers/router"]
    # the router takes the FP32 mean, as the program's plan gives it
    assert len(ref["loss"]) == 2 and len(ref["bits"]) == 1
    assert ref["voted_sizes"] == [4 * 32 * 32]


def test_family_defaults_to_dense_and_names_are_checked(tmp_path):
    assert spec.model_config(TINY_CONFIG).family == "dense"
    bad = dict(copy.deepcopy(TINY_CONFIG), reference="../model")
    try:
        spec.reference_module(bad)
    except ValueError as e:
        assert "../model" in str(e)
    else:
        raise AssertionError("a path was taken for a module name")


REAL = {
    "qwen3-0.6b": dict(
        cfg=dict(num_layers=28, d_model=1024, num_heads=16, num_kv_heads=8,
                 d_ff=3072, vocab_size=151936, head_dim=128,
                 rope_theta=1000000, norm_eps=1e-06, tie_embeddings=True,
                 dtype="bfloat16", qk_norm=True, qkv_bias=False, remat=True),
        flops=3_752_067_072, admitted=440_401_920),
    "qwen2.5-14b-2layer": dict(
        cfg=dict(num_layers=2, d_model=5120, num_heads=40, num_kv_heads=8,
                 d_ff=13824, vocab_size=19008, rope_theta=1000000.0,
                 norm_eps=1e-05, tie_embeddings=False, dtype="bfloat16",
                 qk_norm=False, qkv_bias=True, remat=True),
        flops=3_918_397_440, admitted=550_516_736),
}


@pytest.mark.parametrize("name", sorted(REAL))
def test_real_configurations_read_as_before(name):
    """The two configurations give the ModelConfig and the counts they
    gave before a configuration could name its fields and reference."""
    from harness import flops
    from repro.models import ModelConfig

    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    want = REAL[name]
    assert spec.model_config(config) == ModelConfig(
        name=name, family="dense", **want["cfg"])
    assert flops.model_flops_per_token(config, 512) == want["flops"]
    assert flops.admitted_elements(config) == want["admitted"]

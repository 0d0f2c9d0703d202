"""Find a cell by name: BENCHMARK.json, its configuration and traffic files.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/``; this module
resolves them by the names ``BENCHMARK.json`` gives, so a new cell is new
files plus an entry, never an edit here.  A configuration file states the
program's model (``program``: any field of ``repro.models.ModelConfig``,
the nested configurations such as ``moe`` as objects) and names its plain
reference (``reference``: a module under ``bench/reference/``).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import sys
import typing

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

#: Hugging Face ``config.json`` key -> the program's ModelConfig field
HF_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    root: pathlib.Path      # the checkout whose bench/ holds the files
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic file
    limits: dict            # bench/limits/<cell>.json ({} when absent)
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple        # metric entries that this cell reports

    @property
    def reference(self):
        """The plain reference module the configuration names."""
        return reference_module(self.config, self.root)

    @property
    def global_batch(self) -> int:
        return self.traffic["batch_per_chip"] * self.chips

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.traffic["seq_len"]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve workload ``name`` of ``root/BENCHMARK.json`` to its files."""
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    mesh = traffic["mesh"]
    if mesh[0] * mesh[1] != w["chips"]:
        raise ValueError(f"traffic {w['traffic']!r} mesh {mesh} does not "
                         f"span the cell's {w['chips']} chips")
    limits_path = root / "bench" / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    per_layer = tuple(m for m in bm["per_layer"]
                      if name in m.get("workloads", cells))
    end_to_end = tuple(m for m in bm["end_to_end"]
                       if name in m.get("workloads", cells))
    return Cell(name=name, root=root, chips=w["chips"], config=config,
                traffic=traffic, limits=limits, end_to_end=end_to_end,
                per_layer=per_layer)


def model_config(config: dict):
    """The program's ModelConfig for a configuration file.

    The file holds the source's ``config.json`` keys as they are run;
    ``program`` holds what the source states by its model type (QK norm,
    QKV bias, ``family``, a ``moe`` block) and how the program runs it
    (remat), by the names of ModelConfig's fields.  ``family`` is
    ``dense`` unless stated.  A field whose type is a dataclass (``moe``,
    ``ssm``) is given as an object of that dataclass's fields.
    """
    from repro.models import ModelConfig
    fields = {HF_KEYS[k]: v for k, v in config.items() if k in HF_KEYS}
    fields.update(config.get("program", {}))
    fields.setdefault("family", "dense")
    return _build(ModelConfig, dict(fields, name=config["name"]))


def _build(cls, values: dict):
    """``cls(**values)``, each object given for a field typed by a
    dataclass built into that dataclass the same way."""
    hints = typing.get_type_hints(cls)
    out = {}
    for k, v in values.items():
        sub = _dataclass_of(hints.get(k))
        out[k] = _build(sub, v) if sub is not None and isinstance(v, dict) \
            else v
    return cls(**out)


def _dataclass_of(tp):
    """The dataclass a field's type names (``X``, ``Optional[X]``), or None."""
    for t in (tp, *typing.get_args(tp)):
        if dataclasses.is_dataclass(t):
            return t
    return None


def reference_module(config: dict, root: pathlib.Path = ROOT):
    """The plain reference a configuration names with ``"reference"``
    (``model`` when it names none): ``bench/reference/<name>.py`` of the
    checkout ``root``.  ``reference/common.py`` says what it provides."""
    return _load_reference(config.get("reference", "model"),
                           pathlib.Path(root))


@functools.lru_cache(maxsize=None)
def _load_reference(name: str, root: pathlib.Path):
    if not name.isidentifier():
        raise ValueError(f"reference {name!r} is not a module name")
    path = root / "bench" / "reference" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reference module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}_{abs(hash(str(path)))}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod

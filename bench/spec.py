"""Find a cell's configuration, traffic mix, metrics and architecture by
name.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration and
a traffic mix; each is a JSON file under ``bench/configs/`` and
``bench/traffic/``.  A per-layer metric is a module ``bench/metrics/
<name>.py`` that defines ``LAYER``, ``MOVES``, ``MATCH`` and ``read(ctx)``.
A configuration of another architecture than the dense Qwen2/Qwen3
decoder names a module ``bench/arch/<name>.py`` under its key
``architecture`` (its reference and work counts), and maps further
published keys to the program's ``ModelConfig`` fields under its key
``program_keys``.  Adding a cell, a mix, a metric or an architecture adds
files and entries; no code here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
ARCH_DIR = HERE / "arch"

# the configuration file's published keys -> the program's ModelConfig
# names; a configuration's own "program_keys" are merged over these
PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json end_to_end entries of this cell
    per_layer: list         # BENCHMARK.json per_layer entries of this cell
    reduced: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                reduced=list(conf.get("reduced", [])))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader module of per-layer metric ``name``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_architecture(name: str):
    """The module ``bench/arch/<name>.py`` that a configuration's
    ``architecture`` key names.  It defines the reference,
    ``init_weights(c, seed)`` and ``forward_logits(w, tokens, pos, c,
    control)``, and may define any of ``bench/work.py``'s counts, each of
    which replaces the dense one for that configuration."""
    return _load_module(ARCH_DIR / f"{name}.py")


@functools.lru_cache(maxsize=None)
def _load_module(path: Path):
    if not path.exists():
        raise FileNotFoundError(f"no architecture module {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_arch_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(config: dict):
    """The architecture module a configuration names, or ``None`` for the
    dense Qwen2/Qwen3 decoder of ``bench/reference.py``."""
    name = config.get("architecture")
    return None if name is None else load_architecture(name)


def program_keys(config: dict) -> dict:
    """Published key -> ``ModelConfig`` field: ``PROGRAM_KEYS`` with the
    configuration's own ``program_keys`` merged over it."""
    return {**PROGRAM_KEYS, **config.get("program_keys", {})}


def program_kwargs(config: dict, reduced, *, rehearse: bool = False) -> dict:
    """``model_kwargs`` for the replica: the program's architecture name
    plus every key the cell cuts (``reduced``) or, in a CPU rehearsal,
    every mapped key of the shape with the rehearsal block laid over it."""
    keys = program_keys(config)
    kw = {"arch": config["program_arch"]}
    if rehearse:
        src = model_shape(config, rehearse=True)
        for k in keys:
            if k in src:
                kw[keys[k]] = src[k]
        return kw
    for k in reduced:
        if k not in keys:
            raise KeyError(f"reduced key {k!r} is in neither PROGRAM_KEYS nor "
                           f"the configuration's program_keys (have "
                           f"{sorted(keys)})")
        kw[keys[k]] = config[k]
    return kw


def model_shape(config: dict, *, rehearse: bool = False) -> dict:
    """The configuration's shape keys, with the rehearsal's tiny widths
    laid over them when ``rehearse``."""
    out = dict(config)
    if rehearse:
        out.update(config["rehearse"]["shape"])
    return out

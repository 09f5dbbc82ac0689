"""Find a cell's configuration, traffic mix and metrics by name.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration and
a traffic mix; each is a JSON file under ``bench/configs/`` and
``bench/traffic/``.  A per-layer metric is a module ``bench/metrics/
<name>.py`` that defines ``LAYER``, ``MOVES``, ``MATCH`` and ``read(ctx)``.
Adding a cell, a mix or a metric adds files and entries; no code here
changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# the configuration file's published keys -> the program's ModelConfig names
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


def program_kwargs(config: dict, reduced, *, rehearse: bool = False) -> dict:
    """``model_kwargs`` for the replica: the program's architecture name
    plus every key the cell cuts (``reduced``) or, in a CPU rehearsal,
    every width of the rehearsal block."""
    kw = {"arch": config["program_arch"]}
    keys = list(reduced)
    src = config
    if rehearse:
        src = model_shape(config, rehearse=True)
        keys = list(PROGRAM_KEYS)
    for k in keys:
        kw[PROGRAM_KEYS[k]] = src[k]
    return kw


def model_shape(config: dict, *, rehearse: bool = False) -> dict:
    """The configuration's shape keys, with the rehearsal's tiny widths
    laid over them when ``rehearse``."""
    out = dict(config)
    if rehearse:
        out.update(config["rehearse"]["shape"])
    return out

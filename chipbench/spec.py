"""Find a cell, its configuration, its traffic mix and its metrics by name.

Everything is found from ``BENCHMARK.json`` and the files beside this one:
``configs/<config>.json``, ``traffic/<mix>.json`` and
``metrics/<metric>.py``.  A new cell or metric is new files and entries;
no file here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# published config key -> the program's ModelConfig field it must equal
WIDTH_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_q_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
    "mlp_bias": "mlp_bias",
    "mlp_variant": "mlp_variant",
}


@dataclasses.dataclass
class Cell:
    name: str
    config: dict        # configs/<config>.json, with "name" set
    traffic: dict       # traffic/<mix>.json, with "name" set
    chips: int
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench: dict | None = None,
              here: Path = HERE) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    config = load_json(here / "configs" / f"{w['config']}.json")
    config["name"] = w["config"]
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")
    traffic["name"] = w["traffic"]
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_metric(name: str, here: Path = HERE):
    """The module ``metrics/<name>.py``: ``NAME``, ``UNIT``, ``LAYER`` and
    ``compute(ctx)`` (see ``metrics/_common.py``), which returns a number or None
    when the run holds nothing for it to read."""
    path = here / "metrics" / f"{name}.py"
    for d in (str(here), str(here / "metrics")):
        if d not in sys.path:
            sys.path.insert(0, d)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if mod.NAME != name:
        raise ValueError(f"{path} defines NAME {mod.NAME!r}, not {name!r}")
    return mod


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    The repo's config named by ``repo_config`` is taken as it is; keys in
    ``reduced`` are set from the file.  Every key of ``WIDTH_FIELDS`` in
    the file must then equal the program's value, so the program cannot
    drift from the configuration the benchmark states."""
    from repro.configs import get_config

    cfg = get_config(config["repo_config"])
    changes = {WIDTH_FIELDS[k]: config[k] for k in config.get("reduced", [])}
    if changes:
        cfg = dataclasses.replace(cfg, **changes)
    wrong = {k: (config[k], getattr(cfg, f)) for k, f in WIDTH_FIELDS.items()
             if k in config and getattr(cfg, f) != config[k]}
    if wrong:
        raise ValueError(f"{config['name']}: the program's config differs "
                         f"from the file (file, program): {wrong}")
    return cfg

"""Find a cell, its configuration, its model family, its traffic mix and
its metrics by name.

Everything is found from ``BENCHMARK.json`` and the files beside this one:
``configs/<config>.json``, ``families/<family>.py`` (named by the
configuration's ``"family"`` key), ``traffic/<mix>.json`` and
``metrics/<metric>.py``.  A new cell, family or metric is new files and
entries; no file here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# published config key -> the program's ModelConfig field it must equal, for
# the keys every family shares; a family adds its own (``WIDTH_FIELDS``)
COMMON_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_q_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
# what a family module defines: its published keys beyond the common ones
# (``WIDTH_FIELDS``), the keys its forward pass reads (``FORWARD_KEYS``), the
# weights' table and draws (``layout``, ``STD_SCALE``, ``CENTRED``; see
# weights.py), the reference's forward pass (``logits``; see reference.py),
# and the counts with counts.py's signatures
FAMILY_API = ("WIDTH_FIELDS", "FORWARD_KEYS", "layout", "STD_SCALE",
              "CENTRED", "logits", "params", "matmul_params",
              "kv_bytes_per_token", "token_flops", "prefill_flops",
              "decode_attn_bytes")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


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
    family_of(config, here)     # loaded from here; later lookups by name
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


def load_family(name: str, here: Path = HERE):
    """The model family ``families/<name>.py``: what ``FAMILY_API`` lists.

    Loaded once per name in a process: every later call for the name gets
    that module, wherever it looks."""
    mod_name = f"chipbench_family_{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    path = here / "families" / f"{name}.py"
    if not NAME.fullmatch(name) or not path.is_file():
        raise ValueError(f"no model family {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [a for a in FAMILY_API if not hasattr(mod, a)]
    if missing:
        raise ValueError(f"{path} does not define {missing}")
    sys.modules[mod_name] = mod
    return mod


def family_of(config: dict, here: Path = HERE):
    """The model family the configuration file names."""
    if "family" not in config:
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"model family: give it \"family\", the name of a "
                         f"file {here / 'families'}/<name>.py")
    return load_family(config["family"], here)


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    The repo's config named by ``repo_config`` is taken as it is; keys in
    ``reduced`` are set from the file.  Every key of ``COMMON_FIELDS`` and
    of the family's ``WIDTH_FIELDS`` in the file must then equal the
    program's value, so the program cannot drift from the configuration the
    benchmark states."""
    from repro.configs import get_config

    fields = {**COMMON_FIELDS, **family_of(config).WIDTH_FIELDS}
    reduced = config.get("reduced", [])
    unknown = [k for k in reduced if k not in fields]
    if unknown:
        raise ValueError(f"{config['name']}: reduced keys {unknown} map to no "
                         f"field of the program's config")
    cfg = get_config(config["repo_config"])
    changes = {fields[k]: config[k] for k in reduced}
    if changes:
        cfg = dataclasses.replace(cfg, **changes)
    wrong = {k: (config[k], getattr(cfg, f)) for k, f in fields.items()
             if k in config and getattr(cfg, f) != config[k]}
    if wrong:
        raise ValueError(f"{config['name']}: the program's config differs "
                         f"from the file (file, program): {wrong}")
    return cfg

"""The harness finds cells, configurations, mixes and metrics by name from
files alone; adding one needs no edit to a file that is there."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import spec  # noqa: E402

ROOT = HERE.parent


def test_benchmark_names_files_that_exist():
    bench = spec.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT, bench)
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    for m in bench["per_layer"]:
        mod = spec.load_metric(m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER) == (m["name"], m["unit"],
                                                  m["layer"])
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


@pytest.mark.parametrize("path", ["configs/starcoder2-3b.json",
                                  "tests/data/yi-9b.json"])
def test_config_files_match_the_program(path):
    name = Path(path).stem
    c = spec.load_json(HERE / path)
    c["name"] = name
    cfg = spec.program_config(c)
    assert cfg.n_layers == c["num_hidden_layers"]
    bad = dict(c, hidden_size=c["hidden_size"] + 1)
    with pytest.raises(ValueError):
        spec.program_config(bad)


def test_a_new_cell_and_metric_are_files_and_entries(tmp_path):
    """Copy the benchmark, add a config, a mix, a metric and a cell, and
    find them; the copied files stay byte for byte as they were."""
    root = tmp_path / "checkout"
    here = root / "chipbench"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "configs" / "newmodel.json").write_text(
        (here / "configs" / "starcoder2-3b.json").read_text())
    mix = json.loads((here / "traffic" / "codegen.json").read_text())
    mix["clients"] = 8
    (here / "traffic" / "newmix.json").write_text(json.dumps(mix))
    (here / "metrics" / "new_metric.py").write_text(
        'NAME = "new_metric"\nUNIT = "ms"\nLAYER = "device"\n\n\n'
        'def compute(ctx):\n    return 1.5\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "newmodel.newmix",
                               "config": "newmodel", "traffic": "newmix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "tpot_p95_ms",
                               "workloads": ["newmodel.newmix"]})
    cell = spec.load_cell("newmodel.newmix", root, bench, here)
    assert cell.traffic["clients"] == 8
    assert cell.config["name"] == "newmodel"
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert spec.load_metric("new_metric", here).compute({}) == 1.5
    assert all(p.read_bytes() == b for p, b in before.items())


TOY_FAMILY = '''"""A toy expert family for the harness's tests: per layer a softmax
router over a stack of experts, and an untied head."""
import jax
import jax.numpy as jnp
import numpy as np

from reference import round_weight

WIDTH_FIELDS = {"num_experts": "n_experts", "moe_intermediate_size": "d_ff"}
FORWARD_KEYS = ("vocab_size",)
STD_SCALE = {"blocks/experts/w_out": 0.5}
CENTRED = ()


def layout(c):
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    E, ff = c["num_experts"], c["moe_intermediate_size"]
    return {"embed": ((V, d), "embed"), "lm_head": ((d, V), "embed"),
            "blocks/router": ((L, d, E), "matrix"),
            "blocks/experts/w_in": ((L, E, d, ff), "matrix"),
            "blocks/experts/w_out": ((L, E, ff, d), "matrix")}


def logits(c, quant, w, tokens):
    def layer(x, lw):
        lw = {k: round_weight(v.astype(jnp.float32), quant)
              for k, v in lw.items()}
        r = jax.nn.softmax(x @ lw["router"], axis=-1)
        h = jax.nn.gelu(jnp.einsum("sd,edf->sef", x, lw["w_in"]))
        y = jnp.einsum("sef,efd->sed", h, lw["w_out"])
        return x + jnp.einsum("se,sed->sd", r, y), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, {"router": w["blocks"]["router"],
                                   **w["blocks"]["experts"]})
    head = round_weight(w["lm_head"].astype(jnp.float32), quant)
    return (x @ head)[:, :c["vocab_size"]]


def params(c):
    return sum(int(np.prod(s)) for s, _ in layout(c).values())


def matmul_params(c):
    return params(c) - c["vocab_size"] * c["hidden_size"]


def kv_bytes_per_token(c, itemsize):
    return 0


def token_flops(c, keys):
    return 2.0 * matmul_params(c)


def prefill_flops(c, prompt):
    return token_flops(c, 0) * prompt


def decode_attn_bytes(c, keys, itemsize):
    return 0
'''


def test_a_new_family_is_files_and_entries(tmp_path):
    """Copy the benchmark, add a model family and a configuration that
    names it, with a reduced key outside the common map and a leaf of
    stacked experts; the cell, the program's config, the weights, a count
    and the reference all go through it, and the copied files stay byte
    for byte as they were."""
    import numpy as np

    import counts
    import reference
    import weights

    root = tmp_path / "checkout"
    here = root / "chipbench"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "families" / "toymoe.py").write_text(TOY_FAMILY)
    c = {"repo_config": "olmoe-1b-7b", "family": "toymoe",
         "num_hidden_layers": 2, "hidden_size": 32, "num_experts": 4,
         "moe_intermediate_size": 16, "vocab_size": 64,
         "num_key_value_heads": 16, "head_dim": 128,
         "tie_word_embeddings": False,
         "reduced": ["num_hidden_layers", "hidden_size", "num_experts",
                     "moe_intermediate_size", "vocab_size"]}
    (here / "configs" / "toy.json").write_text(json.dumps(c))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy.codegen", "config": "toy",
                               "traffic": "codegen", "chips": 1,
                               "why": "test"})
    cell = spec.load_cell("toy.codegen", root, bench, here)
    c = cell.config
    cfg = spec.program_config(c)
    assert (cfg.n_experts, cfg.d_ff, cfg.n_layers) == (4, 16, 2)
    with pytest.raises(ValueError):
        spec.program_config(dict(c, num_key_value_heads=8))
    table = weights.layout(c)
    assert table["blocks/experts/w_in"][0] == (2, 4, 32, 16)
    assert counts.params(c) == weights.param_count(c)
    assert counts.token_flops(c, 100) == 2.0 * counts.matmul_params(c)
    w = weights.make(c, 2**31 + 3)
    assert w["blocks"]["experts"]["w_out"].shape == (2, 4, 16, 32)
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 64, 9), rng.integers(0, 64, 4).tolist()
    g, gc = reference.request_gaps(c, w, prompt, served, 16, 4, "int8")
    assert g.shape == gc.shape == (4,) and (g >= 0).all() and (gc >= 0).all()
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("family", [None, "no-such-family"])
def test_a_config_names_a_family_that_exists(family):
    c = spec.load_json(HERE / "configs" / "starcoder2-3b.json")
    c["name"] = "starcoder2-3b"
    del c["family"]
    if family:
        c["family"] = family
    with pytest.raises(ValueError, match=re.escape(str(HERE / "families"))):
        spec.family_of(c)


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", ROOT)

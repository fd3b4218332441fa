"""The harness finds cells, configurations, mixes and metrics by name from
files alone; adding one needs no edit to a file that is there."""
import json
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


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", ROOT)

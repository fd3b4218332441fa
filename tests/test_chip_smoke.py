"""``chip_smoke.py`` and the compile-cache helper, on the CPU at tiny size.

The chip smoke refuses to run off the chip, so its phases are driven here
directly with a reduced config: the control flow, the checks and the
teacher-forced reference stay exercised on every change.
"""
import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.types import TPU_V5E_SPEC, hardware_spec_for
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hardware_spec_lookup():
    assert hardware_spec_for("TPU v5 lite") is TPU_V5E_SPEC
    with pytest.raises(ValueError, match="no HardwareSpec"):
        hardware_spec_for("cpu")


def test_one_chip_phase_serves_and_matches_forward(smoke):
    cfg = get_config("gemma2-2b").reduced(n_q_heads=4, n_kv_heads=2,
                                          d_model=64, vocab=512)
    out = smoke.one_chip_phase(cfg, TPU_V5E_SPEC, 0, blocks_per_chip=64,
                               seqs_per_chip=8, prompt_lens=(16, 40, 72),
                               new_tokens=(8, 16))
    assert out["replicas"] == [("jnp", False)]
    same = total = 0
    for rid, _, prompt, _ in out["reqs"]:
        toks = np.asarray(out["served"][rid])
        am, _, _ = smoke.teacher_forced(out["params"], cfg, prompt,
                                        list(toks))
        same += int((am == toks).sum())
        total += len(toks)
    assert same >= 0.9 * total


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

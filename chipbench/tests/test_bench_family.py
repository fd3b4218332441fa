"""The dense family gives what the harness gave before the model behind a
configuration became a file of its own, bit for bit.

``data/dense_parity.json`` was recorded from the harness whose forward
pass, weight table and counts were its own dense code: a digest of every
leaf ``weights.make`` draws for two seeds, the reference's gap arrays for
three fixed requests (the longest past one block of queries) with and
without the int8 control, and every count at fixed arguments.
"""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402

DATA = HERE / "tests" / "data"
PARITY = json.loads((DATA / "dense_parity.json").read_text())
FILES = {"tiny": DATA / "tiny.json", "tiny_yi": DATA / "tiny_yi.json",
         "starcoder2-3b": HERE / "configs" / "starcoder2-3b.json",
         "yi-9b": DATA / "yi-9b.json"}


def config(name):
    c = spec.load_json(FILES[name])
    c["name"] = name
    return c


def digest(x):
    a = np.asarray(x)
    return (f"{a.dtype} {list(a.shape)} "
            f"{hashlib.sha256(a.tobytes()).hexdigest()}")


@pytest.mark.parametrize("seed", PARITY["seeds"])
@pytest.mark.parametrize("name", ["tiny", "tiny_yi"])
def test_dense_weights_are_the_recorded_ones(name, seed):
    got = {p: digest(x) for p, x in
           weights.flatten(weights.make(config(name), seed)).items()}
    assert got == PARITY["weights"][name][str(seed)]


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("name", ["tiny", "tiny_yi"])
def test_dense_reference_gaps_are_the_recorded_ones(name, quant):
    c = config(name)
    w = weights.make(c, PARITY["seeds"][0])
    for r, (g_hex, gc_hex) in zip(PARITY["requests"],
                                  PARITY["gaps"][name][str(quant)]):
        g, gc = reference.request_gaps(c, w, np.array(r["prompt"]),
                                       r["served"], PARITY["seq_len"],
                                       PARITY["n_read"], quant)
        assert g.tobytes().hex() == g_hex
        assert gc.tobytes().hex() == gc_hex


@pytest.mark.parametrize("name", sorted(FILES))
def test_dense_counts_are_the_recorded_ones(name):
    c = config(name)
    got = {"params": counts.params(c),
           "matmul_params": counts.matmul_params(c),
           "kv_bytes_per_token": counts.kv_bytes_per_token(c),
           "token_flops_1000": repr(counts.token_flops(c, 1000)),
           "prefill_flops_777": repr(counts.prefill_flops(c, 777)),
           "decode_attn_bytes_1234": counts.decode_attn_bytes(c, 1234),
           "param_count": weights.param_count(c)}
    assert got == PARITY["counts"][name]


@pytest.mark.parametrize("quant", reference.QUANTS)
def test_a_stack_of_matrices_is_rounded_one_matrix_at_a_time(quant):
    """Layers x experts x [in, out]: each matrix gets its own scales."""
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 8, 5), jnp.float32)
    w = w * jnp.arange(1, 7, dtype=jnp.float32).reshape(2, 3, 1, 1)
    got = np.asarray(reference.round_weight(w, quant))
    for i in range(2):
        for j in range(3):
            np.testing.assert_array_equal(
                got[i, j], np.asarray(reference.round_weight(w[i, j], quant)))
    assert not np.array_equal(got, np.asarray(w))

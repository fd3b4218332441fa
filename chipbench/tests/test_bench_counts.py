"""The count functions against hand-computed values, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402
import peaks  # noqa: E402
import weights  # noqa: E402

HERE = Path(__file__).resolve().parents[1]
# yi-9b is no cell's configuration; its published widths are test data
FILES = {"starcoder2-3b": HERE / "configs" / "starcoder2-3b.json",
         "yi-9b": HERE / "tests" / "data" / "yi-9b.json"}


def config(name):
    return json.loads(FILES[name].read_text())


# hand-computed from the published widths:
# starcoder2-3b: tied 49152 x 3072 embedding; per layer q/o 3072^2, k/v
#   3072 x 256, biases 3072 + 2 x 256, MLP 2 x 3072 x 12288 + 12288 + 3072,
#   two norms; a final norm.
# yi-9b: untied 64000 x 4096 embedding and head; per layer q/o 4096^2, k/v
#   4096 x 512, SwiGLU 3 x 4096 x 11008, two norms; a final norm.
HAND = {
    "starcoder2-3b": {
        "params": 49152 * 3072 + 30 * (2 * 3072 * 3072 + 2 * 3072 * 256
                                       + 3072 + 2 * 256
                                       + 2 * 3072 * 12288 + 12288 + 3072
                                       + 2 * 3072) + 3072,
        "kv": 30 * 2 * 2 * 128 * 2,
    },
    "yi-9b": {
        "params": 2 * 64000 * 4096 + 48 * (2 * 4096 * 4096
                                           + 2 * 4096 * 512
                                           + 3 * 4096 * 11008 + 2 * 4096)
        + 4096,
        "kv": 48 * 2 * 4 * 128 * 2,
    },
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_params_and_kv_bytes(name):
    c = config(name)
    assert counts.params(c) == HAND[name]["params"]
    assert weights.param_count(c) == HAND[name]["params"]
    assert counts.kv_bytes_per_token(c) == HAND[name]["kv"]


def test_hand_values_are_the_published_sizes():
    assert HAND["starcoder2-3b"]["params"] == 3_030_091_776      # 3.03B
    assert HAND["yi-9b"]["params"] == 8_829_407_232              # 8.83B
    assert HAND["starcoder2-3b"]["kv"] == 30_720
    assert HAND["yi-9b"]["kv"] == 98_304


@pytest.mark.parametrize("name", sorted(HAND))
def test_token_and_prefill_flops(name):
    c = config(name)
    L, H, D = (c["num_hidden_layers"], c["num_attention_heads"],
               c["head_dim"])
    mm = counts.matmul_params(c)
    # every matrix a token multiplies through, the head included
    emb = c["vocab_size"] * c["hidden_size"]
    head = 0 if c["tie_word_embeddings"] else emb
    assert mm == counts.params(c) - emb - head + emb - _vectors(c)
    assert counts.token_flops(c, 1000) == 2 * mm + 4 * L * H * D * 1000
    assert counts.prefill_flops(c, 3) == pytest.approx(
        sum(counts.token_flops(c, k) for k in (1, 2, 3)))


def _vectors(c):
    """Biases and norm weights: parameters no token multiplies through."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    v = L * 2 * d + d
    if c["qkv_bias"]:
        v += L * (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
            * c["head_dim"]
    if c["mlp_bias"]:
        v += L * (c["intermediate_size"] + d)
    return v


def test_decode_attention_bytes():
    c = config("starcoder2-3b")
    # 30 layers x (K and V: 1000 keys x 2 heads x 128; q and out: 24 x 128)
    # x 2 bytes
    assert counts.decode_attn_bytes(c, 1000) == 30 * (
        2 * 1000 * 2 * 128 + 2 * 24 * 128) * 2


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")

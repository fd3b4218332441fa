"""Operations and bytes, counted from a configuration file's widths.

Nothing here asks the program: the same work is counted whatever serves it.
"""
from __future__ import annotations

BF16 = 2


def _dims(c: dict) -> tuple:
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"])


def params(c: dict) -> int:
    """Every parameter the served model holds."""
    L, d, H, Hkv, D, ff, V = _dims(c)
    attn = d * H * D + 2 * d * Hkv * D + H * D * d
    if c["qkv_bias"]:
        attn += H * D + 2 * Hkv * D
    n_in = 2 if c["mlp_variant"] in ("swiglu", "geglu") else 1
    mlp = (n_in + 1) * d * ff
    if c["mlp_bias"]:
        mlp += ff + d
    head = 0 if c["tie_word_embeddings"] else d * V
    return V * d + head + L * (attn + mlp + 2 * d) + d


def matmul_params(c: dict) -> int:
    """Parameters a token multiplies through: every matrix of the layers
    and the output head (the embedding lookup is a gather)."""
    L, d, H, Hkv, D, ff, V = _dims(c)
    n_in = 2 if c["mlp_variant"] in ("swiglu", "geglu") else 1
    layer = d * H * D + 2 * d * Hkv * D + H * D * d + (n_in + 1) * d * ff
    return L * layer + d * V


def kv_bytes_per_token(c: dict, itemsize: int = BF16) -> int:
    L, _, _, Hkv, D, _, _ = _dims(c)
    return L * 2 * Hkv * D * itemsize


def token_flops(c: dict, keys: int) -> float:
    """Model FLOPs of one token that attends ``keys`` positions: two per
    multiplied parameter, and QK^T and PV over the keys in every layer."""
    L, _, H, _, D, _, _ = _dims(c)
    return 2.0 * matmul_params(c) + 4.0 * L * H * D * keys


def prefill_flops(c: dict, prompt: int) -> float:
    """A causal prefill of ``prompt`` tokens: token i attends i + 1 keys."""
    L, _, H, _, D, _, _ = _dims(c)
    return (2.0 * matmul_params(c) * prompt
            + 4.0 * L * H * D * prompt * (prompt + 1) / 2)


def decode_attn_bytes(c: dict, keys: int, itemsize: int = BF16) -> int:
    """Bytes one sequence's decode attention must move in one step: its K
    and V at ``keys`` positions in every layer, the query read and the
    output written."""
    L, _, H, Hkv, D, _, _ = _dims(c)
    return L * (2 * keys * Hkv * D + 2 * H * D) * itemsize

"""Operations and bytes, counted from a configuration file's widths.

Nothing here asks the program: the same work is counted whatever serves it.
Each count is the configuration's model family's (``families/<family>.py``,
found by the file's ``"family"`` key), with the signature given here.
"""
from __future__ import annotations

import spec

BF16 = 2


def params(c: dict) -> int:
    """Every parameter the served model holds."""
    return spec.family_of(c).params(c)


def matmul_params(c: dict) -> int:
    """Parameters a token multiplies through: every matrix of the layers
    and the output head (the embedding lookup is a gather)."""
    return spec.family_of(c).matmul_params(c)


def kv_bytes_per_token(c: dict, itemsize: int = BF16) -> int:
    return spec.family_of(c).kv_bytes_per_token(c, itemsize)


def token_flops(c: dict, keys: int) -> float:
    """Model FLOPs of one token that attends ``keys`` positions."""
    return spec.family_of(c).token_flops(c, keys)


def prefill_flops(c: dict, prompt: int) -> float:
    """A causal prefill of ``prompt`` tokens: token i attends i + 1 keys."""
    return spec.family_of(c).prefill_flops(c, prompt)


def decode_attn_bytes(c: dict, keys: int, itemsize: int = BF16) -> int:
    """Bytes one sequence's decode attention must move in one step: its
    cache at ``keys`` positions in every layer, the query read and the
    output written."""
    return spec.family_of(c).decode_attn_bytes(c, keys, itemsize)

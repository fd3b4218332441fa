"""The dense decoder-only transformer family.

Its forward pass, weight layout and counts, read from a configuration
file's published keys.  A block is RMSNorm, rotary positions
(rotate-half), grouped-query causal attention with optional q/k/v biases,
and a GELU (tanh) or SwiGLU MLP with optional biases; then a final norm
and the output head, tied to the embedding or not.

Written out in ``jax.numpy`` and float32 with matmuls at ``HIGHEST``
precision; it imports nothing of the program.  Layers run one at a time in
a scan, each cast to float32 inside it, and attention runs in blocks of
queries, so the reference fits beside nothing but the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from counts import BF16
from reference import round_weight

# published config key -> the program's ModelConfig field it must equal,
# beyond the keys every family shares (spec.COMMON_FIELDS)
WIDTH_FIELDS = {
    "intermediate_size": "d_ff",
    "qkv_bias": "qkv_bias",
    "mlp_bias": "mlp_bias",
    "mlp_variant": "mlp_variant",
}
# the config keys ``logits`` reads (the reference's jit cache key)
FORWARD_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                "norm_eps", "rope_theta", "qkv_bias", "mlp_bias",
                "mlp_variant", "tie_word_embeddings", "vocab_size")

# Drawn as ``weights.make`` draws them alone, a deep stack collapses: every
# position's last hidden state tends to one vector, so all positions of all
# prompts put the same one or two tokens first, and a comparison of served
# tokens makes one decision per request.  Two changes keep positions apart:
# ``w_down``'s columns sum to zero, so the GELU's positive mean adds no
# vector common to every position; and the attention output is scaled
# down, so its averaging over the context does not pull them together.
ATTN_OUT_SCALE = 0.3
STD_SCALE = {"blocks/attn/wo": ATTN_OUT_SCALE}
CENTRED = ("blocks/mlp/w_down",)

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def layout(c: dict) -> dict:
    """{leaf path: (shape, kind)} of the served weights for config file
    ``c``; kind is "matrix", "embed", "bias" or "norm"."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    ff, V = c["intermediate_size"], c["vocab_size"]
    out = {
        "embed": ((V, d), "embed"),
        "final_norm": ((d,), "norm"),
        "blocks/ln1": ((L, d), "norm"),
        "blocks/ln2": ((L, d), "norm"),
        "blocks/attn/wq": ((L, d, qd), "matrix"),
        "blocks/attn/wk": ((L, d, kvd), "matrix"),
        "blocks/attn/wv": ((L, d, kvd), "matrix"),
        "blocks/attn/wo": ((L, qd, d), "matrix"),
        "blocks/mlp/w_up": ((L, d, ff), "matrix"),
        "blocks/mlp/w_down": ((L, ff, d), "matrix"),
    }
    if c["mlp_variant"] in ("swiglu", "geglu"):
        out["blocks/mlp/w_gate"] = ((L, d, ff), "matrix")
    if c["qkv_bias"]:
        out["blocks/attn/bq"] = ((L, qd), "bias")
        out["blocks/attn/bk"] = ((L, kvd), "bias")
        out["blocks/attn/bv"] = ((L, kvd), "bias")
    if c["mlp_bias"]:
        out["blocks/mlp/b_up"] = ((L, ff), "bias")
        out["blocks/mlp/b_down"] = ((L, d), "bias")
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "embed")
    return out


def _rms(x, w1, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w1)


def _rope(x, pos, theta):
    """x [S, H, D], pos [S]: rotate-half rotary embedding."""
    d = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2) / d)),
                        jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(c: dict, quant, x, lw):
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    mm = {k: round_weight(v, quant) if v.ndim == 2 else v
          for k, v in f32.items()}
    S = x.shape[0]
    H, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["norm_eps"]
    pos = jnp.arange(S)
    h = _rms(x, mm["ln1"], eps)
    q = jnp.dot(h, mm["wq"], precision=HI)
    k = jnp.dot(h, mm["wk"], precision=HI)
    v = jnp.dot(h, mm["wv"], precision=HI)
    if c["qkv_bias"]:
        q, k, v = q + mm["bq"], k + mm["bk"], v + mm["bv"]
    q = _rope(q.reshape(S, H, D), pos, c["rope_theta"])
    k = _rope(k.reshape(S, Hkv, D), pos, c["rope_theta"])
    v = v.reshape(S, Hkv, D)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = S // Q_BLOCK if S % Q_BLOCK == 0 else 1
    qb = q.reshape(nb, S // nb, H, D)

    def attend(args):
        qi, i = args
        qpos = i * (S // nb) + jnp.arange(S // nb)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / np.sqrt(D)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(attend, (qb, jnp.arange(nb))).reshape(S, H * D)
    x = x + jnp.dot(o, mm["wo"], precision=HI)
    h = _rms(x, mm["ln2"], eps)
    up = jnp.dot(h, mm["w_up"], precision=HI)
    if c["mlp_bias"]:
        up = up + mm["b_up"]
    if c["mlp_variant"] == "swiglu":
        act = jax.nn.silu(jnp.dot(h, mm["w_gate"], precision=HI)) * up
    elif c["mlp_variant"] == "gelu":
        act = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(f"mlp_variant {c['mlp_variant']!r}")
    out = jnp.dot(act, mm["w_down"], precision=HI)
    if c["mlp_bias"]:
        out = out + mm["b_down"]
    return x + out


def logits(c: dict, quant, w: dict, tokens):
    """Logits [S, vocab] of one padded sequence ``tokens`` [S], with every
    weight matrix rounded to ``quant`` first (None: as made)."""
    x = w["embed"][tokens].astype(jnp.float32)
    blocks = {**w["blocks"]["attn"], **w["blocks"]["mlp"],
              "ln1": w["blocks"]["ln1"], "ln2": w["blocks"]["ln2"]}
    x, _ = jax.lax.scan(lambda x, lw: (_layer(c, quant, x, lw), None),
                        x, blocks)
    x = _rms(x, w["final_norm"].astype(jnp.float32), c["norm_eps"])
    head = (w["embed"].T if c["tie_word_embeddings"] else w["lm_head"])
    head = round_weight(head.astype(jnp.float32), quant)
    return jnp.dot(x, head, precision=HI)[:, :c["vocab_size"]]


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

"""The expert (mixture-of-experts) decoder family, as OLMoE has it.

Its forward pass, weight layout and counts, read from a configuration
file's published keys; the reference follows HF's ``OlmoeForCausalLM``.
A block is RMSNorm; attention whose q and k projections are each
RMS-normalised over the whole projection (weights of q and kv width)
before the split into heads, rotary positions (rotate-half), causal
attention; RMSNorm; a softmax router over all ``num_router_experts``
experts, top ``num_experts_per_tok``, with the top weights renormalised
only where ``norm_topk_prob`` says so; SwiGLU experts.  Then a final norm
and the output head.

The configuration is one chip's share of an expert-parallel deployment:
the file's ``num_experts`` experts, from ``first_expert`` on, are held
here.  Departures from HF, each as the program serves it:

- only the held experts' part of each expert layer is computed; what the
  experts held on other chips add is left out, in the program and here
  alike (the router still scores every expert);
- the norm weights are stored as ``weight - 1``, as the program stores
  them;
- the vocabulary is padded to a multiple of 256 rows in the embedding and
  columns in the head, as the program pads it; the padding is never read.

Written out in ``jax.numpy`` and float32 with matmuls at ``HIGHEST``
precision; it imports nothing of the program.  Layers run one at a time in
a scan and attention in blocks of queries; every held expert runs on every
token, weighted by the routing (zero where a token did not pick it).
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
    "num_experts": "experts_held",
    "num_router_experts": "n_experts",
    "first_expert": "expert_offset",
    "num_experts_per_tok": "top_k",
    "norm_topk_prob": "norm_topk_prob",
    "qk_norm_scope": "qk_norm_scope",
}
# the config keys ``logits`` reads (the reference's jit cache key)
FORWARD_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                "norm_eps", "rope_theta", "num_experts",
                "num_router_experts", "first_expert", "num_experts_per_tok",
                "norm_topk_prob", "qk_norm_scope", "tie_word_embeddings",
                "vocab_size")

# Drawn as ``weights.make`` draws them alone, this stack defeats the
# comparison twice.  Its positions collapse: attention averages the context
# into every position and the held experts add little (top-8 weights of a
# softmax over 64), so every position puts the same few tokens first and a
# served answer repeats one token; the control then errs where the program
# does.  And its widest gaps are routing flips: where bf16 rounding in the
# program moves a token's top-8 boundary, it adds an expert the reference
# leaves out, which reads as wide a gap as the int8 control's.  (At a
# 16-layer, d 1,024 cut on the CPU, a reference forced to route as the
# program routed takes the program's widest gap from 0.045-0.068 to
# 0.014-0.019, while int8 reads 0.106-0.125.)  So the embedding is drawn
# wider and a token's identity survives the stack; the attention output is
# halved; and the experts' output is a quarter, centred as the dense
# family's ``w_down``, so that a flipped expert moves the output little.
EMBED_SCALE = 25.0
ATTN_OUT_SCALE = 0.5
EXPERT_OUT_SCALE = 0.25
STD_SCALE = {"embed": EMBED_SCALE, "blocks/attn/wo": ATTN_OUT_SCALE,
             "blocks/moe/w_down": EXPERT_OUT_SCALE}
CENTRED = ("blocks/moe/w_down",)

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
VOCAB_MULTIPLE = 256


def _padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def layout(c: dict) -> dict:
    """{leaf path: (shape, kind)} of the served weights for config file
    ``c``; kind is "matrix", "embed", "bias" or "norm"."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    ff, V = c["intermediate_size"], _padded_vocab(c)
    H, E = c["num_experts"], c["num_router_experts"]
    whole = c["qk_norm_scope"] == "proj"
    out = {
        "embed": ((V, d), "embed"),
        "final_norm": ((d,), "norm"),
        "blocks/ln1": ((L, d), "norm"),
        "blocks/ln2": ((L, d), "norm"),
        "blocks/attn/wq": ((L, d, qd), "matrix"),
        "blocks/attn/wk": ((L, d, kvd), "matrix"),
        "blocks/attn/wv": ((L, d, kvd), "matrix"),
        "blocks/attn/wo": ((L, qd, d), "matrix"),
        "blocks/attn/q_norm": ((L, qd if whole else c["head_dim"]), "norm"),
        "blocks/attn/k_norm": ((L, kvd if whole else c["head_dim"]), "norm"),
        "blocks/moe/router": ((L, d, E), "matrix"),
        "blocks/moe/w_gate": ((L, H, d, ff), "matrix"),
        "blocks/moe/w_up": ((L, H, d, ff), "matrix"),
        "blocks/moe/w_down": ((L, H, ff, d), "matrix"),
    }
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


def routing(c: dict, h, router):
    """h [S, d] -> the combine weight of each held expert [S, held]: the
    softmax over every expert, its top k, renormalised or not, and zero
    for experts a token did not pick or that are held elsewhere."""
    probs = jax.nn.softmax(jnp.dot(h, router, precision=HI), axis=-1)
    w, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    held = jax.nn.one_hot(idx - c["first_expert"], c["num_experts"],
                          dtype=jnp.float32)          # [S, K, held]
    return jnp.einsum("sk,skh->sh", w, held, precision=HI)


def _layer(c: dict, quant, x, lw):
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    mm = {k: round_weight(v, quant) if v.ndim >= 2 else v
          for k, v in f32.items()}
    S = x.shape[0]
    H, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["norm_eps"]
    pos = jnp.arange(S)
    h = _rms(x, mm["ln1"], eps)
    q = jnp.dot(h, mm["wq"], precision=HI)
    k = jnp.dot(h, mm["wk"], precision=HI)
    v = jnp.dot(h, mm["wv"], precision=HI).reshape(S, Hkv, D)
    if c["qk_norm_scope"] == "proj":
        q, k = _rms(q, mm["q_norm"], eps), _rms(k, mm["k_norm"], eps)
        q, k = q.reshape(S, H, D), k.reshape(S, Hkv, D)
    else:
        q = _rms(q.reshape(S, H, D), mm["q_norm"], eps)
        k = _rms(k.reshape(S, Hkv, D), mm["k_norm"], eps)
    q = _rope(q, pos, c["rope_theta"])
    k = _rope(k, pos, c["rope_theta"])
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
    gates = routing(c, h, mm["router"])                   # [S, held]

    def expert(args):
        wg, wu, wd, g = args
        a = jax.nn.silu(jnp.dot(h, wg, precision=HI))
        a = a * jnp.dot(h, wu, precision=HI)
        return jnp.dot(a, wd, precision=HI) * g[:, None]

    y = jax.lax.map(expert, (mm["w_gate"], mm["w_up"], mm["w_down"],
                             gates.T)).sum(0)
    return x + y


def logits(c: dict, quant, w: dict, tokens):
    """Logits [S, vocab] of one padded sequence ``tokens`` [S], with every
    weight matrix rounded to ``quant`` first (None: as made)."""
    x = w["embed"][tokens].astype(jnp.float32)
    blocks = {**w["blocks"]["attn"], **w["blocks"]["moe"],
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


def experts_per_token(c: dict) -> float:
    """Experts a token passes through here: its top k, times the share of
    the router's experts held here (8 x 16 / 64 = 2 for OLMoE's share of
    EP=4)."""
    return (c["num_experts_per_tok"] * c["num_experts"]
            / c["num_router_experts"])


def params(c: dict) -> int:
    """Every parameter the served model holds (the held experts, and the
    vocabulary unpadded)."""
    L, d, H, Hkv, D, ff, V = _dims(c)
    qk = (H + Hkv) * D if c["qk_norm_scope"] == "proj" else 2 * D
    attn = 2 * d * H * D + 2 * d * Hkv * D + qk
    moe = d * c["num_router_experts"] + c["num_experts"] * 3 * d * ff
    head = 0 if c["tie_word_embeddings"] else d * V
    return V * d + head + L * (attn + moe + 2 * d) + d


def matmul_params(c: dict) -> float:
    """Parameters a token multiplies through: the attention projections,
    the router over every expert, the experts it passes through here on
    average (``experts_per_token``), and the output head."""
    L, d, H, Hkv, D, ff, V = _dims(c)
    layer = (2 * d * H * D + 2 * d * Hkv * D + d * c["num_router_experts"]
             + experts_per_token(c) * 3 * d * ff)
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


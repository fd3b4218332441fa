"""The plain reference that decides ``correct``, and its control.

A decoder-only transformer written out in ``jax.numpy`` and float32 with
matmuls at ``HIGHEST`` precision: RMSNorm, rotary positions (rotate-half),
grouped-query causal attention, a GELU (tanh) or SwiGLU MLP, and the output
head.  It follows the configuration file (widths, biases, tied head, norm
epsilon, rope theta) and the weights the benchmark made; it imports nothing
of the program.  Layers run one at a time in a scan, each cast to float32
inside it, and attention runs in blocks of queries, so the reference fits
beside nothing but the weights.

What it reads, per served request, is teacher-forced: the reference runs
once over prompt + served tokens, and at each position that chose a served
token, the gap by which that token's logit lies below the reference's best.
The control is the same reference with every weight matrix rounded to a
lower precision first (``quant``); at the same positions it reads the gap of
the token the lower precision puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
QUANTS = ("int8", "fp8")


def _round_weight(w: jax.Array, quant: str | None) -> jax.Array:
    """``w`` [in, out] rounded as a lower-precision path would store it:
    symmetric, one scale per output column."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-12) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-12) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"quant must be one of {QUANTS} or None")


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
    mm = {k: _round_weight(v, quant) if v.ndim == 2 else v
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


def _logits(c: dict, quant, w: dict, tokens):
    """Logits [S, vocab] of one padded sequence ``tokens`` [S]."""
    x = w["embed"][tokens].astype(jnp.float32)
    blocks = {**w["blocks"]["attn"], **w["blocks"]["mlp"],
              "ln1": w["blocks"]["ln1"], "ln2": w["blocks"]["ln2"]}
    x, _ = jax.lax.scan(lambda x, lw: (_layer(c, quant, x, lw), None),
                        x, blocks)
    x = _rms(x, w["final_norm"].astype(jnp.float32), c["norm_eps"])
    head = (w["embed"].T if c["tie_word_embeddings"] else w["lm_head"])
    head = _round_weight(head.astype(jnp.float32), quant)
    return jnp.dot(x, head, precision=HI)[:, :c["vocab_size"]]


def _freeze(c: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "norm_eps", "rope_theta", "qkv_bias", "mlp_bias", "mlp_variant",
            "tie_word_embeddings", "vocab_size")
    return tuple((k, c[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _gaps_fn(frozen: tuple, quant):
    c = dict(frozen)

    def gaps(w, tokens, served, pos):
        """At each position ``pos[i]``: the gap of ``served[i]`` below the
        reference's best, and (with ``quant``) the gap of the control's
        first choice."""
        ref = _logits(c, None, w, tokens)
        r = ref[pos]
        best = jnp.max(r, axis=-1)
        g_served = best - jnp.take_along_axis(r, served[:, None], 1)[:, 0]
        if quant is None:
            return g_served, jnp.zeros_like(g_served)
        ctl = _logits(c, quant, w, tokens)[pos]
        pick = jnp.argmax(ctl, axis=-1)
        g_ctl = best - jnp.take_along_axis(r, pick[:, None], 1)[:, 0]
        return g_served, g_ctl

    return jax.jit(gaps)


def request_gaps(c: dict, w: dict, prompt: np.ndarray, served: list,
                 seq_len: int, n_read: int, quant: str | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Gaps at every served position of one request: (served token's gap,
    control's gap; zeros without ``quant``).

    Every request is padded to the same ``seq_len`` tokens and ``n_read``
    read positions (the mix's longest context and output), so a run
    compiles the reference once; causal attention keeps the padding out of
    every position read."""
    P, N = len(prompt), len(served)
    if P + N - 1 > seq_len or N > n_read:
        raise ValueError(f"request of {P} + {N} tokens exceeds the "
                         f"reference's {seq_len} / {n_read}")
    buf = np.zeros(seq_len, np.int32)
    buf[:P] = prompt
    buf[P:P + N - 1] = served[:-1]
    srv = np.zeros(n_read, np.int32)
    srv[:N] = served
    pos = np.zeros(n_read, np.int32)
    pos[:N] = P - 1 + np.arange(N)
    fn = _gaps_fn(_freeze(c), quant)
    g, gc = fn(w, jnp.asarray(buf), jnp.asarray(srv), jnp.asarray(pos))
    return np.asarray(g)[:N], np.asarray(gc)[:N]

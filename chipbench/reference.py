"""The plain reference that decides ``correct``, and its control.

The forward pass is the configuration's model family's: ``logits`` of
``families/<family>.py``, found by the file's ``"family"`` key, written
out in ``jax.numpy`` and float32 at ``HIGHEST`` precision, following the
configuration file and the weights the benchmark made, and importing
nothing of the program.  This module holds what every family shares: the
padding, the teacher forcing, the control's rounding and the gaps.

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

import spec

QUANTS = ("int8", "fp8")


def round_weight(w: jax.Array, quant: str | None) -> jax.Array:
    """``w`` [..., in, out] rounded as a lower-precision path would store
    it: symmetric, one scale per output column of each matrix, so a stack
    of matrices (layers, experts) is rounded one matrix at a time."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-12) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-12) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"quant must be one of {QUANTS} or None")


def _freeze(c: dict) -> tuple:
    keys = spec.family_of(c).FORWARD_KEYS
    return (("family", c["family"]), *((k, c[k]) for k in keys))


@functools.lru_cache(maxsize=None)
def _gaps_fn(frozen: tuple, quant):
    c = dict(frozen)
    logits = spec.load_family(c["family"]).logits

    def gaps(w, tokens, served, pos):
        """At each position ``pos[i]``: the gap of ``served[i]`` below the
        reference's best, and (with ``quant``) the gap of the control's
        first choice."""
        ref = logits(c, None, w, tokens)
        r = ref[pos]
        best = jnp.max(r, axis=-1)
        g_served = best - jnp.take_along_axis(r, served[:, None], 1)[:, 0]
        if quant is None:
            return g_served, jnp.zeros_like(g_served)
        ctl = logits(c, quant, w, tokens)[pos]
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

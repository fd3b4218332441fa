"""Top-k mixture of experts over the experts this program holds, drop-free.

The router scores every one of ``cfg.n_experts`` experts in float32:
softmax, then top-k, with the top-k weights renormalised to sum to one only
where the model does (``cfg.norm_topk_prob``: granite yes, OLMoE no).  The
program holds the contiguous range ``[expert_offset, expert_offset +
n_held)`` of them (every expert by default; one chip's share of an
expert-parallel deployment otherwise) and computes only those experts' part
of the layer's output, for exactly the (token, slot) pairs routed to them:
the pairs are sorted by held expert and run through a grouped matmul
(``kernels.expert_gmm`` on an unsharded TPU program, ``lax.ragged_dot``
elsewhere), then weighted and summed back per token.  Nothing is dropped,
whatever the load, and expert FLOPs follow the routed pairs.  What the
experts held elsewhere would add is left out: no exchange between chips is
modelled here.

``moe_block`` also returns the layer's load: routed pairs per held expert,
counting only the tokens marked valid (bucket padding is not traffic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.expert_gmm import expert_gmm
from repro.models.config import ModelConfig
from repro.pshard import current_rules

HI = jax.lax.Precision.HIGHEST


def init_moe(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    d, ff, E, H = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(ff)
    return {
        "router": (jax.random.normal(ks[0], (d, E)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (H, d, ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (H, d, ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (H, ff, d)) * s_out).astype(dtype),
    }


def route(xt: jax.Array, p: dict, cfg: ModelConfig):
    """xt [N, d] -> (combine weights [N, K] f32, experts [N, K] int32),
    over all ``cfg.n_experts``; ``(probs [N, E])`` beside them."""
    logits = jnp.dot(xt.astype(jnp.float32), p["router"], precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    return w, idx, probs


def _ragged(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


@jax.custom_vjp
def _gmm_kernel(lhs, rhs, sizes):
    return expert_gmm(lhs, rhs, sizes)


def _gmm_fwd(lhs, rhs, sizes):
    return expert_gmm(lhs, rhs, sizes), (lhs, rhs, sizes)


def _gmm_bwd(res, g):
    # the kernel leaves rows past the groups undefined and ragged_dot zeros
    # them; callers mask those rows, so their cotangent is zero either way
    lhs, rhs, sizes = res
    _, vjp = jax.vjp(lambda a, b: _ragged(a, b, sizes), lhs, rhs)
    return (*vjp(g), None)


_gmm_kernel.defvjp(_gmm_fwd, _gmm_bwd)


def _gmm(lhs, rhs, sizes):
    """Grouped matmul: the Pallas kernel in an unsharded program lowered
    for TPU (GSPMD does not partition a pallas call), ``ragged_dot``
    elsewhere."""
    if current_rules() is not None:
        return _ragged(lhs, rhs, sizes)
    return jax.lax.platform_dependent(lhs, rhs, sizes, tpu=_gmm_kernel,
                                      default=_ragged)


def _per_expert(local, mask, H: int):
    """Pairs with ``mask`` per held expert [H] (``local`` in [0, H))."""
    group = jnp.where(mask, local, H).reshape(-1)
    return jnp.zeros((H + 1,), jnp.int32).at[group].add(1)[:H]


def moe_block(x: jax.Array, p: dict, cfg: ModelConfig,
              valid: jax.Array | None = None):
    """x [B, S, d] -> (the held experts' part [B, S, d], load [n_held]
    int32: pairs of ``valid`` [B, S] tokens (default all) routed to each
    held expert)."""
    B, S, d = x.shape
    N, K, H = B * S, cfg.top_k, cfg.n_held
    xt = x.reshape(N, d)
    w, idx, _ = route(xt, p, cfg)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < H)
    # pairs sorted by held expert; those held elsewhere (H) go last
    order = jnp.argsort(jnp.where(held, local, H).reshape(N * K), stable=True)
    sizes = _per_expert(local, held, H)
    rows = xt[order // K]                                 # [N*K, d] by expert
    gate = _gmm(rows, p["w_gate"], sizes)
    up = _gmm(rows, p["w_up"], sizes)
    h = (jax.nn.silu(gate.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(x.dtype)
    out = _gmm(h, p["w_down"], sizes)                     # [N*K, d]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * K))
    out = out[inv].reshape(N, K, d).astype(jnp.float32)
    # rows of pairs held elsewhere are undefined: select, never multiply
    y = jnp.where(held[..., None], out * w[..., None], 0.0).sum(axis=1)
    load = (sizes if valid is None
            else _per_expert(local, held & valid.reshape(N, 1), H))
    return y.astype(x.dtype).reshape(B, S, d), load


def load_balance_loss(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * P_e / K over all the
    router's experts.

    f_e = fraction of tokens whose top-k set contains e; P_e = mean router
    probability.  Keeps routing balanced, so no expert's share of the
    deployment's chips carries far more than its part of the load.
    """
    E = cfg.n_experts
    _, idx, probs = route(x.reshape(-1, x.shape[-1]), p, cfg)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)  # [N, E]
    return E * jnp.sum(chosen.mean(0) * probs.mean(0)) / cfg.top_k


def moe_ref(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Oracle: every held expert on every token, weighted by the routing
    (tiny shapes only)."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    w, idx, _ = route(xt, p, cfg)
    local = idx - cfg.expert_offset
    onehot = jax.nn.one_hot(local, cfg.n_held, dtype=jnp.float32)  # [N,K,H]
    gates = jnp.einsum("nk,nkh->nh", w, onehot)
    f32 = {k: p[k].astype(jnp.float32) for k in ("w_gate", "w_up", "w_down")}
    xf = xt.astype(jnp.float32)
    gate = jnp.einsum("nd,hdf->hnf", xf, f32["w_gate"], precision=HI)
    up = jnp.einsum("nd,hdf->hnf", xf, f32["w_up"], precision=HI)
    y_all = jnp.einsum("hnf,hfd->hnd", jax.nn.silu(gate) * up, f32["w_down"],
                       precision=HI)
    y = jnp.einsum("hnd,nh->nd", y_all, gates, precision=HI)
    return y.astype(x.dtype).reshape(B, S, d)

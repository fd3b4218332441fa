"""The expert layer holds a share of the experts and drops nothing.

A program told it holds ``[expert_offset, expert_offset + experts_held)``
of the router's experts computes exactly those experts' part of the
layer; the parts of the shares of a deployment add up to the uncut layer;
the grouped dispatch is exact at any load; the combine weights and the
QK-norm take the form the model's config says.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.expert_gmm import expert_gmm
from repro.models import attention as attn_lib
from repro.models import init_params
from repro.models import model as M
from repro.models import moe as moe_lib


def olmoe(**kw):
    cfg = dataclasses.replace(get_smoke_config("olmoe-1b-7b"), n_layers=1,
                              n_experts=8, top_k=4)
    return dataclasses.replace(cfg, **kw)


def share(params, lo, n):
    """The weights a program holding experts [lo, lo + n) is given."""
    def cut(bp):
        moe = {**bp["moe"], **{k: bp["moe"][k][:, lo:lo + n]
                               for k in ("w_gate", "w_up", "w_down")}}
        return {**bp, "moe": moe}
    return {**params, "blocks": cut(params["blocks"])}


def test_four_shares_add_up_to_the_uncut_layer():
    """Each of 4 shares computes attention alike and its experts' part;
    the attention counted once plus the 4 parts is the uncut block, and
    the uncut part is ``moe_ref``'s."""
    cfg = olmoe()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    bp = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    whole, *_ = M._block(x, bp, cfg, 0, pos, "full")
    attn, *_ = M._mixer(M.rms_norm(x, bp["ln1"], cfg.norm_eps), bp, cfg, 0,
                        pos, "full")
    after_attn = x + attn
    parts = []
    for lo in range(0, 8, 2):
        scfg = dataclasses.replace(cfg, experts_held=2, expert_offset=lo)
        sbp = jax.tree.map(lambda a: a[0], share(params, lo, 2)["blocks"])
        out, *_ = M._block(x, sbp, scfg, 0, pos, "full")
        parts.append(out - after_attn)
    np.testing.assert_allclose(np.asarray(after_attn + sum(parts)),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    h2 = M.rms_norm(after_attn, bp["ln2"], cfg.norm_eps)
    np.testing.assert_allclose(
        np.asarray(moe_lib.moe_ref(h2, bp["moe"], cfg)),
        np.asarray(whole - after_attn), rtol=1e-4, atol=1e-5)


def test_grouped_dispatch_is_exact_under_skew_above_2048_tokens():
    """2,200 tokens with a router that sends nearly every token to expert
    1, a held expert: far past any capacity a dropping path would give,
    and still exact against ``moe_ref``."""
    cfg = dataclasses.replace(olmoe(), d_model=32, d_ff=16, top_k=2,
                              experts_held=4, expert_offset=0)
    p = moe_lib.init_moe(jax.random.PRNGKey(2), cfg, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(3), (cfg.d_model,))
    u = u / jnp.linalg.norm(u)
    p["router"] = p["router"].at[:, 1].set(8.0 * u)
    x = (jax.random.normal(jax.random.PRNGKey(4), (2, 1100, cfg.d_model))
         + 3.0 * u)
    y, load = moe_lib.moe_block(x, p, cfg)
    assert int(load[1]) > 0.95 * 2200
    assert int(load[1]) > 2 * 2200 * cfg.top_k / cfg.n_experts
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(moe_lib.moe_ref(x, p, cfg)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("renorm", [False, True])
def test_combine_weights_by_hand(renorm):
    """softmax over all 8 experts, top 4; the weights summed as they are
    (OLMoE) or over their own sum (granite); only held experts count."""
    cfg = dataclasses.replace(olmoe(), d_model=16, d_ff=8,
                              norm_topk_prob=renorm, experts_held=4,
                              expert_offset=2)
    p = moe_lib.init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 5, cfg.d_model))
    y, _ = moe_lib.moe_block(x, p, cfg)
    xs = np.asarray(x[0], np.float64)
    P = {k: np.asarray(v, np.float64) for k, v in p.items()}
    want = np.zeros_like(xs)
    for n in range(5):
        z = xs[n] @ P["router"]
        probs = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        top = np.argsort(-probs)[:4]
        w = probs[top] / (probs[top].sum() if renorm else 1.0)
        for e, we in zip(top, w):
            if 2 <= e < 6:
                g = xs[n] @ P["w_gate"][e - 2]
                h = g / (1 + np.exp(-g)) * (xs[n] @ P["w_up"][e - 2])
                want[n] += we * (h @ P["w_down"][e - 2])
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-4, atol=1e-5)


def test_whole_projection_qk_norm_is_hf_olmoe():
    """HF's OlmoeAttention: q_norm = RMSNorm(num_heads * head_dim) over
    q_proj(x), k_norm = RMSNorm(num_kv_heads * head_dim) over k_proj(x),
    weight * x * rsqrt(mean(x^2) + eps), then the split into heads."""
    cfg = dataclasses.replace(get_smoke_config("olmoe-1b-7b"),
                              pos_embedding="none")
    assert cfg.qk_norm and cfg.qk_norm_scope == "proj"
    p = attn_lib.init_attention(jax.random.PRNGKey(7), cfg, jnp.float32)
    assert p["q_norm"].shape == (cfg.q_dim,)
    assert p["k_norm"].shape == (cfg.kv_dim,)
    p["q_norm"] = 0.3 * jax.random.normal(jax.random.PRNGKey(8), (cfg.q_dim,))
    p["k_norm"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                          (cfg.kv_dim,))
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 3, cfg.d_model))
    q, k, _ = attn_lib._project_qkv(x, p, cfg, None)

    def hf_rms(h, w_minus_1):
        var = np.mean(h * h, axis=-1, keepdims=True)
        return (1.0 + w_minus_1) * (h / np.sqrt(var + cfg.norm_eps))

    xs = np.asarray(x, np.float64)
    P = {n: np.asarray(v, np.float64) for n, v in p.items()}
    want_q = hf_rms(xs @ P["wq"], P["q_norm"]).reshape(q.shape)
    want_k = hf_rms(xs @ P["wk"], P["k_norm"]).reshape(k.shape)
    np.testing.assert_allclose(np.asarray(q), want_q, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), want_k, rtol=1e-4, atol=1e-5)
    # the per-head form is another function of the same projection
    head = dataclasses.replace(cfg, qk_norm_scope="head")
    ph = {**p, "q_norm": p["q_norm"][:cfg.head_dim],
          "k_norm": p["k_norm"][:cfg.head_dim]}
    qh, _, _ = attn_lib._project_qkv(x, ph, head, None)
    assert not np.allclose(np.asarray(qh), want_q, atol=1e-3)


def test_granite_and_chameleon_keep_their_forms():
    """Every expert held and renormalised weights (granite); per-head
    QK-norm weights of head_dim (Chameleon)."""
    for cfg in (get_config("granite-moe-3b-a800m"),
                get_smoke_config("granite-moe-3b-a800m")):
        assert cfg.norm_topk_prob
        assert (cfg.n_held, cfg.expert_offset) == (cfg.n_experts, 0)
    g = get_smoke_config("granite-moe-3b-a800m")
    shapes = jax.eval_shape(lambda k: init_params(g, k),
                            jax.random.PRNGKey(0))
    assert shapes["blocks"]["moe"]["w_gate"].shape == (
        g.n_layers, g.n_experts, g.d_model, g.d_ff)
    for cfg in (get_config("chameleon-34b"),
                get_smoke_config("chameleon-34b")):
        assert cfg.qk_norm and cfg.qk_norm_scope == "head"
    c = get_smoke_config("chameleon-34b")
    shapes = jax.eval_shape(lambda k: init_params(c, k),
                            jax.random.PRNGKey(0))
    assert shapes["blocks"]["attn"]["q_norm"].shape == (c.n_layers,
                                                        c.head_dim)


def test_the_planner_prices_the_held_share():
    """OLMoE's chip share of EP=4: 16 experts held, a router over 64, and
    2 experts a token on average here."""
    whole = get_config("olmoe-1b-7b")
    cut = dataclasses.replace(whole, experts_held=16)
    d, ff, L = whole.d_model, whole.d_ff, whole.n_layers
    expert = 3 * d * ff
    assert whole.param_count() - cut.param_count() == L * 48 * expert
    # the profile leaves out the QK-norm and final-norm weights
    assert cut.param_count() == 2_087_323_648 - L * 2 * d - d
    prof = cut.profile()
    assert prof.top_k == 2.0 and prof.router_experts == 64
    assert (whole.profile().active_param_count
            - prof.active_param_count) == L * 6 * expert


def test_expert_gmm_kernel_matches_ragged_dot():
    """The Pallas kernel (interpreted) over groups that start mid-tile,
    an empty group, and rows past the last group."""
    lhs = jax.random.normal(jax.random.PRNGKey(11), (300, 64))
    rhs = jax.random.normal(jax.random.PRNGKey(12), (5, 64, 256))
    sizes = jnp.array([10, 0, 130, 7, 100], jnp.int32)
    got = expert_gmm(lhs, rhs, sizes, tm=32, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=1e-5, atol=1e-4)


"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting shapes and finiteness; decode == forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.data.pipeline import DataConfig, packed_batches
from repro.models import (decode_step, forward, init_cache, init_params,
                          prefill)
from repro.train.trainer import TrainConfig, init_train_state, make_train_step


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward_shapes_no_nan(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 64
    if cfg.modality == "audio_stub":
        embeds = jax.random.normal(jax.random.PRNGKey(1),
                                   (B, S, cfg.d_model)) * 0.02
        logits = forward(params, cfg, embeds=embeds)
    else:
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab_size)
        logits = forward(params, cfg, tokens)
    assert logits.shape == (B, S, cfg.padded_vocab())
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    tcfg = TrainConfig(remat=False)
    step = jax.jit(make_train_step(cfg, tcfg))
    state = init_train_state(cfg, jax.random.PRNGKey(0), tcfg)
    B, S = 2, 32
    rng = np.random.RandomState(0)
    batch = {"labels": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)}
    if cfg.modality == "audio_stub":
        batch["embeds"] = rng.randn(B, S, cfg.d_model).astype(np.float32) * .02
    else:
        batch["tokens"] = rng.randint(0, cfg.vocab_size,
                                      (B, S)).astype(np.int32)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["nll"]))
    assert float(metrics["grad_norm"]) > 0


@pytest.mark.parametrize("arch", ["yi-9b", "gemma2-2b", "olmoe-1b-7b",
                                  "mamba2-370m", "hymba-1.5b"])
def test_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    lp, cache = prefill(params, cfg, tokens)[:2]
    nxt = jnp.argmax(lp[:, :cfg.vocab_size], -1).astype(jnp.int32)
    big = init_cache(cfg, B, S + 4, jnp.float32)
    if cache.k is not None:
        big.k = big.k.at[:, :, :S].set(cache.k)
        big.v = big.v.at[:, :, :S].set(cache.v)
    if cache.ssm is not None:
        big.ssm, big.conv = cache.ssm, cache.conv
    big.pos = cache.pos
    ld, _ = decode_step(params, cfg, nxt, big)
    ext = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    lf = forward(params, cfg, ext)[:, -1]
    rel = float(jnp.max(jnp.abs(ld - lf))) / (float(jnp.max(jnp.abs(lf))) + 1e-9)
    assert rel < 2e-2


def test_training_reduces_loss():
    from repro.train.optimizer import AdamWConfig
    cfg = get_smoke_config("yi-9b")
    tcfg = TrainConfig(remat=False,
                       opt=AdamWConfig(lr=1e-3, warmup_steps=5,
                                       total_steps=100))
    step = jax.jit(make_train_step(cfg, tcfg))
    state = init_train_state(cfg, jax.random.PRNGKey(0), tcfg)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, batch_size=8)
    it = packed_batches(dc)
    losses = []
    for i in range(25):
        state, m = step(state, next(it))
        losses.append(float(m["nll"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05


def test_remat_equivalence():
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    a = forward(params, cfg, tokens, remat=False)
    b = forward(params, cfg, tokens, remat=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_gemma2_local_global_differ():
    """The local mask must actually change layer behaviour."""
    cfg = dataclasses.replace(get_smoke_config("gemma2-2b"), n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    S = 200   # > reduced local window (64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0,
                                cfg.vocab_size)
    a = forward(params, cfg, tokens)
    no_local = dataclasses.replace(cfg, local_window=0)
    b = forward(params, no_local, tokens)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4


def test_grad_compression_roundtrip_close():
    from repro.train.optimizer import compress_roundtrip
    tree = {"w": jnp.asarray(np.random.RandomState(0).randn(64, 33),
                             jnp.float32)}
    out = compress_roundtrip(tree)
    err = float(jnp.max(jnp.abs(out["w"] - tree["w"])))
    assert err < 0.05   # int8 blockwise quantization error bound

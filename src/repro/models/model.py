"""The unified decoder-only model covering all assigned architecture families.

One scan-over-layers body handles: dense GQA attention (with gemma2's
local/global alternation + softcaps + sandwich norms), MoE MLPs, SSD mixers
(mamba2), and hybrid parallel attention+SSM heads (hymba).  VLM/audio archs
use the same backbone; their modality frontends are stubs that feed
precomputed token ids / frame embeddings (see ``repro.launch.dryrun
.input_specs``).

Entry points:
  init_params(cfg, key)                  -> parameter pytree (layers stacked)
  forward(params, cfg, tokens|embeds)    -> logits           (train)
  prefill(params, cfg, tokens)           -> (logits, DecodeCache)
  decode_step(params, cfg, token, cache) -> (logits, DecodeCache)
  decode_loop_paged(params, cfg, tokens, state, key, step0, horizon)
      -> ([B, horizon] tokens, PagedDecodeState)   (fused multi-step decode)

The serving programs (``prefill``, ``prefill_chunk``,
``decode_loop_paged``) of an expert model (``cfg.is_moe``) also return its
routing counts for the execution, last (``route_counts``): routed (token,
expert) pairs per held expert summed over layers and steps, and the
largest count of one expert in one layer and step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import init_mlp, mlp, rms_norm, sincos_embedding, softcap
from repro.pshard import logical


# --------------------------------------------------------------------------
# Decode cache.
# --------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "ssm", "conv", "pos"],
    meta_fields=[],
)
@dataclasses.dataclass
class DecodeCache:
    k: Any      # [L, B, Smax, Hkv, D] or None
    v: Any
    ssm: Any    # [L, B, H, P, N] fp32 or None
    conv: Any   # [L, B, W-1, conv_ch] or None
    pos: Any    # [B] int32: number of tokens already in the cache


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "block_table", "lens", "ssm", "conv"],
    meta_fields=[],
)
@dataclasses.dataclass
class PagedDecodeState:
    """Device-resident paged decode state (one jitted step's working set).

    The K/V pools stay in kernel-native layout so the Pallas paged kernel
    (and the jnp fallback) read pages without per-step transposes.
    """
    k: Any            # [L, P, Hkv, page, D] paged pool or None
    v: Any
    block_table: Any  # [B, n_pages] int32 physical page ids
    lens: Any         # [B] int32 tokens already cached per sequence
    ssm: Any          # [L, B, H, P, N] fp32 or None
    conv: Any         # [L, B, W-1, conv_ch] or None


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> DecodeCache:
    L = cfg.n_layers
    k = v = ssm = conv = None
    if cfg.has_attn:
        shape = (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
    if cfg.has_ssm:
        ssm = jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                         cfg.ssm_state), jnp.float32)
        conv = jnp.zeros((L, batch, cfg.ssm_conv_width - 1,
                          ssm_lib.conv_channels(cfg)), dtype)
    return DecodeCache(k, v, ssm, conv, jnp.zeros((batch,), jnp.int32))


# --------------------------------------------------------------------------
# Parameter init.
# --------------------------------------------------------------------------


def _init_block(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: dict = {"ln1": jnp.zeros((d,), dtype)}
    if cfg.has_attn:
        p["attn"] = attn_lib.init_attention(ks[0], cfg, dtype)
    if cfg.has_ssm:
        p["ssm"] = ssm_lib.init_ssm(ks[1], cfg, dtype)
    if cfg.hybrid:
        p["attn_out_norm"] = jnp.zeros((d,), dtype)
        p["ssm_out_norm"] = jnp.zeros((d,), dtype)
    if cfg.sandwich_norm:
        p["post_ln1"] = jnp.zeros((d,), dtype)
    if cfg.is_moe:
        p["ln2"] = jnp.zeros((d,), dtype)
        p["moe"] = moe_lib.init_moe(ks[2], cfg, dtype)
    elif cfg.d_ff > 0:
        p["ln2"] = jnp.zeros((d,), dtype)
        p["mlp"] = init_mlp(ks[3], d, cfg.d_ff, cfg.mlp_variant,
                            cfg.mlp_bias, dtype)
    if cfg.sandwich_norm and (cfg.is_moe or cfg.d_ff > 0):
        p["post_ln2"] = jnp.zeros((d,), dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    V = cfg.padded_vocab()
    embed = (jax.random.normal(k_embed, (V, cfg.d_model)) * 0.02).astype(dtype)
    layer_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: _init_block(k, cfg, dtype))(layer_keys)
    params = {
        "embed": embed,
        "blocks": blocks,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, V)) * 0.02).astype(dtype)
    return params


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# Block application (shared by all modes).
# --------------------------------------------------------------------------


def _mixer(x, bp, cfg: ModelConfig, layer_idx, positions, mode,
           kv=None, ssm_state=None, conv=None, pos=None, paged=None):
    """Token mixing: attention and/or SSM.  Returns (out, new_kv, new_ssm_pair).

    ``paged`` (decode only): dict with ``table`` [B, n_pages] plus static
    ``impl``/``interpret`` — routes attention through the paged KV pool
    (kv holds the layer's page pools) instead of a dense cache.
    """
    is_local = (layer_idx % 2 == 0) if cfg.local_window > 0 else False
    attn_out = None
    new_k = new_v = None
    if cfg.has_attn:
        if mode == "chunk":
            attn_out, new_k, new_v = attn_lib.prefill_chunk_attention(
                x, bp["attn"], cfg, kv[0], kv[1], paged["table"], pos,
                paged["n_valid"], paged["trash"], is_local)
        elif mode == "decode" and paged is not None \
                and paged["impl"] == "buffered":
            # horizon loop: pools read-only, new K/V rides the side buffer
            # (new_k/new_v are the updated buffer rows, not pools)
            attn_out, new_k, new_v = attn_lib.paged_decode_attention_buffered(
                x, bp["attn"], cfg, kv[0], kv[1], paged["table"],
                paged["pool_lens"], paged["kh"], paged["vh"], paged["step"],
                is_local)
        elif mode == "decode" and paged is not None:
            attn_out, new_k, new_v = attn_lib.paged_decode_attention(
                x, bp["attn"], cfg, kv[0], kv[1], paged["table"], pos,
                is_local, impl=paged["impl"], interpret=paged["interpret"],
                layer=paged.get("layer"))
        elif mode == "decode":
            attn_out, new_k, new_v = attn_lib.decode_attention(
                x, bp["attn"], cfg, kv[0], kv[1], pos, is_local)
        else:
            attn_out = attn_lib.full_attention(
                x, bp["attn"], cfg, positions, is_local)
    ssm_out = None
    new_state = new_conv = None
    if cfg.has_ssm:
        if mode == "decode":
            ssm_out, new_state, new_conv = ssm_lib.ssm_decode_step(
                x, bp["ssm"], cfg, ssm_state, conv)
        else:
            ssm_out, new_state, new_conv = ssm_lib.ssm_forward(
                x, bp["ssm"], cfg, ssm_state, conv)
    if cfg.hybrid:
        out = 0.5 * (rms_norm(attn_out, bp["attn_out_norm"], cfg.norm_eps)
                     + rms_norm(ssm_out, bp["ssm_out_norm"], cfg.norm_eps))
    elif cfg.has_attn:
        out = attn_out
    else:
        out = ssm_out
    return out, (new_k, new_v), (new_state, new_conv)


def route_counts(load):
    """load [..., L, n_held] (one layer's routed pairs per held expert, per
    step) -> (pairs per held expert over layers and steps [n_held], the
    largest single-layer count)."""
    per_expert = load.reshape(-1, load.shape[-1]).sum(0)
    return per_expert, jnp.max(load)


def _routed(out: tuple, load) -> tuple:
    """A serving program's ``out``, with ``route_counts(load)`` last where
    the model has expert layers (``load`` is None where it has none)."""
    return out if load is None else (*out, route_counts(load))


def _block(x, bp, cfg: ModelConfig, layer_idx, positions, mode,
           kv=None, ssm_state=None, conv=None, pos=None, with_aux=False,
           paged=None, valid=None):
    """One layer; returns (x, new_kv, new_ssm, aux, load), where load is
    the expert layer's routed pairs per held expert (None without one)."""
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    mix, new_kv, new_ssm = _mixer(h, bp, cfg, layer_idx, positions, mode,
                                  kv, ssm_state, conv, pos, paged)
    if cfg.sandwich_norm:
        mix = rms_norm(mix, bp["post_ln1"], cfg.norm_eps)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    load = None
    if cfg.is_moe or cfg.d_ff > 0:
        h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            m, load = moe_lib.moe_block(h2, bp["moe"], cfg, valid)
            if with_aux:
                aux = moe_lib.load_balance_loss(h2, bp["moe"], cfg)
        else:
            m = mlp(h2, bp["mlp"], cfg.mlp_variant)
        if cfg.sandwich_norm:
            m = rms_norm(m, bp["post_ln2"], cfg.norm_eps)
        x = x + m
    # Residual-stream boundary: "act_seq" is sequence-parallel (sharded
    # over `model`) in training plans to cut layer-boundary activation memory.
    x = logical(x, "batch", "act_seq", "d_model")
    return x, new_kv, new_ssm, aux, load


# --------------------------------------------------------------------------
# Embedding & head.
# --------------------------------------------------------------------------


def embed_inputs(params, cfg: ModelConfig, tokens=None, embeds=None,
                 positions=None):
    if embeds is None:
        x = params["embed"][tokens]
    else:
        x = embeds.astype(params["embed"].dtype)
    if cfg.scale_embedding:
        x = x * np.sqrt(cfg.d_model)
    if cfg.pos_embedding == "sincos":
        x = x + sincos_embedding(positions, cfg.d_model).astype(x.dtype)
    return x


def lm_logits(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # accumulated and kept in float32: logits rounded to bf16 move the
    # choice between near-tied tokens (a step of 1/64 at logits of 2-4)
    logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
    logits = softcap(logits, cfg.final_logit_softcap)
    return logical(logits, "batch", "seq", "vocab")


# --------------------------------------------------------------------------
# Full-sequence forward (training).
# --------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            pos_offset: int = 0, remat: bool = False, with_aux: bool = False):
    """Returns logits [B, S, padded_vocab] (fp32); (logits, aux) if with_aux."""
    B, S = (tokens.shape if tokens is not None else embeds.shape[:2])
    positions = pos_offset + jnp.arange(S)[None, :].astype(jnp.int32)
    positions = jnp.broadcast_to(positions, (B, S))
    x = embed_inputs(params, cfg, tokens, embeds, positions)
    x = logical(x, "batch", "act_seq", "d_model")

    def body(carry, scanned):
        x, aux_sum = carry
        bp, layer_idx = scanned
        x, _, _, aux, _ = _block(x, bp, cfg, layer_idx, positions, "full",
                                 with_aux=with_aux)
        return (x, aux_sum + aux), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux_sum), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["blocks"], jnp.arange(cfg.n_layers)),
        unroll=cfg.scan_unroll)
    logits = lm_logits(params, cfg, x)
    if with_aux:
        return logits, aux_sum / cfg.n_layers
    return logits


# --------------------------------------------------------------------------
# Prefill: full-sequence forward that materializes the decode cache.
# --------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None):
    """Returns (last-token logits [B, Vpad], DecodeCache at length S), and
    an expert model's ``route_counts`` last."""
    B, S = (tokens.shape if tokens is not None else embeds.shape[:2])
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)).astype(jnp.int32)
    x = embed_inputs(params, cfg, tokens, embeds, positions)

    def body(x, scanned):
        bp, layer_idx = scanned
        # full-mode block, capturing per-layer K/V and SSM terminal state
        h = rms_norm(x, bp["ln1"], cfg.norm_eps)
        caches = {}
        is_local = (layer_idx % 2 == 0) if cfg.local_window > 0 else False
        attn_out = None
        if cfg.has_attn:
            q, k, v = attn_lib._project_qkv(h, bp["attn"], cfg, positions)
            caches["k"], caches["v"] = k, v
            attn_out = attn_lib.full_attention(h, bp["attn"], cfg, positions,
                                               is_local)
        ssm_out = None
        if cfg.has_ssm:
            ssm_out, state, conv_w = ssm_lib.ssm_forward(h, bp["ssm"], cfg)
            caches["ssm"], caches["conv"] = state, conv_w
        if cfg.hybrid:
            mix = 0.5 * (rms_norm(attn_out, bp["attn_out_norm"], cfg.norm_eps)
                         + rms_norm(ssm_out, bp["ssm_out_norm"], cfg.norm_eps))
        elif cfg.has_attn:
            mix = attn_out
        else:
            mix = ssm_out
        if cfg.sandwich_norm:
            mix = rms_norm(mix, bp["post_ln1"], cfg.norm_eps)
        x = x + mix
        if cfg.is_moe or cfg.d_ff > 0:
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            if cfg.is_moe:
                m, caches["load"] = moe_lib.moe_block(h2, bp["moe"], cfg)
            else:
                m = mlp(h2, bp["mlp"], cfg.mlp_variant)
            if cfg.sandwich_norm:
                m = rms_norm(m, bp["post_ln2"], cfg.norm_eps)
            x = x + m
        x = logical(x, "batch", "seq", "d_model")
        return x, caches

    x, caches = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.n_layers)),
        unroll=cfg.scan_unroll)
    logits = lm_logits(params, cfg, x[:, -1:, :])[:, 0]
    pos = jnp.full((B,), S, jnp.int32)
    cache = DecodeCache(
        k=caches.get("k"), v=caches.get("v"),
        ssm=caches.get("ssm"), conv=caches.get("conv"), pos=pos)
    return _routed((logits, cache), caches.get("load"))


def prefill_chunk(params, cfg: ModelConfig, tokens, k, v, block_table,
                  start, n_valid, trash_page: int):
    """One *chunk* of a paged prefill: positions ``start + [0, C)``.

    Chunked prefill (Sarathi-style) splits a long prompt into fixed-size
    chunks interleaved with decode steps; each chunk's K/V is scattered into
    the paged pool *inside* this forward (fused prefill->page scatter) and
    its attention reads the earlier chunks back through the block table, so
    no dense whole-prompt cache is ever materialized.

    Args:
      tokens: [B, C] int32; every sequence shares ``start``.  The chunk may
        be bucketed: only the first ``n_valid`` positions are real — the
        tail scatters to ``trash_page`` and is excluded from the logits.
      k / v: [L, P, Hkv, page, D] pools (kernel-native layout).
      block_table: [B, n_pages] page ids covering ``start + C`` tokens.
      start: scalar int32 tokens already resident; n_valid: scalar int32.
    Returns: (logits at position ``start + n_valid - 1`` [B, Vpad] fp32,
      new k, new v), and an expert model's ``route_counts`` of the valid
      positions last.

    SSM/hybrid architectures are not supported (the SSD scan has no
    per-position state checkpoint to resume a bucketed chunk from); callers
    fall back to one-shot prefill for them.
    """
    if cfg.has_ssm:
        raise NotImplementedError(
            "chunked prefill supports attention-only models")
    B, C = tokens.shape
    pos = start + jnp.arange(C, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos[None, :], (B, C))
    x = embed_inputs(params, cfg, tokens, None, positions)
    x = logical(x, "batch", "seq", "d_model")
    paged = {"table": block_table, "n_valid": n_valid, "trash": trash_page}
    valid = jnp.broadcast_to(jnp.arange(C)[None, :] < n_valid, (B, C))

    def body(x, scanned):
        bp, layer_idx, k_l, v_l = scanned
        x, new_kv, _, _, load = _block(x, bp, cfg, layer_idx, positions,
                                       "chunk", kv=(k_l, v_l), pos=start,
                                       paged=paged, valid=valid)
        ys = {"k": new_kv[0], "v": new_kv[1]}
        if load is not None:
            ys["load"] = load
        return x, ys

    x, ys = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.n_layers), k, v),
        unroll=cfg.scan_unroll)
    x_last = jnp.take(x, n_valid - 1, axis=1)[:, None]   # [B, 1, d]
    logits = lm_logits(params, cfg, x_last)[:, 0]
    return _routed((logits, ys["k"], ys["v"]), ys.get("load"))


# --------------------------------------------------------------------------
# Decode step.
# --------------------------------------------------------------------------


def _decode_core(params, cfg: ModelConfig, tokens, embeds, pos,
                 k, v, ssm, conv, paged, valid=None):
    """Shared decode-step body: embed, layer scan, logits.

    ``paged=None`` runs dense cached attention over k/v [L, B, Smax, Hkv, D];
    a paged dict (see ``_mixer``) runs paged attention over pools.  The
    Pallas kernel path carries every layer's pools through the layer loop
    and writes and reads them at the layer in place: a loop that took a
    layer's pool in and stacked the updated pools out would write a second
    copy of them every step.
    Returns (logits [B, Vpad] fp32, per-layer ys dict of new state, with
    the expert layers' ``load`` [L, n_held] of the ``valid`` [B] rows;
    ``k``/``v`` are the new pools).
    """
    positions = pos[:, None]
    x = embed_inputs(params, cfg, None if tokens is None else tokens[:, None],
                     embeds, positions)
    x = logical(x, "batch", "seq", "d_model")
    carried = (paged is not None and paged["impl"] == "kernel"
               and cfg.has_attn)

    def body(carry, scanned):
        bp, layer_idx, k_l, v_l, ssm_l, conv_l = scanned
        x = carry[0]
        lpaged = paged
        if carried:
            k_l, v_l = carry[1:]
            lpaged = {**paged, "layer": layer_idx}
        x, new_kv, new_ssm, _, load = _block(
            x, bp, cfg, layer_idx, positions, "decode",
            kv=(k_l, v_l), ssm_state=ssm_l, conv=conv_l, pos=pos,
            paged=lpaged, valid=None if valid is None else valid[:, None])
        ys = {}
        if cfg.has_attn and not carried:
            ys["k"], ys["v"] = new_kv
        if cfg.has_ssm:
            ys["ssm"], ys["conv"] = new_ssm
        if load is not None:
            ys["load"] = load
        return (x, *new_kv) if carried else (x,), ys

    L = cfg.n_layers
    dummy = jnp.zeros((L,), jnp.int32)
    xs = (params["blocks"], jnp.arange(L),
          dummy if k is None or carried else k,
          dummy if v is None or carried else v,
          ssm if ssm is not None else dummy,
          conv if conv is not None else dummy)
    init = (x, k, v) if carried else (x,)
    carry, ys = jax.lax.scan(body, init, xs, unroll=cfg.scan_unroll)
    if carried:
        ys["k"], ys["v"] = carry[1:]
    return lm_logits(params, cfg, carry[0])[:, 0], ys


def decode_step(params, cfg: ModelConfig, tokens, cache: DecodeCache,
                embeds=None):
    """One token for every sequence in the batch.

    Args:
      tokens: [B] int32 (or embeds [B, 1, d] for stub-frontend archs).
      cache: DecodeCache whose attention K/V buffers have a fixed max length.
    Returns: (logits [B, Vpad] fp32, updated DecodeCache)
    """
    logits, ys = _decode_core(params, cfg, tokens, embeds, cache.pos,
                              cache.k, cache.v, cache.ssm, cache.conv, None)
    new_cache = DecodeCache(
        k=ys.get("k", cache.k), v=ys.get("v", cache.v),
        ssm=ys.get("ssm", cache.ssm), conv=ys.get("conv", cache.conv),
        pos=cache.pos + 1)
    return logits, new_cache


def decode_step_paged(params, cfg: ModelConfig, tokens,
                      state: PagedDecodeState, embeds=None, *,
                      attn_impl: str = "jnp", interpret: bool = False):
    """One token for every sequence, attending the paged KV pool directly.

    The per-layer new K/V token is scattered into its page and attention
    reads pages through the block table — no dense [B, S, Hkv, D] cache is
    ever materialized (the device-resident serving decode path).

    Args:
      tokens: [B] int32 (or embeds [B, 1, d] for stub-frontend archs).
      state: PagedDecodeState; ``state.lens`` is the write/attend position.
      attn_impl: "kernel" (Pallas paged kernel) or "jnp" (exact fallback).
    Returns: (logits [B, Vpad] fp32, updated PagedDecodeState with lens+1)
    """
    return _step_paged(params, cfg, tokens, state, embeds, attn_impl,
                       interpret)[:2]


def _step_paged(params, cfg: ModelConfig, tokens, state: PagedDecodeState,
                embeds, attn_impl, interpret, valid=None):
    """``decode_step_paged``, with the expert layers' load [L, n_held] of
    the ``valid`` [B] rows (None without expert layers) last."""
    paged = {"table": state.block_table, "impl": attn_impl,
             "interpret": interpret}
    logits, ys = _decode_core(params, cfg, tokens, embeds, state.lens,
                              state.k, state.v, state.ssm, state.conv, paged,
                              valid)
    new_state = PagedDecodeState(
        k=ys.get("k", state.k), v=ys.get("v", state.v),
        block_table=state.block_table, lens=state.lens + 1,
        ssm=ys.get("ssm", state.ssm), conv=ys.get("conv", state.conv))
    return logits, new_state, ys.get("load")


def _decode_core_buffered(params, cfg: ModelConfig, tokens, pos, k, v,
                          ssm, conv, table, kh, vh, step_idx, pool_lens,
                          valid=None):
    """One buffered decode step: like ``_decode_core`` in paged mode, but
    the pools are consumed READ-ONLY (scan xs of the layer scan — never
    copied) and each layer's new K/V token is written to its row of the
    horizon buffer ``kh``/``vh`` [L, B, H, Hkv, head_dim], which the layer
    scan re-stacks into ``ys["k"]``/``ys["v"]``.
    """
    positions = pos[:, None]
    x = embed_inputs(params, cfg, tokens[:, None], None, positions)
    x = logical(x, "batch", "seq", "d_model")

    def body(x, scanned):
        bp, layer_idx, k_l, v_l, kh_l, vh_l, ssm_l, conv_l = scanned
        paged = {"impl": "buffered", "table": table, "kh": kh_l, "vh": vh_l,
                 "step": step_idx, "pool_lens": pool_lens}
        x, new_kv, new_ssm, _, load = _block(
            x, bp, cfg, layer_idx, positions, "decode",
            kv=(k_l, v_l), ssm_state=ssm_l, conv=conv_l, pos=pos,
            paged=paged, valid=None if valid is None else valid[:, None])
        ys = {}
        if cfg.has_attn:
            ys["k"], ys["v"] = new_kv          # updated buffer rows
        if cfg.has_ssm:
            ys["ssm"], ys["conv"] = new_ssm
        if load is not None:
            ys["load"] = load
        return x, ys

    L = cfg.n_layers
    dummy = jnp.zeros((L,), jnp.int32)
    xs = (params["blocks"], jnp.arange(L),
          k if k is not None else dummy,
          v if v is not None else dummy,
          kh if kh is not None else dummy,
          vh if vh is not None else dummy,
          ssm if ssm is not None else dummy,
          conv if conv is not None else dummy)
    x, ys = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)
    return lm_logits(params, cfg, x)[:, 0], ys


def decode_loop_paged(params, cfg: ModelConfig, tokens,
                      state: PagedDecodeState, key, step0, horizon: int, *,
                      attn_impl: str = "jnp", interpret: bool = False,
                      temperature: float = 0.0, valid=None):
    """``horizon`` fused decode steps, entirely on device (``lax.scan``).

    Each scan iteration runs one full paged decode step — attention over
    the block table, SSM state update — then samples the next token with
    the per-step folded key (``sampling.step_key(key, step0 + i)``) and
    feeds it straight back as the next step's input, so generating
    ``horizon`` tokens costs one jit dispatch and (at the caller) one
    device→host transfer instead of ``horizon`` of each.

    K/V pool traffic is O(pool) per HORIZON, not per token: for the jnp
    attention path the pools are scan *constants* — each step writes its
    K/V token into a [L, B, H, Hkv, D] side buffer that attention overlays
    onto the gathered pages (bit-identical result, see
    ``attention.paged_decode_attention_buffered``) — and the buffer is
    scattered through the block table once after the loop.  The Pallas
    kernel path keeps the scatter-first loop (the kernel reads pages in
    place, and on TPU buffer donation makes the in-loop pool updates
    in-place).

    The caller must have pre-extended page capacity for ``horizon`` more
    tokens per sequence: positions ``lens .. lens + horizon - 1`` are
    written through the block table with no host allocation in the loop.
    ``step0`` is the global decode-step counter (a traced scalar is fine);
    with ``temperature == 0`` the keys are ignored and the loop is exactly
    ``horizon`` greedy decode steps.

    Args:
      tokens: [B] int32 — each sequence's last generated token.
      state: PagedDecodeState at the pre-loop lengths.
      horizon: static step count (callers bucket it to keep compilations
        O(log max_horizon)).
      valid: [B] bool rows that are traffic (default all); only they count
        in the routing counts.
    Returns: (tokens [B, horizon] int32, PagedDecodeState with lens +
      horizon), and an expert model's ``route_counts`` over the horizon
      last.
    """
    from repro.models.sampling import sample, step_key

    if not cfg.has_attn or attn_impl == "kernel":
        # scatter-first loop: pools (if any) ride the scan carry
        def body(carry, i):
            toks, st = carry
            logits, st, load = _step_paged(params, cfg, toks, st, None,
                                           attn_impl, interpret, valid)
            toks = sample(logits, cfg, step_key(key, step0 + i),
                          temperature=temperature)
            return (toks, st), (toks, load)

        (_, state), (toks_h, loads) = jax.lax.scan(
            body, (tokens, state), jnp.arange(horizon, dtype=jnp.int32))
        return _routed((jnp.moveaxis(toks_h, 0, 1), state), loads)

    # buffered loop (jnp path): pools stay out of the carry
    B = tokens.shape[0]
    L = cfg.n_layers
    pool_lens = state.lens
    kh = jnp.zeros((L, B, horizon, cfg.n_kv_heads, cfg.head_dim),
                   state.k.dtype)
    # horizon side buffer shards like the pool: layers over pp, KV heads
    # over tp (identity when no sharding rules are installed)
    kh = logical(kh, "layers", "batch", None, "kv_heads", None)
    vh = jnp.zeros_like(kh)

    def body(carry, i):
        toks, lens, kh, vh, ssm, conv = carry
        logits, ys = _decode_core_buffered(
            params, cfg, toks, lens, state.k, state.v, ssm, conv,
            state.block_table, kh, vh, i, pool_lens, valid)
        toks = sample(logits, cfg, step_key(key, step0 + i),
                      temperature=temperature)
        return (toks, lens + 1, ys["k"], ys["v"],
                ys.get("ssm", ssm), ys.get("conv", conv)), (toks,
                                                            ys.get("load"))

    init = (tokens, state.lens, kh, vh, state.ssm, state.conv)
    (_, lens, kh, vh, ssm, conv), (toks_h, loads) = jax.lax.scan(
        body, init, jnp.arange(horizon, dtype=jnp.int32))

    # the horizon's ONE pool scatter: buffer -> pages via the block table
    table = state.block_table
    page = state.k.shape[3]
    tpos = pool_lens[:, None] + jnp.arange(horizon)[None, :]      # [B, H]
    pid = jnp.take_along_axis(table, tpos // page, axis=1)        # [B, H]
    off = tpos % page
    dpad = state.k.shape[-1] - kh.shape[-1]
    if dpad:
        kh = jnp.pad(kh, ((0, 0),) * 4 + ((0, dpad),))
        vh = jnp.pad(vh, ((0, 0),) * 4 + ((0, dpad),))
    hidx = jnp.arange(cfg.n_kv_heads)[None, None, :]
    k_pages = state.k.at[:, pid[:, :, None], hidx, off[:, :, None]].set(
        kh.astype(state.k.dtype))
    v_pages = state.v.at[:, pid[:, :, None], hidx, off[:, :, None]].set(
        vh.astype(state.v.dtype))
    new_state = PagedDecodeState(k=k_pages, v=v_pages, block_table=table,
                                 lens=lens, ssm=ssm, conv=conv)
    return _routed((jnp.moveaxis(toks_h, 0, 1), new_state), loads)

"""Grouped-query attention: full-sequence (train/prefill) and cached decode.

Sharding strategy (resolved per-arch by ``repro.launch.sharding``):
  * train/prefill: heads sharded over `model` when divisible, optionally
    padded to the next multiple of TP ("pad"), else replicated.
  * decode: the KV cache is sharded along the *sequence* axis over `model`
    ("kv_seq" logical axis) — flash-decoding semantics; GSPMD inserts the
    partial-softmax combine collectives.  This removes head-divisibility
    constraints and spreads KV memory evenly at 32k-500k contexts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm, softcap
from repro.pshard import logical

NEG_INF = -2.0 ** 30


def init_attention(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 6)
    d, q_dim, kv_dim = cfg.d_model, cfg.q_dim, cfg.kv_dim
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, q_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (q_dim, d)) / np.sqrt(q_dim)).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((q_dim,), dtype)
        p["bk"] = jnp.zeros((kv_dim,), dtype)
        p["bv"] = jnp.zeros((kv_dim,), dtype)
    if cfg.qk_norm:
        whole = cfg.qk_norm_scope == "proj"
        p["q_norm"] = jnp.zeros((q_dim if whole else cfg.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((kv_dim if whole else cfg.head_dim,), dtype)
    return p


def _project_qkv(x, p, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm and cfg.qk_norm_scope == "proj":
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = q.reshape(B, S, cfg.n_q_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm and cfg.qk_norm_scope == "head":
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(k: jax.Array, n_q_heads: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hq, D] by group broadcast."""
    B, S, Hkv, D = k.shape
    rep = n_q_heads // Hkv
    if rep == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, Hkv, rep, D))
    return k.reshape(B, S, Hkv * rep, D)


def causal_mask(S: int, window: int = 0) -> jax.Array:
    """[S, S] additive mask; window > 0 limits lookback (local attention)."""
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    ok = j <= i
    if window > 0:
        ok = ok & (j > i - window)
    return jnp.where(ok, 0.0, NEG_INF)


CHUNK_THRESHOLD = 2048   # sequences longer than this use the chunked path
Q_CHUNK = 1024


def _attend(q, k, v, cfg: ModelConfig, q_pos, k_pos, is_local):
    """softmax((q k^T) * scale + mask) v with explicit position masks.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; q_pos: [Sq]; k_pos: [Sk].
    """
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = softcap(scores, cfg.attn_logit_softcap)
    ok = k_pos[None, :] <= q_pos[:, None]
    if cfg.local_window > 0:
        ok_local = ok & (k_pos[None, :] > q_pos[:, None] - cfg.local_window)
        ok = jnp.where(jnp.asarray(is_local), ok_local, ok)
    scores = jnp.where(ok[None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def full_attention(x: jax.Array, p: dict, cfg: ModelConfig,
                   positions: jax.Array, is_local: jax.Array | bool = False
                   ) -> jax.Array:
    """Train/prefill self-attention over the whole sequence.

    For S > CHUNK_THRESHOLD the query dimension is processed in rematted
    chunks (flash-style memory behavior: the [S, S] score matrix is never
    materialized; the chunk body is recomputed in the backward pass).

    is_local: python bool or traced scalar selecting the gemma2 local mask.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg, positions)
    q = logical(q, "batch", "seq", "heads", None)
    k = logical(k, "batch", "seq", "kv_heads", None)
    v = logical(v, "batch", "seq", "kv_heads", None)
    k = _expand_kv(k, cfg.n_q_heads)
    v = _expand_kv(v, cfg.n_q_heads)
    pos1d = jnp.arange(S)

    if S <= CHUNK_THRESHOLD:
        out = _attend(q, k, v, cfg, pos1d, pos1d, is_local)
    else:
        C = Q_CHUNK
        n_chunks = (S + C - 1) // C
        assert S % C == 0, f"seq {S} must be a multiple of chunk {C}"
        qc = q.reshape(B, n_chunks, C, cfg.n_q_heads, cfg.head_dim)
        qc = jnp.moveaxis(qc, 1, 0)

        def body(_, args):
            q_i, i = args
            q_pos = i * C + jnp.arange(C)
            o = _attend(q_i, k, v, cfg, q_pos, pos1d, is_local)
            return None, o

        body = jax.checkpoint(body, prevent_cse=False)
        # unroll with the layer scan so dry-run cost_analysis counts every trip
        _, oc = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)),
                             unroll=(cfg.scan_unroll > 1))
        out = jnp.moveaxis(oc, 0, 1).reshape(B, S, cfg.n_q_heads, cfg.head_dim)

    out = logical(out, "batch", "seq", "heads", None)
    out = out.reshape(B, S, cfg.q_dim)
    return out @ p["wo"]


def _gather_pages_dense(k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array, head_dim: int
                        ) -> tuple[jax.Array, jax.Array]:
    """Gather a block table's pages as dense [B, n_pages * page, Hkv, D]
    caches (kernel-native pool layout in, head_pad columns dropped).  The
    ONE page->dense layout transform — every jnp attention path shares it,
    so a pool-layout change cannot silently desynchronize them.
    """
    B = block_table.shape[0]
    k = k_pages[block_table]                # [B, n, Hkv, page, D]
    v = v_pages[block_table]
    _, n, Hkv, page, D = k.shape
    k = jnp.moveaxis(k, 3, 2).reshape(B, n * page, Hkv, D)
    v = jnp.moveaxis(v, 3, 2).reshape(B, n * page, Hkv, D)
    return k[..., :head_dim], v[..., :head_dim]


def paged_attention_jnp(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array, lens: jax.Array,
                        start: jax.Array, cfg: ModelConfig) -> jax.Array:
    """jnp reference paged attention (CPU / interpret fallback).

    q: [B, Hq, D]; k_pages/v_pages: [P, Hkv, page, D] (kernel-native layout);
    block_table: [B, n_pages]; lens: [B] #positions attended (incl. current
    token); start: [B] lower position bound.  Returns [B, Hq, D].

    The gather stays in native layout; the score/mask/softmax math is the
    shared oracle (``ref.flash_decode_ref``), so dense, paged, and kernel
    paths all agree token-for-token under greedy decode.
    """
    from repro.kernels.ref import flash_decode_ref
    k, v = _gather_pages_dense(k_pages, v_pages, block_table, cfg.head_dim)
    return flash_decode_ref(q, k, v, lens, start=start,
                            softcap=float(cfg.attn_logit_softcap))


def paged_decode_attention(x: jax.Array, p: dict, cfg: ModelConfig,
                           k_pages: jax.Array, v_pages: jax.Array,
                           block_table: jax.Array, lens: jax.Array,
                           is_local: jax.Array | bool = False, *,
                           impl: str = "jnp", interpret: bool = False,
                           layer: jax.Array | None = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step directly against the paged KV pool (gather-free).

    Also the body of the fused multi-step horizon loop
    (``models.decode_loop_paged``): the pool scatter + table read are pure
    functional updates on the scan carry, so H consecutive steps run
    device-resident with the caller's block table pre-extended for all H
    tokens — nothing here may touch the host.

    Args:
      x: [B, 1, d_model] current token embedding.
      k_pages / v_pages: [P, Hkv, page, D] one layer's pool, kernel-native
        layout; the new K/V token is scattered into its page in place.
        With ``layer`` (a traced scalar; ``impl="kernel"`` only): every
        layer's pools [L, P, Hkv, page, D], written and read at that layer
        in place, so a layer loop that carries them copies no pool.
      block_table: [B, n_pages] physical page ids (padded rows may point at
        a trash page — the scatter then lands there harmlessly).
      lens: [B] number of tokens already cached; the new token is written at
        position ``lens`` and attention covers [start, lens+1).
      impl: "kernel" routes through the Pallas paged kernel, "jnp" uses the
        gathered reference path (exact vs the dense decode path).
    Returns: (attn_out [B, 1, d_model], new k_pages, new v_pages)
    """
    B = x.shape[0]
    pos = lens
    q, k_new, v_new = _project_qkv(x, p, cfg, pos[:, None])
    # sharded replicas: q by (tp) heads, the new K/V token by KV heads, so
    # the page scatter below stays local to the head shard that owns it
    q = logical(q, "batch", None, "heads", None)
    page = k_pages.shape[-2]
    Hkv = k_pages.shape[-3]
    dpad = k_pages.shape[-1] - cfg.head_dim   # pool head_pad (kernel path)
    kn, vn = k_new[:, 0], v_new[:, 0]
    kn = logical(kn, "batch", "kv_heads", None)
    vn = logical(vn, "batch", "kv_heads", None)
    if dpad:
        kn = jnp.pad(kn, ((0, 0), (0, 0), (0, dpad)))
        vn = jnp.pad(vn, ((0, 0), (0, 0), (0, dpad)))
    pid = block_table[jnp.arange(B), pos // page]         # [B]
    off = pos % page
    hidx = jnp.arange(Hkv)[None, :]
    at = (pid[:, None], hidx, off[:, None])
    if layer is not None:
        at = (layer, *at)
    k_pages = k_pages.at[at].set(kn.astype(k_pages.dtype))
    v_pages = v_pages.at[at].set(vn.astype(v_pages.dtype))

    len_att = pos + 1
    if cfg.local_window > 0:
        lo = jnp.maximum(len_att - cfg.local_window, 0)
        start = jnp.where(jnp.asarray(is_local), lo, 0)
    else:
        start = jnp.zeros_like(len_att)
    if impl == "kernel":
        from repro.kernels import flash_decode as _fd
        qk = q[:, 0]
        if dpad:                      # pool is pre-padded; pad q alone
            qk = jnp.pad(qk, ((0, 0), (0, 0), (0, dpad)))
        out = _fd.flash_decode_paged_native(
            qk, k_pages, v_pages, block_table, len_att, start=start,
            softcap=float(cfg.attn_logit_softcap),
            scale=1.0 / np.sqrt(cfg.head_dim),
            interpret=interpret, layer=layer)[..., :cfg.head_dim]
    else:
        out = paged_attention_jnp(q[:, 0], k_pages, v_pages, block_table,
                                  len_att, start, cfg)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], k_pages, v_pages


def paged_decode_attention_buffered(x: jax.Array, p: dict, cfg: ModelConfig,
                                    k_pages: jax.Array, v_pages: jax.Array,
                                    block_table: jax.Array,
                                    pool_lens: jax.Array,
                                    kh: jax.Array, vh: jax.Array,
                                    step_idx: jax.Array,
                                    is_local: jax.Array | bool = False
                                    ) -> tuple[jax.Array, jax.Array,
                                               jax.Array]:
    """One decode step of the fused horizon loop: pools stay READ-ONLY.

    Inside a ``lax.scan`` over H decode steps, writing the per-step K/V
    token into the paged pool would force the whole pool through the scan
    carry (an O(pool) copy per token on backends without aliasing).
    Instead the horizon's new K/V lives in a small side buffer ``kh``/``vh``
    ([B, H, Hkv, head_dim], scan-carried), and attention overlays the
    buffer onto the gathered pages at its absolute positions — producing
    the *bit-identical* dense cache the scatter-first path would have
    gathered (overwritten lanes past the valid length are masked to exact
    zeros either way), so tokens match the per-step path exactly.  The
    caller scatters the buffer into the pool once per horizon
    (``models.decode_loop_paged``).

    Args:
      x: [B, 1, d_model] current token embedding.
      k_pages / v_pages: [P, Hkv, page, D] one layer's pool (not written).
      block_table: [B, n_pages] physical page ids covering the horizon.
      pool_lens: [B] tokens resident in pages BEFORE the horizon started.
      kh / vh: [B, H, Hkv, head_dim] this horizon's K/V so far; position
        ``step_idx`` is written here.
      step_idx: scalar int32 — loop iteration (absolute position is
        ``pool_lens + step_idx``).
    Returns: (attn_out [B, 1, d_model], new kh, new vh)
    """
    from repro.kernels.ref import flash_decode_ref
    B = x.shape[0]
    H = kh.shape[1]
    pos = pool_lens + step_idx
    q, k_new, v_new = _project_qkv(x, p, cfg, pos[:, None])
    q = logical(q, "batch", None, "heads", None)
    kh = kh.at[:, step_idx].set(k_new[:, 0].astype(kh.dtype))
    vh = vh.at[:, step_idx].set(v_new[:, 0].astype(vh.dtype))
    kh = logical(kh, "batch", None, "kv_heads", None)
    vh = logical(vh, "batch", None, "kv_heads", None)

    # gather the paged prefix, then overlay the horizon buffer at its
    # absolute positions (entries past ``lens`` are masked out below, so
    # the not-yet-generated tail of the buffer is harmless)
    k, v = _gather_pages_dense(k_pages, v_pages, block_table, cfg.head_dim)
    bidx = jnp.arange(B)[:, None]
    tpos = pool_lens[:, None] + jnp.arange(H)[None, :]    # [B, H]
    k = k.at[bidx, tpos].set(kh)
    v = v.at[bidx, tpos].set(vh)

    len_att = pos + 1
    if cfg.local_window > 0:
        lo = jnp.maximum(len_att - cfg.local_window, 0)
        start = jnp.where(jnp.asarray(is_local), lo, 0)
    else:
        start = jnp.zeros_like(len_att)
    out = flash_decode_ref(q[:, 0], k, v, len_att, start=start,
                           softcap=float(cfg.attn_logit_softcap))
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], kh, vh


def prefill_chunk_attention(x: jax.Array, p: dict, cfg: ModelConfig,
                            k_pages: jax.Array, v_pages: jax.Array,
                            block_table: jax.Array, start: jax.Array,
                            n_valid: jax.Array, trash_page: int,
                            is_local: jax.Array | bool = False
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One prefill *chunk* attending the paged prefix + itself (chunked
    prefill: the prefill->page scatter is fused into the forward).

    Args:
      x: [B, C, d_model] chunk embeddings (positions ``start + [0, C)``;
        every sequence in the batch shares the same ``start``).
      k_pages / v_pages: [P, Hkv, page, D] one layer's pool, kernel-native
        layout; the chunk's K/V is scattered into its pages in place, then
        attention reads the table's pages (prefix chunks included) — no
        dense per-sequence cache is ever materialized outside the pool.
      block_table: [B, n_pages] physical page ids covering start + C tokens.
      start: scalar int32 — tokens already resident (earlier chunks).
      n_valid: scalar int32 — real tokens in this chunk (the tail of a
        bucketed chunk scatters to ``trash_page`` and is masked out).
    Returns: (attn_out [B, C, d_model], new k_pages, new v_pages)
    """
    B, C = x.shape[0], x.shape[1]
    pos = start + jnp.arange(C, dtype=jnp.int32)           # [C]
    q, k_new, v_new = _project_qkv(x, p, cfg, pos[None, :])
    q = logical(q, "batch", "seq", "heads", None)
    k_new = logical(k_new, "batch", "seq", "kv_heads", None)
    v_new = logical(v_new, "batch", "seq", "kv_heads", None)
    page = k_pages.shape[2]
    Hkv = k_pages.shape[1]
    n_pages = block_table.shape[1]
    dpad = k_pages.shape[-1] - cfg.head_dim
    if dpad:
        k_new = jnp.pad(k_new, ((0, 0),) * 3 + ((0, dpad),))
        v_new = jnp.pad(v_new, ((0, 0),) * 3 + ((0, dpad),))
    valid = jnp.arange(C) < n_valid                        # [C]
    tidx = jnp.minimum(pos // page, n_pages - 1)           # [C]
    pid = jnp.where(valid[None, :], block_table[:, tidx], trash_page)  # [B, C]
    off = (pos % page)[None, :]                            # [1, C]
    hidx = jnp.arange(Hkv)[None, None, :]
    k_pages = k_pages.at[pid[:, :, None], hidx, off[:, :, None]].set(
        k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[pid[:, :, None], hidx, off[:, :, None]].set(
        v_new.astype(v_pages.dtype))

    # gather prefix + chunk through the table (pages past the live length
    # hold trash and are position-masked below)
    k, v = _gather_pages_dense(k_pages, v_pages, block_table, cfg.head_dim)
    k = _expand_kv(k, cfg.n_q_heads).astype(q.dtype)
    v = _expand_kv(v, cfg.n_q_heads).astype(q.dtype)
    out = _attend(q, k, v, cfg, pos, jnp.arange(n_pages * page), is_local)
    out = out.reshape(B, C, cfg.q_dim)
    return out @ p["wo"], k_pages, v_pages


def decode_attention(x: jax.Array, p: dict, cfg: ModelConfig,
                     k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, is_local: jax.Array | bool = False
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step with a dense KV cache.

    Args:
      x: [B, 1, d_model] current token embedding.
      k_cache / v_cache: [B, Smax, Hkv, D]; the new K/V is written at `pos`.
      pos: [B] int32 write/attend position per sequence.
    Returns:
      (attn_out [B, 1, d_model], new k_cache, new v_cache)
    """
    B, Smax = k_cache.shape[0], k_cache.shape[1]
    q, k_new, v_new = _project_qkv(x, p, cfg, pos[:, None])
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, pos].set(k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, pos].set(v_new[:, 0].astype(v_cache.dtype))
    # Sequence-sharded cache: flash-decoding combine happens inside the
    # softmax/contraction that GSPMD partitions along `kv_seq`.
    k_cache = logical(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = logical(v_cache, "batch", "kv_seq", "kv_heads", None)

    kk = _expand_kv(k_cache, cfg.n_q_heads)
    vv = _expand_kv(v_cache, cfg.n_q_heads)
    if kk.dtype != x.dtype:      # fp8/quantized caches upcast for compute
        kk = kk.astype(x.dtype)
        vv = vv.astype(x.dtype)
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    scores = softcap(scores, cfg.attn_logit_softcap)
    j = jnp.arange(Smax)[None, None, None, :]
    ok = j <= pos[:, None, None, None]
    if cfg.local_window > 0:
        lo = pos[:, None, None, None] - cfg.local_window
        ok_local = ok & (j > lo)
        sel = jnp.asarray(is_local)
        ok = jnp.where(sel, ok_local, ok)
    scores = jnp.where(ok, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], k_cache, v_cache

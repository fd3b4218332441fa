"""Flash-decode — batched single-token attention over a (paged) KV cache.

Variants:
  * ``flash_decode``             — dense cache [B, S, Hkv, D], grid (B, Hq, n_k)
    with online-softmax scratch accumulation and per-sequence length masking.
  * ``flash_decode_paged``       — vLLM-style paged cache: the block table rides
    in scalar-prefetch SMEM (PrefetchScalarGridSpec) and the K/V index maps
    dereference it, so pages are fetched HBM->VMEM exactly once, in table
    order.  This is the TPU-native form of the serving engine's decode path.
  * ``flash_decode_paged_batch`` — multi-layer entry point over pools already
    stored in kernel-native layout [L, P, Hkv, page, D]: the engine issues one
    pallas_call per layer with no per-(layer, step) transposes or reshapes.

Per-sequence masking is a [start, len) window: ``lens`` masks the invalid
tail, ``start`` (optional) masks the head for local/sliding-window layers.
Blocks entirely outside the window are skipped (``pl.when`` early exit) and
their K/V index maps are clamped into the live range so no extra pages are
DMA'd.  Softcap supports gemma2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_k: int, n_k: int, softcap: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # [1, D] (token block)
    k = k_ref[0, 0].astype(jnp.float32)              # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < len_ref[b], s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, lens: jax.Array,
                 *, softcap: float = 0.0, block_k: int = 128,
                 scale: float | None = None,
                 interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D] one token per sequence; k/v: [B, S, Hkv, D]; lens [B].

    Returns [B, Hq, D].  S % block_k == 0 (ops.py pads).
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    group = Hq // Hkv
    n_k = S // block_k
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qt = q[:, :, None, :]                         # [B, Hq, 1, D]
    kt = jnp.swapaxes(k, 1, 2)                    # [B, Hkv, S, D]
    vt = jnp.swapaxes(v, 1, 2)

    # kernel signature with scalar prefetch: (lens, q, k, v, o, scratch...)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               n_k=n_k, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, lens: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, lens: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D),
                               lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), qt, kt, vt)
    return out[:, :, 0]


def _paged_kernel(lens_ref, start_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, block, n_blocks, softcap):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    start = start_ref[b]
    first_blk = start // block
    last_blk = jnp.maximum(length - 1, 0) // block

    # early exit: blocks fully outside [start, length) contribute nothing;
    # their index maps are clamped into the live range so they also move no
    # new data HBM->VMEM (same block index as the previous grid step).
    @pl.when((j >= first_blk) & (j <= last_blk) & (length > 0))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [1, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [block, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((pos >= start) & (pos < length), s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(j == jnp.minimum(last_blk, n_blocks - 1))
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def _paged_call(qt: jax.Array, kt: jax.Array, vt: jax.Array,
                block_table: jax.Array, lens: jax.Array, start: jax.Array,
                *, softcap: float, scale: float, interpret: bool) -> jax.Array:
    """Core pallas_call over kernel-native layouts.

    qt: [B, Hq, 1, D]; kt/vt: [P, Hkv, page, D]; block_table: [B, max_pages];
    lens/start: [B].  Returns [B, Hq, D].
    """
    B, Hq, _, D = qt.shape
    _, Hkv, page, _ = kt.shape
    group = Hq // Hkv
    max_pages = block_table.shape[1]

    def kv_index(b, h, j, lens, start, tbl):
        first = start[b] // page
        last = jnp.maximum(lens[b] - 1, 0) // page
        jj = jnp.clip(j, first, last)
        return (tbl[b, jj], h // group, 0, 0)

    kernel = functools.partial(_paged_kernel, scale=scale, block=page,
                               n_blocks=max_pages, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # lens, start, block_table
        grid=(B, Hq, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D),
                         lambda b, h, j, lens, start, tbl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D), kv_index),
            pl.BlockSpec((1, 1, page, D), kv_index),
        ],
        # Mosaic wants a block's last two dims equal to the array's (or
        # 8/128-aligned): a [B, Hq, 1, D] output with (1, 1, 1, D) blocks
        # lowers, a [B, Hq, D] one with (1, 1, D) blocks does not
        out_specs=pl.BlockSpec((1, 1, 1, D),
                               lambda b, h, j, lens, start, tbl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), qt.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(lens.astype(jnp.int32), start.astype(jnp.int32),
      block_table.astype(jnp.int32), qt, kt, vt)
    return out[:, :, 0]


def flash_decode_paged_native(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              lens: jax.Array, *,
                              start: jax.Array | None = None,
                              softcap: float = 0.0,
                              scale: float | None = None,
                              interpret: bool = False) -> jax.Array:
    """Paged decode over kernel-native pools (the serving engine's layout).

    q: [B, Hq, D]; k_pages/v_pages: [num_pages, Hkv, page, D] — already in
    kernel layout, so no per-call transpose.  Other args as
    ``flash_decode_paged``.  Returns [B, Hq, D].
    """
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if start is None:
        start = jnp.zeros_like(lens)
    return _paged_call(q[:, :, None, :], k_pages, v_pages, block_table, lens,
                       start, softcap=softcap, scale=scale,
                       interpret=interpret)


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_table: jax.Array, lens: jax.Array,
                       *, start: jax.Array | None = None,
                       softcap: float = 0.0, scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """Paged decode attention.

    Args:
      q: [B, Hq, D]; k_pages/v_pages: [num_pages, page, Hkv, D];
      block_table: [B, max_pages] int32 physical page per logical page;
      lens: [B] sequence lengths; start: [B] optional lower position bound
        (local/sliding-window attention), defaults to 0.
    Returns [B, Hq, D].
    """
    B, Hq, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if start is None:
        start = jnp.zeros_like(lens)
    qt = q[:, :, None, :]
    kt = jnp.swapaxes(k_pages, 1, 2)               # [pages, Hkv, page, D]
    vt = jnp.swapaxes(v_pages, 1, 2)
    return _paged_call(qt, kt, vt, block_table, lens, start,
                       softcap=softcap, scale=scale, interpret=interpret)


def flash_decode_paged_batch(q: jax.Array, k_pages: jax.Array,
                             v_pages: jax.Array, block_table: jax.Array,
                             lens: jax.Array, *,
                             start: jax.Array | None = None,
                             softcap: float = 0.0, scale: float | None = None,
                             interpret: bool = False) -> jax.Array:
    """Multi-layer paged decode over kernel-native pools.

    Args:
      q: [L, B, Hq, D] one token per sequence per layer;
      k_pages/v_pages: [L, num_pages, Hkv, page, D] (kernel-native layout —
        no per-call transpose); block_table: [B, max_pages]; lens/start: [B].
    Returns [L, B, Hq, D] with exactly one pallas_call per layer (the layer
    loop is a rolled ``lax.map``; table/lens prefetch is shared).
    """
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if start is None:
        start = jnp.zeros_like(lens)

    def one_layer(args):
        ql, kl, vl = args
        return _paged_call(ql[:, :, None, :], kl, vl, block_table, lens,
                           start, softcap=softcap, scale=scale,
                           interpret=interpret)

    return jax.lax.map(one_layer, (q, k_pages, v_pages))

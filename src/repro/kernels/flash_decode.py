"""Flash-decode — batched single-token attention over a (paged) KV cache.

Variants:
  * ``flash_decode``             — dense cache [B, S, Hkv, D], grid (B, Hq, n_k)
    with online-softmax scratch accumulation and per-sequence length masking.
  * ``flash_decode_paged``       — vLLM-style paged cache, the TPU-native form
    of the serving engine's decode path (below).
  * ``flash_decode_paged_batch`` — multi-layer entry point over pools already
    stored in kernel-native layout [L, P, Hkv, page, D]: the engine issues one
    pallas_call per layer with no per-(layer, step) transposes or reshapes.

The paged kernel's grid is (B, Hkv, n_blocks).  A grid step takes all the
query heads of one KV head's group as one [group, D] block and one block of
``pages_per_block`` pages of that head, so each K/V page is read once per KV
head and scores are a [group, block tokens] matmul.  Pages per block is the
largest power of two with pages x page <= 256 tokens that the table width
holds and divides (``_pages_per_block``); the engine's page buckets are
powers of two.  The pools stay in HBM (``pl.ANY``); the block table rides in
scalar-prefetch SMEM, and each block's pages are copied into a
double-buffered VMEM block, one DMA per page, while the previous block
computes: each live step starts the copies of the next live step in grid
order.

Per-sequence masking is a [start, len) window: ``lens`` masks the invalid
tail, ``start`` (optional) masks the head for local/sliding-window layers.
A block wholly outside the window starts no copy and no matmul; in a block
that straddles its edge, pages outside it are not copied either.  Softcap
supports gemma2.
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


# Tokens one grid step of the paged kernel covers at most: enough that a
# step's fixed cost is small beside its copies, few enough that two K and
# two V blocks stay small in VMEM.
BLOCK_TOKENS = 256


def _pages_per_block(page: int, max_pages: int) -> int:
    """Pages one grid step covers: the largest power of two whose pages hold
    at most ``BLOCK_TOKENS`` tokens, no more than the table holds, and that
    divides the table width.  The engine's page buckets are powers of two,
    so the last condition only bites on odd tables from direct callers."""
    ppb = 1
    while (2 * ppb * page <= BLOCK_TOKENS and 2 * ppb <= max_pages
           and max_pages % (2 * ppb) == 0):
        ppb *= 2
    return ppb


def _paged_kernel(lens_ref, start_ref, table_ref, layer_ref, q_ref, k_hbm,
                  v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem, flight, m_scr,
                  l_scr, acc_scr, *, scale, softcap, ppb, n_blocks):
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_b, n_h = pl.num_programs(0), pl.num_programs(1)
    page = k_hbm.shape[3]
    bk = ppb * page

    def live_pages(bb):
        """Pages [first, last] of sequence ``bb`` that hold [start, lens);
        last < first when the window is empty."""
        length, start = lens_ref[bb], start_ref[bb]
        last = jnp.where(start < length,
                         (jnp.maximum(length, 1) - 1) // page, -1)
        return start // page, jnp.minimum(last, n_blocks * ppb - 1)

    def block_copies(bb, hh, jj, slot, wait):
        """Start (or wait for) the HBM->VMEM copies of block ``jj`` of
        sequence ``bb``, KV head ``hh``: one per live page, into ``slot``."""
        first, last = live_pages(bb)
        for i in range(ppb):
            p = jj * ppb + i

            @pl.when((p >= first) & (p <= last))
            def _copy():
                for pool, buf, sem in ((k_hbm, k_buf, k_sem),
                                       (v_hbm, v_buf, v_sem)):
                    cp = pltpu.make_async_copy(
                        pool.at[layer_ref[0], table_ref[bb, p], hh],
                        buf.at[slot, pl.ds(i * page, page)], sem.at[slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    # flight[0]: the slot holding the current block; flight[1]: 1 when the
    # previous live step already started this block's copies
    @pl.when((b == 0) & (h == 0) & (j == 0))
    def _first_step():
        flight[0] = 0
        flight[1] = 0

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = live_pages(b)

    # dead blocks (wholly outside [start, lens)) start no copy and no matmul
    @pl.when((j * ppb <= last) & (j * ppb + ppb - 1 >= first))
    def _compute():
        slot = flight[0]

        @pl.when(flight[1] == 0)
        def _start_own():
            block_copies(b, h, j, slot, wait=False)

        # the next live step in grid order: the next block of this head, the
        # first block of the next head, or that of the next sequence
        b1 = jnp.minimum(b + 1, n_b - 1)
        first1, last1 = live_pages(b1)
        more_blocks = (j + 1) * ppb <= last
        more_heads = h + 1 < n_h
        next_seq = (b + 1 < n_b) & (first1 <= last1)
        nb = jnp.where(more_blocks | more_heads, b, b1)
        nh = jnp.where(more_blocks, h, jnp.where(more_heads, h + 1, 0))
        nj = jnp.where(more_blocks, j + 1,
                       jnp.where(more_heads, first // ppb, first1 // ppb))
        has_next = more_blocks | more_heads | next_seq

        @pl.when(has_next)
        def _prefetch():
            block_copies(nb, nh, nj, 1 - slot, wait=False)

        flight[0] = jnp.where(has_next, 1 - slot, slot)
        flight[1] = has_next.astype(jnp.int32)
        block_copies(b, h, j, slot, wait=True)

        q = q_ref[0, 0]                                  # [group, D]
        k = k_buf[slot]                                  # [bk, D]
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(q.astype(dt), k.astype(dt),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        length, start = lens_ref[b], start_ref[b]
        pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((pos >= start) & (pos < length), s, NEG_INF)
        # pages outside the window were not copied: their rows hold stale
        # VMEM, which must not reach P.V even at zero weight
        row = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where((row >= start) & (row < length),
                      v_buf[slot].astype(jnp.float32), 0.0)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def _paged_call(q: jax.Array, kt: jax.Array, vt: jax.Array,
                block_table: jax.Array, lens: jax.Array, start: jax.Array,
                *, softcap: float, scale: float, interpret: bool,
                layer: jax.Array | None = None) -> jax.Array:
    """Core pallas_call over kernel-native layouts.

    q: [B, Hq, D]; kt/vt: [P, Hkv, page, D], or with ``layer`` (a traced
    scalar) every layer's pools [L, P, Hkv, page, D], of which the kernel
    reads layer ``layer`` in place; block_table: [B, max_pages];
    lens/start: [B].  Returns [B, Hq, D].
    """
    if layer is None:
        kt, vt, layer = kt[None], vt[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    B, Hq, D = q.shape
    _, _, Hkv, page, _ = kt.shape
    group = Hq // Hkv
    max_pages = block_table.shape[1]
    ppb = _pages_per_block(page, max_pages)
    n_blocks = max_pages // ppb
    bk = ppb * page

    kernel = functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                               ppb=ppb, n_blocks=n_blocks)
    # the group's query heads as one block whose last two dims are the
    # array's, which Mosaic takes for any group size
    q_spec = pl.BlockSpec((1, 1, group, D),
                          lambda b, h, j, *prefetched: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,             # lens, start, block_table, layer
        grid=(B, Hkv, n_blocks),
        in_specs=[q_spec,
                  pl.BlockSpec(memory_space=pl.ANY),   # pools stay in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, bk, D), kt.dtype),      # double-buffered blocks
            pltpu.VMEM((2, bk, D), vt.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        # a block's copies are started by the live step before it, so the
        # grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(lens.astype(jnp.int32), start.astype(jnp.int32),
      block_table.astype(jnp.int32), layer, q.reshape(B, Hkv, group, D), kt,
      vt)
    return out.reshape(B, Hq, D)


def flash_decode_paged_native(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              lens: jax.Array, *,
                              start: jax.Array | None = None,
                              softcap: float = 0.0,
                              scale: float | None = None,
                              interpret: bool = False,
                              layer: jax.Array | None = None) -> jax.Array:
    """Paged decode over kernel-native pools (the serving engine's layout).

    q: [B, Hq, D]; k_pages/v_pages: [num_pages, Hkv, page, D] — already in
    kernel layout, so no per-call transpose — or, with ``layer``, the
    stacked pools of every layer [L, num_pages, Hkv, page, D], read at
    that layer in place (no slice of the stack is copied).  Other args as
    ``flash_decode_paged``.  Returns [B, Hq, D].
    """
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if start is None:
        start = jnp.zeros_like(lens)
    return _paged_call(q, k_pages, v_pages, block_table, lens, start,
                       softcap=softcap, scale=scale,
                       interpret=interpret, layer=layer)


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
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if start is None:
        start = jnp.zeros_like(lens)
    kt = jnp.swapaxes(k_pages, 1, 2)               # [pages, Hkv, page, D]
    vt = jnp.swapaxes(v_pages, 1, 2)
    return _paged_call(q, kt, vt, block_table, lens, start,
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
        return _paged_call(ql, kl, vl, block_table, lens,
                           start, softcap=softcap, scale=scale,
                           interpret=interpret)

    return jax.lax.map(one_layer, (q, k_pages, v_pages))

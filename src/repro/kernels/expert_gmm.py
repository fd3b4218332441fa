"""Grouped matmul over the experts a program holds (Pallas, TPU).

``expert_gmm(lhs, rhs, group_sizes)``: the rows of ``lhs`` [m, k] are
sorted by group, group g owning the next ``group_sizes[g]`` rows, and each
group's rows are multiplied by its matrix ``rhs[g]`` [k, n].  Rows past the
last group are left undefined; callers mask them.

The grid walks (n tiles, group-visited m tiles): an m tile of ``tm`` rows is
visited once by every group with rows in it, so a group's weights are read
once per tile it touches, and tiles past the last group are never visited
(the grid's length is traced, like megablox's).  Each visit multiplies the
whole tile at full k and stores only its group's rows.  The pallas call
is named ``expert_gmm``: in a device trace its ops read
``expert_gmm.<n> (tpu_custom_call)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

NAME = "expert_gmm"
TM = 128                  # rows a visit multiplies: one MXU pass on v5e
VMEM_LIMIT = 48 * 2**20


def _tile_n(n: int) -> int:
    for t in (1024, 512, 256, 128):
        if n > t and n % t == 0:
            return t
    return n


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def expert_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
               tm: int = TM, interpret: bool = False) -> jax.Array:
    """[m, k] rows sorted by group x [G, k, n] -> [m, n] in ``lhs.dtype``
    (float32 accumulation)."""
    m, k = lhs.shape
    G, _, n = rhs.shape
    m_pad = -(-m // tm) * tm
    lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    # rows of no held group make one trailing group the grid never visits
    sizes = jnp.concatenate(
        [group_sizes.astype(jnp.int32),
         (m_pad - jnp.sum(group_sizes)).astype(jnp.int32)[None]])
    meta, n_tiles = make_group_metadata(
        group_sizes=sizes, m=m_pad, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False)
    tn = _tile_n(n)

    def kernel(meta_ref, lhs_ref, rhs_ref, out_ref):
        offsets, group_ids, m_tile_ids = meta_ref
        i = pl.program_id(1)
        g = group_ids[i]
        acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)
        row = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
               + m_tile_ids[i] * tm)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])

    def lhs_map(j, i, meta):
        return meta[2][i], 0

    def rhs_map(j, i, meta):
        return meta[1][i], 0, j

    def out_map(j, i, meta):
        return meta[2][i], j

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, k), lhs_map),
                      pl.BlockSpec((None, k, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, n_tiles)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )
    return call(meta, lhs, rhs)[:m]

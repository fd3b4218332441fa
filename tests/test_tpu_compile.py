"""The main serving path compiled for a described TPU v5e, no chip attached.

Interpret-mode tests cannot see what the TPU's compiler refuses (block
shapes Mosaic cannot tile, programs that do not fit HBM).  These compile
the main-path kernels and jitted steps at published widths for one chip of
a described ``v5e:2x2`` topology and check the compiled memory analysis.
Nothing runs, so they say nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.types import hardware_spec_for
from repro.kernels import flash_decode as fd
from repro.models import model as M

PAGE = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def hbm_bytes(topo):
    return hardware_spec_for(topo.devices[0].device_kind).hbm_bytes


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _param_shapes(cfg, sharding):
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda x: _sds(sharding, x.shape, x.dtype), shapes)


@pytest.mark.parametrize("arch,B", [
    pytest.param("gemma2-2b", 8, id="gemma2-2b"),
    pytest.param("starcoder2-3b", 8, id="starcoder2-3b"),
    pytest.param("starcoder2-3b", 16, id="starcoder2-3b-codegen"),
    pytest.param("yi-9b", 8, id="yi-9b"),
])
def test_paged_decode_kernel_compiles(arch, B, one_chip, no_persistent_cache):
    """One kernel per geometry: group 2 / D 256, group 12 / D 128 (also at
    the codegen cell's B=16 over a 128-page table), group 8 / D 128.  The
    benchmark's kernel reading keys on the custom call's name."""
    cfg = get_config(arch)
    P, n_pages = 2048, 128
    Hq, Hkv, D = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    pool = _sds(one_chip, (P, Hkv, PAGE, D), jnp.bfloat16)
    compiled = jax.jit(fd.flash_decode_paged_native).lower(
        _sds(one_chip, (B, Hq, D), jnp.bfloat16), pool, pool,
        _sds(one_chip, (B, n_pages), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile()
    assert any("paged_decode_attention" in line
               for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)


def test_gemma2_decode_loop_kernel_path_fits(one_chip, hbm_bytes,
                                             no_persistent_cache):
    """The engine's fused decode loop, kernel path, B=8 over a pool of
    2,048 pages (32k tokens), pools donated as the engine donates them."""
    cfg = get_config("gemma2-2b")
    B, P, n_pages = 8, 2048, 128
    pool = _sds(one_chip, (cfg.n_layers, P + 1, cfg.n_kv_heads, PAGE,
                           cfg.head_dim), jnp.bfloat16)

    def loop(params, k, v, table, lens, toks, key):
        st = M.PagedDecodeState(k=k, v=v, block_table=table, lens=lens,
                                ssm=None, conv=None)
        out, st = M.decode_loop_paged(params, cfg, toks, st, key,
                                      jnp.int32(0), 1, attn_impl="kernel")
        return out, st.k, st.v

    compiled = jax.jit(loop, donate_argnums=(1, 2)).lower(
        _param_shapes(cfg, one_chip), pool, pool,
        _sds(one_chip, (B, n_pages), jnp.int32),
        _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (2,), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < hbm_bytes


def test_gemma2_prefill_compiles(one_chip, hbm_bytes, no_persistent_cache):
    cfg = get_config("gemma2-2b")
    compiled = jax.jit(lambda p, t: M.prefill(p, cfg, tokens=t)).lower(
        _param_shapes(cfg, one_chip),
        _sds(one_chip, (1, 2048), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < hbm_bytes


def test_olmoe_share_decode_loop_fits_with_no_pool_copy(one_chip, hbm_bytes,
                                                       no_persistent_cache):
    """The olmoe-1b-7b.chat cell's fused decode loop: one chip's share of
    EP=4 (16 of 64 experts), B=64 over a pool of 4,096 pages.  The grouped
    expert kernel is in it, and the pools are written and read in place:
    the program's own buffers stay far under one copy of them (4.3 GB)."""
    import dataclasses

    cfg = dataclasses.replace(get_config("olmoe-1b-7b"), experts_held=16)
    B, P, n_pages = 64, 4096, 64
    pool = _sds(one_chip, (cfg.n_layers, P + 1, cfg.n_kv_heads, PAGE,
                           cfg.head_dim), jnp.bfloat16)

    def loop(params, k, v, table, lens, toks, key):
        st = M.PagedDecodeState(k=k, v=v, block_table=table, lens=lens,
                                ssm=None, conv=None)
        out, st, route = M.decode_loop_paged(
            params, cfg, toks, st, key, jnp.int32(0), 1, attn_impl="kernel")
        return out, st.k, st.v, route

    compiled = jax.jit(loop, donate_argnums=(1, 2)).lower(
        _param_shapes(cfg, one_chip), pool, pool,
        _sds(one_chip, (B, n_pages), jnp.int32),
        _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (2,), jnp.uint32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any("expert_gmm" in line for line in calls)
    assert any("paged_decode_attention" in line for line in calls)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < hbm_bytes

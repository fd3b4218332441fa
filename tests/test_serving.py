"""Serving substrate: paged KV, engine exactness, router, simulator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.costmodel import CostModel
from repro.core.types import ClusterSpec, H100_SPEC, WorkloadType
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serving.baselines import OServePolicy, VLLMStaticPolicy
from repro.serving.engine import ServingEngine
from repro.serving.kvcache import BlockAllocator, PagedKVCache
from repro.serving.request import synthesize_trace
from repro.serving.router import FlowRouter, RoundRobinRouter
from repro.serving.simulator import simulate


def test_block_allocator_lifecycle():
    a = BlockAllocator(8)
    blocks = a.alloc(5)
    assert len(set(blocks)) == 5
    assert a.n_free == 3
    a.release(blocks[:2])
    assert a.n_free == 5
    with pytest.raises(MemoryError):
        a.alloc(6)


def test_paged_cache_roundtrip():
    cfg = get_smoke_config("yi-9b")
    cache = PagedKVCache.create(cfg, num_blocks=32, block_size=4, max_seqs=4)
    cache.admit(0, prompt_len=10)
    k = jnp.arange(cfg.n_layers * 10 * cfg.n_kv_heads * cfg.head_dim,
                   dtype=jnp.float32).reshape(cfg.n_layers, 10,
                                              cfg.n_kv_heads, cfg.head_dim)
    cache.write_prefill(0, k, k * 2)
    kd, vd, lens = cache.gather_dense(np.array([0]), 12)
    np.testing.assert_allclose(np.asarray(kd[:, 0, :10]), np.asarray(k))
    np.testing.assert_allclose(np.asarray(vd[:, 0, :10]), np.asarray(k * 2))
    assert int(lens[0]) == 10
    cache.release_slot(0)
    assert cache.allocator.n_free == 32


def test_engine_token_exact_vs_reference():
    cfg = get_smoke_config("olmoe-1b-7b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 10, 14)]
    news = [5, 3, 6]

    def ref_gen(prompt, n_new):
        lp, cache = prefill(params, cfg, jnp.asarray(prompt)[None])[:2]
        big = init_cache(cfg, 1, len(prompt) + n_new + 2, jnp.float32)
        if cache.k is not None:
            big.k = big.k.at[:, :, :len(prompt)].set(cache.k)
            big.v = big.v.at[:, :, :len(prompt)].set(cache.v)
        if cache.ssm is not None:
            big.ssm, big.conv = cache.ssm, cache.conv
        big.pos = cache.pos
        toks = [int(jnp.argmax(lp[0, :cfg.vocab_size]))]
        for _ in range(n_new - 1):
            lg, big = decode_step(params, cfg,
                                  jnp.asarray([toks[-1]], jnp.int32), big)
            toks.append(int(jnp.argmax(lg[0, :cfg.vocab_size])))
        return toks

    refs = [ref_gen(p, n) for p, n in zip(prompts, news)]
    eng = ServingEngine(cfg, params, num_blocks=64, block_size=8, max_seqs=2)
    for i, (p, n) in enumerate(zip(prompts, news)):
        eng.submit(i, p, n)
    done = {r.rid: r.generated for r in eng.run_to_completion()}
    for i in range(3):
        assert done[i] == refs[i]


def test_engine_continuous_batching_admits_as_slots_free():
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ServingEngine(cfg, params, num_blocks=64, block_size=8, max_seqs=2)
    for i in range(5):
        eng.submit(i, np.arange(8, dtype=np.int32) + i, 4)
    finished = eng.run_to_completion()
    assert len(finished) == 5
    assert eng.cache.allocator.n_free == 64   # all pages reclaimed


def test_flow_router_tracks_fractions():
    r = FlowRouter([[0.75, 0.0], [0.25, 1.0]])
    picks = [r.route(0) for _ in range(100)]
    frac0 = picks.count(0) / 100
    assert 0.7 <= frac0 <= 0.8
    assert all(r.route(1) == 1 for _ in range(10))


def test_round_robin_router_skips_down():
    r = RoundRobinRouter(3)
    up = np.array([True, False, True])
    picks = {r.route(0, up) for _ in range(6)}
    assert picks == {0, 2}


@pytest.fixture(scope="module")
def sim_setup():
    cfg = get_config("opt-30b")
    cm = CostModel(cfg.profile(), hw=H100_SPEC)
    cluster = ClusterSpec(16, hw=H100_SPEC)
    arch = [WorkloadType(1275, 287), WorkloadType(139, 133),
            WorkloadType(1181, 1824), WorkloadType(282, 1121)]
    reqs = synthesize_trace(6, 120, trace_id=2, seed=0)
    for r in reqs:
        # crude typing for the test
        r.type_id = int(r.out_len > 500) * 2 + int(r.in_len > 600)
    return cm, cluster, arch, reqs


def test_simulator_conservation(sim_setup):
    cm, cluster, arch, reqs = sim_setup
    avg = np.array([30.0, 30.0, 30.0, 30.0])
    pol = VLLMStaticPolicy(cm, cluster, arch, avg)
    res = simulate([r for r in reqs], pol, cm, arch, 6)
    done = sum(1 for r in res.requests if r.finish >= 0)
    assert done + res.dropped == len(reqs)
    for r in res.requests:
        if r.finish >= 0:
            assert r.finish >= r.start >= r.arrival - 1e-9
            assert r.first_token >= r.start


def test_simulator_oserve_runs(sim_setup):
    cm, cluster, arch, reqs = sim_setup
    pol = OServePolicy(cm, cluster, arch)
    res = simulate([r for r in reqs], pol, cm, arch, 6)
    m = res.metrics()
    assert m["completed"] > 0
    assert np.isfinite(m.get("p99", np.inf))

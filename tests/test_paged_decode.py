"""Device-resident paged decode: kernel parity + fused engine behavior.

Three layers of checks:
  * ``flash_decode_paged`` (interpret) vs dense ``flash_decode`` vs the jnp
    oracle across GQA group sizes, ragged lens, and softcaps;
  * the fused bucketed engine step vs the seed dense-gather engine,
    token-for-token under greedy sampling;
  * scheduling/compilation invariants: prefill no longer starves decode,
    and the fused step compiles O(log) distinct variants, not one per
    active-set size.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops, ref
from repro.models import init_params
from repro.serving.engine import ServingEngine

R = np.random.RandomState(7)


def arr(*shape, scale=0.5):
    return jnp.asarray(R.randn(*shape).astype(np.float32) * scale)


def _paged_case(B=3, pages=16, page=8, Hkv=2, group=4, D=64, maxp=4):
    Hq = Hkv * group
    q = arr(B, Hq, D)
    kp = arr(pages, page, Hkv, D)
    vp = arr(pages, page, Hkv, D)
    tbl = jnp.asarray(R.randint(0, pages, (B, maxp)), jnp.int32)
    lens = jnp.asarray(R.randint(1, maxp * page + 1, B), jnp.int32)
    return q, kp, vp, tbl, lens


def _gather(k_pages, tbl):
    k = k_pages[tbl]                      # [B, maxp, page, Hkv, D]
    B, n, p, H, D = k.shape
    return k.reshape(B, n * p, H, D)


@pytest.mark.parametrize("group", [1, 4, 8, 2, 12])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_vs_dense_vs_ref(group, softcap):
    q, kp, vp, tbl, lens = _paged_case(group=group)
    o_paged = ops.flash_decode_paged(q, kp, vp, tbl, lens, softcap=softcap,
                                     interpret=True)
    o_dense = ops.flash_decode(q, _gather(kp, tbl), _gather(vp, tbl), lens,
                               softcap=softcap, interpret=True)
    o_ref = ref.flash_decode_paged_ref(q, kp, vp, tbl, lens, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_ref),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(o_dense), np.asarray(o_ref),
                               atol=3e-5)


def test_paged_start_window_masks_head():
    """`start` lower bound (local/sliding-window layers) matches the oracle."""
    q, kp, vp, tbl, lens = _paged_case(B=4, maxp=4)
    start = jnp.asarray([0, 5, 17, 30], jnp.int32)
    start = jnp.minimum(start, jnp.maximum(lens - 1, 0))
    o = ops.flash_decode_paged(q, kp, vp, tbl, lens, start=start,
                               softcap=30.0, interpret=True)
    r = ref.flash_decode_paged_ref(q, kp, vp, tbl, lens, start=start,
                                   softcap=30.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=3e-5)


# (Hkv, group, D, page, table width, lens, start, softcap, dtype): a grid
# step covers min(256 tokens, table) of pages, so widths 1 and 2 give blocks
# smaller than the rule's, 32+ give the rule's; lengths of 1, ending
# mid-page and mid-block, a full table, and empty (padded) slots between
# live ones, across which the next block's copies are not started early
BLOCK_CASES = {
    "g2_d256_softcap_start": (2, 2, 256, 16, 64, [700, 300, 1024, 1],
                              [520, 0, 600, 0], 50.0, jnp.float32),
    "g8_d128": (1, 8, 128, 16, 32, [1, 17, 264, 512], None, 0.0,
                jnp.float32),
    "g12_d128": (2, 12, 128, 16, 128, [367, 1530, 2048], None, 0.0,
                 jnp.float32),
    "g12_d128_bf16": (2, 12, 128, 16, 128, [900, 31, 2048], None, 0.0,
                      jnp.bfloat16),
    "width1": (2, 4, 64, 16, 1, [1, 9, 16], None, 0.0, jnp.float32),
    "width2": (2, 4, 64, 16, 2, [5, 32, 17], None, 30.0, jnp.float32),
    "page8_width64": (2, 2, 64, 8, 64, [300, 255, 512, 8], None, 0.0,
                      jnp.float32),
    "empty_slots": (2, 4, 128, 16, 32, [0, 40, 0, 0, 300, 0], None, 0.0,
                    jnp.float32),
}


@pytest.mark.parametrize("case", list(BLOCK_CASES), ids=list(BLOCK_CASES))
def test_paged_blocks_vs_ref(case):
    Hkv, group, D, page, maxp, lens, start, softcap, dtype = BLOCK_CASES[case]
    B = len(lens)
    pages = B * maxp + 3
    q = arr(B, Hkv * group, D).astype(dtype)
    kp = arr(pages, page, Hkv, D).astype(dtype)
    vp = arr(pages, page, Hkv, D).astype(dtype)
    tbl = jnp.asarray(R.permutation(pages)[:B * maxp].reshape(B, maxp),
                      jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    if start is not None:
        start = jnp.asarray(start, jnp.int32)
    o = ops.flash_decode_paged(q, kp, vp, tbl, lens, start=start,
                               softcap=softcap, interpret=True)
    r = ref.flash_decode_paged_ref(q, kp, vp, tbl, lens, start=start,
                                   softcap=softcap)
    live = np.asarray(lens) > 0          # an empty window has no softmax
    atol = 3e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(o, np.float32)[live],
                               np.asarray(r, np.float32)[live], atol=atol)
    assert not np.any(np.isnan(np.asarray(o, np.float32)))


def test_paged_batch_entry_matches_per_layer():
    """Multi-layer entry (one pallas_call per layer, hoisted reshapes)."""
    L = 3
    q, kp, vp, tbl, lens = _paged_case()
    qL = jnp.stack([q * (0.5 + i) for i in range(L)])
    kn = jnp.swapaxes(kp, 1, 2)          # kernel-native [P, Hkv, page, D]
    vn = jnp.swapaxes(vp, 1, 2)
    kL = jnp.stack([kn * (1.0 + 0.1 * i) for i in range(L)])
    vL = jnp.stack([vn * (1.0 - 0.1 * i) for i in range(L)])
    out = ops.flash_decode_paged_batch(qL, kL, vL, tbl, lens, interpret=True)
    for i in range(L):
        r = ref.flash_decode_paged_ref(
            qL[i], jnp.swapaxes(kL[i], 1, 2), jnp.swapaxes(vL[i], 1, 2),
            tbl, lens)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(r),
                                   atol=3e-5)


def _run_engine(cfg, params, jobs, mode, max_seqs=2):
    eng = ServingEngine(cfg, params, num_blocks=64, block_size=8,
                        max_seqs=max_seqs, decode_mode=mode)
    for i, (p, n) in enumerate(jobs):
        eng.submit(i, p, n)
    return {r.rid: r.generated for r in eng.run_to_completion()}, eng


def test_fused_engine_matches_dense_engine_tokens():
    """Bucketed fused paged step == seed dense-gather engine, greedy."""
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.RandomState(1)
    jobs = [(rng.randint(0, cfg.vocab_size, n).astype(np.int32), new)
            for n, new in ((8, 4), (8, 6), (12, 3))]
    got_paged, _ = _run_engine(cfg, params, jobs, "paged")
    got_dense, _ = _run_engine(cfg, params, jobs, "dense")
    assert got_paged == got_dense


def test_fused_engine_matches_dense_engine_local_window():
    """gemma2-style local/global alternation through the paged start bound."""
    cfg = get_smoke_config("gemma2-2b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.RandomState(2)
    jobs = [(rng.randint(0, cfg.vocab_size, n).astype(np.int32), new)
            for n, new in ((8, 4), (8, 5))]
    got_paged, _ = _run_engine(cfg, params, jobs, "paged")
    got_dense, _ = _run_engine(cfg, params, jobs, "dense")
    assert got_paged == got_dense


def test_kernel_impl_engine_with_head_padded_pool():
    """attn_impl="kernel" pads head_dim once at pool allocation (TPU layout);
    the Pallas path (interpret on CPU) must match the jnp path token-for-
    token over the padded pool."""
    cfg = get_smoke_config("yi-9b")            # head_dim 32 -> pool padded
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.RandomState(5)
    jobs = [(rng.randint(0, cfg.vocab_size, 8).astype(np.int32), 4)
            for _ in range(2)]

    def run(impl):
        eng = ServingEngine(cfg, params, num_blocks=64, block_size=8,
                            max_seqs=2, attn_impl=impl)
        for i, (p, n) in enumerate(jobs):
            eng.submit(i, p, n)
        out = {r.rid: r.generated for r in eng.run_to_completion()}
        return out, eng

    got_kernel, eng = run("kernel")
    assert eng.cache.k.shape[-1] == 128        # pool allocated pre-padded
    got_jnp, _ = run("jnp")
    assert got_kernel == got_jnp


def test_mixed_prefill_decode_no_starvation():
    """Admitting prompts must not stall running decodes: every sequence that
    was active before a step gains exactly one token on that step."""
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ServingEngine(cfg, params, num_blocks=128, block_size=8, max_seqs=4)
    rng = np.random.RandomState(3)
    eng.submit(0, rng.randint(0, cfg.vocab_size, 8).astype(np.int32), 10)
    eng.step()                               # prefill request 0
    saw_mixed_step = False
    for i in range(1, 4):                    # staggered arrivals
        eng.submit(i, rng.randint(0, cfg.vocab_size, 8).astype(np.int32), 6)
        active_before = list(eng.active.values())
        counts = {r.rid: len(r.generated) for r in active_before}
        eng.step()
        if counts:
            saw_mixed_step = True            # prefill + decode in one step
        for r in active_before:
            assert len(r.generated) == counts[r.rid] + 1, (
                f"request {r.rid} starved during a prefill step")
    assert saw_mixed_step
    eng.run_to_completion()
    assert eng.cache.allocator.n_free == 128


def test_fused_step_compilations_bucketed():
    """Distinct fused-step compilations stay O(log max_seqs * log max_pages)
    — the active-set size must not leak into the jit cache key unbucketed."""
    cfg = get_smoke_config("yi-9b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ServingEngine(cfg, params, num_blocks=256, block_size=8, max_seqs=8)
    rng = np.random.RandomState(4)
    for i in range(11):                      # active set sweeps 1..8 and back
        n = 6 + (i % 5) * 2
        eng.submit(i, rng.randint(0, cfg.vocab_size, n).astype(np.int32),
                   3 + (i % 6))
    eng.run_to_completion()
    n_compiles = eng._fused._cache_size()
    decode_steps = eng.steps
    # batch buckets {1,2,4,8} x page buckets {1,2,4}: well under one-per-step
    assert n_compiles <= 12, n_compiles
    assert decode_steps > n_compiles


def test_run_decode_is_gather_free():
    src = inspect.getsource(ServingEngine._run_decode)
    assert "gather_dense" not in src

"""The expert family on the CPU at a test size: its layout is the
program's, a sound run through ``ClusterRuntime`` agrees with its reference
within the file's limit, three faults of an expert model fail there, its
counts are the hand counts, and the expert layer's reader reads known
values.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import check  # noqa: E402
import counts  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402
import xplane  # noqa: E402
from drive import Record  # noqa: E402
from peaks import peaks_for  # noqa: E402

from repro.serving.telemetry import Event  # noqa: E402

SEED = 2**31 + 5
SECONDS = 3.0
DATA = HERE / "tests" / "data"


def config(path=DATA / "tiny_olmoe.json"):
    c = spec.load_json(path)
    c["name"] = Path(path).stem
    return c


def tiny_cell():
    m = spec.load_json(DATA / "tiny_closed.json")
    m["name"] = "tiny_closed"
    return spec.Cell("tiny_olmoe.tiny_closed", config(), m, 1, [], [])


def serve():
    return run.serve(tiny_cell(), SEED, SECONDS, False, jax.devices()[:1],
                     None, "TPU v5 lite")


@pytest.mark.parametrize("path", [DATA / "tiny_olmoe.json",
                                  HERE / "configs" / "olmoe-1b-7b.json"])
def test_the_layout_is_the_programs(path):
    c = config(path)
    cfg = spec.program_config(c)
    weights.check_layout(c, cfg)
    assert (cfg.n_held, cfg.n_experts, cfg.top_k) == (
        c["num_experts"], c["num_router_experts"], c["num_experts_per_tok"])
    assert not cfg.norm_topk_prob and cfg.qk_norm_scope == "proj"


def test_the_benchmark_config_is_one_chips_share_of_ep4():
    c = config(HERE / "configs" / "olmoe-1b-7b.json")
    assert c["reduced"] == ["num_experts"]
    assert (c["num_experts"], c["published"]["num_experts"],
            c["num_router_experts"]) == (16, 64, 64)
    assert counts.params(c) == 2_087_323_648
    with pytest.raises(ValueError):
        spec.program_config(dict(c, num_router_experts=16))


@pytest.fixture(scope="module")
def sound():
    return serve()


def test_a_sound_run_agrees_with_the_reference(sound):
    assert check.is_correct(sound["compared"]), sound["compared"]
    assert len(sound["picked"]) == 8


def renormalised(monkeypatch):
    """The top-k weights renormalised, as granite has them."""
    import repro.models.moe as moe_lib
    real = moe_lib.route

    def route(xt, p, cfg):
        w, idx, probs = real(xt, p, cfg)
        return w / w.sum(-1, keepdims=True), idx, probs
    monkeypatch.setattr(moe_lib, "route", route)


def per_head_qk_norm(monkeypatch):
    """QK-norm over each head, the whole-projection weights cut to heads."""
    import repro.models.attention as attn_lib
    real = attn_lib._project_qkv

    def project(x, p, cfg, positions):
        head = dataclasses.replace(cfg, qk_norm_scope="head")
        ph = dict(p, q_norm=p["q_norm"].reshape(cfg.n_q_heads, -1),
                  k_norm=p["k_norm"].reshape(cfg.n_kv_heads, -1))
        return real(x, ph, head, positions)
    monkeypatch.setattr(attn_lib, "_project_qkv", project)


def every_held_expert(monkeypatch):
    """Every held expert on every token, weighted by its probability."""
    import repro.models.moe as moe_lib
    real = moe_lib.route

    def route(xt, p, cfg):
        _, idx, probs = real(xt, p, cfg)
        slots = jnp.arange(cfg.top_k)
        held = cfg.expert_offset + slots % cfg.n_held
        w = jnp.where(slots < cfg.n_held, probs[:, held], 0.0)
        return w, jnp.broadcast_to(held, idx.shape), probs
    monkeypatch.setattr(moe_lib, "route", route)


@pytest.mark.parametrize("fault", [renormalised, per_head_qk_norm,
                                   every_held_expert])
def test_a_fault_of_an_expert_model_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = serve()
    assert not check.is_correct(out["compared"]), out["compared"]


def test_the_counts_are_the_hand_counts():
    """tiny_olmoe: L 2, d 64, 4 heads of 16 (MHA), ff 32, 4 of 16 experts
    held, top 2, vocab 256, untied: a token passes 2 x 4 / 16 = 0.5
    experts here."""
    c = config()
    attn = 4 * 64 * 64                        # q, k, v, o
    qk_norm = 64 + 64
    router = 64 * 16
    expert = 3 * 64 * 32
    layer = attn + qk_norm + router + 4 * expert + 2 * 64
    assert counts.params(c) == 2 * 256 * 64 + 2 * layer + 64
    assert counts.matmul_params(c) == (2 * (attn + router + 0.5 * expert)
                                       + 64 * 256)
    assert counts.kv_bytes_per_token(c) == 2 * 2 * 64 * 2
    assert counts.token_flops(c, 10) == (2.0 * counts.matmul_params(c)
                                         + 4 * 2 * 64 * 10)
    assert counts.prefill_flops(c, 3) == (6.0 * counts.matmul_params(c)
                                          + 4 * 2 * 64 * 6)
    assert counts.decode_attn_bytes(c, 10) == 2 * (2 * 10 * 64 + 2 * 64) * 2


def reader_ctx():
    """A 10-s window with two decode dispatches of 64 tokens (one of them
    a horizon of 2) and one prefill of 200 tokens, and one outside it."""
    route = [Event("moe_route", 1.0, -1, 0, {
                 "phase": "prefill", "tokens": 200, "steps": 1,
                 "routed_here": 1000, "max_load": 90}),
             Event("moe_route", 2.0, -1, 0, {
                 "phase": "decode", "tokens": 64, "steps": 1,
                 "routed_here": 256, "max_load": 20}),
             Event("moe_route", 3.0, -1, 0, {
                 "phase": "decode", "tokens": 128, "steps": 2,
                 "routed_here": 512, "max_load": 21}),
             Event("moe_route", 11.0, -1, 0, {
                 "phase": "decode", "tokens": 64, "steps": 1,
                 "routed_here": 999, "max_load": 64})]
    red = xplane.Reduction(window_s=10.0, chips=1, busy_s=7.5, op_s={},
                           module_s={}, module_n={}, kernel_s={}, gaps=[])
    return {"rec": Record(flights=[], t0=0.0, t1=10.0), "red": red,
            "events": route, "config": config(HERE / "configs" /
                                              "olmoe-1b-7b.json"),
            "traffic": {}, "peaks": peaks_for("TPU v5 lite"), "chips": 1}


def test_the_expert_readers_on_a_synthetic_window():
    """Decode events inside the window only: (256 + 512) pairs over 16
    held experts x 16 layers x 3 steps."""
    got = spec.load_metric("moe_tokens_per_expert").compute(reader_ctx())
    assert got == pytest.approx((256 + 512) / (16 * 16 * 3))


def test_the_expert_readers_with_nothing_to_read_return_none():
    ctx = reader_ctx()
    ctx["events"] = [e for e in ctx["events"] if e.data["phase"] != "decode"]
    assert spec.load_metric("moe_tokens_per_expert").compute(ctx) is None
    ctx["events"] = []
    assert spec.load_metric("moe_tokens_per_expert").compute(ctx) is None

"""OLMoE-1B-7B [arXiv:2409.02060; hf:allenai/OLMoE-1B-7B-0924]: 16L, d=2048,
16H MHA, 64 experts top-8.

d_ff=1024 is the per-expert FFN width; ~1.3B active / ~6.9B total params.
The router is a softmax over all 64 experts, then top-8, and the top-8
weights are NOT renormalised (``norm_topk_prob: false``).  QK-norm is an
RMSNorm over the whole q projection and the whole k projection (weights of
2,048 each), applied before the split into heads.  No biases, untied head.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b",
    family="moe",
    n_layers=16,
    d_model=2048,
    n_q_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=1024,
    vocab_size=50304,
    n_experts=64,
    top_k=8,
    norm_topk_prob=False,
    qk_norm=True,
    qk_norm_scope="proj",
    norm_eps=1e-5,
    rope_theta=10_000.0,
    tie_embeddings=False,
    # under a mesh the held experts' stack shards over the model axis
    # (64 / 16-way = 4 per shard); a one-chip share of an expert-parallel
    # deployment sets experts_held / expert_offset instead
    expert_sharding="ep",
    # hillclimb-adopted (EXPERIMENTS.md SPerf cell A): at 16L x d=2048 the
    # sequence-parallel residual costs more in collectives than it saves
    seq_parallel=False,
)

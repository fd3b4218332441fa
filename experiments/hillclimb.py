"""Perf hillclimb driver: named variants per cell, unrolled re-lower+compile,
terms recorded to experiments/hillclimb/<cell>__<variant>.json."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # CPU dry run: never take the chip
import json
import sys

sys.path.insert(0, "src")
from repro.launch.dryrun import run_cell  # noqa: E402

VARIANTS = {
    # rolled baselines for ratio comparisons
    ("olmoe-1b-7b@base", "train_4k"): {"baseline": dict()},
    ("qwen1.5-110b@base", "decode_32k"): {"baseline": dict()},
    ("gemma2-2b@base", "prefill_32k"): {"baseline": dict()},
    # A) olmoe train_4k: collective-bound (11.1s vs 6.3s memory)
    ("olmoe-1b-7b", "train_4k"): {
        "nosp_nofsdp": dict(fsdp=False, extra_rules={"act_seq": None}),
        "noremat": dict(remat=False),
        "nofsdp": dict(fsdp=False),
        "nosp": dict(extra_rules={"act_seq": None}),
        "expert_tp": dict(cfg_overrides={"expert_sharding": "tp"}),
        "nosp_noremat": dict(remat=False, extra_rules={"act_seq": None}),
        # round 2 (was a separate dict entry; merged — completed variants
        # are skipped via their recorded JSONs, so re-listing is free)
        "nosp_v2_nofsdp": dict(fsdp=False, extra_rules={"act_seq": None}),
    },
    # B) qwen decode_32k: collective-bound (4.0s vs 1.5s memory) from FSDP
    #    weight gathers; replicate the small batch + shard KV seq 2D instead
    ("qwen1.5-110b", "decode_32k"): {
        "repl_batch_kv2d": dict(extra_rules={
            "batch": None, "kv_seq": ("data", "model")}),
        "kv2d_only": dict(extra_rules={"kv_seq": ("data", "model")}),
        "nofsdp_kv2d": dict(fsdp=False, extra_rules={
            "batch": None, "kv_seq": ("data", "model")}),
        # fp8 KV cache halves KV bytes AND lets the weights fit without
        # FSDP row-sharding -> no per-step weight all-gathers at all
        "nofsdp_f8kv": dict(fsdp=False, cache_dtype="f8"),
        "f8kv": dict(cache_dtype="f8"),
    },
    # C) gemma2 prefill_32k: worst memory term (29.2s) from replicated attn
    ("gemma2-2b", "prefill_32k"): {
        "pad_heads": dict(cfg_overrides={"attn_sharding": "pad"}),
        "pad_heads_fsdp": dict(cfg_overrides={"attn_sharding": "pad"},
                               fsdp=True),
    },
}


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else None
    os.makedirs("experiments/hillclimb", exist_ok=True)
    for (arch, shape), variants in VARIANTS.items():
        for vname, kwargs in variants.items():
            tag = f"{arch}_{shape}__{vname}"
            if only and only not in tag:
                continue
            path = f"experiments/hillclimb/{tag}.json"
            if os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        print(f"EXISTS {tag}")
                        continue
            print(f"=== {tag} ===", flush=True)
            unroll = os.environ.get("HILLCLIMB_UNROLL", "0") == "1"
            import jax.numpy as jnp
            if kwargs.get("cache_dtype") == "f8":
                kwargs = dict(kwargs, cache_dtype=jnp.float8_e4m3fn)
            rec = run_cell(arch.split("@")[0], shape, multi_pod=False, out_dir=None,
                           verbose=False, unroll=unroll, **kwargs)
            rec["variant"] = vname
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec.get("ok"):
                coll = rec["collectives"]["total_bytes"]
                print(f"  ok compile={rec.get('compile_s')}s "
                      f"flops/dev={rec['per_device_flops']:.3e} "
                      f"bytes/dev={rec['per_device_bytes']:.3e} "
                      f"coll/dev={coll/1e9:.2f}GB", flush=True)
            else:
                print(f"  FAIL {rec.get('error')}", flush=True)


if __name__ == "__main__":
    main()

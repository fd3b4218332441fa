"""Serve at published widths on a TPU through the normal entry points.

    python chip_smoke.py             # gemma2-2b on one chip, Pallas decode kernel
    python chip_smoke.py --chips 4   # yi-9b over four chips: sharded replicas
                                     # and a live switch that reshards KV

Both phases drive ``Orchestrator.plan_span`` -> ``ClusterRuntime.apply_plan``
/ ``submit`` / ``step`` / ``run_until_idle`` / ``finish_span`` with random
bf16 weights made from ``--seed``, then check the served tokens against
``models.forward`` teacher-forced over prompt + served tokens.

Exits non-zero, with no result line, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The times printed are smoke numbers from one short run, not benchmark
numbers.  One process from start to finish: it starts no children.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAGE = 16
N_REQUESTS = 8
MIN_AGREEMENT = 0.99


class SmokeFailure(RuntimeError):
    """A check failed: the run must exit non-zero."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_or_exit(chips: int) -> dict:
    """The device as JAX reports it; anything but ``chips`` TPUs ends the
    run here, before a single compile."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {d.platform!r}, not a "
                 f"TPU; this check runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- traffic ------------------------------------------------------------------


def make_requests(rng: np.random.RandomState, vocab: int, n: int,
                  prompt_lens: tuple, new_tokens: tuple,
                  rid0: int = 0) -> list[tuple]:
    """``n`` requests (rid, type_id, prompt, max_new); the type is the index
    of the prompt length, so one-shot prefill compiles once per length."""
    out = []
    for i in range(n):
        t = int(rng.randint(len(prompt_lens)))
        prompt = rng.randint(0, vocab, prompt_lens[t]).astype(np.int32)
        new = int(rng.randint(new_tokens[0], new_tokens[1] + 1))
        out.append((rid0 + i, t, prompt, new))
    return out


def mix_of(reqs: list[tuple], prompt_lens: tuple) -> list:
    """The planner's forecast for a batch: one type per prompt length, its
    mean output length, its count as the rate."""
    from repro.core.types import WorkloadType
    ws = []
    for t, pl in enumerate(prompt_lens):
        news = [r[3] for r in reqs if r[1] == t]
        ws.append(WorkloadType(pl, int(np.mean(news)) if news else 1,
                               float(len(news))))
    return ws


# -- the served path ------------------------------------------------------------


def log_params(params) -> None:
    import jax
    leaves = jax.tree.leaves(params)
    log(f"params: {sum(x.size for x in leaves) / 1e9:.3f}B, "
        f"{sum(x.nbytes for x in leaves) / 1e9:.3f} GB, "
        f"dtypes={sorted({str(x.dtype) for x in leaves})}")


def replica_lines(rt) -> list[tuple]:
    """(attn_impl, interpret) per live replica, each logged."""
    out = []
    for h in rt.replicas:
        e = h.engine
        log(f"  replica {h.index} {h.rc}: attn_impl={e.attn_impl} "
            f"interpret={e.interpret} max_seqs={e.max_seqs} "
            f"kv_blocks={e.cache.num_blocks}")
        out.append((e.attn_impl, e.interpret))
    return out


def check_switch(report, span: int) -> None:
    log(f"  switch (span {span}): changed={report.changed} "
        f"drained={report.drained} migrated={report.migrated} "
        f"handoff={report.handoff} copied={report.copied} "
        f"pages_copied={report.pages_copied} "
        f"reprefilled={report.reprefilled} requeued={report.requeued} "
        f"rolled_back={report.rolled_back}")
    check(not report.rolled_back,
          f"span {span} switch rolled back: {report.failure}")


def check_clean(rt, reqs: list[tuple]) -> int:
    """Every request finished with its full token count, nothing shed, no
    replica dead; returns the tokens served."""
    missing = [r[0] for r in reqs if r[0] not in rt.results]
    check(not missing, f"requests never finished: {missing}")
    short = [r[0] for r in reqs if len(rt.results[r[0]].generated) != r[3]]
    check(not short, f"requests finished short of max_new_tokens: {short}")
    check(rt.total_shed == 0, f"shed requests: {rt.all_shed_rids}")
    check(not rt.dead_replicas, f"dead replicas: {rt.dead_replicas}")
    rolled = [i for i, s in enumerate(rt.switch_reports) if s.rolled_back]
    check(not rolled, f"rolled-back switches: {rolled}")
    log(f"requests completed: {len(reqs)}/{len(reqs)}, shed=0, "
        f"dead_replicas=0, rolled_back_switches=0")
    return sum(r[3] for r in reqs)


# -- the reference --------------------------------------------------------------


def _bucket(n: int, mult: int = 128) -> int:
    return -(-n // mult) * mult


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg):
    """jit: tokens [1, S] -> (argmax [S], top-1/top-2 gap [S], logits [S, V])
    of ``models.forward`` over the true vocab."""
    import jax
    import jax.numpy as jnp

    from repro.models import forward

    def ref(params, tokens):
        logits = forward(params, cfg, tokens=tokens)[0]
        logits = logits[:, :cfg.vocab_size]
        top, _ = jax.lax.top_k(logits, 2)
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                top[:, 0] - top[:, 1], logits)
    return jax.jit(ref)


def teacher_forced(params, cfg, prompt: np.ndarray, served: list):
    """Reference argmax, top-2 gap and logits at the positions that chose
    each served token (position P-1+i predicts served[i]).  The sequence is
    padded at its end to a 128 bucket: causal attention keeps the padding
    out of every position read here, and the padding keeps compiles few."""
    P, N = len(prompt), len(served)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    buf = np.zeros((1, _bucket(len(seq))), np.int32)
    buf[0, :len(seq)] = seq
    am, gap, logits = _reference_fn(cfg)(params, buf)
    sl = slice(P - 1, P - 1 + N)
    return np.asarray(am[sl]), np.asarray(gap[sl]), logits[sl]


def agreement(cfg, out: dict) -> None:
    """Teacher-forced agreement of the served tokens with ``models.forward``
    on the same bf16 params.

    The margin comes from a measured logit error: ``forward`` in bf16
    against ``forward`` on the same weights upcast to float32, over the
    longest-output request.  Two bf16 paths that order their arithmetic
    differently (the served decode steps, the reference's one pass) can
    disagree on the argmax only where the top-1/top-2 gap is within their
    rounding error, so agreement is counted at the positions whose gap
    exceeds twice the measured error.  ``out`` is the phase's result; its
    bf16 params are dropped before the float32 copy is made.
    """
    import jax
    import jax.numpy as jnp

    served, reqs = out["served"], out["reqs"]
    probe = max(reqs, key=lambda r: (r[3], -len(r[2])))
    same, gaps = [], []
    for rid, _, prompt, _ in reqs:
        toks = np.asarray(served[rid])
        am, gap, logits = teacher_forced(out["params"], cfg, prompt,
                                         list(toks))
        same.append(am == toks)
        gaps.append(gap)
        if rid == probe[0]:
            ref16 = np.asarray(logits, np.float32)
        del logits
    leaves, tree = jax.tree.flatten(out.pop("params"))
    leaves = [leaves.pop(0).astype(jnp.float32) for _ in range(len(leaves))]
    params32 = jax.tree.unflatten(tree, leaves)
    del leaves
    with jax.default_matmul_precision("highest"):   # true f32 matmuls
        ref32 = np.asarray(teacher_forced(params32, cfg, probe[2],
                                          served[probe[0]])[2], np.float32)
    del params32
    err = float(np.max(np.abs(ref16 - ref32)))
    margin = 2.0 * err
    same, kept = np.concatenate(same), np.concatenate(gaps) > margin
    n_all, n_kept = len(same), int(kept.sum())
    frac = float((same & kept).sum()) / max(n_kept, 1)
    log(f"logit error (forward bf16 vs float32 weights, rid {probe[0]}, "
        f"{len(served[probe[0]])} positions): max_abs={err!r}")
    log(f"teacher-forced agreement: {frac!r} over {n_kept}/{n_all} "
        f"positions with top-2 gap > margin={margin!r} "
        f"(exact over all positions: {int(same.sum())}/{n_all})")
    for f in (0.0, 1.0, 4.0):                  # how the choice of 2x bites
        k = np.concatenate(gaps) > f * err
        log(f"  at margin {f} x error: {int((same & k).sum())}/"
            f"{int(k.sum())} agree")
    check(4 * n_kept >= n_all,
          f"margin {margin!r} leaves only {n_kept}/{n_all} positions")
    check(frac >= MIN_AGREEMENT,
          f"agreement {frac!r} < {MIN_AGREEMENT} at margin {margin!r}")


# -- phases ----------------------------------------------------------------------


def one_chip_phase(cfg, spec, seed: int, *, blocks_per_chip: int,
                   seqs_per_chip: int, prompt_lens: tuple,
                   new_tokens: tuple) -> dict:
    """One replica on one chip: plan, apply, serve a seeded batch twice
    (the first pass compiles every shape, the second is timed), check."""
    import jax
    import jax.numpy as jnp

    from repro.core.costmodel import CostModel
    from repro.core.orchestrator import Orchestrator
    from repro.core.types import ClusterSpec
    from repro.models import init_params
    from repro.serving.cluster import ClusterRuntime

    t0 = time.perf_counter()
    params = jax.jit(functools.partial(init_params, cfg,
                                       dtype=jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    log_params(params)
    orch = Orchestrator(CostModel(cfg.profile(), hw=spec),
                        ClusterSpec(1, hw=spec))
    rt = ClusterRuntime(cfg, params, orch, blocks_per_chip=blocks_per_chip,
                        seqs_per_chip=seqs_per_chip, block_size=PAGE,
                        dtype=jnp.bfloat16, seed=seed)
    rng = np.random.RandomState(seed)
    reqs = make_requests(rng, cfg.vocab_size, N_REQUESTS, prompt_lens,
                         new_tokens)
    plan = orch.plan_span(mix_of(reqs, prompt_lens))
    log(f"span 0 plan: {plan.deployment}")
    check_switch(rt.apply_plan(plan), 0)
    replicas = replica_lines(rt)

    def serve(batch):
        for rid, t, prompt, new in batch:
            rt.submit(rid, prompt, new, type_id=t)
        rt.run_until_idle()

    serve(reqs)                                       # compiles every shape
    jax.block_until_ready(rt.pool.k)
    setup_s = time.perf_counter() - t0
    again = [(rid + N_REQUESTS, t, p, n) for rid, t, p, n in reqs]
    t1 = time.perf_counter()
    serve(again)
    jax.block_until_ready(rt.pool.k)
    serve_s = time.perf_counter() - t1
    rt.finish_span()
    tokens = check_clean(rt, reqs + again)
    repeat = [r[0] for r in reqs if rt.results[r[0]].generated
              != rt.results[r[0] + N_REQUESTS].generated]
    check(not repeat, f"second pass served other tokens for {repeat}")
    log(f"smoke numbers (one short run, not a benchmark): "
        f"setup_s={setup_s:.3f} (params, plan, first pass with compiles) "
        f"serve_s={serve_s:.3f} for {tokens // 2} tokens "
        f"tokens_per_s={tokens / 2 / serve_s:.1f}")
    served = {r[0]: list(rt.results[r[0]].generated) for r in reqs}
    del rt
    gc.collect()
    return {"replicas": replicas, "params": params, "reqs": reqs,
            "served": served}


# Forecasts for the two spans of the four-chip phase: decode-heavy traffic
# (short prompts, long outputs) and then prompt-heavy traffic.  For yi-9b
# on four v5e chips the planner serves the first on one tp=4 replica and
# the second on two tp=2 replicas, so the switch between them moves the
# in-flight KV across meshes.  The requests actually sent are a short
# sample of each type.
SPAN_MIXES = (((128, 2048, 150.0), (512, 64, 8.0)),
              ((128, 2048, 9.0), (512, 64, 900.0)))


def four_chip_phase(cfg, spec, seed: int) -> dict:
    """Sharded replicas over four devices: span 0 on the planner's first
    deployment, requests left in flight, then a switch to its second
    deployment that reshards their KV, served to the end.

    The master params are made directly in the tp=4 replica's layout, so a
    tp=4 replica places them without a copy."""
    import jax
    import jax.numpy as jnp

    from repro.core.costmodel import CostModel
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    from repro.core.types import ClusterSpec, WorkloadType
    from repro.launch.mesh import make_replica_mesh
    from repro.launch.sharding import make_plan, named, param_pspecs
    from repro.models import init_params
    from repro.serving.cluster import ClusterRuntime

    devices = jax.devices()[:4]
    t0 = time.perf_counter()
    plan4, run_cfg = make_plan(cfg, "serve", False, 1, tp=4)
    check(run_cfg == cfg, "tp=4 pads this config's heads; the master "
          "layout would not match the replicas'")
    mesh = make_replica_mesh(devices, 4)
    params = jax.jit(
        functools.partial(init_params, cfg, dtype=jnp.bfloat16),
        out_shardings=named(mesh, param_pspecs(cfg, plan4)))(
        jax.random.PRNGKey(seed))
    log_params(params)
    # pure tp: a pp replica shards the layer axis that the decode scan
    # walks, which GSPMD serves by gathering every layer onto every chip
    orch = Orchestrator(CostModel(cfg.profile(), hw=spec),
                        ClusterSpec(4, hw=spec), OrchestratorConfig(max_pp=1))
    rt = ClusterRuntime(cfg, params, orch, blocks_per_chip=192,
                        block_size=PAGE, dtype=jnp.bfloat16, seed=seed,
                        shard=True, devices=devices)
    rng = np.random.RandomState(seed)
    reqs = make_requests(rng, cfg.vocab_size, N_REQUESTS, (128, 512),
                         (32, 64))
    deployments, replicas = [], []
    for span, mix in enumerate(SPAN_MIXES):
        plan = orch.plan_span([WorkloadType(i, o, r) for i, o, r in mix])
        log(f"span {span} plan: {plan.deployment}")
        report = rt.apply_plan(plan)
        check_switch(report, span)
        replicas += replica_lines(rt)
        deployments.append(plan.deployment)
        if span == 0:
            for rid, t, prompt, new in reqs:
                rt.submit(rid, prompt, new, type_id=t)
            for _ in range(8):              # until every request decodes
                rt.step()
                engines = [h.engine for h in rt.replicas]
                if not any(e.waiting for e in engines) and all(
                        len(r.generated) > 1 for e in engines
                        for r in e.active.values()):
                    break
        else:
            check(report.copied >= 1 and report.pages_copied >= 1,
                  "the switch moved no in-flight KV between meshes")
            rt.run_until_idle()
        rt.finish_span()
    check(deployments[0].replicas != deployments[1].replicas,
          f"the planner kept {deployments[0]}: no switch to exercise")
    check_clean(rt, reqs)
    served = {r[0]: list(rt.results[r[0]].generated) for r in reqs}
    log(f"smoke numbers (one short run, not a benchmark): "
        f"setup_and_serve_s={time.perf_counter() - t0:.3f} "
        f"(params, two spans with compiles and one switch)")
    del rt
    gc.collect()
    return {"replicas": replicas, "params": params, "reqs": reqs,
            "served": served, "mesh": mesh, "rules": plan4.rules}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: gemma2-2b on one chip (default); 4: yi-9b "
                         "sharded over four chips, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = device_or_exit(args.chips)

    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    from repro.configs import get_config
    from repro.core.types import hardware_spec_for
    from repro.pshard import sharding_rules

    spec = hardware_spec_for(device["kind"])
    log(f"hardware spec: {spec.name} (from device kind {device['kind']!r})")
    cfg = get_config("gemma2-2b" if args.chips == 1 else "yi-9b")
    log(f"model: {cfg.name} unreduced, bf16: layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_q_heads}/{cfg.n_kv_heads} "
        f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    if args.chips == 1:
        out = one_chip_phase(cfg, spec, args.seed, blocks_per_chip=2048,
                             seqs_per_chip=8, prompt_lens=(128, 512, 1024),
                             new_tokens=(32, 64))
        impls = set(out["replicas"])
        check(impls == {("kernel", False)},
              f"replicas resolved {impls}, not the compiled Pallas kernel")
        log("attn_impl=kernel interpret=False on every replica")
        agreement(cfg, out)
    else:
        out = four_chip_phase(cfg, spec, args.seed)
        impls = {impl for impl, _ in out["replicas"]}
        check(impls == {"jnp"}, f"sharded replicas resolved {impls}")
        log("attn_impl=jnp on every sharded replica (the Pallas kernel is "
            "not shard_map-wired)")
        with sharding_rules(out["mesh"], out["rules"]):
            agreement(cfg, out)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU dry run over virtual devices: never take the chip (its subprocesses
# would then find it held)
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import (jax locks the device
# count at first init).  Everything below is ordinary code.

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, get_config          # noqa: E402
from repro.launch import sharding as shd                      # noqa: E402
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.launch.shapes import SHAPES, applicable            # noqa: E402
from repro.models import config as mcfg                       # noqa: E402
from repro.models.model import (DecodeCache, decode_step,     # noqa: E402
                                init_cache, init_params, prefill)
from repro.pshard import sharding_rules                       # noqa: E402
from repro.train.trainer import (TrainConfig, init_train_state,  # noqa: E402
                                 make_train_step)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# --------------------------------------------------------------------------
# Inputs (ShapeDtypeStruct stand-ins; no allocation).
# --------------------------------------------------------------------------


def input_specs(cfg: mcfg.ModelConfig, shape_name: str,
                cache_dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStructs for every model input of the given shape cell."""
    sh = SHAPES[shape_name]
    B, S = sh.global_batch, sh.seq_len
    sds = jax.ShapeDtypeStruct
    use_embeds = cfg.modality == "audio_stub"
    if sh.kind == "train":
        batch = {"labels": sds((B, S), jnp.int32)}
        if use_embeds:
            batch["embeds"] = sds((B, S, cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = sds((B, S), jnp.int32)
        return {"batch": batch}
    if sh.kind == "prefill":
        if use_embeds:
            return {"embeds": sds((B, S, cfg.d_model), jnp.bfloat16)}
        return {"tokens": sds((B, S), jnp.int32)}
    # decode: one new token against a cache of S tokens
    cache = jax.eval_shape(
        lambda: init_cache(cfg, B, S, cache_dtype))
    out = {"cache": cache}
    if use_embeds:
        out["embeds"] = sds((B, 1, cfg.d_model), jnp.bfloat16)
    else:
        out["tokens"] = sds((B,), jnp.int32)
    return out


# --------------------------------------------------------------------------
# Cell construction: (fn, example args, in/out shardings).
# --------------------------------------------------------------------------


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               fsdp: bool | None = None, tp: int = 16,
               remat: bool = True, extra_rules: dict | None = None,
               unroll: bool = False, cfg_overrides: dict | None = None,
               cache_dtype=jnp.bfloat16):
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    if unroll:
        cfg = _dc.replace(cfg, scan_unroll=cfg.n_layers)
    sh = SHAPES[shape_name]
    ok, reason = applicable(cfg, sh)
    if not ok:
        raise ValueError(f"skip: {reason}")
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan, run_cfg = shd.make_plan(cfg, sh.kind, multi_pod, sh.global_batch,
                                  tp=tp, fsdp=fsdp)
    if extra_rules:
        plan.rules.update(extra_rules)
    pspecs = shd.param_pspecs(run_cfg, plan)
    batch_axes = plan.rules["batch"]
    ins = input_specs(run_cfg, shape_name, cache_dtype)

    if sh.kind == "train":
        tcfg = TrainConfig(remat=remat, param_dtype=jnp.float32,
                           microbatches=1)
        fn = make_train_step(run_cfg, tcfg)
        state_sds = jax.eval_shape(
            lambda: init_train_state(run_cfg, jax.random.PRNGKey(0), tcfg))
        state_specs = {"params": pspecs, "opt": shd.opt_pspecs(pspecs)}
        batch_specs = jax.tree.map(
            lambda x: P(batch_axes, *([None] * (len(x.shape) - 1))),
            ins["batch"])
        metrics_specs = {k: P() for k in
                         ("nll", "accuracy", "tokens", "aux_loss",
                          "grad_norm", "lr")}
        args = (state_sds, ins["batch"])
        in_specs = (state_specs, batch_specs)
        out_specs = (state_specs, metrics_specs)
        donate = (0,)
    elif sh.kind == "prefill":
        def fn(params, inputs):
            return prefill(params, run_cfg,
                           tokens=inputs.get("tokens"),
                           embeds=inputs.get("embeds"))[:2]
        in_batch = {k: v for k, v in ins.items()}
        in_specs = (pspecs, jax.tree.map(
            lambda x: P(batch_axes, *([None] * (len(x.shape) - 1))), in_batch))
        cache_specs = shd.cache_pspecs(run_cfg, plan)
        # prefill produces the cache already sequence-sharded for decode
        out_specs = (P(batch_axes, plan.rules["vocab"]), cache_specs)
        params_sds = jax.eval_shape(
            lambda: init_params(run_cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        args = (params_sds, in_batch)
        donate = ()
    else:  # decode
        def fn(params, inputs, cache):
            return decode_step(params, run_cfg, inputs.get("tokens"),
                               cache, embeds=inputs.get("embeds"))
        cache_specs = shd.cache_pspecs(run_cfg, plan)
        tok = {k: v for k, v in ins.items() if k != "cache"}
        tok_specs = jax.tree.map(
            lambda x: P(batch_axes, *([None] * (len(x.shape) - 1))), tok)
        params_sds = jax.eval_shape(
            lambda: init_params(run_cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        in_specs = (pspecs, tok_specs, cache_specs)
        out_specs = (P(batch_axes, plan.rules["vocab"]), cache_specs)
        args = (params_sds, tok, ins["cache"])
        donate = (2,)

    return dict(cfg=run_cfg, mesh=mesh, plan=plan, fn=fn, args=args,
                in_specs=in_specs, out_specs=out_specs, donate=donate,
                shape=sh)


# --------------------------------------------------------------------------
# HLO collective accounting.
# --------------------------------------------------------------------------


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    bpe = _DTYPE_BYTES.get(dtype)
    if bpe is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * bpe


def collective_stats(hlo_text: str) -> dict:
    """Per-op-kind {count, operand bytes} summed over the module (per-device
    shapes: the compiled module is already SPMD-partitioned)."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            token = f" {kind}("
            idx = line.find(token)
            if idx < 0:
                # also match "-start(" variants for async collectives
                token = f" {kind}-start("
                idx = line.find(token)
                if idx < 0:
                    continue
            operand_part = line[idx + len(token):]
            matches = _SHAPE_RE.findall(operand_part)
            b = sum(_shape_bytes(dt, dims) for dt, dims in matches)
            if b == 0:
                # fall back to the result shape(s) before '='
                matches = _SHAPE_RE.findall(line[:idx])
                b = sum(_shape_bytes(dt, dims) for dt, dims in matches)
            stats[kind]["count"] += 1
            stats[kind]["bytes"] += b
            break
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


# --------------------------------------------------------------------------
# One cell: lower + compile + analyses.
# --------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, verbose: bool = True,
             **build_kwargs) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False,
           "unroll": bool(build_kwargs.get("unroll", False))}
    t0 = time.time()
    try:
        cell = build_cell(arch, shape_name, multi_pod, **build_kwargs)
    except ValueError as e:
        rec.update(skipped=True, reason=str(e))
        if out_dir:
            _save(rec, out_dir)
        return rec
    mesh, plan = cell["mesh"], cell["plan"]
    rec["plan"] = plan.describe()
    try:
        named_in = shd.named(mesh, cell["in_specs"])
        named_out = shd.named(mesh, cell["out_specs"])
        jitted = jax.jit(cell["fn"], in_shardings=named_in,
                         out_shardings=named_out,
                         donate_argnums=cell["donate"])
        with mesh:
            with sharding_rules(mesh, plan.rules):
                lowered = jitted.lower(*cell["args"])
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        cost = compiled.cost_analysis() or {}
        rec["per_device_flops"] = float(cost.get("flops", 0.0))
        rec["per_device_bytes"] = float(cost.get("bytes accessed", 0.0))
        mem = compiled.memory_analysis()
        if mem is not None:
            rec["memory"] = {
                k: int(getattr(mem, k, 0)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes")}
            if verbose:
                print(mem)
        if verbose:
            print({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()
        rec["collectives"] = collective_stats(hlo)
        rec["hlo_lines"] = hlo.count("\n")
        rec["n_devices"] = mesh.size
        rec["ok"] = True
    except Exception as e:  # record failure for the report
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    if out_dir:
        _save(rec, out_dir)
    return rec


def _save(rec: dict, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


# --------------------------------------------------------------------------
# CLI.
# --------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", choices=ASSIGNED_ARCHS + ["all"], default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--subprocess", action="store_true",
                    help="one subprocess per cell (isolates compile memory)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan (true HLO FLOP accounting; "
                         "slower compiles)")
    args = ap.parse_args(argv)

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = []
    for arch in archs:
        cfg = get_config(arch)
        for sname in shapes:
            ok, reason = applicable(cfg, SHAPES[sname])
            if not ok:
                print(f"SKIP {arch} x {sname}: {reason}")
                continue
            for mp in meshes:
                cells.append((arch, sname, mp))

    failures = []
    for arch, sname, mp in cells:
        mesh_name = "multi" if mp else "single"
        path = os.path.join(args.out, f"{arch}_{sname}_{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"EXISTS {arch} x {sname} x {mesh_name}")
                    continue
        print(f"=== {arch} x {sname} x {mesh_name} ===", flush=True)
        if args.subprocess:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", sname,
                   "--mesh", mesh_name, "--out", args.out]
            if args.unroll:
                cmd.append("--unroll")
            r = subprocess.run(cmd, capture_output=True, text=True)
            print(r.stdout[-2000:])
            ok = False
            if os.path.exists(path):
                with open(path) as f:
                    ok = json.load(f).get("ok", False)
            if not ok:
                print(r.stderr[-2000:])
                failures.append((arch, sname, mesh_name))
        else:
            rec = run_cell(arch, sname, mp, out_dir=args.out,
                           unroll=args.unroll)
            if not rec["ok"] and not rec.get("skipped"):
                print(rec.get("error"))
                failures.append((arch, sname, mesh_name))
            else:
                print(f"ok={rec['ok']} lower={rec.get('lower_s')}s "
                      f"compile={rec.get('compile_s')}s "
                      f"coll={rec.get('collectives', {}).get('total_bytes', 0)/1e9:.2f}GB/dev")
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells OK")
    if failures:
        print("FAILURES:", failures)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

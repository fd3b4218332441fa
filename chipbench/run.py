"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

In order: find a TPU (none, or fewer chips than the cell asks for: exit
non-zero, no result); make the weights on the device from the seed; build
the runtime as a deployment would; warm up the cell's own shapes, with the
compile cache in ``<checkout>/.jax_cache`` (or ``JAX_COMPILATION_CACHE_DIR``
when set); drive the served path for ``--seconds``; free the program's state
and check what it served against the plain reference; print the result.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` turns on
the program's telemetry and the JAX profiler for the window and reports the
per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``.  One process
from start to finish: it starts no children.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import e2e  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402
from drive import Driver, summary  # noqa: E402
from peaks import peaks_for  # noqa: E402


class BenchError(RuntimeError):
    """The run cannot produce a result: exit non-zero, print none."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices_or_exit(chips: int) -> list:
    """The cell's devices as JAX reports them; no TPU, or fewer than
    ``chips``, ends the run before anything else."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found platform {devs[0].platform!r}, not a "
                         f"TPU; the benchmark runs only on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} TPU devices, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept, however
    quick its compile, so a warm run builds nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Builds:
    """Counts programs built: compiled, or loaded from the persistent
    cache (a hit), with the names of those built."""

    def __init__(self):
        from jax import monitoring

        self.names: list[str] = []
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_build)
        monitoring.register_event_listener(self._on_event)

    def _on_build(self, event: str, _secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __call__(self) -> dict:
        return {"built": len(self.names), "loaded": self.hits}


def forecast(span: dict) -> list:
    from repro.core.types import WorkloadType

    return [WorkloadType(int(i), int(o), float(r))
            for i, o, r in span["forecast"]]


def build(cell: spec.Cell, seed: int, devices: list, telemetry,
          device_kind: str):
    """Weights, planner and runtime, as a deployment would build them; the
    planner prices ``device_kind``."""
    import jax.numpy as jnp

    import weights
    from repro.configs import get_config
    from repro.core.costmodel import CostModel
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    from repro.core.types import ClusterSpec, hardware_spec_for
    from repro.serving.cluster import ClusterRuntime

    c = cell.config
    cfg = spec.program_config(c)
    weights.check_layout(c, cfg)
    shardings = None
    if c.get("shard"):
        from repro.launch.mesh import make_replica_mesh
        from repro.launch.sharding import make_plan, named, param_pspecs

        plan, run_cfg = make_plan(cfg, "serve", False, 1, tp=len(devices))
        if run_cfg != cfg:
            raise BenchError("tp over every chip pads this config's heads; "
                             "the master layout would not be the replicas'")
        shardings = named(make_replica_mesh(devices, len(devices)),
                          param_pspecs(cfg, plan))
    params = weights.make(c, seed, jnp.bfloat16, shardings)
    hw = hardware_spec_for(device_kind)
    # a test-size configuration may price the model it stands for
    profile = (get_config(c["plan_as"]).profile() if "plan_as" in c
               else cfg.profile())
    orch = Orchestrator(CostModel(profile, hw=hw),
                        ClusterSpec(len(devices), hw=hw),
                        OrchestratorConfig(max_pp=c.get("max_pp", 4)))
    kw = dict(shard=True, devices=devices) if c.get("shard") else {}
    rt = ClusterRuntime(cfg, params, orch, blocks_per_chip=c["pages_per_chip"],
                        seqs_per_chip=c["seqs_per_chip"],
                        block_size=c["page_size"], dtype=jnp.bfloat16,
                        seed=seed, telemetry=telemetry, **kw)
    return cfg, params, orch, rt


def serve(cell: spec.Cell, seed: int, seconds: float, traced: bool,
          devices: list, trace_dir: str | None,
          device_kind: str | None = None) -> dict:
    """Everything up to and including the check; returns what the result
    line and the earlier lines need.  ``device_kind`` (default: the
    devices') is the chip the planner prices."""
    import jax

    mix, c = cell.traffic, cell.config
    traffic.validate(mix)
    telemetry = None
    if traced:
        from repro.serving.telemetry import Telemetry
        telemetry = Telemetry(clock=time.perf_counter, capacity=1 << 22)
    builds = Builds()
    cfg, params, orch, rt = build(cell, seed, devices, telemetry,
                                  device_kind or devices[0].device_kind)
    spans = mix["spans"]
    plans = [orch.plan_span(forecast(s)) for s in spans]
    for i, p in enumerate(plans):
        log(f"span {i} plan: {p.deployment}")
    drv = Driver(rt, cfg.vocab_size, seed, pages_per_chip=c["pages_per_chip"],
                 page_size=c["page_size"], traced=traced)
    # warm every deployment the window will serve, the first one last
    for p in plans[::-1]:
        rt.apply_plan(p)
        drv.warm(mix, c["page_size"])
    builds_setup = builds()

    def switch(i: int):
        def fn():
            with drv.span("switch"):
                with drv.span("finish_span"):
                    rt.finish_span()
                with drv.span("plan_span"):
                    plan = orch.plan_span(forecast(spans[i]))
                with drv.span("apply_plan"):
                    rep = rt.apply_plan(plan)
                    for h in rt.replicas:
                        jax.block_until_ready(h.engine.cache.k)
            return {"migrated": rep.migrated, "copied": rep.copied,
                    "pages_copied": rep.pages_copied,
                    "reprefilled": rep.reprefilled,
                    "rolled_back": rep.rolled_back,
                    "deployment": str(plan.deployment)}
        return fn

    def start():
        if traced:
            jax.profiler.start_trace(trace_dir)

    def stop():
        if traced:
            jax.profiler.stop_trace()

    if mix["loop"] == "closed":
        reqs = traffic.closed_requests(mix, seed)
        rec = drv.run_closed(reqs, mix["clients"], seconds, builds,
                             on_open=start, on_close=stop)
    else:
        reqs = traffic.open_requests(mix, seed, seconds)
        marks, t = [], 0.0
        for i in range(1, len(spans)):
            t += spans[i - 1]["share_of_window"] * seconds
            marks.append((t, switch(i)))
        rec = drv.run_open(reqs, seconds, builds, marks, on_open=start,
                           on_close=stop)
    setup_s = rec.t0 - T_START
    b0 = rec.builds_at_open["built"]
    built = builds.names[b0:b0 + rec.builds_in_window["built"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    events = list(telemetry.tracer.events) if traced else []
    # what the check reads, then the program's state goes
    rec.failed.update({r: "stalled" for r in check.stalled(rec)})
    # requests served tokens on both sides of a switch: their KV moved
    must = [f.rid for f in rec.flights for sw in rec.switches[:1]
            if f.arrivals and f.arrivals[0] < sw["start"]
            and f.arrivals[-1] > sw["end"]]
    picked = check.sample(rec, c["correct"]["sample_requests"], seed, must)
    served = {f.rid: list(rt.request_log[f.rid].emitted) for f in picked}
    prompts = {f.rid: traffic.prompt_tokens(
        dataclasses.replace(f.req, rid=f.rid), cfg.vocab_size, seed)
        for f in picked}
    del drv, rt, orch
    gc.collect()
    t_ref = time.perf_counter()
    seq_len = mix["max_context"]
    n_read = max(t["output"]["max"] for t in mix["types"])
    gaps = check.served_gaps(c, params, picked, served, prompts, seq_len,
                             n_read)
    compared = check.compare(rec, gaps, served,
                             c["correct"]["max_logit_gap"])
    return {"rec": rec, "setup_s": setup_s, "peak": peak, "events": events,
            "compared": compared, "picked": picked, "must": must,
            "builds_setup": builds_setup, "built_in_window": built[:20],
            "ref_s": time.perf_counter() - t_ref, "params": params, "gaps": gaps, "served": served,
            "prompts": prompts, "seq_len": seq_len, "n_read": n_read}


def per_layer(cell: spec.Cell, out: dict, red, device_kind: str) -> dict:
    ctx = {"rec": out["rec"], "red": red, "events": out["events"],
           "config": cell.config, "traffic": cell.traffic,
           "peaks": peaks_for(device_kind), "chips": cell.chips}
    got = {}
    for m in cell.per_layer:
        mod = spec.load_metric(m["name"])
        v = mod.compute(ctx)
        if v is not None:
            got[m["name"]] = {"value": v, "unit": m["unit"]}
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    try:
        cell = spec.load_cell(args.workload)
        devices = devices_or_exit(cell.chips)
        src = ROOT / "src"
        if not (src / "repro").is_dir():
            raise BenchError(f"the program is not in this checkout ({src})")
        sys.path.insert(0, str(src))
        log(f"compile cache: {use_compile_cache()}")
        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices)}
        log(f"device: platform={d0.platform} kind={d0.device_kind} "
            f"count={len(devices)}")
        if cell.config["correct"]["max_logit_gap"] is None:
            raise BenchError(f"{cell.config['name']}: no limit set for "
                             f"max_logit_gap")
        trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace \
            else None
        try:
            out = serve(cell, args.seed, args.seconds, bool(args.trace),
                        devices, trace_dir)
            red = None
            if args.trace:
                import xplane as tr
                path = tr.find_xplane(trace_dir)
                if args.keep_trace:
                    os.makedirs(args.keep_trace, exist_ok=True)
                    shutil.copy(path, args.keep_trace)
                red = tr.reduce(tr.load(path))
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
    except BenchError as e:
        log(f"chipbench: {e}")
        return 2
    rec = out["rec"]
    info = {**summary(rec), **e2e.samples(rec),
            "at_deadline": e2e.at_deadline(rec),
            "builds_in_setup": out["builds_setup"],
            "switches": rec.switches, "reference_s": out["ref_s"],
            "sampled": [[f.rid, f.req.prompt_len, f.req.max_new]
                        for f in out["picked"]],
            "across_switch": len(out["must"])}
    log("run: " + json.dumps(info))
    device["memory_peak_bytes"] = int(out["peak"])
    if args.trace:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        metrics = per_layer(cell, out, red, device["kind"])
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                v = out["setup_s"]
            else:
                v = e2e.COMPUTE[m["name"]](rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    compared = out["compared"]
    result = {"correct": check.is_correct(compared),
              "attempted": len(rec.flights), "failed": len(rec.failed),
              "metrics": metrics, "device": device}
    if args.trace:
        import xplane as tr
        result["breakdown"] = tr.breakdown(red)
    result["compared"] = compared
    for name, v in compared.items():
        log(f"compared {name}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

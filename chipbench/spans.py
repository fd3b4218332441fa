"""The program's own host spans in a traced run, and what they say.

``xplane.load`` keeps the benchmark's spans (``xplane.HOST_SPANS``) from
the trace's host plane.  This module keeps the program's as well (their
names and nesting are in ``serving/telemetry.py``'s docstring), with
Python's ``gc`` and JAX's ``backend_compile*`` events, and reads from
them:

- each span's self time (its duration less the part its children cover)
  per tick of the window;
- ``step_host_ms``: the mean over ``cluster.step`` spans of the span's
  duration less the time its ``engine.wait_device`` descendants cover:
  the program's own host work per tick;
- ``prefill_ms_per_ktok``: the summed duration of ``engine.prefill`` spans
  over the prompt tokens, in thousands, of the requests whose first token
  landed in the window;
- the idle gaps, named by the innermost of all these spans (the rule of
  ``xplane.reduce``).

    python3 chipbench/spans.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep-trace <dir>]

runs the cell as ``run.py --trace 1`` does and prints one JSON line: the
numbers above, the cell's per-layer metrics and the traced window's rate.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import xplane  # noqa: E402

PROGRAM_SPANS = ("cluster.step", "engine.admit", "engine.prefill",
                 "engine.table_update", "engine.decode_dispatch",
                 "engine.finish", "engine.wait_device", "cluster.sync_log",
                 "cluster.post", "gc")
COMPILE_PREFIX = "backend_compile"


def kept(name: str) -> bool:
    return name in PROGRAM_SPANS or name.startswith(COMPILE_PREFIX)


def load(path: str) -> xplane.Trace:
    """``xplane.load``'s trace with the program's spans added to ``host``."""
    from jax.profiler import ProfileData

    tr = xplane.load(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if kept(e.name)]
    return tr


def program_spans(tr: xplane.Trace) -> list:
    """The program's spans clipped to the ``window`` span, in start order."""
    (lo, hi), = [(s, e) for n, s, e in tr.host if n == "window"]
    return sorted(((n, max(s, lo), min(e, hi)) for n, s, e in tr.host
                   if kept(n) and min(e, hi) > max(s, lo)),
                  key=lambda x: (x[1], -x[2]))


def parents(spans: list) -> list:
    """For each span (in start order), the index of the span that
    directly holds it, or -1."""
    parent, stack = [], []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack and e <= spans[stack[-1]][2]
                      else -1)
        stack.append(i)
    return parent


def self_ms(spans: list) -> dict:
    """Span name -> summed self time in ms: duration less the part its
    direct children cover."""
    own = [e - s for _, s, e in spans]
    for i, p in enumerate(parents(spans)):
        if p >= 0:
            own[p] -= spans[i][2] - spans[i][1]
    out: dict = {}
    for (n, _, _), t in zip(spans, own):
        out[n] = out.get(n, 0.0) + t / 1e6
    return out


def step_host_ms(spans: list) -> float | None:
    """Mean over ``cluster.step`` spans of the duration less the time
    their ``engine.wait_device`` descendants cover."""
    parent = parents(spans)
    host = {i: e - s for i, (n, s, e) in enumerate(spans)
            if n == "cluster.step"}
    if not host:
        return None
    for i, (n, s, e) in enumerate(spans):
        if n != "engine.wait_device":
            continue
        p = parent[i]
        while p >= 0 and p not in host:
            p = parent[p]
        if p >= 0:
            host[p] -= e - s
    return sum(host.values()) / len(host) / 1e6


def prefill_ms_per_ktok(spans: list, prompt_tokens: int) -> float | None:
    ms = sum(e - s for n, s, e in spans if n == "engine.prefill") / 1e6
    if not ms or not prompt_tokens:
        return None
    return ms / (prompt_tokens / 1000)


def prefilled_tokens(rec) -> int:
    """Prompt tokens of the requests whose first token landed in the
    window."""
    return sum(f.req.prompt_len for f in rec.flights
               if f.arrivals and rec.t0 <= f.arrivals[0] < rec.t1)


def report(tr: xplane.Trace, red: xplane.Reduction, rec) -> dict:
    """What the program's spans say about the window of ``rec``;
    ``red`` is ``xplane.reduce(tr)``."""
    spans = program_spans(tr)
    ticks = max(rec.ticks, 1)
    return {"step_host_ms": step_host_ms(spans),
            "prefill_ms_per_ktok": prefill_ms_per_ktok(
                spans, prefilled_tokens(rec)),
            "self_ms_per_tick": {n: t / ticks
                                 for n, t in sorted(self_ms(spans).items())},
            "ticks": rec.ticks,
            "idle_gaps": xplane.breakdown(red)["idle_gaps"]}


def main(argv=None) -> int:
    import e2e
    import run
    import spec

    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the run's .xplane.pb here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = run.devices_or_exit(cell.chips)
    except run.BenchError as e:
        run.log(f"spans: {e}")
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-spans-")
    try:
        out = run.serve(cell, args.seed, args.seconds, True, devices,
                        trace_dir)
        path = xplane.find_xplane(trace_dir)
        if args.keep_trace:
            Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, args.keep_trace)
        tr = load(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec, kind = out["rec"], devices[0].device_kind
    red = xplane.reduce(tr)
    result = report(tr, red, rec)
    result["per_layer"] = {k: v["value"] for k, v in run.per_layer(
        cell, out, red, kind).items()}
    result["traced"] = {n: e2e.COMPUTE[n](rec) for n in
                        ("output_tokens_per_s", "tpot_p95_ms")}
    result["device"] = {"kind": kind, "count": len(devices)}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

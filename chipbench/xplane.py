"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

What the reduction relies on in the trace, as JAX 0.9 writes it for a TPU:

- one plane per chip, named ``/device:TPU:<n>``, with a line ``XLA Ops``
  (one event per executed HLO instruction, named by its HLO text,
  ``%<name> = <type> <opcode>(...)``; a Pallas kernel's text holds
  ``tpu_custom_call``; a ``while`` or ``call`` event spans the events of
  its body, so such containers are left out of the per-op sums) and a line
  ``XLA Modules`` (one event per executed program, named by its jitted
  function and a hash, such as ``jit_fused(1234...)``);
- the host plane ``/host:CPU``, whose events include the benchmark's own
  ``TraceAnnotation`` spans (``window``, ``step``, ``submit``, ``wait``,
  ``switch``, ``finish_span``, ``plan_span``, ``apply_plan``) on the same
  clock as the device events.

Busy time is the union of op intervals inside the ``window`` span, per
chip; idle gaps are its complement, each named by the innermost host span
that covers its middle (``none`` when the host was in no span).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("window", "step", "submit", "wait", "switch", "finish_span",
              "plan_span", "apply_plan")


@dataclasses.dataclass
class Trace:
    ops: dict          # device -> [(name, start_ns, end_ns)]
    modules: dict      # device -> [(name, start_ns, end_ns)]
    host: list         # [(span name, start_ns, end_ns)]


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, found "
                         f"{paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == OPS_LINE:
                    ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in HOST_SPANS]
    return Trace(ops=ops, modules=modules, host=host)


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def short_name(text: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...)`` -> ``fusion.3 (fusion)``."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(" " + rest.split(" ", 1)[-1]) if rest else None
    if "tpu_custom_call" in rest:
        op = "tpu_custom_call"
    else:
        op = m.group(1) if m else "?"
    return f"{name.lstrip('%')} ({op})"


def leaves(evs: list) -> list:
    """The events that hold no other event (drops ``while`` and ``call``
    containers, whose time their body's events already count)."""
    order = sorted(evs, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack: open events as [event, has_child]
    for ev in order:
        while stack and stack[-1][0][2] <= ev[1]:
            top = stack.pop()
            if not top[1]:
                out.append(top[0])
        if stack and ev[2] <= stack[-1][0][2]:
            stack[-1][1] = True
        stack.append([ev, False])
    out += [e for e, child in stack if not child]
    return out


def _clipped(evs: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if min(e, hi) > max(s, lo)]


@dataclasses.dataclass
class Reduction:
    window_s: float
    chips: int
    busy_s: float                  # mean over chips
    op_s: dict                     # op name -> seconds, mean over chips
    module_s: dict                 # module name -> seconds, mean over chips
    module_n: dict                 # module name -> executions, per chip
    kernel_s: dict                 # module name -> its tpu_custom_call s
    gaps: list                     # [(host span, seconds)], longest first


def reduce(tr: Trace) -> Reduction:
    wins = [(s, e) for n, s, e in tr.host if n == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' span in the trace, found "
                         f"{len(wins)}")
    lo, hi = wins[0]
    devices = sorted(tr.ops)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    spans = sorted(((n, s, e) for n, s, e in tr.host if n != "window"),
                   key=lambda x: x[1])
    span_starts = [x[1] for x in spans]
    busy, op_s, module_s, module_n, kernel_s, gaps = 0.0, {}, {}, {}, {}, []
    k = len(devices)
    for dev in devices:
        ops = _clipped(tr.ops[dev], lo, hi)
        merged = union([(s, e) for _, s, e in ops], lo, hi)
        busy += sum(e - s for s, e in merged) / 1e9 / k
        for n, s, e in leaves(ops):
            n = short_name(n)
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9 / k
        mods = _clipped(tr.modules.get(dev, []), lo, hi)
        for n, s, e in mods:
            module_s[n] = module_s.get(n, 0.0) + (e - s) / 1e9 / k
            module_n[n] = module_n.get(n, 0) + 1.0 / k
        # kernels by the program that ran them: the module whose interval
        # holds the kernel's middle
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for n, s, e in ops:
            if "tpu_custom_call" not in n:
                continue
            i = bisect.bisect_right(starts, (s + e) / 2) - 1
            if i >= 0 and mods[i][2] >= (s + e) / 2:
                name = mods[i][0]
                kernel_s[name] = kernel_s.get(name, 0.0) + (e - s) / 1e9 / k
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                name = _host_span(spans, span_starts, (prev + s) / 2)
                gaps.append((name, (s - prev) / 1e9))
            prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=(hi - lo) / 1e9, chips=k, busy_s=busy,
                     op_s=op_s, module_s=module_s, module_n=module_n,
                     kernel_s=kernel_s,
                     gaps=gaps)


def _host_span(spans: list, starts: list, t: float) -> str:
    """The innermost (latest-starting) host span covering ``t``; spans
    nest at most a few deep, so a short look back finds it."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 64, -1), -1):
        n, _, e = spans[j]
        if e >= t:
            return n
    return "none"


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red.gaps[:top]]}

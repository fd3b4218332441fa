"""Shared reading for the per-layer metrics: what the window holds.

Each metric lives in a module of its own beside this one, with ``NAME``,
``UNIT``, ``LAYER`` and ``compute(ctx)``; ``ctx`` holds the run's record
(``rec``), the trace reduction (``red``), the program's telemetry events
(``events``), the configuration and traffic files, the chip's ``peaks``
and the cell's ``chips``.  ``compute`` returns None when the run holds
nothing for it to read, and the harness then leaves the metric out.
"""
from __future__ import annotations

import re

# module names in the trace, as the program's jitted entries are named
DECODE_MODULE = re.compile(r"^jit_fused\b")


def in_window(rec, t: float) -> bool:
    return rec.t0 <= t < rec.t1


def window_events(ctx, kind: str) -> list:
    rec = ctx["rec"]
    return [e for e in ctx["events"] if e.kind == kind
            and in_window(rec, e.ts)]


def decode_tokens(rec):
    """(flight, keys) of every decoded token that reached the client in the
    window; token j >= 1 of a request attends its prompt and j tokens."""
    for f in rec.flights:
        for j, t in enumerate(f.arrivals):
            if j >= 1 and in_window(rec, t):
                yield f, f.req.prompt_len + j


def prefilled(rec):
    """Flights whose prefill (first token) landed in the window."""
    return [f for f in rec.flights
            if f.arrivals and in_window(rec, f.arrivals[0])]


def module_time(red, pattern) -> tuple[float, float]:
    """(seconds, executions) of the modules matching ``pattern``, per chip."""
    s = sum(v for k, v in red.module_s.items() if pattern.search(k))
    n = sum(v for k, v in red.module_n.items() if pattern.search(k))
    return s, n

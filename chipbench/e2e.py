"""End-to-end metrics, from the client's own timestamps over the window.

Each is taken over all the work and all the time of the window: a rate is
every token that reached the client in the window over its seconds, and a
tail is the tail of all requests, never a median of chunks.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _in(rec, t: float) -> bool:
    return rec.t0 <= t < rec.t1


def output_tokens_per_s(rec) -> float:
    n = sum(1 for f in rec.flights for t in f.arrivals if _in(rec, t))
    return n / (rec.t1 - rec.t0)


def tpot_samples(rec) -> list[float]:
    """Per request with at least two tokens in the window: (last - first
    arrival in the window) / (tokens - 1), in seconds."""
    out = []
    for f in rec.flights:
        ts = [t for t in f.arrivals if _in(rec, t)]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def ttft_samples(rec) -> list[float]:
    """Per request due in the window: first token's arrival - the time it
    was due.  A request that failed counts as waiting until it was given
    up (the end of the untimed tail), so it can only raise the tail."""
    out = []
    give_up = max([t for f in rec.flights for t in f.arrivals] + [rec.t1])
    for f in rec.flights:
        if not _in(rec, f.due):
            continue
        first = f.arrivals[0] if f.arrivals else give_up
        out.append(first - f.due)
    return out


def p95(xs: list[float]) -> float:
    return float(np.percentile(np.asarray(xs, float), 95))


def tpot_p95_ms(rec) -> float | None:
    xs = tpot_samples(rec)
    return p95(xs) * 1e3 if xs else None


def ttft_p95_ms(rec) -> float | None:
    xs = ttft_samples(rec)
    return p95(xs) * 1e3 if xs else None


COMPUTE = {
    "output_tokens_per_s": output_tokens_per_s,
    "tpot_p95_ms": tpot_p95_ms,
    "ttft_p95_ms": ttft_p95_ms,
}


def at_deadline(rec) -> dict:
    """The rate and the pace tail as a window cut at its deadline would
    read them, leaving out the tick in progress (an earlier line)."""
    cut = dataclasses.replace(rec, t1=rec.deadline or rec.t1)
    return {"output_tokens_per_s": output_tokens_per_s(cut),
            "tpot_p95_ms": tpot_p95_ms(cut)}


def samples(rec) -> dict:
    """The sample count behind each tail (an earlier line)."""
    return {"tpot_requests": len(tpot_samples(rec)),
            "ttft_requests": len(ttft_samples(rec)),
            "tokens_in_window": sum(1 for f in rec.flights
                                    for t in f.arrivals if _in(rec, t))}

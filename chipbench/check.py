"""The comparison that decides ``correct``.

Once the window has closed, a sample drawn from the seed of the requests
the run served (always with the one served the most tokens, and in a cell
with a span switch, requests that were in flight across it) is run through
the plain reference, teacher-forced over prompt + every token served to the
close.  Requests that finished come first; where the mix's outputs are long
beside the window, few finish in it, and the sample is filled with requests
still decoding, compared on the tokens they had been served.  The number
compared is the widest gap by which a served token's logit lies below the
reference's best (``reference.request_gaps``).  Beside it, two exact
counts: sampled requests whose served tokens fall short (a finished one
of its tokens, one in flight of the tokens the client saw), and requests
that failed (shed, never given a first token, or stalled mid-decode).
"""
from __future__ import annotations

import numpy as np

import reference
from traffic import rng_for

STALL_S = 10.0      # a decoding request with no token for this long failed


def stalled(rec) -> list[int]:
    """Requests that had begun decoding and then got no token for
    ``STALL_S`` seconds of the window (ticks give every decoding request a
    token, so only a fault leaves one behind)."""
    out = []
    for f in rec.flights:
        if f.done is not None or not f.arrivals:
            continue
        if rec.t1 - f.arrivals[-1] > STALL_S and rec.t1 - rec.t0 > STALL_S:
            out.append(f.rid)
    return out


def sample(rec, n: int, seed: int, must: list[int] = ()) -> list:
    """``n`` served flights drawn from the seed: the one served the most
    tokens, then up to half of the rest from ``must`` (requests in flight
    across a switch), then finished ones, then ones still decoding."""
    served = [f for f in rec.flights
              if len(f.arrivals) >= 2 and f.rid not in rec.failed]
    if not served:
        return []
    rng = rng_for(seed, "sample")
    longest = max(served, key=lambda f: (len(f.arrivals), f.rid))
    picked = [longest]
    crossed = [f for f in served if f.rid in set(must) and f is not longest]
    for i in rng.permutation(len(crossed))[:max(1, (n - 1) // 2)]:
        picked.append(crossed[int(i)])
    for done in (True, False):
        rest = [f for f in served if f not in picked
                and (f.done is not None) == done]
        for i in rng.permutation(len(rest))[:max(0, n - len(picked))]:
            picked.append(rest[int(i)])
    return picked


def served_gaps(cfg_file: dict, weights, flights: list, served: dict,
                prompts: dict, seq_len: int, n_read: int,
                quant: str | None = None) -> dict:
    """rid -> (served gaps, control gaps) over every position read."""
    return {f.rid: reference.request_gaps(cfg_file, weights, prompts[f.rid],
                                          served[f.rid], seq_len, n_read,
                                          quant)
            for f in flights}


def compare(rec, gaps: dict, served: dict, limit: float) -> dict:
    """The numbers compared, each with its limit; ``correct`` is that
    every value is at or under its limit."""
    widest = max((float(np.max(g)) for g, _ in gaps.values()),
                 default=float("inf"))
    short = sum(1 for f in rec.flights
                if f.rid in served and (len(served[f.rid]) != f.req.max_new
                                        if f.done is not None else
                                        len(served[f.rid]) < len(f.arrivals)))
    failed = len(rec.failed)
    return {"max_logit_gap": {"value": widest, "limit": limit},
            "short_requests": {"value": short, "limit": 0},
            "failed_requests": {"value": failed, "limit": 0}}


def is_correct(compared: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())

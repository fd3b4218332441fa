"""The one traffic generator: every mix under ``traffic/`` is data it reads.

A mix file (``traffic/<name>.json``) holds parameters only:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when its last one completes) or ``"open"`` (requests due on a
  schedule, whatever the server does);
- ``types``: request types, each with lognormal ``prompt`` and ``output``
  lengths (``median``, ``sigma``, clipped to ``min``..``max``, and rounded
  up to a multiple of ``quantum`` where one is given);
- ``max_context``: prompt + output never exceeds it (outputs are cut);
- ``spans``: each with its ``share_of_window``, its ``mix`` (share of each
  type) and its declared ``forecast`` for the planner (``[prompt, output,
  rate]`` per type); a closed loop has one span;
- closed loop: ``requests``, the size of the fixed set of sizes that the
  clients cycle through; ``steady_start`` (default false): the window
  opens on the loop's steady state, not on a batch that starts together
  (see ``closed_requests``);
- open loop: ``rate_scale``, the requests per second of a span whose
  forecast rates sum to 1 (the spans' rates keep the ratio of their
  forecasts).

Every seed gets the same set of requests and arrival gaps, in another
order: lengths are the mid-point quantiles of their lognormal, paired by a
fixed shuffle, and gaps those of an exponential; the seed permutes them.
A closed loop's seed permutes only its first block, the requests in
flight when the window opens, over the clients; the requests that replace
them go in the fixed order, since the few that enter a window decide its
work.  So seeds change the order of the work, not its amount, and runs on
different seeds spread no wider than runs on one.
``serving/request.py``'s ``synthesize_trace`` (lognormal lengths per
archetype, Poisson arrivals) is the model this copies; the program's own
copy is not used, so a change to it cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

LOOPS = ("closed", "open")


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as the client sends it.  ``due`` is seconds after the
    window opens (open loop); a closed loop's requests have no due time."""
    rid: int
    type_id: int
    prompt_len: int
    max_new: int
    span: int = 0
    due: float | None = None


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one use of the seed (``stream`` names the use), so
    sizes, order and token ids never share draws.  Any non-negative integer
    seed works, 2**31 and above included."""
    return np.random.default_rng(
        [int(seed), *(ord(c) for c in stream)])


def quantile_lengths(n: int, dist: dict) -> np.ndarray:
    """``n`` lengths at the mid-point quantiles of a lognormal of
    ``dist["median"]`` and ``dist["sigma"]``, rounded up to
    ``dist["quantum"]`` (default 1) and clipped to
    ``dist["min"]``..``dist["max"]``.  Deterministic."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    q = dist.get("quantum", 1)
    x = np.ceil(dist["median"] * np.exp(dist["sigma"] * z) / q) * q
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def _pair_lengths(n: int, typ: dict, mix: dict) -> list[tuple]:
    """``n`` (prompt, output) pairs of one type: two quantile sets paired
    by a fixed shuffle (the same for every seed), outputs cut so the
    context fits ``max_context``."""
    fixed = rng_for(0, "pairing")
    prompts = fixed.permutation(quantile_lengths(n, typ["prompt"]))
    outputs = fixed.permutation(quantile_lengths(n, typ["output"]))
    cap = mix["max_context"]
    return [(int(p), int(max(1, min(o, cap - p))))
            for p, o in zip(prompts, outputs)]


def validate(mix: dict) -> None:
    """Raise ``ValueError`` on a mix file the generator cannot read."""
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}, not "
                         f"{mix.get('loop')!r}")
    types = mix.get("types") or []
    if not types:
        raise ValueError("a mix needs at least one type")
    for t in types:
        for part in ("prompt", "output"):
            d = t[part]
            if not 1 <= d["min"] <= d["median"] <= d["max"]:
                raise ValueError(f"type {t['name']}: {part} needs "
                                 f"1 <= min <= median <= max")
            q = d.get("quantum", 1)
            if d["min"] % q or d["max"] % q:
                raise ValueError(f"type {t['name']}: {part} min and max "
                                 f"must be multiples of its quantum")
        if t["prompt"]["max"] >= mix["max_context"]:
            raise ValueError(f"type {t['name']}: prompts reach "
                             f"max_context {mix['max_context']}")
    spans = mix.get("spans") or []
    if not spans or abs(sum(s["share_of_window"] for s in spans)
                        - 1.0) > 1e-9:
        raise ValueError("span shares must sum to 1")
    for s in spans:
        if len(s["mix"]) != len(types) or len(s["forecast"]) != len(types):
            raise ValueError("one mix share and one forecast per type in "
                             "every span")
    if mix["loop"] == "closed":
        if len(spans) != 1:
            raise ValueError("a closed loop has one span")
        if mix["clients"] < 1 or mix["requests"] < mix["clients"]:
            raise ValueError("closed loop: need 1 <= clients <= requests")


def closed_requests(mix: dict, seed: int) -> list[Req]:
    """The closed loop's fixed set of ``mix["requests"]`` sizes; clients
    take them in turn and cycle.  The set goes in a fixed order, and the
    seed permutes its first block of ``clients`` requests over the
    clients: every seed starts the same batch, and the requests that
    replace it come in the same order, whichever client sends them.  With
    ``steady_start`` the first block, which is in flight when the window
    opens, is spread over its outputs (``_advanced``), so contexts span
    their range and requests complete and are replaced from the window's
    start."""
    rng = rng_for(seed, "sizes")
    n = mix["requests"]
    counts = _split(n, mix["spans"][0]["mix"])
    pairs = []
    for j, (typ, c) in enumerate(zip(mix["types"], counts)):
        pairs += [(j, p, o) for p, o in _pair_lengths(c, typ, mix)]
    pairs = [pairs[k] for k in rng_for(0, "order").permutation(len(pairs))]
    b = mix["clients"]
    if mix.get("steady_start"):
        pairs[:b] = [_advanced(j, b, pairs[j], mix) for j in range(b)]
    order = [*rng.permutation(b), *range(b, len(pairs))]
    return [Req(rid=i, type_id=pairs[k][0], prompt_len=pairs[k][1],
                max_new=pairs[k][2]) for i, k in enumerate(order)]


def _advanced(j: int, b: int, pair: tuple, mix: dict) -> tuple:
    """Request ``j`` of the first block of ``b``, already ``(j + 0.5) / b``
    of the way through its output, as a caller of a loop in steady state
    finds it: the part served so far joins the prompt (rounded down to the
    prompt's quantum) and the rest is still to come.  The fraction goes by
    the request's place in the fixed order, so every seed starts on the
    same contexts."""
    t, p, o = pair
    q = mix["types"][t]["prompt"].get("quantum", 1)
    a = int((j + 0.5) / b * o) // q * q
    return t, p + a, o - a


def span_rates(mix: dict) -> list[float]:
    """Requests per second of each span of an open loop."""
    return [mix["rate_scale"] * sum(f[2] for f in s["forecast"])
            for s in mix["spans"]]


def open_requests(mix: dict, seed: int, seconds: float) -> list[Req]:
    """Every request of an open loop due in a window of ``seconds``.

    Each span holds ``round(rate * its seconds)`` requests, split over the
    types by the span's mix; their gaps are that many exponential
    quantiles, permuted by the seed and scaled to fill the span."""
    rng = rng_for(seed, "sizes")
    reqs: list[Req] = []
    t0 = 0.0
    for s, (span, rate) in enumerate(zip(mix["spans"], span_rates(mix))):
        dur = seconds * span["share_of_window"]
        n = max(1, round(rate * dur))
        counts = _split(n, span["mix"])
        kinds = []
        for j, (typ, c) in enumerate(zip(mix["types"], counts)):
            kinds += [(j, p, o) for p, o in _pair_lengths(c, typ, mix)]
        kinds = [kinds[k] for k in rng.permutation(len(kinds))]
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        gaps = rng.permutation(gaps)
        due = t0 + dur * np.cumsum(gaps) / gaps.sum() * n / (n + 0.5)
        for (j, p, o), d in zip(kinds, due):
            reqs.append(Req(rid=len(reqs), type_id=j, prompt_len=p,
                            max_new=o, span=s, due=float(d)))
        t0 += dur
    return reqs


def prompt_tokens(req: Req, vocab: int, seed: int) -> np.ndarray:
    """The prompt's token ids: drawn from the seed and the request id, so
    a request's prompt is the same whenever it is made."""
    rng = rng_for(seed, f"prompt{req.rid}")
    return rng.integers(0, vocab, req.prompt_len, dtype=np.int32)


def _split(n: int, shares: list[float]) -> list[int]:
    """``n`` split by ``shares`` (largest remainders), summing to ``n``."""
    w = np.asarray(shares, float) / float(sum(shares))
    raw = w * n
    out = np.floor(raw).astype(int)
    for k in np.argsort(-(raw - out))[: n - out.sum()]:
        out[k] += 1
    return [int(c) for c in out]

"""The one traffic generator: seeds, the declared clips, the two loops."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import traffic  # noqa: E402

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2**31 + 12345


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def all_mixes():
    return sorted(p.stem for p in MIXES.glob("*.json"))


def requests(m, seed, seconds=60.0):
    if m["loop"] == "closed":
        return traffic.closed_requests(m, seed)
    return traffic.open_requests(m, seed, seconds)


@pytest.mark.parametrize("name", all_mixes())
def test_same_seed_same_requests_large_seeds_work(name):
    m = mix(name)
    traffic.validate(m)
    a, b = requests(m, BIG), requests(m, BIG)
    assert a == b
    assert a != requests(m, BIG + 1)
    r = a[0]
    assert np.array_equal(traffic.prompt_tokens(r, 1000, BIG),
                          traffic.prompt_tokens(r, 1000, BIG))


@pytest.mark.parametrize("name", all_mixes())
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    m = mix(name)
    a, b = requests(m, 1), requests(m, 2)
    key = lambda r: (r.type_id, r.prompt_len, r.max_new)  # noqa: E731
    assert sorted(map(key, a)) == sorted(map(key, b))
    assert list(map(key, a)) != list(map(key, b))


@pytest.mark.parametrize("name", all_mixes())
def test_declared_clips(name):
    """The sizes as drawn keep the clips; a steady start only moves part
    of a first request's output into its prompt."""
    m = mix(name)
    for r in requests({**m, "steady_start": False}, 7):
        t = m["types"][r.type_id]
        p, o = t["prompt"], t["output"]
        assert p["min"] <= r.prompt_len <= p["max"]
        assert r.prompt_len % p.get("quantum", 1) == 0
        assert 1 <= r.max_new <= o["max"]
        assert r.prompt_len + r.max_new <= m["max_context"]
    for r in requests(m, 7):
        p = m["types"][r.type_id]["prompt"]
        assert r.prompt_len % p.get("quantum", 1) == 0 and r.max_new >= 1
        assert p["min"] <= r.prompt_len
        assert r.prompt_len + r.max_new <= m["max_context"]


def test_lengths_follow_the_lognormal():
    d = {"median": 256, "sigma": 0.7, "min": 1, "max": 10**6}
    x = traffic.quantile_lengths(1001, d)
    assert np.median(x) == 256
    assert np.all(np.diff(x) >= 0)
    clipped = traffic.quantile_lengths(
        1000, {**d, "min": 64, "max": 1024, "quantum": 64})
    assert clipped.min() == 64 and clipped.max() == 1024
    assert set(clipped % 64) == {0}


CLOSED = {"loop": "closed", "clients": 4, "requests": 8, "max_context": 100,
          "types": [{"name": "a",
                     "prompt": {"median": 20, "sigma": 0.5, "min": 10,
                                "max": 40},
                     "output": {"median": 30, "sigma": 0.5, "min": 5,
                                "max": 80}}],
          "spans": [{"share_of_window": 1.0, "mix": [1.0],
                     "forecast": [[20, 30, 4.0]]}]}
OPEN = {**CLOSED, "loop": "open", "rate_scale": 0.5,
        "types": CLOSED["types"] * 2,
        "spans": [{"share_of_window": 0.5, "mix": [0.9, 0.1],
                   "forecast": [[20, 30, 9.0], [20, 30, 1.0]]},
                  {"share_of_window": 0.5, "mix": [0.1, 0.9],
                   "forecast": [[20, 30, 10.0], [20, 30, 30.0]]}]}


def test_closed_loop_has_no_due_times():
    traffic.validate(CLOSED)
    reqs = traffic.closed_requests(CLOSED, 3)
    assert len(reqs) == 8 and all(r.due is None for r in reqs)


def test_steady_start_spreads_the_first_block():
    """The first block is part-served by its place in the fixed order:
    same contexts for every seed, prompts grown by whole quanta, outputs
    shortened by as much, and the rest of the set untouched."""
    steady = {**CLOSED, "steady_start": True,
              "types": [{**CLOSED["types"][0],
                         "prompt": {**CLOSED["types"][0]["prompt"],
                                    "quantum": 5}}]}
    firsts = []
    for seed in (3, 2**31 + 11):
        plain = traffic.closed_requests({**steady, "steady_start": False},
                                        seed)
        reqs = traffic.closed_requests(steady, seed)
        firsts.append(sorted((r.prompt_len, r.max_new) for r in reqs[:4]))
        assert [(r.prompt_len, r.max_new) for r in reqs[4:]] == [
            (r.prompt_len, r.max_new) for r in plain[4:]]
    assert firsts[0] == firsts[1]
    before = sorted((r.prompt_len, r.max_new) for r in plain[:4])
    assert sorted(p + o for p, o in firsts[0]) == sorted(
        p + o for p, o in before)
    assert all(p % 5 == 0 and o >= 1 for p, o in firsts[0])
    assert sum(o for _, o in firsts[0]) < sum(o for _, o in before)


def test_open_loop_rates_spans_and_due_times():
    traffic.validate(OPEN)
    assert traffic.span_rates(OPEN) == [5.0, 20.0]
    reqs = traffic.open_requests(OPEN, 3, seconds=20.0)
    by_span = [[r for r in reqs if r.span == s] for s in (0, 1)]
    assert [len(x) for x in by_span] == [50, 200]
    assert all(0 <= r.due < 10 for r in by_span[0])
    assert all(10 <= r.due < 20 for r in by_span[1])
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    # the span's mix splits its requests over the types
    assert sum(r.type_id == 0 for r in by_span[0]) == 45
    assert sum(r.type_id == 1 for r in by_span[1]) == 180


@pytest.mark.parametrize("bad", [
    {"loop": "sideways"},
    {"max_context": 30},
    {"spans": [{"share_of_window": 0.5, "mix": [1.0],
                "forecast": [[20, 30, 4.0]]}]},
    {"clients": 9},
])
def test_validate_refuses(bad):
    with pytest.raises(ValueError):
        traffic.validate({**CLOSED, **bad})


def test_closed_loop_replaces_in_one_order_for_every_seed():
    """The seed moves the first block over the clients; the requests that
    replace it come in the same order for every seed, so every window
    holds the same work."""
    m = mix("codegen")
    a, b = traffic.closed_requests(m, 1), traffic.closed_requests(m, BIG)
    key = lambda r: (r.type_id, r.prompt_len, r.max_new)  # noqa: E731
    n = m["clients"]
    assert list(map(key, a[:n])) != list(map(key, b[:n]))
    assert sorted(map(key, a[:n])) == sorted(map(key, b[:n]))
    assert list(map(key, a[n:])) == list(map(key, b[n:]))

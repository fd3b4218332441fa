"""``correct`` on the CPU at a test size: a sound run passes, the control
and each fault the cell can have fail.

The harness's look for a chip is skipped (``run.serve`` is called with the
CPU's device); the rest of a run is driven as on the chip: weights from
the seed, warm-up, the window, the sample, the reference.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import check  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402

SEED = 2**31 + 5
SECONDS = 3.0


def tiny_cell():
    c = spec.load_json(HERE / "tests" / "data" / "tiny.json")
    c["name"] = "tiny"
    m = spec.load_json(HERE / "tests" / "data" / "tiny_closed.json")
    m["name"] = "tiny_closed"
    return spec.Cell("tiny.tiny_closed", c, m, 1, [], [])


def serve(cell=None):
    return run.serve(cell or tiny_cell(), SEED, SECONDS, False,
                     jax.devices()[:1], None, "TPU v5 lite")


@pytest.fixture(scope="module")
def sound():
    return serve()


def test_a_sound_run_is_correct(sound):
    assert check.is_correct(sound["compared"]), sound["compared"]
    assert len(sound["picked"]) == 8


def test_the_control_fails(sound):
    """The reference in fp8 put in the program's place, read at the same
    positions, lies beyond the limit the program keeps."""
    limit = sound["compared"]["max_logit_gap"]["limit"]
    g = check.served_gaps(tiny_cell().config, sound["params"],
                          sound["picked"], sound["served"], sound["prompts"],
                          sound["seq_len"], sound["n_read"], quant="fp8")
    assert max(float(c.max()) for _, c in g.values()) > limit


def altered_tokens(monkeypatch):
    """Every decoded token is the one after the best."""
    import repro.models.sampling as sampling
    real = sampling.sample

    def sample(logits, cfg, key, temperature=0.0):
        return (real(logits, cfg, key, temperature) + 1) % cfg.vocab_size
    monkeypatch.setattr(sampling, "sample", sample)


def state_unchanged(monkeypatch):
    """The decode step returns the KV pool it was given."""
    import repro.serving.engine as engine
    real = engine.decode_loop_paged

    def loop(params, cfg, tokens, state, *a, **kw):
        toks, st = real(params, cfg, tokens, state, *a, **kw)
        return toks, dataclasses.replace(st, k=state.k, v=state.v)
    monkeypatch.setattr(engine, "decode_loop_paged", loop)


def half_batch(monkeypatch):
    """Each decode step leaves out half of its batch."""
    import repro.serving.engine as engine
    real = engine.ServingEngine._dispatch_decode

    def dispatch(self, slots, horizon):
        return real(self, sorted(slots)[: max(1, len(slots) // 2)], horizon)
    monkeypatch.setattr(engine.ServingEngine, "_dispatch_decode", dispatch)
    monkeypatch.setattr(check, "STALL_S", SECONDS / 4)


@pytest.mark.parametrize("fault", [altered_tokens, state_unchanged,
                                   half_batch])
def test_a_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = serve()
    assert not check.is_correct(out["compared"]), out["compared"]

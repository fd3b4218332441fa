"""Every per-layer reader on a synthetic window: the number it reads, and
None where the run holds nothing for it."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import counts  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402
from drive import Flight, Record  # noqa: E402
from peaks import peaks_for  # noqa: E402
from traffic import Req  # noqa: E402

from repro.serving.telemetry import Event  # noqa: E402

CONFIG = spec.load_json(HERE / "configs" / "starcoder2-3b.json")
METRICS = sorted(p.stem for p in (HERE / "metrics").glob("[a-z]*.py"))


def ctx(events=(), switches=(), kernel_s=0.004, decode_s=0.2):
    """A 10-s window: one request of prompt 100 prefilled at 1 s, then a
    token every 0.1 s; the trace holds 10 decode programs."""
    arr = [1.0 + 0.1 * i for i in range(20)]
    f = Flight(req=Req(0, 0, 100, 20), rid=0, due=0.5, sent=0.5,
               arrivals=arr, done=arr[-1])
    rec = Record(flights=[f], t0=0.0, t1=10.0, kv_used=[(1.0, 0.25),
                                                         (2.0, 0.75)],
                 switches=list(switches))
    red = xplane.Reduction(
        window_s=10.0, chips=1, busy_s=7.5, op_s={},
        module_s={"jit_fused(1)": decode_s, "jit__lambda(2)": 0.05},
        module_n={"jit_fused(1)": 10.0, "jit__lambda(2)": 1.0},
        kernel_s={"jit_fused(1)": kernel_s}, gaps=[])
    return {"rec": rec, "red": red, "events": list(events),
            "config": CONFIG, "traffic": {}, "peaks": peaks_for(
                "TPU v5 lite"), "chips": 1}


def test_every_reader_declares_itself():
    for name in METRICS:
        m = spec.load_metric(name)
        assert m.NAME == name and m.UNIT and m.LAYER


def test_readers_on_a_synthetic_window():
    ev = [Event("dispatch", 2.0, -1, 0, {"n": 16, "h": 1}),
          Event("dispatch", 3.0, -1, 0, {"n": 14, "h": 1}),
          Event("admit", 1.0, 0, 0, {"queue_delay_s": 0.5}),
          Event("dispatch", 11.0, -1, 0, {"n": 1, "h": 1})]
    c = ctx(ev, switches=[{"start": 5.0, "end": 6.5}])
    got = {n: spec.load_metric(n).compute(c) for n in METRICS}
    assert got["decode_batch_mean"] == 15.0
    assert got["kv_used_share"] == pytest.approx(50.0)
    assert got["decode_step_ms"] == pytest.approx(20.0)
    assert got["device_idle_share"] == pytest.approx(25.0)
    b = sum(counts.decode_attn_bytes(CONFIG, 100 + j) for j in range(1, 20))
    assert got["decode_attn_roofline"] == pytest.approx(
        100 * b / 0.004 / 819e9)
    flops = counts.prefill_flops(CONFIG, 100) + sum(
        counts.token_flops(CONFIG, 100 + j) for j in range(1, 20))
    assert got["mfu"] == pytest.approx(100 * flops / (10.0 * 197e12))


def test_readers_with_nothing_to_read_return_none():
    c = ctx(kernel_s=0.0, decode_s=0.0)
    c["red"].module_n = {}
    assert spec.load_metric("decode_attn_roofline").compute(c) is None
    assert spec.load_metric("decode_step_ms").compute(c) is None
    assert spec.load_metric("decode_batch_mean").compute(c) is None

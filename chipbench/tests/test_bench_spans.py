"""The program's spans in a traced run: self time, host time per tick,
prefill cost per 1,000 prompt tokens, and idle gaps named by the innermost
program span; then a traced CPU run of a tiny cell, whose trace holds the
spans nested as ``serving/telemetry.py`` lists them."""
import sys
import tempfile
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import run  # noqa: E402
import spans  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402
from drive import Flight, Record  # noqa: E402
from traffic import Req  # noqa: E402

MS = 1_000_000   # ns


def two_ticks():
    """A 100 ms window, two client ticks of 40 ms.  Tick 1 (0-40): admit
    0-2, a prefill 2-12 whose first-token read is 8-12, a table update
    12-14, a decode dispatch 14-18, finish 18-38 waiting 19-37, sync_log
    38-39.  Tick 2 (50-90): dispatch 50-55, finish 55-89 waiting 56-88,
    with a collection 88.5-89.  The device runs 14-37 and 55-88."""
    host = [("window", 0, 100 * MS), ("step", 0, 40 * MS),
            ("cluster.step", 0, 40 * MS), ("engine.admit", 0, 2 * MS),
            ("engine.prefill", 2 * MS, 12 * MS),
            ("engine.wait_device", 8 * MS, 12 * MS),
            ("engine.table_update", 12 * MS, 14 * MS),
            ("engine.decode_dispatch", 14 * MS, 18 * MS),
            ("engine.finish", 18 * MS, 38 * MS),
            ("engine.wait_device", 19 * MS, 37 * MS),
            ("cluster.sync_log", 38 * MS, 39 * MS),
            ("step", 50 * MS, 90 * MS), ("cluster.step", 50 * MS, 90 * MS),
            ("engine.decode_dispatch", 50 * MS, 55 * MS),
            ("engine.finish", 55 * MS, 89 * MS),
            ("engine.wait_device", 56 * MS, 88 * MS),
            ("gc", 88.5 * MS, 89 * MS)]
    ops = {"/device:TPU:0": [("%fusion.1 = bf16[2]{0} fusion(bf16[2] %a)",
                              14 * MS, 37 * MS),
                             ("%fusion.1 = bf16[2]{0} fusion(bf16[2] %a)",
                              55 * MS, 88 * MS)]}
    return xplane.Trace(ops=ops, modules={}, host=host)


def record(prompts=(100, 300), first=(0.01, 0.2), ticks=2):
    flights = [Flight(req=Req(i, 0, p, 8), rid=i, due=0.0, sent=0.0,
                      arrivals=[t, t + 0.01])
               for i, (p, t) in enumerate(zip(prompts, first))]
    return Record(flights=flights, t0=0.0, t1=0.1, ticks=ticks)


def test_self_time_is_what_no_child_covers():
    got = spans.self_ms(spans.program_spans(two_ticks()))
    assert got["cluster.step"] == pytest.approx((40 - 39) + (40 - 39))
    assert got["engine.prefill"] == pytest.approx(10 - 4)
    assert got["engine.finish"] == pytest.approx((20 - 18) + (34 - 32.5))
    assert got["engine.wait_device"] == pytest.approx(4 + 18 + 32)
    assert got["gc"] == pytest.approx(0.5)
    assert "step" not in got and "window" not in got


def test_step_host_time_leaves_out_the_waits():
    s = spans.program_spans(two_ticks())
    assert spans.step_host_ms(s) == pytest.approx(
        ((40 - 4 - 18) + (40 - 32)) / 2)


def test_prefill_cost_per_thousand_prompt_tokens():
    s = spans.program_spans(two_ticks())
    rec = record()          # only request 0's first token is in the window
    assert spans.prefilled_tokens(rec) == 100
    assert spans.prefill_ms_per_ktok(s, 100) == pytest.approx(10 / 0.1)
    assert spans.prefill_ms_per_ktok(s, 0) is None
    no_prefill = [x for x in s if x[0] != "engine.prefill"]
    assert spans.prefill_ms_per_ktok(no_prefill, 100) is None


def test_a_trace_without_program_spans_reads_nothing():
    tr = two_ticks()
    tr.host = [h for h in tr.host if not spans.kept(h[0])]
    s = spans.program_spans(tr)
    assert s == [] and spans.step_host_ms(s) is None
    assert spans.prefill_ms_per_ktok(s, 100) is None


def test_gaps_are_named_by_the_innermost_program_span():
    red = xplane.reduce(two_ticks())
    # 0-14: its middle (7 ms) lies in the prefill; 37-55: its middle in the
    # client's own time between ticks; 88-100: after the last tick
    assert [n for n, _ in red.gaps] == ["none", "engine.prefill", "none"]
    assert [t for _, t in red.gaps] == pytest.approx([0.018, 0.014, 0.012])
    rep = spans.report(two_ticks(), red, record())
    assert rep["ticks"] == 2
    assert rep["self_ms_per_tick"]["cluster.step"] == pytest.approx(1.0)
    assert rep["idle_gaps"][1][0] == "engine.prefill"


def test_the_recorded_chip_trace_reduces_as_before():
    """The recorded trace predates the program's spans: the reduction
    reads as ``xplane.load`` gives it."""
    path = str(HERE / "tests" / "data" / "codegen_1s.xplane.pb")
    a, b = xplane.reduce(xplane.load(path)), xplane.reduce(spans.load(path))
    assert (a.busy_s, a.op_s, a.module_s, a.kernel_s) == (
        b.busy_s, b.op_s, b.module_s, b.kernel_s)
    assert sorted(a.gaps) == sorted(b.gaps)
    assert spans.step_host_ms(spans.program_spans(spans.load(path))) is None


def test_a_traced_cpu_run_holds_the_program_spans_in_the_clients_step():
    c = spec.load_json(HERE / "tests" / "data" / "tiny.json")
    c["name"] = "tiny"
    m = spec.load_json(HERE / "tests" / "data" / "tiny_closed.json")
    m["name"] = "tiny_closed"
    cell = spec.Cell("tiny.tiny_closed", c, m, 1, [], [])
    with tempfile.TemporaryDirectory() as d:
        out = run.serve(cell, 2**31 + 11, 1.0, True, jax.devices()[:1], d,
                        "TPU v5 lite")
        tr = spans.load(xplane.find_xplane(d))
    (lo, hi), = [(s, e) for n, s, e in tr.host if n == "window"]
    held = sorted(((n, s, e) for n, s, e in tr.host
                   if n != "window" and lo <= s and e <= hi
                   and not n.startswith(spans.COMPILE_PREFIX)),
                  key=lambda x: (x[1], -x[2]))
    names = [n for n, _, _ in held]
    nests = {(n, names[p] if p >= 0 else None)
             for (n, _, _), p in zip(held, spans.parents(held)) if n != "gc"}
    top = {"engine.admit", "engine.table_update", "engine.decode_dispatch",
           "engine.finish", "cluster.sync_log", "cluster.post"}
    assert {("cluster.step", "step"), ("engine.wait_device", "engine.finish")
            } | {(n, "cluster.step") for n in top} <= nests
    assert nests <= ({("step", None), ("submit", None),
                      ("cluster.step", "step"),
                      ("engine.prefill", "cluster.step"),
                      ("engine.wait_device", "engine.prefill"),
                      ("engine.wait_device", "engine.finish")}
                     | {(n, "cluster.step") for n in top})
    assert names.count("cluster.step") == out["rec"].ticks
    assert spans.step_host_ms(spans.program_spans(tr)) > 0

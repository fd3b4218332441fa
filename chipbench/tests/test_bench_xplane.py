"""The trace reduction: busy time, per-op sums, kernels by program, idle
gaps named by the host span they fell in."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import xplane  # noqa: E402

MS = 1_000_000   # ns


FUSION1 = "%fusion.1 = bf16[16,3072]{1,0:T(8,128)} fusion(bf16[16] %a)"
KERNEL = ('%closed_call.3 = bf16[16,24,1,128]{3,2,1,0} custom-call(s32[16] '
          '%b), custom_call_target="tpu_custom_call"')


def synthetic():
    """Two chips over a 100 ms window.  Chip 0: a decode program 10-40 ms
    whose ``while`` holds two fusions and a kernel 15-35 ms, and a copy
    60-70 ms.  Chip 1: one fusion 20-80 ms.  The host waits 40-60 ms and
    steps 60-100 ms."""
    ops = {"/device:TPU:0": [
        ("%while.7 = (s32[], bf16[2]{0}) while((s32[], bf16[2]) %t)",
         10 * MS, 40 * MS),
        (FUSION1, 10 * MS, 15 * MS),
        (KERNEL, 15 * MS, 35 * MS),
        ("%fusion.2 = bf16[2]{0} fusion(bf16[2] %c)", 35 * MS, 40 * MS),
        ("%copy.4 = bf16[2]{0} copy(bf16[2] %d)", 60 * MS, 70 * MS),
        ("%copy.9 = bf16[2]{0} copy(bf16[2] %d)", 120 * MS, 130 * MS)],
        "/device:TPU:1": [(FUSION1, 20 * MS, 80 * MS)]}
    modules = {"/device:TPU:0": [("jit_fused(1)", 10 * MS, 40 * MS),
                                 ("jit_copy(2)", 60 * MS, 70 * MS)],
               "/device:TPU:1": [("jit_fused(1)", 20 * MS, 80 * MS)]}
    host = [("window", 0, 100 * MS), ("step", 0, 40 * MS),
            ("wait", 40 * MS, 60 * MS), ("step", 60 * MS, 100 * MS),
            ("submit", 62 * MS, 64 * MS)]
    return xplane.Trace(ops=ops, modules=modules, host=host)


def test_busy_is_the_union_of_ops_in_the_window_averaged_over_chips():
    red = xplane.reduce(synthetic())
    assert red.window_s == pytest.approx(0.1)
    assert red.chips == 2
    assert red.busy_s == pytest.approx((0.040 + 0.060) / 2)


def test_ops_modules_and_kernels():
    red = xplane.reduce(synthetic())
    assert red.op_s["fusion.1 (fusion)"] == pytest.approx((0.005 + 0.060) / 2)
    assert red.op_s["closed_call.3 (tpu_custom_call)"] == pytest.approx(
        0.020 / 2)
    assert "while.7 (while)" not in red.op_s  # a container, not a leaf
    assert "copy.9 (copy)" not in red.op_s    # outside the window
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s)
    assert red.module_n["jit_fused(1)"] == pytest.approx(1.0)
    assert red.module_s["jit_fused(1)"] == pytest.approx((0.03 + 0.06) / 2)
    assert red.kernel_s == {"jit_fused(1)": pytest.approx(0.020 / 2)}


def test_idle_gaps_are_named_by_the_host_span():
    red = xplane.reduce(synthetic())
    gaps = {round(s, 6): n for n, s in red.gaps}
    # chip 0: 0-10 (step), 40-60 (wait), 70-100 (step); chip 1: 0-20
    # (step), 80-100 (step)
    assert sorted(red.gaps, key=lambda g: -g[1])[0] == ("step",
                                                        pytest.approx(0.03))
    assert gaps[0.02] in ("wait", "step")
    assert sum(s for _, s in red.gaps) == pytest.approx(
        0.1 * 2 - (0.040 + 0.060))
    b = xplane.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion.1 (fusion)"


def test_a_trace_without_its_window_is_refused():
    tr = synthetic()
    tr.host = [h for h in tr.host if h[0] != "window"]
    with pytest.raises(ValueError):
        xplane.reduce(tr)


RECORDED = HERE / "tests" / "data" / "codegen_1s.xplane.pb"


def test_a_recorded_chip_trace():
    """``starcoder2-3b.codegen`` traced on one v5e, cut to the first second
    of its window (a chip run of PR 12; events outside that second and the
    names no kept event uses were dropped): the names the reduction keys
    on are there, busy time is the leaf ops' sum (up to the time a
    ``while`` spends between its body's ops), and the decode kernel runs
    inside the fused decode program."""
    red = xplane.reduce(xplane.load(str(RECORDED)))
    assert red.chips == 1 and red.window_s == pytest.approx(1.0)
    assert 0.9 < red.busy_s <= red.window_s
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s, rel=1e-3)
    fused = [k for k in red.module_s if k.startswith("jit_fused")]
    assert fused and red.module_n[fused[0]] >= 1
    assert red.kernel_s[fused[0]] > 0.5 * red.module_s[fused[0]]
    assert any("(tpu_custom_call)" in k for k in red.op_s)
    assert {n for n, _ in red.gaps} <= {"step", "submit", "none"}

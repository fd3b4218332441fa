"""The open loop with a live span switch, on four virtual CPU devices at a
test size: yi-9b's block shape, sharded replicas, tp=4 switched to two
tp=2 replicas with requests in flight, checked against the reference."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[2])
    import jax
    import check, e2e, run, spec
    here = spec.HERE / "tests" / "data"
    c = spec.load_json(here / "tiny_yi.json"); c["name"] = "tiny_yi"
    m = spec.load_json(here / "tiny_shift.json"); m["name"] = "tiny_shift"
    cell = spec.Cell("tiny_yi.tiny_shift", c, m, 4, [], [])
    out = run.serve(cell, 2**31 + 99, 6.0, False, jax.devices()[:4], None,
                    "TPU v5 lite")
    rec = out["rec"]
    print(json.dumps({
        "correct": check.is_correct(out["compared"]),
        "switches": rec.switches, "must": out["must"],
        "picked": [f.rid for f in out["picked"]],
        "ttft_p95_ms": e2e.ttft_p95_ms(rec),
        "due": sum(1 for f in rec.flights if rec.t0 <= f.due < rec.t1),
        "first_tokens": sum(1 for f in rec.flights if f.arrivals)}))
""")


def test_open_loop_switch_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(HERE), str(HERE.parent / "src")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sw = out["switches"]
    assert len(sw) == 1 and not sw[0]["rolled_back"]
    assert sw[0]["deployment"] == "DP=2 [(TP=2), (TP=2)]"
    assert sw[0]["migrated"] >= 1
    # requests in flight across the switch are in the checked sample
    assert set(out["must"]) & set(out["picked"])
    assert out["first_tokens"] == out["due"]
    assert out["correct"]

"""Readings that a cell's correctness limit is set from, and its control.

    python chipbench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 ... [--quant int8 fp8]

For each seed, in one process: the cell as ``run.py`` runs it (weights from
the seed, warm-up, a window of the cell's own load, the sample, the
reference), then the control on the same prompts and served tokens: the
reference with its weight matrices rounded to ``--quant``, read at the same
positions as the gap of the token it puts first.  Prints one JSON line per
seed with the program's widest gap and each control's, each with the
verdict that ``check.compare`` gives it at the configuration's limit, and a
last line with the largest program reading and the smallest control
reading.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check  # noqa: E402
import e2e  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
from drive import summary  # noqa: E402


def readings(cell, seed: int, seconds: float, quants: list, devices,
             device_kind=None) -> dict:
    """One seed: the program's widest gap and each control's, and the
    verdict of ``check.compare`` at the file's limit on each."""
    limit = cell.config["correct"]["max_logit_gap"]
    t = time.perf_counter()
    out = run.serve(cell, seed, seconds, False, devices, None, device_kind)
    rec = out["rec"]
    row = {"seed": seed,
           "program": out["compared"]["max_logit_gap"]["value"],
           "limit": limit,
           "program_correct": check.is_correct(out["compared"]),
           "output_tokens_per_s": e2e.output_tokens_per_s(rec),
           "tpot_p95_ms": e2e.tpot_p95_ms(rec),
           "ttft_p95_ms": e2e.ttft_p95_ms(rec),
           "builds_in_window": rec.builds_in_window,
           "page_buckets": summary(rec)["page_buckets"],
           "tokens_in_window": e2e.samples(rec)["tokens_in_window"],
           "short": out["compared"]["short_requests"]["value"],
           "failed": out["compared"]["failed_requests"]["value"],
           "positions": int(sum(len(g) for g, _ in out["gaps"].values())),
           "setup_s": rec.t0 - t, "reference_s": out["ref_s"]}
    for q in quants:
        g = check.served_gaps(cell.config, out["params"], out["picked"],
                              out["served"], out["prompts"], out["seq_len"],
                              out["n_read"], quant=q)
        row[q] = max(float(gc_.max()) for _, gc_ in g.values())
        # the control put in the program's place, judged as a run is
        as_served = {rid: (gc_, gc_) for rid, (_, gc_) in g.items()}
        row[f"{q}_correct"] = check.is_correct(
            check.compare(rec, as_served, out["served"], limit))
    del out
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--quant", nargs="*", default=["int8", "fp8"])
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = run.devices_or_exit(cell.chips)
    except run.BenchError as e:
        run.log(f"calibrate: {e}")
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed, args.seconds, args.quant, devices))
        print(json.dumps(rows[-1]), flush=True)
    last = {"program_max": max(r["program"] for r in rows)}
    for q in args.quant:
        last[f"{q}_min"] = min(r[q] for r in rows)
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

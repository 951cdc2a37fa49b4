#!/usr/bin/env python3
"""Find the knee of an open-loop served cell: one set-up, then a window at
each offered rate, lowest first, on the chip.

    python3 bench/sweep.py --workload graph3.serve --seeds 7 8 --seconds 51 \
        --rates 1 1.2 1.4

The graph comes from the first seed; each rate gets one window per seed,
whose requests come from that seed.  For each window it prints the
requests offered, the median and 90th percentile latency, the completion
rate (requests over the time to the last answer), the growth of the queue
(the median latency of the window's last third of requests against its
first third) and each distinct error with its count.  The knee is the
highest rate at which the queue does not grow; the cell's traffic file
states 0.8 of it.  Answers are not compared here.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere outside the checkout


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import numpy as np

    from bench import harness

    cell = harness.Cell.load(args.workload)
    harness.use_compile_cache()
    harness.device_info(cell.chips)
    runner = cell.module("runners", cell.traffic["runner"])
    out = ROOT / "artifacts" / "bench" / f"sweep-{args.seeds[0]}"
    out.mkdir(parents=True, exist_ok=True)
    ctx = harness.RunContext(cell=cell, seed=args.seeds[0], seconds=args.seconds,
                             trace=False, t_start=T_START, out_dir=out,
                             chips=cell.chips, counter=harness.CompileCounter())
    lg = runner.LoadgenProcess(cell.bench)
    try:
        data, pg, _ = runner.setup(ctx, lg, args.rates[0])
        for rate, seed in ((r, s) for r in args.rates for s in args.seeds):
            reqs = runner.make_requests(cell.traffic, cell.config, seed,
                                        args.seconds, rate)
            rec = runner.window(ctx, lg, pg, reqs, rate, f"r{rate}-{seed}")
            lat = np.array([np.inf if x is None else x for x in rec["latency_s"]])
            third = max(len(lat) // 3, 1)
            errs = list(rec["errors"].values())
            row = {"rate": rate, "seed": seed, "requests": len(lat),
                   **runner.latency_stats(rec, args.seconds
                                          + float(cell.traffic["reply_wait_s"])),
                   "completed_per_s": len(lat) / rec["elapsed_s"],
                   "first_third_ms": float(np.median(lat[:third]) * 1e3),
                   "last_third_ms": float(np.median(lat[-third:]) * 1e3),
                   "failed": int(sum(not ok for ok in rec["ok"])),
                   "never_answered": int(sum(x is None for x in rec["latency_s"])),
                   "errors": {e: errs.count(e) for e in sorted(set(errs))},
                   "coalesce_width": (rec["counters"]["width_sum"]
                                      / max(rec["counters"]["width_count"], 1))}
            print(json.dumps(row), flush=True)
    finally:
        lg.stop()


if __name__ == "__main__":
    main()

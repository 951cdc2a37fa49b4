#!/usr/bin/env python3
"""The controls: the reference put in the program's place with one step a
later change could be tempted by, read with the cell's own comparison.
Each must come out not correct.

    python3 bench/control.py --workload graph3.serve --seeds 11 12 13
    python3 bench/control.py --workload g500.analytics --seeds 11 12 13

* ``serve_open`` cells state exact answers, in no precision: the control
  breaks that guarantee by answering with the forward pass alone (no
  backward pass), for the sample of replies a run of ``run_seconds``
  compares.
* ``analytics_cycle`` cells state PageRank in float32: the control
  computes it in bfloat16, the precision below (on the default device).

Prints one JSON line per seed: each compared number beside its limit.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def serve_control(cell, seed: int, seconds: float) -> dict:
    from bench import reference

    runner = cell.module("runners", "serve_open")
    cfg, traffic = cell.config, cell.traffic
    data = cell.module("generators", cfg["generator"]).generate(cfg, seed)
    reqs = runner.make_requests(traffic, cfg, seed, seconds, float(traffic["rate_qps"]))
    ref = reference.PatternRef(data, cfg)
    rec = {"ok": [True] * len(reqs["texts"]),
           "masks": {i: runner.reply_masks(*ref.match(reqs["specs"][i], backward=False))
                     for i in reqs["keep"]}}
    return runner.check(data, cfg, reqs, rec)


def analytics_control(cell, seed: int) -> dict:
    import jax.numpy as jnp

    from bench import reference

    runner = cell.module("runners", "analytics_cycle")
    cfg, p = cell.config, cell.config["jobs"]
    data = cell.module("generators", cfg["generator"]).generate(cfg, seed)
    g = reference.RefGraph(data["src"], data["dst"])
    pr = reference.pagerank_lowp(g, damping=float(p["pagerank_damping"]),
                                 iters=int(p["pagerank_iters"]), dtype=jnp.bfloat16)
    return runner.check(g, cfg, [("pagerank", None, 0.0, pr)])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    from bench import harness

    cell = harness.Cell.load(args.workload)
    seconds = harness.load_json(ROOT / "BENCHMARK.json")["run_seconds"]
    for seed in args.seeds:
        if cell.traffic["runner"] == "serve_open":
            checks = serve_control(cell, seed, seconds)
        else:
            checks = analytics_control(cell, seed)
        row = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": all(v <= lim for v, lim in checks.values()),
                          "checks": row}), flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip.

    python3 bench/run.py --workload graph3.serve --seed 7 --seconds 51 --trace 0

The run makes its data from ``--seed``, builds the graph through the
program's bulk path, warms the cell's own programs (from the compile cache
after a cell's first run), measures for ``--seconds``, compares what the
window produced with the plain reference (``bench/reference.py``) and
prints one JSON object as the last line of standard output.  ``--trace 1``
records the profiler over the window and reports the per-layer metrics
instead of the end-to-end ones.  Without a TPU, or with fewer chips than
the cell asks for, it exits 2 before measuring and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere outside the checkout


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    cell = harness.Cell.load(args.workload)
    harness.use_compile_cache()
    device = harness.device_info(cell.chips)
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              device=device)
    harness.emit(result)


if __name__ == "__main__":
    main()

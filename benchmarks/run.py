"""Benchmark runner — one section per paper table/figure + kernel/roofline rows.

Prints ``name,us_per_call,derived`` CSV (benchmarks/common.emit).  Sections:
  tab1_build   — DI construction ladder (paper Tab. I / §V)
  fig6_insert  — attribute insertion per DIP variant (paper Fig. 6)
  fig5_query   — query throughput per DIP variant + impl (paper Fig. 5, §VII-B;
                 includes the DIP-LISTD linked-chase 10× validation)
  kernels      — Pallas kernels vs oracles (interpret mode)
  scan         — bit-packed vs byte mask plane: scan bandwidth/bytes-moved
                 at n≥1M and fused predicate+label match vs two-op
                 composition (JSON lines appended to ``BENCH_scan.json`` —
                 override with ``BENCH_JSON_PATH``; see bench_scan.py)
  match        — pattern-engine rows (beyond-paper; JSON lines via
                 benchmarks.common.emit_json, see bench_match.py)
  shard        — sharded-store locale sweep 1→8 virtual devices (JSON lines;
                 run ``python -m benchmarks.bench_shard`` standalone to get
                 8 virtual devices — in-process it sweeps what's visible)
  traverse     — frontier engine: k-hop CSR vs edge-centric vs k repeated
                 single-hop match() calls, property-aware components,
                 mesh sweep (JSON lines; ALWAYS appended to
                 ``BENCH_traverse.json`` — override with
                 ``BENCH_JSON_PATH``; see bench_traverse.py)
  analytics    — semiring analytics: weighted shortest paths, PageRank,
                 label-propagation communities + mesh sweep, every row
                 oracle-verified before timing (JSON lines appended to
                 ``BENCH_traverse.json`` like the traverse section — they
                 share the frontier engine; see bench_analytics.py)
  serve        — service layer: coalesced concurrent serving vs sequential
                 per-request baseline, concurrency 1/2/4/8, adaptive- vs
                 fixed-window, plus cross-process TCP rows (JSON lines;
                 ALWAYS appended to ``BENCH_serve.json`` — override with
                 ``BENCH_JSON_PATH`` — so the perf trajectory records;
                 see bench_serve.py)
  sample       — fused property-filtered neighborhood sampling: one-launch
                 pattern→sample vs match→host→per-seed-loop baseline, the
                 coalesced 8×256 batched launch, served QPS at c∈{1,8}
                 vs sequential submission, and sample+embed fused vs
                 two-program — every row oracle-verified before timing
                 (JSON lines; ALWAYS appended to ``BENCH_sample.json`` —
                 override with ``BENCH_JSON_PATH``; see bench_sample.py)
  ingest       — overlay subsystem: streamed-batch ingest on the delta
                 write path vs full-rebuild path, read latency under write
                 load, compaction ≡ from-scratch verification (JSON lines;
                 ALWAYS appended to ``BENCH_ingest.json`` — override with
                 ``BENCH_JSON_PATH``; see bench_ingest.py)
Roofline rows come from the dry-run: ``python -m benchmarks.roofline``.
"""
from __future__ import annotations

import os
import sys


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    small = "--small" in sys.argv
    print("name,us_per_call,derived")

    print("# tab1_build (DI construction, paper Tab. I ladder)")
    from benchmarks import bench_build
    bench_build.run(scales=(10_000, 100_000) if small else (10_000, 100_000, 1_000_000))

    print("# fig6_insert (attribute insertion per DIP variant)")
    from benchmarks import bench_insert
    bench_insert.run(scales=(100_000,) if small else (100_000, 1_000_000))

    print("# fig5_query (query throughput per DIP variant / impl)")
    from benchmarks import bench_query
    bench_query.run(m=100_000 if small else 1_000_000)

    print("# kernels (Pallas interpret vs jnp oracle)")
    from benchmarks import bench_kernels
    bench_kernels.run()

    print("# scan (bit-packed vs byte mask plane: bandwidth + fused match)")
    from benchmarks import bench_scan
    bench_scan.run(n=100_000 if small else 1_000_000,
                   json_path=os.environ.get("BENCH_JSON_PATH",
                                            "BENCH_scan.json"))

    print("# match (pattern engine: declarative vs hand-composed, fusion, skew)")
    from benchmarks import bench_match
    bench_match.run(m=20_000 if small else 100_000)

    print("# shard (sharded DIP stores: locale sweep over virtual devices)")
    from benchmarks import bench_shard
    bench_shard.run(m=20_000 if small else 100_000)

    print("# traverse (frontier engine: khop csr/frontier/per-hop-match, components)")
    from benchmarks import bench_traverse
    bench_traverse.run(m=20_000 if small else 100_000,
                       json_path=os.environ.get("BENCH_JSON_PATH",
                                                "BENCH_traverse.json"))

    print("# analytics (semiring engine: shortest paths, pagerank, communities)")
    from benchmarks import bench_analytics
    bench_analytics.run(m=20_000 if small else 100_000,
                        json_path=os.environ.get("BENCH_JSON_PATH",
                                                 "BENCH_traverse.json"))

    print("# serve (service layer: coalesced vs sequential, concurrency sweep,")
    print("#        adaptive vs fixed window, cross-process TCP)")
    from benchmarks import bench_serve
    bench_serve.run(m=10_000 if small else 50_000,
                    requests=32 if small else 64,
                    json_path=os.environ.get("BENCH_JSON_PATH",
                                             "BENCH_serve.json"))

    print("# sample (fused pattern→sample→embed: one-launch vs host loop, QPS)")
    from benchmarks import bench_sample
    bench_sample.run(m=10_000 if small else 50_000,
                     requests=32 if small else 64,
                     json_path=os.environ.get("BENCH_JSON_PATH",
                                              "BENCH_sample.json"))

    print("# ingest (overlay delta write path vs rebuild, reads under writes)")
    from benchmarks import bench_ingest
    bench_ingest.run(m=5_000 if small else 20_000,
                     json_path=os.environ.get("BENCH_JSON_PATH",
                                              "BENCH_ingest.json"))


if __name__ == "__main__":
    main()

"""Service-layer throughput/latency: coalesced concurrent serving vs the
sequential per-request baseline, in-process and over the pgd wire
(docs/ARCHITECTURE.md §8–§9).

The workload is ``launch.pgserve``'s synthetic multi-tenant stream: a
zipf-skewed draw over a 12-pattern pool — hot patterns repeat, the
distribution request coalescing and result caching exist for.  Rows (JSON
via ``benchmarks.common.emit_json``; ``benchmarks/run.py`` points them at
``BENCH_serve.json`` so the cross-PR perf trajectory records):

  * ``serve_seq_baseline_m{m}``      — per-request ``PropGraph.match`` loop
    (no service, no caches, no coalescing), the concurrency-independent
    denominator.
  * ``serve_arr_c{c}_m{m}``          — full service (adaptive-window
    micro-batching + coalesced launches + plan/result caches) at c
    closed-loop clients, c ∈ {1, 2, 4, 8}; ``speedup`` = qps / baseline.
  * ``serve_arr_cold_c1_m{mw}`` vs ``serve_arr_cold_fixedwin_c1_m{mw}`` —
    the ROADMAP "cold-pattern latency tax": result cache and submit
    fastpath disabled so EVERY request crosses the batching queue at c=1.
    Under the PR 3 fixed window a lone request sat out ``window_ms``
    before executing (p50 grows by ≈ the window); the adaptive window
    executes it immediately — compare the two rows' p50.  These rows run
    on a small graph (mw = min(m, 10k)) on purpose: they isolate the
    SCHEDULER's latency floor, which a large graph's execution time would
    bury in noise.
  * ``serve_arr_nocache_c{c}_m{m}``  — result cache disabled: what
    coalescing + plan caching buy on their own (the honesty row — every
    request executes).
  * ``serve_net_c{c}_m{m}``          — the same workload over TCP
    (``PGServer`` on a thread of this process, c ``PGClient``
    connections); measures the wire + framing overhead on top of the
    in-process rows.

Both paths are warmed first (jit compiles for every pattern shape and
every Q bucket; the wire server serves the same warmed graph), so rows
measure steady-state serving, not compilation; every row is
best-of-``repeats`` replays (closed-loop threading is highly exposed to
cgroup CPU-quota throttling — the best run is the least-interfered
estimate; ``runs`` in each row records it).  Each service row is verified
bitwise against direct match before timing, including through the wire.
"""
from __future__ import annotations

import argparse
from typing import Optional

import numpy as np

from benchmarks.common import emit_json


def _verify_service(svc_query, pg, pool) -> None:
    for p in pool:
        got = svc_query(p)
        ref = pg.match(p)
        assert (np.asarray(got.vertex_mask) == np.asarray(ref.vertex_mask)).all(), p
        assert (np.asarray(got.edge_mask) == np.asarray(ref.edge_mask)).all(), p


def run(m: int = 50_000, requests: int = 64, concurrencies=(1, 2, 4, 8),
        seed: int = 0, repeats: int = 3, net: bool = True,
        json_path: Optional[str] = None) -> None:
    from repro.launch.pgserve import (
        build_tenant_graph,
        pattern_pool,
        run_sequential,
        run_workload,
        run_workload_net,
        synthetic_workload,
        warm_serving_path,
    )
    from repro.service import PGClient, PGServer, Service, ServiceConfig

    pg = build_tenant_graph("arr", m, seed=seed)
    graphs = {"tenant0": pg}
    pool = pattern_pool()
    wl = synthetic_workload(sorted(graphs), pool, requests, seed=seed)

    # -- warmup: compile every pattern's propagation program AND every Q
    # bucket — batch composition varies with concurrency, so an unvisited
    # bucket would pay its compile inside a measured window
    warm_serving_path(pg, pool)

    # verification before timing: service ≡ direct match on every pattern
    with Service() as v:
        v.add_graph("tenant0", pg)
        _verify_service(lambda p: v.query("tenant0", p), pg, pool)

    seq = run_sequential(graphs, wl, repeats=repeats)
    emit_json(f"serve_seq_baseline_m{m}", seq["wall_s"] / requests,
              path=json_path, qps=round(seq["qps"], 1), requests=requests,
              m=m, runs=repeats, mode="sequential-match")

    def service_row(name: str, config, c: int, *, graph=pg, workload=wl,
                    baseline=None, **extra) -> None:
        with Service(config=config) as svc:  # fresh caches per row; jits warm
            svc.add_graph("tenant0", graph)
            met = run_workload(svc, workload, c, repeats=repeats)
            stats = svc.stats()
        if baseline is not None:
            extra["speedup"] = round(met["qps"] / baseline["qps"], 2)
        emit_json(
            name, met["wall_s"] / len(workload), path=json_path,
            qps=round(met["qps"], 1), concurrency=c,
            requests=len(workload),
            p50_ms=round(met["p50_ms"], 3), p95_ms=round(met["p95_ms"], 3),
            runs=repeats,
            coalesced_launches=stats.get("coalesced_launches", 0),
            result_hits=stats.get("result_hits", 0), **extra,
        )

    for c in concurrencies:
        service_row(f"serve_arr_c{c}_m{m}", None, c, baseline=seq, m=m,
                    mode="service-coalesced")

    # the fixed-window tax the adaptive window removes: with caches/fastpath
    # off, every c=1 request crosses the queue — under a fixed window it
    # waits out window_ms first, under the adaptive one it runs immediately.
    # Small graph on purpose (docstring): isolate the scheduler, not the
    # executor.
    mw = min(m, 10_000)
    pg_win = pg if mw == m else build_tenant_graph("arr", mw, seed=seed)
    if pg_win is not pg:
        warm_serving_path(pg_win, pool)
    cold = dict(result_cache_size=0, submit_fastpath=False)
    for name, cfg, mode in (
        (f"serve_arr_cold_c1_m{mw}", ServiceConfig(**cold),
         "service-cold-adaptive"),
        (f"serve_arr_cold_fixedwin_c1_m{mw}",
         ServiceConfig(adaptive_window=False, **cold),
         "service-cold-fixed-window"),
    ):
        service_row(name, cfg, 1, graph=pg_win, m=mw, mode=mode,
                    window_ms=ServiceConfig().window_ms)

    service_row(f"serve_arr_nocache_c{max(concurrencies)}_m{m}",
                ServiceConfig(result_cache_size=0), max(concurrencies),
                baseline=seq, m=m, mode="service-coalesce-only")

    # -- observability overhead guard (docs/ARCHITECTURE.md §13): the
    # metrics registry must be free when disabled and near-free when on.
    # Measure the same c=max coalesced workload with metrics enabled vs
    # disabled (fresh service each, jits warm) in ALTERNATING trials —
    # back-to-back blocks read scheduler drift as flag overhead at this
    # row's ~tens-of-ms wall time — take best-of per side, and record the
    # relative difference; the build fails if flipping the flag moves the
    # coalesce timing by ≥5%.
    from repro.obs import set_enabled

    cmax = max(concurrencies)

    def _measure(c: int):
        with Service() as svc:
            svc.add_graph("tenant0", pg)
            return run_workload(svc, wl, c, repeats=repeats)

    met_on, met_off = None, None
    for _ in range(max(repeats, 3)):
        m_on = _measure(cmax)
        prev = set_enabled(False)
        try:
            m_off = _measure(cmax)
        finally:
            set_enabled(prev)
        if met_on is None or m_on["wall_s"] < met_on["wall_s"]:
            met_on = m_on
        if met_off is None or m_off["wall_s"] < met_off["wall_s"]:
            met_off = m_off
    overhead = (met_on["wall_s"] - met_off["wall_s"]) / met_off["wall_s"]
    emit_json(
        f"serve_arr_metrics_off_c{cmax}_m{m}",
        met_off["wall_s"] / requests, path=json_path,
        qps=round(met_off["qps"], 1), concurrency=cmax, requests=requests,
        m=m, p50_ms=round(met_off["p50_ms"], 3), runs=repeats,
        qps_metrics_on=round(met_on["qps"], 1),
        metrics_overhead=round(overhead, 4), mode="service-metrics-disabled",
    )
    # one-sided: negative readings mean scheduler noise beat the best-of
    # filter (metrics can't make the service faster), not a regression
    assert overhead < 0.05, (
        f"metrics flag slowed c={cmax} coalesce timing by "
        f"{overhead:+.1%} (guard: <5%)")

    if not net:
        return
    # -- wire: same workload through a PGServer over TCP.  The server runs
    # as a thread of THIS process, which already holds the device: a
    # spawned server process could not open an accelerator the parent holds.
    with Service() as svc:
        svc.add_graph("tenant0", pg)
        server = PGServer(svc, port=0).start()
        try:
            with PGClient(port=server.port) as c0:
                _verify_service(lambda p: c0.query("tenant0", p), pg, pool)
            for c in (1, max(concurrencies)):
                met = run_workload_net(server.port, wl, c, repeats=repeats)
                emit_json(
                    f"serve_net_c{c}_m{m}", met["wall_s"] / requests,
                    path=json_path, qps=round(met["qps"], 1), concurrency=c,
                    requests=requests, m=m, p50_ms=round(met["p50_ms"], 3),
                    p95_ms=round(met["p95_ms"], 3),
                    speedup=round(met["qps"] / seq["qps"], 2), runs=repeats,
                    mode="service-net",
                )
        finally:
            server.close()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=50_000)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--no-net", action="store_true",
                    help="skip the TCP wire rows")
    ap.add_argument("--json-path", default=None)
    a = ap.parse_args()
    run(m=a.m, requests=a.requests, net=not a.no_net, json_path=a.json_path)

"""Graphalytics jobs in whole cycles: one analyst's closed loop.

One analyst calls ``PropGraph`` directly (library calls, no service, no
result cache): BFS from the generator's ``search_keys`` where it gives
them (Graph500's), else from sources drawn from the seed among vertices of
degree ≥ 1 (Graph500's search-key rule), PageRank, WCC (``components``)
and CDLP (``communities``), in a seeded order within each cycle.  Set-up
runs one job of each kind (compile, or read from the cache).  The window
runs whole cycles and closes when the cycle in flight at ``--seconds``
ends, so no job is cut and every run's mix is whole cycles.

``analytics_evps`` is LDBC Graphalytics' EVPS: (n + m) × jobs completed /
time from the window's start to the end of its last job, with n the
vertices and m the undirected edges, each counted once.

Correct means every job's result equals the plain reference: BFS depths,
WCC and CDLP labels exactly, PageRank to within the float32 limit the
configuration states.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from bench import harness, reference


def draw_sources(src: np.ndarray, dst: np.ndarray, count: int, seed: int) -> np.ndarray:
    """``count`` search keys, uniform over the generated ids of degree ≥ 1."""
    top = int(max(src.max(), dst.max())) + 1
    deg = np.bincount(src, minlength=top) + np.bincount(dst, minlength=top)
    rng = np.random.default_rng([seed, 20])
    out = []
    while len(out) < count:
        x = rng.integers(0, top, 4 * count)
        out.extend(int(v) for v in x[deg[x] > 0])
    return np.array(out[:count])


def jobs_for(pg, cfg: dict) -> Dict[str, callable]:
    p = cfg["jobs"]
    return {
        "bfs": lambda s: pg.bfs([s], max_iters=int(p["bfs_max_iters"])),
        "pagerank": lambda s: pg.pagerank(damping=float(p["pagerank_damping"]),
                                          iters=int(p["pagerank_iters"])),
        "wcc": lambda s: pg.components(),
        "cdlp": lambda s: pg.communities(max_iters=int(p["cdlp_iters"])),
    }


def run_cycles(ctx, jobs, order, sources, seconds: float, annotate: bool):
    """Whole cycles until ``seconds`` have passed (at most ``len(order)``);
    returns the jobs run as ``(kind, source, seconds, device result)`` and
    the elapsed time."""
    import jax

    done = []
    t0 = time.perf_counter()
    cycle = 0
    while cycle == 0 or (cycle < len(order) and time.perf_counter() - t0 < seconds):
        for kind in order[cycle]:
            src = int(sources[cycle])
            ann = jax.profiler.TraceAnnotation(f"bench.job.{kind}") if annotate else None
            if ann is not None:
                ann.__enter__()
            out, dt = harness.timed(lambda: jobs[kind](src))
            if ann is not None:
                ann.__exit__(None, None, None)
            done.append((kind, src, dt, out))
        cycle += 1
    return done, time.perf_counter() - t0


def check(g: reference.RefGraph, cfg: dict, done) -> Dict[str, tuple]:
    """Each job's ``(kind, source, seconds, result)`` against the
    reference, computed once per kind (once per source for BFS)."""
    p = cfg["jobs"]
    lim = cfg["limits"]
    wrong = {"bfs": 0, "wcc": 0, "cdlp": 0}
    pr_err = 0.0
    memo = {}
    for kind, src, _, out in done:
        got = np.asarray(out)
        if kind == "bfs":
            s = int(np.searchsorted(g.nodes, src))
            if ("bfs", s) not in memo:
                memo[("bfs", s)] = reference.bfs(g, s)
            want = memo[("bfs", s)]
        elif kind == "pagerank":
            if "pagerank" not in memo:
                memo["pagerank"] = reference.pagerank(
                    g, damping=float(p["pagerank_damping"]), iters=int(p["pagerank_iters"]))
            want = memo["pagerank"]
            if got.shape != want.shape:
                pr_err = np.inf
            else:
                pr_err = max(pr_err, float(np.max(np.abs(got - want) / want)))
            continue
        elif kind == "wcc":
            if "wcc" not in memo:
                memo["wcc"] = reference.wcc(g)
            want = memo["wcc"]
        else:
            if "cdlp" not in memo:
                memo["cdlp"] = reference.cdlp(g, iters=int(p["cdlp_iters"]))
            want = memo["cdlp"]
        wrong[kind] += (want.size if got.shape != want.shape
                        else int(np.count_nonzero(got != want)))
    return {"bfs_depths_wrong": (wrong["bfs"], 0),
            "wcc_labels_wrong": (wrong["wcc"], 0),
            "cdlp_labels_wrong": (wrong["cdlp"], 0),
            "pagerank_max_rel_err": (pr_err, float(lim["pagerank_max_rel_err"]))}


def setup(ctx):
    """Generate, build and warm; returns (data, pg, jobs, order, sources)."""
    import jax

    from repro.core import PropGraph

    cfg = ctx.cell.config
    t = time.perf_counter()
    data = ctx.cell.module("generators", cfg["generator"]).generate(cfg, ctx.seed)
    kinds = list(ctx.cell.traffic["jobs"])
    cycles = int(ctx.cell.traffic["max_cycles"])
    rng = np.random.default_rng([ctx.seed, 21])
    order = [[kinds[i] for i in rng.permutation(len(kinds))] for _ in range(cycles)]
    sources = data.get("search_keys")
    if sources is None:
        sources = draw_sources(data["src"], data["dst"], cycles + 1, ctx.seed)
    harness.log(f"setup: generate {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    pg = PropGraph(backend=cfg["backend"]).add_edges_from(data["src"], data["dst"])
    jax.block_until_ready(pg.graph.src)
    harness.log(f"setup: build {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    jobs = jobs_for(pg, cfg)
    for kind in kinds:
        _, dt = harness.timed(lambda: jobs[kind](int(sources[-1])))
        harness.log(f"setup: warm {kind} {dt:.3f} s")
    harness.log(f"setup: warm {time.perf_counter() - t:.3f} s")
    return data, pg, jobs, order, sources


def run(ctx) -> harness.Outcome:
    cfg = ctx.cell.config
    data, pg, jobs, order, sources = setup(ctx)
    with ctx.open_window():
        done, elapsed = run_cycles(ctx, jobs, order, sources, ctx.seconds, ctx.trace)
    mem = ctx.memory_peak()
    done = [(k, s, dt, np.asarray(out)) for k, s, dt, out in done]
    del pg, jobs
    gc.collect()
    harness.log("jobs: " + ", ".join(f"{k} {dt:.3f} s" for k, _, dt, _ in done)
                + f"; window {elapsed:.3f} s")
    t = time.perf_counter()
    g = reference.RefGraph(data["src"], data["dst"])
    checks = check(g, cfg, done)
    harness.log(f"reference: {time.perf_counter() - t:.3f} s")
    m_und = int(np.count_nonzero(g.src < g.dst))
    evps = (g.n + m_und) * len(done) / elapsed
    harness.log(f"graph: n {g.n}, undirected m {m_und}, stored m {g.m}")
    return harness.Outcome(
        attempted=len(done), failed=0,
        end_to_end={"analytics_evps": evps},
        layer={"jobs": [(k, dt) for k, _, dt, _ in done]},
        checks=checks, memory_peak_bytes=mem)

"""Graph500's Kronecker generator (spec §3), vectorised.

``edge_factor · 2**scale`` edges; at each of ``scale`` levels every edge
picks a quadrant with probabilities (A, B, C, 1−A−B−C), as the spec's
reference code does (``ii_bit = rand > A+B``, ``jj_bit = rand > C/(1−A−B)``
or ``A/(A+B)``).  Then the vertex labels are permuted and the edge list is
shuffled.  With ``undirected`` the graph is stored with each edge in both
directions after self-loops are dropped; duplicates are left for the
store, which keeps one edge per (u, v) anyway.

The quadrant draws, the vertex permutation and the ``search_keys`` BFS
sources (spec §4: vertices of degree ≥ 1, drawn at random) come from the
configuration's ``graph_seed``, as a benchmark's data set is one file; the
run's ``seed`` draws the order in which the edges are handed to the store.
So every seed runs the same graph and the same searches: the same n, m and
shapes, the same work, and every program found in the compile cache after
a cell's first run.  The ids stay fixed because the work of WCC depends on
them: its smallest-id hooking takes more or fewer rounds by where each
component's smallest id sits.
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
                    graph_seed: int):
    """The unpermuted edge list, in generation order."""
    n, m = 1 << scale, edge_factor << scale
    rng = np.random.default_rng([graph_seed, 0])
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    ij = np.zeros((2, m), np.int32)
    for level in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        ij[0] |= ii.astype(np.int32) << level
        ij[1] |= jj.astype(np.int32) << level
    return ij[0], ij[1]


def search_keys(u: np.ndarray, v: np.ndarray, n: int, count: int,
                graph_seed: int) -> np.ndarray:
    """``count`` distinct ids of degree ≥ 1, uniform, in the order drawn."""
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    return np.random.default_rng([graph_seed, 1]).permutation(
        np.flatnonzero(deg > 0))[:count]


def generate(cfg: dict, seed: int) -> dict:
    scale, gs = int(cfg["scale"]), int(cfg["graph_seed"])
    u, v = kronecker_edges(scale, int(cfg["edge_factor"]), float(cfg["a"]),
                           float(cfg["b"]), float(cfg["c"]), gs)
    keep = u != v
    u, v = u[keep], v[keep]
    keys = search_keys(u, v, 1 << scale, int(cfg["search_keys"]), gs)
    perm = np.random.default_rng([gs, 2]).permutation(1 << scale).astype(np.int32)
    order = np.random.default_rng([seed, 1]).permutation(len(u))
    u, v = perm[u][order], perm[v][order]
    if cfg.get("undirected", False):
        src, dst = np.concatenate([u, v]), np.concatenate([v, u])
    else:
        src, dst = u, v
    return {"src": src, "dst": dst, "search_keys": perm[keys]}

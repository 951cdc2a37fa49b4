"""The paper's §VII-A generator: uniform endpoints over a pool of m ids.

Two endpoint arrays of length ``edges`` filled with uniform integers from a
pool of ``vertex_pool`` ids (the paper sets the pool to m "to minimize the
amount of multiple edges"), so n ≈ 0.865·m distinct vertices.  Labels and
relationships follow §VII-A's attribute assignment: ``n`` (resp. ``m``)
draws, each picking an entity uniformly with replacement and giving it one
of ``labels`` (resp. ``relationships``) values — some entities get several,
some none.  ``age`` is one int per vertex, uniform in ``[0, age_max]``.

The graph and its attributes are one draw from the configuration's
``graph_seed``, as a benchmark's data set is one file; the run's ``seed``
renames the vertex ids by a permutation of the pool and shuffles the edge
order.  So every seed builds the same labelled graph up to its ids: the
same n, m and shapes, the same work, and every program found in the
compile cache after a cell's first run.
"""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, seed: int) -> dict:
    m, pool = int(cfg["edges"]), int(cfg["vertex_pool"])
    gs = int(cfg["graph_seed"])
    rng = np.random.default_rng([gs, 0])
    src0 = rng.integers(0, pool, m, dtype=np.int32)
    dst0 = rng.integers(0, pool, m, dtype=np.int32)
    nodes0 = np.unique(np.concatenate([src0, dst0]))
    n = len(nodes0)
    rng = np.random.default_rng([gs, 1])
    v_ent0 = rng.integers(0, n, n, dtype=np.int32)
    v_att = rng.integers(0, int(cfg["labels"]), n, dtype=np.int32)
    rng = np.random.default_rng([gs, 2])
    e_ent0 = rng.integers(0, m, m, dtype=np.int32)
    e_att = rng.integers(0, int(cfg["relationships"]), m, dtype=np.int32)
    age0 = np.random.default_rng([gs, 3]).integers(
        0, int(cfg["age_max"]) + 1, n).astype(np.int32)

    perm = np.random.default_rng([seed, 0]).permutation(pool).astype(np.int32)
    order = np.random.default_rng([seed, 1]).permutation(m)
    src, dst = perm[src0][order], perm[dst0][order]
    nodes = np.sort(perm[nodes0])
    at = np.searchsorted(nodes, perm[nodes0]).astype(np.int32)  # old position → new
    age = np.empty(n, np.int32)
    age[at] = age0
    inv = np.empty(m, np.int32)
    inv[order] = np.arange(m, dtype=np.int32)
    return {"src": src, "dst": dst, "nodes": nodes, "v_ent": at[v_ent0],
            "v_att": v_att, "e_ent": inv[e_ent0], "e_att": e_att, "age": age}

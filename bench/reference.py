"""Plain numpy references, built from the generated data alone.

Nothing here imports the program or reads what it made.  Vertex ids are
the sorted distinct generated ids and edges the sorted distinct (src, dst)
pairs over them — the order the store documents for its masks and result
arrays — so a reply can be compared entity by entity.

Pattern semantics (every served template is a chain of fixed single hops):
slot i's candidates are the vertices holding any of its labels and passing
its predicate; hop i's edges hold any of its relationships, with the tail
in slot i and the head in slot i+1 (``dir`` −1 reads the stored edge
backwards).  A forward pass keeps what is reachable along the chain, a
backward pass what also reaches its end; a vertex or edge is in the answer
when it lies on a whole match.
"""
from __future__ import annotations

import operator
from typing import Dict, List, Optional, Sequence

import numpy as np

PRED_OPS = {">": operator.gt, ">=": operator.ge, "<": operator.lt,
            "<=": operator.le, "==": operator.eq, "!=": operator.ne}


class RefGraph:
    """Distinct vertices (sorted ids) and distinct edges (sorted pairs)."""

    def __init__(self, src, dst, nodes: Optional[np.ndarray] = None):
        self.nodes = np.unique(np.concatenate([src, dst])) if nodes is None else nodes
        n = self.n = len(self.nodes)
        self._s = np.searchsorted(self.nodes, src).astype(np.int64)
        self._d = np.searchsorted(self.nodes, dst).astype(np.int64)
        self.key = np.unique(self._s * n + self._d)
        self.src = (self.key // n).astype(np.int32)
        self.dst = (self.key % n).astype(np.int32)
        self.m = len(self.key)

    def edge_of_raw(self, raw: np.ndarray) -> np.ndarray:
        """Edge id of each generated (raw) edge index."""
        return np.searchsorted(self.key, self._s[raw] * self.n + self._d[raw])


class AttrIndex:
    """(entity, attribute) pairs, answering "any of these attributes"."""

    def __init__(self, ent: np.ndarray, att: np.ndarray, size: int, k: int):
        order = np.argsort(att, kind="stable")
        self.members = ent[order]
        self.bounds = np.searchsorted(att[order], np.arange(k + 1))
        self.size = size

    def any_of(self, ids: Sequence[int]) -> np.ndarray:
        out = np.zeros(self.size, bool)
        for a in ids:
            out[self.members[self.bounds[a]:self.bounds[a + 1]]] = True
        return out


class PatternRef:
    """The served-pattern reference over a generated labelled graph."""

    def __init__(self, data: Dict[str, np.ndarray], cfg: dict):
        self.g = RefGraph(data["src"], data["dst"], data["nodes"])
        g = self.g
        self.labels = AttrIndex(data["v_ent"], data["v_att"], g.n, int(cfg["labels"]))
        self.rels = AttrIndex(g.edge_of_raw(data["e_ent"]), data["e_att"], g.m,
                              int(cfg["relationships"]))
        self.props = {"age": data["age"]}

    def _cand(self, node: dict) -> np.ndarray:
        c = (self.labels.any_of(node["labels"]) if node["labels"]
             else np.ones(self.g.n, bool))
        if node.get("pred"):
            name, op, value = node["pred"]
            c &= PRED_OPS[op](self.props[name], value)
        return c

    def match(self, spec: dict, *, backward: bool = True):
        """``(vertex mask, edge mask, [slot masks])`` of a chain pattern.
        ``backward=False`` stops after the forward pass."""
        g = self.g
        cands = [self._cand(nd) for nd in spec["nodes"]]
        fwd, local, ends = [cands[0]], [], []
        for i, e in enumerate(spec["edges"]):
            tail, head = (g.src, g.dst) if e["dir"] == 1 else (g.dst, g.src)
            ok = (self.rels.any_of(e["rels"]) if e["rels"] else np.ones(g.m, bool))
            ok &= cands[i][tail] & cands[i + 1][head]
            local.append(ok)
            ends.append((tail, head))
            nxt = np.zeros(g.n, bool)
            nxt[head[ok & fwd[i][tail]]] = True
            fwd.append(nxt)
        h = len(local)
        if not backward:
            alive = [local[i] & fwd[i][ends[i][0]] for i in range(h)]
            back = fwd
        else:
            back: List[Optional[np.ndarray]] = [None] * (h + 1)
            back[h] = fwd[h]
            alive = [None] * h
            for i in range(h - 1, -1, -1):
                tail, head = ends[i]
                alive[i] = local[i] & fwd[i][tail] & back[i + 1][head]
                b = np.zeros(g.n, bool)
                b[tail[alive[i]]] = True
                back[i] = b
        vmask = np.logical_or.reduce(back)
        emask = np.logical_or.reduce(alive) if h else np.zeros(g.m, bool)
        return vmask, emask, list(back)


def wrong_bits(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> int:
    """Entities on which a reply differs from the reference, over every
    mask; a missing or mis-shaped mask counts all of its entities."""
    bad = 0
    for k, w in want.items():
        a = got.get(k)
        if a is None or np.shape(a) != w.shape:
            bad += w.size
        else:
            bad += int(np.count_nonzero(np.asarray(a, bool) != w))
    return bad


# ------------------------------------------------------------ Graphalytics
def bfs(g: RefGraph, source: int) -> np.ndarray:
    """Hop depth from ``source`` along stored edges, −1 where unreached."""
    depth = np.full(g.n, -1, np.int32)
    depth[source] = 0
    frontier = np.zeros(g.n, bool)
    frontier[source] = True
    level = 0
    while frontier.any():
        level += 1
        reach = np.zeros(g.n, bool)
        reach[g.dst[frontier[g.src]]] = True
        frontier = reach & (depth < 0)
        depth[frontier] = level
    return depth


def pagerank(g: RefGraph, *, damping: float, iters: int) -> np.ndarray:
    """float64 power iteration; dangling mass spread over every vertex."""
    out_deg = np.bincount(g.src, minlength=g.n).astype(np.float64)
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1), 0.0)
    r = np.full(g.n, 1.0 / g.n)
    for _ in range(iters):
        agg = np.bincount(g.dst, weights=(r * inv)[g.src], minlength=g.n)
        r = (1 - damping) / g.n + damping * (agg + r[out_deg == 0].sum() / g.n)
    return r


def pagerank_lowp(g: RefGraph, *, damping: float, iters: int, dtype) -> np.ndarray:
    """The same iteration with every value held in ``dtype`` (the control:
    one precision below the float32 the store computes in)."""
    import jax
    import jax.numpy as jnp

    out_deg = np.bincount(g.src, minlength=g.n)
    inv = jnp.asarray(np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1), 0.0), dtype)
    dangling = jnp.asarray(out_deg == 0)
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    tele = jnp.asarray((1 - damping) / g.n, dtype)
    r = jnp.full(g.n, 1.0 / g.n, dtype)
    for _ in range(iters):
        agg = jax.ops.segment_sum((r * inv)[src], dst, g.n)
        dang = jnp.sum(jnp.where(dangling, r, jnp.zeros((), dtype)))
        r = (tele + jnp.asarray(damping, dtype) * (agg + dang / g.n)).astype(dtype)
    return np.asarray(r.astype(jnp.float32), np.float64)


def wcc(g: RefGraph) -> np.ndarray:
    """Weakly connected components, each labelled by its smallest vertex."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    _, comp = connected_components(
        coo_matrix((np.ones(g.m, np.int8), (g.src, g.dst)), shape=(g.n, g.n)),
        directed=True, connection="weak")
    first = np.full(comp.max() + 1, g.n, np.int64)
    np.minimum.at(first, comp, np.arange(g.n))
    return first[comp].astype(np.int32)


def cdlp(g: RefGraph, *, iters: int) -> np.ndarray:
    """Graphalytics CDLP on the undirected graph: each round every vertex
    takes the label most frequent among its neighbours (each neighbour
    counted once), the smallest on a tie; a vertex with no neighbour keeps
    its own.  Labels start as vertex ids."""
    und = g.src < g.dst  # each undirected edge once (stored both ways)
    u, v = g.src[und].astype(np.int64), g.dst[und].astype(np.int64)
    heads = np.concatenate([u, v])
    tails = np.concatenate([v, u])
    labels = np.arange(g.n, dtype=np.int64)
    n = g.n
    for _ in range(iters):
        key = np.sort(heads * n + labels[tails])
        start = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        count = np.diff(np.r_[start, len(key)])
        vert, lab = key[start] // n, key[start] % n
        vstart = np.flatnonzero(np.r_[True, vert[1:] != vert[:-1]])
        best = np.maximum.reduceat(count, vstart)
        best_of = np.repeat(best, np.diff(np.r_[vstart, len(vert)]))
        top = np.flatnonzero(count == best_of)
        first = top[np.r_[True, vert[top][1:] != vert[top][:-1]]]
        new = labels.copy()
        new[vert[first]] = lab[first]
        if np.array_equal(new, labels):
            break
        labels = new
    return labels.astype(np.int32)

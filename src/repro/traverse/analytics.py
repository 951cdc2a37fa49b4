"""Property-aware analytics over the semiring frontier engine.

The paper's §I workloads (cybersecurity flows, brain networks) are
reachability-shaped: "which hosts are within k ``flows``-hops of a flagged
host", "components of the ``follows`` subgraph".  The Arachne follow-up
work (community detection, weighted analytics) extends the same shape to
numeric semirings.  These run here as clients of
:func:`repro.traverse.engine.semiring_relax` that RESPECT the property
layer: every function takes (or derives from a single-hop pattern, via
``single_hop_filters``) vertex/edge masks and an optional numeric edge
weight, so labels, relationship types and typed-property predicates all
filter the traversal — no subgraph is ever materialized.

Instances (docs/ARCHITECTURE.md §12):

  * ``components_masked``       — (min, select) min-hook label propagation
    + pointer jumping to a fixed point.
  * ``shortest_paths_masked``   — (min, +) tropical Bellman–Ford from a
    seed set over a numeric edge property; unreachable = +inf.
  * ``pagerank_masked``         — (+, ×) power iteration with out-degree
    normalization on the property-filtered subgraph (the §I kernel,
    filter-aware).
  * ``label_propagation_masked``— mode relax (argmax neighbor-label count,
    smallest label breaks ties): synchronous label propagation, the
    community-detection entry point.

``single_hop_filters`` is the shared pattern→masks front door for
``PropGraph.khop`` / ``components`` / ``shortest_paths`` / ``pagerank`` /
``communities``: a node-only or single-hop pattern
(``"(a:host)-[:flows {bytes > 0}]->(b)"``) becomes
(tail mask, head mask, edge mask, direction), the same §VI masks the
query engine composes.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.di import DIGraph
from repro.traverse.engine import (
    COUNTING,
    MINLABEL,
    TROPICAL,
    _all_edges,
    _pad_edges,
    _sharded_relax_fn,
    semiring_relax,
)

__all__ = [
    "components_masked",
    "shortest_paths_masked",
    "shortest_paths_sharded",
    "pagerank_masked",
    "pagerank_sharded",
    "label_propagation_masked",
    "single_hop_filters",
]


@partial(jax.jit, static_argnames=("max_iters",))
def components_masked(
    g: DIGraph,
    vertex_allowed: Optional[jax.Array] = None,
    edge_allowed: Optional[jax.Array] = None,
    *,
    max_iters: int = 128,
) -> jax.Array:
    """Connected components of the masked subgraph: (n,) int32 labels
    (component id = smallest member vertex id), -1 for vertices outside
    ``vertex_allowed``.  Edges are treated as undirected; an edge
    participates iff its own mask AND both endpoint masks are set.
    The hook step is the (min, select) :data:`MINLABEL` instance of the
    semiring relax, iterated with pointer jumping: O(log n) rounds."""
    n = g.n
    v_ok = jnp.ones((n,), jnp.bool_) if vertex_allowed is None else vertex_allowed
    e_ok = jnp.ones((g.m,), jnp.bool_) if edge_allowed is None else edge_allowed
    e_act = e_ok & v_ok[g.src] & v_ok[g.dst]
    big = jnp.int32(n)  # sentinel: excluded vertices never hook anything
    labels0 = jnp.where(v_ok, jnp.arange(n, dtype=jnp.int32), big)

    def body(state):
        labels, _, it = state
        hook = semiring_relax(g, labels, e_act, MINLABEL, undirected=True)
        new = jnp.minimum(labels, hook)
        # pointer jumping — only real labels (< n) chase; the sentinel
        # would index out of range
        jumped = new[jnp.clip(new, 0, max(n - 1, 0))]
        new = jnp.where(new < n, jumped, new)
        return new, jnp.any(new != labels), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), jnp.int32(0)))
    return jnp.where(v_ok, labels, jnp.int32(-1))


# ------------------------------------------------------- shortest paths (min,+)
@partial(jax.jit, static_argnames=("direction", "undirected", "max_iters"))
def shortest_paths_masked(
    g: DIGraph,
    seed_mask: jax.Array,
    weights: Optional[jax.Array] = None,
    edge_allowed: Optional[jax.Array] = None,
    *,
    direction: int = 1,
    undirected: bool = False,
    max_iters: Optional[int] = None,
) -> jax.Array:
    """Multi-source shortest-path distances over the (min, +) tropical
    semiring: (n,) f32, 0.0 at the seeds, +inf where unreachable.

    Bellman–Ford as a frontier fixed point: each round relaxes every
    allowed edge (``dist' = min(dist, ⊕ dist[tail] + w)``) inside one
    jitted ``while_loop`` with early exit when no distance improves.
    ``weights`` defaults to unit weights (hop counts); masked edges carry
    +inf (the ⊗ absorber), so they never relax.  With non-negative
    weights n-1 rounds always suffice; ``max_iters`` (default n+1) bounds
    the loop so a negative cycle cannot spin it forever."""
    w = (jnp.ones((g.m,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    e_ok = _all_edges(g, edge_allowed)
    ew = jnp.where(e_ok, w, jnp.inf)
    dist0 = jnp.where(seed_mask, jnp.float32(0), jnp.inf)
    bound = (g.n + 1) if max_iters is None else max_iters

    def body(state):
        dist, _, it = state
        new = jnp.minimum(dist, semiring_relax(
            g, dist, ew, TROPICAL, direction=direction, undirected=undirected))
        return new, jnp.any(new != dist), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < bound)

    dist, _, _ = jax.lax.while_loop(
        cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))
    return dist


@lru_cache(maxsize=None)
def _sharded_bellman_fn(mesh, direction: int, undirected: bool):
    """Jitted tropical Bellman–Ford whose relax runs under ``shard_map``:
    per-device partial (n,) distance vectors, ⊕-combined with ONE ``pmin``
    all-reduce per round.  min over f32 is exact, so the result is
    bitwise-identical to the single-device path."""
    from repro.launch.sharding import pg_entity_shards

    step = _sharded_relax_fn(mesh, direction, undirected, TROPICAL)
    p = pg_entity_shards(mesh)

    @partial(jax.jit, static_argnames=("max_iters",))
    def fn(g: DIGraph, dist0, ew, *, max_iters: int):
        tail, head, ew = _pad_edges(g, ew, p, direction, TROPICAL.zero)

        def body(state):
            dist, _, it = state
            new = jnp.minimum(dist, step(tail, head, ew, dist))
            return new, jnp.any(new != dist), it + 1

        def cond(state):
            _, changed, it = state
            return changed & (it < max_iters)

        dist, _, _ = jax.lax.while_loop(
            cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))
        return dist

    return fn


def shortest_paths_sharded(
    g: DIGraph,
    seed_mask: jax.Array,
    weights: Optional[jax.Array] = None,
    edge_allowed: Optional[jax.Array] = None,
    *,
    mesh,
    direction: int = 1,
    undirected: bool = False,
    max_iters: Optional[int] = None,
) -> jax.Array:
    """``shortest_paths_masked`` with the per-round shard_map/``pmin``
    all-reduce layout; bitwise-identical to the single-device path."""
    w = (jnp.ones((g.m,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    ew = jnp.where(_all_edges(g, edge_allowed), w, jnp.inf)
    dist0 = jnp.where(seed_mask, jnp.float32(0), jnp.inf)
    fn = _sharded_bellman_fn(mesh, direction, undirected)
    bound = (g.n + 1) if max_iters is None else max_iters
    return fn(g, dist0, ew, max_iters=bound)


# ------------------------------------------------------------ pagerank (+, ×)
@partial(jax.jit, static_argnames=("iters",))
def pagerank_masked(
    g: DIGraph,
    vertex_allowed: Optional[jax.Array] = None,
    edge_allowed: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    *,
    damping: float = 0.85,
    iters: int = 20,
) -> jax.Array:
    """PageRank on the property-filtered subgraph: (n,) f32 ranks, 0.0
    outside ``vertex_allowed``.

    Power iteration whose per-step aggregation is the (+, ×)
    :data:`COUNTING` instance of the semiring relax: contributions
    ``rank[tail] / out_deg[tail] · w[e]`` scatter-⊕ (sum) into the heads.
    Out-degrees are (weight-)summed over ALLOWED edges only; an edge
    participates iff its own mask AND both endpoint masks are set.
    Dangling mass (allowed vertices with no allowed out-edge) and the
    teleport term redistribute over the |allowed| vertex count — with no
    vertex filter this is exactly the classic iteration the §I kernel
    suite ran (``repro.graph.pagerank`` now delegates here)."""
    w = (jnp.ones((g.m,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    if edge_allowed is not None:
        w = jnp.where(edge_allowed, w, jnp.float32(0))
    tail, head = g.src, g.dst
    if vertex_allowed is not None:
        w = jnp.where(vertex_allowed[tail] & vertex_allowed[head], w,
                      jnp.float32(0))
        n_eff = jnp.maximum(jnp.sum(vertex_allowed.astype(jnp.float32)), 1.0)
        r0 = jnp.where(vertex_allowed, 1.0 / n_eff, 0.0).astype(jnp.float32)
    else:
        n_eff = g.n  # static: keeps the unfiltered formula exactly the
        # pre-semiring graph/algorithms.py iteration (regression-pinned to
        # 1 ulp — the relax scatter fuses differently than segment_sum)
        r0 = jnp.full((g.n,), 1.0 / max(g.n, 1), jnp.float32)
    out_deg = jax.ops.segment_sum(w, tail, g.n)
    inv_deg = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1e-30), 0.0)

    def step(r, _):
        agg = semiring_relax(g, r * inv_deg, w, COUNTING)
        dangling = jnp.sum(jnp.where(out_deg > 0, 0.0, r))
        r_new = (1 - damping) / n_eff + damping * (agg + dangling / n_eff)
        if vertex_allowed is not None:
            r_new = jnp.where(vertex_allowed, r_new, 0.0)
        return r_new, None

    r, _ = jax.lax.scan(step, r0, None, length=iters)
    return r


@lru_cache(maxsize=None)
def _sharded_pagerank_fn(mesh):
    """Jitted power iteration whose aggregation runs under ``shard_map``:
    per-device partial contribution sums, ⊕-combined with ONE ``psum``
    all-reduce per step.  float sums reassociate across device blocks, so
    the sharded ranks agree with the single-device path within tolerance
    (atol), not bitwise — the one non-idempotent ⊕ in the table (§12)."""
    from repro.launch.sharding import pg_entity_shards

    step_relax = _sharded_relax_fn(mesh, 1, False, COUNTING)
    p = pg_entity_shards(mesh)

    @partial(jax.jit, static_argnames=("iters",))
    def fn(g: DIGraph, v_ok, w, damping, *, iters: int):
        tail, head, wp = _pad_edges(g, w, p, 1, COUNTING.zero)
        n_eff = jnp.maximum(jnp.sum(v_ok.astype(jnp.float32)), 1.0)
        out_deg = jax.ops.segment_sum(w, tail[: g.m], g.n)
        inv_deg = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1e-30), 0.0)
        r0 = jnp.where(v_ok, 1.0 / n_eff, 0.0).astype(jnp.float32)

        def step(r, _):
            agg = step_relax(tail, head, wp, r * inv_deg)
            dangling = jnp.sum(jnp.where(out_deg > 0, 0.0, r))
            r_new = (1 - damping) / n_eff + damping * (agg + dangling / n_eff)
            return jnp.where(v_ok, r_new, 0.0), None

        r, _ = jax.lax.scan(step, r0, None, length=iters)
        return r

    return fn


def pagerank_sharded(
    g: DIGraph,
    vertex_allowed: Optional[jax.Array] = None,
    edge_allowed: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    *,
    mesh,
    damping: float = 0.85,
    iters: int = 20,
) -> jax.Array:
    """``pagerank_masked`` with the per-step shard_map/``psum`` all-reduce
    layout; equal to the single-device path within float tolerance."""
    w = (jnp.ones((g.m,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    if edge_allowed is not None:
        w = jnp.where(edge_allowed, w, jnp.float32(0))
    tail, head = g.src, g.dst
    v_ok = (jnp.ones((g.n,), jnp.bool_) if vertex_allowed is None
            else vertex_allowed)
    if vertex_allowed is not None:
        w = jnp.where(v_ok[tail] & v_ok[head], w, jnp.float32(0))
    fn = _sharded_pagerank_fn(mesh)
    return fn(g, v_ok, w, jnp.float32(damping), iters=iters)


# ------------------------------------------------- label propagation (mode)
@partial(jax.jit, static_argnames=("max_iters",))
def label_propagation_masked(
    g: DIGraph,
    vertex_allowed: Optional[jax.Array] = None,
    edge_allowed: Optional[jax.Array] = None,
    *,
    max_iters: int = 64,
) -> jax.Array:
    """Community detection by synchronous label propagation: (n,) int32
    community labels, -1 outside ``vertex_allowed``.

    Mode relax under a FIXED deterministic tie-break: every round, every
    allowed vertex simultaneously adopts the most frequent label among its
    allowed neighbors (edges count as undirected, both endpoint masks and
    the edge mask gate participation); ties break toward the SMALLEST
    label; a vertex with no allowed incident edge keeps its label.  Labels
    start as vertex ids, so label ids are always member vertex ids.

    The per-round mode is built from the engine's scatter-⊕ machinery: a
    two-key lexicographic sort groups (head, neighbor label) pairs (no
    fused int key — safe for any n, m < 2**31 with x64 off), a segment
    sum counts each group, then two idempotent ⊕ scatters pick the
    argmax: scatter-max the counts per head, scatter-min the labels that
    achieve them.  Every op is integer, so the result is exact — sharded
    execution (GSPMD over placed arrays) is bitwise-identical; there is
    no hand-written all-reduce path because partial per-device label
    counts would need a cross-device join, not an elementwise ⊕.

    Synchronous updates can oscillate on bipartite structures, so the
    fixed point is capped at ``max_iters`` rounds (the sequential oracle
    in tests/test_semiring.py replays the same rule and cap)."""
    n = g.n
    v_ok = jnp.ones((n,), jnp.bool_) if vertex_allowed is None else vertex_allowed
    e_ok = jnp.ones((g.m,), jnp.bool_) if edge_allowed is None else edge_allowed
    labels0 = jnp.where(v_ok, jnp.arange(n, dtype=jnp.int32), jnp.int32(0))
    if g.m == 0 or n == 0:
        return jnp.where(v_ok, labels0, jnp.int32(-1))
    e_act = e_ok & v_ok[g.src] & v_ok[g.dst]
    # undirected: every edge contributes its tail's label to its head in
    # both orientations
    heads = jnp.concatenate([g.dst, g.src])
    tails = jnp.concatenate([g.src, g.dst])
    ok2 = jnp.concatenate([e_act, e_act])
    n_pos = int(heads.shape[0])

    def body(state):
        labels, _, it = state
        h = jnp.where(ok2, heads, jnp.int32(n))  # masked pairs sort last
        l = jnp.where(ok2, labels[tails], jnp.int32(0))
        sh, sl = jax.lax.sort((h, l), num_keys=2)
        start = jnp.concatenate([
            jnp.ones((1,), jnp.bool_),
            (sh[1:] != sh[:-1]) | (sl[1:] != sl[:-1])])
        sid = jnp.cumsum(start.astype(jnp.int32)) - 1
        valid = sh < n
        group_cnt = jax.ops.segment_sum(
            valid.astype(jnp.int32), sid, num_segments=n_pos,
            indices_are_sorted=True)
        cnt = group_cnt[sid]  # every position carries its group's count
        shc = jnp.clip(sh, 0, max(n - 1, 0))
        best_cnt = jnp.zeros((n,), jnp.int32).at[sh].max(
            jnp.where(valid, cnt, 0), mode="drop")
        is_best = valid & (cnt == best_cnt[shc])
        best_lab = jnp.full((n,), n, jnp.int32).at[sh].min(
            jnp.where(is_best, sl, jnp.int32(n)), mode="drop")
        new = jnp.where(best_lab < n, best_lab, labels)
        return new, jnp.any(new != labels), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), jnp.int32(0)))
    return jnp.where(v_ok, labels, jnp.int32(-1))


def single_hop_filters(
    pg, pattern
) -> Tuple[Optional[jax.Array], Optional[jax.Array], Optional[jax.Array], int]:
    """Derive traversal filters from a node-only or single-hop pattern.

    Returns ``(tail_mask, head_mask, edge_mask, direction)`` — each mask
    ``None`` when unconstrained.  For ``(a:x {p})-[:r {q}]->(b:y)``: an
    edge is traversable iff it holds ``r`` and satisfies ``q``, its tail
    (in traversal order — ``<-[...]-`` flips it) matches ``a`` and its
    head matches ``b``.  A node-only pattern constrains BOTH endpoints
    (traversal confined to matching vertices).  Multi-hop and
    variable-length patterns are rejected: k-hop/components/shortest
    paths take their step structure from ``k``/the fixed point, not from
    the pattern — this is the ``shortestPath()``-style hook (a path
    predicate wraps a single-hop step pattern, never a chain).
    """
    from repro.query import parse
    from repro.query.planner import validate_pattern

    if pattern is None:
        return None, None, None, 1
    pat = parse(pattern) if isinstance(pattern, str) else pattern
    if pat.hops > 1:
        raise ValueError(
            f"khop/components take a node-only or single-hop filter pattern, "
            f"got {pat.hops} hops in {pat.to_text()!r}")
    validate_pattern(pat)  # plan-time contract: string predicates etc.

    def node_mask(node):
        mask = None
        if node.labels:
            mask = pg.query_labels(list(node.labels))
        for p in node.predicates:
            pm = pg.vertex_predicate_mask(p.name, p.op, p.value)
            mask = pm if mask is None else mask & pm
        return mask

    if pat.hops == 0:
        vm = node_mask(pat.nodes[0])
        return vm, vm, None, 1

    edge = pat.edges[0]
    if not edge.is_fixed:
        raise ValueError(
            f"variable-length hop {edge.to_text()!r} in a khop/components "
            "filter: the traversal depth comes from k / the fixed point, "
            "use a plain single-hop filter")
    em = None
    if edge.rels:
        em = pg.query_relationships(list(edge.rels))
    for p in edge.predicates:
        pm = pg.edge_predicate_mask(p.name, p.op, p.value)
        em = pm if em is None else em & pm
    return node_mask(pat.nodes[0]), node_mask(pat.nodes[1]), em, edge.direction

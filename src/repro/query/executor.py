"""Plan execution — one fused, jitted constraint-propagation pipeline.

Execution in three stages:

1. **Mask materialization** (host-orchestrated, device-executed): every
   planned attribute mask runs through the DIP store with the planner's
   chosen impl; ``arr`` node-label masks marked ``fused`` go through the
   batched ``bitmap_query`` entry in ONE launch.  Predicate masks come off
   the typed property columns.
2. **Local consistency**: per hop, an edge survives iff its own mask is set
   and both endpoint candidate masks are set (the §VI mask-intersection
   contract, directional — ``induce_edge_mask`` generalized per endpoint).
3. **Chain propagation** (single jit, static hop structure): a forward pass
   computes per-position reachable sets, a backward pass prunes to vertices
   /edges that participate in at least one COMPLETE match of the pattern —
   the ``repro.traverse`` frontier step run once in each direction instead
   of k times in one.  Variable-length hops (``-[:r*lo..hi]->``, ``*``)
   expand through the same step: bounded hops unroll ``hi`` exact-length
   frontier layers in each direction and combine them (walk-length algebra
   below); unbounded hops run the frontier to a fixed point
   (``while_loop``, ≤ n rounds).  For a var hop between slots i and i+1
   with forward layers ``u_s`` (s steps from the forward-complete slot-i
   set) and backward layers ``w_t`` (t reverse steps from the
   backward-complete slot-i+1 set):

     slot-i survivors   = fwd_i ∧ ∪_{L∈[lo,hi]} w_L
     hop edges (alive)  = allowed ∧ ∪_{s+t∈[lo-1,hi-1]} u_s[tail] ∧ w_t[head]
     interior vertices  = ∪_{s,t≥1, lo≤s+t≤hi} u_s ∧ w_t

   Interior vertices are unconstrained by the slot masks (Cypher-style);
   every traversed edge must satisfy the hop's relationship/predicate
   masks.  Matches are WALKS: a traversal may revisit vertices and edges
   (see query/README.md "Variable-length hops").

The result is exact (not an estimate): ``vertex_mask``/``edge_mask`` are
the unions of all full-pattern assignments.

Sharded execution (``PropGraph(mesh=...)``): stages 1–2 run shard-local —
every DIP mask comes off a ``shard_map`` query that touches only the
device's own entity slice (``core.dip_shard``), and predicate masks come
off entity-sharded columns.  At the mask-combination point the per-slot
candidate masks are replicated across the mesh in ONE all-gather
(``_gather_masks``) so the chain propagation's arbitrary src/dst gathers
run collective-free; masks are tiny (1 byte/entity) next to the stores the
shard-local stage avoided streaming.

Compacted fixed hops.  A fixed hop whose slot has a relationship step
touches only that relationship's edges, and the plan already bounds how
many: ``MaskStep.est_count`` (Σ of exact ``attr_counts``, an upper bound
under overlap).  ``_finish_propagation`` gives such a hop the static
capacity ``cap = bucket(est_count)`` (next power of two, at least
``COMPACT_MIN_CAP``) and ``_propagate`` runs it over ``cap`` edge ids
instead of all m: one sort of the m keys ``where(mask, iota, m)`` yields
the hop's ids ascending (sentinel m pads), and every gather and scatter of
the hop — forward heads, backward tails, the (m,) ``alive`` write-back —
spans ``cap`` lanes.  The compaction is a sort, not ``nonzero(size=)``,
whose cumsum-and-bincount lowering is an m-wide scatter-add: the very cost
removed.  Crossover, measured on one TPU v5e at m = 10⁷, n = 8.6·10⁶: a
dense 1-hop chain (four m-wide gathers, two m-wide scatter-max, each an
index sort plus a serial update) takes 448 ms, ≈ 45 ns an edge; a
compacted one at cap = 2¹⁸ takes 35 ms, of which the m-key sort is 10 ms
(≈ 1 ns a key) and the cap-wide gathers, scatters and fills ≈ 95 ns a
lane.  So compaction wins while 1·m + 95·cap < 45·m, cap ≲ 0.46·m;
``COMPACT_MAX_SHARE`` = 1/4 leaves twice that room for the model's error,
and larger caps keep the dense hop.  Variable-length hops, hops with no
relationship step and mesh graphs stay dense.  Plans are cached across
writes, so a bound can go stale: the program counts each compacted hop's
mask on the device and ``lax.cond`` runs the whole chain dense unless every
count fits its cap — the answer is exact either way, bit for bit the dense
one.
"""
from __future__ import annotations

import dataclasses
import operator
from functools import partial, reduce
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitplane
from repro.core.di import DIGraph
from repro.core.queries import extract_subgraph, induce_edge_mask_directed
from repro.obs.metrics import GLOBAL as _OBS
from repro.obs.metrics import enabled as _obs_enabled
from repro.query.plan import Plan
from repro.traverse.engine import frontier_step, reach_closure

__all__ = ["MatchResult", "execute_plan", "execute_plan_with_masks"]

# process-global execution accounting (docs/ARCHITECTURE.md §13) —
# resolved once at import; host-side counts only, never a device sync
_M_PLANS = _OBS.counter("pg_exec_plans", "plans run through propagation")
_M_MASKS = _OBS.counter("pg_exec_mask_steps", "attribute mask steps materialized")
_M_FUSED = _OBS.counter(
    "pg_exec_fused_masks", "mask steps that rode a fused batched launch")
_M_COMPACT = _OBS.counter(
    "pg_exec_compact_hops", "hops dispatched over their compacted edge list")
_M_DENSE = _OBS.counter(
    "pg_exec_dense_hops", "hops dispatched over all m edges")

# compacted fixed hops (module docstring): the smallest capacity bucket, and
# the largest share of m a capacity may take before the dense hop is cheaper
COMPACT_MIN_CAP = 1024
COMPACT_MAX_SHARE = 0.25


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["vertex_mask", "edge_mask", "node_masks", "edge_masks"],
    meta_fields=["plan"],
)
@dataclasses.dataclass(frozen=True)
class MatchResult:
    """Result of ``PropGraph.match``: exact participation masks.

    ``node_masks[i]`` / ``edge_masks[i]`` are per-slot masks in the PLAN's
    chain order (use ``bindings()`` for name-keyed access — variable names
    travel with their slots through any planner reorientation).  For a
    variable-length hop, ``edge_masks[i]`` covers every edge on some
    matched walk of that hop, and interior walk vertices appear in
    ``vertex_mask`` but in no ``node_masks`` slot (they bind no variable).
    Registered as a pytree (masks = leaves) so ``jax.block_until_ready`` /
    ``jit`` compose with results directly.
    """

    vertex_mask: jax.Array  # (n,) bool — vertices in ≥1 full match
    edge_mask: jax.Array  # (m,) bool — edges in ≥1 full match
    node_masks: Tuple[jax.Array, ...]  # per node slot, (n,) bool
    edge_masks: Tuple[jax.Array, ...]  # per edge slot, (m,) bool
    plan: Plan

    def bindings(self) -> Dict[str, jax.Array]:
        """Variable name → participation mask (node vars (n,), edge vars (m,))."""
        out: Dict[str, jax.Array] = {}
        for node, mask in zip(self.plan.pattern.nodes, self.node_masks):
            if node.var:
                out[node.var] = out[node.var] | mask if node.var in out else mask
        for edge, mask in zip(self.plan.pattern.edges, self.edge_masks):
            if edge.var:
                out[edge.var] = out[edge.var] | mask if edge.var in out else mask
        return out

    def n_vertices(self) -> int:
        return int(jnp.sum(self.vertex_mask))

    def n_edges(self) -> int:
        return int(jnp.sum(self.edge_mask))

    def subgraph(self, g: DIGraph):
        """Materialize the matched edges as a fresh DI graph."""
        return extract_subgraph(g, self.edge_mask)

    def expand(self, g: DIGraph, k: int, *, edge_allowed: Optional[jax.Array] = None):
        """NScale-style neighborhood expansion: vertices within ``k`` hops of
        the match, following ``edge_allowed`` (default: every edge)."""
        from repro.graph.typed_algorithms import khop_typed

        seeds = jnp.asarray(np.flatnonzero(np.asarray(self.vertex_mask)), jnp.int32)
        allowed = (
            jnp.ones((g.m,), jnp.bool_) if edge_allowed is None else edge_allowed
        )
        return khop_typed(g, seeds, allowed, k=k)


def _fill_get(mask: jax.Array, idx: jax.Array) -> jax.Array:
    """``mask[idx]`` with out-of-range lanes (the compaction's padding)
    reading False."""
    return mask.at[idx].get(mode="fill", fill_value=False)


def _hop_edges(g: DIGraph, emask: jax.Array, cap: int, d: int):
    """The first ``cap`` ids of ``emask``'s edges, ascending, padded with the
    sentinel m, and their tail and head ends (padding reads n): one sort of
    the m keys ``where(emask, iota, m)``."""
    keys = jnp.where(emask, jnp.arange(g.m, dtype=jnp.int32), jnp.int32(g.m))
    ids = lax.sort(keys, is_stable=False)[:cap]
    src = g.src.at[ids].get(mode="fill", fill_value=g.n)
    dst = g.dst.at[ids].get(mode="fill", fill_value=g.n)
    return (ids, src, dst) if d == 1 else (ids, dst, src)


@partial(jax.jit, static_argnames=("hops",))
def _propagate(
    g: DIGraph,
    cands: Tuple[jax.Array, ...],
    emasks: Tuple[jax.Array, ...],
    hops: Tuple[Tuple[int, int, int, int], ...],
):
    """Forward/backward chain propagation (static hop structure ⇒ fully
    unrolled, one XLA program for the whole pattern).  ``hops`` carries one
    ``(direction, lo, hi, cap)`` per hop; ``hi == -1`` means unbounded;
    ``cap > 0`` runs a fixed hop over its compacted edge list of ``cap``
    lanes (module docstring), ``0`` over all m edges.

    The compacted chain holds only while every compacted hop's mask has at
    most ``cap`` edges; otherwise ``lax.cond`` runs the dense chain, so a
    stale bound costs time, never an edge.
    """
    caps = [(i, cap) for i, (_, _, _, cap) in enumerate(hops) if cap]
    if not caps:
        return _chain(g, cands, emasks, hops)
    fits = reduce(operator.and_, [
        jnp.sum(emasks[i], dtype=jnp.int32) <= cap for i, cap in caps])
    dense = tuple((d, lo, hi, 0) for d, lo, hi, _ in hops)
    return lax.cond(fits, partial(_chain, hops=hops),
                    partial(_chain, hops=dense), g, cands, emasks)


def _chain(g: DIGraph, cands, emasks, hops):
    """The chain propagation itself, traced inside ``_propagate``.

    Fixed hops ((d, 1, 1) — the original math):
      forward:  f_0 = c_0;  f_i = heads(A_i ∧ f_{i-1}[tail])
      backward: b_h = f_h;  alive_i = A_i ∧ f_{i-1}[tail] ∧ b_i[head];
                b_{i-1} = tails(alive_i)
    where A_i is the locally-consistent edge set of hop i and tail/head
    follow each hop's direction.  b_i = position-i vertices on a full match;
    alive_i = hop-i edges on a full match.  A compacted hop computes the
    same sets over its edge list; since f_i ⊆ c_i and b_i ⊆ f_i, it reads
    A_i ∧ f_{i-1}[tail] as f_{i-1}[tail] ∧ c_i[head] on the hop's edges.

    Variable-length hops run the module-docstring walk algebra through
    ``repro.traverse.frontier_step``: bounded hops keep exact-step frontier
    layers in both directions; unbounded hops keep the two fixed-point
    closures.  Interior walk vertices are returned separately (they belong
    to no slot) and union into the vertex mask only.
    """
    h = len(hops)
    ends = [(g.src, g.dst) if d == 1 else (g.dst, g.src) for d, *_ in hops]

    fwd = [cands[0]]
    local = [None] * h  # fixed hops: locally-consistent edge sets
    sub = [None] * h  # compacted hops: (ids, tail, head, forward survivors)
    flayers = [None] * h  # bounded var hops: forward exact-step layers
    fclosure = [None] * h  # unbounded var hops: forward closure
    for i, (d, lo, hi, cap) in enumerate(hops):
        tail, head = ends[i]
        if cap:
            ids, t, hd = _hop_edges(g, emasks[i], cap, d)
            a = _fill_get(fwd[i], t) & _fill_get(cands[i + 1], hd)
            sub[i] = (ids, t, hd, a)
            fwd.append(jnp.zeros_like(cands[i + 1]).at[hd].max(a, mode="drop"))
        elif (lo, hi) == (1, 1):
            local[i] = induce_edge_mask_directed(
                g, cands[i], cands[i + 1], emasks[i], d)
            a = local[i] & fwd[i][tail]
            fwd.append(jnp.zeros_like(cands[i + 1]).at[head].max(a))
        elif hi == -1:
            U = reach_closure(g, fwd[i], emasks[i], direction=d)
            fclosure[i] = U
            reach = U if lo == 0 else frontier_step(g, U, emasks[i], direction=d)
            fwd.append(cands[i + 1] & reach)
        else:
            layers = [fwd[i]]
            for _ in range(hi):
                layers.append(frontier_step(g, layers[-1], emasks[i], direction=d))
            flayers[i] = layers
            reach = layers[lo]
            for L in range(lo + 1, hi + 1):
                reach = reach | layers[L]
            fwd.append(cands[i + 1] & reach)

    back = [None] * (h + 1)
    back[h] = fwd[h]
    alive = [None] * h
    interiors = []  # var-hop walk vertices that belong to no slot
    for i in range(h - 1, -1, -1):
        d, lo, hi, cap = hops[i]
        tail, head = ends[i]
        if cap:
            ids, t, hd, a = sub[i]
            al = a & _fill_get(back[i + 1], hd)
            alive[i] = jnp.zeros((g.m,), jnp.bool_).at[ids].set(al, mode="drop")
            back[i] = jnp.zeros_like(fwd[i]).at[t].max(al, mode="drop")
        elif (lo, hi) == (1, 1):
            al = local[i] & fwd[i][tail] & back[i + 1][head]
            alive[i] = al
            back[i] = jnp.zeros_like(fwd[i]).at[tail].max(al)
        elif hi == -1:
            U = fclosure[i]
            W = reach_closure(g, back[i + 1], emasks[i], direction=-d)
            alive[i] = emasks[i] & U[tail] & W[head]
            back[i] = fwd[i] & (
                W if lo == 0 else frontier_step(g, W, emasks[i], direction=-d))
            interiors.append(
                frontier_step(g, U, emasks[i], direction=d)
                & frontier_step(g, W, emasks[i], direction=-d)
            )
        else:
            u = flayers[i]
            w = [back[i + 1]]
            for _ in range(hi):
                w.append(frontier_step(g, w[-1], emasks[i], direction=-d))
            # prefix unions keep the per-s window unions O(1) whenever the
            # window reaches down to its base (always true for lo ≤ 1, the
            # common patterns) — without them this pass is O(hi²) masks,
            # the program-size blowup MAX_VARLEN exists to bound
            pre0 = [w[0]]  # pre0[j] = w[0] | … | w[j]
            for t in range(1, hi + 1):
                pre0.append(pre0[-1] | w[t])
            pre1 = [None, w[1]] if hi >= 1 else [None]  # pre1[j] = w[1] | … | w[j]
            for t in range(2, hi + 1):
                pre1.append(pre1[-1] | w[t])

            def w_union(a, b):  # ∪ w[a..b], 0 ≤ a ≤ b ≤ hi
                if a == 0:
                    return pre0[b]
                if a == 1:
                    return pre1[b]
                out = w[a]
                for t in range(a + 1, b + 1):
                    out = out | w[t]
                return out

            back[i] = fwd[i] & w_union(lo, hi)
            acc = jnp.zeros((g.m,), jnp.bool_)
            for s in range(hi):
                hu = w_union(max(0, lo - 1 - s), hi - 1 - s)
                acc = acc | (u[s][tail] & hu[head])
            alive[i] = emasks[i] & acc
            inter = jnp.zeros((g.n,), jnp.bool_)
            for s in range(1, hi):
                a, b = max(1, lo - s), hi - s
                if a <= b:
                    inter = inter | (u[s] & w_union(a, b))
            interiors.append(inter)

    vmask = back[0]
    for b in back[1:]:
        vmask = vmask | b
    for x in interiors:
        vmask = vmask | x
    if h:
        emask = alive[0]
        for a in alive[1:]:
            emask = emask | a
    else:
        emask = jnp.zeros((g.m,), jnp.bool_)
    return vmask, emask, tuple(back), tuple(alive)


def _fused_step_sets(plan: Plan):
    """The (node steps, edge steps) riding the fused batched launches, plus
    the fused slot-id sets — shared by the bool and packed materializers so
    the ``pg_exec_fused_masks`` accounting is identical on both paths."""
    fused_n = set(plan.fused_node_slots)
    fused_e = set(getattr(plan, "fused_edge_slots", ()))
    nsteps = [s for s in plan.mask_steps if s.kind == "node" and s.slot in fused_n]
    esteps = [s for s in plan.mask_steps if s.kind == "edge" and s.slot in fused_e]
    if _obs_enabled():
        _M_MASKS.inc(len(plan.mask_steps))
        _M_FUSED.inc(len(nsteps) + len(esteps))
    return fused_n, fused_e, nsteps, esteps


def _materialize_masks(pg, plan: Plan) -> Tuple[Dict[int, jax.Array], Dict[int, jax.Array]]:
    """Run every planned attribute mask, fusing batched slots into one call.

    Node AND edge slots marked fused each coalesce into one
    ``query_any_batched`` launch against their store (node and edge stores
    are distinct (K, N) planes, so that is the launch floor: two)."""
    node_masks: Dict[int, jax.Array] = {}
    edge_masks: Dict[int, jax.Array] = {}

    fused_n, fused_e, fused_nsteps, fused_esteps = _fused_step_sets(plan)
    if fused_nsteps:
        stacked = pg._vstore.query_any_batched(
            [s.values for s in fused_nsteps], impl=fused_nsteps[0].impl
        )
        for s, row in zip(fused_nsteps, stacked):
            node_masks[s.slot] = row
    if fused_esteps:
        stacked = pg._estore.query_any_batched(
            [s.values for s in fused_esteps], impl=fused_esteps[0].impl
        )
        for s, row in zip(fused_esteps, stacked):
            edge_masks[s.slot] = row

    for s in plan.mask_steps:
        if s.kind == "node" and s.slot not in fused_n:
            node_masks[s.slot] = pg._vstore.query_any(s.values, impl=s.impl)
        elif s.kind == "edge" and s.slot not in fused_e:
            edge_masks[s.slot] = pg._estore.query_any(s.values, impl=s.impl)
    return node_masks, edge_masks


def _materialize_mask_words(pg, plan: Plan) -> Tuple[Dict[int, jax.Array], Dict[int, jax.Array]]:
    """Packed analog of ``_materialize_masks``: every mask stays a uint32
    word vector off the stores' packed planes — no bool materialization."""
    node_words: Dict[int, jax.Array] = {}
    edge_words: Dict[int, jax.Array] = {}

    fused_n, fused_e, fused_nsteps, fused_esteps = _fused_step_sets(plan)
    if fused_nsteps:
        stacked = pg._vstore.query_any_batched_words(
            [s.values for s in fused_nsteps], impl=fused_nsteps[0].impl
        )
        for s, row in zip(fused_nsteps, stacked):
            node_words[s.slot] = row
    if fused_esteps:
        stacked = pg._estore.query_any_batched_words(
            [s.values for s in fused_esteps], impl=fused_esteps[0].impl
        )
        for s, row in zip(fused_esteps, stacked):
            edge_words[s.slot] = row

    for s in plan.mask_steps:
        if s.kind == "node" and s.slot not in fused_n:
            node_words[s.slot] = pg._vstore.query_any_words(s.values, impl=s.impl)
        elif s.kind == "edge" and s.slot not in fused_e:
            edge_words[s.slot] = pg._estore.query_any_words(s.values, impl=s.impl)
    return node_words, edge_words


def _gather_masks(masks, mesh):
    """The sharded pipeline's single all-gather: replicate the combined
    per-slot masks across the mesh in ONE batched transfer."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    return list(jax.device_put(list(masks), [rep] * len(masks)))


# predicate ops mirrored from PropGraph._PRED_OPS (plain operator functions;
# kept local so the fused combine needs no property_graph import)
_PRED_FNS = {
    "==": operator.eq, "!=": operator.ne, "<": operator.lt,
    "<=": operator.le, ">": operator.gt, ">=": operator.ge,
}


def _ones_words(n: int) -> jax.Array:
    """Packed all-True mask over ``n`` entities — full words 0xFFFFFFFF,
    tail bits zero (the invariant every word-space AND/OR preserves)."""
    w = bitplane.n_words(n)
    words = jnp.full((w,), 0xFFFFFFFF, jnp.uint32)
    rem = n % bitplane.WORD
    if w and rem:
        words = words.at[-1].set(jnp.uint32((1 << rem) - 1))
    return words


@partial(jax.jit, static_argnames=("n", "m", "vops", "eops"))
def _combine_packed(nwords, ewords, vpreds, epreds, av, ae, *,
                    n: int, m: int, vops, eops):
    """The fused mask-combination launch (tentpole stage 3): predicate
    evaluation, bit-packing, word-space AND with label/relationship words
    and packed tombstone masks, and the SINGLE unpack at the propagation
    boundary — one jitted program instead of one mask op per predicate
    composed through separate dispatches.

    ``nwords[slot]`` / ``ewords[slot]``: packed store words or None
    (unconstrained).  ``vpreds[slot]`` / ``epreds[slot]``: tuples of
    ``(col, valid, value)`` with the matching op names in the static
    ``vops`` / ``eops``.  ``av`` / ``ae``: alive bool masks or None.
    """
    av_w = bitplane.pack_mask(av) if av is not None else None
    ae_w = bitplane.pack_mask(ae) if ae is not None else None

    def combine(words, preds, ops, size, alive_w):
        out = words if words is not None else _ones_words(size)
        for (col, valid, value), op in zip(preds, ops):
            pm = valid & _PRED_FNS[op](col, value)
            if int(pm.shape[0]) < size:  # short edge column: pad rows invalid
                pm = jnp.concatenate(
                    [pm, jnp.zeros((size - int(pm.shape[0]),), jnp.bool_)])
            out = out & bitplane.pack_mask(pm)
        if alive_w is not None:
            out = out & alive_w
        return bitplane.unpack_mask(out, size)

    cands = tuple(
        combine(nwords[i], vpreds[i], vops[i], n, av_w)
        for i in range(len(nwords)))
    emasks = tuple(
        combine(ewords[i], epreds[i], eops[i], m, ae_w)
        for i in range(len(ewords)))
    return cands, emasks


def _packed_combine_applies(pg) -> bool:
    """The packed end-to-end combine path: single-device arr graphs whose
    stores hold word planes.  Mesh graphs keep the bool combine (their
    masks replicate across devices before propagation anyway) but still
    scan packed planes inside ``dip_shard``."""
    return (
        pg.backend == "arr"
        and getattr(pg, "mesh", None) is None
        and pg._vstore.packed
        and pg._estore.packed
    )


def _execute_plan_packed(pg, plan: Plan) -> MatchResult:
    """Packed execution: store words → fused predicate/alive combine in
    word space → ONE unpack at the propagation boundary."""
    g = pg._require_graph()
    if _obs_enabled():
        _M_PLANS.inc()
    node_words, edge_words = _materialize_mask_words(pg, plan)

    n_slots = len(plan.pattern.nodes)
    e_slots = len(plan.pattern.edges)
    vpreds = [[] for _ in range(n_slots)]
    vops = [[] for _ in range(n_slots)]
    epreds = [[] for _ in range(e_slots)]
    eops = [[] for _ in range(e_slots)]
    for step in plan.predicate_steps:
        # host-side validation (KeyError/ValueError/TypeError fire eagerly,
        # before any launch) + raw column fetch for the fused combine
        col, valid = pg._predicate_parts(
            step.kind, step.predicate.name, step.predicate.op,
            step.predicate.value)
        entry = (col, valid, jnp.asarray(step.predicate.value))
        if step.kind == "node":
            vpreds[step.slot].append(entry)
            vops[step.slot].append(step.predicate.op)
        else:
            epreds[step.slot].append(entry)
            eops[step.slot].append(step.predicate.op)

    av = pg._alive_vertex_mask() if hasattr(pg, "_alive_vertex_mask") else None
    ae = pg._alive_edge_mask() if hasattr(pg, "_alive_edge_mask") else None
    cands, emasks = _combine_packed(
        tuple(node_words.get(i) for i in range(n_slots)),
        tuple(edge_words.get(i) for i in range(e_slots)),
        tuple(map(tuple, vpreds)), tuple(map(tuple, epreds)), av, ae,
        n=g.n, m=g.m,
        vops=tuple(map(tuple, vops)), eops=tuple(map(tuple, eops)))
    return _finish_propagation(pg, plan, g, list(cands), list(emasks))


def execute_plan(pg, plan: Plan) -> MatchResult:
    """Execute ``plan`` against ``pg``; see module docstring for stages."""
    pg._require_graph()  # the documented RuntimeError, before store access
    if _packed_combine_applies(pg):
        return _execute_plan_packed(pg, plan)
    label_masks, rel_masks = _materialize_masks(pg, plan)
    return execute_plan_with_masks(pg, plan, label_masks, rel_masks)


def execute_plan_with_masks(
    pg,
    plan: Plan,
    label_masks: Dict[int, jax.Array],
    rel_masks: Dict[int, jax.Array],
) -> MatchResult:
    """Stages 2–3 of ``execute_plan``, taking PRE-MATERIALIZED attribute
    masks: ``label_masks[slot]`` / ``rel_masks[slot]`` replace the plan's
    ``mask_steps`` outputs (missing slots mean "no attribute constraint").

    This is the service layer's coalescing entry point
    (``src/repro/service/``): a micro-batch of requests materializes ALL
    its label/relationship masks in one ``bitmap_query_batched`` launch,
    then runs each request's propagation here.  Masks must cover the same
    entity universe the plan's own steps would produce — for bitwise parity
    with ``execute_plan``, hand in masks computed from the same stores
    (any DIP-ARR impl; they agree bitwise)."""
    g = pg._require_graph()
    if _obs_enabled():
        _M_PLANS.inc()

    cands = []
    for slot, node in enumerate(plan.pattern.nodes):
        c = label_masks.get(slot, jnp.ones((g.n,), jnp.bool_))
        for step in plan.predicate_steps:
            if step.kind == "node" and step.slot == slot:
                c = c & pg.vertex_predicate_mask(
                    step.predicate.name, step.predicate.op, step.predicate.value
                )
        cands.append(c)

    emasks = []
    for slot, edge in enumerate(plan.pattern.edges):
        e = rel_masks.get(slot, jnp.ones((g.m,), jnp.bool_))
        for step in plan.predicate_steps:
            if step.kind == "edge" and step.slot == slot:
                e = e & pg.edge_predicate_mask(
                    step.predicate.name, step.predicate.op, step.predicate.value
                )
        emasks.append(e)

    # overlay tombstones (docs/ARCHITECTURE.md §11): deleted vertices/edges
    # drop out of EVERY slot — including unconstrained ones, whose all-ones
    # default would otherwise resurrect them — before propagation runs
    av = pg._alive_vertex_mask() if hasattr(pg, "_alive_vertex_mask") else None
    if av is not None:
        cands = [c & av for c in cands]
    ae = pg._alive_edge_mask() if hasattr(pg, "_alive_edge_mask") else None
    if ae is not None:
        emasks = [e & ae for e in emasks]

    return _finish_propagation(pg, plan, g, cands, emasks)


def _hop_caps(pg, plan: Plan, g: DIGraph) -> Tuple[int, ...]:
    """Per hop, the compacted edge list's capacity, or 0 for the dense hop
    (module docstring): read off the plan's relationship estimates, no store
    access and no device sync.  Counts each hop by the path chosen."""
    est = {}  # mesh graphs keep every hop dense
    if getattr(pg, "mesh", None) is None:
        est = {s.slot: s.est_count for s in plan.mask_steps if s.kind == "edge"}
    caps = []
    for slot, e in enumerate(plan.pattern.edges):
        cap = 0
        if e.is_fixed and slot in est:
            cap = max(COMPACT_MIN_CAP, 1 << max(est[slot] - 1, 0).bit_length())
            if cap > COMPACT_MAX_SHARE * g.m:
                cap = 0
        caps.append(cap)
    if _obs_enabled():
        compact = sum(1 for c in caps if c)
        _M_COMPACT.inc(compact)
        _M_DENSE.inc(len(caps) - compact)
    return tuple(caps)


def _finish_propagation(pg, plan: Plan, g: DIGraph, cands, emasks) -> MatchResult:
    """Shared stage-3 tail: mesh replication of the combined per-slot masks
    (no-op single-device), the static-hop chain propagation, and result
    packaging — identical for the bool and packed combine paths."""
    mesh = getattr(pg, "mesh", None)
    if mesh is not None:
        cands = _gather_masks(cands, mesh)
        emasks = _gather_masks(emasks, mesh)

    hops = tuple(
        (e.direction, e.lo, -1 if e.hi is None else e.hi, cap)
        for e, cap in zip(plan.pattern.edges, _hop_caps(pg, plan, g))
    )
    vmask, emask, node_masks, alive = _propagate(
        g, tuple(cands), emasks=tuple(emasks), hops=hops)
    return MatchResult(
        vertex_mask=vmask,
        edge_mask=emask,
        node_masks=node_masks,
        edge_masks=alive,
        plan=plan,
    )

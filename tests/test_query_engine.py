"""Pattern engine: parser round-trips, planner selectivity decisions, and
match() ≡ hand-composed mask pipelines on random graphs, all DIP backends."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PropGraph
from repro.core.queries import induce_edge_mask
from repro.query import (
    EdgePattern,
    NodePattern,
    ParseError,
    Pattern,
    Predicate,
    parse,
    plan_pattern,
)
from repro.query.planner import BUDGET_SEL_CUTOFF


# ------------------------------------------------------------------ parser
@pytest.mark.parametrize(
    "text",
    [
        "(a)",
        "(a:person)",
        "(:person|place)",
        "(a:person {age > 30})",
        '(a:person {age >= 30, name == "bob"})',
        "(a:person)-[:follows]->(b:person)",
        "(a)<-[r:follows|likes]-(b:place {x < -3})",
        "(a:l1)-[:r1]->(b)-[e2:r2 {w != 0.5}]->(c:l2|l3)",
        "(a {score <= 1.5})",
        "(a:x)-[:r*1..3]->(b)",
        "(a)-[v:r|s*]->(b:y)",
        "(a)<-[:r*2..]-(b)",
        "(a)-[:r*3 {w > 0.5}]->(b)",
        "(a)-[:r*0..2]->(b)",
    ],
)
def test_parse_roundtrip(text):
    pat = parse(text)
    assert parse(pat.to_text()) == pat


def test_parse_star_bounds():
    assert parse("(a)-[:r*]->(b)").edges[0].lo == 1
    assert parse("(a)-[:r*]->(b)").edges[0].hi is None
    assert (parse("(a)-[:r*..4]->(b)").edges[0].lo,
            parse("(a)-[:r*..4]->(b)").edges[0].hi) == (1, 4)
    assert (parse("(a)-[:r*2]->(b)").edges[0].lo,
            parse("(a)-[:r*2]->(b)").edges[0].hi) == (2, 2)
    assert parse("(a)-[:r]->(b)").edges[0].is_fixed
    assert not parse("(a)-[:r*1..2]->(b)").edges[0].is_fixed
    # bounds keep float literals intact: '1.' is still a number elsewhere
    assert parse("(a {x > 1.})").nodes[0].predicates[0].value == 1.0


@pytest.mark.parametrize("bad", [
    "(a)-[:r*3..1]->(b)",      # upper below lower
    "(a)-[:r*1.5]->(b)",       # non-integer bound
    "(a)-[:r*-2]->(b)",        # negative bound
    "(a:x*2)-[:r]->(b)",       # '*' is edge-only syntax
])
def test_parse_star_errors(bad):
    with pytest.raises(ParseError):
        parse(bad)


def test_parse_duplicate_variable_raises():
    """Repeated variables would read as an equality join, which the engine
    does not implement — rejected at parse time instead of silently
    OR-ing the masks (the old documented wart)."""
    for bad in ["(a)-[:r]->(a)", "(a)-[x:r]->(b)<-[x:s]-(c)",
                "(v)-[v:r]->(b)"]:
        with pytest.raises(ParseError, match="bound more than once"):
            parse(bad)
    parse("(a)-[:r]->(b)-[:s]->(c)")  # anonymous slots never collide


def test_parse_ast_shape():
    pat = parse('(a:person {age > 30})-[f:follows]->(b:person|place)')
    assert pat == Pattern(
        nodes=(
            NodePattern(var="a", labels=("person",),
                        predicates=(Predicate("age", ">", 30),)),
            NodePattern(var="b", labels=("person", "place")),
        ),
        edges=(EdgePattern(var="f", rels=("follows",), direction=1),),
    )
    assert pat.hops == 1


def test_parse_direction_and_eq_normalization():
    pat = parse("(a)<-[:r]-(b {x = 3})")
    assert pat.edges[0].direction == -1
    assert pat.nodes[1].predicates[0].op == "=="


@pytest.mark.parametrize("bad", ["(a", "(a)-(b)", "(a)-[:r]-(b)", "(a)->[:r]->(b)", "(a{x~3})"])
def test_parse_errors(bad):
    with pytest.raises(ParseError):
        parse(bad)


def test_pattern_reversed_involution():
    pat = parse("(a:l1)-[:r1]->(b)<-[:r2]-(c:l2)")
    assert pat.reversed().reversed() == pat
    assert pat.reversed().edges[0].direction == 1  # <-[:r2]- flips to -[:r2]->


# ----------------------------------------------------------------- fixture
@pytest.fixture(params=["arr", "list", "listd"])
def pg(request, rng):
    src = rng.integers(0, 60, 300)
    dst = rng.integers(0, 60, 300)
    g = PropGraph(backend=request.param).add_edges_from(src, dst)
    nodes = np.asarray(g.graph.node_map)
    labels = rng.choice(["rare", "mid", "common"], size=len(nodes), p=[0.1, 0.3, 0.6])
    g.add_node_labels(nodes, labels)
    es, ed = np.asarray(g.graph.src), np.asarray(g.graph.dst)
    rels = rng.choice(["follows", "likes"], size=len(es), p=[0.2, 0.8])
    g.add_edge_relationships(nodes[es], nodes[ed], rels)
    g.add_node_properties("age", nodes, rng.integers(0, 60, len(nodes)).astype(np.int32))
    g._labels_np, g._rels_np = labels, rels
    return g


# ---------------------------------------------------------------- planner
def test_planner_reverses_toward_selective_end(pg):
    plan = plan_pattern(pg, parse("(a:common)-[:follows]->(b:rare)"))
    assert plan.reversed_chain
    assert plan.pattern.nodes[0].labels == ("rare",)
    assert plan.pattern.edges[0].direction == -1
    plan = plan_pattern(pg, parse("(a:rare)-[:follows]->(b:common)"))
    assert not plan.reversed_chain


def test_planner_skewed_selectivity_picks_cheaper_impl():
    """listd: a selective query plans the output-sized budget gather, an
    unselective one the full inverted scan — driven by attr_counts skew."""
    rng = np.random.default_rng(7)
    src = rng.integers(0, 200, 2000)
    dst = rng.integers(0, 200, 2000)
    pg = PropGraph(backend="listd").add_edges_from(src, dst)
    nodes = np.asarray(pg.graph.node_map)
    labels = rng.choice(["needle", "hay"], size=len(nodes), p=[0.02, 0.98])
    pg.add_node_labels(nodes, labels)

    plan_sel = plan_pattern(pg, parse("(a:needle)"))
    plan_uns = plan_pattern(pg, parse("(a:hay)"))
    (step_sel,) = plan_sel.mask_steps
    (step_uns,) = plan_uns.mask_steps
    assert step_sel.impl == "budget"
    assert step_uns.impl == "inverted"
    assert step_sel.est_selectivity < BUDGET_SEL_CUTOFF < step_uns.est_selectivity
    assert "budget" in pg.explain("(a:needle)")
    assert "inverted" in pg.explain("(a:hay)")
    # both impls produce the same (correct) mask
    expect = labels == "needle"
    assert (np.asarray(pg.match("(a:needle)").vertex_mask) == expect).all()


def test_planner_fuses_arr_label_masks(pg):
    plan = plan_pattern(pg, parse("(a:rare)-[:follows]->(b:common)"))
    if pg.backend == "arr":
        assert plan.fused_node_slots == (0, 1)
        assert all(s.fused for s in plan.mask_steps if s.kind == "node")
        assert "fused" in plan.describe()
    else:
        assert plan.fused_node_slots == ()


def test_impl_override_respected(pg):
    override = {"arr": "scan", "list": None, "listd": "inverted"}[pg.backend]
    plan = plan_pattern(pg, parse("(a:rare)-[:follows]->(b:common)"), impl=override)
    assert plan.fused_node_slots == ()
    if override:
        assert all(s.impl == override for s in plan.mask_steps)


# --------------------------------------------------------------- executor
def _hand_single_hop(pg, l_tail, rel, l_head):
    """The §VI hand-composed pipeline the acceptance criterion names."""
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    vm_t = np.asarray(pg.query_labels([l_tail]))
    vm_h = np.asarray(pg.query_labels([l_head]))
    em = np.asarray(pg.query_relationships([rel]))
    emask = em & vm_t[es] & vm_h[ed]
    vmask = np.zeros(pg.n_vertices, bool)
    vmask[es[emask]] = True
    vmask[ed[emask]] = True
    return vmask, emask


def test_match_equals_hand_composed_pipeline(pg):
    res = pg.match("(a:rare)-[:follows]->(b:common)")
    vexp, eexp = _hand_single_hop(pg, "rare", "follows", "common")
    assert (np.asarray(res.edge_mask) == eexp).all()
    assert (np.asarray(res.vertex_mask) == vexp).all()


def test_match_same_label_equals_induce_edge_mask(pg):
    """Uniform-label hop ≡ the existing induce_edge_mask + endpoint collect."""
    res = pg.match("(a:mid)-[:likes]->(b:mid)")
    vm = pg.query_labels(["mid"])
    em = pg.query_relationships(["likes"])
    eexp = np.asarray(induce_edge_mask(pg.graph, vm, em))
    assert (np.asarray(res.edge_mask) == eexp).all()


def _brute_force(pg, node_label_sets, edge_specs):
    """Exhaustive path enumeration over the chain (exponential; tiny graphs)."""
    labels, rels = pg._labels_np, pg._rels_np
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    n, m, h = pg.n_vertices, pg.n_edges, len(edge_specs)
    nodeok = [
        np.ones(n, bool) if ls is None else np.isin(labels, ls)
        for ls in node_label_sets
    ]
    edgeok = [
        np.ones(m, bool) if rs is None else np.isin(rels, rs)
        for rs, _ in edge_specs
    ]
    adj_out = [[] for _ in range(n)]
    adj_in = [[] for _ in range(n)]
    for i, (a, b) in enumerate(zip(es, ed)):
        adj_out[a].append((i, b))
        adj_in[b].append((i, a))
    vexp = np.zeros(n, bool)
    eexp = np.zeros(m, bool)

    def rec(pos, v, vs, epath):
        if pos == h:
            vexp[vs] = True
            eexp[epath] = True
            return
        _, direction = edge_specs[pos]
        for ei, w in adj_out[v] if direction == 1 else adj_in[v]:
            if edgeok[pos][ei] and nodeok[pos + 1][w]:
                rec(pos + 1, w, vs + [w], epath + [ei])

    for v in np.flatnonzero(nodeok[0]):
        rec(0, int(v), [int(v)], [])
    return vexp, eexp


@pytest.mark.parametrize(
    "text,node_sets,edge_specs",
    [
        ("(a:rare)-[:follows]->(b)-[:likes]->(c:common)",
         [["rare"], None, ["common"]], [(["follows"], 1), (["likes"], 1)]),
        ("(a:rare)<-[:likes]-(b:mid|common)",
         [["rare"], ["mid", "common"]], [(["likes"], -1)]),
        ("(a)-[:follows]->(b:rare)<-[:follows]-(c)",
         [None, ["rare"], None], [(["follows"], 1), (["follows"], -1)]),
        ("(a:common)-[:follows|likes]->(b:rare)",
         [["common"]], None),  # reversed-chain case, specs filled below
    ],
)
def test_match_equals_brute_force(pg, text, node_sets, edge_specs):
    if edge_specs is None:
        node_sets = [["common"], ["rare"]]
        edge_specs = [(["follows", "likes"], 1)]
    res = pg.match(text)
    vexp, eexp = _brute_force(pg, node_sets, edge_specs)
    assert (np.asarray(res.vertex_mask) == vexp).all(), text
    assert (np.asarray(res.edge_mask) == eexp).all(), text


def test_match_with_predicates(pg):
    res = pg.match("(a:rare|mid {age > 30})-[:likes]->(b)")
    ages = np.asarray(pg.vertex_props["age"][0])
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    vm_a = np.isin(pg._labels_np, ["rare", "mid"]) & (ages > 30)
    eexp = (pg._rels_np == "likes") & vm_a[es]
    assert (np.asarray(res.edge_mask) == eexp).all()


def test_match_single_node_pattern(pg):
    res = pg.match("(a:rare {age <= 20})")
    ages = np.asarray(pg.vertex_props["age"][0])
    expect = (pg._labels_np == "rare") & (ages <= 20)
    assert (np.asarray(res.vertex_mask) == expect).all()
    assert res.n_edges() == 0


def test_match_bindings_and_subgraph(pg):
    res = pg.match("(a:rare)-[f:follows]->(b:common)")
    b = res.bindings()
    assert set(b) == {"a", "f", "b"}
    vexp, eexp = _hand_single_hop(pg, "rare", "follows", "common")
    assert (np.asarray(b["f"]) == eexp).all()
    assert (np.asarray(b["a"] | b["b"]) == vexp).all()
    sub, kept = res.subgraph(pg.graph)
    assert sub.m == int(eexp.sum())
    expanded = res.expand(pg.graph, 1)
    assert bool(jnp.all(res.vertex_mask <= expanded))


def test_match_unknown_label_empty(pg):
    res = pg.match("(a:nope)-[:follows]->(b)")
    assert res.n_vertices() == 0 and res.n_edges() == 0


def test_match_unknown_property_raises(pg):
    with pytest.raises(KeyError):
        pg.match("(a {height > 3})")


def test_match_string_predicate_raises(pg):
    """Strings parse as literals but columns are numeric — ==/!= would
    silently broadcast to a scalar, so they are rejected at PLAN time
    (naming the column), before any store work or server round-trip."""
    with pytest.raises(TypeError, match="labels/relationships"):
        pg.match('(a {age != "old"})')
    with pytest.raises(TypeError, match="age"):
        pg.explain('(a {age != "old"})')  # explain plans too — no execution


def test_match_result_is_pytree(pg):
    import jax

    res = pg.match("(a:rare)-[:follows]->(b:common)")
    leaves = jax.tree_util.tree_leaves(res)
    assert all(hasattr(x, "dtype") for x in leaves)  # masks only, plan is meta
    jax.block_until_ready(res)  # benchmarks rely on this blocking for real


# ------------------------------------------------------ satellite regressions
def test_query_any_empty_values_fast_path(pg):
    assert not np.asarray(pg.query_labels([])).any()
    assert not np.asarray(pg.query_relationships([])).any()
    assert not np.asarray(pg._vstore.query_any([])).any()


def test_queries_before_build_raise_runtime_error():
    pg = PropGraph(backend="arr")
    with pytest.raises(RuntimeError, match="add_edges_from"):
        pg.query_labels(["x"])
    with pytest.raises(RuntimeError, match="add_edges_from"):
        pg.query_relationships(["x"])
    with pytest.raises(RuntimeError, match="add_edges_from"):
        pg.subgraph(labels=["x"])
    with pytest.raises(RuntimeError, match="add_edges_from"):
        pg.match("(a:x)")


def test_attr_counts_match_histogram(pg):
    counts = pg.label_counts()
    for lab in ("rare", "mid", "common"):
        assert counts[lab] == int((pg._labels_np == lab).sum())
    rcounts = pg.relationship_counts()
    assert rcounts["follows"] == int((pg._rels_np == "follows").sum())


def test_query_any_batched_consistent(pg):
    queries = [["rare"], ["mid", "common"], ["nope"]]
    batched = np.asarray(pg._vstore.query_any_batched(queries))
    for q, row in zip(queries, batched):
        assert (row == np.asarray(pg.query_labels(q))).all()
    if pg.backend == "arr":  # scan/kernel impls agree with matvec
        for impl in ("scan", "kernel"):
            alt = np.asarray(pg._vstore.query_any_batched(queries, impl=impl))
            assert (alt == batched).all(), impl


# ------------------------------------------------------ compacted fixed hops
def _compact_graph(seed=3, m=20_000, n=5_000, rels=20, mesh=None):
    """A graph big enough that a relationship's bucket (≈ m/rels → 1024)
    sits under the compaction cutoff (m/4): the host path compacts."""
    rng = np.random.default_rng(seed)
    pg = PropGraph(backend="arr", mesh=mesh).add_edges_from(
        rng.integers(0, n, m), rng.integers(0, n, m))
    nodes = np.asarray(pg.graph.node_map)
    pg.add_node_labels(nodes, rng.choice(["a", "b", "c"], len(nodes)))
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    pg.add_edge_relationships(
        nodes[es], nodes[ed], rng.choice([f"r{i}" for i in range(rels)], len(es)))
    return pg, rng


def _multi_rel(pg, rng):
    """Give a third of r1's edges r2 as well: edges holding both."""
    nodes = np.asarray(pg.graph.node_map)
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    r1 = np.flatnonzero(np.asarray(pg.query_relationships(["r1"])))[::3]
    pg.add_edge_relationships(nodes[es[r1]], nodes[ed[r1]], ["r2"] * len(r1))


def _tombstones(pg, rng):
    nodes = np.asarray(pg.graph.node_map)
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    pg.delete_vertices(rng.choice(nodes, 200, replace=False))
    kill = rng.choice(len(es), 500, replace=False)
    pg.delete_edges(nodes[es[kill]], nodes[ed[kill]])


def _overlay(pg, rng):
    nodes = np.asarray(pg.graph.node_map)
    s, d = rng.choice(nodes, 400), rng.choice(nodes, 400)
    pg.insert_edges(s, d)
    pg.add_edge_relationships(s, d, ["r1"] * len(s))


COMPACT_CASES = {
    "1-hop forward": ("(x:a)-[:r1]->(y:b)", None),
    "1-hop backward": ("(x:a)<-[:r2]-(y:b)", None),
    "2-hop": ("(x:a)-[:r1]->(y)-[:r3]->(z:c)", None),
    "multi-relationship hop": ("(x)-[:r1|r2]->(y:b)", _multi_rel),
    "tombstoned vertices and edges": ("(x:a)-[:r1]->(y)<-[:r4]-(z:b)", _tombstones),
    "overlay with delta edges": ("(x:a)-[:r1]->(y:b|c)", _overlay),
    "empty relationship": ("(x:a)-[:nope]->(y)", None),
}


def _propagate_args(monkeypatch, pg, plan):
    """The arguments the executor hands ``_propagate`` for ``plan``."""
    from repro.query import executor

    seen = {}
    real = executor._propagate

    def spy(g, cands, emasks, hops):
        seen.update(g=g, cands=cands, emasks=emasks, hops=hops)
        return real(g, cands, emasks=emasks, hops=hops)

    monkeypatch.setattr(executor, "_propagate", spy)
    executor.execute_plan(pg, plan)
    monkeypatch.undo()
    return seen


def _run(args, hops):
    from repro.query.executor import _propagate

    out = _propagate(args["g"], args["cands"], emasks=args["emasks"], hops=hops)
    return [np.asarray(x) for x in (out[0], out[1], *out[2], *out[3])]


def _dense(hops):
    return tuple((d, lo, hi, 0) for d, lo, hi, _ in hops)


def _assert_same(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and (a == b).all()


@pytest.mark.parametrize("case", sorted(COMPACT_CASES))
def test_compacted_propagation_equals_dense(monkeypatch, case):
    """Every fixed relationship hop takes its compacted edge list on the
    host path, and the answer is bitwise the dense one's."""
    text, mutate = COMPACT_CASES[case]
    pg, rng = _compact_graph()
    if mutate is not None:
        mutate(pg, rng)
    args = _propagate_args(monkeypatch, pg, plan_pattern(pg, parse(text)))
    assert all(cap >= 1024 for *_, cap in args["hops"]), args["hops"]
    _assert_same(_run(args, args["hops"]), _run(args, _dense(args["hops"])))


def test_stale_bound_takes_the_dense_branch(monkeypatch):
    """A plan made before a write keeps its old ``est_count``: once the
    relationship outgrows the cap, the program runs the dense chain and
    stays exact; the compacted chain alone would have dropped edges."""
    from repro.query.executor import _chain

    pg, rng = _compact_graph()
    plan = plan_pattern(pg, parse("(x)-[:r1]->(y)"))
    (cap,) = [c for *_, c in _propagate_args(monkeypatch, pg, plan)["hops"]]
    nodes = np.asarray(pg.graph.node_map)
    s, d = rng.choice(nodes, cap), rng.choice(nodes, cap)
    pg.insert_edges(s, d)
    pg.add_edge_relationships(s, d, ["r1"] * len(s))
    args = _propagate_args(monkeypatch, pg, plan)  # the stale plan
    assert args["hops"][0][3] == cap
    assert int(np.asarray(args["emasks"][0]).sum()) > cap
    got = _run(args, args["hops"])
    _assert_same(got, _run(args, _dense(args["hops"])))
    assert (got[1] == np.asarray(pg.match("(x)-[:r1]->(y)").edge_mask)).all()
    truncated = _chain(args["g"], args["cands"], args["emasks"], args["hops"])
    assert int(np.asarray(truncated[1]).sum()) < int(got[1].sum())


def _hlo_ops(hlo: str):
    """Per computation of a lowered HLO module: (scatter update sizes, sort
    sizes), and the computations reachable from ENTRY without entering
    branch 0 of a conditional (``lax.cond``'s false branch, here the dense
    chain)."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        hdr = re.match(r"^(ENTRY )?([\w.\-]+) \{$", line)
        if hdr:
            cur = hdr.group(2)
            comps[cur] = []
            entry = cur if hdr.group(1) else entry
        elif line == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    ops = {}
    for name, lines in comps.items():
        size, scatters, sorts = {}, [], []
        for line in lines:
            d = re.match(r"\s*(?:ROOT )?([\w.\-]+) = \w+\[([\d,]*)\]", line)
            if d:
                size[d.group(1)] = int(np.prod([int(x) for x in d.group(2).split(",") if x]))
            s = re.search(r" scatter\(([^)]*)\)", line)
            if s:
                scatters.append(size[s.group(1).split(", ")[2]])
            if re.search(r" sort\(", line):
                sorts.append(size[d.group(1)])
        ops[name] = (scatters, sorts)
    live, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in live:
            continue
        live.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:to_apply|calls|body|condition|true_computation|false_computation)"
                r"=([\w.\-]+)", line)
            for br in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip() for b in br.split(",")][1:]
    return ops, live


def test_compacted_hop_program_has_no_m_wide_scatter(monkeypatch):
    """Outside the dense fallback, a compacted 1-hop ``_propagate`` holds no
    scatter whose updates span m elements and one sort of m keys."""
    from repro.query.executor import _propagate

    pg, _ = _compact_graph()
    args = _propagate_args(monkeypatch, pg, plan_pattern(pg, parse("(x:a)-[:r1]->(y:b)")))
    m = pg.graph.m
    hlo = _propagate.lower(args["g"], args["cands"], emasks=args["emasks"],
                           hops=args["hops"]).as_text(dialect="hlo")
    ops, live = _hlo_ops(hlo)
    scatters = [u for c in live for u in ops[c][0]]
    sorts = [s for c in live for s in ops[c][1]]
    assert scatters and max(scatters) < m, scatters
    assert sorts.count(m) == 1, sorts
    # the guard can see an m-wide scatter: the dense fallback holds them
    assert any(u == m for sc, _ in ops.values() for u in sc)


@pytest.mark.parametrize("text,mesh,compact,dense", [
    ("(x:a)-[:r1]->(y:b)", False, 1, 0),  # relationship hop
    ("(x:a)-[]->(y:b)", False, 0, 1),  # unconstrained hop
    ("(x:a)-[:r1]->(y)-[:r2*1..2]->(z)", False, 1, 1),  # fixed + variable-length
    ("(x:a)-[:r1]->(y:b)", True, 0, 1),  # mesh graphs stay dense
])
def test_hop_path_counters(text, mesh, compact, dense):
    from repro.launch.mesh import make_entity_mesh
    from repro.obs import metrics

    pg, _ = _compact_graph(mesh=make_entity_mesh() if mesh else None)
    c = metrics.GLOBAL.counter("pg_exec_compact_hops")
    d = metrics.GLOBAL.counter("pg_exec_dense_hops")
    prev = metrics.set_enabled(True)
    try:
        c0, d0 = c.value(), d.value()
        res = pg.match(text)
        assert (c.value() - c0, d.value() - d0) == (compact, dense)
    finally:
        metrics.set_enabled(prev)
    if mesh:  # and the mesh answer is the single-device one
        single, _ = _compact_graph()
        assert (np.asarray(res.edge_mask) == np.asarray(single.match(text).edge_mask)).all()

"""Service-layer semantics (src/repro/service/, docs/ARCHITECTURE.md §8).

The contracts under test:

* coalesced / batched execution is bitwise-equal to sequential
  ``PropGraph.match`` on ALL three backends (and on a device mesh when the
  interpreter has >1 device — CI runs the suite under 8 virtual devices);
* the result cache is invalidated by ``add_node_labels`` /
  ``add_edges_from`` version bumps (registry → mutation hook → purge);
* plan-cache hits are accounted (and survive mutations — plans are keyed
  without the graph version on purpose);
* mesh-mode stores never cache a dense single-device replica (the PR 2
  follow-up: per-device memory O(NK/P)).
"""
import threading

import numpy as np
import pytest

from repro.core import PropGraph
from repro.graph import random_uniform_graph
from repro.launch.pgserve import build_tenant_graph
from repro.service import GraphRegistry, LRUCache, Service, ServiceConfig
from repro.service.scheduler import execute_coalesced

BACKENDS = ("arr", "list", "listd")
PATTERNS = (
    "(a:l1|l2)-[:follows]->(b:l3)",
    "(a:l0 {age > 30})-[:likes]->(b)",
    "(a)<-[:likes]-(b:l4|l5)",
    "(a:l6)-[:follows]->(b)-[:likes]->(c:l7)",
)


def _build(backend, m=800, seed=3, mesh=None):
    # the same synthetic tenant the smoke/bench paths serve — one recipe
    return build_tenant_graph(backend, m, mesh=mesh, seed=seed)


def _eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool((a == b).all())


def _assert_same_result(got, ref):
    assert _eq(got.vertex_mask, ref.vertex_mask)
    assert _eq(got.edge_mask, ref.edge_mask)
    gb, rb = got.bindings(), ref.bindings()
    assert sorted(gb) == sorted(rb)
    for k in rb:
        assert _eq(gb[k], rb[k]), k


# ------------------------------------------------------------- equivalence
@pytest.mark.parametrize("backend", BACKENDS)
def test_coalesced_batch_equals_sequential_match(backend):
    """query_batch (ONE coalesced group, deterministic composition) ≡
    per-request match, bitwise, duplicates included."""
    pg = _build(backend)
    patterns = list(PATTERNS) + [PATTERNS[0], PATTERNS[2]]  # dups coalesce
    with Service() as svc:
        svc.add_graph("g", pg)
        got = svc.query_batch("g", patterns)
        stats = svc.stats()
    for p, res in zip(patterns, got):
        _assert_same_result(res, pg.match(p))
    if backend == "arr":
        assert stats["coalesced_launches"] > 0
        assert stats["coalesced_masks"] >= 4
    else:
        assert stats["fallback_requests"] > 0  # same API, per-request path
    assert stats["dedup_hits"] == 2


@pytest.mark.parametrize("backend", BACKENDS)
def test_concurrent_submit_equals_sequential_match(backend):
    """Futures resolved through the async micro-batching path carry the
    same masks as direct match, regardless of how batches formed."""
    pg = _build(backend)
    refs = {p: pg.match(p) for p in PATTERNS}
    with Service() as svc:
        svc.add_graph("g", pg)
        futs = []
        threads = [
            threading.Thread(
                target=lambda p=p: futs.append((p, svc.submit("g", p))))
            for p in PATTERNS for _ in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for p, f in futs:
            _assert_same_result(f.result(timeout=120), refs[p])


def test_execute_coalesced_bucket_padding_exact():
    """Padding Q to a bucket with empty queries must not leak into results
    (pad rows are all-False and sliced off)."""
    pg = _build("arr")
    from repro.query import parse, plan_pattern

    for n_plans in (1, 2, 3):  # crosses Q buckets 2 and 4 with edge masks
        plans = [plan_pattern(pg, parse(p)) for p in PATTERNS[:n_plans]]
        got = execute_coalesced(pg, plans)
        for p, res in zip(PATTERNS[:n_plans], got):
            _assert_same_result(res, pg.match(p))


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 2,
    reason="mesh equivalence needs >1 device (CI forces 8)",
)
def test_service_on_mesh_equals_single_device():
    from repro.launch.mesh import make_entity_mesh

    mesh = make_entity_mesh()
    pg1 = _build("arr")
    pg2 = _build("arr", mesh=mesh)
    with Service() as svc:
        svc.add_graph("g", pg2)
        for res, p in zip(svc.query_batch("g", list(PATTERNS)), PATTERNS):
            _assert_same_result(res, pg1.match(p))


# ------------------------------------------------------------ invalidation
def test_result_cache_invalidated_by_label_mutation():
    pg = _build("arr")
    with Service() as svc:
        svc.add_graph("g", pg)
        v0 = svc.registry.version("g")
        first = svc.query("g", PATTERNS[0])
        assert svc.query("g", PATTERNS[0]) is first  # cached object served
        nodes = np.asarray(pg.graph.node_map)
        pg.add_node_labels(nodes[:9], ["l1"] * 9)  # version bump via hook
        assert svc.registry.version("g") == v0 + 1
        stats = svc.stats()
        assert stats["invalidated_results"] >= 1
        assert len(svc.result_cache) == 0  # eager purge, not just new keys
        fresh = svc.query("g", PATTERNS[0])
        _assert_same_result(fresh, pg.match(PATTERNS[0]))
        assert not _eq(fresh.vertex_mask, first.vertex_mask)  # l1 grew


def test_result_cache_invalidated_by_edge_rebuild():
    """add_edges_from (structure rebuild) also bumps + purges."""
    pg = _build("arr", m=400, seed=5)
    with Service() as svc:
        svc.add_graph("g", pg)
        svc.query("g", PATTERNS[0])
        assert len(svc.result_cache) == 1
        src, dst = random_uniform_graph(500, seed=11)
        pg.add_edges_from(src, dst)  # fresh stores, attributes dropped
        assert len(svc.result_cache) == 0
        nodes = np.asarray(pg.graph.node_map)
        pg.add_node_labels(nodes, ["l1"] * len(nodes))
        pg.add_edge_relationships(
            nodes[np.asarray(pg.graph.src)], nodes[np.asarray(pg.graph.dst)],
            ["follows"] * pg.n_edges)
        res = svc.query("g", "(a:l1)-[:follows]->(b:l1)")
        _assert_same_result(res, pg.match("(a:l1)-[:follows]->(b:l1)"))


def test_version_counter_covers_every_mutator():
    pg = PropGraph(backend="arr")
    assert pg.version == 0
    src, dst = random_uniform_graph(200, seed=1)
    pg.add_edges_from(src, dst)
    nodes = np.asarray(pg.graph.node_map)
    pg.add_node_labels(nodes[:5], ["x"] * 5)
    pg.add_edge_relationships(src[:3], dst[:3], ["r"] * 3)
    pg.add_node_properties("p", nodes[:5], np.arange(5))
    pg.add_edge_properties("q", src[:3], dst[:3], np.arange(3))
    assert pg.version == 5


# -------------------------------------------------------------- accounting
def test_plan_cache_hit_accounting():
    """Same canonical pattern → one plan miss then hits; plans survive
    version bumps (keyed without version — perf-only staleness)."""
    pg = _build("arr")
    cfg = ServiceConfig(result_cache_size=0)  # isolate the plan cache
    with Service(config=cfg) as svc:
        svc.add_graph("g", pg)
        svc.query("g", PATTERNS[0])
        svc.query("g", " (a:l1|l2)-[:follows]->(b:l3) ")  # canonicalizes same
        stats = svc.stats()
        assert stats["plan_misses"] == 1
        assert stats["plan_hits"] == 1
        pg.add_node_labels(np.asarray(pg.graph.node_map)[:3], ["l9"] * 3)
        svc.query("g", PATTERNS[0])
        assert svc.stats()["plan_hits"] == 2  # survived the bump


def test_bad_request_does_not_poison_cobatched_group():
    """A request that fails planning (unknown property) must fail alone —
    co-batched valid requests still get their results."""
    pg = _build("arr")
    with Service(config=ServiceConfig(window_ms=250.0)) as svc:
        svc.add_graph("g", pg)
        bad = svc.submit("g", "(a {nosuchprop > 1})-[:follows]->(b)")
        good = svc.submit("g", PATTERNS[0])  # same window, same group
        with pytest.raises(KeyError, match="nosuchprop"):
            bad.result(timeout=120)
        _assert_same_result(good.result(timeout=120), pg.match(PATTERNS[0]))
    # the deterministic form, via the shared serve pipeline directly
    with Service() as svc:
        svc.add_graph("g", pg)
        good_c, good_ast = svc._canon(PATTERNS[0])
        bad_c, bad_ast = svc._canon("(a {nosuchprop > 1})-[:follows]->(b)")
        out = svc._serve_group(pg, "g", None,
                               {bad_c: bad_ast, good_c: good_ast})
        assert isinstance(out[bad_c], KeyError)
        _assert_same_result(out[good_c], pg.match(PATTERNS[0]))


def test_failed_coalesced_group_is_counted_and_rerun_per_request():
    """When the coalesced launch itself raises, every request of the group
    re-runs on its own, and ``group_fallbacks`` counts the group."""
    pg = _build("arr")
    with Service() as svc:
        svc.add_graph("g", pg)
        asts = dict(svc._canon(p) for p in PATTERNS[:2])

        def broken(*_a, **_k):
            raise RuntimeError("coalesced launch failed")

        svc._execute_plans = broken
        out = svc._serve_group(pg, "g", None, asts)
        stats = svc.stats()
    assert stats["group_fallbacks"] == 1 and stats.get("errors", 0) == 0
    for (c, _), p in zip(asts.items(), PATTERNS[:2]):
        _assert_same_result(out[c], pg.match(p))


def test_result_cache_hit_and_fastpath_accounting():
    pg = _build("arr")
    with Service() as svc:
        svc.add_graph("g", pg)
        svc.query("g", PATTERNS[0])
        svc.query("g", PATTERNS[0])
        svc.query_batch("g", [PATTERNS[0]])
        stats = svc.stats()
    assert stats["result_hits"] == 2
    assert stats["fastpath_hits"] == 1  # 2nd query skipped the queue
    assert stats["result_misses"] == 1


def test_lru_cache_eviction_and_disable():
    c = LRUCache(2)
    c.put("a", 1), c.put("b", 2)
    assert c.get("a") == 1
    c.put("c", 3)  # evicts b (a was refreshed)
    assert c.get("b") is None and c.get("c") == 3
    assert c.stats()["evictions"] == 1
    off = LRUCache(0)
    off.put("a", 1)
    assert off.get("a") is None and len(off) == 0


def test_lru_cache_put_on_existing_key_refreshes_recency():
    """Regression: re-inserting a hot key must move it to the MRU end —
    an overwrite that leaves the entry in its old position gets the entry
    evicted as if cold."""
    c = LRUCache(2)
    c.put("hot", 1)
    c.put("b", 2)
    c.put("hot", 10)  # overwrite must also refresh recency
    c.put("c", 3)  # evicts b — NOT the just-re-inserted "hot"
    assert c.get("hot") == 10
    assert c.get("b") is None
    assert c.get("c") == 3


def test_registry_concurrent_subscribe_during_notify():
    """Regression: subscribe/unsubscribe racing an in-flight _notify must
    not corrupt the listener list (snapshot under the registry lock)."""
    pg = _build("arr", m=300, seed=9)
    reg = GraphRegistry()
    reg.register("g", pg)
    nodes = np.asarray(pg.graph.node_map)
    stop = threading.Event()
    errors = []

    def churn():
        try:
            while not stop.is_set():
                listeners = [lambda name, g: None for _ in range(4)]
                for ln in listeners:
                    reg.subscribe(ln)
                for ln in listeners:
                    reg.unsubscribe(ln)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    def mutate():
        try:
            for i in range(60):  # every mutation fires _notify
                pg.add_node_labels(nodes[:2], [f"l{i % 3}"] * 2)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    churners = [threading.Thread(target=churn) for _ in range(3)]
    mut = threading.Thread(target=mutate)
    for t in churners:
        t.start()
    mut.start()
    mut.join(timeout=120)
    stop.set()
    for t in churners:
        t.join(timeout=30)
    assert not errors
    # steady state: only the registration hook's listeners remain
    survivor = []
    reg.subscribe(lambda name, g: survivor.append(name))
    pg.add_node_labels(nodes[:2], ["x"] * 2)
    assert survivor == ["g"]


# ---------------------------------------------------------------- registry
def test_registry_load_and_errors(tmp_path):
    from repro.core.io import save_propgraph

    pg = _build("arr", m=300, seed=9)
    path = save_propgraph(str(tmp_path / "pg"), pg)
    reg = GraphRegistry()
    reg.load("disk", path, backend="listd")
    assert "disk" in reg and reg.names() == ["disk"]
    got = reg.get("disk").match(PATTERNS[0])
    _assert_same_result(got, pg.match(PATTERNS[0]))
    with pytest.raises(KeyError, match="unknown graph"):
        reg.get("nope")
    with Service(registry=reg) as svc:
        with pytest.raises(KeyError, match="unknown graph"):
            svc.submit("nope", PATTERNS[0]).result(timeout=60)
    assert reg._listeners == []  # closed service detached from the registry


def test_registry_reregister_is_idempotent_and_silences_replaced_graph():
    """Refreshing a registration must not stack duplicate hooks, and a
    replaced graph's mutations must stop notifying under the name."""
    pg1 = _build("arr", m=300, seed=9)
    pg2 = _build("arr", m=300, seed=10)
    reg = GraphRegistry()
    events = []
    reg.subscribe(lambda name, pg: events.append((name, pg)))
    reg.register("g", pg1)
    reg.register("g", pg1)  # refresh: same graph, no extra hook
    events.clear()
    nodes = np.asarray(pg1.graph.node_map)
    pg1.add_node_labels(nodes[:2], ["x"] * 2)
    assert len(events) == 1  # one hook, one notification
    reg.register("g", pg2)  # replacement
    events.clear()
    pg1.add_node_labels(nodes[:2], ["y"] * 2)  # old graph mutates
    assert events == []  # replaced graph is silent under the name
    pg2.add_node_labels(np.asarray(pg2.graph.node_map)[:2], ["z"] * 2)
    assert len(events) == 1


# --------------------------------------------- O(NK/P) dense-copy release
def test_mesh_mode_never_caches_dense_store():
    """The PR 2 follow-up closed: with a mesh, queries AND planner stats
    must not leave a dense single-device store cached anywhere."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (CI forces 8)")
    from repro.launch.mesh import make_entity_mesh

    pg = _build("arr", mesh=make_entity_mesh())
    pg.match(PATTERNS[0])  # planner stats + sharded query
    pg.label_counts()  # stats-only read
    for store in (pg._vstore, pg._estore):
        assert store._store is None
        assert store._host is None  # host build released after placement
        assert store._sharded is not None
        assert store._counts is not None


def test_label_counts_reads_cached_stats_without_device_store():
    """label_counts/relationship_counts come off attr_counts — derived
    host-side; reading them must not build a device store."""
    pg = _build("list", m=300, seed=2)
    counts = pg.label_counts()
    assert pg._vstore._store is None  # stats never touched a device store
    labels = np.asarray(pg._vstore.amap.values)
    assert set(counts) == set(labels.tolist())
    rcounts = pg.relationship_counts()
    assert pg._estore._store is None
    assert sum(rcounts.values()) == pg._estore.nnz
    # and the stats agree with the actual query masks
    for lab, c in counts.items():
        assert int(np.asarray(pg.query_labels([lab])).sum()) == c

#!/usr/bin/env python3
"""chip_smoke — the property-graph main path, end to end, on a TPU.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # four chips: the mesh phase only

One process drives everything (the chip belongs to one process): it builds
the paper's Tab. I ``graph3`` tenant — m = 10⁷ uniform edges, 8,646,926
vertices, 12 labels, 2 relationships, an ``age`` column and a ``w`` weight
(``pgserve.build_tenant_graph("arr", 10_000_000, seed=0)``) — and checks,
each against a plain numpy reference built from the same host arrays:

* patterns: ``match`` on every pool pattern; fused multi-label, predicate,
  2-hop and reverse patterns bit for bit; ``explain`` names the compiled
  Pallas kernel for the fused label masks;
* traversal and analytics: ``khop``/``bfs`` depths, ``shortest_paths``
  (Bellman–Ford optimality certificate), ``pagerank`` (float64 power
  iteration, sums to 1), ``communities`` (labels stay inside components);
* sampling: pattern seeds, fanouts [10, 5], a relationship filter, every
  layer through the structural oracle ``neighbor_sample.ref.check_sample``;
* writes: ``delete_edges`` + ``insert_edges`` + relationships, then
  ``compact()``, each followed by a query compared with numpy on the
  combined edge list;
* the served path: ``Service`` + ``PGServer`` on localhost, 8 ``PGClient``
  threads (blocking and pipelined), every answer bitwise ``match()``, no
  failed or re-run group, and a background compaction with no error.

The first call of every read runs concurrently with the others, as under
concurrent users, so their compilations overlap; each second call runs
alone and must equal the first.  Writes swap edges one for one (the vertex
universe and the edge count stay), so each compaction rebuilds at the
shapes the first build compiled.

``--chips 4`` builds the same graph unsharded and on ``make_entity_mesh()``
and compares match, khop, shortest_paths, pagerank and sample across them.

Timings printed on the way are single first-light readings (first calls
include compilation), not benchmark numbers.  The last line of standard
output is one JSON object, printed only when every check passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before any phase; it never falls
back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

M_EDGES = 10_000_000  # paper Tab. I graph3
SEED = 0
COMMUNITY_ROUNDS = 8   # label-propagation rounds (the library default is 64)
P_FUSED = "(a:l1|l2)-[:follows]->(b:l3)"
P_PRED = "(a:l2|l3 {age <= 60})-[:likes]->(b:l0)"
P_2HOP = "(a:l4)-[:follows]->(b)-[:likes]->(c:l5)"
P_REV = "(a)<-[:likes]-(b:l7|l8)"
P_SAMPLE_SEEDS = "(a:l1 {age >= 85})"
P_SAMPLE_FILTER = "(a)-[:follows]->(b)"
FANOUTS = [10, 5]


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int) -> dict:
    """Versions and devices; exit non-zero unless ``chips`` TPUs are there."""
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    devs = jax.devices()
    log(f"devices: {devs}")
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_line(tag: str) -> None:
    import jax

    for d in jax.devices():
        st = d.memory_stats() or {}
        log(f"[first-light] {tag} {d}: bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")


def timed(fn):
    """``(fn(), wall seconds)``, the result ready on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def first_calls(reads: dict) -> dict:
    """Run every read's first call at once (their compilations overlap);
    ``{label: (result, seconds)}``.  A read that raises fails the script."""
    with ThreadPoolExecutor(max_workers=len(reads)) as ex:
        futs = {k: ex.submit(timed, fn) for k, fn in reads.items()}
        return {k: f.result() for k, f in futs.items()}


def run_reads(reads: dict) -> dict:
    """First calls concurrently, then each second call alone; the two must
    be identical.  ``{label: result}``."""
    first = first_calls(reads)
    out = {}
    for k, fn in reads.items():
        a, t1 = first[k]
        b, t2 = timed(fn)
        log(f"[first-light] {k}: first {t1:.3f} s (concurrent), second {t2:.3f} s")
        same_result(a, b, f"{k} repeat")
        out[k] = b
    return out


def same(a, b, what: str) -> None:
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and np.array_equal(a, b), (
        f"{what}: mismatch (shape {a.shape} vs {b.shape}, "
        f"{int(np.sum(a != b)) if a.shape == b.shape else '-'} entries differ)")


def same_result(a, b, what: str) -> None:
    """Bitwise equality of two results of one read (match, sample, array)."""
    if hasattr(a, "edge_mask") and hasattr(a, "vertex_mask"):
        same(a.vertex_mask, b.vertex_mask, f"{what} vertex_mask")
        same(a.edge_mask, b.edge_mask, f"{what} edge_mask")
    elif isinstance(a, list):
        assert len(a) == len(b), what
        for x, y in zip(a, b):
            for f in ("src_nodes", "dst_nodes", "edge_src", "edge_dst", "edge_mask"):
                same(getattr(x, f), getattr(y, f), f"{what} {f}")
    else:
        same(a, b, what)


# ----------------------------------------------------------- numpy reference
class Ref:
    """The tenant as host arrays, rebuilt without the library: sorted-unique
    vertex ids, edges deduped in (src, dst) order, the seeded draws."""

    def __init__(self, m: int, seed: int):
        from repro.graph import random_uniform_graph
        from repro.launch.pgserve import tenant_draws

        src, dst = random_uniform_graph(m, seed=seed)
        self.node_map = np.unique(np.concatenate([src, dst]))
        n = self.n = len(self.node_map)
        key = np.unique(np.searchsorted(self.node_map, src).astype(np.int64) * n
                        + np.searchsorted(self.node_map, dst))
        self.key = key
        self.src = (key // n).astype(np.int32)
        self.dst = (key % n).astype(np.int32)
        self.m = len(key)
        labels, rels, self.age, self.w = tenant_draws(n, self.m, seed=seed)
        names, inv = np.unique(labels, return_inverse=True)
        self.label = np.array([int(s[1:]) for s in names])[inv]
        self.follows = rels == "follows"  # the other relationship is likes

    def lab(self, *ids) -> np.ndarray:
        return np.isin(self.label, ids)

    def seg(self) -> np.ndarray:
        return np.searchsorted(self.src, np.arange(self.n + 1)).astype(np.int64)


def hop_ref(n, src, dst, e_ok):
    """(vertex mask, tail mask, head mask) of the edges ``e_ok`` selects."""
    tail = np.zeros(n, bool)
    head = np.zeros(n, bool)
    tail[src[e_ok]] = True
    head[dst[e_ok]] = True
    return tail | head, tail, head


def pattern_refs(R: Ref, src=None, dst=None, follows=None, alive=None):
    """pattern → (vertex_mask, edge_mask, {var: mask}) by direct numpy;
    ``alive`` drops deleted edges from every pattern."""
    src = R.src if src is None else src
    dst = R.dst if dst is None else dst
    follows = R.follows if follows is None else follows
    likes = ~follows
    if alive is not None:
        follows, likes = follows & alive, likes & alive
    out = {}
    e = follows & R.lab(1, 2)[src] & R.lab(3)[dst]
    v, a, b = hop_ref(R.n, src, dst, e)
    out[P_FUSED] = (v, e, {"a": a, "b": b})
    e = likes & R.lab(2, 3)[src] & (R.age <= 60)[src] & R.lab(0)[dst]
    v, a, b = hop_ref(R.n, src, dst, e)
    out[P_PRED] = (v, e, {"a": a, "b": b})
    e = likes & R.lab(7, 8)[src]
    v, b, a = hop_ref(R.n, src, dst, e)
    out[P_REV] = (v, e, {"a": a, "b": b})
    e1 = follows & R.lab(4)[src]
    e2 = likes & R.lab(5)[dst]
    into = np.zeros(R.n, bool)
    outof = np.zeros(R.n, bool)
    into[dst[e1]] = True
    outof[src[e2]] = True
    mid = into & outof
    e1, e2 = e1 & mid[dst], e2 & mid[src]
    _, a, _ = hop_ref(R.n, src, dst, e1)
    _, _, c = hop_ref(R.n, src, dst, e2)
    out[P_2HOP] = (a | mid | c, e1 | e2, {"a": a, "b": mid, "c": c})
    return out


def check_match(res, ref, what: str) -> None:
    v, e, binds = ref
    same(res.vertex_mask, v, f"{what} vertex_mask")
    same(res.edge_mask, e, f"{what} edge_mask")
    got = res.bindings()
    assert sorted(got) == sorted(binds), (what, sorted(got))
    for var, mask in binds.items():
        same(got[var], mask, f"{what} binding {var}")


def bfs_ref(R: Ref, seeds: np.ndarray, max_iters: int) -> np.ndarray:
    depth = np.full(R.n, -1, np.int32)
    depth[seeds] = 0
    frontier = np.zeros(R.n, bool)
    frontier[seeds] = True
    for it in range(max_iters):
        cand = np.zeros(R.n, bool)
        cand[R.dst[frontier[R.src]]] = True
        new = cand & (depth < 0)
        if not new.any():
            break
        depth[new] = it + 1
        frontier = new
    return depth


def pagerank_ref(R: Ref, damping=0.85, iters=20) -> np.ndarray:
    """float64 power iteration over every edge, unweighted."""
    out_deg = np.bincount(R.src, minlength=R.n).astype(np.float64)
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1), 0.0)
    r = np.full(R.n, 1.0 / R.n)
    for _ in range(iters):
        agg = np.bincount(R.dst, weights=(r * inv)[R.src], minlength=R.n)
        dangling = r[out_deg == 0].sum()
        r = (1 - damping) / R.n + damping * (agg + dangling / R.n)
    return r


def swap_edges(src, dst, n: int, key: np.ndarray, k: int, rng):
    """``k`` existing edges to delete and ``k`` new ones to insert (internal
    ids), chosen so that every vertex keeps an edge: each deleted edge's
    endpoints have another edge and appear in no other deleted edge.  The
    vertex universe and the edge count survive the swap."""
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    cand = rng.permutation(len(src))[: 40 * k]
    cand = cand[(src[cand] != dst[cand]) & (deg[src[cand]] >= 2)
                & (deg[dst[cand]] >= 2)]
    used: set = set()
    gone = []
    for e in cand.tolist():
        s, d = int(src[e]), int(dst[e])
        if s not in used and d not in used:
            used.update((s, d))
            gone.append(e)
            if len(gone) == k:
                break
    assert len(gone) == k, "not enough edges to swap"
    u = rng.integers(0, n, 4 * k)
    v = rng.integers(0, n, 4 * k)
    new = u.astype(np.int64) * n + v
    _, first = np.unique(new, return_index=True)
    keep = np.sort(first)
    keep = keep[~np.isin(new[keep], key)][:k]
    assert len(keep) == k, "not enough new edges"
    return np.array(gone), u[keep].astype(np.int32), v[keep].astype(np.int32)


# -------------------------------------------------------------------- reads
def pattern_reads(pg) -> dict:
    from repro.launch.pgserve import pattern_pool

    return {f"match {p}": (lambda p=p: pg.match(p)) for p in pattern_pool()}


def traversal_reads(pg, orig_seeds) -> dict:
    return {
        "khop k=3": lambda: pg.khop(orig_seeds, 3),
        "bfs": lambda: pg.bfs(orig_seeds),
        "shortest_paths weight=w": lambda: pg.shortest_paths(orig_seeds, weight="w"),
        "pagerank": lambda: pg.pagerank(),
        f"communities max_iters={COMMUNITY_ROUNDS}":
            lambda: pg.communities(max_iters=COMMUNITY_ROUNDS),
    }


def sample_read(pg):
    return lambda: pg.sample(P_SAMPLE_SEEDS, FANOUTS, pattern=P_SAMPLE_FILTER, seed=7)


def check_patterns(pg, res: dict, R: Ref) -> None:
    refs = pattern_refs(R)
    for p, ref in refs.items():
        check_match(res[f"match {p}"], ref, p)
        log(f"  {p}: {int(np.sum(ref[1]))} edges ≡ numpy")
    for p in (P_FUSED, P_PRED):
        plan = pg.explain(p)
        assert "fused-batch[kernel]" in plan, plan  # the TPU planner's pick
    log("  explain: fused label masks run impl=kernel")


def check_traversal(res: dict, R: Ref, seeds: np.ndarray) -> None:
    same(res["khop k=3"], bfs_ref(R, seeds, 3) >= 0, "khop k=3")
    depth = bfs_ref(R, seeds, 64)
    same(res["bfs"], depth, "bfs depths")
    log(f"  khop/bfs ≡ numpy ({int((depth >= 0).sum())} reached, "
        f"max depth {int(depth.max())})")

    dist = np.asarray(res["shortest_paths weight=w"])
    reach = bfs_ref(R, seeds, R.n) >= 0
    same(np.isfinite(dist), reach, "shortest_paths reachability")
    assert (dist[seeds] == 0).all()
    cand = (dist[R.src] + R.w).astype(np.float32)  # one f32 add, as the relax
    best = np.full(R.n, np.inf, np.float32)
    np.minimum.at(best, R.dst, cand)
    assert (dist <= best).all(), "an edge still relaxes: not a fixed point"
    tight = reach.copy()
    tight[seeds] = False
    assert (dist[tight] == best[tight]).all(), "a distance has no tight in-edge"
    log("  shortest_paths: fixed point, every distance tight, reach ≡ BFS")

    pr = np.asarray(res["pagerank"])
    assert np.isfinite(pr).all() and (pr >= 0).all()
    total = float(pr.astype(np.float64).sum())
    assert abs(total - 1.0) < 1e-3, total
    assert np.allclose(pr, pagerank_ref(R), rtol=1e-4, atol=1e-9), (
        "pagerank differs from the float64 power iteration")
    log(f"  pagerank: sums to {total:.6f}, ≡ float64 power iteration")

    lab = np.asarray(res[f"communities max_iters={COMMUNITY_ROUNDS}"])
    assert ((lab >= 0) & (lab < R.n)).all(), "community label out of range"
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    _, comp = connected_components(
        coo_matrix((np.ones(R.m, np.int8), (R.src, R.dst)), shape=(R.n, R.n)),
        directed=True, connection="weak")
    same(comp[lab], comp, "community crosses a component")
    log(f"  communities: {len(np.unique(lab))} labels, each inside its "
        f"component ({comp.max() + 1} components)")


def sample_eids(R_key: np.ndarray, n: int, dst_nodes, blk):
    """(nbrs, eids, mask) of one block in the oracle's (seed × fanout) form."""
    fan = blk.n_edges // max(blk.n_dst, 1)
    mask = blk.edge_mask.reshape(blk.n_dst, fan)
    nbrs = np.where(mask, blk.src_nodes[blk.edge_src].reshape(mask.shape), -1)
    key = dst_nodes[:, None].astype(np.int64) * n + np.maximum(nbrs, 0)
    pos = np.clip(np.searchsorted(R_key, key), 0, len(R_key) - 1)
    eids = np.where(mask & (R_key[pos] == key), pos, -1)
    return nbrs, eids, mask


def check_sample(blocks, R: Ref) -> None:
    from repro.kernels.neighbor_sample.ref import check_sample as oracle

    seeds = np.flatnonzero(R.lab(1) & (R.age >= 85))
    assert np.array_equal(blocks[-1].dst_nodes, seeds), "pattern seeds differ"
    seg = R.seg()
    for layer, blk in enumerate(reversed(blocks)):
        nbrs, eids, mask = sample_eids(R.key, R.n, blk.dst_nodes, blk)
        oracle(seg, R.dst, blk.dst_nodes, R.follows, FANOUTS[layer], nbrs, eids, mask)
    log(f"  sample: {len(seeds)} pattern seeds, layers pass check_sample")


# -------------------------------------------------------------------- phases
def phase_writes(pg, R: Ref) -> None:
    """Swap 1000 edges through the overlay, then fold them in."""
    gone, u, v = swap_edges(R.src, R.dst, R.n, R.key, 1000, np.random.default_rng(SEED + 1))
    su, sv = R.node_map[u], R.node_map[v]
    t0 = time.perf_counter()
    pg.delete_edges(R.node_map[R.src[gone]], R.node_map[R.dst[gone]])
    pg.insert_edges(su, sv)
    pg.add_edge_relationships(su, sv, ["follows"] * len(u))
    log(f"[first-light] delete_edges + insert_edges {len(u)} + relationships: "
        f"{time.perf_counter() - t0:.3f} s")
    alive = np.ones(R.m + len(u), bool)
    alive[gone] = False
    src_c, dst_c = np.concatenate([R.src, u]), np.concatenate([R.dst, v])
    fol_c = np.concatenate([R.follows, np.ones(len(u), bool)])
    ref = pattern_refs(R, src_c, dst_c, fol_c, alive)[P_FUSED]
    a, t = timed(lambda: pg.match(P_FUSED))
    log(f"[first-light] match over the overlay: {t:.3f} s")
    check_match(a, ref, "over the overlay")

    t0 = time.perf_counter()
    pg.compact()
    log(f"[first-light] compact: {time.perf_counter() - t0:.3f} s")
    same(pg.graph.node_map, R.node_map, "compacted node_map")
    order = np.argsort(src_c.astype(np.int64) * R.n + dst_c, kind="stable")
    order = order[alive[order]]
    same(pg.graph.src, src_c[order], "compacted src")
    same(pg.graph.dst, dst_c[order], "compacted dst")
    a, t = timed(lambda: pg.match(P_FUSED))
    log(f"[first-light] match after compact: {t:.3f} s")
    v_ref, e_ref, binds = ref
    check_match(a, (v_ref, e_ref[order], binds), "after compact")
    log(f"  writes: {len(u)} edges deleted and {len(u)} inserted; overlay "
        f"and compacted answers ≡ numpy")


def host_result(res):
    """A ``match`` result as the ``(vertex, edge, bindings)`` host triple."""
    return (np.asarray(res.vertex_mask), np.asarray(res.edge_mask),
            {k: np.asarray(v) for k, v in res.bindings().items()})


def phase_served(pg) -> None:
    from repro.launch.pgserve import pattern_pool
    from repro.service import PGClient, PGServer, Service, ServiceConfig

    pool = pattern_pool()
    direct = {p: host_result(pg.match(p)) for p in pool}
    swap = 8
    # the wire swap below (8 deletes + 8 inserts) starts one compaction
    cfg = ServiceConfig(result_cache_size=0, auto_compact_threshold=2 * swap)
    failures: list = []
    with Service(config=cfg) as svc:
        svc.add_graph("g", pg)
        server = PGServer(svc, port=0).start()
        try:
            def client(i: int) -> None:
                try:
                    with PGClient(port=server.port) as c:
                        mine = [pool[(i + j) % len(pool)] for j in range(24)]
                        for p in mine[:16]:
                            check_match(c.query("g", p), direct[p], f"served {p}")
                        for p, r in zip(mine[16:], c.query_batch("g", mine[16:])):
                            check_match(r, direct[p], f"served pipelined {p}")
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    failures.append(e)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            assert not any(t.is_alive() for t in threads), "client hung"
            if failures:
                raise failures[0]
            log(f"[first-light] served 192 requests over 8 clients: "
                f"{time.perf_counter() - t0:.3f} s")

            # writes through the wire; the background compactor folds them in
            g = pg.graph
            src, dst = np.asarray(g.src), np.asarray(g.dst)
            nodes = np.asarray(g.node_map)
            key = src.astype(np.int64) * g.n + dst
            gone, u, v = swap_edges(src, dst, g.n, key, swap,
                                    np.random.default_rng(SEED + 2))
            with PGClient(port=server.port) as c:
                c.delete_edges("g", nodes[src[gone]], nodes[dst[gone]])
                c.insert_edges("g", nodes[u], nodes[v])
                t0 = time.perf_counter()
                while svc.stats()["compactor"]["compactions"] < 1:
                    assert time.perf_counter() - t0 < 600, "no compaction"
                    time.sleep(0.2)
                log(f"[first-light] background compaction: "
                    f"{time.perf_counter() - t0:.3f} s")
                assert pg.graph.n == g.n and pg.graph.m == g.m
                check_match(c.query("g", P_FUSED), host_result(pg.match(P_FUSED)),
                            "served after background compaction")
        finally:
            server.close()
        st = svc.stats()
    log(f"  served: errors={st.get('errors', 0)} "
        f"group_fallbacks={st.get('group_fallbacks', 0)} "
        f"coalesced_launches={st.get('coalesced_launches', 0)} "
        f"compactor={st['compactor']}")
    assert st.get("errors", 0) == 0, st
    assert st.get("group_fallbacks", 0) == 0, st
    assert st.get("coalesced_launches", 0) > 0, st
    assert st["compactor"]["errors"] == 0, st["compactor"]
    assert st["compactor"]["compactions"] >= 1, st["compactor"]


def seal(pg, tag: str) -> None:
    """The first label and relationship queries seal the stores on the
    device; done once, before reads run concurrently."""
    _, t = timed(lambda: (pg.query_labels(["l0"]), pg.query_relationships(["follows"])))
    log(f"[first-light] {tag}seal label + relationship stores: {t:.3f} s")


def build_graph(m: int, mesh=None):
    """The tenant graph, and its edge array to wait on."""
    from repro.launch.pgserve import build_tenant_graph

    pg = build_tenant_graph("arr", m, mesh=mesh, seed=SEED)
    return pg, pg.graph.src


def run_single(m: int) -> None:
    with ThreadPoolExecutor(max_workers=1) as ex:
        ref = ex.submit(timed, lambda: Ref(m, SEED))  # host numpy, alongside
        (pg, _), t = timed(lambda: build_graph(m))
        R, t_ref = ref.result()
    log(f"[first-light] host build m={m}: {t:.1f} s")
    log(f"  numpy reference build (alongside): {t_ref:.1f} s (n={R.n}, m={R.m})")
    same(pg.graph.node_map, R.node_map, "node_map")
    same(pg.graph.src, R.src, "DI src")
    same(pg.graph.dst, R.dst, "DI dst")
    memory_line("after build")
    seal(pg, "")

    seeds = np.array([0, R.n // 3, R.n // 2, R.n - 1])
    log("phase reads: patterns, traversal and analytics, sample")
    reads = {**pattern_reads(pg), **traversal_reads(pg, R.node_map[seeds]),
             "sample": sample_read(pg)}
    t0 = time.perf_counter()
    res = run_reads(reads)
    log(f"[first-light] reads phase: {time.perf_counter() - t0:.1f} s")
    log("phase patterns")
    check_patterns(pg, res, R)
    log("phase traversal")
    check_traversal(res, R, seeds)
    log("phase sample")
    check_sample(res["sample"], R)
    log("phase writes")
    phase_writes(pg, R)
    log("phase served")
    phase_served(pg)
    memory_line("after all phases")


def phase_mesh(m: int) -> None:
    """Sharded (4 chips) ≡ unsharded, in this one process."""
    from repro.launch.mesh import make_entity_mesh

    mesh = make_entity_mesh()
    ndev = len(mesh.devices.ravel())
    built = first_calls({"unsharded": lambda: build_graph(m),
                         "sharded": lambda: build_graph(m, mesh)})
    one, sh = built["unsharded"][0][0], built["sharded"][0][0]
    log(f"[first-light] host builds, concurrent: unsharded "
        f"{built['unsharded'][1]:.1f} s, sharded {built['sharded'][1]:.1f} s")

    def spans(x, what):
        got = len(x.sharding.device_set)
        assert got == ndev, f"{what} lives on {got} device(s), not {ndev}"

    g = sh.graph
    for f in ("src", "dst", "seg"):
        spans(getattr(g, f), f"graph.{f}")
    seal(one, "unsharded ")
    seal(sh, "sharded ")
    spans(sh._vstore.finalize_sharded().bitmap, "label plane")
    spans(sh._estore.finalize_sharded().bitmap, "relationship plane")
    for name, (col, msk) in list(sh.vertex_props.items()) + list(sh.edge_props.items()):
        spans(col, f"column {name}")

    nodes = np.asarray(one.graph.node_map)
    seeds = nodes[[0, len(nodes) // 3, len(nodes) // 2, len(nodes) - 1]]
    reads = {}
    for tag, pg in (("unsharded", one), ("sharded", sh)):
        ops = {**pattern_reads(pg),
               "khop k=3": lambda pg=pg: pg.khop(seeds, 3),
               "shortest_paths weight=w":
                   lambda pg=pg: pg.shortest_paths(seeds, weight="w"),
               "pagerank weight=w": lambda pg=pg: pg.pagerank(weight="w"),
               "sample": sample_read(pg)}
        reads.update({(tag, k): fn for k, fn in ops.items()})
    t0 = time.perf_counter()
    res = first_calls(reads)
    log(f"[first-light] mesh reads, concurrent: {time.perf_counter() - t0:.1f} s")
    for (tag, k), (_, t) in res.items():
        log(f"[first-light] {tag} {k}: first {t:.3f} s (concurrent)")

    for (tag, k), (a, _) in res.items():
        if tag != "sharded":
            continue
        b = res[("unsharded", k)][0]
        if k.startswith("match"):
            spans(a.edge_mask, f"{k} result")
            same_result(a, b, f"mesh {k}")
        elif k == "sample":
            same_result(a, b, "mesh sample")
        elif k.startswith("pagerank"):
            spans(a, f"{k} result")
            assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-9), "mesh pagerank"
        else:
            spans(a, f"{k} result")
            same(a, b, f"mesh {k}")
    log(f"  mesh P={ndev}: match, khop, shortest_paths, sample bitwise; "
        f"pagerank within tolerance; stores and results span all {ndev} devices")
    memory_line("after mesh phase")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-mesh phase over four chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    device = device_check(args.chips)
    if args.chips == 4:
        phase_mesh(M_EDGES)
    else:
        run_single(M_EDGES)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

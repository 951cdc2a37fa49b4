"""Open-loop served pattern queries: independent dashboard users.

The chip process builds the graph through the program's bulk path, serves
it with a ``PGServer`` over a ``Service`` at the shipped ``ServiceConfig``,
and a load-generator process off the chip (``loadgen.py``) sends the
window's requests over ``clients`` ``PGClient`` connections.

Requests come from the traffic file's templates (chains of single hops
whose labels, relationships and ``age`` constants each request draws).
Every seed offers the same arrivals: the inter-arrival gaps (exponential
quantiles at ``rate_qps``) and the count of each template (Zipf ``zipf_s``
over the templates' order) are dealt evenly over ``blocks`` stretches of
the window, in one order drawn from the traffic file's ``order_seed``.  A
tail over a few dozen requests at 0.8 of the knee turns on where the long
requests fall among the short gaps, so the order is part of the work and
is the same for every seed; the seed draws the graph and what each request
asks for (labels, relationships, constants) and the replies compared.

Correct means every request of the window came back without error, and
the replies of a sample drawn from the seed (one request of each template
present, the rest uniform) equal the plain reference bit for bit.
"""
from __future__ import annotations

import gc
import json
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench import harness, reference


# ------------------------------------------------------------- the requests
def render(spec: dict, cfg: dict) -> str:
    """A chain spec as Cypher-lite text."""
    parts = []
    for i, node in enumerate(spec["nodes"]):
        txt = "abcdefgh"[i]
        if node["labels"]:
            txt += ":" + "|".join(f"{cfg['label_prefix']}{x}" for x in node["labels"])
        if node.get("pred"):
            name, op, value = node["pred"]
            txt += f" {{{name} {op} {value}}}"
        parts.append(f"({txt})")
        if i < len(spec["edges"]):
            e = spec["edges"][i]
            rel = "|".join(f"{cfg['relationship_prefix']}{x}" for x in e["rels"])
            parts.append(f"-[:{rel}]->" if e["dir"] == 1 else f"<-[:{rel}]-")
    return "".join(parts)


def instantiate(tmpl: dict, cfg: dict, rng: np.random.Generator,
                labels=None) -> dict:
    """Fill a template's slots with distinct labels and relationships and
    its predicate with a constant, all drawn from ``rng`` (the same number
    of draws for every template).  ``labels`` gives the labels to deal out
    in slot order instead of the drawn ones."""
    drawn = [int(x) for x in rng.choice(int(cfg["labels"]), 6, replace=False)]
    rels = [int(x) for x in rng.choice(int(cfg["relationships"]), 2, replace=False)]
    const = int(rng.integers(0, int(cfg["age_max"]) + 1))
    labels = drawn if labels is None else [int(x) for x in labels]
    nodes, at = [], 0
    for node in tmpl["nodes"]:
        k = int(node.get("labels", 0))
        nd = {"labels": labels[at:at + k], "pred": None}
        at += k
        if node.get("pred"):
            nd["pred"] = [node["pred"][0], node["pred"][1], const]
        nodes.append(nd)
    edges = [{"rels": rels[i:i + int(e.get("rels", 1))], "dir": int(e["dir"])}
             for i, e in enumerate(tmpl["edges"])]
    return {"nodes": nodes, "edges": edges}


def warm_patterns(traffic: dict, cfg: dict, data: dict, seed: int) -> List[str]:
    """Two instances of each template: the rarest labels first and the
    commonest last, then the other way round.  The planner starts a chain
    at its more selective end, so between them they run every orientation
    the window's draws can give a template."""
    by_count = np.argsort(np.bincount(data["v_att"], minlength=int(cfg["labels"])),
                          kind="stable")
    rng = np.random.default_rng([seed, 13])
    out = []
    for tmpl in traffic["templates"]:
        k = [int(nd.get("labels", 0)) for nd in tmpl["nodes"]]
        for order in (by_count, by_count[::-1]):
            first, last = list(order[:k[0]]), list(order[::-1][:k[-1]])
            middle = [x for x in order if x not in first + last][:sum(k[1:-1])]
            labels = first + middle + last if len(k) > 1 else first
            out.append(render(instantiate(tmpl, cfg, rng, labels), cfg))
    return out


def template_counts(n: int, k: int, s: float) -> np.ndarray:
    """Largest-remainder split of ``n`` requests over ``k`` templates by
    Zipf weights 1/rank**s."""
    w = np.arange(1, k + 1, dtype=np.float64) ** -s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    counts[np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]] += 1
    return counts


def stratified(values: np.ndarray, blocks: int, rng: np.random.Generator) -> np.ndarray:
    """``values`` in a seeded order in which every one of ``blocks``
    consecutive blocks holds an even share of them: sorted values are
    dealt to the blocks in turn and each block is shuffled."""
    dealt = [np.sort(values)[b::blocks] for b in range(blocks)]
    return np.concatenate([rng.permutation(d) for d in dealt])


def make_requests(traffic: dict, cfg: dict, seed: int, seconds: float,
                  rate: float) -> dict:
    """The window's schedule: due times, specs, texts and the sample kept
    for the reference comparison.  Gaps and templates are each dealt
    evenly over ``blocks`` stretches of the window, in the order that
    ``order_seed`` draws, so every seed offers the same arrivals and the
    load stays level through the window."""
    n = max(int(rate * seconds), 1)
    blocks = max(min(int(traffic["blocks"]), n), 1)
    q = (np.arange(n) + 0.5) / n
    rng = np.random.default_rng([int(traffic["order_seed"]), 10])
    gaps = stratified(-np.log1p(-q) / rate, blocks, rng)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    tmpls = traffic["templates"]
    counts = template_counts(n, len(tmpls), float(traffic["zipf_s"]))
    which = stratified(np.repeat(np.arange(len(tmpls)), counts), blocks, rng)
    draw = np.random.default_rng([seed, 11])
    specs = [instantiate(tmpls[t], cfg, draw) for t in which]
    texts = [render(s, cfg) for s in specs]
    pick = np.random.default_rng([seed, 12])
    keep = [int(pick.choice(np.flatnonzero(which == t))) for t in np.unique(which)]
    rest = np.setdiff1d(np.arange(n), keep)
    extra = max(int(traffic["sample_replies"]) - len(keep), 0)
    keep += [int(x) for x in pick.choice(rest, min(extra, len(rest)), replace=False)]
    return {"due": due, "which": which, "specs": specs, "texts": texts,
            "keep": sorted(keep)}


# ----------------------------------------------------------- the program
def build_graph(data: Dict[str, np.ndarray], cfg: dict):
    """The labelled graph through the bulk ingestion path a cold server
    pays: edges, labels, relationships, the ``age`` column."""
    from repro.core import PropGraph

    nodes = data["nodes"]
    lnames = np.array([f"{cfg['label_prefix']}{i}" for i in range(int(cfg["labels"]))],
                      dtype=object)
    rnames = np.array([f"{cfg['relationship_prefix']}{i}"
                       for i in range(int(cfg["relationships"]))], dtype=object)
    t = time.perf_counter()
    pg = PropGraph(backend=cfg["backend"]).add_edges_from(data["src"], data["dst"])
    t = _lap("build edges", t)
    pg.add_node_labels(nodes[data["v_ent"]], lnames[data["v_att"]])
    t = _lap("build labels", t)
    e = data["e_ent"]
    pg.add_edge_relationships(data["src"][e], data["dst"][e], rnames[data["e_att"]])
    t = _lap("build relationships", t)
    pg.add_node_properties("age", nodes, data["age"])
    _lap("build age", t)
    return pg


def _lap(what: str, t0: float) -> float:
    t = time.perf_counter()
    harness.log(f"setup: {what} {t - t0:.3f} s")
    return t


class LoadgenProcess:
    """The load generator's process and its line protocol."""

    def __init__(self, bench: Path):
        self.proc = subprocess.Popen(
            [sys.executable, str(bench / "runners" / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=harness.spawn_env())

    def call(self, cmd: str, **args) -> dict:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **args}) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise harness.BenchError(f"load generator exited during {cmd!r}")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise harness.BenchError(f"load generator: {reply.get('error')}")
        return reply

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write(json.dumps({"cmd": "exit"}) + "\n")
                self.proc.stdin.close()
                self.proc.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


class Served:
    """A ``Service`` and its ``PGServer`` for one graph."""

    def __init__(self, pg, name: str):
        from repro.service import PGServer, Service, ServiceConfig

        self.svc = Service(config=ServiceConfig())
        self.svc.add_graph(name, pg)
        self.server = PGServer(self.svc, port=0).start()

    def close(self) -> None:
        self.server.close()
        self.svc.close()


def counters(svc) -> Dict[str, float]:
    st = svc.stats()
    width = st.get("pg_sched_coalesce_width", {"count": 0, "sum": 0.0})
    return {"submitted": st.get("submitted", 0), "result_hits": st.get("result_hits", 0),
            "width_count": width["count"], "width_sum": width["sum"],
            "errors": st.get("errors", 0), "group_fallbacks": st.get("group_fallbacks", 0)}


def max_masks(traffic: dict, max_batch: int) -> int:
    """The most masks one store's coalesced launch can take: a full batch
    of the template with the most labelled node slots or edges."""
    per = max(max(sum(1 for nd in t["nodes"] if nd.get("labels")), len(t["edges"]))
              for t in traffic["templates"])
    return max_batch * per


def setup(ctx, lg: LoadgenProcess, rate: float):
    """Generate, build, seal and warm; returns (data, pg, requests)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.bitmap_query.ops import Q_BUCKETS, bucketed_q
    from repro.launch.pgserve import warm_serving_path
    from repro.service import ServiceConfig

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    t = time.perf_counter()
    data = ctx.cell.module("generators", cfg["generator"]).generate(cfg, ctx.seed)
    harness.log(f"setup: generate {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    pg = build_graph(data, cfg)
    jax.block_until_ready(pg.graph.src)
    harness.log(f"setup: build {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    jax.block_until_ready((pg.query_labels([f"{cfg['label_prefix']}0"]),
                           pg.query_relationships([f"{cfg['relationship_prefix']}0"])))
    harness.log(f"setup: seal {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    # the stores' batched queries at every batch bucket a window can reach
    # (the program's own warm-up), and the row split each bucket's result takes
    top = bucketed_q(max_masks(traffic, ServiceConfig().max_batch))
    warm_serving_path(pg, [], max_masks=top)
    t1 = _lap(f"warm buckets to {top}", t)
    for q in Q_BUCKETS:
        if q > top:
            break
        for size in (len(data["nodes"]), int(pg.graph.m)):
            jax.block_until_ready(jnp.zeros((q, size), bool)[0])
    t1 = _lap("warm row splits", t1)
    warm = warm_patterns(traffic, cfg, data, ctx.seed)
    served = Served(pg, traffic["graph_name"])
    try:
        lg.call("connect", port=served.server.port, clients=int(traffic["clients"]))
        t1 = _lap("warm service start", t1)
        w = lg.call("warm", graph=traffic["graph_name"], patterns=warm)
        harness.log(f"setup: warm {len(warm)} patterns {w['seconds']:.3f} s")
        lg.call("close")
    finally:
        t1 = time.perf_counter()
        served.close()
        _lap("warm service close", t1)
    harness.log(f"setup: warm {time.perf_counter() - t:.3f} s")
    reqs = make_requests(traffic, cfg, ctx.seed, ctx.seconds, rate)
    return data, pg, reqs


def window(ctx, lg: LoadgenProcess, pg, reqs: dict, rate: float, tag: str) -> dict:
    """One open-loop window on a fresh service; returns the loadgen's
    record (with the kept masks) and the service counters over it."""
    traffic = ctx.cell.traffic
    served = Served(pg, traffic["graph_name"])
    out = str(ctx.out_dir / f"replies-{tag}")
    try:
        lg.call("connect", port=served.server.port, clients=int(traffic["clients"]))
        c0 = counters(served.svc)
        with ctx.open_window():
            lg.call("run", graph=traffic["graph_name"],
                    schedule=[[float(d), t] for d, t in zip(reqs["due"], reqs["texts"])],
                    keep=reqs["keep"], out=out, wait_s=float(traffic["reply_wait_s"]))
        c1 = counters(served.svc)
        lg.call("close")
    finally:
        served.close()
    with open(out + ".json") as f:
        rec = json.load(f)
    with np.load(out + ".npz") as z:
        packed = {k: z[k] for k in z.files}
    Path(out + ".json").unlink()
    Path(out + ".npz").unlink()
    rec["masks"] = {}
    for i in reqs["keep"]:
        names = [k.split(":")[1] for k in packed if k.startswith(f"{i}:") and
                 not k.endswith(":n")]
        rec["masks"][i] = {k: np.unpackbits(packed[f"{i}:{k}"])[: int(packed[f"{i}:{k}:n"])]
                           .astype(bool) for k in names}
    rec["counters"] = {k: c1[k] - c0[k] for k in c0}
    rec["rate"] = rate
    return rec


def latency_stats(rec: dict, limit_s: float) -> Dict[str, float]:
    """Median and 90th percentile over every request of the window; a
    request that failed or never came back counts as ``limit_s``, the
    longest the load generator waits, beyond any latency limit."""
    lat = np.array([limit_s if (x is None or not ok) else x
                    for x, ok in zip(rec["latency_s"], rec["ok"])])
    return {f"p{q}_ms": float(np.percentile(lat, q) * 1e3) for q in (50, 75, 90)}


def reply_masks(vertex, edge, slots) -> Dict[str, np.ndarray]:
    """A match as the masks a wire reply carries, keyed as ``loadgen`` keeps them."""
    out = {"vertex": vertex, "edge": edge}
    out.update({f"bind_{'abcdefgh'[s]}": m for s, m in enumerate(slots)})
    return out


def check(data, cfg, reqs, rec) -> Dict[str, tuple]:
    """The reference comparison: wrong mask bits over the kept replies,
    and requests that failed or never came back."""
    ref = reference.PatternRef(data, cfg)
    wrong, compared = 0, 0
    for i in reqs["keep"]:
        got = rec["masks"].get(i)
        if got is None:
            continue  # failed or never came: counted below
        wrong += reference.wrong_bits(got, reply_masks(*ref.match(reqs["specs"][i])))
        compared += 1
    failed = sum(1 for ok in rec["ok"] if not ok)
    return {"mask_bits_wrong": (wrong, 0),
            "replies_compared_missing": (len(reqs["keep"]) - compared, 0),
            "requests_failed": (failed, 0)}


def run(ctx) -> harness.Outcome:
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    rate = float(traffic["rate_qps"])
    lg = LoadgenProcess(ctx.cell.bench)
    try:
        data, pg, reqs = setup(ctx, lg, rate)
        rec = window(ctx, lg, pg, reqs, rate, "window")
    finally:
        lg.stop()
    mem = ctx.memory_peak()
    del pg
    gc.collect()
    late = np.array(rec["dispatch_late_s"])
    harness.log(f"loadgen: {len(late)} requests at {rate} q/s; dispatch late "
                f"p50 {np.median(late) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms; "
                f"last answer {rec['elapsed_s']:.3f} s after the start")
    harness.log("service: " + ", ".join(f"{k} {v:g}" for k, v in rec["counters"].items()))
    for err in sorted(set(rec["errors"].values()))[:3]:
        harness.log(f"loadgen: {sum(e == err for e in rec['errors'].values())} "
                    f"request(s) failed: {err}")
    t = time.perf_counter()
    checks = check(data, cfg, reqs, rec)
    harness.log(f"reference: {time.perf_counter() - t:.3f} s for "
                f"{len(reqs['keep'])} replies")
    lat = latency_stats(rec, ctx.seconds + float(traffic["reply_wait_s"]))
    harness.log("latency: " + ", ".join(f"{k} {v:.3f} ms" for k, v in lat.items()))
    spans = [s for s in rec["spans"] if s]
    return harness.Outcome(
        attempted=len(rec["ok"]), failed=checks["requests_failed"][0],
        end_to_end={"match_p50_ms": lat["p50_ms"], "match_p90_ms": lat["p90_ms"]},
        layer={"spans": spans, "counters": rec["counters"],
               "graph": {"n": len(data["nodes"]), "labels": int(cfg["labels"]),
                         "relationships": int(cfg["relationships"])}},
        checks=checks, memory_peak_bytes=mem)


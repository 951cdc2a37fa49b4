"""Both traffic runners and their reference comparisons at a tiny size, on
the CPU, with the harness's look for a chip skipped.

The cells here come from files the benchmark does not ship: a
``BENCHMARK.json``, two configurations, a traffic mix and a metric written
into a scratch checkout beside a copy of ``bench/``, so the harness is
shown to find a cell from new files alone.  Wrong answers planted under
the timed path (and the controls) make ``correct`` come out false.
"""
import dataclasses
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402

SERVE = "tiny3.serve"
ANALYTICS = "tiny500.analytics"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    g3 = json.loads((harness.BENCH / "configs" / "graph3.json").read_text())
    g3.update(name="tiny3", edges=6000, vertex_pool=6000, labels=6, relationships=3)
    (bench / "configs" / "tiny3.json").write_text(json.dumps(g3))
    g5 = json.loads((harness.BENCH / "configs" / "g500.json").read_text())
    g5.update(name="tiny500", scale=8)
    (bench / "configs" / "tiny500.json").write_text(json.dumps(g5))
    serve = json.loads((harness.BENCH / "traffic" / "serve_open.json").read_text())
    serve.update(rate_qps=200.0, clients=4, sample_replies=1000,
                 templates=[serve["templates"][i] for i in (0, 2, 3, 7)])
    (bench / "traffic" / "tiny_serve.json").write_text(json.dumps(serve))
    (bench / "metrics" / "replies_traced.py").write_text(
        "def read(layer):\n"
        "    return float(len(layer['spans'])) if layer.get('spans') else None\n")
    bm = {
        "configs": [
            {"name": "tiny3", "file": "bench/configs/tiny3.json"},
            {"name": "tiny500", "file": "bench/configs/tiny500.json"}],
        "workloads": [
            {"name": SERVE, "config": "tiny3", "traffic": "tiny_serve", "chips": 1},
            {"name": ANALYTICS, "config": "tiny500", "traffic": "graphalytics_cycle",
             "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "match_p50_ms", "unit": "ms", "workloads": [SERVE]},
            {"name": "match_p90_ms", "unit": "ms", "workloads": [SERVE]},
            {"name": "analytics_evps", "unit": "ev/s", "workloads": [ANALYTICS]}],
        "per_layer": [
            {"name": "replies_traced", "unit": "requests", "moves": "match_p50_ms",
             "workloads": [SERVE]},
            {"name": "cdlp_job_ms", "unit": "ms", "moves": "analytics_evps"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def run(root, tmp_path, name, trace=False, seconds=0.5):
    cell = harness.Cell.load(name, root=root)
    dev = harness.device_info(1, require_tpu=False)
    res = harness.run_cell(cell, seed=2**33 + 5, seconds=seconds, trace=trace,
                           t_start=0.0, device=dev, out_dir=tmp_path)
    return cell, res


def test_serve_cell_from_new_files(root, tmp_path):
    cell, res = run(root, tmp_path, SERVE, trace=True)
    assert cell.bench == root / "bench"
    assert res["correct"], res["checks"]
    assert res["attempted"] == 100 and res["failed"] == 0
    assert res["checks"]["mask_bits_wrong"] == {"value": 0, "limit": 0}
    assert res["metrics"]["replies_traced"]["value"] == 100
    assert list(res)[-1] == "checks"


def test_analytics_cell_from_new_files(root, tmp_path):
    _, res = run(root, tmp_path, ANALYTICS)
    assert res["correct"], res["checks"]
    assert res["attempted"] % 4 == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"setup_s", "analytics_evps"}
    assert res["metrics"]["analytics_evps"]["value"] > 0


# ----------------------------------------------- faults under the timed path
def _altered(fn):
    def wrapped(*a, **k):
        res = fn(*a, **k)
        return dataclasses.replace(res, vertex_mask=res.vertex_mask.at[0].set(
            ~res.vertex_mask[0]))
    return wrapped


def _unchanged(g, cands, emasks, hops):
    v = cands[0]
    for c in cands[1:]:
        v = v | c
    e = emasks[0]
    for x in emasks[1:]:
        e = e | x
    return v, e, tuple(cands), tuple(emasks)


def _rotated(fn):
    def wrapped(pg, plans, **k):
        out = fn(pg, plans, **k)
        return out[1:] + out[:1]
    return wrapped


SERVE_FAULTS = {
    "answer altered where produced":
        ("repro.query.executor", "_finish_propagation", _altered),
    "propagation returns its state unchanged":
        ("repro.query.executor", "_propagate", lambda fn: _unchanged),
    "coalesced answers handed to the wrong requests":
        ("repro.service.service", "execute_coalesced", _rotated),
}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_not_correct(root, tmp_path, monkeypatch, fault):
    import importlib

    mod_name, attr, make = SERVE_FAULTS[fault]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))
    _, res = run(root, tmp_path, SERVE)
    assert not res["correct"], res["checks"]
    assert res["checks"]["mask_bits_wrong"]["value"] > 0


def _pagerank_unchanged(g, v_ok, e_ok, w, *, damping, iters):
    import jax.numpy as jnp

    return jnp.full((g.n,), 1.0 / g.n, jnp.float32)


def _bfs_altered(fn):
    def wrapped(*a, **k):
        return fn(*a, **k).at[0].add(1)
    return wrapped


ANALYTICS_FAULTS = {
    "pagerank returns its state unchanged":
        ("repro.traverse", "pagerank_masked", lambda fn: _pagerank_unchanged,
         "pagerank_max_rel_err"),
    "bfs answer altered where produced":
        ("repro.core.property_graph", "filtered_bfs", _bfs_altered, "bfs_depths_wrong"),
}


@pytest.mark.parametrize("fault", sorted(ANALYTICS_FAULTS))
def test_analytics_fault_is_not_correct(root, tmp_path, monkeypatch, fault):
    import importlib

    mod_name, attr, make, number = ANALYTICS_FAULTS[fault]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))
    _, res = run(root, tmp_path, ANALYTICS, seconds=0.2)
    assert not res["correct"], res["checks"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]


# --------------------------------------------------------------- controls
def test_controls_are_not_correct(root):
    """The control of each cell (forward-pass answers; bfloat16 PageRank),
    read with the cell's own comparison, fails it."""
    from bench import control

    serve = control.serve_control(harness.Cell.load(SERVE, root=root), 7, 1.0)
    assert serve["mask_bits_wrong"][0] > serve["mask_bits_wrong"][1]
    an = control.analytics_control(harness.Cell.load(ANALYTICS, root=root), 7)
    value, limit = an["pagerank_max_rel_err"]
    assert value > limit
    assert np.isfinite(value)

"""Bit-packed mask plane (core.bitplane): layout, invariants, end-to-end parity.

Four layers:

* **Round-trip**: host and device pack/unpack agree with each other and with
  ``np.packbits(bitorder='little')`` — property-style over sizes straddling
  word boundaries (hypothesis-driven when the package is present, a seeded
  sweep otherwise, same assertions either way).
* **Tail-padding invariant**: every mutator path that produces packed words
  (bulk build, incremental ``insert``, overlay deltas/tombstones,
  compaction, sharded placement) leaves the padding bits of the last word
  ZERO — the property word-space algebra relies on.
* **Packed ≡ byte parity**: match / khop / components / overlay views give
  bitwise-identical results with ``REPRO_PG_BYTE_MASKS`` forced on and off,
  across all three backends and the mesh path, plus a subprocess rerun at
  P=8 virtual devices (modeled on ``test_shard_pg``).
* **Executor accounting**: the ``pg_exec_fused_masks`` counter counts EDGE
  mask steps riding the fused batched launch (regression: they used to run
  standalone), and the wire codec round-trips packed masks bitwise.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PropGraph, bitplane, dip_arr
from repro.graph import random_uniform_graph
from repro.launch.mesh import make_entity_mesh

BACKENDS = ("arr", "list", "listd")
SIZES = (0, 1, 5, 31, 32, 33, 63, 64, 100, 257, 1000, 4095, 4096, 4097)


def _tail_zero(words: np.ndarray, n: int) -> bool:
    """True iff every bit for entities ≥ n is zero (rows may batch)."""
    words = np.asarray(words)
    w = bitplane.n_words(n)
    if words.shape[-1] > w:  # padded word axis (sharded planes)
        if np.any(words[..., w:]):
            return False
        words = words[..., :w]
    rem = n % bitplane.WORD
    if w == 0 or rem == 0:
        return True
    return not np.any(words[..., w - 1] >> rem)


# ------------------------------------------------------------- round-trips
def _check_roundtrip(bits: np.ndarray) -> None:
    n = bits.shape[-1]
    host = bitplane.pack_bits_host(bits)
    # little-endian layout contract: packbits bytes == the words' byte view
    ref8 = np.packbits(bits, axis=-1, bitorder="little")
    assert np.array_equal(
        np.ascontiguousarray(host).view(np.uint8)[..., : ref8.shape[-1]], ref8)
    assert _tail_zero(host, n)
    assert np.array_equal(bitplane.unpack_bits_host(host, n), bits)
    dev = np.asarray(bitplane.pack_mask(jnp.asarray(bits)))
    assert np.array_equal(dev, host)  # device layout == host layout
    assert np.array_equal(
        np.asarray(bitplane.unpack_mask(jnp.asarray(host), n)), bits)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.booleans(), min_size=0, max_size=300))
    def test_roundtrip_hypothesis(bits):
        _check_roundtrip(np.asarray(bits, bool))

except ImportError:  # seeded sweep with the same assertions

    @pytest.mark.parametrize("n", SIZES)
    def test_roundtrip_sweep(n):
        rng = np.random.default_rng(n + 1)
        for density in (0.0, 0.3, 1.0):
            _check_roundtrip(rng.random(n) < density)


def test_roundtrip_2d():
    rng = np.random.default_rng(3)
    bits = rng.random((5, 100)) < 0.4
    packed = bitplane.pack_bits_host(bits)
    assert packed.shape == (5, bitplane.n_words(100))
    assert np.array_equal(bitplane.unpack_bits_host(packed, 100), bits)
    assert np.array_equal(np.asarray(bitplane.pack_mask(jnp.asarray(bits))),
                          packed)


def test_or_reduce_matches_bool_any():
    rng = np.random.default_rng(9)
    bits = rng.random((7, 130)) < 0.2
    words = bitplane.pack_mask(jnp.asarray(bits))
    got = np.asarray(bitplane.unpack_mask(bitplane.or_reduce(words), 130))
    assert np.array_equal(got, bits.any(axis=0))


# ------------------------------------------------- tail bits after mutators
@pytest.mark.parametrize("n", (1, 31, 33, 100, 1000))
def test_tail_zero_dip_arr_build_and_insert(n):
    rng = np.random.default_rng(n)
    k = 6
    ent = rng.integers(0, n, 3 * n)
    att = rng.integers(0, k, 3 * n)
    dip = dip_arr.build_dip_arr_host(ent, att, k=k, n=n, packed=True)
    assert dip.packed and _tail_zero(dip.bitmap, n)
    # incremental insert, including out-of-range ids (dropped, not wrapped)
    dip2 = dip_arr.insert(dip, np.array([0, n - 1, n, n + 31]),
                          np.array([1, 2, 3, 4]))
    assert _tail_zero(dip2.bitmap, n)
    dev = dip_arr.build_dip_arr(ent, att, k=k, n=n, packed=True)
    assert _tail_zero(dev.bitmap, n)


def test_tail_zero_propgraph_mutators_and_compaction():
    rng = np.random.default_rng(4)
    n, m = 333, 900  # 333 % 32 != 0 → real padding bits to corrupt
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)

    def planes(pg):
        out = []
        for store in (pg._vstore, pg._estore):
            if store is None:
                continue
            host = getattr(store, "_host", None)
            if host is not None and getattr(host, "packed", False):
                out.append((host.bitmap, host.n))
            dev = getattr(store, "_store", None)
            if dev is not None and getattr(dev, "packed", False):
                out.append((np.asarray(dev.bitmap), dev.n))
        return out

    pg = PropGraph(backend="arr").add_edges_from(src, dst)
    pg.add_node_labels(np.arange(0, n, 3), "a")
    pg.add_edge_relationships(src[::2], dst[::2], "r")
    for plane, size in planes(pg):
        assert _tail_zero(plane, size)
    # overlay: delta edges first (endpoints must be alive), then tombstones
    pg.insert_edges(src[:5], np.roll(dst[:5], 1))
    pg.delete_vertices(np.arange(0, n, 41))
    pg.delete_edges(src[::97], dst[::97])
    pg.add_node_labels(np.arange(1, n, 50), "b")
    d = pg._vstore._delta
    if d.size:
        ids = pg._vstore.known_ids(["b"])
        words = d.mask_words(ids, pg._vstore.out_n)
        assert _tail_zero(words, pg._vstore.out_n)
    for plane, size in planes(pg):
        assert _tail_zero(plane, size)
    pg.compact()
    pg.query_labels(["a"])  # force the compacted stores to materialize
    pg.query_relationships(["r"])
    assert planes(pg), "compacted arr graph should hold packed planes"
    for plane, size in planes(pg):
        assert _tail_zero(plane, size)


def test_tail_zero_sharded_plane():
    mesh = make_entity_mesh()
    rng = np.random.default_rng(5)
    n, m = 271, 800
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    pg = PropGraph(backend="arr", mesh=mesh).add_edges_from(src, dst)
    pg.add_node_labels(np.arange(0, n, 2), "x")
    ss = pg._vstore.finalize_sharded()
    assert ss.packed
    assert _tail_zero(np.asarray(ss.bitmap), ss.n)


# --------------------------------------------------- packed ≡ byte parity
def _build_graph(backend, mesh=None, m=1000, seed=11):
    rng = np.random.default_rng(seed)
    src, dst = random_uniform_graph(m, seed=seed)
    pg = PropGraph(backend=backend, mesh=mesh).add_edges_from(src, dst)
    nodes = np.asarray(pg.graph.node_map)
    pg.add_node_labels(nodes, rng.choice(["p", "q", "r"], len(nodes)))
    es, ed = np.asarray(pg.graph.src), np.asarray(pg.graph.dst)
    pg.add_edge_relationships(
        nodes[es], nodes[ed], rng.choice(["f", "g"], len(es)))
    pg.add_node_properties(
        "age", nodes, rng.integers(0, 90, len(nodes)).astype(np.int32))
    pg.delete_vertices(nodes[:: max(len(nodes) // 10, 1)])
    return pg


def _parity_surfaces(pg):
    """Deterministic result bundle covering match/khop/components/overlay."""
    out = []
    res = pg.match("(a:p {age > 20})-[:f]->(b:q|r)")
    out += [res.vertex_mask, res.edge_mask, *res.node_masks, *res.edge_masks]
    res = pg.match("(a:p)-[:f*1..3]->(b)")
    out += [res.vertex_mask, res.edge_mask]
    nodes = np.asarray(pg.graph.node_map)
    out.append(pg.khop(nodes[:3], 2, pattern="(a)-[:f]->(b)"))
    out.append(pg.components("(a)-[:f|g]->(b)"))
    snap = pg.snapshot()  # overlay view: snapshot isolation surface
    out.append(snap.query_labels(["p"]))
    out.append(snap.match("(a:q)-[:g]->(b)").vertex_mask)
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("backend", BACKENDS)
def test_packed_equals_byte(backend):
    results = {}
    for packed in (True, False):
        with bitplane.byte_masks(not packed):
            results[packed] = _parity_surfaces(_build_graph(backend))
    for a, b in zip(results[True], results[False]):
        assert np.array_equal(a, b)


def test_packed_equals_byte_mesh():
    mesh = make_entity_mesh()
    results = {}
    for packed in (True, False):
        with bitplane.byte_masks(not packed):
            results[packed] = _parity_surfaces(_build_graph("arr", mesh=mesh))
    for a, b in zip(results[True], results[False]):
        assert np.array_equal(a, b)


def test_env_flag_forces_byte_store():
    with bitplane.byte_masks():
        pg = _build_graph("arr")
        assert not pg._vstore.packed
    pg = _build_graph("arr")
    assert pg._vstore.packed  # default this release


def test_eight_virtual_devices_subprocess():
    """P=8 parity: packed ≡ byte across backends and the mesh, in a fresh
    interpreter with 8 virtual CPU devices (word-axis sharding + the packed
    OR all-reduce frontier actually cross shard boundaries)."""
    code = """
import numpy as np
import jax
assert jax.device_count() == 8, jax.device_count()
import tests.test_bitplane as tb
from repro.core import bitplane
from repro.launch.mesh import make_entity_mesh

for backend in tb.BACKENDS:
    results = {}
    for packed in (True, False):
        with bitplane.byte_masks(not packed):
            results[packed] = tb._parity_surfaces(tb._build_graph(backend))
    for a, b in zip(results[True], results[False]):
        assert np.array_equal(a, b), backend
mesh = make_entity_mesh()
results = {}
for packed in (True, False):
    with bitplane.byte_masks(not packed):
        results[packed] = tb._parity_surfaces(tb._build_graph("arr", mesh=mesh))
for a, b in zip(results[True], results[False]):
    assert np.array_equal(a, b), "mesh"
print("P8 PARITY OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.join(os.path.dirname(__file__), ".."),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "P8 PARITY OK" in out.stdout


# -------------------------------------------------- executor fused counter
def test_edge_masks_ride_fused_batched_launch():
    """Regression: plans with ≥2 edge relationship masks fuse them into one
    batched launch — ``pg_exec_fused_masks`` counts node AND edge steps."""
    from repro.obs import metrics

    pg = _build_graph("arr")
    pattern = "(a:p)-[:f]->(b:q)-[:g]->(c:r)"  # 3 node + 2 edge mask steps
    plan_fused = pg.match(pattern).plan  # warm: also asserts it executes
    assert plan_fused.fused_node_slots == (0, 1, 2)
    assert plan_fused.fused_edge_slots == (0, 1)
    fused = metrics.GLOBAL.counter("pg_exec_fused_masks")
    masks = metrics.GLOBAL.counter("pg_exec_mask_steps")
    prev_enabled = metrics.set_enabled(True)
    f0, m0 = fused.value(), masks.value()
    try:
        pg.match(pattern)
    finally:
        metrics.set_enabled(prev_enabled)
    assert masks.value() - m0 == 5
    assert fused.value() - f0 == 5  # all five steps fused, edges included


# ------------------------------------------------------- wire round-trip
def test_wire_packed_masks_bitwise():
    from repro.service import wire

    pg = _build_graph("arr")
    res = pg.match("(a:p)-[:f]->(b)")
    meta, arrays = wire.result_to_wire(res)
    assert all(isinstance(a, (wire.PackedMask, wire.SparseMask))
               for a in arrays)
    frame = wire.encode_msg(dict(meta, op="match_result"), arrays)
    # device-encoded blobs must be byte-identical to the generic bool path
    plain = [wire._as_bool_mask(x) for x in arrays]
    assert frame == wire.encode_msg(dict(meta, op="match_result"), plain)
    import socket

    a, b = socket.socketpair()
    try:
        wire.send_msg(a, dict(meta, op="match_result"), arrays)
        hdr, arrs = wire.recv_msg(b)
    finally:
        a.close()
        b.close()
    got = wire.wire_to_result({"vars": meta["vars"]}, arrs)
    assert np.array_equal(got.vertex_mask, np.asarray(res.vertex_mask))
    assert np.array_equal(got.edge_mask, np.asarray(res.edge_mask))
    for k, v in res.bindings().items():
        assert np.array_equal(got.bindings()[k], np.asarray(v))

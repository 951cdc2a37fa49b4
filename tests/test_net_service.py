"""pgd network front-end: wire codec, server/client round-trips, adaptive
batching (src/repro/service/{wire,server,client}.py, ARCHITECTURE §9).

The contracts under test:

* the codec round-trips headers and arrays exactly (a bool mask travels
  as its set positions when 32·popcount < n, else packbits-packed, and
  comes back bitwise-identical either way), and rejects garbage frames
  with ``ProtocolError`` instead of misreading them;
* a query through ``PGClient`` → TCP → ``PGServer`` → ``Service`` returns
  masks bitwise-equal to in-process ``PropGraph.match`` (the paper §III
  client–server split must be invisible to correctness), including
  pipelined bursts, cross-backend ``load_graph`` reopens, and mutations
  applied over the wire;
* failures stay isolated: a bad request errors its own response (with the
  real exception type) and the session keeps serving;
* the adaptive micro-batch window (ROADMAP item): no batching latency when
  the queue is empty, window-batching under pressure, and ``window_ms=0``
  stays live (the negative-timeout clamp regression).
"""
import socket
import threading
import time

import numpy as np
import pytest

from repro.core import PropGraph
from repro.launch.pgserve import build_tenant_graph, pattern_pool
from repro.service import MicroBatcher, PGClient, PGServer, Service, ServiceConfig
from repro.service import wire

PATTERNS = (
    "(a:l1|l2)-[:follows]->(b:l3)",
    "(a:l0 {age > 30})-[:likes]->(b)",
    "(a)<-[:likes]-(b:l4|l5)",
    "(a:l6)-[:follows]->(b)-[:likes]->(c:l7)",
)


def _eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool((a == b).all())


def _assert_wire_matches(got, ref):
    assert _eq(got.vertex_mask, ref.vertex_mask)
    assert _eq(got.edge_mask, ref.edge_mask)
    gb, rb = got.bindings(), ref.bindings()
    assert sorted(gb) == sorted(rb)
    for k in rb:
        assert _eq(gb[k], rb[k]), k


# ------------------------------------------------------------------- codec
def test_wire_roundtrip_header_and_arrays():
    arrays = [
        np.arange(7, dtype=np.int32),
        np.random.default_rng(0).standard_normal((3, 5)).astype(np.float32),
        np.array([], dtype=np.int64),
        np.random.default_rng(1).random(83) > 0.5,  # bool: packbits path
        np.zeros((2, 9), dtype=np.bool_),
    ]
    header = {"op": "query", "id": 3, "pattern": "(a)-[]->(b)", "impl": None}
    a, b = socket.socketpair()
    try:
        wire.send_msg(a, header, arrays)
        got_header, got_arrays = wire.recv_msg(b)
        assert got_header == header
        assert len(got_arrays) == len(arrays)
        for orig, back in zip(arrays, got_arrays):
            assert back.dtype == orig.dtype and back.shape == orig.shape
            assert _eq(back, orig)
    finally:
        a.close(), b.close()


def test_wire_rejects_garbage_and_truncation():
    a, b = socket.socketpair()
    try:
        a.sendall(b"HTTP/1.1 200 OK\r\n\r\n" + b"x" * 20)
        with pytest.raises(wire.ProtocolError, match="magic"):
            wire.recv_msg(b)
    finally:
        a.close(), b.close()
    a, b = socket.socketpair()
    try:
        frame = wire.encode_msg({"op": "ping", "id": 1}, [np.arange(100)])
        a.sendall(frame[: len(frame) // 2])
        a.close()  # truncated mid-frame
        with pytest.raises(wire.ProtocolError, match="truncated"):
            wire.recv_msg(b)
    finally:
        b.close()


def test_wire_rejects_hostile_array_specs():
    """A frame whose header carries bad array specs must surface as
    ProtocolError (the session/client loops only handle protocol errors),
    never a raw numpy exception."""
    import json
    import struct

    def frame_with_specs(specs, blob=b""):
        hdr = json.dumps({"op": "x", "id": 1, "arrays": specs}).encode()
        payload = struct.pack("!I", len(hdr)) + hdr + blob
        return wire.MAGIC + struct.pack("!I", len(payload)) + payload

    for specs in (
        [{"dtype": "bogus", "shape": [3]}],
        [{"dtype": "int32", "shape": [-4]}],
        [{"dtype": "object", "shape": [2]}],
        [{"shape": [2]}],
        [{"dtype": "int32", "shape": [2**30, 2**30, 2**30]}],  # int64 wrap
        "not-a-list",
    ):
        a, b = socket.socketpair()
        try:
            a.sendall(frame_with_specs(specs))
            with pytest.raises(wire.ProtocolError):
                wire.recv_msg(b)
        finally:
            a.close(), b.close()


def _mask_with(n, k, seed=0, spread=False):
    """A (n,) mask with k set bits: random, or one a word (bit 32·i)."""
    mask = np.zeros(n, bool)
    if spread:
        mask[np.arange(k) * 32] = True
    else:
        mask[np.random.default_rng(seed).choice(n, k, replace=False)] = True
    return mask


def _masks_encoded():
    from repro.obs.metrics import GLOBAL

    return {enc: GLOBAL.counter("pg_wire_masks", enc=enc).value()
            for enc in ("ids", "bitmap")}


# (n, popcount, one set bit a word): empty and single bits, the break-even
# n/32 ± 1 (n a multiple of 32 and not, nor of 8), each edge of the
# device's nonzero-word buffers (the census's 1024, then 4096, 16384, 65536
# and the largest ids count; a spread mask fills them exactly), all-true
@pytest.mark.parametrize("n,k,spread", [
    (1, 0, False), (1, 1, False), (64, 1, False), (64, 2, False),
    (100, 3, False), (100, 4, False),
    (8_191, 0, False), (8_191, 255, False), (8_191, 256, False),
    (40_000, 1_249, False), (40_000, 1_250, False), (40_000, 1_251, False),
    (40_003, 1_024, False), (40_003, 1_025, False), (40_003, 1_250, False),
    (40_003, 1_251, False),
    (70_000, 1_024, True), (70_000, 1_025, True),
    (300_007, 4_096, False), (300_007, 4_097, False), (300_007, 4_097, True),
    (600_007, 16_384, False), (600_007, 16_385, False),
    (600_007, 16_385, True),
    (2_100_007, 65_536, False), (2_100_007, 65_537, False),
    (2_100_007, 65_625, False), (2_100_007, 65_537, True),
    (1_000, 1_000, False),
])
def test_wire_mask_encoding_by_popcount(n, k, spread):
    """A (n,) bool mask ships as ids exactly when 32·k < n, from a host
    array and from a device array alike, and comes back bitwise equal."""
    import jax.numpy as jnp

    mask = _mask_with(n, k, spread=spread)
    sparse = 32 * k < n
    (payload,) = wire._mask_payloads([jnp.asarray(mask)])
    assert isinstance(payload, wire.SparseMask if sparse else wire.PackedMask)
    assert _eq(wire._as_bool_mask(payload), mask)
    before = _masks_encoded()
    frame = wire.encode_msg({"op": "x"}, [payload, mask])
    after = _masks_encoded()
    enc = "ids" if sparse else "bitmap"
    assert after[enc] - before[enc] == 2
    hdr_len = int.from_bytes(frame[8:12], "big")
    specs = __import__("json").loads(frame[12:12 + hdr_len])["arrays"]
    assert [s.get("enc") for s in specs] == ["ids" if sparse else None] * 2
    a, b = socket.socketpair()
    try:
        threading.Thread(target=a.sendall, args=(frame,), daemon=True).start()
        _, arrays = wire.recv_msg(b)
    finally:
        a.close(), b.close()
    for back in arrays:
        assert back.dtype == np.bool_ and _eq(back, mask)


def _ids_frame(spec, ids):
    import json
    import struct

    blob = np.asarray(ids, "<i4").tobytes()
    hdr = json.dumps({"op": "x", "id": 1, "arrays": [spec]}).encode()
    payload = struct.pack("!I", len(hdr)) + hdr + blob
    return wire.MAGIC + struct.pack("!I", len(payload)) + payload


def _ids_spec(n, count, **kw):
    return dict({"dtype": "bool", "shape": [n], "enc": "ids",
                 "count": count}, **kw)


@pytest.mark.parametrize("spec,ids", [
    (_ids_spec(10, 2), [3, 10]),  # id = n
    (_ids_spec(10, 2), [-1, 3]),  # negative
    (_ids_spec(10, 3), [1, 5, 4]),  # unsorted
    (_ids_spec(10, 3), [1, 4, 4]),  # duplicate
    (_ids_spec(10, 2), [1, 4, 6]),  # count < blob
    (_ids_spec(10, 3), [1, 4]),  # count > blob
    (_ids_spec(3, 4), [0, 1, 2, 2]),  # count > n
    (_ids_spec(10, -1), []),
    (_ids_spec(10, "2"), [1, 2]),
    (_ids_spec(10, True), [1]),
    (_ids_spec(10, 1, enc="rle"), [1]),
    (_ids_spec(10, 1, dtype="int32"), [1]),
    (dict(_ids_spec(10, 1), shape=[2, 5]), [1]),
    ({"dtype": "bool", "shape": [10], "enc": "ids"}, [1]),  # no count
])
def test_wire_rejects_hostile_ids_specs(spec, ids):
    a, b = socket.socketpair()
    try:
        a.sendall(_ids_frame(spec, ids))
        with pytest.raises(wire.ProtocolError):
            wire.recv_msg(b)
    finally:
        a.close(), b.close()


def test_wire_clean_eof_is_connection_error():
    a, b = socket.socketpair()
    a.close()
    try:
        with pytest.raises(ConnectionError):
            wire.recv_msg(b)
    finally:
        b.close()


def test_wire_exception_roundtrip():
    e = wire.wire_to_exc(wire.exc_to_wire(KeyError("nosuchprop")))
    assert isinstance(e, KeyError) and "nosuchprop" in str(e)
    e = wire.wire_to_exc({"type": "SomeServerOnlyError", "message": "boom"})
    assert isinstance(e, wire.RemoteError) and "boom" in str(e)


def test_wire_match_result_roundtrip():
    pg = build_tenant_graph("arr", 400, seed=7)
    ref = pg.match(PATTERNS[0])
    meta, arrays = wire.result_to_wire(ref)
    back = wire.wire_to_result(meta, [np.asarray(x) for x in arrays])
    _assert_wire_matches(back, ref)


# ----------------------------------------------------------- server/client
@pytest.fixture(scope="module")
def served():
    """One server (own thread pool, real TCP socket) + the graph it serves;
    module-scoped — sessions are cheap, graphs are not."""
    pg = build_tenant_graph("arr", 800, seed=3)
    svc = Service()
    svc.add_graph("g", pg)
    server = PGServer(svc, port=0).start()
    yield server, pg
    server.close()
    svc.close()


def test_net_query_bitwise_equals_match(served):
    server, pg = served
    with PGClient(port=server.port) as c:
        assert c.ping()
        for p in PATTERNS:
            _assert_wire_matches(c.query("g", p), pg.match(p))


def test_net_reply_masks_as_ids_or_bitmap(served):
    """A labelled hop's masks come back as ids, an untyped relationship
    hop's (more than n/32 vertices) as bitmaps, both bitwise equal to the
    in-process match; a sample reply stays bitwise equal too."""
    server, pg = served
    n = pg.graph.n
    with PGClient(port=server.port) as c:
        for pattern, enc in (("(a:l1|l2)-[:follows]->(b:l3)", "ids"),
                             ("(a)-[:follows]->(b)", "bitmap")):
            ref = pg.match(pattern)
            dense = 32 * int(np.asarray(ref.vertex_mask).sum()) >= n
            assert dense == (enc == "bitmap")
            before = _masks_encoded()
            _assert_wire_matches(c.query("g", pattern), ref)
            moved = {e: _masks_encoded()[e] - before[e] for e in before}
            assert moved[enc] >= 3 and moved[enc] + moved[
                {"ids": "bitmap", "bitmap": "ids"}[enc]] == 4, moved
        seeds = np.asarray(pg.graph.node_map)[:32]
        got = c.sample("g", seeds, [4, 2], seed=8)
    ref = pg.sample(seeds, [4, 2], seed=8)
    assert len(got) == len(ref)
    for bg, br in zip(got, ref):
        for f in ("src_nodes", "dst_nodes", "edge_src", "edge_dst",
                  "edge_mask"):
            assert _eq(getattr(bg, f), getattr(br, f)), f


def test_net_pipelined_batch_with_duplicates(served):
    server, pg = served
    burst = list(PATTERNS) + [PATTERNS[0], PATTERNS[2]]
    with PGClient(port=server.port) as c:
        got = c.query_batch("g", burst)
    for p, res in zip(burst, got):
        _assert_wire_matches(res, pg.match(p))


def test_net_out_of_order_resolution(served):
    """Submit A then B, read B first: responses are matched by id, not
    arrival order — the pipelining contract."""
    server, pg = served
    with PGClient(port=server.port) as c:
        ha = c.submit("g", PATTERNS[0])
        hb = c.submit("g", PATTERNS[1])
        _assert_wire_matches(hb.result(), pg.match(PATTERNS[1]))
        _assert_wire_matches(ha.result(), pg.match(PATTERNS[0]))


def test_net_errors_fail_alone_and_session_survives(served):
    server, pg = served
    with PGClient(port=server.port) as c:
        with pytest.raises(KeyError, match="nosuchprop"):
            c.query("g", "(a {nosuchprop > 1})-[:follows]->(b)")
        with pytest.raises(KeyError, match="unknown graph"):
            c.query("nope", PATTERNS[0])
        with pytest.raises(Exception):  # noqa: B017 — any server-side error
            c._call("no_such_op")
        # the connection is still good after three failed requests
        _assert_wire_matches(c.query("g", PATTERNS[0]), pg.match(PATTERNS[0]))
        assert "plan" in c.explain("g", PATTERNS[0]).lower()
        stats = c.stats()
        assert stats["completed"] > 0
        assert c.graphs()["g"] == pg.version


def test_net_mutation_invalidates_and_stays_bitwise():
    pg = build_tenant_graph("arr", 500, seed=11)
    local = build_tenant_graph("arr", 500, seed=11)  # in-process reference
    with Service() as svc:
        svc.add_graph("g", pg)
        with PGServer(svc, port=0) as server, PGClient(port=server.port) as c:
            before = c.query("g", PATTERNS[0])
            nodes = np.asarray(local.graph.node_map)
            v = c.add_node_labels("g", nodes[:9], ["l1"] * 9)
            local.add_node_labels(nodes[:9], ["l1"] * 9)
            assert v == local.version
            after = c.query("g", PATTERNS[0])
            _assert_wire_matches(after, local.match(PATTERNS[0]))
            assert before is not None  # first query really executed
            stats = c.stats()
            assert stats.get("invalidated_results", 0) >= 1  # purge fired
            # property mutation over the wire too
            c.add_node_properties("g", "age", nodes[:5],
                                  np.full(5, 99, np.int32))
            local.add_node_properties("age", nodes[:5], np.full(5, 99, np.int32))
            _assert_wire_matches(c.query("g", PATTERNS[1]),
                                 local.match(PATTERNS[1]))


def test_net_load_graph_cross_backend(served, tmp_path):
    from repro.core.io import save_propgraph

    server, pg = served
    path = save_propgraph(str(tmp_path / "pg"), pg)
    with PGClient(port=server.port) as c:
        info = c.load_graph("disk", path, backend="listd")
        assert info["backend"] == "listd"
        assert info["n"] == pg.n_vertices and info["m"] == pg.n_edges
        _assert_wire_matches(c.query("disk", PATTERNS[0]), pg.match(PATTERNS[0]))


def test_net_concurrent_client_connections(served):
    """Several OS-level connections at once: per-session framing must not
    interleave (each session has its own write lock)."""
    server, pg = served
    refs = {p: pg.match(p) for p in PATTERNS}
    errors = []

    def one_client():
        try:
            with PGClient(port=server.port) as c:
                for p in PATTERNS:
                    _assert_wire_matches(c.query("g", p), refs[p])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=one_client) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors


def test_net_graceful_drain_completes_inflight():
    pg = build_tenant_graph("arr", 500, seed=13)
    svc = Service()
    svc.add_graph("g", pg)
    server = PGServer(svc, port=0).start()
    try:
        with PGClient(port=server.port) as c:
            handles = [c.submit("g", p) for p in PATTERNS]
            c.drain()  # stops the listener, waits for the futures above
            for h, p in zip(handles, PATTERNS):
                _assert_wire_matches(h.result(), pg.match(p))
            # drained server accepts no NEW connections
            with pytest.raises(OSError):
                PGClient(port=server.port, connect_timeout=2).ping()
    finally:
        server.close()
        svc.close()


# ------------------------------------------------------- adaptive batching
def _collecting_batcher(**kw):
    batches, done = [], threading.Event()

    def execute(batch):
        batches.append(list(batch))
        done.set()

    return MicroBatcher(execute, **kw), batches, done


def test_adaptive_window_skips_wait_when_idle():
    """With a HUGE window, an idle-queue request must still execute
    immediately — the adaptive bypass is what removes the c=1 latency tax."""
    b, batches, done = _collecting_batcher(window_ms=5_000.0, adaptive=True)
    try:
        t0 = time.monotonic()
        b.submit("r1")
        assert done.wait(timeout=2.0), "request stuck behind the window"
        assert time.monotonic() - t0 < 2.0
        assert batches[0] == ["r1"]
    finally:
        b.close(timeout=1.0)


def test_window_opens_under_queue_pressure():
    """When requests are already queued, the window forms a real batch."""
    gate = threading.Event()
    batches = []

    def execute(batch):
        batches.append(list(batch))
        gate.wait(timeout=5.0)  # hold the worker so pressure builds

    b = MicroBatcher(execute, window_ms=200.0, adaptive=True, max_batch=8)
    try:
        b.submit("first")  # worker blocks inside execute()
        time.sleep(0.05)
        for i in range(5):
            b.submit(f"r{i}")  # all queued while the worker is held
        gate.set()
        b.close(timeout=5.0)  # drains: the 5 must have batched together
        assert batches[0] == ["first"]
        assert ["r%d" % i for i in range(5)] in batches  # one pressure batch
    finally:
        gate.set()
        b.close(timeout=1.0)


def test_window_ms_zero_stays_live():
    """The negative-timeout clamp regression: a zero (or already-expired)
    window must drain what is queued and never pass a negative timeout to
    the queue wait."""
    b, batches, _ = _collecting_batcher(window_ms=0.0, adaptive=False)
    try:
        for i in range(16):
            b.submit(i)
        b.close(timeout=5.0)
        assert sorted(x for batch in batches for x in batch) == list(range(16))
    finally:
        b.close(timeout=1.0)


def test_service_window_ms_zero_end_to_end():
    pg = build_tenant_graph("arr", 400, seed=5)
    with Service(config=ServiceConfig(window_ms=0.0)) as svc:
        svc.add_graph("g", pg)
        futs = [svc.submit("g", p) for p in PATTERNS]
        for f, p in zip(futs, PATTERNS):
            got = f.result(timeout=120)
            assert _eq(got.vertex_mask, pg.match(p).vertex_mask)


def test_fixed_window_config_still_available():
    """adaptive_window=False restores the PR 3 behavior (benchmark's
    fixed-window comparison row depends on it)."""
    pg = build_tenant_graph("arr", 400, seed=5)
    cfg = ServiceConfig(adaptive_window=False, window_ms=1.0)
    with Service(config=cfg) as svc:
        svc.add_graph("g", pg)
        got = svc.query("g", PATTERNS[0])
        assert _eq(got.vertex_mask, pg.match(PATTERNS[0]).vertex_mask)


# ------------------------------------------------------------ cross-process
def test_cross_process_net_roundtrip():
    """A REAL second OS process: spawn the serve-mode CLI, query it over
    TCP, compare bitwise against this process's match().  (The CI smoke
    runs the full three-backend version; this keeps a single-backend gate
    inside the suite.)"""
    from repro.launch.pgserve import spawn_server

    pg = build_tenant_graph("arr", 400, seed=0)
    proc, port = spawn_server(["--backends", "arr", "--m", "400", "--seed", "0"])
    try:
        with PGClient(port=port) as c:
            for p in PATTERNS[:2]:
                _assert_wire_matches(c.query("arr", p), pg.match(p))
            c.shutdown()
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


# ------------------------------------------------------------ observability
def test_net_trace_id_roundtrip(served):
    """The client mints a trace id per query; the server's span tree comes
    back in the response header rooted at that id (ARCHITECTURE §13)."""
    server, pg = served
    with PGClient(port=server.port) as c:
        h = c.submit("g", PATTERNS[0])
        res = h.result()
        assert h.trace_id and h.trace is not None
        assert h.trace["trace_id"] == h.trace_id
        names = [s["name"] for s in h.trace["spans"]]
        assert "serialize" in names, names
        assert "parse" in names or "cache" in names, names
        assert c.last_trace is h.trace
        _assert_wire_matches(res, pg.match(PATTERNS[0]))


def test_net_trace_opt_out(served):
    """client.trace = False sends no trace id; no tree comes back."""
    server, pg = served
    with PGClient(port=server.port) as c:
        c.trace = False
        h = c.submit("g", PATTERNS[1])
        h.result()
        assert h.trace_id is None and h.trace is None


def test_net_slow_query_ring_captures_client_trace():
    """slow_query_ms=0 marks every query slow: the traces verb's slow ring
    must hold span trees rooted at the CLIENT's ids."""
    pg = build_tenant_graph("arr", 400, seed=7)
    svc = Service(config=ServiceConfig(slow_query_ms=0.0))
    svc.add_graph("g", pg)
    server = PGServer(svc, port=0).start()
    try:
        with PGClient(port=server.port) as c:
            hs = [c.submit("g", p) for p in PATTERNS]
            for h in hs:
                h.result()
            payload = c.traces()
            slow_ids = {t["trace_id"] for t in payload["slow"]}
            assert {h.trace_id for h in hs} <= slow_ids
            assert {t["trace_id"] for t in payload["traces"]} >= slow_ids
    finally:
        server.close()
        svc.close()


def test_net_metrics_verb_parses_and_counts(served):
    """The metrics verb returns Prometheus text that parses, moves by
    exactly the burst size, and agrees with the stats verb."""
    from repro.obs import parse_prometheus

    server, pg = served
    with PGClient(port=server.port) as c:
        m1 = parse_prometheus(c.metrics())
        for p in PATTERNS:
            c.query("g", p)
        m2 = parse_prometheus(c.metrics())
        st = c.stats()
    assert (m2["pg_service_submitted_total"]
            == m1["pg_service_submitted_total"] + len(PATTERNS))
    assert m2["pg_service_submitted_total"] == st["submitted"]
    assert m2["pg_service_completed_total"] == st["completed"]
    # wire instrumentation rode along (labeled GLOBAL counters)
    assert any(k.startswith("pg_wire_bytes") for k in m2), sorted(m2)[:10]

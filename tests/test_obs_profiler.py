"""The served path's spans in the profiler trace (src/repro/obs/trace.py,
docs/ARCHITECTURE.md §13), and the benchmark's readers of them.

The contracts under test:

* every stage span of a served query also appears in a ``jax.profiler``
  trace as a host event named ``"pg." + <span name>``, over the same
  interval: its duration equals the span's ``ms`` in the wire trace;
* the scheduler's profiler-only events (``pg.sched.idle``,
  ``pg.sched.window``, ``pg.batch``) and the session's ``pg.submit`` are
  there too;
* the reply's wait for the device is ``device.wait``, not ``serialize``:
  a deliberately slow jitted result lands in the one and not the other;
* the per-layer readers of these spans find their numbers in a run's
  record, and return ``None`` (never raise) in a record without them.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch.pgserve import build_tenant_graph, pattern_pool
from repro.obs.trace import PROFILER_PREFIX, Trace, stage
from repro.service import PGClient, PGServer, Service

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402

STAGES = ("parse", "plan", "execute", "device.wait", "serialize")
PROFILER_ONLY = ("sched.idle", "sched.window", "batch", "submit")


@pytest.fixture(scope="module")
def served():
    pg = build_tenant_graph("arr", 600, seed=5)
    svc = Service()
    svc.add_graph("g", pg)
    server = PGServer(svc, port=0).start()
    yield server, svc
    server.close()
    svc.close()


def _pg_events(log_dir):
    """``{name: [(start_ns, duration_ns), ...]}`` of the ``pg.`` events on
    the trace's host planes, each list in start order."""
    from jax.profiler import ProfileData

    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    out = {}
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROFILER_PREFIX):
                    out.setdefault(e.name, []).append((e.start_ns, e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def _root_spans(trace):
    return {s["name"]: s["ms"] for s in trace["spans"]}


def test_span_context_manager_writes_a_profiler_event(tmp_path):
    tr = Trace()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("inner.step"):
            jnp.ones(8).block_until_ready()
        with stage("detached") as st:
            pass
    finally:
        jax.profiler.stop_trace()
    events = _pg_events(tmp_path)
    ms = _root_spans(tr.to_dict())["inner.step"]
    (_, dur), = events["pg.inner.step"]
    assert abs(dur / 1e6 - ms) < 1.0
    (_, dur), = events["pg.detached"]
    assert abs(dur / 1e6 - (st.t1 - st.t0) * 1e3) < 1.0


def test_served_spans_are_profiler_events_of_the_same_duration(served, tmp_path):
    server, _ = served
    patterns = pattern_pool()[:4]
    with PGClient(port=server.port) as c:
        c.query("g", patterns[0])  # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            traces = []
            for p in patterns[1:]:
                c.query("g", p)
                traces.append(_root_spans(c.last_trace))
        finally:
            jax.profiler.stop_trace()
    events = _pg_events(tmp_path)
    for name in STAGES + PROFILER_ONLY:
        assert PROFILER_PREFIX + name in events, sorted(events)
    # one query at a time: the i-th event of a stage is the i-th query's
    for name in STAGES:
        evs = events[PROFILER_PREFIX + name]
        assert len(evs) == len(traces), (name, len(evs))
        for (_, dur), spans in zip(evs, traces):
            assert abs(dur / 1e6 - spans[name]) < 1.0, (name, dur, spans[name])
    # the reply's spans come in causal order inside one pg.batch event
    for i in range(len(traces)):
        t = {n: events[PROFILER_PREFIX + n][i][0] for n in ("execute", "device.wait", "serialize")}
        assert t["execute"] < t["device.wait"] < t["serialize"]


def _slow(mask, x):
    """``mask`` unchanged, after a tenth of a second or more of device
    work that it depends on."""
    y = lax.fori_loop(0, 200, lambda i, y: jnp.tanh(y @ y), x)
    return mask ^ (y[0, 0] > 2.0)


def test_device_wait_takes_the_wait_and_serialize_does_not(served, monkeypatch):
    server, svc = served
    pattern = pattern_pool()[5]
    with PGClient(port=server.port) as c:
        mask = c.query("g", pattern).vertex_mask  # compiles the whole path
    svc.result_cache.purge(lambda k, v: True)  # so it is served again
    slow = jax.jit(_slow)
    x = jnp.full((512, 512), 0.01, jnp.float32)
    slow(jnp.asarray(mask), x).block_until_ready()  # compile
    alone = []
    for _ in range(3):
        with stage("alone") as st:
            slow(jnp.asarray(mask), x).block_until_ready()
        alone.append((st.t1 - st.t0) * 1e3)
    plain = svc._execute_plans

    def slowed(pg, plans, impl):
        return [dataclasses.replace(r, vertex_mask=slow(r.vertex_mask, x))
                for r in plain(pg, plans, impl)]

    monkeypatch.setattr(svc, "_execute_plans", slowed)
    with PGClient(port=server.port) as c:
        c.query("g", pattern)
        spans = _root_spans(c.last_trace)
    assert spans["device.wait"] > 0.5 * min(alone), (spans, alone)
    assert spans["serialize"] < 0.25 * spans["device.wait"], spans
    assert spans["execute"] < 0.25 * spans["device.wait"], spans


# ------------------------------------------------------------ the readers
def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def test_span_readers_take_the_median():
    layer = {"spans": [{"plan": 1.0, "execute": 3.0, "serialize": 5.0},
                       {"plan": 2.0, "execute": 4.0, "serialize": 9.0},
                       {"plan": 6.0, "execute": 8.0, "serialize": 7.0}]}
    assert _reader("plan_ms")(layer) == 2.0
    assert _reader("execute_host_ms")(layer) == 4.0
    assert _reader("wire_serialize_ms")(layer) == 7.0


def test_idle_host_share_subtracts_the_empty_queue_and_no_span():
    layer = {"trace": {"busy_s": 20.0, "window_s": 50.0, "breakdown": {
        "idle_gaps": [["pg.sched.idle", 25.0], ["(no host span)", 1.0],
                      ["pg.serialize", 2.0], ["pg.plan", 1.5]]}}}
    # idle 30 s: 25 waiting for requests, 1 unattributed, 4 host work
    assert _reader("idle_host_share.serve")(layer) == pytest.approx(8.0)


@pytest.mark.parametrize("layer", [
    {},
    {"spans": [{"batch.wait": 1.0}], "trace": {"busy_s": 1.0, "window_s": 2.0}},
    {"trace": {"busy_s": 20.0, "window_s": 50.0, "breakdown": {
        "idle_gaps": [["(no host span)", 29.0], ["PjitFunction(f)", 1.0]]}}},
], ids=["empty", "no-new-spans", "parent-trace"])
def test_readers_find_nothing_without_the_new_spans(layer):
    for name in ("wire_serialize_ms", "execute_host_ms", "plan_ms",
                 "idle_host_share.serve"):
        assert _reader(name)(layer) is None, name

"""The trace reduction and the roofline counts, off the chip."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import peaks, roofline_counts, trace_reduce  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "tpu_small.xplane.pb"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur), stats=stats)


def fake_trace():
    """A window 0..1000 ns (×1e3) with two device ops and host spans."""
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.window", 0, 1_000_000),
                                  ev("plan", 300_000, 250_000),
                                  ev("serialize", 650_000, 300_000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(123)", 90_000, 220_000),
                                       ev("jit_b(456)", 540_000, 120_000),
                                       ev("jit_a(123)", 890_000, 220_000)]),
        NS(name="XLA Ops", events=[ev("%fusion.1 = pred[8]{0} fusion(s32[8] %x)", 100_000, 200_000),
                                   ev("%scatter.2 = pred[8]{0} scatter(...)", 550_000, 100_000),
                                   ev("%fusion.1 = pred[8]{0} fusion(s32[8] %x)", 900_000, 200_000)]),
        NS(name="Steps", events=[ev("step", 0, 2_000_000)])])
    return NS(planes=[host, dev])


def test_reduce_busy_ops_and_gaps():
    r = trace_reduce.reduce(fake_trace())
    # busy: [100,300] + [550,650] + [900,1000 (clipped)] µs = 400 µs of 1000
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["busy_s"] == pytest.approx(400e-6)
    assert r["per_op"]["jit_a:fusion.1"] == pytest.approx(300e-6)
    assert r["per_op"]["jit_b:scatter.2"] == pytest.approx(100e-6)
    assert r["breakdown"]["device_ops"][0] == ["jit_a:fusion.1", pytest.approx(300e-6)]
    # launches that start in the window, the second jit_a clipped at its end
    assert r["per_module"]["jit_a"] == [2, pytest.approx(330e-6)]
    assert r["per_module"]["jit_b"] == [1, pytest.approx(120e-6)]
    idle = dict(r["breakdown"]["idle_gaps"])
    # gaps: [0,100] no span; [300,550] plan; [650,900] serialize
    assert idle == {"(no host span)": pytest.approx(100e-6),
                    "plan": pytest.approx(250e-6),
                    "serialize": pytest.approx(250e-6)}


def test_gap_split_between_host_spans():
    """A gap is charged piece by piece: to the innermost span covering each
    stretch, and to "(no host span)" where none does."""
    host = [(0, 100, "outer"), (20, 40, "inner"), (150, 160, "late")]
    got = trace_reduce.attribute([(10 * 10**6, 10**8)], [(a * 10**6, b * 10**6, n)
                                                          for a, b, n in host])
    assert got == {"outer": pytest.approx(0.07), "inner": pytest.approx(0.02)}
    got = trace_reduce.attribute([(0, 2 * 10**8)], [(a * 10**6, b * 10**6, n)
                                                     for a, b, n in host])
    assert got == {"outer": pytest.approx(0.08), "inner": pytest.approx(0.02),
                   "late": pytest.approx(0.01), "(no host span)": pytest.approx(0.09)}


def test_reduce_without_window_or_device_is_none():
    t = fake_trace()
    t.planes[0].lines[0].events.pop(0)
    assert trace_reduce.reduce(t) is None
    t = fake_trace()
    t.planes.pop()
    assert trace_reduce.reduce(t) is None


def test_merge_and_gaps_cover_the_window():
    busy = trace_reduce.merge([(5, 9), (1, 3), (2, 4), (8, 12)], 0, 10)
    assert busy == [[1, 4], [5, 10]]
    assert trace_reduce.gaps(busy, 0, 10) == [(0, 1), (4, 5)]


def test_recorded_trace():
    """A trace recorded on a TPU v5e: three jitted calls inside the
    window; the device was busy for part of it and the gaps are charged
    to host spans."""
    if not FIXTURE.exists():
        pytest.skip("no recorded TPU trace in bench/tests/data")
    from jax.profiler import ProfileData

    r = trace_reduce.reduce(ProfileData.from_file(str(FIXTURE)))
    assert r is not None
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
    assert sum(v for _, v in r["breakdown"]["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    launches, seconds = zip(*r["per_module"].values())
    assert sum(launches) >= 1 and 0 < sum(seconds) <= r["window_s"]


def test_bitmap_query_counts_at_graph3_widths():
    n, k = 8_646_926, 50
    w = roofline_counts.words(n)
    assert w == 270_217
    assert roofline_counts.bitmap_query_bytes(64, k, w) == 4 * (k * w + 64 * k + 64 * w)
    assert roofline_counts.bitmap_query_ops(64, k, w) == 2 * 64 * k * w
    t, bound = roofline_counts.least_seconds(
        roofline_counts.bitmap_query_bytes(64, k, w),
        roofline_counts.bitmap_query_ops(64, k, w), peaks.peaks("TPU v5 lite"))
    assert bound == "hbm"
    assert t == pytest.approx(4 * (k * w + 64 * k + 64 * w) / 819e9)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_metric_readers_find_nothing_in_an_empty_run():
    from bench import harness

    for path in sorted((harness.BENCH / "metrics").glob("*.py")):
        assert harness.load_module(path).read({}) is None, path.name
    layer = {"spans": [{"batch.wait": 2.0}, {"batch.wait": 4.0, "execute": 1.0}],
             "counters": {"width_sum": 6, "width_count": 4},
             "jobs": [("cdlp", 2.0), ("cdlp", 4.0), ("bfs", 1.0)],
             "trace": {"busy_s": 0.25, "window_s": 1.0,
                       "per_module": {"jit__propagate": [4, 2.0], "jit_other": [1, 9.0]}}}
    read = {p.stem: harness.load_module(p).read(layer)
            for p in (harness.BENCH / "metrics").glob("*.py")}
    assert read["sched_wait_ms"] == 3.0
    assert read["coalesce_width"] == 1.5
    assert read["cdlp_job_ms"] == 3000.0
    assert read["device_idle_share.serve"] == 75.0
    assert np.isclose(read["propagate_device_ms"], 500.0)

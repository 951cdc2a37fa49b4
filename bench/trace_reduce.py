"""From a JAX profiler trace (``.xplane.pb``) to device busy time, device
time per operation and the idle breakdown.

The window is the host span ``bench.window`` the harness wraps the measured
window in.  Device operations are the events on the ``XLA Ops`` line of
each ``/device:TPU:<i>`` plane; each is named by the program it ran in
(the ``XLA Modules`` event that holds it, without its fingerprint, such as
``jit__propagate``) and its HLO instruction (``fusion.3``); ``per_module``
counts each program's launches that start in the window and their device
time (averaged over the devices).  A device is
busy where the union of its
operations' intervals covers the window; ``busy_s`` is averaged over the
devices that ran anything.  Each stretch of an idle gap of the first such
device is charged to the shortest host event (from any host thread) that
covers it: that is what the host was doing meanwhile.  The device's clock
is aligned with the host's to about a millisecond in the traces seen, so
the charge of gaps that short is uncertain.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 100_000  # gaps under 0.1 ms are summed, not attributed
LONG_NS = 10_000_000
TOP = 10


def find_xplane(log_dir: Path) -> Optional[Path]:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _op_name(hlo: str) -> str:
    """``"%fusion.3 = pred[...] fusion(...)"`` → ``"fusion.3"``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def _module_name(name: str) -> str:
    """``"jit__propagate(9744717393335159101)"`` → ``"jit__propagate"``."""
    return name.split("(", 1)[0]


def device_ops(pd):
    """Per TPU plane, its ops as ``(start_ns, end_ns, "module:op", stats)``
    and its program launches as ``(start_ns, end_ns, module)``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, _module_name(e.name))
                      for e in lines.get(MODULES_LINE, []))
        starts = np.array([m[0] for m in mods], np.float64)
        evs = []
        for e in lines.get(OPS_LINE, []):
            i = int(np.searchsorted(starts, e.start_ns, side="right")) - 1
            module = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "(no module)"
            evs.append((e.start_ns, e.start_ns + e.duration_ns,
                        f"{module}:{_op_name(e.name)}", _stats(e)))
        out.append((evs, mods))
    return out


def host_events(pd) -> List[Tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.duration_ns > 0]
    return out


def merge(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Sorted, disjoint union of ``intervals`` clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy: List[List[float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def attribute(gap_list, host: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of idle gap charged to each host event name: every stretch
    of a gap goes to the shortest host event covering it, "(no host span)"
    where none does.  Events up to ``LONG_NS`` long are looked up by start
    time; the few longer ones are checked against every gap."""
    out: Dict[str, float] = defaultdict(float)
    hs = np.array([h[0] for h in host], np.float64)
    he = np.array([h[1] for h in host], np.float64)
    names = np.array([h[2] for h in host], dtype=object)
    long = (he - hs) > LONG_NS
    order = np.argsort(hs[~long], kind="stable")
    short = (hs[~long][order], he[~long][order], names[~long][order])
    for g0, g1 in gap_list:
        if g1 - g0 < MIN_GAP_NS:
            out["(gaps under 0.1 ms)"] += (g1 - g0) * 1e-9
            continue
        i0, i1 = np.searchsorted(short[0], [g0 - LONG_NS, g1])
        start = np.concatenate([short[0][i0:i1], hs[long]])
        end = np.concatenate([short[1][i0:i1], he[long]])
        name = np.concatenate([short[2][i0:i1], names[long]])
        hit = (end > g0) & (start < g1)
        start, end, name = np.maximum(start[hit], g0), np.minimum(end[hit], g1), name[hit]
        cuts = np.unique(np.concatenate([[g0, g1], start, end]))
        span = end - start
        for a, b in zip(cuts[:-1], cuts[1:]):
            cover = np.flatnonzero((start <= a) & (end >= b))
            who = name[cover[np.argmin(span[cover])]] if cover.size else "(no host span)"
            out[who] += (b - a) * 1e-9
    return dict(out)


def reduce(pd, window_span: str = WINDOW_SPAN) -> Optional[dict]:
    """``None`` where the trace holds no window span or no device op."""
    host = host_events(pd)
    wins = [h for h in host if h[2] == window_span]
    if not wins:
        return None
    lo, hi, _ = max(wins, key=lambda h: h[1] - h[0])
    host = [h for h in host if h[2] != window_span]
    devs = [d for d in device_ops(pd) if any(e[1] > lo and e[0] < hi for e in d[0])]
    if not devs:
        return None
    busy_each = []
    per_op: Dict[str, float] = defaultdict(float)
    per_module: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    for evs, mods in devs:
        busy = merge([(e[0], e[1]) for e in evs], lo, hi)
        busy_each.append(sum(b - a for a, b in busy) * 1e-9)
        for a, b, name, _ in evs:
            d = (min(b, hi) - max(a, lo)) * 1e-9
            if d > 0:
                per_op[name] += d / len(devs)
        for a, b, name in mods:
            if lo <= a < hi:
                per_module[name][0] += 1 / len(devs)
                per_module[name][1] += (min(b, hi) - a) * 1e-9 / len(devs)
    first = merge([(e[0], e[1]) for e in devs[0][0]], lo, hi)
    idle = attribute(gaps(first, lo, hi), host)

    def top(d: Dict[str, float]):
        return [[k, float(v)] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": float(np.mean(busy_each)), "window_s": (hi - lo) * 1e-9,
            "per_op": dict(per_op), "per_module": dict(per_module),
            "breakdown": {"device_ops": top(per_op), "idle_gaps": top(idle)}}


def reduce_dir(log_dir: Path) -> Optional[dict]:
    path = find_xplane(log_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)))

"""Discovery, the chip check, the measured window and the result line.

``BENCHMARK.json`` names every cell.  What belongs to one configuration,
traffic mix or per-layer metric sits in a file of its own, found by name:

* configuration ``<c>``  — the JSON file its entry names (``configs/<c>.json``),
  whose ``generator`` key names ``generators/<generator>.py``;
* traffic mix ``<t>``    — ``traffic/<t>.json``, whose ``runner`` key names
  ``runners/<runner>.py``, the general generator of that kind of traffic;
* per-layer metric ``<m>`` — ``metrics/<m>.py``, whose ``read(layer)``
  returns the metric's value or ``None`` where it finds nothing to read.

So a cell, a configuration or a metric is added by adding files and
``BENCHMARK.json`` entries; no file here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
WINDOW_SPAN = "bench.window"


class BenchError(RuntimeError):
    """A cell that cannot be run as ``BENCHMARK.json`` describes it."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything it names, read from files."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: Path

    @classmethod
    def load(cls, name: str, root: Path = ROOT, bench: Optional[Path] = None) -> "Cell":
        bench = root / "bench" if bench is None else bench
        bm = load_json(root / "BENCHMARK.json")
        work = {w["name"]: w for w in bm["workloads"]}
        if name not in work:
            raise BenchError(f"unknown workload {name!r}; known: {sorted(work)}")
        w = work[name]
        configs = {c["name"]: c for c in bm["configs"]}
        config = load_json(root / configs[w["config"]]["file"])
        traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
        e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
        e2e_names = {m["name"] for m in e2e}
        layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
        return cls(name=name, chips=int(w["chips"]), config=config,
                   traffic=traffic, end_to_end=e2e, per_layer=layer, bench=bench)

    def module(self, kind: str, name: str):
        return load_module(self.bench / kind / f"{name}.py")


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` where the environment sets one), every
    program cached so that only a cell's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, *, require_tpu: bool = True) -> Dict[str, Any]:
    """The devices as JAX reports them; exit 2 without a TPU or without
    ``chips`` of them, before anything is measured."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devs[0].platform}")
        sys.exit(2)
    if len(devs) < chips:
        log(f"bench: needs {chips} chips, JAX found {len(devs)}")
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the cell's devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA compilations and persistent-cache reads while active —
    there should be none inside the measured window."""

    def __init__(self):
        import jax.monitoring as mon

        self.active = False
        self.compiles = 0
        self.cache_reads = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if self.active and name.endswith("backend_compile_duration"):
            self.compiles += 1

    def _on_event(self, name: str, **_) -> None:
        if self.active and name.endswith("cache_hits"):
            self.cache_reads += 1


class Window:
    """The measured window: ends set-up, and with ``trace`` records the
    profiler over it, inside a ``bench.window`` annotation the trace
    reduction finds the window by."""

    def __init__(self, trace_dir: Optional[Path], counter: CompileCounter):
        self.trace_dir = trace_dir
        self.counter = counter
        self.t0 = self.t1 = None
        self._ann = None

    def __enter__(self) -> "Window":
        import jax

        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
        self.counter.active = True
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self.t1 = time.perf_counter()
        self.counter.active = False
        if self.trace_dir is not None:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


@dataclasses.dataclass
class RunContext:
    """What a runner gets: the cell, its seed and window, and the hooks
    that end set-up and read the device."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    out_dir: Path
    chips: int
    counter: CompileCounter
    window: Optional[Window] = None

    def open_window(self) -> Window:
        self.window = Window(self.out_dir / "trace" if self.trace else None,
                             self.counter)
        return self.window

    @property
    def setup_s(self) -> float:
        return self.window.t0 - self.t_start

    def memory_peak(self) -> Optional[int]:
        return memory_peak_bytes(self.chips)


@dataclasses.dataclass
class Outcome:
    """A runner's report of one run.

    ``end_to_end`` holds the values of the cell's end-to-end metrics other
    than ``setup_s``; ``layer`` is what the per-layer readers read;
    ``checks`` maps each compared number to ``(value, limit)`` — the run is
    correct when every value is at most its limit."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    layer: Dict[str, Any]
    checks: Dict[str, tuple]
    memory_peak_bytes: Optional[int]


def read_per_layer(cell: Cell, layer: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        value = cell.module("metrics", m["name"]).read(layer)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, device: Dict[str, Any],
             out_dir: Optional[Path] = None) -> Dict[str, Any]:
    """Drive one run of ``cell`` and assemble its result line (a dict)."""
    out_dir = out_dir or ROOT / "artifacts" / "bench" / f"{cell.name}-{seed}-{os.getpid()}"
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, out_dir=out_dir, chips=cell.chips,
                     counter=CompileCounter())
    runner = cell.module("runners", cell.traffic["runner"])
    res: Outcome = runner.run(ctx)
    log(f"bench: window compiled {ctx.counter.compiles} program(s), read "
        f"{ctx.counter.cache_reads} from the cache")
    dev = dict(device)
    dev["memory_peak_bytes"] = res.memory_peak_bytes
    line: Dict[str, Any] = {}
    if trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce_dir(out_dir / "trace")
        layer = dict(res.layer, trace=reduced)
        metrics = read_per_layer(cell, layer)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
    else:
        values = dict(res.end_to_end, setup_s=ctx.setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise BenchError(f"runner reported no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    correct = all(v <= lim for v, lim in res.checks.values())
    result = {"correct": bool(correct), "attempted": int(res.attempted),
              "failed": int(res.failed), "metrics": metrics, "device": dev}
    result.update(line)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in res.checks.items()}
    return result


def emit(result: Dict[str, Any]) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line of standard output."""
    for k, c in result["checks"].items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


def spawn_env() -> Dict[str, str]:
    """Environment for a helper process that must stay off the chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC), str(ROOT)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def timed(fn: Callable[[], Any]):
    """``(fn(), seconds)`` with the result ready on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0

"""EXPLAIN ANALYZE — executed-plan profiling with the compile/execute
split (docs/ARCHITECTURE.md §13).

``PropGraph.explain()`` shows the plan the optimizer CHOSE;
``explain_analyze()`` runs it and reports where the wall time WENT:
per-stage times (parse, plan, mask materialization, propagation) and —
the number JAX makes easy to misread — how much of the first call was
XLA compilation versus device execution.

The split is measured, not inferred: each device stage runs twice under
``jax.block_until_ready``.  The first run pays tracing + compilation iff
the jit cache is cold for this (plan structure, graph shape) signature;
the immediate re-run hits the compiled executable, so

    compile_ms ≈ max(0, first_ms − steady_ms)   per stage.

On a warm cache both runs take ~the same time and compile_ms ≈ 0 — which
is exactly the acceptance probe: profile a fresh pattern shape, then
profile it again, and the report's compile share collapses.  The re-run
costs one extra steady-state execution (µs–ms); that's the price of an
honest number and why this is a profiling verb, not the default path.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax

__all__ = ["ProfileReport", "profile_match"]

_now = time.perf_counter

# below this, first-vs-steady deltas are timer noise, not compilation
_COMPILE_NOISE_MS = 0.5


@dataclass
class ProfileReport:
    """Executed-plan annotation returned by ``explain_analyze()`` /
    ``match(..., profile=True)``.  All times in milliseconds; ``*_first``
    is the as-observed first call, the unsuffixed device-stage fields are
    the steady-state re-run."""

    plan: Any
    parse_ms: float
    plan_ms: float
    masks_first_ms: float
    masks_ms: float
    execute_first_ms: float
    execute_ms: float
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def compile_ms(self) -> float:
        """Estimated XLA tracing+compilation share of the first call."""
        c = (max(0.0, self.masks_first_ms - self.masks_ms)
             + max(0.0, self.execute_first_ms - self.execute_ms))
        return c if c >= _COMPILE_NOISE_MS else 0.0

    @property
    def cold(self) -> bool:
        """True iff the first call visibly paid compilation."""
        return self.compile_ms > 0.0

    @property
    def total_first_ms(self) -> float:
        return (self.parse_ms + self.plan_ms
                + self.masks_first_ms + self.execute_first_ms)

    @property
    def steady_ms(self) -> float:
        return self.parse_ms + self.plan_ms + self.masks_ms + self.execute_ms

    def to_dict(self) -> Dict[str, Any]:
        return {
            "parse_ms": round(self.parse_ms, 4),
            "plan_ms": round(self.plan_ms, 4),
            "masks_first_ms": round(self.masks_first_ms, 4),
            "masks_ms": round(self.masks_ms, 4),
            "execute_first_ms": round(self.execute_first_ms, 4),
            "execute_ms": round(self.execute_ms, 4),
            "compile_ms": round(self.compile_ms, 4),
            "total_first_ms": round(self.total_first_ms, 4),
            "steady_ms": round(self.steady_ms, 4),
            "cold": self.cold,
            **self.attrs,
        }

    def describe(self) -> str:
        """``Plan.describe()`` plus the measured timing annotation."""
        n_steps = len(self.plan.mask_steps)
        n_fused = len(self.plan.fused_node_slots)
        lines = [self.plan.describe(), "-- analyze --"]
        lines.append(f"  parse                {self.parse_ms:9.3f} ms")
        lines.append(f"  plan                 {self.plan_ms:9.3f} ms")
        lines.append(
            f"  {'masks (%d steps, %d fused)' % (n_steps, n_fused):<21}"
            f" first {self.masks_first_ms:9.3f} ms"
            f" / steady {self.masks_ms:9.3f} ms")
        lines.append(
            f"  propagate            first {self.execute_first_ms:9.3f} ms"
            f" / steady {self.execute_ms:9.3f} ms")
        if self.cold:
            lines.append(
                f"  compile (first call) {self.compile_ms:9.3f} ms"
                "  <- XLA tracing+compilation, absent on warm cache")
        else:
            lines.append("  compile (first call)     ~0       ms  (jit cache warm)")
        lines.append(
            f"  total                first {self.total_first_ms:9.3f} ms"
            f" / steady {self.steady_ms:9.3f} ms")
        return "\n".join(lines)


def profile_match(pg, pattern, *, impl: Optional[str] = None):
    """Run ``pattern`` against ``pg`` with per-stage timing; returns
    ``(MatchResult, ProfileReport)``.  Implements
    ``PropGraph.match(..., profile=True)`` and ``explain_analyze()``."""
    from repro.query import parse, plan_pattern
    from repro.query.executor import _materialize_masks, execute_plan_with_masks

    t0 = _now()
    pat = parse(pattern) if isinstance(pattern, str) else pattern
    t1 = _now()
    plan = plan_pattern(pg, pat, impl=impl)
    t2 = _now()

    pg._require_graph()
    label_masks, rel_masks = _materialize_masks(pg, plan)
    jax.block_until_ready((label_masks, rel_masks))
    t3 = _now()
    label_masks, rel_masks = _materialize_masks(pg, plan)
    jax.block_until_ready((label_masks, rel_masks))
    t4 = _now()

    result = execute_plan_with_masks(pg, plan, label_masks, rel_masks)
    jax.block_until_ready(result)
    t5 = _now()
    result = execute_plan_with_masks(pg, plan, label_masks, rel_masks)
    jax.block_until_ready(result)
    t6 = _now()

    report = ProfileReport(
        plan=plan,
        parse_ms=(t1 - t0) * 1e3,
        plan_ms=(t2 - t1) * 1e3,
        masks_first_ms=(t3 - t2) * 1e3,
        masks_ms=(t4 - t3) * 1e3,
        execute_first_ms=(t5 - t4) * 1e3,
        execute_ms=(t6 - t5) * 1e3,
        attrs={"backend": plan.backend,
               "mask_steps": len(plan.mask_steps),
               "fused_slots": len(plan.fused_node_slots),
               "traversal": plan.has_traversal},
    )
    return result, report

"""Median of the ``plan`` span over the window's replies, in ms: the
planner's time for a request's group on the scheduler's thread."""
import statistics


def read(layer):
    vals = [s["plan"] for s in layer.get("spans", []) if "plan" in s]
    return statistics.median(vals) if vals else None

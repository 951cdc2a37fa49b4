"""Median of the ``batch.wait`` span (enqueue to the scheduler's batch start), over the window's replies, in ms."""
import statistics


def read(layer):
    vals = [s["batch.wait"] for s in layer.get("spans", []) if "batch.wait" in s]
    return statistics.median(vals) if vals else None

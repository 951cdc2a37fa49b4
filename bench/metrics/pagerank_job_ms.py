"""Median wall time of the window's ``pagerank`` jobs, from the benchmark's
own timing around each call (result ready on the device), in ms."""
import statistics


def read(layer):
    vals = [dt for kind, dt in layer.get("jobs", []) if kind == "pagerank"]
    return 1e3 * statistics.median(vals) if vals else None

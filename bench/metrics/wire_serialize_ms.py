"""Median of the ``serialize`` span over the window's replies, in ms: the
reply's encoding alone (device pack, device-to-host copy and framing),
after ``device.wait`` has waited for the result's masks."""
import statistics


def read(layer):
    vals = [s["serialize"] for s in layer.get("spans", []) if "serialize" in s]
    return statistics.median(vals) if vals else None

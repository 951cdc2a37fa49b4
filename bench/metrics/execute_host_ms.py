"""Median of the ``execute`` span over the window's replies, in ms: the
executor's host time, from the end of planning until the group's last mask
and propagation launch is dispatched (the device's time is not in it)."""
import statistics


def read(layer):
    vals = [s["execute"] for s in layer.get("spans", []) if "execute" in s]
    return statistics.median(vals) if vals else None

"""Share of the traced window in which no operation ran on the device,
in %: 1 − (union of device op intervals) / window, averaged over the
cell's chips (``bench/trace_reduce.py``)."""


def read(layer):
    t = layer.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None

"""Mean requests fused per coalesced launch over the window
(``pg_sched_coalesce_width``: its sum over its count)."""


def read(layer):
    c = layer.get("counters", {})
    return c["width_sum"] / c["width_count"] if c.get("width_count") else None

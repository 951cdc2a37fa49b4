"""Share of the traced window in which the device idled while the host
worked on the served path, in %: the device's idle time less what the
trace reduction charges to ``pg.sched.idle`` (the scheduler waiting on an
empty queue) and to ``(no host span)`` (no program event open), over the
window (``bench/trace_reduce.py``).  Those two are the largest idle
charges, so the reduction's top entries hold them.  ``None`` where the
program writes no ``pg.sched.idle`` event."""
QUEUE_EMPTY = "pg.sched.idle"
NO_SPAN = "(no host span)"


def read(layer):
    t = layer.get("trace")
    if not t:
        return None
    idle = dict(t.get("breakdown", {}).get("idle_gaps", []))
    if QUEUE_EMPTY not in idle:
        return None
    host = t["window_s"] - t["busy_s"] - idle[QUEUE_EMPTY] - idle.get(NO_SPAN, 0.0)
    return 100.0 * host / t["window_s"]

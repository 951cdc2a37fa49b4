"""Mean device time of one ``jit__propagate`` launch in the traced window,
in ms: the executor's propagation of a request's masks over the edges
(one launch per request answered), from the profiler trace's program
launches (``bench/trace_reduce.py``)."""
PROGRAM = "jit__propagate"


def read(layer):
    launches, seconds = (layer.get("trace") or {}).get("per_module", {}).get(PROGRAM, (0, 0.0))
    return 1e3 * seconds / launches if launches else None

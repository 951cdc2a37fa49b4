"""Open-loop load generator: a process of its own, off the chip.

Started by ``serve_open.py`` with ``JAX_PLATFORMS=cpu`` (importing
``repro.service`` imports JAX, and the chip belongs to the server's
process).  It reads one JSON command per line on standard input and answers
each with one JSON line on standard output:

* ``{"cmd": "connect", "port": p, "clients": c}`` — open ``c`` ``PGClient``
  connections;
* ``{"cmd": "warm", "graph": g, "patterns": [...]}`` — each pattern once,
  one after another;
* ``{"cmd": "run", "graph": g, "schedule": [[due_s, pattern], ...],
  "keep": [i, ...], "out": path, "wait_s": w}`` — send request ``i`` at
  ``due_s`` after the start whatever came back before (open loop), each on
  the next free connection; wait for answers until ``w`` seconds after the
  last due time; write timings, span trees and the packed masks of the
  ``keep`` replies to ``out`` (``.json`` + ``.npz``);
* ``{"cmd": "close"}`` and ``{"cmd": "exit"}``.

Latency is timed from each request's due time, so a request that waits for
a free connection or a stalled server is charged for the wait.  How late
the dispatcher itself ran is reported beside it.
"""
from __future__ import annotations

import json
import queue
import sys
import threading
import time

import numpy as np

STAGES = ("parse", "cache", "batch.wait", "plan", "execute", "serialize")


def _stage_ms(trace):
    """{stage: ms} from a server span tree (root children)."""
    out = {}
    for sp in (trace or {}).get("spans", []):
        if sp.get("name") in STAGES:
            out[sp["name"]] = out.get(sp["name"], 0.0) + float(sp.get("ms", 0.0))
    return out


class Loadgen:
    def __init__(self):
        self.port = None
        self.clients = []

    def _client(self):
        from repro.service import PGClient

        return PGClient(port=self.port, timeout=600.0)

    def connect(self, port, clients):
        self.close()
        self.port = port
        self.clients = [self._client() for _ in range(int(clients))]
        return {}

    def close(self):
        for c in self.clients:
            c.close()
        self.clients = []
        return {}

    def warm(self, graph, patterns):
        t0 = time.perf_counter()
        for p in patterns:
            self.clients[0].query(graph, p)
        return {"seconds": time.perf_counter() - t0}

    def run(self, graph, schedule, keep, out, wait_s):
        n = len(schedule)
        keep = set(int(i) for i in keep)
        due = np.array([float(d) for d, _ in schedule])
        done = np.full(n, np.nan)
        late = np.full(n, np.nan)
        ok = np.zeros(n, bool)
        errors, spans, masks = {}, [None] * n, {}
        work: "queue.Queue" = queue.Queue()
        lock = threading.Lock()
        # Set when this run stops waiting: a worker still blocked in a reply
        # then drops out on its own, touching no later run's state.
        abandoned = threading.Event()

        def worker(slot):
            c = self.clients[slot]
            while True:
                i = work.get()
                if i is None:
                    return
                try:
                    res = c.query(graph, schedule[i][1])
                except Exception as e:  # noqa: BLE001 — counted as failed
                    if abandoned.is_set():
                        return
                    done[i] = time.perf_counter()
                    errors[i] = f"{type(e).__name__}: {e}"
                    c.close()
                    try:
                        c = self._client()
                    except OSError as e2:
                        errors[i] += f"; reconnect: {type(e2).__name__}: {e2}"
                        return  # this connection's later requests never run
                    with lock:
                        if not abandoned.is_set():
                            self.clients[slot] = c
                    continue
                done[i] = time.perf_counter()
                ok[i] = True
                spans[i] = _stage_ms(c.last_trace)
                if i in keep:
                    got = {"vertex": res.vertex_mask, "edge": res.edge_mask}
                    got.update({f"bind_{k}": v for k, v in res.bindings().items()})
                    with lock:
                        for k, v in got.items():
                            v = np.asarray(v, bool)
                            masks[f"{i}:{k}"] = np.packbits(v)
                            masks[f"{i}:{k}:n"] = np.array(v.size)

        threads = [threading.Thread(target=worker, args=(s,), daemon=True)
                   for s in range(len(self.clients))]
        for t in threads:
            t.start()
        t0 = time.perf_counter() + 0.05
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - (t0 + due[i])
            work.put(i)
        for _ in threads:
            work.put(None)
        deadline = t0 + (due[-1] if n else 0.0) + float(wait_s)
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        t_end = time.perf_counter()
        if any(t.is_alive() for t in threads):
            abandoned.set()
        lat = done - (t0 + due)
        info = {
            "latency_s": [None if np.isnan(x) else float(x) for x in lat],
            "ok": ok.tolist(),
            "errors": {str(k): v for k, v in errors.items()},
            "dispatch_late_s": late.tolist(),
            "spans": spans,
            "elapsed_s": t_end - t0,
        }
        with open(out + ".json", "w") as f:
            json.dump(info, f)
        np.savez(out + ".npz", **masks)
        return {"out": out}


def main() -> None:
    lg = Loadgen()
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd.pop("cmd")
        if op == "exit":
            lg.close()
            break
        try:
            reply = getattr(lg, op)(**cmd)
            reply["ok"] = True
        except Exception as e:  # noqa: BLE001 — reported to the server side
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(reply), flush=True)


if __name__ == "__main__":
    main()

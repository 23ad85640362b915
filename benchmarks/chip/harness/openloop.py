"""Open-loop load: a fixed schedule, latency timed from each due time.

The schedule and percentile arithmetic are copied from the program's
``service/loadgen.py`` (commit 4c81949), so the yardstick stays put when the
program changes: slot ``k`` is due at ``t0 + k / rate``, shared across
connections; a request's latency runs from its due time, so a stall is
charged to every request queued behind it; sheds and errors are counted
apart.  Added here: how late the generator itself sent each request.
"""
from __future__ import annotations

import threading
import time


def percentile(vals: list, q: float) -> float:
    """Nearest-rank percentile (``loadgen._percentile``)."""
    if not vals:
        return 0.0
    vals = sorted(vals)
    k = min(len(vals) - 1, max(0, int(round(q * (len(vals) - 1)))))
    return vals[k]


def run(clients, n_due: int, rate: float, send, *,
        timeout_s: float = 30.0) -> dict:
    """Send slots ``0..n_due-1`` at ``rate`` per second, one thread per
    client; ``send(slot, client) -> (ok, shed)``.  Returns the latencies of
    the answered requests, shed and error counts, the generator's
    lateness (ms) and the wall time until the last answer."""
    lock = threading.Lock()
    next_slot = [0]
    t0 = [0.0]
    barrier = threading.Barrier(len(clients) + 1)
    per = [{"lats": [], "late": [], "shed": 0, "errors": 0}
           for _ in clients]

    def worker(client, res):
        barrier.wait()
        while True:
            with lock:
                slot = next_slot[0]
                next_slot[0] += 1
            if slot >= n_due:
                return
            due = t0[0] + slot / rate
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            res["late"].append(time.perf_counter() - due)
            try:
                ok, shed = send(slot, client)
            except Exception:  # noqa: BLE001 - a transport failure
                res["errors"] += 1
                continue
            if ok:
                res["lats"].append(time.perf_counter() - due)
            elif shed:
                res["shed"] += 1
            else:
                res["errors"] += 1

    threads = [threading.Thread(target=worker, args=(c, r), daemon=True)
               for c, r in zip(clients, per)]
    for t in threads:
        t.start()
    t0[0] = time.perf_counter()   # published before the barrier opens
    barrier.wait()
    for t in threads:
        t.join(timeout=n_due / rate + 2 * timeout_s)
    wall = time.perf_counter() - t0[0]
    late_ms = sorted(1e3 * v for r in per for v in r["late"])
    return {"latencies_s": [v for r in per for v in r["lats"]],
            "shed": sum(r["shed"] for r in per),
            "errors": sum(r["errors"] for r in per) + sum(
                1 for t in threads if t.is_alive()),
            "lateness_ms": {"p50": percentile(late_ms, 0.5),
                            "p99": percentile(late_ms, 0.99),
                            "max": late_ms[-1] if late_ms else 0.0},
            "wall_s": wall}

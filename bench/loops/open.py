"""Open loop: txns fall due at seeded arrival times, whatever the system
does.  Each ``run_batch`` call admits every txn that is due, up to
``batch_txns``; while none is due the client sleeps until the next.  A
txn's latency runs from the moment it was due to the return of its
batch, so a stall is charged to every txn that waited behind it.  Every
txn due in the window is served; the window ends when the last is."""
from __future__ import annotations

import time

import numpy as np


def run(server, start, traffic, seconds, arrivals):
    cap = traffic["batch_txns"]
    n = len(arrivals)
    done = np.empty(n)
    j = 0
    t0 = time.perf_counter()
    while j < n:
        now = time.perf_counter() - t0
        due = int(np.searchsorted(arrivals, now, side="right"))
        if due <= j:
            time.sleep(arrivals[j] - now)
            continue
        hi = min(due, j + cap)
        done[j:hi] = server.serve(start + j, start + hi) - t0
        j = hi
    return dict(t0=t0, t1=time.perf_counter(), lo=start, hi=start + n,
                latency_s=done - arrivals)

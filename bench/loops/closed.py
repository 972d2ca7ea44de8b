"""Closed loop: one client admits a batch of ``batch_txns`` txns, waits for
every result, and admits the next.  The window ends at the first batch
boundary after ``seconds``."""
from __future__ import annotations

import time


def run(server, start, traffic, seconds, arrivals=None):
    batch = traffic["batch_txns"]
    i = start
    t0 = time.perf_counter()
    end = t0 + seconds
    t = t0
    while t < end:
        t = server.serve(i, i + batch)
        i += batch
    return dict(t0=t0, t1=t, lo=start, hi=i)

"""Seeded arrival times of an open-loop traffic mix, offsets in seconds
from the window's start.  The processes are those of
``repro.obs.load.poisson_arrivals`` and ``bursty_arrivals``."""
from __future__ import annotations

import numpy as np


def poisson(rng, rate, seconds):
    """Poisson arrivals at ``rate``/s in [0, seconds)."""
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 100)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    if t[-1] < seconds:
        raise ValueError("too few arrivals drawn")
    return t[t < seconds]


def bursty(rng, rate, seconds, burst=16, cv=4.0):
    """Bursts of geometric size (mean ``burst``) at mean ``rate``/s; gaps
    within a burst are ``cv`` times shorter."""
    out, t = [], 0.0
    while True:
        s = int(rng.geometric(1.0 / burst))
        lead = max(1e-12, s / rate - (s - 1) / (rate * cv))
        gaps = np.concatenate([[rng.exponential(lead)],
                               rng.exponential(1.0 / (rate * cv), size=s - 1)])
        times = t + np.cumsum(gaps)
        out.append(times)
        t = times[-1]
        if t >= seconds:
            t = np.concatenate(out)
            return t[t < seconds]


def generate(rng, traffic, seconds):
    """The arrivals a traffic file asks for."""
    kw = dict(traffic.get("arrival_params", {}))
    fn = {"poisson": poisson, "bursty": bursty}[traffic["arrivals"]]
    return fn(rng, traffic["rate_txn_per_s"], seconds, **kw)

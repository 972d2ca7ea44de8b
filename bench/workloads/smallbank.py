"""SmallBank (Alomari et al., ICDE 2008) with Payment, as P4DB section 7.2
runs it, vectorised.

Account a of node n has checking key ``key_of(n, 2a)`` and savings key
``key_of(n, 2a + 1)``.  A hot txn uses hot accounts (a < hot_per_node), a
cold txn cold ones; each account is on a random node with probability
``dist_frac``, else on the txn's home node.  Types and ops are those of
``repro.workloads.smallbank.generate``; ``tests/test_bench_traffic.py``
compares the two.
"""
from __future__ import annotations

import numpy as np

from ops import ADD, ADDP, CADD, NOP, READ, WRITE, key_of

KINDS = ("balance", "deposit", "transact", "amalgamate", "writecheck",
         "payment")
K = 3                      # the most ops a SmallBank txn has


def hot_keys(p):
    return [key_of(n, 2 * a + s) for n in range(p["n_nodes"])
            for a in range(p["hot_per_node"]) for s in (0, 1)]


def _accounts(rng, p, home, hot):
    n = len(home)
    remote = rng.random(n) < p["dist_frac"]
    node = np.where(remote, rng.integers(p["n_nodes"], size=n), home)
    a = np.where(hot, rng.integers(p["hot_per_node"], size=n),
                 rng.integers(p["hot_per_node"], p["accounts_per_node"],
                              size=n))
    return node, a


def generate(rng, n, p):
    """``n`` txns as arrays: op, key, val [n, 3] (NOP-padded); n_ops,
    home, kind [n]."""
    home = rng.integers(p["n_nodes"], size=n)
    hot = rng.random(n) < p["p_hot_txn"]
    kind = rng.choice(len(KINDS), size=n, p=[p["mix"][k] for k in KINDS])
    n1, a1 = _accounts(rng, p, home, hot)
    n2, a2 = _accounts(rng, p, home, hot)
    amt = rng.integers(1, 100, size=n)
    credit = rng.random(n) < 0.8
    # the second account of amalgamate and payment is never the first
    same = (n2 == n1) & (a2 == a1)
    span = np.maximum(np.where(hot, p["hot_per_node"],
                               p["accounts_per_node"]), 2)
    a2 = np.where(same, (a2 + 1) % span, a2)
    chk1, sav1 = key_of(n1, 2 * a1), key_of(n1, 2 * a1 + 1)
    chk2 = key_of(n2, 2 * a2)
    op = np.full((n, K), NOP, np.int64)
    key = np.zeros((n, K), np.int64)
    val = np.zeros((n, K), np.int64)
    n_ops = np.zeros(n, np.int64)

    def put(mask, ops):
        n_ops[mask] = len(ops)
        for j, (o, k, v) in enumerate(ops):
            op[mask, j] = o
            key[mask, j] = k[mask]
            val[mask, j] = v[mask] if isinstance(v, np.ndarray) else v

    put(kind == 0, [(READ, chk1, 0), (READ, sav1, 0)])
    put(kind == 1, [(ADD, chk1, amt)])
    put(kind == 2, [(CADD, sav1, np.where(credit, amt, -amt))])
    # read savings(a1), zero it, move it into checking(a2)
    put(kind == 3, [(READ, sav1, 0), (WRITE, sav1, 0), (ADDP, chk2, 0)])
    put(kind == 4, [(READ, sav1, 0), (CADD, chk1, -amt)])
    put(kind == 5, [(CADD, chk1, -amt), (ADD, chk2, amt)])
    return dict(op=op, key=key, val=val, n_ops=n_ops,
                home=home.astype(np.int64), kind=kind.astype(np.int64),
                hot=hot)


def initial_values(rng, touched, p):
    """The keys that are loaded before the run and their values: every hot
    key and every cold key the traffic touches, each balance drawn
    uniformly from [min_balance, max_balance] (the OLTP-Bench SmallBank
    loader's rule), one distribution for hot and cold accounts."""
    keys = np.union1d(np.asarray(hot_keys(p), np.int64),
                      np.asarray(touched, np.int64))
    vals = rng.integers(p["min_balance"], p["max_balance"] + 1,
                        size=len(keys))
    return keys, vals.astype(np.int64)

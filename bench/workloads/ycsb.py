"""YCSB (Cooper et al., SoCC 2010) as P4DB section 7.2 runs it, vectorised.

One table partitioned round-robin over the nodes; a txn is a group of
``ops_per_txn`` read/write ops.  A hot txn draws op j from hot-key class
j mod ``ops_per_txn`` of its node; a cold txn draws uniformly from the
node's cold keys; each op is remote with probability ``dist_frac``.  The
distributions are those of ``repro.workloads.ycsb.generate``, drawn in
bulk; ``tests/test_bench_traffic.py`` compares the two.
"""
from __future__ import annotations

import numpy as np

from ops import READ, WRITE, key_of

KINDS = ("ycsb",)


def hot_keys(p):
    return [key_of(n, i) for n in range(p["n_nodes"])
            for i in range(p["hot_per_node"])]


def generate(rng, n, p):
    """``n`` txns as arrays: op, key, val [n, K]; n_ops, home, kind [n]."""
    N, K = p["n_nodes"], p["ops_per_txn"]
    H = p["hot_per_node"]
    home = rng.integers(N, size=n)
    hot = rng.random(n) < p["p_hot_txn"]
    remote = rng.random((n, K)) < p["dist_frac"]
    node = np.where(remote, rng.integers(N, size=(n, K)), home[:, None])
    # hot op j: a member of class j, i.e. local = j + K * u, u uniform
    # over the class's ceil((H - j) / K) members
    cls = np.arange(K) % K
    members = (H - cls + K - 1) // K
    hot_local = cls + K * np.floor(rng.random((n, K)) * members).astype(
        np.int64)
    cold_local = rng.integers(H, p["keys_per_node"], size=(n, K))
    key = key_of(node, np.where(hot[:, None], hot_local, cold_local))
    write = rng.random((n, K)) < p["write_frac"]
    op = np.where(write, WRITE, READ)
    val = np.where(write, rng.integers(0, 1000, size=(n, K)), 0)
    return dict(op=op.astype(np.int64), key=key.astype(np.int64),
                val=val.astype(np.int64), n_ops=np.full(n, K, np.int64),
                home=home.astype(np.int64), kind=np.zeros(n, np.int64),
                hot=hot)


def initial_values(rng, touched, p):
    """The keys that are loaded before the run and their values: every hot
    key, with a seeded value in [0, 1000).  Cold keys keep the store's
    default of 0, so ``touched`` (the cold keys the traffic uses) needs
    no load."""
    keys = np.asarray(hot_keys(p), np.int64)
    return keys, rng.integers(0, 1000, size=len(keys)).astype(np.int64)

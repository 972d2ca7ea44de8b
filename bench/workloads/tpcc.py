"""TPC-C NewOrder + Payment (TPC-C standard specification revision 5.11,
clauses 2.4 and 2.5) as P4DB section 7 runs them, vectorised.

Warehouse ``w`` and every key of its rows live on node ``w % n_nodes``;
ITEM is replicated, and an item read goes to the home node's copy.  Per
warehouse W_TAX and W_YTD, per district D_TAX, D_YTD and D_NEXT_O_ID are
hot; customer, stock, item and inserted rows are cold, so every txn is
warm.  Ids are 0-based; txn ``i`` of a stream inserts order ``i`` (or
history row ``i``).  Key layout, profiles and initial values are those of
``repro.workloads.tpcc``; ``tests/test_bench_tpcc.py`` compares the two.
"""
from __future__ import annotations

import numpy as np

from ops import ADD, NOP, READ, WRITE, key_of

KINDS = ("neworder", "payment")
N_DISTRICTS = 10
MAX_LINES = 15
K = 6 + 3 * MAX_LINES          # the most ops a NewOrder has
ORDER_LINES = 16               # key slots per order in ORDER_LINE

W_YTD, D_NEXT_O_ID, D_YTD = 10_000_000, 20_000_000, 30_000_000
S_QUANTITY, C_BALANCE = 40_000_000, 50_000_000
W_TAX, D_TAX, C_DISCOUNT = 70_000_000, 80_000_000, 90_000_000
C_YTD_PAYMENT, C_PAYMENT_CNT, I_PRICE = (100_000_000, 110_000_000,
                                         120_000_000)
ORDER, NEW_ORDER, HISTORY, ORDER_LINE = (200_000_000, 300_000_000,
                                         400_000_000, 500_000_000)

# clause 4.3.3.1 as integers: (table offset, low, high) of U[low, high];
# inserted rows are not populated
INITIAL = ((W_YTD, 300_000, 300_000), (D_NEXT_O_ID, 3001, 3001),
           (D_YTD, 30_000, 30_000), (S_QUANTITY, 10, 100),
           (C_BALANCE, -10, -10), (W_TAX, 0, 2000), (D_TAX, 0, 2000),
           (C_DISCOUNT, 0, 5000), (C_YTD_PAYMENT, 10, 10),
           (C_PAYMENT_CNT, 1, 1), (I_PRICE, 1, 100))


def hot_keys(p):
    ks = []
    for w in range(p["n_warehouses"]):
        node = w % p["n_nodes"]
        ks += [key_of(node, W_TAX + w), key_of(node, W_YTD + w)]
        for d in range(N_DISTRICTS):
            wd = w * N_DISTRICTS + d
            ks += [key_of(node, D_TAX + wd), key_of(node, D_YTD + wd),
                   key_of(node, D_NEXT_O_ID + wd)]
    return ks


def nurand(rng, a, x, y, c, size):
    """NURand(A, x, y) of clause 2.1.6 with run-time constant ``c``."""
    return ((rng.integers(0, a + 1, size=size)
             | rng.integers(x, y + 1, size=size)) + c) % (y - x + 1) + x


def _other(rng, W, w):
    """A warehouse other than ``w``, uniformly (``w`` itself if W is 1)."""
    return (w + 1 + rng.integers(max(W - 1, 1), size=w.shape)) % W


def generate(rng, n, p):
    """``n`` txns as arrays: op, key, val [n, 51] (NOP-padded); n_ops,
    home, kind [n]."""
    W, N = p["n_warehouses"], p["n_nodes"]
    n_items, n_cust = p["n_items"], p["n_customers"]
    c_cust, c_item = int(rng.integers(0, 1024)), int(rng.integers(0, 8192))
    kind = (rng.random(n) >= p["mix"]["neworder"]).astype(np.int64)
    w = rng.integers(W, size=n)
    node = w % N
    d = rng.integers(N_DISTRICTS, size=n)
    c = nurand(rng, 1023, 1, n_cust, c_cust, n) - 1
    idx = np.arange(n, dtype=np.int64)

    # NewOrder: ol_cnt lines of distinct items
    ol_cnt = rng.integers(p["ol_cnt_min"], p["ol_cnt_max"] + 1, size=n)
    items = nurand(rng, 8191, 1, n_items, c_item, (n, MAX_LINES)) - 1
    for j in range(1, MAX_LINES):
        dup = (items[:, :j] == items[:, j:j + 1]).any(axis=1)
        while dup.any():
            items[dup, j] = nurand(rng, 8191, 1, n_items, c_item,
                                   int(dup.sum())) - 1
            dup = (items[:, :j] == items[:, j:j + 1]).any(axis=1)
    remote_line = (rng.random((n, MAX_LINES)) < p["remote_line_frac"]) \
        & (W > 1)
    sw = np.where(remote_line,
                  _other(rng, W, np.broadcast_to(w[:, None],
                                                 (n, MAX_LINES))),
                  w[:, None])
    qty = rng.integers(1, 11, size=(n, MAX_LINES))

    # Payment: the customer is remote for a remote_payment_frac share
    remote_c = (rng.random(n) < p["remote_payment_frac"]) & (W > 1)
    cw = np.where(remote_c, _other(rng, W, w), w)
    cd = np.where(remote_c, rng.integers(N_DISTRICTS, size=n), d)
    h = rng.integers(1, p["max_amount"] + 1, size=n)

    op = np.full((n, K), NOP, np.int64)
    key = np.zeros((n, K), np.int64)
    val = np.zeros((n, K), np.int64)
    wd = w * N_DISTRICTS + d

    def put(mask, j, o, k, v):
        op[mask, j], key[mask, j], val[mask, j] = o, k[mask], \
            v[mask] if isinstance(v, np.ndarray) else v

    no, pay = kind == 0, kind == 1
    cust = (wd * 3000) + c
    put(no, 0, READ, key_of(node, W_TAX + w), 0)
    put(no, 1, READ, key_of(node, D_TAX + wd), 0)
    put(no, 2, ADD, key_of(node, D_NEXT_O_ID + wd), 1)
    put(no, 3, READ, key_of(node, C_DISCOUNT + cust), 0)
    put(no, 4, WRITE, key_of(node, ORDER + idx), c)
    put(no, 5, WRITE, key_of(node, NEW_ORDER + idx), idx)
    for line in range(MAX_LINES):
        m = no & (line < ol_cnt)
        j = 6 + 3 * line
        put(m, j, READ, key_of(node, I_PRICE + items[:, line]), 0)
        put(m, j + 1, ADD,
            key_of(sw[:, line] % N, S_QUANTITY + sw[:, line] * 100_000
                   + items[:, line]), -qty[:, line])
        put(m, j + 2, WRITE,
            key_of(node, ORDER_LINE + idx * ORDER_LINES + line),
            qty[:, line])
    pcust = (cw * N_DISTRICTS + cd) * 3000 + c
    cnode = cw % N
    put(pay, 0, ADD, key_of(node, W_YTD + w), h)
    put(pay, 1, ADD, key_of(node, D_YTD + wd), h)
    put(pay, 2, ADD, key_of(cnode, C_BALANCE + pcust), -h)
    put(pay, 3, ADD, key_of(cnode, C_YTD_PAYMENT + pcust), h)
    put(pay, 4, ADD, key_of(cnode, C_PAYMENT_CNT + pcust), 1)
    put(pay, 5, WRITE, key_of(node, HISTORY + idx), h)
    n_ops = np.where(no, 6 + 3 * ol_cnt, 6)
    return dict(op=op, key=key, val=val, n_ops=n_ops.astype(np.int64),
                home=node.astype(np.int64), kind=kind)


def initial_values(rng, touched, p):
    """The keys that are loaded before the run and their values: every hot
    key and every touched row of a populated table, as clause 4.3.3.1
    draws them; inserted rows start absent.  I_PRICE is drawn once per
    item, so every node's replica of ITEM agrees."""
    keys = np.union1d(np.asarray(hot_keys(p), np.int64),
                      np.asarray(touched, np.int64))
    local = keys % 1_000_000_000
    vals = np.zeros(len(keys), np.int64)
    loaded = np.zeros(len(keys), bool)
    for base, lo, hi in INITIAL:
        m = (local >= base) & (local < base + 10_000_000)
        if base == I_PRICE:
            items, at = np.unique(local[m], return_inverse=True)
            vals[m] = rng.integers(lo, hi + 1, size=len(items))[at]
        else:
            vals[m] = rng.integers(lo, hi + 1, size=int(m.sum()))
        loaded |= m
    return keys[loaded], vals[loaded]

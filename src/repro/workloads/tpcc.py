"""TPC-C NewOrder + Payment (TPC-C standard specification revision 5.11,
clauses 2.4 and 2.5) as P4DB (Jasny et al., SIGMOD 2022, section 7) runs
them: the contended warehouse and district columns are offloaded to the
switch, customer, stock, item and inserted rows stay cold on the nodes,
so every txn of the mix is *warm* (paper section 6.2).

One int32 per key.  Warehouse ``w`` (0-based) lives on node
``w % n_nodes``, and so does every key of its rows.  ITEM is read-only
and replicated: an item read goes to the txn's home node's copy.  Ids are
0-based (district 0..9, customer 0..2999, item 0..99999).

Profiles (``generate``; NURand per clause 2.1.6, its ``C`` constants
drawn from the seed):

  NewOrder  d ~ U[0,9], c ~ NURand(1023,1,3000), ol_cnt ~ U[5,15], items
            ~ NURand(8191,1,100000), distinct within the order; a line's
            supply warehouse is the home one except for a
            ``remote_line_frac`` share; quantity ~ U[1,10].  Ops:
            READ w_tax, READ d_tax, ADD d_next_o_id +1, READ c_discount,
            WRITE order, WRITE new_order, then per line READ i_price,
            ADD s_quantity -q, WRITE order_line (21 to 51 ops).
  Payment   d ~ U[0,9]; the customer is in the home warehouse and
            district except for a ``remote_payment_frac`` share (another
            warehouse, district ~ U[0,9]); c ~ NURand(1023,1,3000);
            h ~ U[1,5000] whole dollars.  Ops: ADD w_ytd +h, ADD d_ytd +h,
            ADD c_balance -h, ADD c_ytd_payment +h, ADD c_payment_cnt +1,
            WRITE history (6 ops).

Departures from the spec (no restock rule, customers by id only, no
NewOrder rollbacks, insert keys from the txn's index in the stream, whole
dollars and basis points, only touched rows loaded) are listed under
``assumed`` in ``bench/configs/tpcc_np.json``.

``workloads/drift.py`` keeps its own NewOrder/Payment over the key
helpers ``w_ytd``, ``d_next_oid``, ``d_ytd``, ``stock``, ``cust_bal``,
``order_row`` and its ``HOT_ITEMS`` hot stock rows.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.packets import ADD, READ, WRITE
from repro.db.txn import Txn, key_of

N_DISTRICTS = 10
HOT_ITEMS = 20          # drift's hot stock rows per warehouse
ORDER_LINES = 16        # key slots per order in ORDER_LINE (ol_cnt <= 15)
P_NEWORDER = 0.5        # the rest are Payments
OL_CNT = (5, 15)        # order lines per NewOrder, U[5, 15]
MAX_AMOUNT = 5000       # Payment's H_AMOUNT, U[1, 5000] whole dollars

# local key offset of each table's column (one int32 per key)
W_YTD, D_NEXT_O_ID, D_YTD = 10_000_000, 20_000_000, 30_000_000
S_QUANTITY, C_BALANCE, ORDER_ROW = 40_000_000, 50_000_000, 60_000_000
W_TAX, D_TAX, C_DISCOUNT = 70_000_000, 80_000_000, 90_000_000
C_YTD_PAYMENT, C_PAYMENT_CNT, I_PRICE = (100_000_000, 110_000_000,
                                         120_000_000)
# inserted rows, keyed by the txn's index in the stream
ORDER, NEW_ORDER, HISTORY, ORDER_LINE = (200_000_000, 300_000_000,
                                         400_000_000, 500_000_000)
MAX_TXNS = (1_000_000_000 - ORDER_LINE) // ORDER_LINES


@dataclass
class TPCCParams:
    n_nodes: int = 8
    n_warehouses: int = 8
    n_items: int = 100_000
    n_customers: int = 3000         # per district
    remote_line_frac: float = 0.01
    remote_payment_frac: float = 0.15


def _node(p, w):
    return w % p.n_nodes


def _cust(w, d, c):
    return (w * N_DISTRICTS + d) * 3000 + c


def w_ytd(p, w):
    return key_of(_node(p, w), W_YTD + w)


def w_tax(p, w):
    return key_of(_node(p, w), W_TAX + w)


def d_next_oid(p, w, d):
    return key_of(_node(p, w), D_NEXT_O_ID + w * N_DISTRICTS + d)


def d_ytd(p, w, d):
    return key_of(_node(p, w), D_YTD + w * N_DISTRICTS + d)


def d_tax(p, w, d):
    return key_of(_node(p, w), D_TAX + w * N_DISTRICTS + d)


def stock(p, w, i):
    """S_QUANTITY of item ``i`` in warehouse ``w``."""
    return key_of(_node(p, w), S_QUANTITY + w * 100_000 + i)


def cust_bal(p, w, d, c):
    """C_BALANCE."""
    return key_of(_node(p, w), C_BALANCE + _cust(w, d, c))


def c_discount(p, w, d, c):
    return key_of(_node(p, w), C_DISCOUNT + _cust(w, d, c))


def c_ytd_payment(p, w, d, c):
    return key_of(_node(p, w), C_YTD_PAYMENT + _cust(w, d, c))


def c_payment_cnt(p, w, d, c):
    return key_of(_node(p, w), C_PAYMENT_CNT + _cust(w, d, c))


def i_price(p, node, i):
    """I_PRICE of item ``i`` in ``node``'s replica of ITEM."""
    return key_of(node, I_PRICE + i)


def order_row(p, w, uniq):
    """drift's order rows."""
    return key_of(_node(p, w), ORDER_ROW + uniq)


def order(p, w, o):
    """The ORDER row of order ``o``, unique across the stream."""
    return key_of(_node(p, w), ORDER + o)


def new_order(p, w, o):
    return key_of(_node(p, w), NEW_ORDER + o)


def order_line(p, w, o, line):
    return key_of(_node(p, w), ORDER_LINE + o * ORDER_LINES + line)


def history(p, w, h):
    return key_of(_node(p, w), HISTORY + h)


def hot_keys(p: TPCCParams):
    """Per warehouse W_TAX and W_YTD, per district D_TAX, D_YTD and
    D_NEXT_O_ID: 32 per warehouse."""
    ks = []
    for w in range(p.n_warehouses):
        ks += [w_tax(p, w), w_ytd(p, w)]
        for d in range(N_DISTRICTS):
            ks += [d_tax(p, w, d), d_ytd(p, w, d), d_next_oid(p, w, d)]
    return ks


def nurand(rng, a, x, y, c):
    """NURand(A, x, y) of clause 2.1.6 with run-time constant ``c``."""
    return ((int(rng.integers(0, a + 1)) | int(rng.integers(x, y + 1)))
            + c) % (y - x + 1) + x


def _other(rng, p, w):
    """A warehouse other than ``w``, uniformly."""
    return (w + 1 + int(rng.integers(p.n_warehouses - 1))) % p.n_warehouses


def _neworder(rng, p, i, c_cust, c_item):
    w = int(rng.integers(p.n_warehouses))
    home = _node(p, w)
    d = int(rng.integers(N_DISTRICTS))
    c = nurand(rng, 1023, 1, p.n_customers, c_cust) - 1
    ol_cnt = int(rng.integers(OL_CNT[0], OL_CNT[1] + 1))
    items = []
    while len(items) < ol_cnt:            # distinct: redraw duplicates
        it = nurand(rng, 8191, 1, p.n_items, c_item) - 1
        if it not in items:
            items.append(it)
    ops = [(READ, w_tax(p, w), 0), (READ, d_tax(p, w, d), 0),
           (ADD, d_next_oid(p, w, d), 1),
           (READ, c_discount(p, w, d, c), 0),
           (WRITE, order(p, w, i), c), (WRITE, new_order(p, w, i), i)]
    for line, it in enumerate(items):
        sw = w
        if p.n_warehouses > 1 and rng.random() < p.remote_line_frac:
            sw = _other(rng, p, w)
        q = int(rng.integers(1, 11))
        ops += [(READ, i_price(p, home, it), 0), (ADD, stock(p, sw, it), -q),
                (WRITE, order_line(p, w, i, line), q)]
    return Txn("neworder", ops, home)


def _payment(rng, p, i, c_cust):
    w = int(rng.integers(p.n_warehouses))
    d = int(rng.integers(N_DISTRICTS))
    cw, cd = w, d
    if p.n_warehouses > 1 and rng.random() < p.remote_payment_frac:
        cw, cd = _other(rng, p, w), int(rng.integers(N_DISTRICTS))
    c = nurand(rng, 1023, 1, p.n_customers, c_cust) - 1
    h = int(rng.integers(1, MAX_AMOUNT + 1))
    ops = [(ADD, w_ytd(p, w), h), (ADD, d_ytd(p, w, d), h),
           (ADD, cust_bal(p, cw, cd, c), -h),
           (ADD, c_ytd_payment(p, cw, cd, c), h),
           (ADD, c_payment_cnt(p, cw, cd, c), 1),
           (WRITE, history(p, w, i), h)]
    return Txn("payment", ops, _node(p, w))


def generate(rng: np.random.Generator, n: int, p: TPCCParams):
    """``n`` NewOrder / Payment txns; txn ``i`` of the stream inserts the
    rows of order ``i`` (or history row ``i``), so insert keys are unique
    per txn and the stream is a pure function of the seed."""
    if n > MAX_TXNS:
        raise ValueError(f"at most {MAX_TXNS} txns have distinct insert keys")
    c_cust, c_item = int(rng.integers(0, 1024)), int(rng.integers(0, 8192))
    return [_neworder(rng, p, i, c_cust, c_item)
            if rng.random() < P_NEWORDER else _payment(rng, p, i, c_cust)
            for i in range(n)]


# clause 4.3.3.1 as integers: (table offset, low, high) of U[low, high]
_INITIAL = ((W_YTD, 300_000, 300_000), (D_NEXT_O_ID, 3001, 3001),
            (D_YTD, 30_000, 30_000), (S_QUANTITY, 10, 100),
            (C_BALANCE, -10, -10), (W_TAX, 0, 2000), (D_TAX, 0, 2000),
            (C_DISCOUNT, 0, 5000), (C_YTD_PAYMENT, 10, 10),
            (C_PAYMENT_CNT, 1, 1), (I_PRICE, 1, 100))


def initial_values(rng: np.random.Generator, keys):
    """``{key: initial value}`` for the populated keys among ``keys``;
    inserted rows (and drift's order rows) are not populated.  I_PRICE is
    drawn once per item, so every node's replica of ITEM agrees."""
    out, price = {}, {}
    for k in keys:
        local = k % 1_000_000_000
        for base, lo, hi in _INITIAL:
            if base <= local < base + 10_000_000:
                if base != I_PRICE:
                    out[k] = int(rng.integers(lo, hi + 1))
                elif local not in price:
                    out[k] = price[local] = int(rng.integers(lo, hi + 1))
                else:
                    out[k] = price[local]
                break
    return out


def traces(txns):
    return [[(k, o) for o, k, _ in t.ops] for t in txns]

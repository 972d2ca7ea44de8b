"""TPC-C NewOrder + Payment through the served path, against the plain
reference.

  * ``repro.workloads.tpcc.generate`` follows the two profiles: op shapes,
    line counts, distinct items, remote shares, unique insert keys.
  * Every txn of the mix is warm, and ``Cluster.run_batch`` + ``drain``
    (async and sync hot path) gives every client result, hot register and
    cold row that ``tests/oracle.py`` gives serially; ``warm_dispatches``
    counts one switch sub-txn per warm commit.
"""
import copy
import functools

import numpy as np
import pytest

from repro.core.hotset import build_hot_index
from repro.core.packets import ADD, READ, WRITE, SwitchConfig
from repro.db.dbms import Cluster
from repro.db.txn import Txn, node_of
from repro.workloads import tpcc
from oracle import OracleDB

SW = SwitchConfig(n_stages=16, regs_per_stage=512, max_instrs=16)
P = tpcc.TPCCParams(n_nodes=2, n_warehouses=2)

NEWORDER_HEAD = [READ, READ, ADD, READ, WRITE, WRITE]
NEWORDER_LINE = [READ, ADD, WRITE]
PAYMENT = [ADD, ADD, ADD, ADD, ADD, WRITE]


def test_the_generator_follows_the_profiles():
    p = tpcc.TPCCParams(n_nodes=8, n_warehouses=8)
    txns = tpcc.generate(np.random.default_rng(3), 3000, p)
    hot = set(tpcc.hot_keys(p))
    assert len(hot) == 32 * 8
    no = [t for t in txns if t.kind == "neworder"]
    pay = [t for t in txns if t.kind == "payment"]
    assert len(no) / len(txns) == pytest.approx(0.5, abs=0.03)
    lines = 0
    remote_lines = 0
    for t in no:
        ops = [o for o, _, _ in t.ops]
        n = (len(ops) - 6) // 3
        assert 5 <= n <= 15 and len(ops) == 6 + 3 * n
        assert ops == NEWORDER_HEAD + NEWORDER_LINE * n
        keys = t.keys()
        assert [k in hot for k in keys] == [True] * 3 + [False] * (3 + 3 * n)
        assert {node_of(k) for k in keys[:6]} == {t.home}
        items = keys[6::3]
        assert len(set(items)) == n                     # distinct items
        assert {node_of(k) for k in items} == {t.home}  # home's ITEM copy
        assert all(1 <= -v <= 10 for _, _, v in t.ops[7::3])
        lines += n
        remote_lines += sum(node_of(k) != t.home for k in keys[7::3])
    assert lines / len(no) == pytest.approx(10, abs=0.3)
    assert remote_lines / lines == pytest.approx(0.01, abs=0.005)
    remote_pay = 0
    for t in pay:
        assert [o for o, _, _ in t.ops] == PAYMENT
        keys = t.keys()
        assert [k in hot for k in keys] == [True] * 2 + [False] * 4
        h = t.ops[0][2]
        assert 1 <= h <= 5000
        assert [v for _, _, v in t.ops] == [h, h, -h, h, 1, h]
        remote_pay += node_of(keys[2]) != t.home
    assert remote_pay / len(pay) == pytest.approx(0.15, abs=0.03)
    inserts = [k for t in txns for o, k, _ in t.ops if o == WRITE]
    assert len(set(inserts)) == len(inserts)


def test_initial_values_follow_the_population_rule():
    p = tpcc.TPCCParams()
    rng = np.random.default_rng(0)
    init = tpcc.initial_values(rng, [
        tpcc.w_ytd(p, 3), tpcc.d_ytd(p, 3, 4), tpcc.d_next_oid(p, 3, 4),
        tpcc.cust_bal(p, 1, 2, 3), tpcc.c_ytd_payment(p, 1, 2, 3),
        tpcc.c_payment_cnt(p, 1, 2, 3), tpcc.order(p, 1, 7),
        tpcc.history(p, 1, 7)])
    assert sorted(init.values()) == [-10, 1, 10, 3001, 30_000, 300_000]
    taxes = tpcc.initial_values(rng, [tpcc.w_tax(p, w) for w in range(8)])
    assert all(0 <= v <= 2000 for v in taxes.values())


def test_every_replica_of_an_item_has_one_price():
    p = tpcc.TPCCParams()
    init = tpcc.initial_values(np.random.default_rng(1), [
        tpcc.i_price(p, node, i) for i in range(50)
        for node in range(p.n_nodes)])
    prices = [[init[tpcc.i_price(p, node, i)] for node in range(p.n_nodes)]
              for i in range(50)]
    assert all(len(set(row)) == 1 for row in prices)
    assert all(1 <= row[0] <= 100 for row in prices)
    assert len({row[0] for row in prices}) > 1


@functools.lru_cache(maxsize=None)
def _workload():
    hot = tpcc.hot_keys(P)
    hi = build_hot_index([[(k, ADD) for k in hot]], len(hot), SW)
    txns = tpcc.generate(np.random.default_rng(11), 256, P)
    keys = {k for t in txns for k in t.keys()} | set(hot)
    init = tpcc.initial_values(np.random.default_rng(12), sorted(keys))
    return hi, txns, init


@pytest.mark.parametrize("async_hot", [False, True])
def test_run_batch_matches_the_oracle(async_hot):
    hi, txns, init = _workload()
    c = Cluster(P.n_nodes, SW, hi, use_switch=True, async_hot=async_hot)
    ref = OracleDB()
    for k, v in init.items():
        ref.load(k, v)
        if hi.is_hot(k):
            c.load(k, v)
        else:
            c.nodes[node_of(k)].store[k] = v
    c.snapshot_offload()
    txns = copy.deepcopy(txns)
    assert c._classify_batch(txns) == ["warm"] * len(txns)
    s0 = dict(c.stats)
    got = []
    for i in range(0, len(txns), 64):
        res = c.run_batch(txns[i:i + 64])
        c.drain()
        got += list(res)
    want = [ref.apply_txn(t) for t in txns]
    assert got == want
    n = len(txns)
    assert c.stats["warm"] == n and c.stats["hot"] == s0.get("hot", 0)
    assert c.stats["warm_dispatches"] == n
    assert c.stats["commits"] - s0.get("commits", 0) == n
    assert c.stats["warm_syncs"] == 0      # no hot txn is ever pending
    for k, v in ref.values.items():
        if hi.is_hot(k):
            assert c.switch.read_value(hi.slot(k)) == v, k
        else:
            assert c.nodes[node_of(k)].store[k] == v, k


@pytest.mark.parametrize("async_hot", [False, True])
def test_warm_syncs_count_consistency_points_with_pending_work(async_hot):
    """A warm txn after buffered hot txns flushes them (one sync); a warm
    txn right after another finds nothing pending (none)."""
    hi, txns, _ = _workload()
    c = Cluster(P.n_nodes, SW, hi, use_switch=True, async_hot=async_hot)
    c.snapshot_offload()
    bump = Txn("bump", [(ADD, tpcc.w_ytd(P, 0), 1)], 0)
    batch = [copy.deepcopy(bump), copy.deepcopy(txns[0]),
             copy.deepcopy(txns[1]), copy.deepcopy(bump),
             copy.deepcopy(txns[2])]
    c.run_batch(batch)
    c.drain()
    assert c.stats["warm_syncs"] == 2
    assert c.stats["warm_dispatches"] == 3

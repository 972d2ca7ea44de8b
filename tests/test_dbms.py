"""Host-DBMS integration: P4DB cluster == No-Switch cluster on final state;
warm transactions; durability & recovery incl. the paper's Fig-9 scenario."""
import copy

import numpy as np
import pytest

from repro.core.hotset import build_hot_index
from repro.core.packets import ADD, CADD, READ, WRITE, SwitchConfig
from repro.db.dbms import GAVE_UP, Cluster
from repro.db.txn import Txn, key_of
from repro.workloads import smallbank, tpcc, ycsb

SW = SwitchConfig(n_stages=16, regs_per_stage=512, max_instrs=16)


def _value(c, k):
    if c.use_switch and c.hot_index.is_hot(k):
        return c.switch.read_value(c.hot_index.slot(k))
    return c.nodes[k // 1_000_000_000].store[k]


def _run_pair(txns, hi, n_nodes=4):
    c1 = Cluster(n_nodes, SW, hi, use_switch=True)
    c2 = Cluster(n_nodes, SW, hot_index=None, use_switch=False)
    c1.snapshot_offload()
    for t in txns:
        c1.run(copy.deepcopy(t))
        c2.run(copy.deepcopy(t))
    keys = {k for t in txns for k in t.keys()}
    for k in keys:
        assert _value(c1, k) == _value(c2, k), k
    return c1, c2


def test_ycsb_state_equivalence():
    p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 64, SW)
    txns = ycsb.generate(np.random.default_rng(1), 300, p)
    c1, _ = _run_pair(txns, hi)
    assert c1.stats["hot"] > 0 and c1.stats["cold"] > 0


def test_tpcc_warm_transactions():
    """Every NewOrder and Payment is warm: one switch sub-txn per commit,
    and the same final state as the cluster without a switch."""
    p = tpcc.TPCCParams(n_nodes=4, n_warehouses=8)
    sample = tpcc.generate(np.random.default_rng(0), 10000, p)
    hot = tpcc.hot_keys(p)
    hi = build_hot_index(tpcc.traces(sample), len(hot), SW)
    assert set(hi.placement.slot) == set(hot)
    txns = tpcc.generate(np.random.default_rng(1), 200, p)
    assert {hi.classify(tr) for tr in tpcc.traces(txns)} == {"warm"}
    c1, c2 = _run_pair(txns, hi)
    assert c1.stats["warm"] == c1.stats["warm_dispatches"] == len(txns)
    assert c1.stats["commits"] == c2.stats["commits"] == len(txns)
    assert c1.stats["hot"] == c1.stats["cold"] == 0
    assert c2.stats["warm_dispatches"] == 0


def test_switch_recovery_from_wals():
    p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 64, SW)
    c = Cluster(4, SW, hi, use_switch=True)
    for k in list(hi.placement.slot)[:20]:
        c.load(k, 7)
    c.snapshot_offload()
    txns = ycsb.generate(np.random.default_rng(2), 200, p)
    for t in txns:
        c.run(t)
    before = np.asarray(c.switch.registers).copy()
    known, unknown = c.crash_switch_and_recover()
    np.testing.assert_array_equal(before, np.asarray(c.switch.registers))
    assert known > 0


def test_fig9_inflight_recovery_order_from_rw_sets():
    """Fig 9: node 1's result entry is lost; the order of T1, T2 must be
    recoverable from read/write-set dependencies — here execution is
    deterministic ADDs, so any replay order gives the same state, and the
    replay must reproduce the registers exactly."""
    hi = build_hot_index([[(key_of(0, 1), ADD)]], 4, SW)
    c = Cluster(2, SW, hi, use_switch=True)
    c.load(key_of(0, 1), 1)
    c.snapshot_offload()
    t1 = Txn("t1", [(ADD, key_of(0, 1), 2)], home=0)
    t2 = Txn("t2", [(ADD, key_of(0, 1), 3)], home=1)
    c.run(t1)
    c.run(t2)
    # drop node0's switch_result entry (in-flight at crash time)
    c.nodes[0].wal = [e for e in c.nodes[0].wal
                      if e.kind != "switch_result"]
    before = np.asarray(c.switch.registers).copy()
    known, unknown = c.crash_switch_and_recover()
    assert unknown == 1 and known == 1
    np.testing.assert_array_equal(before, np.asarray(c.switch.registers))


def test_node_crash_recovery():
    p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 64, SW)
    c = Cluster(4, SW, hi, use_switch=True)
    c.snapshot_offload()
    txns = ycsb.generate(np.random.default_rng(3), 200, p)
    for t in txns:
        c.run(t)
    snap = dict(c.nodes[1].store)
    c.crash_node_and_recover(1)
    rec = c.nodes[1].store
    # stores are defaultdicts: reads materialize zero entries that recovery
    # legitimately omits — compare value semantics
    for k, v in snap.items():
        assert rec.get(k, 0) == v, k


def test_smallbank_constraints_hold():
    """CADD (constrained write) may never drive a balance negative —
    neither on the switch nor on nodes."""
    p = smallbank.SmallBankParams(n_nodes=2, accounts_per_node=50,
                                  hot_per_node=4)
    sample = smallbank.generate(np.random.default_rng(0), 2000, p)
    hi = build_hot_index(smallbank.traces(sample), 16, SW)
    c = Cluster(2, SW, hi, use_switch=True)
    for k in smallbank.hot_keys(p):
        c.load(k, 100)
    c.snapshot_offload()
    for t in smallbank.generate(np.random.default_rng(1), 300, p):
        c.run(t)
    regs = np.asarray(c.switch.registers)
    slots = list(hi.placement.slot.values())
    for _, s, r in slots:
        assert regs[s, r] >= 0


def test_hot_counter_semantics():
    """Counter-semantics audit pin (ISSUE 9 satellite 6).  The claimed
    "hot double-count on the batch path" does NOT exist: "hot" counts
    admissions, exactly once per hot txn, on BOTH the per-txn and batch
    paths; a warm txn's switch sub-txn never bumps it.  "cold"/"warm"
    count execution *attempts* -- each retry after an abort bumps again,
    and exhaustion adds one "gave_up"."""
    p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 64, SW)
    txns = ycsb.generate(np.random.default_rng(6), 250, p)

    # oracle: classification alone, no execution
    oracle = Cluster(4, SW, hi, use_switch=True)
    n_hot = sum(oracle.classify(t) == "hot" for t in txns)
    assert n_hot > 0

    c_run = Cluster(4, SW, hi, use_switch=True)
    c_run.snapshot_offload()
    for t in copy.deepcopy(txns):
        c_run.run(t)
    assert c_run.stats["hot"] == n_hot          # once per admission

    c_batch = Cluster(4, SW, hi, use_switch=True)
    c_batch.snapshot_offload()
    c_batch.run_batch(copy.deepcopy(txns))
    c_batch.drain()
    assert c_batch.stats["hot"] == n_hot        # batch path: same count

    # a warm txn calls _run_hot for its switch sub-txn; that is NOT a hot
    # admission and must not bump "hot"
    hot_key = next(iter(hi.placement.slot))
    cold_key = next(k for n in range(4) for i in range(1000)
                    if not hi.is_hot(k := key_of(n, i)))
    c_warm = Cluster(4, SW, hi, use_switch=True)
    c_warm.snapshot_offload()
    out = c_warm.run(Txn("warm", [(ADD, hot_key, 1), (ADD, cold_key, 1)],
                         home=0))
    assert out is not None
    assert c_warm.stats["warm"] == 1 and c_warm.stats["hot"] == 0

    # attempts semantics: a doomed cold CADD (balance 0, delta -5) aborts
    # every attempt -- one "cold" bump per attempt, one final "gave_up"
    c_cold = Cluster(4, SW, hot_index=None, use_switch=False)
    out = c_cold.run(Txn("doomed", [(CADD, cold_key, -5)], home=0),
                     max_retries=4)
    assert out is GAVE_UP and not out
    assert c_cold.stats["cold"] == 4
    assert c_cold.stats["aborts"] == 4
    assert c_cold.stats["gave_up"] == 1
    assert c_cold.stats["hot"] == 0

    # same attempts rule on the warm path (cold part is abort-proofed
    # first, so the constraint failure retries the whole warm txn)
    c_wd = Cluster(4, SW, hi, use_switch=True)
    c_wd.snapshot_offload()
    out = c_wd.run(Txn("doomed-warm", [(ADD, hot_key, 1),
                                       (CADD, cold_key, -5)], home=0),
                   max_retries=3)
    assert out is GAVE_UP and not out
    assert c_wd.stats["warm"] == 3 and c_wd.stats["gave_up"] == 1
    assert c_wd.stats["hot"] == 0

"""Sharded multi-switch register plane (tentpole): the N-shard engine and
cluster must be observationally identical to the single-switch reference.

Pins, per ISSUE 7:
  * ``ShardedSwitchEngine`` with ``n_switches == 1`` delegates verbatim to
    ``SwitchEngine`` — byte-identical results, registers, GIDs, dispatch
    accounting, in every engine mode;
  * N in {2, 4} matches a "virtual big switch" oracle (one pipeline with
    ``N * n_stages`` stages and the same global-stage packets) on random
    mixed batches — including cross-shard rows, CADD, multipass ops and
    cross-shard ADDP forwarding — sync and async;
  * whole clusters at N in {1, 2, 4} produce identical results, GIDs,
    per-key values, stores and WAL streams for the same workload across
    engine modes and sync/async hot paths;
  * a migration crossing an undrained async batch stays exact at N = 2;
  * hot capacity is linear in the shard count.
"""
import copy

import numpy as np
import pytest

from repro.core.engine import ShardedSwitchEngine, SwitchEngine
from repro.core.heat import HeatTracker
from repro.core.hotset import HotIndex, build_hot_index
from repro.core.layout import Placement
from repro.core.packets import (ADD, ADDP, CADD, READ, WRITE, SwitchConfig,
                                build_packets)
from repro.db.dbms import Cluster
from repro.db.migrate import EpochController
from repro.db.txn import Txn, key_of, node_of

from conftest import force_engine

S, R, M = 4, 32, 8


def CFG(n):
    return SwitchConfig(n_stages=S, regs_per_stage=R, max_instrs=M,
                        n_switches=n)


def _round_robin_placement(n_switches, keys):
    """Keys dealt across switches, then stages, then registers — every
    switch holds an equal share and co-accessed keys usually straddle
    shards (the worst case for the cross-shard path)."""
    slot = {}
    for i, k in enumerate(keys):
        sw = i % n_switches
        st = (i // n_switches) % S
        rg = i // (n_switches * S)
        slot[k] = (sw, st, rg)
    return Placement(slot=slot)


def _mixed_txns(rng, keys, n_txns, ops_pool):
    txns = []
    for _ in range(n_txns):
        n_ops = int(rng.integers(1, 5))
        picks = rng.choice(len(keys), size=n_ops, replace=False)
        ops = [(ops_pool[int(rng.integers(len(ops_pool)))],
                keys[int(p)], int(rng.integers(1, 9))) for p in picks]
        txns.append(Txn("r", ops, 0))
    return txns


def _safe_txns(rng, hi, keys, n_txns):
    """Single-pass rows: ops sorted by global (switch, stage) slot order,
    READ/WRITE/ADD only — legal under every explicit engine mode."""
    order = {k: hi.placement.slot[k] for k in keys}
    txns = []
    for _ in range(n_txns):
        picks = rng.choice(len(keys), size=int(rng.integers(1, 4)),
                           replace=False)
        ks = sorted((keys[int(p)] for p in picks), key=order.__getitem__)
        ops = [( [READ, WRITE, ADD][int(rng.integers(3))],
                 k, int(rng.integers(1, 9))) for k in ks]
        txns.append(Txn("s", ops, 0))
    return txns


def _drain(engine, pkts, meta, mode):
    pb = engine.execute_batch(copy.deepcopy(pkts), dict(meta), mode=mode)
    return pb.results_np().copy(), pb.ok_np().copy()


# ===================================================================== #
#  N = 1: the sharded facade IS the single switch                       #
# ===================================================================== #

@pytest.mark.parametrize("mode", ["auto", "serial", "affine", "staged"])
def test_n1_facade_byte_identical(mode):
    rng = np.random.default_rng(3)
    keys = [key_of(0, i) for i in range(24)]
    hi = HotIndex(_round_robin_placement(1, keys))
    txns = _safe_txns(rng, hi, keys, 20)
    pkts, meta = build_packets(txns, hi, CFG(1))
    ref, sh = SwitchEngine(CFG(1)), ShardedSwitchEngine(CFG(1))
    for _ in range(3):                     # repeated batches: gid stream
        r_ref = _drain(ref, pkts, meta, mode)
        r_sh = _drain(sh, pkts, meta, mode)
        np.testing.assert_array_equal(r_ref[0], r_sh[0])
        np.testing.assert_array_equal(r_ref[1], r_sh[1])
    np.testing.assert_array_equal(np.asarray(ref.read_all()),
                                  np.asarray(sh.read_all()))
    assert ref.next_gid == sh.next_gid
    assert ref.dispatch_count == sh.dispatch_count


# ===================================================================== #
#  N in {2, 4} vs the virtual-big-switch oracle                         #
# ===================================================================== #

def _oracle(n):
    """One pipeline with n*S stages: global-stage packets run on it
    unchanged, so it defines ground truth for any shard count."""
    return SwitchEngine(SwitchConfig(n_stages=n * S, regs_per_stage=R,
                                     max_instrs=M))


def _assert_matches_oracle(n, txns, mode, async_dispatch=False):
    keys = sorted({k for t in txns for _, k, _ in t.ops})
    hi = HotIndex(_round_robin_placement(n, keys))
    pkts, meta = build_packets(txns, hi, CFG(n))
    big = _oracle(n)
    sh = ShardedSwitchEngine(CFG(n), async_dispatch=async_dispatch)
    r_big = _drain(big, pkts, meta, mode)
    r_sh = _drain(sh, pkts, meta, mode)
    np.testing.assert_array_equal(r_big[0], r_sh[0])
    np.testing.assert_array_equal(r_big[1], r_sh[1])
    np.testing.assert_array_equal(
        np.asarray(big.read_all()),
        np.asarray(sh.read_all()).reshape(n * S, R))
    assert big.next_gid == sh.next_gid
    return hi


@pytest.mark.parametrize("mode", ["auto", "serial"])
@pytest.mark.parametrize("n", [2, 4])
def test_sharded_matches_oracle_mixed(n, mode):
    rng = np.random.default_rng(11 + n)
    keys = [key_of(0, i) for i in range(32)]
    txns = _mixed_txns(rng, keys, 24, [READ, WRITE, ADD, CADD])
    _assert_matches_oracle(n, txns, mode)


@pytest.mark.parametrize("mode", ["affine", "staged"])
def test_sharded_matches_oracle_safe_modes(mode):
    rng = np.random.default_rng(5)
    keys = [key_of(0, i) for i in range(32)]
    hi = HotIndex(_round_robin_placement(2, keys))
    txns = _safe_txns(rng, hi, keys, 24)
    _assert_matches_oracle(2, txns, mode)


def test_cross_shard_addp_forwarding():
    """ADDP whose source register lives on ANOTHER switch: the facade
    resolves the gathered operand on the host (the inter-switch hop) and
    must match the big-switch serial oracle exactly."""
    A, B, C = key_of(0, 0), key_of(0, 1), key_of(0, 2)
    hi = HotIndex(Placement(slot={A: (0, 0, 0), B: (1, 0, 0),
                                  C: (1, 1, 0)}))
    txns = [Txn("w", [(WRITE, A, 7), (WRITE, B, 30), (WRITE, C, 500)], 0),
            Txn("u", [(READ, B, 0), (ADDP, A, 0)], 0),   # B -> A x-shard
            Txn("s", [(ADD, A, 1), (READ, C, 0)], 0),
            Txn("u2", [(READ, A, 0), (ADDP, C, 0)], 0)]  # A -> C x-shard
    pkts, meta = build_packets(txns, hi, CFG(2))
    big, sh = _oracle(2), ShardedSwitchEngine(CFG(2))
    r_big = _drain(big, pkts, meta, "auto")
    r_sh = _drain(sh, pkts, meta, "auto")
    np.testing.assert_array_equal(r_big[0], r_sh[0])
    np.testing.assert_array_equal(
        np.asarray(big.read_all()),
        np.asarray(sh.read_all()).reshape(2 * S, R))
    # the forwarded ADDP really landed: A = 7 + 30 + 1, C = 500 + A-read
    assert sh.read_value((0, 0, 0)) == 38
    assert sh.read_value((1, 1, 0)) == 538


def test_async_sharded_matches_sync():
    rng = np.random.default_rng(9)
    keys = [key_of(0, i) for i in range(32)]
    txns = _mixed_txns(rng, keys, 24, [READ, WRITE, ADD, CADD])
    hi = HotIndex(_round_robin_placement(2, keys))
    pkts, meta = build_packets(txns, hi, CFG(2))
    sync_e = ShardedSwitchEngine(CFG(2))
    async_e = ShardedSwitchEngine(CFG(2), async_dispatch=True)
    rs = _drain(sync_e, pkts, meta, "auto")
    ra = _drain(async_e, pkts, meta, "auto")
    np.testing.assert_array_equal(rs[0], ra[0])
    np.testing.assert_array_equal(np.asarray(sync_e.read_all()),
                                  np.asarray(async_e.read_all()))


def test_snapshot_restore_roundtrip_sharded():
    rng = np.random.default_rng(21)
    keys = [key_of(0, i) for i in range(16)]
    hi = HotIndex(_round_robin_placement(2, keys))
    pkts, meta = build_packets(_mixed_txns(rng, keys, 12,
                                           [WRITE, ADD]), hi, CFG(2))
    e = ShardedSwitchEngine(CFG(2))
    e.execute_batch(pkts, meta).results_np()
    snap = e.snapshot()
    before = np.asarray(e.read_all()).copy()
    e.execute_batch(pkts, meta).results_np()
    e.restore(snap)
    np.testing.assert_array_equal(before, np.asarray(e.read_all()))


# ===================================================================== #
#  Cluster-level N in {1, 2, 4} equivalence                             #
# ===================================================================== #

N_NODES = 2


def _workload(n_hot=40, n_txns=120, seed=7):
    """Hot / warm / cold mix over a fixed key universe; traces mention
    only hot keys so every shard count detects the SAME hot set (the
    placements differ, the classification does not)."""
    rng = np.random.default_rng(seed)
    hot = [key_of(i % N_NODES, i) for i in range(n_hot)]
    cold = [key_of(i % N_NODES, 1000 + i) for i in range(12)]
    txns = []
    for _ in range(n_txns):
        r = rng.random()
        picks = rng.choice(n_hot, size=2, replace=False)
        h0, h1 = hot[int(picks[0])], hot[int(picks[1])]
        v = int(rng.integers(1, 9))
        if r < 0.65:                                     # hot
            txns.append(Txn("h", [(ADD, h0, v), (READ, h1, 0)],
                            node_of(h0)))
        elif r < 0.85:                                   # warm
            ck = cold[int(rng.integers(len(cold)))]
            txns.append(Txn("w", [(WRITE, ck, v), (ADD, h0, v)],
                            node_of(ck)))
        else:                                            # cold
            ck = cold[int(rng.integers(len(cold)))]
            txns.append(Txn("c", [(ADD, ck, v)], node_of(ck)))
    traces = [[(k, op) for op, k, _ in t.ops if k in set(hot)]
              for t in txns if t.kind == "h"]
    return txns, traces, hot


def _cluster(n, traces, hot, async_hot):
    cfg = CFG(n)
    hi = build_hot_index(traces, len(hot), cfg)
    c = Cluster(N_NODES, cfg, hi, use_switch=True, async_hot=async_hot)
    for k in hot:
        c.load(k, 100)
    c.snapshot_offload()
    return c


def _wal_stream(c):
    return [[(e.kind, e.tid) for e in n.wal] for n in c.nodes]


@pytest.mark.parametrize("async_hot", [False, True])
@pytest.mark.parametrize("mode", ["auto", "serial"])
def test_cluster_equivalent_across_shard_counts(mode, async_hot,
                                                monkeypatch):
    force_engine(monkeypatch, mode)
    txns, traces, hot = _workload()
    worlds = {}
    for n in (1, 2, 4):
        c = _cluster(n, traces, hot, async_hot)
        res = []
        for i in range(0, len(txns), 32):
            res += c.run_batch([copy.deepcopy(t)
                                for t in txns[i:i + 32]])
        c.drain()
        worlds[n] = (c, res)
    c1, r1 = worlds[1]
    for n in (2, 4):
        cn, rn = worlds[n]
        assert r1 == rn, f"results diverge at N={n}"
        assert c1.switch.next_gid == cn.switch.next_gid
        for key in ("commits", "aborts", "hot", "warm", "cold"):
            assert c1.stats[key] == cn.stats[key], (n, key)
        for k in hot:
            assert c1.read(k) == cn.read(k), (n, k)
        for a, b in zip(c1.nodes, cn.nodes):
            assert dict(a.store) == dict(b.store)
        assert _wal_stream(c1) == _wal_stream(cn)


@pytest.mark.parametrize("mode", ["affine", "staged"])
def test_cluster_explicit_modes_match_across_shards(mode, monkeypatch):
    force_engine(monkeypatch, mode)
    txns, traces, hot = _workload(n_txns=60, seed=13)
    c1 = _cluster(1, traces, hot, False)
    c2 = _cluster(2, traces, hot, False)
    r1 = c1.run_batch([copy.deepcopy(t) for t in txns])
    r2 = c2.run_batch([copy.deepcopy(t) for t in txns])
    assert r1 == r2
    for k in hot:
        assert c1.read(k) == c2.read(k)
    assert _wal_stream(c1) == _wal_stream(c2)


def test_cluster_recovery_at_n2():
    """Crash/recover of the sharded plane: WAL replay onto the [N, S, R]
    register file reproduces the pre-crash state exactly."""
    txns, traces, hot = _workload(n_txns=80, seed=17)
    c = _cluster(2, traces, hot, False)
    c.run_batch([copy.deepcopy(t) for t in txns])
    before = np.asarray(c.switch.read_all()).copy()
    c.crash_switch_and_recover()
    np.testing.assert_array_equal(before, np.asarray(c.switch.read_all()))


# ===================================================================== #
#  Migration crossing an undrained batch at N = 2                       #
# ===================================================================== #

def test_migration_crosses_undrained_batch_n2():
    A1, A2 = key_of(0, 0), key_of(0, 1)
    Bk = [key_of(0, 10 + i) for i in range(2)]
    cfg = CFG(2)
    hi = HotIndex(Placement(slot={A1: (0, 0, 0), A2: (1, 0, 0)}))
    txns = [Txn("h", [(ADD, A1, i + 1), (READ, A2, 0)], 0)
            for i in range(6)]
    txns += [Txn("c", [(ADD, Bk[i % 2], 7)], 0) for i in range(30)]
    loads = [(A1, 5), (A2, 11), (Bk[0], 100), (Bk[1], 200)]

    def build(async_hot):
        c = Cluster(1, cfg, copy.deepcopy(hi), use_switch=True,
                    async_hot=async_hot, max_inflight=8)
        for k, v in loads:
            c.load(k, v)
        c.snapshot_offload()
        EpochController(c, HeatTracker(window=64, decay=0.5),
                        interval=25, top_k=2)
        return c

    cs, ca = build(False), build(True)
    rs = cs.run_batch([copy.deepcopy(t) for t in txns])
    ra = ca.run_batch([copy.deepcopy(t) for t in txns])
    assert rs == ra
    assert cs.stats["migrations"] == ca.stats["migrations"] == 1
    # eviction flushed the in-flight hot group's effects to the store
    assert ca.nodes[0].store[A1] == cs.nodes[0].store[A1] \
        == 5 + sum(range(1, 7))
    np.testing.assert_array_equal(np.asarray(cs.switch.read_all()),
                                  np.asarray(ca.switch.read_all()))
    for c in (cs, ca):
        before = np.asarray(c.switch.read_all()).copy()
        c.crash_switch_and_recover()
        np.testing.assert_array_equal(before,
                                      np.asarray(c.switch.read_all()))


# ===================================================================== #
#  Capacity                                                             #
# ===================================================================== #

def test_hot_capacity_linear_in_shard_count():
    base = CFG(1).total_slots
    for n in (1, 2, 4, 8):
        assert CFG(n).total_slots == n * base
        assert CFG(n).slots_per_switch == base

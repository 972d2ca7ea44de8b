"""Batched hot-path pipeline: ``Cluster.run_batch`` must be observationally
identical to the per-txn loop — results, register state, GID assignment,
WAL-recoverable state — across engine modes and on batches containing hot,
warm, cold, and multipass transactions; and it must do so in one switch
dispatch per hot group."""
import collections
import copy

import numpy as np
import pytest

from repro.core.engine import SwitchEngine, _bucket
from repro.core.hotset import build_hot_index
from repro.core.layout import random_layout
from repro.core.packets import (ADD, ADDP, SwitchConfig, build_packets,
                                empty_packets)
from repro.db.dbms import Cluster
from repro.workloads import smallbank, tpcc, ycsb

from conftest import force_engine

SW = SwitchConfig(n_stages=16, regs_per_stage=512, max_instrs=16)


def _ycsb(variant="A", top_k=64, layout_fn=None, n=240):
    p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16,
                        variant=variant)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    kw = dict(layout_fn=layout_fn) if layout_fn else {}
    hi = build_hot_index(ycsb.traces(sample), top_k, SW, **kw)
    return ycsb.generate(np.random.default_rng(1), n, p), hi, []


def _smallbank(n=240, no_addp=False):
    p = smallbank.SmallBankParams(n_nodes=2, accounts_per_node=50,
                                  hot_per_node=4)
    sample = smallbank.generate(np.random.default_rng(0), 2000, p)
    hi = build_hot_index(smallbank.traces(sample), 16, SW)
    txns = smallbank.generate(np.random.default_rng(1), n, p)
    if no_addp:
        txns = [t for t in txns
                if all(o != ADDP for o, _, _ in t.ops)]
    loads = [(k, 100) for k in smallbank.hot_keys(p)]
    return txns, hi, loads


def _make_cluster(hi, loads, n_nodes):
    c = Cluster(n_nodes, SW, hi, use_switch=True)
    for k, v in loads:
        c.load(k, v)
    c.snapshot_offload()
    return c


def _warm_syncs(c, txns, batch_size):
    """Warm txns that find hot txns admitted before them, since the last
    warm txn, in their batch."""
    n = 0
    for i in range(0, len(txns), batch_size):
        pending = False
        for t in txns[i:i + batch_size]:
            kind = c.classify(t)
            n += kind == "warm" and pending
            pending = kind == "hot" or (pending and kind == "cold")
    return n


def _assert_equivalent(txns, hi, loads, n_nodes=4, batch_size=64):
    c1 = _make_cluster(hi, loads, n_nodes)
    c2 = _make_cluster(hi, loads, n_nodes)
    r1 = [c1.run(copy.deepcopy(t)) for t in txns]
    r2 = []
    for i in range(0, len(txns), batch_size):
        r2 += c2.run_batch([copy.deepcopy(t) for t in txns[i:i + batch_size]])
    assert r1 == r2
    np.testing.assert_array_equal(np.asarray(c1.switch.registers),
                                  np.asarray(c2.switch.registers))
    assert c1.switch.next_gid == c2.switch.next_gid
    # every counter agrees but ``warm_syncs``: only the batch path holds
    # hot txns back, so only there does a warm txn flush a pending group
    s1, s2 = collections.Counter(c1.stats), collections.Counter(c2.stats)
    assert s1.pop("warm_syncs", 0) == 0
    assert s2.pop("warm_syncs", 0) == _warm_syncs(c1, txns, batch_size)
    assert s1 == s2
    # grouping must strictly reduce dispatches whenever hot txns exist
    # (captured here: recovery below swaps in fresh engines)
    if c1.stats["hot"]:
        assert c2.switch.dispatch_count < c1.switch.dispatch_count
    # WAL-recoverable state: switch rebuilt from the nodes' logs must land
    # on the same registers in both worlds, and node recovery on the same
    # stores
    for c in (c1, c2):
        before = np.asarray(c.switch.registers).copy()
        c.crash_switch_and_recover()
        np.testing.assert_array_equal(before, np.asarray(c.switch.registers))
    # node recovery lands both worlds on the same store (value semantics:
    # defaultdict zero-materialization may differ; initial `load` values
    # predate the WAL and are out of recovery's scope in both worlds alike)
    for nid in range(n_nodes):
        c1.crash_node_and_recover(nid)
        c2.crash_node_and_recover(nid)
        s1, s2 = c1.nodes[nid].store, c2.nodes[nid].store
        for k in set(s1) | set(s2):
            assert s1.get(k, 0) == s2.get(k, 0), (nid, k)
    return c1, c2


@pytest.mark.parametrize("mode", ["auto", "serial", "affine", "staged"])
def test_ycsb_batched_equals_per_txn(mode, monkeypatch):
    force_engine(monkeypatch, mode)
    txns, hi, loads = _ycsb()
    c1, c2 = _assert_equivalent(txns, hi, loads)
    assert c1.stats["hot"] > 0 and c1.stats["cold"] > 0


@pytest.mark.parametrize("mode", ["auto", "serial", "affine", "staged"])
def test_ycsb_warm_and_multipass_batches(mode, monkeypatch):
    """Small hot index -> warm txns; random layout -> multipass packets."""
    force_engine(monkeypatch, mode)
    txns, hi, loads = _ycsb(top_k=40, layout_fn=random_layout)
    c1, c2 = _assert_equivalent(txns, hi, loads)
    assert c1.stats["warm"] > 0
    assert c1.stats["multipass"] > 0


@pytest.mark.parametrize("mode,no_addp", [("auto", False),
                                          ("serial", False),
                                          ("auto", True)],
                         ids=["auto", "serial", "auto-no_addp"])
def test_smallbank_batched_equals_per_txn(mode, no_addp, monkeypatch):
    """Full SmallBank mix: CADD constraints, ADDP read-dependent writes,
    warm txns; and the same mix with the ADDP txns left out."""
    force_engine(monkeypatch, mode)
    txns, hi, loads = _smallbank(no_addp=no_addp)
    c1, _ = _assert_equivalent(txns, hi, loads, n_nodes=2,
                               batch_size=50 if no_addp else 64)
    assert c1.stats["hot"] > 0


def test_build_packets_matches_per_txn_builder():
    txns, hi, _ = _smallbank()
    c = Cluster(2, SW, hi, use_switch=True)
    hot = [t for t in txns if c.classify(t) == "hot"][:64]
    pkts, meta = build_packets(hot, hi, SW)
    for b, t in enumerate(hot):
        pkt1, order1 = c._to_packet(t)
        for f in ("op", "stage", "reg", "operand", "nb_recircs"):
            np.testing.assert_array_equal(pkt1[f][0], pkts[f][b], err_msg=f)
        assert pkt1["is_multipass"][0] == pkts["is_multipass"][b]
        assert list(order1) == list(meta["order"][b, :len(t.ops)])
    assert meta["has_cadd"] and meta["has_addp"]


def test_build_packets_empty_and_metadata():
    txns, hi, _ = _ycsb(n=32)
    pkts, meta = build_packets([], hi, SW)
    assert pkts["op"].shape == (0, SW.max_instrs)
    assert not meta["has_cadd"] and not meta["has_addp"]
    assert not meta["addp_unsafe"]
    # an empty batch with metadata must execute as a no-op
    e = SwitchEngine(SW)
    res, ok, gids = e.execute_batch(pkts, meta)
    assert res.shape == (0, SW.max_instrs) and len(gids) == 0
    assert e.next_gid == 0 and e.dispatch_count == 0
    c = Cluster(4, SW, hi, use_switch=True)
    hot = [t for t in txns if c.classify(t) == "hot"]
    pkts, meta = build_packets(hot, hi, SW)
    assert not meta["has_cadd"] and not meta["has_addp"]
    assert not meta["addp_unsafe"]
    np.testing.assert_array_equal(meta["n_ops"],
                                  [len(t.ops) for t in hot])


def _family(name):
    """A short stream of a workload family the benchmark serves, at a
    small plane: (txns, hot index, loads, nodes, run_batch size)."""
    if name == "ycsb":
        txns, hi, loads = _ycsb()
        return txns, hi, loads, 4, 64
    if name == "smallbank":
        # the deployment's 8 nodes with 10 hot accounts each
        p = smallbank.SmallBankParams(accounts_per_node=1000)
        sample = smallbank.generate(np.random.default_rng(0), 4000, p)
        hot = smallbank.hot_keys(p)
        hi = build_hot_index(smallbank.traces(sample), len(hot), SW)
        txns = smallbank.generate(np.random.default_rng(1), 256, p)
        return txns, hi, [(k, 100) for k in hot], 8, 256
    p = tpcc.TPCCParams(n_nodes=2, n_warehouses=2)
    hot = tpcc.hot_keys(p)
    hi = build_hot_index([[(k, ADD) for k in hot]], len(hot), SW)
    txns = tpcc.generate(np.random.default_rng(11), 64, p)
    keys = {k for t in txns for k in t.keys()} | set(hot)
    init = tpcc.initial_values(np.random.default_rng(12), sorted(keys))
    return txns, hi, sorted(init.items()), 2, 64


# the engines each family's stream dispatches on under auto
FAMILY_ENGINES = {"ycsb": {"affine"},
                  "smallbank": {"serial", "staged", "affine"},
                  "tpcc": {"affine"}}


@pytest.mark.parametrize("family", sorted(FAMILY_ENGINES))
def test_auto_runs_the_engines_each_family_calls_for(family, monkeypatch):
    """``auto`` picks each hot group's engine from its opcodes: YCSB's
    READ / WRITE groups run on affine; SmallBank's CADD groups on serial,
    its ADDP groups without CADD on staged, the rest on affine; TPC-C's
    warm txns on affine, one txn per dispatch."""
    txns, hi, loads, n_nodes, batch = _family(family)
    c = _make_cluster(hi, loads, n_nodes)
    seen = []
    execute_batch = SwitchEngine.execute_batch

    def spy(self, *a, **kw):
        pb = execute_batch(self, *a, **kw)
        seen.append((pb.mode, pb.B))
        return pb

    monkeypatch.setattr(SwitchEngine, "execute_batch", spy)
    for i in range(0, len(txns), batch):
        c.run_batch([copy.deepcopy(t) for t in txns[i:i + batch]])
    assert {m for m, _ in seen} == FAMILY_ENGINES[family]
    if family == "tpcc":
        assert {b for _, b in seen} == {1}


def test_one_dispatch_per_hot_group():
    """A batch of hot-only txns commits in exactly ONE engine dispatch."""
    txns, hi, loads = _ycsb(n=600)
    c = _make_cluster(hi, loads, 4)
    hot = [t for t in txns if c.classify(t) == "hot"][:256]
    assert len(hot) == 256
    before = c.switch.dispatch_count
    res = c.run_batch(hot)
    assert c.switch.dispatch_count - before == 1
    assert c.stats["commits"] == 256
    assert all(r is not None for r in res)
    # per-txn loop pays 256 dispatches for the same work
    c2 = _make_cluster(hi, loads, 4)
    for t in hot:
        c2.run(copy.deepcopy(t))
    assert c2.switch.dispatch_count == 256


def _interleaved_unsafe(arrangement):
    """Hot txns from an 'S'(safe)/'U'(multipass-ADDP) pattern, all on one
    node.  The unsafe txn reads a stage-1 tuple and ADDPs it into a
    stage-0 tuple — the same-or-later-stage source that forces the serial
    engine."""
    from repro.core.hotset import HotIndex
    from repro.core.layout import Placement
    from repro.core.packets import ADD, READ
    from repro.db.txn import Txn, key_of
    A, B, C_ = key_of(0, 0), key_of(0, 1), key_of(0, 2)
    hi = HotIndex(Placement(slot={A: (0, 0), B: (1, 0), C_: (2, 0)}))
    txns = []
    for i, ch in enumerate(arrangement):
        if ch == "S":
            txns.append(Txn("safe", [(ADD, A, i + 1), (ADD, B, 2 * i + 1),
                                     (READ, C_, 0)], 0))
        else:
            txns.append(Txn("unsafe", [(READ, B, 0), (ADDP, A, 0)], 0))
    loads = [(A, 7), (B, 11), (C_, 13)]
    return txns, hi, loads


@pytest.mark.parametrize("mode", ["auto", "serial"])
@pytest.mark.parametrize("arrangement",
                         ["USSU", "SSUSS", "USSUSSSU", "UUSSU"])
def test_group_split_equals_per_txn(arrangement, mode, monkeypatch):
    """A hot group with multipass-ADDP txns at head/middle/tail matches the
    per-txn loop exactly — results, registers, GIDs, WAL recovery — in
    every mode that can run such packets (the group splits at unsafe txns
    either way; auto runs the safe parts on affine, serial runs every
    part on serial)."""
    force_engine(monkeypatch, mode)
    txns, hi, loads = _interleaved_unsafe(arrangement)
    _assert_equivalent(txns, hi, loads, n_nodes=1, batch_size=len(txns))


def test_group_split_keeps_safe_runs_vectorized():
    """Under auto mode the batch splits at unsafe txns: one dispatch per
    contiguous run (not per txn), safe runs on the vectorized affine
    engine, unsafe runs on the serial oracle."""
    arrangement = "USSUSSSU"                       # runs: U|SS|U|SSS|U
    txns, hi, loads = _interleaved_unsafe(arrangement)
    c = _make_cluster(hi, loads, 1)
    d0 = c.switch.dispatch_count           # fixture loads dispatch too
    modes = []
    orig = c.switch.execute_batch

    def spy(pkts, meta=None, **kw):
        pb = orig(pkts, meta, **kw)
        modes.append(pb.mode)
        return pb

    c.switch.execute_batch = spy
    res = c.run_batch(txns)
    assert all(r is not None for r in res)
    assert c.switch.dispatch_count - d0 == 5       # runs, not 8 txns
    assert modes == ["serial", "affine", "serial", "affine", "serial"]
    # per-txn world pays one dispatch per txn
    c2 = _make_cluster(hi, loads, 1)
    d0 = c2.switch.dispatch_count
    for t in _interleaved_unsafe(arrangement)[0]:
        c2.run(t)
    assert c2.switch.dispatch_count - d0 == len(arrangement)


def test_bucket_padding_preserves_results_and_gids():
    """Non-power-of-two batch sizes pad with NOP rows: same results as the
    unpadded serial oracle, GIDs only for real packets."""
    assert [_bucket(b) for b in (1, 2, 3, 5, 64, 65)] == \
        [1, 2, 4, 8, 64, 128]
    rng = np.random.default_rng(0)
    cfg = SwitchConfig(n_stages=4, regs_per_stage=8, max_instrs=3)
    for B in (1, 3, 5, 13):
        p = empty_packets(B, cfg)
        p["op"] = rng.integers(0, 4, (B, 3)).astype(np.int32)
        p["stage"] = rng.integers(0, 4, (B, 3)).astype(np.int32)
        p["reg"] = rng.integers(0, 8, (B, 3)).astype(np.int32)
        p["operand"] = rng.integers(-20, 20, (B, 3)).astype(np.int32)
        regs0 = rng.integers(0, 50, (4, 8))
        e1, e2 = SwitchEngine(cfg, regs0), SwitchEngine(cfg, regs0)
        r1, ok1, g1 = e1.execute(p, mode="serial")
        res, ok, g2 = e2.execute_batch(p, mode="affine")
        assert np.asarray(res).shape == (B, 3)
        np.testing.assert_array_equal(r1, np.asarray(res))
        np.testing.assert_array_equal(g1, g2)
        assert e2.next_gid == e1.next_gid
        np.testing.assert_array_equal(e1.read_all(), e2.read_all())

"""Async device-resident hot path (double-buffered dispatch + lazy result
plane): the async pipeline must be byte-identical to the synchronous path
— results, registers, GIDs, WAL-recoverable state — across engine modes,
warm/cold interleaves, and migrations crossing undrained batches; plus
dispatch-cache stability, donation safety after exceptions, deterministic
drain ordering, and the EpochController's cost-benefit migration gate."""
import copy

import numpy as np
import pytest

import test_batch as TB
from repro.core.engine import (PendingBatch, SwitchEngine, _DISPATCH_CACHE,
                               _bucket)
from repro.core.heat import HeatTracker
from repro.core.hotset import HotIndex
from repro.core.layout import Placement, random_layout
from repro.core.packets import (ADD, CADD, READ, WRITE, SwitchConfig,
                                build_packets, empty_packets, result_plane)
from repro.db.dbms import Cluster, LazyResults
from repro.db.migrate import EpochController
from repro.db.txn import Txn, key_of

from conftest import force_engine

SW = TB.SW


def _make_pair(hi, loads, n_nodes, max_inflight=2):
    """(sync, async) cluster twins over the same placement and loads."""
    cs = Cluster(n_nodes, SW, hi, use_switch=True)
    ca = Cluster(n_nodes, SW, hi, use_switch=True, async_hot=True,
                 max_inflight=max_inflight)
    for c in (cs, ca):
        for k, v in loads:
            c.load(k, v)
        c.snapshot_offload()
    return cs, ca


def _assert_async_equals_sync(txns, hi, loads, n_nodes=4, batch_size=64,
                              max_inflight=2):
    cs, ca = _make_pair(hi, loads, n_nodes, max_inflight)
    rs, ra = [], []
    for i in range(0, len(txns), batch_size):
        chunk = txns[i:i + batch_size]
        rs += cs.run_batch([copy.deepcopy(t) for t in chunk])
        ra_part = ca.run_batch([copy.deepcopy(t) for t in chunk])
        assert isinstance(ra_part, LazyResults)
        ra.append(ra_part)
    # LazyResults == list drains on comparison
    flat_a = [r for part in ra for r in part]
    assert rs == flat_a
    assert not ca._inflight
    np.testing.assert_array_equal(np.asarray(cs.switch.registers),
                                  np.asarray(ca.switch.registers))
    assert cs.switch.next_gid == ca.switch.next_gid
    assert cs.stats == ca.stats
    # identical recovery: switch rebuilt from WALs, node stores replayed
    for c in (cs, ca):
        before = np.asarray(c.switch.registers).copy()
        c.crash_switch_and_recover()
        np.testing.assert_array_equal(before,
                                      np.asarray(c.switch.registers))
    for nid in range(n_nodes):
        cs.crash_node_and_recover(nid)
        ca.crash_node_and_recover(nid)
        s1, s2 = cs.nodes[nid].store, ca.nodes[nid].store
        for k in set(s1) | set(s2):
            assert s1.get(k, 0) == s2.get(k, 0), (nid, k)
    return cs, ca


@pytest.mark.parametrize("mode", ["auto", "serial", "affine", "staged"])
def test_async_equals_sync_ycsb(mode, monkeypatch):
    force_engine(monkeypatch, mode)
    txns, hi, loads = TB._ycsb()
    cs, _ = _assert_async_equals_sync(txns, hi, loads)
    assert cs.stats["hot"] > 0 and cs.stats["cold"] > 0


@pytest.mark.parametrize("mode", ["auto", "serial"])
def test_async_equals_sync_warm_and_multipass(mode, monkeypatch):
    """Warm interleaves force mid-batch drains; random layout forces
    multipass packets."""
    force_engine(monkeypatch, mode)
    txns, hi, loads = TB._ycsb(top_k=40, layout_fn=random_layout)
    cs, _ = _assert_async_equals_sync(txns, hi, loads)
    assert cs.stats["warm"] > 0 and cs.stats["multipass"] > 0


def test_async_equals_sync_smallbank():
    """CADD constraints + ADDP read-dependent writes + warm txns."""
    txns, hi, loads = TB._smallbank()
    cs, _ = _assert_async_equals_sync(txns, hi, loads, n_nodes=2)
    assert cs.stats["hot"] > 0


def test_async_equals_sync_deep_inflight():
    """A large in-flight window (many undrained groups) stays exact."""
    txns, hi, loads = TB._ycsb(n=192)
    _assert_async_equals_sync(txns, hi, loads, batch_size=16,
                              max_inflight=8)


# ------------------------------------------------- lazy result plane ----

def _all_hot(hi_txns=96):
    txns, hi, loads = TB._ycsb(n=600)
    probe = Cluster(4, SW, hi, use_switch=True)
    hot = [t for t in txns if probe.classify(t) == "hot"][:hi_txns]
    assert len(hot) == hi_txns
    return hot, hi, loads


def test_lazy_results_defer_materialization():
    hot, hi, loads = _all_hot()
    _, ca = _make_pair(hi, loads, 4, max_inflight=8)
    res = ca.run_batch(hot)
    # dispatched (commit-on-send: sends logged, commits counted) ...
    assert ca.stats["commits"] == len(hot)
    sends = sum(e.kind == "switch_send" for n in ca.nodes for e in n.wal)
    assert sends == len(hot)
    # ... but nothing materialized yet: the result plane is lazy
    assert ca._inflight
    assert not any(e.kind == "switch_result"
                   for n in ca.nodes for e in n.wal)
    # first read drains everything, in dispatch order
    assert res[0] is not None
    assert not ca._inflight
    rescnt = sum(e.kind == "switch_result" for n in ca.nodes for e in n.wal)
    assert rescnt == len(hot)


def test_inflight_window_bounded():
    """Double buffering: at most max_inflight undrained handles exist;
    older groups are drained as newer ones are dispatched."""
    hot, hi, loads = _all_hot()
    _, ca = _make_pair(hi, loads, 4, max_inflight=2)
    for i in range(0, len(hot), 16):
        ca.run_batch(hot[i:i + 16])
        assert len(ca._inflight) <= 2
    ca.drain()
    assert not ca._inflight


def test_warm_txn_is_a_drain_point():
    """A warm txn touches hot keys: every outstanding handle must be
    materialized (switch_result logged) before the warm txn's own switch
    send.  Warm txns are identifiable in the WAL as the tids that log
    both a switch_send AND a commit (their 2PC'd cold part)."""
    txns, hi, loads = TB._ycsb(top_k=40, layout_fn=random_layout)
    _, ca = _make_pair(hi, loads, 4, max_inflight=8)
    list(ca.run_batch(txns))
    assert ca.stats["warm"] > 0
    warm_checked = 0
    for n in ca.nodes:
        committed = {e.tid for e in n.wal if e.kind == "commit"}
        unresulted = set()
        for e in n.wal:
            if e.kind == "switch_send":
                if e.tid in committed:          # a warm txn's send:
                    assert not unresulted, (n.id, e.tid, unresulted)
                    warm_checked += 1
                unresulted.add(e.tid)
            elif e.kind == "switch_result":
                unresulted.discard(e.tid)
    assert warm_checked > 0


def test_drain_ordering_deterministic():
    """Interleaved hot/warm/cold admission drains in dispatch order and
    twin runs produce identical WALs (kinds, tids, gids, results)."""
    txns, hi, loads = TB._ycsb(top_k=40, layout_fn=random_layout, n=160)
    walseqs = []
    for _ in range(2):
        _, ca = _make_pair(hi, loads, 4, max_inflight=3)
        res = ca.run_batch([copy.deepcopy(t) for t in txns])
        list(res)                                   # drain
        walseqs.append([(n.id, e.kind, e.tid, e.payload.get("gid"),
                         e.payload.get("results"))
                        for n in ca.nodes for e in n.wal])
        # switch_result gids are monotone per node (drain = FIFO)
        for n in ca.nodes:
            gids = [e.payload["gid"] for e in n.wal
                    if e.kind == "switch_result"]
            assert gids == sorted(gids)
    assert walseqs[0] == walseqs[1]


# ------------------------------------ migration x undrained batches ----

def _drift_setup():
    """Initial hot set {A1, A2}; cold keys B* get hammered so the next
    epoch's top-k flips to them."""
    A1, A2 = key_of(0, 0), key_of(0, 1)
    Bk = [key_of(0, 10 + i) for i in range(2)]
    hi = HotIndex(Placement(slot={A1: (0, 0), A2: (1, 0)}))
    hot_txns = [Txn("h", [(ADD, A1, i + 1), (READ, A2, 0)], 0)
                for i in range(6)]
    cold_txns = [Txn("c", [(ADD, Bk[i % 2], 7)], 0) for i in range(30)]
    loads = [(A1, 5), (A2, 11), (Bk[0], 100), (Bk[1], 200)]
    return hi, hot_txns + cold_txns, loads, Bk


def _attach_controller(c, interval, **kw):
    return EpochController(c, HeatTracker(window=64, decay=0.5),
                           interval=interval, top_k=2, **kw)


def test_migration_crosses_undrained_batch():
    """The controller fires while a freshly dispatched hot group is still
    in flight; migrate() drains it, evicts the post-group register values
    and recovery stays exact — identical to the sync world."""
    hi, txns, loads, Bk = _drift_setup()
    cs, ca = _make_pair(hi, loads, 1, max_inflight=8)
    ctl_s = _attach_controller(cs, interval=25)
    ctl_a = _attach_controller(ca, interval=25)
    rs = cs.run_batch([copy.deepcopy(t) for t in txns])
    ra = ca.run_batch([copy.deepcopy(t) for t in txns])
    assert rs == ra
    assert cs.stats["migrations"] == ca.stats["migrations"] == 1
    assert ctl_s.plans == ctl_a.plans
    # the migrated-to placement covers the hammered keys
    assert set(Bk) <= set(ca.hot_index.placement.slot)
    # eviction wrote the hot group's ADD effects back to the node store
    assert ca.nodes[0].store[key_of(0, 0)] == \
        cs.nodes[0].store[key_of(0, 0)] == 5 + sum(range(1, 7))
    np.testing.assert_array_equal(np.asarray(cs.switch.registers),
                                  np.asarray(ca.switch.registers))
    for c in (cs, ca):
        before = np.asarray(c.switch.registers).copy()
        c.crash_switch_and_recover()
        np.testing.assert_array_equal(before,
                                      np.asarray(c.switch.registers))


# --------------------------------------------- engine-level contracts ----

def test_pending_batch_lazy_and_backcompat():
    rng = np.random.default_rng(0)
    cfg = SwitchConfig(n_stages=4, regs_per_stage=8, max_instrs=4)
    B = 6
    p = empty_packets(B, cfg)
    p["op"] = rng.integers(0, 5, (B, 4)).astype(np.int32)   # NOP..CADD
    p["stage"] = rng.integers(0, 4, (B, 4)).astype(np.int32)
    p["reg"] = rng.integers(0, 8, (B, 4)).astype(np.int32)
    p["operand"] = rng.integers(-5, 20, (B, 4)).astype(np.int32)
    regs0 = rng.integers(0, 50, (4, 8))
    e1, e2 = SwitchEngine(cfg, regs0), SwitchEngine(cfg, regs0)
    ref, ok_ref, g1 = e1.execute(p, mode="serial")
    pb = e2.execute_batch(p, mode="serial")
    assert isinstance(pb, PendingBatch) and not pb.ready()
    # back-compat tuple unpacking yields device slices
    res_d, ok_d, g2 = pb
    np.testing.assert_array_equal(np.asarray(res_d), ref)
    np.testing.assert_array_equal(g1, g2)
    # lazy materialization reconstructs base + compact == full plane
    np.testing.assert_array_equal(pb.results_np(), ref)
    assert pb.ready()
    np.testing.assert_array_equal(pb.ok_np(), ok_ref)
    np.testing.assert_array_equal(e1.read_all(), e2.read_all())


def test_direct_engine_deep_defer_stays_correct():
    """A DIRECT engine user issuing more deferred dispatches than the
    staging pool holds must still get exact results: `_submit` joins the
    oldest job before a staging buffer could be recycled under it."""
    txns, hi, _ = TB._ycsb(n=200)
    probe = Cluster(4, SW, hi, use_switch=True)
    hot = [t for t in txns if probe.classify(t) == "hot"][:60]
    e_async = SwitchEngine(SW, async_dispatch=True)    # pool = default 4
    e_ref = SwitchEngine(SW)
    handles = []
    for i in range(0, 60, 6):                          # 10 deferred groups
        pkts, meta = build_packets(hot[i:i + 6], hi, SW)
        handles.append(e_async.execute_batch(pkts, meta, defer=True))
    for i, pb in enumerate(handles):                   # drain afterwards
        pkts, meta = build_packets(hot[i * 6:i * 6 + 6], hi, SW)
        ref = e_ref.execute_batch(pkts, meta)
        np.testing.assert_array_equal(pb.results_np(), ref.results_np())
        np.testing.assert_array_equal(pb.gids, ref.gids)
    np.testing.assert_array_equal(e_async.read_all(), e_ref.read_all())


def test_result_plane_split():
    cfg = SwitchConfig(n_stages=2, regs_per_stage=4, max_instrs=4)
    p = empty_packets(2, cfg)
    p["op"][0] = [WRITE, READ, ADD, 0]
    p["operand"][0] = [9, 0, 3, 0]
    p["op"][1] = [CADD, WRITE, 0, 0]
    p["operand"][1] = [-1, 4, 0, 0]
    base, idx = result_plane(p)
    np.testing.assert_array_equal(base, [[9, 0, 0, 0], [0, 4, 0, 0]])
    np.testing.assert_array_equal(idx, [1, 2, 4])   # READ, ADD, CADD


def test_dispatch_cache_stable_across_bucket_boundaries():
    """Steady-state execute_batch calls across batch-size buckets reuse
    compiled executables: the cache stops growing after warmup while
    dispatch_count keeps counting."""
    txns, hi, _ = TB._ycsb(n=96)
    probe = Cluster(4, SW, hi, use_switch=True)
    hot = [t for t in txns if probe.classify(t) == "hot"][:40]
    sizes = (3, 5, 8, 13, 19)                 # buckets 4, 8, 8, 16, 32
    e = SwitchEngine(SW)
    for s in sizes:                           # warm every (Bp, Mp) pair
        e.execute_batch(*build_packets(hot[:s], hi, SW))
    cached = len(_DISPATCH_CACHE)
    before = e.dispatch_count
    for _ in range(3):
        for s in sizes:
            e.execute_batch(*build_packets(hot[:s], hi, SW))
    assert len(_DISPATCH_CACHE) == cached
    assert e.dispatch_count == before + 3 * len(sizes)
    assert _bucket(13) == 16 and _bucket(19) == 32


def test_donated_registers_survive_rejected_dispatch():
    """A dispatch rejected before execution (explicit-mode validation)
    must not have donated the live register buffer: the engine's state
    stays readable and the next dispatch works."""
    cfg = SwitchConfig(n_stages=2, regs_per_stage=4, max_instrs=2)
    e = SwitchEngine(cfg)
    p = empty_packets(1, cfg)
    p["op"][0, 0] = CADD
    p["operand"][0, 0] = 5
    before = e.read_all().copy()
    with pytest.raises(ValueError):
        e.execute_batch(p, mode="affine")     # CADD rejected pre-dispatch
    np.testing.assert_array_equal(e.read_all(), before)   # not donated
    res, ok, _ = e.execute(p, mode="serial")  # engine still serviceable
    assert res[0, 0] == 5


def test_init_registers_copies_for_donation_safety():
    """Caller-held arrays are never aliased by the donated buffer."""
    cfg = SwitchConfig(n_stages=2, regs_per_stage=4, max_instrs=2)
    vals = np.arange(8, dtype=np.int32).reshape(2, 4)
    e = SwitchEngine(cfg, vals)
    p = empty_packets(1, cfg)
    p["op"][0, 0] = ADD
    p["operand"][0, 0] = 100
    e.execute(p)                              # donates the register buffer
    np.testing.assert_array_equal(vals.reshape(-1),
                                  np.arange(8))            # caller intact


# --------------------------------------- cost-benefit migration gate ----

def test_gate_off_is_default_behavior():
    hi, txns, loads, _ = _drift_setup()
    c1 = Cluster(1, SW, hi, use_switch=True)
    c2 = Cluster(1, SW, hi, use_switch=True)
    for c in (c1, c2):
        for k, v in loads:
            c.load(k, v)
        c.snapshot_offload()
    ctl1 = _attach_controller(c1, interval=25)                 # default off
    ctl2 = _attach_controller(c2, interval=25, gate_t_reconfig=0.0)
    r1 = c1.run_batch([copy.deepcopy(t) for t in txns])
    r2 = c2.run_batch([copy.deepcopy(t) for t in txns])
    assert r1 == r2
    assert ctl1.plans == ctl2.plans and ctl1.gated == ctl2.gated == 0
    assert c1.stats == c2.stats


def test_gate_blocks_unprofitable_migration():
    """A pause costing more txns than the new placement would win skips
    the migration (hysteresis): placement and registers stay put."""
    hi, txns, loads, _ = _drift_setup()
    c = Cluster(1, SW, hi, use_switch=True)
    for k, v in loads:
        c.load(k, v)
    c.snapshot_offload()
    ctl = _attach_controller(c, interval=25, gate_t_reconfig=1.0,
                             gate_txn_rate=1e9)
    c.run_batch(txns)
    assert ctl.gated >= 1
    assert c.stats["migrations"] == 0
    assert c.hot_index is hi                     # index never swapped


def test_gate_allows_profitable_migration():
    hi, txns, loads, Bk = _drift_setup()
    c = Cluster(1, SW, hi, use_switch=True)
    for k, v in loads:
        c.load(k, v)
    c.snapshot_offload()
    ctl = _attach_controller(c, interval=25, gate_t_reconfig=1e-9,
                             gate_txn_rate=1.0)
    c.run_batch(txns)
    assert ctl.gated == 0 and c.stats["migrations"] == 1
    assert set(Bk) <= set(c.hot_index.placement.slot)


def test_projected_gain_sign():
    hi, txns, loads, Bk = _drift_setup()
    c = Cluster(1, SW, hi, use_switch=True)
    ctl = _attach_controller(c, interval=25)
    tr = ctl.tracker
    for t in txns:
        tr.observe_trace([(k, o) for o, k, _ in t.ops])
    traces = tr.window_traces()
    new = Placement(slot={Bk[0]: (0, 0), Bk[1]: (1, 0)})
    assert ctl.projected_gain(new, traces) > 0        # covers the hammering
    same = Placement(slot=dict(hi.placement.slot))
    assert ctl.projected_gain(same, traces) == 0
    assert ctl.projected_gain(new, []) == 0

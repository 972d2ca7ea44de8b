"""In-network read tier (tentpole, ISSUE 8): the switch-served read path
— ``Cluster.read_batch`` / ``Cluster.scan`` over ``execute_reads`` /
``execute_scan`` and the scan-prune kernels — locked down by a
differential harness against a plain-dict oracle (tests/oracle.py).

Pins:
  * randomized mixed read/write/scan streams are byte-identical to the
    oracle across engine modes x sync/async x N in {1, 2, 4} switches
    (tier-1 runs the auto/serial corner; the full matrix is @slow);
  * reads on an async cluster need NO drain: the FIFO dispatch thread
    orders the gather after every in-flight write group while their
    result planes stay device-resident (``_inflight`` untouched);
  * reads stay correct mid-migration (partial availability: evicted
    keys from home stores, live hot keys raise ``SwitchUnavailable``),
    after crash recovery, after standby failover, and for keys a
    completed migration evicted to the cold tier;
  * property tests (hypothesis when installed, the deterministic
    fallback sweep otherwise): read-after-write-prefix equals the
    oracle; the scan-prune/top-k kernels equal their numpy refs for
    arbitrary predicates and selectivities including the empty-result
    and all-pass edges.
"""
import copy

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from conftest import force_engine
from oracle import OracleDB
from repro.core.hotset import build_hot_index
from repro.core.packets import (ADD, CADD, READ, WRITE, SwitchConfig,
                                build_read_packets)
from repro.db.dbms import Cluster
from repro.db.faults import FaultPlan, SimulatedCrash, SwitchUnavailable
from repro.db.migrate import migrate
from repro.db.txn import Txn, key_of, node_of

S, R, MI = 4, 32, 8
N_NODES = 4
MODES = ["auto", "serial", "affine", "staged"]


def CFG(n=1):
    return SwitchConfig(n_stages=S, regs_per_stage=R, max_instrs=MI,
                        n_switches=n)


def _fixture(n_switches=1, async_hot=False, seed=0, n_hot_per_node=12,
             **kw):
    """(cluster, oracle, hot_keys, cold_keys) twins over one placement."""
    cfg = CFG(n_switches)
    hot = [key_of(nd, i) for nd in range(N_NODES)
           for i in range(n_hot_per_node)]
    hi = build_hot_index([[(k, "W")] for k in hot], len(hot), cfg)
    assert set(hi.placement.slot) == set(hot)
    c = Cluster(N_NODES, cfg, hi, async_hot=async_hot, **kw)
    o = OracleDB()
    cold = [key_of(nd, 500 + i) for nd in range(N_NODES) for i in range(6)]
    rng = np.random.default_rng(seed)
    for k in hot + cold:
        v = int(rng.integers(0, 100))
        c.load(k, v)
        o.load(k, v)
    c.snapshot_offload()
    return c, o, hot, cold


def _mixed_txns(rng, hot, cold, n, allow_cadd=True):
    """Write txns in the three tiers: all-hot (optionally CADD — the
    abort-free switch op), all-cold, and warm (one hot + one cold).
    CADD is restricted to all-hot txns: its cold-path semantics is an
    abort, not a clamp, so mixed streams keep WRITE/ADD there."""
    txns = []
    for _ in range(n):
        r = rng.random()
        if r < 0.55:
            ks = rng.choice(hot, size=int(rng.integers(1, 4)),
                            replace=False)
            ops = []
            for k in ks:
                op = int(rng.choice([WRITE, ADD, CADD] if allow_cadd
                                    else [WRITE, ADD]))
                v = int(rng.integers(0, 60)) if op == WRITE \
                    else int(rng.integers(-30, 40))
                ops.append((op, int(k), v))
        elif r < 0.8:
            ks = rng.choice(cold, size=int(rng.integers(1, 3)),
                            replace=False)
            ops = [(int(rng.choice([WRITE, ADD])), int(k),
                    int(rng.integers(-20, 60))) for k in ks]
        else:
            ops = [(int(rng.choice([WRITE, ADD])), int(rng.choice(hot)),
                    int(rng.integers(-20, 60))),
                   (int(rng.choice([WRITE, ADD])), int(rng.choice(cold)),
                    int(rng.integers(-20, 60)))]
        txns.append(Txn("t", ops, node_of(ops[0][1])))
    return txns


def _differential_stream(c, o, hot, cold, seed=1, n_steps=24,
                         allow_cadd=True):
    """Drive both worlds with one randomized stream, interleaving point
    reads, batch reads and scans (with and without limit) between write
    batches; every read-class output must be byte-identical."""
    rng = np.random.default_rng(seed)
    all_keys = hot + cold
    for step in range(n_steps):
        txns = _mixed_txns(rng, hot, cold, int(rng.integers(1, 5)),
                           allow_cadd)
        c.run_batch([copy.deepcopy(t) for t in txns])
        for t in txns:
            o.apply_txn(t)
        if step % 2 == 0:
            ks = rng.choice(all_keys, size=10, replace=False)
            assert c.read_batch(ks) == o.read_batch(ks)
        if step % 3 == 0:
            k = int(rng.choice(all_keys))
            assert c.read(k) == o.read(k)
        if step % 4 == 0:
            lo = int(rng.integers(-10, 60))
            hi_ = lo + int(rng.integers(0, 90))
            assert c.scan(lo, hi_) == o.scan(lo, hi_, hot)
            lim = int(rng.integers(1, 7))
            assert c.scan(lo, hi_, keys=all_keys, limit=lim) == \
                o.scan(lo, hi_, all_keys, lim)
    c.drain()
    assert c.read_batch(all_keys) == o.read_batch(all_keys)


# ===================================================================== #
#  Differential matrix: modes x sync/async x shard counts               #
# ===================================================================== #

@pytest.mark.parametrize("async_hot", [False, True])
@pytest.mark.parametrize("n_switches", [1, 2])
@pytest.mark.parametrize("mode", ["auto", "serial"])
def test_mixed_stream_matches_oracle(n_switches, async_hot, mode,
                                     monkeypatch):
    force_engine(monkeypatch, mode)
    c, o, hot, cold = _fixture(n_switches, async_hot)
    _differential_stream(c, o, hot, cold)


@pytest.mark.slow
@pytest.mark.parametrize("async_hot", [False, True])
@pytest.mark.parametrize("n_switches", [1, 2, 4])
@pytest.mark.parametrize("mode", MODES)
def test_mixed_stream_matches_oracle_full_matrix(n_switches, async_hot,
                                                 mode, monkeypatch):
    force_engine(monkeypatch, mode)
    c, o, hot, cold = _fixture(n_switches, async_hot, seed=2)
    # affine and staged reject CADD; auto and serial keep it in the mix
    _differential_stream(c, o, hot, cold, seed=3, n_steps=32,
                         allow_cadd=mode in ("auto", "serial"))


def test_async_reads_do_not_drain_inflight_writes():
    """The key async-compatibility pin: a read observes every deferred
    write group via dispatch-thread FIFO order, while the groups' result
    planes stay undrained on the device (``_inflight`` untouched)."""
    c, o, hot, cold = _fixture(async_hot=True, max_inflight=4)
    rng = np.random.default_rng(5)
    for _ in range(3):
        txns = _mixed_txns(rng, hot, cold, 3, allow_cadd=False)
        # hot-only batches keep the groups parked undrained
        txns = [t for t in txns
                if all(k in set(hot) for _, k, _ in t.ops)] or \
            [Txn("t", [(WRITE, hot[0], 7)], node_of(hot[0]))]
        c.run_batch([copy.deepcopy(t) for t in txns])
        for t in txns:
            o.apply_txn(t)
    assert c._inflight, "fixture failed to park undrained groups"
    n_parked = len(c._inflight)
    assert c.read_batch(hot) == o.read_batch(hot)
    assert c._inflight and len(c._inflight) == n_parked, \
        "read_batch drained the in-flight window"
    assert c.stats["switch_reads"] == len(hot)
    c.drain()


def test_read_batch_routes_and_counts():
    c, o, hot, cold = _fixture()
    got = c.read_batch(hot[:5] + cold[:3])
    assert got == o.read_batch(hot[:5] + cold[:3])
    assert c.stats["switch_reads"] == 5
    assert c.stats["store_reads"] == 3
    assert c.switch.read_dispatch_count == 1     # one gather per batch
    # reads are non-durable by construction: no WAL growth, no GID burn
    wal_before = sum(len(n.wal) for n in c.nodes)
    gid_before = c.switch.next_gid
    c.read_batch(hot)
    c.scan(0, 1000)
    assert sum(len(n.wal) for n in c.nodes) == wal_before
    assert c.switch.next_gid == gid_before


def test_scan_prunes_shipped_rows():
    """The pruning contract: a selective scan ships the kernel's cap-row
    compaction, never the full hot set."""
    c, o, hot, cold = _fixture()
    # value layout: exactly 4 hot keys land in [1000, 1003]
    for i, k in enumerate(hot):
        v = 1000 + i if i < 4 else i
        c.run_batch([Txn("t", [(WRITE, k, v)], node_of(k))])
        o.apply([(WRITE, k, v)])
    out = c.scan(1000, 1003)
    assert out == o.scan(1000, 1003, hot)
    assert len(out) == 4
    # shipped <= first-pass cap (16), far below the 48-key hot set
    assert c.stats["scan_rows_shipped"] <= 16 < len(hot)


# ===================================================================== #
#  Reads under migration / crash / failover                             #
# ===================================================================== #

def _rotated_index(hot, cfg, drop=8):
    """A same-shape re-placement that evicts ``drop`` keys."""
    keep = hot[drop:]
    return build_hot_index([[(k, "W")] for k in keep], len(keep), cfg), \
        hot[:drop]


def test_reads_mid_migration_partial_availability():
    c, o, hot, cold = _fixture(
        fault_plan=FaultPlan("mid_migration"))
    _differential_stream(c, o, hot, cold, n_steps=6)
    new_hi, evicted = _rotated_index(hot, CFG())
    with pytest.raises(SimulatedCrash):
        migrate(c, new_hi)
    # evicted keys: authoritative in home stores, still byte-identical
    assert c.read_batch(evicted + cold) == o.read_batch(evicted + cold)
    assert c.read(evicted[0]) == o.read(evicted[0])
    # any surviving hot key needs live registers -> unavailable
    with pytest.raises(SwitchUnavailable):
        c.read_batch([hot[-1]])
    with pytest.raises(SwitchUnavailable):
        c.scan(0, 10**6)
    # scans over the readable subset keep working while down
    assert c.scan(0, 10**6, keys=evicted + cold) == \
        o.scan(0, 10**6, evicted + cold)
    # recovery abandons the migration: full service, full equivalence
    c.recover_switch()
    assert c.read_batch(hot + cold) == o.read_batch(hot + cold)
    _differential_stream(c, o, hot, cold, seed=9, n_steps=4)


def test_reads_after_completed_migration_serve_evicted_from_stores():
    c, o, hot, cold = _fixture()
    _differential_stream(c, o, hot, cold, n_steps=6)
    new_hi, evicted = _rotated_index(hot, CFG())
    migrate(c, new_hi)
    # evicted keys are cold now: store-served, values carried over
    before = c.stats["store_reads"]
    assert c.read_batch(evicted) == o.read_batch(evicted)
    assert c.stats["store_reads"] - before == len(evicted)
    assert c.read_batch(hot + cold) == o.read_batch(hot + cold)
    assert c.scan(0, 10**6) == o.scan(0, 10**6, hot[len(evicted):])


def test_reads_after_crash_recovery_and_failover():
    for kw, recover in ((dict(), lambda c: c.crash_switch_and_recover()),
                        (dict(standby=True), lambda c: c.fail_over())):
        c, o, hot, cold = _fixture(checkpoint_interval=8, **kw)
        _differential_stream(c, o, hot, cold, n_steps=8)
        recover(c)
        assert c.read_batch(hot + cold) == o.read_batch(hot + cold)
        assert c.scan(0, 10**6) == o.scan(0, 10**6, hot)
        _differential_stream(c, o, hot, cold, seed=11, n_steps=4)


# ===================================================================== #
#  Property tests (hypothesis when installed, fallback sweep otherwise) #
# ===================================================================== #

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_read_after_write_prefix_matches_oracle(seed):
    """Any seeded write prefix, then a full read sweep: cluster ==
    oracle on every committed value (the read path can never observe a
    torn or stale register)."""
    c, o, hot, cold = _fixture(seed=seed % 17, n_hot_per_node=6)
    rng = np.random.default_rng(seed)
    txns = _mixed_txns(rng, hot, cold, int(rng.integers(1, 12)))
    c.run_batch([copy.deepcopy(t) for t in txns])
    for t in txns:
        o.apply_txn(t)
    assert c.read_batch(hot + cold) == o.read_batch(hot + cold)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(0, 100))
def test_scan_prune_kernel_matches_ref(seed, selectivity):
    """The pallas scan-prune kernel equals the numpy ref for arbitrary
    predicates/selectivities — ``selectivity`` spans the empty-result
    (0) and all-pass (100) edges by construction."""
    from repro.kernels.switch_txn.ref import scan_prune_ref, scan_topk_ref
    from repro.kernels.switch_txn.switch_txn import scan_prune_call

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 300))
    src = rng.integers(-1000, 1000, n).astype(np.int32)
    if selectivity == 0:
        lo, hi = 2000, 3000                       # empty by construction
    elif selectivity == 100:
        lo, hi = -1000, 1000                      # all pass
    else:
        lo = int(np.percentile(src, max(0, 50 - selectivity // 2)))
        hi = int(np.percentile(src, min(100, 50 + selectivity // 2)))
    cap = int(rng.integers(1, n + 8))
    vals, idx, agg = scan_prune_call(
        np.asarray(src), lo, hi, cap=cap, chunk=64)
    rv, ri, ra = scan_prune_ref(src, lo, hi, cap)
    np.testing.assert_array_equal(np.asarray(vals), rv)
    np.testing.assert_array_equal(np.asarray(idx), ri)
    np.testing.assert_array_equal(np.asarray(agg), ra)
    k = int(rng.integers(1, n + 1))
    import jax.numpy as jnp
    from repro.kernels.switch_txn import ops as ktx
    tv, ti, tc = ktx.scan_topk(jnp.asarray(src).reshape(1, -1),
                               jnp.arange(n, dtype=jnp.int32), lo, hi, k=k)
    rv, ri, rc = scan_topk_ref(src, lo, hi, k)
    assert int(tc) == rc
    t = min(rc, k)
    np.testing.assert_array_equal(np.asarray(tv)[:t], rv[:t])
    np.testing.assert_array_equal(np.asarray(ti)[:t], ri[:t])

"""Durability subsystem (ISSUE 6): segmented hash-chained WAL, incremental
checkpoints, deterministic replay, warm-standby failover, the
fault-injection crash-point matrix, and the DES durability mirror.

Pin inventory:
  * legacy-WAL identity — ``wal_mode="segmented"`` (the default) is
    byte-identical to the legacy in-memory list across engine modes;
  * every crash point recovers to byte-identical registers vs. an
    uncrashed run of the surviving transaction prefix;
  * ``verify()`` rejects a flipped byte / reordering / sealed-segment
    truncation, and accepts a torn open tail;
  * warm-standby takeover replays ONLY post-checkpoint sends;
  * same log => byte-identical replay (hypothesis-shim property test);
  * default-off sim knobs leave the result dict untouched;
  * ``_canon_record`` (shape templates, the prebuilt encoder) is byte for
    byte ``_canon``, raises what ``json.dumps`` raises, and on served
    YCSB-A / SmallBank / TPC-C NP traffic builds >= 99% of the bodies from
    templates while every hash equals the reference encoder's.
"""
import copy

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.core.hotset import build_hot_index
from repro.core.packets import ADD, READ, SwitchConfig
from repro.db.dbms import Cluster, LogEntry
from repro.db.faults import FaultPlan, SimulatedCrash, SwitchUnavailable
from repro.db.wal import (CheckpointStore, SegmentedWAL, WALIntegrityError,
                          _canon, _canon_record, main as wal_cli,
                          record_hash)
from repro.db.txn import Txn, key_of
from repro.workloads import smallbank, tpcc, ycsb

SW = SwitchConfig(n_stages=8, regs_per_stage=128, max_instrs=8)
KEYS = [key_of(n, i) for n in range(2) for i in range(12)]
HI = build_hot_index([[(k, ADD)] for k in KEYS], 32, SW)


def _txns(seed, n, n_ops=2):
    rng = np.random.default_rng(seed)
    return [Txn("t", [(ADD, KEYS[rng.integers(len(KEYS))],
                       int(rng.integers(1, 9))) for _ in range(n_ops)],
                home=int(rng.integers(2))) for _ in range(n)]


def _cluster(**kw):
    c = Cluster(2, SW, HI, **kw)
    for k in KEYS:
        c.load(k, 5)
    c.snapshot_offload()
    return c


def _regs(c):
    return np.asarray(c.switch.registers).copy()


# ===================================================================== #
#  SegmentedWAL unit surface                                            #
# ===================================================================== #

def _fill(wal, n, kind="switch_send"):
    for i in range(n):
        wal.append(kind, i, dict(ops=[[ADD, KEYS[0], i]]))


def test_wal_chain_verify_ok():
    wal = SegmentedWAL(segment_size=4)
    _fill(wal, 10)
    rep = wal.verify()
    assert rep["records"] == 10 and rep["segments"] == 3
    assert rep["sealed"] == 2                       # 4 + 4 + open(2)
    assert len(wal) == 10 and wal[-1].tid == 9
    assert [e.tid for e in wal[2:5]] == [2, 3, 4]   # slices -> plain list


def test_wal_verify_rejects_flipped_byte():
    wal = SegmentedWAL(segment_size=4)
    _fill(wal, 6)
    wal[3].payload["ops"][0][2] += 1                # flip one value
    with pytest.raises(WALIntegrityError, match="corrupt"):
        wal.verify()


def test_wal_verify_rejects_reordering():
    wal = SegmentedWAL(segment_size=8)
    _fill(wal, 6)
    wal._records[2], wal._records[3] = wal._records[3], wal._records[2]
    with pytest.raises(WALIntegrityError):
        wal.verify()


def test_wal_verify_rejects_sealed_truncation():
    wal = SegmentedWAL(segment_size=4)
    _fill(wal, 9)
    # rip a record out of sealed history (bypassing tear_tail, which
    # refuses to touch sealed segments)
    del wal._records[5]
    with pytest.raises(WALIntegrityError):
        wal.verify()


def test_wal_torn_tail_is_clean_prefix():
    wal = SegmentedWAL(segment_size=4)
    _fill(wal, 10)
    assert wal.tear_tail(5) == 2        # only the open segment can tear
    assert len(wal) == 8
    wal.verify()                        # surviving prefix stays valid
    wal.append("switch_send", 99, dict(ops=[]))     # chain continues
    wal.verify()
    assert wal[-1].tid == 99


def test_wal_save_load_roundtrip_and_cli(tmp_path):
    wal = SegmentedWAL(segment_size=4)
    _fill(wal, 11)
    d = str(tmp_path / "wal")
    wal.save(d)
    loaded = SegmentedWAL.load(d)
    assert loaded.verify()["records"] == 11
    assert [(e.kind, e.tid, e.payload) for e in loaded] == \
        [(e.kind, e.tid, e.payload) for e in wal]
    assert wal_cli(["verify", d]) == 0
    # flip one byte on disk -> the CLI walk must fail
    seg = tmp_path / "wal" / "seg-00000.jsonl"
    text = seg.read_text()
    seg.write_text(text.replace('"tid":1', '"tid":7', 1))
    assert wal_cli(["verify", d]) == 1


def test_checkpoint_store_reconstructs_from_diffs():
    cs = CheckpointStore()
    rng = np.random.default_rng(0)
    regs = rng.integers(0, 50, (4, 8)).astype(np.int32)
    assert cs.checkpoint(regs)["kind"] == "full"
    for step in range(3):
        regs = regs.copy()
        regs[rng.integers(4), rng.integers(8)] += 1
        entry = cs.checkpoint(regs)
        assert entry["kind"] == "incremental" and entry["n_changed"] <= 1
    np.testing.assert_array_equal(cs.reconstruct(), cs.state())
    np.testing.assert_array_equal(cs.reconstruct(), regs)


# ===================================================================== #
#  Canonical record bodies                                              #
# ===================================================================== #

def _circular():
    loop = []
    loop.append(loop)
    return dict(ops=loop)


_OPS = [(ADD, KEYS[0], 3), (READ, KEYS[13], 0)]
# (kind, payload): every kind the DBMS logs, then payloads the shape
# templates must pass to the general encoder, then ones json.dumps rejects
CANON_CASES = dict(
    write=("write", dict(key=KEYS[0], old=5, new=8)),
    commit=("commit", {}),
    switch_send=("switch_send", dict(ops=list(_OPS))),
    switch_result=("switch_result", dict(gid=12, results=[8, 0])),
    early_abort=("early_abort", dict(attempt=2)),
    ckpt=("ckpt", dict(reason="migration", n_changed=7)),
    np_int64_write=("write", dict(key=np.int64(KEYS[0]), old=5, new=8)),
    np_int32_op=("switch_send", dict(ops=[(np.int32(ADD), KEYS[0], 3)])),
    np_int64_results=("switch_result",
                      dict(gid=1, results=[np.int64(4), 5])),
    np_array_results=("switch_result",
                      dict(gid=np.int32(1), results=np.arange(3))),
    np_array_ops=("switch_send", dict(ops=np.array(_OPS))),
    bool_values=("write", dict(key=True, old=False, new=1)),
    bool_result=("switch_result", dict(gid=1, results=[True, 0])),
    big_and_negative_ints=("write", dict(key=-(2 ** 70), old=2 ** 63,
                                         new=-1)),
    floats=("switch_result", dict(gid=1, results=[1.5, float("nan"),
                                                  float("inf"),
                                                  float("-inf"), -0.0])),
    np_float=("write", dict(key=1, old=np.float32(0.1), new=2.0)),
    sets=("ckpt", dict(reason="x", keys={3, 1, 2},
                       frozen=frozenset({-1}))),
    nested_lists=("switch_send", dict(ops=[[ADD, [1, [2]], 3]])),
    non_ascii_and_escapes=("ckpt", {"reason": 'h\u00e9llo "q"\n\t\\ \u2603',
                                    "cl\u00e9\n": 1, "a": None}),
    empty_list=("switch_send", dict(ops=[])),
    empty_results=("switch_result", dict(gid=0, results=[])),
    write_extra_key=("write", dict(key=1, old=2, new=3, x=4)),
    write_missing_key=("write", dict(key=1, old=2)),
    commit_payload=("commit", dict(by="gc")),
    tuple_ops=("switch_send", dict(ops=tuple(_OPS))),
    short_op=("switch_send", dict(ops=[(ADD, 1)])),
    tuple_results=("switch_result", dict(gid=1, results=(1,))),
    unknown_kind=("migrate_begin", dict(epoch=3, moved=[1, 2])),
    unserializable=("ckpt", dict(reason=object())),
    circular=("switch_send", _circular()),
)


def _body_or_error(fn):
    try:
        return fn()
    except Exception as e:       # the class is what must agree
        return type(e)


@pytest.mark.parametrize("case", sorted(CANON_CASES))
def test_canon_record_is_byte_identical_to_canon(case):
    kind, payload = CANON_CASES[case]
    for lsn, tid in ((0, 0), (123456, 7654321), (5, -3)):
        fast = _body_or_error(lambda: _canon_record(lsn, kind, tid, payload))
        ref = _body_or_error(lambda: _canon([lsn, kind, tid, payload]))
        assert fast == ref
        assert isinstance(ref, bytes) == (case not in ("unserializable",
                                                       "circular"))


def _served_wal_run(workload, async_hot, seed=11):
    """Seeded YCSB-A / SmallBank / TPC-C NP traffic through ``run_batch``
    in drained batches of 64, as the benchmark serves it."""
    rng = np.random.default_rng(seed)
    sw = SwitchConfig(n_stages=16, regs_per_stage=512, max_instrs=16)
    loads = ()
    if workload == "ycsb":
        p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16)
        hi = build_hot_index(ycsb.traces(ycsb.generate(rng, 1500, p)), 64,
                             sw)
        txns = ycsb.generate(rng, 256, p)
    elif workload == "smallbank":
        p = smallbank.SmallBankParams(n_nodes=2, accounts_per_node=50,
                                      hot_per_node=4)
        hi = build_hot_index(
            smallbank.traces(smallbank.generate(rng, 2000, p)), 16, sw)
        txns = smallbank.generate(rng, 256, p)
        loads = [(k, 100) for k in smallbank.hot_keys(p)]
    else:
        p = tpcc.TPCCParams(n_nodes=2, n_warehouses=2)
        hot = tpcc.hot_keys(p)
        hi = build_hot_index([[(k, ADD) for k in hot]], len(hot), sw)
        txns = tpcc.generate(rng, 128, p)
        loads = tpcc.initial_values(rng, hot).items()
    c = Cluster(p.n_nodes, sw, hi, async_hot=async_hot)
    for k, v in loads:
        c.load(k, v)
    c.snapshot_offload()
    for i in range(0, len(txns), 64):
        c.run_batch(txns[i:i + 64])
        c.drain()
    return c


@pytest.mark.parametrize("async_hot", [False, True])
@pytest.mark.parametrize("workload", ["ycsb", "smallbank", "tpcc"])
def test_fast_bodies_keep_the_hash_chain(workload, async_hot, tmp_path):
    c = _served_wal_run(workload, async_hot)
    w = c.wal_counters()
    assert w["records"] == sum(len(n.wal) for n in c.nodes) > 0
    assert w["templated"] >= 0.99 * w["records"]
    assert w["bytes"] == sum(len(_canon([r.lsn, r.kind, r.tid, r.payload]))
                             for n in c.nodes for r in n.wal)
    for n in c.nodes:
        prev = "0" * 64
        for r in n.wal:
            assert r.prev == prev
            assert r.hash == record_hash(prev, r.lsn, r.kind, r.tid,
                                         r.payload)
            prev = r.hash
        report = n.wal.verify()
        d = str(tmp_path / f"node{n.id}")
        n.wal.save(d)
        loaded = SegmentedWAL.load(d)
        assert loaded.verify() == report
        assert [r.hash for r in loaded] == [r.hash for r in n.wal]
        assert wal_cli(["verify", d]) == 0


def test_numpy_payloads_take_no_template():
    wal = SegmentedWAL(segment_size=4)
    k = np.int64(KEYS[0])
    for i in range(3):
        wal.append("write", i, dict(key=k, old=np.int64(5), new=np.int64(i)))
        wal.append("switch_send", i, dict(ops=[(np.int64(ADD), k, 1)]))
        wal.append("switch_result", i, dict(gid=np.int32(i),
                                            results=[np.int32(5)]))
        wal.append("ckpt", i, dict(reason="x", n_changed=np.int64(1)))
    assert wal.records_appended == 12 and wal.records_templated == 0
    assert all(r.hash == record_hash(r.prev, r.lsn, r.kind, r.tid, r.payload)
               for r in wal)
    wal.verify()


# ===================================================================== #
#  Legacy-WAL identity pin                                              #
# ===================================================================== #

@pytest.mark.parametrize("mode", ["auto", "serial",
                                  pytest.param("staged",
                                               marks=pytest.mark.slow)])
@pytest.mark.parametrize("async_hot", [False, True])
def test_segmented_wal_identity_with_legacy_list(mode, async_hot):
    """The segmented WAL behind the ``log()`` API is observationally
    identical to the PR 5 in-memory list: same results, same registers,
    same stats, same (kind, tid, payload) record stream, same recovery."""
    txns = _txns(7, 60)
    outs = {}
    for wal_mode in ("segmented", "list"):
        c = Cluster(2, SW, HI, switch_mode=mode, async_hot=async_hot,
                    wal_mode=wal_mode)
        for k in KEYS:
            c.load(k, 5)
        c.snapshot_offload()
        res = c.run_batch([copy.deepcopy(t) for t in txns])
        c.drain()
        outs[wal_mode] = (list(res), _regs(c), dict(c.stats),
                          [[(e.kind, e.tid, e.payload) for e in n.wal]
                           for n in c.nodes],
                          c.crash_switch_and_recover(), _regs(c))
    seg, legacy = outs["segmented"], outs["list"]
    assert seg[0] == legacy[0]
    np.testing.assert_array_equal(seg[1], legacy[1])
    assert seg[2] == legacy[2]
    assert seg[3] == legacy[3]
    assert seg[4] == legacy[4]
    np.testing.assert_array_equal(seg[5], legacy[5])


# ===================================================================== #
#  Incremental checkpoints bound recovery                               #
# ===================================================================== #

def test_checkpoint_interval_bounds_recovery():
    txns = _txns(11, 80)
    replayed = {}
    for interval in (0, 16):
        c = _cluster(checkpoint_interval=interval)
        for lo in range(0, len(txns), 20):
            c.run_batch([copy.deepcopy(t) for t in txns[lo:lo + 20]])
        before = _regs(c)
        known, unknown = c.crash_switch_and_recover()
        np.testing.assert_array_equal(before, _regs(c))
        replayed[interval] = known + unknown
        if interval:
            # bounded: everything before the last marker is checkpointed
            assert known + unknown <= 20
    assert replayed[16] < replayed[0]


def test_migration_checkpoint_is_incremental():
    """Migration-boundary checkpoints record diffs, not full registers:
    n_changed stays far below the register file size."""
    from repro.core.heat import HeatTracker
    from repro.db.migrate import EpochController
    c = _cluster()
    EpochController(c, HeatTracker(), interval=30, top_k=16)
    for lo in range(0, 90, 30):
        c.run_batch([copy.deepcopy(t) for t in _txns(13 + lo, 30)])
    assert c.stats["migrations"] >= 1
    full = SW.n_stages * SW.regs_per_stage
    assert all(d["id"] >= 1 and len(d["cells"]) < full
               for d in c.ckpts.diffs)
    # recovery from the incremental chain is exact
    before = _regs(c)
    c.crash_switch_and_recover()
    np.testing.assert_array_equal(before, _regs(c))


# ===================================================================== #
#  Fault-injection crash-point matrix                                   #
# ===================================================================== #

def _run_until_crash(c, txns, chunk=10):
    """Feed txns in admission chunks until the armed fault fires; returns
    the crash point name."""
    with pytest.raises(SimulatedCrash) as exc:
        for lo in range(0, len(txns), chunk):
            c.run_batch([copy.deepcopy(t) for t in txns[lo:lo + chunk]])
        pytest.fail("fault plan never fired")
    return exc.value.point


def _logged_send_tids(c):
    return {e.tid for n in c.nodes for e in n.wal
            if e.kind == "switch_send"}


def _reference_regs(txns, tids):
    """Registers of an uncrashed cluster running exactly the txns whose
    sends survived in the crashed cluster's WALs (admission order)."""
    ref = _cluster()
    survivors = [copy.deepcopy(t) for t in txns if t.tid in tids]
    ref.run_batch(survivors)
    ref.drain()
    return _regs(ref)


@pytest.mark.parametrize("async_hot", [False, True])
def test_crash_mid_group_dispatch_recovers(async_hot):
    txns = _txns(17, 50)
    c = _cluster(async_hot=async_hot,
                 fault_plan=FaultPlan("mid_group_dispatch", after=3))
    assert _run_until_crash(c, txns) == "mid_group_dispatch"
    known, unknown = c.recover_switch()
    assert unknown > 0        # the interrupted group never got results
    np.testing.assert_array_equal(
        _regs(c), _reference_regs(txns, _logged_send_tids(c)))
    # the cluster is operational again after recovery
    c.run(copy.deepcopy(_txns(99, 1)[0]))


def test_crash_undrained_async_batch_recovers():
    """Recovery crossing an undrained async PendingBatch: device work may
    have run, but no response reached a host — the handles are lost and
    the sends replay as unknowns."""
    txns = _txns(19, 60)
    c = _cluster(async_hot=True, max_inflight=4,
                 fault_plan=FaultPlan("undrained_async", after=4))
    assert _run_until_crash(c, txns) == "undrained_async"
    assert not c._inflight                 # handles dropped, not drained
    known, unknown = c.recover_switch()
    assert unknown > 0
    np.testing.assert_array_equal(
        _regs(c), _reference_regs(txns, _logged_send_tids(c)))


def test_crash_torn_tail_recovers_surviving_prefix():
    """A crash tears the last txn's records (send + result) off the home
    node's open WAL segment: the surviving log is a clean verifiable
    prefix and recovery rebuilds exactly the surviving transactions."""
    # single-home stream so the torn node is deterministic
    rng = np.random.default_rng(23)
    txns = [Txn("t", [(ADD, KEYS[rng.integers(12)], int(rng.integers(1, 9)))],
                home=0) for _ in range(30)]
    c = _cluster(fault_plan=FaultPlan("torn_tail", after=3,
                                      tear_records=2, tear_node=0))
    assert _run_until_crash(c, txns) == "torn_tail"
    c.nodes[0].wal.verify()                # torn tail = valid prefix
    c.recover_switch()
    np.testing.assert_array_equal(
        _regs(c), _reference_regs(txns, _logged_send_tids(c)))


def test_crash_mid_migration_recovers_and_serves_evicted():
    """Crash between migrate_begin and migrate_end: the old placement
    stands, recovery replays under it, and — the partial-availability
    window — evicted keys stay readable/writable from their home stores
    while the switch is down."""
    from repro.core.heat import HeatTracker
    from repro.db.migrate import EpochController

    txns = _txns(29, 80)
    c = _cluster(fault_plan=FaultPlan("mid_migration"))
    # drive traffic onto a subset so the re-placement evicts the rest
    skew = [t for t in txns if all(k in KEYS[:6] for _, k, _ in t.ops)]
    skew = (skew * 8)[:40] or txns[:40]
    EpochController(c, HeatTracker(), interval=20, top_k=4)
    point = _run_until_crash(c, [copy.deepcopy(t) for t in skew])
    assert point == "mid_migration"
    assert c._mid_migration_evicted
    evicted = next(iter(c._mid_migration_evicted))
    live = next(k for k in KEYS if k not in c._mid_migration_evicted)
    # evicted key: readable from its home store; live hot key: unavailable
    assert c.read(evicted) == c.nodes[evicted // 1_000_000_000].store[evicted]
    with pytest.raises(SwitchUnavailable):
        c.read(live)
    # a txn touching ONLY evicted keys demotes to the cold path
    t_ev = Txn("t", [(ADD, evicted, 1)], home=0)
    assert c.run(t_ev) is not None
    with pytest.raises(SwitchUnavailable):
        c.run(Txn("t", [(ADD, live, 1)], home=0))
    # recovery: old index stands, registers rebuilt under it
    before_stats = c.stats["migrations"]
    c.recover_switch()
    assert c.stats["migrations"] == before_stats == 0
    assert not c._mid_migration_evicted
    c.run(copy.deepcopy(_txns(99, 1)[0]))   # operational again


# ===================================================================== #
#  Warm-standby failover                                                #
# ===================================================================== #

def test_warm_standby_bounded_takeover():
    c = _cluster(checkpoint_interval=16, standby=True)
    for lo in range(0, 72, 24):
        c.run_batch([copy.deepcopy(t) for t in _txns(31 + lo, 24)])
    before = _regs(c)
    since = c._sends_since_ckpt
    gid_before = c.switch.next_gid
    known, unknown = c.fail_over()
    # bounded recovery: ONLY post-checkpoint sends replay
    assert known + unknown == since
    np.testing.assert_array_equal(before, _regs(c))
    assert c.stats["failovers"] == 1
    # new txns keep committing with fresh GIDs above the pre-crash stream
    assert c.switch.next_gid >= gid_before
    c.run_batch([copy.deepcopy(t) for t in _txns(37, 10)])
    c.drain()


def test_failover_replays_less_than_cold_recovery():
    txns = _txns(41, 60)
    cold = _cluster()        # no interval checkpoints
    cold.run_batch([copy.deepcopy(t) for t in txns])
    cold_replayed = sum(cold.crash_switch_and_recover())
    warm = _cluster(checkpoint_interval=16, standby=True)
    for lo in range(0, len(txns), 20):
        warm.run_batch([copy.deepcopy(t) for t in txns[lo:lo + 20]])
    warm_replayed = sum(warm.fail_over())
    assert warm_replayed < cold_replayed


def test_failover_without_standby_raises():
    c = _cluster()
    with pytest.raises(RuntimeError, match="standby"):
        c.fail_over()


def test_double_fault_failover_falls_back_to_cold_recovery():
    """Double fault: the warm standby itself dies DURING takeover
    (``mid_failover`` crash point).  The switch stays down, the standby
    is gone, and cold WAL+checkpoint recovery must still rebuild the
    registers byte-identical to the pre-crash drained state."""
    c = _cluster(checkpoint_interval=16, standby=True,
                 fault_plan=FaultPlan("mid_failover"))
    for lo in range(0, 48, 24):
        c.run_batch([copy.deepcopy(t) for t in _txns(11 + lo, 24)])
    c.drain()
    before = _regs(c)
    with pytest.raises(SimulatedCrash):
        c.fail_over()
    assert c._standby is None                # the standby died too
    assert c._switch_down                    # nothing took over
    c.recover_switch()                       # cold fallback
    np.testing.assert_array_equal(before, _regs(c))
    # the recovered cluster keeps committing
    c.run_batch([copy.deepcopy(t) for t in _txns(99, 8)])
    c.drain()
    assert c.stats["recoveries"] == 1


def test_load_then_failover_recovers_new_value():
    """Standby blind-spot regression: a post-checkpoint ``load()`` must be
    a logged write (WAL write + switch_send/switch_result), so failover
    replay recovers it — not the pre-load checkpoint value."""
    sw = SwitchConfig(n_stages=4, regs_per_stage=16, max_instrs=4)
    k = key_of(0, 0)
    hi = build_hot_index([[(k, ADD)]], 1, sw)
    c = Cluster(1, sw, hi, use_switch=True, standby=True)
    c.load(k, 100)
    c.snapshot_offload()          # checkpoint: standby sees 100
    c.run(Txn("t", [(ADD, k, 1)], 0))
    c.load(k, 500)                # post-checkpoint load: the blind spot
    assert c.read(k) == 500
    c.fail_over()
    assert c.read(k) == 500, "standby recovered a stale pre-load value"
    # and the home store agrees (load is a logged node write too)
    assert c.nodes[0].store[k] == 500


# ===================================================================== #
#  Deterministic replay (property)                                      #
# ===================================================================== #

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_same_log_replays_byte_identical(seed):
    """Same WAL ⇒ byte-identical registers, results and GID order: two
    independent replays of one cluster's log agree exactly, and a second
    crash+recover of the already-recovered cluster is a fixed point."""
    c = _cluster(checkpoint_interval=8)
    c.run_batch([copy.deepcopy(t) for t in _txns(seed, 25)])
    c.drain()
    e1, e2 = c._fresh_engine(), c._fresh_engine()
    n1 = c._replay_into(e1)
    n2 = c._replay_into(e2)
    assert n1 == n2
    np.testing.assert_array_equal(np.asarray(e1.read_all()),
                                  np.asarray(e2.read_all()))
    assert e1.next_gid == e2.next_gid
    before = _regs(c)
    c.crash_switch_and_recover()
    np.testing.assert_array_equal(before, _regs(c))
    c.crash_switch_and_recover()            # idempotent fixed point
    np.testing.assert_array_equal(before, _regs(c))


def test_wal_survives_disk_roundtrip_and_replays(tmp_path):
    """Persist a node's WAL, reload it, splice it into a fresh node-set:
    recovery over the reloaded log reproduces the original registers."""
    c = _cluster()
    c.run_batch([copy.deepcopy(t) for t in _txns(43, 30)])
    c.drain()
    before = _regs(c)
    for n in c.nodes:
        d = str(tmp_path / f"node{n.id}")
        n.wal.save(d)
        n.wal = SegmentedWAL.load(d)
        n.wal.verify()
    c.crash_switch_and_recover()
    np.testing.assert_array_equal(before, _regs(c))


# ===================================================================== #
#  DES durability mirror                                                #
# ===================================================================== #

def _sim(profiles, hot_index=None, **sys_kw):
    from repro.sim.model import ClusterSim, SystemConfig, Timing
    cs = ClusterSim(profiles, 2, 4, SystemConfig(kind="p4db", **sys_kw),
                    timing=Timing(), seed=5, sim_time=0.01, warmup=2e-3)
    return cs.run()


def _sim_profiles():
    from repro.sim.model import profile_txn
    return [profile_txn(t, HI, t.home) for t in _txns(53, 200)]


def test_sim_default_knobs_add_nothing():
    """crash_at=0 / ckpt_interval=0 / gate=0 / partial off is the
    pre-durability model, event for event."""
    profs = _sim_profiles()
    a = _sim(profs)
    b = _sim(profs, crash_at=0.0, ckpt_interval=0.0, gate_t_reconfig=0.0,
             partial_availability=False)
    assert a == b
    assert "failover" not in a and "reconfigs_gated" not in a


def test_sim_failover_outage_shrinks_with_ckpt_interval():
    profs = _sim_profiles()
    outs = {ck: _sim(profs, max_batch=8, crash_at=6e-3, ckpt_interval=ck)
            for ck in (0.0, 2e-3, 0.5e-3)}
    outages = {ck: o["failover"]["outage"] for ck, o in outs.items()}
    assert outages[0.5e-3] <= outages[2e-3] <= outages[0.0]
    assert outages[0.5e-3] < outages[0.0]
    for ck, o in outs.items():
        assert o["failover"]["replayed"] >= 0
        assert o["breakdown"].get("failover", 0) > 0
        if ck:
            assert o["ckpts_taken"] > 0


@pytest.mark.slow
def test_sim_gate_mirrors_functional_controller():
    """gate_t_reconfig huge ⇒ every due migration is gated (and the run
    pays no reconfig pause); gate off ⇒ the PR 4 controller, untouched."""
    import sys as _sys, os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    from benchmarks import common as C
    gen = C.drift_generators(fast=True)[0][1]
    hi, k = C.drift_hot_index(gen, C.ADAPTIVE_TOP_K)
    t = C.adaptive_sim_time(True)
    from repro.sim.model import SystemConfig
    free = C.run_drift_sim(gen, "adaptive", k, t, hot_index=hi)
    gated = C.run_drift_sim(gen, "adaptive", k, t, hot_index=hi,
                            system=SystemConfig(kind="p4db",
                                                gate_t_reconfig=1.0))
    assert free["reconfigs"] > 0
    assert gated["reconfigs"] == 0 and gated["reconfigs_gated"] > 0
    assert gated["breakdown"].get("reconfig", 0) == 0


def test_sim_partial_availability_serves_evicted_keys():
    """Under a drifting workload whose old hot keys keep tail traffic
    (RotatingZipf), evicted-key txns commit during the migration pause
    instead of waiting it out."""
    import sys as _sys, os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    from benchmarks import common as C
    from repro.sim.model import SystemConfig, Timing
    from repro.workloads import drift
    gen = drift.RotatingZipf(n_nodes=C.N_NODES, period=C.DRIFT_PERIOD)
    hi, k = C.drift_hot_index(gen, 50 * C.N_NODES)
    t = C.adaptive_sim_time(True)
    T = Timing(t_reconfig=2e-3)          # long pause: availability matters
    base = C.run_drift_sim(gen, "adaptive", k, t, hot_index=hi, timing=T)
    pa = C.run_drift_sim(gen, "adaptive", k, t, hot_index=hi, timing=T,
                         system=SystemConfig(kind="p4db",
                                             partial_availability=True))
    assert pa["partial_served"] > 0
    assert pa["throughput"] >= base["throughput"]

"""Contention-resilience layer (ISSUE 10): in-flight conflict detection
with network-assisted early aborts, the deterministic retry discipline,
graceful brown-out degradation, and the DES mirror.

Pin inventory:
  * ``early_abort`` on vs off reaches IDENTICAL committed state on
    ADD-based storms (functional arena AND sequential ``run_batch``
    across engine modes / sync+async / N nodes) — only the
    abort/retry/wasted accounting differs;
  * WAL recovery never replays an early-aborted attempt, even when a
    later attempt of the same tid commits (crafted stale-record case);
  * no lock survives a crash, a wound, or an exhausted retry budget
    (hypothesis-shim property over seeds x fault timing, including
    ``mid_2pc_prepare``);
  * brown-out enter/exit restores registers byte-identical to a cluster
    that never browned out; demotions stop at ``demote_cap``;
  * sim defaults-off leaves the result dict untouched; zero-contention
    sim runs are identical on vs off.
"""
import copy
import pickle

import numpy as np
import pytest

from conftest import force_engine
from hypothesis_compat import given, settings, st
from repro.core.hotset import build_hot_index
from repro.core.packets import ADD, READ, SwitchConfig
from repro.db.conflict import (GAVE_UP, ConflictDetector, ContentionArena,
                               Intent, RetryPolicy, _GaveUp)
from repro.db.dbms import Cluster
from repro.db.faults import (Brownout, FaultPlan, SimulatedCrash,
                             SwitchUnavailable)
from repro.db.txn import Txn, key_of, node_of
from repro.obs.names import H_RETRIES
from repro.sim.model import ClusterSim, SystemConfig, Timing, profile_txn
from repro.workloads import storms

SW = SwitchConfig(n_stages=8, regs_per_stage=128, max_instrs=8)
P = storms.StormParams(n_nodes=2, keys_per_node=200, contended_per_node=2,
                       hot_per_node=4)


def _storm(seed=0, n=60, p=P):
    return storms.ycsb_a_storm(np.random.default_rng(seed), n, p)


def _cold_cluster(proto="WAIT_DIE", n_nodes=P.n_nodes, **kw):
    return Cluster(n_nodes, SW, hot_index=None, use_switch=False,
                   protocol=proto, **kw)


def _arena_run(txns, proto="WAIT_DIE", ea=True, workers=8, max_retries=48,
               **kw):
    c = _cold_cluster(proto, **kw)
    pol = RetryPolicy.for_protocol(proto, max_retries=max_retries, seed=1)
    r = ContentionArena(c, policy=pol, early_abort=ea).run(
        copy.deepcopy(txns), workers=workers)
    return c, r


def _stores(c):
    return [dict(n.store) for n in c.nodes]


# ===================================================================== #
#  RetryPolicy                                                          #
# ===================================================================== #

def test_retry_policy_deterministic():
    a = RetryPolicy(max_retries=8, seed=3)
    b = RetryPolicy(max_retries=8, seed=3)
    assert list(a.schedule(42)) == list(b.schedule(42))
    # different seed or tid -> different jitter draws somewhere
    c = RetryPolicy(max_retries=8, seed=4)
    assert list(a.schedule(42)) != list(c.schedule(42))
    assert list(a.schedule(42)) != list(a.schedule(43))


def test_retry_backoff_bounds_and_cap():
    p = RetryPolicy(base=1.0, multiplier=2.0, cap=16.0, jitter=0.5)
    for attempt in range(2, 14):
        raw = min(p.cap, p.base * p.multiplier ** (attempt - 2))
        w = p.backoff(7, attempt)
        assert raw * (1 - p.jitter) <= w <= raw * (1 + p.jitter)
    # deep attempts stay bounded by cap * (1 + jitter)
    assert p.backoff(7, 100) <= p.cap * (1 + p.jitter)


def test_retry_schedule_deadline_bounds_cumulative_backoff():
    p = RetryPolicy(max_retries=50, base=1.0, multiplier=2.0, cap=64.0,
                    jitter=0.0, deadline=10.0)
    sched = list(p.schedule(1))
    assert sched[0] == (1, 0.0)                  # attempt 1 is immediate
    assert sum(w for _, w in sched) <= p.deadline
    assert len(sched) < 50                       # deadline cut it short
    # no deadline -> the full attempt budget
    assert len(list(RetryPolicy(max_retries=6).schedule(1))) == 6


def test_retry_for_protocol_defaults():
    wd = RetryPolicy.for_protocol("WAIT_DIE")
    assert (wd.multiplier, wd.jitter) == (1.5, 0.25)
    nw = RetryPolicy.for_protocol("NO_WAIT")
    assert (nw.multiplier, nw.jitter) == (2.0, 0.5)
    # explicit kwargs win over protocol defaults
    assert RetryPolicy.for_protocol("WAIT_DIE", multiplier=3.0).multiplier \
        == 3.0


# ===================================================================== #
#  GAVE_UP sentinel                                                     #
# ===================================================================== #

def test_gave_up_singleton_semantics():
    assert not GAVE_UP                       # falsy: `if result:` skips it
    assert GAVE_UP is not None               # but NOT the undrained slot
    assert _GaveUp() is GAVE_UP              # singleton construction
    assert copy.deepcopy(GAVE_UP) is GAVE_UP
    assert pickle.loads(pickle.dumps(GAVE_UP)) is GAVE_UP
    assert repr(GAVE_UP) == "GAVE_UP"


# ===================================================================== #
#  ConflictDetector                                                     #
# ===================================================================== #

def test_detector_no_wait_registrant_dies_on_overlap():
    d = ConflictDetector("NO_WAIT")
    assert d.admit(1, 10, reads=(), writes={5}) == (True, [])
    admitted, wounded = d.admit(2, 11, reads={5}, writes=())
    assert not admitted and wounded == []
    assert 2 not in d.inflight               # loser was never registered
    assert d.stats["early_aborts"] == 1 and d.stats["wounds"] == 0


def test_detector_read_read_is_compatible():
    d = ConflictDetector("NO_WAIT")
    assert d.admit(1, 10, reads={5}, writes=())[0]
    assert d.admit(2, 11, reads={5}, writes=())[0]
    assert d.stats["conflicts"] == 0


def test_detector_wait_die_younger_registrant_dies():
    d = ConflictDetector("WAIT_DIE")
    assert d.admit(1, 10, reads=(), writes={5})[0]
    admitted, wounded = d.admit(2, 11, reads=(), writes={5})  # younger
    assert not admitted and wounded == [] and 1 in d.inflight


def test_detector_wait_die_older_wounds_younger_inflight():
    d = ConflictDetector("WAIT_DIE")
    assert d.admit(2, 11, reads=(), writes={5})[0]
    admitted, wounded = d.admit(1, 10, reads=(), writes={5})  # older
    assert admitted and [it.tid for it in wounded] == [2]
    assert 2 not in d.inflight and 1 in d.inflight
    assert d.stats["wounds"] == 1 and d.stats["early_aborts"] == 0


def test_detector_woundable_veto_kills_registrant_instead():
    d = ConflictDetector("WAIT_DIE")
    assert d.admit(2, 11, reads=(), writes={5})[0]
    # the younger txn already reached its commit decision: not woundable
    admitted, wounded = d.admit(1, 10, reads=(), writes={5},
                                woundable=lambda it: False)
    assert not admitted and wounded == [] and 2 in d.inflight


def test_detector_release_readmits():
    d = ConflictDetector("NO_WAIT")
    d.admit(1, 10, reads=(), writes={5})
    d.release(1)
    assert d.admit(2, 11, reads=(), writes={5})[0]
    d.release(99)                            # unknown tid is a no-op


# ===================================================================== #
#  ContentionArena: functional semantics                                #
# ===================================================================== #

def test_arena_disjoint_matches_sequential_reference():
    """With no key overlap the arena must equal plain sequential runs:
    same results, same stores, zero aborts/waste."""
    txns = [Txn("t", [(ADD, key_of(i % 2, 10 + i), i + 1),
                      (READ, key_of(i % 2, 10 + i), 0)], i % 2)
            for i in range(20)]
    c, r = _arena_run(txns, ea=True, workers=4)
    ref = _cold_cluster()
    ref_results = [ref.run(copy.deepcopy(t)) for t in txns]
    assert r.results == ref_results
    assert _stores(c) == _stores(ref)
    assert r.aborts == r.wasted_ops == r.early_aborts == 0
    assert len(r.committed) == len(txns) and not r.gave_up


@pytest.mark.parametrize("proto", ["NO_WAIT", "WAIT_DIE"])
def test_arena_early_abort_on_off_state_identity(proto):
    """The differential pin: ADD storms commute, so on vs off must land
    on IDENTICAL stores while on-mode wastes strictly less work."""
    txns = _storm(seed=2, n=80)
    c_off, r_off = _arena_run(txns, proto, ea=False, max_retries=64)
    c_on, r_on = _arena_run(txns, proto, ea=True, max_retries=64)
    assert not r_off.gave_up and not r_on.gave_up
    assert r_off.committed == r_on.committed
    assert _stores(c_off) == _stores(c_on)
    assert r_on.early_aborts > 0
    assert r_on.wasted_ops < r_off.wasted_ops


def test_arena_storm_recovers_to_committed_state():
    """After an early-abort-heavy storm every node's WAL must recover to
    exactly the committed stores — early-aborted attempts (including
    wounds that landed mid-2PC-prepare) are never replayed."""
    c, r = _arena_run(_storm(seed=5, n=80), "WAIT_DIE", ea=True)
    assert r.wounds > 0                      # the interesting window hit
    before = _stores(c)
    for nid in range(len(c.nodes)):
        c.crash_node_and_recover(nid)
    # recovery rebuilds only logged keys; every logged key must agree
    for nid, n in enumerate(c.nodes):
        for k, v in n.store.items():
            assert before[nid][k] == v, f"node {nid} key {k} diverged"


def test_early_abort_record_cancels_stale_writes_only():
    """Crafted WAL: attempt 1 logs write records, the wound lands
    (early_abort), then a LATER attempt of the same tid commits.
    Recovery must replay only the post-early-abort writes."""
    c = _cold_cluster(n_nodes=1)
    n = c.nodes[0]
    k = key_of(0, 3)
    n.log("write", 7, key=k, old=0, new=5)       # doomed attempt
    n.log("early_abort", 7, attempt=1)           # the multicast, durable
    n.log("write", 7, key=k, old=0, new=9)       # retry's redo record
    n.log("commit", 7)
    n.crash()
    n.recover_local()
    assert n.store[k] == 9

    # without a later commit nothing of tid 7 survives
    c2 = _cold_cluster(n_nodes=1)
    n2 = c2.nodes[0]
    n2.log("write", 7, key=k, old=0, new=5)
    n2.log("early_abort", 7, attempt=1)
    n2.crash()
    n2.recover_local()
    assert n2.store[k] == 0


def test_gave_up_and_retry_histogram():
    """A brutal budget makes txns give up: ``gave_up`` is counted (not
    silently dropped), results hold the GAVE_UP sentinel by identity,
    and every finished cold txn lands in the txn_retries histogram."""
    txns = _storm(seed=1, n=40)
    c, r = _arena_run(txns, "NO_WAIT", ea=False, workers=None,
                      max_retries=2)
    assert r.gave_up                         # the budget was brutal
    assert c.stats["gave_up"] == len(r.gave_up)
    for t in txns:
        if t.tid in r.gave_up:
            got = r.results[next(i for i, x in enumerate(txns)
                                 if x.tid == t.tid)]
            assert got is GAVE_UP and not got and got is not None
    h = c.metrics.get(H_RETRIES, klass="cold")
    assert h is not None and h.count == len(txns)


# ===================================================================== #
#  Lock-leak property (hypothesis shim)                                 #
# ===================================================================== #

@settings(max_examples=12, deadline=None)
@given(st.integers(0, 100), st.integers(1, 40))
def test_no_lock_leak_across_crash_and_abort(seed, after):
    """Whatever happens — early aborts, wounds mid-2PC-prepare, exhausted
    retries, or a SimulatedCrash at the ``mid_2pc_prepare`` fault point —
    no arena txn may leave a lock behind on any node."""
    txns = _storm(seed=seed, n=30)
    c = _cold_cluster("WAIT_DIE",
                      fault_plan=FaultPlan("mid_2pc_prepare", after=after))
    pol = RetryPolicy.for_protocol("WAIT_DIE", max_retries=3, seed=seed)
    arena = ContentionArena(c, policy=pol, early_abort=True)
    try:
        arena.run(copy.deepcopy(txns), workers=6)
    except SimulatedCrash:
        pass                                 # the armed point fired
    tids = {t.tid for t in txns}
    for n in c.nodes:
        for key, (mode, owners) in n.locks.items():
            leaked = set(owners) & tids
            assert not leaked, f"lock {key} leaked by {leaked}"
    assert not (set(arena.detector.inflight) & tids)


# ===================================================================== #
#  Brown-out: graceful degradation                                      #
# ===================================================================== #

BSW = SwitchConfig(n_stages=4, regs_per_stage=16, max_instrs=4)


def _hot_cluster(**kw):
    keys = [key_of(n, i) for n in range(2) for i in range(4)]
    hi = build_hot_index([[(k, ADD)] for k in keys], 16, BSW)
    c = Cluster(2, BSW, hi, use_switch=True, **kw)
    for k in keys:
        c.load(k, 10)
    c.snapshot_offload()
    return c, keys


def test_brownout_demotes_hot_to_cold():
    c, keys = _hot_cluster()
    c.enter_brownout()
    assert c.stats["brownouts"] == 1
    c.enter_brownout()                       # idempotent while active
    assert c.stats["brownouts"] == 1
    hot_before = c.stats["hot"]
    for k in keys:
        c.run(Txn("t", [(ADD, k, 1)], node_of(k)))
    assert c.stats["hot"] == hot_before      # nothing reached the switch
    assert c.stats["demoted_brownout"] == len(keys)
    for k in keys:
        assert c.read(k) == 11               # served from the home store


def test_brownout_cap_sheds_past_budget():
    c, keys = _hot_cluster()
    c.enter_brownout(Brownout(demote_cap=2))
    done = 0
    for k in keys:
        try:
            c.run(Txn("t", [(ADD, k, 1)], node_of(k)))
            done += 1
        except SwitchUnavailable:
            pass
    assert done == 2 and c.stats["demoted_brownout"] == 2
    c.exit_brownout()                        # restores hot service
    c.run(Txn("t", [(ADD, keys[0], 1)], node_of(keys[0])))
    assert c.stats["hot"] > 0


def test_brownout_exit_restores_register_identity():
    """Registers after enter->serve->exit must be byte-identical to a
    cluster that served the same txns with no brown-out at all."""
    rng = np.random.default_rng(3)
    c, keys = _hot_cluster()
    ref, _ = _hot_cluster()
    txns = [Txn("t", [(ADD, keys[int(rng.integers(len(keys)))],
                       int(rng.integers(1, 9)))],
                0) for _ in range(30)]
    mid = len(txns) // 2
    for t in txns[:mid]:
        c.run(copy.deepcopy(t))
    c.enter_brownout()
    for t in txns[mid:]:
        c.run(copy.deepcopy(t))              # demoted through cold path
    c.exit_brownout()
    for t in txns:
        ref.run(copy.deepcopy(t))
    c.drain(), ref.drain()
    for k in keys:
        assert c.read(k) == ref.read(k)
    np.testing.assert_array_equal(np.asarray(c.switch.registers),
                                  np.asarray(ref.switch.registers))
    # and the WAL-logged eviction/reload survives switch recovery
    c.crash_switch_and_recover()
    for k in keys:
        assert c.read(k) == ref.read(k)


def test_brownout_validation():
    with pytest.raises(ValueError):
        Brownout(demote_cap=-1)
    with pytest.raises(ValueError):
        Brownout(slow_factor=0.5)
    c, _ = _hot_cluster()
    c.exit_brownout()                        # not in brown-out: no-op
    assert not c._brownout


# ===================================================================== #
#  Sequential differential: early_abort on/off across engine modes      #
# ===================================================================== #

DIFF_P = storms.StormParams(n_nodes=2, keys_per_node=60,
                            contended_per_node=2, hot_per_node=4,
                            p_hot_txn=0.4)


def _diff_batch(n_nodes, async_hot):
    p = storms.StormParams(**{**DIFF_P.__dict__, "n_nodes": n_nodes})
    txns = storms.ycsb_a_storm(np.random.default_rng(9), 50, p)
    hi = build_hot_index([[(k, ADD)] for k in storms.hot_keys(p)], 16, BSW)
    outs = []
    for ea in (False, True):
        c = Cluster(n_nodes, BSW, hi, use_switch=True, async_hot=async_hot,
                    early_abort=ea)
        for k in storms.hot_keys(p):
            c.load(k, 10)
        c.snapshot_offload()
        res = list(c.run_batch([copy.deepcopy(t) for t in txns]))
        c.drain()
        outs.append((c, res))
    (c_off, r_off), (c_on, r_on) = outs
    assert r_off == r_on
    assert _stores(c_off) == _stores(c_on)
    np.testing.assert_array_equal(np.asarray(c_off.switch.registers),
                                  np.asarray(c_on.switch.registers))
    # WAL-recoverable state identical too
    for c in (c_off, c_on):
        for nid in range(n_nodes):
            c.crash_node_and_recover(nid)
        c.crash_switch_and_recover()
    assert _stores(c_off) == _stores(c_on)
    np.testing.assert_array_equal(np.asarray(c_off.switch.registers),
                                  np.asarray(c_on.switch.registers))


@pytest.mark.parametrize("n_nodes", [1, 2])
@pytest.mark.parametrize("async_hot", [False, True])
def test_run_batch_early_abort_differential(n_nodes, async_hot):
    _diff_batch(n_nodes, async_hot)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["serial", "staged", "affine"])
@pytest.mark.parametrize("n_nodes", [1, 2])
@pytest.mark.parametrize("async_hot", [False, True])
def test_run_batch_early_abort_differential_modes(mode, n_nodes,
                                                  async_hot, monkeypatch):
    force_engine(monkeypatch, mode)
    _diff_batch(n_nodes, async_hot)


# ===================================================================== #
#  DES mirror                                                           #
# ===================================================================== #

def _sim_profiles(n=150, p=P, seed=4):
    txns = storms.ycsb_a_storm(np.random.default_rng(seed), n, p)
    return [profile_txn(t, None, t.home) for t in txns]


def _sim(profs, proto, ea, seed_locks=None, sim_time=0.003):
    cs = ClusterSim(profs, n_nodes=P.n_nodes, workers_per_node=4,
                    system=SystemConfig(kind="p4db", protocol=proto,
                                        early_abort=ea,
                                        drop_on_abort=False),
                    timing=Timing(), seed=7, sim_time=sim_time,
                    warmup=sim_time * 0.1)
    for k in (seed_locks or ()):
        cs.lock_of(k)
    return cs


def test_sim_defaults_off_result_dict_untouched():
    out = _sim(_sim_profiles(), "NO_WAIT", ea=False).run()
    assert "early_abort" not in out


def test_sim_zero_contention_on_off_identical():
    """With no contended locks the detector never fires; the on-run's
    result dict must equal the off-run's exactly (modulo its own gated,
    all-zero section)."""
    profs = _sim_profiles()
    off = _sim(profs, "WAIT_DIE", ea=False).run()
    on = _sim(profs, "WAIT_DIE", ea=True).run()
    sec = on.pop("early_abort")
    assert sec["early_aborts"] == 0 and sec["wounds"] == 0
    assert on == off


def test_sim_storm_wait_die_reduces_waste():
    profs = _sim_profiles()
    locks = storms.contended_keys(P)
    cs_off = _sim(profs, "WAIT_DIE", ea=False, seed_locks=locks)
    cs_off.run()
    cs_on = _sim(profs, "WAIT_DIE", ea=True, seed_locks=locks)
    out = cs_on.run()
    assert cs_on.early_aborts > 0
    assert cs_on.wasted_ops < cs_off.wasted_ops
    assert out["early_abort"]["early_aborts"] == cs_on.early_aborts

"""What the served path's spans and counters cover, and that they change
nothing.

  * ``run_batch`` + ``drain`` over a mixed hot/warm/cold batch, and over
    TPC-C NewOrder + Payment (every txn warm), record the ``cold``,
    ``warm-split``, ``wal-send``, ``scatter`` and ``packet-build`` spans,
    and no two spans overlap: each instant of a call is charged to one
    span.
  * Every ``build_packets`` call of the batch path, the whole-group build
    of ``_flush_hot_group`` included, runs inside a ``packet-build`` span.
  * The WAL counters equal the records in the logs and the bytes their
    hashes cover; append time is read only under a caller's tracer.
  * Results, registers, stats and every WAL record (hash chain included)
    are identical with a caller's tracer, the default one and none.
  * The engines count their H2D and D2H bytes and compile under one
    program name per mode; ``GcMeter`` counts collections and leaves
    ``gc.callbacks`` as it found them.
"""
import copy
import functools
import gc

import numpy as np
import pytest

import repro.db.dbms as dbms
from repro.core import engine
from repro.core.engine import N_PLANES, SwitchEngine
from repro.core.hotset import build_hot_index
from repro.core.packets import (ADD, READ, WRITE, SwitchConfig,
                                build_packets)
from repro.db.dbms import Cluster
from repro.db.txn import Txn, node_of
from repro.db.wal import _canon
from repro.obs import FUNCTIONAL_SPANS, GcMeter, Tracer
from repro.workloads import smallbank, tpcc, ycsb

SW = SwitchConfig(n_stages=16, regs_per_stage=512, max_instrs=16)


def _with_warm(txns, hi):
    """The generators draw all-hot or all-cold txns at these sizes; add a
    warm deposit (one hot key, one cold key) every 32 txns."""
    keys = [k for t in txns for k in t.keys()]
    hot = next(k for k in keys if hi.is_hot(k))
    cold = next(k for k in keys if not hi.is_hot(k))
    for i in range(8, len(txns), 32):
        txns.insert(i, Txn("deposit", [(ADD, hot, 5), (ADD, cold, 5)],
                           node_of(hot)))
    return txns


@functools.lru_cache(maxsize=None)
def _ycsb():
    p = ycsb.YCSBParams(n_nodes=4, keys_per_node=1000, hot_per_node=16)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 64, SW)
    txns = ycsb.generate(np.random.default_rng(1), 256, p)
    return 4, hi, _with_warm(txns, hi), ()


@functools.lru_cache(maxsize=None)
def _smallbank():
    p = smallbank.SmallBankParams(n_nodes=2, accounts_per_node=50,
                                  hot_per_node=4)
    sample = smallbank.generate(np.random.default_rng(0), 2000, p)
    hi = build_hot_index(smallbank.traces(sample), 16, SW)
    txns = smallbank.generate(np.random.default_rng(1), 256, p)
    return 2, hi, _with_warm(txns, hi), \
        tuple((k, 100) for k in smallbank.hot_keys(p))


@functools.lru_cache(maxsize=None)
def _tpcc():
    p = tpcc.TPCCParams(n_nodes=2, n_warehouses=2)
    hot = tpcc.hot_keys(p)
    hi = build_hot_index([[(k, ADD) for k in hot]], len(hot), SW)
    txns = tpcc.generate(np.random.default_rng(1), 128, p)
    return 2, hi, txns, tuple(tpcc.initial_values(
        np.random.default_rng(2), hot).items())


WORKLOADS = dict(ycsb=_ycsb, smallbank=_smallbank, tpcc=_tpcc)
# the classes each workload's batch holds
KINDS = dict(ycsb={"hot", "warm", "cold"}, smallbank={"hot", "warm", "cold"},
             tpcc={"warm"})


def _cluster(workload, async_hot, **kw):
    """The loaded cluster, its tracer cleared, and the txns to serve."""
    n_nodes, hi, txns, loads = WORKLOADS[workload]()
    c = Cluster(n_nodes, SW, hi, use_switch=True, async_hot=async_hot, **kw)
    for k, v in loads:
        c.load(k, v)
    c.snapshot_offload()
    if c.tracer is not None:
        c.tracer.clear()
    return c, copy.deepcopy(txns)


def _drive(c, txns):
    """Serve ``txns`` as the benchmark does: batches, each drained."""
    out = []
    for i in range(0, len(txns), 64):
        res = c.run_batch(txns[i:i + 64])
        c.drain()
        out += list(res)
    return out


def _serve(workload, async_hot, **kw):
    c, txns = _cluster(workload, async_hot, **kw)
    return c, txns, _drive(c, txns)


def _spans(c):
    return [s for tr in c.tracer.traces for s in tr.spans]


@pytest.mark.parametrize("async_hot", [False, True])
@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_batch_path_spans_are_disjoint_and_named(workload, async_hot):
    c, txns, _ = _serve(workload, async_hot, tracer=Tracer(capacity=1 << 16))
    kinds = {c.classify(t) for t in txns}
    assert kinds == KINDS[workload]
    spans = sorted(_spans(c), key=lambda s: (s.t0, s.t1))
    names = {s.name for s in spans}
    assert {"classify", "warm-split", "cold", "packet-build", "wal-send",
            "dispatch", "drain", "scatter"} <= names
    assert names <= set(FUNCTIONAL_SPANS)
    assert all(s.depth == 0 and s.t1 >= s.t0 for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a.t1 <= b.t0, (a, b)


def test_every_batch_path_packet_build_is_spanned(monkeypatch):
    """SmallBank's multipass ADDP splits groups, so the whole-group build
    in ``_flush_hot_group`` and the per-split builds both run."""
    calls = []

    def timed_build(*a, **kw):
        t0 = dbms.time.perf_counter()
        out = build_packets(*a, **kw)
        calls.append((t0, dbms.time.perf_counter()))
        return out

    monkeypatch.setattr(dbms, "build_packets", timed_build)
    c, txns = _cluster("smallbank", True, tracer=Tracer(capacity=1 << 16))
    calls.clear()                              # the load's builds
    d0 = c.switch.dispatch_count
    _drive(c, txns)
    builds = [(s.t0, s.t1) for s in _spans(c) if s.name == "packet-build"]
    # a split group is built whole, then once per dispatched part
    assert len(calls) > c.switch.dispatch_count - d0 > 0
    for t0, t1 in calls:
        assert any(b0 <= t0 and t1 <= b1 for b0, b1 in builds), (t0, t1)


def _wal_records(c):
    return [[(r.lsn, r.kind, r.tid, r.payload, r.prev, r.hash)
             for r in n.wal] for n in c.nodes]


@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_tracing_changes_no_result_register_or_wal_record(workload):
    runs = [_serve(workload, True, tracer=Tracer(capacity=1 << 16)),
            _serve(workload, True),
            _serve(workload, True, telemetry=False)]
    (c0, _, out0), rest = runs[0], runs[1:]
    for c, _, out in rest:
        assert out == out0
        assert dict(c.stats) == dict(c0.stats)
        np.testing.assert_array_equal(c.switch.read_all(),
                                      c0.switch.read_all())
        assert _wal_records(c) == _wal_records(c0)


def test_wal_counters_match_the_logs():
    c, _, _ = _serve("ycsb", True, tracer=Tracer(capacity=1 << 16))
    w = c.wal_counters()
    assert w["records"] == sum(len(n.wal) for n in c.nodes) > 0
    assert w["bytes"] == sum(len(_canon([r.lsn, r.kind, r.tid, r.payload]))
                             for n in c.nodes for r in n.wal)
    assert w["append_s"] > 0.0
    # the default tracer leaves appends untimed; the counts still run
    d, _, _ = _serve("ycsb", True)
    assert d.wal_counters() == dict(w, append_s=0.0)


def test_engine_counts_its_transfers():
    eng = SwitchEngine(SW)
    txns = [Txn("w", [(WRITE, k, k + 1), (READ, k + 1, 0)], 0)
            for k in range(0, 12, 2)]
    hi = build_hot_index([[(k, 1) for k in range(12)]], 12, SW)
    pkts, meta = build_packets(txns, hi, SW)
    pb = eng.execute_batch(pkts, meta)
    Bp, K = engine._bucket(len(txns)), pkts["op"].shape[1]
    assert eng.h2d_bytes == N_PLANES * Bp * K * 4
    assert eng.d2h_bytes == 0                  # nothing copied back yet
    pb.results_np()
    pb.results_np()                            # cached: copied once
    Mp = min(engine._bucket(len(meta["gather_idx"])), Bp * K)
    assert eng.d2h_bytes == Mp * 4
    # release frees the device arrays; the host copy stays
    res = pb.results_np().copy()
    pb.release()
    assert pb.res is None and pb.ok is None and pb.compact is None
    np.testing.assert_array_equal(pb.results_np(), res)


@pytest.mark.parametrize("mode", ["serial", "staged", "affine"])
def test_each_engine_compiles_under_its_own_name(mode):
    fn = engine._compiled_engine(mode, 4, 64, 2, 4, 8)
    assert fn.as_text().startswith(f"HloModule jit_run.{mode},")


def test_gc_meter_counts_a_forced_collection():
    before = list(gc.callbacks)
    with GcMeter() as m:
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
    assert m.collections >= 1 and m.full_collections >= 1
    assert m.pause_s > 0.0
    assert gc.callbacks == before
    n = m.collections
    gc.collect()                               # stopped: not counted
    assert m.collections == n
    m.stop()                                   # a second stop is harmless
    assert gc.callbacks == before

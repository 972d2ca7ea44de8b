"""Shared setup for the paper-figure benchmarks."""
from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

from repro.core.hotset import build_hot_index
from repro.core.layout import random_layout
from repro.core.packets import SwitchConfig
from repro.sim.model import ClusterSim, SystemConfig, Timing, profile_txn
from repro.workloads import smallbank, tpcc, ycsb

# 12 MAU stages x 2 register arrays == 24 virtual stages (DESIGN.md)
SWITCH = SwitchConfig(n_stages=24, regs_per_stage=65536, max_instrs=16)
N_NODES = 8
SIM_TIME = 0.025
WARMUP = 0.005


def ycsb_profiles(variant="A", dist=0.2, hot_per_node=50, n=3000,
                  layout="optimal", top_k=None, seed=0, p_hot=0.75):
    p = ycsb.YCSBParams(n_nodes=N_NODES, hot_per_node=hot_per_node,
                        variant=variant, dist_frac=dist, p_hot_txn=p_hot)
    rng = np.random.default_rng(seed)
    sample = ycsb.generate(rng, 4000, p)
    lf = random_layout if layout == "random" else None
    kw = dict(layout_fn=lf) if lf else {}
    hi = build_hot_index(ycsb.traces(sample),
                         top_k=top_k or hot_per_node * N_NODES,
                         switch=SWITCH, **kw)
    txns = ycsb.generate(np.random.default_rng(seed + 1), n, p)
    return [profile_txn(t, hi, t.home) for t in txns], hi


def smallbank_profiles(hot_per_node=10, dist=0.2, n=3000, layout="optimal",
                       seed=0):
    p = smallbank.SmallBankParams(n_nodes=N_NODES, hot_per_node=hot_per_node,
                                  dist_frac=dist)
    rng = np.random.default_rng(seed)
    sample = smallbank.generate(rng, 6000, p)
    lf = random_layout if layout == "random" else None
    kw = dict(layout_fn=lf) if lf else {}
    hi = build_hot_index(smallbank.traces(sample),
                         top_k=hot_per_node * N_NODES * 2, switch=SWITCH,
                         **kw)
    txns = smallbank.generate(np.random.default_rng(seed + 1), n, p)
    return [profile_txn(t, hi, t.home) for t in txns], hi


def tpcc_profiles(warehouses=8, dist=None, n=3000, layout="optimal",
                  seed=0):
    """``dist``: the share of remote order lines and of remote payments
    alike; None keeps the spec's 1% and 15%."""
    p = tpcc.TPCCParams(n_nodes=N_NODES, n_warehouses=warehouses)
    if dist is not None:
        p.remote_line_frac = p.remote_payment_frac = dist
    rng = np.random.default_rng(seed)
    sample = tpcc.generate(rng, 20000, p)
    lf = random_layout if layout == "random" else None
    kw = dict(layout_fn=lf) if lf else {}
    hi = build_hot_index(tpcc.traces(sample), top_k=len(tpcc.hot_keys(p)),
                         switch=SWITCH, **kw)
    txns = tpcc.generate(np.random.default_rng(seed + 1), n, p)
    return [profile_txn(t, hi, t.home) for t in txns], hi


def run_sim(profiles, system: SystemConfig, workers=20, sim_time=SIM_TIME,
            seed=0, timing=None, batch_window=None, max_batch=None,
            pipeline_depth=None, nic_line_rate=None):
    """Run the timing sim; ``batch_window``/``max_batch``/
    ``pipeline_depth``/``nic_line_rate`` override the switch-admission
    knobs on ``system`` when given (None = keep)."""
    overrides = {k: v for k, v in dict(
        batch_window=batch_window, max_batch=max_batch,
        pipeline_depth=pipeline_depth, nic_line_rate=nic_line_rate).items()
        if v is not None}
    if overrides:
        system = replace(system, **overrides)
    cs = ClusterSim(profiles, N_NODES, workers, system,
                    timing=timing or Timing(), seed=seed,
                    sim_time=sim_time, warmup=WARMUP)
    return cs.run()


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, time.time() - t0


# ------------------------------------ batched switch-admission compare ----
# shared by benchmarks/run.py::bench_sim_batch and
# benchmarks/bench_batch.py::sim_batch so the CI smoke and the paper-figure
# run can never desynchronize their sweep grids / workload sets

SIM_BATCH_SWEEP_FAST = [(8, 5e-6), (32, 5e-6)]          # (max_batch, window)
SIM_BATCH_SWEEP_FULL = [(8, 5e-6), (16, 5e-6), (32, 2e-6), (32, 5e-6),
                        (64, 5e-6)]


def sim_batch_workloads(fast=True, n=3000):
    """(name, profiles) pairs for the admission comparison: YCSB A/B/C +
    SmallBank + all-hot YCSB-A (fast: YCSB-A + all-hot only)."""
    wl = [("ycsb_A", ycsb_profiles(variant="A", n=n)[0])]
    if not fast:
        wl += [("ycsb_B", ycsb_profiles(variant="B", n=n)[0]),
               ("ycsb_C", ycsb_profiles(variant="C", n=n)[0]),
               ("smallbank", smallbank_profiles(n=n)[0])]
    wl.append(("ycsb_A_allhot",
               ycsb_profiles(variant="A", n=n, p_hot=1.0)[0]))
    return wl


def sim_batch_compare(profiles, sweeps, sim_time=SIM_TIME):
    """Per-txn p4db baseline plus each batched (max_batch, window) point.

    Returns ``(per, rows)`` with rows = [(max_batch, window, out), ...]."""
    per = run_sim(profiles, SystemConfig(kind="p4db"), sim_time=sim_time)
    rows = [(mb, w, run_sim(profiles, SystemConfig(kind="p4db"),
                            sim_time=sim_time, batch_window=w,
                            max_batch=mb))
            for mb, w in sweeps]
    return per, rows


# ----------------------------------- pipelined switch-round sweep ---------
# shared by benchmarks/run.py::bench_sim_pipeline and
# benchmarks/bench_batch.py::sim_pipeline (BENCH_sim_pipeline.json): a
# depth x batch-size grid at the PR 2 gather window, locating the
# crossover batch size where batched admission starts beating per-txn

SIM_PIPELINE_WINDOW = 5e-6                       # PR 2's gather window
SIM_PIPELINE_DEPTHS_FAST = [1, 4]
SIM_PIPELINE_DEPTHS_FULL = [1, 2, 4, 8]
SIM_PIPELINE_BATCHES_FAST = [4, 32]
SIM_PIPELINE_BATCHES_FULL = [2, 4, 8, 16, 32]
NIC_10G = 1.25e9                                 # paper setup: 10G NICs


def sim_pipeline_workloads(fast=True, n=3000):
    """(name, profiles) pairs for the pipelined-round sweep: all-hot
    YCSB-A (the ceiling measurement) plus the standard YCSB-A mix."""
    wl = [("ycsb_A_allhot", ycsb_profiles(variant="A", n=n, p_hot=1.0)[0])]
    if not fast:
        wl.append(("ycsb_A", ycsb_profiles(variant="A", n=n)[0]))
    return wl


def sim_pipeline_compare(profiles, depths, batches, sim_time=SIM_TIME,
                         window=SIM_PIPELINE_WINDOW, nic_line_rate=None):
    """Per-txn p4db baseline plus each (depth, max_batch) grid point.

    Returns ``(per, rows)`` with rows = [(depth, max_batch, out), ...].
    ``nic_line_rate`` (when given) applies to the baseline AND the grid,
    so speedups stay apples-to-apples under explicit NIC serialization."""
    nic = dict(nic_line_rate=nic_line_rate) if nic_line_rate else {}
    per = run_sim(profiles, SystemConfig(kind="p4db"), sim_time=sim_time,
                  **nic)
    rows = [(d, mb, run_sim(profiles, SystemConfig(kind="p4db"),
                            sim_time=sim_time, batch_window=window,
                            max_batch=mb, pipeline_depth=d, **nic))
            for d in depths for mb in batches]
    return per, rows


# ------------------------------- adaptive hot-set management (drift) ------
# shared by benchmarks/bench_adaptive.py and benchmarks/run.py::
# bench_adaptive: drifting workloads, the static/adaptive/oracle trio, and
# the headline recovery ratio (BENCH_adaptive.json acceptance: adaptive
# restores >= 0.8x the per-epoch oracle's hot-txn rate; static decays)

DRIFT_PERIOD = 4e-3                    # seconds per drift phase
RECONFIG_INTERVAL = 0.4e-3             # adaptive controller epoch
TRACKER_DECAY = 0.1
ADAPTIVE_TOP_K = 400                   # = hot_per_node * N_NODES (ycsb)
ADAPTIVE_SIM_TIME_FAST = 0.014         # 3 full drift phases post-warmup
ADAPTIVE_SIM_TIME_FULL = 0.022         # 5


def adaptive_sim_time(fast: bool) -> float:
    return ADAPTIVE_SIM_TIME_FAST if fast else ADAPTIVE_SIM_TIME_FULL


def drift_generators(fast=True):
    """(name, generator, top_k) triples; fast keeps the YCSB hotspot
    shift only."""
    from repro.workloads import drift
    gens = [("ycsb_shift",
             drift.YCSBHotspotShift(n_nodes=N_NODES, period=DRIFT_PERIOD),
             ADAPTIVE_TOP_K)]
    if not fast:
        gens += [
            ("rotating_zipf",
             drift.RotatingZipf(n_nodes=N_NODES, period=DRIFT_PERIOD),
             50 * N_NODES),
            ("tpcc_rotation",
             drift.TPCCWarehouseRotation(n_nodes=N_NODES,
                                         period=DRIFT_PERIOD),
             None),                    # sized from the phase-0 hot set
        ]
    return gens


def drift_hot_index(gen, top_k, seed=0, n_sample=2000):
    """Initial (phase-0) placement — what a static deployment ships."""
    from repro.core.hotset import build_hot_index
    from repro.workloads import drift
    txns = gen.sample_phase(np.random.default_rng(seed), 0, n_sample)
    k = top_k if top_k is not None else len(set(gen.hot_keys_at(0.0)))
    return build_hot_index(drift.traces(txns), k, SWITCH), k


def run_drift_sim(gen, mode, top_k, sim_time, hot_index=None, workers=20,
                  seed=0, interval=RECONFIG_INTERVAL, system=None,
                  timing=None):
    """One drifting-workload sim run.  mode: 'static' (reconfig off —
    the placement shipped at phase 0 serves the whole run), 'adaptive'
    (tracker-driven epochs every ``interval``) or 'oracle' (ground-truth
    re-placement at each phase boundary)."""
    from repro.core.heat import HeatTracker
    if hot_index is None:
        hot_index, top_k = drift_hot_index(gen, top_k, seed=seed)
    sys_cfg = system or SystemConfig(kind="p4db")
    sys_cfg = replace(sys_cfg, reconfig_interval=0.0 if mode == "static"
                      else interval)
    tracker = HeatTracker(decay=TRACKER_DECAY) if mode == "adaptive" \
        else None
    # short warmup (vs the figure sweeps' 5 ms): phase 0 — where the
    # static placement is still correct — must appear in the measurement
    # window so the per-phase decay curve starts from its true baseline
    cs = ClusterSim([], N_NODES, workers, sys_cfg,
                    timing=timing or Timing(), seed=seed,
                    sim_time=sim_time, warmup=2e-3, dynamic=gen,
                    hot_index=hot_index, switch_cfg=SWITCH, tracker=tracker,
                    oracle=(mode == "oracle"), reconfig_top_k=top_k)
    return cs.run()


def run_drift_modes(gen, top_k, sim_time, hot_index=None,
                    modes=("static", "adaptive", "oracle")):
    """The static/adaptive/oracle trio over ONE drifting stream — the
    single driver behind both published artifacts (BENCH_adaptive.json
    via bench_adaptive.py and the bench_adaptive CSV via run.py), so
    they can never desynchronize their experiment."""
    if hot_index is None:
        hot_index, top_k = drift_hot_index(gen, top_k)
    return {mode: run_drift_sim(gen, mode, top_k, sim_time,
                                hot_index=hot_index)
            for mode in modes}


def adaptive_recovery_ratio(adaptive_out, oracle_out):
    """Headline: adaptive hot-txn rate as a fraction of the per-epoch
    oracle's (hot commits per post-warmup second).  Workloads that are
    warm-by-construction (TPC-C: every txn carries cold rows) have no
    fully-hot txns under ANY placement; there the switch-riding rate
    (hot + warm commits/s) is the drift-sensitive metric."""
    if oracle_out["hot_rate"] > 0:
        return adaptive_out["hot_rate"] / oracle_out["hot_rate"]
    return adaptive_out["switch_rate"] / max(oracle_out["switch_rate"],
                                             1e-9)


def static_decay_ratio(static_out):
    """Last-phase over first-phase hot share under the static placement
    — how much of the hot rate drift destroyed (switch share on
    warm-by-construction workloads, as above)."""
    ph = static_out["phase_hot_rate"]
    if not any(ph.values()):
        ph = static_out["phase_switch_rate"]
    first, last = min(ph), max(ph)
    return ph[last] / max(ph[first], 1e-9)


def pipeline_crossover(per, rows):
    """Per depth, the smallest max_batch whose throughput beats the
    per-txn baseline (None = no batch size wins at that depth)."""
    out = {}
    for d, mb, r in sorted(rows, key=lambda x: (x[0], x[1])):
        if d not in out and r["throughput"] > per["throughput"]:
            out[d] = mb
    return {d: out.get(d) for d in sorted({d for d, _, _ in rows})}


# --------------------------------------------- durability (PR 6) ----
# shared by benchmarks/bench_durability.py (BENCH_durability.json) and
# run.py::bench_durability (CSV figure) so the published artifact and
# the harness row can never desynchronize their experiment

DURABILITY_SWITCH = SwitchConfig(n_stages=16, regs_per_stage=2048,
                                 max_instrs=16)
DURABILITY_N_NODES = 4
DURABILITY_CHUNK = 64                    # txns per run_batch admission
# checkpoint every N switch sends; 0 = only the initial offload snapshot
DURABILITY_CKPT_INTERVALS_FAST = [0, 128, 32]
DURABILITY_CKPT_INTERVALS_FULL = [0, 512, 128, 32]
# sim failover sweep: seconds between incremental checkpoints
DURABILITY_SIM_CKPTS = [0.0, 2e-3, 0.5e-3]


# ------------------------------------ open-loop serving (PR 9) ------------
# shared by benchmarks/bench_serve.py (BENCH_serve.json) and the CI smoke
# so the published saturation curves and the harness row can never
# desynchronize their experiment.  The figure-sweep default SystemConfig
# folds NIC wire time and switch-ingress admission away (nic_line_rate=0,
# switch_service_rate=0 -> no serving bottleneck at any offered rate); the
# serving config makes both explicit so the open-loop sweep has a
# saturation knee INSIDE the swept range.

SERVE_SWITCH_RATE = 2e6          # shared switch-ingress admission, pkts/s
SERVE_ADMIT_CAP = 64             # queued arrivals/node before shedding
# offered rates as fractions of the p4db closed-loop capacity — the same
# absolute grid is swept for BOTH systems so the curves are comparable
# (>= 5 points per system, the BENCH_serve.json acceptance floor); the
# low end reaches down to 0.05x so the slower system's knee is still
# inside the grid, not censored at the floor
SERVE_FRACS = [0.05, 0.1, 0.15, 0.3, 0.6, 0.9, 1.2, 1.8]


def serve_system(kind="p4db"):
    """Bottlenecked serving config: explicit 10G NICs + finite switch
    ingress, batched hot admission (the PR 2 rounds).  Unlike the
    figure sweeps (which count committed txns and drop aborts, as the
    paper does), serving clients RETRY aborted txns — goodput stays
    ~= offered below saturation, and past it the retry load itself
    saturates the admit pool, so the knee is well-defined for abort-
    prone systems too (NoSwitch's contention aborts otherwise shave
    goodput at every load level and no 90%-of-offered point exists)."""
    return SystemConfig(kind=kind, max_batch=8, batch_window=5e-6,
                        nic_line_rate=NIC_10G,
                        switch_service_rate=SERVE_SWITCH_RATE,
                        drop_on_abort=False)


def run_open_loop_sim(profiles, system, rate, sim_time=SIM_TIME, seed=0,
                      workers=20, max_arrivals=None,
                      admit_queue_cap=SERVE_ADMIT_CAP):
    """One open-loop DES point: Poisson client sources at ``rate``/s
    (cluster-wide) instead of closed-loop workers; per-class admission
    rides the worker-slot pool, arrivals beyond ``admit_queue_cap``
    waiters are shed at the door."""
    cs = ClusterSim(profiles, N_NODES, workers, system, timing=Timing(),
                    seed=seed, sim_time=sim_time, warmup=WARMUP,
                    open_loop_rate=rate, max_arrivals=max_arrivals,
                    admit_queue_cap=admit_queue_cap)
    return cs.run()


def serve_sim_row(out):
    """Flatten one open-loop sim result into a ServeResult-shaped row
    (same keys as obs.load.serve_open_loop, so find_knee works on both)."""
    ol = out["open_loop"]
    lat = out["latency"].get("all", {})
    return dict(offered_rate=ol["offered_rate"],
                achieved_rate=ol["achieved_rate"],
                arrivals=ol["arrivals"], served=ol["served"],
                dropped=ol["dropped"],
                p50=lat.get("p50", 0.0), p99=lat.get("p99", 0.0),
                p999=lat.get("p999", 0.0), mean=lat.get("mean", 0.0),
                utilization=out["utilization"])


def durability_workload(n, seed=0, hot_per_node=16):
    """Mostly-hot YCSB stream + placement sized for DURABILITY_SWITCH —
    recovery work (replayed switch sends) dominates, which is the signal
    the checkpoint-interval sweep measures."""
    p = ycsb.YCSBParams(n_nodes=DURABILITY_N_NODES, keys_per_node=1000,
                        hot_per_node=hot_per_node)
    sample = ycsb.generate(np.random.default_rng(seed), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 4 * hot_per_node,
                         DURABILITY_SWITCH)
    txns = ycsb.generate(np.random.default_rng(seed + 1), n, p)
    return txns, hi


def _durability_cluster(hi, **kw):
    from repro.db.dbms import Cluster
    c = Cluster(DURABILITY_N_NODES, DURABILITY_SWITCH, hi, **kw)
    for k in list(hi.placement.slot)[:32]:
        c.load(k, 10)
    c.snapshot_offload()
    return c


def _durability_run(c, txns):
    for i in range(0, len(txns), DURABILITY_CHUNK):
        c.run_batch(txns[i:i + DURABILITY_CHUNK])
    c.drain()


def durability_recovery_row(txns, hi, interval):
    """Run the stream under one checkpoint interval, crash the switch,
    time the WAL-replay recovery; asserts byte-identical registers.
    Returns (cluster, row) — the cluster so callers can persist a WAL."""
    c = _durability_cluster(hi, checkpoint_interval=interval)
    _durability_run(c, txns)
    before = np.asarray(c.switch.registers).copy()
    (known, unknown), dt = timed(c.crash_switch_and_recover)
    assert np.array_equal(before, np.asarray(c.switch.registers)), \
        f"recovery diverged at interval={interval}"
    return c, dict(interval=interval, recover_s=dt,
                   replayed=known + unknown,
                   checkpoints=int(c.stats["checkpoints"]),
                   wal_records=sum(len(n.wal) for n in c.nodes))


def durability_standby_row(txns, hi, interval):
    """Same stream with a warm standby: time the takeover and assert the
    bounded-recovery contract (replayed == sends since last checkpoint)."""
    c = _durability_cluster(hi, checkpoint_interval=interval, standby=True)
    _durability_run(c, txns)
    before = np.asarray(c.switch.registers).copy()
    since = c._sends_since_ckpt
    (known, unknown), dt = timed(c.fail_over)
    assert known + unknown == since, \
        f"unbounded takeover: replayed {known + unknown}, expected {since}"
    assert np.array_equal(before, np.asarray(c.switch.registers)), \
        "failover diverged"
    return dict(interval=interval, takeover_s=dt, replayed=known + unknown)


def storm_profiles(gen_name="ycsb_a_storm", n=1500, seed=0, n_nodes=4,
                   params=None):
    """Cold TxnProfiles for a contention storm (PR 10): every txn is
    cold-classified (hot_index=None), so the whole stream funnels through
    the 2PL/2PC path the early-abort detector watches.  Returns
    ``(profiles, params)``; seed the sim's contended locks with
    ``ClusterSim.lock_of(k) for k in storms.contended_keys(params)``."""
    from repro.workloads import storms
    p = params or storms.StormParams(n_nodes=n_nodes)
    gen = getattr(storms, gen_name)
    txns = gen(np.random.default_rng(seed), n, p)
    return [profile_txn(t, None, t.home) for t in txns], p


def durability_sim_rows(sim_time=0.01, seed=3,
                        ckpt_intervals=tuple(DURABILITY_SIM_CKPTS)):
    """Priced failover in the DES: one switch crash at 70% of the run,
    outage = t_failover + replayed sends * t_replay_send, swept over the
    checkpoint cadence that bounds the replay term."""
    profs, _ = ycsb_profiles(n=1500)
    rows = []
    for ck in ckpt_intervals:
        r = run_sim(profs, SystemConfig(kind="p4db", max_batch=8,
                                        crash_at=0.7 * sim_time,
                                        ckpt_interval=ck),
                    sim_time=sim_time, seed=seed)
        rows.append(dict(interval=ck,
                         outage_s=r["failover"]["outage"],
                         replayed=r["failover"]["replayed"],
                         throughput=r["throughput"]))
    return rows

"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = mean committed
txn latency in the simulated cluster; derived = the figure's headline
metric, usually speedup over No-Switch).  Full per-point CSVs are written
to artifacts/bench/.

  fig11  YCSB speedup vs contention + vs %distributed
  fig12  YCSB hot/cold commit breakdown
  fig13  SmallBank speedup (hot-set sizes, %distributed)
  fig14  TPC-C speedup (warehouses, %distributed)
  fig15  hot-ratio sweep + multi-pass optimization stack
  fig16  optimal vs random data layout (throughput + latency)
  fig17  hot-set exceeding switch capacity (graceful degradation)
  fig18  TPC-C latency breakdown + existing-optimization stack
  bench_adaptive  drifting hot set: static vs adaptive vs oracle placement
  bench_durability  recovery time vs checkpoint interval + priced failover
  engine switch-engine execution modes (serial / affine / staged)
"""
from __future__ import annotations

import csv
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common as C
from repro.sim.model import SystemConfig

OUT = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")
ROWS = []


def emit(name, us_per_call, derived):
    print(f"{name},{us_per_call:.2f},{derived}")
    ROWS.append((name, us_per_call, derived))


def save_csv(name, header, rows):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{name}.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)


# ------------------------------------------------------------- fig 11 ----

def fig11_ycsb(fast=True):
    rows = []
    workers_list = [8, 20] if fast else [8, 12, 16, 20]
    for variant in "ABC":
        profs, _ = C.ycsb_profiles(variant=variant)
        for w in workers_list:
            r = {}
            for sysk in ("p4db", "noswitch", "lmswitch"):
                out = C.run_sim(profs, SystemConfig(kind=sysk), workers=w)
                r[sysk] = out
            sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
            sl = r["lmswitch"]["throughput"] / max(
                r["noswitch"]["throughput"], 1)
            rows.append([variant, w, r["p4db"]["throughput"],
                         r["noswitch"]["throughput"],
                         r["lmswitch"]["throughput"], sp, sl])
            if w == workers_list[-1]:
                emit(f"fig11_ycsb{variant}_contention",
                     r["p4db"].get("lat_all", 0) * 1e6,
                     f"speedup={sp:.2f}x lm={sl:.2f}x")
    # distributed-txn sweep (lower row)
    for variant in ("A",) if fast else "ABC":
        for dist in ([0.0, 0.5, 1.0] if fast else [0, .25, .5, .75, 1.0]):
            profs, _ = C.ycsb_profiles(variant=variant, dist=dist)
            r = {}
            for sysk in ("p4db", "noswitch"):
                r[sysk] = C.run_sim(profs, SystemConfig(kind=sysk))
            sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
            rows.append([f"{variant}_dist", dist, r["p4db"]["throughput"],
                         r["noswitch"]["throughput"], "", sp, ""])
            emit(f"fig11_ycsb{variant}_dist{int(dist * 100)}",
                 r["p4db"].get("lat_all", 0) * 1e6, f"speedup={sp:.2f}x")
    save_csv("fig11_ycsb", ["variant", "workers_or_dist", "p4db",
                            "noswitch", "lmswitch", "speedup", "lm_speedup"],
             rows)


def fig12_breakdown():
    rows = []
    for variant in "AC":
        profs, _ = C.ycsb_profiles(variant=variant)
        for sysk in ("p4db", "noswitch"):
            out = C.run_sim(profs, SystemConfig(kind=sysk))
            c = out["commits"]
            hot = c.get("hot", 0)
            cold = c.get("cold", 0) + c.get("warm", 0)
            tot = max(hot + cold, 1)
            rows.append([variant, sysk, out["throughput"], hot / tot,
                         cold / tot, sum(out["aborts"].values())])
            emit(f"fig12_breakdown_{variant}_{sysk}",
                 out.get("lat_all", 0) * 1e6,
                 f"hot_frac={hot / tot:.2f} tput={out['throughput']:.0f}")
    save_csv("fig12_breakdown", ["variant", "system", "tput", "hot_frac",
                                 "cold_frac", "aborts"], rows)


def fig13_smallbank(fast=True):
    rows = []
    for hs in ([5, 15] if fast else [5, 10, 15]):
        profs, hi = C.smallbank_profiles(hot_per_node=hs)
        for w in ([20] if fast else [8, 12, 16, 20]):
            r = {}
            for sysk in ("p4db", "noswitch"):
                r[sysk] = C.run_sim(profs, SystemConfig(kind=sysk),
                                    workers=w)
            sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
            rows.append([hs, w, r["p4db"]["throughput"],
                         r["noswitch"]["throughput"], sp])
            emit(f"fig13_smallbank_hs{hs}_w{w}",
                 r["p4db"].get("lat_all", 0) * 1e6, f"speedup={sp:.2f}x")
    for dist in [0.0, 0.5, 1.0]:
        profs, _ = C.smallbank_profiles(hot_per_node=10, dist=dist)
        r = {k: C.run_sim(profs, SystemConfig(kind=k))
             for k in ("p4db", "noswitch")}
        sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
        rows.append([f"dist{dist}", 20, r["p4db"]["throughput"],
                     r["noswitch"]["throughput"], sp])
        emit(f"fig13_smallbank_dist{int(dist * 100)}",
             r["p4db"].get("lat_all", 0) * 1e6, f"speedup={sp:.2f}x")
    save_csv("fig13_smallbank", ["hotset_or_dist", "workers", "p4db",
                                 "noswitch", "speedup"], rows)


def fig14_tpcc(fast=True):
    rows = []
    for wh in ([8, 32] if fast else [8, 16, 32]):
        profs, _ = C.tpcc_profiles(warehouses=wh)
        r = {k: C.run_sim(profs, SystemConfig(kind=k))
             for k in ("p4db", "noswitch")}
        sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
        rows.append([wh, "spec", r["p4db"]["throughput"],
                     r["noswitch"]["throughput"], sp])
        emit(f"fig14_tpcc_wh{wh}", r["p4db"].get("lat_all", 0) * 1e6,
             f"speedup={sp:.2f}x")
    for dist in [0.0, 0.5, 1.0]:
        profs, _ = C.tpcc_profiles(warehouses=8, dist=dist)
        r = {k: C.run_sim(profs, SystemConfig(kind=k))
             for k in ("p4db", "noswitch")}
        sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
        rows.append([8, dist, r["p4db"]["throughput"],
                     r["noswitch"]["throughput"], sp])
        emit(f"fig14_tpcc_dist{int(dist * 100)}",
             r["p4db"].get("lat_all", 0) * 1e6, f"speedup={sp:.2f}x")
    save_csv("fig14_tpcc", ["warehouses", "dist", "p4db", "noswitch",
                            "speedup"], rows)


def fig15_hotratio_and_opts(fast=True):
    rows = []
    for ph in [0.0, 0.25, 0.5, 0.75, 1.0]:
        p4, ns = None, None
        import repro.workloads.ycsb as Y
        import numpy as np
        from repro.core.hotset import build_hot_index
        from repro.sim.model import profile_txn
        p = Y.YCSBParams(n_nodes=C.N_NODES, hot_per_node=50, variant="A",
                         dist_frac=0.2, p_hot_txn=ph)
        sample = Y.generate(np.random.default_rng(0), 4000, p)
        hi = build_hot_index(Y.traces(sample), top_k=400, switch=C.SWITCH)
        txns = Y.generate(np.random.default_rng(1), 3000, p)
        profs = [profile_txn(t, hi, t.home) for t in txns]
        r = {k: C.run_sim(profs, SystemConfig(kind=k))
             for k in ("p4db", "noswitch")}
        sp = r["p4db"]["throughput"] / max(r["noswitch"]["throughput"], 1)
        rows.append([ph, r["p4db"]["throughput"], r["noswitch"]["throughput"],
                     sp])
        emit(f"fig15ab_hotratio{int(ph * 100)}",
             r["p4db"].get("lat_all", 0) * 1e6, f"speedup={sp:.2f}x")
    save_csv("fig15ab_hotratio", ["p_hot", "p4db", "noswitch", "speedup"],
             rows)

    # fig15c: optimization stack for multi-pass txns (hot txns only).
    # random layout -> multi-pass heavy; then +fast-recirc, +2-bit locks,
    # then the optimal declustered layout.
    rows = []
    base_profs, _ = C.ycsb_profiles(variant="A", layout="random",
                                    hot_per_node=50)
    hot_only = [p for p in base_profs if p.klass == "hot"]
    opt_profs, _ = C.ycsb_profiles(variant="A", layout="optimal",
                                   hot_per_node=50)
    hot_opt = [p for p in opt_profs if p.klass == "hot"]
    configs = [
        ("unoptimized", hot_only, SystemConfig(pipeline_locks=1,
                                               fast_recirc=False)),
        ("+fast_recirc", hot_only, SystemConfig(pipeline_locks=1,
                                                fast_recirc=True)),
        ("+2bit_locks", hot_only, SystemConfig(pipeline_locks=2,
                                               fast_recirc=True)),
        ("+opt_layout", hot_opt, SystemConfig(pipeline_locks=2,
                                              fast_recirc=True)),
    ]
    base_tput = None
    for name, profs, sysc in configs:
        out = C.run_sim(profs, sysc)
        if base_tput is None:
            base_tput = out["throughput"]
        rows.append([name, out["throughput"],
                     out["throughput"] / base_tput])
        emit(f"fig15c_{name}", out.get("lat_all", 0) * 1e6,
             f"speedup_vs_unopt={out['throughput'] / base_tput:.2f}x")
    save_csv("fig15c_opts", ["config", "tput", "speedup_vs_unopt"], rows)


def fig16_layout(fast=True):
    rows = []
    for wl, mk in [("ycsb", C.ycsb_profiles), ("smallbank",
                                               C.smallbank_profiles),
                   ("tpcc", C.tpcc_profiles)]:
        for layout in ("optimal", "random"):
            profs, hi = mk(layout=layout)
            out = C.run_sim(profs, SystemConfig(kind="p4db"))
            spr = hi.placement.stats.get("single_pass_rate", 1.0)
            rows.append([wl, layout, out["throughput"],
                         out.get("lat_all", 0) * 1e6, spr])
            emit(f"fig16_layout_{wl}_{layout}",
                 out.get("lat_all", 0) * 1e6,
                 f"tput={out['throughput']:.0f} single_pass={spr:.2f}")
    save_csv("fig16_layout", ["workload", "layout", "tput", "lat_us",
                              "single_pass_rate"], rows)


def fig17_capacity(fast=True):
    """Hot-set grows past switch capacity: overflowed tuples stay on nodes
    (classify as cold/warm) -> graceful degradation."""
    rows = []
    capacities = [400] if fast else [200, 400, 800]
    hotsizes = [50, 100, 200, 400] if fast else [25, 50, 100, 200, 400, 800]
    for cap in capacities:
        for hs in hotsizes:
            profs, _ = C.ycsb_profiles(variant="A", hot_per_node=hs,
                                       top_k=min(cap, hs * C.N_NODES))
            out = C.run_sim(profs, SystemConfig(kind="p4db"))
            ns = C.run_sim(profs, SystemConfig(kind="noswitch"))
            rows.append([cap, hs * C.N_NODES, out["throughput"],
                         ns["throughput"]])
            emit(f"fig17_cap{cap}_hot{hs * C.N_NODES}",
                 out.get("lat_all", 0) * 1e6,
                 f"tput={out['throughput']:.0f} "
                 f"ratio_vs_noswitch={out['throughput'] / max(ns['throughput'], 1):.2f}")
    save_csv("fig17_capacity", ["switch_capacity", "hotset", "p4db",
                                "noswitch"], rows)


def fig18_latency_and_optstack(fast=True):
    rows = []
    profs, _ = C.tpcc_profiles(warehouses=8)
    for sysk in ("p4db", "noswitch"):
        out = C.run_sim(profs, SystemConfig(kind=sysk))
        bd = out["breakdown"]
        tot = sum(bd.values()) or 1
        parts = {k: v / tot for k, v in sorted(bd.items())}
        rows.append([sysk, out.get("lat_all", 0) * 1e6, str(parts)])
        emit(f"fig18a_latency_{sysk}", out.get("lat_all", 0) * 1e6,
             " ".join(f"{k}={v:.2f}" for k, v in parts.items()))
    save_csv("fig18a_latency", ["system", "lat_us", "breakdown"], rows)

    # fig18b: Plain 2PL/2PC (80% dist) -> +opt partitioning (20% dist)
    # -> +Chiller-like early lock release -> P4DB
    rows = []
    profs80, _ = C.tpcc_profiles(warehouses=8, dist=0.8)
    profs20, _ = C.tpcc_profiles(warehouses=8, dist=0.2)
    stack = [
        ("plain_2pl_2pc", profs80, SystemConfig(kind="noswitch")),
        ("+opt_partitioning", profs20, SystemConfig(kind="noswitch")),
        ("+chiller_early_release", profs20,
         SystemConfig(kind="noswitch", early_release=True)),
        ("p4db", profs20, SystemConfig(kind="p4db")),
    ]
    base = None
    for name, profs, sysc in stack:
        out = C.run_sim(profs, sysc)
        base = base or out["throughput"]
        rows.append([name, out["throughput"], out["throughput"] / base])
        emit(f"fig18b_{name}", out.get("lat_all", 0) * 1e6,
             f"speedup_vs_plain={out['throughput'] / base:.2f}x")
    save_csv("fig18b_optstack", ["config", "tput", "speedup"], rows)


def bench_sim_batch(fast=True):
    """Batched vs per-txn switch admission in the timing sim (the batched
    hot-path pipeline's amortized rtt_switch, ISSUE 2): YCSB A/B/C +
    SmallBank + all-hot YCSB-A, p4db, per-txn (batch_window=0/max_batch=1)
    against batched rounds."""
    rows = []
    sweeps = C.SIM_BATCH_SWEEP_FAST if fast else C.SIM_BATCH_SWEEP_FULL
    for name, profs in C.sim_batch_workloads(fast=False):
        per, pts = C.sim_batch_compare(profs, sweeps)
        rows.append([name, 1, 0.0, per["throughput"], 1.0, 0,
                     per.get("lat_all", 0) * 1e6])
        best = per
        for mb, w, out in pts:
            sp = out["throughput"] / max(per["throughput"], 1)
            rows.append([name, mb, w, out["throughput"], sp,
                         out["avg_batch"], out.get("lat_all", 0) * 1e6])
            if out["throughput"] > best["throughput"]:
                best = out
        emit(f"sim_batch_{name}", best.get("lat_all", 0) * 1e6,
             f"best_batched_speedup="
             f"{best['throughput'] / max(per['throughput'], 1):.2f}x")
    save_csv("bench_sim_batch", ["workload", "max_batch", "window_s",
                                 "tput", "speedup_vs_per_txn", "avg_batch",
                                 "lat_us"], rows)


def bench_sim_pipeline(fast=True):
    """Pipelined switch rounds in the timing sim (ISSUE 3): depth x
    batch-size grid over all-hot YCSB-A (+ the standard mix when full),
    with and without explicit 10G NIC serialization.  depth=1 is the PR 2
    serialized model; the crossover column records the smallest batch
    size beating the per-txn baseline at each depth."""
    rows = []
    depths = C.SIM_PIPELINE_DEPTHS_FAST if fast \
        else C.SIM_PIPELINE_DEPTHS_FULL
    batches = C.SIM_PIPELINE_BATCHES_FAST if fast \
        else C.SIM_PIPELINE_BATCHES_FULL
    for name, profs in C.sim_pipeline_workloads(fast=fast):
        for label, nic in (("no_nic", None), ("nic_10g", C.NIC_10G)):
            per, pts = C.sim_pipeline_compare(profs, depths, batches,
                                              nic_line_rate=nic)
            cross = C.pipeline_crossover(per, pts)
            rows.append([name, label, 0, 1, per["throughput"], 1.0, 0,
                         per.get("lat_all", 0) * 1e6, ""])
            best = per
            for d, mb, out in pts:
                sp = out["throughput"] / max(per["throughput"], 1)
                rows.append([name, label, d, mb, out["throughput"], sp,
                             out["avg_batch"],
                             out.get("lat_all", 0) * 1e6, cross.get(d)])
                if out["throughput"] > best["throughput"]:
                    best = out
            emit(f"sim_pipeline_{name}_{label}",
                 best.get("lat_all", 0) * 1e6,
                 f"best_speedup="
                 f"{best['throughput'] / max(per['throughput'], 1):.2f}x "
                 f"crossover={ {d: cross.get(d) for d in depths} }")
    save_csv("bench_sim_pipeline",
             ["workload", "nic", "depth", "max_batch", "tput",
              "speedup_vs_per_txn", "avg_batch", "lat_us",
              "crossover_batch"], rows)


def bench_adaptive(fast=True):
    """Adaptive hot-set management under drift (ISSUE 4): the same
    drifting stream under static / adaptive / per-epoch-oracle placement;
    the figure is hot-txn rate per drift phase plus the adaptive/oracle
    recovery ratio (acceptance bar 0.8, recorded in
    BENCH_adaptive.json)."""
    rows = []
    sim_time = C.adaptive_sim_time(fast)
    for name, gen, top_k in C.drift_generators(fast):
        outs = C.run_drift_modes(gen, top_k, sim_time)
        for mode, out in outs.items():
            for ph, hr in sorted(out["phase_hot_rate"].items()):
                rows.append([name, mode, ph, out["throughput"],
                             out["hot_rate"], hr, out["reconfigs"]])
        ratio = C.adaptive_recovery_ratio(outs["adaptive"], outs["oracle"])
        decay = C.static_decay_ratio(outs["static"])
        emit(f"adaptive_{name}",
             outs["adaptive"].get("lat_all", 0) * 1e6,
             f"adaptive_vs_oracle={ratio:.2f} static_decay={decay:.2f} "
             f"reconfigs={outs['adaptive']['reconfigs']}")
    save_csv("bench_adaptive", ["workload", "mode", "phase", "tput",
                                "hot_rate", "phase_hot_rate", "reconfigs"],
             rows)


# ------------------------------------------------------------ summary ----
# every BENCH_*.json artifact at the repo root and where its headline
# ratio lives — the one-table trajectory view of the repo's PRs
SUMMARY_HEADLINES = [
    ("BENCH_batch.json", ("headline_ycsb_a_hot256", "speedup"),
     "batched vs per-txn switch dispatch (functional, PR 1)"),
    ("BENCH_sim_batch.json", ("headline_allhot_speedup",),
     "batched switch admission vs per-txn (timing sim, PR 2)"),
    ("BENCH_sim_pipeline.json", ("headline_pipelined_speedup",),
     "pipelined switch rounds vs per-txn (timing sim, PR 3)"),
    ("BENCH_adaptive.json", ("headline_adaptive_vs_oracle",),
     "adaptive vs oracle hot rate under drift (PR 4)"),
    ("BENCH_durability.json", ("headline_recovery_speedup",),
     "bounded recovery: checkpointed vs full-WAL replay (PR 6)"),
    ("BENCH_multiswitch.json", ("headline_multiswitch_speedup",),
     "sharded 4-switch plane vs capacity-capped 1 switch (PR 7)"),
    ("BENCH_reads.json", ("headline_read_speedup",),
     "switch-served hot reads vs store-served baseline (PR 8)"),
    ("BENCH_serve.json", ("headline_serve_knee_ratio",),
     "open-loop saturation knee: p4db vs noswitch serving (PR 9)"),
    ("BENCH_contention.json", ("headline_wasted_work_reduction",),
     "wasted-work cut by network-assisted early aborts (PR 10)"),
]


def bench_summary():
    """Collate the headline ratio of every BENCH_*.json into one
    trajectory table (stdout + artifacts/bench/summary_trajectory.csv).
    Missing artifacts are reported, not fatal — regenerate them with the
    commands in the README bench table."""
    root = os.path.join(os.path.dirname(__file__), "..")
    rows = []
    print(f"{'artifact':25s} {'headline':>9s}  meaning")
    for fname, path, desc in SUMMARY_HEADLINES:
        try:
            with open(os.path.join(root, fname)) as f:
                v = json.load(f)
            for k in path:
                v = v[k]
            val = f"{v:.2f}x"
        except (FileNotFoundError, KeyError, json.JSONDecodeError):
            v, val = "", "missing"
        rows.append([fname, ".".join(path), v, desc])
        print(f"{fname:25s} {val:>9s}  {desc}")
    save_csv("summary_trajectory",
             ["artifact", "metric", "value", "meaning"], rows)
    return rows


def bench_durability(fast=True):
    """Bounded recovery + priced failover (PR 6): recovery time vs
    checkpoint interval on the functional cluster, and the DES failover
    outage vs checkpoint cadence.  The published artifact
    (BENCH_durability.json) comes from benchmarks/bench_durability.py —
    both drive the same helpers in benchmarks/common.py."""
    n = 400 if fast else 2000
    intervals = C.DURABILITY_CKPT_INTERVALS_FAST if fast \
        else C.DURABILITY_CKPT_INTERVALS_FULL
    txns, hi = C.durability_workload(n)
    rows = []
    base = None
    for interval in intervals:
        _, row = C.durability_recovery_row(txns, hi, interval)
        rows.append([interval, row["recover_s"] * 1e3, row["replayed"],
                     row["checkpoints"]])
        if base is None:
            base = row
        emit(f"durability_recover_ck{interval}", row["recover_s"] * 1e6,
             f"{base['recover_s'] / max(row['recover_s'], 1e-9):.1f}x "
             f"faster than unckpt")
    save_csv("bench_durability_recovery",
             ["ckpt_interval", "recover_ms", "replayed", "checkpoints"],
             rows)
    sim_rows = C.durability_sim_rows(sim_time=0.01 if fast else 0.02)
    save_csv("bench_durability_sim_failover",
             ["ckpt_interval_s", "outage_s", "replayed", "throughput"],
             [[r["interval"], r["outage_s"], r["replayed"],
               r["throughput"]] for r in sim_rows])
    for r in sim_rows:
        emit(f"durability_sim_ck{r['interval']:g}", r["outage_s"] * 1e6,
             f"{r['replayed']} sends replayed at takeover")


def engine_micro():
    """Switch-engine execution modes on one batch (functional layer)."""
    import jax
    import numpy as np
    from repro.core.engine import SwitchEngine
    from repro.core.packets import SwitchConfig, empty_packets

    cfg = SwitchConfig(n_stages=12, regs_per_stage=4096, max_instrs=8)
    rng = np.random.default_rng(0)
    B, K = 4096, 8
    p = empty_packets(B, cfg)
    p["op"] = rng.integers(1, 4, (B, K)).astype(np.int32)
    p["stage"] = np.sort(rng.integers(0, 12, (B, K)), axis=1).astype(np.int32)
    p["reg"] = rng.integers(0, 4096, (B, K)).astype(np.int32)
    p["operand"] = rng.integers(-100, 100, (B, K)).astype(np.int32)
    rows = []
    for mode in ("serial", "affine", "staged"):
        eng = SwitchEngine(cfg)
        eng.execute(p, mode=mode)  # compile
        t0 = time.time()
        n = 3
        for _ in range(n):
            eng.execute(p, mode=mode)
        jax.block_until_ready(eng.registers)
        us = (time.time() - t0) / (n * B) * 1e6
        rows.append([mode, us])
        emit(f"engine_{mode}", us, f"{1e6 / max(us, 1e-9):.0f} txn/s")
    save_csv("engine_micro", ["mode", "us_per_txn"], rows)


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="P4DB paper-figure benchmark harness; see module "
                    "docstring for the figure list.  Writes per-point "
                    "CSVs to artifacts/bench/.")
    ap.add_argument("--full", action="store_true",
                    help="full sweep grids (default: fast subsets)")
    ap.add_argument("--summary", action="store_true",
                    help="only collate the headline ratio of every "
                         "BENCH_*.json artifact into one trajectory table")
    args = ap.parse_args()
    if args.summary:
        bench_summary()
        return
    fast = not args.full
    t0 = time.time()
    fig11_ycsb(fast)
    fig12_breakdown()
    fig13_smallbank(fast)
    fig14_tpcc(fast)
    fig15_hotratio_and_opts(fast)
    fig16_layout(fast)
    fig17_capacity(fast)
    fig18_latency_and_optstack(fast)
    bench_sim_batch(fast)
    bench_sim_pipeline(fast)
    bench_adaptive(fast)
    bench_durability(fast)
    engine_micro()
    bench_summary()
    save_csv("summary", ["name", "us_per_call", "derived"], ROWS)
    print(f"# benchmarks done in {time.time() - t0:.0f}s "
          f"({len(ROWS)} rows) -> artifacts/bench/")


if __name__ == "__main__":
    main()

"""Committed-txn throughput: per-txn loop vs the batched hot-path pipeline.

Runs YCSB A/B/C and SmallBank through a functional Cluster twice — once via
``run(t)`` per transaction (one switch dispatch per hot txn) and once via
``run_batch`` at several batch sizes (one dispatch per hot group) — and
reports throughput plus engine dispatch counts.  The headline measurement
is a 256-txn all-hot YCSB-A batch: 1 dispatch vs 256 and the resulting
hot-txn throughput ratio.

A second section runs the TIMING simulator (``repro.sim``) with the
matching batched switch-admission model: per-txn rounds
(batch_window=0/max_batch=1, pinned to reproduce the defaults exactly)
against batched rounds across YCSB A/B/C + SmallBank + all-hot YCSB-A.

A third section sweeps PIPELINED switch rounds (``pipeline_depth`` x
``max_batch``, with and without explicit 10G NIC serialization): depth=1
is the serialized PR 2 model, depth>1 overlaps round k+1's assembly with
round k's flight and records the crossover batch size where batched
admission starts beating 20 synchronous workers.

  PYTHONPATH=src python benchmarks/bench_batch.py \\
      [--fast] [--sim-only] [--pipeline-only] [--no-sim] \\
      [--out FILE] [--out-sim FILE] [--out-sim-pipeline FILE]

Emits BENCH_batch.json, BENCH_sim_batch.json and BENCH_sim_pipeline.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.compile_cache import enable_compile_cache  # noqa: E402

import numpy as np

from repro.core.hotset import build_hot_index
from repro.core.packets import SwitchConfig
from repro.db.dbms import Cluster
from repro.workloads import smallbank, ycsb

SW = SwitchConfig(n_stages=16, regs_per_stage=1024, max_instrs=16)
N_NODES = 4


def ycsb_workload(variant, n, all_hot=False):
    p = ycsb.YCSBParams(n_nodes=N_NODES, keys_per_node=2000, hot_per_node=16,
                        variant=variant,
                        p_hot_txn=1.0 if all_hot else 0.75)
    sample = ycsb.generate(np.random.default_rng(0), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 16 * N_NODES, SW)
    txns = ycsb.generate(np.random.default_rng(1), n, p)
    return txns, hi, []


def smallbank_workload(n):
    p = smallbank.SmallBankParams(n_nodes=N_NODES, accounts_per_node=200,
                                  hot_per_node=8)
    sample = smallbank.generate(np.random.default_rng(0), 3000, p)
    hi = build_hot_index(smallbank.traces(sample), 8 * N_NODES * 2, SW)
    txns = smallbank.generate(np.random.default_rng(1), n, p)
    return txns, hi, [(k, 10_000) for k in smallbank.hot_keys(p)]


def fresh_cluster(hi, loads):
    c = Cluster(N_NODES, SW, hi, use_switch=True)
    for k, v in loads:
        c.load(k, v)
    return c


def run_per_txn(txns, hi, loads):
    c = fresh_cluster(hi, loads)
    t0 = time.perf_counter()
    for t in txns:
        c.run(t)
    dt = time.perf_counter() - t0
    return c, dt


def run_batched(txns, hi, loads, batch_size):
    c = fresh_cluster(hi, loads)
    t0 = time.perf_counter()
    for i in range(0, len(txns), batch_size):
        c.run_batch(txns[i:i + batch_size])
    dt = time.perf_counter() - t0
    return c, dt


def record(c, dt, n):
    return dict(time_s=round(dt, 6),
                commits=int(c.stats["commits"]),
                hot=int(c.stats["hot"]),
                txn_per_s=round(n / dt, 1),
                committed_per_s=round(c.stats["commits"] / dt, 1),
                dispatches=int(c.switch.dispatch_count))


def bench_workload(name, txns, hi, loads, batch_sizes):
    # warm run first so jit/AOT compiles are off the clock, then measure
    run_per_txn(list(txns), hi, loads)
    c, dt = run_per_txn(list(txns), hi, loads)
    out = {"n_txns": len(txns), "per_txn": record(c, dt, len(txns)),
           "batched": {}}
    for bs in batch_sizes:
        run_batched(list(txns), hi, loads, bs)
        c, dt = run_batched(list(txns), hi, loads, bs)
        r = record(c, dt, len(txns))
        r["speedup_vs_per_txn"] = round(
            r["committed_per_s"] / out["per_txn"]["committed_per_s"], 2)
        out["batched"][str(bs)] = r
    best = max(out["batched"].values(), key=lambda r: r["committed_per_s"])
    print(f"  {name:12s} per-txn {out['per_txn']['committed_per_s']:>10.0f} "
          f"commits/s ({out['per_txn']['dispatches']} dispatches)  "
          f"best batched {best['committed_per_s']:>10.0f} commits/s "
          f"({best['dispatches']} dispatches, "
          f"{best['speedup_vs_per_txn']}x)")
    return out


def bench_headline():
    """256 all-hot YCSB-A txns: exactly 1 dispatch vs 256."""
    txns, hi, loads = ycsb_workload("A", 256, all_hot=True)
    c = fresh_cluster(hi, loads)
    assert all(c.classify(t) == "hot" for t in txns), "headline needs hot"
    # warm both paths
    run_per_txn(list(txns), hi, loads)
    run_batched(list(txns), hi, loads, 256)
    c1, dt1 = run_per_txn(list(txns), hi, loads)
    c2, dt2 = run_batched(list(txns), hi, loads, 256)
    assert c1.switch.dispatch_count == 256, c1.switch.dispatch_count
    assert c2.switch.dispatch_count == 1, c2.switch.dispatch_count
    assert c1.stats["commits"] == c2.stats["commits"] == 256
    speedup = dt1 / dt2
    print(f"  headline: 256-txn all-hot YCSB-A batch — dispatches "
          f"{c1.switch.dispatch_count} -> {c2.switch.dispatch_count}, "
          f"hot-txn throughput {256 / dt1:,.0f} -> {256 / dt2:,.0f} "
          f"commits/s ({speedup:.1f}x)")
    return dict(n_txns=256,
                per_txn=record(c1, dt1, 256),
                batched_256=record(c2, dt2, 256),
                speedup=round(speedup, 2))


def sim_batch(fast: bool, out_path: str):
    """Timing-sim batched admission: per-txn vs batched switch rounds."""
    from benchmarks import common as C
    from repro.sim.model import SystemConfig

    sim_time = 0.01 if fast else C.SIM_TIME
    n = 1000 if fast else 3000
    sweeps = C.SIM_BATCH_SWEEP_FAST if fast else C.SIM_BATCH_SWEEP_FULL
    workloads = C.sim_batch_workloads(fast, n=n)

    results = {"config": dict(fast=fast, sim_time=sim_time, n_profiles=n,
                              sweeps=[list(s) for s in sweeps])}

    # regression pin: explicit batch_window=0/max_batch=1 must reproduce
    # the default (per-txn) admission exactly
    profs = workloads[0][1]
    base = C.run_sim(profs, SystemConfig(kind="p4db"), sim_time=sim_time)
    pinned = C.run_sim(profs, SystemConfig(kind="p4db"), sim_time=sim_time,
                       batch_window=0.0, max_batch=1)
    results["per_txn_pin"] = dict(
        default_tput=base["throughput"], zeroed_tput=pinned["throughput"],
        exact=base == pinned)
    assert base == pinned, "batch_window=0/max_batch=1 must be per-txn"

    for name, profs in workloads:
        per, pts = C.sim_batch_compare(profs, sweeps, sim_time=sim_time)
        wl = {"per_txn": dict(tput=per["throughput"],
                              lat_us=per.get("lat_all", 0) * 1e6),
              "batched": {}}
        for mb, w, out in pts:
            wl["batched"][f"mb{mb}_w{w:g}"] = dict(
                tput=out["throughput"],
                speedup_vs_per_txn=round(
                    out["throughput"] / max(per["throughput"], 1), 3),
                avg_batch=round(out["avg_batch"], 2),
                switch_rounds=out["switch_rounds"],
                lat_us=out.get("lat_all", 0) * 1e6)
        best = max(wl["batched"].values(), key=lambda r: r["tput"])
        wl["best_speedup"] = best["speedup_vs_per_txn"]
        results[name] = wl
        print(f"  sim {name:14s} per-txn {per['throughput']:>12,.0f} txn/s"
              f"  best batched {best['tput']:>12,.0f} txn/s "
              f"({best['speedup_vs_per_txn']}x, avg batch "
              f"{best['avg_batch']})")

    hl = results["ycsb_A_allhot"]["best_speedup"]
    results["headline_allhot_speedup"] = hl
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}")
    if hl < 1.0:
        print(f"WARNING: all-hot batched sim speedup {hl}x < 1x")


def sim_pipeline(fast: bool, out_path: str):
    """Timing-sim pipelined switch rounds: depth x batch-size sweep."""
    from benchmarks import common as C
    from repro.sim.model import SystemConfig

    sim_time = 0.01 if fast else C.SIM_TIME
    n = 1000 if fast else 3000
    depths = C.SIM_PIPELINE_DEPTHS_FAST if fast \
        else C.SIM_PIPELINE_DEPTHS_FULL
    batches = C.SIM_PIPELINE_BATCHES_FAST if fast \
        else C.SIM_PIPELINE_BATCHES_FULL
    workloads = C.sim_pipeline_workloads(fast, n=n)

    results = {"config": dict(fast=fast, sim_time=sim_time, n_profiles=n,
                              depths=depths, batches=batches,
                              window=C.SIM_PIPELINE_WINDOW,
                              nic_line_rate=C.NIC_10G)}

    # depth=1 vs the PR 2 golden fixture (generated from the PR 2 code
    # BEFORE the pipelined refactor), recorded for the artifact reader.
    # The equivalence CONTRACT is owned by the test suite
    # (tests/test_sim_pipeline.py::test_depth1_pins_to_pr2_batched_trace);
    # here a mismatch or missing fixture only warns.
    golden_path = os.path.join(os.path.dirname(__file__), "..", "tests",
                               "data", "golden_sim_pr2.json")
    try:
        with open(golden_path) as f:
            pr2 = json.load(f)["allhot_batched_mb32_w5us"]
        gprofs = C.ycsb_profiles(variant="A", n=1500, p_hot=1.0)[0]
        d1 = C.run_sim(gprofs, SystemConfig(kind="p4db"), sim_time=0.01,
                       seed=3, batch_window=5e-6, max_batch=32,
                       pipeline_depth=1)
        results["depth1_pin"] = dict(pr2_tput=pr2["throughput"],
                                     depth1_tput=d1["throughput"],
                                     exact=pr2 == d1)
        if pr2 != d1:
            print("WARNING: depth=1 no longer matches the PR 2 golden "
                  "fixture (run the test suite for the real pin)")
    except (FileNotFoundError, KeyError, json.JSONDecodeError):
        results["depth1_pin"] = None

    for name, profs in workloads:
        wl = {}
        for label, nic in (("no_nic", None), ("nic_10g", C.NIC_10G)):
            per, rows = C.sim_pipeline_compare(
                profs, depths, batches, sim_time=sim_time,
                nic_line_rate=nic)
            sec = {"per_txn": dict(tput=per["throughput"],
                                   lat_us=per.get("lat_all", 0) * 1e6),
                   "grid": {}}
            for d, mb, out in rows:
                sec["grid"][f"d{d}_mb{mb}"] = dict(
                    tput=out["throughput"],
                    speedup_vs_per_txn=round(
                        out["throughput"] / max(per["throughput"], 1), 3),
                    avg_batch=round(out["avg_batch"], 2),
                    switch_rounds=out["switch_rounds"],
                    lat_us=out.get("lat_all", 0) * 1e6)
            sec["crossover_batch_by_depth"] = {
                str(d): mb for d, mb in
                C.pipeline_crossover(per, rows).items()}
            d1_best = max((r["throughput"] for d, _, r in rows if d == 1),
                          default=0)
            deep_best = max((r["throughput"] for d, _, r in rows if d > 1),
                            default=0)
            sec["depth1_ceiling_tput"] = d1_best
            sec["best_pipelined_tput"] = deep_best
            sec["pipelined_vs_depth1"] = round(
                deep_best / max(d1_best, 1), 3)
            wl[label] = sec
            print(f"  sim {name:14s} [{label:7s}] per-txn "
                  f"{per['throughput']:>12,.0f} txn/s  depth1 ceiling "
                  f"{d1_best:>12,.0f}  best pipelined {deep_best:>12,.0f} "
                  f"({sec['pipelined_vs_depth1']}x)  crossover "
                  f"{sec['crossover_batch_by_depth']}")
        results[name] = wl

    hl = results["ycsb_A_allhot"]["no_nic"]
    results["headline_pipelined_vs_depth1"] = hl["pipelined_vs_depth1"]
    results["headline_pipelined_speedup"] = round(
        hl["best_pipelined_tput"] / max(hl["per_txn"]["tput"], 1), 3)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}")
    if results["headline_pipelined_vs_depth1"] <= 1.0:
        print("WARNING: pipelined rounds did not beat the depth-1 ceiling")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small smoke configuration for CI (~30 s)")
    ap.add_argument("--sim-only", action="store_true",
                    help="run only the timing-sim batched-admission "
                         "comparison")
    ap.add_argument("--pipeline-only", action="store_true",
                    help="run only the pipelined-round timing-sim sweep")
    ap.add_argument("--no-sim", action="store_true",
                    help="skip the timing-sim comparisons")
    ap.add_argument("--out", default="BENCH_batch.json")
    ap.add_argument("--out-sim", default="BENCH_sim_batch.json")
    ap.add_argument("--out-sim-pipeline", default="BENCH_sim_pipeline.json")
    args = ap.parse_args()

    if args.pipeline_only:
        print("timing-sim pipelined switch-round benchmark")
        sim_pipeline(args.fast, args.out_sim_pipeline)
        return
    if args.sim_only:
        print("timing-sim batched admission benchmark")
        sim_batch(args.fast, args.out_sim)
        return

    n = 192 if args.fast else 512
    batch_sizes = (64, 256) if args.fast else (32, 64, 128, 256)

    results = {"config": dict(fast=args.fast, n_txns=n,
                              batch_sizes=list(batch_sizes),
                              n_nodes=N_NODES, n_stages=SW.n_stages,
                              regs_per_stage=SW.regs_per_stage)}
    print("batched hot-path pipeline benchmark "
          f"(n={n}, batch sizes {list(batch_sizes)})")
    results["headline_ycsb_a_hot256"] = bench_headline()
    for variant in ("A", "B", "C"):
        txns, hi, loads = ycsb_workload(variant, n)
        results[f"ycsb_{variant}"] = bench_workload(
            f"ycsb_{variant}", txns, hi, loads, batch_sizes)
    txns, hi, loads = smallbank_workload(n)
    results["smallbank"] = bench_workload("smallbank", txns, hi, loads,
                                          batch_sizes)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")
    hl = results["headline_ycsb_a_hot256"]
    if hl["speedup"] < 3.0:
        print(f"WARNING: headline speedup {hl['speedup']}x < 3x target")

    if not args.no_sim:
        print("timing-sim batched admission benchmark")
        sim_batch(args.fast, args.out_sim)
        print("timing-sim pipelined switch-round benchmark")
        sim_pipeline(args.fast, args.out_sim_pipeline)


if __name__ == "__main__":
    main()

"""Adaptive hot-set management under workload drift (ISSUE 4).

For each drifting workload (YCSB hotspot shift; full runs add rotating
zipf and TPC-C warehouse rotation) the TIMING sim runs the same drifting
transaction stream three ways:

  static    — the phase-0 placement serves the whole run (what the paper's
              offline pipeline ships): its hot-txn rate collapses when the
              hot set moves;
  adaptive  — a HeatTracker-driven epoch controller re-detects the hot
              set every ``reconfig_interval``, re-runs the declustered
              layout on the observed trace window, and migrates (paying a
              ``t_reconfig`` switch pause per epoch);
  oracle    — ground-truth re-placement at each phase boundary: the
              per-epoch upper bound.

Headline (acceptance): adaptive restores >= 0.8x the oracle's hot-txn
rate while static demonstrably decays.  A second section exercises the
FUNCTIONAL layer end-to-end — live migrations on a real Cluster with
value-preservation and post-migration recovery checks — so the artifact
also witnesses the migration protocol, not just the timing model.

  PYTHONPATH=src python benchmarks/bench_adaptive.py [--fast] [--out FILE]

Emits BENCH_adaptive.json.
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

MODES = ("static", "adaptive", "oracle")


def sim_section(fast: bool):
    from benchmarks import common as C

    sim_time = C.adaptive_sim_time(fast)
    results = {}
    for name, gen, top_k in C.drift_generators(fast):
        hi, k = C.drift_hot_index(gen, top_k)
        wl, raw = {}, {}
        for mode in MODES:
            t0 = time.time()
            out = C.run_drift_sim(gen, mode, k, sim_time, hot_index=hi)
            raw[mode] = out
            wl[mode] = dict(
                tput=out["throughput"],
                hot_rate=out["hot_rate"],
                switch_rate=out["switch_rate"],
                lat_us=out.get("lat_all", 0) * 1e6,
                reconfigs=out["reconfigs"],
                phase_hot_rate={str(p): round(v, 4)
                                for p, v in out["phase_hot_rate"].items()},
                phase_switch_rate={
                    str(p): round(v, 4)
                    for p, v in out["phase_switch_rate"].items()},
                wall_s=round(time.time() - t0, 1))
        wl["adaptive_vs_oracle"] = round(
            C.adaptive_recovery_ratio(raw["adaptive"], raw["oracle"]), 3)
        wl["static_decay"] = round(
            C.static_decay_ratio(raw["static"]), 3)
        results[name] = wl
        print(f"  sim {name:14s} hot-rate static "
              f"{wl['static']['hot_rate']:>12,.0f}/s  adaptive "
              f"{wl['adaptive']['hot_rate']:>12,.0f}/s  oracle "
              f"{wl['oracle']['hot_rate']:>12,.0f}/s  "
              f"adaptive/oracle {wl['adaptive_vs_oracle']}  "
              f"static last/first phase {wl['static_decay']}")
    return results, dict(sim_time=sim_time,
                         reconfig_interval=C.RECONFIG_INTERVAL,
                         drift_period=C.DRIFT_PERIOD,
                         tracker_decay=C.TRACKER_DECAY)


def functional_section(fast: bool):
    """Live migrations on the functional cluster: run a drifting stream
    through Cluster + EpochController, then verify value preservation
    against a no-switch replay and register recovery from the WALs."""
    import copy

    from repro.core.heat import HeatTracker
    from repro.core.hotset import build_hot_index
    from repro.core.packets import SwitchConfig
    from repro.db.dbms import Cluster
    from repro.db.migrate import EpochController
    from repro.db.txn import node_of
    from repro.workloads import drift

    SW = SwitchConfig(n_stages=16, regs_per_stage=1024, max_instrs=16)
    n_nodes = 4
    gen = drift.YCSBHotspotShift(n_nodes=n_nodes, keys_per_node=4000,
                                 hot_per_node=16, n_blocks=4,
                                 p_hot_txn=0.9)
    hi = build_hot_index(
        drift.traces(gen.sample_phase(np.random.default_rng(0), 0, 1000)),
        16 * n_nodes, SW)
    c = Cluster(n_nodes, SW, hi, use_switch=True)
    for k in gen.hot_keys_at(0.0):
        c.load(k, 5)
    c.snapshot_offload()
    EpochController(c, HeatTracker(window=1024, decay=0.2), interval=250,
                    top_k=16 * n_nodes)
    n_per = 400 if fast else 1200
    phases = (0, 1, 2) if fast else (0, 1, 2, 3)
    batches = [gen.sample_phase(np.random.default_rng(10 + i), ph, n_per)
               for i, ph in enumerate(phases)]
    hot_by_phase = []
    t0 = time.time()
    for b in batches:
        before = c.stats["hot"]
        c.run_batch([copy.deepcopy(t) for t in b])
        hot_by_phase.append((c.stats["hot"] - before) / n_per)
    wall = time.time() - t0

    ref = Cluster(n_nodes, SW, None, use_switch=False)
    for k in gen.hot_keys_at(0.0):
        ref.load(k, 5)
    for b in batches:
        for t in b:
            ref.run(copy.deepcopy(t))

    def value(cl, k):
        if cl.use_switch and cl.hot_index.is_hot(k):
            return cl.switch.read_value(cl.hot_index.slot(k))
        return cl.nodes[node_of(k)].store[k]

    keys = {k for b in batches for t in b for k in t.keys()}
    mismatches = sum(value(c, k) != value(ref, k) for k in keys)
    before = np.asarray(c.switch.registers).copy()
    known, unknown = c.crash_switch_and_recover()
    recovered = bool((before == np.asarray(c.switch.registers)).all())
    out = dict(
        n_txns=len(batches) * n_per,
        migrations=int(c.stats["migrations"]),
        migrated_tuples=int(c.stats["migrated_tuples"]),
        hot_frac_by_phase=[round(h, 3) for h in hot_by_phase],
        value_mismatches_vs_noswitch=int(mismatches),
        recovery_replayed_sends=known,
        recovery_registers_exact=recovered,
        wall_s=round(wall, 2))
    print(f"  functional: {out['migrations']} migrations "
          f"({out['migrated_tuples']} tuples), hot frac by phase "
          f"{out['hot_frac_by_phase']}, mismatches {mismatches}, "
          f"recovery exact {recovered}")
    assert mismatches == 0, "migration broke value preservation"
    assert recovered, "recovery across migration boundary diverged"
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small smoke configuration for CI (~1 min)")
    ap.add_argument("--out", default="BENCH_adaptive.json")
    args = ap.parse_args()

    print("adaptive hot-set management benchmark "
          f"({'fast' if args.fast else 'full'})")
    sim, config = sim_section(args.fast)
    results = {"config": dict(fast=args.fast, **config)}
    results.update(sim)
    results["functional"] = functional_section(args.fast)

    hl = results["ycsb_shift"]
    results["headline_adaptive_vs_oracle"] = hl["adaptive_vs_oracle"]
    results["headline_static_decay"] = hl["static_decay"]
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")
    if hl["adaptive_vs_oracle"] < 0.8:
        print(f"WARNING: adaptive recovered only "
              f"{hl['adaptive_vs_oracle']}x of the oracle hot rate "
              f"(< 0.8x acceptance bar)")
    if hl["static_decay"] > 0.5:
        print(f"WARNING: static placement decayed only to "
              f"{hl['static_decay']} of its first-phase hot share — "
              f"drift too mild to matter")


if __name__ == "__main__":
    main()

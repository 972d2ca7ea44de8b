"""Open-loop serving benchmark: saturation knee + SLO tails (PR 9).

Both layers serve *arrival streams* instead of replaying a closed-loop
stream, so offered load is set by the client process, not by completions
-- past the knee the backlog (and the p99/p999 tail) blows up, which is
the behavior a latency SLO talks about and closed-loop replay can never
show.

  * **functional** -- ``repro.obs.load.serve_open_loop`` plays Poisson
    arrivals against a live ``Cluster`` (p4db async hot path vs a
    ``use_switch=False`` baseline): txns queue in a bounded backlog,
    ``run_batch``+``drain`` service times are measured wall-clock, and
    latency is arrival-to-completion on the virtual clock.  The rate grid
    is self-calibrating: a closed-loop capacity probe sets the base, the
    sweep covers SERVE_FRACS x base (same absolute grid for both systems).
  * **sim** -- the DES in open-loop mode (``open_loop_rate``): per-node
    Poisson sources, per-class admission on the worker-slot pool, arrivals
    shed at ``admit_queue_cap`` waiters.  The serving config makes the NIC
    (10G) and switch ingress (SERVE_SWITCH_RATE) explicit so the knee
    falls inside the swept range (the figure-sweep default folds both
    away -- no bottleneck at any offered rate).
  * **des_million** -- one saturated p4db run with >= 1M simulated client
    arrivals (acceptance floor; --fast does 50k): sheds at the admission
    door, reports the achieved rate and the post-warmup tail.

Emits BENCH_serve.json (wired into ``run.py --summary`` and CI) plus a
Prometheus scrape of the functional p4db cluster's registry
(artifacts/obs/serve_scrape.prom, validated by ``repro.obs.export
--check`` in CI):

  headline_serve_knee_ratio        -- DES knee p4db / noswitch (the
                                      modeled-hardware serving claim)
  headline_functional_knee_ratio   -- same ratio on the live engines;
                                      secondary, because the emulated
                                      switch pays a ~ms accelerator
                                      dispatch per hot round that real
                                      Tofino hardware does not
  rows.functional / rows.sim       -- >= 5 offered-load points per
                                      system, each with achieved rate +
                                      p50/p99/p999
  rows.des_million                 -- the million-arrival saturated run

A knee of 0 means no swept point achieved >= 90% of its offered rate --
the system saturates below the lowest rate in the grid; the headline then
divides by the grid floor and is a lower bound.

  PYTHONPATH=src python benchmarks/bench_serve.py [--fast] [--out F]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.compile_cache import enable_compile_cache  # noqa: E402

import numpy as np

from benchmarks import common as C
from repro.core.hotset import build_hot_index
from repro.core.packets import SwitchConfig
from repro.db.dbms import Cluster
from repro.obs import (MetricsRegistry, find_knee, parse_prometheus,
                       poisson_arrivals, serve_open_loop, to_prometheus)
from repro.workloads import ycsb

# functional serving universe: small switch (fast JIT), mostly-hot YCSB
SW = SwitchConfig(n_stages=16, regs_per_stage=2048, max_instrs=16)
N_NODES_F = 4
SERVE_BATCH = 64                 # front-end admission batch
SERVE_BACKLOG = 512              # bounded backlog (drop-newest past this)
SERVE_GATHER = 0.05              # p4db group-commit gather window (s) —
                                 # the functional mirror of the sim's
                                 # batch_window: without it, light load
                                 # dispatches batch-of-one device rounds
                                 # and capacity collapses to the per-
                                 # dispatch rate (noswitch sweeps with 0:
                                 # its per-txn path has no dispatch cost
                                 # to amortize, so a window only adds a
                                 # latency floor)
DES_RATE = 5e6                   # offered rate of the million-arrival run


def serve_workload(seed=0):
    """Hot index + a seed-deterministic txn stream factory (fresh Txn
    objects per sweep point -- the same cluster serves every point, one
    JIT compile across the whole sweep)."""
    p = ycsb.YCSBParams(n_nodes=N_NODES_F, keys_per_node=1000,
                        hot_per_node=16)
    sample = ycsb.generate(np.random.default_rng(seed), 1500, p)
    hi = build_hot_index(ycsb.traces(sample), 64, SW)

    def stream(s, n):
        return ycsb.generate(np.random.default_rng(1000 + s), n, p)

    return hi, stream


def serve_cluster(hi, **kw):
    c = Cluster(N_NODES_F, SW, hi, **kw)
    for k in list(hi.placement.slot)[:32]:
        c.load(k, 10)
    c.snapshot_offload()
    return c


def warm_shape_buckets(c, stream):
    """Execute batches across the power-of-two shape-bucket range before
    any timing: the engine compiles one executable per (mode, bucket)
    pair AOT, and an open-loop sweep admits variable-size batches -- a
    first-touch compile landing inside a timed batch would otherwise show
    up as a seconds-long latency spike on that point."""
    txns = stream(98, 512)
    i = 0
    for s in (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64):
        c.run_batch(txns[i:i + s])
        i += s
    c.drain()


def measure_capacity(c, stream, n=2000):
    """Closed-loop capacity probe: warm the JIT caches on a prefix, then
    time the rest back-to-back -- the base the rate grid scales from."""
    txns = stream(99, n)
    warm = min(4 * SERVE_BATCH, n // 2)
    for i in range(0, warm, SERVE_BATCH):
        c.run_batch(txns[i:i + SERVE_BATCH])
    c.drain()
    t0 = time.perf_counter()
    for i in range(warm, n, SERVE_BATCH):
        c.run_batch(txns[i:i + SERVE_BATCH])
    c.drain()
    return (n - warm) / (time.perf_counter() - t0)


def functional_sweep(fast):
    n = 2000 if fast else 6000
    hi, stream = serve_workload()
    clusters = {"p4db": serve_cluster(hi, async_hot=True),
                "noswitch": serve_cluster(hi, use_switch=False)}
    for c in clusters.values():
        warm_shape_buckets(c, stream)
    base = measure_capacity(clusters["p4db"], stream, n=min(n, 2000))
    rates = [f * base for f in C.SERVE_FRACS]
    # one untimed DRY RUN of the whole sweep per cluster: the engine
    # AOT-compiles one executable per (mode, batch bucket, result-plane
    # bucket) triple, and mode/result-plane depend on group CONTENT, not
    # just size — replaying the exact point streams is the only reliable
    # way to reach the specializations the timed points will hit, so any
    # first-touch compile lands here instead of inside a timed latency
    # histogram.  Wall cost is just total service time (the virtual clock
    # is free), a few seconds per cluster.
    windows = {"p4db": SERVE_GATHER, "noswitch": 0.0}
    for name, c in clusters.items():
        for j, rate in enumerate(rates):
            txns = stream(j, n)
            serve_open_loop(c, txns,
                            poisson_arrivals(rate, len(txns), seed=j),
                            batch=SERVE_BATCH, max_backlog=SERVE_BACKLOG,
                            gather_window=windows[name])
    rows = {}
    for name, c in clusters.items():
        rows[name] = []
        for j, rate in enumerate(rates):
            txns = stream(j, n)
            arr = poisson_arrivals(rate, len(txns), seed=j)
            # long-lived state (WALs, stores) grows across the sweep; a
            # gen2 GC pass over it is a 100ms+ stall that would land as a
            # fake latency spike in whatever batch it interrupts — freeze
            # the old generations out of the collector and disable GC for
            # the timed region (the driver itself allocates modestly)
            gc.collect()
            gc.freeze()
            gc.disable()
            try:
                r = serve_open_loop(c, txns, arr, batch=SERVE_BATCH,
                                    max_backlog=SERVE_BACKLOG,
                                    gather_window=windows[name],
                                    registry=MetricsRegistry())
            finally:
                gc.enable()
            rows[name].append(dict(r))
    return base, rates, rows, clusters["p4db"]


def sim_sweep(fast):
    profs, _ = C.ycsb_profiles(n=1500 if fast else 3000)
    cap = C.run_sim(profs, C.serve_system("p4db"))["throughput"]
    rates = [f * cap for f in C.SERVE_FRACS]
    rows = {}
    for kind in ("p4db", "noswitch"):
        rows[kind] = [C.serve_sim_row(
            C.run_open_loop_sim(profs, C.serve_system(kind), r, seed=2))
            for r in rates]
    return cap, rates, rows


def des_million(fast):
    """The acceptance run: >= 1M simulated client arrivals through the
    open-loop DES at a saturating rate (most are shed at the admission
    door -- one event each, which is what keeps this tractable)."""
    n_arr = 50_000 if fast else 1_000_000
    sim_time = n_arr / DES_RATE + 2 * C.WARMUP
    profs, _ = C.ycsb_profiles(n=1500)
    out, dt = C.timed(C.run_open_loop_sim, profs, C.serve_system("p4db"),
                      DES_RATE, sim_time=sim_time, max_arrivals=n_arr,
                      seed=3)
    ol = out["open_loop"]
    lat = out["latency"].get("all", {})
    return dict(offered_rate=DES_RATE, arrivals=ol["arrivals"],
                dropped=ol["dropped"], served=ol["served"],
                achieved_rate=ol["achieved_rate"],
                shed_frac=round(ol["dropped"] / max(ol["arrivals"], 1), 4),
                p50=lat.get("p50", 0.0), p99=lat.get("p99", 0.0),
                p999=lat.get("p999", 0.0),
                utilization=out["utilization"], wall_s=round(dt, 1))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: 2k-txn functional points, 50k-arrival "
                         "DES run (full: 8k / 1M)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    t_start = time.time()

    results = {"config": dict(
        fast=args.fast, fracs=C.SERVE_FRACS, batch=SERVE_BATCH,
        max_backlog=SERVE_BACKLOG, p4db_gather_window=SERVE_GATHER,
        n_nodes_functional=N_NODES_F,
        sim_switch_rate=C.SERVE_SWITCH_RATE, sim_nic=C.NIC_10G,
        sim_admit_cap=C.SERVE_ADMIT_CAP, cpu_count=os.cpu_count())}

    base, rates, frows, c_p4 = functional_sweep(args.fast)
    knees_f = {k: find_knee(frows[k]) for k in frows}
    results["rows"] = {"functional": frows}
    results["functional_base_rate"] = round(base, 1)
    print(f"functional (base {base:,.0f} txn/s closed-loop)")
    for name in ("p4db", "noswitch"):
        for r in frows[name]:
            print(f"  {name:9s} offered {r['offered_rate']:>9,.0f}/s "
                  f"achieved {r['achieved_rate']:>9,.0f}/s "
                  f"p50 {r['p50'] * 1e3:7.2f}ms p99 {r['p99'] * 1e3:8.2f}ms"
                  f" dropped {r['dropped']}")
        print(f"  {name:9s} knee = {knees_f[name]:,.0f}/s")

    # Prometheus scrape of the p4db serving cluster -- CI validates this
    # artifact with `python -m repro.obs.export --check`
    scrape = c_p4.export_metrics()
    parse_prometheus(scrape)
    obs_dir = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                           "obs")
    os.makedirs(obs_dir, exist_ok=True)
    scrape_path = os.path.join(obs_dir, "serve_scrape.prom")
    with open(scrape_path, "w") as f:
        f.write(scrape)
    print(f"  scrape: {len(parse_prometheus(scrape))} families -> "
          f"{os.path.relpath(scrape_path)}")

    cap, srates, srows = sim_sweep(args.fast)
    knees_s = {k: find_knee(srows[k]) for k in srows}
    results["rows"]["sim"] = srows
    results["sim_closed_loop_capacity"] = round(cap, 1)
    print(f"sim (closed-loop capacity {cap:,.0f} txn/s under the serving "
          f"config)")
    for name in ("p4db", "noswitch"):
        for r in srows[name]:
            print(f"  {name:9s} offered {r['offered_rate']:>9,.0f}/s "
                  f"achieved {r['achieved_rate']:>9,.0f}/s "
                  f"p50 {r['p50'] * 1e6:6.1f}us p99 {r['p99'] * 1e6:7.1f}us"
                  f" shed {r['dropped']}")
        print(f"  {name:9s} knee = {knees_s[name]:,.0f}/s")

    dm = des_million(args.fast)
    results["rows"]["des_million"] = dm
    print(f"des_million: {dm['arrivals']:,} arrivals at "
          f"{dm['offered_rate']:,.0f}/s -> served {dm['served']:,} "
          f"({dm['achieved_rate']:,.0f}/s), shed {dm['shed_frac']:.0%}, "
          f"p99 {dm['p99'] * 1e6:.1f}us  [{dm['wall_s']}s wall]")

    results["knees"] = {"functional": knees_f, "sim": knees_s}
    # Headline = the DES knee ratio: the sim prices the actual hardware
    # (10G NICs, Tofino-rate ingress, sub-us switch rounds), which is
    # where the paper's serving claim lives.  The functional ratio is
    # secondary and honest-by-construction: the emulated switch pays a
    # ~ms accelerator dispatch per hot round, so at tiny-txn scale the
    # pure-python noswitch baseline can out-serve it -- that measures the
    # emulation harness, not in-network OLTP.  knee=0 = saturated below
    # the grid floor; divide by the floor so the ratio is a conservative
    # lower bound instead of a ZeroDivision.
    results["headline_serve_knee_ratio"] = round(
        knees_s["p4db"] / max(knees_s["noswitch"], srates[0]), 3)
    results["headline_functional_knee_ratio"] = round(
        knees_f["p4db"] / max(knees_f["noswitch"], rates[0]), 3)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"headline: sim knee ratio "
          f"{results['headline_serve_knee_ratio']}x (functional "
          f"{results['headline_functional_knee_ratio']}x -- emulated-"
          f"switch dispatch cost, see module docstring)   wrote "
          f"{args.out} [{time.time() - t_start:.0f}s total]")


if __name__ == "__main__":
    main()

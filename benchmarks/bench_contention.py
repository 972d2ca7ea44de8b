"""Contention-storm benchmark: network-assisted early aborts on vs off
(PR 10).

Hot txns are abort-free on the switch; this benchmark measures what the
contention-resilience layer buys the traffic that ISN'T — cold/warm 2PC
storms funneling through a handful of contended keys.  Two storm shapes
(``repro.workloads.storms``), both ADD-based so on/off reach identical
final state under any serialization:

  * **ycsb_a_storm** — mixed YCSB-A, contended keys at varied positions
    inside 8-op txns: doomed attempts burn private work before the
    conflict surfaces, which is exactly what an early abort reclaims.
  * **tpcc_payment_storm** — TPC-C payment, warehouse YTD row FIRST:
    conflicts surface at op 0, so there is little waste to reclaim —
    the honest negative control (NO_WAIT gains nothing; WAIT_DIE wounds
    can even ADD waste by killing mid-flight holders).

Both execution planes run each storm with ``early_abort`` off and on:

  * **functional** — ``db.conflict.ContentionArena`` drives real 2PL
    fibers against a live ``Cluster`` under a 16-worker closed loop;
    wasted ops, retries, gave-up and tail latency are measured in ticks.
  * **sim** — the DES prices the same mechanism in seconds
    (``SystemConfig.early_abort``, ``Timing.t_abort_notify``) with
    contended locks pre-seeded and ``drop_on_abort=False`` (retry to
    commit, the tail an SLO sees).

Emits BENCH_contention.json (wired into ``run.py --summary`` and CI):

  headline_wasted_work_reduction -- functional YCSB-A storm, WAIT_DIE:
                                    wasted ops off / on (x)
  rows.functional / rows.sim     -- per storm x protocol x {off,on}:
                                    wasted, aborts, early aborts, wounds,
                                    gave_up, p99/p999, commits
  acceptance                     -- the ISSUE-10 floor, asserted: >= 25%
                                    wasted-work cut AND p99 improvement
                                    on the YCSB-A storm, both planes

  PYTHONPATH=src python benchmarks/bench_contention.py [--fast] [--out F]
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.compile_cache import enable_compile_cache  # noqa: E402

import numpy as np

from benchmarks import common as C
from repro.core.packets import SwitchConfig
from repro.db.conflict import ContentionArena, RetryPolicy
from repro.db.dbms import Cluster
from repro.sim.model import ClusterSim, SystemConfig, Timing
from repro.workloads import storms

SW = SwitchConfig(n_stages=16, regs_per_stage=512, max_instrs=16)
N_NODES = 4
WORKERS = 16                 # functional arena closed-loop pool
PROTOCOLS = ("NO_WAIT", "WAIT_DIE")
STORMS = ("ycsb_a_storm", "tpcc_payment_storm")


def functional_rows(fast: bool):
    n = 120 if fast else 300
    p = storms.StormParams(n_nodes=N_NODES)
    rows = []
    for gen_name in STORMS:
        txns = getattr(storms, gen_name)(np.random.default_rng(0), n, p)
        for proto in PROTOCOLS:
            for ea in (False, True):
                c = Cluster(N_NODES, SW, hot_index=None, use_switch=False,
                            protocol=proto)
                pol = RetryPolicy.for_protocol(proto, max_retries=24,
                                               seed=1)
                arena = ContentionArena(c, policy=pol, early_abort=ea)
                t0 = time.time()
                r = arena.run(copy.deepcopy(txns), workers=WORKERS)
                rows.append(dict(
                    storm=gen_name, protocol=proto, early_abort=ea,
                    txns=n, commits=len(r.committed),
                    gave_up=len(r.gave_up), aborts=r.aborts,
                    early_aborts=r.early_aborts, wounds=r.wounds,
                    wasted_ops=r.wasted_ops, ticks=r.ticks,
                    p50=r.percentile(0.50), p99=r.percentile(0.99),
                    p999=r.percentile(0.999),
                    wall_s=round(time.time() - t0, 2)))
    return rows


def sim_rows(fast: bool):
    n = 600 if fast else 1500
    sim_time = 0.005 if fast else 0.02
    profs, p = C.storm_profiles("ycsb_a_storm", n=n, n_nodes=N_NODES)
    profs_t, _ = C.storm_profiles("tpcc_payment_storm", n=n,
                                  n_nodes=N_NODES, params=p)
    rows = []
    for gen_name, pp in (("ycsb_a_storm", profs),
                         ("tpcc_payment_storm", profs_t)):
        for proto in PROTOCOLS:
            for ea in (False, True):
                sys_ = SystemConfig(kind="p4db", protocol=proto,
                                    early_abort=ea, drop_on_abort=False)
                cs = ClusterSim(pp, n_nodes=N_NODES, workers_per_node=4,
                                system=sys_, timing=Timing(), seed=7,
                                sim_time=sim_time, warmup=sim_time * 0.1)
                for k in storms.contended_keys(p):
                    cs.lock_of(k)       # the storm funnel takes real locks
                out = cs.run()
                h = cs._h_lat.get("cold")
                commits = h.count if h is not None else 0
                # commits == 0 means the baseline COLLAPSED under the
                # sustained storm (livelock: nothing commits after
                # warmup); p99 is then None (infinite), not 0.0
                rows.append(dict(
                    storm=gen_name, protocol=proto, early_abort=ea,
                    throughput=out["throughput"], commits=commits,
                    aborts=sum(out["aborts"].values()),
                    early_aborts=cs.early_aborts, wounds=cs.ea_wounds,
                    wasted_ops=cs.wasted_ops,
                    p50=h.percentile(0.50) if commits else None,
                    p99=h.percentile(0.99) if commits else None,
                    p999=h.percentile(0.999) if commits else None))
    return rows


def _pair(rows, storm, proto):
    off = next(r for r in rows if r["storm"] == storm
               and r["protocol"] == proto and not r["early_abort"])
    on = next(r for r in rows if r["storm"] == storm
              and r["protocol"] == proto and r["early_abort"])
    return off, on


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_contention.json"))
    args = ap.parse_args()
    t_start = time.time()
    results = {"fast": args.fast, "rows": {}}

    frows = functional_rows(args.fast)
    results["rows"]["functional"] = frows
    print("functional (ContentionArena, 16-worker closed loop, ticks):")
    for r in frows:
        print(f"  {r['storm']:18s} {r['protocol']:8s} "
              f"ea={'on ' if r['early_abort'] else 'off'} "
              f"wasted {r['wasted_ops']:5d} aborts {r['aborts']:5d} "
              f"early {r['early_aborts']:5d} wounds {r['wounds']:4d} "
              f"gave_up {r['gave_up']:3d} p99 {r['p99']:6.0f} "
              f"p999 {r['p999']:6.0f}")

    srows = sim_rows(args.fast)
    results["rows"]["sim"] = srows
    print("sim (DES, WAIT_DIE retries age via first-attempt ts, seconds):")
    for r in srows:
        p99 = (f"{r['p99'] * 1e6:7.1f}us" if r["p99"] is not None
               else "collapsed")
        print(f"  {r['storm']:18s} {r['protocol']:8s} "
              f"ea={'on ' if r['early_abort'] else 'off'} "
              f"wasted {r['wasted_ops']:5d} aborts {r['aborts']:5d} "
              f"early {r['early_aborts']:5d} wounds {r['wounds']:4d} "
              f"tput {r['throughput']:8.0f}/s p99 {p99}")

    # headline + acceptance: the YCSB-A storm under WAIT_DIE (the
    # disciplined configuration: retries keep their timestamp and age
    # into priority, wounds free locks mid-flight)
    f_off, f_on = _pair(frows, "ycsb_a_storm", "WAIT_DIE")
    s_off, s_on = _pair(srows, "ycsb_a_storm", "WAIT_DIE")
    f_cut = 1.0 - f_on["wasted_ops"] / max(f_off["wasted_ops"], 1)
    s_cut = 1.0 - s_on["wasted_ops"] / max(s_off["wasted_ops"], 1)
    acceptance = dict(
        functional_wasted_cut=round(f_cut, 3),
        functional_p99_off=f_off["p99"], functional_p99_on=f_on["p99"],
        sim_wasted_cut=round(s_cut, 3),
        sim_p99_off_us=(round(s_off["p99"] * 1e6, 1)
                        if s_off["p99"] is not None else None),
        sim_p99_on_us=(round(s_on["p99"] * 1e6, 1)
                       if s_on["p99"] is not None else None))
    results["acceptance"] = acceptance
    results["headline_wasted_work_reduction"] = round(
        f_off["wasted_ops"] / max(f_on["wasted_ops"], 1), 3)
    assert f_cut >= 0.25, f"functional wasted-work cut {f_cut:.0%} < 25%"
    assert s_cut >= 0.25, f"sim wasted-work cut {s_cut:.0%} < 25%"
    assert f_on["p99"] < f_off["p99"], \
        f"functional p99 did not improve: {f_off['p99']} -> {f_on['p99']}"
    # off-mode committing NOTHING post-warmup (p99 None) is total
    # collapse — the strongest possible improvement, not a failure
    assert s_on["p99"] is not None, "sim on-mode committed nothing"
    assert s_off["p99"] is None or s_on["p99"] < s_off["p99"], \
        f"sim p99 did not improve: {s_off['p99']} -> {s_on['p99']}"

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    s_off_us = (f"{s_off['p99'] * 1e6:.0f}us"
                if s_off["p99"] is not None else "collapsed")
    print(f"headline: wasted-work reduction "
          f"{results['headline_wasted_work_reduction']}x (functional "
          f"YCSB-A/WAIT_DIE; cut {f_cut:.0%} functional, {s_cut:.0%} sim; "
          f"p99 {f_off['p99']:.0f}->{f_on['p99']:.0f} ticks functional, "
          f"{s_off_us}->{s_on['p99'] * 1e6:.0f}us sim)   "
          f"wrote {args.out} [{time.time() - t_start:.0f}s total]")


if __name__ == "__main__":
    main()

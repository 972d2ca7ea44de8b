"""In-network read tier: switch-served hot reads + scan pruning (the
ISSUE 8 tentpole headline).

Three sections, all equivalence-checked before any timing:

  * **read path** — all-hot YCSB-C (READ-only txns) at B=256: the
    switch-served tier (``Cluster.read_batch`` — one device gather per
    batch, no WAL, no GID, no locks) vs the store-served baseline (the
    same txns through ``run_batch`` on a ``use_switch=False`` cluster:
    per-key 2PL acquire/release + commit logging).  Acceptance:
    ``headline_read_speedup`` >= 3x.
  * **scan pruning** — selectivity sweep over the hot tier: the
    scan-prune kernel ships <= (selectivity + padding) of the scanned
    rows device -> host (padding = the first-pass cap / M), vs a full
    register read-back shipping everything.
  * **sim** — the DES prices the read tier (``read_path=True``:
    ``t_read_pipe`` transit, no pipeline lock, no recirculation) on
    YCSB-C and read-mostly YCSB-B; off = byte-identical pre-read model.

Emits BENCH_reads.json (wired into ``run.py --summary`` and CI):
  headline_read_speedup          — switch-served vs store-served reads/s
  headline_scan_shipped_frac     — shipped row fraction at 5% selectivity
  rows.read_path / rows.scan / rows.sim

  PYTHONPATH=src python benchmarks/bench_reads.py [--fast]
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

from repro.core.hotset import build_hot_index
from repro.core.packets import READ, SwitchConfig
from repro.db.dbms import Cluster
from repro.db.txn import Txn, key_of, node_of

# 8 stages x 64 regs = 512 hot slots — the whole read working set fits
SW = SwitchConfig(n_stages=8, regs_per_stage=64, max_instrs=8)
N_NODES = 2
N_KEYS = 512
OPS_PER_TXN = 4
BATCH = 256


def setup(seed=0, use_switch=True, n_switches=1, mode="auto"):
    """Cluster + the loaded key/value universe (values = 3k + 7, so scan
    selectivity is controllable by value range)."""
    from dataclasses import replace
    cfg = replace(SW, n_switches=n_switches)
    keys = [key_of(i % N_NODES, i) for i in range(N_KEYS)]
    hi = build_hot_index([[(k, "W")] for k in keys], N_KEYS, cfg)
    c = Cluster(N_NODES, cfg, hi, use_switch=use_switch, switch_mode=mode)
    vals = {}
    for i, k in enumerate(keys):
        vals[k] = 3 * i + 7
        c.load(k, vals[k])
    c.snapshot_offload()
    return c, keys, vals


def read_txns(keys, n_batches, seed=1):
    """YCSB-C: READ-only txns, OPS_PER_TXN uniform keys each."""
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(n_batches):
        txns = []
        for _ in range(BATCH):
            ks = rng.choice(len(keys), size=OPS_PER_TXN, replace=False)
            ops = [(READ, keys[int(j)], 0) for j in ks]
            txns.append(Txn("ycsbC", ops, node_of(ops[0][1])))
        batches.append(txns)
    return batches


def store_served(c, batches):
    out = []
    for txns in batches:
        out += c.run_batch([Txn(t.kind, list(t.ops), t.home)
                            for t in txns])
    return out


def switch_served(c, batches):
    """The read tier: each admission batch becomes ONE gather dispatch."""
    out = []
    for txns in batches:
        flat = [k for t in txns for _, k, _ in t.ops]
        vals = c.read_batch(flat)
        i = 0
        for t in txns:
            out.append(vals[i:i + len(t.ops)])
            i += len(t.ops)
    return out


def equivalence(batches, vals, n_switches, mode):
    """Cross-mode equivalence BEFORE timing: switch-served reads must
    equal the store-served baseline's results AND the loaded truth."""
    cs, keys, _ = setup(use_switch=True, n_switches=n_switches, mode=mode)
    cb, _, _ = setup(use_switch=False)
    a = switch_served(cs, batches[:1])
    b = store_served(cb, batches[:1])
    truth = [[vals[k] for _, k, _ in t.ops] for t in batches[0]]
    assert a == b == truth, \
        f"read tier diverged (N={n_switches}, mode={mode})"
    # and the pruned scan agrees with a host-side filter of the truth
    lo, hi = 100, 400
    want = sorted((k, v) for k, v in vals.items() if lo <= v <= hi)
    assert cs.scan(lo, hi) == want, "scan diverged"


def timed(fn, *args, reps=3):
    best = None
    for _ in range(reps):
        gc.disable()
        t0 = time.perf_counter()
        fn(*args)
        dt = time.perf_counter() - t0
        gc.enable()
        best = dt if best is None else min(best, dt)
    return best


def bench_read_path(n_batches, reps):
    c_sw, keys, _ = setup(use_switch=True)
    c_st, _, _ = setup(use_switch=False)
    batches = read_txns(keys, n_batches)
    n_reads = n_batches * BATCH * OPS_PER_TXN
    switch_served(c_sw, batches[:1])          # warm AOT gather cache
    store_served(c_st, batches[:1])
    t_sw = timed(switch_served, c_sw, batches, reps=reps)
    t_st = timed(store_served, c_st, batches, reps=reps)
    return dict(n_batches=n_batches, batch=BATCH, ops_per_txn=OPS_PER_TXN,
                switch_reads_per_s=round(n_reads / t_sw, 1),
                store_reads_per_s=round(n_reads / t_st, 1),
                dispatches=int(c_sw.switch.read_dispatch_count),
                speedup=round(t_st / t_sw, 3))


def bench_scan_pruning():
    """Shipped-fraction sweep: values are 3i+7 over i<512, so value range
    [7, 7 + 3*(s*M)) selects exactly s*M rows."""
    c, keys, vals = setup()
    M = len(keys)
    rows = []
    for sel in (0.01, 0.05, 0.25, 1.0):
        n_match = max(1, int(sel * M))
        lo, hi = 7, 7 + 3 * (n_match - 1)
        before = c.stats["scan_rows_shipped"]
        out = c.scan(lo, hi)
        shipped = c.stats["scan_rows_shipped"] - before
        want = sorted((k, v) for k, v in vals.items() if lo <= v <= hi)
        assert out == want and len(out) == n_match
        frac = shipped / M
        # padding: the 16-row first pass (+ the rescan's exact cap)
        assert frac <= sel + 16 / M + 1e-9, \
            f"pruning shipped {frac:.3f} > selectivity {sel} + padding"
        rows.append(dict(selectivity=sel, matched=n_match,
                         rows_shipped=int(shipped),
                         shipped_frac=round(frac, 4),
                         full_readback_rows=M))
    return rows


def bench_sim(fast):
    from common import run_sim, ycsb_profiles
    from repro.sim.model import SystemConfig

    n = 1500 if fast else 3000
    out = {}
    for name, variant in (("ycsb_C", "C"), ("ycsb_B", "B")):
        profs, _ = ycsb_profiles(variant=variant, n=n)
        off = run_sim(profs, SystemConfig(kind="p4db", max_batch=8))
        on = run_sim(profs, SystemConfig(kind="p4db", max_batch=8,
                                         read_path=True))
        out[name] = dict(
            throughput_off=off["throughput"],
            throughput_on=on["throughput"],
            speedup=round(on["throughput"] / off["throughput"], 4),
            read_pipe_s=round(on["breakdown"].get("read_pipe", 0.0), 9))
        assert "read_pipe" not in off["breakdown"], \
            "read_path=False must add zero read events"
        assert out[name]["read_pipe_s"] > 0, \
            "read_path=True priced no reads on a read-heavy mix"
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small CI smoke; still asserts cross-mode "
                         "equivalence before timing")
    ap.add_argument("--out", default="BENCH_reads.json")
    args = ap.parse_args()

    n_batches = 4 if args.fast else 16
    reps = 2 if args.fast else 4

    results = {"config": dict(fast=args.fast, n_batches=n_batches,
                              batch=BATCH, ops_per_txn=OPS_PER_TXN,
                              n_keys=N_KEYS, n_nodes=N_NODES,
                              reps=reps, cpu_count=os.cpu_count())}
    print(f"read-tier benchmark (B={BATCH}, {OPS_PER_TXN} reads/txn, "
          f"{N_KEYS} hot keys)")

    _, keys, vals = setup()
    eq_batches = read_txns(keys, 1, seed=9)
    for ns, mode in ((1, "auto"), (1, "pallas"), (2, "auto")):
        equivalence(eq_batches, vals, ns, mode)
    results["equivalence"] = {"checked": ["n1/auto", "n1/pallas",
                                          "n2/auto"], "ok": True}
    print("  equivalence (switch == store == truth, + scan): OK")

    rp = bench_read_path(n_batches, reps)
    results["rows"] = {"read_path": rp}
    print(f"  switch-served {rp['switch_reads_per_s']:>12,.0f} reads/s  "
          f"store-served {rp['store_reads_per_s']:>12,.0f} reads/s  "
          f"-> {rp['speedup']}x")

    scan_rows = bench_scan_pruning()
    results["rows"]["scan"] = scan_rows
    for r in scan_rows:
        print(f"  scan sel={r['selectivity']:<5} shipped "
              f"{r['rows_shipped']:>4}/{r['full_readback_rows']} rows "
              f"({r['shipped_frac']:.3f})")

    results["rows"]["sim"] = bench_sim(args.fast)
    for name, r in results["rows"]["sim"].items():
        print(f"  sim {name}: read_path off {r['throughput_off']:,.0f} "
              f"-> on {r['throughput_on']:,.0f} txn/s "
              f"({r['speedup']}x)")

    results["headline_read_speedup"] = rp["speedup"]
    results["headline_scan_shipped_frac"] = next(
        r["shipped_frac"] for r in scan_rows if r["selectivity"] == 0.05)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"  headline: {rp['speedup']}x read speedup   wrote {args.out}")
    if rp["speedup"] < 3.0 and not args.fast:
        print(f"WARNING: read speedup {rp['speedup']}x < 3x acceptance "
              f"target (switch-served YCSB-C vs store-served)")


if __name__ == "__main__":
    main()

"""Sharded multi-switch register plane: hot capacity + aggregate
hot-dispatch throughput scaling (the ISSUE 7 tentpole headline).

The bench is CAPACITY-driven, the regime where multiple switches pay off
even on one host: the would-be-hot key universe is sized to ~3.5x a
single switch's register capacity.  Every txn pairs one per-community
HEAD key (the 56 heads fit a single switch's 64 slots, so N=1's clamped
``top_k`` keeps them hot) with one TAIL key (the 168 tails only fit the
sharded plane).  At N=1 the tail key is demoted, so nearly every txn
takes the warm path — host locks + a per-txn B=1 switch sub-dispatch
for its hot half.  At N=4 the whole universe fits and every txn commits
through grouped hot dispatches (one engine call per batch).

For every N in the sweep the same workload runs on a cluster whose
switch config differs ONLY in ``n_switches``; results and final per-key
values are asserted identical across N first (a wrong sharded plane must
never publish a speedup).

Emits BENCH_multiswitch.json:
  rows[N]   — hot_capacity, top_k, hot/warm/cold counts, txn_per_s,
              hot_txn_per_s, speedup_vs_n1 (overall txn/s ratio)
  headline_multiswitch_speedup — end-to-end txn/s on the same workload,
    N=4 vs N=1 (acceptance: >= 2x)
  hot_dispatch_speedup_n4_vs_n1 — aggregate switch-dispatch (hot-path)
    throughput ratio; far larger, since capacity-bound N=1 demotes most
    txns off the register plane entirely
  capacity  — total hot slots per N (acceptance: linear in N)

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      PYTHONPATH=src python benchmarks/bench_multiswitch.py [--fast]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# the sharded engine pins one plane per JAX device when several exist;
# emulate a 4-device mesh unless the caller already forced a mesh
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.compile_cache import enable_compile_cache  # noqa: E402

import numpy as np

from repro.core.hotset import build_hot_index
from repro.core.packets import ADD, READ, SwitchConfig
from repro.db.dbms import Cluster
from repro.db.txn import Txn, key_of, node_of

# one SMALL switch: 4 stages x 16 regs = 64 hot slots per shard, so the
# ~3.5x-capacity key universe saturates 1 shard and fits 4 with slack
SW1 = SwitchConfig(n_stages=4, regs_per_stage=16, max_instrs=8)
N_NODES = 2
COMM = 16                      # co-access community size
N_COMM = 14
N_KEYS = COMM * N_COMM         # 224 keys vs 64 slots/shard
HEADS = 4                      # per-community heavy hitters (4*14 = 56)


def workload(n_txns, seed=0):
    """All-would-be-hot YCSB-A-style txns (one ADD + one READ) whose
    co-access graph has COMMUNITY structure: both keys of a txn come
    from the same 16-key community — the regime the paper's
    multi-switch case targets, where the level-1 mincut can place whole
    communities per switch so sharding costs (cross-switch rows) stay
    rare while capacity scales.  Each txn pairs a community HEAD key
    (drawn from the first ``HEADS`` — every txn touches one, so heads
    dominate the heat ranking and survive N=1's ``top_k`` clamp) with a
    TAIL key (the other 12, each drawn 1/12th as often — demoted at
    N=1, hot only once the sharded plane adds capacity)."""
    rng = np.random.default_rng(seed)
    keys = [key_of(i % N_NODES, i) for i in range(N_KEYS)]
    txns = []
    for _ in range(n_txns):
        comm = int(rng.integers(N_COMM)) * COMM
        a = int(rng.integers(HEADS))
        b = HEADS + int(rng.integers(COMM - HEADS))
        ka, kb = keys[comm + a], keys[comm + b]
        txns.append(Txn("ycsbA", [(ADD, ka, int(rng.integers(1, 9))),
                                  (READ, kb, 0)], node_of(ka)))
    traces = [[(k, o) for o, k, _ in t.ops] for t in txns]
    return txns, traces, keys


def make_cluster(n_switches, traces, keys, async_hot=True):
    from dataclasses import replace
    cfg = replace(SW1, n_switches=n_switches)
    top_k = min(N_KEYS, cfg.total_slots)      # capacity clamp: the point
    hi = build_hot_index(traces, top_k, cfg)
    c = Cluster(N_NODES, cfg, hi, use_switch=True, async_hot=async_hot)
    for k in keys:
        if hi.is_hot(k):
            c.load(k, 0)
    c.snapshot_offload()
    return c, top_k


def key_value(c, k):
    return c.read(k) if c.hot_index.is_hot(k) \
        else c.nodes[node_of(k)].store[k]


def run_once(c, txns, batch):
    res = []
    for i in range(0, len(txns), batch):
        res += c.run_batch([Txn(t.kind, list(t.ops), t.home)
                            for t in txns[i:i + batch]])
    c.drain()
    return res


def timed(n_switches, txns, traces, keys, batch, reps):
    best = None
    counts = {}
    for _ in range(reps):
        c, top_k = make_cluster(n_switches, traces, keys)
        run_once(c, txns[:batch], batch)            # warm AOT caches
        base = {s: c.stats[s] for s in ("hot", "warm", "cold")}
        gc.disable()
        t0 = time.perf_counter()
        run_once(c, txns, batch)
        dt = time.perf_counter() - t0
        gc.enable()
        counts = {s: c.stats[s] - base[s] for s in base}
        if best is None or dt < best:
            best = dt
    return dict(n_switches=n_switches, hot_capacity=top_k, top_k=top_k,
                **counts,
                txn_per_s=round(len(txns) / best, 1),
                hot_txn_per_s=round(counts["hot"] / best, 1),
                wall_s=round(best, 4))


def equivalence(sweep, traces, keys, n_txns, batch):
    """Same workload, every shard count: identical results and final
    per-key values (the hot/warm/cold SPLIT differs by design)."""
    txns = [Txn(t.kind, list(t.ops), t.home)
            for t in workload(n_txns, seed=1)[0]]
    ref = None
    for n in sweep:
        c, _ = make_cluster(n, traces, keys, async_hot=False)
        res = run_once(c, txns, batch)
        vals = [key_value(c, k) for k in keys]
        if ref is None:
            ref = (res, vals)
        else:
            assert res == ref[0], f"results diverge at N={n}"
            assert vals == ref[1], f"key values diverge at N={n}"
    return {"checked_n": list(sweep), "ok": True}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small CI smoke; still asserts cross-N "
                         "equivalence before timing")
    ap.add_argument("--out", default="BENCH_multiswitch.json")
    args = ap.parse_args()

    n = 768 if args.fast else 3072
    batch = 128
    reps = 2 if args.fast else 4
    sweep = (1, 2, 4)

    import jax
    results = {"config": dict(fast=args.fast, n_txns=n, batch=batch,
                              reps=reps, sweep=list(sweep),
                              n_keys=N_KEYS, n_nodes=N_NODES,
                              slots_per_switch=SW1.total_slots,
                              jax_devices=len(jax.devices()),
                              cpu_count=os.cpu_count())}
    print(f"multi-switch benchmark (n={n}, B={batch}, "
          f"{N_KEYS} keys over {SW1.total_slots}-slot shards, "
          f"{len(jax.devices())} devices)")

    txns, traces, keys = workload(n)
    results["equivalence"] = equivalence(sweep, traces, keys,
                                         min(n, 512), batch)
    print("  equivalence across N in {1,2,4}: OK")

    rows = {}
    for ns in sweep:
        r = timed(ns, txns, traces, keys, batch, reps)
        rows[f"n{ns}"] = r
        print(f"  N={ns}: capacity {r['hot_capacity']:>4} slots  "
              f"hot/warm/cold {r['hot']}/{r['warm']}/{r['cold']}  "
              f"{r['txn_per_s']:>10,.0f} txn/s  "
              f"(hot {r['hot_txn_per_s']:>10,.0f}/s)")
    base = rows["n1"]
    for ns in sweep:
        rows[f"n{ns}"]["speedup_vs_n1"] = round(
            rows[f"n{ns}"]["txn_per_s"] / base["txn_per_s"], 3)
    results["rows"] = rows
    results["capacity"] = {f"n{ns}": rows[f"n{ns}"]["hot_capacity"]
                           for ns in sweep}
    hl = rows["n4"]["speedup_vs_n1"]
    hot_hl = round(rows["n4"]["hot_txn_per_s"] / base["hot_txn_per_s"], 3)
    results["headline_multiswitch_speedup"] = hl
    results["hot_dispatch_speedup_n4_vs_n1"] = hot_hl
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"  N=4 vs N=1: {hl}x overall txn/s "
          f"(hot-dispatch {hot_hl}x)   wrote {args.out}")
    if hl < 2.0 and not args.fast:
        print(f"WARNING: multi-switch speedup {hl}x < 2x acceptance "
              f"target (capacity-bound all-hot workload)")


if __name__ == "__main__":
    main()

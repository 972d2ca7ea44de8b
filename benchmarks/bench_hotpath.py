"""Async device-resident hot path vs the PR 1 batched pipeline.

Three implementations of the same batched hot-txn semantics race on
all-hot YCSB-A at B=256 (the ISSUE 5 headline):

  pr1    — the PR 1 ``run_batch`` dispatch, vendored verbatim below:
           four padded H2D transfers per group, full-plane device
           result, blocking ``np.asarray`` sync per group, per-op
           Python result/WAL loop.
  sync   — today's synchronous path (``async_hot=False``): fused
           single-buffer H2D, on-device result compaction, vectorized
           drain — but every group still materializes before the next
           one builds.
  async  — the asynchronous pipeline (``async_hot=True``): group k's
           device execution overlaps group k+1's packet build on the
           engine's dispatch thread; results/WAL entries fill lazily at
           ``drain()``.  Swept over ``max_inflight`` in {1, 2, 4}.

Acceptance (ISSUE 5): async >= 1.5x pr1 hot-txn throughput on CPU, and
async/sync/pr1 byte-identical (results, registers, GIDs, WAL recovery)
— the equivalence section ASSERTS this, so the --fast CI smoke fails
loudly on any divergence.

  PYTHONPATH=src python benchmarks/bench_hotpath.py [--fast] [--out FILE]

Emits BENCH_hotpath.json.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.compile_cache import enable_compile_cache  # noqa: E402

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_batch import (N_NODES, SW, smallbank_workload,
                                    ycsb_workload)
from repro.core import engine as E
from repro.core.engine import SwitchEngine
from repro.core.packets import build_packets
from repro.db.dbms import Cluster

# --------------------------------------------- the vendored PR 1 path ----
# Frozen copy of the PR 1 batched dispatch (the pre-async code), kept as
# the benchmark baseline so the measured ratio is against the actual
# shipped implementation, not a strawman.  It shares today's packet
# builder and classification (both conservative: they FAVOR the
# baseline).

_PR1_CACHE = {}


def _pr1_compiled(mode, S, R, B, K):
    key = (mode, S, R, B, K)
    fn = _PR1_CACHE.get(key)
    if fn is None:
        spec = jax.ShapeDtypeStruct((B, K), jnp.int32)
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message="Some donated buffers")
            fn = jax.jit(E._ENGINE_IMPLS[mode], donate_argnums=0).lower(
                jax.ShapeDtypeStruct((S, R), jnp.int32),
                spec, spec, spec, spec).compile()
        _PR1_CACHE[key] = fn
    return fn


def _pr1_execute_batch(eng: SwitchEngine, pkts, meta, mode):
    """PR 1 ``SwitchEngine.execute_batch``: four separate padded H2D
    transfers, no compaction, device arrays returned for the caller to
    sync."""
    op_np = np.asarray(pkts["op"], np.int32)
    B, K = op_np.shape
    mode = SwitchEngine._resolve_mode(mode, meta["has_cadd"],
                                      meta["has_addp"], meta["addp_unsafe"])
    gids = np.arange(eng.next_gid, eng.next_gid + B, dtype=np.int64)
    Bp = E._bucket(B)
    pad = ((0, Bp - B), (0, 0))

    def dev(x):
        a = np.asarray(x, np.int32)
        return jnp.asarray(np.pad(a, pad) if Bp != B else a)

    op = dev(op_np)
    stage = dev(pkts["stage"])
    reg = dev(pkts["reg"])
    val = dev(pkts["operand"])
    S, R = eng.registers.shape
    fn = _pr1_compiled(mode, S, R, Bp, K)
    regs, res, ok = fn(eng.registers, op, stage, reg, val)
    eng.dispatch_count += 1
    eng.registers = regs
    eng.next_gid += B
    return res[:B], ok[:B], gids


class PR1Cluster(Cluster):
    """The PR 1 batched hot path, vendored as the benchmark baseline."""

    def _classify_batch(self, txns):
        # PR 1 classified per txn with Python dict probes
        return [self.classify(t) for t in txns]

    def _dispatch_hot_group(self, pending, results, prebuilt=None):
        group = [t for _, t in pending]
        pkts, meta = prebuilt or build_packets(group, self.hot_index,
                                               self.switch_cfg)
        self._validate_mode(meta)
        for t in group:
            self.nodes[t.home].log("switch_send", t.tid,
                                   ops=[(o, k, v) for o, k, v in t.ops])
        res_d, ok_d, gids = _pr1_execute_batch(self.switch, pkts, meta,
                                               self.switch_mode)
        res = np.asarray(res_d)                  # one host sync per group
        order = meta["order"]
        for b, (i, t) in enumerate(pending):
            n_ops = len(t.ops)
            self.nodes[t.home].log("switch_result", t.tid, gid=int(gids[b]),
                                   results=res[b, :n_ops].tolist())
            self.stats["commits"] += 1
            if pkts["is_multipass"][b]:
                self.stats["multipass"] += 1
            out = [0] * n_ops
            for slot in range(n_ops):
                out[order[b, slot]] = int(res[b, slot])
            results[i] = out


# ------------------------------------------------------------- harness ----

def fresh(kind, hi, loads, mi=2):
    if kind == "pr1":
        c = PR1Cluster(N_NODES, SW, hi, use_switch=True)
    else:
        c = Cluster(N_NODES, SW, hi, use_switch=True,
                    async_hot=(kind == "async"), max_inflight=mi)
    for k, v in loads:
        c.load(k, v)
    return c


def run_once(kind, txns, hi, loads, batch, mi=2):
    c = fresh(kind, hi, loads, mi)
    gc.collect()
    t0 = time.perf_counter()
    for i in range(0, len(txns), batch):
        c.run_batch(txns[i:i + batch])
    c.drain()
    dt = time.perf_counter() - t0
    return c, dt


def timed(kind, txns, hi, loads, batch, reps, mi=2):
    run_once(kind, txns, hi, loads, batch, mi)          # warm (compiles)
    runs = [run_once(kind, txns, hi, loads, batch, mi)
            for _ in range(reps)]
    c = runs[-1][0]                 # counters identical across reps
    dt = statistics.median([r[1] for r in runs])
    return dict(time_ms=round(dt * 1e3, 3),
                txn_per_s=round(len(txns) / dt, 1),
                commits=int(c.stats["commits"]),
                dispatches=int(c.switch.dispatch_count))


def equivalence(txns, hi, loads, batch):
    """pr1 / sync / async must land on identical client results,
    registers, GIDs, stats and WAL-recovered registers."""
    outs = {}
    for kind in ("pr1", "sync", "async"):
        c = fresh(kind, hi, loads, mi=3)
        res = []
        for i in range(0, len(txns), batch):
            res += list(c.run_batch(txns[i:i + batch]))
        c.drain()
        wal_results = [(n.id, e.tid, e.payload["gid"], e.payload["results"])
                       for n in c.nodes for e in n.wal
                       if e.kind == "switch_result"]
        before = np.asarray(c.switch.read_all()).copy()
        c.crash_switch_and_recover()
        outs[kind] = dict(res=res, regs=before,
                          rec=np.asarray(c.switch.read_all()),
                          gid=c.switch.next_gid, stats=dict(c.stats),
                          wal=sorted(wal_results))
    ref = outs["pr1"]
    checks = {}
    for kind in ("sync", "async"):
        o = outs[kind]
        checks[kind] = dict(
            results_equal=o["res"] == ref["res"],
            registers_equal=bool((o["regs"] == ref["regs"]).all()),
            recovery_equal=bool((o["rec"] == ref["rec"]).all()),
            gids_equal=o["gid"] == ref["gid"],
            stats_equal=o["stats"] == ref["stats"],
            wal_results_equal=o["wal"] == ref["wal"])
        assert all(checks[kind].values()), (kind, checks[kind])
    return checks


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small CI smoke (~30 s); still asserts "
                         "async == sync == pr1 equivalence")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    args = ap.parse_args()

    n = 1024 if args.fast else 4096
    batch = 256
    reps = 3 if args.fast else 7
    mis = (1, 2, 4)

    results = {"config": dict(fast=args.fast, n_txns=n, batch=batch,
                              reps=reps, max_inflight_sweep=list(mis),
                              n_nodes=N_NODES, n_stages=SW.n_stages,
                              regs_per_stage=SW.regs_per_stage,
                              cpu_count=os.cpu_count())}
    print(f"async hot-path benchmark (n={n}, B={batch}, reps={reps})")

    # equivalence FIRST (fixed seed): a wrong fast path must never get
    # to publish a speedup
    txns, hi, loads = ycsb_workload("A", n, all_hot=True)
    results["equivalence"] = equivalence(txns[:512], hi, loads, batch)
    print("  equivalence pr1 == sync == async: OK")

    hl = {}
    hl["pr1"] = timed("pr1", txns, hi, loads, batch, reps)
    hl["sync"] = timed("sync", txns, hi, loads, batch, reps)
    best = None
    for mi in mis:
        r = timed("async", txns, hi, loads, batch, reps, mi=mi)
        r["max_inflight"] = mi
        hl[f"async_mi{mi}"] = r
        if best is None or r["txn_per_s"] > best["txn_per_s"]:
            best = r
    hl["speedup_async_vs_pr1"] = round(
        best["txn_per_s"] / hl["pr1"]["txn_per_s"], 3)
    hl["speedup_async_vs_sync"] = round(
        best["txn_per_s"] / hl["sync"]["txn_per_s"], 3)
    hl["speedup_sync_vs_pr1"] = round(
        hl["sync"]["txn_per_s"] / hl["pr1"]["txn_per_s"], 3)
    hl["best_inflight"] = best["max_inflight"]
    results["headline_allhot_b256"] = hl
    print(f"  all-hot YCSB-A B=256: pr1 {hl['pr1']['txn_per_s']:>10,.0f} "
          f"txn/s  sync {hl['sync']['txn_per_s']:>10,.0f}  async "
          f"{best['txn_per_s']:>10,.0f} (mi={best['max_inflight']}) — "
          f"{hl['speedup_async_vs_pr1']}x vs pr1, "
          f"{hl['speedup_async_vs_sync']}x vs sync")

    # secondary: mixed workloads (hot groups interleaved with cold/warm)
    results["workloads"] = {}
    for name, (txns, hi, loads) in (
            ("ycsb_A", ycsb_workload("A", n // 2)),
            ("smallbank", smallbank_workload(n // 2))):
        w = {"pr1": timed("pr1", txns, hi, loads, batch, max(reps - 4, 2)),
             "async": timed("async", txns, hi, loads, batch,
                            max(reps - 4, 2), mi=4)}
        w["speedup_async_vs_pr1"] = round(
            w["async"]["txn_per_s"] / w["pr1"]["txn_per_s"], 3)
        results["workloads"][name] = w
        print(f"  {name:12s} pr1 {w['pr1']['txn_per_s']:>10,.0f} txn/s  "
              f"async {w['async']['txn_per_s']:>10,.0f} "
              f"({w['speedup_async_vs_pr1']}x)")

    results["headline_async_speedup"] = hl["speedup_async_vs_pr1"]
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")
    if hl["speedup_async_vs_pr1"] < 1.5:
        print(f"WARNING: async speedup {hl['speedup_async_vs_pr1']}x "
              f"< 1.5x acceptance target vs the PR 1 batched path")


if __name__ == "__main__":
    main()

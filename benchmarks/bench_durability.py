"""Durability benchmarks (ISSUE 6): bounded recovery + priced failover.

Three sections:

  recovery   — FUNCTIONAL: the same mostly-hot YCSB stream runs under a
               sweep of checkpoint intervals (N switch sends per
               incremental checkpoint; 0 = only the initial offload
               snapshot), then the switch crashes and recovery replays
               the post-checkpoint WAL suffix.  Tighter intervals replay
               fewer sends and recover faster — the headline is the
               recovery-time speedup of the tightest interval over the
               uncheckpointed baseline.  Every run asserts byte-identical
               registers after recovery.
  standby    — FUNCTIONAL: same stream with a warm standby tailing the
               checkpoint stream; ``fail_over()`` promotes it, replaying
               ONLY the sends since the last checkpoint (the
               bounded-recovery contract, asserted).
  sim        — DES mirror: one switch crash mid-run, outage =
               ``t_failover`` + replayed sends * ``t_replay_send``,
               swept over the checkpoint cadence.

The emitted WAL (``--wal-out``) is one node's segmented hash-chained log
saved to disk; CI runs ``python -m repro.db.wal verify`` over it as an
end-to-end integrity check of the persistence path.

  PYTHONPATH=src python benchmarks/bench_durability.py [--fast]
      [--out FILE] [--wal-out DIR]

Emits BENCH_durability.json.
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


def recovery_section(fast: bool, wal_out: str | None):
    from benchmarks import common as C

    n = 400 if fast else 2000
    intervals = C.DURABILITY_CKPT_INTERVALS_FAST if fast \
        else C.DURABILITY_CKPT_INTERVALS_FULL
    txns, hi = C.durability_workload(n)
    rows, wal_info = [], None
    for interval in intervals:
        c, row = C.durability_recovery_row(txns, hi, interval)
        rows.append(row)
        print(f"recovery interval={interval:4d}: {row['recover_s']*1e3:7.1f} ms"
              f"  replayed={row['replayed']:5d}"
              f"  checkpoints={row['checkpoints']}")
        if wal_out and interval == intervals[-1]:
            node = c.nodes[0]
            node.wal.save(wal_out)
            wal_info = dict(node=0, saved_to=wal_out, **node.wal.verify())
            print(f"wal saved: {wal_info['records']} records, "
                  f"{wal_info['segments']} segments -> {wal_out}")
    base = rows[0]
    tight = rows[-1]
    assert tight["replayed"] < base["replayed"], \
        "tighter checkpoints must bound replay"
    return dict(rows=rows, wal=wal_info,
                speedup=base["recover_s"] / max(tight["recover_s"], 1e-9),
                replay_reduction=base["replayed"] / max(tight["replayed"], 1))


def standby_section(fast: bool):
    from benchmarks import common as C

    n = 400 if fast else 2000
    interval = C.DURABILITY_CKPT_INTERVALS_FAST[-1] if fast \
        else C.DURABILITY_CKPT_INTERVALS_FULL[-1]
    txns, hi = C.durability_workload(n)
    row = C.durability_standby_row(txns, hi, interval)
    print(f"standby  interval={interval:4d}: takeover "
          f"{row['takeover_s']*1e3:7.1f} ms  replayed={row['replayed']}")
    return row


def sim_section(fast: bool):
    from benchmarks import common as C

    rows = C.durability_sim_rows(sim_time=0.01 if fast else 0.02)
    for r in rows:
        print(f"sim ckpt={r['interval']*1e3:5.2f} ms: outage "
              f"{r['outage_s']*1e6:8.1f} us  replayed={r['replayed']:6d}  "
              f"tput={r['throughput']:.2e}")
    outages = [r["outage_s"] for r in rows]
    assert min(outages[1:]) < outages[0], \
        "checkpointing must shrink the failover outage"
    return dict(rows=rows, outage_reduction=outages[0] / min(outages[1:]))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: fewer txns, fewer sweep points")
    ap.add_argument("--out", default="BENCH_durability.json")
    ap.add_argument("--wal-out", default=None,
                    help="directory to persist one node's segmented WAL "
                         "(CI verifies it with python -m repro.db.wal)")
    args = ap.parse_args()
    t0 = time.time()
    recovery = recovery_section(args.fast, args.wal_out)
    standby = standby_section(args.fast)
    sim = sim_section(args.fast)
    results = dict(
        fast=args.fast,
        recovery=recovery,
        standby=standby,
        sim_failover=sim,
        headline_recovery_speedup=recovery["speedup"],
        elapsed_s=time.time() - t0,
    )
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {args.out} in {results['elapsed_s']:.0f}s "
          f"(recovery speedup {recovery['speedup']:.2f}x, replay reduction "
          f"{recovery['replay_reduction']:.1f}x, sim outage reduction "
          f"{sim['outage_reduction']:.1f}x)")


if __name__ == "__main__":
    main()

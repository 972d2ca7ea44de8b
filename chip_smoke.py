#!/usr/bin/env python3
"""Chip smoke test: the P4DB switch plane end to end on a TPU.

Drives the main path through the entry points a user calls
(``Cluster.load`` / ``run_batch`` / ``read_batch`` / ``scan`` /
``crash_switch_and_recover``) at the default switch width, 20 stages x
65,536 int32 registers, with the paper's YCSB and SmallBank deployments
(8 nodes; 100,000 keys or 125,000 accounts per node).  Every phase is
checked against the plain serial reference ``tests/oracle.py``: every
client result, every final register and home-store value.

    python chip_smoke.py               # one chip: every single-switch phase
    python chip_smoke.py --four-chips  # only N = 4 switches on four chips,
                                       # against N = 1 on the same traffic

Each phase prints one JSON line: txns committed, switch dispatches,
backend compiles and their seconds, persistent-cache hits, and its wall
seconds.  The seconds are smoke timings, not benchmark numbers.  The last
line is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero and prints no result; it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from oracle import OracleDB  # noqa: E402
from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.hotset import build_hot_index  # noqa: E402
from repro.core.packets import SwitchConfig, build_packets  # noqa: E402
from repro.db.dbms import Cluster  # noqa: E402
from repro.db.txn import node_of  # noqa: E402
from repro.kernels.switch_txn.switch_txn import interpret_default  # noqa: E402
from repro.workloads import smallbank, ycsb  # noqa: E402

SEED = 0
N_NODES = 8              # YCSBParams / SmallBankParams defaults
N_YCSB = 4000            # YCSB-A txns per stream
N_SMALLBANK = 3000
N_PALLAS = 1000          # the pallas cluster runs the kernels per instr
N_FOUR = 2000
SAMPLE = 4000            # txns sampled offline to detect the hot set
BATCH = 1024             # run_batch admission batch
COLD_BALANCE = 10 ** 6   # cold SmallBank CADDs never abort (see smallbank)


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


class CompileCounter:
    """Backend compiles (a persistent-cache hit is still a compile request,
    counted apart) and their seconds, from JAX's monitoring events."""

    def __init__(self):
        self.compiles, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.compiles, self.seconds, self.hits


# ----------------------------------------------------------- helpers ----

def device_info():
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def make_cluster(cfg, hi, loads, **kw):
    """A cluster of the deployments' 8 nodes, seeded through
    ``Cluster.load`` (logged writes; hot keys dispatch to the switch),
    then checkpointed."""
    c = Cluster(N_NODES, cfg, hi, **kw)
    for k, v in loads.items():
        c.load(k, v)
    c.snapshot_offload()
    return c


def make_oracle(loads):
    o = OracleDB()
    for k, v in loads.items():
        o.load(k, v)
    return o


def run_stream(c, txns, batch_sizes):
    """Admit ``txns`` through ``run_batch`` in consecutive batches of the
    given sizes (the last size repeats); returns every client result."""
    out, i, sizes = [], 0, list(batch_sizes)
    while i < len(txns):
        b = sizes.pop(0) if len(sizes) > 1 else sizes[0]
        out += list(c.run_batch([copy.deepcopy(t) for t in txns[i:i + b]]))
        i += b
    c.drain()
    return out


def check_results(got, want, what):
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    check(len(got) == len(want) and not bad,
          f"{what}: {len(bad)} client results differ from the oracle, "
          f"first at txn {bad[:1]}: {[got[i] for i in bad[:1]]} != "
          f"{[want[i] for i in bad[:1]]}")


def key_values(c, keys):
    """Committed value of every key: hot keys from the register file (at
    their placement slot), cold keys from their home-node store."""
    keys = np.asarray(sorted(keys), np.int64)
    hot = c.hot_index.hot_mask_np(keys)
    regs = c.switch.read_all()
    sw, st, rg = c.hot_index.slots_np(keys[hot])
    vals = np.zeros(len(keys), np.int64)
    vals[hot] = regs[st, rg] if regs.ndim == 2 else regs[sw, st, rg]
    vals[~hot] = [c.nodes[node_of(int(k))].store[int(k)]
                  for k in keys[~hot]]
    return dict(zip(keys.tolist(), vals.tolist()))


def check_state(c, o, keys, what):
    got = key_values(c, keys)
    bad = [k for k, v in got.items() if v != o.values[k]]
    check(not bad, f"{what}: {len(bad)} register/store values differ from "
                   f"the oracle, e.g. key {bad[:1]}")
    return got


def gids(c):
    """tid -> GID of every switch result the nodes logged."""
    return {r.tid: r.payload["gid"] for n in c.nodes for r in n.wal
            if r.kind == "switch_result"}


def counts(*clusters):
    return dict(committed=sum(c.stats["commits"] for c in clusters),
                dispatch_count=sum(c.switch.dispatch_count
                                   for c in clusters))


# --------------------------------------------------------- workloads ----

def ycsb_workload(cfg, n_txns, seed=SEED, p=None):
    """YCSB-A at the paper's defaults; hot index from a sampled trace."""
    p = p or ycsb.YCSBParams()
    sample = ycsb.generate(np.random.default_rng(seed), SAMPLE, p)
    hi = build_hot_index(ycsb.traces(sample), len(ycsb.hot_keys(p)), cfg)
    txns = ycsb.generate(np.random.default_rng(seed + 1), n_txns, p)
    keys = set(ycsb.hot_keys(p)) | {k for t in txns for k in t.keys()}
    rng = np.random.default_rng(seed + 2)
    loads = dict(zip(sorted(keys), rng.integers(0, 1000, len(keys)).tolist()))
    return p, hi, txns, loads


def smallbank_workload(cfg, n_txns, seed=SEED):
    """SmallBank at the paper's defaults.  Hot balances start small, so
    hot CADDs fail and the switch keeps the value (the oracle's rule);
    cold balances start at COLD_BALANCE, so no cold CADD aborts (a cold
    abort is a retry, which the serial oracle does not model)."""
    p = smallbank.SmallBankParams()
    sample = smallbank.generate(np.random.default_rng(seed), SAMPLE, p)
    hot_keys = smallbank.hot_keys(p)
    hi = build_hot_index(smallbank.traces(sample), len(hot_keys), cfg)
    txns = smallbank.generate(np.random.default_rng(seed + 1), n_txns, p)
    rng = np.random.default_rng(seed + 2)
    loads = {k: int(v) for k, v in zip(hot_keys,
                                       rng.integers(0, 200, len(hot_keys)))}
    for t in txns:
        for k in t.keys():
            loads.setdefault(k, COLD_BALANCE)
    return hi, txns, loads


# ------------------------------------------------------------ phases ----

def phase_ycsb(cfg, state):
    """YCSB-A through run_batch in auto mode, sync and async."""
    p, hi, txns, loads = ycsb_workload(cfg, N_YCSB)
    o = make_oracle(loads)
    want = [o.apply_txn(t) for t in txns]
    out = []
    for async_hot in (False, True):
        c = make_cluster(cfg, hi, loads, async_hot=async_hot)
        got = run_stream(c, txns, [BATCH])
        check_results(got, want, f"ycsb-a async_hot={async_hot}")
        check_state(c, o, loads, f"ycsb-a async_hot={async_hot}")
        check(c.stats["gave_up"] == 0, "ycsb-a: a txn gave up")
        out.append(c)
    state["ycsb"] = (p, hi, txns, loads, o, out[0])
    return dict(counts(*out), hot=out[0].stats["hot"],
                cold=out[0].stats["cold"])


def phase_smallbank(cfg, state):
    """SmallBank: CADD and ADDP.  Batches of 2 give groups with ADDP and
    no CADD (the staged engine); batches of 512 run the serial engine
    over thousands of instructions."""
    hi, txns, loads = smallbank_workload(cfg, N_SMALLBANK)
    o = make_oracle(loads)
    want = [o.apply_txn(t) for t in txns]
    c = make_cluster(cfg, hi, loads)
    got = run_stream(c, txns, [2] * 500 + [512])
    check_results(got, want, "smallbank")
    check_state(c, o, loads, "smallbank")
    check(c.stats["gave_up"] == 0, "smallbank: a txn gave up")
    S, R = cfg.n_stages, cfg.regs_per_stage
    modes = {k[0] for k in engine._DISPATCH_CACHE if k[1:3] == (S, R)}
    check({"serial", "staged"} <= modes,
          f"smallbank: auto ran {sorted(modes)}, not serial and staged")
    state["smallbank"] = c
    return dict(counts(c), engines=sorted(modes))


def reads_and_scans(c, o, p, hot_keys, what):
    """read_batch on YCSB-C keys; scan with and without a limit, over the
    hot set and over a hot + cold key list."""
    rtx = ycsb.generate(np.random.default_rng(SEED + 3), 500,
                        replace(p, variant="C"))
    keys = [k for t in rtx for k in t.keys()]
    check(c.read_batch(keys) == o.read_batch(keys), f"{what}: read_batch")
    cold = sorted(set(keys) - set(hot_keys))[:200]
    mixed = sorted(hot_keys) + cold
    for lo, hi in ((250, 749), (0, 99), (2000, 3000)):
        check(c.scan(lo, hi) == o.scan(lo, hi, hot_keys),
              f"{what}: scan [{lo}, {hi}]")
        check(c.scan(lo, hi, limit=25) == o.scan(lo, hi, hot_keys, 25),
              f"{what}: scan [{lo}, {hi}] limit 25")
        check(c.scan(lo, hi, keys=mixed) == o.scan(lo, hi, mixed),
              f"{what}: scan [{lo}, {hi}] over hot + cold keys")
    return len(keys)


def phase_reads(cfg, state):
    """The read tier: the jit gather and the compiled scan kernels."""
    p, hi, _, _, o, c = state["ycsb"]
    n = reads_and_scans(c, o, p, sorted(hi.placement.slot), "reads")
    return dict(reads=n, switch_reads=c.stats["switch_reads"],
                scans_switch=c.stats["scans_switch"],
                read_dispatches=c.switch.read_dispatch_count)


def phase_pallas(cfg, state):
    """switch_mode="pallas": the switch_exec and gather kernels on the
    write path and the gather kernel on the read path; identical results
    and registers to the auto cluster on the same stream."""
    p, hi, txns, loads, _, _ = state["ycsb"]
    txns = txns[:N_PALLAS]
    o = make_oracle(loads)
    want = [o.apply_txn(t) for t in txns]
    ca = make_cluster(cfg, hi, loads)
    cp = make_cluster(cfg, hi, loads, switch_mode="pallas")
    ra = run_stream(ca, txns, [256])
    rp = run_stream(cp, txns, [256])
    check(rp == ra, "pallas: client results differ from the auto cluster")
    check_results(rp, want, "pallas")
    check(np.array_equal(cp.switch.read_all(), ca.switch.read_all()),
          "pallas: registers differ from the auto cluster")
    check_state(cp, o, loads, "pallas")
    reads_and_scans(cp, o, p, sorted(hi.placement.slot), "pallas reads")
    return counts(cp)


def phase_recover(cfg, state):
    """crash_switch_and_recover: registers rebuilt from the WALs and the
    checkpoint chain are byte-identical to the registers before."""
    _, _, _, loads, o, c = state["ycsb"]
    replayed = 0
    for name, cl in (("ycsb-a", c), ("smallbank", state["smallbank"])):
        before = cl.switch.read_all().copy()
        known, unknown = cl.crash_switch_and_recover()
        after = cl.switch.read_all()
        check(before.dtype == after.dtype
              and before.tobytes() == after.tobytes(),
              f"recover {name}: registers differ after replay")
        replayed += known + unknown
    check_state(c, o, loads, "recover ycsb-a")
    return dict(replayed_txns=replayed)


def phase_four_switches(cfg, state):
    """N = 4 switches, one plane per chip, against N = 1 on chip 0: the
    same seeded YCSB-A stream (cross-shard rows included) gives equal
    client results, GIDs and per-key values."""
    devs = jax.devices()
    cfg4 = replace(cfg, n_switches=4)
    p, hi1, txns, loads = ycsb_workload(cfg, N_FOUR)
    _, hi4, _, _ = ycsb_workload(cfg4, N_FOUR)
    check(set(hi4.placement.slot) == set(hi1.placement.slot),
          "four: the hot sets differ")
    o = make_oracle(loads)
    want = [o.apply_txn(t) for t in txns]
    c1 = make_cluster(cfg, hi1, loads)
    r1 = run_stream(c1, txns, [BATCH])
    check_results(r1, want, "four: N=1")
    v1 = check_state(c1, o, loads, "four: N=1")
    _, meta = build_packets(
        [t for t in txns if all(hi4.is_hot(k) for k in t.keys())],
        hi4, cfg4)
    cross = int(np.count_nonzero(meta["shard"] < 0))
    check(cross > 0, "four: the stream has no cross-shard rows")
    out = [c1]
    for async_hot in (False, True):
        c4 = make_cluster(cfg4, hi4, loads, async_hot=async_hot)
        planes = [next(iter(pl.registers.devices()))
                  for pl in c4.switch.planes]
        check(len(set(planes)) == 4 and set(planes) <= set(devs),
              f"four: planes sit on {planes}, not four distinct chips")
        r4 = run_stream(c4, txns, [BATCH])
        check(r4 == r1, f"four async={async_hot}: results differ from N=1")
        check(gids(c4) == gids(c1),
              f"four async={async_hot}: GIDs differ from N=1")
        check(check_state(c4, o, loads, "four") == v1,
              f"four async={async_hot}: key values differ from N=1")
        after = [next(iter(pl.registers.devices()))
                 for pl in c4.switch.planes]
        check(after == planes, "four: a plane's registers moved chips")
        reads_and_scans(c4, o, p, sorted(hi4.placement.slot),
                        f"four async={async_hot} reads")
        out.append(c4)
    return dict(counts(*out), cross_shard_rows=cross,
                planes=[str(d) for d in planes])


ONE_CHIP = [("ycsb_a", phase_ycsb), ("smallbank", phase_smallbank),
            ("reads_scans", phase_reads), ("pallas", phase_pallas),
            ("crash_recover", phase_recover)]
FOUR_CHIPS = [("four_switches", phase_four_switches)]


def run(phases, cfg):
    counter = CompileCounter()
    dev = device_info()
    state = {}
    for name, fn in phases:
        c0, s0, h0 = counter.snapshot()
        t0 = time.perf_counter()
        info = fn(cfg, state)
        wall = time.perf_counter() - t0
        c1, s1, h1 = counter.snapshot()
        print(json.dumps(dict(phase=name, **info, compiles=c1 - c0,
                              compile_s=s1 - s0, cache_hits=h1 - h0,
                              smoke_wall_s=wall, platform=dev["platform"],
                              device_kind=dev["kind"],
                              device_count=dev["count"])), flush=True)
    return dev


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only N = 4 switches on four chips vs N = 1")
    args = ap.parse_args(argv)
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} chips, found {dev['count']}",
              file=sys.stderr)
        return 1
    check(not interpret_default(), "Pallas kernels would run interpreted")
    cache = enable_compile_cache()
    print(json.dumps(dict(compile_cache=cache)), flush=True)
    run(FOUR_CHIPS if args.four_chips else ONE_CHIP, SwitchConfig())
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

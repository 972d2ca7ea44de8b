#!/usr/bin/env python3
"""The control of the check that decides ``correct``.

The system runs no model and states no precision, so the control breaks
one guarantee every configuration states: hot txns serialisable in
admission order.  It is the serial reference, put in the program's
place, with the hot txns of each ``run_batch`` batch executed in reverse
admission order.  Its outputs go through the same comparison as a run's;
the check has to find them not correct.

    python bench/control.py --workload <cell> --txns <n> --seeds <s> ...

prints, per seed, the compared numbers of the control and of the
reference itself (which must read 0) for the cell's first ``n`` txns.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH)]

import numpy as np  # noqa: E402

from reference import Reference, compare, flat_ops, run_reference  # noqa


def reordered(arrs, n, initial, hot_keys, batch):
    """The reference's outputs with each batch's hot txns run last to
    first (cold and warm txns keep their places)."""
    ref = Reference(initial, hot_keys)
    flat, K = flat_ops({f: arrs[f][:n] for f in ("op", "key", "val")})
    n_ops = arrs["n_ops"][:n].tolist()
    ops = [flat[i * K:i * K + n_ops[i]] for i in range(n)]
    hot = [all(k in ref.hot for _, k, _ in o) for o in ops]
    results = [None] * n
    for lo in range(0, n, batch):
        idx = list(range(lo, min(lo + batch, n)))
        order = [i for i in idx if hot[i]][::-1] + [i for i in idx
                                                    if not hot[i]]
        for i in order:
            results[i] = ref.apply(ops[i])
    return results, ref.values


def readings(cfg, traffic, seed, n):
    """(control's, reference's) compared numbers for the first ``n`` txns
    of ``seed``'s traffic."""
    import harness
    fam = harness.family(cfg["workload"])
    p = cfg["params"]
    arrs = fam.generate(harness.rng(seed, harness.TRAFFIC), n, p)
    hot_keys = fam.hot_keys(p)
    valid = np.arange(arrs["op"].shape[1])[None, :] < arrs["n_ops"][:, None]
    keys, vals = fam.initial_values(harness.rng(seed, harness.LOAD),
                                    np.unique(arrs["key"][valid]), p)
    initial = dict(zip(keys.tolist(), vals.tolist()))
    want, want_values = run_reference(arrs, n, initial, hot_keys)
    hot = set(hot_keys)

    def as_run(values):
        return dict(hot={k: v for k, v in values.items() if k in hot},
                    cold={k: v for k, v in values.items() if k not in hot})

    got, got_values = reordered(arrs, n, initial, hot_keys,
                                traffic["batch_txns"])
    control = compare(got, want, as_run(got_values), want_values)
    sound = compare(want, want, as_run(want_values), want_values)
    return control, sound


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--txns", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness
    import manifest
    cell = manifest.cell(manifest.load(BENCH.parent / "BENCHMARK.json"),
                         args.workload)
    cfg = harness.load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = harness.load_json(BENCH / "traffic" / f"{cell['name']}.json")
    for seed in args.seeds:
        control, sound = readings(cfg, traffic, seed, args.txns)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              txns=args.txns, control=control,
                              reference=sound)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json`` from ``--seed``, warms up
every program its window can use, measures for ``--seconds``, checks
every client result and final value against the serial reference, and
prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``breakdown`` (traced runs), ``device`` and, last, ``checks``: each
number compared with its limit, also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import manifest  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    man = manifest.load(ROOT / "BENCHMARK.json")
    cell = manifest.cell(man, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    from repro.common.compile_cache import enable_compile_cache
    from repro.kernels.switch_txn.switch_txn import interpret_default
    if interpret_default():
        print("bench: Pallas kernels would run interpreted", file=sys.stderr)
        return 1
    enable_compile_cache()

    import harness
    out = harness.run_cell(man, cell, args.seed, args.seconds,
                           bool(args.trace), T_START)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

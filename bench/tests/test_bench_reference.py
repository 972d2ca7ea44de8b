"""The serial reference agrees with ``Cluster`` on short streams of every
configuration at 1,024 registers per stage, through the harness's own
set-up and served path."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import json

import pytest

import harness
from reference import compare, run_reference

N, BATCH = 600, 200


def tiny(name, **params):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["switch"]["regs_per_stage"] = 1024
    cfg["params"].update(params)
    return cfg


@pytest.mark.parametrize("name,params", [
    ("ycsb_a", {}),
    ("smallbank", {}),
    # balances low enough that CADDs fail: a hot one leaves the balance,
    # a cold one aborts until the client gets GAVE_UP
    ("smallbank", {"min_balance": 0, "max_balance": 60}),
    ("ycsb_a_4sw", {}),
])
def test_the_reference_agrees_with_the_cluster(name, params):
    cfg = tiny(name, **params)
    fam = harness.family(cfg["workload"])
    p = cfg["params"]
    arrs = fam.generate(harness.rng(5, harness.TRAFFIC), N, p)
    hot_keys = fam.hot_keys(p)
    c, initial = harness.build_cluster(cfg, fam, arrs, hot_keys, 5, None)
    server = harness.Server(c, harness.make_txns(arrs, fam.KINDS))
    for lo in range(0, N, BATCH):
        server.serve(lo, lo + BATCH)
    got = [r if isinstance(r, list) else None for r in server.results]
    want, want_values = run_reference(arrs, N, initial, hot_keys)
    assert got == want
    checks = compare(got, want, harness.final_values(c, hot_keys),
                     want_values)
    assert {k: v for k, (v, _) in checks.items()} == dict.fromkeys(checks, 0)
    if params:
        assert None in want, "no cold CADD failed: the case tests nothing"
        assert c.stats["gave_up"] == want.count(None)

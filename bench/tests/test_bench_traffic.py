"""The vectorised traffic copies draw what ``repro.workloads`` draws: the
same hot-txn share, remote-op share and op mix over a fixed seed; and
the benchmark's opcode copy is the program's."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import json

import numpy as np
import pytest

import ops
from workloads import smallbank, ycsb

N = 6000


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_opcodes_and_key_encoding_are_the_programs():
    from repro.core import packets
    from repro.db.txn import key_of
    for name in ("NOP", "READ", "WRITE", "ADD", "CADD", "ADDP"):
        assert getattr(ops, name) == getattr(packets, name)
    assert ops.key_of(3, 17) == key_of(3, 17)


def stats(op, key, n_ops, home, hot_set, per_node):
    valid = np.arange(op.shape[1])[None, :] < n_ops[:, None]
    is_hot = np.isin(key, list(hot_set)) & valid
    hot_txn = (is_hot | ~valid).all(axis=1)
    remote = (key // ops.NODE_STRIDE != home[:, None]) & valid
    mix = {o: float(((op == o) & valid).sum() / valid.sum())
           for o in range(6)}
    return dict(hot=float(hot_txn.mean()),
                remote=float(remote.sum() / valid.sum()), mix=mix,
                ops_per_txn=float(n_ops.mean()))


def program_arrays(txns, K):
    n = len(txns)
    op = np.zeros((n, K), np.int64)
    key = np.zeros((n, K), np.int64)
    for i, t in enumerate(txns):
        for j, (o, k, _) in enumerate(t.ops):
            op[i, j], key[i, j] = o, k
    n_ops = np.array([len(t.ops) for t in txns])
    home = np.array([t.home for t in txns])
    return op, key, n_ops, home


def close(a, b):
    assert a["hot"] == pytest.approx(b["hot"], abs=0.02)
    assert a["remote"] == pytest.approx(b["remote"], abs=0.01)
    assert a["ops_per_txn"] == pytest.approx(b["ops_per_txn"], rel=0.02)
    for o in a["mix"]:
        assert a["mix"][o] == pytest.approx(b["mix"][o], abs=0.015)


def test_ycsb_matches_the_program_generator():
    from repro.workloads import ycsb as prog
    p = cfg("ycsb_a")["params"]
    mine = ycsb.generate(np.random.default_rng(11), N, p)
    pp = prog.YCSBParams(**{k: v for k, v in p.items()
                            if k != "write_frac"})
    theirs = program_arrays(prog.generate(np.random.default_rng(11), N, pp),
                            p["ops_per_txn"])
    hot = set(ycsb.hot_keys(p))
    assert hot == set(prog.hot_keys(pp))
    a = stats(mine["op"], mine["key"], mine["n_ops"], mine["home"], hot, 0)
    b = stats(*theirs, hot, 0)
    close(a, b)
    assert a["hot"] == pytest.approx(p["p_hot_txn"], abs=0.02)


def test_smallbank_matches_the_program_generator():
    from repro.workloads import smallbank as prog
    p = cfg("smallbank")["params"]
    assert tuple(p["mix"][k] for k in smallbank.KINDS) == prog.MIX
    mine = smallbank.generate(np.random.default_rng(12), N, p)
    pp = prog.SmallBankParams(**{k: p[k] for k in (
        "n_nodes", "accounts_per_node", "hot_per_node", "p_hot_txn",
        "dist_frac")})
    theirs = program_arrays(prog.generate(np.random.default_rng(12), N, pp),
                            smallbank.K)
    hot = set(smallbank.hot_keys(p))
    assert hot == set(prog.hot_keys(pp))
    close(stats(mine["op"], mine["key"], mine["n_ops"], mine["home"], hot, 0),
          stats(*theirs, hot, 0))


def test_the_same_seed_gives_the_same_traffic_and_a_large_seed_works():
    p = cfg("smallbank")["params"]
    import harness
    seed = 2 ** 40 + 3
    a = smallbank.generate(harness.rng(seed, harness.TRAFFIC), 500, p)
    b = smallbank.generate(harness.rng(seed, harness.TRAFFIC), 500, p)
    assert all(np.array_equal(a[k], b[k]) for k in a)

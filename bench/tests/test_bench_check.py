"""The check that decides ``correct``: sound runs pass it; the control
(the reference with each batch's hot txns run in reverse order) and runs
whose timed path is broken underneath fail it.  The runs skip the look
for a chip and drive the rest of a run at a tiny size."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import json
import time

import numpy as np
import pytest

import control
import harness
import manifest

MAN = manifest.load(BENCH.parent / "BENCHMARK.json")


def tiny_run(cell_name, batch=32, seconds=0.3, seed=2 ** 33 + 7):
    config = cell_name.split(".")[0]
    cell = dict(name=cell_name, config=config, chips=1)
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["switch"].update(n_stages=8, regs_per_stage=1024)
    traffic = json.loads((BENCH / "traffic" / f"{cell_name}.json")
                         .read_text())
    traffic.update(batch_txns=batch, warmup_batches=1)
    if traffic["loop"] == "closed":
        traffic["max_txn_per_s"] = 20000
    else:
        traffic["rate_txn_per_s"] = 500
    return harness.run(MAN, cell, cfg, traffic, seed, seconds, False,
                       time.perf_counter())


@pytest.mark.parametrize("cell", ["ycsb_a.closed", "ycsb_a.rate",
                                  "ycsb_a_4sw.closed"])
def test_a_sound_run_is_correct(cell):
    out = tiny_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s"}


def test_a_sound_smallbank_run_is_correct():
    out = tiny_run("smallbank.closed", batch=8, seconds=0.2)
    assert out["correct"], out["checks"]


def state_unchanged(mp):
    """Every engine returns the register file it was given."""
    from repro.core import engine
    for mode, impl in list(engine._ENGINE_IMPLS.items()):
        def frozen(registers, *a, _impl=impl):
            return (registers,) + tuple(_impl(registers, *a)[1:])
        mp.setitem(engine._ENGINE_IMPLS, mode, frozen)
    mp.setattr(engine, "_DISPATCH_CACHE", {})


def half_batch(mp):
    """run_batch serves the first half of each batch and drops the rest."""
    from repro.db.dbms import Cluster
    orig = Cluster.run_batch
    mp.setattr(Cluster, "run_batch",
               lambda self, txns, *a, **k: orig(self, txns[:len(txns) // 2],
                                                *a, **k))


def no_exchange(mp):
    """A txn whose keys span switches is not executed across them."""
    from repro.core.engine import ShardedSwitchEngine
    mp.setattr(ShardedSwitchEngine, "_exec_cross_row",
               lambda self, op, *a: (np.zeros(len(op), np.int32),
                                     np.ones(len(op), bool)))


def answer_altered(mp):
    """One result of every dispatch is off by one where it is produced."""
    from repro.core.engine import PendingBatch
    orig = PendingBatch.results_np

    def altered(self):
        out = orig(self).copy()
        if out.size:
            out.reshape(-1)[0] += 1
        return out
    mp.setattr(PendingBatch, "results_np", altered)


@pytest.mark.parametrize("cell,fault", [
    ("ycsb_a.closed", state_unchanged),
    ("ycsb_a.closed", half_batch),
    ("ycsb_a.closed", answer_altered),
    ("ycsb_a.rate", half_batch),
    ("ycsb_a_4sw.closed", no_exchange),
    ("ycsb_a_4sw.closed", answer_altered),
])
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = tiny_run(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["ycsb_a.closed", "smallbank.closed",
                                  "ycsb_a.rate", "ycsb_a_4sw.closed"])
def test_the_control_is_not_correct_and_the_reference_is(cell):
    config = cell.split(".")[0]
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell}.json").read_text())
    for seed in (1, 2, 3):
        ctl, sound = control.readings(cfg, traffic, seed, 3000)
        assert any(v > lim for v, lim in ctl.values()), ctl
        assert all(v <= lim for v, lim in sound.values()), sound

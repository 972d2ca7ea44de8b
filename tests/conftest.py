import os
import sys

import pytest

# NB: no XLA_FLAGS here — smoke tests and benches must see the real device
# count; only launch/dryrun.py forces 512 host devices.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="session", autouse=True)
def _workload_seed_determinism():
    """Session-wide guard (ISSUE 8 satellite): every workload generator
    must be a pure function of its RNG — two same-seed instantiations
    yield identical txn streams.  Every differential harness in the
    suite (oracle twins, sync/async twins, N-switch twins) silently
    assumes this; a generator that consults global state would turn
    their failures into noise."""
    import numpy as np

    from repro.workloads import drift, smallbank, tpcc, ycsb

    def sig(txns):
        # tids come from a global counter — identity is (kind, home, ops)
        return [(t.kind, t.home, tuple(t.ops)) for t in txns]

    yp = ycsb.YCSBParams(n_nodes=2, keys_per_node=1000, hot_per_node=16)
    sp = smallbank.SmallBankParams(n_nodes=2)
    tp = tpcc.TPCCParams(n_nodes=2, n_warehouses=2)
    streams = [
        ("ycsb", lambda r: ycsb.generate(r, 60, yp)),
        ("smallbank", lambda r: smallbank.generate(r, 60, sp)),
        ("tpcc", lambda r: tpcc.generate(r, 60, tp)),
        ("drift", lambda r: drift.YCSBHotspotShift(n_nodes=2)
         .sample_phase(r, 1, 60)),
    ]
    for name, gen in streams:
        a = sig(gen(np.random.default_rng(7)))
        b = sig(gen(np.random.default_rng(7)))
        assert a == b, f"{name} generator is seed-nondeterministic"
    yield


def force_engine(monkeypatch, mode):
    """Make every switch engine resolve ``"auto"`` to ``mode`` for the
    rest of the test: the batch's engine is then ``mode``, still
    validated by the real ``SwitchEngine._resolve_mode`` (a batch ``mode``
    cannot run raises).  Patching the class also covers the engines a
    cluster rebuilds after a recovery.  ``mode="auto"`` changes nothing."""
    from repro.core.engine import SwitchEngine
    if mode == "auto":
        return
    resolve = SwitchEngine._resolve_mode

    def forced(m, has_cadd, has_addp, addp_unsafe):
        return resolve(mode if m == "auto" else m, has_cadd, has_addp,
                       addp_unsafe)

    monkeypatch.setattr(SwitchEngine, "_resolve_mode", staticmethod(forced))


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked @pytest.mark.slow (the heavy "
             "equivalence matrices; CI's slow-tests job runs them)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy matrix kept out of the default tier-1 run "
        "(wall-clock budget; see README 'Tests'). Run with --runslow.")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(
        reason="slow matrix — run with --runslow (CI slow-tests job)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)

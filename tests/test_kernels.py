"""Pallas kernels vs pure-jnp oracles, across shape/dtype sweeps
(interpret=True on CPU; same code path targets TPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_route.ops import route_positions
from repro.kernels.moe_route.ref import positions_ref
from repro.kernels.switch_txn.ops import gather_results


@pytest.mark.parametrize("B,K,m,chunk", [
    (16, 3, 7, 8),
    (64, 5, 64, 64),
    (100, 8, 301, 128),        # padding path (m not a chunk multiple)
    (1, 7, 1, 4),              # single gathered row
])
def test_result_gather_kernel(B, K, m, chunk):
    """The result-compaction gather vs a plain numpy fancy index,
    including out-of-range indices (clamped, like the fused jnp.take)."""
    rng = np.random.default_rng(B * 100 + m)
    res = jnp.asarray(rng.integers(-50, 100, (B, K)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, B * K + 3, m), jnp.int32)  # some OOR
    out = gather_results(res, idx, chunk=chunk)
    ref = np.asarray(res).reshape(-1)[np.minimum(np.asarray(idx),
                                                 B * K - 1)]
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("n,n_experts,block", [
    (64, 4, 16),
    (1000, 7, 128),        # padding path
    (4096, 128, 512),
    (513, 1, 64),          # single expert, all one segment
])
def test_moe_route_kernel(n, n_experts, block):
    rng = np.random.default_rng(n)
    ids = np.sort(rng.integers(0, n_experts, n)).astype(np.int32)
    p1 = positions_ref(jnp.asarray(ids))
    p2 = route_positions(jnp.asarray(ids), block=block)
    np.testing.assert_array_equal(p1, p2)


def test_moe_route_matches_switch_counter_semantics():
    """Positions == the pre-increment counter each token reads when tokens
    (packets) increment their expert's register in admission order."""
    from repro.core.engine import SwitchEngine
    from repro.core.packets import ADD, SwitchConfig, empty_packets
    rng = np.random.default_rng(0)
    E, N = 8, 64
    ids = np.sort(rng.integers(0, E, N)).astype(np.int32)
    cfg = SwitchConfig(n_stages=1, regs_per_stage=E, max_instrs=1)
    eng = SwitchEngine(cfg)
    p = empty_packets(N, cfg)
    p["op"][:, 0] = ADD
    p["reg"][:, 0] = ids
    p["operand"][:, 0] = 1
    res, _, _ = eng.execute(p)                  # post-increment values
    pos = np.asarray(route_positions(jnp.asarray(ids)))
    np.testing.assert_array_equal(pos, res[:, 0] - 1)

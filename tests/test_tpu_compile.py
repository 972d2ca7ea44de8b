"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

Every device program of the P4DB switch plane is lowered and compiled by
the TPU compiler at the default switch width (20 stages x 65,536 int32
registers, a 5 MiB register file), batch B = 256, K = 8 instructions:

  * the three ``kernels/switch_txn`` gather and scan programs with
    ``interpret=False``
    (Mosaic refuses what interpret mode accepts: scalar VMEM stores, more
    VMEM or SMEM than a core has);
  * the three fused jit engines (``serial`` / ``affine`` / ``staged``) the
    hot path dispatches, donation included, the serial engine's Pallas
    loop compiled for Mosaic;
  * the READ-only gather of the read tier.

Nothing runs, so this proves compilation only, not results (the kernels'
results are pinned against ref.py in interpret mode by test_kernels.py and
test_reads.py).  The topology is described inside a module fixture: only
one process may load the TPU library, so nothing here touches it while
the module is imported.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import _bucket, _compiled_engine, _compiled_reader
from repro.core.packets import SwitchConfig
from repro.kernels.switch_txn import ops as ktx
from repro.kernels.switch_txn import switch_txn

CFG = SwitchConfig()                       # 20 x 65,536: the paper's budget
S, R = CFG.n_stages, CFG.regs_per_stage
B, K = 256, CFG.max_instrs
M = B * K // 2                             # compacted result rows
SCAN_M = 400                               # YCSB's hot set: 8 nodes x 50


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """The first described chip, with JAX's persistent compilation cache
    off: a compile for a described chip is written to it but cannot be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dev):
    return jax.ShapeDtypeStruct(shape, jnp.int32,
                                sharding=SingleDeviceSharding(dev))


def _kernel_args(name, dev):
    regs = _spec((S, R), dev)
    plane = _spec((B, K), dev)
    scalar = _spec((), dev)
    if name == "gather_results":
        return (plane, _spec((M,), dev)), {}
    if name == "scan_prune":
        return (regs, _spec((SCAN_M,), dev), scalar, scalar), \
            dict(cap=SCAN_M)
    return (regs, _spec((SCAN_M,), dev), scalar, scalar), dict(k=64)


@pytest.mark.parametrize("name", ["gather_results", "scan_prune",
                                  "scan_topk"])
def test_switch_txn_kernel_compiles(chip, name):
    args, static = _kernel_args(name, chip)
    compiled = getattr(ktx, name).lower(*args, interpret=False,
                                        **static).compile()
    # the Mosaic kernel itself is in the program, not an interpreted loop
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["serial", "affine", "staged"])
def test_fused_engine_compiles(chip, mode, monkeypatch):
    # as on the chip, the serial engine's Pallas loop compiles for Mosaic
    monkeypatch.setattr(switch_txn, "interpret_default", lambda: False)
    fn = _compiled_engine(mode, S, R, B, K, _bucket(M), chip)
    mem = fn.memory_analysis()
    # the register file is donated: updated in place, not copied
    assert mem.alias_size_in_bytes >= S * R * 4
    assert ("tpu_custom_call" in fn.as_text()) == (mode == "serial")


def test_read_gather_compiles(chip):
    fn = _compiled_reader(S, R, _bucket(SCAN_M), chip)
    assert fn.memory_analysis() is not None

"""jit'd wrappers around the switch_txn Pallas kernels: the result gather
and the scan-prune / top-k queries over the register file."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.switch_txn.switch_txn import (result_gather_call,
                                                 scan_prune_call)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gather_results(res, idx, chunk=1024, interpret=None):
    """Result compaction for the async hot path: gather the device-only
    result positions out of the full [B, K] plane so the host transfer
    covers only what the client actually reads.

    res: [B, K] int32; idx: [M] int32 flat row-major positions (clamped).
    Returns [M] int32."""
    m = idx.shape[0]
    return result_gather_call(res.reshape(-1), idx,
                              chunk=min(chunk, max(m, 1)),
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cap", "chunk", "interpret"))
def scan_prune(registers, idx, lo, hi, cap, chunk=1024, interpret=None):
    """Scan/filter query over the hot slots, pruned on device.

    Composes the PR 5 result-compaction gather with the predicate-scan
    kernel in ONE compiled call: gather the ``idx`` slots out of the
    register file, filter by ``lo <= v <= hi``, compact the first ``cap``
    survivors.  Only (vals, pos, agg) — ≤ cap rows — ever cross
    device -> host, never the full gathered stream.

    registers: [S, R] int32; idx: [M] int32 flat slot positions in key
    order.  Returns (vals [cap], pos [cap] positions into idx, agg [4]
    = count/sum/min/max over all matches)."""
    m = idx.shape[0]
    src = result_gather_call(registers.reshape(-1), idx,
                             chunk=min(chunk, max(m, 1)),
                             interpret=interpret)
    return scan_prune_call(src, lo, hi, cap=cap,
                           chunk=min(chunk, max(m, 1)),
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "interpret"))
def scan_topk(registers, idx, lo, hi, k, chunk=1024, interpret=None):
    """Top-k gather: the k largest in-range values among the hot slots,
    selected on device (ties break toward the lower key position, the
    ``lax.top_k`` rule).  Returns (vals [k], pos [k] positions into idx,
    count of all matches); slots past ``count`` hold the int32-min
    sentinel.  Requires k <= len(idx) (callers clamp)."""
    m = idx.shape[0]
    src = result_gather_call(registers.reshape(-1), idx,
                             chunk=min(chunk, max(m, 1)),
                             interpret=interpret)
    in_range = (src >= lo) & (src <= hi)
    masked = jnp.where(in_range, src, jnp.iinfo(jnp.int32).min)
    vals, pos = jax.lax.top_k(masked, k)
    return vals, pos.astype(jnp.int32), in_range.sum(dtype=jnp.int32)

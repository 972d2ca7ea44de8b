"""Warm-up of the switch engines' compiled programs before the window.

The engines compile one program per (mode, batch bucket, gather bucket,
device): a group of B hot txns runs padded to the power-of-two bucket of
B, and its M device-result ops (every op but WRITE and NOP) gather into
the power-of-two bucket of M, at most ``bucket(B) * max_instrs``.  Which
groups a window forms depends on its traffic, so the warm-up covers every
bucket that the cell's traffic can reach: the group sizes its loop can
form, and for each the range of M that the traffic's own per-txn counts
allow (exact bounds for small groups, the mean +- 8 standard deviations
for large ones).  Each program runs once on a throwaway engine of the
same configuration, so the cluster's registers are untouched.
"""
from __future__ import annotations

import math
from dataclasses import replace

import numpy as np

from ops import ADDP, CADD, NOP, READ, WRITE

EXACT_BELOW = 64           # groups this small get the exact bounds on M
SIGMAS = 8.0


def bucket(n):
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _buckets(lo, hi):
    out, b = [], bucket(max(lo, 1))
    while b <= bucket(max(hi, 1)):
        out.append(b)
        b *= 2
    return out


def shapes(arrs, hot_txn, loop, max_batch, max_instrs, n_switches):
    """The (mode, Bp, Mp) programs the window can use."""
    op = arrs["op"][hot_txn]
    dev_ops = ((op != NOP) & (op != WRITE)).sum(axis=1)
    if not len(dev_ops):
        return set()
    mlo, mhi = int(dev_ops.min()), int(dev_ops.max())
    mu, sd = float(dev_ops.mean()), float(dev_ops.std())
    has_cadd = bool((op == CADD).any())
    has_addp = bool((op == ADDP).any())
    modes = {"affine"}
    if has_cadd or has_addp:
        modes.add("serial")
    if has_addp:
        modes.add("staged")
    share = float(hot_txn.mean())
    if loop == "closed" and not has_addp and n_switches == 1:
        # groups are a whole batch's hot txns: binomial in the batch size
        s = math.sqrt(max_batch * share * (1 - share))
        b_lo = max(1, math.floor(max_batch * share - SIGMAS * s))
        b_hi = min(max_batch, math.ceil(max_batch * share + SIGMAS * s))
    else:
        # groups are split (unsafe ADDP rows, cross-shard rows) or batches
        # vary (open loop): any size up to the largest batch
        b_lo, b_hi = 1, max_batch
    out = set()
    for bp in _buckets(b_lo, b_hi):
        lo_b, hi_b = max(b_lo, bp // 2 + 1, 1), min(b_hi, bp)
        if lo_b > hi_b:
            continue
        m_lo, m_hi = lo_b * mlo, hi_b * mhi
        if lo_b >= EXACT_BELOW:
            m_lo = max(m_lo, math.floor(lo_b * mu - SIGMAS * sd * math.sqrt(
                lo_b)))
            m_hi = min(m_hi, math.ceil(hi_b * mu + SIGMAS * sd * math.sqrt(
                hi_b)))
        for mp in _buckets(m_lo, m_hi):
            out.update((m, bp, min(mp, bp * max_instrs)) for m in modes)
    if n_switches > 1:
        # a cross-shard row runs op by op: B = 1 serial dispatches
        out.add(("serial", 1, 1))
    return out


def warm(shape_set, switch_cfg, devices):
    """Run each program once on a throwaway engine per device (``None``:
    the default device, as a single-switch cluster uses)."""
    from repro.core.engine import SwitchEngine
    cfg = replace(switch_cfg, n_switches=1)
    K = cfg.max_instrs
    for dev in devices:
        eng = SwitchEngine(cfg, device=dev)
        for mode, bp, mp in sorted(shape_set):
            op = np.full((bp, K), NOP, np.int32)
            op.reshape(-1)[:mp] = READ
            z = np.zeros((bp, K), np.int32)
            eng.execute_batch(dict(op=op, stage=z, reg=z, operand=z),
                              mode=mode).results_np()

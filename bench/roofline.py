"""The least bytes the window's switch work needs, and the chip's peaks.

The engines do integer work only, so their roofline is bound by bytes.
The work is counted from the instructions, whatever engine runs them:
per instruction, 4 int32 fields in (op, stage, register, operand) and 1
int32 result out; per batch, each distinct register slot it touches is
read once and written once.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from ops import NOP

IN_BYTES, OUT_BYTES, SLOT_BYTES = 4 * 4, 4, 2 * 4


def work_bytes(op, slot):
    """Bytes one batch of switch instructions needs: ``op`` and ``slot``
    are [B, K] (NOP-padded); ``slot`` names each op's register slot."""
    live = np.asarray(op) != NOP
    n = int(live.sum())
    return n * (IN_BYTES + OUT_BYTES) + \
        len(np.unique(np.asarray(slot)[live])) * SLOT_BYTES


def window_bytes(arrs, hot_op, batches):
    """Bytes of every run_batch call's switch work: the ops on hot keys
    (all of a hot txn's, the hot part of a warm one), a hot key being one
    register slot."""
    total = 0
    for lo, hi in batches:
        op = np.where(hot_op[lo:hi], arrs["op"][lo:hi], NOP)
        total += work_bytes(op, arrs["key"][lo:hi])
    return total


def peaks(device_kind):
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, not a default."""
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]

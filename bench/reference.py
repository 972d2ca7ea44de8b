"""The plain serial reference that decides ``correct``.

One Python dict holds every value; txns execute one at a time in
admission order.  It imports nothing of the program and takes nothing the
program made: it is given the seeded initial values, the workload's hot
key set and the admitted txns, and follows the semantics each path of the
system documents.

* A hot txn (every key hot) runs on the switch: serialisable in admission
  order, abort-free.  READ returns the value, WRITE / ADD / ADDP the new
  value; ADDP adds the result of an earlier op of the same txn (its
  operand is that op's index); a CADD whose result would be negative
  leaves the value and returns it unchanged.
* A cold txn (no key hot) runs under NO_WAIT 2PL + 2PC on the nodes.  It
  applies all its writes or none.  A CADD whose result would be negative
  aborts it; run alone it aborts again on every retry, so the client gets
  GAVE_UP, returned here as ``None``.
* A warm txn (some keys hot) runs its cold ops first, under the cold
  rules, then its hot ops as one switch txn; the ADDP operands of each
  part index that part's own ops.
"""
from __future__ import annotations

from ops import ADD, ADDP, CADD, READ, WRITE


class Reference:
    def __init__(self, initial, hot_keys):
        self.values = dict(initial)         # a key never loaded reads 0
        self.hot = frozenset(hot_keys)

    def apply(self, ops):
        """Run one txn's ``[(op, key, operand)]``; its client result."""
        hot = [k in self.hot for _, k, _ in ops]
        if all(hot):
            return self._switch(ops)
        if not any(hot):
            return self._two_phase(ops)
        cold_ops = [op for op, h in zip(ops, hot) if not h]
        hot_ops = [op for op, h in zip(ops, hot) if h]
        cold_res = self._two_phase(cold_ops)
        if cold_res is None:
            return None
        hot_res = iter(self._switch(hot_ops))
        cold_res = iter(cold_res)
        return [next(hot_res) if h else next(cold_res) for h in hot]

    def _switch(self, ops):
        values, res = self.values, []
        last = len(ops) - 1
        for o, k, v in ops:
            cur = values.get(k, 0)
            if o == ADDP:
                o, v = ADD, res[min(max(v, 0), last)]
            if o == READ:
                res.append(cur)
            elif o == WRITE:
                values[k] = v
                res.append(v)
            elif o == ADD:
                values[k] = cur + v
                res.append(cur + v)
            elif o == CADD:
                if cur + v >= 0:
                    values[k] = cur + v
                    res.append(cur + v)
                else:
                    res.append(cur)
            else:
                raise ValueError(f"unknown opcode {o}")
        return res

    def _two_phase(self, ops):
        staged, res = {}, []
        for o, k, v in ops:
            cur = staged.get(k, self.values.get(k, 0))
            if o == READ:
                res.append(cur)
                continue
            if o == WRITE:
                new = v
            elif o == ADD:
                new = cur + v
            elif o == ADDP:
                new = cur + res[v]
            elif o == CADD:
                if cur + v < 0:
                    return None              # aborts on every retry
                new = cur + v
            else:
                raise ValueError(f"opcode {o} on the cold path")
            staged[k] = new
            res.append(new)
        self.values.update(staged)
        return res


def flat_ops(arrs):
    """Every op slot of generator arrays as an ``(op, key, operand)``
    tuple, row-major, and the slots per txn: txn i's ops are
    ``flat[i * K:i * K + n_ops[i]]``."""
    K = arrs["op"].shape[1]
    return list(zip(*(arrs[f].ravel().tolist()
                      for f in ("op", "key", "val")))), K


def run_reference(arrs, n, initial, hot_keys):
    """The reference's results for txns ``0..n-1`` and its final values."""
    ref = Reference(initial, hot_keys)
    flat, K = flat_ops({f: arrs[f][:n] for f in ("op", "key", "val")})
    n_ops = arrs["n_ops"][:n].tolist()
    results = [ref.apply(flat[i * K:i * K + n_ops[i]]) for i in range(n)]
    return results, ref.values


def compare(got_results, want_results, got_values, want_values):
    """The numbers ``correct`` is decided by, each with its limit: client
    results that differ (a missing or GAVE_UP result differs from a
    committed one), and final values that differ, hot keys read from the
    switch's register planes, cold keys from their home stores."""
    differ = sum(g != w for g, w in zip(got_results, want_results))
    differ += abs(len(got_results) - len(want_results))
    hot = [k for k in want_values if k in got_values["hot"]]
    cold = [k for k in want_values if k not in got_values["hot"]]
    return {
        "results_differ": (differ, 0),
        "hot_values_differ": (sum(got_values["hot"][k] != want_values[k]
                                  for k in hot), 0),
        "cold_values_differ": (sum(got_values["cold"].get(k, 0)
                                   != want_values[k] for k in cold), 0),
    }

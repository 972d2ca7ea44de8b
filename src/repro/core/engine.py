"""The in-switch transaction engine, adapted Tofino -> TPU.

Semantics (paper §5.1): packets are never reordered and each MAU stage holds
one packet per cycle, so pipelined execution of a batch equals the serial
schedule in admission order.  Multi-pass packets hold the pipeline lock, so
the serial order still equals admission order (§5.2).

Three functional execution paths produce that serial-equivalent result;
``_resolve_mode`` picks one per batch from its opcodes (rules below):

  serial  — a sequential loop over the flattened instruction stream, run
            on the batch's working set (its distinct slots), never the
            register plane: a Pallas kernel on scalars in SMEM.  Handles
            every opcode including CADD (constrained write).

  affine  — the TPU-native adaptation: every {READ, WRITE, ADD} op is an
            affine map v' = a*v + c; affine maps compose associatively, so a
            *segmented associative scan* over (register, admission-order)
            sorted instructions yields every pre/post value in O(log n)
            depth, fully vectorized.  Serializability-by-pipelining becomes
            serializability-by-scan.  Batches containing CADD fall back to
            the serial path (the paper similarly falls back to multi-pass
            for complex constraints).

  staged  — ADDP batches whose every source slot sits at an earlier
            stage: stages run in order, each a per-register affine scan,
            and ADDP operands forward from earlier stages' results (the
            pipeline's cross-stage forwarding).

Every executed transaction gets a globally-unique ID (GID) reflecting the
serial order; GIDs drive WAL recovery in repro.db (paper §6.1).

Batched execution (the hot path)
--------------------------------
The switch commits hot transactions at line rate with no coordination
(paper §5); the TPU analogue is one large dispatch per *batch* of hot
packets, not one per transaction.  ``execute_batch`` is that path:

  * registers stay resident on device across calls — nothing is synced
    back to host unless the DBMS reads a value;
  * when the packet builder supplies opcode-presence metadata
    (``build_packets``), the engine picks its execution path without
    re-scanning arrays on host;
  * batch sizes are padded up to power-of-two shape buckets so the number
    of jit specializations is O(log max_B), not O(#distinct B); padding
    rows are NOPs, which every engine treats as no-ops;
  * each (mode, shape) pair is lowered and compiled once ahead-of-time and
    cached, so steady-state calls go straight to the compiled executable
    (no jit dispatch/tracing machinery on the hot path);
  * the register buffer is donated to the compiled call, so on TPU the
    update is in-place rather than a copy of the full [S, R] register
    file per batch;
  * a group crosses host -> device as ONE fused staging buffer (pooled
    ``PacketStager``), and the compiled call gathers the device-only
    result rows into a compact array, so a drain ships M values instead
    of the full B*K result plane (result compaction);
  * ``execute_batch`` returns an opaque ``PendingBatch`` handle — a
    lazy result plane; with ``async_dispatch`` + ``defer=True`` the
    compiled call runs on a single-worker dispatch thread (XLA releases
    the GIL), overlapping device execution with the caller's next
    packet build while preserving FIFO admission order.

Engine-mode dispatch rules (``mode="auto"``):

  CADD in batch               -> serial  (constrained write needs the oracle)
  "unsafe" ADDP in batch      -> serial  (an ADDP whose source slot sits at
                                          the same or a later stage — i.e. a
                                          multipass packet — cannot be
                                          forwarded by the pipeline)
  ADDP in batch, all safe     -> staged  (cross-stage result forwarding)
  otherwise                   -> affine  (fully vectorized scan)

Explicit modes validate instead of silently mis-executing: ``affine``
rejects CADD/ADDP, ``staged`` rejects CADD and unsafe ADDP.
"""
from __future__ import annotations

import collections
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packets import (ADD, ADDP, CADD, NOP, READ, WRITE,
                                N_PLANES, PacketStager, ReadPacket,
                                SwitchConfig, result_plane, shard_rows)
from repro.kernels.switch_txn.switch_txn import serial_ws_call


# ------------------------------------------------------------- serial ----

# instructions per serial kernel call: the call's seven [n] int32 arrays
# (224 KiB) stay well inside a v5e core's 1 MiB of SMEM
_SERIAL_CALL_INSTRS = 8192


def _serial_engine_impl(registers, op, stage, reg, val):
    """Sequential execution of the [B, K] instruction stream in
    (txn, instr) order.  Handles every opcode; ADDP resolves the result of
    an earlier instruction of the same txn.

    The loop runs on the batch's working set, never the register plane
    (``_serial_rows``).  A batch larger than one kernel call's SMEM
    budget runs as consecutive row blocks, each seeing the last's
    writes."""
    S, R = registers.shape
    B, K = op.shape
    rows = max(1, min(B, _SERIAL_CALL_INSTRS // K))
    flat = registers.reshape(-1)
    res, ok = [], []
    for lo in range(0, B, rows):
        blk = slice(lo, lo + rows)
        flat, r, o = _serial_rows(flat, op[blk], stage[blk] * R + reg[blk],
                                  val[blk])
        res.append(r)
        ok.append(o)
    return (flat.reshape(S, R), jnp.concatenate(res).reshape(B, K),
            jnp.concatenate(ok).reshape(B, K).astype(bool))


def _serial_rows(flat, op, g, val):
    """Run the [b, K] rows ``op``/``val`` on slots ``g`` of the flat
    register file: each slot is renamed to its rank among the rows'
    distinct slots (a stable sort, segment starts and a cumsum, as in
    ``_affine_engine_impl``), those slots are gathered into an [N]
    working set, ``serial_ws_call`` steps through the instructions on it
    and the working set is scattered back.  The kernel holds the
    streams, the working set, the results and the flags in SMEM and steps
    on the scalar unit (an XLA loop's step cost ~12.5 us on a TPU v5e,
    whatever it carried).  Returns (flat, results [N], ok [N] int32)."""
    n_slots = flat.shape[0]
    K = op.shape[1]
    N = op.size
    g = g.reshape(-1)
    order = jnp.argsort(g, stable=True)
    gs = g[order]
    seg_start = jnp.concatenate([jnp.ones((1,), bool), gs[1:] != gs[:-1]])
    rank = (jnp.cumsum(seg_start) - 1).astype(jnp.int32)
    local = jnp.zeros((N,), jnp.int32).at[order].set(rank)
    # the distinct slot of each rank; ranks past the last point off the
    # plane, so the final scatter drops them
    slots = jnp.full((N,), n_slots, jnp.int32).at[
        jnp.where(seg_start, rank, N)].set(gs, mode="drop")
    ws = jnp.take(flat, slots, mode="clip")
    ws, res, ok = serial_ws_call(op.reshape(-1), local, val.reshape(-1), ws,
                                 k=K)
    return flat.at[slots].set(ws, mode="drop"), res, ok


def _staged_engine_impl(registers, op, stage, reg, val):
    """The pipeline-structured vectorized engine: stages execute in order
    (as on the switch); within a stage, per-register segmented affine scans
    give the serial-equivalent values; ADDP operands resolve from earlier
    stages' results — legal exactly because the declustered layout puts
    dependency sources in earlier stages (single-pass property, paper §4).

    Opcodes: NOP/READ/WRITE/ADD/ADDP.  CADD needs the serial path.
    """
    S, R = registers.shape
    B, K = op.shape
    results = jnp.zeros((B, K), jnp.int32)
    regs = registers

    for s in range(S):                       # the pipeline: stage by stage
        active = op * jnp.where(stage == s, 1, 0)  # NOP out other stages
        prev = jnp.take_along_axis(results, jnp.clip(val, 0, K - 1), axis=1)
        v_eff = jnp.where(active == ADDP, prev, val)
        o_eff = jnp.where(active == ADDP, ADD, active)
        stage_regs, res_s, _ = _affine_engine_impl(
            regs[s][None, :], o_eff, jnp.zeros_like(stage), reg, v_eff)
        regs = regs.at[s].set(stage_regs[0])
        results = jnp.where(active != NOP, res_s, results)
    return regs, results, jnp.ones((B, K), bool)


# ------------------------------------------------------------- affine ----

def _combine(x, y):
    """Segmented affine composition: elements are (flag, a, c); flag marks a
    segment start.  Associative."""
    f1, a1, c1 = x
    f2, a2, c2 = y
    a = jnp.where(f2, a2, a2 * a1)
    c = jnp.where(f2, c2, a2 * c1 + c2)
    return (f1 | f2, a, c)


def _affine_engine_impl(registers, op, stage, reg, val):
    """Vectorized serial-equivalent execution for {NOP, READ, WRITE, ADD}."""
    S, R = registers.shape
    B, K = op.shape
    N = B * K
    flat = registers.reshape(-1)

    opf = op.reshape(-1)
    g = (stage * R + reg).reshape(-1)
    g = jnp.where(opf == NOP, S * R, g)          # sort NOPs to the end
    v = val.reshape(-1)

    order = jnp.argsort(g, stable=True)          # admission order per register
    gs = g[order]
    os_ = opf[order]
    vs = v[order]

    a = jnp.where(os_ == WRITE, 0, 1).astype(jnp.int32)
    c = jnp.where((os_ == WRITE) | (os_ == ADD), vs, 0).astype(jnp.int32)
    seg_start = jnp.concatenate([jnp.ones((1,), bool), gs[1:] != gs[:-1]])

    # inclusive segmented scan of affine maps
    fi, ai, ci = jax.lax.associative_scan(_combine, (seg_start, a, c))
    v0 = flat[jnp.minimum(gs, S * R - 1)]
    post = ai * v0 + ci                          # value after op i
    # pre-value = post of previous op in segment (or v0 at the start)
    prev_post = jnp.concatenate([post[:1] * 0, post[:-1]])
    pre = jnp.where(seg_start, v0, prev_post)
    res_sorted = jnp.where(os_ == READ, pre,
                 jnp.where(os_ == NOP, 0, post))

    # final register value = post at each segment's last element
    seg_end = jnp.concatenate([gs[1:] != gs[:-1], jnp.ones((1,), bool)])
    upd_idx = jnp.where(seg_end & (gs < S * R), gs, S * R)
    flat = jnp.concatenate([flat, jnp.zeros((1,), flat.dtype)])
    flat = flat.at[upd_idx].set(jnp.where(seg_end, post, 0), mode="drop")
    new_regs = flat[:-1].reshape(S, R)

    # unsort results
    res = jnp.zeros((N,), res_sorted.dtype).at[order].set(res_sorted)
    ok = jnp.ones((N,), bool)
    return new_regs, res.reshape(B, K), ok.reshape(B, K)


# -------------------------------------------------------------- facade ----

# jitted aliases (back-compat / direct use outside the facade cache)
_serial_engine = jax.jit(_serial_engine_impl)
_staged_engine = jax.jit(_staged_engine_impl)
_affine_engine = jax.jit(_affine_engine_impl)

_ENGINE_IMPLS = {"serial": _serial_engine_impl,
                 "staged": _staged_engine_impl,
                 "affine": _affine_engine_impl}

# (mode, S, R, Bp, K, Mp) -> AOT-compiled executable.  jax.jit would also
# cache per shape, but calling a compiled executable directly skips the
# dispatch path (tracing-cache lookup, argument canonicalization) entirely —
# that overhead is exactly what dominates B=1 switch calls on CPU/TPU.
_DISPATCH_CACHE: Dict[tuple, object] = {}


def _fused_engine_impl(mode: str, Mp: int):
    """Wrap an engine impl to (a) consume the single fused [N_PLANES, Bp, K]
    staging buffer (one H2D transfer per group instead of four) and (b)
    emit the compacted device-only result rows alongside the full plane —
    all inside ONE compiled dispatch.  The program is named
    ``jit_run.<mode>``, so a profiler trace tells the engines apart."""
    impl = _ENGINE_IMPLS[mode]

    def run(registers, fused):
        op, stage, reg, val = fused[0], fused[1], fused[2], fused[3]
        idx = fused[4].reshape(-1)[:Mp]
        regs, res, ok = impl(registers, op, stage, reg, val)
        compact = jnp.take(res.reshape(-1), idx, mode="clip")
        return regs, res, ok, compact

    run.__name__ = run.__qualname__ = f"run.{mode}"
    return run


def _compiled_engine(mode: str, S: int, R: int, B: int, K: int, M: int,
                     dev=None):
    key = (mode, S, R, B, K, M, dev)
    fn = _DISPATCH_CACHE.get(key)
    if fn is None:
        if dev is None:
            spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        else:
            # per-shard AOT: lower for the plane's own device so each
            # shard's executable runs (and donates) on its own buffer
            sharding = jax.sharding.SingleDeviceSharding(dev)
            spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                      sharding=sharding)
        with warnings.catch_warnings():
            # register donation is a no-op on CPU; silence the advisory
            warnings.filterwarnings("ignore", message="Some donated buffers")
            fn = jax.jit(_fused_engine_impl(mode, M),
                         donate_argnums=0).lower(
                spec((S, R)), spec((N_PLANES, B, K))).compile()
        _DISPATCH_CACHE[key] = fn
    return fn


def _bucket(b: int) -> int:
    """Round a batch size up to its power-of-two shape bucket, bounding the
    number of compiled specializations to O(log max_B)."""
    return 1 if b <= 1 else 1 << (b - 1).bit_length()


def _read_gather_impl(registers, idx):
    """The READ-only fast path's whole device program: one gather out of
    the resident register file.  No RMW, no result plane, no donation —
    the registers buffer stays valid for the next write dispatch."""
    return jnp.take(registers.reshape(-1), idx, mode="clip")


def _compiled_reader(S: int, R: int, Mp: int, dev=None):
    key = ("read", S, R, Mp, dev)
    fn = _DISPATCH_CACHE.get(key)
    if fn is None:
        if dev is None:
            spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        else:
            sharding = jax.sharding.SingleDeviceSharding(dev)
            spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                      sharding=sharding)
        fn = jax.jit(_read_gather_impl).lower(
            spec((S, R)), spec((Mp,))).compile()
        _DISPATCH_CACHE[key] = fn
    return fn


class PendingRead:
    """Opaque handle to one dispatched READ-only batch — the read tier's
    ``PendingBatch`` sibling.  Carries only the gathered values (device-
    resident until ``values_np()``); there is no ok plane, no GID and no
    WAL footprint: reads are non-durable by construction."""

    __slots__ = ("vals", "n", "_fut", "_np")

    def __init__(self, vals, n, fut=None):
        self.vals, self.n = vals, n
        self._fut = fut
        self._np = None

    def _resolve(self):
        if self._fut is not None:
            self.vals = self._fut.result()
            self._fut = None

    def values_np(self) -> np.ndarray:
        """Materialize the [n] value vector on host (cached)."""
        if self._np is None:
            self._resolve()
            self._np = np.asarray(self.vals)[:self.n]
        return self._np

    def block(self):
        self._resolve()
        jax.block_until_ready(self.vals)
        return self

    def ready(self) -> bool:
        return self._np is not None


class PendingBatch:
    """Opaque handle to one dispatched batch — the async hot path's unit
    of in-flight work.

    Device-resident outputs stay on device: ``res`` (full [Bp, K] result
    plane), ``ok`` (success flags) and ``compact`` (the gathered
    device-only result rows).  Host-side metadata — ``base`` (the
    host-derivable results: WRITE echoes, NOP zeros), ``idx`` (flat
    positions of the gathered rows) and ``gids`` — is available
    immediately.  A deferred dispatch carries a future instead of arrays
    until resolved; either way nothing crosses device -> host until
    ``results_np()`` runs, and that transfer ships only the M compacted
    values, not the whole B*K plane.

    Iteration yields ``(results[:B], ok[:B], gids)`` device slices, so
    legacy ``res, ok, gids = engine.execute_batch(...)`` unpacking keeps
    working unchanged."""

    __slots__ = ("res", "ok", "compact", "gids", "B", "K", "base", "idx",
                 "mode", "engine", "_fut", "_res_np")

    def __init__(self, res, ok, compact, gids, B, K, base, idx,
                 mode="auto", fut=None, engine=None):
        self.res, self.ok, self.compact = res, ok, compact
        self.gids, self.B, self.K = gids, B, K
        self.base, self.idx, self.mode = base, idx, mode
        self.engine = engine        # counts the D2H bytes, when given
        self._fut = fut
        self._res_np = None

    def _to_host(self, arr) -> np.ndarray:
        out = np.asarray(arr)
        if self.engine is not None:
            self.engine.d2h_bytes += out.nbytes
        return out

    def _resolve(self):
        """Join the dispatch thread's future (deferred handles only)."""
        if self._fut is not None:
            _, self.res, self.ok, self.compact = self._fut.result()
            self._fut = None

    def results_np(self) -> np.ndarray:
        """Materialize the [B, K] result plane on host: the host-known
        base overlaid with the compacted device gather (cached)."""
        if self._res_np is None:
            self._resolve()
            out = self.base.copy()
            if len(self.idx):
                out.reshape(-1)[self.idx] = \
                    self._to_host(self.compact)[:len(self.idx)]
            self._res_np = out
        return self._res_np

    def ok_np(self) -> np.ndarray:
        self._resolve()
        return self._to_host(self.ok)[:self.B]

    def release(self):
        """Free the device arrays once ``results_np()`` has copied what the
        caller needs: the group's buffers go now, not whenever the last
        reference to the handle dies."""
        self._resolve()
        self.res = self.ok = self.compact = None

    def block(self):
        """Barrier: wait for this dispatch's device work to finish."""
        self._resolve()
        jax.block_until_ready((self.res, self.ok, self.compact))
        return self

    def ready(self) -> bool:
        return self._res_np is not None

    def __iter__(self):
        self._resolve()
        yield self.res[:self.B]
        yield self.ok[:self.B]
        yield self.gids


class SwitchEngine:
    """Functional switch: holds register state on device, executes packet
    batches in serial-equivalent order, assigns GIDs.

    ``dispatch_count`` counts device dispatches (compiled-engine calls) —
    the batched DBMS hot path commits a whole group of hot transactions in
    exactly one.  ``h2d_bytes`` counts the fused staging buffers the write
    dispatches send to the device, ``d2h_bytes`` the result arrays their
    handles copy back.  ``serial_steps`` counts the serial engine's loop
    steps (``Bp * K`` per dispatch, NOP padding included) and
    ``serial_instrs`` the non-NOP instructions among them."""

    def __init__(self, cfg: SwitchConfig, registers=None,
                 stager_pool: int = 4, async_dispatch: bool = False,
                 device=None):
        self.cfg = cfg
        # ``device`` pins this engine's register buffer (and every compiled
        # call) to one device of the mesh — the per-shard plane of a
        # ShardedSwitchEngine; None keeps the default-device behavior
        self._device = device
        self.registers = self._put(
            np.zeros((cfg.n_stages, cfg.regs_per_stage), np.int32)
            if registers is None else registers)
        self.next_gid = 0
        self.dispatch_count = 0
        self.read_dispatch_count = 0    # READ-only gathers (no GID, no WAL)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.serial_steps = 0
        self.serial_instrs = 0
        # reusable host staging buffers (one fused H2D per dispatch); the
        # pool must stay deeper than the caller's async in-flight window
        self._stager = PacketStager(pool=stager_pool)
        # async dispatch: a single-worker thread owns all device calls
        # (XLA releases the GIL during execution, so group k's compute
        # genuinely overlaps the host building group k+1); one worker =
        # FIFO = the switch's serial admission order is preserved
        self.async_dispatch = bool(async_dispatch)
        self._pool = None
        self._last_fut = None
        self._defer_futs = collections.deque()   # submitted, not yet run

    def _put(self, host):
        """One host -> device transfer straight onto this plane's device
        (the default device when unpinned), never via another chip.
        ``np.array`` copies first: staging buffers are recycled and
        register values are caller-owned, and a device buffer (donated to
        compiled calls) must never alias either."""
        return jax.device_put(np.array(host, np.int32), self._device)

    # ------------------------------------------------ dispatch thread --
    def _submit(self, job, defer: bool):
        """Run ``job`` inline (sync engine), or on the dispatch thread.
        Returns (outputs, future): exactly one is non-None; ``defer``
        asks for the future, otherwise the call blocks for outputs.

        Backpressure: a staging buffer may only be recycled after the
        job reading it has executed, so outstanding deferred jobs are
        bounded to the stager pool depth — the oldest is joined before a
        submit that would overflow it.  This enforces the pool contract
        for DIRECT engine users too (the Cluster's in-flight window is
        sized to never hit it)."""
        if not self.async_dispatch:
            return job(), None
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="switch-dispatch")
        fut = self._pool.submit(job)
        self._last_fut = fut
        if defer:
            self._defer_futs.append(fut)
            while len(self._defer_futs) > self._stager.pool - 2:
                self._defer_futs.popleft().result()
            return None, fut
        out = fut.result()      # FIFO worker: every earlier job is done
        self._defer_futs.clear()
        return out, None

    def _join(self):
        """Wait for every submitted dispatch to finish (register state is
        only host-readable at a quiescent point).  EVERY outstanding
        future is joined, not just the last: a failed dispatch re-raises
        here — GIDs/WAL accounting already advanced at submit, so
        silently returning stale registers would let the two diverge."""
        while self._defer_futs:
            self._defer_futs.popleft().result()
        if self._last_fut is not None:
            fut, self._last_fut = self._last_fut, None
            fut.result()

    @staticmethod
    def _resolve_mode(mode: str, has_cadd: bool, has_addp: bool,
                      addp_unsafe: bool) -> str:
        if mode == "auto":
            return ("serial" if has_cadd or addp_unsafe else
                    "staged" if has_addp else "affine")
        if mode == "affine" and (has_cadd or has_addp):
            raise ValueError("affine engine handles {READ,WRITE,ADD} only")
        if mode == "staged" and has_cadd:
            raise ValueError("staged engine cannot execute CADD; use serial")
        if mode == "staged" and addp_unsafe:
            raise ValueError("staged engine forwards ADDP results from "
                             "earlier stages only; multipass ADDP packets "
                             "need the serial path")
        if mode not in ("serial", "staged", "affine"):
            raise ValueError(mode)
        return mode

    def execute(self, pkts: Dict[str, np.ndarray], mode: str = "auto"
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Execute a batch (serial order = batch order).

        Returns (results [B,K], success [B,K], gids [B]) on host."""
        pb = self.execute_batch(pkts, meta=None, mode=mode)
        return pb.results_np(), np.asarray(pb.ok_np()), pb.gids

    def execute_batch(self, pkts: Dict[str, np.ndarray],
                      meta: Optional[dict] = None, mode: str = "auto",
                      defer: bool = False, gids=None) -> PendingBatch:
        """The batched hot path: execute all B packets in one device
        dispatch (serial order = batch order) and return an opaque
        ``PendingBatch`` handle WITHOUT forcing materialization.

        ``meta`` is the opcode-presence (+ result-plane) metadata from
        ``packets.build_packets``; when given, no host-side re-scan of the
        op arrays is needed.  The batch dimension is padded to a
        power-of-two bucket with NOP rows and the whole group crosses H2D
        as ONE fused staging buffer; GIDs are assigned to the B real
        packets only.  The compiled call also gathers the device-only
        result rows (everything but WRITE echoes / NOP zeros) into a
        compact array, so draining the handle ships M values to host
        instead of B*K.

        With ``defer=True`` on an ``async_dispatch`` engine the compiled
        call runs on the engine's dispatch thread (XLA releases the GIL,
        so device compute overlaps the caller's next packet build) and
        the handle carries a future; GIDs and dispatch accounting are
        still assigned synchronously, so admission order is untouched.

        The handle unpacks as ``(results [B,K], success [B,K], gids [B])``
        device arrays for legacy callers; ``results_np()`` is the lazy
        drain."""
        op_np = np.asarray(pkts["op"], np.int32)
        B, K = op_np.shape
        if meta is None:
            from repro.core.packets import scan_flags
            meta = scan_flags(pkts)
        mode = self._resolve_mode(mode, meta["has_cadd"], meta["has_addp"],
                                  meta["addp_unsafe"])
        if gids is None:
            gids = np.arange(self.next_gid, self.next_gid + B,
                             dtype=np.int64)
        else:
            # explicit gids: the caller (a sharding facade) owns the global
            # serial order and hands each sub-dispatch its rows' ids
            gids = np.asarray(gids, np.int64)
        if B == 0:
            return PendingBatch(np.zeros((0, K), np.int32),
                                np.zeros((0, K), bool),
                                np.zeros(0, np.int32), gids, 0, K,
                                np.zeros((0, K), np.int32),
                                np.zeros(0, np.int32), mode)

        base = meta.get("res_base")
        idx = meta.get("gather_idx")
        if base is None or idx is None:
            base, idx = result_plane(pkts)
        Bp = _bucket(B)
        Mp = min(_bucket(max(len(idx), 1)), Bp * K)
        # staged on the host thread (the packet arrays may be reused by
        # the caller); the job reads self.registers AT EXECUTION time on
        # the dispatch thread, chaining register state in FIFO order
        staged = self._stager.stage(pkts, idx, Bp, Mp)
        S, R = self.cfg.n_stages, self.cfg.regs_per_stage
        fn = _compiled_engine(mode, S, R, Bp, K, Mp, self._device)

        def job():
            fused = self._put(staged)
            regs, res, ok, compact = fn(self.registers, fused)
            self.registers = regs
            return regs, res, ok, compact

        self.dispatch_count += 1
        self.h2d_bytes += staged.nbytes
        if mode == "serial":
            self.serial_steps += Bp * K
            self.serial_instrs += int(np.count_nonzero(op_np))
        self.next_gid = max(self.next_gid, int(gids[-1]) + 1)
        out, fut = self._submit(job, defer)
        if fut is not None:
            return PendingBatch(None, None, None, gids, B, K, base, idx,
                                mode, fut=fut, engine=self)
        _, res, ok, compact = out
        return PendingBatch(res, ok, compact, gids, B, K, base, idx, mode,
                            engine=self)

    def execute_reads(self, rp: ReadPacket, defer: bool = False
                      ) -> PendingRead:
        """The switch-served read path: answer a READ-only packet batch
        straight from the resident device registers, skipping everything
        the write path needs — no GID, no WAL entry, no pipeline lock, no
        recirculation, no result plane.  One AOT-cached gather per call
        (power-of-two index bucket), values returned in key order.

        Async-compatible: on an ``async_dispatch`` engine the gather runs
        on the same single-worker FIFO dispatch thread as every write
        dispatch, so a read submitted after a deferred write group
        observes that group's register effects WITHOUT the caller having
        to drain its ``PendingBatch`` result planes.  ``defer=True``
        returns immediately with a future-backed handle; otherwise the
        call blocks until the values exist (FIFO ⇒ all earlier writes
        committed first either way)."""
        M = rp.n
        if M == 0:
            return PendingRead(np.zeros(0, np.int32), 0)
        Mp = _bucket(M)
        idx = np.zeros(Mp, np.int32)
        idx[:M] = rp.flat_idx(self.cfg)
        S, R = self.cfg.n_stages, self.cfg.regs_per_stage
        fn = _compiled_reader(S, R, Mp, self._device)

        def job():
            # reads self.registers AT EXECUTION time on the dispatch
            # thread — FIFO chaining puts it after every earlier write
            return fn(self.registers, self._put(idx))

        self.read_dispatch_count += 1
        out, fut = self._submit(job, defer)
        if fut is not None:
            return PendingRead(None, M, fut=fut)
        return PendingRead(out, M)

    def execute_scan(self, rp: ReadPacket, lo: int, hi: int,
                     cap: Optional[int] = None, k: Optional[int] = None):
        """Switch-side pruned scan over a READ-only slot set: gather the
        slots, filter by ``lo <= v <= hi`` on device, ship only the
        surviving rows (the kernels/switch_txn scan-prune path).

        Exactly one of ``cap``/``k``: ``cap`` returns the first ``cap``
        survivors in slot order plus (count, sum, min, max) aggregates;
        ``k`` returns the k largest in-range values (ties toward the
        lower slot position) plus the match count.  Returns host arrays
        ``(vals, pos, agg_or_count)`` where ``pos`` indexes into ``rp``'s
        key order; like ``execute_reads`` the device call runs on the
        FIFO dispatch thread, so it observes every earlier write without
        a result-plane drain."""
        from repro.kernels.switch_txn import ops as ktx
        if (cap is None) == (k is None):
            raise ValueError("exactly one of cap/k")
        idx = self._put(rp.flat_idx(self.cfg))

        def job():
            if k is not None:
                return ktx.scan_topk(self.registers, idx, lo, hi, k=k)
            return ktx.scan_prune(self.registers, idx, lo, hi, cap=cap)

        self.read_dispatch_count += 1
        out, _ = self._submit(job, defer=False)
        vals, pos, tail = out
        return (np.asarray(vals), np.asarray(pos),
                np.asarray(tail) if k is None else int(tail))

    def read_all(self) -> np.ndarray:
        self._join()
        return np.asarray(self.registers)

    def snapshot(self):
        self._join()
        return np.asarray(self.registers).copy(), self.next_gid

    def restore(self, snap):
        self._join()
        regs, gid = snap
        # _put copies: the register buffer is donated to later compiled
        # calls, so the restored snapshot (a checkpoint the warm standby
        # may restore from repeatedly) must never be aliased
        self.registers = self._put(regs)
        self.next_gid = gid

    def load_registers(self, values):
        """Replace the whole register file ([S, R] host array) — the bulk
        path migration/restore uses; copies, never aliases the input."""
        self._join()
        self.registers = self._put(values)

    def read_value(self, slot) -> int:
        """Read one register by placement slot ((switch, stage, reg) or
        legacy (stage, reg); a plain engine IS switch 0)."""
        *sw, s, r = slot
        return int(self.read_all()[s, r])


class ShardedSwitchEngine:
    """N-switch register plane: one ``SwitchEngine`` per shard, each with
    its own donated device buffer (pinned to one device of the JAX mesh
    when several are available), its own AOT dispatch cache and its own
    dispatch thread.

    A batch arrives with the global-stage encoding (``stage = switch *
    n_stages + stage``; see ``packets.build_packets``).  Rows that live
    entirely on one shard are grouped per shard — preserving per-shard
    admission order — and dispatched concurrently (different shards touch
    disjoint registers, so their rows commute in the serial order).  A
    cross-shard row is a barrier: pending groups flush first, then its ops
    execute one mini-dispatch at a time in slot order, forwarding ADDP
    operands across shards on the host (the model of an inter-switch hop
    per dependency).

    The facade owns the GLOBAL gid sequence — sub-dispatches receive their
    rows' ids explicitly — so results, WAL entries and recovery replay
    order are identical to a single switch executing the same admission
    order.  With ``n_switches == 1`` every call delegates verbatim to the
    single plane: the sharded path is byte-identical to ``SwitchEngine``
    by construction (regression-pinned)."""

    def __init__(self, cfg: SwitchConfig, registers=None,
                 stager_pool: int = 4, async_dispatch: bool = False):
        from dataclasses import replace
        self.cfg = cfg
        self.n = cfg.n_switches
        self.async_dispatch = bool(async_dispatch)
        self.next_gid = 0
        devs = jax.devices()
        use_dev = self.n > 1 and len(devs) > 1
        plane_cfg = replace(cfg, n_switches=1)
        if registers is not None:
            regs = np.asarray(registers)
            if regs.ndim == 2:
                regs = regs[None] if self.n == 1 else None
            if regs is None or regs.shape[0] != self.n:
                raise ValueError("registers must be [n_switches, S, R]")
        self.planes = [
            SwitchEngine(plane_cfg,
                         registers=None if registers is None else regs[i],
                         stager_pool=stager_pool,
                         async_dispatch=async_dispatch,
                         device=devs[i % len(devs)] if use_dev else None)
            for i in range(self.n)
        ]

    # ------------------------------------------------------- bookkeeping --
    @property
    def dispatch_count(self) -> int:
        return sum(p.dispatch_count for p in self.planes)

    @property
    def read_dispatch_count(self) -> int:
        return sum(p.read_dispatch_count for p in self.planes)

    @property
    def h2d_bytes(self) -> int:
        return sum(p.h2d_bytes for p in self.planes)

    @property
    def d2h_bytes(self) -> int:
        return sum(p.d2h_bytes for p in self.planes)

    @property
    def serial_steps(self) -> int:
        return sum(p.serial_steps for p in self.planes)

    @property
    def serial_instrs(self) -> int:
        return sum(p.serial_instrs for p in self.planes)

    @property
    def registers(self):
        if self.n == 1:
            return self.planes[0].registers
        return jnp.stack([jnp.asarray(p.read_all()) for p in self.planes])

    @registers.setter
    def registers(self, values):
        self.load_registers(np.asarray(values))

    def _join(self):
        for p in self.planes:
            p._join()

    # --------------------------------------------------------- execution --
    def execute(self, pkts: Dict[str, np.ndarray], mode: str = "auto"
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        pb = self.execute_batch(pkts, meta=None, mode=mode)
        return pb.results_np(), np.asarray(pb.ok_np()), pb.gids

    def execute_batch(self, pkts: Dict[str, np.ndarray],
                      meta: Optional[dict] = None, mode: str = "auto",
                      defer: bool = False, gids=None):
        if self.n == 1:
            pb = self.planes[0].execute_batch(pkts, meta, mode=mode,
                                              defer=defer, gids=gids)
            self.next_gid = self.planes[0].next_gid
            return pb
        op_np = np.asarray(pkts["op"], np.int32)
        B, K = op_np.shape
        if meta is None:
            from repro.core.packets import scan_flags
            meta = scan_flags(pkts)
        shard = meta.get("shard")
        if shard is None:
            shard = shard_rows(pkts, self.cfg)
        # one mode for the whole batch, resolved exactly like the single
        # switch would (explicit modes validate against whole-batch flags)
        mode = SwitchEngine._resolve_mode(
            mode, meta["has_cadd"], meta["has_addp"], meta["addp_unsafe"])
        if gids is None:
            gids = np.arange(self.next_gid, self.next_gid + B,
                             dtype=np.int64)
        else:
            gids = np.asarray(gids, np.int64)
        if B == 0:
            return PendingBatch(np.zeros((0, K), np.int32),
                                np.zeros((0, K), bool),
                                np.zeros(0, np.int32), gids, 0, K,
                                np.zeros((0, K), np.int32),
                                np.zeros(0, np.int32), mode)
        self.next_gid = max(self.next_gid, int(gids.max()) + 1)

        stage_np = np.asarray(pkts["stage"], np.int32)
        reg_np = np.asarray(pkts["reg"], np.int32)
        val_np = np.asarray(pkts["operand"], np.int32)
        S = self.cfg.n_stages
        flags = dict(has_cadd=meta["has_cadd"], has_addp=meta["has_addp"],
                     addp_unsafe=meta["addp_unsafe"])
        parts = []
        pend: Dict[int, list] = {}

        def flush():
            for sw in sorted(pend):
                ridx = np.asarray(pend[sw])
                sub_op = op_np[ridx]
                # global stage -> this shard's local pipeline stage
                sub = dict(op=sub_op,
                           stage=np.where(sub_op != NOP,
                                          stage_np[ridx] - sw * S,
                                          0).astype(np.int32),
                           reg=reg_np[ridx], operand=val_np[ridx])
                base, idx = result_plane(sub)
                sub_meta = dict(flags, res_base=base, gather_idx=idx)
                pb = self.planes[sw].execute_batch(
                    sub, sub_meta, mode=mode,
                    defer=self.async_dispatch, gids=gids[ridx])
                parts.append((ridx, pb, None, None))
            pend.clear()

        for i in range(B):
            sh = int(shard[i])
            if sh >= 0:
                pend.setdefault(sh, []).append(i)
                continue
            flush()        # barrier: a cross-shard row sees every earlier
            res_row, ok_row = self._exec_cross_row(   # row's effects
                op_np[i], stage_np[i], reg_np[i], val_np[i], int(gids[i]))
            parts.append((np.array([i]), None, res_row, ok_row))
        flush()

        handle = _MergedBatch(gids, B, K, parts, mode)
        if not defer and self.async_dispatch:
            handle.block()     # non-deferred contract: work is done on
        return handle          # return, matching SwitchEngine._submit

    def _exec_cross_row(self, op, stage, reg, val, gid):
        """Execute one cross-shard packet op-by-op in slot order: each op
        is a B=1 serial mini-dispatch on its shard, and ADDP operands are
        resolved on the host from the already-known earlier results (the
        inter-switch result forwarding a real deployment would do with a
        recirculating hop per dependency)."""
        K = len(op)
        S = self.cfg.n_stages
        res = np.zeros(K, np.int32)
        ok = np.ones(K, bool)
        for k in range(K):
            o = int(op[k])
            if o == NOP:
                continue
            sw, s_loc = divmod(int(stage[k]), S)
            v = int(val[k])
            if o == ADDP:       # source result is already materialized:
                o, v = ADD, int(res[min(max(int(val[k]), 0), K - 1)])
            mini = dict(op=np.array([[o]], np.int32),
                        stage=np.array([[s_loc]], np.int32),
                        reg=np.array([[int(reg[k])]], np.int32),
                        operand=np.array([[v]], np.int32))
            pb = self.planes[sw].execute_batch(
                mini, mode="serial", gids=np.array([gid], np.int64))
            res[k] = int(pb.results_np()[0, 0])
            ok[k] = bool(pb.ok_np()[0, 0])
        return res, ok

    def execute_reads(self, rp: ReadPacket, defer: bool = False):
        """Sharded read path: split the READ-only batch by shard, gather
        each shard's values concurrently on its own plane (its own device
        + dispatch thread), scatter back to key order on drain.  Reads
        touch disjoint registers per shard and modify nothing, so no
        cross-shard barrier exists — unlike writes, a 'cross-shard read'
        cannot happen (each key lives on exactly one shard)."""
        if self.n == 1:
            return self.planes[0].execute_reads(rp, defer=defer)
        M = rp.n
        if M == 0:
            return PendingRead(np.zeros(0, np.int32), 0)
        parts = []
        for sw in range(self.n):
            pos = np.flatnonzero(rp.switch == sw)
            if not len(pos):
                continue
            sub = ReadPacket(switch=np.zeros(len(pos), np.int32),
                             stage=rp.stage[pos], reg=rp.reg[pos])
            # defer per shard even on a sync call: the shards gather in
            # parallel; _MergedRead's materialization joins them in order
            pr = self.planes[sw].execute_reads(sub,
                                               defer=self.async_dispatch)
            parts.append((pos, pr))
        handle = _MergedRead(M, parts)
        if not defer and self.async_dispatch:
            handle.block()
        return handle

    def execute_scan(self, rp: ReadPacket, lo: int, hi: int,
                     cap: Optional[int] = None, k: Optional[int] = None):
        """Sharded pruned scan: each shard filters its own slots on its
        own device, ships ≤ cap (or k) survivors, and the host merges by
        global key position — the per-shard prefix property makes the
        merge exact (the global first-``cap`` survivors are a union of
        per-shard survivor prefixes, so no shard can hide one)."""
        if self.n == 1:
            return self.planes[0].execute_scan(rp, lo, hi, cap=cap, k=k)
        if (cap is None) == (k is None):
            raise ValueError("exactly one of cap/k")
        cand_pos, cand_vals, aggs, total = [], [], [], 0
        for sw in range(self.n):
            pos = np.flatnonzero(rp.switch == sw)
            if not len(pos):
                continue
            sub = ReadPacket(switch=np.zeros(len(pos), np.int32),
                             stage=rp.stage[pos], reg=rp.reg[pos])
            cc = None if cap is None else min(cap, len(pos))
            kk = None if k is None else min(k, len(pos))
            vals, p, tail = self.planes[sw].execute_scan(
                sub, lo, hi, cap=cc, k=kk)
            if cap is not None:
                t = min(int(tail[0]), cc)
                cand_pos.append(pos[p[:t]])
                cand_vals.append(vals[:t])
                aggs.append(tail)
            else:
                cand_pos.append(pos[p])
                cand_vals.append(vals)
                total += tail
        if cap is not None:
            gp = np.concatenate(cand_pos) if cand_pos else np.zeros(0, np.int32)
            gv = np.concatenate(cand_vals) if cand_vals else np.zeros(0, np.int32)
            order = np.argsort(gp, kind="stable")[:cap]
            vals = np.zeros(cap, np.int32)
            posg = np.full(cap, -1, np.int32)
            vals[:len(order)] = gv[order]
            posg[:len(order)] = gp[order]
            if aggs:
                a = np.stack(aggs)
                agg = np.array([a[:, 0].sum(dtype=np.int32),
                                a[:, 1].sum(dtype=np.int32),
                                a[:, 2].min(), a[:, 3].max()], np.int32)
            else:
                from repro.kernels.switch_txn.switch_txn import (
                    AGG_MAX_EMPTY, AGG_MIN_EMPTY)
                agg = np.array([0, 0, AGG_MIN_EMPTY, AGG_MAX_EMPTY],
                               np.int32)
            return vals, posg, agg
        from repro.kernels.switch_txn.switch_txn import AGG_MAX_EMPTY
        gp = np.concatenate(cand_pos) if cand_pos else np.zeros(0, np.int32)
        gv = np.concatenate(cand_vals) if cand_vals else np.zeros(0, np.int32)
        # global top-k by (-value, global key position): the same tie rule
        # lax.top_k applies inside one plane
        order = np.lexsort((gp, -gv.astype(np.int64)))[:k]
        vals = np.full(k, AGG_MAX_EMPTY, np.int32)
        posg = np.zeros(k, np.int32)
        vals[:len(order)] = gv[order]
        posg[:len(order)] = gp[order]
        return vals, posg, int(total)

    # ------------------------------------------------------ state access --
    def read_all(self) -> np.ndarray:
        """[S, R] with one shard, [N, S, R] stacked otherwise."""
        if self.n == 1:
            return self.planes[0].read_all()
        return np.stack([p.read_all() for p in self.planes])

    def snapshot(self):
        if self.n == 1:
            snap = self.planes[0].snapshot()
            self.next_gid = self.planes[0].next_gid
            return snap
        return self.read_all().copy(), self.next_gid

    def restore(self, snap):
        regs, gid = snap
        if self.n == 1:
            self.planes[0].restore(snap)
        else:
            regs = np.asarray(regs)
            for i, p in enumerate(self.planes):
                p.restore((regs[i], gid))
        self.next_gid = gid

    def load_registers(self, values):
        values = np.asarray(values)
        if self.n == 1:
            self.planes[0].load_registers(
                values if values.ndim == 2 else values[0])
            return
        if values.ndim != 3 or values.shape[0] != self.n:
            raise ValueError("expected [n_switches, S, R] register stack")
        for i, p in enumerate(self.planes):
            p.load_registers(values[i])

    def read_value(self, slot) -> int:
        sw, s, r = (0, *slot) if len(slot) == 2 else slot
        plane = self.planes[sw]
        return int(plane.read_all()[s, r])


class _MergedRead:
    """PendingRead-compatible handle over a sharded read gather: per-shard
    value vectors scatter back into the caller's key order on drain."""

    __slots__ = ("n", "_parts", "_np")

    def __init__(self, n, parts):
        self.n = n
        self._parts = parts        # (positions [m], PendingRead)
        self._np = None

    def values_np(self) -> np.ndarray:
        if self._np is None:
            out = np.zeros(self.n, np.int32)
            for pos, pr in self._parts:
                out[pos] = pr.values_np()
            self._np = out
        return self._np

    def block(self):
        for _, pr in self._parts:
            pr.block()
        return self

    def ready(self) -> bool:
        return self._np is not None


class _MergedBatch:
    """PendingBatch-compatible handle over a sharded dispatch: the per-
    shard sub-batches' compacted results scatter back into the caller's
    [B, K] plane on drain; cross-shard rows carry their (already
    materialized) per-op results inline."""

    __slots__ = ("gids", "B", "K", "mode", "_parts", "_res_np", "_ok_np")

    def __init__(self, gids, B, K, parts, mode="auto"):
        # parts: (row_idx [b], PendingBatch | None, res_row, ok_row)
        self.gids, self.B, self.K, self.mode = gids, B, K, mode
        self._parts = parts
        self._res_np = None
        self._ok_np = None

    def _materialize(self):
        if self._res_np is None:
            res = np.zeros((self.B, self.K), np.int32)
            ok = np.ones((self.B, self.K), bool)
            for rows, pb, res_row, ok_row in self._parts:
                if pb is not None:
                    res[rows] = pb.results_np()
                    ok[rows] = pb.ok_np()
                else:
                    res[rows[0]] = res_row
                    ok[rows[0]] = ok_row
            self._res_np, self._ok_np = res, ok

    def results_np(self) -> np.ndarray:
        self._materialize()
        return self._res_np

    def ok_np(self) -> np.ndarray:
        self._materialize()
        return self._ok_np

    def release(self):
        self._materialize()
        for _, pb, _, _ in self._parts:
            if pb is not None:
                pb.release()

    def block(self):
        for _, pb, _, _ in self._parts:
            if pb is not None:
                pb.block()
        return self

    def ready(self) -> bool:
        return self._res_np is not None

    def __iter__(self):
        self._materialize()
        yield jnp.asarray(self._res_np)
        yield jnp.asarray(self._ok_np)
        yield self.gids

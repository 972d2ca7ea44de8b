"""Pallas TPU kernels: the switch pipeline as a VMEM-resident register file.

Hardware mapping (Tofino -> TPU):
  * the MAU stages' SRAM register arrays live in ONE VMEM scratch buffer
    for the whole kernel invocation, laid out as ``[rows, 128]`` int32
    (one lane per register slot, slot ``g`` at row ``g // 128``, lane
    ``g % 128``).  It is copied in from HBM at the first grid step and
    back out at the last one; the HBM buffer is aliased input -> output,
    so a donated register file is updated in place.  The scratch persists
    across the sequential TPU grid, like stage SRAM persists across
    packets;
  * the packet stream (op / slot / operand) is blocked into SMEM tiles of
    ``chunk`` instructions — scalar memory, where per-instruction scalar
    reads are legal; grid steps execute in order, so instruction order ==
    serial order == the switch's pipeline admission order;
  * per instruction, one register row is loaded, the slot's value is
    selected out of its lane, the opcode is applied on scalars (including
    CADD, the P4 constrained-write the vectorized affine engine cannot
    express), and the row is stored back with only that lane changed.
    Results and success flags are scalar stores into SMEM output tiles.
    Mosaic has no scalar store into VMEM, so every VMEM update is such a
    masked whole-row store.

The gather and the scan-prune kernels use the same two layouts: index and
value streams in SMEM, anything addressed at random in ``[rows, 128]``
VMEM.  VMEM use is the register file (5 MiB at the default 20 x 65,536
configuration) plus the compacted scan output; ``_vmem_limit`` sets the
compiler's scoped VMEM limit from those sizes.

This is the faithful-execution path; the affine-scan engine (core/engine)
is the vectorized path.  Both are validated against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NOP, READ, WRITE, ADD, CADD = 0, 1, 2, 3, 4

LANES = 128
_TILE = 8 * LANES                 # one (8, 128) int32 tile
_VMEM_FLOOR = 16 << 20            # the compiler's default scoped limit
_VMEM_HEADROOM = 2 << 20          # Mosaic's own internal scratch


def interpret_default() -> bool:
    """Interpret mode exactly when running on the CPU backend (tests);
    on a TPU every kernel runs compiled."""
    return jax.default_backend() == "cpu"


def _resolve(interpret):
    return interpret_default() if interpret is None else interpret


def _vmem_limit(nbytes: int) -> int:
    """Scoped VMEM limit for a kernel holding ``nbytes`` of VMEM scratch
    and blocks: never below the compiler's default, raised only by what
    the kernel holds plus headroom (the v5e has 128 MiB of VMEM)."""
    return max(_VMEM_FLOOR, nbytes + _VMEM_HEADROOM)


def _params(nbytes: int):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_vmem_limit(nbytes))


def _as_rows(flat):
    """[n] int32 -> [rows, 128], zero-padded to whole (8, 128) tiles.
    Free (a reshape) when n is already a multiple of 1024."""
    pad = (-flat.shape[0]) % _TILE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, LANES)


def _pad_stream(x, chunk, fill=0):
    pad = (-x.shape[0]) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x


def _lanes():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def _smem_stream(chunk):
    return pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.SMEM)


def _smem_whole(n):
    return pl.BlockSpec((n,), lambda i: (0,), memory_space=pltpu.SMEM)


_HBM = pl.BlockSpec(memory_space=pl.ANY)


# ------------------------------------------------------ switch RMW kernel --

def _kernel(op_ref, g_ref, val_ref, regs_hbm, regs_out_hbm, res_ref, ok_ref,
            regs_v, *, chunk, n_slots):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _load():
        pltpu.sync_copy(regs_hbm, regs_v)

    lanes = _lanes()

    def body(i, _):
        o = op_ref[i]
        g = jnp.minimum(g_ref[i], n_slots - 1)
        v = val_ref[i]
        row_ix = pl.ds(g // LANES, 1)
        row = regs_v[row_ix, :]
        hit = lanes == g % LANES
        cur = jnp.sum(jnp.where(hit, row, 0))
        post = cur + v
        cadd_ok = post >= 0
        # READ and NOP leave new == cur, so the row store is a no-op there
        new = jnp.where(o == WRITE, v,
              jnp.where(o == ADD, post,
              jnp.where((o == CADD) & cadd_ok, post, cur)))
        regs_v[row_ix, :] = jnp.where(hit, new, row)
        res_ref[i] = jnp.where(o == READ, cur, jnp.where(o == NOP, 0, new))
        ok_ref[i] = jnp.where(o == CADD, cadd_ok, True).astype(jnp.int32)
        return ()

    jax.lax.fori_loop(0, chunk, body, ())

    @pl.when(step == pl.num_programs(0) - 1)
    def _store():
        pltpu.sync_copy(regs_v, regs_out_hbm)


def switch_txn_call(registers_flat, op, g, val, *, chunk=1024,
                    interpret=None):
    """registers_flat: [n_slots] int32; op/g/val: [N] int32, any N >= 1.

    Streams that are not a multiple of ``chunk`` are padded with NOP
    instructions up to the next chunk boundary (NOPs leave registers and
    results untouched); the padded tail is sliced off before returning.

    Returns (new_registers [n_slots], results [N], ok [N] int32)."""
    n_slots = registers_flat.shape[0]
    n = op.shape[0]
    op = _pad_stream(op, chunk, NOP)
    g = _pad_stream(g, chunk)
    val = _pad_stream(val, chunk)
    regs = _as_rows(registers_flat)
    stream = _smem_stream(chunk)
    regs, res, ok = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_slots=n_slots),
        grid=(op.shape[0] // chunk,),
        in_specs=[stream, stream, stream, _HBM],
        out_specs=[_HBM, stream, stream],
        out_shape=[
            jax.ShapeDtypeStruct(regs.shape, jnp.int32),
            jax.ShapeDtypeStruct(op.shape, jnp.int32),
            jax.ShapeDtypeStruct(op.shape, jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM(regs.shape, jnp.int32)],
        input_output_aliases={3: 0},
        compiler_params=_params(regs.size * 4),
        interpret=_resolve(interpret),
    )(op, g, val, regs)
    return regs.reshape(-1)[:n_slots], res[:n], ok[:n]


# ----------------------------------------------------------- gather kernel --

def _gather_kernel(idx_ref, src_hbm, out_ref, src_v, *, chunk, n_src):
    @pl.when(pl.program_id(0) == 0)
    def _load():
        pltpu.sync_copy(src_hbm, src_v)

    lanes = _lanes()

    def body(i, _):
        j = jnp.clip(idx_ref[i], 0, n_src - 1)
        row = src_v[pl.ds(j // LANES, 1), :]
        out_ref[i] = jnp.sum(jnp.where(lanes == j % LANES, row, 0))
        return ()

    jax.lax.fori_loop(0, chunk, body, ())


def result_gather_call(src, idx, *, chunk=1024, interpret=None):
    """Result-compaction gather: out[i] = src[clip(idx[i], 0, n-1)].

    The async hot path's result plane ships only the compacted READ-class
    results device -> host; this kernel is the gather step for the pallas
    engine mode (the jit engines fuse an equivalent ``jnp.take`` into
    their compiled call).  ``idx`` is padded by the packet stager to a
    power-of-two bucket; pad entries point at slot 0 and are sliced off
    by the caller, so clamping (not masking) is sufficient.

    src: [N] int32; idx: [M] int32, any M >= 1.  Returns [M] int32."""
    n_src = src.shape[0]
    m = idx.shape[0]
    idx = _pad_stream(idx, chunk)
    rows = _as_rows(src)
    stream = _smem_stream(chunk)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, chunk=chunk, n_src=n_src),
        grid=(idx.shape[0] // chunk,),
        in_specs=[stream, _HBM],
        out_specs=stream,
        out_shape=jax.ShapeDtypeStruct(idx.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM(rows.shape, jnp.int32)],
        compiler_params=_params(rows.size * 4),
        interpret=_resolve(interpret),
    )(idx, rows)
    return out[:m]


# ------------------------------------------------------- scan-prune kernel --

AGG_MIN_EMPTY = 2147483647        # int32 identities the aggregate lanes
AGG_MAX_EMPTY = -2147483648       # start from (empty-scan sentinels)


def _scan_prune_kernel(lo_ref, hi_ref, src_ref, vals_ref, idx_ref, agg_ref,
                       *, chunk, n, cap):
    """Predicate scan + on-device compaction over a value stream.

    Walks the stream in order (sequential grid, like the RMW kernel);
    every in-range element bumps the aggregate lanes (count/sum/min/max)
    and — while the output buffer has room — is appended to the compacted
    (value, position) outputs, which stay VMEM-resident for the whole
    grid.  The append position is ``min(count, cap)``: the count of
    earlier matches.  Branchless: a rejected or overflow element stores
    its row back unchanged.  Only the ``cap``-row output (not the full
    stream) leaves the device, which is the whole point: scan/filter
    queries ship ≤ cap rows to the host no matter how large the scanned
    register file is."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        agg_ref[0] = 0                    # count (ALL matches, beyond cap)
        agg_ref[1] = 0                    # sum
        agg_ref[2] = AGG_MIN_EMPTY        # min
        agg_ref[3] = AGG_MAX_EMPTY        # max
        vals_ref[...] = jnp.zeros(vals_ref.shape, jnp.int32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)

    lo = lo_ref[0]
    hi = hi_ref[0]
    lanes = _lanes()

    def body(i, _):
        pos = step * chunk + i
        v = src_ref[i]
        m = (v >= lo) & (v <= hi) & (pos < n)
        c = jnp.minimum(agg_ref[0], cap)
        w = jnp.minimum(c, cap - 1)
        row_ix = pl.ds(w // LANES, 1)
        hit = (lanes == w % LANES) & m & (c < cap)
        vals_ref[row_ix, :] = jnp.where(hit, v, vals_ref[row_ix, :])
        idx_ref[row_ix, :] = jnp.where(hit, pos, idx_ref[row_ix, :])
        agg_ref[0] = agg_ref[0] + m.astype(jnp.int32)
        agg_ref[1] = agg_ref[1] + jnp.where(m, v, 0)
        agg_ref[2] = jnp.minimum(agg_ref[2], jnp.where(m, v, AGG_MIN_EMPTY))
        agg_ref[3] = jnp.maximum(agg_ref[3], jnp.where(m, v, AGG_MAX_EMPTY))
        return ()

    jax.lax.fori_loop(0, chunk, body, ())


def scan_prune_call(src, lo, hi, *, cap, chunk=1024, interpret=None):
    """Switch-side scan pruning: filter ``src`` by the inclusive range
    predicate ``lo <= v <= hi`` and return only the first ``cap``
    surviving rows (in stream order) plus whole-stream aggregates.

    src: [N] int32 value stream; lo/hi: int32 scalars (traced OK);
    cap: static output capacity.  Returns
      vals [cap] int32 — surviving values (0-padded past the count),
      idx  [cap] int32 — their stream positions (-1-padded),
      agg  [4]   int32 — (count, sum, min, max) over ALL matches,
                         min/max = int32 identities when count == 0;
                         ``count > cap`` tells the caller the output
                         was truncated (rescan with a bigger cap).
    """
    n = src.shape[0]
    src = _pad_stream(jnp.asarray(src, jnp.int32), chunk)
    rows = pl.cdiv(cap, LANES)
    cap_block = pl.BlockSpec((rows, LANES), lambda i: (0, 0))
    vals, idx, agg = pl.pallas_call(
        functools.partial(_scan_prune_kernel, chunk=chunk, n=n, cap=cap),
        grid=(src.shape[0] // chunk,),
        in_specs=[_smem_whole(1), _smem_whole(1), _smem_stream(chunk)],
        out_specs=[cap_block, cap_block, _smem_whole(4)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32),
        ],
        # two outputs, each double-buffered
        compiler_params=_params(4 * rows * LANES * 4),
        interpret=_resolve(interpret),
    )(jnp.asarray([lo], jnp.int32), jnp.asarray([hi], jnp.int32), src)
    return vals.reshape(-1)[:cap], idx.reshape(-1)[:cap], agg

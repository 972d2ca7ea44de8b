"""Pallas TPU kernels of the switch: the serial engine's loop, the
result gather, and the scan-prune query.

Hardware mapping (Tofino -> TPU):
  * index and value streams are blocked into SMEM tiles of ``chunk``
    entries — scalar memory, where per-element scalar reads are legal;
    grid steps execute in order, so stream order is preserved;
  * anything addressed at random (a gather's source: the register file or
    a result plane) lives in VMEM as ``[rows, 128]`` int32 (one lane per
    slot, slot ``g`` at row ``g // 128``, lane ``g % 128``), copied in
    from HBM at the first grid step.  Mosaic has no scalar load or store
    on VMEM, so an element is read as a masked reduction of its row and
    written as a masked whole-row store.

The serial engine's loop (``serial_ws_call``) is all SMEM: it runs on
the batch's working set, one word per distinct slot (``core/engine``
gathers it from the register file and scatters it back), so every step
is scalar loads and stores, including CADD, the P4 constrained write the
vectorized engines cannot express.  VMEM use is the gather source (the
register file is 5 MiB at the default 20 x 65,536 configuration) plus
the compacted scan output; ``_vmem_limit`` sets the compiler's scoped
VMEM limit from those sizes.

The scan kernels are validated against ref.py, the serial engine
against the tests' serial loop reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NOP, READ, WRITE, ADD, CADD, ADDP = 0, 1, 2, 3, 4, 5

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


def _pad_stream(x, chunk):
    pad = (-x.shape[0]) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x


def _lanes():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def _smem_stream(chunk):
    return pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.SMEM)


def _smem_whole(n):
    return pl.BlockSpec((n,), lambda i: (0,), memory_space=pltpu.SMEM)


_HBM = pl.BlockSpec(memory_space=pl.ANY)


# -------------------------------------------- serial working-set kernel --

def _serial_ws_kernel(op_ref, li_ref, val_ref, ws_in_ref, ws_ref, res_ref,
                      ok_ref, *, n, k):
    """The serial engine's loop: all of it in SMEM, on scalars.  ``ws``
    is the batch's working set (one word per distinct slot), ``li`` each
    instruction's index into it; instruction ``i`` is op ``i % k`` of txn
    ``i // k``, so an ADDP's source result sits in the same row of
    ``res``."""
    def init(i, _):
        ws_ref[i] = ws_in_ref[i]
        res_ref[i] = 0
        return ()

    jax.lax.fori_loop(0, n, init, ())

    def body(i, _):
        o = op_ref[i]
        li = li_ref[i]
        v = val_ref[i]
        cur = ws_ref[li]
        prev = res_ref[i - i % k + jnp.clip(v, 0, k - 1)]
        post = cur + jnp.where(o == ADDP, prev, v)
        cadd_ok = post >= 0
        # READ and NOP leave new == cur, so the store is a no-op there
        new = jnp.where(o == WRITE, v,
              jnp.where((o == ADD) | (o == ADDP), post,
              jnp.where((o == CADD) & cadd_ok, post, cur)))
        ws_ref[li] = new
        res_ref[i] = jnp.where(o == READ, cur, jnp.where(o == NOP, 0, new))
        ok_ref[i] = jnp.where(o == CADD, cadd_ok, True).astype(jnp.int32)
        return ()

    jax.lax.fori_loop(0, n, body, ())


def serial_ws_call(op, li, val, ws, *, k, interpret=None):
    """Sequential execution of a flattened [n = B*k] instruction stream
    over a working set: ``op``/``val`` are the instructions, ``li[i]``
    indexes instruction ``i``'s slot in ``ws`` (every array [n] int32).

    Returns (ws after the batch [n], results [n], ok [n] int32)."""
    n = op.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_serial_ws_kernel, n=n, k=k),
        in_specs=[smem] * 4,
        out_specs=[smem] * 3,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * 3,
        interpret=_resolve(interpret),
    )(op, li, val, ws)


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

    The gather step of the scan queries (``ops.scan_prune`` /
    ``ops.scan_topk``): it pulls the queried slots out of the register
    file (the engines fuse an equivalent ``jnp.take`` into their compiled
    call instead).  Pad entries of ``idx`` point at slot 0 and are sliced
    off by the caller, so clamping (not masking) is sufficient.

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

    Walks the stream in order (sequential grid);
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

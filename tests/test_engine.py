"""Switch-engine properties: all execution paths produce the serial-
equivalent result; GIDs reflect serial order; state is recoverable.

Deterministic seed sweeps live here; the hypothesis-driven property
versions are in test_engine_properties.py (skipped when hypothesis is not
installed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.engine import _ENGINE_IMPLS, ShardedSwitchEngine, SwitchEngine
from repro.core.packets import (ADD, ADDP, CADD, NOP, READ, WRITE,
                                SwitchConfig, empty_packets, make_packet,
                                mark_multipass, split_passes)

CFG = SwitchConfig(n_stages=6, regs_per_stage=16, max_instrs=5)


def random_batch(rng, B, K, ops=(NOP, READ, WRITE, ADD), stage_sorted=False):
    p = empty_packets(B, CFG)
    p["op"] = rng.integers(min(ops), max(ops) + 1, (B, K)).astype(np.int32)
    st_ = rng.integers(0, CFG.n_stages, (B, K)).astype(np.int32)
    p["stage"] = np.sort(st_, axis=1) if stage_sorted else st_
    p["reg"] = rng.integers(0, CFG.regs_per_stage, (B, K)).astype(np.int32)
    p["operand"] = rng.integers(-100, 100, (B, K)).astype(np.int32)
    return p


def staged_addp_batch(rng, B=32, K=4):
    """Random batch with stage-sorted packets and safe (earlier-stage
    source) ADDP instructions — the shape the declustered layout emits."""
    p = empty_packets(B, CFG)
    for b in range(B):
        stages = np.sort(rng.choice(CFG.n_stages, size=K, replace=False))
        for k in range(K):
            if k > 0 and rng.random() < 0.4:
                p["op"][b, k] = ADDP
                p["operand"][b, k] = rng.integers(0, k)
            else:
                p["op"][b, k] = rng.choice([READ, WRITE, ADD])
                p["operand"][b, k] = rng.integers(-50, 50)
            p["stage"][b, k] = stages[k]
            p["reg"][b, k] = rng.integers(0, CFG.regs_per_stage)
    return p


@pytest.mark.parametrize("seed,B", [(0, 1), (1, 3), (2, 17), (3, 33),
                                    (4, 64)])
def test_affine_equals_serial(seed, B):
    rng = np.random.default_rng(seed)
    p = random_batch(rng, B, CFG.max_instrs)
    regs0 = rng.integers(-50, 50, (CFG.n_stages, CFG.regs_per_stage))
    e1, e2 = SwitchEngine(CFG, regs0), SwitchEngine(CFG, regs0)
    r1, ok1, g1 = e1.execute(p, mode="serial")
    r2, ok2, g2 = e2.execute(p, mode="affine")
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(e1.read_all(), e2.read_all())
    np.testing.assert_array_equal(g1, g2)


@pytest.mark.parametrize("seed", range(4))
def test_staged_equals_serial_with_addp(seed):
    rng = np.random.default_rng(seed)
    p = staged_addp_batch(rng)
    regs0 = rng.integers(0, 50, (CFG.n_stages, CFG.regs_per_stage))
    e1, e2 = SwitchEngine(CFG, regs0), SwitchEngine(CFG, regs0)
    r1, _, _ = e1.execute(p, mode="serial")
    r2, _, _ = e2.execute(p, mode="staged")
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(e1.read_all(), e2.read_all())


def test_unsafe_addp_dispatches_serial():
    """An ADDP whose source slot sits at a later stage (multipass packet)
    must take the serial path in auto mode and be rejected by staged."""
    p = empty_packets(1, CFG)
    # READ at stage 3; ADDP at stage 1 referencing it -> unsafe
    p["op"][0, 0], p["stage"][0, 0], p["reg"][0, 0] = READ, 3, 2
    p["op"][0, 1], p["stage"][0, 1], p["reg"][0, 1] = ADDP, 1, 5
    p["operand"][0, 1] = 0
    regs0 = np.zeros((CFG.n_stages, CFG.regs_per_stage), np.int32)
    regs0[3, 2] = 40
    regs0[1, 5] = 2
    e = SwitchEngine(CFG, regs0)
    res, _, _ = e.execute(p)                     # auto -> serial
    assert res[0, 1] == 42
    assert e.read_all()[1, 5] == 42
    with pytest.raises(ValueError):
        SwitchEngine(CFG, regs0).execute(p, mode="staged")


def test_batch_order_is_serial_order():
    """Two conflicting txns: second must observe the first (pipeline
    no-reorder property, paper §5.1)."""
    e = SwitchEngine(CFG)
    p = empty_packets(2, CFG)
    p["op"][0, 0], p["stage"][0, 0], p["reg"][0, 0], p["operand"][0, 0] = \
        WRITE, 2, 5, 77
    p["op"][1, 0], p["stage"][1, 0], p["reg"][1, 0] = READ, 2, 5
    res, _, gids = e.execute(p)
    assert res[1, 0] == 77
    assert gids[0] < gids[1]


def test_cadd_constrained_write():
    e = SwitchEngine(CFG)
    e.execute(make_packet([(WRITE, 0, 0, 5)], CFG))
    res, ok, _ = e.execute(make_packet([(CADD, 0, 0, -9)], CFG))
    assert not ok[0, 0] and e.read_all()[0, 0] == 5
    res, ok, _ = e.execute(make_packet([(CADD, 0, 0, -3)], CFG))
    assert ok[0, 0] and e.read_all()[0, 0] == 2


def test_pass_splitting():
    pk = make_packet([(READ, 0, 0, 0), (ADD, 2, 1, 5), (WRITE, 1, 0, 7)],
                     CFG)
    assert pk["is_multipass"][0]
    assert len(split_passes(pk, 0)) == 2
    pk = make_packet([(READ, 0, 0, 0), (ADD, 1, 1, 5), (WRITE, 2, 0, 7)],
                     CFG)
    assert not pk["is_multipass"][0]


def test_snapshot_restore():
    rng = np.random.default_rng(0)
    e = SwitchEngine(CFG)
    e.execute(random_batch(rng, 16, 4))
    snap = e.snapshot()
    e.execute(random_batch(rng, 16, 4))
    e.restore(snap)
    np.testing.assert_array_equal(e.read_all(), snap[0])
    assert e.next_gid == snap[1]


# ------------------------------------------- plain-Python serial oracle --

def serial_loop_ref(regs0, p):
    """The switch's serial semantics as a plain loop over rows, then
    instructions: NOP does nothing; READ returns the register; WRITE
    stores the operand; ADD adds it; CADD adds it only if the sum is >= 0
    (else the register is kept, ``ok`` is false and the result is the
    unchanged value); ADDP adds the result of instruction ``operand`` of
    the same txn.  Returns (results, ok, registers)."""
    regs = [[int(x) for x in row] for row in regs0]
    B, K = p["op"].shape
    res = [[0] * K for _ in range(B)]
    ok = [[True] * K for _ in range(B)]
    for b in range(B):
        for k in range(K):
            o = int(p["op"][b, k])
            s, r = int(p["stage"][b, k]), int(p["reg"][b, k])
            v = int(p["operand"][b, k])
            if o == NOP:
                continue
            cur = regs[s][r]
            if o == READ:
                res[b][k] = cur
                continue
            if o == WRITE:
                new = v
            elif o == ADD:
                new = cur + v
            elif o == ADDP:
                new = cur + res[b][v]
            else:                                  # CADD
                new = cur + v
                if new < 0:
                    ok[b][k], new = False, cur
            regs[s][r] = res[b][k] = new
    return np.array(res), np.array(ok), np.array(regs)


def _rows(rows, K=CFG.max_instrs):
    """Packets from explicit rows of (op, stage, reg, operand) tuples,
    NOP-padded to K like the packet builder pads them."""
    p = empty_packets(len(rows), CFG)
    for b, row in enumerate(rows):
        for k, (o, s, r, v) in enumerate(row):
            p["op"][b, k], p["stage"][b, k] = o, s
            p["reg"][b, k], p["operand"][b, k] = r, v
    return p


def _mixed_batch(rng, B, K=CFG.max_instrs):
    """Every opcode, slots drawn from a few registers so they repeat
    within and across txns, ADDP sources at any earlier instruction
    (whatever its stage), NOP padding at each row's tail."""
    p = empty_packets(B, CFG)
    for b in range(B):
        n = int(rng.integers(0, K + 1))
        for k in range(n):
            o = int(rng.choice([READ, WRITE, ADD, CADD, ADDP] if k else
                               [READ, WRITE, ADD, CADD]))
            p["op"][b, k] = o
            p["stage"][b, k] = rng.integers(0, CFG.n_stages)
            p["reg"][b, k] = rng.integers(0, 3)
            p["operand"][b, k] = (rng.integers(0, k) if o == ADDP else
                                  rng.integers(-60, 40))
    return p


SERIAL_CASES = {
    # balance 5: -9 fails (kept, ok false), -3 applies, then +1
    "cadd_fails_and_applies": lambda rng: _rows(
        [[(CADD, 2, 3, -9), (CADD, 2, 3, -3), (CADD, 2, 3, 1)],
         [(CADD, 2, 3, -3), (READ, 2, 3, 0)]]),
    # multipass ADDP: the source READ sits at the same stage, or later
    "addp_source_same_stage": lambda rng: _rows(
        [[(READ, 3, 2, 0), (ADDP, 3, 5, 0), (ADDP, 3, 2, 1)]]),
    "addp_source_later_stage": lambda rng: _rows(
        [[(READ, 4, 1, 0), (ADDP, 1, 5, 0), (READ, 5, 0, 0),
          (ADDP, 0, 7, 2)],
         [(ADD, 4, 1, 3), (READ, 1, 5, 0)]]),
    "slot_repeated_in_and_across_txns": lambda rng: _rows(
        [[(ADD, 1, 4, 2), (READ, 1, 4, 0), (WRITE, 1, 4, 9),
          (ADD, 1, 4, -1)],
         [(READ, 1, 4, 0), (CADD, 1, 4, -20), (ADD, 1, 4, 5)],
         [(READ, 1, 4, 0)]]),
    "nop_padding": lambda rng: _rows(
        [[(NOP, 0, 0, 0), (ADD, 2, 2, 4), (NOP, 0, 0, 0),
          (READ, 2, 2, 0)], [], [(WRITE, 5, 15, 3)]]),
    # a real op on slot (0, 0) while every padding NOP also points there
    "slot_00_shared_with_padding": lambda rng: _rows(
        [[(ADD, 0, 0, 7), (READ, 0, 0, 0)], [], [(WRITE, 0, 0, -2)],
         [(CADD, 0, 0, 1), (READ, 3, 3, 0)], []]),
    **{f"random_b{B}": (lambda rng, B=B: _mixed_batch(rng, B))
       for B in (1, 3, 8, 64, 200)},          # 200 pads to 256
}


@pytest.mark.parametrize("case", sorted(SERIAL_CASES))
def test_serial_equals_loop_reference(case):
    rng = np.random.default_rng(sorted(SERIAL_CASES).index(case))
    p = SERIAL_CASES[case](rng)
    regs0 = rng.integers(0, 30, (CFG.n_stages, CFG.regs_per_stage))
    regs0[2, 3] = 5
    want_res, want_ok, want_regs = serial_loop_ref(regs0, p)
    e = SwitchEngine(CFG, regs0)
    res, ok, _ = e.execute(p, mode="serial")
    np.testing.assert_array_equal(res, want_res)
    np.testing.assert_array_equal(ok, want_ok)
    np.testing.assert_array_equal(e.read_all(), want_regs)


@pytest.mark.parametrize("S,R,B,K", [
    (4, 8, 16, 3),
    (6, 32, 64, 5),
    (12, 64, 100, 8),          # B pads to the 128 bucket
    (6, 32, 37, 5),
    (4, 16, 1, 7),             # B=1 per-txn shape, odd K
])
def test_serial_shapes_equal_loop_reference(S, R, B, K):
    """The serial engine across plane and batch shapes: random NOP / READ
    / WRITE / ADD / CADD streams over the whole plane."""
    rng = np.random.default_rng(S * 1000 + B)
    cfg = SwitchConfig(n_stages=S, regs_per_stage=R, max_instrs=K)
    p = empty_packets(B, cfg)
    p["op"] = rng.integers(NOP, CADD + 1, (B, K)).astype(np.int32)
    p["stage"] = rng.integers(0, S, (B, K)).astype(np.int32)
    p["reg"] = rng.integers(0, R, (B, K)).astype(np.int32)
    p["operand"] = rng.integers(-30, 30, (B, K)).astype(np.int32)
    regs0 = rng.integers(-50, 100, (S, R))
    want_res, want_ok, want_regs = serial_loop_ref(regs0, p)
    e = SwitchEngine(cfg, regs0)
    res, ok, _ = e.execute(p, mode="serial")
    np.testing.assert_array_equal(res, want_res)
    np.testing.assert_array_equal(ok, want_ok)
    np.testing.assert_array_equal(e.read_all(), want_regs)


AUTO_CASES = {
    "affine": lambda rng: random_batch(rng, 24, CFG.max_instrs),
    "staged": lambda rng: staged_addp_batch(rng, 24),
    "serial_cadd": lambda rng: random_batch(rng, 24, CFG.max_instrs,
                                            ops=(NOP, READ, WRITE, ADD,
                                                 CADD)),
}


@pytest.mark.parametrize("case", sorted(AUTO_CASES))
def test_auto_picks_the_engine_from_the_batch(case):
    """``auto`` picks the engine from the batch's opcodes: READ / WRITE /
    ADD only -> affine, safe ADDP -> staged, CADD -> serial; the results
    are the serial semantics either way (multipass ADDP -> serial is
    ``test_unsafe_addp_dispatches_serial``)."""
    rng = np.random.default_rng(sorted(AUTO_CASES).index(case))
    p = AUTO_CASES[case](rng)
    regs0 = rng.integers(0, 50, (CFG.n_stages, CFG.regs_per_stage))
    want_res, want_ok, want_regs = serial_loop_ref(regs0, p)
    e = SwitchEngine(CFG, regs0)
    pb = e.execute_batch(p)
    assert pb.mode == case.split("_")[0]
    np.testing.assert_array_equal(pb.results_np(), want_res)
    np.testing.assert_array_equal(pb.ok_np(), want_ok)
    np.testing.assert_array_equal(e.read_all(), want_regs)


@pytest.mark.parametrize("mode", ["pallas", "bogus"])
def test_engine_rejects_an_unknown_mode(mode):
    """The engines are ``serial``, ``staged`` and ``affine``, or ``auto``
    to pick one from the batch; any other name is refused before a
    dispatch."""
    e = SwitchEngine(CFG)
    with pytest.raises(ValueError):
        e.execute(random_batch(np.random.default_rng(0), 4, 2), mode=mode)
    assert e.dispatch_count == 0 and e.next_gid == 0


def test_serial_row_blocks_equal_loop_reference(monkeypatch):
    """A batch above one kernel call's SMEM budget runs as consecutive
    row blocks, each seeing the writes of the blocks before it."""
    monkeypatch.setattr(engine, "_SERIAL_CALL_INSTRS", 4 * CFG.max_instrs)
    rng = np.random.default_rng(11)
    p = _mixed_batch(rng, 30)
    regs0 = rng.integers(0, 30, (CFG.n_stages, CFG.regs_per_stage))
    want_res, want_ok, want_regs = serial_loop_ref(regs0, p)
    # a fresh function object, so the patched budget is traced
    regs, res, ok = jax.jit(lambda *a: _ENGINE_IMPLS["serial"](*a))(
        regs0.astype(np.int32), p["op"], p["stage"], p["reg"], p["operand"])
    np.testing.assert_array_equal(res, want_res)
    np.testing.assert_array_equal(ok, want_ok)
    np.testing.assert_array_equal(regs, want_regs)


def _loop_carries(jaxpr):
    """Avals of every scan / while carry in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        prm = eqn.params
        if eqn.primitive.name == "scan":
            nc = prm["num_consts"]
            yield from (v.aval for v in
                        eqn.invars[nc:nc + prm["num_carry"]])
        elif eqn.primitive.name == "while":
            n = prm["cond_nconsts"] + prm["body_nconsts"]
            yield from (v.aval for v in eqn.invars[n:])
        for val in prm.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _loop_carries(sub)


@pytest.mark.parametrize("Bp", [8, 1024])
def test_serial_loop_carries_no_register_plane(Bp):
    """At the benchmark's 20 x 65,536 plane no loop of the serial engine
    carries the register plane: its loop runs on the batch's working
    set, at most ``Bp * K`` words."""
    S, R, K = 20, 65536, 8
    spec = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(_ENGINE_IMPLS["serial"])(
        spec((S, R), jnp.int32), *[spec((Bp, K), jnp.int32)] * 4)
    sizes = [int(np.prod(a.shape)) for a in _loop_carries(closed.jaxpr)]
    assert sizes, "the serial engine has no loop"
    assert S * R not in sizes and max(sizes) <= Bp * K


# ------------------------------------------------- serial counters ----

def test_serial_counters_count_steps_and_instrs():
    p = _rows([[(CADD, 0, 1, -1), (READ, 2, 2, 0)],
               [(ADD, 1, 1, 3), (NOP, 0, 0, 0), (WRITE, 4, 0, 2)],
               [(READ, 5, 5, 0)]])
    e = SwitchEngine(CFG)
    e.execute(p)                                   # auto -> serial (CADD)
    assert (e.serial_steps, e.serial_instrs) == (4 * 5, 5)
    e.execute(p, mode="serial")
    assert (e.serial_steps, e.serial_instrs) == (2 * 20, 2 * 5)


@pytest.mark.parametrize("mode", ["affine", "staged"])
def test_serial_counters_ignore_other_engines(mode):
    rng = np.random.default_rng(5)
    p = (random_batch(rng, 8, CFG.max_instrs) if mode == "affine" else
         staged_addp_batch(rng, 8))
    e = SwitchEngine(CFG)
    e.execute(p, mode=mode)
    assert e.dispatch_count == 1
    assert (e.serial_steps, e.serial_instrs) == (0, 0)


def test_sharded_serial_counters_sum_over_planes():
    cfg = SwitchConfig(n_stages=CFG.n_stages,
                       regs_per_stage=CFG.regs_per_stage,
                       max_instrs=CFG.max_instrs, n_switches=2)
    S = cfg.n_stages
    # one row per shard (a B = 1 group of K steps on each plane), and one
    # cross-shard row: two 1 x 1 serial mini-dispatches
    p = _rows([[(CADD, 1, 2, -1), (READ, 2, 2, 0)],
               [(ADD, S + 1, 1, 3), (READ, S + 3, 0, 0)],
               [(READ, 0, 0, 0), (ADD, S, 0, 4)]])
    e = ShardedSwitchEngine(cfg)
    e.execute(p)
    K = CFG.max_instrs
    assert e.serial_steps == sum(q.serial_steps for q in e.planes)
    assert e.serial_instrs == sum(q.serial_instrs for q in e.planes)
    assert e.serial_steps == K + K + 2
    assert e.serial_instrs == 2 + 2 + 2

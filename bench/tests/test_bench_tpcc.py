"""The TPC-C NewOrder + Payment family: the vectorised copy draws what
``repro.workloads.tpcc`` draws, the hot index the harness builds from its
sample holds every hot key, a tiny run of ``tpcc_np.closed`` is correct
and planted faults in its timed path are not, and at the cell's own size
a reference with the warm protocol broken is not correct either."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import json

import numpy as np
import pytest

import control
import harness
from ops import ADD, READ, WRITE, key_of
from reference import Reference, flat_ops
from test_bench_check import answer_altered, state_unchanged, tiny_run
from test_bench_traffic import program_arrays
from workloads import tpcc

N = 4000
CFG = json.loads((BENCH / "configs" / "tpcc_np.json").read_text())
P = CFG["params"]


def program_params(p):
    from repro.workloads import tpcc as prog
    assert p["mix"]["neworder"] == prog.P_NEWORDER
    assert (p["ol_cnt_min"], p["ol_cnt_max"]) == prog.OL_CNT
    assert p["max_amount"] == prog.MAX_AMOUNT
    return prog.TPCCParams(**{k: p[k] for k in (
        "n_nodes", "n_warehouses", "n_items", "n_customers",
        "remote_line_frac", "remote_payment_frac")})


def table(key):
    """Which column or inserted table a key belongs to."""
    local = key % 1_000_000_000
    return np.where(local < 200_000_000, local // 10_000_000,
                    100 + local // 100_000_000)


def stats(op, key, val, n_ops, kind):
    valid = np.arange(op.shape[1])[None, :] < n_ops[:, None]
    no, pay = kind == 0, kind == 1
    tab = table(key)
    local = key % 1_000_000_000
    ol_cnt = (n_ops[no] - 6) // 3
    # a line's supply warehouse, from its S_QUANTITY key
    stock = (tab == tpcc.S_QUANTITY // 10_000_000) & valid
    sw = (local - tpcc.S_QUANTITY) // 100_000
    home_w = local[:, 0] - tpcc.W_TAX                 # NewOrder's op 0
    remote_line = (sw != home_w[:, None]) & stock
    # a payment's customer warehouse, from its C_BALANCE key
    cw = (local[:, 2] - tpcc.C_BALANCE) // (tpcc.N_DISTRICTS * 3000)
    pay_w = local[:, 0] - tpcc.W_YTD
    items = (local - tpcc.I_PRICE)[(tab == tpcc.I_PRICE // 10_000_000)
                                   & valid]
    _, counts = np.unique(items, return_counts=True)
    mix = {}
    for o, t in zip(op[valid].tolist(), tab[valid].tolist()):
        mix[o, t] = mix.get((o, t), 0) + 1
    total = int(valid.sum())
    return dict(
        neworder=float(no.mean()), ol_mean=float(ol_cnt.mean()),
        ol_range=(int(ol_cnt.min()), int(ol_cnt.max())),
        payment_ops=set(n_ops[pay].tolist()),
        remote_line=float(remote_line[no].sum() / stock[no].sum()),
        remote_payment=float((cw[pay] != pay_w[pay]).mean()),
        mix={k: v / total for k, v in mix.items()},
        top_items=float(np.sort(counts)[::-1][:100].sum() / counts.sum()),
        amount=float(val[pay, 0].mean()))


def test_tpcc_matches_the_program_generator():
    from repro.workloads import tpcc as prog
    pp = program_params(P)
    assert set(tpcc.hot_keys(P)) == set(prog.hot_keys(pp))
    assert len(tpcc.hot_keys(P)) == 32 * P["n_warehouses"]
    mine = tpcc.generate(np.random.default_rng(13), N, P)
    txns = prog.generate(np.random.default_rng(13), N, pp)
    op, key, n_ops, _ = program_arrays(txns, tpcc.K)
    val = np.zeros_like(op)
    for i, t in enumerate(txns):
        val[i, :len(t.ops)] = [v for _, _, v in t.ops]
    kind = np.array([tpcc.KINDS.index(t.kind) for t in txns])
    a = stats(mine["op"], mine["key"], mine["val"], mine["n_ops"],
              mine["kind"])
    b = stats(op, key, val, n_ops, kind)
    assert a["neworder"] == pytest.approx(b["neworder"], abs=0.03)
    assert a["neworder"] == pytest.approx(0.5, abs=0.03)
    assert a["ol_range"] == b["ol_range"] == (5, 15)
    assert a["ol_mean"] == pytest.approx(b["ol_mean"], abs=0.2)
    assert a["ol_mean"] == pytest.approx(10, abs=0.2)
    assert a["payment_ops"] == b["payment_ops"] == {6}
    assert a["remote_line"] == pytest.approx(b["remote_line"], abs=0.005)
    assert a["remote_line"] == pytest.approx(0.01, abs=0.005)
    assert a["remote_payment"] == pytest.approx(b["remote_payment"],
                                                abs=0.03)
    assert a["remote_payment"] == pytest.approx(0.15, abs=0.03)
    assert set(a["mix"]) == set(b["mix"])
    for k in a["mix"]:
        assert a["mix"][k] == pytest.approx(b["mix"][k], abs=0.01), k
    # NURand(8191, 1, 100000): the 100 most ordered items take far more
    # than their uniform 0.1% of the lines
    assert a["top_items"] == pytest.approx(b["top_items"], abs=0.02)
    assert a["top_items"] > 0.02
    assert a["amount"] == pytest.approx(b["amount"], rel=0.05)
    # every txn is warm: hot and cold keys in each
    hot = np.isin(mine["key"], tpcc.hot_keys(P))
    valid = np.arange(tpcc.K)[None, :] < mine["n_ops"][:, None]
    assert (hot & valid).any(axis=1).all()
    assert (~hot & valid).any(axis=1).all()
    assert set(np.unique(mine["op"][valid]).tolist()) == {READ, WRITE, ADD}


def test_insert_keys_are_unique_and_the_seed_decides_the_traffic():
    seed = 2 ** 40 + 5
    a = tpcc.generate(harness.rng(seed, harness.TRAFFIC), 3000, P)
    b = tpcc.generate(harness.rng(seed, harness.TRAFFIC), 3000, P)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    valid = np.arange(tpcc.K)[None, :] < a["n_ops"][:, None]
    ins = a["key"][valid & (a["op"] == WRITE)]
    assert len(np.unique(ins)) == len(ins)
    keys, vals = tpcc.initial_values(harness.rng(seed, harness.LOAD),
                                     np.unique(a["key"][valid]), P)
    assert not np.isin(ins, keys).any()            # inserts start absent
    assert set(tpcc.hot_keys(P)) <= set(keys.tolist())
    assert vals.min() >= -10 and vals.max() <= 300_000


@pytest.mark.parametrize("seed", [0, 1, 2, 2 ** 33 + 7, 2 ** 40 + 3])
def test_the_sampled_hot_index_holds_every_hot_key(seed):
    """As ``harness.build_cluster`` builds it, at the configuration's
    sample size: no NURand stock or item row displaces a district key."""
    from repro.core.hotset import detect_hotset
    sample = tpcc.generate(harness.rng(seed, harness.SAMPLE),
                           CFG["hot_sample_txns"], P)
    n_ops = sample["n_ops"].tolist()
    traces = [list(zip(k[:n], o[:n])) for k, o, n in
              zip(sample["key"].tolist(), sample["op"].tolist(), n_ops)]
    hot = tpcc.hot_keys(P)
    assert set(detect_hotset(traces, len(hot))) == set(hot)


def test_a_sound_tpcc_run_is_correct():
    out = tiny_run("tpcc_np.closed", batch=16, seconds=0.2)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, answer_altered])
def test_a_broken_tpcc_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = tiny_run("tpcc_np.closed", batch=16, seconds=0.2)
    assert not out["correct"], out["checks"]


def test_every_replica_of_an_item_has_one_price():
    items = np.arange(50)
    touched = [key_of(node, tpcc.I_PRICE + items)
               for node in range(P["n_nodes"])]
    keys, vals = tpcc.initial_values(np.random.default_rng(3),
                                     np.concatenate(touched), P)
    price = dict(zip(keys.tolist(), vals.tolist()))
    by_node = np.array([[price[k] for k in t.tolist()] for t in touched])
    assert (by_node == by_node[0]).all()
    assert by_node.min() >= 1 and by_node.max() <= 100
    assert len(set(by_node[0].tolist())) > 1


def warm_reordered(arrs, n, initial, hot_keys, batch):
    """The reference's outputs with the warm protocol broken: in each
    batch every warm txn's cold part runs in admission order, and then
    their hot parts run last to first (hot and cold txns keep their
    places)."""
    ref = Reference(initial, hot_keys)
    flat, K = flat_ops({f: arrs[f][:n] for f in ("op", "key", "val")})
    n_ops = arrs["n_ops"][:n].tolist()
    results = [None] * n
    for lo in range(0, n, batch):
        pending = []
        for i in range(lo, min(lo + batch, n)):
            ops = flat[i * K:i * K + n_ops[i]]
            hot = [k in ref.hot for _, k, _ in ops]
            if all(hot) or not any(hot):
                results[i] = ref.apply(ops)
                continue
            cold = ref._two_phase([o for o, h in zip(ops, hot) if not h])
            if cold is not None:
                pending.append((i, ops, hot, cold))
        for i, ops, hot, cold in reversed(pending):
            hot_res = iter(ref._switch([o for o, h in zip(ops, hot) if h]))
            cold_res = iter(cold)
            results[i] = [next(hot_res) if h else next(cold_res)
                          for h in hot]
    return results, ref.values


@pytest.mark.parametrize("seed", [1, 2 ** 40 + 9])
def test_the_warm_control_is_not_correct_at_the_cells_size(seed,
                                                           monkeypatch):
    """``bench/control.py`` reverses all-hot txns only, and this cell has
    none, so there it equals the reference.  Reversing each batch's warm
    hot parts instead, over four of the cell's 1,024-txn batches, changes
    the D_NEXT_O_ID, W_YTD and D_YTD answers, and the check sees it."""
    traffic = json.loads((BENCH / "traffic" / "tpcc_np.closed.json")
                         .read_text())
    n = 4 * traffic["batch_txns"]
    ctl, sound = control.readings(CFG, traffic, seed, n)
    assert ctl == sound
    monkeypatch.setattr(control, "reordered", warm_reordered)
    ctl, sound = control.readings(CFG, traffic, seed, n)
    print(json.dumps(dict(seed=seed, txns=n, control=ctl)))
    assert ctl["results_differ"][0] > n // 4, ctl
    assert any(v > lim for v, lim in ctl.values()), ctl
    assert all(v <= lim for v, lim in sound.values()), sound

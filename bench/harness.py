"""One run of one benchmark cell: set-up, the measured window, the check
against the serial reference, and the metrics.

Everything a cell needs is found by name: its configuration in
``configs/<config>.json``, its traffic in ``traffic/<cell>.json``, the
generator of its workload family in ``workloads/<family>.py``, the loop
that drives it in ``loops/<loop>.py`` and each per-layer metric in
``metrics/<metric>.py``.  From the program the harness takes only the
system under test (``Cluster`` and what builds it), its spans
(``Tracer``), its counters (``dispatch_count``) and its program names in
the profiler trace.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import roofline
import tracing
import warm
from ops import NODE_STRIDE
from reference import compare, flat_ops, run_reference

BENCH = Path(__file__).resolve().parent

# independent streams drawn from one --seed
TRAFFIC, SAMPLE, LOAD, ARRIVALS = range(4)


class TrafficExhausted(RuntimeError):
    """The run needed more txns than were generated for it."""


def rng(seed, stream):
    return np.random.default_rng([seed, stream])


def load_json(path):
    with open(path) as f:
        return json.load(f)


def family(name):
    return importlib.import_module(f"workloads.{name}")


def loop_driver(name):
    return importlib.import_module(f"loops.{name}")


def metric_reader(name):
    """The ``read(rec)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Programs built (compiled, or loaded from the persistent cache), from
    JAX's backend-compile events, which fire for a cache hit too."""

    def __init__(self):
        import jax
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


@dataclass
class Server:
    """The client side of the served path: admits txns ``lo..hi`` through
    ``Cluster.run_batch`` and waits for every result with ``drain``."""
    cluster: object
    txns: list
    results: list = field(default_factory=list)
    calls: list = field(default_factory=list)    # (t0, t1, lo, hi)

    def __post_init__(self):
        self.results = [None] * len(self.txns)

    def serve(self, lo, hi):
        if hi > len(self.txns):
            raise TrafficExhausted(
                f"the run needed txn {hi}, {len(self.txns)} were generated")
        t0 = time.perf_counter()
        out = self.cluster.run_batch(self.txns[lo:hi])
        self.cluster.drain()
        res = list(out)
        t1 = time.perf_counter()
        # a result the program never returned stays None: it differs
        self.results[lo:lo + min(len(res), hi - lo)] = res[:hi - lo]
        self.calls.append((t0, t1, lo, hi))
        return t1


def make_txns(arrs, kinds):
    """The program's Txn objects for the generated arrays."""
    from repro.db.txn import Txn
    flat, K = flat_ops(arrs)
    n_ops, home, kind = (arrs[f].tolist() for f in ("n_ops", "home", "kind"))
    return [Txn(kinds[kind[i]], flat[i * K:i * K + n_ops[i]], home[i])
            for i in range(len(n_ops))]


def hot_mask(arrs, hot_keys):
    """[n] True for txns whose every key is hot."""
    hk = np.sort(np.asarray(hot_keys, np.int64))
    valid = np.arange(arrs["op"].shape[1])[None, :] < arrs["n_ops"][:, None]
    pos = np.minimum(np.searchsorted(hk, arrs["key"]), len(hk) - 1)
    is_hot = hk[pos] == arrs["key"]
    return (is_hot | ~valid).all(axis=1), is_hot & valid


def build_cluster(cfg, fam, arrs, hot_keys, seed, tracer):
    """The deployment: hot index from a sampled trace, a cluster as the
    configuration states, initial values loaded.  Returns the cluster and
    the initial values the reference starts from."""
    from repro.core.hotset import build_hot_index
    from repro.core.packets import SwitchConfig
    from repro.db.dbms import Cluster
    p = cfg["params"]
    sw = SwitchConfig(**cfg["switch"])
    sample = fam.generate(rng(seed, SAMPLE), cfg["hot_sample_txns"], p)
    n_ops = sample["n_ops"].tolist()
    traces = [list(zip(k[:n], o[:n])) for k, o, n in
              zip(sample["key"].tolist(), sample["op"].tolist(), n_ops)]
    hi = build_hot_index(traces, len(hot_keys), sw)
    c = Cluster(p["n_nodes"], sw, hi, tracer=tracer, **cfg["cluster"])
    hot = set(hot_keys)
    valid = np.arange(arrs["op"].shape[1])[None, :] < arrs["n_ops"][:, None]
    touched = np.unique(arrs["key"][valid])
    keys, vals = fam.initial_values(rng(seed, LOAD), touched, p)
    initial = dict(zip(keys.tolist(), vals.tolist()))
    for k, v in initial.items():
        if k in hot:
            c.load(k, v)           # a logged write, dispatched to the switch
        else:
            # bulk load of a cold row straight into its home store
            c.nodes[k // NODE_STRIDE].store[k] = v
    c.snapshot_offload()
    return c, initial


def final_values(c, hot_keys):
    """Hot keys' values from every register plane, cold rows' values from
    their home stores."""
    keys = np.asarray(sorted(hot_keys), np.int64)
    regs = c.switch.read_all()
    hot = dict.fromkeys(keys.tolist())
    try:
        sw, st, rg = c.hot_index.slots_np(keys)
        vals = regs[st, rg] if regs.ndim == 2 else regs[sw, st, rg]
        hot = dict(zip(keys.tolist(), vals.tolist()))
    except KeyError:
        pass                        # a hot key with no slot: every one differs
    cold = {}
    for n in c.nodes:
        cold.update(n.store)
    return dict(hot=hot, cold=cold)


def traffic_size(cfg, traffic, seconds, seed):
    """How many txns the run generates, and the open loop's arrivals."""
    warm_n = traffic["warmup_batches"] * traffic["batch_txns"]
    if traffic["loop"] == "open":
        import arrivals
        arr = arrivals.generate(rng(seed, ARRIVALS), traffic, seconds)
        return warm_n + len(arr), arr
    return warm_n + int(np.ceil(traffic["max_txn_per_s"] * seconds)), None


def device_info(devices):
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind, count=len(devices))


def memory_peak(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(manifest, cell, seed, seconds, trace, t_start):
    """One run of the cell as its files describe it; returns the result
    object the run prints."""
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['name']}.json")
    return run(manifest, cell, cfg, traffic, seed, seconds, trace, t_start)


def run(manifest, cell, cfg, traffic, seed, seconds, trace, t_start):
    """One run of ``cell`` with configuration ``cfg`` and traffic
    ``traffic``."""
    import jax
    from repro.obs.trace import Tracer

    fam = family(cfg["workload"])
    p = cfg["params"]
    devices = jax.devices()
    chips = devices[:cell["chips"]]
    counter = CompileCounter()

    n, arrivals = traffic_size(cfg, traffic, seconds, seed)
    arrs = fam.generate(rng(seed, TRAFFIC), n, p)
    hot_keys = fam.hot_keys(p)
    hot_txn, hot_op = hot_mask(arrs, hot_keys)
    tracer = Tracer(capacity=1 << 24) if trace else None
    c, initial = build_cluster(cfg, fam, arrs, hot_keys, seed, tracer)
    txns = make_txns(arrs, fam.KINDS)

    # every program the window can use, then a few real batches
    sw = cfg["switch"]
    shape_set = warm.shapes(arrs, hot_txn, traffic["loop"],
                            traffic["batch_txns"], sw["max_instrs"],
                            sw["n_switches"])
    warm.warm(shape_set, c.switch_cfg,
              list(chips) if sw["n_switches"] > 1 else [None])
    server = Server(c, txns)
    start = 0
    for _ in range(traffic["warmup_batches"]):
        server.serve(start, start + traffic["batch_txns"])
        start += traffic["batch_txns"]
    # the pre-built traffic is the benchmark's, not the program's: keep
    # the collector from walking it during the window
    gc.collect()
    gc.freeze()
    if tracer is not None:
        tracer.clear()
    server.calls.clear()
    d0, k0 = c.switch.dispatch_count, counter.compiles
    setup_s = time.perf_counter() - t_start

    with tracing.capture(trace) as cap:
        win = loop_driver(traffic["loop"]).run(server, start, traffic,
                                               seconds, arrivals)
    compiles = counter.compiles - k0
    dispatches = c.switch.dispatch_count - d0
    mem = memory_peak(chips)

    lo, hi = win["lo"], win["hi"]
    got = [r if isinstance(r, list) else None for r in server.results[:hi]]
    committed = sum(r is not None for r in got[lo:hi])
    spans = []
    if tracer is not None:
        spans = [(s.name, s.t0, s.t1) for tr in tracer.traces
                 for s in tr.spans]
    values = final_values(c, hot_keys)
    calls = [(a, b) for a, b, _, _ in server.calls]
    batches = [(l, h) for _, _, l, h in server.calls]
    del c, server, txns, tracer
    gc.unfreeze()
    gc.collect()

    want, want_values = run_reference(arrs, hi, initial, hot_keys)
    checks = compare(got, want, values, want_values)
    correct = all(v <= lim for v, lim in checks.values())

    elapsed = win["t1"] - win["t0"]
    e2e = dict(setup_s=(setup_s, "s"),
               txn_per_s=(committed / elapsed, "txn/s"))
    if "latency_s" in win:
        lat = np.asarray(win["latency_s"]) * 1e3
        e2e["p50_ms"] = (float(np.percentile(lat, 50)), "ms")
        e2e["p99_ms"] = (float(np.percentile(lat, 99)), "ms")

    device = dict(device_info(devices), memory_peak_bytes=mem)
    out = dict(correct=correct, attempted=hi - lo, failed=hi - lo - committed)
    if not trace:
        names = [m["name"] for m in manifest["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
        out["metrics"] = {k: dict(value=e2e[k][0], unit=e2e[k][1])
                          for k in names}
    else:
        red = tracing.reduce(cap.events, spans, calls,
                             (win["t0"], win["t1"]), cap.t0)
        span_s = {}
        for name, t0, t1 in spans:
            span_s[name] = span_s.get(name, 0.0) + (t1 - t0)
        rec = dict(committed=committed, admitted=hi - lo, elapsed_s=elapsed,
                   batches=len(batches), spans_s=span_s,
                   call_s=sum(b - a for a, b in calls),
                   dispatches=dispatches, compiles=compiles, device=red,
                   work_bytes=roofline.window_bytes(arrs, hot_op, batches),
                   hbm_bytes_per_s=None if red is None else roofline.peaks(
                       device["kind"])["hbm_bytes_per_s"])
        out["metrics"] = {}
        for m in manifest["per_layer"]:
            if cell["name"] not in m["workloads"]:
                continue
            v = metric_reader(m["name"])(rec)
            if v is not None:
                out["metrics"][m["name"]] = dict(value=v, unit=m["unit"])
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = dict(device_ops=red["device_ops"],
                                    idle_gaps=red["idle_gaps"])
    out["device"] = device
    out["checks"] = {k: dict(value=v, limit=lim)
                     for k, (v, lim) in checks.items()}
    return out

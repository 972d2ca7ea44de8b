"""The reduction from a profiler trace and host spans to device numbers,
on small traces counted by hand and on one recorded on a TPU v5e."""
from bench_paths import BENCH

import json

import pytest

import tracing

OFF = 5e9 - 100.0 * 1e9          # host clock (s) -> trace clock (ns)
US = 1e3


def ev(plane, line, name, t0_us, t1_us):
    return (plane, line, name, 5e9 + t0_us * US, (t1_us - t0_us) * US)


def host(t_us):
    return 100.0 + t_us * 1e-6


TPU0 = "/device:TPU:0"
EVENTS = [
    ("/host:CPU", "python", tracing.WINDOW_MARK, 5e9, 1e6),
    ev(TPU0, tracing.OPS_LINE, "fusion.1", 100, 200),
    ev(TPU0, tracing.OPS_LINE, "fusion.2", 150, 300),   # overlaps the first
    ev(TPU0, tracing.OPS_LINE, "copy.3", 600, 700),
    ev(TPU0, tracing.MODULES_LINE, "jit_run(7)", 100, 300),
    ev(TPU0, tracing.MODULES_LINE, "jit_other(8)", 600, 700),
]
SPANS = [("classify", host(50), host(80)), ("dispatch", host(80), host(120)),
         ("drain", host(300), host(500))]
CALLS = [(host(50), host(800))]
WINDOW = (host(0), host(1000))


def test_one_chip_counted_by_hand():
    red = tracing.reduce(EVENTS, SPANS, CALLS, WINDOW, 100.0)
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(1000e-6)
    assert red["busy_s"] == pytest.approx(300e-6)          # [100, 300] + 100
    assert red["engine_s"] == pytest.approx(200e-6)        # jit_run only
    assert dict(red["device_ops"]) == pytest.approx(
        {"jit_run(7)": 200e-6, "jit_other(8)": 100e-6})
    # idle [0,100] [300,600] [700,1000] by what the host was doing
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"client": 250e-6, "classify": 30e-6, "dispatch": 20e-6,
         "drain": 200e-6, "unspanned": 200e-6})
    assert [k for k, _ in red["idle_gaps"]][0] == "client"


def test_two_chips_average_and_events_outside_the_window_are_clipped():
    tpu1 = "/device:TPU:1"
    evs = EVENTS + [ev(tpu1, tracing.OPS_LINE, "f", -500, 1500),
                    ev(tpu1, tracing.MODULES_LINE, "jit_run(9)", -500, 1500)]
    red = tracing.reduce(evs, SPANS, CALLS, WINDOW, 100.0)
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((300e-6 + 1000e-6) / 2)
    assert red["busy_total_s"] == pytest.approx(1300e-6)
    assert red["engine_s"] == pytest.approx(1200e-6)
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(700e-6 / 2)


def test_no_device_events_reduce_to_nothing():
    host_only = [e for e in EVENTS if not e[0].startswith("/device")]
    assert tracing.reduce(host_only, SPANS, CALLS, WINDOW, 100.0) is None



@pytest.mark.parametrize("name,engine", [
    ("jit_run(7)", True), ("jit_run", True), ("jit_run.3", True),
    ("jit_running(7)", False), ("jit__read_gather_impl(2)", False),
])
def test_only_the_fused_engines_count_as_engine_time(name, engine):
    evs = EVENTS[:4] + [ev(TPU0, tracing.MODULES_LINE, name, 100, 300)]
    red = tracing.reduce(evs, SPANS, CALLS, WINDOW, 100.0)
    assert red["engine_s"] == pytest.approx(200e-6 if engine else 0.0)


def test_a_trace_recorded_on_a_tpu_v5e():
    """The start of a SmallBank window traced on one TPU v5e chip
    (``data/trace_smallbank_v5e.json``): the chip's plane, its op and
    program lines and the engines' program names are the ones ``reduce``
    reads, and its numbers hold together."""
    rec = json.loads((BENCH / "tests" / "data" / "trace_smallbank_v5e.json")
                     .read_text())
    red = tracing.reduce([tuple(e) for e in rec["events"]],
                         [tuple(s) for s in rec["spans"]],
                         [tuple(c) for c in rec["calls"]],
                         tuple(rec["window"]), rec["mark_t0"])
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(
        rec["window"][1] - rec["window"][0])
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["engine_s"] < red["window_s"]
    assert all(name.startswith(tracing.ENGINE_MODULE + "(")
               for name, _ in red["device_ops"])
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])
    assert {"classify", "packet-build", "dispatch", "drain"} <= set(idle)

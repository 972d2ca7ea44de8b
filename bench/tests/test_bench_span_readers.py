"""The readers of the cluster's ``cold``, ``wal-send`` and ``scatter`` spans
and of the time no span covers, on records counted by hand; and the trace
reduction over engine programs named by mode (``jit_run.<mode>``) and the
new spans."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import pytest

import harness
import tracing

COMMITTED = 2000
SPANS_S = {"classify": 0.010, "packet-build": 0.020, "dispatch": 0.004,
           "drain": 0.030, "cold": 0.100, "wal-send": 0.008,
           "scatter": 0.016}


def rec(spans_s=SPANS_S, committed=COMMITTED, call_s=0.200):
    return dict(committed=committed, admitted=committed, spans_s=spans_s,
                call_s=call_s)


@pytest.mark.parametrize("metric,span", [
    ("cold_us_per_txn", "cold"),
    ("wal_send_us_per_txn", "wal-send"),
    ("scatter_us_per_txn", "scatter"),
])
def test_a_span_reader_reads_its_span_per_committed_txn(metric, span):
    read = harness.metric_reader(metric)
    assert read(rec()) == pytest.approx(SPANS_S[span] * 1e6 / COMMITTED)
    # a program without the span (the parent of this metric) reads nothing
    old = {k: v for k, v in SPANS_S.items() if k != span}
    assert read(rec(old)) is None
    assert read(rec(committed=0)) is None


def test_unattributed_is_the_call_time_less_every_span():
    read = harness.metric_reader("unattributed_us_per_txn")
    # 0.200 s of calls, 0.188 s in spans: 12 ms over 2,000 txns
    assert read(rec()) == pytest.approx(6.0)
    assert read(rec({})) is None
    assert read(rec(committed=0)) is None
    # never more than the old reader's four spans leave over
    unspanned = harness.metric_reader("unspanned_us_per_txn")(rec())
    assert unspanned == pytest.approx((0.200 - 0.064) * 1e6 / COMMITTED)
    assert read(rec()) <= unspanned


US = 1e3
TPU0 = "/device:TPU:0"


def ev(line, name, t0_us, t1_us):
    return (TPU0, line, name, 5e9 + t0_us * US, (t1_us - t0_us) * US)


def host(t_us):
    return 100.0 + t_us * 1e-6


def test_engines_named_by_mode_still_count_as_engine_time():
    evs = [("/host:CPU", "python", tracing.WINDOW_MARK, 5e9, 1e6),
           ev(tracing.OPS_LINE, "fusion.1", 100, 300),
           ev(tracing.OPS_LINE, "fusion.2", 400, 450),
           ev(tracing.OPS_LINE, "copy.3", 600, 700),
           ev(tracing.MODULES_LINE, "jit_run.serial(11)", 100, 300),
           ev(tracing.MODULES_LINE, "jit_run.affine(12)", 400, 450),
           ev(tracing.MODULES_LINE, "jit_other(8)", 600, 700)]
    spans = [("cold", host(0), host(100)), ("wal-send", host(300), host(350)),
             ("dispatch", host(350), host(400)),
             ("scatter", host(450), host(500))]
    red = tracing.reduce(evs, spans, [(host(0), host(800))],
                         (host(0), host(1000)), 100.0)
    assert red["engine_s"] == pytest.approx(250e-6)         # 200 + 50
    assert dict(red["device_ops"]) == pytest.approx(
        {"jit_run.serial(11)": 200e-6, "jit_run.affine(12)": 50e-6,
         "jit_other(8)": 100e-6})
    # idle [0,100] [300,400] [450,600] [700,1000] by host activity
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"cold": 100e-6, "wal-send": 50e-6, "dispatch": 50e-6,
         "scatter": 50e-6, "unspanned": 200e-6, "client": 200e-6})

"""The reductions from counters, spans and a trace to per-layer metrics,
each on a small recorded input."""

import pytest

from benchmarks import trace_reduce
from benchmarks.readers import (client_stat, counter_ratio, histogram,
                                span_self, trace_idle, trace_kernel)
from benchmarks.readers._common import parse_exposition

# Two `admin.metrics_text` answers of a controller, cut to what is read.
TEXT_A = """# TYPE ripplemq_produce_messages_total counter
ripplemq_produce_messages_total 1000
# TYPE ripplemq_settle_commit_wait_us histogram
ripplemq_settle_commit_wait_us_bucket{le="8191"} 7
ripplemq_settle_commit_wait_us_bucket{le="+Inf"} 10
ripplemq_settle_commit_wait_us_sum 50000
ripplemq_settle_commit_wait_us_count 10
ripplemq_engine_chain_rounds_sum 12
ripplemq_engine_chain_rounds_count 10
"""
TEXT_B = TEXT_A.replace("_total 1000", "_total 6120").replace(
    "_us_sum 50000", "_us_sum 350000").replace(
    "_us_count 10", "_us_count 40").replace("rounds_sum 12", "rounds_sum 52")


def run_with(**kw):
    base = {"t0_ns": 100, "t1_ns": 200, "snapshots": [
        (90, parse_exposition(TEXT_A)), (101, parse_exposition(TEXT_A)),
        (199, parse_exposition(TEXT_B)), (260, {})]}
    base.update(kw)
    return base


def test_histogram_delta_mean():
    v = histogram.read({"name": "settle.commit_wait_us", "scale": 0.001},
                       run_with())
    assert v == pytest.approx((350000 - 50000) / 30 / 1000)
    assert histogram.read({"name": "no.such_us"}, run_with()) is None


def test_counter_ratio_over_the_window():
    v = counter_ratio.read(
        {"numerator": {"counter": "produce.messages"},
         "denominator": {"histogram_sum": "engine.chain_rounds"}}, run_with())
    assert v == pytest.approx(5120 / 40)


# One sampled produce: the client's call (10 ms) holds one rpc attempt
# (9 ms); the broker, on another clock, spends 8 ms in rpc.recv, of which
# 1 ms is admission and 5 ms the round's two overlapping settle stages.
SPANS = [
    dict(kind="client.produce", trace=7, span=1, parent=0, t0=1.000, dur_us=10000, proc="p"),
    dict(kind="client.rpc", trace=7, span=2, parent=1, t0=1.0005, dur_us=9000, proc="p"),
    dict(kind="rpc.recv", trace=7, span=3, parent=2, t0=500.0, dur_us=8000, proc="b0"),
    dict(kind="admission", trace=7, span=4, parent=3, t0=500.0001, dur_us=1000, proc="b0"),
    dict(kind="settle.commit_wait", trace=7, span=5, parent=3, t0=500.002, dur_us=3000, proc="b0"),
    dict(kind="settle.persist", trace=7, span=6, parent=3, t0=500.004, dur_us=3000, proc="b0"),
    # a consume trace must not be counted among the produce calls
    dict(kind="client.consume", trace=9, span=20, parent=0, t0=2.0, dur_us=700, proc="c"),
    dict(kind="client.rpc", trace=9, span=21, parent=20, t0=2.0, dur_us=600, proc="c"),
]


def test_span_self_time_never_compares_two_clocks():
    selfs = span_self.self_times(SPANS)
    assert selfs[1] == 1000          # 10 ms - its 9 ms child, same clock
    assert selfs[2] == 1000          # 9 ms - 8 ms served elsewhere: by length
    assert selfs[3] == 8000 - 1000 - 5000  # children's union, not their sum
    client = span_self.read({"kinds": ["client.produce", "client.rpc"],
                             "scale": 0.001}, {"spans": SPANS})
    server = span_self.read({"kinds": ["rpc.recv", "admission"],
                             "scale": 0.001}, {"spans": SPANS})
    assert client == pytest.approx(2.0)
    assert server == pytest.approx(2.0 + 1.0)
    assert span_self.read({"kinds": ["rpc.recv"]}, {"spans": []}) is None


# A recorded trace, as trace_reduce.extract hands it on: 10 ms window, one
# device, two launches of the round program, ops that overlap.
PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [("%fusion.1 = pred[8]", 1e6, 1e6),
                    ("%_step_sparse_j.1 = u8[3,8,4,8,128]{4,3,2,1,0} custom-call(x)", 1.5e6, 1e6),
                    ("%_gf_matmul_jit.1 = u8[2,64,512]{2,1,0} custom-call(y)", 6.2e6, 5e5),
                    ("%fusion.1 = pred[8]", 6e6, 1e6)],
        "XLA Modules": [("jit__step_sparse_j(1)", 1e6, 1.5e6),
                        ("jit__step_sparse_j(1)", 6e6, 1e6)],
    },
    "/host:CPU": {"python": [("PjitFunction(_step_sparse_j)", 2.6e6, 3.3e6),
                             ("short", 3e6, 1e5)]},
}


def test_idle_share_and_kernel_time_from_a_recorded_trace():
    s = trace_reduce.summarize(PLANES, window_s=0.010)
    assert s["busy_s"] == pytest.approx(0.0025)  # 1.0-2.5 ms and 6-7 ms
    assert s["devices_traced"] == 1
    assert s["idle_gaps"][0][0] == "PjitFunction(_step_sparse_j)"
    assert s["idle_gaps"][0][1] == pytest.approx(0.0035)
    assert s["device_ops"][0] == ["%fusion.1 = pred[8]", pytest.approx(0.002)]
    run = {"trace": s}
    assert trace_idle.read({}, run) == pytest.approx(75.0)
    assert trace_kernel.read({"line": "modules", "match": "^jit__step",
                              "stat": "mean_ms"}, run) == pytest.approx(1.25)
    assert trace_kernel.read({"line": "modules", "match": "^jit_other",
                              "stat": "mean_ms"}, run) is None
    assert trace_idle.read({}, {"trace": None}) is None


def test_roofline_share_counts_bytes_from_shapes():
    s = trace_reduce.summarize(PLANES, window_s=0.010)
    s.update(start_ns=100, stop_ns=200)
    run = run_with(trace=s, peaks={"hbm_bytes_per_s": 819e9}, config={
        "cluster": {"engine": {"slot_bytes": 128, "replicas": 3}}})
    v = trace_kernel.read(
        {"line": "ops", "match": r"^%_step\S* = u8\[[0-9,]+\]\S* custom-call\(",
         "stat": "roofline_pct", "bytes": "append_rows"}, run)
    rows = 5120 * (100 / 98)  # counter delta, scaled to the traced window
    assert v == pytest.approx(100 * rows * 128 * 4 / 819e9 / 0.001)


def test_client_stat_uses_the_percentile_rule():
    run = {"client": {"late_ms": list(range(2000)), "ack_ms": []}}
    assert client_stat.read({"series": "late_ms", "pct": 99}, run) == 1979.0
    assert client_stat.read({"series": "ack_ms"}, run) is None

"""The per-layer metrics of PR 24 (the host stages and the span ring's
loss counters), each loaded from its file under layer_metrics/ and read
with its reader from a recorded pair of `admin.metrics_text` answers: the
controller of a CPU rehearsal of omb-1024p-100b.steady, cut to the series
these metrics read (data/metrics_text_a.txt early in the window,
data/metrics_text_b.txt at its end). By hand, like the rest of this
directory."""

import importlib
import json
import os

import pytest

from benchmarks.readers._common import parse_exposition
from run import metrics_for

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")

# metric -> the registry series it is read from (a histogram's window
# mean, or a ratio of two counters' deltas)
NEW_METRICS = {
    "spans.lost_share": ("spans.overwritten", "spans.recorded"),
    "server.rpc_queue_ms": "rpc.queue_wait_us",
    "dataplane.queue_wait_ms": "produce.queue_wait_us",
    "dataplane.drain_ms": "round.drain_us",
    "dataplane.lock_wait_ms": "round.lock_wait_us",
    "dataplane.launch_ms": "engine.dispatch_us",
    "saturate.drain_ms": "round.drain_us",
    "saturate.launch_ms": "engine.dispatch_us",
    "consume.serve_ms": "consume.ack_us",
    "consume.read_ms": "read.serve_us",
    "store.seal_encode_ms": "seal.rs_encode_us",
}


def recorded(name: str) -> dict:
    with open(os.path.join(HERE, "data", f"metrics_text_{name}.txt")) as f:
        return parse_exposition(f.read())


def run_with(a: dict, b: dict) -> dict:
    return {"t0_ns": 100, "t1_ns": 200,
            "snapshots": [(101, a), (199, b)]}


def load(metric: str) -> dict:
    with open(os.path.join(LAYER_METRICS, f"{metric}.json")) as f:
        return json.load(f)


def read(metric: str, run: dict):
    m = load(metric)
    reader = importlib.import_module(
        f"benchmarks.readers.{m['reader']['kind']}")
    return reader.read(m["reader"]["args"], run)


def series(name: str, suffix: str) -> str:
    return "ripplemq_" + name.replace(".", "_") + suffix


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_reads_the_recorded_pair(metric):
    a, b = recorded("a"), recorded("b")
    got = read(metric, run_with(a, b))
    src = NEW_METRICS[metric]
    if isinstance(src, tuple):  # ratio of two counters' deltas
        num, den = (b[series(s, "_total")] - a[series(s, "_total")]
                    for s in src)
        assert den > 0
        assert got == pytest.approx(num / den)
        assert got == 0.0  # the rehearsal's ring lost nothing
    else:  # window mean of a `_us` histogram, in ms
        ds = b[series(src, "_sum")] - a[series(src, "_sum")]
        dn = b[series(src, "_count")] - a[series(src, "_count")]
        assert dn > 0
        assert got == pytest.approx(ds / dn / 1000.0)
        assert got > 0


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_is_left_out_where_the_program_lacks_it(metric):
    """Against the parent commit the program has none of the new series
    (only consume.ack_us is older): the reader returns None, it does not
    raise, and the harness leaves the metric out of the line."""
    old = {k: v for k, v in recorded("b").items()
           if k.startswith("ripplemq_consume_ack_us")}
    got = read(metric, run_with(old, old))
    assert got is None
    if metric != "consume.serve_ms":
        early = {k: v for k, v in recorded("a").items()
                 if k.startswith("ripplemq_consume_ack_us")}
        assert read(metric, run_with(early, old)) is None


def test_new_metric_files_match_their_benchmark_entries():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    # cell -> the metrics it reports (since PR 46 the cell's own list)
    lists = {w["name"]: [m["name"] for m in metrics_for(w["name"])]
             for w in bench["workloads"]}
    for metric in NEW_METRICS:
        m, e = load(metric), entries[metric]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert m[key] == e[key], (metric, key)
        assert "workloads" not in m  # a file says what is read, not where
        assert e["workloads"] == [c for c in lists if metric in lists[c]]
        # every cell that reports a metric reports the end-to-end metric
        # it moves
        assert e["workloads"] and set(e["workloads"]) \
            <= set(e2e[m["moves"]]["workloads"])


def test_round_stage_sums_close_over_the_recorded_pair():
    """The five step-thread stages partition that thread's time: between
    the two recorded answers (35 sampler ticks of 0.25 s plus the RPCs'
    own time) their sums grew by the same stretch of time, whatever the
    mix of stages in it."""
    a, b = recorded("a"), recorded("b")
    grown = sum(b[series(s, "_sum")] - a[series(s, "_sum")]
                for s in ("round.idle_us", "round.coalesce_us",
                          "round.drain_us", "round.lock_wait_us",
                          "engine.dispatch_us"))
    assert 35 * 0.25e6 <= grown <= 35 * 0.25e6 * 1.25

"""The rack-aware deployment's own pieces (`omb-16p-1kb-rack`, PR 47):
the configuration held against its base `omb-16p-1kb` key by key, the
cell's consumer against the configuration's `consumer_fetch`, the cell's
traffic against its control `omb-16p-1kb.tail`, the tie to
`BENCHMARK.json`, the span reader kind on recorded span records, and a
whole rehearsal run (CPU backend, the files' rehearsal sizes; ~30 s)."""

import json
import os

import pytest

from readers import span_stat
from run import Run, metrics_for

BASE, CONFIG = "omb-16p-1kb", "omb-16p-1kb-rack"
CONTROL, CELL = "omb-16p-1kb.tail", "omb-16p-1kb-rack.tail"
HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
RACK = ["rack.follower_served_share", "rack.floor_lag_ms",
        "rack.wake_late_ms", "rack.serve_ms", "rack.requests_per_delivery",
        "rack.floor_push_ms"]


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def test_the_configuration_differs_from_its_base_in_the_listed_keys():
    cfg, base = load("configs", CONFIG), load("configs", BASE)
    assert set(cfg) == set(base)
    for key in set(cfg) - {"name", "source", "source_note", "deployment",
                           "assumed", "guarantees", "cluster"}:
        assert cfg[key] == base[key], key  # reduced, compiles, rehearsal
    dep, bdep = cfg["deployment"], base["deployment"]
    assert set(dep) - set(bdep) == {"racks"}
    for key in bdep:
        if key != "consumer_fetch":
            assert dep[key] == bdep[key], key
    racks = dep["racks"]
    assert sorted(racks) == ["0", "1", "2"] and len(set(racks.values())) == 3
    assert dep["consumer_fetch"] == dict(
        bdep["consumer_fetch"], client_rack=racks["1"],
        replica_selector="RackAwareReplicaSelector")
    cl, bcl = cfg["cluster"], base["cluster"]
    assert set(cl) - set(bcl) == {"follower_reads", "broker_racks",
                                  "follower_page_cache_bytes"}
    for key, value in bcl.items():  # engine block and all, to the letter
        assert cl[key] == value, key
    assert cl["follower_reads"] is True and cl["broker_racks"] == racks
    # how many seconds of the cell's stream a standby can hand out
    rate = load("workloads", CELL)["producers"]["params"]["rate_msgs_per_s"]
    seconds = cl["follower_page_cache_bytes"] / (
        rate * cl["engine"]["slot_bytes"])
    assert 10 < seconds < 13
    assert f"{seconds:.1f} s" in cfg["assumed"]["follower_page_cache_bytes"]
    assert set(cfg["assumed"]) - set(base["assumed"]) == {
        "racks", "consumer rack", "follower_page_cache_bytes"}
    for key, value in base["assumed"].items():
        assert cfg["assumed"][key] == value, key
    g, bg = cfg["guarantees"], base["guarantees"]
    assert set(g) - set(bg) == {"follower reads"}
    for key, value in bg.items():
        assert g[key] == value, key
    for word in ("only settled rows", "high watermark", "each once",
                 "committed offsets live with the leader"):
        assert word in g["follower reads"], word
    assert len(cfg["source"]) <= 200
    for word in ("1-topic-16-partitions-1kb.yaml", "driver-kafka/kafka.yaml",
                 "KIP-392", "broker.rack", "RackAwareReplicaSelector",
                 "client.rack"):
        assert word in cfg["source"], word
    assert "from memory" in cfg["source_note"]
    assert cfg["reduced"] == ["producerRate"]
    assert cfg["steady_state_compiles"] == {}


def test_the_cluster_file_the_harness_writes_parses():
    """`run.boot` lays `brokers` and `topics` over `cluster`: the racks
    are a key of their own and reach the program's configuration."""
    from ripplemq_tpu.metadata.cluster_config import parse_cluster_config

    cfg = load("configs", CONFIG)
    raw = dict(cfg["cluster"], topics=cfg["deployment"]["topics"],
               brokers=[{"id": i, "host": "127.0.0.1", "port": 9000 + i}
                        for i in range(cfg["deployment"]["brokers"])])
    parsed = parse_cluster_config(raw)
    assert parsed.follower_reads and parsed.standby_count == 2
    assert dict(parsed.broker_racks) == {
        int(b): r for b, r in cfg["deployment"]["racks"].items()}


def test_the_cell_is_its_control_but_for_where_the_fetch_is_served():
    cfg, cell, ctl = (load("configs", CONFIG), load("workloads", CELL),
                      load("workloads", CONTROL))
    fetch = cfg["deployment"]["consumer_fetch"]
    # the cell's consumers.client, read against consumer_fetch
    assert cell["consumers"]["client"] == {
        "long_poll_s": fetch["max_wait_ms"] / 1000,
        "client_rack": fetch["client_rack"]}
    assert "trace_sample_n" not in cell["consumers"]["client"]
    assert "trace_sample_n" not in cell
    for key in ("traffic", "chips", "warm_s", "drain_limit_s", "delivery",
                "subscription", "producers", "end_to_end", "rehearsal"):
        assert cell[key] == ctl[key], key
    assert cell["consumers"]["processes"] == ctl["consumers"]["processes"]
    assert cell["consumers"]["params"] == ctl["consumers"]["params"]
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert len(cell["why"]) <= 200
    knee = cell["knee_sweep"]
    rate = cell["producers"]["params"]["rate_msgs_per_s"]
    assert rate == knee["rate_chosen_msgs_per_s"]
    assert rate <= 0.6 * knee["knee_msgs_per_s"]
    assert f"{rate:,} msgs/s" in cfg["deployment"]["chips"]


def test_metrics_for_resolves_and_benchmark_json_lists_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg, cell, ctl = (load("configs", CONFIG), load("workloads", CELL),
                      load("workloads", CONTROL))
    names = [m["name"] for m in metrics_for(CELL)]
    assert names == cell["layer_metrics"] and len(names) == 25
    assert names[-6:] == RACK
    # the control's list less what reads the controller's consume path
    assert names[:-6] == [n for n in ctl["layer_metrics"]
                          if not n.startswith(("consume.", "tail."))]
    entry = bench["configs"][-1]
    assert (entry["name"], entry["source"], entry["reduced"], entry["file"]
            ) == (CONFIG, cfg["source"], cfg["reduced"],
                  f"benchmarks/configs/{CONFIG}.json")
    assert bench["workloads"][-1] == {
        k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}
    for m in bench["end_to_end"]:
        if m["name"] in ("produce_ack_p50_ms", "deliver_p50_ms"):
            assert m["workloads"][-1] == CELL
        elif "workloads" in m:
            assert CELL not in m["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == RACK
    for name in names:
        m = load("layer_metrics", name)
        assert listed[name]["workloads"][-1] == CELL, name
        assert {k: m[k] for k in listed[name] if k != "workloads"} \
            == {k: v for k, v in listed[name].items() if k != "workloads"}
        assert m["moves"] in cell["end_to_end"], name
    for name in RACK:
        assert listed[name]["workloads"] == [CELL]
        assert "workloads" not in load("layer_metrics", name)
    assert sorted(n for n, m in listed.items() if CELL in m["workloads"]) \
        == sorted(names)


def span(kind: str, end_s: float, dur_us: int, **fields) -> dict:
    return dict(fields, kind=kind, t0=end_s - dur_us / 1e6, dur_us=dur_us,
                trace=1, span=1, parent=0, proc="broker-1", seq=0)


def test_the_span_reader_on_recorded_span_records():
    run = {"t0_ns": 10_000_000_000, "t1_ns": 30_000_000_000, "spans": [
        span("follower.fetch", 9.9, 400, served=1, refused=0),   # before
        span("follower.fetch", 11.0, 40_000, served=1, refused=0),
        span("follower.park", 11.0, 39_000),
        span("follower.fetch", 12.0, 21_000, served=2, refused=1),
        span("follower.park", 11.99, 10_000),
        span("follower.park", 12.0, 10_000),
        span("follower.fetch", 13.0, 500_000, served=0, refused=0),
        span("follower.park", 13.0, 499_700),
        span("follower.wake", 12.0, 300), span("follower.wake", 11.0, 500),
        span("follower.floor", 12.0, 2_000), span("follower.floor", 13.0, 4_000),
        span("follower.floor", 31.0, 90_000),                    # after
        span("rpc.recv", 12.0, 77)]}
    want = {"rack.follower_served_share": 3 / 4,
            "rack.floor_lag_ms": 3.0, "rack.wake_late_ms": 0.4,
            "rack.serve_ms": (561_000 - 558_700) / 3 / 1000,
            "rack.requests_per_delivery": 3 / 2}
    for name, value in want.items():
        args = load("layer_metrics", name)["reader"]
        assert args["kind"] == "span_stat"
        assert span_stat.read(args["args"], run) == pytest.approx(value), name
        # a program without the spans (the parent): nothing, no raise
        assert span_stat.read(args["args"], dict(run, spans=[
            r for r in run["spans"] if r["kind"] == "rpc.recv"])) is None
        assert span_stat.read(args["args"], dict(run, spans=None)) is None


def test_a_sound_rehearsal_is_correct():
    run = Run(CELL, 4300000047, 3.0, False, rehearse=True)
    out = run.run()
    numbers = {name: value for name, value, _ in run.numbers}
    assert out["correct"] is True and out["failed"] == 0
    assert numbers["replicas.scanned"] == 3 and out["attempted"] > 0
    for name, value in numbers.items():
        if name.startswith("delivery.") and name != "delivery.subscriptions":
            assert value == 0, name
    assert set(out["metrics"]) == {"produce_ack_p50_ms", "deliver_p50_ms",
                                   "setup_s"}
    m = out["metrics"]
    ack, delivered = (m[k]["value"] for k in ("produce_ack_p50_ms",
                                              "deliver_p50_ms"))
    # woken by the follower's floor, not by a poll schedule (see
    # test_tail.py for why the gap is held only under a sound ack)
    assert ack >= 250.0 or delivered < ack + 60.0

"""The tailing deployment's own pieces (`omb-16p-1kb`, PR 43): the
configuration's arithmetic and its guarantees against its sibling's, the
cell's consumer held against the configuration's `consumer_fetch`, the
cell's parameters, its per-layer metric files, and whole rehearsal runs
(CPU backend, the files' rehearsal sizes) of `omb-16p-1kb.tail` - sound,
and with one delivered byte flipped underneath. About 25 s each of the
two runs."""

import json
import os

from run import Run

CONFIG, CELL = "omb-16p-1kb", "omb-16p-1kb.tail"
HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def test_the_configuration_says_what_it_holds():
    cfg, sib = load("configs", CONFIG), load("configs", "omb-100p-1kb")
    eng, dep = cfg["cluster"]["engine"], cfg["deployment"]
    assert dep["message_bytes"] == 1024 and eng["slot_bytes"] == 1152
    assert dep["topics"] == [{"name": "bench", "partitions": 16,
                              "replication_factor": 3}]
    assert (dep["brokers"], dep["subscriptions"], dep["producers"],
            dep["consumers"]) == (3, 1, 1, 1)
    # PR 37's measured block, to the letter
    assert (eng["partitions"], eng["slots"], eng["max_batch"],
            eng["read_batch"]) == (16, 36096, 512, 256)
    ring = 3 * eng["partitions"] * (eng["slots"] + eng["max_batch"]) \
        * eng["slot_bytes"]
    assert ring == 2_024_275_968 and f"{ring:,} B" in dep["chips"]
    assert 2 * 8 * eng["max_batch"] * eng["slot_bytes"] <= 16 << 20
    assert cfg["guarantees"] == sib["guarantees"]  # word for word
    # every cluster key of the 1 KB deployment but the engine's shape
    for key, value in sib["cluster"].items():
        if key != "engine":
            assert cfg["cluster"][key] == value, key
    for key, value in sib["cluster"]["engine"].items():
        if key not in ("partitions", "slots"):
            assert eng[key] == value, key
    assert cfg["reduced"] == ["producerRate"]
    assert cfg["steady_state_compiles"] == {}
    assert len(cfg["source"]) <= 200
    for word in ("1-topic-16-partitions-1kb.yaml", "driver-kafka/kafka.yaml",
                 "fetch.min.bytes=1", "fetch.max.wait.ms=500"):
        assert word in cfg["source"], word
    assert "from memory" in cfg["source_note"]
    assert set(cfg["assumed"]) >= {"engine.partitions", "engine.slots",
                                   "engine.slot_bytes", "engine.max_batch",
                                   "engine.read_batch"}


def test_the_cell_reads_the_way_the_configuration_says():
    cfg, cell = load("configs", CONFIG), load("workloads", CELL)
    dep, eng = cfg["deployment"], cfg["cluster"]["engine"]
    fetch = dep["consumer_fetch"]
    assert fetch == {"min_bytes": 1, "max_wait_ms": 500}
    c = cell["consumers"]
    # fetch.max.wait.ms is the client's long_poll_s; fetch.min.bytes=1 is
    # what the park does (answered at the first settled row), no option
    assert c["client"] == {"long_poll_s": fetch["max_wait_ms"] / 1000}
    assert (c["processes"], c["params"]["threads"]) == (
        dep["consumers"], 1)
    assert (c["params"]["prefetch"], c["params"]["poll_interval_s"],
            c["params"]["max_messages"]) == (1, 0.0, eng["read_batch"])
    assert "idle_sleep_s" not in c["params"]  # the harness's default
    subs = cell.get("subscriptions", [cell.get("subscription")])
    assert len(subs) == dep["subscriptions"] == 1
    p = cell["producers"]
    assert (p["processes"], p["generator"], p["params"]["batch"],
            p["params"]["senders"]) == (dep["producers"], "open_loop", 128, 32)
    assert (cell["warm_s"], cell["drain_limit_s"], cell["delivery"],
            cell["chips"], cell["config"], cell["traffic"]) == (
        8.0, 15.0, "whole", 1, CONFIG, "tail")
    assert cell["end_to_end"] == ["produce_ack_p50_ms", "deliver_p50_ms",
                                  "setup_s"]
    assert len(cell["why"]) <= 200
    knee = cell["knee_sweep"]
    rate = p["params"]["rate_msgs_per_s"]
    assert rate == knee["rate_chosen_msgs_per_s"]
    assert 0.5 <= rate / knee["knee_msgs_per_s"] <= 0.6
    # a partition's produce period is no whole number of 10 ms
    period_ms = 16 * p["params"]["batch"] / rate * 1000
    assert 0.5 < period_ms % 10 < 9.5, period_ms
    assert f"{rate:,} msgs/s" in dep["chips"]


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg, cell = load("configs", CONFIG), load("workloads", CELL)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == \
        cfg["reduced"] and entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    w = bench["workloads"][-1]
    assert w == {k: cell[k] for k in ("name", "config", "traffic", "chips",
                                      "why")}
    for m in bench["end_to_end"]:
        if m["name"] in ("produce_ack_p50_ms", "deliver_p50_ms"):
            assert m["workloads"][-1] == CELL
    # what the cell reads per layer is its own list (PR 46): the six of
    # the parked fetch under the cell's prefix, the shared path under
    # the names every cell reads it by, and the three the cap of 128 had
    # taken in PR 43's check back as list entries
    names = cell["layer_metrics"]
    assert len(names) == len(set(names)) == 27
    assert sorted(n for n in names if n.startswith("tail.")) == [
        "tail.expired_share", "tail.fetch_parts_per_request", "tail.park_ms",
        "tail.parked_share", "tail.requests_per_delivery",
        "tail.wake_late_ms"]
    assert {"dataplane.drain_ms", "dataplane.stage_fill", "store.fsync_ms",
            "host.interp_wake_late_ms", "host.plane_lock_wait_rpc_ms",
            "lat.append_roofline_pct", "client.deliver_p99_ms"} <= set(names)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in names:
        m = load("layer_metrics", name)
        assert CELL in listed[name]["workloads"], name
        assert {k: m[k] for k in listed[name] if k != "workloads"} \
            == {k: v for k, v in listed[name].items() if k != "workloads"}
        assert m["moves"] in cell["end_to_end"], name
    assert sorted(n for n, m in listed.items() if CELL in m["workloads"]) \
        == sorted(names)


def run_cell(**kw):
    run = Run(CELL, 4300000033, 3.0, False, rehearse=True, **kw)
    out = run.run()
    return out, {name: value for name, value, _ in run.numbers}


def test_a_sound_rehearsal_is_correct():
    out, numbers = run_cell()
    assert out["correct"] is True and out["failed"] == 0
    assert numbers["replicas.scanned"] == 3 and out["attempted"] > 0
    assert numbers["delivery.subscriptions"] == 1
    assert set(out["metrics"]) == {"produce_ack_p50_ms", "deliver_p50_ms",
                                   "setup_s"}
    # the consumer is woken, it does not poll on a schedule: on the CPU,
    # at rehearsal size, delivery is within a few ms of the ack (~20 ms).
    # A machine too busy for the rehearsal's rate (acks of seconds, the
    # senders late) says nothing of the wake: the gap is held only where
    # the ack itself is sound.
    m = out["metrics"]
    ack, delivered = (m[k]["value"] for k in ("produce_ack_p50_ms",
                                              "deliver_p50_ms"))
    assert ack >= 250.0 or delivered < ack + 60.0


def test_a_flipped_byte_in_a_delivered_message_is_not_correct():
    out, numbers = run_cell(fault="flip_delivered")
    assert out["correct"] is False and numbers["delivery.differ"] > 0
    assert out["delivery.differ"] == numbers["delivery.differ"]

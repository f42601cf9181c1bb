"""The 1 KB deployment's own pieces (`omb-100p-1kb`, PR 33): the payload
and the reference at 1,024 B, a flipped byte deep in a 1 KB body caught by
the comparison, the configuration's arithmetic, and whole rehearsal runs
(CPU backend, the files' rehearsal sizes) of `omb-100p-1kb.steady` - sound,
and with one delivered byte flipped underneath. About 25 s each of the
two runs."""

import json
import os

import numpy as np

from benchmarks import payload
from benchmarks.reference_log import RECORD, ReferenceLog, compare_all
from run import Run

CELL = "omb-100p-1kb.steady"
HERE = os.path.dirname(__file__)
SEED, SIZE, STREAMS = 2147483693, 1024, 3


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def ref_and_sound():
    rows = [(s, 1, call * 128, 128, 50_000 + call, call * 128, 0, 0)
            for s in range(STREAMS) for call in range(2)]
    ref = ReferenceLog(SEED, SIZE, np.array(rows, dtype=RECORD), STREAMS)
    return ref, {s: ref.stream(s).tobytes() for s in range(STREAMS)}


def test_a_1kb_message_is_head_plus_1008_pool_bytes_and_all_distinct():
    pool = payload.make_pool(SEED)
    block = payload.build(pool, 2, 1, np.arange(256), 77, SIZE)
    assert block.shape == (256, SIZE)
    off = int(payload.body_offsets(2, 1, 5, SIZE))
    assert off + SIZE - payload.HEAD <= payload.POOL_BYTES
    assert block[5, payload.HEAD:].tobytes() == pool[
        off:off + SIZE - payload.HEAD].tobytes()
    assert len({r.tobytes() for r in block}) == 256
    assert (payload.stamps_of(block) == 77).all()


def test_one_flipped_byte_in_a_1kb_body_is_caught():
    ref, got = ref_and_sound()
    assert ref.total == STREAMS * 256
    ok = compare_all(ref, got, prefix_ok=False)
    assert (ok["differ"], ok["missing"], ok["extra"]) == (0, 0, 0)
    b = bytearray(got[2])
    b[200 * SIZE + 1000] ^= 0x01  # byte 1,000 of message 200: deep in the body
    got[2] = bytes(b)
    bad = compare_all(ref, got, prefix_ok=False)
    assert bad["differ"] == 1 and bad["bad_streams"] == [2]


def test_the_configuration_says_what_it_holds():
    cfg, cell = load("configs", "omb-100p-1kb"), load("workloads", CELL)
    sib = load("configs", "omb-1024p-100b")
    eng, dep = cfg["cluster"]["engine"], cfg["deployment"]
    assert dep["message_bytes"] == 1024 and eng["slot_bytes"] == 1152
    assert eng["slot_bytes"] % 128 == 0 and eng["slot_bytes"] - 8 >= 1024
    assert (eng["slot_bytes"] - 128) - 8 < 1024  # the smallest that holds it
    assert dep["topics"] == [{"name": "bench", "partitions": 100,
                              "replication_factor": 3}]
    assert eng["partitions"] >= 100 and eng["partitions"] % 8 == 0
    ring = 3 * eng["partitions"] * (eng["slots"] + eng["max_batch"]) \
        * eng["slot_bytes"]
    assert f"{ring:,} B" in dep["chips"] and 1.9e9 < ring < 2.1e9
    assert 2 * 8 * eng["max_batch"] * eng["slot_bytes"] <= 16 << 20
    assert cfg["guarantees"] == sib["guarantees"]  # to the letter
    for key in ("standby_count", "replication", "durability", "segment_bytes",
                "host_workers", "coalesce_s"):
        assert cfg["cluster"][key] == sib["cluster"][key], key
    assert cfg["reduced"] == [] and cfg["steady_state_compiles"] == {}
    assert len(cfg["source"]) <= 200 and "1-topic-100-partitions-1kb" in \
        cfg["source"] and "driver-kafka/kafka.yaml" in cfg["source"]
    p = cell["producers"]
    assert (p["processes"], p["generator"], p["params"]["batch"],
            p["params"]["senders"]) == (4, "open_loop", 128, 32)
    assert p["params"]["batch"] % 8 == 0 <= eng["max_batch"]
    c = cell["consumers"]
    assert (c["processes"], c["params"]["threads"], c["params"]["prefetch"],
            c["params"]["max_messages"]) == (4, 8, 1, eng["read_batch"])
    assert cell["delivery"] == "whole" and cell["chips"] == 1
    knee = cell["knee_sweep"]
    assert p["params"]["rate_msgs_per_s"] == knee["rate_chosen_msgs_per_s"]
    # the issue's fallback, 0.6 x the knee the siblings' rule reads, and
    # NOT a whole number of calls a second a partition's poll interval
    # divides (the file says why it is 0.594 x)
    assert 0.58 <= knee["rate_chosen_msgs_per_s"] / knee["knee_msgs_per_s"] \
        <= 0.6
    per_partition_s = 100 * p["params"]["batch"] / p["params"]["rate_msgs_per_s"]
    cycles = per_partition_s / c["params"]["poll_interval_s"]
    assert 0.05 < cycles % 1 < 0.95


def run_cell(**kw):
    run = Run(CELL, 4000000033, 3.0, False, rehearse=True, **kw)
    out = run.run()
    return out, {name: value for name, value, _ in run.numbers}


def test_a_sound_rehearsal_is_correct():
    out, numbers = run_cell()
    assert out["correct"] is True and out["failed"] == 0
    assert numbers["replicas.scanned"] == 3 and out["attempted"] > 0
    assert set(out["metrics"]) == {"produce_ack_p50_ms", "deliver_p50_ms",
                                   "setup_s"}


def test_a_flipped_byte_in_a_delivered_1kb_message_is_not_correct():
    out, numbers = run_cell(fault="flip_delivered")
    assert out["correct"] is False and numbers["delivery.differ"] > 0
    assert out["delivery.differ"] == numbers["delivery.differ"]

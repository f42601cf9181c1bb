"""More than one subscription a cell (PR 37): who consumes what, the
reference held against every subscription, the DRAIN order with two of
them, the cell's options for the program's consumer client, and whole
rehearsal runs (CPU backend, the files' rehearsal sizes) of
`omb-100p-1kb.steady` - as its files have it, whose line gains one
compared key, and read by four subscriptions (no accepted cell has more
than one: the test lays them on the files it loads), sound and with one
subscription one message short. The three runs take about 25 s each."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import run as bench_run
from benchmarks import child
from benchmarks.reference_log import (RECORD, ReferenceLog,
                                      compare_subscriptions)
from benchmarks.tests.test_drain import (BATCH, SIZE, FakeClient, Harness,
                                         batch)
from control import caught, control_run

KB = "omb-100p-1kb.steady"
SEED, STREAMS = 2147483693, 3


# ------------------------------------------------------------- who has what
@pytest.mark.parametrize("n_subs", [1, 2, 16])
@pytest.mark.parametrize("nprocs", [1, 2, 3, 4])
@pytest.mark.parametrize("n_streams", [1, 6, 16])
def test_every_subscription_and_stream_has_exactly_one_thread(
        n_subs, nprocs, n_streams):
    most = max(len(child.hosted(n_subs, i, nprocs)) for i in range(nprocs))
    for threads in {most, most + 1, 8, 16, 17} - set(range(most)):
        owners: dict = {}
        for i in range(nprocs):
            entries = child.hosted(n_subs, i, nprocs)
            for tid in range(threads):
                e, own = child.thread_share(entries, tid, threads, n_streams)
                for s in own:
                    owners.setdefault((entries[e][0], s), []).append((i, tid))
        assert sorted(owners) == [(q, s) for q in range(n_subs)
                                  for s in range(n_streams)]
        assert all(len(v) == 1 for v in owners.values())


def test_one_subscription_is_sliced_as_it_always_was():
    """The accepted cells: process i takes streams i::nprocs, thread t of
    it every `threads`-th of those."""
    for nprocs, threads, n in ((4, 8, 1024), (4, 8, 100), (2, 3, 6)):
        for i in range(nprocs):
            entries = child.hosted(1, i, nprocs)
            assert entries == [(0, i, nprocs)]
            for tid in range(threads):
                assert child.thread_share(entries, tid, threads, n) == (
                    0, list(range(i, n, nprocs))[tid::threads])


def test_sixteen_subscriptions_on_four_by_eight_threads_are_two_threads_each():
    seen = set()
    for i in range(4):
        entries = child.hosted(16, i, 4)
        assert entries == [(q, 0, 1) for q in range(i, 16, 4)]
        for tid in range(8):
            e, own = child.thread_share(entries, tid, 8, 16)
            assert len(own) == 8
            seen.add((entries[e][0], tuple(own)))
    assert seen == {(q, tuple(range(k, 16, 2)))
                    for q in range(16) for k in (0, 1)}


def lay_on(monkeypatch, cell_over: dict, deployment_over: dict = {}) -> None:
    """What `run.load_json` reads from now on: every cell file without
    its subscription and with `cell_over` laid on it, every configuration
    with `deployment_over` on its deployment."""
    real = bench_run.load_json

    def load(*parts):
        out = real(*parts)
        if parts[0] == "workloads":
            out.pop("subscription", None)
            out = bench_run.merge(out, cell_over)
        elif parts[0] == "configs":
            out = bench_run.merge(out, {"deployment": deployment_over})
        return out

    monkeypatch.setattr(bench_run, "load_json", load)


def cell_run(monkeypatch, **cell_over) -> bench_run.Run:
    lay_on(monkeypatch, cell_over)
    run = bench_run.Run(KB, 1, 1.0, False)
    run.bootstrap = ["127.0.0.1:1"]
    return run


def test_subscription_and_a_list_of_one_give_the_same_consumer_specs(
        monkeypatch):
    one = cell_run(monkeypatch, subscription="x")
    many = cell_run(monkeypatch, subscriptions=["x"])
    for i in range(4):
        a, b = (r.child_spec("consumers", i) for r in (one, many))
        assert a.pop("work") != b.pop("work")
        assert a == b
        assert a["subscriptions"] == [["x", 0, i, 4]] and a["client"] == {}
        assert "subscriptions" not in one.child_spec("producers", i)


def test_a_process_with_more_subscriptions_than_threads_refuses_the_run(
        monkeypatch):
    run = cell_run(monkeypatch, subscriptions=[f"s{i}" for i in range(40)])
    with pytest.raises(bench_run.RunFailed, match="10 subscriptions on 8"):
        run.child_spec("consumers", 0)


# ------------------------------------------------- the client's own options
def test_a_cell_hands_its_options_to_the_consumer_client(monkeypatch):
    opts = {"follower_reads": True, "long_poll_s": 0.5}
    run = cell_run(monkeypatch, subscription="x", consumers={"client": opts})
    assert run.child_spec("consumers", 0)["client"] == opts


@pytest.mark.parametrize("key", ["follow_reads", "max_messages",
                                 "consumer_id"])
def test_an_option_the_client_does_not_take_prints_no_result(tmp_path, key):
    """A key `ConsumerClient` does not take, or one the harness sets: the
    consumer child ends before READY, which `Run.await_ready` makes a run
    that failed - no result line, not a silent default."""
    spec = {"params": {"threads": 1, "max_messages": 64}, "bootstrap": [],
            "client": {key: 1}}
    with pytest.raises(TypeError, match=key):
        child.client_kwargs(spec)
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.child", "--role", "consume",
         "--spec", str(path)], capture_output=True, text=True, timeout=60,
        cwd=bench_run.REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0 and "READY" not in done.stdout
    assert "TypeError" in done.stderr and key in done.stderr
    assert child.client_kwargs(dict(spec, client={"follower_reads": True})) \
        == {"max_messages": 64, "prefetch": 0, "rpc_timeout_s": 30.0,
            "follower_reads": True}


class KeptClient(FakeClient):
    made: list = []

    def __init__(self, bootstrap, consumer_id, **kw) -> None:
        KeptClient.made.append((consumer_id, kw))


def test_the_options_reach_the_client_beside_the_harness_s_three(
        tmp_path, monkeypatch):
    KeptClient.made.clear()
    h = Harness(tmp_path, monkeypatch, KeptClient,
                client={"follower_reads": True})
    h.up_to_date()
    h.tell(f"DRAIN {time.monotonic_ns()} -")
    h.end()
    assert KeptClient.made == [("s", {
        "max_messages": 64, "prefetch": 0, "rpc_timeout_s": 30.0,
        "follower_reads": True})]


# ------------------------------------------ the DRAIN order, two subscriptions
class TwoSubscriptions(FakeClient):
    """Each subscription is served the script on its own: `a` gets its
    late batch when `late` is set, `b` never does."""

    def __init__(self, bootstrap, consumer_id, **kw) -> None:
        self.name = consumer_id

    def consume(self, topic, partition):
        cls = type(self)
        n = cls.served.setdefault((self.name, partition), 0)
        late = cls.late.is_set() and self.name == "a"
        ready = {0: 3 if late else 2, 1: 1}[partition]
        if n < ready:
            cls.served[(self.name, partition)] = n + 1
            return batch(10 * partition + n)
        cls.idle_polls += 1
        return []


def test_drain_holds_every_subscription_to_the_counts(tmp_path, monkeypatch):
    """Two subscriptions on two threads, both one batch short of stream 0
    at DRAIN; `a` gets it and leaves whole, `b` is short at the deadline -
    and what each received is kept apart."""
    h = Harness(tmp_path, monkeypatch, TwoSubscriptions,
                subscriptions=[["a", 0, 0, 1], ["b", 1, 0, 1]],
                params={"threads": 2, "max_messages": 64,
                        "poll_interval_s": 0.0, "idle_sleep_s": 0.001})
    h.tell("GO")
    until = time.monotonic() + 5
    while h.client.idle_polls < 6 and time.monotonic() < until:
        time.sleep(0.001)
    limit_s = 0.4
    t = time.monotonic_ns()
    h.tell(f"DRAIN {t + int(limit_s * 1e9)} "
           f"{h.counts_file([3 * BATCH, BATCH])}")
    time.sleep(0.05)
    h.client.late.set()
    res = h.end()
    assert (time.monotonic_ns() - t) / 1e9 >= limit_s  # b held it open
    assert res["short_at_deadline"] == 1 and res["errors"] == []
    assert res["received"] == 4 * BATCH + 3 * BATCH
    assert set(res["received_by_t1"]) == {"a", "b"}
    a, b = h.received(0), h.received(1)
    assert a[0] == b"".join(batch(0) + batch(1) + batch(2))
    assert b[0] == b"".join(batch(0) + batch(1))
    assert a[1] == b[1] == b"".join(batch(10))
    index = np.load(os.path.join(h.work, "recv-0.index.npy"))
    assert index[:, :2].tolist() == [[0, 0], [0, 1], [1, 0], [1, 1]]


# ------------------------------------- the reference, against each subscription
def ref_and_sound():
    rows = [(s, 1, call * 8, 8, 50_000 + call, call * 8, 0, 0)
            for s in range(STREAMS) for call in range(2)]
    ref = ReferenceLog(SEED, SIZE, np.array(rows, dtype=RECORD), STREAMS)
    return ref, {s: ref.stream(s).tobytes() for s in range(STREAMS)}


def numbers(dl: dict, want_subs: int = 2) -> dict:
    N = bench_run.delivery_numbers(dl, True, want_subs)
    assert [n for n, _, _ in N] == ["delivery.differ", "delivery.extra",
                                    "delivery.missing",
                                    "delivery.subscriptions"]
    return bench_run.failing(N)


def test_two_sound_subscriptions_are_correct():
    ref, sound = ref_and_sound()
    dl = compare_subscriptions(ref, {"a": sound, "b": dict(sound)},
                               ["a", "b"], prefix_ok=False)
    assert dl["reported"] == 2 and dl["bad"] == [] and numbers(dl) == {}
    assert numbers(dl, want_subs=1) == {"delivery.subscriptions": 2}


def test_a_byte_off_in_one_subscription_is_not_correct_and_names_it():
    ref, sound = ref_and_sound()
    off = dict(sound)
    b = bytearray(off[1])
    b[5 * SIZE + 40] ^= 0x01
    off[1] = bytes(b)
    dl = compare_subscriptions(ref, {"a": sound, "b": off}, ["a", "b"],
                               prefix_ok=False)
    assert numbers(dl) == {"delivery.differ": 1}
    assert dl["bad"] == ["b"]
    assert dl["by_subscription"]["b"]["bad_streams"] == [1]
    assert dl["by_subscription"]["a"]["differ"] == 0


def test_a_message_short_in_one_subscription_is_not_correct_and_names_it():
    ref, sound = ref_and_sound()
    short = dict(sound)
    short[2] = short[2][:-SIZE]
    dl = compare_subscriptions(ref, {"a": short, "b": sound}, ["a", "b"],
                               prefix_ok=False)
    assert numbers(dl) == {"delivery.missing": 1} and dl["bad"] == ["a"]
    # an exact prefix will do where the cell says so; the lag is kept
    dl = compare_subscriptions(ref, {"a": short, "b": sound}, ["a", "b"],
                               prefix_ok=True)
    assert numbers(dl) == {} and dl["lag"] == 1


def test_a_subscription_nobody_consumed_is_not_correct_and_names_it():
    ref, sound = ref_and_sound()
    dl = compare_subscriptions(ref, {"a": sound}, ["a", "b"],
                               prefix_ok=False)
    assert dl["reported"] == 1 and dl["bad"] == ["b"]
    assert numbers(dl) == {"delivery.missing": ref.total,
                           "delivery.subscriptions": 1}
    # one that reported and received nothing is late, not absent
    dl = compare_subscriptions(ref, {"a": sound, "b": {}}, ["a", "b"],
                               prefix_ok=True)
    assert dl["reported"] == 2 and numbers(dl) == {}
    assert dl["lag"] == ref.total


# ------------------------------------------------------- whole rehearsal runs
KEYS = ["delivery.differ", "delivery.extra", "delivery.missing",
        "delivery.subscriptions", "delivery.consumer_errors",
        "reference.duplicate_offsets", "replicas.scanned"] + [
    f"replica{i}.{k}" for i in range(3)
    for k in ("differ", "missing", "extra")] + [
    "brokers.stat_errors", "window.unexpected_compiles",
    "producers.failed_calls"]


def rehearse(cell: str, **kw):
    run = bench_run.Run(cell, 4000000037, 3.0, False, rehearse=True, **kw)
    out = run.run()
    return out, {name: value for name, value, _ in run.numbers}


FOUR = [f"bench-sub-{q}" for q in range(4)]


def test_a_sound_rehearsal_read_by_four_subscriptions_is_correct(monkeypatch):
    lay_on(monkeypatch, {"subscriptions": FOUR}, {"subscriptions": 4})
    out, num = rehearse(KB)
    assert out["correct"] is True and out["failed"] == 0
    assert num["delivery.subscriptions"] == 4 and num["replicas.scanned"] == 3
    assert out["compared"]["delivery.subscriptions"] == [4, "== 4"]
    assert set(out["metrics"]) == {"produce_ack_p50_ms", "deliver_p50_ms",
                                   "setup_s"}
    assert list(out["compared"]) == KEYS


def test_one_subscription_of_four_one_message_short_is_not_correct(
        monkeypatch):
    lay_on(monkeypatch, {"subscriptions": FOUR}, {"subscriptions": 4})
    out, failed = control_run(KB, 4000000039, 3.0, rehearse=True,
                              breaks="drop")
    assert out["correct"] is False and out["delivery.missing"] == 1
    assert caught("drop", out, failed)
    assert out["compared"]["delivery.subscriptions"] == [4, "== 4"]
    assert out["drain.deadline_reached"] == 1


def test_the_1kb_cell_s_line_gains_one_compared_key_and_nothing_else():
    out, num = rehearse(KB)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "cold", "compared"]
    assert list(out["compared"]) == KEYS  # the parent's, and the fourth
    assert out["compared"]["delivery.subscriptions"] == [1, "== 1"]

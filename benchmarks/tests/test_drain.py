"""The DRAIN order reaches a consumer thread complete or not at all, and a
run that is not correct says by which number. No chip, no cluster: the
orders come through a pipe, the program's `ConsumerClient` is a fake that
serves scripted batches, and the counts' loader is held by a patched
`np.load`, which is where a thread used to slip out (PERF.md section 6,
PR 31). A second or two together."""

import json
import os
import threading
import time

import numpy as np
import pytest

import run as bench_run
from benchmarks import child

SIZE = 100
BATCH = 4
HOLD_S = 0.05  # how long the loader is held: a busy consumer process


def batch(k: int) -> list[bytes]:
    return [bytes([k]) * SIZE for _ in range(BATCH)]


class FakeClient:
    """Stream 0 serves two batches at once and a third when `late` is set;
    stream 1 serves one. `idle_polls` counts the polls that found nothing
    ready. `role_consume` builds its client from the class, so the script
    lives on a subclass that each Harness makes anew."""

    late: threading.Event
    served: dict
    idle_polls: int

    def __init__(self, *a, **k) -> None:
        pass

    def consume(self, topic, partition):
        cls = type(self)
        n = cls.served[partition]
        ready = {0: 3 if cls.late.is_set() else 2, 1: 1}[partition]
        if n < ready:
            cls.served[partition] = n + 1
            return batch(10 * partition + n)
        cls.idle_polls += 1
        return []

    def close(self) -> None:
        pass


class Harness:
    """One consumer child in this process: its orders through a pipe."""

    def __init__(self, tmp_path, monkeypatch, client_class=None,
                 **spec) -> None:
        import ripplemq_tpu.client as client

        self.client = type("ScriptedClient", (client_class or FakeClient,), {
            "late": threading.Event(), "served": {0: 0, 1: 0},
            "idle_polls": 0})
        monkeypatch.setattr(client, "ConsumerClient", self.client)
        r, w = os.pipe()
        self.pipe, self.stdin = os.fdopen(w, "w"), os.fdopen(r)
        self.orders = child.Orders(stdin=self.stdin)
        self.work = str(tmp_path)
        self.spec = {
            "params": {"threads": 1, "max_messages": 64,
                       "poll_interval_s": 0.0, "idle_sleep_s": 0.001},
            "message_bytes": SIZE, "streams": [["t", 0], ["t", 1]],
            "proc_id": 0, "nprocs": 1, "bootstrap": [],
            "subscriptions": [["s", 0, 0, 1]], "work": self.work, **spec}
        self.result: dict = {}
        self.thread = threading.Thread(target=lambda: self.result.update(
            child.role_consume(self.spec, self.orders)))
        self.thread.start()

    def tell(self, line: str) -> None:
        self.pipe.write(line + "\n")
        self.pipe.flush()

    def up_to_date(self) -> None:
        """GO, and everything that is ready has been taken."""
        self.tell("GO")
        until = time.monotonic() + 5
        while self.client.idle_polls < 3 and time.monotonic() < until:
            time.sleep(0.001)
        assert self.client.served == {0: 2, 1: 1}

    def counts_file(self, counts) -> str:
        path = os.path.join(self.work, "expect.npy")
        np.save(path, np.array(counts, np.int64))
        return path

    def end(self, timeout: float = 5.0) -> dict:
        self.thread.join(timeout)
        assert not self.thread.is_alive()
        self.pipe.close()
        self.hung_up()
        return self.result

    def hung_up(self) -> None:
        """The orders' reader has seen the pipe close; its end closed too."""
        until = time.monotonic() + 5
        while not self.orders.gone and time.monotonic() < until:
            time.sleep(0.001)
        assert self.orders.gone
        self.stdin.close()

    def received(self, subscription: int = 0) -> dict:
        flat = np.load(os.path.join(self.work, "recv-0.bytes.npy"))
        out, at = {}, 0
        for q, s, n in np.load(os.path.join(self.work, "recv-0.index.npy")):
            if q == subscription:
                out[int(s)] = bytes(flat[at:at + int(n)])
            at += int(n)
        return out


@pytest.fixture
def held_loader(monkeypatch):
    """`np.load` as `child` sees it: says when it was entered, then holds
    until released (at most HOLD_S unless the test holds it longer)."""
    entered, release = threading.Event(), threading.Event()
    real = np.load

    def load(path, *a, **k):
        entered.set()
        release.wait(HOLD_S)
        return real(path, *a, **k)

    monkeypatch.setattr(child.np, "load", load)
    return entered, release


def test_drain_is_not_set_before_its_counts_are_stored(tmp_path, held_loader):
    entered, release = held_loader
    r, w = os.pipe()
    stdin = os.fdopen(r)
    orders = child.Orders(stdin=stdin)
    path = os.path.join(str(tmp_path), "expect.npy")
    np.save(path, np.array([7, 9], np.int64))
    with os.fdopen(w, "w") as pipe:
        pipe.write(f"DRAIN 12345 {path}\n")
        pipe.flush()
        assert entered.wait(5)
        until = time.monotonic() + HOLD_S / 2
        while time.monotonic() < until:  # the loader is held: no order yet
            assert not orders.drain.is_set() and orders.want is None
            time.sleep(0.001)
        release.set()
        assert orders.drain.wait(5)
        assert orders.want.tolist() == [7, 9]
        assert orders.drain_deadline == 12345 and orders.drain_error is None
        assert not orders.gone
    until = time.monotonic() + 5  # hung up: the reader ends, then its end
    while not orders.gone and time.monotonic() < until:
        time.sleep(0.001)
    stdin.close()


def test_a_whole_consumer_one_batch_short_at_drain_reads_on(
        tmp_path, monkeypatch, held_loader):
    """The run that PR 30 lost: DRAIN arrives with the thread up to date
    but one batch short of what was acked, and the loader is slow. The
    thread must still be polling when the batch comes."""
    h = Harness(tmp_path, monkeypatch)
    h.up_to_date()
    deadline = time.monotonic_ns() + int(5e9)
    h.tell(f"DRAIN {deadline} {h.counts_file([3 * BATCH, BATCH])}")
    time.sleep(2 * HOLD_S)  # round the loop many times, loader held
    h.client.late.set()
    res = h.end()
    assert res["received"] == 4 * BATCH and res["errors"] == []
    assert res["short_at_deadline"] == 0
    got = h.received()
    assert got[0] == b"".join(batch(0) + batch(1) + batch(2))
    assert got[1] == b"".join(batch(10))
    assert time.monotonic_ns() < deadline  # left when whole, not at the limit


def test_a_whole_consumer_stops_at_the_deadline_and_says_what_was_short(
        tmp_path, monkeypatch):
    h = Harness(tmp_path, monkeypatch)
    h.up_to_date()
    limit_s = 0.3
    t = time.monotonic_ns()
    h.tell(f"DRAIN {t + int(limit_s * 1e9)} "
           f"{h.counts_file([3 * BATCH, BATCH])}")
    res = h.end()
    took = (time.monotonic_ns() - t) / 1e9
    assert limit_s <= took < limit_s + 2.0
    assert res["received"] == 3 * BATCH and res["short_at_deadline"] == 1
    assert res["errors"] == []  # late is the comparison's to say, not an error


def test_a_prefix_consumer_leaves_at_drain(tmp_path, monkeypatch):
    h = Harness(tmp_path, monkeypatch)
    h.up_to_date()
    h.client.late.set()  # more is ready: a prefix cell does not wait for it
    h.tell(f"DRAIN {time.monotonic_ns() + int(60e9)} -")
    res = h.end()
    assert h.orders.want is None and res["errors"] == []
    assert res["short_at_deadline"] == 0
    assert 3 * BATCH <= res["received"] <= 4 * BATCH


def test_counts_that_cannot_be_loaded_are_a_consumer_error(
        tmp_path, monkeypatch):
    h = Harness(tmp_path, monkeypatch)
    h.up_to_date()
    h.tell(f"DRAIN {time.monotonic_ns() + int(60e9)} "
           f"{os.path.join(h.work, 'no-such-file.npy')}")
    res = h.end()
    assert len(res["errors"]) == 1 and "DRAIN counts" in res["errors"][0]
    assert res["received"] == 3 * BATCH


def test_a_parent_that_goes_away_ends_the_drain(tmp_path, monkeypatch):
    h = Harness(tmp_path, monkeypatch)
    h.up_to_date()
    h.tell(f"DRAIN {time.monotonic_ns() + int(60e9)} "
           f"{h.counts_file([3 * BATCH, BATCH])}")
    time.sleep(0.02)
    h.pipe.close()
    h.thread.join(5)
    assert not h.thread.is_alive()
    h.hung_up()


NUMBERS = [("delivery.differ", 0, "0"), ("delivery.missing", 512, "0"),
           ("replicas.scanned", 2, "== 3"),
           ("window.unexpected_compiles", np.int64(0), "0")]
DRAIN = {"drain_s": 1.1, "drain.deadline_reached": 0}


def test_an_incorrect_line_names_the_numbers_that_failed():
    assert bench_run.failing(NUMBERS) == {"delivery.missing": 512,
                                          "replicas.scanned": 2}
    end = bench_run.verdict(NUMBERS, DRAIN)
    assert list(end) == ["delivery.missing", "replicas.scanned", "drain_s",
                         "drain.deadline_reached", "compared"]
    assert end["delivery.missing"] == 512 and end["replicas.scanned"] == 2
    assert end["compared"]["replicas.scanned"] == [2, "== 3"]
    json.dumps(end)  # plain numbers, whatever numpy handed in
    lines = bench_run.compared_lines(NUMBERS)
    assert lines[1] == "compared delivery.missing = 512 (limit 0)  <-- FAILS"
    assert lines[0] == "compared delivery.differ = 0 (limit 0)"


def test_a_sound_line_carries_the_comparison_and_nothing_else():
    sound = [(n, 3 if lim.startswith("==") else 0, lim)
             for n, _, lim in NUMBERS]
    assert bench_run.failing(sound) == {}
    end = bench_run.verdict(sound, DRAIN)
    assert list(end) == ["compared"]
    assert end["compared"] == {n: [v, lim] for n, v, lim in sound}


class FakeRun:
    numbers = NUMBERS

    def __init__(self, *a, **k) -> None:
        pass

    def run(self) -> dict:
        out = {"correct": False, "attempted": 1, "failed": 0, "metrics": {},
               "device": {}, "cold": False}
        out.update(bench_run.verdict(self.numbers, DRAIN))
        return out


def test_the_result_ends_stdout_and_the_comparison_ends_stderr(
        monkeypatch, capsys):
    monkeypatch.setattr(bench_run, "Run", FakeRun)
    rc = bench_run.main(["--workload", "x", "--seed", "1", "--seconds", "1"])
    cap = capsys.readouterr()
    assert rc == 0
    last = json.loads(cap.out.strip().splitlines()[-1])
    assert last["correct"] is False and last["delivery.missing"] == 512
    assert list(last)[-1] == "compared"
    assert cap.err.strip().splitlines()[-len(NUMBERS):] \
        == bench_run.compared_lines(NUMBERS)


def compile_log(*msgs: str) -> list[str]:
    return [json.dumps({"t_ns": i, "msg": m}) + "\n"
            for i, m in enumerate(msgs)]


STEP = "Compiling jit(_step_many_sparse_j) with global shapes and types"
READ = "Compiling jit(_read_many) with global shapes and types"


@pytest.mark.parametrize("msgs, over", [
    ((), False),
    ((STEP, "Finished XLA compilation of jit(_step_many_sparse_j)"), False),
    ((STEP, "Persistent compilation cache hit for 'jit__step'", READ), False),
    ((STEP, READ, "Finished jaxpr to MLIR module conversion"), False),
    ((STEP, READ, "Finished XLA compilation of jit(_read_many) in 0.5"), True),
    ((READ, "Persistent compilation cache hit for 'jit__read_many'"), True),
])
def test_warm_up_is_over_when_its_last_program_is_built(msgs, over):
    """The boot race: a probe acked between two of the broker's warm-up
    programs must not start the cell's traffic."""
    assert bench_run.warm_over(compile_log(*msgs)) is over
    assert bench_run.warm_over(compile_log(*msgs) + ['{"t_ns": 9, "ms']) \
        is over  # a line half written is not read

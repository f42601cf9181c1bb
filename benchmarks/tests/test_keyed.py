"""The keyed cell's own pieces: the key draw is a pure function of the
seed with YCSB's zipfian shape, the arrival schedule keeps its count,
and whole rehearsal runs (CPU backend, the files' rehearsal sizes) of
`omb-1024p-100b-keyed.zipf` - sound, with the per-key order broken
underneath by a patched client, and with a partition the subscription
never delivers, which the cell's own drain must fail at its limit and
name in the result line. About 20 s each of the three runs."""

import json
import os

import numpy as np
import pytest

from generators.keyed_open_loop import KeySpace, key_bytes, schedule
from run import Run

CELL = "omb-1024p-100b-keyed.zipf"


def draw(seed: int, n: int = 400_000, epoch: int = 2):
    space = KeySpace(seed, 1_000_000, 0.99)
    ranks = space.ranks(np.random.default_rng([seed, 0, 1]), n)
    return ranks, space.key_ids(epoch, ranks)


def test_key_draw_is_a_pure_function_of_the_seed():
    r1, k1 = draw(2**31 + 5)
    r2, k2 = draw(2**31 + 5)
    r3, k3 = draw(2**31 + 6)
    assert (r1 == r2).all() and (k1 == k2).all()
    assert (k1 != k3).mean() > 0.9
    h = (np.arange(1, 1_000_001, dtype=np.float64) ** -0.99).sum()
    top = (r1 == 0).mean()
    assert abs(top - 1 / h) < 0.1 / h, (top, 1 / h)


def test_every_epoch_maps_ranks_to_keys_one_to_one_and_moves_the_hot_set():
    space = KeySpace(9, 100_000, 0.99)
    all_ranks = np.arange(100_000)
    a, b = space.key_ids(0, all_ranks), space.key_ids(1, all_ranks)
    assert len(np.unique(a)) == len(np.unique(b)) == 100_000
    assert a[0] != b[0] and key_bytes(int(a[0])) == b"key-%07d" % a[0]


def test_the_schedule_keeps_its_count_and_knows_one_arrival_law():
    even = schedule(0.5, 0, 2 * 10**9, 1000, "even")
    assert len(even) == 2000 and (np.diff(even) > 0).all()
    assert 0 <= even[0] and even[-1] < 2 * 10**9
    with pytest.raises(ValueError):
        schedule(0.5, 0, 10**9, 1000, "bursts")


def test_the_cell_drains_as_steady_does_and_its_generator_waits_for_nothing():
    def cell(name):
        with open(os.path.join(os.path.dirname(__file__), "..", "workloads",
                               f"{name}.json")) as f:
            return json.load(f)

    keyed, steady = cell(CELL), cell("omb-1024p-100b.steady")
    assert "drain_limit_s" not in keyed["producers"]["params"]
    assert keyed["drain_limit_s"] == steady["drain_limit_s"] == 15.0
    assert keyed["delivery"] == steady["delivery"] == "whole"
    assert keyed["consumers"] == steady["consumers"]


def run_cell(drain_limit_s=None):
    run = Run(CELL, 4000000011, 3.0, False, rehearse=True)
    if drain_limit_s is not None:  # a failing drain need not take 15 s
        run.cell["drain_limit_s"] = drain_limit_s
    out = run.run()
    return out, {name: value for name, value, _ in run.numbers}


def test_a_sound_keyed_run_is_correct():
    out, numbers = run_cell()
    assert out["correct"] is True and out["failed"] == 0
    assert numbers["replicas.scanned"] == 3 and out["attempted"] > 1000
    assert numbers["producers.failed_calls"] == 0


PATCH = '''
import ripplemq_tpu.client.producer as P
_orig, _first, _once = P.ProducerClient._finish, {}, []
def _finish(self, r, resp, err):
    if err is None and resp and resp.get("ok"):
        for part, res in zip(r.parts, resp["parts"]):
            if not res.get("ok"):
                continue
            if part.tp in _first and not _once and len(_first) > 4:
                res["base_offset"] = _first[part.tp]  # acked "below"
                _once.append(1)
            _first.setdefault(part.tp, res["base_offset"])
    return _orig(self, r, resp, err)
P.ProducerClient._finish = _finish
'''


def patch_children(tmp_path, monkeypatch, patch: str) -> None:
    (tmp_path / "sitecustomize.py").write_text(patch)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))  # sitecustomize imports the program
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        [str(tmp_path), root]
        + [p for p in (os.environ.get("PYTHONPATH"),) if p]))


def test_parts_acked_out_of_order_are_failed_calls(tmp_path, monkeypatch):
    """A client that acks a later part of a partition at an earlier part's
    offset: the generator's order check turns it into failed calls."""
    patch_children(tmp_path, monkeypatch, PATCH)
    out, numbers = run_cell()
    assert out["correct"] is False
    assert numbers["producers.failed_calls"] > 0


NOT_DELIVERED = '''
import ripplemq_tpu.client.consumer as C
_consume = C.ConsumerClient.consume
def consume(self, topic, partition=None, max_messages=None):
    if partition == 3:
        return []
    return _consume(self, topic, partition, max_messages)
C.ConsumerClient.consume = consume
'''


def test_a_subscription_short_at_the_drain_limit_is_not_correct_and_says_so(
        tmp_path, monkeypatch):
    """Consumers that never deliver one partition: its thread reads on to
    the cell's drain limit, not beyond, and the result line names what
    failed, how long the drain took and that it ran into its limit."""
    patch_children(tmp_path, monkeypatch, NOT_DELIVERED)
    out, numbers = run_cell(drain_limit_s=2.0)
    assert out["correct"] is False
    assert numbers["delivery.missing"] > 0
    assert out["delivery.missing"] == numbers["delivery.missing"]
    assert out["drain.deadline_reached"] == 1
    assert 2.0 <= out["drain_s"] < 10.0
    assert list(out)[-1] == "compared"
    assert out["compared"]["delivery.missing"] \
        == [numbers["delivery.missing"], "0"]

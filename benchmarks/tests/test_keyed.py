"""The keyed cell's own pieces: the key draw is a pure function of the
seed with YCSB's zipfian shape, the arrival schedule keeps its count,
and whole rehearsal runs (CPU backend, the files' rehearsal sizes) of
`omb-1024p-100b-keyed.zipf` - sound, with the per-key order broken
underneath by a patched client, and with a subscription that never
commits, which the generator's wait must fail inside the cell's drain
limit. About 20 s each of the three runs."""

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


def test_the_generator_waits_no_longer_than_the_cell_drains():
    with open(os.path.join(os.path.dirname(__file__), "..", "workloads",
                           f"{CELL}.json")) as f:
        cell = json.load(f)
    assert cell["producers"]["params"]["drain_limit_s"] \
        == cell["drain_limit_s"]


def run_cell(drain_limit_s=None):
    run = Run(CELL, 4000000011, 3.0, False, rehearse=True)
    if drain_limit_s is not None:  # a failing wait need not take 15 s
        run.cell["producers"]["params"]["drain_limit_s"] = drain_limit_s
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


NO_COMMIT = '''
import ripplemq_tpu.client.consumer as C
C.ConsumerClient._auto_commit = lambda self, *a, **k: None
'''


def test_a_subscription_behind_at_the_drain_limit_is_failed_calls(
        tmp_path, monkeypatch):
    """Consumers that receive and never commit: everything is delivered,
    yet the subscription's position never passes the acks, and the
    generator's wait must say so when the limit is up, not wait on."""
    patch_children(tmp_path, monkeypatch, NO_COMMIT)
    out, numbers = run_cell(drain_limit_s=2.0)
    assert out["correct"] is False
    assert numbers["producers.failed_calls"] > 0

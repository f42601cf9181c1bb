"""The rest of a run, driven without the look for a chip (the files'
rehearsal sizes, CPU backend), with the timed path broken underneath: it
must come out as not correct. About half a minute each."""

import pytest

from control import control_run
from run import Run


def run_cell(**kw):
    run = Run("omb-1024p-100b.steady", 4000000007, 3.0, False, rehearse=True,
              **kw)
    out = run.run()
    return out, {name: value for name, value, _ in run.numbers}


def test_a_sound_run_is_correct():
    out, numbers = run_cell()
    assert out["correct"] is True and out["failed"] == 0
    assert numbers["replicas.scanned"] == 3
    assert out["attempted"] > 0 and out["metrics"]["setup_s"]["value"] > 0
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "cold", "compared"]  # no name: none failed
    assert {k: v for k, (v, _) in out["compared"].items()} == numbers


@pytest.mark.parametrize("fault, number, at_deadline", [
    ("flip_delivered", "delivery.differ", 0),
    ("drop_delivered", "delivery.differ", 1),  # a stream one short: waited for
    ("short_replica", "replica2.missing", 0),
])
def test_an_altered_answer_is_not_correct(fault, number, at_deadline):
    out, numbers = run_cell(fault=fault)
    assert out["correct"] is False
    assert numbers[number] > 0
    assert out[number] == numbers[number]  # the line says which, and by what
    assert out["drain.deadline_reached"] == at_deadline and out["drain_s"] > 0


def test_fewer_acknowledgements_than_configured_is_not_correct():
    """The control kept at a size a test run can hold: the cluster acks on
    two copies where the configuration states three."""
    out, failed = control_run("ref-compose.sync", 77, 3.0, rehearse=True)
    assert out["correct"] is False
    assert "replicas.scanned" in failed and "delivery.differ" in failed
    assert any(f.endswith(".missing") for f in failed)
    for name in failed:  # each a top-level key, with its `compared` value
        assert out[name] == out["compared"][name][0]
    assert out["replicas.scanned"] == 2 and list(out)[-1] == "compared"

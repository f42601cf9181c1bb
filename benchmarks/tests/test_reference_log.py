"""The comparison that decides `correct`, shown to fail."""

import numpy as np
import pytest

from benchmarks import payload
from benchmarks.reference_log import RECORD, ReferenceLog, compare_all

SEED, SIZE, STREAMS = 2147483659, 100, 4


def records():
    rows = []
    for s in range(STREAMS):
        for call in range(3):  # acked out of send order: offsets decide
            rows.append((s, 1, call * 8, 8, 10_000 + call, (2 - call) * 16,
                         0, 0))
    return np.array(rows, dtype=RECORD)


@pytest.fixture()
def ref():
    return ReferenceLog(SEED, SIZE, records(), STREAMS)


def sound(ref):
    return {s: ref.stream(s).tobytes() for s in range(STREAMS)}


def test_reference_is_in_ack_offset_order_and_rebuilt_from_the_seed(ref):
    assert ref.total == STREAMS * 24 and ref.duplicate_offsets == 0
    first = ref.stream(2)[0]
    head = np.frombuffer(first[:16].tobytes(), dtype=[
        ("stream", "<u2"), ("client", "<u2"), ("seq", "<u4"), ("stamp", "<u8")])
    assert (head["stream"][0], head["seq"][0], head["stamp"][0]) == (2, 16, 10_002)
    pool = payload.make_pool(SEED)
    off = payload.body_offsets(2, 1, 16, SIZE)
    assert bytes(first[16:]) == pool[int(off):int(off) + SIZE - 16].tobytes()


def test_sound_answers_pass_whole_and_as_prefix(ref):
    got = sound(ref)
    for prefix_ok in (False, True):
        r = compare_all(ref, got, prefix_ok)
        assert (r["differ"], r["missing"], r["extra"]) == (0, 0, 0)


def test_a_flipped_byte_fails(ref):
    got = sound(ref)
    b = bytearray(got[1])
    b[5 * SIZE + 57] ^= 0x01
    got[1] = bytes(b)
    r = compare_all(ref, got, prefix_ok=True)
    assert r["differ"] == 1 and r["bad_streams"] == [1]


def test_a_dropped_message_fails(ref):
    got = sound(ref)
    got[3] = got[3][:7 * SIZE] + got[3][8 * SIZE:]
    r = compare_all(ref, got, prefix_ok=False)
    assert r["differ"] > 0 and r["missing"] == 1


def test_a_short_replica_fails_but_a_lagging_subscription_is_a_prefix(ref):
    got = sound(ref)
    got[0] = got[0][:20 * SIZE]
    assert compare_all(ref, got, prefix_ok=False)["missing"] == 4
    lag = compare_all(ref, got, prefix_ok=True)
    assert (lag["differ"], lag["missing"], lag["extra"], lag["lag"]) == (0, 0, 0, 4)


def test_what_no_producer_sent_fails(ref):
    got = sound(ref)
    got[2] = got[2] + b"\x00" * SIZE
    assert compare_all(ref, got, prefix_ok=True)["extra"] == 1
    got = sound(ref)
    got[9] = b"\x01" * SIZE  # a partition the cell does not have
    assert compare_all(ref, got, prefix_ok=True)["extra"] >= 1


def test_two_acks_at_one_offset_are_seen():
    recs = records()
    recs["offset"][1] = recs["offset"][0]
    assert ReferenceLog(SEED, SIZE, recs, STREAMS).duplicate_offsets == 1

"""Striped replication plane (ripplemq_tpu/stripes/): codec matrix,
rebuilt-from-any-k recovery, the k-of-k+m refusal ladder, full↔striped
committed-prefix parity, and the promotion rebuild end-to-end.

The rebuild-from-any-k matrix is the acceptance core: every C(k+m, k)
survivor subset of a multi-round striped store must reconstruct the
record stream byte-for-byte, and every k-1 subset must refuse into the
rebuild-or-quarantine ladder instead of fabricating bytes."""

from __future__ import annotations

import itertools
import time

import pytest

from ripplemq_tpu.stripes.codec import (
    RS_K,
    RS_M,
    StripeShortError,
    encode_group,
    parse_frame,
    reconstruct_group,
    serialize_records,
    stripe_assignment,
)
from ripplemq_tpu.stripes.recovery import (
    StripeDataLossError,
    StripeRecoveryError,
    rebuild_records,
)

N = RS_K + RS_M

# Representative multi-round record stream: append rows, a pid entry,
# an offset batch — the exact shapes the settle path replicates.
RECORDS = [
    (1, 0, 0, b"row-" * 32),
    (4, 0, 1, b"\x01\x00\x00\x00" + b"\x00" * 20),
    (1, 1, 8, bytes(range(256)) * 3),
    (2, 1, 2, b"\x02\x00\x00\x00\x09\x00\x00\x00"),
]


def _frames(records=RECORDS, epoch=1, gsn=5, **kw):
    return encode_group(records, epoch, gsn, **kw)


# ------------------------------------------------------------- codec

def test_any_k_subset_reconstructs_byte_for_byte():
    frames = _frames()
    parsed = {i: parse_frame(f) for i, f in enumerate(frames)}
    assert all(p is not None for p in parsed.values())
    for subset in itertools.combinations(range(N), RS_K):
        got = reconstruct_group({i: parsed[i] for i in subset})
        assert got == RECORDS, f"subset {subset} diverged"


def test_every_below_k_subset_refuses():
    frames = _frames()
    parsed = {i: parse_frame(f) for i, f in enumerate(frames)}
    for r in range(RS_K):
        for subset in itertools.combinations(range(N), r):
            with pytest.raises(StripeShortError):
                reconstruct_group({i: parsed[i] for i in subset})


def test_frame_crc_corruption_is_missing_never_wrong():
    frames = _frames()
    # Flip one byte anywhere — header and payload positions alike must
    # refuse at parse (the segment-store header-covered-CRC rule).
    for pos in (4, 9, 17, 30, len(frames[0]) - 1):
        b = bytearray(frames[0])
        b[pos] ^= 0xFF
        assert parse_frame(bytes(b)) is None, f"corruption at {pos} passed"
    # A rotted stripe degrades the group to the remaining k, exactly.
    parsed = {i: parse_frame(f) for i, f in enumerate(frames)}
    survivors = {i: parsed[i] for i in (1, 2, 4)}
    assert reconstruct_group(survivors) == RECORDS


def test_wire_bytes_scale_with_k_plus_m_over_k():
    records = [(1, 0, i, bytes(1024)) for i in range(512)]
    blob = len(serialize_records(records))
    total = sum(len(f) for f in _frames(records))
    ratio = total / blob
    # (k+m)/k = 1.667 plus k+m fixed frame headers — the class ladder
    # must pad COMPUTE only, never the wire (the whole byte story).
    assert ratio < 1.70, ratio


def test_stripe_assignment_covers_all_stripes_deterministically():
    assert stripe_assignment(()) == ()
    assert stripe_assignment((7,)) == (7,) * N
    two = stripe_assignment((9, 4))
    assert set(two) == {4, 9} and len(two) == N
    assert stripe_assignment([4, 9]) == two  # order-insensitive
    four = stripe_assignment((3, 1, 2, 0))
    assert four == (0, 1, 2, 3, 0)


def test_empty_group_roundtrip():
    frames = _frames([], epoch=2, gsn=0)
    parsed = {i: parse_frame(f) for i, f in enumerate(frames)}
    assert reconstruct_group({0: parsed[0], 3: parsed[3], 4: parsed[4]}) == []


# ---------------------------------------------------- recovery matrix

def _holder_stores(groups, members=(10, 11, 12, 13, 14)):
    """Distribute live-group stripes per the replicated assignment over
    `members` simulated holder stores → {bid: [REC_STRIPE records]}.
    Each group's frames carry the settled floor of its PREDECESSOR
    (the encoder's contiguous-settle watermark: everything before the
    group in flight has settled) — the shape a healthy run stamps."""
    from ripplemq_tpu.storage.segment import REC_STRIPE

    held = stripe_assignment(members)
    stores: dict[int, list] = {b: [] for b in members}
    prev = 0
    for epoch, gsn, records in groups:
        frames = encode_group(records, epoch, gsn, settled_floor=prev)
        prev = gsn
        for i, f in enumerate(frames):
            stores[held[i]].append(
                (REC_STRIPE, i, gsn & 0x7FFFFFFF, f)
            )
    return stores


GROUPS = [
    (1, 100, RECORDS),
    (1, 101, [(1, 0, 8, b"second-round" * 10)]),
    (1, 102, [(1, 1, 16, b"third" * 50), (2, 1, 1, b"\x00" * 8)]),
]


def _fetcher(records):
    def fetch(after):
        return [p for _, _, _, p in records], None
    return fetch


def test_rebuild_from_any_k_holder_subset_matrix():
    stores = _holder_stores(GROUPS)
    members = sorted(stores)
    want = [r for _, _, recs in GROUPS for r in recs]
    for subset in itertools.combinations(members, RS_K):
        local, *peers = subset
        got = rebuild_records(
            iter(stores[local]),
            [(f"peer{b}", _fetcher(stores[b])) for b in peers],
        )
        assert got == want, f"survivors {subset} diverged"


def test_below_k_holders_refuse_into_the_ladder():
    stores = _holder_stores(GROUPS)
    members = sorted(stores)
    for subset in itertools.combinations(members, RS_K - 1):
        local, *peers = subset
        # Every configured peer consulted → DEFINITIVE loss.
        with pytest.raises(StripeDataLossError):
            rebuild_records(
                iter(stores[local]),
                [(f"peer{b}", _fetcher(stores[b])) for b in peers],
            )

    # Same shortfall with a peer UNREACHABLE → transient, retryable.
    def down(after):
        raise ConnectionError("down")

    local = members[0]
    with pytest.raises(StripeRecoveryError):
        rebuild_records(
            iter(stores[local]),
            [(f"peer{members[1]}", down)],
        )


def test_torn_tail_groups_drop_but_midstream_loss_refuses():
    stores = _holder_stores(GROUPS)
    members = sorted(stores)
    held = stripe_assignment(members)
    tail_gsn = GROUPS[-1][1] & 0x7FFFFFFF
    mid_gsn = GROUPS[1][1] & 0x7FFFFFFF

    def drop_gsn(store, gsn):
        return [r for r in store if r[2] != gsn]

    # Keep only 2 stripes of the TAIL group (never reached k acks):
    # rebuild drops it and returns the settled prefix.
    keep = set(i for i, b in enumerate(held))
    merged = [r for b in members for r in stores[b]]
    tail_short = [
        r for r in merged
        if r[2] != tail_gsn or r[1] in (0, 1)
    ]
    got = rebuild_records(iter(tail_short), [])
    assert got == [r for _, _, recs in GROUPS[:-1] for r in recs]

    # The SAME shortfall mid-stream is acked-data loss: refuse.
    mid_short = [
        r for r in merged
        if r[2] != mid_gsn or r[1] in (0, 1)
    ]
    with pytest.raises(StripeDataLossError):
        rebuild_records(iter(mid_short), [])
    del keep


def test_tombstoned_group_drops_even_below_the_settled_floor():
    """A terminally NACKED group can leave partial stripes on standby
    disks while the settled floor advances past it (the controller
    refused its rounds — producers never saw an ack). The tombstone
    the sender fans out is what keeps recovery from reading those
    leftovers as acked loss and falsely quarantining a healthy store."""
    from ripplemq_tpu.storage.segment import REC_STRIPE

    recs = []
    ok1 = [(1, 0, 0, b"settled-one" * 4)]
    nacked = [(1, 0, 8, b"nacked" * 10)]
    ok2 = [(1, 0, 8, b"settled-two" * 4)]
    for i, f in enumerate(encode_group(ok1, 1, 10)):
        recs.append((REC_STRIPE, i, 10, f))
    # Only ONE stripe of the nacked group ever landed...
    f_nacked = encode_group(nacked, 1, 11, settled_floor=10)
    recs.append((REC_STRIPE, 0, 11, f_nacked[0]))
    # ...plus its tombstone (plane._fail_groups), and a LATER settled
    # group whose floor has passed the nacked gsn.
    tomb = encode_group([], 1, 11, tombstone=True, settled_floor=10)
    recs.append((REC_STRIPE, 0, 11, tomb[0]))
    for i, f in enumerate(encode_group(ok2, 1, 12, settled_floor=11)):
        recs.append((REC_STRIPE, i, 12, f))
    got = rebuild_records(iter(recs), [])
    assert got == ok1 + ok2
    # WITHOUT the tombstone the same leftovers are (correctly) read as
    # settled-and-lost: quarantine-grade.
    no_tomb = [r for r in recs if r[3] != tomb[0]]
    with pytest.raises(StripeDataLossError):
        rebuild_records(iter(no_tomb), [])


def test_catchup_groups_replay_before_same_epoch_live_groups():
    from ripplemq_tpu.storage.segment import REC_STRIPE

    # Live group (low gsn) carries rows 8.. ; the catch-up group
    # (HIGHER gsn, cu flag) carries the prefix rows 0.. — replay must
    # order catch-up first or the prefix would truncate the live rows.
    live = [(1, 0, 8, b"live-rows" * 4)]
    prefix = [(1, 0, 0, b"prefix-rows" * 8)]
    recs = []
    for i, f in enumerate(encode_group(live, 3, 50)):
        recs.append((REC_STRIPE, i, 50, f))
    for i, f in enumerate(encode_group(prefix, 3, 90, catchup=True)):
        recs.append((REC_STRIPE, i, 90, f))
    got = rebuild_records(iter(recs), [])
    assert got == prefix + live


# --------------------------------------------------------- clusters

def _wait(pred, timeout=30.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _mk_cluster(tmp_path, name, replication, n_brokers=3):
    from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
    from ripplemq_tpu.metadata.models import Topic

    config = make_cluster_config(
        n_brokers=n_brokers, topics=(Topic("t", 1, 3),),
        replication=replication,
    )
    cluster = InProcCluster(config, data_dir=str(tmp_path / name))
    cluster.start()
    cluster.wait_for_leaders()
    assert _wait(cluster.controller_ready), "no standby joined"
    return cluster


def _drain(cluster, consumer_name, expect_at_least=0, timeout=30.0):
    from ripplemq_tpu.client import ConsumerClient

    boot = [b.address for b in cluster.config.brokers]
    cons = ConsumerClient(boot, consumer_name,
                          transport=cluster.client(consumer_name),
                          metadata_refresh_s=0.3)
    got, idle = [], 0
    deadline = time.time() + timeout
    try:
        while idle < 8 and time.time() < deadline:
            try:
                batch = cons.consume("t", partition=0, max_messages=16)
            except Exception:
                idle += 1
                time.sleep(0.2)
                continue
            if batch:
                got.extend(batch)
                idle = 0
                if expect_at_least and len(got) >= expect_at_least:
                    # Two clean empties confirm the tail.
                    expect_at_least = 0
            else:
                idle += 1
                time.sleep(0.1)
    finally:
        cons.close()
    return [m.decode() for m in got]


def test_full_and_striped_committed_prefixes_are_identical(tmp_path):
    from ripplemq_tpu.client import ProducerClient

    logs = {}
    for mode in ("full", "striped"):
        cluster = _mk_cluster(tmp_path, mode, mode)
        try:
            boot = [b.address for b in cluster.config.brokers]
            prod = ProducerClient(boot, transport=cluster.client("p"),
                                  metadata_refresh_s=0.3)
            for i in range(24):
                prod.produce("t", f"msg-{i}".encode(), partition=0)
            prod.close()
            logs[mode] = _drain(cluster, f"auditor-{mode}",
                                expect_at_least=24)
        finally:
            cluster.stop()
    assert logs["full"] == logs["striped"]
    assert logs["full"][:24] == [f"msg-{i}" for i in range(24)]


def test_striped_promotion_rebuilds_committed_prefix(tmp_path):
    from ripplemq_tpu.client import ProducerClient

    cluster = _mk_cluster(tmp_path, "promo", "striped", n_brokers=4)
    try:
        boot = [b.address for b in cluster.config.brokers]
        st = cluster.client("s").call(boot[0], {"type": "admin.stats"},
                                      timeout=5.0)
        assert st["stripe_mode"] == "striped"
        assert len(st["stripe_holders"]) == N
        assert set(st["stripe_holders"]) <= set(
            st["controller"]["standbys"]
        )
        prod = ProducerClient(boot, transport=cluster.client("p"),
                              metadata_refresh_s=0.3)
        for i in range(30):
            prod.produce("t", f"pre-{i}".encode(), partition=0)
        ctrl = st["controller"]["id"]
        cluster.kill(ctrl)
        # The promoted standby must REBUILD the full stream from any k
        # surviving stripes and accept fresh writes.
        ok = _wait(lambda: _try_produce(prod), timeout=60.0, interval=0.2)
        assert ok, "no post-failover produce"
        log = _drain(cluster, "promo-auditor", expect_at_least=31,
                     timeout=45.0)
        assert log[:30] == [f"pre-{i}" for i in range(30)]
        assert "post" in log
        rebuilds = sum(
            b._stripe_rebuilds for i, b in cluster.brokers.items()
            if not b._stopped
        )
        assert rebuilds >= 1
        prod.close()
    finally:
        cluster.stop()


def _try_produce(prod):
    try:
        prod.produce("t", b"post", partition=0)
        return True
    except Exception:
        return False


def test_repl_stripes_handler_refuses_corrupt_frames(tmp_path):
    cluster = _mk_cluster(tmp_path, "crc", "striped")
    try:
        st = cluster.client("s").call(
            cluster.broker_addr(0), {"type": "admin.stats"}, timeout=5.0
        )
        standby = st["controller"]["standbys"][0]
        epoch = st["controller"]["epoch"]
        frames = encode_group(RECORDS, epoch, 999_999)
        bad = bytearray(frames[0])
        bad[25] ^= 0xFF
        resp = cluster.brokers[standby].dispatch({
            "type": "repl.stripes", "epoch": epoch,
            "frames": [bytes(bad)],
        })
        assert not resp.get("ok")
        assert resp.get("error") == "bad_stripe_frame"
        # The intact frame lands.
        resp = cluster.brokers[standby].dispatch({
            "type": "repl.stripes", "epoch": epoch,
            "frames": [frames[0]],
        })
        assert resp.get("ok"), resp
    finally:
        cluster.stop()


def test_checker_stripe_contract_gates_on_m():
    from ripplemq_tpu.chaos.history import check_history

    ops = [{
        "op": "produce", "client": "p", "topic": "t", "partition": 0,
        "payload": "lost", "status": "ok", "attempts": 1, "i": 0,
        "t": 0.0,
    }]
    logs = {("t", 0): []}
    # Within the k-of-k+m contract (<= m holders down): absolute.
    v = check_history(ops, logs, stripe={"k": RS_K, "m": RS_M,
                                         "holders_down": RS_M})
    assert any("acked loss" in x for x in v)
    # Beyond it: the documented beyond-contract regime.
    v = check_history(ops, logs, stripe={"k": RS_K, "m": RS_M,
                                         "holders_down": RS_M + 1})
    assert v == []

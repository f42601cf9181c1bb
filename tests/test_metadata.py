"""Metadata plane: assigner properties, models, config loader.

The assigner's contract mirrors the reference PartitionAssigner
(mq-broker/src/main/java/metadata/PartitionAssigner.java:25-115): sticky,
least-loaded top-up, error on infeasible RF. SURVEY.md §4 calls for
property tests here — the reference had none.
"""

import random

import pytest

from ripplemq_tpu.core.config import EngineConfig
from ripplemq_tpu.metadata import (
    BrokerInfo,
    PartitionAssignment,
    Topic,
    assign_partitions,
)
from ripplemq_tpu.metadata.cluster_config import (
    ClusterConfig,
    parse_cluster_config,
)
from ripplemq_tpu.metadata.models import topics_from_wire, topics_to_wire


def mk_topics(spec):
    return [Topic(name, parts, rf) for name, parts, rf in spec]


def all_assignments(topics):
    return [(t.name, a) for t in topics for a in t.assignments]


def test_assign_satisfies_rf_and_uniqueness():
    topics = mk_topics([("t1", 3, 3), ("t2", 5, 2)])
    out = assign_partitions(topics, live_brokers=[0, 1, 2, 3, 4])
    for name, a in all_assignments(out):
        t = next(t for t in out if t.name == name)
        assert len(a.replicas) == t.replication_factor
        assert len(set(a.replicas)) == len(a.replicas)  # no duplicate replica


def test_assign_balances_load():
    topics = mk_topics([("t", 10, 3)])
    out = assign_partitions(topics, live_brokers=list(range(5)))
    load = {b: 0 for b in range(5)}
    for _, a in all_assignments(out):
        for b in a.replicas:
            load[b] += 1
    assert sum(load.values()) == 30
    assert max(load.values()) - min(load.values()) <= 1


def test_assign_deterministic():
    topics = mk_topics([("a", 7, 3), ("b", 4, 2)])
    r1 = assign_partitions(topics, [0, 1, 2, 3])
    r2 = assign_partitions(topics, [3, 2, 1, 0])  # order must not matter
    assert r1 == r2


def test_assign_sticky_keeps_live_replicas():
    topics = mk_topics([("t", 4, 3)])
    first = assign_partitions(topics, [0, 1, 2, 3, 4])
    # Kill broker 0; survivors must be retained.
    second = assign_partitions(topics, [1, 2, 3, 4], previous=first)
    for t_first, t_second in zip(first, second):
        for a1, a2 in zip(t_first.assignments, t_second.assignments):
            kept = [b for b in a1.replicas if b != 0]
            assert all(b in a2.replicas for b in kept)
            assert 0 not in a2.replicas
            assert len(a2.replicas) == 3


def test_assign_leader_retained_or_cleared():
    topics = mk_topics([("t", 2, 3)])
    first = assign_partitions(topics, [0, 1, 2])
    with_leaders = [
        t.with_assignments(
            tuple(
                PartitionAssignment(a.partition_id, a.replicas, a.replicas[0])
                for a in t.assignments
            )
        )
        for t in first
    ]
    # Leader broker stays alive → retained.
    same = assign_partitions(topics, [0, 1, 2], previous=with_leaders)
    for t in same:
        for a in t.assignments:
            assert a.leader is not None
    # Kill every leader → cleared (unknown until re-election).
    dead = {a.leader for t in with_leaders for a in t.assignments}
    alive = [b for b in [0, 1, 2, 3, 4] if b not in dead]
    healed = assign_partitions(topics, alive, previous=with_leaders)
    for t in healed:
        for a in t.assignments:
            assert a.leader is None


def test_assign_preserves_replica_slot_positions():
    """A surviving broker must keep its INDEX in the replicas tuple: the
    index is its physical replica slot in the device state, and per-slot
    logs never move on reassignment. The replacement for a dead broker
    must occupy the dead broker's position (it inherits that stale
    physical slot and gets resynced), not shift everyone else."""
    topics = mk_topics([("t", 4, 3)])
    first = assign_partitions(topics, [0, 1, 2, 3, 4])
    for victim in [0, 1, 2, 3, 4]:
        live = [b for b in [0, 1, 2, 3, 4] if b != victim]
        second = assign_partitions(topics, live, previous=first)
        for t1, t2 in zip(first, second):
            for a1, a2 in zip(t1.assignments, t2.assignments):
                assert len(a2.replicas) == len(a1.replicas)
                for i, b in enumerate(a1.replicas):
                    if b != victim:
                        assert a2.replicas[i] == b, (
                            f"survivor {b} moved from slot {i} "
                            f"to {a2.replicas.index(b)}"
                        )
                    else:
                        assert a2.replicas[i] != victim


def test_assign_positions_stable_under_churn():
    """Position stability holds across arbitrary membership churn, not
    just single failures."""
    rng = random.Random(13)
    topics = mk_topics([("x", 6, 3)])
    live = {0, 1, 2, 3, 4}
    prev = assign_partitions(topics, sorted(live))
    for _ in range(40):
        if len(live) > 3 and rng.random() < 0.5:
            live.discard(rng.choice(sorted(live)))
        else:
            live.add(rng.randrange(8))
        new = assign_partitions(topics, sorted(live), previous=prev)
        for t_new, t_prev in zip(new, prev):
            for a_new, a_prev in zip(t_new.assignments, t_prev.assignments):
                for i, b in enumerate(a_prev.replicas):
                    if b in live:
                        assert a_new.replicas[i] == b
        prev = new


def test_assign_infeasible_rf_raises():
    topics = mk_topics([("t", 1, 3)])
    with pytest.raises(ValueError):
        assign_partitions(topics, [0, 1])


def test_assign_no_live_brokers_raises():
    with pytest.raises(ValueError):
        assign_partitions(mk_topics([("t", 1, 1)]), [])


def test_assign_random_membership_churn_property():
    """Whatever sequence of joins/crashes happens, every assignment stays
    valid: RF met, all replicas live, sticky where possible."""
    rng = random.Random(7)
    topics = mk_topics([("x", 6, 3), ("y", 3, 2)])
    live = {0, 1, 2, 3, 4}
    prev = assign_partitions(topics, sorted(live))
    for _ in range(30):
        if len(live) > 3 and rng.random() < 0.5:
            live.discard(rng.choice(sorted(live)))
        else:
            live.add(rng.randrange(10))
        new = assign_partitions(topics, sorted(live), previous=prev)
        for t in new:
            for a in t.assignments:
                assert len(a.replicas) == t.replication_factor
                assert set(a.replicas) <= live
                prev_t = next(p for p in prev if p.name == t.name)
                pa = prev_t.assignment_for(a.partition_id)
                survivors = [b for b in pa.replicas if b in live][
                    : t.replication_factor
                ]
                assert all(b in a.replicas for b in survivors)
        prev = new


def test_models_wire_roundtrip():
    t = Topic(
        "orders-eu",  # dash in name must be safe (fixed reference quirk)
        2,
        3,
        (
            PartitionAssignment(0, (1, 2, 3), 2),
            PartitionAssignment(1, (0, 1, 4), None),
        ),
    )
    [back] = topics_from_wire(topics_to_wire([t]))
    assert back == t


def test_parse_cluster_config_both_schemas():
    raw = {
        "brokers": [
            {"id": 1, "hostname": "broker1", "port": 9092},   # reference schema
            {"broker_id": 2, "host": "b2", "port": 9093},     # native schema
        ],
        "topics": [
            {"name": "topic1", "partitions": 3, "replicationFactor": 2},
            {"name": "topic2", "partitions": 2, "replication_factor": 2},
        ],
    }
    cfg = parse_cluster_config(raw)
    assert cfg.broker(1) == BrokerInfo(1, "broker1", 9092)
    assert cfg.broker(2).host == "b2"
    assert cfg.engine.partitions == 5  # sum of topic partitions
    assert cfg.engine.replicas == 2
    assert cfg.topics[0].replication_factor == 2


def test_parse_cluster_config_operational_knobs():
    """Round-4 knobs reach the config value (and default sanely): the
    batcher operating point, RPC worker pool, and linearizable reads."""
    raw = {
        "brokers": [{"id": 0, "host": "h", "port": 1}],
        "topics": [{"name": "t", "partitions": 1, "replication_factor": 1}],
        "coalesce_s": 0.01,
        "chain_depth": 8,
        "pipeline_depth": 16,
        "rpc_workers": 128,
        "linearizable_reads": True,
    }
    cfg = parse_cluster_config(raw)
    assert cfg.coalesce_s == 0.01
    assert cfg.chain_depth == 8
    assert cfg.pipeline_depth == 16
    assert cfg.rpc_workers == 128
    assert cfg.linearizable_reads is True
    defaults = parse_cluster_config(
        {"brokers": raw["brokers"], "topics": raw["topics"]}
    )
    assert defaults.coalesce_s == 0.002
    assert defaults.chain_depth == 4
    assert defaults.pipeline_depth == 8
    assert defaults.rpc_workers == 16
    assert defaults.linearizable_reads is False


_ONE_BROKER = {
    "brokers": [{"id": 0, "host": "h", "port": 1}],
    "topics": [{"name": "t", "partitions": 1, "replication_factor": 1}],
}


@pytest.mark.parametrize("where,key,only", [
    ("engine", "fused_control", True),
    ("engine", "packed_writes", True),
    (None, "host_workers", 1),
])
def test_parse_drops_retired_engine_keys_when_true(where, key, only):
    """A retired key at the one value it can still mean parses to the
    same cluster as a file without it. Cluster files written before
    PR 29 carry `fused_control: true` / `packed_writes: true`: what
    they asked for is the only round there is. Every deployment file
    of the benchmark carries `host_workers: 1`: the one host path
    (PR 52)."""
    plain = {**_ONE_BROKER, "engine": {"slots": 128}}
    old = {**plain, "engine": dict(plain["engine"])}
    (old["engine"] if where else old)[key] = only
    cfg = parse_cluster_config(old)
    assert cfg == parse_cluster_config(plain) and cfg.engine.slots == 128
    assert not hasattr(cfg.engine if where else cfg, key)


@pytest.mark.parametrize("where,key,value,pr", [
    ("engine", "fused_control", False, "PR 29"),
    ("engine", "packed_writes", False, "PR 29"),
    ("engine", "packed_writes", 1, "PR 29"),   # not "true": not the value
    (None, "host_workers", 2, "PR 52"),
    (None, "host_workers", 0, "PR 52"),
    (None, "host_workers", True, "PR 52"),
])
def test_parse_refuses_retired_engine_keys_when_false(where, key, value, pr):
    """Any other value of a retired key is refused at parse, by a
    message that names the key, the value and the PR that removed the
    choice: never ignored."""
    raw = {**_ONE_BROKER, "engine": {}}
    (raw["engine"] if where else raw)[key] = value
    name = f"{where}.{key}" if where else key
    with pytest.raises(ValueError, match=rf"{name}: {value!r} is no longer "
                                         rf"possible.*removed \({pr}\)"):
        parse_cluster_config(raw)


@pytest.mark.parametrize("key", ["fused_control", "packed_writes"])
def test_engine_knows_no_retired_field(key):
    with pytest.raises(TypeError):
        EngineConfig(**{key: True})


@pytest.mark.parametrize("value", [1, 2])
def test_cluster_config_knows_no_host_workers_field(value):
    """The option is gone from the config itself: the keyword is a
    TypeError at any value, the one a file may still say included."""
    cfg = parse_cluster_config(_ONE_BROKER)
    with pytest.raises(TypeError, match="host_workers"):
        ClusterConfig(brokers=cfg.brokers, topics=cfg.topics,
                      engine=cfg.engine, host_workers=value)


def test_parse_unknown_engine_key_is_still_an_error():
    with pytest.raises(TypeError, match="fused_writes"):
        parse_cluster_config({**_ONE_BROKER, "engine": {"fused_writes": True}})


def test_parse_rejects_linearizable_reads_without_standbys():
    """`linearizable_reads: true` with `standby_count: 0` would make the
    read barrier a silent no-op (no standby ack stream to prove the
    controller epoch through) — the combination is an explicit parse
    error, not a code-comment contract (VERDICT r4 weak-#6)."""
    raw = {
        "brokers": [{"id": 0, "host": "h", "port": 1}],
        "topics": [{"name": "t", "partitions": 1, "replication_factor": 1}],
        "linearizable_reads": True,
        "standby_count": 0,
    }
    with pytest.raises(ValueError, match="standby_count"):
        parse_cluster_config(raw)
    raw["standby_count"] = 1
    assert parse_cluster_config(raw).linearizable_reads is True

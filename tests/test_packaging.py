"""Deployment packaging consistency: the compose topology, the docker
cluster config, and the broker CLI must agree (the reference ships the
same triple: Dockerfile + docker-compose.yml + cluster_config.yaml,
mq-broker/docker-compose.yml:1-55)."""

from __future__ import annotations

import os

import yaml

from ripplemq_tpu.metadata.cluster_config import load_cluster_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_docker_cluster_config_loads_and_matches_compose():
    config = load_cluster_config(os.path.join(REPO, "examples",
                                              "cluster.docker.yaml"))
    with open(os.path.join(REPO, "docker-compose.yml")) as f:
        compose = yaml.safe_load(f)

    services = compose["services"]
    assert len(services) == len(config.brokers) == 5
    for b in config.brokers:
        name = f"broker{b.broker_id}"
        svc = services[name]
        # Broker addresses use the compose hostname on the internal port.
        assert svc["hostname"] == b.host
        assert svc["command"] == ["--id", str(b.broker_id)]
        # Every mapped port targets the container port the broker binds.
        assert svc["ports"][0].endswith(f":{b.port}")
        # Durable state is volume-backed (controller failover + shard
        # distribution assume per-broker persistent dirs).
        assert any(v.endswith(":/data") for v in svc["volumes"])
    # Host-side ports are distinct (clients bootstrap against any).
    host_ports = {s["ports"][0].split(":")[0] for s in services.values()}
    assert len(host_ports) == 5


def test_local_example_config_loads():
    config = load_cluster_config(os.path.join(REPO, "examples",
                                              "cluster.yaml"))
    assert len(config.brokers) == 5
    assert {t.name for t in config.topics} == {"topic1", "topic2"}


def test_1kb_example_config_loads_and_is_sized_as_the_readme_says():
    """examples/cluster-1kb.yaml: a 1,024 B record fits its slot, the
    append kernel's VMEM block fits a TPU, and the ring is what the
    file's comment says."""
    from ripplemq_tpu.ops.append import append_backend, check_entries_block

    config = load_cluster_config(os.path.join(REPO, "examples",
                                              "cluster-1kb.yaml"))
    e = config.engine
    assert e.slot_bytes % 128 == 0 and e.payload_bytes >= 1024
    assert e.partitions >= sum(t.partitions for t in config.topics)
    assert append_backend(e.slot_bytes, "tpu") == "pallas"
    check_entries_block(e.slot_bytes, e.max_batch, e.partitions,
                        "TPU v5 lite")
    ring = e.replicas * e.partitions * (e.slots + e.max_batch) * e.slot_bytes
    assert ring == 9_732_096
    assert config.standby_count == 2 and config.replication == "full"


def test_dockerfile_entrypoint_matches_cli():
    """The ENTRYPOINT flags must be real broker CLI flags (argparse would
    exit 2 on drift) and reference files the image actually copies."""
    with open(os.path.join(REPO, "Dockerfile")) as f:
        content = f.read()
    assert '"--config", "/app/examples/cluster.docker.yaml"' in content
    assert '"--data-dir", "/data"' in content
    assert "COPY ripplemq_tpu /app/ripplemq_tpu" in content
    assert "COPY native /app/native" in content  # segstore source
    # The flags parse (an unknown flag would SystemExit(2) from argparse
    # before reaching the roster check, which returns 2 instead).
    from ripplemq_tpu.broker import __main__ as broker_main

    rc = broker_main.main([
        "--id", "99",  # not in the roster: fails AFTER parsing
        "--config", os.path.join(REPO, "examples", "cluster.docker.yaml"),
        "--data-dir", "/tmp/pkg-test",
    ])
    assert rc == 2

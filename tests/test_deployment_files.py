"""Every deployment file of the benchmark still parses into a cluster.

`benchmarks/run.py` writes a file's `cluster` block, as it is, into the
`cluster.yaml` each broker parses, beside the deployment's topics and
one address a broker. A PR that removes an option may not edit those
files, so the parser has to go on taking what they say: this is where
that is found out on the CPU, and not as failed cells on the chip. The
files are read as data; nothing of `benchmarks/` is imported.
"""

from __future__ import annotations

import copy
import glob
import json
import os

import pytest

from ripplemq_tpu.metadata.cluster_config import (
    ClusterConfig,
    parse_cluster_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# keys a file may still carry though the choice behind them is gone
RETIRED = {"engine.fused_control", "engine.packed_writes", "host_workers"}
CONFIGS = sorted(
    os.path.basename(p) for p in
    glob.glob(os.path.join(REPO, "benchmarks", "configs", "*.json")))


def _overlaid(base: dict, over: dict) -> dict:
    """`over` laid on `base`, dict by dict (the harness's own rule)."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (_overlaid(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else copy.deepcopy(v))
    return out


def test_there_are_deployment_files():
    assert len(CONFIGS) >= 6, CONFIGS


@pytest.mark.parametrize("rehearse", [False, True],
                         ids=["as-written", "rehearsal"])
@pytest.mark.parametrize("name", CONFIGS)
def test_deployment_file_parses_into_a_cluster(name, rehearse):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as f:
        config = json.load(f)
    if rehearse:
        config = _overlaid(config, config.get("rehearsal", {}))
    dep = config["deployment"]
    raw = dict(config["cluster"])
    raw["brokers"] = [{"id": i, "host": "127.0.0.1", "port": 7000 + i}
                      for i in range(int(dep["brokers"]))]
    raw["topics"] = dep["topics"]
    # what a traced run adds (benchmarks/run.py boot)
    raw["trace_sample_n"] = 8

    cfg = parse_cluster_config(json.loads(json.dumps(raw)))

    assert isinstance(cfg, ClusterConfig)
    assert len(cfg.brokers) == int(dep["brokers"])
    assert [(t.name, t.partitions, t.replication_factor)
            for t in cfg.topics] == [
        (t["name"], t["partitions"],
         t.get("replication_factor", t.get("replicationFactor", 1)))
        for t in dep["topics"]]
    # the engine holds the topics: a slot for every partition, a replica
    # row for the widest topic, a row wide enough for the deployment's
    # message
    assert cfg.engine.partitions >= sum(t.partitions for t in cfg.topics)
    assert cfg.engine.replicas >= max(
        t.replication_factor for t in cfg.topics)
    assert cfg.engine.payload_bytes >= int(dep["message_bytes"])
    # every key the file names landed in the config as written, but for
    # the retired ones, which only the parser still knows (and refuses
    # at any other value): no key is dropped without a word
    said = {f"engine.{k}": v for k, v in raw.get("engine", {}).items()}
    said.update((k, v) for k, v in config["cluster"].items()
                if k != "engine")
    unknown = set()
    for key, value in said.items():
        holder = cfg.engine if key.startswith("engine.") else cfg
        name = key.rpartition(".")[2]
        if not hasattr(holder, name):
            unknown.add(key)
        elif isinstance(value, dict):  # a mapping is kept as sorted pairs
            assert dict(getattr(holder, name)) == {
                (int(k) if k.isdigit() else k): v
                for k, v in value.items()}, key
        else:
            assert getattr(holder, name) == value, key
    assert unknown <= RETIRED, unknown - RETIRED

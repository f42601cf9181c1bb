"""Causal-trace viewer: sampled produce/consume critical-path trees.

The collection + attribution surface of the tracing plane (obs/spans.py
for the rings and wire propagation, obs/assemble.py for the skew model
and tree join). Two modes:

1. Live demo (default): boot an in-proc 3-broker cluster with tracing
   on (`trace_sample_n=1` — every call sampled), run a few produces and
   consumes, page every broker's `admin.spans` ring, merge in the
   client rings, assemble, and render each trace as an attributed tree:

       trace 0x... root=client.produce ack=1.9ms coverage=96% ...
           +0.000ms client.produce  ...
           +0.115ms rpc.recv        ...  [broker0]
           ...

   `--striped` switches replication to the striped plane
   (stripe.send/stripe.apply spans).

2. Offline (`--from-json FILE`): render traces from records on disk —
   either a bare JSON list of span records, or a chaos verdict (the
   harness embeds every postmortem bundle's span ring under
   `postmortems.*.spans` and its own assembled `traces`).

No wall clocks anywhere: every placement is in the root span's
monotonic domain via the assembler's NTP-style per-process offsets.

Run: python profiles/trace_view.py
     python profiles/trace_view.py --striped
     python profiles/trace_view.py --from-json verdict.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Runnable as `python profiles/trace_view.py`: the repo root (where
# `ripplemq_tpu` lives) is this file's parent directory.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def collect_spans(client, addrs: list[str],
                  page: int = 512) -> list[dict]:
    """Page every broker's admin.spans ring to exhaustion (the cursor
    contract: `after` = last seq seen, stop when the cursor holds)."""
    records: list[dict] = []
    for addr in addrs:
        after = -1
        while True:
            resp = client.call(addr, {"type": "admin.spans",
                                      "after": after,
                                      "max_spans": page}, timeout=10.0)
            if not resp.get("ok") or not resp.get("spans"):
                break
            records.extend(resp["spans"])
            if resp.get("cursor", after) == after:
                break
            after = resp["cursor"]
    return records


def _live(args) -> list[dict]:
    from ripplemq_tpu.chaos.cluster import (
        InProcCluster,
        make_cluster_config,
    )
    from ripplemq_tpu.client.consumer import ConsumerClient
    from ripplemq_tpu.client.producer import ProducerClient

    kw = dict(obs=True, trace_sample_n=1)
    if args.striped:
        kw["replication"] = "striped"
    cfg = make_cluster_config(n_brokers=3, **kw)
    with InProcCluster(cfg) as cluster:
        cluster.wait_for_leaders()
        prod = ProducerClient(
            [cluster.broker_addr(0)], transport=cluster.client("p"),
            trace_sample_n=1, producer_name="producer/view")
        cons = ConsumerClient(
            [cluster.broker_addr(0)], "consumer/view",
            transport=cluster.client("c"), trace_sample_n=1)
        for i in range(args.messages):
            prod.produce("topic1", b"m%d" % i, partition=0)
        cons.consume("topic1", partition=0, max_messages=args.messages)
        records = collect_spans(
            cluster.client("spans"),
            [cluster.broker_addr(b) for b in cluster.brokers])
        records += prod.spans.snapshot()
        records += cons.spans.snapshot()
        prod.close()
        cons.close()
    return records


def _from_json(path: str) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):
        return doc
    # A chaos verdict: every postmortem bundle carries its span ring.
    return [r for pm in (doc.get("postmortems") or {}).values()
            for r in pm.get("spans") or ()]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--messages", type=int, default=5,
                    help="sampled produces to run in live mode")
    ap.add_argument("--striped", action="store_true",
                    help="striped replication (stripe.send/apply spans)")
    ap.add_argument("--from-json", default=None, metavar="FILE",
                    help="render span records (or a chaos verdict's "
                         "postmortem spans) from disk instead")
    ap.add_argument("--json", action="store_true",
                    help="emit assembled trees as JSON, not rendered")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ripplemq_tpu.obs.assemble import assemble, render

    records = (_from_json(args.from_json) if args.from_json
               else _live(args))
    trees = assemble(records)
    if args.json:
        print(json.dumps(trees, indent=2, default=str))
        return
    print(f"{len(records)} span records -> {len(trees)} trace(s)")
    for tree in trees:
        print()
        print(render(tree))


if __name__ == "__main__":
    main()

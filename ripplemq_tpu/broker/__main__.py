"""Broker process entry: `python -m ripplemq_tpu.broker --id N --config F`.

The reference boots from `ApplicationMain.main` (reference:
mq-broker/src/main/java/app/ApplicationMain.java:12-54 — load YAML, build
BrokerServer, start, register a shutdown hook) and is launched as
`-id N` per container (mq-broker/docker-compose.yml:8). Same shape here,
with two documented deviations: the broker id is a proper `--id` flag
(the reference checks `args.length < 1` but reads `args[1]` —
ApplicationMain.java:15-20), and the process exits non-zero on a bad
config instead of stack-tracing.

A 5-broker cluster equivalent to the reference's docker-compose is:

    for i in 0 1 2 3 4; do
        python -m ripplemq_tpu.broker --id $i --config examples/cluster.yaml \
            --data-dir /var/lib/ripplemq &
    done
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m ripplemq_tpu.broker",
        description="Start one RippleMQ-TPU broker.",
    )
    ap.add_argument("--id", type=int, required=True, dest="broker_id",
                    help="this broker's id (must appear in the config roster)")
    ap.add_argument("--config", required=True,
                    help="cluster config YAML (roster + topics + engine)")
    ap.add_argument("--data-dir", default=None,
                    help="durable storage root; segments + metadata live "
                         "under <data-dir>/broker-<id>/ (omit for in-memory)")
    ap.add_argument("--engine-mode", default="local",
                    choices=["local", "spmd"],
                    help="device binding for the controller's engine: "
                         "'local' vmaps replicas on one chip, 'spmd' shards "
                         "a (replica x part) device mesh")
    ap.add_argument("--log-level", default="INFO",
                    help="console log level for the ripplemq loggers "
                         "(DEBUG/INFO/WARNING/ERROR)")
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON object per log line (ts/level/"
                         "subsystem/broker/thread/msg) instead of the "
                         "log4j2-style pattern — machine-greppable next "
                         "to the telemetry plane's event timeline")
    ap.add_argument("--coordinator", default=None,
                    help="multi-host SPMD: host 0's host:port for "
                         "jax.distributed (run the controller with "
                         "--engine-mode spmd on every participating "
                         "host; see parallel.multihost_check)")
    ap.add_argument("--num-hosts", type=int, default=1,
                    help="multi-host SPMD: number of participating hosts")
    ap.add_argument("--host-index", type=int, default=0,
                    help="multi-host SPMD: this process's index")
    ap.add_argument("--engine-workers", default=None,
                    help="multi-host SPMD: comma-separated host:port of "
                         "the engine workers on the other hosts (run "
                         "python -m ripplemq_tpu.parallel.worker there); "
                         "required with --coordinator so every process "
                         "launches each mesh computation")
    args = ap.parse_args(argv)

    from ripplemq_tpu.broker.server import BrokerServer
    from ripplemq_tpu.metadata.cluster_config import load_cluster_config
    from ripplemq_tpu.utils.logs import configure_logging

    configure_logging(args.log_level, json_lines=args.log_json,
                      broker_id=args.broker_id)
    # Any broker can come to own the engine programs (genesis controller
    # or promoted standby): place the compile cache before the first one.
    from ripplemq_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    if args.coordinator is not None:
        # Join the global mesh BEFORE any other JAX use: after this,
        # jax.devices() is the global device list and the controller's
        # spmd engine spans every host (collectives ride ICI within a
        # host, DCN across).
        from ripplemq_tpu.parallel.mesh import init_distributed

        n = init_distributed(args.coordinator, args.num_hosts,
                             args.host_index)
        print(f"joined {args.num_hosts}-host mesh: {n} global devices",
              flush=True)

    try:
        config = load_cluster_config(args.config)
        config.broker(args.broker_id)  # fail fast on an id not in the roster
    except (OSError, ValueError, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    data_dir = None
    if args.data_dir is not None:
        data_dir = os.path.join(args.data_dir, f"broker-{args.broker_id}")
        os.makedirs(data_dir, exist_ok=True)

    workers = None
    if args.engine_workers:
        workers = [w.strip() for w in args.engine_workers.split(",") if w.strip()]
    if args.coordinator is not None and args.num_hosts > 1:
        if not workers:
            print("error: --coordinator with --num-hosts > 1 requires "
                  "--engine-workers (every process of a jax.distributed "
                  "mesh must launch each computation; run "
                  "python -m ripplemq_tpu.parallel.worker on the other "
                  "hosts)", file=sys.stderr)
            return 2
        if args.engine_mode != "spmd":
            print("error: --coordinator with --num-hosts > 1 requires "
                  "--engine-mode spmd (mode 'local' would silently serve "
                  "from this host's devices alone while the workers wait "
                  "forever)", file=sys.stderr)
            return 2

    server = BrokerServer(
        args.broker_id, config,
        net=None,  # real TCP sockets
        engine_mode=args.engine_mode,
        data_dir=data_dir,
        engine_workers=workers,
    )

    stop = threading.Event()

    def _on_signal(signum, frame):  # the reference's shutdown hook
        stop.set()

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)

    try:
        server.start()
        role = "controller" if server.is_controller else "frontend"
        print(
            f"ripplemq-tpu broker {args.broker_id} ({role}) serving on "
            f"{server.addr}",
            flush=True,
        )
        while not stop.wait(timeout=1.0):
            pass
    finally:
        server.stop()
        print(f"broker {args.broker_id} stopped", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

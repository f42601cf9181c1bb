"""Engine-worker process: one non-controller host of a multi-host mesh.

Run one per additional host (the controller broker runs on host 0 with
--coordinator/--engine-workers; see broker/__main__.py):

    python -m ripplemq_tpu.parallel.worker \
        --coordinator host0:9777 --num-hosts 2 --host-index 1 \
        --listen-port 9810

The worker starts its TCP endpoint FIRST (so the controller's first
lockstep broadcast always lands), then joins the jax.distributed mesh
(which blocks until every host arrives), then replays the controller's
engine-call stream (parallel.lockstep) until terminated. The engine
shape arrives in the controller's `configure` call — no shape flags
needed here — so the worker compiles the EXACT program the controller
launches; a mesh whose processes disagree on the compiled program
deadlocks at the first collective.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m ripplemq_tpu.parallel.worker")
    ap.add_argument("--coordinator", required=True, help="host0's host:port")
    ap.add_argument("--num-hosts", type=int, required=True)
    ap.add_argument("--host-index", type=int, required=True)
    ap.add_argument("--listen-host", default="0.0.0.0")
    ap.add_argument("--listen-port", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=0,
                    help="force N virtual CPU devices (testing without "
                         "real chips); 0 = the platform's real devices")
    ap.add_argument("--log-level", default="INFO")
    args = ap.parse_args(argv)

    if args.local_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        flags = " ".join(
            f for f in flags.split()
            if "xla_force_host_platform_device_count" not in f
        )
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.local_devices}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if args.local_devices:
        jax.config.update("jax_platforms", "cpu")

    from ripplemq_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    from ripplemq_tpu.parallel.lockstep import LOCKSTEP_TYPE, LockstepWorker
    from ripplemq_tpu.parallel.mesh import init_distributed
    from ripplemq_tpu.utils.logs import configure_logging, get_logger
    from ripplemq_tpu.wire.transport import TcpServer

    configure_logging(args.log_level)
    log = get_logger("worker")

    worker = LockstepWorker()

    def dispatch(req: dict) -> dict:
        if req.get("type") == LOCKSTEP_TYPE:
            return worker.handle(req)
        return {"ok": False, "error": f"unknown request {req.get('type')!r}"}

    server = TcpServer(args.listen_host, args.listen_port, dispatch)
    server.start()  # listening BEFORE the mesh forms (see module doc)
    n = init_distributed(args.coordinator, args.num_hosts, args.host_index)
    log.info("engine worker %d/%d up: %d global devices, listening on %s:%d",
             args.host_index, args.num_hosts, n,
             args.listen_host, args.listen_port)
    print(f"WORKER_READY host={args.host_index} devices={n}", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        while not stop.wait(timeout=1.0):
            pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-host SPMD proof: one replication round committed across OS
processes.

Run the SAME command on every host (here: two processes on one machine,
each contributing virtual CPU devices — the same wiring carries real
TPU pods, where each host contributes its local chips over ICI and the
processes meet over DCN):

    python -m ripplemq_tpu.parallel.multihost_check \
        --coordinator 127.0.0.1:9777 --num-hosts 2 --host-index {0,1}

Each process joins the jax.distributed coordination service, builds ONE
global (replica x part) mesh over all hosts' devices, and executes a
full data round + election round. The quorum psum then physically
crosses the process boundary — this is the DCN claim of parallel.mesh
made executable (and is what tests/test_multihost.py asserts in CI).
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m ripplemq_tpu.parallel.multihost_check")
    ap.add_argument("--coordinator", required=True, help="host0's host:port")
    ap.add_argument("--num-hosts", type=int, required=True)
    ap.add_argument("--host-index", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=0,
                    help="force N virtual CPU devices on this process "
                         "(testing without real multi-chip hosts); 0 = "
                         "use the platform's real devices")
    args = ap.parse_args(argv)

    if args.local_devices:
        # Must precede JAX backend init.
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

    import numpy as np

    from ripplemq_tpu.core.config import EngineConfig
    from ripplemq_tpu.core.encode import build_step_input
    from ripplemq_tpu.parallel.engine import make_spmd_fns
    from ripplemq_tpu.parallel.mesh import init_distributed, make_mesh, pick_axes

    n = init_distributed(args.coordinator, args.num_hosts, args.host_index)
    replicas, part_shards = pick_axes(n)
    P = 2 * part_shards
    # The control phase's stacked leader-broadcast psum is the
    # collective that crosses the process boundary here.
    cfg = EngineConfig(
        partitions=P, replicas=replicas, slots=64, slot_bytes=32,
        max_batch=8, read_batch=8, max_consumers=8, max_offset_updates=4,
    )
    mesh = make_mesh(replicas, part_shards)
    fns = make_spmd_fns(cfg, mesh)
    state = fns.init()

    # Data round: identical host inputs on every process (the controller
    # broadcast); the ballot psum crosses the process boundary.
    inp = build_step_input(
        cfg, appends={p: [b"mh-%d" % p] for p in range(P)}, leader=0, term=1
    )
    alive = np.ones((P, replicas), bool)
    quorum = np.full((P,), cfg.quorum, np.int32)
    state, out = fns.step(state, inp, alive, quorum)
    committed = np.asarray(out.committed)  # outputs are fully replicated
    assert committed.all(), f"round did not commit: {committed}"
    assert (np.asarray(out.votes) == replicas).all()

    # Election round across the same mesh.
    state, elected, votes = fns.vote(
        state, np.zeros((P,), np.int32), np.full((P,), 2, np.int32),
        alive, quorum,
    )
    assert np.asarray(elected).all(), "election failed"
    jax.block_until_ready(jax.tree.leaves(state))
    print(
        f"MULTIHOST_OK host={args.host_index}/{args.num_hosts} "
        f"devices={n} mesh=({replicas}x{part_shards})",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

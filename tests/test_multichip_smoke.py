"""Tier-1 multichip smoke: one spmd step + read on the full
8-virtual-device CPU mesh, in the DEFAULT test selection.

conftest.py forces `XLA_FLAGS=--xla_force_host_platform_device_count=8`
for the whole suite, so the mesh here spans 8 real XLA devices; the
quorum psum and the leader broadcast physically cross device boundaries
(the same wiring carries ICI on a pod slice). The deep scenario
coverage lives in tests/test_spmd.py's parity matrix; this module is
the fast always-on canary, marker-audited into FAST_MODULES
(tests/test_marker_audit.py)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ripplemq_tpu.parallel.engine import make_spmd_fns
from ripplemq_tpu.parallel.mesh import make_mesh, pick_axes
from tests.helpers import decode_read, make_input, small_cfg

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def test_fused_spmd_step_and_read_on_8_device_mesh():
    """One committed spmd round + a cross-shard read + a chained
    launch + an election, on the production mesh shape for 8 devices
    (pick_axes: 2 replicas x 4 partition shards)."""
    replicas, part_shards = pick_axes(8)
    assert (replicas, part_shards) == (2, 4)
    cfg = small_cfg(replicas=replicas, partitions=8)
    mesh = make_mesh(replicas, part_shards)
    assert len(mesh.devices.flatten()) == 8
    fns = make_spmd_fns(cfg, mesh)
    state = fns.init()
    alive = np.ones((replicas,), bool)

    # Data round: appends on both edge shards + an offset commit.
    state, out = fns.step(
        state,
        make_input(cfg, appends={0: [b"m0-a", b"m0-b"], 7: [b"m7"]},
                   offset_updates={0: [(1, 2)]}),
        alive,
    )
    committed = np.asarray(out.committed)
    assert committed[0] and committed[7]

    # Cross-shard reads through the collective path: partition 0 lives
    # on the first part shard, partition 7 on the last.
    data, lens, count = fns.read(state, 0, 0, 0)
    assert decode_read(data, lens, count) == [b"m0-a", b"m0-b"]
    data, lens, count = fns.read(state, replicas - 1, 7, 0)
    assert decode_read(data, lens, count) == [b"m7"]
    assert int(fns.read_offset(state, 0, 0, 1)) == 2

    # Chained launch: 2 complete quorum rounds in one dispatch.
    chain = jax.tree.map(
        lambda x: np.broadcast_to(np.asarray(x),
                                  (2,) + np.asarray(x).shape).copy(),
        make_input(cfg, appends={p: [b"c"] for p in range(8)}),
    )
    state, outs = fns.step_many(state, chain, alive)
    assert np.asarray(outs.committed).all()

    # Election across the mesh (every partition elects replica 1).
    state, elected, votes = fns.vote(
        state, np.ones((8,), np.int32), np.full((8,), 3, np.int32), alive
    )
    assert np.asarray(elected).all()
    assert (np.asarray(votes) == replicas).all()


def test_fused_spmd_quorum_failure_leaves_no_trace_across_shards():
    """Atomicity under the sharded binding: a round refused for
    quorum must leave no trace on ANY shard (ballot-before-write rides
    the replica-axis psum across real device boundaries)."""
    cfg = small_cfg(replicas=2, partitions=8)
    fns = make_spmd_fns(cfg, make_mesh(2, 4))
    state = fns.init()
    state, out = fns.step(
        state, make_input(cfg, appends={3: [b"lost"]}),
        np.array([True, False]),
    )
    assert not bool(np.asarray(out.committed)[3])
    data, lens, count = fns.read(state, 0, 3, 0)
    assert decode_read(data, lens, count) == []
    # The retry commits once quorum returns.
    state, out = fns.step(
        state, make_input(cfg, appends={3: [b"lost"]}), np.ones(2, bool)
    )
    assert bool(np.asarray(out.committed)[3])
    data, lens, count = fns.read(state, 1, 3, 0)
    assert decode_read(data, lens, count) == [b"lost"]

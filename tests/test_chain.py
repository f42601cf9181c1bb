"""Chained rounds: K complete quorum rounds per device dispatch
(engine step_many + the DataPlane burst drain).

Chaining is the dispatch-amortization half of the batching thesis
(SURVEY.md §7 "hard parts": host<->device overhead vs tiny appends) —
the reference pays one RPC + one Raft task per message
(mq-common/.../PartitionClient.java:39); here deep backlogs ride one
launch. Semantics must be EXACTLY K sequential rounds.
"""

import numpy as np
import pytest

from ripplemq_tpu.broker.dataplane import DataPlane
from ripplemq_tpu.core.state import StepInput
from ripplemq_tpu.storage.memstore import MemoryRoundStore
from tests.helpers import make_input, read_all, small_cfg


def _stack(inputs):
    return StepInput(*[
        np.stack([np.asarray(getattr(i, f)) for i in inputs])
        for f in StepInput._fields
    ])


def test_step_many_equals_sequential_steps_local():
    from ripplemq_tpu.parallel.engine import make_local_fns

    cfg = small_cfg(slots=256)
    fns = make_local_fns(cfg)
    alive = np.ones((cfg.replicas,), bool)
    inputs = [
        make_input(cfg, appends={0: [b"k%d" % k], 2: [b"x%d" % k, b"y%d" % k]})
        for k in range(4)
    ]

    seq_state = fns.init()
    seq_outs = []
    for inp in inputs:
        seq_state, out = fns.step(seq_state, inp, alive)
        seq_outs.append(out)

    chain_state, chain_outs = fns.step_many(fns.init(), _stack(inputs), alive)
    for k, out in enumerate(seq_outs):
        np.testing.assert_array_equal(
            np.asarray(out.base), np.asarray(chain_outs.base)[k]
        )
        np.testing.assert_array_equal(
            np.asarray(out.committed), np.asarray(chain_outs.committed)[k]
        )
        np.testing.assert_array_equal(
            np.asarray(out.commit), np.asarray(chain_outs.commit)[k]
        )
    import jax

    for a, b in zip(jax.tree.leaves(seq_state), jax.tree.leaves(chain_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_step_many_equals_sequential_steps_spmd():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from ripplemq_tpu.parallel.engine import make_local_fns, make_spmd_fns
    from ripplemq_tpu.parallel.mesh import make_mesh

    cfg = small_cfg(partitions=4, replicas=2, slots=64)
    local = make_local_fns(cfg)
    spmd = make_spmd_fns(cfg, make_mesh(2, 2))
    alive = np.ones((2,), bool)
    inputs = [
        make_input(cfg, appends={k % 4: [b"c%d" % k]}) for k in range(4)
    ]
    ls, l_outs = local.step_many(local.init(), _stack(inputs), alive)
    ss, s_outs = spmd.step_many(spmd.init(), _stack(inputs), alive)
    for a, b in zip(jax.tree.leaves(l_outs), jax.tree.leaves(s_outs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ls), jax.tree.leaves(ss)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deep_single_slot_queue_drains_in_order_via_chains():
    """A deep backlog on ONE slot (the worst case for the old
    one-round-per-slot-in-flight rule) drains via chained rounds with
    exact offsets and order."""
    cfg = small_cfg(slots=512, max_batch=8)
    dp = DataPlane(cfg, mode="local", store=MemoryRoundStore(),
                   chain_depth=4)
    dp.start()
    try:
        dp.set_leader(0, 0, 1)
        futs = [dp.submit_append(0, [b"deep-%03d" % i]) for i in range(100)]
        offs = [f.result(timeout=60) for f in futs]
        assert len(set(offs)) == 100
        assert offs == sorted(offs)  # FIFO across chained rounds
        msgs, offset = [], 0
        while True:
            got, nxt = dp.read(0, offset, replica=0)
            if nxt == offset:
                break
            msgs.extend(got)
            offset = nxt
        assert msgs == [b"deep-%03d" % i for i in range(100)]
    finally:
        dp.stop()


def test_chain_with_ring_boundary_pad_inside():
    """A chain that crosses the ring boundary mid-chain pads and
    continues — all in one dispatch."""
    cfg = small_cfg(slots=32, max_batch=16)
    dp = DataPlane(cfg, mode="local", store=MemoryRoundStore(),
                   chain_depth=4)
    dp.start()
    try:
        dp.set_leader(0, 0, 1)
        dp.submit_append(0, [b"pre"] * 8).result(timeout=30)  # end=8
        futs = [dp.submit_append(0, [b"w%02d-%d" % (j, i) for i in range(16)])
                for j in range(3)]  # 48 rows: wraps at 32
        offs = [f.result(timeout=30) for f in futs]
        assert offs == [8, 32, 48]  # 24->pad to 32, then contiguous laps
        got, offset = [], 8
        while True:
            g, nxt = dp.read(0, offset, replica=0)
            if nxt == offset:
                break
            got.extend(g)
            offset = nxt
        want = [b"w%02d-%d" % (j, i) for j in range(3) for i in range(16)]
        # the first lap's rows may have been trimmed below the read start
        assert got[-len(want):] == want
    finally:
        dp.stop()


def test_chain_quorum_failure_fails_all_and_preserves_retry_order():
    """Rounds of a chain that lose quorum fail their futures; restoring
    quorum lets retries commit in the original submit order."""
    cfg = small_cfg(slots=256, max_batch=8, replicas=3)
    # The retry budget has to outlast the 0.5 s below at whatever pace the
    # step thread fails rounds: at 50 the futures ran out of rounds inside
    # the sleep ("no quorum after 50 rounds") in 5 of 18 runs on the
    # parent's tree with six copies of this test running at once.
    dp = DataPlane(cfg, mode="local", store=MemoryRoundStore(),
                   chain_depth=4, max_retry_rounds=5000)
    dp.start()
    try:
        dp.set_leader(0, 0, 1)
        dp.submit_append(0, [b"ok"]).result(timeout=30)
        alive = np.ones((cfg.partitions, cfg.replicas), bool)
        alive[:, 1:] = False
        dp.set_alive(alive)
        futs = [dp.submit_append(0, [b"retry-%d" % i]) for i in range(20)]
        import time

        time.sleep(0.5)  # let chained rounds fail and requeue
        dp.set_alive(np.ones((cfg.partitions, cfg.replicas), bool))
        offs = [f.result(timeout=60) for f in futs]
        assert offs == sorted(offs)
        msgs, offset = [], 0
        while True:
            got, nxt = dp.read(0, offset, replica=0)
            if nxt == offset:
                break
            msgs.extend(got)
            offset = nxt
        assert msgs[0] == b"ok"
        assert msgs[1:] == [b"retry-%d" % i for i in range(20)]
    finally:
        dp.stop()

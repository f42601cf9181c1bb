"""SPMD (shard_map over replica × part mesh) vs local (vmap) equivalence.

The same core step code runs under both bindings; on the 8-device virtual
CPU platform we assert bit-identical state evolution. This validates the
multi-chip sharding without TPU hardware (SURVEY.md §7 scale-out).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ripplemq_tpu.parallel.engine import make_local_fns, make_spmd_fns
from ripplemq_tpu.parallel.mesh import make_mesh, pick_axes
from tests.helpers import small_cfg, make_input, decode_read, read_all

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def _scenario(cfg):
    """A few rounds exercising commits, minorities, offsets, multi-leader."""
    R = cfg.replicas
    alive_all = np.ones((R,), bool)
    alive_partial = alive_all.copy()
    alive_partial[-1] = False
    return [
        (make_input(cfg, appends={0: [b"r0-a", b"r0-b"], 3: [b"p3"]}), alive_all),
        (make_input(cfg, appends={1: [b"x"]}, leader={1: R - 1, 0: 0}), alive_all),
        (make_input(cfg, appends={0: [b"c"]}, offset_updates={0: [(2, 2)]}), alive_partial),
        (make_input(cfg, appends={2: [b"only-leader"]}), alive_partial),
    ]


@pytest.mark.parametrize("replicas,part_shards", [(2, 4), (4, 2), (2, 1), (8, 1)])
def test_spmd_matches_local(replicas, part_shards):
    cfg = small_cfg(replicas=replicas, partitions=8)
    mesh = make_mesh(replicas, part_shards)
    local = make_local_fns(cfg)
    spmd = make_spmd_fns(cfg, mesh)

    ls, ss = local.init(), spmd.init()
    for inp, alive in _scenario(cfg):
        ls, lout = local.step(ls, inp, alive)
        ss, sout = spmd.step(ss, inp, alive)
        for a, b in zip(jax.tree.leaves(lout), jax.tree.leaves(sout)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ls), jax.tree.leaves(ss)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # reads agree (partition 0 lives on shard 0, partition 7 on the last)
    for part in (0, 7):
        ld = local.read(ls, 0, part, 0)
        sd = spmd.read(ss, 0, part, 0)
        for a, b in zip(jax.tree.leaves(ld), jax.tree.leaves(sd)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(local.read_offset(ls, 0, 0, 2)) == int(spmd.read_offset(ss, 0, 0, 2))


def test_spmd_vote_and_resync():
    cfg = small_cfg(replicas=2, partitions=8)
    mesh = make_mesh(2, 4)
    spmd = make_spmd_fns(cfg, mesh)
    st = spmd.init()

    # replica 1 dead; quorum(2) = 2 -> round fails, and ATOMICALLY: the
    # failed round leaves no trace on any replica (leader included).
    st, out = spmd.step(
        st, make_input(cfg, appends={0: [b"a"]}), np.array([True, False])
    )
    assert not bool(out.committed[0])
    data, lens, count = spmd.read(st, 0, 0, 0)
    assert decode_read(data, lens, count) == []

    # full quorum commits (the host retries the same entry)
    st, out = spmd.step(st, make_input(cfg, appends={0: [b"a"], 5: [b"b"]}),
                        np.ones(2, bool))
    assert bool(out.committed[0]) and bool(out.committed[5])

    # vote: replica 1 runs for partition 5 with a fresh term
    cand = np.full((8,), -1, np.int32)
    cand[5] = 1
    st, elected, votes = spmd.vote(
        st, cand, np.full((8,), 3, np.int32), np.ones(2, bool)
    )
    assert bool(elected[5]) and int(votes[5]) == 2

    # resync is a no-op between in-sync replicas; state stays consistent
    mask = np.zeros((8,), bool)
    mask[0] = True
    st = spmd.resync(st, jnp.int32(0), jnp.int32(1), mask)
    st, out = spmd.step(st, make_input(cfg, appends={0: [b"c"]}), np.ones(2, bool))
    assert bool(out.committed[0])
    assert read_all(spmd, st, 1, 0) == [b"a", b"c"]


def test_pick_axes():
    from ripplemq_tpu.parallel.mesh import pick_axes

    assert pick_axes(8, 2) == (2, 4)
    assert pick_axes(8) == (2, 4)
    assert pick_axes(15) == (5, 3)
    assert pick_axes(6, 3) == (3, 2)
    assert pick_axes(7) == (1, 7)  # prime, no preferred factor -> all part
    with pytest.raises(ValueError):
        pick_axes(8, 3)  # never silently weaken a requested RF


def test_spmd_read_out_of_range_matches_local():
    cfg = small_cfg(replicas=2, partitions=8)
    local = make_local_fns(cfg)
    spmd = make_spmd_fns(cfg, make_mesh(2, 4))
    ls, ss = local.init(), spmd.init()
    inp = make_input(cfg, appends={0: [b"a"]})
    alive = np.ones(2, bool)
    ls, _ = local.step(ls, inp, alive)
    ss, _ = spmd.step(ss, inp, alive)
    for replica, part in [(99, 0), (0, 99), (-1, 0)]:
        lres = local.read(ls, replica, part, 0)
        sres = spmd.read(ss, replica, part, 0)
        for a, b in zip(jax.tree.leaves(lres), jax.tree.leaves(sres)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Spmd parity matrix: the round under shard_map vs under vmap over a
# richer history than _scenario — every StepOutput and the final full
# state must be bit-identical across empty/partial/quorum-failure/vote/
# resync/ring-wrap/chained rounds, at three mesh shapes.
# ---------------------------------------------------------------------------


def _assert_trees_equal(ref, others, msg):
    ref = jax.tree.map(np.asarray, ref)
    for name, o in others:
        o = jax.tree.map(np.asarray, o)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(o)):
            np.testing.assert_array_equal(a, b, err_msg=f"{name}:{msg}")


@pytest.mark.parametrize("replicas,part_shards", [(2, 4), (4, 2), (2, 1)])
def test_fused_spmd_parity_matrix(replicas, part_shards):
    from ripplemq_tpu.core.state import unfuse_state

    cfg_f = small_cfg(replicas=replicas, partitions=8)
    mesh = make_mesh(replicas, part_shards)
    engines = [
        ("spmd", make_spmd_fns(cfg_f, mesh)),
        ("vmap", make_local_fns(cfg_f)),
    ]
    states = [fns.init() for _, fns in engines]
    R = cfg_f.replicas
    alive_all = np.ones((R,), bool)
    minority = np.zeros((R,), bool)
    minority[0] = True
    majority = alive_all.copy()
    majority[-1] = False

    def step_all(inp, alive, trim=None, tag=""):
        outs = []
        for i, (_, fns) in enumerate(engines):
            states[i], out = fns.step(states[i], inp, alive, None, trim)
            outs.append((engines[i][0], out))
        _assert_trees_equal(outs[0][1], outs[1:], tag)

    # empty round (nothing acks anywhere)
    step_all(make_input(cfg_f), alive_all, tag="empty")
    # partial batch + offsets blend + leaderless partition (-1 default on
    # unnamed partitions)
    step_all(make_input(cfg_f, appends={0: [b"a", b"b"], 7: [b"z"]},
                        offset_updates={1: [(2, 5)]}), alive_all,
             tag="partial")
    # quorum failure: minority alive — atomically no trace anywhere
    step_all(make_input(cfg_f, appends={0: [b"minority"]}), minority,
             tag="quorum-fail")
    # retry at majority commits
    step_all(make_input(cfg_f, appends={0: [b"retry"]}), majority,
             tag="retry")
    # chained dispatch: 3 complete quorum rounds in one launch
    chain = jax.tree.map(
        lambda x: np.broadcast_to(np.asarray(x),
                                  (3,) + np.asarray(x).shape).copy(),
        make_input(cfg_f, appends={p: [b"c"] for p in range(8)}),
    )
    chain_outs = []
    for i, (name, fns) in enumerate(engines):
        states[i], outs = fns.step_many(states[i], chain, alive_all)
        chain_outs.append((name, outs))
    _assert_trees_equal(chain_outs[0][1], chain_outs[1:], "chained")

    # vote round (partition 5 elects replica 0 at a fresh term)
    cand = np.full((8,), -1, np.int32)
    cand[5] = 0
    vres = []
    for i, (name, fns) in enumerate(engines):
        states[i], elected, votes = fns.vote(
            states[i], cand, np.full((8,), 4, np.int32), alive_all
        )
        vres.append((name, (elected, votes)))
    _assert_trees_equal(vres[0][1], vres[1:], "vote")

    # resync (leader 0 -> last replica, masked partitions) + post round
    mask = np.zeros((8,), bool)
    mask[0] = mask[3] = True
    for i, (_, fns) in enumerate(engines):
        states[i] = fns.resync(states[i], jnp.int32(0),
                               jnp.int32(R - 1), mask)
    step_all(make_input(cfg_f, appends={0: [b"post-resync"]}, term=4),
             alive_all, tag="post-resync")

    # ring wrap behind trim: fill partition 0 to capacity, observe the
    # refusal, then trim and wrap a round past the boundary.
    fill = [b"f"] * cfg_f.max_batch
    end = int(np.asarray(states[1].log_end)[0, 0])
    for _ in range((cfg_f.slots - end) // cfg_f.max_batch):
        step_all(make_input(cfg_f, appends={0: fill}, term=4), alive_all,
                 tag="fill")
    step_all(make_input(cfg_f, appends={0: [b"full"]}, term=4), alive_all,
             tag="refusal")
    trim = np.full((8,), cfg_f.max_batch, np.int32)
    step_all(make_input(cfg_f, appends={0: [b"wrap"]}, term=4), alive_all,
             trim=trim, tag="wrap")

    # Final full-state equality (named layout; both bindings write the
    # same extent classes, so the whole physical ring must match).
    finals = [(name, unfuse_state(states[i]))
              for i, (name, _) in enumerate(engines)]
    _assert_trees_equal(finals[0][1], finals[1:], "final-state")

    # Read-path parity on the wrapped state.
    for part in (0, 7):
        reads = [(name, fns.read(states[i], 0, part,
                                 cfg_f.max_batch if part == 0 else 0))
                 for i, (name, fns) in enumerate(engines)]
        _assert_trees_equal(reads[0][1], reads[1:], f"read-p{part}")


def test_spmd_per_device_stride_verdict():
    """make_spmd_fns prices the ring-stride aliasing rule at the
    PER-DEVICE shard: a hazardous stride warns when a device holds
    enough rings to alias (local_P >= the stream threshold) and stays
    silent when sharding leaves too few rings per device — the config's
    global-shape warning cannot know the mesh (core.config)."""
    import warnings

    from ripplemq_tpu.core.config import EngineConfig

    def build(partitions):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # constructor's global warn
            return EngineConfig(
                partitions=partitions, replicas=1, slots=8192,
                slot_bytes=128, max_batch=256, read_batch=32,
            )

    # 512 partitions over 8 shards: 64 rings/device — hazard holds.
    with pytest.warns(UserWarning, match="per-device shard"):
        make_spmd_fns(build(512), make_mesh(1, 8))
    # 256 over 8: 32 rings/device — too few streams, must stay silent.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        make_spmd_fns(build(256), make_mesh(1, 8))

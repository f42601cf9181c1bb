"""The one round program against the Python model, on both bindings.

The device round has one control phase (bookkeeping on a stacked [K, P]
ctrl array — core.step.replica_control), one on-device state layout
(core.state.FusedReplicaState) and one write mode (append DMA windows
clipped to the round's extent class — ops/append.py). This suite replays
one scripted history — empty rounds, partial batches, full batches,
quorum failures, leaderless partitions, offset-commit blends, capacity
backpressure, a trim-gated ring wrap, a chained dispatch, an election, a
resync and a sparse (active-set) dispatch — through the vmap binding and
a 3 x 2 shard_map mesh, beside `tests/round_model.Model`, an independent
pure-Python mirror of the rules, and asserts against the MODEL:

- every StepOutput of every round;
- every scalar state field (log_end/last_term/current_term/commit) and
  the offsets table, per replica, after every phase;
- the committed log prefix, row by row (bytes beyond a round's extent
  class keep their prior content and are unreadable by contract, so the
  comparison stops at each replica's commit index).

Beside it: a chained launch lands where its rounds land one by one, a
sparse round lands where the dense round lands, an input without extents
means the full window on both bindings, and the named recovery image
goes in and comes out unchanged.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ripplemq_tpu.core.config import EngineConfig
from ripplemq_tpu.core.encode import build_step_input, decode_entries
from ripplemq_tpu.core.state import (
    ReplicaState,
    StepInput,
    fuse_state,
    unfuse_state,
)
from ripplemq_tpu.parallel.engine import make_local_fns, make_spmd_fns
from ripplemq_tpu.parallel.mesh import make_mesh
from tests.round_model import Model

# max_batch is two ALIGN blocks, so partial rounds write the one-block
# extent class and full batches the two-block one.
CFG = EngineConfig(
    partitions=4,
    replicas=3,
    slots=64,
    slot_bytes=32,
    max_batch=16,
    read_batch=16,
    max_consumers=8,
    max_offset_updates=4,
)
BINDINGS = ("vmap", "spmd")

ALL = np.ones((3,), bool)
MINORITY = np.array([True, False, False])
MAJORITY = np.array([True, True, False])

# (appends / offset_updates, leader, term, alive) per round — the
# scenario mix the docstring promises.
SCRIPT = [
    # partial batch on one partition
    (dict(appends={0: [b"a", b"b", b"c"]}), 0, 1, ALL),
    # offset blend riding an append + an offsets-only partition
    (dict(appends={1: [b"x"]}, offset_updates={0: [(1, 3)], 2: [(0, 7)]}),
     0, 1, ALL),
    # empty round (no work anywhere): nothing acks
    (dict(), 0, 1, ALL),
    # leaderless partitions
    (dict(appends={0: [b"noleader"]}), -1, 1, ALL),
    # quorum failure: minority alive
    (dict(appends={0: [b"minority"]}), 0, 1, MINORITY),
    # majority commit after the failure (retry semantics)
    (dict(appends={0: [b"retry"]}), 0, 1, MAJORITY),
    # full batch, term bump
    (dict(appends={2: [b"f%d" % i for i in range(CFG.max_batch)]}), 1, 2, ALL),
    # offsets-only round on an idle partition
    (dict(offset_updates={3: [(0, 5), (2, 9)]}), 0, 2, ALL),
    # dead leader: no progress
    (dict(appends={3: [b"dead"]}), 1, 2, np.array([True, False, True])),
]
CHAIN = [dict(appends={0: [b"k%d" % k], 2: [b"c%d" % k] * 3}) for k in range(4)]
SPARSE = dict(appends={2: [b"s1", b"s2"]})


def _fns(binding):
    if binding == "vmap":
        return make_local_fns(CFG)
    if len(jax.devices()) < 6:
        pytest.skip("needs 6 virtual devices")
    return make_spmd_fns(CFG, make_mesh(CFG.replicas, 2))  # 3 replicas x 2 shards


def _snap(state):
    """Host-materialized named-field snapshot: the engine DONATES the
    state argument, so a later step invalidates device snapshots —
    every capture must copy to numpy."""
    return jax.tree.map(np.asarray, unfuse_state(state))


def _inp(work, leader, term):
    return build_step_input(CFG, leader=leader, term=term, **work)


def _stack(inputs):
    return StepInput(*[
        np.stack([np.asarray(getattr(i, f)) for i in inputs])
        for f in StepInput._fields
    ])


def _sparse_args(work, leader, term):
    """The active-set form of one round: the control input without its
    entries, plus the appending partitions' blocks and their ids."""
    full = _inp(work, leader, term)
    ids = np.array(sorted(work["appends"]), np.int32)
    entries_c = np.asarray(full.entries)[ids]
    dummy = np.zeros((CFG.partitions, 1, 1), np.uint8)
    return full._replace(entries=dummy), entries_c, ids


def _run_history(fns):
    """One full scripted history through the engine and the model side
    by side. Returns (outs, states): `outs` pairs each device StepOutput
    (or vote result) with the model's, `states` pairs a device snapshot
    with the model's after every phase."""
    model = Model(CFG)
    state = fns.init()
    outs, states = [], []
    trim = np.zeros((CFG.partitions,), np.int32)  # highest watermark fed

    def out_pair(tag, out, want):
        outs.append((tag, {f: np.asarray(getattr(out, f)) for f in want},
                     want))

    def state_pair(tag):
        states.append((tag, _snap(state), model.snapshot(),
                       [list(r) for r in model.rows], trim.copy()))

    for i, (work, leader, term, alive) in enumerate(SCRIPT):
        state, out = fns.step(state, _inp(work, leader, term), alive)
        out_pair(f"script[{i}]", out, model.round(
            work.get("appends"), work.get("offset_updates"), leader, term,
            alive))
    state_pair("script")

    # Chained dispatch: four complete rounds in one launch; the model
    # takes them one by one.
    state, many = fns.step_many(
        state, _stack([_inp(w, 0, 2) for w in CHAIN]), ALL)
    for k, work in enumerate(CHAIN):
        out_pair(f"chain[{k}]", jax.tree.map(lambda x: x[k], many),
                 model.round(work["appends"], None, 0, 2, ALL))
    state_pair("chain")

    # Capacity backpressure + trim-gated ring wrap: fill partition 0's
    # ring, see the refusal, then trim and wrap a round past the boundary.
    fill = dict(appends={0: [b"z"] * CFG.max_batch})
    while int(model.end[0, 0]) + CFG.max_batch <= CFG.slots:
        state, out = fns.step(state, _inp(fill, 0, 2), ALL)
        out_pair("fill", out, model.round(fill["appends"], None, 0, 2, ALL))
    full = dict(appends={0: [b"full"]})
    state, out = fns.step(state, _inp(full, 0, 2), ALL)
    want = model.round(full["appends"], None, 0, 2, ALL)
    assert not want["committed"][0], "the history must hit the capacity rule"
    out_pair("refused", out, want)
    trim = np.full((CFG.partitions,), CFG.max_batch, np.int32)
    wrap = dict(appends={0: [b"wrap"]})
    state, out = fns.step(state, _inp(wrap, 0, 2), ALL, None, trim)
    want = model.round(wrap["appends"], None, 0, 2, ALL, trim)
    assert want["committed"][0] and want["base"][0] + 8 > CFG.slots, (
        "the history must wrap the ring")
    out_pair("wrapped", out, want)
    state_pair("wrap")

    # Election (partition 1 elects replica 2 at term 5).
    cand = np.full((CFG.partitions,), -1, np.int32)
    cand[1] = 2
    state, elected, votes = fns.vote(
        state, cand, np.full((CFG.partitions,), 5, np.int32), ALL)
    m = [model.vote(p, int(cand[p]), 5, ALL) for p in range(CFG.partitions)]
    outs.append(("vote",
                 {"elected": np.asarray(elected), "votes": np.asarray(votes)},
                 {"elected": np.array([e for e, _ in m]),
                  "votes": np.array([g for _, g in m])}))
    state_pair("vote")

    # A round replica 2 misses, a resync of it, then the full set again.
    lag = dict(appends={1: [b"m1", b"m2"]})
    state, out = fns.step(state, _inp(lag, 0, 5), MAJORITY)
    out_pair("lag", out, model.round(lag["appends"], None, 0, 5, MAJORITY))
    mask = np.array([False, True, False, False])
    state = fns.resync(state, np.int32(0), np.int32(2), mask)
    model.resync(1, 0, 2)
    post = dict(appends={1: [b"m3"]})
    state, out = fns.step(state, _inp(post, 0, 5), ALL)
    want = model.round(post["appends"], None, 0, 5, ALL)
    assert want["votes"][1] == 3, "the resynced replica must ack again"
    out_pair("post-resync", out, want)
    state_pair("resync")

    # Sparse (active-set) dispatch.
    state, out = fns.step_sparse(state, *_sparse_args(SPARSE, 0, 5), ALL)
    out_pair("sparse", out, model.round(SPARSE["appends"], None, 0, 5, ALL))
    state_pair("final")

    # The read path on the final state, against the model's rows.
    reads = []
    for p in range(CFG.partitions):
        off = CFG.max_batch if p == 0 else 0  # partition 0 wrapped: above trim
        data, lens, count = fns.read(state, 0, p, off)
        reads.append((p, decode_entries(data, lens, count), int(count),
                      model.read(p, 0, off)))
    return outs, states, reads


@pytest.fixture(scope="module", params=BINDINGS)
def history(request):
    return _run_history(_fns(request.param))


def test_outputs_match_model(history):
    outs, _, reads = history
    for tag, got, want in outs:
        for f, w in want.items():
            np.testing.assert_array_equal(got[f], w, err_msg=f"{tag}:{f}")
    for p, msgs, count, (want_msgs, want_count) in reads:
        assert (msgs, count) == (want_msgs, want_count), f"read p{p}"


def test_scalar_state_matches_model(history):
    _, states, _ = history
    for tag, got, want, _, _ in states:
        for f, w in want.items():
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), w, err_msg=f"{tag}:{f}")


def test_committed_log_prefix_matches_model(history):
    """Every ring-resident committed row of every replica holds the
    model's payload (or a length-0 padding row) at its absolute offset."""
    from ripplemq_tpu.core.state import row_lens

    _, states, _ = history
    S = CFG.slots
    checked = 0
    for tag, got, want, rows, trim in states:
        log = np.asarray(got.log_data)
        lens = np.asarray(row_lens(log))
        for r in range(CFG.replicas):
            for p in range(CFG.partitions):
                end, commit = int(want["log_end"][r, p]), int(want["commit"][r, p])
                # Ring-resident and not reclaimable: the last lap, above
                # the highest trim watermark the history fed.
                for a in range(max(0, end - S, int(trim[p])), commit):
                    n = int(lens[r, p, a % S])
                    payload = log[r, p, a % S, 8 : 8 + n].tobytes()
                    assert payload == rows[p][a], f"{tag}: r{r} p{p} @{a}"
                    checked += 1
    assert checked > 500  # the history is not vacuous


@pytest.mark.parametrize("binding", BINDINGS)
def test_step_many_lands_where_steps_land(binding):
    fns = _fns(binding)
    inputs = [_inp(w, 0, 2) for w in CHAIN]
    one, many = fns.init(), fns.init()
    singles = []
    for inp in inputs:
        one, out = fns.step(one, inp, ALL)
        singles.append(out)
    many, outs = fns.step_many(many, _stack(inputs), ALL)
    for k, out in enumerate(singles):
        _assert_tree_equal(out, jax.tree.map(lambda x: x[k], outs),
                           f"chain[{k}]")
    _assert_tree_equal(_snap(one), _snap(many), "state after the chain")


@pytest.mark.parametrize("binding", BINDINGS)
def test_step_sparse_lands_where_step_lands(binding):
    fns = _fns(binding)
    dense, sparse = fns.init(), fns.init()
    for work in (SPARSE, dict(appends={0: [b"d"] * CFG.max_batch, 3: [b"e"]})):
        dense, d_out = fns.step(dense, _inp(work, 0, 1), ALL)
        sparse, s_out = fns.step_sparse(
            sparse, *_sparse_args(work, 0, 1), ALL)
        _assert_tree_equal(d_out, s_out, "sparse out")
    _assert_tree_equal(_snap(dense), _snap(sparse), "state after sparse")


def test_spmd_fills_missing_extents_with_the_full_window():
    """Hand-built inputs may carry extents=None (pytree-empty): the spmd
    wrapper must fill the full window instead of treedef-mismatching
    against its compiled specs, the vmap binding takes None as it is,
    and both write the whole [B, SB] block."""
    spmd, local = _fns("spmd"), _fns("vmap")
    ss, ls = spmd.init(), local.init()
    inp = _inp(dict(appends={1: [b"nofill"]}), 0, 2)._replace(extents=None)
    ss, s_out = spmd.step(ss, inp, ALL)
    ls, l_out = local.step(ls, inp, ALL)
    assert bool(np.asarray(l_out.committed)[1])
    _assert_tree_equal(l_out, s_out, "extents=None out")
    _assert_tree_equal(_snap(ls), _snap(ss), "extents=None state")
    # One payload row, and the block's padding rows (length 0, the
    # round's term in their header) written out to the window's end.
    block = np.asarray(inp.entries)[1]
    assert block[8:, 4].all()
    for r in range(CFG.replicas):
        np.testing.assert_array_equal(
            np.asarray(ss.log_data)[r, 1, : CFG.max_batch], block)


def _assert_tree_equal(a, b, msg):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def test_fuse_unfuse_roundtrip():
    fns = make_local_fns(CFG)
    state = fns.init()
    state, _ = fns.step(
        state, _inp(dict(appends={0: [b"rt"]}), 0, 1), ALL)
    named = _snap(state)
    _assert_tree_equal(named, unfuse_state(fuse_state(named)),
                       "fuse/unfuse roundtrip")


def test_fused_accessors_match_fields():
    fns = make_local_fns(CFG)
    state = fns.init()
    state, _ = fns.step(
        state, _inp(dict(appends={1: [b"v"]}), 0, 3), ALL)
    plain = unfuse_state(state)
    for i, f in enumerate(("log_end", "last_term", "current_term", "commit")):
        np.testing.assert_array_equal(
            np.asarray(getattr(state, f)), np.asarray(state.ctrl)[:, i, :],
            err_msg=f)
        np.testing.assert_array_equal(
            np.asarray(getattr(state, f)), np.asarray(getattr(plain, f)),
            err_msg=f)


def test_init_from_image_parity():
    """A recovered image installed through either binding comes back out
    of the named accessors as it went in, on every replica
    (broker/replication.py's recovery path rides init_from)."""
    P, S, B, SB, C = (CFG.partitions, CFG.slots, CFG.max_batch,
                      CFG.slot_bytes, CFG.max_consumers)
    rng = np.random.default_rng(5)
    image = ReplicaState(
        log_data=rng.integers(0, 256, size=(P, S + B, SB), dtype=np.uint8),
        log_end=np.array([8, 0, 16, 8], np.int32),
        last_term=np.array([1, 0, 2, 1], np.int32),
        current_term=np.array([1, 0, 2, 1], np.int32),
        commit=np.array([8, 0, 16, 8], np.int32),
        offsets=rng.integers(0, 99, size=(P, C)).astype(np.int32),
    )
    for binding in BINDINGS:
        got = unfuse_state(_fns(binding).init_from(image))
        for r in range(CFG.replicas):
            _assert_tree_equal(
                image, jax.tree.map(lambda x: np.asarray(x)[r], got),
                f"{binding}: replica {r}")

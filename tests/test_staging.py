"""How a dispatch's rows get from the pendings into the device input
(DataPlane._build_round_locked decides under the lock, DataPlane._stage
builds outside it, PR 42), held to the builder it replaced.

The reference below is that builder as it stood: under the plane's lock
one `np.zeros((B, SB))` block a listed partition, the pendings' rows
copied in, the term stamped over all B rows; outside it one
`ec[k, a] = block` a listed partition. Two planes get the same seeded
queues; one drains through the plane's own path, the other through the
reference. Equal: every StepInput field, slot_ids, h2d_bytes, the
store / standby records, the device state after the launch, and the
block stack over every row the write phase moves (the extent class of
ops.append). Past the class the reference carries the term stamp and the
plane's stack zero rows: the append's DMA is clipped away from them, and
the device-state case says so byte for byte.

No chip: the XLA scatter applies the same class rule as the Pallas
kernel (ops/append.py's contract, tests/test_append_kernel*.py)."""

import random

import jax
import numpy as np
import pytest

from ripplemq_tpu import EngineConfig
from ripplemq_tpu.broker.dataplane import DataPlane, _OFFSET_HORIZON
from ripplemq_tpu.core.config import ALIGN
from ripplemq_tpu.core.encode import row_extents
from ripplemq_tpu.core.state import StepInput
from ripplemq_tpu.obs.metrics import Metrics
from ripplemq_tpu.ops.append import _class_roundup, class_rows
from ripplemq_tpu.storage.segment import REC_APPEND
from tests.test_gather import HandClock


# ------------------------------------------------------------ the reference


def stamp_term(block: np.ndarray, term: int) -> None:
    block[:, 4:8] = np.frombuffer(np.int32(term).tobytes(), np.uint8)


def ref_build_round_locked(self, pred_end):
    """The parent's `_build_round_locked` (PR 41's tree), metrics left
    out: blocks built, filled and stamped per listed slot."""
    cfg = self.cfg
    P, B, SB, U = (cfg.partitions, cfg.max_batch, cfg.slot_bytes,
                   cfg.max_offset_updates)
    blocks = {}
    counts = np.zeros((P,), np.int32)
    off_slots = np.zeros((P, U), np.int32)
    off_vals = np.zeros((P, U), np.int32)
    off_counts = np.zeros((P,), np.int32)
    round_appends, round_offsets, round_bases = {}, {}, {}
    S = cfg.slots
    can_trim = self.store is not None and self.log_index is not None
    for slot, queue in list(self._appends.items()):
        if slot in self._busy_a:
            continue
        end = pred_end.get(slot, int(self._log_end[slot]))
        assert end < _OFFSET_HORIZON
        if can_trim:
            needed = min(end + B - S, int(self._persisted[slot]))
            if needed > self.trim[slot]:
                self.trim[slot] = needed
            cap = min(B, S - end % S)
        else:
            cap = B
        taken = []
        fill = 0
        while queue and fill + len(queue[0].payloads) <= cap:
            pend = queue.pop(0)
            n = len(pend.payloads)
            taken.append((pend, fill, n))
            fill += n
        if taken:
            block = np.zeros((B, SB), np.uint8)
            for pend, start, n in taken:
                block[start: start + n] = pend.rows
            stamp_term(block, int(self.term[slot]))
            blocks[slot] = block
            counts[slot] = fill
            round_appends[slot] = taken
            round_bases[slot] = end
            pred_end[slot] = end + -(-fill // ALIGN) * ALIGN
        elif queue and can_trim:
            pad = S - end % S
            block = np.zeros((B, SB), np.uint8)
            stamp_term(block, int(self.term[slot]))
            blocks[slot] = block
            counts[slot] = pad
            round_appends[slot] = []
            round_bases[slot] = end
            pred_end[slot] = end + pad
        if not queue:
            self._appends.pop(slot, None)
    for slot, queue in list(self._offsets.items()):
        if slot in self._busy_o:
            continue
        taken_off = []
        fill = 0
        while queue and fill + len(queue[0].payloads) <= U:
            pend = queue.pop(0)
            for i, (cslot, off) in enumerate(pend.payloads):
                off_slots[slot, fill + i] = cslot
                off_vals[slot, fill + i] = off
            fill += len(pend.payloads)
            taken_off.append(pend)
        if taken_off:
            off_counts[slot] = fill
            round_offsets[slot] = taken_off
        if not queue:
            self._offsets.pop(slot, None)
    if not round_appends and not round_offsets:
        return None
    inp = StepInput(
        entries=self._dummy_entries(), counts=counts, off_slots=off_slots,
        off_vals=off_vals, off_counts=off_counts, leader=self.leader.copy(),
        term=self.term.copy(), extents=row_extents(counts))
    return inp, {"appends": round_appends, "offsets": round_offsets,
                 "bases": round_bases, "entries": blocks,
                 "counts": {s: int(counts[s]) for s in blocks}}


def ref_drain(self):
    """The parent's `_drain` from the second lock take on."""
    cfg = self.cfg
    with self._lock:
        pred_end = {}
        rounds = []
        for _ in range(self.chain_depth):
            r = ref_build_round_locked(self, pred_end)
            if r is None:
                break
            rounds.append(r)
        if not rounds:
            return None
        alive = self.alive.copy()
        quorum = self.quorum.copy()
        trim = self.trim.astype(np.int32)
        if len(rounds) > 1:
            zero = self._zero_round_template()
            pad_inp = StepInput(self._dummy_entries(), *zero,
                                leader=self.leader.copy(),
                                term=self.term.copy(), extents=zero[0])
            while len(rounds) < self.chain_depth:
                rounds.append((pad_inp, {"appends": {}, "offsets": {},
                                         "bases": {}, "entries": {},
                                         "counts": {}}))
    chain = [r[1] for r in rounds]
    B, SB = cfg.max_batch, cfg.slot_bytes
    A = self._active_bucket(max(len(rc["entries"]) for rc in chain))
    ec = np.zeros((len(chain), A, B, SB), np.uint8)
    ids = np.full((len(chain), A), -1, np.int32)
    for k, rc in enumerate(chain):
        for a, (slot, block) in enumerate(sorted(rc["entries"].items())):
            ec[k, a] = block
            ids[k, a] = slot
    if len(rounds) == 1:
        inp = rounds[0][0]
        entries_c, slot_ids = ec[0], ids[0]
    else:
        inp = StepInput(*[
            np.stack([np.asarray(getattr(r[0], f)) for r in rounds])
            for f in StepInput._fields])
        entries_c, slot_ids = ec, ids
    h2d = sum(getattr(a, "nbytes", 0) for a in
              (*inp, entries_c, slot_ids, alive, quorum, trim))
    return inp, {"chain": chain, "entries_c": entries_c,
                 "slot_ids": slot_ids, "alive": alive, "quorum": quorum,
                 "trim": trim, "h2d_bytes": h2d}


def ref_round_records(rc, committed):
    """The APPEND records of the parent's `_round_records`."""
    records = []
    for slot in rc["appends"]:
        n = rc["counts"].get(slot, 0)
        if not committed[slot] or n == 0:
            continue
        adv = int(-(-n // ALIGN) * ALIGN)
        records.append((REC_APPEND, int(slot), int(rc["bases"][slot]),
                        rc["entries"][slot][:adv].tobytes()))
    return records


# ------------------------------------------------------------------ the rig


def engine(partitions=12, slots=64, slot_bytes=128, max_batch=32):
    return EngineConfig(partitions=partitions, replicas=3, slots=slots,
                        slot_bytes=slot_bytes, max_batch=max_batch,
                        read_batch=8, max_consumers=8, max_offset_updates=4)


def bare_plane(cfg, chain_depth=1):
    """A local plane on a hand clock, every slot led, NOT started."""
    metrics = Metrics(clock=HandClock())
    dp = DataPlane(cfg, mode="local", max_retry_rounds=3, metrics=metrics,
                   chain_depth=chain_depth)
    for slot in range(cfg.partitions):
        dp.set_leader(slot, slot % cfg.replicas, 1 + slot % 5)
    return dp


def fill_queues(dp, seed, slots, pendings=(1, 3), rows=(1, 5), pids=False,
                offsets=()):
    """Seeded random queues: per slot of `slots` some pendings of some
    rows of random bytes and lengths."""
    rnd = random.Random(seed)
    cfg = dp.cfg
    for slot in slots:
        for j in range(rnd.randint(*pendings)):
            msgs = [rnd.randbytes(rnd.randint(1, cfg.payload_bytes))
                    for _ in range(rnd.randint(*rows))]
            if pids:
                dp.submit_append(slot, msgs, pid=7 + slot, seq=100 * j)
            else:
                dp.submit_append(slot, msgs)
    for slot in offsets:
        dp.submit_offsets(slot, [(rnd.randrange(cfg.max_consumers),
                                  rnd.randrange(1000))])


class _Absent:
    """A store and an index the drain only asks about being there."""

    def flush(self):
        pass

    close = flush


def at_the_boundary(dp, slot, rows_left):
    """Pretend a store and an index, and write `slot` up to `rows_left`
    rows before the ring's end on the device and in the host's shadow
    (what a resolver and a persist would have left)."""
    dp.store = dp.log_index = _Absent()
    rows = dp.cfg.slots - rows_left
    assert 0 < rows <= dp.cfg.max_batch and rows % ALIGN == 0
    dp.submit_append(slot, [b"x"] * rows)
    inp, ctx = dp._drain()
    dp._state, out = dp.fns.step_sparse(
        dp._state, inp, ctx["entries_c"], ctx["slot_ids"], ctx["alive"],
        ctx["quorum"], ctx["trim"])
    assert np.asarray(out.committed)[slot]
    dp._log_end[slot] = dp._persisted[slot] = rows


RING = {"slots": 24, "max_batch": 16}  # a lap is a round and a half

CASES = {
    # name: (engine kwargs, chain_depth, fill kwargs, boundary or None)
    "one_pending_a_slot": ({}, 1, dict(slots=[1, 4, 7], pendings=(1, 1)),
                           None),
    "several_pendings_a_slot": ({}, 1, dict(slots=[0, 2, 3, 9, 11],
                                            pendings=(2, 4), rows=(3, 6)),
                                None),
    # 17-24 rows: an extent of 24, moved as the class of 32.
    "extent_below_its_class": ({}, 1, dict(slots=[1, 4, 6], pendings=(1, 1),
                                           rows=(17, 24)), None),
    "extents_of_every_class": ({"max_batch": 64}, 1,
                               dict(slots=range(12), pendings=(1, 1),
                                    rows=(1, 64)), None),
    "chain_with_padded_rounds": ({}, 4, dict(slots=[2, 5, 6], pendings=(3, 3),
                                             rows=(12, 14)), None),
    "chain_filled": ({}, 2, dict(slots=[0, 1, 8], pendings=(4, 5),
                                 rows=(17, 20)), None),
    "boundary_padding_round": (RING, 1, dict(slots=[3, 5], pendings=(1, 1),
                                             rows=(10, 12)), (3, 8)),
    "boundary_padding_in_a_chain": (RING, 3, dict(slots=[3, 5],
                                                  pendings=(2, 2),
                                                  rows=(10, 12)), (3, 8)),
    "capped_at_the_ring_boundary": (RING, 1, dict(slots=[3, 5],
                                                  pendings=(3, 3),
                                                  rows=(3, 5)), (5, 8)),
    "bucket_8": ({"partitions": 40}, 1, dict(slots=range(0, 40, 6)), None),
    "bucket_32": ({"partitions": 40}, 1, dict(slots=range(0, 40, 2),
                                              rows=(1, 20)), None),
    "bucket_partitions": ({"partitions": 40}, 1, dict(slots=range(40)),
                          None),
    "bucket_32_in_a_chain": ({"partitions": 40}, 2,
                             dict(slots=range(1, 40, 3), pendings=(2, 3),
                                  rows=(15, 18)), None),
    "slot_bytes_256": ({"slot_bytes": 256}, 1, dict(slots=[0, 6, 10],
                                                    pendings=(1, 3),
                                                    rows=(1, 9)), None),
    "slot_bytes_1152": ({"slot_bytes": 1152, "max_batch": 8}, 2,
                        dict(slots=[1, 2, 3, 5], pendings=(2, 3),
                             rows=(3, 5)), None),
    "full_blocks": ({}, 1, dict(slots=[0, 4, 5], pendings=(1, 1),
                                rows=(32, 32)), None),
    "offsets_only": ({}, 1, dict(slots=[], offsets=[2, 7]), None),
    "offsets_beside_appends": ({}, 2, dict(slots=[1, 2], pendings=(2, 2),
                                           rows=(17, 17), offsets=[2, 3]),
                               None),
    "producer_ids": ({}, 1, dict(slots=[0, 3], pendings=(2, 2), pids=True),
                     None),
}


def drained_pair(case, seed):
    kw, chain_depth, fill, boundary = CASES[case]
    planes = []
    for _ in range(2):
        dp = bare_plane(engine(**kw), chain_depth)
        if boundary is not None:
            at_the_boundary(dp, *boundary)
        fill_queues(dp, seed, **fill)
        planes.append(dp)
    new, ref = planes
    return new, new._drain(), ref, ref_drain(ref)


def listed_blocks(ctx):
    """(k, a, slot, rows counted) of every listed slot of a dispatch."""
    ids = np.atleast_2d(ctx["slot_ids"])
    for k, rc in enumerate(ctx["chain"]):
        for a, slot in enumerate(sorted(rc["counts"])):
            assert ids[k, a] == slot
            yield k, a, slot, rc["counts"][slot]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
@pytest.mark.parametrize("case", list(CASES))
def test_the_staged_dispatch_is_the_per_slot_builders(case, seed):
    new, (inp, ctx), ref, (rinp, rctx) = drained_pair(case, seed)
    cfg = new.cfg
    B = cfg.max_batch
    try:
        for f in StepInput._fields:
            got, want = np.asarray(getattr(inp, f)), np.asarray(
                getattr(rinp, f))
            assert got.dtype == want.dtype and np.array_equal(got, want), f
        for key in ("slot_ids", "alive", "quorum", "trim"):
            assert ctx[key].dtype == rctx[key].dtype
            assert np.array_equal(ctx[key], rctx[key]), key
        assert ctx["h2d_bytes"] == rctx["h2d_bytes"]
        ec, rec = ctx["entries_c"], rctx["entries_c"]
        assert ec.shape == rec.shape and ec.dtype == rec.dtype
        ec4 = ec.reshape(-1, *ec.shape[-3:])
        rec4 = rec.reshape(ec4.shape)
        assert len(ctx["chain"]) == len(rctx["chain"]) == ec4.shape[0]
        moved = np.zeros(ec4.shape[:3], bool)
        for k, a, slot, n in listed_blocks(ctx):
            assert rctx["chain"][k]["counts"][slot] == n > 0
            extent = int(row_extents(np.int32(n)))
            span = int(class_rows(extent, B))
            assert extent <= span <= B
            moved[k, a, :span] = True
            # Past the class: zero rows here, the term alone there.
            assert not ec4[k, a, span:].any()
            past = rec4[k, a, span:].copy()
            past[:, 4:8] = 0
            assert not past.any()
        # Everything the write phase moves, and every block no slot has.
        assert np.array_equal(ec4[moved], rec4[moved])
        assert not ec4[~moved].any()
        # The store's and the standbys' records, all rounds committed.
        yes = np.ones((cfg.partitions,), bool)
        for rc, rrc in zip(ctx["chain"], rctx["chain"]):
            records = new._round_records(rc, yes)
            appends = [r for r in records if r[0] == REC_APPEND]
            assert appends == ref_round_records(rrc, yes)
            assert records == ref._round_records(
                {**rrc, "rows": rc.get("rows"),
                 "rows_at": rc.get("rows_at", {})}, yes)
            for _, slot, _, payload in appends:
                assert len(payload) == cfg.slot_bytes * int(
                    row_extents(np.int32(rc["counts"][slot])))
    finally:
        new.stop()
        ref.stop()


def launch(dp, inp, ctx):
    step = (dp.fns.step_sparse if len(ctx["chain"]) == 1
            else dp.fns.step_many_sparse)
    state, out = step(dp._state, inp, ctx["entries_c"], ctx["slot_ids"],
                      ctx["alive"], ctx["quorum"], ctx["trim"])
    return jax.tree_util.tree_map(np.asarray, (state, out))


@pytest.mark.parametrize("case", [
    "several_pendings_a_slot", "extent_below_its_class",
    "chain_with_padded_rounds", "boundary_padding_in_a_chain", "bucket_32",
    "slot_bytes_256"])
def test_the_device_ends_in_the_same_state(case):
    """Rows past a write's extent class are zero in the plane's stack and
    term-stamped in the reference's: the device state and the round's
    outputs come out the same, bit for bit, so nothing reads them."""
    new, (inp, ctx), ref, (rinp, rctx) = drained_pair(case, 11)
    try:
        short = any(class_rows(n, new.cfg.max_batch) < new.cfg.max_batch
                    for _, _, _, n in listed_blocks(ctx))
        assert short != np.array_equal(ctx["entries_c"], rctx["entries_c"])
        got, want = launch(new, inp, ctx), launch(ref, rinp, rctx)
        flat_g, tree_g = jax.tree_util.tree_flatten(got)
        flat_w, tree_w = jax.tree_util.tree_flatten(want)
        assert tree_g == tree_w
        for g, w in zip(flat_g, flat_w):
            assert g.dtype == w.dtype and np.array_equal(g, w)
        # The first round's writes all land (a chain that outruns the
        # ring fails the capacity check on its later rounds, on both).
        committed = np.atleast_2d(got[1].committed)
        assert all(committed[k, slot]
                   for k, _, slot, _ in listed_blocks(ctx) if k == 0)
    finally:
        new.stop()
        ref.stop()


@pytest.mark.parametrize("case", [
    "several_pendings_a_slot", "chain_filled", "boundary_padding_in_a_chain",
    "capped_at_the_ring_boundary", "bucket_partitions", "slot_bytes_1152"])
def test_the_mirror_holds_what_a_slice_a_record_wrote(case):
    """The settle thread's host mirror takes a dispatch's rows in one
    indexed assignment (`_mirror_records`): the ring and its watermarks
    are those of the slice assignment a record it replaced."""
    new, (inp, ctx), ref, _ = drained_pair(case, 21)
    try:
        cfg = new.cfg
        S, SB = cfg.slots, cfg.slot_bytes
        yes = np.ones((cfg.partitions,), bool)
        records = [r for rc in ctx["chain"]
                   for r in new._round_records(rc, yes)]
        want = new._host_ring.copy()
        ends = {}
        for rec_type, slot, base, payload in records:
            if rec_type != REC_APPEND:
                continue
            rows = np.frombuffer(payload, np.uint8).reshape(-1, SB)
            want[slot, base % S: base % S + len(rows)] = rows
            ends[slot] = base + len(rows)
        assert ends and want.any()
        new._cache_end[:] = new._log_end  # mirrored up to the log's end
        new._mirror_records(records)
        assert np.array_equal(new._host_ring, want)
        assert {s: int(new._cache_end[s]) for s in ends} == ends
    finally:
        new.stop()
        ref.stop()


@pytest.mark.parametrize("B", [8, 16, 24, 512])
def test_the_host_class_rule_is_the_kernels(B):
    extents = np.arange(B + 1, dtype=np.int32)
    BA = B // ALIGN
    eb = np.clip((extents + ALIGN - 1) // ALIGN, 1, BA)
    want = np.asarray(_class_roundup(jax.numpy.asarray(eb), BA)) * ALIGN
    assert np.array_equal(class_rows(extents, B), want)
    assert class_rows(np.int32(B), B) == B


# ------------------------------------------- what the mechanism rests on


def stage_copies(listed):
    dp = bare_plane(EngineConfig(
        partitions=512, replicas=3, slots=16, slot_bytes=128, max_batch=8,
        read_batch=8, max_consumers=8, max_offset_updates=4))
    try:
        fill_queues(dp, 5, range(listed), pendings=(1, 2), rows=(1, 3))
        inp, ctx = dp._drain()
        assert len(ctx["chain"][0]["counts"]) == listed
        h = dp.metrics.histogram("round.stage_copies")
        assert h.count == 1 == dp.metrics.histogram("round.stage_us").count
        return h.total
    finally:
        dp.stop()


def test_stage_copies_do_not_go_with_the_listed_slots():
    few, many = stage_copies(4), stage_copies(400)
    assert few == many < 10


@pytest.mark.parametrize("chain_depth", [1, 3])
def test_nothing_of_a_blocks_size_is_made_under_the_lock(chain_depth):
    """What `_build_round_locked` hands back holds no array of B x SB
    bytes or more: lists, ints and the [P]-sized StepInput fields."""
    dp = bare_plane(engine(), chain_depth)
    try:
        fill_queues(dp, 3, [0, 1, 5, 9], pendings=(2, 3), rows=(6, 9))
        block = dp.cfg.max_batch * dp.cfg.slot_bytes
        with dp._lock:
            inp, rc = dp._build_round_locked({})

        def arrays(x):
            if isinstance(x, np.ndarray):
                yield x
            elif isinstance(x, dict):
                for v in x.values():
                    yield from arrays(v)
            elif isinstance(x, (list, tuple)):
                for v in x:
                    yield from arrays(v)

        held = list(arrays(tuple(inp))) + list(arrays(
            {k: v for k, v in rc.items() if k != "appends"}))
        assert held and all(a.nbytes < block for a in held)
        assert set(rc) == {"appends", "offsets", "bases", "counts", "terms"}
        assert rc["terms"] == {s: int(dp.term[s]) for s in rc["counts"]}
        for taken in rc["appends"].values():  # references, nothing new
            for pend, start, n in taken:
                assert pend.rows.shape == (n, dp.cfg.slot_bytes)
    finally:
        dp.stop()


def staged_rows(ctx, slot):
    rc = ctx["chain"][0]
    a = sorted(rc["counts"]).index(slot)
    ec = ctx["entries_c"].reshape(-1, *ctx["entries_c"].shape[-3:])
    return ec[0, a, :rc["counts"][slot]].copy()


def test_a_retry_stages_the_same_rows_under_the_new_term():
    """`pend.rows` is never written: a dispatch failed through
    `_fail_round` leaves them bit for bit, and the same pendings back at
    the queue's front (what a nacked round's requeue does) stage the
    same payload bytes under the term the slot has by then."""
    dp = bare_plane(engine())
    try:
        fill_queues(dp, 9, [2, 6], pendings=(2, 2), rows=(2, 4))
        pends = {s: list(q) for s, q in dp._appends.items()}
        before = {s: [p.rows.copy() for p in q] for s, q in pends.items()}
        inp, ctx = dp._drain()
        first = {s: staged_rows(ctx, s) for s in pends}
        dp._fail_round(ctx, RuntimeError("the launch failed"))
        for s, q in pends.items():
            assert all(p.future.done() for p in q)
            for p, rows in zip(q, before[s]):
                assert np.array_equal(p.rows, rows)
                assert not p.rows[:, 4:8].any()  # the term field: unset
        old = {s: int(dp.term[s]) for s in pends}
        for s, q in pends.items():
            dp.set_leader(s, 0, old[s] + 3)
            with dp._lock:
                dp._appends.setdefault(s, [])[0:0] = q
        inp, ctx = dp._drain()
        for s in pends:
            again = staged_rows(ctx, s)
            term = np.frombuffer(np.int32(old[s] + 3).tobytes(), np.uint8)
            assert (again[:, 4:8] == term).all()
            assert (first[s][:, 4:8] == np.frombuffer(
                np.int32(old[s]).tobytes(), np.uint8)).all()
            again[:, 4:8] = first[s][:, 4:8] = 0
            assert np.array_equal(again, first[s])
            assert np.array_equal(again, np.concatenate(before[s]))
    finally:
        dp.stop()

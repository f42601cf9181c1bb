"""The pure-Python model of the round: an independent mirror of
core/step.py's rules (replica_control, vote_step, the resync copy) with
explicit per-replica state. It imports nothing of the engine but
`ALIGN`; tests/test_model_check.py fuzzes the engine against it and
tests/test_control_fusion.py replays a scripted history through it on
both bindings."""

from __future__ import annotations

import numpy as np

from ripplemq_tpu.core.config import ALIGN


class Model:
    """Pure-Python mirror of core/step.py's replica_control, vote_step,
    and the resync copy, with explicit per-replica state."""

    def __init__(self, cfg):
        self.cfg = cfg
        P, R, C = cfg.partitions, cfg.replicas, cfg.max_consumers
        self.rows: list[list[bytes]] = [[] for _ in range(P)]  # global log
        self.end = np.zeros((R, P), np.int64)
        self.last_term = np.zeros((R, P), np.int64)
        self.current_term = np.zeros((R, P), np.int64)
        self.commit = np.zeros((R, P), np.int64)
        self.offsets = np.zeros((R, P, C), np.int64)

    # ---- one data round for one partition (mirrors replica_control) ----
    def step(self, p, payloads, off_updates, leader, term, alive, trim):
        cfg = self.cfg
        B, S, R = cfg.max_batch, cfg.slots, cfg.replicas
        counts = len(payloads)
        advance = -(-counts // ALIGN) * ALIGN if counts else 0
        leader_known = 0 <= leader < R
        leader_alive = leader_known and alive[leader]
        # base / leader_last_term: psum of leader's values masked alive.
        base = int(self.end[leader, p]) if leader_alive else 0
        llt = int(self.last_term[leader, p]) if leader_alive else 0
        acks = []
        for r in range(R):
            term_ok = term >= self.current_term[r, p]
            log_match = self.end[r, p] == base and (
                base == 0 or self.last_term[r, p] == llt
            )
            capacity = counts == 0 or (base + B - trim <= S)
            work = counts > 0 or len(off_updates) > 0
            acks.append(bool(
                alive[r] and leader_alive and term_ok and log_match
                and capacity and work
            ))
        votes = sum(acks)
        committed = votes >= cfg.quorum
        for r in range(R):
            do_write = acks[r] and committed
            if do_write and counts:
                self.end[r, p] = base + advance
                self.last_term[r, p] = term
            if do_write:
                self.commit[r, p] = max(
                    self.commit[r, p],
                    base + advance if counts else base,
                )
                for cslot, off in off_updates:
                    self.offsets[r, p, cslot] = off
            # Unconditional (matches the device exactly).
            self.current_term[r, p] = max(self.current_term[r, p], term)
        if committed and counts and base == len(self.rows[p]):
            self.rows[p].extend(payloads)
            self.rows[p].extend([b""] * (advance - counts))
        return base, votes, committed

    # ---- one election for one partition (mirrors vote_step) ----
    def vote(self, p, cand, cand_term, alive):
        cfg = self.cfg
        R = cfg.replicas
        cand_alive = 0 <= cand < R and alive[cand]
        c_end = int(self.end[cand, p]) if cand_alive else 0
        c_lt = int(self.last_term[cand, p]) if cand_alive else 0
        grants = 0
        granted = []
        for r in range(R):
            up_to_date = c_lt > self.last_term[r, p] or (
                c_lt == self.last_term[r, p] and c_end >= self.end[r, p]
            )
            g = bool(alive[r] and cand_alive
                     and cand_term > self.current_term[r, p] and up_to_date)
            granted.append(g)
            grants += g
        for r in range(R):
            if granted[r]:
                self.current_term[r, p] = cand_term
        return grants >= cfg.quorum, grants

    def resync(self, p, src, dst):
        for leaf in (self.end, self.last_term, self.current_term,
                     self.commit):
            leaf[dst, p] = leaf[src, p]
        self.offsets[dst, p] = self.offsets[src, p]

    def read(self, p, replica, offset):
        cfg = self.cfg
        commit = int(self.commit[replica, p])
        count = min(max(commit - max(offset, 0), 0), cfg.read_batch)
        window = self.rows[p][offset : offset + count]
        return [m for m in window if m], count

    # ---- whole-input conveniences for scripted histories ----
    def round(self, appends, offset_updates, leader, term, alive, trim=None):
        """One data round over every partition (`leader`/`term` one value
        for all, `trim` a [P] array or None). Returns the StepOutput the
        device must report: base, votes, committed and the post-round
        commit index (the max over replicas, like the device's pmax)."""
        P = self.cfg.partitions
        base = np.zeros((P,), np.int64)
        votes = np.zeros((P,), np.int64)
        committed = np.zeros((P,), bool)
        for p in range(P):
            base[p], votes[p], committed[p] = self.step(
                p, (appends or {}).get(p, []),
                (offset_updates or {}).get(p, []), leader, term, alive,
                0 if trim is None else int(trim[p]),
            )
        return {"base": base, "votes": votes, "committed": committed,
                "commit": self.commit.max(axis=0)}

    def snapshot(self):
        """Copies of the per-replica scalar state, named like the
        engine's state fields."""
        return {"log_end": self.end.copy(),
                "last_term": self.last_term.copy(),
                "current_term": self.current_term.copy(),
                "commit": self.commit.copy(),
                "offsets": self.offsets.copy()}

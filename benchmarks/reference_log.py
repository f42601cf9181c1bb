"""The plain reference: what each partition's log must hold, and the
comparisons that decide `correct`.

The reference is a per-partition list built from the generators' own
records of the produce calls they were ACKED for, in ack-offset order. It
imports nothing of the broker and reads nothing the broker made except the
base offset each ack carried (the order) - the bytes are rebuilt from
--seed and the recorded stamps (payload.py).

A record is one acked produce call:
    stream, client, seq0, n, stamp (ns), offset (the ack's base offset)

Two comparisons, each a set of counts with the limit 0, both through
`compare_all` (every partition against the reference; messages the
producers never sent are `extra`):
  * what a subscription received per partition, whole or as an exact
    prefix - once for every subscription of the cell
    (`compare_subscriptions`), since each is owed the whole log;
  * what one broker's data dir holds per partition.
"""

from __future__ import annotations

import numpy as np

from benchmarks import payload

RECORD = np.dtype([("stream", "<u2"), ("client", "<u2"), ("seq0", "<u4"),
                   ("n", "<u4"), ("stamp", "<u8"), ("offset", "<i8"),
                   ("send", "<i8"), ("ack", "<i8")])


class ReferenceLog:
    """stream -> [n_messages, size] uint8, in ack-offset order."""

    def __init__(self, seed: int, size: int, records: np.ndarray,
                 n_streams: int) -> None:
        self.size = int(size)
        self.n_streams = int(n_streams)
        pool = payload.make_pool(seed)
        order = np.lexsort((records["offset"], records["stream"]))
        recs = records[order]
        reps = recs["n"].astype(np.int64)
        total = int(reps.sum())
        first = np.cumsum(reps) - reps
        within = np.arange(total, dtype=np.int64) - np.repeat(first, reps)
        stream = np.repeat(recs["stream"], reps)
        block = payload.build(
            pool, stream, np.repeat(recs["client"], reps),
            (np.repeat(recs["seq0"], reps).astype(np.int64) + within)
            .astype(np.uint32),
            np.repeat(recs["stamp"], reps), self.size)
        counts = np.bincount(stream, minlength=self.n_streams)
        ends = np.cumsum(counts)
        self._block = block
        self._start = ends - counts
        self.counts = counts
        # Two acked calls on one partition can never share a base offset.
        same = (recs["stream"][1:] == recs["stream"][:-1]) & (
            recs["offset"][1:] == recs["offset"][:-1])
        self.duplicate_offsets = int(same.sum())

    def stream(self, s: int) -> np.ndarray:
        a = int(self._start[s])
        return self._block[a:a + int(self.counts[s])]

    @property
    def total(self) -> int:
        return int(self.counts.sum())


def _as_rows(blob, size: int):
    """(rows [k, size], leftover bytes) of a received byte string."""
    arr = np.frombuffer(blob, np.uint8) if not isinstance(blob, np.ndarray) \
        else blob
    k = len(arr) // size
    return arr[:k * size].reshape(k, size), len(arr) - k * size


def compare_stream(want: np.ndarray, got_blob, size: int,
                   prefix_ok: bool) -> dict:
    """One partition: `got` must equal `want` (or, with prefix_ok, be an
    exact prefix of it). Counts messages that differ, are missing, or were
    never sent."""
    got, ragged = _as_rows(got_blob, size)
    k = min(len(got), len(want))
    differ = int((got[:k] != want[:k]).any(axis=1).sum()) if k else 0
    missing = len(want) - k
    extra = len(got) - k + (1 if ragged else 0)
    return {"differ": differ, "extra": extra,
            "missing": 0 if prefix_ok else missing, "lag": missing}


def compare_all(ref: ReferenceLog, got: dict, prefix_ok: bool) -> dict:
    """Every partition of the reference against `got` (stream -> bytes or
    uint8 array; a stream absent from `got` received nothing). A stream in
    `got` the reference does not know is all `extra`."""
    out = {"differ": 0, "missing": 0, "extra": 0, "lag": 0,
           "bad_streams": []}
    for s in range(ref.n_streams):
        r = compare_stream(ref.stream(s), got.get(s, b""), ref.size,
                           prefix_ok)
        for k in ("differ", "missing", "extra", "lag"):
            out[k] += r[k]
        if r["differ"] or r["missing"] or r["extra"]:
            out["bad_streams"].append(s)
    for s, blob in got.items():
        if not 0 <= s < ref.n_streams and len(blob):
            out["extra"] += len(blob) // ref.size + 1
            out["bad_streams"].append(int(s))
    out["bad_streams"] = out["bad_streams"][:8]
    return out


def compare_subscriptions(ref: ReferenceLog, got: dict, names: list,
                          prefix_ok: bool) -> dict:
    """`compare_all` once for every subscription in `names` against
    `got` (name -> stream -> bytes; a name is in `got` if any consumer of
    that subscription reported, with nothing received or not). The counts
    are the sums; `reported` is how many of `names` are in `got`;
    `by_subscription` keeps each one's own result, and `bad` names those
    with a count that is not 0. One that never reported is owed
    everything: all `missing` (all `lag` where a prefix will do)."""
    out = {"differ": 0, "missing": 0, "extra": 0, "lag": 0,
           "reported": sum(n in got for n in names), "by_subscription": {},
           "bad": []}
    for name in names:
        r = compare_all(ref, got.get(name, {}), prefix_ok)
        out["by_subscription"][name] = r
        for k in ("differ", "missing", "extra", "lag"):
            out[k] += r[k]
        if r["bad_streams"] or name not in got:
            out["bad"].append(name)
    return out

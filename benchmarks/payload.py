"""Seeded message bodies: the one place that says what a benchmark message is.

A message is `size` bytes (the configuration's `message_bytes`: 100 or
1,024):

    [0:2]   stream   u16  index of its (topic, partition) in the cell's stream list
    [2:4]   client   u16  the producer client that sent it
    [4:8]   seq      u32  that client's own message counter
    [8:16]  stamp    u64  time.monotonic_ns() it was due (open loop) or sent
                          (closed loop): one machine, one clock
    [16:]   body     bytes cut from a pool made from --seed, at an offset
                          that (stream, client, seq) decide

(client, seq) make every message distinct; the body makes a flipped byte or
a message moved between partitions show. Everything but the stamp follows
from the seed, and the generator records the stamp of every call it was
acked for, so `reference_log` can rebuild the exact bytes without reading
anything the system under test produced.
"""

from __future__ import annotations

import numpy as np

HEAD = 16
POOL_BYTES = 1 << 20


def make_pool(seed: int) -> np.ndarray:
    """The body pool of a run: POOL_BYTES random bytes from the seed."""
    return np.random.default_rng([int(seed), 0x706F6F6C]).integers(
        0, 256, POOL_BYTES, dtype=np.uint8)


def body_offsets(stream, client, seq, size: int) -> np.ndarray:
    """Where in the pool each message's body starts (vectorised)."""
    stream = np.asarray(stream, np.uint64)
    client = np.asarray(client, np.uint64)
    seq = np.asarray(seq, np.uint64)
    x = (stream * np.uint64(0x9E3779B1) + client * np.uint64(0x85EBCA77)
         + seq * np.uint64(0xC2B2AE3D)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(15)
    x = (x * np.uint64(0x2C1B3C6D)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(12)
    return (x % np.uint64(POOL_BYTES - (size - HEAD))).astype(np.int64)


def build(pool: np.ndarray, stream, client, seq, stamp, size: int) -> np.ndarray:
    """[n, size] uint8: the messages with these heads (arrays of length n,
    or scalars broadcast against `seq`)."""
    seq = np.atleast_1d(np.asarray(seq, np.uint32))
    n = len(seq)
    out = np.empty((n, size), np.uint8)
    head = np.empty(n, dtype=[("stream", "<u2"), ("client", "<u2"),
                              ("seq", "<u4"), ("stamp", "<u8")])
    head["stream"] = stream
    head["client"] = client
    head["seq"] = seq
    head["stamp"] = stamp
    out[:, :HEAD] = head.view(np.uint8).reshape(n, HEAD)
    off = body_offsets(head["stream"], head["client"], seq, size)
    window = np.lib.stride_tricks.sliding_window_view(pool, size - HEAD)
    out[:, HEAD:] = window[off]
    return out


def to_messages(block: np.ndarray) -> list[bytes]:
    """The rows of a [n, size] block as the list of bytes the SDK takes."""
    n, size = block.shape
    blob = block.tobytes()
    return [blob[i * size:(i + 1) * size] for i in range(n)]


def stamps_of(block: np.ndarray) -> np.ndarray:
    """The u64 stamps of a [n, size] block of received messages."""
    return np.ascontiguousarray(block[:, 8:16]).view("<u8")[:, 0]

"""Erasure-coded protection for sealed log segments (RS(3,2) over GF(2⁸)).

The reference's only durability story is JRaft's full replication — every
broker stores every byte of every partition it replicates (reference:
mq-broker/src/main/java/metadata/raft/PartitionRaftServer.java:88-90
storage URIs; SURVEY.md §2.4). Here, sealed (rotated, immutable) segment
files additionally get k+m = 5 Reed–Solomon shards at 5/3× overhead; any
k = 3 surviving shards rebuild the segment byte-for-byte, so a corrupt or
lost sealed segment no longer costs the data (the torn-tail contract only
protects the ACTIVE segment's tail). Encoding runs on the encoding
PROCESS's default JAX backend (ripplemq_tpu.ops.rs): the Pallas GF(2⁸)
matmul kernel in the broker that owns the chip, the XLA path on host
cores in every broker started pinned to CPU — which is every broker but
the chip owner, because a chip belongs to one process.

Layout: shards of `segment-XXXXXXXX.log` live in `<store>/rs/` as
`segment-XXXXXXXX.log.shard{0..4}`. Shard i < k is data quarter i; shard
k+i is parity i. Each shard file carries its own CRC plus the CRC of the
whole original segment, so repair can tell a stale shard set from a
usable one.

Protection window note: protect_store treats shard-file PRESENCE of a
complete set as protected without re-reading shard CRCs (a full CRC scrub
per flush would defeat the off-path design), so a shard that rots on disk
silently lowers that segment's loss tolerance below m until the next
boot. The window CLOSES at boot: repair_store validates every shard's
CRC and rewrites any set short of k+m valid shards — including a fully
rotted or mixed-generation set over a healthy segment, which is
re-encoded fresh (directed coverage: tests/test_storage.py shard-rot
repair tests).
"""

from __future__ import annotations

import contextlib
import os
import struct
import zlib
from typing import Optional

import numpy as np

from ripplemq_tpu.ops.rs import rs_encode, rs_reconstruct, shard_bucket
from ripplemq_tpu.utils.logs import get_logger

_log = get_logger("storage")

# ONE RS geometry for the whole repo: the sealed-segment shards here and
# the hot-path replication stripes (ripplemq_tpu/stripes/) share the
# codec constants, so both reconstruct with the same extended-Cauchy
# matrices and a deployment reasons about a single k-of-k+m contract.
from ripplemq_tpu.stripes.codec import RS_K as K, RS_M as M

_MAGIC = 0x52535348  # "RSSH"
_VERSION = 1
# magic, version, shard index, k, m, original segment length, crc of the
# original segment bytes, crc of this shard's payload
_HEADER = struct.Struct("<IBBBBQII")


class ShardError(Exception):
    pass


def _rs_dir(store_dir: str) -> str:
    return os.path.join(store_dir, "rs")


def shard_paths(store_dir: str, seg_name: str) -> list[str]:
    return [
        os.path.join(_rs_dir(store_dir), f"{seg_name}.shard{i}")
        for i in range(K + M)
    ]


def _shard_length(orig_len: int) -> int:
    return -(-orig_len // K)  # ceil; last data shard is zero-padded


def store_bucket(segment_bytes: int) -> int:
    """The shard length the RS program of a store with this segment size
    runs at: the ladder entry (ops/rs.shard_bucket) of a full segment's
    shard. Built at open (warm_encode); every seal of that store is
    padded up to it (encode_segment `bucket_floor`)."""
    return shard_bucket(_shard_length(segment_bytes))


def warm_encode(segment_bytes: int, **kw) -> int:
    """Encode one zeroed input at a full segment's bucket, so the RS
    program a store of this segment size needs exists before its first
    seal; returns the full segment's shard length. Sealed segments fall
    short of segment_bytes (rotation comes before the write that would
    cross it) by one write, which may be a ladder entry or more: the
    store pads them up to this bucket."""
    rs_encode(np.zeros((K, store_bucket(segment_bytes)), np.uint8),
              k=K, m=M, **kw)
    return _shard_length(segment_bytes)


def encode_segment(store_dir: str, seg_name: str, stage=None,
                   bucket_floor: int = 0, **kw) -> list[str]:
    """Write the K+M shard files for one sealed segment. Atomic per shard
    (tmp + rename); returns the shard paths. `stage(shard_len)`, where
    given, returns a context manager opened around the RS encode alone
    (the owning store's `seal.rs_encode` timer). `bucket_floor` is the
    least length the encoder runs at: the owning store gives the bucket
    of its `segment_bytes`, the one program it built at open, so a
    segment that sealed short (rotation comes BEFORE the write that
    would cross `segment_bytes`, and one round of 1 KB rows can be many
    MiB) is padded up to it instead of compiling a program of its own
    beside traffic. `kw` routes to ops/rs.gf_matmul (use_pallas /
    interpret)."""
    seg_path = os.path.join(store_dir, seg_name)
    with open(seg_path, "rb") as f:
        raw = f.read()
    data_crc = zlib.crc32(raw) & 0xFFFFFFFF
    n = _shard_length(len(raw))
    # Shard j is raw[j*n:(j+1)*n], the last zero-padded to n. The rows
    # are laid out at the bucket length the encoder runs at, so the
    # zeros it needs past n are these and nothing is copied again.
    nb = max(shard_bucket(n), bucket_floor)
    data = np.zeros((K, nb), np.uint8)
    flat = np.frombuffer(raw, np.uint8)
    for j in range(K):
        part = flat[j * n : (j + 1) * n]
        data[j, : len(part)] = part
    with stage(nb) if stage is not None else contextlib.nullcontext():
        parity = rs_encode(data, k=K, m=M, **kw)
    shards = [*data[:, :n], *parity[:, :n]]
    os.makedirs(_rs_dir(store_dir), exist_ok=True)
    paths = shard_paths(store_dir, seg_name)
    for i, path in enumerate(paths):
        payload = shards[i]  # a contiguous row: hashed and written in place
        header = _HEADER.pack(
            _MAGIC, _VERSION, i, K, M, len(raw), data_crc,
            zlib.crc32(payload) & 0xFFFFFFFF,
        )
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(header)
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except FileNotFoundError:
            # The rs/ directory vanished under us (disaster-recovery
            # teardown racing a still-draining encode worker). Shards
            # are DERIVED data: skip — the next protect pass re-encodes
            # from the sealed segment instead of crashing the worker.
            return []
    return paths


def _read_shard(path: str) -> Optional[tuple[int, int, int, np.ndarray]]:
    """→ (index, orig_len, data_crc, payload) or None if missing/corrupt."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    if len(blob) < _HEADER.size:
        return None
    magic, version, idx, k, m, orig_len, data_crc, shard_crc = _HEADER.unpack(
        blob[: _HEADER.size]
    )
    if magic != _MAGIC or version != _VERSION or (k, m) != (K, M):
        return None
    payload = blob[_HEADER.size :]
    if len(payload) != _shard_length(orig_len):
        return None
    if (zlib.crc32(payload) & 0xFFFFFFFF) != shard_crc:
        return None
    return idx, orig_len, data_crc, np.frombuffer(payload, np.uint8)


def reconstruct_segment(store_dir: str, seg_name: str, **kw) -> bytes:
    """Rebuild one segment's bytes from any K valid shards. Raises
    ShardError if fewer than K shards survive or the rebuilt bytes fail
    the recorded segment CRC."""
    present: dict[int, np.ndarray] = {}
    meta: Optional[tuple[int, int]] = None
    for path in shard_paths(store_dir, seg_name):
        got = _read_shard(path)
        if got is None:
            continue
        idx, orig_len, data_crc, payload = got
        if meta is None:
            meta = (orig_len, data_crc)
        elif meta != (orig_len, data_crc):
            raise ShardError(f"mixed shard generations for {seg_name}")
        present[idx] = payload
    if meta is None or len(present) < K:
        raise ShardError(
            f"{seg_name}: only {len(present)} valid shards, need {K}"
        )
    orig_len, data_crc = meta
    if all(i in present for i in range(K)):
        data = np.stack([present[i] for i in range(K)])
    else:
        data = rs_reconstruct(present, k=K, m=M, **kw)
    raw = data.reshape(-1).tobytes()[:orig_len]
    if (zlib.crc32(raw) & 0xFFFFFFFF) != data_crc:
        raise ShardError(f"{seg_name}: reconstructed bytes fail segment CRC")
    return raw


def _segment_names(store_dir: str) -> list[str]:
    if not os.path.isdir(store_dir):
        return []
    return sorted(
        f for f in os.listdir(store_dir)
        if f.startswith("segment-") and f.endswith(".log")
    )


def _shard_counts(store_dir: str) -> dict[str, int]:
    rs_dir = _rs_dir(store_dir)
    if not os.path.isdir(rs_dir):
        return {}
    counts: dict[str, int] = {}
    for f in os.listdir(rs_dir):
        stem, _, suffix = f.rpartition(".shard")
        if stem and suffix.isdigit():
            counts[stem] = counts.get(stem, 0) + 1
    return counts


def _protected_names(store_dir: str) -> set[str]:
    """Segment names with at least one shard file present (repair decides
    usability from shard CONTENTS — presence of any shard is enough to
    consider the set, since up to M shards may themselves be lost)."""
    return set(_shard_counts(store_dir))


def unprotected_names(store_dir: str) -> list[str]:
    """Sealed segments (every segment but the highest-numbered, which is
    still being appended) that hold data and lack a COMPLETE shard set —
    a crash mid-encode leaves a partial set, which must not count as
    protected (it may tolerate fewer than M losses, or none). Empty
    segments (a restart artifact: both store backends open a fresh index
    on boot) carry no data and are skipped. What a protect pass has to
    encode, and what the owning store observes as `seal.pending`."""
    counts = _shard_counts(store_dir)
    out = []
    for name in _segment_names(store_dir)[:-1]:
        if counts.get(name, 0) >= K + M:
            continue
        try:
            if os.path.getsize(os.path.join(store_dir, name)) == 0:
                continue
        except OSError:
            continue  # GC'd between the listing and the stat
        out.append(name)
    return out


def protect_store(store_dir: str, limit: Optional[int] = None,
                  **kw) -> list[str]:
    """Encode shards for the sealed segments that lack a complete shard
    set (unprotected_names). `limit` bounds work per call so callers can
    amortize. Returns the segment names encoded."""
    done = []
    for name in unprotected_names(store_dir):
        encode_segment(store_dir, name, **kw)
        done.append(name)
        if limit is not None and len(done) >= limit:
            break
    return done


def shard_file_names(store_dir: str) -> list[str]:
    """Names of every shard file in the store's rs/ dir (push duty)."""
    rs_dir = _rs_dir(store_dir)
    if not os.path.isdir(rs_dir):
        return []
    return sorted(
        f for f in os.listdir(rs_dir)
        if ".shard" in f and not f.endswith(".tmp")
    )


def valid_shard_name(name: str) -> bool:
    """Guard for wire-supplied shard file names (path-traversal safety +
    exact shape check — segment-XXXXXXXX.log.shardN — before anything
    touches the filesystem or parses the index digits)."""
    stem, _, suffix = name.rpartition(".shard")
    return (
        len(stem) == 20
        and suffix.isdigit()
        and int(suffix) < K + M
        and stem.startswith("segment-")
        and stem.endswith(".log")
        and stem[8:16].isdigit()
        and "/" not in name
        and "\\" not in name
        and ".." not in name
    )


def refill_from_peers(store_dir: str, list_fns, get_fn) -> list[str]:
    """Re-populate rs/ with peer-held shard copies for sealed segments
    MISSING from this store, so the ordinary repair_store pass can
    rebuild them — the disaster path when a broker lost both a segment
    and its local shards (the reference survives this only because every
    broker fully replicates every partition it hosts,
    PartitionRaftServer.java:88-90; here any K of the K+M distributed
    shards suffice at (K+M)/K x overhead).

    `list_fns` is [(peer_tag, callable() -> shard file names held for
    this owner)], `get_fn(peer_tag, name) -> bytes | None`. Fetched blobs
    are CRC-validated by the shard reader before being trusted; invalid
    or unsafe names are skipped. Best-effort: unreachable peers are the
    caller's problem to log. Returns the segment names refilled."""
    # Which shard sets do peers hold that we cannot reconstruct locally?
    # Keyed on local shard count < K, NOT on segment-file presence: a
    # present-but-corrupt segment whose local shards were also lost is
    # exactly as dead as a missing one, and only peer shards can save it
    # (a present-and-healthy file costs at most K redundant fetches —
    # repair validates health before rewriting anything). Segments below
    # the persisted GC floor were deleted deliberately — never refill
    # them.
    from ripplemq_tpu.storage.segment import gc_floor, segment_index

    floor = gc_floor(store_dir)
    remote: dict[str, list[tuple[str, str]]] = {}  # seg -> [(peer, fname)]
    for peer, list_fn in list_fns:
        try:
            names = list_fn()
        except Exception:
            continue
        for fname in names:
            if not valid_shard_name(fname):
                continue
            stem = fname.rpartition(".shard")[0]
            if segment_index(stem) < floor:
                continue
            remote.setdefault(stem, []).append((peer, fname))
    refilled = []
    rs_dir = _rs_dir(store_dir)
    for stem, sources in sorted(remote.items()):
        # VALID local shards only — a corrupt shard file present on disk
        # must not count toward reconstructability, and must not block
        # its index from being refilled (it gets overwritten below).
        valid_idx = {
            i for i, p in enumerate(shard_paths(store_dir, stem))
            if _read_shard(p) is not None
        }
        have = len(valid_idx)
        if have >= K:
            continue  # locally reconstructable already
        got = 0
        seen_idx: set[int] = set(valid_idx)
        for peer, fname in sources:
            if have + got >= K:
                break  # K shards reconstruct; repair re-encodes the rest
            idx = int(fname.rpartition(".shard")[2])
            if idx in seen_idx:
                continue
            try:
                blob = get_fn(peer, fname)
            except Exception:
                continue
            if not blob:
                continue
            os.makedirs(rs_dir, exist_ok=True)
            tmp = os.path.join(rs_dir, fname + ".tmp")
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            if _read_shard(tmp) is None:  # CRC/shape reject
                os.remove(tmp)
                continue
            os.replace(tmp, os.path.join(rs_dir, fname))
            seen_idx.add(idx)
            got += 1
        if got:
            refilled.append(stem)
    return refilled


def segment_index_gaps(store_dir: str) -> bool:
    """True when the store's segment numbering has holes (indices rotate
    contiguously, so a hole means a sealed segment FILE was lost) — the
    cheap local evidence that gates boot-time peer refill. Indices below
    the persisted GC floor were deleted deliberately and are not
    holes."""
    from ripplemq_tpu.storage.segment import gc_floor

    names = _segment_names(store_dir)
    if not names:
        return False
    indices = {int(n[8:16]) for n in names}
    floor = gc_floor(store_dir)
    return indices != set(range(floor, max(indices) + 1))


def repair_store(store_dir: str, errors: Optional[list] = None,
                 **kw) -> list[str]:
    """Rebuild sealed segment files that are missing or fail their shard-
    recorded CRC. Called before replay (recover_image). Best-effort by
    design: segments without shard sets — and ones whose shard sets are
    too damaged to reconstruct (> M losses) — are left to the scanner's
    own corruption handling, so a half-dead shard set degrades exactly
    like a dead one instead of blocking broker boot. Returns the segment
    names repaired. A shard re-encode that fails never blocks the
    repair, but it is logged and appended to `errors` (the broker
    carries that list into admin.stats `erasure_errors`) instead of
    vanishing."""
    repaired = []

    def reencode(name: str) -> None:
        # Shards are derived data and encode runs device kernels
        # (rs_encode), so any failure here — OSError or a JAX/XLA
        # runtime error — must not block recovery/boot.
        try:
            encode_segment(store_dir, name, **kw)
        except Exception as e:
            msg = f"{name}: re-encode failed: {type(e).__name__}: {e}"
            _log.warning("erasure repair of %s: %s", store_dir, msg)
            if errors is not None:
                errors.append(msg)

    for name in sorted(_protected_names(store_dir)):
        seg_path = os.path.join(store_dir, name)
        # The health check must use a CONSISTENT shard generation: a stale
        # straggler shard must not mark a healthy segment unhealthy
        # (reconstruct_segment refuses mixed generations anyway), so
        # require every valid shard to agree on (orig_len, data_crc).
        gens: set[tuple[int, int]] = set()
        valid_shards = 0
        for path in shard_paths(store_dir, name):
            got = _read_shard(path)
            if got is not None:
                _, o, c, _ = got
                gens.add((o, c))
                valid_shards += 1
        if len(gens) != 1:
            # No single consistent generation survives: every shard
            # rotted, or stale stragglers disagree. protect_store counts
            # shard-file PRESENCE (the documented protection window), so
            # without this branch such a set would stay "protected"
            # while protecting nothing. If the segment file itself is
            # readable, re-encode a fresh consistent set from it; an
            # unreadable segment with no usable shards stays the
            # scanner's problem, as before.
            if os.path.isfile(seg_path):
                reencode(name)
            continue
        orig_len, data_crc = next(iter(gens))
        try:
            with open(seg_path, "rb") as f:
                raw = f.read()
            healthy = (
                len(raw) == orig_len
                and (zlib.crc32(raw) & 0xFFFFFFFF) == data_crc
            )
        except OSError:
            healthy = False
        if not healthy:
            try:
                raw = reconstruct_segment(store_dir, name, **kw)
            except ShardError:
                continue  # > M losses: fall through to the scanner
            tmp = seg_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(raw)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, seg_path)
            repaired.append(name)
        if valid_shards < K + M:
            # Restore full m-loss tolerance: re-derive the lost/corrupt
            # shards from the (now healthy) segment bytes.
            reencode(name)
    return repaired

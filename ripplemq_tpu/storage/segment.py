"""Append-only CRC-framed segment store (ctypes ↔ native/segstore.cpp).

One record per committed replication round (or offset-commit batch or
metadata blob). The native C++ library owns the hot write path; a pure
-Python implementation writes the byte-identical format (shared CRC-32 /
framing), so files are interchangeable and CPU-only environments need no
toolchain. See native/segstore.cpp for the frame layout and the torn-tail
crash contract.

The library is compiled on demand from the checked-in source (no network,
just g++) and cached next to it.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import threading
import zlib
from typing import Iterator, Optional

from ripplemq_tpu.obs.lockwitness import make_lock
from ripplemq_tpu.utils.logs import get_logger

_log = get_logger("storage")

REC_APPEND = 1
REC_OFFSETS = 2
REC_META = 3
# Idempotent-producer dedup entries: one record per committed round and
# slot, written immediately AFTER that slot's REC_APPEND (a torn tail
# may drop the pid record but never leave it without its rows — the
# reverse order would let a dedup-ack point at rows that were never
# persisted). Payload: packed (pid u32, seq i64, rows u32, base i64)
# per producer batch; `base` in the header carries the entry count.
REC_PIDSEQ = 4
# Striped replication (ripplemq_tpu/stripes/): a standby in
# replication="striped" mode persists Reed–Solomon stripe FRAMES of the
# committed-round stream instead of full rows. Header fields: slot =
# stripe index, base = gsn & 0x7FFFFFFF (display/filtering only — the
# self-describing frame header inside the payload is the authority);
# payload = one stripes/codec.py frame (its own header-covered CRC on
# top of this store frame's). Promotion/boot replay reconstructs the
# record stream from any k of the k+m stripes (stripes/recovery.py).
REC_STRIPE = 5

_MAGIC = 0x474C5152
_HEADER = struct.Struct("<IBIIII")  # magic, type, slot, base, len, crc
_HEADER_PREFIX = struct.Struct("<IBIII")  # the 17 bytes the crc covers
_CRC = struct.Struct("<I")


def _frame_crc(header17: bytes, payload: bytes) -> int:
    """CRC-32 of a record frame: the 17 header bytes BEFORE the crc
    field, chained with the payload. Header corruption (a flipped bit
    in type/slot/base/len) must fail verification exactly like payload
    rot — a payload-only crc let a bit-flipped `base` pass the boot
    health walk and replay acked rows at the wrong offsets (the chaos
    disk_flip matrix; sealed+erasure-encoded segments were covered by
    the shard-level whole-file crc, but the active and not-yet-encoded
    segments were not).

    FORMAT BREAK (PR 4): frames written by the pre-PR-4 payload-only
    crc fail this check — deliberately unversioned, because a legacy
    fallback would accept exactly the header damage this closes (a
    flipped header byte passes the payload-only check by construction).
    No store artifacts cross versions in this repo (data dirs are
    ephemeral test/drill state); a deployment upgrading live stores
    would need a one-shot rewrite migration first."""
    return zlib.crc32(payload, zlib.crc32(header17)) & 0xFFFFFFFF

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False


def _load_native() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    with _LIB_LOCK:
        if _LIB_TRIED:
            return _LIB
        _LIB_TRIED = True
        src_dir = os.path.abspath(_NATIVE_DIR)
        so_path = os.path.join(src_dir, "libsegstore.so")
        src_path = os.path.join(src_dir, "segstore.cpp")
        def compile_and_load(force: bool) -> ctypes.CDLL:
            if force or not os.path.exists(so_path) or (
                os.path.getmtime(so_path) < os.path.getmtime(src_path)
            ):
                # Build to a private name, then rename: the brokers of a
                # cluster start together and all find the library
                # missing, and g++ writing `-o so_path` in place lets a
                # sibling dlopen a half-written file.
                tmp_path = f"{so_path}.{os.getpid()}.tmp"
                try:
                    subprocess.run(
                        ["g++", "-O2", "-fPIC", "-std=c++17", "-shared",
                         "-o", tmp_path, src_path],
                        check=True, capture_output=True, timeout=120,
                    )
                    os.replace(tmp_path, so_path)
                finally:
                    if os.path.exists(tmp_path):
                        os.remove(tmp_path)
            return ctypes.CDLL(so_path)

        try:
            if not os.path.exists(src_path):
                return None
            lib = compile_and_load(force=False)
            try:
                _bind(lib)
            except AttributeError:
                # A cached .so from older source can carry a fresher
                # mtime (copied artifacts, clock skew) yet lack newer
                # symbols: rebuild once from the checked-in source.
                lib = compile_and_load(force=True)
                _bind(lib)
        except (OSError, subprocess.SubprocessError, AttributeError):
            return None
        _LIB = lib
        return _LIB


def _bind(lib) -> None:
    """Declare every exported symbol's signature — inside the loader's
    try so a stale library missing a symbol degrades to the Python path
    instead of crashing boot."""
    lib.segstore_open.restype = ctypes.c_void_p
    lib.segstore_open.argtypes = [ctypes.c_char_p, ctypes.c_long]
    lib.segstore_append.restype = ctypes.c_int
    lib.segstore_append.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.segstore_append_at.restype = ctypes.c_int
    lib.segstore_append_at.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_long),
    ]
    lib.segstore_append_blob.restype = ctypes.c_int
    lib.segstore_append_blob.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_long),
    ]
    lib.segstore_flush.restype = ctypes.c_int
    lib.segstore_flush.argtypes = [ctypes.c_void_p]
    lib.segstore_close.restype = None
    lib.segstore_close.argtypes = [ctypes.c_void_p]
    lib.segscan_open.restype = ctypes.c_void_p
    lib.segscan_open.argtypes = [ctypes.c_char_p]
    lib.segscan_next.restype = ctypes.c_int
    lib.segscan_next.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
    ]
    lib.segscan_next_at.restype = ctypes.c_int
    lib.segscan_next_at.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_long),
    ]
    lib.segscan_close.restype = None
    lib.segscan_close.argtypes = [ctypes.c_void_p]


def native_available() -> bool:
    return _load_native() is not None


class CorruptStoreError(Exception):
    """CRC/framing failure in the middle of the store (not a torn tail)."""


def list_segment_files(directory: str) -> list[str]:
    """Sorted segment file names in a store directory (with
    segment_index/segment_name below, the one place the naming scheme is
    interpreted on the Python side; the native scanner mirrors it in
    segstore.cpp list_segments)."""
    if not os.path.isdir(directory):
        return []
    return sorted(
        f for f in os.listdir(directory)
        if f.startswith("segment-") and f.endswith(".log")
    )


def segment_index(name: str) -> int:
    """segment-XXXXXXXX.log (or a derived shard name's stem) → index."""
    return int(name[8:16])


def segment_name(index: int) -> str:
    return f"segment-{index:08d}.log"


class SegmentStore:
    """Writer. `use_native=None` auto-selects the C++ library.

    `erasure=True` additionally RS(3,2)-encodes sealed segments from a
    background thread kicked by flush(): any 3 of the 5 shards rebuild a
    lost/corrupt sealed segment on recovery (see storage/erasure.py;
    repair runs in recover_image before replay). The encode runs OFF the
    flush path — flush is the replication step thread's durability
    barrier and must not stall for a whole segment's GF matmul — and an
    unencoded sealed segment is simply picked up by a later kick. The
    same thread's first job, at open, is to compile the program those
    encodes run (_erasure_warm)."""

    def __init__(self, directory: str, segment_bytes: int = 64 << 20,
                 use_native: Optional[bool] = None,
                 erasure: bool = False,
                 retention_bytes: Optional[int] = None,
                 metrics=None) -> None:
        self.directory = directory
        # Telemetry (obs.Metrics registry, usually the owning broker's):
        # append bytes/records and fsync latency are the disk half of the
        # settle-path decomposition. None or a DISABLED registry → the
        # handles stay None and the hot paths skip even the clock reads
        # (the obs=False A/B arm must actually shed the cost).
        self.metrics = metrics
        if metrics is not None and getattr(metrics, "enabled", True):
            self._h_fsync = metrics.histogram("store.fsync_us")
            self._c_append_bytes = metrics.counter("store.append_bytes")
            self._c_records = metrics.counter("store.append_records")
            self._clock = metrics.clock
            # One sealed segment's RS encode (obs/stages.py), and how
            # many RS programs this store has asked for: one per shard
            # length BUCKET (ops/rs.shard_bucket), the first of them at
            # open (_erasure_warm), so a rise later is a compile beside
            # traffic.
            self._st_rs_encode = metrics.stage("seal.rs_encode")
            self._c_rs_new_shapes = metrics.counter("rs.new_shapes")
            # Whether seals keep up, as numbers: segments sealed (an
            # append landed in a later segment than the last one did),
            # and at each erasure kick how many sealed segments still
            # lack their shard set.
            self._c_sealed = metrics.counter("seal.segments")
            self._h_seal_pending = metrics.histogram("seal.pending")
        else:
            self._h_fsync = None
            self._c_append_bytes = self._c_records = None
            self._clock = None
            self._st_rs_encode = self._c_rs_new_shapes = None
            self._c_sealed = self._h_seal_pending = None
        self._rs_shapes: set[int] = set()  # erasure thread only
        self.segment_bytes = segment_bytes
        self.erasure = erasure
        # Size-capped disk retention: gc() deletes the OLDEST sealed
        # segments (and their local shards) while the sealed total
        # exceeds this. None = unlimited (the default; the reference
        # grows without bound too — in JVM heap).
        self.retention_bytes = retention_bytes
        self._erasure_thread: Optional[threading.Thread] = None
        self._erasure_check_t = 0.0
        self.erasure_errors: list[str] = []
        # Deferred-fsync machinery (flush_async): one flusher thread per
        # store, started on first use.
        self._flusher: Optional[threading.Thread] = None
        self._flush_event = threading.Event()
        self._flush_stop = threading.Event()
        self.flush_errors: list[str] = []
        # Active segment index shadow for the flusher (avoids a listdir
        # per sync tick); updated by append() on both writer paths.
        self._active_seg = -1
        self._last_synced_seg = -1
        os.makedirs(directory, exist_ok=True)
        lib = _load_native() if use_native in (None, True) else None
        if use_native is True and lib is None:
            raise RuntimeError("native segstore requested but unavailable")
        self._lib = lib
        self._lock = make_lock("SegmentStore._lock")
        if lib is not None:
            self._handle = lib.segstore_open(
                directory.encode(), ctypes.c_long(segment_bytes)
            )
            if not self._handle:
                raise OSError(f"segstore_open failed for {directory}")
            self._file = None
        else:
            self._handle = None
            self._seg_index = self._next_index()
            self._file = open(self._seg_path(self._seg_index), "ab")
        if erasure:
            self._erasure_thread = threading.Thread(
                target=self._erasure_warm, daemon=True,
                name="segstore-erasure",
            )
            self._erasure_thread.start()

    # -- python fallback helpers --
    def _seg_path(self, index: int) -> str:
        return os.path.join(self.directory, f"segment-{index:08d}.log")

    def _next_index(self) -> int:
        existing = list_segment_files(self.directory)
        if not existing:
            return 0
        return int(existing[-1][8:16]) + 1

    # -- API --
    @property
    def is_native(self) -> bool:
        return self._handle is not None

    def append(self, rec_type: int, slot: int, base: int,
               payload: bytes) -> tuple[int, int]:
        """Append one framed record; returns its locator
        (segment_index, payload_byte_offset) — the position the retention
        read path (storage.logindex) serves lagging consumers from."""
        if len(payload) > (1 << 30):
            # The scanners reject length fields above 1 GiB as corruption;
            # writing one would be an acked-but-unreadable record.
            raise ValueError(
                f"record payload of {len(payload)} bytes exceeds the "
                f"1 GiB store record cap"
            )
        try:
            return self._append_locked(rec_type, slot, base, payload)
        finally:
            if self._c_append_bytes is not None:
                self._c_append_bytes.inc(len(payload))
                self._c_records.inc()

    def _append_locked(self, rec_type: int, slot: int, base: int,
                       payload: bytes) -> tuple[int, int]:
        with self._lock:
            if self._handle is not None:
                seg = ctypes.c_int()
                off = ctypes.c_long()
                rc = self._lib.segstore_append_at(
                    self._handle, rec_type, slot, base, payload, len(payload),
                    ctypes.byref(seg), ctypes.byref(off),
                )
                if rc != 0:
                    raise OSError("segstore_append failed")
                self._landed_in_locked(seg.value)
                return seg.value, off.value
            hdr = _HEADER_PREFIX.pack(
                _MAGIC, rec_type, slot, base, len(payload)
            )
            frame = hdr + _CRC.pack(_frame_crc(hdr, payload)) + payload
            if (
                self._file.tell() + len(frame) > self.segment_bytes
                and self._file.tell() > 0
            ):
                self._file.close()
                self._seg_index += 1
                self._file = open(self._seg_path(self._seg_index), "ab")
            locator = (self._seg_index, self._file.tell() + _HEADER.size)
            self._file.write(frame)
            self._file.flush()
            self._landed_in_locked(self._seg_index)
            return locator

    def append_many(
        self, records: list[tuple[int, int, int, bytes]]
    ) -> list[tuple[int, int]]:
        """Append a batch of records as ONE framed blob + ONE store
        write; returns each record's locator in order. Per-record
        append() calls pay a ctypes marshal + GIL round-trip each —
        under load that per-call overhead, not bandwidth, was the
        persist stage's capacity (PROFILE.md "host path"). The blob is
        framed identically to append(), so scan/recovery see the same
        stream. Batches are bounded by the callers (a settle window's
        records, a repl.rounds frame) — far under segment_bytes, so a
        blob never straddles segments."""
        if not records:
            return []
        frames: list[bytes] = []
        rel: list[int] = []  # payload offset of each record in the blob
        pos = 0
        payload_total = 0  # append_bytes counts PAYLOAD bytes (both paths)
        for rec_type, slot, base, payload in records:
            if len(payload) > (1 << 30):
                raise ValueError(
                    f"record payload of {len(payload)} bytes exceeds the "
                    f"1 GiB store record cap"
                )
            hdr = _HEADER_PREFIX.pack(
                _MAGIC, rec_type, slot, base, len(payload)
            )
            frames.append(hdr + _CRC.pack(_frame_crc(hdr, payload)))
            frames.append(payload)
            rel.append(pos + _HEADER.size)
            pos += _HEADER.size + len(payload)
            payload_total += len(payload)
        blob = b"".join(frames)
        try:
            return self._append_blob_locked(blob, rel)
        finally:
            if self._c_append_bytes is not None:
                self._c_append_bytes.inc(payload_total)
                self._c_records.inc(len(records))

    def _append_blob_locked(self, blob: bytes,
                            rel: list[int]) -> list[tuple[int, int]]:
        with self._lock:
            if self._handle is not None:
                seg = ctypes.c_int()
                off = ctypes.c_long()
                rc = self._lib.segstore_append_blob(
                    self._handle, blob, len(blob),
                    ctypes.byref(seg), ctypes.byref(off),
                )
                if rc != 0:
                    raise OSError("segstore_append_blob failed")
                self._landed_in_locked(seg.value)
                return [(seg.value, off.value + r) for r in rel]
            if (
                self._file.tell() + len(blob) > self.segment_bytes
                and self._file.tell() > 0
            ):
                self._file.close()
                self._seg_index += 1
                self._file = open(self._seg_path(self._seg_index), "ab")
            start = self._file.tell()
            self._file.write(blob)
            self._file.flush()
            self._landed_in_locked(self._seg_index)
            return [(self._seg_index, start + r) for r in rel]

    def _landed_in_locked(self, seg: int) -> None:
        """An append landed in segment `seg` (caller holds _lock): the
        segment the flusher syncs, and — once this open has written one —
        every index passed since the last append is a segment sealed."""
        if self._c_sealed is not None and 0 <= self._active_seg < seg:
            self._c_sealed.inc(seg - self._active_seg)
        self._active_seg = seg

    def flush(self) -> None:
        """fsync the active segment (the durability barrier)."""
        t0 = self._clock() if self._h_fsync is not None else 0.0
        with self._lock:
            if self._handle is not None:
                if self._lib.segstore_flush(self._handle) != 0:
                    raise OSError("segstore_flush failed")
            elif self._file is not None:
                self._file.flush()
                os.fsync(self._file.fileno())
            else:
                return  # closed: close()'s final fsync was the barrier
        if self._h_fsync is not None:
            self._h_fsync.observe(self._clock() - t0)
        if self.erasure:
            self._kick_erasure()

    def flush_async(self) -> None:
        """Schedule an fsync on the store's flusher thread and return
        immediately. Same durability contract as the callers' periodic
        flush() cadence — disk lags the buffered append stream by at
        most one flush interval (plus one in-flight fsync) — but the
        HOT PATH no longer waits out the device's fsync latency, which
        on a networked filesystem is tens to hundreds of ms per call
        (measured p50 47 ms / p99 163 ms on a 9p mount: inline, that
        single syscall WAS the settle pipeline's and the standby ack
        path's capacity). Barrier call sites — boot replay, promotion,
        stop — keep calling flush() directly."""
        if self._flush_stop.is_set():
            return
        if self._flusher is None:
            with self._lock:
                if self._flusher is None and not self._flush_stop.is_set():
                    self._flusher = threading.Thread(
                        target=self._flush_loop, daemon=True,
                        name="segstore-flush",
                    )
                    self._flusher.start()
        self._flush_event.set()

    def _flush_loop(self) -> None:
        while not self._flush_stop.is_set():
            if not self._flush_event.wait(timeout=0.2):
                continue
            self._flush_event.clear()
            try:
                self._sync_active_segment()
                if self.erasure:
                    self._kick_erasure()
            except Exception as e:  # surfaced via stats, not a dead thread
                self.flush_errors.append(f"{type(e).__name__}: {e}")
                del self.flush_errors[:-20]

    def _sync_active_segment(self) -> None:
        """fsync the active segment through an INDEPENDENT fd: fsync
        syncs the inode, not the fd, so the flusher never holds the
        store lock across the device sync — appends keep flowing while
        the filesystem catches up (holding the lock instead re-created
        the inline stall on a different thread: appenders queue on the
        lock for the fsync's full latency). If the store rotated between
        the name lookup and the sync, the sealed segment gets (a useful)
        sync and the fresh active one is covered by the next tick —
        within the same one-interval durability lag. The user-space
        buffer is already drained: the python writer flush()es per
        append, the native writer write()s unbuffered. Rotation between
        two ticks must not orphan the SEALED segment's unsynced tail —
        every index from the last synced segment up to the active one
        is covered, so the one-interval lag holds across rotations."""
        seg = self._active_seg
        if seg < 0:
            return  # nothing appended yet
        t0 = self._clock() if self._h_fsync is not None else 0.0
        first = self._last_synced_seg if self._last_synced_seg >= 0 else seg
        for idx in range(first, seg + 1):
            try:
                fd = os.open(self._seg_path(idx), os.O_RDONLY)
            except OSError:
                continue  # GC'd away: nothing left to sync
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        self._last_synced_seg = seg
        if self._h_fsync is not None:
            self._h_fsync.observe(self._clock() - t0)

    def _kick_erasure(self) -> None:
        """Start (or skip, if one is running) the background shard
        encoder, and observe `seal.pending`; rate-limited to once a
        second so rotation-free flushes don't pay even a listdir.
        Check-and-start runs under the store lock: the kick is
        reachable from the settle path's flush, barrier flushes, and
        the flusher thread, and the unguarded alive-check let two
        concurrent kicks both start a worker (ownership lint, PR 11;
        harmless output, doubled encode I/O). Callers never hold _lock
        here — flush() releases it before kicking."""
        import time

        now = time.monotonic()
        with self._lock:
            if now - self._erasure_check_t < 1.0:
                return
            self._erasure_check_t = now
            t = self._erasure_thread
            if t is None or not t.is_alive():
                t = threading.Thread(
                    target=self._erasure_worker, daemon=True,
                    name="segstore-erasure",
                )
                self._erasure_thread = t
                t.start()
        if self._h_seal_pending is not None:
            # Two directory listings, at most once a second, outside
            # the lock: what the worker (just started, or still busy
            # with an earlier seal) has in front of it.
            from ripplemq_tpu.storage.erasure import unprotected_names

            try:
                self._h_seal_pending.observe_int(
                    len(unprotected_names(self.directory)))
            except OSError:
                pass  # a directory went mid-listing: telemetry only

    def _erasure_worker(self) -> None:
        from ripplemq_tpu.storage.erasure import protect_store, store_bucket

        try:
            # Every seal runs the program _erasure_warm built, however
            # short of segment_bytes the segment rotated.
            protect_store(self.directory, stage=self._seal_stage
                          if self._st_rs_encode is not None else None,
                          bucket_floor=store_bucket(self.segment_bytes))
        except Exception as e:  # derived data: never take the store down
            self._erasure_failed("encode", e)

    def _erasure_warm(self) -> None:
        """The erasure thread's first job, started at open: build the RS
        program this store's seals will run, on this process's backend,
        before there is traffic to stall behind a compile (on the
        chip-owning broker ~2.5 s under the GIL every serving thread
        shares). It takes no lock of the store or the dataplane; a kick
        that finds it running is skipped like any other (the next flush
        kicks again), so a seal never compiles beside it."""
        from ripplemq_tpu.storage.erasure import warm_encode

        try:
            self._count_rs_bucket(warm_encode(self.segment_bytes))
        except Exception as e:  # a seal will compile it, as before
            self._erasure_failed("warm-up", e)

    def _erasure_failed(self, what: str, e: Exception) -> None:
        _log.warning("erasure %s failed for %s: %s: %s",
                     what, self.directory, type(e).__name__, e)
        # append + del-slice trim must not interleave with another
        # writer (ownership lint, PR 11): error path, lock is free.
        with self._lock:
            self.erasure_errors.append(f"{type(e).__name__}: {e}")
            del self.erasure_errors[:-20]

    def _count_rs_bucket(self, shard_len: int) -> None:
        from ripplemq_tpu.ops.rs import shard_bucket

        bucket = shard_bucket(shard_len)
        if bucket not in self._rs_shapes:
            self._rs_shapes.add(bucket)
            if self._c_rs_new_shapes is not None:
                self._c_rs_new_shapes.inc()

    def _seal_stage(self, shard_len: int):
        """The timed region storage/erasure.py opens around one sealed
        segment's RS encode: `seal.rs_encode`, counting a shard length
        BUCKET not met before under rs.new_shapes."""
        self._count_rs_bucket(shard_len)
        return self._st_rs_encode.timed()

    def gc(self) -> list[int]:
        """Delete the oldest sealed segments while their total size
        exceeds retention_bytes; returns the deleted segment INDICES.
        Records in deleted segments are gone — consumers below the new
        floor jump forward to the earliest retained record (the
        documented earliest-reset semantics); callers must prune any
        (segment, offset) indexes they hold (DataPlane.drop_index_segments).
        The persisted gc floor (`gc_floor` file) distinguishes deliberate
        head-of-store deletion from disk loss, so boot-time peer-shard
        refill is not triggered by GC gaps."""
        if self.retention_bytes is None:
            return []
        with self._lock:
            sealed = list_segment_files(self.directory)[:-1]
            sizes = {
                n: os.path.getsize(os.path.join(self.directory, n))
                for n in sealed
            }
            total = sum(sizes.values())
            deleted: list[int] = []
            for n in sealed:
                if total <= self.retention_bytes:
                    break
                idx = int(n[8:16])
                os.remove(os.path.join(self.directory, n))
                rs_dir = os.path.join(self.directory, "rs")
                if os.path.isdir(rs_dir):
                    for f in os.listdir(rs_dir):
                        if f.startswith(n + ".shard"):
                            try:
                                os.remove(os.path.join(rs_dir, f))
                            except OSError:
                                pass
                total -= sizes[n]
                deleted.append(idx)
            if deleted:
                floor = max(deleted) + 1
                tmp = os.path.join(self.directory, "gc_floor.tmp")
                with open(tmp, "w") as f:
                    f.write(str(floor))
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, os.path.join(self.directory, "gc_floor"))
            return deleted

    def protect_async(self) -> None:
        """Kick the background sealed-segment encoder. Duty loops call
        this periodically: flush() also kicks it, but flushes stop with
        write traffic, and a burst's final sealed segments must not stay
        unprotected until the next burst."""
        if self.erasure:
            self._kick_erasure()

    def wait_erasure(self, timeout: Optional[float] = None) -> None:
        """Join an in-flight background encode (tests / orderly shutdown)."""
        t = self._erasure_thread
        if t is not None and t.is_alive():
            t.join(timeout)

    def scan(self) -> Iterator[tuple[int, int, int, bytes]]:
        """Records in write order (see scan_store). Safe to call while the
        store is open for append: records written after the scan reaches
        the tail may be missed (a concurrently-written tail record reads
        as torn and ends the scan), never misread — callers that need a
        consistent prefix must order themselves against append (see
        broker/replication.py catch-up protocol)."""
        return scan_store(self.directory)

    def scan_indexed(self) -> Iterator[tuple[int, int, int, bytes, tuple[int, int]]]:
        """Like scan(), plus each record's locator (boot-time index build
        for the retention read path). Uses the native scanner's position-
        reporting walk when this store runs natively (the boot scan of a
        multi-GB store is C-speed, not Python framing); a store built
        with use_native=False keeps its opt-out here too."""
        return scan_store_indexed(
            self.directory,
            use_native=None if self._lib is not None else False,
        )

    def read_payload(self, locator: tuple[int, int], byte_start: int,
                     nbytes: int) -> bytes:
        """Read `nbytes` of a record's payload starting `byte_start` bytes
        in, by seek — no framing walk. The caller (storage.logindex) got
        `locator` from append()/scan_indexed() and knows the payload
        length; a short read means the store was truncated under us and
        raises."""
        seg_idx, off = locator
        with open(self._seg_path(seg_idx), "rb") as f:
            f.seek(off + byte_start)
            data = f.read(nbytes)
        if len(data) != nbytes:
            raise OSError(
                f"short payload read in segment {seg_idx} at {off}+{byte_start}"
            )
        return data

    def close(self) -> None:
        # Stop the async flusher first: close's own fsync below is the
        # final barrier, and a flusher fsyncing a closed file would race.
        self._flush_stop.set()
        self._flush_event.set()
        t = self._flusher
        if t is not None and t.ident is not None:
            t.join(timeout=10)
        with self._lock:
            if self._handle is not None:
                self._lib.segstore_close(self._handle)
                self._handle = None
            elif self._file is not None:
                self._file.flush()
                os.fsync(self._file.fileno())
                self._file.close()
                self._file = None
        if self.erasure:
            # Orderly shutdown: finish protection synchronously (the
            # background worker may be mid-encode or rate-limited out).
            # If the worker is STILL alive after the join timeout, skip
            # the synchronous run — two unsynchronized encoders would
            # race on the same shard .tmp paths; the straggler finishes
            # the job (or the next boot's repair pass does).
            self.wait_erasure(timeout=30)
            t = self._erasure_thread
            if t is None or not t.is_alive():
                self._erasure_worker()


def verify_store(directory: str, repair_torn_tail: bool = False) -> int:
    """Full CRC framing walk of a store directory; returns the record
    count. Raises CorruptStoreError on any damage the torn-tail crash
    contract does not cover:

    - a corrupt record in a non-final segment (what the scanners refuse
      at replay time), and
    - a corrupt record in the FINAL segment that is FOLLOWED by valid
      frames. The plain scanners cannot tell bit rot mid-file from a
      torn tail — they stop and silently drop every acked record after
      the damage; the look-ahead here upgrades that to quarantine-grade
      corruption so recovery re-replicates instead of serving a
      silently shortened history.

    `repair_torn_tail=True` additionally TRUNCATES a tolerated torn
    tail off the final segment (fsync'd). Both writers open a NEW
    segment after the highest existing index, so an un-truncated torn
    tail becomes the tail of a SEALED segment the moment the store
    reopens — and every later scan refuses it as mid-store corruption
    (the chaos proc drills hit exactly this: a phase-0 torn tail read
    clean at that boot, then crash-looped the broker's next promotion).
    The boot health gate must therefore repair what it tolerates.

    This is the boot-time health gate behind quarantine: a broker must
    know its store is fully servable BEFORE claiming any role that
    serves from it, instead of crash-looping at its next promotion
    (chaos disk-fault drills, ISSUE 4). Python framing by design — the
    walk must analyze the damage, not just refuse at it."""
    n = 0
    files = list_segment_files(directory)
    for fi, name in enumerate(files):
        last_file = fi + 1 == len(files)
        with open(os.path.join(directory, name), "rb") as f:
            blob = f.read()
        pos = 0
        bad_at = None
        while True:
            if pos == len(blob):
                break
            if pos + _HEADER.size > len(blob):
                bad_at = pos  # trailing partial header
                break
            magic, _t, _s, _b, length, crc = _HEADER.unpack(
                blob[pos : pos + _HEADER.size]
            )
            if magic != _MAGIC or length > (1 << 30):
                bad_at = pos
                break
            payload = blob[pos + _HEADER.size : pos + _HEADER.size + length]
            if (len(payload) < length
                    or _frame_crc(
                        blob[pos : pos + _HEADER_PREFIX.size], payload
                    ) != crc):
                bad_at = pos
                break
            pos += _HEADER.size + length
            n += 1
        if bad_at is None:
            continue
        if not last_file:
            raise CorruptStoreError(
                f"corrupt record in sealed segment {name}"
            )
        if _valid_frame_after(blob, bad_at + 1):
            raise CorruptStoreError(
                f"corrupt record mid-{name}: valid records follow the "
                f"damage at byte {bad_at} — bit rot, not a torn tail"
            )
        # True torn tail: tolerated (replay drops it).
        if repair_torn_tail:
            path = os.path.join(directory, name)
            with open(path, "r+b") as f:
                f.truncate(bad_at)
                f.flush()
                os.fsync(f.fileno())
            _log.info("truncated torn tail of %s at byte %d", name, bad_at)
    return n


def _valid_frame_after(blob: bytes, start: int) -> bool:
    """Whether any CRC-valid record frame begins at-or-after `start` —
    the discriminator between a torn tail (nothing follows) and mid-file
    corruption (acked records follow the damage)."""
    magic = struct.pack("<I", _MAGIC)
    pos = blob.find(magic, start)
    while pos != -1:
        if pos + _HEADER.size <= len(blob):
            _m, _t, _s, _b, length, crc = _HEADER.unpack(
                blob[pos : pos + _HEADER.size]
            )
            if (length <= (1 << 30)
                    and pos + _HEADER.size + length <= len(blob)):
                payload = blob[pos + _HEADER.size : pos + _HEADER.size + length]
                if _frame_crc(
                    blob[pos : pos + _HEADER_PREFIX.size], payload
                ) == crc:
                    return True
        pos = blob.find(magic, pos + 1)
    return False


def quarantine_store(directory: str) -> str:
    """Move a damaged store directory aside (`<dir>.quarantine-N`,
    lowest unused N) and return the new path. The caller reopens a
    fresh, empty store at `directory` and re-replicates through the
    standby catch-up protocol; the damaged bytes are preserved for
    forensics rather than deleted."""
    n = 0
    while True:
        target = f"{directory}.quarantine-{n}"
        if not os.path.exists(target):
            break
        n += 1
    os.replace(directory, target)
    return target


def gc_floor(directory: str) -> int:
    """Lowest segment index deliberately retained after GC (0 if the
    store was never GC'd). Segments below this were DELETED on purpose,
    not lost — disaster tooling (erasure.segment_index_gaps, peer-shard
    refill) must not try to resurrect them."""
    try:
        with open(os.path.join(directory, "gc_floor")) as f:
            return int(f.read().strip() or 0)
    except (OSError, ValueError):
        return 0


def scan_store(
    directory: str, use_native: Optional[bool] = None
) -> Iterator[tuple[int, int, int, bytes]]:
    """Yield (type, slot, base, payload) records in write order. A torn
    tail record is silently dropped (crash contract); corruption anywhere
    else raises CorruptStoreError."""
    for rec_type, slot, base, payload, _loc in scan_store_indexed(
        directory, use_native
    ):
        yield rec_type, slot, base, payload


def scan_store_indexed(
    directory: str, use_native: Optional[bool] = None
) -> Iterator[tuple[int, int, int, bytes, tuple[int, int]]]:
    """Yield (type, slot, base, payload, (segment_index, payload_offset))
    in write order — scan_store plus each record's locator. Same torn-
    tail/corruption contract."""
    if not os.path.isdir(directory):
        return
    lib = _load_native() if use_native in (None, True) else None
    if use_native is True and lib is None:
        raise RuntimeError("native segstore requested but unavailable")
    if lib is not None:
        yield from _scan_native_indexed(lib, directory)
    else:
        for seg_idx, off, rec in _scan_python_indexed(directory):
            rec_type, slot, base, payload = rec
            yield rec_type, slot, base, payload, (seg_idx, off)


def _scan_native_indexed(lib, directory: str):
    handle = lib.segscan_open(directory.encode())
    if not handle:
        return
    t = ctypes.c_int()
    slot = ctypes.c_int()
    base = ctypes.c_int()
    need = ctypes.c_int()
    seg = ctypes.c_int()
    off = ctypes.c_long()
    buflen = 1 << 20
    buf = ctypes.create_string_buffer(buflen)
    try:
        while True:
            rc = lib.segscan_next_at(
                handle, ctypes.byref(t), ctypes.byref(slot),
                ctypes.byref(base), buf, buflen, ctypes.byref(need),
                ctypes.byref(seg), ctypes.byref(off),
            )
            if rc == -3:  # grow the buffer and retry
                buflen = max(buflen * 2, need.value)
                buf = ctypes.create_string_buffer(buflen)
                continue
            if rc == -1:
                return
            if rc == -2:
                raise CorruptStoreError(f"corrupt record in {directory}")
            # string_at copies exactly rc bytes (buf.raw would first
            # materialize the whole — possibly grown — buffer per record).
            yield (t.value, slot.value, base.value,
                   ctypes.string_at(buf, rc), (seg.value, off.value))
    finally:
        lib.segscan_close(handle)




def _scan_python_indexed(directory: str):
    """Python framing walk yielding (segment_index, payload_offset,
    (type, slot, base, payload)) — same torn-tail/corruption contract as
    scan_store."""
    files = list_segment_files(directory)
    for fi, name in enumerate(files):
        last_file = fi + 1 == len(files)
        seg_idx = int(name[8:16])
        with open(os.path.join(directory, name), "rb") as f:
            while True:
                hdr = f.read(_HEADER.size)
                if not hdr:
                    break
                if len(hdr) < _HEADER.size:
                    if last_file:
                        return  # torn tail
                    raise CorruptStoreError(f"short header in {name}")
                magic, rec_type, slot, base, length, crc = _HEADER.unpack(hdr)
                if magic != _MAGIC:
                    if last_file:
                        return
                    raise CorruptStoreError(f"bad magic in {name}")
                if length > (1 << 30):
                    # Corrupt length field: reject BEFORE allocating a
                    # read of that size (mirrors the native scanner).
                    if last_file:
                        return
                    raise CorruptStoreError(f"absurd record length in {name}")
                payload_off = f.tell()
                payload = f.read(length)
                if (len(payload) < length
                        or _frame_crc(hdr[:_HEADER_PREFIX.size], payload)
                        != crc):
                    if last_file:
                        return  # torn/corrupt tail record
                    raise CorruptStoreError(f"bad record in {name}")
                yield seg_idx, payload_off, (rec_type, slot, base, payload)

"""Rows nine times as wide (PR 33): 1,024 B records in 1,152 B slots.

`slot_bytes` 1152 is the smallest multiple of the 128-byte lane width
that holds a 1 KB record and its 8 B row head - the `omb-100p-1kb`
deployment's row, and the first width in this tree that is not a power
of two. Every case here compares the program with a plain per-partition
list, byte for byte: the served path (client -> round -> standby stream
-> store -> consume, every replica's data dir scanned), the admission
limit, the kernel against the scatter over every extent class, and a
sealed segment of such rows through the RS code. The ring wrap and the
randomized kernel parity at this width are cases of the tests that
already ran them at 128 (tests/test_read_cache.py,
tests/test_append_kernel.py).
"""

import itertools
import os

import numpy as np
import pytest

from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
from ripplemq_tpu.client import ConsumerClient, ProducerClient
from ripplemq_tpu.core.config import ALIGN, ROW_HEADER, EngineConfig
from ripplemq_tpu.core.encode import pack_payload_rows
from ripplemq_tpu.metadata.models import Topic
from ripplemq_tpu.ops.append import (
    SCOPED_VMEM_BYTES,
    _append_active_pallas,
    _extent_classes,
    active_bucket,
    active_buckets,
    append_rows_active_xla,
    check_entries_block,
)
from ripplemq_tpu.storage import erasure
from ripplemq_tpu.storage.segment import REC_APPEND, SegmentStore, scan_store
from tests.helpers import wait_until

SB = 1152
SIZE = 1024
PARTS = 4


def records(rng, part: int, n: int, size: int = SIZE) -> list[bytes]:
    """Seeded random records; byte 0 names the partition, so a record
    that lands in another partition's log shows."""
    block = rng.integers(0, 256, (n, size), dtype=np.uint8)
    block[:, 0] = part
    return [r.tobytes() for r in block]


def rows_to_messages(body: bytes, sb: int = SB) -> list[bytes]:
    """A stored REC_APPEND body ([n, sb] rows) -> its messages; rows of
    length 0 are alignment padding."""
    block = np.frombuffer(body, np.uint8).reshape(-1, sb)
    lens = block[:, :4].copy().view("<i4")[:, 0]
    return [block[i, ROW_HEADER:ROW_HEADER + n].tobytes()
            for i, n in enumerate(lens) if n > 0]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three brokers, one topic x 4 partitions RF 3, two standbys, a
    data dir each, segments small enough to seal under the test."""
    root = tmp_path_factory.mktemp("wide")
    engine = EngineConfig(partitions=PARTS, replicas=3, slots=128,
                          slot_bytes=SB, max_batch=32, read_batch=32,
                          max_consumers=8, max_offset_updates=4)
    config = make_cluster_config(
        n_brokers=3, topics=(Topic("kb", PARTS, 3),), engine=engine,
        standby_count=2, segment_bytes=96 * 1024)
    cluster = InProcCluster(config, data_dir=str(root))
    cluster.start()
    cluster.wait_for_leaders()
    yield cluster, str(root)
    cluster.stop()


def test_served_path_byte_exact_on_every_replica(served):
    """Produce through the client, consume back, scan all three data
    dirs: each equals the plain per-partition list."""
    cluster, root = served
    boot = [b.address for b in cluster.config.brokers]
    rng = np.random.default_rng(33)
    want: dict[int, list[bytes]] = {p: [] for p in range(PARTS)}
    prod = ProducerClient(boot, transport=cluster.client("wp"))
    for _ in range(5):
        for p in range(PARTS):
            batch = records(rng, p, 16)
            prod.produce_batch("kb", batch, partition=p)
            want[p].extend(batch)
    prod.close()

    cons = ConsumerClient(boot, "wide-sub", transport=cluster.client("wc"),
                          max_messages=32)
    got: dict[int, list[bytes]] = {p: [] for p in range(PARTS)}
    assert wait_until(lambda: all(
        got[p].extend(cons.consume("kb", partition=p)) or
        len(got[p]) >= len(want[p]) for p in range(PARTS)), timeout=60)
    cons.close()
    assert got == want

    ctrl = cluster.brokers[cluster.controller_id()]
    assert ctrl.dataplane.step_errors == 0
    # The per-byte instruments this PR adds, on the controller.
    snap = ctrl.dispatch({"type": "admin.metrics"})["metrics"]
    assert snap["counters"]["read.bytes"] >= 80 * PARTS * SIZE
    assert snap["counters"]["read.calls"] > 0
    assert snap["histograms"]["round.stage_us"]["count"] > 0
    assert snap["counters"]["seal.segments"] >= 3  # ~370 KB into 96 KiB
    assert wait_until(lambda: ctrl.dispatch({"type": "admin.metrics"})[
        "metrics"]["histograms"].get("seal.shard_put_us", {}).get(
            "count", 0) > 0, timeout=20)
    hist = ctrl.dispatch({"type": "admin.metrics"})["metrics"]["histograms"]
    assert hist["seal.pending"]["count"] > 0

    cluster.stop()
    for b in range(3):
        per_slot: dict[int, list[bytes]] = {}
        for rec_type, slot, base, body in sorted(
                scan_store(os.path.join(root, f"broker-{b}", "segments")),
                key=lambda r: (r[1], r[2])):
            if rec_type == REC_APPEND:
                per_slot.setdefault(slot, []).extend(rows_to_messages(body))
        on_disk = {msgs[0][0]: msgs for msgs in per_slot.values() if msgs}
        assert on_disk == want, f"broker {b}'s data dir differs"


@pytest.fixture(scope="module")
def limit_cluster():
    engine = EngineConfig(partitions=1, replicas=3, slots=64, slot_bytes=SB,
                          max_batch=8, read_batch=8, max_consumers=8,
                          max_offset_updates=4)
    config = make_cluster_config(n_brokers=3, topics=(Topic("kb", 1, 3),),
                                 engine=engine)
    with InProcCluster(config) as cluster:
        cluster.wait_for_leaders()
        yield cluster


@pytest.mark.parametrize("size,ok", [(SB - ROW_HEADER, True),
                                     (SB - ROW_HEADER + 1, False)])
def test_admission_limit_is_slot_bytes_less_the_row_head(limit_cluster,
                                                         size, ok):
    """1,144 B is the largest record a 1,152 B slot takes; 1,145 B is
    refused at admission, with the numbers, and nothing is stored."""
    cluster = limit_cluster
    boot = [b.address for b in cluster.config.brokers]
    prod = ProducerClient(boot, transport=cluster.client(f"lp{size}"))
    msg = bytes([7]) * size
    try:
        if ok:
            prod.produce("kb", msg, partition=0)
            cons = ConsumerClient(boot, f"lim{size}",
                                  transport=cluster.client(f"lc{size}"))
            got: list[bytes] = []
            assert wait_until(lambda: got.extend(
                cons.consume("kb", partition=0)) or msg in got, timeout=30)
            cons.close()
        else:
            with pytest.raises(Exception, match=(
                    f"payload of {size} bytes exceeds payload_bytes "
                    f"{SB - ROW_HEADER}")):
                prod.produce("kb", msg, partition=0)
    finally:
        prod.close()


def test_kernel_equals_scatter_over_every_extent_class_at_1152():
    """The Pallas append in interpret mode against the XLA scatter at
    SB 1152, in the active-set form the served path runs: one grid block
    of eight consecutive partitions in lockstep (the one-DMA fast path),
    one ragged block with every extent class, padding ids, a partition
    nobody writes and a replica that does not write."""
    rng = np.random.default_rng(1152)
    R, P, S, B = 2, 20, 128, 64
    assert _extent_classes(B // ALIGN) == [1, 2, 4, 8]
    log = rng.integers(0, 256, (R, P, S, SB), dtype=np.uint8)
    ids = np.array([*range(8), 17, 9, -1, 19, 12, -1, 14, 10], np.int32)
    entries = rng.integers(0, 256, (len(ids), B, SB), dtype=np.uint8)
    base = (rng.integers(0, (S - B) // ALIGN + 1, P) * ALIGN).astype(np.int32)
    extents = rng.integers(1, B + 1, P).astype(np.int32)
    base[:8], extents[:8] = 3 * ALIGN, 2 * ALIGN          # block 0: uniform
    extents[[17, 9, 19, 12, 14, 10]] = [8, 16, 32, 64, 1, 33]  # every class
    do_write = np.ones((R, P), bool)
    do_write[:, 14] = False
    do_write[1, 9] = False
    want = np.asarray(append_rows_active_xla(log, entries, ids, base,
                                             do_write, extents))
    got = np.asarray(_append_active_pallas(
        log, entries, ids, base, do_write, extents=extents, interpret=True))
    np.testing.assert_array_equal(got, want)
    # ... it wrote (partition 17's first 8 rows are entry 8's), and it
    # clipped: rows past a window's class keep what they held.
    b = int(base[17])
    np.testing.assert_array_equal(got[0, 17, b:b + 8], entries[8, :8])
    np.testing.assert_array_equal(got[0, 17, b + 8:b + B],
                                  log[0, 17, b + 8:b + B])
    np.testing.assert_array_equal(got[:, 14], log[:, 14])


def test_sealed_segment_of_1kb_rows_rebuilds_from_any_three_shards(tmp_path):
    """A segment of 1 KB rows, sealed, RS(3,2)-encoded: every choice of
    three of its five shards gives the segment back, and its rows decode
    to the records that went in."""
    cfg = EngineConfig(partitions=1, replicas=3, slots=64, slot_bytes=SB,
                       max_batch=16, read_batch=16)
    rng = np.random.default_rng(5)
    store = SegmentStore(str(tmp_path / "s"), segment_bytes=64 * 1024,
                         use_native=False)
    want: list[bytes] = []
    for i in range(8):  # 8 x 16 x 1,152 B: two sealed segments and a tail
        batch = records(rng, 0, 16)
        want.extend(batch)
        store.append(REC_APPEND, 0, i * 16,
                     pack_payload_rows(cfg, batch).tobytes())
    store.close()
    d = store.directory
    sealed = erasure.unprotected_names(d)
    assert len(sealed) >= 2
    assert erasure.protect_store(d) == sealed
    assert erasure.unprotected_names(d) == []
    name = sealed[0]
    with open(os.path.join(d, name), "rb") as f:
        seg_bytes = f.read()
    paths = erasure.shard_paths(d, name)
    for lost in itertools.combinations(range(5), 2):
        saved = {i: open(paths[i], "rb").read() for i in lost}
        for i in lost:
            os.remove(paths[i])
        assert erasure.reconstruct_segment(d, name) == seg_bytes, lost
        for i, blob in saved.items():
            with open(paths[i], "wb") as f:
                f.write(blob)
    os.remove(os.path.join(d, name))
    assert erasure.repair_store(d) == [name]
    got = [m for _, _, _, body in scan_store(d, use_native=False)
           for m in rows_to_messages(body)]
    assert got == want


def test_seals_and_pending_are_counted(tmp_path):
    """`seal.segments` counts segments an append left behind;
    `seal.pending` is observed at an erasure kick and reads what is
    sealed and not yet protected."""
    from ripplemq_tpu.obs.metrics import Metrics

    m = Metrics()
    store = SegmentStore(str(tmp_path / "c"), segment_bytes=4096,
                         use_native=False, erasure=True, metrics=m)
    store.wait_erasure()  # the warm-up encode started at open
    for i in range(4):  # every append after the first rotates
        store.append(REC_APPEND, 0, i * 8, bytes(3000))
    assert m.snapshot()["counters"]["seal.segments"] == 3
    assert len(erasure.unprotected_names(store.directory)) == 3
    store._erasure_check_t = 0.0
    store.flush()  # kicks: observes 3 pending, starts the worker
    store.wait_erasure()
    assert m.snapshot()["histograms"]["seal.pending"]["max"] == 3
    store._erasure_check_t = 0.0
    store.flush()
    store.close()
    snap = m.snapshot()["histograms"]["seal.pending"]
    assert snap["count"] == 2 and erasure.unprotected_names(
        store.directory) == []


V5E = "TPU v5 lite"


def test_engine_build_prices_the_kernels_vmem_block():
    """`max_batch` x `slot_bytes` over the scoped VMEM the kernel gets
    on the device's kind is an error at engine build, with the numbers:
    two blocks where the bucket spans more than one grid step, one
    where it does not; a kind the table lacks is left to its compiler.
    The deployment's shape and the 128 B deployments' pass."""
    assert 2 * 8 * 512 * SB == 9_437_184 <= SCOPED_VMEM_BYTES[V5E]
    assert active_buckets(104) == (8, 32, 104)
    assert active_buckets(1024) == (8, 32, 128, 512, 1024)
    assert active_buckets(8) == (8,) and active_buckets(3) == (3,)
    assert [active_bucket(n, 104) for n in (1, 8, 9, 32, 33, 104)] == [
        8, 8, 32, 32, 104, 104]
    check_entries_block(SB, 512, 104, V5E)
    check_entries_block(128, 4096, 1024, V5E)
    with pytest.raises(ValueError, match=(
            r"at the 32-partition bucket .* 2 x 8 x 1024 x 1152 = 18874368 B, "
            r"over the 16777216 B.*at most 904")):
        check_entries_block(SB, 1024, 104, V5E)
    check_entries_block(SB, 904, 104, V5E)
    with pytest.raises(ValueError, match="at most 904"):
        check_entries_block(SB, 912, 104, V5E)
    # one grid step (partitions <= 8): one block, so twice the rows fit
    check_entries_block(SB, 1024, 8, V5E)
    check_entries_block(SB, 1816, 8, V5E)
    with pytest.raises(ValueError, match=r"1 x 8 x 1824 x 1152.*at most 1816"):
        check_entries_block(SB, 1824, 8, V5E)
    # 13 partitions: bucket 8 keeps 8 blocks, bucket 13 runs 1 a step
    check_entries_block(SB, 1816, 13, V5E)
    # twice the scoped VMEM on v6e; an unknown kind is not priced here
    check_entries_block(SB, 1024, 104, "TPU v6 lite")
    with pytest.raises(ValueError, match="33554432 B"):
        check_entries_block(SB, 2048, 104, "TPU v6 lite")
    check_entries_block(SB, 1 << 20, 104, "TPU v9 imagined")


# -------------------------------------------- the chip's compiler, no chip
#
# The TPU compiler is installed here and compiles for a described v5e
# (on-chip-measurement guide, section 2): what Mosaic refuses at this
# width it refuses here, at no chip time. The topology is described
# inside a fixture, never at import (one process at a time may load the
# TPU library; xdist workers all import this file). Where it cannot be
# described the cases are skipped AND a warning says so in the run's
# summary, so the agreement is never passed by silence.

@pytest.fixture(scope="module")
def one_chip():
    import warnings

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = jax.config.jax_enable_compilation_cache
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            why = f"no v5e:2x2 topology can be described here: {e}"
            warnings.warn(f"tests/test_wide_rows.py: the v5e compiler did "
                          f"NOT check the VMEM rule - {why}")
            pytest.skip(why)
        assert topo.devices[0].device_kind == V5E
        jax.config.update("jax_enable_compilation_cache", False)
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _compile_append(one_chip, P, S, A, B):
    import jax
    import jax.numpy as jnp

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = jax.jit(lambda log, e, ids, base, dw, ext: _append_active_pallas(
        log, e, ids, base, dw, extents=ext), donate_argnums=(0,))
    return fn.lower(sds((3, P, S, SB), jnp.uint8), sds((A, B, SB), jnp.uint8),
                    sds((A,), jnp.int32), sds((P,), jnp.int32),
                    sds((3, P), jnp.bool_), sds((P,), jnp.int32)).compile()


_edge = pytest.mark.slow  # ~30 s of compiler for the three: by hand, -m slow


@pytest.mark.parametrize("partitions,bucket,batch,fits", [
    (104, 104, 512, True),   # omb-100p-1kb's widest bucket: tier-1
    pytest.param(104, 32, 904, True, marks=_edge),   # the edge the error
    pytest.param(104, 32, 912, False, marks=_edge),  # message names: two
                             # blocks of 8 x 912 x 1152 are 32 KB over
    pytest.param(8, 8, 1816, True, marks=_edge),     # one grid step keeps
                             # ONE block (1824 is the same 32 KB over:
                             # compiled by hand, PR 33)
])
def test_v5e_compiler_agrees_with_the_vmem_rule(one_chip, partitions, bucket,
                                                batch, fits):
    assert bucket in active_buckets(partitions)
    if fits:
        check_entries_block(SB, batch, partitions, V5E)
        _compile_append(one_chip, partitions, 2048 + batch, bucket, batch)
    else:
        with pytest.raises(ValueError):
            check_entries_block(SB, batch, partitions, V5E)
        with pytest.raises(Exception, match="vmem"):
            _compile_append(one_chip, partitions, 2048 + batch, bucket, batch)

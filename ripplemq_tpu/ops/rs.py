"""Reed–Solomon GF(2⁸) erasure coding as a TPU matmul (Pallas kernel).

The reference tolerates broker loss only by full replication — RF copies
of every byte (reference: mq-broker/src/main/java/metadata/
PartitionAssigner.java:81-89; JRaft replicates whole log entries). For
sealed, immutable log segments that is 5× storage for 2-loss tolerance.
RS(k=3, m=2) gets the same 2-loss tolerance at 5/3× — SURVEY.md §7 step 6
calls this "the one genuinely kernel-level component" (the reference has
no counterpart; BASELINE.json config #4).

Encoding IS a matmul over GF(2⁸): parity[m, n] = G[m, k] ·_gf data[k, n],
and reconstruction is the same product with rows of the inverted
generator. The TPU-native formulation exploits GF(2) linearity instead of
byte-table gathers (TPU gathers serialize): multiplying byte x by a
constant c is XOR over x's set bits of c·2^b, so one GF matmul-by-
constant-matrix is 8·K broadcast-select-XORs on the VPU, fully
vectorized, no lookup tables on device. The Pallas kernel streams
[TR, 128] blocks of each shard through VMEM; the XLA fallback shares the
identical bit-linear math (equivalence asserted in tests against a
numpy log/exp-table reference).

Field: GF(2⁸) with the 0x11D polynomial (the usual RS/ISA-L field).
Generator: extended-Cauchy [I_k; C], C[i,j] = (x_i ⊕ y_j)⁻¹ — every k×k
submatrix of an extended Cauchy matrix is invertible, so ANY k of the
k+m shards reconstruct the data (MDS property).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1

# --------------------------------------------------------------------------
# Host-side field arithmetic (table-based; used for matrices + reference)
# --------------------------------------------------------------------------


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _build_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[(_LOG[a] + _LOG[b]) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("gf_inv(0)")
    return int(_EXP[255 - _LOG[a]])


def gf_matmul_ref(coeffs, shards: np.ndarray) -> np.ndarray:
    """Numpy reference: [M, K] constant matrix ·_gf [K, N] uint8 shards."""
    shards = np.asarray(shards, np.uint8)
    out = np.zeros((len(coeffs), shards.shape[1]), np.uint8)
    for i, row in enumerate(coeffs):
        acc = np.zeros(shards.shape[1], np.uint8)
        for j, c in enumerate(row):
            if c == 0:
                continue
            table = np.array([gf_mul(c, v) for v in range(256)], np.uint8)
            acc ^= table[shards[j]]
        out[i] = acc
    return out


def generator_matrix(k: int, m: int) -> tuple[tuple[int, ...], ...]:
    """The m×k Cauchy parity matrix C: C[i][j] = (x_i ⊕ y_j)⁻¹ with
    x = {0..m-1}, y = {m..m+k-1} (disjoint, so never singular)."""
    return tuple(
        tuple(gf_inv(i ^ (m + j)) for j in range(k)) for i in range(m)
    )


def extended_matrix(k: int, m: int) -> tuple[tuple[int, ...], ...]:
    """[I_k; C]: row r < k emits data shard r verbatim, row k+i emits
    parity i. Any k rows are invertible (extended-Cauchy MDS property)."""
    ident = tuple(
        tuple(1 if i == j else 0 for j in range(k)) for i in range(k)
    )
    return ident + generator_matrix(k, m)


def gf_invert(matrix) -> tuple[tuple[int, ...], ...]:
    """Invert a k×k matrix over GF(2⁸) (Gauss–Jordan; k is tiny)."""
    k = len(matrix)
    a = [list(row) + [1 if i == j else 0 for j in range(k)]
         for i, row in enumerate(matrix)]
    for col in range(k):
        pivot = next((r for r in range(col, k) if a[r][col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        a[col], a[pivot] = a[pivot], a[col]
        inv_p = gf_inv(a[col][col])
        a[col] = [gf_mul(inv_p, v) for v in a[col]]
        for r in range(k):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [v ^ gf_mul(f, w) for v, w in zip(a[r], a[col])]
    return tuple(tuple(row[k:]) for row in a)


# --------------------------------------------------------------------------
# Device matmul: shared bit-linear math, Pallas-blocked on TPU
# --------------------------------------------------------------------------

_LANE = 128        # TPU lane width (int32 lanes after packing)
_BLOCK_ROWS = 512  # packed rows per VMEM block per shard (512·128·4 = 256 KiB)
_PACK = 4 * _LANE  # bytes per packed lane row
_BLOCK_BYTES = _BLOCK_ROWS * _PACK  # one shard's bytes in one kernel block
_ONES = 0x01010101  # bit b of every byte lane of a packed int32 word


def _gf_combine(coeffs, xs):
    """The bit-linear GF matmul body. xs is a list of K int32 arrays of
    PACKED bytes (4 field elements per word, any common shape). x·c =
    XOR_{b: bit b of x set} c·2^b, so each (row, shard) pair costs 8
    select-XORs on the VPU — no per-byte table gathers. The packing is
    sound because every op is per-byte-lane independent: `(x >> b) &
    0x01010101` extracts bit b of each byte (mask positions 0/8/16/24 are
    never touched by int32 sign-extension for b ≤ 7), and `bits · v` with
    v ≤ 255 and 0/1 byte lanes never carries across lanes. Shared
    verbatim by the Pallas kernel and the XLA fallback so their semantics
    cannot diverge."""
    bits = [[(x >> b) & _ONES for b in range(8)] for x in xs]
    outs = []
    for row in coeffs:
        acc = jnp.zeros_like(xs[0])
        for j, c in enumerate(row):
            if c == 0:
                continue
            for b in range(8):
                v = gf_mul(int(c), 1 << b)
                acc = acc ^ (bits[j][b] * v)
        outs.append(acc)
    return outs


def _rs_kernel(coeffs, K, in_ref, out_ref):
    # Blocks are raw uint8 [*, tr, 512]; pack/unpack happens in VMEM so
    # HBM sees exactly one read of data and one write of parity. Packing
    # is by 128-lane quarters of each 512-byte block row: word (r, l) =
    # bytes (r, l | l+128 | l+256 | l+384). Which byte lands in which
    # lane is irrelevant (the math is per-byte-lane independent); only
    # pack/unpack symmetry matters, and unpack below mirrors this slice.
    xs = []
    for j in range(K):
        x = in_ref[j].astype(jnp.int32)
        xs.append(
            x[:, 0 * _LANE : 1 * _LANE]
            | (x[:, 1 * _LANE : 2 * _LANE] << 8)
            | (x[:, 2 * _LANE : 3 * _LANE] << 16)
            | (x[:, 3 * _LANE : 4 * _LANE] << 24)
        )
    for i, acc in enumerate(_gf_combine(coeffs, xs)):
        out_ref[i] = jnp.concatenate(
            [(acc >> (8 * q)) & 0xFF for q in range(4)], axis=1
        ).astype(jnp.uint8)


def _gf_matmul_pallas(coeffs, shards, *, interpret=False):
    K, nb = shards.shape  # nb: whole kernel blocks (shard_bucket)
    M = len(coeffs)
    rows = nb // _PACK
    view = shards.reshape(K, rows, _PACK)
    out = pl.pallas_call(
        functools.partial(_rs_kernel, coeffs, K),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((K, _BLOCK_ROWS, _PACK), lambda g: (0, g, 0))],
        out_specs=pl.BlockSpec((M, _BLOCK_ROWS, _PACK), lambda g: (0, g, 0)),
        out_shape=jax.ShapeDtypeStruct((M, rows, _PACK), jnp.uint8),
        interpret=interpret,
    )(view)
    return out.reshape(M, nb)


def shard_bucket(n: int) -> int:
    """The shard length the program that serves an [K, n] input is built
    for: the smallest entry >= n of a ladder of whole kernel blocks.

    The shard length is a shape of the compiled program, and a sealed
    segment's differs by a record batch every time one rotates — left
    free, every seal traced, lowered and compiled a program of its own
    (~2.5 s on the chip-owning broker, under the GIL its step, settle
    and RPC threads share). So `gf_matmul` zero-pads on the host to this
    length instead. Up to 2^20 the step is one kernel block (256 KiB);
    above, a quarter of the power of two below n — the entries are
    2^k x {1, 1.25, 1.5, 1.75}, at most 25% over. A pure function of n,
    not an option: nothing about a deployment changes the right answer.

    For the default 64 MiB segments one entry, 24 MiB, holds every
    segment of (60, 72] MiB: a segment rotates BEFORE the write that
    would cross segment_bytes, so sealed lengths fall short of it by at
    most one write (a round's records, a standby's group-commit frame).
    Where one write is longer than that (a round of 1 KB rows), the
    store pads the short segment up to the entry of its segment_bytes
    (storage/erasure.encode_segment `bucket_floor`): one program a
    store, built at open."""
    half = 1 << max((n - 1).bit_length() - 1, 0)  # 2^k < n <= 2^(k+1)
    step = max(_BLOCK_BYTES, half >> 2)
    return -(-n // step) * step


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _gf_matmul_jit(coeffs, shards, use_pallas, interpret):
    # `shards` arrives at a bucket length (shard_bucket): a whole number
    # of kernel blocks, so neither form pads or slices on the device.
    K, nb = shards.shape
    M = len(coeffs)
    if use_pallas or interpret:
        return _gf_matmul_pallas(coeffs, shards, interpret=interpret)
    # XLA fallback: same packed math, byte planes packed as shard
    # quarters (plane q = bytes [q·nb/4, (q+1)·nb/4) — no [..., 4]
    # minor dim, whose TPU tiling would pad 32×).
    rows = nb // 4 // _LANE
    planes = shards.reshape(K, 4, rows, _LANE).astype(jnp.int32)
    packed = (
        planes[:, 0] | (planes[:, 1] << 8)
        | (planes[:, 2] << 16) | (planes[:, 3] << 24)
    ).reshape(K, rows * _LANE)
    out = jnp.stack(_gf_combine(coeffs, [packed[j] for j in range(K)]))
    out = out.reshape(M, rows, _LANE)
    planes_out = jnp.stack(
        [(out >> (8 * q)) & 0xFF for q in range(4)], axis=1
    ).astype(jnp.uint8)
    return planes_out.reshape(M, nb)


def gf_matmul(coeffs, shards, *, use_pallas: bool | None = None,
              interpret: bool = False) -> np.ndarray:
    """[M, K] static coefficient matrix ·_gf [K, N] uint8 shards → [M, N],
    on the host.

    `coeffs` must be a tuple of tuples of python ints (it is baked into
    the compiled program; encode uses the fixed generator, reconstruction
    one of the C(k+m, k) inverses — each pattern compiles once per
    bucket). Shards are zero-padded ON THE HOST to `shard_bucket(N)` and
    the product is cut back to N on the host too (zeros encode to zeros
    — GF linearity — so the cut is exact): a pad or slice on the device
    would be a program per N again. An input already at a bucket length
    is passed through uncopied.

    Runs on the calling process's DEFAULT backend: the Pallas kernel
    when that is a TPU, the XLA form of the same math on host cores
    otherwise. No caller pins a platform: which processes may touch the
    chip is decided where processes are started (one chip-owning broker;
    every other broker, client and tool runs with JAX_PLATFORMS=cpu),
    not per call site.
    """
    coeffs = tuple(tuple(int(c) for c in row) for row in coeffs)
    shards = np.asarray(shards, np.uint8)
    if shards.ndim != 2 or len(coeffs) == 0 or len(coeffs[0]) != shards.shape[0]:
        raise ValueError(
            f"coeffs {len(coeffs)}x{len(coeffs[0]) if coeffs else 0} does not "
            f"match shards {shards.shape}"
        )
    K, n = shards.shape
    if n == 0:
        return np.zeros((len(coeffs), 0), np.uint8)
    nb = shard_bucket(n)
    if nb != n:
        padded = np.zeros((K, nb), np.uint8)
        padded[:, :n] = shards
        shards = padded
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    out = _gf_matmul_jit(coeffs, shards, bool(use_pallas), bool(interpret))
    return np.asarray(out)[:, :n]


# --------------------------------------------------------------------------
# RS(k, m) encode / reconstruct on top of the matmul
# --------------------------------------------------------------------------


def rs_encode(data_shards, k: int = 3, m: int = 2, **kw) -> np.ndarray:
    """[k, N] data shards → [m, N] parity shards."""
    if data_shards.shape[0] != k:
        raise ValueError(f"expected {k} data shards, got {data_shards.shape}")
    return gf_matmul(generator_matrix(k, m), data_shards, **kw)


def rs_reconstruct(present: dict[int, "np.ndarray"], k: int = 3,
                   m: int = 2, **kw) -> np.ndarray:
    """Rebuild the [k, N] data block from ANY k available shards.

    `present` maps shard index (0..k-1 data, k..k+m-1 parity) → [N] bytes.
    Raises if fewer than k shards are supplied.
    """
    if len(present) < k:
        raise ValueError(f"need {k} shards to reconstruct, have {len(present)}")
    rows = sorted(present)[:k]
    ext = extended_matrix(k, m)
    inv = gf_invert([ext[r] for r in rows])
    stacked = np.stack([np.asarray(present[r], np.uint8) for r in rows])
    return gf_matmul(inv, stacked, **kw)

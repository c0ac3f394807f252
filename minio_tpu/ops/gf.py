"""GF(2^8) host-side math: tables, Reed-Solomon matrices, and the GF(2)
bit-matrix expansion that turns erasure coding into a TPU MXU matmul.

Field/matrix layout reproduces klauspost/reedsolomon (the library behind
/root/reference/cmd/erasure-coding.go:62): field polynomial 0x11D, a
systematic coding matrix derived from a Vandermonde matrix whose top k x k
square is inverted away. Bit-exactness is enforced by the golden-vector
self-test ported from /root/reference/cmd/erasure-coding.go:157-215.

TPU-first design note: rather than porting AVX2 PSHUFB nibble lookups, we
exploit that multiplication by a constant in GF(2^8) is linear over GF(2).
Every byte coefficient c becomes an 8x8 bit-matrix; a full (m x k) coding
matrix becomes an (8m x 8k) 0/1 matrix; and encode/reconstruct become
`(8m x 8k) @ (8k x S) mod 2` — an int8 matmul with parity extraction,
which is exactly what the MXU is built for. See ops/rs.py for the device
kernels that consume these matrices.
"""

from __future__ import annotations

import functools

import numpy as np

# Field polynomial used by klauspost/reedsolomon's galois tables
# (x^8 + x^4 + x^3 + x^2 + 1).
FIELD_POLY = 0x11D

MAX_SHARDS = 256  # data+parity ceiling, ref cmd/erasure-coding.go:47


def _gen_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= FIELD_POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP_TABLE, LOG_TABLE = _gen_tables()


def gf_mul(a, b):
    """Elementwise GF(2^8) multiply of uint8 arrays/scalars."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    out = EXP_TABLE[(LOG_TABLE[a] + LOG_TABLE[b]) % 255]
    zero = (a == 0) | (b == 0)
    return np.where(zero, np.uint8(0), out)


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("inverse of 0 in GF(2^8)")
    return int(EXP_TABLE[(255 - LOG_TABLE[a]) % 255])


def gf_exp(a: int, n: int) -> int:
    """a**n in GF(2^8), matching klauspost galExp semantics."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP_TABLE[(int(LOG_TABLE[a]) * n) % 255])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """GF(2^8) matrix product of byte matrices [R,K] x [K,C] -> [R,C]."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    # products[r, k, c] = a[r,k] * b[k,c] in GF; XOR-reduce over k.
    prod = gf_mul(a[:, :, None], b[None, :, :])
    return np.bitwise_xor.reduce(prod, axis=1)


def gf_mat_inv(mat: np.ndarray) -> np.ndarray:
    """Invert a square byte matrix over GF(2^8) via Gauss-Jordan.

    Raises ValueError for singular matrices (maps to ErrTooFewShards at the
    codec layer when a reconstruction submatrix is singular).
    """
    mat = np.asarray(mat, dtype=np.uint8)
    n = mat.shape[0]
    if mat.shape != (n, n):
        raise ValueError("matrix must be square")
    # copy-ok: meta (k x k coding matrix, not payload)
    work = np.concatenate([mat.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = None
        for r in range(col, n):
            if work[r, col] != 0:
                pivot = r
                break
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        inv_p = gf_inv(int(work[col, col]))
        work[col] = gf_mul(work[col], inv_p)
        for r in range(n):
            if r != col and work[r, col] != 0:
                work[r] ^= gf_mul(work[r, col], work[col])
    return work[:, n:].copy()  # copy-ok: meta (coding matrix)


def vandermonde(rows: int, cols: int) -> np.ndarray:
    """vm[r, c] = r**c in GF(2^8) (klauspost vandermonde())."""
    out = np.zeros((rows, cols), dtype=np.uint8)
    for r in range(rows):
        for c in range(cols):
            out[r, c] = gf_exp(r, c)
    return out


@functools.lru_cache(maxsize=None)
def rs_matrix(data_shards: int, parity_shards: int) -> np.ndarray:
    """Systematic (k+m, k) coding matrix identical to klauspost buildMatrix:
    Vandermonde(total, k) times inverse of its top k x k square. The top k
    rows come out as the identity, so data shards pass through unchanged.
    """
    total = data_shards + parity_shards
    vm = vandermonde(total, data_shards)
    top_inv = gf_mat_inv(vm[:data_shards])
    out = gf_matmul(vm, top_inv)
    out.setflags(write=False)
    return out


@functools.lru_cache(maxsize=None)
def parity_matrix(data_shards: int, parity_shards: int) -> np.ndarray:
    """The (m, k) parity rows of the systematic coding matrix."""
    # copy-ok: meta (m x k coding matrix, built once per lru key)
    out = rs_matrix(data_shards, parity_shards)[data_shards:].copy()
    out.setflags(write=False)
    return out


def bit_matrix(mat: np.ndarray) -> np.ndarray:
    """Expand a GF(2^8) byte matrix [R, C] into its GF(2) form [8R, 8C].

    Bit order is LSB-first: output row 8*i + a is bit `a` of output byte i;
    input column 8*j + b is bit `b` of input byte j. Column 8*j+b of the
    block for coefficient c holds bits(c * 2^b), because x = XOR_b 2^b and
    multiplication distributes over XOR.
    """
    mat = np.asarray(mat, dtype=np.uint8)
    r, c = mat.shape
    basis = (np.uint8(1) << np.arange(8, dtype=np.uint8))  # [8] input bits
    # prod[i, j, b] = mat[i,j] * 2^b in GF(2^8)
    prod = gf_mul(mat[:, :, None], basis[None, None, :])
    # bits[i, j, b, a] = bit a of prod[i, j, b]
    bits = (prod[:, :, :, None] >> np.arange(8, dtype=np.uint8)) & 1
    # -> [i, a, j, b] -> [8R, 8C]
    out = bits.transpose(0, 3, 1, 2).reshape(8 * r, 8 * c).astype(np.int8)
    return out


def bit_matrix_for(mat: np.ndarray) -> np.ndarray:
    """Cached front-end to bit_matrix, keyed by matrix content: the
    encode/reconstruct hot paths ask for the same few expansions on
    every block batch, and re-deriving the [8R, 8C] expansion per call
    showed up in the device-engine dispatch overhead. Returns a
    read-only array — callers share it."""
    # copy-ok: meta (coding-matrix bytes form the cache key)
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    return _bit_matrix_cached(mat.shape, mat.tobytes())  # copy-ok: meta


@functools.lru_cache(maxsize=512)
def _bit_matrix_cached(shape: tuple, buf: bytes) -> np.ndarray:
    out = bit_matrix(np.frombuffer(buf, dtype=np.uint8).reshape(shape))
    out.setflags(write=False)
    return out


def reconstruct_matrix(
    data_shards: int,
    parity_shards: int,
    present: list[int],
    targets: list[int],
) -> np.ndarray:
    """Cached front-end: a heal/degraded-read of an N-block part asks for
    the SAME (present, targets) matrix N times; the inversion costs
    ~0.6 ms a call, which dominated heal throughput before caching."""
    return _reconstruct_matrix_cached(
        data_shards, parity_shards, tuple(present), tuple(targets)
    )


import functools as _functools


@_functools.lru_cache(maxsize=256)
def _reconstruct_matrix_cached(
    data_shards: int,
    parity_shards: int,
    present: tuple,
    targets: tuple,
) -> np.ndarray:
    full = rs_matrix(data_shards, parity_shards)
    return reconstruct_matrix_from(full, data_shards, present, targets)


def reconstruct_matrix_from(
    full: np.ndarray,
    data_shards: int,
    present: tuple | list,
    targets: tuple | list,
) -> np.ndarray:
    """Byte matrix mapping k chosen present shards to the target shards,
    for ANY systematic (k+m, k) coding matrix `full` — the shared math
    behind every registered codec's reconstruct path (dense Vandermonde
    here, Cauchy in ops/cauchy.py).

    `present` must list >= k available shard indices (data first is not
    required); the first k are used, mirroring klauspost's reconstruct()
    which collects the first dataShards valid shards. `targets` are the
    shard indices to regenerate (data or parity).

    Returns an (len(targets), k) byte matrix M with
    target_shards = M @_GF present_shards[:k].
    """
    k = data_shards
    if len(present) < k:
        raise ValueError("need at least dataShards present shards")
    rows = list(present[:k])
    sub = full[rows]  # [k, k]
    inv = gf_mat_inv(sub)  # present -> original data
    out = np.zeros((len(targets), k), dtype=np.uint8)
    for t_i, t in enumerate(targets):
        if t < k:
            out[t_i] = inv[t]
        else:
            out[t_i] = gf_matmul(full[t : t + 1], inv)[0]
    return out


def gf_matmul_shards_ref(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """Numpy reference: apply byte matrix [R, K] to shards [K, S] -> [R, S].

    Used as the host-side oracle the JAX kernels are tested against.
    """
    mat = np.asarray(mat, dtype=np.uint8)
    shards = np.asarray(shards, dtype=np.uint8)
    out = np.zeros((mat.shape[0], shards.shape[-1]), dtype=np.uint8)
    for i in range(mat.shape[0]):
        acc = np.zeros(shards.shape[-1], dtype=np.uint8)
        for j in range(mat.shape[1]):
            acc ^= gf_mul(mat[i, j], shards[j])
        out[i] = acc
    return out

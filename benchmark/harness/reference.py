"""The plain reference of an erasure-coded object store's shard files.

Independent of the program: it imports nothing of `minio_tpu`, of JAX or
of the C library, and takes nothing the program made. From an object's
bytes and its geometry (k data + m parity shards, block size, the codec
id its xl.meta names) it computes what every drive has to hold: for each
erasure block and each of the k+m shards one frame of
`HighwayHash-256(chunk) || chunk`.

The arithmetic follows the published definitions, as the program's own
host oracle does (`minio_tpu/ops/gf.py`, `ops/cauchy.py`,
`ops/highwayhash.py`; originals listed in PERF.md for a later PR):
GF(2^8) with polynomial 0x11D, klauspost/reedsolomon's systematic
Vandermonde matrix or a Cauchy block, and HighwayHash-256 keyed with
MinIO's bitrot key.
"""

from __future__ import annotations

import functools
import hashlib

import numpy as np

FIELD_POLY = 0x11D
DIGEST = 32


def payload(seed: int, key: str, size: int) -> bytes:
    """An object's bytes, made from the run's seed and a name alone."""
    h = hashlib.sha256(f"{seed}:{key}".encode()).digest()
    return np.random.default_rng(np.frombuffer(h, dtype=np.uint32)).bytes(size)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator of its own for each use of the run's seed, which may be
    wider than 32 bits."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


# --- GF(2^8) -------------------------------------------------------------


@functools.cache
def _tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
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
    a = np.arange(256)
    mul = exp[(log[a][:, None] + log[a][None, :]) % 255]
    mul[0, :] = 0
    mul[:, 0] = 0
    return exp, log, mul  # mul[c][x] = c * x


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("inverse of 0 in GF(2^8)")
    exp, log, _ = _tables()
    return int(exp[(255 - log[a]) % 255])


def _gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    exp, log, _ = _tables()
    return int(exp[(int(log[a]) * n) % 255])


def _mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    mul = _tables()[2]
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for t in range(a.shape[1]):
                acc ^= int(mul[a[i, t], b[t, j]])
            out[i, j] = acc
    return out


def _mat_inv(mat: np.ndarray) -> np.ndarray:
    n = mat.shape[0]
    mul = _tables()[2]
    work = np.concatenate([mat.astype(np.uint8), np.eye(n, dtype=np.uint8)],
                          axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if work[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        work[col] = mul[gf_inv(int(work[col, col]))][work[col]]
        for r in range(n):
            if r != col and work[r, col]:
                work[r] ^= mul[int(work[r, col])][work[col]]
    return work[:, n:]


def _vandermonde_parity(k: int, m: int) -> np.ndarray:
    """klauspost/reedsolomon buildMatrix: Vandermonde(k+m, k) times the
    inverse of its top square; the rows under the identity."""
    vm = np.array([[_gf_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    return _mat_mul(vm, _mat_inv(vm[:k]))[k:]


def _cauchy_parity(k: int, m: int) -> np.ndarray:
    """C[i][j] = 1 / ((k + i) xor j)."""
    return np.array([[gf_inv((k + i) ^ j) for j in range(k)]
                     for i in range(m)], dtype=np.uint8)


# codec id as the object's xl.meta names it -> its (m, k) parity rows
PARITY_MATRICES = {
    "": _vandermonde_parity,          # absent on disk means dense
    "dense-gf8": _vandermonde_parity,
    "cauchy-xor": _cauchy_parity,
}


@functools.cache
def parity_matrix(codec: str, k: int, m: int) -> np.ndarray:
    if codec not in PARITY_MATRICES:
        raise KeyError(f"the reference knows no codec {codec!r}")
    return PARITY_MATRICES[codec](k, m)


def apply_matrix(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """[R, K] byte matrix over shards [..., K, S] -> [..., R, S]."""
    mul = _tables()[2]
    out = np.zeros(shards.shape[:-2] + (mat.shape[0], shards.shape[-1]),
                   dtype=np.uint8)
    for i in range(mat.shape[0]):
        for j in range(mat.shape[1]):
            c = int(mat[i, j])
            if c:
                out[..., i, :] ^= mul[c][shards[..., j, :]]
    return out


# --- HighwayHash-256 (batch of equal-length chunks, in lockstep) ---------

BITROT_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")
_INIT0 = np.array([0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
                   0x13198A2E03707344, 0x243F6A8885A308D3], dtype=np.uint64)
_INIT1 = np.array([0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
                   0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=np.uint64)
_U = np.uint64
_LOW32 = _U(0xFFFFFFFF)


def _rot32(x):
    return (x >> _U(32)) | (x << _U(32))


def _byte(v, b: int):
    return v & _U(0xFF << (8 * b))


def _zipper_add(dst, src):
    ve, vo = src[..., 0::2], src[..., 1::2]
    dst[..., 0::2] += (
        ((_byte(ve, 3) | _byte(vo, 4)) >> _U(24))
        | ((_byte(ve, 5) | _byte(vo, 6)) >> _U(16))
        | _byte(ve, 2) | (_byte(ve, 1) << _U(32))
        | (_byte(vo, 7) >> _U(8)) | (ve << _U(56)))
    dst[..., 1::2] += (
        ((_byte(vo, 3) | _byte(ve, 4)) >> _U(24))
        | _byte(vo, 2) | (_byte(vo, 5) >> _U(16))
        | (_byte(vo, 1) << _U(24)) | (_byte(ve, 6) >> _U(8))
        | (_byte(vo, 0) << _U(48)) | _byte(ve, 7))


class _State:
    def __init__(self, key: bytes, shape: tuple):
        k = np.frombuffer(key, dtype="<u8")
        shape = shape + (4,)
        self.mul0 = np.broadcast_to(_INIT0, shape).copy()
        self.mul1 = np.broadcast_to(_INIT1, shape).copy()
        self.v0 = self.mul0 ^ np.broadcast_to(k, shape)
        self.v1 = self.mul1 ^ np.broadcast_to(_rot32(k), shape)

    def update(self, packet):
        self.v1 += self.mul0 + packet
        self.mul0 ^= (self.v1 & _LOW32) * (self.v0 >> _U(32))
        self.v0 += self.mul1
        self.mul1 ^= (self.v0 & _LOW32) * (self.v1 >> _U(32))
        _zipper_add(self.v0, self.v1)
        _zipper_add(self.v1, self.v0)

    def remainder(self, tail):
        n = tail.shape[-1]
        mod4, full4 = n & 3, n & ~3
        self.v0 += _U((n << 32) + n)
        c, inv = _U(n), _U(32 - n)
        lo, hi = self.v1 & _LOW32, self.v1 >> _U(32)
        lo = ((lo << c) | (lo >> inv)) & _LOW32
        hi = ((hi << c) | (hi >> inv)) & _LOW32
        self.v1 = (hi << _U(32)) | lo
        packet = np.zeros(tail.shape[:-1] + (32,), dtype=np.uint8)
        packet[..., :full4] = tail[..., :full4]
        if n & 16:
            packet[..., 28:32] = tail[..., n - 4:n]
        elif mod4:
            rest = tail[..., full4:]
            packet[..., 16] = rest[..., 0]
            packet[..., 17] = rest[..., mod4 >> 1]
            packet[..., 18] = rest[..., mod4 - 1]
        self.update(packet.view("<u8").reshape(tail.shape[:-1] + (4,)))

    def digest(self):
        for _ in range(10):
            self.update(_rot32(self.v0[..., [2, 3, 0, 1]]))

        def reduce(a3u, a2, a1, a0):
            a3 = a3u & _U(0x3FFFFFFFFFFFFFFF)
            m1 = (a1 ^ ((a3 << _U(1)) | (a2 >> _U(63)))
                  ^ ((a3 << _U(2)) | (a2 >> _U(62))))
            return a0 ^ (a2 << _U(1)) ^ (a2 << _U(2)), m1

        v0, v1, mul0, mul1 = self.v0, self.v1, self.mul0, self.mul1
        h0, h1 = reduce(v1[..., 1] + mul1[..., 1], v1[..., 0] + mul1[..., 0],
                        v0[..., 1] + mul0[..., 1], v0[..., 0] + mul0[..., 0])
        h2, h3 = reduce(v1[..., 3] + mul1[..., 3], v1[..., 2] + mul1[..., 2],
                        v0[..., 3] + mul0[..., 3], v0[..., 2] + mul0[..., 2])
        out = np.ascontiguousarray(np.stack([h0, h1, h2, h3], axis=-1))
        return out.view(np.uint8).reshape(out.shape[:-1] + (32,))


def highwayhash256(data: np.ndarray, key: bytes = BITROT_KEY) -> np.ndarray:
    """[..., L] uint8 -> [..., 32] uint8, every chunk hashed on its own."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    shape, length = data.shape[:-1], data.shape[-1]
    state = _State(key, shape)
    n = length // 32
    if n:
        packets = data[..., :n * 32].view("<u8").reshape(shape + (n, 4))
        with np.errstate(over="ignore"):
            for p in range(n):
                state.update(packets[..., p, :])
    with np.errstate(over="ignore"):
        if length % 32:
            state.remainder(data[..., n * 32:])
        return state.digest()


# --- what the drives have to hold -----------------------------------------


def shard_size(block_len: int, k: int) -> int:
    return -(-block_len // k)


def expected_shards(bodies: list[bytes], k: int, m: int, block_size: int,
                    codec: str) -> tuple[np.ndarray, np.ndarray]:
    """Chunks [N, blocks, k+m, S] and digests [N, blocks, k+m, 32] of N
    objects of one size whose length is a whole number of blocks."""
    size = len(bodies[0])
    if size % block_size or any(len(b) != size for b in bodies):
        raise ValueError("the reference takes objects of one size, a whole "
                         "number of blocks long")
    blocks, s = size // block_size, shard_size(block_size, k)
    data = np.zeros((len(bodies), blocks, k * s), dtype=np.uint8)
    for i, body in enumerate(bodies):
        data[i, :, :block_size] = np.frombuffer(
            body, dtype=np.uint8).reshape(blocks, block_size)
    data = data.reshape(len(bodies), blocks, k, s)
    parity = apply_matrix(parity_matrix(codec, k, m), data)
    chunks = np.concatenate([data, parity], axis=2)
    return chunks, highwayhash256(chunks)

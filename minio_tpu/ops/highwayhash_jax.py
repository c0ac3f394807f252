"""HighwayHash-256 on TPU via JAX, written so that the packet step is
elementwise from end to end and the chip's compiler can fuse it.

Layout. The hash state is four vectors (v0, v1, mul0, mul1) of four u64
lanes. TPU vector units are 32-bit, so a u64 is a `(hi, lo)` pair of uint32
arrays, and every lane is its OWN pair of arrays over the batch dims: a
vector is a Python tuple of four `(hi, lo)` pairs, the state 32 arrays of
the data's batch shape (`[B, n]` in the engines). The four lanes are never
an array axis. Everything the algorithm does across lanes (the even/odd
split of the zipper merge, its re-interleave, the `[2, 3, 0, 1]` permutation
and the 32-bit rotation of the finalisation) is then a choice of which array
to read, made at trace time: the step holds arithmetic alone (no gather,
concatenate, reshape or convert). With the lanes as the minor axis, as this
module had them until PR 30, the chip padded 4 to 128 and ran 54 tiny
programs per 32-byte packet: 3.9-7.0 us a packet whatever the batch, 0.8-1.6
since (PERF.md section 6).

The packets are turned into words once, before the scan: the bytes are put
together into little-endian uint32 where they lie and moved once into one
`[P, 8, ...batch]` array, so that step p reads eight whole batch-shaped
slabs off a leading axis (static picks: an address each). The batch dims
stay as they come: on the mesh the shard axis `n` is sharded over the chips,
and flattening it into the block axis would make the partitioner gather the
stripe. The ten rounds of the finalisation are a loop over the same step,
which a remainder packet enters as its first trip: a program holds the step
twice, whatever its length.

Semantics are identical to ops/highwayhash.py (the numpy oracle, itself
validated against the reference bitrot self-test). The packet chain inside
one chunk is sequential (lax.scan); independent chunks are the batch axes,
mirroring how the reference hashes each shardSize chunk independently
(/root/reference/cmd/bitrot-streaming.go:48-59). Typical use: hash all
(k+m) shard chunks of a batch of erasure blocks in one device dispatch,
fused after the RS encode matmul.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxenv import place_compile_cache
from .highwayhash import MAGIC_KEY, _INIT0, _INIT1

place_compile_cache()

_U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)
_BYTE3 = np.uint32(0xFF000000)
_LANES = range(4)


# --- u64 as (hi, lo) uint32 pairs; all ops elementwise over arrays ---

def _carry(wrapped, hi):
    """hi + 1 where the low word's sum wrapped (a select, not a widening of
    the comparison's result: the step holds no convert)."""
    return jax.lax.select(wrapped, hi + np.uint32(1), hi)


def _add(a, b):
    lo = a[1] + b[1]
    return (_carry(lo < a[1], a[0] + b[0]), lo)


def _xor(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _shl(a, n: int, fill=None):
    """a << n for 0 < n < 32; the bits shifted in are the top of `fill`'s."""
    lo = a[1] << n
    if fill is not None:
        lo = lo | (fill[0] >> (32 - n))
    return ((a[0] << n) | (a[1] >> (32 - n)), lo)


def _mul32(a32, b32):
    """Full 32x32 -> 64 product of uint32 arrays, via 16-bit limbs."""
    al, ah = a32 & _MASK16, a32 >> 16
    bl, bh = b32 & _MASK16, b32 >> 16
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    hh = ah * bh
    # lo = ll + ((lh + hl) << 16); hi = hh + ((lh + hl) >> 16) + carries
    mid = lh + (hl & _MASK16)  # may carry into bit 32 of mid*2^16
    lo = ll + (mid << 16)
    hi = hh + (hl >> 16) + (mid >> 16)
    hi = jax.lax.select(mid < lh, hi + np.uint32(1 << 16), hi)  # carry out of mid
    return (_carry(lo < ll, hi), lo)


def _zipper_pair(ve, vo):
    """Same byte shuffle as ops/highwayhash.py:_zipper_pair, on 32-bit words.

    With e0..e7 / o0..o7 the bytes of the even / odd lane (0 = least
    significant), the reference's masks and shifts give
    add_even = [e3, o4, e2, e5, o6, e1, o7, e0] and
    add_odd  = [o3, e4, o2, o5, o1, e6, o0, e7]; each output word takes its
    four bytes straight from the input words.
    """
    (eh, el), (oh, ol) = ve, vo
    even = (
        ((oh >> 16) & 0xFF) | (el & 0xFF00) | ((oh >> 8) & 0xFF0000) | (el << 24),
        (el >> 24) | ((oh & 0xFF) << 8) | (el & 0xFF0000) | ((eh << 16) & _BYTE3),
    )
    odd = (
        ((ol >> 8) & 0xFF) | ((eh >> 8) & 0xFF00) | ((ol & 0xFF) << 16) | (eh & _BYTE3),
        (ol >> 24) | ((eh & 0xFF) << 8) | (ol & 0xFF0000) | ((oh << 16) & _BYTE3),
    )
    return even, odd


def _zipper_add(dst, src):
    add0, add1 = _zipper_pair(src[0], src[1])
    add2, add3 = _zipper_pair(src[2], src[3])
    return tuple(_add(d, a) for d, a in zip(dst, (add0, add1, add2, add3)))


def _update(state, packet):
    """One packet step; state = (v0, v1, mul0, mul1), each (and the packet)
    a tuple of four (hi, lo) lanes."""
    v0, v1, mul0, mul1 = state
    v1 = tuple(_add(v1[i], _add(mul0[i], packet[i])) for i in _LANES)
    # (v1 & low32) * (v0 >> 32)
    mul0 = tuple(_xor(mul0[i], _mul32(v1[i][1], v0[i][0])) for i in _LANES)
    v0 = tuple(_add(v0[i], mul1[i]) for i in _LANES)
    mul1 = tuple(_xor(mul1[i], _mul32(v0[i][1], v1[i][0])) for i in _LANES)
    v0 = _zipper_add(v0, v1)
    v1 = _zipper_add(v1, v0)
    return (v0, v1, mul0, mul1)


def _permuted(v0):
    """The finalisation's packet: lanes [2, 3, 0, 1] of v0, each rotated by
    32 bits (hi and lo change places)."""
    return tuple((v0[i][1], v0[i][0]) for i in (2, 3, 0, 1))


def _modular_reduction(a3u, a2, a1, a0):
    a3 = (a3u[0] & np.uint32(0x3FFFFFFF), a3u[1])
    # (a3:a2) is one 128-bit value: what leaves a2 at the top enters a3.
    m1 = _xor(a1, _xor(_shl(a3, 1, fill=a2), _shl(a3, 2, fill=a2)))
    m0 = _xor(a0, _xor(_shl(a2, 1), _shl(a2, 2)))
    return m0, m1


def _init_state(key: bytes, batch_shape):
    def lanes(u64s):
        return tuple(
            (jnp.full(batch_shape, np.uint32(int(x) >> 32)),
             jnp.full(batch_shape, np.uint32(int(x) & 0xFFFFFFFF)))
            for x in u64s
        )

    k = np.frombuffer(key, dtype="<u8")
    k_rot = (k >> np.uint64(32)) | (k << np.uint64(32))
    return (lanes(_INIT0 ^ k), lanes(_INIT1 ^ k_rot), lanes(_INIT0), lanes(_INIT1))


def _packet_words(data, n_packets: int):
    """The full packets of uint8 [..., L] as the scan's input: uint32
    [P, 8, ...batch], packet-major, word 2i the low and 2i+1 the high half of
    lane i. The words are put together where the bytes lie (L minor), then
    moved once, as 32-bit values."""
    batch = data.shape[:-1]
    nb = len(batch)
    by = data[..., : n_packets * 32]
    w = (
        by[..., 0::4].astype(_U32) | (by[..., 1::4].astype(_U32) << 8)
        | (by[..., 2::4].astype(_U32) << 16) | (by[..., 3::4].astype(_U32) << 24)
    ).reshape(batch + (n_packets, 8))
    return jnp.transpose(w, (nb, nb + 1) + tuple(range(nb)))


def _packet_lanes(words):
    """One packet's eight words [8, ...batch] as four (hi, lo) lanes."""
    return tuple((words[2 * i + 1], words[2 * i]) for i in _LANES)


def _remainder_packet(tail, mod32: int):
    """The padded last packet of `mod32` (1..31) bytes, as four (hi, lo)
    lanes: whole 4-byte groups in place, then either the last four bytes at
    28..31 (mod32 >= 16) or up to three bytes spread over 16..18."""
    mod4 = mod32 & 3
    full4 = mod32 & ~3
    src = [None] * 32  # packet byte -> index into the tail
    src[:full4] = range(full4)
    if mod32 & 16:
        src[28:32] = range(mod32 - 4, mod32)
    elif mod4:
        src[16:19] = (full4, full4 + (mod4 >> 1), full4 + mod4 - 1)
    zero = jnp.zeros(tail.shape[:-1], _U32)

    def word(j):
        w = zero
        for b in range(4):
            if src[4 * j + b] is not None:
                w = w | (tail[..., src[4 * j + b]].astype(_U32) << (8 * b))
        return w

    return tuple((word(2 * i + 1), word(2 * i)) for i in _LANES)


def _rotate32_by(count: int, a):
    return (
        (a[0] << count) | (a[0] >> (32 - count)),
        (a[1] << count) | (a[1] >> (32 - count)),
    )


def _finalize256(state, last_packet=None):
    """Ten permute-and-update rounds, then the modular reduction. The rounds
    are one loop over the packet step; a remainder packet (`last_packet`) is
    that loop's first trip, so that a program holds the step twice (scan and
    here) whatever its length."""
    def round_(i, st):
        packet = _permuted(st[0])
        if last_packet is not None:
            first = i == 0
            packet = tuple(
                tuple(jax.lax.select(first, x, y) for x, y in zip(last, perm))
                for last, perm in zip(last_packet, packet)
            )
        return _update(st, packet)

    rounds = 10 + (last_packet is not None)
    v0, v1, mul0, mul1 = jax.lax.fori_loop(0, rounds, round_, state)
    h0, h1 = _modular_reduction(
        _add(v1[1], mul1[1]), _add(v1[0], mul1[0]),
        _add(v0[1], mul0[1]), _add(v0[0], mul0[0]),
    )
    h2, h3 = _modular_reduction(
        _add(v1[3], mul1[3]), _add(v1[2], mul1[2]),
        _add(v0[3], mul0[3]), _add(v0[2], mul0[2]),
    )
    # Serialize LE: per hash word, lo bytes then hi bytes.
    w = jnp.stack([x for h in (h0, h1, h2, h3) for x in (h[1], h[0])], axis=-1)
    by = jax.lax.bitcast_convert_type(w, jnp.uint8)  # [..., 8, 4]
    return by.reshape(w.shape[:-1] + (32,))


def _scan_unroll(n_packets: int) -> int:
    """Packets per loop trip of the scan. The loop's control cost is paid
    once a trip and the program grows with the trip's body: on a v5e
    (PR 30) 16,384 packets at [1, 4] took 30.2 / 19.9 / 17.9 / 19.9 ms at
    1 / 2 / 4 / 8 a trip, 2,730 at [2, 16] 2.38 / 2.30 / 2.12 / 2.06, while
    the compile went from 1.5 s to 3.5 s (on the CPU from 0.5 s to 6-30 s).
    Four has the gain; heal compiles this program for every new failure
    pattern, so eight would cost it what it might give a PUT."""
    return min(4, n_packets)


def _zero_chains(batch_shape) -> int:
    """How many chains each chain becomes on a new trailing axis, all but the
    first hashing zeros: 1 (none) from nine chains on. Eight chains or fewer
    are too few for the compiler to lay along the chip's 128 lanes; it then
    keeps the words in the bytes' order and pulls every packet's out with
    cross-lane reductions, which cost more than the hash (on a v5e, PR 30:
    [1, 4] x 524,288 bytes 15.8 ms as it comes, 9.0 as 32 chains; [2, 2] x
    87,382 4.2 and 2.3). A vector op costs the same at 4 chains as at 32, so
    the zero chains are free; from 16 chains on widening only adds bytes to
    move ([8, 2] 2.0 ms as it comes, 3.8 as 128)."""
    chains = math.prod(batch_shape)
    return 32 // chains if chains <= 8 else 1


def _build_hash_fn(length: int, key: bytes):
    """Returns a jitted fn hashing [..., length] uint8 -> [..., 32] uint8."""
    n_packets = length // 32
    rem = length % 32

    def fn(data):
        batch = data.shape[:-1]
        extra = _zero_chains(batch)

        def widen(x):
            if extra == 1:
                return x
            return jnp.pad(x[..., None], [(0, 0)] * x.ndim + [(0, extra - 1)])

        state = _init_state(key, batch if extra == 1 else batch + (extra,))
        if n_packets:
            # scan over the packet axis; batch dims ride along.
            state, _ = jax.lax.scan(
                lambda st, words: (_update(st, _packet_lanes(words)), None),
                state, widen(_packet_words(data, n_packets)),
                unroll=_scan_unroll(n_packets),
            )
        last = None
        if rem:
            v0, v1, mul0, mul1 = state
            inc = np.uint32(rem)
            v0 = tuple(_add(v, (inc, inc)) for v in v0)
            v1 = tuple(_rotate32_by(rem, v) for v in v1)
            state = (v0, v1, mul0, mul1)
            last = jax.tree.map(
                widen, _remainder_packet(data[..., n_packets * 32 :], rem))
        digests = _finalize256(state, last)
        return digests if extra == 1 else digests[..., 0, :]

    # jax-ok: sole caller _hash_fn_cache is lru_cached per (length, key)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _hash_fn_cache(length: int, key: bytes):
    return _build_hash_fn(length, key)


def hash256_batch_jax(data, key: bytes = MAGIC_KEY) -> jax.Array:
    """Device-side HighwayHash-256 of a batch of equal-length chunks.

    data: uint8 [..., L]; returns uint8 [..., 32]. Compiled per (L, key).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    return _hash_fn_cache(int(data.shape[-1]), key)(data)

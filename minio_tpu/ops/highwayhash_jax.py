"""HighwayHash-256 on TPU via JAX: uint64 state emulated as uint32 (hi, lo)
lane pairs (TPU vector units are 32-bit; u64 is decomposed explicitly so the
kernel lowers to plain VPU ops, no x64 mode needed).

Semantics are identical to ops/highwayhash.py (the numpy oracle, itself
validated against the reference bitrot self-test). The packet chain inside
one chunk is sequential (lax.scan); independent chunks are the batch axis,
mirroring how the reference hashes each shardSize chunk independently
(/root/reference/cmd/bitrot-streaming.go:48-59). Typical use: hash all
(k+m) shard chunks of a batch of erasure blocks in one device dispatch,
fused after the RS encode matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxenv import place_compile_cache
from .highwayhash import MAGIC_KEY, _INIT0, _INIT1

place_compile_cache()

_U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)


# --- u64 as (hi, lo) uint32 pairs; all ops elementwise over arrays ---

def _u64(hi, lo):
    return (jnp.asarray(hi, _U32), jnp.asarray(lo, _U32))


def _add(a, b):
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(_U32)
    return (a[0] + b[0] + carry, lo)


def _xor(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _or(a, b):
    return (a[0] | b[0], a[1] | b[1])


def _shl(a, n: int):
    if n == 0:
        return a
    if n >= 32:
        return (a[1] << (n - 32) if n > 32 else a[1], jnp.zeros_like(a[1]))
    return ((a[0] << n) | (a[1] >> (32 - n)), a[1] << n)


def _shr(a, n: int):
    if n == 0:
        return a
    if n >= 32:
        return (jnp.zeros_like(a[0]), a[0] >> (n - 32) if n > 32 else a[0])
    return (a[0] >> n, (a[1] >> n) | (a[0] << (32 - n)))


def _and_const(a, c: int):
    hi = np.uint32(c >> 32)
    lo = np.uint32(c & 0xFFFFFFFF)
    return (a[0] & hi, a[1] & lo)


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
    mid_carry = (mid < lh).astype(_U32)  # carry out of 32-bit mid sum
    lo = ll + (mid << 16)
    carry_lo = (lo < ll).astype(_U32)
    hi = hh + (hl >> 16) + (mid >> 16) + (mid_carry << 16) + carry_lo
    return (hi, lo)


def _rot64_by_32(a):
    return (a[1], a[0])


def _mask_byte(a, b: int):
    return _and_const(a, 0xFF << (8 * b))


def _zipper_pair(ve, vo):
    """Same byte shuffle as ops/highwayhash.py:_zipper_pair on (hi,lo)."""
    add_even = _or(
        _or(
            _shr(_or(_mask_byte(ve, 3), _mask_byte(vo, 4)), 24),
            _shr(_or(_mask_byte(ve, 5), _mask_byte(vo, 6)), 16),
        ),
        _or(
            _or(_mask_byte(ve, 2), _shl(_mask_byte(ve, 1), 32)),
            _or(_shr(_mask_byte(vo, 7), 8), _shl(ve, 56)),
        ),
    )
    add_odd = _or(
        _or(
            _shr(_or(_mask_byte(vo, 3), _mask_byte(ve, 4)), 24),
            _or(_mask_byte(vo, 2), _shr(_mask_byte(vo, 5), 16)),
        ),
        _or(
            _or(_shl(_mask_byte(vo, 1), 24), _shr(_mask_byte(ve, 6), 8)),
            _or(_shl(_mask_byte(vo, 0), 48), _mask_byte(ve, 7)),
        ),
    )
    return add_even, add_odd


def _pair_slice(a, sl):
    return (a[0][..., sl], a[1][..., sl])


def _pair_concat_even_odd(even, odd):
    """Interleave even/odd lane pairs back into [..., 4] order."""
    def weave(e, o):
        return jnp.stack([e[..., 0], o[..., 0], e[..., 1], o[..., 1]], axis=-1)
    return (weave(even[0], odd[0]), weave(even[1], odd[1]))


def _zipper_add(dst, src):
    ve = _pair_slice(src, slice(0, None, 2))
    vo = _pair_slice(src, slice(1, None, 2))
    add_even, add_odd = _zipper_pair(ve, vo)
    de = _add(_pair_slice(dst, slice(0, None, 2)), add_even)
    do = _add(_pair_slice(dst, slice(1, None, 2)), add_odd)
    return _pair_concat_even_odd(de, do)


def _update(state, packet):
    v0, v1, mul0, mul1 = state
    v1 = _add(v1, _add(mul0, packet))
    mul0 = _xor(mul0, _mul32(v1[1], v0[0]))  # (v1 & low32) * (v0 >> 32)
    v0 = _add(v0, mul1)
    mul1 = _xor(mul1, _mul32(v0[1], v1[0]))
    v0 = _zipper_add(v0, v1)
    v1 = _zipper_add(v1, v0)
    return (v0, v1, mul0, mul1)


def _permute_and_update(state):
    v0 = state[0]
    perm = _rot64_by_32((v0[0][..., [2, 3, 0, 1]], v0[1][..., [2, 3, 0, 1]]))
    return _update(state, perm)


def _modular_reduction(a3u, a2, a1, a0):
    a3 = _and_const(a3u, 0x3FFFFFFFFFFFFFFF)
    m1 = _xor(a1, _xor(_or(_shl(a3, 1), _shr(a2, 63)), _or(_shl(a3, 2), _shr(a2, 62))))
    m0 = _xor(a0, _xor(_shl(a2, 1), _shl(a2, 2)))
    return m0, m1


def _lane(a, i):
    return (a[0][..., i], a[1][..., i])


def _init_state(key: bytes, batch_shape):
    k64 = np.frombuffer(key, dtype="<u8")
    k = _u64(
        jnp.broadcast_to(jnp.asarray((k64 >> 32).astype(np.uint32)), batch_shape + (4,)),
        jnp.broadcast_to(jnp.asarray((k64 & 0xFFFFFFFF).astype(np.uint32)), batch_shape + (4,)),
    )
    i0 = _u64(
        jnp.broadcast_to(jnp.asarray((_INIT0 >> np.uint64(32)).astype(np.uint32)), batch_shape + (4,)),
        jnp.broadcast_to(jnp.asarray((_INIT0 & np.uint64(0xFFFFFFFF)).astype(np.uint32)), batch_shape + (4,)),
    )
    i1 = _u64(
        jnp.broadcast_to(jnp.asarray((_INIT1 >> np.uint64(32)).astype(np.uint32)), batch_shape + (4,)),
        jnp.broadcast_to(jnp.asarray((_INIT1 & np.uint64(0xFFFFFFFF)).astype(np.uint32)), batch_shape + (4,)),
    )
    mul0, mul1 = i0, i1
    v0 = _xor(mul0, k)
    v1 = _xor(mul1, _rot64_by_32(k))
    return (v0, v1, mul0, mul1)


def _bytes_to_lanes(packet_bytes):
    """[..., 32] uint8 -> (hi, lo) [..., 4] uint32, little-endian u64 lanes."""
    b = packet_bytes.astype(jnp.uint32).reshape(packet_bytes.shape[:-1] + (4, 8))
    w0 = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)
    w1 = b[..., 4] | (b[..., 5] << 8) | (b[..., 6] << 16) | (b[..., 7] << 24)
    return (w1, w0)


def _rotate32_by(count: int, a):
    if count == 0:
        return a
    return (
        (a[0] << count) | (a[0] >> (32 - count)),
        (a[1] << count) | (a[1] >> (32 - count)),
    )


def _finalize256(state):
    for _ in range(10):
        state = _permute_and_update(state)
    v0, v1, mul0, mul1 = state
    h0, h1 = _modular_reduction(
        _add(_lane(v1, 1), _lane(mul1, 1)), _add(_lane(v1, 0), _lane(mul1, 0)),
        _add(_lane(v0, 1), _lane(mul0, 1)), _add(_lane(v0, 0), _lane(mul0, 0)),
    )
    h2, h3 = _modular_reduction(
        _add(_lane(v1, 3), _lane(mul1, 3)), _add(_lane(v1, 2), _lane(mul1, 2)),
        _add(_lane(v0, 3), _lane(mul0, 3)), _add(_lane(v0, 2), _lane(mul0, 2)),
    )
    # Serialize LE: per hash word, lo bytes then hi bytes.
    words = []
    for h in (h0, h1, h2, h3):
        words.extend([h[1], h[0]])  # lo32, hi32
    w = jnp.stack(words, axis=-1)  # [..., 8] uint32
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    by = (w[..., :, None] >> shifts) & jnp.uint32(0xFF)
    return by.reshape(w.shape[:-1] + (32,)).astype(jnp.uint8)


def _build_hash_fn(length: int, key: bytes):
    """Returns a jitted fn hashing [..., length] uint8 -> [..., 32] uint8."""
    n_packets = length // 32
    rem = length % 32

    def fn(data):
        batch_shape = data.shape[:-1]
        state = _init_state(key, batch_shape)
        if n_packets:
            packets = data[..., : n_packets * 32].reshape(
                batch_shape + (n_packets, 32)
            )
            # scan over the packet axis; batch dims ride along.
            packets = jnp.moveaxis(packets, -2, 0)  # [P, ..., 32]

            def step(st, pkt):
                return _update(st, _bytes_to_lanes(pkt)), None

            state, _ = jax.lax.scan(step, state, packets)
        if rem:
            mod32 = rem
            mod4 = mod32 & 3
            full4 = mod32 & ~3
            tail = data[..., n_packets * 32 :]
            v0, v1, mul0, mul1 = state
            inc = _u64(
                jnp.full_like(v0[0], np.uint32(mod32)),
                jnp.full_like(v0[1], np.uint32(mod32)),
            )
            v0 = _add(v0, inc)
            v1 = _rotate32_by(mod32, v1)
            packet = jnp.zeros(batch_shape + (32,), dtype=jnp.uint8)
            packet = packet.at[..., :full4].set(tail[..., :full4])
            if mod32 & 16:
                packet = packet.at[..., 28:32].set(tail[..., mod32 - 4 : mod32])
            elif mod4:
                remainder = tail[..., full4:]
                packet = packet.at[..., 16].set(remainder[..., 0])
                packet = packet.at[..., 17].set(remainder[..., mod4 >> 1])
                packet = packet.at[..., 18].set(remainder[..., mod4 - 1])
            state = _update((v0, v1, mul0, mul1), _bytes_to_lanes(packet))
        return _finalize256(state)

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

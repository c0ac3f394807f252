"""JAX device kernels for Reed-Solomon GF(2^8) encode/reconstruct.

TPU-first formulation (see ops/gf.py for the math): a GF(2^8) coding
matrix is expanded once on the host into a GF(2) 0/1 matrix [8R, 8K];
shard bytes are unpacked to bit-planes on device; then

    out_bits[8R, S] = (bitmat[8R, 8K] @ bits[8K, S]) mod 2

runs on the MXU as an int8 x int8 -> int32 matmul (contraction dim
8K <= 128 for any real erasure set, so a single MXU pass per tile),
followed by a parity extract (& 1) and a bit-plane repack on the VPU.
XLA fuses unpack/matmul/pack in this module's path.

This replaces the reference's AVX2 galois-field nibble-table loops
(klauspost/reedsolomon, used at /root/reference/cmd/erasure-coding.go:62,
EncodeData :76-90, DecodeDataBlocks :95-108).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxenv import place_compile_cache

place_compile_cache()


@functools.partial(jax.jit, donate_argnums=())
def _apply_bits(bitmat: jax.Array, shards: jax.Array) -> jax.Array:
    """Apply a GF(2) expanded matrix to shard bytes.

    bitmat: int8 [8R, 8K] with entries in {0, 1}
    shards: uint8 [..., K, S]
    returns uint8 [..., R, S]
    """
    k8 = bitmat.shape[1]
    r8 = bitmat.shape[0]
    k = k8 // 8
    r = r8 // 8
    lead = shards.shape[:-2]
    s = shards.shape[-1]

    bit_idx = jnp.arange(8, dtype=jnp.uint8)
    # [..., K, 8, S] bit-planes, LSB-first, then flatten (K, 8) -> 8K.
    bits = ((shards[..., :, None, :] >> bit_idx[:, None]) & 1).astype(jnp.int8)
    bits = bits.reshape(*lead, k8, s)

    acc = jnp.einsum(
        "pq,...qs->...ps", bitmat, bits, preferred_element_type=jnp.int32
    )
    obits = (acc & 1).astype(jnp.uint8).reshape(*lead, r, 8, s)
    weights = (jnp.uint8(1) << bit_idx)
    out = (obits * weights[:, None]).sum(axis=-2, dtype=jnp.uint32)
    return out.astype(jnp.uint8)


def apply_gf_matrix(bitmat, shards) -> jax.Array:
    """Public entry: bitmat int8 [8R,8K] (from gf.bit_matrix), shards
    uint8 [..., K, S]. Leading dims are batch. XLA fuses the einsum
    into one unpack/matmul/pack kernel."""
    bitmat = jnp.asarray(bitmat, dtype=jnp.int8)
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    return _apply_bits(bitmat, shards)


def gf_matmul_shards_np(bitmat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """Pure-numpy bit-matrix path (same math, no JAX) for small host work."""
    k8 = bitmat.shape[1]
    shards = np.asarray(shards, dtype=np.uint8)
    k, s = shards.shape[-2], shards.shape[-1]
    bits = ((shards[..., :, None, :] >> np.arange(8, dtype=np.uint8)[:, None]) & 1)
    bits = bits.reshape(*shards.shape[:-2], k8, s).astype(np.int32)
    acc = (bitmat.astype(np.int32) @ bits) & 1
    r = bitmat.shape[0] // 8
    obits = acc.reshape(*shards.shape[:-2], r, 8, s)
    weights = (1 << np.arange(8)).reshape(8, 1)
    return (obits * weights).sum(axis=-2).astype(np.uint8)

"""Pallas TPU kernel for fused GF(2^8) Reed-Solomon coding — an
EXPERIMENT, off by default.

The einsum path (ops/rs.py) materializes int8 bit-planes around the
matmul; this kernel keeps the unpack/matmul/pack chain of one tile in
VMEM. The production codec dispatches the einsum; MTPU_RS_KERNEL=pallas
opts in to this kernel on a TPU backend. Whether it earns its place is
ROADMAP D3's question: neither path has a timing on record from this
attachment. The kernel structure:

    bytes [K, T] --unpack--> bits [8K, T] --MXU--> acc [8R, T]
                 --&1, pack--> bytes [R, T]

per grid step (batch block, shard tile). The contraction dim 8K <= 128
for every real erasure set (K <= 16), so each tile is a single MXU pass;
8K = 96 for the 12+4 north-star config is naturally a multiple of the
int8 sublane tile (32).

Replaces the AVX2 galois-field loops behind the reference's EncodeData /
DecodeDataBlocks (/root/reference/cmd/erasure-coding.go:76-108,
klauspost/reedsolomon). Bit-exactness is enforced against the ported
golden vectors (tests/test_codec_golden.py) and the numpy oracle
(ops/gf.gf_matmul_shards_ref).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import spans as _spans
from ..utils.jaxenv import place_compile_cache

place_compile_cache()

# Shard bytes processed per grid step. 8 KiB keeps VMEM well under
# budget: in 8K*T int8 bits (768 KiB @ K=12) + 8R*T int32 acc (1 MiB @
# R=4) + tiles, with headroom for double buffering.
DEFAULT_TILE = 8192


def _gf_kernel(bitmat_ref, shards_ref, out_ref):
    """One (batch block, shard tile): fused unpack -> matmul -> pack.

    Bit-planes are PLANE-MAJOR: bits row b*K + j is bit b of input row j,
    built by concatenating the 8 shifted planes along sublanes. The
    original interleaved layout (row j*8 + b) needed a stack+reshape that
    Mosaic lowers to an expensive relayout. The caller permutes
    bitmat's columns to match (_plane_major_cols)."""
    r8 = bitmat_ref.shape[0]
    r = r8 // 8

    tile = shards_ref[0].astype(jnp.int32)  # [K, T]
    planes = [((tile >> b) & 1) for b in range(8)]
    bits = jnp.concatenate(planes, axis=0)  # [8K, T] plane-major

    acc = jax.lax.dot_general(
        bitmat_ref[...].astype(jnp.int8), bits.astype(jnp.int8),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [8R, T]

    obits = (acc & 1).reshape(r, 8, tile.shape[-1])
    weights = (jnp.int32(1) << jax.lax.broadcasted_iota(
        jnp.int32, (1, 8, 1), dimension=1
    ))
    packed = jnp.sum(obits * weights, axis=1)  # [R, T] int32
    out_ref[0] = packed.astype(jnp.uint8)


@functools.cache
def _plane_major_cols(k8: int) -> tuple[int, ...]:
    """Column permutation taking an interleaved bit-matrix (col j*8 + b)
    to the kernel's plane-major bit order (col b*K + j)."""
    k = k8 // 8
    return tuple(j * 8 + b for b in range(8) for j in range(k))


@functools.partial(
    jax.jit, static_argnames=("tile", "interpret")
)
def _apply_bits_pallas(bitmat: jax.Array, shards: jax.Array,
                       tile: int = DEFAULT_TILE,
                       interpret: bool = False) -> jax.Array:
    """bitmat int8 [8R, 8K], shards uint8 [B, K, S] -> uint8 [B, R, S]."""
    b, k, s = shards.shape
    r8, k8 = bitmat.shape
    assert k8 == 8 * k, (bitmat.shape, shards.shape)
    r = r8 // 8
    t = min(tile, s)

    grid = (b, pl.cdiv(s, t))
    return pl.pallas_call(
        _gf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((r8, k8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k, t), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r, t), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, s), jnp.uint8),
        interpret=interpret,
    )(bitmat, shards)


def apply_gf_matrix_pallas(bitmat, shards, tile: int = DEFAULT_TILE,
                           interpret: bool = False) -> jax.Array:
    """Fused-kernel variant of ops.rs.apply_gf_matrix.

    Accepts shards uint8 [..., K, S] with any leading batch shape (the
    kernel itself runs on [B, K, S]).
    """
    bitmat = jnp.asarray(bitmat, dtype=jnp.int8)
    bitmat = bitmat[:, list(_plane_major_cols(bitmat.shape[1]))]
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    lead = shards.shape[:-2]
    k, s = shards.shape[-2:]
    flat = shards.reshape((-1, k, s))
    out = _apply_bits_pallas(bitmat, flat, tile=tile, interpret=interpret)
    return out.reshape(*lead, bitmat.shape[0] // 8, s)


class HostFeed:
    """Pipelined host→device staging stage for the device encode engine.

    An encode loop that does H2D, dispatch and D2H from ONE host
    thread leaves the link idle while the host packs or flushes. Run
    as a stage of pipeline/executor.Pipeline, this callable moves the
    H2D copy onto its own worker: the transfer of batch N+1 overlaps
    the MXU compute of batch N and the host write fan-out of batch
    N-1 — double buffering falls out of the executor's bounded queues
    (queue_depth=1 keeps exactly one staged batch ahead).

    The transfer is COMPLETED inside the stage (block_until_ready):
    returning a lazy handle would make the dispatch stage pay the wait
    and re-serialize the feed. Per-stage items/bytes/timing telemetry
    comes from the executor's StageStats, not from this class.

    `sharding` stages onto a sharded layout (the mesh engine's
    dp-groups) instead of the default device; `accept` gates which
    batches stage at all — a declined batch passes through on the host
    and the downstream codec stages it itself (the mesh engine declines
    ragged batches whose row count doesn't divide dp, since those need
    padding the feed must not own).
    """

    def __init__(self, name: str = "h2d", sharding=None, accept=None):
        self.name = name
        self._sharding = sharding
        self._accept = accept

    def __call__(self, batch):
        import jax

        if self._accept is not None and not self._accept(batch):
            return batch
        with _spans.span("device-h2d",
                         "mesh" if self._sharding is not None else "device"):
            if self._sharding is not None:
                dev = jax.device_put(batch, self._sharding)
            else:
                dev = jax.device_put(batch)
            dev.block_until_ready()
        return dev


def pallas_supported() -> bool:
    """True on the one backend this kernel lowers for (Mosaic: TPU).
    A platform answer only — a kernel that fails to compile there
    raises at its call site, it is never mistaken for "unsupported"."""
    return jax.default_backend() == "tpu"

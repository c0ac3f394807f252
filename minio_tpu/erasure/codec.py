"""Erasure codec: the TPU-backed equivalent of the reference's `Erasure`
value type (/root/reference/cmd/erasure-coding.go:34-149).

Shard geometry (ShardSize/ShardFileSize/ShardFileOffset), split semantics,
and the empty/all-zero early-outs reproduce the reference exactly; output
bytes are bit-identical to klauspost/reedsolomon (validated against the
golden xxhash64 vectors of erasureSelfTest, cmd/erasure-coding.go:157-215).

The compute itself is redesigned for TPU: parity generation and
reconstruction are GF(2) bit-matrix matmuls (ops/gf.py, ops/rs.py) that
run on the MXU, batched over many 1 MiB blocks per dispatch instead of the
reference's one-block-at-a-time goroutine fan-out.
"""

from __future__ import annotations

import functools

import numpy as np

from ..observability import spans as _spans
from ..ops import gf, rs
from ..utils import ceil_frac
from ..utils.errors import (
    ErrInvShardNum,
    ErrMaxShardNum,
    ErrReconstructRequired,
    ErrShardSize,
    ErrShortData,
    ErrTooFewShards,
)
from . import registry

@functools.lru_cache(maxsize=64)
def cached_erasure(data_blocks: int, parity_blocks: int, block_size: int,
                   codec: str = registry.DEFAULT_CODEC) -> "Erasure":
    """Geometry-keyed Erasure cache: an erasure set re-derives the same
    coding/bit matrices on every PUT when it constructs a fresh Erasure
    per object (the c5 pool-batched-PUT setup cost). Erasure instances
    are stateless after __init__ apart from the lazily device-put parity
    bit-matrix (a benign idempotent race), so sharing one per
    (geometry, codec) across PUT/GET/heal is safe."""
    return Erasure(data_blocks, parity_blocks, block_size, codec)


class Erasure:
    """Erasure coding engine for one (data, parity, block_size, codec)
    geometry. The codec id names a registry entry (erasure/registry.py)
    whose matrix constructors supply the coding algebra; every engine
    substrate applies those byte matrices through its existing
    any-matrix kernel, so all substrates stay byte-identical per codec.
    """

    def __init__(self, data_blocks: int, parity_blocks: int,
                 block_size: int, codec: str = registry.DEFAULT_CODEC):
        # Parameter checks mirror NewErasure (cmd/erasure-coding.go:41-49).
        if data_blocks <= 0 or parity_blocks <= 0:
            raise ErrInvShardNum(
                f"data={data_blocks} parity={parity_blocks} must be > 0"
            )
        if data_blocks + parity_blocks > gf.MAX_SHARDS:
            raise ErrMaxShardNum(
                f"data+parity={data_blocks + parity_blocks} exceeds 256"
            )
        self.data_blocks = data_blocks
        self.parity_blocks = parity_blocks
        self.block_size = block_size
        self.total_shards = data_blocks + parity_blocks
        self.codec_id = codec
        self._entry = registry.get(codec)  # loud on unknown codec ids
        if not self._entry.geometry_ok(data_blocks, parity_blocks):
            raise ErrInvShardNum(
                f"codec {codec!r} does not support geometry "
                f"{data_blocks}+{parity_blocks}"
            )
        # Sub-packetization: shard lengths are rounded up to multiples
        # of α and every matrix application reshapes [.., K, S] to
        # [.., K·α, S/α] — byte-identical views, so expanded matrices
        # ride the same any-matrix kernels (ops/regen.py layout note).
        self.subshards = self._entry.alpha(data_blocks, parity_blocks)
        # Host-side byte matrices (lru-cached per codec module).
        self.matrix = self._entry.coding_matrix(data_blocks, parity_blocks)
        self._parity_mat = self._entry.parity_matrix(
            data_blocks, parity_blocks
        )
        self._parity_bits_np = gf.bit_matrix_for(self._parity_mat)
        self._parity_bits_dev = None  # lazily device_put on first large encode

    # --- geometry (cmd/erasure-coding.go:120-149) ---

    def _round_shard(self, size: int) -> int:
        """Round a shard byte-length up to the codec's sub-packetization.
        Zero-pad-and-truncate would NOT be safe instead: sub-packetized
        parity bytes in a truncated tail depend on real data columns, so
        the pad must exist on disk, exactly like split()'s block pad."""
        a = self.subshards
        return ceil_frac(size, a) * a if a > 1 else size

    def shard_size(self) -> int:
        """Actual shard size from the erasure blockSize."""
        return self._round_shard(
            ceil_frac(self.block_size, self.data_blocks)
        )

    def shard_file_size(self, total_length: int) -> int:
        """Final erasure size on each disk from the original object size."""
        if total_length == 0:
            return 0
        if total_length == -1:
            return -1
        num_shards = total_length // self.block_size
        last_block_size = total_length % self.block_size
        last_shard_size = self._round_shard(
            ceil_frac(last_block_size, self.data_blocks)
        )
        return num_shards * self.shard_size() + last_shard_size

    def shard_file_offset(self, start_offset: int, length: int, total_length: int) -> int:
        """Effective per-shard offset where erasure reading ends."""
        shard_size = self.shard_size()
        shard_file_size = self.shard_file_size(total_length)
        end_shard = (start_offset + length) // self.block_size
        till_offset = end_shard * shard_size + shard_size
        if till_offset > shard_file_size:
            till_offset = shard_file_size
        return till_offset

    # --- device matrix helpers ---

    def _parity_bitmat(self, on_device: bool):
        if not on_device:
            return self._parity_bits_np
        if self._parity_bits_dev is None:
            import jax

            self._parity_bits_dev = jax.device_put(self._parity_bits_np)
        return self._parity_bits_dev

    def _subshard_view(self, shards: np.ndarray) -> np.ndarray:
        """[.., K, S] -> [.., K·α, S/α] — a byte-identical reshape (the
        α sub-shards of one shard are its contiguous S/α-byte slices),
        matching the sub-shard indexing of the expanded matrices."""
        a = self.subshards
        s = shards.shape[-1]
        if s % a:
            raise ErrShardSize(
                f"shard length {s} not a multiple of sub-packetization "
                f"{a} for codec {self.codec_id!r}"
            )
        return shards.reshape(*shards.shape[:-2],
                              shards.shape[-2] * a, s // a)

    def _apply(self, mat_gf: np.ndarray, shards: np.ndarray,
               bits_np: np.ndarray | None = None,
               dev_bitmat=None) -> np.ndarray:
        """Apply a GF(2^8) matrix (byte form `mat_gf` [R, K]) to [.., K, S]
        shards via the selected engine. `bits_np`/`dev_bitmat` supply
        precomputed GF(2) expansions for the numpy/device paths. For
        sub-packetized codecs the matrix addresses sub-shards: inputs
        and outputs are reshaped around the kernel, whole-shard shapes
        at the boundary either way."""
        from ..ops import gf_native

        out_s = shards.shape[-1]
        if self.subshards > 1:
            shards = self._subshard_view(shards)
        engine = registry.select_engine(shards.shape[-1],
                                        codec_id=self.codec_id)
        registry.note_dispatch(self.codec_id, engine, "apply")
        if engine == "native":
            if shards.ndim == 3:
                out = gf_native.apply_matrix_batch(mat_gf, shards)
            else:
                out = gf_native.apply_matrix(mat_gf, shards)
        elif engine == "device":
            bits = dev_bitmat
            if bits is None:
                bits = bits_np if bits_np is not None else gf.bit_matrix_for(mat_gf)
            from . import device_engine

            # The unfused device path (tail blocks, degraded GETs): one
            # dispatch, so one device-call on the request's span tree.
            with _spans.span("device-call", "apply"):
                dev_out = rs.apply_gf_matrix(bits, shards)
            out = device_engine.to_host(dev_out)
        else:
            # Host fallback: the codec's own numpy realization (dense
            # GF(2) bit-matmul, or the Cauchy XOR schedule).
            out = self._entry.host_apply(mat_gf, shards)
        if self.subshards > 1:
            out = out.reshape(*out.shape[:-2],
                              out.shape[-2] // self.subshards, out_s)
        return out

    def parity_apply_batch_native(self, blocks: np.ndarray,
                                  out: np.ndarray | None = None
                                  ) -> np.ndarray:
        """gf_native parity application for [B, K, S] blocks with the
        codec's sub-shard reshape applied around the kernel — the one
        entry point the streaming encode drivers use, so no native call
        site can forget the α view."""
        from ..ops import gf_native

        a = self.subshards
        if a == 1:
            return gf_native.apply_matrix_batch(self._parity_mat, blocks,
                                                out=out)
        nb, _, s = blocks.shape
        res = gf_native.apply_matrix_batch(
            self._parity_mat,
            self._subshard_view(blocks),
            out=None if out is None else out.reshape(
                nb, self.parity_blocks * a, s // a
            ),
        )
        return res.reshape(nb, self.parity_blocks, s)

    def _apply_parity(self, shards: np.ndarray) -> np.ndarray:
        on_device = (
            registry.select_engine(shards.shape[-1],
                                   codec_id=self.codec_id)
            == "device"
        )
        return self._apply(
            self._parity_mat,
            shards,
            bits_np=self._parity_bits_np,
            dev_bitmat=self._parity_bitmat(True) if on_device else None,
        )

    # --- split / encode (cmd/erasure-coding.go:76-90 + klauspost Split) ---

    def split(self, data) -> list[np.ndarray]:
        """Split data into k zero-padded data shards plus m empty parity
        shard buffers, matching reedsolomon.Encoder.Split."""
        data = np.frombuffer(memoryview(data), dtype=np.uint8)
        if data.size == 0:
            raise ErrShortData("cannot split empty data")
        per_shard = self._round_shard(
            ceil_frac(data.size, self.data_blocks)
        )
        padded = np.zeros(self.total_shards * per_shard, dtype=np.uint8)
        padded[: data.size] = data
        return list(padded.reshape(self.total_shards, per_shard))

    def encode_data(self, data) -> list[np.ndarray]:
        """Split + encode one block of bytes into k+m shards.

        Empty input returns k+m empty shards (cmd/erasure-coding.go:77-79).
        """
        data = np.frombuffer(memoryview(data), dtype=np.uint8)
        if data.size == 0:
            return [np.zeros(0, dtype=np.uint8) for _ in range(self.total_shards)]
        shards = self.split(data)
        data_mat = np.stack(shards[: self.data_blocks])
        parity = self._apply_parity(data_mat)
        for i in range(self.parity_blocks):
            shards[self.data_blocks + i] = parity[i]
        return shards

    def encode_batch(self, blocks: np.ndarray) -> np.ndarray:
        """Batched encode: blocks [B, K, S] data shards -> [B, M, S] parity.

        This is the TPU throughput path: many 1 MiB blocks per dispatch so
        the MXU matmul amortizes transfers (unlike the reference's
        block-at-a-time Encode loop, cmd/erasure-encode.go:80-108).
        """
        blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
        return self._apply_parity(blocks)

    def encode_batch_async(self, blocks: np.ndarray, with_hashes: bool):
        """Dispatch a batched encode (and optionally the per-shard bitrot
        hashes) WITHOUT materializing results on the host.

        Returns (parity, hashes) where parity is a device array [B, M, S]
        (or host ndarray on the small-shard path) and hashes is a device
        array [B, K+M, 32] or None. The caller overlaps the device compute
        with host IO and materializes via np.asarray when needed — the
        double-buffered pipeline of SURVEY §7.2(4).

        Fusing the HighwayHash-256 of every output shard into the same
        dispatch replaces the reference's per-shard host hashing inside
        parallelWriter (cmd/erasure-encode.go:93 + bitrot-streaming.go:48).

        `blocks` may already be a DEVICE array — the pipelined host-feed
        stage (device_engine.HostFeed) stages the H2D transfer of batch
        N+1 while batch N computes; coercing it through numpy here would
        silently pull it back to the host and undo the overlap. The
        device path runs on the fused single-dispatch engine
        (erasure/device_engine.DeviceCodec): one jitted call per batch
        covering parity AND digests, the staged input buffer donated to
        XLA, and the D2H of both outputs started asynchronously at
        dispatch — np.asarray on the returned handles finds the bytes
        already in flight.
        """
        staged_on_device = not isinstance(blocks, np.ndarray) and hasattr(
            blocks, "block_until_ready"
        )
        if not staged_on_device:
            blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
        engine = registry.select_engine(blocks.shape[-1],
                                        self.total_shards, self.codec_id)
        registry.note_dispatch(self.codec_id, engine, "encode")
        if staged_on_device and engine not in ("device", "mesh"):
            blocks = np.asarray(blocks)  # tiny-shard fallback: host engines
        if engine == "native":
            # Synchronous but fast (GFNI/SSSE3); the writers hash each
            # shard with the native AVX2 HighwayHash, so no fused-digest
            # dispatch is needed.
            return self.parity_apply_batch_native(blocks), None
        if engine == "numpy":
            if self.subshards > 1:
                s = blocks.shape[-1]
                parity = self._entry.host_apply(
                    self._parity_mat, self._subshard_view(blocks)
                )
                parity = parity.reshape(*parity.shape[:-2],
                                        self.parity_blocks, s)
            else:
                parity = self._entry.host_apply(self._parity_mat, blocks)
            return parity, None
        if engine == "mesh":
            # Lane-sharded mesh dispatch: same fused parity+digest
            # contract as the device engine, partitioned over the
            # ('dp', 'lane') mesh instead of one chip.
            from ..parallel.mesh_engine import for_geometry as mesh_geometry

            codec = mesh_geometry(self.data_blocks, self.parity_blocks,
                                  self.codec_id)
            return codec.encode_async(blocks, with_hashes)
        from .device_engine import for_geometry

        codec = for_geometry(self.data_blocks, self.parity_blocks,
                             self.codec_id)
        return codec.encode_async(blocks, with_hashes)

    # --- reconstruct / decode (cmd/erasure-coding.go:95-118) ---

    def decode_data_blocks(self, shards: list) -> list:
        """Reconstruct ONLY missing data shards in-place; parity entries may
        remain missing. Mirrors Erasure.DecodeDataBlocks semantics: if no
        shard is missing — or every shard is missing (0-byte payload) — it
        is a no-op."""
        # Reference counts with an early break, so the all-missing early-out
        # only triggers for a single-shard list; with >=1 missing shard in a
        # normal k+m list, reconstruction runs (and raises ErrTooFewShards
        # when everything is gone), cmd/erasure-coding.go:96-106.
        is_zero = 0
        for b in shards:
            if b is None or len(b) == 0:
                is_zero += 1
                break
        if is_zero == 0 or is_zero == len(shards):
            return shards
        return self._reconstruct(shards, data_only=True)

    def decode_data_and_parity_blocks(self, shards: list) -> list:
        """Reconstruct all missing shards (data and parity)."""
        if len(shards) != self.total_shards:
            raise ErrTooFewShards(
                f"got {len(shards)} shards, want {self.total_shards}"
            )
        missing = [i for i, b in enumerate(shards) if b is None or len(b) == 0]
        if not missing:
            return shards
        return self._reconstruct(shards, data_only=False)

    def _reconstruct(self, shards: list, data_only: bool) -> list:
        if len(shards) != self.total_shards:
            raise ErrTooFewShards(
                f"got {len(shards)} shards, want {self.total_shards}"
            )
        present = [i for i, b in enumerate(shards) if b is not None and len(b) > 0]
        if len(present) < self.data_blocks:
            raise ErrTooFewShards(
                f"{len(present)} shards present, need {self.data_blocks}"
            )
        shard_len = len(shards[present[0]])
        for i in present:
            if len(shards[i]) != shard_len:
                raise ErrShardSize("present shards differ in size")

        present_set = set(present)
        missing = [i for i in range(self.total_shards) if i not in present_set]
        if data_only:
            missing = [i for i in missing if i < self.data_blocks]
        if not missing:
            return shards

        try:
            mat = self._entry.reconstruct_matrix(
                self.data_blocks, self.parity_blocks, present, missing
            )
        except ValueError as exc:
            # Singular present-subset submatrix == not enough independent
            # shards to reconstruct.
            raise ErrTooFewShards(str(exc)) from exc
        src = np.stack(
            [np.frombuffer(memoryview(shards[i]), dtype=np.uint8)
             for i in present[: self.data_blocks]]
        )
        out = self._apply(mat, src)
        for t_i, t in enumerate(missing):
            shards[t] = out[t_i]
        return shards

    def reconstruct_targets(self, shards: list, targets: list[int]) -> list[np.ndarray]:
        """Regenerate exactly `targets` shard indices from >=k present
        shards without mutating the input list. Used by the heal engine
        (equivalent of cmd/erasure-lowlevel-heal.go:28-48, where only the
        stale disks receive writes)."""
        if len(shards) != self.total_shards:
            raise ErrTooFewShards(
                f"got {len(shards)} shards, want {self.total_shards}"
            )
        present = [i for i, b in enumerate(shards) if b is not None and len(b) > 0]
        if len(present) < self.data_blocks:
            raise ErrTooFewShards(
                f"{len(present)} shards present, need {self.data_blocks}"
            )
        shard_len = len(shards[present[0]])
        for i in present:
            if len(shards[i]) != shard_len:
                raise ErrShardSize("present shards differ in size")
        try:
            mat = self._entry.reconstruct_matrix(
                self.data_blocks, self.parity_blocks, present, targets
            )
        except ValueError as exc:
            raise ErrTooFewShards(str(exc)) from exc
        src = np.stack(
            [np.frombuffer(memoryview(shards[i]), dtype=np.uint8)
             for i in present[: self.data_blocks]]
        )
        out = self._apply(mat, src)
        return [out[i] for i in range(len(targets))]

    def join(self, shards: list, out_size: int) -> bytes:
        """Concatenate data shards and trim padding (reedsolomon.Join)."""
        if len(shards) < self.data_blocks:
            raise ErrTooFewShards("not enough shards to join")
        for i in range(self.data_blocks):
            if shards[i] is None or len(shards[i]) == 0:
                raise ErrReconstructRequired(f"data shard {i} missing")
        data = np.concatenate(
            [np.frombuffer(memoryview(shards[i]), dtype=np.uint8)
             for i in range(self.data_blocks)]
        )
        if data.size < out_size:
            raise ErrShortData("shards hold less data than requested")
        return data[:out_size].tobytes()
